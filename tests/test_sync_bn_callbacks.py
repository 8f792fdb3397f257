"""SyncBatchNorm (torch + in-jit) and training callbacks."""

import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
import horovod_tpu.jax as hvd_jax
from horovod_tpu import callbacks as cb

from test_eager_multiprocess import run_job


@pytest.fixture(autouse=True, scope="module")
def _hvd_init():
    hvd.init()
    yield


# np=4 re-proves the same cross-rank-stats math the np=2 run pins, at
# ~41s vs ~19s on the current box — slow tier keeps the redundant
# width, tier-1 keeps the gate.
@pytest.mark.parametrize(
    "np_", [2, pytest.param(4, marks=pytest.mark.slow)])
def test_torch_sync_bn_matches_full_batch(np_):
    run_job("sync_bn", np_)


def test_callbacks_multiprocess():
    run_job("callbacks", 2)


def test_jax_sync_batch_norm_vs_numpy(mesh8):
    rng = np.random.RandomState(0)
    x = rng.randn(16, 5, 3).astype(np.float32)  # [B, W, C], B over dp

    def f(xs, scale, bias):
        y, mean, var = hvd_jax.sync_batch_norm(
            xs, axis_name="dp", scale=scale, bias=bias)
        return y, mean, var

    g = shard_map(f, mesh=mesh8, in_specs=(P("dp"), P(), P()),
                  out_specs=(P("dp"), P(), P()))
    scale = jnp.asarray([1.5, 2.0, 0.5])
    bias = jnp.asarray([0.1, -0.2, 0.0])
    y, mean, var = jax.jit(g)(jnp.asarray(x), scale, bias)

    want_mean = x.reshape(-1, 3).mean(0)
    want_var = x.reshape(-1, 3).var(0)
    np.testing.assert_allclose(np.asarray(mean), want_mean, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(var), want_var, rtol=1e-4,
                               atol=1e-6)
    want = (x - want_mean) / np.sqrt(want_var + 1e-5)
    want = want * np.asarray(scale) + np.asarray(bias)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)


def test_warmup_callback_multiplier():
    c = cb.LearningRateWarmupCallback(0.1, warmup_epochs=4, size=8)
    metrics = {}
    c.on_epoch_end(0, metrics)           # after epoch 1
    np.testing.assert_allclose(metrics["lr"], 0.1 * (1 + 7 / 4))
    c.on_epoch_end(9, metrics)           # past warmup: lr = base * size
    np.testing.assert_allclose(metrics["lr"], 0.8)


def test_warmup_optax_schedule():
    sched = cb.warmup_schedule(0.1, warmup_steps=10, size=4)
    np.testing.assert_allclose(float(sched(0)), 0.1)
    np.testing.assert_allclose(float(sched(5)), 0.1 * (1 + 3 * 0.5))
    np.testing.assert_allclose(float(sched(10)), 0.4)
    np.testing.assert_allclose(float(sched(100)), 0.4)
    after = cb.warmup_schedule(0.1, warmup_steps=4, size=2,
                               after=lambda s: 0.2 * 0.5 ** (s // 4))
    np.testing.assert_allclose(float(after(8)), 0.1)


def test_torch_lr_schedule_callback():
    import torch
    m = torch.nn.Linear(2, 2)
    opt = torch.optim.SGD(m.parameters(), lr=0.5)
    c = cb.LearningRateScheduleCallback(0.5, lambda e: 0.1 ** e, set_lr=opt)
    c.on_epoch_end(0)
    np.testing.assert_allclose(opt.param_groups[0]["lr"], 0.05)


def test_best_model_checkpoint(tmp_path):
    path = str(tmp_path / "best.pkl")
    c = cb.BestModelCheckpoint(path, monitor="loss")
    c.on_epoch_end(0, {"loss": 2.0}, state={"w": 1})
    c.on_epoch_end(1, {"loss": 3.0}, state={"w": 2})   # worse: no save
    with open(path, "rb") as f:
        assert pickle.load(f) == {"w": 1}
    c.on_epoch_end(2, {"loss": 1.0}, state={"w": 3})   # better: saved
    with open(path, "rb") as f:
        assert pickle.load(f) == {"w": 3}


def test_broadcast_parameters_callback_jax():
    r = hvd.rank()
    params = {"w": jnp.full((3,), 7.0 if r == 0 else 0.0)}
    c = cb.BroadcastParametersCallback(params)
    out = c.on_train_begin()
    np.testing.assert_allclose(np.asarray(out["w"]), 7.0)
