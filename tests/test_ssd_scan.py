"""A chunk's SSD through ``hvd_ssd_scan`` (``ops/ssd_scan.py``,
interpret mode here) against the XLA form it replaced in
``mamba2_chunk``, ``decode.ssd_scan`` over the same blocks, and against
the recurrence a position at a time, ``decode.ssd_step`` (ISSUE 64)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.ops import ssd_scan as scan_lib
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

#: heads, a head's channels, groups, a group's state columns, a block
HM, P, G, N, BLOCK = 8, 8, 2, 16, 8


def inputs(T, seed=0, resumed=True, batch=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[5], (batch, HM, P, N))
    return (jax.random.normal(ks[0], (batch, T, HM, P)),
            jax.random.uniform(ks[1], (batch, T, HM), minval=1e-3,
                               maxval=0.5),
            -jnp.exp(jax.random.normal(ks[2], (HM,))),
            jax.random.normal(ks[3], (batch, T, G, N)),
            jax.random.normal(ks[4], (batch, T, G, N)),
            state if resumed else jnp.zeros_like(state))


def zeroed(dt, length):
    """``dt`` as ``mamba2_chunk`` hands it on: 0 from ``length`` on."""
    return jnp.where(jnp.arange(dt.shape[1])[None, :, None] < length, dt, 0.0)


def close(got, want):
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


KERNEL = jax.jit(scan_lib.ssd_scan, static_argnames=("block", "heads"))


# heads a grid step: inside a group of four, a whole group, both groups
@pytest.mark.parametrize("heads", [2, 4, 8])
# a bucket of one block and of several; a length at it, inside its last
# block, inside an earlier block and a whole block short of it
@pytest.mark.parametrize("T,length", [
    (8, 8), (8, 3), (32, 32), (32, 29), (32, 24), (32, 17), (32, 1)])
@pytest.mark.parametrize("resumed", [False, True])
def test_the_kernel_is_the_scan_over_the_same_blocks(resumed, T, length,
                                                     heads):
    """``y`` at the real positions (with the layer's ``D x`` where the
    call is given ``D``) and the state after them to float32 round-off;
    a block wholly past ``length`` is zeros."""
    x, dt, a, b, c, state = inputs(T, resumed=resumed, batch=2)
    dt = zeroed(dt, length)
    y_want, want = decode_lib.ssd_scan(x, dt, a, b, c, state, BLOCK)
    skip = jnp.linspace(-1.0, 2.0, HM) if resumed else None
    if resumed:
        y_want = y_want + skip[:, None] * x
    y, new = KERNEL(x, dt, a, b, c, state + 0.0, length, block=BLOCK,
                    skip=skip, heads=heads)
    close(y[:, :length], y_want[:, :length])
    close(new, want)
    skipped = -(-length // BLOCK) * BLOCK
    assert (y[:, skipped:] == 0).all()


@pytest.mark.parametrize("T,length", [(24, 24), (24, 11)])
@pytest.mark.parametrize("resumed", [False, True])
def test_the_kernel_is_the_recurrence_a_position_at_a_time(resumed, T,
                                                           length):
    """Against ``decode.ssd_step`` from the same state: each real
    position's ``y``, and the state that of the LAST REAL position."""
    x, dt, a, b, c, state = inputs(T, seed=1, resumed=resumed)
    y, new = KERNEL(x, zeroed(dt, length), a, b, c, state + 0.0, length,
                    block=BLOCK)
    for t in range(length):
        y_t, state = decode_lib.ssd_step(x[:, t], dt[:, t], a, b[:, t],
                                         c[:, t], state)
        close(y[:, t], y_t)
    close(new, state)


def test_what_lies_in_a_skipped_block_is_not_read():
    """NaN in every input of the blocks wholly past ``length``: the
    state and the real positions' ``y`` are what they were."""
    T, length = 32, 13
    x, dt, a, b, c, state = inputs(T, seed=2)
    dt = zeroed(dt, length)
    y_want, want = KERNEL(x, dt, a, b, c, state + 0.0, length, block=BLOCK)
    past = jnp.arange(T) >= 2 * BLOCK
    x, dt, b, c = (jnp.where(past.reshape((1, T) + (1,) * (v.ndim - 2)),
                             jnp.nan, v) for v in (x, dt, b, c))
    y, new = KERNEL(x, dt, a, b, c, state + 0.0, length, block=BLOCK)
    assert (y[:, :length] == y_want[:, :length]).all()
    assert (new == want).all() and (y[:, 2 * BLOCK:] == 0).all()


def test_a_length_of_nothing_returns_the_state_given():
    x, dt, a, b, c, state = inputs(16, seed=3)
    y, new = KERNEL(x, zeroed(dt, 0), a, b, c, state + 0.0, 0, block=BLOCK)
    assert (new == state).all() and (y == 0).all()


def test_a_chunk_that_is_no_whole_blocks_is_padded_as_the_xla_form_pads():
    """Only the interpreter is asked for one (``taken``)."""
    x, dt, a, b, c, state = inputs(20, seed=4)
    y_want, want = decode_lib.ssd_scan(x, dt, a, b, c, state, BLOCK)
    y, new = KERNEL(x, dt, a, b, c, state + 0.0, 20, block=BLOCK)
    assert y.shape == x.shape
    close(y, y_want)
    close(new, want)


def test_heads_that_straddle_a_group_are_refused():
    x, dt, a, b, c, state = inputs(8)
    with pytest.raises(ValueError, match="whole groups"):
        scan_lib.ssd_scan(x[:, :, :6], dt[:, :, :6], a[:6], b, c,
                          state[:, :6], 8, block=BLOCK, heads=2)
    with pytest.raises(ValueError, match="ssd_scan: x"):
        scan_lib.ssd_scan(x, dt[:, :, :6], a, b, c, state, 8, block=BLOCK)


# Nemotron's, then each refusal: a state that is not whole tiles (rows,
# lanes), heads whose channels are no whole lanes, a block that is no
# whole lanes, a chunk that is no whole blocks
@pytest.mark.parametrize("backend,shape,kernel", [
    ("tpu", (64, 128, 128, 8, 128, 1024), True),
    ("tpu", (64, 128, 128, 8, 128, 256), True),
    ("tpu", (128, 128, 16, 1, 128, 128), True),
    ("tpu", (64, 128, 128, 8, 128, 1000), False),
    ("tpu", (64, 128, 128, 8, 128, 64), False),
    ("tpu", (64, 128, 128, 8, 64, 1024), False),
    ("tpu", (64, 64, 128, 8, 128, 1024), False),
    ("tpu", (12, 128, 128, 8, 128, 1024), False),
    ("tpu", (48, 128, 128, 8, 128, 1024), False),
    ("tpu", (8, 16, 8, 2, 16, 32), False),
    ("cpu", (8, 16, 8, 2, 16, 24), True)])
def test_whole_tiles_and_whole_blocks_take_the_kernel(monkeypatch, backend,
                                                      shape, kernel):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert scan_lib.taken(*shape) is kernel


@pytest.mark.parametrize("bucket", [256, 512, 1024])
def test_every_bucket_of_the_nemotron_cell_takes_the_kernel(monkeypatch,
                                                            bucket):
    """The cell's configuration and traffic as the benchmark's files
    have them: ``snapshot()``'s ``ssd_scan_kernel_share`` is 1.0
    there."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config, traffic = (json.load(open(os.path.join(
        root, "benchmark", *path))) for path in (
        ("configs", "nemotron-3-super-120b-ep4-11l.json"),
        ("traffic", "agent-backlog.json")))
    fields = {**config["model"], **config.get("run", {})}
    fields["dtype"] = getattr(jnp, fields["dtype"])
    cfg = TransformerConfig(**fields)
    assert bucket in traffic["engine"]["prefill_buckets"]
    assert len(traffic["engine"]["prefill_buckets"]) == 3
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decode_lib.ssd_scan_taken(cfg, bucket)


def tiny(**kw):
    """Two mamba2 layers whose states are not whole tiles: the
    fall-back on a TPU."""
    base = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=4, d_ff=64, max_seq=64, norm_eps=1e-6,
                dtype=jnp.float32, remat=False,
                layer_types=("mamba2", "mamba2"), mamba_d_state=16,
                mamba_d_conv=4, mamba_expand=2, mamba2_head_dim=8,
                mamba2_groups=2, mamba2_chunk=8, tie_embeddings=True)
    base.update(kw)
    return TransformerConfig(**base)


def test_a_state_that_is_not_whole_tiles_keeps_the_xla_form(monkeypatch):
    """A resumed chunk of a tiny stack as a TPU would trace it (its
    states are not whole tiles: ``decode.ssd_scan``, and no Pallas call
    in the program) against the same chunk through the kernel: the
    logits, the states and the convolution's rows of the slot."""
    cfg = tiny()
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    cache = init_kv_cache(cfg, 9, 8, n_slots=3)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    kc = tuple(jax.random.normal(ks[0], a.shape, a.dtype) for a in cache.k)
    vc = tuple(jax.random.normal(ks[1], a.shape, a.dtype) for a in cache.v)
    toks = jnp.arange(1, 25, dtype=jnp.int32)
    args = (params, kc, vc, toks, jnp.int32(8), jnp.int32(19),
            (jnp.arange(1, 9, dtype=jnp.int32), jnp.int32(2)))

    def resume():
        return decode_lib.mixed_programs(cfg, 8, 8, 0,
                                         head=lambda lg: lg)[1]

    def no_call(*args, **kw):
        raise AssertionError("the fall-back called the kernel")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(scan_lib, "_call", no_call)
    assert not decode_lib.ssd_scan_taken(cfg, 24)
    traced = jax.make_jaxpr(resume())(*args)
    assert "pallas_call" not in str(traced)
    xla = jax.jit(resume())(*args)
    monkeypatch.undo()
    assert decode_lib.ssd_scan_taken(cfg, 24)
    assert str(jax.make_jaxpr(resume())(*args)).count("hvd_ssd_scan") == 2
    kernel = jax.jit(resume())(*args)
    close(kernel[2], xla[2])
    at = cache.kinds.index("mamba2")
    for got, want, before in ((kernel[0][at], xla[0][at], kc[at]),
                              (kernel[1][at], xla[1][at], vc[at])):
        close(got, want)
        assert (got[:, 1] == before[:, 1]).all()
        assert (got[:, 2] != before[:, 2]).any()


def test_the_layers_of_a_program_trace_the_kernel_once(monkeypatch):
    """The wrapper is jitted of itself: two layers and two programs of
    one bucket, one trace of the Pallas call."""
    cfg = tiny(mamba2_head_dim=16)             # a shape no other test has
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    cache = init_kv_cache(cfg, 9, 8, n_slots=2)
    traced, call = [], scan_lib._call
    monkeypatch.setattr(scan_lib, "_call", lambda *args, **kw: (
        traced.append(kw["heads"]), call(*args, **kw))[1])
    prefill, resume = decode_lib.mixed_programs(cfg, 8, 8, 0)[:2]
    toks = jnp.arange(16, dtype=jnp.int32)
    addr = (jnp.arange(1, 9, dtype=jnp.int32), jnp.int32(1))
    jax.jit(prefill).lower(params, cache.k, cache.v, toks, jnp.int32(13),
                           addr)
    jax.jit(resume).lower(params, cache.k, cache.v, toks, jnp.int32(8),
                          jnp.int32(13), addr)
    assert len(traced) == 1


@pytest.mark.parametrize("kernel", [True, False])
def test_the_spans_and_the_snapshot_say_whose_scan_the_kernel_ran(
        monkeypatch, tmp_path, kernel):
    """``scan_kernel`` beside ``scanned`` on every ``serve:prefill``
    span (all of the bucket or none), and their sums as a share."""
    if not kernel:
        monkeypatch.setattr(scan_lib, "taken", lambda *shape: False)
    cfg = tiny()
    eng = ServeEngine(cfg, init_transformer(cfg, jax.random.PRNGKey(0)),
                      ServeConfig(max_batch=2, max_prompt=48,
                                  max_new_tokens=2, block_size=8,
                                  prefill_chunk=16,
                                  prefill_buckets=(8, 16), batch_buckets=(2,),
                                  prefix_caching=False))
    rng = np.random.default_rng(0)
    for n in (37, 5):
        eng.submit(rng.integers(1, 128, n).tolist(), 2, trace_id=n)
    eng.run_until_idle()
    path = tmp_path / "spans.json"
    eng.metrics.export_chrome_trace(str(path))
    chunks = [e["args"] for e in json.load(open(path))["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "serve:prefill"]
    assert sorted(c["scanned"] for c in chunks) == [8, 8, 16, 16]
    assert all(c["scan_kernel"] == (c["scanned"] if kernel else 0)
               for c in chunks)
    snap = eng.metrics.snapshot()
    assert snap["ssd_scanned_positions_total"] == 48
    assert snap["ssd_scan_kernel_share"] == (1.0 if kernel else 0.0)
