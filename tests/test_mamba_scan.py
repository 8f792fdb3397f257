"""A chunk's selective scan through ``hvd_mamba_scan``
(``ops/mamba_scan.py``, interpret mode here) against the XLA form it
replaced in ``mamba_chunk``: ``decode.mamba_scan`` with the step of the
positions past ``length`` zeroed, the same inputs and the same state
(ISSUE 49). Tiny shapes: the interpreter is slow."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.ops import mamba_scan as scan_lib
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

N, DI = 8, 128


def inputs(B, T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        u=jax.random.normal(ks[0], (B, T, DI)),
        step=jax.random.uniform(ks[1], (B, T, DI), minval=1e-3, maxval=0.1),
        a=-jnp.exp(jax.random.normal(ks[2], (N, DI))),
        b=jax.random.normal(ks[3], (B, T, N)),
        c=jax.random.normal(ks[4], (B, T, N)),
        state=jax.random.normal(ks[5], (B, N, DI)))


def xla(x, length):
    """``mamba_chunk``'s form before the kernel: the padding's step 0."""
    real = jnp.arange(x["u"].shape[1])[None, :, None] < length
    return decode_lib.mamba_scan(x["u"], jnp.where(real, x["step"], 0.0),
                                 x["a"], x["b"], x["c"], x["state"])


@functools.lru_cache(maxsize=None)
def _jitted(sizes):
    return jax.jit(lambda x, length: scan_lib.mamba_scan(
        x["u"], x["step"], x["a"], x["b"], x["c"], x["state"], length,
        **dict(sizes)))


def kernel(x, length, **sizes):
    """One compilation a set of sizes and shapes: ``length`` is
    traced."""
    return _jitted(tuple(sorted(sizes.items())))(x, jnp.int32(length))


# T, length, B, the kernel's sizes
CASES = {
    "the_whole_chunk": (32, 32, 1, dict(block=16)),
    "inside_a_time_block": (32, 21, 1, dict(block=16)),
    "at_a_blocks_edge": (32, 16, 1, dict(block=16)),
    "shorter_than_one_block": (32, 5, 1, dict(block=16)),
    "all_padding": (32, 0, 1, dict(block=16)),
    "two_rows": (32, 21, 2, dict(block=16)),
    "the_programs_own_sizes": (24, 17, 1, {}),
    "channels_in_two_grid_steps_and_two_loops": (
        16, 11, 2, dict(block=8, channels=64, width=32, unroll=2)),
    "one_position_a_loop": (16, 11, 1, dict(block=8, unroll=1)),
    "a_chunk_that_is_no_whole_tile": (13, 9, 1, {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_scan_up_to_length(case):
    """The state after ``length`` and the real positions' ``y`` to
    float32 round-off (the sum over the state rows is taken in another
    order); the padded positions' ``y`` zeros, where the XLA form left
    the last real state's ``sum s c``; a chunk that is all padding hands
    back the state it was given, bit for bit."""
    T, length, B, sizes = CASES[case]
    x = inputs(B, T)
    y, state = kernel(x, length, **sizes)
    y_want, s_want = xla(x, length)
    np.testing.assert_allclose(state, s_want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[:, :length], y_want[:, :length], rtol=1e-5,
                               atol=1e-5)
    assert (np.asarray(y[:, length:]) == 0).all()
    if length == 0:
        assert (np.asarray(state) == np.asarray(x["state"])).all()


def test_two_chunks_from_the_carried_state_are_one_pass_over_both():
    """A sequence's second chunk goes on from the state its first left,
    the first one padded: what ``mamba_chunk`` does call after call."""
    x = inputs(1, 32)
    first, second = 11, 16

    def part(lo, hi, T, state):
        cut = {k: jnp.pad(v[:, lo:hi], ((0, 0), (0, T - (hi - lo)), (0, 0)))
               for k, v in x.items() if k in ("u", "step", "b", "c")}
        return kernel(dict(cut, a=x["a"], state=state), hi - lo, block=8)

    y1, state = part(0, first, 16, x["state"])
    y2, state = part(first, first + second, 16, state)
    y, s_want = kernel(x, first + second, block=8)
    np.testing.assert_allclose(state, s_want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        jnp.concatenate([y1[:, :first], y2], 1), y[:, :first + second],
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("length", [0, 5, 16, 21])
def test_what_the_padding_holds_moves_nothing(length):
    """Positions from ``length`` on are not read: NaN in their ``u``,
    ``step``, ``b`` and ``c`` leaves the state and the real ``y`` what
    they are with clean padding, bit for bit, and the padded ``y`` 0."""
    x = inputs(2, 32)
    real = jnp.arange(32)[None, :, None] < length
    dirty = dict(x, **{k: jnp.where(real, x[k], jnp.nan)
                       for k in ("u", "step", "b", "c")})
    y, state = kernel(dirty, length, block=16)
    y_want, s_want = kernel(x, length, block=16)
    assert (np.asarray(state) == np.asarray(s_want)).all()
    assert (np.asarray(y) == np.asarray(y_want)).all()
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("wrong", ["state_dtype", "a", "b", "channels",
                                   "block"])
def test_the_kernel_refuses_shapes_that_do_not_belong(wrong):
    x = inputs(1, 16)
    kw = {}
    if wrong == "state_dtype":
        x["state"] = x["state"].astype(jnp.bfloat16)
    elif wrong == "a":
        x["a"] = x["a"][:, :DI // 2]
    elif wrong == "b":
        x["b"] = x["b"][:, :-1]
    elif wrong == "channels":
        kw["channels"] = 48
    else:
        kw["block"] = 12
    with pytest.raises(ValueError, match="mamba_scan"):
        scan_lib.mamba_scan(x["u"], x["step"], x["a"], x["b"], x["c"],
                            x["state"], 16, **kw)


@pytest.mark.parametrize("backend,n_state,d_inner,positions,kernel", [
    ("cpu", 4, 64, 13, True), ("tpu", 16, 5120, 512, True),
    ("tpu", 16, 5120, 128, True), ("tpu", 8, 128, 8, True),
    ("tpu", 4, 64, 16, False), ("tpu", 16, 5120 + 64, 512, False),
    ("tpu", 12, 256, 64, False), ("tpu", 16, 5120, 12, False)])
def test_whole_tiles_take_the_kernel(monkeypatch, backend, n_state, d_inner,
                                     positions, kernel):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert scan_lib.taken(n_state, d_inner, positions) is kernel


@pytest.mark.parametrize("length", [16, 11])
def test_a_shape_that_is_not_whole_tiles_keeps_the_xla_form(monkeypatch,
                                                            length):
    """A chunk of a tiny stack (64 channels, 4 state rows: the fall-back
    on a TPU) through ``decode.mamba_scan``, as a TPU would trace it
    (no Pallas call in the program), against the same program through
    the kernel: the logits at ``length - 1``, the slot's state and its
    convolution rows. The padded positions' ``y`` differ between the
    two (zeros against the last real state's) and nothing that is kept
    reads them."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=1,
        d_ff=64, max_seq=64, norm_eps=1e-6,
        layer_types=("mamba", "full", "mamba"), mamba_d_state=4,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
        tie_embeddings=True, dtype=jnp.float32, remat=False)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    cache = init_kv_cache(cfg, 9, 8, n_slots=4)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    kc = tuple(jax.random.normal(ks[0], a.shape, a.dtype) for a in cache.k)
    vc = tuple(jax.random.normal(ks[1], a.shape, a.dtype) for a in cache.v)
    tokens = jax.random.randint(ks[2], (16,), 0, 128)
    table = jnp.array([1, 2, 3, 4], jnp.int32)

    def run():
        resume = decode_lib.mixed_programs(cfg, 8, 4, 0,
                                           head=lambda lg: lg)[1]
        args = (params, kc, vc, tokens, jnp.int32(8), jnp.int32(length),
                (table, jnp.int32(2)))
        return (jax.jit(resume)(*args),
                str(jax.make_jaxpr(resume)(*args)).count("hvd_mamba_scan"))

    monkeypatch.setattr(scan_lib, "taken", lambda n, d, t: False)
    xla_form, calls = run()
    assert calls == 0
    monkeypatch.undo()
    through_kernel, calls = run()
    assert calls == 2
    at = cache.kinds.index("mamba")
    np.testing.assert_allclose(through_kernel[2], xla_form[2], rtol=2e-5,
                               atol=2e-5)
    for got, want in ((through_kernel[0][at], xla_form[0][at]),
                      (through_kernel[1][at], xla_form[1][at])):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
