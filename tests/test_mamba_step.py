"""A decode step's selective scan through ``hvd_mamba_step`` and its
convolution rows through ``hvd_mamba_rows`` (``ops/mamba_step.py``,
interpret mode here) against the XLA form they replaced in
``mamba_step_layer``: ``decode.mamba_step`` on the gathered states and
the rows' scatter by slot, the same pools and the same slots
(ISSUE 48)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.ops import mamba_step as step_lib
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import NULL_SLOT, init_kv_cache

LAYERS, SLOTS, N, DI, CONV = 3, 21, 4, 64, 4


def shuffled(rng):
    """Every slot in the batch, in no order."""
    return 1 + rng.permutation(SLOTS)


def a_part(rng):
    """A batch smaller than the slots (and no multiple of 8 rows)."""
    return shuffled(rng)[:5]


def blocks_of_rows(rng):
    """16 rows: two of the kernel's blocks of 8."""
    return shuffled(rng)[:16]


def padded(rng):
    """A bucket's padding: several rows at the null slot, among and
    after the real ones."""
    slots = shuffled(rng)[:8]
    slots[[2, 5, 6, 7]] = NULL_SLOT
    return slots


CASES = {"shuffled": shuffled, "a_part": a_part,
         "blocks_of_rows": blocks_of_rows, "padded": padded}


def inputs(slots, rows_dtype, seed=0):
    B = len(slots)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return dict(
        u=jax.random.normal(ks[0], (B, DI)),
        step=jax.random.uniform(ks[1], (B, DI), minval=1e-3, maxval=0.1),
        a=-jnp.exp(jax.random.normal(ks[2], (N, DI))),
        b=jax.random.normal(ks[3], (B, N)),
        c=jax.random.normal(ks[4], (B, N)),
        states=jax.random.normal(ks[5], (LAYERS, SLOTS + 1, N, DI)),
        rows=jax.random.normal(ks[6], (LAYERS, SLOTS + 1, (CONV - 1) * DI)
                               ).astype(rows_dtype),
        new=jax.random.normal(ks[7], (B, DI)).astype(rows_dtype),
        slots=jnp.asarray(slots, jnp.int32))


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_step_on_the_rows_own_states(case, layer):
    """``y`` and the stepped states to float32 round-off (the sum over
    the state rows is taken in another order), and every byte of the
    pool that is not a stepped slot of ``layer`` what it was: the other
    layers, and the slots that are not in the batch."""
    x = inputs(CASES[case](np.random.default_rng(layer)), jnp.float32)
    slots, real = x["slots"], np.asarray(x["slots"]) != NULL_SLOT
    y, states = jax.jit(step_lib.mamba_step)(
        x["u"], x["step"], x["a"], x["b"], x["c"], x["states"],
        jnp.int32(layer), slots)
    y_want, s_want = decode_lib.mamba_step(
        x["u"], x["step"], x["a"], x["b"], x["c"], x["states"][layer, slots])
    np.testing.assert_allclose(y[real], y_want[real], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(states[layer, slots[real]], s_want[real],
                               rtol=1e-6, atol=1e-6)
    untouched = np.ones((LAYERS, SLOTS + 1), bool)
    untouched[layer, np.asarray(slots)] = False
    assert (np.asarray(states)[untouched]
            == np.asarray(x["states"])[untouched]).all()
    assert np.isfinite(np.asarray(states[layer, NULL_SLOT])).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_rows_kernel_is_the_scatter_by_slot(case, layer, dtype):
    """A stepped slot drops its oldest row and takes its row of the
    batch, bit for bit what the scatter wrote; every other slot and
    layer is what it was. The null slot holds one of its rows."""
    x = inputs(CASES[case](np.random.default_rng(layer)), jnp.dtype(dtype))
    slots, real = x["slots"], np.asarray(x["slots"]) != NULL_SLOT
    rows = jax.jit(step_lib.shift_rows)(x["rows"], jnp.int32(layer), slots,
                                        x["new"])
    want = x["rows"].at[layer, slots[real]].set(jnp.concatenate(
        [x["rows"][layer, slots[real]][:, DI:], x["new"][real]], 1))
    keep = np.ones(SLOTS + 1, bool)
    keep[NULL_SLOT] = real.all()
    assert rows.dtype == x["rows"].dtype
    assert (np.asarray(rows, np.float32)[:, keep]
            == np.asarray(want, np.float32)[:, keep]).all()
    if not real.all():
        newest = np.asarray(rows[layer, NULL_SLOT, -DI:], np.float32)
        assert any((newest == np.asarray(r, np.float32)).all()
                   for r in x["new"][~real])


def test_one_row_before_the_convolution_is_replaced():
    """``mamba_d_conv = 2``: a slot keeps one row and nothing shifts."""
    x = inputs(a_part(np.random.default_rng(0)), jnp.float32)
    rows = x["rows"][..., :DI]
    got = step_lib.shift_rows(rows, 1, x["slots"], x["new"])
    assert (got == rows.at[1, x["slots"]].set(x["new"])).all()


@pytest.mark.parametrize("wrong", ["pool_dtype", "a", "slots", "channels"])
def test_the_kernel_refuses_shapes_that_do_not_belong(wrong):
    x = inputs(a_part(np.random.default_rng(0)), jnp.float32)
    args = [x["u"], x["step"], x["a"], x["b"], x["c"], x["states"], 0,
            x["slots"]]
    kw = {}
    if wrong == "pool_dtype":
        args[5] = args[5].astype(jnp.bfloat16)
    elif wrong == "a":
        args[2] = args[2][:, :DI // 2]
    elif wrong == "slots":
        args[7] = args[7][:-1]
    else:
        kw["channels"] = 48
    with pytest.raises(ValueError, match="mamba_step"):
        step_lib.mamba_step(*args, **kw)
    with pytest.raises(ValueError, match="shift_rows"):
        step_lib.shift_rows(x["rows"][..., 1:], 0, x["slots"], x["new"])


@pytest.mark.parametrize("backend,n_state,d_inner,kernel", [
    ("cpu", 4, 64, True), ("tpu", 16, 5120, True), ("tpu", 8, 128, True),
    ("tpu", 4, 64, False), ("tpu", 16, 5120 + 64, False),
    ("tpu", 12, 256, False)])
def test_a_state_of_whole_tiles_takes_the_kernel(monkeypatch, backend,
                                                 n_state, d_inner, kernel):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert step_lib.taken(n_state, d_inner) is kernel


def test_a_state_that_is_not_whole_tiles_keeps_the_xla_form(monkeypatch):
    """The decode program of a tiny stack (64 channels, 4 state rows:
    the fall-back on a TPU) through the XLA form, as a TPU would trace
    it, against the same program through the kernels: tokens' logits,
    states and rows of the batch's slots, and the slot that is not in
    the batch untouched by both."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=1,
        d_ff=64, max_seq=64, norm_eps=1e-6,
        layer_types=("mamba", "full", "mamba"), mamba_d_state=4,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
        tie_embeddings=True, dtype=jnp.float32, remat=False)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    cache = init_kv_cache(cfg, 9, 8, n_slots=4)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    kc = tuple(jax.random.normal(ks[0], a.shape, a.dtype) for a in cache.k)
    vc = tuple(jax.random.normal(ks[1], a.shape, a.dtype) for a in cache.v)
    tokens = jnp.array([5, 9, 0], jnp.int32)
    positions = jnp.array([3, 11, 0], jnp.int32)
    tables = jnp.array([[1, 2], [3, 4], [0, 0]], jnp.int32)
    slots = jnp.array([3, 1, NULL_SLOT], jnp.int32)

    def run():
        decode = decode_lib.mixed_programs(cfg, 8, 2, 0,
                                           head=lambda lg: lg)[2]
        return jax.jit(decode)(params, kc, vc, tokens, positions,
                               (tables, slots))

    monkeypatch.setattr(step_lib, "taken", lambda n, d: False)
    xla = run()
    monkeypatch.undo()
    kernel = run()
    at = cache.kinds.index("mamba")
    np.testing.assert_allclose(kernel[2][:2], xla[2][:2], rtol=2e-5,
                               atol=2e-5)
    for got, want, before in ((kernel[0][at], xla[0][at], kc[at]),
                              (kernel[1][at], xla[1][at], vc[at])):
        np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=2e-5,
                                   atol=2e-5)
        assert (got[:, 2] == before[:, 2]).all()
        assert (got[:, 4] == before[:, 4]).all()
