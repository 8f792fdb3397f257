"""A decode step's recurrence through ``hvd_state_step``
(``ops/state_step.py``, interpret mode here) against the XLA forms it
replaced in ``mamba2_step_layer`` and ``kda_step_layer``:
``decode.ssd_step`` and ``decode.kda_step`` on the rows' own states, the
same pools and the same slots (ISSUE 62)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.ops import state_step as step_lib
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import NULL_SLOT, init_kv_cache

LAYERS, SLOTS = 3, 13
#: heads, a head's state rows and columns (ssd: P by N, over 2 groups)
SHAPES = {"ssd": (8, 8, 16), "kda": (4, 16, 16)}
GROUPS = 2


def shuffled(rng):
    """Every slot in the batch, in no order."""
    return 1 + rng.permutation(SLOTS)


def a_part(rng):
    """A batch smaller than the slots (and no multiple of 8 rows)."""
    return shuffled(rng)[:5]


def padded(rng):
    """A bucket's padding: several rows at the null slot, among and
    after the real ones."""
    slots = shuffled(rng)[:8]
    slots[[2, 5, 6, 7]] = NULL_SLOT
    return slots


CASES = {"shuffled": shuffled, "a_part": a_part, "padded": padded}


def inputs(rule, slots, seed=0):
    """``(per-row inputs in the rule's order, the pool, the slots)``."""
    B = len(slots)
    H, rows, cols = SHAPES[rule]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = jax.random.normal(ks[5], (LAYERS, SLOTS + 1, H, rows, cols))
    if rule == "ssd":
        per_row = (jax.random.normal(ks[0], (B, H, rows)),
                   jax.random.uniform(ks[1], (B, H), minval=1e-3, maxval=0.1),
                   -jnp.exp(jax.random.normal(ks[2], (H,))),
                   jax.random.normal(ks[3], (B, GROUPS, cols)),
                   jax.random.normal(ks[4], (B, GROUPS, cols)))
    else:
        per_row = (jax.random.normal(ks[0], (B, H, rows)) * rows ** -0.5,
                   jax.random.normal(ks[1], (B, H, rows)) * rows ** -0.5,
                   jax.random.normal(ks[2], (B, H, cols)),
                   -jnp.exp(jax.random.normal(ks[3], (B, H, rows))),
                   jax.nn.sigmoid(jax.random.normal(ks[4], (B, H))))
    return per_row, pool, jnp.asarray(slots, jnp.int32)


KERNEL = {"ssd": step_lib.ssd_step, "kda": step_lib.kda_step}
JITTED = {rule: jax.jit(fn) for rule, fn in KERNEL.items()}
XLA = {"ssd": decode_lib.ssd_step, "kda": decode_lib.kda_step}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("rule", sorted(SHAPES))
def test_the_kernel_is_the_step_on_the_rows_own_states(rule, case, layer):
    """The outputs and the stepped states to float32 round-off, and
    every byte of the pool that is not a stepped slot of ``layer`` what
    it was: the other layers, and the slots that are not in the batch.
    The null slot, which the padding's rows share, holds something
    finite."""
    per_row, pool, slots = inputs(rule, CASES[case](
        np.random.default_rng(layer)))
    real = np.asarray(slots) != NULL_SLOT
    out, new = JITTED[rule](*per_row, pool, jnp.int32(layer), slots)
    out_want, s_want = XLA[rule](*per_row, pool[layer, slots])
    np.testing.assert_allclose(out[real], out_want[real], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(new[layer, slots[real]], s_want[real],
                               rtol=1e-6, atol=1e-6)
    untouched = np.ones((LAYERS, SLOTS + 1), bool)
    untouched[layer, np.asarray(slots)] = False
    assert (np.asarray(new)[untouched] == np.asarray(pool)[untouched]).all()
    assert np.isfinite(np.asarray(new[layer, NULL_SLOT])).all()


@pytest.mark.parametrize("rule,heads", [
    ("ssd", 2), ("ssd", 4), ("ssd", 8), ("kda", 1), ("kda", 2), ("kda", 4)])
def test_the_heads_a_grid_step_holds_do_not_change_the_step(rule, heads):
    """A block of heads inside one group of ``b`` and ``c`` (2 of 4), a
    whole group (4), both groups (8); a kda layer's heads one, two and
    all at a time: the same states and outputs, bit for bit."""
    per_row, pool, slots = inputs(rule, a_part(np.random.default_rng(1)))
    want = JITTED[rule](*per_row, pool, jnp.int32(1), slots)
    got = KERNEL[rule](*per_row, pool, 1, slots, heads=heads)
    assert (got[0] == want[0]).all() and (got[1] == want[1]).all()


@pytest.mark.parametrize("rule,wrong", [
    (rule, wrong) for rule in sorted(SHAPES)
    for wrong in ("pool_dtype", "pool_heads", "first", "slots", "heads")])
def test_the_kernel_refuses_shapes_that_do_not_belong(rule, wrong):
    per_row, pool, slots = inputs(rule, a_part(np.random.default_rng(0)))
    per_row, kw = list(per_row), {}
    if wrong == "pool_dtype":
        pool = pool.astype(jnp.bfloat16)
    elif wrong == "pool_heads":
        pool = pool[:, :, 1:]
    elif wrong == "first":
        per_row[0] = per_row[0][..., 1:]
    elif wrong == "slots":
        slots = slots[:-1]
    else:
        kw["heads"] = 3
    with pytest.raises(ValueError, match=f"{rule}_step"):
        KERNEL[rule](*per_row, pool, 0, slots, **kw)


def test_a_block_of_heads_is_inside_a_group_or_whole_groups():
    """Six heads in two groups of three: two heads a grid step would
    straddle the groups' border."""
    (x, dt, a, b, c), pool, slots = inputs("ssd", a_part(
        np.random.default_rng(0)))
    with pytest.raises(ValueError, match="neither inside a group"):
        step_lib.ssd_step(x[:, :6], dt[:, :6], a[:6], b, c, pool[:, :, :6],
                          0, slots, heads=2)


@pytest.mark.parametrize("backend,rows,cols,kernel", [
    ("cpu", 8, 16, True), ("tpu", 64, 128, True), ("tpu", 128, 128, True),
    ("tpu", 8, 128, True), ("tpu", 8, 16, False), ("tpu", 64, 192, False),
    ("tpu", 12, 128, False)])
def test_a_state_of_whole_tiles_takes_the_kernel(monkeypatch, backend, rows,
                                                 cols, kernel):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert step_lib.taken(rows, cols) is kernel


def tiny(kind):
    """Two layers of ``kind`` whose states are not whole tiles: the
    fall-back on a TPU."""
    base = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=4, d_ff=64, max_seq=64, norm_eps=1e-6,
                dtype=jnp.float32, remat=False)
    if kind == "mamba2":
        return TransformerConfig(
            **base, layer_types=("mamba2", "mamba2"),
            mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
            mamba2_head_dim=8, mamba2_groups=2, tie_embeddings=True)
    return TransformerConfig(
        **base, layer_types=("kda", "kda"), kda_conv=4)


@pytest.mark.parametrize("kind", ["mamba2", "kda"])
def test_a_state_that_is_not_whole_tiles_keeps_the_xla_form(monkeypatch,
                                                            kind):
    """The decode program of a tiny stack through the XLA form, as a
    TPU would trace it (its states are not whole tiles), against the
    same program through the kernel: tokens' logits, states and rows of
    the batch's slots, and the slots that are not in the batch untouched
    by both."""
    cfg = tiny(kind)
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

    def no_call(*args, **kw):
        raise AssertionError("the fall-back called the kernel")

    monkeypatch.setattr(step_lib, "taken", lambda rows, cols: False)
    monkeypatch.setattr(step_lib, "_call", no_call)
    xla = run()
    monkeypatch.undo()
    kernel = run()
    at = cache.kinds.index(kind)
    np.testing.assert_allclose(kernel[2][:2], xla[2][:2], rtol=2e-5,
                               atol=2e-5)
    for got, want, before in ((kernel[0][at], xla[0][at], kc[at]),
                              (kernel[1][at], xla[1][at], vc[at])):
        np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=2e-5,
                                   atol=2e-5)
        assert (got[:, 2] == before[:, 2]).all()
        assert (got[:, 4] == before[:, 4]).all()
