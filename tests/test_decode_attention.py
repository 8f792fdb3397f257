"""The paged attention of the serve programs (PR 25):
``serve/decode.py`` ``_attend_pages`` reads each K/V page once, in the
cache's dtype and with its Hkv heads, and contracts the GQA group as a
free dimension of the dot. Since PR 30 it takes the whole pool and a
layer: the cases read layer 1 of a three-layer pool whose other layers
hold other numbers. Checked against a plain float32
repeat-then-attend written out here, and, on the lowered ``decode`` of
a GQA model, by the shapes that must not appear: no K or V repeated
across the group, no float32 copy of the gathered pages."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import NULL_BLOCK

BS, WIDTH, HKV, DH = 4, 5, 2, 16          # S = 20 keys a sequence
N_BLOCKS = 1 + 4 * WIDTH
LAYERS, LAYER = 3, 1
_attend = jax.jit(decode_lib._attend_pages)


def attend_pages(q, kc_l, vc_l, tables, pos):
    """One layer's pool attended as layer ``LAYER`` of a whole pool,
    the layers around it negated: nothing of them may be read."""
    def whole(pool_l):
        return jnp.stack([-pool_l] * LAYER + [pool_l]
                         + [-pool_l] * (LAYERS - LAYER - 1))
    return _attend(q, whole(kc_l), whole(vc_l), LAYER, tables, pos)


def _case(rep, B, C, seed):
    """A random pool (the null block holds garbage too), per-sequence
    tables whose tail is null-block padding, and chunk positions inside
    each sequence's real blocks."""
    rng = np.random.default_rng(seed)
    pool = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((N_BLOCKS, BS, HKV, DH)), jnp.bfloat16)
    kc, vc = pool(), pool()
    q = jnp.asarray(rng.standard_normal((B, C, HKV * rep, DH)),
                    jnp.bfloat16)
    tables = np.full((B, WIDTH), NULL_BLOCK, np.int32)
    pos = np.zeros((B, C), np.int32)
    free = rng.permutation(np.arange(1, N_BLOCKS))
    for b in range(B):
        n_real = int(rng.integers(1, WIDTH + 1))
        tables[b, :n_real] = free[b * WIDTH:b * WIDTH + n_real]
        start = int(rng.integers(0, max(1, n_real * BS - C + 1)))
        pos[b] = np.minimum(start + np.arange(C), n_real * BS - 1)
    return q, kc, vc, jnp.asarray(tables), jnp.asarray(pos)


def _repeat_then_attend(q, kc, vc, tables, pos):
    """The reference: float32 throughout, K and V repeated across the
    group, one softmax per (sequence, query, head) over exactly the keys
    at positions 0..p."""
    q, kc, vc = (np.asarray(a, np.float32) for a in (q, kc, vc))
    B, C, H, Dh = q.shape
    rep = H // kc.shape[2]
    out = np.zeros((B, C, H, Dh), np.float32)
    for b in range(B):
        k = np.repeat(kc[np.asarray(tables[b])].reshape(-1, HKV, Dh),
                      rep, axis=1)                          # [S, H, Dh]
        v = np.repeat(vc[np.asarray(tables[b])].reshape(-1, HKV, Dh),
                      rep, axis=1)
        for c in range(C):
            live = int(pos[b, c]) + 1
            for h in range(H):
                s = k[:live, h] @ q[b, c, h] * Dh ** -0.5
                p = np.exp(s - s.max())
                out[b, c, h] = (p / p.sum()) @ v[:live, h]
    return out.reshape(B, C, H * Dh)


@pytest.mark.parametrize("C", [1, 3])
@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("rep", [1, 4, 8])
def test_attend_pages_matches_repeat_then_attend(rep, B, C):
    q, kc, vc, tables, pos = _case(rep, B, C, seed=100 * rep + 10 * B + C)
    got = attend_pages(q, kc, vc, tables, pos)
    assert got.shape == (B, C, HKV * rep * DH) and got.dtype == q.dtype
    want = _repeat_then_attend(q, kc, vc, tables, pos)
    # bf16 output (8 bits of mantissa) of bf16 probabilities times bf16
    # values, summed in float32: outputs are O(1).
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2, atol=2e-2)

    # A padded batch row (all-null table, position 0) reads the null
    # block and nothing else: other blocks' contents do not reach it.
    null_tables = jnp.full((B, WIDTH), NULL_BLOCK, jnp.int32)
    zeros = jnp.zeros((B, C), jnp.int32)
    keep_null = (jnp.arange(N_BLOCKS) == NULL_BLOCK)[:, None, None, None]
    padded = attend_pages(q, kc, vc, null_tables, zeros)
    again = attend_pages(
        q, jnp.where(keep_null, kc, -kc), jnp.where(keep_null, vc, 7 + vc),
        null_tables, zeros)
    np.testing.assert_array_equal(np.asarray(padded, np.float32),
                                  np.asarray(again, np.float32))


def test_decode_of_a_gqa_model_never_repeats_or_widens_the_pages():
    """What the device trace has to show, pinned where no chip is
    needed: the lowered ``decode`` of a bf16 GQA model (rep = 4) holds
    no value shaped like K or V repeated across the group, and no
    float32 value as large as the gathered pages; the gather still
    carries its name."""
    B, bs, width = 4, 8, 3
    cfg = TransformerConfig.tiny(d_model=128, n_heads=8, n_kv_heads=2,
                                 d_ff=96, vocab_size=160, remat=False)
    assert cfg.dtype == jnp.bfloat16
    H, Hkv, Dh, S = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, bs * width
    decode = decode_lib.make_serve_fns(cfg, None, block_size=bs,
                                       table_width=width)[2]
    params = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))
    cache = jax.ShapeDtypeStruct(
        (cfg.n_layers, 1 + B * width, bs, Hkv, Dh), cfg.dtype)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    lowered = decode.lower(params, cache, cache, i32(B), i32(B),
                           i32(B, width))
    text = lowered.as_text(debug_info=True)
    values = {(tuple(int(n) for n in dims[:-1].split("x")), dtype)
              for dims, dtype in re.findall(r"tensor<((?:\d+x)+)(\w+)>",
                                            text)}
    shapes = {shape for shape, _ in values}
    assert (B, S, Hkv, Dh) in shapes          # the gathered pages
    assert (B, S, H, Dh) not in shapes
    assert (B, S, Hkv, H // Hkv, Dh) not in shapes
    pages = B * S * Hkv * Dh
    wide = [(shape, dtype) for shape, dtype in values
            if dtype == "f32" and int(np.prod(shape)) >= pages]
    assert not wide, wide
    assert re.search(r'loc\("[^"]*attn/kv_gather', text)
