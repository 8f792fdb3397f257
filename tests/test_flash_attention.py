"""Flash-attention Pallas kernel vs. naive attention — forward and
gradients must match to float tolerance (interpret mode on CPU; the
same kernel compiles for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import local_attention


def _qkv(b=2, t=256, h=4, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype) * 0.5 for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_naive(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal)
    want = local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_forward_unaligned_seq_len():
    """T not a multiple of the block size exercises the pad/mask path."""
    q, k, v = _qkv(t=100)
    got = flash_attention(q, k, v, causal=True)
    want = local_attention(q, k, v, causal=True)
    assert got.shape == want.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_naive(causal):
    q, k, v = _qkv(t=128)
    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) * cot)

    def loss_naive(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=causal) * cot)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("hkv", [1, 2])
def test_gqa_native_matches_tiled(hkv):
    """Grouped K/V via the kernel's index map must equal tiling KV up
    to H and running square attention — forward and gradients."""
    h = 4
    q, _, _ = _qkv(h=h, t=128)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    k = jax.random.normal(ks[0], (2, 128, hkv, 64)) * 0.5
    v = jax.random.normal(ks[1], (2, 128, hkv, 64)) * 0.5
    rep = h // hkv
    kt = jnp.repeat(k, rep, axis=2)
    vt = jnp.repeat(v, rep, axis=2)

    got = flash_attention(q, k, v, causal=True)
    want = local_attention(q, kt, vt, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    cot = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    g1 = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True) * cot),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(
        local_attention(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                        causal=True) * cot), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name}")


def test_transformer_flash_gqa_tp_exceeds_kv_heads(devices):
    """tp > Hkv (tiny: H=4, Hkv=2, tp=4): the island must fall back to
    tiling KV so the head axis still divides over tp, and the loss must
    still match the local impl."""
    from horovod_tpu.models import transformer as tr
    from horovod_tpu.parallel import build_mesh

    mesh = build_mesh(dp=2, tp=4)
    cfg_f = tr.TransformerConfig.tiny(sp_attention="flash",
                                      dtype=jnp.float32, remat=False)
    assert cfg_f.n_kv_heads < mesh.shape["tp"]
    cfg_l = tr.TransformerConfig.tiny(sp_attention="local",
                                      dtype=jnp.float32, remat=False)
    params = tr.init_params(cfg_f, jax.random.PRNGKey(0), mesh)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 256)
    lf = float(jax.jit(lambda p: tr.lm_loss(p, {"tokens": toks}, cfg_f,
                                            mesh))(params))
    ll = float(tr.lm_loss(jax.device_get(params), {"tokens": toks},
                          cfg_l, None))
    np.testing.assert_allclose(lf, ll, rtol=1e-4)


def test_bf16_runs_and_is_close():
    q, k, v = _qkv(t=128, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True)
    want = local_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_transformer_with_flash_attention(devices):
    from horovod_tpu.models import transformer as tr

    cfg_f = tr.TransformerConfig.tiny(sp_attention="flash",
                                      dtype=jnp.float32, remat=False)
    cfg_l = tr.TransformerConfig.tiny(sp_attention="local",
                                      dtype=jnp.float32, remat=False)
    params = tr.init_params(cfg_f, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)
    lf = float(tr.lm_loss(params, {"tokens": toks}, cfg_f, None))
    ll = float(tr.lm_loss(params, {"tokens": toks}, cfg_l, None))
    np.testing.assert_allclose(lf, ll, rtol=1e-4)
    g = jax.grad(lambda p: tr.lm_loss(p, {"tokens": toks}, cfg_f, None))(
        params)
    assert all(np.isfinite(np.asarray(x, np.float32)).all()
               for x in jax.tree.leaves(g))


def test_transformer_flash_on_multi_device_mesh(devices):
    """flash must compose with dp/fsdp/tp sharding (the kernel runs as
    a manual island per device block)."""
    from horovod_tpu.models import transformer as tr
    from horovod_tpu.parallel import build_mesh

    mesh = build_mesh(dp=2, fsdp=2, tp=2)
    cfg = tr.TransformerConfig.tiny(sp_attention="flash",
                                    dtype=jnp.float32, remat=False)
    params = tr.init_params(cfg, jax.random.PRNGKey(0), mesh)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 256)
    loss = float(jax.jit(lambda p: tr.lm_loss(p, {"tokens": toks}, cfg,
                                              mesh))(params))
    cfg_l = tr.TransformerConfig.tiny(sp_attention="local",
                                      dtype=jnp.float32, remat=False)
    want = float(tr.lm_loss(jax.device_get(params), {"tokens": toks},
                            cfg_l, None))
    np.testing.assert_allclose(loss, want, rtol=1e-4)


def test_flash_rejects_sp_composition(devices):
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.parallel.ring_attention import make_sp_attention

    mesh = build_mesh(sp=2, dp=4)
    with pytest.raises(NotImplementedError, match="ring_flash"):
        make_sp_attention(mesh, impl="flash")


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_local(devices, causal):
    """Ring attention with the Pallas kernel in the block loop must
    equal full local attention — forward and gradients — on an sp=4
    mesh (the long-context + sequence-parallel composition)."""
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.parallel.ring_attention import make_sp_attention

    mesh = build_mesh(sp=4, dp=2)
    q, k, v = _qkv(t=256)
    att = make_sp_attention(mesh, impl="ring_flash", causal=causal)
    got = jax.jit(att)(q, k, v)
    want = local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)

    cot = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    g1 = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(att(q, k, v) * cot),
        argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(
        lambda q, k, v: jnp.sum(local_attention(q, k, v, causal=causal)
                                * cot), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name}")


def test_transformer_ring_flash_trains(devices):
    from horovod_tpu.models import transformer as tr
    from horovod_tpu.parallel import build_mesh

    mesh = build_mesh(sp=2, dp=2, tp=2)
    cfg = tr.TransformerConfig.tiny(sp_attention="ring_flash",
                                    dtype=jnp.float32, remat=False)
    init_state, jit_step, _ = tr.make_train_step(cfg, mesh)
    state = init_state(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 256)
    state, loss = jit_step(state, {"tokens": toks})
    _, loss2 = jit_step(state, {"tokens": toks})
    assert float(loss2) < float(loss)


def _residuals(t, h, hkv, dtype=jnp.float32, out_dtype=None, causal=True):
    """Kernel-layout inputs ([B·H, T, D] q over [B·Hkv, T, D] k/v), the
    forward's residuals for them and a cotangent of the output."""
    import horovod_tpu.ops.flash_attention as fa

    ks = jax.random.split(jax.random.PRNGKey(t + h), 4)
    q = (jax.random.normal(ks[0], (h, t, 32)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (hkv, t, 32)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (hkv, t, 32)) * 0.5).astype(dtype)
    scale = 32 ** -0.5
    out, lse = fa._fwd(q, k, v, scale=scale, causal=causal, block_q=128,
                       block_k=128, interpret=True, out_dtype=out_dtype,
                       q_per_kv=h // hkv)
    g = jax.random.normal(ks[3], out.shape).astype(out.dtype)
    return scale, (q, k, v, out, lse), g


def _assert_grads_close(got, want, exact):
    """``exact``: float32 operands, so the kernels' arithmetic is the
    reference's but for the order of sums. Otherwise P and dS were
    rounded to bf16 for the MXU and the results to their dtype: each
    tensor within 1 % of the reference's rms."""
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape, name
        if exact:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                       err_msg=name)
        else:
            rms = np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2))
            assert rms < 0.01, (name, rms)


@pytest.fixture
def small_bwd_tiles(monkeypatch):
    """128 x 128 backward tiles, so that a few hundred positions make
    several of them: at 384 a causal grid has skipped tiles, tiles the
    diagonal crosses and tiles below it; 192 and 200 end in a padded
    tile."""
    import horovod_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_bwd_blocks", lambda *shape: (256, 128))
    return fa


@pytest.mark.parametrize("q_per_kv", [1, 2, 4])
@pytest.mark.parametrize("t", [192, 200, 384])
@pytest.mark.parametrize("causal", [True, False])
def test_pallas_backward_matches_einsum_reference(small_bwd_tiles, causal,
                                                  t, q_per_kv):
    """The dq / dkv kernels against the float32 einsum backward they
    replaced (``reference_flash_bwd.py``), from the same residuals."""
    from reference_flash_bwd import einsum_backward

    scale, res, g = _residuals(t, 4, 4 // q_per_kv, causal=causal)
    got = small_bwd_tiles._backward(scale, causal, True, q_per_kv, res, g)
    want = einsum_backward(scale, causal, res, g, q_per_kv=q_per_kv)
    _assert_grads_close(got, want, exact=True)


@pytest.mark.parametrize("dtype,out_dtype", [
    (jnp.float32, jnp.float32),
    (jnp.bfloat16, jnp.float32),       # ring attention's chunks
    (jnp.bfloat16, jnp.bfloat16)])
@pytest.mark.parametrize("t", [192, 200])
@pytest.mark.parametrize("causal", [True, False])
def test_pallas_backward_with_lse_cotangent(small_bwd_tiles, causal, t,
                                            dtype, out_dtype):
    """Ring attention consumes the log-sum-exp, so its cotangent has to
    reach dq and dk: a structured one through
    ``flash_attention_with_lse``'s own VJP, against the reference."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse
    from reference_flash_bwd import einsum_backward

    scale, res, g = _residuals(t, 2, 2, dtype, out_dtype, causal)
    q, k, v, _, lse = res
    g_lse = jnp.arange(lse.size, dtype=jnp.float32).reshape(
        lse.shape) / lse.size
    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=causal, block_q=128, block_k=128,
            out_dtype=out_dtype), q, k, v)
    got = vjp((g, g_lse))
    want = einsum_backward(scale, causal, res, g, g_lse)
    assert [x.dtype for x in got] == [dtype] * 3
    _assert_grads_close(got, want, exact=dtype == jnp.float32)


@pytest.mark.parametrize("q_per_kv", [1, 4, 8])
@pytest.mark.parametrize("window", [64, 128, 200])
@pytest.mark.parametrize("t", [192, 200, 384])
def test_window_kernels_match_the_dense_reference(monkeypatch, t, window,
                                                  q_per_kv):
    """The three kernels with a window (ISSUE 34), forward, dq, dk, dv
    and the log-sum-exp's cotangent, against the dense form with the
    same mask. Blocks of 128 with tiles of 64, so that a window of 64
    is smaller than a block, 128 equal to one and 200 no multiple of
    one: at 384 the grids hold blocks wholly behind the window (never
    visited), blocks its edge crosses, and whole ones; 192 and 200 end
    in a padded block. A window that reaches the row's start is no
    window."""
    import horovod_tpu.ops.flash_attention as fa
    from reference_flash_bwd import dense_forward, einsum_backward

    monkeypatch.setattr(fa, "_bwd_blocks", lambda *shape: (128, 64))
    h = 8
    ks = jax.random.split(jax.random.PRNGKey(t + window), 5)
    q = jax.random.normal(ks[0], (h, t, 32)) * 0.5
    k = jax.random.normal(ks[1], (h // q_per_kv, t, 32)) * 0.5
    v = jax.random.normal(ks[2], (h // q_per_kv, t, 32)) * 0.5
    g = jax.random.normal(ks[3], q.shape)
    g_lse = jax.random.normal(ks[4], (h, t))
    scale = 32 ** -0.5
    w = fa._checked_window(window, True, t)
    assert (w is None) == (window >= t)
    out, lse = fa._fwd(q, k, v, scale=scale, causal=True, block_q=128,
                       block_k=128, interpret=True, q_per_kv=q_per_kv,
                       window=w)
    want_out, want_lse = dense_forward(scale, True, q, k, v, q_per_kv,
                                       window)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)
    res = (q, k, v, out, lse)
    got = fa._backward(scale, True, True, q_per_kv, res, g, g_lse, window=w)
    want = einsum_backward(scale, True, res, g, g_lse, q_per_kv,
                           window=window)
    _assert_grads_close(got, want, exact=True)


def test_a_window_as_long_as_the_row_is_no_window_bitwise():
    from horovod_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 200, 4, 32))
    k, v = (jax.random.normal(kk, (1, 200, 2, 32)) for kk in ks[1:])

    def run(**kw):
        return jax.value_and_grad(
            lambda q, k, v: flash_attention(q, k, v, **kw).sum(),
            (0, 1, 2))(q, k, v)

    for a, b in zip(jax.tree.leaves(run()),
                    jax.tree.leaves(run(window=200))):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128)])
def test_causal_block_skip_multiblock_grid(bq, bk):
    """The causal block-skip branch with a REAL multi-block kv grid
    (every other test clamps to one sequence-spanning block): values
    must match plain attention, including the on-diagonal boundary
    blocks the skip condition must keep visible."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import local_attention

    B, T, H, D = 1, 512, 2, 128  # T/bk in {2, 4}: ki grid > 1
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.float32)
               for kk in ks)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# -- the forward over keys that carry their positions (ISSUE 44) ----------

def _keys_reference(q, k, v, q_pos, k_pos, scale, window, page_mask=None,
                    page=None):
    """Plain attention in numpy's float64 with the mask read from the
    positions: key j of a query's group is seen where ``0 <= k_pos <=
    q_pos`` and, with a window, ``k_pos > q_pos - window``; a query
    that sees no key reads zeros at ``lse = NEG_INF``. ``page_mask``
    [BHkv, C, P]: key j besides only where its page ``j // page`` is
    the query's KV row's."""
    from horovod_tpu.ops.flash_attention import NEG_INF
    heads = q.shape[0] // q_pos.shape[0]
    rep = q.shape[0] // k.shape[0]
    q, k, v = (np.asarray(a.astype(jnp.float32), np.float64)
               for a in (q, k, v))
    k, v = np.repeat(k, rep, 0), np.repeat(v, rep, 0)
    qp = np.repeat(np.asarray(q_pos), heads, 0)[:, :, None]
    kp = np.repeat(np.asarray(k_pos), heads, 0)[:, None, :]
    seen = (kp >= 0) & (kp <= qp)
    if window is not None:
        seen &= kp > qp - window
    if page_mask is not None:
        by_key = np.repeat(np.asarray(page_mask), page, 2)[..., :k.shape[1]]
        seen = seen & np.repeat(by_key, rep, 0)
    s = np.where(seen, np.einsum("bqd,bkd->bqk", q, k) * scale, -np.inf)
    any_seen = seen.any(-1)
    top = np.where(any_seen, s.max(-1), 0.0)
    e = np.exp(s - top[..., None])
    lse = np.where(any_seen, top + np.log(np.maximum(e.sum(-1), 1e-300)),
                   NEG_INF)
    p = e / np.maximum(e.sum(-1, keepdims=True), 1e-300)
    return np.einsum("bqk,bkd->bqd", p, v), lse


def _keys_case(heads, kv_heads, c, keys, dk, dv, q_pos, k_pos,
               dtype=jnp.float32):
    rng = np.random.default_rng(0)
    groups = np.asarray(q_pos).shape[0]
    q, k, v = (jnp.asarray(0.5 * rng.standard_normal(shape, np.float32),
                           dtype)
               for shape in ((groups * heads, c, dk),
                             (groups * kv_heads, keys, dk),
                             (groups * kv_heads, keys, dv)))
    return (q, k, v, jnp.asarray(q_pos, jnp.int32),
            jnp.asarray(k_pos, jnp.int32))


def _at(offset, n):
    return offset + np.arange(n)


_KEYS_CASES = {
    # a chunk of 48 resumed at 112 (a multiple of 16, not of the tile)
    # over 160 keys, the latent layers' widths, Kimi's 64 heads; the
    # chunk is padded to the tile: a padded query tail
    "an_offset_that_is_no_tile_192_128_64_heads": dict(
        heads=64, kv_heads=64, c=48, keys=160, dk=192, dv=128,
        q_pos=[_at(112, 48)], k_pos=[_at(0, 160)]),
    # Ling's 32 heads, two q tiles and three kv tiles
    "two_q_tiles_three_kv_tiles_32_heads": dict(
        heads=32, kv_heads=32, c=144, keys=300, dk=192, dv=128,
        q_pos=[_at(144, 144)], k_pos=[_at(0, 300)],
        block_q=128, block_k=128),
    # positions that are not indices: a gap, keys that are not there,
    # and a tail of keys past every query (a key block's end)
    "a_gap_a_hole_and_a_tail_past_every_query": dict(
        heads=4, kv_heads=4, c=40, keys=272, dk=48, dv=32,
        q_pos=[_at(1000, 40)],
        k_pos=[np.concatenate([_at(0, 90), np.full(10, -1), _at(900, 120),
                               _at(1040, 52)])],
        block_q=128, block_k=128),
    # a whole kv tile past every query, and one of holes: both skipped
    "tiles_with_no_visible_pair_are_skipped": dict(
        heads=2, kv_heads=2, c=64, keys=512, dk=32, dv=32,
        q_pos=[_at(64, 64)],
        k_pos=[np.concatenate([_at(0, 128), np.full(128, -1),
                               _at(128, 256)])],
        block_q=128, block_k=128),
    # keys in no order: a ring's places
    "a_ring_s_positions_under_a_window": dict(
        heads=4, kv_heads=2, c=24, keys=128, dk=32, dv=32, window=50,
        q_pos=[_at(200, 24)],
        k_pos=[(224 - 1) - ((224 - 1) - np.arange(128)) % 128]),
    # two sequences at their own positions, GQA through the index map
    "two_groups_gqa_and_a_window": dict(
        heads=4, kv_heads=1, c=130, keys=260, dk=32, dv=16, window=70,
        q_pos=[_at(130, 130), _at(16, 130)],
        k_pos=[_at(0, 260), np.where(np.arange(260) < 146,
                                     np.arange(260), -1)],
        block_q=128, block_k=128),
    # a query below every key sees none: zeros at NEG_INF
    "a_query_that_sees_no_key": dict(
        heads=2, kv_heads=2, c=16, keys=128, dk=32, dv=32,
        q_pos=[_at(-4, 16)], k_pos=[_at(0, 128)]),
    "bf16_operands_float32_statistics": dict(
        heads=4, kv_heads=4, c=64, keys=256, dk=192, dv=128,
        q_pos=[_at(192, 64)], k_pos=[_at(0, 256)], dtype=jnp.bfloat16,
        block_q=128, block_k=128, tol=2e-2),
}


@pytest.mark.parametrize("case", sorted(_KEYS_CASES))
def test_keys_forward_matches_the_plain_reference(case):
    from horovod_tpu.ops.flash_attention import flash_attention_keys
    kw = dict(_KEYS_CASES[case])
    window, tol = kw.pop("window", None), kw.pop("tol", 2e-5)
    tiles = {n: kw.pop(n) for n in ("block_q", "block_k") if n in kw}
    q, k, v, q_pos, k_pos = _keys_case(**kw)
    out, lse = flash_attention_keys(q, k, v, q_pos, k_pos, scale=0.11,
                                    window=window, **tiles)
    assert out.shape == (q.shape[0], q.shape[1], v.shape[2])
    assert out.dtype == lse.dtype == jnp.float32
    want, want_lse = _keys_reference(q, k, v, q_pos, k_pos, 0.11, window)
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)
    np.testing.assert_allclose(lse, want_lse, rtol=tol, atol=tol)


@pytest.mark.parametrize("block_q", [128, 256], ids=["two_q_tiles",
                                                     "one_q_tile"])
def test_key_blocks_carried_from_call_to_call_are_one_attention(block_q):
    """A chunk over three key blocks, a block a call, the last one
    seen by some of its queries only (and by none of the first q tile):
    carried through the kernel from call to call, the result is the
    call's over all keys."""
    from horovod_tpu.ops.flash_attention import flash_attention_keys
    q, k, v, q_pos, k_pos = _keys_case(
        heads=4, kv_heads=2, c=160, keys=384, dk=48, dv=32,
        q_pos=[_at(136, 160)], k_pos=[_at(0, 384)])
    seen = None
    for j in range(3):
        at = slice(128 * j, 128 * (j + 1))
        seen = flash_attention_keys(q, k[:, at], v[:, at], q_pos,
                                    k_pos[:, at], scale=0.2, carry=seen,
                                    block_q=block_q, block_k=128)
    want, want_lse = _keys_reference(q, k, v, q_pos, k_pos, 0.2, None)
    np.testing.assert_allclose(seen[0], want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(seen[1], want_lse, rtol=2e-5, atol=2e-5)


def test_keys_forward_refuses_shapes_that_do_not_belong_together():
    from horovod_tpu.ops.flash_attention import flash_attention_keys
    q, k, v, q_pos, k_pos = _keys_case(
        heads=2, kv_heads=2, c=16, keys=32, dk=16, dv=16,
        q_pos=[_at(16, 16)], k_pos=[_at(0, 32)])
    with pytest.raises(ValueError, match="k_pos"):
        flash_attention_keys(q, k, v, q_pos, k_pos[:, :31], scale=1.0)


# -- ... under a mask by page and query (ISSUE 51) -------------------------

def _page_mask_case(name):
    """(arguments, keywords, page mask) of one masked call: 128 queries
    at 128.. over 256 keys (512 where the tail matters) in pages of 32,
    tiles of 128 (4 pages a kv tile), every query of a KV row allowed
    each page with probability 0.4 and then what the case is about."""
    group = {"a_gqa_group_of_16": 16}.get(name, 2)
    groups = 2 if name == "a_gqa_group_of_1_in_two_position_groups" else 1
    heads = 2 if groups == 2 else group
    c = 100 if name == "a_padded_query" else 128
    keys = 512 if name == "keys_past_the_last_tile_the_call_sees" else 256
    q, k, v, q_pos, k_pos = _keys_case(
        heads=heads, kv_heads=heads // (1 if groups == 2 else group), c=c,
        keys=keys, dk=32, dv=32,
        q_pos=[_at(128 + 28 * g, c) for g in range(groups)],
        k_pos=[_at(0, keys)] * groups)
    rng = np.random.default_rng(1)
    mask = rng.random((k.shape[0], c, keys // 32)) < 0.4
    if name == "a_query_that_allows_every_page":
        mask[:, 3] = True
    if name == "a_first_tile_that_holds_none_of_a_query_s_pages":
        mask[:, 7, :4], mask[:, 7, 4] = False, True
        mask[:, 9] = False                  # and a query with no page
    if name == "keys_past_the_last_tile_the_call_sees":
        # a KV row none of whose queries was given a page of the second
        # tile, and NaN wherever no tile pair is computed
        mask[0, :, 4:8] = False
        k = k.at[:, 256:].set(np.nan).at[0, 128:].set(np.nan)
        v = v.at[:, 256:].set(np.nan).at[0, 128:].set(np.nan)
    return (q, k, v, q_pos, k_pos), dict(
        scale=0.17, block_q=128, block_k=128, page=32), mask


@pytest.mark.parametrize("case", [
    "a_gqa_group_of_1_in_two_position_groups", "a_gqa_group_of_16",
    "a_query_that_allows_every_page",
    "a_first_tile_that_holds_none_of_a_query_s_pages", "a_padded_query",
    "keys_past_the_last_tile_the_call_sees", "carried_from_call_to_call"])
def test_keys_forward_under_a_page_mask_matches_the_plain_reference(case):
    """``flash_attention_keys(page_mask=)`` against the float64 softmax
    over the keys a query sees by position AND by its KV row's pages."""
    from horovod_tpu.ops.flash_attention import flash_attention_keys
    (q, k, v, q_pos, k_pos), kw, mask = _page_mask_case(case)
    if case == "carried_from_call_to_call":
        seen = None
        for at, pages in ((slice(0, 128), slice(0, 4)),
                          (slice(128, 256), slice(4, 8))):
            seen = flash_attention_keys(
                q, k[:, at], v[:, at], q_pos, k_pos[:, at], carry=seen,
                page_mask=jnp.asarray(mask[..., pages]), **kw)
        out, lse = seen
    else:
        out, lse = flash_attention_keys(q, k, v, q_pos, k_pos,
                                        page_mask=jnp.asarray(mask), **kw)
    finite = np.nan_to_num(np.asarray(k)), np.nan_to_num(np.asarray(v))
    want, want_lse = _keys_reference(q, *map(jnp.asarray, finite), q_pos,
                                     k_pos, 0.17, None, mask, 32)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)
    if case == "a_first_tile_that_holds_none_of_a_query_s_pages":
        assert not np.asarray(out[:, 9]).any()


def test_a_page_mask_is_refused_where_a_kv_tile_is_not_whole_pages():
    from horovod_tpu.ops.flash_attention import flash_attention_keys
    (q, k, v, q_pos, k_pos), kw, mask = _page_mask_case("a_padded_query")
    for page, pages in ((48, 6), (2, 128), (32, 9)):   # 128 / 2 > 32 bits
        with pytest.raises(ValueError, match="page_mask"):
            flash_attention_keys(
                q, k, v, q_pos, k_pos, **dict(kw, page=page),
                page_mask=jnp.ones((k.shape[0], 100, pages), bool))


def _unmasked_keys_calls():
    from horovod_tpu.ops.flash_attention import flash_attention_keys
    f32, i32 = jnp.float32, jnp.int32

    def carried(q, k, v, qp, kp, o, lse):
        return flash_attention_keys(q, k, v, qp, kp, scale=0.2,
                                    carry=(o, lse), block_k=128)
    return {
        "plain": (lambda *a: flash_attention_keys(*a, scale=0.11), (
            ((8, 160, 48), f32), ((8, 384, 48), f32), ((8, 384, 32), f32),
            ((1, 160), i32), ((1, 384), i32))),
        "two_groups_gqa_and_a_window": (
            lambda *a: flash_attention_keys(
                *a, scale=0.11, window=70, block_q=128, block_k=128), (
            ((8, 130, 32), jnp.bfloat16), ((2, 260, 32), jnp.bfloat16),
            ((2, 260, 16), jnp.bfloat16), ((2, 130), i32), ((2, 260), i32))),
        "carried": (carried, (
            ((4, 160, 48), f32), ((2, 384, 48), f32), ((2, 384, 32), f32),
            ((1, 160), i32), ((1, 384), i32), ((4, 160, 32), f32),
            ((4, 160), f32))),
    }


def test_the_keys_forward_without_a_mask_lowers_to_the_program_before_it():
    """ISSUE 51 gave ``flash_attention_keys`` a page mask as one more
    optional operand: without one (the latent chunks of the Kimi and
    Ling cells) it lowers, interpret mode and no locations, to the text
    it lowered to at the commit before, so its results are that
    commit's bit for bit. The digests were written from a checkout of
    c1c86e2."""
    import hashlib
    got = {}
    for name, (f, args) in _unmasked_keys_calls().items():
        text = jax.jit(f).lower(*(jax.ShapeDtypeStruct(s, d)
                                  for s, d in args)).as_text()
        got[name] = hashlib.sha256(text.encode()).hexdigest()
    assert got == _BEFORE_PR51


_BEFORE_PR51 = {
    "plain":
        "41b8d71e8ede76c589125ca092596720ac43c5a258120a9d57da00a065f77e99",
    "two_groups_gqa_and_a_window":
        "5b019334342a34be14a88e6f6f78027f886bc0490f345c83c723b82177627b53",
    "carried":
        "0b67238247e2239c908f557c0c03addbb048e207379906899f322417b6293788",
}


def _old_entry_points():
    from horovod_tpu.ops.flash_attention import (flash_attention,
                                                 flash_attention_with_lse)
    q, kv, bh = (2, 256, 4, 64), (2, 256, 2, 64), (8, 192, 64)
    return {
        "causal_gqa": (lambda q, k, v: flash_attention(q, k, v), (q, kv, kv)),
        "not_causal": (lambda q, k, v: flash_attention(q, k, v, causal=False),
                       (q, q, q)),
        "window": (lambda q, k, v: flash_attention(q, k, v, window=64),
                   ((1, 200, 4, 32), (1, 200, 2, 32), (1, 200, 2, 32))),
        "backward": (jax.grad(lambda q, k, v: flash_attention(q, k, v).sum(),
                              (0, 1, 2)), (q, kv, kv)),
        "with_lse": (lambda q, k, v: flash_attention_with_lse(
            q, k, v, causal=True, out_dtype=jnp.float32), (bh, bh, bh)),
    }


def _lowered_digest(f, shapes):
    import hashlib
    text = jax.jit(f).lower(*(jax.ShapeDtypeStruct(s, jnp.float32)
                              for s in shapes)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_the_old_entry_points_lower_to_the_programs_before_the_keys_forward():
    """ISSUE 44 gave the forward over keys with positions a kernel body
    of its own: ``flash_attention`` and ``flash_attention_with_lse``,
    forward and backward, with and without a window, lower (interpret
    mode, no locations) to the text they lowered to at the commit
    before it, so their outputs are that commit's bit for bit on the
    shapes this file covers (``tests/test_tpu_lowering.py`` holds the
    v5e's compiled kernels the same way). The digests were written from
    a checkout of 68601a6; a PR that changes those kernels on purpose
    writes them anew."""
    assert {name: _lowered_digest(*case)
            for name, case in _old_entry_points().items()} == _BEFORE_PR44


_BEFORE_PR44 = {
    "causal_gqa":
        "8ea96c23f0830defe1265696d88ad832f239e52c0be7e30707536b7f85efd7d1",
    "not_causal":
        "a5df8aea76404c21b8c0d420e025652c65fad3ef0be6131981f33a211a08fbe3",
    "window":
        "76132d3305290db8cf87c0fe3b646e2977ec5acc5092c48ef27ff9c4c8ff8955",
    "backward":
        "6b880fd4a018f7c8d6b7dc914053a030271b7d9ba47d8a74a6b2b10753cd4b6c",
    "with_lse":
        "d1dc2e04f2e1ddc831fb3883a7d253080cd921abec03e393cc829b2877f48320",
}
