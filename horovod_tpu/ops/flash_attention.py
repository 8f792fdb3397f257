"""Flash attention — a Pallas TPU kernel for the hot op.

The reference has no device kernels of its own (it drives NCCL); on
TPU the framework's hot op is attention, and this module implements it
as a **fused Pallas kernel**: online-softmax over KV blocks so the
(T, T) score matrix never materializes in HBM — scores live in VMEM a
block at a time and the MXU sees two big matmuls per block. Forward
saves the per-row logsumexp; backward recomputes probabilities from it
(the standard memory-for-FLOPs trade) in plain XLA, which fuses well
and keeps the custom_vjp exactly consistent with the kernel's math.

Used via ``TransformerConfig(sp_attention="flash")`` or directly:

    out = flash_attention(q, k, v, causal=True)   # [B, T, H, D] each

On CPU (tests, the virtual mesh) the kernel runs in Pallas interpret
mode automatically.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                seq_len: int):
    """One (batch*head, q-block, kv-block) grid step of the online
    softmax. Scratch (acc, m, l) persists across the kv dimension."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # Causal block skip: a kv block strictly above the diagonal
    # (every k_pos > every q_pos) contributes nothing — masking it
    # after the matmul would still pay the full MXU cost, which is
    # HALF the causal grid at long sequence (measured ~1.7x forward
    # throughput at seq 8192 on v5e). Skipped steps still issue their
    # K/V block DMAs — clamping the index maps to the last visible
    # block (so Mosaic elides the fetch) measured no faster within
    # run-to-run noise, so the simple monotonic index stays.
    visible = ((qi + 1) * block_q - 1 >= ki * block_k) if causal else True

    @pl.when(visible)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos >= seq_len                     # padded kv rows
        if causal:
            mask = mask | (k_pos > q_pos)
        s = jnp.where(mask, NEG_INF, s)

        m_prev = m_scr[:]                            # [bq, 1]
        l_prev = l_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l > 0, l, 1.0)        # fully-masked (pad) rows
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(safe_l))[:, 0]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _default_blocks(t: int):
    """Shape-derived tile sizes. Sequence-spanning blocks win through
    medium sequence — grid overhead dominates small tiles (1024×1024
    at seq 1024 measures 61.6% vs 53.3% MFU for 128×128 on v5e,
    d=2048×8L) — while 512×1024 wins from ~4k up (measured at seq 8192
    for both forward and fwd+bwd). Capped at 1024: a 2048×2048 tile's
    f32 scores need 21 MiB of scoped VMEM at seq 4096 against the v5e's
    16 MiB default (Mosaic refuses it); at seq 2048 it compiles, and
    whether it would be faster there is not measured."""
    if t <= 4096:
        b = min(1024, _round_up(t, 128))
        return b, b
    return 512, 1024


def _fwd(q, k, v, *, scale, causal, block_q, block_k, interpret,
         out_dtype=None, q_per_kv: int = 1):
    """q: [BH, T, D]; k/v: [B·Hkv, T, D] with BH = B·Hkv·q_per_kv ->
    (out [BH, T, D], lse [BH, T]).

    GQA runs natively: the K/V BlockSpec index map sends each query
    head's grid step to its kv group's block, so grouped K/V are never
    materialized ``q_per_kv`` times in HBM (the [B,H] flattening is
    batch-major, so ``kv_index = q_index // q_per_kv``)."""
    bh, t, d = q.shape
    out_dtype = q.dtype if out_dtype is None else out_dtype
    bq = min(block_q, _round_up(t, 128))
    bk = min(block_k, _round_up(t, 128))
    tp = _round_up(t, max(bq, bk))
    if tp != t:
        pad = [(0, 0), (0, tp - t), (0, 0)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))

    grid = (bh, tp // bq, tp // bk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_len=t)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (b // q_per_kv, j, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (b // q_per_kv, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            # lse rides a (bh, 1, T) layout so every block's trailing
            # two dims are TPU-tileable (1 == full dim, bq % 128 == 0).
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, d), out_dtype),
            jax.ShapeDtypeStruct((bh, 1, tp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        # The kernel's name in a device trace (PERF.md §3); a Pallas
        # backward takes "hvd_flash_bwd".
        name="hvd_flash_fwd",
    )(q, k, v)
    return out[:, :t], lse[:, 0, :t]


# Above this query length the backward recompute runs q-chunked: the
# dense form materializes [B·H, Tq, Tk] f32 score/probability tensors
# (O(T²) HBM — ~2 GB per B·H=8 at T=8192, OOM well before 32k); the
# chunked form caps live intermediates at [B·H, chunk, Tk].
_BWD_CHUNK_T = 4096
_BWD_CHUNK = 1024


def _bwd(scale, causal, residuals, g, g_lse=None, q_per_kv: int = 1):
    """Recompute-based backward from the saved logsumexp: exact same
    probabilities the kernel computed, expressed as XLA matmul chains
    (fused by the compiler). ``g_lse`` carries the logsumexp cotangent
    when the caller consumed it (ring-attention block merging);
    d lse/d q = (p @ k)·scale and d lse/d k_j = p_j · q · scale.

    GQA (``q_per_kv > 1``): q-side tensors reshape to a [B·Hkv, rep]
    grouping (consecutive query heads share a kv head under the
    batch-major flattening) and dk/dv sum over the group.

    Long sequences dispatch to the q-chunked form (same math, bounded
    memory)."""
    if residuals[0].shape[1] > _BWD_CHUNK_T:
        return _bwd_chunked(scale, causal, residuals, g, g_lse, q_per_kv)
    q, k, v, out, lse = residuals
    rep = q_per_kv
    bkv = k.shape[0]
    t = q.shape[1]
    d = q.shape[2]
    as_grp = lambda x: x.astype(jnp.float32).reshape(bkv, rep, t, d)  # noqa: E731
    gl = (None if g_lse is None
          else g_lse.astype(jnp.float32).reshape(bkv, rep, t))
    dq, dk, dv = _bwd_rows(
        as_grp(q), as_grp(g), as_grp(out), lse.reshape(bkv, rep, t), gl,
        k.astype(jnp.float32), v.astype(jnp.float32), 0, scale, causal)
    return (dq.reshape(q.shape).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


def _bwd_rows(qc, doc, outc, lsec, glc, kf, vf, q_pos0, scale, causal):
    """Gradient contributions of one block of query rows (f32 in/out):
    the shared body of the dense and chunked backwards. ``q_pos0`` is
    the block's global query offset for the causal mask."""
    tk = kf.shape[1]
    s = jnp.einsum("brqd,bkd->brqk", qc, kf) * scale
    if causal:
        q_pos = q_pos0 + jnp.arange(qc.shape[2])[:, None]
        k_pos = jnp.arange(tk)[None, :]
        s = jnp.where(k_pos > q_pos, NEG_INF, s)
    p = jnp.exp(s - lsec[..., None])             # [bkv, rep, rows, tk]

    dv = jnp.einsum("brqk,brqd->bkd", p, doc)
    dp = jnp.einsum("brqd,bkd->brqk", doc, vf)
    delta = jnp.sum(doc * outc, axis=-1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("brqk,bkd->brqd", ds, kf)
    dk = jnp.einsum("brqk,brqd->bkd", ds, qc)
    if glc is not None:
        dq = dq + glc[..., None] * jnp.einsum("brqk,bkd->brqd", p, kf) * scale
        dk = dk + jnp.einsum("brq,brqk,brqd->bkd", glc, p, qc) * scale
    return dq, dk, dv


def _bwd_chunked(scale, causal, residuals, g, g_lse, q_per_kv):
    """The backward above with the query axis processed in
    ``_BWD_CHUNK``-row slices under ``lax.scan``: per-step tensors are
    [bkv, rep, chunk, tk] instead of [bkv, rep, tq, tk], so HBM stays
    bounded for long sequences. Padding rows (q/do/out zeros, lse 0)
    contribute exactly zero to every accumulated gradient."""
    q, k, v, out, lse = residuals
    rep = q_per_kv
    bkv = k.shape[0]
    t, d = q.shape[1], q.shape[2]
    chunk = _BWD_CHUNK
    pad = (-t) % chunk

    def prep(x):  # [bkv*rep, t, d] -> padded [bkv, rep, T, d], own dtype
        x = x.reshape(bkv, rep, t, d)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))

    # Padded in the INPUT dtype: the f32 cast happens per chunk inside
    # step(), keeping the f32 working set at O(chunk), not O(T).
    qf, do, outf = prep(q), prep(g), prep(out)
    lseg = jnp.pad(lse.reshape(bkv, rep, t), ((0, 0), (0, 0), (0, pad)))
    gl = (None if g_lse is None else
          jnp.pad(g_lse.astype(jnp.float32).reshape(bkv, rep, t),
                  ((0, 0), (0, 0), (0, pad))))
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    n = (t + pad) // chunk

    def step(carry, i):
        dk_acc, dv_acc = carry
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=i * chunk, slice_size=chunk,
                               axis=2)
        f32 = lambda x: sl(x).astype(jnp.float32)  # noqa: E731
        dq_c, dk_c, dv_c = _bwd_rows(
            f32(qf), f32(do), f32(outf), sl(lseg),
            None if gl is None else sl(gl), kf, vf, i * chunk, scale,
            causal)
        return (dk_acc + dk_c, dv_acc + dv_c), dq_c.astype(q.dtype)

    (dk, dv), dq_chunks = jax.lax.scan(
        step, (jnp.zeros_like(kf), jnp.zeros_like(vf)), jnp.arange(n))
    # [n, bkv, rep, chunk, d] -> [bkv, rep, t, d] (pad rows dropped)
    dq = jnp.moveaxis(dq_chunks, 0, 2).reshape(
        bkv, rep, n * chunk, d)[:, :, :t, :]
    return (dq.reshape(q.shape), dk.astype(k.dtype), dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, q_per_kv):
    out, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret, q_per_kv=q_per_kv)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               q_per_kv):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret,
                    q_per_kv=q_per_kv)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, q_per_kv,
               residuals, g):
    with jax.named_scope("flash_bwd"):
        return _bwd(scale, causal, residuals, g, q_per_kv=q_per_kv)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, scale, causal, block_q, block_k, interpret,
               out_dtype, q_per_kv):
    return _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret, out_dtype=out_dtype,
                q_per_kv=q_per_kv)


def _flash_lse_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                   out_dtype, q_per_kv):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret,
                    out_dtype=out_dtype, q_per_kv=q_per_kv)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(scale, causal, block_q, block_k, interpret, out_dtype,
                   q_per_kv, residuals, g):
    g_out, g_lse = g
    with jax.named_scope("flash_bwd"):
        return _bwd(scale, causal, residuals, g_out, g_lse,
                    q_per_kv=q_per_kv)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(q, k, v, *, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             out_dtype=None):
    """``[BH, T, D]``-layout flash attention returning ``(out, lse)``
    — the building block for blockwise composition (ring attention
    merges per-chunk results by logsumexp weighting). Differentiable
    in both outputs. ``out_dtype=jnp.float32`` keeps chunk outputs at
    merge precision (callers that round once at the end)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    dq, dk = _default_blocks(q.shape[1])
    return _flash_lse(q, k, v, float(scale), causal,
                      dq if block_q is None else block_q,
                      dk if block_k is None else block_k, interpret,
                      jnp.dtype(out_dtype) if out_dtype else None, 1)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Fused attention over ``[B, T, H, D]`` q with ``[B, T, Hkv, D]``
    k/v, ``H % Hkv == 0`` — **GQA runs natively**: grouped K/V are read
    by index-map inside the kernel, never materialized per query head
    (an Hkv=H/4 model moves 4× less K/V through HBM than pre-tiling).
    Differentiable via custom VJP.

    Block sizes default by SHAPE (``_default_blocks``): sequence-
    spanning tiles through seq 4096, 512×1024 beyond (measured on v5e
    at seq 8192, with the causal block skip, 512×1024 is fastest for
    BOTH forward and fwd+bwd — 1.6× the old 128×128 tiles, whose grid
    overhead dwarfs their cache friendliness). Pass explicit values to
    override."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, t, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv or v.shape[2] != hkv:
        raise ValueError(
            f"q heads ({h}) must be a multiple of kv heads ({hkv}); "
            f"v has {v.shape[2]}")

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], t, d)

    dq, dk = _default_blocks(t)
    out = _flash(to_bh(q), to_bh(k), to_bh(v), float(scale), causal,
                 dq if block_q is None else block_q,
                 dk if block_k is None else block_k, interpret, h // hkv)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
