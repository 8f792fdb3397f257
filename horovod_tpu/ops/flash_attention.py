"""Flash attention — Pallas TPU kernels for the hot op.

The reference has no device kernels of its own (it drives NCCL); on
TPU the framework's hot op is attention, and this module implements it
as **fused Pallas kernels**: online-softmax over KV blocks so the
(T, T) score matrix never materializes in HBM — scores live in VMEM a
block at a time and the MXU sees two big matmuls per block. Forward
(``hvd_flash_fwd``) saves the per-row logsumexp; backward recomputes
probabilities from it (the standard memory-for-FLOPs trade), also a
tile at a time in VMEM, in two kernels: ``hvd_flash_bwd_dkv`` (a kv
block's dK and dV, summed over its group's query heads) and
``hvd_flash_bwd_dq``. One backward serves every caller: both entry
points, any length, GQA, causal or not.

Used via ``TransformerConfig(sp_attention="flash")`` or directly:

    out = flash_attention(q, k, v, causal=True)   # [B, T, H, D] each

On CPU (tests, the virtual mesh) the kernels run in Pallas interpret
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
                seq_len: int, window: Optional[int] = None):
    """One (batch*head, q-block, kv-block) grid step of the online
    softmax. Scratch (acc, m, l) persists across the kv dimension.
    With a ``window`` the kv dimension of the grid holds only as many
    steps as a q block has kv blocks that are not wholly behind it, and
    step j is the j-th of them (``_fwd_first_kv``)."""
    qi = pl.program_id(1)
    step = ki = pl.program_id(2)
    if window is not None:
        ki = _fwd_first_kv(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
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
        if window is not None:
            mask = mask | (k_pos <= q_pos - window)
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

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l > 0, l, 1.0)        # fully-masked (pad) rows
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(safe_l))[:, 0]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _fwd_first_kv(qi, block_q: int, block_k: int, window: int):
    """The first kv block that holds a key some query of q block ``qi``
    sees through the window (``qi`` a Python or a traced integer): the
    block of the key ``window - 1`` before the q block's first query."""
    first_key = qi * block_q - window + 1
    if isinstance(qi, int):
        return max(first_key, 0) // block_k
    return jnp.maximum(first_key, 0) // block_k


def _default_blocks(t: int, window: Optional[int] = None):
    """Shape-derived tile sizes. Sequence-spanning blocks win through
    medium sequence — grid overhead dominates small tiles (1024×1024
    at seq 1024 measures 61.6% vs 53.3% MFU for 128×128 on v5e,
    d=2048×8L) — while 512×1024 wins from ~4k up (measured at seq 8192
    for both forward and fwd+bwd). Capped at 1024: a 2048×2048 tile's
    f32 scores need 21 MiB of scoped VMEM at seq 4096 against the v5e's
    16 MiB default (Mosaic refuses it); at seq 2048 it compiles, and
    whether it would be faster there is not measured.

    With a ``window`` (shorter than ``t``: the callers drop one that is
    not): square blocks of the window's size, so that a q block's grid
    is two kv blocks, up to the same 1024. On the v5e at ``[32, 8192,
    128]`` bf16 with window 1024, ms a forward: 1024x1024 **3.59**,
    512x1024 3.71, 512x512 4.25, 256x512 4.55, 1024x512 5.30, 256x256
    6.39 (6.53 without a window): fewer and larger steps win again,
    though half of each of the two blocks is masked."""
    if window is not None:
        b = min(1024, _round_up(window, 128))
        return b, b
    if t <= 4096:
        b = min(1024, _round_up(t, 128))
        return b, b
    return 512, 1024


def _fwd(q, k, v, *, scale, causal, block_q, block_k, interpret,
         out_dtype=None, q_per_kv: int = 1, window: Optional[int] = None):
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

    def kv_block(i, j):
        return j

    if window is not None:
        # Only the kv blocks between the window's edge and the diagonal
        # are steps of the grid: what lies wholly behind the window is
        # neither fetched nor computed, and costs no grid step. A step
        # past the diagonal (a q block near the start sees fewer blocks)
        # names the diagonal's block again, which is not fetched twice.
        def first(i):
            return _fwd_first_kv(i, bq, bk, window)

        def last(i):
            return ((i + 1) * bq - 1) // bk

        grid = grid[:2] + (max(last(i) - first(i) + 1
                               for i in range(tp // bq)),)
        kernel = functools.partial(kernel, window=window)

        def kv_block(i, j):
            return jnp.minimum(first(i) + j, last(i))

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (b // q_per_kv, kv_block(i, j), 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (b // q_per_kv, kv_block(i, j), 0)),
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
        # The kernel's name in a device trace (PERF.md §3); the
        # backward's two begin "hvd_flash_bwd".
        name="hvd_flash_fwd",
    )(q, k, v)
    return out[:, :t], lse[:, 0, :t]


def _keys_fwd_kernel(q_hi_ref, q_lo_ref, k_lo_ref, k_hi_ref, *rest,
                     scale: float, window: Optional[int], heads: int,
                     carried: bool, page: Optional[int] = None,
                     q_per_kv: int = 1):
    """One (batch*head, q-block, kv-block) grid step of the forward over
    keys that carry their positions (:func:`flash_attention_keys`): the
    online softmax of :func:`_fwd_kernel` with the mask read from the
    two position tiles (``qp`` [bq, 1], ``kp`` [1, bk]) instead of the
    grid's indices, the operands in their own dtype and the value as
    wide as it is. The four prefetched arrays hold the lowest and the
    highest position of every q and kv tile, a position group after
    another: a tile pair with no visible (query, key) computes
    nothing. ``carried``: two more inputs, the ``(out, lse)`` of the
    same queries over earlier keys, from which the softmax runs on
    (``acc = out``, ``m = lse``, ``l = 1`` is that state). ``page``: a
    mask by page and query besides. Two more prefetched tables a (KV
    row, q tile), the last kv tile in which the q tile computes
    anything (the index maps' clamp, not read here) and, a kv tile
    each, whether some query of the q tile was given some page of it;
    and one more input, ``bits`` [bq, 1]: bit ``i`` of a query's int32
    is page ``i`` of this kv tile."""
    if page is not None:
        _, given_ref, *rest = rest
    q_ref, k_ref, v_ref, qp_ref, kp_ref, *rest = rest
    if page is not None:
        bits_ref, *rest = rest
    if carried:
        o_in_ref, lse_in_ref, o_ref, lse_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, lse_ref, acc, m_scr, l_scr = rest
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    g = b // heads
    q_tile = g * pl.num_programs(1) + qi
    k_tile = g * pl.num_programs(2) + ki

    @pl.when(ki == 0)
    def _init():
        if carried:
            acc[:] = o_in_ref[0]
            m_scr[:] = lse_in_ref[0, 0][:, None]
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            acc[:] = jnp.zeros_like(acc)
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)

    visible = (k_lo_ref[k_tile] <= q_hi_ref[q_tile]) & (k_hi_ref[k_tile] >= 0)
    if window is not None:
        visible &= k_hi_ref[k_tile] > q_lo_ref[q_tile] - window
    if page is not None:
        visible &= given_ref[((b // q_per_kv) * pl.num_programs(1) + qi)
                             * pl.num_programs(2) + ki] != 0

    @pl.when(visible)
    def _body():
        v = v_ref[0]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp, kp = qp_ref[0], kp_ref[0]                # [bq, 1], [1, bk]
        seen = (kp >= 0) & (kp <= qp)
        if window is not None:
            seen &= kp > qp - window
        if page is not None:
            of_page = jax.lax.broadcasted_iota(
                jnp.int32, kp.shape, 1) // page
            seen &= (jax.lax.shift_right_logical(bits_ref[0], of_page)
                     & 1) != 0
        s = jnp.where(seen, s, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + p.sum(axis=1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        # A row that saw no key: l is 0 where every tile of it was
        # skipped and the count of the masked keys where one was not
        # (exp(NEG_INF - NEG_INF)); zeros either way, and NEG_INF + log l
        # is NEG_INF in float32.
        l, m = l_scr[:], m_scr[:]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = jnp.where(m > NEG_INF, acc[:] / safe_l,
                             0.0).astype(o_ref.dtype)
        lse_ref[0, 0] = (m + jnp.log(safe_l))[:, 0]


def _keys_blocks(c: int, k: int):
    """(q tile, kv tile) of :func:`flash_attention_keys` from the
    lengths: a chunk's queries as one tile up to 1024, kv tiles of 1024.
    On the v5e (2026-09-30, ``tools/prefill_attn_sweep.py --latent``:
    ``[64, C, 192]`` bf16 queries of a chunk that ends at key 8192,
    values 128 wide, key blocks of 1024 a call, ms a layer with the
    expansion of the latents): C = 1024 at 1024x1024 **5.02**, 512x1024
    5.15, 256x1024 5.53, 1024x512 6.60, 512x512 6.74; C = 256 at
    256x1024 2.03, at x512 2.42-2.45. Fewer and larger steps win, as
    in :func:`_default_blocks`; a 1024x1024 float32 score tile beside
    192-wide operands fits the 16 MiB of scoped VMEM. The kernel alone
    takes 0.55 ms a call at ``[64, 1024, 192]`` over 1024 keys and 0.31
    at 32 heads: about 75 us a call and 7.5 us a head and tile, 45 %
    of the matrix unit's peak on a tile, where :func:`_fwd_kernel`
    stands; leaving the mask out of the tiles that need none moved it
    by 1 % and was not kept.

    Under a page mask (2026-10-02, ``tools/prefill_attn_sweep.py
    --sparse``: ``[32, 1024, 128]`` bf16 queries over 2 KV heads' keys
    in pages of 64, a chunk that ends at key 8192 / 16 384 / 32 768,
    every query 64 pages of its own choice, ms a layer with both
    gathers): 1024x1024 **1.42 / 2.50 / 4.55**, 512x1024 1.61 / 2.72 /
    4.89, 1024x512 1.90 / 3.22 / 5.83, 512x512 2.03 / 3.24 / 5.58: the
    same order. A KV head a grid row with its 16 heads' queries
    interleaved (q tiles of 64 queries) took 3.34 / 4.38 / 6.46: the
    tiles are the same, the mask repeated 16 times in XLA is not. With
    the index maps' clamp off 1.42 / 2.50 / 4.55 and with the skip by
    choice off 1.42 / 2.51 / 4.56: neither costs or gives anything
    where every tile holds some query's page; where a chunk's queries
    agree on their 64 pages, 89 % of the causal tile pairs at 32 768
    keys are computed in 4.11."""
    return min(1024, _round_up(c, 128)), min(1024, _round_up(k, 128))


def _page_bits(page_mask, cp: int, n_k: int, per: int):
    """``page_mask`` [R, C, P] bool as one int32 a (row, kv tile,
    query), [R, n_k, cp]: bit ``i`` is page ``i`` of the kv tile's
    ``per``. A padded query and a page past ``P`` have no bit."""
    r, c, p = page_mask.shape
    mask = jnp.pad(page_mask, ((0, 0), (0, cp - c), (0, n_k * per - p)))
    bits = jnp.left_shift(mask.reshape(r, cp, n_k, per).astype(jnp.int32),
                          jnp.arange(per, dtype=jnp.int32)).sum(-1)
    return jnp.moveaxis(bits, 2, 1)


def _page_tables(bits, bounds, bq: int, window: Optional[int]):
    """The two prefetched tables of a masked call from ``bits``
    [R, n_k, cp] and the tiles' position ``bounds`` (a group after
    another; ``R`` whole groups): ``last`` [R * n_q], the last kv tile
    in which a q tile computes anything (0 where it computes nothing),
    and ``given`` [R * n_q * n_k], whether the tile pair holds a query
    that may see a key by position and some query that was given some
    page of the kv tile."""
    r, n_k, cp = bits.shape
    n_q = cp // bq
    q_hi, q_lo, k_lo, k_hi = (
        jnp.repeat(a.reshape(-1, n), r // (a.size // n), 0)
        for a, n in zip(bounds, (n_q, n_q, n_k, n_k)))
    given = (k_lo[:, None] <= q_hi[..., None]) & (k_hi[:, None] >= 0)
    if window is not None:
        given &= k_hi[:, None] > q_lo[..., None] - window
    given &= jnp.moveaxis(
        (bits.reshape(r, n_k, n_q, bq) != 0).any(-1), 1, 2)
    last = jnp.where(given, jnp.arange(n_k, dtype=jnp.int32), 0).max(-1)
    return last.reshape(-1), given.reshape(-1).astype(jnp.int32)


def flash_attention_keys(q, k, v, q_pos, k_pos, *, scale: float,
                         window: Optional[int] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         carry=None, page_mask=None,
                         page: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """The flash forward over keys that carry their positions: ``q``
    ``[BH, C, Dk]`` at positions ``q_pos`` ``[G, C]`` over ``k``
    ``[BHkv, K, Dk]`` and ``v`` ``[BHkv, K, Dv]`` at ``k_pos``
    ``[G, K]`` (int32, traced; ``G`` divides ``BH``, head-major groups
    as ``[B, H]`` flattens; ``BHkv`` divides ``BH``, GQA by index map).
    A key is seen by a query where ``0 <= k_pos <= q_pos`` and, with a
    ``window``, ``k_pos > q_pos - window``: a negative position is a
    key that is not there. ``C != K``, ``Dv != Dk`` and positions in any
    order are all fine; nothing is assumed of them but what the mask
    says. Returns ``(out [BH, C, Dv], lse [BH, C])`` in float32, a row
    that saw no key as zeros at ``lse = NEG_INF``. ``carry``: the
    ``(out, lse)`` of the same queries over OTHER keys (an earlier
    call's): the running softmax starts from it (``acc = out``, ``m =
    lse``, ``l = 1`` is that state) and the result is the attention
    over both sets of keys, the carried pair updated where it lies.
    That is how a caller attends a key block a call with no pass over
    the result between the calls (merging two results by their
    logsumexp outside the kernel took 5.23 ms a layer where carrying
    takes 4.98, ``_keys_blocks``' sweep).

    ``page_mask`` ``[BHkv, C, P]`` bool with ``page``: the keys lie in
    pages of ``page`` (key ``n`` in page ``n // page``; a page past
    the ``P`` given is seen by nobody), and query ``c`` of every head of KV row ``r`` sees a key only where
    ``page_mask[r, c]`` has its page besides (a sparse layer's chunk:
    ``serve/decode.py::sparse_attend_pages``). The kv tile must be whole
    pages, 32 at most: a (KV row, kv tile, query) is one int32 with a
    bit a page, a ``[bq, 1]`` tile beside the queries' positions, and
    the mask inside the tile is a shift, an and and a compare. A tile
    pair in which no query was given a page is skipped like one with no
    visible pair, and the K/V index maps stop at the last kv tile a q
    tile computes (a repeated block is not fetched again), so that
    keys past what the call sees cost nothing (:func:`_page_tables`).
    ``BHkv`` must be whole position groups. With ``page_mask=None`` the
    call is the one it was, operand for operand.

    Scores, softmax statistics and the accumulator are float32 tiles in
    VMEM; the two dots take the operands in their own dtype and ``p``
    rounded to the value's. Tile pairs with no visible pair are skipped
    by the tiles' lowest and highest positions, which the wrapper
    computes and the kernel reads from SMEM. Forward only (the serve
    programs' kernel; ``hvd_flash_keys_fwd`` in a device trace)."""
    bh, c, dk = q.shape
    bkv, n_keys, dv = v.shape
    groups = q_pos.shape[0]
    if bh % groups or bh % bkv or k.shape != (bkv, n_keys, dk) or (
            q_pos.shape, k_pos.shape) != ((groups, c), (groups, n_keys)):
        raise ValueError(
            f"flash_attention_keys: q {q.shape}, k {k.shape}, v {v.shape}, "
            f"q_pos {q_pos.shape}, k_pos {k_pos.shape}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    tile_q, tile_k = _keys_blocks(c, n_keys)
    bq = min(tile_q if block_q is None else block_q, _round_up(c, 128))
    bk = min(tile_k if block_k is None else block_k, _round_up(n_keys, 128))
    cp, kp = _round_up(c, bq), _round_up(n_keys, bk)
    q = jnp.pad(q, ((0, 0), (0, cp - c), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, kp - n_keys), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, kp - n_keys), (0, 0)))
    # padded queries see nothing, padded keys are not there
    q_pos = jnp.pad(q_pos.astype(jnp.int32), ((0, 0), (0, cp - c)),
                    constant_values=-1)
    k_pos = jnp.pad(k_pos.astype(jnp.int32), ((0, 0), (0, kp - n_keys)),
                    constant_values=-1)
    q_tiles = q_pos.reshape(groups, cp // bq, bq)
    k_tiles = k_pos.reshape(groups, kp // bk, bk)
    bounds = (q_tiles.max(-1).reshape(-1), q_tiles.min(-1).reshape(-1),
              k_tiles.min(-1).reshape(-1), k_tiles.max(-1).reshape(-1))
    heads, q_per_kv = bh // groups, bh // bkv
    out_spec = pl.BlockSpec((1, bq, dv), lambda b, i, j, *_: (b, i, 0))
    lse_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j, *_: (b, 0, i))
    carried = [] if carry is None else [
        jnp.pad(carry[0], ((0, 0), (0, cp - c), (0, 0))),
        jnp.pad(carry[1], ((0, 0), (0, cp - c)),
                constant_values=NEG_INF)[:, None, :]]
    n_q, n_k = cp // bq, kp // bk

    def kv_tile(b, i, j, *_):
        return j

    tables, masked, bits_spec = (), [], []
    if page_mask is not None:
        if (page is None or bk % page or bk // page > 32 or bkv % groups
                or page_mask.shape[:2] != (bkv, c)
                or page_mask.shape[2] * page > kp):
            raise ValueError(
                f"flash_attention_keys: page_mask {page_mask.shape} with "
                f"pages of {page} for q {q.shape}, k {k.shape} in "
                f"{groups} position groups and kv tiles of {bk}")
        bits = _page_bits(page_mask, cp, n_k, bk // page)
        tables = _page_tables(bits, bounds, bq, window)
        masked = [bits.reshape(bkv * n_k, cp, 1)]

        def kv_tile(b, i, j, *pre):
            # pre[4]: the last kv tile a (KV row, q tile) computes
            return jnp.minimum(j, pre[4][(b // q_per_kv) * n_q + i])

        bits_spec = [pl.BlockSpec(
            (1, bq, 1), lambda b, i, j, *pre: (
                (b // q_per_kv) * n_k + kv_tile(b, i, j, *pre), i, 0))]

    inputs = (*bounds, *tables, q, k, v, q_pos[:, :, None],
              k_pos[:, None, :], *masked, *carried)
    out, lse = pl.pallas_call(
        functools.partial(_keys_fwd_kernel, scale=float(scale),
                          window=window, heads=heads,
                          carried=carry is not None,
                          page=page if masked else None, q_per_kv=q_per_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 + len(tables),
            grid=(bh, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, bq, dk), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, bk, dk), lambda b, i, j, *pre: (
                    b // q_per_kv, kv_tile(b, i, j, *pre), 0)),
                pl.BlockSpec((1, bk, dv), lambda b, i, j, *pre: (
                    b // q_per_kv, kv_tile(b, i, j, *pre), 0)),
                pl.BlockSpec((1, bq, 1),
                             lambda b, i, j, *_: (b // heads, i, 0)),
                pl.BlockSpec((1, 1, bk), lambda b, i, j, *pre: (
                    b // heads, 0, kv_tile(b, i, j, *pre))),
            ] + bits_spec + ([out_spec, lse_spec] if carried else []),
            out_specs=[out_spec, lse_spec],
            scratch_shapes=[
                pltpu.VMEM((bq, dv), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, cp, dv), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, cp), jnp.float32),
        ],
        # the carried pair is updated where it lies (the last two inputs,
        # behind the prefetched arrays and the operands)
        input_output_aliases=(
            {len(inputs) - 2: 0, len(inputs) - 1: 1} if carried else {}),
        interpret=interpret,
        name="hvd_flash_keys_fwd",
    )(*inputs)
    return out[:, :c], lse[:, 0, :c]


def _bwd_blocks(t: int, d: int, itemsize: int):
    """The backward's (block, sub) from the shapes: square blocks of
    ``block`` query rows by ``block`` kv rows a grid step, the diagonal
    ones computed as ``sub``-sized tiles (``_bwd_visit``).

    Chosen on the v5e at the training cells' ``[32, 4096, 128]`` bf16,
    ms a backward (both kernels and delta; XLA's einsums took 13.9):
    block 512 4.06, 1024 3.64, 1024 with sub 512 **3.34** (256: 3.44,
    128: 3.57; the dq kernel likes 512, the dkv kernel 128-256, one
    value for both); rectangular 512x1024 / 1024x512 3.9-4.0; 2048 does
    not fit. Large blocks win as in the forward: fewer grid steps, and
    the MXU ran at 85 % of its peak on what a 1024 block executes.
    What fits is set by the row blocks' bytes beside the float32
    ``block x block`` tiles (scores, dP, dS) live at once: the v5e
    compiler's 16 MiB of scoped VMEM admit 1024 rows of 128 in bf16 or
    float32 and of 256 in bf16, and refuse 256 in float32, 512 in bf16
    and any 2048 (compile-only): 512 KiB a row block, halving ``block``
    past it. A power of two, so that ``sub`` divides it on a lane
    boundary.

    A window changes neither: at ``[32, 8192, 128]`` with window 1024,
    forward + backward took 8.70 ms at sub 512, 9.10 at 256 and 9.59 at
    128 (the edge block is tiled as the diagonal one is)."""
    block = 1024
    while block > 128 and block * d * itemsize > 512 * 1024:
        block //= 2
    block = min(block, 1 << (_round_up(t, 128).bit_length() - 1))
    return block, min(512, block)


def _bwd_tile(q, k, v, do, lse, delta, *, scale, hidden=None):
    """One tile of the backward, TRANSPOSED (kv rows down, query rows
    across): the probabilities ``P^T`` recomputed from the saved
    log-sum-exp, and ``P^T * (dP^T - delta)``, which is dS^T but for
    the factor ``scale`` that the callers apply once to their
    accumulators; both float32. In this orientation the per-query
    ``lse`` and ``delta`` rows ([1, queries], the forward's layout)
    broadcast down the sublanes as they lie, and dV = P^T dO, dK = dS^T Q
    are plain matmuls. ``hidden(kv row, query column)`` says which
    entries of the tile are masked to probability 0."""
    nt = (((1,), (1,)), ((), ()))
    st = jax.lax.dot_general(
        k, q, nt, preferred_element_type=jnp.float32) * scale
    if hidden is not None:
        st = jnp.where(
            hidden(jax.lax.broadcasted_iota(jnp.int32, st.shape, 0),
                   jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)),
            NEG_INF, st)
    pt = jnp.exp(st - lse)
    wide = jnp.promote_types(v.dtype, do.dtype)
    dpt = jax.lax.dot_general(v.astype(wide), do.astype(wide), nt,
                              preferred_element_type=jnp.float32)
    return pt, pt * (dpt - delta)


def _bwd_tiles(below: int, block: int, sub: int, window: Optional[int]):
    """What a causal backward computes of the square block ``below``
    blocks under the diagonal (0: the one on it): a list of ``(q rows,
    kv rows, hidden)`` for :func:`_bwd_tile`. A block with every pair
    visible is one entry, whole and unmasked; a block that the diagonal
    or the window's edge crosses is its ``sub``-sized tiles, those with
    no visible pair left out and only those that an edge crosses
    masked (so the MXU does 3/4 or 5/8 of such a block and not all of
    it). Key c of the block is hidden from its query a above the
    diagonal, ``c > a + below * block``, and behind the window, ``c <=
    a + below * block - window``; ``hidden`` takes the kv row and the
    query column inside a tile."""
    above = below * block                       # c > a + above: not yet
    behind = None if window is None else below * block - window
    if block - 1 <= above and (behind is None or block - 1 + behind < 0):
        return [(slice(None), slice(None), None)]

    def rows_over(d):                           # kv rows > query col + d
        return (lambda row, col: row > col) if d == 0 else (
            lambda row, col: row > col + d)

    def rows_upto(d):                           # kv rows <= query col + d
        return lambda row, col: row <= col + d

    tiles = []
    for a in range(0, block, sub):
        for c in range(0, block, sub):
            if c > a + sub - 1 + above or (
                    behind is not None and c + sub - 1 <= a + behind):
                continue
            masks = []
            if c + sub - 1 > a + above:
                masks.append(rows_over(a + above - c))
            if behind is not None and c <= a + sub - 1 + behind:
                masks.append(rows_upto(a + behind - c))
            hidden = None
            if len(masks) == 1:
                hidden = masks[0]
            elif masks:
                hidden = (lambda m: lambda row, col: m[0](row, col)
                          | m[1](row, col))(masks)
            tiles.append((pl.ds(a, sub), pl.ds(c, sub), hidden))
    return tiles


def _bwd_blocks_below(block: int, window: int) -> int:
    """How many blocks under the diagonal hold a pair inside the
    window: the block ``below`` is wholly behind it once ``window <=
    (below - 1) * block + 1``."""
    return (window - 2) // block + 1


def _bwd_visit(add, qi, ki, *, causal, block, sub, n_k, valid, window=None):
    """What one (q block ``qi``, kv block ``ki``) grid step computes:
    ``add(q rows, kv rows, hidden)`` for the parts of the square block
    that hold a visible (query, key) pair (:func:`_bwd_tiles`). Causal:
    a block below the diagonal whole and unmasked, the block on the
    diagonal as tiles, a block above it nothing, the forward's rule.
    With a ``window`` the grid only holds steps from the diagonal down
    to the last block the window reaches, each whole or as tiles by its
    distance from the diagonal; a step that falls off the sequence
    computes nothing. ``valid`` rows of the last kv block are keys and
    the rest padding, which a causal mask hides from every real query;
    without one they are masked by count."""
    whole = slice(None)
    if not causal:
        if valid == block:
            add(whole, whole, None)
        else:
            pl.when(ki != n_k - 1)(lambda: add(whole, whole, None))
            pl.when(ki == n_k - 1)(
                lambda: add(whole, whole, lambda row, col: row >= valid))
        return
    if window is None:
        pl.when(ki < qi)(lambda: add(whole, whole, None))
        cases = [(0, ki == qi)]
    else:
        on_sequence = (ki >= 0) & (qi < n_k)
        cases = [(below, (qi - ki == below) & on_sequence)
                 for below in range(_bwd_blocks_below(block, window) + 1)]
    for below, here in cases:
        @pl.when(here)
        def _tiles(below=below):
            for qs, ks, hidden in _bwd_tiles(below, block, sub, window):
                add(qs, ks, hidden)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, n_q, scale, **where):
    """One (batch*kv-head, kv-block, group-head x q-block) grid step:
    the kv block's dK and dV accumulate in float32 scratch over every
    query head of its group and every q block that sees it (``n_q`` a
    head: all of them, or with a window those from the diagonal down to
    its edge), and are written once. The GQA sum over the group happens
    here."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def add(qs, ks, hidden):
        q, do = q_ref[0, qs], do_ref[0, qs]
        pt, dst = _bwd_tile(q, k_ref[0, ks], v_ref[0, ks], do,
                            lse_ref[0, :, qs], delta_ref[0, :, qs],
                            scale=scale, hidden=hidden)
        dv_acc[ks] += jax.lax.dot(pt.astype(do.dtype), do,
                                  preferred_element_type=jnp.float32)
        dk_acc[ks] += jax.lax.dot(dst.astype(q.dtype), q,
                                  preferred_element_type=jnp.float32)

    qi, ki = j % n_q, pl.program_id(1)
    if where["window"] is not None:
        qi = ki + qi
    _bwd_visit(add, qi, ki, **where)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, **where):
    """One (batch*head, q-block, kv-block) grid step: the q block's dQ
    = dS K accumulates in float32 scratch over the kv blocks it sees
    (with a window the steps are the blocks from its edge up to the
    diagonal)."""
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def add(qs, ks, hidden):
        k = k_ref[0, ks]
        _, dst = _bwd_tile(q_ref[0, qs], k, v_ref[0, ks], do_ref[0, qs],
                           lse_ref[0, :, qs], delta_ref[0, :, qs],
                           scale=scale, hidden=hidden)
        # dS^T is [keys, queries]: contract the keys
        dq_acc[qs] += jax.lax.dot_general(
            dst.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    qi, ki = pl.program_id(1), step
    if where["window"] is not None:
        ki = qi - (pl.num_programs(2) - 1) + step
    _bwd_visit(add, qi, ki, **where)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _backward(scale, causal, interpret, q_per_kv, residuals, g,
              g_lse=None, window=None):
    """dq, dk, dv from the saved log-sum-exp, as two Pallas kernels that
    recompute the probabilities a tile at a time in VMEM: nothing of
    size [.., T, T] exists in HBM at any length, blocks above the
    diagonal are skipped, the MXU takes the inputs' dtype and
    accumulates in float32. ``g_lse`` is the log-sum-exp's cotangent
    when the caller consumed it (ring attention's block merge): since
    d lse / dS = P, it enters as dS = P * (dP - (delta - g_lse)) * scale
    and both entry points share the kernels."""
    q, k, v, out, lse = residuals
    bh, t, d = q.shape
    block, sub = _bwd_blocks(t, d, max(q.dtype.itemsize, g.dtype.itemsize))
    tp = _round_up(t, block)
    n = tp // block
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)

    def rows(x):     # [.., t, d], zero rows up to tp
        return jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))

    def stat(x):     # [bh, t] -> the forward's (bh, 1, tp) layout
        return jnp.pad(x, ((0, 0), (0, tp - t)))[:, None, :]

    args = (rows(q), rows(k), rows(v), rows(g), stat(lse), stat(delta))
    where = dict(scale=scale, causal=causal, block=block, sub=sub, n_k=n,
                 valid=t - (n - 1) * block, window=window)
    # With a window the steps are the blocks between the diagonal and
    # the window's edge alone: what lies wholly behind it is no step of
    # either grid. ``m`` steps a head (dkv) or a q block (dq).
    m = n if window is None else min(n, _bwd_blocks_below(block, window) + 1)

    # A causal step above the diagonal computes nothing, nor does a
    # windowed one that falls off the sequence: it names the block of
    # the nearest step that does, which is then not fetched again
    # (3.84 -> 3.71 ms a backward at the cells' shape, v5e).
    def q_seeing(qi, ki):     # dkv: the first q block to see kv block ki
        if window is not None:              # qi counts from the diagonal
            return jnp.minimum(ki + qi, n - 1)
        return jnp.maximum(qi, ki) if causal else qi

    def kv_seen(ki, qi):      # dq: the last kv block q block qi sees
        if window is not None:              # ki counts from the edge
            return jnp.maximum(qi - (m - 1) + ki, 0)
        return jnp.minimum(ki, qi) if causal else ki

    # kv block outermost; the group's heads and their q blocks reduce.
    q_rows = pl.BlockSpec(
        (1, block, d),
        lambda b, i, j: (b * q_per_kv + j // m, q_seeing(j % m, i), 0))
    q_stat = pl.BlockSpec(
        (1, 1, block),
        lambda b, i, j: (b * q_per_kv + j // m, 0, q_seeing(j % m, i)))
    kv_rows = pl.BlockSpec((1, block, d), lambda b, i, j: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=m, **where),
        grid=(bh // q_per_kv, n, q_per_kv * m),
        in_specs=[q_rows, kv_rows, kv_rows, q_rows, q_stat, q_stat],
        out_specs=[kv_rows, kv_rows],
        out_shape=[jax.ShapeDtypeStruct((k.shape[0], tp, d), k.dtype),
                   jax.ShapeDtypeStruct((v.shape[0], tp, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        interpret=interpret,
        name="hvd_flash_bwd_dkv",
    )(*args)

    q_rows = pl.BlockSpec((1, block, d), lambda b, i, j: (b, i, 0))
    q_stat = pl.BlockSpec((1, 1, block), lambda b, i, j: (b, 0, i))
    kv_rows = pl.BlockSpec(
        (1, block, d), lambda b, i, j: (b // q_per_kv, kv_seen(j, i), 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **where),
        grid=(bh, n, m),
        in_specs=[q_rows, kv_rows, kv_rows, q_rows, q_stat, q_stat],
        out_specs=q_rows,
        out_shape=jax.ShapeDtypeStruct((bh, tp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        interpret=interpret,
        name="hvd_flash_bwd_dq",
    )(*args)
    return dq[:, :t], dk[:, :t], dv[:, :t]


def _checked_window(window: Optional[int], causal: bool, t: int):
    """``window`` as the kernels take it: None where it hides nothing."""
    if window is None:
        return None
    if not causal:
        raise NotImplementedError(
            "flash attention with a window and causal=False: the window "
            "is the causal one, p - window < j <= p; no kernel here hides "
            "keys ahead of a window's end without hiding all keys ahead")
    if window < 1:
        raise ValueError(f"attention window {window} holds no key")
    return None if window >= t else int(window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, q_per_kv,
           window):
    out, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret, q_per_kv=q_per_kv,
                  window=window)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               q_per_kv, window):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret,
                    q_per_kv=q_per_kv, window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, q_per_kv, window,
               residuals, g):
    with jax.named_scope("flash_bwd"):
        return _backward(scale, causal, interpret, q_per_kv, residuals, g,
                         window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_lse(q, k, v, scale, causal, block_q, block_k, interpret,
               out_dtype, q_per_kv, window):
    return _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret, out_dtype=out_dtype,
                q_per_kv=q_per_kv, window=window)


def _flash_lse_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                   out_dtype, q_per_kv, window):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret,
                    out_dtype=out_dtype, q_per_kv=q_per_kv, window=window)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(scale, causal, block_q, block_k, interpret, out_dtype,
                   q_per_kv, window, residuals, g):
    g_out, g_lse = g
    with jax.named_scope("flash_bwd"):
        return _backward(scale, causal, interpret, q_per_kv, residuals,
                         g_out, g_lse, window=window)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(q, k, v, *, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: Optional[bool] = None,
                             out_dtype=None, window: Optional[int] = None):
    """``[BH, T, D]``-layout flash attention returning ``(out, lse)``
    — the building block for blockwise composition (ring attention
    merges per-chunk results by logsumexp weighting). Differentiable
    in both outputs. ``out_dtype=jnp.float32`` keeps chunk outputs at
    merge precision (callers that round once at the end). ``window``
    as :func:`flash_attention` has it."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    window = _checked_window(window, causal, q.shape[1])
    dq, dk = _default_blocks(q.shape[1], window)
    return _flash_lse(q, k, v, float(scale), causal,
                      dq if block_q is None else block_q,
                      dk if block_k is None else block_k, interpret,
                      jnp.dtype(out_dtype) if out_dtype else None, 1, window)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
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
    override.

    ``window`` (causal only): a query at p sees the keys j with
    ``p - window < j <= p``. Blocks wholly behind the window are
    neither fetched nor computed, forward and backward: the work is
    the window's pairs and not the triangle's. ``None``, or a window
    that reaches the row's start from its end, is plain causal
    attention, the same kernels instruction for instruction."""
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

    window = _checked_window(window, causal, t)
    dq, dk = _default_blocks(t, window)
    out = _flash(to_bh(q), to_bh(k), to_bh(v), float(scale), causal,
                 dq if block_q is None else block_q,
                 dk if block_k is None else block_k, interpret, h // hkv,
                 window)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
