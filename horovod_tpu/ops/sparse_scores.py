"""A sparse layer's block scores for a chunk of queries, made on the chip.

One Pallas call a layer (``hvd_sparse_scores`` in a device trace). A
query head's scores over the compressed keys, their softmax and its
pooling by block never reach HBM: a grid step holds one head's
``[q tile, pages]`` float32 products a kernel of a page (a *plane*),
keeps the softmax's running maximum and sum over the kernels a query
sees and the largest raw score of every block, and at the head's end
adds ``exp(largest - maximum) / sum`` into the GQA group's
``[q tile, table]`` sums: the largest softmax score over a block's
kernels IS that, element for element. Key tiles past the chunk's last
complete kernel are neither read nor computed. The sums leave the chip
once a group, transposed (the queries in the lanes): the layout in which
the top-k behind the call sorts a chunk's queries together. The XLA form
it replaces
for a chunk (``serve/decode.py::sparse_block_scores``: a float32
``[Hkv, H / Hkv, C, J]`` array over EVERY kernel of the table, passed
over ten times in HBM) stays as a decode step's form, as the tests'
reference and as the sweep's baseline.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.flash_attention import NEG_INF, _round_up


def q_tile(chunk: int, table_width: int) -> Optional[int]:
    """The queries a grid step holds for a chunk of ``chunk`` positions
    over a table of ``table_width`` pages, or None where the chunk is
    not whole tiles: the first of ``_Q_TILES`` that divides the chunk
    and whose output block, twice, blocks' maxima and group's sums
    (float32 ``[tile, table]`` each) fit ``_HELD_BYTES`` of fast
    memory. By the shapes alone, here and on a TPU."""
    pages = _round_up(table_width, _PAGES)
    return next((tile for tile in _Q_TILES if chunk % tile == 0
                 and 4 * 4 * tile * pages <= _HELD_BYTES), None)


def _kernel(pre_ref, q_ref, planes_ref, o_ref, m_scr, l_scr, best_scr,
            sum_scr, *, scale: float, per: int, strides: int, stride: int):
    """KV head ``g``, q tile ``i``, head ``r`` of the group, key tile
    ``t`` (grid ``(g, i, r, t)``, ``t`` fastest): ``sum_scr`` is the
    group's ``[tq, table]`` scores, summed over ``r``; ``o_ref``
    ``[table, tq]`` takes them at the group's end, the queries in the
    lanes (:func:`_scores` says why). ``planes_ref`` [per + strides - 1, pages, Dh]: plane ``u < per`` is
    kernel ``u`` of each page of the tile, plane ``per + n`` the kernel
    that starts ``n + 1`` strides before the page and reaches into it.
    Kernel ``u`` of page ``b`` spans ``[block b + stride u, .. + kernel)``
    and is seen by the queries at or past its last position; the
    prefetched ``(offset, last)`` are the chunk's first position and
    its last key tile with a kernel to see."""
    i, r, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    tq, pages = q_ref.shape[0], planes_ref.shape[1]
    offset, last = pre_ref[0], pre_ref[1]
    kernel, block = strides * stride, per * stride

    @pl.when((r == 0) & (t == 0))
    def _group():
        sum_scr[...] = jnp.zeros_like(sum_scr)

    @pl.when(t == 0)
    def _head():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(t <= last)
    def _tile():
        q = q_ref[...]
        pos = offset + i * tq + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        starts = block * (t * pages + lax.broadcasted_iota(
            jnp.int32, (1, pages), 1))

        def scores(plane, first, exists=None):
            s = lax.dot_general(q, planes_ref[plane], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            seen = starts <= pos - (first + kernel - 1)
            if exists is not None:
                seen &= exists
            return jnp.where(seen, s, NEG_INF)

        own = [scores(u, stride * u) for u in range(per)]
        best = functools.reduce(jnp.maximum, own)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, best.max(axis=1, keepdims=True))
        # a row that sees no kernel sums exp(0) here: `_pool` drops it
        l_scr[...] = jnp.exp(m_prev - m_new) * l_scr[...] + sum(
            jnp.exp(s - m_new) for s in own).sum(axis=1, keepdims=True)
        m_scr[...] = m_new
        for n in range(strides - 1):
            back = stride * (n + 1)
            best = jnp.maximum(best, scores(per + n, -back, starts >= back))
        best_scr[t] = best

    @pl.when(t == pl.num_programs(3) - 1)
    def _pool():
        m, l = m_scr[...], l_scr[...]
        sees = m > NEG_INF
        l = jnp.where(sees, l, 1.0)
        for tile in range(best_scr.shape[0]):
            @pl.when(tile <= last)
            def _add(tile=tile):
                sum_scr[tile] += jnp.where(
                    sees, jnp.exp(best_scr[tile] - m) / l, 0.0)

    @pl.when((r == pl.num_programs(2) - 1) & (t == pl.num_programs(3) - 1))
    def _out():
        for tile in range(best_scr.shape[0]):
            o_ref[tile * pages:(tile + 1) * pages, :] = sum_scr[tile].T


def sparse_scores(q, ck, offset, length, *, stride: int, kernel: int,
                  block_q: Optional[int] = None,
                  interpret: Optional[bool] = None):
    """What the queries of a chunk make of each block of keys: ``q``
    [C, H, Dh] at positions ``offset + [0, C)`` over the compressed keys
    ``ck`` [W, Hkv, per, Dh] of a table's W pages (kernel ``u`` of page
    ``b`` is the mean key of positions ``[per stride b + stride u, .. +
    kernel)``), ``offset`` and ``length`` traced int32. A head's scores
    are a softmax, over the kernels complete at the query's position,
    of ``q . c / sqrt(Dh)`` (the operands in their own dtype, a float32
    product); a block's is the largest over the kernels that meet it,
    summed over the heads of the GQA group in float32:
    ``serve/decode.py::sparse_block_scores`` value for value, but for
    the order of a softmax's sum. Returns [C, Hkv, W] float32.

    A query sees no kernel past ``offset + length - 1``: the rows from
    ``length`` on (a bucket's padding) read fewer kernels than their
    positions would and nobody reads them; a block no query of the
    chunk sees scores 0, and a row that sees no kernel 0 everywhere.

    ``block_q`` (a grid step's queries) is the sweep's; a program
    leaves it alone."""
    C, H, Dh = q.shape
    W, Hkv, per, _ = ck.shape
    if ck.shape[3] != Dh or H % Hkv or kernel % stride:
        raise ValueError(f"sparse_scores: q {q.shape}, ck {ck.shape}, "
                         f"kernels of {kernel} every {stride}")
    block_q = block_q or q_tile(C, W)
    if not block_q or C % block_q:
        raise ValueError(f"sparse_scores: a chunk of {C} queries over {W} "
                         f"pages in tiles of {block_q}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _scores(q, ck, offset, length, stride=stride, kernel=kernel,
                   block_q=block_q, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "stride", "kernel", "block_q", "interpret"))
def _scores(q, ck, offset, length, *, stride: int, kernel: int, block_q: int,
            interpret: bool):
    """The Pallas call, jitted of itself: a program's call sites (a
    sparse layer each) trace and lower the kernel once. Its output lies
    ``[Hkv, table, C]``: XLA gives a custom call's result the layout it
    is written in and the elementwise fusion behind it the same, and
    the top-k's sort of 520 blocks runs over whatever lies in the lanes
    at once (on the v5e 0.4 ms a layer for a chunk of 1024 queries
    there, 5 ms for the blocks there: what a first form of this call,
    which wrote ``[C, Hkv x table]``, cost the chunk programs)."""
    C, H, Dh = q.shape
    W, Hkv, per, _ = ck.shape
    strides, group = kernel // stride, H // Hkv
    padded = _round_up(W, _PAGES)
    n_tiles = padded // _PAGES
    own = jnp.pad(jnp.moveaxis(ck, 0, 2),
                  ((0, 0), (0, 0), (0, padded - W), (0, 0)))
    def reaching(n):
        # the kernel that starts n strides before a page is kernel -n mod
        # per of the page ceil(n / per) before it
        before = -(-n // per)
        return jnp.pad(own[:, -n % per, :padded - before],
                       ((0, 0), (before, 0), (0, 0)))

    reach = [reaching(n) for n in range(1, strides)]
    planes = jnp.concatenate([own, *(p[:, None] for p in reach)], 1)
    # the last kernel complete at the chunk's end meets the blocks up to
    # that of its last stride
    newest = (offset + length - kernel) // stride + strides - 1
    last = jnp.clip(newest // per // _PAGES, 0, n_tiles - 1)
    f32, dtype = jnp.float32, jnp.promote_types(q.dtype, ck.dtype)
    held = (2 * 2 * (block_q + len(reach) * _PAGES + per * _PAGES) * Dh
            + 4 * block_q * (4 * padded + (per + 3) * _PAGES + 2 * 128))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=Dh ** -0.5, per=per,
                          strides=strides, stride=stride),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Hkv, C // block_q, group, n_tiles),
            in_specs=[
                pl.BlockSpec((None, block_q, Dh),
                             lambda g, i, r, t, pre: (g * group + r, i, 0)),
                # a tile past the last asks for it again: not fetched twice
                pl.BlockSpec((None, per + strides - 1, _PAGES, Dh),
                             lambda g, i, r, t, pre: (
                                 g, 0, jnp.minimum(t, pre[1]), 0)),
            ],
            out_specs=pl.BlockSpec((None, padded, block_q),
                                   lambda g, i, r, t, pre: (g, 0, i)),
            scratch_shapes=[pltpu.VMEM((block_q, 1), f32),
                            pltpu.VMEM((block_q, 1), f32),
                            pltpu.VMEM((n_tiles, block_q, _PAGES), f32),
                            pltpu.VMEM((n_tiles, block_q, _PAGES), f32)]),
        out_shape=jax.ShapeDtypeStruct((Hkv, padded, C), f32),
        cost_estimate=pl.CostEstimate(
            flops=2 * C * H * Dh * (per + strides - 1) * padded,
            transcendentals=C * H * (per + 1) * padded,
            bytes_accessed=(q.dtype.itemsize * C * H * Dh
                            + ck.dtype.itemsize * H * (C // block_q)
                            * (per + strides - 1) * padded * Dh
                            + 4 * C * Hkv * padded)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=max(held + (8 << 20), 16 << 20)),
        interpret=interpret,
        name="hvd_sparse_scores",
    )(jnp.stack([offset, last]).astype(jnp.int32),
      jnp.moveaxis(q, 1, 0).astype(dtype), planes.astype(dtype))
    # [Hkv, W, C] as it lies; the caller's [C, Hkv, W] is the same
    # bytes under another layout, which XLA keeps for the top-k
    return jnp.moveaxis(out[:, :W], 2, 0)


#: Pages a key tile holds: a lane tile of blocks a plane.
_PAGES = 128
#: The q tiles a chunk may take, the widest first. On the v5e
#: (2026-10-02, ``tools/prefill_attn_sweep.py --select``: ``[C, 32,
#: 128]`` bf16 queries over 2 KV heads' compressed keys, 4 kernels a page
#: of 64, a table of 520; ms a layer of the XLA form -> the kernel, a
#: chunk that ends at key 8192 / 16 384 / 32 768): C = 1024 5.78 / 5.79
#: / 5.79 -> **0.23 / 0.34 / 0.57**, C = 512 2.90 / 2.89 / 2.89 -> **0.16
#: / 0.23 / 0.36**, C = 256 1.04 / 1.04 / 1.04 -> **0.13 / 0.17 /
#: 0.24**. A chunk of 1024 in q tiles of 512 takes 0.26 / 0.38 / 0.63
#: and of 256 0.30 / 0.46 / 0.76 (the planes are fetched once a head and
#: q tile); key tiles of 256 pages 0.28 / 0.27 / 0.44 and of 512 0.38 /
#: 0.38 / 0.38 (fewer lane reductions a product, a later stop after the
#: chunk's end, a table padded to 768 or 1024): within 0.05 ms at the
#: cell's mean chunk end, so the narrowest stays. The vector unit sets
#: the time: 0.15 ms a 1024-chunk and 8192 keys (2 x 16 heads x 5
#: planes of 1024 x 128 products: a scale, a compare, a select, two
#: maxima, a subtraction, an exponential and a sum each), where the
#: products alone are 0.03 ms of the matrix unit.
_Q_TILES = (1024, 512, 256)
#: What a grid step's output block (two buffers), blocks' maxima and
#: group's sums may take of fast memory beside its operands and products
#: (the call asks for what it holds and 8 MiB more of the chip's 128).
_HELD_BYTES = 12 << 20
