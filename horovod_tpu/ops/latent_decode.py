"""A decode step's latent attention over the pool where it lies.

One Pallas call a layer (``hvd_latent_decode`` in a device trace): for
each row of the batch, the absorbed queries of every head against the
latents of that row's own pages, read once out of the pool in HBM, a
key block at a time into VMEM, and no further than the row's length.
The keys are the values (one latent a position: all of it scored, its
first ``rank`` summed), so a page crosses the memory once for both
dots. The XLA form it replaces (``tests/reference_mla.py``) is the
tests' reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF


def _wave_pages(block_size: int) -> int:
    """Pages a wave of copies brings (a key block): 1024 positions.
    On the v5e (2026-10-01, ``tools/prefill_attn_sweep.py
    --latent-decode``: bf16 queries ``[rows, H, 640]`` over a pool of
    pages ``[16, 640]`` behind shuffled tables of 1088; ms a layer of
    the kernel alone and the GB/s of the pages it reads, at key blocks
    of 512 / **1024** / 2048 positions; ``xla`` is the form it replaced
    with the two absorbed products, ``tests/reference_mla.py`` over
    ``mla_pages`` to the longest row):

    == ==== ================== ==== ====================== ===============
    H  rows lengths            xla  kernel alone, ms       GB/s
    == ==== ================== ==== ====================== ===============
    64 32   8192 each          1.40 0.81 / **0.70** / 0.69 415 / 482 / 488
    64 32   4096 .. 16 384     2.61 0.99 / **0.90** / 0.91 435 / 477 / 473
    64 32   16 384, 256 .. 3 k 2.59 0.35 / **0.36** / 0.37 271 / 266 / 259
    32 64   8192 each          2.49 1.36 / **1.18** / 1.17 494 / 568 / 574
    32 64   4096 .. 16 384     4.78 1.76 / **1.54** / 1.57 493 / 564 / 554
    32 64   16 384, 256 .. 3 k 4.82 0.49 / **0.48** / 0.51 313 / 315 / 295
    == ==== ================== ==== ====================== ===============

    (The third lengths: one row of 16 384 among rows of 256 to 3072.)
    64 heads at 64 rows and 32 at 32 lie between (0.65-1.63 ms, 515-518
    GB/s on whole blocks). A whole key block of 1024 takes 2.3-2.8 us
    where the memory's 819 GB/s would take 1.6, and four fifths of that
    is the copies, not the dots: with both dots taken out the kernel
    takes 0.71 of its 0.90 ms at 64 heads and 32 rows of 4-16 k (598
    GB/s: 64 copies of 20 KB from scattered pages, 34 ns each), the
    score dot adds 0.08, the value dot 0.04, the softmax 0.09, and 32
    heads take nine tenths of 64's time. Behind the engine's tables,
    where a sequence's pages mostly follow one another, the same kernel
    reads 629 GB/s (the Kimi cell's trace, 0.695 ms a layer). A block
    at a row's end, and so every block of a row under 1024 positions,
    starts and awaits its pages in a loop (0.4 us a page more): rows of
    256 to 3 k read at 270-315 GB/s. With every block's copies in such
    a loop the same kernel read 341-362 GB/s on whole blocks (0.99 and
    1.24 ms where 0.70 and 0.90 stand): the straight-line copies beside
    the dots are a third of its speed. Scoring with the latents as the
    streamed operand (``latent . q^T``, turned back) was slower, 1.23
    ms. 2048 is no faster on long rows and slower on short ones; 512
    starts twice the blocks."""
    return max(1, 1024 // block_size)


def key_block(block_size: int, table_width: int) -> int:
    """Positions a key block of :func:`latent_decode` holds over pages of
    ``block_size`` behind tables ``table_width`` wide."""
    return min(_wave_pages(block_size), table_width) * block_size


def _kernel(layer_ref, len_ref, first_ref, tab_ref, q_ref, pool_ref, o_ref,
            buf, sem, acc, m_scr, l_scr, *, scale: float, rank: int,
            width: int):
    """Row ``b`` of the batch (one grid step): its key blocks in a
    loop, block ``j`` waited for in one half of ``buf`` while the pages
    of the next (the row's, or the first of row ``b + 1``) are on their
    way into the other. ``first_ref[b]`` counts the key blocks of the
    rows before ``b``: its parity says which half block 0 arrives in.
    Where this block and the next are both whole, the next one's copies
    are started as straight-line code in the block that holds the dots
    (the scalar unit issues them while the matrix unit works) and this
    one's are waited for at once; a block at a row's end takes a loop
    over the pages it has."""
    b, rows = pl.program_id(0), pl.num_programs(0)
    _, pages, page, _ = buf.shape
    kb = pages * page
    layer, length = layer_ref[0], len_ref[b]
    n_blocks = pl.cdiv(length, kb)

    def copy(r, j, half, i):
        return pltpu.make_async_copy(
            pool_ref.at[layer, tab_ref[r * width + j * pages + i]],
            buf.at[half, i], sem.at[half])

    def pages_of(r, j):
        """The pages of row r's key block j that hold positions below
        the row's length."""
        return jnp.minimum(pages, pl.cdiv(len_ref[r] - j * kb, page))

    def wave(r, j, half, how):
        """``start`` or ``wait`` for the copies of row r's key block j:
        the pages below the row's length, no other."""
        def one(i, _):
            getattr(copy(r, j, half, i), how)()
            return _
        lax.fori_loop(0, pages_of(r, j), one, 0)

    @pl.when(b == 0)
    def _first():
        # what a wave does not fill is what an earlier one left, and is
        # masked: it has to be a number
        buf[...] = jnp.zeros_like(buf)
        wave(0, 0, 0, "start")

    acc[...] = jnp.zeros_like(acc)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    q = q_ref[...]

    def attend(j, half):
        kv = buf[half].reshape(kb, buf.shape[-1])
        s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_pos = j * kb + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        fade = jnp.exp(m_prev - m_new)
        l_scr[...] = fade * l_scr[...] + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * fade + lax.dot(
            p.astype(kv.dtype), kv[:, :rank],
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    def block(j, _):
        half = (first_ref[b] + j) % 2
        last = j == n_blocks - 1

        def ragged():
            @pl.when(jnp.logical_not(last))
            def _next():
                wave(b, j + 1, 1 - half, "start")

            @pl.when(last & (b + 1 < rows))
            def _next_row():
                wave(b + 1, 0, 1 - half, "start")

            wave(b, j, half, "wait")
            attend(j, half)

        def whole():
            for i in range(pages):
                copy(b, j + 1, 1 - half, i).start()
            # one wait for the bytes of all of this half's copies
            pltpu.make_async_copy(buf.at[1 - half], buf.at[half],
                                  sem.at[half]).wait()
            attend(j, half)

        # j + 2 blocks lie below the length: block j + 1 is whole too
        lax.cond((j + 2) * kb <= length, whole, ragged)
        return _

    lax.fori_loop(0, n_blocks, block, 0)
    o_ref[...] = (acc[...] / l_scr[...]).astype(o_ref.dtype)


def latent_decode(q, pool, layer, tables, lengths, *, rank: int,
                  scale: float, interpret: Optional[bool] = None):
    """Absorbed latent attention of one query a row over the row's
    pages: ``q`` ``[B, H, row]`` (``[q W_uk^T | q_rope]``, zeros from
    ``rank + R`` on) against ``pool`` ``[layers, n_blocks, block_size,
    row]`` at ``layer`` (traced: the layers of a stack share one
    compiled kernel), row b's positions ``0 .. lengths[b] - 1`` (at
    least one: a length under 1 is read as 1) in the pages ``tables[b]``
    ``[B, W]`` names in order. Returns ``[B, H, rank]`` in ``q``'s
    dtype: the softmax of ``scale * q . latent`` over the row's
    positions, times the latents' first ``rank`` values.

    The pool stays in HBM and is never sliced or gathered outside the
    kernel: a key block's pages (:func:`key_block` positions: 64 pages
    of 20 KB at a block of 16 rows of 640 bf16) are copied into VMEM by
    as many asynchronous copies, into one half of a double buffer while the
    other half's block is attended, the next row's first block under the
    last of this one's. A page past a row's length is not copied and a
    key block past it is not visited, so a call reads ``sum_b
    ceil(lengths[b] / block_size)`` pages whatever the longest row is.
    Float32 scores, softmax and accumulator over operands in the pool's
    dtype, ``p`` rounded to it for the value dot: the numerics of the
    XLA form. ``tables`` and ``lengths`` are read from SMEM (scalar
    prefetch)."""
    B, H, row = q.shape
    n_layers, n_pages, page, pool_row = pool.shape
    width = tables.shape[1]
    if (pool_row != row or tables.shape[0] != B or lengths.shape != (B,)
            or rank > row):
        raise ValueError(
            f"latent_decode: q {q.shape}, pool {pool.shape}, tables "
            f"{tables.shape}, lengths {lengths.shape}, rank {rank}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    pages = key_block(page, width) // page
    # a row with no block would start no copy for the row after it,
    # which would wait for one for ever: every row reads one position
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)
    n_blocks = -(-lengths // (pages * page))
    first = jnp.cumsum(n_blocks) - n_blocks
    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), rank=rank,
                          width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, H, row), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, H, rank), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, page, row), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, rank), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hvd_latent_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths,
      first.astype(jnp.int32), tables.astype(jnp.int32).reshape(-1), q, pool)
