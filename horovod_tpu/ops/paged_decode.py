"""A decode step's attention over a pool's pages where they lie.

One Pallas call a layer: for each row of the batch, its one query a head
against that row's own pages, read once out of the pools in HBM, a key
block at a time into VMEM, and no further than the row's length (tables
and lengths as prefetched scalars, a key block's pages by asynchronous
copies into one half of a double buffer while the other half is
attended, one running softmax). ONE kernel body, two Pallas calls of it:

``hvd_paged_decode`` (:func:`paged_decode`, :func:`paged_decode_stats`,
:func:`ring_decode`)
    a ``full`` layer's K and V pages, two pools. The XLA form it
    replaced (``serve/decode.py``: ``_attend_keys`` over every row's
    whole table, gathered) is the tests' reference. A window layer's
    rings are such pools too (:func:`ring_decode`: a slot's ring as
    pages, the table arithmetic, a row's window from the middle of its
    first page on), against ``_attend_keys`` over whole rings.
``hvd_latent_decode`` (:func:`latent_decode`, :func:`latent_ring_decode`)
    an ``mla`` layer's latents, one pool (an ``mla_sliding`` layer's
    rings of latents are such a pool, read as :func:`ring_decode` reads
    rings of keys): the keys are the values (one
    latent a position: all of it scored, its first ``rank`` summed), so
    a page crosses the memory once for both dots. The XLA form it
    replaced (``tests/reference_mla.py``) is the tests' reference.
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
    """Pages a wave of copies brings of each pool (a key block): 1024
    positions, for K and V pages and for latents alike.

    **K and V pages.** On the v5e (2026-10-02, ``tools/prefill_attn_sweep.py
    --paged-decode``: bf16 queries ``[rows, H, Dh]`` over two pools
    behind shuffled tables, the rows' lengths log-uniform; ms a layer of
    the kernel alone and the GB/s of the K and V pages it reads, at key
    blocks of 512 / **1024** / 2048 positions; ``xla`` is the form it
    replaced, ``_attend_keys`` over every row's whole table, gathered):

    ======= ==== ========== ====== ==== ===== ========================= ===============
    cell    rows lengths    tables H    xla   kernel alone, ms          GB/s
    ======= ==== ========== ====== ==== ===== ========================= ===============
    lfm2    128  256..2560  160    32/8 7.81  0.575 / **0.554** / 0.594 445 / 462 / 431
    trinity 32   1024..8576 536    48/8 5.39  0.744 / **0.758** / 0.761 632 / 620 / 617
    jamba   256  64..1536   96     20/1 0.85  0.548 / **0.541** / 0.543 117 / 118 / 118
    ======= ==== ========== ====== ==== ===== ========================= ===============

    (lfm2: pages ``[16, 512]``, 8 heads of 64 end to end; trinity: ``[16,
    8, 128]``; jamba: ``[16, 1, 128]``. 7.8 k pages of each pool a call
    in all three.) A page of each pool costs 69-71 ns at lfm2's 16 KB
    and at jamba's 4 KB alike: nearly every key block there is a row's
    last one, whose copies are issued in a loop, and what a copy costs
    is its issue (34 ns, below), not its bytes; at trinity's 32 KB,
    where most blocks are whole, it is the memory (105
    ns a page pair where 819 GB/s would take 80). A first form that
    started a page a turn of the loop and waited for every copy by
    itself took 0.664, 0.747 and 0.625 ms: the eight pages a turn and
    the one wait a power of two are 17 % of lfm2's time and 14 % of
    jamba's. The masked form of trinity's pages (every query head
    scored against every KV head's keys, 8 x the exponentials) is not
    what bounds it: 620-632 GB/s is the latent call's speed behind
    shuffled tables with one pool. 2048 is no faster anywhere; 512 is 2
    % ahead at trinity's shapes and 4 % behind at lfm2's. (With a
    block's offset into the tables taken once and not once a page, as
    it stands: 0.546, 0.749 and 0.526 ms at 1024.)

    **Rings.** A window layer's step (:func:`ring_decode`; on the v5e,
    2026-10-03, builder, PR 59, ``--paged-decode --cells ring trinity``
    in one call: 32 rows of 48 / 8 heads at positions log-uniform
    1024..8576 in 33 slots' rings of 5136 places under a window of 4096,
    11 rows past the window and 8 past the ring, 98 399 visible
    positions; ``xla`` is ``_attend_keys`` over every slot's whole ring,
    694 MB a layer, WITHOUT the slices of the stacked cache in front of
    it, which were 1.6 ms each and eight a step in the cell), at a ring
    read as pages of **16** / 48 positions (the two multiples of the
    block that divide 5136 = 16 * 3 * 107):

    ==== ==== ======== ====== ==== ===== ===================== =========
    cell rows visible  tables H    xla   kernel alone, ms      GB/s
    ==== ==== ======== ====== ==== ===== ===================== =========
    ring 32   1 .. 4 k 257/87 48/8 1.304 **0.6718** / 0.6734   602 / 605
    ==== ==== ======== ====== ==== ===== ===================== =========

    (6 171 pages of 16 or 2 072 of 48 of each ring: 404 and 407 MB; the
    ``trinity`` row above read 0.8919 ms and 653.6 GB/s in the same
    call.) A page of 48 is a third of the copies at three times the
    bytes and is level to a quarter of a percent: at 32 KB a page pair
    the memory bounds the copy, not its issue (the table above), so
    :func:`ring_page` is the block and a ring's call is the very
    instance of the kernel the ``full`` layers run. The masked first
    page (``skip``) costs one compare a score tile.

    **Latents.** On the v5e (``tools/prefill_attn_sweep.py
    --latent-decode``: bf16 queries ``[rows, H, 640]`` over a pool of
    pages ``[16, 640]`` behind shuffled tables of 1088; ms a layer of
    the kernel alone and the GB/s of the pages it reads, at key blocks
    of 512 / **1024** / 2048 positions; ``xla``, 2026-10-01, is the
    form it replaced with the two absorbed products,
    ``tests/reference_mla.py`` over ``mla_pages`` to the longest row;
    the kernel's columns 2026-10-03, builder, PR 58, as it stands: this
    module's one body):

    == ==== ================== ==== ====================== ===============
    H  rows lengths            xla  kernel alone, ms       GB/s
    == ==== ================== ==== ====================== ===============
    64 32   8192 each          1.40 0.76 / **0.66** / 0.62 440 / 509 / 542
    64 32   4096 .. 16 384     2.61 0.96 / **0.84** / 0.82 449 / 513 / 527
    64 32   16 384, 256 .. 3 k 2.59 0.33 / **0.30** / 0.31 289 / 317 / 307
    32 64   8192 each          2.49 1.34 / **1.13** / 1.02 501 / 594 / 658
    32 64   4096 .. 16 384     4.78 1.74 / **1.46** / 1.36 500 / 594 / 639
    32 64   16 384, 256 .. 3 k 4.82 0.44 / **0.40** / 0.41 349 / 379 / 371
    == ==== ================== ==== ====================== ===============

    (The third lengths: one row of 16 384 among rows of 256 to 3072. The
    sweep shares a program's time out over its six chained calls, so
    every level carries about 0.1 ms of the program's own cost:
    ``PERF.md`` §7.) 64 heads at 64 rows and 32 at 32 lie between
    (0.62-1.54 ms, 541-552 GB/s on whole blocks). To PR 57 this call had
    a kernel of its own, whose blocks at a row's end started and awaited
    a page a turn of the loop; beside this one in one call (builder, PR
    58) its 1024 column read 0.70 / 0.89 / 0.34 and 1.17 / 1.54 / 0.48
    ms: the eight pages a turn and the one wait a power of two are 14-18
    % of the time of short rows and 4-7 % of rows of 4-16 k, whose last
    TWO blocks take that form (``whole`` needs the next block whole
    too). A whole key block of 1024 takes 2.3-2.8 us where the memory's
    819 GB/s would take 1.6, and four fifths of that is the copies, not
    the dots: with both dots taken out the kernel took 0.71 of its 0.90
    ms at 64 heads and 32 rows of 4-16 k (598 GB/s: 64 copies of 20 KB
    from scattered pages, 34 ns each), the score dot adds 0.08, the
    value dot 0.04, the softmax 0.09, and 32 heads take nine tenths of
    64's time (builder, PR 45, as what follows). Behind the engine's
    tables, where a sequence's pages mostly follow one another, the
    kernel read 629 GB/s (the Kimi cell's trace, 0.695 ms a layer). With
    every block's copies in a loop a page a turn it read 341-362 GB/s on
    whole blocks (0.99 and 1.24 ms where 0.70 and 0.90 stood): the
    straight-line copies beside the dots are a third of its speed.
    Scoring with the latents as the streamed operand (``latent . q^T``,
    turned back) was slower, 1.23 ms. 512 starts twice the blocks. 2048
    was level with 1024 on long rows and is 3-10 % ahead of it since PR
    58 (a row's ragged blocks, its last two, cost less than they did),
    level or 3 % behind on short rows; not taken: one rule for both
    calls, and at the K and V cells' shapes 2048 is no faster (above)."""
    return max(1, 1024 // block_size)


def key_block(block_size: int, table_width: int) -> int:
    """Positions a key block of this module's calls holds over pages of
    ``block_size`` behind tables ``table_width`` wide."""
    return min(_wave_pages(block_size), table_width) * block_size


def _kernel(layer_ref, len_ref, first_ref, tab_ref, *refs,
            scale: float, width: int, page: int, group: int,
            stats: bool = False, skips: bool = False):
    """Row ``b`` of the batch (one grid step): its key blocks in a
    loop, block ``j`` waited for in one half of ``buf`` (pool ``n``'s
    pages at ``[half, n]``) while the pages of the next (the row's, or
    the first of row ``b + 1``) are on their way into the other.
    ``first_ref[b]`` counts the key blocks of the rows before ``b``: its
    parity says which half block 0 arrives in. Where this block and the
    next are both whole, the next one's copies are started as
    straight-line code in the block that holds the dots (the scalar unit
    issues them while the matrix unit works) and this one's are waited
    for at once; a block at a row's end takes a loop over the pages it
    has.

    ``refs``: with ``skips`` one more prefetched scalar a row first (the
    positions at the head of the row's first page that are copied with
    it and NOT seen: a ring's window begins in the middle of a page,
    :func:`ring_decode`; ``len_ref[b]`` counts them), then the queries,
    the pools (as many as ``buf`` has at ``[half]``: K and V,
    or the one of latents, whose keys are the values, their first
    ``acc``-wide columns), the output, with ``stats`` one more (the
    softmax's logsumexp a head, along the lanes of a ``[H, 128]`` tile,
    for a caller that merges this call's keys with others' in one
    softmax: :func:`paged_decode_stats`), and the scratch.

    A page is ``page * group`` rows of the buffer: one a position
    (``group`` 1: every KV head in the row, the queries laid
    block-diagonal over it), or one a position and KV head (``group``
    ``Hkv``: column ``c`` of the scores is position ``c // group`` under
    KV head ``c % group``, and a query head sees its own KV head's
    columns alone)."""
    if skips:
        skip_ref, *refs = refs
    q_ref, *refs = refs
    buf, sem, acc, m_scr, l_scr = refs[-5:]
    pools, o_ref = refs[:buf.shape[1]], refs[buf.shape[1]]
    b, rows = pl.program_id(0), pl.num_programs(0)
    pages = buf.shape[2]
    kb = pages * page
    layer, length = layer_ref[0], len_ref[b]
    n_blocks = pl.cdiv(length, kb)

    def copies(r, j, half):
        """``of(i)``: the copies, one a pool, of page i of row r's key
        block j into ``half``."""
        first = r * width + j * pages

        def of(i):
            at = tab_ref[first + i]
            return [pltpu.make_async_copy(pool.at[layer, at],
                                          buf.at[half, n, i], sem.at[half])
                    for n, pool in enumerate(pools)]
        return of

    def pages_of(r, j):
        """The pages of row r's key block j that hold positions below
        the row's length."""
        return jnp.minimum(pages, pl.cdiv(len_ref[r] - j * kb, page))

    def start(r, j, half):
        """Start the copies of row r's key block j: the pages below the
        row's length, no other; eight pages' in a turn of the loop, so
        that the scalar unit issues them without a branch between."""
        n, of = pages_of(r, j), copies(r, j, half)

        def some(count):
            def turn(i, _):
                for u in range(count):
                    for c in of(i * count + u):
                        c.start()
                return _
            return turn
        lax.fori_loop(0, n // 8, some(8), 0)
        lax.fori_loop(n // 8 * 8, n, some(1), 0)

    def wait(r, j, half):
        """Wait for the bytes of the ``n`` pages :func:`start` asked of
        each pool: one wait for each power of two in ``n``, not one a
        copy."""
        n = pages_of(r, j)
        for bit in reversed(range(pages.bit_length())):
            @pl.when(n & (1 << bit) != 0)
            def _wait():
                pltpu.make_async_copy(
                    buf.at[1 - half, :, pl.ds(0, 1 << bit)],
                    buf.at[half, :, pl.ds(0, 1 << bit)], sem.at[half]).wait()

    @pl.when(b == 0)
    def _first():
        # what a wave does not fill is what an earlier one left, and is
        # masked: it has to be a number
        buf[...] = jnp.zeros_like(buf)
        start(0, 0, 0)

    acc[...] = jnp.zeros_like(acc)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    q = q_ref[...]

    def attend(j, half):
        k = buf[half, 0].reshape(kb * group, buf.shape[-1])
        v = (buf[half, 1].reshape(kb * group, buf.shape[-1])
             if len(pools) > 1 else k[:, :acc.shape[1]])
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        at = j * kb + col // group
        seen = at < length
        if skips:
            seen &= at >= skip_ref[b]
        if group > 1:
            head = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            seen &= col % group == head // (s.shape[0] // group)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        fade = jnp.exp(m_prev - m_new)
        l_scr[...] = fade * l_scr[...] + p.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * fade + lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    def block(j, _):
        half = (first_ref[b] + j) % 2
        last = j == n_blocks - 1

        def ragged():
            @pl.when(jnp.logical_not(last))
            def _next():
                start(b, j + 1, 1 - half)

            @pl.when(last & (b + 1 < rows))
            def _next_row():
                start(b + 1, 0, 1 - half)

            wait(b, j, half)
            attend(j, half)

        def whole():
            of = copies(b, j + 1, 1 - half)
            for i in range(pages):
                for c in of(i):
                    c.start()
            # one wait for the bytes of all of this half's copies
            pltpu.make_async_copy(buf.at[1 - half], buf.at[half],
                                  sem.at[half]).wait()
            attend(j, half)

        # j + 2 blocks lie below the length: block j + 1 is whole too
        lax.cond((j + 2) * kb <= length, whole, ragged)
        return _

    lax.fori_loop(0, n_blocks, block, 0)
    o_ref[...] = (acc[...] / l_scr[...]).astype(o_ref.dtype)
    if stats:
        lse_ref = refs[len(pools) + 1]
        lse_ref[...] = jnp.broadcast_to(m_scr[...] + jnp.log(l_scr[...]),
                                        lse_ref.shape)


def paged_decode(q, k_pool, v_pool, layer, tables, lengths, *,
                 interpret: Optional[bool] = None):
    """Attention of one query a row and head over the row's pages:
    ``q`` ``[B, H, Dh]`` against ``k_pool`` and ``v_pool`` ``[layers,
    n_blocks, block_size, *tail]`` at ``layer`` (traced: the layers of a
    stack share one compiled kernel), row b's positions ``0 ..
    lengths[b] - 1`` (at least one: a length under 1 is read as 1) in
    the pages ``tables[b]`` ``[B, W]`` names in order. ``tail`` is what
    ``kv_cache.page_tail`` gives a position: ``(Hkv, Dh)``, or ``(Hkv *
    Dh,)``, the heads end to end. Returns ``[B, H, Dh]`` in ``q``'s
    dtype: the softmax of ``Dh ** -0.5 * q . k`` over the row's
    positions under each query head's KV head, times the same positions'
    values.

    The pools stay in HBM and are never sliced or gathered outside the
    kernel: a key block's pages (:func:`key_block` positions of each
    pool) are copied into VMEM by as many asynchronous copies, into one
    half of a double buffer while the other half's block is attended,
    the next row's first block under the last of this one's. A page past
    a row's length is not copied and a key block past it is not visited,
    so a call reads ``sum_b ceil(lengths[b] / block_size)`` pages of
    each pool whatever the tables' width is. Float32 scores, softmax
    and accumulator over operands in the pools' dtype, ``p`` rounded to
    it for the value dot: the numerics of ``_attend_keys``. ``tables``
    and ``lengths`` are read from SMEM (scalar prefetch).

    The two page shapes are one kernel. Rows of ``Hkv * Dh``: the
    queries are laid block-diagonal ``[H, Hkv * Dh]`` (head h in the
    columns of its KV head, zeros elsewhere), one dot against the whole
    row scores every head and one of ``p`` against the whole V row sums
    it, each head's own ``Dh`` columns picked afterwards (``Hkv`` times
    the dots' operations, which the copies hide). Heads of their own
    dimension: the pools are read as ``[.., block_size * Hkv, Dh]`` (the
    same bytes), the one dot scores every query head against every KV
    head's keys and the mask keeps a head's own."""
    B, H, Dh = q.shape
    page, tail = k_pool.shape[2], k_pool.shape[3:]
    n_kv = tail[0] if len(tail) == 2 else tail[0] // Dh
    if (v_pool.shape != k_pool.shape or len(tail) not in (1, 2)
            or tail[-1] != (Dh if len(tail) == 2 else n_kv * Dh)
            or H % n_kv or tables.shape[0] != B or lengths.shape != (B,)):
        raise ValueError(
            f"paged_decode: q {q.shape}, pools {k_pool.shape} and "
            f"{v_pool.shape}, tables {tables.shape}, lengths {lengths.shape}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _decode(q, (k_pool, v_pool), jnp.asarray(layer, jnp.int32),
                   tables, lengths, name="hvd_paged_decode",
                   scale=Dh ** -0.5, rank=tail[-1],
                   pages=key_block(page, tables.shape[1]) // page,
                   interpret=interpret)


def paged_decode_stats(q, k_pool, v_pool, layer, tables, lengths, *,
                       key_positions: int,
                       interpret: Optional[bool] = None):
    """:func:`paged_decode` (the same kernel) for a caller whose rows
    attend OTHER keys besides, in one softmax: returns ``(out [B, H,
    Dh], lse [B, H])``, both float32, the call's own softmax and its
    logsumexp, which two calls merge exactly (``exp(lse_a - lse)`` and
    ``exp(lse_b - lse)`` weigh the two outs; an eva layer's step: the
    open window's rows and the summaries' pages,
    ``serve/decode.py::mixed_programs``). A row whose length is under 1
    still reads one position, as there: the caller gives that row's
    ``lse`` no weight. ``key_positions``: the key block, in whole pages
    (the caller's: with as many KV heads as query heads a position is
    ``H`` rows of the buffer, and 1024 of them would be 33 MB of VMEM
    and 4 MB a float32 tile of scores)."""
    B, H, Dh = q.shape
    page, tail = k_pool.shape[2], k_pool.shape[3:]
    if (v_pool.shape != k_pool.shape or len(tail) != 2 or tail[-1] != Dh
            or H % tail[0] or tables.shape[0] != B or lengths.shape != (B,)
            or key_positions % page):
        raise ValueError(
            f"paged_decode_stats: q {q.shape}, pools {k_pool.shape} and "
            f"{v_pool.shape}, tables {tables.shape}, lengths "
            f"{lengths.shape}, key blocks of {key_positions}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _decode(q, (k_pool, v_pool), jnp.asarray(layer, jnp.int32),
                   tables, lengths, name="hvd_paged_decode",
                   scale=Dh ** -0.5, rank=tail[-1],
                   pages=min(key_positions // page, tables.shape[1]),
                   interpret=interpret, stats=True)


def ring_page(ring: int, block_size: int) -> int:
    """Positions :func:`ring_decode` reads a ring's places as one page:
    ``block_size``, the page of the ``full`` layers' pools (``ring`` is
    a whole number of them, ``kv_cache.ring_width``), so that the call
    is the instance of the kernel those layers run. A larger divisor of
    the ring (48 of 5136) was level on the chip: :func:`_wave_pages`."""
    if ring % block_size:
        raise ValueError(f"a ring of {ring} is not whole blocks of "
                         f"{block_size}")
    return block_size


def ring_reach(positions, window: int, ring: int):
    """``(at, n_vis)`` of rows at ``positions`` (an int32 array, jax's
    or numpy's) in rings of ``ring`` places under a ``window``: the
    place of the first key a row sees and how many it sees, up to its
    own. In pages of ``page`` the row reads ``ceil((at % page + n_vis)
    / page)`` of them (:func:`ring_decode`; the engine's counters)."""
    n_vis = (positions + 1).clip(max=min(window, ring))
    return (positions + 1 - n_vis) % ring, n_vis


def ring_decode(q, k_rings, v_rings, layer, slots, positions, *,
                window: int, page: int, interpret: Optional[bool] = None):
    """A window layer's decode step, each row over its own slot's ring
    where it lies: ``q`` ``[B, H, Dh]`` against ``k_rings`` and
    ``v_rings`` ``[layers, n_slots, ring, Hkv, Dh]`` at ``layer``, row b
    at position ``positions[b]`` of the sequence in slot ``slots[b]``
    (position p lies at place ``p % ring``, already written), over the
    ``min(p + 1, window)`` positions up to its own (a ring narrower
    than the window holds ``ring`` of them, and is the window). Returns
    ``[B, H, Dh]`` in ``q``'s dtype, :func:`paged_decode`'s kernel and
    numerics.

    The rings are read as a pool of ``n_slots * ring // page`` pages of
    ``page`` positions (the same bytes: ``page`` divides ``ring``), and
    a row's table is arithmetic: from the page that holds its first
    visible position ``j0 = p + 1 - min(p + 1, window)``, at place ``a =
    j0 % ring``, the ring's pages in order, round its end: ``slot * P +
    (a // page + t) % P``. The visible keys are then the table's
    positions ``skip .. skip + n_vis - 1`` in order, ``skip = a %
    page``, whether or not the window wraps; the first ``skip`` are
    copied with their page and masked (the kernel's one more scalar a
    row). A table is ``ceil((window + page - 1) / page)`` pages wide,
    not the ring's: places outside the window are never addressed, and
    a row reads ``ceil((skip + n_vis) / page)`` pages of each ring."""
    B, H, Dh = q.shape
    n_layers, n_slots, ring = k_rings.shape[:3]
    if (v_rings.shape != k_rings.shape or k_rings.ndim != 5 or ring % page
            or k_rings.shape[4] != Dh or H % k_rings.shape[3]
            or slots.shape != (B,)
            or positions.shape != (B,)):
        raise ValueError(
            f"ring_decode: q {q.shape}, rings {k_rings.shape} and "
            f"{v_rings.shape}, slots {slots.shape}, positions "
            f"{positions.shape}, a window of {window} in pages of {page}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    tables, skip, n_vis = _ring_tables(slots, positions, window, ring, page)
    return _decode(
        q, tuple(r.reshape(n_layers, n_slots * (ring // page), page,
                           *r.shape[3:])
                 for r in (k_rings, v_rings)),
        jnp.asarray(layer, jnp.int32), tables, skip + n_vis, skip,
        name="hvd_paged_decode", scale=Dh ** -0.5, rank=Dh,
        pages=key_block(page, tables.shape[1]) // page, interpret=interpret)


def latent_decode(q, pool, layer, tables, lengths, *, rank: int,
                  scale: float, interpret: Optional[bool] = None):
    """Absorbed latent attention of one query a row over the row's
    pages: ``q`` ``[B, H, row]`` (``[q W_uk^T | q_rope]``, zeros from
    ``rank + R`` on) against ``pool`` ``[layers, n_blocks, block_size,
    row]`` at ``layer`` (traced, as :func:`paged_decode`'s), row b's
    positions ``0 .. lengths[b] - 1`` (at least one: a length under 1 is
    read as 1) in the pages ``tables[b]`` ``[B, W]`` names in order.
    Returns ``[B, H, rank]`` in ``q``'s dtype: the softmax of ``scale *
    q . latent`` over the row's positions, times the latents' first
    ``rank`` values. :func:`paged_decode`'s kernel, copies and numerics
    (a key block: 64 pages of 20 KB at a block of 16 rows of 640 bf16)
    over the one pool, every score column below the length seen."""
    B, H, row = q.shape
    n_layers, n_pages, page, pool_row = pool.shape
    if (pool_row != row or tables.shape[0] != B or lengths.shape != (B,)
            or rank > row):
        raise ValueError(
            f"latent_decode: q {q.shape}, pool {pool.shape}, tables "
            f"{tables.shape}, lengths {lengths.shape}, rank {rank}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _decode(q, (pool,), jnp.asarray(layer, jnp.int32), tables,
                   lengths, name="hvd_latent_decode", scale=float(scale),
                   rank=rank, pages=key_block(page, tables.shape[1]) // page,
                   interpret=interpret)


def latent_ring_decode(q, rings, layer, slots, positions, *, window: int,
                       page: int, rank: int, scale: float,
                       interpret: Optional[bool] = None):
    """An ``mla_sliding`` layer's decode step, absorbed, each row over
    its own slot's ring of latents where it lies: ``q`` ``[B, H, row]``
    (:func:`latent_decode`'s) against ``rings`` ``[layers, n_slots, ring,
    row]`` at ``layer``, row b at position ``positions[b]`` of the
    sequence in slot ``slots[b]``, over the ``min(p + 1, window)``
    positions up to its own. :func:`ring_decode`'s arithmetic tables and
    ``skip`` over :func:`latent_decode`'s one pool: the ring read as
    pages of ``page`` places from the page that holds the window's
    first key, the places before it in that page copied and masked.
    Returns ``[B, H, rank]`` in ``q``'s dtype (``hvd_latent_decode`` in
    a device trace, under the caller's scope)."""
    B, H, row = q.shape
    n_layers, n_slots, ring = rings.shape[:3]
    if (rings.ndim != 4 or rings.shape[3] != row or ring % page
            or rank > row or slots.shape != (B,)
            or positions.shape != (B,)):
        raise ValueError(
            f"latent_ring_decode: q {q.shape}, rings {rings.shape}, slots "
            f"{slots.shape}, positions {positions.shape}, rank {rank}, a "
            f"window of {window} in pages of {page}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    tables, skip, n_vis = _ring_tables(slots, positions, window, ring, page)
    return _decode(
        q, (rings.reshape(n_layers, n_slots * (ring // page), page, row),),
        jnp.asarray(layer, jnp.int32), tables, skip + n_vis, skip,
        name="hvd_latent_decode", scale=float(scale), rank=rank,
        pages=key_block(page, tables.shape[1]) // page, interpret=interpret)


def _ring_tables(slots, positions, window: int, ring: int, page: int):
    """``(tables [B, W], skip [B], n_vis [B])`` of rows at ``positions``
    in the rings of ``slots`` read as pages of ``page`` places
    (:func:`ring_decode`'s docstring has the arithmetic)."""
    per = ring // page
    width = -(-(min(window, ring) + page - 1) // page)
    at, n_vis = ring_reach(positions.astype(jnp.int32), window, ring)
    skip = at % page
    tables = slots[:, None] * per + (
        at[:, None] // page + jnp.arange(width, dtype=jnp.int32)) % per
    return tables, skip, n_vis


@functools.partial(jax.jit, static_argnames=(
    "name", "scale", "rank", "pages", "interpret", "stats"))
def _decode(q, pools, layer, tables, lengths, skip=None, *, name: str,
            scale: float, rank: int, pages: int, interpret: bool,
            stats: bool = False):
    """The Pallas call ``name`` over ``pools`` (a tuple), jitted of
    itself: a program of several such layers traces and lowers the
    kernel once, not once a layer (``ops/mamba_scan.py::_scan``).
    ``rank``: the columns of a value that are summed (K and V pages:
    all). ``stats``: float32 out and the logsumexp beside it
    (:func:`paged_decode_stats`). ``skip`` [B]: the positions at the
    head of each row's first page that ``lengths`` counts and the row
    does not see (:func:`ring_decode`); None, and no fifth scalar, for
    a caller whose rows begin with their pages: its kernel is then text
    for text what it was without the notion."""
    B, H, Dh = q.shape
    n_layers, n_pages, page = pools[0].shape[:3]
    tail, width = pools[0].shape[3:], tables.shape[1]
    if len(tail) == 2:
        group, row = tail
        pools = tuple(pool.reshape(n_layers, n_pages, page * group, row)
                      for pool in pools)
    else:
        group, row = 1, tail[0]
    n_kv = row // Dh            # KV heads end to end in a row: 1 or Hkv
    if n_kv > 1:
        own = jnp.eye(n_kv, dtype=q.dtype)
        q = jnp.einsum("bgrd,gk->bgrkd", q.reshape(B, n_kv, H // n_kv, Dh),
                       own).reshape(B, H, row)
    # a row with no block would start no copy for the row after it,
    # which would wait for one for ever: every row reads one position
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)
    n_blocks = -(-lengths // (pages * page))
    first = jnp.cumsum(n_blocks) - n_blocks
    wave = len(pools) * pages * page * group * row * pools[0].dtype.itemsize
    scores = H * pages * page * group * 4
    out_spec = pl.BlockSpec((None, H, rank), lambda b, *_: (b, 0, 0))
    o = pl.pallas_call(
        functools.partial(_kernel, scale=scale, width=width, page=page,
                          group=group, stats=stats, skips=skip is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 + (skip is not None),
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, row), lambda b, *_: (b, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=([out_spec, pl.BlockSpec((None, H, 128),
                                               lambda b, *_: (b, 0, 0))]
                       if stats else out_spec),
            scratch_shapes=[
                pltpu.VMEM((2, len(pools), pages, page * group, row),
                           pools[0].dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, rank), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ]),
        out_shape=([jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
                    jax.ShapeDtypeStruct((B, H, 128), jnp.float32)]
                   if stats else jax.ShapeDtypeStruct((B, H, rank), q.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # K and V pages: both halves of the buffer and the float32
            # tiles of a key block's scores, with room for what the
            # compiler keeps. The one pool of latents fits the default
            # and asks for nothing: what a call asks for XLA cannot
            # prefetch into around it, and asking for these 20.7 MB
            # moved the schedule of Kimi's whole decode program and the
            # last bits of its logits (builder, PR 58)
            vmem_limit_bytes=(2 * wave + 8 * scores + (16 << 20)
                              if len(pools) > 1 else None)),
        interpret=interpret,
        name=name,
    )(layer.reshape(1), lengths, first.astype(jnp.int32),
      tables.astype(jnp.int32).reshape(-1),
      *(() if skip is None else (skip.astype(jnp.int32),)), q, *pools)
    if stats:
        return o[0], o[1][:, :, 0]
    if n_kv > 1:
        o = jnp.einsum("bgrkd,gk->bgrd",
                       o.reshape(B, n_kv, H // n_kv, n_kv, Dh), own
                       ).reshape(B, H, Dh)
    return o
