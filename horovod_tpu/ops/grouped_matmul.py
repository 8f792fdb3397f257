"""A grouped matmul over rows sorted by group, each group's matrix read
once: a whole mixture's products (``moe.moe_ffn_dropless``) and a chip's
share of the experts' (``moe._held_rows``) alike.

``grouped_matmul(lhs [M, K], rhs [G, K, N], sizes [G]) -> [M, N]`` has
the semantics of ``lax.ragged_dot``: rows ``sizes[:g].sum() ..
sizes[:g + 1].sum() - 1`` of ``lhs`` times ``rhs[g]``; ``sizes.sum() <=
M`` and the rows behind the last group are unspecified. One Pallas call
(``hvd_grouped_matmul`` in a device trace), built as
``ops/paged_decode.py`` is: which group a grid step serves, which row
tile it writes and where each group's rows begin are prefetched scalars
made from ``sizes`` in the program (the shape of
``jax.experimental.pallas.ops.tpu.megablox.gmm``), and a group's matrix
comes into VMEM WHOLE (the whole contraction and all of ``N``: 7.3 MB
at the LFM2 cell's ``[2048, 1792]``) by one asynchronous copy into one
half of a double buffer, started when the group BEFORE it takes its
first row tile, so that it arrives under all of that group's steps and
not under its last one alone. A group that spans several row tiles
takes as many steps over the same half; an empty group takes no step
and no copy; row tiles are ``_ROW_TILE`` rows, shared by the groups
that meet in one and stored under a row mask. Float32 accumulation (the
whole contraction is one dot) rounded once to the operands' dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
#: Rows of a row tile: ``models/moe.py``'s ``_ROW_TILE``.
_ROW_TILE = 128
#: The chip's operations a byte (197e12 / 819e9 = 240) say where a
#: product stops being bound by its matrices' bytes; the kernel is taken
#: under this many mean rows a group (:func:`taken`).
_MAX_ROWS_A_GROUP = 256
#: Both halves of the buffer of whole matrices may take this much VMEM.
_MATRIX_BUFFER_BYTES = 48 << 20


def taken(lhs, rhs) -> bool:
    """Whether ``lhs`` [M, K] times ``rhs`` [G, K, N] runs through the
    kernel: a rule from shapes alone. A product is bound by its
    matrices' bytes, and not by the matrix unit, when the mean rows a
    group lie under the chip's operations a byte; there one pass over
    each matrix is all there is to win. ``K`` and ``N`` have to be whole
    lane tiles, the operands of one dtype, and two whole matrices have
    to fit the buffer.

    On the v5e (2026-10-03, ``tools/grouped_matmul_sweep.py
    --megablox``: bf16 ``[M, 2048] x [32, 2048, 1792]`` / ``[M, 1792] x
    [32, 1792, 2048]``, the LFM2 cell's gate or up / down; ms a call on
    the device's side of the launch, a program of 32 calls less one of
    8; ``sizes`` from a seeded router whose fullest expert holds 1.7 to
    2.1 times the mean, even, and skewed: one group holds half the
    rows and eight are empty, 176 MB of matrix for 235; ``ragged_dot``
    the compiler's kernel, ``kernel@tm`` this one at a row tile of
    **128** / 256, ``megablox`` jax's ``gmm`` at tiles ``(128, K, N /
    2)``, whose matrices come a grid STEP ahead; the GB/s are of the
    matrices the groups with rows read, at ``kernel@128``):

    ===== ====== ============= ================= ============= ============= =========
    M     sizes  ragged_dot    kernel@128        kernel@256    megablox      GB/s
    ===== ====== ============= ================= ============= ============= =========
    512   router 0.932 / 0.906 **0.383 / 0.375** 0.404 / 0.398 0.388 / 0.385 613 / 626
    512   even   0.926 / 0.898 **0.380 / 0.375** 0.386 / 0.396 0.368 / 0.375 617 / 625
    512   skewed 0.713 / 0.699 **0.306 / 0.305** 0.313 / 0.316 0.307 / 0.308 575 / 577
    1024  router 0.955 / 0.926 **0.382 / 0.386** 0.417 / 0.428 0.422 / 0.426 614 / 608
    1024  even   0.936 / 0.902 **0.382 / 0.381** 0.402 / 0.388 0.380 / 0.367 615 / 616
    1024  skewed 0.737 / 0.716 **0.307 / 0.325** 0.333 / 0.341 0.330 / 0.338 574 / 541
    2048  router 1.031 / 0.990 **0.385 / 0.398** 0.463 / 0.457 0.478 / 0.473 610 / 589
    2048  even   0.924 / 0.905 **0.387 / 0.397** 0.405 / 0.412 0.386 / 0.383 607 / 591
    2048  skewed 0.796 / 0.784 **0.348 / 0.348** 0.378 / 0.385 0.381 / 0.391 506 / 506
    4096  router 1.139 / 1.098 **0.446 / 0.446** 0.544 / 0.545 0.575 / 0.580 526 / 527
    4096  even   0.949 / 0.925 **0.416 / 0.422** 0.434 / 0.427 0.400 / 0.403 564 / 557
    4096  skewed 0.914 / 0.900 **0.404 / 0.398** 0.449 / 0.460 0.473 / 0.489 436 / 443
    8192  router 1.371 / 1.338 **0.615 / 0.629** 0.698 / 0.704               381 / 373
    16384 router 1.896 / 1.807 **0.966 / 0.981** 1.028 / 1.033               243 / 239
    ===== ====== ============= ================= ============= ============= =========

    (The bytes' least time at 819 GB/s is 0.287 ms, 0.215 skewed.) The
    compiler's kernels take 0.70-0.93 ms whatever 512 to 2048 pairs
    hold, some 25 us a GROUP; the kernel takes what its copies take
    (with the dot taken out it took the same to a hundredth of a ms at
    512 pairs and 0.03 ms less at 4096) and holds 607-626 GB/s to 2048
    pairs. In the cell's own programs, where
    a layer's three products follow one another, a step's product reads
    **0.317 ms** (741 GB/s; the compiler's 0.843) and a chunk's 0.346
    (0.86-1.03) (a traced run of the LFM2 cell, the same day). A group
    ahead and not a step ahead is what a router's sizes want at 2048
    pairs and more, where most groups span two tiles (0.446 for
    ``megablox``'s 0.575 at 4096); one copy a matrix or eight made no
    difference, a row tile of 64 is 0.05 ms behind at 4096 and 256
    behind everywhere: a group's dot at 128 rows takes under its
    matrix's copy.
    At OLMoE's trainer's ``[65536, 2048] x [64, 2048, 1024]`` (1024
    rows a group) the kernel reads 1.96 ms at 128 and 1.93 at 256 for
    the compiler's 2.96 and ``megablox``'s 2.10: it is ahead FORWARD on
    the matrix unit's side too, so ``_MAX_ROWS_A_GROUP`` marks where the
    bytes stop bounding a product (the chip's 240 operations a byte),
    not where the kernel stops winning; the trainers keep
    ``lax.ragged_dot`` until their backward has kernels too.

    A chip's SHARE of the experts (``moe._held_rows``, since ISSUE 61
    under the same rule; the v5e, 2026-10-04, the same tool with
    ``--held-share``: of the ``M = N·K`` rows only the share's own lie
    in a group, the others behind the last one, which cost the kernel
    grid steps that do nothing; ``router`` sizes, row tile 128; gate or
    up / down, ms a call as above, GB/s of the matrices the groups with
    rows read):

    ======== ================== ===== ======== ============= ================= =========
    cell     rhs                M     in group ragged_dot    kernel@128        GB/s
    ======== ================== ===== ======== ============= ================= =========
    Nemotron [128, 1024, 2688]  2816  704      2.818 / 2.670 **0.991 / 0.992** 700 / 699
    Nemotron (5.5 MB a matrix)  5632  1408     3.653 / 3.426 **1.021 / 1.025** 691 / 687
    Nemotron                    11264 2816     3.736 / 3.559 **1.046 / 1.039** 674 / 678
    Nemotron                    22528 5632     3.905 / 3.910 **1.079 / 1.073** 653 / 657
    ling     [128, 2560, 768]   512   128      1.184 / 1.142 **0.469 / 0.460** 646 / 659
    ling     (3.9 MB)           2048  512      1.846 / 1.777 **0.699 / 0.707** 687 / 679
    ling                        4096  1024     1.925 / 1.873 **0.725 / 0.730** 688 / 684
    ling                        8192  2048     1.975 / 1.909 **0.757 / 0.755** 665 / 666
    trinity  [32, 3072, 3072]   128   16       0.488 / 0.486 **0.422 / 0.419** 627 / 630
    trinity  (18.9 MB)          1024  128      1.917 / 1.915 **0.844 / 0.842** 694 / 695
    trinity                     2048  256      1.975 / 1.978 **0.872 / 0.875** 693 / 690
    trinity                     4096  512      1.971 / 1.970 **0.885 / 0.867** 683 / 697
    ======== ================== ===== ======== ============= ================= =========

    (A step is each cell's first row, its largest chunk the last; at a
    step 2 of Nemotron's 128 groups, 51 of ling's and 18 of trinity's
    32 were empty.) The kernel holds 627-700 GB/s at every shape, a
    matrix of 18.9 MB, two and a half times LFM2's, included: two of
    them are the most the buffer holds. The compiler's kernels pay
    their toll a group here too (Nemotron's 21-31 us a group over 128,
    ling's 9-15, trinity's 60 over 32 at a chunk), and only trinity's
    step, 14 matrices touched of 32, is near even (0.49 ms for 0.42). The rows behind the last group add
    a tenth from the step to the largest chunk (176 row tiles' steps
    for 44 tiles of rows at Nemotron's). No shape loses, so the rule
    stands as it was. Kimi's share (``[12, 7168, 2048]``: two matrices
    of 29.4 MB are 58.7 MB, over ``_MATRIX_BUFFER_BYTES``) and
    mellum's trainer's (``[16, 2304, 896]`` at 24 576 rows and more:
    1536 a group) fall on the compiler's side by their shapes."""
    groups, k, n = rhs.shape
    return (lhs.shape[0] < _MAX_ROWS_A_GROUP * groups
            and k % _LANES == 0 and n % _LANES == 0
            and lhs.dtype == rhs.dtype
            and 2 * k * n * rhs.dtype.itemsize <= _MATRIX_BUFFER_BYTES)


def _schedule(sizes, rows: int, tm: int):
    """The grid's steps from ``sizes`` [G]: a group takes one step for
    each row tile it has a row in, in order; ``rows // tm + G - 1``
    steps hold any sizes, and the steps behind the last real one repeat
    it (same group, same tile: nothing is fetched or written for them).
    Returns int32 ``(group [S], tile [S], half [S], ahead [S], starts
    [G + 1], n_steps [1])``: the group and row tile of each step, the
    half of the buffer its matrix is in (the parity of the group's
    place among those that have rows), the next group that has rows (-1
    behind the last) and each group's first row."""
    groups = sizes.shape[0]
    steps = -(-rows // tm) + groups - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    visits = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    step_end = jnp.cumsum(visits)
    n_steps = step_end[-1]
    at = jnp.arange(steps, dtype=jnp.int32)

    def group_at(step):                    # the group whose steps hold it
        return jnp.minimum((step_end[None, :] <= step[:, None]).sum(1),
                           groups - 1).astype(jnp.int32)

    real = jnp.minimum(at, jnp.maximum(n_steps - 1, 0))
    group = group_at(real)
    tile = first[group] + real - (step_end - visits)[group]
    place = jnp.cumsum(visits > 0) - 1     # among the groups with rows
    after = step_end[group]                # the next group's first step
    ahead = jnp.where(after < n_steps,
                      group_at(jnp.minimum(after, steps - 1)), -1)
    return (group, tile.astype(jnp.int32),
            (place[group] % 2).astype(jnp.int32), ahead.astype(jnp.int32),
            jnp.concatenate([starts, ends[-1:]]).astype(jnp.int32),
            n_steps.reshape(1).astype(jnp.int32))


def _kernel(group_ref, tile_ref, half_ref, ahead_ref, start_ref, steps_ref,
            lhs_ref, rhs_ref, out_ref, buf, sem):
    """Grid step ``s``: row tile ``tile_ref[s]`` of ``lhs`` times the
    matrix of group ``group_ref[s]``, stored in the rows of the tile
    that are the group's. A group's first step starts the copy of the
    NEXT group's matrix into the other half and waits for its own; a
    tile's first step zeroes the rows that are no group's yet, a later
    one keeps what the groups before it stored."""
    s = pl.program_id(0)
    before = jnp.maximum(s - 1, 0)
    g, half = group_ref[s], half_ref[s]
    new_group = (s == 0) | (group_ref[before] != g)
    new_tile = (s == 0) | (tile_ref[before] != tile_ref[s])

    def matrix(of, into):
        return pltpu.make_async_copy(rhs_ref.at[of], buf.at[into],
                                     sem.at[into])

    @pl.when(s == 0)
    def _first():
        matrix(g, half).start()

    @pl.when(new_group)
    def _arrive():
        @pl.when(ahead_ref[s] >= 0)
        def _next():
            matrix(ahead_ref[s], 1 - half).start()

        matrix(g, half).wait()

    @pl.when(s < steps_ref[0])
    def _product():
        rows = out_ref.shape[0]
        out = jnp.dot(lhs_ref[...], buf[half],
                      preferred_element_type=jnp.float32
                      ).astype(out_ref.dtype)
        row = tile_ref[s] * rows + lax.broadcasted_iota(
            jnp.int32, out.shape, 0)
        own = (row >= start_ref[g]) & (row < start_ref[g + 1])

        @pl.when(new_tile)
        def _open():
            out_ref[...] = jnp.where(own, out, 0)

        @pl.when(jnp.logical_not(new_tile))
        def _join():
            out_ref[...] = jnp.where(own, out, out_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _call(lhs, rhs, group, tile, half, ahead, starts, n_steps, *, tm: int,
          interpret: bool):
    """The Pallas call, jitted of itself: a program of many products
    traces and lowers the kernel once a shape, not once a product
    (``ops/paged_decode.py::_decode``)."""
    (m, k), n = lhs.shape, rhs.shape[2]
    item = lhs.dtype.itemsize
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(group.shape[0],),
            in_specs=[
                pl.BlockSpec((tm, k), lambda s, g, t, *_: (t[s], 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tm, n), lambda s, g, t, *_: (t[s], 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k, n), rhs.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # both halves of the matrices, the row tiles of lhs and out
            # twice each, the float32 product, and room for the compiler
            vmem_limit_bytes=(2 * k * n * item + 2 * tm * (k + n) * item
                              + 2 * tm * n * 4 + (16 << 20))),
        interpret=interpret,
        name="hvd_grouped_matmul",
    )(group, tile, half, ahead, starts, n_steps, lhs, rhs)


def _forward(lhs, rhs, sizes, tm: int = _ROW_TILE,
             interpret: Optional[bool] = None):
    if (lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]
            or sizes.shape != rhs.shape[:1] or lhs.dtype != rhs.dtype):
        raise ValueError(
            f"grouped_matmul: lhs {lhs.shape} {lhs.dtype}, rhs {rhs.shape} "
            f"{rhs.dtype}, sizes {sizes.shape}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _call(lhs, rhs, *_schedule(sizes.astype(jnp.int32), lhs.shape[0],
                                      tm), tm=tm, interpret=interpret)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, sizes):
    """``lax.ragged_dot(lhs, rhs, sizes)`` through the kernel: ``lhs``
    ``[M, K]`` sorted by group, ``rhs`` ``[G, K, N]`` of the same dtype,
    ``sizes`` ``[G]`` integers with ``sizes.sum() <= M``. Returns ``[M,
    N]`` in that dtype; the rows behind the last group are unspecified
    (zeros in a row tile that a group reaches, unwritten behind it).
    Differentiable: the cotangents are ``lax.ragged_dot``'s own at the
    same operands (the compiler's kernels: backward kernels are a later
    change's)."""
    return _forward(lhs, rhs, sizes)


def _grouped_matmul_bwd(res, g):
    lhs, rhs, sizes = res
    d_lhs, d_rhs = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes),
                           lhs, rhs)[1](g)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(
    lambda lhs, rhs, sizes: (_forward(lhs, rhs, sizes), (lhs, rhs, sizes)),
    _grouped_matmul_bwd)
