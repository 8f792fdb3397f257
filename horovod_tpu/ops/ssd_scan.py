"""A chunk's state-space duality with its state and its decays held on
the chip.

One Pallas call a layer (``hvd_ssd_scan`` in a device trace), after
``ops/mamba_scan.py`` and ``ops/state_step.py``: for each block of
heads, the float32 state tile ``[heads, P, N]`` is read from HBM at the
first time block (zeros, or the slot's state), stays in VMEM over the
chunk's blocks of ``block`` positions and is written once; a block's
``[block, block]`` scores and decays are VMEM tiles that never reach
HBM. A time block wholly past ``length`` reads nothing, computes
nothing and writes zeros. The XLA form it replaces
(``serve/decode.py::ssd_scan``: a ``lax.scan`` over the blocks, each
iteration a cumulative sum, a ``[1, 128, 128, 128]`` float32 decay of
8 MB written, multiplied into ``C B^T`` and read back, four einsums and
the 4 MB state through HBM) stays as the form of shapes the kernel does
not take, as the tests' reference and as the sweep's baseline.

One wrapper (:func:`_call`: grid, addressing, aliasing, the skipped
blocks) over one body (:func:`_ssd_body`); a kda layer's chunk
(``decode.kda_scan``) can be the second body, as
``ops/state_step.py::_call`` has two.

On the v5e (2026-10-05, ``tools/mamba_scan_sweep.py --scan --rule ssd``
at Nemotron's sizes: ``x [1, T, 128, 64]``, ``B`` and ``C`` ``[1, T, 8,
128]``, a resumed state ``[1, 128, 64, 128]``, blocks of 128, each form
with the layer's ``D x``; ms a layer as the difference of a program of
twenty layers and one of five, at a ``length`` of the bucket / an eighth
short of it / a block short of it; ``xla`` is ``decode.ssd_scan``):

==== ===================== ===================== =====================
T    xla                   heads a grid step: 8  16
==== ===================== ===================== =====================
1024 0.314 / 0.314 / -     0.229 / 0.213 / -     0.195 / 0.184 / -
512  0.129 / 0.129 / 0.134 0.128 / 0.129 / 0.113 0.111 / 0.111 / 0.100
256  0.082 / 0.081 / 0.083 0.069 / 0.075 / 0.052 0.058 / 0.060 / 0.041
==== ===================== ===================== =====================

==== ========================= =================
T    **32**                    64
==== ========================= =================
1024 **0.178 / 0.167 / -**     0.166 / 0.156 / -
512  **0.099 / 0.102 / 0.093** out of VMEM
256  **0.052 / 0.057 / 0.041**
==== ========================= =================

A grid step's fixed cost (the running sums at ``HIGHEST``, ``C B^T``,
the pipeline's copies) is spread over its heads, so more heads a step
are faster until the tiles no longer fit: at 64 heads the state tile
and the block of ``x`` are 2 MiB each and the program of a 512-chunk
ran out of VMEM (XLA had placed the call's ``y`` there), so a step
holds 32 (``_HEADS``) under ``_TILE_BYTES``. Both forms stand 0.134 (a
1024-chunk; 0.121 the others) from the recurrence a position at a time
in float64 at a ``y`` of 48.4, and 0.0069 at a state of 2.7-3.0: the
products of both round their float32 operands once on the way into
the matrix unit; the two forms differ by 0.037 in ``y`` and 0.003 in
the state; a skipped block's ``y`` is 0.0.

What the sweep does NOT show is what the call saves round it. Inside
the chunk programs of the Nemotron cell the XLA form's scope read 1.30
ms a layer (0.313 s over 48 chunk calls of five layers), four times the
form alone: ``y + D x`` is a ``[.., 128, 64]`` product, the compiler
works it with the positions' axis innermost (64 channels fill half a
register's lanes), and the compiled program held three copies of the
chunk's 32 MB rows a layer under the scope. With ``D x`` added inside
the call (``skip``) none is left there, and the scope reads 0.109 ms a
layer (0.0306 s over 56 chunk calls; traced runs, PR 64).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: A float32 tile: sublanes by lanes.
_TILE, _LANES = 8, 128
#: Heads a grid step holds (the module's table), at most, and the bytes
#: of its state tile and of its block of ``x`` at most: a grid step keeps
#: two of each and two of ``y``, and the default 16 MiB of VMEM hold
#: eight of 1 MiB beside the body's own tiles (64 heads of Nemotron's,
#: 2 MiB, ran out).
_HEADS, _TILE_BYTES = 32, 1 << 20


def _head_block(n_heads: int, groups: int, p: int, n: int,
                block: int) -> int:
    """The heads a grid step holds: the most, up to ``_HEADS`` and
    ``_TILE_BYTES``, that divide the layer's heads and lie inside one
    group or are whole groups."""
    per_group = n_heads // groups
    most = max(1, min(_HEADS, n_heads,
                      _TILE_BYTES // (4 * p * max(n, block))))
    return next(h for h in range(most, 0, -1)
                if n_heads % h == 0 and (per_group % h == 0
                                         or h % per_group == 0))


def taken(p: int, n: int, heads: int, groups: int, block: int,
          positions: int) -> bool:
    """Whether a chunk of ``positions`` of a Mamba-2 layer of ``heads``
    heads of ``[p, n]`` state in ``groups`` groups runs its SSD over
    blocks of ``block`` through the kernel: on a TPU the state has to be
    whole (8, 128) float32 tiles, a grid step's heads whole lanes of
    ``x`` and whole sublanes of ``Delta``, the chunk whole blocks and
    the block whole lanes; the interpreter on the CPU takes any."""
    if jax.default_backend() == "cpu":
        return True
    hb = _head_block(heads, groups, p, n, block)
    return (p % _TILE == 0 and n % _LANES == 0
            and (_LANES % p == 0 or p % _LANES == 0)
            and hb * p % _LANES == 0 and (hb % _TILE == 0 or hb == heads)
            and block % _LANES == 0 and positions % block == 0)


def _ssd_body(p: int, per_group: int, together: int):
    """The heads ``[j heads, (j + 1) heads)`` of one row over one block
    of positions, ``decode.ssd_scan``'s ``one_block`` term for term:
    ``x`` and ``y`` ``[block, heads * P]`` (a head's ``P`` channels side
    by side along the lanes), ``Delta`` and ``Delta a`` by column
    ``[block, heads]``, ``Delta a`` by row too ``[heads, block]``, ``B``
    and ``C`` ``[block, groups * N]`` of the block's groups, ``D`` a
    channel ``[1, heads * P]``, the state ``[heads * P, N]``.

    ``L``, the running sum of ``Delta a`` inside the block, is made on
    the matrix unit at full float32 (a product with a triangle of ones),
    by column for ``exp(L)`` along the positions and by row for ``D_ts =
    exp(L_t - L_s)``. ``together`` heads are one slab of lanes (two of
    64 channels: a whole vector register's width): their ``x``,
    ``exp(L)`` and ``Delta`` are made side by side, each head's ``(C B^T
    * D)`` multiplies the slab and keeps its own lanes, and ``C S^T``
    and ``B^T (..)`` are one product a slab."""
    def body(x_ref, dt_ref, dta_ref, dta_t_ref, b_ref, c_ref, skip_ref,
             s_ref, y_ref):
        block, heads = dt_ref.shape
        n = s_ref.shape[1]
        f32 = jnp.float32
        wide = together * p
        full = lax.Precision.HIGHEST

        def product(lhs, rhs, dims, precision=None):
            return lax.dot_general(lhs, rhs, (dims, ((), ())),
                                   precision=precision,
                                   preferred_element_type=f32)

        rows = lax.broadcasted_iota(jnp.int32, (block, block), 0)
        cols = lax.broadcasted_iota(jnp.int32, (block, block), 1)
        causal = rows >= cols
        ones = causal.astype(f32)
        dt = dt_ref[...]
        # L_t = sum_{s <= t} dt_s a, by column [block, heads] and by row
        run = product(ones, dta_ref[...], ((1,), (0,)), full)
        run_t = product(dta_t_ref[...], ones, ((1,), (1,)), full)
        # exp(L_end) a head, as a column over the heads
        lane = lax.broadcasted_iota(jnp.int32, run_t.shape, 1)
        to_last = jnp.exp(jnp.sum(jnp.where(lane == block - 1, run_t, 0.0),
                                  axis=1, keepdims=True))
        head_at = lax.broadcasted_iota(jnp.int32, (block, heads), 1)
        lane_at = lax.broadcasted_iota(jnp.int32, (block, wide), 1)
        row_at = lax.broadcasted_iota(jnp.int32, (wide, 1), 0)

        def column(of, h):
            return jnp.sum(jnp.where(head_at == h, of, 0.0), axis=1,
                           keepdims=True)

        def side_by_side(parts, at, along):
            """``parts[i]`` where ``at`` lies in head i of the slab."""
            out = jnp.broadcast_to(parts[-1], along)
            for i in range(together - 2, -1, -1):
                out = jnp.where(at < (i + 1) * p, parts[i], out)
            return out

        scores = {}
        for h0 in range(0, heads, together):
            g = h0 // per_group
            at_g = slice(g * n, (g + 1) * n)
            bb, cb = b_ref[:, at_g], c_ref[:, at_g]
            if g not in scores:                       # C B^T, once a group
                scores[g] = product(cb, bb, ((1,), (1,)))
            at = slice(h0 * p, h0 * p + wide)
            hs = range(h0, h0 + together)
            run_h = [column(run, h) for h in hs]
            along = (block, wide)
            run_x = side_by_side(run_h, lane_at, along)
            x = x_ref[:, at]
            drive = side_by_side([column(dt, h) for h in hs], lane_at,
                                 along) * x
            inside = [product(
                scores[g] * jnp.exp(jnp.where(
                    causal, run_h[i] - run_t[h:h + 1], -jnp.inf)),
                drive, ((1,), (0,))) for i, h in enumerate(hs)]
            state = s_ref[at]
            y_ref[:, at] = (
                side_by_side(inside, lane_at, along)
                + jnp.exp(run_x) * product(cb, state, ((1,), (1,)))
                + skip_ref[:, at] * x)
            to_end = jnp.exp(run_x[block - 1:] - run_x)
            s_ref[at] = (
                side_by_side([to_last[h:h + 1] for h in hs], row_at,
                             (wide, 1)) * state
                + product((drive * to_end).T, bb, ((1,), (0,))))
    return body


def _call(body, wide, narrow, narrow_t, by_group, layer, state, length, *,
          block: int, heads: int, groups: int, flops: int, interpret: bool):
    """``body(*wide, *narrow, *narrow_t, *by_group, *layer, state, y)``
    on every ``(row, block of heads, block of positions)``, the positions
    fastest: the head block's tile of ``state`` ``[B, H * rows, N]`` is
    the row's own at the first time block and stays in VMEM over the
    others (``state`` is aliased in to out; the body updates it in
    place), and a block of positions wholly past ``length`` is neither
    fetched nor computed: its ``y`` is zeros.

    ``wide``: arrays ``[B, T, H * w]`` of which a grid step holds
    ``block`` positions of its ``heads`` (``y`` is as the first);
    ``narrow``: one value a head and position, ``[B, H / heads, T,
    heads]``; ``narrow_t``: the same by row, ``[B, H, T]``;
    ``by_group``: ``[B, T, G * w]`` of which a grid step holds its
    heads' groups; ``layer``: the layer's own, ``[1, H * w]``. Returns
    ``(y, state)``."""
    B, T = wide[0].shape[:2]
    n_heads = narrow_t[0].shape[1]
    step = n_heads // heads

    def last(length):
        # the last time block that holds a real position: the blocks
        # past it ask for this one again, which is not fetched twice
        return jnp.maximum(length[0] - 1, 0) // block

    def then(t, length):
        return jnp.minimum(t, last(length))

    # a grid step holds its heads' groups: one, or those of its heads
    held = max(1, heads * groups // n_heads)
    in_specs = [
        *(pl.BlockSpec((None, block, v.shape[2] // step),
                       lambda r, j, t, length: (r, then(t, length), j))
          for v in wide),
        *(pl.BlockSpec((None, None, block, heads),
                       lambda r, j, t, length: (r, j, then(t, length), 0))
          for _ in narrow),
        *(pl.BlockSpec((None, heads, block),
                       lambda r, j, t, length: (r, j, then(t, length)))
          for _ in narrow_t),
        *(pl.BlockSpec((None, block, v.shape[2] // groups * held),
                       lambda r, j, t, length: (
                           r, then(t, length),
                           j * heads * groups // (n_heads * held)))
          for v in by_group),
        *(pl.BlockSpec((1, v.shape[1] // step),
                       lambda r, j, t, length: (0, j)) for v in layer)]
    carried = pl.BlockSpec((None, state.shape[1] // step, state.shape[2]),
                           lambda r, j, t, length: (r, j, 0))
    n_in = len(in_specs)

    def kernel(length_ref, *refs):
        ins, (s_ref, y_ref, o_ref) = refs[:n_in], refs[n_in:]
        t = pl.program_id(2)

        @pl.when(t == 0)
        def _():
            o_ref[...] = s_ref[...]

        @pl.when(t * block >= length_ref[0])
        def _():
            y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(t * block < length_ref[0])
        def _():
            body(*ins, o_ref, y_ref)

    y, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, step, T // block),
            in_specs=in_specs + [carried],
            out_specs=[pl.BlockSpec((None, block, wide[0].shape[2] // step),
                                    lambda r, j, t, length: (r, t, j)),
                       carried]),
        out_shape=[jax.ShapeDtypeStruct(wide[0].shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operands count the prefetched scalar: the state is the last
        input_output_aliases={1 + n_in: 1},
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=B * T * n_heads * (block + 2),
            bytes_accessed=4 * (2 * state.size + wide[0].size + sum(
                v.size for v in (*wide, *narrow, *narrow_t, *by_group,
                                 *layer)))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="hvd_ssd_scan",
    )(jnp.asarray(length, jnp.int32).reshape(1), *wide, *narrow, *narrow_t,
      *by_group, *layer, state)
    return y, state


def ssd_scan(x, dt, a, b, c, state, length, *, block: int, skip=None,
             heads: Optional[int] = None):
    """Mamba-2's recurrence over the first ``length`` positions of a
    chunk, as ``serve/decode.py::ssd_scan`` over blocks of ``block``
    positions term for term: ``x`` ``[B, T, Hm, P]``, ``dt`` (``Delta``,
    >= 0, and 0 from ``length`` on: the caller's) ``[B, T, Hm]``, ``a``
    ``[Hm]``, ``b`` and ``c`` ``[B, T, G, N]`` (head h reads group ``h
    // (Hm / G)``), ``state`` ``[B, Hm, P, N]``, all float32; ``length``
    a traced int32, the same for every row. A ``T`` that is not whole
    blocks is padded to them here with a ``dt`` of 0 (decay 1, drive 0),
    as the XLA form pads it: only the interpreter is asked for one
    (:func:`taken`). A head:

        S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t
        y_t = S_t c_t  (+ skip x_t)

    Returns ``(y [B, T, Hm, P], the state after position length - 1)``,
    the state's array the one given (aliased in to out). With ``skip``
    (``D`` ``[Hm]``) the layer's ``D x`` is added here, as the tile of
    ``x`` lies in VMEM: added behind the call it is a ``[.., Hm, P]``
    product in XLA, which on the chip turns ``y``, ``x`` and the gate to
    the positions' axis innermost and back, four copies of 32 MB a
    layer at a chunk of 1024 where one is left, under ``mamba2_norm``
    (the module's notes). A block of positions
    wholly past ``length`` is not read, whatever it holds, and its ``y``
    is 0; a ``length`` of 0 returns the state given. Every product takes
    float32 operands at the precision the XLA form's einsums ask, or
    higher (the running sums at ``HIGHEST``), into float32.

    ``heads`` (a grid step's) is the sweep's; a program leaves it
    alone."""
    B, T, n_heads, p = x.shape
    groups, n = b.shape[2:]
    if (dt.shape != (B, T, n_heads) or a.shape != (n_heads,)
            or b.shape != (B, T, groups, n) or c.shape != b.shape
            or state.shape != (B, n_heads, p, n)
            or state.dtype != jnp.float32 or n_heads % groups
            or (skip is not None and skip.shape != (n_heads,))):
        raise ValueError(
            f"ssd_scan: x {x.shape}, dt {dt.shape}, a {a.shape}, b "
            f"{b.shape}, c {c.shape}, state {state.shape} {state.dtype}, "
            f"blocks of {block}")
    per_group = n_heads // groups
    heads = heads or _head_block(n_heads, groups, p, n, block)
    if n_heads % heads or (per_group % heads and heads % per_group):
        raise ValueError(
            f"ssd_scan: {heads} heads a grid step do not divide {n_heads}, "
            f"or are neither inside a group of {per_group} nor whole groups")
    if skip is None:
        skip = jnp.zeros((n_heads,), jnp.float32)
    return _scan(x, dt, a, b, c, skip, state, length, block=block,
                 heads=heads, interpret=jax.default_backend() == "cpu")


@functools.partial(jax.jit, static_argnames=("block", "heads", "interpret"))
def _scan(x, dt, a, b, c, skip, state, length, *, block: int, heads: int,
          interpret: bool):
    """The Pallas call, jitted of itself: a program of five mamba2
    layers, and the three buckets' programs of a process, trace and
    lower the kernel once a shape, not once a layer (as
    ``ops/mamba_scan.py::_scan``; XLA inlines the call)."""
    B, T, n_heads, p = x.shape
    groups, n = b.shape[2:]
    per_group = n_heads // groups
    f32 = jnp.float32
    # heads whose channels lie side by side in one vector register's
    # lanes, inside one group
    together = math.gcd(math.gcd(heads, per_group), max(1, _LANES // p))
    pad = -T % block
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c))
        T += pad
    dt = dt.astype(f32)
    dta = dt * a.astype(f32)

    def by_column(v):
        return v.reshape(B, T, n_heads // heads, heads).swapaxes(1, 2)

    y, state = _call(
        _ssd_body(p, per_group, together),
        (x.astype(f32).reshape(B, T, n_heads * p),),
        (by_column(dt), by_column(dta)), (dta.swapaxes(1, 2),),
        tuple(v.astype(f32).reshape(B, T, groups * n) for v in (b, c)),
        (jnp.repeat(skip.astype(f32), p).reshape(1, n_heads * p),),
        state.reshape(B, n_heads * p, n), length, block=block, heads=heads,
        groups=groups,
        flops=B * T * n_heads * (6 * p * n + 2 * p * block)
        + 2 * B * T * groups * n * block,
        interpret=interpret)
    return (y.reshape(x.shape)[:, :T - pad],
            state.reshape(B, n_heads, p, n))
