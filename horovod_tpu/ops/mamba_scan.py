"""A chunk's selective scan with its state held on the chip, as far as
the chunk's real positions go.

One Pallas call a layer (``hvd_mamba_scan`` in a device trace): a block
of channels of the float32 state ``[N, Di]`` is read from HBM once, is
carried in registers over every position of the chunk up to ``length``,
and is written once. A time block wholly past ``length`` reads nothing
and writes zeros. The XLA form it replaces
(``serve/decode.py::mamba_scan``, a ``lax.scan`` a position at a time
whose state goes through HBM every eight positions, the bucket's padding
included) stays as the form of shapes the kernel does not take, as the
tests' reference and as the sweep's baseline.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: A float32 tile: sublanes (positions of a chunk, rows of a state) by
#: lanes (channels).
_TILE, _LANES = 8, 128


def taken(n_state: int, d_inner: int, positions: int) -> bool:
    """Whether a chunk of ``positions`` of a layer of ``n_state`` state
    rows over ``d_inner`` channels scans through the kernel: on a TPU
    the state has to be whole (8, 128) tiles and the chunk whole sublane
    tiles; the interpreter on the CPU takes any."""
    return jax.default_backend() == "cpu" or (
        n_state % _TILE == 0 and d_inner % _LANES == 0
        and positions % _TILE == 0)


def _first_divisor(of: int, sizes) -> int:
    return next((size for size in sizes if of % size == 0), of)


def _kernel(length_ref, u_ref, step_ref, b_ref, c_ref, a_ref, s_ref, y_ref,
            o_ref, b_cols, c_cols, *, width: int, unroll: int):
    """Row ``r`` of the batch, channel block ``k``, time block ``t``
    (grid ``(r, k, t)``, ``t`` fastest): ``o_ref`` is the state's block
    ``[N, channels]``, the same over ``t``; it takes ``s_ref`` at the
    first time block and each later block goes on from it. ``b`` and
    ``c`` arrive as rows ``[1, N]`` a position and are turned to columns
    ``[N, 1]`` through the diagonal of an ``[N, N]`` tile (a select and
    a sum along the lanes, as ``ops/mamba_step.py::_kernel`` does), once
    a position and grid step, and kept across the lanes in ``b_cols`` /
    ``c_cols``; then ``width`` channels at a time run the block's real
    positions with their state in registers."""
    t = pl.program_id(2)
    block, channels = u_ref.shape
    n_state = a_ref.shape[0]
    real = jnp.clip(length_ref[0] - t * block, 0, block)

    @pl.when(t == 0)
    def _():
        o_ref[...] = s_ref[...]

    @pl.when(real < block)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    diagonal = (lax.broadcasted_iota(jnp.int32, (n_state, n_state), 0)
                == lax.broadcasted_iota(jnp.int32, (n_state, n_state), 1))

    def columns(g, _):
        # a sublane tile of positions an iteration, the last tile's
        # padding with it: a lane sum waits long for its result, and
        # eight of them in flight hide that
        for i in range(_TILE):
            p = jnp.minimum(g * _TILE + i, block - 1)
            for ref, cols in ((b_ref, b_cols), (c_ref, c_cols)):
                column = jnp.sum(
                    jnp.where(diagonal, ref[pl.ds(p, 1), :], 0.0), axis=1,
                    keepdims=True)
                cols[p] = jnp.broadcast_to(column, cols.shape[1:])
        return _

    lax.fori_loop(0, pl.cdiv(real, _TILE), columns, 0)

    def across(cols, p):
        return jnp.concatenate([cols[p]] * (width // cols.shape[2]), axis=1)

    def some_channels(j, _):
        at = pl.ds(pl.multiple_of(j * width, width), width)
        a = a_ref[:, at]

        def position(p, state):
            row = pl.ds(p, 1)
            step = step_ref[row, at]
            state = (jnp.exp(step * a) * state
                     + (step * u_ref[row, at]) * across(b_cols, p))
            y_ref[row, at] = jnp.sum(state * across(c_cols, p), axis=0,
                                     keepdims=True)
            return state

        def positions(g, state):
            for i in range(unroll):
                state = position(g * unroll + i, state)
            return state

        whole = real // unroll
        state = lax.fori_loop(0, whole, positions, o_ref[:, at])
        o_ref[:, at] = lax.fori_loop(whole * unroll, real, position, state)
        return _

    if channels == width:
        # no traced lane offset: Mosaic refuses one of a single tile's
        # width ("dynamic load with unaligned indices")
        some_channels(0, 0)
    else:
        lax.fori_loop(0, channels // width, some_channels, 0)


def mamba_scan(u, step, a, b, c, state, length, *,
               channels: Optional[int] = None, block: Optional[int] = None,
               width: Optional[int] = None, unroll: int = 8,
               interpret: Optional[bool] = None):
    """The selective scan of a mamba layer over the first ``length``
    positions of a chunk: ``u`` (the convolved input) and ``step``
    (``Delta``) ``[B, T, Di]``, ``a`` ``[N, Di]``, ``b`` and ``c``
    ``[B, T, N]``, ``state`` ``[B, N, Di]``, all float32; ``length`` a
    traced int32, the same for every row. A channel d and a state row n,
    as ``serve/decode.py::mamba_scan`` term for term:

        s_t = exp(step_t a) s_{t-1} + (step_t u_t) b_t
        y_t = sum_n s_t c_t

    Returns ``(y [B, T, Di], the state after position length - 1)``, the
    state's array the one given (aliased in to out). Positions from
    ``length`` on are not read, whatever they hold, and their ``y`` is
    0; a ``length`` of 0 returns the state given.

    ``channels`` (a grid step's), ``block`` (positions a grid step),
    ``width`` (channels whose state the loop carries in registers) and
    ``unroll`` (positions a loop iteration) are the sweep's; a program
    leaves them alone."""
    B, T, d_inner = u.shape
    n_state = a.shape[0]
    if (step.shape != u.shape or a.shape != (n_state, d_inner)
            or b.shape != (B, T, n_state) or c.shape != b.shape
            or state.shape != (B, n_state, d_inner)
            or state.dtype != jnp.float32):
        raise ValueError(
            f"mamba_scan: u {u.shape}, step {step.shape}, a {a.shape}, b "
            f"{b.shape}, c {c.shape}, state {state.shape} {state.dtype}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    channels = channels or _channels(d_inner)
    block = block or _first_divisor(T, _POSITIONS)
    width = width or _first_divisor(channels, _WIDTHS)
    if d_inner % channels or channels % width or T % block:
        raise ValueError(
            f"mamba_scan: {width} channels a loop and {channels} a grid step "
            f"do not divide {d_inner}, or {block} positions {T}")
    return _scan(u, step, a, b, c, state, length, channels=channels,
                 block=block, width=width, unroll=unroll, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "channels", "block", "width", "unroll", "interpret"))
def _scan(u, step, a, b, c, state, length, *, channels: int, block: int,
          width: int, unroll: int, interpret: bool):
    """The Pallas call, jitted of itself: a program of 26 mamba layers
    traces and lowers the kernel once, not once a layer (on the chip's
    host a call site took 0.18 s to trace and 0.67 s to lower, 130 s of
    a warm set-up over the jamba cell's six chunk programs; XLA inlines
    the call)."""
    B, T, d_inner = u.shape
    n_state = a.shape[0]
    f32 = jnp.float32
    lanes = _LANES if width % _LANES == 0 else width

    def last(length):
        # the last time block that holds a real position: the blocks
        # past it ask for this one again, which is not fetched twice
        return jnp.maximum(length[0] - 1, 0) // block

    def by_channel():
        return pl.BlockSpec(
            (None, block, channels),
            lambda r, k, t, length: (r, jnp.minimum(t, last(length)), k))

    def by_state_row():
        return pl.BlockSpec(
            (None, block, n_state),
            lambda r, k, t, length: (r, jnp.minimum(t, last(length)), 0))

    def carried():
        return pl.BlockSpec((None, n_state, channels),
                            lambda r, k, t, length: (r, 0, k))

    held = 4 * (2 * (3 * block * channels + 2 * block * n_state
                     + 3 * n_state * channels) + 2 * block * n_state * lanes)
    y, state = pl.pallas_call(
        functools.partial(_kernel, width=width, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, d_inner // channels, T // block),
            in_specs=[by_channel(), by_channel(), by_state_row(),
                      by_state_row(),
                      pl.BlockSpec((n_state, channels),
                                   lambda r, k, t, length: (0, k)),
                      carried()],
            out_specs=[pl.BlockSpec((None, block, channels),
                                    lambda r, k, t, length: (r, t, k)),
                       carried()],
            scratch_shapes=[pltpu.VMEM((block, n_state, lanes), f32)] * 2),
        out_shape=[jax.ShapeDtypeStruct(u.shape, f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands count the prefetched scalar: the state is the seventh
        input_output_aliases={6: 1},
        cost_estimate=pl.CostEstimate(
            flops=7 * B * T * n_state * d_inner,
            transcendentals=B * T * n_state * d_inner,
            bytes_accessed=4 * B * (3 * T * d_inner + 2 * T * n_state
                                    + 2 * n_state * d_inner)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(held + (4 << 20), 16 << 20)),
        interpret=interpret,
        name="hvd_mamba_scan",
    )(jnp.asarray(length, jnp.int32).reshape(1), u.astype(f32),
      step.astype(f32), b.astype(f32), c.astype(f32), a.astype(f32), state)
    return y, state


#: Positions a grid step holds and channels the loop carries in
#: registers: the first that divides (a shape none divides goes whole,
#: which the interpreter takes). See :func:`_channels` for the chip's
#: table.
_POSITIONS = (64, 32, 16, 8)
_WIDTHS = (512, 256)
#: The most channels a grid step holds: a chunk's rows of 64 positions
#: in, twice, and out, in two buffers each, are 12.6 MB at this many.
_MOST_CHANNELS = 8192


def _channels(d_inner: int) -> int:
    """Channels a grid step holds: all of them up to ``_MOST_CHANNELS``,
    else the most whole lane tiles that divide them. On the v5e
    (2026-10-01, ``tools/mamba_scan_sweep.py``: one chunk of 512
    positions at 5120 channels and 16 state rows, 26 layers in turn in
    one program; ms a layer at a ``length`` of 512 / 384; the XLA form
    ``decode.mamba_scan`` beside it 0.299 / 0.299, 0.151 at a chunk of
    256 and 0.078 at 128, the padding scanned like the rest):

    ========== ========= ========= ====== ===============
    a grid step          a loop
    -------------------- ---------------- ---------------
    channels   positions channels  unroll ms a layer
    ========== ========= ========= ====== ===============
    **5120**   **64**    **512**   **8**  0.115 / 0.090
    5120       128 / 32  512       8      0.114 / 0.117
    5120       64        256       8      0.130 / 0.103
    5120       64        1024      8      0.111 / 0.087
    5120       64        512       1      0.202 / 0.154
    5120       64        512       4      0.125 / 0.098
    5120       64        512       16     0.110 / 0.086
    2560       64        512       8      0.130 / 0.102
    1024       64        512       8      0.158 / 0.124
    512        64        512       8      0.206 / 0.162
    ========== ========= ========= ====== ===============

    (a chunk of 256 at 256 / 192: 0.059 / 0.047; of 128 at 128 / 96:
    0.032 / 0.025.) The vector unit sets the time, not the memory: the
    algorithm's 32 MB a layer would take 0.04 ms. A position of a
    ``[16, 512]`` block of state is about 72 vector operations on 8
    registers and takes 19 cycles, 0.104 ms a layer of ten blocks and
    512 positions; what a grid step adds is the columns of ``b`` and
    ``c``, 0.011 ms a layer and channel block (0.047 before eight of
    their lane sums were put in flight at a time, when a channel block
    of 1024 made the kernel slower than the XLA form: 0.332), so a
    grid step takes every channel. Its positions do not matter from 32
    to 128; 64 keep the blocks in fast memory under 8 MB. A loop of 16
    positions or 1024 channels is 4 % faster and twice the code: not
    taken."""
    most = min(d_inner, _MOST_CHANNELS) // _LANES * _LANES
    return next((channels for channels in range(most, 0, -_LANES)
                 if d_inner % channels == 0), d_inner)
