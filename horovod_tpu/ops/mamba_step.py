"""A decode step's selective scan on each row's own state, where it lies.

One Pallas call a layer (``hvd_mamba_step`` in a device trace): for each
row of the batch, the slot's float32 state ``[N, Di]`` out of the pool
in HBM, once, one position of the recurrence, and the state back to the
same place, once. The pool is aliased in to out and addressed through
the prefetched ``(layer, slots[b])``: a slot that is not in the batch is
not touched. The XLA form it replaces (``serve/decode.py::mamba_step``
over every slot of the layer, the batch's rows carried to their slots)
is ``mamba_scan``'s position and the tests' reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def taken(n_state: int, d_inner: int) -> bool:
    """Whether a layer of ``n_state`` state rows over ``d_inner``
    channels steps through the kernel: on a TPU a state has to be whole
    (8, 128) tiles; the interpreter on the CPU takes any."""
    return jax.default_backend() == "cpu" or (
        n_state % 8 == 0 and d_inner % 128 == 0)


#: Rows of the batch a block of the per-row inputs holds: a float32
#: tile's. A batch is padded to whole blocks with rows that step slot 0,
#: the null slot, by 0.
_ROWS = 8


def _channels(d_inner: int) -> int:
    """Channels the kernel's loop holds in registers at a time. On the
    v5e (2026-10-01, ``tools/mamba_scan_sweep.py --step``: rows at
    shuffled slots of a pool ``[26, 257, 16, 5120]``, every layer in
    turn in one program; ms a layer and the GB/s of the rows' states
    read and written, 168 MB a layer at 256 rows; ``xla`` is the form
    it replaced, every slot's state where it lies with the rows carried
    to their slots, alone: inside the decode program the compiler read
    the state twice a layer and it took 0.72 ms):

    ==== ===== ============ ====================== ===============
    rows xla   a grid step  channels a loop        GB/s
    ==== ===== ============ ====================== ===============
    256  0.390 **5120**     256 / **512** / 5120   618 / 620 / 619
                            0.271 / 0.271 / 0.271
    256        2560         512: 0.341             492
    256        1280         256: 0.523             321
    64   0.379 **5120**     256 / **512** / 5120   585 / 592 / 588
                            0.072 / 0.071 / 0.071
    64         2560 / 1280  0.089 / 0.133          474 / 315
    ==== ===== ============ ====================== ===============

    A row's whole state a grid step: the copy of 328 KB in and out sets
    the time (1.06 us a row where the memory's 819 GB/s would take
    0.80), the loop's width does not, and a state cut into channel
    blocks of 2560 or 1280 pays a grid step's 0.35 us twice or four
    times a row. The pipeline is Pallas's own, two buffers a block
    (Mosaic takes no third: "only single and double buffering are
    supported"); explicit copies were not built, since what is left
    above the memory's own time is a quarter."""
    for width in (512, 256):
        if d_inner % width == 0:
            return width
    # Mosaic refuses a row of 128 at a traced sublane ("dynamic load
    # with unaligned indices"): such a width goes whole
    return d_inner


def _kernel(layer_ref, slots_ref, uc_ref, step_ref, b_ref, c_ref, a_ref,
            s_ref, y_ref, o_ref, *, channels: int):
    """Row ``r`` of the block of rows ``g``, channel block ``k`` (grid
    ``(g, k, r)``): the slot's state is ``s_ref``, its place in the pool
    ``o_ref``; the per-row inputs are row ``r`` of their blocks, which
    stay where they are while ``r`` runs. ``b`` and ``c`` arrive as
    rows ``[1, N]`` and are turned to columns ``[N, 1]`` through the
    diagonal of an ``[N, N]`` tile (a select and a sum along the
    lanes)."""
    del layer_ref, slots_ref          # the index maps' own
    row = pl.ds(pl.program_id(2), 1)
    n_state, d_inner = a_ref.shape
    diagonal = (lax.broadcasted_iota(jnp.int32, (n_state, n_state), 0)
                == lax.broadcasted_iota(jnp.int32, (n_state, n_state), 1))

    def column(ref):
        return jnp.sum(jnp.where(diagonal, ref[row, :], 0.0), axis=1,
                       keepdims=True)

    b, c = column(b_ref), column(c_ref)

    def block(j, _):
        at = pl.ds(pl.multiple_of(j * channels, channels), channels)
        step = step_ref[row, at]
        state = (jnp.exp(step * a_ref[:, at]) * s_ref[:, at]
                 + (step * uc_ref[row, at]) * b)
        o_ref[:, at] = state
        y_ref[row, at] = jnp.sum(state * c, axis=0, keepdims=True)
        return _

    lax.fori_loop(0, d_inner // channels, block, 0, unroll=True)


def mamba_step(u, step, a, b, c, pool, layer, slots, *,
               channels: Optional[int] = None, block: Optional[int] = None,
               interpret: Optional[bool] = None):
    """One position of the selective scan for each row of the batch, on
    the row's own state in ``pool`` ``[layers, n_slots, N, Di]`` float32
    at ``(layer, slots[i])``: ``u`` (the convolved input) and ``step``
    (``Delta``) ``[B, Di]``, ``a`` ``[N, Di]``, ``b`` and ``c``
    ``[B, N]``, all float32; ``layer`` a traced int32 (the layers of a
    stack share one compiled kernel), ``slots`` ``[B]`` int32. A channel
    d and a state row n, as ``serve/decode.py::mamba_step`` term for
    term:

        s = exp(step a) s + (step u) b
        y = sum_n s c

    Returns ``(y [B, Di], pool)``, the pool the one given (aliased in to
    out: donate it) with the B states stepped and no other byte of it
    read or written. Rows that share a slot (a bucket's padding at the
    null slot) leave in it the state of one of them stepped from one of
    the states it held: it holds nothing. ``channels`` and ``block``
    (the channels a grid step holds: all of them) are the sweep's; a
    program leaves them alone."""
    B, d_inner = u.shape
    n_layers, n_slots, n_state, pool_inner = pool.shape
    if (pool_inner != d_inner or a.shape != (n_state, d_inner)
            or step.shape != u.shape or b.shape != (B, n_state)
            or c.shape != b.shape or slots.shape != (B,)
            or pool.dtype != jnp.float32):
        raise ValueError(
            f"mamba_step: u {u.shape}, step {step.shape}, a {a.shape}, b "
            f"{b.shape}, c {c.shape}, slots {slots.shape}, pool {pool.shape} "
            f"{pool.dtype}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block = block or d_inner
    channels = channels or _channels(block)
    if d_inner % block or block % channels:
        raise ValueError(f"mamba_step: {channels} channels a loop and "
                         f"{block} a grid step do not divide {d_inner}")
    rows, f32 = _ROWS, jnp.float32
    pad = [(0, -B % rows), (0, 0)]
    u, step, b, c = (jnp.pad(x.astype(f32), pad) for x in (u, step, b, c))
    slots = jnp.pad(slots.astype(jnp.int32), pad[0])   # the null slot's

    def by_rows():
        return pl.BlockSpec((rows, block), lambda g, k, r, *_: (g, k))

    def whole_rows():
        return pl.BlockSpec((rows, n_state), lambda g, k, r, *_: (g, 0))

    def state():
        return pl.BlockSpec(
            (None, None, n_state, block),
            lambda g, k, r, layer, slots: (layer[0], slots[g * rows + r], 0,
                                           k))

    y, pool = pl.pallas_call(
        functools.partial(_kernel, channels=channels),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(u.shape[0] // rows, d_inner // block, rows),
            in_specs=[by_rows(), by_rows(), whole_rows(), whole_rows(),
                      pl.BlockSpec((n_state, block),
                                   lambda g, k, r, *_: (0, k)),
                      state()],
            out_specs=[by_rows(), state()]),
        out_shape=[jax.ShapeDtypeStruct(u.shape, f32),
                   jax.ShapeDtypeStruct(pool.shape, f32)],
        # operands count the prefetched scalars: the pool is the eighth
        input_output_aliases={7: 1},
        cost_estimate=pl.CostEstimate(
            flops=6 * B * n_state * d_inner,
            transcendentals=B * n_state * d_inner,
            bytes_accessed=4 * (2 * B * n_state + 3 * B + n_state) * d_inner),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
        name="hvd_mamba_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots, u, step, b, c,
      a.astype(f32), pool)
    return y[:B], pool


#: Slots a block of :func:`shift_rows` holds: a bf16 tile's rows.
_SLOTS = 16


def _rows_kernel(layer_ref, row_of_ref, new_ref, rows_ref, o_ref, newest, *,
                 d_inner: int):
    """Block ``t`` of ``_SLOTS`` slots: the slots that stepped (a row of
    the batch at ``row_of_ref``) drop their oldest row and take their
    batch row of ``new_ref``, the others are copied. The shift is along
    the lanes by whole tiles; a slot is a sublane, and which sublanes
    stepped is a select."""
    del layer_ref
    first = pl.program_id(0) * _SLOTS
    slot = lax.broadcasted_iota(jnp.int32, (_SLOTS, 1), 0)
    stepped = jnp.zeros((_SLOTS, 1), jnp.int32)
    for j in range(_SLOTS):
        at = row_of_ref[first + j]
        newest[j:j + 1, :] = new_ref[pl.ds(jnp.maximum(at, 0), 1), :]
        stepped = jnp.where(slot == j, at, stepped)
    stepped = stepped >= 0
    kept = rows_ref.shape[1] - d_inner
    if kept:
        o_ref[:, :kept] = jnp.where(stepped, rows_ref[:, d_inner:],
                                    rows_ref[:, :kept])
    o_ref[:, kept:] = jnp.where(stepped, newest[...].astype(o_ref.dtype),
                                rows_ref[:, kept:])


def shift_rows(rows, layer, slots, new, *, interpret: Optional[bool] = None):
    """The convolution's rows of one layer after one more position of
    the batch: ``rows`` ``[layers, n_slots, (K - 1) Di]`` (a slot's
    newest ``K - 1`` rows before the convolution, end to end), of which
    the slots ``slots`` [B] of ``layer`` drop their oldest ``Di`` values
    and take ``new`` [B, Di] as their last. Returns the array given
    (aliased in to out: donate it), no other layer of it read or
    written.

    One Pallas call (``hvd_mamba_rows``) over the layer's slots in
    blocks of 16, every block read and written once where it lies: a
    slot's rows are one sublane of a tile of ``[8 slots, 128 channels]``
    pairs, which no copy addresses alone (Mosaic: "slice shape along
    dimension 1 must be aligned to tiling (8), but is 1"), so the kernel
    goes by the slots and asks each for its row of the batch
    (``row_of``: prefetched, -1 where a slot did not step), out of the
    batch's new rows held whole in VMEM as float32 (``B Di`` values in
    two buffers: 10.5 MB at 256 rows of 5120, of the 32 MiB asked for).
    Rows that share a slot (the null slot's) leave one of theirs.

    On the v5e (2026-10-01, ``tools/mamba_scan_sweep.py --step``, ms a
    layer of ``[26, 257, 15360]`` bf16 at 256 / 64 rows, each beside
    the gather of the rows before the convolution, 0.013 alone): the
    scatter by slot it replaced 0.359 / 0.096 (one op over the whole
    array a layer inside the decode program: 0.31 in PR 47's trace);
    the same select written in XLA 0.091 / 0.059 (and in a decode
    program of 26 layers the compiler copied the whole array for two
    of them); **this kernel 0.068 / 0.042** (0.026 inside the decode
    program: PR 48's trace); with the new rows laid out by slot in XLA
    first, and no row of the batch looked up here, 0.079 / 0.046."""
    n_layers, n_slots, width = rows.shape
    B, d_inner = new.shape
    if width % d_inner or slots.shape != (B,):
        raise ValueError(f"shift_rows: rows {rows.shape}, new {new.shape}, "
                         f"slots {slots.shape}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    blocks = pl.cdiv(n_slots, _SLOTS)
    row_of = jnp.full((blocks * _SLOTS,), -1, jnp.int32).at[slots].set(
        jnp.arange(B, dtype=jnp.int32))

    def block():
        return pl.BlockSpec((None, _SLOTS, width),
                            lambda t, layer, row_of: (layer[0], t, 0))

    return pl.pallas_call(
        functools.partial(_rows_kernel, d_inner=d_inner),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks,),
            in_specs=[pl.BlockSpec((B, d_inner), lambda t, *_: (0, 0)),
                      block()],
            out_specs=block(),
            scratch_shapes=[pltpu.VMEM((_SLOTS, d_inner), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="hvd_mamba_rows",
    )(jnp.asarray(layer, jnp.int32).reshape(1), row_of,
      new.astype(jnp.float32), rows)

