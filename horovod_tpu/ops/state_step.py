"""A decode step's recurrence on each row's own state, where it lies.

One Pallas call a layer (``hvd_state_step`` in a device trace), after
``ops/mamba_step.py``: for each row of the batch and each block of
heads, the slot's float32 state tile ``[heads, rows, cols]`` out of the
pool in HBM, once, the layer kind's update rule on it, the row's output
for those heads, and the tile back to the same place, once. The pool
``[layers, n_slots, H, rows, cols]`` is aliased in to out and addressed
through the prefetched ``(layer, slots[b])``: a slot that is not in the
batch is not touched.

Two rules, each the body its layer kind passes to the one wrapper
(:func:`_call`: grid, addressing, aliasing, cost estimate): Mamba-2's
SSD (:func:`ssd_step`, a scalar decay a head and a rank-1 drive) and
the delta rule of a kda layer (:func:`kda_step`, a decay a key channel
and ``v - S^T k``, which needs the whole head first). The XLA forms they
replace (``serve/decode.py::ssd_step`` and ``::kda_step`` over every
slot of the layer, the batch's rows carried to their slots) are the
fall-back of a shape :func:`taken` refuses, the tests' reference and
the sweep's baseline.

On the v5e (2026-10-04, ``tools/mamba_scan_sweep.py --step --rule ssd``
and ``--rule kda``: rows at shuffled slots of Nemotron's pool ``[5, 129,
128, 64, 128]`` and of ling's ``[6, 65, 32, 128, 128]``, every layer in
turn in one program; ms a layer and the GB/s of the rows' states read
and written, 1.07 GB a layer at 128 rows of ssd and 0.27 GB at 64 of
kda; ``xla`` is the form it replaced, every slot's state where it lies
with the rows carried to their slots, alone: inside the decode programs
the compiler took 2.66 and 0.67 ms a layer):

==== ==== ===== ===================================== ==================
rule rows xla   heads a grid step                     GB/s
==== ==== ===== ===================================== ==================
ssd  128  2.540 8 / 16 / 32 / **64**                  490 / 538 / 612 /
                2.192 / 1.997 / 1.753 / **1.701**     **631**
ssd  64   2.530 1.102 / 1.007 / 0.879 / **0.861**     487 / 533 / 611 /
                                                      **624**
kda  64   0.657 8 / **16** / 32                       542 / **622** /
                0.495 / **0.431** / 0.433             620
kda  16   0.654 0.139 / **0.123** / 0.123             483 / **545** / 544
==== ==== ===== ===================================== ==================

A grid step costs about 0.35 us beside its copies (as
``ops/mamba_step.py`` found), so a tile is large: 64 heads of ssd (2 MB,
which the default 16 MiB of VMEM hold twice in and twice out), 16 of
kda (1 MB; 32 bought nothing). What is left above the memory's own
819 GB/s is what a plain read-and-write pass of XLA leaves too (590
GB/s over 2 GiB). The bodies PR 62 timed beside these and did not
keep, at 32 heads of ssd and 16 of kda: ssd a head at a time with ``dt
x`` turned ``[heads, P]`` to ``[P, heads]`` and a head's column
broadcast along the lanes 478 GB/s, or through the diagonal of a ``[P,
P]`` tile 604, where the whole block's ``x[:, :, None]`` (Mosaic's own
relayout) gives 612; kda's
whole block at once (``e[:, :, None] * S``, sums over axis 1) 549 where
the head loop over turned inputs gives 622. On the chip every body's
states and outputs were bit for bit the XLA forms' on the sweep's
inputs (a gap of 0.0); the interpreter on the CPU sums in another order
(2e-6 at most in the tests).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def taken(rows: int, cols: int) -> bool:
    """Whether a layer whose heads each keep ``[rows, cols]`` of state
    steps through the kernel: on a TPU a head's state has to be whole
    (8, 128) tiles; the interpreter on the CPU takes any."""
    return jax.default_backend() == "cpu" or (
        rows % 8 == 0 and cols % 128 == 0)


def _call(body, by_head: Sequence, by_row: Sequence, pool, layer, slots, *,
          heads: int, width: int, flops: int):
    """``body(*by_head blocks, *by_row blocks, state, out, new state)``
    on every ``(row, block of heads)`` of the batch, the state tile
    ``[heads, rows, cols]`` of ``pool`` at ``(layer, slots[row])`` read
    into VMEM and written back to where it lay. ``by_head``: arrays
    ``[B, H, w]`` of which a grid step holds its ``heads`` rows;
    ``by_row``: arrays ``[B, g, w]`` a grid step holds whole (they stay
    in VMEM while the row's head blocks run). Returns ``(out [B, H,
    width], pool)``."""
    B = slots.shape[0]
    n_heads, rows, cols = pool.shape[2:]

    def state():
        return pl.BlockSpec(
            (None, None, heads, rows, cols),
            lambda r, j, layer, slots: (layer[0], slots[r], j, 0, 0))

    def head_rows(w):
        return pl.BlockSpec((None, heads, w), lambda r, j, *_: (r, j, 0))

    out, pool = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_heads // heads),
            in_specs=[*(head_rows(x.shape[2]) for x in by_head),
                      *(pl.BlockSpec((None,) + x.shape[1:],
                                     lambda r, j, *_: (r, 0, 0))
                        for x in by_row),
                      state()],
            out_specs=[head_rows(width), state()]),
        out_shape=[jax.ShapeDtypeStruct((B, n_heads, width), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, jnp.float32)],
        # operands count the two prefetched scalars: the pool is the last
        input_output_aliases={2 + len(by_head) + len(by_row): 1},
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=0,
            bytes_accessed=4 * (2 * B * n_heads * rows * cols
                                + sum(x.size for x in (*by_head, *by_row))
                                + B * n_heads * width)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=jax.default_backend() == "cpu",
        name="hvd_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      *by_head, *by_row, pool)
    return out, pool


def _refuse(name: str, why: bool, **shapes):
    if why:
        raise ValueError(f"{name}: " + ", ".join(
            f"{k} {getattr(v, 'shape', v)}" for k, v in shapes.items()))


def _heads(name: str, heads: Optional[int], n_heads: int, default: int):
    """The heads a grid step holds: the sweep's, or ``default`` where
    that divides the layer's heads (else all of them)."""
    if heads is None:
        heads = default if n_heads % default == 0 else n_heads
    if n_heads % heads:
        raise ValueError(f"{name}: {heads} heads a grid step do not divide "
                         f"{n_heads}")
    return heads


#: Heads of a Mamba-2 layer a grid step holds (the module's table).
_SSD_HEADS = 64


def _ssd_body(per_group: int):
    """The heads ``[j heads, (j + 1) heads)`` of one row: ``d`` (the
    decay, the same along its row) and ``xdt`` ``[heads, P]``, the
    row's ``b`` and ``c`` ``[G, N]`` of which head h reads group ``h //
    per_group``."""
    def body(layer_ref, slots_ref, d_ref, x_ref, b_ref, c_ref, s_ref, y_ref,
             o_ref):
        del layer_ref, slots_ref          # the index maps' own
        heads = s_ref.shape[0]
        first = pl.program_id(1) * heads
        # a block of heads lies inside one group, or holds whole groups
        for at in range(0, heads, per_group):
            hs = slice(at, min(at + per_group, heads))
            group = pl.ds((first + at) // per_group, 1)
            state = (d_ref[hs][:, :, None] * s_ref[hs]
                     + x_ref[hs][:, :, None] * b_ref[group][None])
            o_ref[hs] = state
            y_ref[hs] = jnp.sum(state * c_ref[group][None], axis=-1)
    return body


def ssd_step(x, dt, a, b, c, pool, layer, slots, *,
             heads: Optional[int] = None):
    """One position of Mamba-2's recurrence for each row of the batch,
    on the row's own state in ``pool`` ``[layers, n_slots, Hm, P, N]``
    float32 at ``(layer, slots[i])``: ``x`` ``[B, Hm, P]``, ``dt``
    (``Delta``) ``[B, Hm]``, ``a`` ``[Hm]``, ``b`` and ``c`` ``[B, G,
    N]`` (head h reads group ``h // (Hm / G)``), all float32; ``layer``
    a traced int32 (the layers of a stack share one compiled kernel),
    ``slots`` ``[B]`` int32. A head, as ``serve/decode.py::ssd_step``
    term for term (the decay and ``dt x`` are made here, in XLA):

        S = exp(dt a) S + (dt x) (x) b
        y = S c

    Returns ``(y [B, Hm, P], pool)``, the pool the one given (aliased
    in to out: donate it) with the B states stepped and no other byte
    of it read or written. Rows that share a slot (a bucket's padding
    at the null slot) leave in it the state of one of them stepped from
    one of the states it held: it holds nothing. ``heads`` is the
    sweep's; a program leaves it alone."""
    B, n_heads, p = x.shape
    groups, n = b.shape[1:]
    _refuse("ssd_step",
            pool.ndim != 5 or pool.shape[2:] != (n_heads, p, n)
            or pool.dtype != jnp.float32 or dt.shape != (B, n_heads)
            or a.shape != (n_heads,) or c.shape != b.shape
            or b.shape[0] != B or n_heads % groups or slots.shape != (B,),
            x=x, dt=dt, a=a, b=b, c=c, slots=slots, pool=pool,
            dtype=pool.dtype)
    per_group = n_heads // groups
    heads = _heads("ssd_step", heads, n_heads, _SSD_HEADS)
    if per_group % heads and heads % per_group:
        raise ValueError(f"ssd_step: {heads} heads a grid step are neither "
                         f"inside a group of {per_group} nor whole groups")
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)
    return _call(_ssd_body(per_group), (decay, dt[..., None] * x),
                 (b.astype(f32), c.astype(f32)), pool, layer, slots,
                 heads=heads, width=p, flops=5 * B * n_heads * p * n)


#: Heads of a kda layer a grid step holds (the module's table).
_KDA_HEADS = 16


def _kda_body(layer_ref, slots_ref, q_ref, k_ref, v_ref, e_ref, beta_ref,
              s_ref, y_ref, o_ref):
    """A block of heads of one row, a head at a time: ``q``, ``k``, ``e``
    (``exp(g)``) ``[heads, Dk]``, ``v`` and ``beta`` (the same along
    its row) ``[heads, Dv]``. A head's state ``[Dk, Dv]`` is whole in
    VMEM, so ``S'^T k``, ``S'^T q`` and the update are one read of it.
    What goes by key channel is turned once a block (``[heads, Dk]`` to
    ``[Dk, heads]``), so that a head's is a column along the state's
    rows."""
    del layer_ref, slots_ref          # the index maps' own
    by_key = [ref[...].T for ref in (q_ref, k_ref, e_ref)]
    kq = jnp.sum(k_ref[...] * q_ref[...], -1, keepdims=True)
    for h in range(s_ref.shape[0]):
        row = slice(h, h + 1)
        q, k, e = (x[:, row] for x in by_key)
        decayed = e * s_ref[h]
        sk = jnp.sum(decayed * k, axis=0, keepdims=True)
        sq = jnp.sum(decayed * q, axis=0, keepdims=True)
        u = beta_ref[row] * (v_ref[row] - sk)
        y_ref[row] = sq + kq[row] * u
        o_ref[h] = decayed + k * u


def kda_step(q, k, v, g, beta, pool, layer, slots, *,
             heads: Optional[int] = None):
    """One position of the delta rule for each row of the batch, on the
    row's own state in ``pool`` ``[layers, n_slots, H, Dk, Dv]`` float32
    at ``(layer, slots[i])``: ``q``, ``k``, ``g`` ``[B, H, Dk]``, ``v``
    ``[B, H, Dv]``, ``beta`` ``[B, H]``, all float32. A head, as
    ``serve/decode.py::kda_step`` term for term (``exp(g)`` is made
    here, in XLA):

        S' = Diag(exp(g)) S
        u  = beta (v - S'^T k)
        S  = S' + k u^T
        o  = S'^T q + (k . q) u

    Returns ``(o [B, H, Dv], pool)``; the pool, the slots and ``heads``
    as :func:`ssd_step`'s."""
    B, n_heads, dk = q.shape
    dv = v.shape[-1]
    _refuse("kda_step",
            pool.ndim != 5 or pool.shape[2:] != (n_heads, dk, dv)
            or pool.dtype != jnp.float32 or k.shape != q.shape
            or g.shape != q.shape or v.shape != (B, n_heads, dv)
            or beta.shape != (B, n_heads) or slots.shape != (B,),
            q=q, k=k, v=v, g=g, beta=beta, slots=slots, pool=pool,
            dtype=pool.dtype)
    heads = _heads("kda_step", heads, n_heads, _KDA_HEADS)
    f32 = jnp.float32
    v = v.astype(f32)
    return _call(_kda_body,
                 (q.astype(f32), k.astype(f32), v, jnp.exp(g.astype(f32)),
                  jnp.broadcast_to(beta.astype(f32)[..., None], v.shape)),
                 (), pool, layer, slots, heads=heads, width=dv,
                 flops=9 * B * n_heads * dk * dv)
