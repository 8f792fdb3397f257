"""Quantized in-jit mesh collectives (EQuARX, arXiv:2506.17615).

PR 3 compressed the host TCP ring; this module compresses the plane the
models actually train on — the in-``jit`` collectives over NamedSharding
meshes. Pure ``jnp``, callable only under ``shard_map`` with the named
axis manual.

The reduced value leaves through ``lax.all_gather``, which jax types
as *varying* over the axis although every rank holds the same bytes
(the public API has no varying→invariant cast). A ``shard_map`` that
returns it — or anything computed from it — under a replicated
``out_specs`` therefore needs ``check_vma=False``.

Codecs, mirroring ``native/src/codec.cc`` exactly:

* **bf16 / fp16** — cast the wire representation down; the backend
  reduces the narrow operand.
* **int8** — blockwise-scaled: each :data:`INT8_BLOCK_ELEMS`-element
  block carries a ``absmax/127`` f32 scale; values quantize with
  round-to-nearest-even (``jnp.round`` lowers to
  ``lax.round(ROUND_TO_NEAREST_EVEN)``, the same RNE contract as the
  native plane's branchless magic-constant trick in ``codec.cc`` —
  bit-identical over the ±127 range) and clamp to ``[-127, 127]``.

The allreduce is the MLPerf-TPU reduce-scatter + all-gather
decomposition (arXiv:1909.09756) with both hops shipping narrow bytes:

1. quantize the local value, blockwise per destination shard;
2. reduce-scatter the narrow payload — expressed as ``lax.all_to_all``
   of the int8 bytes plus a local f32 fold, because a reduction
   collective cannot sum int8 encodings under per-rank scales; the
   wire bytes equal ``psum_scatter``'s, which is what the cast codecs
   (bf16/fp16) use directly;
3. **requantize** the reduced shard;
4. ``lax.all_gather`` the narrow bytes and dequantize.

Determinism contract (same as ``HostAccumulate``): the fold is a fixed
``sum(axis=0)`` over peer order and every decode is a *multiply* by the
scale (``q * s``, never ``q / inv``) — a constant division gets
algebraically rewritten under jit. Every codec is bitwise stable run
to run. The cast codecs (bf16/fp16) are also bitwise jit vs op-by-op
eager; int8 may differ from eager by an f32 ULP, where XLA contracts
its decode multiply into the fold as an FMA.

Error feedback (int8): the rank-local residual telescopes the rounding
error across steps exactly like the host plane's EF slabs. Both
quantization points are compensated: hop 1's encode error everywhere,
and hop 2's requantize error on the shard this rank owns (it is the
rank that performed that encode), so the summed decoded contributions
reconstruct the collective's actual output and the time-average of the
quantized mean converges to the true mean on a fixed gradient (the
telescoping identity pinned in tests/test_quantized.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.ops_enum import Average, ReduceOp, Sum

# Elements per int8 quantization block — pinned to the native plane's
# kInt8BlockElems (native/include/hvd/codec.h) by tests/test_wire_abi.py
# and the tools/lint wire-codec-pins rule, so one knob means one block
# geometry on both planes.
INT8_BLOCK_ELEMS = 256

#: In-jit codec names (the `in_jit_codec` values compression.py maps to).
CODECS = ("none", "bf16", "fp16", "int8")

_CAST_WIRE = {"bf16": jnp.bfloat16, "fp16": jnp.float16}


# ---------------------------------------------------------------------------
# Blockwise int8 codec (pure jnp, shapes static)
# ---------------------------------------------------------------------------

def int8_blocks(n: int) -> int:
    """ceil-div block count for ``n`` elements (codec.h Int8Blocks)."""
    return -(-n // INT8_BLOCK_ELEMS)


def blockwise_int8_encode(x):
    """Quantize ``x`` [..., C] blockwise along the last axis.

    Returns ``(q, scales)``: ``q`` int8 [..., NB*B] (C zero-padded up to
    whole blocks — pad lanes quantize to exactly 0 and never perturb a
    block's absmax), ``scales`` f32 [..., NB] with ``absmax/127`` per
    block (0 for an all-zero block, matching codec.cc).
    """
    x = x.astype(jnp.float32)
    c = x.shape[-1]
    nb = int8_blocks(c)
    pad = nb * INT8_BLOCK_ELEMS - c
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    v = x.reshape(x.shape[:-1] + (nb, INT8_BLOCK_ELEMS))
    absmax = jnp.max(jnp.abs(v), axis=-1)
    scales = absmax * jnp.float32(1.0 / 127.0)
    inv = jnp.where(scales > 0, 1.0 / scales, 0.0)
    q = jnp.clip(jnp.round(v * inv[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape[:-1] + (nb * INT8_BLOCK_ELEMS,)), scales


def blockwise_int8_decode(q, scales, c: int):
    """Dequantize ``(q, scales)`` back to f32 [..., c].

    Decode is ``q * scale`` — the native plane's exact arithmetic
    (Int8DecodeBlocks) and the jit-stable spelling (see module doc).
    """
    nb = scales.shape[-1]
    v = q.astype(jnp.float32).reshape(q.shape[:-1] + (nb, INT8_BLOCK_ELEMS))
    out = (v * scales[..., None]).reshape(q.shape)
    return out[..., :c]


# ---------------------------------------------------------------------------
# The quantized allreduce
# ---------------------------------------------------------------------------

def _check_codec(codec: str):
    if codec not in CODECS:
        raise ValueError(f"unknown in-jit codec {codec!r}; one of {CODECS}")


def _check_axis_name(axis_name, fn_name: str):
    """Up-front rejection of tuple/list axis names on the quantized
    paths: the all_to_all decomposition addresses ONE named axis, and a
    tuple that slipped through used to die deep inside the collective
    with an opaque XLA shape error. A clear ValueError at the API edge
    is the contract (reshape the mesh, or reduce axis-by-axis — which
    is exactly how the fsdp+dp train step composes its hops)."""
    if not isinstance(axis_name, str):
        raise ValueError(
            f"{fn_name} reduces over a single named mesh axis; got "
            f"{axis_name!r}. Reshape the mesh or reduce axis-by-axis "
            "(sequential single-axis hops are the supported spelling "
            "for multi-axis meshes).")


def quantized_allreduce(x, op: ReduceOp = Average, axis_name: str = "dp", *,
                        codec: str, residual: Optional[jax.Array] = None):
    """Allreduce ``x`` over ``axis_name`` with narrow bytes on both hops.

    Call under ``shard_map`` with ``axis_name`` manual. ``codec`` is one
    of :data:`CODECS`; ``"none"`` takes the exact pre-existing
    ``lax.psum`` path (bitwise identical to an uncompressed allreduce).
    ``residual`` (int8/bf16/fp16; optional) is this rank's error-feedback
    buffer, shaped and typed like ``x`` in f32 — when given, the value
    quantized is ``x + residual`` and the call returns
    ``(reduced, new_residual)``; without it the rounding error of this
    step is dropped (plain quantized) and only ``reduced`` returns.

    Only ``Sum``/``Average`` are compressible (MIN/MAX/PRODUCT have no
    meaningful quantized composition); other ops raise.
    """
    _check_codec(codec)
    if codec == "none":
        y = lax.psum(x, axis_name)
        if op == Average:
            y = y / lax.axis_size(axis_name)
        elif op != Sum:
            raise ValueError("quantized_allreduce supports Sum/Average")
        return (y, residual) if residual is not None else y
    if op not in (Sum, Average):
        raise ValueError(
            f"compression={codec!r} supports op=Sum/Average only, got {op!r}")
    _check_axis_name(axis_name, "quantized_allreduce")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise TypeError(
            f"cannot quantize dtype {x.dtype}; compression applies to "
            "float gradients")

    p = lax.axis_size(axis_name)
    orig_shape, orig_dtype = x.shape, x.dtype
    n = x.size
    xf = x.astype(jnp.float32).reshape(-1)
    if residual is not None:
        xf = xf + residual.astype(jnp.float32).reshape(-1)
    n_per = -(-n // p)                     # elements per scattered shard
    if n_per * p != n:
        xf = jnp.pad(xf, (0, n_per * p - n))
    v = xf.reshape(p, n_per)               # row r -> shard owned by rank r

    if codec == "int8":
        q1, s1 = blockwise_int8_encode(v)          # [P, NB*B], [P, NB]
        qr = lax.all_to_all(q1, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
        sr = lax.all_to_all(s1, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
        y = blockwise_int8_decode(qr, sr, n_per).sum(axis=0)   # [n_per] f32
        q2, s2 = blockwise_int8_encode(y[None])    # [1, NB*B], [1, NB]
        gq = lax.all_gather(q2[0], axis_name, axis=0, tiled=False)
        gs = lax.all_gather(s2[0], axis_name, axis=0, tiled=False)
        z = blockwise_int8_decode(gq, gs, n_per)   # [P, n_per] f32
        if residual is not None:
            e1 = v - blockwise_int8_decode(q1, s1, n_per)
            e2 = y - blockwise_int8_decode(q2, s2, n_per)[0]
    else:
        wire = _CAST_WIRE[codec]
        w1 = v.astype(wire)
        # psum_scatter-native hop: the backend reduces the narrow
        # operand itself — one collective, same wire bytes as the
        # all_to_all spelling, summation in the wire dtype.
        y = lax.psum_scatter(w1, axis_name,
                             scatter_dimension=0).astype(jnp.float32)
        w2 = y.astype(wire)
        z = lax.all_gather(w2, axis_name, axis=0,
                           tiled=False).astype(jnp.float32)
        if residual is not None:
            e1 = v - w1.astype(jnp.float32)
            e2 = y - w2.astype(jnp.float32)

    if op == Average:
        z = z * jnp.float32(1.0 / p)
    out = z.reshape(-1)[:n].reshape(orig_shape).astype(orig_dtype)
    if residual is None:
        return out
    # EF update: hop-1 encode error everywhere; hop-2 requantize error
    # on this rank's own shard row (sum space — the averaging factor
    # never enters the residual; see module doc).
    own = (jnp.arange(p) == lax.axis_index(axis_name))[:, None]
    new_r = e1 + jnp.where(own, e2[None, :], 0.0)
    new_r = new_r.reshape(-1)[:n].reshape(orig_shape)
    return out, new_r


def quantized_reduce_scatter(x, op: ReduceOp = Sum,
                             axis_name: str = "fsdp", *, codec: str,
                             axis: int = 0,
                             residual: Optional[jax.Array] = None):
    """Reduce-scatter ``x`` over ``axis_name`` with the hop bytes
    narrowed by ``codec`` — the explicit, interceptable spelling of the
    GSPMD-inserted fsdp gradient reduce-scatter.

    Composition (same contract as hop 1 of the allreduce): quantize
    blockwise per destination shard → ``lax.all_to_all`` of the narrow
    payload (+f32 scales for int8) → fixed-order **multiply-only** f32
    fold; the wire bytes equal ``psum_scatter``'s. For the cast codecs
    the fold lowers as ONE sub-f32 ``lax.psum_scatter`` (the backend
    reduces the narrow operand itself).

    ``x``'s dim ``axis`` must divide by the axis size; this rank
    returns its slice (``x.shape`` with that dim divided). ``"none"``
    folds the exact f32 values (bitwise the psum-then-slice result
    under the same fixed fold order). ``residual`` (f32, ``x``-shaped)
    is this rank's EF buffer for the single encode point; with it the
    call returns ``(shard, new_residual)``.
    """
    _check_codec(codec)
    _check_axis_name(axis_name, "quantized_reduce_scatter")
    if op not in (Sum, Average):
        raise ValueError(
            f"quantized_reduce_scatter supports op=Sum/Average, got {op!r}")
    if codec != "none" and not jnp.issubdtype(x.dtype, jnp.floating):
        raise TypeError(
            f"cannot quantize dtype {x.dtype}; compression applies to "
            "float gradients")
    p = lax.axis_size(axis_name)
    axis = axis % x.ndim
    if x.shape[axis] % p:
        raise ValueError(
            f"quantized_reduce_scatter: dim {axis} of shape {x.shape} "
            f"does not divide by the {axis_name!r} axis size {p}")
    orig_dtype = x.dtype
    moved = jnp.moveaxis(x, axis, 0)
    # Row r of `rows` is the contiguous slab destined for rank r.
    rows = moved.astype(jnp.float32).reshape(p, -1)
    if residual is not None and codec != "none":
        rows = rows + jnp.moveaxis(residual.astype(jnp.float32),
                                   axis, 0).reshape(p, -1)
    shard_shape = (moved.shape[0] // p,) + moved.shape[1:]

    if codec == "none":
        rr = lax.all_to_all(rows, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
        y = rr.sum(axis=0)
        e1 = None
    elif codec == "int8":
        q1, s1 = blockwise_int8_encode(rows)
        qr = lax.all_to_all(q1, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
        sr = lax.all_to_all(s1, axis_name, split_axis=0, concat_axis=0,
                            tiled=True)
        y = blockwise_int8_decode(qr, sr, rows.shape[-1]).sum(axis=0)
        if residual is not None:
            e1 = rows - blockwise_int8_decode(q1, s1, rows.shape[-1])
    else:
        wire = _CAST_WIRE[codec]
        w1 = rows.astype(wire)
        y = lax.psum_scatter(w1, axis_name,
                             scatter_dimension=0).astype(jnp.float32)
        if residual is not None:
            e1 = rows - w1.astype(jnp.float32)

    if op == Average:
        y = y * jnp.float32(1.0 / p)
    shard = jnp.moveaxis(y.reshape(shard_shape), 0, axis).astype(orig_dtype)
    if residual is None:
        return shard
    if e1 is None:                       # codec "none": nothing dropped
        return shard, residual
    # Encode error in SUM space (the Average factor never enters the
    # residual, same discipline as the allreduce's EF update).
    return shard, jnp.moveaxis(e1.reshape(moved.shape), 0, axis)


def quantized_allgather(x, axis_name: str = "dp", *, codec: str,
                        axis: int = 0):
    """All-gather ``x`` with the wire bytes narrowed by ``codec``
    (tiled, like :func:`horovod_tpu.ops.collectives.allgather`). The
    int8 form ships blockwise q+scales and dequantizes after the hop;
    lossy like the allreduce's hop 2. ``"none"`` is the exact plain
    gather."""
    _check_codec(codec)
    if codec == "none":
        return lax.all_gather(x, axis_name, axis=axis, tiled=True)
    _check_axis_name(axis_name, "quantized_allgather")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise TypeError(f"cannot quantize dtype {x.dtype}")
    orig_dtype = x.dtype
    if codec in _CAST_WIRE:
        w = x.astype(_CAST_WIRE[codec])
        return lax.all_gather(w, axis_name, axis=axis,
                              tiled=True).astype(orig_dtype)
    moved = jnp.moveaxis(x, axis, -1)
    c = moved.shape[-1]
    q, s = blockwise_int8_encode(moved)
    gq = lax.all_gather(q, axis_name, axis=-1, tiled=True)
    gs = lax.all_gather(s, axis_name, axis=-1, tiled=True)
    p = gq.shape[-1] // q.shape[-1]
    gq = gq.reshape(gq.shape[:-1] + (p, q.shape[-1]))
    gs = gs.reshape(gs.shape[:-1] + (p, s.shape[-1]))
    out = blockwise_int8_decode(gq, gs, c)          # [..., P, c]
    out = out.reshape(moved.shape[:-1] + (p * c,))  # concat peers in order
    return jnp.moveaxis(out, -1, axis).astype(orig_dtype)


# ---------------------------------------------------------------------------
# The quantized alltoall (MoE dispatch/combine hop, ISSUE 18)
# ---------------------------------------------------------------------------

def _plain_alltoall(x, axis_name: str):
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)


def _alltoall_value(x, axis_name: str, codec: str):
    """Forward value of the quantized alltoall: each destination slab
    ``x[d]`` is flattened and encoded as ONE blockwise payload (same
    slab-flattening discipline as the allreduce's per-shard rows, so
    block utilization never depends on the trailing-dim geometry), the
    narrow bytes (+f32 scales for int8) ride ``lax.all_to_all``, and
    the received slabs decode back to ``x.dtype``."""
    if codec == "none":
        return _plain_alltoall(x, axis_name)
    shape, dtype = x.shape, x.dtype
    if codec in _CAST_WIRE:
        w = x.astype(_CAST_WIRE[codec])
        return _plain_alltoall(w, axis_name).astype(dtype)
    rows = x.astype(jnp.float32).reshape(shape[0], -1)
    q, s = blockwise_int8_encode(rows)
    qr = _plain_alltoall(q, axis_name)
    sr = _plain_alltoall(s, axis_name)
    out = blockwise_int8_decode(qr, sr, rows.shape[-1])
    return out.reshape(shape).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _qa2a(x, axis_name: str, codec: str, bwd_codec: str):
    return _alltoall_value(x, axis_name, codec)


def _qa2a_fwd(x, axis_name, codec, bwd_codec):
    return _alltoall_value(x, axis_name, codec), None


def _qa2a_bwd(axis_name, codec, bwd_codec, _res, g):
    # The tiled (split=concat=0) alltoall is its own transpose: the
    # slab that went p->q routes back q->p under the identical op. The
    # cotangent rides the SAME narrow wire (bwd_codec), quantized the
    # straight-through way — the rounding of the forward hop never
    # enters the backward graph (jnp.round's zero derivative would
    # otherwise kill every gradient flowing through the dispatch).
    return (_alltoall_value(g, axis_name, bwd_codec),)


_qa2a.defvjp(_qa2a_fwd, _qa2a_bwd)


def quantized_alltoall(x, axis_name: str = "ep", *, codec: str,
                       bwd_codec: Optional[str] = None):
    """Alltoall ``x`` over ``axis_name`` with the wire narrowed by
    ``codec`` — the explicit MoE dispatch/combine hop (EQuARX applied
    to the one collective that dominates sparse-model step time).

    Call under ``shard_map`` with ``axis_name`` manual. ``x``'s leading
    dim must equal the axis size P; slab ``x[d]`` is delivered to rank
    ``d`` and the result's slab ``[s]`` came from rank ``s`` (tiled
    ``lax.all_to_all`` semantics, split/concat axis 0).

    ``codec`` is one of :data:`CODECS`; ``"none"`` is the exact plain
    ``lax.all_to_all`` — bitwise the uncompressed hop, native autodiff.
    The lossy codecs are differentiable with a straight-through custom
    VJP whose backward hop ships ``bwd_codec`` (default: same as
    ``codec``) in the reverse direction — both directions of the
    exchange stay narrow.
    """
    _check_codec(codec)
    bwd = codec if bwd_codec is None else bwd_codec
    _check_codec(bwd)
    if codec == "none" and bwd == "none":
        return _plain_alltoall(x, axis_name)
    _check_axis_name(axis_name, "quantized_alltoall")
    p = lax.axis_size(axis_name)
    if x.shape[0] != p:
        raise ValueError(
            f"quantized_alltoall: leading dim {x.shape[0]} must equal "
            f"the {axis_name!r} axis size {p} (one slab per peer)")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise TypeError(
            f"cannot quantize dtype {x.dtype}; compression applies to "
            "float activations")
    return _qa2a(x, axis_name, codec, bwd)
