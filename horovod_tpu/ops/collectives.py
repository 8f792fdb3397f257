"""Functional collectives — the in-``jit`` SPMD data plane.

This is the TPU-native replacement for the reference's op layer
(``horovod/common/ops/collective_operations.h:38-288`` and the NCCL
implementations in ``ops/nccl_operations.cc``): instead of enqueueing
tensors to a background thread that drives ``ncclAllReduce`` on a
private stream, collectives here are *traced into the user's XLA
program* (``lax.psum``/``all_gather``/``psum_scatter``/``all_to_all``/
``ppermute``) and lowered by XLA onto ICI. Fusion (reference
``fusion_buffer_manager.cc``) is unnecessary in this tier: XLA's
combiner pass batches small collectives, and multi-operand ``psum`` of
a whole gradient pytree is the "grouped allreduce" of
``operations.cc:943`` for free.

All functions take ``axis_name`` (one of the mesh axes, or a tuple of
axes to reduce over several at once) and must be called under
``shard_map``/``pjit`` with a bound mesh.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.ops_enum import ReduceOp, Average, Sum

AxisName = Union[str, Sequence[str]]


def axis_rank(axis_name: AxisName = "dp"):
    """This shard's index along ``axis_name`` (cf. ``hvd.rank()``)."""
    return lax.axis_index(axis_name)


def axis_size(axis_name: AxisName = "dp") -> int:
    """Static size of the named axis (cf. ``hvd.size()``)."""
    return lax.axis_size(axis_name)


def _scale(x, factor):
    if factor is None or factor == 1.0:
        return x
    if not jnp.issubdtype(x.dtype, jnp.inexact):
        raise TypeError(
            f"scaling (average/prescale/postscale) is not defined for integer "
            f"dtype {x.dtype}; use op=Sum or cast to a float dtype first")
    # Scale in f32 for low-precision inputs to avoid bf16 rounding of the
    # factor itself (reference scales in the fusion buffer with a fused
    # kernel, ops/cuda/cuda_kernels.cu; XLA fuses this multiply for free).
    if jnp.dtype(x.dtype).itemsize < 4:
        return (x.astype(jnp.float32) * factor).astype(x.dtype)
    return x * factor


def allreduce(x, op: ReduceOp = Average, axis_name: AxisName = "dp", *,
              prescale_factor: Optional[float] = None,
              postscale_factor: Optional[float] = None,
              compression=None):
    """Reduce ``x`` across ``axis_name`` on every shard.

    Reference semantics: ``horovod/common/operations.cc:914``
    ``EnqueueTensorAllreduce`` + pre/postscale (``operations.cc:955-970``).
    ``Average`` divides by the axis size after summation.

    ``compression`` (a ``hvd.Compression`` member; None/none =
    uncompressed, the exact pre-existing path) routes Sum/Average
    through the quantized reduce-scatter + all-gather in
    :mod:`horovod_tpu.ops.quantized` so the collective ships narrow
    bytes inside the XLA graph — the in-jit face of the same knob the
    eager TCP plane reads as a wire codec.
    """
    from horovod_tpu import compression as compression_lib
    codec = compression_lib.in_jit_codec(compression)
    if codec != "none":
        if (op in (ReduceOp.AVERAGE, ReduceOp.SUM)
                and isinstance(axis_name, str)):
            from horovod_tpu.ops.quantized import quantized_allreduce
            x = _scale(x, prescale_factor)
            y = quantized_allreduce(x, op=op, axis_name=axis_name,
                                    codec=codec)
            return _scale(y, postscale_factor)
        if codec == "int8":
            raise ValueError(
                f"compression=int8 supports op=Sum/Average over a single "
                f"named axis (got op={op!r}, axis {axis_name!r}); the "
                "cast codecs (bf16/fp16) wrap the other shapes")
        # Cast codecs wrap everything else the plain path supports
        # (Max/Min/Product/Adasum, tuple axes): cast to the wire dtype
        # around the uncompressed collective — the same fallback
        # contract as allreduce_gradients.
        c, ctx = compression.compress(x)
        y = allreduce(c, op, axis_name, prescale_factor=prescale_factor,
                      postscale_factor=postscale_factor)
        return compression.decompress(y, ctx)
    x = _scale(x, prescale_factor)
    if op == ReduceOp.ADASUM:
        from horovod_tpu.ops.adasum import adasum_allreduce
        y = adasum_allreduce(x, axis_name)
    elif op in (ReduceOp.AVERAGE, ReduceOp.SUM):
        y = lax.psum(x, axis_name)
        if op == ReduceOp.AVERAGE:
            y = _scale(y, 1.0 / axis_size(axis_name))
    elif op == ReduceOp.MIN:
        y = lax.pmin(x, axis_name)
    elif op == ReduceOp.MAX:
        y = lax.pmax(x, axis_name)
    elif op == ReduceOp.PRODUCT:
        # XLA has no product collective; gather then reduce locally. The
        # trailing pmax is a no-op on the (identical) per-shard results
        # that re-establishes the replicated value type for shard_map.
        g = lax.all_gather(x, axis_name)
        y = lax.pmax(jnp.prod(g, axis=0), axis_name)
    else:
        raise ValueError(f"unknown reduce op {op!r}")
    return _scale(y, postscale_factor)


def grouped_allreduce(xs, op: ReduceOp = Average, axis_name: AxisName = "dp", *,
                      prescale_factor: Optional[float] = None,
                      postscale_factor: Optional[float] = None,
                      compression=None):
    """Allreduce a pytree of tensors as one logical step.

    Reference: ``EnqueueTensorAllreduces`` (``operations.cc:943``) +
    ``GroupTable`` atomic completion (``common/group_table.h:31``). In
    XLA a multi-operand ``psum`` compiles to batched collectives over
    one fused buffer — the moral equivalent of the reference's fusion
    buffer without the explicit memcpy kernels.

    ``compression`` routes each leaf through the quantized path (see
    :func:`allreduce`); XLA's combiner still batches the per-leaf
    narrow collectives.
    """
    from horovod_tpu import compression as compression_lib
    if compression_lib.in_jit_codec(compression) != "none":
        return jax.tree.map(
            lambda t: allreduce(t, op, axis_name,
                                prescale_factor=prescale_factor,
                                postscale_factor=postscale_factor,
                                compression=compression), xs)
    if op == ReduceOp.ADASUM:
        from horovod_tpu.ops.adasum import adasum_allreduce
        xs = jax.tree.map(lambda l: _scale(l, prescale_factor), xs)
        reduced = adasum_allreduce(xs, axis_name)
        return jax.tree.map(lambda l: _scale(l, postscale_factor), reduced)
    if op in (ReduceOp.AVERAGE, ReduceOp.SUM):
        leaves, treedef = jax.tree.flatten(xs)
        leaves = [_scale(l, prescale_factor) for l in leaves]
        reduced = lax.psum(tuple(leaves), axis_name)
        if op == ReduceOp.AVERAGE:
            inv = 1.0 / axis_size(axis_name)
            reduced = [_scale(l, inv) for l in reduced]
        reduced = [_scale(l, postscale_factor) for l in reduced]
        return jax.tree.unflatten(treedef, reduced)
    return jax.tree.map(
        lambda t: allreduce(t, op, axis_name, prescale_factor=prescale_factor,
                            postscale_factor=postscale_factor), xs)


def allgather(x, axis_name: AxisName = "dp", axis: int = 0):
    """Concatenate each shard's ``x`` along ``axis`` (reference
    ``EnqueueTensorAllgather``, ``operations.cc:1055``; like Horovod,
    shards may differ in dim-``axis`` *only* — ragged sizes are handled
    by the eager tier, not in-jit where shapes are static)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


def broadcast(x, root_rank: int = 0, axis_name: AxisName = "dp"):
    """Every shard receives shard ``root_rank``'s value.

    Reference: ``EnqueueTensorBroadcast`` (``operations.cc:1091``).
    Implemented as masked ``psum`` — one ICI reduction, no gather blowup;
    XLA recognises the select+reduce idiom.
    """
    n = axis_size(axis_name)
    if not (0 <= root_rank < n):
        raise ValueError(f"root_rank {root_rank} out of range for axis "
                         f"{axis_name!r} of size {n}")
    idx = lax.axis_index(axis_name)
    if jnp.issubdtype(x.dtype, jnp.bool_):
        y = lax.psum(jnp.where(idx == root_rank, x, False).astype(jnp.int8),
                     axis_name)
        return y.astype(jnp.bool_)
    return lax.psum(jnp.where(idx == root_rank, x, jnp.zeros_like(x)), axis_name)


def alltoall(x, axis_name: AxisName = "dp", split_axis: int = 0,
             concat_axis: int = 0):
    """Scatter ``x`` along ``split_axis`` to the axis peers and gather
    their slices along ``concat_axis`` (reference
    ``EnqueueTensorAlltoall``, ``operations.cc:1131``; on TPU this is
    the Ulysses/MoE primitive and lowers to an ICI all-to-all)."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def reducescatter(x, op: ReduceOp = Average, axis_name: AxisName = "dp",
                  scatter_axis: int = 0):
    """Sum across the axis, leaving each shard with its 1/N slice along
    ``scatter_axis``. The reference only reaches reduce-scatter inside
    hierarchical allreduce (``nccl_operations.cc:187-360``); on TPU it
    is first-class — the FSDP gradient path."""
    if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
        raise ValueError("reducescatter supports SUM/AVERAGE")
    y = lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                         tiled=True)
    if op == ReduceOp.AVERAGE:
        y = _scale(y, 1.0 / axis_size(axis_name))
    return y


def ring_permute(x, axis_name: AxisName = "sp", shift: int = 1):
    """Send ``x`` to the neighbor ``shift`` hops along the axis ring
    (``lax.ppermute``) — the building block of ring attention and the
    TPU analog of neighbor exchanges the reference never needed
    (its DP-only model has no ring pipelines)."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm=perm)
