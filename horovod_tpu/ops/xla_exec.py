"""XLA executor for eager CALLBACK-mode responses.

The NCCL-ops analog (reference ``horovod/common/ops/nccl_operations.cc``):
the native controller decides *when* and *in what order* a fused batch
runs; this module decides *how* — by launching a jitted XLA program.

Process topologies:

* size == 1: collectives over ranks degenerate to (scaled) identity —
  jitted so dtype/scale semantics match the distributed path exactly.
* multi-process under ``jax.distributed`` with one device per process
  (brought up by ``hvd.init()`` when ``HOROVOD_XLA_EXEC=1`` /
  ``horovodrun --xla-exec``): every op in the matrix — allreduce
  (fused batches), allgather (uneven rows), broadcast, alltoall (with
  splits), reducescatter — runs as a jitted global-array program over a
  1-D "rank" mesh. XLA lowers the sharded-in/replicated-or-resharded-
  out programs to all-reduce / all-gather / collective-permute /
  all-to-all over ICI/DCN. The controller's broadcast ResponseList
  guarantees all processes launch identical programs in identical
  order — the invariant XLA multi-controller execution requires.

Fusion note: a fused allreduce response becomes ONE program over the
concatenation of its flattened tensors (XLA's combiner plays the role
of the reference's fusion-buffer memcpy kernels,
``cuda/cuda_kernels.cu``); per-tensor average/prescale/postscale
factors are applied as a traced per-segment factor vector, so dynamic
loss scaling never recompiles.
"""

from __future__ import annotations

from functools import lru_cache
from typing import List, Tuple

import numpy as np

from horovod_tpu.common import basics
from horovod_tpu.common.ops_enum import ReduceOp


def invalidate_world() -> None:
    """Drop every cached mesh and jitted program. Called when the
    process-spanning XLA runtime is torn down (elastic re-formation,
    ``Runtime._teardown_jax_distributed``): the cached programs bake in
    the old world's mesh/devices, which no longer exist after
    ``clear_backends``."""
    for fn in (_rank_mesh, _scale_jit, _allreduce_prog, _allgather_prog,
               _broadcast_prog, _alltoall_prog, _reducescatter_prog):
        fn.cache_clear()


def zeros_state(name: str, op: int, n_elems: int, dtype_id: int,
                reduce_op: int):
    """Placeholder in-flight state for a rank with no local tensor (it
    joined): a zeros contribution so the SPMD program still launches
    here with collectives identical to every other process (reference
    feeds zeros for joined ranks, ``operations.cc:260``)."""
    import jax.numpy as jnp
    from horovod_tpu.runtime import _InFlight

    st = _InFlight()
    st.name = name
    st.op = op
    st.orig_kind = "jax"
    st.reduce_op = ReduceOp(reduce_op)
    st.input_dev = jnp.zeros((int(n_elems),), basics.np_dtype(dtype_id))
    return st


def _scale_factor(st, size: int) -> float:
    f = st.prescale * st.postscale
    if st.reduce_op == ReduceOp.AVERAGE:
        f /= size
    return f


def _check_scalable(dtype, factor: float) -> None:
    dt = np.dtype(dtype)
    is_float = dt.kind == "f" or dt.name in ("bfloat16", "float8_e4m3",
                                             "float8_e5m2")
    if factor != 1.0 and not is_float:
        raise TypeError(
            f"scaling (average/prescale/postscale) is not defined for "
            f"integer dtype {dt.name}; use op=Sum or cast to a float dtype "
            "first")


def _apply_factor(y, factor):
    """Shared dtype-promotion policy for the traced scale factor: low
    precision upcasts to f32 for the multiply; f32 and wider multiply
    in their own dtype (the factor is passed as float64 so f64 inputs
    keep full precision under x64 mode)."""
    import jax.numpy as jnp

    if jnp.dtype(y.dtype).itemsize < 4:
        return (y.astype(jnp.float32) * factor.astype(jnp.float32)).astype(
            y.dtype)
    return y * factor.astype(y.dtype)


def _factor_scalar(f: float) -> np.float64:
    """Factor as a numpy scalar for the jitted programs. float64 so f64
    tensors don't lose precision; under default (x64-disabled) JAX this
    traces as f32, which is all the device path supports anyway."""
    return np.float64(f)


@lru_cache(maxsize=None)
def _scale_jit():
    """Jitted x*f with the factor TRACED (one compile per dtype/shape,
    not per factor value — dynamic loss scaling changes the factor
    every few steps). Callers must reject integer dtypes first
    (:func:`_check_scalable`)."""
    import jax

    return jax.jit(_apply_factor)


_OP_SPAN = {basics.OP_ALLREDUCE: "allreduce",
            basics.OP_ALLGATHER: "allgather",
            basics.OP_BROADCAST: "broadcast",
            basics.OP_ALLTOALL: "alltoall",
            basics.OP_REDUCESCATTER: "reducescatter"}


def execute(op: int, states, sizes: List[int], size: int, rank: int):
    """Execute one CALLBACK response. Wrapped in a ``jax.profiler``
    span so device traces show the collective under the same phase
    names as the host timeline (the reference's NVTX ranges,
    ``common/nvtx_op_range.cc``; here the device story is
    ``jax.profiler.trace``/TensorBoard)."""
    import jax.profiler

    name = states[0].name if states else "?"
    with jax.profiler.TraceAnnotation(
            f"hvd:{_OP_SPAN.get(op, op)}:{name}"):
        return _execute(op, states, sizes, size, rank)


def _execute(op: int, states, sizes: List[int], size: int, rank: int):
    if size == 1:
        outs = []
        for st in states:
            x = st.input_dev
            if op in (basics.OP_ALLREDUCE, basics.OP_REDUCESCATTER):
                f = _scale_factor(st, 1)
                if f != 1.0:
                    _check_scalable(x.dtype, f)
                    x = _scale_jit()(x, _factor_scalar(f))
            # allgather/broadcast/alltoall over 1 rank: identity
            # (alltoall recvsplits are filled by the native core).
            outs.append(x)
        return outs
    if op == basics.OP_ALLREDUCE:
        return _dist_allreduce(states, size)
    if op == basics.OP_ALLGATHER:
        # Fused responses carry per-tensor blocks of `size` row counts.
        return [_dist_allgather(st, tuple(sizes[t * size:(t + 1) * size]),
                                size)
                for t, st in enumerate(states)]
    if op == basics.OP_BROADCAST:
        return [_dist_broadcast(states[0], size)]
    if op == basics.OP_ALLTOALL:
        return [_dist_alltoall(states[0], tuple(sizes), size, rank)]
    if op == basics.OP_REDUCESCATTER:
        return [_dist_reducescatter(states[0], tuple(sizes), size, rank)]
    raise NotImplementedError(f"unknown CALLBACK op {op}")


# ---------------------------------------------------------------------------
# distributed programs (multi-process, one device per process)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _rank_mesh():
    """1-D mesh over all processes' devices, axis "rank". Requires one
    device per process so the axis length equals the world size."""
    import jax
    from jax.sharding import Mesh

    if jax.local_device_count() != 1:
        raise NotImplementedError(
            "eager distributed XLA execution requires one device per "
            "process (the Horovod process model). On multi-chip TPU "
            "hosts launch with `horovodrun --tpu`, which carves each "
            "host into single-chip processes (runner/tpu.py); or use "
            "the SPMD functional API (horovod_tpu.ops) for multi-device "
            "processes")
    # Position along "rank" must be the Horovod rank. hvd.init hands it
    # to jax.distributed as the process id, but on a TPU host the
    # backend numbers processes itself, differently from run to run
    # (first four-chip runs, PR 21: rank 0 came up as process 3 and a
    # broadcast from root 0 delivered another rank's tensor). So ask:
    # one tiny gather over the process-ordered mesh tells every process
    # which Horovod rank sits behind each device. Every process reaches
    # this on the same (first) CALLBACK response, like any other
    # program here.
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = sorted(jax.devices(), key=lambda d: d.process_index)
    by_process = Mesh(np.asarray(devices, dtype=object), ("rank",))
    ranks = _make_global(np.int32(basics.get_lib().hvd_rank()),
                         len(devices), by_process)
    ranks = np.asarray(_local(jax.jit(
        lambda a: a, out_shardings=NamedSharding(by_process, P()))(ranks)))
    if sorted(ranks.tolist()) != list(range(len(devices))):
        raise RuntimeError(
            f"processes report Horovod ranks {ranks.tolist()}; expected a "
            f"permutation of 0..{len(devices) - 1}")
    return Mesh(np.asarray(devices, dtype=object)[np.argsort(ranks)],
                ("rank",))


def _make_global(local, size: int, mesh=None):
    """Assemble the (size, ...) global array whose rank-th row is this
    process's ``local`` (shape ``local.shape``), sharded over "rank"
    (of ``mesh``; default the rank mesh)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        mesh = _rank_mesh()
    sharding = NamedSharding(mesh, P("rank"))
    dev = mesh.local_mesh.devices.flat[0]
    local = jax.device_put(local[None], dev)
    return jax.make_array_from_single_device_arrays(
        (size,) + tuple(local.shape[1:]), sharding, [local])


def _local(arr):
    """This process's addressable piece of a global array (the full
    value for replicated outputs, the local shard otherwise)."""
    return arr.addressable_data(0)


def _pad_rows(x, rows: int):
    import jax.numpy as jnp

    if x.shape[0] == rows:
        return x
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def _reduce_over_ranks(op: ReduceOp, arr):
    """Shared rank-axis reduction for allreduce / reducescatter
    programs (axis 0 is the mesh-sharded rank axis)."""
    import jax.numpy as jnp

    if op in (ReduceOp.AVERAGE, ReduceOp.SUM):
        return jnp.sum(arr, axis=0)
    if op == ReduceOp.MIN:
        return jnp.min(arr, axis=0)
    if op == ReduceOp.MAX:
        return jnp.max(arr, axis=0)
    if op == ReduceOp.PRODUCT:
        return jnp.prod(arr, axis=0)
    if op == ReduceOp.ADASUM:
        raise ValueError("adasum reducescatter is not defined; use allreduce")
    raise ValueError(f"unknown reduce op {op!r}")


def _adasum_tree(arr, spans: Tuple[int, ...]):
    """Adasum over the rank axis of a (size, total) batch: zero-pad
    ranks to a power of two (a zero operand passes its partner through
    unchanged) and fold consecutive pairs — the same binary operator
    tree as the native core's distance-doubling (ops.cc
    AdasumAllreduce), with dot/norm coefficients PER fused segment
    (per-tensor weighting, reference adasum.h:101-122)."""
    import jax.numpy as jnp

    acc = jnp.promote_types(arr.dtype, jnp.float32)
    offs = np.concatenate([[0], np.cumsum(spans)])
    m = arr.shape[0]
    pow2 = 1 << max(0, int(m - 1).bit_length())
    if pow2 != m:
        arr = jnp.pad(arr, [(0, pow2 - m)] + [(0, 0)] * (arr.ndim - 1))
    x = arr.astype(acc)
    while x.shape[0] > 1:
        a, b = x[0::2], x[1::2]
        segs = []
        for i in range(len(spans)):
            sa, sb = a[:, offs[i]:offs[i + 1]], b[:, offs[i]:offs[i + 1]]
            dot = jnp.sum(sa * sb, axis=1, keepdims=True)
            na2 = jnp.sum(sa * sa, axis=1, keepdims=True)
            nb2 = jnp.sum(sb * sb, axis=1, keepdims=True)
            ac = jnp.where(na2 > 0,
                           1.0 - dot / (2.0 * jnp.where(na2 > 0, na2, 1.0)),
                           1.0)
            bc = jnp.where(nb2 > 0,
                           1.0 - dot / (2.0 * jnp.where(nb2 > 0, nb2, 1.0)),
                           1.0)
            segs.append(ac * sa + bc * sb)
        x = jnp.concatenate(segs, axis=1)
    return x[0].astype(arr.dtype)


def _op_class(op: ReduceOp) -> ReduceOp:
    """Program-identity class: AVERAGE folds into SUM (averaging rides
    the traced factor vector), mirroring the controller's fusion classes
    so every rank — including joined ranks that only know the
    response-level op — derives the identical program key. ADASUM stays
    distinct: its program body differs."""
    if op == ReduceOp.AVERAGE:
        return ReduceOp.SUM
    return op


@lru_cache(maxsize=None)
def _allreduce_prog(op: ReduceOp, spans: Tuple[int, ...], inexact: bool):
    """One program per (reduce class, segment layout, dtype kind):
    reduce the (size, total) batch over ranks, then apply the traced
    per-segment factor vector. Program identity must NOT depend on
    factor values — a joined rank synthesizes factor 1.0 and still has
    to trace the identical HLO — so the multiply is always present for
    inexact dtypes (the factors are jit arguments)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _rank_mesh()
    repl = NamedSharding(mesh, P())
    repeats = np.asarray(spans)

    def fn(arr, factors):
        if op == ReduceOp.ADASUM:
            y = _adasum_tree(arr, spans)
        else:
            y = _reduce_over_ranks(op, arr)
        if inexact:
            y = _apply_factor(y, jnp.repeat(factors, repeats,
                                            total_repeat_length=int(
                                                repeats.sum())))
        return y

    return jax.jit(fn, out_shardings=repl)


def _dist_allreduce(states, size: int):
    """One fused program over the concatenation of the batch's
    flattened tensors (all share a dtype — the controller's fusion
    criterion)."""
    import jax.numpy as jnp

    spans = tuple(int(np.prod(st.input_dev.shape, dtype=np.int64))
                  for st in states)
    factors = [_scale_factor(st, size) for st in states]
    for st, f in zip(states, factors):
        if f != 1.0:
            _check_scalable(st.input_dev.dtype, f)
    local = jnp.concatenate(
        [jnp.ravel(jnp.asarray(st.input_dev)) for st in states])
    arr = _make_global(local, size)
    inexact = np.dtype(local.dtype).kind == "f" or \
        np.dtype(local.dtype).name == "bfloat16"
    if states[0].reduce_op == ReduceOp.ADASUM and not inexact:
        raise TypeError(
            f"adasum requires a float dtype, got {local.dtype}")
    # numpy f64 in, silent downcast to f32 unless x64 is enabled — same
    # policy as _factor_scalar.
    y = _allreduce_prog(_op_class(states[0].reduce_op), spans, inexact)(
        arr, jnp.asarray(np.asarray(factors, dtype=np.float64)))
    y = _local(y)
    outs, off = [], 0
    for st, span in zip(states, spans):
        outs.append(y[off:off + span].reshape(st.input_dev.shape))
        off += span
    return outs


@lru_cache(maxsize=None)
def _allgather_prog(sizes: Tuple[int, ...], rest: Tuple[int, ...]):
    """Gather uneven-row tensors: ranks pad to the max row count, the
    program slices out the real rows and concatenates (XLA lowers the
    replicated output to an all-gather)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _rank_mesh()
    repl = NamedSharding(mesh, P())

    def fn(arr):  # (size, max_rows, *rest)
        return jnp.concatenate(
            [arr[r, :sizes[r]] for r in range(len(sizes))], axis=0)

    return jax.jit(fn, out_shardings=repl)


def _dist_allgather(st, sizes: Tuple[int, ...], size: int):
    import jax.numpy as jnp

    x = jnp.asarray(st.input_dev)
    arr = _make_global(_pad_rows(x, max(sizes)), size)
    return _local(_allgather_prog(sizes, tuple(x.shape[1:]))(arr))


@lru_cache(maxsize=None)
def _broadcast_prog(root: int):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _rank_mesh()
    repl = NamedSharding(mesh, P())
    return jax.jit(lambda arr: arr[root], out_shardings=repl)


def _dist_broadcast(st, size: int):
    import jax.numpy as jnp

    arr = _make_global(jnp.asarray(st.input_dev), size)
    return _local(_broadcast_prog(int(st.root_rank))(arr))


@lru_cache(maxsize=None)
def _alltoall_prog(matrix: Tuple[int, ...], size: int,
                   max_send: int, rest: Tuple[int, ...]):
    """Uneven all-to-all from the full splits matrix
    (``matrix[r*size+k]`` = rows rank r RECEIVES from rank k, i.e.
    rank k's send chunk to r). Every rank pads its send buffer to
    ``max_send`` rows; the program re-slices chunks into each
    receiver's (padded) output row, sharded back over ranks."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _rank_mesh()
    out_sh = NamedSharding(mesh, P("rank"))

    def send_chunk(k: int, r: int) -> Tuple[int, int]:
        # Rows k sends to r start after k's chunks for ranks < r.
        start = sum(matrix[q * size + k] for q in range(r))
        return start, matrix[r * size + k]

    recv_rows = [sum(matrix[r * size + k] for k in range(size))
                 for r in range(size)]
    max_recv = max(recv_rows + [1])

    def fn(arr):  # (size, max_send, *rest)
        rows = []
        for r in range(size):
            chunks = []
            for k in range(size):
                start, n = send_chunk(k, r)
                if n:
                    chunks.append(arr[k, start:start + n])
            row = (jnp.concatenate(chunks, axis=0) if chunks
                   else jnp.zeros((0,) + rest, arr.dtype))
            rows.append(_pad_rows(row, max_recv))
        return jnp.stack(rows)

    return jax.jit(fn, out_shardings=out_sh)


def _dist_alltoall(st, matrix: Tuple[int, ...], size: int, rank: int):
    import jax.numpy as jnp

    x = jnp.asarray(st.input_dev)
    # Every rank must pad to the same static max; send totals are the
    # column sums of the matrix.
    send_totals = [sum(matrix[r * size + k] for r in range(size))
                   for k in range(size)]
    max_send = max(send_totals + [1])
    arr = _make_global(_pad_rows(x, max_send), size)
    out = _alltoall_prog(matrix, size, max_send, tuple(x.shape[1:]))(arr)
    my_rows = sum(matrix[rank * size + k] for k in range(size))
    return _local(out)[0][:my_rows]


@lru_cache(maxsize=None)
def _reducescatter_prog(op: ReduceOp, sizes: Tuple[int, ...],
                        inexact: bool):
    """Reduce over ranks, then scatter dim-0 shards back (uneven shards
    via per-rank slices padded to the max; output sharded over ranks so
    XLA can lower to reduce-scatter). Factor traced, same identity
    policy as :func:`_allreduce_prog`."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _rank_mesh()
    out_sh = NamedSharding(mesh, P("rank"))
    offs = np.concatenate([[0], np.cumsum(sizes)]).tolist()
    max_shard = max(sizes)

    def fn(arr, factor):  # (size, n0, *rest)
        y = _reduce_over_ranks(op, arr)
        if inexact:
            y = _apply_factor(y, factor)
        return jnp.stack([
            _pad_rows(y[offs[r]:offs[r + 1]], max_shard)
            for r in range(len(sizes))])

    return jax.jit(fn, out_shardings=out_sh)


def _dist_reducescatter(st, sizes: Tuple[int, ...], size: int, rank: int):
    import jax.numpy as jnp

    x = jnp.asarray(st.input_dev)
    f = _scale_factor(st, size)
    if f != 1.0:
        _check_scalable(x.dtype, f)
    inexact = np.dtype(x.dtype).kind == "f" or \
        np.dtype(x.dtype).name == "bfloat16"
    arr = _make_global(x, size)
    out = _reducescatter_prog(_op_class(st.reduce_op), sizes, inexact)(
        arr, _factor_scalar(f))
    return _local(out)[0][:sizes[rank]]
