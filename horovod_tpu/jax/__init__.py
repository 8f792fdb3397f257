"""JAX binding: the first-class TPU framework surface.

``import horovod_tpu.jax as hvd`` mirrors what ``horovod.tensorflow``
is to TF (reference ``tensorflow/__init__.py:427-790``): the full
collective API plus

* :func:`distributed_optimizer` — an optax ``GradientTransformation``
  wrapper (the ``DistributedOptimizer`` analog),
* :func:`distributed_value_and_grad` / :func:`allreduce_gradients` —
  the ``DistributedGradientTape`` analog,
* :func:`broadcast_parameters` / :func:`broadcast_object` /
  :func:`allgather_object` — bootstrap + checkpoint helpers on
  pytrees.

Two execution tiers, chosen by ``axis_name``:

* ``axis_name=None`` (default): the **eager named-tensor runtime** —
  per-leaf grouped allreduce negotiated by the native core, matching
  Horovod's process-per-rank model.
* ``axis_name="dp"`` (or a tuple): **in-jit SPMD** — ``lax.psum`` /
  ``pmean`` inside your ``shard_map``/``pjit`` program, compiled onto
  ICI by XLA. This is the TPU-idiomatic fast path.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Union

import horovod_tpu.api as api
from horovod_tpu.api import (  # noqa: F401
    init, shutdown, is_initialized, rank, size, local_rank, local_size,
    cross_rank, cross_size, allreduce, allreduce_async, grouped_allreduce,
    grouped_allreduce_async, allgather, allgather_async, broadcast,
    broadcast_async, alltoall, alltoall_async, reducescatter,
    reducescatter_async, join, barrier, synchronize, poll,
    mpi_threads_supported, start_timeline, stop_timeline,
    metrics, metrics_prometheus, metrics_aggregate, metrics_reset,
    stalled_tensors, start_metrics_server,
)
from horovod_tpu.common.exceptions import HorovodInternalError  # noqa: F401
from horovod_tpu.common.ops_enum import (  # noqa: F401
    Adasum, Average, Max, Min, Product, ReduceOp, Sum,
)
from horovod_tpu.compression import Compression  # noqa: F401
from horovod_tpu.functions import (  # noqa: F401
    allgather_object, broadcast_object,
)

AxisName = Union[str, tuple]


def _pvary(tree: Any, axis_name: Optional[AxisName]) -> Any:
    """Promote every leaf to device-varying over ``axis_name`` (no-op
    leaf-wise where already varying, outside a manual-axes trace, under
    ``check_vma=False``, and on the eager tier's ``axis_name=None``)."""
    import jax
    from jax import lax
    if axis_name is None:
        return tree
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)

    def one(a):
        vma = jax.typeof(a).vma
        return lax.pcast(a, tuple(ax for ax in axes if ax not in vma),
                         to="varying")
    return jax.tree.map(one, tree)


def allreduce_gradients(grads: Any, *, axis_name: Optional[AxisName] = None,
                        op: ReduceOp = Average,
                        compression=Compression.none,
                        name: str = "grads", ef: Any = None) -> Any:
    """Reduce a gradient pytree across ranks.

    In-jit (``axis_name`` given): per-leaf ``lax.psum``/``pmean`` —
    call inside ``shard_map``; XLA fuses and schedules the collectives.
    Only leaves that are actually device-varying over ``axis_name``
    (``jax.typeof(leaf).vma``) are reduced: under JAX's varying-manual-
    axes typing, autodiff cotangents of *replicated* parameters are
    already globally correct (the mean-vs-sum choice lives in the loss
    — see :func:`distributed_value_and_grad`), and an explicit psum on
    them would double-count. Under ``shard_map(check_vma=False)``
    nothing carries that type and autodiff leaves cotangents
    rank-local, so every leaf is reduced. With ``compression``,
    reduced leaves ride the quantized reduce-scatter + all-gather of
    :mod:`horovod_tpu.ops.quantized` (narrow bytes on both hops); its
    results are typed varying, so the enclosing ``shard_map`` needs
    ``check_vma=False`` to return them replicated.
    Eager (no ``axis_name``): one grouped allreduce over all leaves via
    the native-negotiated runtime, so fusion batches small gradients;
    ``compression`` maps to the framework cast (bf16/fp16) or the
    native wire codec (int8) — the same knob either way.

    ``ef`` (in-jit int8 only): a pytree of rank-local error-feedback
    residuals matching ``grads`` (f32, zeros at step 0). When given,
    returns ``(reduced, new_ef)`` so callers — normally
    :func:`distributed_optimizer`, which threads it as optimizer-state
    leaves — carry this step's rounding error into the next. Without
    it, quantization error is dropped each step.
    """
    import jax

    from horovod_tpu import compression as compression_lib
    if compression is None:  # every surface reads None = uncompressed
        compression = Compression.none
    codec = compression_lib.in_jit_codec(compression)

    leaves, treedef = jax.tree.flatten(grads)
    if axis_name is not None:
        from jax import lax
        axes = ({axis_name} if isinstance(axis_name, str)
                else set(axis_name))
        if codec == "int8":
            # int8 has no cast form to fall back on: anything the
            # quantized path can't express is an error up front.
            if op not in (Average, Sum):
                raise ValueError(
                    f"in-jit compression=int8 supports op=Average/Sum "
                    f"only (there is no meaningful quantized {op!r}); "
                    "the cast codecs (bf16/fp16) still wrap "
                    "Max/Min/Adasum")
            if not isinstance(axis_name, str):
                raise NotImplementedError(
                    "in-jit compression=int8 reduces over a single "
                    f"named axis; got {axis_name!r} — reshape the mesh "
                    "or reduce axis-by-axis")

        # axis_index is varying by construction: an empty vma on it
        # means the enclosing shard_map runs with check_vma=False.
        vma_tracked = all(
            ax in jax.typeof(lax.axis_index(ax)).vma for ax in axes)

        def leaf_varies(g):
            return not vma_tracked or bool(axes & jax.typeof(g).vma)

        if (codec != "none" and op in (Average, Sum)
                and isinstance(axis_name, str)):
            from horovod_tpu.ops.quantized import quantized_allreduce
            ef_leaves = (jax.tree.flatten(ef)[0] if ef is not None
                         else [None] * len(leaves))
            out, new_ef = [], []
            for g, r in zip(leaves, ef_leaves):
                if not leaf_varies(g):
                    out.append(g)
                    new_ef.append(r)
                    continue
                res = quantized_allreduce(g, op=op, axis_name=axis_name,
                                          codec=codec, residual=r)
                if r is None:
                    out.append(res)
                    new_ef.append(None)
                else:
                    out.append(res[0])
                    new_ef.append(res[1])
            reduced = jax.tree.unflatten(treedef, out)
            if ef is None:
                return reduced
            return reduced, jax.tree.unflatten(treedef, new_ef)

        def reduce_leaf(g):
            if not leaf_varies(g):
                return g  # replicated or already-reduced cotangent
            # Cast codecs wrap whatever the quantized branch doesn't
            # take (Max/Min/Adasum, and tuple-axis reductions) the
            # pre-PR way: cast to the wire dtype around the collective
            # (identity for Compression.none). Single-axis Average/Sum
            # with a codec never reach here — they ride the quantized
            # branch above.
            g, ctx = compression.compress(g)
            if op == Average:
                g = lax.pmean(g, axis_name)
            elif op == Sum:
                g = lax.psum(g, axis_name)
            elif op == Max:
                g = lax.pmax(g, axis_name)
            elif op == Min:
                g = lax.pmin(g, axis_name)
            elif op == Adasum:
                from horovod_tpu.ops.adasum import adasum_allreduce
                g = adasum_allreduce(g, axis_name)
            else:
                raise ValueError(
                    f"in-jit gradient reduction with op={op!r} is not "
                    "supported (use Average/Sum/Max/Min/Adasum)")
            return compression.decompress(g, ctx)

        reduced = jax.tree.unflatten(treedef, [reduce_leaf(g)
                                               for g in leaves])
        return (reduced, ef) if ef is not None else reduced

    if ef is not None:
        raise ValueError(
            "ef= residuals are an in-jit concern; the eager tier's int8 "
            "error feedback lives inside the native wire codec "
            "(native/src/codec.cc)")
    if not getattr(compression, "cast_tier", True):
        # Wire-only codec (int8): no framework cast exists — the knob
        # rides the native plane as a per-chunk wire codec instead, so
        # eager and in-jit callers share one setting.
        reduced = api.grouped_allreduce(leaves, name=name, op=op,
                                        compression=compression)
        return jax.tree.unflatten(treedef, list(reduced))
    compressed, ctxs = [], []
    for g in leaves:
        c, ctx = compression.compress(g)
        compressed.append(c)
        ctxs.append(ctx)
    reduced = api.grouped_allreduce(compressed, name=name, op=op)
    out = [compression.decompress(r, ctx) for r, ctx in zip(reduced, ctxs)]
    return jax.tree.unflatten(treedef, out)


def distributed_optimizer(optimizer, *,
                          axis_name: Optional[AxisName] = None,
                          op: ReduceOp = Average,
                          compression=Compression.none,
                          name: str = "distributed_optimizer",
                          backward_passes_per_step: int = 1):
    """Wrap an optax ``GradientTransformation`` so incoming gradients
    are reduced across ranks before the inner update — the optax
    analog of ``hvd.DistributedOptimizer``.

    Use inside ``jit``/``shard_map`` with ``axis_name=...``, or eagerly
    (one process per rank) without.

    ``backward_passes_per_step=N`` enables local gradient aggregation
    (the JAX analog of the reference's
    ``tensorflow/gradient_aggregation.py:16`` and the torch wrapper's
    same-named knob): gradients are summed LOCALLY for N calls and
    reduced across ranks only on every N-th — one collective per N
    microbatches. Non-boundary calls emit zero updates (parameters and
    inner optimizer state advance only on the boundary), so
    ``optax.apply_updates`` can run unconditionally every microbatch.
    The boundary update equals one big-batch update on the SUM of the
    local microbatch gradients, matching the torch tier (average the
    loss over the N passes, or scale the LR, exactly as with the
    reference).
    """
    import optax

    from horovod_tpu import compression as compression_lib

    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    # In-jit int8 threads rank-local error-feedback residuals as
    # explicit optimizer-state leaves (the mesh-plane analog of the
    # wire codec's EF slabs): state grows an "ef" pytree of f32 zeros
    # and every reduce consumes/produces it, so int8 rounding error
    # telescopes across steps instead of compounding.
    use_ef = (axis_name is not None
              and compression_lib.needs_error_feedback(compression))

    def reduce_grads(grads, ef=None):
        return allreduce_gradients(
            grads, axis_name=axis_name, op=op, compression=compression,
            name=name, ef=ef)

    def init_ef(params):
        import jax
        import jax.numpy as jnp
        return _pvary(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params), axis_name)

    if backward_passes_per_step == 1:
        if use_ef:
            def init_fn(params):
                return {"inner": optimizer.init(params),
                        "ef": init_ef(params)}

            def update_fn(updates, state, params=None, **extra):
                reduced, new_ef = reduce_grads(updates, state["ef"])
                out, inner = optimizer.update(reduced, state["inner"],
                                              params, **extra)
                return out, {"inner": inner, "ef": new_ef}

            return optax.GradientTransformation(init_fn, update_fn)

        def init_fn(params):
            return optimizer.init(params)

        def update_fn(updates, state, params=None, **extra):
            return optimizer.update(reduce_grads(updates), state, params,
                                    **extra)

        return optax.GradientTransformation(init_fn, update_fn)

    import jax
    import jax.numpy as jnp

    n = backward_passes_per_step

    def init_acc(params):
        state = {"inner": optimizer.init(params),
                 # Varying from the start: keeps the accumulator's
                 # VMA type STABLE between init and update, so the
                 # canonical lax.scan-over-microbatches carry typechecks.
                 "acc": _pvary(jax.tree.map(jnp.zeros_like, params),
                               axis_name),
                 "count": jnp.zeros((), jnp.int32)}
        if use_ef:
            state["ef"] = init_ef(params)
        return state

    def boundary_update(acc, inner, ef, params, extra):
        if use_ef:
            reduced, ef = reduce_grads(acc, ef)
        else:
            reduced = reduce_grads(acc)
        new_updates, new_inner = optimizer.update(
            reduced, inner, params, **extra)
        zero_acc = jax.tree.map(jnp.zeros_like, acc)
        return new_updates, zero_acc, new_inner, ef

    def update_acc(updates, state, params=None, **extra):
        acc = _pvary(jax.tree.map(jnp.add, state["acc"], updates),
                     axis_name)
        count = state["count"] + 1
        ef = state.get("ef")

        if axis_name is None:
            # Eager tier: concrete control flow (the native-runtime
            # collective is a host call and cannot live under lax.cond).
            if int(count) >= n:
                out, acc, inner, ef = boundary_update(
                    acc, state["inner"], ef, params, extra)
                count = jnp.zeros((), jnp.int32)
            else:
                out = jax.tree.map(jnp.zeros_like, updates)
                inner = state["inner"]
        else:
            # In-jit tier: both branches trace; `count` is replicated
            # so every rank takes the same one and the collectives in
            # the boundary branch stay SPMD-legal.
            from jax import lax

            def hold(acc, inner, ef):
                # FRESH-constant zeros, not zeros_like(acc): constants
                # are replicated under VMA typing, matching the
                # boundary branch's post-reduction updates — and the
                # emitted zero updates keep params replicated, exactly
                # like the N=1 path. (zeros_like would inherit acc's
                # device-varying type and poison params' VMA.)
                zeros = jax.tree.map(
                    lambda a: jnp.zeros(a.shape, a.dtype), acc)
                return zeros, acc, inner, ef

            out, acc, inner, ef = lax.cond(
                count >= n,
                lambda a, i, e: boundary_update(a, i, e, params, extra),
                hold, acc, state["inner"], ef)
            count = jnp.where(count >= n, 0, count)

        new_state = {"inner": inner, "acc": acc, "count": count}
        if use_ef:
            new_state["ef"] = ef
        return out, new_state

    return optax.GradientTransformation(init_acc, update_acc)


def distributed_value_and_grad(fun: Callable, argnums=0, *,
                               has_aux: bool = False,
                               axis_name: Optional[AxisName] = None,
                               op: ReduceOp = Average,
                               compression=Compression.none,
                               name: str = "distributed_grad") -> Callable:
    """``jax.value_and_grad`` whose gradients arrive pre-reduced across
    ranks — the ``DistributedGradientTape`` analog (reference
    ``tensorflow/__init__.py:723-790``).

    In-jit tier: the *loss itself* is reduced over ``axis_name``
    (``pmean`` for Average, ``psum`` for Sum) and autodiff then yields
    the exactly-corresponding global gradients — the VMA-correct way to
    express data-parallel training under ``shard_map`` (an explicit
    psum of replicated-param cotangents would double-count).
    Eager tier: local grads are computed, then group-allreduced.
    """
    import jax

    if axis_name is not None:
        from jax import lax
        if op not in (Average, Sum):
            raise ValueError(
                "in-jit distributed_value_and_grad supports Average/Sum")

        from horovod_tpu import compression as compression_lib

        if compression_lib.in_jit_codec(compression) != "none":
            # Grads must exist explicitly before the collective for
            # the quantized reduce-scatter + all-gather to ride them
            # (autodiff of a pmean'd loss never materializes an
            # interceptable gradient allreduce): local grads, then
            # reduce both loss and grads. The differentiated args are
            # cast varying first — under VMA typing the cotangent of a
            # replicated arg arrives already psummed, uncompressed.
            lvg = jax.value_and_grad(fun, argnums=argnums,
                                     has_aux=has_aux)
            nums = (argnums,) if isinstance(argnums, int) else argnums

            def local_wrapped(*args, **kwargs):
                args = tuple(_pvary(a, axis_name) if i in nums else a
                             for i, a in enumerate(args))
                value, grads = lvg(*args, **kwargs)
                loss = value[0] if has_aux else value
                loss = (lax.pmean(loss, axis_name) if op == Average
                        else lax.psum(loss, axis_name))
                value = (loss, value[1]) if has_aux else loss
                grads = allreduce_gradients(
                    grads, axis_name=axis_name, op=op,
                    compression=compression, name=name)
                return value, grads

            return local_wrapped

        def global_fun(*args, **kwargs):
            out = fun(*args, **kwargs)
            if has_aux:
                loss, aux = out
            else:
                loss, aux = out, None
            loss = (lax.pmean(loss, axis_name) if op == Average
                    else lax.psum(loss, axis_name))
            return (loss, aux) if has_aux else loss

        return jax.value_and_grad(global_fun, argnums=argnums,
                                  has_aux=has_aux)

    vg = jax.value_and_grad(fun, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        value, grads = vg(*args, **kwargs)
        grads = allreduce_gradients(
            grads, axis_name=axis_name, op=op, compression=compression,
            name=name)
        return value, grads

    return wrapped


def broadcast_parameters(params: Any, root_rank: int = 0,
                         name: str = "broadcast_parameters") -> Any:
    """Broadcast a parameter pytree from ``root_rank``; returns the
    synced pytree (functional — jax arrays are immutable, unlike the
    reference's in-place ``torch/functions.py:29``)."""
    import jax

    leaves, treedef = jax.tree.flatten(params)
    handles = [api.broadcast_async(leaf, root_rank=root_rank,
                                   name=f"{name}.{i}")
               for i, leaf in enumerate(leaves)]
    synced = []
    for leaf, h in zip(leaves, handles):
        out = api.synchronize(h)
        synced.append(out.reshape(leaf.shape) if hasattr(out, "reshape")
                      else out)
    return jax.tree.unflatten(treedef, synced)


def sync_batch_norm(x, *, axis_name: AxisName = "dp",
                    scale=None, bias=None, eps: float = 1e-5,
                    reduce_dims=None):
    """Normalize ``x`` with batch statistics taken over BOTH the local
    reduce dims and the ``axis_name`` mesh axis — the in-jit SPMD analog
    of the reference's SyncBatchNorm (``torch/sync_batch_norm.py:22``,
    ``tensorflow/sync_batch_norm.py:22``). Call inside
    ``shard_map``/``pjit``; stats ride two small ``psum``\\ s that XLA
    fuses into one.

    ``reduce_dims`` defaults to all dims except the last (channel).
    Returns ``(y, mean, var)`` so callers can maintain running stats.
    For flax models, ``flax.linen.BatchNorm(axis_name="dp")`` achieves
    the same inside ``pjit`` — this helper is the framework-free form.
    """
    import jax.numpy as jnp
    from jax import lax

    if reduce_dims is None:
        reduce_dims = tuple(range(x.ndim - 1))
    reduce_dims = tuple(d % x.ndim for d in reduce_dims)
    h = x.astype(jnp.float32)
    n_local = 1
    for d in reduce_dims:
        n_local *= x.shape[d]
    stats = jnp.stack([jnp.sum(h, axis=reduce_dims),
                       jnp.sum(h * h, axis=reduce_dims)])
    stats = lax.psum(stats, axis_name)
    from horovod_tpu.ops.collectives import axis_size
    n = n_local * axis_size(axis_name)
    mean = stats[0] / n
    var = stats[1] / n - mean * mean
    # Broadcast stats back to x's layout: kept (channel) dims stay,
    # reduced dims become 1 — so NCHW-style reduce_dims=(0, 2, 3)
    # works, not just channels-last.
    bshape = [1 if d in reduce_dims else x.shape[d] for d in range(x.ndim)]
    y = (h - mean.reshape(bshape)) * lax.rsqrt(var.reshape(bshape) + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32).reshape(bshape)
    if bias is not None:
        y = y + bias.astype(jnp.float32).reshape(bshape)
    return y.astype(x.dtype), mean, var
