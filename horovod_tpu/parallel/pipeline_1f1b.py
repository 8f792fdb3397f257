"""1F1B (one-forward-one-backward) pipeline schedule over ``pp``.

GPipe (:mod:`horovod_tpu.parallel.pipeline`) runs all forwards then
lets reverse-mode AD replay the schedule backwards — simple, but every
stage holds activations for ALL ``M`` in-flight microbatches. The 1F1B
schedule (PipeDream-Flush ordering) starts each microbatch's backward
as soon as the last stage finishes its forward, bounding the in-flight
residuals per stage to ``O(S)`` regardless of ``M`` — the memory
headroom that lets deep pipelines raise ``n_micro`` to amortize the
bubble.

Cost model — stated, not implied (see docs/parallelism.md for the
measurements): each backward unit RECOMPUTES its stage forward from
the stored stage input (``jax.value_and_grad`` per tick), and both
units run on every one of the ``M + 2S - 1`` ticks including the
masked fill/drain ones, so the analytic per-device cost is
``4(M + 2S - 1)`` stage-forward units vs the no-bubble ideal's
``3M`` (an idealized non-recomputing 1F1B à la Megatron-LM would be
``3M`` plus bubble). Measured on a real chip the trade lands well:
at pp=1 the island runs ~1.26x FASTER than the flat step (XLA drops
part of the masked work; the recompute matches what the default remat
policy pays anyway) — but the recompute factor is real and this
module chooses it deliberately for the O(S) activation bound.

Reverse-mode AD cannot express interleaved forward/backward, so this
module computes the backward EXPLICITLY inside the schedule
(``jax.value_and_grad`` per stage per tick, recompute-from-residual
style — each stage stores only its INPUT) and exposes the whole thing
through ``jax.custom_vjp``:

* forward: run the 1F1B schedule — per-microbatch loss is computed
  INSIDE the last stage (that is what makes cotangents available one
  tick after a microbatch's forward), and the parameter/input grads
  come out as primal by-products;
* backward: scale the stashed grads by the incoming loss cotangent
  (the gradients are linear in it — exact).

The embedding stays OUTSIDE the island (its vocab-parallel lookup is
its own manual shard_map and Shardy cannot nest manual islands); its
gradient flows through the returned per-microbatch input cotangents.
The head/loss sit inside the last stage under GSPMD auto axes (plain
matmuls — no nested island needed), guarded by ``lax.cond`` so only
the last rank pays for them.

Schedule shape (``S`` stages, ``M`` microbatches, one fwd unit AND one
bwd unit per tick):

* forward of microbatch ``m`` at stage ``s``: tick ``m + s``;
* backward of ``m`` at stage ``s``: tick ``m + 2S - 1 - s`` (the last
  stage backs up ``m`` one tick after its forward; cotangents ppermute
  UP one stage per tick, and the validity windows of sender and
  receiver align tick-for-tick);
* residual lifetime at stage ``s``: ``2(S - s) - 1 < 2S`` ticks — a
  ``2S``-slot ring buffer per stage holds the stage inputs.

Total ticks: ``M + 2S - 1`` (vs GPipe's ``M + S - 1`` forward ticks +
AD replay); the recompute and the extra masked ticks are the price of
the ``O(S)`` activation bound — see the module docstring's cost model
and docs/parallelism.md for measured numbers.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel.pipeline import _stage_specs


def pipeline_1f1b(stage_fn: Callable, last_fn: Callable, stage_params,
                  last_params, microbatches, *, mesh: Mesh,
                  axis_name: str = "pp",
                  extra_axes: frozenset = frozenset(),
                  mb_spec=None):
    """Run the 1F1B schedule; returns ``(loss_sum, stage_grads,
    last_grads, d_microbatches)`` — all PRIMAL values (f32 grads).

    ``stage_fn(layer_slice, x) -> (y, aux)`` is one stage's block
    (shape and dtype preserving) plus a scalar auxiliary loss (0.0 when
    unused; the MoE load-balancing term otherwise — it is ADDED to the
    stage scalar, so its gradient rides the same per-stage vjp and its
    value is psum'd into the returned loss);
    ``last_fn(last_params, y, m_idx) -> scalar_loss`` is the last
    stage's head+loss applied AFTER its block (``m_idx`` is the
    microbatch index, for targets closed over outside).
    ``stage_params`` leaves carry a leading stage dim ``S``;
    ``last_params`` is replicated over ``pp`` (only the last stage
    touches it — its grads come back masked-psum'd).
    ``microbatches``: ``[M, mb, ...]``.

    Wrap with :func:`make_1f1b_loss` for a differentiable scalar.
    """
    S = mesh.shape[axis_name]
    M = microbatches.shape[0]
    R = 2 * S  # residual ring slots; lifetime 2(S-s)-1 < R

    dtype = microbatches.dtype
    f32_wire = (jax.default_backend() == "cpu" and dtype == jnp.bfloat16)
    if f32_wire:
        # Same XLA-CPU limitation as pipeline.py: shard_map-level bf16
        # reductions crash the CPU AllReducePromotion pass.
        microbatches = microbatches.astype(jnp.float32)

    def island(sp, lp, mb):
        local = jax.tree.map(lambda a: a[0], sp)     # my stage's layers
        s_idx = lax.axis_index(axis_name)
        vzero = (s_idx * 0).astype(dtype)
        vzero32 = (s_idx * 0).astype(jnp.float32)
        mb_shape = mb.shape[1:]

        def stage_loss(lparams, lastp, x, g_in, m_idx):
            """One scalar per stage whose gradient is exactly the vjp
            this stage needs: the true loss on the last stage (``m_idx``
            lets the head index per-microbatch targets closed over in
            ``last_fn``), and <stage output, incoming cotangent>
            elsewhere (its gradient w.r.t. (params, x) IS
            vjp-with-cotangent-``g_in``). The stage's auxiliary term
            (MoE load balancing) adds to the scalar on EVERY stage —
            the total objective is loss + sum of auxes, and addition
            makes the vjp exact. Returns (scalar, aux) so the aux
            VALUE can be accumulated without a second forward."""
            yy, aux = stage_fn(lparams, x)
            aux = aux.astype(jnp.float32)

            def last_branch(op):
                lastp_, yy_ = op
                return last_fn(lastp_, yy_, m_idx).astype(jnp.float32)

            def mid_branch(op):
                _, yy_ = op
                return (yy_.astype(jnp.float32)
                        * g_in.astype(jnp.float32)).sum()

            return lax.cond(s_idx == S - 1, last_branch, mid_branch,
                            (lastp, yy)) + aux, aux

        def tick(carry, t):
            (acts_f, g_up, ring, grads, lgrads, dmb, loss_acc) = carry

            # ---------------- forward unit ----------------
            mf = t - s_idx
            f_real = (mf >= 0) & (mf < M)
            mfc = jnp.clip(mf, 0, M - 1)
            x0 = lax.dynamic_index_in_dim(mb, mfc, 0, keepdims=False)
            if f32_wire:
                x0 = (x0 + vzero.astype(x0.dtype)).astype(dtype)
            x_in = jnp.where(s_idx == 0, x0, acts_f)
            y, _ = stage_fn(local, x_in)
            ring = jnp.where(
                f_real,
                lax.dynamic_update_index_in_dim(ring, x_in, mfc % R, 0),
                ring)

            # ---------------- backward unit ---------------
            mb_i = t - (2 * S - 1 - s_idx)
            b_real = (mb_i >= 0) & (mb_i < M)
            mbc = jnp.clip(mb_i, 0, M - 1)
            x_res = lax.dynamic_index_in_dim(ring, mbc % R, 0,
                                             keepdims=False)
            (loss_m, aux_m), (dlp, dlast, dx) = jax.value_and_grad(
                stage_loss, argnums=(0, 1, 2), has_aux=True)(
                    local, lp, x_res, g_up, mbc)
            grads = jax.tree.map(
                lambda acc, g: acc
                + jnp.where(b_real, g.astype(jnp.float32), 0.0),
                grads, dlp)
            lgrads = jax.tree.map(
                lambda acc, g: acc + jnp.where(
                    b_real & (s_idx == S - 1), g.astype(jnp.float32),
                    0.0),
                lgrads, dlast)
            # Stage 0's dx is the embedded-input cotangent: bank it.
            # Written once per microbatch (never accumulated), so the
            # wire dtype is lossless-enough — an f32 buffer would
            # double the largest O(M) carry and its psum for nothing.
            dmb = jnp.where(
                b_real & (s_idx == 0),
                lax.dynamic_update_index_in_dim(
                    dmb, dx.astype(dtype), mbc, 0),
                dmb)
            # Last stage: loss_m already includes its own aux; other
            # stages contribute only their aux value (their scalar's
            # dot term is a vjp artifact, not a loss).
            loss_acc = loss_acc + jnp.where(
                b_real, jnp.where(s_idx == S - 1, loss_m, aux_m), 0.0)

            # ---------------- shifts ----------------------
            # Forward activations flow DOWN (s -> s+1) ...
            acts_f = lax.ppermute(y, axis_name,
                                  [(i, i + 1) for i in range(S - 1)])
            # ... cotangents flow UP (s -> s-1). Masked-invalid ticks
            # ship garbage, but sender and receiver share the same
            # microbatch index per tick, so garbage only lands where
            # b_real is false.
            g_up = lax.ppermute(dx.astype(dtype), axis_name,
                                [(i + 1, i) for i in range(S - 1)])
            return (acts_f, g_up, ring, grads, lgrads, dmb,
                    loss_acc), None

        init = (
            jnp.zeros(mb_shape, dtype) + vzero,            # acts_f
            jnp.zeros(mb_shape, dtype) + vzero,            # g_up
            jnp.zeros((R,) + mb_shape, dtype) + vzero,     # ring
            jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32) + vzero32,
                local),                                    # grads
            jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32) + vzero32,
                lp),                                       # lgrads
            jnp.zeros((M,) + mb_shape, dtype) + vzero,     # dmb
            jnp.zeros((), jnp.float32) + vzero32,          # loss
        )
        # Last tick: stage 0's backward of microbatch M-1 at
        # (M-1) + 2S - 1 - 0 -> ticks 0 .. M+2S-2 inclusive.
        n_ticks = M + 2 * S - 1
        (_, _, _, grads, lgrads, dmb, loss_acc), _ = lax.scan(
            tick, init, jnp.arange(n_ticks))

        # Replicate the loss (every stage contributes: the last one
        # its loss+aux, the rest their aux), the last stage's head
        # grads, and stage 0's input cotangents to every pp rank.
        # Under pp+sp (extra_axes) the loss and the head/layer grads
        # are additionally PARTIAL over the sequence shards — each sp
        # shard computed its local-token share — so those reductions
        # span the sp axis too; stage 0's input cotangents stay
        # sp-LOCAL (the embedding outside is sequence-sharded).
        repl_axes = (axis_name,) + tuple(extra_axes)
        loss = lax.psum(loss_acc, repl_axes)
        lgrads = jax.tree.map(
            lambda g: lax.psum(
                jnp.where(s_idx == S - 1, g, jnp.zeros_like(g)),
                repl_axes), lgrads)
        if f32_wire:
            dmb = lax.psum(
                jnp.where(s_idx == 0, dmb.astype(jnp.float32),
                          jnp.zeros(dmb.shape, jnp.float32)),
                axis_name)
        else:
            dmb = lax.psum(
                jnp.where(s_idx == 0, dmb, jnp.zeros_like(dmb)),
                axis_name)
        if extra_axes:
            # Layer grads: each sp shard holds its local-token share;
            # the stage's true gradient sums over the sequence shards.
            grads = jax.tree.map(
                lambda g: lax.psum(g, tuple(extra_axes)), grads)
        grads = jax.tree.map(lambda g: g[None], grads)  # restage [1,..]
        return loss, grads, lgrads, dmb

    sspec = _stage_specs(stage_params)
    last_repl = jax.tree.map(lambda _: P(), last_params)
    mspec = P() if mb_spec is None else mb_spec
    # check_vma=False: masked psums + pallas-containing stage_fns defeat
    # the VMA inference (same as the GPipe island).
    return jax.shard_map(
        island, mesh=mesh,
        in_specs=(sspec, last_repl, mspec),
        out_specs=(P(), sspec, last_repl, mspec),
        axis_names=frozenset({axis_name}) | frozenset(extra_axes),
        check_vma=False)(
            stage_params, last_params, microbatches)


def make_1f1b_loss(stage_fn, last_fn, mesh, axis_name: str = "pp",
                   extra_axes: frozenset = frozenset(), mb_spec=None):
    """Differentiable ``loss(stage_params, last_params, microbatches)``
    whose forward runs the 1F1B schedule and whose backward returns the
    schedule's own stashed gradients scaled by the loss cotangent."""

    @jax.custom_vjp
    def loss_fn(stage_params, last_params, microbatches):
        loss, _, _, _ = pipeline_1f1b(
            stage_fn, last_fn, stage_params, last_params, microbatches,
            mesh=mesh, axis_name=axis_name, extra_axes=extra_axes,
            mb_spec=mb_spec)
        return loss

    def fwd(stage_params, last_params, microbatches):
        loss, grads, lgrads, dmb = pipeline_1f1b(
            stage_fn, last_fn, stage_params, last_params, microbatches,
            mesh=mesh, axis_name=axis_name, extra_axes=extra_axes,
            mb_spec=mb_spec)
        # Residuals must be arrays: cast the stashed f32 grads to the
        # primal dtypes now; bwd only scales them.
        grads = jax.tree.map(lambda g, a: g.astype(a.dtype), grads,
                             stage_params)
        lgrads = jax.tree.map(lambda g, a: g.astype(a.dtype), lgrads,
                              last_params)
        return loss, (grads, lgrads, dmb.astype(microbatches.dtype))

    def bwd(res, g):
        grads, lgrads, dmb = res
        scale = g.astype(jnp.float32)

        def sc(gr):
            return (gr.astype(jnp.float32) * scale).astype(gr.dtype)

        return (jax.tree.map(sc, grads), jax.tree.map(sc, lgrads),
                sc(dmb))

    loss_fn.defvjp(fwd, bwd)
    return loss_fn
