"""Pipeline parallelism over the ``pp`` mesh axis.

The reference is DP-only (SURVEY.md §2.6) — pipeline parallelism is a
TPU-first addition. Design: a **GPipe microbatch schedule written as a
``shard_map`` island, manual over ``pp`` only** (``axis_names={"pp"}``),
so GSPMD keeps handling tp/fsdp sharding *inside* every stage:

* every pp rank holds one stage's slice of the layer-stacked params
  (leading dim ``S`` sharded over ``pp``);
* one ``lax.scan`` over ``M + S - 1`` ticks; each tick every stage
  runs its block on its current microbatch and ``ppermute``-shifts the
  activation one hop down the chain (stage 0 ingests a fresh
  microbatch, the last stage banks its output);
* outputs are replicated back to all pp ranks with a masked ``psum``.

The schedule is differentiable end to end (``jax.grad`` reverses the
scan and the ppermutes), giving GPipe's forward-then-backward with a
bubble fraction of ``(S-1)/(M+S-1)`` — raise ``n_micro`` to amortize.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _stage_specs(stage_params) -> Any:
    """Leading dim of every leaf is the stage dim → shard over pp."""
    return jax.tree.map(
        lambda a: P("pp", *([None] * (jnp.ndim(a) - 1))), stage_params)


def pipeline_apply(stage_fn: Callable, stage_params, microbatches, *,
                   mesh: Mesh, axis_name: str = "pp",
                   remat_stage: bool = True, remat_policy=None,
                   with_aux: bool = False, check_vma: bool = True,
                   extra_axes: frozenset = frozenset(),
                   mb_spec: Any = None):
    """Run ``microbatches [M, mb, ...]`` through ``S`` pipeline stages.

    ``stage_fn(params_slice, x) -> y`` must preserve ``x``'s
    shape/dtype (decoder blocks do); ``stage_params`` leaves carry a
    leading stage dim of size ``S = mesh.shape[axis_name]``. Returns
    outputs shaped like ``microbatches``, replicated over ``pp``.

    ``with_aux=True``: ``stage_fn`` returns ``(y, aux_scalar_f32)``
    (e.g. the MoE load-balancing term); aux is accumulated over every
    REAL (non-bubble) tick and summed over stages — the return becomes
    ``(outputs, aux_total)``.

    ``extra_axes``/``mb_spec`` extend the island's MANUAL axis set
    beyond ``pp`` (pp+sp composition: Shardy cannot NEST a manual sp
    island inside the pp island, but ONE island manual over both axes
    is fine — ``stage_fn`` then sees sequence-LOCAL shards and runs
    the ring attention body directly). ``mb_spec`` is the microbatch
    in/out spec over the manual axes (default: replicated).
    """
    S = mesh.shape[axis_name]
    M = microbatches.shape[0]
    base_fn = stage_fn
    if not with_aux:
        def base_fn(p, x):  # noqa: F811 — uniform (y, aux) contract
            return stage_fn(p, x), jnp.zeros((), jnp.float32)
    fn = (jax.checkpoint(base_fn, policy=remat_policy) if remat_stage
          else base_fn)
    # XLA-CPU workaround: under partial-manual shard_map the Shardy
    # partitioner leaves a sharding_constraint inside all-reduce reducer
    # regions, and the CPU AllReducePromotion pass aborts cloning any
    # BF16 all-reduce shaped like that ("Invalid binary instruction
    # opcode copy"). Every shard_map-level psum here — the forward
    # output replication AND the autodiff transpose psum at the
    # replicated-microbatch boundary — must therefore be f32 on CPU.
    # TPU reduces bf16 natively and skips all of this.
    dtype = microbatches.dtype
    f32_wire = (jax.default_backend() == "cpu" and dtype == jnp.bfloat16)
    if f32_wire:
        microbatches = microbatches.astype(jnp.float32)

    def island(sp, mb):
        local = jax.tree.map(lambda a: a[0], sp)   # my stage's slice
        idx = lax.axis_index(axis_name)
        if f32_wire:
            # Make mb pp-varying FIRST (adding a varying zero), THEN
            # cast down: the replicated→varying boundary is where
            # autodiff inserts its transpose psum, and it must sit on
            # the f32 side of the cast.
            mb = (mb + (idx * 0).astype(mb.dtype)).astype(dtype)

        def tick(carry, t):
            acts, outs, aux_acc = carry
            m = t - idx                             # my microbatch index
            mc = jnp.clip(m, 0, M - 1)
            x0 = lax.dynamic_index_in_dim(mb, mc, 0, keepdims=False)
            inp = jnp.where(idx == 0, x0, acts)
            y, aux = fn(local, inp)
            real = (m >= 0) & (m < M)               # non-bubble tick
            aux_acc = aux_acc + jnp.where(real, aux, 0.0)
            bank = real & (idx == S - 1)
            outs = jnp.where(bank,
                             lax.dynamic_update_index_in_dim(outs, y, mc, 0),
                             outs)
            # Shift down the chain (no wraparound: stage 0's next input
            # comes from mb, the last stage's output was banked).
            acts = lax.ppermute(y, axis_name,
                                [(i, i + 1) for i in range(S - 1)])
            return (acts, outs, aux_acc), None

        # The zeros are constant across pp but the loop makes them
        # device-varying, so the scan carry needs a varying type on
        # both sides. Adding a varying zero (derived from axis_index)
        # does that WITHOUT lax.pcast: pcast's transpose is a psum over
        # pp, and XLA's CPU AllReducePromotion pass crashes on the
        # resulting bf16 all-reduce; the add's transpose stays local.
        vzero = (idx * 0).astype(mb.dtype)
        init = jax.tree.map(lambda a: a + vzero,
                            (jnp.zeros_like(mb[0]), jnp.zeros_like(mb)))
        init = (*init, jnp.zeros((), jnp.float32)
                + (idx * 0).astype(jnp.float32))
        (_, outs, aux_acc), _ = lax.scan(tick, init,
                                         jnp.arange(M + S - 1))
        # Only the last stage's bank is real; replicate it everywhere
        # (f32 on the wire under the CPU workaround above). Aux sums
        # over stages (already f32, so the psum is CPU-safe).
        masked = jnp.where(idx == S - 1, outs, jnp.zeros_like(outs))
        if f32_wire:
            outs = lax.psum(masked.astype(jnp.float32),
                            axis_name).astype(dtype)
        else:
            outs = lax.psum(masked, axis_name)
        aux_total = lax.psum(aux_acc, axis_name)
        return outs, aux_total

    # check_vma=False is needed when stage_fn contains a pallas_call
    # (its out_shape carries no VMA annotation — same limitation as the
    # ring_flash island in ring_attention.py).
    if mb_spec is None:
        mb_spec = P()
    outs, aux_total = jax.shard_map(island, mesh=mesh,
                                    in_specs=(_stage_specs(stage_params),
                                              mb_spec),
                                    out_specs=(mb_spec, P()),
                                    axis_names=frozenset({axis_name})
                                    | extra_axes,
                                    check_vma=check_vma)(
                                        stage_params, microbatches)
    if with_aux:
        return outs, aux_total
    return outs


# ---------------------------------------------------------------------------
# Transformer integration
# ---------------------------------------------------------------------------

def pp_reshape_layers(params, n_stages: int):
    """[L, ...]-stacked layer leaves → [S, L/S, ...] for the stage dim."""
    def reshape(a):
        L = a.shape[0]
        if L % n_stages:
            raise ValueError(
                f"n_layers={L} not divisible by pp={n_stages}")
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return {**params, "layers": jax.tree.map(reshape, params["layers"])}


def pp_param_specs(cfg, n_stages: int):
    """Sharding specs matching :func:`pp_reshape_layers`: stage dim over
    ``pp``, the rest as in the flat model."""
    from horovod_tpu.models import transformer as tr

    base = tr.param_specs(cfg)
    def respecs(s):
        return P("pp", *s)  # s already leads with None for the L dim
    return {**base, "layers": jax.tree.map(
        respecs, base["layers"], is_leaf=lambda x: isinstance(x, P))}


def _wire_train_step(cfg, mesh: Mesh, loss_fn, optimizer):
    """Shared tail of both pp step factories: stage-reshaped params,
    value_and_grad step, init and donated step jitted with the state's
    layout pinned (:func:`~horovod_tpu.models.transformer.jit_sharded_state`)."""
    import optax

    from horovod_tpu.models import transformer as tr

    tr._refuse_mixed(cfg, "the pipeline's train step (make_pp_train_step, "
                     "make_pp_train_step_1f1b)")
    S = mesh.shape["pp"]
    specs = pp_param_specs(cfg, S)

    def init_state(key):
        params = pp_reshape_layers(tr.init_params(cfg, key), S)
        return {"params": params, "opt": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    def step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        updates, new_opt = optimizer.update(grads, state["opt"],
                                            state["params"])
        params = optax.apply_updates(state["params"], updates)
        return {"params": params, "opt": new_opt,
                "step": state["step"] + 1}, loss

    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))
    batch_sh = {"tokens": NamedSharding(mesh, P(("dp", "fsdp"), None))}
    init_state, jit_step = tr.jit_sharded_state(
        init_state, step, mesh, param_sh, batch_sh, donate=True)
    return init_state, jit_step, param_sh


def _pp_stage_attention(cfg, mesh: Mesh):
    """Per-stage attention for inside the pp island, plus the island
    config it implies: ``(attend, sp_size, extra_axes, mb_spec)``.

    sp == 1 — plain XLA attention on the stage's full sequence. The
    flash Pallas kernel is NOT used: inside the pp island the batch/
    head dims stay under GSPMD (auto axes), and the partitioner
    replicates operands around a Mosaic call it cannot shard
    (measured: 3x the all-gathers and +30% temp memory vs local
    attention on a dp×pp×tp mesh) — XLA's fused attention is the
    better per-stage choice until pallas calls carry sharding rules.

    sp > 1 — **pp+sp composes in ONE island manual over both axes**:
    Shardy cannot nest the sp island inside the pp island, but the
    pure-XLA attention BODIES (raw ppermute / all_to_all code) run
    directly inside the combined island on sequence-local shards.
    ``cfg.sp_attention="ulysses"`` keeps Ulysses (head-scatter
    all-to-all); everything else maps to the ring (the Pallas ring
    blocks hit the same Mosaic auto-partitioning wall as flash here).
    """
    import functools

    from horovod_tpu.models import transformer as tr
    from horovod_tpu.parallel.ring_attention import (ring_self_attention,
                                                     ulysses_attention)

    sp_size = dict(mesh.shape).get("sp", 1)
    if sp_size == 1:
        attend = tr._attention_island(
            dataclasses.replace(cfg, sp_attention="local"), None)
        return attend, 1, frozenset(), None
    body = (ulysses_attention if cfg.sp_attention == "ulysses"
            else ring_self_attention)
    attend = functools.partial(body, axis_name="sp", causal=True)
    return attend, sp_size, frozenset({"sp"}), P(None, None, "sp", None)


def make_pp_train_step(cfg, mesh: Mesh, n_micro: int, optimizer=None):
    """GPipe training step for the transformer over a mesh with pp>1
    (compose with dp/fsdp/tp/sp/ep as usual). Sequence parallelism
    composes via a single island manual over {pp, sp}: per-stage
    attention becomes the ring body over ``sp`` and rotary positions
    are shard-offset (see :func:`_pp_stage_attention`). sp+MoE inside
    a pipeline stays unsupported (the aux statistic would need its
    own cross-shard reduction).

    MoE composes: the load-balancing aux term threads through the
    schedule, computed per microbatch (the natural statistic inside a
    pipeline — it differs from a full-batch aux exactly as microbatched
    MoE training always does).

    Returns ``(init_state, jit_step, param_shardings)`` like
    :func:`horovod_tpu.models.transformer.make_train_step`.
    """
    import optax

    from horovod_tpu.models import transformer as tr

    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)
    S = mesh.shape["pp"]
    constrain = tr._constrainer(mesh)
    attend, sp_size, extra_axes, mb_spec = _pp_stage_attention(cfg, mesh)
    if sp_size > 1 and cfg.n_experts > 0:
        raise NotImplementedError(
            "pp + sp + MoE is not supported (the per-shard aux "
            "statistic needs its own cross-sp reduction)")

    def stage_fn(stage_layers, x):
        # Inside the island x is sequence-LOCAL under sp; rotary
        # positions must be the global ones for this shard.
        off = (lax.axis_index("sp") * x.shape[1] if sp_size > 1 else 0)

        def one(x, lp):
            return tr.decoder_layer(cfg, attend, lambda v, *s: v, x, lp,
                                    pos_offset=off)
        y, auxes = lax.scan(one, x, stage_layers)
        return y, auxes.sum()

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        B, T = inp.shape
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
        x = tr.embed_lookup(params["embed"], inp, cfg.dtype, mesh)
        x = constrain(x, ("dp", "fsdp"), "sp" if sp_size > 1 else None,
                      None)
        mb = x.reshape(n_micro, B // n_micro, T, x.shape[-1])
        y, aux = pipeline_apply(stage_fn, params["layers"], mb, mesh=mesh,
                                remat_stage=cfg.remat,
                                remat_policy=tr.remat_policy_fn(cfg),
                                with_aux=True, extra_axes=extra_axes,
                                mb_spec=mb_spec)
        x = y.reshape(B, T, -1)
        x = tr._rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = (x @ params["lm_head"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        # Aux accumulated once per (stage, microbatch); lm_loss's flat
        # form sums per-layer aux once over the whole batch — per-
        # microbatch MoE terms are means over their microbatch, so the
        # microbatch-summed aux must be averaged back.
        return nll.mean() + aux / n_micro

    return _wire_train_step(cfg, mesh, loss_fn, optimizer)


def make_pp_train_step_1f1b(cfg, mesh: Mesh, n_micro: int, optimizer=None):
    """1F1B training step for the transformer over a mesh with pp>1 —
    the memory-bounded alternative to :func:`~horovod_tpu.parallel.
    pipeline.make_pp_train_step` (GPipe): per-stage residency is
    ``O(pp)`` microbatch activations instead of ``O(n_micro)``, so deep
    pipelines can raise ``n_micro`` to shrink the bubble without
    scaling activation memory.

    Same composition rules as the GPipe step: dp/fsdp/tp/sp/ep compose
    under GSPMD, with sp riding the combined {pp, sp} manual island
    (the MoE aux loss rides the per-stage scalar through the explicit
    backward; sp+MoE stays unsupported).

    Returns ``(init_state, jit_step, param_shardings)``.
    """
    import optax

    from horovod_tpu.models import transformer as tr
    from horovod_tpu.parallel.pipeline_1f1b import make_1f1b_loss

    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)
    S = mesh.shape["pp"]
    constrain = tr._constrainer(mesh)
    attend, sp_size, extra_axes, mb_spec = _pp_stage_attention(cfg, mesh)
    if sp_size > 1 and cfg.n_experts > 0:
        raise NotImplementedError(
            "pp + sp + MoE is not supported (the per-shard aux "
            "statistic needs its own cross-sp reduction)")

    def one_layer(x, lp):
        off = (lax.axis_index("sp") * x.shape[1] if sp_size > 1 else 0)
        return tr.decoder_layer(cfg, attend, lambda v, *s: v, x, lp,
                                pos_offset=off)

    layer = one_layer
    if cfg.remat:
        layer = jax.checkpoint(one_layer, policy=tr.remat_policy_fn(cfg),
                               prevent_cse=cfg.remat_prevent_cse)

    def stage_fn(stage_layers, x):
        y, auxes = lax.scan(layer, x, stage_layers)
        # Per-microbatch MoE aux is a mean over its microbatch; summed
        # across the schedule's microbatches it must be averaged back
        # (same normalization as the GPipe step's aux / n_micro).
        return y, auxes.sum() / n_micro

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        B, T = inp.shape
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
        x = tr.embed_lookup(params["embed"], inp, cfg.dtype, mesh)
        x = constrain(x, ("dp", "fsdp"), "sp" if sp_size > 1 else None,
                      None)
        mb = x.reshape(n_micro, B // n_micro, T, x.shape[-1])
        tgt_mb = tgt.reshape(n_micro, B // n_micro, T)

        def last_fn(lastp, y, m_idx):
            h = tr._rmsnorm(y, lastp["final_norm"], cfg.norm_eps)
            logits = (h @ lastp["lm_head"]).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            t_m = lax.dynamic_index_in_dim(tgt_mb, m_idx, 0,
                                           keepdims=False)
            if sp_size > 1:
                # tgt_mb is a closure capture — replicated into the
                # island — while y is this shard's sequence slice;
                # take the matching target slice.
                t_m = lax.dynamic_slice_in_dim(
                    t_m, lax.axis_index("sp") * y.shape[1], y.shape[1],
                    axis=1)
            nll = -jnp.take_along_axis(logp, t_m[..., None],
                                       axis=-1)[..., 0]
            # Per-microbatch mean / n_micro: the schedule SUMS the
            # microbatch losses, so the total is the full-batch mean.
            # Under sp the head sees only this shard's tokens and the
            # schedule psums over sp too, so the local mean divides by
            # the shard count to stay the GLOBAL token mean.
            return nll.mean() / (n_micro * sp_size)

        pl = make_1f1b_loss(stage_fn, last_fn, mesh,
                            extra_axes=extra_axes, mb_spec=mb_spec)
        lastp = {"final_norm": params["final_norm"],
                 "lm_head": params["lm_head"]}
        return pl(params["layers"], lastp, mb)

    return _wire_train_step(cfg, mesh, loss_fn, optimizer)
