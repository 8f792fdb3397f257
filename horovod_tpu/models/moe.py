"""Mixture-of-Experts FFN with expert parallelism over the ``ep`` mesh
axis.

The reference has no MoE (its only relevant primitive is alltoall,
``horovod/common/operations.cc:1131`` — SURVEY.md §2.6 explicitly maps
MoE expert dispatch onto it). The TPU-native design is the GShard
dense-dispatch formulation: routing builds one-hot dispatch/combine
tensors and the expert dimension is *sharded over* ``ep``, so GSPMD
lowers the two dispatch einsums to ICI all-to-alls — no hand-written
collectives, fully fused by XLA, and differentiable end to end.

Shapes (per layer): tokens ``[B, T, D]``, experts ``E``, per-group
capacity ``C = ceil(k · T · capacity_factor / E)`` with groups = batch
rows. Top-k (default 2) gating with the standard load-balancing
auxiliary loss (Switch/GShard form).

A configuration without a capacity (``capacity_factor=None``: OLMoE and
the other fine-grained sparse decoders) takes :func:`moe_ffn_dropless`
instead: the ``N·K`` (token, choice) pairs are sorted by expert, the
experts run as one grouped matmul over the sorted rows
(:func:`_grouped_product`, the whole mixture's and a chip's share's
alike: ``ops/grouped_matmul.py``'s kernel where the matrices' bytes
bound the product, ``lax.ragged_dot``, which the TPU compiler lowers to
a Mosaic grouped matmul, where the matrix unit does), and nothing is
dropped. Its largest value is ``[N·K, D]``;
the one-hot tensors above, whose size grows with ``E · C``, do not
exist there. :func:`make_moe_ffn` picks between the two from the
configuration alone.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import grouped_matmul as grouped_matmul_lib

#: Dispatch-plane values for the HOROVOD_MOE_DISPATCH knob /
#: ``TransformerConfig.moe_dispatch`` (docs/perf_tuning.md).
MOE_DISPATCH_MODES = ("gspmd", "island")


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    #: ``None``: no capacity, no dropped token (the sorted dispatch).
    capacity_factor: Optional[float] = 1.25
    aux_loss_coef: float = 0.01
    #: Coefficient of the router z-loss, mean ``logsumexp(logits)²``.
    z_loss_coef: float = 0.0
    #: Renormalise the chosen gates to sum to 1 (GShard); ``False``
    #: uses the softmax's own values (OLMoE's ``norm_topk_prob``).
    norm_topk_prob: bool = True
    #: ``"softmax"`` over all experts, or ``"sigmoid"`` of each logit:
    #: the K experts with the largest score plus the per-expert
    #: selection bias ``lp["router_bias"]`` are chosen, and weighed by
    #: their scores alone (dropless dispatch only).
    scoring: str = "softmax"
    #: The chosen gates, after ``norm_topk_prob``, times this.
    route_scale: float = 1.0
    #: One SwiGLU of the experts' width that every token takes, beside
    #: the routed sum (dropless dispatch only).
    shared_expert: bool = False
    #: One chip's share: the router scores all ``n_experts``; the
    #: layer holds experts ``[expert_offset, expert_offset +
    #: experts_held)``, computes the part of the result they give and
    #: adds nothing for the rest (``None``: it holds them all).
    experts_held: Optional[int] = None
    expert_offset: int = 0
    #: Group-limited selection (DeepSeek-V3's ``noaux_tc``): the
    #: experts lie in ``n_group`` equal groups, a group's score is the
    #: sum of its two largest selection scores, and the K experts are
    #: chosen inside the ``topk_group`` best groups (1, 1: no limit).
    n_group: int = 1
    topk_group: int = 1
    #: An expert's form: ``"swiglu"`` (``silu(x Wg) * (x Wu)`` then
    #: ``Wd``: three matrices) or ``"relu2"`` (``relu(x Wu)^2`` then
    #: ``Wd``: UNGATED, two matrices and no ``w_gate``), or
    #: ``"polynorm"`` (:func:`polynorm` of ``x Wg`` where SwiGLU has
    #: SiLU, with three weights and a bias of its own an expert,
    #: ``poly_w`` [Eh, 3] and ``poly_b`` [Eh], float32), the shared
    #: expert's too.
    activation: str = "swiglu"
    #: PolyNorm's output scale, the clamp on its bias and the eps of
    #: its norms (the model's ``norm_eps``).
    polynorm_scale: float = 1.0
    polynorm_bias_clamp: float = 0.5
    norm_eps: float = 1e-5
    #: The routed experts read and write a LATENT of this many values
    #: (0: the model's width): ``latent_down`` [D, latent] before the
    #: dispatch (a dispatched row is ``latent`` wide), ``latent_up``
    #: [latent, D] after the routed sum (linear, so once for the sum and
    #: not once an expert). The router and the shared expert read the
    #: full-width input.
    latent: int = 0
    #: The shared expert's width (``None``: the routed experts').
    shared_d_ff: Optional[int] = None

    def __post_init__(self):
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown MoE scoring {self.scoring!r}")
        if self.activation not in ("swiglu", "relu2", "polynorm"):
            raise ValueError(f"unknown MoE activation {self.activation!r}")
        if self.experts_held is None and (
                self.activation != "swiglu" or self.latent
                or self.shared_d_ff is not None):
            raise ValueError(
                "an ungated or PolyNorm activation, a latent around the "
                "experts and a "
                "shared expert of its own width are the held dispatch's "
                "(experts_held, a chip's share, which may be all of them; "
                "its products take ops/grouped_matmul.py's kernel by the "
                "same rule, taken): the whole mixture's dispatch "
                "(moe_ffn_dropless) runs a SwiGLU at the model's width")
        if not (1 <= self.topk_group <= self.n_group
                and self.n_experts % self.n_group == 0
                and (self.n_group == 1 or self.top_k
                     <= self.topk_group * self.n_experts // self.n_group)):
            raise ValueError(
                f"{self.topk_group} of {self.n_group} groups over "
                f"{self.n_experts} experts do not hold top_k={self.top_k}")
        if self.capacity_factor is not None and (
                self.scoring != "softmax" or self.shared_expert
                or self.experts_held is not None or self.route_scale != 1.0):
            raise ValueError(
                "sigmoid scoring, route_scale, a shared expert and a "
                "chip's share of the experts are the dropless dispatch's "
                "(capacity_factor=None); the one-hot dispatch has none")
        if self.experts_held is not None and not (
                0 < self.experts_held
                and 0 <= self.expert_offset
                and self.expert_offset + self.experts_held
                <= self.n_experts):
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.experts_held}) are not among {self.n_experts}")

    @property
    def n_held(self) -> int:
        """Experts whose matrices the layer holds."""
        return (self.n_experts if self.experts_held is None
                else self.experts_held)


def capacity(cfg: MoEConfig, seq_len: int) -> int:
    return max(1, math.ceil(cfg.top_k * seq_len * cfg.capacity_factor
                            / cfg.n_experts))


def moe_param_specs(n_layers_leading: bool = True,
                    cfg: Optional[MoEConfig] = None) -> Dict[str, Any]:
    """PartitionSpecs for one MoE FFN block (leading ``L`` dim when
    stacked for the layer scan): experts over ``ep``, matrix dims over
    ``fsdp``/``tp`` like the dense FFN. ``cfg`` adds the leaves its
    scoring and its shared expert bring."""
    lead = (None,) if n_layers_leading else ()
    specs = {
        "router": P(*lead, None, None),           # [L?, D, E] replicated
        "w_gate": P(*lead, "ep", "fsdp", "tp"),   # [L?, E, D, F]
        "w_up": P(*lead, "ep", "fsdp", "tp"),
        "w_down": P(*lead, "ep", "tp", "fsdp"),   # [L?, E, F, D]
    }
    if cfg is not None and cfg.scoring == "sigmoid":
        specs["router_bias"] = P(*lead, None)     # [L?, E]
    if cfg is not None and cfg.shared_expert:
        specs.update(shared_gate=P(*lead, "fsdp", "tp"),    # [L?, D, F]
                     shared_up=P(*lead, "fsdp", "tp"),
                     shared_down=P(*lead, "tp", "fsdp"))    # [L?, F, D]
    if cfg is not None and cfg.latent:
        specs.update(latent_down=P(*lead, "fsdp", None),    # [L?, D, latent]
                     latent_up=P(*lead, None, "fsdp"))      # [L?, latent, D]
    if cfg is not None and cfg.activation == "relu2":
        for gate in {"w_gate", "shared_gate"} & set(specs):
            del specs[gate]
    if cfg is not None and cfg.activation == "polynorm":
        specs.update(poly_w=P(*lead, "ep", None), poly_b=P(*lead, "ep"))
        if cfg.shared_expert:
            specs.update(shared_poly_w=P(*lead, None), shared_poly_b=P(*lead))
    return specs


def polynorm(z, w, b, scale: float, clamp: float, eps: float):
    """PolyNorm of ``z`` [.., F] in float32: ``scale * (w1 N(z^3) + w2
    N(z^2) + w3 N(z) + clip(b, +-clamp))``, ``N(t) = t / sqrt(mean_F(t^2)
    + eps)``. ``w`` [.., 3] and ``b`` [..] are one set for every row (a
    feed-forward's own) or a set a row (a sorted row's expert's)."""
    z = z.astype(jnp.float32)

    def normed(t):
        return t * lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + eps)

    with jax.named_scope("polynorm"):
        w = w.astype(jnp.float32)[..., None, :]
        b = jnp.clip(b.astype(jnp.float32), -clamp, clamp)[..., None]
        return scale * (w[..., 0] * normed(z * z * z) + w[..., 1]
                        * normed(z * z) + w[..., 2] * normed(z) + b)


def init_polynorm(key, lead) -> Dict[str, Any]:
    """``poly_w`` [*lead, 3] and ``poly_b`` [*lead], float32: a third
    each and zero, as published, plus noise so that a seeded model's
    sets differ and a term left out shows."""
    kw, kb = jax.random.split(key)
    return {"poly_w": 1 / 3 + 0.1 * jax.random.normal(kw, (*lead, 3),
                                                      jnp.float32),
            "poly_b": 0.2 * jax.random.normal(kb, tuple(lead), jnp.float32)}


def init_moe_params(key, n_layers: int, d_model: int, d_ff: int,
                    cfg: MoEConfig, dtype) -> Dict[str, Any]:
    kr, kg, ku, kd = jax.random.split(key, 4)
    L, D, F, E, Eh = n_layers, d_model, d_ff, cfg.n_experts, cfg.n_held
    Dx = cfg.latent or D          # what the routed experts read and write

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    gated = cfg.activation != "relu2"      # relu2: no gate matrix
    params = {
        # Router in f32: small, and routing decisions are precision-
        # sensitive (standard practice).
        "router": (jax.random.normal(kr, (L, D, E), jnp.float32) * D ** -0.5),
        **({"w_gate": dense(kg, (L, Eh, Dx, F), Dx)} if gated else {}),
        "w_up": dense(ku, (L, Eh, Dx, F), Dx),
        "w_down": dense(kd, (L, Eh, F, Dx), F),
    }
    if cfg.scoring == "sigmoid":
        # The selection bias: what load balancing moves in training,
        # zeros at a seeded initialisation.
        params["router_bias"] = jnp.zeros((L, E), jnp.float32)
    if cfg.shared_expert:
        ks = jax.random.split(jax.random.fold_in(key, 1), 3)
        Fs = cfg.shared_d_ff or F
        if gated:
            params["shared_gate"] = dense(ks[0], (L, D, Fs), D)
        params.update(shared_up=dense(ks[1], (L, D, Fs), D),
                      shared_down=dense(ks[2], (L, Fs, D), Fs))
    if cfg.latent:
        kl = jax.random.split(jax.random.fold_in(key, 2), 2)
        params.update(latent_down=dense(kl[0], (L, D, Dx), D),
                      latent_up=dense(kl[1], (L, Dx, D), Dx))
    if cfg.activation == "polynorm":
        kp = jax.random.split(jax.random.fold_in(key, 3), 2)
        params.update(init_polynorm(kp[0], (L, Eh)))
        if cfg.shared_expert:
            params.update({"shared_" + name: a for name, a in
                           init_polynorm(kp[1], (L,)).items()})
    return params


def _top_k_gates(logits, cfg: MoEConfig, bias=None):
    """``(probs [.., E], gates [.., K], experts [.., K])`` of router
    logits: softmax over all experts, the K largest, renormalised to
    sum to 1 (GShard) unless the configuration says not to. With
    sigmoid scoring ``probs`` is each logit's sigmoid, the K experts
    are those with the largest ``probs + bias`` ([E]: it chooses and
    never weighs), and the gates are scaled by ``route_scale``. With
    ``n_group`` > 1 the choice is made inside the ``topk_group`` groups
    whose two largest selection scores sum highest."""
    if cfg.scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        select = probs + bias
    else:
        probs = select = jax.nn.softmax(logits, axis=-1)
    if cfg.n_group > 1:
        grouped = select.reshape(*select.shape[:-1], cfg.n_group, -1)
        best = jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0].sum(-1)
        _, groups = jax.lax.top_k(best, cfg.topk_group)
        kept = (groups[..., None] == jnp.arange(cfg.n_group)).any(-2)
        select = jnp.where(kept[..., None], grouped, -jnp.inf
                           ).reshape(select.shape)
    if select is probs:
        gates, experts = jax.lax.top_k(probs, cfg.top_k)
    else:
        _, experts = jax.lax.top_k(select, cfg.top_k)
        gates = jnp.take_along_axis(probs, experts, axis=-1)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if cfg.route_scale != 1.0:
        gates = gates * cfg.route_scale
    return probs, gates, experts


def _route(x, router, cfg: MoEConfig, C: int):
    """GShard routing on ``x`` [B, T, D] (any batch slice): top-k
    gating, (t, k)-ordered capacity assignment, one-hot dispatch /
    combine tensors. Per-token math only — no cross-batch-row coupling
    (the capacity cumsum runs within each row), so routing a batch
    SHARD equals the global routing restricted to those rows. The
    island leans on exactly this property.

    Returns ``(dispatch [B,T,E,C], combine [B,T,E,C], probs [B,T,E],
    top1 [B,T,E], sel [B,T,K,E], within [B,T,K,E], logits [B,T,E])``.
    """
    B, T, _D = x.shape
    E, K = cfg.n_experts, cfg.top_k

    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), router)
    # Top-k expert choice per token: probs [B, T, E], the rest [B, T, K].
    probs, gate_vals, gate_idx = _top_k_gates(logits, cfg)

    # Capacity positions: for the k-th choice, a token's slot in expert
    # e is the number of earlier (token-major, choice-major) claims on
    # e. Flatten choices so priorities are (t, k) ordered.
    sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)   # [B, T, K, E]
    # (t, k) priority: token t's k-th choice claims a slot before any
    # claim of token t+1.
    sel_flat = sel.reshape(B, T * K, E)
    pos = jnp.cumsum(sel_flat, axis=1) - sel_flat      # claims before mine
    pos = pos.reshape(B, T, K, E)
    within = (pos < C) * sel                           # keep under-capacity
    slot = pos.astype(jnp.int32)

    # dispatch [B, T, E, C]: 1 where token (b,t) occupies slot c of e.
    slot_oh = jax.nn.one_hot(slot, C, dtype=jnp.float32)   # [B, T, K, E, C]
    dispatch = jnp.einsum("btke,btkec->btec", within, slot_oh)
    combine = jnp.einsum("btk,btke,btkec->btec",
                         gate_vals, within, slot_oh)

    top1 = jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32)
    return dispatch, combine, probs, top1, sel, within, logits


def _z_loss(logits):
    """Router z-loss: mean over tokens of ``logsumexp(logits)²``."""
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)


def _expert_ffn(xin, lp, dtype):
    """SwiGLU over per-expert token slabs ``xin`` [E', b, C, D] with
    expert weights ``lp`` [E', D, F] — shared verbatim by the GSPMD
    path (E' = E, b = B) and the island (E' = E/ep, b = ep·B/ep), so
    the per-element contraction math is identical in both."""
    g = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", xin,
                               lp["w_gate"]).astype(jnp.float32))
    u = jnp.einsum("ebcd,edf->ebcf", xin, lp["w_up"]).astype(jnp.float32)
    h = (g * u).astype(dtype)
    return jnp.einsum("ebcf,efd->ebcd", h, lp["w_down"])


def moe_ffn(x, lp, cfg: MoEConfig):
    """One MoE FFN block. ``x``: [B, T, D] (cfg.dtype); ``lp``: this
    layer's param dict (no leading L). Returns (y [B, T, D], aux_loss
    scalar f32).

    Dispatch math follows GShard: one-hot ``dispatch [B,T,E,C]``
    scatters tokens into per-expert capacity slots, the ``ebcd``
    einsums move tokens to the ``ep``-sharded expert dim (GSPMD →
    all-to-all over ICI), experts run SwiGLU batched over their local
    shard, and ``combine`` (dispatch × gate prob) returns weighted
    outputs. Tokens over capacity are dropped (their residual path
    passes through unchanged — standard Switch behavior).
    """
    E = cfg.n_experts
    C = capacity(cfg, x.shape[1])
    dispatch, combine, probs, top1, _sel, _within, logits = _route(
        x, lp["router"], cfg, C)

    # Load-balancing aux loss (Switch eq. 4): E * sum_e f_e * p_e with
    # f = fraction of tokens whose TOP-1 lands on e, p = mean prob.
    aux = cfg.aux_loss_coef * E * jnp.sum(
        top1.mean((0, 1)) * probs.mean((0, 1)))
    if cfg.z_loss_coef:
        aux = aux + cfg.z_loss_coef * _z_loss(logits)

    # To experts (ep all-to-all by GSPMD), run SwiGLU, and back.
    xin = jnp.einsum("btec,btd->ebcd", dispatch.astype(x.dtype), x)
    xout = _expert_ffn(xin, lp, x.dtype)
    y = jnp.einsum("btec,ebcd->btd", combine.astype(x.dtype), xout)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# The sorted, dropless dispatch (ISSUE 26): no capacity, no one-hot
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_sorted(x, order, inverse, K: int, held=None):
    """Rows of ``x`` [N, D] in sorted (token, choice) order, [N·K, D].
    ``order`` sorts the ``N·K`` pairs by expert and ``inverse`` undoes
    it, so the cotangent is a gather too, ``g[inverse]`` summed over a
    token's K choices: autodiff alone would scatter-add over tokens.
    ``held`` [N, K] (a chip's share of the experts) says which pairs'
    rows are used at all: the cotangent of a row behind the last group
    is no result either, and is left out of the sum."""
    return x[order // K]


def _take_sorted_fwd(x, order, inverse, K, held=None):
    return x[order // K], (inverse, held)


def _take_sorted_bwd(K, res, g):
    inverse, held = res
    pairs = g[inverse].reshape(-1, K, g.shape[-1])
    if held is not None:
        pairs = jnp.where(held[..., None], pairs, 0)
    return pairs.sum(1).astype(g.dtype), None, None, None


_take_sorted.defvjp(_take_sorted_fwd, _take_sorted_bwd)


@jax.custom_vjp
def _take_unsorted(y, order, inverse):
    """Sorted rows ``y`` [N·K, D] back in (token, choice) order; the
    cotangent is the gather ``g[order]``."""
    return y[inverse]


_take_unsorted.defvjp(lambda y, order, inverse: (y[inverse], order),
                      lambda order, g: (g[order], None, None))


def _router_logits(xf, router):
    """Router logits [N, E] of tokens ``xf`` [N, D], float32 in
    earnest: on the TPU a default-precision f32 matmul multiplies in
    bf16, and near-ties of the top-k follow it."""
    return jnp.dot(xf.astype(jnp.float32), router,
                   precision=lax.Precision.HIGHEST)


def _sorted_by_expert(experts, n_experts: int):
    """``(order, group_sizes [E])``: the stable sort of the flat
    ``[N·K]`` expert ids, and how many pairs fell on each expert (read
    off the sorted ids, so no ``[N·K, E]`` one-hot exists)."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    ends = jnp.searchsorted(flat[order], jnp.arange(1, n_experts + 1,
                                                    dtype=flat.dtype))
    sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    return order, sizes


#: Grouped products traced in this process (the whole mixture's and a
#: held share's alike), and of them those that took the kernel
#: (``moe_grouped_kernel_products_share``).
_grouped_traced = [0, 0]


def _grouped_product(rows, w, sizes, sharded: bool = False):
    """One grouped product of a mixture, the whole one's
    (:func:`moe_ffn_dropless`) or a held share's (:func:`_held_rows`):
    ``rows`` [M, ·] sorted by expert times ``w`` [G, ·, ·], ``sizes``
    [G] with ``sizes.sum() <= M`` (a held share's other pairs lie
    behind the last group; what the product leaves there is no result).
    Through ``ops/grouped_matmul.py``'s kernel (``hvd_grouped_matmul``:
    each expert's matrix read once, whole) where ``grouped_matmul.taken``
    says the matrices' bytes bound it, from the shapes alone: the mean
    rows an expert under the chip's operations a byte (the LFM2 cell's
    16 in a decode step and 32 to 128 in a chunk; 22 and up to 176 over
    the Nemotron cell's 128 held experts); ``lax.ragged_dot`` otherwise
    (OLMoE's trainer's 1024, mellum's 1536 and more, Kimi's two
    matrices of 29 MB over the buffer), and for a shard of a mesh's
    tokens (``sharded``: a Pallas result says nothing of the axes it
    varies over, which :func:`_dropless_over_mesh`'s ``shard_map``
    checks). The choice is made as the program is traced and counted
    there."""
    kernel = not sharded and grouped_matmul_lib.taken(rows, w)
    with _moe_metrics_lock:
        _grouped_traced[0] += 1
        _grouped_traced[1] += kernel
        share = _grouped_traced[1] / _grouped_traced[0]
    record_moe_stats({"moe_grouped_kernel_products_share": share})
    if kernel:
        return grouped_matmul_lib.grouped_matmul(rows, w, sizes)
    return lax.ragged_dot(rows, w, sizes)


def moe_ffn_dropless(x, lp, cfg: MoEConfig, token_axes=()):
    """One MoE FFN block with no capacity and no dropped token. Same
    signature and return as :func:`moe_ffn`.

    Flatten to ``N = B·T`` tokens; router logits, softmax and top-k in
    float32; stable-sort the ``N·K`` (token, choice) pairs by expert;
    gather their rows into ``[N·K, D]``; the three SwiGLU matrices as
    grouped matmuls over the E groups; un-sort, weight by the gates and
    sum over K. ``aux`` is ``aux_loss_coef · E · Σ_e f_e·p_e`` with
    ``f_e`` the share of the ``N·K`` choices that fell on expert e and
    ``p_e`` its mean router probability, plus ``z_loss_coef`` times the
    router z-loss.

    ``token_axes`` names the mesh axes this call's tokens are one shard
    of (inside :func:`make_moe_ffn`'s ``shard_map``): routing is per
    token, so each shard sorts its own rows, and only the two means of
    the auxiliary losses are taken over every shard.
    """
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    xf = x.reshape(B * T, D)

    def everywhere(v):                     # mean over all shards' tokens
        return lax.pmean(v, token_axes) if token_axes else v

    with jax.named_scope("moe_router"):
        logits = _router_logits(xf, lp["router"])
        probs, gates, experts = _top_k_gates(logits, cfg,
                                             lp.get("router_bias"))

    def router_losses(claims):             # claims [E] of the N·K choices
        with jax.named_scope("moe_router"):
            f = everywhere(claims.astype(jnp.float32) / (B * T * K))
            aux = cfg.aux_loss_coef * E * jnp.sum(
                f * everywhere(probs.mean(0)))
            if cfg.z_loss_coef:
                aux = aux + cfg.z_loss_coef * everywhere(_z_loss(logits))
            return aux

    if cfg.experts_held is not None:
        # A chip sorts by the experts it holds; the load-balancing term
        # is over all n_experts router outputs of this chip's tokens,
        # so the choices are counted without a sort.
        claims = (experts[..., None] == jnp.arange(E, dtype=experts.dtype)
                  ).sum((0, 1))
        return (_held_experts(x, lp, cfg, gates, experts, token_axes),
                router_losses(claims))
    with jax.named_scope("moe_dispatch"):
        order, sizes = _sorted_by_expert(experts, E)
        inverse = jnp.argsort(order)
        rows = _take_sorted(xf, order, inverse, K)            # [N·K, D]
    aux = router_losses(sizes)
    with jax.named_scope("moe_experts"):
        sharded = bool(token_axes)
        g = jax.nn.silu(_grouped_product(rows, lp["w_gate"], sizes, sharded)
                        .astype(jnp.float32))
        u = _grouped_product(rows, lp["w_up"], sizes, sharded
                             ).astype(jnp.float32)
        out = _grouped_product((g * u).astype(x.dtype), lp["w_down"], sizes,
                               sharded)
    with jax.named_scope("moe_combine"):
        y = jnp.einsum(
            "nkd,nk->nd",
            _take_unsorted(out, order, inverse).reshape(B * T, K, D),
            gates.astype(x.dtype))
    if cfg.shared_expert:
        y = y + _shared_expert(xf, lp)
    return y.reshape(B, T, D).astype(x.dtype), aux


def _shared_expert(xf, lp, cfg: Optional[MoEConfig] = None):
    """The expert every token takes, on ``xf`` [N, D]: a SwiGLU (with
    PolyNorm for its SiLU where the parameters hold
    ``shared_poly_w``), or where they hold no gate matrix ``relu(x
    Wu)^2 Wd``."""
    with jax.named_scope("moe_shared"):
        if "shared_poly_w" in lp:
            g = polynorm(xf @ lp["shared_gate"], lp["shared_poly_w"],
                         lp["shared_poly_b"], cfg.polynorm_scale,
                         cfg.polynorm_bias_clamp, cfg.norm_eps)
            h = g * (xf @ lp["shared_up"]).astype(jnp.float32)
        elif "shared_gate" in lp:
            g = jax.nn.silu((xf @ lp["shared_gate"]).astype(jnp.float32))
            h = g * (xf @ lp["shared_up"]).astype(jnp.float32)
        else:
            h = jnp.square(jax.nn.relu(
                (xf @ lp["shared_up"]).astype(jnp.float32)))
        return (h.astype(xf.dtype) @ lp["shared_down"]).astype(xf.dtype)


def held_pairs(experts, cfg: MoEConfig):
    """``(local [N, K], held [N, K])``: each chosen expert's index
    among the held ones, and whether it is held at all. A pair that is
    not takes the index ``n_held``, one past the last group, so the
    sort puts it behind every pair that counts."""
    local = experts - cfg.expert_offset
    held = (local >= 0) & (local < cfg.n_held)
    return jnp.where(held, local, cfg.n_held), held


#: The compacted dispatch engages where it leaves out at least this many
#: of a call's ``N·K`` rows (a static decision from shapes): below it,
#: a served chunk or a decode step, a second compiled branch costs more
#: than the rows it saves.
_COMPACT_MIN_ROWS_SAVED = 16384
#: Rows of the grouped matmul's row block (the v5e's MXU), in whole
#: multiples of which the bound is taken.
_ROW_TILE = 128


def held_row_bound(pairs: int, cfg: MoEConfig) -> Optional[int]:
    """The rows ``C`` that :func:`_held_experts` compacts a call of
    ``pairs`` (token, choice) pairs to: one and a half times the pairs
    an even router puts on the held experts (what the load-balancing
    term drives towards), in whole row tiles. ``None`` where the rows
    it would leave out are fewer than ``_COMPACT_MIN_ROWS_SAVED``: the
    call then runs all ``pairs`` rows, as it does when its held pairs
    exceed ``C``. The dispatch and the routing report both ask here."""
    tiles = -(-3 * pairs * cfg.n_held // (2 * cfg.n_experts * _ROW_TILE))
    bound = _ROW_TILE * tiles
    return bound if pairs - bound >= _COMPACT_MIN_ROWS_SAVED else None


def _held_rows(xf, w, gates, held, order, inverse, sizes, rows=None,
               sharded: bool = False, poly=None):
    """The routed sum ``y`` [N, D] of the held experts over the first
    ``rows`` sorted places (``None``: all ``N·K`` of them). ``order``
    puts the held pairs first, by expert, so with ``sizes.sum() <=
    rows`` the places left out hold no pair that counts: no row is
    gathered for them, no matmul, no SwiGLU, and the combine's ``N·K``
    slots read a source of ``rows`` rows. The two (ungated) or three
    products are :func:`_grouped_product`'s, the whole mixture's rule:
    the kernel where the matrices' bytes bound them, unless the tokens
    are one shard of a mesh's (``sharded``). ``poly``: ``(poly_w [Eh,
    3], poly_b [Eh], cfg)``, PolyNorm for the gate's SiLU, each sorted
    row under its own expert's set."""
    K = gates.shape[1]
    w_gate, w_up, w_down = w
    with jax.named_scope("moe_dispatch"):
        if rows is not None:
            order = order[:rows]
            inverse = jnp.minimum(inverse, rows - 1)
        taken = _take_sorted(xf, order, inverse, K, held)      # [rows, D]
    with jax.named_scope("moe_experts"):
        if w_gate is None:
            # ungated: relu(x Wu)^2, two products and not three
            h = jnp.square(jax.nn.relu(_grouped_product(
                taken, w_up, sizes, sharded).astype(jnp.float32)))
        elif poly is not None:
            poly_w, poly_b, cfg = poly
            # the expert of each sorted place: the groups lie in order
            # (a place behind the last group reads the last expert's set
            # and is masked below)
            of = jnp.minimum(jnp.searchsorted(
                jnp.cumsum(sizes), jnp.arange(taken.shape[0]), side="right"),
                sizes.shape[0] - 1)
            g = polynorm(_grouped_product(taken, w_gate, sizes, sharded),
                         poly_w[of], poly_b[of], cfg.polynorm_scale,
                         cfg.polynorm_bias_clamp, cfg.norm_eps)
            h = g * _grouped_product(taken, w_up, sizes, sharded
                                     ).astype(jnp.float32)
        else:
            g = jax.nn.silu(_grouped_product(taken, w_gate, sizes, sharded)
                            .astype(jnp.float32))
            u = _grouped_product(taken, w_up, sizes, sharded
                                 ).astype(jnp.float32)
            h = g * u
        out = _grouped_product(h.astype(xf.dtype), w_down, sizes, sharded)
    with jax.named_scope("moe_combine"):
        # what lies behind the last group is not a result (the kernel
        # leaves those rows unwritten): masked, not multiplied by a zero
        # gate
        out = jnp.where(held.reshape(-1, 1),
                        _take_unsorted(out, order, inverse), 0)
        return jnp.einsum("nkd,nk->nd", out.reshape(-1, K, xf.shape[-1]),
                          gates.astype(xf.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_rows_bounded(bound: int, sharded: bool, xf, w, gates, held, order,
                       inverse, sizes):
    """:func:`_held_rows` over ``bound`` rows where the held pairs fit
    them and over all ``N·K`` where they do not: the same sum either
    way, every held pair run. The branch is taken outside
    differentiation, once forward and once backward, and the residuals
    are the block's own inputs: ``jax.grad`` through a ``lax.cond``
    would make both branches' residuals results of the forward and
    fill the untaken one's ``[N·K, ·]`` with zeros every layer."""
    return lax.cond(
        sizes.sum() <= bound,
        functools.partial(_held_rows, rows=bound, sharded=sharded),
        functools.partial(_held_rows, sharded=sharded),
        xf, w, gates, held, order, inverse, sizes)


def _held_rows_bounded_fwd(bound, sharded, *args):
    return _held_rows_bounded(bound, sharded, *args), args


def _held_rows_bounded_bwd(bound, sharded, res, g):
    xf, w, gates, *how = res

    def pull(rows):
        def branch(xf, w, gates, g):
            return jax.vjp(
                lambda *a: _held_rows(*a, *how, rows=rows, sharded=sharded),
                xf, w, gates)[1](g)
        return branch

    return (*lax.cond(how[-1].sum() <= bound, pull(bound), pull(None),
                      xf, w, gates, g), None, None, None, None)


_held_rows_bounded.defvjp(_held_rows_bounded_fwd, _held_rows_bounded_bwd)


def _held_experts(x, lp, cfg: MoEConfig, gates, experts, token_axes=()):
    """One chip's part of a MoE block whose experts are spread over
    chips (``experts_held``): of the ``N·K`` (token, choice) pairs the
    router made over ALL experts, those on a held expert are sorted to
    the front and run as grouped matmuls, every one of them (no
    capacity); the others fall behind the last group, their rows are
    never read and they add nothing, as the chip that holds their
    expert would add it in the deployment's combine. Where
    :func:`held_row_bound` gives a bound, only that many sorted places
    are gathered, multiplied and combined, unless a call's held pairs
    exceed it, which then runs all ``N·K``. The shared expert
    is added here once: summed over chips, the deployment adds it on
    one of them. ``token_axes`` is :func:`moe_ffn_dropless`'s: a shard
    of a mesh's tokens keeps ``lax.ragged_dot`` for its products.

    Differentiable: gradients reach the held experts' matrices, the
    router through the gates of held pairs, and ``x``; both row
    movements are gathers backward too."""
    B, T, D = x.shape
    K = cfg.top_k
    xf = x.reshape(B * T, D)
    bound = held_row_bound(B * T * K, cfg)
    with jax.named_scope("moe_dispatch"):
        local, held = held_pairs(experts, cfg)
        order, sizes = _sorted_by_expert(local, cfg.n_held + 1)
        sizes = sizes[:cfg.n_held]
        inverse = jnp.argsort(order)
    # (an ungated expert has no gate matrix: None, a pytree of nothing)
    w = (lp.get("w_gate"), lp["w_up"], lp["w_down"])
    rows = xf
    if cfg.latent:
        # what is dispatched is the latent row: a quarter of the bytes
        # of a row at Nemotron's 1024 of 4096
        with jax.named_scope("moe_latent_down"):
            rows = xf @ lp["latent_down"]
    sharded = bool(token_axes)
    # PolyNorm's sets, an expert's own: every row of such a mixture is
    # run (the bounded form's custom_vjp carries the matrices alone, and
    # a served chunk or step is under its threshold anyway)
    poly = ((lp["poly_w"], lp["poly_b"], cfg)
            if cfg.activation == "polynorm" else None)
    if bound is None or poly is not None:
        y = _held_rows(rows, w, gates, held, order, inverse, sizes,
                       sharded=sharded, poly=poly)
    else:
        y = _held_rows_bounded(bound, sharded, rows, w, gates, held, order,
                               inverse, sizes)
    if cfg.latent:
        # after the sum over a token's experts: linear, so once
        with jax.named_scope("moe_latent_up"):
            y = (y @ lp["latent_up"]).astype(x.dtype)
    if cfg.shared_expert:
        y = y + _shared_expert(xf, lp, cfg)
    return y.reshape(B, T, D).astype(x.dtype)


def _dropless_over_mesh(cfg: MoEConfig, mesh):
    """:func:`moe_ffn_dropless` for ``x`` laid out over ``mesh``: each
    shard of the token axes (``dp``, ``fsdp``, ``sp``) routes its own
    rows against the whole (gathered) expert matrices."""
    shape = mesh.shape if mesh is not None else {}
    if shape.get("ep", 1) > 1:
        raise NotImplementedError(
            "a MoE configuration without a capacity "
            "(moe_capacity_factor=None) runs every expert on every chip: "
            f"the sorted dispatch is not spread over ep={shape['ep']} yet "
            "(ROADMAP B7). Use a mesh with ep=1, or set a capacity.")
    axes = tuple(a for a in ("dp", "fsdp", "sp") if shape.get(a, 1) > 1)
    if not axes:
        return lambda x, lp: moe_ffn_dropless(x, lp, cfg)
    batch = tuple(a for a in axes if a != "sp") or None
    seq = "sp" if "sp" in axes else None

    def shard(x, lp):
        return moe_ffn_dropless(x, lp, cfg, token_axes=axes)

    return jax.shard_map(shard, mesh=mesh,
                         in_specs=(P(batch, seq, None), P()),
                         out_specs=(P(batch, seq, None), P()),
                         axis_names=set(axes))


# ---------------------------------------------------------------------------
# The quantized-dispatch island (ISSUE 18)
# ---------------------------------------------------------------------------

def moe_ffn_island(x, lp, cfg: MoEConfig, mesh, *, codec: str = "int8"):
    """:func:`moe_ffn` with the dispatch/combine hops as an explicit
    ``shard_map`` island over ``ep``, both riding
    :func:`~horovod_tpu.ops.quantized.quantized_alltoall` — the EQuARX
    treatment applied to the one collective that dominates sparse-model
    step time (the reference's alltoall, ``operations.cc:1131``).

    Token rows are batch-sharded over ``ep`` inside the island; each
    shard routes its rows locally (identical to the global routing —
    the capacity cumsum is per batch row, see :func:`_route`), packs
    per-expert token slabs, and exchanges them with the expert owners
    over the quantized alltoall: blockwise int8 (+f32 scales) at
    ~1/3.94 of the f32 wire bytes, bf16 at 1/2, ``"none"`` the plain
    f32 hop (same island math, lossless wire — the A/B control the
    int8 error-bound tests compare against). The expert SwiGLU and the
    combine weighting are byte-for-byte the GSPMD path's math.

    Requires ``B % ep == 0`` and ``E % ep == 0``.

    Capacity overflow is handled exactly like the GSPMD path (dropped
    tokens ride the residual stream); :func:`moe_routing_stats` is the
    telemetry face of the same routing math.
    """
    ep = mesh.shape.get("ep", 1) if mesh is not None else 1
    if ep <= 1:
        return moe_ffn(x, lp, cfg)       # no exchange to quantize
    E = cfg.n_experts
    B = x.shape[0]
    if E % ep:
        raise ValueError(
            f"moe_ffn_island: n_experts={E} must divide by the ep axis "
            f"size {ep} (each shard owns E/ep experts)")
    if B % ep:
        raise ValueError(
            f"moe_ffn_island: batch {B} must divide by the ep axis "
            f"size {ep} (token rows are batch-sharded over ep)")
    return _jitted_island(cfg, mesh, codec)(
        x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"])


@functools.lru_cache(maxsize=None)
def _jitted_island(cfg: MoEConfig, mesh, codec: str):
    """The island of :func:`moe_ffn_island`, built once per
    ``(cfg, mesh, codec)`` so eager callers hit jit's cache instead of
    compiling a fresh closure every call."""
    from horovod_tpu.ops.quantized import quantized_alltoall

    E = cfg.n_experts
    ep = mesh.shape["ep"]
    e_loc = E // ep

    def island(xl, router, wg, wu, wd):
        b_loc, T, D = xl.shape
        C = capacity(cfg, T)
        dispatch, combine, probs, top1, _sel, _within, logits = _route(
            xl, router, cfg, C)

        # Aux loss from the GLOBAL f/p vectors (pmean of equal-sized
        # shard means == the global mean), so the island's aux equals
        # the GSPMD path's — NOT a pmean of per-shard aux values,
        # which would average the nonlinear f·p product instead.
        f = lax.pmean(top1.mean((0, 1)), "ep")
        pbar = lax.pmean(probs.mean((0, 1)), "ep")
        aux = cfg.aux_loss_coef * E * jnp.sum(f * pbar)
        if cfg.z_loss_coef:
            aux = aux + cfg.z_loss_coef * lax.pmean(_z_loss(logits), "ep")

        # Pack per-expert slabs for ALL E experts from local rows,
        # grouped by owner shard, and trade them: after the alltoall,
        # axis 0 indexes the SOURCE shard and the local expert slabs
        # cover this shard's E/ep experts for every token row.
        xin = jnp.einsum("btec,btd->ebcd", dispatch.astype(xl.dtype), xl)
        xin = xin.reshape(ep, e_loc, b_loc, C, D)
        r = quantized_alltoall(xin, "ep", codec=codec)
        r = jnp.moveaxis(r, 0, 1).reshape(e_loc, ep * b_loc, C, D)

        xout = _expert_ffn(r, {"w_gate": wg, "w_up": wu, "w_down": wd},
                           xl.dtype)

        # Quantized combine hop back to the token owners (axis 0 now
        # indexes the expert-OWNER shard), then the weighted combine.
        back = jnp.moveaxis(xout.reshape(e_loc, ep, b_loc, C, D), 0, 1)
        back = quantized_alltoall(back, "ep", codec=codec)
        xfull = back.reshape(E, b_loc, C, D)
        y = jnp.einsum("btec,ebcd->btd", combine.astype(xl.dtype), xfull)
        return y.astype(xl.dtype), aux

    # Partial-manual over ep only (dp/fsdp/tp ride auto/GSPMD). Jitted
    # so eager callers run the same compiled island as jitted ones:
    # jax's op-by-op eager shard_map rounds the pmean differently.
    return jax.jit(jax.shard_map(
        island, mesh=mesh,
        in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep")),
        out_specs=(P("ep"), P()), axis_names={"ep"}))


def resolve_moe_knobs(dispatch: Optional[str] = None,
                      codec: Optional[str] = None):
    """Resolve the MoE dispatch-plane knobs: explicit config values win,
    ``None`` falls back to the env knobs (docs/perf_tuning.md) —
    ``HOROVOD_MOE_DISPATCH`` (default ``gspmd``) and
    ``HOROVOD_MOE_COMPRESSION`` (default ``int8``, the codec the
    island exists for). Returns ``(dispatch, codec)`` validated."""
    from horovod_tpu.ops.quantized import CODECS

    d = dispatch or os.environ.get("HOROVOD_MOE_DISPATCH", "gspmd")
    c = codec or os.environ.get("HOROVOD_MOE_COMPRESSION", "int8")
    if d not in MOE_DISPATCH_MODES:
        raise ValueError(
            f"unknown MoE dispatch mode {d!r}; one of {MOE_DISPATCH_MODES}")
    if c not in CODECS:
        raise ValueError(f"unknown MoE codec {c!r}; one of {CODECS}")
    return d, c


def make_moe_ffn(cfg: MoEConfig, mesh, *, dispatch: Optional[str] = None,
                 codec: Optional[str] = None):
    """Single construction point for the transformer's MoE FFN call:
    returns ``fn(x, lp) -> (y, aux)``.

    Routing discipline (the PR 9 ``compression=none`` contract):
    ``dispatch="gspmd"``, ``codec="none"``, a meshless build, or
    ``ep == 1`` all take the EXACT pre-existing GSPMD einsum path —
    so "island at compression=none is bitwise-identical to GSPMD"
    holds by construction, and only a genuinely narrow wire pays the
    island's restructuring. ``dispatch="island"`` with a lossy codec
    builds :func:`moe_ffn_island`; build-time failures (E not
    divisible by ep) raise HERE with the mesh in hand, not mid-trace.

    A configuration without a capacity takes the sorted, dropless
    :func:`moe_ffn_dropless` whatever the two knobs say: they choose
    the wire of the one-hot dispatch over ``ep``, which it does not have.
    """
    d, c = resolve_moe_knobs(dispatch, codec)
    if cfg.capacity_factor is None:
        return _dropless_over_mesh(cfg, mesh)
    ep = mesh.shape.get("ep", 1) if mesh is not None else 1
    if d == "gspmd" or c == "none" or ep <= 1:
        return lambda x, lp: moe_ffn(x, lp, cfg)
    if cfg.n_experts % ep:
        raise ValueError(
            f"moe_dispatch='island': n_experts={cfg.n_experts} must "
            f"divide by ep={ep}")
    return lambda x, lp: moe_ffn_island(x, lp, cfg, mesh, codec=c)


# ---------------------------------------------------------------------------
# Routing telemetry (overflow counter / dropped-token fraction)
# ---------------------------------------------------------------------------

#: Python-plane MoE metric keys, locked to docs/observability.md by the
#: tools/lint metric-sync rule (same lockstep discipline as the native
#: registry's name tables).
MOE_METRIC_KEYS = (
    "moe_dispatch_overflow_tokens_total",
    "moe_dispatch_dropped_token_frac",
    "moe_dispatch_bytes_saved_pct",
    "moe_expert_load_max_over_mean",
    "moe_compact_calls_share",
    "moe_held_pairs_over_bound_max",
    "moe_grouped_kernel_products_share",
    "moe_held_pairs_run",
    "moe_held_pairs_not_run",
    "moe_held_experts_touched_mean",
)

_moe_metrics: Dict[str, float] = {}
_moe_metrics_lock = threading.Lock()


def routing_counts(x, router, cfg: MoEConfig, bias=None):
    """``(claims per expert [E], claims past capacity)`` of one batch
    ``x`` [B, T, D], by the routing math of the dispatch the
    configuration takes: :func:`_route`'s for the one-hot dispatch,
    the top-k alone for the dropless one, which turns none away.
    With a chip's share of the experts: the claims on the held ones
    [held], counted from the router's choices, and how many of them
    :func:`_held_experts`' sort and group sizes do not run
    (:func:`held_pairs_not_run`). ``bias`` is the selection bias of
    sigmoid scoring. Jittable."""
    if cfg.capacity_factor is None:
        _, _, experts = _top_k_gates(
            _router_logits(x.reshape(-1, x.shape[-1]), router), cfg, bias)
        if cfg.experts_held is not None:
            local = held_pairs(experts, cfg)[0].reshape(-1)
            claims = jnp.zeros(cfg.n_held + 1, jnp.float32).at[local].add(1.0)
            return claims[:cfg.n_held], held_pairs_not_run(experts, cfg)
        _, sizes = _sorted_by_expert(experts, cfg.n_experts)
        return sizes.astype(jnp.float32), jnp.zeros((), jnp.float32)
    C = capacity(cfg, x.shape[1])
    _d, _c, _p, _t1, sel, within, _l = _route(x, router, cfg, C)
    return sel.sum((0, 1, 2)), sel.sum() - within.sum()


def held_pairs_not_run(experts, cfg: MoEConfig):
    """Of the pairs the router put on a held expert, how many
    :func:`_held_experts` would not run through that expert: its
    grouped matmuls run sorted place i with the matrices of the group
    that the running sum of ``sizes`` puts i in, so a pair is run iff
    its place lies in a group, the expert sorted there is that
    group's, and the place is among the rows the call runs (all of
    them, or :func:`held_row_bound`'s where the held pairs fit it).
    Read off the dispatch's own ``order``, ``sizes`` and bound; the
    held pairs are counted from the router's choices, without the
    sort. 0 unless the sort, the sizes, the bound or a capacity lose a
    pair."""
    local, held = held_pairs(experts, cfg)
    order, sizes = _sorted_by_expert(local, cfg.n_held + 1)
    sizes = sizes[:cfg.n_held]
    ends = jnp.cumsum(sizes)
    place = jnp.arange(order.size, dtype=ends.dtype)
    group = jnp.searchsorted(ends, place, side="right")
    run = (group < cfg.n_held) & (local.reshape(-1)[order] == group)
    bound = held_row_bound(order.size, cfg)
    if bound is not None:
        run &= (place < bound) | (sizes.sum() > bound)
    return (held.sum() - run.sum()).astype(jnp.float32)


def compaction_summary(counts, pairs: int, cfg: MoEConfig
                       ) -> Dict[str, float]:
    """How often :func:`_held_experts` compacts, from the held experts'
    claims ``counts`` [layers, held] of calls of ``pairs`` (token,
    choice) pairs each: ``moe_compact_calls_share``, the layers whose
    held pairs fit :func:`held_row_bound` (the others run every row),
    and ``moe_held_pairs_over_bound_max``, the largest layer's held
    pairs over that bound. Empty where a call of that size has no
    bound: nothing there could compact."""
    bound = held_row_bound(pairs, cfg)
    if bound is None:
        return {}
    held = counts.sum(-1)
    return {"moe_compact_calls_share": float((held <= bound).mean()),
            "moe_held_pairs_over_bound_max": float(held.max() / bound)}


def routing_summary(counts, overflow) -> Dict[str, float]:
    """The exported series from :func:`routing_counts`' two values;
    ``counts`` may carry leading (layer) dimensions, and the load ratio
    reported is then the largest of them."""
    claims = float(counts.sum())
    overflow = float(overflow.sum())
    return {
        "moe_dispatch_overflow_tokens_total": overflow,
        "moe_dispatch_dropped_token_frac": (
            overflow / claims if claims else 0.0),
        "moe_expert_load_max_over_mean": float(
            (counts.max(-1) / jnp.maximum(counts.mean(-1), 1e-9)).max()),
    }


def moe_routing_stats(x, router, cfg: MoEConfig) -> Dict[str, float]:
    """Routing telemetry for one batch of one layer: runs the exact
    routing math of the dispatch (so the numbers describe what it
    actually dropped, not an estimate) and returns

    * ``moe_dispatch_overflow_tokens_total`` — (token, choice) claims
      that landed past an expert's capacity this batch (0 without a
      capacity);
    * ``moe_dispatch_dropped_token_frac`` — that count over the
      ``B·T·k`` total claims;
    * ``moe_expert_load_max_over_mean`` — claims on the fullest expert
      over the mean claims per expert (1.0 is an even load).

    Host-callable (no mesh needed — routing is per batch row); feed
    the result to :func:`record_moe_stats` to accumulate into the
    exported series. ``transformer.moe_routing_report`` gives the same
    keys for every layer of a model on a batch of tokens.
    """
    return routing_summary(*routing_counts(x, router, cfg))


def _render_moe_metrics() -> str:
    from horovod_tpu.metrics import NAMESPACE, render_gauges
    with _moe_metrics_lock:
        vals = dict(_moe_metrics)
    return render_gauges(NAMESPACE, vals)


def record_moe_stats(stats: Dict[str, float]) -> None:
    """Fold one batch's telemetry into the exported MoE series:
    ``*_total`` keys accumulate (counters), everything else is a
    last-value gauge. First call registers the exporter, so the rows
    ride :func:`horovod_tpu.metrics.metrics_prometheus` alongside the
    native registry (docs/observability.md)."""
    from horovod_tpu.metrics import register_exporter
    with _moe_metrics_lock:
        register = not _moe_metrics
        for k, v in stats.items():
            if k.endswith("_total"):
                _moe_metrics[k] = _moe_metrics.get(k, 0.0) + float(v)
            else:
                _moe_metrics[k] = float(v)
    if register:
        register_exporter("moe", _render_moe_metrics)


def moe_metrics() -> Dict[str, float]:
    """Current values of the recorded MoE series (empty before the
    first :func:`record_moe_stats`)."""
    with _moe_metrics_lock:
        return dict(_moe_metrics)
