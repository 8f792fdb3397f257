"""The plain reference of the Kimi-K2 (``kimi_k2``: DeepSeek-V3's layer)
decoder as one chip of 32 that share each layer serves it: what
``correct`` is decided against for a served model whose every layer is
latent attention (MLA) with a query rank, rotated by YaRN, over sigmoid
routing with a selection bias over a chip's share of the experts. One
copy lives beside the benchmark (``benchmark/reference_kimi_k2.py``)
and one beside the tier-1 tests (``tests/reference_kimi_k2.py``); a
test holds the two identical below this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, keys
and values expanded from the latent for every position and every head,
no cache, no absorbed form, routing by a mask an expert over the held
experts; no sort, no kernel, no batching, and no import from the
program. It takes the program's parameter tree and a plain dict of
sizes, and upcasts one layer's attention, one expert's or one column
block of the dense layer's matrices at a time, so that it fits on the
chip beside the engine.

The layer, as this repository reads ``config.json`` (every reading the
file does not settle is under ``assumed`` in
``benchmark/configs/kimi-k2.7-code-ep32-6l.json``). ``x`` [T, D], H
heads, pre-norm residual layers, RMSNorm with ``norm_eps``, no biases:

* **attention**: ``u = RMSNorm(x)``; ``c_q = RMSNorm(u W_dq)`` [Q];
  ``q = c_q W_uq`` as H heads of ``[q_n (Dh) | q_r (R)]``;
  ``[c | k_r] = u W_dkv`` as ``[C | R]``, ``c <- RMSNorm(c)``;
  ``[k_n | v] = c W_ukv`` as H heads of ``[Dh | Dh]``; ``rot`` on
  ``q_r`` and on ``k_r`` (one rotated key part for all heads); scores
  ``(q_n . k_n + q_r . k_r) (Dh + R)^-1/2 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1``; causal softmax; ``o = sum p v``;
  ``x + concat(o) W_o``. No gate on the heads.
* **rot**: pairs ``(2i, 2i+1)`` of the R values turned by ``pos f_i``;
  ``f_i`` YaRN's (Peng et al. 2023, as DeepSeek-V3's modelling code
  computes them): with ``plain_i = theta^(-2i/R)``, the pair that turns
  ``b`` times within ``original_max_seq`` positions is pair ``R ln(L /
  (2 pi b)) / (2 ln theta)``; ``low`` = that of ``beta_fast`` rounded
  down, ``high`` = that of ``beta_slow`` rounded up, ``ramp_i =
  clip((i - low) / (high - low), 0, 1)``; ``f_i = plain_i / factor *
  ramp_i + plain_i (1 - ramp_i)``. cos and sin times
  ``attention_factor`` (1 here: ``mscale = mscale_all_dim``).
* the leading ``n_dense_layers`` layers: a SwiGLU of width
  ``d_ff_dense``, ``W_down(silu(W_gate u) * W_up u)``;
* the others: ``s = sigmoid(u W_r)`` over ALL ``n_experts``; the
  ``top_k`` largest of ``s + bias`` are chosen (``n_group`` 1: no
  group limit); weights ``s[chosen] / sum(s[chosen]) * route_scale``;
  ``y = shared(u) + sum_i w_i expert_i(u)`` over the experts this chip
  **holds** (``experts_held`` from ``expert_offset``) and nothing for
  the others;
* final RMSNorm, untied head over this chip's slice of the vocabulary.

``store`` and ``wrong`` exist for ``benchmark/tools/kimi_tolerance.py``
and ``tests/test_kimi_k2.py``, which show what the check refuses: the
same reference with weights and the residual stream stored in a narrower
float, or with one mechanism miscomputed (a name of ``WRONG``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_Q_BLOCK = 1024
_HEAD_GROUP = 8
_FF_BLOCK = 2048

#: What ``wrong`` may name, each one mechanism miscomputed.
WRONG = (
    "no_mscale",            # the softmax scale without m^2
    "plain_rope",           # theta^(-2i/R) for every pair: no YaRN
    "rope_halves",          # pairs (i, i + R/2) and not (2i, 2i+1)
    "no_q_norm",            # c_q not normalised
    "no_bias",              # the 8 largest of s, not of s + bias
    "no_route_scale",       # weights not multiplied by route_scale
    "router_in_bf16",       # the router's scores from bf16 operands
)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    rotary = dict(dict(m["layer_rotary"])["mla"])
    return {"n_layers": m["n_layers"], "n_heads": m["n_heads"],
            "head_dim": m["d_head"], "d_model": m["d_model"],
            "norm_eps": m["norm_eps"],
            "n_dense_layers": m["n_dense_layers"],
            "mla_kv_rank": m["mla_kv_rank"],
            "mla_rope_dim": m["mla_rope_dim"],
            "theta": rotary["theta"], "factor": rotary["factor"],
            "original_max_seq": rotary["original_max_seq"],
            "beta_fast": rotary.get("beta_fast", 32.0),
            "beta_slow": rotary.get("beta_slow", 1.0),
            "attention_factor": rotary.get("attention_factor", 1.0),
            "mscale_all_dim": rotary.get("mscale_all_dim", 0.0),
            "n_experts": m["n_experts"], "top_k": m["moe_top_k"],
            "route_scale": m["moe_route_scale"],
            "experts_held": m["moe_experts_held"],
            "expert_offset": m["moe_expert_offset"]}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_frequencies(sizes, plain: bool = False) -> np.ndarray:
    """The R / 2 pairs' angles a position."""
    d, theta = sizes["mla_rope_dim"], sizes["theta"]
    f = theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)
    if plain or not sizes["factor"]:
        return f.astype(np.float32)

    def pair_turning(times):
        return d * math.log(sizes["original_max_seq"]
                            / (2 * math.pi * times)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(sizes["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(sizes["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (f / sizes["factor"] * ramp + f * (1 - ramp)).astype(np.float32)


def softmax_scale(sizes, wrong=None) -> float:
    scale = (sizes["head_dim"] + sizes["mla_rope_dim"]) ** -0.5
    if (wrong == "no_mscale" or not sizes["mscale_all_dim"]
            or not sizes["factor"] or sizes["factor"] <= 1):
        return scale
    m = 0.1 * sizes["mscale_all_dim"] * math.log(sizes["factor"]) + 1.0
    return scale * m * m


def _rope(x, freq, factor, halves=False):
    """x [T, H, R], positions 0..T-1; pairs (2i, 2i+1), or with
    ``halves`` (i, i + R/2)."""
    t, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    if halves:
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


# -- attention -------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "n_heads", "head_dim", "rank", "rope_dim", "norm_eps", "factor",
    "wrong"))
def _mla_inputs(x, lp, freq, *, n_heads, head_dim, rank, rope_dim, norm_eps,
                factor, wrong):
    """q_n, keys, values [T, H, Dh]; q_r [T, H, R]; the rotated key
    part every head shares [T, R]."""
    t = x.shape[0]
    halves = wrong == "rope_halves"
    u = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    cq = u @ lp["w_dq"].astype(F32)
    if wrong != "no_q_norm":
        cq = _rmsnorm(cq, lp["dq_norm"].astype(F32), norm_eps)
    q = (cq @ lp["w_uq"].astype(F32)).reshape(t, n_heads, head_dim + rope_dim)
    cr = u @ lp["w_dkv"].astype(F32)
    c = _rmsnorm(cr[:, :rank], lp["kv_norm"].astype(F32), norm_eps)
    r = _rope(cr[:, None, rank:], freq, factor, halves)[:, 0]
    kv = (c @ lp["w_ukv"].astype(F32)).reshape(t, n_heads, 2, head_dim)
    return (q[..., :head_dim], _rope(q[..., head_dim:], freq, factor, halves),
            kv[:, :, 0], kv[:, :, 1], r)


@jax.jit
def _attend(qn, qr, k, v, r, first, scale):
    """Some heads, one block of queries at positions ``first + 0..``:
    qn, k, v [., G, Dh], qr [Tq, G, R], r [T, R]."""
    s = (jnp.einsum("qgd,kgd->gqk", qn, k) + jnp.einsum("qgr,kr->gqk", qr, r)
         ) * scale
    i = first + jnp.arange(qn.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    return jnp.einsum("gqk,kgd->qgd", p, v)


@jax.jit
def _mla_out(x, o, wo):
    return x + o.reshape(x.shape[0], -1) @ wo.astype(F32)


def _mla(x, lp, sizes, wrong):
    qn, qr, k, v, r = _mla_inputs(
        x, lp, jnp.asarray(yarn_frequencies(sizes, wrong == "plain_rope")),
        n_heads=sizes["n_heads"], head_dim=sizes["head_dim"],
        rank=sizes["mla_kv_rank"], rope_dim=sizes["mla_rope_dim"],
        norm_eps=sizes["norm_eps"], factor=sizes["attention_factor"],
        wrong=wrong)
    scale = softmax_scale(sizes, wrong)
    # some heads and a block of queries at a time: the scores are then
    # [8, 1024, T] and not [H, T, T]
    o = jnp.concatenate([jnp.concatenate(
        [_attend(qn[t:t + _Q_BLOCK, h:h + _HEAD_GROUP],
                 qr[t:t + _Q_BLOCK, h:h + _HEAD_GROUP],
                 k[:, h:h + _HEAD_GROUP], v[:, h:h + _HEAD_GROUP], r, t,
                 scale)
         for t in range(0, x.shape[0], _Q_BLOCK)], axis=0)
        for h in range(0, sizes["n_heads"], _HEAD_GROUP)], axis=1)
    return _mla_out(x, o, lp["wo"])


# -- the feed-forward blocks ----------------------------------------

@jax.jit
def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
            ) @ w_down.astype(F32)


def _dense(u, lp):
    """The dense layer's SwiGLU a block of its columns at a time (the
    sum over blocks of the hidden width is the whole product)."""
    y = jnp.zeros_like(u)
    for f in range(0, lp["w_gate"].shape[1], _FF_BLOCK):
        y = y + _swiglu(u, lp["w_gate"][:, f:f + _FF_BLOCK],
                        lp["w_up"][:, f:f + _FF_BLOCK],
                        lp["w_down"][f:f + _FF_BLOCK])
        y.block_until_ready()
    return y


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale",
                                              "biased", "narrow"))
def _route(u, router, bias, *, top_k, route_scale, biased, narrow=False):
    """The chosen experts [T, K] and their weights [T, K]; ``narrow``:
    the scores' operands rounded to bfloat16 where the configuration
    says float32."""
    router = router.astype(F32)
    if narrow:
        u, router = (a.astype(jnp.bfloat16).astype(F32) for a in (u, router))
    s = jax.nn.sigmoid(u @ router)                          # [T, E]
    _, chosen = jax.lax.top_k(s + bias.astype(F32) if biased else s, top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / w.sum(-1, keepdims=True) * route_scale


@jax.jit
def _expert(u, w_gate, w_up, w_down, mine, weights):
    """One expert on every token, weighted by the weight of the tokens
    that chose it (``mine`` [T, K] masks their choices), by 0 for the
    others."""
    return (jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)[:, None]
            * _swiglu(u, w_gate, w_up, w_down))


def moe(u, mp, sizes, wrong=None):
    """The sparse FFN on ``u`` [T, D]: the shared expert plus the
    weighted sum of the chosen experts that this chip holds. Returns
    (y [T, D], chosen [T, K], weights [T, K])."""
    chosen, weights = _route(
        u, mp["router"], mp["router_bias"], top_k=sizes["top_k"],
        route_scale=1.0 if wrong == "no_route_scale" else sizes["route_scale"],
        biased=wrong != "no_bias", narrow=wrong == "router_in_bf16")
    y = _swiglu(u, mp["shared_gate"], mp["shared_up"], mp["shared_down"])
    for e in range(sizes["experts_held"]):
        y = y + _expert(u, mp["w_gate"][e], mp["w_up"][e], mp["w_down"][e],
                        chosen == sizes["expert_offset"] + e, weights)
        # one expert at a time in earnest: a loop that runs ahead of the
        # device holds every expert's result at once
        y.block_until_ready()
    return y, chosen, weights


def layer(x, lp, sizes, wrong=None):
    """One layer on ``x`` [T, D] in float32; ``lp`` its parameters."""
    x = _mla(x, lp, sizes, wrong)
    u = _rmsnorm(x, lp["mlp_norm"].astype(F32), sizes["norm_eps"])
    y = moe(u, lp["moe"], sizes, wrong)[0] if "moe" in lp else _dense(u, lp)
    return x + y


def layer_params(params, sizes, i):
    """Layer ``i``'s parameters out of the two lists of layers."""
    n_dense = sizes["n_dense_layers"]
    return (params["dense_layers"][i] if i < n_dense
            else params["layers"][i - n_dense])


def logits(params, tokens, sizes, last: int = 0, *, store=None, wrong=None):
    """Float32 logits of ONE sequence ``tokens`` [T], over this chip's
    slice of the vocabulary: every position [T, V], or only the last
    ``last`` positions. ``store``: a dtype the weights and the residual
    stream are rounded to on the way (None: as they are). ``wrong``: a
    name of ``WRONG``."""
    assert wrong is None or wrong in WRONG, wrong

    def stored(tree):
        if store is None:
            return tree
        return jax.tree.map(
            lambda a: a.astype(store).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    with jax.default_matmul_precision("highest"):
        x = stored(params["embed"][jnp.asarray(tokens)]).astype(F32)
        for i in range(sizes["n_layers"]):
            x = stored(layer(x, stored(layer_params(params, sizes, i)),
                             sizes, wrong))
        x = _rmsnorm(x[-last:], stored(params["final_norm"]).astype(F32),
                     sizes["norm_eps"])
        return x @ stored(params["lm_head"]).astype(F32)
