"""The plain reference of the Ling-3.0-flash (``bailing_hybrid``) decoder
as one chip of four that share each layer serves it: what ``correct`` is
decided against for a served model of delta-rule linear-attention layers
(KDA) beside a latent-attention layer (MLA), with group-limited sigmoid
routing over a chip's share of the experts. One copy lives beside the
benchmark (``benchmark/reference_ling3.py``) and one beside the tier-1
tests (``tests/reference_ling3.py``); a test holds the two identical
below this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, KDA
as the recurrence a position at a time (``lax.scan`` over positions: no
blocks, no triangular system), MLA expanded to every head's keys and
values with no cache and no absorbed form, routing by a mask an expert
over the held experts; no sort, no kernel, no cache, no batching, and no
import from the program. It takes the program's parameter tree and a
plain dict of sizes, and upcasts one layer's (and of its experts, one
expert's) matrices at a time, so that it fits on the chip beside the
engine.

The layers, as this repository reads ``config.json`` (every reading
that the file does not settle is under ``assumed`` in
``benchmark/configs/ling-3.0-flash-ep4-7l.json``). ``x`` [T, D], H heads,
pre-norm residual layers, RMSNorm with ``norm_eps``, no biases:

* **kda** (``d_k = d_v = Dh`` a head): ``u = RMSNorm(x)``; rows ``u Wq,
  u Wk, u Wv``; a causal depthwise convolution of ``kda_conv`` taps over
  each (zeros before the sequence's start), then SiLU; a head's
  ``q = q' / |q'| Dh^-1/2``, ``k = k' / |k'|``; log-decay a channel
  ``g = floor sigmoid(exp(A_h) (u Wa + b))`` (floor -5), ``a = exp g``;
  ``beta = sigmoid(u Wbeta)`` a head; from ``S_0 = 0`` in float32

      S'_t = Diag(a_t) S_{t-1}
      S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
      o_t  = S_t^T q_t

  ``y = RMSNorm_head(o) sigmoid(u Wz)``; ``x + y Wo``.
* **mla**: ``q = u Wq`` as ``[H, Dh | R]``; ``[c | r] = u W_dkv`` as
  ``[C | R]``, ``c <- RMSNorm(c)``; rotary embedding (``rope_theta``,
  pairs ``(2i, 2i+1)``) on q's R and on r, one rotated key part for all
  heads; ``[k | v] = c W_ukv`` as ``[H, Dh | Dh]``; scores ``(q_nope .
  k + q_rope . r) / sqrt(Dh + R)``, causal, softmax; ``o = sum p v``
  times one ``sigmoid(u Wg)`` a head; ``x + o Wo``.
* the leading ``n_dense_layers`` layers: a SwiGLU of width ``d_ff_dense``;
* the others: ``s = sigmoid(u Wr)`` over ALL ``n_experts``; ``c = s +
  bias``; ``n_group`` equal groups, a group's score the sum of its two
  largest ``c``; the ``top_k`` largest ``c`` inside the ``topk_group``
  best groups are chosen; weights ``s[chosen] / sum(s[chosen]) *
  route_scale``; ``y = shared(u) + sum_i w_i expert_i(u)`` over the
  experts this chip **holds** (``experts_held`` from ``expert_offset``)
  and nothing for the others;
* final RMSNorm, untied head over this chip's slice of the vocabulary.

``store`` and ``wrong`` exist for ``benchmark/tools/ling3_tolerance.py``
and ``tests/test_ling3.py``, which show what the check refuses: the same
reference with weights and the residual stream stored in a narrower
float, or with one mechanism miscomputed (a name of ``WRONG``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
_Q_BLOCK = 1024
_HEAD_GROUP = 8

#: What ``wrong`` may name, each one mechanism miscomputed.
WRONG = (
    "state_in_bf16",        # the recurrent state rounded to bf16 a position
    "no_decay",             # a = 1
    "no_beta",              # beta = 1
    "no_conv",              # the convolution left out (SiLU kept)
    "no_l2norm",            # q and k not normalised (q keeps its scale)
    "no_floor",             # g = -exp(A) softplus(u Wa + b), unbounded
    "no_head_gate",         # the mla layer's gate left out
    "rope_halves",          # pairs (i, i + d/2) and not (2i, 2i+1)
    "no_group_limit",       # top_k over all experts
    "no_route_scale",       # weights not multiplied by route_scale
)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    return {"n_layers": m["n_layers"], "n_heads": m["n_heads"],
            "head_dim": m["d_head"], "d_model": m["d_model"],
            "rope_theta": m["rope_theta"], "norm_eps": m["norm_eps"],
            "n_dense_layers": m["n_dense_layers"],
            "layer_types": tuple(m["layer_types"]),
            "kda_conv": m["kda_conv"],
            "kda_decay_floor": m["kda_decay_floor"],
            "mla_kv_rank": m["mla_kv_rank"],
            "mla_rope_dim": m["mla_rope_dim"],
            "n_experts": m["n_experts"], "top_k": m["moe_top_k"],
            "route_scale": m["moe_route_scale"],
            "n_group": m["moe_n_group"], "topk_group": m["moe_topk_group"],
            "experts_held": m["moe_experts_held"],
            "expert_offset": m["moe_expert_offset"]}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta, halves=False):
    """x [T, H, R], positions 0..T-1; pairs (2i, 2i+1), or with
    ``halves`` (i, i + R/2)."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if halves:
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


# -- kda -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "n_heads", "head_dim", "norm_eps", "taps", "floor", "wrong"))
def _kda(x, lp, *, n_heads, head_dim, norm_eps, taps, floor, wrong):
    """The layer's branch on ``x`` [T, D] and the state after the last
    position [H, Dh, Dh]."""
    t = x.shape[0]
    u = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)

    def conv(w, c):
        rows = u @ lp[w].astype(F32)
        if wrong != "no_conv":
            padded = jnp.concatenate(
                [jnp.zeros((taps - 1, rows.shape[1]), F32), rows])
            rows = sum(padded[j:j + t] * lp[c][j].astype(F32)
                       for j in range(taps))
        return jax.nn.silu(rows).reshape(t, n_heads, head_dim)

    q, k, v = conv("wq", "conv_q"), conv("wk", "conv_k"), conv("wv", "conv_v")
    if wrong != "no_l2norm":
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * head_dim ** -0.5
    a = (u @ lp["wa"].astype(F32) + lp["a_bias"].astype(F32)
         ).reshape(t, n_heads, head_dim)
    rate = jnp.exp(lp["a_log"].astype(F32))[:, None]
    g = (-rate * jax.nn.softplus(a) if wrong == "no_floor"
         else floor * jax.nn.sigmoid(rate * a))
    if wrong == "no_decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(u @ lp["wbeta"].astype(F32))
    if wrong == "no_beta":
        beta = jnp.ones_like(beta)

    def position(S, row):
        q, k, v, g, beta = row
        S = jnp.exp(g)[..., None] * S
        err = v - jnp.einsum("hkv,hk->hv", S, k)
        S = S + beta[:, None, None] * k[..., None] * err[:, None, :]
        if wrong == "state_in_bf16":
            # (not a cast there and back, which a compiler that keeps
            # excess precision takes out)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hkv,hk->hv", S, q)

    S, o = jax.lax.scan(position,
                        jnp.zeros((n_heads, head_dim, head_dim), F32),
                        (q, k, v, g, beta))
    y = _rmsnorm(o, lp["o_norm"].astype(F32), norm_eps).reshape(t, -1)
    y = y * jax.nn.sigmoid(u @ lp["wz"].astype(F32))
    return x + y @ lp["wo"].astype(F32), S


# -- mla -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "n_heads", "head_dim", "rank", "rope_dim", "rope_theta", "norm_eps",
    "wrong"))
def _mla_inputs(x, lp, *, n_heads, head_dim, rank, rope_dim, rope_theta,
                norm_eps, wrong):
    """u [T, D]; q_nope, keys, values [T, H, Dh]; q_rope [T, H, R];
    the rotated key part every head shares [T, R]."""
    t = x.shape[0]
    halves = wrong == "rope_halves"
    u = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    q = (u @ lp["wq"].astype(F32)).reshape(t, n_heads, head_dim + rope_dim)
    cr = u @ lp["w_dkv"].astype(F32)
    c = _rmsnorm(cr[:, :rank], lp["kv_norm"].astype(F32), norm_eps)
    r = _rope(cr[:, None, rank:], rope_theta, halves)[:, 0]
    kv = (c @ lp["w_ukv"].astype(F32)).reshape(t, n_heads, 2, head_dim)
    return (u, q[..., :head_dim], _rope(q[..., head_dim:], rope_theta, halves),
            kv[:, :, 0], kv[:, :, 1], r)


@jax.jit
def _mla_attend(qn, qr, k, v, r, first):
    """Some heads, one block of queries at positions ``first + 0..``:
    qn, k, v [., G, Dh], qr [Tq, G, R], r [T, R]."""
    s = (jnp.einsum("qgd,kgd->gqk", qn, k) + jnp.einsum("qgr,kr->gqk", qr, r)
         ) * (qn.shape[-1] + qr.shape[-1]) ** -0.5
    i = first + jnp.arange(qn.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    return jnp.einsum("gqk,kgd->qgd", p, v)


@functools.partial(jax.jit, static_argnames=("wrong",))
def _mla_out(x, u, o, lp, *, wrong):
    if wrong != "no_head_gate":
        o = o * jax.nn.sigmoid(u @ lp["wg"].astype(F32))[..., None]
    return x + o.reshape(x.shape[0], -1) @ lp["wo"].astype(F32)


def _mla(x, lp, sizes, wrong):
    u, qn, qr, k, v, r = _mla_inputs(
        x, lp, n_heads=sizes["n_heads"], head_dim=sizes["head_dim"],
        rank=sizes["mla_kv_rank"], rope_dim=sizes["mla_rope_dim"],
        rope_theta=sizes["rope_theta"], norm_eps=sizes["norm_eps"],
        wrong=wrong)
    # some heads and a block of queries at a time: the scores are then
    # [8, 1024, T] and not [H, T, T]
    o = jnp.concatenate([jnp.concatenate(
        [_mla_attend(qn[t:t + _Q_BLOCK, h:h + _HEAD_GROUP],
                     qr[t:t + _Q_BLOCK, h:h + _HEAD_GROUP],
                     k[:, h:h + _HEAD_GROUP], v[:, h:h + _HEAD_GROUP], r, t)
         for t in range(0, x.shape[0], _Q_BLOCK)], axis=0)
        for h in range(0, sizes["n_heads"], _HEAD_GROUP)], axis=1)
    return _mla_out(x, u, o, lp, wrong=wrong)


# -- the feed-forward blocks ----------------------------------------

@jax.jit
def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
            ) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "route_scale", "n_group", "topk_group"))
def _route(u, router, bias, *, top_k, route_scale, n_group, topk_group):
    """The chosen experts [T, K] and their weights [T, K]."""
    s = jax.nn.sigmoid(u @ router.astype(F32))              # [T, E]
    c = s + bias.astype(F32)
    if n_group > 1:
        by_group = c.reshape(c.shape[0], n_group, -1)
        score = jnp.sort(by_group, -1)[..., -2:].sum(-1)     # [T, G]
        kept = jnp.argsort(-score, -1)[:, :topk_group]
        allowed = (kept[:, :, None] == jnp.arange(n_group)).any(1)
        c = jnp.where(allowed[:, :, None], by_group, -jnp.inf
                      ).reshape(c.shape)
    _, chosen = jax.lax.top_k(c, top_k)
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
    limited = wrong != "no_group_limit"
    chosen, weights = _route(
        u, mp["router"], mp["router_bias"], top_k=sizes["top_k"],
        route_scale=1.0 if wrong == "no_route_scale" else sizes["route_scale"],
        n_group=sizes["n_group"] if limited else 1,
        topk_group=sizes["topk_group"] if limited else 1)
    y = _swiglu(u, mp["shared_gate"], mp["shared_up"], mp["shared_down"])
    for e in range(sizes["experts_held"]):
        y = y + _expert(u, mp["w_gate"][e], mp["w_up"][e], mp["w_down"][e],
                        chosen == sizes["expert_offset"] + e, weights)
        # one expert at a time in earnest: a loop that runs ahead of the
        # device holds every expert's result at once
        y.block_until_ready()
    return y, chosen, weights


def layer(x, lp, sizes, i, wrong=None):
    """Layer ``i`` on ``x`` [T, D] in float32; ``lp`` its parameters.
    Returns the new ``x`` and, of a kda layer, the state after the last
    position (None of an mla layer)."""
    if sizes["layer_types"][i] == "kda":
        x, state = _kda(x, lp, n_heads=sizes["n_heads"],
                        head_dim=sizes["head_dim"],
                        norm_eps=sizes["norm_eps"], taps=sizes["kda_conv"],
                        floor=sizes["kda_decay_floor"], wrong=wrong)
    else:
        x, state = _mla(x, lp, sizes, wrong), None
    u = _rmsnorm(x, lp["mlp_norm"].astype(F32), sizes["norm_eps"])
    if "moe" not in lp:
        # rows a block at a time: [1024, d_ff_dense] and not [T, ...]
        y = jnp.concatenate(
            [_swiglu(u[t:t + _Q_BLOCK], lp["w_gate"], lp["w_up"],
                     lp["w_down"]) for t in range(0, x.shape[0], _Q_BLOCK)])
    else:
        y = moe(u, lp["moe"], sizes, wrong)[0]
    return x + y, state


def layer_params(params, sizes, i):
    """Layer ``i``'s parameters out of the two lists of layers."""
    n_dense = sizes["n_dense_layers"]
    return (params["dense_layers"][i] if i < n_dense
            else params["layers"][i - n_dense])


def logits(params, tokens, sizes, last: int = 0, *, store=None, wrong=None,
           states: bool = False):
    """Float32 logits of ONE sequence ``tokens`` [T], over this chip's
    slice of the vocabulary: every position [T, V], or only the last
    ``last`` positions. ``store``: a dtype the weights and the residual
    stream are rounded to on the way (None: as they are). ``wrong``: a
    name of ``WRONG``. ``states``: also the kda layers' states after the
    last position, [n_kda, H, Dh, Dh]."""
    assert wrong is None or wrong in WRONG, wrong

    def stored(tree):
        if store is None:
            return tree
        return jax.tree.map(
            lambda a: a.astype(store).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    kept = []
    with jax.default_matmul_precision("highest"):
        x = stored(params["embed"][jnp.asarray(tokens)]).astype(F32)
        for i in range(sizes["n_layers"]):
            x, state = layer(x, stored(layer_params(params, sizes, i)),
                             sizes, i, wrong)
            x = stored(x)
            if state is not None:
                kept.append(state)
        x = _rmsnorm(x[-last:], stored(params["final_norm"]).astype(F32),
                     sizes["norm_eps"])
        out = x @ stored(params["lm_head"]).astype(F32)
    return (out, jnp.stack(kept)) if states else out
