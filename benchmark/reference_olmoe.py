"""The plain reference of the OLMoE decoder: what ``correct`` is decided
against for a sparse decoder with q/k normalisation. One copy lives
beside the benchmark and one beside the tier-1 tests
(``tests/reference_olmoe.py``); a test holds the two identical below
this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers and
over the experts with a mask per expert; no sort, no kernel, no scan, no
cache, and no import from the program. It takes the program's parameter
tree (whatever its type) and a plain dict of sizes, and upcasts one
layer (and of its experts, one expert) at a time.

The layer, as published (OLMoE-1B-7B, arXiv 2409.02060, and the model's
``config.json``): a pre-norm decoder,

    x = x + attn(rmsnorm(x));   x = x + moe(rmsnorm(x))

* attention: q, k, v projections without bias; RMSNorm with a learned
  weight over the WHOLE projected q vector (``n_heads * head_dim`` wide)
  and the whole projected k vector, before the split into heads; rotary
  embedding (theta 10000); causal softmax attention, ``n_heads`` heads
  (``n_kv_heads == n_heads``: plain multi-head, though the grouping is
  written out); output projection without bias;
* MoE: router logits ``h @ W_r`` (no bias) in float32; softmax over all
  experts; the ``top_k`` largest probabilities used as they are
  (``norm_topk_prob`` false: no renormalisation); each chosen expert is
  ``w_down(silu(w_gate h) * w_up h)``; the output is the weighted sum of
  the chosen experts' outputs; no shared expert;
* final RMSNorm, untied head, no biases anywhere, ``clip_qkv`` null.

Training loss: mean next-token cross-entropy
+ ``aux_coef`` × the load-balancing loss, ``E × Σ_e f_e · p_e`` with
``f_e`` the share of all (token, choice) pairs that fell on expert e and
``p_e`` the mean router probability of e, summed over layers
+ ``z_coef`` × the router z-loss, the mean over tokens of
``logsumexp(router logits)²``, summed over layers.

Departures, forced by having to read the program's weights:

* rotary pairs are interleaved ``(x[2i], x[2i+1])`` as the program lays
  its q/k columns out, where the published code pairs ``(x[i],
  x[i+d/2])``; under seeded random weights that is a fixed permutation
  of each head's columns (``benchmark/reference.py`` notes the same);
* the experts are three stacked arrays ``[E, D, F]``, ``[E, D, F]``,
  ``[E, F, D]`` (the published checkpoint stores one small matrix per
  expert holding the same numbers).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    return {"n_layers": m["n_layers"], "n_heads": m["n_heads"],
            "n_kv_heads": m["n_kv_heads"],
            "head_dim": m["d_model"] // m["n_heads"],
            "rope_theta": m["rope_theta"], "norm_eps": m["norm_eps"],
            "n_experts": m["n_experts"], "top_k": m["moe_top_k"],
            "aux_coef": m["moe_aux_loss_coef"],
            "z_coef": m["moe_z_loss_coef"]}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, T, H, Dh], positions 0..T-1, interleaved pairs."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "rope_theta", "norm_eps"))
def _attention(x, lp, *, n_heads, n_kv_heads, head_dim, rope_theta,
               norm_eps):
    """``x + attn(rmsnorm(x))`` on ``x`` [B, T, D] in float32."""
    lp = {k: v.astype(F32) for k, v in lp.items() if k != "moe"}
    b, t, _ = x.shape
    h = _rmsnorm(x, lp["attn_norm"], norm_eps)
    q = _rmsnorm(h @ lp["wq"], lp["q_norm"], norm_eps)
    k = _rmsnorm(h @ lp["wk"], lp["k_norm"], norm_eps)
    q = _rope(q.reshape(b, t, n_heads, head_dim), rope_theta)
    k = _rope(k.reshape(b, t, n_kv_heads, head_dim), rope_theta)
    v = (h @ lp["wv"]).reshape(b, t, n_kv_heads, head_dim)
    q = q.reshape(b, t, n_kv_heads, n_heads // n_kv_heads, head_dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * head_dim ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(
        b, t, n_heads * head_dim)
    return x + o @ lp["wo"]


@functools.partial(jax.jit, static_argnames=("norm_eps", "top_k"))
def _router(x, mlp_norm, router, *, norm_eps, top_k):
    """The MoE's input ``h`` [N, D], the chosen experts and their gates
    [N, K], the mean probability of each expert [E] and the z-loss."""
    h = _rmsnorm(x, mlp_norm.astype(F32), norm_eps).reshape(-1, x.shape[-1])
    router_logits = h @ router.astype(F32)                  # [N, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, chosen = jax.lax.top_k(probs, top_k)             # [N, K]
    z = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    return h, chosen, gates, probs.mean(0), z


@jax.jit
def _expert(h, w_gate, w_up, w_down, mine, gates):
    """One expert on every token, weighted by the gate of the tokens
    that chose it (``mine`` [N, K] masks their choices) and by 0 for
    the others; and the share of the N*K choices that are its."""
    weight = jnp.sum(jnp.where(mine, gates, 0.0), axis=-1)
    out = (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))
           ) @ w_down.astype(F32)
    return weight[:, None] * out, jnp.mean(mine.astype(F32))


def _layer(x, lp, sizes):
    """One decoder block on ``x`` [B, T, D] in float32. Returns the new
    ``x``, the layer's load-balancing loss, its router z-loss, and the
    experts each token chose [B*T, K] (for counting near-ties)."""
    x = _attention(x, lp, **{k: sizes[k] for k in (
        "n_heads", "n_kv_heads", "head_dim", "rope_theta", "norm_eps")})
    moe = lp["moe"]
    h, chosen, gates, mean_prob, z = _router(
        x, lp["mlp_norm"], moe["router"], norm_eps=sizes["norm_eps"],
        top_k=sizes["top_k"])
    y = jnp.zeros_like(h)
    share = []
    for e in range(sizes["n_experts"]):
        out, share_e = _expert(h, moe["w_gate"][e], moe["w_up"][e],
                               moe["w_down"][e], chosen == e, gates)
        y = y + out
        share.append(share_e)
    balance = sizes["n_experts"] * jnp.sum(jnp.stack(share) * mean_prob)
    return x + y.reshape(x.shape), balance, z, chosen


@jax.jit
def _head(x, final_norm, lm_head, eps):
    return _rmsnorm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


def hidden(params, tokens, sizes):
    """``(x, balance, z)``: final hidden states [B, T, D] (before the
    last norm) of ``tokens`` [B, T], and the two router losses summed
    over layers."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        balance = z = jnp.zeros((), F32)
        for i in range(sizes["n_layers"]):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, b_i, z_i, _ = _layer(x, lp, sizes)
            balance, z = balance + b_i, z + z_i
        return x, balance, z


def logits(params, tokens, sizes, last: int = 0):
    """Float32 logits of ``tokens`` [B, T]: every position [B, T, V],
    or only the last ``last`` positions."""
    x = hidden(params, tokens, sizes)[0]
    with jax.default_matmul_precision("highest"):
        return _head(x[:, -last:], params["final_norm"], params["lm_head"],
                     sizes["norm_eps"])


def loss_terms(params, rows, sizes) -> Dict[str, Any]:
    """The three terms of the training loss on ``rows`` [B, T+1], before
    their coefficients, and ``loss``, their weighted sum."""
    rows = jnp.asarray(rows)
    x, balance, z = hidden(params, rows[:, :-1], sizes)
    with jax.default_matmul_precision("highest"):
        lg = _head(x, params["final_norm"], params["lm_head"],
                   sizes["norm_eps"])
    logp = jax.nn.log_softmax(lg, axis=-1)
    ce = -jnp.take_along_axis(logp, rows[:, 1:, None], axis=-1).mean()
    return {"cross_entropy": ce, "load_balance": balance, "router_z": z,
            "loss": ce + sizes["aux_coef"] * balance + sizes["z_coef"] * z,
            "logits": lg}


def loss(params, rows, sizes):
    """The training loss of ``rows`` [B, T+1]; ``jax.grad`` of this is
    the reference's gradient."""
    return loss_terms(params, rows, sizes)["loss"]
