"""The plain reference of the Trinity (``afmoe``) decoder as one chip of
an expert-parallel group serves it: what ``correct`` is decided against
for a served model with a leading dense layer before sparse ones,
sigmoid routing with a shared expert, gated window and full attention.
One copy lives beside the benchmark and one beside the tier-1 tests
(``tests/reference_trinity.py``); a test holds the two identical below
this docstring.

What decides ``correct`` in the benchmark (``generators/
serve_backlog_sparse.py``): two check requests go through the engine,
chunked prefill, both kinds of cache and 24 decode steps; this file
runs ONCE over each request's prompt and outputs, with no cache and no
batching, and every served token's reference logit must lie within the
traffic file's tolerance of the reference's largest at that position
(as a share of the largest magnitude). The tolerance and its readings
are in the traffic file (``check_why``).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, over
groups of heads and over the held experts with a mask per expert; no
sort, no kernel, no scan, no cache, and no import from the program. It
takes the program's parameter tree and a plain dict of sizes, and
upcasts one layer's (and of its experts, one expert's) matrices at a
time, so that it fits on the chip beside the engine.

The layer, as this repository reads it (Trinity-Large-Preview's
``config.json``, ``model_type`` ``afmoe``, and the family's modelling
code as remembered: ``assumed`` in the configuration file):

    x0 = embed[tokens] * sqrt(D)
    h  = x + post_attn_norm(attn(input_norm(x)))
    x' = h + post_mlp_norm(ffn(pre_mlp_norm(h)))

* attention: q, k, v without bias; RMSNorm with a gain of size
  ``head_dim`` over each head of q and of k; a **sliding** layer rotates
  q and k (theta 10000) and sees the keys ``p - window < j <= p``; a
  **full** layer applies no rotary embedding and sees every ``j <= p``;
  softmax attention over ``n_kv_heads`` groups; the output times
  ``sigmoid(u Wg)``, u the layer's normed input; output projection;
* the leading ``n_dense_layers`` layers: a SwiGLU of width ``d_ff_dense``;
* the others: ``s = sigmoid(u Wr)`` over ALL ``n_experts``; the ``top_k``
  experts with the largest ``s + b`` (b the selection bias: it chooses,
  it never weighs); weights ``s[chosen] / sum(s[chosen]) * route_scale``;
  ``y = shared(u) + sum_i w_i expert_i(u)``, where this chip adds the
  experts it **holds** (``experts_held`` from ``expert_offset``) and
  nothing for the others: the chips that hold those add them in the
  deployment's combine. The shared expert is counted here once;
* final RMSNorm, untied head over this chip's slice of the vocabulary.

Departures, forced by having to read the program's weights: rotary
pairs are interleaved ``(x[2i], x[2i+1])`` as the program lays its q/k
columns out (a fixed permutation of each head's columns under seeded
random weights); the experts are three stacked arrays.

``store``, ``window`` and ``gate`` exist for
``benchmark/tools/trinity_tolerance.py``, which shows what the tolerance
refuses: the same reference with weights and the residual stream stored
in an 8-bit float, with the window mask left out, with the gate left
out.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
_Q_BLOCK = 1024


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    return {"n_layers": m["n_layers"], "n_heads": m["n_heads"],
            "n_kv_heads": m["n_kv_heads"], "head_dim": m["d_head"],
            "d_model": m["d_model"],
            "rope_theta": m["rope_theta"], "norm_eps": m["norm_eps"],
            "n_dense_layers": m["n_dense_layers"],
            "layer_types": tuple(m["layer_types"]),
            "window": m["attn_window"],
            "n_experts": m["n_experts"], "top_k": m["moe_top_k"],
            "route_scale": m["moe_route_scale"],
            "experts_held": m["moe_experts_held"],
            "expert_offset": m["moe_expert_offset"]}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, Dh], positions 0..T-1, interleaved pairs."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "rope_theta", "norm_eps",
    "sliding"))
def _qkv(x, lp, *, n_heads, n_kv_heads, head_dim, rope_theta, norm_eps,
         sliding):
    """The normed input u [T, D], q [T, H, Dh], k and v [T, Hkv, Dh]."""
    t = x.shape[0]
    u = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    q = (u @ lp["wq"].astype(F32)).reshape(t, n_heads, head_dim)
    k = (u @ lp["wk"].astype(F32)).reshape(t, n_kv_heads, head_dim)
    v = (u @ lp["wv"].astype(F32)).reshape(t, n_kv_heads, head_dim)
    q = _rmsnorm(q, lp["q_norm"].astype(F32), norm_eps)
    k = _rmsnorm(k, lp["k_norm"].astype(F32), norm_eps)
    if sliding:
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    return u, q, k, v


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_group(q, k, v, first, *, window):
    """One KV head's group, one block of queries: q [Tq, G, Dh], the
    queries at positions ``first + 0..Tq-1``, over all of k, v [T, Dh];
    ``window`` None for a full layer."""
    s = jnp.einsum("qgd,kd->gqk", q, k) * q.shape[-1] ** -0.5
    i = first + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("gqk,kd->qgd", p, v)


@functools.partial(jax.jit, static_argnames=("norm_eps", "gate"))
def _attention_out(x, u, a, lp, *, norm_eps, gate):
    """``x + post_attn_norm((a * sigmoid(u Wg)) Wo)``, a [T, H * Dh]."""
    if gate:
        a = a * jax.nn.sigmoid(u @ lp["wg"].astype(F32))
    return x + _rmsnorm(a @ lp["wo"].astype(F32),
                        lp["post_attn_norm"].astype(F32), norm_eps)


@jax.jit
def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
            ) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale"))
def _route(u, router, bias, *, top_k, route_scale):
    """The chosen experts [T, K] and their weights [T, K]."""
    s = jax.nn.sigmoid(u @ router.astype(F32))              # [T, E]
    _, chosen = jax.lax.top_k(s + bias.astype(F32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / w.sum(-1, keepdims=True) * route_scale


@jax.jit
def _expert(u, w_gate, w_up, w_down, mine, weights):
    """One expert on every token, weighted by the weight of the tokens
    that chose it (``mine`` [T, K] masks their choices), by 0 for the
    others."""
    return (jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)[:, None]
            * _swiglu(u, w_gate, w_up, w_down))


def moe(u, mp, sizes):
    """The sparse FFN on ``u`` [T, D]: the shared expert plus the
    weighted sum of the chosen experts that this chip holds. Returns
    (y [T, D], chosen [T, K])."""
    chosen, weights = _route(u, mp["router"], mp["router_bias"],
                             top_k=sizes["top_k"],
                             route_scale=sizes["route_scale"])
    y = _swiglu(u, mp["shared_gate"], mp["shared_up"], mp["shared_down"])
    for e in range(sizes["experts_held"]):
        y = y + _expert(u, mp["w_gate"][e], mp["w_up"][e], mp["w_down"][e],
                        chosen == sizes["expert_offset"] + e, weights)
        # one expert at a time in earnest: a loop that runs ahead of the
        # device holds every expert's result at once
        y.block_until_ready()
    return y, chosen


def layer(x, lp, sizes, i, *, window=True, gate=True):
    """Layer ``i`` on ``x`` [T, D] in float32; ``lp`` its parameters.
    Returns the new ``x`` and the experts each token chose ([T, K];
    None for a dense layer)."""
    sliding = sizes["layer_types"][i] == "sliding"
    u, q, k, v = _qkv(x, lp, sliding=sliding, **{k_: sizes[k_] for k_ in (
        "n_heads", "n_kv_heads", "head_dim", "rope_theta", "norm_eps")})
    g = sizes["n_heads"] // sizes["n_kv_heads"]
    w = sizes["window"] if sliding and window else None
    # a group of heads and a block of queries at a time: the scores
    # are then [G, 1024, T] and not [H, T, T]
    a = jnp.concatenate([jnp.concatenate(
        [_attend_group(q[t:t + _Q_BLOCK, h * g:(h + 1) * g], k[:, h],
                       v[:, h], t, window=w)
         for t in range(0, x.shape[0], _Q_BLOCK)], axis=0)
        for h in range(sizes["n_kv_heads"])], axis=1)
    x = _attention_out(x, u, a.reshape(x.shape[0], -1), lp,
                       norm_eps=sizes["norm_eps"], gate=gate)
    u = _rmsnorm(x, lp["mlp_norm"].astype(F32), sizes["norm_eps"])
    chosen = None
    if "moe" not in lp:
        # rows a block at a time: [1024, d_ff_dense] and not [T, ...]
        y = jnp.concatenate(
            [_swiglu(u[t:t + _Q_BLOCK], lp["w_gate"], lp["w_up"],
                     lp["w_down"]) for t in range(0, x.shape[0], _Q_BLOCK)])
    else:
        y, chosen = moe(u, lp["moe"], sizes)
    return x + _rmsnorm(y, lp["post_mlp_norm"].astype(F32),
                        sizes["norm_eps"]), chosen


def layer_params(params, sizes, i):
    """Layer ``i``'s parameters out of the two lists of layers."""
    n_dense = sizes["n_dense_layers"]
    return (params["dense_layers"][i] if i < n_dense
            else params["layers"][i - n_dense])


def logits(params, tokens, sizes, last: int = 0, *, store=None,
           window=True, gate=True):
    """Float32 logits of ONE sequence ``tokens`` [T], over this chip's
    slice of the vocabulary: every position [T, V], or only the last
    ``last`` positions. ``store``: a dtype the weights and the residual
    stream are rounded to on the way (None: as they are)."""
    def stored(tree):
        if store is None:
            return tree
        return jax.tree.map(
            lambda a: a.astype(store).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    with jax.default_matmul_precision("highest"):
        x = stored(params["embed"][jnp.asarray(tokens)]).astype(F32)
        x = stored(x * sizes["d_model"] ** 0.5)
        for i in range(sizes["n_layers"]):
            x, _ = layer(x, stored(layer_params(params, sizes, i)), sizes, i,
                         window=window, gate=gate)
            x = stored(x)
        x = _rmsnorm(x[-last:], stored(params["final_norm"]).astype(F32),
                     sizes["norm_eps"])
        return x @ stored(params["lm_head"]).astype(F32)
