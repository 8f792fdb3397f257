"""The plain reference of the dense GQA decoder: what ``correct`` is
decided against.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no scan, and no import from the program. It takes the
program's parameter tree (the weights as served or trained, whatever
their type) and a plain dict of sizes, and upcasts one layer at a time,
so the float32 copy of a 7 B model never has to exist at once.

The equations are the published ones of InternLM2 and Mistral-7B
(pre-norm decoder, RMSNorm, rotary embedding, grouped-query causal
attention, SwiGLU, untied head, no biases). Departures, both forced by
having to read the program's weights:

* rotary pairs are interleaved ``(x[2i], x[2i+1])`` as the program lays
  its q/k columns out, where the published code pairs ``(x[i],
  x[i+d/2])``; under seeded random weights that is a fixed permutation
  of columns;
* q, k and v are three matrices (InternLM2 publishes one fused ``wqkv``
  holding the same numbers).
"""

from __future__ import annotations

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
            "rope_theta": m["rope_theta"], "norm_eps": m["norm_eps"]}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, Dh], positions 0..T-1, interleaved pairs."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _layer(x, lp, *, n_heads, n_kv_heads, head_dim, rope_theta, norm_eps):
    """One decoder block on ``x`` [T, D] in float32."""
    lp = jax.tree.map(lambda a: a.astype(F32), lp)
    t = x.shape[0]
    h = _rmsnorm(x, lp["attn_norm"], norm_eps)
    q = _rope((h @ lp["wq"]).reshape(t, n_heads, head_dim), rope_theta)
    k = _rope((h @ lp["wk"]).reshape(t, n_kv_heads, head_dim), rope_theta)
    v = (h @ lp["wv"]).reshape(t, n_kv_heads, head_dim)
    group = n_heads // n_kv_heads
    q = q.reshape(t, n_kv_heads, group, head_dim)
    s = jnp.einsum("qhgd,khd->hgqk", q, k) * head_dim ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v).reshape(t, n_heads * head_dim)
    x = x + o @ lp["wo"]
    h = _rmsnorm(x, lp["mlp_norm"], norm_eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp[
        "w_down"]


_layer_jit = jax.jit(_layer, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "rope_theta", "norm_eps"))


@jax.jit
def _head(x, final_norm, lm_head, eps):
    return _rmsnorm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


def hidden(params, tokens, sizes):
    """Final hidden states [T, D] (before the last norm) of one
    sequence ``tokens`` [T]."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        per_layer = {k: v for k, v in sizes.items() if k != "n_layers"}
        for i in range(sizes["n_layers"]):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x = _layer_jit(x, lp, **per_layer)
        return x


def logits(params, tokens, sizes, last: int = 0):
    """Float32 logits of one sequence: every position [T, V], or only
    the last ``last`` positions."""
    x = hidden(params, tokens, sizes)
    with jax.default_matmul_precision("highest"):
        return _head(x[-last:], params["final_norm"], params["lm_head"],
                     sizes["norm_eps"])


def loss(params, row, sizes):
    """Mean next-token cross-entropy of one row of T+1 tokens."""
    row = jnp.asarray(row)
    lg = logits(params, row[:-1], sizes)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, row[1:, None], axis=-1).mean()
