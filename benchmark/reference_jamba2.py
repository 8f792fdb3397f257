"""The plain reference of the AI21-Jamba2-3B (``jamba``) decoder, whole:
what ``correct`` is decided against for a served model of Mamba-1
selective state-space layers beside multi-query attention layers, a
dense SwiGLU in every layer and a head tied to the embedding. One copy
lives beside the benchmark (``benchmark/reference_jamba2.py``) and one
beside the tier-1 tests (``tests/reference_jamba2.py``); a test holds
the two identical below this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, the
selective scan as the recurrence a position at a time (``lax.scan`` over
positions: no blocks, no associative scan), attention over the whole
sequence with its one key-value head repeated to every query head; no
kernel, no cache, no batching, and no import from the program. It takes
the program's parameter tree and a plain dict of sizes, and upcasts one
layer's matrices at a time, so that it fits on the chip beside the
engine.

The layers, as this repository reads ``config.json`` (every reading
that the file does not settle is under ``assumed`` in
``benchmark/configs/jamba2-3b.json``). ``x`` [T, D], pre-norm residual
layers ``x += mixer(RMSNorm(x)); x += SwiGLU(RMSNorm(x))``, RMSNorm with
``norm_eps``, no bias but the convolution's and the step's:

* **mamba** (``Di = expand * D`` channels, ``N`` state rows, ``R`` the
  step's rank): ``[u | z] = h W_in``; ``u'_t = SiLU(b + sum_j w_j
  u_{t - (taps - 1) + j})``, depthwise and causal, zeros before the
  sequence's start; ``[delta | B | C] = u' W_x`` as ``R | N | N``, each
  RMS-normed with a gain of its own; ``Delta = softplus(delta W_dt +
  b_dt)`` [Di]; ``A = -exp(A_log)``; from ``s_0 = 0`` in float32

      s_t = exp(Delta_t A) s_{t-1} + (Delta_t u'_t) B_t
      y_t = s_t C_t + D u'_t

  ``x + (y SiLU(z)) W_out``.
* **full**: q of ``n_heads`` heads, k and v of ``n_kv_heads`` (one),
  scores ``/ sqrt(Dh)``, causal over everything, no rotary or other
  positional embedding; ``x + o W_o``.
* every layer: a dense SwiGLU of width ``d_ff``.
* final RMSNorm; logits ``x E^T`` with ``E`` the embedding.

Departures from the published layout, none from the mathematics:
``A_log`` and a state lie TURNED, ``[N, Di]`` and not ``[Di, N]`` (as
the program's parameters and cache hold them, a channel a lane), and the
states this returns are so too.

``store``, ``wrong`` and ``pads`` exist for
``benchmark/tools/jamba2_tolerance.py`` and ``tests/test_jamba2.py``,
which show what the check refuses: the same reference with weights and
the residual stream stored in a narrower float, with one mechanism
miscomputed (a name of ``WRONG``), or with a bucket's padding run
through the convolution and the scan as if it were the sequence's.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
_ROW_BLOCK = 1024

#: What ``wrong`` may name, each one mechanism miscomputed.
WRONG = (
    "state_in_bf16",        # the scan's state rounded to bf16 a position
    "no_dt_norm",           # delta not normed
    "no_b_norm",            # B not normed
    "no_c_norm",            # C not normed
    "no_conv_bias",         # the convolution's bias left out
    "no_d_skip",            # D u' left out
    "no_softplus",          # Delta = delta W_dt + b_dt as it comes
    "rope_on_attention",    # q and k of the attention layers rotated
    "no_attn_scale",        # scores not divided by sqrt(Dh)
)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    return {"n_layers": m["n_layers"], "n_heads": m["n_heads"],
            "n_kv_heads": m["n_kv_heads"],
            "head_dim": m.get("d_head") or m["d_model"] // m["n_heads"],
            "d_model": m["d_model"], "norm_eps": m["norm_eps"],
            "layer_types": tuple(m["layer_types"]),
            "mamba_d_state": m["mamba_d_state"],
            "mamba_d_conv": m["mamba_d_conv"],
            "mamba_dt_rank": m["mamba_dt_rank"]}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta=10000.0):
    """x [T, H, Dh], positions 0..T-1, pairs (2i, 2i+1): what the
    attention layers do NOT apply (``rope_on_attention``)."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "norm_eps", "taps", "rank", "n_state", "wrong"))
def _mamba(x, lp, *, norm_eps, taps, rank, n_state, wrong):
    """The layer's mixer branch on ``x`` [T, D], residual included, and
    the state after the last position [N, Di]."""
    t = x.shape[0]
    h = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    u, z = jnp.split(h @ lp["w_in"].astype(F32), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u])
    conv = sum(padded[j:j + t] * lp["conv_w"][j].astype(F32)
               for j in range(taps))
    if wrong != "no_conv_bias":
        conv = conv + lp["conv_b"].astype(F32)
    u = jax.nn.silu(conv)
    dbc = u @ lp["w_x"].astype(F32)

    def normed(a, gain, skip):
        return a if wrong == skip else _rmsnorm(a, lp[gain].astype(F32),
                                                norm_eps)

    delta = normed(dbc[:, :rank], "dt_norm", "no_dt_norm")
    b = normed(dbc[:, rank:rank + n_state], "b_norm", "no_b_norm")
    c = normed(dbc[:, rank + n_state:], "c_norm", "no_c_norm")
    step = delta @ lp["w_dt"].astype(F32) + lp["b_dt"].astype(F32)
    if wrong != "no_softplus":
        step = jax.nn.softplus(step)
    a = -jnp.exp(lp["a_log"].astype(F32))                       # [N, Di]

    def position(s, row):
        step_t, u_t, b_t, c_t = row
        s = jnp.exp(step_t[None] * a) * s + (step_t * u_t)[None] * b_t[:, None]
        if wrong == "state_in_bf16":
            # (not a cast there and back, which a compiler that keeps
            # excess precision takes out)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.sum(s * c_t[:, None], 0)

    s, y = jax.lax.scan(position, jnp.zeros_like(a), (step, u, b, c))
    if wrong != "no_d_skip":
        y = y + lp["d_skip"].astype(F32) * u
    return x + (y * jax.nn.silu(z)) @ lp["w_out"].astype(F32), s


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "norm_eps", "wrong"))
def _attention(x, lp, seen, *, n_heads, n_kv_heads, head_dim, norm_eps,
               wrong):
    """The attention branch on ``x`` [T, D], residual included; a key
    is seen by the queries at and after it, where ``seen`` [T] says it
    is the sequence's."""
    t = x.shape[0]
    h = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    q = (h @ lp["wq"].astype(F32)).reshape(t, n_heads, head_dim)
    k = (h @ lp["wk"].astype(F32)).reshape(t, n_kv_heads, head_dim)
    v = (h @ lp["wv"].astype(F32)).reshape(t, n_kv_heads, head_dim)
    if wrong == "rope_on_attention":
        q, k = _rope(q), _rope(k)
    k = jnp.repeat(k, n_heads // n_kv_heads, axis=1)
    v = jnp.repeat(v, n_heads // n_kv_heads, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k)
    if wrong != "no_attn_scale":
        s = s * head_dim ** -0.5
    mask = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]) & seen[None, :]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v).reshape(t, n_heads * head_dim)
    return x + o @ lp["wo"].astype(F32)


@jax.jit
def _swiglu(u, w_gate, w_up, w_down):
    return ((jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32)))
            @ w_down.astype(F32))


def layer(x, lp, sizes, i, seen, wrong=None):
    """Layer ``i`` on ``x`` [T, D] in float32; ``lp`` its parameters.
    Returns the new ``x`` and, of a mamba layer, the state after the
    last position (None of an attention layer)."""
    if sizes["layer_types"][i] == "mamba":
        x, state = _mamba(x, lp, norm_eps=sizes["norm_eps"],
                          taps=sizes["mamba_d_conv"],
                          rank=sizes["mamba_dt_rank"],
                          n_state=sizes["mamba_d_state"], wrong=wrong)
    else:
        x, state = _attention(
            x, lp, seen, n_heads=sizes["n_heads"],
            n_kv_heads=sizes["n_kv_heads"], head_dim=sizes["head_dim"],
            norm_eps=sizes["norm_eps"], wrong=wrong), None
    u = _rmsnorm(x, lp["mlp_norm"].astype(F32), sizes["norm_eps"])
    # rows a block at a time: [1024, d_ff] and not [T, d_ff]
    y = jnp.concatenate(
        [_swiglu(u[t:t + _ROW_BLOCK], lp["w_gate"], lp["w_up"], lp["w_down"])
         for t in range(0, x.shape[0], _ROW_BLOCK)])
    return x + y, state


def logits(params, tokens, sizes, last: int = 0, *, store=None, wrong=None,
           states: bool = False, pads: Optional[Tuple[int, int]] = None):
    """Float32 logits of ONE sequence ``tokens`` [T]: every position
    [T, V], or only the last ``last`` positions. ``store``: a dtype the
    weights and the residual stream are rounded to on the way (None: as
    they are). ``wrong``: a name of ``WRONG``. ``states``: also the
    mamba layers' states after the last position, [n_mamba, N, Di].
    ``pads`` ``(at, n)``: ``n`` positions of token 0 after the first
    ``at`` tokens run through every mamba layer's convolution and scan
    as if they were the sequence's (what a chunk does whose bucket's
    padding is not masked: no layer reads a position, so this is that
    fault to the letter); attention does not see them and their rows
    are dropped before the head."""
    assert wrong is None or wrong in WRONG, wrong

    def stored(tree):
        if store is None:
            return tree
        return jax.tree.map(
            lambda a: a.astype(store).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    tokens = jnp.asarray(tokens)
    seen = jnp.ones(tokens.shape, bool)
    if pads is not None:
        at, n = pads
        tokens = jnp.concatenate(
            [tokens[:at], jnp.zeros((n,), tokens.dtype), tokens[at:]])
        seen = jnp.concatenate([seen[:at], jnp.zeros((n,), bool), seen[at:]])
    kept = []
    with jax.default_matmul_precision("highest"):
        embed = stored(params["embed"])
        x = embed[tokens].astype(F32)
        for i in range(sizes["n_layers"]):
            x, state = layer(x, stored(params["layers"][i]), sizes, i, seen,
                             wrong)
            x = stored(x)
            if state is not None:
                kept.append(state)
        x = _rmsnorm(x[seen][-last:], stored(params["final_norm"]).astype(F32),
                     sizes["norm_eps"])
        out = x @ embed.astype(F32).T
    return (out, jnp.stack(kept)) if states else out
