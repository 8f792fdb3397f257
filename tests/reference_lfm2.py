"""The plain reference of the LFM2-8B-A1B (``lfm2_moe``) decoder: what
``correct`` is decided against for a served model of gated
short-convolution layers beside grouped-query attention layers, two
leading dense SwiGLU layers and a mixture of 32 experts, 4 a token, in
every layer after them, and a head tied to the embedding. One copy lives
beside the benchmark (``benchmark/reference_lfm2.py``) and one beside
the tier-1 tests (``tests/reference_lfm2.py``); a test holds the two
identical below this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, the
convolution as three shifted products over the whole sequence, attention
over the whole sequence a query head at a time with its key-value head
looked up by ``h // (H / Hkv)``, the mixture as EVERY expert run on
every token and summed under a ``[T, E]`` matrix of gates that is zero
where an expert was not chosen (no sort, no groups, no dispatch); no
kernel, no cache, no batching, and no import from the program. It takes
the program's parameter tree and a plain dict of sizes, and upcasts one
layer's (one expert's) matrices at a time, so that it fits on the chip
beside the engine.

The layers, as this repository reads ``config.json`` (every reading
that the file does not settle is under ``assumed`` in
``benchmark/configs/lfm2-8b-a1b-14l.json``). ``x`` [T, D], pre-norm
residual layers ``x += Op(RMSNorm(x)); x += FFN(RMSNorm(x))``, RMSNorm
with ``norm_eps`` and a learned gain, no bias anywhere:

* **conv** (``Lfm2ShortConv``): ``[B | C | u] = h W_in`` (D -> 3 D);
  ``z = B * u``; ``c_t = sum_j w_j * z_{t - (taps - 1) + j}``, depthwise
  and causal, zeros before the sequence's start, no bias and no
  activation; ``x + (C * c) W_out``. What a sequence keeps between calls
  is the newest ``taps - 1`` rows of ``z``.
* **full**: q of ``n_heads`` heads, k and v of ``n_kv_heads``; RMSNorm
  with a gain of ``head_dim`` over each head of q and of k; rotary at
  ``rope_theta`` on q and k; causal, scores ``/ sqrt(Dh)``; ``x + o
  W_o``.
* **FFN**: the first ``n_dense_layers`` layers a SwiGLU of width
  ``d_ff_dense``; every later layer ``s = sigmoid(h W_r)`` over the
  experts, the ``top_k`` experts with the largest ``s + bias`` (the
  bias chooses and never weighs), gates ``s`` at those, divided by their
  sum, times ``route_scale``; ``sum_k g_k Expert_k(h)``, each a SwiGLU
  of width ``d_ff``.
* final RMSNorm; logits ``x E^T`` with ``E`` the embedding.

Departures from the published layout, none from the mathematics: the
rotary embedding is over the interleaved pairs ``(x[2i], x[2i+1])`` as
the program lays its q/k columns, where the published code rotates the
halves ``(x[i], x[i + Dh/2])``: the same function under a fixed
permutation of each head's q/k columns and q/k gains (noted at
``_rope``); the taps lie ``[taps, D]``, the last the newest row, where
the published ``conv.weight`` is ``[D, 1, taps]`` (noted at ``_conv``).

``store``, ``wrong``, ``pads`` and ``cut`` exist for
``benchmark/tools/lfm2_tolerance.py`` and ``tests/test_lfm2.py``, which
show what the check refuses: the same reference with weights and the
residual stream stored in a narrower float, with one mechanism
miscomputed (a name of ``WRONG``), with a bucket's padding pushed
through the convolutions' rows, or with the rows not carried over a
chunk's boundary.
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
    "taps_reversed",        # w_j met z_{t-j}: the taps in reverse order
    "no_b_gate",            # z = u, B left out
    "no_c_gate",            # the convolution's output not gated by C
    "rows_in_float8",       # z rounded to float8_e4m3 before the taps
    "bias_as_weight",       # gates = (s + bias) at the chosen experts
    "no_renorm",            # the chosen gates not divided by their sum
    "no_qk_norm",           # q and k not normed a head
    "no_rope",              # q and k not rotated
    "wrong_kv_group",       # query head h reads KV head h % Hkv
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
            "conv_taps": m["conv_taps"],
            "rope_theta": m["layer_rotary"]["full"]["theta"],
            "n_dense_layers": m["n_dense_layers"],
            "top_k": m["moe_top_k"],
            "norm_topk_prob": m["moe_norm_topk_prob"],
            "route_scale": m["moe_route_scale"]}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, Dh], positions 0..T-1. DEPARTURE: pairs (2i, 2i+1), as
    the program lays its q/k columns; the published code pairs
    (i, i + Dh/2)."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("norm_eps", "taps", "wrong",
                                             "cut"))
def _conv(x, lp, *, norm_eps, taps, wrong, cut):
    """The layer's operator branch on ``x`` [T, D], residual included,
    and the newest ``taps - 1`` rows of ``z`` [taps - 1, D] after the
    last position. DEPARTURE: ``conv_w`` is [taps, D], tap ``taps - 1``
    on the newest row (the published ``conv.weight`` [D, 1, taps], the
    same numbers turned). ``cut``: the rows before position ``cut`` are
    not carried over it (zeros in their place: a resumed chunk that
    starts from nothing, and hands those zeros on where it is shorter
    than the rows kept)."""
    t = x.shape[0]
    h = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    b, c, u = jnp.split(h @ lp["w_in"].astype(F32), 3, axis=-1)
    z = u if wrong == "no_b_gate" else b * u
    if wrong == "rows_in_float8":
        # (not a cast there and back, which a compiler that keeps excess
        # precision takes out)
        z = jax.lax.reduce_precision(z, exponent_bits=4, mantissa_bits=3)
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), F32), z])
    w = lp["conv_w"].astype(F32)
    if wrong == "taps_reversed":
        w = w[::-1]
    at = jnp.arange(t)[:, None]
    y = jnp.zeros_like(z)
    for j in range(taps):
        term = padded[j:j + t] * w[j]
        if cut:
            # the row this tap reads lies at t - (taps - 1) + j
            term = jnp.where((at >= cut) & (at - (taps - 1) + j < cut),
                             0.0, term)
        y = y + term
    if wrong != "no_c_gate":
        y = c * y
    rows = padded[t:]
    if cut:
        # a chunk shorter than the rows kept hands on what it started from
        rows = jnp.where(jnp.arange(t - (taps - 1), t)[:, None] < cut, 0.0,
                         rows)
    return x + y @ lp["w_out"].astype(F32), rows


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "norm_eps", "theta", "wrong"))
def _attention(x, lp, seen, *, n_heads, n_kv_heads, head_dim, norm_eps,
               theta, wrong):
    """The attention branch on ``x`` [T, D], residual included; a key
    is seen by the queries at and after it, where ``seen`` [T] says it
    is the sequence's. Positions count the sequence's own tokens."""
    t = x.shape[0]
    h = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    q = (h @ lp["wq"].astype(F32)).reshape(t, n_heads, head_dim)
    k = (h @ lp["wk"].astype(F32)).reshape(t, n_kv_heads, head_dim)
    v = (h @ lp["wv"].astype(F32)).reshape(t, n_kv_heads, head_dim)
    if wrong != "no_qk_norm":
        q = _rmsnorm(q, lp["q_norm"].astype(F32), norm_eps)
        k = _rmsnorm(k, lp["k_norm"].astype(F32), norm_eps)
    if wrong != "no_rope":
        q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv_heads
    heads = jnp.arange(n_heads)
    kv_of = heads % n_kv_heads if wrong == "wrong_kv_group" else heads // rep
    mask = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]) & seen[None, :]
    scale = 1.0 if wrong == "no_attn_scale" else head_dim ** -0.5

    def one_head(args):                       # a head at a time: [T, T]
        qh, g = args
        s = (qh @ k[:, g].T) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return p @ v[:, g]

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2), kv_of))   # [H, T, Dh]
    o = o.transpose(1, 0, 2).reshape(t, n_heads * head_dim)
    return x + o @ lp["wo"].astype(F32)


@jax.jit
def _swiglu(u, w_gate, w_up, w_down):
    return ((jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32)))
            @ w_down.astype(F32))


@functools.partial(jax.jit, static_argnames=(
    "top_k", "norm_topk_prob", "route_scale", "wrong"))
def _gates(u, router, bias, *, top_k, norm_topk_prob, route_scale, wrong):
    """``[T, E]``: a token's gate on each expert, 0 where it was not
    chosen. The bias chooses and never weighs."""
    s = jax.nn.sigmoid(u @ router.astype(F32))
    _, chosen = jax.lax.top_k(s + bias.astype(F32), top_k)
    g = jnp.take_along_axis(
        s + bias.astype(F32) if wrong == "bias_as_weight" else s, chosen, -1)
    if norm_topk_prob and wrong != "no_renorm":
        # the source adds 1e-6 (the program clamps the sum at 1e-9): the
        # sum is at least top_k times the least chosen sigmoid, far from
        # either
        g = g / (g.sum(-1, keepdims=True) + 1e-6)
    g = g * route_scale
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                chosen].set(g)


def _ffn(u, lp, sizes, wrong):
    """The feed-forward branch's output on normed rows ``u`` [T, D]."""
    if "moe" not in lp:
        return _swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"])
    moe = lp["moe"]
    gates = _gates(u, moe["router"], moe["router_bias"],
                   top_k=sizes["top_k"],
                   norm_topk_prob=sizes["norm_topk_prob"],
                   route_scale=sizes["route_scale"], wrong=wrong)
    y = jnp.zeros_like(u)
    for e in range(moe["w_gate"].shape[0]):       # every expert, every row
        y = y + gates[:, e:e + 1] * _swiglu(
            u, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e])
    return y


def layer(x, lp, sizes, i, seen, wrong=None, cut=0):
    """Layer ``i`` on ``x`` [T, D] in float32; ``lp`` its parameters.
    Returns the new ``x`` and, of a conv layer, the rows after the last
    position (None of an attention layer)."""
    if sizes["layer_types"][i] == "conv":
        x, rows = _conv(x, lp, norm_eps=sizes["norm_eps"],
                        taps=sizes["conv_taps"], wrong=wrong, cut=cut)
    else:
        x, rows = _attention(
            x, lp, seen, n_heads=sizes["n_heads"],
            n_kv_heads=sizes["n_kv_heads"], head_dim=sizes["head_dim"],
            norm_eps=sizes["norm_eps"], theta=float(sizes["rope_theta"]),
            wrong=wrong), None
    u = _rmsnorm(x, lp["mlp_norm"].astype(F32), sizes["norm_eps"])
    # rows a block at a time: [1024, width] and not [T, width]
    y = jnp.concatenate(
        [_ffn(u[t:t + _ROW_BLOCK], lp, sizes, wrong)
         for t in range(0, x.shape[0], _ROW_BLOCK)])
    return x + y, rows


def logits(params, tokens, sizes, last: int = 0, *, store=None, wrong=None,
           states: bool = False, pads: Optional[Tuple[int, int]] = None,
           cut: int = 0):
    """Float32 logits of ONE sequence ``tokens`` [T]: every position
    [T, V], or only the last ``last`` positions. ``store``: a dtype the
    weights and the residual stream are rounded to on the way (None: as
    they are). ``wrong``: a name of ``WRONG``. ``states``: also the rows
    every conv layer keeps after the last position, [n_conv, taps - 1,
    D]. ``pads`` ``(at, n)``: ``n`` positions of token 0 after the first
    ``at`` tokens pushed through every conv layer's rows as if they were
    the sequence's (what a chunk does that leaves the rows at its
    bucket's end and not at ``length``: no conv layer reads a position,
    so this is that fault to the letter); attention neither sees them
    nor counts them, and their rows are dropped before the head.
    ``cut``: no conv layer carries its rows over position ``cut`` (a
    resumed chunk that starts from zeros)."""
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
    stack = list(params.get("dense_layers", ())) + list(params["layers"])
    kept = []
    with jax.default_matmul_precision("highest"):
        embed = stored(params["embed"])
        x = embed[tokens].astype(F32)
        for i in range(sizes["n_layers"]):
            if pads is not None and sizes["layer_types"][i] != "conv":
                # attention is over the sequence's own tokens at their own
                # positions: the pads out, the layer, the pads back in
                real, rows = layer(x[seen], stored(stack[i]), sizes, i,
                                   jnp.ones(int(seen.sum()), bool), wrong)
                x = x.at[jnp.flatnonzero(seen)].set(real)
            else:
                x, rows = layer(x, stored(stack[i]), sizes, i, seen, wrong,
                                cut)
            x = stored(x)
            if rows is not None:
                kept.append(rows)
        x = _rmsnorm(x[seen][-last:], stored(params["final_norm"]).astype(F32),
                     sizes["norm_eps"])
        out = x @ embed.astype(F32).T
    return (out, jnp.stack(kept)) if states else out
