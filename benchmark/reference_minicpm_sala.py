"""The plain reference of the MiniCPM-SALA (``minicpm_sala``) decoder:
what ``correct`` is decided against for a served model of InfLLM-v2
sparse-attention layers (``minicpm4``) beside Lightning linear-attention
layers (``lightning-attn``), a dense SwiGLU in every layer and MiniCPM's
three multipliers. One copy lives beside the benchmark
(``benchmark/reference_minicpm_sala.py``) and one beside the tier-1
tests (``tests/reference_minicpm_sala.py``); a test holds the two
identical below this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, the
linear recurrence a position at a time (``lax.scan`` over positions: no
blocks), the selection a query at a time from the definitions (``vmap``
over a block of queries, so that 12k positions fit), attention over the
whole sequence under each query's own mask; no kernel, no cache, no
batching, and no import from the program. It takes the program's
parameter tree and a plain dict of sizes, and upcasts one layer's
matrices at a time, so that it fits on the chip beside the engine.

The layers, as this repository reads ``config.json`` (every reading
that the file does not settle is under ``assumed`` in
``benchmark/configs/minicpm-sala-8l.json``). ``x`` [T, D] from ``e *
E[token]``; a layer is ``x += m mixer(RMSNorm(x)); x += m
SwiGLU(RMSNorm(x))``; a final RMSNorm; logits ``(x / d) W_head``. No
bias anywhere.

* **sparse**: q of ``n_heads`` heads, k and v of ``n_kv_heads``; RMSNorm
  with a gain over each head of q and k; no rotary. Kernel j covers
  positions ``[stride j, stride j + kernel)`` and its compressed key
  ``c_j`` is the mean of its normed keys, a KV head; it exists for a
  query at t once ``stride j + kernel - 1 <= t``. A query at ``t >=
  dense_len``: ``s_hj = softmax_j(q_h . c_j / sqrt(Dh))`` over the
  kernels that exist; block b = positions ``[block b, block b + block)``
  scores ``sum_{h in group} max_{j meets b} s_hj``; the first
  ``init_blocks`` blocks and those that hold the ``window`` positions
  before the query come first; the group attends the keys at or before
  t in its ``topk`` best blocks (a tie: the lower block). A query below
  ``dense_len`` attends every key at or before it. Scores ``/
  sqrt(Dh)``; ``o * sigmoid(h W_g)``, ``W_o``.
* **lightning**: q, k, v of ``n_heads`` heads each; RMSNorm with a
  gain over each head of q and k; rotary over the whole head in rotated
  halves ``(i, i + Dh/2)`` at ``rope_theta``; ``q / sqrt(Dh)``; from
  ``S = 0`` in float32 ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t =
  q_t S_t`` with ``lambda_h = exp(-2^(-8 (h + 1) / heads))``; RMSNorm
  with a gain over all heads' outputs together, ``* sigmoid(h W_g)``,
  ``W_o``.

``store`` and ``wrong`` exist for ``benchmark/tools/sala_tolerance.py``
and ``tests/test_minicpm_sala.py``, which show what the check refuses:
the same reference with weights and the residual stream stored in a
narrower float, or with one mechanism miscomputed (a name of ``WRONG``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_ROW_BLOCK = 1024
_QUERY_BLOCK = 128

#: What ``wrong`` may name, each one mechanism miscomputed.
WRONG = (
    "no_selection",           # every query attends every key before it
    "random_blocks",          # the unforced blocks drawn, not scored
    "no_init_block",          # the first blocks not forced
    "no_local_window",        # the window's blocks not forced
    "heads_choose_alone",     # a head's own best blocks, not its group's
    "mean_for_max",           # a block's score the mean of its kernels'
    "rope_on_sparse",         # q and k of the sparse layers rotated
    "no_rope_on_lightning",   # q and k of the lightning layers not
    "one_decay",              # every head decays as the middle head
    "state_in_bf16",          # the state rounded to bf16 a position
    "no_sparse_gate",         # the sparse layers' output gate left out
    "no_lightning_gate",      # the lightning layers' left out
    "no_output_norm",         # the lightning layers' output norm left out
    "no_embed_multiplier",
    "no_residual_multiplier",
    "no_logit_divisor",
)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    dh = m.get("d_head") or m["d_model"] // m["n_heads"]
    return {"n_layers": m["n_layers"], "n_heads": m["n_heads"],
            "n_kv_heads": m["n_kv_heads"], "head_dim": dh,
            "norm_eps": m["norm_eps"], "rope_theta": m["rope_theta"],
            "layer_types": tuple(m["layer_types"]),
            "kernel": m["sparse_kernel"], "stride": m["sparse_stride"],
            "block": m["sparse_block"], "topk": m["sparse_topk"],
            "init_blocks": m["sparse_init_blocks"],
            "window": m["sparse_window"], "dense_len": m["sparse_dense_len"],
            "embed_multiplier": m.get("embed_multiplier") or 1.0,
            "residual_multiplier": m.get("residual_multiplier") or 1.0,
            "logit_divisor": m.get("logit_divisor") or 1.0}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope_halves(x, theta):
    """x [T, H, Dh], positions 0..T-1, pairs (i, i + Dh/2)."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _heads(h, lp, w, norm, heads, head_dim, eps):
    y = (h @ lp[w].astype(F32)).reshape(h.shape[0], heads, head_dim)
    return y if norm is None else _rmsnorm(y, lp[norm].astype(F32), eps)


def kernels_meeting(n_blocks: int, n_kernels: int, kernel: int, stride: int,
                    block: int) -> np.ndarray:
    """[n_blocks, most] int: the kernels that share a position with
    each block, from the definition, -1 where a block meets fewer."""
    meets = [[j for j in range(n_kernels)
              if stride * j < block * (b + 1) and stride * j + kernel
              > block * b] for b in range(n_blocks)]
    most = max(map(len, meets))
    return np.array([m + [-1] * (most - len(m)) for m in meets], np.int32)


_SPARSE_SIZES = ("n_heads", "n_kv_heads", "head_dim", "norm_eps", "kernel",
                 "stride", "block", "topk", "init_blocks", "window",
                 "dense_len", "theta", "wrong")


@functools.partial(jax.jit, static_argnames=_SPARSE_SIZES)
def _sparse_inputs(x, lp, *, n_heads, n_kv_heads, head_dim, norm_eps, kernel,
                   stride, theta, wrong, **_):
    """``h``, q [T, H, Dh], k and v [T, Hkv, Dh], and the compressed
    keys ``c`` [J, Hkv, Dh]: ``c_j`` the mean of kernel j's keys, for
    every kernel that lies whole in the sequence (one row of zeros
    where none does)."""
    t = x.shape[0]
    h = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    q = _heads(h, lp, "wq", "q_norm", n_heads, head_dim, norm_eps)
    k = _heads(h, lp, "wk", "k_norm", n_kv_heads, head_dim, norm_eps)
    v = _heads(h, lp, "wv", None, n_kv_heads, head_dim, norm_eps)
    if wrong == "rope_on_sparse":
        q, k = _rope_halves(q, theta), _rope_halves(k, theta)
    n_kernels = max((t - kernel) // stride + 1, 0)
    if not n_kernels:
        return h, q, k, v, jnp.zeros((1, n_kv_heads, head_dim), F32)
    covers = stride * jnp.arange(n_kernels)[:, None] + jnp.arange(kernel)
    return h, q, k, v, k[covers].mean(1)


@functools.partial(jax.jit, static_argnames=_SPARSE_SIZES)
def _sparse_queries(q_b, pos_b, draw_b, k, v, c, meets, *, n_heads,
                    n_kv_heads, head_dim, kernel, stride, block, topk,
                    init_blocks, window, dense_len, wrong, **_):
    """A block of queries ``q_b`` [Q, H, Dh] at ``pos_b`` [Q]: what
    they attend [Q, H, Dh] and the blocks each chose
    [Q, Hkv, n_blocks] bool (all False below ``dense_len``)."""
    t = k.shape[0]
    group = n_heads // n_kv_heads
    n_blocks = meets.shape[0]
    last = stride * jnp.arange(c.shape[0]) + kernel - 1     # a kernel's end
    blocks = jnp.arange(n_blocks)

    def choose(q_t, pos, draw_t):
        """One query [H, Dh] at ``pos``: its groups' blocks
        [Hkv, n_blocks] bool (a head's own under
        ``heads_choose_alone``: [H, n_blocks])."""
        exists = last <= pos
        s = jnp.einsum("hd,jhd->hj", q_t, jnp.repeat(c, group, 1)) \
            * head_dim ** -0.5
        s = jnp.where(exists & jnp.any(exists), jax.nn.softmax(
            jnp.where(exists, s, -jnp.inf), -1), 0.0)
        met = jnp.where(meets >= 0, s[:, meets], 0.0)    # [H, n_blocks, most]
        if wrong == "mean_for_max":
            score = met.sum(-1) / jnp.maximum((meets >= 0).sum(-1), 1)
        else:
            score = met.max(-1)
        if wrong != "heads_choose_alone":
            score = score.reshape(n_kv_heads, group, n_blocks).sum(1)
        if wrong == "random_blocks":
            score = draw_t
        own = pos // block
        valid = blocks <= own
        forced = jnp.zeros_like(valid)
        if wrong != "no_init_block":
            forced |= blocks < init_blocks
        if wrong != "no_local_window":
            forced |= blocks > own - window // block
        rank = jnp.where(forced & valid, jnp.inf,
                         jnp.where(valid, score, -jnp.inf))
        order = jnp.argsort(-rank, axis=-1, stable=True)[..., :topk]
        picked = jnp.zeros(rank.shape, bool).at[
            jnp.arange(rank.shape[0])[:, None], order].set(True)
        return picked & valid

    picked = jax.vmap(choose)(q_b, pos_b, draw_b)
    selects = (pos_b >= dense_len)[:, None, None]
    if wrong == "no_selection":
        selects = jnp.zeros_like(selects)
    picked &= selects
    allowed = picked | ~selects
    if allowed.shape[1] != n_heads:              # a group's choice: its heads'
        allowed = jnp.repeat(allowed, group, 1)
    s = jnp.einsum("qhd,shd->qhs", q_b, jnp.repeat(k, group, 1)) \
        * head_dim ** -0.5
    mask = (jnp.arange(t)[None, None, :] <= pos_b[:, None, None]) \
        & jnp.repeat(allowed, block, -1)[..., :t]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return (jnp.einsum("qhs,shd->qhd", p, jnp.repeat(v, group, 1)),
            picked[:, :n_kv_heads])


def _sparse(x, lp, **sizes):
    """The sparse layer's mixer on ``x`` [T, D] and the blocks each
    query chose [T, Hkv, n_blocks] bool, a block of queries at a
    time."""
    t = x.shape[0]
    h, q, k, v, c = _sparse_inputs(x, lp, **sizes)
    n_blocks = -(-t // sizes["block"])
    meets = jnp.asarray(kernels_meeting(
        n_blocks, c.shape[0], sizes["kernel"], sizes["stride"],
        sizes["block"]))
    draw = jax.random.uniform(jax.random.PRNGKey(7),
                              (t, sizes["n_kv_heads"], n_blocks))
    outs, chosen = zip(*(
        _sparse_queries(q[at:at + _QUERY_BLOCK],
                        jnp.arange(at, min(at + _QUERY_BLOCK, t)),
                        draw[at:at + _QUERY_BLOCK], k, v, c, meets, **sizes)
        for at in range(0, t, _QUERY_BLOCK)))
    o = jnp.concatenate(outs).reshape(t, -1)
    if sizes["wrong"] != "no_sparse_gate":
        o = o * jax.nn.sigmoid(h @ lp["wg"].astype(F32))
    return o @ lp["wo"].astype(F32), jnp.concatenate(chosen)


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "norm_eps", "theta", "wrong"))
def _lightning(x, lp, *, heads, head_dim, norm_eps, theta, wrong):
    """The lightning layer's mixer on ``x`` [T, D] and the state after
    the last position [heads, Dh, Dh]."""
    t = x.shape[0]
    h = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    q = _heads(h, lp, "wq", "q_norm", heads, head_dim, norm_eps)
    k = _heads(h, lp, "wk", "k_norm", heads, head_dim, norm_eps)
    v = _heads(h, lp, "wv", None, heads, head_dim, norm_eps)
    if wrong != "no_rope_on_lightning":
        q, k = _rope_halves(q, theta), _rope_halves(k, theta)
    q = q * head_dim ** -0.5
    n = jnp.arange(1, heads + 1, dtype=F32)
    if wrong == "one_decay":
        n = jnp.full_like(n, heads // 2)
    decay = jnp.exp(-(2.0 ** (-8.0 * n / heads)))[:, None, None]

    def position(s, row):
        q_t, k_t, v_t = row
        s = decay * s + k_t[:, :, None] * v_t[:, None, :]
        if wrong == "state_in_bf16":
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    s, o = jax.lax.scan(position, jnp.zeros((heads, head_dim, head_dim), F32),
                        (q, k, v))
    o = o.reshape(t, heads * head_dim)
    if wrong != "no_output_norm":
        o = _rmsnorm(o, lp["o_norm"].astype(F32), norm_eps)
    if wrong != "no_lightning_gate":
        o = o * jax.nn.sigmoid(h @ lp["wg"].astype(F32))
    return o @ lp["wo"].astype(F32), s


@jax.jit
def _swiglu(u, w_gate, w_up, w_down):
    return ((jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32)))
            @ w_down.astype(F32))


def layer(x, lp, sizes, i, wrong=None):
    """Layer ``i`` on ``x`` [T, D] in float32; ``lp`` its parameters.
    Returns the new ``x`` and, of a lightning layer, its state after the
    last position, of a sparse layer the blocks its queries chose."""
    m = 1.0 if wrong == "no_residual_multiplier" else sizes[
        "residual_multiplier"]
    if sizes["layer_types"][i] == "lightning":
        y, kept = _lightning(
            x, lp, heads=sizes["n_heads"], head_dim=sizes["head_dim"],
            norm_eps=sizes["norm_eps"], theta=sizes["rope_theta"],
            wrong=wrong)
    else:
        y, kept = _sparse(
            x, lp, n_heads=sizes["n_heads"], n_kv_heads=sizes["n_kv_heads"],
            head_dim=sizes["head_dim"], norm_eps=sizes["norm_eps"],
            kernel=sizes["kernel"], stride=sizes["stride"],
            block=sizes["block"], topk=sizes["topk"],
            init_blocks=sizes["init_blocks"], window=sizes["window"],
            dense_len=sizes["dense_len"], theta=sizes["rope_theta"],
            wrong=wrong)
    x = x + m * y
    u = _rmsnorm(x, lp["mlp_norm"].astype(F32), sizes["norm_eps"])
    # rows a block at a time: [1024, d_ff] and not [T, d_ff]
    y = jnp.concatenate(
        [_swiglu(u[t:t + _ROW_BLOCK], lp["w_gate"], lp["w_up"], lp["w_down"])
         for t in range(0, x.shape[0], _ROW_BLOCK)])
    return x + m * y, kept


def logits(params, tokens, sizes, last: int = 0, *, store=None, wrong=None,
           kept: bool = False):
    """Float32 logits of ONE sequence ``tokens`` [T]: every position
    [T, V], or only the last ``last`` positions. ``store``: a dtype the
    weights and the residual stream are rounded to on the way (None: as
    they are). ``wrong``: a name of ``WRONG``. ``kept``: also the
    lightning layers' states after the last position [n_lightning,
    heads, Dh, Dh] and the blocks every query of every sparse layer
    chose [n_sparse, T, n_kv_heads, n_blocks] bool."""
    assert wrong is None or wrong in WRONG, wrong

    def stored(tree):
        if store is None:
            return tree
        return jax.tree.map(
            lambda a: a.astype(store).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    states, chosen = [], []
    with jax.default_matmul_precision("highest"):
        x = stored(params["embed"])[jnp.asarray(tokens)].astype(F32)
        if wrong != "no_embed_multiplier":
            x = x * sizes["embed_multiplier"]
        for i in range(sizes["n_layers"]):
            x, of_layer = layer(x, stored(params["layers"][i]), sizes, i,
                                wrong)
            x = stored(x)
            (states if sizes["layer_types"][i] == "lightning"
             else chosen).append(of_layer)
        x = _rmsnorm(x[-last:], stored(params["final_norm"]).astype(F32),
                     sizes["norm_eps"])
        if wrong != "no_logit_divisor":
            x = x / sizes["logit_divisor"]
        out = x @ stored(params["lm_head"]).astype(F32)
    return (out, jnp.stack(states), jnp.stack(chosen)) if kept else out
