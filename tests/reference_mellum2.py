"""The plain reference of the Mellum 2 decoder as one chip of an
expert-parallel group trains it: what ``correct`` is decided against
for a trained stack of window and full layers whose full layers rotate
by YaRN and whose sparse FFN holds a chip's share of the experts. One
copy lives beside the benchmark and one beside the tier-1 tests
(``tests/reference_mellum2.py``); a test holds the two identical below
this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, over
KV heads and blocks of queries, and over the held experts with a mask
per expert; no sort, no kernel, no scan, and no import from the program.
It takes the program's parameter tree (two lists of layers; whatever its
dtype) and a plain dict of sizes, and upcasts one layer's (and of its
experts, one expert's) matrices at a time, so that 8192 positions fit on
the chip beside the program. ``jax.grad`` of :func:`loss` is the
reference's gradient; :func:`gradient_by_layer` is the same gradient
taken a layer at a time, which is how it fits at 8192 positions.

The layer, as this repository reads it (Mellum2-12B-A2.5B-Instruct's
``config.json``, ``model_type`` ``mellum``; what the config does not
say is ``assumed`` in the configuration file): a pre-norm decoder,

    x = x + attn(rmsnorm(x)) Wo;   x = x + moe(rmsnorm(x))

* attention: q, k, v without bias, no q/k norm; ``n_heads`` query heads
  over ``n_kv_heads`` KV heads of ``head_dim``; scores ``q k / sqrt(head_dim)``,
  float32 softmax. A **sliding** layer (three of four) rotates q and k
  plainly, pair i at ``theta^(-2i/d)``, and a query at p sees the keys
  ``p - window < j <= p``. A **full** layer sees every ``j <= p`` and
  rotates by **YaRN**: pair i at ``f_i = e_i / factor * ramp_i + e_i *
  (1 - ramp_i)`` with ``e_i = theta^(-2i/d)``, ``ramp_i = clip((i - low)
  / (high - low), 0, 1)``, ``low = floor(c(beta_fast))``, ``high =
  ceil(c(beta_slow))`` clipped to ``[0, d - 1]``, ``c(r) = d ln(original
  / (2 pi r)) / (2 ln theta)``; cos and sin times ``attention_factor``,
  on q and on k alike; the frequencies fixed whatever the row's length;
* the sparse FFN: ``p = softmax(h Wr)`` over ALL ``n_experts`` in
  float32; the ``top_k`` largest; their gates ``p_e / sum of the chosen
  p`` (``norm_topk_prob``: over all chosen, held here or not); ``y =
  sum over the chosen experts this chip HOLDS (``experts_held`` from
  ``expert_offset``) of gate_e * Wd_e(silu(Wg_e h) * Wu_e h)`` and
  nothing for the others: the chips that hold those add them in the
  deployment's combine;
* final RMSNorm, untied head over this chip's slice of the vocabulary.

Training loss: mean next-token cross-entropy over the slice
+ ``aux_coef`` x the load-balancing term, ``E x sum_e f_e p_e`` over all
``n_experts`` with ``f_e`` the share of this chip's (token, choice)
pairs that fell on expert e and ``p_e`` the mean router probability of
e over this chip's tokens, summed over layers.

Departures, forced by having to read the program's weights: rotary
pairs are interleaved ``(x[2i], x[2i+1])`` as the program lays its q/k
columns out, where the published code pairs ``(x[i], x[i + d/2])`` (a
fixed permutation of each head's columns under seeded random weights);
the experts are three stacked arrays.

``store`` and ``without`` exist for ``benchmark/tools/mellum2_tolerance.py``
and the tests, which show what the comparison refuses: the same
reference with weights and the residual stream stored in a narrower
float, or with one mechanism left out (``"window"``, ``"yarn"``: the
plain rotary on full layers; ``"attention_factor"``: it stays 1;
``"renorm"``: the gates as the softmax gave them; ``"aux"``).
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


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    return {"n_layers": m["n_layers"], "n_heads": m["n_heads"],
            "n_kv_heads": m["n_kv_heads"], "head_dim": m["d_head"],
            "norm_eps": m["norm_eps"],
            "layer_types": tuple(m["layer_types"]),
            "window": m["attn_window"],
            "rotary": {kind: dict(how) for kind, how
                       in m["layer_rotary"].items()},
            "n_experts": m["n_experts"], "top_k": m["moe_top_k"],
            "experts_held": m["moe_experts_held"],
            "expert_offset": m["moe_expert_offset"],
            "aux_coef": m["moe_aux_loss_coef"]}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotary_table(how: Dict[str, Any], d: int, without=()):
    """``(angle a position of each of the d/2 pairs, factor on cos and
    sin)`` of one kind of layer: ``how`` holds ``theta`` and, for YaRN,
    ``factor``, ``original_max_seq``, ``beta_fast``, ``beta_slow`` and
    ``attention_factor``."""
    plain = how["theta"] ** -(np.arange(0, d, 2, dtype=np.float64) / d)
    if how.get("factor") is None:
        return plain, 1.0
    m = 1.0 if "attention_factor" in without else how["attention_factor"]
    if "yarn" in without:
        return plain, m

    def pair_turning(times):
        return d * math.log(how["original_max_seq"] / (2 * math.pi * times)
                            ) / (2 * math.log(how["theta"]))

    low = max(math.floor(pair_turning(how["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(how["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return plain / how["factor"] * ramp + plain * (1 - ramp), m


def _rope(x, freqs, m):
    """x [B, T, H, Dh], positions 0..T-1, interleaved pairs."""
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs[None, :]
    cos, sin = m * jnp.cos(ang)[:, None, :], m * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "norm_eps", "m"))
def _qkv(x, lp, freqs, *, n_heads, n_kv_heads, head_dim, norm_eps, m):
    """q [B, T, H, Dh], k and v [B, T, Hkv, Dh], q and k rotated."""
    b, t, _ = x.shape
    u = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    q = (u @ lp["wq"].astype(F32)).reshape(b, t, n_heads, head_dim)
    k = (u @ lp["wk"].astype(F32)).reshape(b, t, n_kv_heads, head_dim)
    v = (u @ lp["wv"].astype(F32)).reshape(b, t, n_kv_heads, head_dim)
    return _rope(q, freqs, m), _rope(k, freqs, m), v


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_group(q, k, v, first, *, window):
    """One KV head's group, one block of queries: q [B, Tq, G, Dh], the
    queries at positions ``first + 0..Tq-1``, over all of k, v
    [B, T, Dh]; ``window`` None for a full layer."""
    s = jnp.einsum("bqgd,bkd->bgqk", q, k) * q.shape[-1] ** -0.5
    i = first + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bgqk,bkd->bqgd", p, v)


@functools.partial(jax.jit, static_argnames=("norm_eps", "top_k", "renorm"))
def _router(x, mlp_norm, router, *, norm_eps, top_k, renorm):
    """The FFN's input ``h`` [N, D], the chosen experts and their gates
    [N, K], and the mean probability of each expert [E]."""
    h = _rmsnorm(x, mlp_norm.astype(F32), norm_eps).reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(h @ router.astype(F32), axis=-1)     # [N, E]
    gates, chosen = jax.lax.top_k(probs, top_k)                 # [N, K]
    if renorm:
        gates = gates / gates.sum(-1, keepdims=True)
    return h, chosen, gates, probs.mean(0)


@jax.jit
def _expert(h, w_gate, w_up, w_down, mine, gates):
    """One expert on every token, weighted by the gate of the tokens
    that chose it (``mine`` [N, K] masks their choices), by 0 for the
    others."""
    weight = jnp.sum(jnp.where(mine, gates, 0.0), axis=-1)
    out = (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))
           ) @ w_down.astype(F32)
    return weight[:, None] * out


def layer(x, lp, sizes, i, without=()):
    """Layer ``i`` on ``x`` [B, T, D] in float32; ``lp`` its parameters.
    Returns the new ``x``, the layer's load-balancing term and the
    experts each token chose [B*T, K]."""
    kind = sizes["layer_types"][i]
    freqs, m = rotary_table(sizes["rotary"][kind], sizes["head_dim"],
                            without)
    q, k, v = _qkv(x, lp, jnp.asarray(freqs, F32), m=float(m), **{
        k_: sizes[k_] for k_ in ("n_heads", "n_kv_heads", "head_dim",
                                 "norm_eps")})
    g = sizes["n_heads"] // sizes["n_kv_heads"]
    w = (sizes["window"] if kind == "sliding" and "window" not in without
         else None)
    # a group of heads and a block of queries at a time: the scores are
    # then [B, G, 1024, T] and not [B, H, T, T], and a backward keeps
    # the block's arguments and makes its scores again
    attend = jax.checkpoint(functools.partial(_attend_group, window=w))
    a = jnp.concatenate([jnp.concatenate(
        [attend(q[:, t:t + _Q_BLOCK, h * g:(h + 1) * g], k[:, :, h],
                v[:, :, h], t)
         for t in range(0, x.shape[1], _Q_BLOCK)], axis=1)
        for h in range(sizes["n_kv_heads"])], axis=2)
    x = x + a.reshape(*x.shape[:2], -1) @ lp["wo"].astype(F32)

    moe = lp["moe"]
    h, chosen, gates, mean_prob = _router(
        x, lp["mlp_norm"], moe["router"], norm_eps=sizes["norm_eps"],
        top_k=sizes["top_k"], renorm="renorm" not in without)
    y = jnp.zeros_like(h)
    for e in range(sizes["experts_held"]):
        y = y + jax.checkpoint(_expert)(
            h, moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e],
            chosen == sizes["expert_offset"] + e, gates)
    share = jnp.stack([jnp.mean((chosen == e).astype(F32))
                       for e in range(sizes["n_experts"])])
    balance = sizes["n_experts"] * jnp.sum(share * mean_prob)
    return x + y.reshape(x.shape), balance, chosen


def _stored(tree, store):
    """``tree`` with its floats rounded to ``store`` on the way (None:
    as they are)."""
    if store is None:
        return tree
    return jax.tree.map(
        lambda a: a.astype(store).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def _layer_stored(x, lp, sizes, i, store, without):
    """Layer ``i`` with its weights and its output stored as ``store``:
    the new ``x`` and the layer's load-balancing term."""
    x, balance, _ = layer(x, _stored(lp, store), sizes, i, without)
    return _stored(x, store), balance


def _head(x, final_norm, lm_head, rows, sizes, store):
    """Mean next-token cross-entropy over the slice, and the float32
    logits [B, T, V]."""
    x = _rmsnorm(x, _stored(final_norm, store).astype(F32),
                 sizes["norm_eps"])
    logits = x @ _stored(lm_head, store).astype(F32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, rows[:, 1:, None], axis=-1).mean(), \
        logits


def loss_terms(params, rows, sizes, *, store=None, without=()
               ) -> Dict[str, Any]:
    """The two terms of the training loss on ``rows`` [B, T+1], before
    the coefficient, ``loss``, their weighted sum, and the float32
    ``logits`` [B, T, V] over this chip's slice of the vocabulary.
    ``store``: a dtype the weights and the residual stream are rounded
    to on the way (None: as they are)."""
    rows = jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        x = _stored(params["embed"], store)[rows[:, :-1]].astype(F32)
        balance = jnp.zeros((), F32)
        for i in range(sizes["n_layers"]):
            x, b_i = _layer_stored(x, params["layers"][i], sizes, i, store,
                                   without)
            balance = balance + b_i
        ce, logits = _head(x, params["final_norm"], params["lm_head"], rows,
                           sizes, store)
    aux = 0.0 if "aux" in without else sizes["aux_coef"]
    return {"cross_entropy": ce, "load_balance": balance,
            "loss": ce + aux * balance, "logits": logits}


def loss(params, rows, sizes, **how):
    """The training loss of ``rows`` [B, T+1]; ``jax.grad`` of this is
    the reference's gradient."""
    return loss_terms(params, rows, sizes, **how)["loss"]


def gradient_by_layer(params, rows, sizes, *, store=None, without=()):
    """``jax.grad`` of :func:`loss`, a layer at a time: yields ``(path,
    gradient)`` for every leaf of ``params``, in float32, the head
    first, then the layers from the last to the first, the embedding
    last; ``path`` is the leaf's keys in ``params``, as a tuple. The
    forward keeps each layer's input, and the backward takes one
    layer's parameters up to float32 at a time, so that what is held at
    once is one layer and not the stack: the caller takes each gradient
    off the device before it asks for the next."""
    rows = jnp.asarray(rows)
    aux = 0.0 if "aux" in without else sizes["aux_coef"]

    def up(tree):
        return jax.tree.map(lambda a: a.astype(F32), tree)

    with jax.default_matmul_precision("highest"):
        x, back_embed = jax.vjp(
            lambda embed: _stored(embed, store)[rows[:, :-1]],
            up(params["embed"]))
        xs = [x]
        for i in range(sizes["n_layers"]):
            xs.append(_layer_stored(xs[-1], params["layers"][i], sizes, i,
                                    store, without)[0])
        back = jax.vjp(
            lambda x, norm, head: _head(x, norm, head, rows, sizes, store),
            xs.pop(), up(params["final_norm"]), up(params["lm_head"]),
            has_aux=True)[1]
        dx, d_norm, d_head = back(jnp.ones((), F32))
        del back
        yield ("final_norm",), d_norm
        yield ("lm_head",), d_head
        del d_norm, d_head
        for i in reversed(range(sizes["n_layers"])):
            back = jax.vjp(
                lambda x, lp, i=i: _layer_stored(x, lp, sizes, i, store,
                                                 without),
                xs.pop(), up(params["layers"][i]))[1]
            dx, d_layer = back((dx, jnp.asarray(aux, F32)))
            del back
            leaves, _ = jax.tree_util.tree_flatten_with_path(d_layer)
            del d_layer
            while leaves:
                path, leaf = leaves.pop()
                yield ("layers", i) + tuple(k.key for k in path), leaf
                del leaf
        yield ("embed",), back_embed(dx)[0]
