"""The plain reference of the Motif-3 (``model_type: "Motif"``) decoder:
what ``correct`` is decided against for a served model whose every
layer is grouped differential attention over a latent cache (GDLA), in
window and full layers, on a four-stream mHC residual, with PolyNorm
feed-forwards: a dense one, or one chip's share of a mixture's experts.
One copy lives beside the benchmark (``benchmark/reference_motif3.py``)
and one beside the tier-1 tests (``tests/reference_motif3.py``); a test
holds the two identical below this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, the
attention EXPANDED (every KV head's keys and values made from the
latents of every position; each query over all keys under the mask; no
absorbed form, no ring, no pages), the held experts as a loop (each
over every row, weighed by a gate that is zero where the row did not
choose it); no kernel, no cache, no batching, and no import from the
program. It takes the program's parameter tree and a plain dict of
sizes, works on a block of positions, a group of heads or one expert at
a time, and upcasts one matrix at a time, so that it fits on the chip
beside the engine.

The layer, as this repository reads ``config.json`` (``D`` the width,
``n`` streams). Every line marked ASSUMED is one the config has no key
for; each is listed with its reason under ``assumed`` in
``benchmark/configs/motif-3-beta-ep8-5l.json``.

* **The stream (mHC, arXiv:2512.24880)** is ``X`` [n, D] a position:
  the embedding copied to the n streams at the bottom (ASSUMED); their
  sum, the final RMSNorm and the head at the top (ASSUMED). Around each
  of a layer's two branches ``F`` (attention, feed-forward), with
  ``x~ = vec X / rms(vec X)`` (no gain; ASSUMED):

      H_pre  = sigmoid(a_pre x~ W_pre + b_pre)                    [n]
      H_post = 2 sigmoid(a_post x~ W_post + b_post)               [n]
      H_res  = SinkhornKnopp_iters(exp(a_res reshape(x~ W_res) + b_res))
      u = H_pre X;  y = F(RMSNorm(u));  X' = H_res X + H_post^T y

  SinkhornKnopp: ``iters`` alternations of dividing every row by its
  sum and then every column by its sum (ASSUMED unmodified:
  ``described_as`` says "modified mHC" and not how).
* **GDLA** (``H`` query heads, ``G`` KV heads, ``H / G - 1`` signal
  heads and 1 noise head a group): ``c_q = RMSNorm(h W_dq)``, ``q = c_q
  W_uq`` as ``H`` heads of ``[Dh | R]``; ``[c | r] = h W_dkv``, a
  position keeps ``[RMSNorm(c) | RoPE(r)]``; ``[k_g | v_g] = c W_ukv``
  for each KV head ``g``, ``k = [k_g | r]``; interleaved-pair RoPE at
  ``theta`` on the ``R`` values of q and k, in every layer alike
  (ASSUMED: ``apply_yarn_scaling`` false means no interpolation and no
  softmax ``mscale``); scores times ``(Dh + R)^-1/2``; causal, and in a
  window layer also ``key_pos > pos - window``. KV head ``g`` serves
  the query heads ``g H/G .. g H/G + H/G - 1``, the last of them the
  noise head (ASSUMED: the order). ``A_j = softmax(.) v_g``; ``o_s =
  A_s - sigmoid(h W_lambda)_s A_noise(g)`` for the signal heads
  (ASSUMED: Differential Transformer V2, lambda a token and head, no
  norm after the subtraction); ``o <- o * sigmoid(h W_gate)``
  elementwise; ``y = o W_o`` (ASSUMED: the signal heads alone).
* **PolyNorm** ``P(z) = s (w1 N(z^3) + w2 N(z^2) + w3 N(z) + clip(b,
  +-clamp))``, ``N(t) = t / sqrt(mean_width(t^2) + eps)`` (ASSUMED:
  where ``s`` and the clamp apply; three weights and a bias a
  feed-forward, an expert its own). Feed-forward: ``(P(h W_gate) * (h
  W_up)) W_down``.
* **Mixture**: ``s = sigmoid(h W_r)`` over all E outputs in float32
  (plus a selection bias that chooses and never weighs: zeros as
  seeded), the ``top_k`` largest, their scores over their sum times
  ``route_scale``, on the experts' OUTPUTS; ``sum_e g_e expert_e(h)``
  over the experts this chip HOLDS (``[expert_offset, expert_offset +
  experts_held)``: a pair routed elsewhere adds nothing) plus the
  shared expert.

Left out, here as in the program: the prediction module
(``num_nextn_predict_layers``), which would lie on the last stage.

``store`` and ``wrong`` exist for ``benchmark/tools/motif3_tolerance.py``
and ``tests/test_motif3.py``, which show what the check refuses: the
same reference with weights and the stream stored in a narrower float,
or with one mechanism miscomputed (a name of ``WRONG``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: Positions a block of the stream or of a feed-forward's rows holds,
#: and queries a block of the attention (its float32 scores are ``[heads
#: of a group, _Q_BLOCK, T]``: 170 MB at 5 x 512 x 16 896).
_BLOCK = 1024
_Q_BLOCK = 512

#: What ``wrong`` may name, each one mechanism miscomputed.
WRONG = (
    "lambda_0",             # the noise heads unused: o_s = A_s
    "noise_other_group",    # the noise head of the NEXT KV group subtracted
    "no_window",            # a window layer sees every key before it
    "window_on_full",       # the full layer masked to the window too
    "sinkhorn_1",           # one alternation for the published count
    "post_without_2",       # H_post = sigmoid(.), the 2 left out
    "mappings_in_bf16",     # x~, W and the three mappings in bfloat16
    "silu_for_polynorm",    # SiLU where PolyNorm stands
    "polynorm_no_cubic",    # PolyNorm without its z^3 term
    "no_gate",              # the elementwise output gate left out
    "route_scale_1",        # route_scale left out
    "top_6",                # 6 experts a token for the published count
    "no_shared",            # the shared expert left out
    "latent_in_f8",         # what a position keeps rounded to float8_e4m3fn
    "rope_halves",          # rotation in halves, not interleaved pairs
)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    held = m.get("moe_experts_held")
    rotary = (m.get("layer_rotary") or {}).get("mla") or {}
    return {"n_layers": m["n_layers"], "layer_types": tuple(m["layer_types"]),
            "n_dense_layers": m.get("n_dense_layers", 0),
            "d_model": m["d_model"], "norm_eps": m["norm_eps"],
            "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
            "head_dim": m["d_head"], "window": m["attn_window"],
            "mla_kv_rank": m["mla_kv_rank"],
            "mla_rope_dim": m["mla_rope_dim"],
            "theta": rotary.get("theta", m.get("rope_theta", 500000.0)),
            "streams": m["mhc_streams"],
            "sinkhorn_iters": m["mhc_sinkhorn_iters"],
            "polynorm_scale": m["polynorm_scale"],
            "polynorm_bias_clamp": m["polynorm_bias_clamp"],
            "n_experts": m["n_experts"], "top_k": m["moe_top_k"],
            "route_scale": m["moe_route_scale"],
            "experts_held": m["n_experts"] if held is None else held,
            "expert_offset": m.get("moe_expert_offset", 0)}


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _in_blocks(x):
    """``x`` in blocks of ``_BLOCK`` of its leading positions."""
    return [x[t:t + _BLOCK] for t in range(0, x.shape[0], _BLOCK)]


def _rope(x, first, theta, halves=False):
    """x [T, H, R] at positions ``first + 0..``; pairs (2i, 2i+1), or
    with ``halves`` (i, i + R/2)."""
    t, d = x.shape[0], x.shape[-1]
    freq = theta ** -(jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (first + jnp.arange(t, dtype=F32))[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if halves:
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


# -- the stream ------------------------------------------------------

def sinkhorn_knopp(m, iters: int):
    """``iters`` alternations on the positive ``m`` [.., n, n]: every
    row over its sum, then every column over its sum."""
    for _ in range(iters):
        m = m / m.sum(-1, keepdims=True)
        m = m / m.sum(-2, keepdims=True)
    return m


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "wrong"))
def mappings(x, p, *, n, iters, eps, wrong=None):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of the stream
    ``x`` [T, n, D] through one branch's ``p`` (``w`` [n D, n n + 2 n]
    as ``[W_pre | W_post | W_res]``, ``b`` alike, ``alpha`` [3])."""
    flat = x.reshape(x.shape[0], -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + eps)
    w, b, a = p["w"].astype(F32), p["b"].astype(F32), p["alpha"].astype(F32)
    if wrong == "mappings_in_bf16":
        flat, w = (v.astype(jnp.bfloat16).astype(F32) for v in (flat, w))
    z = flat @ w

    def rounded(v):
        return (v.astype(jnp.bfloat16).astype(F32)
                if wrong == "mappings_in_bf16" else v)

    z = rounded(z)
    pre = rounded(jax.nn.sigmoid(a[0] * z[:, :n] + b[:n]))
    post = rounded((1.0 if wrong == "post_without_2" else 2.0)
                   * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n]))
    res = sinkhorn_knopp(
        jnp.exp(a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n),
        1 if wrong == "sinkhorn_1" else iters)
    return pre, post, rounded(res)


@jax.jit
def _read(x, pre):
    return jnp.einsum("tn,tnd->td", pre, x)


@jax.jit
def _mix(x, y, post, res):
    return (jnp.einsum("tmn,tnd->tmd", res, x) + post[:, :, None]
            * y[:, None, :])


# -- attention -------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "n_heads", "head_dim", "rope_dim", "norm_eps", "theta", "halves"))
def _queries(h, lp, first, *, n_heads, head_dim, rope_dim, norm_eps, theta,
             halves):
    """q without position [T, H, Dh] and rotated [T, H, R], of a block
    of positions ``first + 0..``."""
    cq = _rmsnorm(h @ lp["w_dq"].astype(F32), lp["dq_norm"].astype(F32),
                  norm_eps)
    q = (cq @ lp["w_uq"].astype(F32)).reshape(h.shape[0], n_heads,
                                              head_dim + rope_dim)
    return q[..., :head_dim], _rope(q[..., head_dim:], first, theta, halves)


@functools.partial(jax.jit, static_argnames=(
    "n_kv_heads", "head_dim", "rank", "norm_eps", "theta", "halves", "f8"))
def _keys(h, lp, *, n_kv_heads, head_dim, rank, norm_eps, theta, halves, f8):
    """Every KV head's keys and values [T, G, Dh] and the rotated part
    every head shares [T, R], from what a position keeps."""
    cr = h @ lp["w_dkv"].astype(F32)
    c = _rmsnorm(cr[:, :rank], lp["kv_norm"].astype(F32), norm_eps)
    r = _rope(cr[:, None, rank:], 0, theta, halves)[:, 0]
    if f8:
        c, r = (v.astype(jnp.float8_e4m3fn).astype(F32) for v in (c, r))
    kv = (c @ lp["w_ukv"].astype(F32)).reshape(h.shape[0], n_kv_heads, 2,
                                               head_dim)
    return kv[:, :, 0], kv[:, :, 1], r


@functools.partial(jax.jit, static_argnames=("window",))
def _attend(qn, qr, k, v, r, first, scale, *, window):
    """One KV head's group of query heads, a block of queries at
    positions ``first + 0..``, over ALL keys under the mask: qn [Tq, S,
    Dh], qr [Tq, S, R], k and v [T, Dh], r [T, R]. Returns [Tq, S,
    Dh]."""
    s = (jnp.einsum("qsd,kd->sqk", qn, k) + jnp.einsum("qsr,kr->sqk", qr, r)
         ) * scale
    i = first + jnp.arange(qn.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("sqk,kd->qsd", p, v)


@functools.partial(jax.jit, static_argnames=("groups", "wrong"))
def _attention_out(h, a, lp, *, groups, wrong):
    """The heads' results ``a`` [T, H, Dh] to the branch's output: the
    differential subtraction, the elementwise gate, ``W_o``."""
    t, n_heads, d = a.shape
    a = a.reshape(t, groups, n_heads // groups, d)
    signal, noise = a[:, :, :-1], a[:, :, -1:]
    if wrong == "noise_other_group":
        noise = jnp.roll(noise, -1, axis=1)
    lam = jax.nn.sigmoid(h @ lp["w_lambda"].astype(F32)).reshape(
        t, groups, -1, 1)
    if wrong == "lambda_0":
        lam = 0.0 * lam
    o = (signal - lam * noise).reshape(t, -1)
    if wrong != "no_gate":
        o = o * jax.nn.sigmoid(h @ lp["wg"].astype(F32))
    return o @ lp["wo"].astype(F32)


def attention_keys(h, lp, sizes, wrong=None):
    """What the GDLA branch keeps of EVERY position of the normed ``h``
    [T, D]: each KV head's keys and values and the shared rotated part
    (:func:`_keys`)."""
    return _keys(h, lp, n_kv_heads=sizes["n_kv_heads"],
                 head_dim=sizes["head_dim"], rank=sizes["mla_kv_rank"],
                 norm_eps=sizes["norm_eps"], theta=sizes["theta"],
                 halves=wrong == "rope_halves", f8=wrong == "latent_in_f8")


def attention(hb, first, keys, lp, sizes, kind: str, wrong=None):
    """The GDLA branch for the queries of a block ``hb`` [Tb, D] of the
    normed input at positions ``first + 0..``, over ``keys``
    (:func:`attention_keys` of all positions), in a layer of ``kind``
    ("mla": full, "mla_sliding": window)."""
    k, v, r = keys
    G, H = sizes["n_kv_heads"], sizes["n_heads"]
    window = sizes["window"] if kind == "mla_sliding" else None
    if wrong == "no_window":
        window = None
    if wrong == "window_on_full":
        window = sizes["window"]
    scale = (sizes["head_dim"] + sizes["mla_rope_dim"]) ** -0.5
    per = H // G
    outs = []
    for at in range(0, hb.shape[0], _Q_BLOCK):
        hq = hb[at:at + _Q_BLOCK]
        qn, qr = _queries(hq, lp, first + at, n_heads=H,
                          head_dim=sizes["head_dim"],
                          rope_dim=sizes["mla_rope_dim"],
                          norm_eps=sizes["norm_eps"], theta=sizes["theta"],
                          halves=wrong == "rope_halves")
        a = jnp.concatenate(
            [_attend(qn[:, g * per:(g + 1) * per],
                     qr[:, g * per:(g + 1) * per], k[:, g], v[:, g], r,
                     first + at, scale, window=window) for g in range(G)], 1)
        outs.append(_attention_out(hq, a, lp, groups=G, wrong=wrong))
        outs[-1].block_until_ready()
    return jnp.concatenate(outs, 0)


# -- the feed-forward blocks ----------------------------------------

def polynorm(z, w, b, *, scale, clamp, eps, wrong=None):
    """``P(z)`` over the last dimension of ``z``; ``w`` [3], ``b`` []."""
    if wrong == "silu_for_polynorm":
        return jax.nn.silu(z)

    def normed(t):
        return t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + eps)

    cubic = 0.0 if wrong == "polynorm_no_cubic" else w[0] * normed(z ** 3)
    return scale * (cubic + w[1] * normed(z ** 2) + w[2] * normed(z)
                    + jnp.clip(b, -clamp, clamp))


@functools.partial(jax.jit, static_argnames=("scale", "clamp", "eps",
                                              "wrong"))
def _gated(h, w_gate, w_up, w_down, pw, pb, *, scale, clamp, eps, wrong):
    g = polynorm(h @ w_gate.astype(F32), pw.astype(F32), pb.astype(F32),
                 scale=scale, clamp=clamp, eps=eps, wrong=wrong)
    return (g * (h @ w_up.astype(F32))) @ w_down.astype(F32)


def _poly(sizes, wrong):
    return {"scale": sizes["polynorm_scale"],
            "clamp": sizes["polynorm_bias_clamp"], "eps": sizes["norm_eps"],
            "wrong": wrong if wrong in ("silu_for_polynorm",
                                        "polynorm_no_cubic") else None}


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale"))
def gates(h, router, bias, *, top_k, route_scale):
    """The chosen experts [T, K] and their weights [T, K]."""
    s = jax.nn.sigmoid(h @ router.astype(F32))               # [T, E]
    _, chosen = jax.lax.top_k(s + bias.astype(F32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / w.sum(-1, keepdims=True) * route_scale


def mixture(h, mp, sizes, wrong=None, shared: bool = True):
    """The sparse feed-forward on ``h`` [T, D]: the weighted sum of the
    chosen experts that this chip holds, plus (``shared``) the shared
    expert."""
    chosen, weights = gates(
        h, mp["router"], mp["router_bias"],
        top_k=6 if wrong == "top_6" else sizes["top_k"],
        route_scale=1.0 if wrong == "route_scale_1" else sizes["route_scale"])
    poly = _poly(sizes, wrong)
    y = jnp.zeros_like(h)
    if shared and wrong != "no_shared":
        y = _gated(h, mp["shared_gate"], mp["shared_up"], mp["shared_down"],
                   mp["shared_poly_w"], mp["shared_poly_b"], **poly)
    for e in range(sizes["experts_held"]):
        mine = chosen == sizes["expert_offset"] + e
        y = y + (jnp.sum(jnp.where(mine, weights, 0.0), -1)[:, None]
                 * _gated(h, mp["w_gate"][e], mp["w_up"][e], mp["w_down"][e],
                          mp["poly_w"][e], mp["poly_b"][e], **poly))
        # one expert at a time in earnest: a loop that runs ahead of the
        # device holds every expert's result at once
        y.block_until_ready()
    return y


def feed_forward(h, lp, sizes, wrong=None):
    """The layer's feed-forward branch on the normed ``h`` [T, D]."""
    if "moe" in lp:
        return mixture(h, lp["moe"], sizes, wrong)
    return _gated(h, lp["w_gate"], lp["w_up"], lp["w_down"], lp["poly_w"],
                  lp["poly_b"], **_poly(sizes, wrong))


# -- the layer and the model ----------------------------------------

def _branch(xs, lp, sizes, which: str, kind: str, wrong=None):
    """One branch around the stream ``xs``, a LIST of blocks [<= _BLOCK,
    n, D] of its positions: ``which`` "attn" (a layer of ``kind``) or
    "mlp". A block at a time, each dropped from the list as its
    successor is made, so that the stream is held once; the attention
    first makes what it keeps of every position."""
    how = {"n": sizes["streams"], "iters": sizes["sinkhorn_iters"],
           "eps": sizes["norm_eps"], "wrong": wrong if wrong in (
               "sinkhorn_1", "post_without_2", "mappings_in_bf16") else None}
    maps = [mappings(xb, lp[f"mhc_{which}"], **how) for xb in xs]

    def normed(i):
        return _rmsnorm(_read(xs[i], maps[i][0]),
                        lp[f"{which}_norm"].astype(F32), sizes["norm_eps"])

    if which == "attn":
        keys = attention_keys(
            jnp.concatenate([normed(i) for i in range(len(xs))], 0), lp,
            sizes, wrong)
    out, first = [], 0
    for i, (_, post, res) in enumerate(maps):
        h = normed(i)
        y = (attention(h, first, keys, lp, sizes, kind, wrong)
             if which == "attn" else feed_forward(h, lp, sizes, wrong))
        out.append(_mix(xs[i], y, post, res))
        # in earnest: a loop that runs ahead of the device holds the old
        # stream and the new at once
        out[-1].block_until_ready()
        first += xs[i].shape[0]
        xs[i] = None
    return out


def branch(x, lp, sizes, which: str, kind: str = "mla", wrong=None):
    """:func:`_branch` on the stream as one array ``x`` [T, n, D]."""
    return jnp.concatenate(_branch(_in_blocks(x), lp, sizes, which, kind,
                                   wrong), 0)


def layer(xs, lp, sizes, i: int, wrong=None):
    """Layer ``i`` on the stream ``xs`` (a list of blocks, see
    :func:`_branch`) in float32."""
    kind = sizes["layer_types"][i]
    return _branch(_branch(xs, lp, sizes, "attn", kind, wrong), lp, sizes,
                   "mlp", kind, wrong)


def layer_params(params, sizes, i):
    """Layer ``i``'s parameters out of the two lists of layers."""
    n_dense = sizes["n_dense_layers"]
    return (params["dense_layers"][i] if i < n_dense
            else params["layers"][i - n_dense])


def logits(params, tokens, sizes, last: int = 0, *, store=None, wrong=None):
    """Float32 logits of ONE sequence ``tokens`` [T], over this chip's
    slice of the vocabulary: every position [T, V], or only the last
    ``last`` positions. ``store``: a dtype the weights and the stream
    are rounded to on the way (None: as they are). ``wrong``: a name of
    ``WRONG``."""
    assert wrong is None or wrong in WRONG, wrong

    def stored(tree):
        if store is None:
            return tree
        return jax.tree.map(
            lambda a: a.astype(store).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    with jax.default_matmul_precision("highest"):
        x = stored(params["embed"][jnp.asarray(tokens)]).astype(F32)
        xs = [jnp.broadcast_to(xb[:, None], (xb.shape[0], sizes["streams"],
                                             xb.shape[1]))
              for xb in _in_blocks(x)]
        for i in range(sizes["n_layers"]):
            xs = stored(layer(xs, stored(layer_params(params, sizes, i)),
                              sizes, i, wrong))
        x = jnp.concatenate([xb.sum(1) for xb in xs], 0)[-last:]
        x = _rmsnorm(x, stored(params["final_norm"]).astype(F32),
                     sizes["norm_eps"])
        return x @ stored(params["lm_head"]).astype(F32)
