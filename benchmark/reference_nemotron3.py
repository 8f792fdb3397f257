"""The plain reference of the NVIDIA-Nemotron-3-Super (``nemotron_h``)
decoder: what ``correct`` is decided against for a served model whose
layers are ONE branch each: a Mamba-2 (SSD) mixer, a grouped-query
attention or a LatentMoE feed-forward, the last as one chip's share of
its experts. One copy lives beside the benchmark
(``benchmark/reference_nemotron3.py``) and one beside the tier-1 tests
(``tests/reference_nemotron3.py``); a test holds the two identical below
this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, the
Mamba-2 layer as the literal recurrence a position at a time
(``lax.scan`` over positions: no chunks, no products over a chunk's
decays), attention over the whole sequence with its key-value heads
repeated to every query head, the experts as a loop over the HELD ones
in blocks (each expert over every row, weighed by a gate that is zero
where the row did not choose it); no kernel, no cache, no batching, and
no import from the program. It takes the program's parameter tree and a
plain dict of sizes, and upcasts one layer's matrices (one block of
experts) at a time, so that it fits on the chip beside the engine.

The layers, as this repository reads ``config.json``. ``x`` [T, D]; a
layer is ``x += Branch(N(x))``, ``N(x) = x / sqrt(mean(x^2) + eps) g``,
no bias anywhere but the convolution's. Every line marked ASSUMED is
one the config has no key for; each is listed with its reason under
``assumed`` in ``benchmark/configs/nemotron-3-super-120b-ep4-11l.json``.

* **mamba2** (``Hm`` heads of ``P`` values, ``Di = Hm P`` channels,
  ``N`` state columns, ``G`` groups): ``[z | xBC | dt] = h W_in`` as
  ``Di | Di + 2 G N | Hm``; ``xBC_t = SiLU(b + sum_j w_j xBC_{t - (taps
  - 1) + j})``, depthwise and causal, zeros before the sequence's
  start; split ``x`` [Hm, P], ``B`` and ``C`` [G, N], head ``h`` reads
  group ``h // (Hm / G)``; ``Delta = softplus(dt + dt_bias)`` [Hm];
  ``A = -exp(A_log)``, ONE scalar a head; from ``S_0 = 0`` in float32

      S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t (x) B_t     [Hm, P, N]
      y_t = S_t C_t + D x_t

  ``y <- RMSNorm_g(y SiLU(z)) w``, the statistics over each of the G
  groups of ``Di / G`` channels, the gate BEFORE the norm (ASSUMED:
  Mamba-2's ``norm_before_gate`` false); ``x + y W_out``.
* **full**: q of ``n_heads`` heads, k and v of ``n_kv_heads``, scores
  ``/ sqrt(Dh)``, causal over everything, NO rotary or other positional
  embedding (ASSUMED: the family's attention has none); ``x + o W_o``.
* **ffn** (LatentMoE): ``s = sigmoid(h W_r)`` over all E outputs of the
  full-width ``h`` in float32 (ASSUMED), a selection bias [E] added to
  choose and never to weigh (ASSUMED), the ``top_k`` largest chosen,
  their scores normalised to sum 1 and times ``route_scale``; ``z = h
  W_down_latent``; expert e ``relu(z W1_e)^2 W2_e`` (no gate matrix);
  ``r = sum_e g_e expert_e(z)`` over the experts this chip HOLDS
  (``[expert_offset, expert_offset + experts_held)``: a pair routed to
  an absent expert adds nothing); ``r W_up_latent + relu(h Ws1)^2
  Ws2``, the shared expert on the full-width ``h``.
* after the last layer ``N``, then the head ``x W_head`` (untied), over
  this chip's slice of the vocabulary.

Departure from the published layout, none from the mathematics: the
columns of ``in_proj`` lie in two matrices, ``w_in`` (``z | xBC``) and
``w_dt`` (``dt``), as the program's parameters hold them.

Left out, here as in the program: the multi-token-prediction module
(it drafts and changes no served token).

``store`` and ``wrong`` exist for
``benchmark/tools/nemotron3_tolerance.py`` and
``tests/test_nemotron3.py``, which show what the check refuses: the
same reference with weights and the residual stream stored in a
narrower float, or with one mechanism miscomputed (a name of ``WRONG``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: Experts upcast and run at a time (a block of ``[8, 1024, 2688]``
#: float32 pairs is 176 MB at the published widths).
_EXPERT_BLOCK = 8

#: What ``wrong`` may name, each one mechanism miscomputed.
WRONG = (
    "state_in_bf16",        # the recurrence's state rounded to bf16 a position
    "no_d_skip",            # D x left out
    "no_conv_bias",         # the convolution's bias left out
    "norm_whole",           # the gated norm over all Di channels, not by group
    "gate_after_norm",      # RMSNorm_g(y) w SiLU(z): the gate after the norm
    "relu_not_relu2",       # relu for relu^2, routed and shared experts
    "route_scale_1",        # routed_scaling_factor left out
    "top_8",                # 8 experts a token for the published count
    "no_shared",            # the shared expert left out
    "rope_on_attention",    # q and k of the attention layers rotated
)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    d_inner = m["mamba_expand"] * m["d_model"]
    held = m.get("moe_experts_held")
    return {"n_layers": m["n_layers"], "layer_types": tuple(m["layer_types"]),
            "d_model": m["d_model"], "norm_eps": m["norm_eps"],
            "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
            "head_dim": m.get("d_head") or m["d_model"] // m["n_heads"],
            "mamba_heads": d_inner // m["mamba2_head_dim"],
            "mamba_head_dim": m["mamba2_head_dim"],
            "mamba_d_state": m["mamba_d_state"],
            "mamba_d_conv": m["mamba_d_conv"],
            "mamba_groups": m["mamba2_groups"],
            "n_experts": m["n_experts"], "top_k": m["moe_top_k"],
            "route_scale": m["moe_route_scale"],
            "experts_held": m["n_experts"] if held is None else held,
            "expert_offset": m.get("moe_expert_offset", 0)}


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
    "norm_eps", "heads", "head_dim", "n_state", "groups", "taps", "wrong"))
def mamba2(x, lp, *, norm_eps, heads, head_dim, n_state, groups, taps,
           wrong=None, state=None):
    """The Mamba-2 branch on ``x`` [T, D], residual included, from
    ``state`` [Hm, P, N] (None: zeros), and the state after the last
    position."""
    t = x.shape[0]
    d_inner, gn = heads * head_dim, groups * n_state
    h = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    zx = h @ lp["w_in"].astype(F32)
    z, xbc = zx[:, :d_inner], zx[:, d_inner:]
    dt = h @ lp["w_dt"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[j:j + t] * lp["conv_w"][j].astype(F32)
               for j in range(taps))
    if wrong != "no_conv_bias":
        conv = conv + lp["conv_b"].astype(F32)
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(t, heads, head_dim)
    # head h reads group h // (heads / groups)
    b = jnp.repeat(xbc[:, d_inner:d_inner + gn].reshape(t, groups, n_state),
                   heads // groups, axis=1)
    c = jnp.repeat(xbc[:, d_inner + gn:].reshape(t, groups, n_state),
                   heads // groups, axis=1)
    step = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))       # [T, Hm]
    a = -jnp.exp(lp["a_log"].astype(F32))                        # [Hm]

    def position(s, row):
        step_t, x_t, b_t, c_t = row
        s = (jnp.exp(step_t * a)[:, None, None] * s
             + (step_t[:, None] * x_t)[..., None] * b_t[:, None, :])
        if wrong == "state_in_bf16":
            # (not a cast there and back, which a compiler that keeps
            # excess precision takes out)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    if state is None:
        state = jnp.zeros((heads, head_dim, n_state), F32)
    state, y = jax.lax.scan(position, state, (step, xs, b, c))
    if wrong != "no_d_skip":
        y = y + lp["d_skip"].astype(F32)[:, None] * xs
    y, gate = y.reshape(t, d_inner), jax.nn.silu(z)
    w = lp["o_norm"].astype(F32)

    def normed(v):
        if wrong == "norm_whole":
            return _rmsnorm(v, w, norm_eps)
        by_group = v.reshape(t, groups, d_inner // groups)
        return _rmsnorm(by_group, 1.0, norm_eps).reshape(t, d_inner) * w

    y = normed(y) * gate if wrong == "gate_after_norm" else normed(y * gate)
    return x + y @ lp["w_out"].astype(F32), state


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv_heads", "head_dim", "norm_eps", "wrong"))
def attention(x, lp, *, n_heads, n_kv_heads, head_dim, norm_eps, wrong=None):
    """The attention branch on ``x`` [T, D], residual included."""
    t = x.shape[0]
    h = _rmsnorm(x, lp["attn_norm"].astype(F32), norm_eps)
    q = (h @ lp["wq"].astype(F32)).reshape(t, n_heads, head_dim)
    k = (h @ lp["wk"].astype(F32)).reshape(t, n_kv_heads, head_dim)
    v = (h @ lp["wv"].astype(F32)).reshape(t, n_kv_heads, head_dim)
    if wrong == "rope_on_attention":
        q, k = _rope(q), _rope(k)          # ASSUMED absent: no rotary here
    k = jnp.repeat(k, n_heads // n_kv_heads, axis=1)
    v = jnp.repeat(v, n_heads // n_kv_heads, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * head_dim ** -0.5
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v).reshape(t, n_heads * head_dim)
    return x + o @ lp["wo"].astype(F32)


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale"))
def gates(h, router, bias, *, top_k, route_scale):
    """The router's weights as one dense matrix [T, E]: a row's chosen
    experts hold their weight, every other expert 0. Sigmoid scores in
    float32 (ASSUMED) of the full-width ``h``; the bias chooses and
    never weighs (ASSUMED); no groups (``n_group`` 1)."""
    scores = jax.nn.sigmoid(h @ router.astype(F32))
    _, chosen = jax.lax.top_k(scores + bias.astype(F32), top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / weight.sum(-1, keepdims=True) * route_scale
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(weight)


@functools.partial(jax.jit, static_argnames=("n", "wrong"))
def _expert_block(z, w_up, w_down, gate, first, *, n, wrong):
    """``sum_e gate_e expert_e(z)`` over the ``n`` held experts from
    ``first``: each over every row, float32."""
    up = jax.lax.dynamic_slice_in_dim(w_up, first, n).astype(F32)
    down = jax.lax.dynamic_slice_in_dim(w_down, first, n).astype(F32)
    g = jax.lax.dynamic_slice_in_dim(gate, first, n, axis=1)        # [T, n]
    a = jax.nn.relu(jnp.einsum("td,edf->etf", z, up))
    if wrong != "relu_not_relu2":
        a = a * a
    return jnp.einsum("etf,efd,te->td", a, down, g)


@functools.partial(jax.jit, static_argnames=("wrong",))
def _shared(h, up, down, *, wrong):
    a = jax.nn.relu(h @ up.astype(F32))
    if wrong != "relu_not_relu2":
        a = a * a
    return a @ down.astype(F32)


def latent_moe(x, lp, sizes, wrong=None, shared: bool = True):
    """The feed-forward branch on ``x`` [T, D], residual included: the
    held experts' part of the routed sum through the latent pair, and
    (``shared``) the shared expert."""
    moe = lp["moe"]
    h = _rmsnorm(x, lp["mlp_norm"].astype(F32), sizes["norm_eps"])
    gate = gates(
        h, moe["router"], moe["router_bias"],
        top_k=8 if wrong == "top_8" else sizes["top_k"],
        route_scale=1.0 if wrong == "route_scale_1" else sizes["route_scale"])
    held, offset = sizes["experts_held"], sizes["expert_offset"]
    gate = gate[:, offset:offset + held]
    z = h @ moe["latent_down"].astype(F32)
    routed = jnp.zeros_like(z)
    for first in range(0, held, _EXPERT_BLOCK):
        routed = routed + _expert_block(
            z, moe["w_up"], moe["w_down"], gate, first,
            n=min(_EXPERT_BLOCK, held - first), wrong=wrong)
    y = routed @ moe["latent_up"].astype(F32)
    if shared and wrong != "no_shared":
        y = y + _shared(h, moe["shared_up"], moe["shared_down"], wrong=wrong)
    return x + y


def layer(x, lp, sizes, i, wrong=None):
    """Layer ``i``, ONE branch, on ``x`` [T, D] in float32; ``lp`` its
    parameters. Returns the new ``x`` and, of a mamba2 layer, the state
    after the last position (None of another)."""
    kind = sizes["layer_types"][i]
    if kind == "mamba2":
        return mamba2(x, lp, norm_eps=sizes["norm_eps"],
                      heads=sizes["mamba_heads"],
                      head_dim=sizes["mamba_head_dim"],
                      n_state=sizes["mamba_d_state"],
                      groups=sizes["mamba_groups"],
                      taps=sizes["mamba_d_conv"], wrong=wrong)
    if kind == "full":
        return attention(
            x, lp, n_heads=sizes["n_heads"], n_kv_heads=sizes["n_kv_heads"],
            head_dim=sizes["head_dim"], norm_eps=sizes["norm_eps"],
            wrong=wrong), None
    assert kind == "ffn", kind
    return latent_moe(x, lp, sizes, wrong), None


def logits(params, tokens, sizes, last: int = 0, *, store=None, wrong=None,
           states: bool = False):
    """Float32 logits of ONE sequence ``tokens`` [T]: every position
    [T, V], or only the last ``last`` positions. ``store``: a dtype the
    weights and the residual stream are rounded to on the way (None: as
    they are). ``wrong``: a name of ``WRONG``. ``states``: also the
    mamba2 layers' states after the last position, [n_mamba2, Hm, P,
    N]."""
    assert wrong is None or wrong in WRONG, wrong

    def stored(tree):
        if store is None:
            return tree
        return jax.tree.map(
            lambda a: a.astype(store).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

    tokens = jnp.asarray(tokens)
    kept = []
    with jax.default_matmul_precision("highest"):
        x = stored(params["embed"])[tokens].astype(F32)
        for i in range(sizes["n_layers"]):
            x, state = layer(x, stored(params["layers"][i]), sizes, i, wrong)
            x = stored(x)
            if state is not None:
                kept.append(state)
        x = _rmsnorm(x[-last:], stored(params["final_norm"]).astype(F32),
                     sizes["norm_eps"])
        out = x @ stored(params["lm_head"]).astype(F32)
    return (out, jnp.stack(kept)) if states else out
