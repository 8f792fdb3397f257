"""The plain reference of the EvaByte decoder (``model_type``
``evabyte``, ``attention_class`` ``eva``): what ``correct`` is decided
against for a served byte-level model of EVA attention layers (an exact,
block-aligned window beside one attended summary a chunk of everything
before it, in ONE softmax), a float32 residual stream, norms with a unit
offset and a head of several prediction rows. One copy lives beside the
benchmark (``benchmark/reference_evabyte.py``) and one beside the tier-1
tests (``tests/reference_evabyte.py``); a test holds the two identical
below this docstring.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: a Python loop over layers, the
attention a query head at a time over ALL the sequence's keys and ALL
its chunks' summaries under a mask, a block of queries at a time so that
32 768 positions fit (the scores of a block are ``[block, T + T / c]``);
no kernel, no cache, no batching, and no import from the program. It
takes the program's parameter tree and a plain dict of sizes, and
upcasts one layer's matrices at a time, so that it fits on the chip
beside the engine.

The layers, as ISSUE 56 writes them down (EVA: Zheng et al., "Efficient
Attention via Control Variates", ICLR 2023, in the simplified form of
EvaByte's modelling code AS RECALLED, no network: every line the
published ``config.json`` has no key for is marked ASSUMED here and is
under ``assumed`` in ``benchmark/configs/evabyte-6.5b-8l.json``).
``s = Dh ** -0.5``, ``W`` the window, ``c`` the chunk:

* block: ``x += Attn(N(x)); x += MLP(N(x))``, the stream and both
  additions float32 (``fp32_skip_add``); ``N(x) = x / sqrt(mean(x^2) +
  eps) * (1 + g)`` with the STORED gain ``g`` (``norm_add_unit_offset``);
  ``MLP(h) = W_down(silu(W_gate h) * W_up h)``; the embedding is not
  scaled.
* **eva**: q, k, v of ``n_heads`` / ``n_kv_heads`` heads; q and k
  rotated in HALVES, pairs ``(i, i + Dh / 2)``, at ``rope_theta``
  (ASSUMED), v not. Chunk ``m`` is positions ``c m .. c m + c - 1``,
  window ``w`` positions ``W w .. W w + W - 1``. A chunk's summary:
  ``k~_m = sum_j softmax_j(s mu.k_j) k_j``, ``v~_m = sum_j softmax_j(s
  phi.k_j) v_j`` over its positions, ``mu`` and ``phi`` the layer's
  vectors a KV head (the pooling logits' factor ``s``: ASSUMED). The
  query at ``t``, ``w = t // W``: ONE softmax over ``{s q.k_j : W w <= j
  <= t}`` and ``{s q.k~_m : m < (W / c) w}``; ``o = sum_j p_j v_j +
  sum_m p_m v~_m``; then ``W_o``.
* **full** (a stack that mixes the kinds, the tests'): causal over
  everything, no rotary embedding (as the program's full layers beside
  a kind of their own).
* head: ``N``, then ``h W_head`` with ``W_head`` ``[D, rows * V]`` read
  as ``[rows, V]``: the prediction rows are the columns' SLOW index
  (ASSUMED); row ``j`` predicts token ``t + 1 + j``.

``wrong`` and ``store`` exist for ``benchmark/tools/evabyte_tolerance.py``
and ``tests/test_evabyte.py``, which show what the check refuses: the
same reference with one mechanism miscomputed or one precision narrowed
(a name of ``WRONG``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
_ROW_BLOCK = 1024
_NEG = -1e30

#: What ``wrong`` may name, each one mechanism miscomputed or one
#: precision narrowed.
WRONG = (
    "bf16_throughout",      # weights, stream, norms, softmax: all bfloat16
    "bf16_stream",          # the stream rounded to bfloat16 at every add
    "bf16_softmax",         # scores and the softmax's weights in bfloat16
    "offset_folded",        # the gain bf16(1 + g) for 1 + g
    "no_pool_scale",        # the pooling logits mu.k and phi.k not times s
    "two_softmaxes",        # window and summaries each a softmax, added
    "sliding_window",       # the newest W keys exactly, not the aligned W
    "open_chunks_seen",     # the open window's closed chunks as summaries
    "no_rope",              # q and k not rotated
    "rope_pairs",           # rotated in interleaved pairs, not in halves
    "rows_fast",            # the head's rows the columns' FAST index
)


def sizes_of(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from a configuration file."""
    m = config["model"]
    return {"n_layers": m["n_layers"], "n_heads": m["n_heads"],
            "n_kv_heads": m["n_kv_heads"],
            "head_dim": m.get("d_head") or m["d_model"] // m["n_heads"],
            "d_model": m["d_model"], "norm_eps": m["norm_eps"],
            "layer_types": tuple(m["layer_types"]),
            "rope_theta": m["rope_theta"], "window": m["eva_window"],
            "chunk": m["eva_chunk"], "head_rows": m["head_rows"],
            "vocab_size": m["vocab_size"],
            "unit_offset": m["norm_unit_offset"]}


def _norm(x, g, sizes, wrong):
    """``x / rms(x) * (1 + g)``, ``g`` as stored."""
    g = g.astype(F32)
    if sizes["unit_offset"]:
        g = ((1.0 + g).astype(BF16).astype(F32) if wrong == "offset_folded"
             else 1.0 + g)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + sizes["norm_eps"]) * g
    return y.astype(BF16).astype(F32) if wrong == "bf16_throughout" else y


def _rope(x, theta, wrong):
    """x [T, H, Dh], positions 0..T-1, rotated in HALVES (ASSUMED)."""
    if wrong == "no_rope":
        return x
    T, _, d = x.shape
    inv = theta ** -(jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if wrong == "rope_pairs":
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         -1).reshape(x.shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _summaries(k, v, mu, phi, chunk, wrong):
    """``(k~, v~)`` [M, Hkv, Dh] of the ``M = T // chunk`` whole chunks
    of ``k``, ``v`` [T, Hkv, Dh]."""
    M = k.shape[0] // chunk
    kc = k[:M * chunk].reshape(M, chunk, *k.shape[1:])
    vc = v[:M * chunk].reshape(M, chunk, *v.shape[1:])
    s = 1.0 if wrong == "no_pool_scale" else k.shape[-1] ** -0.5  # ASSUMED

    def pooled(by, rows):
        w = jax.nn.softmax(s * jnp.einsum("hd,mchd->mch", by, kc), axis=1)
        return jnp.einsum("mch,mchd->mhd", w, rows)

    return pooled(mu, kc), pooled(phi, vc)


@functools.partial(jax.jit, static_argnames=("kind", "window", "chunk",
                                             "block", "wrong"))
def _attend(q, k, v, ks, vs, *, kind, window, chunk, block, wrong):
    """q [T, H, Dh] over k, v [T, Hkv, Dh] and the summaries ks, vs
    [M, Hkv, Dh]: a head at a time, ``block`` queries at a time (T in
    whole blocks). Returns [T, H * Dh]."""
    T, H, Dh = q.shape
    rep = H // k.shape[1]
    M = ks.shape[0]
    j = jnp.arange(T)[None]
    m = jnp.arange(M)[None]
    s = Dh ** -0.5

    def block_of(a):
        t = a + jnp.arange(block)[:, None]                       # [blk, 1]
        if kind == "full":
            exact, summed = j <= t, jnp.zeros((block, M), bool)
        elif wrong == "sliding_window":
            exact = (j <= t) & (j > t - window)
            summed = chunk * (m + 1) <= t - window + 1
        else:
            exact = (j <= t) & (j >= t // window * window)
            summed = m < t // window * (window // chunk)
            if wrong == "open_chunks_seen":
                summed = chunk * (m + 1) <= t + 1
                exact &= j >= (t + 1) // chunk * chunk

        def head(h):
            qh = jax.lax.dynamic_slice_in_dim(q[:, h], a, block)  # [blk, Dh]
            g = h // rep
            se = jnp.where(exact, s * qh @ k[:, g].T, _NEG)
            ss = jnp.where(summed, s * qh @ ks[:, g].T, _NEG)
            if wrong in ("bf16_softmax", "bf16_throughout"):
                se, ss = (x.astype(BF16) for x in (se, ss))
            if wrong == "two_softmaxes":
                pe = jax.nn.softmax(se, -1).astype(F32)
                ps = jnp.where(summed.any(-1, keepdims=True),
                               jax.nn.softmax(ss, -1).astype(F32), 0.0)
                return pe @ v[:, g] + ps @ vs[:, g]
            p = jax.nn.softmax(jnp.concatenate([se, ss], -1), -1).astype(F32)
            return p[:, :T] @ v[:, g] + p[:, T:] @ vs[:, g]

        return jax.lax.map(head, jnp.arange(H))                # [H, blk, Dh]

    o = jax.lax.map(block_of, jnp.arange(0, T, block))       # [n, H, blk, Dh]
    return o.transpose(0, 2, 1, 3).reshape(T, H * Dh)


def layer(x, lp, sizes, i, block, last, wrong=None):
    """One block over the float32 stream ``x`` [T, D] (T in whole
    ``block``, the first ``last`` positions the sequence's): ``(x,
    kept)``, ``kept`` what the sequence holds of an eva layer after
    position ``last - 1``, the open window's rotated keys and values and
    every whole chunk's summaries (None of a full layer)."""
    H, Hkv, Dh = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    kind = sizes["layer_types"][i]
    T = x.shape[0]
    low = wrong == "bf16_throughout"

    def mat(name):
        return lp[name].astype(F32)

    def add(x, y):      # a branch's output added into the stream
        y = x + y
        return (y.astype(BF16).astype(F32)
                if wrong in ("bf16_stream", "bf16_throughout") else y)

    def rounded(y):
        return y.astype(BF16).astype(F32) if low else y

    h = _norm(x, lp["attn_norm"], sizes, wrong)
    q = rounded(h @ mat("wq")).reshape(T, H, Dh)
    k = rounded(h @ mat("wk")).reshape(T, Hkv, Dh)
    v = rounded(h @ mat("wv")).reshape(T, Hkv, Dh)
    # Arrays of [T, D] are dropped as they are done with, and the host
    # waits for each stage: dispatched ahead of the device, the stages'
    # outputs are all allocated before the first is freed, and beside a
    # full engine the peak then followed the HOST's speed (14.6 or 16.2
    # GB of 16.9 from one run to the next: chip, PR 56).
    del h
    jax.block_until_ready(v)
    kept = None
    if kind == "eva":
        q, k = (rounded(_rope(a, sizes["rope_theta"], wrong)) for a in (q, k))
        ks, vs = _summaries(k, v, mat("eva_mu"), mat("eva_phi"),
                            sizes["chunk"], wrong)
        ks, vs = rounded(ks), rounded(vs)
        first = (last - 1) // sizes["window"] * sizes["window"]
        kept = (k[first:last], v[first:last], ks[:last // sizes["chunk"]],
                vs[:last // sizes["chunk"]])
    else:
        ks = vs = jnp.zeros((0, Hkv, Dh), F32)
    o = _attend(q, k, v, ks, vs, kind=kind, window=sizes["window"],
                chunk=sizes["chunk"], block=block, wrong=wrong)
    jax.block_until_ready(o)
    del q, k, v, ks, vs
    x = add(x, rounded(rounded(o) @ mat("wo")))
    del o
    h = _norm(x, lp["mlp_norm"], sizes, wrong)
    y = jnp.concatenate([
        rounded(rounded(jax.nn.silu(h[t:t + _ROW_BLOCK] @ mat("w_gate"))
                        * (h[t:t + _ROW_BLOCK] @ mat("w_up")))
                @ mat("w_down"))
        for t in range(0, T, _ROW_BLOCK)])
    del h
    return jax.block_until_ready(add(x, y)), kept


def forward(params, tokens, sizes, last: int = 0, *,
            wrong: Optional[str] = None, kept: bool = False):
    """Float32 logits of ONE sequence ``tokens`` [T]: every position
    ``[T, rows, V]``, or only the last ``last`` positions. ``wrong``: a
    name of ``WRONG``. ``kept``: also, an eva layer after another, what
    the sequence holds after its last position: ``(k, v)`` the live rows
    of its OPEN window ``[T % W or W .., Hkv, Dh]`` (positions ``W (T -
    1) // W .. T - 1``) and ``(k~, v~)`` the summaries of all its whole
    chunks ``[T // c, Hkv, Dh]``."""
    assert wrong is None or wrong in WRONG, wrong
    tokens = jnp.asarray(tokens)
    T, c = tokens.shape[0], sizes["chunk"]
    block = _ROW_BLOCK if T > _ROW_BLOCK else -(-T // c) * c
    padded = -(-T // block) * block
    tokens = jnp.pad(tokens, (0, padded - T))   # behind every real query
    held = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for i in range(sizes["n_layers"]):
            x, rows = layer(x, params["layers"][i], sizes, i, block, T,
                            wrong)
            if rows is not None:
                held.append(rows)
        x = _norm(x[:T][-last:], params["final_norm"], sizes, wrong)
        out = x @ params["lm_head"].astype(F32)
        rows, V = sizes["head_rows"], sizes["vocab_size"]
        out = (out.reshape(-1, V, rows).swapaxes(1, 2)
               if wrong == "rows_fast" else out.reshape(-1, rows, V))
    return (out, held) if kept else out
