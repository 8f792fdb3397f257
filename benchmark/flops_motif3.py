"""Operations and bytes of a served decoder whose every layer is grouped
differential attention over a latent cache (GDLA), in window layers
(rings of latents by slot) and full layers (latent pages), on an mHC
residual, over a chip's share of PolyNorm experts (Motif-3-Beta).
Computed from shapes and from what the engine's calls did while the
profiler ran (``traced_work`` of ``generators/serve_backlog_gdla.py``:
the ``decode_rows``, the ring places and the page positions those rows'
attention had to read a layer (``decode_ring_places``,
``decode_latent_positions``: the spans' own arguments), ``prefill_calls``
and their ``prefill_tokens`` (real tokens: a bucket's padding is the
implementation's), the keys a chunk's queries saw a layer of each kind
(``prefill_seen_window``, ``prefill_seen_full``)) and from the routing
counter the cell reads at set-up (``counters``:
``moe_local_pair_share``). ``model`` is the ``model`` group of a
configuration file. Each count is the work the ALGORITHM needs, whatever
implements it: a program that does more (a whole ring expanded where a
window and a chunk would do, a key block's padding, the absorbed form's
wider products) reads a lower share, and none can read over 100 %.
"""

from __future__ import annotations

from typing import Any, Dict


def _sizes(model: Dict[str, Any]) -> Dict[str, int]:
    types = model["layer_types"]
    rank, rope = model["mla_kv_rank"], model["mla_rope_dim"]
    return {"n_window": sum(t == "mla_sliding" for t in types),
            "n_full": sum(t == "mla" for t in types),
            "n_sparse": model["n_layers"] - model.get("n_dense_layers", 0),
            "rank": rank, "rope": rope}


def held_share(model: Dict[str, Any], counters=None) -> float:
    """The share of a token's pairs that fall on a held expert: as the
    cell counted it, or the share of the experts held."""
    counted = (counters or {}).get("moe_local_pair_share")
    held = model.get("moe_experts_held") or model["n_experts"]
    return held / model["n_experts"] if counted is None else counted


def matmul_flops_per_token(model: Dict[str, Any], counters=None) -> float:
    """The matrix products one computed token needs, the head apart: a
    layer's attention projections (``W_dq``, ``W_uq``, ``W_dkv``, the
    token's own key and value out of ``W_ukv`` (or as much for the
    absorbed form's ``q W_uk`` and ``o W_uv``), lambda, the gate,
    ``W_o``) and its two branches' mHC mappings (``n D`` by ``n n + 2
    n``) and mixes (``n D`` reading, ``n n D + n D`` writing), the dense
    layer's three matrices, and of a sparse layer the router (all
    ``n_experts`` outputs), the shared expert and three matrices for
    each of the token's pairs on a HELD expert."""
    s = _sizes(model)
    d, h, g, dh = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                   model["d_head"])
    q, n = model["mla_q_rank"], model["mhc_streams"]
    signal = h - model["mla_noise_heads"]
    attn = (d * q + q * h * (dh + s["rope"]) + d * (s["rank"] + s["rope"])
            + s["rank"] * g * 2 * dh + d * signal + 2 * d * signal * dh)
    mhc = 2 * (n * d * (n * n + 2 * n) + n * d + n * n * d + n * d)
    dense = 3 * d * model["d_ff_dense"]
    sparse = (d * model["n_experts"] + 3 * d * model["d_ff"]
              + model["moe_top_k"] * held_share(model, counters)
              * 3 * d * model["d_ff"])
    n_dense = model.get("n_dense_layers", 0)
    return 2.0 * (model["n_layers"] * (attn + mhc) + n_dense * dense
                  + s["n_sparse"] * sparse)


def served_work(model: Dict[str, Any], work: Dict[str, float],
                counters=None) -> Dict[str, float]:
    """The operations of everything the traced calls computed: the
    matrix products of every token computed, chunk and decode row
    alike; the head for the one row a chunk call or a decode row emits;
    the attention's scores and sums over the keys the queries saw
    (``2 (Dh + R) + 2 Dh`` a head and key: the expanded count, for a
    decode row too, whose absorbed form does more)."""
    s = _sizes(model)
    tokens = work["prefill_tokens"] + work["decode_rows"]
    emitted = work["prefill_calls"] + work["decode_rows"]
    seen = (s["n_window"] * (work["prefill_seen_window"]
                             + work["decode_ring_places"])
            + s["n_full"] * (work["prefill_seen_full"]
                             + work["decode_latent_positions"]))
    parts = {
        "matmul_flops": tokens * matmul_flops_per_token(model, counters),
        "head_flops": 2.0 * emitted * model["d_model"] * model["vocab_size"],
        "attention_flops": seen * model["n_heads"] * (
            2.0 * (model["d_head"] + s["rope"]) + 2.0 * model["d_head"])}
    return {**parts, "flops": sum(parts.values())}
