"""Operations and bytes of a decoder whose layers are window and full
attention in turn and whose sparse FFN holds a chip's share of the
experts, computed from shapes: ``flops.py``'s and ``flops_moe.py``'s
counts for such a stack. ``model`` is the ``model`` group of a
configuration file (``layer_types``, ``attn_window``, ``d_head``,
``moe_experts_held`` beside the usual sizes). Only what a token is
routed through HERE counts (the experts this chip holds), only the
(query, key) pairs a layer's mask lets through count, and recomputed
operations (remat) never do: every count below is the least the
mathematics needs, so a share of a roofline built on it cannot pass 100.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def visible_pairs(seq: int, window: Optional[int] = None) -> int:
    """(query, key) pairs of one head on one row of ``seq`` positions: a
    query at p sees ``j <= p`` and, with a window, only ``j > p -
    window``. The first ``window`` queries see p + 1 keys, the others
    ``window``: ``seq * window - window * (window - 1) / 2`` (7 864 832
    at 8192 and 1024, 23.4 % of the triangle's 33 558 528)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return seq * window - window * (window - 1) // 2


def _window_of(model: Dict[str, Any], kind: str) -> Optional[int]:
    return model["attn_window"] if kind == "sliding" else None


def active_matmul_params(model: Dict[str, Any]) -> float:
    """Parameters that one token multiplies through on this chip: the
    projections (``n_heads * d_head`` wide, which is not ``d_model``),
    the router's ``n_experts`` outputs, of its ``moe_top_k`` experts the
    ``moe_experts_held / n_experts`` that are held here in expectation
    (2 of 8 at 16 of 64), and the head over the slice."""
    d, dh = model["d_model"], model["d_head"]
    h, hkv = model["n_heads"], model["n_kv_heads"]
    held = model["moe_experts_held"] / model["n_experts"]
    per_layer = (d * h * dh + 2 * d * hkv * dh + h * dh * d   # wq wk wv wo
                 + d * model["n_experts"]                     # router
                 + model["moe_top_k"] * held * 3 * d * model["d_ff"])
    return model["n_layers"] * per_layer + d * model["vocab_size"]


def attention_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Forward operations of attention for one token, summed over the
    layers: two matmuls (scores, values) of ``2 * d_head`` operations a
    visible pair and head, the pairs of a row shared out over its
    ``seq`` tokens."""
    per_pair = 4.0 * model["n_heads"] * model["d_head"]
    return sum(per_pair * visible_pairs(seq, _window_of(model, kind)) / seq
               for kind in model["layer_types"])


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Forward and backward of one token in a row of ``seq`` tokens: 6
    per active matmul parameter and three times attention's forward."""
    return (6.0 * active_matmul_params(model)
            + 3.0 * attention_flops_per_token(model, seq))


def _flash_bytes(model: Dict[str, Any], seq: int, rows: int, wide: int,
                 stats: int) -> float:
    """``wide`` tensors at the query heads' width and as many at the KV
    heads', bf16, and ``stats`` float32 rows a head."""
    h, hkv, dh = model["n_heads"], model["n_kv_heads"], model["d_head"]
    return float(rows * seq * (2 * wide * (h + hkv) * dh + 4 * stats * h))


def flash_fwd(model: Dict[str, Any], seq: int, rows: int = 1,
              kind: str = "full") -> Dict[str, float]:
    """One call of the flash forward of a layer of ``kind`` on ``rows``
    rows of ``seq`` tokens: 4 * d_head operations a visible pair and
    head (``visible_pairs``: the window's pairs for a sliding layer, not
    the triangle's); q read and the output written once, K and V read
    once at their grouped width, bf16, plus the float32 log-sum-exp."""
    pairs = visible_pairs(seq, _window_of(model, kind))
    return {"flops": rows * model["n_heads"] * 4.0 * model["d_head"] * pairs,
            "bytes": _flash_bytes(model, seq, rows, wide=2, stats=1)}


def flash_bwd(model: Dict[str, Any], seq: int, rows: int = 1,
              kind: str = "full") -> Dict[str, float]:
    """ONE backward of a layer of ``kind``, both kernels together
    (``flops_flash_bwd.flash_bwd``'s count with the layer's pairs): the
    five matmuls (scores, dV, dP, dQ, dK) of 2 * d_head operations a
    visible pair and head; q, k, v, the output and its cotangent read
    and dq, dk, dv written once in bf16, the float32 log-sum-exp and
    delta rows. The two matmuls each kernel recomputes do not count."""
    pairs = visible_pairs(seq, _window_of(model, kind))
    return {"flops": rows * model["n_heads"] * 10.0 * model["d_head"] * pairs,
            "bytes": _flash_bytes(model, seq, rows, wide=4, stats=2)}


def grouped_matmul(model: Dict[str, Any], pairs: float) -> Dict[str, float]:
    """One grouped matmul over the held experts with ``pairs`` (token,
    choice) rows that fell on one of them: ``2 * pairs * d_model *
    d_ff`` operations whichever of the three SwiGLU matrices and
    whichever of its three products (forward, the rows' gradient, the
    matrices' gradient); the held experts' matrix once and those rows on
    both sides, bf16. The rows behind the last group (pairs on experts
    that other chips hold) are not work."""
    d, f = model["d_model"], model["d_ff"]
    return {"flops": 2.0 * pairs * d * f,
            "bytes": 2.0 * (model["moe_experts_held"] * d * f
                            + pairs * (d + f))}
