"""Operations and bytes of a sparse (mixture-of-experts) decoder,
computed from shapes: ``flops.py``'s counts for a model whose FFN is
``n_experts`` SwiGLU experts of width ``d_ff`` of which a token runs
``moe_top_k``. ``model`` is the ``model`` group of a configuration file.
Only the experts a token is routed to count (active parameters), and
recomputed operations (remat) never do.
"""

from __future__ import annotations

from typing import Any, Dict


def active_matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that one token multiplies through: the projections,
    the router, ``moe_top_k`` of the experts' SwiGLU matrices, and the
    output head. The q/k norm vectors are elementwise, the embedding is
    a lookup."""
    d, h, hkv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    dh = d // h
    per_layer = (d * h * dh + 2 * d * hkv * dh + h * dh * d   # wq wk wv wo
                 + d * model["n_experts"]                     # router
                 + model["moe_top_k"] * 3 * d * model["d_ff"])
    return model["n_layers"] * per_layer + d * model["vocab_size"]


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Forward and backward of one token in a row of ``seq`` tokens: 6
    per active matmul parameter, plus causal attention as
    ``flops.train_flops_per_token`` counts it."""
    return (6.0 * active_matmul_params(model)
            + 6.0 * model["n_layers"] * seq * model["d_model"])


def grouped_matmul(model: Dict[str, Any], seq: int, rows: int = 1
                   ) -> Dict[str, float]:
    """One grouped matmul of one layer's experts on ``rows`` rows of
    ``seq`` tokens: the ``N·K`` routed rows against each row's expert,
    ``2·N·K·D·F`` operations whichever of the three SwiGLU matrices it
    is and whichever of its three products (forward, the rows' gradient,
    the matrices' gradient); the bytes of every expert's matrix once and
    the routed rows on both sides, in bf16. A layer's forward is three of
    these (``3 × 2·N·K·D·F``), its backward six."""
    d, f = model["d_model"], model["d_ff"]
    routed = rows * seq * model["moe_top_k"]
    return {"flops": 2.0 * routed * d * f,
            "bytes": 2.0 * (model["n_experts"] * d * f + routed * (d + f))}
