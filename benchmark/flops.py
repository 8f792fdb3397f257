"""Operations and bytes the algorithm needs, computed from shapes.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. ``model`` is the ``model`` group of a configuration file.
Recomputed operations (remat) never count.
"""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that a token multiplies through: the projections, the
    SwiGLU matrices and the output head. The embedding table is a
    lookup and the norms are elementwise, so neither counts."""
    d, h, hkv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    dh = d // h
    per_layer = (d * h * dh            # wq
                 + 2 * d * hkv * dh    # wk, wv
                 + h * dh * d          # wo
                 + 3 * d * model["d_ff"])
    return model["n_layers"] * per_layer + d * model["vocab_size"]


def total_params(model: Dict[str, Any]) -> int:
    d = model["d_model"]
    return (matmul_params(model) + model["vocab_size"] * d
            + model["n_layers"] * 2 * d + d)


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Forward and backward of one token in a row of ``seq`` tokens:
    6 per matmul parameter, plus causal attention. A query at position p
    meets p+1 keys, seq/2 on average, in two matmuls (QK^T and PV) of
    2*n_heads*head_dim operations per key: 2*seq*d forward, three times
    that with the backward, in every layer."""
    d_attn = model["d_model"]  # n_heads * head_dim
    return (6.0 * matmul_params(model)
            + 6.0 * model["n_layers"] * seq * d_attn)


def flash_fwd(model: Dict[str, Any], seq: int, rows: int = 1
              ) -> Dict[str, float]:
    """One call of the causal flash forward on ``rows`` rows of ``seq``
    tokens, one layer: the operations of the lower triangle, and the
    bytes of reading q, k, v and writing the output once in bf16 (K and
    V at their grouped width) plus the f32 log-sum-exp row."""
    h, hkv = model["n_heads"], model["n_kv_heads"]
    dh = model["d_model"] // h
    flops = rows * h * 4.0 * dh * seq * (seq + 1) / 2
    bytes_ = rows * seq * (2 * (2 * h * dh + 2 * hkv * dh) + 4 * h)
    return {"flops": flops, "bytes": float(bytes_)}


def roofline_least_s(cost: Dict[str, float], peak: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = cost["flops"] / peak["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
