"""Operations and bytes of the flash attention backward, computed from
shapes: ``flops.flash_fwd``'s counterpart for the two Pallas kernels
(``hvd_flash_bwd_dkv``, ``hvd_flash_bwd_dq``) that make one backward.
``model`` is the ``model`` group of a configuration file. Recomputed
operations never count (``flops.py``'s rule): each kernel recomputes the
scores and dP for itself, seven matmuls run where five are needed.
"""

from __future__ import annotations

from typing import Any, Dict


def flash_bwd(model: Dict[str, Any], seq: int, rows: int = 1
              ) -> Dict[str, float]:
    """ONE causal backward on ``rows`` rows of ``seq`` tokens, one
    layer, both kernels together: the five matmuls of the lower triangle
    (the scores, dV, dP, dQ, dK: 10 operations per query, key and head
    dimension), and the bytes of reading q, k, v, the output and its
    cotangent in bf16 (K and V at their grouped width) and the f32
    log-sum-exp and delta rows, and writing dq, dk, dv once."""
    h, hkv = model["n_heads"], model["n_kv_heads"]
    dh = model["d_model"] // h
    flops = rows * h * 10.0 * dh * seq * (seq + 1) / 2
    bytes_ = rows * seq * (2 * (4 * h * dh + 4 * hkv * dh) + 2 * 4 * h)
    return {"flops": flops, "bytes": float(bytes_)}
