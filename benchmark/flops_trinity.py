"""Operations and bytes of the grouped expert matmuls of a served sparse
decoder that holds one chip's share of its experts, computed from
shapes. ``model`` is the ``model`` group of a configuration file.
"""

from __future__ import annotations

from typing import Any, Dict


def grouped_matmul(model: Dict[str, Any], pairs: int) -> Dict[str, float]:
    """ONE grouped matmul of one layer's held experts over the rows of
    a call that routed ``pairs`` (token, choice) pairs over ALL experts
    (``tokens x moe_top_k``).

    Operations: the pairs that fall on held experts, ``pairs x held /
    n_experts`` of them if the router is even, against ``[D, F]`` each:
    ``2 P D F``. The rows behind the last group (the pairs of absent
    experts) are not counted: the kernel need not touch them.
    Bytes: every held expert's matrix once, and the held pairs' rows on
    both sides, in bf16. The matrices are a true minimum when every
    held expert has a pair, which a chunk gives (16 expected an expert
    at 1024 tokens); a decode step touches only some experts, which is
    why decode's kernels get a time share and no roofline."""
    d, f = model["d_model"], model["d_ff"]
    held = model.get("moe_experts_held") or model["n_experts"]
    local = pairs * held / model["n_experts"]
    return {"flops": 2.0 * local * d * f,
            "bytes": 2.0 * (held * d * f + local * (d + f))}


def grouped_matmul_min_s(model: Dict[str, Any], pairs: int,
                         peak: Dict[str, Any]) -> float:
    """The least time of one such kernel: the larger of its operations
    over the peak and its bytes over the memory bandwidth. A layer's
    call runs three (``3 x 2 P D F``; 1.81 GB of matrices for 32 experts
    of 3072 x 3072)."""
    cost = grouped_matmul(model, pairs)
    return max(cost["flops"] / peak["bf16_flops_per_s"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
