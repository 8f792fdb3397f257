"""Operations and bytes of a served decoder whose layers are ONE branch
each: Mamba-2 (SSD) mixers, grouped-query attention, and LatentMoE
feed-forwards of which this chip holds a share (NVIDIA-Nemotron-3-Super).
Computed from shapes and from what the engine's calls did while the
profiler ran (``traced_work`` of ``generators/serve_backlog_ssm.py``:
``decode_calls`` and their ``decode_rows``, ``prefill_calls`` and their
``prefill_tokens`` (real tokens: a bucket's padding is the
implementation's and is not counted), the positions the attention
layer's queries saw) and from the routing counters the cell reads at
set-up (``counters``: ``moe_local_pair_share``, the share of a token's
pairs that fall on a held expert, and ``moe_held_experts_touched_mean``,
the held experts a decode step gives at least one pair). ``model`` is
the ``model`` group of a configuration file. Each count is the work the
ALGORITHM needs, whatever implements it: a program that does more (SSD
blocks over a bucket's padding, a step over the null slot, a state read
twice, an expert's matrices read where no pair fell on it) reads a lower
share, and none can read over 100 %.
"""

from __future__ import annotations

from typing import Any, Dict

#: Operations of the recurrence a head, a value, a state column and a
#: position: the decay's product with the state (1), the drive ``(Delta
#: x) B`` (1), their sum (1), and ``S C`` summed over the columns (2).
#: The decay is ONE scalar a head: its exponential is not counted.
_OPS = 5.0


def _sizes(model: Dict[str, Any]) -> Dict[str, int]:
    types = model["layer_types"]
    di = model["mamba_expand"] * model["d_model"]
    return {"n_mamba2": sum(t == "mamba2" for t in types),
            "n_full": sum(t == "full" for t in types),
            "n_ffn": sum(t == "ffn" for t in types),
            "di": di, "n": model["mamba_d_state"],
            "groups": model["mamba2_groups"],
            "heads": di // model["mamba2_head_dim"]}


def mamba2_step(model: Dict[str, Any], work: Dict[str, float],
                counters=None) -> Dict[str, float]:
    """The decode calls' steps: a row in use and a layer hold a state
    of ``Di x N`` float32 (``Hm x P x N``), which a step has to read
    once and write once (``8 Di N`` bytes: 8.4 MB at 8192 x 128), beside
    its inputs and its output once (``x`` and ``y`` of ``Di`` values,
    ``B`` and ``C`` of ``G N``, in the activations' 2 bytes). 0.6
    operations a byte: memory-bound."""
    s = _sizes(model)
    rows = work["decode_rows"] * s["n_mamba2"]
    return {"flops": _OPS * rows * s["di"] * s["n"],
            "bytes": rows * (8.0 * s["di"] * s["n"] + 2.0 * (
                2 * s["di"] + 2 * s["groups"] * s["n"]))}


def mamba2_scan(model: Dict[str, Any], work: Dict[str, float],
                counters=None) -> Dict[str, float]:
    """The chunk calls' SSD: the recurrence's operations a real token
    and a layer (the block form's products come to about as many: 6.5 M
    for the recurrence's 5.2 M a token at the published sizes), against
    the rows in and out once (``x``, ``y``, ``B``, ``C`` in the
    activations' 2 bytes, ``Delta`` in 4) and a call's state in and out
    (``8 Di N`` bytes a layer). By these counts the matrix unit bounds it
    only if every product runs there at its peak; a form that writes its
    ``[heads, block, block]`` decays to memory reads low."""
    s = _sizes(model)
    tokens, calls = work["prefill_tokens"], work["prefill_calls"]
    return {"flops": _OPS * tokens * s["n_mamba2"] * s["di"] * s["n"],
            "bytes": s["n_mamba2"] * (
                tokens * (2.0 * (2 * s["di"] + 2 * s["groups"] * s["n"])
                          + 4.0 * s["heads"])
                + calls * 8.0 * s["di"] * s["n"])}


def held_share(model: Dict[str, Any], counters=None) -> float:
    """The share of a token's pairs that fall on a held expert: as the
    cell counted it, or the share of the experts held."""
    counted = (counters or {}).get("moe_local_pair_share")
    held = model.get("moe_experts_held") or model["n_experts"]
    return held / model["n_experts"] if counted is None else counted


def latent_experts_step(model: Dict[str, Any], work: Dict[str, float],
                        counters=None) -> Dict[str, float]:
    """The decode calls' routed experts, a mixture layer: the two
    matrices of every held expert TOUCHED (``2 x latent x d_ff`` values
    in 2 bytes: 11 MB at 1024 x 2688) read once a call, and the held
    pairs' rows in and out (``latent`` values each way and ``d_ff`` both
    ways between the two products, 2 bytes); two products a pair. At 5.5
    pairs an expert 1 operation a byte: the matrices' bytes bound it."""
    s = _sizes(model)
    latent, width = model["moe_latent"] or model["d_model"], model["d_ff"]
    held = model.get("moe_experts_held") or model["n_experts"]
    touched = (counters or {}).get("moe_held_experts_touched_mean", held)
    pairs = (work["decode_rows"] * model["moe_top_k"]
             * held_share(model, counters) * s["n_ffn"])
    return {"flops": 4.0 * pairs * latent * width,
            "bytes": (work["decode_calls"] * s["n_ffn"] * touched
                      * 4.0 * latent * width
                      + pairs * 4.0 * (latent + width))}


def matmul_flops_per_token(model: Dict[str, Any], counters=None) -> float:
    """The matrix products one computed token needs, the head apart: a
    mamba2 layer's ``W_in``, ``W_dt`` and ``W_out``, the attention
    layer's q, k, v and o, a mixture layer's router (all ``n_experts``
    outputs), both latent projections, the shared expert's two matrices
    and two matrices for each of the token's pairs on a HELD expert."""
    s = _sizes(model)
    d, h, hkv, dh = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                     model["d_head"])
    latent, width = model["moe_latent"] or d, model["d_ff"]
    conv = s["di"] + 2 * s["groups"] * s["n"]
    mamba2 = d * (s["di"] + conv + s["heads"]) + s["di"] * d
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    ffn = (d * model["n_experts"] + 2 * d * (latent if model["moe_latent"]
                                             else 0)
           + 2 * d * (model.get("moe_shared_d_ff") or width)
           + model["moe_top_k"] * held_share(model, counters)
           * 2 * latent * width)
    return 2.0 * (s["n_mamba2"] * mamba2 + s["n_full"] * attn
                  + s["n_ffn"] * ffn)


def served_work(model: Dict[str, Any], work: Dict[str, float],
                counters=None) -> Dict[str, float]:
    """The operations of everything the traced calls computed: the
    matrix products of every token computed, chunk and decode row
    alike; the head for the one row a chunk call or a decode row emits;
    the attention layer's scores and sums over the positions its queries
    saw (``4 H Dh`` a position); the SSD's and the steps' recurrence."""
    s = _sizes(model)
    tokens = work["prefill_tokens"] + work["decode_rows"]
    emitted = work["prefill_calls"] + work["decode_rows"]
    seen = work["prefill_positions_seen"] + work["decode_positions_seen"]
    parts = {
        "matmul_flops": tokens * matmul_flops_per_token(model, counters),
        "head_flops": 2.0 * emitted * model["d_model"] * model["vocab_size"],
        "attention_flops": (4.0 * s["n_full"] * model["n_heads"]
                            * model["d_head"] * seen),
        "recurrence_flops": (mamba2_scan(model, work)["flops"]
                             + mamba2_step(model, work)["flops"])}
    return {**parts, "flops": sum(parts.values())}
