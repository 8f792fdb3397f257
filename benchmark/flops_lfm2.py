"""Operations and bytes of a served decoder of gated short-convolution
layers beside grouped-query attention layers, with a mixture of experts
held WHOLE on the chip (LFM2-8B-A1B). Computed from shapes and from what
the engine's calls did while the profiler ran (``traced_work`` of
``generators/serve_backlog_conv.py``): ``decode_calls`` and their
``decode_rows`` (sequences a call, summed), ``prefill_calls`` and their
``prefill_tokens`` (real tokens: a bucket's padding is the
implementation's and is not counted), the positions the attention
layers' queries saw (``prefill_positions_seen``,
``decode_positions_seen``). ``model`` is the ``model`` group of a
configuration file. Each count is the work the ALGORITHM needs, whatever
implements it: a program that does more (a convolution or a dispatch
over a bucket's padding, a table gathered whole, an expert read twice)
reads a lower share, and none can read over 100 %.
"""

from __future__ import annotations

from typing import Any, Dict

_BF16 = 2.0


def _sizes(model: Dict[str, Any]):
    types = model["layer_types"]
    return (sum(t == "conv" for t in types), sum(t == "full" for t in types),
            model["n_layers"] - model["n_dense_layers"])


def _layer_params(model: Dict[str, Any]) -> Dict[str, float]:
    """Matrix parameters of one operator or feed-forward of each sort."""
    d, h, hkv, dh = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                     model["d_head"])
    return {"conv": d * 3 * d + d * d,
            "attn": d * h * dh + 2 * d * hkv * dh + h * dh * d,
            "dense": 3 * d * model["d_ff_dense"],
            "expert": 3 * d * model["d_ff"],
            "router": d * model["n_experts"]}


def matmul_flops_per_token(model: Dict[str, Any]) -> float:
    """The matrix products one computed token needs, the head apart: a
    conv layer's ``W_in`` and ``W_out``, an attention layer's q, k, v
    and o, a dense layer's SwiGLU, a sparse layer's router and the
    SwiGLUs of the ``moe_top_k`` experts the token takes (NOT of all
    ``n_experts``: what is held and not taken is bytes, below)."""
    n_conv, n_full, n_moe = _sizes(model)
    p = _layer_params(model)
    return 2.0 * (n_conv * p["conv"] + n_full * p["attn"]
                  + model["n_dense_layers"] * p["dense"]
                  + n_moe * (p["router"] + model["moe_top_k"] * p["expert"]))


def weight_bytes_a_call(model: Dict[str, Any], touched: float) -> float:
    """What one call, chunk or decode step, has to read of the weights:
    every operator and dense SwiGLU, every sparse layer's router and its
    ``touched`` experts ONCE (all ``n_experts`` wherever a call holds a
    few pairs an expert: 16 a decode step of 128 rows, 32 a chunk of
    256), and the head, which is the embedding table; bf16."""
    n_conv, n_full, n_moe = _sizes(model)
    p = _layer_params(model)
    return _BF16 * (n_conv * p["conv"] + n_full * p["attn"]
                    + model["n_dense_layers"] * p["dense"]
                    + n_moe * (p["router"] + touched * p["expert"])
                    + model["d_model"] * model["vocab_size"])


def moe_experts_product(model: Dict[str, Any], pairs: int, touched: float
                        ) -> Dict[str, float]:
    """ONE grouped product of one sparse layer in a call that dispatched
    ``pairs`` (token, choice) pairs: ``2 P D F`` operations against the
    ``touched`` experts' ``[D, F]`` matrix read once and the pairs' rows
    on both sides, bf16. 16 pairs an expert in a decode step of 128
    rows, 128 in a chunk of 1024: 16 and 110 operations a byte against
    the chip's 240, so the matrices' bytes bound both."""
    d, f = model["d_model"], model["d_ff"]
    return {"flops": 2.0 * pairs * d * f,
            "bytes": _BF16 * (touched * d * f + pairs * (d + f))}


def served_work(model: Dict[str, Any], work: Dict[str, float],
                touched: float = None) -> Dict[str, float]:
    """The operations of everything the traced calls computed: the
    matrix products of every token computed, chunk and decode row alike,
    with ``moe_top_k`` experts a token; the head for the one row a chunk
    call or a decode row emits; both programs' attention over the
    positions their queries saw (``4 H Dh`` a position and full layer);
    the convolutions' gates and taps. And the BYTES the calls had to
    read: the weights once a call (:func:`weight_bytes_a_call`), the K
    and V pages of the positions the decode rows saw (a chunk attends
    what it computed or read once), every stepped or resumed slot's rows
    in and out."""
    n_conv, n_full, _ = _sizes(model)
    d, taps = model["d_model"], model["conv_taps"]
    touched = model["n_experts"] if touched is None else touched
    tokens = work["prefill_tokens"] + work["decode_rows"]
    emitted = work["prefill_calls"] + work["decode_rows"]
    calls = work["prefill_calls"] + work["decode_calls"]
    seen = work["prefill_positions_seen"] + work["decode_positions_seen"]
    parts = {
        "matmul_flops": tokens * matmul_flops_per_token(model),
        "head_flops": 2.0 * emitted * d * model["vocab_size"],
        "attention_flops": (4.0 * n_full * model["n_heads"] * model["d_head"]
                            * seen),
        # B * u, the taps' products and sums, C * c: 2 taps + 2 a channel
        "conv_flops": tokens * n_conv * (2.0 * taps + 2.0) * d}
    read = {
        "weight_bytes": calls * weight_bytes_a_call(model, touched),
        "page_bytes": (work["decode_positions_seen"] * n_full * 2
                       * model["n_kv_heads"] * model["d_head"] * _BF16),
        "row_bytes": (emitted * n_conv * 2 * (taps - 1) * d * _BF16)}
    return {**parts, **read, "flops": sum(parts.values()),
            "bytes": sum(read.values())}
