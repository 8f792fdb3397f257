"""Operations and bytes of a served byte-level decoder whose every layer
is EVA attention (EvaByte): an exact, block-aligned window beside one
attended summary a chunk of every window that has closed. Computed from
shapes and from what the engine's calls did while the profiler ran
(``traced_work`` of ``generators/serve_backlog_eva.py``):
``decode_calls`` and their ``decode_rows``, ``prefill_calls`` and their
``prefill_tokens`` (real bytes: a bucket's padding is the
implementation's and is not counted), the exact keys and the summaries
the chunks' queries saw (``prefill_keys_exact``,
``prefill_keys_summaries``: the causal half counted once), and the rows
and summaries the decode rows' attention HAD to read
(``decode_rows_read``: each row's open window up to its own position;
``decode_summaries_read``: one summary a chunk of its closed windows).
``model`` is the ``model`` group of a configuration file. Each count is
the work the ALGORITHM needs, counted from the traffic and not from the
implementation: a program that does more (a window read past a row's
count, a table gathered whole, scores made against every head's keys)
reads a lower share, and none can read over 100 %.
"""

from __future__ import annotations

from typing import Any, Dict

_BF16 = 2.0


def _widths(model: Dict[str, Any]):
    d, h, hkv = model["d_model"], model["n_heads"], model["n_kv_heads"]
    return d, h, hkv, model.get("d_head") or d // h


def layer_params(model: Dict[str, Any]) -> float:
    """Matrix parameters of one layer: q, k, v, o and the SwiGLU (the
    pooling vectors are ``2 Hkv Dh`` more, not matrices)."""
    d, h, hkv, dh = _widths(model)
    return (d * h * dh + 2 * d * hkv * dh + h * dh * d
            + 3 * d * model["d_ff"])


def head_params(model: Dict[str, Any]) -> float:
    return model["d_model"] * model["head_rows"] * model["vocab_size"]


def weight_bytes_a_call(model: Dict[str, Any]) -> float:
    """What one call, chunk or decode step, has to read of the weights:
    every layer and the head once (the embedding is a lookup); bf16."""
    return _BF16 * (model["n_layers"] * layer_params(model)
                    + head_params(model))


def decode_attention(model: Dict[str, Any], work: Dict[str, float]
                     ) -> Dict[str, float]:
    """The decode calls' attention alone, all layers: ``4 H Dh``
    operations a (query, key) pair, and the K and V (or k~ and v~) of
    every row and summary a decode row had to read, once a layer."""
    _, h, hkv, dh = _widths(model)
    keys = work["decode_rows_read"] + work["decode_summaries_read"]
    return {"flops": 4.0 * model["n_layers"] * h * dh * keys,
            "bytes": model["n_layers"] * keys * 2 * hkv * dh * _BF16}


def chunk_attention(model: Dict[str, Any], work: Dict[str, float]
                    ) -> Dict[str, float]:
    """The chunks' attention alone, all layers: ``4 H Dh`` a (query,
    key) pair over the exact keys at or before each query in its window
    (the causal half counted once) and the summaries of the closed
    windows; q, the keys and values once and the output, bf16."""
    _, h, hkv, dh = _widths(model)
    pairs = work["prefill_keys_exact"] + work["prefill_keys_summaries"]
    tokens = work["prefill_tokens"]
    return {"flops": 4.0 * model["n_layers"] * h * dh * pairs,
            "bytes": model["n_layers"] * tokens * _BF16 * (
                2 * h * dh + 2 * hkv * dh)}


def served_work(model: Dict[str, Any], work: Dict[str, float]
                ) -> Dict[str, float]:
    """The operations of everything the traced calls computed: the
    matrix products of every byte computed, chunk and decode row alike;
    the ``head_rows x vocab`` head for the one row a chunk call or a
    decode row emits; both programs' attention over the keys and
    summaries their queries really had; the summaries' pooling (every
    position is pooled once, when its chunk closes: two softmaxes of
    ``eva_chunk`` logits a KV head, ``8 Hkv Dh`` a position). And the
    BYTES the calls had to read: the weights once a call, a decode
    step's live rows and summaries, a chunk's window behind it."""
    d, h, hkv, dh = _widths(model)
    tokens = work["prefill_tokens"] + work["decode_rows"]
    emitted = work["prefill_calls"] + work["decode_rows"]
    calls = work["prefill_calls"] + work["decode_calls"]
    step, chunk = decode_attention(model, work), chunk_attention(model, work)
    parts = {
        "matmul_flops": 2.0 * tokens * model["n_layers"] * layer_params(model),
        "head_flops": 2.0 * emitted * head_params(model),
        "attention_flops": step["flops"] + chunk["flops"],
        "pooling_flops": 8.0 * tokens * model["n_layers"] * hkv * dh}
    read = {"weight_bytes": calls * weight_bytes_a_call(model),
            "decode_state_bytes": step["bytes"],
            "chunk_state_bytes": chunk["bytes"]}
    return {**parts, **read, "flops": sum(parts.values()),
            "bytes": sum(read.values())}
