"""Operations and bytes of a served decoder of sparse-attention layers
(a selection inside paged attention) beside linear-attention layers (a
decayed float32 state a sequence): MiniCPM-SALA. Computed from shapes
and from what the engine's calls did while the profiler ran
(``traced_work`` of ``generators/serve_backlog_longctx.py``). ``model``
is the ``model`` group of a configuration file. Each count is the work
the ALGORITHM needs, whatever implements it: a program that does more (a
chunk that attends every key block under a mask, scores in float32 at
six passes, a step over slots that are not in the batch, a gather of
both KV heads for each) reads a lower share, and none can read over
100 %.
"""

from __future__ import annotations

from typing import Any, Dict

#: Positions a block of the chunkwise scan holds in these counts: the
#: program's (``serve/decode.py::_LIGHTNING_BLOCK``).
SCAN_BLOCK = 128


def _sizes(model: Dict[str, Any]):
    types = model["layer_types"]
    return (sum(t == "sparse" for t in types),
            sum(t == "lightning" for t in types),
            model["n_heads"] * model["d_head"], model["d_head"])


def _kv_row_bytes(model: Dict[str, Any]) -> float:
    """One position's keys (or values, or one compressed key) of every
    KV head, in the pages' 2 bytes."""
    return 2.0 * model["n_kv_heads"] * model["d_head"]


def sparse_select(model: Dict[str, Any], work: Dict[str, float]
                  ) -> Dict[str, float]:
    """Compressing and choosing: every query that chose scored the
    kernels complete at its position with each of its heads (``2 Dh``
    operations a head and kernel); a chunk call reads the compressed
    keys it scores once, a decode row its own; every new key is read
    once more for its kernels' means and a compressed key written for
    every ``sparse_stride`` of them. The softmax, the pooling and the
    top-k are not counted."""
    n = _sizes(model)[0]
    scored = work["prefill_kernels_scored"] + work["decode_kernels_scored"]
    read = work["prefill_kernels_read"] + work["decode_kernels_scored"]
    new = work["prefill_tokens"] + work["decode_rows"]
    return {"flops": n * 2.0 * model["n_heads"] * model["d_head"] * scored,
            "bytes": n * _kv_row_bytes(model) * (
                read + new * (1.0 + 1.0 / model["sparse_stride"]))}


def sparse_attend(model: Dict[str, Any], work: Dict[str, float]
                  ) -> Dict[str, float]:
    """Attention over the CHOSEN keys alone: a query past
    ``sparse_dense_len`` the keys of its ``sparse_topk`` blocks, one
    below it every key before it (``4 Dh`` operations a head and key).
    A chunk call reads the keys and values up to its end once, a decode
    row its own chosen pages of each KV head once."""
    n = _sizes(model)[0]
    keys = (work["prefill_keys_chosen"] + work["prefill_keys_dense"]
            + work["decode_keys_attended"])
    return {"flops": n * 4.0 * model["n_heads"] * model["d_head"] * keys,
            "bytes": n * 2.0 * _kv_row_bytes(model) * (
                work["prefill_keys_read"] + work["decode_keys_attended"])}


def lightning_scan(model: Dict[str, Any], work: Dict[str, float]
                   ) -> Dict[str, float]:
    """The chunk calls' scans, chunkwise at blocks of ``SCAN_BLOCK``: a
    real token and head the products inside its block over the
    positions before it (``q k`` and ``p v``: ``2 Dh SCAN_BLOCK``) and
    with the state (``q S`` and ``k^T v``: ``4 Dh Dh``); q, k, v and o
    once in the activations' 2 bytes and a call's state in and out
    (``8 H Dh Dh`` bytes a layer)."""
    _, n, width, dh = _sizes(model)
    tokens, calls = work["prefill_tokens"], work["prefill_calls"]
    return {"flops": n * tokens * width * (2.0 * SCAN_BLOCK + 4.0 * dh),
            "bytes": n * (tokens * 2.0 * 4 * width + calls * 8.0 * width * dh)}


def lightning_step(model: Dict[str, Any], work: Dict[str, float]
                   ) -> Dict[str, float]:
    """The decode calls' steps: a row in use and a layer hold a state
    of ``H Dh Dh`` float32, which a step reads once and writes once,
    beside q, k, v and o once; five operations a state element (the
    decay's product, ``k v``, their sum, ``q S`` summed)."""
    _, n, width, dh = _sizes(model)
    rows = work["decode_rows"] * n
    return {"flops": 5.0 * rows * width * dh,
            "bytes": rows * (8.0 * width * dh + 2.0 * 4 * width)}


def matmul_flops_per_token(model: Dict[str, Any]) -> float:
    """The matrix products one computed token needs, the head apart: a
    sparse layer's q, k, v, o and gate, a lightning layer's five of
    full width, every layer's SwiGLU."""
    n_sparse, n_lightning, width, _ = _sizes(model)
    d, h, hkv, dh = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                     model["d_head"])
    sparse = 3 * d * h * dh + 2 * d * hkv * dh
    return 2.0 * (n_sparse * sparse + n_lightning * 5 * d * width
                  + model["n_layers"] * 3 * d * model["d_ff"])


def served_work(model: Dict[str, Any], work: Dict[str, float]
                ) -> Dict[str, float]:
    """The operations of everything the traced calls computed: the
    matrix products of every token computed, chunk and decode row
    alike; the head for the one row a chunk call or a decode row emits;
    the sparse layers' scoring and their attention over the chosen
    keys; the scans' and the steps' recurrence."""
    tokens = work["prefill_tokens"] + work["decode_rows"]
    emitted = work["prefill_calls"] + work["decode_rows"]
    parts = {
        "matmul_flops": tokens * matmul_flops_per_token(model),
        "head_flops": 2.0 * emitted * model["d_model"] * model["vocab_size"],
        "select_flops": sparse_select(model, work)["flops"],
        "attention_flops": sparse_attend(model, work)["flops"],
        "recurrence_flops": (lightning_scan(model, work)["flops"]
                             + lightning_step(model, work)["flops"])}
    return {**parts, "flops": sum(parts.values())}
