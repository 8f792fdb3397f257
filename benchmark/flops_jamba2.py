"""Operations and bytes of a served decoder of state-space layers beside
attention layers (AI21-Jamba2: Mamba-1's selective scan over a float32
state a sequence). Computed from shapes and from what the engine's
calls did while the profiler ran (``traced_work`` of
``generators/serve_backlog_ssm.py``): ``decode_calls`` and their
``decode_rows`` (sequences a call, summed), ``prefill_calls`` and their
``prefill_tokens`` (real tokens: a bucket's padding is the
implementation's and is not counted), the positions the attention
layers' queries saw (``prefill_positions_seen``,
``decode_positions_seen``). ``model`` is the ``model`` group of a
configuration file. Each count is the work the ALGORITHM needs, whatever
implements it: a program that does more (a scan over a bucket's padding,
a step over slots that are not in the batch, a state read twice) reads a
lower share, and none can read over 100 %.
"""

from __future__ import annotations

from typing import Any, Dict

#: Operations of the recurrence a channel, a state row and a position:
#: ``Delta a`` (1), the decay's product with the state (1), the drive
#: ``(Delta u) b`` (1), their sum (1), and ``s c`` summed over the rows
#: (2). The exponential is not counted.
_OPS = 6.0


def _sizes(model: Dict[str, Any]):
    types = model["layer_types"]
    return (sum(t == "mamba" for t in types), sum(t == "full" for t in types),
            model["mamba_expand"] * model["d_model"], model["mamba_d_state"])


def mamba_step(model: Dict[str, Any], work: Dict[str, float]
               ) -> Dict[str, float]:
    """The decode calls' steps: a row in use and a layer hold a state
    of ``Di x N`` float32, which a step has to read once and write once
    (``8 Di N`` bytes), beside its inputs and its output once (``u'``,
    ``Delta`` and ``y`` of ``Di`` values, ``B`` and ``C`` of ``N``, in
    the activations' 2 bytes). 0.7 operations a byte: memory-bound."""
    n, _, di, ns = _sizes(model)
    rows = work["decode_rows"] * n
    return {"flops": _OPS * rows * di * ns,
            "bytes": rows * (8.0 * di * ns + 2.0 * (3 * di + 2 * ns))}


def mamba_scan(model: Dict[str, Any], work: Dict[str, float]
               ) -> Dict[str, float]:
    """The chunk calls' scans: the recurrence's operations a real token
    and a layer, against the rows in and out once (``u'``, ``Delta``,
    ``y``, ``B``, ``C`` in the activations' 2 bytes) and a call's state
    in and out (``8 Di N`` bytes a layer). 16 operations a byte, under
    the chip's 240: by these counts the rows' traffic bounds it, and a
    scan that writes its ``[T, N, Di]`` decays and states to memory
    reads low."""
    n, _, di, ns = _sizes(model)
    tokens, calls = work["prefill_tokens"], work["prefill_calls"]
    return {"flops": _OPS * tokens * n * di * ns,
            "bytes": n * (tokens * 2.0 * (3 * di + 2 * ns)
                          + calls * 8.0 * di * ns)}


def matmul_flops_per_token(model: Dict[str, Any]) -> float:
    """The matrix products one computed token needs, the head apart: a
    mamba layer's ``W_in``, ``W_x``, ``W_dt`` and ``W_out``, an
    attention layer's q, k, v and o, every layer's SwiGLU."""
    n_mamba, n_full, di, ns = _sizes(model)
    d, h, hkv, dh = (model["d_model"], model["n_heads"], model["n_kv_heads"],
                     model["d_head"])
    r = model["mamba_dt_rank"]
    mamba = d * 2 * di + di * (r + 2 * ns) + r * di + di * d
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    return 2.0 * (n_mamba * mamba + n_full * attn
                  + model["n_layers"] * 3 * d * model["d_ff"])


def served_work(model: Dict[str, Any], work: Dict[str, float]
                ) -> Dict[str, float]:
    """The operations of everything the traced calls computed: the
    matrix products of every token computed, chunk and decode row
    alike; the head for the one row a chunk call or a decode row emits;
    the attention layers' scores and sums over the positions their
    queries saw (``4 H Dh`` a position); the scans' and the steps'
    recurrence."""
    _, n_full, _, _ = _sizes(model)
    tokens = work["prefill_tokens"] + work["decode_rows"]
    emitted = work["prefill_calls"] + work["decode_rows"]
    seen = work["prefill_positions_seen"] + work["decode_positions_seen"]
    parts = {
        "matmul_flops": tokens * matmul_flops_per_token(model),
        "head_flops": 2.0 * emitted * model["d_model"] * model["vocab_size"],
        "attention_flops": (4.0 * n_full * model["n_heads"] * model["d_head"]
                            * seen),
        "recurrence_flops": (mamba_scan(model, work)["flops"]
                             + mamba_step(model, work)["flops"])}
    return {**parts, "flops": sum(parts.values())}
