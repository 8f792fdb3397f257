"""Operations and bytes of the layers of a served decoder whose state is
not cached keys: a delta-rule recurrence (kda) over a state a sequence,
and absorbed latent attention (mla) over a pool of latents. Computed
from shapes and from what the engine's calls did while the profiler
ran (``traced_work`` of ``generators/serve_backlog_hybrid.py``):
``decode_calls`` and their ``decode_rows`` (sequences a call, summed),
the ``latent_positions`` those rows held (summed over calls),
``prefill_calls`` and their ``prefill_tokens`` (real tokens, a bucket's
padding not counted). ``model`` is the ``model`` group of a
configuration file. Each count is the work the ALGORITHM needs,
whatever implements it: a program that does more reads a lower share,
and none can read over 100 %.
"""

from __future__ import annotations

from typing import Any, Dict


def _kinds(model: Dict[str, Any]) -> Dict[str, int]:
    types = model["layer_types"]
    return {kind: sum(t == kind for t in types) for kind in ("kda", "mla")}


def kda_step(model: Dict[str, Any], work: Dict[str, float]
             ) -> Dict[str, float]:
    """The decode calls' recurrence steps: a row, a layer and a head
    hold a state of ``Dh x Dh`` float32, which a step has to read once
    and write once (``2 x 4 Dh^2`` bytes; q, k, v, g and o are ``5 Dh``
    values beside ``Dh^2`` and are left out). Operations: decay the
    state (``Dh^2``), ``S^T k`` (``2 Dh^2``), the rank-one update (``2
    Dh^2``), ``S^T q`` (``2 Dh^2``): ``7 Dh^2``, far under the bytes'
    time at 0.9 operations a byte."""
    per_row = _kinds(model)["kda"] * model["n_heads"] * model["d_head"] ** 2
    return {"flops": 7.0 * work["decode_rows"] * per_row,
            "bytes": 8.0 * work["decode_rows"] * per_row}


def kda_scan(model: Dict[str, Any], work: Dict[str, float]
             ) -> Dict[str, float]:
    """The chunk calls' recurrence: the recurrence's own operations a
    token, a layer and a head, ``6 Dh^2`` (``S^T k``, the rank-one
    update and ``S^T q`` at ``2 Dh^2`` each; the decay is ``Dh^2``
    multiplications more and is left out, as are the chunked form's
    extra products, which are the implementation's), against the rows
    in and out (q, k, v, g in and o out, ``5 Dh`` values a token and a
    head, in the 2 bytes the program's activations have) and a call's
    state in and out (``2 x 4 Dh^2`` bytes a layer and a head). 77
    operations a byte at ``Dh`` = 128, under the chip's 240: by these
    counts the rows' traffic bounds it, and a scan that computes in
    float32 at the highest precision (six passes of the matrix unit)
    or keeps its blocks' products in memory reads low."""
    n, h, dh = _kinds(model)["kda"], model["n_heads"], model["d_head"]
    tokens, calls = work["prefill_tokens"], work["prefill_calls"]
    return {"flops": 6.0 * tokens * n * h * dh * dh,
            "bytes": n * h * (tokens * 5 * dh * 2.0 + calls * 8.0 * dh * dh)}


def mla_decode(model: Dict[str, Any], work: Dict[str, float]
               ) -> Dict[str, float]:
    """The decode calls' absorbed attention: every position a row's
    sequence holds is ``C + R`` values of 2 bytes, read once a layer;
    a head scores it (``2 (C + R)`` operations) and sums it (``2 C``).
    The queries, ``W_uk`` and ``W_uv`` are left out (small beside the
    pool at any length the cell has). 60 operations a byte, under the
    chip's 240: memory-bound. A program that gathers more positions
    than the sequences hold (whole key blocks, a whole table) reads
    low: that is the debt this share prices."""
    c, r = model["mla_kv_rank"], model["mla_rope_dim"]
    positions = work["latent_positions"] * _kinds(model)["mla"]
    return {"flops": positions * model["n_heads"] * (2.0 * (c + r) + 2.0 * c),
            "bytes": positions * (c + r) * 2.0}
