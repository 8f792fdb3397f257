"""Operations and bytes of a served decoder whose every layer is latent
attention (mla) with a query rank over a chip's share of the experts,
computed from shapes and from what the engine's calls did while the
profiler ran (``traced_work`` of ``generators/serve_backlog_shared.py``):
``prefill_calls`` and their ``prefill_tokens`` (the tokens COMPUTED:
a position mapped from the prefix cache is no work, a bucket's padding
is not counted), ``prefill_positions_seen`` (for every computed token
the positions it attends, itself included: ``n offset + n (n + 1) / 2``
a call), ``prefill_latents_read`` (``offset + n`` a call),
``decode_rows`` and the ``latent_positions`` they held.
``model`` is the ``model`` group of a configuration file. Each count is
the work the ALGORITHM needs, whatever implements it: a program that
does more reads a lower share, and none can read over 100 %.
"""

from __future__ import annotations

from typing import Any, Dict


def _n_mla(model: Dict[str, Any]) -> int:
    return sum(t == "mla" for t in model["layer_types"])


def mla_prefill(model: Dict[str, Any], work: Dict[str, float]
                ) -> Dict[str, float]:
    """The chunk calls' expanded attention: a head scores a position
    it sees (``2 (Dh + R)`` operations) and sums its value (``2 Dh``);
    a call reads the latents of every position up to its end once a
    layer, ``C + R`` values of 2 bytes. The expansion of the latents to
    keys and values (``c W_ukv``) is the implementation's (the absorbed
    form has none) and is left out, as are the queries. Compute-bound at
    any chunk over a few positions."""
    h, dh = model["n_heads"], model["d_head"]
    c, r = model["mla_kv_rank"], model["mla_rope_dim"]
    n = _n_mla(model)
    return {"flops": n * h * work["prefill_positions_seen"]
            * (2.0 * (dh + r) + 2.0 * dh),
            "bytes": n * work["prefill_latents_read"] * (c + r) * 2.0}


def mla_decode_flops(model: Dict[str, Any], work: Dict[str, float]) -> float:
    """The decode calls' absorbed attention, as ``flops_ling3.mla_decode``
    counts it: a head scores a latent (``2 (C + R)``) and sums it
    (``2 C``)."""
    c, r = model["mla_kv_rank"], model["mla_rope_dim"]
    return (work["latent_positions"] * _n_mla(model) * model["n_heads"]
            * (2.0 * (c + r) + 2.0 * c))


def matmul_flops_per_token(model: Dict[str, Any],
                           held_pairs_per_token: float) -> float:
    """The matrix products one computed token needs, the head apart:
    every layer's attention projections (``W_dq``, ``W_uq``, ``W_dkv``,
    its own key's and value's share of ``W_ukv``, or as much for the
    absorbed form's ``q W_uk`` and ``o W_uv``, and ``W_o``), the dense
    layers' SwiGLU, and of a sparse layer the router, the shared expert
    and ``held_pairs_per_token`` routed experts (the pairs the routers
    put on the experts this chip holds; the others are another chip's
    work)."""
    d, h, dh = model["d_model"], model["n_heads"], model["d_head"]
    c, r, q = model["mla_kv_rank"], model["mla_rope_dim"], model["mla_q_rank"]
    attn = (d * q + q * h * (dh + r) + d * (c + r) + c * h * 2 * dh
            + h * dh * d)
    dense = 3 * d * model["d_ff_dense"]
    expert = 3 * d * model["d_ff"]
    sparse = d * model["n_experts"] + expert * (1 + held_pairs_per_token)
    n_dense = model["n_dense_layers"]
    n_sparse = model["n_layers"] - n_dense
    return 2.0 * (model["n_layers"] * attn + n_dense * dense
                  + n_sparse * sparse)


def served_work(model: Dict[str, Any], work: Dict[str, float],
                local_pair_share: float = None) -> Dict[str, float]:
    """The operations of everything the traced calls computed: the
    matrix products of every token computed, chunk and decode row
    alike; the head for the one row a call or a decode row emits; both
    attentions. ``local_pair_share``: the share of a layer's (token,
    choice) pairs that fell on held experts (the routing counter; None:
    the even router's ``held / n_experts``)."""
    if local_pair_share is None:
        local_pair_share = model["moe_experts_held"] / model["n_experts"]
    tokens = work["prefill_tokens"] + work["decode_rows"]
    emitted = work["prefill_calls"] + work["decode_rows"]
    parts = {
        "matmul_flops": tokens * matmul_flops_per_token(
            model, model["moe_top_k"] * local_pair_share),
        "head_flops": 2.0 * emitted * model["d_model"] * model["vocab_size"],
        "prefill_attention_flops": mla_prefill(model, work)["flops"],
        "decode_attention_flops": mla_decode_flops(model, work)}
    return {**parts, "flops": sum(parts.values())}
