"""The readings behind ``check_tol`` and ``check_allowed_over`` of
``traffic/mixed-backlog-decode.json``, taken on the chip:

    python -m benchmark.tools.trinity_tolerance --seeds 2147483651 2147483652

For each seed, at the published widths: the cell's check requests go
through the engine as the cell sends them (chunked prefill, both kinds
of cache, a full batch, 24 decode steps each), and
``benchmark/reference_trinity.py`` runs over each prompt and its served
outputs. The served tokens, and the tokens that the REFERENCE itself
would have served at those positions when it is computed wrongly, then
go through the cell's own ``serve_backlog_sparse.token_gaps`` and
``verdict``: with weights and the residual stream stored as bfloat16
(the program's precision: the near-ties of the router), stored in the
nearest precision below, ``float8_e4m3fn``, with the window mask left
out, and with the gate left out. The limit has to admit the first two
and refuse the last three (``admitted`` on each line; the tool exits 1
if it does not): it is on ``tokens_over_tol``, the number of tokens
further than ``check_tol``, because the furthest token does not tell
them apart (a router near-tie that falls the other way moves one token
far, in bf16 and in the reference stored as bf16 alike).
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmark import harness, reference_trinity as ref
from benchmark.generators import serve_backlog_sparse as cell_kind

#: name -> (how the reference is miscomputed, whether the check has to
#: admit its tokens)
CONTROLS = {"stored_as_bf16": (dict(store="bfloat16"), True),
            "stored_as_fp8": (dict(store="float8_e4m3fn"), False),
            "no_window_mask": (dict(window=False), False),
            "no_gate": (dict(gate=False), False)}


def control_verdicts(params, sizes, traffic, prompts, served):
    """``{name: verdict}`` of the served tokens (``program``) and of
    each control's tokens, all against the reference as it is."""
    import jax.numpy as jnp

    n_out = traffic["check_output_len"]
    gaps = {name: [] for name in ("program", *CONTROLS)}
    for prompt, toks in zip(prompts, served):
        seq = np.asarray(prompt + toks[:-1])
        want = np.asarray(ref.logits(params, seq, sizes, last=n_out))
        gaps["program"] += cell_kind.token_gaps(want, toks)
        for name, (how, _) in CONTROLS.items():
            kw = {k: getattr(jnp, v) if k == "store" else v
                  for k, v in how.items()}
            got = np.asarray(ref.logits(params, seq, sizes, last=n_out, **kw))
            gaps[name] += cell_kind.token_gaps(want, got.argmax(-1))
    return {name: cell_kind.verdict(g, traffic) for name, g in gaps.items()}


def as_wanted(verdicts) -> bool:
    return verdicts["program"]["correct"] and all(
        verdicts[name]["correct"] == admit
        for name, (_, admit) in CONTROLS.items())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve-trinity-ep8-mixed-backlog")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import jax

    from horovod_tpu.common.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell, config, traffic = harness.find_cell(args.workload)
    harness.require_tpu(cell["chips"])
    sizes = ref.sizes_of(config)
    ok = True
    for seed in args.seeds:
        engine, params, cfg, _ = cell_kind.make_engine(
            config, traffic, seed, harness.model_config(config))
        prompts, served, alongside = cell_kind.serve_check_requests(
            engine, traffic, cfg.vocab_size, np.random.default_rng([seed, 0]))
        del engine
        verdicts = control_verdicts(params, sizes, traffic, prompts, served)
        ok = ok and as_wanted(verdicts)
        harness.say(seed=seed, fillers_decoding_alongside=alongside,
                    as_wanted=as_wanted(verdicts),
                    **{name: {"admitted": v["correct"],
                              "over_tol": v["tokens_over_tol"],
                              "off_the_argmax":
                                  v["tokens_off_the_reference_s_argmax"],
                              "worst": v["worst_logit_gap"]}
                       for name, v in verdicts.items()})
        del params
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
