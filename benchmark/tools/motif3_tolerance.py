"""The readings behind ``check_tol`` and ``check_allowed_over`` of
``traffic/mixed-longtail-backlog.json``, taken on the chip:

    python -m benchmark.tools.motif3_tolerance --cell-model 1 --seeds 2147483651

For each seed, at the published widths: the cell's check requests go
through the engine as the cell sends them (chunked and padded prefill,
a prompt past 16384 whose keys wrap the rings, a full batch of 64, 24
decode steps each through rings and pages), and
``benchmark/reference_motif3.py`` runs over each prompt and its served
outputs, given the same share of the experts. The served tokens, and the
tokens that the REFERENCE itself would have served at those positions
when it is computed wrongly, then go through the cell's own
``token_gaps`` and ``verdict``. The controls: the reference with weights
and the stream stored as bfloat16 (the program's precision: it has to be
admitted), stored in the nearest precision below, ``float8_e4m3fn``, and
with one mechanism miscomputed (``reference_motif3.WRONG``): each has to
be refused, by the count of tokens over ``check_tol``. A control that
the cell's limits cannot refuse at these sizes is printed under
``not_as_wanted`` and has to be named in the traffic file
(``check_not_refused``, with the readings in ``check_why``: the tool
exits 1 on any other); the CPU tests hold every one of them at a tiny
size in float32 (``tests/test_motif3.py``). ``--seeds`` draw models;
``--cell-model 1`` reads the cell's own (``seeded_weights.seed``) too.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from benchmark import harness, reference_motif3 as ref
from benchmark.generators import serve_backlog_gdla as gdla
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_sparse as sparse

#: name -> (how the reference is miscomputed, whether the check has to
#: admit it)
CONTROLS = {"stored_as_bf16": (dict(store="bfloat16"), True),
            "stored_as_fp8": (dict(store="float8_e4m3fn"), False),
            **{name: (dict(wrong=name), False) for name in ref.WRONG}}


def control_verdicts(params, sizes, traffic, prompts, served, only=None):
    """``{name: verdict}`` of the served tokens (``program``) and of
    each control's tokens (of the controls ``only`` names, if any), all
    against the reference as it is."""
    import jax.numpy as jnp

    n_out = traffic["check_output_len"]
    controls = {name: how for name, how in CONTROLS.items()
                if not only or name in only}
    gaps = {name: [] for name in ("program", *controls)}
    for prompt, toks in zip(prompts, served):
        seq = np.asarray(prompt + toks[:-1])
        want = np.asarray(ref.logits(params, seq, sizes, last=n_out))
        gaps["program"] += sparse.token_gaps(want, toks)
        for name, (how, _) in controls.items():
            kw = dict(how)
            if "store" in kw:
                kw["store"] = getattr(jnp, kw["store"])
            got = ref.logits(params, seq, sizes, last=n_out, **kw)
            gaps[name] += sparse.token_gaps(want, np.asarray(got).argmax(-1))
    return {name: sparse.verdict(g, traffic) for name, g in gaps.items()}


def not_as_wanted(verdicts):
    """The names whose verdict is not what the check has to give."""
    bad = [] if verdicts["program"]["correct"] else ["program"]
    return bad + [name for name, (_, admit) in CONTROLS.items()
                  if name in verdicts and verdicts[name]["correct"] != admit]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="serve-motif3-ep8-mixed-longtail-backlog")
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--cell-model", type=int, default=0)
    ap.add_argument("--only", nargs="*", choices=sorted(CONTROLS),
                    help="these controls alone")
    args = ap.parse_args()
    import jax

    from horovod_tpu.common.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell, config, traffic = harness.find_cell(args.workload)
    harness.require_tpu(cell["chips"])
    sizes = ref.sizes_of(config)
    cfg = harness.model_config(config)
    known = set(traffic.get("check_not_refused", ()))
    names = np.arange(cfg.vocab_size)
    ok = True
    seeds = ([config["seeded_weights"]["seed"]] if args.cell_model else []
             ) + args.seeds
    for seed in seeds:
        t0 = time.perf_counter()
        engine, params, _ = gdla.seeded_engine(config, traffic, names, cfg,
                                               seed)
        prompts, results, alongside = hybrid.serve_check_requests(
            engine, traffic, cfg.vocab_size, np.random.default_rng([seed, 0]))
        del engine
        verdicts = control_verdicts(params, sizes, traffic, prompts,
                                    [r.tokens for r in results], args.only)
        bad = not_as_wanted(verdicts)
        ok = ok and set(bad) <= known
        harness.say(seed=seed, fillers_decoding_alongside=alongside,
                    not_as_wanted=bad, took_s=time.perf_counter() - t0,
                    **{name: {"admitted": v["correct"],
                              "over_tol": v["tokens_over_tol"],
                              "off_the_argmax":
                                  v["tokens_off_the_reference_s_argmax"],
                              "worst": round(v["worst_logit_gap"], 5)}
                       for name, v in verdicts.items()})
        del params
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
