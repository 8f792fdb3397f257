"""The readings behind ``check_tol``, ``check_allowed_over``,
``check_state_tol`` and ``check_first_state_tol`` of
``traffic/agent-backlog.json``, taken on the chip:

    python -m benchmark.tools.nemotron3_tolerance --cell-model 1 --seeds 2147483651

For each seed, at the published widths: the cell's check requests go
through the engine as the cell sends them (chunked and padded prefill,
resumed chunks carrying state and rows, the states by slot, a full batch
of 128, 24 decode steps each), and ``benchmark/reference_nemotron3.py``
runs over each prompt and its served outputs, given the same share of
the experts. The served tokens, and the tokens that the REFERENCE itself
would have served at those positions when it is computed wrongly, then
go through the cell's own ``token_gaps`` and ``verdict``; the states
through its ``state_gaps`` and ``state_verdict``. The controls: the
reference with weights and the residual stream stored as bfloat16 (the
program's precision: it has to be admitted), stored in the nearest
precision below, ``float8_e4m3fn``, and with one mechanism miscomputed
(``reference_nemotron3.WRONG``): each has to be refused, by the count of tokens over
``check_tol`` or by one of the two limits on the state. A control that
the cell's limits cannot refuse at these sizes is printed under
``not_as_wanted`` and has to be named in the traffic file
(``check_not_refused``, with the readings in ``check_why``: the tool
exits 1 on any other); the CPU tests hold every one of them at a tiny
size in float32 (``tests/test_nemotron3.py``). ``--seeds`` draw models;
``--cell-model 1`` reads the cell's own (``seeded_weights.seed``) too.
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmark import harness, reference_nemotron3 as ref
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_sparse as sparse
from benchmark.generators import serve_common

#: name -> (how the reference is miscomputed, whether the check has to
#: admit it)
CONTROLS = {"stored_as_bf16": (dict(store="bfloat16"), True),
            "stored_as_fp8": (dict(store="float8_e4m3fn"), False),
            **{name: (dict(wrong=name), False) for name in ref.WRONG}}


def control_verdicts(params, sizes, traffic, prompts, served,
                     left=None, only=None):
    """``{name: verdict}`` of the served tokens and the states they
    ``left`` in their slots (``program``), and of each control's tokens
    and states (of the controls ``only`` names, if any), all against
    the reference as it is."""
    import jax.numpy as jnp

    n_out = traffic["check_output_len"]
    controls = {name: how for name, how in CONTROLS.items()
                if not only or name in only}
    gaps = {name: [] for name in ("program", *controls)}
    states = {name: [] for name in gaps}
    for i, (prompt, toks) in enumerate(zip(prompts, served)):
        seq = np.asarray(prompt + toks[:-1])
        want, state = ref.logits(params, seq, sizes, last=n_out, states=True)
        want = np.asarray(want)
        gaps["program"] += sparse.token_gaps(want, toks)
        states["program"].append(
            hybrid.state_gaps(state if left is None else left[i], state))
        for name, (how, _) in controls.items():
            kw = dict(how)
            if "store" in kw:
                kw["store"] = getattr(jnp, kw["store"])
            got, theirs = ref.logits(params, seq, sizes, last=n_out,
                                     states=True, **kw)
            gaps[name] += sparse.token_gaps(want, np.asarray(got).argmax(-1))
            states[name].append(hybrid.state_gaps(theirs, state))
    out = {}
    for name, g in gaps.items():
        by_tokens = sparse.verdict(g, traffic)
        by_state = hybrid.state_verdict(states[name], traffic)
        out[name] = {**by_tokens, **by_state,
                     "correct": bool(by_tokens["correct"]
                                     and by_state["correct"])}
    return out


def not_as_wanted(verdicts):
    """The names whose verdict is not what the check has to give."""
    bad = [] if verdicts["program"]["correct"] else ["program"]
    return bad + [name for name, (_, admit) in CONTROLS.items()
                  if name in verdicts and verdicts[name]["correct"] != admit]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve-nemotron3-super-ep4-agent-backlog")
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
    ok = True
    seeds = ([config["seeded_weights"]["seed"]] if args.cell_model else []
             ) + args.seeds
    for seed in seeds:
        engine, params, _, _ = serve_common.make_engine(
            config, traffic, seed, cfg)
        prompts, results, alongside = hybrid.serve_check_requests(
            engine, traffic, cfg.vocab_size, np.random.default_rng([seed, 0]))
        kept = engine.cache.of("mamba2")[0]
        left = [np.asarray(kept[:, r.slot]) for r in results]
        del engine, kept
        verdicts = control_verdicts(params, sizes, traffic, prompts,
                                    [r.tokens for r in results], left,
                                    args.only)
        bad = not_as_wanted(verdicts)
        ok = ok and set(bad) <= known
        harness.say(seed=seed, fillers_decoding_alongside=alongside,
                    not_as_wanted=bad,
                    **{name: {"admitted": v["correct"],
                              "over_tol": v["tokens_over_tol"],
                              "off_the_argmax":
                                  v["tokens_off_the_reference_s_argmax"],
                              "worst": round(v["worst_logit_gap"], 5),
                              "state_gap": round(v["state_gap_worst"], 6),
                              "first_state_gap":
                                  round(v["state_gap_first"], 6)}
                       for name, v in verdicts.items()})
        del params
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
