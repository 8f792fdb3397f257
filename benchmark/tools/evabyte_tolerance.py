"""The readings behind ``check_tol``, ``check_allowed_over``,
``check_logits_tol``, ``check_rows_tol`` and ``check_summaries_tol`` of
``traffic/bytedoc-backlog.json``, taken on the chip:

    python -m benchmark.tools.evabyte_tolerance --cell-model 1 --seeds A B

For each model, at the published widths: the cell's check requests go
through the engine as the cell sends them (a prompt of 12 287 whose
first output closes the sixth window and whose decode crosses the
boundary, a prompt of 1 500 of one whole and one padded chunk that reads
no page, 24 outputs each, the fillers decoding in every other slot);
what each leaves on the engine and its last token's logits through the
served programs (``serve_backlog_eva.left_by``), and
``benchmark/reference_evabyte.py`` over each prompt and its served
outputs. The served tokens, the logits of all eight rows and the rows
and summaries left, and those the REFERENCE itself gives when it is
computed wrongly, then go through the cell's own ``token_gaps``,
``verdict`` and ``kept_gaps``. The controls (``reference_evabyte.WRONG``):
the same model in bfloat16 throughout, with a bfloat16 stream, with the
softmax in bfloat16, with the unit offset folded, with the pooling's
``s`` left out, with window and summaries in two softmaxes added, and
the further ways a mechanism can be miscomputed: each has to be refused
by one of the cell's limits. ``program_bf16_stream`` is the PROGRAM
itself built with ``stream_fp32`` off and served the same requests: what
the check reads when the fault is in the program and not in the
reference. A control that the cell's limits cannot refuse at these sizes
is printed under ``not_as_wanted`` and has to be named in the traffic
file (``check_not_refused``, with the readings in ``check_why``: the
tool exits 1 on any other); the CPU tests hold every one of them at a
tiny size in float32 (``tests/test_evabyte.py``). ``--seeds`` draw
models; ``--cell-model 1`` reads the cell's own
(``seeded_weights.seed``) too; ``--raw PATH`` keeps every gap.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json

import numpy as np

from benchmark import harness, reference_evabyte as ref
from benchmark.generators import serve_backlog_eva as eva
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_sparse as sparse

#: The one control that is the program's own: the engine with a
#: bfloat16 stream.
PROGRAM_BF16_STREAM = "program_bf16_stream"


def readings(want, held, tokens, logits, kept):
    """(token gaps, logits gap, kept gaps) of what was served, or what
    a control would have served, against the reference's ``want``
    [n_out + 1, rows, V] and ``held``."""
    n_out = len(tokens)
    return (sparse.token_gaps(want[:n_out, 0], tokens),
            eva.logits_gap(logits, want[n_out]), eva.kept_gaps(kept, held))


def control_verdicts(params, sizes, traffic, prompts, served, left,
                     only=None, raw=None, more=None):
    """``{name: verdict}`` of the served tokens, logits and state
    (``program``: ``served`` the tokens and ``left`` the
    ``left_by`` of each check request), and of each control's (of the
    controls ``only`` names, if any), all against the reference as it
    is. ``more``: ``{name: (served, left)}`` of further engines that
    were served the same prompts. ``raw``: a dict that is given each
    name's gaps."""
    names = [w for w in ref.WRONG if not only or w in only]
    got = {name: ([], [], []) for name in ("program", *names, *(more or ()))}

    def add(name, r):
        got[name][0].extend(r[0])
        got[name][1].append(r[1])
        got[name][2].append(r[2])

    for i, (prompt, toks) in enumerate(zip(prompts, served)):
        seq, n_out = np.asarray(prompt + toks), len(toks)
        want, held = ref.forward(params, seq, sizes, last=n_out + 1,
                                 kept=True)
        want = np.asarray(want)
        add("program", readings(want, held, toks, *left[i]))
        for name, (theirs, their_left) in (more or {}).items():
            # another engine's own tokens: its logits are its sequence's
            w2, h2 = ref.forward(params, np.asarray(prompt + theirs[i]),
                                 sizes, last=n_out + 1, kept=True)
            add(name, readings(np.asarray(w2), h2, theirs[i],
                               *their_left[i]))
        for name in names:
            lg, theirs = ref.forward(params, seq, sizes, last=n_out + 1,
                                     kept=True, wrong=name)
            lg = np.asarray(lg)
            add(name, readings(want, held, lg[:n_out, 0].argmax(-1),
                               lg[n_out], theirs))
    out = {}
    if raw is not None:
        raw.update({name: {"token_gaps": g[0], "logits_gaps": g[1],
                           "kept": g[2]} for name, g in got.items()})
    for name, (gaps, far, kept) in got.items():
        by_tokens = sparse.verdict(gaps, traffic)
        by_state = eva.verdict(far, kept, traffic)
        out[name] = {**by_tokens, **by_state,
                     "correct": bool(by_tokens["correct"]
                                     and by_state["correct"])}
    return out


def not_as_wanted(verdicts):
    """The names whose verdict is not what the check has to give: the
    program admitted, every control refused."""
    return [name for name, v in verdicts.items()
            if v["correct"] != (name == "program")]


def served_by(config, traffic, names, cfg, seed):
    """The check requests through an engine of ``cfg``: ``(params,
    prompts, served tokens, left_by of each, fillers alongside)``."""
    engine, params, _ = eva.seeded_engine(config, traffic, names, cfg, seed)
    prompts, results, alongside = hybrid.serve_check_requests(
        engine, traffic, cfg.vocab_size, np.random.default_rng([seed, 0]))
    step = eva.logits_step(engine, cfg, max(map(len, prompts))
                           + traffic["check_output_len"])
    left = [eva.left_by(engine, params, cfg, p, r, step)
            for p, r in zip(prompts, results)]
    del engine, step
    gc.collect()
    return params, prompts, [r.tokens for r in results], left, alongside


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve-evabyte-8l-bytedoc-backlog")
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--cell-model", type=int, default=0)
    ap.add_argument("--only", nargs="*", choices=sorted(ref.WRONG),
                    help="these controls alone")
    ap.add_argument("--raw", help="every gap, as JSON, to this file")
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
    names = np.arange(cfg.vocab_size)        # the vocabulary as it is
    ok = True
    raw = {}
    seeds = ([config["seeded_weights"]["seed"]] if args.cell_model else []
             ) + args.seeds
    for seed in seeds:
        # (its engine and its weights gone before the next is built)
        low = served_by(config, traffic, names,
                        dataclasses.replace(cfg, stream_fp32=False),
                        seed)[2:4]
        gc.collect()
        params, prompts, served, left, alongside = served_by(
            config, traffic, names, cfg, seed)
        verdicts = control_verdicts(
            params, sizes, traffic, prompts, served, left, args.only,
            raw.setdefault(seed, {}),
            more={PROGRAM_BF16_STREAM: low})
        bad = not_as_wanted(verdicts)
        ok = ok and set(bad) <= known
        harness.say(seed=seed, fillers_decoding_alongside=alongside,
                    not_as_wanted=bad,
                    **{name: {"admitted": v["correct"],
                              "over_tol": v["tokens_over_tol"],
                              "worst_token": round(v["worst_logit_gap"], 5),
                              "logits_gap": round(v["logits_gap"], 6),
                              **{k: round(v[k + "_gap"], 6)
                                 for k in eva.KEPT}}
                       for name, v in verdicts.items()})
        del params, low
        if args.raw:
            with open(args.raw, "w") as f:
                json.dump(raw, f)
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
