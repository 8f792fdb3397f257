"""The readings behind ``check_tol``, ``check_allowed_over``,
``check_mean_state_tol`` and ``check_first_state_tol`` of
``traffic/assistant-backlog.json``, taken on the chip:

    python -m benchmark.tools.lfm2_tolerance --cell-model 1 --seeds A B

For each model, at the published widths: the cell's check requests go
through the engine as the cell sends them (a prompt whose resumed chunk
is ONE position and which ends with its prefill, so that its slot is
left holding a carried row; a prompt of one padded chunk and one of two
chunks, each decoding ``check_output_lens`` tokens; two rows a slot, the
fillers decoding in every other slot), and ``benchmark/reference_lfm2.py``
runs over each prompt and its served outputs. The served tokens, and the
tokens that the REFERENCE itself would have served at those positions
when it is computed wrongly, then go through the cell's own
``token_gaps`` and ``verdict``; the rows each leaves in the conv layers
through its ``state_gaps`` and ``rows_verdict``. The controls: the
reference with weights and the residual stream stored as bfloat16 (the
program's precision: it has to be admitted), stored in the nearest
precision below, ``float8_e4m3fn``, with one mechanism miscomputed
(``reference_lfm2.WRONG``), with the padding of each request's last
chunk pushed through the rows (``pads_convolved``), and with the rows
not carried into a resumed chunk (``rows_not_carried``): each has to be
refused, by the count of tokens over ``check_tol`` or by one of the two
limits on the rows. A control that the cell's limits cannot refuse at
these sizes is printed under ``not_as_wanted`` and has to be named in
the traffic file (``check_not_refused``, with the readings in
``check_why``: the tool exits 1 on any other); the CPU tests hold every
one of them at a tiny size in float32 (``tests/test_lfm2.py``).
``--seeds`` draw models; ``--cell-model 1`` reads the cell's own
(``seeded_weights.seed``) too; ``--raw PATH`` keeps every token's gap
and every request's rows' gap a layer, of the program and of each
control, for choosing the limits.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark import harness, reference_lfm2 as ref
from benchmark.generators import serve_backlog_conv as conv
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_sparse as sparse
from benchmark.tools.jamba2_tolerance import padding_of

#: name -> (how the reference is miscomputed, whether the check has to
#: admit it)
CONTROLS = {"stored_as_bf16": (dict(store="bfloat16"), True),
            "stored_as_fp8": (dict(store="float8_e4m3fn"), False),
            **{name: (dict(wrong=name), False) for name in ref.WRONG},
            "pads_convolved": (dict(pads=True), False),
            "rows_not_carried": (dict(cut=True), False)}


def control_verdicts(params, sizes, traffic, scfg, prompts, served,
                     left=None, only=None, raw=None):
    """``{name: verdict}`` of the served tokens and the rows they
    ``left`` in their slots (``program``), and of each control's tokens
    and rows (of the controls ``only`` names, if any), all against the
    reference as it is. ``raw``: a dict that is given each name's
    gaps."""
    import jax.numpy as jnp

    controls = {name: how for name, how in CONTROLS.items()
                if not only or name in only}
    gaps = {name: [] for name in ("program", *controls)}
    rows = {name: [] for name in gaps}
    for i, (prompt, toks) in enumerate(zip(prompts, served)):
        seq, n_out = np.asarray(prompt + toks[:-1]), len(toks)
        want, kept = ref.logits(params, seq, sizes, last=n_out, states=True)
        want = np.asarray(want)
        gaps["program"] += sparse.token_gaps(want, toks)
        rows["program"].append(
            hybrid.state_gaps(kept if left is None else left[i], kept))
        for name, (how, _) in controls.items():
            kw = dict(how)
            if "store" in kw:
                kw["store"] = getattr(jnp, kw["store"])
            if kw.pop("pads", False):
                kw["pads"] = (len(prompt), padding_of(len(prompt), scfg))
            if kw.pop("cut", False):
                # the last chunk's start (0: a prompt of one chunk, which
                # carries nothing and cannot show the fault)
                kw["cut"] = (len(prompt) - 1) // scfg.prefill_chunk \
                    * scfg.prefill_chunk
            got, theirs = ref.logits(params, seq, sizes, last=n_out,
                                     states=True, **kw)
            gaps[name] += sparse.token_gaps(want, np.asarray(got).argmax(-1))
            rows[name].append(hybrid.state_gaps(theirs, kept))
    out = {}
    if raw is not None:
        raw.update({name: {"token_gaps": gaps[name], "rows": rows[name]}
                    for name in gaps})
    for name, g in gaps.items():
        by_tokens = sparse.verdict(g, traffic)
        by_rows = conv.rows_verdict(rows[name], traffic)
        out[name] = {**by_tokens, **by_rows,
                     "correct": bool(by_tokens["correct"]
                                     and by_rows["correct"])}
    return out


def not_as_wanted(verdicts):
    """The names whose verdict is not what the check has to give."""
    bad = [] if verdicts["program"]["correct"] else ["program"]
    return bad + [name for name, (_, admit) in CONTROLS.items()
                  if name in verdicts and verdicts[name]["correct"] != admit]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="serve-lfm2-8b-a1b-assistant-backlog")
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--cell-model", type=int, default=0)
    ap.add_argument("--only", nargs="*", choices=sorted(CONTROLS),
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
        engine, params, scfg = conv.seeded_engine(config, traffic, names,
                                                  cfg, seed)
        prompts, results, alongside = conv.serve_check_requests(
            engine, traffic, cfg.vocab_size, np.random.default_rng([seed, 0]))
        left = [conv.rows_left(engine, r.slot) for r in results]
        del engine
        verdicts = control_verdicts(params, sizes, traffic, scfg, prompts,
                                    [r.tokens for r in results], left,
                                    args.only, raw.setdefault(seed, {}))
        bad = not_as_wanted(verdicts)
        ok = ok and set(bad) <= known
        harness.say(seed=seed, fillers_decoding_alongside=alongside,
                    not_as_wanted=bad,
                    **{name: {"admitted": v["correct"],
                              "over_tol": v["tokens_over_tol"],
                              "off_the_argmax":
                                  v["tokens_off_the_reference_s_argmax"],
                              "worst": round(v["worst_logit_gap"], 5),
                              "rows_gap": round(v["state_gap_worst"], 6),
                              "mean_rows_gap":
                                  round(v["state_gap_mean"], 6),
                              "first_rows_gap":
                                  round(v["state_gap_first"], 6)}
                       for name, v in verdicts.items()})
        del params
        if args.raw:
            with open(args.raw, "w") as f:
                json.dump(raw, f)
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
