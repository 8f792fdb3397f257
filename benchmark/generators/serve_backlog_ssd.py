"""A backlog that never empties of agent and reasoning traffic, through
ONE CHIP'S SHARE of a model whose layers are one branch each: Mamba-2
(SSD) mixers with a float32 state of 4 MB a batch slot a layer, one
grouped-query attention layer over pages, and LatentMoE feed-forwards
(22 of 512 ungated experts in a 1024-wide latent, 128 held here; ISSUE
60). ``serve_backlog_ssm.py``'s cell (the configuration built first of
all, one seeded model under the names ``--seed`` gives the vocabulary, a
warm-up of every program the window can meet, a check of served tokens
and of the states left in their slots, in a full batch, against the
plain reference, ``serve_tok_s`` cut at the same whole block of one
fixed list in every run, the machine's standstills taken out whole) for
a sparse model whose head is its own.

What differs from ``serve_backlog_ssm``:

* **The model's head is untied** and holds a slice of the vocabulary:
  ``serve_backlog_hybrid.seeded_engine`` renames the embedding's rows
  and the head's columns alike.
* **What decides ``correct``.** ``benchmark/reference_nemotron3.py`` run
  once over prompt and outputs of each check request, given the SAME
  share of the experts; the verdict on the tokens is
  ``serve_backlog_sparse.verdict``'s (a limit on HOW MANY lie over
  ``check_tol``: five routers' near-ties tip tokens, as in the Ling
  cell) and the verdict on the states
  ``serve_backlog_hybrid.state_verdict``'s, over every mamba2 layer's
  state (the first layer's, which no router precedes, under its own
  limit). And no (token, choice) pair on a held expert was left out by
  the dispatch (``routing_counters``).
* **The counters** carry the held experts' load
  (``serve_backlog_sparse.routing_counters``: a decode-sized batch and a
  chunk) beside the state's slots and bytes.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, machine_pauses, reference_nemotron3
from benchmark.generators import serve_backlog, serve_common
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_shared as shared
from benchmark.generators import serve_backlog_sparse as sparse
from benchmark.generators import serve_backlog_ssm as ssm


def check_against_reference(engine, params, config, traffic, vocab: int,
                            rng) -> Dict[str, Any]:
    """``ssm.check_against_reference`` against this configuration's
    reference and its mamba2 layers' states."""
    n_out = traffic["check_output_len"]
    sizes = reference_nemotron3.sizes_of(config)
    prompts, results, alongside = hybrid.serve_check_requests(
        engine, traffic, vocab, rng)
    if results is None:
        return {"correct": False, "why": "a check request did not end well"}
    gaps: List[float] = []
    states: List[List[float]] = []
    kept = engine.cache.of("mamba2")[0]
    for prompt, res in zip(prompts, results):
        want, state = reference_nemotron3.logits(
            params, np.asarray(prompt + res.tokens[:-1]), sizes, last=n_out,
            states=True)
        gaps += sparse.token_gaps(want, res.tokens)
        states.append(hybrid.state_gaps(kept[:, res.slot], state))
    out = sparse.verdict(gaps, traffic)
    by_state = hybrid.state_verdict(states, traffic)
    # every slot but the check requests' own was decoding beside them
    out["fillers_decoding_alongside"] = alongside
    out["correct"] = (out["correct"] and by_state.pop("correct")
                      and alongside == traffic["check_fillers"]["n"])
    return {**out, **by_state}


def run(ctx) -> Dict[str, Any]:
    config, traffic, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    seed = ctx["seed"]
    # First of all: a program that does not know the configuration's
    # fields (the parent of the PR that brought them) fails here with a
    # TypeError, at once.
    cfg = ctx.get("model_cfg") or harness.model_config(config)

    from horovod_tpu.serve import QueueFull

    model_seed = config["seeded_weights"]["seed"]
    names = hybrid.vocabulary_names(seed, cfg.vocab_size)
    engine, params, scfg = hybrid.seeded_engine(config, traffic, names, cfg)
    rng = hybrid.Renamed([model_seed, 0], names)

    def mark(phase, **kv):    # where set-up's seconds and the peak go
        stats = ctx["devices"][0].memory_stats() or {}
        harness.say(phase=phase, programs_lowered=ctx["compiles"].count,
                    since_start_s=round(
                        time.perf_counter() - ctx["t_start"], 2),
                    peak_gb=stats.get("peak_bytes_in_use", 0) / 1e9, **kv)

    mark("engine")
    n_warm = hybrid.warm_up(engine, scfg, cfg.vocab_size, rng)
    mark("warm", requests=n_warm)
    check = check_against_reference(engine, params, config, traffic,
                                    cfg.vocab_size, rng)
    mark("check", check=check)
    routing = sparse.routing_counters(params, cfg, scfg, rng)
    mark("routing")
    blocks = ssm.length_blocks(traffic)
    block = len(blocks[0])
    harness.say(lengths={
        "n": traffic["n_lengths"], "block": block,
        "prompt_quartiles": serve_common.quartiles(
            [p for b in blocks for p, _ in b]),
        "output_quartiles": serve_common.quartiles(
            [o for b in blocks for _, o in b]),
        "output_sum_by_block": [sum(o for _, o in b) for b in blocks],
        "prompt_sum_by_block": [sum(p for p, _ in b) for b in blocks]})
    stream = ssm.request_stream(traffic, model_seed, names)

    submitted: Dict[int, int] = {}               # rid -> output length
    order: List[int] = []                        # rids as submitted
    shed = 0

    def submit_next() -> bool:
        nonlocal shed
        prompt, n_out = next(stream)
        try:
            rid = engine.submit(prompt, n_out, trace_id=len(submitted) + 1)
        except QueueFull:
            shed += 1
            return False
        submitted[rid] = n_out
        order.append(rid)
        return True

    def top_up():
        while (engine.admission_snapshot()["queue_depth"]
               < traffic["queue_target"]) and submit_next():
            pass

    # serve_backlog_ssm.run's loop: fill every slot, then step with the
    # queue topped up; a block is complete when all its requests have
    # retired, and the first block's end opens the window. Garbage is
    # collected now and kept out of the window, as there.
    for _ in range(scfg.max_batch):
        submit_next()
    gc.collect()
    gc.freeze()
    gc.disable()
    m = engine.metrics
    stamps: List[float] = []
    tokens: List[int] = []
    in_use: List[int] = []
    cuts: List[int] = []
    compiles_at_open = None
    trace = ctx["trace_window"]
    n_cut = traffic["window_blocks"]
    limit = hybrid.WINDOW_SLACK * seconds
    with machine_pauses.MachinePauses() as probe:
        while True:
            with ctx["annotate"]("bench:submit"):
                top_up()
            engine.step()
            now = time.perf_counter()
            stamps.append(now)
            tokens.append(m.tokens_generated)
            in_use.append(m.state_slots_in_use)
            while len(order) >= (len(cuts) + 1) * block and all(
                    engine.result(r) is not None for r in
                    order[len(cuts) * block:(len(cuts) + 1) * block]):
                cuts.append(len(stamps) - 2)
                if compiles_at_open is None:
                    compiles_at_open = ctx["compiles"].count
            if cuts:
                since_open = now - stamps[cuts[0]]
                trace.poll(since_open)
                if (since_open >= seconds and len(cuts) > n_cut
                        or since_open >= limit):
                    break
        stood = probe.stop()
    trace.stop()
    gc.enable()
    gc.unfreeze()
    compiles = ctx["compiles"].count - compiles_at_open

    win = serve_backlog.window_rate(stamps, tokens, cuts[:n_cut + 1], limit)
    if win is None:
        raise SystemExit("benchmark: no whole block inside the window")
    t_open, t_close = win["t_open"], win["t_close"]
    spans = serve_common.engine_spans(engine, ctx["workload"])
    still = machine_pauses.inside(stood, t_open, t_close, stamps)
    stood_s = sum(s for _, s in still)
    rate = win["tokens"] / (t_close - t_open - stood_s)
    by_excess = shared.pause_costs(still, spans, scfg.prefill_buckets)
    lo, hi = stamps[win["i_open"] + 1], stamps[win["i_close"] + 1]
    done = {rid: r for rid in submitted
            if (r := engine.result(rid)) is not None
            and lo < r.finished_at <= hi}
    failed = shed + sum(
        1 for rid, r in done.items()
        if r.status != "ok" or len(r.tokens) != submitted[rid])
    ttft = [r.first_token_at - r.submitted_at for r in done.values()
            if r.first_token_at is not None]
    durs = [b - a for a, b in zip(stamps[win["i_open"]:win["i_close"]],
                                  stamps[win["i_open"] + 1:win["i_close"] + 1])]
    usual = sorted(durs)[len(durs) // 2]
    snap = m.snapshot()
    work = ssm.traced_work(trace, spans, stamps)
    harness.say(window={"blocks": win["blocks"], "tokens": win["tokens"],
                        "rate": rate, "rate_by_the_clock": win["rate"]},
                machine_pauses={"probe": probe.state, "stood_still_s": stood_s,
                                "at_s_for_ms_excess_ms": [
                                    [round(a - t_open, 3), round(1e3 * s, 1),
                                     round(1e3 * cost, 1)]
                                    for a, s, cost in by_excess]},
                retired=len(done), longest_sequence=max(
                    (r.n_prompt + len(r.tokens) for r in done.values()),
                    default=0),
                window_s=t_close - t_open, steps=win["i_close"] - win["i_open"],
                blocks_closed_at_s=[round(stamps[c] - t_open, 2) for c in cuts],
                step_s={"median": usual, "max": max(durs)},
                warm_traffic_s=round(t_open - stamps[0], 2),
                shed=shed, compiles_in_window=compiles,
                state={"slots_in_use_mean": float(np.mean(
                           in_use[win["i_open"]:win["i_close"]])),
                       "slots_in_use_at_end": snap["state_slots_in_use"],
                       "bytes": snap["state_bytes"],
                       "blocks_high_water": snap["kv_blocks_high_water"]},
                traced_work=work,
                ttft_quartiles_ms=[1e3 * x for x in
                                   serve_common.quartiles(ttft)])
    return {
        "correct": (bool(check["correct"]) and compiles == 0
                    and routing["moe_dispatch_dropped_token_frac"] == 0
                    and win["blocks"] == n_cut),
        "attempted": len(done) + shed,
        "failed": failed,
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {"serve_tok_s": rate},
        "spans": spans,
        "counters": {"compiles_in_window": compiles, "shed": shed,
                     "state_slots_in_use": snap["state_slots_in_use"],
                     "state_bytes": snap["state_bytes"],
                     "window_blocks": win["blocks"], **routing},
        "samples": {"ttft_s": ttft},
        "traced_work": work,
        "engine": {"max_batch": scfg.max_batch,
                   "prefill_chunk": scfg.prefill_chunk},
        "model": config["model"],
    }
