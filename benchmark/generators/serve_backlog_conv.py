"""A backlog that never empties of assistant traffic, through a model
whose layers are gated short convolutions (two rows a batch slot and no
recurrence) beside grouped-query attention over pages, with a mixture of
experts held WHOLE on the chip (ISSUE 54): ``serve_backlog_ssm.py``'s
cell (the configuration built first of all, one seeded model under the
names ``--seed`` gives the vocabulary, a warm-up of every program the
window can meet, a check of served tokens and of what the slots are left
holding, in a full batch, against the plain reference, ``serve_tok_s``
cut at the same whole block of one fixed list in every run, the hybrid
cell's rule for the machine's standstills) for a sparse model whose
by-slot state is convolution rows.

What differs from ``serve_backlog_ssm`` (its ``length_blocks``,
``request_stream`` and loop are used as they are):

* **The seeded model** (:func:`seeded_engine`): the per-head q and k
  gains at ``seeded_weights.qk_norm_gain`` and every sparse layer's
  selection bias drawn ``N(0, seeded_weights.router_bias_std)``, so that
  a bias changes some choices (a bias of zeros could not be told from
  one left out); the embedding's rows, which are the head's columns,
  laid out under ``names``.
* **What decides ``correct``.** ``benchmark/reference_lfm2.py`` run once
  over prompt and outputs of each check request; the verdict on the
  tokens is ``serve_backlog_sparse.verdict``'s; the verdict on what the
  slots hold is :func:`rows_verdict`, over the rows of every conv layer
  (the first layer's, which no router precedes, under its own limit),
  one check request ending with its prefill so that its slot shows a
  carried row (:func:`serve_check_requests`); no (token, choice) pair
  was dropped
  (``routing_counters``: every pair is on a held expert).
* **What the traced seconds did** (``traced_work``):
  ``serve_backlog_ssm.traced_work``'s calls, rows, real tokens and
  positions seen, and the positions the chunks' convolutions ran (a
  bucket's padding included: ``convolved``), the slots the decode calls
  stepped, and the (token, choice) pairs the mixture dispatched.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, machine_pauses, reference_lfm2
from benchmark.generators import serve_backlog, serve_common
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_sparse as sparse
from benchmark.generators import serve_backlog_ssm as ssm


def seeded_params(cfg, key, config: Dict[str, Any], old_of):
    """``init_transformer``'s weights with the q and k gains and the
    selection biases of ``seeded_weights``, the embedding's rows under
    ``old_of``. The reference reads the same tree."""
    import jax

    from horovod_tpu.models import init_transformer

    how = config["seeded_weights"]
    p = init_transformer(cfg, key)

    def seeded(i, lp):
        if "q_norm" in lp:
            lp = {**lp, "q_norm": lp["q_norm"] * how["qk_norm_gain"],
                  "k_norm": lp["k_norm"] * how["qk_norm_gain"]}
        if "moe" in lp:
            bias = how["router_bias_std"] * jax.random.normal(
                jax.random.fold_in(key, 1000 + i),
                lp["moe"]["router_bias"].shape, lp["moe"]["router_bias"].dtype)
            lp = {**lp, "moe": {**lp["moe"], "router_bias": bias}}
        return lp

    return {**p, "embed": p["embed"][old_of],
            "dense_layers": [seeded(i, lp) for i, lp in
                             enumerate(p["dense_layers"])],
            "layers": [seeded(len(p["dense_layers"]) + i, lp)
                       for i, lp in enumerate(p["layers"])]}


def seeded_engine(config, traffic, names, cfg, seed=None):
    """``ssm.seeded_engine`` with :func:`seeded_params` (``seed``: another
    model than the cell's, for the tolerance tool)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serve import ServeEngine

    scfg = serve_common.serve_config(traffic)
    params = jax.jit(lambda key, old_of: seeded_params(cfg, key, config,
                                                       old_of))(
        jax.random.PRNGKey((config["seeded_weights"]["seed"]
                            if seed is None else seed) % 2 ** 32),
        jnp.asarray(np.argsort(names)))
    return ServeEngine(cfg, params, scfg, clock=time.perf_counter), params, scfg


def rows_left(engine, slot: int):
    """The rows a sequence left in ``slot``, [n_conv, taps - 1, D], as
    the reference returns them."""
    kept = engine.cache.of("conv")[0]
    return np.asarray(kept[:, slot], np.float32).reshape(
        kept.shape[0], -1, engine.model_cfg.d_model)


def serve_check_requests(engine, traffic, vocab: int, rng):
    """``hybrid.serve_check_requests`` (the fillers first, still decoding
    when the last check request ends) with an output length of its own
    for each check prompt (``check_output_lens``): a request of ONE
    token ends with its prefill, so the rows its slot is left holding
    are the ones its last chunk wrote and carried, read before any
    decode step has shifted them out."""
    fill = traffic["check_fillers"]
    fillers = [engine.submit(
        rng.integers(0, vocab, fill["prompt_len"]).tolist(),
        fill["output_len"]) for _ in range(fill["n"])]
    prompts = [rng.integers(0, vocab, n).tolist()
               for n in traffic["check_prompt_lens"]]
    rids = [engine.submit(p, n)
            for p, n in zip(prompts, traffic["check_output_lens"])]
    engine.run_until_idle()
    res = [engine.result(r) for r in rids]
    if not all(r is not None and r.status == "ok" and len(r.tokens) == n
               for r, n in zip(res, traffic["check_output_lens"])):
        return prompts, None, 0
    first = min(r.first_token_at for r in res)
    last = max(r.finished_at for r in res)
    alongside = sum(
        1 for f in map(engine.result, fillers)
        if f.status == "ok" and f.first_token_at <= first
        and f.finished_at >= last)
    return prompts, res, alongside


def rows_verdict(gaps: List[List[float]], traffic: Dict[str, Any]
                 ) -> Dict[str, Any]:
    """What the check says of rows that lie ``gaps`` off the reference's
    (``hybrid.state_gaps`` of each check request, a number a conv
    layer). Two limits, because two things are read. The FIRST conv
    layer lies before every router: its rows differ from the reference's
    by the product ``B * u`` alone, and ``check_first_state_tol`` holds
    the furthest request's (``hybrid.state_verdict``'s first limit). The
    later layers' rows also carry the routers' near-ties that fell the
    other way upstream, one request's one layer far off and most near:
    ``check_mean_state_tol`` holds the MEAN over requests and layers,
    which parts the program from a stream that moved by three times
    where the furthest layer parts them by under two (``check_why``);
    the furthest is said and not held."""
    gaps = np.asarray(gaps)                 # a nan stays one: not correct
    first, mean = float(gaps[:, 0].max()), float(gaps.mean())
    return {"state_gap_first": first, "state_gap_mean": mean,
            "state_gap_worst": float(gaps.max()),
            "first_state_tol": traffic["check_first_state_tol"],
            "mean_state_tol": traffic["check_mean_state_tol"],
            "correct": bool(first <= traffic["check_first_state_tol"]
                            and mean <= traffic["check_mean_state_tol"])}


def check_against_reference(engine, params, config, traffic, vocab: int,
                            rng) -> Dict[str, Any]:
    """``ssm.check_against_reference`` against this configuration's
    reference and its conv layers' rows."""
    sizes = reference_lfm2.sizes_of(config)
    prompts, results, alongside = serve_check_requests(engine, traffic,
                                                       vocab, rng)
    if results is None:
        return {"correct": False, "why": "a check request did not end well"}
    gaps: List[float] = []
    rows: List[List[float]] = []
    for prompt, res in zip(prompts, results):
        want, kept = reference_lfm2.logits(
            params, np.asarray(prompt + res.tokens[:-1]), sizes,
            last=len(res.tokens), states=True)
        gaps += sparse.token_gaps(want, res.tokens)
        rows.append(hybrid.state_gaps(rows_left(engine, res.slot), kept))
    out = sparse.verdict(gaps, traffic)
    by_rows = rows_verdict(rows, traffic)
    # every slot but the check requests' own was decoding beside them
    out["fillers_decoding_alongside"] = alongside
    out["correct"] = (out["correct"] and by_rows.pop("correct")
                      and alongside == traffic["check_fillers"]["n"])
    return {**out, **by_rows}


def traced_work(trace, spans, stamps, model) -> Dict[str, float]:
    """``ssm.traced_work`` of the calls between the profiler's start and
    stop, with the positions the chunks' convolutions ran
    (``prefill_convolved``: the bucket, padding too) in place of a
    scan's, and the (token, choice) pairs the mixture dispatched a
    sparse layer (every computed row, a bucket's padding and a padded
    batch row too, takes ``moe_top_k`` experts)."""
    work = ssm.traced_work(trace, spans, stamps)
    if not work:
        return work
    lo, hi = trace.started_at, trace.stopped_at
    chunks = [s["args"] for s in spans if s["name"] == "serve:prefill"
              and lo <= s["t0"] + s["dur"] <= hi]
    del work["prefill_scanned"]
    convolved = sum(a.get("convolved", a["n_tokens"]) for a in chunks)
    return {**work, "prefill_convolved": convolved,
            "pairs_dispatched": (convolved + work["slots_stepped"])
            * model["moe_top_k"]}


def run(ctx) -> Dict[str, Any]:
    config, traffic, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    seed = ctx["seed"]
    # First of all: a program that does not know the configuration's
    # fields (or its kind of layer) fails here, at once.
    cfg = ctx.get("model_cfg") or harness.model_config(config)

    from horovod_tpu.serve import QueueFull

    model_seed = config["seeded_weights"]["seed"]
    names = hybrid.vocabulary_names(seed, cfg.vocab_size)
    engine, params, scfg = seeded_engine(config, traffic, names, cfg)
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
        "resumed_share": float(np.mean(
            [p > scfg.prefill_chunk for b in blocks for p, _ in b])),
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
    work = traced_work(trace, spans, stamps, config["model"])
    slots_mean = float(np.mean(in_use[win["i_open"]:win["i_close"]]))
    harness.say(window={"blocks": win["blocks"], "tokens": win["tokens"],
                        "rate": rate, "rate_by_the_clock": win["rate"]},
                machine_pauses={"probe": probe.state, "stood_still_s": stood_s,
                                "at_s_for_ms": [
                                    [round(a - t_open, 3), round(1e3 * s, 1)]
                                    for a, s in still]},
                retired=len(done), longest_sequence=max(
                    (r.n_prompt + len(r.tokens) for r in done.values()),
                    default=0),
                window_s=t_close - t_open, steps=win["i_close"] - win["i_open"],
                blocks_closed_at_s=[round(stamps[c] - t_open, 2) for c in cuts],
                step_s={"median": usual, "max": max(durs)},
                warm_traffic_s=round(t_open - stamps[0], 2),
                shed=shed, compiles_in_window=compiles,
                state={"slots_in_use_mean": slots_mean,
                       "slots_in_use_at_end": snap["state_slots_in_use"],
                       "bytes": snap["state_bytes"],
                       "blocks_high_water": snap["kv_blocks_high_water"]},
                traced_work=work,
                ttft_quartiles_ms=[1e3 * x for x in
                                   serve_common.quartiles(ttft)])
    return {
        "correct": (bool(check["correct"]) and compiles == 0
                    and routing["moe_dispatch_dropped_token_frac"] == 0
                    and routing["moe_local_pair_share"] == 1.0
                    and win["blocks"] == n_cut),
        "attempted": len(done) + shed,
        "failed": failed,
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {"serve_tok_s": rate},
        "spans": spans,
        "counters": {"compiles_in_window": compiles, "shed": shed,
                     "state_slots_in_use": snap["state_slots_in_use"],
                     "window_blocks": win["blocks"], **routing},
        "samples": {"ttft_s": ttft},
        "traced_work": work,
        "engine": {"max_batch": scfg.max_batch,
                   "prefill_chunk": scfg.prefill_chunk},
        "model": config["model"],
    }
