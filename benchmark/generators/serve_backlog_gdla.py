"""A backlog that never empties of documents and transcripts of mixed
length with answers of a thousand tokens, through ONE CHIP'S SHARE of a
model whose every layer is grouped differential attention over a latent
cache (GDLA): window layers that keep a RING of latents a batch slot
beside full layers that keep latent pages behind the block tables, a
four-stream mHC residual, and a share of PolyNorm experts (ISSUE 63).
``serve_backlog_ssd.py``'s cell (the configuration built first of all,
one seeded model under the names ``--seed`` gives the vocabulary, a
warm-up of every program the window can meet, a check of served tokens
in a full batch against the plain reference, ``serve_tok_s`` cut at the
same whole block of one fixed list in every run, the machine's
standstills taken out whole) with ``serve_backlog_hybrid.py``'s long
tail (``long_every``, ``long_prompt_len``) and no state to read back.

What differs from ``serve_backlog_ssd``:

* **The seeded model** (:func:`seeded_engine`): ``w_uq`` times
  ``seeded_weights.q_gain`` in every layer, so that a query weighs a
  few keys and a window is one; the untied head and the embedding laid
  out under ``names``.
* **What decides ``correct``.** ``benchmark/reference_motif3.py`` run
  once over prompt and outputs of each check request (one of two
  chunks; one past 16384, whose keys wrap a window layer's ring many
  times in prefill and fill a thousand pages of the full layer), given
  the SAME share of the experts; the served tokens are held to the
  reference's LOGITS (``serve_backlog_sparse.token_gaps``: the
  reference's largest logit less its logit for the served token) by
  ``serve_backlog_sparse.verdict`` (a limit on how many lie over
  ``check_tol``). The reference runs over each request when that
  request has been served and before the next is read, a block of
  positions at a time, beside the engine. And no (token, choice) pair
  on a held expert was left out by the dispatch (``routing_counters``).
* **What the traced seconds did** (:func:`traced_work`): the calls,
  rows and real tokens; the ring places and the page positions the
  decode rows' attention had to read a layer (the spans' own
  ``latent_ring_places`` and ``latent_positions``); the keys a chunk's
  queries saw under the window and without it.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, machine_pauses, reference_motif3
from benchmark.generators import serve_backlog, serve_common
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_shared as shared
from benchmark.generators import serve_backlog_sparse as sparse


def seeded_engine(config, traffic, names, cfg, seed=None):
    """The engine of the one model of ``seeded_weights.seed`` (``seed``:
    another model than the cell's, for the tolerance tool), its
    embedding's rows and its head's columns laid out under ``names``,
    every layer's ``w_uq`` times ``seeded_weights.q_gain``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve import ServeEngine

    scfg = serve_common.serve_config(traffic)
    how = config["seeded_weights"]

    def init(key, old_of):
        p = init_transformer(cfg, key)

        def sharpened(layers):
            return [{**lp, "w_uq": lp["w_uq"] * jnp.asarray(
                how["q_gain"], lp["w_uq"].dtype)} for lp in layers]

        return {**p, "layers": sharpened(p["layers"]),
                "dense_layers": sharpened(p["dense_layers"]),
                "embed": p["embed"][old_of],
                "lm_head": p["lm_head"][:, old_of]}

    params = jax.jit(init)(
        jax.random.PRNGKey((how["seed"] if seed is None else seed) % 2 ** 32),
        jnp.asarray(np.argsort(names)))
    return ServeEngine(cfg, params, scfg, clock=time.perf_counter), params, scfg


def check_against_reference(engine, params, config, traffic, vocab: int,
                            rng) -> Dict[str, Any]:
    """The check requests served in a full batch, then each one's
    served tokens against the reference's logits."""
    n_out = traffic["check_output_len"]
    sizes = reference_motif3.sizes_of(config)
    prompts, results, alongside = hybrid.serve_check_requests(
        engine, traffic, vocab, rng)
    if results is None:
        return {"correct": False, "why": "a check request did not end well"}
    gaps: List[float] = []
    for prompt, res in zip(prompts, results):
        want = reference_motif3.logits(
            params, np.asarray(prompt + res.tokens[:-1]), sizes, last=n_out)
        gaps += sparse.token_gaps(np.asarray(want), res.tokens)
    out = sparse.verdict(gaps, traffic)
    # every slot but the check requests' own was decoding beside them
    out["fillers_decoding_alongside"] = alongside
    out["correct"] = (out["correct"]
                      and alongside == traffic["check_fillers"]["n"])
    return out


def traced_work(trace, spans, stamps, model) -> Dict[str, float]:
    """``hybrid.traced_work`` (calls, rows and real tokens between the
    profiler's start and stop), and of the same calls: what the decode
    rows' attention had to read a layer (the ring places inside each
    row's window, the positions in the latent pages), the keys the
    chunks' real queries saw (the i-th of a call at ``offset``:
    ``offset + i + 1`` in a full layer, at most ``window`` of them in a
    window layer), and the seconds from the profiler's start to the end
    of the last call inside."""
    work = hybrid.traced_work(trace, spans, stamps, [0] * len(stamps))
    if not work:
        return work
    lo, hi = trace.started_at, trace.stopped_at
    calls = [s for s in spans if lo <= s["t0"] + s["dur"] <= hi
             and s["name"] in ("serve:prefill", "serve:decode")]
    chunks = [s["args"] for s in calls if s["name"] == "serve:prefill"]
    steps = [s["args"] for s in calls if s["name"] == "serve:decode"]
    window = model["attn_window"]
    del work["latent_positions"]

    def seen_window(a):
        at = a["offset"] + np.arange(1, a["n_tokens"] + 1)
        return int(np.minimum(at, window).sum())

    return {**work,
            "decode_ring_places": sum(a["latent_ring_places"]
                                      for a in steps),
            "decode_latent_positions": sum(a["latent_positions"]
                                           for a in steps),
            "prefill_seen_full": sum(
                a["n_tokens"] * a["offset"]
                + a["n_tokens"] * (a["n_tokens"] + 1) // 2 for a in chunks),
            "prefill_seen_window": sum(map(seen_window, chunks)),
            "traced_s": max((s["t0"] + s["dur"] for s in calls),
                            default=lo) - lo}


def run(ctx) -> Dict[str, Any]:
    config, traffic, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    seed = ctx["seed"]
    # First of all: a program that does not know the configuration's
    # fields or its kind of layer (the parent of the PR that brought
    # them) fails here, at once.
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
    blocks = hybrid.length_blocks(traffic)
    block = len(blocks[0])
    harness.say(lengths={
        "n": traffic["n_lengths"], "block": block,
        "prompt_quartiles": serve_common.quartiles(
            [p for b in blocks for p, _ in b]),
        "output_quartiles": serve_common.quartiles(
            [o for b in blocks for _, o in b]),
        "long_by_block": [sum(p >= traffic["long_prompt_len"]["min"]
                              for p, _ in b) for b in blocks],
        "output_sum_by_block": [sum(o for _, o in b) for b in blocks],
        "prompt_sum_by_block": [sum(p for p, _ in b) for b in blocks]})
    stream = hybrid.request_stream(traffic, model_seed, names)

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
    work = traced_work(trace, spans, stamps, config["model"])
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
                cache={"latent_positions_max": snap["kv_latent_positions_max"],
                       "latent_ring_positions_max":
                           snap["kv_latent_ring_positions_max"],
                       "ring_blocks_in_use": snap["kv_window_blocks_in_use"],
                       "blocks_high_water": snap["kv_blocks_high_water"],
                       "latent_ring_decode_pages":
                           snap["latent_ring_decode_pages_total"]},
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
                     "kv_latent_positions_max":
                         snap["kv_latent_positions_max"],
                     "kv_latent_ring_positions_max":
                         snap["kv_latent_ring_positions_max"],
                     "window_blocks": win["blocks"], **routing},
        "samples": {"ttft_s": ttft},
        "traced_work": work,
        "engine": {"max_batch": scfg.max_batch,
                   "prefill_chunk": scfg.prefill_chunk},
        "model": config["model"],
    }
