"""A backlog that never empties, through a model whose layers keep a
recurrent state a batch slot and a paged pool of latents (ISSUE 38):
``serve_backlog_sparse.py``'s cell (the configuration built first of
all, a warm-up of every program the window can meet, a check of many served
tokens in a full batch against the plain reference, ``serve_tok_s`` cut
at whole blocks of one fixed list) for a traffic mix with a long tail
and a state that is read back.

What differs from ``serve_backlog_sparse``:

* **The lengths are a mixture**: of every ``long_every`` prompts one is
  a long document (``long_prompt_len``), the others come from
  ``prompt_len``; the fixed multiset is dealt so that every block of
  ``block_requests`` holds the same number of long documents and nearly
  equal sums of prompt and of output tokens.
* **What decides ``correct``**: as there (``token_gaps`` and ``verdict``
  are that module's), against ``benchmark/reference_ling3.py``; and
  the recurrent state that each check request leaves in its batch slot,
  read from the device at its end, lies within ``check_state_tol`` of
  the reference's state after the same tokens (the norm of the
  difference over the reference's norm, a layer), and the first kda
  layer's, which no router precedes, within ``check_first_state_tol``
  (:func:`state_verdict`); no (token, choice) pair on a held expert was
  dropped; nothing compiled inside the window; ``window_blocks`` whole
  blocks closed in it.
* **Where the window closes**: at the cut that ends its
  ``window_blocks``-th block, the same block in every run, and the
  loop goes on until that cut has come (:data:`WINDOW_SLACK` bounds
  the wait). A batch of 64 holds two blocks of 32 at a time, so the
  cuts are not evenly spaced (10, 18 and 11 s apart on the chip) and
  the rate to the third cut is 2.3 % under the rate to the fourth,
  which falls 39.9-40.3 s after the opening: cut "at the last block
  inside the seconds" a run would read one rate or the other by a
  tenth of a second. Four blocks are the whole of ``--seconds`` to
  within a hundredth; three (the first hand-in of PR 38) left the last
  11 s unread and spread 0.61-0.66 % over seeds, over half the bound.
* **What is taken out of the window's seconds**: the time in which
  the whole machine stood still, as a process beside this one saw it
  (``benchmark/machine_pauses.py``: every process of a one-chip
  machine stops for 95-125 ms whenever a neighbour on its host takes
  or leaves a chip; a window met none to four such pauses, a quarter
  of a percent each, and their count was the cell's spread, 0.74 % on
  one machine and 0.24 % on another on the same code). Only a pause of
  50 ms or more that the probe saw, inside the window, in which this
  loop finished no step; nothing of the program's own (a collection, a
  slow call, a stall of the device) can be among them, because the
  probe shares nothing with it. ``window.rate_by_the_clock`` in the
  run's output is the rate with nothing taken out, and
  ``machine_pauses`` says when and how long; without a probe the two
  rates are one.
* **One model and one set of prompts for every ``--seed``, under a
  vocabulary that ``--seed`` renames** (:func:`seeded_engine`,
  :class:`Renamed`): a step's time follows the pairs that the weights
  put on the 128 held experts, and over models drawn from ``--seed``
  the same schedule took 2-3 % longer or shorter (decode alone 8.73,
  8.96 and 9.14 s for the same 384 steps on three seeds, each the same
  to 0.06 % from process to process: PR 38, my chip runs), which a
  bound of 1 % cannot carry. So the weights, the check's prompts and
  the traffic's are drawn from the configuration file's
  ``seeded_weights.seed``; ``--seed`` draws a permutation of the
  vocabulary, under which the embedding's rows, the head's columns and
  every prompt's ids are renamed: the same function of the same
  symbols, so every run does the same work, as every run has the same
  schedule (``train_moe_window.py`` came to the same for the same
  reason).
* **What the traced seconds did**, for the rooflines: the decode calls,
  their rows and the latent positions they had to read, the chunk calls
  and their tokens, counted off the engine's spans between the
  profiler's start and its stop (``traced_work``).
"""

from __future__ import annotations

import gc
import itertools
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import harness, lengths, machine_pauses, reference_ling3
from benchmark.generators import serve_backlog, serve_common
from benchmark.generators import serve_backlog_sparse as sparse

# The window's last block may close this much past ``--seconds`` (as a
# share of them); a run in which it has not closed by then is cut there
# and is not correct.
WINDOW_SLACK = 1.25


def length_blocks(traffic: Dict[str, Any]) -> List[List[Tuple[int, int]]]:
    """The fixed multiset of ``n_lengths`` (prompt, output) pairs in
    blocks of ``block_requests``: each block's long documents, its
    other prompts and its outputs dealt by ``lengths.balanced_deal``,
    paired by one fixed permutation a block."""
    n, size = traffic["n_lengths"], traffic["block_requests"]
    every = traffic["long_every"]
    if n % size or size % every:
        raise ValueError(f"n_lengths {n}, block_requests {size} and "
                         f"long_every {every} do not divide")
    n_blocks, n_long = n // size, n // every

    def deal(key, count):
        return lengths.balanced_deal(
            [int(round(x)) for x in lengths.stratified(traffic[key], count)],
            n_blocks)

    prompts = [a + b for a, b in zip(deal("long_prompt_len", n_long),
                                     deal("prompt_len", n - n_long))]
    rng = np.random.default_rng(0)
    return [[(p[int(i)], o[int(j)]) for i, j in
             zip(rng.permutation(size), rng.permutation(size))]
            for p, o in zip(prompts, deal("output_len", n))]


class Renamed:
    """A generator of token ids whose every draw is the draw of
    ``np.random.default_rng(key)`` under the names ``--seed`` gives the
    vocabulary (``names[old id]``): what ``rng.integers`` is to the
    generators this one follows."""

    def __init__(self, key, names):
        self._rng, self._names = np.random.default_rng(key), names

    def integers(self, low, high, size):
        return self._names[self._rng.integers(low, high, size)]


def vocabulary_names(seed: int, vocab: int):
    """The name ``--seed`` gives each id of the vocabulary."""
    return np.random.default_rng([seed, 2]).permutation(vocab)


def request_stream(traffic: Dict[str, Any], model_seed: int, names):
    rng = Renamed([model_seed, 1], names)
    pairs = [pair for block in length_blocks(traffic) for pair in block]
    for n_prompt, n_out in itertools.cycle(pairs):
        yield rng.integers(0, len(names), n_prompt).tolist(), n_out


def seeded_engine(config, traffic, names, cfg):
    """``serve_common.make_engine`` for the one model of
    ``seeded_weights.seed``, its embedding's rows and its head's
    columns laid out under ``names``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve import ServeEngine

    scfg = serve_common.serve_config(traffic)

    def init(key, old_of):
        p = init_transformer(cfg, key)
        return {**p, "embed": p["embed"][old_of],
                "lm_head": p["lm_head"][:, old_of]}

    params = jax.jit(init)(
        jax.random.PRNGKey(config["seeded_weights"]["seed"] % 2 ** 32),
        jnp.asarray(np.argsort(names)))
    return ServeEngine(cfg, params, scfg, clock=time.perf_counter), params, scfg


def warm_up(engine, scfg, vocab: int, rng) -> int:
    """``sparse.warm_up`` (every chunk bucket of ``prefill_resume``, and
    ``decode``), and the monolithic ``prefill`` at EVERY bucket: this
    mix has prompts shorter than a chunk, which take it at their own
    bucket. One request at a time, three tokens each."""
    plens = ([scfg.prefill_chunk + b for b in scfg.prefill_buckets]
             + list(scfg.prefill_buckets))
    for plen in plens:
        engine.submit(rng.integers(0, vocab, plen).tolist(), 3)
        engine.run_until_idle()
    return len(plens)


def serve_check_requests(engine, traffic, vocab: int, rng):
    """``sparse.serve_check_requests`` (a FULL batch: the fillers go in
    first and are still decoding when the last check request ends),
    returning the check requests' results themselves: a result names
    the batch slot whose state the sequence left behind."""
    n_out = traffic["check_output_len"]
    fill = traffic["check_fillers"]
    fillers = [engine.submit(
        rng.integers(0, vocab, fill["prompt_len"]).tolist(),
        fill["output_len"]) for _ in range(fill["n"])]
    prompts = [rng.integers(0, vocab, n).tolist()
               for n in traffic["check_prompt_lens"]]
    rids = [engine.submit(p, n_out) for p in prompts]
    engine.run_until_idle()
    res = [engine.result(r) for r in rids]
    if not all(r is not None and r.status == "ok"
               and len(r.tokens) == n_out for r in res):
        return prompts, None, 0
    first = min(r.first_token_at for r in res)
    last = max(r.finished_at for r in res)
    alongside = sum(
        1 for f in map(engine.result, fillers)
        if f.status == "ok" and f.first_token_at <= first
        and f.finished_at >= last)
    return prompts, res, alongside


def state_gaps(left, want) -> List[float]:
    """For each kda layer, how far the state a sequence ``left`` in its
    slot lies from the reference's (``want``), both [n_kda, H, Dh, Dh]:
    the norm of the difference over the norm of the reference's."""
    left, want = np.asarray(left, np.float32), np.asarray(want, np.float32)
    return [float(np.linalg.norm(a - b) / np.linalg.norm(b))
            for a, b in zip(left, want)]


def state_verdict(gaps: List[List[float]], traffic: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """What the check says of states that lie ``gaps`` off the
    reference's (:func:`state_gaps` of each check request). Two limits,
    because two things are read. The FIRST kda layer lies before every
    router: its state differs from the reference's by arithmetic alone,
    and ``check_first_state_tol`` holds it. Every later layer's state
    also carries the routers' near-ties that fell the other way
    upstream, as the tokens do (``check_why``), and
    ``check_state_tol`` holds the furthest of them."""
    by_layer = np.asarray(gaps).max(0)      # a nan stays one: not correct
    first, worst = float(by_layer[0]), float(by_layer.max())
    return {"state_gap_first": first, "state_gap_worst": worst,
            "first_state_tol": traffic["check_first_state_tol"],
            "state_tol": traffic["check_state_tol"],
            "correct": bool(first <= traffic["check_first_state_tol"]
                            and worst <= traffic["check_state_tol"])}


def check_against_reference(engine, params, config, traffic, vocab: int,
                            rng) -> Dict[str, Any]:
    n_out = traffic["check_output_len"]
    sizes = reference_ling3.sizes_of(config)
    prompts, results, alongside = serve_check_requests(engine, traffic,
                                                       vocab, rng)
    if results is None:
        return {"correct": False, "why": "a check request did not end well"}
    gaps: List[float] = []
    states: List[List[float]] = []
    kept = engine.cache.of("kda")[0]
    for prompt, res in zip(prompts, results):
        want, state = reference_ling3.logits(
            params, np.asarray(prompt + res.tokens[:-1]), sizes, last=n_out,
            states=True)
        gaps += sparse.token_gaps(want, res.tokens)
        states.append(state_gaps(kept[:, res.slot], state))
    out = sparse.verdict(gaps, traffic)
    by_state = state_verdict(states, traffic)
    # every slot but the check requests' own was decoding beside them
    out["fillers_decoding_alongside"] = alongside
    out["correct"] = (out["correct"] and by_state.pop("correct")
                      and alongside == traffic["check_fillers"]["n"])
    return {**out, **by_state}


def traced_work(trace, spans, stamps, latent_live) -> Dict[str, float]:
    """What the engine's calls between the profiler's start and stop
    did: a call counts when its span ends inside."""
    if trace.started_at is None or trace.stopped_at is None:
        return {}
    lo, hi = trace.started_at, trace.stopped_at
    inside = [s for s in spans if lo <= s["t0"] + s["dur"] <= hi]
    decode = [s for s in inside if s["name"] == "serve:decode"]
    prefill = [s for s in inside if s["name"] == "serve:prefill"]
    return {"decode_calls": len(decode),
            "decode_rows": sum(s["args"]["n_active"] for s in decode),
            "latent_positions": sum(
                live for t, live in zip(stamps, latent_live) if lo <= t <= hi),
            "prefill_calls": len(prefill),
            "prefill_tokens": sum(s["args"]["n_tokens"] for s in prefill)}


def run(ctx) -> Dict[str, Any]:
    config, traffic, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    seed = ctx["seed"]
    # First of all: a program that does not know the configuration's
    # fields fails here with a TypeError, at once.
    cfg = ctx.get("model_cfg") or harness.model_config(config)

    from horovod_tpu.serve import QueueFull

    model_seed = config["seeded_weights"]["seed"]
    names = vocabulary_names(seed, cfg.vocab_size)
    engine, params, scfg = seeded_engine(config, traffic, names, cfg)
    rng = Renamed([model_seed, 0], names)

    def mark(phase, **kv):    # where set-up's seconds and the peak go
        stats = ctx["devices"][0].memory_stats() or {}
        harness.say(phase=phase, programs_lowered=ctx["compiles"].count,
                    since_start_s=round(
                        time.perf_counter() - ctx["t_start"], 2),
                    peak_gb=stats.get("peak_bytes_in_use", 0) / 1e9, **kv)

    mark("engine")
    n_warm = warm_up(engine, scfg, cfg.vocab_size, rng)
    mark("warm", requests=n_warm)
    check = check_against_reference(engine, params, config, traffic,
                                    cfg.vocab_size, rng)
    mark("check", check=check)
    routing = sparse.routing_counters(params, cfg, scfg, rng)
    mark("routing")
    blocks = length_blocks(traffic)
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
    stream = request_stream(traffic, model_seed, names)

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

    # serve_backlog_sparse.run's loop: fill every slot, then step with
    # the queue topped up; a block is complete when all its requests
    # have retired, and the first block's end opens the window. Garbage
    # is collected now and kept out of the window, as there.
    for _ in range(scfg.max_batch):
        submit_next()
    gc.collect()
    gc.freeze()
    gc.disable()
    m = engine.metrics
    stamps: List[float] = []
    tokens: List[int] = []
    latent_live: List[int] = []
    cuts: List[int] = []
    compiles_at_open = None
    trace = ctx["trace_window"]
    n_cut = traffic["window_blocks"]
    limit = WINDOW_SLACK * seconds
    with machine_pauses.MachinePauses() as probe:
        while True:
            with ctx["annotate"]("bench:submit"):
                top_up()
            engine.step()
            now = time.perf_counter()
            stamps.append(now)
            tokens.append(m.tokens_generated)
            latent_live.append(m.kv_latent_positions_live)
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
    still = machine_pauses.inside(stood, t_open, t_close, stamps)
    stood_s = sum(s for _, s in still)
    rate = win["tokens"] / (t_close - t_open - stood_s)
    spans = serve_common.engine_spans(engine, ctx["workload"])
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
    work = traced_work(trace, spans, stamps, latent_live)
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
                state={"slots_in_use": snap["state_slots_in_use"],
                       "bytes": snap["state_bytes"],
                       "latent_positions_max":
                           snap["kv_latent_positions_max"],
                       "latent_positions_live_mean":
                           float(np.mean(latent_live)),
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
                     "kv_latent_positions_max":
                         snap["kv_latent_positions_max"],
                     "state_slots_in_use": snap["state_slots_in_use"],
                     "window_blocks": win["blocks"], **routing},
        "samples": {"ttft_s": ttft},
        "traced_work": work,
        "engine": {"max_batch": scfg.max_batch,
                   "prefill_chunk": scfg.prefill_chunk},
        "model": config["model"],
    }
