"""A backlog that never empties and never sheds: the saturated cell.

The engine's queue is topped up to ``queue_target`` before every step,
so no slot starves and nothing is refused. ``serve_tok_s`` is cut at
retirements, not by the clock (``window_rate``): between the end of one
block of the traffic's list and the end of a later one. Lockstep waves
of prefill and decode are then counted whole or not at all, every window
holds whole blocks of the same mix, and the count does not depend on
where the window falls.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from benchmark import harness
from benchmark.generators import serve_common


def window_rate(stamps: List[float], tokens: List[int], cuts: List[int],
                seconds: float) -> Optional[Dict[str, Any]]:
    """Tokens per second between two retirements.

    ``stamps[i]`` is the host time after step ``i`` and ``tokens[i]``
    the engine's cumulative count of output tokens then. ``cuts`` are
    the steps after which a whole block of the traffic's list was
    complete (its last request had just produced its last token): the
    first is the end of the traffic's warm-up, when every slot has
    retired its first request. The window opens there and closes at the
    last cut no later than ``seconds`` after it. Between two such cuts
    the engine has done whole blocks of the same mix, and a lockstep
    wave is counted whole or not at all."""
    if not cuts:
        return None
    i_open = cuts[0]
    i_close = [c for c in cuts if stamps[c] <= stamps[i_open] + seconds][-1]
    if i_close == i_open:
        return None
    return {"i_open": i_open, "i_close": i_close,
            "t_open": stamps[i_open], "t_close": stamps[i_close],
            "blocks": cuts.index(i_close),
            "tokens": tokens[i_close] - tokens[i_open],
            "rate": (tokens[i_close] - tokens[i_open])
            / (stamps[i_close] - stamps[i_open])}


def run(ctx) -> Dict[str, Any]:
    config, traffic, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    ready = serve_common.prepare(ctx)
    engine, scfg, check, stream = (ready[k] for k in
                                   ("engine", "scfg", "check", "stream"))
    submitted: Dict[int, int] = {}               # rid -> output length
    order: List[int] = []                        # rids as submitted
    shed = 0

    def submit_next() -> bool:
        nonlocal shed
        from horovod_tpu.serve import QueueFull
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

    # Fill every slot, then step with the queue topped up. Requests are
    # submitted in the list's blocks; a block is complete when all its
    # requests have retired, and the first block's end is the end of the
    # traffic's own warm-up: every slot has retired a request.
    block = scfg.max_batch
    for _ in range(block):
        submit_next()
    m = engine.metrics
    stamps: List[float] = []
    tokens: List[int] = []
    cuts: List[int] = []
    compiles_at_open = None
    trace = ctx["trace_window"]
    while True:
        with ctx["annotate"]("bench:submit"):
            top_up()
        engine.step()
        now = time.perf_counter()
        stamps.append(now)
        tokens.append(m.tokens_generated)
        while len(order) >= (len(cuts) + 1) * block and all(
                engine.result(r) is not None
                for r in order[len(cuts) * block:(len(cuts) + 1) * block]):
            # Seen after this step, retired at its start: the block's
            # last token came out of the step before.
            cuts.append(len(stamps) - 2)
            if compiles_at_open is None:
                compiles_at_open = ctx["compiles"].count
        if cuts:
            since_open = now - stamps[cuts[0]]
            trace.poll(since_open)
            if since_open >= seconds:
                break
    trace.stop()
    compiles = ctx["compiles"].count - compiles_at_open

    win = window_rate(stamps, tokens, cuts, seconds)
    if win is None:
        raise SystemExit("benchmark: no whole block inside the window")
    t_open, t_close = win["t_open"], win["t_close"]
    spans = serve_common.engine_spans(engine, ctx["workload"])
    # A request whose last token came out of step i is retired, and
    # stamped, at the start of step i + 1.
    lo, hi = stamps[win["i_open"] + 1], stamps[win["i_close"] + 1]
    done = {rid: r for rid in submitted
            if (r := engine.result(rid)) is not None
            and lo < r.finished_at <= hi}
    failed = shed + sum(
        1 for rid, r in done.items()
        if r.status != "ok" or len(r.tokens) != submitted[rid])
    ttft = [r.first_token_at - r.submitted_at for r in done.values()
            if r.first_token_at is not None]
    harness.say(window={k: win[k] for k in ("blocks", "tokens", "rate")},
                retired=len(done),
                window_s=t_close - t_open, steps=win["i_close"] - win["i_open"],
                warm_traffic_s=round(t_open - stamps[0], 2),
                shed=shed, compiles_in_window=compiles,
                ttft_quartiles_ms=[1e3 * x for x in
                                   serve_common.quartiles(ttft)])
    return {
        "correct": bool(check["correct"]) and compiles == 0,
        "attempted": len(done) + shed,
        "failed": failed,
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {"serve_tok_s": win["rate"]},
        "spans": spans,
        "counters": {"compiles_in_window": compiles, "shed": shed},
        "samples": {"ttft_s": ttft},
        "engine": {"max_batch": scfg.max_batch},
        "model": config["model"],
    }
