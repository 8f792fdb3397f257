"""An open loop: independent users, arrivals on a fixed schedule,
whatever the engine is doing.

Arrival gaps and lengths are fixed multisets in one fixed order
(``benchmark/lengths.py``). A request is timed from when it was due,
not from when the loop got round to submitting it, and how late the
generator ran is printed. Arrivals run for ``ramp_s`` before the window
opens, so that it opens on a batch as full as it will stay.

The gaps between the output tokens of one request are the end-to-end
metrics: every gap whose later token falls inside the window.
"""

from __future__ import annotations

import statistics
import time
from typing import Any, Dict, List, Tuple

from benchmark import harness, lengths
from benchmark.generators import serve_common
from benchmark.reducers._common import percentile


def token_gaps(times: Dict[int, List[float]], t_open: float, t_close: float
               ) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token was stamped inside the window."""
    return [b - a for ts in times.values() for a, b in zip(ts, ts[1:])
            if t_open < b <= t_close]


def run(ctx) -> Dict[str, Any]:
    config, traffic = ctx["config"], ctx["traffic"]
    seconds = ctx["seconds"]
    rate = ctx.get("rate_per_s") or traffic["rate_per_s"]
    traffic = {**traffic, "rate_per_s": rate}
    ready = serve_common.prepare({**ctx, "traffic": traffic})
    engine, scfg, check, stream = (ready[k] for k in
                                   ("engine", "scfg", "check", "stream"))
    harness.say(rate_per_s=rate)

    from horovod_tpu.serve import QueueFull
    gaps = lengths.arrival_gaps(traffic)
    ramp = float(traffic["ramp_s"])
    submitted: Dict[int, Tuple[int, int, float]] = {}  # rid->(tid,n_out,due)
    prompt_lens: Dict[int, int] = {}
    late: List[float] = []
    shed = 0
    stamps: List[float] = []
    queue_depth: List[int] = []
    trace = ctx["trace_window"]
    compiles_at_open = None

    t0 = time.perf_counter()
    t_open, t_close = t0 + ramp, t0 + ramp + seconds
    next_due = t0 + next(gaps)
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        if compiles_at_open is None and now >= t_open:
            compiles_at_open = ctx["compiles"].count
        if now >= t_open:
            trace.poll(now - t_open)
        with ctx["annotate"]("bench:submit"):
            while next_due <= now:
                prompt, n_out = next(stream)
                tid = len(prompt_lens) + 1
                prompt_lens[tid] = len(prompt)
                try:
                    rid = engine.submit(prompt, n_out, trace_id=tid)
                    submitted[rid] = (tid, n_out, next_due)
                    late.append(now - next_due)
                except QueueFull:
                    shed += 1
                next_due += next(gaps)
        if engine.pending:
            engine.step()
            stamps.append(time.perf_counter())
            queue_depth.append(engine.admission_snapshot()["queue_depth"])
        else:
            with ctx["annotate"]("bench:wait_for_arrival"):
                time.sleep(min(max(next_due - now, 0.0), 0.005))
    trace.stop()
    if compiles_at_open is None:
        compiles_at_open = ctx["compiles"].count
    compiles = ctx["compiles"].count - compiles_at_open

    spans = serve_common.engine_spans(engine, ctx["workload"])
    times = serve_common.token_times(spans, stamps, prompt_lens)
    gaps_s = token_gaps(times, t_open, t_close)
    if len(gaps_s) < 100:
        raise SystemExit(f"benchmark: only {len(gaps_s)} token gaps in the "
                         "window")
    due = {tid: d for tid, _, d in submitted.values()}
    ttft = [ts[0] - due[tid] for tid, ts in times.items()
            if ts and t_open < ts[0] <= t_close]
    results = {rid: engine.result(rid) for rid in submitted}
    done = {rid: r for rid, r in results.items()
            if r is not None and t_open < (r.finished_at or 0) <= t_close}
    failed = shed + sum(
        1 for rid, r in done.items()
        if r.status != "ok" or len(r.tokens) != submitted[rid][1])
    in_win = [q for s, q in zip(stamps, queue_depth) if t_open < s <= t_close]
    harness.say(
        generator_lateness_ms={
            "max": 1e3 * max(late, default=0.0),
            "mean": 1e3 * (sum(late) / len(late) if late else 0.0)},
        arrivals=len(submitted) + shed, shed=shed,
        finished_in_window=len(done), gaps=len(gaps_s),
        gap_quartiles_ms=[1e3 * x for x in serve_common.quartiles(gaps_s)],
        ttft_quartiles_ms=[1e3 * x for x in serve_common.quartiles(ttft)],
        queue_depth={"max": max(in_win, default=0), "first": in_win[:1],
                     "last": in_win[-1:],
                     "mean": sum(in_win) / max(len(in_win), 1)},
        compiles_in_window=compiles)
    return {
        "correct": bool(check["correct"]) and compiles == 0,
        "attempted": len(done) + shed,
        "failed": failed,
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {
            "itl_mean_ms": 1e3 * statistics.fmean(gaps_s),
            "itl_p95_ms": 1e3 * percentile(gaps_s, 95)},
        "spans": spans,
        "counters": {"compiles_in_window": compiles, "shed": shed,
                     "queue_depth_last": in_win[-1] if in_win else 0,
                     "queue_depth_max": max(in_win, default=0)},
        "samples": {"ttft_s": ttft, "itl_s": gaps_s},
        "engine": {"max_batch": scfg.max_batch},
        "model": config["model"],
    }
