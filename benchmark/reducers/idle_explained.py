"""The share of device 0's idle seconds that the engine's own account
of them covers, in percent: the idle gaps of the traced seconds (the
complement of the operations' busy union, as ``device_idle_pct`` takes
it) that lie inside a ``serve:unfed`` or a ``serve:no_work`` span.

The gaps are on the trace's device plane, the spans on the engine's
clock, and the trace's host plane lies between them:

* device plane to host plane, by causality: a program cannot start
  before the runtime enqueued it, so the device plane is shifted by the
  least amount under which none does (the largest ``enqueue - start``
  over the runs whose enqueue the trace holds). A run's enqueue is the
  host event with its ``run_id`` whose name says ``Enqueue``
  (``DoEnqueueProgram``); the completion callback carries the same
  ``run_id`` and is no launch.
* host plane to engine clock, from the device calls' twins: a twin
  annotation and its span end at the same point (``ServeMetrics.finish``)
  and carry the same ``call``, so the offset is the median of ``twin's
  end - span's end`` over the calls both hold. A twin starts at its
  launch where the span of a call launched ahead starts at the read
  before it, so neither starts nor durations pair them; a call in
  flight when the trace stopped has no twin and is left out.

It prints ``idle_explained``: the idle seconds by the covering span's
``why`` and part (``readback``, a host phase by name, ``outside_step``,
``unnamed``, ``dispatch``), ``no_work``, and what no span covers: by
where in its gap it lies (``after_a_program_s``: from the gap's start,
the program before it over and its result not yet the host's;
``before_a_program_s``: to the gap's end, the jitted call returned and
the program not yet started; ``whole_gaps_s``; ``inside_s``), with its
five longest stretches and the module that ran next; and the other
direction, ``unfed_while_busy_s``: the ``serve:unfed`` time of the
traced seconds in which device 0 ran an operation, by part (the jitted
call returns after the runtime enqueued the program, so the end of
``dispatch`` lies over the program's start).
"""

from __future__ import annotations

import re
import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness
from benchmark.reducers import _scopes
from benchmark.trace_reduce import Interval, subtract, total, union

#: ``(start, end, (why, part))`` on the engine's clock.
Cover = Tuple[float, float, Tuple[str, str]]


def read(path: str) -> Optional[Dict[str, Any]]:
    """Device 0's busy intervals and module runs (device plane), the
    enqueues by ``run_id`` and the twins by ``call`` (host plane)."""
    from benchmark.reducers import hvd_xplane_pb2

    space = hvd_xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices = sorted((p for p in space.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if not devices:
        return None
    dev = devices[0]
    names = {k: v.name for k, v in dev.stat_metadata.items()}
    lines = {ln.name: ln for ln in dev.lines}
    if "XLA Ops" not in lines or "XLA Modules" not in lines:
        return None
    busy = union((a, b) for a, b, _ in _scopes._line_events(lines["XLA Ops"]))
    modules = sorted(
        (a, dev.event_metadata[e.metadata_id].name,
         _scopes._stats(e.stats, names).get("run_id"))
        for a, _, e in _scopes._line_events(lines["XLA Modules"]))
    enqueued: Dict[Any, float] = {}
    twins: Dict[int, Tuple[str, float]] = {}
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        hnames = {k: v.name for k, v in plane.stat_metadata.items()}
        for ln in plane.lines:
            for a, b, e in _scopes._line_events(ln):
                if not e.stats:
                    continue
                name = plane.event_metadata[e.metadata_id].name
                stats = _scopes._stats(e.stats, hnames)
                if "run_id" in stats and "Enqueue" in name:
                    rid = stats["run_id"]
                    enqueued[rid] = min(a, enqueued.get(rid, a))
                elif "call" in stats and name.count(":") == 1:
                    twins[stats["call"]] = (name, b)
    return {"busy": busy, "modules": modules, "enqueued": enqueued,
            "twins": twins}


def _covers(spans: List[Dict[str, Any]]) -> List[Cover]:
    """Every ``serve:unfed`` span cut into its parts, and every
    ``serve:no_work`` span whole. The host's part is given to the phase
    spans inside it; of what they leave, the stretch that ends where a
    ``serve:schedule`` begins is ``outside_step`` (the caller's, if the
    span counted one) and the rest ``unnamed``."""
    out: List[Cover] = []
    phases = sorted((s["t0"], s["t0"] + s["dur"], s["name"]) for s in spans
                    if "call" not in s["args"])
    for s in spans:
        t0, end = s["t0"], s["t0"] + s["dur"]
        if s["name"] == "serve:no_work":
            out.append((t0, end, ("no_work", "")))
        if s["name"] != "serve:unfed":
            continue
        a = s["args"]
        why = a["why"]
        read_end = t0 + a["readback_ms"] * 1e-3
        launch = read_end + a["host_ms"] * 1e-3
        out.append((t0, read_end, (why, "readback")))
        cur = read_end
        for lo, hi, name in phases:
            if hi <= read_end or lo >= launch or name not in a["phases"]:
                continue
            if lo > cur:
                rest = ("outside_step" if name == "serve:schedule"
                        and "outside_step" in a["phases"] else "unnamed")
                out.append((cur, lo, (why, rest)))
            out.append((max(lo, cur), min(hi, launch), (why, name)))
            cur = min(hi, launch)
        if cur < launch:
            out.append((cur, launch, (why, "unnamed")))
        out.append((launch, end, (why, "dispatch")))
    return sorted(c for c in out if c[1] > c[0])


def _by_key(intervals: List[Interval], covers: List[Cover]
            ) -> Dict[Tuple[str, str], float]:
    """Seconds of the disjoint sorted ``intervals`` under each cover's
    key (covers sorted and disjoint)."""
    out: Dict[Tuple[str, str], float] = {}
    j = 0
    for lo, hi in intervals:
        while j < len(covers) and covers[j][1] <= lo:
            j += 1
        k = j
        while k < len(covers) and covers[k][0] < hi:
            a, b, key = covers[k]
            out[key] = out.get(key, 0.0) + min(b, hi) - max(a, lo)
            k += 1
    return out


def explain(path: str, spans: List[Dict[str, Any]]
            ) -> Optional[Dict[str, Any]]:
    """The account of one trace against one run's spans (``{name, t0,
    dur, args}`` on the engine's clock), or ``None`` where the trace
    has no device plane, no enqueue to shift it by or no twin to put it
    on the engine's clock."""
    trace = read(path)
    if trace is None or not trace["busy"]:
        return None
    ahead = [trace["enqueued"][rid] - start
             for start, _, rid in trace["modules"] if rid in trace["enqueued"]]
    ends = {s["args"]["call"]: (s["name"], s["t0"] + s["dur"])
            for s in spans if "call" in s["args"]
            and s["name"] != "serve:stall"}     # which names its call too
    offsets = [end - ends[call][1] for call, (name, end)
               in trace["twins"].items() if ends.get(call, ("",))[0] == name]
    if not ahead or not offsets:
        return None
    shift, offset = max(ahead), statistics.median(offsets)

    def to_engine(t: float) -> float:
        return t + shift - offset

    busy = [(to_engine(a), to_engine(b)) for a, b in trace["busy"]]
    lo, hi = busy[0][0], busy[-1][1]
    gaps = subtract([(lo, hi)], busy)
    # the run's spans are tens of thousands, the trace's a few hundred
    traced = [s for s in spans if s["t0"] < hi and s["t0"] + s["dur"] > lo]
    covers = [(max(a, lo), min(b, hi), key) for a, b, key
              in _covers(traced) if b > lo and a < hi]
    idle = _by_key(gaps, covers)
    left = subtract(gaps, union((a, b) for a, b, _ in covers))
    modules = [(to_engine(a), re.sub(r"\(.*", "", name))
               for a, name, _ in trace["modules"]]
    longest = sorted(left, key=lambda g: g[0] - g[1])[:5]
    starts, stops = {a for a, _ in gaps}, {b for _, b in gaps}
    where = dict.fromkeys(("after_a_program_s", "before_a_program_s",
                           "whole_gaps_s", "inside_s"), 0.0)
    for a, b in left:
        where["whole_gaps_s" if a in starts and b in stops
              else "after_a_program_s" if a in starts
              else "before_a_program_s" if b in stops
              else "inside_s"] += b - a
    by_why: Dict[str, Dict[str, float]] = {}
    for (why, part), s in idle.items():
        if why != "no_work":
            by_why.setdefault(why, {})[part] = s
    unfed = [c for c in covers if c[2][0] != "no_work"]
    while_busy: Dict[str, float] = {}
    for (_, part), s in _by_key(busy, unfed).items():
        while_busy[part] = while_busy.get(part, 0.0) + s
    idle_s = total(gaps)
    return {
        "idle_s": idle_s, "explained_s": sum(idle.values()),
        "window_s": hi - lo,
        "idle_by_why": by_why,
        "no_work": idle.get(("no_work", ""), 0.0),
        "unexplained": {
            "s": total(left), **where,
            "longest": [{"ms": 1e3 * (b - a), "at_s": a - lo,
                         "next": next((n for t, n in modules
                                       if t >= b - 1e-9), None)}
                        for a, b in longest]},
        "unfed_in_trace_s": sum(b - a for a, b, _ in unfed),
        "unfed_while_busy_s": while_busy,
        "device_plane_shifted_by_ms": 1e3 * shift,
        "launches": len(ahead),
        "trace_less_engine_clock_s": offset, "twins_paired": len(offsets),
        "twins_range_us": 1e6 * (max(offsets) - min(offsets))}


def reduce(meas):
    spans = meas.get("spans", [])
    if not any(s["name"] in ("serve:unfed", "serve:no_work") for s in spans):
        return None     # a program from before PR 52 keeps no account
    parsed = _scopes.load(meas)
    if not parsed:
        return None
    found = explain(parsed["path"], spans)
    if found is None or not found["idle_s"]:
        return None
    harness.say(idle_explained=found)
    return 100.0 * found["explained_s"] / found["idle_s"]
