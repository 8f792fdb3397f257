"""The serving engine's device calls, each joined to its run on the
chip, and the idle time of the chip split by them.

Since PR 36 the engine numbers its device calls
(``horovod_tpu/serve/metrics.py``, ``DeviceCall``): ``call`` is an arg
of the ``serve:prefill`` / ``serve:decode`` / ``serve:spec_*`` span, a
stat of its ``TraceAnnotation`` twin and of the nested ``:dispatch``,
``:sync``, ``:wait`` and ``:readback``. In the run's ``.xplane.pb`` the
host's launch of a program is an event with a ``run_id``
(``DoEnqueueProgram``), and so is the program's run on the device's
``XLA Modules`` line. So a call is joined to its run by identity: the
launches that fall inside the twin of call *n* are call *n*'s runs (the
engine waits for a call's result before it makes the next, so they are
no other call's). Nothing is counted from the end of a list and no
duration is compared. The launch is made by a thread of the runtime and
not by the one that called the jitted function: on the chip it falls
as often after ``:dispatch`` has ended as inside it, which is why the
twin and not ``:dispatch`` is what holds it.

**One clock, by causality.** The trace's device plane and host plane do
not share a clock: the device's runs lie 0.3 to 1.7 ms early on the
host's (``_scopes.skew_bound``). For call *i* let ``L_i`` and ``S_i`` be
its twin's start and end (host plane), ``E_i`` the launch event of each
of its runs, ``C_i`` the host's completion callback of the run (an
event ``CompleteCallbacks`` with its ``run_id``; where the trace has
none, the end of the call's ``:wait``), ``a_i`` and ``b_i`` the run's
start and end (device plane). A program starts after it was launched
and has ended when the host is told so, so the shift *d* that puts the
device plane on the host's clock satisfies ``max(E_i - a_i) <= d <=
min(C_i - b_i)``. :func:`clock_window` takes the middle. Then
``launch_i = a_i + d - L_i`` (the host's dispatch and the launch until
the program runs) and ``readback_i = S_i - b_i - d`` (until the host is
woken, and the copy), while their sum, ``(S_i - L_i) - (b_i - a_i)``,
does not depend on *d*.

A program without the numbers (before PR 36) gives no calls, and every
reader here returns ``None``.
"""

from __future__ import annotations

import bisect
import os
import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness, trace_reduce
from benchmark.reducers import _scopes

#: An interval of shifts that is empty by more than this is no interval:
#: the calls and the runs were not joined as they happened.
SLACK_S = 50e-6

#: The host event in which the runtime learns that a run has ended.
_COMPLETED = "CompleteCallbacks"

Interval = Tuple[float, float]


def parse(path: str) -> Optional[Dict[str, Any]]:
    """What this module needs of the trace, or ``None`` with no TPU
    plane or no protobuf: on the host the annotations with a ``call``
    (``twins`` by call, ``nested`` by call and suffix), the program's
    other annotations, the first event of each ``run_id`` (its launch)
    and its ``CompleteCallbacks``; on device 0 the runs by ``run_id``
    and the operations' intervals."""
    try:
        from benchmark.reducers import hvd_xplane_pb2
    except ImportError:
        return None
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
    runs: Dict[Any, Tuple[float, float, str]] = {}
    for a, b, e in _scopes._line_events(lines["XLA Modules"]) \
            if "XLA Modules" in lines else []:
        rid = _scopes._stats(e.stats, names).get("run_id")
        if rid is not None:
            runs[rid] = (a, b, dev.event_metadata[e.metadata_id].name)
    ops = [(a, b) for a, b, _ in _scopes._line_events(lines["XLA Ops"])
           ] if "XLA Ops" in lines else []

    twins: Dict[int, Tuple[float, float, str]] = {}
    nested: Dict[Tuple[int, str], List[Interval]] = {}
    annotations: List[Tuple[float, float, str]] = []
    launches: Dict[Any, float] = {}
    completions: Dict[Any, float] = {}
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        hnames = {k: v.name for k, v in plane.stat_metadata.items()}
        for ln in plane.lines:
            for a, b, e in _scopes._line_events(ln):
                name = plane.event_metadata[e.metadata_id].name
                stats = _scopes._stats(e.stats, hnames) if e.stats else {}
                if name.startswith(trace_reduce.ANNOTATIONS):
                    annotations.append((a, b, name))
                    call = stats.get("call")
                    if call is None:
                        pass
                    elif name.count(":") == 1:
                        twins[call] = (a, b, name)
                    else:
                        nested.setdefault(
                            (call, name.rsplit(":", 1)[1]), []
                        ).append((a, b))
                rid = stats.get("run_id")
                if rid is not None:
                    launches[rid] = min(a, launches.get(rid, a))
                    if name == _COMPLETED:
                        completions[rid] = a
    return {"path": path, "twins": twins, "nested": nested,
            "annotations": annotations, "launches": launches,
            "completions": completions, "runs": runs,
            "ops": trace_reduce.union(ops)}


def join(parsed: Dict[str, Any]) -> List[Dict[str, Any]]:
    """One record a device call that the trace holds whole: ``call``,
    ``name``, ``L`` and ``S`` (its twin) and ``runs``: ``(E, a, b, C,
    run_id)`` for every program launched inside the twin, in the order
    they ran (``C``: the run's completion callback, else the end of the
    call's last ``:wait``, else ``S``). A call none of whose launches
    has a run on device 0 is left out."""
    twins = sorted((L, S, call) for call, (L, S, _)
                   in parsed["twins"].items())
    starts = [L for L, _, _ in twins]
    by_call: Dict[int, List[Tuple[float, float, float, float, Any]]] = {}
    for rid, t in parsed["launches"].items():
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t > twins[i][1] or rid not in parsed["runs"]:
            continue
        _, S, call = twins[i]
        waited = max((b for _, b in parsed["nested"].get((call, "wait"), [])
                      ), default=S)
        a, b, _ = parsed["runs"][rid]
        by_call.setdefault(call, []).append(
            (t, a, b, parsed["completions"].get(rid, waited), rid))
    out = []
    for call, runs in sorted(by_call.items()):
        L, S, name = parsed["twins"][call]
        out.append({"call": call, "name": name, "L": L, "S": S,
                    "runs": sorted(runs, key=lambda r: r[1])})
    return out


def clock_window(calls: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """The shifts of the device plane that no call contradicts, and the
    middle one: ``{lo_s, hi_s, shift_s, width_us, calls}``. ``None``
    with no calls, or when the interval is empty by more than
    ``SLACK_S`` (then the edges are printed, and no split is made)."""
    if not calls:
        return None
    lo = max(E - a for c in calls for E, a, _, _, _ in c["runs"])
    hi = min(C - b for c in calls for _, _, b, C, _ in c["runs"])
    if lo - hi > SLACK_S:
        harness.say(device_clock="no shift of the device plane puts every "
                    "run after its launch and before its completion",
                    lo_s=lo, hi_s=hi, calls=len(calls))
        return None
    return {"lo_s": lo, "hi_s": hi, "shift_s": (lo + hi) / 2,
            "width_us": 1e6 * (hi - lo), "calls": len(calls)}


def device_s(call: Dict[str, Any]) -> float:
    """Seconds the call's programs ran on the device."""
    return sum(b - a for _, a, b, _, _ in call["runs"])


def launch_s(call: Dict[str, Any], shift: float) -> float:
    """From the call's first line to its first program's start."""
    return call["runs"][0][1] + shift - call["L"]


def readback_s(call: Dict[str, Any], shift: float) -> float:
    """From its last program's end to the call's last line."""
    return call["S"] - call["runs"][-1][2] - shift


def innermost(annotations: List[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """The host's time as pieces ``(start, end, name)``, each named for
    the shortest annotation that lies over it; what none covers is left
    out."""
    cuts = sorted({t for a, b, _ in annotations for t in (a, b)})
    todo = sorted(annotations)
    pieces, active, k = [], [], 0
    for x, y in zip(cuts, cuts[1:]):
        while k < len(todo) and todo[k][0] <= x:
            active.append(todo[k])
            k += 1
        active = [an for an in active if an[1] > x]
        if active:
            pieces.append((x, y, min(active, key=lambda an: an[1] - an[0]
                                     )[2]))
    return pieces


def split_gaps(gaps: List[Interval],
               annotations: List[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap is split over
    the innermost annotations it overlaps, in proportion (where
    ``trace_reduce`` gives a gap whole to the one at its middle); what
    no annotation covers is ``unattributed``."""
    pieces = innermost(annotations)
    starts = [p[0] for p in pieces]
    by: Dict[str, float] = {}
    for lo, hi in gaps:
        left = hi - lo
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(pieces) and pieces[i][0] < hi:
            x, y, name = pieces[i]
            part = min(y, hi) - max(x, lo)
            if part > 0:
                by[name] = by.get(name, 0.0) + part
                left -= part
            i += 1
        if left > 1e-12:
            by["unattributed"] = by.get("unattributed", 0.0) + left
    return by


def load(meas: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """This run's device calls on one clock (read once, kept in
    ``meas``): ``{calls, window, shift_s}``, or ``None``: no
    trace, a program that does not number its calls, or no consistent
    shift. Prints, once a run, the window of shifts, the three parts of
    the idle time, the longest calls with where their time went (a
    stall that fell into the traced seconds is among them) and the idle
    gaps of device 0 split on the shifted clock, to stand beside
    ``breakdown.idle_gaps``."""
    if "_calls" in meas:
        return meas["_calls"]
    meas["_calls"] = None
    path = _scopes.newest_xplane() if meas.get("trace") else None
    parsed = parse(path) if path else None
    if not parsed:
        return None
    calls = join(parsed)
    window = clock_window(calls)
    if not window:
        return None
    shift = window["shift_s"]
    busy = [(a + shift, b + shift) for a, b in parsed["ops"]]
    span = (busy[0][0], busy[-1][1])
    gaps = trace_reduce.subtract([span], busy)
    by_name: Dict[str, List[Dict[str, Any]]] = {}
    for c in calls:
        by_name.setdefault(c["name"], []).append(c)
    runs = trace_reduce.union((a + shift, b + shift) for c in calls
                              for _, a, b, _, _ in c["runs"])
    inside = trace_reduce.total(runs) - trace_reduce.total(
        trace_reduce.subtract(runs, gaps))
    harness.say(
        device_calls_read_from=os.path.relpath(path, harness.ROOT),
        device_clock_window_us=window["width_us"],
        device_clock_window={k: window[k] for k in
                             ("lo_s", "hi_s", "shift_s", "calls")},
        idle_s={"window_s": span[1] - span[0],
                "idle_s": trace_reduce.total(gaps),
                "launch_s": sum(launch_s(c, shift) for c in calls),
                "readback_s": sum(readback_s(c, shift) for c in calls),
                "between_ops_of_a_run_s": inside},
        lag_p50_ms={n: {"launch": 1e3 * statistics.median(
                            [launch_s(c, shift) for c in cs]),
                        "readback": 1e3 * statistics.median(
                            [readback_s(c, shift) for c in cs]),
                        "calls": len(cs)}
                    for n, cs in by_name.items()},
        longest_calls_ms=[
            {"call": c["call"], "name": c["name"],
             "span": 1e3 * (c["S"] - c["L"]),
             "launch": 1e3 * launch_s(c, shift),
             "on_device": 1e3 * device_s(c),
             "readback": 1e3 * readback_s(c, shift)}
            for c in sorted(calls, key=lambda c: c["L"] - c["S"])[:3]],
        idle_gaps_on_one_clock=sorted(
            ([n, t] for n, t in
             split_gaps(gaps, parsed["annotations"]).items()),
            key=lambda r: -r[1])[:12])
    meas["_calls"] = {"calls": calls, "window": window, "shift_s": shift}
    return meas["_calls"]
