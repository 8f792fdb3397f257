"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX. A
device plane (``/device:TPU:<n>``) has a line of XLA operations and a
line of XLA modules (one event per run of a jitted program); the host
plane's lines are threads, and hold the ``TraceAnnotation`` spans the
program and the harness wrote (``serve:prefill``, ``serve:decode``,
``bench:*``).

:func:`reduce_xplane` gives, for the traced window:

* ``busy_s``: the union of the intervals in which an operation ran on
  the device, averaged over the devices; ``window_s``: from the first
  device event's start to the last one's end, over all devices;
* ``ops``: time per operation name on device 0, the self time of an
  operation being its duration less what its children on the same line
  cover (a ``while`` holds its body's operations), with the count;
* ``idle_gaps``: the idle intervals of device 0, summed by what the
  host was doing: the innermost annotation (of the prefixes in
  ``ANNOTATIONS``) that covers the gap's middle, else ``before:<the
  module that ran next>``, else ``unattributed``;
* ``collective_s`` and ``collective_exposed_s``: time during which a
  collective operation was on the core's line or in flight on the line
  of asynchronous operations of device 0, and the part of it that was on
  the core's line, where nothing else computes meanwhile.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

#: Host annotations that may name an idle gap.
ANNOTATIONS = ("serve:", "bench:", "train:")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_ASYNC_LINE = "Async XLA Ops"

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of the disjoint sorted ``a`` outside the disjoint
    sorted ``b``."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events: List[Tuple[float, float, str]]
               ) -> List[Tuple[str, float]]:
    """``(name, self seconds)`` per event of one line: duration less the
    time its direct children (events nested inside it) take."""
    out: List[List[Any]] = []
    stack: List[int] = []
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and out[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= end - start
        out.append([name, end - start, end])
        stack.append(len(out) - 1)
    return [(name, max(t, 0.0)) for name, t, _ in out]


def op_name(text: str) -> str:
    """A short name for an operation. On the TPU an event's name is the
    whole HLO instruction (``%fusion.161 = (f32[32,1792]{...}, ...)
    fusion(...), kind=kOutput, ...``); the short name keeps the
    instruction's name and its first result shape: ``fusion.161
    f32[32,1792]``. Patterns of metrics match the whole text."""
    m = re.match(r"%?(\S+)\s*=\s*\(?([a-z0-9]+\[[0-9,]*\])?", text)
    if not m:
        return text[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def _events(line) -> List[Tuple[float, float, Any]]:
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e)
            for e in line.events]


def _device_planes(profile):
    planes = [p for p in profile.planes if p.name.startswith("/device:TPU:")]
    return sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def _line(plane, name: str):
    return next((ln for ln in plane.lines if ln.name == name), None)


def reduce_xplane(path: str, top: int = 10) -> Optional[Dict[str, Any]]:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    devices = _device_planes(profile)
    if not devices:
        return None
    per_device, lo, hi = [], float("inf"), float("-inf")
    for plane in devices:
        line = _line(plane, _OPS_LINE)
        ops = _events(line) if line is not None else []
        if ops:
            lo = min(lo, min(a for a, _, _ in ops))
            hi = max(hi, max(b for _, b, _ in ops))
        per_device.append(ops)
    if hi <= lo:
        return None
    busy = [total(union((a, b) for a, b, _ in ops)) for ops in per_device]

    ops0 = per_device[0]
    named = [(a, b, e.name) for a, b, e in ops0]
    by_name: Dict[str, List[float]] = {}
    for text, t in self_times(named):
        acc = by_name.setdefault(text, [0.0, 0])
        acc[0] += t
        acc[1] += 1
    # rows: [short name, self seconds, count, the whole instruction]
    ops_table = sorted(([op_name(n), t, c, n] for n, (t, c) in
                        by_name.items()), key=lambda r: -r[1])

    busy0 = union((a, b) for a, b, _ in ops0)
    gaps = subtract([(lo, hi)], busy0)
    host = [(a, b, e.name) for p in profile.planes
            if p.name.startswith("/host:") for ln in p.lines
            for a, b, e in _events(ln) if e.name.startswith(ANNOTATIONS)]
    modules_line = _line(devices[0], _MODULES_LINE)
    modules = sorted((a, e.name) for a, _, e in _events(modules_line)
                     ) if modules_line is not None else []
    by_cause: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        inner = min(((hb - ha, n) for ha, hb, n in host if ha <= mid <= hb),
                    default=None)
        if inner is not None:
            cause = inner[1]
        else:
            nxt = next((n for t, n in modules if t >= b - 1e-9), None)
            cause = ("before:" + re.sub(r"\(.*", "", nxt)) if nxt \
                else "unattributed"
        by_cause[cause] = by_cause.get(cause, 0.0) + (b - a)

    # The operations line is the core's one stream: while a collective
    # (or the wait for an asynchronous one, its ``-done``) is on it,
    # nothing else computes. Asynchronous collectives span from start to
    # done on the line of asynchronous operations.
    on_core = union((a, b) for a, b, n in named
                    if COLLECTIVE.match(op_name(n)))
    async_line = _line(devices[0], _ASYNC_LINE)
    in_flight = [(a, b) for a, b, e in _events(async_line)
                 if COLLECTIVE.match(op_name(e.name))] if async_line else []
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "n_devices": len(devices),
        "ops": ops_table,
        "device_ops": [[r[0], r[1]] for r in ops_table[:top]],
        "idle_gaps": sorted(([c, t] for c, t in by_cause.items()),
                            key=lambda r: -r[1])[:top],
        "collective_s": total(union(on_core + in_flight)),
        "collective_exposed_s": total(on_core),
    }

