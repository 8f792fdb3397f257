"""Device time by the names the program wrote (``jax.named_scope``, a
Pallas kernel's ``name``), read from the run's ``.xplane.pb``.

``jax.profiler.ProfileData`` (what ``trace_reduce.py`` reads with) shows
an event's own stats only. The path of scopes an operation was traced
under (``jit(step)/.../attn/flash_bwd/mul``) is the ``tf_op`` stat of
the event's *metadata*, beside ``hlo_category``, ``flops`` and
``bytes_accessed``, so this module parses the file itself, with a
generated reader of the container (``hvd_xplane_pb2``, from
``hvd_xplane.proto``; protobuf comes with JAX) and not TensorFlow's.
It runs after the window has closed.

``meas`` does not carry the trace's path, so :func:`load` takes the
newest ``*.xplane.pb`` under ``harness.OUT_DIR/trace`` (``TraceWindow``
empties the cell's directory before recording) and says which.

:func:`load` gives, for device 0: ``rows`` — one per distinct
operation, ``{name, tf_op, category, flops, bytes, self_s, count}``,
self times by ``trace_reduce.self_times`` — and prints once a run: time
by scope, the collective time on the core's line by scope (several
chips), the offset between the engine's clock and the trace's from the
``serve:decode`` twins, and a bound on the skew between the trace's
device and host planes.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness, trace_reduce

_PS = 1e-12
#: Path components that say how an operation was reached, not which
#: part of the model it belongs to.
_STRUCTURE = {"while", "body", "cond", "closed_call", "checkpoint",
              "rematted_computation", "pallas_call", "shard_map", "scan"}
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def scope_of(tf_op: str) -> Tuple[str, str]:
    """``(program, scope path)`` of an operation's ``tf_op``:
    ``jit(step)/transpose(jvp())/while/body/checkpoint/attn/flash_bwd/mul``
    is ``("step", "attn/flash_bwd")`` and ``jit(step)/jvp(head)/mul``
    ``("step", "head")``. Transformations (``jvp(..)``,
    ``transpose(..)``) are looked through, functions jitted inside
    (``jit(silu)``), loop structure and the primitive's own name are
    left out; no scope at all gives ``""``."""
    parts = tf_op.rsplit(":", 1)[0].split("/")
    program, kept = "", []
    for i, part in enumerate(parts[:-1]):
        m = _WRAPPED.match(part)
        while m and m.group(1) != "jit":
            part = m.group(2)
            m = _WRAPPED.match(part)
        if m:
            if i == 0:
                program = m.group(2)
            continue
        if part and part not in _STRUCTURE and not part.startswith("branch_"):
            kept.append(part)
    return program, "/".join(kept)


def newest_xplane() -> Optional[str]:
    paths = glob.glob(os.path.join(harness.OUT_DIR, "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _stat_value(stat, stat_names: Dict[int, str]):
    which = stat.WhichOneof("value")
    if which is None:
        return None
    value = getattr(stat, which)
    return stat_names.get(value, "") if which == "ref_value" else value


def _stats(stats, stat_names: Dict[int, str]) -> Dict[str, Any]:
    return {stat_names.get(s.metadata_id, ""): _stat_value(s, stat_names)
            for s in stats}


def _line_events(line) -> List[Tuple[float, float, Any]]:
    """``(start s, end s, event)``; a line's events are offsets from
    the line's own timestamp."""
    base = line.timestamp_ns * 1e-9
    return [(base + e.offset_ps * _PS,
             base + (e.offset_ps + e.duration_ps) * _PS, e)
            for e in line.events]


def parse(path: str) -> Optional[Dict[str, Any]]:
    """The trace as this module needs it, or ``None`` with no TPU plane
    or no protobuf to parse with."""
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

    ops = _line_events(lines["XLA Ops"]) if "XLA Ops" in lines else []
    per_op: Dict[int, List[float]] = {}
    for mid, t in trace_reduce.self_times(
            [(a, b, e.metadata_id) for a, b, e in ops]):
        acc = per_op.setdefault(mid, [0.0, 0])
        acc[0] += t
        acc[1] += 1
    meta = {}
    for mid in per_op:
        md = dev.event_metadata[mid]
        meta[mid] = (md.name, _stats(md.stats, names))
    rows = [{"name": name, "tf_op": st.get("tf_op") or "",
             "category": st.get("hlo_category") or "",
             "flops": float(st.get("flops") or 0),
             "bytes": float(st.get("bytes_accessed") or 0),
             "self_s": per_op[mid][0], "count": per_op[mid][1]}
            for mid, (name, st) in meta.items()]
    rows.sort(key=lambda r: -r["self_s"])

    collectives: Dict[str, float] = {}
    for a, b, e in ops:
        name, st = meta[e.metadata_id]
        if trace_reduce.COLLECTIVE.match(trace_reduce.op_name(name)):
            scope = scope_of(st.get("tf_op") or "")[1] or "(no scope)"
            collectives[scope] = collectives.get(scope, 0.0) + (b - a)

    # Host side: the program's annotations, and the launches by run_id.
    modules = [(a, _stats(e.stats, names).get("run_id"))
               for a, _, e in _line_events(lines["XLA Modules"])
               ] if "XLA Modules" in lines else []
    annotations: List[Tuple[float, float, str]] = []
    launches: Dict[Any, float] = {}
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        hnames = {k: v.name for k, v in plane.stat_metadata.items()}
        for ln in plane.lines:
            for a, b, e in _line_events(ln):
                name = plane.event_metadata[e.metadata_id].name
                if name.startswith(trace_reduce.ANNOTATIONS):
                    annotations.append((a, b, name))
                for s in e.stats:
                    if hnames.get(s.metadata_id) == "run_id":
                        rid = _stat_value(s, hnames)
                        launches[rid] = min(a, launches.get(rid, a))
    # A program cannot start on the device before the host has enqueued
    # it: where it seems to, the device plane's clock runs ahead of the
    # host plane's by at least that much.
    ahead = [launches[rid] - start for start, rid in modules
             if rid in launches]
    return {"path": path, "n_devices": len(devices), "rows": rows,
            "collectives_on_core_s": collectives,
            "annotations": annotations,
            "device_ahead_of_host_s": ahead}


def skew_bound(ahead: List[float]) -> Optional[Dict[str, Any]]:
    if not ahead:
        return None
    return {"launches": len(ahead), "max_ms": 1e3 * max(ahead),
            "median_ms": 1e3 * statistics.median(ahead),
            "launches_the_device_began_first": sum(x > 0 for x in ahead)}


def clock_offset(spans: List[Dict[str, Any]],
                 annotations: List[Tuple[float, float, str]],
                 name: str = "serve:decode") -> Optional[Dict[str, Any]]:
    """Trace clock less engine clock, from the twins of ``name``: the
    engine writes each such span under both clocks at the same two
    points (``ServeMetrics.phase``). The trace ends after the engine's
    last span, so the pairs are matched from the end; a pair whose two
    durations differ by more than 0.1 ms is not a pair."""
    mine = sorted((s for s in spans if s["name"] == name),
                  key=lambda s: s["t0"])
    theirs = sorted(a for a in annotations if a[2] == name)
    n = min(len(mine), len(theirs))
    if n == 0:
        return None
    pairs = [(a[0] - s["t0"], (a[1] - a[0]) - s["dur"])
             for s, a in zip(mine[-n:], theirs[-n:])]
    good = [off for off, d in pairs if abs(d) <= 1e-4]
    if len(good) < 2:
        return {"pairs": n, "matched": len(good)}
    q = statistics.quantiles(good, n=4)
    return {"pairs": n, "matched": len(good),
            "median_s": statistics.median(good),
            "iqr_us": 1e6 * (q[2] - q[0]),
            "range_us": 1e6 * (max(good) - min(good))}


def scope_table(rows: List[Dict[str, Any]], top: int = 24
                ) -> List[List[Any]]:
    """``[program, scope, seconds, percent of all op time]``."""
    total = sum(r["self_s"] for r in rows) or 1.0
    by: Dict[Tuple[str, str], float] = {}
    for r in rows:
        key = scope_of(r["tf_op"])
        by[key] = by.get(key, 0.0) + r["self_s"]
    table = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[p, s or "(no scope)", round(t, 6), round(100.0 * t / total, 2)]
            for (p, s), t in table]


def load(meas: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The parsed trace of this run (read once, kept in ``meas``), or
    ``None``: no TPU trace was reduced, or nothing to parse with."""
    if "_scopes" in meas:
        return meas["_scopes"]
    parsed = None
    if meas.get("trace"):
        path = newest_xplane()
        parsed = parse(path) if path else None
    meas["_scopes"] = parsed
    if parsed:
        harness.say(
            scopes_read_from=os.path.relpath(parsed["path"], harness.ROOT),
            time_by_scope=scope_table(parsed["rows"]),
            unnamed_ops=[[trace_reduce.op_name(r["name"]),
                          round(r["self_s"], 6), r["tf_op"]]
                         for r in parsed["rows"]
                         if not scope_of(r["tf_op"])[1]][:12],
            collectives_on_core_by_scope_s={
                k: round(v, 6) for k, v in
                parsed["collectives_on_core_s"].items()}
            if parsed["n_devices"] > 1 else None,
            engine_to_trace_clock=clock_offset(meas.get("spans", []),
                                               parsed["annotations"]),
            device_plane_ahead_of_host_plane=skew_bound(
                parsed["device_ahead_of_host_s"]))
    return parsed


def matching(rows: List[Dict[str, Any]], match: Optional[str] = None,
             unless: Optional[str] = None, category: Optional[str] = None
             ) -> List[Dict[str, Any]]:
    """Rows whose ``tf_op`` has ``match`` and lacks ``unless``, and whose
    ``hlo_category`` has ``category``; each may be left out."""
    rx, ux, cx = (re.compile(p) if p else None
                  for p in (match, unless, category))
    return [r for r in rows
            if (rx is None or rx.search(r["tf_op"]))
            and (ux is None or not ux.search(r["tf_op"]))
            and (cx is None or cx.search(r["category"]))]
