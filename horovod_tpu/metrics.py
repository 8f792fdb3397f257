"""Runtime telemetry: native metrics registry -> Python exposition.

The chrome timeline answers "what happened" after the fact; this module
is the "what is happening NOW" half (docs/observability.md): it reads
the native registry's versioned packed snapshot (``hvd_metrics_snapshot``,
``native/include/hvd/metrics.h``) and renders it three ways —

* :func:`metrics` — flat dict of counters, gauges, and per-histogram
  count/sum/p50/p99;
* :func:`metrics_prometheus` — Prometheus text exposition, including
  any registered secondary exporter (the serving engine registers its
  :class:`~horovod_tpu.serve.metrics.ServeMetrics` here, so training
  and serving export through ONE endpoint in ONE format);
* :func:`metrics_aggregate` — cross-rank min/max/sum of every series,
  reduced over the existing allreduce data plane, so rank 0 can report
  straggler spread (e.g. ``shm_barrier_us_p99`` max vs min) without a
  side channel.

Everything here works before ``hvd.init()`` (the registry is
process-global); only :func:`metrics_aggregate` requires an initialized
multi-rank job, because it IS a collective.
"""

from __future__ import annotations

import ctypes
import json
import threading
import weakref
from typing import Callable, Dict, List, Optional

from horovod_tpu.common import basics

#: Prometheus metric-name prefix for the native registry's series.
NAMESPACE = "hvd"


def _lib():
    return basics.get_lib()


# ---------------------------------------------------------------------------
# snapshot parsing
# ---------------------------------------------------------------------------

_names_cache = None


def _names():
    """(counter_names, counter_kinds, hist_names) from the native name
    tables — fixed for a loaded library, so read once."""
    global _names_cache
    if _names_cache is None:
        lib = _lib()
        nc = lib.hvd_metrics_num_counters()
        nh = lib.hvd_metrics_num_hists()
        _names_cache = (
            [lib.hvd_metrics_counter_name(i).decode() for i in range(nc)],
            [lib.hvd_metrics_counter_kind(i) for i in range(nc)],
            [lib.hvd_metrics_hist_name(i).decode() for i in range(nh)],
        )
    return _names_cache


def snapshot() -> dict:
    """One structured point-in-time read of the native registry:
    ``{"version", "counters": {name: int}, "histograms":
    {name: {"count", "sum", "buckets": [...]}}}``. Bucket ``i`` counts
    observations ``v <= 2**i`` (non-cumulative; the last bucket is
    +Inf)."""
    lib = _lib()
    needed = lib.hvd_metrics_snapshot(None, 0)
    buf = (ctypes.c_int64 * needed)()
    got = lib.hvd_metrics_snapshot(buf, needed)
    if got != needed:  # registry shape changed mid-read: impossible
        raise RuntimeError(f"metrics snapshot size skew ({got} != {needed})")
    version, nc, nh, nb = buf[0], buf[1], buf[2], buf[3]
    if version != basics.METRICS_VERSION:
        raise RuntimeError(
            f"metrics snapshot version {version}, expected "
            f"{basics.METRICS_VERSION}")
    cnames, _kinds, hnames = _names()
    i = 4
    counters = {}
    for name in cnames[:nc]:
        counters[name] = buf[i]
        i += 1
    hists = {}
    for name in hnames[:nh]:
        count, total = buf[i], buf[i + 1]
        i += 2
        hists[name] = {"count": count, "sum": total,
                       "buckets": list(buf[i:i + nb])}
        i += nb
    return {"version": version, "counters": counters, "histograms": hists}


def hist_quantile(count: int, buckets: List[int], q: float) -> float:
    """Upper-bound quantile estimate from the log2 buckets (within 2x
    of the true value by construction): the ``le`` edge of the bucket
    holding the q-th observation. 0.0 on an empty histogram; +Inf when
    the quantile landed in the overflow bucket."""
    if count <= 0:
        return 0.0
    target = max(1, int(q * count + 0.9999999))
    cum = 0
    for i, b in enumerate(buckets):
        cum += b
        if cum >= target:
            return float("inf") if i == len(buckets) - 1 else float(2 ** i)
    return float("inf")


def metrics() -> Dict[str, float]:
    """Flat dict of every native series: counters/gauges by name, and
    per histogram ``<name>_count``, ``<name>_sum``, ``<name>_avg``,
    ``<name>_p50``, ``<name>_p99`` (quantiles are log2-bucket upper
    bounds, i.e. within 2x)."""
    snap = snapshot()
    out: Dict[str, float] = dict(snap["counters"])
    for name, h in snap["histograms"].items():
        out[f"{name}_count"] = h["count"]
        out[f"{name}_sum"] = h["sum"]
        out[f"{name}_avg"] = (h["sum"] / h["count"]) if h["count"] else 0.0
        out[f"{name}_p50"] = hist_quantile(h["count"], h["buckets"], 0.50)
        out[f"{name}_p99"] = hist_quantile(h["count"], h["buckets"], 0.99)
    return out


def metrics_reset() -> None:
    """Zero every counter and histogram (e.g. to scope a measurement
    window)."""
    _lib().hvd_metrics_reset()


def metrics_enabled() -> bool:
    return bool(_lib().hvd_metrics_enabled())


def set_metrics_enabled(on: bool) -> None:
    """Process-wide observation switch. Off short-circuits every
    observation site (including the scoped timers' clock reads) — the
    overhead guard in tests/test_metrics.py times the identical
    workload both ways."""
    _lib().hvd_metrics_set_enabled(1 if on else 0)


# ---------------------------------------------------------------------------
# stall findings (beyond the log line)
# ---------------------------------------------------------------------------

def _unescape_stall_name(s: str) -> str:
    # hvd_stalled_tensors backslash-escapes \\, \t, \n in tensor names
    # (they are arbitrary user strings, and tab/newline are the wire's
    # field/record separators).
    out = []
    it = iter(s)
    for c in it:
        if c == "\\":
            n = next(it, "")
            out.append({"t": "\t", "n": "\n", "\\": "\\"}.get(n, n))
        else:
            out.append(c)
    return "".join(out)


def stalled_tensors() -> List[dict]:
    """Coordinator-side stall findings as data: one
    ``{"name", "age_secs", "missing_ranks"}`` per tensor past the
    warning age (``HOROVOD_STALL_CHECK_TIME_SECONDS``). Empty on
    worker ranks — only the coordinator holds the pending table."""
    lib = _lib()
    # The table can grow between the size probe and the copy; retry
    # with the newly reported size rather than parse a truncated line.
    need = lib.hvd_stalled_tensors(None, 0)
    while True:
        buf = ctypes.create_string_buffer(need + 256)
        need = lib.hvd_stalled_tensors(buf, len(buf))
        if need <= len(buf):
            break
    out = []
    for line in buf.value.decode().splitlines():
        name, age, ranks = line.split("\t")
        out.append({
            "name": _unescape_stall_name(name),
            "age_secs": float(age),
            "missing_ranks": [int(r) for r in ranks.split(",") if r],
        })
    return out


# ---------------------------------------------------------------------------
# flight recorder (the postmortem half of the stall/metrics story)
# ---------------------------------------------------------------------------

def _parse_flight_text(text: str) -> List[dict]:
    """Parse the flight dump/snapshot text format (header line plus one
    ``seq\\tt_us\\tname\\ta0\\ta1`` row per event) into event dicts.
    Shared with ``bin/hvd-trace``, which reads the same format off
    disk. ``t_us`` is CLOCK_MONOTONIC microseconds; the header's
    ``mono_us``/``wall_us`` pair (:func:`_parse_flight_header`) maps it
    onto wall time."""
    events = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        seq, t_us, name, a0, a1 = line.split("\t")
        events.append({
            "seq": int(seq),
            "t_us": int(t_us),
            "event": name,
            "a0": int(a0),
            "a1": int(a1),
        })
    return events


def _parse_flight_header(text: str) -> dict:
    """``{"version", "pid", "mono_us", "wall_us"}`` from the dump's
    ``# flight v1 pid=... mono_us=... wall_us=...`` header line."""
    out: dict = {}
    for line in text.splitlines():
        if not line.startswith("# flight"):
            continue
        for tok in line.split():
            if tok.startswith("v") and tok[1:].isdigit():
                out["version"] = int(tok[1:])
            elif "=" in tok:
                k, _, v = tok.partition("=")
                out[k] = int(v)
        break
    return out


def _flight_text() -> str:
    lib = _lib()
    need = lib.hvd_flight_snapshot(None, 0)
    while True:
        buf = ctypes.create_string_buffer(int(need) + 256)
        need = lib.hvd_flight_snapshot(buf, len(buf))
        if need <= len(buf):
            break
    return buf.value.decode()


def flight_events() -> List[dict]:
    """The flight recorder's surviving ring, oldest first: one
    ``{"seq", "t_us", "event", "a0", "a1"}`` per control-plane event
    (catalog with argument units in docs/observability.md). ``t_us``
    is on the ``time.monotonic()`` axis, so an event's age is
    ``time.monotonic() - e["t_us"] / 1e6``."""
    return _parse_flight_text(_flight_text())


def flight_record(event: int, a0: int = 0, a1: int = 0) -> None:
    """Record one event into the native ring (ids:
    ``basics.FLIGHT_*``). Python control planes — the fleet router's
    peer-death/requeue path — share the ring with the native core so
    one dump tells the whole story."""
    _lib().hvd_flight_record(int(event), int(a0), int(a1))


def flight_dump(path: Optional[str] = None) -> bool:
    """Write the postmortem dump. ``None`` uses the
    ``HOROVOD_FLIGHT_DIR`` auto-dump path armed at library load;
    returns False when neither resolves (no directory configured)."""
    p = path.encode() if isinstance(path, str) else path
    return _lib().hvd_flight_dump(p) == 0


def flight_clear() -> None:
    """Empty the ring (test/measurement-window scoping)."""
    _lib().hvd_flight_clear()


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

def _sanitize(name: str) -> str:
    """Prometheus metric-name charset: [a-zA-Z_:][a-zA-Z0-9_:]*."""
    s = "".join(c if (c.isalnum() or c in "_:") else "_" for c in name)
    return ("_" + s) if s and s[0].isdigit() else (s or "_")


def _escape_label(v: str) -> str:
    """Prometheus label-value escaping: backslash, quote, newline."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def render_gauges(prefix: str, values: Dict[str, object],
                  labels: Optional[Dict[str, str]] = None) -> str:
    """Shared exposition helper: render a flat dict as gauge families
    under ``prefix`` (None values are skipped — an empty latency series
    has no sample, not a 0; a dict of numbers, a count by cause, is a
    family a key: ``<key>_<its key>``). The serving engine's snapshot
    renders through here, so serving and training speak one text format.
    ``labels`` (e.g. ``{"instance": "3"}``) ride every sample so
    several exporters of the same family — N engine replicas in one
    process — emit distinguishable series instead of colliding on the
    bare name (:func:`metrics_prometheus` dedupes the per-family TYPE
    line across fragments)."""
    label_str = ""
    if labels:
        label_str = "{" + ",".join(
            f'{_sanitize(k)}="{_escape_label(v)}"'
            for k, v in sorted(labels.items())) + "}"
    lines = []
    flat = {f"{key}_{sub}" if sub else key: v
            for key, val in values.items()
            for sub, v in (val.items() if isinstance(val, dict)
                           else (("", val),))}
    for key in sorted(flat):
        v = flat[key]
        if v is None or isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        name = f"{_sanitize(prefix)}_{_sanitize(key)}"
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name}{label_str} {v}")
    return "\n".join(lines) + ("\n" if lines else "")


def render_native(snap: Optional[dict] = None) -> str:
    """Native registry snapshot -> Prometheus text: counters
    (``*_total``) and gauges as-is, histograms in the cumulative
    ``_bucket{le=...}`` / ``_sum`` / ``_count`` shape (the log2 buckets
    are exactly the ``le`` edges ``2**i``)."""
    snap = snap or snapshot()
    _cnames, kinds, _hnames = _names()
    lines = []
    for idx, (name, v) in enumerate(snap["counters"].items()):
        full = f"{NAMESPACE}_{_sanitize(name)}"
        kind = "gauge" if (idx < len(kinds) and kinds[idx] == 1) else "counter"
        lines.append(f"# TYPE {full} {kind}")
        lines.append(f"{full} {v}")
    for name, h in snap["histograms"].items():
        full = f"{NAMESPACE}_{_sanitize(name)}"
        lines.append(f"# TYPE {full} histogram")
        cum = 0
        for i, b in enumerate(h["buckets"]):
            cum += b
            le = "+Inf" if i == len(h["buckets"]) - 1 else str(2 ** i)
            lines.append(f'{full}_bucket{{le="{le}"}} {cum}')
        lines.append(f"{full}_sum {h['sum']}")
        lines.append(f"{full}_count {h['count']}")
    return "\n".join(lines) + "\n"


# Secondary exporters: other subsystems (the serving engine) register a
# zero-arg callable returning an exposition fragment; metrics_prometheus
# appends every live fragment so one scrape covers the whole process.
_exporters: Dict[str, Callable[[], str]] = {}
_exporters_lock = threading.Lock()


def register_exporter(key: str, fn: Callable[[], str]) -> None:
    """Register (or replace) a named exposition-fragment source. Pass a
    bound method of a long-lived object; use a weakref wrapper if the
    object's lifetime should control the registration (see
    ``ServeMetrics``)."""
    with _exporters_lock:
        _exporters[key] = fn


def unregister_exporter(key: str) -> None:
    with _exporters_lock:
        _exporters.pop(key, None)


def register_exporter_weak(key: str, obj, method_name: str) -> None:
    """Weakly-bound registration: the fragment renders while ``obj`` is
    alive and silently disappears (unregistering itself) once it is
    collected — so an abandoned engine can't pin itself or poison the
    scrape."""
    ref = weakref.ref(obj)

    def _render() -> str:
        o = ref()
        if o is None:
            unregister_exporter(key)
            return ""
        return getattr(o, method_name)()

    register_exporter(key, _render)


def metrics_prometheus() -> str:
    """Full-process Prometheus text exposition: the native registry
    plus every registered secondary exporter (serving). Scrape it via
    :func:`start_metrics_server` or dump it with
    ``bin/hvd-metrics-dump``. Duplicate per-family ``# TYPE`` lines
    across fragments are dropped (the format allows one TYPE line per
    metric name): N engine replicas each export the same ``serve_*``
    families with different ``instance`` labels, and the first
    fragment's TYPE line speaks for all of them."""
    parts = [render_native()]
    with _exporters_lock:
        fns = list(_exporters.items())
    for _key, fn in fns:
        try:
            frag = fn()
        except Exception:
            continue  # one sick exporter must not kill the scrape
        if frag:
            parts.append(frag)
    lines: List[str] = []
    typed: set = set()
    for part in parts:
        for line in part.splitlines():
            if line.startswith("# TYPE "):
                # Tolerate a malformed exporter line (too few tokens):
                # the per-exporter try/except above can't catch THIS
                # loop, and one sick fragment must not 500 the scrape.
                toks = line.split()
                fam = toks[2] if len(toks) >= 3 else None
                if fam is not None:
                    if fam in typed:
                        continue
                    typed.add(fam)
            lines.append(line)
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# cross-rank aggregation
# ---------------------------------------------------------------------------

#: Series order for the aggregation vector: counters, then per-hist
#: count/sum/p99. Fixed by the native enum order, so every rank builds
#: the identical vector.
def _agg_series(snap: dict):
    keys, vals = [], []
    for name, v in snap["counters"].items():
        keys.append(name)
        vals.append(float(v))
    for name, h in snap["histograms"].items():
        keys.append(f"{name}_count")
        vals.append(float(h["count"]))
        keys.append(f"{name}_sum")
        vals.append(float(h["sum"]))
        # Per-rank p99 aggregates meaningfully under min/max (the
        # straggler spread); its sum column is meaningless — consumers
        # read min/max for *_p99 keys.
        keys.append(f"{name}_p99")
        vals.append(hist_quantile(h["count"], h["buckets"], 0.99))
    return keys, vals


def metrics_aggregate() -> Dict[str, Dict[str, float]]:
    """Cross-rank aggregation: ``{series: {"min", "max", "sum"}}`` over
    every counter and per-histogram count/sum/p99, reduced over the
    existing allreduce data plane (three float64 allreduces). This IS a
    collective — every rank must call it, and every rank gets the same
    result; rank 0 typically reports. The min/max spread of a timing
    series (e.g. ``shm_barrier_us_p99``) is the straggler signal
    (docs/observability.md)."""
    import numpy as np

    from horovod_tpu import api
    from horovod_tpu.common.ops_enum import Max, Min, Sum

    keys, vals = _agg_series(snapshot())
    # +Inf (empty-quantile sentinel is 0.0, overflow-bucket p99 is inf)
    # would poison the sum reduction on every rank; clamp to a finite
    # ceiling that still reads as "overflow bucket".
    vec = np.nan_to_num(np.asarray(vals, dtype=np.float64),
                        posinf=float(2 ** 62))
    reduced = {}
    for tag, op in (("min", Min), ("max", Max), ("sum", Sum)):
        reduced[tag] = api.allreduce(vec, op=op,
                                     name=f"hvd.metrics_agg.{tag}")
    return {
        k: {"min": float(reduced["min"][i]), "max": float(reduced["max"][i]),
            "sum": float(reduced["sum"][i])}
        for i, k in enumerate(keys)
    }


# ---------------------------------------------------------------------------
# exposition HTTP server (rank-0 scrape endpoint)
# ---------------------------------------------------------------------------

def start_metrics_server(port: int = 0, addr: str = "0.0.0.0"):
    """Serve :func:`metrics_prometheus` over HTTP on a daemon thread:
    ``GET /metrics`` (or ``/``) returns the text exposition, ``GET
    /metrics.json`` the flat :func:`metrics` dict. Returns the
    ``ThreadingHTTPServer`` — read the bound port from
    ``server.server_address[1]`` (``port=0`` picks a free one), stop it
    with ``server.shutdown(); server.server_close()`` (``shutdown()``
    alone leaves the socket listening, so scrapers hang in the backlog
    instead of getting connection-refused). Typically started on rank 0
    only; the
    ``bin/hvd-metrics-dump --url`` CLI and any Prometheus scraper
    attach here (docs/observability.md)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — http.server API
            path = self.path.split("?")[0].rstrip("/") or "/metrics"
            if path == "/metrics.json":
                body = json.dumps(metrics()).encode()
                ctype = "application/json"
            elif path in ("/metrics", ""):
                body = metrics_prometheus().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            else:
                self.send_error(404)
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # scrapes must not spam stderr
            pass

    server = ThreadingHTTPServer((addr, port), Handler)
    t = threading.Thread(target=server.serve_forever, daemon=True,
                         name="hvd-metrics-http")
    t.start()
    return server
