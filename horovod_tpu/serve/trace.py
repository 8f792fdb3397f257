"""Request-scoped distributed tracing for the serving fleet.

The serve plane already records per-step chrome spans on every engine
(:meth:`ServeMetrics.phase`), but a
cross-process fleet scatters one request's life across processes with
*different* ``perf_counter`` epochs and no shared request identity:
you can see that *a* prefill ran on worker 2, not that it was *your*
request's prefill. This module adds the missing identity and the
router-side half of the timeline:

* **Trace ids.** The router mints one 64-bit id per request at submit
  (:func:`mint_trace_id` — FNV-1a over (salt, rid), deterministic for
  a fixed fleet seed, never 0: id 0 means "unsampled" everywhere).
  The id rides the RPC frame header (``rpc.py`` protocol v2) to the
  worker, which tags the engine spans it already records; the router
  tags its own queue-wait / placement / handoff / e2e spans here.
* **Sampling.** ``HOROVOD_TRACE_SAMPLE`` (sane-env style: a fraction
  in [0, 1], default 1 = trace everything) decides per request,
  deterministically by rid hash — the same request traces or doesn't
  across reruns. An unsampled request carries trace id 0 and pays
  nothing beyond the sampling test itself.
* **One timebase.** Every export carries a ``(clock_now, wall_now)``
  anchor pair in its metadata; remote workers additionally get the
  router's RTT-estimated ``clock_offset`` (heartbeat midpoints, the
  PR 11 age-re-anchor discipline extended to a persistent offset).
  ``bin/hvd-trace merge`` maps every span onto the router's wall
  clock with them.

See docs/observability.md "Distributed request tracing".
"""

from __future__ import annotations

import json
import os
import time
import warnings
from typing import Any, Dict, List, Optional

#: Sane-env sampling knob: fraction of requests to trace, default 1.0
#: (everything). 0 disables minting entirely. Documented in
#: docs/observability.md.
TRACE_SAMPLE_ENV = "HOROVOD_TRACE_SAMPLE"

#: Span cap, same drop-newest policy as ``ServeMetrics`` events.
MAX_TRACE_EVENTS = 100_000

_warned_bad_sample = False


def trace_sample_rate() -> float:
    """:data:`TRACE_SAMPLE_ENV` as a fraction in [0, 1]. Lenient
    parse in the sane-env tradition: unset/empty = 1.0, garbage warns
    once and falls back to 1.0 (a typo must not silently kill the
    whole observability plane), and numeric values clamp into
    range."""
    global _warned_bad_sample
    raw = os.environ.get(TRACE_SAMPLE_ENV, "").strip()
    if not raw:
        return 1.0
    try:
        val = float(raw)
    except ValueError:
        if not _warned_bad_sample:
            _warned_bad_sample = True
            warnings.warn(
                f"{TRACE_SAMPLE_ENV}={raw!r} is not a number; tracing "
                "every request (the default)", stacklevel=2)
        return 1.0
    return min(max(val, 0.0), 1.0)


def _fnv1a64(*parts: int) -> int:
    h = 0xcbf29ce484222325
    for p in parts:
        v = p & 0xFFFFFFFFFFFFFFFF
        for _ in range(8):
            h ^= v & 0xFF
            h = (h * 0x100000001b3) & 0xFFFFFFFFFFFFFFFF
            v >>= 8
    return h


def mint_trace_id(rid: int, salt: int = 0,
                  sample: Optional[float] = None) -> int:
    """Trace id for router request ``rid``: FNV-1a over (salt, rid),
    never 0 (0 = unsampled, everywhere). The sampling decision is
    deterministic by rid hash, so a replayed seeded run traces the
    same requests; ``sample`` overrides the env knob (tests)."""
    rate = trace_sample_rate() if sample is None else sample
    if rate <= 0.0:
        return 0
    h = _fnv1a64(salt, rid)
    if rate < 1.0 and (h % 1_000_000) >= int(rate * 1_000_000):
        return 0
    return h or 1


class RouterTrace:
    """Chrome-event recorder for the router's half of a request's
    life: submit, queue wait, placement verdict, RPC wire time,
    handoffs/migrations, requeues, and the end-to-end span. All
    timestamps are on the ROUTER clock (``ts`` microseconds since
    construction, the same convention as ``ServeMetrics._span``);
    :meth:`export` writes the anchor pair that maps them onto wall
    time."""

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self.started_at = clock()
        self._events: List[dict] = []

    def _ts(self, t: float) -> float:
        return round((t - self.started_at) * 1e6, 1)

    def span(self, name: str, t0: float, dur_s: float,
             trace: int = 0, **args: Any) -> None:
        if len(self._events) >= MAX_TRACE_EVENTS:
            return
        if trace:
            args["trace"] = trace
        self._events.append({
            "name": name, "ph": "X", "pid": 0, "tid": 0,
            "ts": self._ts(t0), "dur": round(dur_s * 1e6, 1),
            "args": args})

    def instant(self, name: str, t: Optional[float] = None,
                trace: int = 0, **args: Any) -> None:
        if len(self._events) >= MAX_TRACE_EVENTS:
            return
        if trace:
            args["trace"] = trace
        self._events.append({
            "name": name, "ph": "i", "s": "t", "pid": 0, "tid": 0,
            "ts": self._ts(self._clock() if t is None else t),
            "args": args})

    @property
    def events(self) -> List[dict]:
        return self._events

    def metadata(self, **extra: Any) -> Dict[str, Any]:
        """Anchor metadata for :meth:`export`: the ``(clock_now,
        wall_now)`` pair every merge timebase computation needs, plus
        whatever the caller adds (kind/instance/offsets)."""
        md = {
            "kind": "router",
            "pid": os.getpid(),
            "started_at": self.started_at,
            "clock_now": self._clock(),
            "wall_now": time.time(),
            "clock_offset": 0.0,
        }
        md.update(extra)
        return md

    def export(self, path: str, **extra: Any) -> None:
        with open(path, "w") as f:
            json.dump({"traceEvents": self._events,
                       "displayTimeUnit": "ms",
                       "metadata": self.metadata(**extra)}, f)
