"""Serving metrics: throughput, latency tails, and scheduler health.

The MLPerf TPU-pod scaling writeup (PAPERS.md) motivates reporting
throughput *and* tail latency as first-class serving metrics — a
batch-packing change that raises tokens/sec while blowing p99
first-token latency is a regression for interactive traffic, and
neither number alone shows it.

Surfaces:

* :meth:`ServeMetrics.snapshot` — counters + percentiles as a flat
  dict.
* :meth:`ServeMetrics.export_chrome_trace` — per-step spans in the
  chrome-tracing JSON format, viewable in the same ``chrome://tracing``
  / Perfetto UI as the host timeline (``hvd.start_timeline`` /
  ``horovodrun --timeline-filename``).
* :meth:`ServeMetrics.phase` — how the engine writes those spans: one
  host phase is one span on the engine's clock AND a
  ``jax.profiler.TraceAnnotation`` of the same name, opened and closed
  at the same two points. Every program span therefore has a twin in
  a profiler trace's host plane, and the offset between the engine's
  clock and the trace's is the difference of any twin pair
  (docs/observability.md lists the spans and their args). A phase
  that calls the device is a :class:`DeviceCall`: one numbered record
  from launch to readback, which a reader of the trace joins to the
  program's run on the chip by that number.
* :meth:`ServeMetrics.launch` / :meth:`ServeMetrics.finish` — the two
  ends of a :class:`DeviceCall` by hand, for the decode call that the
  engine launches in one ``step()`` and reads in the next, while its
  successor already runs (PR 37). ``serve:decode`` is then one decode
  step as a client sees it: from the later of the call's launch and
  the end of the previous call's read, to the end of its own read,
  with ``ahead`` saying whether a call was in flight when it was
  launched. ``serve:host_gap`` is written only for time in which no
  call was in flight.
* ``serve:unfed`` — every interval in which the engine had work and
  the device had nothing enqueued: from the moment a call's result was
  ready with no other call in flight to the moment the next call's
  jitted call returned, in three parts (the readback, the host between
  the read and the next launch, the dispatch), the host's part by the
  phases that ran in it, and with why no successor was in flight
  (:data:`UNFED_WHYS`). Where the engine ran out of work in between
  (:meth:`ServeMetrics.record_idle`) the interval is the traffic's and
  is written as ``serve:no_work``. ``device_unfed_s_total``,
  ``device_unfed_s_by_why`` and ``device_no_work_s_total`` sum them for
  an operator who has no profiler.
* ``serve:stall`` — a device call or a host gap many times longer
  than its kind usually is, written with what the host was doing in it
  (its own CPU time, the process's, the collector's pauses, what
  compiled), counted in ``stalls_total`` and logged at WARNING.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import json
import logging
import os
import statistics
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from horovod_tpu.common.compile_cache import compile_stats
from horovod_tpu.models.moe import moe_metrics
from horovod_tpu.ops.paged_decode import ring_reach

#: Keep at most this many latency samples per series (drop-oldest);
#: long-running engines must not grow without bound.
MAX_SAMPLES = 100_000

#: A device call or a host gap is a stall when it is longer than
#: ``STALL_FACTOR`` times the median of the last ``STALL_WINDOW`` of its
#: kind AND than ``STALL_MIN_S``; a kind is judged once it has
#: ``STALL_MIN_SAMPLES``. A decode step's spread is a few percent and a
#: prompt's prefill varies eightfold with its length, so eight medians
#: is a call that did not merely have more to do.
STALL_WINDOW = 64
STALL_FACTOR = 8.0
STALL_MIN_S = 0.050
STALL_MIN_SAMPLES = 8
#: Why the engine read a decode call in flight before it could launch
#: the next one ahead (``decode_drains_<cause>_total``): a prefill was
#: due; the batch fits a smaller bucket; nothing is left to launch; a
#: queued request waits for the slot of a sequence that ends at the
#: call in flight; pages are about to move in or out (export, inject).
DRAIN_CAUSES = ("prefill", "bucket", "idle", "admit", "migrate")
#: Why no successor was in flight when a call's result was ready
#: (``why`` of ``serve:unfed``): the call was a decode call drained for
#: one of :data:`DRAIN_CAUSES`; it was a prefill chunk, whose token the
#: host reads before it launches anything; it was part of a speculative
#: round, whose acceptance the host reads.
UNFED_WHYS = DRAIN_CAUSES + ("prefill_read", "spec")
_WHY_AFTER = {"serve:prefill": "prefill_read", "serve:spec_draft": "spec",
              "serve:spec_verify": "spec"}
#: The median is taken again every this many samples, so that a call
#: pays one comparison and not a sort.
_REMEDIAN_EVERY = 8
#: The CPU clocks are a system call each (6 us on the v5e's sandbox,
#: where they also tick at 10 ms), so a device call does not read them:
#: a mark is kept, taken again at the end of a device call once it is
#: this old, and a stall's CPU time is counted from the mark.
_CPU_MARK_S = 0.1

_log = logging.getLogger("horovod_tpu")


def percentile(samples: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in [0, 100]); None on no samples."""
    if not samples:
        return None
    xs = sorted(samples)
    idx = min(len(xs) - 1, max(0, int(round(q / 100.0 * (len(xs) - 1)))))
    return xs[idx]


class Phase:
    """What :meth:`ServeMetrics.phase` yields: the span's start on the
    engine's clock, its args (the caller may add to them, also after
    the span closed) and, once closed, its duration."""

    __slots__ = ("t0", "dur", "args")

    def __init__(self, t0: float, args: dict):
        self.t0, self.dur, self.args = t0, 0.0, args

    @property
    def end(self) -> float:
        return self.t0 + self.dur


class DeviceCall(Phase):
    """A phase that launches a device program and reads its result
    back, as one record. ``call`` is the engine's count of such
    phases: it is written on the span, on its annotation and on the
    nested ones, so that a trace's reader finds the runtime's launch
    inside this call's annotation and, through the launch's ``run_id``,
    the program's run on the chip. The host's side is kept in parts: until
    the jitted call returned (:meth:`dispatch`), until its result was
    ready, and until that was copied to the host (:meth:`read`). A
    phase of several launches (a speculative round's k draft steps)
    sums each part.

    The record has two ends, :meth:`ServeMetrics.launch` and
    :meth:`ServeMetrics.finish`, which need not lie in one ``step()``:
    the annotation of the call's name (its twin) is entered at the one
    and left at the other by hand, so the twins of two neighbouring
    decode calls overlap. ``t0``, where its span starts, is the launch,
    or the end of the previous call's read when that came later (the
    call was launched ahead and has been queueing behind its
    predecessor until then)."""

    __slots__ = ("name", "call", "dispatch_s", "wait_s", "_clock", "_mark",
                 "_gc0", "_twin", "_before_s", "_gap", "_metrics")

    def __init__(self, name: str, call: int, metrics: "ServeMetrics",
                 args: dict):
        self._twin = TraceAnnotation(name, call=call)
        self._twin.__enter__()
        clock = metrics._clock
        super().__init__(clock(), args)
        self.name, self.call, self._clock = name, call, clock
        self._metrics = metrics
        self.dispatch_s = self.wait_s = self._before_s = 0.0
        self._mark = self.t0
        self._gc0 = metrics._gc.seq
        self._gap = None

    def _lap(self) -> float:
        now = self._clock()
        lap, self._mark = now - self._mark, now
        return lap

    def _starts_at(self, t: float) -> None:
        """The previous call's read ended at ``t``, after this call
        was launched: its span starts there, and what it spent in
        dispatch lies before its span."""
        self.t0 = self._mark = t
        self._before_s = self.dispatch_s

    def dispatch(self) -> "_Dispatch":
        """Around the jitted call: the ``:dispatch`` annotation, and
        ``dispatch_ms`` when it returned."""
        return _Dispatch(self)

    def read(self, out, to_host):
        """``to_host(out)`` once the jitted call's result ``out`` is
        ready, as ``:wait`` and then ``:readback``: the wait for the
        program, then the rest of the wait for the copy
        (``np.asarray``, ``int``). The copy is asked for before the
        wait, so that it follows the program on the device's queue as
        it does when ``np.asarray`` meets a result that is not ready;
        asked for after the wait it costs the host one more round trip
        (0.1 ms a call on the v5e). Where no other call is in flight
        the device has nothing to run from the wait's end on
        (``serve:unfed``)."""
        name, call = self.name, self.call
        with TraceAnnotation(name + ":wait", call=call):
            out.copy_to_host_async()
            out.block_until_ready()
        self.wait_s += self._lap()
        self._metrics._result_ready(self)
        with TraceAnnotation(name + ":readback", call=call):
            return to_host(out)

    @property
    def ready_s(self) -> float:
        """From the span's start until the result was ready (the
        dispatch that lies inside the span, and the wait)."""
        return self.dispatch_s - self._before_s + self.wait_s

    def part(self) -> str:
        """Where most of the span's time went. The ``wait`` of a call
        launched ahead holds what the host did between the previous
        call's read and this one's, which is one ``step()``'s own work."""
        parts = {"dispatch": self.dispatch_s - self._before_s,
                 "wait": self.wait_s,
                 "readback": self.dur - self.ready_s}
        return max(parts, key=parts.get)


class _Dispatch:
    """The block :meth:`DeviceCall.dispatch` opens (a class and not a
    generator: this runs once a device call)."""

    __slots__ = ("_call", "_annotation")

    def __init__(self, call: DeviceCall):
        self._call = call
        self._annotation = TraceAnnotation(call.name + ":dispatch",
                                           call=call.call)

    def __enter__(self) -> None:
        self._annotation.__enter__()

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)
        self._call.dispatch_s += self._call._lap()
        if self._call._metrics._unfed is not None:
            self._call._metrics._fed_again(self._call)


class _Unfed:
    """The device has had nothing enqueued since ``ready``, when the
    result of call ``after`` was ready with no other call in flight.
    ``read_end`` is where that call's read ended (``finish``); the host
    phases that end after it add their time to ``phases``, and a
    ``step()`` that begins after it the time outside any
    (``outside_step``)."""

    __slots__ = ("ready", "read_end", "after", "why", "phases", "no_work")

    def __init__(self, ready: float, after: int, why: str):
        self.ready, self.after, self.why = ready, after, why
        self.read_end: Optional[float] = None
        self.phases: Dict[str, float] = {}
        self.no_work = False


class _Typical:
    """The running median of the last ``STALL_WINDOW`` durations of one
    kind of span, and the limit past which one more is a stall."""

    __slots__ = ("last", "seen", "median", "limit")

    def __init__(self):
        self.last: collections.deque = collections.deque(maxlen=STALL_WINDOW)
        self.seen = 0
        self.median = 0.0
        self.limit = float("inf")

    def add(self, dur: float) -> bool:
        """Take one duration in; was it a stall by what came before?"""
        stalled = dur > self.limit
        self.last.append(dur)
        self.seen += 1
        if (self.seen % _REMEDIAN_EVERY == 0
                and len(self.last) >= STALL_MIN_SAMPLES):
            self.median = statistics.median(self.last)
            self.limit = max(STALL_FACTOR * self.median, STALL_MIN_S)
        return stalled


class _GcWatch:
    """The collector's pauses, for every engine of the process: one
    ``gc.callbacks`` hook. Each pause is ``(start, duration,
    generation)`` on ``time.perf_counter`` (the engine's clock unless a
    test gave it another) and a ``serve:gc`` annotation, so that in a
    profiler's trace an idle gap under a collection is named for it. A
    reader notes ``seq`` at its interval's ends and asks
    :meth:`between` for the pauses inside."""

    def __init__(self):
        self.pauses: collections.deque = collections.deque(maxlen=256)
        self.seq = 0
        self._t0: Optional[float] = None
        self._annotation = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._annotation = TraceAnnotation(
                "serve:gc", generation=info["generation"])
            self._annotation.__enter__()
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))
            self.seq += 1
            self._t0 = None
            self._annotation.__exit__(None, None, None)

    def between(self, seq0: int, seq1: Optional[int] = None) -> list:
        """The pauses that ended after ``seq`` read ``seq0`` and by the
        time it read ``seq1`` (now, if left out), as far as kept."""
        oldest = self.seq - len(self.pauses)
        hi = self.seq if seq1 is None else seq1
        return list(self.pauses)[max(seq0 - oldest, 0):max(hi - oldest, 0)]


_gc_watch: Optional[_GcWatch] = None
_gc_watch_lock = threading.Lock()


def _watch_gc() -> _GcWatch:
    """The process's one :class:`_GcWatch`, hooked in by the first
    :class:`ServeMetrics`."""
    global _gc_watch
    with _gc_watch_lock:
        if _gc_watch is None:
            _gc_watch = _GcWatch()
            gc.callbacks.append(_gc_watch)
        return _gc_watch


#: Process-wide monotonic default for the per-engine ``instance``
#: label: N replicas sharing one exposition endpoint must not collide
#: on the bare ``serve_`` series names (Prometheus reads duplicate
#: unlabeled samples as one broken family, and fleet sums silently
#: undercount). An explicit instance (the router passes its replica
#: id) overrides the counter.
_instance_ids = itertools.count()


class ServeMetrics:
    def __init__(self, clock=time.perf_counter,
                 instance: Optional[str] = None):
        self._clock = clock
        self._allocator = None
        self._alloc_base = (0, 0, 0)
        self.instance = (str(next(_instance_ids)) if instance is None
                         else str(instance))
        # Device calls are numbered for the engine's life, not since
        # reset(): a number names one call in any export.
        self._calls = itertools.count(1)
        # Device calls launched and not finished, oldest first: like
        # the numbers, not reset() (a call may be in flight across it).
        self._flying: List[DeviceCall] = []
        self._gc = _watch_gc()
        self.reset()
        # Export through the process-wide telemetry endpoint: a scrape
        # of hvd.metrics_prometheus() (or the rank-0 metrics server)
        # covers training AND serving in one text format. Weakly bound
        # so an abandoned engine's metrics vanish with it.
        from horovod_tpu.metrics import register_exporter_weak
        register_exporter_weak(f"serve_{id(self)}", self, "prometheus")

    def reset(self) -> None:
        self.started_at = self._clock()
        self.tokens_generated = 0
        self.requests_submitted = 0
        self.requests_finished = 0
        self.requests_expired = 0
        self.requests_rejected = 0
        self.handoffs_in = 0
        self.handoffs_out = 0
        self.prefill_steps = 0
        self.decode_steps = 0
        # Decode calls launched while their predecessor was still in
        # flight, and the times a call in flight was read before a
        # successor could be launched, by what stood in the way.
        self.decode_ahead_total = 0
        self.decode_drains: Dict[str, int] = dict.fromkeys(DRAIN_CAUSES, 0)
        # Seconds the device had nothing enqueued while the engine had
        # work (the `serve:unfed` spans' sum, and by their `why`), and
        # while it had none (`serve:no_work`); the interval that is
        # open now, if one is.
        self.device_unfed_s = 0.0
        self.device_unfed_s_by_why: Dict[str, float] = dict.fromkeys(
            UNFED_WHYS, 0.0)
        self.device_no_work_s = 0.0
        self._unfed: Optional[_Unfed] = None
        # Key blocks of the latent pool that the decode calls' kernel
        # read (every row to its own length), and what a loop to the
        # batch's longest row would have gathered for every row.
        self.latent_decode_key_blocks_total = 0
        self.latent_decode_key_blocks_longest_total = 0
        # Pages of the full layers' K pool (V's are as many) that the
        # decode calls' kernel read, every row to its own length, and
        # what every row's whole table would have been.
        self.paged_decode_pages_total = 0
        self.paged_decode_pages_table_total = 0
        # Pages of the window layers' K rings (V's are as many) that the
        # decode calls' kernel read, every row from the page of its
        # window's first key to its own position, and what every
        # slot's whole ring holds.
        self.window_decode_pages_total = 0
        self.window_decode_pages_ring_total = 0
        self.queue_depth = 0
        self.max_queue_depth = 0
        self.stalls_total = 0
        self._occupancy_sum = 0.0
        # Token-granularity prefix-cache accounting: per admission,
        # how many prompt tokens were served out of the cache vs
        # prefilled. The block-granularity counters (hits / misses /
        # evictions) live on the attached BlockAllocator.
        self.prefix_hit_tokens = 0
        self.prefix_prefill_tokens = 0
        # The second kind of cache (a configuration with window
        # layers; kv_cache.KVCache): the most positions a window layer
        # held for one sequence, and the rings in use, in blocks.
        self.kv_window_positions_max = 0
        self.kv_window_blocks_in_use = 0
        # The kinds whose state is not keys (kda, mamba, lightning,
        # conv, eva, mla): the batch slots whose recurrent state, whose
        # convolution rows alone, or whose open window's rows a
        # sequence holds and their bytes; the
        # positions the latent pool held for the rows of the last
        # decode call (their sum: what absorbed attention has to read),
        # and the most it held for one sequence.
        self.state_slots_in_use = 0
        self.state_bytes = 0
        self.kv_latent_positions_live = 0
        self.kv_latent_positions_max = 0
        # ... and the most places an mla_sliding layer's ring of latents
        # held of one sequence, with the pages of such rings the decode
        # calls read (ops/paged_decode.py::latent_ring_decode).
        self.kv_latent_ring_positions_max = 0
        self.latent_ring_decode_pages_total = 0
        # Sparse layers (a selection inside paged attention): the
        # queries of the prefill calls and the rows of the decode calls
        # that chose their blocks (at or past sparse_dense_len), those
        # of the queries whose chunk scored through the kernel
        # (decode.sparse_select_taken), and the most compressed keys
        # (kernels) held for one sequence.
        self.sparse_selected_queries_total = 0
        self.sparse_select_kernel_queries_total = 0
        self.sparse_selected_rows_total = 0
        self.kv_compressed_max = 0
        # Mamba2 layers (a chunk's SSD over blocks): the positions the
        # prefill calls scanned (their buckets, pads too) and those of
        # them whose scan the kernel ran (decode.ssd_scan_taken).
        self.ssd_scanned_positions_total = 0
        self.ssd_scan_kernel_positions_total = 0
        # Eva layers (an aligned window's rows by slot beside chunk
        # summaries in pages): the summary pages the rows of the last
        # decode call attend (those of their closed windows; the pages
        # of an open window are allocated and written, and not read
        # yet), the most a call attended, and the windows closed (by a
        # chunk or a decode step that wrote a window's last row).
        self.eva_summary_pages_in_use = 0
        self.eva_summary_pages_max = 0
        self.eva_windows_closed_total = 0
        # Speculative decoding (serve/speculative.py): proposal /
        # acceptance tallies (their ratio is the token-weighted accept
        # rate) and the per-round draft / verify wall-time series.
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_draft_s: List[float] = []
        self.spec_verify_s: List[float] = []
        self.first_token_s: List[float] = []
        self.per_token_s: List[float] = []
        # Drop-oldest: an engine that has run for an hour exports its
        # most recent spans, not its first ones.
        self._events: collections.deque = collections.deque(
            maxlen=MAX_SAMPLES)
        # Where the last device call's host sync ended with no other
        # call in flight (engine clock), and whether a step() began
        # since: `serve:host_gap` runs from there to the next device
        # call's first line; with it the collector's count at that
        # point, for a gap that stalls.
        self._device_idle_since: Optional[float] = None
        self._idle_gc = 0
        self._stepped_since = False
        # (engine clock, this thread's CPU seconds, the process's)
        self._cpu_mark = (self.started_at, time.thread_time(),
                          time.process_time())
        self._typical: Dict[str, _Typical] = collections.defaultdict(
            _Typical)
        # Allocator counters are lifetime totals; baseline them here
        # so snapshots report the same window as every other counter
        # in this object (reset-to-now), not engine-lifetime numbers.
        if self._allocator is not None:
            a = self._allocator
            self._alloc_base = (a.prefix_hits, a.prefix_misses,
                                a.evictions)

    def attach_allocator(self, allocator) -> None:
        """Let snapshots/trace export read the block pool's gauges
        (blocks in use, cached, high water) and prefix counters
        without the engine copying them in per step."""
        self._allocator = allocator
        self._alloc_base = (allocator.prefix_hits,
                            allocator.prefix_misses,
                            allocator.evictions)

    # -- recording ---------------------------------------------------

    def _span(self, name: str, t0: float, dur: float, args: dict) -> None:
        # chrome-tracing "complete" event; ts/dur in microseconds. The
        # event keeps `args` itself, not a copy: a phase's caller may
        # complete them after the span closed (a speculative round
        # knows what was accepted only after its verify step).
        self._events.append({
            "name": name, "ph": "X", "pid": 0, "tid": 0,
            "ts": round((t0 - self.started_at) * 1e6, 1),
            "dur": round(dur * 1e6, 1), "args": args})

    @contextlib.contextmanager
    def phase(self, name: str, device: bool = False, **args):
        """One host phase of the engine under two clocks at once: a
        ``TraceAnnotation(name)`` for a profiler trace's host plane and
        a chrome span of the same name on the engine's clock, read at
        the same two points. Yields the :class:`Phase` (``t0``, and
        ``args`` to add to); ``dur`` is set when the block ends, and
        the span is written then (not if the block raised).

        ``device=True`` marks a phase that dispatches a device program
        and ends in its host sync, and yields a :class:`DeviceCall`
        (:meth:`launch` at the block's first line, :meth:`finish` at
        its last): the span and the annotation carry its number
        (``call``), the span ``dispatch_ms`` (the jitted call, until it
        returned) and ``ready_ms`` (from the span's start until the
        result was ready; the rest is the copy to the host). The time
        since the previous such phase ended, if no call was in flight
        in it, is written as ``serve:host_gap`` (chrome span only,
        after the fact), with ``across_steps`` saying whether a
        ``step()`` began in between (then it holds the caller's time
        too). Either one, when it is a stall by its kind's running
        median, is also written as ``serve:stall`` (:meth:`_stall`)."""
        if not device:
            with TraceAnnotation(name):
                span = Phase(self._clock(), args)
                yield span
                span.dur = self._clock() - span.t0
            self._span(name, span.t0, span.dur, span.args)
            unfed = self._unfed
            if unfed is not None and unfed.read_end is not None:
                unfed.phases[name] = unfed.phases.get(name, 0.0) + span.dur
            return
        span = self.launch(name, **args)
        try:
            yield span
        except BaseException:
            self._flying.remove(span)
            self._unfed = None
            span._twin.__exit__(None, None, None)
            raise
        self.finish(span)

    def launch(self, name: str, **args) -> DeviceCall:
        """The first line of a device call: number it, enter its twin
        annotation and read the clock. The caller dispatches inside
        :meth:`DeviceCall.dispatch`, reads the result with
        :meth:`DeviceCall.read`, in this ``step()`` or a later one, and
        then calls :meth:`finish`. If no other call is in flight, the
        time since the last one's read ended is this call's
        ``serve:host_gap`` (written by :meth:`finish`)."""
        call = args["call"] = next(self._calls)
        span = DeviceCall(name, call, self, args)
        since = self._device_idle_since
        if since is not None and not self._flying:
            span._gap = (since, span.t0 - since, self._stepped_since,
                         self._gc.between(self._idle_gc, span._gc0))
        self._device_idle_since = None
        self._flying.append(span)
        return span

    def finish(self, span: DeviceCall) -> None:
        """The last line of a device call, after its read: leave the
        twin, write the span (and the host gap before its launch, if
        it had one), move the start of a call still in flight to here,
        and look for a stall."""
        end = self._clock()
        span._twin.__exit__(None, None, None)
        span.dur = end - span.t0
        name, call, args = span.name, span.call, span.args
        args["dispatch_ms"] = span.dispatch_s * 1e3
        args["ready_ms"] = span.ready_s * 1e3
        self._flying.remove(span)
        for other in self._flying:
            other._starts_at(end)
        if self._unfed is not None:
            self._unfed.read_end = end
        stalls = []
        if span._gap is not None:
            since, gap, stepped, gc_pauses = span._gap
            self._span("serve:host_gap", since, gap,
                       {"across_steps": stepped})
            if self._typical["serve:host_gap"].add(gap):
                stalls.append(("serve:host_gap", since, gap, "host_gap",
                               gc_pauses))
        if not self._flying:
            self._device_idle_since = end
            self._idle_gc = self._gc.seq
        self._stepped_since = False
        self._span(name, span.t0, span.dur, args)
        if self._typical[name].add(span.dur):
            stalls.append((name, span.t0, span.dur, span.part(),
                           self._gc.between(span._gc0)))
        if stalls or end - self._cpu_mark[0] >= _CPU_MARK_S:
            mark = (end, time.thread_time(), time.process_time())
            for stall in stalls:
                self._stall(*stall, call, self._cpu_mark, mark)
            self._cpu_mark = mark

    def _result_ready(self, span: DeviceCall) -> None:
        """``span``'s wait ended (at its ``_mark``): with no other call
        in flight the device is unfed from here on."""
        if len(self._flying) == 1:
            self._unfed = _Unfed(span._mark, span.call,
                                 _WHY_AFTER.get(span.name, span.name))

    def _fed_again(self, span: DeviceCall) -> None:
        """``span``'s jitted call returned (at its ``_mark``) while the
        device was unfed: write the interval as ``serve:unfed``, or as
        ``serve:no_work`` where the engine ran out of work in it. Its
        parts: the readback (until call ``after``'s read ended), the
        host (until ``span``'s launch, by the phases that ran in it)
        and the dispatch. Between two launches of one call (a
        speculative round's draft steps) no read's end is stamped, and
        the whole interval reads as dispatch, as ``dispatch_ms`` of
        the call's own span does."""
        unfed, self._unfed = self._unfed, None
        end = span._mark
        dur = end - unfed.ready
        if unfed.no_work:
            self.device_no_work_s += dur
            self._span("serve:no_work", unfed.ready, dur,
                       {"after": unfed.after, "before": span.call})
            return
        read_end = launch = unfed.ready
        if unfed.read_end is not None:
            read_end, launch = unfed.read_end, span.t0
        host_ms = (launch - read_end) * 1e3
        phases = {name: s * 1e3 for name, s in unfed.phases.items()}
        self.device_unfed_s += dur
        self.device_unfed_s_by_why[unfed.why] = (
            self.device_unfed_s_by_why.get(unfed.why, 0.0) + dur)
        self._span("serve:unfed", unfed.ready, dur, {
            "readback_ms": (read_end - unfed.ready) * 1e3,
            "host_ms": host_ms, "dispatch_ms": (end - launch) * 1e3,
            "phases": phases,
            "unnamed_ms": host_ms - sum(phases.values()),
            "why": unfed.why, "after": unfed.after, "before": span.call,
            "across_steps": self._stepped_since})

    def _stall(self, of: str, t0: float, dur: float, part: str,
               gc_pauses: list, call: int, mark0: tuple, mark1: tuple
               ) -> None:
        """One span of kind ``of`` took many times its kind's median:
        count it, write it as ``serve:stall`` with what the host was
        doing in it, and say so at WARNING, so that a run that stalls
        leaves its cause whether or not anything was tracing.
        ``part`` is where the time went (``dispatch`` | ``wait`` |
        ``readback`` of a device call, or ``host_gap``). ``cpu_ms`` and
        ``process_cpu_ms`` are this thread's and the process's CPU time
        between two reads of the clocks, ``mark0`` (at most
        ``_CPU_MARK_S`` before the stalled span began) and ``mark1``
        (the end of the device call that found the stall), which lie
        ``cpu_over_ms`` apart: a wait shows no CPU time of this thread,
        a collection or a computing host as much as wall time, another
        thread's work in ``process_cpu_ms`` alone. ``compiles`` names
        what the compile log saw inside the span."""
        ended = time.time() - (mark1[0] - (t0 + dur))
        compiles = sorted({
            str(e["fun_name"]) for e in compile_stats()["recent"]
            if e["at"] > ended - dur and e["at"] - e["seconds"] < ended})
        args = {
            "of": of, "call": call, "part": part,
            "typical_ms": self._typical[of].median * 1e3,
            "cpu_ms": (mark1[1] - mark0[1]) * 1e3,
            "process_cpu_ms": (mark1[2] - mark0[2]) * 1e3,
            "cpu_over_ms": (mark1[0] - mark0[0]) * 1e3,
            "gc_ms": sum(p[1] for p in gc_pauses) * 1e3,
            "gc_gen": max((p[2] for p in gc_pauses), default=None),
            "compiles": compiles}
        self.stalls_total += 1
        self._span("serve:stall", t0, dur, args)
        _log.warning(
            "serve stall: %s (call %d) took %.1f ms where %.1f ms is "
            "typical; the time went in %s; in the %.1f ms to its call's "
            "end: cpu %.1f ms, process cpu %.1f ms; gc %.1f ms (generation "
            "%s), compiled %s",
            of, call, dur * 1e3, args["typical_ms"], part,
            args["cpu_over_ms"], args["cpu_ms"], args["process_cpu_ms"],
            args["gc_ms"], args["gc_gen"], compiles or "nothing")

    def record_step(self, now: float) -> None:
        """A scheduler iteration with work to do began at ``now``:
        pool occupancy goes on a counter track next to the spans (live
        blocks vs warm refcount-0 cached blocks), once a step. Where
        the device is unfed, what no phase has held since the last
        read ended is the caller's time between two ``step()``s (and
        the few lines of ``step()`` after its last phase):
        ``outside_step`` among the interval's ``phases``."""
        self._stepped_since = True
        unfed = self._unfed
        if unfed is not None and unfed.read_end is not None:
            unfed.phases["outside_step"] = (
                unfed.phases.get("outside_step", 0.0) + now - unfed.read_end
                - sum(unfed.phases.values()))
        if self._allocator is not None:
            self._events.append({
                "name": "kv_blocks", "ph": "C", "pid": 0, "tid": 0,
                "ts": round((now - self.started_at) * 1e6, 1),
                "args": {"in_use": self._allocator.n_used,
                         "cached": self._allocator.n_cached}})

    def record_window_positions(self, held: int) -> None:
        """A window layer's ring now holds ``held`` positions of one
        sequence."""
        self.kv_window_positions_max = max(self.kv_window_positions_max,
                                           held)

    def record_latent_ring_positions(self, held: int) -> None:
        """An mla_sliding layer's ring of latents now holds ``held``
        positions of one sequence."""
        self.kv_latent_ring_positions_max = max(
            self.kv_latent_ring_positions_max, held)

    def record_latent_ring_decode(self, lengths, window: int, ring: int,
                                  page: int, layers: int) -> None:
        """A decode call was launched whose rows hold ``lengths``
        positions (an array, a padded row at 1), each seeing its newest
        ``window`` in a ring of ``ring`` latents read as pages of
        ``page``, in each of ``layers`` mla_sliding layers
        (:meth:`record_window_decode`'s count, of the one pool)."""
        at, seen = ring_reach(lengths - 1, window, ring)
        self.latent_ring_decode_pages_total += layers * int(
            (-(-(at % page + seen) // page)).sum())

    def record_latent_positions(self, held) -> None:
        """The latent pool holds ``held`` positions for each row of a
        decode call (an array, the padded rows left out)."""
        self.kv_latent_positions_live = int(held.sum())
        self.kv_latent_positions_max = max(self.kv_latent_positions_max,
                                           int(held.max(initial=0)))

    def record_sparse(self, cfg, held: int, prefill: int = 0,
                      decode: int = 0, kernel: int = 0) -> None:
        """A call was launched after which the sparse layers hold
        ``held`` positions of its longest sequence; ``prefill`` of its
        queries, or ``decode`` of its rows, choose their blocks,
        ``kernel`` of the queries by scores the kernel made."""
        self.sparse_selected_queries_total += prefill
        self.sparse_select_kernel_queries_total += kernel
        self.sparse_selected_rows_total += decode
        self.kv_compressed_max = max(
            self.kv_compressed_max,
            (held - cfg.sparse_kernel) // cfg.sparse_stride + 1)

    def record_scan(self, scanned: int, kernel: int) -> None:
        """A prefill call was launched whose mamba2 layers scan
        ``scanned`` positions, ``kernel`` of them through the kernel."""
        self.ssd_scanned_positions_total += scanned
        self.ssd_scan_kernel_positions_total += kernel

    def record_eva(self, pages: Optional[int] = None,
                   closed: int = 0) -> None:
        """A call was launched that closes ``closed`` windows of its eva
        layers' sequences; a decode call's rows attend ``pages`` summary
        pages (None: a chunk, which moves no gauge)."""
        self.eva_windows_closed_total += closed
        if pages is not None:
            self.eva_summary_pages_in_use = pages
            self.eva_summary_pages_max = max(self.eva_summary_pages_max,
                                             pages)

    def record_latent_decode(self, lengths, key_block: int,
                             layers: int) -> None:
        """A decode call was launched whose rows hold ``lengths``
        positions (an array: every row of the call, a padded row at 1)
        in the latent pool of ``layers`` mla layers, attended a
        ``key_block`` of positions at a time."""
        blocks = -(-lengths // key_block)
        self.latent_decode_key_blocks_total += layers * int(blocks.sum())
        self.latent_decode_key_blocks_longest_total += (
            layers * len(blocks) * int(blocks.max()))

    def record_paged_decode(self, lengths, block_size: int,
                            table_width: int, layers: int) -> None:
        """A decode call was launched whose rows hold ``lengths``
        positions (an array: every row of the call, a padded row at 1)
        in pages of ``block_size`` behind tables ``table_width`` wide,
        in each of ``layers`` full layers."""
        self.paged_decode_pages_total += layers * int(
            (-(-lengths // block_size)).sum())
        self.paged_decode_pages_table_total += (
            layers * len(lengths) * table_width)

    def record_window_decode(self, lengths, window: int, ring: int,
                             page: int, n_slots: int, layers: int) -> None:
        """A decode call was launched whose rows hold ``lengths``
        positions (an array: every row of the call, a padded row at 1)
        of which each sees its newest ``window``, in rings of ``ring``
        places read as pages of ``page`` (``ops/paged_decode.py::
        ring_decode``: the first page a row reads is the one that holds
        its window's first key), ``n_slots`` rings in each of ``layers``
        window layers."""
        at, seen = ring_reach(lengths - 1, window, ring)
        self.window_decode_pages_total += layers * int(
            (-(-(at % page + seen) // page)).sum())
        self.window_decode_pages_ring_total += (
            layers * n_slots * (ring // page))

    def record_idle(self) -> None:
        """The engine ran out of work: the wait for the next request is
        nobody's host gap, and the time the device goes unfed is the
        traffic's (``serve:no_work``)."""
        self._device_idle_since = None
        if self._unfed is not None:
            self._unfed.no_work = True

    def record_queue_depth(self, depth: int) -> None:
        self.queue_depth = depth
        self.max_queue_depth = max(self.max_queue_depth, depth)

    def record_admitted(self, submitted_at: float, now: float,
                        trace: int = 0) -> None:
        """One request left the queue for a batch slot: its wait is the
        ``serve:queue`` span (chrome span only, after the fact)."""
        self._span("serve:queue", submitted_at, now - submitted_at,
                   {"trace": trace} if trace else {})

    def record_request(self, submitted_at: float, finished_at: float,
                       **args) -> None:
        """One finished request, submission to last token, as the
        ``serve:request`` span (chrome span only, after the fact)."""
        self._span("serve:request", submitted_at,
                   finished_at - submitted_at, args)

    def record_prefill(self) -> None:
        """Count one prefill chunk. Its ``serve:prefill`` span is
        written by the :meth:`phase` the engine ran the chunk under."""
        self.prefill_steps += 1

    def record_prefix_lookup(self, hit_tokens: int,
                             suffix_tokens: int) -> None:
        """One admission's cache outcome: ``hit_tokens`` prompt tokens
        mapped from the prefix cache, ``suffix_tokens`` left to
        prefill. Their running ratio is the hit rate."""
        self.prefix_hit_tokens += hit_tokens
        self.prefix_prefill_tokens += suffix_tokens

    def record_prefix_extend(self, tokens: int) -> None:
        """Tokens converted from pending-prefill to cache hits by the
        engine's second cache walk at prefill time (same-step burst
        siblings publish between admission and prefill)."""
        self.prefix_hit_tokens += tokens
        self.prefix_prefill_tokens -= tokens

    def record_decode_ahead(self) -> None:
        """A decode call was launched while its predecessor was still
        in flight (its span says ``ahead``)."""
        self.decode_ahead_total += 1

    def record_decode_drain(self, cause: str) -> None:
        """A decode call in flight was read with no successor launched
        behind it, for ``cause`` (one of :data:`DRAIN_CAUSES`): that is
        why the device is unfed since."""
        self.decode_drains[cause] += 1
        if self._unfed is not None:
            self._unfed.why = cause

    def record_decode(self, dur_s: float, n_active: int,
                      max_batch: int) -> None:
        """Count one decode step of ``dur_s`` whose tokens, one for
        each of ``n_active`` sequences, the host now holds. The
        ``serve:decode`` span itself is written by :meth:`finish`."""
        self.decode_steps += 1
        self.tokens_generated += n_active
        self._occupancy_sum += n_active / max_batch
        if len(self.per_token_s) < MAX_SAMPLES:
            # Every active sequence advanced one token this step, so
            # the step wall time IS the per-token latency sample.
            self.per_token_s.append(dur_s)

    def record_spec_round(self, draft_dur_s: float,
                          verify_dur_s: float, n_active: int,
                          max_batch: int, *, proposed: int,
                          accepted: int, emitted: int) -> None:
        """One speculative iteration: the k batched draft decode steps
        plus the single chunked verify step (one :meth:`phase` span
        each, written by the round), with the round's
        proposal/acceptance tallies. Feeds the same
        throughput/occupancy series a plain decode step feeds so
        tokens/sec and batch_occupancy compare across speculative and
        plain engines; the per-token latency sample is the round wall
        time over tokens-per-sequence (a round delivers several tokens
        at once — the inter-token interval a client sees is the round
        amortized over them)."""
        self.spec_rounds += 1
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        self.tokens_generated += emitted
        self._occupancy_sum += n_active / max_batch
        dur = draft_dur_s + verify_dur_s
        if emitted and len(self.per_token_s) < MAX_SAMPLES:
            self.per_token_s.append(dur * n_active / emitted)
        if len(self.spec_draft_s) < MAX_SAMPLES:
            self.spec_draft_s.append(draft_dur_s)
        if len(self.spec_verify_s) < MAX_SAMPLES:
            self.spec_verify_s.append(verify_dur_s)

    def record_first_token(self, latency_s: float) -> None:
        # The first token comes out of prefill, not a decode step —
        # count it here so tokens/sec covers all generated tokens.
        self.tokens_generated += 1
        if len(self.first_token_s) < MAX_SAMPLES:
            self.first_token_s.append(latency_s)

    def record_submitted(self) -> None:
        self.requests_submitted += 1

    def record_withdrawn(self) -> None:
        """A queued request reclaimed by ``ServeEngine.withdraw``: it
        leaves without a result and will be re-counted wherever the
        router re-submits it, so it must not stay in this replica's
        submitted tally (fleet sums would report phantom in-flight
        requests forever)."""
        self.requests_submitted -= 1

    def record_finished(self) -> None:
        self.requests_finished += 1

    def record_expired(self) -> None:
        self.requests_expired += 1

    def record_rejected(self) -> None:
        self.requests_rejected += 1

    def record_handoff_out(self) -> None:
        """A completed prefill left this replica for a decode pool."""
        self.handoffs_out += 1

    def record_handoff_in(self) -> None:
        """A prefilled sequence arrived to decode on this replica."""
        self.handoffs_in += 1

    # -- export ------------------------------------------------------

    def snapshot(self) -> Dict[str, float]:
        elapsed = max(self._clock() - self.started_at, 1e-9)

        def ms(x):
            return None if x is None else round(x * 1e3, 3)

        # A speculative round occupies batch slots exactly like a
        # decode step — both feed the occupancy numerator, so both
        # count in the denominator.
        occ_steps = self.decode_steps + self.spec_rounds
        occ = self._occupancy_sum / occ_steps if occ_steps else 0.0
        looked_up = self.prefix_hit_tokens + self.prefix_prefill_tokens
        out = {
            "elapsed_s": round(elapsed, 3),
            "tokens_generated": self.tokens_generated,
            "tokens_per_sec": round(self.tokens_generated / elapsed, 2),
            "requests_submitted": self.requests_submitted,
            "requests_finished": self.requests_finished,
            "requests_expired": self.requests_expired,
            "requests_rejected": self.requests_rejected,
            "handoffs_in": self.handoffs_in,
            "handoffs_out": self.handoffs_out,
            "prefill_steps": self.prefill_steps,
            "decode_steps": self.decode_steps,
            # of those, launched with their predecessor still in
            # flight; and calls in flight read before a successor
            # could be launched, in all and by cause
            "decode_ahead_total": self.decode_ahead_total,
            "decode_drains_total": sum(self.decode_drains.values()),
            **{f"decode_drains_{cause}_total": n
               for cause, n in self.decode_drains.items()},
            # seconds the device had nothing enqueued while the engine
            # had work (the `serve:unfed` spans' sum), by why no
            # successor was in flight (UNFED_WHYS: a key a cause), and
            # while the engine had none (`serve:no_work`)
            "device_unfed_s_total": round(self.device_unfed_s, 6),
            "device_unfed_s_by_why": {
                why: round(s, 6)
                for why, s in self.device_unfed_s_by_why.items()},
            "device_no_work_s_total": round(self.device_no_work_s, 6),
            # key blocks of the latent pool the decode calls read, a
            # row to its own length, and what reading every row to the
            # call's longest would have taken (zeros without mla layers)
            "latent_decode_key_blocks_total":
                self.latent_decode_key_blocks_total,
            "latent_decode_key_blocks_longest_total":
                self.latent_decode_key_blocks_longest_total,
            # pages of the K pool the decode calls' full layers read, a
            # row to its own length, and what the rows' whole tables
            # hold (zeros without full layers of several kinds)
            "paged_decode_pages_total": self.paged_decode_pages_total,
            "paged_decode_pages_table_total":
                self.paged_decode_pages_table_total,
            # pages of the K rings the decode calls' window layers
            # read, a row no further back than its window, and what
            # the slots' whole rings hold (zeros without window layers)
            "window_decode_pages_total": self.window_decode_pages_total,
            "window_decode_pages_ring_total":
                self.window_decode_pages_ring_total,
            # of the whole mixture's grouped products this process
            # traced, the share that took ops/grouped_matmul.py's kernel
            # (models/moe.py counts it as it traces; 0.0 without any)
            "moe_grouped_kernel_products_share": moe_metrics().get(
                "moe_grouped_kernel_products_share", 0.0),
            # a chip's share of the experts, a layer, on the tokens of
            # the last decode.moe_share_report (a set-up program; zeros
            # before one): held pairs its dispatch ran and did not run
            # (the second is 0 unless the sort or a bound loses a pair),
            # and the held experts with at least one pair
            **{key: moe.get(key, 0.0) for moe in [moe_metrics()]
               for key in ("moe_held_pairs_run", "moe_held_pairs_not_run",
                           "moe_held_experts_touched_mean")},
            "queue_depth": self.queue_depth,
            "max_queue_depth": self.max_queue_depth,
            # device calls and host gaps many times their kind's
            # median, each also a `serve:stall` span and a WARNING
            "stalls_total": self.stalls_total,
            "batch_occupancy": round(occ, 4),
            "prefix_cache_hit_rate": (
                round(self.prefix_hit_tokens / looked_up, 4)
                if looked_up else 0.0),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_prefill_tokens": self.prefix_prefill_tokens,
            # kv_blocks_in_use (below) is the full layers' pool; these
            # are the window layers' rings (zeros without such layers)
            "kv_window_positions_max": self.kv_window_positions_max,
            "kv_window_blocks_in_use": self.kv_window_blocks_in_use,
            # a kda layer's states by slot, an mla layer's latent pages
            # (zeros without such layers)
            "state_slots_in_use": self.state_slots_in_use,
            "state_bytes": self.state_bytes,
            "kv_latent_positions_live": self.kv_latent_positions_live,
            "kv_latent_positions_max": self.kv_latent_positions_max,
            "kv_latent_ring_positions_max": self.kv_latent_ring_positions_max,
            "latent_ring_decode_pages_total":
                self.latent_ring_decode_pages_total,
            # sparse layers (zeros without such layers)
            "sparse_selected_queries_total":
                self.sparse_selected_queries_total,
            "sparse_select_kernel_queries_total":
                self.sparse_select_kernel_queries_total,
            "sparse_selected_rows_total": self.sparse_selected_rows_total,
            "kv_compressed_max": self.kv_compressed_max,
            # mamba2 layers (zeros without such layers): the share of
            # the chunks' scanned positions the kernel hvd_ssd_scan ran
            "ssd_scanned_positions_total": self.ssd_scanned_positions_total,
            "ssd_scan_kernel_share": (
                self.ssd_scan_kernel_positions_total
                / self.ssd_scanned_positions_total
                if self.ssd_scanned_positions_total else 0.0),
            # eva layers (zeros without such layers)
            "eva_summary_pages_in_use": self.eva_summary_pages_in_use,
            "eva_summary_pages_max": self.eva_summary_pages_max,
            "eva_windows_closed_total": self.eva_windows_closed_total,
            "p50_first_token_ms": ms(percentile(self.first_token_s, 50)),
            "p99_first_token_ms": ms(percentile(self.first_token_s, 99)),
            "p50_per_token_ms": ms(percentile(self.per_token_s, 50)),
            "p99_per_token_ms": ms(percentile(self.per_token_s, 99)),
            # Speculative decoding: counters are zeros on a plain
            # engine (so mixed-fleet rollups sum without key checks);
            # the accept rate is token-weighted (accepted DRAFT tokens
            # over proposed — correction tokens are the target's own
            # and count in neither).
            "spec_rounds": self.spec_rounds,
            "spec_proposed_total": self.spec_proposed,
            "spec_accepted_total": self.spec_accepted,
            "spec_accept_rate": (
                round(self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed else 0.0),
            "p50_spec_draft_ms": ms(percentile(self.spec_draft_s, 50)),
            "p99_spec_draft_ms": ms(percentile(self.spec_draft_s, 99)),
            "p50_spec_verify_ms": ms(percentile(self.spec_verify_s, 50)),
            "p99_spec_verify_ms": ms(percentile(self.spec_verify_s, 99)),
        }
        if self._allocator is not None:
            a = self._allocator
            hits0, misses0, evict0 = self._alloc_base
            out.update({
                # Block-pool health: peak-vs-current reservation cost
                # and how much "free" capacity is really warm cache.
                # Counters are deltas since reset() (same window as
                # the token counters above); the high-water gauge is
                # engine-lifetime by design (capacity planning).
                "kv_blocks_in_use": a.n_used,
                "kv_blocks_cached": a.n_cached,
                "kv_blocks_high_water": a.high_water,
                # pages more than one sequence holds at once (a prefix
                # mapped, not copied), now and at most: engine-lifetime
                # as the high water above
                "kv_pages_shared": a.n_shared,
                "kv_pages_shared_max": a.shared_high_water,
                "prefix_block_hits": a.prefix_hits - hits0,
                "prefix_block_misses": a.prefix_misses - misses0,
                "prefix_block_evictions": a.evictions - evict0,
            })
        return out

    def prometheus(self) -> str:
        """This snapshot as Prometheus text, rendered through the SAME
        exposition helper as the native registry
        (``horovod_tpu.metrics.render_gauges``) under the ``serve_``
        prefix — serving and training export one format, one endpoint
        (docs/observability.md). Every sample carries this engine's
        ``instance`` label so N replicas in one process stay
        distinguishable in one scrape and fleet-level PromQL sums
        (``sum(serve_tokens_generated)``) are correct."""
        from horovod_tpu.metrics import render_gauges
        return render_gauges("serve", self.snapshot(),
                             labels={"instance": self.instance})

    def trace_metadata(self, **extra) -> dict:
        """Timebase anchor for :meth:`export_chrome_trace` and the
        RPC ``export_trace`` verb: span ``ts`` values are microseconds
        since ``started_at`` on this engine's clock, and the
        ``(clock_now, wall_now)`` pair taken here lets
        ``bin/hvd-trace merge`` map them onto any other process's
        clock (docs/observability.md "One timebase")."""
        md = {
            "kind": "engine",
            "instance": self.instance,
            "pid": os.getpid(),
            "started_at": self.started_at,
            "clock_now": self._clock(),
            "wall_now": time.time(),
        }
        md.update(extra)
        return md

    def export_chrome_trace(self, path: str, **extra) -> None:
        """Write recorded step spans as a chrome-tracing file (the
        timeline format the rest of the framework emits), with the
        :meth:`trace_metadata` anchor so merged fleet views can
        re-anchor the spans onto one timebase."""
        with open(path, "w") as f:
            json.dump({"traceEvents": list(self._events),
                       "displayTimeUnit": "ms",
                       "metadata": self.trace_metadata(**extra)}, f)
