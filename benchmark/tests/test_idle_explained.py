"""``idle_explained`` and ``span_arg_time_share`` on a plane made by
hand (PR 52): an engine that launches two decode calls ahead, drains
the third for ``admit``, runs out of work after a prefill, and whose
trace stops with a call in flight: the case PR 36's readers of the idle
time fell silent on.

Times below are milliseconds on the trace's HOST plane. The device
plane's clock is 0.7 ms behind it (a program seems to start 0.7 ms
before it did) and the engine's clock 5 s ahead. ``e`` is the run's
enqueue (``DoEnqueueProgram``), ``d`` where the jitted call returned.

====  ==============  =======  =====  =====  ============  =====  ======
call  program         launch   e      d      runs          ready  finish
====  ==============  =======  =====  =====  ============  =====  ======
0     (before the trace; its completion callback alone)    0.2-2.0
1     prefill         1.0      1.5    2.0    2.0-12.0      12.1   12.4
2     decode          13.0     13.6   14.0   13.6-33.6     33.7   34.0
3     decode, ahead   20.0     20.5   21.0   33.6-53.6     53.7   54.0
4     decode, ahead   40.0     40.5   41.0   53.6-73.6     73.7   74.0
5     prefill_resume  75.6     76.0   76.6   76.2-86.2     86.3   86.6
6     prefill         95.0     95.5   96.0   95.7-(99.0: the trace stops)
====  ==============  =======  =====  =====  ============  =====  ======

Call 2 starts the moment it is enqueued, so causality finds the 0.7 ms
exactly. The device idles 12.0-13.6, 73.6-76.2 and 86.2-95.7: 13.7 ms.
The engine wrote ``serve:unfed`` 12.1-14.0 (``prefill_read``: readback
to 12.4, ``serve:prefill_post`` 12.5-12.8, launch 13.0), ``serve:unfed``
73.7-76.6 (``admit``: readback to 74.0, ``serve:decode_post`` to 74.5,
the caller to 75.0, ``serve:schedule`` to 75.2, ``serve:prefill_prep``
75.3-75.5, launch 75.6) and ``serve:no_work`` 86.3-96.0. They cover
12.1-13.6, 73.7-76.2 and 86.3-95.7: 13.4 ms; 0.1 ms before each is
nobody's, and the dispatches' last 0.4 ms each lie over their programs.
"""
import os

import pytest

from benchmark import harness
from benchmark.reducers import (hvd_xplane_pb2, idle_explained,
                                span_arg_time_share, span_time_share)

MS = 1e-3
SKEW_MS = 0.7           # host plane less device plane
ENGINE_AHEAD_S = 5.0    # engine clock less host plane

# call: (twin's name, launch, enqueue, dispatch end, run start, run end,
# finish), host-plane ms; None where the trace does not hold it
CALLS = {
    1: ("serve:prefill", 1.0, 1.5, 2.0, 2.0, 12.0, 12.4),
    2: ("serve:decode", 13.0, 13.6, 14.0, 13.6, 33.6, 34.0),
    3: ("serve:decode", 20.0, 20.5, 21.0, 33.6, 53.6, 54.0),
    4: ("serve:decode", 40.0, 40.5, 41.0, 53.6, 73.6, 74.0),
    5: ("serve:prefill", 75.6, 76.0, 76.6, 76.2, 86.2, 86.6),
    6: ("serve:prefill", 95.0, 95.5, 96.0, 95.7, 99.0, None),
}
MODULES = {1: "jit_prefill(123)", 2: "jit_decode(45)", 3: "jit_decode(45)",
           4: "jit_decode(45)", 5: "jit_prefill_resume(6)",
           6: "jit_prefill(123)"}


class Plane:
    """One plane of an ``XSpace`` under construction."""

    def __init__(self, space, name):
        self.plane = space.planes.add()
        self.plane.name = name
        self.events, self.stats, self.lines = {}, {}, {}

    def _id(self, table, metadata, name):
        if name not in table:
            table[name] = len(table) + 1
            metadata[table[name]].id = table[name]
            metadata[table[name]].name = name
        return table[name]

    def add(self, line, name, start_ms, end_ms, **stats):
        if line not in self.lines:
            ln = self.plane.lines.add()
            ln.name, ln.timestamp_ns = line, 1_000_000 * (len(self.lines) + 1)
            self.lines[line] = ln
        ln = self.lines[line]
        e = ln.events.add()
        e.metadata_id = self._id(self.events, self.plane.event_metadata, name)
        e.offset_ps = round(start_ms * 1e9) - ln.timestamp_ns * 1000
        e.duration_ps = round((end_ms - start_ms) * 1e9)
        for key, value in stats.items():
            s = e.stats.add()
            s.metadata_id = self._id(self.stats, self.plane.stat_metadata,
                                     key)
            s.int64_value = value


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    space = hvd_xplane_pb2.XSpace()
    dev = Plane(space, "/device:TPU:0")
    host = Plane(space, "/host:CPU")
    # a run enqueued before the trace began: the host plane holds its
    # completion alone, under the same run_id
    dev.add("XLA Modules", "jit_decode(45)", 0.2 - SKEW_MS, 2.0 - SKEW_MS,
            run_id=100)
    dev.add("XLA Ops", "%fusion.0 = f32[8]", 0.2 - SKEW_MS, 2.0 - SKEW_MS)
    host.add("futex", "CompleteCallbacks", 2.1, 2.2, run_id=100)
    for call, (name, launch, enq, ret, a, b, fin) in CALLS.items():
        dev.add("XLA Modules", MODULES[call], a - SKEW_MS, b - SKEW_MS,
                run_id=100 + call)
        # two operations a run, back to back, one nested in the first
        mid = (a + b) / 2
        dev.add("XLA Ops", "%while.1 = f32[8]", a - SKEW_MS, mid - SKEW_MS)
        dev.add("XLA Ops", "%fusion.1 = f32[8]", a - SKEW_MS,
                (a + mid) / 2 - SKEW_MS)
        dev.add("XLA Ops", "%fusion.2 = f32[8]", mid - SKEW_MS, b - SKEW_MS)
        # the runtime enqueues on whatever thread it likes
        host.add("main" if call % 2 else "pjrt-tpu-tasks",
                 "DoEnqueueProgram", enq, enq + 0.03, run_id=100 + call)
        host.add("python3", name + ":dispatch", launch, ret, call=call)
        if fin is not None:
            host.add("futex", "CompleteCallbacks", b + 0.05, b + 0.08,
                     run_id=100 + call)
            host.add("python3", name, launch, fin, call=call)
            host.add("python3", name + ":wait", fin - 1.0, fin - 0.3,
                     call=call)
            host.add("python3", name + ":readback", fin - 0.3, fin,
                     call=call)
    host.add("python3", "serve:schedule", 75.0, 75.2)
    path = tmp_path_factory.mktemp("plane") / "vm.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return str(path)


def _span(name, t0_ms, end_ms, **args):
    return {"name": name, "t0": ENGINE_AHEAD_S + t0_ms * MS,
            "dur": (end_ms - t0_ms) * MS, "args": args}


@pytest.fixture(scope="module")
def spans():
    """What the engine wrote, on its clock: a span of a call launched
    ahead starts at the read before it, not at its twin's start, and
    call 6 was finished after the trace had stopped."""
    return [
        _span("serve:prefill", 1.0, 12.4, call=1),
        _span("serve:prefill_post", 12.5, 12.8),
        _span("serve:unfed", 12.1, 14.0, readback_ms=0.3, host_ms=0.6,
              dispatch_ms=1.0, phases={"serve:prefill_post": 0.3},
              unnamed_ms=0.3, why="prefill_read", after=1, before=2,
              across_steps=False),
        _span("serve:host_gap", 12.4, 13.0, across_steps=False),
        _span("serve:decode", 13.0, 34.0, call=2, ahead=False),
        _span("serve:decode_post", 34.0, 34.5),     # call 3 is in flight
        _span("serve:decode", 34.0, 54.0, call=3, ahead=True),
        _span("serve:decode", 54.0, 74.0, call=4, ahead=True),
        _span("serve:decode_post", 74.0, 74.5),
        _span("serve:schedule", 75.0, 75.2),
        _span("serve:prefill_prep", 75.3, 75.5),
        _span("serve:unfed", 73.7, 76.6, readback_ms=0.3, host_ms=1.6,
              dispatch_ms=1.0, phases={
                  "serve:decode_post": 0.5, "outside_step": 0.5,
                  "serve:schedule": 0.2, "serve:prefill_prep": 0.2},
              unnamed_ms=0.2, why="admit", after=4, before=5,
              across_steps=True),
        _span("serve:prefill", 75.6, 86.6, call=5),
        _span("serve:no_work", 86.3, 96.0, after=5, before=6),
        _span("serve:prefill", 95.0, 106.0, call=6),
    ]


def test_the_planes_are_put_on_the_engine_s_clock(xplane, spans):
    found = idle_explained.explain(xplane, spans)
    # causality over the six enqueues the trace holds; run 100's
    # completion callback is no launch (taken for one, it would shift
    # the plane by 2.1 - (0.2 - 0.7) = 2.6 ms)
    assert found["launches"] == 6
    assert found["device_plane_shifted_by_ms"] == pytest.approx(SKEW_MS)
    # five twins end inside the trace; call 6 is in flight at its end
    assert found["twins_paired"] == 5
    assert found["trace_less_engine_clock_s"] == pytest.approx(
        -ENGINE_AHEAD_S)
    assert found["twins_range_us"] == pytest.approx(0.0, abs=1e-3)
    assert found["window_s"] == pytest.approx(98.8 * MS)


def test_the_idle_seconds_by_why_and_part(xplane, spans):
    found = idle_explained.explain(xplane, spans)
    assert found["idle_s"] == pytest.approx(13.7 * MS)
    assert found["explained_s"] == pytest.approx(13.4 * MS)

    def ms(parts):
        return {k: pytest.approx(v * MS, abs=1e-9) for k, v in parts.items()}

    assert found["idle_by_why"] == {
        "prefill_read": ms({"readback": 0.3, "serve:prefill_post": 0.3,
                            "unnamed": 0.3, "dispatch": 0.6}),
        "admit": ms({"readback": 0.3, "serve:decode_post": 0.5,
                     "outside_step": 0.5, "serve:schedule": 0.2,
                     "serve:prefill_prep": 0.2, "unnamed": 0.2,
                     "dispatch": 0.6})}
    assert found["no_work"] == pytest.approx(9.4 * MS)
    left = found["unexplained"]
    assert left["s"] == pytest.approx(0.3 * MS)
    # each is the wake-up after a program: the host had its result
    # 0.1 ms after the program was over
    assert left["after_a_program_s"] == pytest.approx(0.3 * MS)
    assert left["before_a_program_s"] == left["whole_gaps_s"] == \
        left["inside_s"] == 0.0
    assert sorted((round(g["at_s"] / MS, 3), g["next"])
                  for g in left["longest"]) == [
        (11.8, "jit_decode"), (73.4, "jit_prefill_resume"),
        (86.0, "jit_prefill")]
    assert all(g["ms"] == pytest.approx(0.1) for g in left["longest"])


def test_the_unfed_time_that_lies_over_a_program(xplane, spans):
    found = idle_explained.explain(xplane, spans)
    # 12.1-14.0 and 73.7-76.6; no_work is not the engine's
    assert found["unfed_in_trace_s"] == pytest.approx(4.8 * MS)
    # the dispatches returned 0.4 ms after their programs had started
    assert found["unfed_while_busy_s"] == {
        "dispatch": pytest.approx(0.8 * MS)}


def test_the_entry_reads_the_newest_trace_and_says_what_it_found(
        xplane, spans, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    os.makedirs(tmp_path / "trace" / "cell")
    os.link(xplane, tmp_path / "trace" / "cell" / "vm.xplane.pb")
    meas = {"trace": {"busy_s": 0.0851, "window_s": 0.0988}, "spans": spans}
    assert idle_explained.reduce(meas) == pytest.approx(100 * 13.4 / 13.7)
    assert '"idle_explained"' in capsys.readouterr().out
    # no trace, a program that keeps no account of its unfed time (the
    # parent of PR 52) or one that wrote no call: silent, not zero
    assert idle_explained.reduce({"trace": None, "spans": spans}) is None
    assert idle_explained.reduce({"trace": meas["trace"], "spans": [
        s for s in spans if s["name"] not in (
            "serve:unfed", "serve:no_work")]}) is None
    assert idle_explained.explain(xplane, [
        s for s in spans if "call" not in s["args"]]) is None


def test_the_shares_of_the_window_by_argument(spans):
    meas = {"spans": spans, "t_open": ENGINE_AHEAD_S,
            "t_close": ENGINE_AHEAD_S + 0.1}
    share = span_arg_time_share.reduce
    unfed = span_time_share.reduce(meas, span="serve:unfed")
    assert unfed == pytest.approx(4.8)                  # 4.8 ms of 100
    parts = [share(meas, span="serve:unfed", arg=a + "_ms")
             for a in ("readback", "host", "dispatch")]
    assert parts == [pytest.approx(0.6), pytest.approx(2.2),
                     pytest.approx(2.0)]
    assert sum(parts) == pytest.approx(unfed)
    assert share(meas, span="serve:unfed", arg="unnamed_ms") == \
        pytest.approx(0.5)
    assert share(meas, span="serve:unfed", where={"why": "admit"}) == \
        pytest.approx(2.9)
    assert share(meas, span="serve:unfed",
                 where={"why": "prefill_read"}) == pytest.approx(1.9)
    # spans, and none of that cause: 0.0; no such span at all: silent
    assert share(meas, span="serve:unfed", where={"why": "bucket"}) == 0.0
    assert share(meas, span="serve:nothing", arg="host_ms") is None
    assert span_time_share.reduce(meas, span="serve:no_work") == \
        pytest.approx(9.7)
