"""The device calls of the serving engine joined to their runs on the
chip (``reducers/_calls.py``, PR 36): hand-worked calls and runs with a
known shift between the trace's two planes, and a small trace recorded
on the v5e (``tools/record_engine_trace.py``: a tiny engine, two
prefills and eleven decode steps, one of them stalled by a collection).

The hand-worked calls, in ms. The device plane lies 1.0 early: a run
that the host's clock would put at 10.5 is written at 9.5.

    call  L      E      a (dev)  b (dev)  C      S      E-a    C-b
    1     10.0   10.4   9.5      19.5     20.6   20.7   0.90   1.10
    2     21.0   21.3   20.45    30.45    31.65  31.7   0.85   1.20
    3     32.0   32.5   31.75    41.75    42.8   42.9   0.75   1.05

so the shifts that no call contradicts are 0.90 to 1.05, the middle is
0.975, the width 150 us, and the true 1.0 lies inside.
"""
import json
import os
import shutil

import pytest

from benchmark import harness, trace_reduce
from benchmark.reducers import (_calls, device_call_idle,
                                device_call_overhead, stall_count)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ENGINE = os.path.join(DATA, "tiny-engine.xplane.pb")
ENGINE_SPANS = os.path.join(DATA, "tiny-engine-spans.json")
MS = 1e-3


def _call(n, L, E, a, b, C, S, name="serve:decode"):
    return {"call": n, "name": name, "L": L * MS, "S": S * MS,
            "runs": [(E * MS, a * MS, b * MS, C * MS, 100 + n)]}


def _completed(call, C):
    (E, a, b, _, rid), = call["runs"]
    call["runs"] = [(E, a, b, C * MS, rid)]


@pytest.fixture
def calls():
    return [_call(1, 10.0, 10.4, 9.5, 19.5, 20.6, 20.7),
            _call(2, 21.0, 21.3, 20.45, 30.45, 31.65, 31.7),
            _call(3, 32.0, 32.5, 31.75, 41.75, 42.8, 42.9)]


def test_the_shift_lies_between_the_latest_launch_and_the_earliest_wake(
        calls):
    w = _calls.clock_window(calls)
    assert w["lo_s"] == pytest.approx(0.90 * MS)
    assert w["hi_s"] == pytest.approx(1.05 * MS)
    assert w["shift_s"] == pytest.approx(0.975 * MS)
    assert w["width_us"] == pytest.approx(150.0)
    assert w["lo_s"] <= 1.0 * MS <= w["hi_s"] and w["calls"] == 3
    assert _calls.launch_s(calls[0], w["shift_s"]) == pytest.approx(
        0.475 * MS)
    assert _calls.readback_s(calls[0], w["shift_s"]) == pytest.approx(
        0.225 * MS)


def test_launch_and_readback_together_do_not_depend_on_the_shift(calls):
    for c in calls:
        both = {round(_calls.launch_s(c, d * MS)
                      + _calls.readback_s(c, d * MS), 12)
                for d in (0.90, 0.975, 1.0, 1.05)}
        L, S = c["L"], c["S"]
        assert both == {round((S - L) - _calls.device_s(c), 12)}
    assert [_calls.device_s(c) for c in calls] == pytest.approx([10 * MS] * 3)


def test_an_empty_interval_is_no_shift(calls, capsys):
    # the host hears of call 3's end 0.25 after it on the device's
    # clock: no shift of 0.9 or more allows that
    _completed(calls[2], 42.0)
    assert _calls.clock_window(calls) is None
    said = json.loads(capsys.readouterr().out.strip())
    assert said["lo_s"] == pytest.approx(0.9 * MS)
    assert said["hi_s"] == pytest.approx(0.25 * MS)
    # empty by 30 us, inside the slack: the middle still
    _completed(calls[2], 41.75 + 0.87)
    w = _calls.clock_window(calls)
    assert w["width_us"] == pytest.approx(-30.0)
    assert w["shift_s"] == pytest.approx(0.885 * MS)
    assert _calls.clock_window([]) is None


def test_a_gap_is_split_over_the_innermost_annotations_in_proportion():
    annotations = [(0.0, 10.0, "serve:decode"),
                   (4.0, 10.0, "serve:decode:sync"),
                   (5.0, 10.0, "serve:decode:readback"),
                   (10.0, 12.0, "serve:decode_post")]
    gaps = [(2.0, 3.0), (4.5, 6.0), (8.0, 11.0), (12.0, 13.0)]
    by = _calls.split_gaps(gaps, annotations)
    assert by == pytest.approx({
        "serve:decode": 1.0,              # (2, 3)
        "serve:decode:sync": 0.5,         # (4.5, 5)
        "serve:decode:readback": 3.0,     # (5, 6) and (8, 10)
        "serve:decode_post": 1.0,         # (10, 11)
        "unattributed": 1.0})             # (12, 13)
    assert sum(by.values()) == pytest.approx(trace_reduce.total(gaps))
    # trace_reduce gives (8, 11) whole to what covers 9.5
    assert _calls.innermost(annotations) == [
        (0.0, 4.0, "serve:decode"), (4.0, 5.0, "serve:decode:sync"),
        (5.0, 10.0, "serve:decode:readback"),
        (10.0, 12.0, "serve:decode_post")]


def test_calls_find_their_runs_by_the_launch_inside_their_twin():
    parsed = {
        "twins": {7: (1.0, 2.0, "serve:decode"),
                  8: (3.0, 5.0, "serve:spec_draft")},
        "nested": {(7, "dispatch"): [(1.1, 1.2)], (7, "wait"): [(1.2, 1.9)],
                   (8, "dispatch"): [(3.1, 3.2), (4.0, 4.1)],
                   (8, "wait"): [(3.2, 3.9), (4.1, 4.8)]},
        # the runtime's own thread launches: after :dispatch has ended
        "launches": {41: 1.25, 42: 3.15, 43: 4.05,
                     44: 2.5,                # inside no call
                     46: 1.22},              # no run on device 0
        "completions": {41: 1.8, 42: 3.8},
        "runs": {41: (0.3, 0.8, "jit_decode(1)"),
                 43: (3.2, 3.7, "jit_decode(1)"),
                 42: (2.2, 2.8, "jit_decode(1)"),
                 44: (1.5, 1.6, "jit_other(2)")}}
    one, two = _calls.join(parsed)
    assert (one["call"], one["name"], one["L"], one["S"]) == (
        7, "serve:decode", 1.0, 2.0)
    assert one["runs"] == [(1.25, 0.3, 0.8, 1.8, 41)]
    assert two["call"] == 8
    # as they ran; a run without its callback is over by the last wait
    assert [(r[4], r[3]) for r in two["runs"]] == [(42, 3.8), (43, 4.8)]
    assert _calls.device_s(two) == pytest.approx(1.1)
    assert _calls.launch_s(two, 0.9) == pytest.approx(2.2 + 0.9 - 3.0)
    assert _calls.readback_s(two, 0.9) == pytest.approx(5.0 - 3.7 - 0.9)
    w = _calls.clock_window([one, two])
    assert (w["lo_s"], w["hi_s"]) == pytest.approx((0.95, 1.0))


def test_the_reducers_on_hand_worked_calls(calls):
    spans = [{"name": "serve:decode", "t0": 0.0, "dur": d * MS,
              "args": {"call": n}}
             for n, d in ((1, 10.7), (2, 10.7), (3, 10.9), (4, 50.0))]
    meas = {"spans": spans, "trace": {"window_s": 40 * MS},
            "t_open": -1.0, "t_close": 1.0,
            "_calls": {"calls": calls, "shift_s": 0.975 * MS}}
    assert device_call_overhead.reduce(
        meas, span="serve:decode", q=50) == pytest.approx(0.7)
    assert device_call_overhead.reduce(
        meas, span="serve:prefill", q=50) is None
    # launches 0.475 + 0.425 + 0.725, readbacks 0.225 + 0.275 + 0.175
    assert device_call_idle.reduce(meas, part="launch") == pytest.approx(
        100 * 1.625 / 40)
    assert device_call_idle.reduce(meas, part="readback") == pytest.approx(
        100 * 0.675 / 40)
    assert stall_count.reduce(meas) == 0.0
    spans.append({"name": "serve:stall", "t0": 0.5, "dur": 0.2,
                  "args": {"of": "serve:decode", "call": 3}})
    spans.append({"name": "serve:stall", "t0": 0.9, "dur": 0.2,  # ends after
                  "args": {"of": "serve:decode", "call": 4}})
    assert stall_count.reduce(meas) == 1.0


def test_a_program_that_does_not_number_its_calls_reports_nothing():
    spans = [{"name": "serve:decode", "t0": 0.0, "dur": 0.01,
              "args": {"n_active": 4, "dispatch_ms": 0.6}}]
    meas = {"spans": spans, "trace": None, "t_open": -1.0, "t_close": 1.0}
    assert stall_count.reduce(meas) is None
    assert device_call_overhead.reduce(meas, span="serve:decode", q=50) \
        is None
    assert device_call_idle.reduce(meas, part="launch") is None
    assert meas["_calls"] is None


# -- the trace recorded on the chip ------------------------------------


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """A traced run's measurements whose newest trace is the recorded
    file, with the engine's spans of the same run."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    os.makedirs(tmp_path / "trace" / "cell")
    shutil.copy(ENGINE, tmp_path / "trace" / "cell" / "vm.xplane.pb")
    with open(ENGINE_SPANS) as f:
        exported = json.load(f)
    t_ref = exported["metadata"]["started_at"]
    spans = [{"name": e["name"], "t0": t_ref + e["ts"] * 1e-6,
              "dur": e["dur"] * 1e-6, "args": e.get("args", {})}
             for e in exported["traceEvents"] if e.get("ph") == "X"]
    return {"trace": trace_reduce.reduce_xplane(ENGINE), "spans": spans,
            "t_open": t_ref, "t_close": t_ref + 3600.0}


def test_every_recorded_call_finds_its_run_by_its_number(recorded):
    parsed = _calls.parse(ENGINE)
    traced = sorted(parsed["twins"])     # the device spans the profiler saw
    calls = _calls.join(parsed)
    assert len(traced) == 13 and [c["call"] for c in calls] == traced
    names = [c["name"] for c in calls]
    assert names.count("serve:prefill") == 2
    assert names.count("serve:decode") == 11
    spans = {s["args"]["call"]: s for s in recorded["spans"]
             if "call" in s["args"] and s["name"] != "serve:stall"}
    for c in calls:
        assert len(c["runs"]) == 1
        E, a, b, C, rid = c["runs"][0]
        assert c["L"] <= E <= C <= c["S"]
        program = parsed["runs"][rid][2]
        assert ("prefill" if c["name"] == "serve:prefill" else "decode") \
            in program
        # the twin is the span: same name, same length within 20 us
        assert spans[c["call"]]["name"] == c["name"]
        assert (c["S"] - c["L"]) == pytest.approx(
            spans[c["call"]]["dur"], abs=2e-5)
        # and the run is shorter than the call that waited for it
        assert 0 < b - a < c["S"] - c["L"]


def test_the_recorded_planes_are_put_on_one_clock(recorded, capsys):
    joined = _calls.load(recorded)
    w = joined["window"]
    assert w["calls"] == 13 and 0 <= w["width_us"] < 1000
    for c in joined["calls"]:
        assert _calls.launch_s(c, w["shift_s"]) > 0
        assert _calls.readback_s(c, w["shift_s"]) > 0
    said = [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]
    (line,) = [s for s in said if "device_clock_window_us" in s]
    assert line["device_clock_window_us"] == pytest.approx(w["width_us"])
    # the stalled step leads the longest calls: all of it before launch
    worst = line["longest_calls_ms"][0]
    assert worst["name"] == "serve:decode" and worst["span"] > 100
    assert worst["launch"] > 0.99 * worst["span"] - 5
    idle = line["idle_s"]
    gaps = dict(line["idle_gaps_on_one_clock"])
    # the stalled step's collection names the idle time under it
    assert gaps["serve:gc"] > 0.05
    assert sum(gaps.values()) <= idle["idle_s"] + 1e-9
    assert _calls.load(recorded) is joined               # read once


def test_the_reducers_on_the_recorded_run(recorded):
    over = device_call_overhead.reduce(recorded, span="serve:decode", q=50)
    launch = device_call_idle.reduce(recorded, part="launch")
    readback = device_call_idle.reduce(recorded, part="readback")
    assert 0 < over < 5.0
    assert 0 < readback < launch < 100.0      # the stalled dispatch is launch
    assert stall_count.reduce(recorded) == 1.0
    (stall,) = [s for s in recorded["spans"] if s["name"] == "serve:stall"]
    assert stall["args"]["of"] == "serve:decode"
    assert stall["args"]["part"] == "dispatch"
    assert stall["args"]["gc_ms"] > 50 and stall["args"]["gc_gen"] == 2
