"""``stall_count`` (``stalls_in_window.*``): the engine's ``serve:stall``
spans that end inside the window, by hand and on the spans of a tiny
engine recorded on the v5e."""
import json
import os

from benchmark.reducers import stall_count

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _decode(call, dur):
    return {"name": "serve:decode", "t0": 0.0, "dur": dur,
            "args": {"call": call}}


def test_stalls_are_counted_when_they_end_inside_the_window():
    spans = [_decode(n, 0.0107) for n in (1, 2, 3, 4)]
    meas = {"spans": spans, "t_open": -1.0, "t_close": 1.0}
    assert stall_count.reduce(meas) == 0.0
    spans.append({"name": "serve:stall", "t0": 0.5, "dur": 0.2,
                  "args": {"of": "serve:decode", "call": 3}})
    spans.append({"name": "serve:stall", "t0": 0.9, "dur": 0.2,  # ends after
                  "args": {"of": "serve:decode", "call": 4}})
    assert stall_count.reduce(meas) == 1.0


def test_a_program_that_does_not_number_its_calls_reports_no_count():
    spans = [{"name": "serve:decode", "t0": 0.0, "dur": 0.01,
              "args": {"n_active": 4, "dispatch_ms": 0.6}}]
    assert stall_count.reduce(
        {"spans": spans, "t_open": -1.0, "t_close": 1.0}) is None


def test_the_stall_of_the_engine_recorded_on_the_chip():
    """The engine's own spans of a tiny run on the v5e (PR 36: two
    prefills, eleven decode steps, a collection of a large heap inside
    one step's dispatch), as ``export_chrome_trace`` wrote them."""
    with open(os.path.join(DATA, "tiny-engine-spans.json")) as f:
        exported = json.load(f)
    t_ref = exported["metadata"]["started_at"]
    spans = [{"name": e["name"], "t0": t_ref + e["ts"] * 1e-6,
              "dur": e["dur"] * 1e-6, "args": e.get("args", {})}
             for e in exported["traceEvents"] if e.get("ph") == "X"]
    meas = {"spans": spans, "t_open": t_ref, "t_close": t_ref + 3600.0}
    assert stall_count.reduce(meas) == 1.0
    (stall,) = [s for s in spans if s["name"] == "serve:stall"]
    assert stall["args"]["of"] == "serve:decode"
    assert stall["args"]["part"] == "dispatch"
    assert stall["args"]["gc_ms"] > 50 and stall["args"]["gc_gen"] == 2
