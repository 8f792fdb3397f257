"""The ``serve:stall`` spans that ended inside the window: device calls
and host gaps that took many times their kind's median
(``ServeMetrics._stall``). A program that does not number its device
calls (before PR 36) cannot write one: the metric is left out, not
reported as 0. Every stall of the run, warm-up included, is printed
with when it ended (seconds since the window opened) and its cause."""
from benchmark import harness
from benchmark.reducers._common import window_spans


def reduce(meas):
    if not any("call" in s["args"] for s in meas["spans"]):
        return None
    stalls = [s for s in meas["spans"] if s["name"] == "serve:stall"]
    if stalls:
        harness.say(stalls=[
            {"ended_at_s": s["t0"] + s["dur"] - meas["t_open"],
             "ms": 1e3 * s["dur"], **s["args"]} for s in stalls])
    return float(len(window_spans(meas, "serve:stall")))
