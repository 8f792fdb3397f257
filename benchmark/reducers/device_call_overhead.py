"""What a kind of device call costs beyond its program's time on the
chip, in ms: a percentile, over the calls of the traced seconds, of the
engine span's duration less the duration of the run it was joined to by
its ``call`` (``_calls.py``). Launch and readback together, whatever the
clocks of the trace's two planes: the difference of two durations."""
from benchmark.reducers import _calls
from benchmark.reducers._common import percentile


def reduce(meas, span, q):
    joined = _calls.load(meas)
    if not joined:
        return None
    spans = {s["args"].get("call"): s for s in meas["spans"]
             if s["name"] == span}
    over = [spans[c["call"]]["dur"] - _calls.device_s(c)
            for c in joined["calls"]
            if c["name"] == span and c["call"] in spans]
    return 1e3 * percentile(over, q) if over else None
