"""A percentile of the durations of one kind of span in the window."""
from benchmark.reducers._common import percentile, window_spans


def reduce(meas, span, q, scale=1000.0):
    durs = [s["dur"] for s in window_spans(meas, span)]
    return scale * percentile(durs, q) if durs else None
