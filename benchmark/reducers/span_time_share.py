"""The share of the window that spans of one kind cover, in percent."""
from benchmark.reducers._common import window_spans


def reduce(meas, span):
    spans = window_spans(meas, span)
    if not spans:
        return None
    return 100.0 * sum(s["dur"] for s in spans) / (
        meas["t_close"] - meas["t_open"])
