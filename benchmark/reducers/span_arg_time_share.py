"""The share of the window that one argument of a kind of span sums to,
in percent: the argument is in milliseconds (``readback_ms`` of
``serve:unfed``). With ``where`` (argument: value) only the spans that
say so count, and with no ``arg`` their durations; a window that has
the spans and none that says so reads 0.0."""
from benchmark.reducers._common import window_spans


def reduce(meas, span, arg=None, where=None):
    spans = window_spans(meas, span)
    if not spans:
        return None
    chosen = [s for s in spans if all(s["args"].get(k) == v
                                      for k, v in (where or {}).items())]
    seconds = sum(s["args"][arg] * 1e-3 if arg else s["dur"] for s in chosen)
    return 100.0 * seconds / (meas["t_close"] - meas["t_open"])
