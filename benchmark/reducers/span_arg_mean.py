"""The mean of one argument of a kind of span over the window, over a
value of the measurement (``over``, dotted), times ``scale``."""
from benchmark.reducers._common import lookup, window_spans


def reduce(meas, span, arg, over=None, scale=1.0):
    values = [s["args"][arg] for s in window_spans(meas, span)
              if arg in s["args"]]
    if not values:
        return None
    mean = sum(values) / len(values)
    return scale * mean / (lookup(meas, over) if over else 1.0)
