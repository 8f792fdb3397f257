"""A percentile of one of the run's sample lists (``ttft_s``,
``itl_s``), times ``scale``."""
from benchmark.reducers._common import percentile


def reduce(meas, sample, q, scale=1000.0):
    values = meas["samples"].get(sample)
    return scale * percentile(values, q) if values else None
