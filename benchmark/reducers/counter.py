"""A counter of the run, as counted."""


def reduce(meas, name):
    value = meas["counters"].get(name)
    return None if value is None else float(value)
