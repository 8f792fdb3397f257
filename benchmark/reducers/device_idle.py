"""1 - busy union over the traced window, in percent, averaged over
the chips used."""


def reduce(meas):
    trace = meas.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
