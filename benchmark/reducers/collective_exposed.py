"""Collective-operation time on device 0 during which nothing else ran
there, over the traced window, in percent."""


def reduce(meas):
    trace = meas.get("trace")
    if not trace or trace["n_devices"] < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
