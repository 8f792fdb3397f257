"""Peak bytes in use on the fullest chip, in GB."""


def reduce(meas):
    peak = meas["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
