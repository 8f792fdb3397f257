"""Self time of the device operations whose name matches ``pattern``,
as a share of all operation time on device 0, in percent."""
from benchmark.reducers._common import matching_ops


def reduce(meas, pattern):
    trace = meas.get("trace")
    if not trace or not trace["ops"]:
        return None
    busy = sum(row[1] for row in trace["ops"])
    return 100.0 * sum(row[1] for row in matching_ops(meas, pattern)) / busy
