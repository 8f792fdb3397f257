"""The share of the traced window, in percent, in which the chip waited
for one ``part`` of the engine's device calls, summed over all of them:
``launch`` (from a call's first line until its program started on the
chip: the host's dispatch and the launch) or ``readback`` (from the
program's end until the call's last line: waking the host, and the copy
of the tokens). The device plane is put on the host's clock by
``_calls.clock_window``; the window is ``device_idle_pct``'s."""
from benchmark.reducers import _calls

_PARTS = {"launch": _calls.launch_s, "readback": _calls.readback_s}


def reduce(meas, part):
    joined = _calls.load(meas)
    if not joined:
        return None
    shift = joined["shift_s"]
    return 100.0 * sum(_PARTS[part](c, shift) for c in joined["calls"]
                       ) / meas["trace"]["window_s"]
