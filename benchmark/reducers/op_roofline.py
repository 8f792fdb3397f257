"""A kernel's share of its roofline: the least time the chip could take
for the calls traced (``benchmark/flops.py``'s ``cost`` function, from
shapes, times the number of calls) over the time they took. Says on a
diagnostic line which bound sets the least time."""
from benchmark import flops, harness
from benchmark.reducers._common import lookup, matching_ops


def reduce(meas, pattern, cost, cost_args):
    trace = meas.get("trace")
    rows = matching_ops(meas, pattern) if trace and meas.get("peak") else []
    seconds = sum(r[1] for r in rows)
    calls = sum(r[2] for r in rows)
    if not calls or seconds <= 0:
        return None
    kwargs = {k: lookup(meas, v) if isinstance(v, str) else v
              for k, v in cost_args.items()}
    least = flops.roofline_least_s(
        getattr(flops, cost)(meas["model"], **kwargs), meas["peak"])
    harness.say(roofline=cost, pattern=pattern, calls=calls,
                measured_s_per_call=seconds / calls,
                least_s_per_call=least["least_s"], bound=least["bound"])
    return 100.0 * least["least_s"] * calls / seconds
