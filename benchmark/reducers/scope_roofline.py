"""A named kernel's share of its roofline: the device time of its calls
against the least time of ``flops.<cost>`` for as many calls, the calls
found by the kernel's name in their scope path (``match`` over the
``tf_op`` of the operation's metadata, see ``_scopes.py``), not by the
shape of the call. ``category`` (over ``hlo_category``) keeps the
calls themselves apart from the small copies XLA makes of a kernel's
results, which inherit its path."""
from benchmark import flops, harness
from benchmark.reducers import _scopes
from benchmark.reducers._common import lookup


def reduce(meas, match, cost, cost_args, category=None):
    parsed = _scopes.load(meas)
    rows = _scopes.matching(parsed["rows"], match, category=category) \
        if parsed and meas.get("peak") else []
    seconds = sum(r["self_s"] for r in rows)
    calls = sum(r["count"] for r in rows)
    if not calls or seconds <= 0:
        return None
    kwargs = {k: lookup(meas, v) if isinstance(v, str) else v
              for k, v in cost_args.items()}
    least = flops.roofline_least_s(
        getattr(flops, cost)(meas["model"], **kwargs), meas["peak"])
    harness.say(roofline=cost, match=match, calls=calls,
                measured_s_per_call=seconds / calls,
                least_s_per_call=least["least_s"], bound=least["bound"])
    return 100.0 * least["least_s"] * calls / seconds
