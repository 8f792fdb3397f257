"""A sparse or linear-attention scope's share of its roofline, the work counted off the
engine's calls: the device time under ``match`` (less ``unless``; over
the ``tf_op`` of an operation's metadata, see ``_scopes.py``) against
the least time of ``flops_sala.<cost>(model, traced_work)``, both over
the seconds the profiler ran. Nothing to read (no trace, no such scope,
no such count: the parent of the PR that brought the configuration)
gives ``None``."""
from benchmark import flops, flops_sala, harness
from benchmark.reducers import _scopes


def reduce(meas, match, cost, unless=None):
    parsed = _scopes.load(meas)
    work = meas.get("traced_work")
    if not parsed or not meas.get("peak") or not work:
        return None
    rows = _scopes.matching(parsed["rows"], match, unless)
    seconds = sum(r["self_s"] for r in rows)
    try:
        needed = getattr(flops_sala, cost)(meas["model"], work)
    except KeyError:
        return None
    if seconds <= 0 or needed["bytes"] <= 0:
        return None
    least = flops.roofline_least_s(needed, meas["peak"])
    harness.say(roofline=cost, match=match, work=work, measured_s=seconds,
                least_s=least["least_s"], bound=least["bound"])
    return 100.0 * least["least_s"] / seconds
