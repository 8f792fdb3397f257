"""``scope_roofline_ling3`` for the costs of ``flops_kimi.py``: the
device time under ``match`` (less ``unless``; ``_scopes.py``) against the
least time of ``flops_kimi.<cost>(model, traced_work)``, both over the
seconds the profiler ran. Nothing to read (no trace, no such scope, no
such count: the parent of the PR that brought the configuration) gives
``None``."""
from benchmark import flops, flops_kimi, harness
from benchmark.reducers import _scopes


def reduce(meas, match, cost, unless=None):
    parsed = _scopes.load(meas)
    work = meas.get("traced_work")
    if not parsed or not meas.get("peak") or not work:
        return None
    rows = _scopes.matching(parsed["rows"], match, unless)
    seconds = sum(r["self_s"] for r in rows)
    try:
        needed = getattr(flops_kimi, cost)(meas["model"], work)
    except KeyError:
        return None
    if seconds <= 0 or needed["flops"] <= 0:
        return None
    least = flops.roofline_least_s(needed, meas["peak"])
    harness.say(roofline=cost, match=match, work=work, measured_s=seconds,
                least_s=least["least_s"], bound=least["bound"])
    return 100.0 * least["least_s"] / seconds
