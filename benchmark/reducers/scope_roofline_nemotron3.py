"""A scope's share of its roofline in a decoder of one-branch layers,
the work counted off the engine's calls and the cell's routing counters:
the device time under ``match`` (less ``unless``; over the ``tf_op`` of
an operation's metadata, see ``_scopes.py``) against the least time of
``flops_nemotron3.<cost>(model, traced_work, counters)``, both over the
seconds the profiler ran. ``step_rows``: of the matching operations only
those whose result has a decode step's ``max_batch x moe_top_k`` rows
(the kernels the TPU compiler makes of ``lax.ragged_dot`` keep no scope
path, only their own name, so the program they belong to is told by
their shape, as ``grouped_matmul_roofline.py`` tells a chunk's).
Nothing to read (no trace, no such scope, no such count: the parent of
the PR that brought the configuration) gives ``None``."""
import re

from benchmark import flops, flops_nemotron3, harness
from benchmark.reducers import _scopes

_RESULT_ROWS = re.compile(r"=\s*\(?\w+\[(\d+),")


def reduce(meas, match, cost, unless=None, category=None,
           step_rows=False):
    parsed = _scopes.load(meas)
    work = meas.get("traced_work")
    if not parsed or not meas.get("peak") or not work:
        return None
    rows = _scopes.matching(parsed["rows"], match, unless, category)
    try:
        if step_rows:
            want = str(meas["engine"]["max_batch"]
                       * meas["model"]["moe_top_k"])
            rows = [r for r in rows
                    if _RESULT_ROWS.findall(r["name"])[:1] == [want]]
        needed = getattr(flops_nemotron3, cost)(meas["model"], work,
                                                meas.get("counters"))
    except KeyError:
        return None
    seconds = sum(r["self_s"] for r in rows)
    if seconds <= 0 or needed["bytes"] <= 0:
        return None
    least = flops.roofline_least_s(needed, meas["peak"])
    harness.say(roofline=cost, match=match, measured_s=seconds,
                least_s=least["least_s"], bound=least["bound"])
    return 100.0 * least["least_s"] / seconds
