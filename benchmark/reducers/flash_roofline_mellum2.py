"""A flash attention pass's share of its roofline in a stack of window
and full layers, the two kinds told apart by scope.

``match`` (over the ``tf_op`` of the operation's metadata, see
``_scopes.py``) finds the kernels of one kind of layer and one
direction: the kind's scope (``attn_window`` | ``attn_full``) and the
kernels' stem (``hvd_flash_fwd`` | ``hvd_flash_bwd``) in one path. The
word the stem begins is a kernel's name, and the kernels of one pass
(a backward is a dkv and a dq kernel) are held together to the ONE
least time of ``flops_mellum2.<cost>`` for that ``kind`` of layer: the
passes traced are the calls over the number of distinct kernels. A
sliding layer's count is the window's pairs and not the triangle's, so
a kernel that computed the whole triangle reads about a quarter.
``category`` (over ``hlo_category``) keeps the calls apart from copies
of their results. Nothing to read (no trace; no such scope, as in the
parent of the PR that brought them) gives ``None``."""
import re

from benchmark import flops, flops_mellum2, harness
from benchmark.reducers import _scopes
from benchmark.reducers._common import lookup


def reduce(meas, match, stem, cost, kind, cost_args, category=None):
    parsed = _scopes.load(meas)
    if not parsed or not meas.get("peak"):
        return None
    kernel = re.compile(stem + r"\w*")
    calls, seconds = {}, 0.0
    for r in _scopes.matching(parsed["rows"], match, category=category):
        name = kernel.search(r["tf_op"]).group(0)
        calls[name] = calls.get(name, 0) + r["count"]
        seconds += r["self_s"]
    if not calls or seconds <= 0:
        return None
    passes = sum(calls.values()) / len(calls)
    kwargs = {k: lookup(meas, v) if isinstance(v, str) else v
              for k, v in cost_args.items()}
    least = flops.roofline_least_s(
        getattr(flops_mellum2, cost)(meas["model"], kind=kind, **kwargs),
        meas["peak"])
    harness.say(roofline=cost, kind=kind, match=match, calls=calls,
                measured_s_per_pass=seconds / passes,
                least_s_per_pass=least["least_s"], bound=least["bound"])
    return 100.0 * least["least_s"] * passes / seconds
