"""The flash attention backward's share of its roofline. One backward is
several kernels (a dkv and a dq kernel since PR 33), all named from one
stem: ``match`` (over the ``tf_op`` of the operation's metadata, see
``_scopes.py``) finds the stem, the word it begins is the kernel's name,
and the kernels of one backward are held together to the ONE least time
of ``flops_flash_bwd.flash_bwd``: the backwards traced are the calls
over the number of distinct kernels. ``category`` (over
``hlo_category``) keeps the calls apart from copies of their results.
Nothing to read (no trace; no such kernel, as in the parent of the PR
that brought them) gives ``None``."""
import re

from benchmark import flops, flops_flash_bwd, harness
from benchmark.reducers import _scopes
from benchmark.reducers._common import lookup


def reduce(meas, match, cost_args, category=None):
    parsed = _scopes.load(meas)
    if not parsed or not meas.get("peak"):
        return None
    kernel = re.compile(match + r"\w*")
    calls, seconds = {}, 0.0
    for r in _scopes.matching(parsed["rows"], match, category=category):
        name = kernel.search(r["tf_op"]).group(0)
        calls[name] = calls.get(name, 0) + r["count"]
        seconds += r["self_s"]
    if not calls or seconds <= 0:
        return None
    backwards = sum(calls.values()) / len(calls)
    kwargs = {k: lookup(meas, v) if isinstance(v, str) else v
              for k, v in cost_args.items()}
    least = flops.roofline_least_s(
        flops_flash_bwd.flash_bwd(meas["model"], **kwargs), meas["peak"])
    harness.say(roofline="flash_bwd", match=match, calls=calls,
                measured_s_per_backward=seconds / backwards,
                least_s_per_backward=least["least_s"], bound=least["bound"])
    return 100.0 * least["least_s"] * backwards / seconds
