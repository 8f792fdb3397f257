"""The chunk-sized grouped expert matmuls' share of their roofline, for
a served sparse decoder (``flops_trinity.py``).

The kernels XLA's TPU compiler makes of ``lax.ragged_dot`` keep no scope
path, only their own name as ``tf_op`` (``_scopes.py``), so the program
they belong to is told by their shape: the instruction's result is
``[pairs, width]``, and a kernel counts when it has at least
``min_pairs`` rows (a prefill chunk's; a decode step's has ``batch x
top_k``, far fewer, and gets no roofline: which experts it touches is
not known to the trace). Each kernel is held to the least time of ITS
number of pairs. Nothing to read (no trace, no such kernel: the parent
of the PR that brought the configuration) gives ``None``.
"""
import re

from benchmark import flops_trinity, harness
from benchmark.reducers import _scopes

_RESULT = re.compile(r"=\s*\(?\w+\[(\d+),(\d+)\]")


def reduce(meas, match, min_pairs, category=None):
    parsed = _scopes.load(meas)
    if not parsed or not meas.get("peak"):
        return None
    seconds = least = 0.0
    seen = {}
    for r in _scopes.matching(parsed["rows"], match, category=category):
        shape = _RESULT.search(r["name"])
        if not shape or int(shape.group(1)) < min_pairs:
            continue
        pairs = int(shape.group(1))
        seconds += r["self_s"]
        least += r["count"] * flops_trinity.grouped_matmul_min_s(
            meas["model"], pairs, meas["peak"])
        seen[pairs] = seen.get(pairs, 0) + r["count"]
    if not seen or seconds <= 0:
        return None
    harness.say(roofline="grouped_matmul_min_s", match=match,
                calls_by_pairs=seen, measured_s=seconds, least_s=least)
    return 100.0 * least / seconds
