"""The grouped expert products' share of their roofline, where the
mixture is held whole (``flops_lfm2.moe_experts_product``): a decode
step's, or with ``chunk`` a prefill chunk's.

The kernels XLA's TPU compiler makes of ``lax.ragged_dot`` keep no scope
path, only their own name as ``tf_op`` (``_scopes.py``), so the program
they belong to is told by their shape, as
``grouped_matmul_roofline.py`` tells a chunk's: the instruction's result
is ``[pairs, width]``, and a kernel is a decode step's when it has
exactly ``max_batch x moe_top_k`` rows (the one batch bucket) and a
chunk's when it has more (the smallest chunk bucket dispatches twice as
many). A step's kernel is held to the least time of that many pairs over
the experts a decode-sized batch touched at set-up
(``counters.moe_held_experts_touched_mean``; all of them where the
counter is missing); a chunk's to the least time of ITS pairs, the
bucket's padding with them since the kernel is handed it, over all the
experts (the smallest bucket brings 32 pairs an expert). Nothing to read
(no trace, no such kernel: the parent of the PR that brought the
configuration) gives ``None``."""
import re

from benchmark import flops, flops_lfm2, harness
from benchmark.reducers import _scopes

_RESULT = re.compile(r"=\s*\(?\w+\[(\d+),(\d+)\]")


def reduce(meas, match, category=None, chunk=False):
    parsed = _scopes.load(meas)
    if not parsed or not meas.get("peak"):
        return None
    model = meas["model"]
    try:
        step = meas["engine"]["max_batch"] * model["moe_top_k"]
        touched = model["n_experts"] if chunk else meas["counters"].get(
            "moe_held_experts_touched_mean", model["n_experts"])

        def least_s(pairs):
            return flops.roofline_least_s(
                flops_lfm2.moe_experts_product(model, pairs, touched),
                meas["peak"])

        least_s(step)
    except KeyError:
        return None
    seconds, least, calls, by_pairs = 0.0, 0.0, 0, {}
    for r in _scopes.matching(parsed["rows"], match, category=category):
        shape = _RESULT.search(r["name"])
        pairs = int(shape.group(1)) if shape else 0
        if pairs > step if chunk else pairs == step:
            seconds += r["self_s"]
            calls += r["count"]
            least += least_s(pairs)["least_s"] * r["count"]
            n, s = by_pairs.get(pairs, (0, 0.0))
            by_pairs[pairs] = (n + r["count"], s + r["self_s"])
    if not calls or seconds <= 0:
        return None
    harness.say(roofline="moe_experts_chunk" if chunk else "moe_experts_step",
                match=match, calls=calls, experts_touched=touched,
                by_pairs={str(p): {"calls": n, "measured_s_per_call": s / n,
                                   **{k: least_s(p)[k]
                                      for k in ("least_s", "bound")}}
                          for p, (n, s) in sorted(by_pairs.items())})
    return 100.0 * least / seconds
