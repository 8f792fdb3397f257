"""The grouped expert matmuls' share of their roofline where the layer
holds a chip's share of the experts (``flops_mellum2.grouped_matmul``).

The kernels XLA's TPU compiler makes of ``lax.ragged_dot`` keep no scope
path, only their own name as ``tf_op`` (``_scopes.py``), and their
shapes are those of ALL ``N x K`` (token, choice) pairs, of which only
the pairs on a held expert lie in a group and are work. How many those
are is the router's doing and not in the trace: ``pairs`` names the
counter the generator read it into (the share of a layer's pairs that
fell on a held expert, mean over layers and over the batches the traced
steps ran in turn, with the parameters the window left), times
``counters.pairs_per_layer``.
Every kernel, forward, recomputed or backward, is held to the least
time of that many pairs. Nothing to read (no trace, no such kernel, no
such counter: the parent of the PR that brought the configuration)
gives ``None``."""
from benchmark import flops, flops_mellum2, harness
from benchmark.reducers import _scopes


def reduce(meas, match, pairs, category=None):
    parsed = _scopes.load(meas)
    share = meas["counters"].get(pairs)
    if not parsed or not meas.get("peak") or share is None:
        return None
    rows = _scopes.matching(parsed["rows"], match, category=category)
    seconds = sum(r["self_s"] for r in rows)
    calls = sum(r["count"] for r in rows)
    if not calls or seconds <= 0:
        return None
    held = share * meas["counters"]["pairs_per_layer"]
    least = flops.roofline_least_s(
        flops_mellum2.grouped_matmul(meas["model"], held), meas["peak"])
    harness.say(roofline="grouped_matmul", match=match, calls=calls,
                kernels=len(rows), pairs_on_held_experts=held,
                measured_s_per_call=seconds / calls,
                least_s_per_call=least["least_s"], bound=least["bound"])
    return 100.0 * least["least_s"] * calls / seconds
