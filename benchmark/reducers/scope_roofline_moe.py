"""A kernel's share of its roofline for a sparse decoder:
``scope_roofline`` with the cost function taken from ``flops_moe.py``.
The grouped matmuls that XLA's TPU compiler makes of ``lax.ragged_dot``
keep no scope path, only their own name (``ragged-dot-...``) as
``tf_op``, so ``match`` finds them by that; every one of them, forward,
recomputed or backward, is the same ``2·N·K·D·F`` operations."""
from benchmark import flops, flops_moe, harness
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
        getattr(flops_moe, cost)(meas["model"], **kwargs), meas["peak"])
    harness.say(roofline=cost, match=match, calls=calls, kernels=len(rows),
                measured_s_per_call=seconds / calls,
                least_s_per_call=least["least_s"], bound=least["bound"])
    return 100.0 * least["least_s"] * calls / seconds
