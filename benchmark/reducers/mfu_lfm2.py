"""The whole served step's share of the chip's peak, for a decoder of
short-convolution and attention layers with its mixture held whole: the
operations of everything the engine's calls computed while the profiler
ran (``flops_lfm2.served_work``: the matrix products of every token
computed with ``moe_top_k`` experts a token, the emitted rows' head, both
programs' attention, the convolutions) over the traced seconds
(``traced_work.traced_s``) and the chip's peak. The bytes the same calls
had to read, and the least time of the two, are printed beside it: where
every call reads every expert the step is bound by memory, and this
share by what the bandwidth lets the matrix unit do. Nothing to read (no
trace, no such count) gives ``None``."""
from benchmark import flops, flops_lfm2, harness


def reduce(meas):
    work = meas.get("traced_work")
    if not work or not meas.get("peak") or not work.get("traced_s"):
        return None
    try:
        did = flops_lfm2.served_work(
            meas["model"], work,
            meas["counters"].get("moe_held_experts_touched_mean"))
    except KeyError:
        return None
    least = flops.roofline_least_s(did, meas["peak"])
    harness.say(served_work=did, traced_s=work["traced_s"],
                least_s=least["least_s"], bound=least["bound"])
    return (100.0 * did["flops"] / work["traced_s"]
            / meas["peak"]["bf16_flops_per_s"])
