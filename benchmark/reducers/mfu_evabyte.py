"""The whole served step's share of the chip's peak, for a decoder of
EVA attention layers: the operations of everything the engine's calls
computed while the profiler ran (``flops_evabyte.served_work``: the
matrix products of every byte computed, the emitted rows' 8 x 320 head,
both programs' attention over the keys and summaries their queries had,
the summaries' pooling) over the traced seconds
(``traced_work.traced_s``) and the chip's peak. The bytes the same calls
had to read, and the least time of the two, are printed beside it: a
decode step of 16 rows is bound by memory, and this share by what the
bandwidth lets the matrix unit do. Nothing to read (no trace, no such
count: the parent of the PR that brought the configuration) gives
``None``."""
from benchmark import flops, flops_evabyte, harness


def reduce(meas):
    work = meas.get("traced_work")
    if not work or not meas.get("peak") or not work.get("traced_s"):
        return None
    try:
        did = flops_evabyte.served_work(meas["model"], work)
    except KeyError:
        return None
    least = flops.roofline_least_s(did, meas["peak"])
    harness.say(served_work=did, traced_s=work["traced_s"],
                least_s=least["least_s"], bound=least["bound"])
    return (100.0 * did["flops"] / work["traced_s"]
            / meas["peak"]["bf16_flops_per_s"])
