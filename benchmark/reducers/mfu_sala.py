"""The whole served step's share of the chip's peak, for a decoder of
sparse-attention and linear-attention layers: the operations of
everything the engine's calls computed while the profiler ran
(``flops_sala.served_work``: the matrix products of every token
computed, chunk and decode row alike, the emitted rows' head, the
scoring and the attention over the chosen keys, the recurrence) over
the traced seconds
(``traced_work.traced_s``: from the profiler's start to the end of the
last call inside) and the chip's peak. Nothing to read (no trace, no
such count) gives ``None``."""
from benchmark import flops_sala, harness


def reduce(meas):
    work = meas.get("traced_work")
    if not work or not meas.get("peak") or not work.get("traced_s"):
        return None
    try:
        did = flops_sala.served_work(meas["model"], work)
    except KeyError:
        return None
    harness.say(served_work=did, traced_s=work["traced_s"])
    return (100.0 * did["flops"] / work["traced_s"]
            / meas["peak"]["bf16_flops_per_s"])
