"""The whole served step's share of the chip's peak, for a decoder of
GDLA layers on an mHC residual over a chip's share of PolyNorm experts:
the operations of everything the engine's calls computed while the
profiler ran (``flops_motif3.served_work``: the matrix products of every
token computed, chunk and decode row alike, with a token's pairs on HELD
experts as the cell counted them, the emitted rows' head, the
attention's scores and sums over the keys the queries saw) over the
traced seconds (``traced_work.traced_s``) and the chip's peak. Nothing
to read (no trace, no such count) gives ``None``."""
from benchmark import flops_motif3, harness


def reduce(meas):
    work = meas.get("traced_work")
    if not work or not meas.get("peak") or not work.get("traced_s"):
        return None
    try:
        did = flops_motif3.served_work(meas["model"], work,
                                       meas.get("counters"))
    except KeyError:
        return None
    harness.say(served_work=did, traced_s=work["traced_s"])
    return (100.0 * did["flops"] / work["traced_s"]
            / meas["peak"]["bf16_flops_per_s"])
