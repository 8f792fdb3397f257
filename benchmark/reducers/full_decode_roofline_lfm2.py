"""A decode step's attention over the pages of the ``full`` layers, as a
share of its roofline: the device time of ``jit(decode)`` under
``match`` (the ``attn_full`` scope: the step's writes of its new rows,
whatever reads the pages, the softmax and the value product) against
the least time of what ``flops_lfm2.served_work`` counts off the
engine's calls for the decode rows alone, both over the seconds the
profiler ran: ``page_bytes`` (the K and V of every position a decode
row saw, once a full layer) and the same positions' operations
(``attention_flops``, ``4 H Dh`` a position and layer). The work is the
ALGORITHM's on the scope, whatever implements it: a program that
gathers every row's whole table first reads a few percent, one that
reads each row's own pages where they lie reads its copies' share of
the memory's bandwidth, and none can read over 100 %. Nothing to read
(no trace, no such scope, no such count) gives ``None``."""
from benchmark import flops, flops_lfm2, harness
from benchmark.reducers import _scopes


def reduce(meas, match, unless=None):
    parsed = _scopes.load(meas)
    work = meas.get("traced_work")
    if not parsed or not meas.get("peak") or not work:
        return None
    seconds = sum(r["self_s"] for r in
                  _scopes.matching(parsed["rows"], match, unless))
    try:
        did = flops_lfm2.served_work(
            meas["model"], {**work, "prefill_positions_seen": 0})
    except KeyError:
        return None
    needed = {"flops": did["attention_flops"], "bytes": did["page_bytes"]}
    if seconds <= 0 or needed["bytes"] <= 0:
        return None
    least = flops.roofline_least_s(needed, meas["peak"])
    harness.say(roofline="full_decode", match=match,
                decode_positions_seen=work["decode_positions_seen"],
                **needed, measured_s=seconds, least_s=least["least_s"],
                bound=least["bound"])
    return 100.0 * least["least_s"] / seconds
