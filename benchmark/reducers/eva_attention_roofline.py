"""An EVA layer's attention as a share of its roofline, a decode step's
or with ``chunk`` a prefill chunk's: the device time of the program's
operations under ``match`` (the ``attn_eva`` scope: the write of the new
rows, the closing of chunks, both parts of the attention and their
merge; every Pallas call of the kind runs under it, ``hvd_paged_decode``
twice a layer in a step and ``hvd_flash_keys_fwd`` twice in a chunk)
against the least time of what ``flops_evabyte`` counts off the engine's
calls over the seconds the profiler ran. A step: the K and V of the live
rows up to each row's own position and of one summary a chunk of its
closed windows, once a layer, at the memory's bandwidth
(``decode_attention``); a chunk: the operations of its queries over the
exact keys at or before them in their window and the closed windows'
summaries, the causal half counted once (``chunk_attention``). The work
is the ALGORITHM's, counted from the traffic, whatever implements it: a
later kernel is read by the same yardstick, and none can read over 100
%. Nothing to read (no trace, no such scope, no such count) gives
``None``."""
from benchmark import flops, flops_evabyte, harness
from benchmark.reducers import _scopes


def reduce(meas, match, unless=None, chunk=False):
    parsed = _scopes.load(meas)
    work = meas.get("traced_work")
    if not parsed or not meas.get("peak") or not work:
        return None
    seconds = sum(r["self_s"] for r in
                  _scopes.matching(parsed["rows"], match, unless))
    try:
        needed = (flops_evabyte.chunk_attention if chunk
                  else flops_evabyte.decode_attention)(meas["model"], work)
    except KeyError:
        return None
    if seconds <= 0 or needed["flops"] <= 0:
        return None
    least = flops.roofline_least_s(needed, meas["peak"])
    harness.say(roofline="eva_chunk" if chunk else "eva_decode", match=match,
                **needed, measured_s=seconds, least_s=least["least_s"],
                bound=least["bound"])
    return 100.0 * least["least_s"] / seconds
