"""A backlog that never empties of documents asked several times each,
through a model whose every layer keeps latent pages behind the block
tables, with the engine's prefix cache ON (ISSUE 43):
``serve_backlog_hybrid.py``'s cell (the configuration built first of
all, one seeded model under the names ``--seed`` gives the vocabulary, a
warm-up of every program the window can meet, a check of served tokens
in a full batch against the plain reference, ``serve_tok_s`` cut at the
same whole block of one fixed list in every run less the seconds the
whole machine stood still, as ``machine_pauses.py`` saw them) for
traffic whose prompts share prefixes.

What differs from ``serve_backlog_hybrid``:

* **The traffic.** A DOCUMENT (``document_len``, a whole number of the
  engine's blocks) is asked ``asks_per_document`` times: a request is
  the document followed by a question (``question_len``) and gets
  ``output_len`` tokens. The fixed multiset of ``n_lengths`` requests
  lies in balanced blocks of ``block_requests`` (``block_requests /
  asks_per_document`` documents each, their questions and outputs
  dealt by ``lengths.balanced_deal``); inside a block the asks of one
  document lie ``ask_stride`` requests apart, so a later ask meets the
  document's pages sometimes still held by an earlier ask and sometimes
  freed and revived. Every cycle of the list draws new documents.
* **What decides ``correct``.** ``benchmark/reference_kimi_k2.py`` run
  once over prompt and outputs of (i) a document asked cold (several
  chunks, the last padded), (ii) THE SAME document asked again, whose
  pages the engine has to map from its cache (the run is not correct
  unless the second ask's prefill computed its question alone), each
  decoding ``check_output_len`` tokens past a block's boundary, in a
  full batch (``check_fillers``); the verdict on the tokens is
  ``serve_backlog_sparse.verdict``'s. Beside that: no pair on a held
  expert was dropped, nothing compiled inside the window,
  ``window_blocks`` whole blocks closed in it, and the share of prompt
  tokens mapped lies within ``prefix_hit_share_tol`` of the list's own
  (``expected_hit_share``): a cache that stopped hitting would
  otherwise read as a slower engine and not as a fault.
* **What a standstill of the machine is charged** (:func:`pause_costs`).
  The hybrid cell takes a pause's whole length out of its window: its
  device calls are 27 ms, and a pause of 110 ms hides at most the rest
  of one. Here 96 % of the window is device calls of 37 to 330 ms that
  the host sits waiting for, and while the host stands still the chip
  goes on with the call it has: over six runs a pause of 105-113 ms
  cost the window between nothing and 99 ms, and one of 2.96 s all but
  its whole length (PERF.md section 6, PR 43: the same calls matched
  across runs). So a pause is charged what the device call that met it
  ran OVER what such a call takes in the same run: a chunk's time is a
  line in its offset a bucket (residuals of 1.3-4.7 ms), a decode
  call's the median of its eight neighbours'; never more than the
  pause's own length, and the whole of it where no call was in flight.
  ``window.rate_by_the_clock`` has nothing taken out,
  ``window.rate_less_whole_pauses`` the hybrid cell's rule.
* **What the traced seconds did** (``traced_work``): as there, and for
  each prefill call the positions its tokens attended and the latents
  it had to read, and the positions mapped and not computed.
"""

from __future__ import annotations

import bisect
import gc
import itertools
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import harness, lengths, machine_pauses, reference_kimi_k2
from benchmark.generators import serve_backlog, serve_common
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_sparse as sparse

#: A request of the list: (document of its block, document's length,
#: question's length, output length).
Ask = Tuple[int, int, int, int]


def ask_blocks(traffic: Dict[str, Any]) -> List[List[Ask]]:
    """The fixed multiset of ``n_lengths`` requests in blocks of
    ``block_requests``, the same for every seed: block b's documents
    ``0 .. per - 1``, ask a of document d at place ``a * ask_stride +
    d``; questions and outputs dealt into balanced blocks and paired by
    one fixed permutation a block."""
    n, size = traffic["n_lengths"], traffic["block_requests"]
    asks, stride = traffic["asks_per_document"], traffic["ask_stride"]
    per = size // asks
    if n % size or size % asks or stride != per:
        raise ValueError(f"n_lengths {n}, block_requests {size}, "
                         f"asks_per_document {asks} and ask_stride {stride} "
                         "do not divide")
    n_blocks, unit = n // size, traffic["document_multiple_of"]

    def deal(key, count, to=1):
        return lengths.balanced_deal(
            [int(round(x / to)) * to
             for x in lengths.stratified(traffic[key], count)], n_blocks)

    docs = deal("document_len", n // asks, unit)
    rng = np.random.default_rng(0)
    out = []
    for d, q, o in zip(docs, deal("question_len", n), deal("output_len", n)):
        d = [d[int(i)] for i in rng.permutation(per)]
        out.append([(i % per, d[i % per], q[int(j)], o[int(k)])
                    for i, j, k in zip(range(size), rng.permutation(size),
                                       rng.permutation(size))])
    return out


def documents_of(block: List[Ask]) -> Dict[int, int]:
    """A block's documents: ``{document: its length}``."""
    return {i: d for i, d, _, _ in block}


def expected_hit_share(blocks: List[List[Ask]]) -> float:
    """The share of the list's prompt tokens that lie in a document
    already asked: what a prefix cache that never misses maps."""
    prompt = sum(d + q for b in blocks for _, d, q, _ in b)
    asked = sum(d for b in blocks for _, d, _, _ in b)
    first = sum(sum(documents_of(b).values()) for b in blocks)
    return (asked - first) / prompt


def request_stream(traffic: Dict[str, Any], model_seed: int, names):
    rng = hybrid.Renamed([model_seed, 1], names)
    for block in itertools.cycle(ask_blocks(traffic)):
        docs: Dict[int, List[int]] = {}
        for i, d, _, _ in block:                 # in the block's order
            if i not in docs:
                docs[i] = rng.integers(0, len(names), d).tolist()
        for i, _, q, n_out in block:
            yield docs[i] + rng.integers(0, len(names), q).tolist(), n_out


def seeded_engine(config, traffic, names, cfg):
    """``hybrid.seeded_engine`` with the routers' selection bias drawn
    too (``seeded_weights.router_bias`` of the configuration file): the
    one model of ``seeded_weights.seed``, its embedding's rows and its
    head's columns laid out under ``names``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve import ServeEngine

    scfg = serve_common.serve_config(traffic)
    spread = config["seeded_weights"].get("router_bias_std", 0.0)

    def init(key, old_of):
        p = init_transformer(cfg, key)
        layers = [{**lp, "moe": {**lp["moe"], "router_bias": spread
                                 * jax.random.normal(
                                     jax.random.fold_in(key, 7 + i),
                                     lp["moe"]["router_bias"].shape,
                                     jnp.float32)}}
                  for i, lp in enumerate(p["layers"])]
        return {**p, "layers": layers, "embed": p["embed"][old_of],
                "lm_head": p["lm_head"][:, old_of]}

    params = jax.jit(init)(
        jax.random.PRNGKey(config["seeded_weights"]["seed"] % 2 ** 32),
        jnp.asarray(np.argsort(names)))
    return ServeEngine(cfg, params, scfg, clock=time.perf_counter), params, scfg


def warm_up(engine, scfg, vocab: int, rng) -> int:
    """Every chunk bucket of ``prefill_resume`` (a prompt of one whole
    chunk and a last chunk of each bucket) and ``decode`` at the one
    batch bucket: no prompt of this traffic is as short as a chunk, so
    the monolithic ``prefill`` is never met and is not compiled. One
    request at a time, three tokens each."""
    plens = [scfg.prefill_chunk + b for b in scfg.prefill_buckets]
    for plen in plens:
        engine.submit(rng.integers(0, vocab, plen).tolist(), 3)
        engine.run_until_idle()
    return len(plens)


def serve_check_requests(engine, traffic, vocab: int, rng):
    """The check's asks of ONE document through the engine in a FULL
    batch (``check_fillers`` go in first and are still decoding when the
    last ask ends): ``(prompts, results or None, fillers alongside,
    positions each ask's prefill spans say were mapped)``."""
    n_out = traffic["check_output_len"]
    fill = traffic["check_fillers"]
    fillers = [engine.submit(
        rng.integers(0, vocab, fill["prompt_len"]).tolist(),
        fill["output_len"]) for _ in range(fill["n"])]
    doc = rng.integers(0, vocab, traffic["check_document_len"]).tolist()
    prompts = [doc + rng.integers(0, vocab, n).tolist()
               for n in traffic["check_question_lens"]]
    rids = [engine.submit(p, n_out, trace_id=10 ** 6 + i)
            for i, p in enumerate(prompts)]
    engine.run_until_idle()
    res = [engine.result(r) for r in rids]
    if not all(r is not None and r.status == "ok"
               and len(r.tokens) == n_out for r in res):
        return prompts, None, 0, []
    spans = serve_common.engine_spans(engine, "check")
    mapped = [sum(s["args"].get("mapped", 0) for s in spans
                  if s["name"] == "serve:prefill"
                  and s["args"].get("trace") == 10 ** 6 + i)
              for i in range(len(prompts))]
    first = min(r.first_token_at for r in res)
    last = max(r.finished_at for r in res)
    alongside = sum(
        1 for f in map(engine.result, fillers)
        if f.status == "ok" and f.first_token_at <= first
        and f.finished_at >= last)
    return prompts, res, alongside, mapped


def check_against_reference(engine, params, config, traffic, vocab: int,
                            rng) -> Dict[str, Any]:
    n_out = traffic["check_output_len"]
    sizes = reference_kimi_k2.sizes_of(config)
    prompts, results, alongside, mapped = serve_check_requests(
        engine, traffic, vocab, rng)
    if results is None:
        return {"correct": False, "why": "a check request did not end well"}
    gaps: List[float] = []
    for prompt, res in zip(prompts, results):
        gaps += sparse.token_gaps(reference_kimi_k2.logits(
            params, np.asarray(prompt + res.tokens[:-1]), sizes, last=n_out),
            res.tokens)
    out = sparse.verdict(gaps, traffic)
    # the first ask computed everything; every later one mapped the
    # document and computed its question alone
    doc = traffic["check_document_len"]
    out["positions_mapped_by_ask"] = mapped
    out["fillers_decoding_alongside"] = alongside
    out["correct"] = (out["correct"]
                      and mapped == [0] + [doc] * (len(prompts) - 1)
                      and alongside == traffic["check_fillers"]["n"])
    return out


def _usual_seconds(calls, buckets) -> Dict[int, float]:
    """What each device call of ``calls`` takes when nothing stands in
    its way, from the run's own calls (by ``id``): a chunk's time as a
    line in its offset, fitted a bucket with the calls far off the line
    left out (three passes); a decode call's as the median of its four
    neighbours' on either side."""
    usual: Dict[int, float] = {}
    by_bucket: Dict[int, list] = {}
    for s in calls:
        if s["name"] == "serve:prefill":
            n = s["args"]["n_tokens"]
            by_bucket.setdefault(next(b for b in buckets if n <= b),
                                 []).append(s)
    for group in by_bucket.values():
        x = np.array([s["args"]["offset"] for s in group], float)
        y = np.array([s["dur"] for s in group])
        fitted = np.full(len(y), np.median(y))
        if len(group) >= 4:
            a = np.stack([np.ones_like(x), x], 1)
            keep = np.ones(len(y), bool)
            for _ in range(3):
                line = np.linalg.lstsq(a[keep], y[keep], rcond=None)[0]
                fitted = a @ line
                off = np.abs(y - fitted)
                keep = off < max(3 * float(np.std((y - fitted)[keep])), 2e-3)
                if keep.sum() < 3:
                    break
        usual.update((id(s), float(f)) for s, f in zip(group, fitted))
    steps = [s for s in calls if s["name"] == "serve:decode"]
    d = np.array([s["dur"] for s in steps])
    for i, s in enumerate(steps):
        near = np.r_[d[max(0, i - 4):i], d[i + 1:i + 5]]
        usual[id(s)] = float(np.median(near)) if len(near) else s["dur"]
    return usual


def pause_costs(still, spans, buckets) -> List[Tuple[float, float, float]]:
    """What each standstill of the machine ``still`` (``(start,
    seconds)``, ``machine_pauses.inside``'s) cost the serving loop, as
    ``(start, seconds, cost)``: the time by which the device call that
    was in flight when it began ran over what such a call takes in this
    run (:func:`_usual_seconds`), shared among the pauses that met one
    call, at most their own length; the whole length where the host
    stood between two calls (the chip had nothing to go on with)."""
    calls = sorted((s for s in spans
                    if s["name"] in ("serve:prefill", "serve:decode")),
                   key=lambda s: s["t0"])
    usual = _usual_seconds(calls, buckets)
    starts = [s["t0"] for s in calls]
    met: Dict[Any, List[Tuple[float, float]]] = {}
    for a, seconds in still:
        i = bisect.bisect_right(starts, a) - 1
        inside = i >= 0 and a < calls[i]["t0"] + calls[i]["dur"]
        met.setdefault(i if inside else None, []).append((a, seconds))
    out = []
    for i, pauses in met.items():
        whole = sum(seconds for _, seconds in pauses)
        over = whole if i is None else min(whole, max(
            calls[i]["dur"] - usual[id(calls[i])], 0.0))
        out += [(a, seconds, over * seconds / whole)
                for a, seconds in pauses]
    return sorted(out)


def traced_work(trace, spans, stamps, latent_live) -> Dict[str, float]:
    """``hybrid.traced_work``, and what the prefill calls attended: a
    call of ``n`` tokens at ``offset`` computes ``n`` queries, the i-th
    over ``offset + i + 1`` positions, and reads ``offset + n``
    latents; ``mapped`` positions were taken from the cache."""
    work = hybrid.traced_work(trace, spans, stamps, latent_live)
    if not work:
        return work
    lo, hi = trace.started_at, trace.stopped_at
    inside = [s for s in spans if lo <= s["t0"] + s["dur"] <= hi]
    chunks = [(s["args"]["offset"], s["args"]["n_tokens"],
               s["args"].get("mapped", 0))
              for s in inside if s["name"] == "serve:prefill"]
    calls = [s for s in inside
             if s["name"] in ("serve:prefill", "serve:decode")]
    return {**work,
            "prefill_positions_seen": sum(n * o + n * (n + 1) // 2
                                          for o, n, _ in chunks),
            "prefill_latents_read": sum(o + n for o, n, _ in chunks),
            "prefill_mapped": sum(m for _, _, m in chunks),
            "traced_s": max((s["t0"] + s["dur"] for s in calls),
                            default=lo) - lo}


def run(ctx) -> Dict[str, Any]:
    config, traffic, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    seed = ctx["seed"]
    # First of all: a program that does not know the configuration's
    # fields fails here with a TypeError, at once.
    cfg = ctx.get("model_cfg") or harness.model_config(config)

    from horovod_tpu.serve import QueueFull

    model_seed = config["seeded_weights"]["seed"]
    names = hybrid.vocabulary_names(seed, cfg.vocab_size)
    engine, params, scfg = seeded_engine(config, traffic, names, cfg)
    rng = hybrid.Renamed([model_seed, 0], names)

    def mark(phase, **kv):    # where set-up's seconds and the peak go
        stats = ctx["devices"][0].memory_stats() or {}
        harness.say(phase=phase, programs_lowered=ctx["compiles"].count,
                    since_start_s=round(
                        time.perf_counter() - ctx["t_start"], 2),
                    peak_gb=stats.get("peak_bytes_in_use", 0) / 1e9, **kv)

    mark("engine")
    n_warm = warm_up(engine, scfg, cfg.vocab_size, rng)
    mark("warm", requests=n_warm)
    check = check_against_reference(engine, params, config, traffic,
                                    cfg.vocab_size, rng)
    mark("check", check=check)
    routing = sparse.routing_counters(params, cfg, scfg, rng)
    mark("routing")
    blocks = ask_blocks(traffic)
    block = len(blocks[0])
    want_share = expected_hit_share(blocks)
    harness.say(lengths={
        "n": traffic["n_lengths"], "block": block,
        "document_quartiles": serve_common.quartiles(
            [d for b in blocks for d in documents_of(b).values()]),
        "prompt_quartiles": serve_common.quartiles(
            [d + q for b in blocks for _, d, q, _ in b]),
        "output_quartiles": serve_common.quartiles(
            [o for b in blocks for _, _, _, o in b]),
        "output_sum_by_block": [sum(o for _, _, _, o in b) for b in blocks],
        "prompt_sum_by_block": [sum(d + q for _, d, q, _ in b)
                                for b in blocks],
        "expected_hit_share": want_share})
    stream = request_stream(traffic, model_seed, names)

    submitted: Dict[int, int] = {}               # rid -> output length
    order: List[int] = []                        # rids as submitted
    shed = 0

    def submit_next() -> bool:
        nonlocal shed
        prompt, n_out = next(stream)
        try:
            rid = engine.submit(prompt, n_out, trace_id=len(submitted) + 1)
        except QueueFull:
            shed += 1
            return False
        submitted[rid] = n_out
        order.append(rid)
        return True

    def top_up():
        while (engine.admission_snapshot()["queue_depth"]
               < traffic["queue_target"]) and submit_next():
            pass

    # serve_backlog_hybrid.run's loop: fill every slot, then step with
    # the queue topped up; a block is complete when all its requests
    # have retired, and the first block's end opens the window. Garbage
    # is collected now and kept out of the window, as there.
    for _ in range(scfg.max_batch):
        submit_next()
    gc.collect()
    gc.freeze()
    gc.disable()
    m = engine.metrics
    stamps: List[float] = []
    tokens: List[int] = []
    latent_live: List[int] = []
    hits: List[Tuple[int, int]] = []             # (mapped, computed) tokens
    cuts: List[int] = []
    compiles_at_open = None
    trace = ctx["trace_window"]
    n_cut = traffic["window_blocks"]
    limit = hybrid.WINDOW_SLACK * seconds
    with machine_pauses.MachinePauses() as probe:
        while True:
            with ctx["annotate"]("bench:submit"):
                top_up()
            engine.step()
            now = time.perf_counter()
            stamps.append(now)
            tokens.append(m.tokens_generated)
            latent_live.append(m.kv_latent_positions_live)
            hits.append((m.prefix_hit_tokens, m.prefix_prefill_tokens))
            while len(order) >= (len(cuts) + 1) * block and all(
                    engine.result(r) is not None for r in
                    order[len(cuts) * block:(len(cuts) + 1) * block]):
                cuts.append(len(stamps) - 2)
                if compiles_at_open is None:
                    compiles_at_open = ctx["compiles"].count
            if cuts:
                since_open = now - stamps[cuts[0]]
                trace.poll(since_open)
                if (since_open >= seconds and len(cuts) > n_cut
                        or since_open >= limit):
                    break
        stood = probe.stop()
    trace.stop()
    gc.enable()
    gc.unfreeze()
    compiles = ctx["compiles"].count - compiles_at_open

    win = serve_backlog.window_rate(stamps, tokens, cuts[:n_cut + 1], limit)
    if win is None:
        raise SystemExit("benchmark: no whole block inside the window")
    t_open, t_close = win["t_open"], win["t_close"]
    spans = serve_common.engine_spans(engine, ctx["workload"])
    still = pause_costs(
        machine_pauses.inside(stood, t_open, t_close, stamps), spans,
        scfg.prefill_buckets)
    stood_s = sum(cost for _, _, cost in still)
    rate = win["tokens"] / (t_close - t_open - stood_s)
    lo, hi = stamps[win["i_open"] + 1], stamps[win["i_close"] + 1]
    done = {rid: r for rid in submitted
            if (r := engine.result(rid)) is not None
            and lo < r.finished_at <= hi}
    failed = shed + sum(
        1 for rid, r in done.items()
        if r.status != "ok" or len(r.tokens) != submitted[rid])
    ttft = [r.first_token_at - r.submitted_at for r in done.values()
            if r.first_token_at is not None]
    durs = [b - a for a, b in zip(stamps[win["i_open"]:win["i_close"]],
                                  stamps[win["i_open"] + 1:win["i_close"] + 1])]
    usual = sorted(durs)[len(durs) // 2]
    # the prompt tokens of the requests admitted between the window's
    # ends: mapped from the cache, and computed
    (hit0, miss0), (hit1, miss1) = hits[win["i_open"]], hits[win["i_close"]]
    hit_share = (hit1 - hit0) / max(hit1 - hit0 + miss1 - miss0, 1)
    snap = m.snapshot()
    work = traced_work(trace, spans, stamps, latent_live)
    harness.say(window={"blocks": win["blocks"], "tokens": win["tokens"],
                        "rate": rate, "rate_by_the_clock": win["rate"],
                        "rate_less_whole_pauses": win["tokens"] / (
                            t_close - t_open - sum(s for _, s, _ in still))},
                machine_pauses={"probe": probe.state, "charged_s": stood_s,
                                "at_s_for_ms_charged_ms": [
                                    [round(a - t_open, 3), round(1e3 * s, 1),
                                     round(1e3 * cost, 1)]
                                    for a, s, cost in still]},
                retired=len(done), longest_sequence=max(
                    (r.n_prompt + len(r.tokens) for r in done.values()),
                    default=0),
                window_s=t_close - t_open, steps=win["i_close"] - win["i_open"],
                blocks_closed_at_s=[round(stamps[c] - t_open, 2) for c in cuts],
                step_s={"median": usual, "max": max(durs)},
                warm_traffic_s=round(t_open - stamps[0], 2),
                shed=shed, compiles_in_window=compiles,
                prefix={"hit_share_in_window": hit_share,
                        "expected": want_share,
                        "hit_tokens": hit1 - hit0,
                        "computed_tokens": miss1 - miss0,
                        "hit_rate_since_start": snap["prefix_cache_hit_rate"],
                        "pages_shared_max": snap.get("kv_pages_shared_max"),
                        "blocks_cached": snap["kv_blocks_cached"],
                        "evictions": snap["prefix_block_evictions"]},
                state={"latent_positions_max":
                           snap["kv_latent_positions_max"],
                       "latent_positions_live_mean":
                           float(np.mean(latent_live)),
                       "blocks_high_water": snap["kv_blocks_high_water"]},
                traced_work=work,
                ttft_quartiles_ms=[1e3 * x for x in
                                   serve_common.quartiles(ttft)])
    return {
        "correct": (bool(check["correct"]) and compiles == 0
                    and routing["moe_dispatch_dropped_token_frac"] == 0
                    and win["blocks"] == n_cut
                    and abs(hit_share - want_share)
                    <= traffic["prefix_hit_share_tol"]),
        "attempted": len(done) + shed,
        "failed": failed,
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {"serve_tok_s": rate},
        "spans": spans,
        "counters": {"compiles_in_window": compiles, "shed": shed,
                     "kv_latent_positions_max":
                         snap["kv_latent_positions_max"],
                     "kv_pages_shared_max": snap.get("kv_pages_shared_max"),
                     "prefix_hit_token_share_pct": 100.0 * hit_share,
                     "window_blocks": win["blocks"], **routing},
        "samples": {"ttft_s": ttft},
        "traced_work": work,
        "engine": {"max_batch": scfg.max_batch,
                   "prefill_chunk": scfg.prefill_chunk},
        "model": config["model"],
    }
