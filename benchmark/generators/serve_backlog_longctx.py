"""A backlog that never empties of long documents with short answers,
through a model whose layers are sparse-attention layers (a selection
inside paged attention: compressed keys beside the K/V pages, a query's
GQA group reads its best blocks) beside linear-attention layers (a
decayed float32 state a batch slot) (ISSUE 50):
``serve_backlog_ssm.py``'s cell (the configuration built first of all,
one seeded model under the names ``--seed`` gives the vocabulary, a
warm-up of every program the window can meet, a check of served tokens
and of the states left in their slots, in a full batch, against the
plain reference, ``serve_tok_s`` cut at the same whole block of one
fixed list in every run less the machine's standstills) at contexts of
8k to 32k in 16 slots.

What differs from ``serve_backlog_ssm``:

* **The seeded model** has an untied head (``hybrid.seeded_engine``)
  and gains of ``seeded_weights.qk_gain`` on the q and k norms of its
  sparse layers, so that attention scores spread and a choice of blocks
  is one (the reference reads the same tree).
* **What decides ``correct``.** ``benchmark/reference_minicpm_sala.py``
  run once over prompt and outputs of each check request: the tokens by
  ``serve_backlog_sparse.verdict``, the state each request leaves in its
  slot in every lightning layer by ``serve_backlog_hybrid.state_verdict``
  (the first such layer's under its own limit), and the precision the
  engine keeps that state in (``check_state_dtype``: no limit on a gap
  tells a bfloat16 state from a float32 one here, ``check_why``). All
  three are read off the engine that is then timed. The check requests
  are served in a full batch whose other rows (``check_fillers``) are
  past ``sparse_dense_len`` too, so that every decode call of the check
  chooses pages in fifteen rows at once, as the window's calls do.
* **What is printed beside it.** ``selection_agreement``: the check's
  tokens go through the chunk and step programs once more, at a batch
  of one over a cache of its own, with the pages they chose as one more
  output (``mixed_programs``' ``chosen``), and what every query past
  ``sparse_dense_len`` chose of the blocks that are not forced is
  counted against the reference's choice (a near-tie at the last place
  tips a block as it tips an expert). It is a second pass and not the
  served batch's calls, so it decides nothing.
* **What the traced seconds did** (``traced_work``): the chunks'
  positions by bucket (the scan runs the padding too), their queries
  that chose and the keys and kernels those saw; the decode calls' rows,
  those that chose, and the keys their groups attended.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, machine_pauses, reference_minicpm_sala
from benchmark.generators import serve_backlog, serve_common
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_shared as shared
from benchmark.generators import serve_backlog_sparse as sparse
from benchmark.generators import serve_backlog_ssm as ssm


def seeded_engine(config, traffic, names, cfg):
    """``hybrid.seeded_engine``'s model with the q and k gains of
    ``seeded_weights`` on its sparse layers."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve import ServeEngine

    scfg = serve_common.serve_config(traffic)
    gain = config["seeded_weights"]["qk_gain"]

    def init(key, old_of):
        p = init_transformer(cfg, key)
        layers = [
            {**lp, "q_norm": lp["q_norm"] * jnp.asarray(gain, cfg.dtype),
             "k_norm": lp["k_norm"] * jnp.asarray(gain, cfg.dtype)}
            if kind == "sparse" else lp
            for lp, kind in zip(p["layers"], cfg.layer_types)]
        return {**p, "layers": layers, "embed": p["embed"][old_of],
                "lm_head": p["lm_head"][:, old_of]}

    params = jax.jit(init)(
        jax.random.PRNGKey(config["seeded_weights"]["seed"] % 2 ** 32),
        jnp.asarray(np.argsort(names)))
    return ServeEngine(cfg, params, scfg, clock=time.perf_counter), params, scfg


def chosen_pages(params, cfg, tokens, n_prompt: int, block_size: int,
                 chunk: int):
    """The pages every query of every sparse layer chooses when
    ``tokens[:n_prompt]`` run as chunks of ``chunk`` (``prefill_resume``,
    the last padded to whole blocks) and the rest as decode steps of a
    batch of one, over a cache of its own: ``[n_sparse, T, Hkv, W]``
    bool, W the table's blocks, all False for a query below
    ``sparse_dense_len``. The served programs with one more output
    (``decode.mixed_programs``' ``chosen``)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serve.decode import mixed_programs
    from horovod_tpu.serve.kv_cache import init_kv_cache

    T = len(tokens)
    width = -(-(T + chunk) // block_size)
    _, resume, decode, _ = mixed_programs(cfg, block_size, width, 0,
                                          chosen=True)
    resume = jax.jit(resume, donate_argnums=(1, 2))
    decode = jax.jit(decode, donate_argnums=(1, 2))
    cache = init_kv_cache(cfg, width + 1, block_size, n_slots=1)
    kc, vc = cache.k, cache.v
    table = jnp.arange(1, width + 1, dtype=jnp.int32)
    tokens = jnp.asarray(tokens, jnp.int32)
    out = []
    for off in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - off)
        padded = jnp.zeros(-(-n // block_size) * block_size, jnp.int32
                           ).at[:n].set(tokens[off:off + n])
        kc, vc, _, picked = resume(params, kc, vc, padded, jnp.int32(off),
                                   jnp.int32(n), (table, jnp.int32(1)))
        out.append(picked[:, :n])
    for t in range(n_prompt, T):
        kc, vc, _, picked = decode(
            params, kc, vc, tokens[t:t + 1], jnp.full((1,), t, jnp.int32),
            (table[None], jnp.ones((1,), jnp.int32)))
        out.append(picked)
    return jnp.concatenate(out, 1)


def selection_agreement(got, want, cfg) -> Dict[str, Any]:
    """How far the blocks the program's queries chose (``got``
    [n_sparse, T, Hkv, W] bool) are the reference's (``want``, over the
    blocks the sequence has): of the reference's choices that are not
    forced (the first blocks, the window's), the share the program
    made too; and the queries that chose."""
    got, want = np.asarray(got), np.asarray(want)
    got = got[..., :want.shape[-1]]
    t = np.arange(want.shape[1])
    b = np.arange(want.shape[-1])
    own = t[:, None] // cfg["sparse_block"]
    forced = ((b[None] < cfg["sparse_init_blocks"])
              | (b[None] > own - cfg["sparse_window"] // cfg["sparse_block"]))
    free = want & ~forced[None, :, None, :]
    return {"selection_agreement": float((got & free).sum()
                                         / max(free.sum(), 1)),
            "selection_choices": int(free.sum()),
            "selection_queries": int(want.any(-1).any(-1).any(0).sum()),
            "selection_size_matches": bool(
                (got.sum(-1) == want.sum(-1)).all())}


def check_against_reference(engine, params, config, traffic, vocab: int,
                            rng, cfg) -> Dict[str, Any]:
    """``ssm.check_against_reference`` against this configuration's
    reference and its lightning layers' states, kept in
    ``check_state_dtype``; its sparse layers' choices beside them."""
    n_out = traffic["check_output_len"]
    sizes = reference_minicpm_sala.sizes_of(config)
    prompts, results, alongside = hybrid.serve_check_requests(
        engine, traffic, vocab, rng)
    if results is None:
        return {"correct": False, "why": "a check request did not end well"}
    gaps: List[float] = []
    states: List[List[float]] = []
    chose: List[Dict[str, Any]] = []
    kept, = engine.cache.of("lightning")
    bs, chunk = engine.cfg.block_size, engine.cfg.prefill_chunk
    for prompt, res in zip(prompts, results):
        tokens = prompt + res.tokens[:-1]
        want, state, blocks = reference_minicpm_sala.logits(
            params, np.asarray(tokens), sizes, last=n_out, kept=True)
        gaps += sparse.token_gaps(want, res.tokens)
        states.append(hybrid.state_gaps(kept[:, res.slot], state))
        if len(tokens) > config["model"]["sparse_dense_len"]:
            chose.append(selection_agreement(
                chosen_pages(params, cfg, tokens, len(prompt), bs, chunk),
                blocks, config["model"]))
    out = sparse.verdict(gaps, traffic)
    by_state = hybrid.state_verdict(states, traffic)
    # every slot but the check requests' own was decoding beside them
    out["fillers_decoding_alongside"] = alongside
    out["correct"] = (
        out["correct"] and by_state.pop("correct")
        and alongside == traffic["check_fillers"]["n"]
        and str(kept.dtype) == traffic["check_state_dtype"])
    return {**out, **by_state, "state_dtype": str(kept.dtype),
            "selection_agreement": min(c["selection_agreement"]
                                       for c in chose),
            "selection": chose}


def traced_work(trace, spans, stamps, model, buckets) -> Dict[str, float]:
    """``hybrid.traced_work`` (calls, rows and real tokens between the
    profiler's start and stop), and of the same calls: the positions the
    chunks' scans ran (``prefill_scanned``: the bucket, padding too),
    their queries that chose their blocks (``prefill_selected``), the
    kernels those scored (a query at t: those complete at t; and the
    most of one call, which it has to read: ``prefill_kernels_read``)
    and the keys they attended (the chosen blocks', the query's own
    block part filled), the keys the queries below ``sparse_dense_len``
    saw, the keys up to each call's end (``prefill_keys_read``), and of
    the decode calls the rows that chose, the kernels they scored and
    the keys a KV group of every row attended (``attended``); the
    seconds from the profiler's start to the end of the last call
    inside."""
    work = hybrid.traced_work(trace, spans, stamps, [0] * len(stamps))
    if not work:
        return work
    lo, hi = trace.started_at, trace.stopped_at
    calls = [s for s in spans if lo <= s["t0"] + s["dur"] <= hi
             and s["name"] in ("serve:prefill", "serve:decode")]
    chunks = [s["args"] for s in calls if s["name"] == "serve:prefill"]
    steps = [s["args"] for s in calls if s["name"] == "serve:decode"]
    block, dense = model["sparse_block"], model["sparse_dense_len"]
    kernel, stride = model["sparse_kernel"], model["sparse_stride"]
    chosen_keys = (model["sparse_topk"] - 1) * block

    def of_chunk(a):
        """(kernels scored, kernels read once, keys attended by
        choosers, keys seen by the others, keys read once) of one
        chunk's real queries."""
        t = a["offset"] + np.arange(a["n_tokens"])
        chose = t >= dense
        scored = (t[chose] - kernel) // stride + 1
        return (int(scored.sum()), int(scored.max(initial=0)),
                int((chosen_keys + t[chose] % block + 1).sum()),
                int((t[~chose] + 1).sum()), int(t[-1]) + 1)

    per_chunk = np.array([of_chunk(a) for a in chunks]).reshape(-1, 5)
    del work["latent_positions"]
    return {**work,
            "prefill_scanned": sum(
                min(b for b in buckets if b >= a["n_tokens"])
                for a in chunks),
            "prefill_selected": sum(a.get("selected", 0) for a in chunks),
            "prefill_kernels_scored": int(per_chunk[:, 0].sum()),
            "prefill_kernels_read": int(per_chunk[:, 1].sum()),
            "prefill_keys_chosen": int(per_chunk[:, 2].sum()),
            "prefill_keys_dense": int(per_chunk[:, 3].sum()),
            "prefill_keys_read": int(per_chunk[:, 4].sum()),
            "decode_rows_selected": sum(a.get("rows_selected", 0)
                                        for a in steps),
            "decode_keys_attended": sum(a.get("attended", 0) for a in steps),
            "decode_kernels_scored": sum(a.get("scored", 0) for a in steps),
            "decode_calls_selecting": sum(
                1 for a in steps if a.get("rows_selected", 0)),
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
    n_warm = hybrid.warm_up(engine, scfg, cfg.vocab_size, rng)
    mark("warm", requests=n_warm)
    check = check_against_reference(engine, params, config, traffic,
                                    cfg.vocab_size, rng, cfg)
    mark("check", check=check)
    blocks = ssm.length_blocks(traffic)
    block = len(blocks[0])
    harness.say(lengths={
        "n": traffic["n_lengths"], "block": block,
        "prompt_quartiles": serve_common.quartiles(
            [p for b in blocks for p, _ in b]),
        "output_quartiles": serve_common.quartiles(
            [o for b in blocks for _, o in b]),
        "output_sum_by_block": [sum(o for _, o in b) for b in blocks],
        "prompt_sum_by_block": [sum(p for p, _ in b) for b in blocks]})
    stream = ssm.request_stream(traffic, model_seed, names)

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

    # serve_backlog_ssm.run's loop: fill every slot, then step with the
    # queue topped up; a block is complete when all its requests have
    # retired, and the first block's end opens the window. Garbage is
    # collected now and kept out of the window, as there.
    for _ in range(scfg.max_batch):
        submit_next()
    gc.collect()
    gc.freeze()
    gc.disable()
    m = engine.metrics
    stamps: List[float] = []
    tokens: List[int] = []
    in_use: List[int] = []
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
            in_use.append(m.state_slots_in_use)
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
    still = machine_pauses.inside(stood, t_open, t_close, stamps)
    stood_s = sum(seconds for _, seconds in still)
    rate = win["tokens"] / (t_close - t_open - stood_s)
    by_excess = shared.pause_costs(still, spans, scfg.prefill_buckets)
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
    snap = m.snapshot()
    work = traced_work(trace, spans, stamps, config["model"],
                       scfg.prefill_buckets)
    # the window's queries, and those of them that chose their blocks
    inside = [s["args"] for s in spans
              if t_open < s["t0"] + s["dur"] <= t_close]
    queries = sum(a.get("n_tokens", a.get("n_active", 0)) for a in inside
                  if "selected" in a or "rows_selected" in a)
    chose = sum(a.get("selected", 0) + a.get("rows_selected", 0)
                for a in inside)
    slots_mean = float(np.mean(in_use[win["i_open"]:win["i_close"]]))
    harness.say(window={"blocks": win["blocks"], "tokens": win["tokens"],
                        "rate": rate, "rate_by_the_clock": win["rate"],
                        "rate_by_call_excess": win["tokens"] / (
                            t_close - t_open
                            - sum(cost for _, _, cost in by_excess))},
                machine_pauses={"probe": probe.state, "stood_still_s": stood_s,
                                "at_s_for_ms_excess_ms": [
                                    [round(a - t_open, 3), round(1e3 * s, 1),
                                     round(1e3 * cost, 1)]
                                    for a, s, cost in by_excess]},
                retired=len(done), longest_sequence=max(
                    (r.n_prompt + len(r.tokens) for r in done.values()),
                    default=0),
                window_s=t_close - t_open, steps=win["i_close"] - win["i_open"],
                blocks_closed_at_s=[round(stamps[c] - t_open, 2) for c in cuts],
                step_s={"median": usual, "max": max(durs)},
                warm_traffic_s=round(t_open - stamps[0], 2),
                shed=shed, compiles_in_window=compiles,
                state={"slots_in_use_mean": slots_mean,
                       "slots_in_use_at_end": snap["state_slots_in_use"],
                       "bytes": snap["state_bytes"],
                       "blocks_high_water": snap["kv_blocks_high_water"],
                       "kv_compressed_max": snap["kv_compressed_max"]},
                selection={"queries": queries, "chose": chose},
                traced_work=work,
                ttft_quartiles_ms=[1e3 * x for x in
                                   serve_common.quartiles(ttft)])
    return {
        "correct": (bool(check["correct"]) and compiles == 0
                    and win["blocks"] == n_cut),
        "attempted": len(done) + shed,
        "failed": failed,
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {"serve_tok_s": rate},
        "spans": spans,
        "counters": {"compiles_in_window": compiles, "shed": shed,
                     "state_slots_in_use": snap["state_slots_in_use"],
                     "window_blocks": win["blocks"],
                     "sparse_selected_query_share_pct":
                         100.0 * chose / max(queries, 1)},
        "samples": {"ttft_s": ttft},
        "traced_work": work,
        "engine": {"max_batch": scfg.max_batch,
                   "prefill_chunk": scfg.prefill_chunk},
        "model": config["model"],
    }
