"""A backlog that never empties of long byte documents with answers of a
kilobyte or two, through a byte-level model whose every layer is EVA
attention (an exact, block-aligned window beside one attended summary a
chunk of every window that has closed, in one softmax), with a float32
stream, unit-offset norms and a head of eight prediction rows (ISSUE
56): ``serve_backlog_ssm.py``'s cell (the configuration built first of
all, one seeded model under the names ``--seed`` gives the vocabulary, a
warm-up of every program the window can meet, a check in a full batch
against the plain reference read off the engine that is then timed,
``serve_tok_s`` cut at the same whole block of one fixed list in every
run less the machine's standstills) at contexts of 8k to 32k bytes in 16
slots.

What differs from ``serve_backlog_ssm`` (its ``length_blocks``,
``request_stream`` and loop are used as they are):

* **The seeded model** (:func:`seeded_params`): the embedding and the
  two branch-closing matrices at ``seeded_weights``' gains, so that a
  layer's branches are small beside the stream (the regime the float32
  stream exists for), the stored norm gains drawn wide enough to tell
  ``1 + g`` from ``g``; the embedding's rows and the columns of EVERY
  row of the head laid out under ``names``.
* **What decides ``correct``: logits and state, not tokens alone.** Each
  check request is served in a FULL batch; then what it LEFT on the
  engine (its slot's rows of every layer and the pages its table named:
  ``RequestResult.slot`` and ``.blocks``) is copied into a cache of one
  slot, and the served programs' decode step with the logits themselves
  as its output (``mixed_programs``' ``head``) runs the request's LAST
  token over it: all ``head_rows`` rows of logits at that position, and
  then every layer's live rows (K, V) and every whole chunk's summaries
  (k~, v~), against ``benchmark/reference_evabyte.py`` run once over
  prompt and outputs (:func:`left_by`, :func:`verdict`). The served
  tokens are held to the reference's row 0 by
  ``serve_backlog_sparse.verdict`` besides.
* **What the traced seconds did** (:func:`traced_work`): the calls,
  rows and real tokens; the exact keys and the summaries the chunks'
  queries saw; the rows and summaries each decode call's attention had
  to read (``eva_rows``, ``eva_summaries`` of the spans).
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, machine_pauses, reference_evabyte
from benchmark.generators import serve_backlog, serve_common
from benchmark.generators import serve_backlog_hybrid as hybrid
from benchmark.generators import serve_backlog_sparse as sparse
from benchmark.generators import serve_backlog_ssm as ssm

#: What a sequence leaves in an eva layer, in the reference's order.
KEPT = ("rows_k", "rows_v", "summaries_k", "summaries_v")


def seeded_params(cfg, key, config: Dict[str, Any], old_of):
    """``init_transformer``'s weights at ``seeded_weights``' gains (the
    embedding, every layer's ``wo`` and ``w_down``, the stored norm
    gains), the embedding's rows and the columns of every row of the
    head under ``old_of``. The reference reads the same tree."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import init_transformer

    how = config["seeded_weights"]
    p = init_transformer(cfg, key)
    keys = iter(jax.random.split(jax.random.fold_in(key, 1000),
                                 2 * cfg.n_layers + 1))

    def gain(a):
        return a + (how["norm_gain_std"] * jax.random.normal(
            next(keys), a.shape)).astype(a.dtype)

    def scaled(a, by):
        return a * jnp.asarray(by, a.dtype)

    layers = [{**lp, "attn_norm": gain(lp["attn_norm"]),
               "mlp_norm": gain(lp["mlp_norm"]),
               "wo": scaled(lp["wo"], how["branch_gain"]),
               "w_down": scaled(lp["w_down"], how["branch_gain"])}
              for lp in p["layers"]]
    head = p["lm_head"].reshape(cfg.d_model, cfg.head_rows, cfg.vocab_size)
    return {**p, "layers": layers, "final_norm": gain(p["final_norm"]),
            "embed": scaled(p["embed"], how["embed_gain"])[old_of],
            "lm_head": head[:, :, old_of].reshape(cfg.d_model, -1)}


def seeded_engine(config, traffic, names, cfg, seed=None):
    """The engine of the one model of ``seeded_weights.seed`` (``seed``:
    another model than the cell's, for the tolerance tool)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serve import ServeEngine

    scfg = serve_common.serve_config(traffic)
    params = jax.jit(lambda key, old_of: seeded_params(cfg, key, config,
                                                       old_of))(
        jax.random.PRNGKey((config["seeded_weights"]["seed"]
                            if seed is None else seed) % 2 ** 32),
        jnp.asarray(np.argsort(names)))
    return ServeEngine(cfg, params, scfg, clock=time.perf_counter), params, scfg


def _taken(rows, pages, slot, blocks):
    """A cache of one slot and one table out of the engine's ``rows``
    and ``pages``: the null slot and the sequence's, the null page and
    its ``blocks``."""
    import jax.numpy as jnp

    mine = rows[:, slot]
    return (jnp.stack([jnp.zeros_like(mine), mine], 1),
            jnp.concatenate([jnp.zeros_like(pages[:, :1]),
                             pages[:, blocks]], 1))


def logits_step(engine, cfg, positions: int):
    """``(decode, width, taken)``: the served programs' decode step with
    the float32 logits themselves as its output (``mixed_programs``'
    ``head``), jitted over a cache of its own whose tables hold
    ``positions``, and :func:`_taken` jitted: one program of each for
    every check request."""
    import jax

    from horovod_tpu.serve.decode import mixed_programs

    bs = engine.cfg.block_size
    width = -(-positions // bs)
    return (jax.jit(mixed_programs(cfg, bs, width, 0,
                                   head=lambda lg: lg)[2],
                    donate_argnums=(1, 2)), width, jax.jit(_taken))


def left_by(engine, params, cfg, prompt: List[int], res, step=None):
    """What the request ``res`` left on ``engine``, through the served
    programs: the float32 logits ``[head_rows, vocab]`` of its LAST
    token (which the engine emitted and never fed) at its position, over
    a copy of the rows its slot holds and of the pages its table named
    (one slot and one table's pages: made in one program, so that
    nothing else is allocated beside the engine), and after that step
    every eva layer's ``(K rows, V rows, k~, v~)`` as the reference
    returns them. Read before another request is admitted (the blocks
    are free by then). ``step``: :func:`logits_step`'s, where several
    requests are read."""
    import jax.numpy as jnp

    if engine.cache.kinds != ("eva",):
        raise SystemExit("benchmark: serve-backlog-eva checks a stack of "
                         f"eva layers alone, not {engine.cache.kinds}")
    n = len(prompt) + len(res.tokens)               # positions after the step
    decode, width, taken = step or logits_step(engine, cfg, n)
    blocks = np.zeros(width, np.int32)              # (the null block behind)
    blocks[:len(res.blocks)] = res.blocks[:width]
    kc, vc = ((taken(*pair, jnp.int32(res.slot), jnp.asarray(blocks)),)
              for pair in (engine.cache.k[0], engine.cache.v[0]))
    kc, vc, logits = decode(
        params, kc, vc, jnp.asarray(res.tokens[-1:], jnp.int32),
        jnp.asarray([n - 1], jnp.int32),
        (jnp.arange(1, width + 1, dtype=jnp.int32)[None],
         jnp.ones((1,), jnp.int32)))
    (kr, ks), (vr, vs) = kc[0], vc[0]
    live = (n - 1) % cfg.eva_window + 1
    whole = n // cfg.eva_chunk
    kept = [tuple(np.asarray(a, np.float32) for a in (
        kr[c, 1, :live], vr[c, 1, :live],
        ks[c, 1:].reshape(-1, *ks.shape[3:])[:whole],
        vs[c, 1:].reshape(-1, *vs.shape[3:])[:whole]))
        for c in range(kr.shape[0])]
    return np.asarray(logits[0], np.float32), kept


def kept_gaps(got, want) -> Dict[str, List[float]]:
    """How far what a sequence left lies off the reference's, an array
    of :data:`KEPT` after another: for each eva layer the norm of the
    difference over the norm of the reference's (nothing to hold: 0)."""
    def far(a, b):
        b = np.asarray(b, np.float32)
        return (float(np.linalg.norm(a - b) / np.linalg.norm(b))
                if b.size else 0.0)
    return {name: [far(layer[i], theirs[i])
                   for layer, theirs in zip(got, want)]
            for i, name in enumerate(KEPT)}


def logits_gap(got, want) -> float:
    """The furthest logit of all the head's rows off the reference's,
    over the reference's largest magnitude."""
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


def verdict(logit_gaps: List[float], kept: List[Dict[str, List[float]]],
            traffic: Dict[str, Any]) -> Dict[str, Any]:
    """What the check says of logits ``logit_gaps`` (a number a check
    request) and state ``kept`` (:func:`kept_gaps` of each) off the
    reference's: the furthest logits under ``check_logits_tol``, the
    furthest layer's rows under ``check_rows_tol`` and the furthest
    layer's summaries under ``check_summaries_tol`` (``check_why`` has
    the readings the three lie between)."""
    worst = {name: max(max(k[name]) for k in kept) for name in KEPT}
    rows = max(worst["rows_k"], worst["rows_v"])
    sums = max(worst["summaries_k"], worst["summaries_v"])
    return {"logits_gap": max(logit_gaps), "rows_gap": rows,
            "summaries_gap": sums, **{f"{k}_gap": v for k, v in worst.items()},
            "logits_tol": traffic["check_logits_tol"],
            "rows_tol": traffic["check_rows_tol"],
            "summaries_tol": traffic["check_summaries_tol"],
            "correct": bool(max(logit_gaps) <= traffic["check_logits_tol"]
                            and rows <= traffic["check_rows_tol"]
                            and sums <= traffic["check_summaries_tol"])}


def check_against_reference(engine, params, config, traffic, vocab: int,
                            rng, cfg) -> Dict[str, Any]:
    """The check requests served in a full batch, then what each left
    and its last token's logits through the served programs, against
    the reference's one pass over prompt and outputs."""
    n_out = traffic["check_output_len"]
    sizes = reference_evabyte.sizes_of(config)
    prompts, results, alongside = hybrid.serve_check_requests(
        engine, traffic, vocab, rng)
    if results is None:
        return {"correct": False, "why": "a check request did not end well"}
    step = logits_step(engine, cfg, max(map(len, prompts)) + n_out)
    left = [left_by(engine, params, cfg, prompt, res, step)
            for prompt, res in zip(prompts, results)]
    gaps: List[float] = []
    far: List[float] = []
    kept: List[Dict[str, List[float]]] = []
    for prompt, res, (logits, state) in zip(prompts, results, left):
        want, held = reference_evabyte.forward(
            params, np.asarray(prompt + res.tokens), sizes, last=n_out + 1,
            kept=True)
        want = np.asarray(want)
        gaps += sparse.token_gaps(want[:n_out, 0], res.tokens)
        far.append(logits_gap(logits, want[n_out]))
        kept.append(kept_gaps(state, held))
    out = sparse.verdict(gaps, traffic)
    by_state = verdict(far, kept, traffic)
    # every slot but the check requests' own was decoding beside them
    out["fillers_decoding_alongside"] = alongside
    out["correct"] = (out["correct"] and by_state.pop("correct")
                      and alongside == traffic["check_fillers"]["n"])
    return {**out, **by_state, "logits_gaps": far,
            "windows_closed_behind": [
                (len(p) + n_out) // cfg.eva_window for p in prompts]}


def traced_work(trace, spans, stamps, model) -> Dict[str, float]:
    """``hybrid.traced_work`` (calls, rows and real tokens between the
    profiler's start and stop), and of the same calls: the exact keys
    the chunks' real queries saw (the i-th of a chunk at ``offset``:
    its window's ``offset % W + i + 1``) and the summaries they saw
    (``offset // W`` closed windows' each), the rows and the summaries
    the decode calls' attention had to read a layer (``eva_rows``,
    ``eva_summaries`` of the spans), and the seconds from the
    profiler's start to the end of the last call inside."""
    work = hybrid.traced_work(trace, spans, stamps, [0] * len(stamps))
    if not work:
        return work
    lo, hi = trace.started_at, trace.stopped_at
    calls = [s for s in spans if lo <= s["t0"] + s["dur"] <= hi
             and s["name"] in ("serve:prefill", "serve:decode")]
    chunks = [s["args"] for s in calls if s["name"] == "serve:prefill"]
    steps = [s["args"] for s in calls if s["name"] == "serve:decode"]
    W, per = model["eva_window"], model["eva_window"] // model["eva_chunk"]
    del work["latent_positions"]
    return {**work,
            "prefill_keys_exact": sum(
                a["n_tokens"] * (a["offset"] % W)
                + a["n_tokens"] * (a["n_tokens"] + 1) // 2 for a in chunks),
            "prefill_keys_summaries": sum(
                a["n_tokens"] * (a["offset"] // W) * per for a in chunks),
            "decode_rows_read": sum(a.get("eva_rows", 0) for a in steps),
            "decode_summaries_read": sum(a.get("eva_summaries", 0)
                                         for a in steps),
            "traced_s": max((s["t0"] + s["dur"] for s in calls),
                            default=lo) - lo}


def run(ctx) -> Dict[str, Any]:
    config, traffic, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    seed = ctx["seed"]
    # First of all: a program that does not know the configuration's
    # fields (or its kind of layer) fails here, at once.
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
        "longest": max(p + o for b in blocks for p, o in b),
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
    stood_s = sum(s for _, s in still)
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
    snap = m.snapshot()
    work = traced_work(trace, spans, stamps, config["model"])
    slots_mean = float(np.mean(in_use[win["i_open"]:win["i_close"]]))
    harness.say(window={"blocks": win["blocks"], "tokens": win["tokens"],
                        "rate": rate, "rate_by_the_clock": win["rate"]},
                machine_pauses={"probe": probe.state, "stood_still_s": stood_s,
                                "at_s_for_ms": [
                                    [round(a - t_open, 3), round(1e3 * s, 1)]
                                    for a, s in still]},
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
                       "summary_pages_max": snap.get("eva_summary_pages_max"),
                       "windows_closed":
                           snap.get("eva_windows_closed_total")},
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
                     "eva_summary_pages_max":
                         snap.get("eva_summary_pages_max"),
                     "eva_windows_closed_total":
                         snap.get("eva_windows_closed_total"),
                     "window_blocks": win["blocks"]},
        "samples": {"ttft_s": ttft},
        "traced_work": work,
        "engine": {"max_batch": scfg.max_batch,
                   "prefill_chunk": scfg.prefill_chunk},
        "model": config["model"],
    }
