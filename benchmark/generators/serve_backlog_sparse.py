"""A backlog that never empties, through a sparse model served over two
kinds of cache: ``serve_backlog.py``'s cell (queue topped up before
every step, ``serve_tok_s`` cut at whole blocks of the traffic's list by
``serve_backlog.window_rate``) for a configuration whose prompts go in
as chunks.

What differs from ``serve_backlog``:

* **The configuration is built first of all**, before anything is
  imported, made or compiled: a program that does not know its fields
  (the parent of the PR that brought them) fails here with a
  ``TypeError``, at once.
* **Its own warm-up.** ``serve_common.warm_up`` sends prompts no longer
  than a bucket, so with ``prefill_chunk`` set it never reaches
  ``prefill_resume``. Here every chunk bucket of ``prefill_resume`` (a
  prompt of one whole chunk and one last chunk of each bucket), the
  monolithic ``prefill`` that a prompt of exactly one chunk takes, and
  ``decode`` at the one batch bucket run before the window, and nothing
  else.
* **What decides ``correct``**: ``check_prompt_lens`` requests (one of
  two chunks, one longer than a window layer's ring, so that its keys
  wrap round it) go through the engine in a full batch
  (``check_fillers`` one-chunk requests decode beside them) and
  produce ``check_output_len`` tokens each; ``benchmark/
  reference_trinity.py`` runs once over each prompt and its outputs,
  and a served token agrees with it when its reference logit lies
  within ``check_tol`` of the reference's largest at that position, as
  a share of the largest magnitude there
  (``serve_common.FIRST_TOKEN_TOL``'s form and value). At most
  ``check_allowed_over`` of the tokens may disagree: a router picks the
  4 largest of 256 scores, a near-tie between the fourth and the fifth
  falls the other way in bf16 about once in a thousand pairs, and a
  token that takes another expert moves its own logits by up to a fifth
  of their largest, however right the program is (the float32
  reference with its values stored as bf16 does the same). So the
  limit is on how MANY tokens disagree, not on how far the furthest
  does: the readings, on the chip, are in the traffic file's
  ``check_why``. Beside that: no (token, choice) pair on a held expert was
  dropped (``moe_share_report`` on a decode-sized and a chunk-sized
  batch: counted off the dispatch's own sort and group sizes), nothing
  compiled inside the window, and the window layers' rings, read from
  the device at the end, hold what the engine sent them and no more
  than their width.
* **Blocks of ``block_requests``** (the engine's ``max_batch`` unless
  the traffic file says otherwise): the fixed multiset of ``n_lengths``
  pairs dealt into balanced blocks of that size.
"""

from __future__ import annotations

import gc
import itertools
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import harness, lengths, reference_trinity
from benchmark.generators import serve_backlog, serve_common


def seeded_weights(cfg, key, config: Dict[str, Any]):
    """``init_transformer``'s weights with the gains of the per-head
    q and k norms at ``seeded_weights.qk_norm_gain`` of the
    configuration file (its ``why`` says what for: attention that looks
    at few keys, as a trained checkpoint's does, so that a token's
    experts are the token's and not its sequence's). The reference
    reads the same tree."""
    from horovod_tpu.models import init_transformer

    params = init_transformer(cfg, key)
    gain = config.get("seeded_weights", {}).get("qk_norm_gain", 1.0)
    if gain == 1.0:
        return params

    def scaled(lp):
        return {**lp, "q_norm": lp["q_norm"] * gain,
                "k_norm": lp["k_norm"] * gain}

    return {**params,
            "dense_layers": [scaled(lp) for lp in params["dense_layers"]],
            "layers": [scaled(lp) for lp in params["layers"]]}


def make_engine(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
                cfg):
    """``serve_common.make_engine`` with :func:`seeded_weights`."""
    import jax

    from horovod_tpu.serve import ServeEngine

    scfg = serve_common.serve_config(traffic)
    params = jax.jit(lambda key: seeded_weights(cfg, key, config))(
        jax.random.PRNGKey(seed % 2 ** 32))
    engine = ServeEngine(cfg, params, scfg, clock=time.perf_counter)
    return engine, params, cfg, scfg


def length_blocks(traffic: Dict[str, Any]) -> List[List[Tuple[int, int]]]:
    """``lengths.length_blocks`` with the block's size from
    ``block_requests``."""
    n = traffic["n_lengths"]
    size = traffic.get("block_requests", traffic["engine"]["max_batch"])
    if n % size:
        raise ValueError(f"n_lengths {n} is not a multiple of {size}")
    deal = [lengths.balanced_deal(
        [int(round(x)) for x in lengths.stratified(traffic[key], n)],
        n // size) for key in ("prompt_len", "output_len")]
    rng = np.random.default_rng(0)
    return [[(p[int(i)], o[int(j)]) for i, j in
             zip(rng.permutation(size), rng.permutation(size))]
            for p, o in zip(*deal)]


def request_stream(traffic: Dict[str, Any], seed: int, vocab: int):
    rng = np.random.default_rng([seed, 1])
    pairs = [pair for block in length_blocks(traffic) for pair in block]
    for n_prompt, n_out in itertools.cycle(pairs):
        yield rng.integers(0, vocab, n_prompt).tolist(), n_out


def warm_up(engine, scfg, vocab: int, rng) -> int:
    """One request a chunk bucket, of one whole chunk and a last chunk
    of that bucket, and one of exactly a chunk; three tokens each, so
    that ``decode`` runs too. One at a time: a request that shares a
    step's chunk budget with another is cut where the budget ends, and
    the one of exactly a chunk would then never take the monolithic
    ``prefill``."""
    chunk = scfg.prefill_chunk
    plens = [chunk + b for b in scfg.prefill_buckets] + [chunk]
    for plen in plens:
        engine.submit(rng.integers(0, vocab, plen).tolist(), 3)
        engine.run_until_idle()
    return len(plens)


def token_gaps(want, tokens) -> List[float]:
    """For each position, the reference's largest logit less its logit
    for ``tokens``' token there, over the largest magnitude at that
    position (``want`` [n, V])."""
    return [float(row.max() - row[tok]) / float(np.abs(row).max())
            for row, tok in zip(np.asarray(want), tokens)]


def verdict(gaps: List[float], traffic: Dict[str, Any]) -> Dict[str, Any]:
    """What the check says of tokens that lie ``gaps`` off the
    reference: ``benchmark/tools/trinity_tolerance.py`` puts the tokens
    of wrongly computed models through this same function."""
    tol = traffic["check_tol"]
    over = sum(g > tol for g in gaps)
    return {"correct": over <= traffic["check_allowed_over"],
            "tokens_over_tol": over,
            "allowed_over": traffic["check_allowed_over"], "tol": tol,
            "tokens": len(gaps), "worst_logit_gap": max(gaps),
            "mean_logit_gap": float(np.mean(gaps)),
            "tokens_off_the_reference_s_argmax": sum(g > 0 for g in gaps)}


def serve_check_requests(engine, traffic, vocab: int, rng):
    """The check requests' prompts and served tokens (None where one
    did not end well), served in a FULL batch: ``check_fillers``
    requests of one chunk go in first and are still decoding when the
    last check request ends, so the check's decode steps scatter to
    and gather from every slot's ring and not two of them."""
    n_out = traffic["check_output_len"]
    fill = traffic.get("check_fillers", {"n": 0})
    fillers = [engine.submit(
        rng.integers(0, vocab, fill["prompt_len"]).tolist(),
        fill["output_len"]) for _ in range(fill["n"])]
    prompts = [rng.integers(0, vocab, n).tolist()
               for n in traffic["check_prompt_lens"]]
    rids = [engine.submit(p, n_out) for p in prompts]
    engine.run_until_idle()
    res = [engine.result(r) for r in rids]
    served = [r.tokens if r is not None and r.status == "ok"
              and len(r.tokens) == n_out else None for r in res]
    if None in served:
        return prompts, served, 0
    first = min(r.first_token_at for r in res)
    last = max(r.finished_at for r in res)
    alongside = sum(
        1 for f in map(engine.result, fillers)
        if f.status == "ok" and f.first_token_at <= first
        and f.finished_at >= last)
    return prompts, served, alongside


def check_against_reference(engine, params, config, traffic, vocab: int,
                            rng) -> Dict[str, Any]:
    n_out = traffic["check_output_len"]
    sizes = reference_trinity.sizes_of(config)
    prompts, served, alongside = serve_check_requests(engine, traffic, vocab,
                                                      rng)
    if None in served:
        return {"correct": False, "why": f"check requests ended {served}"}
    gaps: List[float] = []
    for prompt, toks in zip(prompts, served):
        gaps += token_gaps(reference_trinity.logits(
            params, np.asarray(prompt + toks[:-1]), sizes, last=n_out), toks)
    out = verdict(gaps, traffic)
    # every slot but the check requests' own was decoding beside them
    fillers = traffic.get("check_fillers", {"n": 0})["n"]
    out["fillers_decoding_alongside"] = alongside
    out["correct"] = out["correct"] and alongside == fillers
    return out


def rings_fullest_slot(rings) -> int:
    """``rings`` [n_window, n_slots, ring, Hkv, Dh] on the device: the
    number of places of one slot's ring that hold a key (a row that is
    not all zeros: the rings start as zeros, and a retired sequence's
    keys stay), the largest over slots and layers."""
    import jax
    import jax.numpy as jnp

    return int(jax.jit(lambda r: jnp.any(r != 0, axis=(-1, -2))
                       .sum(-1).max())(rings))


def routing_counters(params, cfg, scfg, rng) -> Dict[str, float]:
    """The held experts' load on a decode-sized batch (one token of
    each slot) and on a chunk-sized one, read at set-up."""
    from horovod_tpu.serve.decode import moe_share_report

    step = moe_share_report(params, rng.integers(
        0, cfg.vocab_size, (scfg.max_batch, 1)), cfg, scfg.block_size)
    chunk = moe_share_report(params, rng.integers(
        0, cfg.vocab_size, (1, scfg.prefill_chunk)), cfg, scfg.block_size)
    harness.say(routing={"decode_batch": step, "chunk": chunk})
    return {
        "moe_local_pair_share": chunk["moe_local_pair_share"],
        "moe_held_experts_touched_mean":
            step["moe_held_experts_touched_mean"],
        "moe_expert_load_max_over_mean":
            chunk["moe_expert_load_max_over_mean"],
        "moe_dispatch_dropped_token_frac": max(
            step["moe_dispatch_dropped_token_frac"],
            chunk["moe_dispatch_dropped_token_frac"]),
    }


def run(ctx) -> Dict[str, Any]:
    config, traffic, seconds = ctx["config"], ctx["traffic"], ctx["seconds"]
    seed = ctx["seed"]
    # First of all (see the module's docstring).
    cfg = ctx.get("model_cfg") or harness.model_config(config)

    from horovod_tpu.serve import QueueFull
    from horovod_tpu.serve.kv_cache import ring_width

    engine, params, cfg, scfg = make_engine(config, traffic, seed, cfg)
    rng = np.random.default_rng([seed, 0])

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
    routing = routing_counters(params, cfg, scfg, rng)
    mark("routing")
    blocks = length_blocks(traffic)
    harness.say(lengths={
        "n": traffic["n_lengths"], "block": len(blocks[0]),
        "prompt_quartiles": serve_common.quartiles(
            [p for b in blocks for p, _ in b]),
        "output_quartiles": serve_common.quartiles(
            [o for b in blocks for _, o in b]),
        "output_sum_by_block": [sum(o for _, o in b) for b in blocks],
        "prompt_sum_by_block": [sum(p for p, _ in b) for b in blocks]})
    stream = request_stream(traffic, seed, cfg.vocab_size)

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

    # serve_backlog.run's loop: fill every slot, then step with the
    # queue topped up; a block is complete when all its requests have
    # retired, and the first block's end opens the window.
    block = len(blocks[0])
    for _ in range(scfg.max_batch):
        submit_next()
    # Set-up leaves garbage behind (the reference runs hundreds of small
    # eager operations and traces a hundred programs), and a collection
    # of the oldest generation between two steps is a quarter of a
    # second added to the window, or not, from run to run (six seeds
    # fell into two groups 0.7 % apart before this, PR 32). Collect
    # now, and keep what set-up leaves alive out of the window's own
    # collections, as the train-moe generator does.
    gc.collect()
    gc.freeze()
    gc.disable()
    m = engine.metrics
    stamps: List[float] = []
    tokens: List[int] = []
    cuts: List[int] = []
    compiles_at_open = None
    trace = ctx["trace_window"]
    while True:
        with ctx["annotate"]("bench:submit"):
            top_up()
        engine.step()
        now = time.perf_counter()
        stamps.append(now)
        tokens.append(m.tokens_generated)
        while len(order) >= (len(cuts) + 1) * block and all(
                engine.result(r) is not None
                for r in order[len(cuts) * block:(len(cuts) + 1) * block]):
            cuts.append(len(stamps) - 2)
            if compiles_at_open is None:
                compiles_at_open = ctx["compiles"].count
        if cuts:
            since_open = now - stamps[cuts[0]]
            trace.poll(since_open)
            if since_open >= seconds:
                break
    trace.stop()
    gc.enable()
    gc.unfreeze()
    compiles = ctx["compiles"].count - compiles_at_open

    win = serve_backlog.window_rate(stamps, tokens, cuts, seconds)
    if win is None:
        raise SystemExit("benchmark: no whole block inside the window")
    t_open, t_close = win["t_open"], win["t_close"]
    spans = serve_common.engine_spans(engine, ctx["workload"])
    lo, hi = stamps[win["i_open"] + 1], stamps[win["i_close"] + 1]
    done = {rid: r for rid in submitted
            if (r := engine.result(rid)) is not None
            and lo < r.finished_at <= hi}
    failed = shed + sum(
        1 for rid, r in done.items()
        if r.status != "ok" or len(r.tokens) != submitted[rid])
    ttft = [r.first_token_at - r.submitted_at for r in done.values()
            if r.first_token_at is not None]
    # Where a window's seconds went beyond its steps' usual length: the
    # steps far over the median of their kind (with or without a
    # prefill chunk), and what they add up to.
    durs = [b - a for a, b in zip(stamps[win["i_open"]:win["i_close"]],
                                  stamps[win["i_open"] + 1:win["i_close"] + 1])]
    usual = sorted(durs)[len(durs) // 2]
    slow = sorted(((d, i) for i, d in enumerate(durs) if d > 2.5 * usual),
                  reverse=True)
    harness.say(step_s={"median": usual, "max": max(durs),
                        "sum": sum(durs),
                        "steps_over_2.5_medians": len(slow),
                        "their_excess_s": sum(d - usual for d, _ in slow),
                        "slowest": [[i, round(d, 4)] for d, i in slow[:6]]})
    snap = m.snapshot()
    ring = ring_width(cfg.attn_window, scfg.prefill_chunk, scfg.block_size)
    # What the window layers' rings hold, read from the device: the
    # places of a slot's ring with a key in them, the fullest slot of
    # any window layer. The engine's own count (positions it sent to a
    # ring, capped at the ring) has to agree with it: writes that fell
    # on one another or beside the ring would leave fewer.
    held_max = rings_fullest_slot(engine.cache.k[1])
    held_by_engine = snap["kv_window_positions_max"]
    harness.say(window={k: win[k] for k in ("blocks", "tokens", "rate")},
                retired=len(done), longest_sequence=max(
                    (r.n_prompt + len(r.tokens) for r in done.values()),
                    default=0),
                window_s=t_close - t_open, steps=win["i_close"] - win["i_open"],
                warm_traffic_s=round(t_open - stamps[0], 2),
                shed=shed, compiles_in_window=compiles,
                kv={"window_positions_max": held_max,
                    "window_positions_max_by_the_engine": held_by_engine,
                    "ring": ring,
                    "blocks_high_water": snap["kv_blocks_high_water"],
                    "window_blocks_in_use": snap["kv_window_blocks_in_use"]},
                ttft_quartiles_ms=[1e3 * x for x in
                                   serve_common.quartiles(ttft)])
    return {
        "correct": (bool(check["correct"]) and compiles == 0
                    and routing["moe_dispatch_dropped_token_frac"] == 0
                    and 0 < held_max == held_by_engine <= ring),
        "attempted": len(done) + shed,
        "failed": failed,
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {"serve_tok_s": win["rate"]},
        "spans": spans,
        "counters": {"compiles_in_window": compiles, "shed": shed,
                     "kv_window_positions_max": held_max,
                     "window_blocks": win["blocks"], **routing},
        "samples": {"ttft_s": ttft},
        "engine": {"max_batch": scfg.max_batch,
                   "prefill_chunk": scfg.prefill_chunk},
        "model": config["model"],
    }
