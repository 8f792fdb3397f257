"""What the serving generators share: the engine from a traffic file,
seeded weights, the warm-up of exactly the cell's shapes, the check
against the plain reference, and the engine's spans on the harness's
clock.

The program is driven through ``ServeEngine`` / ``ServeConfig`` /
``engine.submit`` / ``engine.step`` only. What the harness reads of it:
``RequestResult``, the counter ``engine.metrics.tokens_generated``,
``admission_snapshot()``
and the spans of ``export_chrome_trace``. Every request is submitted
with a ``trace_id`` of the harness's, which the engine writes on the
prefill span and on every decode span the request takes part in: that
is how a token is given to its request without a per-token timestamp in
the engine.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import harness, lengths, reference

# chip_smoke.py's tolerance for a served token: with random weights the
# largest logits lie within a few bf16 ULPs of each other, so the argmax
# may differ from the float32 reference's while the mathematics agree.
# The served token's reference logit must lie within 2^-5 of the
# largest magnitude below the reference's maximum: bf16 keeps 8 bits
# (2^-9 a rounding), and sixteen layers of roundings stay well inside
# 2^-5, while a model computed in 8-bit floats (2^-4 a rounding) or with
# a layer left out does not.
FIRST_TOKEN_TOL = 2 ** -5


def serve_config(traffic: Dict[str, Any]):
    from horovod_tpu.serve import ServeConfig

    knobs = dict(traffic["engine"])
    for key in ("prefill_buckets", "batch_buckets"):
        if knobs.get(key) is not None:
            knobs[key] = tuple(knobs[key])
    return ServeConfig(**knobs)


def make_engine(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
                cfg=None):
    """(engine, params, cfg, scfg): weights made on the device from the
    seed by one jitted call, in the type they are served in."""
    import jax

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve import ServeEngine

    cfg = cfg or harness.model_config(config)
    scfg = serve_config(traffic)
    params = jax.jit(lambda key: init_transformer(cfg, key))(
        jax.random.PRNGKey(seed % 2 ** 32))
    engine = ServeEngine(cfg, params, scfg, clock=time.perf_counter)
    return engine, params, cfg, scfg


def prepare(ctx) -> Dict[str, Any]:
    """What both serving kinds do before their traffic starts: the
    engine and its seeded weights, the warm-up of the cell's shapes, the
    check against the reference, and the seeded request stream."""
    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    engine, params, cfg, scfg = make_engine(config, traffic, seed,
                                            cfg=ctx.get("model_cfg"))
    rng = np.random.default_rng([seed, 0])
    n_warm = warm_up(engine, scfg, cfg.vocab_size, rng)
    check = check_against_reference(engine, params, config, traffic,
                                    cfg.vocab_size, rng)
    harness.say(phase="warm", requests=n_warm, check=check,
                since_start_s=round(time.perf_counter() - ctx["t_start"], 2))
    pairs = lengths.length_pairs(traffic)
    harness.say(lengths={
        "n": len(pairs),
        "prompt_quartiles": quartiles([p for p, _ in pairs]),
        "output_quartiles": quartiles([o for _, o in pairs]),
        "output_sum": sum(o for _, o in pairs)})
    return {"engine": engine, "scfg": scfg, "check": check,
            "stream": lengths.request_stream(traffic, seed, cfg.vocab_size)}


def warm_up(engine, scfg, vocab: int, rng) -> int:
    """Run every prefill bucket and every decode batch bucket the
    traffic file names, and no other shape. For each batch bucket ``b``,
    ``b`` requests of two tokens each are admitted in one step (one
    prefill each, then one decode of ``b``); their prompts go round the
    prefill buckets. Returns the number of requests served."""
    buckets = cell_buckets(scfg)
    n = 0
    for b in buckets["batch"]:
        for i in range(b):
            plen = buckets["prefill"][(n + i) % len(buckets["prefill"])]
            plen = min(plen, scfg.max_prompt)
            engine.submit(rng.integers(0, vocab, plen).tolist(), 2)
        engine.run_until_idle()
        n += b
    return n


def cell_buckets(scfg) -> Dict[str, Tuple[int, ...]]:
    """The shapes the cell can meet: the traffic file's menus."""
    if scfg.prefill_buckets is None or scfg.batch_buckets is None:
        raise SystemExit("benchmark: a serving traffic file names its "
                         "prefill_buckets and batch_buckets, so that the "
                         "warm-up covers exactly the cell's shapes")
    return {"prefill": tuple(scfg.prefill_buckets),
            "batch": tuple(scfg.batch_buckets)}


def check_against_reference(engine, params, config, traffic, vocab: int,
                            rng) -> Dict[str, Any]:
    """Two seeded requests of two tokens each through the engine: the
    first token comes from prefill, the second from one decode through
    the cache. Each must be the float32 reference's argmax up to
    ``FIRST_TOKEN_TOL`` of its largest logit."""
    sizes = reference.sizes_of(config)
    prompts = [rng.integers(0, vocab, n).tolist()
               for n in traffic["check_prompt_lens"]]
    rids = [engine.submit(p, 2) for p in prompts]
    engine.run_until_idle()
    worst, ok = 0.0, True
    for prompt, rid in zip(prompts, rids):
        res = engine.result(rid)
        if res is None or res.status != "ok" or len(res.tokens) != 2:
            return {"correct": False, "why": f"check request ended {res}"}
        lg = np.asarray(reference.logits(
            params, prompt + res.tokens[:1], sizes, last=2))
        for row, tok in zip(lg, res.tokens):
            gap = float(row.max() - row[tok]) / float(np.abs(row).max())
            worst = max(worst, gap)
            ok = ok and gap <= FIRST_TOKEN_TOL
    return {"correct": ok, "worst_logit_gap": worst, "tol": FIRST_TOKEN_TOL}


def engine_spans(engine, workload: str) -> List[Dict[str, Any]]:
    """The engine's spans as ``{name, t0, dur, args}`` on the harness's
    clock (``time.perf_counter``, which the engine was given)."""
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    path = os.path.join(harness.OUT_DIR, f"spans-{workload}.json")
    engine.metrics.export_chrome_trace(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    t_ref = engine.metrics.started_at
    return [{"name": e["name"], "t0": t_ref + e["ts"] * 1e-6,
             "dur": e["dur"] * 1e-6, "args": e.get("args", {})}
            for e in events if e.get("ph") == "X"]


def token_times(spans: List[Dict[str, Any]], stamps: List[float],
                prompt_lens: Dict[int, int]) -> Dict[int, List[float]]:
    """For each request (by the ``trace_id`` it was submitted with), the
    time of each of its output tokens. The first is the end of the
    prefill span that completed its prompt; each later one is the
    harness's stamp after the ``engine.step()`` whose decode span lists
    the request."""
    out: Dict[int, List[float]] = {}
    for s in spans:
        end = s["t0"] + s["dur"]
        if s["name"] == "serve:prefill":
            tid = s["args"].get("trace")
            if tid and (s["args"]["offset"] + s["args"]["n_tokens"]
                        >= prompt_lens[tid]):
                out.setdefault(tid, []).insert(0, end)
        elif s["name"] == "serve:decode":
            i = bisect.bisect_left(stamps, end - 1e-4)
            stamp = stamps[min(i, len(stamps) - 1)]
            for tid in s["args"].get("traces", ()):
                out.setdefault(tid, []).append(stamp)
    return out


def quartiles(values: List[float]) -> List[float]:
    if len(values) < 2:
        return list(values)
    return statistics.quantiles(values, n=4)
