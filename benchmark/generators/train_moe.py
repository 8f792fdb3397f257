"""Training a sparse (mixture-of-experts) decoder: ``train.py``'s shape,
seeded rows of ``seq`` tokens through ``make_train_step``, with three
differences.

``correct`` is decided against ``benchmark/reference_olmoe.py``: the
first step's loss on the tiled check row, all three terms of it, and
the program's logits at the last ``logit_check_last`` positions of that
row (root-mean-square error over the root-mean-square logit), both
within the tolerances of the traffic file. The routing
counters (``moe_routing_report``) are read on the check batch during
set-up, and a dropped (token, choice) pair fails the run. ``model`` and
the routing counters go to the reducers.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, reference_olmoe


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    config, traffic = ctx["config"], ctx["traffic"]
    # First of all: a program that does not know the configuration's
    # fields (the parent of the PR that brought them) fails here, with a
    # TypeError, before anything is imported, built or compiled.
    cfg = ctx.get("model_cfg") or harness.model_config(config)

    from horovod_tpu.models import make_train_step, transformer_forward
    from horovod_tpu.models.transformer import moe_routing_report
    from horovod_tpu.parallel import build_mesh

    seed, seconds = ctx["seed"], ctx["seconds"]
    devices = ctx["devices"]
    n = len(devices)
    seq, rows = traffic["seq"], traffic["rows_per_chip"]

    def mark(phase):            # where set-up's seconds go
        harness.say(phase=phase, programs_lowered=ctx["compiles"].count,
                    since_start_s=round(
                        time.perf_counter() - ctx["t_start"], 2))

    mesh = build_mesh(devices=devices, **traffic["mesh_by_chips"][str(n)])
    init_state, step, _ = make_train_step(cfg, mesh)
    state = init_state(jax.random.PRNGKey(seed % 2 ** 32))
    jax.block_until_ready(state)
    mark("state")

    rng = np.random.default_rng([seed, 0])
    batch_sh = NamedSharding(mesh, P(("dp", "fsdp"), None))
    check_row = rng.integers(0, cfg.vocab_size, seq + 1, dtype=np.int32)

    def put(tokens):
        return {"tokens": jax.device_put(jnp.asarray(tokens), batch_sh)}

    check_batch = put(np.tile(check_row, (rows * n, 1)))
    batches = [put(rng.integers(0, cfg.vocab_size, (rows * n, seq + 1),
                                dtype=np.int32)) for _ in range(4)]

    # The reference on the check row, from the same initial parameters.
    last = traffic["logit_check_last"]
    sizes = reference_olmoe.sizes_of(config)
    ref = reference_olmoe.loss_terms(state["params"], check_row[None], sizes)
    ref_logits = np.asarray(ref.pop("logits")[0, -last:])
    want = {k: float(v) for k, v in ref.items()}
    mark("reference")
    got_logits = np.asarray(jax.jit(
        lambda p, t: transformer_forward(p, t, cfg)[0, -last:])(
            state["params"], check_row[None, :-1]).astype(jnp.float32))
    # Root-mean-square error over the root-mean-square logit: one
    # token that takes another expert at a near-tie moves its own 50304
    # logits and no more, which a largest-error limit would hang on.
    logit_err = float(np.sqrt(np.mean((got_logits - ref_logits) ** 2))
                      / np.sqrt(np.mean(ref_logits ** 2)))
    logit_tol = traffic["logit_check_tol"]
    mark("logits")
    routing = moe_routing_report(state["params"],
                                 check_batch["tokens"][:, :-1], cfg)
    del ref, ref_logits, got_logits
    mark("routing")

    state, loss = step(state, check_batch)
    got = float(loss)
    mark("first_step")
    tol = traffic["loss_check_tol"] * abs(want["loss"])
    check = {"correct": (abs(got - want["loss"]) <= tol
                         and logit_err <= logit_tol
                         and routing["moe_dispatch_dropped_token_frac"] == 0),
             "loss": got, "reference": want, "tol": tol,
             "logit_err": logit_err, "logit_tol": logit_tol,
             "routing": routing}
    state, loss = step(state, batches[0])   # the donated layout, once more
    loss.block_until_ready()
    harness.say(phase="warm", check=check, mesh=dict(mesh.shape),
                tokens_per_step=rows * n * seq,
                since_start_s=round(time.perf_counter() - ctx["t_start"], 2))

    # Set-up leaves garbage behind (the reference runs thousands of
    # small eager operations). Every step here ends in
    # block_until_ready, so a collection between two steps is added to
    # the step: on the chip one run in three held a step of 440 to
    # 470 ms among its 356 ms ones (PR 26). Collect now, and keep what
    # set-up leaves alive out of the window's own collections.
    gc.collect()
    gc.freeze()
    trace = ctx["trace_window"]
    compiles_at_open = ctx["compiles"].count
    spans: List[Dict[str, Any]] = []
    losses: List[float] = []
    t_open = time.perf_counter()
    i = 0
    while True:
        t0 = time.perf_counter()
        if t0 - t_open >= seconds:
            break
        trace.poll(t0 - t_open)
        with jax.profiler.StepTraceAnnotation("train:step", step_num=i):
            state, loss = step(state, batches[i % len(batches)])
            loss.block_until_ready()
        spans.append({"name": "train:step", "t0": t0,
                      "dur": time.perf_counter() - t0, "args": {}})
        losses.append(loss)
        i += 1
    t_close = time.perf_counter()
    gc.unfreeze()
    trace.stop()
    compiles = ctx["compiles"].count - compiles_at_open
    losses = [float(x) for x in losses]
    finite = all(math.isfinite(x) for x in losses)
    tokens = len(spans) * rows * n * seq
    durs = sorted(1e3 * s["dur"] for s in spans)
    tenth = max(len(spans) // 10, 1)
    harness.say(steps=len(spans), window_s=t_close - t_open,
                first_loss=losses[0], last_loss=losses[-1],
                losses_finite=finite, compiles_in_window=compiles,
                # routing follows the weights as they train, and the
                # grouped matmuls' time follows the routing
                step_ms={"min": durs[0], "median": durs[len(durs) // 2],
                         "max": durs[-1],
                         "slow_steps": [
                             [i, round(1e3 * s["dur"], 1)]
                             for i, s in enumerate(spans)
                             if 1e3 * s["dur"] > 1.05 * durs[len(durs) // 2]],
                         "first_tenth": 1e3 * sum(
                             s["dur"] for s in spans[:tenth]) / tenth,
                         "last_tenth": 1e3 * sum(
                             s["dur"] for s in spans[-tenth:]) / tenth})
    return {
        "correct": bool(check["correct"]) and finite and compiles == 0,
        "attempted": len(spans),
        "failed": sum(not math.isfinite(x) for x in losses),
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {
            "train_tok_s_chip": tokens / (t_close - t_open) / n},
        "spans": spans,
        "counters": {"compiles_in_window": compiles,
                     "tokens_per_step": rows * n * seq, **routing},
        "samples": {},
        "train": {"seq": seq, "rows_per_chip": rows, "chips": n},
        "model": config["model"],
    }
