"""Training: seeded rows of ``seq`` tokens through ``make_train_step``.

The state is made on the device by the factory's one jitted init from
the seed. The first step runs on a batch whose rows are all one seeded
row, so its loss is that row's loss, which the float32 reference
computes from the same initial parameters; that step also compiles (or
loads) the one program of the cell. The window then counts whole steps,
each ended by ``block_until_ready``, until ``--seconds`` have passed.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, reference


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import make_train_step
    from horovod_tpu.parallel import build_mesh

    config, traffic = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    devices = ctx["devices"]
    n = len(devices)
    seq, rows = traffic["seq"], traffic["rows_per_chip"]
    cfg = ctx.get("model_cfg") or harness.model_config(config)
    mesh = build_mesh(devices=devices, **traffic["mesh_by_chips"][str(n)])
    init_state, step, _ = make_train_step(cfg, mesh)
    state = init_state(jax.random.PRNGKey(seed % 2 ** 32))

    # Seeded rows: one for the check, a fresh batch for every step of
    # the window would cost host time, so the window cycles a few.
    rng = np.random.default_rng([seed, 0])
    batch_sh = NamedSharding(mesh, P(("dp", "fsdp"), None))
    check_row = rng.integers(0, cfg.vocab_size, seq + 1, dtype=np.int32)

    def put(tokens):
        return {"tokens": jax.device_put(jnp.asarray(tokens), batch_sh)}

    check_batch = put(np.tile(check_row, (rows * n, 1)))
    batches = [put(rng.integers(0, cfg.vocab_size, (rows * n, seq + 1),
                                dtype=np.int32)) for _ in range(4)]

    want = float(reference.loss(state["params"], check_row,
                                reference.sizes_of(config)))
    state, loss = step(state, check_batch)
    got = float(loss)
    # bf16 activations against float32: the loss is a mean over 4096
    # positions of log-softmax values near ln(vocab), so the roundings
    # average out; a step computed in 8-bit floats, or with a layer or
    # the causal mask left out, moves it by far more than this.
    tol = traffic["loss_check_tol"] * abs(want)
    check = {"correct": abs(got - want) <= tol, "loss": got,
             "reference_loss": want, "tol": tol}
    state, loss = step(state, batches[0])   # the donated layout, once more
    loss.block_until_ready()
    harness.say(phase="warm", check=check, mesh=dict(mesh.shape),
                tokens_per_step=rows * n * seq,
                since_start_s=round(time.perf_counter() - ctx["t_start"], 2))

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
    trace.stop()
    compiles = ctx["compiles"].count - compiles_at_open
    losses = [float(x) for x in losses]
    finite = all(math.isfinite(x) for x in losses)
    tokens = len(spans) * rows * n * seq
    harness.say(steps=len(spans), window_s=t_close - t_open,
                first_loss=losses[0], last_loss=losses[-1],
                losses_finite=finite, compiles_in_window=compiles)
    return {
        "correct": bool(check["correct"]) and finite and compiles == 0,
        "attempted": len(spans),
        "failed": sum(not math.isfinite(x) for x in losses),
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {
            "train_tok_s_chip": tokens / (t_close - t_open) / n},
        "spans": spans,
        "counters": {"compiles_in_window": compiles,
                     "tokens_per_step": rows * n * seq},
        "samples": {},
        "train": {"seq": seq, "rows_per_chip": rows, "chips": n},
        "model": config["model"],
    }
