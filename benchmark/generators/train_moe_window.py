"""Training a sparse decoder whose layers are window and full attention
in turn, on one chip's share of the experts: ``train_moe.py``'s shape
(seeded rows of ``seq`` tokens through ``make_train_step``, four fixed
batches in turn, the steps carrying on from the checked state), with
these differences.

**One input for every ``--seed``.** A step's time follows the (token,
choice) pairs that the weights and the batch put on the held experts
(the grouped matmuls run those rows alone), with a share of the experts
that count is the model's and the row's, and it trains: over models
drawn from ``--seed`` the cell's rate spread 1.3-3.2 %, which a bound
of 1 % cannot carry. Nor can ``--seed`` be let to touch a single number
of the state: with one seeded model under a vocabulary renamed by
``--seed`` (the same function of the same symbols; only the sums over
the vocabulary run in another order) the first loss is the same to the
last bit and the window's end is not, the pairs on the held experts
0.20-0.29 of a batch's by seed and the rate spread 1.04 % (PERF.md
section 6, PR 34, my chip runs): AdamW's first updates are the
gradient's signs, and a last bit turns one. So the model and its five
rows are drawn from the configuration file's ``seeded_weights.seed``,
and every run is that one trajectory, as the serving cells give every
seed one schedule.

``correct`` is decided against ``benchmark/reference_mellum2.py`` (the
same share of the experts and slice of the vocabulary), on the state
that is then timed: the first step's loss on the tiled check row,
auxiliary term included; the program's logits at the last
``logit_check_last`` positions of that row (root-mean-square error over
the root-mean-square logit); and the first step's GRADIENT, every leaf
of it, read out of the step's own output (after one update from zero,
AdamW's first moment is ``(1 - b1) g``) against the reference's
``gradient_by_layer``: the largest over the leaves of
``|g - g_ref| / |g_ref|``. All three within the tolerances of the
traffic file.

The routing counters (``moe_routing_report``: the share of a layer's
pairs that fall on a held expert, the held experts' load, the pairs the
dispatch would not run) are read on the check batch during set-up, and
after the window on each of the four batches with the parameters the
window left: the traced steps are the window's last, over those four in
turn, and the mean of their shares is what
``reducers/held_experts_roofline.py`` counts the grouped matmuls' work
from (the weights still move from step to step, on the whole away from
the held experts, so that mean tends to lie under what the traced steps
ran, and the roofline share with it). A pair on a held expert that is
not run fails the run.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, reference_mellum2

_ADAMW_B1 = 0.9         # optax.adamw's, which make_train_step builds


def leaf_at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def worst_leaf(leaves):
    """Of ``(path, gradient, the reference's)`` for every leaf: the
    largest ``|g - g_ref| / |g_ref|``, the name of its leaf (a NaN
    before any number), and the same over all leaves as one vector."""
    errs, off, size = {}, 0.0, 0.0
    for path, g, g_ref in leaves:
        d = float(np.linalg.norm((np.asarray(g) - g_ref).ravel()))
        r = float(np.linalg.norm(g_ref.ravel()))
        errs["/".join(map(str, path))] = d / r
        off, size = off + d * d, size + r * r
    at = max(errs, key=lambda k: (errs[k] != errs[k], errs[k]))
    return errs[at], at, math.sqrt(off / size)


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

    seconds = ctx["seconds"]
    devices = ctx["devices"]
    n = len(devices)
    seq, rows = traffic["seq"], traffic["rows_per_chip"]

    def mark(phase):            # where set-up's seconds go
        harness.say(phase=phase, programs_lowered=ctx["compiles"].count,
                    since_start_s=round(
                        time.perf_counter() - ctx["t_start"], 2))

    mesh = build_mesh(devices=devices, **traffic["mesh_by_chips"][str(n)])
    init_state, step, _ = make_train_step(cfg, mesh)
    # One model and its rows for every --seed (module docstring).
    seed = config["seeded_weights"]["seed"]
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
    sizes = reference_mellum2.sizes_of(config)
    ref = reference_mellum2.loss_terms(state["params"], check_row[None],
                                       sizes)
    ref_logits = np.asarray(ref.pop("logits")[0, -last:])
    want = {k: float(v) for k, v in ref.items()}
    mark("reference")
    got_logits = np.asarray(jax.jit(
        lambda p, t: transformer_forward(p, t, cfg)[0, -last:])(
            state["params"], check_row[None, :-1]).astype(jnp.float32))
    # Root-mean-square error over the root-mean-square logit: one
    # token that takes another expert at a near-tie moves its own
    # logits and no more, which a largest-error limit would hang on.
    logit_err = float(np.sqrt(np.mean((got_logits - ref_logits) ** 2))
                      / np.sqrt(np.mean(ref_logits ** 2)))
    logit_tol = traffic["logit_check_tol"]
    mark("logits")
    routing = moe_routing_report(state["params"],
                                 check_batch["tokens"][:, :-1], cfg)
    del ref, ref_logits, got_logits
    mark("routing")
    # The reference's gradient, taken off the device leaf by leaf.
    want_grad = {path: np.asarray(g) for path, g in
                 reference_mellum2.gradient_by_layer(
                     state["params"], check_row[None], sizes)}
    mark("reference_gradient")

    state, loss = step(state, check_batch)
    got = float(loss)
    mark("first_step")
    (first_moment,) = [s.mu for s in state["opt"] if hasattr(s, "mu")]
    grad_err, grad_err_at, grad_err_overall = worst_leaf(
        (path, leaf_at(first_moment, path).astype(jnp.float32)
         / (1 - _ADAMW_B1), want_grad.pop(path))
        for path in list(want_grad))
    grad_tol = traffic["grad_check_tol"]
    mark("gradient")
    tol = traffic["loss_check_tol"] * abs(want["loss"])
    check = {"correct": (abs(got - want["loss"]) <= tol
                         and logit_err <= logit_tol
                         and grad_err <= grad_tol
                         and routing["moe_dispatch_dropped_token_frac"] == 0),
             "loss": got, "reference": want, "tol": tol,
             "logit_err": logit_err, "logit_tol": logit_tol,
             "grad_err": grad_err, "grad_err_at": grad_err_at,
             "grad_err_overall": grad_err_overall,
             "grad_tol": grad_tol, "routing": routing}
    state, loss = step(state, batches[0])   # the donated layout, once more
    loss.block_until_ready()
    harness.say(phase="warm", check=check, mesh=dict(mesh.shape),
                tokens_per_step=rows * n * seq,
                since_start_s=round(time.perf_counter() - ctx["t_start"], 2))

    # As train_moe.py: set-up's garbage is collected now and kept out of
    # the window's own collections, which would be added to a step.
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
    # What the window's last steps routed: the parameters it left, on
    # each of the batches (the same program as at set-up: nothing
    # compiles).
    routed = [moe_routing_report(state["params"], b["tokens"][:, :-1], cfg)
              for b in batches]
    held_share = sum(r["moe_local_pair_share"] for r in routed) / len(routed)
    dropped = max(r["moe_dispatch_dropped_token_frac"] for r in routed)
    tokens = len(spans) * rows * n * seq
    durs = sorted(1e3 * s["dur"] for s in spans)
    tenth = max(len(spans) // 10, 1)
    harness.say(steps=len(spans), window_s=t_close - t_open,
                first_loss=losses[0], last_loss=losses[-1],
                losses_finite=finite, compiles_in_window=compiles,
                routing_at_the_windows_end=routed,
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
        "correct": (bool(check["correct"]) and finite and compiles == 0
                    and dropped == 0),
        "attempted": len(spans),
        "failed": sum(not math.isfinite(x) for x in losses),
        "t_open": t_open, "t_close": t_close,
        "end_to_end": {
            "train_tok_s_chip": tokens / (t_close - t_open) / n},
        "spans": spans,
        "counters": {"compiles_in_window": compiles,
                     "tokens_per_step": rows * n * seq,
                     "pairs_per_layer": rows * seq * cfg.moe_top_k,
                     "moe_local_pair_share_traced": held_share,
                     **routing},
        "samples": {},
        "train": {"seq": seq, "rows_per_chip": rows, "chips": n},
        "model": config["model"],
    }
