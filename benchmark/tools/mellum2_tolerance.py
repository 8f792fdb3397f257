"""The readings behind the three limits of
``traffic/pretrain-moe-window-seq8192.json`` (``loss_check_tol``,
``logit_check_tol``, ``grad_check_tol``), taken on the chip:

    python -m benchmark.tools.mellum2_tolerance --seeds 2147483651 2147483652 2147483653

For each seed, on the cell's check row at the published widths: what the bf16 program gives against the
float32 reference (first-step loss; the logits of the last positions,
root-mean-square and largest error; the first step's gradient, read out
of AdamW's first moment as the cell reads it, the worst leaf's
``|g - g_ref| / |g_ref|``), and what the REFERENCE gives

* with its weights and the residual stream stored as bf16, the
  program's precision, which the limits have to admit;
* stored in the nearest precision below it, ``float8_e4m3fn``;
* with one mechanism left out: the window; YaRN (the plain rotary on
  full layers); the attention factor (left at 1); the renormalisation
  of the chosen gates; the auxiliary term,

each of which at least one of the three limits has to refuse. The last
line says which were admitted and which limit refused the others.
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmark import harness, reference_mellum2 as ref
from benchmark.generators.train_moe_window import (_ADAMW_B1, leaf_at,
                                                   worst_leaf)

CONTROLS = {
    "reference_in_bf16": dict(store="bfloat16"),
    "reference_in_fp8": dict(store="float8_e4m3fn"),
    "window_left_out": dict(without=("window",)),
    "yarn_left_out": dict(without=("yarn",)),
    "attention_factor_left_at_1": dict(without=("attention_factor",)),
    "gates_not_renormalised": dict(without=("renorm",)),
    "auxiliary_term_left_out": dict(without=("aux",)),
}
MUST_ADMIT = ("program", "reference_in_bf16")


def errors(loss, logits, grad_err, want, want_logits):
    return {"loss_err_rel": abs(loss - want) / abs(want),
            "grad_worst_leaf": grad_err[0], "grad_worst_leaf_at": grad_err[1],
            "grad_overall": grad_err[2],
            "rms_over_rms": float(
                np.sqrt(np.mean((logits - want_logits) ** 2))
                / np.sqrt(np.mean(want_logits ** 2))),
            "max_over_max": float(np.abs(logits - want_logits).max()
                                  / np.abs(want_logits).max())}


def refused_by(reading, traffic):
    """The limits of the cell that this reading is over."""
    return [name for name, key, tol in (
        ("loss", "loss_err_rel", traffic["loss_check_tol"]),
        ("logits", "rms_over_rms", traffic["logit_check_tol"]),
        ("gradient", "grad_worst_leaf", traffic["grad_check_tol"]))
        if reading[key] > tol]


def readings(config, traffic, seeds, devices):
    """Every seed's readings (printed as they come), and by name the
    limits that refused each on each seed."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import make_train_step, transformer_forward
    from horovod_tpu.parallel import build_mesh

    cfg = harness.model_config(config)
    sizes = ref.sizes_of(config)
    seq, rows, last = (traffic["seq"], traffic["rows_per_chip"],
                       traffic["logit_check_last"])
    mesh = build_mesh(devices=devices, **traffic["mesh_by_chips"]["1"])
    init_state, step, _ = make_train_step(cfg, mesh)
    forward = jax.jit(lambda p, t: transformer_forward(p, t, cfg)[0, -last:])
    verdicts = {}
    for seed in seeds:
        state = init_state(jax.random.PRNGKey(seed % 2 ** 32))
        row = np.random.default_rng([seed, 0]).integers(
            0, cfg.vocab_size, seq + 1, dtype=np.int32)

        def reference(want_grad=None, **how):
            if "store" in how:
                how["store"] = getattr(jnp, how["store"])
            terms = ref.loss_terms(state["params"], row[None], sizes, **how)
            grads = ref.gradient_by_layer(state["params"], row[None], sizes,
                                          **how)
            return (float(terms["loss"]),
                    np.asarray(terms["logits"][0, -last:]),
                    {path: np.asarray(g) for path, g in grads}
                    if want_grad is None else
                    worst_leaf((p, g, want_grad[p]) for p, g in grads))

        want, want_logits, want_grad = reference()
        read = {name: errors(*reference(want_grad, **dict(how)), want,
                             want_logits)
                for name, how in CONTROLS.items()}
        got_logits = np.asarray(forward(state["params"], row[None, :-1])
                                .astype(jnp.float32))
        state, loss = step(state, {"tokens": jnp.asarray(
            np.tile(row, (rows, 1)))})
        (first_moment,) = [s.mu for s in state["opt"] if hasattr(s, "mu")]
        read["program"] = errors(
            float(loss), got_logits, worst_leaf(
                (p, leaf_at(first_moment, p).astype(jnp.float32)
                 / (1 - _ADAMW_B1), want_grad.pop(p))
                for p in list(want_grad)), want, want_logits)
        harness.say(seed=seed, reference_loss=want, **read)
        for name, reading in read.items():
            verdicts.setdefault(name, []).append(
                refused_by(reading, traffic))
        del state
    return verdicts


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-mellum2-ep4-seq8192")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from horovod_tpu.common.compile_cache import use_compile_cache

    use_compile_cache()
    cell, config, traffic = harness.find_cell(args.workload)
    verdicts = readings(config, traffic, args.seeds,
                        harness.require_tpu(cell["chips"]))
    admitted = sorted(n for n, v in verdicts.items() if not any(v))
    harness.say(
        limits={k: traffic[k] for k in ("loss_check_tol", "logit_check_tol",
                                        "grad_check_tol")},
        admitted_on_every_seed=admitted,
        refused={n: v for n, v in verdicts.items() if all(v)},
        refused_on_some_seeds_only={
            n: v for n, v in verdicts.items() if any(v) and not all(v)},
        as_it_should_be=(set(admitted) == set(MUST_ADMIT)
                         and all(all(v) for n, v in verdicts.items()
                                 if n not in MUST_ADMIT)))


if __name__ == "__main__":
    main()
