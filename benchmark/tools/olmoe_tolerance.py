"""The readings behind the two limits of ``traffic/pretrain-moe-seq4096.json``
(``loss_check_tol``, ``logit_check_tol``), taken on the chip:

    python -m benchmark.tools.olmoe_tolerance --seeds 2147483651 2147483652

For each seed, on the cell's check row at the published widths: what the
bf16 program gives against the float32 reference (first-step loss; the
logits of the last positions, largest and root-mean-square error); what
the REFERENCE gives when its weights and the residual stream between
layers are stored in the nearest precision below the configuration's
bf16, ``float8_e4m3fn``, which the limits have to refuse; and how many
of a layer's 4096 x 8 (token, choice) pairs change when the reference
stores the same values in bf16, the program's precision: the near-ties.
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmark import harness, reference_olmoe as ref


def _stored_as(tree, dtype):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a: a.astype(dtype).astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def _reference(params, row, sizes, store=None):
    """``(loss, logits of the last positions' row, chosen per layer)``
    with weights and the residual stream stored as ``store``."""
    import jax
    import jax.numpy as jnp

    def stored(tree):       # one layer at a time: a float32 copy of all
        return tree if store is None else _stored_as(tree, store)  # is 6 GB

    chosen = []
    with jax.default_matmul_precision("highest"):
        x = stored(params["embed"][jnp.asarray(row[None, :-1])]).astype(
            ref.F32)
        balance = z = 0.0
        for i in range(sizes["n_layers"]):
            lp = stored(jax.tree.map(lambda a: a[i], params["layers"]))
            x, b_i, z_i, c_i = ref._layer(x, lp, sizes)
            x = stored(x)
            balance, z = balance + b_i, z + z_i
            chosen.append(np.sort(np.asarray(c_i), axis=-1))
        lg = ref._head(x, stored(params["final_norm"]),
                       stored(params["lm_head"]), sizes["norm_eps"])[0]
    logp = jax.nn.log_softmax(lg, axis=-1)
    ce = -jnp.take_along_axis(logp, jnp.asarray(row[1:, None]), -1).mean()
    loss = ce + sizes["aux_coef"] * balance + sizes["z_coef"] * z
    return float(loss), np.asarray(lg), chosen


def _errors(got, want):
    return {"max_over_max": float(np.abs(got - want).max()
                                  / np.abs(want).max()),
            "rms_over_rms": float(np.sqrt(np.mean((got - want) ** 2))
                                  / np.sqrt(np.mean(want ** 2)))}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-olmoe-1b-7b-seq4096")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from horovod_tpu.common.compile_cache import use_compile_cache
    from horovod_tpu.models import make_train_step, transformer_forward
    from horovod_tpu.parallel import build_mesh

    use_compile_cache()
    cell, config, traffic = harness.find_cell(args.workload)
    devices = harness.require_tpu(cell["chips"])
    cfg = harness.model_config(config)
    sizes = ref.sizes_of(config)
    seq, rows, last = (traffic["seq"], traffic["rows_per_chip"],
                       traffic["logit_check_last"])
    mesh = build_mesh(devices=devices, **traffic["mesh_by_chips"]["1"])
    init_state, step, _ = make_train_step(cfg, mesh)
    forward = jax.jit(lambda p, t: transformer_forward(p, t, cfg)[0, -last:])
    for seed in args.seeds:
        state = init_state(jax.random.PRNGKey(seed % 2 ** 32))
        row = np.random.default_rng([seed, 0]).integers(
            0, cfg.vocab_size, seq + 1, dtype=np.int32)
        want, want_lg, chose = _reference(state["params"], row, sizes)
        as_bf16 = _reference(state["params"], row, sizes, jnp.bfloat16)
        as_fp8 = _reference(state["params"], row, sizes, jnp.float8_e4m3fn)
        got_lg = np.asarray(forward(state["params"], row[None, :-1])
                            .astype(jnp.float32))
        state, loss = step(state, {"tokens": jnp.asarray(
            np.tile(row, (rows, 1)))})
        harness.say(
            seed=seed, reference_loss=want,
            program={"loss_err_rel": abs(float(loss) - want) / want,
                     **_errors(got_lg, want_lg[-last:])},
            reference_in_bf16={"loss_err_rel": abs(as_bf16[0] - want) / want,
                               **_errors(as_bf16[1][-last:],
                                         want_lg[-last:])},
            reference_in_fp8={"loss_err_rel": abs(as_fp8[0] - want) / want,
                              **_errors(as_fp8[1][-last:], want_lg[-last:])},
            choices_changed_in_bf16_by_layer=[
                int((a[:, :, None] != b[:, None, :]).all(-1).sum())
                for a, b in zip(chose, as_bf16[2])],
            choices_a_layer=int(chose[0].size))
        del state


if __name__ == "__main__":
    main()
