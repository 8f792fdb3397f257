"""Decoder-LM pretraining on the in-jit SPMD tier — the idiomatic
TPU path: ONE process drives the whole device mesh, parallelism is
declared as mesh axes, and XLA inserts every collective.

This is the tier the eager examples point at for performance; it has
no reference analog (the reference is process-per-rank only, this is
the TPU-first redesign). Shows: mesh construction (dp/fsdp/tp/sp/pp),
``make_train_step`` (scan-over-layers Llama-family model, remat) or
the pipelined factories (``--pp N --pp-schedule gpipe|1f1b``),
synthetic token stream, loss logging, and a final-checkpoint save via
``orbax`` when available. The factory pins the layout of params and
optimizer state (``param_specs``) on both the init and the step, so the
state is born sharded however the init is called.

Run (the axis sizes must multiply to the device count; ``--dp``
defaults to "all the rest"):
  python examples/lm_pretrain.py --steps 20 --tp 2
CPU smoke (8 virtual devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/lm_pretrain.py --platform cpu --steps 2 --tiny
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--dp", type=int, default=-1)
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (composes with dp/fsdp/tp)")
    ap.add_argument("--pp-schedule", default="gpipe",
                    choices=["gpipe", "1f1b"],
                    help="gpipe: AD-replayed; 1f1b: interleaved "
                         "backward, O(pp) activation residency")
    ap.add_argument("--n-micro", type=int, default=2,
                    help="microbatches per step when --pp > 1")
    ap.add_argument("--moe", action="store_true",
                    help="mixture-of-experts FFN (8 experts, top-2, "
                         "GShard capacity routing); with --ep > 1 the "
                         "dispatch runs as the quantized-alltoall "
                         "shard_map island (docs/perf_tuning.md)")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel axis size (-1 = all remaining "
                         "devices); requires --moe")
    ap.add_argument("--moe-compression", default="int8",
                    choices=["none", "bf16", "int8"],
                    help="island dispatch codec (none = bitwise the "
                         "GSPMD einsum path)")
    ap.add_argument("--tiny", action="store_true",
                    help="2-layer d=64 model (CI smoke)")
    ap.add_argument("--platform", default=None, choices=[None, "cpu", "tpu"])
    ap.add_argument("--out", default=None,
                    help="orbax checkpoint dir (optional)")
    args = ap.parse_args()

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.common.compile_cache import use_compile_cache
    from horovod_tpu.models import TransformerConfig, make_train_step
    from horovod_tpu.parallel import (build_mesh, make_pp_train_step,
                                      make_pp_train_step_1f1b)

    use_compile_cache()
    if args.ep != 1 and not args.moe:
        ap.error("--ep needs --moe (the axis only shards experts)")
    mesh = build_mesh(dp=args.dp, fsdp=args.fsdp, tp=args.tp, sp=args.sp,
                      pp=args.pp, ep=args.ep)
    # MoE: 8 experts, top-2; with ep > 1 the dispatch/combine hops run
    # as the quantized-alltoall island (make_train_step builds it from
    # these cfg fields — codec "none" routes back to the exact GSPMD
    # einsum path by construction).
    ep_size = mesh.shape.get("ep", 1)
    moe_kw = dict(n_experts=8, moe_top_k=2,
                  moe_dispatch="island" if ep_size > 1 else None,
                  moe_compression=args.moe_compression
                  if ep_size > 1 else None) if args.moe else {}
    if args.tiny:
        cfg = TransformerConfig.tiny(max_seq=args.seq, **moe_kw)
    else:
        cfg = TransformerConfig(
            vocab_size=8192, d_model=512, n_layers=4, n_heads=8,
            n_kv_heads=8, d_ff=1376, max_seq=args.seq,
            dtype=jnp.bfloat16,
            sp_attention="ring" if args.sp > 1 else "local", **moe_kw)

    if args.pp > 1:
        factory = (make_pp_train_step_1f1b
                   if args.pp_schedule == "1f1b" else make_pp_train_step)
        init_state, step, _ = factory(cfg, mesh, n_micro=args.n_micro)
    else:
        init_state, step, _ = make_train_step(cfg, mesh)
    state = jax.jit(init_state)(jax.random.PRNGKey(0))
    n_params = sum(int(x.size) for x in jax.tree.leaves(state["params"]))
    print(f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"params={n_params:,}")

    # Synthetic token stream: a fixed random corpus sampled per step
    # (hermetic; swap in a real tokenized dataset loader here).
    data_sharding = NamedSharding(mesh, P(("dp", "fsdp"), None))
    corpus = jax.random.randint(jax.random.PRNGKey(1),
                                (64, args.seq + 1), 0, cfg.vocab_size)

    loss = float("nan")  # --steps 0 still reaches the DONE line
    for i in range(args.steps):
        idx = jax.random.randint(jax.random.PRNGKey(100 + i),
                                 (args.batch,), 0, corpus.shape[0])
        batch = {"tokens": jax.device_put(corpus[idx], data_sharding)}
        state, loss = step(state, batch)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")

    if args.out:
        try:
            import orbax.checkpoint as ocp
            ckptr = ocp.StandardCheckpointer()
            ckptr.save(os.path.abspath(args.out),
                       jax.device_get(state["params"]), force=True)
            ckptr.wait_until_finished()
            print(f"saved params to {args.out}")
        except ImportError:
            print("orbax not installed; skipping checkpoint", file=sys.stderr)

    print(f"DONE loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
