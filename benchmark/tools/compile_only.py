"""Compile-only rehearsal for the v5e, with no chip attached.

    JAX_PLATFORMS=cpu python -m benchmark.tools.compile_only train \
        --config internlm2-1.8b-12l --chips 1 --rows 1 2 4 --remat full dots
    JAX_PLATFORMS=cpu python -m benchmark.tools.compile_only serve \
        --config mistral-7b-v0.3-16l --traffic batch-prefill

Prints ``memory_analysis()`` of each program compiled for ``v5e:2x2``
(what fits, and so the rows per chip of the training traffic) and which
collectives and kernels the compiler put in. Nothing runs: no time, no
result. See the on-chip-measurement guide, section 2.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import harness  # noqa: E402

# The program picks its on-chip branches (Mosaic, not the Pallas
# interpreter) from jax.default_backend(): compile what a process that
# holds the chip would compile (tests/test_tpu_lowering.py does the same).
jax.default_backend = lambda: "tpu"
GB = 1e9


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"argument_gb": round(m.argument_size_in_bytes / GB, 3),
            "output_gb": round(m.output_size_in_bytes / GB, 3),
            "alias_gb": round(m.alias_size_in_bytes / GB, 3),
            "temp_gb": round(m.temp_size_in_bytes / GB, 3),
            "peak_estimate_gb": round(
                (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes) / GB, 3)}


def _ops(text: str) -> dict:
    return {k: len(re.findall(r"\b" + k + r"(?:-start)?\(", text))
            for k in ("all-reduce", "reduce-scatter", "all-gather",
                      "all-to-all", "collective-permute")} | {
        "tpu_custom_call": text.count("tpu_custom_call")}


def train(args, topo) -> None:
    from horovod_tpu.models import make_train_step
    from horovod_tpu.parallel import build_mesh

    config = harness.load_json("configs", args.config + ".json")
    axes = json.loads(args.mesh)
    devices = topo.devices[:args.chips]
    for remat in args.remat:
        for rows in args.rows:
            over = ({"remat": False} if remat == "off"
                    else {"remat": True, "remat_policy": remat})
            cfg = harness.model_config(config, **over)
            mesh = build_mesh(devices=devices, **axes)
            init_state, step, _ = make_train_step(cfg, mesh)
            state = jax.eval_shape(
                init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))
            batch = {"tokens": jax.ShapeDtypeStruct(
                (rows * args.chips, args.seq + 1), jnp.int32)}
            t0 = time.perf_counter()
            try:
                compiled = step.lower(state, batch).compile()
            except Exception as e:  # what the chip's compiler would raise
                harness.say(config=args.config, chips=args.chips, rows=rows,
                            remat=remat, refused=str(e)[:400])
                continue
            harness.say(config=args.config, chips=args.chips, mesh=axes,
                        rows_per_chip=rows, seq=args.seq, remat=remat,
                        compile_s=round(time.perf_counter() - t0, 1),
                        **_mem(compiled), **_ops(compiled.as_text()))


def serve(args, topo) -> None:
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve import decode as decode_lib

    from benchmark.generators import serve_common

    config = harness.load_json("configs", args.config + ".json")
    traffic = harness.load_json("traffic", args.traffic + ".json")
    cfg = harness.model_config(config)
    scfg = serve_common.serve_config(traffic)
    one = SingleDeviceSharding(topo.devices[0])
    bs = scfg.block_size
    width = -(-(-(-scfg.max_prompt // bs) * bs + scfg.max_new_tokens) // bs)
    n_blocks = scfg.max_batch * width + 1

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
    # init_kv_cache's shape: [layers, blocks, block, kv heads, head dim]
    kv = jax.ShapeDtypeStruct(
        (cfg.n_layers, n_blocks, bs, cfg.n_kv_heads, cfg.head_dim),
        cfg.dtype, sharding=one)
    prefill, _, decode, _, _ = decode_lib.make_serve_fns(
        cfg, None, block_size=bs, table_width=width)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    weights_gb = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(params)) / GB
    cache_gb = 2 * kv.size * kv.dtype.itemsize / GB
    harness.say(config=args.config, traffic=args.traffic,
                table_width=width, n_blocks=n_blocks,
                weights_gb=round(weights_gb, 3), kv_cache_gb=round(cache_gb, 3))
    for b in scfg.batch_buckets:
        t0 = time.perf_counter()
        compiled = decode.lower(params, kv, kv, i32(b), i32(b),
                                i32(b, width)).compile()
        harness.say(program="decode", batch=b,
                    compile_s=round(time.perf_counter() - t0, 1),
                    **_mem(compiled))
    for t in scfg.prefill_buckets:
        t0 = time.perf_counter()
        compiled = prefill.lower(params, kv, kv, i32(t), i32(),
                                 i32(width)).compile()
        harness.say(program="prefill", bucket=t,
                    compile_s=round(time.perf_counter() - t0, 1),
                    **_mem(compiled))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("train", "serve"))
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--mesh", default='{"dp": -1}')
    ap.add_argument("--rows", type=int, nargs="+", default=[1])
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--remat", nargs="+", default=["full"])
    args = ap.parse_args()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    harness.say(compiled_for=topo.devices[0].device_kind, ran="nothing")
    (train if args.what == "train" else serve)(args, topo)


if __name__ == "__main__":
    main()
