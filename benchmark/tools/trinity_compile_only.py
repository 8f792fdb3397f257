"""Compile-only rehearsal of the two-cache serve programs for the v5e,
with no chip attached (``compile_only.py serve`` knows one pool):

    JAX_PLATFORMS=cpu python -m benchmark.tools.trinity_compile_only \
        --config trinity-large-ep8-5l --traffic mixed-backlog-decode

Prints, for the weights' initialisation and for every program the cell
warms up, ``memory_analysis()`` and the instructions of the optimised
HLO whose result is as large as a cache or as one layer's experts (a
copy of either is what a step must not make). Nothing runs.
"""

from __future__ import annotations

import argparse
import collections
import re
import time

from benchmark.tools import compile_only as base  # sets the backend up

import jax
import jax.numpy as jnp

from benchmark import harness


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="trinity-large-ep8-5l")
    ap.add_argument("--traffic", default="mixed-backlog-decode")
    ap.add_argument("--init", type=int, default=1)
    ap.add_argument("--only", default=None, help="one program's name")
    ap.add_argument("--dump", default=None,
                    help="directory for the optimised HLO texts")
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve import decode as decode_lib
    from horovod_tpu.serve.kv_cache import init_kv_cache, ring_width

    from benchmark.generators import serve_common

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    harness.say(compiled_for=topo.devices[0].device_kind, ran="nothing")
    cfg = harness.model_config(harness.load_json(
        "configs", args.config + ".json"))
    scfg = serve_common.serve_config(harness.load_json(
        "traffic", args.traffic + ".json"))
    bs = scfg.block_size
    width = -(-(-(-scfg.max_prompt // bs) * bs + scfg.max_new_tokens) // bs)
    n_blocks = scfg.max_batch * width + 1
    ring = ring_width(cfg.attn_window, scfg.prefill_chunk, bs)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    init = jax.jit(lambda key: init_transformer(cfg, key))
    params = on_chip(jax.eval_shape(init, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_kv_cache(
        cfg, n_blocks, bs, n_slots=scfg.max_batch, ring=ring).k)
    kv = on_chip(cache)
    size = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                         for x in jax.tree.leaves(t))
    harness.say(table_width=width, n_blocks=n_blocks, ring=ring,
                weights_gb=round(size(params) / base.GB, 3),
                pool_gb=round(2 * size(kv[0]) / base.GB, 3),
                rings_gb=round(2 * size(kv[1]) / base.GB, 3))
    if args.init:
        t0 = time.perf_counter()
        compiled = init.lower(jax.ShapeDtypeStruct(
            (2,), jnp.uint32, sharding=one)).compile()
        harness.say(program="init_transformer",
                    compile_s=round(time.perf_counter() - t0, 1),
                    **base._mem(compiled))

    def shape_of(s):
        return "bf16[%s]" % ",".join(map(str, s.shape))

    large = {shape_of(kv[0]): "pool", shape_of(kv[1]): "rings",
             shape_of(params["layers"][0]["moe"]["w_gate"]):
                 "one layer's experts"}
    prefill, resume, decode, _, _ = decode_lib.make_serve_fns(
        cfg, None, block_size=bs, table_width=width, ring=ring)
    programs = [("decode", decode, (i32(b), i32(b), (i32(b, width), i32(b))))
                for b in scfg.batch_buckets]
    programs += [("prefill_resume", resume,
                  (i32(t), i32(), i32(), (i32(width), i32())))
                 for t in scfg.prefill_buckets]
    programs.append(("prefill", prefill, (
        i32(max(scfg.prefill_buckets)), i32(), (i32(width), i32()))))
    for name, fn, a in programs:
        if args.only and name != args.only:
            continue
        t0 = time.perf_counter()
        compiled = fn.lower(params, kv, kv, *a).compile()
        if args.dump:
            import os
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(
                    args.dump, f"{name}-{a[0].shape[0]}.hlo"), "w") as f:
                f.write(compiled.as_text())
        ops = collections.Counter()
        for result, opcode in re.findall(r"= (\S+?)\{\S* ([\w\-]+)\(",
                                         compiled.as_text()):
            if result in large and opcode not in (
                    "parameter", "get-tuple-element", "bitcast"):
                ops[f"{large[result]} {opcode}"] += 1
        harness.say(program=name, shape=list(a[0].shape),
                    compile_s=round(time.perf_counter() - t0, 1),
                    large_results=ops, **base._mem(compiled),
                    tpu_custom_call=compiled.as_text().count(
                        "tpu_custom_call"))


if __name__ == "__main__":
    main()
