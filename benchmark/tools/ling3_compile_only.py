"""Compile-only rehearsal, for the v5e and with no chip attached, of the
serve programs of a configuration whose state is kept by kind of layer
(``trinity_compile_only.py`` reads a pair of caches by place):

    JAX_PLATFORMS=cpu python -m benchmark.tools.ling3_compile_only

Prints, for the weights' initialisation and for every program the cell
warms up, ``memory_analysis()`` and the instructions of the optimised
HLO whose result is as large as one of the state's arrays or as one
layer's experts (a copy of either is what a step must not make).
Nothing runs.
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


def cell_shapes(config: str, traffic: str):
    """(cfg, scfg, table width, n_blocks) of a cell's files."""
    from benchmark.generators import serve_common

    cfg = harness.model_config(harness.load_json("configs", config + ".json"))
    scfg = serve_common.serve_config(harness.load_json(
        "traffic", traffic + ".json"))
    bs = scfg.block_size
    width = -(-(-(-scfg.max_prompt // bs) * bs + scfg.max_new_tokens) // bs)
    return cfg, scfg, width, scfg.max_batch * width + 1


def large_results(text: str, large) -> collections.Counter:
    """Instructions of an optimised HLO whose result has one of the
    shapes ``large`` (``{"f32[..]": name}``), parameters and views
    apart."""
    ops = collections.Counter()
    for result, opcode in re.findall(r"= (\S+?)\{\S* ([\w\-]+)\(", text):
        if result in large and opcode not in (
                "parameter", "get-tuple-element", "bitcast"):
            ops[f"{large[result]} {opcode}"] += 1
    return ops


def shape_of(s) -> str:
    return "%s[%s]" % ({"bfloat16": "bf16", "float32": "f32"}[str(s.dtype)],
                       ",".join(map(str, s.shape)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="ling-3.0-flash-ep4-7l")
    ap.add_argument("--traffic", default="reasoning-backlog-longtail")
    ap.add_argument("--init", type=int, default=1)
    ap.add_argument("--only", default=None, help="one program's name")
    ap.add_argument("--dump", default=None,
                    help="directory for the optimised HLO texts")
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve import decode as decode_lib
    from horovod_tpu.serve.kv_cache import init_kv_cache, state_kinds

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    harness.say(compiled_for=topo.devices[0].device_kind, ran="nothing")
    cfg, scfg, width, n_blocks = cell_shapes(args.config, args.traffic)
    bs = scfg.block_size

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    init = jax.jit(lambda key: init_transformer(cfg, key))
    params = on_chip(jax.eval_shape(init, jax.random.PRNGKey(0)))
    kinds = state_kinds(cfg)
    kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(
        init_kv_cache(cfg, n_blocks, bs, n_slots=scfg.max_batch))))
    size = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                         for x in jax.tree.leaves(t))
    harness.say(table_width=width, n_blocks=n_blocks,
                weights_gb=round(size(params) / base.GB, 3),
                state_gb={kind: round(size((k, v)) / base.GB, 3)
                          for kind, k, v in zip(kinds, kc, vc)})
    if args.init:
        t0 = time.perf_counter()
        compiled = init.lower(jax.ShapeDtypeStruct(
            (2,), jnp.uint32, sharding=one)).compile()
        harness.say(program="init_transformer",
                    compile_s=round(time.perf_counter() - t0, 1),
                    **base._mem(compiled))
    large = {shape_of(a): f"{kind}[{n}]"
             for kind, k, v in zip(kinds, kc, vc)
             for n, a in enumerate((k, v)) if a is not None}
    large[shape_of(params["layers"][0]["moe"]["w_gate"])] = \
        "one layer's experts"
    prefill, resume, decode, _, _ = decode_lib.make_serve_fns(
        cfg, None, block_size=bs, table_width=width)
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
        compiled = fn.lower(params, kc, vc, *a).compile()
        text = compiled.as_text()
        if args.dump:
            import os
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(
                    args.dump, f"{name}-{a[0].shape[0]}.hlo"), "w") as f:
                f.write(text)
        harness.say(program=name, shape=list(a[0].shape),
                    compile_s=round(time.perf_counter() - t0, 1),
                    large_results=large_results(text, large),
                    **base._mem(compiled))


if __name__ == "__main__":
    main()
