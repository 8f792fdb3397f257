"""Compile-only rehearsal, for the v5e and with no chip attached, of the
serve programs of a configuration with sparse-attention and
linear-attention layers (``jamba2_compile_only.py``'s, over a state
whose kinds hold one, two or three arrays):

    JAX_PLATFORMS=cpu python -m benchmark.tools.sala_compile_only

Prints, for every program the cell warms up, the seconds it took to
compile, ``memory_analysis()`` and the instructions of the optimised HLO
whose result is as large as one of the state's arrays (a copy of one is
what a step must not make). Nothing runs.
"""

from __future__ import annotations

import argparse
import time

from benchmark.tools import compile_only as base  # sets the backend up
from benchmark.tools.ling3_compile_only import (cell_shapes, large_results,
                                                shape_of)

import jax
import jax.numpy as jnp

from benchmark import harness


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="minicpm-sala-8l")
    ap.add_argument("--traffic", default="longdoc-backlog")
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

    params = on_chip(jax.eval_shape(
        lambda key: init_transformer(cfg, key), jax.random.PRNGKey(0)))
    kinds = state_kinds(cfg)
    kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(
        init_kv_cache(cfg, n_blocks, bs, n_slots=scfg.max_batch))))
    size = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                         for x in jax.tree.leaves(t))
    harness.say(table_width=width, n_blocks=n_blocks,
                weights_gb=round(size(params) / base.GB, 3),
                state_gb={kind: round(size((k, v)) / base.GB, 3)
                          for kind, k, v in zip(kinds, kc, vc)})
    large = {shape_of(a): f"{kind}[{n}]"
             for kind, k, v in zip(kinds, kc, vc)
             for n, a in enumerate(jax.tree.leaves((k, v)))}
    prefill, resume, decode, _, _ = decode_lib.make_serve_fns(
        cfg, None, block_size=bs, table_width=width)
    programs = [("decode", decode, (i32(b), i32(b), (i32(b, width), i32(b))))
                for b in scfg.batch_buckets]
    for t in scfg.prefill_buckets:
        programs.append(("prefill_resume", resume,
                         (i32(t), i32(), i32(), (i32(width), i32()))))
        programs.append(("prefill", prefill,
                         (i32(t), i32(), (i32(width), i32()))))
    total = 0.0
    for name, fn, a in programs:
        if args.only and name != args.only:
            continue
        t0 = time.perf_counter()
        compiled = fn.lower(params, kc, vc, *a).compile()
        took = time.perf_counter() - t0
        total += took
        text = compiled.as_text()
        if args.dump:
            import os
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(
                    args.dump, f"{name}-{a[0].shape[0]}.hlo"), "w") as f:
                f.write(text)
        harness.say(program=name, shape=list(a[0].shape),
                    compile_s=round(took, 1),
                    large_results=large_results(text, large),
                    **base._mem(compiled))
    harness.say(compile_s_in_all=round(total, 1))


if __name__ == "__main__":
    main()
