"""Record the small trace that ``benchmark/tests/test_device_calls.py``
checks ``reducers/_calls.py`` against: a tiny engine serving two
requests on the chip under the profiler (two prefills and eleven
decode steps), with the engine's own spans beside it. One decode step
is made to stall: a collection of a large heap inside its dispatch, so
that the run also shows a ``serve:stall`` counted, logged and carrying
its cause, and a ``serve:gc`` annotation over an idle gap.

    chiprun -- python -m benchmark.tools.record_engine_trace chiprun_out/tiny-engine

Copy ``tiny-engine.xplane.pb`` and ``tiny-engine-spans.json`` from there
to ``benchmark/tests/data/``.
"""

import gc
import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

from benchmark.reducers import hvd_xplane_pb2
from horovod_tpu.common.compile_cache import compile_stats, use_compile_cache
from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import ServeConfig, ServeEngine

STALLED_STEP = 3


def main(out_dir: str) -> None:
    assert jax.devices()[0].platform == "tpu", "needs the chip"
    use_compile_cache()
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(
        cfg, params, ServeConfig(max_batch=4, block_size=8, max_prompt=16,
                                 max_new_tokens=16),
        clock=time.perf_counter)
    def serve_two():
        engine.submit([5, 6, 7, 8, 9], 12, trace_id=41)
        engine.submit([1, 2, 3], 5)
        engine.run_until_idle()

    # Untraced: compile both batch buckets, and give the decode step
    # its median.
    serve_two()

    heap = [[i] for i in range(3_000_000)]
    decode, steps = engine._decode_fn, [0]

    def decode_and_stall_once(*args):
        steps[0] += 1
        if steps[0] == STALLED_STEP:
            gc.collect()
        return decode(*args)

    engine._decode_fn = decode_and_stall_once
    compiled = compile_stats()["programs_lowered"]
    shutil.rmtree(out_dir, ignore_errors=True)
    # Without the Python tracer's event a function call (4 MB of them
    # here): the annotations are the host tracer's and stay.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=options)
    serve_two()
    jax.profiler.stop_trace()
    del heap

    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)
    # Kept without the programs' HLO (`/host:metadata`, 0.6 MB), which
    # no reader of the benchmark opens.
    space = hvd_xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    kept = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(kept)
    with open(os.path.join(out_dir, "tiny-engine.xplane.pb"), "wb") as f:
        f.write(space.SerializeToString())
    spans = os.path.join(out_dir, "tiny-engine-spans.json")
    engine.metrics.export_chrome_trace(spans)
    with open(spans) as f:
        events = json.load(f)["traceEvents"]
    print(json.dumps({
        "xplane_bytes": os.path.getsize(path),
        "spans_bytes": os.path.getsize(spans),
        "lowered_while_traced":
            compile_stats()["programs_lowered"] - compiled,
        "stalls_total": engine.metrics.snapshot()["stalls_total"],
        "stalls": [e for e in events if e["name"] == "serve:stall"]}))


if __name__ == "__main__":
    main(sys.argv[1])
