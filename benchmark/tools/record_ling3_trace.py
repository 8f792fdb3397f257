"""Record the small trace that ``benchmark/tests/test_ling3_cell.py``
checks the ``.ling`` metrics' readers against: a tiny engine of a dense
kda layer, a sparse kda layer and a sparse mla layer (heads of 128, a
chip's share of the experts, chunks of 512) serving three requests on
the chip under the profiler, with the engine's own spans and the
generator's ``traced_work`` beside it.

    chiprun -- python -m benchmark.tools.record_ling3_trace chiprun_out/tiny-ling3

Copy ``tiny-ling3.xplane.pb`` and ``tiny-ling3-meas.json`` from there to
``benchmark/tests/data/``. ``MODEL`` is the ``model`` group the test
hands the readers.
"""

import glob
import json
import os
import shutil
import sys
import time

MODEL = {"vocab_size": 512, "d_model": 256, "n_layers": 3, "n_heads": 2,
         "n_kv_heads": 2, "d_head": 128, "d_ff": 128, "d_ff_dense": 512,
         "n_dense_layers": 1, "max_seq": 2048, "rope_theta": 10000.0,
         "norm_eps": 1e-06, "layer_types": ["kda", "kda", "mla"],
         "kda_conv": 4, "kda_decay_floor": -5.0, "mla_kv_rank": 128,
         "mla_rope_dim": 64, "n_experts": 8, "moe_top_k": 2,
         "moe_capacity_factor": None, "moe_norm_topk_prob": True,
         "moe_scoring": "sigmoid", "moe_route_scale": 2.5,
         "moe_shared_expert": True, "moe_experts_held": 4,
         "moe_expert_offset": 2, "moe_n_group": 4, "moe_topk_group": 2}
RUN = {"dtype": "bfloat16", "sp_attention": "local", "remat": False}
ENGINE = {"max_batch": 4, "max_queue": 8, "max_prompt": 1536,
          "max_new_tokens": 16, "block_size": 16, "prefix_caching": False,
          "prefill_chunk": 512, "prefill_buckets": [256, 512],
          "batch_buckets": [4]}
PROMPTS = (1200, 512, 300)


def main(out_dir: str) -> None:
    import jax
    import numpy as np

    from benchmark import harness
    from benchmark.generators import (serve_backlog_hybrid, serve_common)
    from benchmark.tools.record_mellum2_trace import without_programs
    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve import ServeEngine

    harness.require_tpu(1)
    cfg = harness.model_config({"model": MODEL, "run": RUN})
    scfg = serve_common.serve_config({"engine": ENGINE})
    engine = ServeEngine(cfg, init_transformer(cfg, jax.random.PRNGKey(0)),
                         scfg, clock=time.perf_counter)
    rng = np.random.default_rng(0)

    def serve():
        for n in PROMPTS:
            engine.submit(rng.integers(0, cfg.vocab_size, n).tolist(), 12)
        stamps, live = [], []
        while engine.pending:
            engine.step()
            stamps.append(time.perf_counter())
            live.append(engine.metrics.kv_latent_positions_live)
        return stamps, live

    serve()                                   # untraced: compiles
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    harness.OUT_DIR = out_dir
    window = harness.TraceWindow(True, "tiny-ling3", 0.0)
    window.poll(0.0)
    stamps, live = serve()
    window.stop()
    spans = serve_common.engine_spans(engine, "tiny-ling3")
    work = serve_backlog_hybrid.traced_work(window, spans, stamps, live)
    kept = os.path.join(out_dir, "tiny-ling3.xplane.pb")
    with open(kept, "wb") as f:
        f.write(without_programs(window.xplane()))
    lo = window.started_at
    with open(os.path.join(out_dir, "tiny-ling3-meas.json"), "w") as f:
        json.dump({"traced_work": work, "t_open": lo,
                   "t_close": window.stopped_at,
                   "spans": [s for s in spans if s["t0"] >= lo]}, f)
    shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    for stale in glob.glob(os.path.join(out_dir, "spans-*.json")):
        os.remove(stale)
    print(os.path.getsize(kept), work)


if __name__ == "__main__":
    main(sys.argv[1])
