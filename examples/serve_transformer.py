"""Continuous-batching inference serving on the sharded transformer.

Shows the serve tier end to end: build (or shard) a decoder LM,
stand up a :class:`horovod_tpu.serve.ServeEngine`, submit a burst of
mixed-length requests with per-request deadlines, drive the scheduler,
and read back tokens + the throughput/latency metrics surface.

CPU smoke (no accelerator needed):
  JAX_PLATFORMS=cpu python examples/serve_transformer.py --tiny

Tensor-parallel over 8 virtual devices:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python examples/serve_transformer.py --tiny --tp 2
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel mesh axis for serving")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable content-addressed KV block reuse")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="max prefill tokens per engine step (long "
                         "prompts stream in chunks between decode "
                         "iterations)")
    ap.add_argument("--system-prompt", type=int, default=0,
                    help="prepend this many shared tokens to every "
                         "request (shows the prefix cache working)")
    ap.add_argument("--tiny", action="store_true",
                    help="2-layer d=64 model (CPU smoke)")
    ap.add_argument("--platform", default=None, choices=[None, "cpu", "tpu"])
    ap.add_argument("--trace-out", default=None,
                    help="write a chrome-tracing timeline of the "
                         "scheduler steps")
    args = ap.parse_args()

    import jax
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.common.compile_cache import use_compile_cache
    from horovod_tpu.models import TransformerConfig, init_transformer
    from horovod_tpu.serve import ServeConfig, ServeEngine, make_trace

    use_compile_cache()

    cfg = (TransformerConfig.tiny(dtype=jnp.float32, remat=False)
           if args.tiny else
           TransformerConfig(vocab_size=8192, d_model=512, n_layers=4,
                             n_heads=8, n_kv_heads=4, d_ff=1376,
                             max_seq=1024, dtype=jnp.bfloat16,
                             remat=False))
    mesh = None
    if args.tp > 1:
        from horovod_tpu.parallel import build_mesh
        mesh = build_mesh(dp=-1, tp=args.tp)
    params = init_transformer(cfg, jax.random.PRNGKey(0), mesh)

    max_prompt = min(32 + args.system_prompt,
                     cfg.max_seq - args.max_new - 1)
    if args.system_prompt >= max_prompt:
        ap.error(f"--system-prompt {args.system_prompt} leaves no room "
                 f"for a request within this model's budget "
                 f"(max prompt {max_prompt} at --max-new {args.max_new})")
    engine = ServeEngine(
        cfg, params,
        ServeConfig(max_batch=args.max_batch, block_size=args.block_size,
                    max_prompt=max_prompt, max_new_tokens=args.max_new,
                    max_queue=max(args.requests, 8),
                    prefix_caching=not args.no_prefix_cache,
                    prefill_chunk=args.prefill_chunk),
        mesh=mesh)

    trace = make_trace(args.requests, seed=0,
                       max_prompt=max_prompt - args.system_prompt,
                       max_new=args.max_new, vocab=cfg.vocab_size)
    if args.system_prompt:
        sys_tokens = np.random.RandomState(7).randint(
            1, cfg.vocab_size, size=args.system_prompt).tolist()
        trace = [(sys_tokens + p, n) for p, n in trace]
    import time
    rids = []
    for prompt, max_new in trace:
        # A deadline 30s out: comfortably met here, but shows the knob
        # (stale requests get a 503-style "expired" result instead of
        # burning prefill FLOPs).
        rids.append(engine.submit(prompt, max_new,
                                  deadline=time.perf_counter() + 30.0))

    while engine.pending:
        engine.step()

    for rid in rids[:4]:
        res = engine.result(rid)
        lat = res.first_token_latency_s
        lat = "n/a" if lat is None else f"{lat * 1e3:.1f}ms"
        print(f"request {rid}: {res.status} "
              f"prompt_len={res.n_prompt} -> {len(res.tokens)} tokens "
              f"first_token={lat} "
              f"tokens={res.tokens[:8]}{'...' if len(res.tokens) > 8 else ''}")
    print(f"... and {len(rids) - 4} more")

    snap = engine.metrics.snapshot()
    print("serve metrics:",
          {k: snap[k] for k in ("tokens_per_sec", "batch_occupancy",
                                "p50_first_token_ms", "p99_first_token_ms",
                                "p50_per_token_ms", "p99_per_token_ms",
                                "requests_finished")})
    print("kv pool:",
          {k: snap[k] for k in ("kv_blocks_in_use", "kv_blocks_cached",
                                "kv_blocks_high_water",
                                "prefix_cache_hit_rate",
                                "prefix_block_hits",
                                "prefix_block_evictions")})
    if args.trace_out:
        engine.metrics.export_chrome_trace(args.trace_out)
        print(f"chrome trace written to {args.trace_out}")


if __name__ == "__main__":
    main()
