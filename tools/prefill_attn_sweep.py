"""Prompt attention at the serving cells' prefill buckets, on the chip:

    chiprun -- python tools/prefill_attn_sweep.py

For each bucket ``T``, ms a layer (16 calls chained in one program, the
median of ``--reps`` runs) of ``[1, T, 32, 128]`` bf16 queries over
``[1, T, 8, 128]`` keys and values (``mistral-7b-v0.3-16l``'s heads):
the dense form (K and V repeated across the group, float32
``[32, T, T]`` scores: all ``serve/decode.py::_attend_prompt`` had
before ISSUE 35), ``_attend_prompt`` as it is, and the flash forward at
each candidate tile; and the largest difference of ``_attend_prompt``
from the dense form over float32 inputs at ``HIGHEST``, relative to its
largest magnitude. How ``_attend_prompt`` came by ``_DENSE_PROMPT`` and
``_prompt_block``.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from horovod_tpu.ops.flash_attention import flash_attention  # noqa: E402
from horovod_tpu.parallel.ring_attention import local_attention  # noqa: E402
from horovod_tpu.serve.decode import _attend_prompt  # noqa: E402

LAYERS = 16


def dense(q, k, v):
    rep = q.shape[2] // k.shape[2]
    return local_attention(q, jnp.repeat(k, rep, axis=2),
                           jnp.repeat(v, rep, axis=2),
                           causal=True).reshape(*q.shape[:2], -1)


def ms_a_layer(attend, q, k, v, reps):
    """``attend`` [1, T, H, Dh] -> [1, T, H * Dh], a layer's output the
    next one's queries, so that the calls run one after another."""
    @jax.jit
    def chain(q, k, v):
        return lax.scan(lambda q, _: (attend(q, k, v).reshape(q.shape),
                                      None), q, None, length=LAYERS)[0]
    jax.block_until_ready(chain(q, k, v))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(q, k, v))
        times.append(time.perf_counter() - t0)
    return round(1e3 * statistics.median(times) / LAYERS, 4)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=[128, 256, 512, 1024, 1280, 1536, 1792, 2048])
    ap.add_argument("--blocks", type=int, nargs="+",
                    default=[128, 256, 512, 640, 768, 896, 1024])
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for t in args.buckets:
        keys = jax.random.split(jax.random.PRNGKey(t), 3)
        q, k, v = (
            (jax.random.normal(key, (1, t, h, 128)) * 0.5).astype(jnp.bfloat16)
            for key, h in zip(keys, (32, 8, 8)))
        row = {"T": t, "dense": ms_a_layer(dense, q, k, v, args.reps),
               "attend_prompt": ms_a_layer(_attend_prompt, q, k, v,
                                           args.reps)}
        for b in args.blocks:
            if b <= -(-t // 128) * 128:
                row[f"flash_{b}"] = ms_a_layer(
                    lambda q, k, v: flash_attention(
                        q, k, v, causal=True, block_q=b,
                        block_k=b).reshape(*q.shape[:2], -1),
                    q, k, v, args.reps)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(dense)(*(x.astype(jnp.float32) for x in (q, k, v)))
        got = jax.jit(_attend_prompt)(q, k, v).astype(jnp.float32)
        row["rel_err"] = float(jnp.max(jnp.abs(got - want))
                               / jnp.max(jnp.abs(want)))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
