"""The flash kernels at the window cell's shape, on the chip:

    chiprun -- python -m benchmark.tools.flash_window_sweep

Times (ms a call, median of ``--reps`` after a warm-up) of the forward
and of forward + backward at ``[1, 8192, 32/4 heads, 128]`` bf16 with
window 1024 for each candidate tile, and without a window; then the
gradients of both against float32 ``HIGHEST`` dense attention computed
in query blocks (relative rms / largest error of dq, dk, dv, in the
form of PERF.md's table of PR 33). How ``_default_blocks`` and
``_bwd_blocks`` came by their windowed values.
"""

from __future__ import annotations

import argparse
import statistics
import time

import numpy as np

from benchmark import harness


def _dense(q, k, v, window, block=512):
    """float32 HIGHEST attention over [B, T, H, D] / [B, T, Hkv, D], a
    block of queries at a time; differentiable."""
    import jax
    import jax.numpy as jnp

    b, t, h, d = q.shape
    g = h // k.shape[2]
    kf = jnp.repeat(k.astype(jnp.float32), g, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), g, axis=2)

    @jax.checkpoint         # the backward keeps no block's scores
    def one(qb, kf, vf, first):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kf) * d ** -0.5
        i = first + jnp.arange(block)[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if window is not None:
            seen &= j > i - window
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vf)

    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [one(q[:, first:first + block].astype(jnp.float32), kf, vf,
                 first) for first in range(0, t, block)], axis=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    harness.require_tpu(1)
    t, w = args.seq, args.window
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, t, 32, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, t, 4, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, t, 4, 128), jnp.bfloat16)
    do = jax.random.normal(ks[3], (1, t, 32, 128), jnp.bfloat16)

    def timed(f, *xs):
        jax.block_until_ready(f(*xs))
        out = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*xs))
            out.append(1e3 * (time.perf_counter() - t0))
        return round(statistics.median(out), 4)

    def both(**kw):
        fwd = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, **kw))
        grad = jax.jit(jax.grad(
            lambda q, k, v: (fa.flash_attention(q, k, v, **kw)
                             .astype(jnp.float32)
                             * do.astype(jnp.float32)).sum(), (0, 1, 2)))
        return fwd, grad

    for kw in ([dict()] + [dict(window=w, block_q=bq, block_k=bk)
                           for bq, bk in ((512, 1024), (512, 512),
                                          (1024, 1024), (1024, 512),
                                          (256, 512), (256, 256))]):
        try:
            fwd, grad = both(**kw)
            harness.say(case=kw, fwd_ms=timed(fwd, q, k, v),
                        fwd_bwd_ms=timed(grad, q, k, v))
        except Exception as e:      # a tile Mosaic refuses
            harness.say(case=kw, refused=str(e)[:300])
    blocks = fa._bwd_blocks
    for sub in (128, 256, 512):
        fa._bwd_blocks = lambda *shape, sub=sub: (blocks(*shape)[0], sub)
        _, grad = both(window=w)
        harness.say(window=w, bwd_block=blocks(t, 128, 2)[0], bwd_sub=sub,
                    fwd_bwd_ms=timed(grad, q, k, v))
    fa._bwd_blocks = blocks

    for window in (w, None):
        _, grad = both(window=window)
        got = grad(q, k, v)
        want = jax.jit(jax.grad(
            lambda q, k, v: (_dense(q, k, v, window)
                             * do.astype(jnp.float32)).sum(), (0, 1, 2)))(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32))
        report = {}
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            report[name] = {
                "rel_rms_pct": float(100 * np.sqrt(np.mean((a - b) ** 2)
                                                   / np.mean(b ** 2))),
                "largest": float(np.abs(a - b).max())}
        harness.say(gradients_against_float32_highest=report, window=window,
                    shape=[32, t, 128], q_per_kv=8)


if __name__ == "__main__":
    main()
