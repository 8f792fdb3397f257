"""On the chip: ms a layer of ``serve/decode.py::mamba_scan`` over one
chunk at the published Jamba2-3B sizes (5120 channels, 16 state rows),
a position at a time (``lax.scan``, by positions a loop iteration)
beside ``lax.associative_scan`` inside blocks (the form PR 47 first
built), and of ``mamba_step`` over 257 slots:

    chiprun -- python tools/mamba_scan_sweep.py

What ``decode._MAMBA_UNROLL`` was chosen from (PERF.md, PR 47)."""
import json
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from horovod_tpu.serve import decode as decode_lib  # noqa: E402

DI, N = 5120, 16


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def blocked(u, step, a, b, c, state, block):
    """``lax.associative_scan`` over ``(decay, drive)`` pairs inside
    blocks of ``block`` positions, the state carried between blocks."""
    B, T, Di = u.shape
    n = T // block

    def blocks(x):
        return jnp.moveaxis(x.reshape(B, n, block, -1), 1, 0)

    def then(first, second):
        return (first[0] * second[0], second[0] * first[1] + second[1])

    def one_block(s, xs):
        u, step, b, c = xs
        decay = jnp.exp(step[:, :, None] * a)
        drive = (step * u)[:, :, None] * b[..., None]
        decay, drive = jax.lax.associative_scan(then, (decay, drive), axis=1)
        states = decay * s[:, None] + drive
        return states[:, -1], jnp.sum(states * c[..., None], axis=2)

    state, y = jax.lax.scan(one_block, state,
                            tuple(map(blocks, (u, step, b, c))))
    return jnp.moveaxis(y, 0, 1).reshape(B, T, Di), state


def main():
    key = jax.random.PRNGKey(0)
    a = -jnp.exp(jax.random.normal(key, (N, DI)))
    for T in (512, 4096):
        ks = jax.random.split(key, 4)
        u = jax.random.normal(ks[0], (1, T, DI))
        step = jax.random.uniform(ks[1], (1, T, DI), minval=1e-3, maxval=0.1)
        b = jax.random.normal(ks[2], (1, T, N))
        c = jax.random.normal(ks[3], (1, T, N))
        s0 = jnp.zeros((1, N, DI))
        row = {"T": T}
        for block in (8, 64):
            if block > T:
                continue
            fn = jax.jit(lambda *xs, block=block: blocked(*xs, block=block))
            row[f"blocks_of_{block}_ms"] = round(
                timed(fn, u, step, a, b, c, s0), 3)
        for unroll in (1, 4, 8, 16, 32):
            fn = jax.jit(lambda *xs, unroll=unroll: decode_lib.mamba_scan(
                *xs, unroll=unroll))
            row[f"unroll_{unroll}_ms"] = round(
                timed(fn, u, step, a, b, c, s0), 3)
        print(json.dumps(row), flush=True)
    S = 257
    ks = jax.random.split(key, 5)
    args = (jax.random.normal(ks[0], (S, DI)),
            jax.random.uniform(ks[1], (S, DI), minval=1e-3, maxval=0.1), a,
            jax.random.normal(ks[2], (S, N)), jax.random.normal(ks[3], (S, N)),
            jax.random.normal(ks[4], (S, N, DI)))
    fn = jax.jit(decode_lib.mamba_step, donate_argnums=(5,))
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(50):
        out = fn(*args[:5], out[1])
    jax.block_until_ready(out)
    ms = 1e3 * (time.perf_counter() - t0) / 50
    print(json.dumps({"mamba_step_257_slots_ms": round(ms, 3),
                      "GBps": round(2 * S * N * DI * 4 / ms / 1e6, 1)}))


if __name__ == "__main__":
    main()
