"""What of a held expert block's time follows the rows of its operands,
on the chip:

    chiprun -- python tools/held_experts_sweep.py

At the trained share's shapes (``mellum2-12b-ep4-8l``: ``D`` 2304, ``F``
896, 16 held experts, bf16, ``N·K`` = 65 536 pairs of which ``--held``
lie in a group), for the row operand cut to each ``R`` of ``--rows``:
ms a call (eight calls in one program, each with matrices or indices of
its own and every result a result of the program; the median of
``--reps`` runs) of

* ``up`` / ``down``: ``lax.ragged_dot`` of ``[R, D] x [16, D, F]`` and
  of ``[R, F] x [16, F, D]`` with the same ``sizes``, and their
  transposes as ``jax.vjp`` makes them (``*_dx``: the rows' cotangent,
  ``*_dw``: the matrices');
* ``swiglu``: ``silu(g) * u`` in float32 on ``[R, F]``;
* ``gather``: ``x[idx]`` to ``[R, D]`` from the ``[8192, D]`` tokens;
* ``unsort``: the ``N·K`` slots read out of an ``[R, D]`` result
  (``y[inverse]``: the combine forward, ``g[inverse]`` backward);

and once, whatever ``R``: the stable sort of the 65 536 pairs, the
second ``argsort`` that inverts it and the scatter of ``arange`` that
would do (ROADMAP A10). How ``moe._held_experts`` came by its bound.
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
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

D, F, HELD, TOKENS, K, CALLS = 2304, 896, 16, 8192, 8, 8
PAIRS = TOKENS * K


def ms_a_call(fn, shared, variants, reps):
    """``fn(*shared, variant)`` once a variant in one program, all the
    results kept: nothing is summed or sliced after a call, so no pass
    over its result is timed with it and none of it is left out."""
    chain = jax.jit(lambda shared, variants: [fn(*shared, v)
                                              for v in variants])
    jax.block_until_ready(chain(shared, variants))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(chain(shared, variants))
        times.append(time.perf_counter() - t0)
    return round(1e3 * statistics.median(times) / len(variants), 4)


def normal(key, shape, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(jnp.bfloat16)


def grouped(rows, sizes, w):
    return lax.ragged_dot(rows, w, sizes)


def transpose_of(argnum):
    """The cotangent of ``ragged_dot``'s operand ``argnum`` alone."""
    def fn(rows, cot, sizes, w):
        return jax.vjp(lambda r, m: grouped(r, sizes, m),
                       rows, w)[1](cot)[argnum]
    return fn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[65536, 32768, 24576, 17920])
    ap.add_argument("--held", type=int, default=17408,
                    help="pairs in a group, drawn over the 16 experts")
    ap.add_argument("--sizes", type=int, nargs=HELD, default=None,
                    help="the 16 group sizes themselves, a layer's counts")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    rng = np.random.default_rng(0)
    sizes = np.asarray(args.sizes if args.sizes else rng.multinomial(
        args.held, rng.dirichlet(np.full(HELD, 2.0))), np.int32)
    held = int(sizes.sum())
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "sizes": sizes.tolist(), "held_rows": held}),
          flush=True)
    sz = jnp.asarray(sizes)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 128))
    w_up = [normal(next(keys), (HELD, D, F), D ** -0.5) for _ in range(CALLS)]
    w_down = [normal(next(keys), (HELD, F, D), F ** -0.5)
              for _ in range(CALLS)]
    tokens = normal(next(keys), (TOKENS, D))

    # the dispatch's own order: held pairs first, by expert
    local = np.full(PAIRS, HELD, np.int32)
    local[rng.permutation(PAIRS)[:held]] = np.repeat(np.arange(HELD), sizes)
    locals_ = [jnp.asarray(np.roll(local, i)) for i in range(CALLS)]
    orders = [jnp.argsort(v, stable=True) for v in locals_]
    row = {"sort": ms_a_call(lambda v: jnp.argsort(v, stable=True), (),
                             locals_, args.reps),
           "inverse_by_argsort": ms_a_call(jnp.argsort, (), orders,
                                           args.reps),
           "inverse_by_scatter": ms_a_call(
               lambda o: jnp.zeros_like(o).at[o].set(
                   jnp.arange(o.size, dtype=o.dtype)), (), orders,
               args.reps)}
    print(json.dumps(row), flush=True)

    for r in args.rows:
        if r < held:
            continue
        wide, narrow = normal(next(keys), (r, D)), normal(next(keys), (r, F))
        idx = [o[:r] // K for o in orders]
        inverse = [jnp.minimum(jnp.argsort(o), r - 1) for o in orders]
        row = {
            "R": r,
            "up": ms_a_call(grouped, (wide, sz), w_up, args.reps),
            "down": ms_a_call(grouped, (narrow, sz), w_down, args.reps),
            "up_dx": ms_a_call(transpose_of(0), (wide, narrow, sz), w_up,
                               args.reps),
            "up_dw": ms_a_call(transpose_of(1), (wide, narrow, sz), w_up,
                               args.reps),
            "down_dx": ms_a_call(transpose_of(0), (narrow, wide, sz), w_down,
                                 args.reps),
            "down_dw": ms_a_call(transpose_of(1), (narrow, wide, sz), w_down,
                                 args.reps),
            "swiglu": ms_a_call(
                lambda g, u: (jax.nn.silu(g.astype(jnp.float32))
                              * u.astype(jnp.float32)).astype(g.dtype),
                (narrow,), [normal(next(keys), (r, F)) for _ in range(CALLS)],
                args.reps),
            "gather": ms_a_call(lambda x, i: x[i], (tokens,), idx, args.reps),
            "unsort": ms_a_call(lambda y, i: y[i], (wide,), inverse,
                                args.reps),
        }
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
