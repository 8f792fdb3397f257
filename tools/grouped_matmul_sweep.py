"""What a whole mixture's grouped products cost by who runs them, on the
chip:

    chiprun -- python tools/grouped_matmul_sweep.py

At the LFM2 cell's shapes (``lfm2-8b-a1b-14l``: 32 experts of ``[2048,
1792]`` gate / up and ``[1792, 2048]`` down, bf16), for ``M`` pairs of
``--rows`` (512 a decode step of 128 slots, 1024 / 2048 / 4096 the
chunk buckets) and three draws of the group sizes (``router``: a
multinomial over a Dirichlet whose fullest expert holds about 1.8 times
the mean at 4096 pairs, the cell's seeded router's figure; ``even``;
``skewed``: one group holds half the rows and eight are empty; with
``--held-share`` the sizes sum to that share of ``M`` and the other rows
lie behind the last group, as a chip's share of the experts has them:
``--experts 128 --k 1024 --n 2688 --rows 2816 22528 --held-share 0.25``
is the Nemotron cell's step and largest chunk): ms a
call on the device's side of the launch (:func:`ms_a_call`: a program
of 32 calls less a program of 8, each call with operands of its own
and every result a result of the program; medians of ``--reps`` runs)
of

* ``ragged_dot``: ``lax.ragged_dot``, the compiler's kernel;
* ``kernel@<tm>``: ``ops/grouped_matmul.py`` at each row tile of
  ``--tiles``;
* ``megablox@128x<tn>`` (``--megablox``): jax's own
  ``pallas.ops.tpu.megablox.gmm`` with the whole contraction and half
  of ``N`` a block, whose matrices come through the pipeline's own
  double buffer (the next STEP's block, not the next group's);

each with the GB/s of matrix it read (the matrices of the groups that
have rows, once) and the share of the least time those bytes take at
819 GB/s. Then once at OLMoE's trainer's ``[65536, 2048] x [64, 2048,
1024]`` (1024 rows a group, the matrix unit's side of
``grouped_matmul.taken``'s rule). How ``ops/grouped_matmul.py`` came by
its tiles and its threshold. Rehearse here with ``--rows 256 --experts
4 --k 256 --n 128 --reps 1 --calls 2 --operands 2 --no-olmoe``.
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

from horovod_tpu.ops import grouped_matmul as gm  # noqa: E402

HBM_BYTES_S = 819e9


def ms_a_call(fn, lhs, sizes, matrices, reps):
    """ms a call of ``fn(lhs[j], sizes, matrices[i])`` on the device's
    side of the launch: two programs, one of a call a matrix and one of
    a call a matrix AND row operand (every call with operands of its
    own, all the results kept: nothing is summed or sliced after a
    call), and the difference of their medians over the calls the
    longer one has more. What a program costs whatever it holds (the
    launch and the wait for its end: about a millisecond on the chip's
    machine, a quarter of a product's time if it were shared out over
    eight calls, which a first form of this tool did) is in both and
    drops out; it is returned beside."""
    def median_s(rows):
        chain = jax.jit(lambda rows, matrices: [
            fn(r, sizes, w) for r in rows for w in matrices])
        jax.block_until_ready(chain(rows, matrices))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(chain(rows, matrices))
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    short, long = median_s(lhs[:1]), median_s(lhs)
    a_call = (long - short) / ((len(lhs) - 1) * len(matrices))
    return 1e3 * a_call, 1e3 * (short - len(matrices) * a_call)


def normal(key, shape, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(jnp.bfloat16)


def draws(rng, rows: int, groups: int):
    """The three draws of ``sizes`` [groups] that sum to ``rows``."""
    share = rng.dirichlet(np.full(groups, 6.0))
    skewed = np.zeros(groups, np.int64)
    skewed[groups // 2] = rows // 2
    rest = [g for g in range(groups) if g != groups // 2][groups // 4:]
    skewed[rest] = rng.multinomial(rows - rows // 2,
                                   np.full(len(rest), 1 / len(rest)))
    return {"router": rng.multinomial(rows, share),
            "even": np.full(groups, rows // groups),
            "skewed": skewed}


def forms(args, k, n):
    out = {"ragged_dot": lambda rows, sizes, w: lax.ragged_dot(rows, w,
                                                               sizes)}
    for tm in args.tiles:
        out[f"kernel@{tm}"] = (lambda rows, sizes, w, tm=tm:
                               gm._forward(rows, w, sizes, tm=tm))
    if args.megablox:
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        # half of N a block: its whole matrices twice are over the 16
        # MB of VMEM a call has without a limit of its own
        out[f"megablox@128x{n // 2}"] = lambda rows, sizes, w: gmm(
            rows, w, sizes, preferred_element_type=rows.dtype,
            tiling=(128, k, n // 2),
            interpret=jax.default_backend() == "cpu")
    return out


def product(args, keys, name, rows, k, n, groups, sizes):
    """One line: every form's ms at ``[rows, k] x [groups, k, n]``."""
    w = [normal(next(keys), (groups, k, n), k ** -0.5)
         for _ in range(args.calls)]
    lhs = [normal(next(keys), (rows, k)) for _ in range(args.operands)]
    sz = jnp.asarray(sizes, jnp.int32)
    read = int((sizes > 0).sum()) * k * n * 2
    line = {"product": name, "M": rows, "in_a_group": int(sizes.sum()),
            "K": k, "N": n, "G": groups,
            "max_over_mean": round(float(sizes.max() / sizes.mean()), 2),
            "empty": int((sizes == 0).sum()),
            "least_ms": round(1e3 * read / HBM_BYTES_S, 4)}
    want = lax.ragged_dot(lhs[0], w[0], sz).astype(jnp.float32)
    for form, fn in forms(args, k, n).items():
        try:
            got = fn(lhs[0], sz, w[0]).astype(jnp.float32)
            ms, launch = ms_a_call(fn, lhs, sz, w, args.reps)
        except Exception as e:          # a form the compiler refuses
            line[form] = {"refused": str(e)[:300]}
            continue
        line[form] = {
            "ms": round(ms, 4), "a_program_ms": round(launch, 3),
            "GB/s": round(read / ms / 1e6, 1),
            "of_least_pct": round(100 * line["least_ms"] / ms, 1),
            "max_gap": round(float(jnp.abs(
                got - want)[:int(sizes.sum())].max()), 4)}
    del w
    return line


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[512, 1024, 2048, 4096])
    ap.add_argument("--experts", type=int, default=32)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--n", type=int, default=1792)
    ap.add_argument("--held-share", type=float, default=1.0,
                    help="the share of --rows that lies in a group: a "
                    "quarter where a chip holds 128 of 512 experts; the "
                    "other rows lie behind the last group")
    ap.add_argument("--tiles", type=int, nargs="+", default=[128, 256])
    ap.add_argument("--draws", nargs="+", default=["router", "even", "skewed"])
    ap.add_argument("--megablox", action="store_true")
    ap.add_argument("--no-olmoe", action="store_true")
    ap.add_argument("--calls", type=int, default=8,
                    help="matrices, each its own stack of experts")
    ap.add_argument("--operands", type=int, default=4,
                    help="row operands: calls x operands in a program")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/grouped_matmul_sweep.jsonl")
    args = ap.parse_args()
    rng = np.random.default_rng(0)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 4096))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        def say(line):
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()

        say({"device": jax.devices()[0].device_kind, "calls": args.calls,
             "reps": args.reps})
        for rows in args.rows:
            # a chip's share sorts its own pairs to the front, the
            # others behind the last group (moe._held_rows)
            in_a_group = int(round(rows * args.held_share))
            for draw, sizes in draws(rng, in_a_group, args.experts).items():
                if draw not in args.draws:
                    continue
                for name, k, n in (("gate_up", args.k, args.n),
                                   ("down", args.n, args.k)):
                    say({"sizes": draw, **product(
                        args, keys, name, rows, k, n, args.experts, sizes)})
        if not args.no_olmoe:
            sizes = rng.multinomial(65536, rng.dirichlet(np.full(64, 6.0)))
            args.calls = 4
            say({"sizes": "router", **product(
                args, keys, "olmoe_gate_up", 65536, 2048, 1024, 64, sizes)})


if __name__ == "__main__":
    main()
