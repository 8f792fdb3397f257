"""Sweep the arrival rate of an open-loop cell, once, to find its knee:
the highest rate at which the queue does not grow through a run and no
request is shed. One process per rate, one after another (a chip
belongs to one process), and this parent never touches JAX.

    chiprun -- python3 -m benchmark.tools.sweep_rate \
        --workload serve-mistral7b-chat-steady --seconds 30 \
        --rates 0.8 1.2 1.6 2.0 2.4

The table goes into PERF.md, and four fifths of the knee, as a number,
into the traffic file.
"""

import argparse
import subprocess
import sys

CHILD = ("import json; from benchmark import run; "
         "r = run.run_cell({w!r}, {seed}, {s}, False, "
         "extra={{'rate_per_s': {rate}}}); print(json.dumps(r))")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    for rate in args.rates:
        print(f"=== rate_per_s {rate}", flush=True)
        proc = subprocess.run(
            [sys.executable, "-c", CHILD.format(
                w=args.workload, seed=args.seed, s=args.seconds, rate=rate)],
            capture_output=True, text=True)
        lines = proc.stdout.strip().splitlines()
        print("\n".join(lines[-3:]) if lines else proc.stderr[-2000:],
              flush=True)


if __name__ == "__main__":
    main()
