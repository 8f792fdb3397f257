"""The one command of the benchmark.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this process is started
on, and prints as the last line of its standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, in
a traced run, ``breakdown``. With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` a few seconds inside the
window are profiled and the metrics are its per-layer metrics.
Diagnostics go on earlier lines. No TPU, or fewer chips than the cell
asks for: exit code 2 and no result. There is no option that lets a CPU
through; the tests and the CPU rehearsals call :func:`run_cell` with a
Python argument instead.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

from benchmark import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             devices=None, bench: Optional[Dict[str, Any]] = None,
             config: Optional[Dict[str, Any]] = None,
             traffic: Optional[Dict[str, Any]] = None,
             extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run one cell and return the result object.

    ``devices`` (a test's CPU devices), ``bench``, ``config`` and
    ``traffic`` (a test's tiny files) stand in for what the command line
    finds by itself; ``extra`` reaches the generator (the rate of a
    sweep)."""
    import jax

    bench = bench or harness.load_benchmark()
    if config is None or traffic is None:
        cell, config, traffic = harness.find_cell(workload, bench)
    else:
        cell = next(w for w in bench["workloads"] if w["name"] == workload)
    if devices is None:
        devices = harness.require_tpu(cell["chips"])

    from horovod_tpu.common.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    # Every program goes to the cache, however quickly it compiled, so
    # that only the first run of a cell in a checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    harness.say(workload=workload, seed=seed, seconds=seconds, trace=trace,
                platform=devices[0].platform,
                device_kind=devices[0].device_kind, count=len(devices),
                compile_cache=cache_dir)

    when = traffic.get("trace", {})
    window = harness.TraceWindow(
        trace, workload, max(seconds - when.get("seconds", 3.0), 0.0))
    ctx = {"workload": workload, "config": config, "traffic": traffic,
           "seed": seed, "seconds": seconds, "devices": devices,
           "t_start": _T_START, "compiles": harness.CompileCounter(),
           "trace_window": window, "annotate": jax.profiler.TraceAnnotation,
           **(extra or {})}
    meas = harness.generator(traffic["kind"]).run(ctx)
    meas["end_to_end"]["setup_s"] = meas["t_open"] - _T_START
    meas["device"] = harness.device_report(devices)
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}

    result: Dict[str, Any] = {
        "correct": bool(meas["correct"]), "attempted": meas["attempted"],
        "failed": meas["failed"]}
    if not trace:
        names = [m["name"] for m in
                 harness.cell_metrics(bench, workload, "end_to_end")]
        values = {n: meas["end_to_end"][n] for n in names}
    else:
        harness.say(end_to_end_while_traced=meas["end_to_end"])
        from benchmark import trace_reduce
        path = window.xplane()
        meas["trace"] = trace_reduce.reduce_xplane(path) if path else None
        if meas["trace"]:
            with open(os.path.join(harness.OUT_DIR,
                                   f"ops-{workload}.json"), "w") as f:
                json.dump({k: meas["trace"][k] for k in
                           ("window_s", "busy_s", "ops", "idle_gaps",
                            "collective_s", "collective_exposed_s")}, f)
        meas["peak"] = harness.peak_for(devices[0].device_kind) \
            if devices[0].platform == "tpu" else None
        values = {}
        for m in harness.cell_metrics(bench, workload, "per_layer"):
            spec = harness.load_json("metrics", m["name"] + ".json")
            value = harness.reducer(spec["reducer"]).reduce(
                meas, **spec.get("args", {}))
            if value is not None:
                values[m["name"]] = value
        if meas["trace"]:
            meas["device"]["busy_s"] = meas["trace"]["busy_s"]
            meas["device"]["window_s"] = meas["trace"]["window_s"]
            result["breakdown"] = {
                "device_ops": meas["trace"]["device_ops"],
                "idle_gaps": meas["trace"]["idle_gaps"]}
    result["metrics"] = {n: {"value": v, "unit": units[n]}
                         for n, v in values.items()}
    result["device"] = meas["device"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
