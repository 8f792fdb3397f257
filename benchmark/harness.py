"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, building the program's configuration from a file,
the device check, the compile counter, the profiler window and the
result line.

Nothing here knows a particular configuration, traffic mix or metric:
those are files under ``configs/``, ``traffic/`` and ``metrics/``, and a
kind of traffic or a way of reducing is a module under ``generators/``
or ``reducers/`` found by its name.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: Traces and anything else a run leaves behind (listed in .gitignore).
OUT_DIR = os.path.join(HERE, "_out")


def say(**kv) -> None:
    """A diagnostic line (never the last line of stdout)."""
    print(json.dumps(kv), flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(name: str, bench: Optional[Dict[str, Any]] = None):
    """(cell, config file's contents, traffic file's contents)."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def cell_metrics(bench: Dict[str, Any], cell_name: str, group: str
                 ) -> List[Dict[str, Any]]:
    """The metrics of ``group`` (``end_to_end`` | ``per_layer``) that
    this cell reports: those with no ``workloads`` key, or naming it."""
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]


def model_config(config: Dict[str, Any], **overrides):
    """The program's ``TransformerConfig`` from a configuration file:
    ``model`` (sizes) and ``run`` (settings) hold its fields by name."""
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    fields = {**config["model"], **config.get("run", {}), **overrides}
    if isinstance(fields.get("dtype"), str):
        fields["dtype"] = getattr(jnp, fields["dtype"])
    return TransformerConfig(**fields)


def generator(kind: str):
    """``generators/<kind>.py`` (``-`` in a kind reads ``_``)."""
    return importlib.import_module(
        "benchmark.generators." + kind.replace("-", "_"))


def reducer(name: str):
    return importlib.import_module("benchmark.reducers." + name)


# -- the device ------------------------------------------------------


def require_tpu(chips: int):
    """The cell's devices, or exit non-zero: no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU chip(s); jax.devices() offers "
              f"{len(devices)} x {devices[0].platform!r} "
              f"({devices[0].device_kind}). No result.", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def device_report(devices) -> Dict[str, Any]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def peak_for(device_kind: str) -> Dict[str, Any]:
    """The device's published peaks. A device that is not in the table
    is an error, not a default."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks for device_kind "
                         f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


# -- compilations inside the window ---------------------------------


class CompileCounter:
    """Counts programs lowered or compiled, through ``jax.monitoring``.

    A program found in the persistent cache is still a new shape met
    inside the window, so lowering counts as much as a backend compile.
    """

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1


# -- the profiler window --------------------------------------------


class TraceWindow:
    """Profiles the measured window from ``after`` seconds into it to
    its end, when tracing is on. The generator calls :meth:`poll`
    between steps with the time since the window opened, and
    :meth:`stop` when the window has closed: stopping takes seconds
    (1.3 to 5.7 on the chip), so it falls outside."""

    def __init__(self, on: bool, workload: str, after: float):
        self.on, self.after = on, after
        self.dir = os.path.join(OUT_DIR, "trace", workload)
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None

    def poll(self, since_open: float) -> None:
        if (not self.on or self.started_at is not None
                or since_open < self.after):
            return
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        t0 = time.perf_counter()
        jax.profiler.start_trace(self.dir)
        self.started_at = time.perf_counter()
        say(profiler="started", took_s=self.started_at - t0)

    def stop(self) -> None:
        if self.on and self.started_at is not None \
                and self.stopped_at is None:
            import jax

            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stopped_at = time.perf_counter()
            say(profiler="stopped", took_s=self.stopped_at - t0,
                traced_s=t0 - self.started_at)

    def xplane(self) -> Optional[str]:
        for base, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(base, f)
        return None
