"""Seconds of set-up spent tracing, lowering and compiling (or reading
the persistent cache), from the program's own log
(``horovod_tpu.common.compile_cache.compile_stats``): what of ``setup_s``
a changed program or a missed cache moves. Nothing compiles inside the
window (``compiles_in_window.*``), so the process's total is set-up's.
A program without the log (before PR 24) leaves the metric out."""
from benchmark import harness


def reduce(meas):
    from horovod_tpu.common import compile_cache

    stats = getattr(compile_cache, "compile_stats", None)
    if stats is None:
        return None
    s = stats()
    harness.say(compile_stats={k: v for k, v in s.items() if k != "recent"},
                last_compiled=[[e["kind"], e["fun_name"],
                                round(e["seconds"], 3)]
                               for e in s["recent"][-6:]])
    return s["tracing_s"] + s["lowering_s"] + s["backend_compile_s"]
