"""Where JAX's persistent compilation cache lives, and what compiled.

A cold compile of a full-width train step is a minute or more, and a
chip-tool call keeps nothing but its output directory, so every entry
point that compiles for the device calls :func:`use_compile_cache`
first. The directory is part of the cache key: it is either where the
environment says (``JAX_COMPILATION_CACHE_DIR``, which jax reads by
itself — then nothing is set in code) or one fixed place in the
checkout. Never a temporary name, a pid or a time. The key includes a
program's metadata, so that the names a profile shows are the names of
the code that ran (and a moved line compiles again).

The same call starts a log of what the process traced, lowered and
compiled (one ``jax.monitoring`` listener): :func:`compile_stats` says
how long each took, how many programs came out of the persistent cache,
and which functions were last — which step recompiled, and how much of
a start-up was compilation.
"""

from __future__ import annotations

import collections
import os
import time
from typing import Any, Dict, Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: jax.monitoring duration events -> the kind compile_stats() reports.
#: JAX 0.9 passes ``fun_name`` on the three ``/jax/core/compile`` ones;
#: a retrieval is recorded once per persistent-cache hit, inside the
#: backend-compile event of the same program.
_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_hit",
}
_RECENT = 64


class _CompileLog:
    """Seconds and counts by kind, and the last ``_RECENT`` events."""

    def __init__(self):
        self.seconds = dict.fromkeys(_KINDS.values(), 0.0)
        self.counts = dict.fromkeys(_KINDS.values(), 0)
        self.recent: collections.deque = collections.deque(maxlen=_RECENT)

    def on_duration(self, event: str, duration: float, **kw) -> None:
        kind = _KINDS.get(event)
        if kind is None:
            return
        self.seconds[kind] += duration
        self.counts[kind] += 1
        self.recent.append({"at": time.time(), "kind": kind,
                            "fun_name": kw.get("fun_name"),
                            "seconds": duration})


_log: Optional[_CompileLog] = None


def use_compile_cache() -> str:
    """Place the compilation cache, start the compile log (once a
    process) and return the cache's directory."""
    global _log
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # The names a program carries (jax.named_scope, a kernel's name) are
    # metadata, which the cache's key leaves out by default: a program
    # that differs from a cached one in its names alone would come back
    # from the cache with the old names, and a profile would show those.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if _log is None:
        _log = _CompileLog()
        jax.monitoring.register_event_duration_secs_listener(
            _log.on_duration)
    return path


def compile_stats() -> Dict[str, Any]:
    """What this process traced, lowered and compiled since
    :func:`use_compile_cache` was first called (all zero before).

    ``tracing_s`` / ``lowering_s`` / ``backend_compile_s``: seconds in
    the three phases, as JAX reports them (a jitted function called
    inside another is counted in its caller's tracing too; a backend
    compile that hit the persistent cache is the time to read it).
    ``programs_compiled`` counts backend compiles, ``cache_hits`` those
    served from the persistent cache, ``cache_misses`` the rest (really
    compiled). ``recent``: the last events, oldest first, as
    ``{at (time.time()), kind (trace | lower | compile | cache_hit),
    fun_name, seconds}``. A repeated call of a jitted function with the
    same shapes adds nothing."""
    log = _log or _CompileLog()
    hits = log.counts["cache_hit"]
    return {
        "tracing_s": log.seconds["trace"],
        "lowering_s": log.seconds["lower"],
        "backend_compile_s": log.seconds["compile"],
        "programs_traced": log.counts["trace"],
        "programs_lowered": log.counts["lower"],
        "programs_compiled": log.counts["compile"],
        "cache_hits": hits,
        "cache_misses": log.counts["compile"] - hits,
        "recent": list(log.recent),
    }
