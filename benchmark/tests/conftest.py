"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.

Not part of the repository's tier-1 tests (``tests/``). Four virtual CPU
devices stand in for a four-chip host; nothing here yields a time, a
rate or a share that means anything, only that the harness runs and
counts right.
"""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
# The rehearsals compile for the CPU; keep those programs out of the
# checkout's .jax_cache, which the program's own entry points share.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "_out",
    "cpu_cache"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
