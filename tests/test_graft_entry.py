"""The driver's contract: entry() jits; dryrun_multichip(8) executes a
full sharded training step on the virtual CPU mesh."""

import sys

import jax
import pytest


def test_entry_jits():
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == args[1].shape[0]


@pytest.mark.slow  # ~32s of mesh compiles (ISSUE 12 budget audit).
# Redundancy: the DRIVER executes dryrun_multichip directly every
# round for the MULTICHIP_rNN record (so this exact path runs per PR
# regardless), and the slow-tier driver-path test below runs a strict
# superset of its configs; tier-1 keeps entry()-jits.
def test_dryrun_multichip_8(devices):
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g
    g.dryrun_multichip(8)


@pytest.mark.slow  # ~2-4 min of CPU compiles; duplicates the
# multichip_8 gate's configs plus the wide axes — the driver runs the
# dryrun directly for its MULTICHIP record, so tier-1 keeps only the
# 8-device gate.
def test_dryrun_wide_axes_via_driver_path():
    """The driver's exact invocation (fresh interpreter, no jax state):
    the child self-provisions 16 virtual devices and must run the
    wide-axis configs — tp=4 and sp=4 — on top of the base five (axis
    size >= 4 catches ring-order/GQA-split bugs that all-2s meshes
    cannot). ~2-3 min of CPU compiles; this is the multichip gate."""
    import os
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = ""
    proc = subprocess.run(
        [sys.executable, "/root/repo/__graft_entry__.py", "8"],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    for tag in ["dense dp/fsdp/sp/tp", "ep/moe", "tp4", "sp4",
                "pp", "pp+ep/moe", "pp-1f1b"]:
        assert f"dryrun[{tag}]" in proc.stdout, (tag, proc.stdout)
    assert "'tp': 4" in proc.stdout and "'sp': 4" in proc.stdout
