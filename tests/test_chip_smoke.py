"""``chip_smoke.py`` off the chip: its four phases at tiny widths on
four virtual CPU devices (dp2 x fsdp2, interpret-mode kernel), the
sharded-state assertion against a replicated state, and the script's
refusals — no TPU, or any phase raising, is a non-zero exit with no
result line."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from horovod_tpu.models import TransformerConfig, make_train_step  # noqa: E402
from horovod_tpu.parallel import build_mesh  # noqa: E402

TINY = dict(trainer=dict(rows_per_chip=2, seq=64, steps=3),
            kernel=dict(batch=1, seq=128, heads=4, kv_heads=2, head_dim=32),
            server=dict(n_requests=4, max_prompt=32, new_tokens=4))


def _cfg():
    return TransformerConfig.tiny(dtype=jnp.float32, sp_attention="flash",
                                  remat=False)


def test_phases_pass_tiny_on_four_devices(devices, capsys):
    mesh = build_mesh(dp=2, fsdp=2, devices=devices[:4])
    chip_smoke.run(_cfg(), mesh, on_chip=False, **TINY)
    out = capsys.readouterr().out
    for phase in ("trainer", "kernel", "server", "eager"):
        assert f'"phase": "{phase}"' in out, out


def test_sharded_state_assertion_rejects_replicated_state(devices):
    """What ``jax.jit(init_state)`` handed back before the factories
    pinned the layout: every leaf whole on every device."""
    mesh = build_mesh(dp=2, fsdp=2, devices=devices[:4])
    cfg = _cfg()
    init_state, _, _ = make_train_step(cfg, mesh)
    state = jax.jit(init_state)(jax.random.PRNGKey(0))
    assert chip_smoke.check_state_sharded(state, cfg, mesh) > 0
    from jax.sharding import NamedSharding, PartitionSpec as P
    replicated = jax.device_put(state, NamedSharding(mesh, P()))
    with pytest.raises(AssertionError, match="not sharded"):
        chip_smoke.check_state_sharded(replicated, cfg, mesh)


def _run_script(code=None, **env):
    cmd = [sys.executable] + (["-c", code] if code else
                              [os.path.join(ROOT, "chip_smoke.py")])
    return subprocess.run(cmd, env=dict(os.environ, **env), cwd=ROOT,
                          capture_output=True, text=True, timeout=120)


def test_script_refuses_to_run_without_a_tpu():
    proc = _run_script(JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


_INJECTED = """
import sys, types
sys.path.insert(0, {root!r})
import jax
import chip_smoke

class FakeTpu:
    platform, device_kind = "tpu", "fake"
jax.devices = lambda *a: [FakeTpu()]
chip_smoke.run = lambda *a, **k: (_ for _ in ()).throw(
    AssertionError("injected phase failure"))
sys.exit(chip_smoke.main())
"""


def test_a_failing_phase_fails_the_run():
    proc = _run_script(_INJECTED.format(root=ROOT), JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert "injected phase failure" in proc.stderr
    assert '"ok"' not in proc.stdout
