"""Compile-only for the v5e, with no chip attached.

``jax.experimental.topologies`` hands the installed libtpu a v5e 2x2
topology to compile against, Mosaic included, so a kernel Mosaic
refuses — or a train step that stops reaching the kernel — fails here
on every PR instead of costing chip time. So does a serve program
that moves a buffer as large as the KV pool (PR 30). Runs in a
subprocess: libtpu is noisy at start-up, and the drivers below rebind
``jax.default_backend``.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DRIVER = r"""
import json, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# This process's default backend is the CPU, and the code picks its
# on-chip branches (Mosaic instead of the Pallas interpreter, native
# bf16 island wires) from jax.default_backend(): compile what a process
# holding the chip would compile.
jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, make_train_step
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel import build_mesh

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
out = {{"device_kind": topo.devices[0].device_kind}}
one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())


def flash_fwd_bwd(seq):
    # chip_smoke.py's kernel shape: [2, seq, 16/8 heads, 128] bf16.
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()
    q = jax.ShapeDtypeStruct((2, seq, 16, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, seq, 8, 128), jnp.bfloat16, sharding=one)
    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text().count("tpu_custom_call")


out["flash_1024"] = flash_fwd_bwd(1024)
out["flash_8192"] = flash_fwd_bwd(8192)

# Small, but with the 128-wide heads Mosaic tiles like the real ones.
cfg = TransformerConfig(vocab_size=512, d_model=256, n_layers=2, n_heads=2,
                        n_kv_heads=1, d_ff=512, max_seq=256,
                        dtype=jnp.bfloat16, sp_attention="flash", remat=False)
for name, mesh in (
        ("step_1", build_mesh(dp=-1, devices=topo.devices[:1])),
        ("step_4", build_mesh(dp=2, fsdp=2, devices=topo.devices))):
    init_state, step, _ = make_train_step(cfg, mesh)
    state = jax.eval_shape(init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch = {{"tokens": jax.ShapeDtypeStruct((2 * mesh.devices.size, 257),
                                            jnp.int32)}}
    compiled = step.lower(state, batch).compile()
    out[name] = compiled.as_text().count("tpu_custom_call")
    wq = compiled.input_shardings[0][0]["params"]["layers"]["wq"]
    out[name + "_wq_shard"] = list(wq.shard_shape((2, 256, 256)))
print("LOWERED " + json.dumps(out))
"""


# The serve programs at the sizes of the benchmark's chat cell:
# `mistral-7b-v0.3-16l` under `chat-steady`'s table (blocks of 16, 80 a
# sequence, 16 sequences and the null block), the decode bucket and the
# prefill bucket that traffic uses most. Counted in the optimised HLO:
# instructions whose result is as large as the pool or as one layer of
# it, by opcode, and the scopes of the pool-sized scatters.
_SERVE_DRIVER = r"""
import collections, json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
cfg = TransformerConfig(vocab_size=32768, d_model=4096, n_layers=16,
                        n_heads=32, n_kv_heads=8, d_ff=14336, max_seq=4096,
                        rope_theta=1e6, norm_eps=1e-5, dtype=jnp.bfloat16,
                        remat=False)
BS, WIDTH, N_BLOCKS = 16, 80, 16 * 80 + 1


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one)


def i32(*shape):
    return sds(shape, jnp.int32)


params = jax.tree.map(
    lambda s: sds(s.shape, s.dtype),
    jax.eval_shape(lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kv = sds((cfg.n_layers, N_BLOCKS, BS, cfg.n_kv_heads, cfg.head_dim),
         cfg.dtype)
prefill, _, decode, _, _ = decode_lib.make_serve_fns(
    cfg, None, block_size=BS, table_width=WIDTH)
pool = "bf16[%d,%d,%d,%d,%d]" % kv.shape
layer = "bf16[1,%d,%d,%d,%d]" % kv.shape[1:]
out = {{"device_kind": topo.devices[0].device_kind,
       "pool_bytes": kv.size * kv.dtype.itemsize}}
for name, fn, args in (
        ("decode", decode, (i32(8), i32(8), i32(8, WIDTH))),
        ("prefill", prefill, (i32(256), i32(), i32(WIDTH)))):
    compiled = fn.lower(params, kv, kv, *args).compile()
    ops, scatter_scopes = collections.Counter(), []
    for result, opcode, rest in re.findall(
            r"= (\S+?)\{{\S* ([\w\-]+)\((.*)", compiled.as_text()):
        if result in (pool, layer):
            ops[("pool " if result == pool else "layer ") + opcode] += 1
            if opcode == "scatter":
                scatter_scopes.append(
                    re.search(r'op_name="([^"]*)"', rest).group(1))
    out[name] = {{"ops": ops, "scatter_scopes": scatter_scopes,
                 "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}}
print("LOWERED " + json.dumps(out))
"""


def _compile_for_v5e(driver):
    proc = subprocess.run(
        [sys.executable, "-c", driver.format(root=ROOT)],
        # Compile-only never opens a chip, so two such processes (or a
        # stale /tmp/libtpu_lockfile) need not exclude each other.
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 ALLOW_MULTIPLE_LIBTPU_LOAD="1"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("LOWERED ")][-1]
    out = json.loads(line[len("LOWERED "):])
    assert "v5" in out["device_kind"], out
    return out


def test_flash_kernel_and_train_step_compile_for_v5e():
    out = _compile_for_v5e(_DRIVER)
    # Mosaic compiled the kernel (forward; the backward is XLA einsums)
    # at the smoke's shape and at long sequence ...
    assert out["flash_1024"] >= 1 and out["flash_8192"] >= 1, out
    # ... and the train step reaches it on one chip and on dp2 x fsdp2,
    # where the compiled step takes its state fsdp-sharded.
    assert out["step_1"] >= 1 and out["step_4"] >= 1, out
    assert out["step_1_wq_shard"] == [2, 256, 256], out
    assert out["step_4_wq_shard"] == [2, 128, 256], out


def test_serve_programs_move_no_buffer_of_the_pool_s_size_on_v5e():
    """The counter that says the pool is updated in place (PR 30): the
    layer scan carries the donated pool, so the compiled ``decode`` and
    ``prefill`` hold no copy of it, no restacking ``dynamic-update-slice``
    of it and no slice of a whole layer of it, only the two scatters of
    ``attn/kv_write`` (K and V) on the carry, and their temporaries are
    far under one pool. PR 29's programs held two of each of the three
    and 1.34 GB of temporaries, two pools' worth."""
    out = _compile_for_v5e(_SERVE_DRIVER)
    for program in ("decode", "prefill"):
        got = out[program]
        # Nothing as large as a layer of the pool but the pool itself:
        # the arguments, the carry's tuple elements, and the scatters
        # with the fusions around them.
        assert set(got["ops"]) == {
            "pool parameter", "pool get-tuple-element", "pool fusion",
            "pool scatter"}, (program, got)
        assert got["ops"]["pool scatter"] == 2, (program, got)
        assert all("attn/kv_write" in scope
                   for scope in got["scatter_scopes"]), (program, got)
        assert got["temp_bytes"] < out["pool_bytes"], (program, got)
