"""Compile-only for the v5e, with no chip attached.

``jax.experimental.topologies`` hands the installed libtpu a v5e 2x2
topology to compile against, Mosaic included, so a kernel Mosaic
refuses — or a train step that stops reaching the kernel — fails here
on every PR instead of costing chip time. So does a serve program
that moves a buffer as large as the KV pool (PR 30). Runs in a
subprocess: libtpu is noisy at start-up, and the drivers below rebind
``jax.default_backend``.
"""

import functools
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A Mosaic kernel as a compiled program's text holds it (the base64 of
# its serialized MLIR under "body"), parsed and printed WITHOUT its debug
# locations, which move with the source's lines. Shared by the drivers
# that hold a kernel to the one of an earlier tree.
_KERNEL_DIGEST = r"""
def kernel_digest(body):
    import base64, hashlib
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    with ir.Context() as ctx:
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)
    return hashlib.sha256(asm.encode()).hexdigest()
"""

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


KERNEL_DIGEST


def program_digest(text):
    # The compiled program without what moves when a line of the source
    # moves: op metadata, the tables of files, functions and frames, and
    # the debug locations inside each Mosaic kernel's serialized MLIR
    # (`kernel_digest`).
    import hashlib, re
    text = re.sub(r'"body":"([A-Za-z0-9+/=]+)"',
                  lambda match: '"body":"' + kernel_digest(match.group(1))
                  + '"', text)
    text = re.sub(r", metadata=\{{[^}}]*\}}", "", text)
    text = re.sub(r"\nFileNames\n.*?\n\n\n", "\n", text, flags=re.S)
    return hashlib.sha256(text.encode()).hexdigest()


def flash_fwd_bwd(seq, batch=2, heads=16, kv_heads=8, window=None):
    # chip_smoke.py's kernel shape: [2, seq, 16/8 heads, 128] bf16.
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               window=window).astype(jnp.float32).sum()
    q = jax.ShapeDtypeStruct((batch, seq, heads, 128), jnp.bfloat16,
                             sharding=one)
    kv = jax.ShapeDtypeStruct((batch, seq, kv_heads, 128), jnp.bfloat16,
                              sharding=one)
    compiled = jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    return {{"kernels": text.count("tpu_custom_call"),
            "score_shapes": text.count("%d,%d]" % (seq, seq)),
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "digest": program_digest(text)}}


out["flash_1024"] = flash_fwd_bwd(1024)
out["flash_8192"] = flash_fwd_bwd(8192)
# The training cells' attention: [32, 4096, 128] queries a chip, over 16
# kv heads (InternLM2, q_per_kv 2) and over 32 (OLMoE, q_per_kv 1).
out["flash_cell_gqa"] = flash_fwd_bwd(4096)
out["flash_cell_mha"] = flash_fwd_bwd(4096, kv_heads=16)
out["flash_32768"] = flash_fwd_bwd(32768, batch=1)
# The window cell's attention: [32, 8192, 128] over 4 kv heads, a
# sliding layer's window and a full layer's none.
out["flash_window_cell"] = flash_fwd_bwd(8192, batch=1, heads=32,
                                         kv_heads=4, window=1024)
out["flash_full_cell"] = flash_fwd_bwd(8192, batch=1, heads=32, kv_heads=4)

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
    text = compiled.as_text()
    out[name] = text.count("tpu_custom_call")
    out[name + "_bwd"] = [kernel in text for kernel
                          in ("hvd_flash_bwd_dkv", "hvd_flash_bwd_dq")]
    wq = compiled.input_shardings[0][0]["params"]["layers"]["wq"]
    out[name + "_wq_shard"] = list(wq.shard_shape((2, 256, 256)))
print("LOWERED " + json.dumps(out))
""".replace("KERNEL_DIGEST", _KERNEL_DIGEST)


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


def prompt_attention(compiled, bucket):
    # What `prefill` attends with: the Mosaic calls by name, with the
    # shapes they take, and whatever holds a bucket x bucket square a
    # head (scores, their mask, their bf16 copy).
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    return {{"kernels": [re.search(r'op_name="([^"]*)"', ln).group(1)
                        for ln in calls],
            "kernel_operands": [re.search(
                r"operand_layout_constraints=\{{((?:[^{{}}]|\{{[^{{}}]*\}})*)\}}",
                ln).group(1)
                for ln in calls],
            "score_shapes": text.count("32,%d,%d]" % (bucket, bucket)),
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}}


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
    if name == "prefill":
        out["prefill_256"] = prompt_attention(compiled, 256)
# ... `prefill` again at the chat cell's largest bucket, and at the
# batch cell's largest under `batch-prefill`'s table (132 blocks a
# sequence: 2048 + 64 tokens).
for bucket, width in ((1024, WIDTH), (2048, 132)):
    kv = sds((cfg.n_layers, 16 * width + 1, BS, cfg.n_kv_heads,
              cfg.head_dim), cfg.dtype)
    prefill = decode_lib.make_serve_fns(cfg, None, block_size=BS,
                                        table_width=width)[0]
    out["prefill_%d" % bucket] = prompt_attention(
        prefill.lower(params, kv, kv, i32(bucket), i32(),
                      i32(width)).compile(), bucket)
print("LOWERED " + json.dumps(out))
"""


# What a decode program's text says of its full layers' attention (ISSUE
# 55; a window layer's too since ISSUE 59): the Mosaic calls under
# `hvd_paged_decode` by scope, and what is
# left under `attn_full/kv_gather` (every row's whole table, gathered:
# `bf16[rows * width, 16, ...]` a pool and full layer before). Shared by
# the three drivers whose configurations have a full kind.
_PAGED_DECODE_REPORT = r"""
def paged_decode_report(text):
    calls = re.findall(
        r'custom-call\([^\n]*op_name="([^"]*hvd_paged_decode)[^"]*"', text)
    return {{
        "paged_decode_calls": len(calls),
        "paged_decode_by_path": {{path: calls.count(path)
                                 for path in set(calls)}},
        "table_gathers": len(re.findall(
            r'op_name="[^"]*attn_full/kv_gather', text))}}
"""


# Who runs a program's grouped products (ISSUE 61): the compiler's
# `ragged-dot` custom calls, the Pallas calls `hvd_grouped_matmul`, and
# what `moe._grouped_product` counted as the process traced its programs
# (`moe_grouped_kernel_products_share`, which `ServeMetrics.snapshot()`
# carries). One program holds one kind or the other, never both: the
# benchmark's readers price all of a window's products against the
# kernels they find by name. Shared by the drivers that compile a
# mixture.
_GROUPED_PRODUCTS_REPORT = r"""
def grouped_products(text):
    from horovod_tpu.models import moe as moe_lib
    # of the products traced since the last reading: a program's own
    traced, kernel = moe_lib._grouped_traced
    moe_lib._grouped_traced[:] = [0, 0]
    return {{
        "ragged_dots": len(re.findall(
            r"%ragged-dot-(?!metadata)\S+ = [^\n]* custom-call\(", text)),
        "grouped_kernels": len(re.findall(
            r"custom-call\([^\n]*hvd_grouped_matmul", text)),
        "kernel_products_share": kernel / traced if traced else None}}
"""


# The two-cache serve programs of a configuration with layers of several
# kinds, small but with caches and experts too large for the compiler to
# stage whole in fast memory (it does with small ones, and the copies it
# then makes are not the ones looked for here): instructions of the optimised HLO whose result is as large as
# the full layers' pool, the window layers' rings, or one layer's
# experts, by opcode.
_MIXED_DRIVER = r"""
import collections, dataclasses, json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache, ring_width

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
cfg = TransformerConfig(
    vocab_size=1024, d_model=1024, n_layers=4, n_heads=16, n_kv_heads=8,
    d_head=128, d_ff=2048, d_ff_dense=2048, n_dense_layers=1, max_seq=16384,
    rope_theta=1e4, layer_types=("sliding", "sliding", "sliding", "full"),
    attn_window=4096, qk_norm_per_head=True, attn_gate=True,
    sandwich_norm=True, embed_scale=True, n_experts=64, moe_top_k=4,
    moe_capacity_factor=None, moe_scoring="sigmoid", moe_route_scale=2.448,
    moe_shared_expert=True, moe_experts_held=16, dtype=jnp.bfloat16,
    remat=False)
BS, WIDTH, SLOTS, CHUNK = 16, 512, 16, 256
ring = ring_width(cfg.attn_window, CHUNK, BS)


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


params = on_chip(jax.eval_shape(
    lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kv = on_chip(jax.eval_shape(lambda: init_kv_cache(
    cfg, SLOTS * WIDTH + 1, BS, n_slots=SLOTS, ring=ring).k))
prefill, resume, decode, _, _ = decode_lib.make_serve_fns(
    cfg, None, block_size=BS, table_width=WIDTH, ring=ring)


def shape_of(s):
    return "bf16[%s]" % ",".join(map(str, s.shape))


PAGED_DECODE_REPORT
GROUPED_PRODUCTS_REPORT
large = {{shape_of(kv[0]): "pool", shape_of(kv[1]): "rings",
         shape_of(params["layers"][0]["moe"]["w_gate"]): "experts"}}
out = {{"device_kind": topo.devices[0].device_kind,
       "rings_bytes": kv[1].size * 2, "pool_bytes": kv[0].size * 2}}
for name, fn, args in (
        # a quarter of the slots: what a step gathers of the caches to
        # attend over (206 MB) is then under either cache
        ("decode", decode, (i32(4), i32(4), (i32(4, WIDTH), i32(4)))),
        ("prefill_resume", resume,
         (i32(CHUNK), i32(), i32(), (i32(WIDTH), i32())))):
    compiled = fn.lower(params, kv, kv, *args).compile()
    ops = collections.Counter()
    for result, opcode in re.findall(r"= (\S+?)\{{\S* ([\w\-]+)\(",
                                     compiled.as_text()):
        if result in large:
            ops[large[result] + " " + opcode] += 1
    out[name] = {{"ops": ops,
                 "kernels": compiled.as_text().count("tpu_custom_call"),
                 "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
                 **grouped_products(compiled.as_text()),
                 **paged_decode_report(compiled.as_text())}}

# A decode step over caches of the trinity cell's shapes (ISSUE 59): the
# published 48 / 8 heads of 128, four window layers and a full one, 32
# rows in 33 slots' rings of 4096 + 1024 + 16 places behind tables of
# 536; narrow otherwise, so that it compiles in seconds.
cell = dataclasses.replace(
    cfg, n_layers=5, n_heads=48,
    layer_types=("sliding", "sliding", "sliding", "sliding", "full"))
ring = ring_width(cell.attn_window, 1024, BS)
kv = on_chip(jax.eval_shape(lambda: init_kv_cache(
    cell, 32 * 536 + 1, BS, n_slots=32, ring=ring).k))
compiled = decode_lib.make_serve_fns(
    cell, None, block_size=BS, table_width=536, ring=ring)[2].lower(
    on_chip(jax.eval_shape(
        lambda: init_transformer(cell, jax.random.PRNGKey(0)))),
    kv, kv, i32(32), i32(32), (i32(32, 536), i32(32))).compile()
text = compiled.as_text()
out["decode_cell"] = {{
    "rings": shape_of(kv[1]),
    # whatever else has a ring's places among its dimensions: a slot's
    # or a layer's rings sliced, copied, gathered or scored
    "ring_wide": sorted(set(
        result + " " + opcode for result, opcode in re.findall(
            r"= (\w+\[[\d,]*\b%d\b[\d,]*\])\{{\S* ([\w\-]+)\(" % ring, text)
        if result != shape_of(kv[1]))),
    "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
    **paged_decode_report(text)}}
print("LOWERED " + json.dumps(out))
""".replace("PAGED_DECODE_REPORT", _PAGED_DECODE_REPORT).replace(
    "GROUPED_PRODUCTS_REPORT", _GROUPED_PRODUCTS_REPORT)


# Who reads a state array in a compiled program's entry computation, and
# the Pallas calls of ``ops/state_step.py`` with the scope each stands
# under (ISSUES 48 and 62): shared by the drivers of the stacks with a
# recurrent state.
_STATE_READERS_REPORT = r"""
def readers(text, shape):
    # the entry computation's instructions that take a value of `shape`
    # (alone or in a tuple) and do more than hand it on, by opcode
    entry = text[text.index("\nENTRY "):]
    lines = [ln.strip().removeprefix("ROOT ").split(" = ", 1)
             for ln in entry.splitlines() if " = " in ln]
    held = {{name for name, rest in lines if shape in rest.split(" ")[0]
            or (rest.startswith("(") and shape in rest[:rest.index(") ")])}}
    found = collections.Counter()
    for name, rest in lines:
        call = re.search(r" ([\w\-]+)\((%[^)]*)\)", rest)
        if call and call.group(1) not in ("get-tuple-element", "tuple",
                                          "bitcast"):
            if held & set(re.findall(r"%[\w.\-]+", call.group(2))):
                found[call.group(1)] += 1
    return found


def state_step_calls(text):
    # the scopes (`attn_mamba2/mamba2_step`, `attn_kda/kda_step`) the
    # `hvd_state_step` custom calls stand under, one entry a call
    return sorted(re.findall(
        r"custom-call\([^\n]*/(attn_\w+/\w+)/hvd_state_step/pallas_call",
        text))
"""

# What a decode program's text says of its latent attention (ISSUE 45):
# the Mosaic calls under `hvd_latent_decode` by scope, a key block of
# 1024 positions gathered for every row (`bf16[rows * 64, 16, 640]`,
# what `mla_pages` made for the XLA form) and that form's float32 scores
# of a key block. Shared by the two drivers that compile a decode.
_LATENT_DECODE_REPORT = r"""
def latent_decode_report(text, rows, heads):
    calls = re.findall(
        r'custom-call\([^\n]*op_name="([^"]*hvd_latent_decode)[^"]*"', text)
    return {{
        "latent_decode_calls": len(calls),
        "latent_decode_paths": sorted(set(calls)),
        "gathered_key_blocks": len(re.findall(
            r"bf16\[(%d,16|%d,1024),640\]" % (rows * 64, rows), text)),
        "scores_of_a_decode_key_block": len(re.findall(
            r"f32\[%d,%d,1,1024\]" % (rows, heads), text))}}
"""


# The programs of a configuration whose state is not cached keys (ISSUE
# 38), at the widths, the 64 slots and the table of the cell that brought
# them (a dense kda layer, a sparse kda layer and a sparse mla layer;
# fewer experts held, so that it compiles in a minute).
_LING_DRIVER = r"""
import collections, json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
cfg = TransformerConfig(
    vocab_size=39296, d_model=2560, n_layers=3, n_heads=32, n_kv_heads=32,
    d_head=128, d_ff=768, d_ff_dense=6144, n_dense_layers=1, max_seq=17408,
    rope_theta=6e6, norm_eps=1e-6, layer_types=("kda", "kda", "mla"),
    mla_kv_rank=512, mla_rope_dim=64, n_experts=512, moe_top_k=8,
    moe_capacity_factor=None, moe_scoring="sigmoid", moe_route_scale=2.5,
    moe_shared_expert=True, moe_experts_held=128, moe_expert_offset=128,
    moe_n_group=8, moe_topk_group=4, dtype=jnp.bfloat16, remat=False)
BS, WIDTH, SLOTS, CHUNK = 16, 1088, 64, 1024


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


params = on_chip(jax.eval_shape(
    lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
    cfg, SLOTS * WIDTH + 1, BS, n_slots=SLOTS))))
_, resume, decode, _, _ = decode_lib.make_serve_fns(
    cfg, None, block_size=BS, table_width=WIDTH)
LATENT_DECODE_REPORT
GROUPED_PRODUCTS_REPORT
STATE_READERS_REPORT


def shape_of(s):
    return "%s[%s]" % ({{"bfloat16": "bf16", "float32": "f32"}}[str(s.dtype)],
                       ",".join(map(str, s.shape)))


large = {{shape_of(kc[0]): "state", shape_of(kc[1]): "pool"}}
out = {{"device_kind": topo.devices[0].device_kind,
       "state_bytes": kc[0].size * 4, "pool_bytes": kc[1].size * 2}}
for name, fn, args in (
        ("decode", decode,
         (i32(SLOTS), i32(SLOTS), (i32(SLOTS, WIDTH), i32(SLOTS)))),
        # (the offset is traced: 8192 or any other is this program)
        ("prefill_resume", resume,
         (i32(CHUNK), i32(), i32(), (i32(WIDTH), i32())))):
    compiled = fn.lower(params, kc, vc, *args).compile()
    text = compiled.as_text()
    ops = collections.Counter()
    for result, opcode in re.findall(r"= (\S+?)\{{\S* ([\w\-]+)\(", text):
        if result in large:
            ops[large[result] + " " + opcode] += 1
    aliased = re.search(r"input_output_alias=\{{(.*?)\}}, entry", text)
    out[name] = {{
        "ops": ops, "aliased": len(re.findall(r"may-alias|must-alias",
                                              aliased.group(1))),
        # a float32 tensor with a chunk's queries against a whole table
        "scores_of_the_table": len(re.findall(
            r"f32\[[\d,]*(1024,17408|17408,1024)[\d,]*\]", text)),
        # ... or against one key block, every head's
        "scores_of_a_key_block": len(re.findall(
            r"f32\[[\d,]*32,1024,1024\]", text)),
        "keys_kernel_paths": sorted(set(re.findall(
            r'op_name="([^"]*hvd_flash_keys_fwd)[^"]*"', text))),
        **latent_decode_report(text, SLOTS, cfg.n_heads),
        **grouped_products(text),
        "state_step": state_step_calls(text),
        "state_readers": readers(text, shape_of(kc[0])),
        "scopes": sorted(set(re.findall(r"attn_kda/(\w+)", text))),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}}
print("LOWERED " + json.dumps(out))
""".replace("LATENT_DECODE_REPORT", _LATENT_DECODE_REPORT).replace(
    "GROUPED_PRODUCTS_REPORT", _GROUPED_PRODUCTS_REPORT).replace(
    "STATE_READERS_REPORT", _STATE_READERS_REPORT)


# The decode step of a stack whose every layer is `mla` (ISSUE 43), at
# the widths, the 32 slots and the table of `kimi-k2.7-code-ep32-6l`:
# the dense layer and one sparse layer (two experts held, so that it
# compiles in a minute), both over one latent pool.
_KIMI_DRIVER = r"""
import json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
cfg = TransformerConfig(
    vocab_size=20480, d_model=7168, n_layers=2, n_heads=64, n_kv_heads=64,
    d_head=128, d_ff=2048, d_ff_dense=18432, n_dense_layers=1,
    max_seq=17152, norm_eps=1e-5, layer_types=("mla", "mla"),
    mla_kv_rank=512, mla_rope_dim=64, mla_q_rank=1536, mla_head_gate=False,
    layer_rotary={{"mla": dict(theta=5e4, factor=64.0, original_max_seq=4096,
                              mscale_all_dim=1.0)}},
    n_experts=384, moe_top_k=8, moe_capacity_factor=None,
    moe_scoring="sigmoid", moe_route_scale=2.827, moe_shared_expert=True,
    moe_experts_held=2, moe_expert_offset=12, dtype=jnp.bfloat16,
    remat=False)
BS, WIDTH, SLOTS = 16, 1072, 32


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


params = on_chip(jax.eval_shape(
    lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
    cfg, SLOTS * WIDTH + 1, BS, n_slots=SLOTS))))
decode = decode_lib.make_serve_fns(cfg, None, block_size=BS,
                                   table_width=WIDTH)[2]
LATENT_DECODE_REPORT
GROUPED_PRODUCTS_REPORT
compiled = decode.lower(params, kc, vc, i32(SLOTS), i32(SLOTS),
                        (i32(SLOTS, WIDTH), i32(SLOTS))).compile()
text = compiled.as_text()
pool = "bf16[%s]" % ",".join(map(str, kc[0].shape))
aliased = re.search(r"input_output_alias=\{{(.*?)\}}, entry", text)
print("LOWERED " + json.dumps({{
    "device_kind": topo.devices[0].device_kind,
    "pool_bytes": kc[0].size * 2,
    "decode": {{
        "pool_ops": sorted(set(re.findall(
            r"= %s\{{\S* ([\w\-]+)\(" % re.escape(pool), text))),
        "aliased": len(re.findall(r"may-alias|must-alias",
                                  aliased.group(1))),
        **latent_decode_report(text, SLOTS, cfg.n_heads),
        **grouped_products(text),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}}}}))
""".replace("LATENT_DECODE_REPORT", _LATENT_DECODE_REPORT).replace(
    "GROUPED_PRODUCTS_REPORT", _GROUPED_PRODUCTS_REPORT)


# The trained share of the experts (ISSUE 40): the step of the cell
# `train-mellum2-ep4-seq8192` at its own shapes (one row of 8192, 8
# layers, remat `full`; `mellum2-12b-ep4-8l`'s sizes), where
# `moe._held_experts` compacts 65 536 pairs to 24 576 rows behind a
# `cond`; and a chunk's call of the two served shares, which it leaves
# alone. The optimised HLO is walked by computation: what a fall-back
# branch (the `cond`'s branch 0) calls, and what lies outside them.
_MELLUM_DRIVER = r"""
import collections, json, math, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, make_train_step
from horovod_tpu.models import moe as moe_lib
from horovod_tpu.parallel import build_mesh

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
yarn = dict(theta=5e5, factor=16.0, original_max_seq=8192, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2772588722239782)
cfg = TransformerConfig(
    vocab_size=24576, d_model=2304, n_layers=8, n_heads=32, n_kv_heads=4,
    d_head=128, d_ff=896, max_seq=8192, rope_theta=5e5, norm_eps=1e-6,
    layer_types=("sliding", "sliding", "sliding", "full") * 2,
    attn_window=1024, layer_rotary={{"sliding": {{"theta": 5e5}},
                                    "full": yarn}},
    n_experts=64, moe_top_k=8, moe_capacity_factor=None,
    moe_norm_topk_prob=True, moe_aux_loss_coef=0.001, moe_experts_held=16,
    moe_expert_offset=16, dtype=jnp.bfloat16, sp_attention="flash",
    remat=True, remat_policy="full")
PAIRS, BOUND = 65536, moe_lib.held_row_bound(65536, cfg.moe)
GROUPED_PRODUCTS_REPORT
out = {{"device_kind": topo.devices[0].device_kind, "bound": BOUND}}
init_state, step, _ = make_train_step(
    cfg, build_mesh(dp=-1, devices=topo.devices[:1]))
state = jax.eval_shape(init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))
compiled = step.lower(state, {{"tokens": jax.ShapeDtypeStruct(
    (1, 8193), jnp.int32)}}).compile()

comps, name = {{}}, None
for line in compiled.as_text().splitlines():
    head = re.match(r"(?:ENTRY )?%?([\w.\-]+) (?:\(.*\) -> .*)?\{{\s*$", line)
    if head and not line.startswith(" "):
        name = head.group(1)
        comps[name] = []
    elif line.startswith("}}"):
        name = None
    elif name is not None:
        comps[name].append(line)


def called(lines):
    for line in lines:
        for group in re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)"
                r"|(?:branch|called)_computations=\{{([^}}]*)\}}", line):
            for names in group:
                yield from (n.strip().lstrip("%") for n in names.split(",")
                            if n.strip())


def reached(roots):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in comps and c not in seen:
            seen.add(c)
            todo.extend(called(comps[c]))
    return seen


conds = [re.search(r"branch_computations=\{{%?([\w.\-]+), %?([\w.\-]+)\}}",
                   line).groups()
         for lines in comps.values() for line in lines
         if " conditional(" in line]
fall_back = reached(c[0] for c in conds)
fused = set()                   # fusions' bodies: nothing there is in HBM
for lines in comps.values():
    for line in lines:
        if " fusion(" in line:
            fused.update(re.findall(r"calls=%?([\w.\-]+)", line))
rows_of = {{"inside": collections.Counter(), "outside": collections.Counter()}}
wide = collections.Counter()     # [65536, D] / [65536, F] outside, in HBM
fills = []
for comp, lines in comps.items():
    where = "inside" if comp in fall_back else "outside"
    for line in lines:
        inst = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\((.*)",
                        line)
        if not inst:
            continue
        result, opcode, rest = inst.groups()
        if "ragged-dot-none" in rest and opcode == "custom-call":
            shapes = re.findall(r"\[(\d+),(?:2304|896)\]",
                                result + rest.split("custom_call_target")[0])
            rows_of[where].update(set(shapes))
        if where == "outside" and comp not in fused:
            shape = re.match(r"\w+\[([\d,]*)\]", result)
            dims = [int(d) for d in shape.group(1).split(",") if d] \
                if shape else []
            if dims[:1] == [PAIRS] and dims[1:] in ([2304], [896]):
                wide[opcode + " " + ",".join(map(str, dims[1:]))] += 1
            if opcode == "broadcast" and math.prod(dims) in (
                    PAIRS * 2304, PAIRS * 896):     # in whatever layout
                fills.append(result)
out["step"] = {{"conditionals": len(conds),
               "ragged_rows": {{k: sorted(v) for k, v in rows_of.items()}},
               "ragged_calls_outside": sum(
                   " custom-call(" in line and "ragged-dot-none" in line
                   for comp, lines in comps.items() if comp not in fall_back
                   for line in lines),
               "wide_outside": wide, "fills_outside": fills,
               "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
               **grouped_products(compiled.as_text())}}


# a chunk of 1024 tokens, and a decode step's rows, through the served
# shares' expert layers at the cells' own shapes
def share_call(d, f, tokens, **moe):
    share = moe_lib.MoEConfig(capacity_factor=None, **moe)
    lp = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype, sharding=one),
        jax.eval_shape(lambda: moe_lib.init_moe_params(
            jax.random.PRNGKey(0), 1, d, f, share, jnp.bfloat16)))
    text = jax.jit(lambda x, lp: moe_lib.moe_ffn_dropless(x, lp, share)
                   ).lower(jax.ShapeDtypeStruct((1, tokens, d), jnp.bfloat16,
                                                sharding=one),
                           lp).compile().as_text()
    return {{"bound": moe_lib.held_row_bound(tokens * share.top_k, share),
            "conditionals": text.count(" conditional("),
            **grouped_products(text)}}


SHARES = {{
    "trinity": (3072, 3072, 32, dict(
        n_experts=256, top_k=4, scoring="sigmoid", route_scale=2.448,
        shared_expert=True, experts_held=32)),
    "ling": (2560, 768, 64, dict(
        n_experts=512, top_k=8, scoring="sigmoid", route_scale=2.5,
        shared_expert=True, experts_held=128, expert_offset=128, n_group=8,
        topk_group=4)),
    "kimi": (7168, 2048, 32, dict(
        n_experts=384, top_k=8, scoring="sigmoid", route_scale=2.827,
        shared_expert=True, experts_held=12, expert_offset=12))}}
for name, (d, f, slots, moe) in SHARES.items():
    out[name + "_chunk"] = share_call(d, f, 1024, **moe)
    out[name + "_step"] = share_call(d, f, slots, **moe)
print("LOWERED " + json.dumps(out))
""".replace("GROUPED_PRODUCTS_REPORT", _GROUPED_PRODUCTS_REPORT)


# The two programs of the state-space kind (ISSUE 47) at the widths, the
# 256 slots and the table of `jamba2-3b`: four of its 28 layers, three
# mamba and one multi-query attention layer, a tied head.
_JAMBA_DRIVER = r"""
import collections, json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
cfg = TransformerConfig(
    vocab_size=65536, d_model=2560, n_layers=4, n_heads=20, n_kv_heads=1,
    d_head=128, d_ff=8192, max_seq=1536, norm_eps=1e-6,
    layer_types=("mamba", "mamba", "full", "mamba"), mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160, tie_embeddings=True,
    dtype=jnp.bfloat16, remat=False)
BS, WIDTH, SLOTS, CHUNK = 16, 96, 256, 512


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


params = on_chip(jax.eval_shape(
    lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
    cfg, SLOTS * WIDTH + 1, BS, n_slots=SLOTS))))
_, resume, decode, _, _ = decode_lib.make_serve_fns(
    cfg, None, block_size=BS, table_width=WIDTH)
state = "f32[%s]" % ",".join(map(str, kc[1].shape))
rows = "bf16[%s]" % ",".join(map(str, vc[1].shape))
out = {{"device_kind": topo.devices[0].device_kind,
       "state_bytes": kc[1].size * 4}}


STATE_READERS_REPORT


PAGED_DECODE_REPORT
for name, fn, args in (
        ("decode", decode,
         (i32(SLOTS), i32(SLOTS), (i32(SLOTS, WIDTH), i32(SLOTS)))),
        ("prefill_resume", resume,
         (i32(CHUNK), i32(), i32(), (i32(WIDTH), i32())))):
    compiled = fn.lower(params, kc, vc, *args).compile()
    text = compiled.as_text()
    ops = collections.Counter(
        opcode for result, opcode in
        re.findall(r"= (\S+?)\{{\S* ([\w\-]+)\(", text) if result == state)
    aliased = re.search(r"input_output_alias=\{{(.*?)\}}, entry", text)
    out[name] = {{
        "ops": ops,
        "rows_ops": collections.Counter(
            opcode for result, opcode in
            re.findall(r"= (\S+?)\{{\S* ([\w\-]+)\(", text)
            if result == rows),
        "state_readers": readers(text, state),
        "kernels": {{k: len(re.findall(
            r"custom-call\(.*/%s/pallas_call" % k, text))
            for k in ("hvd_mamba_step", "hvd_mamba_rows",
                      "hvd_mamba_scan")}},
        # a loop in XLA under the scan's scope: the form it replaced
        "scan_whiles": len(re.findall(
            r" while\(.*attn_mamba/mamba_scan", text)),
        "aliased": len(re.findall(r"may-alias|must-alias",
                                              aliased.group(1))),
        # a whole chunk's decays or states, and not a block's
        "whole_chunk_states": len(re.findall(
            r"f32\[[\d,]*512,16,5120\]", text)),
        "scopes": sorted(set(re.findall(
            r"attn_mamba/(mamba_\w+|state_write)", text))),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
        **paged_decode_report(text)}}
print("LOWERED " + json.dumps(out))
""".replace("PAGED_DECODE_REPORT", _PAGED_DECODE_REPORT).replace(
    "STATE_READERS_REPORT", _STATE_READERS_REPORT)

# The two programs of the sparse and lightning kinds (ISSUE 50) at the
# widths, the 16 slots and the table of 520 pages of 64 of
# `minicpm-sala-8l`: four of its 8 layers, two sparse and two lightning.
_SALA_DRIVER = r"""
import collections, json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
cfg = TransformerConfig(
    vocab_size=73448, d_model=4096, n_layers=4, n_heads=32, n_kv_heads=2,
    d_head=128, d_ff=16384, max_seq=33280, norm_eps=1e-6, rope_theta=1e4,
    layer_types=("sparse", "lightning", "lightning", "sparse"),
    qk_norm_per_head=True, attn_gate=True,
    embed_multiplier=12.0, residual_multiplier=0.2475, logit_divisor=16.0,
    dtype=jnp.bfloat16, remat=False)
BS, WIDTH, SLOTS, CHUNK = 64, 520, 16, 1024


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


params = on_chip(jax.eval_shape(
    lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
    cfg, SLOTS * WIDTH + 1, BS, n_slots=SLOTS))))
_, resume, decode, _, _ = decode_lib.make_serve_fns(
    cfg, None, block_size=BS, table_width=WIDTH)
pages = "bf16[%s]" % ",".join(map(str, vc[0].shape))
state = "f32[%s]" % ",".join(map(str, kc[1].shape))
out = {{"device_kind": topo.devices[0].device_kind,
       "pages_bytes": vc[0].size * 2}}
for name, fn, args in (
        ("decode", decode,
         (i32(SLOTS), i32(SLOTS), (i32(SLOTS, WIDTH), i32(SLOTS)))),
        ("prefill_resume", resume,
         (i32(CHUNK), i32(), i32(), (i32(WIDTH), i32())))):
    compiled = fn.lower(params, kc, vc, *args).compile()
    text = compiled.as_text()
    sparse = [ln for ln in text.splitlines() if "attn_sparse" in ln]
    # every array a line under the sparse scope makes: [dims]
    made = [tuple(int(d) for d in dims.split(","))
            for ln in sparse
            for dims in re.findall(r"= \(?\w+\[([\d,]+)\]", ln)]
    keys = SLOTS * WIDTH * BS * 2 * 128      # a row's whole table of keys
    aliased = re.search(r"input_output_alias=\{{(.*?)\}}, entry", text)
    out[name] = {{
        "ops": collections.Counter(
            opcode for result, opcode in
            re.findall(r"= (\S+?)\{{\S* ([\w\-]+)\(", text)
            if result in (pages, state)),
        "aliased": len(re.findall(r"may-alias|must-alias",
                                  aliased.group(1))),
        "whole_table_arrays": sorted(
            d for d in set(made)
            if int(np.prod(d)) >= keys and d != vc[0].shape),
        # the widest gather of pages under the sparse scope, in pages a
        # row and KV head: [rows, heads, pages, 64, 128]
        "gathered_pages": max(
            [d[2] for d in made if len(d) == 5 and d[0] == SLOTS
             and d[3:] == (BS, 128)], default=0),
        # the Pallas calls under the chunk's attention, and the float32
        # arrays there that hold a row of 1024 scores or more a query
        # over a megabyte of them (a KV head's [.., 1024, 1024])
        "attend_kernels": sum(
            "tpu_custom_call" in ln and "sparse_attend" in ln
            for ln in sparse),
        "attend_scores": sorted(set(
            tuple(int(d) for d in dims.split(","))
            for ln in sparse if "sparse_attend" in ln
            for dims in re.findall(r"= \(?f32\[([\d,]+)\]", ln)
            if int(dims.split(",")[-1]) >= 1024
            and int(np.prod([int(d) for d in dims.split(",")]))
            >= 1024 * 1024)),
        # the Pallas calls under the chunk's selection, and the float32
        # arrays there of a chunk's scores over the table's kernels
        # ([.., 1024, 520 * 4]) or more
        "select_kernels": sum(
            "tpu_custom_call" in ln and "sparse_select" in ln
            for ln in sparse),
        "select_scores": sorted(set(
            tuple(int(d) for d in dims.split(","))
            for ln in sparse if "sparse_select" in ln
            for dims in re.findall(r"= \(?f32\[([\d,]+)\]", ln)
            if int(np.prod([int(d) for d in dims.split(",")]))
            >= CHUNK * WIDTH * 4)),
        # the dimension that lies in the lanes of each top-k's sort
        # under the selection: 1 is the chunk's queries
        "select_sort_lanes": [
            int(minor) for ln in sparse
            if " sort(" in ln and "sparse_select" in ln
            for minor in re.findall(
                r"= \(f32\[1,%d,2,%d\]\{{(\d)," % (CHUNK, WIDTH), ln)],
        "scopes": sorted(set(re.findall(
            r"attn_sparse/(kv_write|sparse_\w+)"
            r"|attn_lightning/(lightning_\w+|state_write)", text))),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}}
    out[name]["scopes"] = sorted({{a or b for a, b in out[name]["scopes"]}})
print("LOWERED " + json.dumps(out))
"""


@functools.lru_cache(maxsize=None)
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
    # Mosaic compiled the forward and the backward's dkv and dq kernels
    # at the smoke's shape and at long sequence ...
    assert out["flash_1024"]["kernels"] >= 3, out
    assert out["flash_8192"]["kernels"] >= 3, out
    # ... and the train step reaches all three on one chip and on
    # dp2 x fsdp2, where the compiled step takes its state fsdp-sharded.
    assert out["step_1"] >= 3 and out["step_4"] >= 3, out
    assert out["step_1_bwd"] == [True, True], out
    assert out["step_4_bwd"] == [True, True], out
    assert out["step_1_wq_shard"] == [2, 256, 256], out
    assert out["step_4_wq_shard"] == [2, 128, 256], out


def test_flash_backward_holds_no_score_tensor_at_the_cells_shapes():
    """The counter that says the Pallas backward engages (PR 33): at the
    training cells' attention shapes the v5e compiler's forward +
    backward holds three kernels, no operand or result with a
    ``4096,4096]`` in its shape (the XLA backward wrote four, 1 GB each
    in bf16), and under half a gigabyte of temporaries."""
    out = _compile_for_v5e(_DRIVER)
    for case in ("flash_cell_gqa", "flash_cell_mha"):
        got = out[case]
        assert got["kernels"] == 3, (case, got)
        assert got["score_shapes"] == 0, (case, got)
        assert got["temp_bytes"] < 0.5e9, (case, got)


def test_windowed_flash_compiles_to_three_kernels_at_the_window_cells_shape():
    """ISSUE 34: at ``[32, 8192, 128]`` over 4 kv heads with a window
    of 1024 the v5e compiler's forward + backward is exactly three
    kernels, nothing with an ``8192,8192]`` in its shape and under half
    a gigabyte of temporaries; so is a full layer's at that shape."""
    out = _compile_for_v5e(_DRIVER)
    for case in ("flash_window_cell", "flash_full_cell"):
        got = out[case]
        assert got["kernels"] == 3, (case, got)
        assert got["score_shapes"] == 0, (case, got)
        assert got["temp_bytes"] < 0.5e9, (case, got)
    assert out["flash_window_cell"]["digest"] != \
        out["flash_full_cell"]["digest"]


# sha256 of the v5e-compiled forward + backward WITHOUT a window, taken
# on the tree before the window was built (PR 33's; jax 0.9.0, libtpu
# 0.0.34), with everything that names a source line stripped
# (``program_digest`` in the driver).
_BEFORE_THE_WINDOW = {
    "flash_cell_gqa":
        "822bf4bc03a7552bc9f8ab0a7f3d78bb673ce105a1ff4868bf64448dccbf9c12",
    "flash_cell_mha":
        "3262fd4f61149693568e5f3d551929241aedd981aac1c126d1861539de8cc7b4",
    "flash_8192":
        "6abca45e758bd5fcd331d8871b23aca5d6af3621382c0b034c0f49fd85b9493f",
}


def test_without_a_window_the_kernels_are_the_ones_before_it():
    """``window=None`` compiles to the kernels and the program that the
    training cells ran before ``ops/flash_attention.py`` knew a window:
    the existing cells' attention did not move."""
    out = _compile_for_v5e(_DRIVER)
    for case, digest in _BEFORE_THE_WINDOW.items():
        assert out[case]["digest"] == digest, case


def test_flash_backward_compiles_at_32768():
    """O(T) in HBM at every length, which is what the q-chunked XLA
    backward existed for: a row of 32768 compiles, with nothing
    score-sized and temporaries far under the chip's memory."""
    got = _compile_for_v5e(_DRIVER)["flash_32768"]
    assert got["kernels"] == 3 and got["score_shapes"] == 0, got
    assert got["temp_bytes"] < 1e9, got


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


@pytest.mark.parametrize("bucket", [1024, 2048])
def test_prefill_attends_through_the_flash_forward_on_v5e(bucket):
    """ISSUE 35: the cold prefill of `mistral-7b-v0.3-16l` at the chat
    cell's largest bucket and the batch cell's largest is compiled with
    ONE Mosaic call, ``hvd_flash_fwd`` under ``attn``, that takes the
    queries' 32 heads and K and V with their 8 (the group is the
    kernel's index map: no copy of them repeated to 32), holds nothing
    with a ``[32, bucket, bucket]`` shape (the float32 scores, their
    mask and their bf16 copy), and allocates under 0.3 GB of temporaries:
    0.07 GB at 2048, where the dense form had 1.12 GB."""
    got = _compile_for_v5e(_SERVE_DRIVER)["prefill_%d" % bucket]
    assert len(got["kernels"]) == 1, got
    assert re.search(r"^jit\(prefill\)/.*\battn/hvd_flash_fwd\b",
                     got["kernels"][0]), got
    assert re.findall(r"bf16\[(\d+),(\d+),128\]",
                      got["kernel_operands"][0]) == [
        ("32", str(bucket)), ("8", str(bucket)), ("8", str(bucket))], got
    assert got["score_shapes"] == 0, got
    assert got["temp_bytes"] < 0.3e9, got


def test_a_short_prefill_keeps_the_dense_form_on_v5e():
    """... and the chat cell's commonest bucket, 256, keeps the dense
    form (``decode._DENSE_PROMPT``: there the chip ran it faster than
    the kernel): no Mosaic call, ``[32, 256, 256]`` scores, 8 MB of them in
    float32, which the compiler does not even count as temporaries."""
    got = _compile_for_v5e(_SERVE_DRIVER)["prefill_256"]
    assert got["kernels"] == [] and got["score_shapes"] > 0, got
    assert got["temp_bytes"] < 0.01e9, got


def test_two_cache_serve_programs_copy_neither_cache_nor_experts_on_v5e():
    """ISSUE 32: the programs of a configuration with layers of several
    kinds update both caches where they lie and take each layer's
    experts as they lie. The compiled ``decode`` and ``prefill_resume``
    hold no copy of the pool, of the rings or of a layer's experts (a
    slice of a stack of layers was one: 1.8 GB a step at the published
    widths), only the writes of ``kv_write`` on the donated caches, and
    the grouped matmuls reach Mosaic."""
    out = _compile_for_v5e(_MIXED_DRIVER)
    for program in ("decode", "prefill_resume"):
        got = out[program]
        # (a "custom-call" of the experts' shape is the compiler
        # staging one layer's matrix in fast memory ahead of its
        # kernel, which it does at this size and not at 604 MB; a
        # "copy-done" of it is the same staging as an asynchronous
        # copy into that memory, `S(1)` in its layout, which the
        # compiler chose for one layer once the window layers' steps
        # were Pallas calls too: ISSUE 59)
        assert set(got["ops"]) <= {
            "pool parameter", "pool get-tuple-element", "pool fusion",
            "pool scatter", "pool bitcast", "rings parameter",
            "rings get-tuple-element", "rings fusion", "rings scatter",
            "rings bitcast", "experts parameter",
            "experts get-tuple-element", "experts custom-call",
            "experts copy-done"}, (
                program, got)
        assert got["kernels"] >= 9, (program, got)     # 3 a sparse layer
        # ... and since ISSUE 61 each of them is the Pallas call that
        # takes the experts where they lie, a matrix at a time
        assert got["grouped_kernels"] == 9, (program, got)
        assert got["ragged_dots"] == 0, (program, got)
        assert got["kernel_products_share"] == 1.0, (program, got)
        # (the writes are scatters, or at some shapes an update of a
        # reshaped view; either way on the donated buffer:) all the
        # program allocates, the pages it gathers to attend over and
        # the matrices it stages among them, is under the rings' size,
        # and with a copy of either cache it would not be
        assert got["temp_bytes"] < out["rings_bytes"], (program, got)


@pytest.mark.parametrize("program", ["decode", "prefill_resume"])
def test_state_and_latent_pool_are_updated_where_they_lie_on_v5e(program):
    """ISSUE 38: decode at 64 slots and a chunk of 1024 over a table of
    17 408 positions compile for the v5e with the recurrent state, the
    convolution's rows and the latent pool aliased in and out; no copy
    of the state array (570 MB here, 0.85 GB at the cell's six kda
    layers) or of the pool (1.4 GB), only the in-place writes of
    ``state_write`` and ``kv_write`` (a decode step's state through
    ``hvd_state_step`` since ISSUE 62); no float32 tensor of a chunk's
    queries against the whole table (2.3 GB over 32 heads: the latent
    attention goes a key block of 1024 at a time); and what a call
    allocates is bounded: the scan's blocks and one key block's scores
    for a chunk, one key block's gathered latents for a decode step."""
    out = _compile_for_v5e(_LING_DRIVER)
    got = out[program]
    assert got["aliased"] == 3, got            # state, conv rows, pool
    handed_on = {"state parameter", "state get-tuple-element",
                 "state bitcast", "pool parameter", "pool get-tuple-element",
                 "pool bitcast", "pool fusion", "pool scatter",
                 "pool dynamic-update-slice"}
    if program == "decode":
        # ISSUE 62: a kda layer's step is ONE Pallas call under
        # ``attn_kda/kda_step``, the state array's only reader (the XLA
        # form was a fusion over all 65 slots a layer that decayed,
        # reduced and wrote, and its dynamic-update-slice)
        assert set(got["ops"]) <= handed_on | {"state custom-call"}, got
        assert got["state_step"] == ["attn_kda/kda_step"] * 2, got
        assert got["state_readers"] == {"custom-call": 2}, got
    else:
        assert set(got["ops"]) <= handed_on | {
            "state fusion", "state dynamic-update-slice",
            "state scatter"}, got
        assert got["state_step"] == [], got
    step = "kda_step" if program == "decode" else "kda_scan"
    assert set(got["scopes"]) >= {"kda_conv", "kda_gates", step,
                                  "state_write"}, got
    assert got["scores_of_the_table"] == 0, got
    limit = {"decode": 0.25e9, "prefill_resume": 0.9e9}[program]
    assert got["temp_bytes"] < limit < out["pool_bytes"], got


def test_a_chunk_s_latent_attention_is_the_keys_kernel_on_v5e():
    """ISSUE 44: a chunk of 1024 at Ling's 32 heads compiles for the v5e
    with the flash forward over keys that carry their positions under
    ``attn/attn_mla/mla_attend``, in the loop over key blocks, and
    holds no float32 ``[32, 1024, 1024]`` tensor (a key
    block's scores, 134 MB, and the softmax's passes over them: what the
    einsum form wrote); a decode step has a Pallas call of its own
    (ISSUE 45: ``hvd_latent_decode``, below), under the same scope."""
    out = _compile_for_v5e(_LING_DRIVER)
    chunk, step = out["prefill_resume"], out["decode"]
    assert chunk["keys_kernel_paths"] == [
        "jit(prefill_resume)/attn/attn_mla/mla_attend/while/body/"
        "hvd_flash_keys_fwd"], chunk
    assert chunk["scores_of_a_key_block"] == 0, chunk
    assert chunk["latent_decode_calls"] == 0, chunk
    assert step["keys_kernel_paths"] == [], step
    assert step["latent_decode_paths"] == [
        "jit(decode)/attn/attn_mla/mla_attend/jit(_decode)/"
        "hvd_latent_decode"], step


@pytest.mark.parametrize("shapes", ["ling", "kimi"])
def test_a_decode_step_s_latent_attention_reads_the_pool_where_it_lies(
        shapes):
    """ISSUE 45: ``jit(decode)`` compiled for the v5e at Ling's shapes
    (64 rows, 32 heads, one mla layer of three) and at Kimi's (32 rows,
    64 heads, two mla layers over one pool) holds the Pallas call
    ``hvd_latent_decode`` once a mla layer, under the scope the
    benchmark's reader matches; no key block of 1024 positions gathered
    for every row (``bf16[rows * 64, 16, 640]``: 84 MB a turn of the
    loop it replaced) and no float32 ``[rows, heads, 1, 1024]`` scores;
    the pool aliased in and out and never copied (the kernel takes the
    whole array where it lies, the layer an index), and what a call
    allocates far under the pool's size."""
    driver, layers, large = {"ling": (_LING_DRIVER, 1, 3),
                             "kimi": (_KIMI_DRIVER, 2, 1)}[shapes]
    out = _compile_for_v5e(driver)
    got = out["decode"]
    assert got["latent_decode_calls"] == layers, got
    assert got["latent_decode_paths"] == [
        "jit(decode)/attn/attn_mla/mla_attend/jit(_decode)/"
        "hvd_latent_decode"], got
    assert got["gathered_key_blocks"] == 0, got
    assert got["scores_of_a_decode_key_block"] == 0, got
    assert got["aliased"] == large, got
    if shapes == "kimi":
        assert set(got["pool_ops"]) <= {
            "parameter", "get-tuple-element", "bitcast", "fusion",
            "scatter", "dynamic-update-slice"}, got
    assert got["temp_bytes"] < 0.25e9 < out["pool_bytes"], got


def test_the_trained_share_runs_its_bound_s_rows_outside_the_fall_back():
    """ISSUE 40: the cell's step compiled for the v5e holds two
    ``cond``s a layer (forward and backward; the recomputed forward's is
    dropped with its unused ``y``); every grouped matmul outside the
    fall-back branches takes ``[24576, ·]`` rows, twelve a layer, and
    only inside them ``[65536, ·]``; outside them nothing ``[65536,
    896]`` exists at all (the SwiGLU and its casts run the bound's
    rows), what is ``[65536, 2304]`` there is the reads of the ``N·K``
    slots (a gather forward, two and the masked cotangent backward: the
    token side, ROADMAP A11's remainder), and nothing that large is a
    broadcast: no residual of the untaken branch is filled with
    zeros."""
    out = _compile_for_v5e(_MELLUM_DRIVER)
    got = out["step"]
    assert out["bound"] == 24576, out
    assert got["conditionals"] == 16, got
    assert got["ragged_rows"] == {"inside": ["65536"],
                                  "outside": ["24576"]}, got
    assert got["ragged_calls_outside"] == 8 * 12, got
    assert not any(k.endswith(" 896") for k in got["wide_outside"]), got
    moved = {k: v for k, v in got["wide_outside"].items()
             if k.split()[0] not in ("parameter", "get-tuple-element",
                                     "bitcast", "copy-start", "copy-done")}
    assert set(moved) == {"fusion 2304"} and moved["fusion 2304"] <= 8 * 4, \
        got
    assert got["fills_outside"] == [], got
    assert got["temp_bytes"] < 3.2e9, got


@pytest.mark.parametrize("share", ["trinity_chunk", "ling_chunk"])
def test_a_served_chunk_keeps_the_whole_row_form_with_no_cond(share):
    """... and a served chunk's call (4 096 pairs over 32 of 256
    experts, 8 192 over 128 of 512) is below the size at which the
    bound engages: no ``cond``, three grouped matmuls (since ISSUE 61
    the kernel's, below)."""
    got = _compile_for_v5e(_MELLUM_DRIVER)[share]
    assert got["bound"] is None and got["conditionals"] == 0, got
    assert got["grouped_kernels"] + got["ragged_dots"] == 3, got


@pytest.mark.parametrize("call", ["trinity_chunk", "trinity_step",
                                  "ling_chunk", "ling_step"])
def test_a_served_share_s_products_are_the_kernel_s(call):
    """ISSUE 61: ``moe_ffn_dropless`` over a chip's share of the
    experts at the trinity and ling cells' own shapes (32 of 256
    experts of ``[3072, 3072]``: two whole matrices of 18.9 MB are the
    largest the kernel's buffer holds, and the call compiles for the
    v5e under its ``vmem_limit_bytes``; 128 of 512 of ``[2560, 768]``),
    a chunk of 1024 tokens and a decode step's rows: all three products
    are ``hvd_grouped_matmul`` calls, none is left with the compiler,
    and the engine's counter reads 1.0."""
    got = _compile_for_v5e(_MELLUM_DRIVER)[call]
    assert got["grouped_kernels"] == 3 and got["ragged_dots"] == 0, got
    assert got["kernel_products_share"] == 1.0, got


@pytest.mark.parametrize("program", ["decode", "prefill_resume"])
def test_ling_s_programs_hold_the_kernel_alone(program):
    """... and in ling's two programs at the cell's 128 held experts,
    64 slots and a chunk of 1024 (two sparse layers here): three
    kernels a sparse layer, no ``ragged-dot``."""
    got = _compile_for_v5e(_LING_DRIVER)[program]
    assert got["grouped_kernels"] == 3 * 2 and got["ragged_dots"] == 0, got
    assert got["kernel_products_share"] == 1.0, got


@pytest.mark.parametrize("call", ["kimi_decode", "kimi_chunk", "kimi_step",
                                  "mellum_step"])
def test_the_rule_s_other_side_keeps_the_compiler_s_products(call):
    """ISSUE 61, the shares that ``grouped_matmul.taken`` leaves with
    the compiler by their shapes: Kimi's (two matrices of 29.4 MB are
    over the buffer: its decode program with two held experts, and the
    cell's 12 in a chunk and a step) and mellum's trainer (1536 rows an
    expert and more): no kernel, the ``ragged-dot`` calls they had, and
    a counter of 0.0."""
    got = (_compile_for_v5e(_KIMI_DRIVER)["decode"] if call == "kimi_decode"
           else _compile_for_v5e(_MELLUM_DRIVER)[call.replace("mellum_", "")])
    assert got["grouped_kernels"] == 0, got
    assert got["ragged_dots"] >= 3, got
    assert got["kernel_products_share"] == 0.0, got


@pytest.mark.parametrize("program", ["decode", "prefill_resume"])
def test_the_state_space_programs_lower_for_the_v5e(program):
    """ISSUE 47: a decode step at 256 slots and a chunk of 512 of a
    stack of mamba layers beside a multi-query attention layer compile
    for the v5e with K, V, the selective scan's state and the
    convolution's rows aliased in and out, no copy of the state array
    (253 MB here, 2.19 GB at the cell's 26 layers), a chunk's decays
    and states never whole (``mamba_scan`` goes a position at a time:
    a float32 ``[512, 16, 5120]`` would be 168 MB a layer), every scope
    the benchmark reads by name in the program, and what a call
    allocates under the state's size."""
    out = _compile_for_v5e(_JAMBA_DRIVER)
    got = out[program]
    assert got["aliased"] == 4, got
    assert set(got["ops"]) <= {"parameter", "get-tuple-element", "bitcast",
                               "fusion", "dynamic-update-slice",
                               "scatter"}, got
    assert got["whole_chunk_states"] == 0, got
    step = "mamba_step" if program == "decode" else "mamba_scan"
    assert set(got["scopes"]) == {"mamba_proj", "mamba_conv", step,
                                  "state_write"}, got
    if program == "decode":
        # ISSUE 48: a mamba layer's step is the two Pallas calls, one
        # reader of the state array a layer (the XLA form had a fusion
        # that reduced to y and a second that wrote the state: six), and
        # nothing but the rows' kernel makes a whole array of the
        # convolution's rows (the scatter by slot was a fusion over all
        # of it a layer; the copy-done is this small model's array
        # moved whole into fast memory, which 26 layers' is not)
        assert got["kernels"] == {"hvd_mamba_step": 3, "hvd_mamba_rows": 3,
                                  "hvd_mamba_scan": 0}, got
        assert got["state_readers"] == {"custom-call": 3}, got
        assert set(got["rows_ops"]) <= {"parameter", "bitcast", "copy-done",
                                        "custom-call"}, got
    else:
        # ISSUE 49: a mamba layer's chunk is one Pallas call, and no
        # loop a position at a time is left under its scope
        assert got["kernels"] == {"hvd_mamba_step": 0, "hvd_mamba_rows": 0,
                                  "hvd_mamba_scan": 3}, got
        assert got["scan_whiles"] == 0, got
    assert got["temp_bytes"] < out["state_bytes"], got


@pytest.mark.parametrize("program", ["decode", "prefill_resume"])
def test_the_sparse_and_lightning_programs_lower_for_the_v5e(program):
    """ISSUE 50: a decode step at 16 slots and a chunk of 1024 of sparse
    layers beside lightning layers, over tables of 520 pages of 64,
    compile for the v5e with K pages, compressed keys, V pages and the
    decayed state aliased in and out and no copy of a pool of pages
    (545 MB a kind's array at the cell's two layers). A decode step's
    sparse attention makes no array the size of its rows' whole tables
    of keys (``[16, 520 * 64, 2, 128]``: what a full layer's step
    gathers) and gathers at most 128 pages a row and KV head (a row
    below the dense length all of its own, a row past it its 64
    chosen); a chunk holds no key block's scores at all (ISSUE 51: they
    are tiles in VMEM) and no block scores over the table's kernels
    (ISSUE 53: the same). Every scope the benchmark reads by name is in
    the program."""
    out = _compile_for_v5e(_SALA_DRIVER)
    got = out[program]
    assert got["aliased"] == 4, got
    # (the copy-done and its custom-call are this small model's 71 MB of
    # state moved whole into fast memory, which the cell's 214 MB are
    # not: benchmark/tools/sala_compile_only.py shows none there)
    assert set(got["ops"]) <= {"parameter", "get-tuple-element", "bitcast",
                               "fusion", "dynamic-update-slice", "scatter",
                               "copy-start", "copy-done",
                               "custom-call"}, got
    assert got["whole_table_arrays"] == [], got
    step = program == "decode"
    assert set(got["scopes"]) == {
        "kv_write", "sparse_compress", "sparse_select", "sparse_attend",
        "lightning_step" if step else "lightning_scan", "state_write"}, got
    if step:
        assert 0 < got["gathered_pages"] <= 128, got
    # ISSUE 51: a chunk attends through the Pallas flash forward under
    # its page mask, one call a sparse layer, and holds no float32
    # score array at all; a decode step is what it was (its rows'
    # scores over the 128 pages they gather)
    assert got["attend_kernels"] == (0 if step else 2), got
    assert got["attend_scores"] == (
        [[16, 2, 16, 128 * 64]] if step else []), got
    # ISSUE 53: a chunk's block scores are made by a kernel of their
    # own, one call a sparse layer, and no float32 array of a chunk's
    # scores over the table's kernels (f32[1, 2, 16, 1024, 2080] and six
    # more of its shapes before) is left under the selection; a decode
    # step holds no such call
    assert got["select_kernels"] == (0 if step else 2), got
    assert got["select_scores"] == [], got
    # the kernel hands its scores over with the queries in the lanes,
    # so the top-k behind it sorts 1024 queries at a time, as it did
    # behind the XLA form: laid [1024, 2, 520] as a first form of the
    # kernel laid them, a layer's sort took 5 ms a call for 0.4
    assert got["select_sort_lanes"] == ([] if step else [1, 1]), got
    # the chunk program's temporaries: 854.9 MB before ISSUE 53 (three
    # live copies of a layer's 272 MB of scores), 62.6 MB since; the
    # decode program's 90.2 MB are what they were
    assert got["temp_bytes"] < ((96 if step else 80) << 20), got


# Heads of 64 behind the block tables (ISSUE 54): a conv layer, an
# attention layer of 32 / 8 heads of 64 and the 32 experts of a mixture
# held whole, four a token (ISSUE 57: the pairs an expert decide who
# runs the grouped products), at LFM2-8B-A1B's widths, 128 slots and
# tables of 160 pages.
_LFM2_DRIVER = r"""
import json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
cfg = TransformerConfig(
    vocab_size=8192, d_model=2048, n_layers=3, n_heads=32, n_kv_heads=8,
    d_head=64, d_ff=1792, d_ff_dense=7168, n_dense_layers=1, max_seq=2560,
    norm_eps=1e-5, layer_types=("conv", "full", "conv"), conv_taps=3,
    layer_rotary={{"full": dict(theta=1e6)}}, qk_norm_per_head=True,
    tie_embeddings=True, n_experts=32, moe_top_k=4, moe_capacity_factor=None,
    moe_scoring="sigmoid", dtype=jnp.bfloat16, remat=False)
BS, WIDTH, SLOTS = 16, 160, 128
EXPERTS = 32 * 2048 * 1792          # a layer's stack of one matrix


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


params = on_chip(jax.eval_shape(
    lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
    cfg, SLOTS * WIDTH + 1, BS, n_slots=SLOTS))))
fns = dict(zip(("prefill", "prefill_resume", "decode"),
               decode_lib.make_serve_fns(cfg, None, block_size=BS,
                                         table_width=WIDTH)))
args = {{"prefill_resume": (i32(1024), i32(), i32(), (i32(WIDTH), i32())),
        "decode": (i32(SLOTS), i32(SLOTS), (i32(SLOTS, WIDTH), i32(SLOTS)))}}
pool = "bf16[%s]" % ",".join(map(str, kc[0].shape))
PAGED_DECODE_REPORT
out = {{"device_kind": topo.devices[0].device_kind, "pool": pool,
       "pool_bytes": kc[0].size * 2}}
for name, a in args.items():
    compiled = fns[name].lower(params, kc, vc, *a).compile()
    text = compiled.as_text()
    aliased = re.search(r"input_output_alias=\{{(.*?)\}}, entry", text)
    out[name] = {{
        # copies of anything as large as a pool, whatever its shape
        "pool_copies": sum(
            int(np.prod([int(d) for d in dims.split(",")])) >= kc[0].size
            for dims in re.findall(r"= bf16\[([\d,]+)\]\{{\S* copy\(",
                                   text)),
        "aliased": len(re.findall(r"may-alias|must-alias",
                                  aliased.group(1))),
        "ragged_dots": len(re.findall(r"%ragged-dot-(?!metadata)\S+ = ",
                                      text)),
        "grouped_kernels": len(re.findall(
            r'custom-call\([^\n]*op_name="[^"]*moe_experts/[^"]*'
            r'hvd_grouped_matmul', text)),
        # copies or slices of anything as large as a layer's experts
        "expert_moves": sum(
            int(np.prod([int(d) for d in dims.split(",")])) >= EXPERTS
            for dims in re.findall(
                r"= bf16\[([\d,]+)\]\{{\S* (?:copy|slice|dynamic-slice)\(",
                text)),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
        **paged_decode_report(text)}}


# the rule's other side (ISSUE 57): one layer of OLMoE's trainer, 65 536
# pairs over 64 experts of [2048, 1024], forward and backward
from horovod_tpu.models import moe as moe_lib
olmoe = moe_lib.MoEConfig(n_experts=64, top_k=8, capacity_factor=None,
                          norm_topk_prob=False)
lp = on_chip(jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
    jax.eval_shape(lambda: moe_lib.init_moe_params(
        jax.random.PRNGKey(0), 1, 2048, 1024, olmoe, jnp.bfloat16))))
text = jax.jit(jax.grad(
    lambda x, lp: moe_lib.moe_ffn_dropless(x, lp, olmoe)[0].astype(
        jnp.float32).sum(), (0, 1))).lower(
    jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16, sharding=one), lp
    ).compile().as_text()
out["olmoe_grad"] = {{
    "ragged_dots": len(re.findall(
        r"%ragged-dot-(?!metadata)\S+ = [^\n]* custom-call\(", text)),
    "grouped_kernels": text.count("hvd_grouped_matmul")}}
print("LOWERED " + json.dumps(out))
""".replace("PAGED_DECODE_REPORT", _PAGED_DECODE_REPORT)


def test_a_trainer_s_whole_mixture_keeps_the_compiler_s_grouped_products():
    """ISSUE 57, the rule's other side: ``jax.grad`` of
    ``moe_ffn_dropless`` at OLMoE's trainer's shapes (``[2, 4096,
    2048]``, 64 experts of ``[2048, 1024]``, 8 a token: 1024 pairs an
    expert, which the matrix unit bounds) holds the compiler's
    ``ragged-dot`` kernels, three forward and the two cotangents of
    each backward, and no ``hvd_grouped_matmul``: the trainer's program
    is what it was."""
    got = _compile_for_v5e(_LFM2_DRIVER)["olmoe_grad"]
    assert got["grouped_kernels"] == 0, got
    assert got["ragged_dots"] >= 3 + 6, got


@pytest.mark.parametrize("program", ["decode", "prefill_resume"])
def test_pages_of_narrow_heads_are_never_copied_whole(program):
    """ISSUE 54: with 8 KV heads of 64 a position's heads lie in a page
    as ONE row of 512 (``kv_cache.page_tail``). As ``[.., 16, 8, 64]``
    the v5e keeps the pool with its blocks innermost and a decode step
    of the cell's programs held 20 copies of the 1 GB pool and 7.1 GB of
    temporaries. Here: K and V pages and the convolution's rows aliased
    in and out, no copy of anything as large as a pool, a program's
    temporaries under 0.1 GB (a decode step's gathered tables took 1.06
    GB before ISSUE 55; a chunk's 20 MB), and the whole mixture's three
    grouped products a sparse layer in the program: since ISSUE 57 each
    is ``hvd_grouped_matmul`` under ``moe_experts`` (16 pairs an expert
    in a step, 128 in the chunk: the matrices' bytes bound both), none
    the compiler's ``ragged-dot``, and the kernel reads the matrices
    where the parameters lie (no copy or slice as large as a layer's
    ``bf16[32,2048,1792]``)."""
    out = _compile_for_v5e(_LFM2_DRIVER)
    got = out[program]
    assert out["pool"] == "bf16[1,20481,16,512]", out
    assert got["aliased"] == 3, got
    assert got["pool_copies"] == 0, got
    assert got["grouped_kernels"] == 2 * 3, got
    assert got["ragged_dots"] == 0, got
    assert got["expert_moves"] == 0, got
    # a decode step gathers no table (ISSUE 55; 1.06 GB of them before)
    assert got["temp_bytes"] < 0.1e9, got


@pytest.mark.parametrize("shapes", ["lfm2", "trinity", "jamba"])
def test_a_decode_step_s_full_layers_read_the_pools_where_they_lie(shapes):
    """ISSUE 55: ``jit(decode)`` compiled for the v5e at the three page
    shapes the cells have (LFM2's rows of 512 behind tables of 160 at
    128 slots; trinity's ``[16, 8, 128]`` behind 512; jamba's ``[16, 1,
    128]`` behind 96 at 256 slots) holds the Pallas call
    ``hvd_paged_decode`` once a full layer, under ``attn_full``, and
    nothing under ``attn_full/kv_gather`` (every row's whole table,
    ``bf16[rows * width, 16, ...]`` a pool and layer before); a chunk
    program holds no such call and keeps its one row's gather."""
    out = _compile_for_v5e({"lfm2": _LFM2_DRIVER, "trinity": _MIXED_DRIVER,
                            "jamba": _JAMBA_DRIVER}[shapes])
    step, chunk = out["decode"], out["prefill_resume"]
    # (trinity's three window layers here read their rings through the
    # same call since ISSUE 59, under their own scope: below)
    assert step["paged_decode_by_path"] == {
        "jit(decode)/attn/attn_full/jit(_decode)/hvd_paged_decode": 1,
        **({"jit(decode)/attn/attn_window/jit(_decode)/hvd_paged_decode": 3}
           if shapes == "trinity" else {})}, step
    assert step["table_gathers"] == 0, step
    assert chunk["paged_decode_calls"] == 0, chunk
    assert chunk["table_gathers"] > 0, chunk


def test_a_decode_step_s_window_layers_read_the_rings_where_they_lie():
    """ISSUE 59: ``jit(decode)`` compiled for the v5e over caches of the
    trinity cell's shapes (32 rows of 48 / 8 heads in 33 slots' rings of
    5136 places, four window layers and a full one) holds
    ``hvd_paged_decode`` once a window layer under ``attn_window``
    (``ring_decode``: the rings read as pages where they lie) beside
    the full layer's one under ``attn_full``; nothing but the stacked
    rings themselves (the parameter, ``kv_write``'s scatters on the
    donated array, the bitcast to pages) has a ring's 5136 places among
    its dimensions (before: K and V of every window layer sliced out
    and copied, ``bf16[1,33,5136,8,128]`` eight times a step, 347 MB
    each, and float32 ``[33,8,6,1,5136]`` scores), and the program's
    temporaries are under 0.1 GB (0.41 GB before at the published
    widths)."""
    got = _compile_for_v5e(_MIXED_DRIVER)["decode_cell"]
    assert got["rings"] == "bf16[4,33,5136,8,128]", got
    assert got["paged_decode_by_path"] == {
        "jit(decode)/attn/attn_full/jit(_decode)/hvd_paged_decode": 1,
        "jit(decode)/attn/attn_window/jit(_decode)/hvd_paged_decode": 4}, got
    assert got["ring_wide"] == [], got
    assert got["temp_bytes"] < 0.1e9, got


# The calls of ``ops/paged_decode.py`` that pass no ``skip`` (ISSUE 59
# gave the kernel one more prefetched scalar a row for a ring's window,
# which begins in the middle of a page), each alone at its cell's
# shapes: every Mosaic kernel of the compiled call (`kernel_digest`).
_KERNELS_DRIVER = r"""
import json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

jax.default_backend = lambda: "tpu"

from horovod_tpu.ops import paged_decode as paged_lib

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])


def sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one)


def i32(*shape):
    return sds(*shape, dtype=jnp.int32)


KERNEL_DIGEST


def kernels(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [kernel_digest(body)[:16] for body in re.findall(
        r'"body":"([A-Za-z0-9+/=]+)"', text)]


def paged(rows, heads, width, pool):
    return kernels(
        lambda q, k, v, t, n: paged_lib.paged_decode(q, k, v, 0, t, n),
        sds(rows, heads, pool[-1] if len(pool) == 5 else 64), sds(*pool),
        sds(*pool), i32(rows, width), i32(rows))


def stats(pool):
    return kernels(
        lambda q, k, v, t, n: paged_lib.paged_decode_stats(
            q, k, v, 1, t, n, key_positions=256),
        sds(16, 32, 128), sds(*pool), sds(*pool), i32(16, 128), i32(16))


out = {{
    "device_kind": topo.devices[0].device_kind,
    # LFM2: 128 rows of 32 / 8 heads of 64 behind tables of 160, rows of 512
    "lfm2": paged(128, 32, 160, (1, 20481, 16, 512)),
    # trinity's full layer: 32 rows of 48 / 8 heads behind tables of 536
    "trinity_full": paged(32, 48, 536, (1, 17153, 16, 8, 128)),
    # jamba: 256 rows of 20 heads over one behind tables of 96
    "jamba": paged(256, 20, 96, (3, 24577, 16, 1, 128)),
    # EvaByte: the window's rows as pages of 16, and the summaries' pages
    "eva_window": stats((2, 17 * 128, 16, 32, 128)),
    "eva_summaries": stats((2, 2049, 16, 32, 128)),
    # Kimi: 32 rows of 64 heads over latents of 640 behind tables of 1088
    "kimi": kernels(
        lambda q, p, t, n: paged_lib.latent_decode(
            q, p, 1, t, n, rank=512, scale=0.1),
        sds(32, 64, 640), sds(2, 34817, 16, 640), i32(32, 1088), i32(32)),
}}
print("LOWERED " + json.dumps(out))
""".replace("KERNEL_DIGEST", _KERNEL_DIGEST)

# ... as the driver above printed them on the tree before ISSUE 59
# (4b5f844, unpacked beside this one: the same driver, its root there).
_BEFORE_THE_SKIP = {
    "lfm2": ["8ebd5fdae6605efd"],
    "trinity_full": ["e1a51dd151ca042f"],
    "jamba": ["89ade33d6fa13a18"],
    "eva_window": ["29b3badb245ecf6f"],
    "eva_summaries": ["acb493729cfa3ca2"],
    "kimi": ["2c0aabf1afcf564d"],
}


@pytest.mark.parametrize("call", sorted(_BEFORE_THE_SKIP))
def test_the_calls_without_a_skip_are_the_kernels_before_it(call):
    """ISSUE 59: a row's ``skip`` is ABSENT, not zero, from the calls
    whose rows begin with their pages (``paged_decode`` under
    ``attn_full``, both ``paged_decode_stats`` calls of an eva step,
    ``latent_decode``): compiled for the v5e at LFM2's, trinity's full
    layer's, jamba's, EvaByte's and Kimi's shapes, each call's Mosaic
    module is text for text the one of the tree before (PR 58 found
    what a call that asks the compiler for something it does not need
    does to the program around it)."""
    assert _compile_for_v5e(_KERNELS_DRIVER)[call] == \
        _BEFORE_THE_SKIP[call]


# EVA attention at EvaByte's widths (ISSUE 56): two eva layers of 32
# heads of 128 over 16 slots of window rows and summary pages of 256
# positions behind tables of 128, a float32 stream and an 8 x 320 head.
_EVA_DRIVER = r"""
import json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
cfg = TransformerConfig(
    vocab_size=320, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=32,
    d_ff=11008, max_seq=32768, norm_eps=1e-5, rope_theta=1e5,
    layer_types=("eva", "eva"), eva_window=2048, eva_chunk=16,
    norm_unit_offset=True, stream_fp32=True, head_rows=8,
    dtype=jnp.bfloat16, remat=False)
BS, WIDTH, SLOTS = 256, 128, 16


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


params = on_chip(jax.eval_shape(
    lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
    cfg, SLOTS * WIDTH + 1, BS, n_slots=SLOTS))))
fns = dict(zip(("prefill", "prefill_resume", "decode"),
               decode_lib.make_serve_fns(cfg, None, block_size=BS,
                                         table_width=WIDTH)))
args = {{"prefill_resume": (i32(1024), i32(), i32(), (i32(WIDTH), i32())),
        "decode": (i32(SLOTS), i32(SLOTS), (i32(SLOTS, WIDTH), i32(SLOTS)))}}
rows, pages = kc[0]
out = {{"device_kind": topo.devices[0].device_kind,
       "rows": list(rows.shape), "pages": list(pages.shape)}}
for name, a in args.items():
    compiled = fns[name].lower(params, kc, vc, *a).compile()
    text = compiled.as_text()
    aliased = re.search(r"input_output_alias=\{{(.*?)\}}, entry", text)
    calls = re.findall(
        r'custom-call\([^\n]*op_name="([^"]*hvd_[a-z_]+)[^"]*"', text)
    out[name] = {{
        # copies of rows or pages (.., 32, 128) larger than ONE slot's
        # rows of one layer (a chunk gathers its sequence's summary
        # pages, 1/16 of its closed windows, and half a window of rows)
        "big_copies": sum(
            int(np.prod([int(d) for d in dims.split(",")]))
            > 2048 * 32 * 128
            for dims in re.findall(
                r"= bf16\[([\d,]+,32,128)\]\{{\S* copy\(", text)),
        "aliased": len(re.findall(r"may-alias|must-alias",
                                  aliased.group(1))),
        "kernels": sorted(set(calls)), "n_kernels": len(calls),
        # a projection matrix turned over on its way into its product
        "weight_transposes": len(re.findall(
            r"= bf16\[4096,4096\]\{{\S* copy\(%bitcast", text)),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}}
print("LOWERED " + json.dumps(out))
"""


@pytest.mark.parametrize("program", ["decode", "prefill_resume"])
def test_the_eva_programs_lower_for_the_v5e_at_the_cell_s_shapes(program):
    """ISSUE 56: ``jit(decode)`` and ``jit(prefill_resume)`` of a stack
    of eva layers compiled for the v5e at EvaByte's widths (32 heads of
    128, a window of 2048 in chunks of 16, 16 slots, summary pages of
    256 positions behind tables of 128). A decode step holds the
    extended ``hvd_paged_decode`` twice a layer (``paged_decode_stats``:
    the slot's rows read as pages to each row's count under
    ``eva_window``, the summaries' pages under ``eva_summaries``), a
    chunk ``hvd_flash_keys_fwd`` twice a layer (the summaries, then the
    window carried on them); the four arrays of the kind (K and V rows,
    k~ and v~ pages) are aliased in and out, no rows or pages beyond
    one slot's rows of a layer are copied (no array of rows turned over,
    no closed window re-read, no batch's tables gathered), and a
    program's temporaries stay under 0.25 GB."""
    out = _compile_for_v5e(_EVA_DRIVER)
    got = out[program]
    assert out["rows"] == [2, 17, 2048, 32, 128], out
    assert out["pages"] == [2, 16 * 128 + 1, 16, 32, 128], out
    assert got["aliased"] == 4, got
    assert got["big_copies"] == 0, got
    assert got["n_kernels"] == 4, got
    if program == "decode":
        assert got["kernels"] == [
            "jit(decode)/attn/attn_eva/eva_summaries/jit(_decode)/"
            "hvd_paged_decode",
            "jit(decode)/attn/attn_eva/eva_window/jit(_decode)/"
            "hvd_paged_decode"], got
    else:
        assert got["kernels"] == [
            "jit(prefill_resume)/attn/attn_eva/eva_summaries/"
            "hvd_flash_keys_fwd",
            "jit(prefill_resume)/attn/attn_eva/eva_window/"
            "hvd_flash_keys_fwd"], got
    assert got["temp_bytes"] < 0.25e9, got
    # q, k and v come out of one product over the heads' own dimensions
    # (`attention_inputs`): as `h @ w` reshaped afterwards the compiler
    # transposed wq, wk and wv, 32 MB each, in every call (3 a layer)
    assert got["weight_transposes"] == 0, got


# The serve programs of a stack of ONE-BRANCH layers (ISSUE 60): a
# mamba2 layer's SSD state of 4 MB a slot, pages of 2 KV heads of 128 as
# one row of 256, and a latent mixture of ungated experts held in part,
# at the published widths and few slots.
_NEMOTRON_DRIVER = r"""
import collections, json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
cfg = TransformerConfig(
    vocab_size=32768, d_model=4096, n_layers=4, n_heads=32, n_kv_heads=2,
    d_head=128, d_ff=2688, max_seq=5120, norm_eps=1e-5,
    layer_types=("mamba2", "ffn", "full", "mamba2"), one_branch=True,
    mamba_d_state=128, mamba_d_conv=4, mamba_expand=2, mamba2_head_dim=64,
    mamba2_groups=8, mamba2_chunk=128, n_experts=512, moe_top_k=22,
    moe_capacity_factor=None, moe_scoring="sigmoid", moe_route_scale=5.0,
    moe_shared_expert=True, moe_experts_held=128, moe_expert_offset=128,
    moe_activation="relu2", moe_latent=1024, moe_shared_d_ff=5376,
    dtype=jnp.bfloat16, remat=False)
BS, WIDTH, SLOTS, CHUNK = 16, 320, 64, 1024


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


params = on_chip(jax.eval_shape(
    lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
    cfg, SLOTS * WIDTH + 1, BS, n_slots=SLOTS))))
_, resume, decode, _, _ = decode_lib.make_serve_fns(
    cfg, None, block_size=BS, table_width=WIDTH)
pool = "bf16[%s]" % ",".join(map(str, kc[0].shape))
state = "f32[%s]" % ",".join(map(str, kc[1].shape))
GROUPED_PRODUCTS_REPORT
STATE_READERS_REPORT
out = {{"device_kind": topo.devices[0].device_kind, "pool": pool,
       "state": state, "state_bytes": kc[1].size * 4}}
for name, fn, args in (
        ("decode", decode,
         (i32(SLOTS), i32(SLOTS), (i32(SLOTS, WIDTH), i32(SLOTS)))),
        ("prefill_resume", resume,
         (i32(CHUNK), i32(), i32(), (i32(WIDTH), i32())))):
    compiled = fn.lower(params, kc, vc, *args).compile()
    text = compiled.as_text()
    results = re.findall(r"= (\S+?)\{{\S* ([\w\-]+)\(", text)
    aliased = re.search(r"input_output_alias=\{{(.*?)\}}, entry", text)
    out[name] = {{
        "state_ops": collections.Counter(
            opcode for result, opcode in results if result == state),
        "pool_copies": sum(result == pool and opcode == "copy"
                           for result, opcode in results),
        "aliased": len(re.findall(r"may-alias|must-alias",
                                  aliased.group(1))),
        # the widths the held experts' products come out at
        "grouped_widths": sorted(set(re.findall(
            r"= bf16\[\d+,(\d+)\][^\n]* custom-call\([^\n]*"
            r"moe_experts/[^\n]*hvd_grouped_matmul", text))),
        **grouped_products(text),
        "paged_decode": len(re.findall(
            r"custom-call\(.*attn_full/.*hvd_paged_decode/pallas_call",
            text)),
        "scopes": sorted(set(re.findall(
            r"attn_mamba2/(\w+)", text)) | set(re.findall(
                r"/(moe_\w+)/", text))),
        "both_branches": len(re.findall(
            r'op_name="jit\(\w+\)/attn/[^"]*\bmlp\b', text)),
        "state_step": state_step_calls(text),
        "state_readers": readers(text, state),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}}
print("LOWERED " + json.dumps(out))
""".replace("GROUPED_PRODUCTS_REPORT", _GROUPED_PRODUCTS_REPORT).replace(
    "STATE_READERS_REPORT", _STATE_READERS_REPORT)


@pytest.mark.parametrize("program", ["decode", "prefill_resume"])
def test_the_one_branch_programs_lower_for_the_v5e(program):
    """ISSUE 60: a decode step at 64 slots and a chunk of 1024 of a
    stack of one-branch layers (mamba2, ffn, full, mamba2) at
    Nemotron-3-Super's widths compile for the v5e with K and V pages, the
    SSD state and the convolution's rows aliased in and out; the pages
    are rows of 256 (``kv_cache.page_tail``: as ``[.., 16, 2, 128]`` a
    chunk turned the whole pool over four times) and never copied
    whole; the state array (1.1 GB here) is only ever updated where it
    lies; the held experts' ungated form is TWO products a mixture layer
    (up to 2688, down to the latent's 1024: no gate's), since ISSUE 61
    both ``hvd_grouped_matmul`` calls at the cell's 128 held experts
    (1408 pairs a step, 22 528 a chunk) with no ``ragged-dot`` left
    beside them; since ISSUE 62 a step's recurrence is one
    ``hvd_state_step`` a mamba2 layer; every scope the benchmark reads
    by name is in the program; and a call's temporaries stay under the
    state's size."""
    out = _compile_for_v5e(_NEMOTRON_DRIVER)
    got = out[program]
    assert out["pool"] == "bf16[1,20481,16,256]", out
    assert out["state"] == "f32[2,65,128,64,128]", out
    assert got["aliased"] == 4, got
    assert got["pool_copies"] == 0, got
    handed_on = {"parameter", "get-tuple-element", "bitcast"}
    if program == "decode":
        # ISSUE 62: a mamba2 layer's step is ONE Pallas call under
        # ``attn_mamba2/mamba2_step``, the state array's only reader
        # (the XLA form was a fusion over all 65 slots a layer that
        # decayed, drove and wrote, and a second that reduced ``S c``)
        assert set(got["state_ops"]) <= handed_on | {"custom-call"}, got
        assert got["state_step"] == ["attn_mamba2/mamba2_step"] * 2, got
        assert got["state_readers"] == {"custom-call": 2}, got
    else:
        assert set(got["state_ops"]) <= handed_on | {
            "fusion", "dynamic-update-slice"}, got
        assert got["state_step"] == [], got
    assert got["ragged_dots"] == 0, got
    assert got["grouped_kernels"] == 2 * 1, got
    assert got["grouped_widths"] == ["1024", "2688"], got
    assert got["kernel_products_share"] == 1.0, got
    assert got["paged_decode"] == (1 if program == "decode" else 0), got
    step = "mamba2_step" if program == "decode" else "mamba2_scan"
    assert set(got["scopes"]) >= {
        "mamba2_proj", "conv_taps", step, "state_write", "mamba2_norm",
        "moe_router", "moe_latent_down", "moe_dispatch", "moe_experts",
        "moe_combine", "moe_latent_up", "moe_shared"}, got
    assert got["temp_bytes"] < out["state_bytes"], got


# The serve programs of Motif-3's layers (ISSUE 63): grouped differential
# latent attention in a window layer over RINGS of latents and a full
# layer over latent pages, the four-stream mHC residual and PolyNorm, a
# dense feed-forward and a share of 48 experts, at the published widths
# and few pages.
_MOTIF_DRIVER = r"""
import collections, json, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

jax.default_backend = lambda: "tpu"

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache, ring_width

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)), P())
cfg = TransformerConfig(
    vocab_size=27520, d_model=4096, n_layers=3, n_heads=80, n_kv_heads=16,
    d_head=128, d_ff=1280, d_ff_dense=12288, n_dense_layers=1, max_seq=5120,
    norm_eps=1e-5, layer_types=("mla_sliding", "mla", "mla_sliding"),
    attn_window=128, layer_rotary={{"mla": {{"theta": 10000.0}}}},
    mla_kv_rank=512, mla_rope_dim=64, mla_q_rank=1024, mla_head_gate=False,
    mla_noise_heads=16, mla_elementwise_gate=True, mhc_streams=4,
    mhc_sinkhorn_iters=20, ffn_activation="polynorm",
    moe_activation="polynorm", polynorm_scale=0.5, n_experts=384,
    moe_top_k=8, moe_capacity_factor=None, moe_scoring="sigmoid",
    moe_route_scale=2.0, moe_shared_expert=True, moe_experts_held=48,
    moe_expert_offset=48, dtype=jnp.bfloat16, remat=False)
BS, WIDTH, SLOTS, CHUNK = 16, 320, 64, 1024
RING = ring_width(cfg.attn_window, CHUNK, BS)


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


params = on_chip(jax.eval_shape(
    lambda: init_transformer(cfg, jax.random.PRNGKey(0))))
kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
    cfg, SLOTS * WIDTH + 1, BS, n_slots=SLOTS, ring=RING))))
_, resume, decode, _, _ = decode_lib.make_serve_fns(
    cfg, None, block_size=BS, table_width=WIDTH, ring=RING)
pool = "bf16[%s]" % ",".join(map(str, kc[0].shape))
rings = "bf16[%s]" % ",".join(map(str, kc[1].shape))
GROUPED_PRODUCTS_REPORT
out = {{"device_kind": topo.devices[0].device_kind, "pool": pool,
       "rings": rings, "ring": RING}}
for name, fn, args in (
        ("decode", decode,
         (i32(SLOTS), i32(SLOTS), (i32(SLOTS, WIDTH), i32(SLOTS)))),
        ("prefill_resume", resume,
         (i32(CHUNK), i32(), i32(), (i32(WIDTH), i32())))):
    compiled = fn.lower(params, kc, vc, *args).compile()
    text = compiled.as_text()
    results = re.findall(r"= (\S+?)\{{\S* ([\w\-]+)\(", text)
    aliased = re.search(r"input_output_alias=\{{(.*?)\}}, entry", text)
    names = re.findall(r'op_name="jit\(\w+\)/([^"]*)"', text)
    out[name] = {{
        "copies": sum(result in (pool, rings) and opcode in (
            "copy", "transpose") for result, opcode in results),
        "aliased": len(re.findall(r"may-alias|must-alias",
                                  aliased.group(1))),
        **grouped_products(text),
        "latent_decode": sorted(re.findall(
            r"custom-call\([^\n]*/(attn_mla_\w+)/mla_attend/[^\n]*"
            r"hvd_latent_decode/pallas_call", text)),
        "flash_keys": sorted(re.findall(
            r"custom-call\([^\n]*/(attn_mla_\w+)/mla_attend/[^\n]*"
            r"hvd_flash_keys_fwd", text)),
        "scopes": sorted({{part for n in names for part in n.split("/")
                           if part in ("attn_mla_window", "attn_mla_full",
                                       "mla_attend", "mla_expand",
                                       "kv_gather", "kv_write", "gdla_diff",
                                       "mhc_mix", "polynorm", "attn_gate",
                                       "moe_router", "moe_dispatch",
                                       "moe_experts", "moe_combine",
                                       "moe_shared")}}),
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}}
print("LOWERED " + json.dumps(out))
""".replace("GROUPED_PRODUCTS_REPORT", _GROUPED_PRODUCTS_REPORT)


@pytest.mark.parametrize("program", ["decode", "prefill_resume"])
def test_the_gdla_programs_lower_for_the_v5e(program):
    """ISSUE 63: a decode step at 64 slots and a chunk of 1024 of a
    dense window layer, a sparse full layer and a sparse window layer at
    Motif-3-Beta's widths compile for the v5e with the latent pages and
    the rings of latents (65 slots x 1168 places x 640) aliased in and
    out and never copied or turned whole; a step's attention is ONE
    ``hvd_latent_decode`` a layer, under its kind's scope (rings and
    pages, the same kernel body), a chunk's one ``hvd_flash_keys_fwd`` a
    layer; the share's PolyNorm experts are THREE ``hvd_grouped_matmul``
    a sparse layer with no ``ragged-dot`` beside them; every scope the
    benchmark reads by name is in the program; and a call's temporaries
    stay under half a gigabyte."""
    out = _compile_for_v5e(_MOTIF_DRIVER)
    got = out[program]
    assert out["ring"] == 1168, out
    assert out["pool"] == "bf16[1,20481,16,640]", out
    assert out["rings"] == "bf16[2,65,1168,640]", out
    assert got["aliased"] == 2, got
    assert got["copies"] == 0, got
    assert got["ragged_dots"] == 0, got
    assert got["grouped_kernels"] == 3 * 2, got
    assert got["kernel_products_share"] == 1.0, got
    if program == "decode":
        assert got["latent_decode"] == [
            "attn_mla_full", "attn_mla_window", "attn_mla_window"], got
        assert got["flash_keys"] == [], got
    else:
        assert got["latent_decode"] == [], got
        assert got["flash_keys"] == [
            "attn_mla_full", "attn_mla_window", "attn_mla_window"], got
    assert set(got["scopes"]) >= {
        "attn_mla_window", "attn_mla_full", "mla_attend", "kv_write",
        "gdla_diff", "mhc_mix", "polynorm", "attn_gate", "moe_router",
        "moe_dispatch", "moe_experts", "moe_combine", "moe_shared"} | (
            set() if program == "decode" else {"mla_expand", "kv_gather"}
        ), got
    assert got["temp_bytes"] < 0.5e9, got
