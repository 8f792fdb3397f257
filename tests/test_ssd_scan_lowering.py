"""Compile-only for the v5e, with no chip attached: what stands under
``attn_mamba2/mamba2_scan`` in the Nemotron cell's chunk programs
(ISSUE 64). In a file of its own, beside ``tests/test_tpu_lowering.py``
whose helpers it uses: that file alone sets tier-1's wall time under
``--dist loadfile`` (its tests sum to over 800 s of 977), and these
seven compiles run on another worker meanwhile."""

import pytest

from test_tpu_lowering import _STATE_READERS_REPORT, _compile_for_v5e

# The Nemotron cell's five mamba2 layers alone: every chunk program at
# every bucket of the cell, and the decode step.
_SSD_SCAN_DRIVER = r"""
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
BS, WIDTH, SLOTS = 16, 320, 64


def on_chip(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one), tree)


def i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


STATE_READERS_REPORT
out = {{"device_kind": topo.devices[0].device_kind}}
five = TransformerConfig(
    vocab_size=32768, d_model=4096, n_layers=5, n_heads=32, n_kv_heads=2,
    d_head=128, d_ff=2688, max_seq=5120, norm_eps=1e-5,
    layer_types=("mamba2",) * 5, one_branch=True, mamba_d_state=128,
    mamba_d_conv=4, mamba_expand=2, mamba2_head_dim=64, mamba2_groups=8,
    mamba2_chunk=128, dtype=jnp.bfloat16, remat=False)
params = on_chip(jax.eval_shape(
    lambda: init_transformer(five, jax.random.PRNGKey(0))))
kc, vc = on_chip(jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
    five, SLOTS * WIDTH + 1, BS, n_slots=SLOTS))))
prefill, resume, decode, _, _ = decode_lib.make_serve_fns(
    five, None, block_size=BS, table_width=WIDTH)
for name, fn, args in (
        [("decode", decode,
          (i32(SLOTS), i32(SLOTS), (i32(SLOTS, WIDTH), i32(SLOTS))))]
        + [("prefill_%d" % c, prefill, (i32(c), i32(), (i32(WIDTH), i32())))
           for c in (256, 512, 1024)]
        + [("prefill_resume_%d" % c, resume,
            (i32(c), i32(), i32(), (i32(WIDTH), i32())))
           for c in (256, 512, 1024)]):
    text = fn.lower(params, kc, vc, *args).compile().as_text()
    under = [ln for ln in text.splitlines()
             if "attn_mamba2/mamba2_scan" in ln]
    out[name] = {{
        "ssd_scan": sorted(re.findall(
            r"custom-call\([^\n]*/(attn_\w+/\w+)/jit\(_scan\)/hvd_ssd_scan/"
            r"pallas_call", text)),
        "state_step": state_step_calls(text),
        # a block's decays a head: float32 with two block-wide trailing
        # axes behind all 128 heads (`[8,16,128,128]` at the parent)
        "decays": sorted(shape for shape in set(re.findall(
            r"f32\[([\d,]+),128,128\]", text))
            if np.prod([int(n) for n in shape.split(",")]) >= 128),
        "whiles": sum(bool(re.search(r" while\(", ln)) for ln in under),
        # the chunk's rows turned or copied whole under the scope
        "row_copies": sum(bool(re.search(
            r"= f32\[1,\d+,(8192|128,64)\]\S* (copy|transpose)\(", ln))
            for ln in under)}}
print("LOWERED " + json.dumps(out))
""".replace("STATE_READERS_REPORT", _STATE_READERS_REPORT)


@pytest.mark.parametrize("program", [
    "prefill_256", "prefill_512", "prefill_1024", "prefill_resume_256",
    "prefill_resume_512", "prefill_resume_1024", "decode"])
def test_a_chunk_s_ssd_is_one_kernel_a_layer_on_v5e(program):
    """ISSUE 64: the chunk programs of Nemotron's five mamba2 layers at
    each of the cell's buckets hold one ``hvd_ssd_scan`` a layer under
    ``attn_mamba2/mamba2_scan`` and, with it, no float32 tensor of a
    block's decays a head (``[.., 128, 128, 128]``: 8 MB a block at the
    parent, written and read back), no ``while`` under that scope (the
    parent's ``lax.scan`` over the blocks) and no copy of the chunk's
    rows there (``D x`` is added inside the call: behind it XLA turned
    ``y`` and ``x`` to the positions' axis innermost, 32 MB each a
    layer); the decode step still holds its five ``hvd_state_step`` and
    no ``hvd_ssd_scan``."""
    got = _compile_for_v5e(_SSD_SCAN_DRIVER)[program]
    step = program == "decode"
    assert got["ssd_scan"] == (
        [] if step else ["attn_mamba2/mamba2_scan"] * 5), got
    assert got["state_step"] == (
        ["attn_mamba2/mamba2_step"] * 5 if step else []), got
    assert got["decays"] == [] and got["whiles"] == 0, got
    assert got["row_copies"] == 0, got
