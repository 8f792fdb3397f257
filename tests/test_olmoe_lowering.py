"""Compile-only for the v5e, no chip attached: OLMoE's expert layer at
its published widths through the sorted, dropless dispatch, forward and
backward. The topology is described inside this file's own fixture (the
on-chip-measurement guide, section 2): nothing here touches libtpu while
a module is imported. Nothing runs, so nothing here is a time."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.models import moe as moe_lib

# OLMoE-1B-7B: 64 experts of 2048 x 1024, 8 a token; 2 rows of 4096
# tokens, so N = 8192 and N*K = 65536 routed rows.
E, K, D, F, ROWS, SEQ = 64, 8, 2048, 1024, 2, 4096
N = ROWS * SEQ


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_dropless_expert_layer_compiles_for_v5e_with_no_e_by_n_buffer(
        one_chip):
    cfg = moe_lib.MoEConfig(n_experts=E, top_k=K, capacity_factor=None,
                            norm_topk_prob=False, z_loss_coef=0.001)

    def loss(x, lp):
        y, aux = moe_lib.moe_ffn_dropless(x, lp, cfg)
        return y.astype(jnp.float32).sum() + aux

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = on_chip((ROWS, SEQ, D), jnp.bfloat16)
    lp = {"router": on_chip((D, E), jnp.float32),
          "w_gate": on_chip((E, D, F), jnp.bfloat16),
          "w_up": on_chip((E, D, F), jnp.bfloat16),
          "w_down": on_chip((E, F, D), jnp.bfloat16)}
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, lp).compile()
    text = compiled.as_text()
    # the grouped matmuls are Mosaic kernels, not E masked dense ones:
    # 3 forward, and 2 a matmul backward
    assert text.count('custom_call_target="tpu_custom_call"') >= 9
    assert compiled.cost_analysis()["flops"] < 3.5 * (3 * 2 * N * K * D * F)

    # No buffer of the dispatch grows with E x N (or E x C: with the
    # capacity factor 1.25 of the one-hot dispatch, C would be 1280).
    # The router's own [N, E] logits and probabilities, 2 MB, are the
    # softmax over all experts that the architecture asks for.
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b(?:bf16|f32|s32|u32|pred|f16|s8|u8)"
                                     r"\[([\d,]+)\]", text)}
    routed = N * K
    for shape in shapes:
        if E in shape and sorted(shape) != [E, N]:   # either way round
            rest = [d for d in shape if d != E]
            assert not any(d in (N, routed, 1280) for d in rest), shape
    biggest = max(d0 * (shape[1] if len(shape) > 1 else 1)
                  for shape in shapes for d0 in shape[:1])
    assert biggest <= routed * D
    # what is kept at once: the routed rows in and out and a few copies,
    # far from the 5.4 GB one row of the one-hot dispatch would take
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9
