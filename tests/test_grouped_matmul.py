"""A whole mixture's grouped products through ``hvd_grouped_matmul``
(``ops/grouped_matmul.py``, interpret mode here) against
``lax.ragged_dot``, whose semantics it has and which
``moe.moe_ffn_dropless`` called before ISSUE 57 and still calls where
the matrix unit bounds the product."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models import moe as moe_lib
from horovod_tpu.ops import grouped_matmul as gm

def _router_sizes(rows, groups):
    rng = np.random.default_rng(57)
    return rng.multinomial(rows, rng.dirichlet(np.full(groups, 6.0))).tolist()


#: name: M, K, N, sizes, dtype
CASES = {
    "even_on_the_tiles": (512, 128, 128, [128] * 4, "bfloat16"),
    "even_inside_a_tile": (256, 256, 128, [32] * 8, "bfloat16"),
    "the_seeded_router_s": (512, 128, 256, _router_sizes(512, 8),
                            "bfloat16"),
    "one_group_holds_every_row": (384, 128, 128, [0, 0, 384, 0], "bfloat16"),
    "empty_at_front_middle_and_end": (
        256, 128, 128, [0, 0, 100, 0, 0, 156, 0, 0], "bfloat16"),
    "boundaries_inside_a_tile": (256, 128, 256, [30, 70, 100, 56],
                                 "bfloat16"),
    "boundaries_on_a_tile": (512, 256, 128, [128, 256, 0, 128], "bfloat16"),
    "a_group_over_three_tiles": (384, 128, 128, [50, 300, 12, 22],
                                 "bfloat16"),
    "rows_behind_the_last_group": (384, 128, 128, [50, 60, 70, 80],
                                   "bfloat16"),
    "a_tile_no_group_reaches": (512, 128, 128, [50, 0, 60, 0], "bfloat16"),
    "rows_no_multiple_of_the_tile": (300, 128, 128, [10, 0, 200, 50, 0, 0,
                                                     40, 0], "bfloat16"),
    "less_than_one_tile": (72, 128, 128, [20, 30, 0, 22], "bfloat16"),
    "no_row_at_all": (256, 128, 128, [0] * 4, "bfloat16"),
    "float32_operands": (256, 128, 128, [30, 70, 100, 56], "float32"),
}


def operands(m, k, n, groups, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(keys[0], (m, k)).astype(dtype),
            (jax.random.normal(keys[1], (groups, k, n)) * k ** -0.5
             ).astype(dtype))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_ragged_dot(case):
    """Every row of a group against ``lax.ragged_dot`` at the same
    operands, to one rounding of the result's dtype (the two sum a
    contraction in different orders), and against the groups' products
    in float32. Rows behind the last group are unspecified and not
    compared."""
    m, k, n, sizes, dtype = CASES[case]
    lhs, rhs = operands(m, k, n, len(sizes), dtype)
    sz = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(jax.jit(gm.grouped_matmul)(lhs, rhs, sz), np.float32)
    assert got.shape == (m, n)
    held = sum(sizes)
    want = np.asarray(lax.ragged_dot(lhs, rhs, sz), np.float32)[:held]
    exact = np.concatenate([
        np.asarray(lhs[a:a + s], np.float32) @ np.asarray(rhs[g], np.float32)
        for g, (a, s) in enumerate(zip(np.cumsum([0] + sizes), sizes))])
    ulp = 2.0 ** -8 if dtype == "bfloat16" else 2.0 ** -20
    room = ulp * np.maximum(np.abs(exact), 1.0)
    assert (np.abs(got[:held] - exact) <= room).all()
    assert (np.abs(got[:held] - want) <= 2 * room).all()


@pytest.mark.parametrize("sizes", [[30, 70, 100, 56], [0, 128, 0, 60]])
def test_its_gradients_are_ragged_dot_s(sizes):
    """``jax.grad`` through the kernel for both operands: the
    cotangents ``lax.ragged_dot`` gives at the same operands (backward
    runs the compiler's kernels), the rows behind the last group out of
    the loss."""
    lhs, rhs = operands(256, 128, 128, len(sizes), jnp.float32)
    sz = jnp.asarray(sizes, jnp.int32)
    weigh = jax.random.normal(jax.random.PRNGKey(2), (256, 128))
    weigh = weigh * (jnp.arange(256) < sum(sizes))[:, None]

    def loss(fn):
        return lambda a, b: (jnp.tanh(fn(a, b, sz)) * weigh).sum()

    got = jax.jit(jax.grad(loss(gm.grouped_matmul), (0, 1)))(lhs, rhs)
    want = jax.jit(jax.grad(loss(lax.ragged_dot), (0, 1)))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_the_whole_mixture_takes_the_kernel_by_its_shapes(monkeypatch):
    """``moe_ffn_dropless`` at a shape on each side of ``taken``'s
    threshold: 32 rows an expert run the kernel and give the ``y`` of
    ``lax.ragged_dot`` to a rounding; 512 rows an expert keep
    ``lax.ragged_dot`` and its ``y`` bit for bit; and
    ``moe_grouped_kernel_products_share`` reads what was traced."""
    cfg = moe_lib.MoEConfig(n_experts=4, top_k=2, capacity_factor=None)
    lp = jax.tree.map(lambda p: p[0], moe_lib.init_moe_params(
        jax.random.PRNGKey(0), 1, 128, 128, cfg, jnp.bfloat16))
    monkeypatch.setattr(moe_lib, "_grouped_traced", [0, 0])

    def run(tokens, with_kernel):
        x = jax.random.normal(jax.random.PRNGKey(1), (1, tokens, 128)
                              ).astype(jnp.bfloat16)
        with monkeypatch.context() as patch:
            if not with_kernel:
                patch.setattr(gm, "taken", lambda lhs, rhs: False)
            text = jax.jit(lambda x: moe_lib.moe_ffn_dropless(x, lp, cfg)[0]
                           ).lower(x).as_text(debug_info=True)
            return (np.asarray(moe_lib.moe_ffn_dropless(x, lp, cfg)[0],
                               np.float32), text.count("hvd_grouped_matmul"))

    y, kernels = run(64, True)
    assert kernels >= 3
    assert moe_lib._grouped_traced == [6, 6]         # lowered and run
    assert moe_lib.moe_metrics()[
        "moe_grouped_kernel_products_share"] == 1.0
    want, none = run(64, False)
    assert none == 0
    assert np.abs(y - want).max() <= 2.0 ** -6 * np.abs(want).max()
    assert moe_lib.moe_metrics()[
        "moe_grouped_kernel_products_share"] == 0.5
    y, kernels = run(1024, True)
    assert kernels == 0
    assert moe_lib._grouped_traced == [18, 6]
    np.testing.assert_array_equal(y, run(1024, False)[0])
    assert moe_lib.moe_metrics()[
        "moe_grouped_kernel_products_share"] == 0.25


def test_a_mesh_s_shards_keep_ragged_dot(devices):
    """A shard of a mesh's tokens (``make_moe_ffn``'s ``shard_map``
    over ``dp`` / ``fsdp``) keeps ``lax.ragged_dot`` at shapes whose
    whole batch would take the kernel: a Pallas result carries no
    varying axes for the ``shard_map`` to check. Forward and under
    ``jax.grad``, against the unsharded block."""
    from horovod_tpu.parallel import build_mesh
    cfg = moe_lib.MoEConfig(n_experts=4, top_k=2, capacity_factor=None)
    lp = jax.tree.map(lambda p: p[0], moe_lib.init_moe_params(
        jax.random.PRNGKey(0), 1, 128, 128, cfg, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 128))
    over_mesh = moe_lib._dropless_over_mesh(cfg, build_mesh(dp=4, fsdp=2))

    def loss(fn):
        return lambda x, lp: jnp.tanh(fn(x, lp)[0]).sum()

    whole = lambda x, lp: moe_lib.moe_ffn_dropless(x, lp, cfg)  # noqa: E731
    text = jax.jit(over_mesh).lower(x, lp).as_text(debug_info=True)
    assert "hvd_grouped_matmul" not in text
    assert "hvd_grouped_matmul" in jax.jit(whole).lower(x, lp).as_text(
        debug_info=True)
    np.testing.assert_allclose(jax.jit(over_mesh)(x, lp)[0], whole(x, lp)[0],
                               rtol=1e-5, atol=1e-5)
    got = jax.jit(jax.grad(loss(over_mesh), (0, 1)))(x, lp)
    want = jax.grad(loss(whole), (0, 1))(x, lp)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
