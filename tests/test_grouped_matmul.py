"""A mixture's grouped products through ``hvd_grouped_matmul``
(``ops/grouped_matmul.py``, interpret mode here) against
``lax.ragged_dot``, whose semantics it has and which
``moe.moe_ffn_dropless`` called before ISSUE 57, a chip's share of the
experts (``moe._held_rows``) before ISSUE 61, and both still call where
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


#: A chip's share: experts 4..7 of 16 held, four choices a token. name:
#: (tokens, how the [tokens, 4] choices are drawn, tokens whose rows are
#: NaN and whose choices are all moved off the held experts)
HELD_CASES = {
    # a uniform router: about a quarter of the 256 pairs on the share
    "a_quarter_of_the_pairs": (64, "uniform", 0),
    # held experts 5 and 7 get no pair at all
    "empty_held_groups": (64, "two_empty", 0),
    # every token chooses held expert 6: 160 rows, over two row tiles
    "a_group_over_two_row_tiles": (160, "one_for_all", 0),
    # the rows behind the last group hold NaN
    "nan_behind_the_last_group": (64, "uniform", 24),
    # nothing on the share
    "no_held_pair": (32, "none", 0),
}
HELD = (4, 4)                                   # experts_held, expert_offset


def held_share(form, dtype=jnp.bfloat16):
    """``(cfg, lp)`` of one chip's share, a SwiGLU at the model's width
    (``gated``: three products) or Nemotron's form (``latent_relu2``:
    ``relu(x Wu)^2 Wd`` inside a latent of half the width, two)."""
    more = ({} if form == "gated" else
            {"activation": "relu2", "latent": 128})
    cfg = moe_lib.MoEConfig(n_experts=16, top_k=4, capacity_factor=None,
                            experts_held=HELD[0], expert_offset=HELD[1],
                            **more)
    lp = jax.tree.map(lambda p: p[0], moe_lib.init_moe_params(
        jax.random.PRNGKey(3), 1, 256, 256, cfg, dtype))
    return cfg, lp


def held_inputs(case, dtype=jnp.bfloat16):
    """``(x [1, tokens, 256], gates, experts [tokens, 4], poisoned)``."""
    tokens, draw, poisoned = HELD_CASES[case]
    rng = np.random.default_rng(61)
    away = [e for e in range(16) if not 4 <= e < 8]
    if draw == "none":
        allowed = away
    elif draw == "two_empty":
        allowed = [e for e in range(16) if e not in (5, 7)]
    else:
        allowed = list(range(16))
    experts = np.stack([rng.choice(allowed, 4, replace=False)
                        for _ in range(tokens)])
    if draw == "one_for_all":
        experts[:, 1] = 6
        experts[:, [0, 2, 3]] = np.stack(
            [rng.choice(away, 3, replace=False) for _ in range(tokens)])
    x = rng.standard_normal((1, tokens, 256)).astype(np.float32)
    if poisoned:
        experts[:poisoned] = np.stack([rng.choice(away, 4, replace=False)
                                       for _ in range(poisoned)])
        x[0, :poisoned] = np.nan
    gates = rng.uniform(0.1, 0.4, (tokens, 4)).astype(np.float32)
    return (jnp.asarray(x, dtype), jnp.asarray(gates),
            jnp.asarray(experts, jnp.int32), poisoned)


@pytest.mark.parametrize("case", sorted(HELD_CASES))
@pytest.mark.parametrize("form", ["gated", "latent_relu2"])
def test_a_held_share_takes_the_kernel_by_its_shapes(form, case,
                                                     monkeypatch):
    """``moe._held_experts`` (ISSUE 61): its two or three products run
    the kernel where ``taken`` says so and give the ``y`` of the same
    call with ``taken`` forced false, to a rounding; every product is
    counted. The sizes sum to a part of ``M``: what the kernel leaves
    behind the last group (here the products of NaN rows) is masked out
    of ``y``, which is exactly 0 for a token with no held pair whatever
    its gates."""
    cfg, lp = held_share(form)
    x, gates, experts, poisoned = held_inputs(case)
    products = 3 if form == "gated" else 2
    monkeypatch.setattr(moe_lib, "_grouped_traced", [0, 0])

    def run(with_kernel):
        with monkeypatch.context() as patch:
            if not with_kernel:
                patch.setattr(gm, "taken", lambda lhs, rhs: False)
            fn = jax.jit(lambda x: moe_lib._held_experts(
                x, lp, cfg, gates, experts))
            text = fn.lower(x).as_text(debug_info=True)
            return (np.asarray(fn(x), np.float32)[0],
                    text.count("hvd_grouped_matmul"))

    y, kernels = run(True)
    assert kernels >= products
    assert moe_lib._grouped_traced == [products, products]
    want, none = run(False)
    assert none == 0
    assert moe_lib._grouped_traced == [2 * products, products]
    assert np.isfinite(y).all() and np.isfinite(want).all()
    assert np.abs(y - want).max() <= 2.0 ** -6 * max(np.abs(want).max(), 1.0)
    local = np.asarray(experts) - HELD[1]
    unheld = ~((local >= 0) & (local < HELD[0])).any(1)
    assert unheld[:poisoned].all()
    assert (y[unheld] == 0).all() and (np.abs(y[~unheld]).max(initial=1) > 0)


@pytest.mark.parametrize("form", ["gated", "latent_relu2"])
def test_a_held_share_s_gradients_are_ragged_dot_s(form, monkeypatch):
    """``jax.grad`` through ``_held_experts`` with the kernel forward
    (float32 operands; the cotangents are ``lax.ragged_dot``'s) equals
    the gradient with ``taken`` forced false, for ``x`` and every
    matrix: what the kernel leaves behind the last group reaches
    neither."""
    cfg, lp = held_share(form, jnp.float32)
    x, gates, experts, _ = held_inputs("a_quarter_of_the_pairs", jnp.float32)

    def loss(x, lp):
        return jnp.tanh(moe_lib._held_experts(x, lp, cfg, gates, experts)
                        ).sum()

    text = jax.jit(jax.grad(loss, (0, 1))).lower(x, lp).as_text(
        debug_info=True)
    assert "hvd_grouped_matmul" in text
    got = jax.jit(jax.grad(loss, (0, 1)))(x, lp)
    monkeypatch.setattr(gm, "taken", lambda lhs, rhs: False)
    want = jax.jit(jax.grad(loss, (0, 1)))(x, lp)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("held", [None, 2])
def test_a_mesh_s_shards_keep_ragged_dot(devices, held):
    """A shard of a mesh's tokens (``make_moe_ffn``'s ``shard_map``
    over ``dp`` / ``fsdp``) keeps ``lax.ragged_dot`` at shapes whose
    whole batch would take the kernel: a Pallas result carries no
    varying axes for the ``shard_map`` to check. The whole mixture and
    a held share (experts 1 and 2 of 4) alike, forward and under
    ``jax.grad``, against the unsharded block."""
    from horovod_tpu.parallel import build_mesh
    cfg = moe_lib.MoEConfig(n_experts=4, top_k=2, capacity_factor=None,
                            experts_held=held, expert_offset=1 if held else 0)
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
