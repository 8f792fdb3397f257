"""Mixture-of-Experts / expert parallelism: routing math against a
NumPy model, capacity semantics, ep-mesh execution, and the integrated
MoE transformer training end to end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import moe as moe_lib
from horovod_tpu.models import transformer as tr
from horovod_tpu.parallel import build_mesh


def _params(key, cfg, d=16, f=32, dtype=jnp.float32):
    p = moe_lib.init_moe_params(key, 1, d, f, cfg, dtype)
    return jax.tree.map(lambda a: a[0], p)  # drop layer dim


def test_top1_routing_matches_dense_expert():
    """capacity_factor high + top_k=1: every token goes to exactly its
    argmax expert, so MoE output == per-token dense SwiGLU with that
    expert's weights."""
    cfg = moe_lib.MoEConfig(n_experts=4, top_k=1, capacity_factor=8.0)
    lp = _params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 16))
    y, aux = moe_lib.moe_ffn(x, lp, cfg)

    logits = np.einsum("btd,de->bte", np.asarray(x, np.float64),
                       np.asarray(lp["router"], np.float64))
    choice = logits.argmax(-1)
    want = np.zeros_like(np.asarray(x))
    for b in range(2):
        for t in range(6):
            e = choice[b, t]
            h = np.asarray(x)[b, t]
            g = np.asarray(jax.nn.silu(h @ np.asarray(lp["w_gate"])[e]))
            u = h @ np.asarray(lp["w_up"])[e]
            want[b, t] = (g * u) @ np.asarray(lp["w_down"])[e]
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    assert float(aux) > 0


def test_capacity_drops_overflow_tokens():
    """With capacity 1 and all tokens routed to one expert, only the
    first token per batch row gets routed; the rest emit zeros (their
    residual stream passes through at the transformer level)."""
    cfg = moe_lib.MoEConfig(n_experts=2, top_k=1, capacity_factor=1e-9)
    lp = _params(jax.random.PRNGKey(0), cfg)
    assert moe_lib.capacity(cfg, 6) == 1
    # Force all tokens to expert 0 via a huge router column.
    lp = dict(lp)
    lp["router"] = jnp.zeros_like(lp["router"]).at[:, 0].set(100.0)
    x = jnp.ones((1, 6, 16))
    y, _ = moe_lib.moe_ffn(x, lp, cfg)
    nonzero_rows = np.abs(np.asarray(y[0])).sum(-1) > 1e-9
    assert nonzero_rows.tolist() == [True] + [False] * 5


@pytest.mark.slow  # ~20s of XLA compiles; redundancy (ISSUE 11
# budget audit): gradient flow through the MoE routing is pinned
# tier-1 by test_moe_grad_reaches_every_param on the same ep mesh,
# and the sharded train-step integration by test_models'
# test_transformer_train_step_runs_sharded — the loss-goes-down
# multi-step loop on top is the overlap that rides the slow tier.
def test_moe_transformer_trains_on_ep_mesh(devices):
    mesh = build_mesh(dp=2, ep=2, tp=2)
    cfg = tr.TransformerConfig.tiny(n_experts=4, sp_attention="local",
                                    dtype=jnp.float32, remat=False)
    init_state, jit_step, _ = tr.make_train_step(cfg, mesh)
    state = init_state(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 256)
    losses = []
    for _ in range(3):
        state, loss = jit_step(state, {"tokens": toks})
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_moe_grad_reaches_every_param(devices):
    mesh = build_mesh(ep=2, dp=2, tp=2)
    cfg = tr.TransformerConfig.tiny(n_experts=4, sp_attention="local",
                                    dtype=jnp.float32, remat=False)
    params = tr.init_params(cfg, jax.random.PRNGKey(0), mesh)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 256)
    g = jax.jit(jax.grad(lambda p: tr.lm_loss(p, {"tokens": toks}, cfg,
                                              mesh)))(params)
    norms = jax.tree.map(lambda a: float(jnp.linalg.norm(a.astype(
        jnp.float32))), g["layers"]["moe"])
    assert all(v > 0 for v in jax.tree.leaves(norms)), norms


# ---------------------------------------------------------------------------
# ISSUE 18: the quantized-dispatch island (moe_ffn_island /
# make_moe_ffn) and its telemetry.
# ---------------------------------------------------------------------------

def _island_case(E=8, top_k=2, cf=1.25, B=8, T=6, d=16, f=32, seed=0):
    cfg = moe_lib.MoEConfig(n_experts=E, top_k=top_k, capacity_factor=cf)
    lp = _params(jax.random.PRNGKey(seed), cfg, d=d, f=f)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, T, d))
    return cfg, lp, x


def test_island_codec_none_bitwise_matches_gspmd(devices):
    """The island at compression=none restructures the dispatch into
    explicit per-shard slabs + alltoall hops but must reproduce the
    GSPMD einsum path's EXACT bytes — output and aux both. This is the
    direct pin on the island MATH (eagerly, where both run the same
    kernels); under jit the two are different XLA programs, so there
    the bitwise contract is delivered by make_moe_ffn routing none to
    the GSPMD closure outright (the train-step pin below), and the
    compiled island may only drift by reassociation ulps."""
    mesh = build_mesh(ep=-1)
    cfg, lp, x = _island_case()
    y, aux = moe_lib.moe_ffn(x, lp, cfg)
    yi, auxi = moe_lib.moe_ffn_island(x, lp, cfg, mesh, codec="none")
    np.testing.assert_array_equal(np.asarray(yi), np.asarray(y))
    assert float(auxi) == float(aux)
    yj, _ = jax.jit(lambda: moe_lib.moe_ffn(x, lp, cfg))()
    yij, _ = jax.jit(lambda: moe_lib.moe_ffn_island(
        x, lp, cfg, mesh, codec="none"))()
    np.testing.assert_allclose(np.asarray(yij), np.asarray(yj),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("codec,tol", [("bf16", 1e-2), ("int8", 4e-2)])
def test_island_lossy_codec_error_bounded(devices, codec, tol):
    """Lossy wire, bounded error: the relative max-abs deviation from
    the GSPMD output stays within the codec's band (bf16 ~ 2^-8
    mantissa, int8 ~ blockwise scale/254 per hop, two hops) — and is
    genuinely nonzero, so the test would catch the codec silently
    resolving to none. The aux loss rides pmean'd f32 routing vectors
    and must stay EXACT under every codec."""
    mesh = build_mesh(ep=-1)
    cfg, lp, x = _island_case()
    y, aux = moe_lib.moe_ffn(x, lp, cfg)
    yi, auxi = moe_lib.moe_ffn_island(x, lp, cfg, mesh, codec=codec)
    scale = float(jnp.abs(y).max())
    rel = float(jnp.abs(yi - y).max()) / scale
    assert 0.0 < rel < tol, (codec, rel, scale)
    assert float(auxi) == float(aux)


def test_island_int8_deterministic(devices):
    """Determinism matrix for the int8 island: jit vs eager trace the
    same program (bitwise), and repeated runs are bitwise stable (RNE
    rounding has no data-dependent or stateful tie-break)."""
    mesh = build_mesh(ep=-1)
    cfg, lp, x = _island_case()

    def f():
        return moe_lib.moe_ffn_island(x, lp, cfg, mesh, codec="int8")

    y_eager, aux_eager = f()
    y_jit, aux_jit = jax.jit(f)()
    y_jit2, aux_jit2 = jax.jit(f)()
    np.testing.assert_array_equal(np.asarray(y_jit), np.asarray(y_jit2))
    assert float(aux_jit) == float(aux_jit2)
    np.testing.assert_array_equal(np.asarray(y_jit), np.asarray(y_eager))
    assert float(aux_jit) == float(aux_eager)


def test_island_int8_grads_reach_every_param(devices):
    """The straight-through custom_vjp must carry gradients through
    BOTH quantized hops: router (via dispatch/combine weights and the
    aux loss) and all three expert matrices get nonzero grads."""
    mesh = build_mesh(ep=-1)
    cfg, lp, x = _island_case()

    def loss(lp):
        y, aux = moe_lib.moe_ffn_island(x, lp, cfg, mesh, codec="int8")
        return jnp.sum(y ** 2) + aux

    g = jax.jit(jax.grad(loss))(lp)
    norms = {k: float(jnp.linalg.norm(v)) for k, v in g.items()}
    assert all(v > 0 for v in norms.values()), norms


def test_island_forced_overflow_matches_gspmd(devices):
    """capacity_factor ~ 0 forces capacity 1 with every token claiming
    expert 0: the island must drop the same (t, k)-priority overflow
    rows as the GSPMD path — token 0 of each batch row served, the
    rest riding the residual as zeros — at every codec."""
    mesh = build_mesh(ep=-1)
    cfg, lp, x = _island_case(top_k=1, cf=1e-9)
    # Positive tokens so the forced router column (a linear map — its
    # logit is 100 * sum(x)) wins the argmax on every token.
    x = jnp.abs(x) + 0.1
    lp = dict(lp)
    lp["router"] = jnp.zeros_like(lp["router"]).at[:, 0].set(100.0)
    y, _ = moe_lib.moe_ffn(x, lp, cfg)
    yn, _ = moe_lib.moe_ffn_island(x, lp, cfg, mesh, codec="none")
    np.testing.assert_array_equal(np.asarray(yn), np.asarray(y))
    yq, _ = moe_lib.moe_ffn_island(x, lp, cfg, mesh, codec="int8")
    served = np.abs(np.asarray(y)).sum(-1) > 1e-9
    assert (served.sum(1) == 1).all()          # one survivor per row
    # int8 zeros stay exactly zero (blockwise scale of a zero slab is
    # zero), so the dropped rows agree bitwise even on the lossy wire.
    dropped_q = np.abs(np.asarray(yq)).sum(-1) == 0.0
    np.testing.assert_array_equal(dropped_q, ~served)


def test_island_exact_fit_and_empty_experts(devices):
    """Edge geometry: top_k=1, cf=1.0, T=E gives capacity exactly 1
    (an exact fit when routing is uniform), and a router pinned to
    expert 3 leaves 7 of 8 expert slabs EMPTY — the island's packed
    slabs and both alltoall hops must handle all-zero partitions and
    still match GSPMD bitwise at codec none."""
    mesh = build_mesh(ep=-1)
    cfg, lp, x = _island_case(top_k=1, cf=1.0, T=8)
    assert moe_lib.capacity(cfg, 8) == 1
    lp = dict(lp)
    lp["router"] = jnp.zeros_like(lp["router"]).at[:, 3].set(100.0)
    y, aux = moe_lib.moe_ffn(x, lp, cfg)
    yi, auxi = moe_lib.moe_ffn_island(x, lp, cfg, mesh, codec="none")
    np.testing.assert_array_equal(np.asarray(yi), np.asarray(y))
    assert float(auxi) == float(aux)


def test_island_build_time_gates(devices):
    """Misconfigurations must raise at BUILD time with the mesh in
    hand, not mid-trace: E not divisible by ep, batch not divisible by
    ep."""
    mesh = build_mesh(ep=-1)
    cfg6 = moe_lib.MoEConfig(n_experts=6, top_k=1)
    with pytest.raises(ValueError, match="divide"):
        moe_lib.make_moe_ffn(cfg6, mesh, dispatch="island", codec="int8")
    cfg, lp, x = _island_case()
    with pytest.raises(ValueError, match="batch"):
        moe_lib.moe_ffn_island(x[:5], lp, cfg, mesh, codec="int8")


def test_resolve_moe_knobs_env_and_validation(monkeypatch):
    monkeypatch.delenv("HOROVOD_MOE_DISPATCH", raising=False)
    monkeypatch.delenv("HOROVOD_MOE_COMPRESSION", raising=False)
    assert moe_lib.resolve_moe_knobs() == ("gspmd", "int8")
    monkeypatch.setenv("HOROVOD_MOE_DISPATCH", "island")
    monkeypatch.setenv("HOROVOD_MOE_COMPRESSION", "bf16")
    assert moe_lib.resolve_moe_knobs() == ("island", "bf16")
    # Explicit config values beat the env.
    assert moe_lib.resolve_moe_knobs("gspmd", "none") == ("gspmd", "none")
    with pytest.raises(ValueError, match="dispatch"):
        moe_lib.resolve_moe_knobs("islandd", None)
    monkeypatch.setenv("HOROVOD_MOE_COMPRESSION", "int9")
    with pytest.raises(ValueError, match="codec"):
        moe_lib.resolve_moe_knobs("island", None)


def test_make_moe_ffn_routing_discipline(devices, monkeypatch):
    """The PR 9 contract at the MoE construction point: gspmd, codec
    none, ep=1 and meshless builds all return the EXACT GSPMD closure
    (bitwise by code path); island + lossy genuinely quantizes (output
    differs) and follows the env knobs when the config is silent."""
    monkeypatch.delenv("HOROVOD_MOE_DISPATCH", raising=False)
    monkeypatch.delenv("HOROVOD_MOE_COMPRESSION", raising=False)
    mesh = build_mesh(ep=-1)
    cfg, lp, x = _island_case()
    ref = moe_lib.moe_ffn(x, lp, cfg)
    for fn in (
            moe_lib.make_moe_ffn(cfg, mesh),                  # env default
            moe_lib.make_moe_ffn(cfg, mesh, dispatch="gspmd",
                                 codec="int8"),
            moe_lib.make_moe_ffn(cfg, mesh, dispatch="island",
                                 codec="none"),
            moe_lib.make_moe_ffn(cfg, None, dispatch="island",
                                 codec="int8"),               # meshless
            moe_lib.make_moe_ffn(cfg, build_mesh(dp=-1),
                                 dispatch="island", codec="int8"),  # ep=1
    ):
        y, aux = fn(x, lp)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref[0]))
        assert float(aux) == float(ref[1])
    fn = moe_lib.make_moe_ffn(cfg, mesh, dispatch="island", codec="int8")
    y, _ = fn(x, lp)
    assert float(jnp.abs(y - ref[0]).max()) > 0.0
    # Env fallback drives the island too.
    monkeypatch.setenv("HOROVOD_MOE_DISPATCH", "island")
    monkeypatch.setenv("HOROVOD_MOE_COMPRESSION", "bf16")
    y_env, _ = moe_lib.make_moe_ffn(cfg, mesh)(x, lp)
    y_bf16, _ = moe_lib.moe_ffn_island(x, lp, cfg, mesh, codec="bf16")
    np.testing.assert_array_equal(np.asarray(y_env), np.asarray(y_bf16))


def test_moe_routing_stats_counts_overflow():
    """Hand-checkable overflow arithmetic: capacity 1 with every token
    claiming expert 0 keeps exactly one claim per batch row — overflow
    = B·(T−1), dropped fraction = (T−1)/T — and a roomy capacity
    factor reports zero overflow."""
    cfg = moe_lib.MoEConfig(n_experts=2, top_k=1, capacity_factor=1e-9)
    lp = _params(jax.random.PRNGKey(0), cfg)
    router = jnp.zeros_like(lp["router"]).at[:, 0].set(100.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (4, 6, 16))) + 0.1
    s = moe_lib.moe_routing_stats(x, router, cfg)
    assert s["moe_dispatch_overflow_tokens_total"] == 4 * 5
    assert abs(s["moe_dispatch_dropped_token_frac"] - 5 / 6) < 1e-9
    roomy = moe_lib.MoEConfig(n_experts=2, top_k=1, capacity_factor=8.0)
    s0 = moe_lib.moe_routing_stats(x, router, roomy)
    assert s0["moe_dispatch_overflow_tokens_total"] == 0.0
    assert s0["moe_dispatch_dropped_token_frac"] == 0.0


def test_record_moe_stats_counters_gauges_and_export():
    """*_total keys accumulate across batches (counter semantics), the
    fraction is a last-value gauge, and the first record registers the
    exporter so the rows ride the process's Prometheus exposition
    (docs/observability.md)."""
    # NOTE: horovod_tpu.metrics the ATTRIBUTE is the api metrics()
    # function (package __init__ re-exports shadow the submodule);
    # import the module's names directly, as moe.py itself does.
    from horovod_tpu.metrics import (NAMESPACE, metrics_prometheus,
                                     unregister_exporter)

    with moe_lib._moe_metrics_lock:
        moe_lib._moe_metrics.clear()
    unregister_exporter("moe")
    try:
        moe_lib.record_moe_stats({
            "moe_dispatch_overflow_tokens_total": 3.0,
            "moe_dispatch_dropped_token_frac": 0.25})
        moe_lib.record_moe_stats({
            "moe_dispatch_overflow_tokens_total": 2.0,
            "moe_dispatch_dropped_token_frac": 0.125,
            "moe_dispatch_bytes_saved_pct": 74.6,
            "moe_expert_load_max_over_mean": 1.5,
            "moe_compact_calls_share": 0.875,
            "moe_held_pairs_over_bound_max": 1.25,
            "moe_grouped_kernel_products_share": 0.5,
            "moe_held_pairs_run": 704.0,
            "moe_held_pairs_not_run": 0.0,
            "moe_held_experts_touched_mean": 116.0})
        m = moe_lib.moe_metrics()
        assert m["moe_dispatch_overflow_tokens_total"] == 5.0
        assert m["moe_dispatch_dropped_token_frac"] == 0.125
        assert m["moe_dispatch_bytes_saved_pct"] == 74.6
        assert m["moe_compact_calls_share"] == 0.875
        assert m["moe_held_pairs_over_bound_max"] == 1.25
        assert m["moe_grouped_kernel_products_share"] == 0.5
        assert m["moe_held_pairs_run"] == 704.0
        text = metrics_prometheus()
        for key in moe_lib.MOE_METRIC_KEYS:
            assert f"{NAMESPACE}_{key}" in text, key
    finally:
        unregister_exporter("moe")
        with moe_lib._moe_metrics_lock:
            moe_lib._moe_metrics.clear()


# ---------------------------------------------------------------------------
# Train-step integration: the compression=none bitwise pin and the
# int8 convergence gate (module-scoped f32 baseline, the
# test_quantized.py fixture pattern).
# ---------------------------------------------------------------------------

_MOE_LM_STEPS = 10


def _moe_lm_run(dispatch, compression):
    """One tiny MoE-LM training run on the ep=8 mesh (fixed cfg / data
    / optimizer across arms). Returns (losses, final_params_leaves)."""
    import optax

    mesh = build_mesh(ep=-1)
    # n_layers=1 halves each arm's compile; 8 experts over ep=8, batch
    # 8 rows (the island's B % ep == 0 requirement).
    cfg = tr.TransformerConfig.tiny(
        n_experts=8, n_layers=1, sp_attention="local", dtype=jnp.float32,
        remat=False, moe_dispatch=dispatch, moe_compression=compression)
    init_state, step, _ = tr.make_train_step(cfg, mesh,
                                             optax.adam(1e-2))
    st = jax.jit(init_state)(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                              cfg.vocab_size)
    losses = []
    for _ in range(_MOE_LM_STEPS):
        st, loss = step(st, {"tokens": toks})
        losses.append(float(loss))
    return losses, jax.tree.leaves(st["params"])


@pytest.fixture(scope="module")
def moe_lm_gspmd_reference():
    """The pre-PR GSPMD arm — computed ONCE; the bitwise-none pin and
    the slow int8 convergence gate both diff against it."""
    return _moe_lm_run("gspmd", None)


def test_island_none_train_bitwise_ten_steps(devices,
                                             moe_lm_gspmd_reference):
    """The ISSUE 18 acceptance pin: moe_dispatch='island' at
    compression=none over 10 REAL train steps is bitwise-identical to
    the GSPMD arm — losses and every final parameter byte. Holds by
    construction (make_moe_ffn routes none to the GSPMD closure, the
    PR 9 discipline); this run is the regression guard on that
    routing."""
    ref_losses, ref_params = moe_lm_gspmd_reference
    losses, params = _moe_lm_run("island", "none")
    assert losses == ref_losses
    for a, b in zip(params, ref_params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow  # slow from the start (the ISSUE 18 tier budget
# note): the int8 island's numerics are already pinned tier-1 at the
# block level (test_quantized alltoall error bounds) and the module
# level (test_island_lossy_codec_error_bounded, the grads test); this
# arm adds a third full train-step compile on the 8-device mesh (~30s)
# to show END-TO-END convergence, an overlap that rides the slow tier.
def test_island_int8_lm_convergence_matches_f32(devices,
                                                moe_lm_gspmd_reference):
    """The convergence gate: the MoE LM trained with int8 quantized
    dispatch must track the f32 run — an order of magnitude off the
    starting loss, and within a small absolute band of the f32 arm's
    final loss (both land near memorization here, so a relative band
    would amplify noise-floor jitter)."""
    ref_losses, _ = moe_lm_gspmd_reference
    losses, _ = _moe_lm_run("island", "int8")
    assert losses[-1] < 0.1 * losses[0], losses
    assert losses[-1] < ref_losses[-1] + 0.1, (
        losses[-1], ref_losses[-1])
