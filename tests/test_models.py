"""Model-zoo tests: transformer forward/grad under real mesh shardings
(ring vs local attention equivalence), ResNet-50 shape/grad sanity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map

from horovod_tpu.models import (
    TransformerConfig, init_transformer, transformer_forward, lm_loss,
    make_train_step, resnet50,
)
from horovod_tpu.parallel import build_mesh
from horovod_tpu.parallel.ring_attention import (
    local_attention, ring_self_attention, ulysses_attention,
)
from jax.sharding import NamedSharding, PartitionSpec as P


def test_ring_attention_matches_local(devices):
    mesh = build_mesh(sp=8)
    B, T, H, D = 2, 64, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.float32) for kk in ks)
    ref = local_attention(q, k, v, causal=True)
    spec = P(None, "sp", None, None)
    ring = jax.jit(shard_map(
        lambda a, b, c: ring_self_attention(a, b, c, axis_name="sp"),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))
    out = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_matches_local(devices):
    mesh = build_mesh(dp=2, sp=4)
    B, T, H, D = 2, 32, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.float32) for kk in ks)
    ref = local_attention(q, k, v, causal=True)
    spec = P(None, "sp", None, None)
    uly = jax.jit(shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, axis_name="sp"),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))
    out = uly(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def tiny_cfg():
    return TransformerConfig.tiny(dtype=jnp.float32, remat=False)


def test_transformer_forward_shape(tiny_cfg):
    params = init_transformer(tiny_cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    logits = transformer_forward(params, toks, tiny_cfg)
    assert logits.shape == (2, 16, tiny_cfg.vocab_size)


def test_transformer_sharded_matches_unsharded(devices, tiny_cfg):
    mesh = build_mesh(dp=2, sp=2, tp=2)
    params = init_transformer(tiny_cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                              tiny_cfg.vocab_size)
    ref = lm_loss(params, {"tokens": toks}, tiny_cfg)
    sharded = jax.jit(
        lambda p, b: lm_loss(p, b, tiny_cfg, mesh))(params, {"tokens": toks})
    np.testing.assert_allclose(float(sharded), float(ref), rtol=1e-5)


def test_transformer_train_step_runs_sharded(devices):
    cfg = TransformerConfig.tiny()
    mesh = build_mesh(dp=2, fsdp=2, sp=2, tp=1)
    init_state, step, _ = make_train_step(cfg, mesh)
    state = init_state(jax.random.PRNGKey(0))
    toks = jnp.zeros((4, 33), jnp.int32)
    batch = {"tokens": jax.device_put(
        toks, NamedSharding(mesh, P(("dp", "fsdp"), None)))}
    state, loss1 = step(state, batch)
    state, loss2 = step(state, batch)
    assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
    assert float(loss2) < float(loss1)  # overfits constant batch


def test_train_step_adafactor_statistics_not_pinned_to_param_layout(devices):
    """Adafactor's ``v_row``/``v_col`` share the params' tree but not
    their shapes: only leaves that mirror a param take its sharding."""
    import optax
    cfg = TransformerConfig.tiny()
    mesh = build_mesh(dp=2, fsdp=2, devices=devices[:4])
    init_state, step, param_sh = make_train_step(
        cfg, mesh, optax.adafactor(1e-2, min_dim_size_to_factor=32))
    state = jax.jit(init_state)(jax.random.PRNGKey(0))
    jax.tree.map(lambda a, sh: a.sharding.is_equivalent_to(sh, a.ndim)
                 or pytest.fail(f"{a.shape}: {a.sharding} != {sh}"),
                 state["params"], param_sh)
    v_row = state["opt"][0].v_row
    assert (jax.tree.leaves(v_row)[0].shape
            != jax.tree.leaves(state["params"])[0].shape)
    toks = jnp.zeros((4, 33), jnp.int32)
    state, loss1 = step(state, {"tokens": toks})
    state, loss2 = step(state, {"tokens": toks})
    assert np.isfinite(float(loss1)) and float(loss2) < float(loss1)


def test_train_step_factories_build_under_rbg_prng(devices):
    """The factories size the abstract state from a key of the active
    PRNG implementation (``rbg`` keys are ``(4,)``, not ``(2,)``)."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel.pipeline import make_pp_train_step
    cfg = TransformerConfig.tiny(dtype=jnp.float32)
    with jax.default_prng_impl("rbg"):
        key = jax.random.PRNGKey(0)
        assert key.shape == (4,)
        for mesh, kw in ((build_mesh(dp=2, fsdp=2, devices=devices[:4]), {}),
                         (build_mesh(dp=4, devices=devices[:4]),
                          {"compression": hvd.Compression.int8}),
                         (build_mesh(dp=2, fsdp=2, devices=devices[:4]),
                          {"compression": hvd.Compression.int8})):
            init_state, _, param_sh = make_train_step(cfg, mesh, **kw)
        state = init_state(key)
        assert (jax.tree.structure(state["params"])
                == jax.tree.structure(param_sh))
        make_pp_train_step(cfg, build_mesh(dp=2, pp=2, devices=devices[:4]),
                           n_micro=2)


# ~48s of CPU compile on the current CI box — the single heaviest
# tier-1 test. Conv/BN layer coverage stays via the sync-BN tests and
# the transformer train-step test below; the full resnet smoke runs
# with the slow tier (tier-1 budget discipline, same precedent as
# PR 1's redundant-variant moves).
@pytest.mark.slow
def test_resnet50_forward_and_grad():
    model = resnet50(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)

    def loss_fn(params):
        out, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return jnp.mean(out ** 2)

    g = jax.grad(loss_fn)(variables["params"])
    assert np.isfinite(float(jax.tree.reduce(
        lambda a, b: a + jnp.sum(jnp.abs(b)), g, 0.0)))


def test_embed_lookup_island_matches_gather(devices):
    """Vocab-parallel embed island == plain gather, values and grads."""
    from horovod_tpu.models.transformer import embed_lookup

    mesh = build_mesh(dp=2, fsdp=2, tp=2)
    V, D = 32, 16
    emb = jax.random.normal(jax.random.PRNGKey(0), (V, D), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, V)
    emb_sh = jax.device_put(emb, NamedSharding(mesh, P("tp", "fsdp")))

    out = jax.jit(lambda e, t: embed_lookup(e, t, jnp.float32, mesh))(
        emb_sh, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(emb[toks]),
                               rtol=1e-6, atol=1e-6)

    # Gradients: d/d_emb of a scalar of the looked-up rows must match the
    # plain-gather scatter-add (exercises the island's transpose).
    w = jax.random.normal(jax.random.PRNGKey(2), out.shape, jnp.float32)
    g_island = jax.jit(jax.grad(
        lambda e: (embed_lookup(e, toks, jnp.float32, mesh) * w).sum()))(
            emb_sh)
    g_ref = jax.grad(lambda e: (e[toks] * w).sum())(emb)
    np.testing.assert_allclose(np.asarray(g_island), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)


def test_dryrun_spmd_red_flag_scanner():
    """The dryrun must raise on an SPMD full-remat warning line."""
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g

    g._check_spmd_log("ordinary compile chatter\n")  # clean: no raise
    with pytest.raises(RuntimeError, match="red flag"):
        g._check_spmd_log(
            "W0730 spmd_partitioner.cc:652] [SPMD] Involuntary full "
            "rematerialization. The compiler cannot ...\n")
