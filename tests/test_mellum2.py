"""A stack of window and full layers TRAINED, with a chip's share of
the experts (ISSUE 34): the trainer's loop over a mixed configuration's
lists of layers, the window in the flash kernels, YaRN on full layers,
``_held_experts`` under ``jax.grad`` with the load-balancing term over
every router output. At a tiny size with seeded weights in float32,
against ``tests/reference_mellum2.py``: the plain forward, loss and
``jax.grad`` of the same equations, no kernel, no sort."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_mellum2 as ref
import reference_trinity
from horovod_tpu.models import (TransformerConfig, init_transformer,
                                make_train_step)
from horovod_tpu.models import moe as moe_lib
from horovod_tpu.models.transformer import (Rotary, forward_with_aux, lm_loss,
                                            moe_routing_report)
from horovod_tpu.parallel import build_mesh

SEQ, WINDOW = 48, 16
YARN = {"theta": 1e4, "factor": 4.0, "original_max_seq": 24,
        "beta_fast": 4.0, "beta_slow": 1.0, "attention_factor": 1.25}


def tiny(**kw):
    base = dict(
        vocab_size=96, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        d_head=32, d_ff=32, max_seq=64, rope_theta=1e4, norm_eps=1e-6,
        layer_types=("sliding", "sliding", "sliding", "full"),
        attn_window=WINDOW,
        layer_rotary={"sliding": {"theta": 1e4}, "full": YARN},
        n_experts=8, moe_top_k=2, moe_capacity_factor=None,
        moe_norm_topk_prob=True, moe_aux_loss_coef=0.05,
        moe_experts_held=3, moe_expert_offset=2,
        dtype=jnp.float32, sp_attention="flash", remat=True,
        remat_policy="full")
    base.update(kw)
    return TransformerConfig(**base)


def sizes_of(cfg):
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    model["layer_rotary"] = {
        kind: dataclasses.asdict(how) for kind, how in cfg.layer_rotary}
    return ref.sizes_of({"model": model})


def seeded(cfg, seed=0):
    """Seeded weights whose norm gains are not an initialisation's ones,
    and whose q and k are large enough that attention is not uniform
    (a rotary embedding left out then shows)."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("norm"):
            return a + 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        if name in ("wq", "wk"):
            return 2.0 * a
        return a

    return jax.tree_util.tree_map_with_path(shake, params)


def rows_of(cfg, seed=1, batch=2):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, SEQ + 1), dtype=np.int32)


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# (a) --------------------------------------------------------------------

def test_the_loss_and_every_gradient_leaf_match_the_reference(devices):
    """``make_train_step``'s loss is ``lm_loss``: its value, and its
    gradient with respect to every parameter leaf, against ``jax.grad``
    of the reference's loss; then one step through the factory gives
    that loss."""
    cfg = tiny()
    params, rows = seeded(cfg), rows_of(cfg)
    sizes = sizes_of(cfg)
    want, want_g = jax.value_and_grad(ref.loss)(params, rows, sizes)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(p, {"tokens": jnp.asarray(rows)}, cfg)))(params)
    assert abs(float(got) - float(want)) < 2e-6 * float(want)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_g))
    assert flat_got.keys() == flat_want.keys()
    for path, g in flat_got.items():
        assert float(jnp.abs(flat_want[path]).max()) > 0, path
        assert rel(g, flat_want[path]) < 5e-5, jax.tree_util.keystr(path)

    init, step, _ = make_train_step(cfg, build_mesh(devices=devices[:1],
                                                    dp=1))
    state = init(jax.random.PRNGKey(0))
    state = {**state, "params": jax.device_put(params)}
    _, loss = step(state, {"tokens": jnp.asarray(rows)})
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)


@pytest.mark.parametrize("how", [{}, {"store": jnp.bfloat16},
                                 {"without": ("aux",)}],
                         ids=["as_it_is", "stored_as_bf16", "without_aux"])
def test_the_gradient_taken_a_layer_at_a_time_is_jax_grad(how):
    """``gradient_by_layer`` (what fits at 8192 positions) names every
    leaf once and gives ``jax.grad`` of the reference's loss."""
    cfg = tiny()
    params, rows = seeded(cfg), rows_of(cfg)
    sizes = sizes_of(cfg)
    want = {tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path): g
            for path, g in jax.tree_util.tree_leaves_with_path(
                jax.grad(ref.loss)(params, rows, sizes, **how))}
    got = list(ref.gradient_by_layer(params, rows, sizes, **how))
    assert sorted(map(str, want)) == sorted(str(p) for p, _ in got)
    for path, g in got:
        assert g.dtype == jnp.float32 and g.shape == want[path].shape
        assert rel(g, want[path]) < 1e-5, path


def test_yarn_frequencies_are_the_published_ones():
    """At the published numbers the ramp runs from pair 18 to pair 35
    (ISSUE 34's arithmetic), pairs below keep their frequency and pairs
    above take a sixteenth of it; the program's table is the
    reference's."""
    how = {"theta": 500000.0, "factor": 16.0, "original_max_seq": 8192,
           "beta_fast": 32.0, "beta_slow": 1.0,
           "attention_factor": 1.2772588722239782}
    got = Rotary(**how).frequencies(128)
    want, m = ref.rotary_table(how, 128)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert m == pytest.approx(0.1 * np.log(16.0) + 1.0)
    plain = 500000.0 ** -(np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all(np.diff(got / plain)[18:35] < 0)


# (b) --------------------------------------------------------------------

@pytest.mark.parametrize("mechanism", ["window", "yarn", "attention_factor",
                                       "renorm", "aux"])
def test_a_reference_without_one_mechanism_fails_the_comparison(mechanism):
    cfg = tiny()
    params, rows = seeded(cfg), rows_of(cfg)
    sizes = sizes_of(cfg)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(p, {"tokens": jnp.asarray(rows)}, cfg)))(params)
    want, want_g = jax.value_and_grad(ref.loss)(
        params, rows, sizes, without=(mechanism,))
    worst = max(rel(g, w) for g, w in zip(jax.tree.leaves(got_g),
                                          jax.tree.leaves(want_g)))
    assert (abs(float(got) - float(want)) > 1e-4 * float(want)
            or worst > 1e-2), (mechanism, float(got), float(want), worst)
    assert worst > 5e-4, (mechanism, worst)


# (c) --------------------------------------------------------------------

def test_the_shares_add_up_in_training():
    """Over the four offsets of two experts each: the layer outputs,
    the gradients with respect to ``x`` and the router's gradient from
    the routed sum add up to the uncut layer's, and each share's expert
    gradients are the uncut layer's for those experts."""
    whole = moe_lib.MoEConfig(n_experts=8, top_k=2, capacity_factor=None,
                              norm_topk_prob=True, aux_loss_coef=0.0)
    lp = jax.tree.map(lambda a: a[0], moe_lib.init_moe_params(
        jax.random.PRNGKey(3), 1, 32, 16, whole, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))
    cot = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def routed(x, lp, cfg):
        y, _aux = moe_lib.moe_ffn_dropless(x, lp, cfg)
        return jnp.sum(y * cot), y

    (_, y), (gx, glp) = jax.value_and_grad(routed, (0, 1), has_aux=True)(
        x, lp, whole)
    y_sum, gx_sum, gr_sum = 0.0, 0.0, 0.0
    for offset in (0, 2, 4, 6):
        share = dataclasses.replace(whole, experts_held=2,
                                    expert_offset=offset)
        held = {**lp, **{name: lp[name][offset:offset + 2]
                         for name in ("w_gate", "w_up", "w_down")}}
        (_, y_s), (gx_s, g_s) = jax.value_and_grad(
            routed, (0, 1), has_aux=True)(x, held, share)
        y_sum, gx_sum, gr_sum = y_sum + y_s, gx_sum + gx_s, \
            gr_sum + g_s["router"]
        for name in ("w_gate", "w_up", "w_down"):
            np.testing.assert_allclose(
                g_s[name], glp[name][offset:offset + 2], atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(y_sum, y, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(gx_sum, gx, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(gr_sum, glp["router"], atol=2e-5, rtol=1e-4)


def test_the_balancing_term_of_a_share_is_the_uncut_layers():
    """The auxiliary term is over all router outputs of the chip's
    tokens, so a share's is the whole layer's, value and gradient."""
    whole = moe_lib.MoEConfig(n_experts=8, top_k=2, capacity_factor=None,
                              aux_loss_coef=0.1, z_loss_coef=0.01)
    share = dataclasses.replace(whole, experts_held=2, expert_offset=4)
    lp = jax.tree.map(lambda a: a[0], moe_lib.init_moe_params(
        jax.random.PRNGKey(3), 1, 32, 16, whole, jnp.float32))
    held = {**lp, **{name: lp[name][4:6]
                     for name in ("w_gate", "w_up", "w_down")}}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))

    def aux(x, lp, cfg):
        return moe_lib.moe_ffn_dropless(x, lp, cfg)[1]

    want, want_g = jax.value_and_grad(aux, (0, 1))(x, lp, whole)
    got, got_g = jax.value_and_grad(aux, (0, 1))(x, held, share)
    assert float(want) > 0
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_g[0], want_g[0], atol=1e-7, rtol=1e-5)
    np.testing.assert_allclose(got_g[1]["router"], want_g[1]["router"],
                               atol=1e-7, rtol=1e-5)


# (d) --------------------------------------------------------------------

def test_no_pair_on_a_held_expert_is_dropped_under_a_skewed_router():
    """A router that sends most tokens to one held expert: every pair
    on a held expert is run (the counter reads 0 and the loss is the
    reference's, which has no sort to lose a pair in)."""
    cfg = tiny()
    params, rows = seeded(cfg), rows_of(cfg)
    for lp in params["layers"]:
        lp["moe"]["router"] = lp["moe"]["router"].at[:, 3].multiply(6.0)
    report = moe_routing_report(params, jnp.asarray(rows[:, :-1]), cfg)
    assert report["moe_dispatch_dropped_token_frac"] == 0
    assert report["moe_dispatch_overflow_tokens_total"] == 0
    assert report["moe_expert_load_max_over_mean"] > 1.5
    assert 0.3 < report["moe_local_pair_share"] < 1.0
    want = ref.loss(params, rows, sizes_of(cfg))
    got = jax.jit(lambda p: lm_loss(p, {"tokens": jnp.asarray(rows)}, cfg))(
        params)
    assert abs(float(got) - float(want)) < 2e-6 * float(want)


# (e) --------------------------------------------------------------------

def test_the_trainers_loop_runs_a_trinity_shaped_stack():
    """Dense then sparse layers, an attention gate, sandwich norms and
    no rotary embedding on full layers: ``forward_with_aux`` agrees with
    ``tests/reference_trinity.py``'s forward, so the loop over a mixed
    configuration's lists is not one model's."""
    import test_trinity

    cfg = test_trinity.tiny(remat=True, remat_policy="full")
    params = test_trinity.seeded(cfg)
    tokens = np.random.default_rng(2).integers(0, cfg.vocab_size, 56,
                                               dtype=np.int32)
    want = reference_trinity.logits(params, tokens, test_trinity.sizes_of(cfg))
    got, _aux = jax.jit(lambda p, t: forward_with_aux(p, t, cfg))(
        params, jnp.asarray(tokens)[None])
    assert test_trinity.gap(np.asarray(got[0]), np.asarray(want)) < 2e-5


def test_what_is_not_built_for_a_trained_mixed_stack_is_refused_by_name(
        devices):
    """The pipeline's steps, the quantized steps, and every mesh axis
    but dp."""
    import horovod_tpu as hvd
    from horovod_tpu.parallel.pipeline import (make_pp_train_step,
                                               make_pp_train_step_1f1b)

    cfg = tiny()
    for axis in ("tp", "sp", "ep", "fsdp"):
        mesh = build_mesh(devices=devices[:2], **{axis: 2})
        with pytest.raises(NotImplementedError, match=axis):
            make_train_step(cfg, mesh)
    pp = build_mesh(devices=devices[:2], pp=2)
    for factory in (make_pp_train_step, make_pp_train_step_1f1b):
        with pytest.raises(NotImplementedError, match="pipeline"):
            factory(cfg, pp, n_micro=2)
    with pytest.raises(NotImplementedError, match="compression"):
        make_train_step(cfg, build_mesh(devices=devices[:2], dp=2),
                        compression=hvd.Compression.int8)
    from horovod_tpu.ops.flash_attention import flash_attention
    q = jnp.zeros((1, 32, 2, 32))
    with pytest.raises(NotImplementedError, match="causal=False"):
        flash_attention(q, q, q, causal=False, window=8)


def test_two_data_parallel_chips_train_a_mixed_stack(devices):
    """dp is the one axis built: two chips' step gives the loss of one
    chip on the same rows (each shard's auxiliary term is its own
    tokens', and their mean is taken)."""
    cfg = tiny(moe_aux_loss_coef=0.0)
    rows = jnp.asarray(rows_of(cfg))
    losses = []
    for n in (1, 2):
        init, step, _ = make_train_step(
            cfg, build_mesh(devices=devices[:n], dp=n))
        _, loss = step(init(jax.random.PRNGKey(0)), {"tokens": rows})
        losses.append(float(loss))
    assert losses[0] == pytest.approx(losses[1], rel=1e-5)


# (f) --------------------------------------------------------------------
# The compacted dispatch (ISSUE 40): a call large enough compacts its
# held pairs to ``moe.held_row_bound`` rows, and runs every row where
# they exceed it. Here 256 tokens x 2 choices, 2 of 8 experts held: the
# bound is 256 of the 512 rows, and the size at which it engages (a
# module constant, 16 384 rows left out) is brought down to these shapes.

PAIRS, BOUND = 512, 256


def _a_share():
    share = moe_lib.MoEConfig(n_experts=8, top_k=2, capacity_factor=None,
                              norm_topk_prob=True, aux_loss_coef=0.05,
                              experts_held=2, expert_offset=2)
    lp = jax.tree.map(lambda a: a[0], moe_lib.init_moe_params(
        jax.random.PRNGKey(3), 1, 32, 16, share, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, PAIRS // 4, 32))
    return share, lp, x


def _engage(monkeypatch, share):
    assert moe_lib.held_row_bound(PAIRS, share) is None
    monkeypatch.setattr(moe_lib, "_COMPACT_MIN_ROWS_SAVED", PAIRS - BOUND)
    assert moe_lib.held_row_bound(PAIRS, share) == BOUND
    assert moe_lib.held_row_bound(PAIRS - 1, share) is None


def _close(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("router", ["even", "skewed"])
def test_the_compact_form_is_the_whole_row_form(monkeypatch, router):
    """``y``, ``aux`` and the gradients of ``x``, the router and the
    three expert stacks, with the bound engaged against without: under
    an even router the held pairs fit the bound, under one that sends
    every token to a held expert they do not and every row is run."""
    share, lp, x = _a_share()
    if router == "skewed":
        x = x + 1.0
        lp = {**lp, "router": lp["router"].at[:, 3].add(0.5)
              .at[:, 2].add(0.05)}
    cot = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def block(x, lp):
        y, aux = moe_lib.moe_ffn_dropless(x, lp, share)
        return jnp.sum(y * cot) + aux, (y, aux)

    want = jax.value_and_grad(block, (0, 1), has_aux=True)(x, lp)
    counts, _ = moe_lib.routing_counts(x, lp["router"], share)
    assert moe_lib.compaction_summary(counts[None], PAIRS, share) == {}
    _engage(monkeypatch, share)
    got = jax.value_and_grad(block, (0, 1), has_aux=True)(x, lp)
    _close(got, want)
    assert float(jnp.abs(want[1][1]["router"]).max()) > 0
    counts, not_run = moe_lib.routing_counts(x, lp["router"], share)
    assert float(not_run) == 0
    fits = float(counts.sum()) <= BOUND
    assert fits == (router == "even")
    assert moe_lib.compaction_summary(counts[None], PAIRS, share) == {
        "moe_compact_calls_share": float(fits),
        "moe_held_pairs_over_bound_max": float(counts.sum()) / BOUND}


@pytest.mark.parametrize("held_pairs", [BOUND - 1, BOUND, BOUND + 1])
def test_a_share_at_the_bound_and_one_row_over(monkeypatch, held_pairs):
    """Routing made by hand so that exactly ``held_pairs`` of the 512
    pairs lie on a held expert: at the bound the compact form runs them
    all; one over, the first ``BOUND`` rows alone would lose a pair, and
    the call takes the whole-row form: no pair is dropped either way."""
    share, lp, x = _a_share()
    rng = np.random.default_rng(held_pairs)
    experts = rng.choice([0, 1, 4, 5, 6, 7], PAIRS)
    experts[rng.permutation(PAIRS)[:held_pairs]] = rng.choice(
        [2, 3], held_pairs)
    experts = jnp.asarray(experts.reshape(-1, 2), jnp.int32)
    gates = jax.random.uniform(jax.random.PRNGKey(6), experts.shape)
    cot = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    w = (lp["w_gate"], lp["w_up"], lp["w_down"])

    def block(x, w, gates):
        y = moe_lib._held_experts(
            x, dict(zip(("w_gate", "w_up", "w_down"), w)), share, gates,
            experts)
        return jnp.sum(y * cot), y

    want = jax.value_and_grad(block, (0, 1, 2), has_aux=True)(x, w, gates)
    _engage(monkeypatch, share)
    got = jax.value_and_grad(block, (0, 1, 2), has_aux=True)(x, w, gates)
    _close(got, want)
    assert float(moe_lib.held_pairs_not_run(experts, share)) == 0

    local, held = moe_lib.held_pairs(experts, share)
    order, sizes = moe_lib._sorted_by_expert(local, 3)
    assert int(sizes[:2].sum()) == held_pairs
    first_rows = moe_lib._held_rows(
        x.reshape(-1, 32), w, gates, held, order, jnp.argsort(order),
        sizes[:2], rows=BOUND).reshape(x.shape)
    lost = float(jnp.abs(first_rows - want[0][1]).max())
    assert (lost > 1e-3) == (held_pairs > BOUND), lost
    counts = jnp.asarray([[held_pairs - 7.0, 7.0]])
    assert moe_lib.compaction_summary(counts, PAIRS, share) == {
        "moe_compact_calls_share": float(held_pairs <= BOUND),
        "moe_held_pairs_over_bound_max": held_pairs / BOUND}


@pytest.mark.parametrize("router", ["even", "skewed"])
def test_a_trained_stack_compacts_and_drops_nothing(monkeypatch, router):
    """The whole model with the bound engaged (192 pairs a layer, 3 of
    8 experts held: 128 rows), under remat: the loss and every gradient
    leaf are the reference's, which has no sort and no bound; the report
    says how many layers compacted and how full the fullest was, and
    says neither where no call is large enough."""
    cfg = tiny()
    params, rows = seeded(cfg), rows_of(cfg)
    if router == "skewed":          # every token to one held expert or two
        for lp in params["layers"]:
            r = lp["moe"]["router"]
            lp["moe"]["router"] = r.at[:, 2:5].set(
                6.0 * r[:, 3:4] * jnp.asarray([-1.0, 1.0, 1.0]))
    tokens = jnp.asarray(rows[:, :-1])
    before = moe_routing_report(params, tokens, cfg)
    assert "moe_compact_calls_share" not in before
    assert "moe_held_pairs_over_bound_max" not in before
    monkeypatch.setattr(moe_lib, "_COMPACT_MIN_ROWS_SAVED", 64)
    assert moe_lib.held_row_bound(2 * SEQ * 2, cfg.moe) == 128
    report = moe_routing_report(params, tokens, cfg)
    assert report["moe_dispatch_dropped_token_frac"] == 0
    assert report["moe_local_pair_share"] == before["moe_local_pair_share"]
    fullest = report["moe_held_pairs_over_bound_max"]
    assert fullest >= report["moe_local_pair_share"] * 192 / 128
    assert report["moe_compact_calls_share"] in (0.0, 0.25, 0.5, 0.75, 1.0)
    assert (report["moe_compact_calls_share"] == 1.0) == (fullest <= 1.0)
    if router == "skewed":
        assert fullest > 1.0        # some layer takes the whole-row form
    want, want_g = jax.value_and_grad(ref.loss)(params, rows, sizes_of(cfg))
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(p, {"tokens": jnp.asarray(rows)}, cfg)))(params)
    assert abs(float(got) - float(want)) < 2e-6 * float(want)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert rel(g, w) < 5e-5


# (g) --------------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_one_text():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def body(path):
        text = open(os.path.join(root, path)).read()
        return text[text.index('"""', 3):]

    assert body("tests/reference_mellum2.py") == body(
        "benchmark/reference_mellum2.py")
