"""OLMoE through the program (PR 26): q/k normalisation, the sorted,
dropless expert dispatch, gates as the softmax gave them and the router
z-loss, against the plain float32 reference of ``reference_olmoe.py``
on seeded weights at a tiny size; the four departures that must not
agree; an imbalanced router; two data-parallel shards against one
device; the defaults' programs unchanged; the serve programs' refusal.
"""
import contextlib
import dataclasses
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (TransformerConfig, init_transformer, lm_loss,
                                make_train_step, moe as moe_lib,
                                transformer_forward)
from horovod_tpu.models.transformer import forward_with_aux, moe_routing_report
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import decode as decode_lib

import reference_olmoe as ref

HERE = os.path.dirname(os.path.abspath(__file__))
SEQ = 64
# Both sides compute in float32 on the CPU and differ in the order of
# their sums (sorted rows against one masked pass per expert, a scan
# against a loop): a few ulps a matmul, compounding through two layers
# to about 1e-6 of a value's scale. 2e-5 leaves an order of magnitude;
# the smallest departure below (the z-loss at its coefficient of 0.001)
# moves the loss by 1e-3 of its value.
RTOL = 2e-5


def _cfg(top_k=2, **kw):
    base = dict(dtype=jnp.float32, n_heads=4, n_kv_heads=4, d_ff=32,
                n_experts=8, moe_top_k=top_k, moe_capacity_factor=None,
                moe_norm_topk_prob=False, moe_aux_loss_coef=0.01,
                moe_z_loss_coef=0.001, qk_norm=True, sp_attention="local",
                remat=False)
    base.update(kw)
    return TransformerConfig.tiny(**base)


def _sizes(cfg):
    return ref.sizes_of({"model": {
        "n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads, "d_model": cfg.d_model,
        "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
        "n_experts": cfg.n_experts, "moe_top_k": cfg.moe_top_k,
        "moe_aux_loss_coef": cfg.moe_aux_loss_coef,
        "moe_z_loss_coef": cfg.moe_z_loss_coef}})


def _seeded(cfg, seed=0):
    """Parameters with every norm weight off 1, so that a norm left out
    or misplaced shows, and two rows of tokens."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        w = params["layers"][name]
        params["layers"][name] = w + 0.2 * jax.random.normal(
            next(keys), w.shape, w.dtype)
    rows = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, SEQ + 1), dtype=np.int32)
    return params, rows


def _close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-30)
    err = float(np.abs(got - want).max())
    assert err <= RTOL * scale, f"{what}: {err:.3e} of {scale:.3e}"


def _program_terms(cfg, params, rows):
    """The program's three loss terms, each before its coefficient:
    ``lm_loss`` with both router coefficients 0, then each at 1."""
    def at(aux, z):
        c = dataclasses.replace(cfg, moe_aux_loss_coef=aux,
                                moe_z_loss_coef=z)
        return float(lm_loss(params, {"tokens": rows}, c))
    ce = at(0.0, 0.0)
    return {"cross_entropy": ce, "load_balance": at(1.0, 0.0) - ce,
            "router_z": at(0.0, 1.0) - ce}


def _agrees(cfg, params, rows, sizes):
    """Raises AssertionError unless the program under ``cfg`` agrees
    with the reference at ``sizes``: logits, the three loss terms, the
    loss, and the gradient of every parameter leaf."""
    want = ref.loss_terms(params, rows, sizes)
    _close(transformer_forward(params, rows[:, :-1], cfg),
           want.pop("logits"), "logits")
    got = _program_terms(cfg, params, rows)
    for term in ("cross_entropy", "load_balance", "router_z"):
        # the two router terms come out as differences of losses
        assert abs(got[term] - float(want[term])) <= RTOL * max(
            abs(float(want["loss"])), abs(float(want[term]))), term
    loss, grads = jax.value_and_grad(lm_loss)(params, {"tokens": rows}, cfg)
    _close(loss, want["loss"], "loss")
    want_grads = jax.grad(ref.loss)(params, rows, sizes)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    want_flat = jax.tree.leaves(want_grads)
    assert len(flat) == len(want_flat) == 15
    for (path, g), w in zip(flat, want_flat):
        assert float(np.abs(np.asarray(w)).max()) > 0, path
        _close(g, w, "grad " + jax.tree_util.keystr(path))


@pytest.mark.parametrize("top_k", [2, 4])
def test_program_agrees_with_the_float32_reference(top_k):
    cfg = _cfg(top_k)
    params, rows = _seeded(cfg)
    _agrees(cfg, params, rows, _sizes(cfg))
    stats = moe_routing_report(params, rows[:, :-1], cfg)
    assert stats["moe_dispatch_dropped_token_frac"] == 0.0
    assert stats["moe_expert_load_max_over_mean"] >= 1.0


@pytest.mark.parametrize("departure", [
    # a capacity: the one-hot dispatch turns claims away (and counts
    # the balance loss on first choices only)
    {"moe_capacity_factor": 0.5},
    {"moe_norm_topk_prob": True},       # gates renormalised to sum to 1
    {"qk_norm": False},                 # the q/k norm left out
    {"moe_z_loss_coef": 0.0},           # the z-loss left out
])
def test_a_departure_from_the_architecture_fails_the_comparison(departure):
    cfg = _cfg()
    params, rows = _seeded(cfg)
    with pytest.raises(AssertionError):
        _agrees(_cfg(**departure), params, rows, _sizes(cfg))


def test_an_imbalanced_router_drops_nothing_and_agrees():
    cfg = _cfg()
    params, rows = _seeded(cfg)
    # One feature that every token carries, large and positive after
    # the norm; the router sends it to expert 0 and away from expert 5.
    params["embed"] = params["embed"].at[:, 0].set(4.0)
    router = params["layers"]["moe"]["router"]
    params["layers"]["moe"]["router"] = (
        router.at[:, 0, 0].set(3.0).at[:, 0, 5].set(-3.0))
    layer0 = params["embed"][rows[:, :-1]]            # stands for h
    stats = moe_lib.moe_routing_stats(
        layer0, params["layers"]["moe"]["router"][0], cfg.moe)
    assert stats["moe_dispatch_dropped_token_frac"] == 0.0
    assert stats["moe_dispatch_overflow_tokens_total"] == 0.0
    counts, _ = moe_lib.routing_counts(
        layer0, params["layers"]["moe"]["router"][0], cfg.moe)
    assert counts[0] == 2 * SEQ and counts[5] == 0    # all, and none
    report = moe_routing_report(params, rows[:, :-1], cfg)
    assert report["moe_expert_load_max_over_mean"] >= 3.0
    assert report["moe_dispatch_dropped_token_frac"] == 0.0
    _agrees(cfg, params, rows, _sizes(cfg))
    moe_lib.record_moe_stats(report)
    assert moe_lib.moe_metrics()["moe_expert_load_max_over_mean"] >= 3.0
    assert "moe_expert_load_max_over_mean" in moe_lib.MOE_METRIC_KEYS


@pytest.mark.parametrize("axes", [{"dp": 2}, {"dp": 2, "fsdp": 2}])
def test_shards_that_route_their_own_rows_agree_with_one_device(devices,
                                                                axes):
    cfg = _cfg(remat=True, remat_policy="full")
    rows = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (4, SEQ + 1), dtype=np.int32))

    def two_steps(mesh):
        init, step, _ = make_train_step(cfg, mesh)
        state = init(jax.random.PRNGKey(0))
        state, first = step(state, {"tokens": rows})
        state, second = step(state, {"tokens": rows})
        return float(first), float(second), state["params"]

    n = int(np.prod(list(axes.values())))
    one = two_steps(build_mesh(devices=devices[:1], dp=-1))
    many = two_steps(build_mesh(devices=devices[:n], **axes))
    # the second loss has been through every gradient and Adam's update
    assert many[0] == pytest.approx(one[0], rel=1e-5)
    assert many[1] == pytest.approx(one[1], rel=1e-5)
    router = ("layers", "moe", "router")
    for path in (router, ("layers", "q_norm"), ("layers", "moe", "w_down")):
        a, b = one[2], many[2]
        for key in path:
            a, b = a[key], b[key]
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=0, atol=2e-5)


def test_no_capacity_over_ep_is_refused_in_words(devices):
    mesh = build_mesh(devices=devices[:2], ep=2)
    with pytest.raises(NotImplementedError, match="ROADMAP B7"):
        moe_lib.make_moe_ffn(_cfg().moe, mesh)


# -- what did not change -------------------------------------------------

#: sha256 of ``forward_with_aux``'s lowered StableHLO for the tiny dense
#: and the tiny one-hot MoE configuration, taken on PR 25's tree: the
#: new fields' defaults write the same program, instruction for
#: instruction. A PR that changes the dense or the one-hot forward on
#: purpose takes the digests anew (print ``hashlib.sha256(text)`` below).
_PR25_FORWARD = {
    "dense": "5fd1e99d4add9016dbe3f5a6eaec2c4db1a8d94c601e6ea9178ef7dafa91b880",
    "one_hot_moe":
        "53247718fea6f76d9f870dabd9b4bcfc76275c527d4cb0630bfde219acff7f91",
}


@pytest.mark.parametrize("name,fields", [
    ("dense", {}), ("one_hot_moe", {"n_experts": 4, "moe_top_k": 2})])
def test_default_fields_write_the_programs_of_pr25(name, fields,
                                                   monkeypatch):
    monkeypatch.delenv("HOROVOD_MOE_DISPATCH", raising=False)
    cfg = TransformerConfig.tiny(dtype=jnp.float32, sp_attention="local",
                                 remat=False, **fields)
    params = jax.eval_shape(
        lambda: init_transformer(cfg, jax.random.PRNGKey(0)))
    text = jax.jit(lambda p, t: forward_with_aux(p, t, cfg)).lower(
        params, jax.ShapeDtypeStruct((2, 32), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _PR25_FORWARD[name]


MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "qk_norm")


def _lower_step():
    cfg = _cfg(sp_attention="flash", remat=True, remat_policy="full")
    init, step, _ = make_train_step(
        cfg, build_mesh(devices=jax.devices()[:1], dp=-1))
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    return step.lower(state, {"tokens": jax.ShapeDtypeStruct(
        (2, 33), jnp.int32)})


def _scope_paths(lowered):
    return re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))


def _instructions(lowered):
    text = lowered.compile().as_text()
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not re.match(r"^(FileNames|FunctionNames|"
                                     r"FileLocations|StackFrames|\d+ )",
                                     line.strip()))


def test_the_moe_scopes_are_in_the_step_and_change_no_instruction(
        monkeypatch):
    with_scopes = _lower_step()
    paths = _scope_paths(with_scopes)
    for name in MOE_SCOPES:
        inside = "attn" if name == "qk_norm" else "mlp"
        assert any(re.search(rf"\b{inside}/{name}\b", p) for p in paths), name
    named = _instructions(with_scopes)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _lower_step()
    assert not any(n in p for p in _scope_paths(without) for n in MOE_SCOPES)
    assert _instructions(without) == named


# -- serving --------------------------------------------------------------

@pytest.mark.parametrize("fields,says", [
    ({"qk_norm": True}, "qk_norm"),
    ({"n_experts": 4, "d_ff": 32, "moe_capacity_factor": None},
     "without a capacity"),
])
def test_serve_programs_refuse_what_they_do_not_know(fields, says):
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False, **fields)
    with pytest.raises(NotImplementedError, match="ROADMAP B7") as e:
        decode_lib.make_serve_fns(cfg, None, block_size=8, table_width=3)
    assert says in str(e.value)


# -- the reference ---------------------------------------------------------

def test_the_two_copies_of_the_reference_are_one():
    def below_docstring(path):
        with open(path) as f:
            text = f.read()
        assert text.startswith('"""')
        return text[text.index('"""', 3) + 3:]

    ours = below_docstring(os.path.join(HERE, "reference_olmoe.py"))
    theirs = below_docstring(os.path.join(
        os.path.dirname(HERE), "benchmark", "reference_olmoe.py"))
    assert ours == theirs
    assert "horovod_tpu" not in ours
    assert 'default_matmul_precision("highest")' in ours
