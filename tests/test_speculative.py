"""Speculative decoding tests: the greedy acceptance rule, bitwise
stream parity with plain decode (all-accept, adversarial-reject, and
randomized mixes — the rejected-position KV rollback property), the
zero-contribution draft/target bench rig, composition with prefix
caching / chunked prefill / mid-decode migration, and the spec
metrics surface.

Geometry note: every engine here shares test_serve.py's ``_PFX_KW``
shape, so the target side reuses the serve tier's ONE compiled fn set
via the ``make_serve_fns`` memo; the only new compiles this module
pays are the ``verify`` program (one per spec_k used — k is a jit
chunk dimension, so the module pins k=3 everywhere) and the 1-layer
draft of the zero-contribution rig.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve.speculative import (
    DraftConfig, accept_greedy, make_draft_target_params,
)

# Same geometry as test_serve/test_router: one compiled fn set for the
# whole serve test tier.
_KW = dict(max_batch=4, block_size=4, max_prompt=24, max_new_tokens=6,
           batch_buckets=(4,), prefill_buckets=(4, 8, 16, 24))

#: One spec_k for the whole module: the verify chunk width is a jit
#: dimension, so every test sharing k shares one compiled program.
_K = 3


@pytest.fixture(scope="module")
def served_model():
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk_engine(served_model, draft_seed=None, spec_k=_K, **kw):
    """Engine over the shared tiny model; ``draft_seed`` not None
    turns speculation on with a draft of the SAME config from that
    seed (seed 0 = identical params = all-accept; any other seed =
    a disagreeing draft that forces rejections)."""
    cfg, params = served_model
    opts = dict(_KW)
    opts.update(kw)
    if draft_seed is not None:
        opts.update(draft=DraftConfig(cfg, seed=draft_seed),
                    spec_k=spec_k)
    return ServeEngine(cfg, params, ServeConfig(**opts))


def _prompts(n=6, rng_seed=21, prefix_len=12):
    rng = np.random.RandomState(rng_seed)
    prefix = rng.randint(1, 256, size=prefix_len).tolist()
    return [prefix + rng.randint(1, 256,
                                 size=int(rng.randint(2, 6))).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# The acceptance rule (pure host function)
# ---------------------------------------------------------------------------

def test_accept_greedy_all_match_no_bonus():
    # All k match: exactly the k draft tokens, no (k+1)-th bonus token
    # (forgoing it keeps the draft cursor in lockstep — see module doc).
    n, emitted = accept_greedy([5, 6, 7], [5, 6, 7])
    assert (n, emitted) == (3, [5, 6, 7])


def test_accept_greedy_first_mismatch_emits_correction():
    n, emitted = accept_greedy([5, 6, 7], [5, 9, 7])
    assert (n, emitted) == (1, [5, 9])
    # Immediate mismatch still makes progress: one correction token —
    # plain decode's per-step progress, the worst case.
    n, emitted = accept_greedy([5, 6, 7], [1, 2, 3])
    assert (n, emitted) == (0, [1])


def test_accept_greedy_k1_is_plain_decode():
    # k=1: the emitted token is the target's own argmax either way.
    assert accept_greedy([5], [5]) == (1, [5])
    assert accept_greedy([5], [9]) == (0, [9])


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------

def test_spec_config_validation(served_model):
    cfg, params = served_model
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(cfg, params, ServeConfig(
            **_KW, draft=DraftConfig(cfg)))            # draft, no k
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(cfg, params, ServeConfig(**_KW, spec_k=4))  # k, no
        #                                                        draft
    with pytest.raises(ValueError, match="vocab"):
        ServeEngine(cfg, params, ServeConfig(
            **_KW, spec_k=2,
            draft=DraftConfig(TransformerConfig.tiny(
                vocab_size=128, dtype=jnp.float32, remat=False))))


def test_make_draft_target_params_validation(served_model):
    cfg, _params = served_model
    with pytest.raises(ValueError, match="exceed"):
        make_draft_target_params(cfg, n_layers=cfg.n_layers)


# ---------------------------------------------------------------------------
# Bitwise-greedy parity (the acceptance property)
# ---------------------------------------------------------------------------

def test_spec_all_accept_parity_and_counters(served_model):
    """Draft == target (same config, same seed): every proposal is
    accepted, the stream is bitwise plain decode's, and the spec
    counters show accept rate 1.0."""
    prompts = _prompts()
    ref = _mk_engine(served_model).generate(prompts, 5)
    eng = _mk_engine(served_model, draft_seed=0)
    assert eng.generate(prompts, 5) == ref
    m = eng.metrics
    assert m.spec_rounds > 0
    assert m.spec_proposed > 0
    assert m.spec_accepted == m.spec_proposed
    snap = m.snapshot()
    assert snap["spec_accept_rate"] == 1.0
    assert snap["spec_proposed_total"] == m.spec_proposed
    assert snap["tokens_generated"] == sum(len(t) for t in ref)
    # Fewer verify rounds than plain decode steps — the point.
    plain = _mk_engine(served_model)
    plain.generate(prompts, 5)
    assert m.spec_rounds < plain.metrics.decode_steps
    assert eng.allocator.n_used == 0
    assert eng._spec.allocator.n_used == 0   # draft pool drained too


def test_spec_rejecting_draft_parity(served_model):
    """A disagreeing draft (different init seed) forces rejections at
    every accept length; the emitted stream must STILL be bitwise
    plain decode's — the rejected-position KV rollback in action."""
    prompts = _prompts()
    ref = _mk_engine(served_model).generate(prompts, 5)
    eng = _mk_engine(served_model, draft_seed=1)
    assert eng.generate(prompts, 5) == ref
    m = eng.metrics
    # A random disagreeing draft accepts (almost) nothing — the run
    # must have exercised rejection, or this test is vacuous.
    assert m.spec_accepted < m.spec_proposed
    assert m.snapshot()["spec_accept_rate"] < 1.0
    assert eng.allocator.n_used == 0


def test_spec_rollback_randomized_property(served_model):
    """Randomized rollback property: across random traces, draft
    agreement mixes (all-accept and adversarial-reject drafts), and
    random max_new, speculative streams are bitwise plain decode's,
    the acceptance counters stay sane (0 <= accepted <= proposed),
    and both pools pass full allocator-integrity checks after every
    trace. This is the pinned form of 'rejected-position KV rollback
    corrupts nothing'."""
    plain = _mk_engine(served_model)
    engines = {0: _mk_engine(served_model, draft_seed=0),
               1: _mk_engine(served_model, draft_seed=1)}
    for seed in (3, 4, 5):
        rng = np.random.RandomState(seed)
        prompts = [rng.randint(1, 256,
                               size=int(rng.randint(2, 20))).tolist()
                   for _ in range(int(rng.randint(2, 6)))]
        max_new = int(rng.randint(1, 7))
        ref = plain.generate(prompts, max_new)
        for dseed, eng in engines.items():
            assert eng.generate(prompts, max_new) == ref, (seed, dseed)
            m = eng.metrics
            assert 0 <= m.spec_accepted <= m.spec_proposed
            eng.allocator.verify_integrity()
            eng._spec.allocator.verify_integrity()
    # The disagreeing arm rejected, the agreeing arm did not.
    assert engines[1].metrics.spec_accepted \
        < engines[1].metrics.spec_proposed
    assert engines[0].metrics.spec_accepted \
        == engines[0].metrics.spec_proposed


def test_spec_eos_stops_exactly_like_plain(served_model):
    """An eos token inside an accepted chunk truncates the stream at
    the FIRST eos, exactly where plain decode stops."""
    probe = _mk_engine(served_model).generate([[1, 2, 3]], 6)[0]
    eos = probe[2]
    ref = _mk_engine(served_model, eos_id=eos).generate([[1, 2, 3]], 6)
    eng = _mk_engine(served_model, draft_seed=0, eos_id=eos)
    out = eng.generate([[1, 2, 3]], 6)
    assert out == ref
    assert out[0][-1] == eos and len(out[0]) < len(probe)
    assert eng.allocator.n_used == 0


def test_spec_composes_with_cache_and_chunked_prefill(served_model):
    """Speculation swaps only the decode iteration: prefix caching and
    chunked prefill underneath it leave the stream bitwise plain
    decode's."""
    prompts = _prompts()
    ref = _mk_engine(served_model, prefix_caching=False).generate(
        prompts, 5)
    spec_cached = _mk_engine(served_model, draft_seed=0)
    assert spec_cached.generate(prompts, 5) == ref
    spec_chunked = _mk_engine(served_model, draft_seed=1,
                              prefill_chunk=4)
    assert spec_chunked.generate(prompts, 5) == ref


def test_spec_migration_mid_decode_parity(served_model):
    """export_running/inject_prefilled on speculative engines: the
    target pages move bitwise; the receiving engine's draft catches up
    from the migrated stream (prompt + generated tokens) and the
    remaining tokens are exactly the donor's would-have-beens."""
    prompts = _prompts(3)
    ref = _mk_engine(served_model).generate(prompts, 5)
    a = _mk_engine(served_model, draft_seed=1)
    b = _mk_engine(served_model, draft_seed=1)
    rids = [a.submit(p, 5) for p in prompts]
    a.step()    # prefill + first spec round
    a.step()    # genuinely mid-decode, several tokens in
    movable = a.running_exportable()
    assert movable, "nothing mid-decode — migration would be vacuous"
    moved = {rid: b.inject_prefilled(a.export_running(rid))
             for rid in movable}
    a.run_until_idle()   # retire any already-finished stragglers
    # The donor released BOTH pools' reservations for the movers.
    assert a.allocator.n_used == 0
    assert a._spec.allocator.n_used == 0
    b.run_until_idle()
    got = [(b.result(moved[r]) if r in moved else a.result(r)).tokens
           for r in rids]
    assert got == ref
    assert b._spec.allocator.n_used == 0


def test_spec_draft_pool_covers_prefix_shared_batches(served_model):
    """Regression (review): the target pool admits same-prefix batches
    whose shared blocks are refcounted ONCE, but the draft (no content
    index) pays every sequence's full private reservation — the draft
    pool must be sized for that worst case, or a prefix-heavy batch
    the target happily admitted blows OutOfBlocks out of the spec
    round. Tight target pool + fully-shared prefixes, full batch."""
    prompts = _prompts(4, prefix_len=16)
    # Target pool just big enough for the shared-prefix batch: 4 seqs
    # x (private tail + max_new) + one shared 4-block prefix.
    eng = _mk_engine(served_model, draft_seed=1, n_blocks=24)
    ref = _mk_engine(served_model, n_blocks=24).generate(prompts, 5)
    assert eng.generate(prompts, 5) == ref
    assert eng._spec.allocator.n_used == 0
    assert eng._spec.allocator.n_blocks > eng.allocator.n_blocks


def test_zero_contribution_pair_all_accepts(served_model):
    """The bench rig: a deeper target whose extra layers have zeroed
    residual out-projections computes the draft's exact logits, so a
    DraftConfig(draft_cfg, seed) engine accepts every proposal while
    paying full target-depth FLOPs per verify — accept rate 1.0 is
    the pinned property the speculative benchmark stands on."""
    draft_cfg = TransformerConfig.tiny(n_layers=1, dtype=jnp.float32,
                                       remat=False)
    target_cfg, target_params = make_draft_target_params(
        draft_cfg, n_layers=2, seed=0)
    prompts = _prompts(3)
    sc = ServeConfig(**_KW)
    ref = ServeEngine(target_cfg, target_params, sc).generate(prompts, 4)
    eng = ServeEngine(target_cfg, target_params, ServeConfig(
        **_KW, draft=DraftConfig(draft_cfg, seed=0), spec_k=_K))
    assert eng.generate(prompts, 4) == ref
    m = eng.metrics
    assert m.spec_proposed > 0
    assert m.spec_accepted == m.spec_proposed


@pytest.mark.slow  # tp-mesh compiles (~8s class, like the plain tp
# decode variant): the single-device bitwise parity above pins the
# verify/draft math tier-1, and the tp plumbing is pinned tier-1 by
# test_models — the sharded spec variant rides the slow tier with the
# other mesh-compile-heavy variants.
def test_spec_tp_sharded_parity(served_model, devices):
    """Acceptance: greedy speculative decode under the tp mesh
    (tp-sharded target AND draft pools, in-jit psums in both models'
    programs) emits bitwise the single-device plain streams."""
    from horovod_tpu.parallel import build_mesh

    cfg, _params = served_model
    prompts = _prompts(3)
    ref = _mk_engine(served_model).generate(prompts, 4)
    mesh = build_mesh(dp=4, tp=2)
    params_sh = init_transformer(cfg, jax.random.PRNGKey(0), mesh)
    eng = ServeEngine(cfg, params_sh, ServeConfig(
        **_KW, draft=DraftConfig(cfg, seed=1), spec_k=_K), mesh=mesh)
    assert eng.generate(prompts, 4) == ref


# ---------------------------------------------------------------------------
# Metrics surface
# ---------------------------------------------------------------------------

def test_spec_metrics_snapshot_and_exposition(served_model):
    import re

    from horovod_tpu.metrics import metrics_prometheus

    eng = _mk_engine(served_model, draft_seed=0)
    eng.generate(_prompts(2), 4)
    snap = eng.metrics.snapshot()
    assert snap["spec_rounds"] > 0
    assert snap["spec_proposed_total"] == snap["spec_accepted_total"] > 0
    assert snap["spec_accept_rate"] == 1.0
    assert snap["p99_spec_draft_ms"] >= snap["p50_spec_draft_ms"] > 0
    assert snap["p99_spec_verify_ms"] >= snap["p50_spec_verify_ms"] > 0
    txt = metrics_prometheus()
    inst = re.escape(eng.metrics.instance)
    for fam in ("serve_spec_proposed_total", "serve_spec_accepted_total",
                "serve_spec_accept_rate"):
        assert re.search(r'^%s\{instance="%s"\} ' % (fam, inst), txt,
                         re.M), fam
    # Draft/verify spans ride the chrome trace next to decode's.
    names = {e["name"] for e in eng.metrics._events}
    assert {"serve:spec_draft", "serve:spec_verify"} <= names
    # ... with the round's tallies, which are known only after it.
    by = {n: [e["args"] for e in eng.metrics._events if e["name"] == n]
          for n in names}
    assert sum(a["proposed"] for a in by["serve:spec_draft"]) == \
        snap["spec_proposed_total"]
    assert sum(a["accepted"] for a in by["serve:spec_verify"]) == \
        snap["spec_accepted_total"]
    assert all(a["emitted"] >= 1 for a in by["serve:spec_verify"])
    # A plain engine's snapshot carries the keys too (zeros), so fleet
    # rollups can sum mixed fleets without key checks.
    plain = _mk_engine(served_model)
    plain.generate(_prompts(1), 2)
    psnap = plain.metrics.snapshot()
    assert psnap["spec_rounds"] == 0
    assert psnap["spec_accept_rate"] == 0.0
