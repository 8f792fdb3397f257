"""Layers whose state is not cached keys, served beside one another
(ISSUE 38): delta-rule linear attention (kda) over a recurrent state a
batch slot, latent attention (mla) over a paged pool of latents with an
absorbed decode, and group-limited sigmoid routing over a chip's share
of the experts. At a tiny size with seeded weights in float32, against
``tests/reference_ling3.py``: the plain forward of the same equations
over a whole sequence, a position at a time, no cache."""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_ling3 as ref
from reference_mla import mla_attend_absorbed
from horovod_tpu.models import (TransformerConfig, init_transformer,
                                make_train_step)
from horovod_tpu.models import moe as moe_lib
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache

BS, CHUNK = 8, 32
TYPES = ("kda", "kda", "mla")          # the first one dense, as layer 0 is


def tiny(**kw):
    base = dict(
        vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_head=16, d_ff=32, d_ff_dense=96, n_dense_layers=1, max_seq=256,
        rope_theta=1e4, norm_eps=1e-6, layer_types=TYPES, kda_conv=4,
        kda_decay_floor=-5.0, mla_kv_rank=32, mla_rope_dim=8, n_experts=16,
        moe_top_k=4, moe_capacity_factor=None, moe_scoring="sigmoid",
        moe_route_scale=2.5, moe_shared_expert=True, moe_experts_held=8,
        moe_expert_offset=4, moe_n_group=4, moe_topk_group=2,
        dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def sizes_of(cfg):
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return ref.sizes_of({"model": model})


def seeded(cfg, seed=0):
    """Seeded weights whose norm gains and selection bias are not the
    ones and zeros of an initialisation, so that each is seen."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("norm"):
            return a + 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        if name == "router_bias":
            return 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(shake, params)


@pytest.fixture(autouse=True)
def small_key_blocks(monkeypatch):
    """Key blocks of 32 positions, so that a sequence's latents are
    attended over several."""
    monkeypatch.setattr(decode_lib, "_MLA_KEY_BLOCK", 32)


def serve_logits(cfg, params, prompts, n_decode):
    """Chunked prefill of each of ``prompts`` into its slot, then
    ``n_decode`` greedy steps of ALL of them as one full batch. Returns
    for each prompt (the logits at the last position of each chunk and
    of each step, the positions they belong to, every token) and the
    caches."""
    B = len(prompts)
    width = -(-(max(map(len, prompts)) + n_decode) // BS)
    prefill, resume, decode, _ = decode_lib.mixed_programs(
        cfg, BS, width, 0, head=lambda lg: lg)
    prefill, resume, decode = map(jax.jit, (prefill, resume, decode))
    cache = init_kv_cache(cfg, B * width + 1, BS, n_slots=B)
    kc, vc = cache.k, cache.v
    tables = np.arange(1, B * width + 1, dtype=np.int32).reshape(B, width)
    rows, at, toks = ([[] for _ in prompts], [[] for _ in prompts],
                      [list(p) for p in prompts])
    for b, prompt in enumerate(prompts):
        addr = (jnp.asarray(tables[b]), jnp.int32(b + 1))
        for off in range(0, len(prompt), CHUNK):
            n = min(CHUNK, len(prompt) - off)
            padded = np.zeros(-(-n // BS) * BS, np.int32)
            padded[:n] = prompt[off:off + n]
            if off == 0 and n == len(prompt):
                kc, vc, lg = prefill(params, kc, vc, padded, jnp.int32(n),
                                     addr)
            else:
                kc, vc, lg = resume(params, kc, vc, padded, jnp.int32(off),
                                    jnp.int32(n), addr)
            rows[b].append(np.asarray(lg))
            at[b].append(off + n - 1)
        toks[b].append(int(rows[b][-1].argmax()))
    for _ in range(n_decode):
        pos = [len(t) - 1 for t in toks]
        kc, vc, lg = decode(
            params, kc, vc, jnp.asarray([t[-1] for t in toks], jnp.int32),
            jnp.asarray(pos, jnp.int32),
            (jnp.asarray(tables), jnp.arange(1, B + 1, dtype=jnp.int32)))
        for b in range(B):
            rows[b].append(np.asarray(lg[b]))
            at[b].append(pos[b])
            toks[b].append(int(lg[b].argmax()))
    return [(np.stack(r), a, t) for r, a, t in zip(rows, at, toks)], (kc, vc)


def gap(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


PROMPTS = (77, 32, 5, 64)    # chunks 32+32+13, one whole, 5 of 8, two whole


def prompts_of(cfg, lens=PROMPTS, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


@pytest.fixture(scope="module")
def served():
    """The tiny model's logits through the serve programs, once."""
    with pytest.MonkeyPatch.context() as patch:    # as small_key_blocks
        patch.setattr(decode_lib, "_MLA_KEY_BLOCK", 32)
        cfg = tiny()
        params = seeded(cfg)
        return cfg, params, *serve_logits(cfg, params, prompts_of(cfg), 12)


# (a) --------------------------------------------------------------------

@pytest.mark.parametrize("b", range(len(PROMPTS)),
                         ids=[f"prompt_{n}" for n in PROMPTS])
def test_chunks_and_decode_in_a_full_batch_match_the_reference(served, b):
    """The logits after each chunk and after each of 12 decode steps of
    a full batch, and the state the kda layers leave in the sequence's
    slot, are the reference's one pass over the whole sequence."""
    cfg, params, out, (kc, _) = served
    got, at, toks = out[b]
    want, states = ref.logits(params, np.asarray(toks[:-1]), sizes_of(cfg),
                              states=True)
    assert gap(got, np.asarray(want)[at]) < 2e-4
    assert gap(np.asarray(kc[0][:, b + 1]), np.asarray(states)) < 2e-4


def test_the_engine_serves_the_reference_s_tokens():
    """Through ``ServeEngine``: chunked prefill, slots, a full batch,
    the decode call launched ahead. Every served token is the
    reference's argmax, and the counters say what the states held."""
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_for(cfg, params)
    prompts = prompts_of(cfg)
    rids = [eng.submit(p, 10) for p in prompts]
    eng.step()
    eng.step()
    snap = eng.metrics.snapshot()
    assert snap["state_slots_in_use"] == 4
    # two kda layers: 4 heads of 16 x 16 float32 and 3 rows of 3 x 64
    assert snap["state_bytes"] == 4 * 2 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    eng.run_until_idle()
    for prompt, rid in zip(prompts, rids):
        toks = eng.result(rid).tokens
        want = np.asarray(ref.logits(params, np.asarray(prompt + toks[:-1]),
                                     sizes_of(cfg), last=10))
        assert toks == want.argmax(-1).tolist()
    snap = eng.metrics.snapshot()
    assert snap["kv_latent_positions_max"] == 77 + 10 - 1
    assert snap["state_slots_in_use"] == 0 and snap["decode_ahead_total"] > 4


def engine_for(cfg, params, **kw):
    knobs = dict(max_batch=4, block_size=BS, max_prompt=96,
                 max_new_tokens=16, prefix_caching=False,
                 prefill_chunk=CHUNK, prefill_buckets=(8, 16, 32),
                 batch_buckets=(4,))
    knobs.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**knobs))


# (b) --------------------------------------------------------------------

@pytest.mark.parametrize("wrong", [w for w in ref.WRONG
                                   if w != "state_in_bf16"])
def test_each_mechanism_miscomputed_fails_the_comparison(served, wrong):
    """The reference with one mechanism miscomputed lies further from
    the served logits than (a)'s tolerance by two orders of magnitude,
    on the long prompt and on the short ones."""
    cfg, params, out, _ = served
    got, at, toks = out[0]
    want = np.asarray(ref.logits(params, np.asarray(toks[:-1]),
                                 sizes_of(cfg), wrong=wrong))
    assert gap(got, want[at]) > 2e-2


def test_a_state_kept_in_bf16_shows_in_the_state(served):
    cfg, params, out, (kc, _) = served
    _, _, toks = out[0]
    _, states = ref.logits(params, np.asarray(toks[:-1]), sizes_of(cfg),
                           states=True, wrong="state_in_bf16")
    assert gap(np.asarray(kc[0][:, 1]), np.asarray(states)) > 2e-3


# (c) --------------------------------------------------------------------

def recurrence_inputs(T, seed=0, B=2, H=3, D=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(kk, (B, T, H, D)) for kk in ks[:3])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    # decays from none to the floor itself, 16 positions of which in a
    # row are e^-80
    g = -5.0 * jax.random.uniform(ks[3], (B, T, H, D)) ** 4
    g = g.at[:, 3:20, 0].set(-5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    state = jax.random.normal(ks[5], (B, H, D, D))
    return q, k, v, g, beta, state


def by_position(q, k, v, g, beta, state):
    out = []
    for t in range(q.shape[1]):
        o, state = decode_lib.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                       beta[:, t], state)
        out.append(o)
    return jnp.stack(out, 1), state


@pytest.mark.parametrize("T", [16, 37, 64, 150])
@pytest.mark.parametrize("block", [16, 64])
def test_the_chunked_scan_is_the_recurrence(block, T):
    args = recurrence_inputs(T)
    o, state = decode_lib.kda_scan(*args, block=block)
    want_o, want_state = by_position(*args)
    assert gap(np.asarray(o), np.asarray(want_o)) < 1e-5
    assert gap(np.asarray(state), np.asarray(want_state)) < 1e-5
    assert bool(jnp.isfinite(o).all())


def test_a_padded_chunk_leaves_the_state_at_its_length():
    """A bucket's padding, written as ``g = 0`` and ``beta = 0``, moves
    nothing: through the program, the state and the convolution's rows
    after a chunk of 13 in a bucket of 16 and of 32 are the same, and
    those of the reference after 13 positions."""
    cfg = tiny()
    params = seeded(cfg)
    prompt = prompts_of(cfg, (13,))[0]
    kept = []
    for bucket in (16, 32):
        _, resume, _, _ = decode_lib.mixed_programs(cfg, BS, 8, 0)
        cache = init_kv_cache(cfg, 9, BS, n_slots=1)
        padded = np.full(bucket, 7, np.int32)       # padding that is not 0
        padded[:13] = prompt
        kc, vc, _ = jax.jit(resume)(
            params, cache.k, cache.v, padded, jnp.int32(0), jnp.int32(13),
            (jnp.arange(1, 9, dtype=jnp.int32), jnp.int32(1)))
        kept.append((np.asarray(kc[0][:, 1]), np.asarray(vc[0][:, 1])))
    np.testing.assert_allclose(kept[0][0], kept[1][0], rtol=0, atol=1e-6)
    np.testing.assert_allclose(kept[0][1], kept[1][1], rtol=0, atol=1e-5)
    _, states = ref.logits(params, np.asarray(prompt), sizes_of(cfg),
                           states=True)
    assert gap(kept[0][0], np.asarray(states)) < 2e-4
    # the rows before the convolution of positions 10, 11, 12
    lp = params["dense_layers"][0]
    x = params["embed"][jnp.asarray(prompt[10:13])][None]
    np.testing.assert_allclose(
        kept[0][1][0], np.asarray(tf_lib.kda_rows(cfg, lp, x)[1][0]),
        atol=1e-5)


# (d) --------------------------------------------------------------------

@pytest.mark.parametrize("queries", [1, 5])
def test_absorbed_and_expanded_latent_attention_agree(queries):
    """Every query at a position that some key holds: one that sees no
    key reads zeros expanded and a mean of the keys absorbed."""
    cfg = tiny()
    lp = seeded(cfg)["layers"][1]
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, K = 3, 96
    qn = jax.random.normal(ks[0], (B, queries, 4, 16))
    qr = jax.random.normal(ks[1], (B, queries, 4, 8))
    latents = jax.random.normal(ks[2], (B, K, 40))
    pos = jnp.asarray([[40], [95], [5]]) + jnp.arange(queries)[None] - queries

    def keys_of(j):
        return (jax.lax.dynamic_slice_in_dim(latents, j * 32, 32, 1),
                j * 32 + jnp.arange(32))

    got = [attend(cfg, lp, qn, qr, keys_of, 3, pos)
           for attend in (mla_attend_absorbed, decode_lib._mla_attend)]
    assert gap(np.asarray(got[0]), np.asarray(got[1])) < 1e-5


@pytest.fixture(scope="module")
def a_prompt_s_first_chunks():
    """72 tokens of a prompt (9 pages: their end is no key block's)
    written into slot 1 by the programs as they are."""
    with pytest.MonkeyPatch.context() as patch:    # as small_key_blocks
        patch.setattr(decode_lib, "_MLA_KEY_BLOCK", 32)
        cfg = tiny()
        params = seeded(cfg)
        start, = prompts_of(cfg, (72,), seed=3)
        cache = init_kv_cache(cfg, 40, BS, n_slots=1)
        kc, vc = cache.k, cache.v
        addr = (jnp.arange(1, 17, dtype=jnp.int32), jnp.int32(1))
        resume = jax.jit(decode_lib.mixed_programs(
            cfg, BS, 16, 0, head=lambda lg: lg)[1])
        for off in range(0, 72, CHUNK):
            n = min(CHUNK, 72 - off)
            kc, vc, _ = resume(params, kc, vc,
                               np.asarray(start[off:off + n], np.int32),
                               jnp.int32(off), jnp.int32(n), addr)
        return cfg, params, kc, vc, addr


@pytest.mark.parametrize("width", [8, 16, 32])     # the engine's buckets
@pytest.mark.parametrize("how", ["local", "resumed"])
def test_a_chunk_through_the_kernel_is_the_absorbed_form(
        a_prompt_s_first_chunks, how, width):
    """A chunk of each bucket width through ``mla_chunk``, its last 3
    places padding: as the whole prompt over itself, and resumed at 72
    (a multiple of the page, not of the key block) over the pages the
    earlier chunks wrote. The logits through the Pallas forward are
    those of the same program attending in the absorbed form."""
    cfg, params, kc, vc, addr = a_prompt_s_first_chunks
    n = width - 3
    tokens = np.zeros(width, np.int32)
    tokens[:n] = prompts_of(cfg, (n,), seed=width)[0]

    def logits():
        prefill, resume = map(jax.jit, decode_lib.mixed_programs(
            cfg, BS, 16, 0, head=lambda lg: lg)[:2])
        if how == "local":
            return prefill(params, kc, vc, tokens, jnp.int32(n), addr)[2]
        return resume(params, kc, vc, tokens, jnp.int32(72), jnp.int32(n),
                      addr)[2]

    got = np.asarray(logits())
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(decode_lib, "_mla_attend", mla_attend_absorbed)
        want = np.asarray(logits())
    assert got.shape == want.shape and gap(got, want) < 1e-5


# (e) --------------------------------------------------------------------

def test_the_shares_add_up():
    """Over the four offsets the routed sums, with the shared expert
    counted once, are the uncut layer's."""
    cfg = tiny(moe_experts_held=None, moe_expert_offset=0)
    whole = seeded(cfg)["layers"][0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    want, _ = moe_lib.moe_ffn_dropless(x, whole, cfg.moe)
    shared = moe_lib._shared_expert(x.reshape(-1, cfg.d_model), whole
                                    ).reshape(x.shape)
    total = -3 * shared
    for offset in range(0, 16, 4):
        share = dataclasses.replace(cfg.moe, experts_held=4,
                                    expert_offset=offset)
        lp = {**whole, **{w: whole[w][offset:offset + 4]
                          for w in ("w_gate", "w_up", "w_down")}}
        total = total + moe_lib.moe_ffn_dropless(x, lp, share)[0]
    assert gap(np.asarray(total), np.asarray(want)) < 1e-5


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_group_limited_selection_is_the_brute_force_one(scoring):
    """Every subset of 2 of the 4 groups, scored by the sum of its
    groups' two largest selection scores; the 4 largest scores inside
    the best subset."""
    cfg = moe_lib.MoEConfig(n_experts=16, top_k=4, capacity_factor=None,
                            scoring=scoring, n_group=4, topk_group=2)
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((64, 16)).astype(np.float32)
    bias = rng.standard_normal(16).astype(np.float32) * 0.3
    _, gates, experts = moe_lib._top_k_gates(jnp.asarray(logits), cfg,
                                             jnp.asarray(bias))
    probs = (1 / (1 + np.exp(-logits)) if scoring == "sigmoid"
             else np.asarray(jax.nn.softmax(logits, -1)))
    select = probs + bias if scoring == "sigmoid" else probs
    for t in range(64):
        groups = select[t].reshape(4, 4)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        best = max(itertools.combinations(range(4), 2),
                   key=lambda pair: score[list(pair)].sum())
        inside = [4 * g + e for g in best for e in range(4)]
        chosen = sorted(inside, key=lambda e: -select[t, e])[:4]
        assert sorted(np.asarray(experts[t]).tolist()) == sorted(chosen)
        w = probs[t, np.asarray(experts[t])]
        np.testing.assert_allclose(np.asarray(gates[t]), w / w.sum(),
                                   rtol=1e-5)


def test_no_groups_is_the_choice_it_was():
    cfg = moe_lib.MoEConfig(n_experts=16, top_k=4, capacity_factor=None,
                            scoring="sigmoid")
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    _, _, experts = moe_lib._top_k_gates(logits, cfg, jnp.zeros(16))
    np.testing.assert_array_equal(experts, jax.lax.top_k(logits, 4)[1])
    with pytest.raises(ValueError, match="groups"):
        moe_lib.MoEConfig(n_experts=16, top_k=4, n_group=3)
    with pytest.raises(ValueError, match="groups"):
        moe_lib.MoEConfig(n_experts=16, top_k=8, n_group=4, topk_group=1)


# (f) --------------------------------------------------------------------

def test_a_slot_s_second_tenant_is_a_fresh_engine_s_first():
    """One batch slot, three requests one after another: each starts
    from a zero state and an empty convolution whatever the slot held,
    as each does alone in a fresh engine."""
    cfg = tiny()
    params = seeded(cfg)
    prompts = prompts_of(cfg, (40, 9, 33), seed=4)
    eng = engine_for(cfg, params, max_batch=1, batch_buckets=(1,))
    rids = [eng.submit(p, 6) for p in prompts]
    eng.run_until_idle()
    assert len({eng.result(r).slot for r in rids}) == 1
    for prompt, rid in zip(prompts, rids):
        alone = engine_for(cfg, params, max_batch=1, batch_buckets=(1,))
        assert alone.generate([prompt], 6)[0] == eng.result(rid).tokens


def _sync_step(eng):
    eng.step()
    eng._drain("idle")


def _serve_staged(eng, arrivals, step):
    rids, i = [], 0
    while eng.pending or i <= max(arrivals):
        for prompt, max_new in arrivals.get(i, ()):
            rids.append(eng.submit(prompt, max_new))
        step(eng)
        i += 1
        assert i < 500
    return [eng.result(r).tokens for r in rids]


@pytest.mark.parametrize("ends_by", ["max_new", "eos"])
def test_launched_ahead_the_tokens_are_the_synchronous_engine_s(ends_by):
    """Joins, retirements and a queue that waits for a slot, with the
    decode call launched before its predecessor is read: a step's state
    update follows its predecessor's on the device, and the follower of
    a sequence that ended (by ``eos_id``, its row still in the call
    launched ahead) writes a slot whose next tenant starts from zero."""
    cfg = tiny()
    params = seeded(cfg)
    rng = np.random.default_rng(5)
    reqs = [(rng.integers(1, cfg.vocab_size, int(rng.integers(5, 70))
                          ).tolist(), int(n))
            for n in (9, 3, 16, 5, 12, 2, 7, 11)]
    arrivals = {0: reqs[:3], 2: reqs[3:4], 7: reqs[4:7], 15: reqs[7:]}
    kw = {}
    if ends_by == "eos":
        free = _serve_staged(engine_for(cfg, params), arrivals,
                             lambda e: e.step())
        kw["eos_id"] = free[2][7]          # the third request ends early
    ahead, sync = engine_for(cfg, params, **kw), engine_for(cfg, params, **kw)
    got = _serve_staged(ahead, arrivals, lambda e: e.step())
    assert got == _serve_staged(sync, arrivals, _sync_step)
    if ends_by == "eos":
        assert len(got[2]) <= 8 and got[2][-1] == kw["eos_id"]
    a, s = ahead.metrics.snapshot(), sync.metrics.snapshot()
    assert a["decode_ahead_total"] > 10 and s["decode_ahead_total"] == 0
    for (prompt, n), tokens in zip(reqs[:3], got):
        assert engine_for(cfg, params, **kw).generate([prompt], n)[0] \
            == tokens


# (g) --------------------------------------------------------------------

def test_what_is_not_built_is_refused_by_name(devices):
    cfg = tiny()
    params = seeded(cfg)
    with pytest.raises(NotImplementedError, match="prefix_caching.*B14"):
        engine_for(cfg, params, prefix_caching=True)
    from horovod_tpu.serve.speculative import DraftConfig
    with pytest.raises(NotImplementedError, match="speculative"):
        engine_for(cfg, params, spec_k=2,
                   draft=DraftConfig(model_cfg=TransformerConfig.tiny()))
    eng = engine_for(cfg, params)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.submit([1, 2, 3], 2, prefill_only=True)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.inject_begin({"block_size": BS})
    rid = eng.submit([1, 2, 3], 12)
    eng.step()
    with pytest.raises(NotImplementedError, match="migrate.py"):
        eng.export_running(rid)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng._inject_fn()
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng._verify_fn()
    for axes in (dict(tp=2), dict(ep=2)):
        with pytest.raises(NotImplementedError, match="latent pool"):
            decode_lib.make_serve_fns(
                cfg, build_mesh(devices=devices[:2], **axes), block_size=BS,
                table_width=4)
    with pytest.raises(NotImplementedError, match="kda, mla or mamba.*B14"):
        make_train_step(cfg, build_mesh(devices=devices[:1], dp=1))
    with pytest.raises(NotImplementedError, match="kda, mla or mamba"):
        tf_lib.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


def test_a_configuration_names_its_kinds():
    with pytest.raises(ValueError, match="'kda' | 'mla'"):
        tiny(layer_types=("kda", "kda", "retention"))
    with pytest.raises(ValueError, match="mla_kv_rank"):
        tiny(mla_kv_rank=0)
    with pytest.raises(ValueError, match="n_kv_heads = n_heads"):
        tiny(n_kv_heads=2)
    cfg = tiny()
    assert cfg.stateful and cfg.mixed
    assert [cfg.n_layers_of(k) for k in ("kda", "mla", "full")] == [2, 1, 0]
    cache = init_kv_cache(cfg, 5, BS, n_slots=2)
    assert cache.kinds == ("kda", "mla") and cache.v[1] is None
    assert cache.of("kda")[0].shape == (2, 3, 4, 16, 16)
    assert cache.of("kda")[0].dtype == jnp.float32
    assert cache.of("mla")[0].shape == (1, 5, BS, 128)


# (h) --------------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_one_text():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def body(path):
        text = open(os.path.join(root, path)).read()
        return text[text.index('"""', 3):]

    assert body("tests/reference_ling3.py") == body(
        "benchmark/reference_ling3.py")


def test_no_pair_on_a_held_expert_is_dropped_under_a_skewed_router():
    """A router that sends most tokens to two held experts and many to
    experts that are not held: ``moe_share_report`` counts every held
    pair as run, on a chunk-sized and on a decode-sized batch."""
    cfg = tiny()
    params = seeded(cfg)
    for lp in params["layers"]:
        skew = jnp.zeros(16).at[jnp.asarray([5, 6])].set(3.0).at[0].set(2.0)
        lp["moe"]["router_bias"] = skew
    rng = np.random.default_rng(2)
    for shape in ((1, 32), (4, 1)):
        report = decode_lib.moe_share_report(
            params, rng.integers(1, cfg.vocab_size, shape), cfg, BS)
        assert report["moe_dispatch_dropped_token_frac"] == 0
        assert report["moe_expert_load_max_over_mean"] > 1.5
        assert 0 < report["moe_local_pair_share"] < 1
