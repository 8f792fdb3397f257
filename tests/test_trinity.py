"""A stack of layers of several kinds, served over two kinds of cache
(ISSUE 32): a leading dense layer before sparse ones, sigmoid routing
over all experts with one chip's share of them and a shared expert,
gated window and full attention. At a tiny size with seeded weights,
against ``tests/reference_trinity.py``: the plain float32 forward of
the same equations over a whole sequence, no cache, no kernel."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_trinity as ref
from horovod_tpu.models import (TransformerConfig, init_transformer,
                                make_train_step)
from horovod_tpu.models import moe as moe_lib
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import init_kv_cache, ring_width

BS, CHUNK, WINDOW = 8, 16, 16
RING = ring_width(WINDOW, CHUNK, BS)                     # 40 positions
TYPES = ("sliding", "sliding", "sliding", "sliding", "full")


def tiny(**kw):
    base = dict(
        vocab_size=128, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        d_head=32, d_ff=32, d_ff_dense=96, n_dense_layers=1, max_seq=128,
        rope_theta=1e4, layer_types=TYPES, attn_window=WINDOW,
        qk_norm_per_head=True, attn_gate=True, sandwich_norm=True,
        embed_scale=True, n_experts=16, moe_top_k=2,
        moe_capacity_factor=None, moe_scoring="sigmoid",
        moe_route_scale=2.448, moe_shared_expert=True, moe_experts_held=4,
        moe_expert_offset=8, dtype=jnp.float32, remat=False)
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


def programs(cfg, width):
    """The serve programs returning float32 logits, jitted."""
    prefill, resume, decode, _ = decode_lib.mixed_programs(
        cfg, BS, width, RING, head=lambda lg: lg)
    return jax.jit(prefill), jax.jit(resume), jax.jit(decode)


def serve_logits(cfg, params, prompt, n_decode, slot=1):
    """Chunked prefill of ``prompt`` then ``n_decode`` greedy steps,
    through both caches, as the engine drives them. Returns (the
    logits at the last position of each chunk and of each step, the
    positions they belong to, every token)."""
    width = -(-(len(prompt) + n_decode) // BS)
    prefill, resume, decode = programs(cfg, width)
    cache = init_kv_cache(cfg, width + 1, BS, n_slots=2, ring=RING)
    kc, vc = cache.k, cache.v
    table = jnp.arange(1, width + 1, dtype=jnp.int32)
    addr = (table, jnp.int32(slot))
    rows, at, toks = [], [], list(prompt)
    for off in range(0, len(prompt), CHUNK):
        n = min(CHUNK, len(prompt) - off)
        padded = np.zeros(-(-n // BS) * BS, np.int32)
        padded[:n] = prompt[off:off + n]
        if off == 0 and n == len(prompt):
            kc, vc, lg = prefill(params, kc, vc, padded, jnp.int32(n), addr)
        else:
            kc, vc, lg = resume(params, kc, vc, padded, jnp.int32(off),
                                jnp.int32(n), addr)
        rows.append(np.asarray(lg))
        at.append(off + n - 1)
    toks.append(int(rows[-1].argmax()))
    for _ in range(n_decode):
        pos = len(toks) - 1
        # batch of two: row 1 is padding (null table, null slot)
        kc, vc, lg = decode(
            params, kc, vc, jnp.asarray([toks[-1], 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32),
            (jnp.stack([table, jnp.zeros_like(table)]),
             jnp.asarray([slot, 0], jnp.int32)))
        rows.append(np.asarray(lg[0]))
        at.append(pos)
        toks.append(int(lg[0].argmax()))
    return np.stack(rows), at, toks, (kc, vc)


def gap(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


# (a) --------------------------------------------------------------------

@pytest.mark.parametrize("plen", [45, 48], ids=["off_chunk", "on_chunk"])
def test_prefill_and_decode_through_two_caches_match_the_reference(plen):
    """45 = two chunks and 13 tokens, 48 = three whole chunks; 24 decode
    steps take the sequence to 72 positions, 4.5 windows and 1.8 rings:
    the ring wraps during prefill and again during decode."""
    cfg = tiny()
    params = seeded(cfg)
    prompt = np.random.default_rng(plen).integers(0, cfg.vocab_size, plen)
    got, at, toks, _ = serve_logits(cfg, params, prompt.tolist(), 24)
    assert len(toks) > 2.5 * WINDOW and len(toks) > RING
    want = np.asarray(ref.logits(params, np.asarray(toks[:-1]),
                                 sizes_of(cfg)))[at]
    assert gap(got, want) < 2e-4, gap(got, want)


def test_a_short_prompt_takes_the_monolithic_prefill():
    cfg = tiny()
    params = seeded(cfg)
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, 11)
    got, at, toks, _ = serve_logits(cfg, params, prompt.tolist(), 6)
    want = np.asarray(ref.logits(params, np.asarray(toks[:-1]),
                                 sizes_of(cfg)))[at]
    assert gap(got, want) < 2e-4


# (b) --------------------------------------------------------------------

def test_the_shares_add_up():
    """The routed parts that all shares give, plus the shared expert
    once, are the uncut layer: what the deployment's combine sums."""
    cfg = tiny(moe_experts_held=None, moe_expert_offset=0)
    lp = seeded(cfg)["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    want, _ = ref.moe(u.reshape(-1, cfg.d_model), lp,
                      {**sizes_of(cfg), "experts_held": 16})
    shared = moe_lib._shared_expert(u.reshape(-1, cfg.d_model), lp)
    total, held, n_local = shared, 4, 0
    for offset in range(0, cfg.n_experts, held):
        share = dataclasses.replace(cfg.moe, experts_held=held,
                                    expert_offset=offset)
        mine = {k: (v[offset:offset + held]
                    if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in lp.items()}
        y, _ = moe_lib.moe_ffn_dropless(u, mine, share)
        total = total + (y.reshape(-1, cfg.d_model) - shared)
        n_local += int(moe_lib.routing_counts(
            u, mine["router"], share, mine["router_bias"])[0].sum())
    assert n_local == 2 * 24 * cfg.moe_top_k      # every pair, on one chip
    assert gap(np.asarray(total), np.asarray(want)) < 1e-5
    # and uncut, the program's layer is the reference's
    y, _ = moe_lib.moe_ffn_dropless(u, lp, cfg.moe)
    assert gap(np.asarray(y.reshape(-1, cfg.d_model)), np.asarray(want)) < 1e-5


# (c) --------------------------------------------------------------------

@pytest.mark.parametrize("left_out", [
    "gate", "sandwich_norms", "window_rotation", "full_layer_unrotated",
    "window_mask", "embed_scale", "qk_norm"])
def test_each_mechanism_left_out_fails_the_tolerance(left_out):
    """The program with one mechanism left out no longer meets the
    reference (the seeded gains are not ones, so a norm left out or
    applied without its gain shows)."""
    cfg = tiny()
    params = seeded(cfg)
    without = {
        "gate": dict(attn_gate=False),
        "sandwich_norms": dict(sandwich_norm=False),
        # every layer full: no rotation (and no window) anywhere
        "window_rotation": dict(layer_types=("full",) * 5),
        # every layer sliding: the full layer rotates too
        "full_layer_unrotated": dict(layer_types=("sliding",) * 5,
                                     attn_window=1000),
        "window_mask": dict(attn_window=1000),
        "embed_scale": dict(embed_scale=False),
        "qk_norm": dict(qk_norm_per_head=False),
    }[left_out]
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, 40)
    got, at, toks, _ = serve_logits(tiny(**without), params,
                                    prompt.tolist(), 4)
    want = np.asarray(ref.logits(params, np.asarray(toks[:-1]),
                                 sizes_of(cfg)))[at]
    assert gap(got, want) > 1e-2, (left_out, gap(got, want))


def test_the_reference_s_own_switches_show():
    """What ``benchmark/tools/trinity_tolerance.py`` switches off."""
    cfg = tiny()
    params, sizes = seeded(cfg), sizes_of(tiny())
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, 48)
    want = np.asarray(ref.logits(params, toks, sizes, last=8))
    for kw in (dict(window=False), dict(gate=False),
               dict(store=jnp.float8_e4m3fn)):
        got = np.asarray(ref.logits(params, toks, sizes, last=8, **kw))
        assert gap(got, want) > 1e-2, kw


# (d) --------------------------------------------------------------------

def test_a_selection_bias_changes_the_choice_and_not_the_weights():
    mcfg = tiny().moe
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    zero = jnp.zeros(16)
    bias = zero.at[3].set(10.0)
    s, g0, e0 = moe_lib._top_k_gates(logits, mcfg, zero)
    _, g1, e1 = moe_lib._top_k_gates(logits, mcfg, bias)
    assert bool((e1 == 3).any(-1).all()) and not bool((e0 == 3).any(-1).all())
    # the weights are the chosen scores, normalised and scaled: the
    # bias is in none of them
    chosen = jnp.take_along_axis(s, e1, -1)
    np.testing.assert_allclose(
        np.asarray(g1),
        np.asarray(chosen / chosen.sum(-1, keepdims=True) * 2.448), rtol=1e-6)
    assert float(np.abs(np.asarray(g1.sum(-1)) - 2.448).max()) < 1e-5
    rc, rw = ref._route(logits, jnp.eye(16), bias, top_k=2, route_scale=2.448)
    np.testing.assert_array_equal(np.sort(np.asarray(rc)),
                                  np.sort(np.asarray(e1)))
    np.testing.assert_allclose(np.sort(np.asarray(rw)),
                               np.sort(np.asarray(g1)), rtol=1e-5)


# (e) --------------------------------------------------------------------

def test_no_pair_on_a_held_expert_is_dropped_under_a_skewed_router():
    """Every token chooses held expert 9 first: 48 pairs on one expert
    of four held, three times an even share of ALL pairs, and each of
    them is computed."""
    cfg = tiny()
    lp = dict(seeded(cfg)["layers"][0]["moe"])
    lp["router_bias"] = lp["router_bias"].at[9].set(50.0)
    u = jax.random.normal(jax.random.PRNGKey(6), (3, 16, cfg.d_model))
    counts, dropped = moe_lib.routing_counts(u, lp["router"], cfg.moe,
                                             lp["router_bias"])
    assert int(counts[1]) == 48 and float(dropped) == 0
    want, chosen = ref.moe(u.reshape(-1, cfg.d_model), lp, sizes_of(cfg))
    assert bool((np.asarray(chosen) == 9).any(-1).all())
    y, _ = moe_lib.moe_ffn_dropless(u, lp, cfg.moe)
    assert gap(np.asarray(y.reshape(-1, cfg.d_model)), np.asarray(want)) < 1e-5


def test_the_dropped_count_reads_a_dispatch_that_loses_pairs(monkeypatch):
    """``moe_dispatch_dropped_token_frac`` is read off the dispatch's
    own sort and group sizes: with a cap of 8 rows a group, as a
    capacity would put, the 40 pairs of expert 9's 48 that fall behind
    it are counted, and the report is no longer 0."""
    cfg = tiny()
    params = seeded(cfg)
    lp = dict(params["layers"][0]["moe"])
    lp["router_bias"] = lp["router_bias"].at[9].set(50.0)
    u = jax.random.normal(jax.random.PRNGKey(6), (3, 16, cfg.d_model))
    whole = moe_lib._sorted_by_expert

    def capped(experts, n):
        order, sizes = whole(experts, n)
        return order, jnp.minimum(sizes, 8)

    monkeypatch.setattr(moe_lib, "_sorted_by_expert", capped)
    counts, dropped = moe_lib.routing_counts(u, lp["router"], cfg.moe,
                                             lp["router_bias"])
    assert int(counts[1]) == 48           # the claims do not use the sort
    assert float(dropped) >= 40
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16))
    skewed = {**params, "layers": [
        {**l, "moe": {**l["moe"], "router_bias": lp["router_bias"]}}
        for l in params["layers"]]}
    assert decode_lib.moe_share_report(skewed, toks, cfg, BS)[
        "moe_dispatch_dropped_token_frac"] > 0.2


# (f) --------------------------------------------------------------------

def engine_for(cfg, params, **kw):
    knobs = dict(max_batch=2, block_size=BS, prefill_chunk=CHUNK,
                 prefill_buckets=(8, 16), batch_buckets=(2,),
                 prefix_caching=False, max_prompt=64, max_new_tokens=48)
    knobs.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**knobs))


def test_the_window_cache_keeps_its_bound_and_a_retired_slot_is_clean():
    """A sequence grows to 106 positions, over six windows: a window
    layer never holds more than its ring, and the full layers alone
    draw blocks. The next request takes the retired sequence's slot,
    whose ring still holds the old keys, and is served as on a fresh
    engine."""
    cfg = tiny()
    params = seeded(cfg)
    rng = np.random.default_rng(9)
    long_one = rng.integers(0, cfg.vocab_size, 60).tolist()
    other = rng.integers(0, cfg.vocab_size, 21).tolist()
    engine = engine_for(cfg, params, max_batch=1, batch_buckets=(1,))
    first = engine.generate([long_one], 46)[0]
    snap = engine.metrics.snapshot()
    assert 60 + 46 > 3 * WINDOW
    assert snap["kv_window_positions_max"] == RING == WINDOW + CHUNK + BS
    assert engine.cache.k[1].shape[:3] == (4, 2, RING)       # the rings
    assert engine.cache.k[0].shape[0] == 1                   # the full layer
    assert snap["kv_blocks_high_water"] == -(-(60 + 46) // BS)
    # every decode call read two or three pages of 8 a window layer (a
    # window of 16, from the middle of a page or not) of its slot's
    # ring, where the two slots' whole rings are five pages each
    calls, rest = divmod(snap["window_decode_pages_ring_total"],
                         4 * 2 * (RING // BS))
    assert calls >= 45 and rest == 0, snap
    assert (2 * 4 * calls <= snap["window_decode_pages_total"]
            <= 3 * 4 * calls), snap
    rid = engine.submit(other, 12)
    engine.step()
    assert engine.metrics.snapshot()["kv_window_blocks_in_use"] == RING // BS
    engine.run_until_idle()
    second = engine.result(rid).tokens
    fresh = engine_for(cfg, params, max_batch=1, batch_buckets=(1,))
    assert fresh.generate([other], 12)[0] == second
    # and through the engine the tokens are the direct programs'
    _, _, toks, _ = serve_logits(cfg, params, long_one, 45)
    assert toks[60:] == first


def test_two_sequences_in_one_batch_keep_their_own_rings():
    cfg = tiny()
    params = seeded(cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (50, 19)]
    together = engine_for(cfg, params).generate(prompts, 30)
    for prompt, got in zip(prompts, together):
        alone = engine_for(cfg, params).generate([prompt], 30)[0]
        assert alone == got


# (g) --------------------------------------------------------------------

def test_what_is_not_built_for_two_caches_is_refused_by_name(devices):
    cfg = tiny()
    params = seeded(cfg)
    with pytest.raises(NotImplementedError, match="prefix_caching"):
        engine_for(cfg, params, prefix_caching=True)
    from horovod_tpu.serve.speculative import DraftConfig
    with pytest.raises(NotImplementedError, match="speculative"):
        engine_for(cfg, params, spec_k=2,
                   draft=DraftConfig(model_cfg=TransformerConfig.tiny()))
    engine = engine_for(cfg, params)
    with pytest.raises(NotImplementedError, match="handoff"):
        engine.submit([1, 2, 3], 2, prefill_only=True)
    with pytest.raises(NotImplementedError, match="inject"):
        engine.inject_begin({"block_size": BS})
    rid = engine.submit([1, 2, 3], 40)
    engine.step()
    with pytest.raises(NotImplementedError, match="migrate"):
        engine.export_running(rid)
    with pytest.raises(NotImplementedError, match="verify"):
        engine._verify_fn()
    for axis in ("tp", "ep"):
        mesh = build_mesh(devices=devices[:2], **{axis: 2})
        with pytest.raises(NotImplementedError, match=axis):
            decode_lib.make_serve_fns(cfg, mesh, block_size=BS,
                                      table_width=4, ring=RING)
    # The trainer runs such a stack since ISSUE 34 (tests/test_mellum2.py
    # holds it to the references); what it still refuses, by name:
    import horovod_tpu as hvd
    from horovod_tpu.parallel.pipeline import make_pp_train_step

    init, step, _ = make_train_step(cfg, build_mesh(devices=devices[:1],
                                                    dp=1))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 33)), jnp.int32)
    assert np.isfinite(float(step(init(jax.random.PRNGKey(0)),
                                  {"tokens": tokens})[1]))
    for axis in ("tp", "sp", "ep", "fsdp"):
        with pytest.raises(NotImplementedError, match=axis):
            make_train_step(cfg, build_mesh(devices=devices[:2],
                                            **{axis: 2}))
    with pytest.raises(NotImplementedError, match="pipeline"):
        make_pp_train_step(cfg, build_mesh(devices=devices[:2], pp=2),
                           n_micro=2)
    with pytest.raises(NotImplementedError, match="compression"):
        make_train_step(cfg, build_mesh(devices=devices[:2], dp=2),
                        compression=hvd.Compression.bf16)
    # OLMoE's kind stays refused: softmax routing without a capacity
    with pytest.raises(NotImplementedError, match="softmax"):
        decode_lib.make_serve_fns(
            TransformerConfig.tiny(n_experts=4, moe_capacity_factor=None),
            None, block_size=BS, table_width=4)


def test_a_chunked_engine_needs_a_bucket_for_the_chunk_not_the_prompt():
    """With ``prefill_chunk`` set no program longer than a chunk runs,
    so the longest prompt needs no bucket of its own."""
    cfg = TransformerConfig.tiny()
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    knobs = dict(max_batch=2, block_size=8, prefill_buckets=(8, 16),
                 max_prompt=64, max_new_tokens=8)
    engine = ServeEngine(cfg, params, ServeConfig(prefill_chunk=16, **knobs))
    out = engine.generate([list(range(1, 41))], 4)[0]
    assert len(out) == 4
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        ServeEngine(cfg, params, ServeConfig(**knobs))
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        ServeEngine(cfg, params, ServeConfig(prefill_chunk=32, **knobs))


# counters ---------------------------------------------------------------

def test_moe_share_report_counts_the_pairs_on_the_held_experts():
    cfg = tiny()
    params = seeded(cfg)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16))
    report = decode_lib.moe_share_report(params, toks, cfg, BS)
    assert 0 < report["moe_local_pair_share"] < 1
    assert 1 <= report["moe_held_experts_touched_mean"] <= 4
    assert report["moe_expert_load_max_over_mean"] >= 1
    assert report["moe_dispatch_dropped_token_frac"] == 0
    whole = tiny(moe_experts_held=16, moe_expert_offset=0)
    full = dataclasses.replace(cfg.moe, experts_held=16, expert_offset=0)
    wide = {**params, "layers": [{**lp, "moe": {
        **lp["moe"], **{k: jnp.tile(lp["moe"][k], (4, 1, 1))
                        for k in ("w_gate", "w_up", "w_down")}}}
        for lp in params["layers"]]}
    assert full.n_held == 16
    assert decode_lib.moe_share_report(wide, toks, whole, BS)[
        "moe_local_pair_share"] == 1.0


# (i) --------------------------------------------------------------------

@pytest.mark.parametrize("config", ["gqa", "moe"])
def test_the_existing_programs_did_not_move(config):
    """``TransformerConfig.tiny()`` and a tiny capacity MoE: tokens and
    the whole pool after a prefill and eight decode steps are bitwise
    what the kept reference form of ``tests/test_serve_pool.py`` gives."""
    import test_serve_pool as kept

    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False,
                                 **kept.CONFIGS[config])
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    shape = (cfg.n_layers, kept.N_BLOCKS, kept.BS, cfg.n_kv_heads,
             cfg.head_dim)
    table = jnp.asarray([5, 2, 7], jnp.int32)
    tokens = jnp.asarray(np.random.default_rng(7).integers(1, 256, 16),
                         jnp.int32)

    def run(prefill, decode):
        kc, vc = (jnp.asarray(np.random.default_rng(s).standard_normal(shape),
                              cfg.dtype) for s in (30, 31))
        kc, vc, tok = prefill(params, kc, vc, tokens, jnp.int32(11), table)
        out = [tok]
        for i in range(8):
            kc, vc, nxt = decode(params, kc, vc, out[-1][None],
                                 jnp.asarray([11 + i], jnp.int32), table[None])
            out.append(nxt[0])
        return np.asarray(kc), np.asarray(vc), np.asarray(jnp.stack(out))

    fns = decode_lib.make_serve_fns(cfg, None, block_size=kept.BS,
                                    table_width=kept.WIDTH)
    got = run(fns[0], fns[2])
    want = run(kept._reference(cfg, None, "prefill"),
               kept._reference(cfg, None, "decode"))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# sha256 of the lowered StableHLO of three tiny train steps shaped as the
# three training cells' (a dense GQA decoder on one device and on dp2 x
# fsdp2, a dropless sparse decoder with q/k norm), flash attention and
# remat_policy full, taken on the tree before the trainer ran stacks of
# several kinds (PR 33's; jax 0.9.0).
_TRAIN_STEPS = {
    "dense": (dict(n_kv_heads=2), dict(dp=1), 1,
              "94e2c6b2d23d0437b122b5233fb386552764e05c6dc78307e74da7cc679045df"),
    "dense_dp2_fsdp2": (
        dict(n_kv_heads=2), dict(dp=2, fsdp=2), 4,
        "40f2cefb81b57a21406190dbac7a412bf63f3b9ced5eecc4db720604aaf6e7bf"),
    "sparse_dropless": (
        dict(n_kv_heads=4, qk_norm=True, n_experts=8, moe_top_k=2, d_ff=32,
             moe_capacity_factor=None, moe_norm_topk_prob=False,
             moe_z_loss_coef=0.001), dict(dp=1), 1,
        "e853534e70b81a21f9927475cb4aa872ca13151269473339c04d80b568831e8b"),
}


@pytest.mark.parametrize("case", sorted(_TRAIN_STEPS))
def test_the_existing_train_steps_did_not_move(devices, case):
    """The scanned path of a configuration of one kind of layer is what
    it was: the window in the flash kernels, the rotary embedding by
    kind of layer, the loop over a mixed configuration's lists and the
    held experts' gradients changed no operation of it."""
    import hashlib

    fields, axes, n, want = _TRAIN_STEPS[case]
    cfg = TransformerConfig.tiny(dtype=jnp.float32, sp_attention="flash",
                                 remat_policy="full", **fields)
    init, step, _ = make_train_step(
        cfg, build_mesh(devices=devices[:n], **axes))
    state = jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    text = step.lower(state, {"tokens": jax.ShapeDtypeStruct(
        (2 * n, 65), jnp.int32)}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == want


# sha256 of the lowered StableHLO of the serve programs of the tiny window
# configuration above (over its pool and rings) and of a tiny dense GQA
# decoder (over one pool), taken on the tree before the state became one
# array a kind of layer (PR 37's; jax 0.9.0). The decode program over
# two caches was taken again at PR 55: its full layer's step is the
# Pallas call of ``ops/paged_decode.py`` (the interpreter's form here),
# and at PR 59: so are its four window layers' steps (``ring_decode``).
_SERVE_PROGRAMS = {
    "two_caches/prefill":
        "34a2ab9483578a5c4831799975f027de09230c7bbeb96a294b00d8aa9e7be35c",
    "two_caches/prefill_resume":
        "8dd7e0c23433cdcd04160f713efb59f22c328ca1785170fd7cc3ea38eba3d2b9",
    "two_caches/decode":
        "73067530b4a6134bfef7d5bcd9dd8d1f1479db566c171b1eea33b3dbd9e2fb3e",
    "dense/prefill":
        "2c4fa4dde8b1a2f85ab888aee66330331942b29c379d423f7cd4dcb1b5f1ed5c",
    "dense/prefill_resume":
        "a9b4a0ea4dc41f1cea940728fd467f2bdae972f4210be2637c314191779141ef",
    "dense/decode":
        "7713d90e726dada26c6d7340f579247367a9b30023b763778041935a1b8d83d0",
}


@pytest.mark.parametrize("case", sorted(_SERVE_PROGRAMS))
def test_the_existing_serve_programs_did_not_move(case):
    """The table of kinds, the recurrent states and the latent pool
    (ISSUE 38) changed no operation of the programs that serve window
    and full layers over two caches, nor of the dense ones: the arrays
    keep their places among the arguments and every operation its
    order."""
    import hashlib

    name, program = case.split("/")
    cfg, ring = ((tiny(), RING) if name == "two_caches" else
                 (TransformerConfig.tiny(dtype=jnp.float32, remat=False,
                                         n_kv_heads=2), 0))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    width = 6
    params = jax.eval_shape(
        lambda: init_transformer(cfg, jax.random.PRNGKey(0)))
    kc, vc = jax.eval_shape(lambda: (lambda c: (c.k, c.v))(init_kv_cache(
        cfg, 13, BS, n_slots=2, ring=ring)))
    fns = dict(zip(("prefill", "prefill_resume", "decode"),
                   decode_lib.make_serve_fns(cfg, None, block_size=BS,
                                             table_width=width, ring=ring)))
    one = (i32(width), i32()) if cfg.mixed else i32(width)
    many = (i32(2, width), i32(2)) if cfg.mixed else i32(2, width)
    args = {"prefill": (i32(16), i32(), one),
            "prefill_resume": (i32(16), i32(), i32(), one),
            "decode": (i32(2), i32(2), many)}[program]
    text = fns[program].lower(params, kc, vc, *args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _SERVE_PROGRAMS[case]


def test_the_two_copies_of_the_reference_are_one_text():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def body(path):
        text = open(os.path.join(root, path)).read()
        return text[text.index('"""', 3):]

    assert body("tests/reference_trinity.py") == body(
        "benchmark/reference_trinity.py")
