"""A stack whose every layer is latent attention (mla) with a query
rank, rotated by YaRN with the softmax scale's ``m^2``, over one pool
of latent pages, with 12 of 384 experts held and the engine's prefix
cache over those pages (ISSUE 43). At a tiny size with seeded weights
in float32, against ``tests/reference_kimi_k2.py``: the plain forward
of the same equations over a whole sequence, no cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_kimi_k2 as ref
from reference_mla import mla_attend_absorbed
from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.models import moe as moe_lib
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import (BlockAllocator, init_kv_cache,
                                        state_kinds)

BS, CHUNK = 8, 32
# YaRN over 32 positions, so that a prompt of a hundred lies far past
# the original length and the ramp's pairs turn 64 times slower
ROTARY = dict(theta=50000.0, factor=64.0, original_max_seq=32,
              beta_fast=32.0, beta_slow=1.0, attention_factor=1.0,
              mscale_all_dim=1.0)


def tiny(**kw):
    base = dict(
        vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_head=16, d_ff=32, d_ff_dense=96, n_dense_layers=1, max_seq=256,
        norm_eps=1e-5, layer_types=("mla",) * 3, mla_kv_rank=32,
        mla_rope_dim=16, mla_q_rank=24, mla_head_gate=False,
        layer_rotary={"mla": ROTARY}, n_experts=384, moe_top_k=8,
        moe_capacity_factor=None, moe_scoring="sigmoid",
        moe_route_scale=2.827, moe_shared_expert=True, moe_experts_held=12,
        moe_expert_offset=12, dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def sizes_of(cfg):
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    model["layer_rotary"] = {
        kind: dataclasses.asdict(how) for kind, how in cfg.layer_rotary}
    return ref.sizes_of({"model": model})


def seeded(cfg, seed=0):
    """Seeded weights whose norm gains and selection bias are not the
    ones and zeros of an initialisation, so that each is seen; the bias
    draws half the tokens' choices towards the held experts, so that
    the share's sum is of many pairs."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("norm"):
            return a + 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        if name == "router_bias":
            bias = 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
            return bias.at[..., 12:24].add(0.3)
        return a

    return jax.tree_util.tree_map_with_path(shake, params)


@pytest.fixture(autouse=True)
def small_key_blocks(monkeypatch):
    """Key blocks of 32 positions, so that a sequence's latents are
    attended over several."""
    monkeypatch.setattr(decode_lib, "_MLA_KEY_BLOCK", 32)


def programs(cfg, width):
    fns = decode_lib.mixed_programs(cfg, BS, width, 0, head=lambda lg: lg)
    return tuple(map(jax.jit, fns[:3]))


def chunks(fns, params, kc, vc, prompt, addr, start=0):
    """``prompt`` from position ``start`` into ``addr`` in chunks of
    CHUNK; the logits after each chunk and the positions they are of."""
    prefill, resume, _ = fns
    rows, at = [], []
    for off in range(start, len(prompt), CHUNK):
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
    return kc, vc, rows, at


def serve_logits(cfg, params, prompts, n_decode):
    """Chunked prefill of each of ``prompts``, then ``n_decode`` greedy
    steps of ALL of them as one full batch: for each prompt (the logits
    at the last position of each chunk and of each step, the positions
    they belong to, every token)."""
    B = len(prompts)
    width = -(-(max(map(len, prompts)) + n_decode) // BS)
    fns = programs(cfg, width)
    cache = init_kv_cache(cfg, B * width + 1, BS, n_slots=B)
    kc, vc = cache.k, cache.v
    tables = np.arange(1, B * width + 1, dtype=np.int32).reshape(B, width)
    rows, at, toks = [], [], [list(p) for p in prompts]
    for b, prompt in enumerate(prompts):
        kc, vc, r, a = chunks(fns, params, kc, vc, prompt,
                              (jnp.asarray(tables[b]), jnp.int32(b + 1)))
        rows.append(r)
        at.append(a)
        toks[b].append(int(r[-1].argmax()))
    for _ in range(n_decode):
        pos = [len(t) - 1 for t in toks]
        kc, vc, lg = fns[2](
            params, kc, vc, jnp.asarray([t[-1] for t in toks], jnp.int32),
            jnp.asarray(pos, jnp.int32),
            (jnp.asarray(tables), jnp.arange(1, B + 1, dtype=jnp.int32)))
        for b in range(B):
            rows[b].append(np.asarray(lg[b]))
            at[b].append(pos[b])
            toks[b].append(int(lg[b].argmax()))
    return [(np.stack(r), a, t) for r, a, t in zip(rows, at, toks)]


def gap(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


PROMPTS = (109, 32, 5, 64)   # chunks 32+32+32+13, one whole, 5 of 8, two


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
        return cfg, params, serve_logits(cfg, params, prompts_of(cfg), 12)


# (a) --------------------------------------------------------------------

@pytest.mark.parametrize("b", range(len(PROMPTS)),
                         ids=[f"prompt_{n}" for n in PROMPTS])
def test_chunks_and_decode_in_a_full_batch_match_the_reference(served, b):
    """The logits after each chunk (expanded attention over the pool)
    and after each of 12 decode steps of a full batch (absorbed) are
    the reference's one pass over the whole sequence."""
    cfg, params, out = served
    got, at, toks = out[b]
    want = ref.logits(params, np.asarray(toks[:-1]), sizes_of(cfg))
    assert gap(got, np.asarray(want)[at]) < 2e-4


@pytest.mark.parametrize("wrong", [w for w in ref.WRONG
                                   if w != "router_in_bf16"])
def test_each_mechanism_miscomputed_fails_the_comparison(served, wrong):
    """The reference without the softmax scale's m^2, with plain rotary
    in place of YaRN, with halves for interleaved pairs, without the
    query latent's norm, the selection bias or the routed scale lies
    further from the served logits than (a)'s tolerance by two orders
    of magnitude."""
    cfg, params, out = served
    got, at, toks = out[0]
    want = np.asarray(ref.logits(params, np.asarray(toks[:-1]),
                                 sizes_of(cfg), wrong=wrong))
    assert gap(got, want[at]) > 2e-2


@pytest.mark.parametrize("field", [dict(mscale_all_dim=0.0),
                                   dict(factor=None)],
                         ids=["no_mscale", "no_yarn"])
def test_yarn_and_its_scale_are_the_configuration_s(served, field):
    """Turned off in the PROGRAM's configuration (the scale's m^2
    alone; or YaRN, and m^2 with it, as the source's code has it), the
    program is the reference of that configuration, and far from the
    program with both on: the switch is the mechanism."""
    cfg, params, out = served
    off = tiny(layer_rotary={"mla": {**ROTARY, **field}})
    _, _, toks = out[1]
    got, at, _ = serve_logits(off, params, [toks[:32]], 0)[0]
    want = np.asarray(ref.logits(params, np.asarray(toks[:32]),
                                 sizes_of(off)))
    assert gap(got, want[at]) < 2e-4
    assert gap(got, out[1][0][:1]) > 2e-2


def test_a_router_in_bf16_chooses_other_experts(served):
    """The router's operands rounded to bfloat16 where the
    configuration says float32: some tokens take another expert (the
    logits move where one of the two is held, which at 12 of 384 is
    seldom: the cell's check cannot tell it, the choice itself can)."""
    cfg, params, out = served
    sizes = sizes_of(cfg)
    mp = params["layers"][0]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(9), (512, cfg.d_model))
    right = ref.moe(u, mp, sizes)[1]
    narrow = ref.moe(u, mp, sizes, "router_in_bf16")[1]
    differ = (np.sort(np.asarray(right), -1)
              != np.sort(np.asarray(narrow), -1)).any(-1)
    assert 0 < differ.sum() < 256


# (b) the prefix cache over latent pages ---------------------------------

def test_a_mapped_prefix_gives_the_logits_of_the_cold_request():
    """A document's pages written by one sequence and mapped into
    another's block table: the second request's first call is a resume
    at the document's end, and its logits there and over 10 decode
    steps are those of the same request served cold, and the
    reference's."""
    cfg = tiny()
    params = seeded(cfg)
    doc, q1, q2 = prompts_of(cfg, (72, 21, 13), seed=3)
    width = 16
    fns = programs(cfg, width)
    shared = list(range(1, 10))                          # the document's

    def ask(kc, vc, question, own, start):
        table = np.zeros(width, np.int32)
        table[:9], table[9:9 + len(own)] = shared, own
        addr = (jnp.asarray(table), jnp.int32(1))
        prompt = doc + question
        kc, vc, rows, at = chunks(fns, params, kc, vc, prompt, addr, start)
        toks = prompt + [int(rows[-1].argmax())]
        for _ in range(10):
            kc, vc, lg = fns[2](
                params, kc, vc, jnp.asarray(toks[-1:], jnp.int32),
                jnp.asarray([len(toks) - 1], jnp.int32),
                (jnp.asarray(table)[None], jnp.ones(1, jnp.int32)))
            rows.append(np.asarray(lg[0]))
            at.append(len(toks) - 1)
            toks.append(int(lg[0].argmax()))
        return kc, vc, np.stack(rows), at, toks

    cache = init_kv_cache(cfg, 40, BS, n_slots=1)
    kc, vc, *_ = ask(cache.k, cache.v, q1, [10, 11, 12, 13, 14], 0)
    doc_pages = np.asarray(kc[0][:, 1:10])
    # the second ask: the document's 9 pages mapped, nothing of them
    # computed, its own pages elsewhere
    kc, vc, warm, at, toks = ask(kc, vc, q2, [20, 21, 22, 23], len(doc))
    np.testing.assert_array_equal(np.asarray(kc[0][:, 1:10]), doc_pages)
    fresh = init_kv_cache(cfg, 40, BS, n_slots=1)
    _, _, cold, cold_at, cold_toks = ask(fresh.k, fresh.v, q2,
                                         [20, 21, 22, 23], 0)
    assert toks == cold_toks
    assert gap(warm, cold[-len(warm):]) < 1e-5
    want = np.asarray(ref.logits(params, np.asarray(toks[:-1]),
                                 sizes_of(cfg)))
    assert gap(warm, want[at]) < 2e-4


def engine_for(cfg, params, **kw):
    knobs = dict(max_batch=4, block_size=BS, max_prompt=128,
                 max_new_tokens=16, prefix_caching=True,
                 prefill_chunk=CHUNK, prefill_buckets=(8, 16, 32),
                 batch_buckets=(4,))
    knobs.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**knobs))


def test_the_engine_shares_a_document_s_pages_and_serves_the_reference():
    """Through ``ServeEngine`` with ``prefix_caching`` on: a document
    asked three times, the second ask while the first still decodes
    (its pages held by two sequences), the third after both have
    retired (pages revived from the cached pool). Every ask's tokens
    are the cold engine's and the reference's argmax; the counters and
    the prefill spans say what was mapped."""
    cfg = tiny()
    params = seeded(cfg)
    doc, *questions = prompts_of(cfg, (72, 21, 13, 30), seed=3)
    asks = [doc + q for q in questions]
    eng = engine_for(cfg, params)
    first = eng.submit(asks[0], 12)
    while eng.result(first) is None and not eng._active:
        eng.step()                      # the first ask's prefill is done
    second = eng.submit(asks[1], 12)
    eng.run_until_idle()
    third = eng.submit(asks[2], 12)
    eng.run_until_idle()
    snap = eng.metrics.snapshot()
    assert snap["prefix_hit_tokens"] == 2 * 72
    assert snap["prefix_cache_hit_rate"] == round(144 / sum(map(len, asks)), 4)
    assert snap["kv_pages_shared_max"] == 9 and snap["kv_pages_shared"] == 0
    assert snap["kv_latent_positions_max"] == 72 + 30 + 12 - 1
    # a table of 9 pages is one key block: every row of every decode
    # call (a bucket of 4) reads one a layer, the longest row's too
    assert (snap["latent_decode_key_blocks_total"]
            == snap["latent_decode_key_blocks_longest_total"]
            == cfg.n_layers * 4 * snap["decode_steps"])
    spans = [e for e in eng.metrics._events if e["name"] == "serve:prefill"]
    assert sum(s["args"]["mapped"] for s in spans) == 144
    mapped = [s["args"] for s in spans if s["args"]["mapped"]]
    assert [(a["offset"], a["n_tokens"]) for a in mapped] == [(72, 13),
                                                              (72, 30)]
    cold = engine_for(cfg, params, prefix_caching=False)
    for ask, rid in zip(asks, (first, second, third)):
        toks = eng.result(rid).tokens
        assert toks == cold.generate([ask], 12)[0]
        want = np.asarray(ref.logits(params, np.asarray(ask + toks[:-1]),
                                     sizes_of(cfg), last=12))
        assert toks == want.argmax(-1).tolist()
    eng.allocator.verify_integrity()


def test_the_allocator_counts_the_pages_that_are_shared():
    a = BlockAllocator(8, 4)
    mine = a.alloc(3)
    for i, b in enumerate(mine):
        a.register(b, bytes([i]))
    assert a.n_shared == 0
    theirs = [a.acquire_cached(bytes([i])) for i in range(2)]
    third = a.acquire_cached(bytes([0]))
    assert theirs == mine[:2] and (a.n_shared, a.shared_high_water) == (2, 2)
    a.free([third])
    assert a.n_shared == 2
    a.free(theirs)
    assert (a.n_shared, a.shared_high_water) == (0, 2)
    a.free(mine)                        # cached now: revived, not shared
    assert a.acquire_cached(bytes([2])) == mine[2] and a.n_shared == 0
    a.verify_integrity()


# (c) which configurations may share prefixes ----------------------------

@pytest.mark.parametrize("kinds,named", [
    (("sliding", "full", "full"), "sliding"),
    (("kda", "kda", "mla"), "kda"),
    (("kda", "sliding", "mla"), "kda and sliding")])
def test_prefix_caching_is_refused_by_name_for_a_state_by_slot(kinds, named):
    """A ring or a recurrent state lies by batch slot and cannot be
    mapped into another sequence: those kinds refuse, naming
    themselves; a stack of mla layers alone is served."""
    cfg = tiny(layer_types=kinds, attn_window=16, layer_rotary=None,
               mla_q_rank=0)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError,
                       match=f"prefix_caching \\(its {named} layers.*B14"):
        engine_for(cfg, params)
    assert engine_for(cfg, params, prefix_caching=False)


def test_a_stack_of_latent_layers_keeps_one_pool_and_nothing_by_slot():
    cfg = tiny()
    assert cfg.stateful and cfg.mixed and state_kinds(cfg) == ("mla",)
    cache = init_kv_cache(cfg, 5, BS, n_slots=2)
    assert cache.kinds == ("mla",) and cache.v == (None,)
    assert cache.k[0].shape == (3, 5, BS, 128) and cache.slot_bytes == 0
    lp = init_transformer(cfg, jax.random.PRNGKey(0))["layers"][0]
    assert {"w_dq", "dq_norm", "w_uq"} <= set(lp) and not {"wq", "wg"} & set(lp)
    assert lp["w_uq"].shape == (24, 4 * (16 + 16))
    specs = tf_lib.param_specs(cfg)
    assert jax.tree.structure(specs["layers"][0], is_leaf=lambda x: isinstance(
        x, jax.sharding.PartitionSpec)) == jax.tree.structure(lp)


# (d) the rotary and its scale -------------------------------------------

def test_yarn_as_published_is_the_reference_s():
    """At the published constants: the softmax scale is 192^-1/2 x
    2.0048, the frequencies the reference's (written from the paper
    without the program), cos and sin times 1."""
    published = dict(theta=50000.0, factor=64.0, original_max_seq=4096,
                     beta_fast=32.0, beta_slow=1.0, mscale_all_dim=1.0)
    rotary = tf_lib.Rotary(**published)
    m = 0.1 * np.log(64.0) + 1.0
    assert rotary.softmax_mscale == pytest.approx(m * m)
    assert m * m == pytest.approx(2.0048, abs=1e-4)
    cfg = tiny(d_head=128, mla_rope_dim=64, layer_rotary={"mla": published})
    assert tf_lib.mla_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    sizes = sizes_of(cfg)
    np.testing.assert_allclose(rotary.frequencies(64),
                               ref.yarn_frequencies(sizes), rtol=1e-6)
    assert ref.softmax_scale(sizes) == pytest.approx(tf_lib.mla_scale(cfg))
    plain = ref.yarn_frequencies(sizes, plain=True)
    f = rotary.frequencies(64)
    # fast pairs keep their frequency, slow ones turn 64 times slower
    assert f[0] == plain[0] and f[-1] == pytest.approx(plain[-1] / 64)
    assert tf_lib.Rotary(50000.0).softmax_mscale == 1.0
    with pytest.raises(ValueError, match="'mla'"):
        tiny(layer_rotary={"kda": None})


@pytest.mark.parametrize("queries", [1, 5])
def test_absorbed_and_expanded_latent_attention_agree(queries):
    """Every query at a position that some key holds: one that sees no
    key reads zeros expanded and a mean of the keys absorbed."""
    cfg = tiny()
    lp = seeded(cfg)["layers"][1]
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, K = 3, 96
    qn = jax.random.normal(ks[0], (B, queries, 4, 16))
    qr = jax.random.normal(ks[1], (B, queries, 4, 16))
    latents = jax.random.normal(ks[2], (B, K, 48))
    pos = jnp.asarray([[40], [95], [5]]) + jnp.arange(queries)[None] - queries

    def keys_of(j):
        return (jax.lax.dynamic_slice_in_dim(latents, j * 32, 32, 1),
                j * 32 + jnp.arange(32))

    got = [attend(cfg, lp, qn, qr, keys_of, 3, pos)
           for attend in (mla_attend_absorbed, decode_lib._mla_attend)]
    assert gap(np.asarray(got[0]), np.asarray(got[1])) < 1e-5


@pytest.fixture(scope="module")
def a_document_s_pages():
    """A document of 72 tokens (9 pages: its end is no key block's)
    written into pages 1..9 by the programs as they are."""
    with pytest.MonkeyPatch.context() as patch:    # as small_key_blocks
        patch.setattr(decode_lib, "_MLA_KEY_BLOCK", 32)
        cfg = tiny()
        params = seeded(cfg)
        doc, = prompts_of(cfg, (72,), seed=3)
        cache = init_kv_cache(cfg, 40, BS, n_slots=1)
        table = np.zeros(16, np.int32)
        table[:9] = range(1, 10)
        kc, vc, _, _ = chunks(programs(cfg, 16), params, cache.k, cache.v,
                              doc, (jnp.asarray(table), jnp.int32(1)))
        return cfg, params, kc, vc


@pytest.mark.parametrize("width", [8, 16, 32])     # the engine's buckets
@pytest.mark.parametrize("how", ["local", "resumed_over_mapped_pages"])
def test_a_chunk_through_the_kernel_is_the_absorbed_form(a_document_s_pages,
                                                         how, width):
    """A chunk of each bucket width through ``mla_chunk``, its last 3
    places padding: as the whole prompt over itself, and resumed at the
    document's end (72: a multiple of the page, not of the key block)
    over the document's pages mapped into another sequence's table.
    The logits through the Pallas forward are those of the same program
    attending in the absorbed form."""
    cfg, params, kc, vc = a_document_s_pages
    n = width - 3
    tokens = np.zeros(width, np.int32)
    tokens[:n] = prompts_of(cfg, (n,), seed=width)[0]
    table = np.zeros(16, np.int32)
    if how == "local":
        table[:4] = range(20, 24)
    else:
        table[:9], table[9:13] = range(1, 10), range(20, 24)
    addr = (jnp.asarray(table), jnp.int32(1))

    def logits():
        prefill, resume, _ = programs(cfg, 16)
        if how == "local":
            return prefill(params, kc, vc, tokens, jnp.int32(n), addr)[2]
        return resume(params, kc, vc, tokens, jnp.int32(72), jnp.int32(n),
                      addr)[2]

    got = np.asarray(logits())
    with pytest.MonkeyPatch.context() as patch:
        # the chunks attend absorbed: the expanded form's reference
        patch.setattr(decode_lib, "_mla_attend", mla_attend_absorbed)
        want = np.asarray(logits())
    assert got.shape == want.shape and gap(got, want) < 1e-5


# (e) the share of the experts --------------------------------------------

def test_the_32_shares_add_up():
    """Over the 32 offsets the routed sums of 12 experts each, with the
    shared expert counted once, are the uncut layer's, and the
    reference's share at this chip's offset is the program's."""
    cfg = tiny(moe_experts_held=None, moe_expert_offset=0)
    whole = seeded(cfg)["layers"][0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    want, _ = moe_lib.moe_ffn_dropless(x, whole, cfg.moe)
    shared = moe_lib._shared_expert(x.reshape(-1, cfg.d_model), whole
                                    ).reshape(x.shape)
    total = -31 * shared
    for offset in range(0, 384, 12):
        share = dataclasses.replace(cfg.moe, experts_held=12,
                                    expert_offset=offset)
        lp = {**whole, **{w: whole[w][offset:offset + 12]
                          for w in ("w_gate", "w_up", "w_down")}}
        part = moe_lib.moe_ffn_dropless(x, lp, share)[0]
        if offset == 12:
            mine = ref.moe(x.reshape(-1, cfg.d_model), lp, sizes_of(tiny()))
            assert gap(np.asarray(part).reshape(-1, cfg.d_model),
                       np.asarray(mine[0])) < 1e-5
            assert int((mine[1] // 12 == 1).sum()) > 20    # pairs held
        total = total + part
    assert gap(np.asarray(total), np.asarray(want)) < 1e-5


# (f) --------------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_one_text():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def body(path):
        text = open(os.path.join(root, path)).read()
        return text[text.index('"""', 3):]

    assert body("tests/reference_kimi_k2.py") == body(
        "benchmark/reference_kimi_k2.py")
    assert "horovod_tpu" not in body("tests/reference_kimi_k2.py")
