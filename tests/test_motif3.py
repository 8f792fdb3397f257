"""Motif-3's layers served (ISSUE 63): grouped differential attention
over a latent cache (GDLA: 10 query heads, 2 of them noise heads, over 2
KV heads that come up from one latent), in window layers whose latents
lie in a RING by batch slot (the eleventh kind, ``mla_sliding``) beside
full layers whose latents lie in pages, on a four-stream mHC residual,
with PolyNorm in the dense feed-forward, the shared expert and a chip's
share of the routed experts. At a tiny size with seeded weights, against
``tests/reference_motif3.py``: the plain forward of the same equations
over a whole sequence, the attention expanded over all keys under the
mask, the experts a loop over the held ones, no cache."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_motif3 as ref
from horovod_tpu.models import (TransformerConfig, init_transformer,
                                make_train_step)
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.ops.paged_decode import latent_ring_decode
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import (RING_KINDS, SLOT_KINDS, STATE_KINDS,
                                        init_kv_cache, ring_width)

BS, CHUNK, WINDOW = 4, 16, 8
RING = ring_width(WINDOW, CHUNK, BS)         # 28: a prompt of 77 wraps it
# the cut's own pattern: a dense window layer, then window, FULL, window,
# window
TYPES = ("mla_sliding", "mla_sliding", "mla", "mla_sliding", "mla_sliding")


def tiny(**kw):
    base = dict(
        vocab_size=128, d_model=64, n_layers=5, n_heads=10, n_kv_heads=2,
        d_head=16, d_ff=24, d_ff_dense=96, n_dense_layers=1, max_seq=256,
        norm_eps=1e-5, layer_types=TYPES, attn_window=WINDOW,
        layer_rotary={"mla": {"theta": 10000.0}}, mla_kv_rank=32,
        mla_rope_dim=8, mla_q_rank=24, mla_head_gate=False,
        mla_noise_heads=2, mla_elementwise_gate=True, mhc_streams=4,
        mhc_sinkhorn_iters=20, ffn_activation="polynorm",
        moe_activation="polynorm", polynorm_scale=0.5,
        polynorm_bias_clamp=0.5, n_experts=16, moe_top_k=3,
        moe_capacity_factor=None, moe_norm_topk_prob=True,
        moe_scoring="sigmoid", moe_route_scale=2.0, moe_shared_expert=True,
        moe_experts_held=4, moe_expert_offset=4, dtype=jnp.float32,
        remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def sizes_of(cfg):
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    model["layer_rotary"] = {"mla": {"theta": 10000.0}}
    return ref.sizes_of({"model": model})


def seeded(cfg, seed=0):
    """Seeded weights whose norm gains and selection bias are not the
    ones of an initialisation, and a q three times as large (the cell's
    ``q_gain``: a window is then one), so that each is seen."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("norm") or name == "router_bias":
            return a + (0.3 * jax.random.normal(next(keys), a.shape)
                        ).astype(a.dtype)
        return 3.0 * a if name == "w_uq" else a

    return jax.tree_util.tree_map_with_path(shake, params)


def engine_for(cfg, params, **kw):
    knobs = dict(max_batch=4, max_prompt=128, max_new_tokens=16,
                 block_size=BS, prefill_chunk=CHUNK,
                 prefill_buckets=(4, 8, 16), batch_buckets=(4,),
                 prefix_caching=False)
    knobs.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**knobs))


def serve_logits(cfg, params, prompts, n_decode, chunk=CHUNK, pad_to=BS):
    """Chunked prefill of each of ``prompts`` into its slot (a chunk
    padded to a multiple of ``pad_to``), then ``n_decode`` greedy steps
    of ALL of them as one full batch. Returns for each prompt (the
    logits at the last position of each chunk and of each step, the
    positions they belong to, every token)."""
    B = len(prompts)
    width = -(-(max(map(len, prompts)) + n_decode) // BS) + chunk // BS
    ring = ring_width(cfg.attn_window, chunk, BS)
    prefill, resume, decode, _ = decode_lib.mixed_programs(
        cfg, BS, width, ring, head=lambda lg: lg)
    prefill, resume, decode = map(jax.jit, (prefill, resume, decode))
    cache = init_kv_cache(cfg, B * width + 1, BS, n_slots=B, ring=ring)
    kc, vc = cache.k, cache.v
    tables = np.arange(1, B * width + 1, dtype=np.int32).reshape(B, width)
    rows, at, toks = ([[] for _ in prompts], [[] for _ in prompts],
                      [list(p) for p in prompts])
    for b, prompt in enumerate(prompts):
        addr = (jnp.asarray(tables[b]), jnp.int32(b + 1))
        for off in range(0, len(prompt), chunk):
            n = min(chunk, len(prompt) - off)
            padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
            padded[:n] = prompt[off:off + n]
            if off == 0 and n == len(prompt):
                kc, vc, lg = prefill(params, kc, vc, padded, jnp.int32(n),
                                     addr)
            else:
                kc, vc, lg = resume(params, kc, vc, padded, jnp.int32(off),
                                    jnp.int32(n), addr)
            rows[b].append(np.asarray(lg, np.float32))
            at[b].append(off + n - 1)
        toks[b].append(int(rows[b][-1].argmax()))
    for _ in range(n_decode):
        pos = [len(t) - 1 for t in toks]
        kc, vc, lg = decode(
            params, kc, vc, jnp.asarray([t[-1] for t in toks], jnp.int32),
            jnp.asarray(pos, jnp.int32),
            (jnp.asarray(tables), jnp.arange(1, B + 1, dtype=jnp.int32)))
        for b in range(B):
            rows[b].append(np.asarray(lg[b], np.float32))
            at[b].append(pos[b])
            toks[b].append(int(lg[b].argmax()))
    return [(np.stack(r), a, t) for r, a, t in zip(rows, at, toks)]


def gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


PROMPTS = (77, 16, 5)        # chunks 16 x 4 + 13, one whole, 5 of 8


def prompts_of(cfg, lens=PROMPTS, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


# (a) each branch alone against the reference ----------------------------

@pytest.mark.parametrize("kind,sparse", [
    ("mla", False), ("mla_sliding", False), ("mla_sliding", True)])
def test_each_branch_alone_is_the_reference_s(kind, sparse):
    """A model of ONE layer: full or window attention with the dense
    PolyNorm feed-forward, or with the share of the mixture; a prompt in
    chunks and some decode steps against the reference's one forward."""
    cfg = tiny(n_layers=1, layer_types=(kind,), n_dense_layers=0,
               **({} if sparse else dict(
                   n_experts=0, d_ff=96, moe_experts_held=None,
                   moe_expert_offset=0, moe_shared_expert=False,
                   moe_scoring="softmax", moe_route_scale=1.0,
                   moe_activation="swiglu", moe_capacity_factor=1.25)))
    params, sizes = seeded(cfg), sizes_of(cfg)
    [(rows, at, toks)] = serve_logits(cfg, params, prompts_of(cfg, (29,)), 12)
    want = ref.logits(params, np.asarray(toks[:-1]), sizes)
    assert gap(rows, np.asarray(want)[at]) < 3e-5
    lp = params["layers"][0]
    assert ("moe" in lp) == sparse and ("poly_w" in lp) != sparse
    assert lp["w_ukv"].shape == (32, 2 * 2 * 16)       # 2 KV heads, not 10
    assert lp["wo"].shape == (8 * 16, 64)              # the signal heads


def test_the_branches_are_the_reference_s_one_at_a_time():
    """The feed-forward branch alone (``ffn_block``: ``stream_in``, the
    dense PolyNorm feed-forward or the share of the mixture,
    ``stream_out``) on a random four-stream input against the
    reference's ``branch``."""
    cfg = tiny()
    params, sizes = seeded(cfg), sizes_of(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 4, 64), jnp.float32)
    for i, lp in enumerate(tf_lib._stack_of(params)[:2]):
        y, _ = tf_lib.ffn_block(cfg, lp, x)
        with jax.default_matmul_precision("highest"):
            want = ref.branch(x[0], lp, sizes, "mlp")
        assert gap(y[0], want) < 2e-5, i


# (b) chunks, resumed chunks, decode -------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-5),
                                       (jnp.bfloat16, 0.2)])
def test_chunks_then_decode_equal_the_reference(dtype, tol):
    """Three prompts (77 in five chunks, the last padded, so that its
    keys wrap the ring of 28 twice in prefill; 16 whole; 5 of 8) then 14
    decode steps of all three as one batch, through rings and pages:
    every chunk's end and every step against the reference's ONE forward
    over prompt and outputs."""
    cfg = tiny(dtype=dtype)
    params = seeded(cfg)
    for rows, at, toks in serve_logits(cfg, params, prompts_of(cfg), 14):
        want = ref.logits(params, np.asarray(toks[:-1]), sizes_of(cfg))
        assert gap(rows, np.asarray(want)[at]) < tol


def test_the_absorbed_step_is_the_expanded_chunk():
    """The same 40 positions as chunks alone (expanded attention, the
    subtraction after ``W_uv``) and as a prompt of 12 and 28 decode steps
    (absorbed, the subtraction IN THE LATENT): the logits at the shared
    positions agree."""
    cfg = tiny()
    params = seeded(cfg)
    [(rows, at, toks)] = serve_logits(cfg, params, prompts_of(cfg, (12,)), 28)
    [(again, where, _)] = serve_logits(cfg, params, [toks[:-1]], 0, chunk=4)
    both = sorted(set(at) & set(where))
    assert len(both) >= 8
    assert gap(again[[where.index(p) for p in both]],
               rows[[at.index(p) for p in both]]) < 3e-5


def test_a_prompt_in_padded_chunks_is_the_prompt_whole():
    cfg = tiny()
    params = seeded(cfg)
    prompt = prompts_of(cfg, (40,))
    [(a, _, ta)] = serve_logits(cfg, params, prompt, 6)
    [(b, _, tb)] = serve_logits(cfg, params, prompt, 6, chunk=40, pad_to=8)
    assert ta == tb and gap(a[-6:], b[-6:]) < 3e-5


# (c) the engine ----------------------------------------------------------

def test_the_engine_serves_a_full_batch_and_reuses_its_slots():
    """Seven requests through four slots: chunked prefill, decode of a
    full batch through latent rings and latent pages, slots freed and
    reused; every request's tokens are the reference's."""
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_for(cfg, params)
    prompts = prompts_of(cfg, (77, 16, 5, 33, 50, 9, 41))
    rids = [eng.submit(p, 10 + i) for i, p in enumerate(prompts)]
    eng.step()
    eng.step()
    snap = eng.metrics.snapshot()
    assert snap["kv_window_blocks_in_use"] == 4 * RING // BS
    assert snap["state_slots_in_use"] == 0 and snap["state_bytes"] == 0
    eng.run_until_idle()
    slots = []
    for i, (prompt, rid) in enumerate(zip(prompts, rids)):
        res = eng.result(rid)
        slots.append(res.slot)
        want = ref.logits(params, np.asarray(prompt + res.tokens[:-1]),
                          sizes_of(cfg), last=10 + i)
        assert res.tokens == np.asarray(want).argmax(-1).tolist(), i
    assert len(set(slots)) == 4 and len(slots) == 7
    snap = eng.metrics.snapshot()
    assert snap["kv_latent_ring_positions_max"] == RING
    assert snap["kv_latent_positions_max"] == 77 + 10 - 1
    assert snap["kv_window_positions_max"] == 0
    assert snap["latent_ring_decode_pages_total"] > 0
    assert eng.cache.kinds == ("mla", "mla_sliding")
    assert eng.cache.of("mla_sliding")[0].shape == (4, 5, RING, 128)
    assert eng.cache.of("mla")[0].shape[0] == 1


def test_the_spans_say_what_a_call_s_latent_attention_read(tmp_path):
    cfg = tiny()
    eng = engine_for(cfg, seeded(cfg))
    eng.submit(prompts_of(cfg)[0], 4, trace_id=1)   # 77: 16 x 4 + 13 of 16
    eng.run_until_idle()
    path = tmp_path / "spans.json"
    eng.metrics.export_chrome_trace(str(path))
    spans = [e for e in json.load(open(path))["traceEvents"]
             if e.get("ph") == "X"]
    chunks = [s["args"] for s in spans if s["name"] == "serve:prefill"]
    steps = [s["args"] for s in spans if s["name"] == "serve:decode"]
    assert sorted((a["offset"], a["latent_ring_places"]) for a in chunks) \
        == [(0, 16), (16, RING), (32, RING), (48, RING), (64, RING)]
    # one row at position 77: its window's 8 places, its 78 positions
    assert steps[0]["latent_ring_places"] == WINDOW
    assert steps[0]["latent_positions"] == 77 + 1


# (d) the kernel over rings of latents ------------------------------------

def test_a_row_reads_its_window_of_its_slot_s_ring_where_it_lies():
    """``latent_ring_decode`` against the softmax written out, rows
    before their window fills, inside it, and after the ring wrapped."""
    rng = np.random.default_rng(0)
    n_slots, ring, row, rank, H = 5, 24, 128, 96, 10
    rings = jnp.asarray(rng.normal(size=(2, n_slots, ring, row)),
                        jnp.float32)
    q = jnp.asarray(rng.normal(size=(4, H, row)), jnp.float32)
    slots = jnp.asarray([3, 1, 4, 2], jnp.int32)
    positions = jnp.asarray([2, 7, 30, 100], jnp.int32)
    got = latent_ring_decode(q, rings, 1, slots, positions, window=8, page=4,
                             rank=rank, scale=0.1)
    for b in range(4):
        p = int(positions[b])
        places = [j % ring for j in range(max(0, p - 7), p + 1)]
        keys = rings[1, int(slots[b]), jnp.asarray(places)]
        w = jax.nn.softmax(0.1 * q[b] @ keys.T, axis=-1)
        assert gap(got[b], w @ keys[:, :rank]) < 1e-5, b


# (e) the share of the experts -------------------------------------------

def test_the_eight_shares_and_the_shared_expert_once_are_the_whole_layer():
    """The guide's share test at EVERY offset: the routed parts that the
    eight shares of 2 experts give plus the shared expert counted ONCE
    add up to what the uncut reference gives for the whole mixture."""
    whole = tiny(n_layers=1, layer_types=("mla",), n_dense_layers=0,
                 mhc_streams=1, moe_experts_held=16, moe_expert_offset=0)
    params = seeded(whole)
    lp = {k: v for k, v in params["layers"][0].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    h = tf_lib._rmsnorm(x, lp["mlp_norm"], 1e-5)[0]
    with jax.default_matmul_precision("highest"):
        want = ref.mixture(h, lp["moe"], sizes_of(whole))
        shared = want - ref.mixture(h, lp["moe"], sizes_of(whole),
                                    shared=False)
    total = jnp.zeros_like(want)
    for offset in range(0, 16, 2):
        cfg = tiny(n_layers=1, layer_types=("mla",), n_dense_layers=0,
                   mhc_streams=1, moe_experts_held=2,
                   moe_expert_offset=offset)
        held = {**lp, "moe": {**lp["moe"], **{
            name: lp["moe"][name][offset:offset + 2]
            for name in ("w_gate", "w_up", "w_down", "poly_w", "poly_b")}}}
        y, _ = tf_lib.ffn_block(cfg, held, x)
        total = total + (y[0] - x[0]) - shared
        # and the program's share is the reference's given the same share
        with jax.default_matmul_precision("highest"):
            assert gap(y[0] - x[0], ref.mixture(h, held["moe"],
                                                sizes_of(cfg))) < 3e-5
    assert gap(total + shared, want) < 3e-5


# (f) the stream ----------------------------------------------------------

def test_sinkhorn_s_rows_and_columns_sum_to_one():
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 4, 4))
    m = tf_lib.sinkhorn_knopp(logits, 20)
    assert float(jnp.abs(m.sum(-2) - 1).max()) < 1e-6      # columns: last
    assert float(jnp.abs(m.sum(-1) - 1).max()) < 1e-3
    assert float(m.min()) > 0
    once = tf_lib.sinkhorn_knopp(logits, 1)
    assert float(jnp.abs(once.sum(-1) - 1).max()) > 1e-2
    assert gap(m, ref.sinkhorn_knopp(jnp.exp(logits), 20)) < 1e-5


def test_one_stream_reads_and_adds_as_before():
    cfg = tiny(mhc_streams=1)
    x = jnp.ones((1, 3, 64))
    u, mix = tf_lib.stream_in(cfg, {}, "attn", x)
    assert u is x and mix is None
    assert float(jnp.abs(tf_lib.stream_out(cfg, x, 2 * x, None)
                         - 3 * x).max()) == 0


# (g) what a configuration admits and refuses -----------------------------

def test_the_eleventh_kind_is_a_ring_kind():
    assert len(tf_lib.LAYER_KINDS) == 11 and len(STATE_KINDS) == 11
    assert "mla_sliding" in SLOT_KINDS and "mla_sliding" in RING_KINDS
    cfg = tiny()
    assert cfg.stateful and cfg.mixed and cfg.n_window_layers == 0
    assert cfg.mla_signal_heads == 8
    assert cfg.rotary_of(0) == cfg.rotary_of(2) == tf_lib.Rotary(10000.0)


@pytest.mark.parametrize("kw,match", [
    (dict(attn_window=None), "mla_sliding layers need attn_window"),
    (dict(mla_kv_rank=0), "mla layers need mla_kv_rank"),
    (dict(n_kv_heads=3), "n_kv_heads dividing n_heads"),
    (dict(mla_noise_heads=1), "one noise head a KV head"),
    (dict(n_heads=2, mla_noise_heads=2), "at least one signal head"),
    (dict(mla_head_gate=True), "in place of mla_head_gate"),
    (dict(mhc_streams=0), "mhc_streams 0 rows"),
    (dict(layer_types=("mla_sliding", "mla", "full", "mla", "mla"),
          mla_noise_heads=0, mla_elementwise_gate=False),
     "add a branch to ONE stream"),
    (dict(ffn_activation="gelu"), "unknown ffn_activation"),
    (dict(moe_experts_held=None), "held dispatch's"),
    (dict(layer_types=("mla_sliding", "kda", "mla", "mla", "mla"),
          mla_noise_heads=0, mla_elementwise_gate=False, mhc_streams=1),
     "n_kv_heads = n_heads"),
])
def test_a_configuration_refuses_what_is_not_built(kw, match):
    with pytest.raises(ValueError, match=match):
        cfg = tiny(**kw)
        cfg.moe     # the mixture's own refusals are made when it is asked


def test_what_is_not_built_over_latent_rings_is_refused_by_name(devices):
    cfg = tiny()
    params = seeded(cfg)
    with pytest.raises(NotImplementedError,
                       match=r"prefix_caching \(its mla_sliding layers.*"
                             r"ring of latents.*B9"):
        engine_for(cfg, params, prefix_caching=True)
    with pytest.raises(NotImplementedError, match="speculative decoding"):
        engine_for(cfg, params, draft=(cfg, params), spec_k=2)
    eng = engine_for(cfg, params)
    with pytest.raises(NotImplementedError, match="with mla_sliding layers"):
        eng.submit([1, 2, 3], 2, prefill_only=True)
    with pytest.raises(NotImplementedError, match="inject.*mla_sliding"):
        eng.inject_begin({"block_size": BS})
    rid = eng.submit([1, 2, 3], 12)
    eng.step()
    with pytest.raises(NotImplementedError, match="migrate.*mla_sliding"):
        eng.export_running(rid)
    with pytest.raises(NotImplementedError, match="inject.*of latents"):
        eng._inject_fn()
    with pytest.raises(NotImplementedError, match="verify.*of latents"):
        eng._verify_fn()
    with pytest.raises(NotImplementedError, match="served through"):
        make_train_step(cfg, build_mesh(devices=devices[:1], dp=1))
    with pytest.raises(NotImplementedError, match="served through"):
        tf_lib.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


def test_ungrouped_latent_heads_keep_their_shapes():
    """Kimi's and Ling's mla layers (as many KV heads as query heads, a
    gate a head, one stream) hold what they held."""
    cfg = tiny(n_heads=4, n_kv_heads=4, mla_noise_heads=0,
               mla_elementwise_gate=False, mla_head_gate=True,
               mhc_streams=1, layer_types=("mla",) * 5,
               ffn_activation="swiglu", moe_activation="swiglu")
    lp = init_transformer(cfg, jax.random.PRNGKey(0))["layers"][0]
    assert lp["w_ukv"].shape == (32, 4 * 2 * 16)
    assert lp["wg"].shape == (64, 4) and lp["wo"].shape == (64, 64)
    assert not {"w_lambda", "mhc_attn", "poly_w"} & set(lp)
    assert "poly_w" not in lp["moe"]


# (h) what the check's controls stand for, in float32 ---------------------

@pytest.mark.parametrize("wrong", ref.WRONG)
def test_each_mechanism_miscomputed_is_seen(wrong):
    """Every control of ``benchmark/tools/motif3_tolerance.py`` moves
    the logits of the tiny model by far more than the served model lies
    off the reference (5e-5)."""
    cfg = tiny()
    params = seeded(cfg)
    seq = np.asarray(prompts_of(cfg)[0])
    sizes = sizes_of(cfg)
    want = ref.logits(params, seq, sizes, last=8)
    got = ref.logits(params, seq, sizes, last=8, wrong=wrong)
    assert not gap(got, want) <= 1e-3, gap(got, want)


def test_the_reference_stored_in_bfloat16_is_the_program_s_precision():
    cfg = tiny()
    params = seeded(cfg)
    seq = np.asarray(prompts_of(cfg)[0])
    want = ref.logits(params, seq, sizes_of(cfg), last=8)
    got = ref.logits(params, seq, sizes_of(cfg), last=8, store=jnp.bfloat16)
    assert 1e-4 < gap(got, want) < 0.5


def test_the_two_copies_of_the_reference_are_one_text():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def body(path):
        text = open(os.path.join(root, path)).read()
        return text[text.index('"""', 3):]

    assert body("tests/reference_motif3.py") == body(
        "benchmark/reference_motif3.py")
    assert "horovod_tpu" not in body("tests/reference_motif3.py")
