"""One-branch layers served (ISSUE 60): Mamba-2 (SSD) mixers over a
float32 state ``[heads, head_dim, d_state]`` and the convolution's rows a
batch slot, a grouped-query attention layer with no positional
embedding, and LatentMoE feed-forwards (ungated relu^2 experts in a
latent, a shared expert of its own width on the full-width input, a
chip's share of the experts). At a tiny size with seeded weights,
against ``tests/reference_nemotron3.py``: the plain forward of the same
equations over a whole sequence, the recurrence a position at a time,
the experts a loop over the held ones, no cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_nemotron3 as ref
from horovod_tpu.models import (TransformerConfig, init_transformer,
                                make_train_step)
from horovod_tpu.models import moe as moe_lib
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import (RECURRENT_KINDS, SLOT_KINDS,
                                        STATE_KINDS, init_kv_cache)

BS, CHUNK = 8, 32
TYPES = ("mamba2", "ffn", "mamba2", "full", "ffn")      # MEM*E


def tiny(**kw):
    base = dict(
        vocab_size=128, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        d_head=16, d_ff=24, max_seq=256, norm_eps=1e-5, layer_types=TYPES,
        one_branch=True, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
        mamba2_head_dim=16, mamba2_groups=2, mamba2_chunk=16,
        n_experts=16, moe_top_k=3, moe_capacity_factor=None,
        moe_norm_topk_prob=True, moe_scoring="sigmoid", moe_route_scale=5.0,
        moe_shared_expert=True, moe_experts_held=4, moe_expert_offset=4,
        moe_activation="relu2", moe_latent=32, moe_shared_d_ff=48,
        dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def sizes_of(cfg):
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return ref.sizes_of({"model": model})


def seeded(cfg, seed=0):
    """Seeded weights whose gains, skip and selection bias are not the
    ones of an initialisation, so that each is seen."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("norm") or name in ("d_skip", "router_bias"):
            return a + (0.3 * jax.random.normal(next(keys), a.shape)
                        ).astype(a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(shake, params)


def engine_for(cfg, params, **kw):
    knobs = dict(max_batch=4, max_prompt=128, max_new_tokens=16,
                 block_size=BS, prefill_chunk=CHUNK,
                 prefill_buckets=(8, 16, 32), batch_buckets=(4,),
                 prefix_caching=False)
    knobs.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**knobs))


def serve_logits(cfg, params, prompts, n_decode, chunk=CHUNK, pad_to=BS):
    """Chunked prefill of each of ``prompts`` into its slot (a chunk
    padded to a multiple of ``pad_to``), then ``n_decode`` greedy steps
    of ALL of them as one full batch. Returns for each prompt (the
    logits at the last position of each chunk and of each step, the
    positions they belong to, every token) and the caches."""
    B = len(prompts)
    width = -(-(max(map(len, prompts)) + n_decode) // BS) + chunk // BS
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
    return [(np.stack(r), a, t) for r, a, t in zip(rows, at, toks)], (kc, vc)


def gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


PROMPTS = (77, 32, 5)        # chunks 32+32+13, one whole, 5 of 8
MAMBA2 = 1                   # its place in a cache of kinds (full, mamba2)


def prompts_of(cfg, lens=PROMPTS, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


# (a) each branch alone against the reference ----------------------------

def one_layer(kind, **kw):
    cfg = tiny(n_layers=1, layer_types=(kind,), **kw)
    return cfg, seeded(cfg), sizes_of(cfg)


@pytest.mark.parametrize("kind", ["mamba2", "full", "ffn"])
def test_each_branch_alone_is_the_reference_s(kind):
    """A model of ONE layer of each kind, a prompt in one chunk: the
    logits at every chunk's end and decode step against the reference's
    one forward (the branch, the final norm and the head: nothing else
    is in it)."""
    cfg, params, sizes = one_layer(kind)
    [(rows, at, toks)], _ = serve_logits(cfg, params,
                                         prompts_of(cfg, (29,)), 5)
    want = ref.logits(params, np.asarray(toks[:-1]), sizes)
    assert gap(rows, np.asarray(want)[at]) < 2e-5
    lp = params["layers"][0]
    assert ("mlp_norm" in lp) == (kind == "ffn")
    assert ("attn_norm" in lp) == (kind != "ffn")


# (b) SSD over chunks against the literal recurrence ---------------------

def ssd_inputs(T, B=2, Hm=8, P=4, G=2, N=16, seed=0):
    rng = np.random.default_rng(seed)
    x, b, c = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((B, T, Hm, P), (B, T, G, N), (B, T, G, N)))
    dt = jnp.asarray(rng.uniform(1e-3, 2.0, (B, T, Hm)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, Hm), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(B, Hm, P, N)), jnp.float32)
    return x, dt, a, b, c, s0


@pytest.mark.parametrize("T,block,carried", [
    (37, 16, True), (64, 16, False), (5, 16, True), (48, 8, True),
    (128, 128, False)])
def test_ssd_over_blocks_is_the_recurrence_a_position_at_a_time(T, block,
                                                                carried):
    """``ssd_scan``'s matrix products over blocks (a ``T`` that is no
    whole number of them padded by positions that step by nothing)
    against ``ssd_step`` T times, from zeros and from a carried state."""
    x, dt, a, b, c, s0 = ssd_inputs(T)
    if not carried:
        s0 = jnp.zeros_like(s0)
    y, s = decode_lib.ssd_scan(x, dt, a, b, c, s0, block)
    want, state = [], s0
    for t in range(T):
        o, state = decode_lib.ssd_step(x[:, t], dt[:, t], a, b[:, t],
                                       c[:, t], state)
        want.append(o)
    assert gap(y, jnp.stack(want, 1)) < 5e-5
    assert gap(s, state) < 5e-5


def test_the_ssd_step_is_the_reference_s_position():
    """``ssd_step`` against the reference's literal recurrence written
    out here: S = exp(dt a) S + dt x (x) B, y = S C, head h reading
    group h // (Hm / G)."""
    x, dt, a, b, c, s0 = ssd_inputs(1, B=3)
    y, s = decode_lib.ssd_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], s0)
    for r in range(3):
        for h in range(8):
            g = h // 4
            want = (np.exp(dt[r, 0, h] * a[h]) * np.asarray(s0[r, h])
                    + dt[r, 0, h] * np.outer(x[r, 0, h], b[r, 0, g]))
            assert gap(s[r, h], want) < 1e-5
            assert gap(y[r, h], want @ np.asarray(c[r, 0, g])) < 1e-5


def test_a_position_that_steps_by_nothing_leaves_the_state():
    x, _, a, b, c, s0 = ssd_inputs(24, B=1)
    _, s = decode_lib.ssd_scan(x, jnp.zeros((1, 24, 8)), a, b, c, s0, 16)
    assert np.array_equal(np.asarray(s), np.asarray(s0))


# (c) chunks, resumed chunks, decode: the reference's one forward --------

@pytest.mark.parametrize("dtype,tol,state_tol", [
    (jnp.float32, 3e-5, 3e-5), (jnp.bfloat16, 0.08, 0.08)])
def test_chunks_then_decode_equal_the_reference(dtype, tol, state_tol):
    """Logits at every chunk's end and every decode step of a full
    batch, and the state each sequence leaves in its slot, against the
    reference run once over prompt and outputs (a prompt in one chunk,
    one in resumed chunks that carry state and rows, one shorter than a
    block). bfloat16: the reference reads the same rounded weights in
    float32, so what is left is the activations' rounding and the
    routers' near-ties it tips, which at 64 channels is percents (the
    chip's check reads it at the published widths)."""
    cfg = tiny(dtype=dtype)
    params = seeded(cfg)
    sizes = sizes_of(cfg)
    served, (kc, _) = serve_logits(cfg, params, prompts_of(cfg), 8)
    for b, (rows, at, toks) in enumerate(served):
        want, states = ref.logits(params, np.asarray(toks[:-1]), sizes,
                                  states=True)
        if dtype == jnp.float32:
            assert gap(rows, np.asarray(want)[at]) < tol, b
        else:       # a tipped router moves a row: most rows are held
            gaps = [gap(r, w) for r, w in zip(rows, np.asarray(want)[at])]
            assert np.median(gaps) < tol, (b, gaps)
        left = np.asarray(kc[MAMBA2][:, b + 1])
        assert left.dtype == np.float32
        # the first layer's state lies before every router
        w = np.asarray(states)
        assert np.linalg.norm(left[0] - w[0]) / np.linalg.norm(w[0]) < (
            state_tol)
        if dtype == jnp.float32:
            assert gap(left, w) < state_tol


def test_the_engine_serves_the_reference_s_tokens_and_leaves_its_states():
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_for(cfg, params)
    prompts = prompts_of(cfg)[:2]
    rids = [eng.submit(p, 12) for p in prompts]
    eng.step()
    assert eng.metrics.snapshot()["state_bytes"] > 0
    eng.run_until_idle()
    kept = eng.cache.of("mamba2")[0]
    for prompt, rid in zip(prompts, rids):
        res = eng.result(rid)
        want, states = ref.logits(
            params, np.asarray(prompt + res.tokens[:-1]), sizes_of(cfg),
            last=12, states=True)
        assert res.tokens == np.asarray(want).argmax(-1).tolist()
        assert gap(kept[:, res.slot], states) < 3e-5
    snap = eng.metrics.snapshot()
    assert snap["state_slots_in_use"] == 0


def test_a_prompt_in_padded_chunks_is_the_prompt_whole():
    """77 tokens as 32 + 32 + 13 (the last padded to 32, so that 19
    padded positions follow it) against the same 77 as one chunk padded
    to 80: the logits after it and through 6 decode steps, and the state
    and the convolution's rows left in the slot, which the padding must
    not have touched."""
    cfg = tiny()
    params = seeded(cfg)
    prompt = prompts_of(cfg)[:1]
    [(a, _, ta)], (ka, va) = serve_logits(cfg, params, prompt, 6, pad_to=32)
    [(b, _, tb)], (kb, vb) = serve_logits(cfg, params, prompt, 6, chunk=96)
    assert ta == tb
    assert gap(a[-7:], b[-7:]) < 3e-5
    assert gap(ka[MAMBA2][:, 1], kb[MAMBA2][:, 1]) < 3e-5          # the state
    assert gap(va[MAMBA2][:, 1], vb[MAMBA2][:, 1]) < 3e-5          # the rows


# (d) continuous batching -----------------------------------------------

def test_a_slot_starts_from_zero_and_neighbours_do_not_matter():
    """Six requests through three slots of four (slots in use below
    ``max_batch``, every slot freed and used again): each one's tokens
    are what it gets alone in a fresh engine."""
    cfg = tiny()
    params = seeded(cfg)
    prompts = prompts_of(cfg, (40, 9, 77, 32, 5, 64), seed=3)
    alone = []
    for p in prompts:
        eng = engine_for(cfg, params)
        rid = eng.submit(p, 10)
        eng.run_until_idle()
        alone.append(eng.result(rid).tokens)
    eng = engine_for(cfg, params, max_batch=3, batch_buckets=(4,))
    rids = [eng.submit(p, 10) for p in prompts]
    seen = set()
    while eng.pending:
        eng.step()
        seen.add(eng.metrics.state_slots_in_use)
    results = [eng.result(r) for r in rids]
    assert [r.tokens for r in results] == alone
    assert max(seen) == 3 and len({r.slot for r in results}) <= 3


def test_the_spans_say_what_a_call_scanned_stepped_and_attended(tmp_path):
    import json
    cfg = tiny()
    eng = engine_for(cfg, seeded(cfg))
    eng.submit(prompts_of(cfg)[0], 4, trace_id=1)   # 77: 32 + 32 + 13 of 16
    eng.run_until_idle()
    path = tmp_path / "spans.json"
    eng.metrics.export_chrome_trace(str(path))
    spans = [e for e in json.load(open(path))["traceEvents"]
             if e.get("ph") == "X"]
    chunks = [s["args"] for s in spans if s["name"] == "serve:prefill"]
    steps = [s["args"] for s in spans if s["name"] == "serve:decode"]
    assert sorted((a["n_tokens"], a["scanned"]) for a in chunks) == [
        (13, 16), (32, 32), (32, 32)]
    # every slot's state is stepped where it lies, the null slot's too
    assert steps and all(a["slots_stepped"] == 5 for a in steps)
    # one row at position 77: the keys it sees, itself too
    assert steps[0]["attended"] == 77 + 1


# (e) the share of the experts -------------------------------------------

def test_the_four_shares_and_the_shared_expert_once_are_the_whole_layer():
    """The guide's share test: the routed parts that the four shares
    give (offsets 0, 1/4, 1/2, 3/4 of the experts, each through the
    latent pair) plus the shared expert counted ONCE add up to what the
    uncut reference gives for the whole mixture branch."""
    whole = tiny(n_layers=1, layer_types=("ffn",), moe_experts_held=16,
                 moe_expert_offset=0)
    params = seeded(whole)
    lp = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    want = ref.latent_moe(x[0], lp, sizes_of(whole)) - x[0]
    shared = (ref.latent_moe(x[0], lp, sizes_of(whole))
              - ref.latent_moe(x[0], lp, sizes_of(whole), shared=False))
    total = jnp.zeros_like(want)
    for offset in (0, 4, 8, 12):
        cfg = tiny(n_layers=1, layer_types=("ffn",), moe_experts_held=4,
                   moe_expert_offset=offset)
        held = {**lp, "moe": {
            **lp["moe"],
            "w_up": lp["moe"]["w_up"][offset:offset + 4],
            "w_down": lp["moe"]["w_down"][offset:offset + 4]}}
        y, _ = tf_lib.ffn_block(cfg, held, x)
        total = total + (y[0] - x[0]) - shared
        # and the program's share is the reference's given the same share
        assert gap(y[0], ref.latent_moe(x[0], held, sizes_of(cfg))) < 2e-5
    assert gap(total + shared, want) < 2e-5


def test_at_22_a_token_no_held_pair_is_left_out():
    """A decode row's 22 choices of 512, 128 held: the dispatch's sort
    and group sizes run every pair on a held expert, where a call is too
    small to compact (a decode step, a chunk) and where its bound
    engages (``held_row_bound`` at 22 a token)."""
    cfg = moe_lib.MoEConfig(n_experts=512, top_k=22, capacity_factor=None,
                            scoring="sigmoid", experts_held=128,
                            expert_offset=128, activation="relu2",
                            latent=32, shared_d_ff=48, shared_expert=True)
    assert moe_lib.held_row_bound(128 * 22, cfg) is None       # a step
    assert moe_lib.held_row_bound(1024 * 22, cfg) is None      # a chunk
    assert moe_lib.held_row_bound(4096 * 22, cfg) == 33792
    rng = np.random.default_rng(0)
    for n in (128, 4096):
        scores = jnp.asarray(rng.normal(size=(n, 512)), jnp.float32)
        _, _, experts = moe_lib._top_k_gates(scores, cfg, jnp.zeros(512))
        assert experts.shape == (n, 22)
        assert float(moe_lib.held_pairs_not_run(experts, cfg)) == 0.0
        _, held = moe_lib.held_pairs(experts, cfg)
        assert 0.15 < float(held.mean()) < 0.35
    counts = jnp.full((5, 128), 4096 * 22 / 512)
    assert moe_lib.compaction_summary(counts, 4096 * 22, cfg) == {
        "moe_compact_calls_share": 1.0,
        "moe_held_pairs_over_bound_max": pytest.approx(22528 / 33792)}


def test_the_share_report_counts_run_pairs_and_touched_experts():
    cfg = tiny()
    params = seeded(cfg)
    toks = np.random.default_rng(0).integers(0, 128, (4, 1))
    report = decode_lib.moe_share_report(params, toks, cfg, BS)
    assert report["moe_dispatch_dropped_token_frac"] == 0
    got = moe_lib.moe_metrics()
    assert got["moe_held_pairs_not_run"] == 0
    assert got["moe_held_pairs_run"] == pytest.approx(
        report["moe_local_pair_share"] * 4 * 3)
    assert 0 < got["moe_held_experts_touched_mean"] <= 4
    snap = engine_for(cfg, params).metrics.snapshot()
    assert snap["moe_held_pairs_run"] == got["moe_held_pairs_run"]


# (f) the configuration ---------------------------------------------------

def test_a_configuration_admits_one_branch_layers():
    cfg = tiny()
    assert cfg.stateful and cfg.mixed and cfg.one_branch
    assert [cfg.n_layers_of(k) for k in ("mamba2", "full", "ffn")] == [
        2, 1, 2]
    assert cfg.mamba2_heads == 8 and cfg.mamba2_conv_width == 128 + 64
    assert all(cfg.rotary_of(i) is None for i in range(5))
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    lp = params["layers"][0]
    assert set(lp) == {"attn_norm", "w_in", "w_dt", "conv_w", "conv_b",
                       "dt_bias", "a_log", "d_skip", "o_norm", "w_out"}
    assert lp["w_in"].shape == (64, 128 + 192) and lp["w_dt"].shape == (64, 8)
    for name in ("a_log", "dt_bias", "d_skip"):
        assert lp[name].shape == (8,) and lp[name].dtype == jnp.float32
    step = jax.nn.softplus(lp["dt_bias"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 1e-1 + 1e-6
    rate = jnp.exp(lp["a_log"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    assert set(params["layers"][3]) == {"attn_norm", "wq", "wk", "wv", "wo"}
    assert params["layers"][3]["wk"].shape == (64, 32)    # 2 KV heads of 16
    moe = params["layers"][1]["moe"]
    assert set(params["layers"][1]) == {"mlp_norm", "moe"}
    assert set(moe) == {"router", "router_bias", "latent_down", "latent_up",
                        "w_up", "w_down", "shared_up", "shared_down"}
    assert moe["router"].shape == (64, 16)              # the FULL width
    assert moe["w_up"].shape == (4, 32, 24)             # held, in the latent
    assert moe["w_down"].shape == (4, 24, 32)
    assert moe["shared_up"].shape == (64, 48)
    specs = tf_lib.param_specs(cfg)
    assert (jax.tree.structure(specs, is_leaf=lambda x: isinstance(
        x, jax.sharding.PartitionSpec)) == jax.tree.structure(params))
    cache = init_kv_cache(cfg, 9, BS, n_slots=4)
    assert cache.kinds == ("full", "mamba2")
    state, rows = cache.of("mamba2")
    assert state.shape == (2, 5, 8, 16, 16) and state.dtype == jnp.float32
    assert rows.shape == (2, 5, 3 * 192)
    assert cache.of("full")[0].shape == (1, 9, BS, 2, 16)
    assert cache.slot_bytes == 2 * (8 * 16 * 16 * 4 + 3 * 192 * 4)
    assert "mamba2" in RECURRENT_KINDS and "mamba2" in SLOT_KINDS
    assert STATE_KINDS[-2] == "mamba2" and "ffn" not in STATE_KINDS


@pytest.mark.parametrize("kw,match", [
    (dict(one_branch=False), "layer_types needs"),
    (dict(layer_types=None), "one_branch says"),
    (dict(mamba2_head_dim=48), "whole heads of mamba2_head_dim"),
    (dict(mamba2_groups=3), "mamba2_groups equal groups"),
    (dict(attn_gate=True), "no attn_gate"),
    (dict(sandwich_norm=True), "no attn_gate"),
    (dict(layer_types=("full", "ffn", "full", "full", "ffn"),
          sandwich_norm=True), "one_branch says"),
    (dict(moe_experts_held=None), "held dispatch's"),
    (dict(moe_activation="gelu"), "unknown MoE activation"),
    (dict(layer_types=("mamba2", "ffn", "mamba", "full", "ffn")),
     "mamba_dt_rank"),
])
def test_a_configuration_refuses_what_is_not_built(kw, match):
    with pytest.raises(ValueError, match=match):
        cfg = tiny(**kw)
        cfg.moe     # the mixture's own refusals are made when it is asked


# (g) what is not built is refused by name -------------------------------

def test_what_is_not_built_over_mamba2_is_refused_by_name(devices):
    cfg = tiny()
    params = seeded(cfg)
    with pytest.raises(NotImplementedError,
                       match=r"prefix_caching \(its mamba2 layers.*B14"):
        engine_for(cfg, params, prefix_caching=True)
    with pytest.raises(NotImplementedError, match="speculative decoding"):
        engine_for(cfg, params, draft=(cfg, params), spec_k=2)
    eng = engine_for(cfg, params)
    with pytest.raises(NotImplementedError, match="with mamba2 layers"):
        eng.submit([1, 2, 3], 2, prefill_only=True)
    with pytest.raises(NotImplementedError, match="inject.*mamba2"):
        eng.inject_begin({"block_size": BS})
    rid = eng.submit([1, 2, 3], 12)
    eng.step()
    with pytest.raises(NotImplementedError, match="migrate.*mamba2"):
        eng.export_running(rid)
    with pytest.raises(NotImplementedError, match="inject.*mamba2 layer"):
        eng._inject_fn()
    with pytest.raises(NotImplementedError, match="verify.*mamba2 layer"):
        eng._verify_fn()
    with pytest.raises(NotImplementedError, match="mamba2.*one_branch.*B14"):
        make_train_step(cfg, build_mesh(devices=devices[:1], dp=1))
    with pytest.raises(NotImplementedError, match="layers of one branch"):
        tf_lib.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    # a stack of one-branch layers with nothing kept by slot is the serve
    # programs' alone too
    plain = tiny(n_layers=2, layer_types=("full", "ffn"))
    with pytest.raises(NotImplementedError, match="layers of one branch"):
        tf_lib.forward(init_transformer(plain, jax.random.PRNGKey(0)),
                       jnp.zeros((1, 8), jnp.int32), plain)


# (h) what the check's controls stand for, in float32 ---------------------

@pytest.mark.parametrize("wrong", ref.WRONG)
def test_each_mechanism_miscomputed_is_seen(wrong):
    """Every control of ``benchmark/tools/nemotron3_tolerance.py`` moves
    the logits or a state of the tiny model by far more than the served
    model lies off the reference (3e-5)."""
    cfg = tiny(n_experts=16, moe_top_k=3)
    params = seeded(cfg)
    seq = np.asarray(prompts_of(cfg)[0])
    sizes = sizes_of(cfg)
    want, state = ref.logits(params, seq, sizes, last=8, states=True)
    got, theirs = ref.logits(params, seq, sizes, last=8, states=True,
                             wrong=wrong)
    moved = max(gap(got, want), gap(theirs, state))
    assert not moved <= (1e-3 if wrong != "state_in_bf16" else 2e-4), moved


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

    assert body("tests/reference_nemotron3.py") == body(
        "benchmark/reference_nemotron3.py")
    assert "horovod_tpu" not in body("tests/reference_nemotron3.py")
