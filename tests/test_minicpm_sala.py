"""Sparse-attention layers served beside linear-attention layers (ISSUE
50): InfLLM-v2's selection inside paged attention (compressed keys
beside the K/V pages, a query's GQA group reads its best blocks and no
others) and Lightning Attention's decayed linear state a batch slot,
with MiniCPM's three multipliers. At a tiny size (kernel 4, stride 2,
block 8, top-k 4, window 16, dense length 64) with seeded weights,
against ``tests/reference_minicpm_sala.py``: the plain forward of the
same equations over a whole sequence, a position and a query at a time,
no cache."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_minicpm_sala as ref
from horovod_tpu.models import (TransformerConfig, init_transformer,
                                make_train_step)
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import (RECURRENT_KINDS, SLOT_KINDS,
                                        init_kv_cache, state_kinds)

BS, CHUNK, DENSE = 8, 32, 64
TYPES = ("sparse", "lightning", "lightning", "sparse")


def tiny(**kw):
    base = dict(
        vocab_size=128, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
        d_head=8, d_ff=64, max_seq=256, norm_eps=1e-6, rope_theta=10000.0,
        layer_types=TYPES, qk_norm_per_head=True, attn_gate=True,
        sparse_kernel=4, sparse_stride=2, sparse_block=BS, sparse_topk=4,
        sparse_init_blocks=1, sparse_window=16, sparse_dense_len=DENSE,
        embed_multiplier=12.0, residual_multiplier=1.4 / 32 ** 0.5,
        logit_divisor=16.0,
        dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def sizes_of(cfg):
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return ref.sizes_of({"model": model})


def seeded(cfg, seed=0):
    """Seeded weights whose gains are not the ones of an
    initialisation, so that each is seen, and whose q and k gains
    spread the scores (about 3), so that a choice of blocks is one."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("norm"):
            spread = 3 ** 0.5 if name in ("q_norm", "k_norm") else 1.0
            return (a * spread + 0.3 * jax.random.normal(next(keys), a.shape)
                    ).astype(a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(shake, params)


def engine_for(cfg, params, **kw):
    knobs = dict(max_batch=4, max_prompt=160, max_new_tokens=16,
                 block_size=BS, prefill_chunk=CHUNK,
                 prefill_buckets=(8, 16, 32), batch_buckets=(4,),
                 prefix_caching=False)
    knobs.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**knobs))


def serve_logits(cfg, params, prompts, n_decode, chunk=CHUNK, pad_to=BS,
                 batch=None, slots=None, chosen=False):
    """Chunked prefill of each of ``prompts`` into its slot (a chunk
    padded to a multiple of ``pad_to``), then ``n_decode`` greedy steps
    of ALL of them as one batch (padded with null rows to ``batch``).
    Returns for each prompt (the logits at the last position of each
    chunk and of each step, the positions they belong to, every token)
    and the caches; with ``chosen`` also, for each prompt, the pages
    the queries of its sparse layers chose in those same calls
    [n_sparse, positions, Hkv, W]."""
    B = len(prompts)
    batch = batch or B
    slots = slots or list(range(1, B + 1))
    width = -(-(max(map(len, prompts)) + n_decode) // BS) + chunk // BS
    picks = [[] for _ in prompts]
    prefill, resume, decode, _ = decode_lib.mixed_programs(
        cfg, BS, width, 0, head=lambda lg: lg, chosen=chosen)
    prefill, resume, decode = map(jax.jit, (prefill, resume, decode))
    cache = init_kv_cache(cfg, B * width + 1, BS, n_slots=max(slots))
    kc, vc = cache.k, cache.v
    tables = np.zeros((batch, width), np.int32)
    tables[:B] = np.arange(1, B * width + 1).reshape(B, width)
    rows, at, toks = ([[] for _ in prompts], [[] for _ in prompts],
                      [list(p) for p in prompts])
    for b, prompt in enumerate(prompts):
        addr = (jnp.asarray(tables[b]), jnp.int32(slots[b]))
        for off in range(0, len(prompt), chunk):
            n = min(chunk, len(prompt) - off)
            padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
            padded[:n] = prompt[off:off + n]
            if off == 0 and n == len(prompt):
                kc, vc, lg, *picked = prefill(params, kc, vc, padded,
                                              jnp.int32(n), addr)
            else:
                kc, vc, lg, *picked = resume(params, kc, vc, padded,
                                             jnp.int32(off), jnp.int32(n),
                                             addr)
            picks[b] += [np.asarray(p)[:, :n] for p in picked]
            rows[b].append(np.asarray(lg, np.float32))
            at[b].append(off + n - 1)
        toks[b].append(int(rows[b][-1].argmax()))
    pad = batch - B
    for _ in range(n_decode):
        pos = [len(t) - 1 for t in toks]
        kc, vc, lg, *picked = decode(
            params, kc, vc,
            jnp.asarray([t[-1] for t in toks] + [0] * pad, jnp.int32),
            jnp.asarray(pos + [0] * pad, jnp.int32),
            (jnp.asarray(tables), jnp.asarray(slots + [0] * pad, jnp.int32)))
        for b in range(B):
            picks[b] += [np.asarray(p)[:, b:b + 1] for p in picked]
            rows[b].append(np.asarray(lg[b], np.float32))
            at[b].append(pos[b])
            toks[b].append(int(lg[b].argmax()))
    served = [(np.stack(r), a, t) for r, a, t in zip(rows, at, toks)]
    if chosen:
        return served, (kc, vc), [np.concatenate(p, 1) for p in picks]
    return served, (kc, vc)


def gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def compressed_of(cfg, kc, table, n_tokens):
    """The compressed keys a sequence of ``n_tokens`` holds behind
    ``table``, kernel by kernel [n_sparse, kernels, Hkv, Dh]."""
    ck = np.asarray(kc[state_kinds(cfg).index("sparse")][1])
    n = (n_tokens - cfg.sparse_kernel) // cfg.sparse_stride + 1
    flat = ck[:, np.asarray(table)].swapaxes(2, 3)      # [n, W, per, Hkv, Dh]
    return flat.reshape(ck.shape[0], -1, *flat.shape[3:])[:, :n]


PROMPTS = (150, 70, 5)   # past DENSE in chunks, across it, one of 8


def prompts_of(cfg, lens=PROMPTS, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


# (a) prefill, then decode, against the reference's full forward ---------

@pytest.mark.parametrize("dtype,tol,state_tol", [
    (jnp.float32, 2e-5, 2e-5), (jnp.bfloat16, 0.08, 0.08)])
def test_chunks_then_decode_equal_the_reference(dtype, tol, state_tol):
    """Logits at every chunk's end and every decode step of a full
    batch (one row past the dense length, one that crosses it while it
    decodes, one far below it), and the state each sequence leaves in
    its slot, against the reference run once over prompt and outputs.
    bfloat16: the reference reads the same rounded weights in float32;
    what is left is the activations' rounding and, rarely, a block
    chosen otherwise at a near-tie."""
    cfg = tiny(dtype=dtype)
    params = seeded(cfg)
    served, (kc, _) = serve_logits(cfg, params, prompts_of(cfg), 8)
    lightning = state_kinds(cfg).index("lightning")
    for b, (rows, at, toks) in enumerate(served):
        want, states, _ = ref.logits(params, np.asarray(toks[:-1]),
                                     sizes_of(cfg), kept=True)
        assert gap(rows, np.asarray(want)[at]) < tol, (b, dtype)
        left = np.asarray(kc[lightning][:, b + 1])
        assert left.dtype == np.float32
        for a, w in zip(left, np.asarray(states)):
            assert np.linalg.norm(a - w) / np.linalg.norm(w) < state_tol


def test_the_engine_serves_the_reference_s_tokens_and_leaves_its_states():
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_for(cfg, params)
    prompts = prompts_of(cfg)[:2]
    rids = [eng.submit(p, 12) for p in prompts]
    eng.run_until_idle()
    kept, = eng.cache.of("lightning")
    for prompt, rid in zip(prompts, rids):
        res = eng.result(rid)
        want, states, _ = ref.logits(
            params, np.asarray(prompt + res.tokens[:-1]), sizes_of(cfg),
            last=12, kept=True)
        assert res.tokens == np.asarray(want).argmax(-1).tolist()
        assert gap(kept[:, res.slot], states) < 2e-5
    snap = eng.metrics.snapshot()
    assert snap["state_slots_in_use"] == 0
    # the longest sequence's kernels: 150 + 12 - 1 positions written
    assert snap["kv_compressed_max"] == (161 - 4) // 2 + 1
    # queries at 64.. of the prompts, rows at or past 64 of the steps
    assert snap["sparse_selected_queries_total"] == (150 - 64) + (70 - 64)
    # buckets of 8 to 32 positions: no whole tile of the scores' kernel
    assert snap["sparse_select_kernel_queries_total"] == 0
    assert snap["sparse_selected_rows_total"] == 2 * 11


def test_the_spans_say_what_chose_its_blocks(tmp_path):
    import json
    cfg = tiny()
    eng = engine_for(cfg, seeded(cfg))
    eng.submit(prompts_of(cfg)[1], 4)
    eng.run_until_idle()
    path = tmp_path / "trace.json"
    eng.metrics.export_chrome_trace(str(path))
    spans = json.load(open(path))["traceEvents"]
    chunks = [s["args"] for s in spans if s["name"] == "serve:prefill"]
    steps = [s["args"] for s in spans if s["name"] == "serve:decode"]
    assert [c["selected"] for c in chunks] == [0, 0, 6]
    assert [c["select_kernel"] for c in chunks] == [0, 0, 0]
    assert [s["rows_selected"] for s in steps] == [1, 1, 1]


# (b) the scan against the step, the selection's pieces -------------------

@pytest.mark.parametrize("T,block", [(37, 16), (64, 64), (5, 128), (130, 128)])
def test_a_scan_over_t_positions_is_t_steps(T, block):
    rng = np.random.default_rng(0)
    B, H, D = 2, 3, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
               for _ in range(3))
    g = jnp.broadcast_to(-jnp.asarray([0.9, 0.1, 0.01], jnp.float32),
                         (B, T, H))
    s0 = jnp.asarray(rng.normal(size=(B, H, D, D)), jnp.float32)
    o, s = decode_lib.lightning_scan(q, k, v, g, s0, block=block)
    want, state = [], s0
    for t in range(T):
        y, state = decode_lib.lightning_step(q[:, t], k[:, t], v[:, t],
                                             g[:, t], state)
        want.append(y)
    assert gap(o, jnp.stack(want, 1)) < 1e-5
    assert gap(s, state) < 1e-5


def test_a_position_that_decays_and_writes_nothing_leaves_the_state():
    rng = np.random.default_rng(1)
    q, v = (jnp.asarray(rng.normal(size=(1, 24, 2, 8)), jnp.float32)
            for _ in range(2))
    s0 = jnp.asarray(rng.normal(size=(1, 2, 8, 8)), jnp.float32)
    _, s = decode_lib.lightning_scan(q, jnp.zeros_like(q), v,
                                     jnp.zeros((1, 24, 2)), s0)
    assert np.array_equal(np.asarray(s), np.asarray(s0))


def test_the_decays_are_lightning_attention_s_slopes():
    cfg = tiny(n_heads=32)
    got = np.exp(np.asarray(tf_lib.lightning_decay(cfg)))
    assert got.shape == (32,)
    assert np.allclose(got, np.exp(-2.0 ** (-(np.arange(32) + 1) / 4)))


def test_kernel_means_are_the_means_of_the_kernels():
    rows = jnp.asarray(np.random.default_rng(2).normal(size=(20, 2, 4)),
                       jnp.float32)
    got = decode_lib.kernel_means(rows, 2, 3)        # kernel 6, stride 2
    assert got.shape == (8, 2, 4)
    for i in range(8):
        assert np.allclose(got[i], rows[2 * i:2 * i + 6].mean(0), atol=1e-6)


@pytest.mark.parametrize("per,strides", [(4, 2), (4, 1), (2, 3)])
def test_a_block_s_score_is_its_best_kernel_s_summed_over_the_group(
        per, strides):
    rng = np.random.default_rng(3)
    B, C, H, G, Dh, W = 2, 3, 4, 2, 8, 5
    stride, kernel, block = 2, 2 * strides, 2 * per
    q = jnp.asarray(rng.normal(size=(B, C, H, Dh)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(B, W * per, G, Dh)), jnp.float32)
    exist = jnp.asarray(rng.random((B, C, W * per)) < 0.7)
    got = decode_lib.sparse_block_scores(q, ck, exist, per, strides)
    meets = ref.kernels_meeting(W, W * per, kernel, stride, block)
    for b in range(B):
        for c in range(C):
            s = np.einsum("hd,jhd->hj", q[b, c], np.repeat(ck[b], 2, 1)
                          ) / Dh ** 0.5
            s = np.where(exist[b, c], np.exp(s), 0.0)
            s = s / np.maximum(s.sum(-1, keepdims=True), 1e-30)
            want = np.where(meets >= 0, s[:, meets], 0.0).max(-1)
            want = want.reshape(G, 2, W).sum(1)
            assert np.allclose(got[b, c], want, atol=1e-6), (b, c)


def test_the_forced_blocks_come_first_and_none_lies_past_the_query():
    cfg = tiny()
    scores = jnp.asarray(np.random.default_rng(4).random((1, 3, 2, 12)),
                         jnp.float32)
    at = jnp.asarray([[11, 5, 1]], jnp.int32)
    blocks, ok = decode_lib.sparse_choose(scores, at, cfg)
    blocks, ok = np.asarray(blocks), np.asarray(ok)
    for c, own in enumerate([11, 5, 1]):
        for g in range(2):
            picked = set(blocks[0, c, g][ok[0, c, g]].tolist())
            assert {0, own, own - 1} & set(range(own + 1)) <= picked
            assert max(picked) <= own
            assert len(picked) == min(4, own + 1)
            free = sorted(set(range(own + 1)) - {0, own, own - 1},
                          key=lambda b: -scores[0, c, g, b])
            assert picked - {0, own, own - 1} == set(free[:4 - 3])


# (c) chunks and padding -------------------------------------------------

def test_three_chunkings_give_the_same_logits_states_keys_and_choices():
    """The same prompt as chunks of 32, of 16 and of 64 (the last kind
    crosses the dense length inside a chunk): logits at the prompt's
    end and at every step, the lightning states, the compressed keys
    and the chosen blocks agree."""
    cfg = tiny()
    params = seeded(cfg)
    prompt = prompts_of(cfg)[:1]
    runs = [serve_logits(cfg, params, prompt, 4, chunk=c, chosen=True)
            for c in (32, 16, 64)]
    picks = [picked for _, _, (picked,) in runs]
    (rows0, _, toks0), = runs[0][0]
    kc0 = runs[0][1][0]
    lightning = state_kinds(cfg).index("lightning")
    for ((rows, _, toks),), (kc, _), _ in runs[1:]:
        assert toks == toks0
        assert gap(rows[-5:], rows0[-5:]) < 2e-5
        assert gap(kc[lightning][:, 1], kc0[lightning][:, 1]) < 2e-5
        table = np.arange(1, 21)
        assert gap(compressed_of(cfg, kc, table, 153),
                   compressed_of(cfg, kc0, table, 153)) < 2e-5
    w = min(p.shape[-1] for p in picks)
    assert picks[0][:, DENSE:].any()
    for p in picks[1:]:
        assert np.array_equal(p[..., :w], picks[0][..., :w])
        assert not p[..., w:].any()


def test_a_bucket_s_padding_leaves_state_keys_and_choice_as_length_did():
    """A last chunk of 22 padded to 24 and to 32: the state, the
    compressed keys (no kernel past the 22nd position is written) and
    what the next steps choose are the same."""
    cfg = tiny()
    params = seeded(cfg)
    prompt = prompts_of(cfg, (118,))
    (a, (kca, _)), (b, (kcb, _)) = (
        serve_logits(cfg, params, prompt, 6, pad_to=p) for p in (8, 32))
    assert a[0][2] == b[0][2]
    assert gap(a[0][0], b[0][0]) < 2e-5
    lightning = state_kinds(cfg).index("lightning")
    assert gap(kca[lightning][:, 1], kcb[lightning][:, 1]) < 2e-5
    table = np.arange(1, 21)
    assert gap(compressed_of(cfg, kca, table, 123),
               compressed_of(cfg, kcb, table, 123)) < 2e-5
    # a kernel that was not whole at the chunk's end was not written
    # (but into the null block): the steps wrote it, from the pages
    sparse = state_kinds(cfg).index("sparse")
    assert gap(kca[sparse][1][:, 1:], kcb[sparse][1][:, 1:]) < 2e-5
    assert not np.asarray(kcb[sparse][1][:, 17:]).any()


# (d) the chosen blocks ---------------------------------------------------

def test_the_chosen_blocks_are_the_reference_s():
    cfg = tiny()
    params = seeded(cfg)
    ((_, _, toks),), _, (got,) = serve_logits(
        cfg, params, prompts_of(cfg)[:1], 8, chosen=True)
    _, _, want = ref.logits(params, np.asarray(toks[:-1]), sizes_of(cfg),
                            kept=True)
    want = np.asarray(want)
    blocks = want.shape[-1]
    assert np.array_equal(got[..., :blocks], want)
    assert not got[..., blocks:].any() and not got[:, :DENSE].any()
    # four blocks a query and group, the first and the local two forced
    assert (want[:, DENSE:].sum(-1) == 4).all()
    own = np.arange(DENSE, want.shape[1]) // BS
    for t, b in zip(range(DENSE, want.shape[1]), own):
        assert want[:, t, :, [0, b - 1, b]].all()
    # and the fourth is not one block for every query: scores choose it
    free = want[0, DENSE:, 0].copy()
    free[:, 0] = False
    free[np.arange(len(own)), own] = free[np.arange(len(own)), own - 1] = False
    assert len(set(free.argmax(-1).tolist())) > 3


# (e) a decode batch -----------------------------------------------------

def test_a_batch_of_rows_past_and_under_the_dense_length_and_a_padded_row():
    """A row past the dense length, a row under it and a padded row in
    one decode batch: each equals the row served alone, in its logits
    and in the pages its queries chose."""
    cfg = tiny()
    params = seeded(cfg)
    prompts = prompts_of(cfg, (150, 20))
    together, _, picked = serve_logits(cfg, params, prompts, 6, batch=4,
                                       chosen=True)
    assert picked[0][:, 150:].any() and not picked[1].any()
    for b, prompt in enumerate(prompts):
        ((rows, _, toks),), _, (alone,) = serve_logits(
            cfg, params, [prompt], 6, chosen=True)
        assert toks == together[b][2]
        assert gap(together[b][0], rows) < 2e-5
        w = alone.shape[-1]
        assert np.array_equal(picked[b][..., :w], alone)
        assert not picked[b][..., w:].any()


def test_a_reused_slot_starts_from_zero():
    """Two requests through one slot: the second's tokens are those of
    an engine that never served the first."""
    cfg = tiny()
    params = seeded(cfg)
    first, second = prompts_of(cfg, (150, 90), seed=5)
    eng = engine_for(cfg, params, max_batch=1, batch_buckets=(1,))
    a = eng.submit(first, 6)
    b = eng.submit(second, 6)
    eng.run_until_idle()
    assert eng.result(a).slot == eng.result(b).slot
    fresh = engine_for(cfg, params, max_batch=1, batch_buckets=(1,))
    c = fresh.submit(second, 6)
    fresh.run_until_idle()
    assert eng.result(b).tokens == fresh.result(c).tokens


# (f) the cache, the configuration, the refusals -------------------------

def test_a_kind_s_arrays_are_a_tuple_of_its_own_length():
    cfg = tiny()
    cache = init_kv_cache(cfg, 9, BS, n_slots=3)
    assert cache.kinds == ("sparse", "lightning")
    k, ck, v = cache.of("sparse")
    assert k.shape == v.shape == (2, 9, 2, BS, 8)      # a head's positions
    assert ck.shape == (2, 9, 2, BS // 2, 8)           # together in a page
    state, = cache.of("lightning")
    assert state.shape == (2, 4, 4, 8, 8) and state.dtype == jnp.float32
    assert cache.slot_bytes == 2 * 4 * 8 * 8 * 4
    assert "lightning" in RECURRENT_KINDS and "sparse" not in SLOT_KINDS
    with pytest.raises(ValueError, match="sparse_block"):
        init_kv_cache(cfg, 9, 16, n_slots=3)


@pytest.mark.parametrize("kind,arrays", [
    ("full", 2), ("sliding", 2), ("kda", 2), ("mla", 1), ("mamba", 2)])
def test_the_other_kinds_arrays_are_what_they_were(kind, arrays):
    cfg = {
        "full": dict(layer_types=("full", "sliding"), attn_window=8),
        "sliding": dict(layer_types=("full", "sliding"), attn_window=8),
        "kda": dict(layer_types=("kda", "mla"), n_kv_heads=4,
                    mla_kv_rank=16, mla_rope_dim=8),
        "mla": dict(layer_types=("kda", "mla"), n_kv_heads=4,
                    mla_kv_rank=16, mla_rope_dim=8),
        "mamba": dict(layer_types=("mamba", "full"), mamba_dt_rank=4),
    }[kind]
    cfg = TransformerConfig.tiny(dtype=jnp.float32, **cfg)
    cache = init_kv_cache(cfg, 5, BS, n_slots=2, ring=16)
    assert len(cache.of(kind)) == arrays
    assert all(not isinstance(a, tuple) for a in cache.k)


def test_the_configuration_s_sizes_are_checked():
    with pytest.raises(ValueError, match="sparse layers need"):
        tiny(sparse_window=12)
    with pytest.raises(ValueError, match="sparse layers need"):
        tiny(sparse_topk=2)
    with pytest.raises(ValueError, match="lightning"):
        tiny(layer_types=("sparse", "lightnin", "lightning", "sparse"))
    cfg = tiny()
    assert cfg.stateful and cfg.mixed
    assert cfg.rotary_of(0) is None and cfg.rotary_of(1).theta == 10000.0


def test_the_refusals_name_the_new_kinds():
    cfg = tiny()
    params = seeded(cfg)
    with pytest.raises(NotImplementedError, match="lightning"):
        engine_for(cfg, params, prefix_caching=True)
    with pytest.raises(NotImplementedError, match="sparse or lightning"):
        make_train_step(cfg, build_mesh(devices=jax.devices()[:1], dp=1))
    eng = engine_for(cfg, params)
    rid = eng.submit([1, 2, 3], 12)
    eng.step()
    with pytest.raises(NotImplementedError, match="migrate.*lightning"):
        eng.export_running(rid)
    with pytest.raises(NotImplementedError, match="verify.*lightning.*sparse"):
        eng._verify_fn()
    # a stack of sparse layers alone keeps pages only: prefixes share
    alone = tiny(layer_types=("sparse",) * 4)
    engine_for(alone, seeded(alone), prefix_caching=True)


def test_a_sparse_stack_shares_a_prefix_with_its_compressed_keys():
    """All-sparse layers keep pages alone (K, V and the compressed keys
    behind the same tables): a mapped prefix gives the cold request's
    tokens."""
    cfg = tiny(layer_types=("sparse",) * 4)
    params = seeded(cfg)
    doc = prompts_of(cfg, (96,), seed=7)[0]
    asks = [doc + q for q in prompts_of(cfg, (30, 30), seed=8)]
    cold = [engine_for(cfg, params).generate([a], 6)[0] for a in asks]
    eng = engine_for(cfg, params, prefix_caching=True)
    assert [eng.generate([a], 6)[0] for a in asks] == cold
    assert eng.metrics.snapshot()["prefix_hit_tokens"] >= 64


# (g) each mechanism, miscomputed, is seen --------------------------------

#: What a wrong mechanism moves at this size: the logits, or nothing a
#: token's argmax can show (the logits' common factor).
UNSEEN = {"no_logit_divisor"}


@functools.lru_cache(maxsize=None)
def served_past_the_dense_length():
    """One prompt of 150 and six steps through the programs, once for
    every control: (params, rows, their positions, every token)."""
    cfg = tiny()
    params = seeded(cfg)
    (rows, at, toks), = serve_logits(cfg, params, prompts_of(cfg)[:1], 6)[0]
    return params, rows, at, toks


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_each_mechanism_miscomputed_is_seen(wrong):
    """The reference with one mechanism miscomputed is further from the
    served logits than the program's own rounding (2e-7) by orders of
    magnitude, on a prompt past the dense length; the logits' divisor
    moves every logit by one factor, which the check's relative gap
    cannot see and a scale-aware gap does."""
    params, rows, at, toks = served_past_the_dense_length()
    got = ref.logits(params, np.asarray(toks[:-1]), sizes_of(tiny()),
                     wrong=wrong)
    got = np.asarray(got)[at]
    if wrong in UNSEEN:
        assert gap(got / 16.0, rows) < 2e-5
        assert np.abs(got - rows).max() / np.abs(rows).max() > 1
    else:
        assert gap(got, rows) > 5e-4, wrong


def test_the_two_references_are_one():
    def body(path):
        src = open(path).read()
        return src[src.index('"""', 3) + 3:]
    assert body("tests/reference_minicpm_sala.py") == body(
        "benchmark/reference_minicpm_sala.py")
    assert "horovod_tpu" not in body("tests/reference_minicpm_sala.py")


# (g) a chunk's attention through the kernel (ISSUE 51) -------------------

def pages_case(dtype, chunk=128, page=32, width=14, offset=256):
    """A chunk's queries at ``offset``.. over a layer's K and V pages
    behind a shuffled table, each query of a KV head allowed a third of
    the pages, one of them every page and one none."""
    rng = np.random.default_rng(0)
    H, Hkv, Dh = 8, 2, 16
    kp, vp = (jnp.asarray(rng.standard_normal((2, width + 3, Hkv, page, Dh)),
                          dtype) for _ in range(2))
    q = jnp.asarray(2 * rng.standard_normal((1, chunk, H, Dh)), dtype)
    table = jnp.asarray(1 + rng.permutation(width + 2)[:width], jnp.int32)
    allowed = rng.random((chunk, Hkv, width)) < 0.3
    allowed[3], allowed[5] = True, False
    return (q, kp, vp, 1, table,
            offset + jnp.arange(chunk, dtype=jnp.int32), jnp.asarray(allowed))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 0.08)])
def test_a_chunk_s_pages_through_the_kernel_are_the_running_softmax_s(
        dtype, tol):
    """``sparse_attend_pages`` (one call of the flash forward under the
    page mask) against ``sparse_attend_chunk`` (a key block at a time in
    XLA): a chunk that ends in the table's second key tile, a query
    that sees no key as zeros."""
    args = pages_case(dtype, page=32, width=40, offset=900)
    got = decode_lib.sparse_attend_pages(*args)
    want = decode_lib.sparse_attend_chunk(*args)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    assert gap(got, want) < tol
    assert not np.asarray(got[0, 5], np.float32).any()


@pytest.mark.parametrize("chunk,page,width,taken", [
    (1024, 64, 520, True), (2048, 32, 14, True), (1024, 64, 3, True),
    (512, 64, 520, False),    # no whole tile of 1024 queries
    (1024, 8, 160, False),    # a key tile of 128 pages: more than 32 bits
    (1024, 48, 30, False)])   # a key tile that is no whole pages
def test_the_kernel_is_taken_by_the_shapes_alone(chunk, page, width, taken):
    assert decode_lib.sparse_attend_taken(chunk, page, width) is taken


@pytest.mark.parametrize("chunk,kernel", [(1024, True), (96, False)])
def test_a_sparse_chunk_attends_through_the_kernel_where_it_is_whole_tiles(
        monkeypatch, chunk, kernel):
    """``prefill_resume`` over pages of 64: a chunk of 1024 holds the
    Pallas call once a sparse layer and its logits, states and pages
    are the fall-back's within bfloat16's tolerance, past the dense
    length with a choice of 4 of up to 32 pages; a chunk of 96 holds no
    call and IS the fall-back."""
    page = 64 if kernel else 32
    cfg = tiny(dtype=jnp.bfloat16, sparse_block=page, sparse_window=page,
               sparse_dense_len=2 * page, max_seq=2 * chunk + page)
    params = seeded(cfg)
    prompt = np.asarray(prompts_of(cfg, (2 * chunk,))[0], np.int32)
    width = 2 * chunk // page + 1
    assert decode_lib.sparse_attend_taken(chunk, page, width) is kernel

    def served():
        _, resume, _, _ = decode_lib.mixed_programs(
            cfg, page, width, 0, head=lambda lg: lg)
        cache = init_kv_cache(cfg, width + 1, page, n_slots=1)
        kc, vc, rows = cache.k, cache.v, []
        addr = (jnp.arange(1, width + 1, dtype=jnp.int32), jnp.int32(1))
        text = str(jax.make_jaxpr(resume)(
            params, kc, vc, prompt[:chunk], jnp.int32(0), jnp.int32(chunk),
            addr))
        resume = jax.jit(resume)
        for off in range(0, len(prompt), chunk):
            kc, vc, lg = resume(params, kc, vc, prompt[off:off + chunk],
                                jnp.int32(off), jnp.int32(chunk), addr)
            rows.append(np.asarray(lg, np.float32))
        return text.count("hvd_flash_keys_fwd"), np.stack(rows), kc, vc

    calls, rows, kc, vc = served()
    assert calls == (TYPES.count("sparse") if kernel else 0)
    monkeypatch.setattr(decode_lib, "sparse_attend_taken",
                        lambda *shape: False)
    calls, rows0, kc0, vc0 = served()
    assert calls == 0
    tol = 0.08 if kernel else 0.0
    assert gap(rows, rows0) <= tol
    for a, b in zip(jax.tree.leaves((kc, vc)), jax.tree.leaves((kc0, vc0))):
        assert gap(a, b) <= tol


# (h) a chunk's block scores through the kernel (ISSUE 53) ----------------

@pytest.mark.parametrize("chunk,page,width,taken", [
    (1024, 64, 520, True), (512, 64, 520, True), (256, 64, 520, True),
    (2048, 32, 14, True), (1024, 8, 160, True), (1024, 48, 30, True),
    (768, 64, 520, True),     # three tiles of 256
    (128, 64, 520, False), (96, 32, 14, False),   # no whole tile of queries
    (1024, 64, 4096, False)])  # a tile's [256, table] blocks: 16.8 MB
def test_the_scores_kernel_is_taken_by_the_shapes_alone(chunk, page, width,
                                                        taken):
    assert decode_lib.sparse_select_taken(chunk, page, width) is taken


def test_a_sparse_chunk_scores_through_the_kernel_and_chooses_the_same(
        monkeypatch):
    """A prompt of 600 in chunks of 256 (the last 88 of its bucket): each
    chunk program holds ``hvd_sparse_scores`` once a sparse layer, the
    decode program never, and tokens, logits and every query's chosen
    pages are those of the same programs scoring in XLA."""
    cfg = tiny(max_seq=1024)
    params = seeded(cfg)
    prompt = prompts_of(cfg, (600,))

    def served():
        return serve_logits(cfg, params, prompt, 3, chunk=256, pad_to=256,
                            chosen=True)

    def calls(step):
        width = 128
        programs = decode_lib.mixed_programs(cfg, BS, width, 0)
        cache = init_kv_cache(cfg, width + 1, BS, n_slots=1)
        table = jnp.arange(1, width + 1, dtype=jnp.int32)
        if step == "chunk":
            text = jax.make_jaxpr(programs[1])(
                params, cache.k, cache.v, jnp.zeros(256, jnp.int32),
                jnp.int32(256), jnp.int32(256), (table, jnp.int32(1)))
        else:
            text = jax.make_jaxpr(programs[2])(
                params, cache.k, cache.v, jnp.zeros(1, jnp.int32),
                jnp.full((1,), 300, jnp.int32),
                (table[None], jnp.ones(1, jnp.int32)))
        return str(text).count("hvd_sparse_scores")

    assert calls("chunk") == TYPES.count("sparse") and calls("step") == 0
    ((rows, _, toks),), _, (picks,) = served()
    monkeypatch.setattr(decode_lib, "sparse_select_taken",
                        lambda *shape: False)
    assert calls("chunk") == 0
    ((rows0, _, toks0),), _, (picks0,) = served()
    assert toks == toks0
    assert gap(rows, rows0) < 2e-6
    assert picks.shape == picks0.shape and picks[:, DENSE:].any()
    assert (picks == picks0).all()


def test_the_spans_say_which_queries_the_kernel_scored_for(tmp_path):
    """A prompt of 552 in chunks of 256: the two whole chunks score
    through the kernel, the last 40 in their bucket of 64 do not."""
    import json
    cfg = tiny(max_seq=1024)
    eng = engine_for(cfg, seeded(cfg), max_prompt=640, prefill_chunk=256,
                     prefill_buckets=(64, 256))
    eng.submit(prompts_of(cfg, (552,))[0], 2)
    eng.run_until_idle()
    path = tmp_path / "trace.json"
    eng.metrics.export_chrome_trace(str(path))
    chunks = [s["args"] for s in json.load(open(path))["traceEvents"]
              if s["name"] == "serve:prefill"]
    assert [c["selected"] for c in chunks] == [256 - DENSE, 256, 40]
    assert [c["select_kernel"] for c in chunks] == [256 - DENSE, 256, 0]
    snap = eng.metrics.snapshot()
    assert snap["sparse_selected_queries_total"] == 552 - DENSE
    assert snap["sparse_select_kernel_queries_total"] == 512 - DENSE
