"""EVA attention served beside a float32 stream, unit-offset norms and a
head of several prediction rows (ISSUE 56): an ``eva`` layer attends, in
ONE softmax, the keys of its own block-aligned window exactly and one
summary a chunk of every window that has closed; between calls it keeps
BOTH halves, the open window's K and V rows by batch slot and the
summaries in pages behind the block tables. At a tiny size (a window of
32 in chunks of 4, pages of 8 positions, 4 heads of 16, 2 rows of head
over 40 ids) with seeded weights, against ``tests/reference_evabyte.py``:
the plain forward of the same equations over a whole sequence, no
cache."""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_evabyte as ref
from horovod_tpu.models import (TransformerConfig, init_transformer,
                                make_train_step)
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import (RECURRENT_KINDS, SLOT_KINDS,
                                        STATE_KINDS, init_kv_cache)

BS, CHUNK, W, CH = 8, 16, 32, 4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny(types=("eva", "eva", "eva"), **kw):
    base = dict(
        vocab_size=40, d_model=64, n_layers=len(types), n_heads=4,
        n_kv_heads=4, d_ff=96, max_seq=256, norm_eps=1e-5,
        layer_types=types, rope_theta=1e5, eva_window=W, eva_chunk=CH,
        norm_unit_offset=True, stream_fp32=True, head_rows=2,
        dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def sizes_of(cfg):
    return ref.sizes_of({"model": {f.name: getattr(cfg, f.name)
                                   for f in dataclasses.fields(cfg)}})


def seeded(cfg, seed=0):
    """Seeded weights whose stored gains are not an initialisation's
    zeros, so that ``1 + g`` is seen."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def shake(path, a):
        if path[-1].key.endswith("norm"):
            return a + (0.3 * jax.random.normal(next(keys), a.shape)
                        ).astype(a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(shake, params)


def engine_for(cfg, params, **kw):
    knobs = dict(max_batch=4, max_prompt=160, max_new_tokens=40,
                 block_size=BS, prefill_chunk=CHUNK,
                 prefill_buckets=(8, 16, 32), batch_buckets=(4,),
                 prefix_caching=False)
    knobs.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**knobs))


def serve_logits(cfg, params, prompts, n_decode, chunk=CHUNK, pad_to=BS,
                 dirty=False, slots=None, caches=None, pad_rows=0):
    """Chunked prefill of each of ``prompts`` into its slot (chunks of
    ``chunk`` cut at the windows' ends, as the engine cuts them; each
    padded to a multiple of ``pad_to``; a whole prompt that fits one
    chunk through ``prefill``), then ``n_decode`` greedy steps of ALL
    of them as one batch with ``pad_rows`` padded rows behind them.
    ``dirty``: every array of the cache holds ones first, as sequences
    that left them would. Returns for each prompt (the logits at the
    last position of each chunk and of each step ``[n, rows, V]``, the
    positions they belong to, every token) and the caches."""
    B = len(prompts)
    slots = slots or list(range(1, B + 1))
    width = -(-(max(map(len, prompts)) + n_decode) // BS) + 32 // BS
    prefill, resume, decode, _ = decode_lib.mixed_programs(
        cfg, BS, width, 0, head=lambda lg: lg)
    prefill, resume, decode = map(jax.jit, (prefill, resume, decode))
    n_slots = max(slots)
    if caches is None:
        cache = init_kv_cache(cfg, n_slots * width + 1, BS, n_slots=n_slots)
        kc, vc = cache.k, cache.v
        if dirty:
            kc, vc = jax.tree.map(jnp.ones_like, (kc, vc))
    else:
        kc, vc = caches
    tables = np.stack([np.arange(1 + (s - 1) * width, 1 + s * width,
                                 dtype=np.int32) for s in slots])
    rows, at, toks = ([[] for _ in prompts], [[] for _ in prompts],
                      [list(p) for p in prompts])
    for b, prompt in enumerate(prompts):
        addr = (jnp.asarray(tables[b]), jnp.int32(slots[b]))
        off = 0
        while off < len(prompt):
            n = min(chunk, len(prompt) - off, W - off % W)
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
            off += n
        toks[b].append(int(rows[b][-1][0].argmax()))
    pad = np.zeros(pad_rows, np.int32)
    for _ in range(n_decode):
        pos = [len(t) - 1 for t in toks]
        kc, vc, lg = decode(
            params, kc, vc,
            jnp.asarray(np.r_[[t[-1] for t in toks], pad], jnp.int32),
            jnp.asarray(np.r_[pos, pad], jnp.int32),
            (jnp.asarray(np.r_[tables, np.zeros((pad_rows, width),
                                                np.int32)]),
             jnp.asarray(np.r_[slots, pad], jnp.int32)))
        for b in range(B):
            rows[b].append(np.asarray(lg[b], np.float32))
            at[b].append(pos[b])
            toks[b].append(int(lg[b][0].argmax()))
    return ([(np.stack(r), a, t) for r, a, t in zip(rows, at, toks)],
            (kc, vc), tables)


def held(cfg, caches, table, slot, n):
    """What a sequence of ``n`` positions holds in every eva layer, as
    the reference returns it: the live rows of its open window (K, V)
    and the summaries of its whole chunks (k~, v~) out of its pages."""
    kinds = init_kv_cache(cfg, 2, BS, n_slots=1).kinds
    (kr, ks), (vr, vs) = (c[kinds.index("eva")] for c in caches)
    live = (n - 1) % W + 1
    m = n // CH
    out = []
    for c in range(kr.shape[0]):
        out.append(tuple(np.asarray(a, np.float32) for a in (
            kr[c, slot, :live], vr[c, slot, :live],
            ks[c, table].reshape(-1, *ks.shape[3:])[:m],
            vs[c, table].reshape(-1, *vs.shape[3:])[:m])))
    return out


def gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def kept_gap(got, want):
    return max(gap(g, w) for layer, theirs in zip(got, want)
               for g, w in zip(layer, theirs))


def prompts_of(cfg, lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]


# (a), (b) chunks, then decode, against the reference's one pass ---------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 0.06)])
def test_chunks_then_decode_through_three_windows_equal_the_reference(
        dtype, tol):
    """107 prompt tokens as chunks of 16 cut at the windows' ends (the
    last, 11 tokens, padded to 16, its last group of 4 partial: closed
    later by a decode step from its rows), then 30 decode steps that
    write 107 .. 136: across the boundary 127 -> 128, where the FOURTH
    window closes. Both rows of logits at every chunk's end and every
    step, and what the sequence leaves (the open window's live rows and
    every whole chunk's summaries out of its pages), against the
    reference's one forward pass. bfloat16: the reference reads the same
    rounded weights in float32."""
    cfg = tiny(dtype=dtype)
    params = seeded(cfg)
    [(rows, at, toks)], caches, tables = serve_logits(
        cfg, params, prompts_of(cfg, (107,)), 30)
    assert at[:7] == [15, 31, 47, 63, 79, 95, 106] and at[-1] == 136
    assert 127 in at and 128 in at
    want, kept = ref.forward(params, np.asarray(toks[:-1]), sizes_of(cfg),
                             kept=True)
    assert rows.shape[1:] == (2, 40)
    assert gap(rows, np.asarray(want)[at]) < tol
    assert kept_gap(held(cfg, caches, tables[0], 1, 137), kept) < tol


def test_a_whole_prompt_in_one_call_then_decode_over_a_boundary():
    """A prompt of 30 through the monolithic ``prefill`` (it lies in
    one window and attends over itself), then 8 steps across 31 -> 32:
    the first window closes at a decode step and the next query sees
    itself and 8 summaries."""
    cfg = tiny()
    params = seeded(cfg)
    [(rows, at, toks)], caches, tables = serve_logits(
        cfg, params, prompts_of(cfg, (30,)), 8, chunk=32)
    assert at == [29, 30, 31, 32, 33, 34, 35, 36, 37]
    want, kept = ref.forward(params, np.asarray(toks[:-1]), sizes_of(cfg),
                             kept=True)
    assert gap(rows, np.asarray(want)[at]) < 2e-5
    assert kept_gap(held(cfg, caches, tables[0], 1, 38), kept) < 2e-5


def test_a_padded_chunk_writes_no_row_and_no_summary_past_its_length():
    """Right after a prompt of 43 (its last chunk 11 of a bucket of 16,
    cut at 32): rows 0 .. 10 of the slot are the second window's, the
    rows behind them are what the first window left there (the 5
    padded positions wrote nothing), the summaries of the two whole
    groups of the chunk are the reference's and the partial group's and
    the padding's places hold zeros."""
    cfg = tiny()
    params = seeded(cfg)
    prompt = prompts_of(cfg, (43,))
    [_], caches, tables = serve_logits(cfg, params, prompt, 0, dirty=True,
                                       pad_to=16)
    _, kept = ref.forward(params, np.asarray(prompt[0]), sizes_of(cfg),
                          kept=True)
    assert kept_gap(held(cfg, caches, tables[0], 1, 43), kept) < 2e-5
    kinds = init_kv_cache(cfg, 2, BS, n_slots=1).kinds
    (kr, ks), _ = (c[kinds.index("eva")] for c in caches)
    [_], before, _ = serve_logits(cfg, params, [prompt[0][:32]], 0,
                                  dirty=True)
    assert float(jnp.abs(
        kr[:, 1, 11:] - before[0][kinds.index("eva")][0][:, 1, 11:]
    ).max()) == 0.0
    pages = ks[0, tables[0]].reshape(-1, *ks.shape[3:])
    assert float(jnp.abs(pages[10:12]).max()) == 0.0     # 40 .. 47


# (c) a slot that a longer sequence left ---------------------------------

def test_a_slot_and_pages_a_longer_sequence_left_are_not_read():
    """A sequence of 90 + 12 through slot 1 and its pages, then one of
    37 + 12 through the SAME slot and the same pages: the second is what
    it is over a cache of zeros and over one of ones."""
    cfg = tiny()
    params = seeded(cfg)
    long, short = prompts_of(cfg, (90, 37))
    _, caches, _ = serve_logits(cfg, params, [long], 12)
    [(again, at, _)], _, _ = serve_logits(cfg, params, [short], 12,
                                          caches=caches)
    [(alone, at2, _)], _, _ = serve_logits(cfg, params, [short], 12)
    [(ones, _, _)], _, _ = serve_logits(cfg, params, [short], 12, dirty=True)
    assert at == at2
    assert gap(again, alone) < 1e-6 and gap(ones, alone) < 1e-6


# (d) a batch whose rows stand in different windows ----------------------

def test_a_batch_of_rows_in_different_windows_and_padded_rows():
    """Four sequences of 107, 33, 64 and 5 (in their fourth, second,
    third and first window; fills of 12, 2, 1 and 6 rows) decode 30
    steps as ONE batch with two padded rows behind them (token 0,
    position 0, the null slot and an all-null table), over a cache of
    ones: each is the reference's, and the null slot's garbage reaches
    nobody."""
    cfg = tiny()
    params = seeded(cfg)
    lens = (107, 33, 64, 5)
    served, caches, tables = serve_logits(
        cfg, params, prompts_of(cfg, lens), 30, dirty=True, pad_rows=2)
    sizes = sizes_of(cfg)
    for b, (rows, at, toks) in enumerate(served):
        want, kept = ref.forward(params, np.asarray(toks[:-1]), sizes,
                                 kept=True)
        assert gap(rows, np.asarray(want)[at]) < 2e-5, b
        assert kept_gap(held(cfg, caches, tables[b], b + 1, lens[b] + 30),
                        kept) < 2e-5, b


# (e) eva beside full layers ----------------------------------------------

def test_eva_layers_beside_full_layers_in_one_stack():
    """``eva, full, eva``: the full layer keeps K and V pages behind the
    same tables (pages of 8 positions, a summary page 2 rows) and
    attends everything, through the same programs; the engine serves
    the reference's tokens."""
    cfg = tiny(("eva", "full", "eva"))
    params = seeded(cfg)
    [(rows, at, toks)], _, _ = serve_logits(
        cfg, params, prompts_of(cfg, (70,)), 30)
    want = ref.forward(params, np.asarray(toks[:-1]), sizes_of(cfg))
    assert gap(rows, np.asarray(want)[at]) < 2e-5
    eng = engine_for(cfg, params)
    assert eng.cache.kinds == ("full", "eva")
    prompts = prompts_of(cfg, (70, 33, 5, 96, 41))
    rids = [eng.submit(p, 36) for p in prompts]
    eng.run_until_idle()
    for p, rid in zip(prompts, rids):
        res = eng.result(rid)
        assert res.status == "ok" and len(res.tokens) == 36
        want = ref.forward(params, np.asarray(p + res.tokens[:-1]),
                           sizes_of(cfg), last=36)
        assert np.asarray(want)[:, 0].argmax(-1).tolist() == res.tokens


# the engine: chunks cut at the windows' ends, gauges and counters -------

def test_the_engine_cuts_chunks_at_windows_and_counts_what_it_holds(
        tmp_path):
    """A prefill chunk of 24 over windows of 32: the engine cuts 107 as
    24, 8 | 16, ... (no chunk crosses a window's end), the
    spans say what each decode call's eva layers had to read, and the
    metrics what they held: slots, bytes, summary pages, windows
    closed."""
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_for(cfg, params, prefill_chunk=24,
                     prefill_buckets=(8, 16, 24, 32))
    prompt = prompts_of(cfg, (107,))[0]
    rid = eng.submit(prompt, 30)
    eng.step()
    snap = eng.metrics.snapshot()
    assert snap["state_slots_in_use"] == 1
    assert snap["state_bytes"] == eng.cache.slot_bytes == (
        3 * 2 * W * 4 * 16 * 4)
    eng.run_until_idle()
    res = eng.result(rid)
    want = ref.forward(params, np.asarray(prompt + res.tokens[:-1]),
                       sizes_of(cfg), last=30)
    assert np.asarray(want)[:, 0].argmax(-1).tolist() == res.tokens
    path = tmp_path / "spans.json"
    eng.metrics.export_chrome_trace(str(path))
    spans = [e for e in json.load(open(path))["traceEvents"]
             if e.get("ph") == "X"]
    chunks = [s["args"] for s in spans if s["name"] == "serve:prefill"]
    cuts = [(a["offset"], a["n_tokens"]) for a in chunks]
    assert cuts[:2] == [(0, 24), (24, 8)] and sum(n for _, n in cuts) == 107
    assert all(at // W == (at + n - 1) // W for at, n in cuts), cuts
    steps = [s["args"] for s in spans if s["name"] == "serve:decode"]
    # the first step writes position 107: 12 live rows, 3 closed windows
    assert (steps[0]["eva_rows"], steps[0]["eva_summaries"]) == (12, 24)
    at_128 = steps[128 - 107]
    assert (at_128["eva_rows"], at_128["eva_summaries"]) == (1, 32)
    snap = eng.metrics.snapshot()
    # three windows closed by chunks, the fourth by the step at 127
    assert snap["eva_windows_closed_total"] == 4
    assert snap["eva_summary_pages_max"] == 4 * (W // BS)
    assert snap["state_slots_in_use"] == 0


def test_six_requests_through_three_slots_are_each_what_they_are_alone():
    cfg = tiny()
    params = seeded(cfg)
    prompts = prompts_of(cfg, (107, 33, 5, 64, 90, 17), seed=3)
    eng = engine_for(cfg, params, max_batch=3, batch_buckets=(3,))
    rids = [eng.submit(p, 20) for p in prompts]
    eng.run_until_idle()
    for p, rid in zip(prompts, rids):
        alone = engine_for(cfg, params, max_batch=1, batch_buckets=(1,))
        r1 = alone.submit(p, 20)
        alone.run_until_idle()
        assert eng.result(rid).tokens == alone.result(r1).tokens


# (f) what is not built is refused by name -------------------------------

def test_what_is_not_built_over_rows_and_summaries_is_refused_by_name(
        devices):
    cfg = tiny()
    params = seeded(cfg)
    with pytest.raises(NotImplementedError,
                       match=r"prefix_caching \(its eva layers.*eva "
                             r"layer's open window.*B14"):
        engine_for(cfg, params, prefix_caching=True)
    eng = engine_for(cfg, params)
    with pytest.raises(NotImplementedError, match="with eva layers"):
        eng.submit([1, 2, 3], 2, prefill_only=True)
    with pytest.raises(NotImplementedError, match="inject.*eva"):
        eng.inject_begin({"block_size": BS})
    rid = eng.submit([1, 2, 3], 12)
    eng.step()
    with pytest.raises(NotImplementedError, match="migrate.*eva"):
        eng.export_running(rid)
    with pytest.raises(NotImplementedError,
                       match="inject.*eva layer's open window"):
        eng._inject_fn()
    with pytest.raises(NotImplementedError,
                       match="verify.*eva layer's open window"):
        eng._verify_fn()
    with pytest.raises(NotImplementedError, match="nor eva layers"):
        make_train_step(cfg, build_mesh(devices=devices[:1], dp=1))
    with pytest.raises(NotImplementedError, match="nor eva layers"):
        tf_lib.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


@pytest.mark.parametrize("kw,match", [
    (dict(eva_window=30), "eva_window in whole eva_chunk"),
    (dict(attn_gate=True), "no attn_gate"),
    (dict(layer_types=("eva", "eva", "window")), "of the 11 kinds"),
    (dict(layer_types=None, n_layers=2), "norm_unit_offset, stream_fp32 "
                                         "and head_rows are read by"),
])
def test_a_contradictory_configuration_is_refused_by_name(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny(**kw)


def test_a_page_is_whole_chunks_of_a_window_and_a_bucket_fits_one():
    cfg = tiny()
    with pytest.raises(ValueError, match="whole eva_chunk"):
        init_kv_cache(cfg, 4, 6, n_slots=1)
    with pytest.raises(ValueError, match="does not divide eva_window"):
        init_kv_cache(cfg, 4, 64, n_slots=1)
    with pytest.raises(ValueError, match="no bucket within eva_window"):
        engine_for(cfg, seeded(cfg), prefill_chunk=64,
                   prefill_buckets=(64,))


def test_the_kind_has_both_halves_and_is_no_recurrence():
    cfg = tiny(("eva", "full", "eva"))
    assert "eva" in STATE_KINDS and "eva" in SLOT_KINDS
    assert "eva" not in RECURRENT_KINDS and len(tf_lib.LAYER_KINDS) == 11
    cache = init_kv_cache(cfg, 9, BS, n_slots=2)
    kr, ks, vr, vs = cache.of("eva")
    assert kr.shape == vr.shape == (2, 3, W, 4, 16)
    assert ks.shape == vs.shape == (2, 9, BS // CH, 4, 16)
    assert [a.shape for a in cache.by_slot("eva")] == [kr.shape, vr.shape]
    assert cache.by_slot("full") == ()
    assert cache.slot_bytes == 2 * kr[:, 0].size * 4
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    assert params["lm_head"].shape == (64, 2 * 40)
    assert params["layers"][0]["eva_mu"].shape == (4, 16)
    assert "eva_mu" not in params["layers"][1]
    # the stored gain is g of 1 + g
    assert float(jnp.abs(params["layers"][0]["attn_norm"]).max()) == 0.0
    assert float(jnp.abs(params["final_norm"]).max()) == 0.0


# scopes ------------------------------------------------------------------

@pytest.mark.parametrize("program", ["prefill", "prefill_resume", "decode"])
def test_the_programs_carry_every_scope_name(program):
    """The names the cell's per-layer metrics read, in every program
    that has the part: the rows' write, the summaries' part of the
    attention, the window's, and the closing of chunks."""
    cfg = tiny()
    fns = dict(zip(("prefill", "prefill_resume", "decode"),
                   decode_lib.make_serve_fns(cfg, None, block_size=BS,
                                             table_width=6)))
    params = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))
    kc, vc = jax.eval_shape(lambda: (lambda c: (c.k, c.v))(
        init_kv_cache(cfg, 13, BS, n_slots=2)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    args = {"prefill": (i32(8), i32(), (i32(6), i32())),
            "prefill_resume": (i32(8), i32(), i32(), (i32(6), i32())),
            "decode": (i32(2), i32(2), (i32(2, 6), i32(2)))}[program]
    text = fns[program].lower(params, kc, vc, *args).as_text(debug_info=True)
    paths = ["attn/attn_eva/kv_write", "attn/attn_eva/eva_window",
             "attn/attn_eva/eva_summarise", "mlp", "head", "embed"]
    if program != "prefill":        # a whole prompt has no closed window
        paths.append("attn/attn_eva/eva_summaries")
    for path in paths:
        assert re.search(rf"jit\({program}\)/.*{path}\b", text), path


# (g) what the check's controls stand for, in float32 ---------------------

@pytest.mark.parametrize("name", ref.WRONG)
def test_each_mechanism_miscomputed_is_seen(name):
    """Every control of ``benchmark/tools/evabyte_tolerance.py`` (a
    bfloat16 stream, a folded unit offset and two softmaxes added among
    them) moves the logits or what a sequence holds of the tiny model by
    far more than the served model lies off the reference (2e-5), over
    107 positions and 30 after them: three closed windows behind the
    last queries."""
    cfg = tiny()
    params = seeded(cfg)
    seq = np.asarray(prompts_of(cfg, (137,))[0])
    sizes = sizes_of(cfg)
    want, kept = ref.forward(params, seq, sizes, last=30, kept=True)
    got, theirs = ref.forward(params, seq, sizes, last=30, kept=True,
                              wrong=name)
    moved = max(gap(got, want), kept_gap(theirs, kept))
    assert moved > 1e-3, (name, moved)


def test_the_two_copies_of_the_reference_are_one_text():
    def body(path):
        text = open(os.path.join(ROOT, path)).read()
        return text[text.index('"""', 3):]

    assert body("tests/reference_evabyte.py") == body(
        "benchmark/reference_evabyte.py")
    assert "horovod_tpu" not in body("tests/reference_evabyte.py")
