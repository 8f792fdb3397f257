"""State-space layers served beside multi-query attention (ISSUE 47):
Mamba-1's selective scan over a float32 state and the convolution's rows
a batch slot, full layers of several query heads over ONE key-value head
and no positional embedding, a head tied to the embedding. At a tiny
size with seeded weights, against ``tests/reference_jamba2.py``: the
plain forward of the same equations over a whole sequence, a position
at a time, no cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_jamba2 as ref
from horovod_tpu.models import (TransformerConfig, init_transformer,
                                make_train_step)
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import (RECURRENT_KINDS, SLOT_KINDS,
                                        init_kv_cache)

BS, CHUNK = 8, 32
TYPES = ("mamba", "mamba", "full", "mamba")


def tiny(**kw):
    base = dict(
        vocab_size=128, d_model=32, n_layers=4, n_heads=4, n_kv_heads=1,
        d_ff=64, max_seq=256, norm_eps=1e-6, layer_types=TYPES,
        mamba_d_state=4, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
        tie_embeddings=True, dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def sizes_of(cfg):
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return ref.sizes_of({"model": model})


def seeded(cfg, seed=0):
    """Seeded weights whose gains and skip are not the ones of an
    initialisation, so that each is seen."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("norm") or name == "d_skip":
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


def prompts_of(cfg, lens=PROMPTS, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


# (a) prefill, then decode, against the reference's full forward ---------

@pytest.mark.parametrize("dtype,tol,state_tol", [
    (jnp.float32, 2e-5, 2e-5), (jnp.bfloat16, 0.06, 0.08)])
def test_chunks_then_decode_equal_the_reference(dtype, tol, state_tol):
    """Logits at every chunk's end and every decode step of a full
    batch, and the state each sequence leaves in its slot, against the
    reference run once over prompt and outputs. bfloat16: the
    reference reads the same rounded weights in float32, so what is
    left is the activations' rounding, which at 32 channels is percents
    (the chip's check reads it at the published widths)."""
    cfg = tiny(dtype=dtype)
    params = seeded(cfg)
    sizes = sizes_of(cfg)
    served, (kc, _) = serve_logits(cfg, params, prompts_of(cfg), 8)
    for b, (rows, at, toks) in enumerate(served):
        want, states = ref.logits(params, np.asarray(toks[:-1]), sizes,
                                  states=True)
        assert gap(rows, np.asarray(want)[at]) < tol, (b, dtype)
        left = np.asarray(kc[1][:, b + 1])
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
    kept = eng.cache.of("mamba")[0]
    for prompt, rid in zip(prompts, rids):
        res = eng.result(rid)
        want, states = ref.logits(
            params, np.asarray(prompt + res.tokens[:-1]), sizes_of(cfg),
            last=12, states=True)
        assert res.tokens == np.asarray(want).argmax(-1).tolist()
        assert gap(kept[:, res.slot], states) < 2e-5
    assert eng.metrics.snapshot()["state_slots_in_use"] == 0


# (b) the scan against the step -----------------------------------------

@pytest.mark.parametrize("T,unroll", [(37, 8), (64, 1), (5, 8)])
def test_a_scan_over_t_positions_is_t_steps(T, unroll):
    rng = np.random.default_rng(0)
    B, Di, N = 2, 16, 4
    u, b, c = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((B, T, Di), (B, T, N), (B, T, N)))
    step = jnp.asarray(rng.uniform(1e-3, 2.0, (B, T, Di)), jnp.float32)
    a = -jnp.exp(jnp.asarray(rng.normal(size=(N, Di)), jnp.float32))
    s0 = jnp.asarray(rng.normal(size=(B, N, Di)), jnp.float32)
    y, s = decode_lib.mamba_scan(u, step, a, b, c, s0, unroll=unroll)
    want, state = [], s0
    for t in range(T):
        o, state = decode_lib.mamba_step(u[:, t], step[:, t], a, b[:, t],
                                         c[:, t], state)
        want.append(o)
    assert gap(y, jnp.stack(want, 1)) < 1e-5
    assert gap(s, state) < 1e-5


def test_a_position_that_steps_by_nothing_leaves_the_state():
    rng = np.random.default_rng(1)
    u, b, c = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((1, 24, 16), (1, 24, 4), (1, 24, 4)))
    a = -jnp.ones((4, 16), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(1, 4, 16)), jnp.float32)
    _, s = decode_lib.mamba_scan(u, jnp.zeros_like(u), a, b, c, s0)
    assert np.array_equal(np.asarray(s), np.asarray(s0))


# (c) chunks and padding -------------------------------------------------

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
    assert gap(a[-7:], b[-7:]) < 2e-5
    assert gap(ka[1][:, 1], kb[1][:, 1]) < 2e-5          # the state
    assert gap(va[1][:, 1], vb[1][:, 1]) < 2e-5          # the rows


def test_padding_run_through_the_scan_is_seen():
    """The fault the check's ``pads_scanned`` control stands for: the
    reference with a last chunk's 19 pads run through convolution and
    scan differs from the reference, by far more than the program
    does."""
    cfg = tiny()
    params = seeded(cfg)
    seq = np.asarray(prompts_of(cfg)[0])
    sizes = sizes_of(cfg)
    want, state = ref.logits(params, seq, sizes, last=4, states=True)
    got, theirs = ref.logits(params, seq[:-3], sizes, states=True,
                             pads=(74, 19))
    assert got.shape[0] == 74
    got, theirs = ref.logits(params, seq, sizes, last=4, states=True,
                             pads=(73, 19))
    assert gap(got, want) > 1e-3
    assert gap(theirs, state) > 1e-3


# (d) continuous batching -----------------------------------------------

def test_a_slot_starts_from_zero_and_neighbours_do_not_matter():
    """Six requests through three slots of four (slots in use below
    ``max_batch``, every slot used twice): each one's tokens are what
    it gets alone in a fresh engine."""
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


# (e) the configuration ---------------------------------------------------

def test_a_configuration_admits_mamba_beside_multi_query_layers():
    cfg = tiny()
    assert cfg.stateful and cfg.mixed and cfg.n_kv_heads == 1
    assert [cfg.n_layers_of(k) for k in ("mamba", "full", "kda")] == [3, 1, 0]
    assert all(cfg.rotary_of(i) is None for i in range(4))
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    assert "lm_head" not in params
    assert params["layers"][2]["wk"].shape == (32, 8)     # ONE kv head
    lp = params["layers"][0]
    assert lp["a_log"].shape == (4, 64) and lp["a_log"].dtype == jnp.float32
    assert lp["b_dt"].dtype == lp["d_skip"].dtype == jnp.float32
    step = jax.nn.softplus(lp["b_dt"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 1e-1
    specs = tf_lib.param_specs(cfg)
    assert (jax.tree.structure(specs, is_leaf=lambda x: isinstance(
        x, jax.sharding.PartitionSpec)) == jax.tree.structure(params))
    cache = init_kv_cache(cfg, 9, BS, n_slots=4)
    assert cache.kinds == ("full", "mamba")
    state, rows = cache.of("mamba")
    assert state.shape == (3, 5, 4, 64) and state.dtype == jnp.float32
    assert rows.shape == (3, 5, 3 * 64)
    assert cache.of("full")[0].shape == (1, 9, BS, 1, 8)
    assert cache.slot_bytes == 3 * (4 * 64 * 4 + 3 * 64 * 4)
    assert set(RECURRENT_KINDS) < set(SLOT_KINDS)


@pytest.mark.parametrize("kw,match", [
    (dict(mamba_dt_rank=0), "mamba_dt_rank"),
    (dict(layer_types=("mamba", "retention", "full", "mamba")),
     "'mla' | 'mamba'"),
    (dict(attn_gate=True), "no attn_gate"),
    (dict(qk_norm=True), "no attn_gate"),
    (dict(layer_types=("mamba", "kda", "full", "mamba")),
     "n_kv_heads = n_heads"),
    (dict(layer_types=None), "tie_embeddings"),
    (dict(layer_types=("mamba", "sliding", "full", "mamba")), "attn_window"),
])
def test_a_configuration_still_refuses_what_it_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny(**kw)


# (f) what is not built is refused by name -------------------------------

def test_what_is_not_built_over_mamba_is_refused_by_name(devices):
    cfg = tiny()
    params = seeded(cfg)
    with pytest.raises(NotImplementedError,
                       match=r"prefix_caching \(its mamba layers.*B14"):
        engine_for(cfg, params, prefix_caching=True)
    eng = engine_for(cfg, params)
    with pytest.raises(NotImplementedError, match="with mamba layers"):
        eng.submit([1, 2, 3], 2, prefill_only=True)
    with pytest.raises(NotImplementedError, match="inject.*mamba"):
        eng.inject_begin({"block_size": BS})
    rid = eng.submit([1, 2, 3], 12)
    eng.step()
    with pytest.raises(NotImplementedError, match="migrate.*mamba"):
        eng.export_running(rid)
    with pytest.raises(NotImplementedError, match="inject.*mamba layer"):
        eng._inject_fn()
    with pytest.raises(NotImplementedError, match="verify.*mamba layer"):
        eng._verify_fn()
    with pytest.raises(NotImplementedError, match="kda, mla or mamba.*B14"):
        make_train_step(cfg, build_mesh(devices=devices[:1], dp=1))
    with pytest.raises(NotImplementedError, match="kda, mla or mamba"):
        tf_lib.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


# (g) what the check's controls stand for, in float32 ---------------------

@pytest.mark.parametrize("wrong", ref.WRONG)
def test_each_mechanism_miscomputed_is_seen(wrong):
    """Every control of ``benchmark/tools/jamba2_tolerance.py`` moves
    the logits or a state of the tiny model by far more than the served
    model lies off the reference (2e-5)."""
    cfg = tiny()
    params = seeded(cfg)
    seq = np.asarray(prompts_of(cfg)[0])
    sizes = sizes_of(cfg)
    want, state = ref.logits(params, seq, sizes, last=8, states=True)
    got, theirs = ref.logits(params, seq, sizes, last=8, states=True,
                             wrong=wrong)
    moved = max(gap(got, want), gap(theirs, state))
    # (not a number, as a step below zero makes it, is seen too)
    assert not moved <= (1e-3 if wrong != "state_in_bf16" else 2e-4), moved


def test_a_tied_head_trains_nothing_here_and_reads_the_embedding():
    cfg = tiny()
    params = seeded(cfg)
    assert tf_lib.head_weights(cfg, params).shape == (32, 128)
    assert np.array_equal(tf_lib.head_weights(cfg, params),
                          params["embed"].T)


def test_the_two_copies_of_the_reference_are_one_text():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def body(path):
        text = open(os.path.join(root, path)).read()
        return text[text.index('"""', 3):]

    assert body("tests/reference_jamba2.py") == body(
        "benchmark/reference_jamba2.py")
    assert "horovod_tpu" not in body("tests/reference_jamba2.py")
