"""Gated short-convolution layers served beside grouped-query attention
and a mixture of experts held WHOLE (ISSUE 54): a conv layer keeps two
rows a batch slot and no recurrence, the full layers beside it norm q and
k a head and rotate, and every expert is on this chip, so the serve
programs run the trainer's dropless dispatch (sigmoid scores, a bias that
chooses). At a tiny size with seeded weights, against
``tests/reference_lfm2.py``: the plain forward of the same equations over
a whole sequence, no cache."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_lfm2 as ref
from horovod_tpu.models import (TransformerConfig, init_transformer,
                                make_train_step)
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import ServeConfig, ServeEngine
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import (RECURRENT_KINDS, SLOT_KINDS,
                                        init_kv_cache)

BS, CHUNK = 8, 32
TYPES = ("conv", "conv", "full", "conv", "conv", "full")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny(**kw):
    base = dict(
        vocab_size=128, d_model=32, n_layers=6, n_heads=4, n_kv_heads=2,
        d_head=64, d_ff=16, max_seq=256, norm_eps=1e-5, layer_types=TYPES,
        layer_rotary={"full": {"theta": 1e6}}, qk_norm_per_head=True,
        tie_embeddings=True, n_experts=8, moe_top_k=2,
        moe_capacity_factor=None, moe_scoring="sigmoid",
        moe_norm_topk_prob=True, moe_route_scale=1.0, n_dense_layers=2,
        d_ff_dense=64, conv_taps=3, dtype=jnp.float32, remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def sizes_of(cfg):
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    model["layer_rotary"] = {"full": {"theta": cfg.rotary_of(2).theta}}
    return ref.sizes_of({"model": model})


def seeded(cfg, seed=0, bias=0.5):
    """Seeded weights whose gains are not the ones of an initialisation
    and whose selection bias is wide enough to change some choices, so
    that each is seen."""
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 128))

    def shake(path, a):
        name = path[-1].key
        if name.endswith("norm"):
            return a + (0.3 * jax.random.normal(next(keys), a.shape)
                        ).astype(a.dtype)
        if name == "router_bias":
            return bias * jax.random.normal(next(keys), a.shape, a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(shake, params)


def engine_for(cfg, params, **kw):
    knobs = dict(max_batch=4, max_prompt=128, max_new_tokens=16,
                 block_size=BS, prefill_chunk=CHUNK,
                 prefill_buckets=(8, 16, 32), batch_buckets=(4,),
                 prefix_caching=False)
    knobs.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**knobs))


def serve_logits(cfg, params, prompts, n_decode, chunks=None, pad_to=BS,
                 dirty=False):
    """Chunked prefill of each of ``prompts`` into its slot (chunks of
    the sizes ``chunks``, then of ``CHUNK``; each padded to a multiple of
    ``pad_to``), then ``n_decode`` greedy steps of ALL of them as one
    full batch. ``dirty``: every slot's rows hold ones first, as a
    sequence that left them would. Returns for each prompt (the logits
    at the last position of each chunk and of each step, the positions
    they belong to, every token) and the caches."""
    B = len(prompts)
    width = -(-(max(map(len, prompts)) + n_decode) // BS) + CHUNK // BS
    prefill, resume, decode, _ = decode_lib.mixed_programs(
        cfg, BS, width, 0, head=lambda lg: lg)
    prefill, resume, decode = map(jax.jit, (prefill, resume, decode))
    cache = init_kv_cache(cfg, B * width + 1, BS, n_slots=B)
    kc, vc = cache.k, cache.v
    if dirty:
        kc = tuple(jnp.ones_like(a) if kind == "conv" else a
                   for kind, a in zip(cache.kinds, kc))
    tables = np.arange(1, B * width + 1, dtype=np.int32).reshape(B, width)
    rows, at, toks = ([[] for _ in prompts], [[] for _ in prompts],
                      [list(p) for p in prompts])
    for b, prompt in enumerate(prompts):
        addr = (jnp.asarray(tables[b]), jnp.int32(b + 1))
        sizes, off = list(chunks or ()), 0
        while off < len(prompt):
            n = min(sizes.pop(0) if sizes else CHUNK, len(prompt) - off)
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


def conv_rows(cfg, kc, slot):
    """The rows a slot holds, [n_conv, taps - 1, D], as the reference
    returns them."""
    place = init_kv_cache(cfg, 2, BS, n_slots=1).kinds.index("conv")
    return np.asarray(kc[place][:, slot], np.float32).reshape(
        cfg.n_layers_of("conv"), cfg.conv_taps - 1, cfg.d_model)


def gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


PROMPTS = (77, 32, 5)        # chunks 32+32+13, one whole, 5 of 8


def prompts_of(cfg, lens=PROMPTS, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).tolist() for n in lens]


# (a) prefill, then decode, against the reference's one forward pass -----

@pytest.mark.parametrize("dtype,tol,rows_tol", [
    (jnp.float32, 2e-5, 2e-5), (jnp.bfloat16, 0.08, 0.05)])
def test_a_whole_prompt_then_decode_equal_the_reference(dtype, tol,
                                                        rows_tol):
    """The monolithic prefill of every prompt (one chunk each: nothing
    resumed), then 8 decode steps of the three as one batch of
    different lengths; logits at every call and the rows each sequence
    leaves in every conv layer, against the reference run once over
    prompt and outputs. bfloat16: the reference reads the same rounded
    weights in float32, so what is left is the activations' rounding
    and the near-ties of a seeded router at 32 channels."""
    cfg = tiny(dtype=dtype)
    params = seeded(cfg)
    sizes = sizes_of(cfg)
    served, (kc, _) = serve_logits(cfg, params, prompts_of(cfg), 8,
                                   chunks=(80,))
    for b, (rows, at, toks) in enumerate(served):
        assert len(at) == 9                     # one prefill, eight steps
        want, kept = ref.logits(params, np.asarray(toks[:-1]), sizes,
                                states=True)
        assert gap(rows, np.asarray(want)[at]) < tol, (b, dtype)
        assert gap(conv_rows(cfg, kc, b + 1), kept) < rows_tol, (b, dtype)


def test_uneven_chunks_with_a_padded_last_bucket_carry_the_rows():
    """77 tokens as 24 + 32 + 21 (the last padded to 32, so that 11
    padded positions follow it), each resumed chunk starting from the
    slot's two rows, against the reference's one pass: the logits at
    every chunk's end and through 6 decode steps, and the rows left in
    every conv layer's slot, which are the reference's ``z`` at the last
    two positions and not the bucket's last."""
    cfg = tiny()
    params = seeded(cfg)
    prompt = prompts_of(cfg)[:1]
    [(rows, at, toks)], (kc, _) = serve_logits(
        cfg, params, prompt, 6, chunks=(24, 32, 21), pad_to=32)
    assert at[:3] == [23, 55, 76]
    want, kept = ref.logits(params, np.asarray(toks[:-1]), sizes_of(cfg),
                            states=True)
    assert gap(rows, np.asarray(want)[at]) < 2e-5
    assert gap(conv_rows(cfg, kc, 1), kept) < 2e-5
    # and right after the prompt: the rows at `length`, pads after it
    [_], (kc0, _) = serve_logits(cfg, params, prompt, 0,
                                 chunks=(24, 32, 21), pad_to=32)
    _, kept0 = ref.logits(params, np.asarray(prompt[0]), sizes_of(cfg),
                          states=True)
    assert gap(conv_rows(cfg, kc0, 1), kept0) < 2e-5


def test_a_slot_a_sequence_left_starts_a_new_one_from_zeros():
    """Every slot's rows hold ones before the first chunk, as whatever
    the slot's last sequence left: a first chunk (monolithic, and the
    first of several) starts from zeros all the same."""
    cfg = tiny()
    params = seeded(cfg)
    clean, _ = serve_logits(cfg, params, prompts_of(cfg), 4)
    dirty, _ = serve_logits(cfg, params, prompts_of(cfg), 4, dirty=True)
    for (a, _, ta), (b, _, tb) in zip(clean, dirty):
        assert ta == tb and gap(a, b) < 1e-6


def test_the_engine_serves_the_reference_s_tokens_and_leaves_its_rows():
    cfg = tiny()
    params = seeded(cfg)
    eng = engine_for(cfg, params)
    prompts = prompts_of(cfg)
    rids = [eng.submit(p, 12) for p in prompts]
    seen = set()
    while eng.pending:
        eng.step()
        seen.add(eng.metrics.state_slots_in_use)
    for prompt, rid in zip(prompts, rids):
        res = eng.result(rid)
        want, kept = ref.logits(
            params, np.asarray(prompt + res.tokens[:-1]), sizes_of(cfg),
            last=12, states=True)
        assert res.tokens == np.asarray(want).argmax(-1).tolist()
        assert gap(conv_rows(cfg, eng.cache.k, res.slot), kept) < 2e-5
    # the gauge counts the slots that hold conv rows, and their bytes
    assert max(seen) == 3
    assert eng.metrics.snapshot()["state_slots_in_use"] == 0
    assert eng.cache.slot_bytes == 4 * 2 * 32 * 4


def test_a_request_of_one_token_leaves_the_row_it_was_handed():
    """A prompt one position past a chunk, ended by its prefill: the
    slot holds the row the resumed chunk was HANDED beside the one it
    wrote, read before any decode step shifts them out (what the cell's
    check reads on the chip). The reference's rows there; the
    reference with the rows not carried keeps zeros in the handed row's
    place, and with the bucket's padding convolved a padded position's
    rows: both far off in the FIRST conv layer, which no router
    precedes."""
    cfg = tiny()
    params = seeded(cfg)
    engine = engine_for(cfg, params)
    chunk = engine.cfg.prefill_chunk
    prompt, = prompts_of(cfg, (chunk + 1,))
    rid = engine.submit(list(prompt), max_new_tokens=1)
    engine.run_until_idle()
    res = engine.result(rid)
    sizes = sizes_of(cfg)
    want, rows = ref.logits(params, np.asarray(prompt), sizes, last=1,
                            states=True)
    assert res.tokens == [int(np.asarray(want)[0].argmax())]
    left = conv_rows(cfg, engine.cache.k, res.slot)
    assert gap(left, rows) < 2e-5
    for how in ({"cut": chunk}, {"pads": (chunk + 1, 7)}):
        _, theirs = ref.logits(params, np.asarray(prompt), sizes, last=1,
                               states=True, **how)
        assert gap(theirs[0], rows[0]) > 0.5, how
        assert gap(left[0], theirs[0]) > 0.5, how


def test_six_requests_through_three_slots_are_each_what_they_are_alone():
    """Slots in use below ``max_batch``, every slot used twice: each
    request's tokens are what it gets alone in a fresh engine."""
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
    eng.run_until_idle()
    results = [eng.result(r) for r in rids]
    assert [r.tokens for r in results] == alone
    assert len({r.slot for r in results}) <= 3


# (b) the router ---------------------------------------------------------

def test_a_bias_chooses_other_experts_and_weighs_nothing():
    """A selection bias wide enough to change choices: the served
    logits follow the reference with the bias and not the reference with
    a bias of zeros (which could not be told from one left out), and
    some token's experts differ between the two."""
    cfg = tiny()
    params = seeded(cfg, bias=1.0)
    unbiased = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a)
        if path[-1].key == "router_bias" else a, params)
    sizes = sizes_of(cfg)
    [(rows, at, toks)], _ = serve_logits(cfg, params, prompts_of(cfg)[:1], 4)
    seq = np.asarray(toks[:-1])
    with_bias = np.asarray(ref.logits(params, seq, sizes))[at]
    without = np.asarray(ref.logits(unbiased, seq, sizes))[at]
    assert gap(rows, with_bias) < 2e-5
    assert gap(rows, without) > 1e-2
    from horovod_tpu.models import moe as moe_lib
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    lp = params["layers"][0]["moe"]
    logits = moe_lib._router_logits(x, lp["router"])
    _, gates, chosen = moe_lib._top_k_gates(logits, cfg.moe,
                                            lp["router_bias"])
    _, _, plain = moe_lib._top_k_gates(logits, cfg.moe,
                                       jnp.zeros_like(lp["router_bias"]))
    assert (np.sort(chosen, -1) != np.sort(plain, -1)).any()
    # the gates are the sigmoids at the chosen experts over their sum
    s = jnp.take_along_axis(jax.nn.sigmoid(logits), chosen, -1)
    assert gap(gates, s / s.sum(-1, keepdims=True)) < 1e-6


def test_a_mixture_held_whole_reports_every_pair_local():
    """``moe_share_report`` where nothing is absent: every pair is on a
    held expert, none is dropped, and at most ``n_experts`` are
    touched."""
    cfg = tiny()
    params = seeded(cfg)
    toks = np.asarray(prompts_of(cfg, (32, 32))).astype(np.int32)
    out = decode_lib.moe_share_report(params, toks, cfg, BS)
    assert out["moe_local_pair_share"] == 1.0
    assert out["moe_dispatch_dropped_token_frac"] == 0.0
    assert 2 <= out["moe_held_experts_touched_mean"] <= cfg.n_experts
    assert out["moe_expert_load_max_over_mean"] >= 1.0


# (c) the configuration ---------------------------------------------------

def test_a_configuration_admits_conv_beside_normed_grouped_query_layers():
    cfg = tiny()
    assert cfg.stateful and cfg.mixed
    assert [cfg.n_layers_of(k) for k in ("conv", "full", "mamba")] == [4, 2, 0]
    assert cfg.rotary_of(2).theta == 1e6
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    assert "lm_head" not in params and len(params["dense_layers"]) == 2
    conv, full = params["dense_layers"][0], params["layers"][0]
    assert set(conv) == {"attn_norm", "w_in", "conv_w", "w_out", "mlp_norm",
                         "w_gate", "w_up", "w_down"}
    assert conv["w_in"].shape == (32, 96) and conv["conv_w"].shape == (3, 32)
    assert full["wk"].shape == (32, 128) and full["q_norm"].shape == (64,)
    assert full["moe"]["w_gate"].shape == (8, 32, 16)         # all held
    assert full["moe"]["router_bias"].dtype == jnp.float32
    specs = tf_lib.param_specs(cfg)
    assert (jax.tree.structure(specs, is_leaf=lambda x: isinstance(
        x, jax.sharding.PartitionSpec)) == jax.tree.structure(params))
    cache = init_kv_cache(cfg, 9, BS, n_slots=4)
    assert cache.kinds == ("full", "conv")
    (rows,) = cache.of("conv")
    assert rows.shape == (4, 5, 2 * 32) and rows.dtype == jnp.float32
    assert cache.v[1] is None
    # two heads of 64: a position's heads as one row of 128 lanes
    assert cache.of("full")[0].shape == (2, 9, BS, 128)
    assert cache.slot_bytes == 4 * 2 * 32 * 4
    # by slot, counted by the gauge, and no recurrence
    assert "conv" in SLOT_KINDS and "conv" not in RECURRENT_KINDS
    assert init_kv_cache(tiny(dtype=jnp.bfloat16), 9, BS, n_slots=4
                         ).of("conv")[0].dtype == jnp.bfloat16


@pytest.mark.parametrize("name", [
    "jamba2-3b", "ling-3.0-flash-ep4-7l", "kimi-k2.7-code-ep32-6l",
    "minicpm-sala-8l", "trinity-large-ep8-5l", "lfm2-8b-a1b-14l"])
def test_the_served_configurations_still_construct(name):
    """The refusal of ``qk_norm_per_head`` and GQA beside a by-slot kind
    is narrowed to the kinds that have heads of their own: every mixed
    configuration the benchmark serves still builds."""
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    fields = {**config["model"], **config.get("run", {})}
    fields["dtype"] = getattr(jnp, fields["dtype"])
    cfg = TransformerConfig(**fields)
    assert cfg.mixed and len(cfg.layer_types) == cfg.n_layers


@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=("conv", "retention", "full", "conv", "conv", "full")),
     "'lightning' | 'conv'"),
    (dict(attn_gate=True), "conv layers have no gate.*no attn_gate"),
    (dict(sandwich_norm=True), "conv layers have no gate"),
    (dict(qk_norm=True, qk_norm_per_head=False), "qk_norm over the whole"),
    (dict(layer_types=("conv", "kda", "full", "conv", "conv", "full")),
     "kda layers have n_heads heads.*n_kv_heads = n_heads"),
    (dict(layer_types=("conv", "mla", "full", "conv", "conv", "full"),
          n_kv_heads=4, mla_kv_rank=16, mla_rope_dim=8),
     "mla layers have n_heads heads.*no attn_gate, sandwich_norm or qk_norm"),
    (dict(layer_types=None), "tie_embeddings"),
    (dict(layer_rotary={"conv": {"theta": 1e4}}), "layer_rotary is by kind"),
    (dict(n_dense_layers=6), "n_dense_layers leads a stack"),
])
def test_a_contradictory_configuration_is_still_refused_by_name(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny(**kw)


def test_mamba_beside_normed_grouped_query_layers_now_constructs():
    """What `__post_init__` refused wholesale "in a stack with mamba
    layers either": the full layers beside a kind with no q or k keep
    their own KV heads and per-head norms; the by-slot kind holds no
    such gain."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
        d_ff=64, layer_types=("mamba", "full", "mamba"), mamba_dt_rank=8,
        qk_norm_per_head=True, tie_embeddings=True, dtype=jnp.float32)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    assert "q_norm" in params["layers"][1]
    assert "q_norm" not in params["layers"][0]


# (d) what is not built is refused by name -------------------------------

def test_what_is_not_built_over_conv_rows_is_refused_by_name(devices):
    cfg = tiny()
    params = seeded(cfg)
    with pytest.raises(NotImplementedError,
                       match=r"prefix_caching \(its conv layers.*B14"):
        engine_for(cfg, params, prefix_caching=True)
    eng = engine_for(cfg, params)
    with pytest.raises(NotImplementedError, match="with conv layers"):
        eng.submit([1, 2, 3], 2, prefill_only=True)
    with pytest.raises(NotImplementedError, match="inject.*conv"):
        eng.inject_begin({"block_size": BS})
    rid = eng.submit([1, 2, 3], 12)
    eng.step()
    with pytest.raises(NotImplementedError, match="migrate.*conv"):
        eng.export_running(rid)
    with pytest.raises(NotImplementedError, match="inject.*conv layer's rows"):
        eng._inject_fn()
    with pytest.raises(NotImplementedError, match="verify.*conv layer's rows"):
        eng._verify_fn()
    with pytest.raises(NotImplementedError, match="nor conv layers.*B14"):
        make_train_step(cfg, build_mesh(devices=devices[:1], dp=1))
    with pytest.raises(NotImplementedError, match="nor conv layers"):
        tf_lib.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


# (e) spans, counters and scopes ------------------------------------------

def test_the_spans_say_what_a_call_convolved_stepped_and_attended(tmp_path):
    cfg = tiny()
    eng = engine_for(cfg, seeded(cfg))
    eng.submit(prompts_of(cfg)[0], 4)           # 77: 32 + 32 + 13 of 16
    eng.submit(prompts_of(cfg)[2], 4)           # 5 of 8
    eng.run_until_idle()
    path = tmp_path / "spans.json"
    eng.metrics.export_chrome_trace(str(path))
    spans = [e for e in json.load(open(path))["traceEvents"]
             if e.get("ph") == "X"]
    chunks = [s["args"] for s in spans if s["name"] == "serve:prefill"]
    assert sorted((a["n_tokens"], a["convolved"]) for a in chunks) == [
        (5, 8), (13, 16), (32, 32), (32, 32)]
    steps = [s["args"] for s in spans if s["name"] == "serve:decode"]
    assert steps and all(a["slots_stepped"] == 4 for a in steps)
    # two rows at positions 77 and 5: the keys they see, themselves too
    assert steps[0]["attended"] == 77 + 5 + 2


@pytest.mark.parametrize("program", ["prefill", "prefill_resume", "decode"])
def test_the_programs_carry_every_scope_name(program):
    """The names the cell's per-layer metrics read: ``attn_conv`` with
    ``conv_proj``, ``conv_taps`` and ``state_write`` in all three
    programs, the full layers under ``attn_full``, the mixture under
    ``mlp`` with the dropless dispatch's own names."""
    import re

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
    for path in ("attn/attn_conv/conv_proj", "attn/attn_conv/conv_taps",
                 "attn/attn_conv/state_write", "attn/attn_full/kv_write",
                 "attn/qk_norm", "mlp/moe_router", "mlp/moe_dispatch",
                 "mlp/moe_experts", "mlp/moe_combine", "head", "embed"):
        assert re.search(rf"jit\({program}\)/.*{path}\b", text), path


# (f) what the check's controls stand for, in float32 ---------------------

CONTROLS = tuple((w, {"wrong": w}) for w in ref.WRONG) + (
    ("rows_not_carried", {"cut": 56}),      # a resumed chunk from zeros
    ("pads_convolved", {"pads": (77, 11)}),  # a bucket's padding in the rows
)


@pytest.mark.parametrize("name,how", CONTROLS, ids=[c[0] for c in CONTROLS])
def test_each_mechanism_miscomputed_is_seen(name, how):
    """Every control of ``benchmark/tools/lfm2_tolerance.py`` moves the
    logits or a conv layer's rows of the tiny model by far more than the
    served model lies off the reference (2e-5): 77 prompt tokens (a
    chunk boundary at 56, 11 pads behind the last chunk) and 8 after
    them."""
    cfg = tiny()
    params = seeded(cfg, bias=1.0)
    seq = np.asarray(prompts_of(cfg, (85,))[0])
    sizes = sizes_of(cfg)
    want, rows = ref.logits(params, seq, sizes, last=8, states=True)
    got, theirs = ref.logits(params, seq, sizes, last=8, states=True, **how)
    moved = max(gap(got, want), gap(theirs, rows))
    assert moved > 1e-3, (name, moved)


def test_the_reference_s_own_pads_and_cut_are_what_the_program_avoids():
    """``pads`` and ``cut`` to the letter: with no pad and no cut the
    reference is itself; a cut at 0 carries nothing and changes
    nothing."""
    cfg = tiny()
    params = seeded(cfg)
    seq = np.asarray(prompts_of(cfg, (40,))[0])
    sizes = sizes_of(cfg)
    want = ref.logits(params, seq, sizes, last=4)
    assert gap(ref.logits(params, seq, sizes, last=4, cut=0), want) == 0.0
    assert gap(ref.logits(params, seq, sizes, last=4, pads=(40, 0)),
               want) < 1e-6


def test_the_two_copies_of_the_reference_are_one_text():
    def body(path):
        text = open(os.path.join(ROOT, path)).read()
        return text[text.index('"""', 3):]

    assert body("tests/reference_lfm2.py") == body(
        "benchmark/reference_lfm2.py")
    assert "horovod_tpu" not in body("tests/reference_lfm2.py")
