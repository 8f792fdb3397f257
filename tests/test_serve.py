"""Serve subsystem tests: block allocator, scheduler (admission /
deadline expiry / mid-batch retirement / backpressure), and decode
parity — served greedy decode must be bitwise-identical to the
single-request reference and track the full-context forward."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (
    TransformerConfig, init_transformer, transformer_forward,
)
from horovod_tpu.serve import (
    BlockAllocator, OutOfBlocks, QueueFull, ServeConfig, ServeEngine,
    block_hash, pick_bucket,
)


# ---------------------------------------------------------------------------
# Block allocator
# ---------------------------------------------------------------------------

def test_allocator_basic_alloc_free():
    a = BlockAllocator(n_blocks=9, block_size=4)
    assert a.n_free == 8  # block 0 is the reserved null block
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert a.n_used == 3 and a.n_free == 5
    a.free(got)
    assert a.n_used == 0 and a.n_free == 8


def test_allocator_out_of_blocks_backpressure():
    a = BlockAllocator(n_blocks=5, block_size=4)
    assert a.can_alloc(4) and not a.can_alloc(5)
    first = a.alloc(4)
    with pytest.raises(OutOfBlocks):
        a.alloc(1)
    a.free(first[:1])
    assert a.can_alloc(1)
    a.alloc(1)


def test_allocator_interleaved_reuse_no_fragmentation():
    # Paged pools have no external fragmentation: any free block
    # serves any sequence, so capacity == free count regardless of
    # alloc/free interleaving.
    a = BlockAllocator(n_blocks=9, block_size=2)
    s1, s2 = a.alloc(3), a.alloc(3)
    a.free(s1)  # retire the first sequence mid-life of the second
    s3 = a.alloc(3)
    assert set(s3) == set(s1)  # LIFO reuse, deterministic
    assert a.n_free == 2 and a.high_water == 6
    a.free(s2)
    a.free(s3)
    with pytest.raises(ValueError):
        a.free(s3)  # double free is an error, not corruption


def test_allocator_blocks_for_tokens():
    a = BlockAllocator(n_blocks=5, block_size=8)
    assert a.blocks_for_tokens(0) == 0
    assert a.blocks_for_tokens(1) == 1
    assert a.blocks_for_tokens(8) == 1
    assert a.blocks_for_tokens(9) == 2


def test_block_hash_is_chained():
    h1 = block_hash(b"", [1, 2, 3, 4])
    assert h1 == block_hash(b"", [1, 2, 3, 4])   # deterministic
    assert h1 != block_hash(b"", [1, 2, 3, 5])   # content-sensitive
    # Same block content under a different parent is a different
    # prefix — the chain is what makes hash equality mean whole-prefix
    # equality, not just block equality.
    assert block_hash(h1, [9, 9]) != block_hash(b"", [9, 9])


def test_allocator_register_share_release_cycle():
    a = BlockAllocator(n_blocks=6, block_size=4)
    (b,) = a.alloc(1)
    h = block_hash(b"", [1, 2, 3, 4])
    assert a.register(b, h)
    # Second registration under the same hash loses (dedup): the
    # first mapping survives.
    (b2,) = a.alloc(1)
    assert not a.register(b2, h)
    a.free([b2])
    assert a.n_cached == 0          # anonymous block -> plain free

    # Sharing: a cache hit on a live block just bumps its refcount.
    assert a.acquire_cached(h) == b
    assert a.refcount(b) == 2
    a.free([b])
    assert a.refcount(b) == 1 and a.n_used == 1
    a.free([b])
    # Refcount 0 + registered -> parked in the LRU pool, not freed:
    # still allocatable capacity, still a hit.
    assert a.n_used == 0 and a.n_cached == 1 and a.n_free == 5
    assert a.acquire_cached(h) == b
    assert a.n_used == 1 and a.n_cached == 0
    a.free([b])
    with pytest.raises(ValueError):
        a.free([b])                 # double free detected on cached too


def test_allocator_lru_eviction_only_under_pressure():
    a = BlockAllocator(n_blocks=5, block_size=4)
    blocks = a.alloc(3)
    hs = [block_hash(b"", [i]) for i in range(3)]
    for b, h in zip(blocks, hs):
        a.register(b, h)
    a.free(blocks)                  # release order == LRU order
    assert a.n_cached == 3 and a.n_free == 4
    # One plain-free block remains: the first alloc must consume it
    # and leave the cache intact.
    (x,) = a.alloc(1)
    assert a.n_cached == 3 and a.evictions == 0
    # Pressure: the next alloc evicts the LEAST recently released.
    (y,) = a.alloc(1)
    assert y == blocks[0] and a.evictions == 1
    assert a.acquire_cached(hs[0]) is None      # forgotten
    assert a.acquire_cached(hs[1]) == blocks[1]  # survivors still hit
    assert a.prefix_misses == 1 and a.prefix_hits == 1
    a.free([x, y, blocks[1]])


def test_allocator_randomized_stress():
    """Randomized interleaving of alloc/register/share/free/evict
    against a shadow model: no leaks, no double frees, ``n_used``
    always equals the number of live-ref blocks, eviction never
    reclaims a block that has references, and the three states
    (live/cached/free) always partition the pool."""
    rng = np.random.RandomState(1234)
    n_blocks, bs = 33, 4
    a = BlockAllocator(n_blocks, bs)
    live = {}                       # block -> shadow refcount
    next_tok = itertools.count()
    registered = {}                 # block -> hash (live or cached)
    for step in range(3000):
        op = rng.randint(4)
        if op == 0:                 # alloc 1-4 blocks
            n = int(rng.randint(1, 5))
            if a.can_alloc(n):
                before_cached = a.n_cached
                got = a.alloc(n)
                assert len(set(got)) == n and 0 not in got
                evicted = sum(1 for b in got if b in registered)
                # alloc may shrink the cache (evictions) but never
                # grow it, and every eviction is accounted.
                assert a.n_cached == before_cached - evicted
                for b in got:
                    assert b not in live, "handed out a live block"
                    # Eviction dropped the index entry if this block
                    # came from the LRU pool.
                    registered.pop(b, None)
                    live[b] = 1
            else:
                with pytest.raises(OutOfBlocks):
                    a.alloc(n)
        elif op == 1 and live:      # register a live block
            b = int(rng.choice(sorted(live)))
            if b not in registered:
                h = block_hash(b"", [next(next_tok)])
                assert a.register(b, h)
                registered[b] = h
        elif op == 2 and registered:  # cache-hit / share
            b = int(rng.choice(sorted(registered)))
            got = a.acquire_cached(registered[b])
            assert got == b, "hash must resolve to its block"
            live[b] = live.get(b, 0) + 1
        elif op == 3 and live:      # drop one ref
            b = int(rng.choice(sorted(live)))
            a.free([b])
            live[b] -= 1
            if not live[b]:
                del live[b]
                with pytest.raises(ValueError):
                    a.free([b])     # double free always detected
        # Invariants, every step.
        assert a.n_used == len(live)
        assert {b for b in live} == set(a._refs)
        for b, r in live.items():
            assert a.refcount(b) == r
        assert a.n_used + a.n_free == n_blocks - 1
        assert a.n_cached == len(set(registered) - set(live))
    # Drain: every live ref released -> pool fully reclaimable.
    for b, r in list(live.items()):
        for _ in range(r):
            a.free([b])
    assert a.n_used == 0 and a.n_free == n_blocks - 1


def test_pick_bucket():
    assert pick_bucket(3, (4, 8, 16)) == 4
    assert pick_bucket(4, (4, 8, 16)) == 4
    assert pick_bucket(9, (4, 8, 16)) == 16
    with pytest.raises(ValueError):
        pick_bucket(17, (4, 8, 16))


# ---------------------------------------------------------------------------
# Engine / scheduler
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def served_model():
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(n, rng_seed=0, lo=3, hi=14):
    rng = np.random.RandomState(rng_seed)
    return [rng.randint(1, 256, size=int(rng.randint(lo, hi))).tolist()
            for _ in range(n)]


def _mk_engine(served_model, clock=None, **kw):
    cfg, params = served_model
    defaults = dict(max_batch=4, block_size=8, max_prompt=16,
                    max_new_tokens=8)
    defaults.update(kw)
    return ServeEngine(cfg, params, ServeConfig(**defaults),
                       clock=clock or FakeClock())


def test_submit_validation(served_model):
    eng = _mk_engine(served_model)
    with pytest.raises(ValueError):
        eng.submit([])
    with pytest.raises(ValueError):
        eng.submit([1] * 17)  # > max_prompt
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new_tokens=9)  # > cap
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new_tokens=0)  # zero is an error, not
        # a silent fall-through to the config default


def test_submit_rejects_unservable_reservation(served_model):
    # A request whose worst-case KV reservation exceeds the WHOLE pool
    # could never be admitted; FIFO would starve everything behind it.
    eng = _mk_engine(served_model, n_blocks=2, max_prompt=8,
                     max_new_tokens=8)  # pool: 1 usable block
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit([1] * 8, max_new_tokens=8)  # needs 2 blocks


def test_bucket_menus_validated_at_construction(served_model):
    with pytest.raises(ValueError):
        _mk_engine(served_model, prefill_buckets=(8,))  # < max_prompt 16
    with pytest.raises(ValueError):
        _mk_engine(served_model, batch_buckets=(2,))  # < max_batch 4
    with pytest.raises(ValueError):
        _mk_engine(served_model, prefill_buckets=(12, 16))  # not block-
        # aligned (block_size 8)
    with pytest.raises(ValueError, match="block table"):
        # Block-aligned and >= max_prompt, but its pages exceed the
        # table: would assert mid-prefill after blocks were reserved.
        _mk_engine(served_model, prefill_buckets=(64,))


def test_queue_full_rejection_503(served_model):
    eng = _mk_engine(served_model, max_queue=2)
    eng.submit([1, 2, 3])
    eng.submit([4, 5])
    with pytest.raises(QueueFull) as ei:
        eng.submit([6])
    assert ei.value.http_status == 503
    # Structured, not blanket: the caller learns why and when to come
    # back (0.0 retry before any retirement — no drain signal yet).
    assert ei.value.reason == "queue_full"
    assert ei.value.queue_depth == 2
    assert ei.value.retry_after_s is not None
    assert eng.metrics.requests_rejected == 1


def test_deadline_expiry_503(served_model):
    clock = FakeClock()
    eng = _mk_engine(served_model, clock=clock)
    stale = eng.submit([1, 2, 3], max_new_tokens=2, deadline=clock() + 1.0,
                       deadline_class=2)
    fresh = eng.submit([4, 5, 6], max_new_tokens=2, deadline=clock() + 60.0)
    clock.advance(5.0)  # the first request's deadline passes in queue
    eng.run_until_idle()
    r_stale, r_fresh = eng.result(stale), eng.result(fresh)
    assert r_stale.status == "expired" and r_stale.http_status == 503
    assert r_stale.tokens == []
    # The blanket 503 became a structured rejection: machine-readable
    # reason, the request's class, and a queue-depth-derived back-off.
    assert r_stale.reason == "deadline_expired"
    assert r_stale.deadline_class == 2
    assert r_stale.retry_after_s is not None and r_stale.retry_after_s >= 0
    assert r_fresh.status == "ok" and len(r_fresh.tokens) == 2
    assert r_fresh.reason is None
    assert eng.metrics.requests_expired == 1
    # Expiry must free nothing it never held: pool fully drained.
    assert eng.allocator.n_used == 0


def test_admission_snapshot_is_cheap_and_accurate(served_model):
    """The router's polling surface: correct counters, and reading it
    never steps the engine or touches a device value."""
    eng = _mk_engine(served_model, n_blocks=16)
    s0 = eng.admission_snapshot()
    assert s0["queue_depth"] == 0 and s0["running"] == 0
    assert s0["occupancy"] == 0.0
    assert s0["kv_blocks_free"] == 15 and s0["kv_blocks_used"] == 0
    assert s0["queue_slots_free"] == eng.cfg.max_queue
    eng.submit([1, 2, 3], 2)
    eng.submit([4, 5, 6], 2)
    s1 = eng.admission_snapshot()
    assert s1["queue_depth"] == 2
    assert eng.metrics.decode_steps == 0  # polling stepped nothing
    eng.step()
    s2 = eng.admission_snapshot()
    assert s2["queue_depth"] == 0 and s2["running"] == 2
    assert s2["occupancy"] == 0.5
    assert s2["kv_blocks_used"] > 0
    assert s2["batch_slots_free"] == 2
    eng.run_until_idle()
    assert eng.admission_snapshot()["kv_blocks_used"] == 0


def test_withdraw_reclaims_only_queued(served_model):
    eng = _mk_engine(served_model, max_batch=1)
    a = eng.submit([1, 2, 3], 2)
    b = eng.submit([4, 5, 6], 2)
    eng.step()                      # a admitted; b still queued
    assert not eng.withdraw(a)      # already admitted — refuse
    assert eng.withdraw(b)          # queued — reclaimed, no result
    assert not eng.withdraw(b)      # idempotent refuse
    assert not eng.withdraw(12345)  # unknown rid
    eng.run_until_idle()
    assert eng.result(a).status == "ok"
    assert eng.result(b) is None    # dropped without a result by design
    assert eng.allocator.n_used == 0
    # A withdrawn request is un-counted from submitted (the router
    # re-submits it elsewhere, which counts it there): the
    # submitted == finished+expired+rejected balance must hold.
    assert eng.metrics.requests_submitted == 1
    assert eng.metrics.requests_finished == 1


def test_prefill_handoff_roundtrip_bitwise(served_model):
    """Engine-level disaggregation: prefill on engine A, export the
    K/V pages, inject into engine B, decode there — tokens bitwise
    equal to serving entirely on one engine. The prefill-only
    reservation is prompt-sized (no max_new tail held on A)."""
    prompts = _shared_prefix_prompts(3)
    ref = _mk_engine(served_model, **_PFX_KW).generate(prompts, 5)
    pre = _mk_engine(served_model, **_PFX_KW)
    dec = _mk_engine(served_model, **_PFX_KW)
    rids = [pre.submit(p, 5, prefill_only=True) for p in prompts]
    while len(pre.handoff_ready()) < len(prompts):
        pre.step()
    # Prefill-only reservations cover the prompt, not the decode
    # tail — and the 3 shared prefix blocks are held once (the
    # prefill-time second walk dedupes same-step siblings).
    bft = pre.allocator.blocks_for_tokens
    prompt_only = 3 + sum(bft(len(p)) - 3 for p in prompts)
    with_tails = 3 + sum(bft(len(p) + 5) - 3 for p in prompts)
    assert pre.allocator.n_used == prompt_only < with_tails
    out = {}
    for rid in rids:
        h = pre.export_prefilled(rid)
        assert h.generated and len(h.generated) == 1
        drid = dec.inject_prefilled(h)
        out[rid] = drid
    assert pre.allocator.n_used == 0
    assert pre.metrics.handoffs_out == len(prompts)
    assert dec.metrics.handoffs_in == len(prompts)
    dec.run_until_idle()
    got = [dec.result(out[r]).tokens for r in rids]
    assert got == ref
    assert dec.allocator.n_used == 0
    # The injected prompt blocks were published on B: a fresh request
    # with the same prefix hits them without any local prefill of it.
    before = dec.allocator.prefix_hits
    dec.generate([prompts[0]], 5)
    assert dec.allocator.prefix_hits > before


def test_export_running_mid_decode_bitwise(served_model):
    """The migrating-drain seam (ISSUE 11): a RUNNING sequence
    exported mid-decode and injected into another engine finishes
    with EXACTLY the tokens it would have produced in place — the
    pages (prompt AND generated-token K/V, partial tail block
    included) move bitwise. Finished-but-unretired sequences refuse
    to export (they must retire on the donor)."""
    prompts = _shared_prefix_prompts(3)
    ref = _mk_engine(served_model, **_PFX_KW).generate(prompts, 5)
    a = _mk_engine(served_model, **_PFX_KW)
    b = _mk_engine(served_model, **_PFX_KW)
    rids = [a.submit(p, 5) for p in prompts]
    a.step()        # prefill + first decode
    a.step()        # a couple of tokens in — genuinely mid-decode
    assert set(a.running_exportable()) == set(rids)
    moved = {}
    for rid in rids:
        h = a.export_running(rid)
        assert len(h.generated) >= 2
        assert h.n_cached == len(h.prompt) + len(h.generated) - 1
        moved[rid] = b.inject_prefilled(h)
    assert a.allocator.n_used == 0 and not a.pending
    b.run_until_idle()
    assert [b.result(moved[r]).tokens for r in rids] == ref
    assert b.allocator.n_used == 0
    # Unknown and finished rids refuse.
    with pytest.raises(KeyError):
        a.export_running(99999)
    c = _mk_engine(served_model, **_PFX_KW)
    rid = c.submit(prompts[0], 1)
    c.step()
    # max_new=1: finished at prefill, never RUNNING — not exportable.
    assert c.running_exportable() == []


def test_mid_batch_retirement_frees_blocks(served_model):
    eng = _mk_engine(served_model)
    short = eng.submit([1, 2, 3], max_new_tokens=2)
    long = eng.submit([4, 5, 6], max_new_tokens=8)
    used_timeline = []
    while eng.pending:
        eng.step()
        used_timeline.append(eng.allocator.n_used)
    # The short request retired (blocks freed) while the long one was
    # still decoding — continuous batching's defining property.
    assert eng.result(short).status == "ok"
    assert len(eng.result(short).tokens) == 2
    assert len(eng.result(long).tokens) == 8
    peak = max(used_timeline)
    assert used_timeline[-1] == 0
    # Somewhere mid-run usage dropped below peak while work remained.
    drop_idx = next(i for i, u in enumerate(used_timeline) if 0 < u < peak)
    assert any(u > 0 for u in used_timeline[drop_idx:])


def test_kv_backpressure_queues_then_serves(served_model):
    # Pool sized for ~one worst-case sequence: the second request must
    # wait for the first to retire, then still complete correctly.
    eng = _mk_engine(served_model, n_blocks=4, max_prompt=8,
                     max_new_tokens=8)
    a = eng.submit([1, 2, 3, 4, 5], max_new_tokens=8)  # reserves 2 blocks
    b = eng.submit([6, 7, 8, 9, 10], max_new_tokens=8)  # needs 2, 1 free
    eng.step()
    assert eng.metrics.queue_depth == 1  # b held back by the pool
    eng.run_until_idle()
    assert eng.result(a).status == "ok" and eng.result(b).status == "ok"
    assert len(eng.result(b).tokens) == 8
    assert eng.allocator.n_used == 0


def test_continuous_joins_running_batch(served_model):
    # A request submitted while the batch is mid-decode is admitted on
    # the next iteration, not after the batch drains.
    eng = _mk_engine(served_model)
    first = eng.submit([1, 2, 3], max_new_tokens=8)
    eng.step()
    eng.step()
    late = eng.submit([4, 5], max_new_tokens=2)
    eng.step()
    # The late request prefilled while `first` still had tokens to go.
    assert eng.result(first) is None     # first still running
    eng.run_until_idle()
    assert len(eng.result(late).tokens) == 2
    assert len(eng.result(first).tokens) == 8


def test_served_decode_bitwise_matches_single_request(served_model):
    """Acceptance: greedy decode through the full continuous-batching
    path (mixed batch, shared paged pool, slot/block churn) must be
    BITWISE identical to each request served alone."""
    prompts = _prompts(6, rng_seed=3)
    kw = dict(batch_buckets=(4,))  # same decode program both ways
    served = _mk_engine(served_model, **kw).generate(prompts, 5)
    solo_engine = _mk_engine(served_model, **kw)
    solo = [solo_engine.generate([p], 5)[0] for p in prompts]
    assert served == solo


@pytest.mark.slow  # ~24s: the eager full-context reference loop (12
# un-jitted forwards) dominates. Redundancy: the paged decode path is
# pinned BITWISE tier-1 by test_served_decode_bitwise_matches_single_
# request and the cache/chunked parity test, and the math it reuses
# (_rmsnorm/embed_lookup/local_attention) is pinned against references
# by the models/flash tiers — this cross-check against a from-scratch
# full-context forward rides the slow tier (PR 6 budget discipline;
# tier-1 sat at 818s of the 870s timeout on the PR 8 audit).
def test_served_decode_matches_full_forward(served_model):
    """The paged incremental decode agrees with from-scratch
    full-context forward greedy decode (f32, CPU): same argmax token
    at every step."""
    cfg, params = served_model
    prompts = _prompts(3, rng_seed=7)
    outs = _mk_engine(served_model).generate(prompts, 4)

    for p, got in zip(prompts, outs):
        toks = list(p)
        ref = []
        for _ in range(4):
            logits = transformer_forward(
                params, jnp.asarray([toks], jnp.int32), cfg)[0, -1]
            t = int(jnp.argmax(logits.astype(jnp.float32)))
            ref.append(t)
            toks.append(t)
        assert got == ref


def _prompt_attention_cases():
    for kv_heads, group in ((1, "gqa4"), (4, "mha")):
        # two short lengths, which keep the dense form itself; past 512
        # the kernel: a bucket that is one tile, a length that pads
        # inside it, and one past 1024, two tiles a side
        # (``_prompt_block``)
        for length in (16, 384, 640, 700, 1100):
            yield pytest.param(4, kv_heads, length, None,
                               id=f"{group}-{length}")
    # heads sharded over tp as the projections leave them; and a tp
    # that does not divide the KV heads, which are then repeated
    yield pytest.param(8, 2, 640, dict(tp=2), id="gqa4-640-tp2")
    yield pytest.param(8, 2, 640, dict(dp=2, tp=4), id="gqa4-640-dp2tp4")


@pytest.mark.parametrize("heads,kv_heads,length,mesh_axes",
                         list(_prompt_attention_cases()))
def test_prompt_attention_is_local_attention_over_repeated_kv(
        heads, kv_heads, length, mesh_axes, devices):
    """``prefill``'s attention (past 512 tokens the Pallas flash
    forward, the GQA group an index map, interpreted here) against the
    form it replaced there, which stays the plain reference:
    ``local_attention`` over K and V repeated across the group, with
    its ``[H, T, T]`` scores."""
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.parallel.ring_attention import local_attention
    from horovod_tpu.serve.decode import _attend_prompt

    mesh = None
    if mesh_axes:
        n = int(np.prod(list(mesh_axes.values())))
        mesh = build_mesh(devices=devices[:n], **mesh_axes)
    keys = jax.random.split(jax.random.PRNGKey(length), 3)
    q, k, v = (jax.random.normal(key, (1, length, h, 16), jnp.float32) * 0.5
               for key, h in zip(keys, (heads, kv_heads, kv_heads)))
    rep = heads // kv_heads
    want = local_attention(q, jnp.repeat(k, rep, axis=2),
                           jnp.repeat(v, rep, axis=2), causal=True)
    got = jax.jit(lambda q, k, v: _attend_prompt(q, k, v, mesh))(q, k, v)
    assert got.shape == (1, length, heads * 16) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want.reshape(got.shape)),
                               rtol=2e-5, atol=2e-5)


def test_eos_stops_early(served_model):
    cfg, params = served_model
    probe = _mk_engine(served_model).generate([[1, 2, 3]], 8)[0]
    eos = probe[2]  # declare a mid-sequence token as eos
    eng = _mk_engine(served_model, eos_id=eos)
    out = eng.generate([[1, 2, 3]], 8)[0]
    # Generation must stop exactly at the FIRST eos occurrence.
    assert out == probe[:probe.index(eos) + 1]
    assert out[-1] == eos and len(out) < len(probe)
    assert eng.allocator.n_used == 0


@pytest.mark.slow  # ~8s of tp-mesh compiles. Redundancy: the serve
# programs' single-device bitwise parity (incl. the suffix-resume
# path) is pinned tier-1 above, and the tp mesh plumbing these
# programs shard over (tp-sharded params, in-jit psums) is pinned
# tier-1 by test_models::test_transformer_train_step_runs_sharded —
# the serve-side tp variant rides the slow tier with the other
# compile-heavy mesh variants (PR 8 budget audit: 818s/870s).
def test_tp_sharded_decode_matches(served_model, devices):
    """Tensor-parallel decode over the mesh (tp-sharded params + KV
    pool, GSPMD psums on the hot loop) produces the same tokens —
    including through the prefix-cache suffix-resume path (the shared
    8-token prefix makes request 2+ take it)."""
    from horovod_tpu.parallel import build_mesh

    cfg, params = served_model
    shared = list(range(1, 9))       # one whole block at block_size 8
    prompts = [shared + p for p in _prompts(3, rng_seed=11, lo=2, hi=6)]
    ref = _mk_engine(served_model).generate(prompts, 4)
    mesh = build_mesh(dp=4, tp=2)
    params_sh = init_transformer(cfg, jax.random.PRNGKey(0), mesh)
    eng = ServeEngine(cfg, params_sh,
                      ServeConfig(max_batch=4, block_size=8, max_prompt=16,
                                  max_new_tokens=8), mesh=mesh)
    assert eng.generate(prompts, 4) == ref


def test_metrics_snapshot_and_trace(served_model, tmp_path):
    eng = _mk_engine(served_model)
    eng.generate(_prompts(3, rng_seed=5), 3)
    snap = eng.metrics.snapshot()
    assert snap["requests_finished"] == 3
    assert snap["tokens_generated"] == 9
    assert snap["decode_steps"] > 0 and snap["prefill_steps"] == 3
    assert snap["tokens_per_sec"] > 0
    assert snap["p99_first_token_ms"] >= snap["p50_first_token_ms"] >= 0
    assert 0 < snap["batch_occupancy"] <= 1
    # Block-pool gauges ride every snapshot (high_water used to be
    # computed but never reported anywhere).
    assert snap["kv_blocks_high_water"] == eng.allocator.high_water > 0
    assert snap["kv_blocks_in_use"] == 0          # all retired
    assert snap["kv_blocks_cached"] == eng.allocator.n_cached
    assert snap["prefix_block_evictions"] == 0
    assert 0.0 <= snap["prefix_cache_hit_rate"] <= 1.0
    path = tmp_path / "serve_trace.json"
    eng.metrics.export_chrome_trace(str(path))
    import json
    events = json.loads(path.read_text())["traceEvents"]
    names = {e["name"] for e in events}
    assert {"serve:prefill", "serve:decode"} <= names
    # Pool occupancy exported as a chrome counter track.
    counters = [e for e in events if e["ph"] == "C"
                and e["name"] == "kv_blocks"]
    assert counters and all(
        {"in_use", "cached"} <= set(e["args"]) for e in counters)
    assert max(e["args"]["in_use"] for e in counters) > 0


# ---------------------------------------------------------------------------
# Prefix caching + chunked prefill
# ---------------------------------------------------------------------------

# One shared geometry for every engine below -> one compiled fn set
# (make_serve_fns memoizes on it), keeping tier-1 compile cost flat.
_PFX_KW = dict(max_batch=4, block_size=4, max_prompt=24,
               max_new_tokens=6, batch_buckets=(4,),
               prefill_buckets=(4, 8, 16, 24))


def _shared_prefix_prompts(n=5, prefix_len=12, rng_seed=21):
    rng = np.random.RandomState(rng_seed)
    prefix = rng.randint(1, 256, size=prefix_len).tolist()
    return [prefix + rng.randint(1, 256,
                                 size=int(rng.randint(2, 6))).tolist()
            for _ in range(n)]


def test_prefix_cache_maps_shared_blocks(served_model):
    prompts = _shared_prefix_prompts()
    eng = _mk_engine(served_model, **_PFX_KW)
    eng.generate(prompts, 4)
    a = eng.allocator
    # 12-token prefix = 3 whole blocks; every request after the first
    # maps them instead of re-prefilling (the second walk at prefill
    # time catches even same-step burst siblings).
    assert a.prefix_hits >= 3 * (len(prompts) - 1)
    snap = eng.metrics.snapshot()
    assert snap["prefix_cache_hit_rate"] > 0.5
    assert snap["prefix_hit_tokens"] >= 12 * (len(prompts) - 1)
    # Retired sequences parked their registered blocks in the cache
    # pool: capacity is free, content is warm.
    assert a.n_used == 0 and a.n_cached > 0
    # A fresh same-prefix request pays only its suffix.
    before = a.prefix_hits
    eng.generate([prompts[0]], 4)
    assert a.prefix_hits >= before + 3


def test_prefix_cache_sharing_holds_one_refcount_per_seq(served_model):
    # Two same-prefix sequences decoding concurrently share physical
    # prefix blocks: total blocks in use < 2x the solo footprint.
    prompts = _shared_prefix_prompts(2)
    eng = _mk_engine(served_model, **_PFX_KW)
    r1 = eng.submit(prompts[0], 6)
    r2 = eng.submit(prompts[1], 6)
    eng.step()
    assert eng.allocator.n_used < 2 * eng.allocator.blocks_for_tokens(
        len(prompts[0]) + 6)
    shared = [b for b in eng.allocator._refs
              if eng.allocator.refcount(b) == 2]
    assert len(shared) == 3          # the three whole prefix blocks
    eng.run_until_idle()
    assert (eng.result(r1).status == "ok"
            and eng.result(r2).status == "ok")
    assert eng.allocator.n_used == 0


def test_admission_counts_cached_revivals_against_capacity(served_model):
    """Overcommitted pool: admission's capacity check must count the
    revival of refcount-0 cached matched blocks (they consume free
    capacity exactly like fresh allocations). Miscounting popped the
    request and then blew OutOfBlocks mid-admission instead of
    applying backpressure."""
    prompts = _shared_prefix_prompts(3)
    need = -(-(len(max(prompts, key=len)) + 6) // 4)
    # Pool sized so one sequence fits with almost nothing spare: the
    # second same-prefix request's matched blocks are refcount-0
    # cached (first retired), and its fresh-block need exceeds what
    # remains once the revivals are accounted.
    eng = _mk_engine(served_model, **_PFX_KW, n_blocks=need + 2)
    outs = eng.generate(prompts, 6)      # serialized by backpressure
    assert [len(o) for o in outs] == [6, 6, 6]
    assert eng.allocator.n_used == 0
    # Same prompts again through the now-warm (and repeatedly
    # evicted) cache: still completes, never raises.
    assert eng.generate(prompts, 6) == outs


def test_prefix_cache_and_chunked_bitwise_parity(served_model):
    """Acceptance: decoded token streams are bitwise identical with
    the prefix cache on vs off, and with chunked prefill vs
    monolithic, on a shared-prefix trace. (docs/serving.md points at
    this test by name — an earlier edit had merged it into the
    revival-accounting test above.)"""
    prompts = _shared_prefix_prompts(6)
    ref = _mk_engine(served_model, **_PFX_KW,
                     prefix_caching=False).generate(prompts, 5)
    cached = _mk_engine(served_model, **_PFX_KW).generate(prompts, 5)
    chunked = _mk_engine(served_model, **_PFX_KW,
                         prefill_chunk=4).generate(prompts, 5)
    chunked_nocache = _mk_engine(
        served_model, **_PFX_KW, prefix_caching=False,
        prefill_chunk=4).generate(prompts, 5)
    assert cached == ref
    assert chunked == ref
    assert chunked_nocache == ref


def test_long_cold_prompt_through_the_kernel_matches_chunked_prefill():
    """A cold prompt past ``_DENSE_PROMPT`` is one monolithic
    ``prefill`` that attends through the flash forward (interpreted
    here); the same prompt in chunks runs ``prefill_resume``'s paged
    attention over the pool. Two attentions that share no code give
    the same tokens, and the first is the full forward's."""
    from horovod_tpu.serve.decode import _DENSE_PROMPT

    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False,
                                 max_seq=1024)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    prompt = np.random.RandomState(35).randint(1, 256, size=600).tolist()
    assert len(prompt) > _DENSE_PROMPT
    kw = dict(max_batch=2, block_size=16, max_prompt=640, max_new_tokens=4,
              prefill_buckets=(128, 640), prefix_caching=False)

    def serve(**more):
        eng = ServeEngine(cfg, params, ServeConfig(**kw, **more),
                          clock=FakeClock())
        return eng.generate([prompt], 4)[0], eng.metrics.prefill_steps

    whole, calls = serve()
    assert calls == 1
    chunked, calls = serve(prefill_chunk=128)
    assert calls == 5
    assert whole == chunked
    logits = transformer_forward(params, jnp.asarray([prompt], jnp.int32),
                                 cfg)[0, -1]
    assert whole[0] == int(jnp.argmax(logits.astype(jnp.float32)))


def test_chunked_prefill_interleaves_with_decode(served_model):
    """A long prompt streams in across steps while the running batch
    keeps decoding; the chunking sequence holds its blocks but stays
    out of the decode batch until prefill completes."""
    eng = _mk_engine(served_model, **_PFX_KW, prefill_chunk=4,
                     prefix_caching=False)
    short = eng.submit([1, 2, 3], 6)
    eng.step()                       # short prefills + first decode
    rng = np.random.RandomState(3)
    long_rid = eng.submit(rng.randint(1, 256, size=20).tolist(), 2)
    eng.step()                       # long admitted + chunk 1 of 5
    assert eng._prefilling and eng._prefilling[0].rid == long_rid
    held = eng.allocator.blocks_for_tokens(20 + 2)
    decode_before = eng.metrics.decode_steps
    interleaved = 0
    while eng._prefilling:
        # Mid-prefill the sequence holds its whole reservation but is
        # not in the decode batch and has no result yet.
        assert eng.allocator.n_used >= held
        assert all(s.rid != long_rid for s in eng._active)
        assert eng.result(long_rid) is None
        eng.step()
        interleaved += 1
    # 20 tokens at chunk 4 = 5 chunks: one at admission, the rest one
    # per iteration interleaved with decode.
    assert interleaved >= 4
    # Decode kept running during those steps — the long prompt never
    # monopolized an iteration (the chunking claim).
    assert eng.metrics.decode_steps - decode_before >= 3
    eng.run_until_idle()
    assert len(eng.result(long_rid).tokens) == 2
    assert len(eng.result(short).tokens) == 6
    assert eng.allocator.n_used == 0


# ---------------------------------------------------------------------------
# ISSUE 18: MoE models through the serving stack (GSPMD dispatch —
# the island is a training-path construct; docs/serving.md).
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_moe_model():
    """A tiny MoE LM (8 experts, top-2) — computed once; n_layers=1
    keeps the per-bucket serve compiles cheap.

    moe_capacity_factor=4.0 so capacity NEVER binds (top-2 over 8
    experts puts at most T claims on one expert; C = ceil(2·T·4/8) ≥
    T): capacity dropping couples tokens across time in a full-context
    forward, while incremental decode routes each new token alone — a
    trained-in mismatch of capacity-based MoE, so serve parity with
    the full forward is only exact when nothing overflows
    (docs/serving.md spells out this deployment guidance)."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False,
                                 n_layers=1, n_experts=8,
                                 moe_capacity_factor=4.0)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_moe_served_decode_bitwise_and_tracks_forward(served_moe_model):
    """MoE decode parity, tier-1: batched serving of an MoE model is
    bitwise-identical to serving each request alone (batching cannot
    change routing — capacity is per batch row), and the paged
    incremental decode emits the same greedy tokens as a from-scratch
    full-context forward (the router sees identical hidden states
    with or without the KV cache)."""
    cfg, params = served_moe_model
    prompts = _prompts(3, rng_seed=13)
    batched = _mk_engine(served_moe_model).generate(prompts, 4)
    for p, got in zip(prompts, batched):
        alone = _mk_engine(served_moe_model).generate([p], 4)[0]
        assert got == alone
    # Full-forward cross-check on one prompt (kept short — the eager
    # reference forward is the expensive part of the dense slow-tier
    # variant; 3 steps of a 1-layer model stays in the tier budget).
    toks = list(prompts[0])
    ref = []
    for _ in range(3):
        logits = transformer_forward(
            params, jnp.asarray([toks], jnp.int32), cfg)[0, -1]
        t = int(jnp.argmax(logits.astype(jnp.float32)))
        ref.append(t)
        toks.append(t)
    assert batched[0][:3] == ref


@pytest.mark.slow  # ~30s of ep-mesh serve compiles; redundancy: the
# meshless MoE decode parity above pins the routing/KV math tier-1 and
# test_tp_sharded_decode_matches pins mesh-sharded serving generally —
# this adds the expert-sharded (ep) overlap of the two, so it rides
# the slow tier (ISSUE 18 budget note).
def test_ep_sharded_decode_matches(served_moe_model, devices):
    """Expert-parallel decode parity: serving with the experts sharded
    over ep=8 (GSPMD lowers the dispatch einsums to alltoalls on the
    decode hot loop) emits exactly the meshless engine's tokens."""
    from horovod_tpu.parallel import build_mesh

    cfg, _params = served_moe_model
    prompts = _prompts(3, rng_seed=17, lo=2, hi=8)
    ref = _mk_engine(served_moe_model).generate(prompts, 4)
    mesh = build_mesh(ep=-1)
    params_sh = init_transformer(cfg, jax.random.PRNGKey(0), mesh)
    eng = ServeEngine(cfg, params_sh,
                      ServeConfig(max_batch=4, block_size=8, max_prompt=16,
                                  max_new_tokens=8), mesh=mesh)
    assert eng.generate(prompts, 4) == ref


# ---------------------------------------------------------------------------
# Decode step n+1 launched before step n is read (PR 37)
# ---------------------------------------------------------------------------

def _sync_step(eng):
    """A step of the engine as it was before PR 37: the decode call is
    read in the step that launched it."""
    eng.step()
    eng._drain("idle")


def _serve_staged(eng, arrivals, step):
    """``arrivals``: {step index: [(prompt, max_new), ...]}. Submits as
    the steps come and serves to the end; returns the tokens in the
    order of arrival."""
    rids, i = [], 0
    while eng.pending or i <= max(arrivals):
        for prompt, max_new in arrivals.get(i, ()):
            rids.append(eng.submit(prompt, max_new))
        step(eng)
        i += 1
        assert i < 500
    return [eng.result(r).tokens for r in rids]


def _two_cache_engines(**kw):
    """(a maker of engines over two kinds of cache, the vocabulary)."""
    import test_trinity as tri
    cfg = tri.tiny()
    params = tri.seeded(cfg)
    return (lambda: tri.engine_for(cfg, params, **kw)), cfg.vocab_size


@pytest.mark.parametrize("kind", ["dense", "two_caches"])
def test_launched_ahead_the_tokens_are_the_synchronous_engine_s(
        served_model, kind):
    """Mixed lengths, joins into a running batch, retirements, a queue
    that waits for a slot: the tokens are bitwise those of the engine
    that reads each decode call in the step that launched it, and of
    each request served alone."""
    if kind == "dense":
        def mk():
            return _mk_engine(served_model, max_batch=4, max_new_tokens=16)
        vocab, lo, hi = 256, 3, 14
    else:
        mk, vocab = _two_cache_engines(max_new_tokens=16)
        lo, hi = 5, 40
    rng = np.random.RandomState(5)
    reqs = [(rng.randint(1, vocab, size=int(rng.randint(lo, hi))).tolist(),
             int(n)) for n in (9, 3, 16, 5, 12, 2, 7, 11)]
    arrivals = {0: reqs[:3], 2: reqs[3:4], 7: reqs[4:7], 15: reqs[7:]}
    ahead, sync = mk(), mk()
    got = _serve_staged(ahead, arrivals, lambda e: e.step())
    want = _serve_staged(sync, arrivals, _sync_step)
    assert got == want
    assert [len(t) for t in got] == [n for _, n in reqs]
    for (prompt, n), tokens in zip(reqs[:4], got):
        assert mk().generate([prompt], n)[0] == tokens
    a, s = ahead.metrics.snapshot(), sync.metrics.snapshot()
    assert a["decode_ahead_total"] > 10 and s["decode_ahead_total"] == 0
    assert a["tokens_generated"] == s["tokens_generated"] == sum(
        n for _, n in reqs)
    assert ahead.allocator.n_used == sync.allocator.n_used == 0
    # a call launched ahead was never a bigger bucket's, and a drain
    # for every cause the traffic has was counted
    assert a["decode_drains_prefill_total"] >= 3
    assert a["decode_drains_idle_total"] >= 1


def _in_flight_engine(served_model, n=1, **kw):
    """An engine with ``n`` sequences mid-decode and a call in flight."""
    eng = _mk_engine(served_model, **kw)
    rids = [eng.submit(p, 8) for p in _prompts(n, rng_seed=3)]
    eng.step()                  # prefills, the first decode call launched
    eng.step()                  # the second launched ahead, the first read
    assert eng._in_flight is not None and eng.pending
    assert eng.metrics.decode_ahead_total == 1
    assert sum(eng.metrics.decode_drains.values()) == 0
    return eng, rids


_DRAINS = {
    # what happens with a call in flight -> the cause counted (None: no
    # drain, the call stays in flight and the device is not touched)
    "prefill_due": (lambda e, r: (e.submit([9, 8, 7], 4), e.step()),
                    "prefill"),
    "export_running": (lambda e, r: e.export_running(r[0]), "migrate"),
    "running_exportable": (lambda e, r: e.running_exportable(), "migrate"),
    "inject_prefilled": (
        lambda e, r: e.inject_prefilled(
            _in_flight_engine((e.model_cfg, e._params))[0].export_running(0)),
        "migrate"),
    "admission_snapshot": (lambda e, r: e.admission_snapshot(), None),
    "submit": (lambda e, r: e.submit([9, 8, 7], 4), None),
    "withdraw": (lambda e, r: e.withdraw(e.submit([9, 8, 7], 4)), None),
    "result": (lambda e, r: e.result(r[0]), None),
    "cached_chain_len": (lambda e, r: e.cached_chain_len([b"x"]), None),
}


@pytest.mark.parametrize("what", sorted(_DRAINS))
def test_what_reads_the_call_in_flight_and_what_never_does(
        served_model, what):
    act, cause = _DRAINS[what]
    eng, rids = _in_flight_engine(served_model)
    flying = eng._in_flight
    held = [len(s.generated) for s in eng._active]
    act(eng, rids)
    drains = {c: n for c, n in eng.metrics.decode_drains.items() if n}
    if cause is None:
        assert eng._in_flight is flying and eng.pending and not drains
        assert [len(s.generated) for s in eng._active] == held
    else:
        assert drains == {cause: 1}
        assert eng._in_flight is not flying
        assert eng.metrics.snapshot()[f"decode_drains_{cause}_total"] == 1
    # served to the end all the same, every token there
    eng.run_until_idle()
    for rid in rids:
        if eng.result(rid) is not None:
            assert len(eng.result(rid).tokens) == 8
    assert not eng.pending and eng._in_flight is None


@pytest.mark.parametrize("what,cause", [
    ("last_sequence_ends", "idle"), ("smaller_bucket", "bucket"),
    ("a_request_waits_for_the_slot", "admit")])
def test_drains_the_host_can_foresee(served_model, what, cause):
    """The host knows a step ahead that a sequence reaches its
    max_new_tokens: nothing is launched behind a call that ends the
    last one, a smaller batch goes to its smaller bucket, and a queued
    request is not made to wait a step for the slot."""
    eng = _mk_engine(served_model, max_batch=2)
    p = _prompts(3, rng_seed=8)
    want = [_mk_engine(served_model).generate([q], n)[0]
            for q, n in zip(p, (4, 8, 5))]
    rids = [eng.submit(p[0], 4)]
    if what != "last_sequence_ends":
        rids.append(eng.submit(p[1], 8))
    if what == "a_request_waits_for_the_slot":
        rids.append(eng.submit(p[2], 5))
    steps = 0
    while eng.metrics.decode_drains[cause] == 0:
        assert eng.pending
        eng.step()
        steps += 1
        assert steps < 50
    # the call that held request 0's last token has just been read
    assert eng.metrics.decode_drains == {
        **dict.fromkeys(eng.metrics.decode_drains, 0), cause: 1}
    assert len(eng._active[0].generated) == 4 and eng.result(rids[0]) is None
    if what == "last_sequence_ends":
        assert eng._in_flight is None and eng.pending    # not retired yet
    elif what == "smaller_bucket":
        assert len(eng._in_flight.rows) == 1             # of buckets 1, 2
        assert eng._in_flight.call.args["ahead"] is False
    else:
        assert eng._in_flight is None
        eng.step()              # retires, admits, prefills, launches for 2
        assert eng.result(rids[0]) is not None
        assert eng._in_flight.call.args["n_active"] == 2
    eng.run_until_idle()
    assert [eng.result(r).tokens for r in rids] == want[:len(rids)]


def test_eos_found_a_step_late_discards_one_row(served_model):
    """Sequence a's token of call n is ``eos_id``; call n+1 was
    launched with a row for it. That row's token is discarded, a's
    blocks stay reserved until n+1 is read, and b, in the same batch,
    never notices."""
    pa, pb = [1, 2, 3], [7, 5, 3, 2]
    probe = _mk_engine(served_model).generate([pa], 8)[0]
    k = next(i for i in range(2, 7) if probe[i] not in probe[:i])
    eos = probe[k]
    alone_b = _mk_engine(served_model, eos_id=eos).generate([pb], 8)[0]
    clock = FakeClock()
    eng = _mk_engine(served_model, eos_id=eos, clock=clock)
    a, b = eng.submit(pa, 8, trace_id=7), eng.submit(pb, 8, trace_id=9)
    seq_a = used = None
    while eng.result(a) is None:
        clock.advance(1.0)
        eng.step()
        seq_a = seq_a or eng._active[0]
        if seq_a.generated[-1] == eos and used is None:
            # found at this step's read; the follower is in flight
            # with a's row discarded, and a keeps what it holds
            fl = eng._in_flight
            assert fl.rows[0] is None and fl.held == {a}
            assert fl.call.args["n_active"] == 1
            assert fl.call.args["traces"] == [9]
            assert seq_a in eng._active
            assert set(seq_a.blocks) <= set(
                blk for s in eng._active for blk in s.blocks)
            used = eng.allocator.n_used
            cursor = seq_a.n_cached
    res = eng.result(a)
    assert res.tokens == probe[:k + 1] and res.tokens[-1] == eos
    assert len(res.token_times) == k + 1 and seq_a.n_cached == cursor
    assert res.token_times == sorted(set(res.token_times))
    # retired a step after the follower's read, and its blocks are back
    assert res.finished_at == res.token_times[-1] + 2.0
    assert eng.allocator.n_used < used
    # ... and reusable: a newcomer takes them while b goes on
    c = eng.submit(pa, 8)
    eng.run_until_idle()
    assert eng.result(b).tokens == alone_b
    assert eng.result(c).tokens == res.tokens
    assert eng.allocator.n_used == 0
    assert eng.metrics.tokens_generated == (
        2 * (k + 1) + len(alone_b))


def test_launching_ahead_meets_no_new_entry_of_the_jitted_decode(
        served_model):
    """What the benchmark's warm-up relies on: one prefill and one
    decode call of a bucket (read with nothing launched behind it) have
    met every program and every kind of argument that a long run of
    calls launched ahead meets."""
    eng = _mk_engine(served_model, max_new_tokens=16)
    for n in (1, 2, 4):
        for p in _prompts(n, rng_seed=n):
            eng.submit(p, 2)
        eng.run_until_idle()
    assert eng.metrics.decode_ahead_total == 0
    entries = eng._decode_fn._cache_size()
    for p in _prompts(4, rng_seed=11):
        eng.submit(p, 16)
    eng.run_until_idle()
    eng.generate(_prompts(2, rng_seed=12), 9)
    assert eng.metrics.decode_ahead_total > 20
    assert eng._decode_fn._cache_size() == entries
