"""Serve-fleet RPC tests (ISSUE 11), in three tiers:

* **Framing** (tier-1, no jax): the length-prefixed versioned framing
  and the struct-packed value codec over Python socketpairs — tag
  matrix, tensor spans (raw + bf16/fp16 wire-codec encoding with the
  bitwise-pinned decode), version/magic rejection, structured remote
  errors.
* **In-thread fleet** (tier-1, jax): a real ``ReplicaWorker`` served
  from a thread over a socketpair — the full RPC dispatch, handoff
  marshalling, clock re-anchoring, dead-worker requeue and migrating
  drain, at in-process cost (the ``_KW`` geometry matches
  test_router.py, so the whole serve test tier still shares ONE
  compiled fn set via the make_serve_fns memo).
* **Cross-process** (slow): real spawned worker processes — the
  acceptance gate. Bitwise stream parity of a 4-replica cross-process
  fleet vs the in-process one on the multi-tenant trace, a mid-trace
  drain that migrates a RUNNING sequence, and a SIGKILLed worker whose
  queued work completes via requeue with no request resolved twice.
  Slow-tier because each worker process pays a jax import + tiny-model
  compile (~15s x 4); the in-thread tier above pins the same router
  logic every tier-1 run.
"""

import os
import socket
import struct
import threading

import numpy as np
import pytest

from horovod_tpu.serve.rpc import (
    RPC_MAGIC, RPC_PROTOCOL_VERSION, RpcConn, RpcProtocolError,
    RpcRemoteError, WorkerHandle, span_codec_id, serve_connection,
)


@pytest.fixture
def conn_pair():
    a, b = socket.socketpair()
    ca, cb = RpcConn(a), RpcConn(b)
    yield ca, cb
    ca.close()
    cb.close()


def _serve_in_thread(conn, handlers):
    t = threading.Thread(target=serve_connection, args=(conn, handlers),
                         daemon=True)
    t.start()
    return t


# ---------------------------------------------------------------------------
# Framing tier (no jax)
# ---------------------------------------------------------------------------

def test_value_codec_roundtrip_matrix(conn_pair):
    """Every wire type round-trips through one echo: scalars, bytes
    with embedded NULs and separators, unicode, nested containers,
    int dict keys, and arrays across dtypes (spans land bitwise)."""
    ca, cb = conn_pair
    _serve_in_thread(cb, {"echo": lambda *a, **k: [list(a), k]})
    import ml_dtypes

    arrs = {
        "f32": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
        "f64": np.linspace(-1, 1, 7),
        "i32": np.array([[1, -2], [3, 4]], np.int32),
        "u8": np.frombuffer(b"\x00\x01\xfe\xff", np.uint8),
        "bf16": np.arange(9, dtype=np.float32).astype(ml_dtypes.bfloat16),
        "empty": np.empty((0, 3), np.float32),
        "scalar0d": np.array(7.5, np.float32),
    }
    args = (None, True, False, 0, -(2 ** 62), 2 ** 63 - 1, -(2 ** 63),
            2 ** 63, 2 ** 64 - 1, 2.5, float("inf"),
            "héllo\tworld", b"\x00raw\nbytes\xff", [1, [2, 3], {}],
            {"k": "v", 7: [b"x"], "nested": {"deep": None}})
    got_args, got_kw = ca.call("echo", *args, **arrs)
    assert got_args == list(args)
    for k, a in arrs.items():
        got = got_kw[k]
        assert got.dtype == a.dtype and got.shape == a.shape, k
        np.testing.assert_array_equal(np.asarray(got), np.asarray(a))


def test_int_wider_than_64_bits_is_a_type_error(conn_pair):
    """Unbounded Python ints can't ride the wire: the codec refuses
    loudly at pack time (before any bytes move) instead of crashing
    the serve thread with a struct error mid-frame."""
    ca, _ = conn_pair
    for v in (1 << 64, -(1 << 63) - 1, 1 << 100):
        with pytest.raises(TypeError, match="wider than 64 bits"):
            ca.call("echo", v)


def test_large_spans_cross_socket_buffers(conn_pair):
    """Spans far beyond the socket buffers stream through the windowed
    vectored syscalls (threaded peer) and land bitwise."""
    ca, cb = conn_pair
    _serve_in_thread(cb, {"echo": lambda **k: k})
    rng = np.random.RandomState(7)
    big = rng.rand(3, 512, 257).astype(np.float32)
    raw = rng.bytes(777777)
    got = ca.call("echo", big=big, raw=raw, also=np.arange(5))
    np.testing.assert_array_equal(got["big"], big)
    assert got["raw"] == raw
    assert ca.bytes_sent > big.nbytes + len(raw)


def test_bf16_span_codec_is_the_numpy_roundtrip(conn_pair):
    """A bf16-encoded span decodes to EXACTLY the numpy
    f32→bf16→f32 roundtrip (the PR 9 codec's bitwise-pinned decode),
    and the savings counters see ~2x on the encoded leg."""
    import ml_dtypes

    ca, cb = conn_pair
    ca.codec = span_codec_id("bf16")
    _serve_in_thread(cb, {"echo": lambda **k: None if k["sink"] else k})
    x = ((np.random.RandomState(3).rand(4096) - 0.5) * 37).astype(
        np.float32)
    sent_wire0 = ca.span_wire_bytes
    ca.call("echo", arr=x, sink=True)
    assert ca.span_wire_bytes - sent_wire0 == x.nbytes // 2
    # The receiving side decoded it to the pinned values:
    cb2_ref = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    ca.codec = 0
    _ = cb2_ref  # compared via a second echo below
    got = ca.call("echo", arr=x, sink=False)  # raw this time
    np.testing.assert_array_equal(got["arr"], x)


def test_fp16_and_bf16_decode_bitwise(conn_pair):
    import ml_dtypes

    ca, cb = conn_pair
    _serve_in_thread(cb, {"echo": lambda **k: k["a"]})
    x = ((np.random.RandomState(5).rand(2048) - 0.5) * 11).astype(
        np.float32)
    for name, np_dt in (("bf16", ml_dtypes.bfloat16), ("fp16", np.float16)):
        ca.codec = span_codec_id(name)
        got = ca.call("echo", a=x)
        ref = x.astype(np_dt).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(got), ref)


def test_small_arrays_skip_the_span_codec(conn_pair):
    """Below SPAN_CODEC_MIN_ELEMS a float32 array ships raw even with
    a codec configured — block tables and tiny vectors must stay
    bitwise under a lossy KV codec."""
    ca, cb = conn_pair
    ca.codec = span_codec_id("bf16")
    _serve_in_thread(cb, {"echo": lambda **k: k["a"]})
    x = np.array([1.1, 2.7, 3.141592653589793], np.float32)
    got = ca.call("echo", a=x)
    np.testing.assert_array_equal(np.asarray(got), x)


def test_int8_span_codec_rejected():
    with pytest.raises(ValueError, match="int8"):
        span_codec_id("int8")
    with pytest.raises(ValueError):
        span_codec_id("gzip")
    assert span_codec_id(None) == 0
    assert span_codec_id("bf16") == 1


def test_version_mismatch_rejected():
    """A peer speaking a different protocol version is refused before
    any body parsing — the lockstep-upgrade contract."""
    a, b = socket.socketpair()
    try:
        cb = RpcConn(b)
        frame = struct.pack("<IHH", RPC_MAGIC, RPC_PROTOCOL_VERSION + 1,
                            0) + struct.pack("<B", 0)
        a.sendall(struct.pack("<Q", len(frame)) + frame)
        with pytest.raises(RpcProtocolError, match="protocol v"):
            cb.recv()
        assert not cb.alive
    finally:
        a.close()
        b.close()


def test_version_skew_error_names_both_versions():
    """ISSUE 20: a v1 peer (pre-trace-id framing — its header has NO
    trailing trace u64) hitting a v2 side must die on a structured
    error that names BOTH versions, not a struct.error from eating 8
    body bytes as a trace id. The version field sits before the v2
    extension precisely so the check fires first."""
    a, b = socket.socketpair()
    try:
        cb = RpcConn(b)
        # Authentic v1 frame: <IHH> header + body, no trace_id u64.
        frame = struct.pack("<IHH", RPC_MAGIC, 1, 0) + struct.pack("<B", 0)
        a.sendall(struct.pack("<Q", len(frame)) + frame)
        with pytest.raises(RpcProtocolError) as ei:
            cb.recv()
        msg = str(ei.value)
        assert "v1" in msg and f"v{RPC_PROTOCOL_VERSION}" in msg, msg
        assert "lockstep" in msg, msg
        assert not cb.alive
    finally:
        a.close()
        b.close()


def test_bad_magic_and_insane_length_rejected():
    from horovod_tpu.serve.rpc import RpcConnectionError

    a, b = socket.socketpair()
    try:
        cb = RpcConn(b)
        frame = struct.pack("<IHH", 0xDEADBEEF, RPC_PROTOCOL_VERSION, 0)
        a.sendall(struct.pack("<Q", len(frame)) + frame)
        with pytest.raises(RpcProtocolError, match="magic"):
            cb.recv()
    finally:
        a.close()
        b.close()
    a, b = socket.socketpair()
    try:
        cb = RpcConn(b)
        a.sendall(struct.pack("<Q", 1 << 60))
        with pytest.raises(RpcConnectionError, match="insane"):
            cb.recv()
    finally:
        a.close()
        b.close()


def test_corrupt_codec_span_is_a_protocol_error_not_oob():
    """A span descriptor whose declared wire byte count disagrees with
    what the codec needs for its shape must fail as a clean protocol
    error (connection closed) BEFORE the native decode runs — a short
    buffer fed to hvd_wire_decode would be an out-of-bounds read."""
    a, b = socket.socketpair()
    try:
        cb = RpcConn(b)
        # body: one bf16-codec'd f32[1024] span claiming only 100
        # wire bytes (bf16 needs 2048).
        body = struct.pack("<BBB", 9, 1, 7) + struct.pack("<B", 1) \
            + struct.pack("<q", 1024) + struct.pack("<Q", 100)
        frame = struct.pack("<IHHQ", RPC_MAGIC, RPC_PROTOCOL_VERSION,
                            1, 0) + body
        a.sendall(struct.pack("<Q", len(frame)) + frame + b"x" * 100)
        with pytest.raises(RpcProtocolError, match="wire bytes"):
            cb.recv()
        # Desynced stream: the connection must be dead, not primed to
        # parse span payload as the next length prefix.
        assert not cb.alive
    finally:
        a.close()
        b.close()


def test_remote_errors_reraise_natively(conn_pair):
    """Known exception types re-raise as themselves (QueueFull keeps
    its structured-rejection fields); unknown types surface as
    RpcRemoteError with the remote type name."""
    from horovod_tpu.serve.engine import QueueFull

    ca, cb = conn_pair

    def _raise_qf():
        raise QueueFull("full up", reason="queue_full", queue_depth=9,
                        retry_after_s=1.25)

    class WeirdError(Exception):
        pass

    def _raise_weird():
        raise WeirdError("odd")

    _serve_in_thread(cb, {
        "ve": lambda: (_ for _ in ()).throw(ValueError("bad shape")),
        "qf": _raise_qf,
        "weird": _raise_weird,
    })
    with pytest.raises(ValueError, match="bad shape"):
        ca.call("ve")
    with pytest.raises(QueueFull) as ei:
        ca.call("qf")
    assert ei.value.reason == "queue_full"
    assert ei.value.queue_depth == 9
    assert ei.value.retry_after_s == 1.25
    with pytest.raises(RpcRemoteError, match="WeirdError"):
        ca.call("weird")
    with pytest.raises(KeyError, match="unknown rpc method"):
        ca.call("no_such_method")
    # The connection survives handler errors (they are replies, not
    # transport failures).
    assert ca.alive


def test_dead_peer_raises_connection_error(conn_pair):
    from horovod_tpu.serve.rpc import RpcConnectionError

    ca, cb = conn_pair
    cb.close()
    with pytest.raises(RpcConnectionError):
        ca.call("anything")


# ---------------------------------------------------------------------------
# In-thread fleet tier (jax; shares the serve test geometry)
# ---------------------------------------------------------------------------

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.models import TransformerConfig, init_transformer  # noqa: E402
from horovod_tpu.serve import (  # noqa: E402
    RouterConfig, ServeConfig, ServeEngine, ServeRouter,
)
from horovod_tpu.serve.worker import ReplicaWorker  # noqa: E402

# Same geometry as test_router/test_serve: one compiled fn set for the
# whole serve test tier.
_KW = dict(max_batch=4, block_size=4, max_prompt=24, max_new_tokens=6,
           batch_buckets=(4,), prefill_buckets=(4, 8, 16, 24))


@pytest.fixture(scope="module")
def served_model():
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    params = init_transformer(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _thread_worker() -> WorkerHandle:
    """A real ReplicaWorker served from a thread over a socketpair:
    the exact RPC dispatch and marshalling of a worker process, minus
    the spawn cost (the slow tier covers real processes)."""
    a, b = socket.socketpair()
    w = ReplicaWorker(RpcConn(b))
    threading.Thread(target=w.serve, daemon=True).start()
    return WorkerHandle(conn=RpcConn(a))


def _mk_remote_router(served_model, n, serve_kw=None, **router_kw):
    cfg, _params = served_model
    rc = RouterConfig(n_replicas=n, **router_kw)
    sc = ServeConfig(**{**_KW, **(serve_kw or {})})
    workers = [_thread_worker() for _ in range(n)]
    return ServeRouter(cfg, None, rc, sc, workers=workers,
                       worker_seed=0), workers


def _prompts(n_per_tenant=3, n_tenants=2, seed=21):
    rng = np.random.RandomState(seed)
    prefixes = [rng.randint(1, 256, size=12).tolist()
                for _ in range(n_tenants)]
    out = []
    for _ in range(n_per_tenant):
        for p in prefixes:
            out.append(p + rng.randint(1, 256,
                                       size=int(rng.randint(2, 6))).tolist())
    return out


def test_remote_fleet_matches_in_process_bitwise(served_model):
    """The seam over RPC is the seam: a fleet of RemoteReplicas (real
    worker dispatch, worker-side params from the shared seed) emits
    bitwise the streams of an in-process engine, and the fleet rollup
    sees the remote replicas' work."""
    cfg, params = served_model
    prompts = _prompts()
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 4)
    router, workers = _mk_remote_router(served_model, 2)
    try:
        assert router.generate(prompts, 4) == ref
        snap = router.metrics.snapshot()
        assert snap["requests_finished"] == len(prompts)
        assert snap["tokens_generated"] == sum(len(t) for t in ref)
        assert snap["worker_deaths"] == 0
    finally:
        router.close()


def test_remote_split_fleet_handoff_parity(served_model):
    """KV pages ride the RPC span lists prefill-pool -> router ->
    decode-pool and the streams stay bitwise the single-replica ones
    (chunked prefill on the prefill pool included)."""
    cfg, params = served_model
    prompts = _prompts()
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 4)
    # direct_migration="off" pins the RELAYED data path: this test's
    # byte accounting asserts pages crossed the ROUTER connection; the
    # direct plane (on by default) moves them worker->worker instead
    # and is pinned by the migration parity tests.
    router, workers = _mk_remote_router(
        served_model, 2, n_prefill=1, serve_kw={"prefill_chunk": 4},
        direct_migration="off")
    try:
        assert router.generate(prompts, 4) == ref
        assert router.metrics.handoffs == len(prompts)
        # Pages crossed the wire as spans, not inline body bytes.
        assert workers[0].conn.span_raw_bytes > 0
    finally:
        router.close()


def test_remote_handoff_bf16_compression_saves_and_is_deterministic(
        served_model):
    """handoff_compression="bf16" halves the K/V bytes on the wire
    (counted on the span accounting) and stays deterministic: two
    identically-seeded cross fleets emit identical streams. (It is
    lossy for f32 pools, so it is NOT compared bitwise to the
    uncompressed fleet — that contract is documented.)"""
    def run():
        # Relayed path pinned: the span-savings accounting below reads
        # the router-side connections, which the direct plane bypasses.
        router, workers = _mk_remote_router(
            served_model, 2, n_prefill=1,
            handoff_compression="bf16", direct_migration="off")
        try:
            streams = router.generate(_prompts(), 4)
            saved = sum(w.conn.span_raw_bytes - w.conn.span_wire_bytes
                        for w in workers)
            assert router.metrics.handoffs == len(streams)
            return streams, saved
        finally:
            router.close()

    s1, saved1 = run()
    s2, _ = run()
    assert s1 == s2
    assert saved1 > 0
    assert all(len(s) >= 1 for s in s1)


def test_remote_drain_migrates_running_decodes(served_model):
    """remove_replica(migrate_running=True) on a remote replica moves
    its RUNNING sequences to peers mid-decode (bitwise page RPC) and
    shuts the drained worker down — the streams stay bitwise the
    in-process reference."""
    cfg, params = served_model
    prompts = _prompts(n_per_tenant=2)
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 6)
    # 3 replicas so the survivors have batch slots for the migrants.
    router, workers = _mk_remote_router(served_model, 3)
    try:
        rids = [router.submit(p, 6) for p in prompts]
        router.step()
        router.step()
        victim = router.replicas[0]
        n_out = len(router._replica(victim).outstanding)
        assert n_out > 0, "nothing in flight — drain would be vacuous"
        router.remove_replica(victim, migrate_running=True)
        router.run_until_idle()
        assert victim not in router.replicas
        assert router.metrics.migrations > 0
        res = [router.result(r) for r in rids]
        assert all(x.status == "ok" for x in res)
        assert [x.tokens for x in res] == ref
        # The drained worker's process-side connection was shut down.
        assert not workers[0].conn.alive
    finally:
        router.close()


def test_dead_worker_requeues_and_resolves_exactly_once(served_model):
    """A worker that vanishes mid-trace (connection severed — the
    in-thread stand-in for SIGKILL) triggers requeue-at-front of its
    uncollected work; every request resolves exactly once with the
    reference streams, and the death is visible in the rollup."""
    cfg, params = served_model
    prompts = _prompts()
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 4)
    router, workers = _mk_remote_router(served_model, 2)
    try:
        rids = [router.submit(p, 4) for p in prompts]
        router.step()
        workers[0].conn.close()          # the worker "crashes"
        router.run_until_idle()
        res = [router.result(r) for r in rids]
        assert all(x is not None and x.status == "ok" for x in res)
        assert sorted({x.rid for x in res}) == sorted(rids)
        assert [x.tokens for x in res] == ref
        snap = router.metrics.snapshot()
        assert snap["worker_deaths"] == 1
        assert snap["requeued_total"] > 0
        assert len(router.replicas) == 1
    finally:
        router.close()


def test_worker_death_mid_drain_drops_nothing(served_model):
    """Regression (review round 1): remove_replica used to delete a
    successfully-withdrawn request from `outstanding` immediately — a
    worker dying on the NEXT withdraw RPC then made _handle_dead
    requeue only what was still mapped, stranding the already-
    withdrawn request with no result forever. Now withdrawals commit
    only after the loop, so a mid-drain death requeues everything."""
    cfg, params = served_model
    prompts = _prompts(n_per_tenant=3)
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 3)
    # max_batch=1 keeps most requests QUEUED on the replica, so the
    # drain has several withdrawals to die in the middle of.
    router, workers = _mk_remote_router(served_model, 2,
                                        serve_kw={"max_batch": 1})
    try:
        rids = [router.submit(p, 3) for p in prompts]
        router.step()
        victim = router.replicas[0]
        rep = router._replica(victim)
        assert len(rep.outstanding) >= 3
        # The worker dies between the first and second withdraw RPC.
        orig_withdraw = rep.engine.withdraw
        calls = []

        def dying_withdraw(erid):
            if calls:
                rep.engine.mark_dead()   # next RPC raises
            calls.append(erid)
            return orig_withdraw(erid)

        rep.engine.withdraw = dying_withdraw
        router.remove_replica(victim)
        router.run_until_idle()
        res = [router.result(r) for r in rids]
        assert all(x is not None and x.status == "ok" for x in res), \
            [None if x is None else x.status for x in res]
        assert [x.tokens for x in res] == ref
        assert router.metrics.snapshot()["worker_deaths"] == 1
    finally:
        router.close()


def test_remote_spec_fleet_parity_with_mid_trace_drain(served_model):
    """Acceptance (ISSUE 12): a speculative cross-RPC fleet — workers
    rebuild target AND draft from (config, seed) via configure — emits
    bitwise the plain in-process streams, through a mid-trace
    migrating drain (target pages move; the survivor's draft catches
    up from the migrated stream)."""
    from horovod_tpu.serve.speculative import DraftConfig

    cfg, params = served_model
    prompts = _prompts(n_per_tenant=2)
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 6)
    spec_kw = {"draft": DraftConfig(cfg, seed=1), "spec_k": 3}
    router, workers = _mk_remote_router(served_model, 3,
                                        serve_kw=spec_kw)
    try:
        rids = [router.submit(p, 6) for p in prompts]
        router.step()
        router.step()
        victim = router.replicas[0]
        router.remove_replica(victim, migrate_running=True)
        router.run_until_idle()
        assert victim not in router.replicas
        assert router.metrics.migrations > 0
        assert [router.result(r).tokens for r in rids] == ref
        # The speculative counters crossed the process boundary into
        # the fleet rollup (worker-side engines ran the spec rounds).
        snap = router.metrics.snapshot()
        assert snap["spec_proposed_total"] > 0
        assert 0 <= snap["spec_accept_rate"] <= 1
    finally:
        router.close()


def test_async_step_fanout_order_and_determinism(served_model):
    """The async step fan-out: within one router step, every busy
    remote replica's step request is SENT before any reply is
    collected (the workers compute concurrently), replies apply in
    fleet order, and two identically-seeded runs stay bit-identical —
    placement log included."""
    from horovod_tpu.serve.rpc import RemoteReplica

    events = []
    orig_begin = RemoteReplica.step_begin
    orig_finish = RemoteReplica.step_finish

    def spy_begin(self):
        events.append(("begin", self.instance))
        return orig_begin(self)

    def spy_finish(self):
        events.append(("finish", self.instance))
        return orig_finish(self)

    def run():
        router, _workers = _mk_remote_router(served_model, 2)
        try:
            rids = [router.submit(p, 4) for p in _prompts()]
            router.run_until_idle()
            return ([router.result(r).tokens for r in rids],
                    list(router.placement_log))
        finally:
            router.close()

    RemoteReplica.step_begin = spy_begin
    RemoteReplica.step_finish = spy_finish
    try:
        streams1, log1 = run()
        # Find a step where both replicas were busy: the event stream
        # must show begin,begin,...,finish,finish — never
        # begin,finish,begin,finish (that is the serial shape the
        # fan-out replaces).
        overlapped = any(
            events[i][0] == "begin" and events[i + 1][0] == "begin"
            for i in range(len(events) - 1))
        assert overlapped, events[:12]
        # Replies applied in fleet order within every step.
        finishes = [inst for kind, inst in events if kind == "finish"]
        begins = [inst for kind, inst in events if kind == "begin"]
        assert sorted(finishes) == sorted(begins)
        streams2, log2 = run()
        assert streams1 == streams2
        assert log1 == log2
    finally:
        RemoteReplica.step_begin = orig_begin
        RemoteReplica.step_finish = orig_finish


def test_remote_multi_model_group(served_model):
    """add_model with worker handles: a second model group served by a
    remote replica gets its own configure (the worker rebuilds THAT
    group's engine), requests route by model, streams match the
    reference."""
    cfg, params = served_model
    prompts = _prompts(n_per_tenant=1)
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 3)
    router, _workers = _mk_remote_router(served_model, 1)
    try:
        b_insts = router.add_model(
            "b", cfg, None, serve_cfg=ServeConfig(**_KW),
            n_replicas=1, workers=[_thread_worker()], worker_seed=0)
        rids_a = [router.submit(p, 3) for p in prompts]
        rids_b = [router.submit(p, 3, model="b") for p in prompts]
        router.run_until_idle()
        assert [router.result(r).tokens for r in rids_a] == ref
        assert [router.result(r).tokens for r in rids_b] == ref
        placed = {rid: inst for rid, inst, _, _ in router.placement_log}
        assert all(placed[r] in b_insts for r in rids_b)
        assert all(placed[r] not in b_insts for r in rids_a)
    finally:
        router.close()


def test_death_right_after_same_pass_placement_loses_nothing(
        served_model):
    """Regression (review): a worker that dies immediately after
    accepting a placement — so the SAME placement pass both placed a
    request on it and (via _handle_dead on a later RPC) requeued that
    request — must still resolve it exactly once on a survivor. The
    end-of-pass queue rebuild used to filter the requeued copy out
    with the stale one, stranding the request forever."""
    cfg, params = served_model
    prompts = _prompts(n_per_tenant=2)
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 3)
    router, _workers = _mk_remote_router(served_model, 2)
    try:
        rids = [router.submit(p, 3) for p in prompts]
        rep = router._replicas[0]
        orig_submit = rep.engine.submit

        def dying_submit(*a, **k):
            erid = orig_submit(*a, **k)
            rep.engine.mark_dead()   # dies with the placement booked
            return erid

        rep.engine.submit = dying_submit
        router.run_until_idle()
        res = [router.result(r) for r in rids]
        assert all(x is not None and x.status == "ok" for x in res), \
            [None if x is None else x.status for x in res]
        assert [x.tokens for x in res] == ref
        assert len({x.rid for x in res}) == len(rids)
        snap = router.metrics.snapshot()
        assert snap["worker_deaths"] == 1
        assert snap["requeued_total"] > 0
    finally:
        router.close()


def test_dead_worker_requeue_stays_same_model(served_model):
    """Acceptance (ISSUE 12): in a two-model remote fleet, a crashed
    worker's uncollected requests re-place ONLY on same-model
    survivors and resolve exactly once with the reference streams —
    the other group's traffic is untouched."""
    cfg, params = served_model
    prompts = _prompts(n_per_tenant=2)
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 4)
    router, workers = _mk_remote_router(served_model, 1)
    try:
        b_workers = [_thread_worker(), _thread_worker()]
        b_insts = set(router.add_model(
            "b", cfg, None, serve_cfg=ServeConfig(**_KW),
            n_replicas=2, workers=b_workers, worker_seed=0))
        rids_a = [router.submit(p, 4) for p in prompts]
        rids_b = [router.submit(p, 4, model="b") for p in prompts]
        router.step()
        # Crash the b worker that holds placed work.
        victims = [r for r in router._replicas
                   if r.instance in b_insts and r.outstanding]
        assert victims, "no b replica held work — test would be vacuous"
        victims[0].engine.mark_dead()
        router.run_until_idle()
        res_a = [router.result(r) for r in rids_a]
        res_b = [router.result(r) for r in rids_b]
        assert all(x is not None and x.status == "ok"
                   for x in res_a + res_b)
        assert [x.tokens for x in res_a] == ref
        assert [x.tokens for x in res_b] == ref
        assert len({x.rid for x in res_a + res_b}) \
            == len(rids_a) + len(rids_b)
        # Every placement — requeued re-placements included — stayed
        # inside the request's model group.
        for rid, inst, _m, _c in router.placement_log:
            want = "b" if rid in rids_b else "default"
            got = "b" if inst in b_insts else "default"
            assert got == want, (rid, inst)
        snap = router.metrics.snapshot()
        assert snap["worker_deaths"] == 1
        assert snap["requeued_total"] > 0
    finally:
        router.close()


def test_remote_deadline_reanchors_across_clocks(served_model):
    """Absolute deadlines are router-clock times; the wire carries
    time-remaining and the worker re-anchors onto its own clock — an
    already-expired deadline expires AT THE WORKER even though the
    processes share no clock epoch."""
    from horovod_tpu.serve.rpc import RemoteReplica

    cfg, _params = served_model

    class FakeClock:
        t = 1e9   # an epoch perf_counter will never reach

        def __call__(self):
            return self.t

    handle = _thread_worker()
    rep = RemoteReplica(handle, cfg, ServeConfig(**_KW), seed=0,
                        instance="t", clock=FakeClock())
    try:
        erid = rep.submit([1, 2, 3], 2, deadline=FakeClock.t - 5.0)
        rep.step()
        res = rep.result(erid)
        assert res is not None and res.status == "expired"
        assert res.reason == "deadline_expired"
        # Result times were re-anchored onto the router clock's frame.
        assert res.finished_at is not None
        assert abs(res.finished_at - FakeClock.t) < 60.0
    finally:
        handle.close()


def test_router_scrape_spans_worker_processes(served_model):
    """One scrape of the ROUTER process's exposition carries the
    remote replicas' serve_ series (heartbeat-cached) under their
    instance labels plus the fleet rollup."""
    import re

    from horovod_tpu.metrics import metrics_prometheus

    router, _workers = _mk_remote_router(served_model, 2)
    try:
        router.generate(_prompts(n_per_tenant=1), 2)
        txt = metrics_prometheus()
        fleet = router.metrics.fleet
        for rep in router._replicas:
            pat = (r'^serve_requests_finished\{instance="%s"\} '
                   % re.escape(rep.engine.metrics.instance))
            assert re.search(pat, txt, re.M), pat
        assert re.search(
            r'^serve_fleet_requests_finished\{fleet="%s"\} 2' % fleet,
            txt, re.M)
        assert re.search(
            r'^serve_fleet_worker_deaths\{fleet="%s"\} 0' % fleet,
            txt, re.M)
    finally:
        router.close()


def test_fleet_trace_ids_propagate_and_merge(served_model, tmp_path):
    """ISSUE 20 (in-thread tier): one request's router-side spans and
    its worker-side engine spans share ONE trace id, the fleet export
    + merge puts them on one timebase, and the critical-path
    decomposition partitions the e2e window exactly."""
    from horovod_tpu.serve import trace_merge

    router, _workers = _mk_remote_router(served_model, 2)
    try:
        prompts = _prompts(n_per_tenant=2)
        rids = [router.submit(p, 4) for p in prompts]
        router.run_until_idle()
        assert all(router.result(x).status == "ok" for x in rids)
        tdir = str(tmp_path / "traces")
        paths = router.export_fleet_trace(tdir)
        assert len(paths) == 3 and paths[0].endswith("router.json")
        merged = trace_merge.merge(trace_merge.discover(tdir))
        evs = merged["traceEvents"]
        tids = trace_merge.trace_ids(evs)
        # Default sampling traces every request, each with its own id.
        assert len(tids) == len(rids) and len(set(tids)) == len(rids)
        per_pid_names = {}
        for tid in tids:
            row = trace_merge.critical_path(evs, tid)
            b = row["breakdown_us"]
            # Exact partition: the rows sum to e2e (ISSUE acceptance
            # asks within 5%; the interval construction gives 0%).
            assert sum(b.values()) == pytest.approx(row["e2e_us"],
                                                    abs=0.5)
            assert b["prefill"] > 0, (tid, b)
            carriers = [e for e in evs if trace_merge._carries(e, tid)]
            names = {e["name"] for e in carriers}
            assert {"router:submit", "router:queue_wait",
                    "router:e2e"} <= names, names
            assert "serve:prefill" in names and "serve:decode" in names
            for e in carriers:
                per_pid_names.setdefault(e["pid"], set()).add(e["name"])
        # The id really spans PROCESS-SEPARATED files: router spans and
        # engine spans live under different merged pids.
        router_pids = {p for p, ns in per_pid_names.items()
                       if "router:e2e" in ns}
        engine_pids = {p for p, ns in per_pid_names.items()
                       if "serve:prefill" in ns}
        assert router_pids and engine_pids and not (router_pids
                                                    & engine_pids)
        # Worker-side ids are a subset of what the router minted —
        # nobody invents trace ids.
        minted = set(tids)
        for e in evs:
            args = e.get("args") or {}
            for t in [args.get("trace"), *(args.get("traces") or ())]:
                assert t is None or t in minted, e
        # Offsets were estimated and exported for the remote side.
        import json as _json
        for p in paths[1:]:
            md = _json.load(open(p))["metadata"]
            assert md["kind"] == "engine"
            assert md["clock_rtt"] is not None
            assert abs(md["clock_offset"]) < 5.0   # same host, same epoch
    finally:
        router.close()


def test_trace_sampling_off_tags_nothing(served_model, monkeypatch):
    """HOROVOD_TRACE_SAMPLE=0: no ids minted, no span args tagged —
    the zero-cost configuration really is zero-identity."""
    monkeypatch.setenv("HOROVOD_TRACE_SAMPLE", "0")
    router, _workers = _mk_remote_router(served_model, 2)
    try:
        rids = [router.submit(p, 4) for p in _prompts(n_per_tenant=1)]
        router.run_until_idle()
        assert all(router.result(x).status == "ok" for x in rids)
        for e in router.trace.events:
            assert "trace" not in (e.get("args") or {}), e
        for rep in router._replicas:
            d = rep.engine.export_trace()
            for e in d["events"]:
                args = e.get("args") or {}
                assert not args.get("trace") and not args.get("traces")
    finally:
        router.close()


# ---------------------------------------------------------------------------
# Cross-process tier (slow): real worker processes
# ---------------------------------------------------------------------------

@pytest.mark.slow  # ~3 worker processes x (jax import + compile); the
# in-thread spec fleet test above pins the identical dispatch tier-1.
def test_cross_process_speculative_fleet_parity_with_drain(served_model):
    """Acceptance (ISSUE 12): a SPECULATIVE cross-process fleet —
    every worker process rebuilds target AND draft from (config, seed)
    — emits bitwise the plain in-process streams through a mid-trace
    migrating drain."""
    from horovod_tpu.serve.rpc import spawn_worker
    from horovod_tpu.serve.speculative import DraftConfig

    cfg, params = served_model
    prompts = _prompts(n_per_tenant=2)
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 6)
    sc = ServeConfig(**_KW, draft=DraftConfig(cfg, seed=1), spec_k=3)
    workers = [spawn_worker() for _ in range(3)]
    try:
        router = ServeRouter(cfg, None, RouterConfig(n_replicas=3), sc,
                             workers=workers, worker_seed=0)
        rids = [router.submit(p, 6) for p in prompts]
        router.step()
        router.step()
        victim = router.replicas[0]
        router.remove_replica(victim, migrate_running=True)
        router.run_until_idle()
        assert router.metrics.migrations > 0
        assert [router.result(r).tokens for r in rids] == ref
        snap = router.metrics.snapshot()
        assert snap["spec_proposed_total"] > 0
        router.close()
    finally:
        for w in workers:
            w.kill()


@pytest.mark.slow  # ~4 worker processes x (jax import + tiny compile);
# the in-thread tier above pins the identical router/dispatch logic in
# tier-1 — this is the true end-to-end acceptance gate.
def test_cross_process_fleet_parity_drain_and_kill(served_model):
    """Acceptance (ISSUE 11): a cross-process 4-replica fleet emits
    bitwise the in-process fleet's streams on the multi-tenant trace,
    including a mid-trace drain that MIGRATES a RUNNING sequence to a
    surviving worker; then, on a fresh pass over the surviving
    workers, a SIGKILLed worker's queued requests complete via requeue
    with no request resolved twice."""
    from horovod_tpu.serve.traces import make_multi_tenant_trace
    from horovod_tpu.serve.rpc import spawn_worker

    cfg, params = served_model
    trace = make_multi_tenant_trace(
        16, seed=3, n_tenants=4, prefix_len=12, min_suffix=2,
        max_suffix=6, min_new=4, max_new=6)
    trace = [(p, n) for p, n in trace]
    sc = ServeConfig(**_KW)

    # In-process reference fleet (same params seed the workers use).
    ref_router = ServeRouter(cfg, params, RouterConfig(n_replicas=4), sc)
    ref = ref_router.generate([p for p, _ in trace], 6)

    workers = [spawn_worker() for _ in range(4)]
    try:
        # -- pass 1: parity + migrating drain ------------------------
        router = ServeRouter(cfg, None, RouterConfig(n_replicas=4), sc,
                             workers=workers, worker_seed=0)
        rids = [router.submit(p, 6) for p, _ in trace]
        router.step()
        router.step()
        victim = router.replicas[0]
        router.remove_replica(victim, migrate_running=True)
        router.run_until_idle()
        assert router.metrics.migrations > 0, \
            "drain migrated no RUNNING sequence"
        got = [router.result(r).tokens for r in rids]
        assert got == ref
        survivors = workers[1:]
        assert workers[0].proc.wait(timeout=60) == 0  # drained = exited

        # -- pass 2: SIGKILL failover over the survivors -------------
        router2 = ServeRouter(cfg, None, RouterConfig(n_replicas=3), sc,
                              workers=survivors, worker_seed=0)
        rids2 = [router2.submit(p, 6) for p, _ in trace]
        router2.step()
        survivors[0].kill()              # hard death, no goodbye
        router2.run_until_idle()
        res = [router2.result(r) for r in rids2]
        assert all(x is not None and x.status == "ok" for x in res)
        assert len({x.rid for x in res}) == len(rids2)
        assert [x.tokens for x in res] == ref
        snap = router2.metrics.snapshot()
        assert snap["worker_deaths"] == 1
        assert snap["requeued_total"] > 0
        router2.close()
    finally:
        for w in workers:
            w.kill()


@pytest.mark.slow  # 2 worker processes x (jax import + tiny compile);
# the in-thread trace test above pins the identical id/offset plumbing
# tier-1 — this is the ISSUE 20 end-to-end acceptance gate.
def test_cross_process_trace_merge_and_flight_postmortem(
        served_model, tmp_path, monkeypatch):
    """Acceptance (ISSUE 20): over a REAL 2-worker cross-process fleet
    with a mid-run SIGKILL, one ``export_fleet_trace`` + merge yields a
    single timeline where a request's router and worker spans share
    one trace id on one timebase with an exactly-summing critical
    path, and the surviving router's flight dump ends with the
    peer-death and requeue records that explain the failover."""
    import shutil

    from horovod_tpu.common import basics as _basics
    from horovod_tpu.metrics import flight_clear
    from horovod_tpu.serve import trace_merge
    from horovod_tpu.serve.rpc import spawn_worker

    cfg, _params = served_model
    fdir = tmp_path / "flight"
    fdir.mkdir()
    # Arm the auto-dump path as library load would have with the env
    # set; the router's death path keys off the env var.
    assert _basics.get_lib().hvd_flight_install(str(fdir).encode()) == 0
    monkeypatch.setenv("HOROVOD_FLIGHT_DIR", str(fdir))
    flight_clear()

    workers = [spawn_worker() for _ in range(2)]
    tdir = str(tmp_path / "traces")
    try:
        router = ServeRouter(cfg, None, RouterConfig(n_replicas=2),
                             ServeConfig(**_KW), workers=workers,
                             worker_seed=0)
        rids = [router.submit(p, 4) for p in _prompts(n_per_tenant=2)]
        router.step()
        workers[1].kill()            # hard death, no goodbye
        router.run_until_idle()
        res = [router.result(x) for x in rids]
        assert all(x is not None and x.status == "ok" for x in res)
        snap = router.metrics.snapshot()
        assert snap["worker_deaths"] == 1
        router.export_fleet_trace(tdir)
        router.close()
    finally:
        for w in workers:
            w.kill()

    # The postmortem dump survives in HOROVOD_FLIGHT_DIR and its last
    # events record what the fleet did about the kill.
    dump = fdir / f"flight-{os.getpid()}.txt"
    assert dump.exists(), list(fdir.iterdir())
    names = [ln.split("\t")[2] for ln in
             dump.read_text().splitlines()[1:] if "\t" in ln]
    assert "peer_death" in names and "requeue" in names, names

    # One merge over traces + dump: single timebase, shared ids.
    shutil.copy(str(dump), tdir)
    merged = trace_merge.merge(trace_merge.discover(tdir))
    evs = merged["traceEvents"]
    assert any(e["name"] == "flight:peer_death" for e in evs)
    tids = trace_merge.trace_ids(evs)
    assert len(tids) == len(rids)
    spanned = 0
    for tid in tids:
        row = trace_merge.critical_path(evs, tid)
        b = row["breakdown_us"]
        assert sum(b.values()) == pytest.approx(row["e2e_us"], abs=0.5)
        names = {e["name"] for e in evs if trace_merge._carries(e, tid)}
        if {"router:e2e", "serve:prefill", "serve:decode"} <= names \
                and b["prefill"] > 0:
            spanned += 1
    # The killed worker took its un-exported spans with it; every
    # request that finished on the survivor still stitches end to end.
    assert spanned >= 1, tids


# ---------------- direct KV-page migration (ISSUE 19) ----------------


def _split_fleet_streams(served_model, mode, codec=None, prompts=None,
                         plan=None):
    """Streams + router for a 2-replica split fleet (1 prefill -> 1
    decode, every request migrates its pages) of in-thread remote
    workers under direct_migration ``mode``."""
    prompts = prompts or _prompts()
    router, workers = _mk_remote_router(
        served_model, 2, n_prefill=1, direct_migration=mode,
        handoff_compression=codec)
    if plan is not None:
        router._migration_plan = lambda src, tgt, need: dict(plan)
    try:
        streams = router.generate(prompts, 4)
        snap = router.metrics.snapshot()
        log = list(router.placement_log)
        return streams, snap, log
    finally:
        router.close()


def test_direct_vs_relayed_bitwise_parity_matrix(served_model):
    """Acceptance (ISSUE 19): migrated decode streams are bitwise
    identical with the direct plane on vs off, uncompressed AND under
    bf16 (idempotent cast: one codec pass direct == two passes
    relayed), and the uncompressed streams match the in-process
    single-engine reference."""
    cfg, params = served_model
    prompts = _prompts()
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 4)
    for codec in (None, "bf16"):
        direct, dsnap, _ = _split_fleet_streams(
            served_model, "auto", codec, prompts)
        relayed, rsnap, _ = _split_fleet_streams(
            served_model, "off", codec, prompts)
        assert direct == relayed, f"codec={codec}"
        assert dsnap["direct_migrations_total"] == len(prompts)
        assert rsnap["direct_migrations_total"] == 0
        if codec is None:
            assert direct == ref
    # bf16 parity holds precisely because bf16(bf16(x)) == bf16(x);
    # the codec itself is pinned bitwise by the span-codec tests.


def test_direct_chunked_stream_matches_monolithic(served_model):
    """A chunk schedule (forced 2-page chunks, several peer_chunk
    frames per move) lands bitwise the same streams as the monolithic
    stream and the relayed path — chunks scatter disjoint block rows,
    so chunking is a wire-shape choice, never a semantic one."""
    prompts = _prompts()
    chunked, csnap, _ = _split_fleet_streams(
        served_model, "auto", "bf16", prompts,
        plan={"chunk_pages": 2, "n_chunks": 4, "cost_us": 0.0,
              "wire_bytes": 0})
    mono, _, _ = _split_fleet_streams(
        served_model, "auto", "bf16", prompts)
    relayed, _, _ = _split_fleet_streams(
        served_model, "off", "bf16", prompts)
    assert chunked == mono == relayed
    assert csnap["direct_migrations_total"] == len(prompts)


def test_direct_migration_metrics_and_cost_column(served_model):
    """The exposition contract: direct moves count, bytes accumulate,
    the wall-time histogram renders pooled tails, the link-cost gauge
    is set, and every move writes a cost-column row (match == -1) to
    the placement log."""
    prompts = _prompts()
    streams, snap, log = _split_fleet_streams(
        served_model, "auto", "bf16", prompts)
    assert len(streams) == len(prompts)
    assert snap["direct_migrations_total"] == len(prompts)
    assert snap["migration_bytes_total"] > 0
    assert snap["p50_migration_ms"] is not None
    assert snap["p99_migration_ms"] >= snap["p50_migration_ms"]
    assert snap["migration_link_cost_us"] == 0.0   # no topology model
    moves = [e for e in log if e[2] == -1]
    assert len(moves) == len(prompts)
    assert all(isinstance(e[3], float) for e in moves)


def test_replayed_manifest_epoch_refused_and_requeued(served_model):
    """Exactly-once, target side: a manifest epoch the target has
    already seen is refused (stale partial replays can neither commit
    nor double-inject), the router requeues the request at the queue
    front, and it still resolves exactly once with the right
    tokens."""
    import itertools

    cfg, params = served_model
    prompts = _prompts()
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 4)
    router, workers = _mk_remote_router(
        served_model, 2, n_prefill=1, direct_migration="auto")
    # First two manifests claim the SAME epoch: move 1 lands, move 2
    # is refused by the target as a replay; later moves are fresh.
    router._migration_epochs = itertools.chain(
        [7, 7], itertools.count(1000))
    try:
        streams = router.generate(prompts, 4)
        assert streams == ref
        snap = router.metrics.snapshot()
        assert snap["requeued_total"] >= 1
        assert snap["direct_migrations_total"] >= 1
    finally:
        router.close()


def test_dead_target_mid_direct_stream_requeues(served_model):
    """Exactly-once, source side: when the peer stream fails AFTER the
    export freed the source pages (target's bulk socket closes
    mid-stream), the request requeues at the queue front, re-prefills
    on a fresh placement, and still resolves exactly once with the
    right tokens — the failed move never double-counts."""
    import socket as socket_mod

    cfg, params = served_model
    prompts = _prompts(n_per_tenant=1)
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 4)
    router, workers = _mk_remote_router(
        served_model, 2, n_prefill=1, direct_migration="auto")
    # A listener that accepts and instantly closes: the source's dial
    # succeeds, the stream dies on the first frame — the "exported,
    # then the transfer died" path, not dial_failed fallback.
    ls = socket_mod.socket()
    ls.bind(("127.0.0.1", 0))
    ls.listen(4)

    def reaper():
        while True:
            try:
                srv, _ = ls.accept()
            except OSError:
                return
            srv.close()

    threading.Thread(target=reaper, daemon=True).start()
    decode_rep = next(r for r in router._replicas if r.role == "decode")
    real_port = decode_rep.engine.peer_port
    decode_rep.engine.peer_port = ls.getsockname()[1]
    try:
        rids = [router.submit(p, 4) for p in prompts]
        for _ in range(200):
            router.step()
            if router.metrics.requeued_total >= 1:
                break
        else:
            raise AssertionError("no stream failure was recorded")
        # Heal the fleet: retries (and remaining moves) go direct to
        # the real bulk listener again.
        decode_rep.engine.peer_port = real_port
        router.run_until_idle()
        assert [router.result(r).tokens for r in rids] == ref
        assert len({r for r in rids}) == len(prompts)
        snap = router.metrics.snapshot()
        assert snap["requeued_total"] >= 1
    finally:
        ls.close()
        router.close()


@pytest.mark.slow  # 2 worker processes x (jax import + compile); the
# in-thread stream-death and replay-refusal tests above pin the same
# exactly-once machinery deterministically in tier-1 — this is the
# true SIGKILL-under-load acceptance gate.
def test_sigkill_source_mid_direct_stream_exactly_once(served_model):
    """Acceptance (ISSUE 19): SIGKILL the SOURCE worker while a
    chunked direct drain is streaming. Whatever the kill lands on —
    before export, mid-stream, after commit — every request resolves
    exactly once with the deterministic tokens: committed moves decode
    on the target, in-flight pages die with the stream (the target
    aborts its partial staging on disconnect) and the request
    re-prefills on a survivor via the death requeue."""
    import time as time_mod

    from horovod_tpu.serve.rpc import spawn_worker

    cfg, params = served_model
    prompts = _prompts()
    ref = ServeEngine(cfg, params, ServeConfig(**_KW)).generate(prompts, 6)
    workers = [spawn_worker() for _ in range(3)]
    try:
        router = ServeRouter(cfg, None, RouterConfig(n_replicas=3),
                             ServeConfig(**_KW), workers=workers,
                             worker_seed=0)
        # 1-page chunks: every move streams many peer_chunk frames, so
        # a mid-drain kill has a real window to land mid-stream.
        router._migration_plan = lambda src, tgt, need: {
            "chunk_pages": 1, "n_chunks": need, "cost_us": 0.0,
            "wire_bytes": 0}
        rids = [router.submit(p, 6) for p in prompts]
        router.step()
        router.step()
        victim = router._replicas[0]
        done = threading.Event()

        def drain():
            try:
                router.remove_replica(victim.instance,
                                      migrate_running=True)
                router.run_until_idle()
            finally:
                done.set()

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        time_mod.sleep(0.05)        # let the drain start streaming
        workers[0].kill()           # SIGKILL, no goodbye
        assert done.wait(timeout=120), "fleet never went idle"
        t.join(timeout=10)
        res = [router.result(r) for r in rids]
        assert all(x is not None and x.status == "ok" for x in res)
        assert len({x.rid for x in res}) == len(rids)
        assert [x.tokens for x in res] == ref
        router.close()
    finally:
        for w in workers:
            w.kill()
