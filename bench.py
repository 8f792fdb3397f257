"""Benchmark: ResNet-50 synthetic training throughput (images/sec/chip).

Mirrors the reference protocol (`examples/pytorch/
pytorch_synthetic_benchmark.py:100-118`): ResNet-50, batch 32,
synthetic ImageNet-shaped data, 10 warmup batches then 10 timed rounds
of 10 batches; reports the mean images/sec on this chip.

Prints ONE JSON line:
  {"metric": "resnet50_images_per_sec_per_chip", "value": N,
   "unit": "images/sec", "vs_baseline": R, "extra": {...}}

``vs_baseline`` compares against the reference's only published
absolute throughput — 1,656.82 img/s over 16 P100s for ResNet-101
(`docs/benchmarks.rst:40-43`), i.e. 103.55 img/s/GPU scaled by the
ResNet-101/ResNet-50 FLOP ratio (7.6/3.8 GFLOPs ≈ 2.0) to a ~207
img/s/GPU ResNet-50 equivalent.

``extra`` carries secondary metrics:
* BASELINE.md's fused-allreduce **bus bandwidth** microbenchmark
  (np=4 local processes over the TCP peer mesh; NCCL convention
  busbw = 2·(P−1)/P · bytes/t) per payload size (BENCH_SKIP_BUS=1
  to skip);
* decoder-LM training **tokens/sec + MFU** on this chip — the
  matmul-heavy utilization story the ResNet protocol (batch 32,
  BN/input-bound) can't show. BENCH_SKIP_EXTRAS=1 skips all extras.

The protocol's batch 32/chip already saturates this chip for
ResNet-50: BENCH_BATCH=256 measures within noise of batch 32
(2,563 vs 2,592 img/s on v5e), so no separate large-batch metric is
reported.
"""

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REF_R50_IMG_PER_SEC_PER_DEVICE = 207.0  # P100-derived, see module docstring

_T0 = time.perf_counter()

BUS_SIZES_MB = (1, 16, 64)
BUS_NP = 4
# Fused-small-tensor case: many gradient-sized tensors enqueued in one
# cycle, the shape tensor fusion exists for (the Horovod paper credits
# most of its speedup to exactly this). Reported separately so fusion
# regressions are visible next to the single-tensor sizes.
BUS_FUSED_COUNT = 64
BUS_FUSED_KB = 64
# Wire-compression case (perf_tuning.md HOROVOD_WIRE_COMPRESSION):
# 16 MB payload on the TCP ring (shm disabled — compression only
# touches the inter-process wire). Codec rounds are INTERLEAVED and
# each codec keeps its best round: on a box whose ranks timeshare two
# cores, sequential per-codec blocks drift ±30% between blocks and the
# none/bf16 ratio is unmeasurable; round-robin sampling puts every
# codec under the same interference.
BUS_WIRE_MB = 16
BUS_WIRE_ROUNDS = 8
# Collective-algorithm case (perf_tuning.md HOROVOD_COLLECTIVE_ALGO):
# ring vs halving-doubling vs multi-ring striping on the TCP plane at
# one latency-bound payload (64 KB — where hd's 2·log2 P steps beat the
# ring's 2(P-1)) and one bandwidth-bound payload (16 MB). Algorithm
# rounds are INTERLEAVED like the codec rounds: sequential per-arm
# blocks drift ±30% on this timeshared box (docs/perf_tuning.md).
BUS_ALGO_SIZES = ((64 * 1024, "64KB", 30), ((16 << 20), "16MB", 3))
BUS_ALGO_ROUNDS = 6
BUS_ALGO_ARMS = ("ring", "hd", "striped")
# Small-op latency family (ISSUE 15, persistent arm ISSUE 17):
# round-trip allreduce latency at control-path-bound payloads. Three
# arms — persistent (steady lock + persistent slot plans), locked
# (HOROVOD_STEADY_PERSISTENT=off, the exact PR 15 path), off
# (negotiated). Arms are whole JOBS (both knobs are init-time),
# interleaved per round per the ±30% protocol; each arm keeps its best
# (lowest-p50) round. A raw loopback socket ping-pong rides along as
# the floor the persistent p50 is judged against (target: within 2x).
BUS_LAT_SIZES = ((4, "4B"), (1024, "1KB"), (64 * 1024, "64KB"))
BUS_LAT_ROUNDS = 3
BUS_LAT_ITERS = 250


def _bus_worker():
    """Per-rank body of the allreduce bandwidth microbenchmark (run in
    subprocesses with the standard HOROVOD_* env)."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r, s = hvd.rank(), hvd.size()
    results = {}
    for mb in BUS_SIZES_MB:
        n = mb * (1 << 20) // 4
        x = np.ones(n, np.float32)
        for i in range(2):  # warmup (mesh links, fusion buffer, cache)
            hvd.allreduce(x, op=hvd.Sum, name=f"bw.{mb}")
        # Best-of-3 rounds: with every rank timesharing one CPU core,
        # single measurements drift +-50% run to run (scheduler and
        # host-load interference), which round 4 misread as a
        # regression. The best round is the least-interfered one and
        # is what makes cross-round comparison meaningful.
        iters = 20 if mb <= 1 else 5
        best_dt = None
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(iters):
                hvd.allreduce(x, op=hvd.Sum, name=f"bw.{mb}")
            dt = time.perf_counter() - t0
            best_dt = dt if best_dt is None else min(best_dt, dt)
        algbw = (n * 4 * iters / best_dt) / 1e9
        results[f"{mb}MB"] = round(algbw * 2 * (s - 1) / s, 3)
    # Fused small tensors: one grouped enqueue per iteration, so the
    # whole batch negotiates in one cycle and packs into one fused
    # response (64 x 64KB = 4MB, under the default fusion threshold).
    n_small = BUS_FUSED_KB * 1024 // 4
    xs = [np.ones(n_small, np.float32) for _ in range(BUS_FUSED_COUNT)]
    for _ in range(2):
        hvd.grouped_allreduce(xs, op=hvd.Sum, name="bwf")
    # Telemetry window: the timed fused rounds only, so the derived
    # efficiency keys (fusion fill, cycle p99) describe the workload
    # tensor fusion exists for, not the single-tensor warmups above.
    hvd.metrics_reset()
    total = BUS_FUSED_COUNT * n_small * 4
    iters, best_dt = 10, None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            hvd.grouped_allreduce(xs, op=hvd.Sum, name="bwf")
        dt = time.perf_counter() - t0
        best_dt = dt if best_dt is None else min(best_dt, dt)
    algbw = (total * iters / best_dt) / 1e9
    results[f"fused_{BUS_FUSED_COUNT}x{BUS_FUSED_KB}KB"] = round(
        algbw * 2 * (s - 1) / s, 3)
    if r == 0:
        # Efficiency keys derived from the native metrics registry
        # (docs/observability.md), scoped to the fused rounds by the
        # reset above: how full the fusion batches ran against the live
        # threshold, and the coordinator-cycle tail (log2-bucket upper
        # bound, so a power of two).
        m = hvd.metrics()
        tele = {}
        if m.get("fusion_fill_pct_count"):
            tele["fusion_fill_pct"] = round(
                m["fusion_fill_pct_sum"] / m["fusion_fill_pct_count"], 1)
        if m.get("cycle_us_count"):
            tele["cycle_us_p99"] = m["cycle_us_p99"]
        if tele:
            results["telemetry"] = tele
        print("BUSBW " + json.dumps(results), flush=True)
    hvd.shutdown()


def _bus_wire_worker():
    """Per-rank body of the WIRE-compression busbw case: one TCP-ring
    payload, codecs round-robined so each round's host interference
    hits every codec equally; each codec reports its best round. Also
    prints the exact achieved compression ratio (payload bytes / wire
    bytes) straight from the native codec's size accounting."""
    import ctypes

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common.basics import get_lib

    hvd.init()
    r, s = hvd.rank(), hvd.size()
    n = BUS_WIRE_MB * (1 << 20) // 4
    x = np.ones(n, np.float32)
    codecs = [("none", hvd.Compression.none), ("bf16", hvd.Compression.bf16),
              ("int8", hvd.Compression.int8)]
    for name, comp in codecs:
        for _ in range(2):
            hvd.allreduce(x, op=hvd.Sum, name=f"bww.{name}", compression=comp)
    iters, best = 3, {}
    for _ in range(BUS_WIRE_ROUNDS):
        for name, comp in codecs:
            t0 = time.perf_counter()
            for _ in range(iters):
                hvd.allreduce(x, op=hvd.Sum, name=f"bww.{name}",
                              compression=comp)
            dt = time.perf_counter() - t0
            best[name] = min(best.get(name, dt), dt)
    if r == 0:
        lib = get_lib()
        results = {}
        for name, comp in codecs:
            bw = (n * 4 * iters / best[name]) / 1e9 * 2 * (s - 1) / s
            results[name] = round(bw, 3)
        # Bytes that actually skipped the wire, straight from the
        # codec's encode-site accounting (pre = f32 payload presented
        # to encode, post = encoded bytes sent) across the compressed
        # rounds — measured savings, not the theoretical ratio below.
        m = hvd.metrics()
        if m.get("wire_pre_bytes_total"):
            results["wire_bytes_saved_pct"] = round(
                100.0 * (1 - m["wire_post_bytes_total"]
                         / m["wire_pre_bytes_total"]), 1)
        results["ratio"] = {
            name: round(n * 4 / lib.hvd_wire_encoded_bytes(
                comp.wire_codec, ctypes.c_int64(n)), 2)
            for name, comp in codecs if name != "none"
        }
        # Transport-mode record (perf_tuning.md#zero-copy-transport):
        # which syscall plane the arms above actually rode, plus the
        # measured bytes-per-send-syscall over the whole job — the
        # coalescing ratio the vectored layer is gated on.
        results["transport"] = (
            lib.hvd_tcp_transport_mode_name().decode())
        # Resolved submission-batching verdict rides along the same way
        # (HOROVOD_TCP_IOURING wish ∧ end-to-end ring probe): "syscall"
        # on this 4.4 kernel, "batched" where io_uring delivered.
        results["iouring"] = lib.hvd_tcp_iouring_mode_name().decode()
        if m.get("tcp_sendv_calls_total"):
            results["sendv_bytes_per_call"] = int(
                m["tcp_send_bytes_total"] / m["tcp_sendv_calls_total"])
        print("BUSWIRE " + json.dumps(results), flush=True)
    hvd.shutdown()


def _bus_algo_worker():
    """Per-rank body of the algorithm-selection busbw case: one TCP
    job (HOROVOD_TOPOLOGY_PROBE=force, so a fresh measured model is
    live), each payload size measured under every algorithm arm PLUS
    the measured-model "auto" arm and the hand-band verdict arm, all
    round-robined (best round per arm). Rank 0 dumps the default AND
    synthesized selection tables plus the probe cost, so the bench
    record proves which verdicts the measured model changed and what
    each choice measured."""
    import ctypes

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.common.basics import get_lib

    hvd.init()
    r, s = hvd.rank(), hvd.size()
    lib = get_lib()

    def default_name(n_bytes):
        return lib.hvd_algo_name(lib.hvd_algo_select(
            ctypes.c_int64(n_bytes), s, 0,
            ctypes.c_int64(256 * 1024))).decode()

    best = {}
    for n_bytes, label, iters in BUS_ALGO_SIZES:
        n = n_bytes // 4
        x = np.ones(n, np.float32)
        # The comparison arms: `measured` rides algorithm=None (auto →
        # the cost model, since the probe is forced on), `handbands`
        # forces the hand-seeded default verdict per op — the measured-
        # vs-default sweep the acceptance gate audits.
        arms = list(BUS_ALGO_ARMS) + [
            ("measured", None), ("handbands", default_name(n_bytes))]
        arms = [(a, a) if isinstance(a, str) else a for a in arms]
        for tag, a in arms:
            for _ in range(2):
                hvd.allreduce(x, op=hvd.Sum, name=f"ba.{label}.{tag}",
                              algorithm=a)
        for _ in range(BUS_ALGO_ROUNDS):
            for tag, a in arms:
                t0 = time.perf_counter()
                for _ in range(iters):
                    hvd.allreduce(x, op=hvd.Sum, name=f"ba.{label}.{tag}",
                                  algorithm=a)
                dt = time.perf_counter() - t0
                key = (label, tag)
                best[key] = min(best.get(key, dt), dt)
    if r == 0:
        results = {a: {} for a in
                   list(BUS_ALGO_ARMS) + ["measured", "handbands"]}
        for n_bytes, label, iters in BUS_ALGO_SIZES:
            for tag in results:
                bw = (n_bytes * iters / best[(label, tag)]) / 1e9
                results[tag][label] = round(bw * 2 * (s - 1) / s, 3)
        # Selection tables per log2 payload bucket: the hand bands'
        # verdicts and the measured model's (the synthesized table) —
        # diffing the two is the audit trail of what the probe changed.
        table, synth_table, audit = {}, {}, {}
        for lg in range(10, 27):
            nb = 1 << lg
            dflt = default_name(nb)
            meas = lib.hvd_algo_select_measured(
                ctypes.c_int64(nb), s, 0, ctypes.c_int64(256 * 1024))
            mname = lib.hvd_algo_name(meas).decode() if meas >= 0 else dflt
            table[f"{nb}"] = dflt
            synth_table[f"{nb}"] = mname
            if mname != dflt:
                audit[f"{nb}"] = {"default": dflt, "measured": mname}
        results["table"] = table
        results["synth_table"] = synth_table
        results["audit"] = audit
        results["topology_probe_ms"] = hvd.metrics()["topology_probe_ms"]
        print("ALGO-TABLE np=%d: %s" % (
            s, ", ".join(f"{int(k)//1024}KB={v}" for k, v in table.items())),
            flush=True)
        print("SYNTH-TABLE np=%d: %s" % (
            s, ", ".join(f"{int(k)//1024}KB={v}"
                         for k, v in synth_table.items())), flush=True)
        print("BUSALGO " + json.dumps(results), flush=True)
    hvd.shutdown()


def _latency_worker():
    """Per-rank body of the small-op latency case: each iteration is
    one enqueue -> synchronize round trip, so the measured time is the
    control path (negotiation or the steady lock's token round) plus a
    tiny exchange. The launcher sets HOROVOD_STEADY_LOCK per arm; the
    locked arm reports whether the lock actually engaged so a silently
    negotiating "locked" arm can never masquerade as a win."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    r, s = hvd.rank(), hvd.size()
    results = {}
    engaged = True
    for n_bytes, label in BUS_LAT_SIZES:
        x = np.ones(max(1, n_bytes // 4), np.float32)
        name = f"lat.{label}"
        # Warmup negotiates, populates the cache, and (locked arm)
        # gives the detector its K+1 pure cycles. FIXED op count on
        # every rank: engagement is op-count-deterministic for a
        # synchronous single-tensor loop (the engage broadcast rides
        # op K+2's cycle and is installed before op K+3 completes),
        # while a rank-local engaged-poll would issue rank-divergent
        # collective counts and wedge the job at the size switch.
        for _ in range(12):
            hvd.allreduce(x, op=hvd.Sum, name=name)
        engaged = engaged and (os.environ.get("HOROVOD_STEADY_LOCK") == "off"
                               or hvd.steady_lock_engaged())
        lats = []
        for _ in range(BUS_LAT_ITERS):
            t0 = time.perf_counter()
            hvd.allreduce(x, op=hvd.Sum, name=name)
            lats.append((time.perf_counter() - t0) * 1e6)
        lats.sort()
        results[label] = {
            "p50": round(lats[len(lats) // 2], 1),
            "p99": round(lats[min(len(lats) - 1, int(len(lats) * 0.99))], 1),
        }
    if r == 0:
        results["engaged"] = engaged
        print("BUSLAT " + json.dumps(results), flush=True)
    hvd.shutdown()


def _bus_job(flag, tag, extra_env=None, timeout=120):
    """Launch one np=4 host-plane microbenchmark job (`bench.py
    <flag>`) and return rank 0's parsed "<tag> {json}" payload, or
    None on failure (the primary metric must still print)."""
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for r in range(BUS_NP):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(r), "HOROVOD_SIZE": str(BUS_NP),
            "HOROVOD_LOCAL_RANK": str(r), "HOROVOD_LOCAL_SIZE": str(BUS_NP),
            "HOROVOD_CROSS_RANK": "0", "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_CONTROLLER_ADDR": f"127.0.0.1:{port}",
            "JAX_PLATFORMS": "cpu",
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), flag],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True))
    out0 = None
    # One overall deadline across all ranks (not per-communicate), so
    # the whole microbenchmark is bounded — the headroom its budget
    # gate in main() checks for.
    deadline = time.perf_counter() + timeout
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(
                timeout=max(1.0, deadline - time.perf_counter()))
            if r == 0:
                out0 = out
            if p.returncode != 0:
                return None
    except subprocess.TimeoutExpired:
        return None
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for line in (out0 or "").splitlines():
        if line.startswith(tag + " "):
            return json.loads(line[len(tag) + 1:])
    return None


def _bus_bandwidth():
    """The np=4 host-plane bandwidth job; {size: GB/s} or None."""
    return _bus_job("--bus-worker", "BUSBW")


def _bus_wire_bandwidth():
    """The np=4 TCP-ring wire-compression job (shm disabled so the
    codecs actually touch the wire); {codec: GB/s, ratio: {...}}."""
    return _bus_job("--bus-wire-worker", "BUSWIRE",
                    extra_env={"HOROVOD_SHM_DISABLE": "1"}, timeout=150)


def _bus_algo_bandwidth():
    """The np=4 TCP algorithm-selection job (shm disabled so the
    algorithms actually run the mesh; topology probe FORCED so the
    measured-model arm reflects this draw's links, not a stale cache);
    {algo: {size: GB/s}, table, synth_table, audit,
    topology_probe_ms}."""
    return _bus_job("--bus-algo-worker", "BUSALGO",
                    extra_env={"HOROVOD_SHM_DISABLE": "1",
                               "HOROVOD_TOPOLOGY_PROBE": "force"},
                    timeout=240)


def _bus_latency():
    """The np=4 small-op latency family: persistent vs locked vs off
    arms as whole jobs, interleaved per round, best (lowest-p50) round
    per arm. "locked" pins HOROVOD_STEADY_PERSISTENT=off so it stays
    the exact PR 15 control path the persistent arm's >=1.25x claim is
    measured against. Returns {"persistent": {size: {p50, p99}},
    "locked": {...}, "off": {...}, "engaged": bool} or None."""
    arms = {"persistent": {"HOROVOD_STEADY_LOCK": "auto",
                           "HOROVOD_STEADY_PERSISTENT": "auto"},
            "locked": {"HOROVOD_STEADY_LOCK": "auto",
                       "HOROVOD_STEADY_PERSISTENT": "off"},
            "off": {"HOROVOD_STEADY_LOCK": "off"}}
    best = {}
    engaged = None
    for _ in range(BUS_LAT_ROUNDS):
        for arm, env in arms.items():
            out = _bus_job("--latency-worker", "BUSLAT", extra_env=env,
                           timeout=90)
            if out is None:
                continue
            if arm in ("persistent", "locked"):
                e = out.pop("engaged", None)
                engaged = e if engaged is None else (engaged and e)
            else:
                out.pop("engaged", None)
            cur = best.setdefault(arm, out)
            if out is not cur:
                for label, v in out.items():
                    if v["p50"] < cur[label]["p50"]:
                        cur[label] = v
    if any(arm not in best for arm in arms):
        return None
    best["engaged"] = bool(engaged)
    return best


def _raw_socket_pingpong(iters=BUS_LAT_ITERS):
    """Loopback TCP ping-pong floor: one 8-byte message each way per
    iteration over a single accepted pair — what the kernel charges for
    one socket round trip on this box, with no allreduce machinery at
    all. The persistent arm's 4B locked p50 is judged against 2x this
    floor (the ISSUE 17 target), so the floor rides the record next to
    the family it anchors. Returns the p50 in microseconds or None."""
    import socket
    import threading

    try:
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)

        def _echo():
            conn, _ = srv.accept()
            with conn:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                while True:
                    b = conn.recv(8, socket.MSG_WAITALL)
                    if len(b) < 8:
                        return
                    conn.sendall(b)

        t = threading.Thread(target=_echo, daemon=True)
        t.start()
        msg = b"\x00" * 8
        lats = []
        with socket.create_connection(
                ("127.0.0.1", srv.getsockname()[1])) as cli:
            cli.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            for _ in range(50):  # warmup: connection + first-touch
                cli.sendall(msg)
                cli.recv(8, socket.MSG_WAITALL)
            for _ in range(iters):
                t0 = time.perf_counter()
                cli.sendall(msg)
                cli.recv(8, socket.MSG_WAITALL)
                lats.append((time.perf_counter() - t0) * 1e6)
        srv.close()
        t.join(timeout=5)
        lats.sort()
        return round(lats[len(lats) // 2], 1)
    except OSError:
        return None


def transformer_std_config():
    """``transformer_std``, the headline decoder cell (also what
    ``chip_smoke.py`` trains and serves): a standard-proportioned
    8-layer d=2048 GQA decoder, not a benchmark-friendly shallow/wide
    shape. Tuned by on-chip sweep (r04, before PR 1): flash attention
    with sequence-spanning tiles (halves the attention FLOPs vs
    dense-causal and avoids the [T,T] score materialization), remat
    off, layer scan unrolled, checkpoint CSE allowed."""
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=8192, d_model=2048, n_layers=8, n_heads=16,
        n_kv_heads=8, d_ff=8192, max_seq=1024, dtype=jnp.bfloat16,
        sp_attention="flash", remat=False, scan_unroll=8)


def _transformer_worker():
    """Secondary metric: decoder-LM training throughput + MFU on this
    chip (the matmul-heavy workload the MXU is built for; ResNet-50 at
    the protocol's batch 32 is input/BN-bound and underreports chip
    utilization). Runs in its own subprocess (see _transformer_extra)
    so a slow compile can be killed without losing the primary metric.
    Prints "TFEXTRA {json}"."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.common.compile_cache import use_compile_cache
    from horovod_tpu.models import TransformerConfig, make_train_step
    from horovod_tpu.parallel import build_mesh

    use_compile_cache()
    mesh = build_mesh(dp=-1)
    kind = jax.devices()[0].device_kind.lower()
    peak = {"v5 lite": 197e12, "v5litepod": 197e12,
            "v4": 275e12, "v5p": 459e12}
    peak_flops = next((v for k, v in peak.items() if k in kind), None)
    if peak_flops is None:
        raise RuntimeError(
            f"no peak FLOP/s known for device_kind {kind!r}: MFU is "
            "only defined on a chip in the peak table")

    def measure(cfg, batch, seq, iters=20):
        init_state, step, _ = make_train_step(cfg, mesh)
        state = jax.jit(init_state)(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1),
                                  (batch, seq + 1), 0, cfg.vocab_size)
        b = {"tokens": jax.device_put(
            toks, NamedSharding(mesh, P(("dp", "fsdp"), None)))}
        for _ in range(3):
            state, loss = step(state, b)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = step(state, b)
        float(loss)
        dt = time.perf_counter() - t0
        tok_s = batch * seq * iters / dt / mesh.devices.size
        n_params = sum(int(x.size) for x in
                       jax.tree.leaves(state["params"]))
        del state
        return round(tok_s, 1), round(
            100 * 6 * n_params * tok_s / peak_flops, 1)

    out = {}
    tok_s, mfu = measure(transformer_std_config(),
                         8 * mesh.devices.size, 1024)
    out["transformer_std_tokens_per_sec_per_chip"] = tok_s
    out["transformer_std_mfu_pct"] = mfu
    print("TFEXTRA " + json.dumps(out), flush=True)

    # Secondary: the same d=4096x4L wide-shallow 1.04B SHAPE as
    # rounds 3-4, but the measured CONFIG changed in round 5 —
    # sp_attention local->flash (shape-derived blocks), remat off,
    # scan_unroll=4: 69.1% MFU vs 56.3% for the old settings on
    # v5e. Cross-round deltas on these keys before/after round 5
    # therefore mix tuning with real speedups (the regression gate
    # only trips on drops, so the jump itself cannot false-alarm).
    # remat=False at scan_unroll=1 exceeds HBM on this shape; the
    # unroll is what lets XLA schedule it under 16 GB.
    cfg_wide = TransformerConfig(
        vocab_size=8192, d_model=4096, n_layers=4, n_heads=32,
        n_kv_heads=8, d_ff=16384, max_seq=1024, dtype=jnp.bfloat16,
        sp_attention="flash", remat=False, scan_unroll=4)
    tok_s, mfu = measure(cfg_wide, 8 * mesh.devices.size, 1024)
    out["transformer_tokens_per_sec_per_chip"] = tok_s
    out["transformer_mfu_pct"] = mfu
    print("TFEXTRA " + json.dumps(out), flush=True)

    # In-jit mesh-compression arms (EQuARX, ops/quantized.py): the
    # SAME train step at compression=none|bf16|int8 on one mesh, so
    # the key deltas isolate what the quantized gradient collectives
    # buy end to end. Arms interleave round-robin per the +-30%
    # protocol (docs/perf_tuning.md) and report best-of-rounds;
    # smaller shape than the headline so the extra compiles fit the
    # worker's 300s cap, printed incrementally so a cap kill keeps
    # everything already measured.
    from horovod_tpu.compression import Compression

    def comp_arms(arm_mesh, arms):
        """Interleaved best-of-rounds compression arms on
        ``arm_mesh`` -> ({arm: tokens/sec/chip}, n_params)."""
        cfg_c = TransformerConfig(
            vocab_size=4096, d_model=1024, n_layers=4, n_heads=16,
            n_kv_heads=8, d_ff=4096, max_seq=512, dtype=jnp.bfloat16,
            sp_attention="local", remat=False)
        B, T, iters, rounds = 4 * arm_mesh.devices.size, 512, 5, 3
        toks = jax.random.randint(jax.random.PRNGKey(2), (B, T + 1),
                                  0, cfg_c.vocab_size)
        live, n_params = {}, None
        for name, comp in arms.items():
            init_s, stp, _ = make_train_step(cfg_c, arm_mesh,
                                             compression=comp)
            st = jax.jit(init_s)(jax.random.PRNGKey(0))
            for _ in range(2):                    # compile + warm
                st, loss = stp(st, {"tokens": toks})
            float(loss)
            if n_params is None:
                n_params = sum(int(x.size) for x in
                               jax.tree.leaves(st["params"]))
            live[name] = (stp, st)
        best = {name: 0.0 for name in arms}
        for _ in range(rounds):
            for name in arms:
                stp, st = live[name]
                t0 = time.perf_counter()
                for _ in range(iters):
                    st, loss = stp(st, {"tokens": toks})
                float(loss)
                dt = time.perf_counter() - t0
                live[name] = (stp, st)
                best[name] = max(
                    best[name],
                    B * T * iters / dt / arm_mesh.devices.size)
        return best, n_params

    def emit_arms(best, n_params):
        for name, ts in best.items():
            out[f"transformer_{name}_tokens_per_sec_per_chip"] = round(
                ts, 1)
            out[f"transformer_mfu_{name}"] = round(
                100 * 6 * n_params * ts / peak_flops, 1)
        print("TFEXTRA " + json.dumps(out), flush=True)

    # dp plane: the quantized allreduce needs a dp-only mesh (no
    # GSPMD collective to intercept otherwise) — build_mesh(dp=-1)
    # above qualifies.
    if all(s == 1 for ax, s in mesh.shape.items() if ax != "dp"):
        emit_arms(*comp_arms(mesh, {"comp_none": None,
                                    "bf16": Compression.bf16,
                                    "int8": Compression.int8}))

    # fsdp plane (ISSUE 14): the same shape/protocol on a ZeRO-3
    # mesh — comp_none rides GSPMD's own param-gather/grad-scatter,
    # the codec arms the partial-manual fsdp island, so these keys
    # isolate what quantizing the fsdp reduce-scatter hop buys.
    if mesh.devices.size > 1:
        emit_arms(*comp_arms(
            build_mesh(fsdp=-1),
            {"fsdp_comp_none": None,
             "fsdp_comp_bf16": Compression.bf16,
             "fsdp_comp_int8": Compression.int8}))


def _worker_extra(flag: str, tag: str, remaining_secs: float,
                  cap_secs: float, failed: list):
    """Run one worker (`bench.py <flag>`) in a killable subprocess
    bounded by the remaining budget, and return the parsed payload of
    its LAST "<tag> {json}" line (or None). The parent never touches
    JAX, so the worker owns the chip while it lives. If the child
    overruns, whatever it printed before the kill is kept — but a
    worker that dies or is killed is appended to ``failed`` (with the
    end of its stderr relayed), and main() exits non-zero for it: a
    crashed arm must not look like a skipped one."""
    import subprocess

    timeout = max(30.0, min(remaining_secs, cap_secs))
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag],
            capture_output=True, text=True, timeout=timeout,
            env=dict(os.environ))
        stdout, stderr = proc.stdout, proc.stderr
        died = f"rc={proc.returncode}" if proc.returncode else None
    except subprocess.TimeoutExpired as e:
        stdout, stderr = (
            b.decode(errors="replace") if isinstance(b, bytes) else b or ""
            for b in (e.stdout, e.stderr))
        died = f"killed at {timeout:.0f}s"
    if died:
        failed.append(f"{flag}: {died}")
        print(f"bench: worker {flag} {died}\n{stderr[-2000:]}",
              file=sys.stderr, flush=True)
    found = None
    for line in (stdout or "").splitlines():
        if line.startswith(tag + " "):
            found = json.loads(line[len(tag) + 1:])
    return found


def _transformer_extra(remaining_secs: float, failed: list):
    """Transformer tokens/sec + MFU extra (multi-minute compile —
    hence the killable subprocess)."""
    return _worker_extra("--transformer-worker", "TFEXTRA",
                         remaining_secs, 300.0, failed)


def _moe_worker():
    """Expert-parallel MoE dispatch arms (ISSUE 18): the SAME MoE train
    step under dispatch=gspmd vs the shard_map island at codec
    none|bf16|int8, interleaved best-of-rounds under the ±30% protocol
    like the compression arms, so the key deltas isolate what the
    quantized alltoall dispatch buys end to end. Also reports the
    codec's static dispatch-wire saving (``moe_dispatch_bytes_saved_pct``,
    from the same byte accounting quantized_alltoall itself uses) —
    a plumbing regression shows there even when tokens/sec noise hides
    it. Prints "MOEEXTRA {json}" incrementally so a cap kill keeps the
    finished arms."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from horovod_tpu.common.compile_cache import use_compile_cache
    from horovod_tpu.models.moe import capacity as moe_capacity
    from horovod_tpu.models.transformer import (
        TransformerConfig, make_train_step)
    from horovod_tpu.ops.quantized import alltoall_wire_bytes
    from horovod_tpu.parallel import build_mesh

    use_compile_cache()
    mesh = build_mesh(ep=-1)
    ep = int(mesh.shape.get("ep", 1))
    out = {}
    # d_model >= 256 so every int8 dispatch slab spans multiple
    # 256-elem blocks (a slab that pads its last block understates
    # the codec's real saving); n_experts=8 divides any pow-2 ep.
    # Shape sized so 4 arms x 3 rounds fit the 300s cap even on a
    # host-device box (the gspmd arm's all-experts einsum is ~2x
    # the island's cost there and dominates the budget).
    base = TransformerConfig(
        vocab_size=2048, d_model=256, n_layers=1, n_heads=4,
        n_kv_heads=4, d_ff=512, max_seq=128, dtype=jnp.bfloat16,
        sp_attention="local", remat=False, n_experts=8,
        moe_top_k=2, moe_capacity_factor=1.25)
    B, T, iters, rounds = 2 * mesh.devices.size, 128, 3, 3
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, T + 1),
                              0, base.vocab_size)
    # On a single-device box the island routes to the GSPMD closure
    # by construction (make_moe_ffn's ep<=1 rule): the three island
    # arms would measure the identical XLA program three more
    # times, so only the gspmd reference runs there. The gate only
    # compares keys present in both rounds, so the narrower payload
    # never trips it.
    arms = {"gspmd": ("gspmd", None)}
    if ep > 1:
        arms.update({"none": ("island", "none"),
                     "bf16": ("island", "bf16"),
                     "int8": ("island", "int8")})
    live = {}
    for name, (disp, codec) in arms.items():
        cfg = dataclasses.replace(base, moe_dispatch=disp,
                                  moe_compression=codec)
        init_s, stp, _ = make_train_step(cfg, mesh)
        st = jax.jit(init_s)(jax.random.PRNGKey(0))
        for _ in range(2):                    # compile + warm
            st, loss = stp(st, {"tokens": toks})
        float(loss)
        live[name] = (stp, st)
    best = {name: 0.0 for name in arms}
    for _ in range(rounds):
        for name in arms:
            stp, st = live[name]
            t0 = time.perf_counter()
            for _ in range(iters):
                st, loss = stp(st, {"tokens": toks})
            float(loss)
            dt = time.perf_counter() - t0
            live[name] = (stp, st)
            best[name] = max(best[name],
                             B * T * iters / dt / mesh.devices.size)
        for name, ts in best.items():
            out[f"moe_tokens_per_sec_{name}"] = round(ts, 1)
        print("MOEEXTRA " + json.dumps(out), flush=True)
    if ep > 1:
        # Static accounting for ONE dispatch hop at the measured
        # shape (the combine hop ships the same slabs back, so the
        # ratio is identical): int8 vs the f32 slabs the island
        # would otherwise put on the inter-chip wire.
        C = moe_capacity(base.moe, T)
        shape = (ep, base.n_experts // ep, B // ep, C, base.d_model)
        none_b = alltoall_wire_bytes(shape, "none")
        int8_b = alltoall_wire_bytes(shape, "int8")
        out["moe_dispatch_bytes_saved_pct"] = round(
            100.0 * (1.0 - int8_b / none_b), 1)
        print("MOEEXTRA " + json.dumps(out), flush=True)


def _moe_extra(remaining_secs: float, failed: list):
    """MoE dispatch-plane arms (four train-step compiles — hence the
    killable subprocess, same cap as the transformer extra)."""
    return _worker_extra("--moe-worker", "MOEEXTRA",
                         remaining_secs, 300.0, failed)


def _serve_worker():
    """Serving metrics: continuous-batching throughput + latency tails
    on the mixed-length trace, the chunked-prefill tail on the same
    trace, and the prefix-cache win on the shared-system-prompt trace
    (horovod_tpu/serve/bench.py), run in its own killable subprocess
    like the transformer extra. Prints "SERVEEXTRA {json}" after each
    benchmark so a kill mid-run keeps the finished part."""
    from horovod_tpu.common.compile_cache import use_compile_cache
    from horovod_tpu.serve.bench import (
        run_prefix_benchmark, run_router_benchmark,
        run_serving_benchmark, run_spec_benchmark,
        run_trace_overhead_benchmark,
    )

    use_compile_cache()

    # The benchmark's own contract: continuous batching must beat
    # static on mixed lengths; ride the ratio into the payload so
    # a scheduler regression is visible round-over-round.
    out = run_serving_benchmark(n_requests=32)
    print("SERVEEXTRA " + json.dumps(out), flush=True)
    # Observability tax: request-trace tagging overhead (the
    # always-on <2% promise) + the full-ring flight-dump cost.
    # Both UNGATED trajectory keys; cheap (reuses the tiny model's
    # compiled bucket set).
    out.update(run_trace_overhead_benchmark(n_requests=24))
    print("SERVEEXTRA " + json.dumps(out), flush=True)
    # Prefix-cache tier: cache-on/off ratio + hit rate on the
    # shared-prefix trace (the tokens-per-request lever).
    out.update(run_prefix_benchmark(n_requests=32))
    print("SERVEEXTRA " + json.dumps(out), flush=True)
    # Speculative tier: draft/target pair vs plain decode on the
    # decode-heavy multi-tenant trace (serve_spec_* keys — the
    # tokens-per-weight-pass lever; accept rate rides along).
    out.update(run_spec_benchmark(n_requests=24))
    print("SERVEEXTRA " + json.dumps(out), flush=True)
    # Fleet tier: routed vs random placement at 4 replicas on the
    # multi-tenant trace (the placement lever above the engine).
    # After the single-replica tiers, so a budget kill keeps them.
    out.update(run_router_benchmark(n_requests=32))
    print("SERVEEXTRA " + json.dumps(out), flush=True)
    # No cross-process tier here: this worker owns the chip, and a
    # spawned serve worker that needs it would fail or hang (one
    # process per chip). `python -m horovod_tpu.serve.bench` still runs
    # that arm on CPU; ROADMAP B7 says what spawn_worker needs first.


def _elastic_chaos_child():
    """Per-worker body of the elastic churn-recovery case: train
    BENCH_CHAOS_TOTAL batches of a fixed-name allreduce under the
    elastic driver, logging ``batch t_mono size epoch engaged`` per
    completed step (CLOCK_MONOTONIC is system-wide on Linux, so the
    launcher can difference timestamps across processes). Identity
    localhost:1 SIGKILLs itself once at BENCH_CHAOS_KILL_AT — the
    membership event whose recovery latency the launcher measures."""
    import numpy as np

    import horovod_tpu as hvd
    import horovod_tpu.elastic as elastic

    log_dir = os.environ["BENCH_CHAOS_DIR"]
    total = int(os.environ.get("BENCH_CHAOS_TOTAL", "24"))
    kill_at = int(os.environ.get("BENCH_CHAOS_KILL_AT", "6"))
    ident = os.environ["HOROVOD_ELASTIC_ID"]
    path = os.path.join(log_dir, ident.replace(":", "_") + ".log")

    hvd.init()
    state = elastic.ObjectState(batch=0)

    @elastic.run
    def train(state):
        while state.batch < total:
            hvd.allreduce(np.ones(64, np.float32), op=hvd.Average,
                          name="bench_chaos")
            state.batch += 1
            with open(path, "a") as f:
                f.write(f"{state.batch} {time.monotonic():.6f} "
                        f"{hvd.size()} {hvd.membership().epoch} "
                        f"{int(hvd.steady_lock_engaged())}\n")
            if ident == "localhost:1" and state.batch == kill_at:
                marker = os.path.join(log_dir, "killed")
                if not os.path.exists(marker):
                    with open(marker, "w") as f:
                        f.write(f"{time.monotonic():.6f}\n")
                    os.kill(os.getpid(), 9)  # SIGKILL, no cleanup
            time.sleep(0.05)
            state.commit()
        return state.batch

    train(state)
    hvd.shutdown()


def _elastic_chaos_worker():
    """Elastic churn-recovery latencies (ISSUE 16): one seeded chaos
    job — SIGKILL a worker mid-run, then grow 2->4 — and report

    * ``elastic_recovery_ms``: kill to the first step completed under
      the post-churn membership epoch (restore + re-rendezvous +
      respawn, the whole recovery path);
    * ``steady_relock_after_join_ms``: grow trigger to the first step
      at the grown size with the steady lock re-engaged (how long the
      job pays negotiated cycles after a join).

    Prints "ELASTICEXTRA {json}"."""
    import glob
    import tempfile
    import threading

    from horovod_tpu.runner.elastic_driver import FixedHostDiscovery
    from horovod_tpu.runner.launch import LaunchSettings, launch_elastic

    root = os.path.dirname(os.path.abspath(__file__))
    log_dir = tempfile.mkdtemp(prefix="bench_chaos_")
    kill_at = 6
    env = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": root, "HOROVOD_CYCLE_TIME": "1",
        "BENCH_CHAOS_DIR": log_dir, "BENCH_CHAOS_TOTAL": "70",
        "BENCH_CHAOS_KILL_AT": str(kill_at),
        # Tight watcher poll: the measured windows must not be
        # dominated by a 1 s default poll interval, and the job must
        # still be RUNNING when the joiners arrive (a job that drains
        # before noticing the grow strands them mid-rendezvous).
        "HOROVOD_ELASTIC_POLL_SECS": "0.1",
        # The host-plane recovery path is the thing under test; the
        # XLA data plane would only add compile noise to the clock.
        "HOROVOD_XLA_EXEC": "0",
    }
    settings = LaunchSettings(
        np=0, command=[sys.executable, os.path.abspath(__file__),
                       "--elastic-chaos-child"],
        env=env, start_timeout=60)
    discovery = FixedHostDiscovery({"localhost": 2})
    result = {}

    def runner():
        result["codes"] = launch_elastic(
            settings, discovery, min_np=1, max_np=4,
            discovery_interval=0.3)

    def max_batch():
        out = 0
        for p in glob.glob(os.path.join(log_dir, "*.log")):
            try:
                with open(p) as f:
                    for ln in f:
                        out = max(out, int(ln.split()[0]))
            except (OSError, ValueError, IndexError):
                pass
        return out

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    # Grow AFTER the kill has been recovered from (two completed
    # post-kill steps), so the two measured windows never overlap.
    t_grow = None
    deadline = time.monotonic() + 90
    while time.monotonic() < deadline and t.is_alive():
        if (os.path.exists(os.path.join(log_dir, "killed"))
                and max_batch() >= kill_at + 2):
            discovery.set_hosts({"localhost": 4})
            t_grow = time.monotonic()
            break
        time.sleep(0.05)
    t.join(120)
    if t.is_alive() or t_grow is None:
        print(f"elastic-chaos: job stalled (alive={t.is_alive()}, "
              f"grow_fired={t_grow is not None})", file=sys.stderr)
        sys.exit(1)
    codes = result.get("codes", {})
    if any(c != 0 for c in codes.values()):
        print(f"elastic-chaos: nonzero exits {codes}", file=sys.stderr)
        sys.exit(1)

    with open(os.path.join(log_dir, "killed")) as f:
        t_kill = float(f.read().split()[0])
    rows = []
    for p in glob.glob(os.path.join(log_dir, "*.log")):
        with open(p) as f:
            for ln in f:
                b, ts, size, ep, eng = ln.split()
                rows.append((float(ts), int(size), int(ep), int(eng)))
    ep_kill = max((ep for ts, _, ep, _ in rows if ts <= t_kill),
                  default=0)
    post = [ts for ts, _, ep, _ in rows if ep > ep_kill]
    relock = [ts for ts, size, _, eng in rows
              if size == 4 and eng and ts > t_grow]
    if not post or not relock:
        print(f"elastic-chaos: no measurement (post={len(post)}, "
              f"relock={len(relock)})", file=sys.stderr)
        sys.exit(1)
    print("ELASTICEXTRA " + json.dumps({
        "elastic_recovery_ms": round((min(post) - t_kill) * 1000, 1),
        "steady_relock_after_join_ms": round(
            (min(relock) - t_grow) * 1000, 1),
    }), flush=True)


def _elastic_extra(remaining_secs: float, failed: list):
    """Elastic churn-recovery extra (spawns a small elastic job: a
    kill + a grow over ~30 s of CPU host-plane training)."""
    return _worker_extra("--elastic-chaos-worker", "ELASTICEXTRA",
                         remaining_secs, 150.0, failed)


def _serve_extra(remaining_secs: float, failed: list):
    """Serving benchmark extra (continuous-batching engine +
    speculative decoding + in-process fleet router; the cap grew with
    each added stage — the spec tier compiles a deeper target
    model)."""
    return _worker_extra("--serve-worker", "SERVEEXTRA",
                         remaining_secs, 480.0, failed)


def _previous_bench(bench_dir=None):
    """Parsed metrics of the newest ``BENCH_r{N}.json`` the driver left
    next to this file (the previous round's record), or None."""
    import glob
    import re

    bench_dir = bench_dir or os.path.dirname(os.path.abspath(__file__))
    best, best_n = None, -1
    for p in glob.glob(os.path.join(bench_dir, "BENCH_r[0-9]*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        if m and int(m.group(1)) > best_n:
            best_n, best = int(m.group(1)), p
    if best is None:
        return None
    try:
        with open(best) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    return data.get("parsed", data) if isinstance(data, dict) else None


# Metric direction by flattened-key leaf suffix. Latencies (the serve
# tier's `serve_p50/p99_*_ms` keys) REGRESS when they RISE — comparing
# them higher-is-better reported a latency blowup as an improvement
# and a latency win as a drop. Counter-ish keys (step counts, eviction
# totals, high-water gauges) have no better/worse direction at all and
# are excluded from the gate.
# _us_p50_np4 covers the flat raw-socket ping-pong floor key, whose
# trailing np tag would otherwise hide the `_us` latency direction.
LOWER_IS_BETTER_SUFFIXES = ("_ms", "_us", "_us_p50_np4")
# _us_p99 (coordinator-cycle tail) is a log2-bucket upper bound that
# jumps in powers of two with scheduler noise; _fill_pct tracks the
# autotuner's live fusion threshold. Neither has a stable enough
# better/worse direction for a 10% gate — they are trajectory keys.
# _count covers the fleet-router tallies (handoffs moved, replicas in
# the fleet): pure counts with no better/worse direction, while the
# router's hit-rate/throughput keys gate higher-is-better and its
# *_ms keys ride the latency inversion above.
# _overhead_pct (trace-tagging tax) and _dump_ms (full-ring flight
# dump) are sub-percent / sub-ms observability costs whose round-over-
# round swing is scheduler noise: trajectory keys, never gated — and
# _dump_ms must be listed HERE or the `_ms` suffix would latency-gate
# it.
UNGATED_SUFFIXES = ("_steps", "_evictions", "_high_water", "_us_p99",
                    "_fill_pct", "_count", "_probe_ms", "_overhead_pct",
                    "_dump_ms")


def find_regressions(prev, cur, threshold=0.10):
    """Compare this round's metrics against the previous round's and
    return every metric that REGRESSED by more than ``threshold``
    (fraction): dropped, for the (default) higher-is-better metrics;
    rose, for latency keys (leaf suffix in ``LOWER_IS_BETTER_SUFFIXES``).
    Both trees are flattened (nested extras become dotted keys); only
    keys present in both rounds are compared, so adding or removing a
    metric never trips the gate."""
    def flatten(d, prefix=""):
        out = {}
        for k, v in (d or {}).items():
            if not prefix and k == "regression":
                # The previous payload's own gate output: flattening it
                # would manufacture regression.<metric>.prev keys and
                # spurious flags on back-to-back flagged rounds.
                continue
            if isinstance(v, dict):
                out.update(flatten(v, f"{prefix}{k}."))
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"{prefix}{k}"] = float(v)
        return out

    prev_f, cur_f = flatten(prev), flatten(cur)
    regs = {}
    for k, pv in prev_f.items():
        cv = cur_f.get(k)
        if cv is None or pv <= 0:
            continue
        leaf = k.rsplit(".", 1)[-1]
        if leaf.endswith(UNGATED_SUFFIXES):
            continue
        if leaf.endswith(LOWER_IS_BETTER_SUFFIXES):
            if (cv - pv) / pv > threshold:
                regs[k] = {"prev": pv, "cur": cv,
                           "rise_pct": round(100 * (cv - pv) / pv, 1)}
        elif (pv - cv) / pv > threshold:
            regs[k] = {"prev": pv, "cur": cv,
                       "drop_pct": round(100 * (pv - cv) / pv, 1)}
    return regs


def _resnet_worker():
    """The primary metric: ResNet-50 synthetic training throughput on
    every device this process sees. A worker like the others — the
    parent never touches JAX, so whichever worker is running owns the
    chip. Prints "RESNET {json}"."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.common.compile_cache import use_compile_cache
    from horovod_tpu.models import resnet50
    from horovod_tpu.parallel import build_mesh

    use_compile_cache()
    mesh = build_mesh(dp=-1)
    n_dev = mesh.devices.size

    # The reference protocol is batch 32 PER DEVICE
    # (pytorch_synthetic_benchmark.py); scale the global batch by the dp
    # size so per-chip batch matches on any mesh.
    batch = int(os.environ.get("BENCH_BATCH", str(32 * n_dev)))
    warmup, rounds, iters = 10, 10, 10

    model = resnet50(dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (batch, 224, 224, 3), jnp.bfloat16)
    y = jax.random.randint(rng, (batch,), 0, 1000)

    # One jitted program for init instead of hundreds of per-op
    # dispatches.
    variables = jax.jit(lambda k, xx: model.init(k, xx, train=True))(
        jax.random.PRNGKey(1), x)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(0.01, momentum=0.9)
    opt_state = opt.init(params)

    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P("dp"))

    def loss_fn(params, batch_stats, x, y):
        logits, upd = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        loss = -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()
        return loss, upd["batch_stats"]

    def step(state, _):
        params, batch_stats, opt_state = state
        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch_stats, x, y)
        # Data-parallel gradient combine rides the mesh (GSPMD psum);
        # on one chip it is a no-op, on a slice it is the hvd.allreduce.
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, new_bs, opt_state), loss

    # One jitted "round" = scan of `iters` training steps — the
    # TPU-idiomatic shape of the reference's 10-batch timeit body (no
    # per-step host dispatch in the measured region). State is donated
    # so each round reuses the previous round's buffers in place.
    @partial(jax.jit, donate_argnums=0)
    def run_round(state):
        state, losses = jax.lax.scan(step, state, None, length=iters)
        return state, losses[-1]

    state = (jax.device_put(params, repl), jax.device_put(batch_stats, repl),
             jax.device_put(opt_state, repl))
    x = jax.device_put(x, data_sh)
    y = jax.device_put(y, data_sh)

    for _ in range(max(1, warmup // iters)):
        state, loss = run_round(state)
    loss.block_until_ready()

    # One timed region over all rounds with a single final sync: rounds
    # chain through donated state on-device, so this measures steady-
    # state training throughput with no host round trip per round.
    t0 = time.perf_counter()
    for _ in range(rounds):
        state, loss = run_round(state)
    loss.block_until_ready()
    dt = time.perf_counter() - t0

    per_chip = (batch * iters * rounds / dt) / n_dev
    print("RESNET " + json.dumps({"value": round(per_chip, 2)}), flush=True)


def main():
    """The parent: runs each worker in turn, never imports JAX (a parent
    that has touched JAX holds the chip and its children cannot get
    it), prints the one JSON line, and exits non-zero if any worker
    died."""
    budget = float(os.environ.get("BENCH_TIME_BUDGET_SECS", "480"))
    failed = []
    resnet = _worker_extra("--resnet-worker", "RESNET", budget, 600.0,
                           failed)
    per_chip = resnet["value"] if resnet else None
    # Extras run only while inside the time budget: the primary JSON
    # line must print even if a driver-side timeout looms.
    extras_on = os.environ.get("BENCH_SKIP_EXTRAS") != "1"
    extra = {}
    # Cheap BASELINE.md target first; the transformer extra pays a
    # multi-minute compile and goes last. Gates require headroom for
    # each extra's own worst case, not just "budget not yet spent"
    # (the bus job's communicate() timeouts could otherwise overrun).
    if (extras_on and os.environ.get("BENCH_SKIP_BUS") != "1"
            and budget - (time.perf_counter() - _T0) > 120):
        bus = _bus_bandwidth()
        if bus is not None:
            # Registry-derived efficiency keys (ISSUE 5): the perf
            # trajectory captures fusion efficiency and coordinator
            # tail, not just throughput.
            tele = bus.pop("telemetry", {})
            if tele.get("fusion_fill_pct") is not None:
                extra["host_allreduce_fusion_fill_pct"] = (
                    tele["fusion_fill_pct"])
            if tele.get("cycle_us_p99") is not None:
                extra["host_allreduce_cycle_us_p99"] = tele["cycle_us_p99"]
            # The fused-small-tensor case gets its own key so the
            # fusion win/loss is legible in the perf trajectory next
            # to the single-tensor sizes.
            fused = {k: bus.pop(k) for k in list(bus)
                     if k.startswith("fused_")}
            if fused:
                extra["host_allreduce_busbw_fused_gbps_np4"] = fused
            # Key versioned with the measurement protocol (round 5
            # switched to best-of-3 timing): the regression gate only
            # compares keys present in both rounds, so a protocol
            # change never produces an apples-to-oranges flag.
            extra["host_allreduce_busbw_best3_gbps_np4"] = bus
    # Wire-compression cases (HOROVOD_WIRE_COMPRESSION over the TCP
    # ring): per-codec busbw + the achieved compression ratio, so the
    # BENCH trajectory captures the on-the-wire win (and the none
    # reference measured under the identical interleaved protocol).
    if (extras_on and os.environ.get("BENCH_SKIP_BUS") != "1"
            and budget - (time.perf_counter() - _T0) > 150):
        wire = _bus_wire_bandwidth()
        if wire is not None:
            # The uncompressed arm measured under the vectored/zerocopy
            # transport (ISSUE 10), with the mode and the measured
            # bytes-per-send-syscall attached (strings/aux keys ride
            # along; the gate compares only the shared numeric key).
            extra["host_allreduce_busbw_sendv_gbps_np4"] = {
                f"{BUS_WIRE_MB}MB": wire.get("none"),
                "transport": wire.pop("transport", None),
                "bytes_per_syscall": wire.pop("sendv_bytes_per_call", None),
            }
            ratio = wire.pop("ratio", {})
            saved = wire.pop("wire_bytes_saved_pct", None)
            if saved is not None:
                # Measured on-the-wire savings from the codec's own
                # byte accounting (pre vs post encode) — a codec or
                # plumbing regression shows here even when busbw noise
                # hides it.
                extra["wire_bytes_saved_pct"] = saved
            extra["host_allreduce_busbw_wire_bf16_gbps_np4"] = {
                f"{BUS_WIRE_MB}MB": wire.get("bf16"),
                f"{BUS_WIRE_MB}MB_none_ref": wire.get("none"),
            }
            extra["host_allreduce_busbw_wire_int8_gbps_np4"] = {
                f"{BUS_WIRE_MB}MB": wire.get("int8"),
                f"{BUS_WIRE_MB}MB_none_ref": wire.get("none"),
            }
            extra["wire_compression_ratio"] = ratio
    # Collective-algorithm arms (HOROVOD_COLLECTIVE_ALGO / the
    # selection table): per-algorithm busbw at a latency-bound and a
    # bandwidth-bound payload, measured under the same interleaved
    # protocol, plus the table's auto verdict per payload bucket.
    if (extras_on and os.environ.get("BENCH_SKIP_BUS") != "1"
            and budget - (time.perf_counter() - _T0) > 180):
        algo = _bus_algo_bandwidth()
        if algo is not None:
            table = algo.pop("table", None)
            synth_table = algo.pop("synth_table", None)
            audit = algo.pop("audit", None)
            probe_ms = algo.pop("topology_probe_ms", None)
            for arm, vals in algo.items():
                extra[f"host_allreduce_busbw_{arm}_gbps_np4"] = vals
            if table:
                # Strings, so the regression gate ignores them — the
                # record simply shows what auto would pick per bucket.
                extra["collective_algo_table_np4"] = table
            if synth_table:
                # The measured model's verdicts next to the hand
                # bands', with the changed buckets called out — the
                # audit trail proving which selections the probe moved
                # (the measured/handbands busbw arms above show what
                # each choice was worth).
                extra["collective_algo_synth_table_np4"] = synth_table
                extra["collective_algo_audit_np4"] = audit or {}
            if probe_ms is not None:
                # Probe cost rides the record ungated (_probe_ms in
                # UNGATED_SUFFIXES): tracked, but ±30% box swings make
                # a 10% gate on a ~40 ms measurement pure weather.
                extra["topology_probe_ms"] = probe_ms
    # Small-op latency family (ISSUE 15): steady-lock bypass vs
    # negotiated control path at 4B-64KB, arms interleaved as whole
    # jobs. `*_us` leaves gate lower-is-better; the speedup ratio
    # (off p50 / locked p50, smallest payload — where the control
    # path dominates) gates like any throughput key.
    if (extras_on and os.environ.get("BENCH_SKIP_BUS") != "1"
            and budget - (time.perf_counter() - _T0) > 200):
        lat = _bus_latency()
        if lat is not None:
            # Leaf suffixes carry the gate direction: p50 leaves end in
            # `_us` (lower-is-better, gated), p99 leaves in `_us_p99`
            # (UNGATED — this box's p99 swings 3-6x with scheduler
            # noise; a 10% gate on it would flag pure weather).
            for arm in ("persistent", "locked", "off"):
                for q in ("p50", "p99"):
                    leaf = "_us" if q == "p50" else "_us_p99"
                    extra[f"host_allreduce_latency_us_{q}_{arm}_np4"] = {
                        f"{label}{leaf}": lat[arm][label][q]
                        for _, label in BUS_LAT_SIZES}
            extra["steady_lock_engaged"] = lat["engaged"]  # bool: ungated
            small = BUS_LAT_SIZES[0][1]
            if lat["locked"][small]["p50"] > 0:
                extra["steady_lock_p50_speedup"] = round(
                    lat["off"][small]["p50"] / lat["locked"][small]["p50"],
                    2)
            # The ISSUE 17 headline ratio: classic locked p50 over
            # persistent p50 at the smallest payload (>=1.25x target).
            if lat["persistent"][small]["p50"] > 0:
                extra["steady_persistent_p50_speedup"] = round(
                    lat["locked"][small]["p50"]
                    / lat["persistent"][small]["p50"], 2)
            pp = _raw_socket_pingpong()
            if pp is not None:
                extra["raw_socket_pingpong_us_p50_np4"] = pp
    remaining = budget - (time.perf_counter() - _T0)
    if extras_on and remaining > 30:
        tf = _transformer_extra(remaining, failed)
        if tf is not None:
            extra.update(tf)
    # Expert-parallel MoE dispatch arms (ISSUE 18): gspmd vs the
    # quantized-alltoall island per codec, plus the static dispatch
    # wire saving. Same killable-subprocess treatment as the
    # transformer extra (four train-step compiles).
    remaining = budget - (time.perf_counter() - _T0)
    if (extras_on and os.environ.get("BENCH_SKIP_MOE") != "1"
            and remaining > 30):
        moe = _moe_extra(remaining, failed)
        if moe is not None:
            extra.update(moe)
    # Serving tier: tokens/sec + first-token tails from the
    # continuous-batching engine (ISSUE 1's workload layer). Cheap on
    # CPU (tiny model, ~10s) but still budget-gated.
    remaining = budget - (time.perf_counter() - _T0)
    if (extras_on and os.environ.get("BENCH_SKIP_SERVE") != "1"
            and remaining > 30):
        sv = _serve_extra(remaining, failed)
        if sv is not None:
            extra.update(sv)
    # Elastic churn-recovery tier: kill-to-recovered-step and
    # join-to-relocked wall times from a small seeded chaos job
    # (ISSUE 16's membership plane). `_ms` leaves gate
    # lower-is-better like the serve latency tails.
    remaining = budget - (time.perf_counter() - _T0)
    if (extras_on and os.environ.get("BENCH_SKIP_ELASTIC") != "1"
            and remaining > 40):
        el = _elastic_extra(remaining, failed)
        if el is not None:
            extra.update(el)
    payload = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": per_chip,
        "unit": "images/sec",
        "vs_baseline": (round(per_chip / REF_R50_IMG_PER_SEC_PER_DEVICE, 3)
                        if per_chip is not None else None),
        "extra": extra,
    }
    if failed:
        payload["failed_workers"] = failed
    # Round-over-round gate: a >10% drop on any shared metric rides the
    # JSON line into the driver's BENCH record instead of passing
    # silently (round 4's host-plane drop went unnoticed because
    # nothing compared rounds).
    prev = _previous_bench()
    if prev is not None:
        regs = find_regressions(prev, payload)
        if regs:
            payload["regression"] = regs
    print(json.dumps(payload))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    if "--bus-worker" in sys.argv:
        _bus_worker()
    elif "--latency-worker" in sys.argv:
        _latency_worker()
    elif "--bus-wire-worker" in sys.argv:
        _bus_wire_worker()
    elif "--bus-algo-worker" in sys.argv:
        _bus_algo_worker()
    elif "--resnet-worker" in sys.argv:
        _resnet_worker()
    elif "--transformer-worker" in sys.argv:
        _transformer_worker()
    elif "--moe-worker" in sys.argv:
        _moe_worker()
    elif "--serve-worker" in sys.argv:
        _serve_worker()
    elif "--elastic-chaos-worker" in sys.argv:
        _elastic_chaos_worker()
    elif "--elastic-chaos-child" in sys.argv:
        _elastic_chaos_child()
    else:
        main()
