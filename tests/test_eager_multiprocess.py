"""Launches real multi-process jobs over the TCP controller (the
test/parallel tier of the reference, run via localhost processes the way
its CI runs gloo over loopback)."""

import os
import socket
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "_mp_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_job(scenario: str, np_: int, timeout: int = 120, extra_env=None,
            expected_rc=None, per_rank_env=None):
    """Launch np_ ranks of the worker; expected_rc maps rank -> allowed
    nonzero exit code (default: every rank must exit 0). per_rank_env
    maps rank -> extra env applied to that rank ONLY — used to prove
    coordinator-synced knobs survive deliberately conflicting
    per-rank settings."""
    port = _free_port()
    procs = []
    for r in range(np_):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(r),
            "HOROVOD_SIZE": str(np_),
            "HOROVOD_LOCAL_RANK": str(r),
            "HOROVOD_LOCAL_SIZE": str(np_),
            "HOROVOD_CROSS_RANK": "0",
            "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_CONTROLLER_ADDR": f"127.0.0.1:{port}",
            "JAX_PLATFORMS": "cpu",
        })
        env.update(extra_env or {})
        env.update((per_rank_env or {}).get(r, {}))
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, scenario], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    failed = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(f"rank {r} timed out; output so far unknown")
        outs.append(out)
        if p.returncode != (expected_rc or {}).get(r, 0):
            failed.append((r, p.returncode, out))
    assert not failed, "\n".join(
        f"--- rank {r} rc={rc}\n{out}" for r, rc, out in failed)
    return outs


# np=2 on the TCP plane moved to the slow tier (ISSUE 10 budget
# headroom): transport_digest pins the whole np=2 TCP exchange surface
# per-bit (ring/hd/striped/doubling + fused group + fused allgather +
# broadcast, cross-rank digests), and the np=4 matrix covers every op's
# semantics on the same plane — the np=2 matrix re-proves neither.
@pytest.mark.parametrize("np_, plane", [
    (2, "shm"), (4, "shm"), (4, "tcp"),
    pytest.param(2, "tcp", marks=pytest.mark.slow)])
def test_full_matrix(np_, plane):
    # Both host data planes stay covered: shm is the single-host
    # default; HOROVOD_SHM_DISABLE forces the TCP peer-mesh algorithms
    # multi-host jobs use.
    env = {"HOROVOD_SHM_DISABLE": "1"} if plane == "tcp" else {}
    outs = run_job("matrix", np_, extra_env=env)
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_join(capfd):
    outs = run_job("join", 3)
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_join_race_no_deadlock():
    outs = run_job("join_race", 2, timeout=90)
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_join_solo_announce_no_hang():
    outs = run_job("join_solo_announce", 2, timeout=90)
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def _xla_env(np_):
    """Env for CALLBACK-mode jobs: XLA exec on, explicit coordinator
    (tests bypass the launcher's KV rendezvous)."""
    return {
        "HOROVOD_XLA_EXEC": "1",
        "HOROVOD_XLA_COORD_ADDR": f"127.0.0.1:{_free_port()}",
        # The conftest's 8-virtual-device flag would break the
        # one-device-per-process model; workers get a clean slate.
        "XLA_FLAGS": "",
    }


@pytest.mark.parametrize("np_", [
    2, pytest.param(4, marks=pytest.mark.slow)])  # 4-rank spawn is the
# single costliest variant; np_=2 keeps the coverage in tier-1
def test_xla_matrix(np_):
    """Full op matrix on jax arrays with exec_mode=CALLBACK (the VERDICT
    done-criterion for the eager XLA data plane)."""
    outs = run_job("xla_matrix", np_, timeout=240, extra_env=_xla_env(np_))
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_xla_rank_axis_follows_horovod_ranks():
    """On a TPU host jax's process indices are not the Horovod ranks
    (first four-chip run: a broadcast from root 0 delivered rank 3's
    tensor); the worker reverses the ids jax.distributed is given."""
    outs = run_job("xla_rank_order", 2, timeout=240, extra_env=_xla_env(2))
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_xla_join():
    outs = run_job("xla_join", 3, timeout=240, extra_env=_xla_env(3))
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_alltoall_ndim_mismatch_error_no_hang():
    run_job("alltoall_ndim_mismatch", 2, timeout=60)


def test_shape_mismatch_error_no_hang():
    run_job("shape_mismatch", 2, timeout=60)


def test_dtype_mismatch_error_no_hang():
    run_job("dtype_mismatch", 2, timeout=60)


@pytest.mark.parametrize("np_", [
    2, pytest.param(4, marks=pytest.mark.slow)])  # redundancy (ISSUE 16
# budget audit): the ragged fused-allgather math is width-independent
# and pinned at np=2; the 4-rank spawn re-proves it at the costliest
# process count — same split as test_xla_matrix above.
def test_fused_allgather(np_):
    run_job("fused_allgather", np_)


def test_xla_fused_allgather():
    run_job("xla_fused_allgather", 2, timeout=240, extra_env=_xla_env(2))


def _digests(outs):
    ds = [l.split()[1] for out in outs for l in out.splitlines()
          if l.startswith("DIGEST ")]
    assert len(ds) == len(outs), outs
    return set(ds)


@pytest.mark.parametrize("plane", ["shm", "shm_depth1", "tcp"])
def test_fused_bitwise_and_thread_invariance(plane):
    """Fused multi-tensor allreduce must be bitwise identical to the
    per-tensor path (asserted inside the worker), and the result bytes
    must be invariant to HOROVOD_REDUCE_THREADS — on both host planes
    and at both shm pipeline depths. The tiny segment cap forces the
    fused group across many segments so the pipeline actually runs."""
    base = {
        "shm": {"HOROVOD_SHM_SEGMENT_BYTES": "65536"},
        "shm_depth1": {"HOROVOD_SHM_SEGMENT_BYTES": "65536",
                       "HOROVOD_SHM_SEGMENT_DEPTH": "1"},
        "tcp": {"HOROVOD_SHM_DISABLE": "1"},
    }[plane]
    single = _digests(run_job(
        "fused_bitwise", 2,
        extra_env={**base, "HOROVOD_REDUCE_THREADS": "1"}))
    threaded = _digests(run_job(
        "fused_bitwise", 2,
        extra_env={**base, "HOROVOD_REDUCE_THREADS": "4"}))
    # All ranks agree (allreduce contract) and threads change nothing.
    assert len(single) == 1 and single == threaded, (single, threaded)


def test_timeline_carries_shm_pipeline_phases(tmp_path):
    """HOROVOD_TIMELINE output must name the pack/reduce/unpack phases
    of the pipelined shm allreduce so a stalled stage is diagnosable
    from the trace alone."""
    tl = str(tmp_path / "tl.json")
    run_job("shm_segmented", 2, extra_env={
        "HOROVOD_SHM_SEGMENT_BYTES": "65536",
        "HOROVOD_TIMELINE": tl,
        "HOROVOD_TIMELINE_RANK_SUFFIX": "1",
    })
    raw = open(tl + ".0").read()
    for phase in ("SHM_PACK", "SHM_REDUCE", "SHM_UNPACK"):
        assert phase in raw, f"timeline missing {phase}"


# ---------------------------------------------------------------------------
# On-the-wire gradient compression (HOROVOD_WIRE_COMPRESSION /
# hvd.allreduce(..., compression=...); docs/perf_tuning.md)
# ---------------------------------------------------------------------------

def test_wire_parity_np2():
    """np=2 TCP parity matrix on the doubling exchange: bf16/fp16 wire
    within dtype tolerance of `none`, int8+error-feedback converging on
    a repeated-allreduce loop, grouped compression, and bitwise
    thread-count invariance of the `none` codec."""
    outs = run_job("wire_parity", 2, timeout=180,
                   extra_env={"HOROVOD_SHM_DISABLE": "1"})
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_wire_ring_np4():
    """np=4 ring with every codec: parity vs `none` AND bitwise
    cross-rank agreement under lossy compression (each chunk's encoded
    bytes are forwarded verbatim; the owner self-decodes)."""
    outs = run_job("wire_ring", 4, timeout=180,
                   extra_env={"HOROVOD_SHM_DISABLE": "1"})
    digests = set()
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out
        for line in out.splitlines():
            if line.startswith("DIGEST "):
                digests.add(line)
    assert len(digests) == 1, digests


def test_wire_ragged_doubling_np3_agrees():
    """np=3 forced onto the doubling path (explicitly — the selection
    table would otherwise route this latency-band payload to
    halving-doubling): the ragged fold/unfold republishes the result
    quantized, and EVERY core rank — including the solo one that owns
    no fold partner — must requantize its own copy, or ranks drift by
    one rounding epsilon (regression: only fold-pair ranks
    self-decoded)."""
    outs = run_job("wire_ring", 3, timeout=180, extra_env={
        "HOROVOD_SHM_DISABLE": "1",
        "HOROVOD_COLLECTIVE_ALGO": "doubling",
    })
    digests = set()
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out
        for line in out.splitlines():
            if line.startswith("DIGEST "):
                digests.add(line)
    assert len(digests) == 1, digests


def test_wire_env_knob_applies_job_wide():
    """HOROVOD_WIRE_COMPRESSION=bf16 on every rank: ops without a
    per-op compression= must ride the codec (result differs bitwise
    from `none` but stays within bf16 tolerance)."""
    outs = run_job("wire_env", 2, timeout=120, extra_env={
        "HOROVOD_SHM_DISABLE": "1",
        "HOROVOD_WIRE_COMPRESSION": "bf16",
    })
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_wire_env_garbage_warns_and_falls_back():
    """A typo'd codec name must warn (once) and run uncompressed —
    never alias to a silently different codec."""
    outs = run_job("wire_env", 2, timeout=120, extra_env={
        "HOROVOD_SHM_DISABLE": "1",
        "HOROVOD_WIRE_COMPRESSION": "bf17",
    })
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out
    assert any("HOROVOD_WIRE_COMPRESSION" in out for out in outs), \
        "sanitized parse never warned about the bad codec name"


def test_shm_segmented_allreduce():
    """A 4 KB segment cap forces ~100 segments per op: boundaries land
    mid-entry, the fused group spans segments, and scale factors ride
    the per-segment pack/unpack (the production default is 8 MB; the
    cap also lets payloads larger than an arena slot use shm)."""
    outs = run_job("shm_segmented", 4,
                   extra_env={"HOROVOD_SHM_SEGMENT_BYTES": "4096"})
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


def test_shm_arena_active_single_host():
    """Single-host jobs must actually take the shared-memory data
    plane: the debug log announces the arena on every rank."""
    outs = run_job("matrix", 2, extra_env={"HOROVOD_LOG_LEVEL": "debug"})
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out
        assert "shm: arena" in out, "shm data plane never came up"


@pytest.mark.slow  # ~37s: a 3-rank spawn around a deliberate death
# wait (ISSUE 12 budget audit). Redundancy: the pid-liveness poison
# signal this pins is exercised tier-1 end to end by
# test_elastic_worker_failure_recovers_with_state (a rank hard-killed
# mid-training on the localhost shm plane — survivors can only
# recover because exactly this signal surfaced the death); the
# dedicated surfaces-within-seconds latency bound rides the slow tier.
def test_shm_peer_death_surfaces_fast():
    """A rank dying mid-stream must error the survivors within seconds
    (shm has no socket to break — pid liveness poisons the arena)."""
    np_ = 3
    outs = run_job("shm_die", np_, timeout=90,
                   expected_rc={np_ - 1: 17})  # the deliberate hard exit
    for r in range(np_ - 1):
        assert f"OK rank={r}" in outs[r], f"rank {r}: {outs[r]}"


@pytest.mark.parametrize("np_", [
    2, pytest.param(4, marks=pytest.mark.slow)])  # see test_xla_matrix
def test_torch_differentiable_collectives(np_):
    """Gradients through allreduce/grouped/allgather/broadcast/alltoall/
    reducescatter match the reference autograd contract
    (``torch/mpi_ops.py:186,393,578,663,806``)."""
    outs = run_job("torch_grads", np_, timeout=180)
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


# ---------------------------------------------------------------------------
# Multi-NIC advertise-address election (reference driver NIC
# intersection, runner/driver/driver_service.py:266)
# ---------------------------------------------------------------------------

def test_multi_nic_candidate_election():
    """Two-NIC simulation: every rank advertises a blackhole address
    first and loopback second (HOROVOD_PEER_HOSTS). The mesh dialer
    must fall through the unreachable candidate within its bounded
    slice and form the full peer mesh on the reachable one."""
    outs = run_job("matrix", 3, timeout=120, extra_env={
        "HOROVOD_PEER_HOSTS": "10.255.255.1,127.0.0.1",
        # Force the TCP peer mesh (shm would bypass peer dialing).
        "HOROVOD_SHM_DISABLE": "1",
    })
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out


@pytest.mark.slow  # redundancy (ISSUE 15 budget): the candidate
# election itself is tier-1-gated (test_multi_nic_candidate_election);
# this arm re-proves only the bounded-timeout refusal, ~9s of which is
# the deliberate 6s dial deadline.
def test_multi_nic_all_unreachable_fails_fast():
    """Only unreachable candidates: init must surface a bounded error
    (the non-blocking dialer), never hang on the kernel SYN backoff."""
    import time
    t0 = time.monotonic()
    with pytest.raises(AssertionError):
        run_job("matrix", 3, timeout=90, extra_env={
            "HOROVOD_PEER_HOSTS": "10.255.255.1",
            "HOROVOD_SHM_DISABLE": "1",
            "HOROVOD_CONTROLLER_TIMEOUT_MS": "6000",
        })
    assert time.monotonic() - t0 < 80
