"""Elastic training: assignment unit tests, state semantics, and real
integration jobs — worker killed mid-training recovers with state
intact; scale-up mid-training re-forms the group (the reference's
``test/integration/test_elastic_torch.py`` tier via scripted
discovery, ``elastic_common.py:35-60``)."""

import glob
import os
import sys
import threading
import time

import numpy as np
import pytest


import horovod_tpu as hvd
import horovod_tpu.elastic as elastic
from horovod_tpu.runner.elastic_driver import (
    FixedHostDiscovery, assign_order, slots_for_order,
)
from horovod_tpu.runner import run
from horovod_tpu.runner.launch import LaunchSettings, launch_elastic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(ROOT, "tests", "_elastic_worker.py")
_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": os.pathsep.join([ROOT, os.path.join(ROOT, "tests")]),
    # Fast discovery reaction + commit cadence for tests.
    "HOROVOD_CYCLE_TIME": "1",
}


# ---------------------------------------------------------------------------
# assignment unit tests (reference test_elastic_driver.py tier)
# ---------------------------------------------------------------------------

def test_assign_order_initial_and_stability():
    seq = {}
    order = assign_order({"a": 2, "b": 1}, [], seq, 1, 0)
    assert order == ["a:0", "a:1", "b:0"]
    # b gains a slot; existing identities keep their relative order.
    order2 = assign_order({"a": 2, "b": 2}, order, seq, 1, 0)
    assert order2 == ["a:0", "a:1", "b:0", "b:1"]
    # a loses one slot: one of a's identities survives (first listed).
    order3 = assign_order({"a": 1, "b": 2}, order2, seq, 1, 0)
    assert order3 == ["a:0", "b:0", "b:1"]
    # a comes back: fresh seq, never reuses a:1.
    order4 = assign_order({"a": 2, "b": 2}, order3, seq, 1, 0)
    assert order4 == ["a:0", "b:0", "b:1", "a:2"]


def test_assign_order_min_max():
    seq = {}
    with pytest.raises(RuntimeError, match="need >= 3"):
        assign_order({"a": 2}, [], seq, 3, 0)
    assert assign_order({"a": 5}, [], {}, 1, 2) == ["a:0", "a:1"]


def test_slots_for_order_coordinates():
    table = slots_for_order(["h1:0", "h1:1", "h2:0"])
    s = table["h2:0"]
    assert (s.rank, s.local_rank, s.cross_rank) == (2, 0, 1)
    assert (s.size, s.local_size, s.cross_size) == (3, 1, 2)
    # Rank 0 identity first in order.
    assert table["h1:0"].rank == 0


# ---------------------------------------------------------------------------
# state semantics (single process)
# ---------------------------------------------------------------------------

def test_object_state_commit_restore():
    hvd.init()
    st = elastic.ObjectState(batch=3, data=[1, 2])
    st.batch = 10
    st.data.append(3)
    st.restore()          # back to last save (construction)
    assert st.batch == 3 and st.data == [1, 2]
    st.batch = 7
    st.commit()
    st.batch = 99
    st.restore()
    assert st.batch == 7


def test_torch_state_roundtrip():
    import torch
    from horovod_tpu.torch.elastic import TorchState

    hvd.init()
    model = torch.nn.Linear(2, 2)
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    st = TorchState(model=model, optimizer=opt, epoch=1)
    st.save()
    before = {k: v.clone() for k, v in model.state_dict().items()}
    with torch.no_grad():
        for p in model.parameters():
            p.mul_(0.0)
    st.epoch = 5
    st.restore()
    after = model.state_dict()
    for k in before:
        assert torch.equal(before[k], after[k])
    assert st.epoch == 1


class _TinyDataset:
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n


def test_elastic_sampler_partition_and_resume(monkeypatch):
    from horovod_tpu.torch.elastic import ElasticSampler

    hvd.init()
    s = ElasticSampler(_TinyDataset(10), shuffle=False)
    assert len(s) == 10 and list(s) == list(range(10))
    # Record two batches of 3; the re-shard excludes them.
    s.record_batch(0, 3)
    s.record_batch(1, 3)
    s.reset()
    assert len(s) == 4 and sorted(s) == [6, 7, 8, 9]
    # state_dict round-trip carries epoch + progress.
    clone = ElasticSampler(_TinyDataset(10), shuffle=False)
    clone.load_state_dict(s.state_dict())
    assert sorted(clone) == [6, 7, 8, 9]
    # End of epoch: progress clears, next epoch reshuffles everything.
    s.set_epoch(1)
    assert len(s) == 10 and not s.processed_indices

    # Simulated resize 1 -> 2: the two ranks' shards partition the
    # remainder (shuffle on; same seed/epoch => same permutation).
    import horovod_tpu.api as api
    s2 = ElasticSampler(_TinyDataset(10), seed=7)
    s2.record_indices({0, 1})
    monkeypatch.setattr(api, "size", lambda: 2)
    shards = []
    for r in (0, 1):
        monkeypatch.setattr(api, "rank", lambda r=r: r)
        s2.reset()
        shards.append(list(s2))
    assert len(shards[0]) == len(shards[1]) == 4
    assert sorted(shards[0] + shards[1]) == list(range(2, 10))


def _sampler_sync_worker():
    import horovod_tpu.torch as hvd
    from horovod_tpu.torch.elastic import ElasticSampler, TorchState

    class _Eight:  # local class: cloudpickle ships it by value
        def __len__(self):
            return 8

    hvd.init()
    sampler = ElasticSampler(_Eight(), shuffle=False)
    st = TorchState(sampler=sampler, batch=0)
    it = iter(sampler)
    # Each rank consumes its first batch of 2 from its own shard.
    sampler.record_batch(0, 2)
    st.sync()  # union of both ranks' progress, then common re-shard
    del it
    remaining = sorted(sampler.remaining)
    hvd.shutdown()
    return remaining, len(sampler.processed_indices)


@pytest.mark.slow  # redundancy: the sampler's partition/record/resume
# logic is pinned in-process every run by
# test_elastic_sampler_partition_and_resume, and TorchState sync rides
# the same state-broadcast path the other elastic tests drive — slow
# tier keeps the np=2 union-sync composition without a ~20s tier-1
# spawn.
def test_elastic_sampler_sync_unions_progress():
    results = run(_sampler_sync_worker, np=2, env=_WORKER_ENV,
                  start_timeout=90)
    # rank 0 processed {0, 2}, rank 1 {1, 3} (strided shards of 8).
    for remaining, n_done in results:
        assert n_done == 4
        assert remaining == [4, 5, 6, 7]


# ---------------------------------------------------------------------------
# integration (real driver + real processes on localhost)
# ---------------------------------------------------------------------------

def _run_elastic_job(tmp_path, total, extra_env, discovery, min_np=1,
                     max_np=0, mutate=None, timeout=180):
    log_dir = str(tmp_path)
    env = dict(_WORKER_ENV)
    env["ELASTIC_LOG_DIR"] = log_dir
    env["ELASTIC_TOTAL"] = str(total)
    env.update(extra_env)
    settings = LaunchSettings(
        np=0, command=[sys.executable, WORKER], env=env, start_timeout=90)
    result = {}

    def runner():
        result["codes"] = launch_elastic(
            settings, discovery, min_np=min_np, max_np=max_np,
            discovery_interval=0.3)

    t = threading.Thread(target=runner, daemon=True)
    t.start()
    if mutate:
        # The callback gets the runner thread so an event-driven
        # trigger can bail out the moment the job dies instead of
        # polling a dead job's log until its own deadline.
        mutate(t)
    t.join(timeout)
    assert not t.is_alive(), "elastic job did not finish"
    return result["codes"]


def test_elastic_worker_failure_recovers_with_state(tmp_path, capfd):
    """A rank hard-killed mid-training: survivors restore the last
    commit, the slot respawns, everyone finishes all batches without
    replaying more than the one uncommitted batch."""
    total = 30
    discovery = FixedHostDiscovery({"localhost": 2})
    codes = _run_elastic_job(
        tmp_path, total,
        {"ELASTIC_DIE_AT": "5", "ELASTIC_DIE_ID": "localhost:1",
         "ELASTIC_SLEEP": "0.05"},
        discovery)
    out = capfd.readouterr().out
    results = [ln for ln in out.splitlines() if "RESULT" in ln]
    # Both identities eventually completed all batches at size 2.
    assert sum(f"batch={total}" in ln for ln in results) >= 2, out
    assert all(c == 0 for c in codes.values()), codes

    # Resume-not-restart: the survivor's log replays at most one
    # uncommitted batch per reset (a fresh start would double-count).
    surv = os.path.join(str(tmp_path), "localhost_0.log")
    lines = [int(ln.split()[0]) for ln in open(surv)]
    assert max(lines) == total
    assert len(lines) <= total + 3, f"replayed too much: {len(lines)} lines"
    # The killed identity's log resumes past the failure point rather
    # than restarting at 1 after its respawn.
    dead = os.path.join(str(tmp_path), "localhost_1.log")
    dead_lines = [int(ln.split()[0]) for ln in open(dead)]
    restarts = sum(1 for a, b in zip(dead_lines, dead_lines[1:])
                   if b < a)
    assert restarts <= 1  # at most the respawn boundary
    assert dead_lines.count(1) <= 2


def test_elastic_scale_down_mid_training(tmp_path, capfd):
    """Discovery shrinks localhost:2 -> localhost:1: the removed
    worker's termination is an expected exit (code 0, no blacklist),
    and the survivor finishes alone."""
    total = 60
    discovery = FixedHostDiscovery({"localhost": 2})

    # Event-driven trigger, not a wall-clock sleep: shrink only after
    # the survivor has COMMITTED a few size-2 batches. The old
    # `sleep(2.0)` raced both ends under load — a contended box could
    # still be importing jax when the shrink landed (job then starts
    # directly at size 1, "2" never appears in the log), while an idle
    # one could finish all 60 batches before discovery reacted ("1"
    # never appears). Progress in the worker's own log is the only
    # signal that is right on every box.
    trigger_timed_out = []

    def mutate(job=None):
        first = os.path.join(str(tmp_path), "localhost_0.log")
        # Generous deadline, just under _run_elastic_job's 180s join:
        # a contended box occasionally stalls startup >60s (observed
        # once in a 10x stress run), and a premature raise here is
        # exactly the flake this trigger replaced. On timeout, RECORD
        # and return instead of raising — mutate runs before the join,
        # so a raise here would orphan the still-running job thread and
        # its worker processes into the next test's lap; returning lets
        # the job finish (at size 2) and the assert below fail cleanly
        # after everything is joined.
        deadline = time.monotonic() + 150
        while time.monotonic() < deadline:
            if job is not None and not job.is_alive():
                # Job already over (crashed or finished without us):
                # stop polling a dead job's log — the codes/results
                # asserts below report the real cause immediately.
                return
            try:
                with open(first) as f:
                    committed = [ln for ln in f if " size=2" in ln]
            except OSError:
                committed = []
            if len(committed) >= 3:
                discovery.set_hosts({"localhost": 1})
                return
            time.sleep(0.05)
        trigger_timed_out.append(True)

    codes = _run_elastic_job(
        tmp_path, total, {"ELASTIC_SLEEP": "0.05"}, discovery,
        max_np=2, mutate=mutate)
    assert not trigger_timed_out, "no size=2 training progress within 150s"
    out = capfd.readouterr().out
    results = [ln for ln in out.splitlines() if "RESULT" in ln]
    assert sum(f"batch={total}" in ln for ln in results) >= 1, out
    # Scale-down termination must NOT surface as a failure.
    assert all(c == 0 for c in codes.values()), codes
    first = os.path.join(str(tmp_path), "localhost_0.log")
    sizes = [ln.strip().split("size=")[1] for ln in open(first)]
    assert "2" in sizes and "1" in sizes, sizes[:10]


def test_elastic_scale_up_mid_training(tmp_path, capfd):
    """Discovery grows localhost:1 -> localhost:2 mid-run: the running
    worker re-rendezvouses at the next commit, the new worker syncs
    committed state, and both finish at size 2."""
    total = 60
    discovery = FixedHostDiscovery({"localhost": 1})

    def mutate(job=None):
        time.sleep(2.0)
        discovery.set_hosts({"localhost": 2})

    codes = _run_elastic_job(
        tmp_path, total, {"ELASTIC_SLEEP": "0.05"}, discovery,
        max_np=2, mutate=mutate)
    out = capfd.readouterr().out
    results = [ln for ln in out.splitlines() if "RESULT" in ln]
    assert sum(f"batch={total}" in ln for ln in results) == 2, out
    assert all(c == 0 for c in codes.values()), codes
    # The original worker's log must show the size transition 1 -> 2.
    first = os.path.join(str(tmp_path), "localhost_0.log")
    sizes = [ln.strip().split("size=")[1] for ln in open(first)]
    assert "1" in sizes and "2" in sizes, sizes[:10]
    # The joiner starts from synced state, not from batch 1.
    joiner = os.path.join(str(tmp_path), "localhost_1.log")
    joiner_first = int(open(joiner).readline().split()[0])
    assert joiner_first > 1, "new worker restarted from scratch"


def test_elastic_xla_exec_reforms_world(tmp_path, capfd):
    """--xla-exec elastic (round-4 verdict #1): after a worker death
    the survivor must tear down the old ``jax.distributed`` world and
    re-form it with the respawned peer at the new epoch. A kept stale
    world cannot complete a device collective with the newcomer (it
    rendezvouses a FRESH world), so finishing with correct per-size
    allreduce values is the proof of re-formation."""
    total = 16
    discovery = FixedHostDiscovery({"localhost": 2})
    codes = _run_elastic_job(
        tmp_path, total,
        {"ELASTIC_DIE_AT": "5", "ELASTIC_DIE_ID": "localhost:1",
         "ELASTIC_SLEEP": "0.05", "ELASTIC_JAX": "1",
         "HOROVOD_XLA_EXEC": "1",
         # conftest's 8-device flag would break the one-device-per-
         # process model the eager device plane requires.
         "XLA_FLAGS": ""},
        discovery, timeout=420)
    out = capfd.readouterr().out
    results = [ln for ln in out.splitlines() if "RESULT" in ln]
    assert sum(f"batch={total}" in ln for ln in results) >= 2, out
    assert all(c == 0 for c in codes.values()), codes
    surv = os.path.join(str(tmp_path), "localhost_0.log")
    jprocs = [int(ln.split("jprocs=")[1]) for ln in open(surv)]
    # Device plane active both before the failure and after the reset.
    assert jprocs[0] == 2 and jprocs[-1] == 2, jprocs


def test_elastic_xla_exec_scale_down_then_regrow(tmp_path, capfd):
    """--xla-exec elastic shrink 2 -> 1 -> 2: the survivor's re-init at
    size one must tear the multi-process XLA runtime down (a kept world
    still routes device collectives at a dead peer), and the growth
    back to two must re-form it — the size-1 interlude re-creates the
    local jax backend, which the re-formation has to flush first."""
    total = 80
    discovery = FixedHostDiscovery({"localhost": 2})
    surv = os.path.join(str(tmp_path), "localhost_0.log")

    def _wait_for(pattern, deadline_s=90):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if os.path.exists(surv) and pattern in open(surv).read():
                return True
            time.sleep(0.2)
        return False

    def mutate(job=None):
        # Shrink only once the 2-process world is live (batches logged)
        # so the test exercises teardown of a FORMED world, not the
        # startup race (a shrink mid-formation resolves by worker
        # death + respawn, bounded by the init timeout). Then grow
        # back once size-1 batches prove the interlude ran jax ops.
        assert _wait_for("size=2")
        discovery.set_hosts({"localhost": 1})
        assert _wait_for("size=1")
        discovery.set_hosts({"localhost": 2})

    codes = _run_elastic_job(
        tmp_path, total,
        {"ELASTIC_SLEEP": "0.05", "ELASTIC_JAX": "1",
         "HOROVOD_XLA_EXEC": "1", "XLA_FLAGS": ""},
        discovery, max_np=2, mutate=mutate, timeout=420)
    out = capfd.readouterr().out
    results = [ln for ln in out.splitlines() if "RESULT" in ln]
    assert sum(f"batch={total}" in ln for ln in results) >= 1, out
    assert all(c == 0 for c in codes.values()), codes
    lines = open(surv).read().splitlines()
    sizes = [ln.split("size=")[1].split()[0] for ln in lines]
    jprocs = [int(ln.split("jprocs=")[1]) for ln in lines]
    assert "2" in sizes and "1" in sizes, sizes[:10]
    # Teardown at the shrink: single-process jax while size is 1.
    assert any(s == "1" and j == 1 for s, j in zip(sizes, jprocs)), (
        list(zip(sizes, jprocs))[:20])
    # Re-formation at the growth: the tail runs at size 2 with a
    # 2-process world again.
    assert sizes[-1] == "2" and jprocs[-1] == 2, (sizes[-5:], jprocs[-5:])


def test_elastic_sampler_pad_smaller_than_world(monkeypatch):
    """Epoch tail: 1 unprocessed sample across 4 ranks — every rank
    must still yield exactly num_samples entries (repeat-padding), or
    ranks run unequal step counts and deadlock."""
    import horovod_tpu.api as api
    from horovod_tpu.torch.elastic import ElasticSampler

    hvd.init()
    s = ElasticSampler(_TinyDataset(9), shuffle=False)
    s.record_indices(range(8))  # one sample left
    monkeypatch.setattr(api, "size", lambda: 4)
    for r in range(4):
        monkeypatch.setattr(api, "rank", lambda r=r: r)
        s.reset()
        assert len(s) == 1
        assert list(s) == [8]


def test_epoch_watcher_sees_updates_without_commit(monkeypatch):
    """The background watcher (the notification-RPC analog) must mirror
    a driver epoch bump into the process within a couple of poll
    intervals, and check_host_updates must then interrupt WITHOUT its
    own KV round-trip."""
    import time as _time

    import horovod_tpu.elastic as el
    from horovod_tpu.common.exceptions import HostsUpdatedInterrupt
    from horovod_tpu.runner.http_kv import KVServer, kv_put

    server = KVServer(host="127.0.0.1")
    server.start()
    try:
        addr = f"127.0.0.1:{server.port}"
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", addr)
        monkeypatch.setenv("HOROVOD_RENDEZVOUS_TOKEN", server.token)
        monkeypatch.setenv("HOROVOD_ELASTIC_POLL_SECS", "0.1")
        monkeypatch.setattr(el, "_watcher", None)
        kv_put(addr, el.ASSIGN_SCOPE, "epoch", b"1")

        class S(el.State):
            def save(self):
                pass

            def restore(self):
                pass

            def sync(self):
                pass

        st = S()
        kv_put(addr, el.ASSIGN_SCOPE, "epoch", b"2")
        deadline = _time.monotonic() + 5.0
        while _time.monotonic() < deadline:
            if el._watcher.latest() >= 2:
                break
            _time.sleep(0.05)
        assert el._watcher.latest() >= 2, "watcher never saw the bump"
        # check_host_updates reads the mirrored value (no KV call) and
        # interrupts.
        monkeypatch.setattr(el, "current_epoch",
                            lambda: (_ for _ in ()).throw(
                                AssertionError("KV hit in check")))
        with pytest.raises(HostsUpdatedInterrupt):
            st.check_host_updates()
    finally:
        if el._watcher is not None:
            el._watcher.stop()
        el._watcher = None
        server.stop()
