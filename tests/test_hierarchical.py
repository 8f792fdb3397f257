"""Hierarchical (two-level) allreduce: np=4 as 2 virtual nodes × 2
local ranks on localhost — the host-plane analog of the reference's
NCCLHierarchicalAllreduce test coverage (intra-node reduce-scatter →
cross-node allreduce → intra-node allgather)."""

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


def run_two_node_job(scenario: str, local_size: int, n_nodes: int,
                     timeout: int = 120, extra_env=None):
    """Launch n_nodes*local_size ranks with node-major topology env."""
    np_ = local_size * n_nodes
    port = _free_port()
    procs = []
    for r in range(np_):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(r),
            "HOROVOD_SIZE": str(np_),
            "HOROVOD_LOCAL_RANK": str(r % local_size),
            "HOROVOD_LOCAL_SIZE": str(local_size),
            "HOROVOD_CROSS_RANK": str(r // local_size),
            "HOROVOD_CROSS_SIZE": str(n_nodes),
            "HOROVOD_CONTROLLER_ADDR": f"127.0.0.1:{port}",
            "JAX_PLATFORMS": "cpu",
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, scenario], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    failed = []
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(f"rank {r} timed out")
        outs.append(out)
        if p.returncode != 0:
            failed.append((r, p.returncode, out))
    assert not failed, "\n".join(
        f"--- rank {r} rc={rc}\n{out}" for r, rc, out in failed)
    return outs


HIER_ENV = {
    "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
    # Force every allreduce (even tiny test tensors) down the
    # hierarchical branch.
    "HOROVOD_RING_THRESHOLD": "1",
}


def test_hierarchical_full_matrix_2x2():
    run_two_node_job("matrix", local_size=2, n_nodes=2, extra_env=HIER_ENV)


def test_hierarchical_wire_compression_2x2():
    """Wire codecs under hierarchical mode compress only the
    cross-node doubling exchange (the intra-node ring phases stay full
    precision) — the parity/EF-convergence matrix must hold on the 2x2
    node-major layout with shm arenas off so the TCP phases run."""
    run_two_node_job("wire_parity", local_size=2, n_nodes=2, timeout=180,
                     extra_env={**HIER_ENV, "HOROVOD_SHM_DISABLE": "1"})


def test_hierarchical_2x3_ragged_local():
    """3 ranks per 'node' — ragged ring chunks + non-power-of-two cross
    group exercise the general shapes."""
    run_two_node_job("matrix", local_size=3, n_nodes=2, timeout=180,
                     extra_env=HIER_ENV)


def test_hierarchical_join_falls_back():
    """Under Join the contributor set shrinks: the decomposition no
    longer applies and the flat path must take over seamlessly."""
    run_two_node_job("join", local_size=2, n_nodes=2, extra_env=HIER_ENV)


@pytest.mark.slow  # redundancy (ISSUE 13 budget): layout fitness is
# ONE synced boolean (controller Initialize's AND-agreed my_hier_fit),
# whose downgrade face runs tier-1 on every single-node np=4 job where
# a hier verdict would be refused (ResolveCollectiveAlgo + the
# executor-side guard read the same flag), and whose positive face the
# remaining tier-1 2x2/2x3 hierarchical matrices pin. This ~8s spawn
# re-proves only the flag's refusal wiring — slow tier.
def test_hierarchical_refused_on_bad_layout():
    """A rank whose local/cross env does not fit node-major layout must
    disable hierarchical everywhere (not deadlock): run the matrix with
    topology that doesn't tile (local_size=3 for np=4 handled by
    giving every rank local_size=4... i.e., single-node topology) plus
    the hierarchical flag — it should silently run flat and pass."""
    port = _free_port()
    procs = []
    for r in range(4):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(r), "HOROVOD_SIZE": "4",
            "HOROVOD_LOCAL_RANK": str(r), "HOROVOD_LOCAL_SIZE": "4",
            "HOROVOD_CROSS_RANK": "0", "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_CONTROLLER_ADDR": f"127.0.0.1:{port}",
            "JAX_PLATFORMS": "cpu",
        })
        env.update(HIER_ENV)
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, "matrix"], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=120)
        assert p.returncode == 0, f"rank {r} rc={p.returncode}\n{out}"


# ---------------------------------------------------------------------------
# Hierarchical allgather over the per-node shm arena (reference
# MPIHierarchicalAllgather, mpi_operations.cc:190)
# ---------------------------------------------------------------------------

def _assert_node_arena_engaged(outs):
    joined = "\n".join(outs)
    assert "node arena up" in joined, (
        "per-node shm arena did not engage:\n" + joined[:2000])


def test_hierarchical_allgather_node_shm_2x2():
    """Matrix (ragged allgather included) on 2 virtual nodes x 2 local
    ranks: the per-node arena must come up and the intra-host stages of
    allgather ride it (intra-host shm gather -> leader ring ->
    intra-host shm unpack)."""
    outs = run_two_node_job("matrix", local_size=2, n_nodes=2,
                            extra_env={"HOROVOD_LOG_LEVEL": "info"})
    _assert_node_arena_engaged(outs)


@pytest.mark.slow  # redundancy (ISSUE 15 budget): the node-arena
# engagement wiring is pinned at 2x2 above, and the ragged local_size=3
# decomposition math by test_hierarchical_2x3_ragged_local — this run
# re-proves their intersection only.
def test_hierarchical_allgather_node_shm_2x3():
    outs = run_two_node_job("matrix", local_size=3, n_nodes=2, timeout=180,
                            extra_env={"HOROVOD_LOG_LEVEL": "info"})
    _assert_node_arena_engaged(outs)


def test_hierarchical_fused_allgather_node_shm():
    """Fused async allgathers (one response, several ragged tensors)
    through the node-arena path."""
    outs = run_two_node_job("fused_allgather", local_size=2, n_nodes=2,
                            extra_env={"HOROVOD_LOG_LEVEL": "info"})
    _assert_node_arena_engaged(outs)


@pytest.mark.slow  # redundancy (ISSUE 13 budget): the node-arena
# gating predicate is single-sourced (controller.h
# node_shm_applicable, which ANDs shm_wish) and its positive face runs
# tier-1 every time via test_hierarchical_allgather_node_shm_2x3; the
# shm-disable knob's job-wide semantics are separately pinned by the
# single-host override-warning path. This spawns a full 2x2 matrix job
# (~12s) only to assert a log line is absent — slow tier keeps the
# negative composition without the tier-1 spawn.
def test_node_arena_respects_shm_disable():
    outs = run_two_node_job("matrix", local_size=2, n_nodes=2,
                            extra_env={"HOROVOD_LOG_LEVEL": "info",
                                       "HOROVOD_SHM_DISABLE": "1"})
    assert "node arena up" not in "\n".join(outs)
