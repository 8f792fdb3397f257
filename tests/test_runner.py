"""Launcher tests: host/slot model, KV store, CLI mapping, and real
end-to-end ``horovodrun`` jobs on localhost (the reference's
``test/single/test_run.py`` + ``test/integration/test_static_run.py``
tiers)."""

import os
import sys

import pytest

from horovod_tpu.runner import (
    HostInfo, get_host_assignments, parse_hostfile, parse_hosts, run,
    run_command,
)
from horovod_tpu.runner.http_kv import KVServer, kv_get, kv_put, kv_wait
from horovod_tpu.runner.launch import args_to_env, build_parser

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Workers run on the CPU like this process (conftest pins it; be
# explicit for the children). PYTHONPATH lets cloudpickle by-reference functions from this module
# resolve in workers.
_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": os.pathsep.join([ROOT, os.path.join(ROOT, "tests")]),
}


# ---------------------------------------------------------------------------
# host/slot model
# ---------------------------------------------------------------------------

def test_parse_hosts():
    hosts = parse_hosts("h1:2, h2:4,h3")
    assert hosts == [HostInfo("h1", 2), HostInfo("h2", 4), HostInfo("h3", 1)]
    with pytest.raises(ValueError):
        parse_hosts("h1:x")
    with pytest.raises(ValueError):
        parse_hosts("")


def test_parse_hostfile(tmp_path):
    f = tmp_path / "hosts"
    f.write_text("# comment\nh1 slots=2\nh2:3\nh3\n")
    assert parse_hostfile(str(f)) == [
        HostInfo("h1", 2), HostInfo("h2", 3), HostInfo("h3", 1)]


def test_host_assignments_homogeneous():
    slots = get_host_assignments(parse_hosts("h1:2,h2:2"), 4)
    assert [(s.hostname, s.rank, s.local_rank, s.cross_rank) for s in slots] \
        == [("h1", 0, 0, 0), ("h1", 1, 1, 0), ("h2", 2, 0, 1), ("h2", 3, 1, 1)]
    assert all(s.size == 4 and s.local_size == 2 and s.cross_size == 2
               for s in slots)


def test_host_assignments_heterogeneous_cross():
    # h1 has 2 slots, h2 has 1: the local_rank-1 "column" exists only on
    # h1, so its cross_size is 1 (reference SlotInfo semantics).
    slots = get_host_assignments(parse_hosts("h1:2,h2:1"), 3)
    col1 = [s for s in slots if s.local_rank == 1]
    assert len(col1) == 1 and col1[0].cross_size == 1
    col0 = [s for s in slots if s.local_rank == 0]
    assert [s.cross_rank for s in col0] == [0, 1]


def test_host_assignments_oversubscribed():
    with pytest.raises(ValueError, match="only 2 slots"):
        get_host_assignments(parse_hosts("h1:2"), 3)


def test_host_assignments_partial_use():
    slots = get_host_assignments(parse_hosts("h1:4,h2:4"), 3)
    assert all(s.hostname == "h1" for s in slots)
    assert slots[0].local_size == 3 and slots[0].cross_size == 1


# ---------------------------------------------------------------------------
# KV store
# ---------------------------------------------------------------------------

def test_kv_roundtrip():
    server = KVServer()
    port = server.start()
    addr = f"127.0.0.1:{port}"
    tok = server.token
    try:
        assert kv_get(addr, "s", "missing", token=tok) is None
        kv_put(addr, "s", "k", b"hello", token=tok)
        assert kv_get(addr, "s", "k", token=tok) == b"hello"
        assert kv_wait(addr, "s", "k", timeout=5, token=tok) == b"hello"
        assert server.get_local("s", "k") == b"hello"
        with pytest.raises(TimeoutError):
            kv_wait(addr, "s", "never", timeout=0.3, token=tok)
    finally:
        server.stop()


def test_kv_rejects_bad_token():
    import urllib.error
    server = KVServer()
    port = server.start()
    addr = f"127.0.0.1:{port}"
    try:
        kv_put(addr, "s", "k", b"secret", token=server.token)
        with pytest.raises(urllib.error.HTTPError):
            kv_get(addr, "s", "k", token="wrong")
        with pytest.raises(urllib.error.HTTPError):
            kv_put(addr, "exec", "fn", b"evil", token="")
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_env_mapping():
    args = build_parser().parse_args(
        ["-np", "2", "--fusion-threshold-mb", "32", "--cycle-time-ms", "5",
         "--cache-capacity", "0", "--timeline-filename", "/tmp/tl",
         "--log-level", "debug", "python", "train.py"])
    env = args_to_env(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "5.0"
    assert env["HOROVOD_CACHE_CAPACITY"] == "0"
    assert env["HOROVOD_TIMELINE"] == "/tmp/tl"
    assert env["HOROVOD_LOG_LEVEL"] == "debug"
    assert args.command == ["python", "train.py"]


# ---------------------------------------------------------------------------
# end-to-end on localhost
# ---------------------------------------------------------------------------

_ALLREDUCE_SNIPPET = """
import sys; sys.path.insert(0, {root!r})
import numpy as np
import horovod_tpu as hvd
hvd.init()
out = hvd.allreduce(np.full(4, float(hvd.rank() + 1), np.float32), name="t",
                    op=hvd.Sum)
expect = sum(range(1, hvd.size() + 1))
assert np.allclose(out, expect), (hvd.rank(), out)
print(f"RANK_OK {{hvd.rank()}}/{{hvd.size()}}")
hvd.shutdown()
"""


def test_horovodrun_end_to_end(capfd):
    run_command(
        [sys.executable, "-c", _ALLREDUCE_SNIPPET.format(root=ROOT)],
        np=3, env=_WORKER_ENV, start_timeout=90)
    out = capfd.readouterr().out
    for r in range(3):
        assert f"RANK_OK {r}/3" in out


def test_horovodrun_failure_propagates():
    with pytest.raises(RuntimeError, match="ranks failed"):
        run_command(
            [sys.executable, "-c",
             "import os, sys; sys.exit(3 if os.environ['HOROVOD_RANK'] == '1'"
             " else 0)"],
            np=2, env=_WORKER_ENV, start_timeout=60)


def _fn_for_run(scale):
    import horovod_tpu as hvd
    import numpy as np
    hvd.init()
    out = hvd.allreduce(np.ones(2, np.float32), name="r", op=hvd.Sum)
    result = (hvd.rank() * scale, float(out[0]))
    hvd.shutdown()
    return result


def test_run_function_api():
    results = run(_fn_for_run, args=(10,), np=2, env=_WORKER_ENV,
                  start_timeout=90)
    assert results == [(0, 2.0), (10, 2.0)]


def test_run_function_error_reports_traceback():
    def boom():
        raise ValueError("worker exploded")
    with pytest.raises(RuntimeError, match="worker exploded"):
        run(boom, np=2, env=_WORKER_ENV, start_timeout=60)


def test_autotune_and_hierarchical_flags():
    args = build_parser().parse_args(
        ["-np", "2", "--autotune", "--autotune-log-file", "/tmp/at.csv",
         "--hierarchical-allreduce", "python", "train.py"])
    env = args_to_env(args)
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_AUTOTUNE_LOG"] == "/tmp/at.csv"
    assert env["HOROVOD_HIERARCHICAL_ALLREDUCE"] == "1"
    # absent unless requested
    env2 = args_to_env(build_parser().parse_args(
        ["-np", "2", "python", "train.py"]))
    assert "HOROVOD_AUTOTUNE" not in env2
    assert "HOROVOD_HIERARCHICAL_ALLREDUCE" not in env2


def test_no_shm_flag_maps_to_env():
    args = build_parser().parse_args(
        ["-np", "2", "--no-shm", "--", "python", "x.py"])
    assert args_to_env(args)["HOROVOD_SHM_DISABLE"] == "1"


def test_config_file_defaults_and_cli_override(tmp_path):
    from horovod_tpu.runner.launch import _explicit_dests, apply_config_file

    cfg = tmp_path / "hvd.yaml"
    cfg.write_text(
        "verbose: true\n"
        "params:\n"
        "  fusion_threshold_mb: 48\n"
        "  cycle_time_ms: 7.5\n"
        "  hierarchical_allreduce: true\n"
        "autotune:\n"
        "  enabled: true\n"
        "  log_file: /tmp/at.csv\n"
        "stall_check:\n"
        "  warning_time_seconds: 11\n"
        "logging:\n"
        "  level: debug\n"
        "elastic:\n"
        "  reset_limit: 4\n")
    parser = build_parser()
    argv = ["-np", "2", "--cycle-time-ms", "2.0",
            "--config-file", str(cfg), "--", "python", "x.py"]
    args = parser.parse_args(argv)
    apply_config_file(args, str(cfg), _explicit_dests(parser, argv))
    env = args_to_env(args)
    # Config fills unset knobs...
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(48 * 1024 * 1024)
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_AUTOTUNE_LOG"] == "/tmp/at.csv"
    assert env["HOROVOD_HIERARCHICAL_ALLREDUCE"] == "1"
    assert env["HOROVOD_STALL_CHECK_TIME_SECONDS"] == "11"
    assert env["HOROVOD_LOG_LEVEL"] == "debug"
    assert args.verbose is True and args.reset_limit == 4
    # ...but an explicit CLI flag beats the file.
    assert env["HOROVOD_CYCLE_TIME"] == "2.0"


# ---------------------------------------------------------------------------
# TPU pod-slice launch (--tpu)
# ---------------------------------------------------------------------------

def test_tpu_process_bounds_table_and_topology():
    from horovod_tpu.runner.tpu import parse_topology, process_bounds

    assert parse_topology("4x4") == (4, 4, 1)
    assert parse_topology("2x2x2") == (2, 2, 2)
    with pytest.raises(ValueError, match="tpu-topology"):
        parse_topology("4,4")
    assert process_bounds(4) == (2, 2, 1)
    assert process_bounds(16) == (4, 4, 1)
    assert process_bounds(8, "2x2x2") == (2, 2, 2)
    with pytest.raises(ValueError, match="tiles 8 processes"):
        process_bounds(4, "2x2x2")
    with pytest.raises(ValueError, match="not a legal"):
        process_bounds(6)


def test_tpu_slot_env_contract():
    from horovod_tpu.runner import HostInfo, get_host_assignments
    from horovod_tpu.runner.tpu import tpu_slot_env

    slots = get_host_assignments(
        [HostInfo("h0", 4), HostInfo("h1", 4)], 8)
    env = tpu_slot_env(slots, slots[5])        # h1, local_rank 1
    assert env["TPU_VISIBLE_DEVICES"] == "1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "2,4,1"
    assert env["CLOUD_TPU_TASK_ID"] == "5"
    assert env["TPU_PROCESS_PORT"] == "8477"
    assert env["HOROVOD_XLA_EXEC"] == "1"
    addrs = env["TPU_PROCESS_ADDRESSES"].split(",")
    assert len(addrs) == 8                      # rank-major, all ranks
    assert addrs[0] == "h0:8476" and addrs[5] == "h1:8477"


def test_tpu_cli_rejects_illegal_worlds(capfd):
    from horovod_tpu.runner.launch import main

    assert main(["--tpu", "-np", "6", "--", "python", "x.py"]) == 2
    assert "not a legal" in capfd.readouterr().err
    assert main(["--tpu", "-np", "4", "--host-discovery-script", "d.sh",
                 "--", "python", "x.py"]) == 2
    assert "elastic" in capfd.readouterr().err


_TPU_SNIPPET = """
import os, sys
sys.path.insert(0, {root!r})
lr, r = os.environ["HOROVOD_LOCAL_RANK"], os.environ["HOROVOD_RANK"]
assert os.environ["TPU_VISIBLE_DEVICES"] == lr, "chip carve wrong"
assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
assert os.environ["TPU_PROCESS_BOUNDS"] == "2,2,1"
assert os.environ["CLOUD_TPU_TASK_ID"] == r
assert len(os.environ["TPU_PROCESS_ADDRESSES"].split(",")) == 4
import jax
import jax.numpy as jnp
import horovod_tpu as hvd
hvd.init()   # HOROVOD_XLA_EXEC=1 from the carve -> jax.distributed up
assert jax.local_device_count() == 1, "one device per process"
out = hvd.allreduce(jnp.ones(4, jnp.float32), name="t", op=hvd.Sum)
assert float(out[0]) == 4.0, float(out[0])
print(f"TPU_OK {{hvd.rank()}}/{{hvd.size()}}", flush=True)
hvd.shutdown()
"""


@pytest.mark.slow  # ~15s 4-proc spawn (ISSUE 12 budget audit).
# Redundancy: each layer of this composite is pinned tier-1 on its
# own — the chip-carve/topology env contract by the
# test_tpu_process_bounds* unit tests, the launcher-KV bring-up by
# the http_kv tier, and the eager XLA data plane by
# test_xla_matrix[2] (the VERDICT criterion) — so the end-to-end
# --tpu CLI smoke rides the slow tier with the example-script smokes.
def test_horovodrun_tpu_launches_xla_plane(capfd):
    """--tpu end to end on the virtual CPU mesh: the chip-carve env
    contract reaches every slot, hvd.init() brings up jax.distributed
    through the launcher KV, and the eager XLA data plane runs a real
    cross-process allreduce (one device per process)."""
    env = dict(_WORKER_ENV)
    # One CPU device per process: the conftest's 8-virtual-device
    # XLA_FLAGS would break the one-chip-per-process model.
    env["XLA_FLAGS"] = ""
    run_command(
        [sys.executable, "-c", _TPU_SNIPPET.format(root=ROOT)],
        np=4, env=env, start_timeout=120, tpu=True)
    out = capfd.readouterr().out
    for r in range(4):
        assert f"TPU_OK {r}/4" in out


# ---------------------------------------------------------------------------
# mpirun passthrough (--mpi)
# ---------------------------------------------------------------------------

_STUB_MPIRUN = """#!{python}
import os, subprocess, sys
args = sys.argv[1:]
if "--version" in args:
    print("mpirun (Open MPI) 4.1.5")
    sys.exit(0)
np = None
cmd = None
i = 0
while i < len(args):
    a = args[i]
    if a == "-np":
        np = int(args[i + 1]); i += 2
    elif a in ("-H", "-mca", "-map-by", "-bind-to", "-x"):
        i += 2
    elif a in ("--allow-run-as-root", "--tag-output"):
        i += 1
    else:
        cmd = args[i:]
        break
procs = []
for r in range(np):
    env = dict(os.environ)
    env.update({{"OMPI_COMM_WORLD_RANK": str(r),
                 "OMPI_COMM_WORLD_SIZE": str(np),
                 "OMPI_COMM_WORLD_LOCAL_RANK": str(r),
                 "OMPI_COMM_WORLD_LOCAL_SIZE": str(np)}})
    procs.append(subprocess.Popen(cmd, env=env))
sys.exit(max(p.wait() for p in procs))
"""


@pytest.fixture()
def stub_mpirun(tmp_path, monkeypatch):
    """A fake Open MPI mpirun on PATH: answers --version and spawns -np
    local ranks with the OMPI_COMM_WORLD_* identity contract."""
    path = tmp_path / "mpirun"
    path.write_text(_STUB_MPIRUN.format(python=sys.executable))
    path.chmod(0o755)
    monkeypatch.setenv("PATH", f"{tmp_path}{os.pathsep}{os.environ['PATH']}")
    return str(path)


def test_detect_mpi_implementation(stub_mpirun):
    from horovod_tpu.runner.mpi_run import detect_mpi_implementation

    assert detect_mpi_implementation() == "openmpi"
    assert detect_mpi_implementation(mpirun="/nonexistent/mpirun") is None


def test_build_mpi_command_flags():
    from horovod_tpu.runner.mpi_run import build_mpi_command

    env = {"HOROVOD_RENDEZVOUS_ADDR": "h:1", "PYTHONPATH": "/x",
           "TPU_PROCESS_BOUNDS": "2,2,1", "HOME": "/root"}
    cmd = build_mpi_command(np=4, impl="openmpi", env=env,
                            command=["python", "t.py"], hosts="h1:2,h2:2",
                            ssh_port=2222)
    assert cmd[0] == "mpirun" and cmd[-2:] == ["python", "t.py"]
    assert "-H" in cmd and cmd[cmd.index("-H") + 1] == "h1:2,h2:2"
    # HOROVOD_*/TPU_*/PYTHONPATH forwarded via -x; HOME is not
    xs = [cmd[i + 1] for i, a in enumerate(cmd) if a == "-x"]
    assert set(xs) == {"HOROVOD_RENDEZVOUS_ADDR", "PYTHONPATH",
                       "TPU_PROCESS_BOUNDS"}
    assert cmd[cmd.index("-mca") + 1] == "plm_rsh_args"

    # Hydra family forwards by -genvlist and strips slot counts
    cmd = build_mpi_command(np=2, impl="mpich", env=env,
                            command=["python", "t.py"], hosts="h1:2,h2:2")
    assert cmd[cmd.index("-hosts") + 1] == "h1,h2"
    gl = cmd[cmd.index("-genvlist") + 1].split(",")
    assert "HOROVOD_RENDEZVOUS_ADDR" in gl and "HOME" not in gl


_MPI_SNIPPET = """
import os, sys
sys.path.insert(0, {root!r})
assert "HOROVOD_RANK" not in os.environ   # identity comes from MPI
import numpy as np
import horovod_tpu as hvd
hvd.init()
assert hvd.rank() == int(os.environ["OMPI_COMM_WORLD_RANK"])
assert hvd.size() == int(os.environ["OMPI_COMM_WORLD_SIZE"])
out = hvd.allreduce(np.full(3, float(hvd.rank() + 1), np.float32),
                    name="m", op=hvd.Sum)
assert out[0] == sum(range(1, hvd.size() + 1)), out
print(f"MPI_OK {{hvd.rank()}}/{{hvd.size()}}", flush=True)
hvd.shutdown()
"""


def test_horovodrun_mpi_end_to_end(stub_mpirun, capfd):
    """--mpi end to end: one mpirun invocation, ranks from
    OMPI_COMM_WORLD_*, controller discovered through the launcher KV."""
    from horovod_tpu.runner.launch import main

    env_backup = {k: os.environ.pop(k) for k in list(os.environ)
                  if k.startswith("HOROVOD_")}
    try:
        for k, v in _WORKER_ENV.items():
            os.environ[k] = v
        rc = main(["--mpi", "-np", "2", "--",
                   sys.executable, "-c", _MPI_SNIPPET.format(root=ROOT)])
    finally:
        for k in list(os.environ):
            if k.startswith("HOROVOD_"):
                os.environ.pop(k)
        os.environ.update(env_backup)
    assert rc == 0
    out = capfd.readouterr().out
    for r in range(2):
        assert f"MPI_OK {r}/2" in out


def test_horovodrun_mpi_rejects_tpu_and_elastic(stub_mpirun, capfd):
    from horovod_tpu.runner.launch import main

    assert main(["--mpi", "--tpu", "-np", "4", "--", "python", "x.py"]) == 2
    assert "chip carve" in capfd.readouterr().err
    assert main(["--mpi", "-np", "2", "--host-discovery-script", "d.sh",
                 "--", "python", "x.py"]) == 2
    assert "elastic" in capfd.readouterr().err


def test_horovodrun_mpi_missing_mpirun(capfd, monkeypatch, tmp_path):
    from horovod_tpu.runner.launch import main

    monkeypatch.setenv("PATH", str(tmp_path))  # no mpirun anywhere
    rc = main(["--mpi", "-np", "2", "--", "python", "x.py"])
    assert rc == 2
    assert "could not find a working mpirun" in capfd.readouterr().err


# ---------------------------------------------------------------------------
# ssh preflight (reference runner/launch.py:575-595 + util/cache.py)
# ---------------------------------------------------------------------------

_STUB_SSH = """#!{python}
import sys
host = next(a for a in sys.argv[1:]
            if not a.startswith("-") and a != "true"
            and not a.startswith("StrictHostKeyChecking")
            and not a.startswith("BatchMode")
            and not a.startswith("ConnectTimeout"))
# O_APPEND: concurrent probe processes must not clobber each other.
with open({log!r}, "a") as f:
    f.write(host + chr(10))
if host.startswith("bad"):
    print("ssh: Could not resolve hostname " + host, file=sys.stderr)
    sys.exit(255)
sys.exit(0)
"""


@pytest.fixture()
def stub_ssh(tmp_path, monkeypatch):
    """A fake ssh on PATH that logs probed hosts and fails for any
    hostname starting with 'bad'."""
    log = tmp_path / "ssh.log"
    path = tmp_path / "ssh"
    path.write_text(_STUB_SSH.format(python=sys.executable, log=str(log)))
    path.chmod(0o755)
    monkeypatch.setenv("PATH", f"{tmp_path}{os.pathsep}{os.environ['PATH']}")
    return log


def test_preflight_ssh_aggregates_failures(stub_ssh, tmp_path):
    """One bad host in a 4-host spec -> ONE diagnostic naming exactly
    the unreachable host, before anything spawns."""
    from horovod_tpu.runner.launch import preflight_ssh

    cache = str(tmp_path / "cache.json")
    with pytest.raises(RuntimeError) as ei:
        preflight_ssh(["h1", "h2", "badhost", "h3"], cache_file=cache)
    msg = str(ei.value)
    assert "1 of 4" in msg and "badhost" in msg
    assert "Could not resolve hostname" in msg
    assert "no workers were started" in msg
    # All four hosts were probed concurrently in the one batch.
    assert sorted(stub_ssh.read_text().split()) == ["badhost", "h1",
                                                    "h2", "h3"]


def test_preflight_ssh_caches_successes(stub_ssh, tmp_path):
    from horovod_tpu.runner.launch import preflight_ssh

    cache = str(tmp_path / "cache.json")
    preflight_ssh(["h1", "h2"], cache_file=cache)
    assert sorted(stub_ssh.read_text().split()) == ["h1", "h2"]
    # Second launch: both hosts cached -> zero new probes.
    preflight_ssh(["h1", "h2"], cache_file=cache)
    assert sorted(stub_ssh.read_text().split()) == ["h1", "h2"]
    # A new host probes alone; cached ones stay skipped.
    preflight_ssh(["h1", "h3"], cache_file=cache)
    assert sorted(stub_ssh.read_text().split()) == ["h1", "h2", "h3"]


def test_launch_static_preflights_before_spawn(stub_ssh, tmp_path,
                                               monkeypatch):
    """launch_static with an unreachable remote host fails with the
    aggregated preflight error and never spawns a worker."""
    from horovod_tpu.runner.launch import LaunchSettings, launch_static

    monkeypatch.setenv("HOME", str(tmp_path))  # isolate the real cache
    settings = LaunchSettings(
        np=4, command=[sys.executable, "-c", "raise SystemExit(7)"],
        hosts="badhost1:2,badhost2:2", start_timeout=10)
    with pytest.raises(RuntimeError, match="2 of 2"):
        launch_static(settings)
    # Only the probes ran — the SystemExit(7) command never did (the
    # stub logs every ssh invocation; two probe lines, no exec lines).
    assert sorted(stub_ssh.read_text().split()) == ["badhost1",
                                                    "badhost2"]


# ---------------------------------------------------------------------------
# jsrun passthrough (reference runner/js_run.py tier)
# ---------------------------------------------------------------------------

_STUB_JSRUN = """#!{python}
import os, subprocess, sys
args = sys.argv[1:]
erf = None; smpiargs = None; envs = []; cmd = None
i = 0
while i < len(args):
    a = args[i]
    if a == "--erf_input":
        erf = args[i + 1]; i += 2
    elif a == "--smpiargs":
        smpiargs = args[i + 1]; i += 2
    elif a == "-E":
        envs.append(args[i + 1]); i += 2
    else:
        cmd = args[i:]
        break
assert erf and cmd, (erf, cmd)
ranks = []
for line in open(erf):
    line = line.strip()
    if line.startswith("rank:"):
        # rank: N: ... hostname, cpu range, gpu, mem (ERF line)
        n = int(line.split(":")[1].strip())
        host = line.split("hostname:")[1].split(";")[0].strip()
        ranks.append((n, host))
procs = []
for n, host in sorted(ranks):
    env = dict(os.environ)
    for kv in envs:
        # name-only -E: jsrun forwards the value from its own env
        assert "=" not in kv, "token must not ride the argv: " + kv
        assert kv in os.environ, "forwarded var missing from env: " + kv
    local = sum(1 for m, h in ranks if h == host and m < n)
    lsize = sum(1 for m, h in ranks if h == host)
    env.update({{"OMPI_COMM_WORLD_RANK": str(n),
                 "OMPI_COMM_WORLD_SIZE": str(len(ranks)),
                 "OMPI_COMM_WORLD_LOCAL_RANK": str(local),
                 "OMPI_COMM_WORLD_LOCAL_SIZE": str(lsize)}})
    procs.append(subprocess.Popen(cmd, env=env))
sys.exit(max(p.wait() for p in procs))
"""


@pytest.fixture()
def stub_jsrun(tmp_path, monkeypatch):
    """A fake jsrun on PATH: parses --erf_input/--smpiargs/-E and
    spawns one local process per ERF rank with the OMPI_COMM_WORLD_*
    identity contract (Spectrum MPI is OpenMPI-derived)."""
    path = tmp_path / "jsrun"
    path.write_text(_STUB_JSRUN.format(python=sys.executable))
    path.chmod(0o755)
    monkeypatch.setenv("PATH", f"{tmp_path}{os.pathsep}{os.environ['PATH']}")
    return str(path)


def test_jsrun_rankfile_layout(tmp_path, monkeypatch):
    from horovod_tpu.runner.js_run import generate_jsrun_rankfile

    monkeypatch.setenv("HOROVOD_JSRUN_CORES_PER_HOST", "8")
    rf = str(tmp_path / "r.erf")
    generate_jsrun_rankfile([HostInfo("h1", 2), HostInfo("h2", 2)], 3, rf)
    text = open(rf).read()
    assert "overlapping_rs: allow" in text
    assert "cpu_index_using: logical" in text
    # 3 of the 4 slots used; node-major rank order; even core split.
    assert "rank: 0: { hostname: h1; cpu: {0-3}" in text
    assert "rank: 1: { hostname: h1; cpu: {4-7}" in text
    assert "rank: 2: { hostname: h2; cpu: {0-3}" in text
    assert "rank: 3" not in text

    with pytest.raises(ValueError, match="2 slots < -np 4"):
        generate_jsrun_rankfile([HostInfo("h1", 2)], 4, rf)

    # Oversubscription (slots > cores) wraps cpu indices instead of
    # emitting cores the host doesn't have.
    monkeypatch.setenv("HOROVOD_JSRUN_CORES_PER_HOST", "2")
    generate_jsrun_rankfile([HostInfo("h1", 4)], 4, rf)
    text = open(rf).read()
    assert "rank: 2: { hostname: h1; cpu: {0-0}" in text
    assert "cpu: {2-" not in text and "cpu: {3-" not in text


def test_horovodrun_jsrun_end_to_end(stub_jsrun, capfd):
    """--jsrun end to end: one jsrun invocation, ERF placement, ranks
    from OMPI_COMM_WORLD_*, controller discovered via the launcher
    KV (mirrors test_horovodrun_mpi_end_to_end)."""
    from horovod_tpu.runner.launch import main

    env_backup = {k: os.environ.pop(k) for k in list(os.environ)
                  if k.startswith("HOROVOD_")}
    try:
        for k, v in _WORKER_ENV.items():
            os.environ[k] = v
        rc = main(["--jsrun", "-np", "2", "--",
                   sys.executable, "-c", _MPI_SNIPPET.format(root=ROOT)])
    finally:
        for k in list(os.environ):
            if k.startswith("HOROVOD_"):
                os.environ.pop(k)
        os.environ.update(env_backup)
    assert rc == 0
    out = capfd.readouterr().out
    for r in range(2):
        assert f"MPI_OK {r}/2" in out


def test_horovodrun_jsrun_autoselected_under_lsf(stub_jsrun, capfd,
                                                 monkeypatch):
    """Inside an LSF allocation with jsrun on PATH and no explicit
    launcher flag, horovodrun launches through jsrun (the reference's
    LSF default)."""
    from horovod_tpu.runner.launch import main

    env_backup = {k: os.environ.pop(k) for k in list(os.environ)
                  if k.startswith("HOROVOD_")}
    monkeypatch.setenv("LSB_JOBID", "123")
    monkeypatch.setenv("LSB_MCPU_HOSTS", "localhost 2")
    try:
        for k, v in _WORKER_ENV.items():
            os.environ[k] = v
        rc = main(["-np", "2", "--",
                   sys.executable, "-c", _MPI_SNIPPET.format(root=ROOT)])
    finally:
        for k in list(os.environ):
            if k.startswith("HOROVOD_"):
                os.environ.pop(k)
        os.environ.update(env_backup)
    assert rc == 0
    out = capfd.readouterr().out
    assert "MPI_OK 0/2" in out and "MPI_OK 1/2" in out


def test_horovodrun_jsrun_rejects_tpu_and_elastic(stub_jsrun, capfd):
    from horovod_tpu.runner.launch import main

    assert main(["--jsrun", "--tpu", "-np", "4", "--", "python",
                 "x.py"]) == 2
    assert "chip carve" in capfd.readouterr().err
    assert main(["--jsrun", "-np", "2", "--host-discovery-script", "d.sh",
                 "--", "python", "x.py"]) == 2
    assert "elastic" in capfd.readouterr().err


def test_horovodrun_jsrun_missing(capfd, monkeypatch, tmp_path):
    from horovod_tpu.runner.launch import main

    monkeypatch.setenv("PATH", str(tmp_path))  # no jsrun anywhere
    rc = main(["--jsrun", "-np", "2", "--", "python", "x.py"])
    assert rc == 2
    assert "could not find jsrun" in capfd.readouterr().err


# ---------------------------------------------------------------------------
# Scheduler allocation detection (reference runner/util/lsf.py role)
# ---------------------------------------------------------------------------

def test_lsf_hosts(monkeypatch):
    from horovod_tpu.runner.schedulers import detect_scheduler_hosts

    monkeypatch.setenv("LSB_JOBID", "123")
    # The 1-slot launch node LSF lists first is excluded.
    monkeypatch.setenv("LSB_MCPU_HOSTS", "batch 1 n01 4 n02 4")
    assert detect_scheduler_hosts() == [
        HostInfo("n01", 4), HostInfo("n02", 4)]
    monkeypatch.delenv("LSB_MCPU_HOSTS")
    monkeypatch.setenv("LSB_HOSTS", "n01 n01 n02")
    assert detect_scheduler_hosts() == [HostInfo("n01", 2),
                                        HostInfo("n02", 1)]


def test_slurm_hosts(monkeypatch):
    from horovod_tpu.runner.schedulers import (
        detect_scheduler_hosts, expand_slurm_nodelist,
        expand_slurm_tasks_per_node)

    assert expand_slurm_nodelist("n[01-03,07],gpu1") == [
        "n01", "n02", "n03", "n07", "gpu1"]
    # multi-dimensional names expand every bracket group
    assert expand_slurm_nodelist("r[1-2]n[01-02]") == [
        "r1n01", "r1n02", "r2n01", "r2n02"]
    assert expand_slurm_tasks_per_node("2(x3),1", 4) == [2, 2, 2, 1]
    assert expand_slurm_tasks_per_node("4", 3) == [4, 4, 4]

    monkeypatch.setenv("SLURM_JOB_NODELIST", "c[1-2]")
    monkeypatch.setenv("SLURM_TASKS_PER_NODE", "8(x2)")
    assert detect_scheduler_hosts() == [HostInfo("c1", 8),
                                        HostInfo("c2", 8)]


def test_resolve_hosts_uses_scheduler(monkeypatch):
    from horovod_tpu.runner.launch import LaunchSettings, _resolve_hosts

    monkeypatch.setenv("SLURM_JOB_NODELIST", "nd[1-2]")
    monkeypatch.setenv("SLURM_TASKS_PER_NODE", "2(x2)")
    hosts = _resolve_hosts(LaunchSettings(np=4, command=["x"]))
    assert hosts == [HostInfo("nd1", 2), HostInfo("nd2", 2)]
    # Explicit -H wins over the scheduler env.
    hosts = _resolve_hosts(LaunchSettings(np=2, command=["x"],
                                          hosts="h9:2"))
    assert hosts == [HostInfo("h9", 2)]


def test_pbs_hosts(monkeypatch, tmp_path):
    from horovod_tpu.runner.schedulers import detect_scheduler_hosts

    nf = tmp_path / "nodes"
    nf.write_text("n01\nn01\nn02\n")
    monkeypatch.setenv("PBS_NODEFILE", str(nf))
    assert detect_scheduler_hosts() == [HostInfo("n01", 2),
                                        HostInfo("n02", 1)]


def test_lsf_uniform_single_slot_hosts_kept(monkeypatch):
    from horovod_tpu.runner.schedulers import detect_scheduler_hosts

    monkeypatch.setenv("LSB_JOBID", "1")
    # span[ptile=1]: every host legitimately has one slot — keep all.
    monkeypatch.setenv("LSB_MCPU_HOSTS", "h1 1 h2 1")
    assert detect_scheduler_hosts() == [HostInfo("h1", 1),
                                        HostInfo("h2", 1)]


def test_resolve_hosts_underallocation_falls_back(monkeypatch):
    from horovod_tpu.runner.launch import LaunchSettings, _resolve_hosts

    monkeypatch.setenv("SLURM_JOB_NODELIST", "n1")
    monkeypatch.setenv("SLURM_TASKS_PER_NODE", "1")
    hosts = _resolve_hosts(LaunchSettings(np=8, command=["x"]))
    assert hosts == [HostInfo("localhost", 8)]


def test_hydra_uniform_slots_get_ppn():
    from horovod_tpu.runner.mpi_run import build_mpi_command

    cmd = build_mpi_command(np=4, impl="intel", env={},
                            command=["python", "t.py"], hosts="h1:2,h2:2")
    assert cmd[cmd.index("-ppn") + 1] == "2"
    with pytest.raises(ValueError, match="uniform"):
        build_mpi_command(np=4, impl="mpich", env={},
                          command=["python", "t.py"], hosts="h1:3,h2:1")
