"""Sanitizer tier: rebuild the native core under tsan/asan and drive
the real np=2/np=4 multiprocess scenarios against the instrumented
library. Any sanitizer report fails the test (workers exit with the
sanitizer's exitcode AND the report file is printed), so a data race or
heap error in the threaded data planes is a red build, not a reviewer
catch. Recipes + caveats: docs/development.md#sanitizers.

Everything here is slow-tier (-m slow): each scenario pays the full
native rebuild amortized once per variant plus the sanitizer's runtime
slowdown. Measured wall time on the 2-core dev box (pytest totals,
INCLUDING the one-off per-variant rebuild make amortizes away on
reruns):

    tsan half  (5 scenarios):          ~60s
    asan+ubsan half (5 + 1 scenarios): ~150s

Wiring that is easy to get wrong (and why it is the way it is):
  * HOROVOD_NATIVE_LIB points the ctypes loader at the suffixed .so
    (basics.py override) — python itself stays uninstrumented.
  * The sanitizer RUNTIME must be LD_PRELOADed: the instrumented core
    is dlopen'd into a plain python, and both tsan and asan require
    their runtime to be loaded before anything else allocates.
  * OPENBLAS_NUM_THREADS=1: numpy's import brings up the OpenBLAS
    thread pool, and a later fork (numpy.testing's SVE probe spawns a
    subprocess) deadlocks inside the tsan runtime when other threads
    exist. _mp_worker.py additionally imports numpy.testing before
    hvd.init() so the fork also cannot land after OUR threads start.
  * detect_leaks=0 for asan: CPython intentionally leaks at exit;
    LSan's report would drown any real finding.
Suppressions policy: every scenario must run with ZERO unsuppressed
reports, and scenarios that only exercise our own code run with no
suppressions at all. The single checked-in file
(tsan_jax_suppressions.txt, justification comment per entry) exists
for the one scenario that loads jax in the sanitized process —
jaxlib's uninstrumented runtimes synchronize with atomics tsan cannot
see, and it pairs their intercepted allocations into phantom races.
"""

import glob
import os
import subprocess
import sys

import pytest

from test_eager_multiprocess import _free_port

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "native")
WORKER = os.path.join(ROOT, "tests", "_mp_worker.py")

# The concurrency hot spots this tier exists for (ISSUE 6): the shm
# fused segment pipeline (+ WorkerPool via REDUCE_THREADS=4), the TCP
# ring with every wire codec live, the metrics registry under fused
# load, and an injected stall (background inspector + accessor ABI).
# Envs mirror the tier-1 launches in test_eager_multiprocess/
# test_metrics so a sanitizer run covers the same code paths.
SCENARIOS = [
    ("fused_bitwise", 2, {"HOROVOD_SHM_SEGMENT_BYTES": "65536",
                          "HOROVOD_REDUCE_THREADS": "4"}),
    ("wire_ring", 4, {"HOROVOD_SHM_DISABLE": "1"}),
    ("metrics", 2, {}),
    ("stall", 2, {"HOROVOD_STALL_CHECK_TIME_SECONDS": "0.5"}),
    # Flight recorder (ISSUE 20): Python writer threads race a
    # snapshot reader and a file dumper over the seqlock-lite ring
    # while allreduce traffic feeds it natively — the claim/publish
    # slot protocol and the reader's skip-on-mismatch run under the
    # sanitizer.
    ("flight_churn", 2, {}),
    # Schedule interpreter (ISSUE 7): per-step receiver-thread waves +
    # the encoded-chunk cache, across hd/striped/doubling and every
    # codec, at the ragged np that exercises fold/unfold.
    ("algo_parity", 3, {"HOROVOD_SHM_DISABLE": "1"}),
    # Vectored transport (ISSUE 10): SendV/RecvV windows + the coalesced
    # per-peer span tables + the zero-staging allgather ring, with the
    # buffer pool's first-touch ParallelFor racing the receiver threads'
    # writes — the concurrency this tier exists to prove clean.
    ("transport_digest", 2, {"HOROVOD_SHM_DISABLE": "1"}),
    # Steady-lock churn (ISSUE 15): np=4 loop that locks, a rank
    # injects a shape change to force the consensus unlock, re-locks —
    # three rounds, so the detector/matcher/token rounds and the
    # engaged-flag reads from Python threads run under the sanitizer.
    ("lock_churn", 4, {}),
    # Membership plane (ISSUE 16): join-flush + dead-peer advances and
    # the registered fences racing a Python thread that hammers
    # membership()/metrics()/blacklist while the ring is locked — the
    # plane's two-lock discipline (advance_mu_ ordering fences, mu_
    # guarding state) and the metrics-gauge fill run under the
    # sanitizer.
    ("membership_churn", 4, {}),
    # Direct migration plane (ISSUE 19): the native alpha-beta cost
    # twin cross-checked term-for-term against the Python planner over
    # an injected topology model, then an in-thread serving fleet
    # (native sendv/recvv transport + bf16 wire codec) runs TWO
    # overlapping migrating drains plus one injected worker death —
    # peer bulk streams racing step RPCs and the dead conn's teardown.
    # The only scenario that loads jax in the sanitized process, so it
    # carries the jaxlib false-positive hygiene: the checked-in
    # called_from_lib suppressions (see tsan_jax_suppressions.txt for
    # the per-entry why), plus report_mutex_bugs=0/detect_deadlocks=0 —
    # XLA/MLIR destroy mutexes tsan never saw locked (their sync is
    # uninstrumented atomics), and the resulting phantom
    # "unlock of an unlocked mutex"/lock-order reports span a fresh
    # jaxlib .so per run. The RACE detector — the checker this tier
    # exists for — stays fully on for our instrumented core.
    ("migration_plane", 2, {
        "JAX_PLATFORMS": "cpu",
        "TSAN_OPTIONS_EXTRA":
            "report_mutex_bugs=0 detect_deadlocks=0 suppressions="
            + os.path.join(ROOT, "tests", "tsan_jax_suppressions.txt"),
    }),
]

_RUNTIME_LIB = {"tsan": "libtsan.so", "asan": "libasan.so",
                "ubsan": "libubsan.so"}


def _runtime_path(san: str) -> str:
    out = subprocess.run(["g++", "-print-file-name=" + _RUNTIME_LIB[san]],
                         capture_output=True, text=True).stdout.strip()
    if not os.path.isabs(out):
        pytest.skip(f"{_RUNTIME_LIB[san]} not installed")
    return out


_built = set()


def _build_variant(san: str) -> str:
    """make -C native san-<san> (idempotent; make skips when current)."""
    if san not in _built:
        r = subprocess.run(["make", "-C", NATIVE, f"san-{san}", "-j2"],
                           capture_output=True, text=True)
        assert r.returncode == 0, f"SAN={san} build failed:\n{r.stdout[-4000:]}\n{r.stderr[-4000:]}"
        _built.add(san)
    lib = os.path.join(NATIVE, f"libhorovod_tpu_core.{san}.so")
    assert os.path.exists(lib)
    return lib


def run_san_job(san, scenario, np_, extra_env, tmp_path, timeout=420,
                expected_rc=None):
    lib = _build_variant(san)
    # libstdc++ rides the preload chain AFTER the sanitizer runtime:
    # the runtime resolves real___cxa_throw via RTLD_NEXT at init, and
    # with a plain python main (no libstdc++ in its link map yet) the
    # lookup fails — the first C++ `throw` out of a dlopen'd extension
    # then aborts the rank with "CHECK failed: real___cxa_throw != 0"
    # (jaxlib's MLIR bindings throw during jit lowering, which is how
    # migration_plane found it). Preloading it puts the symbol in the
    # chain before any extension loads; scenarios that never throw are
    # unaffected (same toolchain libstdc++ the native build links).
    stdcxx = subprocess.run(["g++", "-print-file-name=libstdc++.so"],
                            capture_output=True, text=True).stdout.strip()
    preload = _runtime_path(san) + (":" + stdcxx
                                    if os.path.isabs(stdcxx) else "")
    logdir = str(tmp_path / f"{san}-{scenario}")
    os.makedirs(logdir, exist_ok=True)
    report_stem = os.path.join(logdir, "report")
    port = _free_port()
    procs = []
    for r in range(np_):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(r), "HOROVOD_SIZE": str(np_),
            "HOROVOD_LOCAL_RANK": str(r), "HOROVOD_LOCAL_SIZE": str(np_),
            "HOROVOD_CROSS_RANK": "0", "HOROVOD_CROSS_SIZE": "1",
            "HOROVOD_CONTROLLER_ADDR": f"127.0.0.1:{port}",
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_NATIVE_LIB": lib,
            "LD_PRELOAD": preload,
            "OPENBLAS_NUM_THREADS": "1",
            # exitcode=66: a report also fails the rank's exit status,
            # so a race cannot hide behind an otherwise-green scenario.
            "TSAN_OPTIONS": f"log_path={report_stem} exitcode=66 "
                            "second_deadlock_stack=1 halt_on_error=0",
            "ASAN_OPTIONS": f"log_path={report_stem} exitcode=66 "
                            "detect_leaks=0",
            "UBSAN_OPTIONS": f"log_path={report_stem} print_stacktrace=1",
        })
        # A scenario may APPEND to a sanitizer's options (flags,
        # suppressions) without clobbering the log_path/exitcode
        # defaults computed above: "<NAME>_EXTRA" keys concatenate.
        for k, v in extra_env.items():
            if k.endswith("_OPTIONS_EXTRA"):
                base = k[:-len("_EXTRA")]
                env[base] = env.get(base, "") + " " + v
            else:
                env[k] = v
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, scenario], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs, fails = [], []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(
                f"[{san}] rank {r} timed out in {scenario} "
                f"(reports so far: {glob.glob(report_stem + '*')})")
        outs.append(out)
        if p.returncode != (expected_rc or {}).get(r, 0):
            fails.append((r, p.returncode, out))
    reports = sorted(glob.glob(report_stem + "*"))
    if reports or fails:
        msg = [f"[{san}] {scenario}: "
               f"{len(reports)} sanitizer report(s), "
               f"{len(fails)} failed rank(s)"]
        for fn in reports:
            msg.append(f"---- {fn}\n{open(fn).read()[:8000]}")
        for r, rc, out in fails:
            msg.append(f"---- rank {r} rc={rc}\n{out[-3000:]}")
        raise AssertionError("\n".join(msg))
    return outs


@pytest.mark.parametrize("san", ["tsan", "asan", "ubsan"])
def test_variant_is_actually_instrumented(san):
    """Anti-vacuous-green guard #1: the suffixed .so must really link
    the sanitizer runtime (DT_NEEDED). A Makefile refactor that drops
    -fsanitize from the SAN branch would otherwise turn every test in
    this file into a no-op that passes with zero reports forever."""
    lib = _build_variant(san)
    dyn = subprocess.run(["readelf", "-d", lib], capture_output=True,
                         text=True).stdout
    assert f"lib{san}" in dyn, (
        f"{lib} does not DT_NEED lib{san} — SAN={san} built "
        f"uninstrumented?\n{dyn[:2000]}")


def test_harness_catches_a_planted_race(tmp_path):
    """Anti-vacuous-green guard #2: compile a deliberately racy .so
    with the same tsan flags, dlopen it from a preloaded python the
    way run_san_job does, and require the report + exitcode=66 to
    actually surface. This pins the whole detection chain (preload
    order, TSAN_OPTIONS parsing, log_path capture) — if any link
    breaks, this test fails before a real race can slip through."""
    _runtime_path("tsan")
    src = tmp_path / "canary.cc"
    src.write_text(
        "#include <thread>\n"
        "long g = 0;\n"
        "extern \"C\" void race() {\n"
        "  std::thread t([]{ for (int i=0;i<100000;++i) g++; });\n"
        "  for (int i=0;i<100000;++i) g++;\n"
        "  t.join();\n"
        "}\n")
    so = str(tmp_path / "libcanary.so")
    r = subprocess.run(["g++", "-std=c++17", "-fPIC", "-shared",
                        "-fsanitize=thread", "-O1", str(src), "-o", so],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    report = str(tmp_path / "report")
    env = dict(os.environ,
               LD_PRELOAD=_runtime_path("tsan"),
               TSAN_OPTIONS=f"log_path={report} exitcode=66")
    r = subprocess.run(
        [sys.executable, "-c",
         f"import ctypes; ctypes.CDLL({so!r}).race()"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 66, (r.returncode, r.stdout, r.stderr)
    reports = glob.glob(report + "*")
    assert reports and "data race" in open(reports[0]).read(), reports


@pytest.mark.parametrize("scenario,np_,extra",
                         SCENARIOS, ids=[s[0] for s in SCENARIOS])
@pytest.mark.parametrize("san", ["tsan", "asan"])
def test_scenario_clean_under_sanitizer(san, scenario, np_, extra, tmp_path):
    outs = run_san_job(san, scenario, np_, extra, tmp_path)
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out, f"[{san}] {scenario} rank {r}:\n{out}"


@pytest.mark.parametrize("plane", [{}, {"HOROVOD_SHM_DISABLE": "1"}],
                         ids=["cells", "inline"])
@pytest.mark.parametrize("san", ["tsan", "asan"])
def test_persistent_lock_churn_clean_under_sanitizer(san, plane, tmp_path):
    """Persistent locked data plane chaos (ISSUE 17): lock ->
    persistent firings (shm consensus cells / inline token piggyback)
    -> forced unlock -> re-lock -> a SEEDED victim SIGKILLs mid-slot.
    The seqlock cell publish/peek, the plan compile racing the metrics
    snapshot's gauge read, and the teardown paths (liveness tick /
    posted-recv EOF) must all be zero-report; survivors exit 0 and the
    victim dies by exactly the planted signal. Seeding mirrors the
    ISSUE 16 chaos harness: one env seed, every rank and this test
    derive the same schedule."""
    import signal

    import numpy as np

    seed = 17
    victim = int(np.random.RandomState(seed).randint(0, 4))
    extra = dict(plane)
    extra["HOROVOD_CHAOS_SEED"] = str(seed)
    outs = run_san_job(san, "persistent_lock_churn", 4, extra, tmp_path,
                       expected_rc={victim: -signal.SIGKILL})
    for r, out in enumerate(outs):
        if r == victim:
            assert f"VICTIM rank={r}" in out, f"[{san}] rank {r}:\n{out}"
        else:
            assert f"OK rank={r}" in out, f"[{san}] rank {r}:\n{out}"


@pytest.mark.parametrize("scenario,np_,extra", [
    # The ISSUE 13 planes, tsan-only (their hazards are scheduling
    # races, not memory errors, and the asan half already runs long):
    # the startup probe's lockstep ping rounds + the on-demand re-probe
    # racing the live background cycle + measured selection reading the
    # model the API thread re-installs...
    ("topo_probe", 4, {"HOROVOD_TOPOLOGY_PROBE": "force",
                       "HOROVOD_SHM_DISABLE": "1"}),
    # ...and the synthesized np=4 tables: interleaved-hd/striped-3/
    # granularity-2 allreduce through ExecuteSchedule's receiver waves
    # plus allgather/reducescatter/alltoall through the new span
    # interpreter's helper threads.
    ("synth_live", 4, {"HOROVOD_SHM_DISABLE": "1",
                       "HOROVOD_COLLECTIVE_STRIPES": "3",
                       "HOROVOD_COLLECTIVE_GRANULARITY": "2",
                       "HOROVOD_HD_ORDER": "1"}),
    # The ISSUE 14 affinity rider: the fused segment pipeline with the
    # WorkerPool's 4 reducer threads AFFINITY-PINNED (forced explicitly
    # so a future default flip cannot silently drop the coverage) — the
    # pin runs at worker spawn concurrently with the pool's lock-free
    # part claiming and the pinned_ gauge read on the metrics path, the
    # scheduling hazards this tier exists to prove clean.
    ("fused_bitwise", 2, {"HOROVOD_SHM_SEGMENT_BYTES": "65536",
                          "HOROVOD_REDUCE_THREADS": "4",
                          "HOROVOD_REDUCE_THREAD_AFFINITY": "auto"}),
], ids=["topo_probe", "synth_live", "affinity_fused"])
def test_topology_planes_clean_under_tsan(scenario, np_, extra, tmp_path):
    outs = run_san_job("tsan", scenario, np_, extra, tmp_path)
    for r, out in enumerate(outs):
        assert f"OK rank={r}" in out, f"[tsan] {scenario} rank {r}:\n{out}"


def test_ubsan_variant_builds_and_loads(tmp_path):
    """ubsan is build+smoke only: its findings are deterministic (no
    scheduling dependence), so one scenario through the fused pipeline
    is enough to cover the arithmetic in the hot loops."""
    run_san_job("ubsan", "fused_bitwise", 2,
                {"HOROVOD_SHM_SEGMENT_BYTES": "65536",
                 "HOROVOD_REDUCE_THREADS": "4"}, tmp_path)
