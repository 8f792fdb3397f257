"""ctypes bridge to the native coordination core.

Rebuild of the reference's ``horovod/common/basics.py:33-288``
(``HorovodBasics``): loads the shared library, declares the C ABI
signatures, and exposes init/shutdown/rank/size plus the raw enqueue
surface consumed by :mod:`horovod_tpu.runtime`.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_LIB_CANDIDATES = [
    os.path.join(_REPO_ROOT, "native", "libhorovod_tpu_core.so"),
    os.path.join(os.path.dirname(__file__), "libhorovod_tpu_core.so"),
]

# C ABI op codes (native/include/hvd/message.h RequestType).
OP_ALLREDUCE = 0
OP_ALLGATHER = 1
OP_BROADCAST = 2
OP_ALLTOALL = 3
OP_JOIN = 4
OP_BARRIER = 5
OP_REDUCESCATTER = 6

EXEC_HOST = 0
EXEC_CALLBACK = 1

# Native wire/ABI version pins. These MUST match the constants in
# native/include/hvd/message.h (kAbiVersion / kWireVersion*) — the ABI
# is enforced at library load below, and tests/test_wire_abi.py greps
# the header so a native bump can't silently skew this shim even
# before a rebuild happens.
ABI_VERSION = 15
WIRE_VERSION_REQUEST_LIST = 3
WIRE_VERSION_RESPONSE_LIST = 7

# Metrics snapshot layout version (native/include/hvd/metrics.h
# kMetricsVersion): the packed int64 layout hvd_metrics_snapshot
# writes. Checked at library load AND against the header by
# tests/test_metrics_abi.py, the same two-sided pin as the ABI above.
METRICS_VERSION = 9

# Native WireCodec ids (native/include/hvd/codec.h); -1 = follow the
# job-wide HOROVOD_WIRE_COMPRESSION default.
WIRE_CODEC_DEFAULT = -1
WIRE_CODEC_NONE = 0
WIRE_CODEC_BF16 = 1
WIRE_CODEC_FP16 = 2
WIRE_CODEC_INT8 = 3

# Native CollectiveAlgo ids (native/include/hvd/schedule.h); 0 = follow
# the coordinator's selection table / HOROVOD_COLLECTIVE_ALGO. Name
# order mirrors kCollectiveAlgoNames.
COLLECTIVE_ALGOS = {
    "auto": 0,
    "ring": 1,
    "hd": 2,
    "striped": 3,
    "doubling": 4,
    "hier": 5,
}

# Native AlltoallAlgo ids (native/include/hvd/schedule.h); 0 = follow
# the measured pairwise-vs-bruck verdict / HOROVOD_ALLTOALL_ALGO.
# Name order mirrors kAlltoallAlgoNames.
ALLTOALL_ALGOS = {
    "auto": 0,
    "pairwise": 1,
    "bruck": 2,
}


# Native CollKind ids (native/include/hvd/schedule.h): the collective
# a chunk-op table expresses, for hvd_build_coll_schedule.
COLL_ALLREDUCE = 0
COLL_ALLGATHER = 1
COLL_REDUCESCATTER = 2
COLL_ALLTOALL = 3


def collective_algo_id(algorithm) -> int:
    """Map an ``algorithm=`` kwarg (name string, native id, or None) to
    the native CollectiveAlgo id."""
    if algorithm is None:
        return 0
    if isinstance(algorithm, str):
        try:
            return COLLECTIVE_ALGOS[algorithm]
        except KeyError:
            raise ValueError(
                f"unknown collective algorithm {algorithm!r}; want one of "
                f"{sorted(COLLECTIVE_ALGOS)}") from None
    a = int(algorithm)
    if not 0 <= a < len(COLLECTIVE_ALGOS):
        raise ValueError(f"collective algorithm id {a} out of range")
    return a

# numpy dtype -> native DataType id (native/include/hvd/common.h).
_DTYPE_MAP = {
    np.dtype(np.uint8): 0,
    np.dtype(np.int8): 1,
    np.dtype(np.uint16): 2,
    np.dtype(np.int16): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int64): 5,
    np.dtype(np.float16): 6,
    np.dtype(np.float32): 7,
    np.dtype(np.float64): 8,
    np.dtype(np.bool_): 9,
}
_BFLOAT16_ID = 10


def np_dtype(dt_id: int):
    """Inverse of :func:`dtype_id` (bfloat16 via ml_dtypes)."""
    if dt_id == _BFLOAT16_ID:
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    for dt, i in _DTYPE_MAP.items():
        if i == dt_id:
            return dt
    raise TypeError(f"unknown native dtype id {dt_id}")


def dtype_id(dtype) -> int:
    dtype = np.dtype(dtype) if not hasattr(dtype, "name") else dtype
    if getattr(dtype, "name", "") == "bfloat16":
        return _BFLOAT16_ID
    try:
        return _DTYPE_MAP[np.dtype(dtype)]
    except KeyError:
        raise TypeError(f"unsupported dtype for collective: {dtype}") from None


EXEC_CB_TYPE = ctypes.CFUNCTYPE(
    None, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
    ctypes.POINTER(ctypes.c_char_p), ctypes.c_int32,
    ctypes.POINTER(ctypes.c_int64), ctypes.c_int32, ctypes.c_int32,
    ctypes.c_int32)
ALLOC_CB_TYPE = ctypes.CFUNCTYPE(
    ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
    ctypes.c_int32)


def _build_native() -> None:
    # Serialize across processes: concurrently-launched ranks all try to
    # (re)build on import, and an unlocked parallel make could relink
    # the .so while a sibling rank is dlopen()ing it.
    import fcntl
    native_dir = os.path.join(_REPO_ROOT, "native")
    with open(os.path.join(native_dir, ".build.lock"), "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        subprocess.run(["make", "-C", native_dir, "-j"],
                       check=True, capture_output=True)


def load_library() -> ctypes.CDLL:
    # HOROVOD_NATIVE_LIB points the loader at an alternate build of the
    # core — the sanitizer variants (libhorovod_tpu_core.tsan.so, ...)
    # from `make -C native SAN=...` — so the exact same Python test
    # scenarios run against an instrumented library
    # (docs/development.md, tests/test_sanitizers.py). The override is
    # explicit opt-in: no rebuild is attempted (the harness that set it
    # owns the build), but the ABI pin below still applies, so a stale
    # instrumented .so cannot silently skew results.
    override = os.environ.get("HOROVOD_NATIVE_LIB")
    if override:
        if not os.path.exists(override):
            raise OSError(
                f"HOROVOD_NATIVE_LIB={override} does not exist; build it "
                "first (e.g. make -C native SAN=tsan)")
        return _declare_abi(ctypes.CDLL(override), override)
    # With the source tree present the core is built from it or not at
    # all: make is a no-op when the .so is current, and a failed build
    # raises — a .so left over from another state of the tree (the chip
    # tool copies the disk, stale binaries included) must never stand
    # in for sources that no longer compile.
    if os.path.exists(os.path.join(_REPO_ROOT, "native", "Makefile")):
        try:
            _build_native()
        except subprocess.CalledProcessError as e:
            raise OSError(
                "horovod_tpu: building the native core failed "
                f"(make -C native, rc={e.returncode}):\n"
                + e.stderr.decode(errors="replace")[-2000:]) from e
    path = next((p for p in _LIB_CANDIDATES if os.path.exists(p)), None)
    if path is None:
        raise OSError("horovod_tpu native core not found and no source tree "
                      "to build it from")
    return _declare_abi(ctypes.CDLL(path), path)


def _declare_abi(lib: ctypes.CDLL, path: str) -> ctypes.CDLL:
    """Declare the C ABI signatures and enforce the version pins on an
    already-dlopen'd core (shared between the default candidate search
    and the HOROVOD_NATIVE_LIB override path)."""
    try:
        got = lib.hvd_abi_version()
    except AttributeError:
        got = -1
    if got != ABI_VERSION:
        raise OSError(
            f"horovod_tpu native core at {path} has ABI version {got}, "
            f"expected {ABI_VERSION}; rebuild it (make -C native)")

    lib.hvd_init.restype = ctypes.c_int
    lib.hvd_init.argtypes = [ctypes.c_int] * 6
    lib.hvd_shutdown.restype = None
    for fn in ("hvd_initialized", "hvd_rank", "hvd_size", "hvd_local_rank",
               "hvd_local_size", "hvd_cross_rank", "hvd_cross_size",
               "hvd_is_homogeneous"):
        getattr(lib, fn).restype = ctypes.c_int
    lib.hvd_enqueue.restype = ctypes.c_int64
    lib.hvd_enqueue.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_double,
        ctypes.c_double, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.c_int, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int,
    ]
    lib.hvd_last_enqueue_error.restype = ctypes.c_char_p
    lib.hvd_join.restype = ctypes.c_int64
    lib.hvd_barrier.restype = ctypes.c_int64
    lib.hvd_poll.restype = ctypes.c_int
    lib.hvd_poll.argtypes = [ctypes.c_int64]
    lib.hvd_wait.restype = ctypes.c_int
    lib.hvd_wait.argtypes = [ctypes.c_int64, ctypes.c_int, ctypes.c_char_p,
                             ctypes.c_int]
    lib.hvd_release_handle.restype = None
    lib.hvd_release_handle.argtypes = [ctypes.c_int64]
    lib.hvd_get_recvsplits.restype = ctypes.c_int
    lib.hvd_get_recvsplits.argtypes = [ctypes.c_int64,
                                       ctypes.POINTER(ctypes.c_int64),
                                       ctypes.c_int]
    lib.hvd_exec_done.restype = None
    lib.hvd_exec_done.argtypes = [ctypes.c_int64, ctypes.c_int,
                                  ctypes.c_char_p]
    lib.hvd_set_exec_callback.restype = None
    lib.hvd_set_exec_callback.argtypes = [EXEC_CB_TYPE]
    lib.hvd_set_alloc_callback.restype = None
    lib.hvd_set_alloc_callback.argtypes = [ALLOC_CB_TYPE]
    # Returns 0 on success, -1 when the timeline file cannot be opened
    # (surfaced as a Python exception in runtime.start_timeline). A
    # second call on a running timeline restarts it onto the new path.
    lib.hvd_start_timeline.restype = ctypes.c_int
    lib.hvd_start_timeline.argtypes = [ctypes.c_char_p]
    lib.hvd_stop_timeline.restype = None
    lib.hvd_pending_count.restype = ctypes.c_int64
    # Metrics registry (docs/observability.md): versioned packed
    # snapshot + name/kind tables, consumed by horovod_tpu/metrics.py.
    lib.hvd_metrics_snapshot.restype = ctypes.c_int64
    lib.hvd_metrics_snapshot.argtypes = [ctypes.POINTER(ctypes.c_int64),
                                         ctypes.c_int64]
    for fn in ("hvd_metrics_version", "hvd_metrics_num_counters",
               "hvd_metrics_num_hists", "hvd_metrics_hist_buckets",
               "hvd_metrics_enabled"):
        getattr(lib, fn).restype = ctypes.c_int
    lib.hvd_metrics_counter_name.restype = ctypes.c_char_p
    lib.hvd_metrics_counter_name.argtypes = [ctypes.c_int]
    lib.hvd_metrics_counter_kind.restype = ctypes.c_int
    lib.hvd_metrics_counter_kind.argtypes = [ctypes.c_int]
    lib.hvd_metrics_hist_name.restype = ctypes.c_char_p
    lib.hvd_metrics_hist_name.argtypes = [ctypes.c_int]
    lib.hvd_metrics_reset.restype = None
    lib.hvd_metrics_set_enabled.restype = None
    lib.hvd_metrics_set_enabled.argtypes = [ctypes.c_int]
    lib.hvd_metrics_test_add.restype = None
    lib.hvd_metrics_test_add.argtypes = [ctypes.c_int, ctypes.c_int64]
    lib.hvd_metrics_test_observe.restype = None
    lib.hvd_metrics_test_observe.argtypes = [ctypes.c_int, ctypes.c_int64]
    # Stall findings beyond the log (hvd.stalled_tensors()): returns the
    # byte count needed including the NUL, copies at most len-1 bytes.
    lib.hvd_stalled_tensors.restype = ctypes.c_int
    lib.hvd_stalled_tensors.argtypes = [ctypes.c_char_p, ctypes.c_int]
    # Flight recorder (native/include/hvd/flight.h): always-on
    # control-plane event ring with postmortem dump. Snapshot follows
    # the stalled_tensors size-probe protocol.
    lib.hvd_flight_record.restype = None
    lib.hvd_flight_record.argtypes = [ctypes.c_int, ctypes.c_longlong,
                                      ctypes.c_longlong]
    lib.hvd_flight_snapshot.restype = ctypes.c_longlong
    lib.hvd_flight_snapshot.argtypes = [ctypes.c_char_p, ctypes.c_longlong]
    lib.hvd_flight_dump.restype = ctypes.c_int
    lib.hvd_flight_dump.argtypes = [ctypes.c_char_p]
    lib.hvd_flight_install.restype = ctypes.c_int
    lib.hvd_flight_install.argtypes = [ctypes.c_char_p]
    lib.hvd_flight_num_events.restype = ctypes.c_int
    lib.hvd_flight_event_name.restype = ctypes.c_char_p
    lib.hvd_flight_event_name.argtypes = [ctypes.c_int]
    lib.hvd_flight_count.restype = ctypes.c_longlong
    lib.hvd_flight_clear.restype = None
    lib.hvd_flight_set_enabled.restype = None
    lib.hvd_flight_set_enabled.argtypes = [ctypes.c_int]
    lib.hvd_flight_enabled.restype = ctypes.c_int
    got_metrics = lib.hvd_metrics_version()
    if got_metrics != METRICS_VERSION:
        raise OSError(
            f"horovod_tpu native core at {path} has metrics snapshot "
            f"version {got_metrics}, expected {METRICS_VERSION}; rebuild "
            "it (make -C native)")
    # Host reduction kernels + thread budget (perf_tuning.md): exercised
    # directly by the dtype-coverage tests and exposed through
    # hvd.set_reduce_threads / hvd.reduce_threads.
    lib.hvd_host_accumulate.restype = None
    lib.hvd_host_accumulate.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64]
    lib.hvd_host_scale.restype = None
    lib.hvd_host_scale.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                   ctypes.c_int64, ctypes.c_double]
    lib.hvd_set_reduce_threads.restype = None
    lib.hvd_set_reduce_threads.argtypes = [ctypes.c_int]
    lib.hvd_reduce_threads.restype = ctypes.c_int
    # Vectored-transport surface (ABI v8, docs/perf_tuning.md
    # zero-copy transport): real SendV/RecvV/frame paths over
    # caller-owned fds — the socketpair unit-test surface
    # (tests/test_transport.py) plus the resolved-mode probes.
    lib.hvd_tcp_sendv.restype = ctypes.c_int
    lib.hvd_tcp_sendv.argtypes = [ctypes.c_int,
                                  ctypes.POINTER(ctypes.c_void_p),
                                  ctypes.POINTER(ctypes.c_uint64),
                                  ctypes.c_int]
    lib.hvd_tcp_recvv.restype = ctypes.c_int
    lib.hvd_tcp_recvv.argtypes = [ctypes.c_int,
                                  ctypes.POINTER(ctypes.c_void_p),
                                  ctypes.POINTER(ctypes.c_uint64),
                                  ctypes.c_int]
    lib.hvd_tcp_send_frame.restype = ctypes.c_int
    lib.hvd_tcp_send_frame.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                       ctypes.c_uint64]
    lib.hvd_tcp_recv_frame.restype = ctypes.c_int64
    lib.hvd_tcp_recv_frame.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                       ctypes.c_uint64]
    lib.hvd_tcp_transport_mode.restype = ctypes.c_int
    lib.hvd_tcp_transport_mode_name.restype = ctypes.c_char_p
    # Transport riders (ABI v10): io_uring submission-batching verdict
    # (HOROVOD_TCP_IOURING end-to-end probe) and the WorkerPool
    # affinity gauge (HOROVOD_REDUCE_THREAD_AFFINITY pinned-thread
    # count).
    lib.hvd_tcp_iouring_mode.restype = ctypes.c_int
    lib.hvd_tcp_iouring_mode_name.restype = ctypes.c_char_p
    lib.hvd_worker_affinity.restype = ctypes.c_int
    # Steady-state schedule lock (ABI v11, docs/perf_tuning.md
    # "Steady-state schedule lock"): the engaged flag plus the period-
    # detector test hooks tests/test_steady_lock.py drives without
    # spawning ranks.
    lib.hvd_steady_lock_engaged.restype = ctypes.c_int
    # Persistent locked data plane (ABI v13, docs/perf_tuning.md
    # "Persistent locked data plane"): the coordinator-synced
    # HOROVOD_STEADY_PERSISTENT verdict (0 = auto, 1 = off) and the
    # live pre-posted recv buffer count (the tcp_prepost_buffers
    # gauge's backing store).
    lib.hvd_steady_persistent.restype = ctypes.c_int
    lib.hvd_tcp_prepost_buffers.restype = ctypes.c_int64
    lib.hvd_lockdet_create.restype = ctypes.c_void_p
    lib.hvd_lockdet_feed.restype = None
    lib.hvd_lockdet_feed.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                     ctypes.c_char_p]
    lib.hvd_lockdet_ready.restype = ctypes.c_int
    lib.hvd_lockdet_ready.argtypes = [ctypes.c_void_p]
    lib.hvd_lockdet_period.restype = ctypes.c_int
    lib.hvd_lockdet_period.argtypes = [ctypes.c_void_p]
    lib.hvd_lockdet_take.restype = ctypes.c_int
    lib.hvd_lockdet_take.argtypes = [ctypes.c_void_p]
    lib.hvd_lockdet_destroy.restype = None
    lib.hvd_lockdet_destroy.argtypes = [ctypes.c_void_p]
    # Wire-codec kernels (perf_tuning.md HOROVOD_WIRE_COMPRESSION):
    # exercised directly by the codec round-trip/error-feedback tests.
    lib.hvd_wire_encoded_bytes.restype = ctypes.c_int64
    lib.hvd_wire_encoded_bytes.argtypes = [ctypes.c_int, ctypes.c_int64]
    lib.hvd_wire_encode.restype = None
    lib.hvd_wire_encode.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                    ctypes.c_int64, ctypes.c_void_p,
                                    ctypes.c_void_p]
    lib.hvd_wire_decode.restype = None
    lib.hvd_wire_decode.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                    ctypes.c_int64, ctypes.c_void_p]
    lib.hvd_wire_decode_add.restype = None
    lib.hvd_wire_decode_add.argtypes = [ctypes.c_int, ctypes.c_void_p,
                                        ctypes.c_int64, ctypes.c_void_p]
    # Schedule-interpreter surface (docs/perf_tuning.md "Collective
    # algorithm selection"): chunk-op table builder + the default
    # selection table, both pure functions — the simulator tests
    # drive them without spawning ranks.
    lib.hvd_build_schedule.restype = ctypes.c_int
    lib.hvd_build_schedule.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int]
    lib.hvd_algo_select.restype = ctypes.c_int
    lib.hvd_algo_select.argtypes = [ctypes.c_int64, ctypes.c_int,
                                    ctypes.c_int, ctypes.c_int64]
    lib.hvd_algo_name.restype = ctypes.c_char_p
    lib.hvd_algo_name.argtypes = [ctypes.c_int]
    lib.hvd_collective_algo.restype = ctypes.c_int
    # Measured-topology surface (ABI v9, docs/perf_tuning.md "Measured
    # topology & schedule synthesis"): the alpha-beta link model, the
    # on-demand re-probe, the measured selection verdict, the native
    # cost walk, and the any-collective table builder tools/synth.py
    # and the promoted verifier enumerate.
    lib.hvd_topology.restype = ctypes.c_int
    lib.hvd_topology.argtypes = [ctypes.POINTER(ctypes.c_double),
                                 ctypes.POINTER(ctypes.c_double),
                                 ctypes.c_int]
    lib.hvd_topology_probe.restype = ctypes.c_double
    lib.hvd_topology_probe.argtypes = []
    lib.hvd_algo_select_measured.restype = ctypes.c_int
    lib.hvd_algo_select_measured.argtypes = [ctypes.c_int64, ctypes.c_int,
                                             ctypes.c_int, ctypes.c_int64]
    lib.hvd_algo_cost_us.restype = ctypes.c_double
    lib.hvd_algo_cost_us.argtypes = [ctypes.c_int, ctypes.c_int64,
                                     ctypes.c_int, ctypes.c_int,
                                     ctypes.c_int]
    # Point-to-point migration pricing (docs/serving.md "Direct
    # migration"): the native half of the serving router's cost twin
    # (horovod_tpu/serve/migrate.py mirrors both formulas); <0 when no
    # model. The sanitizer tier cross-checks native vs twin.
    lib.hvd_link_cost_us.restype = ctypes.c_double
    lib.hvd_link_cost_us.argtypes = [ctypes.c_int, ctypes.c_int,
                                     ctypes.c_int64]
    lib.hvd_migration_cost_us.restype = ctypes.c_double
    lib.hvd_migration_cost_us.argtypes = [ctypes.c_int, ctypes.c_int,
                                          ctypes.c_int64, ctypes.c_int64]
    lib.hvd_build_coll_schedule.restype = ctypes.c_int
    lib.hvd_build_coll_schedule.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int]
    # Membership plane (ABI v12, docs/elastic.md): the process-global
    # epoch / active-rank / fence surface hvd.membership() reads, plus
    # the decay blacklist the elastic driver and serving router share.
    # Usable BEFORE hvd_init — driver/router processes never init the
    # core.
    lib.hvd_membership_epoch.restype = ctypes.c_int64
    lib.hvd_membership_generation.restype = ctypes.c_int64
    lib.hvd_membership_size.restype = ctypes.c_int
    lib.hvd_membership_ranks.restype = ctypes.c_int
    lib.hvd_membership_ranks.argtypes = [ctypes.POINTER(ctypes.c_int),
                                         ctypes.c_int]
    lib.hvd_membership_advance.restype = ctypes.c_int64
    lib.hvd_membership_advance.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.hvd_membership_reset.restype = None
    lib.hvd_membership_reset.argtypes = [ctypes.c_int64, ctypes.c_int]
    lib.hvd_membership_fence_count.restype = ctypes.c_int
    lib.hvd_blacklist_configure.restype = None
    lib.hvd_blacklist_configure.argtypes = [ctypes.c_double, ctypes.c_double]
    lib.hvd_blacklist_record.restype = ctypes.c_double
    lib.hvd_blacklist_record.argtypes = [ctypes.c_char_p, ctypes.c_double]
    lib.hvd_blacklist_weight.restype = ctypes.c_double
    lib.hvd_blacklist_weight.argtypes = [ctypes.c_char_p, ctypes.c_double]
    lib.hvd_blacklist_check.restype = ctypes.c_int
    lib.hvd_blacklist_check.argtypes = [ctypes.c_char_p, ctypes.c_double]
    lib.hvd_blacklist_count.restype = ctypes.c_int
    lib.hvd_blacklist_count.argtypes = [ctypes.c_double]
    lib.hvd_blacklist_clear.restype = None
    # Topology staleness hooks (ABI v12): keyless model injection + the
    # auto-resolution verdict, the test surface pinning ResolveAlgoAuto's
    # refuse-stale-hostkey rule.
    lib.hvd_topology_inject.restype = ctypes.c_int
    lib.hvd_topology_inject.argtypes = [ctypes.c_char_p]
    lib.hvd_algo_resolve_auto.restype = ctypes.c_int
    lib.hvd_algo_resolve_auto.argtypes = [ctypes.c_int64, ctypes.c_int,
                                          ctypes.c_int]
    return lib

# Membership change reasons (native/include/hvd/membership.h
# MembershipChangeReason — stable ints, part of the ABI surface).
MEMBER_RESET = 0
MEMBER_JOIN = 1
MEMBER_DEAD_PEER = 2
MEMBER_SHRINK = 3

# Flight-recorder event ids (native/include/hvd/flight.h FlightEvent —
# stable ints, part of the ABI surface; only the ones Python records
# are named here, pinned against the native name table by
# tests/test_flight.py).
FLIGHT_PEER_DEATH = 6
FLIGHT_REQUEUE = 10
FLIGHT_INTERNAL_ERROR = 11


_lib: Optional[ctypes.CDLL] = None


def get_lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        _lib = load_library()
    return _lib
