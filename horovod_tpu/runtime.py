"""Eager runtime: Python orchestration over the native coordination core.

The split mirrors the reference: the C++ core owns negotiation, fusion
planning, caching, stall detection and the host (TCP) data plane
(reference ``horovod/common/operations.cc``); this module owns

* tensor registries (keeping inputs/outputs alive while in flight),
* the output **allocator callback** (the ``OpContext::AllocateOutput``
  analog, reference ``common/common.h:196-210``) for late-sized
  allgather/alltoall outputs, and
* the **XLA executor callback** — the NCCL-ops analog: CALLBACK-mode
  responses (JAX device arrays) are executed as jitted XLA collective
  programs instead of being routed through host TCP.
"""

from __future__ import annotations

import atexit
import ctypes
import os
import threading
from typing import Any, Dict, List, Optional

import numpy as np

from horovod_tpu.common import basics
from horovod_tpu.common.exceptions import HorovodInternalError
from horovod_tpu.common.ops_enum import ReduceOp
from horovod_tpu.common.topology import Topology, topology_from_env
from horovod_tpu.compression import wire_codec_id


def _contig(a: np.ndarray) -> np.ndarray:
    """C-contiguous view/copy that PRESERVES 0-d shape
    (``np.ascontiguousarray`` silently promotes 0-d to shape (1,))."""
    out = np.ascontiguousarray(a)
    if out.shape != np.shape(a):
        out = out.reshape(np.shape(a))
    return out


class _InFlight:
    """State for one in-flight collective (registry entry)."""

    __slots__ = ("name", "op", "input_np", "input_dev", "output", "orig_kind",
                 "orig_dtype", "reduce_op", "prescale", "postscale", "splits",
                 "recvsplits", "root_rank")

    def __init__(self):
        self.name = None
        self.op = None
        self.input_np = None      # host buffer (kept alive for native core)
        self.input_dev = None     # jax array for CALLBACK mode
        self.output = None
        self.orig_kind = "np"     # np | jax | torch
        self.orig_dtype = None
        self.reduce_op = ReduceOp.AVERAGE
        self.prescale = 1.0
        self.postscale = 1.0
        self.splits = None
        self.recvsplits = None
        self.root_rank = 0


class Handle:
    """Async collective handle (reference ``horovod/torch/mpi_ops.py``
    handle model + ``handle_manager.h``)."""

    __slots__ = ("native", "runtime")

    def __init__(self, native: int, runtime: "Runtime"):
        self.native = native
        self.runtime = runtime


class Runtime:
    def __init__(self):
        self.lib = None
        self.topology: Optional[Topology] = None
        self._lock = threading.RLock()
        self._inflight: Dict[int, _InFlight] = {}   # native handle -> state
        self._name_to_handle: Dict[str, int] = {}
        self._name_counters: Dict[str, int] = {}
        self._exec_cb = None   # keep callbacks alive for the C core
        self._alloc_cb = None
        self._init_epoch = 0   # keys rendezvous rediscovery on re-init
        self._jax_dist_up = False
        self._exec_worker = None  # elastic device-program worker (watchdog)
        self._exec_q = None
        # Coordinator-address KV key coordinates: (elastic epoch, count
        # of world formations within that epoch). Survivors and freshly
        # respawned workers must derive the SAME key, so it cannot be
        # keyed on the per-process _init_epoch — after a respawn the
        # newcomer is at init 0 while survivors are at init k. The
        # elastic epoch is driver-published and identical everywhere;
        # the per-epoch sequence covers same-epoch re-inits (transient
        # global errors roll no epoch but every process re-inits once).
        self._xla_world_seq = 0
        self._xla_world_epoch_tag: Optional[str] = None
        self._xla_world_key: Optional[str] = None  # of the live world

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def init(self, topology: Optional[Topology] = None) -> None:
        if self.initialized():
            return
        if (topology is None and os.environ.get("HOROVOD_ELASTIC_ID")
                and os.environ.get("HOROVOD_RENDEZVOUS_ADDR")):
            # Driver-spawned elastic worker: the spawn env's epoch (and
            # its controller address) may already be stale if membership
            # churned while this interpreter came up. Rendezvous at the
            # newest driver epoch with in-process retries instead of
            # dying a nonzero death the driver would count as a host
            # flap (elastic.initial_init re-enters here with an
            # explicit topology).
            from horovod_tpu import elastic
            elastic.initial_init(self)
            return
        self.lib = basics.get_lib()
        topo = topology or topology_from_env()
        discovered = False
        if (topo.size > 1 and "HOROVOD_CONTROLLER_ADDR" not in os.environ
                and os.environ.get("HOROVOD_RENDEZVOUS_ADDR")):
            # horovodrun job: discover the controller address through
            # the launcher's KV store instead of a pre-agreed port. The
            # init epoch keys the lookup so a shutdown + re-init gets a
            # fresh port, not the stale published one.
            from horovod_tpu.runner.rendezvous import discover_controller_addr
            timeout = float(os.environ.get("HOROVOD_START_TIMEOUT", "120"))
            os.environ["HOROVOD_CONTROLLER_ADDR"] = discover_controller_addr(
                topo.rank, timeout, epoch=self._init_epoch)
            discovered = True
        if (os.environ.get("HOROVOD_TIMELINE")
                and os.environ.get("HOROVOD_TIMELINE_RANK_SUFFIX") == "1"):
            # Uniform-env launchers (--mpi) cannot suffix the timeline
            # path per slot the way _slot_env does; apply it here, once
            # (the flag is cleared so an elastic re-init in the same
            # process does not re-append).
            os.environ["HOROVOD_TIMELINE"] += f".{topo.rank}"
            os.environ["HOROVOD_TIMELINE_RANK_SUFFIX"] = "0"
        if os.environ.get("HOROVOD_XLA_EXEC") == "1":
            if topo.size > 1:
                self._init_jax_distributed(topo)
            elif self._jax_dist_up:
                # The world shrank to one process (elastic scale-down):
                # the old multi-process XLA runtime is stale; tear it
                # down so jax sees only local devices again.
                self._teardown_jax_distributed()
        self._exec_cb = basics.EXEC_CB_TYPE(self._on_exec)
        self._alloc_cb = basics.ALLOC_CB_TYPE(self._on_alloc)
        self.lib.hvd_set_exec_callback(self._exec_cb)
        self.lib.hvd_set_alloc_callback(self._alloc_cb)
        rc = self.lib.hvd_init(topo.rank, topo.size, topo.local_rank,
                               topo.local_size, topo.cross_rank,
                               topo.cross_size)
        if discovered:
            # The native core has read the env var; don't leak a stale
            # address into re-inits or worker subprocesses.
            os.environ.pop("HOROVOD_CONTROLLER_ADDR", None)
        self._init_epoch += 1
        if rc != 0:
            raise HorovodInternalError("native core initialization failed")
        self.topology = topo

    def _init_jax_distributed(self, topo: Topology) -> None:
        """Bring up the process-spanning XLA runtime (``--xla-exec``):
        ``jax.distributed`` + gloo CPU collectives, so eager CALLBACK
        responses execute as cross-process XLA programs instead of
        staging through the host TCP plane. Must run before the local
        jax backend initializes."""
        import jax

        if self._jax_dist_up:
            # Elastic re-init: membership changed (or a peer died), so
            # the live world is stale — its size may be wrong and its
            # peer connections may be broken. Re-form it at the new
            # membership, the way the reference re-creates its comm
            # context on every rendezvous (``gloo/gloo_context.cc:
            # 154-200``), instead of silently keeping the old one.
            self._teardown_jax_distributed()
        elif self._init_epoch > 0:
            # Re-init after a size-1 interlude (shrink to one, then
            # grow): the interlude's jax calls re-created the LOCAL
            # backend, and ``jax.distributed.initialize`` refuses to
            # run after any backend use — flush it exactly like a full
            # teardown would (a no-op if nothing was initialized).
            import jax.extend.backend as jax_backend
            jax.clear_caches()
            jax_backend.clear_backends()
            from horovod_tpu.ops import xla_exec
            xla_exec.invalidate_world()
        coord = os.environ.get("HOROVOD_XLA_COORD_ADDR")
        if coord and os.environ.get("HOROVOD_ELASTIC_ID"):
            # A static coordinator address cannot follow rank 0 across
            # membership changes (the configured host may be the very
            # one that died); elastic jobs always rendezvous the
            # epoch's coordinator through the launcher KV.
            coord = None
        if not coord:
            if not os.environ.get("HOROVOD_RENDEZVOUS_ADDR"):
                raise HorovodInternalError(
                    "HOROVOD_XLA_EXEC=1 needs HOROVOD_XLA_COORD_ADDR or a "
                    "launcher rendezvous (HOROVOD_RENDEZVOUS_ADDR)")
            from horovod_tpu.runner.http_kv import kv_put, kv_wait
            from horovod_tpu.runner.rendezvous import free_port
            rdv = os.environ["HOROVOD_RENDEZVOUS_ADDR"]
            timeout = float(os.environ.get("HOROVOD_START_TIMEOUT", "120"))
            tag = os.environ.get("HOROVOD_ELASTIC_EPOCH", "")
            if tag != self._xla_world_epoch_tag:
                self._xla_world_epoch_tag = tag
                self._xla_world_seq = 0
            key = f"xla_coord_addr.{tag or 0}.{self._xla_world_seq}"
            if self._xla_world_key is None and os.environ.get(
                    "HOROVOD_ELASTIC_ID"):
                atexit.register(self._leave_jax_distributed_at_exit)
            self._xla_world_key = key
            if topo.rank == 0:
                host = os.environ.get("HOROVOD_CONTROLLER_HOST")
                if not host:
                    # Uniform-env launchers (--mpi) cannot know which
                    # node gets rank 0; advertise our own outbound IP.
                    from horovod_tpu.runner.hosts import local_ip
                    host = local_ip()
                coord = f"{host}:{free_port()}"
                kv_put(rdv, "global", key, coord.encode())
            else:
                coord = kv_wait(rdv, "global", key, timeout).decode()
        # Probing the backend here would initialize it — too early.
        # Decide CPU-ness from the environment alone.
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            try:
                jax.config.update("jax_cpu_collectives_implementation",
                                  "gloo")
            except Exception:
                pass
        start_timeout = float(os.environ.get("HOROVOD_START_TIMEOUT", "120"))
        # Peers come up within the launcher's start timeout or not at
        # all; jax's 300 s default would stall failure detection.
        kwargs = {"initialization_timeout": max(10, int(start_timeout))}
        if os.environ.get("HOROVOD_ELASTIC_ID"):
            # Elastic job: peers can die at any time. Recoverable tasks
            # skip the coordination service's shutdown barrier — without
            # this, a survivor's teardown blocks on the dead peer for
            # the full heartbeat timeout and then LOG(FATAL)s the
            # process (xla client.h). Short timeouts bound how long the
            # re-formation can lag behind the host-plane failure.
            jax.config.update("jax_enable_recoverability", True)
            kwargs.update(heartbeat_timeout_seconds=10,
                          shutdown_timeout_seconds=10)
        try:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=topo.size,
                                       process_id=topo.rank, **kwargs)
        except Exception as e:
            # A half-formed runtime (service up, a peer never joined)
            # must not poison the next attempt with jax's
            # "should only be called once" guard.
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            self._force_reset_jax_dist_state()
            raise HorovodInternalError(
                f"jax.distributed initialization failed: {e}") from e
        finally:
            # Advance on ATTEMPT, not success: formation outcomes can
            # diverge (rank j times out while others connect), and a
            # success-only increment would leave rank j deriving the
            # previous key — and reading its stale coordinator address
            # — on the next same-epoch attempt.
            self._xla_world_seq += 1
        self._jax_dist_up = True

    def _teardown_jax_distributed(self) -> None:
        """Tear down the process-spanning XLA runtime so a later init
        can form a fresh one. Backends must be cleared too — they hold
        the old distributed client — and with them every cached mesh
        and jitted program that baked in the old device set. Live jax
        arrays stay readable (their buffers outlive the backend cache),
        so committed elastic state survives the re-formation."""
        import jax

        try:
            jax.distributed.shutdown()
        except Exception:
            # A dead peer (the very thing that triggered the reset) can
            # break the coordination service's teardown handshake; the
            # client is discarded either way.
            self._force_reset_jax_dist_state()
        jax.clear_caches()
        import jax.extend.backend as jax_backend
        jax_backend.clear_backends()
        from horovod_tpu.ops import xla_exec
        xla_exec.invalidate_world()
        self._jax_dist_up = False

    def _leave_jax_distributed_at_exit(self) -> None:
        """Order the end of an elastic XLA world (registered after
        jax's own exit handler, so run before it). The coordination
        service lives in rank 0's process and a recoverable world has
        no shutdown barrier: a rank 0 that exits first takes the service
        from under its peers, whose clients then end their process with
        LOG(FATAL) (xla client.h, at ``ShutdownTask``) and a non-zero
        code after the work is done. So a peer leaves and says so in
        the launcher's KV store, and rank 0 waits for every peer's word
        before it exits: as long as a peer may take to come up, and no
        longer for one that is gone."""
        if not self._jax_dist_up:
            return
        import jax
        from horovod_tpu.runner.http_kv import kv_put, kv_wait
        rdv = os.environ.get("HOROVOD_RENDEZVOUS_ADDR")
        topo, key = self.topology, self._xla_world_key
        try:
            if topo.rank != 0:
                jax.distributed.shutdown()
                kv_put(rdv, "global", f"{key}.left.{topo.rank}", b"1")
            else:
                timeout = float(os.environ.get("HOROVOD_START_TIMEOUT", "120"))
                for rank in range(1, topo.size):
                    kv_wait(rdv, "global", f"{key}.left.{rank}", timeout)
        except Exception:
            pass  # the peer or the launcher is gone: exit all the same

    @staticmethod
    def _force_reset_jax_dist_state() -> None:
        """Failure-path fallback when the public shutdown cannot run to
        completion: drop the distributed client state directly so a
        later ``initialize`` doesn't refuse with "should only be called
        once". Private-API touch, used only after a failed shutdown or
        a failed initialize."""
        try:
            from jax._src import distributed as jax_dist
            st = jax_dist.global_state
            st.client = None
            st.service = None
            st.preemption_sync_manager = None
            st.coordinator_address = None
            st.process_id = 0
            st.num_processes = 1
        except Exception:
            pass

    def shutdown(self) -> None:
        if self.lib is not None and self.initialized():
            self.lib.hvd_shutdown()
        if self._exec_q is not None:
            self._exec_q.put(None)  # end the idle watchdog worker
            self._exec_worker = None
            self._exec_q = None
        with self._lock:
            self._inflight.clear()
            self._name_to_handle.clear()
            self._name_counters.clear()

    def initialized(self) -> bool:
        return self.lib is not None and bool(self.lib.hvd_initialized())

    def rank(self) -> int:
        self._check_init()
        return self.lib.hvd_rank()

    def size(self) -> int:
        self._check_init()
        return self.lib.hvd_size()

    def local_rank(self) -> int:
        self._check_init()
        return self.lib.hvd_local_rank()

    def local_size(self) -> int:
        self._check_init()
        return self.lib.hvd_local_size()

    def cross_rank(self) -> int:
        self._check_init()
        return self.lib.hvd_cross_rank()

    def cross_size(self) -> int:
        self._check_init()
        return self.lib.hvd_cross_size()

    def reduce_threads(self) -> int:
        """Worker threads the host data plane currently spreads its
        reductions and pack/unpack copies over (``docs/perf_tuning.md``).
        Reflects the coordinator-synced ``HOROVOD_REDUCE_THREADS`` value
        and any autotuned retarget."""
        self._check_init()
        return int(self.lib.hvd_reduce_threads())

    def set_reduce_threads(self, n: int) -> None:
        """Retarget the host-reduction thread budget of THIS process
        (clamped to [1, 64]). Results are bitwise identical at any
        setting, so a per-rank override is always safe — unlike the
        protocol knobs, no cross-rank agreement is needed."""
        self._check_init()
        self.lib.hvd_set_reduce_threads(int(n))

    def _check_init(self) -> None:
        if not self.initialized():
            raise RuntimeError(
                "horovod_tpu has not been initialized; call hvd.init() first")

    # ------------------------------------------------------------------
    # enqueue / synchronize
    # ------------------------------------------------------------------

    def auto_name(self, prefix: str, explicit: Optional[str]) -> str:
        if explicit is not None:
            return explicit
        with self._lock:
            i = self._name_counters.get(prefix, 0)
            self._name_counters[prefix] = i + 1
        return f"{prefix}.noname.{i}"

    @staticmethod
    def _classify(tensor):
        """Returns (kind, np_view_or_none, jax_array_or_none)."""
        mod = type(tensor).__module__
        if isinstance(tensor, np.ndarray):
            return "np", tensor, None
        if mod.startswith("torch"):
            import torch
            t = tensor.detach()
            if t.device.type != "cpu":
                t = t.cpu()
            t = t.contiguous()
            if t.dtype == torch.bfloat16:
                # torch refuses bf16->numpy; stage through a uint16 view
                # and rewrap with ml_dtypes so the native core sees the
                # real dtype.
                import ml_dtypes
                return "torch", t.view(torch.uint16).numpy().view(
                    ml_dtypes.bfloat16), None
            return "torch", t.numpy(), None
        if mod.startswith("jax") or hasattr(tensor, "addressable_shards"):
            return "jax", None, tensor
        # Anything array-like (lists, scalars) becomes numpy.
        return "np", _contig(np.asarray(tensor)), None

    def enqueue(self, op: int, tensor, name: str, *,
                reduce_op: ReduceOp = ReduceOp.AVERAGE,
                root_rank: int = 0,
                prescale_factor: float = 1.0,
                postscale_factor: float = 1.0,
                splits=None,
                group_key: int = -1,
                group_size: int = 0,
                compression=None,
                algorithm=None) -> Handle:
        self._check_init()
        # Per-op wire codec for the host TCP data plane (-1 = follow
        # HOROVOD_WIRE_COMPRESSION). CALLBACK (XLA) responses ignore it
        # — device collectives ride ICI at their own dtype.
        wire_codec = wire_codec_id(compression)
        # Per-op allreduce algorithm (0 = follow the coordinator's
        # selection table / HOROVOD_COLLECTIVE_ALGO); resolved into
        # each response like the wire codec, so mixed per-rank settings
        # are a coordinator error, never a desynced exchange.
        collective_algo = basics.collective_algo_id(algorithm)
        kind, np_in, dev_in = self._classify(tensor)

        st = _InFlight()
        st.name = name
        st.op = op
        st.orig_kind = kind
        st.reduce_op = reduce_op
        st.prescale = prescale_factor
        st.postscale = postscale_factor
        st.root_rank = root_rank

        if kind == "jax" and self.size() > 1 and not _jax_distributed_active():
            # No process-spanning mesh available: stage through the host
            # data plane (the reference's CPU-fallback, gloo_operations.cc).
            # Loud, once — the XLA data plane is opt-in via --xla-exec.
            global _warned_host_staging
            if not _warned_host_staging:
                _warned_host_staging = True
                import warnings
                warnings.warn(
                    "horovod_tpu: jax tensors are staging through the host "
                    "TCP data plane because jax.distributed is not "
                    "initialized; launch with horovodrun --xla-exec (or set "
                    "HOROVOD_XLA_EXEC=1) for the XLA data plane",
                    RuntimeWarning, stacklevel=3)
            kind = "np"
            np_in = np.asarray(dev_in)
            st.orig_kind = "jax"

        if kind == "jax":
            # Device path: the native core negotiates; execution happens
            # in the XLA executor callback.
            exec_mode = basics.EXEC_CALLBACK
            st.input_dev = dev_in
            shape = list(dev_in.shape)
            dt = basics.dtype_id(dev_in.dtype)
            data_ptr = None
            out_ptr = None
        else:
            exec_mode = basics.EXEC_HOST
            np_in = _contig(np_in)
            st.input_np = np_in
            st.orig_dtype = np_in.dtype
            shape = list(np_in.shape)
            dt = basics.dtype_id(np_in.dtype)
            data_ptr = np_in.ctypes.data
            if op in (basics.OP_ALLREDUCE, basics.OP_BROADCAST):
                st.output = np.empty_like(np_in)
                out_ptr = st.output.ctypes.data
            else:
                out_ptr = None  # allocated by callback once sizes known

        shape_arr = (ctypes.c_int64 * len(shape))(*shape)
        if splits is not None:
            splits = list(int(s) for s in splits)
            st.splits = splits
            splits_arr = (ctypes.c_int64 * len(splits))(*splits)
            nsplits = len(splits)
        else:
            splits_arr = None
            nsplits = 0

        with self._lock:
            handle = self.lib.hvd_enqueue(
                op, name.encode(), dt, shape_arr, len(shape), data_ptr,
                out_ptr, root_rank, int(reduce_op), prescale_factor,
                postscale_factor, splits_arr, nsplits, exec_mode,
                group_key, group_size, wire_codec, collective_algo)
            if handle < 0:
                err = self.lib.hvd_last_enqueue_error().decode()
                raise HorovodInternalError(err)
            self._inflight[handle] = st
            self._name_to_handle[name] = handle
        return Handle(handle, self)

    def poll(self, handle: Handle) -> bool:
        return bool(self.lib.hvd_poll(handle.native))

    def synchronize(self, handle: Handle):
        err_buf = ctypes.create_string_buffer(1024)
        rc = self.lib.hvd_wait(handle.native, -1, err_buf, len(err_buf))
        with self._lock:
            st = self._inflight.pop(handle.native, None)
            if st is not None and self._name_to_handle.get(st.name) == handle.native:
                self._name_to_handle.pop(st.name, None)
        if rc != 0:
            self.lib.hvd_release_handle(handle.native)
            raise HorovodInternalError(
                err_buf.value.decode() or f"collective failed (rc={rc})")
        if st is None:
            self.lib.hvd_release_handle(handle.native)
            raise HorovodInternalError("unknown handle")
        # Alltoall recv splits.
        if st.op == basics.OP_ALLTOALL:
            n = self.lib.hvd_get_recvsplits(handle.native, None, 0)
            if n > 0:
                buf = (ctypes.c_int64 * n)()
                self.lib.hvd_get_recvsplits(handle.native, buf, n)
                st.recvsplits = list(buf)
        self.lib.hvd_release_handle(handle.native)

        out = st.output
        if st.orig_kind == "jax":
            import jax.numpy as jnp
            if out is None:
                out = st.input_dev
            elif not hasattr(out, "devices"):
                out = jnp.asarray(out)
            return out, st
        if st.orig_kind == "torch":
            import torch
            out = _contig(out)
            if out.dtype.name == "bfloat16":
                return torch.from_numpy(out.view(np.uint16)).view(
                    torch.bfloat16), st
            return torch.from_numpy(out), st
        return out, st

    # ------------------------------------------------------------------
    # native-core callbacks (run on the background thread)
    # ------------------------------------------------------------------

    def _on_alloc(self, handle: int, shape_ptr, ndim: int) -> int:
        try:
            shape = tuple(shape_ptr[i] for i in range(ndim))
            with self._lock:
                st = self._inflight.get(handle)
                if st is None:
                    return 0
                st.output = np.empty(shape, dtype=st.orig_dtype)
                return st.output.ctypes.data
        except Exception:
            return 0

    def _on_exec(self, exec_id: int, op: int, n: int, names_ptr, dtype: int,
                 sizes_ptr, sizes_len: int, reduce_op: int,
                 contributes: int) -> None:
        try:
            names = [names_ptr[i].decode() for i in range(n)]
            sizes = [sizes_ptr[i] for i in range(sizes_len)] if sizes_len else []
            self._execute_xla(op, names, sizes, dtype, reduce_op,
                              bool(contributes))
            self.lib.hvd_exec_done(exec_id, 0, None)
        except Exception as e:  # noqa: BLE001 — must not unwind into C
            self.lib.hvd_exec_done(exec_id, 1, str(e).encode())

    def _execute_xla(self, op: int, names: List[str], sizes: List[int],
                     dtype: int, reduce_op: int, contributes: bool) -> None:
        """Execute one CALLBACK-mode response with XLA.

        Single-process: collectives over ranks degenerate to (scaled)
        identity. Multi-process pods run under ``jax.distributed`` with
        a process-spanning mesh (the launcher sets it up); every process
        executes this same program in the same order — the ordering is
        guaranteed by the controller's broadcast ResponseList.

        ``contributes`` comes from the Response's contributor set: only
        when this rank is genuinely a non-contributor (it joined) may a
        missing local handle be replaced by a zeros contribution
        (reference feeds zeros for joined ranks, ``operations.cc:260``).
        A missing handle on a contributing rank is a bug (name reuse,
        premature cleanup) and raises instead of corrupting the
        reduction with silent zeros.
        """
        from horovod_tpu.ops import xla_exec

        with self._lock:
            states = []
            for i, nm in enumerate(names):
                h = self._name_to_handle.get(nm)
                if h is not None and h in self._inflight:
                    states.append(self._inflight[h])
                elif not contributes and op == basics.OP_ALLREDUCE:
                    # Joined rank with no local tensor: sizes[i] is the
                    # tensor's element count.
                    states.append(xla_exec.zeros_state(
                        nm, op, sizes[i] if i < len(sizes) else 0, dtype,
                        reduce_op))
                else:
                    raise KeyError(
                        f"no in-flight state for tensor {nm!r} (op {op}, "
                        f"contributes={contributes}); a contributing rank "
                        "must hold a live handle for every response tensor")
        outs = self._run_device_program(op, states, sizes)
        with self._lock:
            for st, out in zip(states, outs):
                st.output = out

    def _run_device_program(self, op: int, states, sizes: List[int]):
        """Run one XLA device program, guarding elastic jobs against a
        peer dying mid-program: the CPU-collective rendezvous has no
        timeout, so a dead peer leaves the program blocked forever and
        with it the whole background thread (and the job — synchronize
        never returns, so the elastic reset never starts). Run the
        program on a helper thread and abandon the wait when the driver
        rolls the membership epoch; the reset that follows tears the
        world down, which cancels the stuck program's pending RPCs."""
        from horovod_tpu.ops import xla_exec

        if not (os.environ.get("HOROVOD_ELASTIC_ID") and self.size() > 1):
            return xla_exec.execute(op, states, sizes, self.size(),
                                    self.rank())

        box: Dict[str, Any] = {}
        done = threading.Event()

        def _run():
            try:
                box["outs"] = xla_exec.execute(op, states, sizes,
                                               self.size(), self.rank())
            except Exception as e:  # noqa: BLE001 — re-raised below
                box["err"] = e
            finally:
                done.set()

        if self._exec_worker is None:
            # Persistent DAEMON worker (not ThreadPoolExecutor, whose
            # non-daemon thread would be joined at interpreter exit —
            # a wedged program would then block process exit forever).
            import queue
            self._exec_q = queue.SimpleQueue()
            q = self._exec_q

            def _loop():
                while True:
                    fn = q.get()
                    if fn is None:
                        return
                    fn()

            self._exec_worker = threading.Thread(
                target=_loop, daemon=True, name="hvd-xla-exec")
            self._exec_worker.start()
        self._exec_q.put(_run)
        from horovod_tpu import elastic as _elastic
        start_epoch = int(os.environ.get("HOROVOD_ELASTIC_EPOCH", "0") or 0)
        while not done.wait(0.5):
            try:
                w = _elastic._watcher
                cur = (w.latest() if w is not None and not w.stale()
                       else _elastic.current_epoch())
            except Exception:
                continue
            if cur > start_epoch:
                # The roll may be a healthy scale-UP (all current peers
                # alive, program about to complete): grant a grace
                # window so growth doesn't cost a rollback to the last
                # commit. A dead-peer program never completes, so after
                # the grace the world is known doomed.
                grace = float(os.environ.get(
                    "HOROVOD_XLA_EXEC_GRACE_SECS", "5"))
                if done.wait(grace):
                    break
                # The stuck op wedges the worker thread until the
                # teardown cancels its RPCs; do not queue future
                # programs behind it (the daemon thread leaks at
                # worst, never blocking exit).
                self._exec_worker = None
                self._exec_q = None
                raise HorovodInternalError(
                    f"membership epoch rolled {start_epoch} -> {cur} while "
                    "a device collective was in flight; abandoning the "
                    "stale world's program")
        if "err" in box:
            raise box["err"]
        return box["outs"]

    # ------------------------------------------------------------------
    # misc
    # ------------------------------------------------------------------

    def join(self) -> Handle:
        self._check_init()
        h = self.lib.hvd_join()
        st = _InFlight()
        st.name, st.op = "join", basics.OP_JOIN
        with self._lock:
            self._inflight[h] = st
            self._name_to_handle[st.name] = h
        return Handle(h, self)

    def barrier(self) -> Handle:
        self._check_init()
        h = self.lib.hvd_barrier()
        st = _InFlight()
        st.name, st.op = "barrier", basics.OP_BARRIER
        with self._lock:
            self._inflight[h] = st
            self._name_to_handle[st.name] = h
        return Handle(h, self)

    def start_timeline(self, path: str) -> None:
        """Start — or RESTART onto a new path — the host timeline.
        Raises when the file cannot be opened (the native call used to
        silently no-op on both failure and restart)."""
        self._check_init()
        if self.lib.hvd_start_timeline(path.encode()) != 0:
            raise HorovodInternalError(
                f"could not open timeline file {path!r}")

    def stop_timeline(self) -> None:
        self._check_init()
        self.lib.hvd_stop_timeline()


_warned_host_staging = False


def _jax_distributed_active() -> bool:
    try:
        import jax
        return jax.process_count() > 1
    except Exception:
        return False


_runtime: Optional[Runtime] = None
_runtime_lock = threading.Lock()


def get_runtime() -> Runtime:
    global _runtime
    with _runtime_lock:
        if _runtime is None:
            _runtime = Runtime()
        return _runtime
