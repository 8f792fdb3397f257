"""Replica worker process: one ``ServeEngine`` behind the RPC seam.

``python -m horovod_tpu.serve.worker --port 0`` (or the
``bin/hvd-serve-worker`` wrapper) listens on a TCP port, announces
``HVD-SERVE-WORKER ready port=<p> pid=<pid>`` on stdout, accepts ONE
router connection, and serves the engine seam over
:mod:`horovod_tpu.serve.rpc` until the router disconnects or sends
``shutdown``. The engine itself is untouched: every replica invariant
the in-process fleet pins (bitwise decode parity, allocator safety,
backpressure) holds because the worker runs exactly the same
``ServeEngine`` code the router would have run in-process.

The worker builds its own params deterministically from the model
config plus a seed (``init_transformer(cfg, PRNGKey(seed))``), so the
router never ships multi-GB weights over the control channel; router
and workers agree on the model by construction (documented contract —
see docs/serving.md "Cross-process fleet").

Heartbeats are pull-based: the router's ``step``/``heartbeat`` RPCs
both return one *beat* payload — the admission state, the full
``ServeMetrics`` snapshot (so the router-process Prometheus scrape
spans worker processes), the latency samples recorded since the last
beat (delta-shipped, bounded), and every newly-finished result, each
timestamp re-anchored as an age relative to this process's clock
(``perf_counter`` epochs are per-process). Liveness is the transport
itself: a worker that dies mid-anything fails the router's next RPC.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from horovod_tpu.serve.kv_cache import page_chunks
from horovod_tpu.serve.rpc import (
    RpcConn, WORKER_READY_PREFIX, handoff_from_wire,
    handoff_meta_from_wire, handoff_meta_to_wire, handoff_to_wire,
    serve_connection,
)


#: Max in-flight (unanswered) frames on a pipelined peer stream.
#: Replies are tiny dicts, so the window exists only to bound the
#: reply backlog — it must comfortably exceed the chunk counts real
#: plans produce, or the pipeline degrades to lockstep.
_PEER_WINDOW = 8


def _build_engine(model_cfg: Dict[str, Any], serve_cfg: Dict[str, Any],
                  seed: int, instance: str):
    """Materialize the engine from wire-shaped configs (the inverse of
    ``rpc.model_cfg_to_wire``/``serve_cfg_to_wire``)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.compression import Compression
    from horovod_tpu.models import TransformerConfig, init_transformer
    from horovod_tpu.serve.engine import ServeConfig, ServeEngine

    mc = dict(model_cfg)
    mc["dtype"] = getattr(jnp, mc["dtype"])
    cfg = TransformerConfig(**mc)
    params = init_transformer(cfg, jax.random.PRNGKey(seed))
    sc = dict(serve_cfg)
    if sc.get("cache_dtype") is not None:
        sc["cache_dtype"] = getattr(jnp, sc["cache_dtype"])
    comp = sc.get("compression")
    sc["compression"] = (None if comp in (None, "none")
                         else getattr(Compression, comp))
    for k in ("batch_buckets", "prefill_buckets"):
        if sc.get(k) is not None:
            sc[k] = tuple(sc[k])
    draft = sc.get("draft")
    if draft is not None:
        # Speculative sub-config: the worker rebuilds the draft model
        # from (config, seed) exactly like it rebuilds the target —
        # the engine's SpecDecoder does the init, so a cross-process
        # speculative fleet agrees on the draft by construction.
        from horovod_tpu.serve.speculative import DraftConfig
        dmc = dict(draft["model_cfg"])
        dmc["dtype"] = getattr(jnp, dmc["dtype"])
        sc["draft"] = DraftConfig(
            TransformerConfig(**dmc), seed=int(draft["seed"]),
            cache_dtype=(None if draft["cache_dtype"] is None
                         else getattr(jnp, draft["cache_dtype"])))
    return ServeEngine(cfg, params, ServeConfig(**sc),
                       instance=instance)


class ReplicaWorker:
    """The dispatch table over one engine. Process-agnostic by design:
    :func:`main` runs it behind a listening socket, and the tier-1
    tests run it in a thread over a socketpair (same dispatch, same
    marshalling, no spawn cost) — only the slow tier pays real
    processes."""

    def __init__(self, conn: RpcConn, clock=time.perf_counter,
                 peer_host: str = "127.0.0.1"):
        self.conn = conn
        self.engine = None
        self._clock = clock
        # Delta cursors: each beat ships only samples recorded since
        # the previous one (heartbeats stay O(step work), never
        # O(lifetime)).
        self._ft_cursor = 0
        self._pt_cursor = 0
        # Direct-migration bulk plane (docs/serving.md "Direct
        # migration"): a second listener peers dial to stream KV pages
        # point-to-point, served on daemon threads. The engine is
        # single-threaded by design, so EVERY engine touch — router
        # verbs and peer streams alike — serializes on this lock
        # (per-worker, so concurrent cross-worker streams can never
        # form a lock cycle: nobody holds their own lock while waiting
        # on a peer's).
        self._lock = threading.RLock()
        self._peer_host = peer_host
        self._peer_lsock = None
        self.peer_port = 0
        # Manifest epochs ever begun here: a replayed epoch (a retried
        # partial stream) is refused — each migration attempt gets a
        # fresh epoch from the router, so stale partials can neither
        # resume nor double-inject.
        self._peer_epochs: set = set()
        # Outbound bulk connections, keyed by (host, port) and reused
        # across migrations — the dial handshake would otherwise
        # dominate small moves. Only the dispatch thread touches this
        # (migrate_to / shutdown run on the router's serialized verb
        # loop). A conn that fails mid-stream is dropped, not retried.
        self._peer_conns: Dict[Any, RpcConn] = {}

    # -- handlers ----------------------------------------------------

    def configure(self, model_cfg, serve_cfg, seed, instance,
                  kv_codec=0):
        """(Re)build the engine. A second configure replaces the
        engine with a fresh one (same process, same jit cache via the
        ``make_serve_fns`` memo): a cold fleet without a respawn.
        ``kv_codec`` sets the span codec for THIS side's replies (the
        export path's K/V pages)."""
        self.engine = _build_engine(model_cfg, serve_cfg, int(seed),
                                    str(instance))
        self.conn.codec = int(kv_codec)
        self._ft_cursor = self._pt_cursor = 0
        self._ensure_peer_listener()
        return {"n_blocks": self.engine.allocator.n_blocks,
                "block_size": self.engine.cfg.block_size,
                "pid": os.getpid(),
                "peer_port": self.peer_port,
                "beat": self._beat()}

    def _require_engine(self):
        if self.engine is None:
            raise RuntimeError("worker not configured yet")
        return self.engine

    def _result_to_wire(self, res, now: float) -> Dict[str, Any]:
        def age(t):
            return None if t is None else now - t

        return {
            "rid": res.rid, "status": res.status,
            "http_status": res.http_status, "tokens": list(res.tokens),
            "n_prompt": res.n_prompt,
            "age_submitted": age(res.submitted_at),
            "age_first_token": age(res.first_token_at),
            "age_finished": age(res.finished_at),
            "reason": res.reason, "deadline_class": res.deadline_class,
            "retry_after_s": res.retry_after_s,
        }

    def _beat(self) -> Dict[str, Any]:
        eng = self._require_engine()
        now = self._clock()
        m = eng.metrics
        ft = [float(x) for x in m.first_token_s[self._ft_cursor:]]
        pt = [float(x) for x in m.per_token_s[self._pt_cursor:]]
        self._ft_cursor += len(ft)
        self._pt_cursor += len(pt)
        # DRAIN finished results into the beat (pop, don't copy): the
        # router is the only consumer — it caches them its side and
        # never re-queries — so shipping is exactly-once by
        # construction, each beat costs O(newly finished), and a
        # long-lived worker's result map stays bounded instead of
        # accumulating every token list it ever served.
        results = {}
        for rid in list(eng._results):
            results[rid] = self._result_to_wire(eng._results.pop(rid),
                                                now)
        return {
            "pending": eng.pending,
            "kv_blocks_free": eng.allocator.n_free,
            "snap": m.snapshot(),
            "ft": ft, "pt": pt,
            "results": results,
            # This worker's clock at beat time: the router brackets
            # the heartbeat RPC and estimates the cross-process clock
            # offset from the RTT midpoint (docs/observability.md
            # "One timebase").
            "now": now,
        }

    def heartbeat(self):
        return self._beat()

    def step(self):
        eng = self._require_engine()
        if eng.pending:
            eng.step()
        return self._beat()

    def admission_snapshot(self):
        return self._require_engine().admission_snapshot()

    def cached_chain_len(self, chain):
        return self._require_engine().cached_chain_len(
            [bytes(c) for c in chain])

    def submit(self, prompt, max_new_tokens=None, deadline_in=None,
               deadline_class=0, prefill_only=False, chain=None):
        eng = self._require_engine()
        deadline = (None if deadline_in is None
                    else self._clock() + float(deadline_in))
        return eng.submit(
            [int(t) for t in prompt], max_new_tokens=max_new_tokens,
            deadline=deadline, deadline_class=int(deadline_class),
            prefill_only=bool(prefill_only),
            chain=[bytes(c) for c in chain] if chain is not None
            else None,
            # The distributed trace id rides the v2 frame header, not
            # the payload — the recv loop parked it on the conn.
            trace_id=self.conn.last_trace_id)

    def withdraw(self, rid):
        return self._require_engine().withdraw(int(rid))

    def handoff_ready(self):
        return self._require_engine().handoff_ready()

    def export_prefilled(self, rid):
        eng = self._require_engine()
        return handoff_to_wire(eng.export_prefilled(int(rid)),
                               self._clock())

    def inject_prefilled(self, wire_handoff):
        eng = self._require_engine()
        return eng.inject_prefilled(
            handoff_from_wire(wire_handoff, self._clock()))

    def running_exportable(self):
        return self._require_engine().running_exportable()

    def export_running(self, rid):
        eng = self._require_engine()
        return handoff_to_wire(eng.export_running(int(rid)),
                               self._clock())

    def export_trace(self):
        """This replica's chrome-trace events plus the timebase anchor
        (``trace_metadata``) — the router's ``export_fleet_trace``
        collects one of these per worker and stamps its RTT-estimated
        clock offset into the metadata so ``bin/hvd-trace merge`` can
        put every span on the router's clock."""
        m = self._require_engine().metrics
        return {"events": list(m._events),
                "meta": m.trace_metadata(worker_pid=os.getpid())}

    def shutdown(self):
        if self._peer_lsock is not None:
            try:
                self._peer_lsock.close()
            except OSError:
                pass
            self._peer_lsock = None
        for conn in self._peer_conns.values():
            conn.close()
        self._peer_conns.clear()
        return {"pid": os.getpid()}

    # -- direct migration (worker <-> worker bulk plane) --------------

    def _ensure_peer_listener(self) -> None:
        """Start the bulk listener peers stream KV pages to (lazy, on
        first configure — a worker that never joins a fleet binds
        nothing). Failure to bind degrades cleanly: ``peer_port``
        stays 0 and the router keeps this replica on the relayed
        path."""
        if self._peer_lsock is not None:
            return
        import socket

        try:
            ls = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            ls.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            ls.bind((self._peer_host, 0))
            ls.listen(8)
        except OSError:
            self.peer_port = 0
            return
        self._peer_lsock = ls
        self.peer_port = ls.getsockname()[1]
        threading.Thread(target=self._peer_accept_loop, args=(ls,),
                         daemon=True).start()

    def _peer_accept_loop(self, lsock) -> None:
        import socket

        while True:
            try:
                sock, _addr = lsock.accept()
            except OSError:
                return   # listener closed (shutdown)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._serve_peer, args=(sock,),
                             daemon=True).start()

    def _serve_peer(self, sock) -> None:
        """One inbound page stream: ``peer_begin`` (manifest; reserves
        blocks), N x ``peer_chunk`` (scatter), ``peer_commit``
        (materialize, reply the new rid). The staging token is
        CONNECTION-LOCAL: if the stream dies before commit — source
        SIGKILLed mid-transfer, reset, anything — the finally aborts
        the staged inject and the partial pages are discarded, so the
        target never holds a half sequence (the router's exactly-once
        requeue handles the request side)."""
        conn = RpcConn(sock)
        state: Dict[str, Any] = {"token": None}

        def peer_begin(epoch, meta):
            with self._lock:
                eng = self._require_engine()
                if int(epoch) in self._peer_epochs:
                    raise ValueError(
                        f"migration manifest epoch {epoch} already "
                        "seen — stale partial stream replayed")
                self._peer_epochs.add(int(epoch))
                state["token"] = eng.inject_begin(
                    handoff_meta_from_wire(meta, self._clock()))
            return True

        def peer_chunk(k_pages, v_pages):
            with self._lock:
                return self._require_engine().inject_chunk(
                    state["token"], k_pages, v_pages)

        def peer_commit():
            with self._lock:
                rid = self._require_engine().inject_commit(
                    state["token"])
            state["token"] = None
            return rid

        try:
            serve_connection(conn, {
                "peer_begin": peer_begin,
                "peer_chunk": peer_chunk,
                "peer_commit": peer_commit,
            })
        finally:
            token = state["token"]
            if token is not None:
                with self._lock:
                    try:
                        if self.engine is not None:
                            self.engine.inject_abort(token)
                    except Exception:
                        pass
            conn.close()

    def _peer_conn(self, host, port) -> Optional[RpcConn]:
        """Cached outbound bulk connection, dialed on first use —
        reused across migrations to the same peer (the TCP handshake
        would otherwise dominate small moves). ``None`` when the dial
        fails: the caller reports ``dial_failed`` and the router keeps
        the relayed path. A cached conn that dies mid-stream is
        dropped by :meth:`migrate_to`, never retried here."""
        import socket

        key = (str(host), int(port))
        conn = self._peer_conns.get(key)
        if conn is not None:
            return conn
        try:
            psock = socket.create_connection(key, timeout=30.0)
        except OSError:
            return None
        psock.settimeout(None)
        psock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn = RpcConn(psock)
        self._peer_conns[key] = conn
        return conn

    def migrate_to(self, kind, erid, host, port, chunk_pages, epoch):
        """Router control frame of the direct plane: export ``erid``
        and stream its pages straight to the peer worker at ``(host,
        port)`` — the router never touches the bulk bytes. Dial-first:
        a failed dial returns ``dial_failed`` with the sequence
        untouched (router falls back to relayed); a stream that dies
        AFTER export returns ``failed`` (pages are gone on both sides
        — router requeues the request, the exactly-once path). The
        engine lock is held only for the export; the wire streaming
        runs lock-free off the exported copies.

        The chunk stream is PIPELINED: begin + chunk frames are
        written without waiting for replies (at most ``_PEER_WINDOW``
        outstanding — replies are tiny, the window only bounds the
        reply backlog so a stalled target can never deadlock the
        socket buffers against us), and only ``peer_commit`` is a
        full round trip. With the cached dial this makes a move cost
        ~one traversal of the pages plus one RTT — the whole claim of
        the direct plane over the relayed two-traversal path."""
        eng = self._require_engine()
        if kind not in ("prefilled", "running"):
            raise ValueError(f"unknown migration kind {kind!r}")
        t0 = self._clock()
        peer = self._peer_conn(host, port)
        if peer is None:
            return {"status": "dial_failed",
                    "error": f"dial {host}:{port} failed"}
        # The router conn's codec is already the native id: the bulk
        # stream ships pages under the same wire codec the relayed
        # export would have. Byte counters are per-conn cumulative, so
        # this move's contribution is a delta.
        peer.codec = int(self.conn.codec)
        raw0, wire0 = peer.span_raw_bytes, peer.span_wire_bytes
        with self._lock:
            h = (eng.export_prefilled(int(erid))
                 if kind == "prefilled"
                 else eng.export_running(int(erid)))
        try:
            pending = 1
            peer.call_begin("peer_begin", epoch=int(epoch),
                            meta=handoff_meta_to_wire(h, self._clock()))
            for lo, hi in page_chunks(h.n_pages, int(chunk_pages)):
                peer.call_begin(
                    "peer_chunk",
                    np.ascontiguousarray(h.k_pages[:, lo:hi]),
                    np.ascontiguousarray(h.v_pages[:, lo:hi]))
                pending += 1
                while pending > _PEER_WINDOW:
                    peer.call_finish()
                    pending -= 1
            while pending:
                peer.call_finish()
                pending -= 1
            new_erid = int(peer.call("peer_commit"))
        except Exception as e:   # noqa: BLE001 — stream died mid-move
            self._peer_conns.pop((str(host), int(port)), None)
            peer.close()
            return {"status": "failed",
                    "error": f"{type(e).__name__}: {e}"}
        return {"status": "ok", "erid": new_erid,
                "raw_bytes": peer.span_raw_bytes - raw0,
                "wire_bytes": peer.span_wire_bytes - wire0,
                "ms": (self._clock() - t0) * 1e3}

    # -- loop --------------------------------------------------------

    def handlers(self) -> Dict[str, Any]:
        def locked(fn):
            def call(*args, **kwargs):
                with self._lock:
                    return fn(*args, **kwargs)
            return call

        out = {
            "configure": self.configure,
            "heartbeat": self.heartbeat,
            "step": self.step,
            "admission_snapshot": self.admission_snapshot,
            "cached_chain_len": self.cached_chain_len,
            "submit": self.submit,
            "withdraw": self.withdraw,
            "handoff_ready": self.handoff_ready,
            "export_prefilled": self.export_prefilled,
            "inject_prefilled": self.inject_prefilled,
            "running_exportable": self.running_exportable,
            "export_running": self.export_running,
            "export_trace": self.export_trace,
            "shutdown": self.shutdown,
        }
        # Peer streams touch the same engine from their own threads,
        # so every router verb serializes on the worker lock —
        # EXCEPT migrate_to, which locks only its export internally
        # (holding the lock across the wire stream would stall peer
        # injects for the whole transfer for no correctness gain).
        out = {m: locked(fn) for m, fn in out.items()}
        out["migrate_to"] = self.migrate_to
        out["__closing__"] = ("shutdown",)
        return out

    def serve(self) -> None:
        serve_connection(self.conn, self.handlers())


def main(argv: Optional[List[str]] = None) -> int:
    import socket

    from horovod_tpu.common.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(
        description="horovod_tpu serve worker: one ServeEngine replica "
                    "behind the fleet RPC seam (see docs/serving.md)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address (default loopback; the RPC "
                         "channel is unauthenticated — keep it on a "
                         "trusted network)")
    ap.add_argument("--port", type=int, default=0,
                    help="listen port (0 = ephemeral, announced on "
                         "stdout)")
    args = ap.parse_args(argv)

    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind((args.host, args.port))
    lsock.listen(1)
    port = lsock.getsockname()[1]
    print(f"{WORKER_READY_PREFIX} port={port} pid={os.getpid()}",
          flush=True)
    sock, _addr = lsock.accept()
    lsock.close()
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    ReplicaWorker(RpcConn(sock), peer_host=args.host).serve()
    return 0


if __name__ == "__main__":
    sys.exit(main())
