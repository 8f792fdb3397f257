"""RPC plane for the cross-process serving fleet.

The PR 8 router talks to replicas through a narrow seam
(``admission_snapshot`` / ``submit`` / ``withdraw`` /
``export_prefilled`` + ``inject_prefilled`` plus ``step``/``drain``)
that was designed to be lifted to RPC. This module lifts it: a
length-prefixed, versioned framing over the native vectored transport
(the ``hvd_tcp_sendv``/``hvd_tcp_recvv`` ctypes surface from PR 10 —
the same syscall paths the collective data plane runs), a small
struct-packed value codec (msgpack-free: tagged scalars/containers
inline in the frame, numpy tensors as raw spans AFTER the frame so
bulk K/V pages ride one ``SendV`` span list and land via ``RecvV``
directly in their destination buffers), and the client tier the
router consumes: :class:`RpcConn` (one blocking request/response
channel), :class:`RemoteReplica` (the engine seam re-exposed over a
connection — the router treats it identically to an in-process
``ServeEngine``), and :func:`spawn_worker` (launch + connect a
``horovod_tpu.serve.worker`` process).

Wire layout of one message::

    [u64 frame_len][frame: u32 magic | u16 version | u16 n_spans |
                    u64 trace_id | packed body]
    [span 0 bytes]...[span n-1 bytes]

The ``trace_id`` header field (protocol v2) carries the distributed
tracing context of :mod:`horovod_tpu.serve.trace`: the router stamps
the request's trace id on the frame that places it (``submit`` /
``inject_prefilled``), the worker reads it off the header
(:attr:`RpcConn.last_trace_id`) and tags its engine spans. 0 = no
trace context (the overwhelmingly common frame).

The body is the request/response value tree; every numpy array in the
tree is replaced by a struct-packed descriptor ``(codec, dtype, shape,
wire_bytes)`` and its bytes shipped as span ``i`` in tree order. The
whole message goes out as ONE vectored send (prefix, frame, and all
spans in a single ``SendV`` span list — the framing is invisible to
iovec boundaries, exactly the PR 10 contract), and the receiver drains
every span with ONE ``RecvV`` straight into the freshly-allocated
destination arrays: no intermediate concatenation buffer on either
side.

**KV-page compression.** A span whose source array is float32 and at
least :data:`SPAN_CODEC_MIN_ELEMS` elements long may be encoded with
the PR 9 wire codecs (``bf16``/``fp16`` — the cast codecs; int8 needs
error-feedback state that has no meaning for one-shot page migration)
via the native ``hvd_wire_encode``/``hvd_wire_decode`` kernels: bf16
halves migration bytes, and the decode is the same bitwise-pinned
multiply-free cast the TCP collective plane ships, so a compressed
handoff is deterministic (encode→decode is exactly the numpy
bf16-roundtrip, pinned by tests/test_rpc.py). The codec rides the
span descriptor, so the receiver needs no configuration.

Versioning: :data:`RPC_PROTOCOL_VERSION` is single-sourced HERE (the
same discipline as the ``kWireVersion*`` pins in ``basics.py`` —
``tools/lint`` enforces that no other module redefines it) and checked
on every received frame; a mismatch raises :class:`RpcProtocolError`
before any body parsing happens.

No jax import at module scope: the framing tier is importable (and
unit-testable over socketpairs) without paying the engine's
dependencies.
"""

from __future__ import annotations

import ctypes
import dataclasses
import os
import struct
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from horovod_tpu.common.basics import dtype_id, get_lib, np_dtype

#: RPC protocol version, checked on every frame. Single definition
#: site (lint rule ``abi-literal`` treats it like the wire-version
#: pins): bump on ANY change to the frame header, the value-codec
#: tags, or the span descriptor layout.
#: v2: the frame header grew a u64 ``trace_id`` after ``n_spans``
#: (distributed request tracing, serve/trace.py) — same magic, same
#: leading fields, so a v1 peer is detected and named before the new
#: field is ever parsed.
RPC_PROTOCOL_VERSION = 2

#: Frame magic ("HRPC", little-endian).
RPC_MAGIC = 0x43505248

#: Sanity cap on one frame's byte length (the body only — tensor spans
#: ride outside the frame, so frames stay small; a corrupt or
#: misaligned length prefix fails here instead of allocating garbage).
MAX_FRAME_BYTES = 64 << 20

#: Below this element count a float32 array ships raw even when a span
#: codec is configured: the encode dispatch costs more than it saves.
SPAN_CODEC_MIN_ELEMS = 256

# Native WireCodec ids accepted for span encoding (codec.h; the int8
# codec carries error-feedback semantics that make no sense for
# one-shot page migration, so it is rejected at configuration time).
_SPAN_CODECS = {"none": 0, "bf16": 1, "fp16": 2}


class RpcError(RuntimeError):
    """Base class for RPC-plane failures."""


class RpcConnectionError(RpcError):
    """The peer is gone (EOF, reset, timeout): the router's
    dead-worker signal. Any call that raises this leaves the
    connection unusable."""


class RpcProtocolError(RpcError):
    """The peer speaks a different protocol (bad magic or version
    mismatch) — fail loudly before parsing anything."""


class RpcRemoteError(RpcError):
    """A remote handler raised an exception type this side cannot
    reconstruct; carries the remote type name and message."""

    def __init__(self, exc_type: str, msg: str,
                 fields: Optional[Dict[str, Any]] = None):
        super().__init__(f"{exc_type}: {msg}")
        self.exc_type = exc_type
        self.fields = fields or {}


def span_codec_id(name) -> int:
    """Map a KV-handoff compression spelling (None / "bf16" / "fp16" /
    a ``hvd.Compression`` member) to the native span codec id."""
    if name is None:
        return 0
    wire = getattr(name, "wire_codec", None)
    if wire is not None:          # a Compression member
        name = {0: "none", 1: "bf16", 2: "fp16", 3: "int8"}.get(int(wire))
    try:
        return _SPAN_CODECS[str(name)]
    except KeyError:
        raise ValueError(
            f"unsupported KV handoff compression {name!r}; want one of "
            f"{sorted(_SPAN_CODECS)} (int8 needs error-feedback state "
            "that one-shot page migration has nowhere to keep)") from None


# ---------------------------------------------------------------------------
# Value codec: tagged, struct-packed, msgpack-free.
# ---------------------------------------------------------------------------

_T_NONE, _T_TRUE, _T_FALSE, _T_INT, _T_FLOAT = 0, 1, 2, 3, 4
_T_BYTES, _T_STR, _T_LIST, _T_DICT, _T_ARRAY = 5, 6, 7, 8, 9
_T_U64 = 10  # ints in [2**63, 2**64): 64-bit ids (FNV-1a trace ids)


class _ArrayStub:
    """Placeholder for a tensor span while its bytes are in flight."""

    __slots__ = ("codec", "dtype", "shape", "wire_bytes", "buf", "arr")

    def __init__(self, codec, dtype, shape, wire_bytes):
        self.codec = codec
        self.dtype = dtype
        self.shape = shape
        self.wire_bytes = wire_bytes
        if codec:
            # Validate the declared span size against what the codec
            # REQUIRES for this shape before the native decode runs —
            # a short buffer would otherwise be an out-of-bounds read
            # inside hvd_wire_decode, not a clean protocol error.
            elems = int(np.prod(shape, dtype=np.int64)) if shape else 1
            want = int(get_lib().hvd_wire_encoded_bytes(codec, elems))
            if wire_bytes != want:
                raise RpcProtocolError(
                    f"codec-{codec} span declares {wire_bytes} wire "
                    f"bytes but shape {shape} needs {want}")
            # Encoded payload lands here; decoded after the RecvV.
            self.buf = np.empty(wire_bytes, np.uint8)
            self.arr = None
        else:
            # Raw payload lands DIRECTLY in the destination array.
            self.arr = np.empty(shape, dtype)
            self.buf = self.arr
            if self.arr.nbytes != wire_bytes:
                raise RpcProtocolError(
                    f"span byte count {wire_bytes} != {self.arr.nbytes} "
                    f"for shape {shape} dtype {dtype}")

    def resolve(self, lib) -> np.ndarray:
        if self.codec:
            out = np.empty(self.shape, np.float32)
            lib.hvd_wire_decode(
                self.codec,
                ctypes.c_void_p(self.buf.ctypes.data), out.size,
                ctypes.c_void_p(out.ctypes.data))
            self.arr = out
        return self.arr


def _pack_value(obj, out: List[bytes],
                spans: List[Tuple[np.ndarray, int]], codec: int) -> None:
    if obj is None:
        out.append(struct.pack("<B", _T_NONE))
    elif obj is True:
        out.append(struct.pack("<B", _T_TRUE))
    elif obj is False:
        out.append(struct.pack("<B", _T_FALSE))
    elif isinstance(obj, (int, np.integer)):
        v = int(obj)
        if -(1 << 63) <= v < (1 << 63):
            out.append(struct.pack("<Bq", _T_INT, v))
        elif (1 << 63) <= v < (1 << 64):
            out.append(struct.pack("<BQ", _T_U64, v))
        else:
            raise TypeError(
                f"rpc value codec cannot marshal {v}: wider than 64 bits")
    elif isinstance(obj, (float, np.floating)):
        out.append(struct.pack("<Bd", _T_FLOAT, float(obj)))
    elif isinstance(obj, bytes):
        out.append(struct.pack("<BI", _T_BYTES, len(obj)))
        out.append(obj)
    elif isinstance(obj, str):
        b = obj.encode("utf-8")
        out.append(struct.pack("<BI", _T_STR, len(b)))
        out.append(b)
    elif isinstance(obj, np.ndarray):
        _pack_array(obj, out, spans, codec)
    elif isinstance(obj, (list, tuple)):
        out.append(struct.pack("<BI", _T_LIST, len(obj)))
        for v in obj:
            _pack_value(v, out, spans, codec)
    elif isinstance(obj, dict):
        out.append(struct.pack("<BI", _T_DICT, len(obj)))
        for k, v in obj.items():
            _pack_value(k, out, spans, codec)
            _pack_value(v, out, spans, codec)
    else:
        raise TypeError(
            f"rpc value codec cannot marshal {type(obj).__name__}; "
            "use scalars, bytes, str, lists, dicts, or numpy arrays")


def _pack_array(a: np.ndarray, out: List[bytes],
                spans: List[Tuple[np.ndarray, int]], codec: int) -> None:
    a = np.asarray(a)
    if not a.flags["C_CONTIGUOUS"]:
        # NOT ascontiguousarray: that helper promotes 0-d to 1-d and
        # would silently change the echoed shape.
        a = np.ascontiguousarray(a).reshape(a.shape)
    use_codec = (codec != 0 and a.dtype == np.float32
                 and a.size >= SPAN_CODEC_MIN_ELEMS)
    if use_codec:
        lib = get_lib()
        wire_n = int(lib.hvd_wire_encoded_bytes(codec, a.size))
        payload = np.empty(wire_n, np.uint8)
        lib.hvd_wire_encode(
            codec, ctypes.c_void_p(a.ctypes.data), a.size,
            ctypes.c_void_p(payload.ctypes.data), None)
        cid = codec
    else:
        payload, cid = a, 0
    out.append(struct.pack("<BBB", _T_ARRAY, cid, dtype_id(a.dtype)))
    out.append(struct.pack("<B", a.ndim))
    out.append(struct.pack(f"<{a.ndim}q", *a.shape))
    out.append(struct.pack("<Q", payload.nbytes))
    spans.append((payload, a.nbytes))


class _Reader:
    __slots__ = ("buf", "pos")

    def __init__(self, buf):
        self.buf = buf
        self.pos = 0

    def take(self, fmt):
        vals = struct.unpack_from(fmt, self.buf, self.pos)
        self.pos += struct.calcsize(fmt)
        return vals

    def take_bytes(self, n):
        b = bytes(self.buf[self.pos:self.pos + n])
        if len(b) != n:
            raise RpcProtocolError("truncated frame body")
        self.pos += n
        return b


def _unpack_value(r: _Reader, stubs: List[_ArrayStub]):
    (tag,) = r.take("<B")
    if tag == _T_NONE:
        return None
    if tag == _T_TRUE:
        return True
    if tag == _T_FALSE:
        return False
    if tag == _T_INT:
        return r.take("<q")[0]
    if tag == _T_U64:
        return r.take("<Q")[0]
    if tag == _T_FLOAT:
        return r.take("<d")[0]
    if tag == _T_BYTES:
        return r.take_bytes(r.take("<I")[0])
    if tag == _T_STR:
        return r.take_bytes(r.take("<I")[0]).decode("utf-8")
    if tag == _T_LIST:
        (n,) = r.take("<I")
        return [_unpack_value(r, stubs) for _ in range(n)]
    if tag == _T_DICT:
        (n,) = r.take("<I")
        out = {}
        for _ in range(n):
            k = _unpack_value(r, stubs)
            out[k] = _unpack_value(r, stubs)
        return out
    if tag == _T_ARRAY:
        cid, did = r.take("<BB")
        (ndim,) = r.take("<B")
        shape = r.take(f"<{ndim}q") if ndim else ()
        (wire_bytes,) = r.take("<Q")
        stub = _ArrayStub(cid, np_dtype(did), tuple(shape), wire_bytes)
        stubs.append(stub)
        return stub
    raise RpcProtocolError(f"unknown value tag {tag}")


def _resolve_stubs(obj, lib):
    if isinstance(obj, _ArrayStub):
        return obj.resolve(lib)
    if isinstance(obj, list):
        return [_resolve_stubs(v, lib) for v in obj]
    if isinstance(obj, dict):
        return {k: _resolve_stubs(v, lib) for k, v in obj.items()}
    return obj


# ---------------------------------------------------------------------------
# Connection
# ---------------------------------------------------------------------------

def _as_iovec(chunks):
    n = len(chunks)
    bufs = (ctypes.c_void_p * n)()
    lens = (ctypes.c_uint64 * n)()
    keep = []
    for i, c in enumerate(chunks):
        if isinstance(c, np.ndarray):
            bufs[i] = ctypes.c_void_p(c.ctypes.data)
            lens[i] = c.nbytes
        else:
            bufs[i] = ctypes.cast(ctypes.c_char_p(c), ctypes.c_void_p)
            lens[i] = len(c)
        keep.append(c)   # hold references across the syscall
    return bufs, lens, n, keep


class RpcConn:
    """One blocking request/response RPC channel over a connected
    socket, driven through the native vectored transport. Not
    thread-safe: one caller at a time (the router's step loop is
    single-threaded by design, and the worker serves one router).

    ``timeout`` (seconds) is applied to the raw fd via
    ``SO_RCVTIMEO``/``SO_SNDTIMEO`` — the native ``recvmsg`` loop then
    returns an error instead of blocking forever on a wedged peer,
    which surfaces here as :class:`RpcConnectionError` (the liveness
    signal).
    """

    def __init__(self, sock, timeout: Optional[float] = None,
                 codec=None):
        import socket as _socket

        self.sock = sock
        self.fd = sock.fileno()
        self.codec = span_codec_id(codec)
        self.alive = True
        # Byte accounting.
        self.msgs_sent = 0
        self.msgs_received = 0
        self.bytes_sent = 0
        self.bytes_received = 0
        self.span_raw_bytes = 0    # pre-codec payload bytes, both ways
        self.span_wire_bytes = 0   # on-the-wire span bytes, both ways
        # Distributed-tracing context (serve/trace.py): `trace_id` is
        # stamped on the NEXT outgoing frame's header and consumed;
        # `last_trace_id` is the most recent received frame's stamp
        # (0 = no context) — the worker's dispatch reads it to tag the
        # engine spans of the request the frame carried.
        self.trace_id = 0
        self.last_trace_id = 0
        if timeout is not None:
            self.set_timeout(timeout)

    def set_timeout(self, timeout: Optional[float]) -> None:
        """(Re)apply SO_RCVTIMEO/SO_SNDTIMEO on the raw fd — the
        native blocking syscalls honor these, unlike Python-level
        socket timeouts. None/0 = block forever."""
        import socket as _socket

        timeout = timeout or 0.0
        tv = struct.pack("<qq", int(timeout),
                         int((timeout % 1.0) * 1e6))
        self.sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVTIMEO, tv)
        self.sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDTIMEO, tv)

    # -- framing -----------------------------------------------------

    def send(self, obj) -> None:
        if not self.alive:
            raise RpcConnectionError("connection already closed")
        body: List[bytes] = []
        spans: List[Tuple[np.ndarray, int]] = []
        _pack_value(obj, body, spans, self.codec)
        trace_id, self.trace_id = self.trace_id, 0
        frame = struct.pack(
            "<IHHQ", RPC_MAGIC, RPC_PROTOCOL_VERSION, len(spans),
            trace_id & 0xFFFFFFFFFFFFFFFF) + b"".join(body)
        chunks = [struct.pack("<Q", len(frame)), frame]
        chunks += [p for p, _ in spans]
        bufs, lens, n, keep = _as_iovec(chunks)
        ok = get_lib().hvd_tcp_sendv(self.fd, bufs, lens, n)
        del keep
        if ok != 1:
            self._dead("send failed (peer gone?)")
        self.msgs_sent += 1
        self.bytes_sent += 8 + len(frame) + sum(p.nbytes for p, _ in spans)
        for p, raw in spans:
            self.span_wire_bytes += p.nbytes
            self.span_raw_bytes += raw

    def _recvv(self, chunks) -> None:
        bufs, lens, n, keep = _as_iovec(chunks)
        ok = get_lib().hvd_tcp_recvv(self.fd, bufs, lens, n)
        del keep
        if ok != 1:
            self._dead("recv failed (peer gone?)")

    def recv(self):
        if not self.alive:
            raise RpcConnectionError("connection already closed")
        hdr = bytearray(8)
        self._recvv([np.frombuffer(hdr, np.uint8)])
        (flen,) = struct.unpack("<Q", hdr)
        if not 8 <= flen <= MAX_FRAME_BYTES:
            self._dead(f"insane frame length {flen}")
        frame = np.empty(flen, np.uint8)
        self._recvv([frame])
        r = _Reader(frame.tobytes())
        magic, version, n_spans = r.take("<IHH")
        if magic != RPC_MAGIC:
            self.close()
            raise RpcProtocolError(
                f"bad frame magic {magic:#x} (expected {RPC_MAGIC:#x})")
        if version != RPC_PROTOCOL_VERSION:
            # Version check runs BEFORE the v2 trace_id field is
            # parsed: a v1 frame's header simply ends here, so skew is
            # a clean structured error naming both versions — never a
            # misparse of body bytes as a trace id.
            self.close()
            raise RpcProtocolError(
                f"peer speaks rpc protocol v{version}, this side "
                f"v{RPC_PROTOCOL_VERSION} — upgrade in lockstep")
        (self.last_trace_id,) = r.take("<Q")
        stubs: List[_ArrayStub] = []
        try:
            obj = _unpack_value(r, stubs)
        except struct.error as e:
            self.close()
            raise RpcProtocolError(f"corrupt frame body: {e}") from None
        except RpcProtocolError:
            # Unknown tag / bad span descriptor: the declared span
            # bytes were never drained, so the stream is desynced —
            # close rather than let the next recv parse span payload
            # as a length prefix.
            self.close()
            raise
        if len(stubs) != n_spans:
            self.close()
            raise RpcProtocolError(
                f"frame declares {n_spans} spans, body references "
                f"{len(stubs)}")
        if stubs:
            self._recvv([s.buf for s in stubs])
        lib = get_lib()
        obj = _resolve_stubs(obj, lib)
        self.msgs_received += 1
        self.bytes_received += 8 + flen + sum(s.wire_bytes for s in stubs)
        for s in stubs:
            self.span_wire_bytes += s.wire_bytes
            self.span_raw_bytes += (s.arr.nbytes if s.arr is not None
                                    else s.wire_bytes)
        return obj

    def _dead(self, why: str):
        self.close()
        raise RpcConnectionError(why)

    def close(self) -> None:
        if self.alive:
            self.alive = False
            try:
                self.sock.close()
            except OSError:
                pass

    # -- request/response --------------------------------------------

    def call_begin(self, method: str, *args, **kwargs) -> None:
        """Write one request frame WITHOUT waiting for the reply — the
        router's async step fan-out sends every busy worker's ``step``
        first, then collects. Must be paired with exactly one
        :meth:`call_finish` before any other call on this connection
        (the channel is strict request/response)."""
        self.send({"t": "call", "m": method, "a": list(args),
                   "k": kwargs})

    def call_finish(self):
        """Collect the reply of a :meth:`call_begin`. Remote
        exceptions of known types (ValueError, KeyError, the serve
        tier's structured rejections) re-raise natively; anything else
        raises :class:`RpcRemoteError`."""
        reply = self.recv()
        t = reply.get("t")
        if t == "ret":
            return reply.get("v")
        if t == "err":
            raise _rebuild_exception(reply)
        self.close()
        raise RpcProtocolError(f"unexpected reply type {t!r}")

    def call(self, method: str, *args, **kwargs):
        """One blocking RPC: ``call_begin`` + ``call_finish``."""
        self.call_begin(method, *args, **kwargs)
        return self.call_finish()


def _exception_to_wire(e: BaseException) -> Dict[str, Any]:
    fields = {}
    for f in ("reason", "queue_depth", "retry_after_s", "deadline_class",
              "http_status"):
        v = getattr(e, f, None)
        if isinstance(v, (int, float, str)) or v is None:
            if hasattr(e, f):
                fields[f] = v
    return {"t": "err", "e": type(e).__name__, "msg": str(e),
            "f": fields}


def _rebuild_exception(reply: Dict[str, Any]) -> BaseException:
    name = reply.get("e", "RuntimeError")
    msg = reply.get("msg", "")
    fields = reply.get("f") or {}
    if name == "ValueError":
        return ValueError(msg)
    if name == "KeyError":
        return KeyError(msg)
    if name == "TypeError":
        return TypeError(msg)
    if name in ("QueueFull", "FleetSaturated"):
        from horovod_tpu.serve.engine import QueueFull
        return QueueFull(msg, reason=fields.get("reason", "queue_full"),
                         queue_depth=int(fields.get("queue_depth") or 0),
                         retry_after_s=fields.get("retry_after_s"))
    if name == "OutOfBlocks":
        from horovod_tpu.serve.kv_cache import OutOfBlocks
        return OutOfBlocks(msg)
    return RpcRemoteError(name, msg, fields)


def serve_connection(conn: RpcConn, handlers: Dict[str, Any]) -> None:
    """Dispatch loop for the server side: read a call, run its
    handler, reply — until the peer disconnects or a handler named in
    ``handlers['__closing__']`` (e.g. ``shutdown``) has replied.
    Handler exceptions become structured error replies; the loop only
    exits on transport-level failure."""
    closing = set(handlers.get("__closing__", ()))
    while True:
        try:
            msg = conn.recv()
        except (RpcConnectionError, RpcProtocolError):
            return
        method = msg.get("m")
        fn = handlers.get(method)
        try:
            if fn is None:
                raise KeyError(f"unknown rpc method {method!r}")
            ret = fn(*(msg.get("a") or []), **(msg.get("k") or {}))
            reply = {"t": "ret", "v": ret}
        except RpcConnectionError:
            return
        except Exception as e:   # noqa: BLE001 — becomes a wire error
            reply = _exception_to_wire(e)
        try:
            conn.send(reply)
        except (RpcConnectionError, RpcProtocolError):
            return
        if method in closing:
            conn.close()
            return


# ---------------------------------------------------------------------------
# Worker lifecycle
# ---------------------------------------------------------------------------

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

#: Stdout announce line prefix the worker prints once it listens.
WORKER_READY_PREFIX = "HVD-SERVE-WORKER ready"


@dataclasses.dataclass
class WorkerHandle:
    """A spawned (or attached) serve worker: its RPC connection plus,
    for spawned workers, the process handle for kill/cleanup.
    ``host`` is where the worker's sockets live — peers dial its bulk
    migration listener at ``(host, peer_port-from-configure)``."""

    conn: RpcConn
    proc: Optional[subprocess.Popen] = None
    port: int = 0
    host: str = "127.0.0.1"

    def kill(self) -> None:
        """Hard-kill the worker (the failover tests' crash lever)."""
        self.conn.close()
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)

    def close(self) -> None:
        """Best-effort graceful stop: shutdown RPC, then reap."""
        if self.conn.alive:
            try:
                self.conn.call("shutdown")
            except RpcError:
                pass
            self.conn.close()
        if self.proc is not None:
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)


def spawn_worker(*, env: Optional[Dict[str, str]] = None,
                 start_timeout: float = 120.0,
                 rpc_timeout: Optional[float] = 300.0,
                 codec=None, via_bin: bool = False) -> WorkerHandle:
    """Launch ``python -m horovod_tpu.serve.worker`` on this host
    (``via_bin=True`` execs the ``bin/hvd-serve-worker`` console entry
    instead — same worker, the spelling a remote host would run), wait
    for its listen announce, connect, and return the handle. The child
    inherits the environment (so ``JAX_PLATFORMS`` etc. apply) with
    the repo root prepended to ``PYTHONPATH``."""
    import socket

    child_env = dict(os.environ)
    if env:
        child_env.update(env)
    child_env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT] + ([child_env["PYTHONPATH"]]
                        if child_env.get("PYTHONPATH") else []))
    child_env.setdefault("PYTHONUNBUFFERED", "1")
    cmd = ([sys.executable, os.path.join(_REPO_ROOT, "bin",
                                         "hvd-serve-worker")]
           if via_bin else
           [sys.executable, "-m", "horovod_tpu.serve.worker"])
    proc = subprocess.Popen(
        cmd + ["--port", "0"],
        stdout=subprocess.PIPE, text=True, env=child_env)
    import select

    port = None
    deadline = time.monotonic() + start_timeout
    while time.monotonic() < deadline:
        # select-gate the readline: a child that wedges SILENTLY
        # (alive, no output) must still honor start_timeout instead
        # of blocking this process on the pipe forever.
        ready, _, _ = select.select([proc.stdout], [], [],
                                    max(0.0, deadline - time.monotonic()))
        if not ready:
            break
        line = proc.stdout.readline()
        if not line:
            proc.kill()
            raise RpcConnectionError(
                f"serve worker exited during startup "
                f"(rc={proc.poll()})")
        if line.startswith(WORKER_READY_PREFIX):
            port = int(line.split("port=")[1].split()[0])
            break
    if port is None:
        proc.kill()
        raise RpcConnectionError(
            f"serve worker did not announce within {start_timeout}s")
    # Keep draining the child's stdout so a chatty jax can never fill
    # the pipe and wedge the worker mid-step.
    threading.Thread(target=_drain, args=(proc.stdout,),
                     daemon=True).start()
    sock = socket.create_connection(("127.0.0.1", port),
                                    timeout=start_timeout)
    sock.settimeout(None)   # native syscalls need a BLOCKING fd
    return WorkerHandle(conn=RpcConn(sock, timeout=rpc_timeout,
                                     codec=codec),
                        proc=proc, port=port)


def _drain(stream) -> None:
    try:
        for _ in stream:
            pass
    except (OSError, ValueError):
        pass


def connect_worker(host: str, port: int, *,
                   rpc_timeout: Optional[float] = 300.0,
                   codec=None) -> WorkerHandle:
    """Attach to an externally-launched worker (e.g. another host
    running ``bin/hvd-serve-worker``)."""
    import socket

    sock = socket.create_connection((host, port), timeout=rpc_timeout)
    sock.settimeout(None)
    return WorkerHandle(conn=RpcConn(sock, timeout=rpc_timeout,
                                     codec=codec), port=port, host=host)


# ---------------------------------------------------------------------------
# Config marshalling (router-side spelling of the worker's configure)
# ---------------------------------------------------------------------------

def model_cfg_to_wire(model_cfg) -> Dict[str, Any]:
    d = dataclasses.asdict(model_cfg)
    d["dtype"] = np.dtype(model_cfg.dtype).name
    return d


def serve_cfg_to_wire(serve_cfg) -> Dict[str, Any]:
    d = dataclasses.asdict(serve_cfg)
    d["cache_dtype"] = (None if serve_cfg.cache_dtype is None
                        else np.dtype(serve_cfg.cache_dtype).name)
    comp = serve_cfg.compression
    d["compression"] = (None if comp is None
                        else getattr(comp, "in_jit_codec", str(comp)))
    for k in ("batch_buckets", "prefill_buckets"):
        if d[k] is not None:
            d[k] = list(d[k])
    # Speculative sub-config: asdict() recursed into it with raw jnp
    # dtype objects the value codec can't ship — rebuild it in wire
    # shape (the draft model config marshals exactly like the target's).
    draft = serve_cfg.draft
    d["draft"] = (None if draft is None else {
        "model_cfg": model_cfg_to_wire(draft.model_cfg),
        "seed": int(draft.seed),
        "cache_dtype": (None if draft.cache_dtype is None
                        else np.dtype(draft.cache_dtype).name),
    })
    return d


def result_from_wire(d: Dict[str, Any], now: float):
    """Rebuild a RequestResult shipped as ages-relative-to-worker-now
    onto THIS process's clock (perf_counter epochs differ across
    processes; uniform re-anchoring preserves every latency delta)."""
    from horovod_tpu.serve.engine import RequestResult

    def at(age):
        return None if age is None else now - age

    return RequestResult(
        rid=int(d["rid"]), status=d["status"],
        http_status=int(d["http_status"]),
        tokens=[int(t) for t in d["tokens"]],
        n_prompt=int(d["n_prompt"]),
        submitted_at=at(d["age_submitted"]),
        first_token_at=at(d["age_first_token"]),
        finished_at=at(d["age_finished"]),
        reason=d["reason"], deadline_class=int(d["deadline_class"]),
        retry_after_s=d["retry_after_s"])


def handoff_from_wire(d: Dict[str, Any], now: float):
    from horovod_tpu.serve.engine import PrefillHandoff

    return PrefillHandoff(
        prompt=[int(t) for t in d["prompt"]],
        max_new=int(d["max_new"]),
        generated=[int(t) for t in d["generated"]],
        submitted_at=now - d["age_submitted"],
        first_token_at=now - d["age_first_token"],
        deadline_class=int(d["deadline_class"]),
        chain=[bytes(c) for c in d["chain"]],
        k_pages=d["k_pages"], v_pages=d["v_pages"],
        block_size=int(d["block_size"]),
        n_cached=int(d["n_cached"]),
        trace_id=int(d.get("trace_id") or 0))


def handoff_to_wire(h, now: float) -> Dict[str, Any]:
    return {
        "prompt": list(h.prompt), "max_new": h.max_new,
        "generated": list(h.generated),
        "age_submitted": now - h.submitted_at,
        "age_first_token": now - h.first_token_at,
        "deadline_class": h.deadline_class,
        "chain": list(h.chain),
        "k_pages": np.asarray(h.k_pages),
        "v_pages": np.asarray(h.v_pages),
        "block_size": h.block_size, "n_cached": h.n_cached,
        "trace_id": h.trace_id,
    }


def handoff_meta_to_wire(h, now: float) -> Dict[str, Any]:
    """The manifest half of a handoff — everything but the pages —
    for the direct-migration ``peer_begin`` frame. The pages follow as
    ``peer_chunk`` spans, so the target can reserve blocks (and fail
    fast on no-capacity) before a single bulk byte moves."""
    return {
        "prompt": list(h.prompt), "max_new": h.max_new,
        "generated": list(h.generated),
        "age_submitted": now - h.submitted_at,
        "age_first_token": now - h.first_token_at,
        "deadline_class": h.deadline_class,
        "chain": list(h.chain),
        "block_size": h.block_size, "n_cached": h.n_cached,
        "n_pages": h.n_pages,
        "trace_id": h.trace_id,
    }


def handoff_meta_from_wire(d: Dict[str, Any], now: float) -> Dict[str, Any]:
    """Inverse of :func:`handoff_meta_to_wire`, re-anchored onto this
    process's clock — the dict ``ServeEngine.inject_begin`` takes."""
    return {
        "prompt": [int(t) for t in d["prompt"]],
        "max_new": int(d["max_new"]),
        "generated": [int(t) for t in d["generated"]],
        "submitted_at": now - d["age_submitted"],
        "first_token_at": now - d["age_first_token"],
        "deadline_class": int(d["deadline_class"]),
        "chain": [bytes(c) for c in d["chain"]],
        "block_size": int(d["block_size"]),
        "n_cached": int(d["n_cached"]),
        "n_pages": int(d["n_pages"]),
        "trace_id": int(d.get("trace_id") or 0),
    }


# ---------------------------------------------------------------------------
# RemoteReplica: the engine seam over a connection
# ---------------------------------------------------------------------------

class _RemoteAllocatorView:
    """The slice of ``BlockAllocator`` the router reads, backed by the
    worker's configure reply and the freshest admission snapshot (the
    router always snapshots before it checks capacity, so the cached
    ``kv_blocks_free`` is current within one placement decision —
    exactly the in-process read pattern)."""

    def __init__(self, n_blocks: int, block_size: int):
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._free = n_blocks - 1

    def blocks_for_tokens(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def can_alloc(self, n: int) -> bool:
        return n <= self._free

    @property
    def n_free(self) -> int:
        return self._free


class RemoteReplicaMetrics:
    """Router-process view of a worker's ``ServeMetrics``: the
    heartbeat payload keeps a cached snapshot plus the delta-shipped
    latency samples, and registers with the shared Prometheus
    exposition so ONE scrape of the router process covers every worker
    process too (same ``serve_*{instance=...}`` series a local replica
    would emit)."""

    def __init__(self, instance: str):
        self.instance = instance
        self.first_token_s: List[float] = []
        self.per_token_s: List[float] = []
        self._snap: Dict[str, Any] = {}
        from horovod_tpu.metrics import register_exporter_weak
        register_exporter_weak(f"serve_remote_{id(self)}", self,
                               "prometheus")

    def update(self, snap: Dict[str, Any], first_token_s, per_token_s):
        from horovod_tpu.serve.metrics import MAX_SAMPLES
        self._snap = snap
        for dst, new in ((self.first_token_s, first_token_s),
                         (self.per_token_s, per_token_s)):
            room = MAX_SAMPLES - len(dst)
            if room > 0:
                dst.extend(float(x) for x in new[:room])

    def snapshot(self) -> Dict[str, Any]:
        return dict(self._snap)

    def prometheus(self) -> str:
        from horovod_tpu.metrics import render_gauges
        return render_gauges("serve", self.snapshot(),
                             labels={"instance": self.instance})


class RemoteReplica:
    """One serve worker process behind the engine seam. The router
    treats this object exactly like a ``ServeEngine`` — same methods,
    same semantics — with three RPC-shaped differences:

    * results/latency samples arrive batched on the ``step`` /
      ``heartbeat`` replies (one round trip per iteration, not one per
      request) and are re-anchored onto the router's clock;
    * ``metrics``/``allocator`` are cached views refreshed by those
      replies (the router always snapshots before acting, so the cache
      is current within a decision);
    * any transport failure raises :class:`RpcConnectionError`, the
      router's dead-worker signal.
    """

    remote = True

    def __init__(self, handle: WorkerHandle, model_cfg, serve_cfg, *,
                 seed: int, instance: str, clock=time.perf_counter,
                 trace=None):
        self._handle = handle
        self._conn = handle.conn
        self._clock = clock
        self.instance = instance
        # Router-side trace recorder (serve/trace.RouterTrace, None =
        # tracing off): placement RPCs record their wire time under
        # the request's trace id.
        self._trace = trace
        # Worker-clock offset estimation (docs/observability.md
        # "One timebase"): every heartbeat reply carries the worker's
        # `now`; this side brackets the RPC with t0/t1 and estimates
        # offset = worker_now - (t0+t1)/2 — the RTT-midpoint re-anchor
        # of the PR 11 age discipline, made persistent. The sample
        # with the smallest RTT seen so far wins (its midpoint bound
        # is tightest), so the estimate survives heartbeat gaps and
        # only ever improves.
        self.clock_offset = 0.0       # worker clock - router clock
        self.clock_rtt = float("inf")  # RTT of the winning sample
        ret = self._conn.call(
            "configure", model_cfg=model_cfg_to_wire(model_cfg),
            serve_cfg=serve_cfg_to_wire(serve_cfg), seed=int(seed),
            instance=instance, kv_codec=self._conn.codec)
        self.allocator = _RemoteAllocatorView(int(ret["n_blocks"]),
                                              int(ret["block_size"]))
        # Direct-migration dial target: the worker's bulk peer
        # listener (docs/serving.md "Direct migration"). 0 = the
        # worker has none; the router then stays on the relayed path.
        self.peer_host = handle.host
        self.peer_port = int(ret.get("peer_port") or 0)
        self.metrics = RemoteReplicaMetrics(instance)
        self._results: Dict[int, Any] = {}
        self._pending = False
        self.last_beat = -float("inf")
        self._absorb_beat(ret["beat"])

    # -- beat plumbing ----------------------------------------------

    def _absorb_beat(self, beat: Dict[str, Any],
                     t0: Optional[float] = None,
                     t1: Optional[float] = None) -> None:
        now = self._clock()
        self._pending = bool(beat["pending"])
        self.allocator._free = int(beat["kv_blocks_free"])
        self.metrics.update(beat["snap"], beat["ft"], beat["pt"])
        for erid, rd in beat["results"].items():
            self._results[int(erid)] = result_from_wire(rd, now)
        self.last_beat = now
        # Offset sample: only from calls the caller bracketed (the
        # cheap symmetric heartbeat — a step RPC's reply time includes
        # the worker's compute, which would skew the midpoint).
        if (t0 is not None and t1 is not None
                and beat.get("now") is not None):
            rtt = t1 - t0
            if rtt < self.clock_rtt:
                self.clock_rtt = rtt
                self.clock_offset = (float(beat["now"])
                                     - (t0 + t1) / 2.0)

    def heartbeat(self) -> None:
        """Liveness probe + metrics scrape + clock-offset sample in
        one round trip; raises :class:`RpcConnectionError` when the
        worker is gone."""
        t0 = self._clock()
        beat = self._conn.call("heartbeat")
        self._absorb_beat(beat, t0, self._clock())

    # -- the engine seam ---------------------------------------------

    @property
    def pending(self) -> bool:
        return self._pending

    def admission_snapshot(self) -> Dict[str, float]:
        snap = self._conn.call("admission_snapshot")
        self.allocator._free = int(snap["kv_blocks_free"])
        return snap

    def cached_chain_len(self, chain: Sequence[bytes]) -> int:
        if not chain:
            return 0
        return int(self._conn.call("cached_chain_len", list(chain)))

    def submit(self, prompt, max_new_tokens=None, deadline=None,
               deadline_class: int = 0, prefill_only: bool = False,
               chain=None, trace_id: int = 0) -> int:
        # Absolute deadlines are ROUTER-clock times; processes don't
        # share a perf_counter epoch, so the wire carries the time
        # REMAINING and the worker re-anchors onto its own clock.
        deadline_in = (None if deadline is None
                       else deadline - self._clock())
        # The trace id rides the NEXT frame's v2 header (not the
        # payload): the worker's dispatch reads it off the conn, so
        # every placement verb propagates identity the same way.
        self._conn.trace_id = trace_id
        t0 = self._clock()
        erid = self._conn.call(
            "submit", prompt=[int(t) for t in prompt],
            max_new_tokens=max_new_tokens, deadline_in=deadline_in,
            deadline_class=deadline_class, prefill_only=prefill_only,
            chain=list(chain) if chain is not None else None)
        if trace_id and self._trace is not None:
            self._trace.span("rpc:submit", t0, self._clock() - t0,
                             trace=trace_id, instance=self.instance)
        self._pending = True
        return int(erid)

    def withdraw(self, rid: int) -> bool:
        return bool(self._conn.call("withdraw", int(rid)))

    def step(self) -> None:
        self._absorb_beat(self._conn.call("step"))

    # -- async step fan-out (router._step_replicas) ------------------

    def step_begin(self) -> bool:
        """Fire the step request frame and return immediately; the
        worker computes its iteration while the router steps other
        replicas. MUST be paired with :meth:`step_finish` (and is —
        the router pairs them within one `_step_replicas`). Returns
        True so the router's `_guard` can distinguish success from a
        detected death."""
        self._conn.call_begin("step")
        return True

    def step_finish(self) -> None:
        """Collect and apply a :meth:`step_begin`'s beat reply."""
        self._absorb_beat(self._conn.call_finish())

    def result(self, rid: int):
        return self._results.get(rid)

    def handoff_ready(self) -> List[int]:
        return [int(r) for r in self._conn.call("handoff_ready")]

    def export_prefilled(self, rid: int):
        d = self._conn.call("export_prefilled", int(rid))
        return handoff_from_wire(d, self._clock())

    def inject_prefilled(self, h) -> int:
        # Tag the frame too: the handoff payload carries trace_id for
        # the engine, the header keeps the wire-level convention
        # uniform across placement verbs.
        self._conn.trace_id = getattr(h, "trace_id", 0)
        erid = self._conn.call("inject_prefilled",
                               handoff_to_wire(h, self._clock()))
        self._pending = True
        return int(erid)

    def export_trace(self) -> Dict[str, Any]:
        """This worker's chrome-trace events + timebase anchor, with
        the router's RTT-estimated clock offset stamped in (the merge
        key ``bin/hvd-trace`` uses to re-anchor worker spans onto the
        router clock)."""
        d = self._conn.call("export_trace")
        d["meta"]["instance"] = self.instance
        d["meta"]["clock_offset"] = self.clock_offset
        d["meta"]["clock_rtt"] = (None if self.clock_rtt == float("inf")
                                  else self.clock_rtt)
        return d

    def running_exportable(self) -> List[int]:
        return [int(r) for r in self._conn.call("running_exportable")]

    def export_running(self, rid: int):
        d = self._conn.call("export_running", int(rid))
        return handoff_from_wire(d, self._clock())

    # -- direct migration (docs/serving.md "Direct migration") -------

    def migrate_direct(self, erid: int, kind: str, host: str,
                       port: int, chunk_pages: int,
                       epoch: int) -> Dict[str, Any]:
        """Ask THIS worker (the source) to stream sequence ``erid``'s
        pages point-to-point to a peer worker's bulk listener — the
        control frame of the direct plane; the router never touches
        the pages. Returns the worker's status dict: ``ok`` (with the
        target-side erid and byte/latency accounting),
        ``dial_failed`` (sequence untouched — fall back to relayed),
        or ``failed`` (exported then lost — requeue the request)."""
        return self._conn.call(
            "migrate_to", kind=str(kind), erid=int(erid),
            host=str(host), port=int(port),
            chunk_pages=int(chunk_pages), epoch=int(epoch))

    def note_remote_inject(self) -> None:
        """A sequence landed on this worker OUTSIDE the router's
        connection (a peer-streamed inject): mark the cached pending
        flag so the step loop drives the worker before the next beat
        refreshes it."""
        self._pending = True

    # -- lifecycle ---------------------------------------------------

    @property
    def alive(self) -> bool:
        return self._conn.alive

    def mark_dead(self) -> None:
        self._conn.close()

    def shutdown(self) -> None:
        self._handle.close()
