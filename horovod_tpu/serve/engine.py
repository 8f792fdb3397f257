"""Continuous-batching inference engine.

The serving analog of the training runtime: one process drives the
whole mesh, and scheduling is **iteration-level** (Orca OSDI'22 /
vLLM): every :meth:`ServeEngine.step` retires sequences that finished
on the previous iteration, expires queued requests past their
deadline, admits new requests into the running batch (one prefill
each), then runs ONE decode iteration for everything active. New
requests join the running batch mid-flight and finished sequences
leave immediately — the batch never drains to admit, which is where
the throughput win over static batching comes from on mixed-length
traffic.

A decode iteration is **launched in one step and read in the next**
(PR 37). The sampled tokens stay on the device: the call in flight's
output array is its successor's ``tokens``, the positions advance by
one, and a sequence that ends by its ``max_new_tokens`` ends at a step
the host knows in advance, so step n+1 needs nothing the host reads
from step n. In the steady state a step launches call n+1, then reads
call n while n+1 runs, and the launch and the readback of a call cost
the device no idle time. Whatever needs the host's view of step n
first (a prefill, a smaller bucket, a queued request that waits for
the slot of a sequence about to end, a migration, the end of work)
reads the call in flight before it goes on ("drains",
:meth:`ServeEngine._drain`); nothing is configured, the engine decides
each step from what it observes.

Admission control is two-layered:

* **queue backpressure** — :meth:`submit` raises :class:`QueueFull`
  (503-style) once ``max_queue`` requests are waiting;
* **KV backpressure** — a request is admitted only when the block
  pool can reserve its worst case (prompt + max_new_tokens), so a
  running sequence can never hit out-of-blocks mid-decode (no
  preemption/swapping tier yet; the reservation is the simple-and-
  safe policy and `high_water` tells you how much it costs).

Two throughput levers sit on top of the paged layout:

* **prefix caching** (``ServeConfig.prefix_caching``) — admission
  walks the prompt's chained block hashes against the allocator's
  content index; every leading whole block already cached is mapped
  straight into the new sequence's block table (one refcount, zero
  FLOPs) and only the unmatched suffix is prefilled. Full prompt
  blocks are published back to the index after they are written, so
  a fleet of requests sharing a system prompt pays its prefill once.
* **chunked prefill** (``ServeConfig.prefill_chunk``) — a long
  suffix is split into block-aligned chunks processed across
  successive :meth:`ServeEngine.step` iterations, interleaved with
  decode, so one long prompt no longer monopolizes an iteration and
  spikes every in-flight sequence's per-token latency. A chunking
  sequence holds all its reserved blocks but does not enter the
  decode batch until its prefill completes.

Deadlines are absolute engine-clock times by which a request must be
*admitted* (first token scheduled); stale requests are rejected with a
**structured rejection** (machine-readable ``reason``, the request's
``deadline_class``, and a ``retry_after_s`` estimate derived from the
queue depth and the engine's recent retirement rate) rather than a
blanket 503 — and rather than burning prefill FLOPs on an answer
nobody is waiting for. The clock is injectable for tests.

Fleet hooks (used by :mod:`horovod_tpu.serve.router`, all cheap
host-side reads or bounded mutations — none of them step the engine;
the first three never touch the device, the handoff ones read the
decode call in flight first):

* :meth:`admission_snapshot` — occupancy / free KV blocks / queue
  depth, what a router polls to pick a replica;
* :meth:`cached_chain_len` — how many leading blocks of a prompt's
  hash chain this replica's content index already holds (the
  cache-affinity placement signal);
* :meth:`withdraw` — reclaim a still-queued request (replica drain);
* ``submit(..., prefill_only=True)`` + :meth:`handoff_ready` /
  :meth:`export_prefilled` / :meth:`inject_prefilled` — the
  disaggregated prefill/decode path: a prefill replica runs the
  prompt through the existing chunked-prefill machinery, parks the
  finished sequence, and the router moves its K/V pages (bitwise) to
  a decode replica's pool where decoding continues.

Determinism: FIFO admission, stable batch-slot assignment, greedy
argmax in-jit — the same submission order always yields bitwise the
same tokens, which the parity test pins.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from horovod_tpu.ops.paged_decode import key_block, ring_page
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import (
    NULL_SLOT, RING_KINDS, SLOT_KINDS, BlockAllocator, hash_chain,
    init_kv_cache, pick_bucket, ring_width,
)
from horovod_tpu.serve.metrics import ServeMetrics


class QueueFull(RuntimeError):
    """Admission-queue backpressure — shed load upstream. Carries the
    structured-rejection fields so a caller (or the fleet router) can
    tell its client *when* to retry instead of hammering a 503."""
    http_status = 503

    def __init__(self, msg: str, *, reason: str = "queue_full",
                 queue_depth: int = 0,
                 retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.reason = reason
        self.queue_depth = queue_depth
        self.retry_after_s = retry_after_s


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (model shape lives in ``TransformerConfig``)."""

    max_batch: int = 8           # decode batch slots
    max_queue: int = 64          # admission queue depth (then 503)
    block_size: int = 16         # KV tokens per block
    n_blocks: Optional[int] = None   # pool size; default = worst case
    max_prompt: int = 512        # longest admissible prompt
    max_new_tokens: int = 128    # per-request generation cap
    eos_id: Optional[int] = None
    # Shape buckets (None = powers-of-two menus). Fewer buckets = fewer
    # compiles; more buckets = less padding waste.
    batch_buckets: Optional[Tuple[int, ...]] = None
    prefill_buckets: Optional[Tuple[int, ...]] = None
    cache_dtype: Any = None      # default: model dtype
    # Map whole-block prompt prefixes out of the content-addressed
    # block cache instead of recomputing them (hit rate shows up in
    # metrics as prefix_cache_hit_rate). Off = every prompt pays full
    # prefill FLOPs, the pre-cache behavior.
    prefix_caching: bool = True
    # Max prefill tokens processed per engine step (block-aligned).
    # None = unbounded: every admitted request's whole suffix
    # prefills in its admission step (monolithic prefill). Set to
    # bound the prefill work one step can absorb, so long prompts
    # stream in across iterations interleaved with decode.
    prefill_chunk: Optional[int] = None
    # In-jit mesh compression for the decode/prefill programs (a
    # hvd.Compression member; None = uncompressed, bitwise the
    # pre-existing programs) — the serving face of the training
    # planes' one knob. See decode.make_serve_fns.
    compression: Any = None
    # Speculative decoding (serve/speculative.py): `draft` is the
    # sub-config naming the draft transformer (a
    # speculative.DraftConfig — model config + params seed + cache
    # dtype; it inherits THIS engine's block geometry), and `spec_k`
    # is how many tokens the draft proposes per scheduler iteration,
    # all verified in ONE chunked target step. Both set = speculation
    # on (greedy streams stay bitwise plain decode's); both unset =
    # plain decode, byte for byte the pre-speculative engine.
    draft: Any = None
    spec_k: int = 0


@dataclasses.dataclass
class RequestResult:
    rid: int
    status: str                  # "ok" | "expired" | "shed"
    http_status: int             # 200 | 503
    tokens: List[int]
    n_prompt: int
    submitted_at: float
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # Structured rejection (status != "ok"): machine-readable reason
    # ("deadline_expired" | "shed_low_class"), the request's deadline
    # class, and how long the client should back off — estimated from
    # the queue depth times the engine's recent retirement interval.
    reason: Optional[str] = None
    deadline_class: int = 0
    retry_after_s: Optional[float] = None
    # Engine-clock time of each token produced on THIS engine (the end
    # of the prefill or decode span it came out of): the gaps a client
    # sees. Not carried over RPC or a handoff, where ages travel, not
    # times; a handed-off sequence lists the tokens it produced here.
    token_times: List[float] = dataclasses.field(default_factory=list)
    # The batch slot whose rings and recurrent states the sequence held
    # on this engine (0: none; kv_cache.KVCache): where a test or a
    # check finds what the sequence left on the device.
    slot: int = 0
    # ... and the blocks its table named, in order, where the
    # configuration keeps a state by kind of layer (empty otherwise):
    # where a check finds the pages it left (an eva layer's summaries).
    # They are free again when the result exists: read before another
    # request is admitted.
    blocks: List[int] = dataclasses.field(default_factory=list)

    @property
    def first_token_latency_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at


@dataclasses.dataclass
class _Queued:
    rid: int
    prompt: List[int]
    max_new: int
    deadline: Optional[float]
    submitted_at: float
    chain: List[bytes]           # content-hash chain, hashed once at
    #                              submit (not per admission retry)
    deadline_class: int = 0
    prefill_only: bool = False   # park for handoff instead of decoding
    trace: int = 0               # distributed trace id (0 = unsampled)


@dataclasses.dataclass
class _Seq:
    rid: int
    prompt: List[int]
    max_new: int
    blocks: List[int]            # refs held: shared prefix + private
    table: np.ndarray            # [table_width] int32 physical block ids
    n_cached: int                # tokens currently in the KV cache
    generated: List[int]
    submitted_at: float
    chain: List[bytes]           # content-hash chain, one per full
    #                              prompt block (empty: caching off)
    registered: int              # prompt blocks published (or mapped
    #                              from the cache) so far
    first_token_at: Optional[float] = None
    last_prefill_tok: int = 0    # argmax of the newest chunk's last
    #                              real position; the first generated
    #                              token once prefill completes
    deadline_class: int = 0
    prefill_only: bool = False
    trace: int = 0               # distributed trace id (0 = unsampled)
    admitted_at: Optional[float] = None   # left the queue (engine clock)
    token_times: List[float] = dataclasses.field(default_factory=list)
    slot: int = 0                # its rings and recurrent states (a
    #                              configuration with a state by kind
    #                              of layer; kv_cache.KVCache)
    mapped: int = 0              # positions mapped from the prefix
    #                              cache that no prefill span has said

    @property
    def last_token(self) -> int:
        return self.generated[-1]

    def finished(self, eos_id: Optional[int]) -> bool:
        return (len(self.generated) >= self.max_new
                or (eos_id is not None and self.last_token == eos_id))


@dataclasses.dataclass
class PrefillHandoff:
    """A sequence packaged for another replica: its K/V pages as host
    copies plus the request state needed to continue decoding
    elsewhere. The pages are bitwise copies and the decode math is
    position-dependent only, so a handed-off sequence decodes to
    exactly the tokens it would have produced in place.

    Two producers share this shape: :meth:`ServeEngine.export_prefilled`
    (a completed prefill leaving a prefill-pool replica — ``n_cached``
    == prompt length, ``generated`` == the one prefill-emitted token)
    and :meth:`ServeEngine.export_running` (a mid-decode sequence
    leaving a draining replica — ``n_cached`` covers every token whose
    K/V is in the pages, ``generated`` everything emitted so far). The
    consumer is one :meth:`ServeEngine.inject_prefilled` either way.
    """

    prompt: List[int]
    max_new: int
    generated: List[int]         # tokens emitted so far (>= 1)
    submitted_at: float
    first_token_at: float
    deadline_class: int
    chain: List[bytes]           # content-hash chain (may be empty)
    k_pages: Any                 # [L, n_pages, bs, Hkv, Dh]
    v_pages: Any
    block_size: int
    n_cached: int                # tokens covered by the pages
    trace_id: int = 0            # distributed trace id (0 = unsampled)

    @property
    def n_pages(self) -> int:
        return int(self.k_pages.shape[1])


@dataclasses.dataclass
class _InFlight:
    """A decode call the device has and the host has not read yet.
    ``rows[i]`` is the sequence whose token comes out at row ``i`` of
    ``out``, or None: a padded row, the row of a sequence that left, or
    of one that the call before ended by ``eos_id`` (``held``: its
    token here is discarded, and it is retired once this call is
    read). A sequence keeps its row while calls follow one another
    without a read in between, so ``out`` is the next call's
    ``tokens`` as it is."""

    call: Any                    # metrics.DeviceCall
    out: Any                     # [bucket] int32, on the device
    rows: List[Optional["_Seq"]]
    positions: np.ndarray        # what the call was given, by row
    tables: np.ndarray
    slots: np.ndarray
    held: set = dataclasses.field(default_factory=set)    # of rids

    def discard(self, i: int) -> None:
        """Row ``i``'s sequence ended at the call before this one."""
        seq, self.rows[i] = self.rows[i], None
        self.held.add(seq.rid)
        args = self.call.args
        args["n_active"] -= 1
        if seq.trace:
            args["traces"].remove(seq.trace)
            if not args["traces"]:
                del args["traces"]


class RetireEma:
    """Inter-retirement interval EMA: the drain-rate signal behind
    every ``retry_after_s`` estimate. One implementation shared by
    the engine and the fleet router so the smoothing (0.8/0.2,
    first-observation seeding) can never diverge between tiers."""

    def __init__(self):
        self.value = 0.0
        self._last: Optional[float] = None

    def observe(self, now: float) -> None:
        if self._last is not None:
            dt = max(now - self._last, 0.0)
            self.value = (0.8 * self.value + 0.2 * dt
                          if self.value else dt)
        self._last = now

    def retry_after(self, queue_depth: int) -> float:
        """Back-off estimate: requests ahead x the recent
        inter-retirement interval. 0.0 before any retirement."""
        return round(queue_depth * self.value, 6)


def validate_request(serve_cfg: ServeConfig, model_cfg, n_pool_blocks: int,
                     prompt: List[int], max_new: int,
                     deadline_class: int) -> None:
    """Shared admission validation — ONE implementation for both the
    engine and the fleet router. The router accepts requests before
    any engine sees them; if its checks ever drifted looser than the
    engine's, an accepted request would blow ValueError out of a later
    placement step (popped from the queue, leaked without a result)
    instead of rejecting at submit."""
    if not prompt:
        raise ValueError("empty prompt")
    if len(prompt) > serve_cfg.max_prompt:
        raise ValueError(
            f"prompt length {len(prompt)} > max_prompt "
            f"{serve_cfg.max_prompt}")
    if not 1 <= max_new <= serve_cfg.max_new_tokens:
        raise ValueError(
            f"max_new_tokens {max_new} outside [1, "
            f"{serve_cfg.max_new_tokens}]")
    if len(prompt) + max_new > model_cfg.max_seq:
        raise ValueError(
            f"prompt+max_new {len(prompt) + max_new} > model max_seq "
            f"{model_cfg.max_seq}")
    if deadline_class < 0:
        raise ValueError(f"deadline_class {deadline_class} < 0")
    need = -(-(len(prompt) + max_new) // serve_cfg.block_size)
    if need > n_pool_blocks - 1:
        # Worst-case reservation exceeds the whole pool: admission
        # could never succeed and FIFO would starve every request
        # behind it — reject now, not never.
        raise ValueError(
            f"request needs {need} KV blocks worst-case but the pool "
            f"holds {n_pool_blocks - 1}; raise n_blocks or lower "
            "max_new_tokens")


def _pow2_menu(lo: int, hi: int) -> Tuple[int, ...]:
    out = []
    b = lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


class ServeEngine:
    def __init__(self, model_cfg, params, serve_cfg: Optional[ServeConfig]
                 = None, mesh: Optional[Any] = None,
                 clock=time.perf_counter,
                 instance: Optional[str] = None):
        cfg = serve_cfg or ServeConfig()
        if (cfg.draft is None) != (cfg.spec_k == 0) or cfg.spec_k < 0:
            raise ValueError(
                f"draft= and spec_k= go together (draft="
                f"{'set' if cfg.draft is not None else None}, spec_k="
                f"{cfg.spec_k}): set both for speculative decoding, "
                "neither for plain decode")
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.mesh = mesh
        self._params = params
        self._clock = clock

        bs = cfg.block_size
        # Prompt buckets are whole blocks (prefill writes pages).
        max_prompt_padded = -(-cfg.max_prompt // bs) * bs
        self._prefill_buckets = cfg.prefill_buckets or _pow2_menu(
            bs, max_prompt_padded)
        self._batch_buckets = cfg.batch_buckets or _pow2_menu(
            1, cfg.max_batch)
        self._table_width = -(-(max_prompt_padded + cfg.max_new_tokens) // bs)
        # Fail at construction, not mid-step after blocks are already
        # reserved: every admissible request must fit a bucket, and
        # every bucket's pages must fit the block table.
        if any(b % bs for b in self._prefill_buckets):
            raise ValueError(
                f"prefill_buckets {self._prefill_buckets} must be "
                f"multiples of block_size {bs}")
        if max(self._prefill_buckets) // bs > self._table_width:
            raise ValueError(
                f"largest prefill bucket {max(self._prefill_buckets)} "
                f"needs {max(self._prefill_buckets) // bs} blocks but "
                f"the block table holds {self._table_width}")
        pick_bucket(cfg.max_batch, self._batch_buckets)
        if cfg.prefill_chunk is None:
            pick_bucket(cfg.max_prompt, self._prefill_buckets)
        else:
            # Chunks must start block-aligned (the resume fn's page
            # writes are blockwise) and fit a bucket; no program longer
            # than a chunk ever runs, so no bucket need hold max_prompt.
            if cfg.prefill_chunk < bs or cfg.prefill_chunk % bs:
                raise ValueError(
                    f"prefill_chunk {cfg.prefill_chunk} must be a "
                    f"positive multiple of block_size {bs}")
            pick_bucket(cfg.prefill_chunk, self._prefill_buckets)
        # A state by kind of layer (a configuration with layers of
        # several kinds, or a chip's share of the experts): a sequence
        # then holds a batch slot's rings and recurrent states beside
        # its pages, and what is not built for that is refused here, by
        # name.
        self._slot_states = model_cfg.mixed
        if self._slot_states:
            # A prefix is shared as pages mapped into another block
            # table: what every layer keeps of a position then has to be
            # a page. A window layer's ring, a kda, mamba or lightning
            # layer's recurrent state and a conv layer's rows lie by
            # batch slot (kv_cache.SLOT_KINDS), so those kinds refuse it
            # (a conv layer's rows at a block boundary would be the
            # cheapest of them to snapshot: ROADMAP B14), and so does
            # an eva layer, whose open window's rows lie by slot beside
            # its summary pages (a prefix of whole windows would need
            # the pages alone: B14 too); full, mla
            # and sparse layers alone (K/V and latent pages, compressed
            # keys behind the same tables) share.
            by_slot = self._kinds_by_slot()
            refused = [what for what, there in (
                (f"prefix_caching (its {' and '.join(by_slot)} layers keep "
                 "a ring or a recurrent state a batch slot: a page behind "
                 "a window (of keys, or an mla_sliding layer's ring of "
                 "latents), a kda, mamba, mamba2 or lightning layer's "
                 "state, a "
                 "conv layer's rows and an eva layer's open window "
                 "after a prefix, cannot be mapped into another sequence: "
                 "engine._admit, kv_cache.BlockAllocator)",
                 cfg.prefix_caching and by_slot),
                ("speculative decoding (draft/spec_k: speculative.py "
                 "rolls back pages, not a ring or a recurrent state)",
                 cfg.draft is not None)) if there]
            if refused:
                raise NotImplementedError(
                    "a configuration with layers of several kinds or a "
                    "chip's share of the experts is served without "
                    + " and without ".join(refused)
                    + " (ROADMAP B9, B14); set prefix_caching=False, "
                    "draft=None")

        # What a decode call's latent attention reads is counted from
        # the positions it is given (metrics.record_latent_decode).
        self._latent_layers = model_cfg.n_layers_of("mla")
        self._latent_key_block = key_block(bs, self._table_width)
        # ... and the pages its full layers read, where they are one
        # kind among several (metrics.record_paged_decode).
        self._paged_layers = (model_cfg.n_layers_of("full")
                              if model_cfg.mixed else 0)
        # An eva layer's chunk lies in ONE aligned window (its program
        # attends the window's earlier rows, its own keys and the closed
        # windows' summaries: decode.mixed_programs), so a prompt's
        # chunks are cut at the windows' ends (_advance_prefills) and
        # the bucket a cut chunk takes has to fit a window too.
        self._eva_window = (model_cfg.eva_window
                            if model_cfg.n_layers_of("eva") else 0)
        if self._eva_window and pick_bucket(
                min(self._eva_window, cfg.prefill_chunk or cfg.max_prompt),
                self._prefill_buckets) > self._eva_window:
            raise ValueError(
                f"prefill_buckets {self._prefill_buckets} hold no bucket "
                f"within eva_window {self._eva_window} for a chunk cut at "
                "a window's end")

        # Inject pad-width menu, in BLOCK units: the prefill buckets
        # (prompt-only handoffs keep their existing programs) plus the
        # full table width (a migrated RUNNING sequence may carry
        # prompt+generated pages beyond the largest prompt bucket).
        self._inject_widths = tuple(sorted(
            {b // bs for b in self._prefill_buckets}
            | {self._table_width}))

        n_blocks = cfg.n_blocks
        if n_blocks is None:
            # Worst case: every batch slot holds a maximal sequence
            # (+1 for the reserved null block). A block is block_size
            # rows of whatever the paged layers keep a position (K and
            # V, or an mla layer's latent): the count is the same.
            n_blocks = cfg.max_batch * self._table_width + 1
        self.allocator = BlockAllocator(n_blocks, bs)
        # A window layer keeps a ring of this many positions for each
        # batch slot, whatever the sequence's length: the full layers
        # alone draw on the allocator.
        self._latent_ring_layers = model_cfg.n_layers_of("mla_sliding")
        ring = (ring_width(model_cfg.attn_window,
                           cfg.prefill_chunk or max(self._prefill_buckets),
                           bs)
                if model_cfg.n_window_layers or self._latent_ring_layers
                else 0)
        # ... of which a decode call reads pages, counted as the full
        # layers' are (metrics.record_window_decode; an mla_sliding
        # layer's ring of latents: record_latent_ring_decode).
        self._window_layers = model_cfg.n_window_layers
        self._ring_page = ring_page(ring, bs) if ring else 0
        self._free_slots = list(range(cfg.max_batch, 0, -1))
        self.cache = init_kv_cache(model_cfg, n_blocks, bs, mesh=mesh,
                                   dtype=cfg.cache_dtype,
                                   n_slots=cfg.max_batch, ring=ring)
        (self._prefill_fn, self._resume_fn, self._decode_fn,
         self._inject_fn, self._verify_fn) = decode_lib.make_serve_fns(
             model_cfg, mesh, block_size=bs,
             table_width=self._table_width, compression=cfg.compression,
             ring=ring)
        # Jitted page gather for handoff export — the twin of the
        # inject scatter. Op-by-op fancy indexing pays a full dispatch
        # per export (measured ~3x the compiled gather on the bench
        # payloads); widths ride the same bucket menu as inject so one
        # program per bucket serves every export.
        self._export_fn = jax.jit(lambda k, v, i: (k[:, i], v[:, i]))

        self.metrics = ServeMetrics(clock=clock, instance=instance)
        self.metrics.attach_allocator(self.allocator)
        self._queue: collections.deque[_Queued] = collections.deque()
        self._active: List[_Seq] = []
        # The decode call launched and not read yet (None: none). What
        # it produces is in no host-side state until it is read.
        self._in_flight: Optional[_InFlight] = None
        # Where a decode call's output lies: host tokens are put there
        # too, so that they and a predecessor's output are one kind of
        # argument to the jitted decode, and one compiled program.
        # Under a mesh the output comes back replicated (and what a
        # call returns is taken up, should a compiler choose
        # otherwise); without one it is uncommitted, as a plain
        # device_put's result is.
        self._tokens_sharding = (
            None if mesh is None else jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
        # Admitted sequences whose prefill has not completed: they
        # hold their block reservation and consume a batch slot, but
        # only join the decode batch once prefill finishes.
        self._prefilling: List[_Seq] = []
        # prefill_only sequences whose prefill completed: parked (with
        # their prompt blocks held) until the router exports them to a
        # decode replica. Not counted in `pending` — draining them is
        # the router's job, not the step loop's.
        self._handoff: Dict[int, _Seq] = {}
        self._results: Dict[int, RequestResult] = {}
        self._rids = itertools.count()
        # Staged (chunked) injects in flight: token -> {meta, blocks,
        # n_pages, cursor}. Invisible to admission/decode until commit;
        # an abort returns the block reservation.
        self._inject_staging: Dict[int, Dict[str, Any]] = {}
        self._inject_tokens = itertools.count()
        # Drain-rate signal behind retry_after_s estimates.
        self._retire_ema = RetireEma()
        # Speculative side-car: draft params + mirror KV pool + the
        # propose/verify/accept round that replaces _decode_once.
        self._spec = None
        if cfg.draft is not None:
            from horovod_tpu.serve.speculative import SpecDecoder
            self._spec = SpecDecoder(self)

    # -- submission --------------------------------------------------

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               deadline: Optional[float] = None,
               deadline_class: int = 0,
               prefill_only: bool = False,
               chain: Optional[List[bytes]] = None,
               trace_id: int = 0) -> int:
        """Enqueue a request; returns its id. Raises :class:`QueueFull`
        when the admission queue is at capacity (backpressure) and
        ``ValueError`` on shapes the engine cannot ever serve.
        ``deadline_class`` rides rejections so upstream shedding can
        order them; ``prefill_only`` parks the sequence for
        :meth:`export_prefilled` instead of decoding it here;
        ``chain`` is the prompt's precomputed hash chain (the router
        hashed it once at fleet admission — passing it through keeps
        the PR 4 hash-ONCE discipline across tiers; trusted, must
        match ``hash_chain(prompt, block_size)``); ``trace_id`` is the
        router-minted distributed trace id (0 = unsampled) that tags
        this request's prefill/decode spans (docs/observability.md)."""
        prompt = list(prompt)
        max_new = (self.cfg.max_new_tokens if max_new_tokens is None
                   else max_new_tokens)
        if prefill_only:
            self._refuse_slot_states("a prefill-only request (handoff)")
        validate_request(self.cfg, self.model_cfg,
                         self.allocator.n_blocks, prompt, max_new,
                         deadline_class)
        if len(self._queue) >= self.cfg.max_queue:
            self.metrics.record_rejected()
            raise QueueFull(
                f"admission queue full ({self.cfg.max_queue} waiting)",
                queue_depth=len(self._queue),
                retry_after_s=self._retry_after())
        rid = next(self._rids)
        chain = ((hash_chain(prompt, self.cfg.block_size)
                  if chain is None else chain)
                 if self.cfg.prefix_caching else [])
        self._queue.append(_Queued(rid, prompt, max_new, deadline,
                                   self._clock(), chain,
                                   deadline_class=deadline_class,
                                   prefill_only=prefill_only,
                                   trace=trace_id))
        self.metrics.record_submitted()
        self.metrics.record_queue_depth(len(self._queue))
        return rid

    # -- results -----------------------------------------------------

    @property
    def pending(self) -> bool:
        """Work left for :meth:`step`, a decode call in flight
        included: its tokens are nobody's until a step has read them."""
        return bool(self._queue or self._prefilling or self._active
                    or self._in_flight is not None)

    def result(self, rid: int) -> Optional[RequestResult]:
        return self._results.get(rid)

    @property
    def results(self) -> Dict[int, RequestResult]:
        return dict(self._results)

    # -- fleet hooks (cheap host-side reads; nothing here steps the
    #    engine or touches the device) ------------------------------

    def _retry_after(self) -> float:
        return self._retire_ema.retry_after(len(self._queue))

    def admission_snapshot(self) -> Dict[str, float]:
        """Router-facing admission state: occupancy, free KV blocks,
        queue depth. Pure host-side counter reads — a router can poll
        every replica per placement decision without stepping anyone
        or syncing a device value. ``running`` and ``kv_blocks_free``
        may lag by the decode call in flight: a sequence whose last
        token that call holds still counts, with its blocks, until a
        step has read it."""
        n_run = len(self._active) + len(self._prefilling)
        return {
            "queue_depth": len(self._queue),
            "queue_slots_free": self.cfg.max_queue - len(self._queue),
            "running": n_run,
            "batch_slots_free": self.cfg.max_batch - n_run,
            "occupancy": n_run / self.cfg.max_batch,
            "kv_blocks_free": self.allocator.n_free,
            "kv_blocks_used": self.allocator.n_used,
            "handoff_parked": len(self._handoff),
            "retry_after_s": self._retry_after(),
        }

    def cached_chain_len(self, chain: Sequence[bytes]) -> int:
        """Leading blocks of ``chain`` this engine's content index
        holds (live or cached) — the prefix-affinity placement signal.
        Non-mutating (`peek`): polling it from a router never inflates
        hit counters or churns the LRU order."""
        n = 0
        for h in chain:
            if self.allocator.peek(h) is None:
                break
            n += 1
        return n

    def withdraw(self, rid: int) -> bool:
        """Remove a still-queued (never admitted) request, dropping it
        without a result. False if ``rid`` is unknown, already
        admitted, or already resolved — the caller keeps its own copy
        of the request if it intends to resubmit elsewhere (this is
        the router's replica-drain path)."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[i]
                # Un-count the submission: the caller re-submits the
                # request elsewhere (which counts it again there), so
                # fleet-summed submitted = finished+expired+rejected
                # stays balanced across drains.
                self.metrics.record_withdrawn()
                self.metrics.record_queue_depth(len(self._queue))
                return True
        return False

    # -- the scheduler iteration ------------------------------------

    def step(self) -> None:
        """One iteration: retire → expire → admit → prefill chunk(s)
        → decode. Each part is a :meth:`ServeMetrics.phase` span
        (docs/observability.md); a step with nothing to do records
        nothing.

        The decode call is left in flight when the step returns, and
        the next step reads it: after it has launched that call's
        successor (``tokens`` is the call's output on the device), or
        before anything that needs the host's view of it. A step with
        prefill work reads it before the prefill is dispatched, so that
        its tokens are not stamped a prefill late, and the sequence
        that completes its prompt joins a batch whose tokens the host
        holds. A token is counted and stamped (``token_times``,
        ``tokens_generated``, the end of ``serve:decode``) when the
        host has it; a sequence that got its last token in one step's
        read is retired at the start of the next."""
        if not self.pending:
            return
        m = self.metrics
        with m.phase("serve:schedule") as ph:
            now = ph.t0
            m.record_step(now)
            ph.args.update(retired=self._retire_finished(now),
                           expired=self._expire_queued(now),
                           admitted=self._admit(now))
            ph.args["queue"] = len(self._queue)
        if not (self._prefilling or self._active):
            m.record_idle()
        # (the rings have their own gauge)
        recurrent = (set(SLOT_KINDS) - set(RING_KINDS)) & set(self.cache.kinds)
        if self.cache.ring or recurrent:
            with m.phase("serve:gauges"):
                in_use = self.cfg.max_batch - len(self._free_slots)
                if self.cache.ring:
                    m.kv_window_blocks_in_use = (
                        in_use * self.cache.ring // self.cfg.block_size)
                if recurrent:
                    m.state_slots_in_use = in_use
                    m.state_bytes = in_use * self.cache.slot_bytes
        if self._prefilling:
            self._drain("prefill")
        self._advance_prefills()
        self._decode_once()
        m.record_queue_depth(len(self._queue))

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        for _ in range(max_steps):
            if not self.pending:
                return
            self.step()
        raise RuntimeError(f"engine still busy after {max_steps} steps")

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: Optional[int] = None) -> List[List[int]]:
        """Convenience batch API: serve ``prompts`` to completion and
        return their generated token lists in order."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        self.run_until_idle()
        return [self._results[r].tokens for r in rids]

    # -- internals ---------------------------------------------------

    def _finish(self, seq: _Seq, now: float) -> None:
        self.allocator.free(seq.blocks)
        if self._slot_states:
            self._free_slots.append(seq.slot)
        if self._spec is not None:
            self._spec.drop(seq.rid)
        self._results[seq.rid] = RequestResult(
            rid=seq.rid, status="ok", http_status=200,
            tokens=list(seq.generated), n_prompt=len(seq.prompt),
            submitted_at=seq.submitted_at,
            first_token_at=seq.first_token_at, finished_at=now,
            slot=seq.slot,
            blocks=list(seq.blocks) if self._slot_states else [],
            deadline_class=seq.deadline_class,
            token_times=seq.token_times)
        self._retire_ema.observe(now)
        self.metrics.record_finished()
        # What one client saw, on one span: the gaps are between the
        # tokens produced here (a handed-off sequence's earlier tokens
        # and its queue wait belong to the replica that had them).
        args: Dict[str, Any] = {"n_prompt": len(seq.prompt),
                                "n_out": len(seq.generated)}
        if seq.trace:
            args["trace"] = seq.trace
        if seq.admitted_at is not None:
            args["queue_ms"] = 1e3 * (seq.admitted_at - seq.submitted_at)
        if seq.first_token_at is not None:
            args["ttft_ms"] = 1e3 * (seq.first_token_at - seq.submitted_at)
        ts = seq.token_times
        if len(ts) > 1:
            args["itl_mean_ms"] = 1e3 * (ts[-1] - ts[0]) / (len(ts) - 1)
            args["itl_max_ms"] = 1e3 * max(
                b - a for a, b in zip(ts, ts[1:]))
        self.metrics.record_request(seq.submitted_at, now, **args)

    def _retire_finished(self, now: float) -> int:
        # A sequence ended by eos_id while it had a row in the call
        # launched ahead keeps its blocks and slot until that call is
        # read: the call still writes there.
        held = self._in_flight.held if self._in_flight is not None else ()
        still = []
        for seq in self._active:
            if seq.finished(self.cfg.eos_id) and seq.rid not in held:
                self._finish(seq, now)
            else:
                still.append(seq)
        n_retired = len(self._active) - len(still)
        self._active = still
        return n_retired

    def _expire_queued(self, now: float) -> int:
        keep: collections.deque[_Queued] = collections.deque()
        for req in self._queue:
            if req.deadline is not None and now > req.deadline:
                # Structured rejection, not a blanket 503: the client
                # learns WHY (deadline passed in queue), at what
                # priority it was classified, and when a retry might
                # actually get served.
                self._results[req.rid] = RequestResult(
                    rid=req.rid, status="expired", http_status=503,
                    tokens=[], n_prompt=len(req.prompt),
                    submitted_at=req.submitted_at, finished_at=now,
                    reason="deadline_expired",
                    deadline_class=req.deadline_class,
                    retry_after_s=self._retry_after())
                self.metrics.record_expired()
            else:
                keep.append(req)
        n_expired = len(self._queue) - len(keep)
        self._queue = keep
        return n_expired

    def _admit(self, now: float) -> int:
        n_admitted = 0
        while (self._queue and
               len(self._active) + len(self._prefilling)
               < self.cfg.max_batch):
            req = self._queue[0]
            plen = len(req.prompt)
            # A prefill-only sequence never decodes here — it writes
            # prompt pages and leaves — so reserving its max_new tail
            # would waste prefill-pool capacity for nothing.
            need = self.allocator.blocks_for_tokens(
                plen if req.prefill_only else plen + req.max_new)
            # Walk the chain against the content index; every leading
            # whole block already cached maps into this sequence's
            # table with one refcount, zero FLOPs. Capped at plen-1
            # tokens: the final prompt token must run through the
            # model — its logits are the first generated token. The
            # first walk is a non-mutating peek: a blocked request
            # retries admission every step, and taking/releasing refs
            # here would inflate the hit counters and churn the LRU
            # order with reuse that never happened.
            matchable = req.chain[:(plen - 1) // self.cfg.block_size]
            n_match, n_revive = 0, 0
            for h in matchable:
                b = self.allocator.peek(h)
                if b is None:
                    break
                n_match += 1
                if self.allocator.refcount(b) == 0:
                    # Reviving a refcount-0 cached block consumes a
                    # unit of n_free just like a fresh allocation, so
                    # it must count against capacity — or an
                    # overcommitted pool passes this check and then
                    # blows OutOfBlocks mid-admission.
                    n_revive += 1
            if not self.allocator.can_alloc(need - n_match + n_revive):
                # KV backpressure (FIFO: no overtaking, so tail
                # latency stays predictable under load).
                break
            self._queue.popleft()
            # Commit: nothing mutated between peek and acquire, so
            # the same blocks resolve — and hits (plus the one
            # boundary miss) count once, for an admission that
            # actually happened.
            matched: List[int] = []
            for h in matchable:
                b = self.allocator.acquire_cached(h)
                if b is None:
                    break
                matched.append(b)
            assert len(matched) == n_match
            blocks = matched + self.allocator.alloc(need - n_match)
            table = np.zeros(self._table_width, np.int32)
            table[:len(blocks)] = blocks
            n_hit = len(matched) * self.cfg.block_size
            self.metrics.record_prefix_lookup(n_hit, plen - n_hit)
            self._prefilling.append(_Seq(
                rid=req.rid, prompt=req.prompt, max_new=req.max_new,
                blocks=blocks, table=table, n_cached=n_hit, mapped=n_hit,
                generated=[], submitted_at=req.submitted_at,
                chain=req.chain, registered=len(matched),
                deadline_class=req.deadline_class,
                prefill_only=req.prefill_only,
                trace=req.trace, admitted_at=now,
                slot=self._free_slots.pop() if self._slot_states else 0))
            self.metrics.record_admitted(req.submitted_at, now, req.trace)
            n_admitted += 1
        return n_admitted

    def _advance_prefills(self) -> None:
        """Run prefill chunks FIFO across admitted-but-incomplete
        sequences, bounded per step by ``prefill_chunk`` tokens
        (always at least one chunk, so progress is guaranteed). With
        ``prefill_chunk=None`` every waiting suffix completes this
        step — the monolithic behavior."""
        budget = self.cfg.prefill_chunk
        spent = 0
        m = self.metrics
        while self._prefilling and (budget is None or spent < budget):
            seq = self._prefilling[0]
            # The host's own work around a chunk runs with nothing in
            # flight: under a name each side of the call, so that
            # `serve:unfed` can say where its host time went.
            with m.phase("serve:prefill_prep"):
                self._extend_prefix_match(seq)
                remaining = len(seq.prompt) - seq.n_cached
                if budget is None:
                    chunk = remaining
                else:
                    # Cap by the UNSPENT budget, not the full chunk
                    # size: several queued suffixes could otherwise
                    # spend up to 2N-1 tokens in one step. Non-final
                    # chunks must end block-aligned (the next chunk's
                    # pages start there).
                    chunk = min(remaining, budget - spent)
                    if chunk < remaining:
                        chunk -= chunk % self.cfg.block_size
                        if chunk == 0:
                            break
                if self._eva_window:
                    # no further than the window's end (whole blocks:
                    # a window is)
                    chunk = min(chunk, self._eva_window
                                - seq.n_cached % self._eva_window)
                toks, extra = self._prefill_call(seq, chunk)
            done_at = self._run_prefill_chunk(seq, chunk, toks, extra)
            with m.phase("serve:prefill_post"):
                self._record_prefill_chunk(seq)
                spent += chunk
                if seq.n_cached >= len(seq.prompt):
                    self._prefilling.pop(0)
                    self._complete_prefill(seq, done_at)

    def _extend_prefix_match(self, seq: _Seq) -> None:
        """Retry the cache walk just before prefilling. Admission in a
        burst step matches against a cache its same-step siblings
        haven't populated yet (they register at prefill, after the
        admission loop); by prefill time an identical prefix admitted
        one slot earlier IS published, so a second walk converts those
        would-be prefill tokens into hits. Safe whenever the cursor
        sits on a whole-block boundary with every block up to it
        published or mapped: the swapped slots hold no K/V yet, and
        the displaced private blocks return to the pool."""
        if (not self.cfg.prefix_caching
                or seq.n_cached != seq.registered * self.cfg.block_size):
            return
        plen = len(seq.prompt)
        extended = 0
        for i in range(seq.registered,
                       (plen - 1) // self.cfg.block_size):
            # peek first: this walk reruns at every block-aligned
            # chunk boundary, and a cold prompt would otherwise log
            # one spurious miss per chunk.
            if self.allocator.peek(seq.chain[i]) is None:
                break
            b = self.allocator.acquire_cached(seq.chain[i])
            if b is None:
                break
            self.allocator.free([seq.blocks[i]])
            seq.blocks[i] = b
            seq.table[i] = b
            seq.n_cached += self.cfg.block_size
            seq.registered += 1
            extended += self.cfg.block_size
        if extended:
            seq.mapped += extended
            self.metrics.record_prefix_extend(extended)

    def _prefill_call(self, seq: _Seq, chunk: int):
        """The next chunk's padded tokens and what its span says of it
        beside ``n_tokens`` and ``offset``."""
        offset = seq.n_cached
        toks = np.zeros(pick_bucket(chunk, self._prefill_buckets), np.int32)
        toks[:chunk] = seq.prompt[offset:offset + chunk]
        m = self.metrics
        extra = {"trace": seq.trace} if seq.trace else {}
        if self.cfg.prefix_caching:
            # positions this call attends that it did not compute
            extra["mapped"], seq.mapped = seq.mapped, 0
        if "mamba" in self.cache.kinds or "mamba2" in self.cache.kinds:
            # positions the selective scan (a mamba2 layer's SSD blocks)
            # runs: the bucket, pads too
            extra["scanned"] = len(toks)
        if "mamba2" in self.cache.kinds:
            # those of them whose SSD the kernel ran: all or none, by
            # the call's bucket
            extra["scan_kernel"] = (
                len(toks) if decode_lib.ssd_scan_taken(
                    self.model_cfg, len(toks)) else 0)
            m.record_scan(extra["scanned"], extra["scan_kernel"])
        if "conv" in self.cache.kinds:
            # positions the short convolutions run and the mixture
            # dispatches: the bucket, pads too (n_tokens are the real)
            extra["convolved"] = len(toks)
        if "sparse" in self.cache.kinds:
            # the call's queries that choose their blocks
            extra["selected"] = max(0, offset + chunk - max(
                offset, self.model_cfg.sparse_dense_len))
            # those of them whose block scores the kernel makes
            extra["select_kernel"] = (
                extra["selected"] if decode_lib.sparse_select_taken(
                    len(toks), self.cfg.block_size, self._table_width)
                else 0)
            m.record_sparse(self.model_cfg, offset + chunk,
                            prefill=extra["selected"],
                            kernel=extra["select_kernel"])
        if self._latent_ring_layers:
            # places of the slot's ring of latents the chunk's
            # attention expands, a layer: its own keys where it is the
            # whole prompt, else the whole ring
            extra["latent_ring_places"] = (self.cache.ring if offset
                                           else len(toks))
        if self._eva_window:
            # the chunk ends a window: from here on its summaries are
            # attended and its rows are dead
            m.record_eva(closed=int(
                (offset + chunk) % self._eva_window == 0))
        return toks, extra

    def _run_prefill_chunk(self, seq: _Seq, chunk: int, toks: np.ndarray,
                           extra: Dict[str, Any]) -> float:
        """Run one chunk; returns when its host sync ended (engine
        clock: the end of its ``serve:prefill`` span)."""
        plen = len(seq.prompt)
        offset = seq.n_cached
        with self.metrics.phase("serve:prefill", device=True, n_tokens=chunk,
                                offset=offset, **extra) as ph:
            with ph.dispatch():
                if offset == 0 and chunk == plen:
                    # Whole cold prompt: the monolithic program (exactly
                    # the pre-cache code path, and the cheaper attention
                    # — prompt-local instead of a full table gather).
                    kc, vc, tok = self._prefill_fn(
                        self._params, self.cache.k, self.cache.v, toks,
                        np.int32(plen), self._address(seq))
                else:
                    kc, vc, tok = self._resume_fn(
                        self._params, self.cache.k, self.cache.v, toks,
                        np.int32(offset), np.int32(chunk),
                        self._address(seq))
            tok = ph.read(tok, int)  # host sync: the step is done now
        self.cache.k, self.cache.v = kc, vc
        seq.n_cached = offset + chunk
        seq.last_prefill_tok = tok
        self._record_window_positions(offset + len(toks))
        return ph.end

    def _record_prefill_chunk(self, seq: _Seq) -> None:
        """Count the chunk that just ran and publish the blocks it
        filled."""
        self.metrics.record_prefill()
        if self.cfg.prefix_caching:
            # Publish the prompt blocks this chunk filled. A losing
            # race (hash already published by a concurrent twin) keeps
            # the private copy anonymous — register() no-ops.
            n_full = seq.n_cached // self.cfg.block_size
            for i in range(seq.registered, n_full):
                self.allocator.register(seq.blocks[i], seq.chain[i])
            seq.registered = max(seq.registered, n_full)

    def _address(self, seq: _Seq):
        """Where a sequence's state lies, as the serve programs take
        it: its block table, and with a state by kind of layer its
        slot too."""
        if self._slot_states:
            return seq.table, np.int32(seq.slot)
        return seq.table

    def _record_window_positions(self, written: int) -> None:
        """``written`` positions of one sequence have gone into every
        window layer's ring, which keeps the newest ``ring`` of them."""
        if self._window_layers:
            self.metrics.record_window_positions(
                min(written, self.cache.ring))
        if self._latent_ring_layers:
            self.metrics.record_latent_ring_positions(
                min(written, self.cache.ring))

    def _kinds_by_slot(self) -> List[str]:
        """The configuration's kinds of layer whose state lies by batch
        slot (``kv_cache.SLOT_KINDS``), by name."""
        return sorted(set(SLOT_KINDS)
                      & set(self.model_cfg.layer_types or ()))

    def _refuse_slot_states(self, what: str) -> None:
        if self._slot_states:
            held = " and ".join(self._kinds_by_slot()) or "several kinds of"
            raise NotImplementedError(
                f"{what} moves a sequence's pages between engines; a "
                f"configuration with {held} layers keeps a window layer's "
                "keys (an mla_sliding layer's latents) in per-slot rings, "
                "a kda, mamba, mamba2 or lightning "
                "layer's recurrent state, a conv layer's rows and an eva "
                "layer's open window's rows by slot, "
                "which are not pages (nor "
                "are a sparse layer's compressed keys K or V pages) and which "
                "migrate.py and engine.inject_* do not move yet (ROADMAP "
                "B9, B14)")

    def _complete_prefill(self, seq: _Seq, now: float) -> None:
        """``now``: the end of the prefill span that produced the first
        token."""
        seq.generated.append(seq.last_prefill_tok)
        seq.token_times.append(now)
        seq.first_token_at = now
        self.metrics.record_first_token(now - seq.submitted_at)
        if seq.finished(self.cfg.eos_id):
            # One-token requests (or an immediate eos) finish right
            # here even in prefill_only mode — nothing left to hand
            # off, so the result stays on this replica.
            self._finish(seq, now)
        elif seq.prefill_only:
            self._handoff[seq.rid] = seq
        else:
            self._active.append(seq)

    # -- prefill/decode disaggregation (KV handoff) ------------------

    def handoff_ready(self) -> List[int]:
        """rids of prefill-only sequences whose prefill completed and
        which are parked awaiting :meth:`export_prefilled`."""
        return list(self._handoff)

    def export_prefilled(self, rid: int) -> PrefillHandoff:
        """Pop a parked prefill-only sequence: copy its written K/V
        pages off this replica's pool, free its blocks, and return the
        package a decode replica feeds to :meth:`inject_prefilled`.
        The page copy is bitwise, so the handoff changes *where*
        decode runs, never *what* it computes."""
        self._drain("migrate")
        return self._export_seq(self._handoff.pop(rid))

    def _export_seq(self, seq: _Seq) -> PrefillHandoff:
        """Package ``seq`` for another replica: bitwise page copies of
        every block its cached tokens touch (the partial tail block
        rides whole — its bytes past ``n_cached`` are never attended
        to, the same null-padding contract decode relies on), then
        free the local reservation."""
        self._refuse_slot_states("export (migrate)")
        n_blk = self.allocator.blocks_for_tokens(seq.n_cached)
        width = pick_bucket(n_blk, self._inject_widths)
        idx = np.zeros(width, np.int32)   # pad gathers the null block
        idx[:n_blk] = seq.blocks[:n_blk]
        k_g, v_g = self._export_fn(self.cache.k, self.cache.v, idx)
        if n_blk == width:
            k_pages = np.asarray(k_g)
            v_pages = np.asarray(v_g)
        else:
            # Trim the padding rows on the host; contiguous because
            # the wire layer ships the buffer as-is.
            k_pages = np.ascontiguousarray(np.asarray(k_g)[:, :n_blk])
            v_pages = np.ascontiguousarray(np.asarray(v_g)[:, :n_blk])
        self.allocator.free(seq.blocks)
        if self._spec is not None:
            self._spec.drop(seq.rid)
        self.metrics.record_handoff_out()
        return PrefillHandoff(
            prompt=list(seq.prompt), max_new=seq.max_new,
            generated=list(seq.generated),
            submitted_at=seq.submitted_at,
            first_token_at=seq.first_token_at,
            deadline_class=seq.deadline_class, chain=list(seq.chain),
            k_pages=k_pages, v_pages=v_pages,
            block_size=self.cfg.block_size, n_cached=seq.n_cached,
            trace_id=seq.trace)

    def running_exportable(self) -> List[int]:
        """rids of RUNNING (decoding) sequences a drain could migrate
        right now: active, prefill complete, and not already finished
        (a finished-but-unretired sequence must retire HERE — exporting
        it would decode it past its cap on the target). Reads the
        decode call in flight first: the list is of what the host
        holds."""
        self._drain("migrate")
        return [s.rid for s in self._active
                if not s.finished(self.cfg.eos_id)]

    def export_running(self, rid: int) -> PrefillHandoff:
        """Pop a RUNNING sequence mid-decode and package it for
        :meth:`inject_prefilled` on another replica — the migrating
        half of a drain. Everything the sequence has computed (prompt
        AND generated-token K/V) moves bitwise, so the remaining
        tokens decode to exactly what they would have been in place.
        The decode call in flight is read first: its token and its
        K/V belong to what moves."""
        self._drain("migrate")
        for i, seq in enumerate(self._active):
            if seq.rid == rid:
                break
        else:
            raise KeyError(f"no running sequence {rid}")
        if seq.finished(self.cfg.eos_id):
            raise ValueError(
                f"sequence {rid} already finished — retire it here "
                "instead of migrating it")
        del self._active[i]
        return self._export_seq(seq)

    def inject_prefilled(self, h: PrefillHandoff) -> int:
        """Admit a handed-off sequence straight into the decode batch:
        reserve its worst-case blocks, scatter its pages into this
        replica's pool, and decode onward from the last emitted token.
        The handoff may be a completed prefill (pool split) or a
        mid-decode RUNNING sequence (migrating drain) — ``n_cached``
        says how many tokens the pages cover either way. Raises
        :class:`QueueFull` (no batch slot) or
        :class:`~horovod_tpu.serve.kv_cache.OutOfBlocks` — the router
        checks :meth:`admission_snapshot` capacity first, so hitting
        either here is a router bug, not backpressure.

        Implemented as the one-chunk case of the staged inject
        (:meth:`inject_begin` / :meth:`inject_chunk` /
        :meth:`inject_commit`) — the relayed and direct migration
        paths run literally the same scatter, which is what makes the
        bitwise direct-vs-relayed parity pin in tests/test_rpc.py a
        tautology rather than a hope."""
        token = self.inject_begin({
            "prompt": h.prompt, "max_new": h.max_new,
            "generated": h.generated, "submitted_at": h.submitted_at,
            "first_token_at": h.first_token_at,
            "deadline_class": h.deadline_class, "chain": h.chain,
            "block_size": h.block_size, "n_cached": h.n_cached,
            "n_pages": h.n_pages, "trace_id": h.trace_id})
        self.inject_chunk(token, h.k_pages, h.v_pages)
        return self.inject_commit(token)

    def inject_begin(self, meta: Dict[str, Any]) -> int:
        """First leg of the staged (chunked) inject: validate the
        handoff manifest — everything :meth:`inject_prefilled` checks,
        pages excluded — and reserve the sequence's worst-case blocks.
        Returns a staging token for :meth:`inject_chunk` /
        :meth:`inject_commit` / :meth:`inject_abort`. Until commit the
        staged sequence is invisible to decode, admission counts, and
        results — an abort (or a dropped peer connection mid-stream)
        simply returns the reservation, which is what makes a
        mid-transfer reset resolve exactly-once at the router."""
        self._refuse_slot_states("inject")
        # Every leg of an inject reads the decode call in flight first:
        # the batch slots and blocks counted here, the pool scattered
        # into and the batch joined are the host's view after it.
        self._drain("migrate")
        if meta["block_size"] != self.cfg.block_size:
            raise ValueError(
                f"handoff block_size {meta['block_size']} != engine "
                f"block_size {self.cfg.block_size} — replicas must "
                "share geometry for pages to map block-for-block")
        plen = len(meta["prompt"])
        n_cached = int(meta["n_cached"])
        if not (plen <= n_cached <= plen + meta["max_new"]
                and meta["generated"]
                and n_cached == plen + len(meta["generated"]) - 1):
            raise ValueError(
                f"inconsistent handoff: n_cached={n_cached} "
                f"prompt={plen} generated={len(meta['generated'])}")
        n_page = int(meta["n_pages"])
        if n_page != self.allocator.blocks_for_tokens(n_cached):
            raise ValueError(
                f"handoff carries {n_page} pages but n_cached="
                f"{n_cached} needs "
                f"{self.allocator.blocks_for_tokens(n_cached)}")
        if len(self._active) + len(self._prefilling) >= self.cfg.max_batch:
            raise QueueFull("no batch slot for handoff",
                            reason="no_batch_slot",
                            retry_after_s=self._retry_after())
        need = self.allocator.blocks_for_tokens(plen + meta["max_new"])
        blocks = self.allocator.alloc(need)
        token = next(self._inject_tokens)
        self._inject_staging[token] = {
            "meta": meta, "blocks": blocks, "n_pages": n_page,
            "cursor": 0}
        return token

    def inject_chunk(self, token: int, k_pages, v_pages) -> int:
        """Scatter one block-aligned run of pages (``[cursor, cursor +
        chunk)`` in manifest page order) into the reserved blocks.
        Jitted donated scatter: pages land in place, O(carried pages),
        never a full-pool copy. The pad width rides the prefill bucket
        menu extended by table_width (a migrated RUNNING sequence can
        exceed the largest prompt bucket): one compiled program per
        width, device transfer proportional to the carried pages,
        NULL_BLOCK targets + zero pages for the padding rows — written
        garbage on the null block is never read, the prefill
        bucket-padding contract. Chunks target disjoint block rows, so
        the committed pool state is bitwise the monolithic scatter's
        regardless of chunking. Returns pages remaining."""
        self._drain("migrate")
        st = self._inject_staging[token]
        k_pages = np.asarray(k_pages)
        v_pages = np.asarray(v_pages)
        cn = int(k_pages.shape[1])
        if cn < 1 or st["cursor"] + cn > st["n_pages"]:
            raise ValueError(
                f"inject chunk of {cn} pages at cursor {st['cursor']} "
                f"overruns the {st['n_pages']}-page manifest")
        width = pick_bucket(cn, self._inject_widths)
        if cn == width:
            # Bucket-exact chunk: no padding rows, no staging copy —
            # the wire arrays feed the scatter directly. This is the
            # shape a topology plan aims for (chunk sizes drawn from
            # the bucket menu), and it halves the inject's host-side
            # memory traffic.
            idx = np.asarray(
                st["blocks"][st["cursor"]:st["cursor"] + cn], np.int32)
            k_pad, v_pad = k_pages, v_pages
        else:
            idx = np.full(width, 0, np.int32)           # NULL_BLOCK
            idx[:cn] = st["blocks"][st["cursor"]:st["cursor"] + cn]
            shape = (k_pages.shape[0], width) + k_pages.shape[2:]
            k_pad = np.zeros(shape, k_pages.dtype)
            v_pad = np.zeros(shape, v_pages.dtype)
            k_pad[:, :cn] = k_pages
            v_pad[:, :cn] = v_pages
        self.cache.k, self.cache.v = self._inject_fn(
            self.cache.k, self.cache.v, idx, k_pad, v_pad)
        st["cursor"] += cn
        return st["n_pages"] - st["cursor"]

    def inject_commit(self, token: int) -> int:
        """Every manifest page landed: materialize the sequence into
        the decode batch and return its rid. Registration, metrics,
        and batch membership all happen HERE — a partially-streamed
        sequence never observes any of them."""
        self._drain("migrate")
        st = self._inject_staging[token]
        if st["cursor"] != st["n_pages"]:
            raise ValueError(
                f"inject commit with {st['cursor']}/{st['n_pages']} "
                "pages streamed")
        del self._inject_staging[token]
        meta, blocks = st["meta"], st["blocks"]
        table = np.zeros(self._table_width, np.int32)
        table[:len(blocks)] = blocks
        rid = next(self._rids)
        seq = _Seq(
            rid=rid, prompt=list(meta["prompt"]),
            max_new=meta["max_new"], blocks=blocks, table=table,
            n_cached=int(meta["n_cached"]),
            generated=list(meta["generated"]),
            submitted_at=meta["submitted_at"],
            chain=list(meta["chain"]), registered=0,
            deadline_class=meta["deadline_class"],
            trace=int(meta.get("trace_id", 0)))
        seq.first_token_at = meta["first_token_at"]
        if self.cfg.prefix_caching:
            # Publish the injected prompt blocks locally: future
            # same-prefix requests (or handoffs) landing here hit them
            # for free. A hash already published keeps this private
            # copy anonymous (register no-ops), same as the twin race.
            for i, ch in enumerate(meta["chain"]):
                self.allocator.register(blocks[i], ch)
            seq.registered = len(meta["chain"])
        self._active.append(seq)
        self.metrics.record_handoff_in()
        return rid

    def inject_abort(self, token: int) -> None:
        """Discard a staged inject (stream died mid-transfer, or the
        source declared the manifest stale): the block reservation
        returns to the pool, any pages already scattered stay as
        unreferenced garbage on freed blocks — never attended to, the
        same contract as any freed block's stale contents. Idempotent
        per token."""
        st = self._inject_staging.pop(token, None)
        if st is not None:
            self.allocator.free(st["blocks"])

    def _decode_once(self) -> None:
        """Launch the next decode call, ahead of the read of the one in
        flight where the host can know its batch without that call's
        tokens: the batch of n+1 is the batch of n less the sequences
        that reach ``max_new_tokens`` at n (one that n ends by
        ``eos_id`` has a row in n+1 all the same, whose token is
        discarded). Then read the call in flight. What the engine
        observes decides, each step; nothing is configured."""
        if self._spec is not None:
            # Speculative iteration: k draft proposals per sequence,
            # one chunked target verify, host-side greedy acceptance
            # with cursor-only rollback of rejected positions. Swaps
            # ONLY this decode iteration — admission, prefill,
            # retirement, handoff all run unchanged above/below it.
            # Acceptance is read on the host, so nothing is left in
            # flight.
            self._spec.round()
            return
        fl = self._in_flight
        if fl is None:
            self._launch(None)
            return
        with self.metrics.phase("serve:decode_plan"):
            stay = np.array([seq is not None
                             and len(seq.generated) + 1 < seq.max_new
                             for seq in fl.rows])
            n = int(stay.sum())
            leaving = sum(seq is not None for seq in fl.rows) - n
            bucket = pick_bucket(n, self._batch_buckets)
        if n == 0:
            self._drain("idle")
        elif leaving and self._queue:
            # A sequence ends at the call in flight and a request waits
            # for a slot: read now and launch nothing, so that the next
            # step retires, admits and prefills, and the newcomer is in
            # the next call. Launched ahead, that call would run a row
            # short and the newcomer would join a step late.
            self._drain("admit")
        elif bucket != len(fl.rows):
            self._drain("bucket")
            self._launch(None)
        else:
            self._launch(fl, stay)
            self._read(fl)

    def _launch(self, prev: Optional[_InFlight],
                stay: Optional[np.ndarray] = None) -> None:
        """Dispatch one decode call and leave it in flight. With
        ``prev`` (the call in flight, not read) the rows that ``stay``
        keep their places, ``tokens`` is ``prev``'s output where it
        lies and a row that left is padded as padded rows are; without
        it the batch is every unfinished sequence, packed from row 0,
        with the tokens the host holds."""
        m = self.metrics
        if prev is None:
            with m.phase("serve:decode_plan"):
                seqs = [s for s in self._active
                        if not s.finished(self.cfg.eos_id)]
            if not seqs:
                return
        with m.phase("serve:decode_prep"):
            if prev is None:
                bucket = pick_bucket(len(seqs), self._batch_buckets)
                rows = seqs + [None] * (bucket - len(seqs))
                tokens = np.zeros(bucket, np.int32)
                positions = np.zeros(bucket, np.int32)
                tables = np.zeros((bucket, self._table_width), np.int32)
                slots = np.full(bucket, NULL_SLOT, np.int32)  # padded rows'
                for i, seq in enumerate(seqs):
                    tokens[i] = seq.last_token
                    positions[i] = seq.n_cached
                    tables[i] = seq.table
                    slots[i] = seq.slot
                # The same kind of argument as a predecessor's output,
                # so that both meet one entry of the jitted decode.
                tokens = jax.device_put(tokens, self._tokens_sharding)
            else:
                rows = [seq if keep else None
                        for seq, keep in zip(prev.rows, stay)]
                tokens = prev.out
                positions = np.where(stay, prev.positions + 1, np.int32(0))
                tables = np.where(stay[:, None], prev.tables, np.int32(0))
                slots = np.where(stay, prev.slots, np.int32(NULL_SLOT))
            address = (tables, slots) if self._slot_states else tables
            n = sum(seq is not None for seq in rows)
            # A decode step serves the whole batch, so it carries the
            # trace ids of every sampled sequence in it (plural key).
            traces = [s.trace for s in rows if s is not None and s.trace]
            extra = {"traces": traces} if traces else {}
            if "mamba" in self.cache.kinds or "mamba2" in self.cache.kinds:
                # (a mamba2 layer's step does step every slot's SSD state
                # where it lies, the null slot's too: mamba2_step_layer)
                # slots whose state the XLA form of the step read and wrote
                # where it lies (since PR 48 the kernels touch the batch's
                # rows alone; the count stays what its reader in the
                # benchmark holds it to until a `benchmark` issue corrects
                # both), and the positions its rows attend in the full layers
                extra["slots_stepped"] = self.cfg.max_batch + 1
                extra["attended"] = int(positions.sum()) + n
            if "conv" in self.cache.kinds:
                # rows whose slot's convolution rows the step shifted (the
                # bucket: a padded row shifts the null slot's), and the
                # positions the batch's rows attend in the full layers
                extra["slots_stepped"] = len(positions)
                extra["attended"] = int(positions.sum()) + n
            if self._latent_ring_layers:
                # what the real rows' absorbed attention has to read, a
                # layer: the ring places inside each row's window, and
                # the positions in the latent pages of the mla layers
                at = positions[[seq is not None for seq in rows]]
                extra["latent_ring_places"] = int(np.minimum(
                    at + 1, self.model_cfg.attn_window).sum())
                extra["latent_positions"] = int(at.sum()) + n
            if self._eva_window:
                # what the eva layers' attention of this call has to
                # read, a layer: the open windows' rows up to each real
                # row's position and the summaries of its closed windows
                # (a padded row reads one row of the null slot: not
                # counted); and the summary pages those are, the windows
                # this step's rows close
                c, W = self.model_cfg, self._eva_window
                at = positions[[seq is not None for seq in rows]]
                extra["eva_rows"] = int((at % W + 1).sum())
                extra["eva_summaries"] = int(
                    (at // W).sum()) * (W // c.eva_chunk)
                m.record_eva(
                    pages=int((at // W).sum()) * (W // self.cfg.block_size),
                    closed=int(((at + 1) % W == 0).sum()))
            if "sparse" in self.cache.kinds:
                # rows that choose their blocks (a padded row is at 0)
                # and the keys a KV group of its rows attends: every one at
                # or before a row below sparse_dense_len, those of its
                # chosen blocks (the last of them its own, part filled) past it
                c = self.model_cfg
                chose = positions >= c.sparse_dense_len
                extra["rows_selected"] = int(chose.sum())
                extra["attended"] = int(np.where(
                    chose, (c.sparse_topk - 1) * c.sparse_block
                    + positions % c.sparse_block, positions).sum()) + n
                # the compressed keys those rows score: the kernels complete
                extra["scored"] = int(
                    ((positions[chose] - c.sparse_kernel) // c.sparse_stride
                     + 1).sum())
                m.record_sparse(c, int(positions.max()) + 1,
                                decode=extra["rows_selected"])
        call = m.launch("serve:decode", n_active=n, ahead=prev is not None,
                        **extra)
        with call.dispatch():
            self.cache.k, self.cache.v, out = self._decode_fn(
                self._params, self.cache.k, self.cache.v, tokens,
                positions, address)
        if self._latent_layers:
            m.record_latent_decode(positions + 1, self._latent_key_block,
                                   self._latent_layers)
        if self._paged_layers:
            m.record_paged_decode(positions + 1, self.cfg.block_size,
                                  self._table_width, self._paged_layers)
        if self._window_layers:
            m.record_window_decode(
                positions + 1, self.model_cfg.attn_window, self.cache.ring,
                self._ring_page, self.cfg.max_batch + 1,
                self._window_layers)
        if self._latent_ring_layers:
            m.record_latent_ring_decode(
                positions + 1, self.model_cfg.attn_window, self.cache.ring,
                self._ring_page, self._latent_ring_layers)
        if prev is not None:
            m.record_decode_ahead()
        elif out.committed:
            self._tokens_sharding = out.sharding
        self._in_flight = _InFlight(call, out, rows, positions, tables,
                                    slots)

    def _read(self, fl: _InFlight) -> None:
        """The host sync of a decode call: its tokens to their
        sequences, counted and stamped at the end of the read."""
        m = self.metrics
        out = fl.call.read(fl.out, np.asarray)
        m.finish(fl.call)
        later = self._in_flight if self._in_flight is not fl else None
        self._in_flight = later
        now = fl.call.end
        with m.phase("serve:decode_post"):
            self._record_window_positions(int(fl.positions.max()) + 1)
            if "mla" in self.cache.kinds:
                m.record_latent_positions(np.array(
                    [p + 1 for p, seq in zip(fl.positions, fl.rows)
                     if seq is not None]))
            n = 0
            for i, seq in enumerate(fl.rows):
                if seq is None:
                    continue
                n += 1
                seq.n_cached += 1
                seq.generated.append(int(out[i]))
                seq.token_times.append(now)
                if (later is not None and later.rows[i] is seq
                        and seq.finished(self.cfg.eos_id)):
                    later.discard(i)
            m.record_decode(fl.call.dur, n, self.cfg.max_batch)

    def _drain(self, cause: str) -> None:
        """Read the decode call in flight, if there is one, with no
        successor launched behind it: what follows needs the host's
        view of it (``cause``, one of ``metrics.DRAIN_CAUSES``)."""
        if self._in_flight is not None:
            self._read(self._in_flight)
            self.metrics.record_decode_drain(cause)
