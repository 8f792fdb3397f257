"""Paged KV-cache management for continuous-batching inference.

The decode batch packs variable-length sequences, so per-sequence
contiguous caches would either waste HBM on worst-case ``max_seq``
slots or force a recompile whenever the packing changes. Instead the
cache is a pool of fixed-size **blocks** (vLLM's PagedAttention
layout): device arrays shaped ``[L, n_blocks, block_size, Hkv, Dh]``
plus a host-side :class:`BlockAllocator` handing out block ids. Each
sequence owns a *block table* (row of physical block ids); the jitted
decode step gathers K/V pages through the table, so batch membership
can change every iteration without touching compiled code.

Block 0 is reserved as the **null block**: padded batch slots and
masked writes are routed there so the scatter in the decode step never
needs a branch, and its contents are never read (attention masks by
sequence length).

Blocks are **refcounted and content-addressed**: a full (immutable)
block can be published under a chained content hash
(``block_hash(parent_hash, block_tokens)``) and later requests whose
prompts share that whole-block prefix map the cached block straight
into their block table instead of recomputing its K/V (prefix
caching, the vLLM/SGLang "automatic prefix cache" design). A block
whose refcount drops to zero keeps its contents and parks in an LRU
pool; it is only *evicted* (contents forgotten) when a fresh
allocation finds the plain free list empty — so "free" capacity
usually means "still cached".

Reference analog: none — the reference framework (training-only
Horovod) has no inference path at all; this layout is the TPU-serving
standard (PagedAttention, vLLM SOSP'23).
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

NULL_BLOCK = 0
#: The ring of a window layer that padded batch rows write to.
NULL_SLOT = 0


def block_hash(parent: bytes, tokens) -> bytes:
    """Chained content hash of one full block: the parent is the hash
    of the preceding block (``b""`` for the first), so equal hashes
    imply an equal whole-token prefix, not just an equal block."""
    m = hashlib.blake2b(parent, digest_size=16)
    m.update(np.asarray(tokens, np.int64).tobytes())
    return m.digest()


def hash_chain(prompt, block_size: int) -> List[bytes]:
    """Chained content hash per full prompt block (the partial tail
    block, if any, stays private and unhashed). One chain entry per
    whole block; entry ``i`` summarizes the whole prefix through block
    ``i``. Shared between the engine (publish/lookup at admission) and
    the fleet router (cache-affinity placement walks replicas' indexes
    against the same chain) — both sides MUST hash identically or
    affinity routes to replicas whose index can never hit."""
    chain, h = [], b""
    for i in range(len(prompt) // block_size):
        h = block_hash(h, prompt[i * block_size:(i + 1) * block_size])
        chain.append(h)
    return chain


class OutOfBlocks(RuntimeError):
    """Raised by :meth:`BlockAllocator.alloc` when the pool cannot
    serve the request — the engine's admission backpressure signal."""


class BlockAllocator:
    """Host-side refcounted free-list over the device block pool.

    Paged allocation has no external fragmentation: any free block can
    serve any sequence, so ``can_alloc(n)`` is simply ``n <= n_free``.
    The plain free list is LIFO so recently-retired blocks (likely
    still warm in cache/HBM pages) are reused first, and allocation
    order is deterministic for tests.

    Three disjoint states partition the non-null blocks:

    * **live** — refcount >= 1 (``alloc`` hands out refcount-1 blocks;
      :meth:`acquire_cached` revives or shares them). Counted by
      ``n_used``.
    * **cached** — refcount 0 but content-addressed: parked in an LRU
      pool, still indexed by hash, revivable for free.
    * **free** — refcount 0, no retained content.

    ``n_free`` counts free + cached (both are allocatable); ``alloc``
    drains the plain free list first and only then evicts the
    least-recently-used cached blocks (``evictions`` counts those).
    Eviction can never touch a block with live references — only
    refcount-0 blocks enter the LRU pool.
    """

    def __init__(self, n_blocks: int, block_size: int):
        if n_blocks < 2:
            raise ValueError(
                f"need at least 2 blocks (1 usable + null), got {n_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.n_blocks = n_blocks
        self.block_size = block_size
        # Block 0 is the null sink — never handed out.
        self._free: List[int] = list(range(n_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}          # live block -> refcount
        # refcount-0 cached blocks, LRU order (oldest first = evicted
        # first); value is the block's content hash.
        self._lru: "collections.OrderedDict[int, bytes]" = \
            collections.OrderedDict()
        self._hash_of_block: Dict[int, bytes] = {}
        self._block_of_hash: Dict[bytes, int] = {}
        self._high_water = 0
        # Blocks with more than one reference (a prefix mapped into
        # several sequences at once), now and at most.
        self._shared = 0
        self._shared_high_water = 0
        # Prefix-cache observability (block granularity; the engine
        # layers token-granularity hit rate on top in ServeMetrics).
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.evictions = 0

    @property
    def n_free(self) -> int:
        """Allocatable blocks: truly free + cached (refcount 0)."""
        return len(self._free) + len(self._lru)

    @property
    def n_used(self) -> int:
        return len(self._refs)

    @property
    def n_cached(self) -> int:
        """Refcount-0 blocks still holding indexed content (the LRU
        pool a future prefix hit can revive for free)."""
        return len(self._lru)

    @property
    def high_water(self) -> int:
        """Peak concurrent blocks in use (capacity-planning stat)."""
        return self._high_water

    @property
    def n_shared(self) -> int:
        """Blocks that more than one sequence holds at once."""
        return self._shared

    @property
    def shared_high_water(self) -> int:
        return self._shared_high_water

    def refcount(self, block: int) -> int:
        return self._refs.get(block, 0)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache entries."""
        return -(-max(n_tokens, 0) // self.block_size)

    def can_alloc(self, n: int) -> bool:
        return n <= self.n_free

    def alloc(self, n: int) -> List[int]:
        if n > self.n_free:
            raise OutOfBlocks(
                f"requested {n} KV blocks, {self.n_free} free "
                f"({len(self._lru)} of them cached; pool "
                f"{self.n_blocks - 1} x {self.block_size} tokens)")
        out: List[int] = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                # Allocation pressure: forget the least-recently-used
                # cached block. Only refcount-0 blocks live here, so
                # eviction can never reclaim a referenced block.
                b, h = self._lru.popitem(last=False)
                del self._hash_of_block[b]
                del self._block_of_hash[h]
                self.evictions += 1
            self._refs[b] = 1
            out.append(b)
        self._high_water = max(self._high_water, len(self._refs))
        return out

    def peek(self, h: bytes) -> Optional[int]:
        """Non-mutating lookup: the block published under ``h`` (live
        or cached), or None. No refcount, no hit/miss counting, no
        LRU reordering — what admission uses to size its reservation
        before committing, so a backpressure retry loop doesn't
        inflate the cache stats or churn eviction order."""
        return self._block_of_hash.get(h)

    def acquire_cached(self, h: bytes) -> Optional[int]:
        """Prefix-cache lookup: if a block is published under ``h``,
        take a reference on it (reviving it from the LRU pool if it
        was refcount 0) and return its id; else record a miss and
        return None."""
        b = self._block_of_hash.get(h)
        if b is None:
            self.prefix_misses += 1
            return None
        if b in self._lru:
            del self._lru[b]
            self._refs[b] = 1
            self._high_water = max(self._high_water, len(self._refs))
        else:
            self._refs[b] += 1
            if self._refs[b] == 2:
                self._shared += 1
                self._shared_high_water = max(self._shared_high_water,
                                              self._shared)
        self.prefix_hits += 1
        return b

    def register(self, block: int, h: bytes) -> bool:
        """Publish a live, full, immutable ``block`` under content hash
        ``h``. Returns False (no-op) if ``h`` is already published —
        two sequences racing to prefill the same prefix both keep
        their private block; the first registration wins and the
        loser's copy stays anonymous (returns to the plain free list
        on release)."""
        if block not in self._refs:
            raise ValueError(
                f"registering block {block} with no live reference")
        if h in self._block_of_hash:
            return False
        if block in self._hash_of_block:
            raise ValueError(f"block {block} already registered")
        self._hash_of_block[block] = h
        self._block_of_hash[h] = block
        return True

    def verify_integrity(self) -> None:
        """Full-pool invariant check (the randomized property tests'
        probe — e.g. the speculative-rollback machine calls it after
        every trace): live / cached / free partition the non-null
        blocks exactly, refcounts are positive, the content index is a
        bijection, and every cached block is indexed. Raises
        ``AssertionError`` on any violation."""
        live, cached, free = (set(self._refs), set(self._lru),
                              set(self._free))
        assert len(self._free) == len(free), "duplicate free-list entry"
        assert not (live & cached) and not (live & free) \
            and not (cached & free), "block in two states"
        assert live | cached | free == set(range(1, self.n_blocks)), \
            "live/cached/free do not partition the pool"
        assert all(r > 0 for r in self._refs.values()), \
            "zero/negative refcount held as live"
        assert self._shared == sum(r > 1 for r in self._refs.values()), \
            "shared-block count out of sync"
        assert len(self._block_of_hash) == len(self._hash_of_block), \
            "content index out of sync"
        for b, h in self._hash_of_block.items():
            assert self._block_of_hash[h] == b, "index not a bijection"
        for b in cached:
            assert b in self._hash_of_block, "anonymous block in LRU"

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per listed block. A block whose refcount
        reaches 0 parks in the LRU cache pool if it was registered
        (revivable by a future prefix hit), else returns to the plain
        free list."""
        seen = set()
        for b in blocks:
            if not 0 < b < self.n_blocks:
                raise ValueError(f"freeing invalid block id {b}")
            if b not in self._refs or b in seen:
                raise ValueError(f"double free of block {b}")
            seen.add(b)
        # Validate-all-then-mutate: the pool is untouched on error.
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b]:
                self._shared -= self._refs[b] == 1
                continue
            del self._refs[b]
            h = self._hash_of_block.get(b)
            if h is None:
                self._free.append(b)
            else:
                self._lru[b] = h        # most-recently-released last


#: The kinds of layer that keep something between calls, in the order
#: their arrays take in :class:`KVCache` (``full`` and ``sliding`` first
#: and in this order: the pair the window configurations' programs and
#: their benchmark read by place).
STATE_KINDS = ("full", "sliding", "kda", "mla", "mamba", "sparse",
               "lightning", "conv", "eva", "mamba2", "mla_sliding")
#: Those of them whose arrays lie by batch slot and not behind the block
#: tables: nothing of theirs is a page that another sequence, another
#: engine or a draft could be handed (what ``engine.py`` refuses over
#: them, and what ``KVCache.slot_bytes`` counts); and those of these
#: that are a recurrent state. ``conv`` joins ``SLOT_KINDS`` alone: its
#: rows lie by slot, so everything refused over a slot's state is
#: refused over them and ``state_slots_in_use`` / ``state_bytes`` count
#: them (the engine reads ``SLOT_KINDS`` for both, the rings apart,
#: which have their own gauge), but they are the layer's last
#: ``conv_taps - 1`` inputs and no recurrence (nothing of a position
#: further back is in them, and nothing in float32 lies beside them).
#: ``RECURRENT_KINDS`` only names which slot kinds are recurrences:
#: nothing in the engine treats them apart. ``eva`` is the one kind with
#: BOTH halves: its summaries are pages behind the tables, but the open
#: window's K and V rows lie by slot, so it is in ``SLOT_KINDS``
#: (everything refused over a slot's state is refused over the rows: a
#: prefix mapped as pages would bring no rows, and ``migrate`` /
#: ``inject`` move pages alone) and not in ``RECURRENT_KINDS``: the rows
#: are the window's own keys and values in the cache's dtype, nothing
#: is folded over positions and a closed window's rows are dead.
#: ``mla_sliding`` joins ``SLOT_KINDS`` as ``sliding`` does: its ring
#: of latents lies by slot, so everything refused over a ring of keys
#: is refused over it. ``RING_KINDS``: the two whose slot holds a ring
#: of the newest positions (``KVCache.ring`` places; the engine's ring
#: gauges count them, ``state_slots_in_use`` / ``state_bytes`` do not).
RECURRENT_KINDS = ("kda", "mamba", "lightning", "mamba2")
RING_KINDS = ("sliding", "mla_sliding")
SLOT_KINDS = ("sliding",) + RECURRENT_KINDS + ("conv", "eva", "mla_sliding")


def state_kinds(cfg) -> Tuple[str, ...]:
    """The kinds of a ``cfg.mixed`` configuration's layers, in
    :data:`STATE_KINDS`' order. A configuration that may hold window
    layers keeps both ``full`` and ``sliding`` (either may have no
    layer: an array with a leading 0)."""
    if not cfg.stateful:
        return STATE_KINDS[:2]
    return tuple(k for k in STATE_KINDS if cfg.n_layers_of(k))


@dataclasses.dataclass
class KVCache:
    """What the layers keep on the device between calls.

    A configuration of one kind of layer: one K and one V array per
    model, ``[L, n_blocks, block_size, Hkv, Dh]``, layer-stacked on the
    leading dim to match the transformer's scan-over-layers parameter
    layout.

    A configuration with layers of several kinds (``cfg.mixed``) keeps
    **a state by kind of layer**: ``kinds`` names them, and
    ``of(kind)`` gives a kind's arrays, a tuple of the kind's own
    length (one, two or three), each stacked over that kind's layers.
    The serve programs take and return them as ``k`` and ``v``, place
    for place by kind: ``k`` a kind's first array and ``v`` its second
    (``None`` where it has one), and where it has three its first two
    as a pair under ``k``:

    * ``full``: K and V pages ``[n, n_blocks, block_size, Hkv, Dh]``
      behind the block tables, as above; where a head is narrower than
      the chip's 128 lanes and the heads together fill whole lanes
      (:func:`page_tail`), ``[n, n_blocks, block_size, Hkv * Dh]``, a
      position's heads as ONE row;
    * ``sliding``: K and V rings ``[n, n_slots + 1, ring, Hkv, Dh]``,
      one ring of ``ring`` positions a batch slot; position p of a
      sequence lies at ``p % ring``;
    * ``kda``: the recurrent state ``[n, n_slots + 1, H, Dh, Dh]``
      float32, and the newest ``kda_conv - 1`` rows before the
      convolution ``[n, n_slots + 1, kda_conv - 1, 3 * H * Dh]``;
    * ``mla``: the latent pages ``[n, n_blocks, block_size,
      latent_row(cfg)]`` behind the block tables, and no second array;
    * ``mamba``: the selective scan's state ``[n, n_slots + 1,
      mamba_d_state, Di]`` float32 (``Di = mamba_expand * d_model``; a
      state's ``[Di, N]`` turned so that its channels lie along the
      lanes: with the 16 values of a channel innermost the chip's
      (8, 128) tiles would hold 16 of 128 lanes), and the newest
      ``mamba_d_conv - 1`` rows before the convolution, end to end,
      ``[n, n_slots + 1, (mamba_d_conv - 1) * Di]`` (as ``[.., 3, Di]``
      the 3 rows were a tile's 16 and every program copied the array on
      its way in and out, a tenth of the device's time: chip, PR 47);
    * ``sparse``: K pages ``[n, n_blocks, Hkv, block_size, Dh]``, the
      COMPRESSED keys ``[n, n_blocks, Hkv, block_size // sparse_stride,
      Dh]`` (the means over the kernels that START in a page, behind
      the same block tables: kernel j lies in page ``j // per`` at
      ``j % per``), and V pages as K's. A page is a selected block
      (``block_size`` is ``sparse_block``) and holds a KV head's
      positions together: a decode step gathers the chosen pages of ONE
      head a GQA group, and with the heads innermost, as a ``full``
      layer's pages have them, every program turned the whole pool over
      on its way in and back out (four copies of 545 MB a decode step:
      compiled for the v5e, PR 50);
    * ``lightning``: the decayed linear state ``[n, n_slots + 1, heads,
      Dh, Dh]`` float32, and no second array;
    * ``conv``: the newest ``conv_taps - 1`` rows before the
      convolution, end to end as a mamba layer's,
      ``[n, n_slots + 1, (conv_taps - 1) * d_model]`` in the
      configuration's dtype, and nothing else: no recurrent state and
      no second array;
    * ``eva``: BOTH halves, four arrays (a pair under ``k`` and a pair
      under ``v``). The open window's K rows and V rows by batch slot,
      ``[n, n_slots + 1, eva_window, Hkv, Dh]``: position ``t`` lies at
      row ``t % eva_window`` (aligned: nothing rolls), and rows ``0 ..
      t % eva_window`` are live, whatever an earlier window or sequence
      left behind them. And the chunk summaries ``k~`` and ``v~`` in
      pages behind the block tables, ``[n, n_blocks, block_size //
      eva_chunk, Hkv, Dh]``: chunk ``m`` (positions ``eva_chunk * m ..``)
      lies in the page of its positions, table entry ``eva_chunk * m //
      block_size``, at row ``m % (block_size // eva_chunk)``, written
      when the chunk closes; attention reads the summaries of CLOSED
      windows alone, ``m < (t // eva_window) * (eva_window //
      eva_chunk)``. ``block_size`` is whole chunks and divides the
      window (:func:`init_kv_cache`), and is otherwise the engine's:
      the pages are allocated position by position as any layer's, and
      a chunk of a prompt is whole blocks as everywhere. (A page a
      WINDOW, ``block_size = eva_window``, is the same layout at its
      widest; the engine's chunks and prefill buckets are whole blocks,
      so a stack that is cut into 1024-chunks takes a smaller one, and
      ``full`` layers beside it share the block size.)

    * ``mamba2``: the SSD state ``[n, n_slots + 1, Hm, P, N]`` float32
      (``Hm = mamba_expand * d_model / mamba2_head_dim`` heads of ``P =
      mamba2_head_dim`` values by ``N = mamba_d_state`` columns: 4 MB a
      slot a layer at Nemotron's 128 x 64 x 128, thirteen times a mamba
      layer's; ``N`` innermost fills the chip's 128 lanes as published,
      so nothing is turned), and the newest ``mamba_d_conv - 1`` rows
      before the convolution, end to end as a mamba layer's, ``[n,
      n_slots + 1, (mamba_d_conv - 1) * (Di + 2 G N)]``. A layer that
      ``layer_types`` names ``"ffn"`` (``one_branch``) keeps nothing and
      has no array.
    * ``mla_sliding``: rings of latents ``[n, n_slots + 1, ring,
      latent_row(cfg)]``, one ring a batch slot as a ``sliding`` layer's
      (position p at ``p % ring``), each place an ``mla`` layer's row,
      and no second array: 1280 B a place at Motif's 576 -> 640 bf16,
      where K and V rings of 16 heads of 192 and 128 would be 10 KB.

    Pages are the allocator's; rings and states are addressed by batch
    slot, slot 0 the null slot, and take nothing from the allocator
    however long a sequence grows. A slot needs no cleaning: a new
    sequence's first chunk starts from a zero state and an empty
    ring."""

    k: Any  # [L, n_blocks, block_size, Hkv, Dh], or an array a kind
    v: Any
    block_size: int
    n_blocks: int
    ring: int = 0   # positions a window layer keeps a sequence
    kinds: Tuple[str, ...] = ()

    def of(self, kind: str) -> Tuple[Any, ...]:
        """``kind``'s arrays, in the order the class lists them."""
        i = self.kinds.index(kind)
        def arrays(of):
            return () if of is None else of if isinstance(of, tuple) else (of,)
        return arrays(self.k[i]) + arrays(self.v[i])

    def by_slot(self, kind: str) -> Tuple[Any, ...]:
        """Those of ``kind``'s arrays that lie by batch slot: all of a
        ``SLOT_KINDS`` kind's, but of ``eva``'s four the two of rows
        (the other two are pages)."""
        if kind not in SLOT_KINDS:
            return ()
        return self.of(kind)[::2] if kind == "eva" else self.of(kind)

    @property
    def slot_bytes(self) -> int:
        """Bytes a batch slot holds whatever its sequence's length:
        its rings, its recurrent state, its open window's rows."""
        # from the shapes: `a[:, 0]` is a slice ON THE DEVICE, and the
        # engine reads this gauge every step (with an eva layer's rows a
        # slice and a copy of 134 MB each, 8 % of the device's time:
        # chip, PR 56)
        return sum(a.size // a.shape[1] * a.dtype.itemsize
                   for kind in self.kinds for a in self.by_slot(kind))

    @property
    def max_blocks_per_seq(self) -> int:
        # Shapes are static per engine: table width is the worst case.
        return self.n_blocks


def latent_row(cfg) -> int:
    """Values a position takes in the mla layers' pool (and in an
    mla_sliding layer's ring): the ``C + R``
    the layer caches, up to whole lanes of 128 (576 -> 640, the last 64
    zeros). Rows of 576 are what the chip's (8, 128) tiles pad to 640
    wherever a row is the innermost dimension; an array
    ``[.., block, 576]`` is therefore kept with its BLOCKS innermost
    (the layout without padding), and every program that gathers pages
    turned the whole pool over on its way in and back on its way out,
    1.28 GB each way a call (compiled for the v5e, PR 38). At 640 the
    layout the gather reads is the array's own."""
    return -(-(cfg.mla_kv_rank + cfg.mla_rope_dim) // 128) * 128


def page_tail(cfg) -> Tuple[int, ...]:
    """What a ``full`` layer's page holds of a position: ``(Hkv, Dh)``,
    or ``(Hkv * Dh,)``, the heads end to end, where ``Dh`` is not whole
    lanes of 128 and ``Hkv * Dh`` is. With 8 heads of 64 innermost the
    chip keeps an array ``[.., block, 8, 64]`` with its BLOCKS innermost
    (the layout without padding, as :func:`latent_row` found of rows of
    576), and every program that writes or gathers pages turned the
    whole pool over on its way in and back out: 20 copies of 1 GB a
    decode step and 7.1 GB of temporaries beside 11.4 GB of arguments
    (compiled for the v5e, PR 54). As rows of 512 the layout the scatter
    and the gather read is the array's own. A head of whole lanes keeps
    its own dimension (those programs lower as before), but for 2 to 7
    of them: ``[.., block, 2, 128]`` is kept in tiles of (2, 128), and a
    chunk's scatter of whole blocks and its gather behind the table turn
    the whole pool to blocks-second-innermost and back, 4 copies of 335
    MB a chunk at Nemotron's 2 heads (8 M cycles each: compiled for the
    v5e, PR 60; none as rows of 256). One head (Jamba2's) and eight or
    more (Trinity's) fill or need no such tile and stay as they were."""
    heads, width = cfg.n_kv_heads, cfg.head_dim
    if (heads * width) % 128 == 0 and (width % 128 or 1 < heads < 8):
        return (heads * width,)
    return (heads, width)


def ring_width(window: int, chunk: int, block_size: int) -> int:
    """Positions a window layer's ring keeps: a chunk's last query sees
    ``window`` keys back from itself and its first query ``window``
    back from the chunk's start, ``window + chunk - 1`` positions in
    all, which must all lie in the ring once the chunk is written
    (padding of its bucket included); a block more, so the width stays
    a whole number of blocks."""
    return window + chunk + block_size


def init_kv_cache(cfg, n_blocks: int, block_size: int,
                  mesh: Optional[Any] = None,
                  dtype: Optional[Any] = None, *, n_slots: int = 0,
                  ring: int = 0) -> KVCache:
    """Allocate the zeroed block pool on device (for a configuration
    with layers of several kinds, each kind's arrays, those addressed
    by slot for ``n_slots`` batch slots: see :class:`KVCache`).

    With a mesh, KV heads are sharded over ``tp`` (matching the
    tp-sharded ``wk``/``wv`` projections so the decode step's cache
    writes stay local to each tp shard — no resharding on the hot
    loop, the EQuARX-motivated property of keeping collectives on ICI
    inside the jitted step).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    dtype = dtype or cfg.dtype
    if cfg.mixed:
        H, Dh = cfg.n_heads, cfg.head_dim
        tail = (cfg.n_kv_heads, Dh)
        d_inner = cfg.mamba_expand * cfg.d_model
        n = {kind: cfg.n_layers_of(kind) for kind in STATE_KINDS}
        if n["sparse"] and block_size != cfg.sparse_block:
            raise ValueError(
                f"a sparse layer's selected block is a page: block_size "
                f"{block_size} is not sparse_block {cfg.sparse_block}")
        if n["eva"] and (block_size % cfg.eva_chunk
                         or cfg.eva_window % block_size):
            raise ValueError(
                f"an eva layer's page holds whole chunks of a window: "
                f"block_size {block_size} is not whole eva_chunk "
                f"{cfg.eva_chunk} or does not divide eva_window "
                f"{cfg.eva_window}")
        pages = ((n["sparse"], n_blocks, cfg.n_kv_heads, block_size, Dh),
                 dtype)
        eva = (((n["eva"], n_slots + 1, cfg.eva_window) + tail, dtype),
               ((n["eva"], n_blocks, block_size // cfg.eva_chunk) + tail,
                dtype))
        shapes = {   # kind -> (shape, dtype) of what k and v hold of it
            "full": 2 * (((n["full"], n_blocks, block_size) + page_tail(cfg),
                          dtype),),
            "sliding": 2 * (((n["sliding"], n_slots + 1, ring) + tail,
                             dtype),),
            "kda": (((n["kda"], n_slots + 1, H, Dh, Dh), jnp.float32),
                    ((n["kda"], n_slots + 1, cfg.kda_conv - 1, 3 * H * Dh),
                     dtype)),
            "mla": (((n["mla"], n_blocks, block_size, latent_row(cfg)),
                     dtype), None),
            "mamba": (((n["mamba"], n_slots + 1, cfg.mamba_d_state, d_inner),
                       jnp.float32),
                      ((n["mamba"], n_slots + 1,
                        (cfg.mamba_d_conv - 1) * d_inner), dtype)),
            "sparse": ((pages, ((n["sparse"], n_blocks, cfg.n_kv_heads,
                                 block_size // cfg.sparse_stride, Dh),
                                dtype)), pages),
            "lightning": (((n["lightning"], n_slots + 1, cfg.n_heads, Dh, Dh),
                           jnp.float32), None),
            "conv": (((n["conv"], n_slots + 1,
                       (cfg.conv_taps - 1) * cfg.d_model), dtype), None),
            "eva": (eva, eva),   # (K rows, k~ pages), (V rows, v~ pages)
            "mamba2": (((n["mamba2"], n_slots + 1, cfg.mamba2_heads,
                         cfg.mamba2_head_dim, cfg.mamba_d_state),
                        jnp.float32),
                       ((n["mamba2"], n_slots + 1,
                         (cfg.mamba_d_conv - 1) * cfg.mamba2_conv_width),
                        dtype)),
            "mla_sliding": (((n["mla_sliding"], n_slots + 1, ring,
                              latent_row(cfg)), dtype), None),
        }
        kinds = state_kinds(cfg)

        def zeros(of):
            """``of``: a (shape, dtype), a pair of such, or None."""
            if of is None:
                return None
            if isinstance(of[1], tuple):
                return tuple(map(zeros, of))
            return jnp.zeros(*of)

        def arrays(place):
            return tuple(zeros(shapes[kind][place]) for kind in kinds)
        return KVCache(k=arrays(0), v=arrays(1), block_size=block_size,
                       n_blocks=n_blocks, ring=ring, kinds=kinds)
    shape = (cfg.n_layers, n_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    sharding = None
    if mesh is not None:
        tp = mesh.shape.get("tp", 1)
        if tp > 1 and cfg.n_kv_heads % tp == 0:
            sharding = NamedSharding(mesh, P(None, None, None, "tp", None))
    def zeros():
        return jnp.zeros(shape, dtype)
    if sharding is not None:
        k = jax.jit(zeros, out_shardings=sharding)()
        v = jax.jit(zeros, out_shardings=sharding)()
    else:
        k, v = zeros(), zeros()
    return KVCache(k=k, v=v, block_size=block_size, n_blocks=n_blocks)


def pick_bucket(n: int, buckets: Tuple[int, ...]) -> int:
    """Smallest bucket >= n (buckets ascending). Bucketing pads batch
    and prompt shapes to a short menu of sizes so the jit cache stays
    small and hot — the no-per-request-recompilation invariant."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds largest bucket {buckets[-1]}")


def page_chunks(n_pages: int, chunk_pages: int) -> List[Tuple[int, int]]:
    """Block-aligned ``[start, stop)`` ranges covering ``n_pages`` KV
    pages in ``chunk_pages``-sized pieces (last one ragged). This is
    the one chunking function shared by the direct-migration stream
    (worker side), chunked inject (engine side), and the Python cost
    twin — all three must agree on the chunk boundaries or the
    scheduled cost describes a transfer that never happens."""
    if n_pages < 0:
        raise ValueError(f"n_pages {n_pages} < 0")
    if chunk_pages < 1:
        raise ValueError(f"chunk_pages {chunk_pages} < 1")
    return [(lo, min(lo + chunk_pages, n_pages))
            for lo in range(0, n_pages, chunk_pages)]
