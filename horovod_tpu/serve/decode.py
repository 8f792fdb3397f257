"""jit'd prefill + decode step functions over the paged KV cache.

These compiled programs drive all serving traffic:

* :func:`prefill` — run one prompt (padded to a length bucket) through
  the transformer, write its K/V into the sequence's cache blocks, and
  emit the first generated token from the last real position's logits.
* :func:`prefill_resume` — the preemptible/suffix variant: run a
  *chunk* of a prompt starting at a block-aligned token ``offset``,
  attending over the pages already present in the sequence's blocks
  (a prefix mapped in from the content-addressed cache, or earlier
  chunks of the same prompt) and writing the chunk's new pages through
  the block table. The chunk length is a new jit bucket dimension;
  ``offset`` stays traced. This is what makes prefix-cache hits pay
  only suffix FLOPs and lets the engine interleave long prefills with
  decode iterations (chunked prefill).
* :func:`decode` — one iteration-level step for the whole running
  batch (padded to a batch bucket): embed each sequence's last token,
  append its K/V at the sequence's current position through the block
  table, attend against the gathered pages, and emit the next token
  per sequence.
* ``verify`` — the speculative chunk step: ``decode``'s addressing
  with ``prefill_resume``'s mask over a few tokens per sequence.

All are shape-bucketed (see ``kv_cache.pick_bucket``) so the jit
cache holds a handful of programs total — batch membership, sequence
lengths, and block placement all change per step without recompiling.

Sharding: params arrive sharded by ``models.transformer.param_specs``
(tp on heads/FFN-hidden, fsdp on the other matrix dim), the KV pool is
tp-sharded on the KV-head dim (``kv_cache.init_kv_cache``), and GSPMD
propagates — the attention-out and FFN-down matmuls end in the same
in-jit tp ``psum`` pair as the training forward, so tensor-parallel
decode exercises :mod:`horovod_tpu.ops.collectives`' data plane on the
hot loop (the EQuARX property: collectives stay inside the XLA
program, on ICI).

Every program runs the same layers, written once (``layers`` in
:func:`_cached_serve_fns`) around ``models.transformer``'s
``attention_inputs`` and ``ffn_block``, the two halves of the trainer's
``decoder_layer``. A program differs from another only in the positions
of its queries, in how a layer's new K/V is written (whole blocks
through block ids, or single rows at a block and an offset) and in
which attention it runs (prompt-local, or :func:`_attend_pages` over
the block tables). So incremental decode tracks the full-context
forward to float tolerance, and served decode is bit-identical to
single-request decode (same programs, row-independent math).

The layer scan carries the whole pool ``[L, n_blocks, bs, Hkv, Dh]``
beside the activations and scans over (a layer's weights, its index
``l``): a layer writes its rows at ``[l, block, offset]`` (or its
blocks at ``[l, blocks]``) and gathers its pages through ``(l,
table)``. The pool is donated and a scan's carry is updated where it
lies, so a call writes the new rows and reads the pages it attends
over. The pool must stay the carry: as the scan's ``xs`` and ``ys`` it
cannot be written over while it is read, and every call then slices
each layer's pool out, stacks it back into a second pool and copies
that (two pools' worth of temporaries, a third of a decode step;
``tests/test_tpu_lowering.py`` counts the pool-sized operations of the
compiled programs, ``tests/test_serve_pool.py`` holds tokens and pool
bitwise to that form).

Attention over the cache is one function, :func:`_attend_pages`, for
``prefill_resume``, ``decode`` and ``verify``: the pages are gathered
through the layer and the block table once, in the cache's dtype and
with their Hkv heads (never repeated across the GQA group, never
copied to float32), and contracted with the queries grouped over KV
heads — the group is a free dimension of both dots — with float32
scores, softmax and accumulators. Only the monolithic ``prefill``
attends prompt-locally (:func:`_attend_prompt`): past 512 tokens
through the Pallas flash forward of ``ops/flash_attention.py``, the
kernel the trainer runs, with the group as the kernel's index map and
the float32 scores a tile at a time in VMEM, so a long cold prompt
holds no ``[H, T, T]`` tensor and no repeated K or V in HBM either; up
to 512, where that tensor is small and XLA's fusions of it are the
faster, in the dense form.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import moe as moe_lib
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import local_attention
from horovod_tpu.serve.kv_cache import NULL_BLOCK

_NEG_BIG = -1e30  # matches ring_attention's finite "-inf"


#: The longest prompt that attends through the dense form (scores as
#: one float32 ``[H, T, T]`` tensor, K and V repeated across the GQA
#: group). Up to here the tensor is at most 33 MB and XLA's fusions of
#: it are faster than the kernel's 32 grid steps of float32 dots: on
#: the v5e at ``[1, T, 32 / 8, 128]`` bf16, ms a layer dense / flash
#: (``tools/prefill_attn_sweep.py``): 128 0.047 / 0.062, 256 0.048 /
#: 0.088, 512 0.079 / 0.123, and ``prefill_p50_ms.chat`` read 16.1-16.3
#: dense and 16.6-16.8 through the kernel. Past it the scores leave fast
#: memory: 1024 0.44 / 0.18, 2048 1.61 / 0.54 alone, and several times
#: that inside ``prefill`` (PERF.md, PR 35).
_DENSE_PROMPT = 512


def _prompt_block(t: int) -> int:
    """The flash forward's square tile for a prompt of ``t`` tokens:
    the fewest blocks of at most 1024 that cover it, evenly sized, in
    multiples of 128. Up to 1024 that is the kernel's own default, one
    sequence-spanning block; past it the default would pad every length
    to a multiple of 1024 and run three 1024-blocks for any ``t`` up to
    2048, where two blocks of half the length cover it with no padding
    and a causal grid of three. On the v5e at ``[1, t, 32 / 8, 128]``
    bf16, ms a layer (``tools/prefill_attn_sweep.py``): 1280 **0.33**
    (block 640) against 0.58 (1024) and 0.50 (512); 1536 **0.41** (768)
    against 0.57 and 0.49; 1792 **0.50** (896) against 0.56 and 0.77;
    2048 0.54 (1024) against 0.76 (512)."""
    n = -(-t // 1024)
    return -(-t // (128 * n)) * 128


def _attend_prompt(q, k, v, mesh=None):
    """Causal attention of a whole prompt over itself (the monolithic
    ``prefill``): q [1, T, H, Dh], k/v [1, T, Hkv, Dh] as projected.
    Returns [1, T, H * Dh]. One mathematics in two forms, chosen by
    ``T`` alone (the shape is what the code observes): float32 scores,
    softmax and accumulators over the inputs' dtype, and the keys of a
    bucket's padding (positions >= ``length``) seen only by padded
    queries, which ``emit`` never reads.

    A short prompt (``T <= _DENSE_PROMPT``) is ``local_attention`` over
    K and V repeated across the GQA group. A longer one is the Pallas
    flash forward (``ops/flash_attention.py``): the group is an index
    map of the kernel, so K and V are read with their Hkv heads and
    never repeated, and scores, softmax statistics and the accumulator
    are tiles in VMEM, so no ``[H, T, T]`` tensor reaches HBM and
    blocks above the diagonal are skipped. Any ``T`` is padded to the
    kernel's blocks inside it and the padded keys are masked there.

    Over a ``mesh`` the kernel runs as an island manual over every
    axis, its heads sharded over ``tp`` as the projections leave them
    and replicated over the rest (GSPMD cannot partition a Mosaic
    call; the dense form it partitions by itself); where ``tp`` does
    not divide Hkv, K and V are repeated up to H first, as the
    trainer's island does."""
    t, rep = q.shape[1], q.shape[2] // k.shape[2]
    if t <= _DENSE_PROMPT:
        return local_attention(q, jnp.repeat(k, rep, axis=2),
                               jnp.repeat(v, rep, axis=2),
                               causal=True).reshape(*q.shape[:2], -1)
    block = _prompt_block(t)
    attend = functools.partial(flash_attention, causal=True, block_q=block,
                               block_k=block)
    if mesh is not None:
        if k.shape[2] % mesh.shape.get("tp", 1):
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        heads = P(None, None, "tp" if "tp" in mesh.axis_names else None)
        attend = jax.shard_map(
            attend, mesh=mesh, in_specs=(heads, heads, heads),
            out_specs=heads, axis_names=frozenset(mesh.axis_names),
            check_vma=False)
    return attend(q, k, v).reshape(*q.shape[:2], -1)


def _attend_pages(q, kc, vc, l, tables, pos):
    """Attention of one query chunk per sequence over all of the
    sequence's pages: the one paged attention of ``prefill_resume``
    (B = 1), ``decode`` (C = 1) and ``verify``.

    ``q`` [B, C, H, Dh] (post-rope); ``kc``/``vc`` the whole pool
    [L, n_blocks, bs, Hkv, Dh] and ``l`` the layer (a traced scalar in
    the layer scan); ``tables`` [B, W] block ids (unused entries hold
    the null block); ``pos`` [B, C] the queries' global positions.
    Returns [B, C, H * Dh] in ``q``'s dtype.

    Each page is read once, through ``(l, table)`` in one gather (no
    layer-sized slice of the pool is taken first), in the cache's dtype
    and with its Hkv heads: the GQA group (``rep`` = H // Hkv, 1 for
    MHA) is a free dimension of both dots, so a (sequence, KV head)
    pair is one ``[rep * C, Dh] x [Dh, S]`` matmul, not ``rep`` vector
    products over a repeated copy of K and V. Scores, softmax and
    accumulators are float32; key j is visible to the query at global
    position p iff j <= p, and every such key is real: a prefix written
    before this call, or the chunk's own keys written by ``kv_write``
    just before it."""
    B, C, H, Dh = q.shape
    Hkv, S = kc.shape[3], tables.shape[1] * kc.shape[2]
    with jax.named_scope("kv_gather"):
        kp = kc[l, tables].reshape(B, S, Hkv, Dh)
        vp = vc[l, tables].reshape(B, S, Hkv, Dh)
    qg = q.reshape(B, C, Hkv, H // Hkv, Dh)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kp,
                   preferred_element_type=jnp.float32) * Dh ** -0.5
    mask = jnp.arange(S, dtype=jnp.int32) <= pos[:, :, None]     # [B, C, S]
    s = jnp.where(mask[:, None, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(vp.dtype), vp,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o.reshape(B, C, H * Dh)


def make_serve_fns(cfg, mesh: Optional[Any] = None, *, block_size: int,
                   table_width: int, compression=None, ring: int = 0):
    """Build (prefill, prefill_resume, decode, inject, verify) jitted
    closures for ``cfg`` over ``mesh``. ``table_width`` is the static
    block-table row length (blocks per sequence, worst case); caches
    are donated so steady-state decode — and the handoff-page
    ``inject`` scatter — update the pool in place. ``verify`` is the
    speculative-decoding chunk step (one target pass over k proposed
    tokens; see serve/speculative.py).

    ``compression`` (a ``hvd.Compression`` member; None = uncompressed,
    bitwise the pre-existing programs) is the serving face of the same
    knob the training planes read: it narrows the embed table's mesh
    movement in every prefill/decode program (see
    ``transformer.embed_lookup``) — the per-step table reshard is the
    one table-sized transfer on the decode hot loop when the vocab-
    parallel island can't run.

    A configuration whose layers are of more than one kind
    (``cfg.mixed``: a leading dense stack, window and full attention)
    or that holds a chip's share of the experts gets the programs of
    :func:`_mixed_serve_fns`, over two kinds of cache; ``ring`` is the
    positions a window layer keeps for a sequence
    (``kv_cache.ring_width``). It has no ``inject`` and no ``verify``.

    Memoized: engines sharing (cfg, mesh, block geometry, compression)
    — e.g. the benchmark's continuous and static schedulers, or a
    fleet of per-tenant engines — reuse one pair of jit closures and
    therefore one compiled program per shape bucket."""
    sigmoid_share = cfg.moe is not None and cfg.moe.scoring == "sigmoid"
    unserved = [what for what, there in (
        ("qk_norm", cfg.qk_norm),
        ("a softmax-routed MoE without a capacity "
         "(moe_capacity_factor=None)",
         cfg.moe is not None and cfg.moe.capacity_factor is None
         and not sigmoid_share)) if there]
    if unserved:
        raise NotImplementedError(
            f"the serve programs do not serve {' or '.join(unserved)} yet: "
            "no reference holds a served model of that kind to anything "
            "(ROADMAP B7). The configuration trains through "
            "make_train_step.")
    if cfg.mixed:
        spread = {a: n for a, n in (mesh.shape.items() if mesh is not None
                                    else ()) if a in ("tp", "ep") and n > 1}
        if spread:
            raise NotImplementedError(
                "the serve programs of a configuration with layers of "
                "several kinds or a chip's share of the experts run on one "
                f"chip: a mesh with {spread} would shard its two caches "
                "and its held experts, which nothing does yet (ROADMAP "
                "B7(ii))")
        return _mixed_serve_fns(cfg, block_size, table_width, ring,
                                compression)
    return _cached_serve_fns(cfg, mesh, block_size, table_width,
                             compression)


@functools.lru_cache(maxsize=64)
def _cached_serve_fns(cfg, mesh, block_size: int, table_width: int,
                      compression=None):
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim

    # Every program names its parts for a device trace (`embed`, `attn`
    # with `kv_write` / `kv_gather` inside it, `mlp`, `head`): the
    # benchmark's per-layer metrics find them by these names (PERF.md
    # §3), so a rename is a change to that interface.
    def embed(params, tokens):
        with jax.named_scope("embed"):
            return tf_lib.embed_lookup(params["embed"], tokens, cfg.dtype,
                                       mesh, compression)

    def layers(params, kc, vc, x, pos, write, attend):
        """The layers of every program, over ``x`` [B, T, D] (embedded
        tokens) at ``pos`` [B, T] (their global positions). The scan
        carries the whole pool beside ``x`` and hands the body the
        layer's index ``l``: ``write(kc, vc, l, k, v) -> (kc, vc)``
        puts layer ``l``'s new K/V [B, T, Hkv, Dh] into the pool
        (``write_blocks`` or ``write_rows`` at the program's
        addresses); ``attend(q, k, v, kc, vc, l) -> [B, T, H * Dh]`` is
        prompt-local or over the pool just written. Returns
        (kc, vc, x)."""
        def body(carry, per_layer):
            x, kc, vc = carry
            lp, l = per_layer
            with jax.named_scope("attn"):
                q, k, v = tf_lib.attention_inputs(cfg, lp, x, pos)
                with jax.named_scope("kv_write"):
                    kc, vc = write(kc, vc, l, k, v)
                o = attend(q, k, v, kc, vc, l)
                x = x + (o @ lp["wo"]).astype(cfg.dtype)
            with jax.named_scope("mlp"):
                # the aux loss is routing telemetry only at serve time
                x, _aux = tf_lib.ffn_block(cfg, lp, x)
            return (x, kc, vc), None

        (x, kc, vc), _ = lax.scan(
            body, (x, kc, vc),
            (params["layers"], jnp.arange(kc.shape[0], dtype=jnp.int32)))
        return kc, vc, x

    def write_blocks(kc, vc, l, k, v, blks):
        """Layer ``l``'s K/V of one block-aligned chunk (B = 1) as
        whole blocks, at the block ids ``blks`` [n_blk]. Ids past a
        sequence's allocation are the null block (id 0): garbage
        written there is never read (attention masks by length)."""
        def put(pool, new):
            return pool.at[l, blks].set(
                new[0].reshape(-1, block_size, Hkv, Dh).astype(pool.dtype))
        return put(kc, k), put(vc, v)

    def write_rows(kc, vc, l, k, v, pos, block_tables):
        """Layer ``l``'s K/V of one token ([B]) or a few ([B, C],
        starting mid-block) per sequence as single rows, at the
        (block, offset) of their positions ``pos`` through
        ``block_tables`` [B, table_width]. Positions past the table (a
        speculative draft's proposal frontier near a sequence's cap)
        route to the null block: the unguarded take_along_axis would
        CLAMP the slot and overwrite the sequence's last real block
        instead."""
        slot = pos // block_size
        blk = jnp.take_along_axis(
            block_tables,
            jnp.minimum(slot, table_width - 1).reshape(pos.shape[0], -1),
            axis=1).reshape(pos.shape)
        blk = jnp.where(slot < table_width, blk, NULL_BLOCK).reshape(-1)
        off = (pos % block_size).reshape(-1)

        def put(pool, new):
            return pool.at[l, blk, off].set(
                new.reshape(-1, Hkv, Dh).astype(pool.dtype))
        return put(kc, k), put(vc, v)

    def emit(params, x, rows):
        """Final norm of ``x`` [B, T, D], the rows wanted of it
        (``rows(x) -> [..., D]``), the head, the argmax of each. (Not
        named ``head``: that is the scope, and a lowering's locations
        hold function names beside scope names.)"""
        with jax.named_scope("head"):
            x = rows(tf_lib._rmsnorm(x, params["final_norm"], cfg.norm_eps))
            logits = (x @ params["lm_head"]).astype(jnp.float32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def prefill(params, kc, vc, tokens, length, block_table):
        """tokens [Tp] (bucket-padded), length scalar i32 (real prompt
        length), block_table [table_width] i32. Returns (kc, vc,
        first_token)."""
        Tp = tokens.shape[0]
        n_blk = Tp // block_size
        assert n_blk <= table_width, (
            f"prompt bucket {Tp} needs {n_blk} blocks > table width "
            f"{table_width}")
        x = embed(params, tokens[None])                         # [1, Tp, D]
        pos = jnp.arange(Tp, dtype=jnp.int32)[None]            # [1, Tp]
        # The padded prompt is block-aligned, so the write is a plain
        # blockwise scatter; bucket blocks past the allocation hold the
        # null block's id.
        kc, vc, x = layers(
            params, kc, vc, x, pos,
            lambda kc, vc, l, k, v: write_blocks(
                kc, vc, l, k, v, block_table[:n_blk]),
            lambda q, k, v, kc, vc, l: _attend_prompt(q, k, v, mesh))
        return kc, vc, emit(params, x,
                            lambda x: jnp.take(x[0], length - 1, axis=0))

    def prefill_resume(params, kc, vc, tokens, offset, length, block_table):
        """One prefill *chunk* starting at block-aligned token
        ``offset``. tokens [Tc] (chunk bucket-padded), offset scalar
        i32 (tokens already in the cache for this sequence: a mapped
        prefix-cache hit and/or earlier chunks), length scalar i32
        (real tokens in this chunk), block_table [table_width] i32.

        Queries attend over ALL pages gathered through the table
        (prefix pages written by whoever computed them + this chunk's
        own pages, scattered first) under a global-position causal
        mask, so the math per real token is position-dependent only —
        identical whether the prefix was computed here, by an earlier
        chunk, or by another sequence entirely (the bitwise
        cache-on/off parity property).

        Returns (kc, vc, tok) where tok is the argmax at the chunk's
        last real position — the sequence's first generated token when
        this is the final chunk; callers ignore it for earlier chunks
        (it reads mid-prompt logits then).
        """
        Tc = tokens.shape[0]
        n_blk = Tc // block_size
        x = embed(params, tokens[None])                         # [1, Tc, D]
        pos = offset + jnp.arange(Tc, dtype=jnp.int32)[None]   # [1, Tc]
        # Chunk rows land in table slots off_blk..off_blk+n_blk. Rows
        # whose slot falls past the table (bucket padding of the last
        # chunk at high offsets) are routed to the null block — same
        # never-read garbage contract as the monolithic prefill's
        # padding blocks. A plain dynamic_slice would CLAMP the start
        # instead and overwrite real prefix pages.
        slot = offset // block_size + jnp.arange(n_blk, dtype=jnp.int32)
        blks = jnp.where(
            slot < table_width,
            jnp.take(block_table, jnp.minimum(slot, table_width - 1)),
            NULL_BLOCK)
        kc, vc, x = layers(
            params, kc, vc, x, pos,
            lambda kc, vc, l, k, v: write_blocks(kc, vc, l, k, v, blks),
            lambda q, k, v, kc, vc, l: _attend_pages(
                q, kc, vc, l, block_table[None], pos))
        return kc, vc, emit(params, x,
                            lambda x: jnp.take(x[0], length - 1, axis=0))

    def decode(params, kc, vc, tokens, positions, block_tables):
        """One continuous-batching step. tokens [B] (each sequence's
        last token), positions [B] (its current cache length — where
        the token's K/V lands), block_tables [B, table_width]. Padded
        batch slots carry token 0 / position 0 / an all-null table;
        their lane writes and reads only touch the null block and
        their outputs are discarded by the engine. Returns (kc, vc,
        next_tokens [B])."""
        x = embed(params, tokens[:, None])                      # [B, 1, D]
        pos = positions[:, None]
        kc, vc, x = layers(
            params, kc, vc, x, pos,
            lambda kc, vc, l, k, v: write_rows(
                kc, vc, l, k, v, positions, block_tables),
            lambda q, k, v, kc, vc, l: _attend_pages(
                q, kc, vc, l, block_tables, pos))
        return kc, vc, emit(params, x, lambda x: x[:, 0])

    def verify(params, kc, vc, tokens, positions, block_tables):
        """Speculative verification (see serve/speculative.py): one
        chunked target step over the batch's already-reserved pages.
        tokens [B, C] — per sequence ``[last_token, d1..d_{C-1}]``;
        positions [B] — each sequence's cache length (where the
        chunk's first K/V lands); block_tables [B, table_width].

        This is ``prefill_resume``'s math batched over sequences with
        ``decode``'s token-granularity page addressing (speculative
        chunks start mid-block): scatter the chunk's K/V through the
        table at per-token physical slots, gather ALL of each
        sequence's pages, attend under the global-position causal
        mask. The argmax at chunk position j is therefore bitwise what
        a plain decode step would emit after consuming
        ``tokens[:, :j+1]`` — the property greedy acceptance needs.
        Chunk positions past the table (proposal frontier near the
        cap) and padded batch rows route to the null block; their
        outputs are compared then discarded host-side (acceptance
        truncates at max_new before any such position can be
        emitted). Returns (kc, vc, out [B, C])."""
        C = tokens.shape[1]
        x = embed(params, tokens)                               # [B, C, D]
        pos = positions[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        kc, vc, x = layers(
            params, kc, vc, x, pos,
            lambda kc, vc, l, k, v: write_rows(
                kc, vc, l, k, v, pos, block_tables),
            lambda q, k, v, kc, vc, l: _attend_pages(
                q, kc, vc, l, block_tables, pos))
        return kc, vc, emit(params, x, lambda x: x)

    def inject(kc, vc, blocks, k_pages, v_pages):
        """Scatter handed-off prompt pages into this pool (the
        prefill/decode disaggregation receive path). blocks
        [table_width] i32 — real target blocks first, then NULL_BLOCK
        padding whose zero pages land on the never-read null block
        (the same padding contract as the prefill bucket blocks);
        k/v_pages [L, table_width, bs, Hkv, Dh]. One compiled program
        per geometry; without it the un-jitted ``.at[].set`` fallback
        copies the ENTIRE pool per handoff instead of O(pages)."""
        kc = kc.at[:, blocks].set(k_pages.astype(kc.dtype))
        vc = vc.at[:, blocks].set(v_pages.astype(vc.dtype))
        return kc, vc

    # Donate the cache pool: steady-state decode rewrites it in place
    # instead of allocating a fresh [L, n_blocks, bs, Hkv, Dh] copy
    # per step. `length`/`offset`/`positions` stay traced (they change
    # every call); only array shapes key the jit cache.
    return (jax.jit(prefill, donate_argnums=(1, 2)),
            jax.jit(prefill_resume, donate_argnums=(1, 2)),
            jax.jit(decode, donate_argnums=(1, 2)),
            jax.jit(inject, donate_argnums=(0, 1)),
            jax.jit(verify, donate_argnums=(1, 2)))


# ---------------------------------------------------------------------------
# Layers of several kinds over two kinds of cache (ISSUE 32)
# ---------------------------------------------------------------------------

def _attend_keys(q, keys, vals, key_pos, pos, window):
    """Attention of a query chunk per sequence over keys that each
    carry the position they hold: the one attention of the mixed
    programs, for a prompt over itself, a block table's pages and a
    window layer's ring.

    ``q`` [B, C, H, Dh]; ``keys``/``vals`` [B, S, Hkv, Dh] in the
    cache's dtype; ``key_pos`` [B, S] the position each key holds
    (negative: none yet); ``pos`` [B, C] the queries' positions. Key j
    is visible to the query at p iff ``0 <= j <= p`` and, with a
    ``window``, ``j > p - window``. Grouped over KV heads with float32
    scores, softmax and accumulators, as :func:`_attend_pages`; a chunk
    (C > 1) goes one KV head at a time, so that its scores are
    ``[H / Hkv, C, S]`` and not ``H`` times ``[C, S]`` at once.
    Returns [B, C, H * Dh]."""
    B, C, H, Dh = q.shape
    Hkv = keys.shape[2]
    mask = (key_pos[:, None, :] >= 0) & (key_pos[:, None, :] <= pos[:, :, None])
    if window is not None:
        mask &= key_pos[:, None, :] > pos[:, :, None] - window   # [B, C, S]

    def heads(qg, kp, vp):
        """qg [B, C, G, R, Dh] over kp/vp [B, S, G, Dh], G KV heads."""
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kp,
                       preferred_element_type=jnp.float32) * Dh ** -0.5
        s = jnp.where(mask[:, None, None], s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(vp.dtype), vp,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    qg = q.reshape(B, C, Hkv, H // Hkv, Dh)
    if C == 1:
        return heads(qg, keys, vals).reshape(B, C, H * Dh)
    o = lax.map(lambda a: heads(a[0][:, :, None], a[1][:, :, None],
                                a[2][:, :, None])[:, :, 0],
                (jnp.moveaxis(qg, 2, 0), jnp.moveaxis(keys, 2, 0),
                 jnp.moveaxis(vals, 2, 0)))              # [Hkv, B, C, R, Dh]
    return jnp.moveaxis(o, 0, 2).reshape(B, C, H * Dh)


def ring_positions(frontier, ring: int):
    """The position each of a ring's ``ring`` places holds once
    ``frontier`` [B] positions of a sequence have been written (position
    p lies at ``p % ring``): the newest ``p < frontier`` with that
    remainder, negative where none was written yet. A retired slot's
    ring needs no cleaning: a new sequence's frontier starts at 0 and
    what lies beyond it reads as a position the mask refuses."""
    r = jnp.arange(ring, dtype=jnp.int32)[None]
    last = frontier[:, None] - 1
    return last - (last - r) % ring


def mixed_programs(cfg, block_size: int, table_width: int, ring: int,
                   compression=None, head=None):
    """(prefill, prefill_resume, decode, held_experts_counts), not
    jitted, of a configuration with layers of several kinds (the last
    is :func:`moe_share_report`'s). The caches are pairs
    ``kc = (pool, rings)``: ``pool`` [n_full, n_blocks, bs, Hkv, Dh] is
    the full layers' paged pool behind the block tables, ``rings``
    [n_window, n_slots, ring, Hkv, Dh] the window layers', one ring a
    batch slot (slot 0 is the null slot, as block 0 is the null block).
    An address is a pair too: ``(block_table, slot)``.

    The layers are a Python loop: each knows its kind, its stack and
    its place in its cache when the program is traced. ``head`` maps
    float32 logits to what a program returns (None: their argmax, the
    next token; the tests read the logits themselves)."""
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    window = cfg.attn_window
    if head is None:
        def head(logits):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # layer -> (its list, its index there, sliding?, its index in its cache)
    plan, n_full, n_win = [], 0, 0
    for i in range(cfg.n_layers):
        stack, j = (("dense_layers", i) if i < cfg.n_dense_layers
                    else ("layers", i - cfg.n_dense_layers))
        if cfg.sliding(i):
            plan.append((stack, j, True, n_win))
            n_win += 1
        else:
            plan.append((stack, j, False, n_full))
            n_full += 1
    def embed(params, tokens):
        with jax.named_scope("embed"):
            x = tf_lib.embed_lookup(params["embed"], tokens, cfg.dtype,
                                    None, compression)
            if cfg.embed_scale:
                x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
            return x

    def layers(params, kc, vc, x, pos, write, attend, moe_fn=None):
        """Every layer over ``x`` [B, T, D] at ``pos`` [B, T].
        ``write(cache, c, sliding, new) -> cache`` puts a layer's new K
        or V into place ``c`` of its kind's cache, and ``attend(q, k,
        v, kc, vc, c, sliding) -> [B, T, H * Dh]`` attends, as in
        :func:`_cached_serve_fns`."""
        for i, (stack, j, sliding, c) in enumerate(plan):
            lp = params[stack][j]
            kind = "attn_window" if sliding else "attn_full"
            with jax.named_scope("attn"):
                q, k, v = tf_lib.attention_inputs(cfg, lp, x, pos, i)
                with jax.named_scope(kind):
                    with jax.named_scope("kv_write"):
                        kc, vc = (write(kc, c, sliding, k),
                                  write(vc, c, sliding, v))
                    o = attend(q, k, v, kc, vc, c, sliding)
                x = tf_lib.attention_residual(cfg, lp, x, o)
            with jax.named_scope("mlp"):
                x, _aux = tf_lib.ffn_block(cfg, lp, x, moe_fn)
        return kc, vc, x

    def put(cache, sliding, at, new):
        """``new`` rows at ``at`` of the pool or of the rings."""
        pool, rings = cache
        if sliding:
            return pool, rings.at[at].set(
                new.reshape(-1, Hkv, Dh).astype(rings.dtype))
        return pool.at[at].set(new.astype(pool.dtype)), rings

    def emit(params, x, rows):
        with jax.named_scope("head"):
            x = rows(tf_lib._rmsnorm(x, params["final_norm"], cfg.norm_eps))
            return head((x @ params["lm_head"]).astype(jnp.float32))

    def chunk_program(params, kc, vc, tokens, offset, length, address,
                      local: bool):
        """A chunk of one sequence at ``offset`` (B = 1): whole blocks
        into the pool, rows into the slot's ring. ``local``: the chunk
        is the whole prompt and attends over itself."""
        table, slot = address
        Tc = tokens.shape[0]
        assert Tc <= ring or not n_win, (
            f"a chunk of {Tc} does not fit a ring of {ring}")
        x = embed(params, tokens[None])
        pos = offset + jnp.arange(Tc, dtype=jnp.int32)[None]    # [1, Tc]
        blk = offset // block_size + jnp.arange(Tc // block_size,
                                                dtype=jnp.int32)
        blks = jnp.where(
            blk < table_width,
            jnp.take(table, jnp.minimum(blk, table_width - 1)), NULL_BLOCK)
        held = ring_positions(offset[None] + Tc, ring) if n_win else None
        S = table_width * block_size

        def write(cache, c, sliding, new):
            if sliding:
                return put(cache, True, (c, slot, pos[0] % ring), new)
            return put(cache, False, (c, blks),
                       new[0].reshape(-1, block_size, Hkv, Dh))

        def attend(q, k, v, kc, vc, c, sliding):
            w = window if sliding else None
            if local:
                return _attend_keys(q, k, v, pos, pos, w)
            if sliding:
                return _attend_keys(q, kc[1][c, slot][None],
                                    vc[1][c, slot][None], held, pos, w)
            with jax.named_scope("kv_gather"):
                kp = kc[0][c, table].reshape(1, S, Hkv, Dh)
                vp = vc[0][c, table].reshape(1, S, Hkv, Dh)
            return _attend_keys(q, kp, vp,
                                jnp.arange(S, dtype=jnp.int32)[None], pos,
                                None)

        kc, vc, x = layers(params, kc, vc, x, pos, write, attend)
        return kc, vc, emit(params, x,
                            lambda x: jnp.take(x[0], length - 1, axis=0))

    def prefill(params, kc, vc, tokens, length, address):
        """A whole prompt, tokens [Tp] bucket-padded. Returns (kc, vc,
        first token)."""
        assert tokens.shape[0] // block_size <= table_width
        return chunk_program(params, kc, vc, tokens, jnp.int32(0), length,
                             address, local=True)

    def prefill_resume(params, kc, vc, tokens, offset, length, address):
        """One chunk at the block-aligned ``offset``, over what earlier
        chunks left in the two caches and its own keys."""
        return chunk_program(params, kc, vc, tokens, offset, length,
                             address, local=False)

    def decode(params, kc, vc, tokens, positions, address):
        """One step of the batch: tokens [B], positions [B], address
        ``(block_tables [B, table_width], slots [B])``. A padded row
        carries token 0, position 0, an all-null table and the null
        slot."""
        tables, slots = address
        B = tokens.shape[0]
        x = embed(params, tokens[:, None])
        pos = positions[:, None]
        blk_i = positions // block_size
        blk = jnp.take_along_axis(
            tables, jnp.minimum(blk_i, table_width - 1)[:, None], axis=1)[:, 0]
        blk = jnp.where(blk_i < table_width, blk, NULL_BLOCK)
        S = table_width * block_size

        def by_slot(rows, n_slots):
            """``rows`` [B, ...] laid out by ring: row i at ``slots[i]``,
            zeros at the slots that are not in the batch."""
            return jnp.zeros((n_slots,) + rows.shape[1:],
                             rows.dtype).at[slots].set(rows)

        def write(cache, c, sliding, new):
            if sliding:
                return put(cache, True, (c, slots, positions % ring), new)
            return put(cache, False, (c, blk, positions % block_size),
                       new.reshape(-1, Hkv, Dh))

        def attend(q, k, v, kc, vc, c, sliding):
            if sliding:
                # Every ring of the layer where it lies, the queries
                # carried to their slots and the results back: the
                # rings are then read once, by the two dots, and not
                # gathered first into a copy the size of the batch's
                # share of them (a gather through (layer, slots) that
                # the compiler made of all layers' rings in slabs, at
                # a twelfth of the memory bandwidth). A slot that is
                # not in the batch has written nothing (frontier 0):
                # every key of its ring is refused and its row is not
                # read back.
                n_slots = kc[1].shape[1]
                at = by_slot(pos + 1, n_slots)                  # [S, 1]
                o = _attend_keys(by_slot(q, n_slots), kc[1][c], vc[1][c],
                                 ring_positions(at[:, 0], ring), at - 1,
                                 window)
                return o[slots]
            with jax.named_scope("kv_gather"):
                kp = kc[0][c, tables].reshape(B, S, Hkv, Dh)
                vp = vc[0][c, tables].reshape(B, S, Hkv, Dh)
            return _attend_keys(q, kp, vp,
                                jnp.arange(S, dtype=jnp.int32)[None], pos,
                                None)

        kc, vc, x = layers(params, kc, vc, x, pos, write, attend)
        return kc, vc, emit(params, x, lambda x: x[:, 0])

    def held_experts_counts(params, tokens):
        """The claims on each held expert of every MoE layer [n_moe,
        held], and the claims of each layer that the dispatch's sort
        and group sizes would not run [n_moe], when ``tokens`` [B, T]
        run as B prompts over themselves (no cache): the routing the
        serve programs' dispatch acts on."""
        counts, not_run = [], []

        def counting(h, lp):
            c, lost = moe_lib.routing_counts(
                h, lp["router"], cfg.moe, lp.get("router_bias"))
            counts.append(c)
            not_run.append(lost)
            return moe_lib.make_moe_ffn(cfg.moe, None)(h, lp)

        B, T = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        layers(params, None, None, embed(params, tokens), pos,
               lambda cache, c, sliding, new: cache,
               lambda q, k, v, kc, vc, c, sliding: _attend_keys(
                   q, k, v, pos, pos, window if sliding else None),
               counting)
        return jnp.stack(counts), jnp.stack(not_run)

    return prefill, prefill_resume, decode, held_experts_counts


@functools.lru_cache(maxsize=16)
def _mixed_serve_fns(cfg, block_size: int, table_width: int, ring: int,
                     compression=None):
    prefill, prefill_resume, decode, _ = mixed_programs(
        cfg, block_size, table_width, ring, compression)

    def unserved(what):
        def refuse(*args, **kwargs):
            raise NotImplementedError(
                f"{what} is not built for a configuration with layers of "
                "several kinds or a chip's share of the experts: a window "
                "layer's ring is not pages another engine or a draft "
                "could be handed (ROADMAP B9)")
        return refuse

    return (jax.jit(prefill, donate_argnums=(1, 2)),
            jax.jit(prefill_resume, donate_argnums=(1, 2)),
            jax.jit(decode, donate_argnums=(1, 2)),
            unserved("inject"), unserved("verify"))


def moe_share_report(params, tokens, cfg, block_size: int = 16):
    """Routing counters of a served MoE that holds a chip's share of the
    experts, on ``tokens`` [B, T] (B prompts of T, or B single tokens):
    a program of its own, for set-up. ``moe_local_pair_share`` (pairs
    on held experts over all ``B·T·K`` pairs of a layer; the share of
    the experts held, if the router is even),
    ``moe_held_experts_touched_mean`` (held experts with at least one
    pair, mean over layers), ``moe_expert_load_max_over_mean`` over the
    held experts (the largest layer's) and
    ``moe_dispatch_dropped_token_frac`` (pairs on held experts, as the
    router chose them, that the dispatch's sort and group sizes do not
    run through their expert: ``moe.held_pairs_not_run``)."""
    count = mixed_programs(cfg, block_size, 1, 0)[3]
    counts, not_run = jax.jit(count)(params, jnp.asarray(tokens, jnp.int32))
    pairs = tokens.shape[0] * tokens.shape[1] * cfg.moe.top_k
    summary = moe_lib.routing_summary(counts, not_run)
    return {
        "moe_local_pair_share": float(counts.sum(-1).mean()) / pairs,
        "moe_held_experts_touched_mean": float((counts > 0).sum(-1).mean()),
        "moe_expert_load_max_over_mean":
            summary["moe_expert_load_max_over_mean"],
        "moe_dispatch_dropped_token_frac":
            summary["moe_dispatch_dropped_token_frac"],
    }
