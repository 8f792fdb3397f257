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
attends prompt-locally (:func:`_attend_prompt`).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.parallel.ring_attention import local_attention
from horovod_tpu.serve.kv_cache import NULL_BLOCK

_NEG_BIG = -1e30  # matches ring_attention's finite "-inf"


def _attend_prompt(q, k, v):
    """Causal attention of a whole prompt over itself (the monolithic
    ``prefill``): q [1, T, H, Dh], k/v [1, T, Hkv, Dh] as projected,
    repeated across the GQA group for ``local_attention``. Returns
    [1, T, H * Dh]."""
    rep = q.shape[2] // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return local_attention(q, k, v, causal=True).reshape(*q.shape[:2], -1)


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
                   table_width: int, compression=None):
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

    Memoized: engines sharing (cfg, mesh, block geometry, compression)
    — e.g. the benchmark's continuous and static schedulers, or a
    fleet of per-tenant engines — reuse one pair of jit closures and
    therefore one compiled program per shape bucket."""
    unserved = [what for what, there in (
        ("qk_norm", cfg.qk_norm),
        ("a MoE without a capacity (moe_capacity_factor=None)",
         cfg.moe is not None and cfg.moe.capacity_factor is None)) if there]
    if unserved:
        raise NotImplementedError(
            f"the serve programs do not serve {' or '.join(unserved)} yet: "
            "no reference holds a served model of that kind to anything "
            "(ROADMAP B7). The configuration trains through "
            "make_train_step.")
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
            lambda q, k, v, kc, vc, l: _attend_prompt(q, k, v))
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
