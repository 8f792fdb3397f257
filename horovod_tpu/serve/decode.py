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

The programs of a configuration with layers of several kinds
(:func:`mixed_programs`) attend through :func:`_attend_keys` (a window
layer's and a full layer's CHUNKS: keys that carry their positions, in
XLA; a decode STEP of either reads its keys where they lie through the
Pallas kernel of ``ops/paged_decode.py``, ``hvd_paged_decode``: a full
layer each row's own K and V pages in the pools, to the row's length,
a window layer each row's own slot's ring in the stacked rings, from
the first key its window admits to its own position),
:func:`kda_scan` / :func:`kda_step` and :func:`mamba_scan` /
:func:`mamba_step` (a recurrent state a batch slot: a delta rule, and
Mamba-1's selective scan, both in XLA) and latent attention, in two
forms. A chunk's queries (``prefill`` over itself,
``prefill_resume`` over the pages, at every bucket width) attend
EXPANDED (:func:`_mla_attend`), a key block at a time through the
Pallas flash forward over keys that carry their positions
(``ops/flash_attention.py::flash_attention_keys``, the kernel
``hvd_flash_keys_fwd``), so that a chunk's scores are tiles in VMEM
too. A decode step's attend ABSORBED (:func:`_mla_decode`), each row
over its own pages where they lie in the pool, through the same kernel
body over the one pool (``ops/paged_decode.py::latent_decode``, the
Pallas call ``hvd_latent_decode``): no key block is gathered and no
score reaches HBM. The absorbed form in XLA
(``tests/reference_mla.py``) is what the tests hold both to. Nothing
chooses between the two but which program calls. An ``eva`` layer
(an exact aligned window beside one attended summary a chunk of every
window that has closed, :func:`eva_summaries`) attends both in ONE
softmax: a chunk through two calls of ``flash_attention_keys``, the
second carrying the first's ``(out, lse)``; a decode step through
``ops/paged_decode.py``'s kernel twice (``paged_decode_stats``: the
slot's rows to the row's count, the summaries' pages), merged by their
logsumexp.
"""

from __future__ import annotations

import functools
import math
import types
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import moe as moe_lib
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_keys)
from horovod_tpu.ops import mamba_scan as mamba_scan_kernel
from horovod_tpu.ops import mamba_step as mamba_step_kernel
from horovod_tpu.ops import sparse_scores as sparse_scores_kernel
from horovod_tpu.ops import ssd_scan as ssd_scan_kernel
from horovod_tpu.ops import state_step as state_step_kernel
from horovod_tpu.ops.paged_decode import (latent_decode,
                                          latent_ring_decode, paged_decode,
                                          paged_decode_stats, ring_decode,
                                          ring_page)
from horovod_tpu.parallel.ring_attention import local_attention
from horovod_tpu.serve.kv_cache import (NULL_BLOCK, latent_row, page_tail,
                                        state_kinds)

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
    (``cfg.mixed``: a leading dense stack; window, full, kda, mla and
    mamba layers) or that holds a chip's share of the experts gets the
    programs of :func:`_mixed_serve_fns`, over a state a kind of layer
    (``kv_cache.KVCache``); ``ring`` is the positions a window layer
    keeps for a sequence (``kv_cache.ring_width``). It has no
    ``inject`` and no ``verify``.

    Memoized: engines sharing (cfg, mesh, block geometry, compression)
    — e.g. a fleet of per-tenant engines — reuse one pair of jit
    closures and therefore one compiled program per shape bucket."""
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
                f"chip: a mesh with {spread} would shard its caches (pages, "
                "rings, recurrent states, the latent pool) and its held "
                "experts, which neither decode.py's mixed_programs nor "
                "kv_cache.init_kv_cache does yet (ROADMAP B7(ii), B8)")
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
# Layers of several kinds, each over its kind's state (ISSUE 32, 38)
# ---------------------------------------------------------------------------

def _attend_keys(q, keys, vals, key_pos, pos, window):
    """Attention of a query chunk per sequence over keys that each
    carry the position they hold: the one attention of the mixed
    programs' chunks, for a prompt over itself, a block table's pages
    and a window layer's ring (no decode step's since ISSUE 59: the
    tests hold ``ops/paged_decode.py``'s calls to it with C = 1).

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


#: Positions a block of :func:`kda_scan` holds, and of the sub-blocks
#: inside it that share one reference point for their decays.
_KDA_BLOCK, _KDA_SUB = 64, 16
#: Key positions :func:`_mla_attend` gathers and attends at a time.
_MLA_KEY_BLOCK = 1024
#: ... and how many such blocks a CHUNK's expanded form gathers, expands
#: and hands the kernel at a time (a call of the kernel costs about
#: 75 us beside 7.5 us a head and 1024 x 1024 tile). On the v5e
#: (2026-09-30, ``tools/prefill_attn_sweep.py --latent``: bf16 queries
#: ``[1, C, H, 128 + 64]`` of a chunk that ends at key 8192 over latents
#: of 512 + 64, ms a layer, the einsum form that wrote float32
#: ``[H, C, 1024]`` scores / this form at 1, **2** and 4 blocks a call):
#: 64 heads C = 1024 17.29 / 4.91, **4.49**, 4.70, C = 256 3.05 / 2.22,
#: **2.18**, 2.47; 32 heads C = 1024 8.62 / 2.47, **2.20**, 2.21, C = 256
#: 0.98 / 1.14, **1.06**, 1.12. At one block a call and 64 heads: C = 512
#: 8.86 / 3.32, 768 13.07 / 4.31, and over 17 408 keys C = 1024 37.09 /
#: 10.26, C = 256 6.25 / 4.51; at 32 heads C = 512 2.58 / 1.57, 768 3.69 /
#: 2.04. The one shape at which the einsum form was ahead, 256 queries
#: at 32 heads (33 MB of scores a block), is 0.08 ms a layer of a stack
#: with one such layer in seven: no second form is kept for it.
_MLA_CHUNK_BLOCKS = 2
_EXACT = lax.Precision.HIGHEST


def kda_scan(q, k, v, g, beta, state, block: int = _KDA_BLOCK,
             sub: int = _KDA_SUB):
    """The delta-rule recurrence of a kda layer over a chunk, a block of
    ``block`` positions at a time: the inside of a block as matrix
    products, the state carried between blocks.

    ``q``, ``k``, ``v``, ``g`` [B, T, H, Dh] float32 (``g`` the
    log-decay a channel, <= 0), ``beta`` [B, T, H], ``state``
    [B, H, Dh, Dh] float32. A head, with ``a_t = exp(g_t)``:

        S'_t = Diag(a_t) S_{t-1}
        S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
        o_t  = S_t^T q_t

    Returns ``(o [B, T, H, Dh], the state after the last position)``. A
    position with ``g = 0`` and ``beta = 0`` leaves the state as it
    was: that is how a bucket's padding is written.

    Inside a block, with ``G`` the running sum of ``g`` from the block's
    start, ``u_j = v_j - S'_j^T k_j`` solves the unit lower-triangular
    system ``(I + L) U = V - (K exp G) S_0``, ``L_ji = beta_i sum_d k_jd
    k_id exp(G_jd - G_id)`` for i < j; then ``O = (Q exp G) S_0 +
    (A beta) U`` with ``A_tj`` the same sum over ``q_t k_j`` for j <= t,
    and ``S_C = exp(G_C) S_0 + (K exp(G_C - G) beta)^T U``. The pairwise
    decays ``exp(G_t - G_j)`` are products ``exp(G_t - R) exp(R - G_j)``
    about a reference ``R`` a sub-block of ``sub`` rows (``G`` at the
    sub-block's start): the first factor is at most 1 and the second at
    most ``exp(sub * floor)``, e^80 at the published floor of -5 a
    position and 16 rows, which float32 holds. Everything in float32 at
    the highest matmul precision."""
    B, T, H, D = q.shape
    s = min(sub, block)
    C = min(block, -(-T // s) * s)       # whole sub-blocks
    pad = -T % C
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    assert C % s == 0, (C, s)
    n, na = (T + pad) // C, C // s

    def blocks(a):                                   # -> [n, B, H, C, .]
        return jnp.moveaxis(a.reshape(B, n, C, H, -1), (1, 3), (0, 2))

    q, k, v, g = map(blocks, (q, k, v, g))
    beta = blocks(beta[..., None])[..., 0]                   # [n, B, H, C]
    G = jnp.cumsum(g, axis=-2)
    # a sub-block's reference: G at the end of the sub-block before it
    ends = G.reshape(n, B, H, na, s, D)[..., -1, :]
    ref = jnp.concatenate([jnp.zeros_like(ends[..., :1, :]),
                           ends[..., :-1, :]], -2)           # [.., na, D]
    row = jnp.exp(G.reshape(n, B, H, na, s, D) - ref[..., None, :])
    seen = (jnp.arange(C) // s)[None, :] <= jnp.arange(na)[:, None]
    col = jnp.exp(jnp.where(seen[..., None],
                            ref[..., None, :] - G[..., None, :, :],
                            -jnp.inf))                   # [.., na, C, D]
    kcol = k[..., None, :, :] * col

    def pairs(a):              # sum_d a_t k_j exp(G_t - G_j) [.., C, C]
        return jnp.einsum("...asd,...acd->...asc",
                          a.reshape(n, B, H, na, s, D) * row, kcol,
                          precision=_EXACT).reshape(n, B, H, C, C)

    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    by_beta = beta[..., None, :]                  # beta_j on column j
    L = jnp.where(i > j, pairs(k), 0.0) * by_beta
    A = jnp.where(i >= j, pairs(q), 0.0) * by_beta

    def row_of_inverse(t, inv):
        """Row t of (I + L)^-1 from the rows above it."""
        new = -jnp.einsum("...j,...jk->...k", L[..., t, :], inv,
                          precision=_EXACT)
        return inv.at[..., t, :].set(new.at[..., t].add(1.0))

    inv = lax.fori_loop(1, C, row_of_inverse,
                        jnp.broadcast_to(jnp.eye(C, dtype=L.dtype), L.shape))
    decayed = jnp.exp(G)
    solved = jnp.einsum("...tj,...jd->...td", inv,
                        jnp.concatenate([v, k * decayed], -1),
                        precision=_EXACT)
    tv, tk = solved[..., :D], solved[..., D:]
    qg = q * decayed
    at_end = decayed[..., -1, :]                             # [n, B, H, D]
    k_end = k * jnp.exp(G[..., -1:, :] - G) * beta[..., None]

    def one_block(S, xs):
        tv, tk, qg, A, k_end, at_end = xs
        u = tv - jnp.einsum("...tk,...kv->...tv", tk, S, precision=_EXACT)
        o = (jnp.einsum("...tk,...kv->...tv", qg, S, precision=_EXACT)
             + jnp.einsum("...tj,...jv->...tv", A, u, precision=_EXACT))
        S = at_end[..., None] * S + jnp.einsum(
            "...tk,...tv->...kv", k_end, u, precision=_EXACT)
        return S, o

    state, o = lax.scan(one_block, state, (tv, tk, qg, A, k_end, at_end))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, T + pad, H, D)
    return o[:, :T], state


def kda_step(q, k, v, g, beta, state):
    """One position of :func:`kda_scan`'s recurrence a row: ``q``,
    ``k``, ``v``, ``g`` [N, H, Dh], ``beta`` [N, H], ``state``
    [N, H, Dh, Dh]. Returns ``(o [N, H, Dh], the new state)``. The
    state is read twice and written once: ``S'^T k`` and ``S'^T q`` in
    one pass, then ``S = S' + beta k u^T`` and ``o = S'^T q + beta (k .
    q) u``."""
    decayed = jnp.exp(g)[..., None] * state
    sk = jnp.einsum("nhkv,nhk->nhv", decayed, k, precision=_EXACT)
    sq = jnp.einsum("nhkv,nhk->nhv", decayed, q, precision=_EXACT)
    u = beta[..., None] * (v - sk)
    o = sq + jnp.sum(k * q, -1, keepdims=True) * u
    return o, decayed + k[..., None] * u[..., None, :]


#: Positions :func:`mamba_scan`'s loop body holds. Since PR 49 the loop
#: is the form of a chunk that ``ops/mamba_scan.py`` does not take (a
#: state that is not whole tiles on a TPU), the kernel's reference in
#: the tests and the sweep's baseline: a chunk of the published sizes
#: runs the Pallas call ``hvd_mamba_scan`` (0.115 ms a layer of 512
#: positions where this loop takes 0.299 inside a program of 26
#: layers). What the 8 was chosen from, on the v5e at the published
#: 5120 channels and 16 state rows, ms a layer of a 512-token chunk
#: alone (``tools/mamba_scan_sweep.py --xla-forms``, 2026-10-01): a
#: position an iteration 0.92, **eight 0.43 or less** (the call's own
#: 0.4 ms hides the rest); ``lax.associative_scan`` inside blocks of
#: 8 / 16 / 32 / 64 positions 0.92 / 1.11 / 1.11 / 1.04 (a block's
#: ``[block, 16, 5120]`` float32 decays and drives go through memory at
#: every level of the tree) and 7.9 / 9.2 at blocks of 128 / 256.
_MAMBA_UNROLL = 8


def mamba_scan(u, step, a, b, c, state, unroll: int = _MAMBA_UNROLL):
    """The selective scan of a mamba layer over a chunk: the recurrence
    a position at a time, ``unroll`` positions a loop iteration, so that
    nothing larger than a state is ever built (a chunk's decays alone
    would be ``[T, N, Di]`` float32, 168 MB a layer at 512 positions
    and the published 5120 x 16).

    ``u`` (the convolved input) and ``step`` (``Delta``, >= 0)
    [B, T, Di], ``a`` [N, Di] (``A`` turned, < 0), ``b`` and ``c``
    [B, T, N], ``state`` [B, N, Di], all float32. A channel d and a
    state row n:

        s_t = exp(step_t a) s_{t-1} + (step_t u_t) b_t
        y_t = sum_n s_t c_t

    Returns ``(y [B, T, Di], the state after the last position)``; the
    caller adds ``D u``. A position with ``step = 0`` leaves the state
    as it was (decay 1, drive 0): that is how a bucket's padding is
    written. Each position is :func:`mamba_step`, the decode step's
    own. ``mamba_chunk`` calls this where ``ops/mamba_scan.py::taken``
    says no; the kernel is held to it."""
    def position(s, row):
        y, s = mamba_step(row[0], row[1], a, row[2], row[3], s)
        return s, y

    state, y = lax.scan(
        position, state,
        tuple(jnp.moveaxis(x, 1, 0) for x in (u, step, b, c)),
        unroll=min(unroll, u.shape[1]))
    return jnp.moveaxis(y, 0, 1), state


def mamba_step(u, step, a, b, c, state):
    """One position of :func:`mamba_scan`'s recurrence a row: ``u``,
    ``step`` [S, Di], ``b``, ``c`` [S, N], ``state`` [S, N, Di].
    Returns ``(y [S, Di], the new state)``: the state read once and
    written once."""
    state = (jnp.exp(step[:, None] * a) * state
             + (step * u)[:, None] * b[..., None])
    return jnp.sum(state * c[..., None], axis=1), state


def ssd_scan(x, dt, a, b, c, state, block: int):
    """Mamba-2's recurrence over a chunk as state-space duality (Dao &
    Gu 2024): matrix products over blocks of ``block`` positions, the
    state carried between blocks.

    ``x`` [B, T, Hm, P], ``dt`` [B, T, Hm] (``Delta``, >= 0), ``a`` [Hm]
    (``A``, < 0, ONE scalar a head), ``b`` and ``c`` [B, T, G, N] (head h
    reads group ``h // (Hm / G)``), ``state`` [B, Hm, P, N], all
    float32. A head:

        S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t
        y_t = S_t c_t

    Returns ``(y [B, T, Hm, P], the state after the last position)``;
    the caller adds ``D x``. A position with ``dt = 0`` leaves the state
    as it was (decay 1, drive 0): that is how a bucket's padding is
    written, and how ``T`` is padded to whole blocks here. Inside a
    block, with ``L`` the running sum of ``dt a`` from the block's
    start: ``Y = exp(L) (S_0 C) + ((C B^T) * D) (dt X)`` with ``D_ts =
    exp(L_t - L_s)`` for s <= t, and ``S_end = exp(L_end) S_0 + B^T
    (exp(L_end - L) dt X)`` (each exponent <= 0: nothing overflows at
    any block size). The products are by GROUP where the operand is
    (``C B^T`` is made once a group, not once a head). Held to
    :func:`ssd_step` a position at a time in the tests. ``mamba2_chunk``
    calls this where :func:`ssd_scan_taken` says no; the kernel
    (``ops/ssd_scan.py``) is held to it."""
    B, T, Hm, P = x.shape
    G, N = b.shape[2:]
    pad = -T % block
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    n = (T + pad) // block

    def blocks(v):                                   # -> [n, B, block, ..]
        return jnp.moveaxis(v.reshape(B, n, block, *v.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((block, block), bool))

    def one_block(S, xs):
        xb, dtb, bb, cb = xs
        run = jnp.cumsum(dtb * a, axis=1)                    # [B, C, Hm] <= 0
        # D_ts = exp(L_t - L_s), s <= t                      [B, Hm, C, C]
        gap = run[:, :, None] - run[:, None, :]              # [B, t, s, Hm]
        decay = jnp.exp(jnp.where(causal[None, :, :, None], gap, -jnp.inf))
        decay = jnp.moveaxis(decay, -1, 1).reshape(B, G, Hm // G, block,
                                                   block)
        scores = jnp.einsum("btgn,bsgn->bgts", cb, bb)       # C B^T a group
        drive = (dtb[..., None] * xb).reshape(B, block, G, Hm // G, P)
        inside = jnp.einsum("bghts,bsghp->btghp",
                            scores[:, :, None] * decay, drive)
        Sg = S.reshape(B, G, Hm // G, P, N)
        before = jnp.einsum("bghpn,btgn->btghp", Sg, cb) * jnp.exp(
            run).reshape(B, block, G, Hm // G, 1)
        to_end = jnp.exp(run[:, -1:] - run)                  # [B, C, Hm]
        new = (jnp.exp(run[:, -1]).reshape(B, G, Hm // G, 1, 1) * Sg
               + jnp.einsum("bsghp,bsgn->bghpn",
                            drive * to_end.reshape(B, block, G, Hm // G, 1),
                            bb))
        return new.reshape(B, Hm, P, N), (inside + before).reshape(
            B, block, Hm, P)

    state, y = lax.scan(one_block, state, tuple(map(blocks, (x, dt, b, c))))
    return jnp.moveaxis(y, 0, 1).reshape(B, T + pad, Hm, P)[:, :T], state


def ssd_scan_taken(cfg, chunk: int) -> bool:
    """Whether a mamba2 layer's chunk of ``chunk`` positions runs its
    SSD through the kernel (``ops/ssd_scan.py``, ``hvd_ssd_scan`` in a
    device trace): by the shapes alone (``ssd_scan.taken``: on a TPU the
    state whole float32 tiles, the chunk whole blocks of whole lanes).
    Any other chunk keeps :func:`ssd_scan`."""
    return ssd_scan_kernel.taken(
        cfg.mamba2_head_dim, cfg.mamba_d_state, cfg.mamba2_heads,
        cfg.mamba2_groups, cfg.mamba2_chunk, chunk)


def ssd_step(x, dt, a, b, c, state):
    """One position of :func:`ssd_scan`'s recurrence a row: ``x`` [S,
    Hm, P], ``dt`` [S, Hm], ``b`` and ``c`` [S, G, N], ``state`` [S, Hm,
    P, N]. Returns ``(y [S, Hm, P], the new state)``: the state read
    once and written once."""
    Hm, G = x.shape[1], b.shape[1]
    bh, ch = (jnp.repeat(v, Hm // G, axis=1)[:, :, None] for v in (b, c))
    state = (jnp.exp(dt * a)[..., None, None] * state
             + (dt[..., None] * x)[..., None] * bh)
    return jnp.sum(state * ch, axis=-1), state


#: Positions a block of :func:`lightning_scan` holds: the inside of a
#: block is two masked ``[block, block]`` products a head, the state
#: two ``[block, Dh] x [Dh, Dh]`` products; at 128 = Dh the two cost
#: the same.
_LIGHTNING_BLOCK = 128
#: Key positions a chunk of a sparse layer gathers and attends at a
#: time (whole pages), under the mask of what each query chose.
_SPARSE_KEY_BLOCK = 1024
#: The q tile of a sparse layer's chunk through the kernel
#: (:func:`sparse_attend_pages`); a narrower chunk stays in XLA
#: (:func:`sparse_attend_chunk`). On the v5e (2026-10-02,
#: ``tools/prefill_attn_sweep.py --sparse``: 32 heads over 2 KV heads
#: of 128, pages of 64 behind a table of 520, each query 64 chosen
#: pages), ms a layer of the XLA form -> the kernel, a chunk that ends
#: at key 8192 / 16 384 / 32 768: C = 1024 5.11 -> **1.42**, 10.12 ->
#: **2.50**, 20.05 -> **4.55** (the XLA form writes a float32
#: ``[2, 16, 1024, 1024]`` a key block and reads it three times; the
#: kernel takes 0.130 ms a key tile of 32 heads, 67 % of the matrix
#: unit's peak, and 0.38 ms a call around its tiles: both gathers, the
#: queries' and the result's transposes, the bits); C = 512 0.73 ->
#: 0.90, 1.33 -> 1.44, 2.53 -> 2.54 and C = 256 0.42 -> 0.60, 0.75 ->
#: 0.92, 1.40 -> 1.52: there XLA keeps a key block's scores on the
#: chip of itself (0.075 ms a block of 32 heads by 512 queries is 58 %
#: of the peak) and gathers no page past the chunk's end, so the call's
#: fixed part decides.
_SPARSE_KERNEL_CHUNK = 1024


def lightning_scan(q, k, v, g, state, block: int = _LIGHTNING_BLOCK):
    """The decayed linear recurrence of a lightning layer over a chunk,
    a block of ``block`` positions at a time: the inside of a block as
    two masked matrix products, the state carried between blocks.

    ``q`` (scaled), ``k``, ``v`` [B, T, H, Dh] float32, ``g`` [B, T, H]
    the log-decay a position (a head's ``ln lambda_h``, <= 0), ``state``
    [B, H, Dh, Dh] float32. A head:

        S_t = exp(g_t) S_{t-1} + k_t^T v_t
        o_t = q_t S_t

    Returns ``(o [B, T, H, Dh], the state after the last position)``. A
    position with ``g = 0`` and ``k = 0`` leaves the state as it was:
    that is how a bucket's padding is written. Inside a block, with
    ``G`` the running sum of ``g`` from the block's start: ``O = (Q
    exp G) S_0 + ((Q K^T) * D) V`` with ``D_ts = exp(G_t - G_s)`` for
    s <= t (each exponent <= 0: nothing overflows at any block size),
    and ``S_C = exp(G_C) S_0 + (K exp(G_C - G))^T V``. Everything in
    float32 at the highest matmul precision."""
    B, T, H, D = q.shape
    C = min(block, T)
    pad = -T % C
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g = jnp.pad(g, ((0, 0), (0, pad), (0, 0)))
    n = (T + pad) // C

    def blocks(a):                                   # -> [n, B, H, C, .]
        return jnp.moveaxis(a.reshape(B, n, C, H, -1), (1, 3), (0, 2))

    q, k, v = map(blocks, (q, k, v))
    G = jnp.cumsum(blocks(g[..., None])[..., 0], axis=-1)   # [n, B, H, C]
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    decay = jnp.exp(jnp.where(i >= j, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    A = jnp.einsum("...td,...sd->...ts", q, k, precision=_EXACT) * decay
    qg = q * jnp.exp(G)[..., None]
    k_end = k * jnp.exp(G[..., -1:] - G)[..., None]
    at_end = jnp.exp(G[..., -1])                            # [n, B, H]

    def one_block(S, xs):
        A, qg, k_end, v, at_end = xs
        o = (jnp.einsum("...tk,...kv->...tv", qg, S, precision=_EXACT)
             + jnp.einsum("...ts,...sv->...tv", A, v, precision=_EXACT))
        S = at_end[..., None, None] * S + jnp.einsum(
            "...tk,...tv->...kv", k_end, v, precision=_EXACT)
        return S, o

    state, o = lax.scan(one_block, state, (A, qg, k_end, v, at_end))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, T + pad, H, D)
    return o[:, :T], state


def lightning_step(q, k, v, g, state):
    """One position of :func:`lightning_scan`'s recurrence a row:
    ``q``, ``k``, ``v`` [N, H, Dh], ``g`` [N, H], ``state``
    [N, H, Dh, Dh]. Returns ``(o [N, H, Dh], the new state)``: the
    state read once and written once."""
    state = (jnp.exp(g)[..., None, None] * state
             + k[..., :, None] * v[..., None, :])
    return jnp.einsum("nhk,nhkv->nhv", q, state, precision=_EXACT), state


def kernel_means(rows, stride: int, strides_a_kernel: int):
    """The compressed keys of the kernels that lie whole in ``rows``
    [N, Hkv, Dh] (N in whole strides): kernel i is the mean of rows
    ``[stride * i, stride * (i + strides_a_kernel))``, float32 sums, in
    ``rows``' dtype. ``N / stride - strides_a_kernel + 1`` of them."""
    groups = rows.astype(jnp.float32).reshape(
        -1, stride, *rows.shape[1:]).mean(1)
    n = groups.shape[0] - strides_a_kernel + 1
    return (sum(groups[u:u + n] for u in range(strides_a_kernel))
            / strides_a_kernel).astype(rows.dtype)


def eva_summaries(k, v, mu, phi):
    """The summaries of whole chunks of an eva layer: ``k`` and ``v``
    [N, chunk, H, Dh] (the rotated keys as the cache holds them, and
    their values), ``mu`` and ``phi`` [H, Dh] the layer's pooling
    vectors. ``k~ = sum_j softmax_j(s mu.k_j) k_j`` and ``v~ = sum_j
    softmax_j(s phi.k_j) v_j`` over a chunk's positions, ``s = Dh **
    -0.5``; float32 throughout, each [N, H, Dh] in its input's dtype.
    (Beside :func:`kernel_means`: a sparse layer's compressed keys are
    plain means that only choose pages; these are attended, key and
    value.)"""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    s = k.shape[-1] ** -0.5

    def pooled(by, rows):
        w = jax.nn.softmax(
            s * jnp.einsum("hd,nchd->nch", by.astype(jnp.float32), k32,
                           precision=_EXACT), axis=1)
        return jnp.einsum("nch,nchd->nhd", w, rows, precision=_EXACT)

    return pooled(mu, k32).astype(k.dtype), pooled(phi, v32).astype(v.dtype)


#: Key positions (rows of the open window, or summaries) a key block of
#: an eva layer's decode step holds: with as many KV heads as query
#: heads a position is H rows of ``ops/paged_decode.py``'s buffer, so a
#: block of 256 is 4.2 MB of K and V a half at 32 heads of 128 in bf16,
#: and its float32 scores 1 MB.
_EVA_KEY_BLOCK = 256
#: ... and the "page" the window's rows are read as (the same bytes):
#: positions a copy brings, 128 KB of each of K and V at those sizes.
_EVA_ROW_PAGE = 16


def _eva_key_block(page: int) -> int:
    """:data:`_EVA_KEY_BLOCK` in whole pages of ``page`` (one at
    least)."""
    return max(page, _EVA_KEY_BLOCK // page * page)


def sparse_block_scores(q, ck, exist, per: int, strides_a_kernel: int):
    """What a sparse layer's queries make of each block of keys: ``q``
    [B, C, H, Dh] over the compressed keys ``ck`` [B, J, Hkv, Dh] (J =
    W * ``per``: ``per`` kernels start in a block) of which query c of
    row b sees ``exist`` [B, C, J]. A head's scores are a softmax over
    the kernels it sees of ``q . c / sqrt(Dh)``; a block's is the
    largest over the kernels that meet it (those that start in it and
    the ``strides_a_kernel - 1`` before them), summed over the heads of
    the GQA group. Returns [B, C, Hkv, W] float32.

    The form in XLA: a float32 ``[B, Hkv, H / Hkv, C, J]`` array over
    every kernel of the table, passed over ten times. A decode step's
    form (a row a query), that of a chunk :func:`sparse_select_taken`
    refuses, and what the tests hold ``ops/sparse_scores.py`` to."""
    B, C, H, Dh = q.shape
    J, Hkv = ck.shape[1:3]
    W, r = J // per, strides_a_kernel
    seen = exist[:, None, None]                            # [B, 1, 1, C, J]
    s = jnp.einsum("bqgrd,bjgd->bgrqj", q.reshape(B, C, Hkv, H // Hkv, Dh),
                   ck, preferred_element_type=jnp.float32) * Dh ** -0.5
    p = jnp.where(seen, jax.nn.softmax(jnp.where(seen, s, _NEG_BIG), -1), 0.0)
    # kernel j at j + r - 1: block b's kernels are then [per b, per b +
    # per + r - 1), a whole group of `per` and the next one's first r - 1
    p = jnp.pad(p, ((0, 0),) * 4 + ((r - 1, per - r + 1),))
    best = p[..., :J].reshape(*p.shape[:-1], W, per).max(-1)
    if r > 1:
        best = jnp.maximum(best, p[..., per:].reshape(
            *p.shape[:-1], W, per)[..., :r - 1].max(-1))
    return jnp.moveaxis(best.sum(2), 1, 2)                  # [B, C, Hkv, W]


def sparse_select_taken(chunk: int, block_size: int,
                        table_width: int) -> bool:
    """Whether a sparse layer's chunk of ``chunk`` positions over a
    table of ``table_width`` pages scores its blocks through the kernel
    (``ops/sparse_scores.py``, ``hvd_sparse_scores`` in a device trace):
    the queries have to be whole tiles of it whose ``[tile, table]``
    blocks fit its fast memory; pages of any ``block_size`` do. By the
    shapes alone, here and on a TPU. Any other chunk, and every decode
    step, keeps :func:`sparse_block_scores`."""
    del block_size
    return sparse_scores_kernel.q_tile(chunk, table_width) is not None


def sparse_choose(scores, at_block, cfg):
    """The ``sparse_topk`` best blocks of ``scores`` [B, C, G, W] for
    queries in block ``at_block`` [B, C] (position // sparse_block):
    among the blocks up to the query's own, the first
    ``sparse_init_blocks`` and those that hold the ``sparse_window``
    positions before it first, then by score (a tie: the lower block).
    Returns ``(blocks [B, C, G, k] int32, chosen [B, C, G, k] bool)``,
    ``k = min(sparse_topk, W)``; ``chosen`` is False where fewer than
    ``k`` blocks lie at or before the query."""
    W = scores.shape[-1]
    b = jnp.arange(W, dtype=jnp.int32)
    at = at_block[..., None, None]
    valid = b <= at
    forced = valid & ((b < cfg.sparse_init_blocks)
                      | (b > at - cfg.sparse_window // cfg.sparse_block))
    ranked = jnp.where(forced, jnp.inf, jnp.where(valid, scores, -jnp.inf))
    best, blocks = lax.top_k(ranked, min(cfg.sparse_topk, W))
    return blocks.astype(jnp.int32), best > -jnp.inf


def sparse_attend_chunk(q, kp, vp, c, table, pos, allowed):
    """``q`` [1, C, H, Dh] at ``pos`` [C] over the pages of layer
    ``c`` behind ``table``, ``key_pages`` pages at a time up to the
    chunk's end, each query over the keys at or before it in the
    pages ``allowed`` [C, Hkv, W] it: float32 scores and a running
    softmax, so that a chunk's scores are one key block's. The form
    in XLA: the chunk of a shape :func:`sparse_attend_taken` refuses,
    and what the tests hold :func:`sparse_attend_pages` to."""
    C, H, Dh = q.shape[1:]
    Hkv, block_size = kp.shape[2:4]
    table_width = table.shape[0]
    key_pages = min(_SPARSE_KEY_BLOCK // block_size or 1, table_width)
    KB = key_pages * block_size
    n_blocks = -(-table_width // key_pages)
    padded = n_blocks * key_pages
    table = jnp.pad(table, (0, padded - table_width))
    allowed = jnp.pad(allowed, ((0, 0), (0, 0),
                                (0, padded - table_width)))
    qg = q[0].reshape(C, Hkv, H // Hkv, Dh)

    def attend(j, carry):
        acc, m, l = carry
        with jax.named_scope("kv_gather"):
            ids = lax.dynamic_slice_in_dim(table, j * key_pages,
                                           key_pages)
            keys = kp[c, ids].swapaxes(0, 1).reshape(Hkv, KB, Dh)
            vals = vp[c, ids].swapaxes(0, 1).reshape(Hkv, KB, Dh)
        key_pos = j * KB + jnp.arange(KB, dtype=jnp.int32)
        seen = jnp.repeat(lax.dynamic_slice_in_dim(
            allowed, j * key_pages, key_pages, 2), block_size, 2)
        seen = seen & (key_pos[None, None] <= pos[:, None, None])
        seen = jnp.moveaxis(seen, 0, 1)[:, None]        # [G, 1, C, KB]
        s = jnp.einsum("qgrd,gkd->grqk", qg, keys,
                       preferred_element_type=jnp.float32) * Dh ** -0.5
        s = jnp.where(seen, s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        scale = jnp.exp(m - m_new)
        acc = acc * scale[..., None] + jnp.einsum(
            "grqk,gkd->grqd", p.astype(vals.dtype), vals,
            preferred_element_type=jnp.float32)
        return acc, m_new, l * scale + p.sum(-1)

    shape = (Hkv, H // Hkv, C)
    acc, _, l = lax.fori_loop(
        0, jnp.minimum(pos[-1] // KB + 1, n_blocks), attend,
        (jnp.zeros(shape + (Dh,), jnp.float32),
         jnp.full(shape, _NEG_BIG, jnp.float32),
         jnp.zeros(shape, jnp.float32)))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(o, 2, 0).reshape(1, C, H * Dh).astype(q.dtype)


def _sparse_key_tile(block_size: int, table_width: int) -> int:
    """The kv tile of :func:`sparse_attend_pages`: ``_SPARSE_KEY_BLOCK``
    positions, or a shorter table's rounded up to whole lanes."""
    return min(_SPARSE_KEY_BLOCK, -(-table_width * block_size // 128) * 128)


def sparse_attend_taken(chunk: int, block_size: int,
                        table_width: int) -> bool:
    """Whether a sparse layer's chunk of ``chunk`` positions over a
    table of ``table_width`` pages of ``block_size`` attends through the
    kernel (:func:`sparse_attend_pages`): the queries have to be whole
    tiles of ``_SPARSE_KERNEL_CHUNK`` and the kv tile whole pages, 32 at
    most (a bit each of an int32). By the shapes alone, here and on a
    TPU."""
    tile = _sparse_key_tile(block_size, table_width)
    return (chunk % _SPARSE_KERNEL_CHUNK == 0 and tile % block_size == 0
            and tile // block_size <= 32)


@jax.jit
def sparse_attend_pages(q, kp, vp, c, table, pos, allowed):
    """:func:`sparse_attend_chunk` as ONE call of the Pallas flash
    forward over keys that carry their positions
    (``ops/flash_attention.py::flash_attention_keys`` with a page mask;
    ``hvd_flash_keys_fwd`` in a device trace): the table's K and V pages
    gathered once (``[Hkv, W * block, Dh]`` each; the kernel's index
    maps stop at the chunk's last kv tile, so what lies past it is
    copied here and read by nobody), a head a grid row with GQA by
    index map, ``allowed`` as a bit a page inside the tile. Float32
    scores, statistics and accumulator in VMEM, ``p`` rounded to the
    values' dtype: no ``[.., C, K]`` array reaches HBM. Jitted of
    itself, so that a program's call sites (a sparse layer each) trace
    and lower it once."""
    C, H, Dh = q.shape[1:]
    Hkv, block_size = kp.shape[2:4]
    tile = _sparse_key_tile(block_size, table.shape[0])
    table = jnp.pad(table, (0, -table.shape[0] % (tile // block_size)))
    with jax.named_scope("kv_gather"):
        keys, vals = (pages[c, table].swapaxes(0, 1).reshape(Hkv, -1, Dh)
                      for pages in (kp, vp))
    n_keys = keys.shape[1]
    o, _ = flash_attention_keys(
        jnp.moveaxis(q[0], 1, 0), keys, vals, pos[None],
        jnp.arange(n_keys, dtype=jnp.int32)[None], scale=Dh ** -0.5,
        page_mask=jnp.moveaxis(allowed, 1, 0), page=block_size,
        block_k=tile)
    return jnp.moveaxis(o, 0, 1).reshape(1, C, H * Dh).astype(q.dtype)


def _mla_attend(cfg, lp, qn, qr, keys_of, n_blocks, pos, window=None):
    """Latent attention of queries ``qn`` [B, C, H, Dh] (no position)
    and ``qr`` [B, C, H, R] (rotated) at positions ``pos`` [B, C] over
    ``n_blocks`` (traced, at least 1) blocks of cached latents, a block
    of keys at a time, so that no more than one block of them is ever
    gathered or expanded. ``keys_of(j) -> (latent [B, K, C + R],
    key_pos [K])`` gives block j; a key is seen where ``key_pos <=
    pos`` and, with a ``window`` (an mla_sliding layer's), ``key_pos >
    pos - window``. Float32 scores, softmax and accumulators over operands in the
    latents' dtype, ``p`` rounded to it for the value sum. Returns
    [B, C, H, Dh].

    The form is the **expanded** one (a chunk's queries: many a
    sequence): a block's latents are expanded to every KV head's key
    ``[c W_uk | r]`` and value ``c W_uv`` (``n_kv_heads`` of them: as
    many as query heads unless the heads are grouped, and then a KV
    head's group of query heads reads it by the kernel's index map)
    and attended by the Pallas
    flash forward over keys that carry their positions
    (``ops/flash_attention.py::flash_attention_keys``,
    ``hvd_flash_keys_fwd`` in a device trace): one contraction of
    ``Dh + R`` a score, scores, mask and running softmax a tile at a
    time in VMEM, so that no ``[H, C, K]`` tensor reaches HBM at any
    chunk width, and the running softmax carried from block to block
    through the kernel. Every chunk width goes this way
    (``_MLA_CHUNK_BLOCKS`` has the chip's times beside the einsum
    form's that it replaced). A decode step's attention is
    :func:`_mla_decode`; the tests hold both to the absorbed form in
    XLA (``tests/reference_mla.py``).

    A query that sees no key (a position below every key's: none a
    program sends) reads zeros."""
    B, C, H, Dh = qn.shape
    G = cfg.n_kv_heads
    rank, R = cfg.mla_kv_rank, cfg.mla_rope_dim
    w_uk, w_uv = tf_lib.mla_up(cfg, lp)
    scale = tf_lib.mla_scale(cfg)
    q = jnp.moveaxis(jnp.concatenate([qn, qr], -1), 2, 1).reshape(
        B * H, C, Dh + R)

    def attend(j, seen):
        latent, key_pos = keys_of(j)
        K = latent.shape[1]
        with jax.named_scope("mla_expand"):
            c, r = latent[..., :rank], latent[..., rank:rank + R]
            keys = jnp.concatenate(
                [jnp.einsum("bkc,chd->bhkd", c, w_uk),
                 jnp.broadcast_to(r[:, None], (B, G, K, R))], -1)
            vals = jnp.einsum("bkc,chd->bhkd", c, w_uv)
        return flash_attention_keys(
            q, keys.reshape(B * G, K, Dh + R), vals.reshape(B * G, K, Dh),
            pos, jnp.broadcast_to(key_pos[None], (B, K)), scale=scale,
            window=window, carry=seen)

    o, _ = lax.fori_loop(
        0, n_blocks, attend,
        (jnp.zeros((B * H, C, Dh), jnp.float32),
         jnp.full((B * H, C), _NEG_BIG, jnp.float32)))
    return jnp.moveaxis(o.reshape(B, H, C, Dh), 1, 2).astype(qn.dtype)


def mla_pages(pool, c, tables, key_block: int):
    """``keys_of`` of :func:`_mla_attend` over the pages of layer ``c``
    of the latent ``pool`` ``[n_mla, n_blocks, block_size, latent_row]``
    behind ``tables`` [B, W]: block j is the ``key_block`` positions
    from ``j * key_block``, gathered when it is attended (a copy
    ``[B, key_block, latent_row]``: what a chunk's expanded form reads,
    and what a decode step's read before :func:`_mla_decode`); and
    ``blocks_to(last)``, how many blocks hold the positions up to
    ``last``."""
    block_size, latent = pool.shape[2:]
    per = key_block // block_size
    key_blocks = -(-tables.shape[1] * block_size // key_block)
    tables = jnp.pad(tables, ((0, 0), (0, key_blocks * per
                                       - tables.shape[1])))

    def keys_of(j):
        with jax.named_scope("kv_gather"):
            ids = lax.dynamic_slice_in_dim(tables, j * per, per, 1)
            return (pool[c, ids].reshape(tables.shape[0], key_block,
                                         latent),
                    j * key_block + jnp.arange(key_block,
                                               dtype=jnp.int32))
    return keys_of, lambda last: jnp.minimum(last // key_block + 1,
                                             key_blocks)


def _absorbed(cfg, lp, h, qn, qr, row, read):
    """A decode step's latent attention, absorbed, for one query a row
    (``qn`` [B, 1, H, Dh], ``qr`` [B, 1, H, R]): ``read(q [B, H, row])
    -> [B, H, rank]`` attends each row's own latents where they lie
    (``hvd_latent_decode`` in a device trace), ``row`` values wide. The
    small products stay in XLA around the call: ``q W_uk^T`` before it
    (a query head by its KV head's ``W_uk``) and ``(sum p c) W_uv``
    after it, and between the two the differential heads' subtraction,
    IN THE LATENT (``tf_lib.gdla_diff``; ``h`` the layer's normed input:
    linear, so ``W_uv`` is applied to the signal heads alone). Returns
    [B, 1, Hs, Dh], the signal heads (all ``H`` without noise heads)."""
    w_uk, w_uv = tf_lib.mla_up(cfg, lp)
    B, _, H, Dh = qn.shape
    G = cfg.n_kv_heads
    # (ungrouped heads keep the two products they had, operation for
    # operation: tools/serve_programs_digest.py holds Kimi's and Ling's)
    if G == H:
        q = jnp.einsum("bqhd,chd->bqhc", qn, w_uk)
    else:
        q = jnp.einsum("bqgsd,cgd->bqgsc", qn.reshape(B, 1, G, H // G, Dh),
                       w_uk).reshape(B, 1, H, -1)
    q = jnp.concatenate([q, qr], -1)
    q = jnp.pad(q[:, 0], ((0, 0), (0, 0), (0, row - q.shape[-1])))
    o = tf_lib.gdla_diff(cfg, lp, h, read(q)[:, None])
    if G == H:
        return jnp.einsum("bqhc,chd->bqhd", o, w_uv)
    signal = o.shape[2] // G
    return jnp.einsum("bqgsc,cgd->bqgsd",
                      o.reshape(B, 1, G, signal, -1), w_uv
                      ).reshape(B, 1, G * signal, Dh)


def _mla_decode(cfg, lp, qn, qr, pool, c, tables, positions, h=None):
    """A decode step's latent attention (:func:`_absorbed`) at
    ``positions`` [B], over the pages of layer ``c`` of the latent
    ``pool`` behind ``tables`` [B, W], read where they lie by
    ``ops/paged_decode.py::latent_decode``: each row's own pages, once,
    no further than its position, with no gathered copy of a key block
    and no score tensor in HBM. ``h``: the layer's normed input, where
    the configuration has noise heads."""
    return _absorbed(
        cfg, lp, h, qn, qr, pool.shape[-1], lambda q: latent_decode(
            q, pool, c, tables, positions + 1, rank=cfg.mla_kv_rank,
            scale=tf_lib.mla_scale(cfg)))


def _mla_ring_decode(cfg, lp, qn, qr, rings, c, slots, positions, page, h):
    """:func:`_mla_decode` of an mla_sliding layer: each row over its
    own slot's ring of latents, from the first key its window admits to
    its own (``ops/paged_decode.py::latent_ring_decode``)."""
    return _absorbed(
        cfg, lp, h, qn, qr, rings.shape[-1], lambda q: latent_ring_decode(
            q, rings, c, slots, positions, window=cfg.attn_window,
            page=page, rank=cfg.mla_kv_rank, scale=tf_lib.mla_scale(cfg)))


def mixed_programs(cfg, block_size: int, table_width: int, ring: int,
                   compression=None, head=None, chosen: bool = False):
    """(prefill, prefill_resume, decode, held_experts_counts), not
    jitted, of a configuration with layers of several kinds (the last
    is :func:`moe_share_report`'s). The caches ``kc`` and ``vc`` are
    tuples with one array a kind of layer, in
    ``kv_cache.state_kinds(cfg)``'s order (``KVCache`` says which array
    is what) for the eleven kinds of layer: pages behind the block tables
    for ``full``, ``mla`` and ``sparse`` layers, and rings (of keys and
    values, or of latents), recurrent
    states and convolution rows for ``sliding``, ``mla_sliding``, ``kda``,
    ``mamba``,
    ``lightning``, ``conv`` and ``mamba2`` layers, one a batch slot (slot 0 is the
    null slot, as block 0 is the null block); an ``eva`` layer alone has
    BOTH halves, its open window's K and V rows by slot and its chunk
    summaries in pages behind the tables. An address is a pair too:
    ``(block_table, slot)``.

    The layers are a Python loop: each knows its kind, its stack and
    its place in its kind's arrays when the program is traced. **A
    kind is one entry of one table** (``kinds`` below): how a chunk of
    one sequence, and how a decode step of the batch, reads and writes
    that kind's state around its attention. A program gives every
    layer the same ``call``: its positions and addresses. ``head`` maps
    float32 logits to what a program returns (None: their argmax, the
    next token, of row 0 where the head has ``head_rows`` rows a
    position, logits ``[.., head_rows, vocab]``; the tests read the
    logits themselves). ``chosen``: the
    programs return a fourth value, the pages the queries of every
    sparse layer chose ([n_sparse, C or B, Hkv, table_width] bool, all
    False for a query below ``sparse_dense_len``): the same programs
    with one more output, for the tests and the tolerance tool."""
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.attn_window
    if head is None:
        def head(logits):
            if cfg.head_rows > 1:
                logits = logits[..., 0, :]      # row 0: the next token
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    place = {kind: n for n, kind in enumerate(state_kinds(cfg))}
    # layer -> (its list, its index there, its kind, its index in its
    # cache); a layer that is a feed-forward alone (tf_lib.FFN) is of no
    # kind that keeps anything
    plan, seen = [], dict.fromkeys((*place, tf_lib.FFN), 0)
    for i in range(cfg.n_layers):
        stack, j = (("dense_layers", i) if i < cfg.n_dense_layers
                    else ("layers", i - cfg.n_dense_layers))
        kind = cfg.kind_of(i)
        plan.append((stack, j, kind, seen[kind]))
        seen[kind] += 1
    n_win = seen.get("sliding", 0) + seen.get("mla_sliding", 0)
    S = table_width * block_size
    latent = (latent_row(cfg) if set(tf_lib.MLA_KINDS) & set(place) else 0)
    # the latent kinds' scope: two names where a stack has both
    mla_scope = ({"mla": "attn_mla_full", "mla_sliding": "attn_mla_window"}
                 if "mla_sliding" in place else {"mla": "attn_mla"})
    # summaries a page of an eva layer holds
    per_eva = block_size // cfg.eva_chunk if "eva" in place else 0
    # an mla chunk's key blocks: whole pages, the table padded to them
    chunk_key_block = min(_MLA_CHUNK_BLOCKS * _MLA_KEY_BLOCK,
                          S) // block_size * block_size

    def embed(params, tokens):
        with jax.named_scope("embed"):
            x = tf_lib.embed_lookup(params["embed"], tokens, cfg.dtype,
                                    None, compression)
            if cfg.embed_scale:
                x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
            if cfg.embed_multiplier is not None:
                x = x * jnp.asarray(cfg.embed_multiplier, cfg.dtype)
            if cfg.mhc_streams > 1:
                # mHC: the embedding copied to every stream
                x = jnp.broadcast_to(
                    x[:, :, None], x.shape[:2] + (cfg.mhc_streams, x.shape[2]))
            # a float32 stream starts here (the norms give cfg.dtype back)
            return x.astype(jnp.float32) if cfg.stream_fp32 else x

    def layers(params, kc, vc, x, call, moe_fn=None):
        """Every layer over ``x`` [B, T, D]: ``kinds[kind][call.step](
        call, lp, kc, vc, c, x, i) -> (kc, vc, x)`` is layer i's
        attention, residual included, over place ``c`` of its kind's
        arrays."""
        for i, (stack, j, kind, c) in enumerate(plan):
            lp = params[stack][j]
            # a stack of one-branch layers: the mixer alone, or ("ffn")
            # the feed-forward alone
            if kind != tf_lib.FFN:
                with jax.named_scope("attn"):
                    kc, vc, x = kinds[kind][call.step](call, lp, kc, vc, c,
                                                       x, i)
            if kind == tf_lib.FFN or not cfg.one_branch:
                with jax.named_scope("mlp"):
                    x, _aux = tf_lib.ffn_block(cfg, lp, x, moe_fn)
        return kc, vc, x

    def put(cache, kind, at, new):
        """``new`` at ``at`` of ``kind``'s array of ``cache`` (None: a
        program that keeps nothing)."""
        if cache is None:
            return None
        n = place[kind]
        return swap(cache, kind, cache[n].at[at].set(
            new.astype(cache[n].dtype)))

    def swap(cache, kind, array):
        """``cache`` with ``array`` as ``kind``'s (a kernel that took
        the old one aliased hands back the whole of it)."""
        n = place[kind]
        return cache[:n] + (array,) + cache[n + 1:]

    def emit(params, x, rows):
        if cfg.mhc_streams > 1:
            # mHC: the emitted rows' streams summed, before the norm
            with jax.named_scope("mhc_mix"):
                x = rows(x).astype(jnp.float32).sum(-2).astype(cfg.dtype)

            def rows(x):
                return x
        with jax.named_scope("head"):
            x = rows(tf_lib.stream_norm(cfg, x, params["final_norm"]))
            if cfg.logit_divisor is not None:
                x = x / jnp.asarray(cfg.logit_divisor, x.dtype)
            if cfg.stream_fp32 or cfg.head_rows > 1:
                # the product kept in float32, and read as head_rows
                # rows of the vocabulary a position
                logits = jnp.einsum(
                    "...d,dv->...v", x, tf_lib.head_weights(cfg, params),
                    preferred_element_type=jnp.float32)
                if cfg.head_rows > 1:
                    logits = logits.reshape(*logits.shape[:-1],
                                            cfg.head_rows, cfg.vocab_size)
                return head(logits)
            if cfg.tie_embeddings:
                # x E^T, the table read where it lies and not turned
                return head(jnp.einsum("...d,vd->...v", x, params["embed"]
                                       ).astype(jnp.float32))
            return head((x @ params["lm_head"]).astype(jnp.float32))

    def softmax_layer(call, lp, kc, vc, x, i, scope, write, attend):
        """A window or full layer: ``write(cache, new) -> cache`` puts
        the layer's new K or V in place, ``attend(q, k, v, kc, vc)``
        attends over them."""
        q, k, v = tf_lib.attention_inputs(cfg, lp, x, call.pos, i)
        with jax.named_scope(scope):
            if kc is not None:
                with jax.named_scope("kv_write"):
                    kc, vc = write(kc, k), write(vc, v)
            o = attend(q, k, v, kc, vc)
        return kc, vc, tf_lib.attention_residual(cfg, lp, x, o)

    def pages(cache, c, table):
        """A ``full`` layer's K or V behind the table of a chunk's one
        sequence (a page's positions hold ``kv_cache.page_tail``)."""
        with jax.named_scope("kv_gather"):
            return cache[place["full"]][c, table].reshape(1, S, Hkv, Dh)

    # -- a chunk of one sequence (B = 1) -----------------------------

    def window_chunk(call, lp, kc, vc, c, x, i):
        def write(cache, new):
            return put(cache, "sliding", (c, call.slot, call.pos[0] % ring),
                       new.reshape(-1, Hkv, Dh))

        def attend(q, k, v, kc, vc):
            if call.local:
                return _attend_keys(q, k, v, call.pos, call.pos, window)
            n = place["sliding"]
            return _attend_keys(q, kc[n][c, call.slot][None],
                                vc[n][c, call.slot][None], call.held,
                                call.pos, window)
        return softmax_layer(call, lp, kc, vc, x, i, "attn_window", write,
                             attend)

    def full_chunk(call, lp, kc, vc, c, x, i):
        def write(cache, new):
            return put(cache, "full", (c, call.blks),
                       new[0].reshape(-1, block_size, *page_tail(cfg)))

        def attend(q, k, v, kc, vc):
            if call.local:
                return _attend_keys(q, k, v, call.pos, call.pos, None)
            return _attend_keys(
                q, pages(kc, c, call.table), pages(vc, c, call.table),
                jnp.arange(S, dtype=jnp.int32)[None], call.pos, None)
        return softmax_layer(call, lp, kc, vc, x, i, "attn_full", write,
                             attend)

    def kda_chunk(call, lp, kc, vc, c, x, i):
        """The chunk's recurrence from the state and the convolution's
        rows the slot holds (zeros for a sequence's first chunk,
        whatever the slot held), and both back as they are AT
        ``length``: a bucket's padding decays nothing and writes
        nothing."""
        B, T = x.shape[:2]
        n = place["kda"]
        with jax.named_scope("attn_kda"):
            h, rows = tf_lib.kda_rows(cfg, lp, x)
            state = jnp.zeros((B, H, Dh, Dh), jnp.float32)
            before = jnp.zeros((B, cfg.kda_conv - 1, rows.shape[-1]),
                               rows.dtype)
            if not call.local:
                resumed = call.offset > 0
                state = jnp.where(resumed, kc[n][c, call.slot][None], state)
                before = jnp.where(resumed, vc[n][c, call.slot][None], before)
            with jax.named_scope("kda_conv"):
                q, k, v = tf_lib.kda_conv(cfg, lp, rows, before)
            with jax.named_scope("kda_gates"):
                g, beta = tf_lib.kda_gates(cfg, lp, h)
                real = jnp.arange(T)[None, :, None] < call.length
                g = jnp.where(real[..., None], g, 0.0)
                beta = jnp.where(real, beta, 0.0)
            with jax.named_scope("kda_scan"):
                o, state = kda_scan(q, k, v, g, beta, state)
            if kc is not None:
                with jax.named_scope("state_write"):
                    newest = lax.dynamic_slice_in_dim(
                        jnp.concatenate([before, rows], 1)[0], call.length,
                        cfg.kda_conv - 1)
                    kc = put(kc, "kda", (c, call.slot), state[0])
                    vc = put(vc, "kda", (c, call.slot), newest)
        return kc, vc, tf_lib.kda_residual(cfg, lp, x, h, o)

    def latent_chunk(kind):
        """An mla layer's chunk, or (``kind`` "mla_sliding") one whose
        latents go into the slot's ring and whose queries see
        ``window`` keys back."""
        rings = kind == "mla_sliding"
        seen = {"window": window} if rings else {}

        def chunk(call, lp, kc, vc, c, x, i):
            """Expanded attention: over the chunk itself where it is
            the whole prompt, else over the sequence's pages (the
            slot's ring, whole: 1168 places at Motif's sizes)."""
            n = place[kind]
            with jax.named_scope(mla_scope[kind]):
                u, mix = tf_lib.stream_in(cfg, lp, "attn", x)
                h, qn, qr, new = tf_lib.mla_inputs(cfg, lp, u, call.pos)
                new = jnp.pad(new, ((0, 0), (0, 0),
                                    (0, latent - new.shape[-1])))
                if kc is not None:
                    with jax.named_scope("kv_write"):
                        if rings:
                            kc = put(kc, kind,
                                     (c, call.slot, call.pos[0] % ring),
                                     new[0])
                        else:
                            kc = put(kc, kind, (c, call.blks),
                                     new[0].reshape(-1, block_size, latent))
                with jax.named_scope("mla_attend"):
                    if call.local:
                        # every row's keys are its own prompt's, one block
                        o = _mla_attend(
                            cfg, lp, qn, qr, lambda j: (new, call.pos[0]), 1,
                            call.pos, **seen)
                    elif rings:
                        with jax.named_scope("kv_gather"):
                            held = kc[n][c, call.slot][None]
                        o = _mla_attend(
                            cfg, lp, qn, qr, lambda j: (held, call.held[0]),
                            1, call.pos, **seen)
                    else:
                        keys_of, blocks_to = mla_pages(
                            kc[n], c, call.table[None], chunk_key_block)
                        o = _mla_attend(cfg, lp, qn, qr, keys_of,
                                        blocks_to(call.pos[0, -1]), call.pos)
                    o = tf_lib.gdla_diff(cfg, lp, h, o)
            return kc, vc, tf_lib.mla_residual(cfg, lp, x, h, o, mix)
        return chunk

    def mamba_chunk(call, lp, kc, vc, c, x, i):
        """The chunk's selective scan from the state and the
        convolution's rows the slot holds (zeros for a sequence's first
        chunk, whatever the slot held), and both back as they are AT
        ``length``: a padded position's step is 0, which decays nothing
        and drives nothing, and the rows kept are the last real ones."""
        B, T = x.shape[:2]
        n = place["mamba"]
        N, Di = cfg.mamba_d_state, cfg.mamba_expand * cfg.d_model
        with jax.named_scope("attn_mamba"):
            with jax.named_scope("mamba_proj"):
                u, z = tf_lib.mamba_rows(cfg, lp, x)
            state = jnp.zeros((B, N, Di), jnp.float32)
            before = jnp.zeros((B, cfg.mamba_d_conv - 1, Di), u.dtype)
            if not call.local:
                resumed = call.offset > 0
                state = jnp.where(resumed, kc[n][c, call.slot][None], state)
                before = jnp.where(
                    resumed, vc[n][c, call.slot].reshape(before.shape),
                    before)
            with jax.named_scope("mamba_conv"):
                uc = tf_lib.mamba_conv(cfg, lp, u, before)
            with jax.named_scope("mamba_proj"):
                step, b, cc = tf_lib.mamba_gates(cfg, lp, uc)
                real = jnp.arange(T)[None, :, None] < call.length
                step = jnp.where(real, step, 0.0)
            with jax.named_scope("mamba_scan"):
                uc = uc.astype(jnp.float32)
                a = -jnp.exp(lp["a_log"])
                if mamba_scan_kernel.taken(N, Di, T):
                    y, state = mamba_scan_kernel.mamba_scan(
                        uc, step, a, b, cc, state, call.length)
                else:
                    y, state = mamba_scan(uc, step, a, b, cc, state)
                y = y + lp["d_skip"] * uc
            if kc is not None:
                with jax.named_scope("state_write"):
                    newest = lax.dynamic_slice_in_dim(
                        jnp.concatenate([before, u], 1)[0], call.length,
                        cfg.mamba_d_conv - 1)
                    kc = put(kc, "mamba", (c, call.slot), state[0])
                    vc = put(vc, "mamba", (c, call.slot), newest.reshape(-1))
        return kc, vc, tf_lib.mamba_residual(cfg, lp, x, y, z)

    def mamba2_chunk(call, lp, kc, vc, c, x, i):
        """The chunk's SSD from the state and the convolution's rows the
        slot holds (zeros for a sequence's first chunk, whatever the
        slot held), and both back as they are AT ``length``: a padded
        position's step is 0, which decays nothing and drives nothing,
        and the rows kept are the last real ones."""
        B, T = x.shape[:2]
        n = place["mamba2"]
        taps, W = cfg.mamba_d_conv, cfg.mamba2_conv_width
        with jax.named_scope("attn_mamba2"):
            with jax.named_scope("mamba2_proj"):
                z, xbc, dt = tf_lib.mamba2_rows(cfg, lp, x)
            state = jnp.zeros((B, cfg.mamba2_heads, cfg.mamba2_head_dim,
                               cfg.mamba_d_state), jnp.float32)
            before = jnp.zeros((B, taps - 1, W), xbc.dtype)
            if not call.local:
                resumed = call.offset > 0
                state = jnp.where(resumed, kc[n][c, call.slot][None], state)
                before = jnp.where(
                    resumed, vc[n][c, call.slot].reshape(before.shape),
                    before)
            with jax.named_scope("conv_taps"):
                xs, b, cc, step = tf_lib.mamba2_inputs(cfg, lp, xbc, dt,
                                                       before)
                real = jnp.arange(T)[None, :, None] < call.length
                step = jnp.where(real, step, 0.0)
            with jax.named_scope("mamba2_scan"):
                a = -jnp.exp(lp["a_log"])
                if ssd_scan_taken(cfg, T):
                    # D x inside the call: behind it, it costs four
                    # copies of the chunk's rows a layer (ops/ssd_scan.py)
                    y, state = ssd_scan_kernel.ssd_scan(
                        xs, step, a, b, cc, state, call.length,
                        block=cfg.mamba2_chunk, skip=lp["d_skip"])
                else:
                    y, state = ssd_scan(xs, step, a, b, cc, state,
                                        cfg.mamba2_chunk)
                    y = y + lp["d_skip"][:, None] * xs
            if kc is not None:
                with jax.named_scope("state_write"):
                    newest = lax.dynamic_slice_in_dim(
                        jnp.concatenate([before, xbc], 1)[0], call.length,
                        taps - 1)
                    kc = put(kc, "mamba2", (c, call.slot), state[0])
                    vc = put(vc, "mamba2", (c, call.slot),
                             newest.reshape(-1))
            x = tf_lib.mamba2_residual(cfg, lp, x, y, z)
        return kc, vc, x


    # A sparse layer's sizes: `per` kernels start in a page, a kernel
    # spans `strides` strides; a row past sparse_dense_len reads its
    # sparse_topk chosen pages and one below it all of its own, at most
    # `step_pages` either way.
    stride = cfg.sparse_stride
    per, strides = block_size // stride, cfg.sparse_kernel // stride
    dense_pages = cfg.sparse_dense_len // block_size
    step_pages = min(max(cfg.sparse_topk, dense_pages), table_width)

    def sparse_kernels_seen(pos):
        """[.., J]: the kernels of a table's pages that are complete at
        ``pos`` [..]."""
        last = stride * jnp.arange(table_width * per, dtype=jnp.int32) \
            + cfg.sparse_kernel - 1
        return last <= pos[..., None]

    def kernels_behind(ck, c, tables):
        """The compressed keys of layer ``c`` behind ``tables`` [B, W],
        kernel by kernel: [B, W * per, Hkv, Dh]."""
        return ck[c, tables].swapaxes(2, 3).reshape(
            tables.shape[0], table_width * per, Hkv, Dh)

    def sparse_chosen_pages(blocks, ok):
        """[.., Hkv, W] bool out of the chosen list [.., Hkv, k]."""
        lead = blocks.shape[:-1]
        at = tuple(jnp.arange(n).reshape((1,) * i + (n,) + (1,) * (
            len(lead) - i)) for i, n in enumerate(lead))
        return jnp.zeros(lead + (table_width,), bool).at[
            at + (blocks,)].set(ok)

    def sparse_chunk(call, lp, kc, vc, c, x, i):
        """A full layer's chunk with the selection inside it: the new
        keys into their pages, the compressed keys of every kernel the
        call completes AT ``length`` (a kernel spans two pages: the
        first of them reaches back into the page before the chunk),
        then each query past ``sparse_dense_len`` scores, pools and
        chooses, and the chunk attends every key block up to its end
        under the mask of what each query chose, with a running
        softmax. A whole prompt no longer than ``sparse_dense_len``
        attends over itself as a full layer's does."""
        n = place["sparse"]
        Tc = x.shape[1]
        heads = jnp.arange(Hkv)
        q, k, v = tf_lib.attention_inputs(cfg, lp, x, call.pos, i)
        with jax.named_scope("attn_sparse"):
            # a whole prompt under sparse_dense_len: a full layer's
            attend_local = call.local and Tc <= cfg.sparse_dense_len
            assert attend_local or kc is not None
            if kc is not None:
                with jax.named_scope("kv_write"):
                    def pages_of(new):      # a page: [Hkv, block, Dh]
                        return new[0].reshape(-1, block_size, Hkv, Dh
                                              ).swapaxes(1, 2)
                    kp, ck = kc[n]
                    kp = kp.at[c, call.blks].set(pages_of(k).astype(kp.dtype))
                    vc = put(vc, "sparse", (c, call.blks), pages_of(v))
                with jax.named_scope("sparse_compress"):
                    back = cfg.sparse_kernel - stride
                    before = jnp.take(call.table, jnp.maximum(
                        call.offset // block_size - 1, 0))
                    rows = jnp.concatenate(
                        [kp[c, before, :, block_size - back:].swapaxes(0, 1),
                         k[0].astype(kp.dtype)])
                    j = (call.offset - back) // stride + jnp.arange(
                        Tc // stride, dtype=jnp.int32)
                    whole = (j >= 0) & (stride * j + cfg.sparse_kernel
                                        <= call.offset + call.length)
                    slot = j // per
                    blk = jnp.where(
                        whole & (slot < table_width),
                        jnp.take(call.table,
                                 jnp.clip(slot, 0, table_width - 1)),
                        NULL_BLOCK)
                    ck = ck.at[c, blk[:, None], heads, (j % per)[:, None]
                               ].set(kernel_means(rows, stride, strides))
                kc = swap(kc, "sparse", (kp, ck))
            if attend_local:
                o = _attend_keys(q, k, v, call.pos, call.pos, None)
            else:
                past = call.pos[0] >= cfg.sparse_dense_len           # [Tc]
                with jax.named_scope("sparse_select"):
                    def select():
                        if sparse_select_taken(Tc, block_size, table_width):
                            scores = sparse_scores_kernel.sparse_scores(
                                q[0], ck[c, call.table], call.offset,
                                call.length, stride=stride,
                                kernel=cfg.sparse_kernel)[None]
                        else:
                            scores = sparse_block_scores(
                                q, kernels_behind(ck, c, call.table[None]),
                                sparse_kernels_seen(call.pos), per, strides)
                        return sparse_chosen_pages(*sparse_choose(
                            scores, call.pos // block_size, cfg))[0]

                    picked = lax.cond(
                        past[-1], select, lambda: jnp.zeros(
                            (Tc, Hkv, table_width), bool))
                    picked &= past[:, None, None]
                with jax.named_scope("sparse_attend"):
                    attend = (sparse_attend_pages if sparse_attend_taken(
                        Tc, block_size, table_width) else sparse_attend_chunk)
                    o = attend(q, kp, vc[n], c, call.table, call.pos[0],
                               picked | ~past[:, None, None])
            if chosen:
                call.chose.append(jnp.zeros((Tc, Hkv, table_width), bool)
                                  if attend_local else picked)
        return kc, vc, tf_lib.attention_residual(cfg, lp, x, o)

    def lightning_chunk(call, lp, kc, vc, c, x, i):
        """The chunk's recurrence from the state the slot holds (zeros
        for a sequence's first chunk, whatever the slot held), and the
        state back as it is AT ``length``: a padded position decays
        nothing and writes nothing."""
        B, T = x.shape[:2]
        n = place["lightning"]
        with jax.named_scope("attn_lightning"):
            h, q, k, v = tf_lib.lightning_inputs(cfg, lp, x, call.pos, i)
            state = jnp.zeros((B, q.shape[2], q.shape[3], q.shape[3]),
                              jnp.float32)
            if not call.local:
                state = jnp.where(call.offset > 0,
                                  kc[n][c, call.slot][None], state)
            real = jnp.arange(T)[None, :, None] < call.length
            g = jnp.where(real, tf_lib.lightning_decay(cfg), 0.0)
            with jax.named_scope("lightning_scan"):
                o, state = lightning_scan(
                    q, jnp.where(real[..., None], k, 0.0), v, g, state)
            if kc is not None:
                with jax.named_scope("state_write"):
                    kc = put(kc, "lightning", (c, call.slot), state[0])
        return kc, vc, tf_lib.lightning_residual(cfg, lp, x, h, o)

    def conv_chunk(call, lp, kc, vc, c, x, i):
        """The chunk's gated convolution from the rows the slot holds
        (zeros for a sequence's first chunk, whatever the slot held),
        and the rows back as they are AT ``length``: the last real
        ones, not the bucket's last (a padded position writes nothing a
        later call reads; a real position sees none after it)."""
        B = x.shape[0]
        n = place["conv"]
        kept = cfg.conv_taps - 1
        with jax.named_scope("attn_conv"):
            with jax.named_scope("conv_proj"):
                b, cc, u = tf_lib.conv_inputs(cfg, lp, x)
            before = jnp.zeros((B, kept, cfg.d_model), u.dtype)
            if not call.local:
                before = jnp.where(
                    call.offset > 0,
                    kc[n][c, call.slot].reshape(before.shape), before)
            with jax.named_scope("conv_taps"):
                z, g = tf_lib.conv_gated(cfg, lp, b, cc, u, before)
            if kc is not None:
                with jax.named_scope("state_write"):
                    newest = lax.dynamic_slice_in_dim(
                        jnp.concatenate([before, z], 1)[0], call.length,
                        kept)
                    kc = put(kc, "conv", (c, call.slot), newest.reshape(-1))
            with jax.named_scope("conv_proj"):
                x = tf_lib.conv_residual(cfg, lp, x, g)
        return kc, vc, x

    def eva_chunk(call, lp, kc, vc, c, x, i):
        """A chunk that lies in ONE window (the engine cuts a prompt's
        chunks at the windows' ends; a whole prompt no longer than a
        window attends over itself): its queries attend, in one softmax
        carried from the first call into the second
        (``flash_attention_keys``' ``carry``), one summary a chunk of
        every CLOSED window out of the pages, then the open window's
        rows from before the chunk and the chunk's own keys up to each
        query. Then the chunk's real rows go into the slot's rows at
        ``offset % eva_window`` (a padded position writes none: rows
        past ``length`` are not live) and the summaries of its whole
        groups of ``eva_chunk`` into its pages (zeros where a group is
        not whole at ``length``: a decode step closes it later, from
        its rows)."""
        n = place["eva"]
        Tc = x.shape[1]
        W, ch = cfg.eva_window, cfg.eva_chunk
        assert Tc <= W and Tc % ch == 0, (
            f"an eva layer's chunk of {Tc} lies in one window of {W}, in "
            f"whole chunks of {ch}")
        q, k, v = tf_lib.attention_inputs(cfg, lp, x, call.pos, i)
        with jax.named_scope("attn_eva"):
            qh = q[0].swapaxes(0, 1)                          # [H, Tc, Dh]
            own = call.pos[0]
            keys, vals, key_pos = k[0], v[0], own
            carry = None
            if not call.local:
                (kr, ks), (vr, vs) = kc[n], vc[n]
                start = call.offset % W
                with jax.named_scope("eva_summaries"):
                    seen = (call.offset // W) * (W // ch)
                    at = jnp.where(jnp.arange(table_width * per_eva,
                                              dtype=jnp.int32) < seen, 0, -1)
                    carry = flash_attention_keys(
                        qh, *(pool[c, call.table].reshape(-1, Hkv, Dh)
                              .swapaxes(0, 1) for pool in (ks, vs)),
                        call.pos, at[None], scale=Dh ** -0.5)
                if Tc < W:
                    # the window's rows before the chunk: start <= W - Tc
                    r = jnp.arange(W - Tc, dtype=jnp.int32)
                    keys = jnp.concatenate(
                        [kr[c, call.slot, :W - Tc].astype(k.dtype), keys])
                    vals = jnp.concatenate(
                        [vr[c, call.slot, :W - Tc].astype(v.dtype), vals])
                    key_pos = jnp.concatenate(
                        [jnp.where(r < start, call.offset - start + r, -1),
                         own])
            with jax.named_scope("eva_window"):
                o, _ = flash_attention_keys(
                    qh, keys.swapaxes(0, 1), vals.swapaxes(0, 1), call.pos,
                    key_pos[None], scale=Dh ** -0.5, carry=carry)
            o = o.swapaxes(0, 1).reshape(1, Tc, H * Dh).astype(q.dtype)
            if kc is not None:
                (kr, ks), (vr, vs) = kc[n], vc[n]
                with jax.named_scope("kv_write"):
                    j = jnp.arange(Tc, dtype=jnp.int32)
                    rows = jnp.where(j < call.length, call.offset % W + j, W)
                    kr = kr.at[c, call.slot, rows].set(
                        k[0].astype(kr.dtype), mode="drop")
                    vr = vr.at[c, call.slot, rows].set(
                        v[0].astype(vr.dtype), mode="drop")
                with jax.named_scope("eva_summarise"):
                    sk, sv = eva_summaries(
                        k[0].astype(kr.dtype).reshape(-1, ch, Hkv, Dh),
                        v[0].astype(vr.dtype).reshape(-1, ch, Hkv, Dh),
                        lp["eva_mu"], lp["eva_phi"])
                    whole = (ch * (1 + jnp.arange(Tc // ch, dtype=jnp.int32))
                             <= call.length)[:, None, None]
                    ks = ks.at[c, call.blks].set(jnp.where(
                        whole, sk, 0).reshape(-1, per_eva, Hkv, Dh))
                    vs = vs.at[c, call.blks].set(jnp.where(
                        whole, sv, 0).reshape(-1, per_eva, Hkv, Dh))
                kc = swap(kc, "eva", (kr, ks))
                vc = swap(vc, "eva", (vr, vs))
        return kc, vc, tf_lib.attention_residual(cfg, lp, x, o)

    # -- a decode step of the batch (one position a row) -------------

    def by_slot(call, rows, n_slots):
        """``rows`` [B, ...] laid out by slot: row i at ``slots[i]``,
        zeros at the slots that are not in the batch."""
        return jnp.zeros((n_slots,) + rows.shape[1:],
                         rows.dtype).at[call.slots].set(rows)

    def window_step(call, lp, kc, vc, c, x, i):
        """Each row over its own slot's ring where it lies in the
        stacked cache, from the first key its window admits to its own
        position (``ops/paged_decode.py::ring_decode``: the rings read
        as pages, ``hvd_paged_decode`` under ``attn_window``): no ring
        is sliced out of the cache and no score reaches HBM."""
        def write(cache, new):
            return put(cache, "sliding",
                       (c, call.slots, call.positions % ring),
                       new.reshape(-1, Hkv, Dh))

        def attend(q, k, v, kc, vc):
            n = place["sliding"]
            o = ring_decode(q[:, 0], kc[n], vc[n], c, call.slots,
                            call.positions, window=window,
                            page=ring_page(ring, block_size))
            return o.reshape(o.shape[0], 1, H * Dh)
        return softmax_layer(call, lp, kc, vc, x, i, "attn_window", write,
                             attend)

    def full_step(call, lp, kc, vc, c, x, i):
        """Each row over its own pages, where they lie in the two pools
        and no further than its position (``ops/paged_decode.py``, the
        Pallas call ``hvd_paged_decode``): no table is gathered and no
        score reaches HBM."""
        def write(cache, new):
            return put(cache, "full",
                       (c, call.blk, call.positions % block_size),
                       new.reshape(-1, *page_tail(cfg)))

        def attend(q, k, v, kc, vc):
            n = place["full"]
            o = paged_decode(q[:, 0], kc[n], vc[n], c, call.tables,
                             call.positions + 1)
            return o.reshape(o.shape[0], 1, H * Dh)
        return softmax_layer(call, lp, kc, vc, x, i, "attn_full", write,
                             attend)

    def kda_step_layer(call, lp, kc, vc, c, x, i):
        """One step of the delta rule on each row's own state where it
        lies in the pool (``ops/state_step.py::kda_step``, the Pallas
        call ``hvd_state_step``: a head's state read once, ``S'^T k``,
        ``S'^T q`` and the update on it in VMEM, and written once; a
        slot that is not in the batch not touched). A state that is not
        whole tiles keeps, on a TPU, the XLA form: every slot's state
        where it lies, the batch's rows carried to their slots and the
        results back, a slot that is not in the batch decayed by 1 and
        written by 0."""
        n = place["kda"]
        with jax.named_scope("attn_kda"):
            h, rows = tf_lib.kda_rows(cfg, lp, x)
            before = vc[n][c, call.slots]
            with jax.named_scope("kda_conv"):
                q, k, v = tf_lib.kda_conv(cfg, lp, rows, before)
            with jax.named_scope("kda_gates"):
                g, beta = tf_lib.kda_gates(cfg, lp, h)
            inputs = tuple(a[:, 0] for a in (q, k, v, g, beta))
            if state_step_kernel.taken(*kc[n].shape[3:]):
                with jax.named_scope("kda_step"):
                    o, state = state_step_kernel.kda_step(
                        *inputs, kc[n], c, call.slots)
                with jax.named_scope("state_write"):
                    kc = swap(kc, "kda", state)
            else:
                with jax.named_scope("kda_step"):
                    n_slots = kc[n].shape[1]
                    o, state = kda_step(*(by_slot(call, a, n_slots)
                                          for a in inputs), kc[n][c])
                    o = o[call.slots]
                with jax.named_scope("state_write"):
                    kc = put(kc, "kda", (c,), state)
            with jax.named_scope("state_write"):
                vc = put(vc, "kda", (c, call.slots),
                         jnp.concatenate([before, rows], 1)[:, 1:])
        return kc, vc, tf_lib.kda_residual(cfg, lp, x, h, o[:, None])

    def latent_step(kind):
        """An mla layer's step, or (``kind`` "mla_sliding") one over
        the slots' rings of latents."""
        rings = kind == "mla_sliding"

        def step(call, lp, kc, vc, c, x, i):
            """Absorbed attention over each row's own pages, or its
            own slot's ring from the first key its window admits, where
            they lie (:func:`_mla_decode`)."""
            n = place[kind]
            with jax.named_scope(mla_scope[kind]):
                u, mix = tf_lib.stream_in(cfg, lp, "attn", x)
                h, qn, qr, new = tf_lib.mla_inputs(cfg, lp, u, call.pos)
                new = jnp.pad(new, ((0, 0), (0, 0),
                                    (0, latent - new.shape[-1])))
                with jax.named_scope("kv_write"):
                    kc = put(kc, kind,
                             (c, call.slots, call.positions % ring) if rings
                             else (c, call.blk, call.positions % block_size),
                             new[:, 0])
                with jax.named_scope("mla_attend"):
                    if rings:
                        o = _mla_ring_decode(
                            cfg, lp, qn, qr, kc[n], c, call.slots,
                            call.positions, ring_page(ring, block_size), h)
                    else:
                        o = _mla_decode(cfg, lp, qn, qr, kc[n], c,
                                        call.tables, call.positions, h)
            return kc, vc, tf_lib.mla_residual(cfg, lp, x, h, o, mix)
        return step

    def mamba_step_layer(call, lp, kc, vc, c, x, i):
        """One step of the selective scan on each row's own state where
        it lies in the pool (``ops/mamba_step.py``, the Pallas call
        ``hvd_mamba_step``: the state read once and written once, a slot
        that is not in the batch not touched), and the convolution's
        rows of the layer shifted where they lie (``hvd_mamba_rows``,
        the layer's slots in blocks). A state that is not whole tiles
        keeps, on a TPU, the XLA form: every slot's state where it lies,
        the batch's rows carried to their slots and the results back (as
        :func:`kda_step_layer` does), a slot that is not in the batch
        stepped by 0."""
        n = place["mamba"]
        n_slots, N, Di = kc[n].shape[1:]
        with jax.named_scope("attn_mamba"):
            with jax.named_scope("mamba_proj"):
                u, z = tf_lib.mamba_rows(cfg, lp, x)
            before = vc[n][c, call.slots].reshape(
                u.shape[0], cfg.mamba_d_conv - 1, -1)
            with jax.named_scope("mamba_conv"):
                uc = tf_lib.mamba_conv(cfg, lp, u, before)
            with jax.named_scope("mamba_proj"):
                step, b, cc = tf_lib.mamba_gates(cfg, lp, uc)
            a = -jnp.exp(lp["a_log"])
            uc = uc.astype(jnp.float32)
            if mamba_step_kernel.taken(N, Di):
                with jax.named_scope("mamba_step"):
                    y, state = mamba_step_kernel.mamba_step(
                        uc[:, 0], step[:, 0], a, b[:, 0], cc[:, 0], kc[n], c,
                        call.slots)
                    y = y[:, None] + lp["d_skip"] * uc
                with jax.named_scope("state_write"):
                    kc = swap(kc, "mamba", state)
                    vc = swap(vc, "mamba", mamba_step_kernel.shift_rows(
                        vc[n], c, call.slots, u[:, 0]))
            else:
                with jax.named_scope("mamba_step"):
                    us, steps, bs, cs = (by_slot(call, r[:, 0], n_slots)
                                         for r in (uc, step, b, cc))
                    y, state = mamba_step(us, steps, a, bs, cs, kc[n][c])
                    y = y[call.slots][:, None] + lp["d_skip"] * uc
                with jax.named_scope("state_write"):
                    kc = put(kc, "mamba", (c,), state)
                    vc = put(vc, "mamba", (c, call.slots),
                             jnp.concatenate([before, u], 1)[:, 1:].reshape(
                                 u.shape[0], -1))
        return kc, vc, tf_lib.mamba_residual(cfg, lp, x, y, z)

    def mamba2_step_layer(call, lp, kc, vc, c, x, i):
        """One step of the recurrence on each row's own state where it
        lies in the pool (``ops/state_step.py::ssd_step``, the Pallas
        call ``hvd_state_step``: 4 MB a slot read once and written
        once, a slot that is not in the batch not touched). A state
        that is not whole tiles keeps, on a TPU, the XLA form, as
        :func:`kda_step_layer` does: every slot stepped where it lies,
        one that is not in the batch by 0."""
        n = place["mamba2"]
        with jax.named_scope("attn_mamba2"):
            with jax.named_scope("mamba2_proj"):
                z, xbc, dt = tf_lib.mamba2_rows(cfg, lp, x)
            before = vc[n][c, call.slots].reshape(
                x.shape[0], cfg.mamba_d_conv - 1, -1)
            with jax.named_scope("conv_taps"):
                xs, b, cc, step = tf_lib.mamba2_inputs(cfg, lp, xbc, dt,
                                                       before)
            a = -jnp.exp(lp["a_log"])
            xr, dr, br, cr = (r[:, 0] for r in (xs, step, b, cc))
            if state_step_kernel.taken(*kc[n].shape[3:]):
                with jax.named_scope("mamba2_step"):
                    y, state = state_step_kernel.ssd_step(
                        xr, dr, a, br, cr, kc[n], c, call.slots)
                with jax.named_scope("state_write"):
                    kc = swap(kc, "mamba2", state)
            else:
                with jax.named_scope("mamba2_step"):
                    n_slots = kc[n].shape[1]
                    xr, dr, br, cr = (by_slot(call, r, n_slots)
                                      for r in (xr, dr, br, cr))
                    y, state = ssd_step(xr, dr, a, br, cr, kc[n][c])
                    y = y[call.slots]
                with jax.named_scope("state_write"):
                    kc = put(kc, "mamba2", (c,), state)
            with jax.named_scope("mamba2_step"):
                y = y[:, None] + lp["d_skip"][:, None] * xs
            with jax.named_scope("state_write"):
                vc = put(vc, "mamba2", (c, call.slots),
                         jnp.concatenate([before, xbc], 1)[:, 1:].reshape(
                             x.shape[0], -1))
            x = tf_lib.mamba2_residual(cfg, lp, x, y, z)
        return kc, vc, x

    def sparse_step(call, lp, kc, vc, c, x, i):
        """A row's new key into its page and, where it completes a
        kernel, that kernel's mean into the compressed keys; then ONE
        gather of at most ``step_pages`` pages a row and KV head: a row
        past ``sparse_dense_len`` scores the compressed keys behind its
        table, pools, chooses, and reads its chosen pages; a row below
        it reads its own first pages, all that hold a key of its. Never
        the table's width of pages."""
        n = place["sparse"]
        B = x.shape[0]
        t = call.positions
        heads = jnp.arange(Hkv)
        q, k, v = tf_lib.attention_inputs(cfg, lp, x, call.pos, i)
        with jax.named_scope("attn_sparse"):
            kp, ck = kc[n]
            with jax.named_scope("kv_write"):
                # a row of Dh values a KV head, as the pages lie
                at = (c, call.blk[:, None], heads, (t % block_size)[:, None])
                kp = kp.at[at].set(k.reshape(-1, Hkv, Dh).astype(kp.dtype))
                vc = put(vc, "sparse", at, v.reshape(-1, Hkv, Dh))
            with jax.named_scope("sparse_compress"):
                first = t - cfg.sparse_kernel + 1
                whole = (first >= 0) & (first % stride == 0)
                at_pos = jnp.maximum(first, 0)[:, None] + jnp.arange(
                    cfg.sparse_kernel, dtype=jnp.int32)        # [B, kernel]
                blks = jnp.take_along_axis(
                    call.tables,
                    jnp.minimum(at_pos // block_size, table_width - 1), 1)
                mean = kp[c, blks[..., None], heads,
                          (at_pos % block_size)[..., None]].astype(
                              jnp.float32).mean(1)
                j = jnp.maximum(first, 0) // stride
                slot = j // per
                blk = jnp.where(
                    whole & (slot < table_width),
                    jnp.take_along_axis(
                        call.tables,
                        jnp.minimum(slot, table_width - 1)[:, None], 1)[:, 0],
                    NULL_BLOCK)
                ck = ck.at[c, blk[:, None], heads, (j % per)[:, None]].set(
                    mean.astype(ck.dtype))
            kc = swap(kc, "sparse", (kp, ck))
            past = t >= cfg.sparse_dense_len                          # [B]
            with jax.named_scope("sparse_select"):
                scores = sparse_block_scores(
                    q, kernels_behind(ck, c, call.tables),
                    sparse_kernels_seen(call.pos), per, strides)
                blocks, ok = sparse_choose(scores, call.pos // block_size,
                                           cfg)
                blocks, ok = blocks[:, 0], ok[:, 0]              # [B, G, k]
                if chosen:
                    call.chose.append(sparse_chosen_pages(blocks, ok)
                                      & past[:, None, None])
                more = step_pages - blocks.shape[-1]
                own = jnp.arange(step_pages, dtype=jnp.int32)
                pages = jnp.where(
                    past[:, None, None],
                    jnp.pad(blocks, ((0, 0), (0, 0), (0, more))), own)
                read = jnp.where(
                    past[:, None, None],
                    jnp.pad(ok, ((0, 0), (0, 0), (0, more))),
                    own <= (t // block_size)[:, None, None])
            with jax.named_scope("sparse_attend"):
                with jax.named_scope("kv_gather"):
                    ids = jnp.take_along_axis(
                        call.tables, pages.reshape(B, -1), 1).reshape(
                            pages.shape)                     # [B, G, pages]
                    keys = kp[c, ids, heads[:, None]]  # [B, G, pages, bs, Dh]
                    vals = vc[n][c, ids, heads[:, None]]
                S = step_pages * block_size
                key_pos = (pages[..., None] * block_size + jnp.arange(
                    block_size, dtype=jnp.int32)).reshape(B, Hkv, S)
                seen = (jnp.repeat(read, block_size, -1)
                        & (key_pos <= t[:, None, None]))[:, :, None]
                sc = jnp.einsum(
                    "bgrd,bgkd->bgrk", q.reshape(B, Hkv, H // Hkv, Dh),
                    keys.reshape(B, Hkv, S, Dh),
                    preferred_element_type=jnp.float32) * Dh ** -0.5
                p = jax.nn.softmax(jnp.where(seen, sc, _NEG_BIG), axis=-1)
                o = jnp.einsum("bgrk,bgkd->bgrd", p.astype(vals.dtype),
                               vals.reshape(B, Hkv, S, Dh),
                               preferred_element_type=jnp.float32
                               ).astype(q.dtype).reshape(B, 1, H * Dh)
        return kc, vc, tf_lib.attention_residual(cfg, lp, x, o)

    def lightning_step_layer(call, lp, kc, vc, c, x, i):
        """One step of the recurrence on every slot's state where it
        lies, the batch's rows carried to their slots and the results
        back (as :func:`kda_step_layer`): a slot that is not in the
        batch decays by 1 and is written by 0."""
        n = place["lightning"]
        with jax.named_scope("attn_lightning"):
            h, q, k, v = tf_lib.lightning_inputs(cfg, lp, x, call.pos, i)
            with jax.named_scope("lightning_step"):
                n_slots = kc[n].shape[1]
                g = jnp.broadcast_to(tf_lib.lightning_decay(cfg),
                                     q.shape[:1] + q.shape[2:3])
                o, state = lightning_step(
                    *(by_slot(call, a[:, 0], n_slots) for a in (q, k, v)),
                    by_slot(call, g, n_slots), kc[n][c])
                o = o[call.slots][:, None]
            with jax.named_scope("state_write"):
                kc = put(kc, "lightning", (c,), state)
        return kc, vc, tf_lib.lightning_residual(cfg, lp, x, h, o)

    def conv_step_layer(call, lp, kc, vc, c, x, i):
        """One position of the gated convolution a row of the batch
        over its own slot's rows, which are shifted where they lie: the
        oldest out, the row's ``z`` in (a padded row shifts the null
        slot's)."""
        n = place["conv"]
        with jax.named_scope("attn_conv"):
            with jax.named_scope("conv_proj"):
                b, cc, u = tf_lib.conv_inputs(cfg, lp, x)
            before = kc[n][c, call.slots].reshape(
                u.shape[0], cfg.conv_taps - 1, -1)
            with jax.named_scope("conv_taps"):
                z, g = tf_lib.conv_gated(cfg, lp, b, cc, u, before)
            with jax.named_scope("state_write"):
                kc = put(kc, "conv", (c, call.slots),
                         jnp.concatenate([before, z], 1)[:, 1:].reshape(
                             u.shape[0], -1))
            with jax.named_scope("conv_proj"):
                x = tf_lib.conv_residual(cfg, lp, x, g)
        return kc, vc, x

    def eva_step(call, lp, kc, vc, c, x, i):
        """A row's new key and value into its slot's rows at ``t %
        eva_window``; where ``t`` ends a chunk, that chunk's summary,
        from its ``eva_chunk`` rows alone, into its page; then each row
        over its own window's rows TO ITS OWN COUNT and over the
        summaries of its own closed windows where they lie, both
        through ``ops/paged_decode.py``'s kernel (the rows of a slot
        read as pages of ``_EVA_ROW_PAGE`` positions, the same bytes)
        and merged by their logsumexp: one softmax over both. No closed
        window's rows are read again and no table is gathered."""
        n = place["eva"]
        W, ch = cfg.eva_window, cfg.eva_chunk
        t = call.positions
        q, k, v = tf_lib.attention_inputs(cfg, lp, x, call.pos, i)
        with jax.named_scope("attn_eva"):
            (kr, ks), (vr, vs) = kc[n], vc[n]
            r = t % W
            with jax.named_scope("kv_write"):
                kr = kr.at[c, call.slots, r].set(k[:, 0].astype(kr.dtype))
                vr = vr.at[c, call.slots, r].set(v[:, 0].astype(vr.dtype))
            with jax.named_scope("eva_summarise"):
                at = (jnp.maximum(r - (ch - 1), 0)[:, None]
                      + jnp.arange(ch, dtype=jnp.int32))            # [B, ch]
                sk, sv = eva_summaries(kr[c, call.slots[:, None], at],
                                       vr[c, call.slots[:, None], at],
                                       lp["eva_mu"], lp["eva_phi"])
                blk = jnp.where(t % ch == ch - 1, call.blk, NULL_BLOCK)
                ks = ks.at[c, blk, t % block_size // ch].set(sk)
                vs = vs.at[c, blk, t % block_size // ch].set(sv)
            with jax.named_scope("eva_window"):
                page = math.gcd(W, _EVA_ROW_PAGE)
                o, lse = paged_decode_stats(
                    q[:, 0], *(rows.reshape(rows.shape[0], -1, page, Hkv, Dh)
                               for rows in (kr, vr)), c,
                    call.slots[:, None] * (W // page)
                    + jnp.arange(W // page, dtype=jnp.int32), r + 1,
                    key_positions=_eva_key_block(page))
            with jax.named_scope("eva_summaries"):
                seen = (t // W) * (W // ch)
                o_s, lse_s = paged_decode_stats(
                    q[:, 0], ks, vs, c, call.tables, seen,
                    key_positions=_eva_key_block(per_eva))
                # a row with no closed window read one summary all the
                # same (the kernel's least): it weighs nothing
                some = (seen > 0)[:, None]
                lse_s = jnp.where(some, lse_s, _NEG_BIG)
                top = jnp.maximum(lse, lse_s)
                a, b = jnp.exp(lse - top), jnp.exp(lse_s - top)
                o = (o * a[..., None] + jnp.where(some[..., None], o_s, 0.0)
                     * b[..., None]) / (a + b)[..., None]
            o = o.astype(q.dtype).reshape(o.shape[0], 1, H * Dh)
            kc = swap(kc, "eva", (kr, ks))
            vc = swap(vc, "eva", (vr, vs))
        return kc, vc, tf_lib.attention_residual(cfg, lp, x, o)

    #: kind of layer -> how a chunk and how a decode step run it
    kinds = {
        "sliding": {"chunk": window_chunk, "step": window_step},
        "full": {"chunk": full_chunk, "step": full_step},
        "kda": {"chunk": kda_chunk, "step": kda_step_layer},
        "mla": {"chunk": latent_chunk("mla"), "step": latent_step("mla")},
        "mamba": {"chunk": mamba_chunk, "step": mamba_step_layer},
        "sparse": {"chunk": sparse_chunk, "step": sparse_step},
        "lightning": {"chunk": lightning_chunk,
                      "step": lightning_step_layer},
        "conv": {"chunk": conv_chunk, "step": conv_step_layer},
        "eva": {"chunk": eva_chunk, "step": eva_step},
        "mamba2": {"chunk": mamba2_chunk, "step": mamba2_step_layer},
        "mla_sliding": {"chunk": latent_chunk("mla_sliding"),
                        "step": latent_step("mla_sliding")},
    }

    def chunk_program(params, kc, vc, tokens, offset, length, address,
                      local: bool):
        """A chunk of one sequence at ``offset`` (B = 1): whole blocks
        into the pages, rows into the slot's ring, the slot's state
        carried over it. ``local``: the chunk is the whole prompt and
        attends over itself."""
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
        call = types.SimpleNamespace(
            step="chunk", local=local, pos=pos, offset=offset, length=length,
            table=table, slot=slot, blks=blks, held=held, chose=[])
        kc, vc, x = layers(params, kc, vc, x, call)
        out = emit(params, x, lambda x: jnp.take(x[0], length - 1, axis=0))
        return (kc, vc, out, jnp.stack(call.chose)) if chosen else (
            kc, vc, out)

    def prefill(params, kc, vc, tokens, length, address):
        """A whole prompt, tokens [Tp] bucket-padded. Returns (kc, vc,
        first token)."""
        assert tokens.shape[0] // block_size <= table_width
        return chunk_program(params, kc, vc, tokens, jnp.int32(0), length,
                             address, local=True)

    def prefill_resume(params, kc, vc, tokens, offset, length, address):
        """One chunk at the block-aligned ``offset``, over what earlier
        chunks left in the caches and its own keys."""
        return chunk_program(params, kc, vc, tokens, offset, length,
                             address, local=False)

    def decode(params, kc, vc, tokens, positions, address):
        """One step of the batch: tokens [B], positions [B], address
        ``(block_tables [B, table_width], slots [B])``. A padded row
        carries token 0, position 0, an all-null table and the null
        slot."""
        tables, slots = address
        x = embed(params, tokens[:, None])
        pos = positions[:, None]
        blk_i = positions // block_size
        blk = jnp.take_along_axis(
            tables, jnp.minimum(blk_i, table_width - 1)[:, None], axis=1)[:, 0]
        blk = jnp.where(blk_i < table_width, blk, NULL_BLOCK)
        call = types.SimpleNamespace(
            step="step", pos=pos, positions=positions, tables=tables,
            slots=slots, blk=blk, chose=[])
        kc, vc, x = layers(params, kc, vc, x, call)
        out = emit(params, x, lambda x: x[:, 0])
        return (kc, vc, out, jnp.stack(call.chose)) if chosen else (
            kc, vc, out)

    def held_experts_counts(params, tokens):
        """The claims on each held expert of every MoE layer [n_moe,
        held], and the claims of each layer that the dispatch's sort
        and group sizes would not run [n_moe], when ``tokens`` [B, T]
        run as B prompts over themselves (nothing kept): the routing
        the serve programs' dispatch acts on."""
        counts, not_run = [], []

        def counting(h, lp):
            c, lost = moe_lib.routing_counts(
                h, lp["router"], cfg.moe, lp.get("router_bias"))
            counts.append(c)
            not_run.append(lost)
            return moe_lib.make_moe_ffn(cfg.moe, None)(h, lp)

        B, T = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        call = types.SimpleNamespace(
            step="chunk", local=True, pos=pos, length=jnp.int32(T),
            slot=None, blks=None)
        layers(params, None, None, embed(params, tokens), call, counting)
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
                "layer's ring (of keys, or an mla_sliding layer's of "
                "latents) and a kda or mamba layer's recurrent state "
                "(a lightning or mamba2 layer's too, a conv layer's rows "
                "and an eva layer's open window) are not "
                "pages another engine or "
                "a draft could be handed, nor are a sparse layer's "
                "compressed keys, "
                "and decode.py's inject and verify know K and V pages alone "
                "(ROADMAP B9, B14)")
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
    run through their expert: ``moe.held_pairs_not_run``). The held
    pairs a layer that ran and did not, and the held experts touched,
    are also recorded (``moe.record_moe_stats``) and shown by the
    engine's ``metrics.snapshot()``."""
    count = mixed_programs(cfg, block_size, 1, 0)[3]
    counts, not_run = jax.jit(count)(params, jnp.asarray(tokens, jnp.int32))
    pairs = tokens.shape[0] * tokens.shape[1] * cfg.moe.top_k
    summary = moe_lib.routing_summary(counts, not_run)
    # the last report's, a layer: what ``metrics.snapshot()`` shows
    moe_lib.record_moe_stats({
        "moe_held_pairs_run": float(counts.sum(-1).mean()
                                    - not_run.mean()),
        "moe_held_pairs_not_run": float(not_run.mean()),
        "moe_held_experts_touched_mean": float((counts > 0).sum(-1).mean())})
    return {
        "moe_local_pair_share": float(counts.sum(-1).mean()) / pairs,
        "moe_held_experts_touched_mean": float((counts > 0).sum(-1).mean()),
        "moe_expert_load_max_over_mean":
            summary["moe_expert_load_max_over_mean"],
        "moe_dispatch_dropped_token_frac":
            summary["moe_dispatch_dropped_token_frac"],
    }
