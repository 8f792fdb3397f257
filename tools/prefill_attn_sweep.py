"""Prompt attention at the serving cells' prefill buckets, on the chip:

    chiprun -- python tools/prefill_attn_sweep.py

For each bucket ``T``, ms a layer (16 calls chained in one program, the
median of ``--reps`` runs) of ``[1, T, 32, 128]`` bf16 queries over
``[1, T, 8, 128]`` keys and values (``mistral-7b-v0.3-16l``'s heads):
the dense form (K and V repeated across the group, float32
``[32, T, T]`` scores: all ``serve/decode.py::_attend_prompt`` had
before ISSUE 35), ``_attend_prompt`` as it is, and the flash forward at
each candidate tile; and the largest difference of ``_attend_prompt``
from the dense form over float32 inputs at ``HIGHEST``, relative to its
largest magnitude. How ``_attend_prompt`` came by ``_DENSE_PROMPT`` and
``_prompt_block``.

``--latent`` sweeps the mla layers' chunk instead (ISSUE 44): ``[1, C,
H, 128 + 64]`` bf16 queries of a chunk that ends at key ``keys`` over
latents ``[keys, 512 + 64]`` gathered and expanded a key block at a time
(``serve/decode.py::_mla_attend``), at Kimi's 64 and
Ling's 32 heads: ms a layer of the einsum form (float32 ``[H, C, block]``
scores in HBM: all ``_mla_attend`` had before ISSUE 44), of
``_mla_attend`` as it is (the Pallas forward over keys that carry their
positions) at each ``--tiles`` pair and ``--key-block``, and of the
kernel alone over one expanded key block; and the largest difference of
the two forms relative to the largest magnitude.

``--latent-decode`` sweeps the mla layers' decode step (ISSUE 45): one
query a row at ``--heads`` heads and ``--rows`` rows over a latent pool
``[1, rows * 1088 + 1, 16, 640]`` behind tables of 1088 pages in
shuffled order, the rows' lengths ``even`` (8192 each), ``spread``
(4096 to 16 384: the Kimi cell's snapshots) or ``tail`` (one of 16 384
among rows of 256 to 3072: the Ling cell's reasoning requests). ms a
layer of the XLA form (``tests/reference_mla.py`` over
``mla_pages`` to the longest row: all a decode step had before ISSUE
45), of ``_mla_decode`` as it is (the two absorbed products around
``ops/paged_decode.py::latent_decode``) at each ``--key-blocks``, and
of the kernel alone with the GB/s of the pages it reads; and the
largest difference of the two forms relative to the largest magnitude.

``--paged-decode`` sweeps a full layer's decode step (ISSUE 55) at the
three page shapes the cells have: ``lfm2`` (128 rows of 256-2560
positions behind tables of 160 pages of rows of 512, 32 / 8 heads of
64), ``trinity`` (32 rows of 1024-8576 behind 536 pages ``[16, 8,
128]``, 48 / 8 heads) and ``jamba`` (256 rows of 64-1536 behind 96
pages ``[16, 1, 128]``, 20 heads over one), the lengths log-uniform,
the tables in shuffled order. ms a layer of the XLA form
(``serve/decode.py::_attend_keys`` over every row's whole table,
gathered: all a decode step had before ISSUE 55) and of
``ops/paged_decode.py::paged_decode`` (the same kernel body: ISSUE 58)
at each ``--key-blocks``, with the GB/s
of the K and V pages it reads; and the largest difference of the two
relative to the largest magnitude. ``--cells ring`` is a window layer's
step (ISSUE 59) at the trinity cell's shapes: 32 rows of 48 / 8 heads at
positions log-uniform 1024-8576 in 33 slots' rings of 5136 places, a
window of 4096. ms a layer of the XLA form (``_attend_keys`` over every
slot's whole ring under ``ring_positions``, the queries carried to
their slots: all ``window_step`` had, without the slices of the stacked
cache in front of it) and of ``ops/paged_decode.py::ring_decode`` at
each ``--ring-pages`` (positions a ring's places are read as one page:
divisors of the ring).

``--sparse`` sweeps a sparse layer's chunk (ISSUE 51) at
``minicpm-sala-8l``'s shapes: ``[1, C, 32, 128]`` bf16 queries of a
chunk that ends at key ``keys`` over pages ``[2, 64, 128]`` behind a
table of 520 in shuffled order, each query of a KV head allowed 64
pages at or before its own (every page below 8192). Two selections:
``independent`` (every query draws its own: what seeded weights give)
and ``agreeing`` (a chunk's queries share one draw). ms a layer of
``serve/decode.py::sparse_attend_chunk`` (float32 ``[2, 16, C, 1024]``
scores in HBM a key block: all a chunk had before ISSUE 51), of
``sparse_attend_pages`` as it is, and of the kernel at each ``--tiles``
pair in both query layouts (``heads``: a head a grid row; ``group``: a
KV head a grid row, its heads' queries interleaved), without the index
maps' clamp and without the skip by choice; beside each, the share of
the causally visible tile pairs that it computes, and the largest
difference from ``sparse_attend_chunk`` relative to the largest
magnitude.

``--select`` sweeps the selection before it (ISSUE 53), same shapes:
the chunk's queries over the compressed keys ``[2, 4, 128]`` a page
behind the same table. ms a layer of
``serve/decode.py::sparse_block_scores`` alone (float32 ``[2, 16, C,
2080]`` scores, their softmax and its pooling in HBM: all a chunk had
before ISSUE 53), of ``sparse_choose``, of the scatter of what it chose
and of the two together, of ``ops/sparse_scores.py``'s kernel alone at
each ``--tiles`` pair (queries x pages a grid step) and with the choice
behind it as the programs run it; and the largest difference of the
kernel's scores from the XLA form's relative to the largest score.
"""

import argparse
import json
import os
import statistics
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from horovod_tpu.models import TransformerConfig  # noqa: E402
from horovod_tpu.models import transformer as tf_lib  # noqa: E402
from horovod_tpu.ops import flash_attention as flash_lib  # noqa: E402
from horovod_tpu.ops import paged_decode as paged_lib  # noqa: E402
from horovod_tpu.ops import sparse_scores as scores_lib  # noqa: E402
from horovod_tpu.ops.flash_attention import flash_attention  # noqa: E402
from horovod_tpu.parallel.ring_attention import local_attention  # noqa: E402
from horovod_tpu.serve import decode as decode_lib  # noqa: E402
from horovod_tpu.serve.decode import _attend_prompt  # noqa: E402
from reference_mla import mla_attend_absorbed  # noqa: E402

LAYERS = 16
LATENT_LAYERS = 6      # the Kimi cell's depth
PAGED_LAYERS = 8       # calls chained in one program


def dense(q, k, v):
    rep = q.shape[2] // k.shape[2]
    return local_attention(q, jnp.repeat(k, rep, axis=2),
                           jnp.repeat(v, rep, axis=2),
                           causal=True).reshape(*q.shape[:2], -1)


def median_ms(fn, xs, reps):
    """ms a call of the jitted ``fn(*xs)``: the median of ``reps`` runs
    after the one that compiles."""
    jax.block_until_ready(fn(*xs))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*xs))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times)


def ms_a_layer(attend, q, k, v, reps):
    """``attend`` [1, T, H, Dh] -> [1, T, H * Dh], a layer's output the
    next one's queries, so that the calls run one after another."""
    @jax.jit
    def chain(q, k, v):
        return lax.scan(lambda q, _: (attend(q, k, v).reshape(q.shape),
                                      None), q, None, length=LAYERS)[0]
    return round(median_ms(chain, (q, k, v), reps) / LAYERS, 4)


def mla_einsum(cfg, lp, qn, qr, keys_of, n_blocks, pos):
    """The expanded form as ``_mla_attend`` had it before ISSUE 44: a
    key block's scores from two einsums, masked, maxed, exponentiated
    and summed as float32 ``[B, H, C, K]`` tensors in HBM."""
    B, C, H, Dh = qn.shape
    rank = cfg.mla_kv_rank
    w_uk, w_uv = tf_lib.mla_up(cfg, lp)
    scale = tf_lib.mla_scale(cfg)

    def block(j, carry):
        m, l, acc = carry
        latent, key_pos = keys_of(j)
        c = latent[..., :rank]
        r = latent[..., rank:rank + cfg.mla_rope_dim]
        vals = jnp.einsum("bkc,chd->bkhd", c, w_uv)
        s = jnp.einsum("bqhd,bkhd->bhqk", qn,
                       jnp.einsum("bkc,chd->bkhd", c, w_uk),
                       preferred_element_type=jnp.float32)
        s = (s + jnp.einsum("bqhr,bkr->bhqk", qr, r,
                            preferred_element_type=jnp.float32)) * scale
        seen = key_pos[None, None, :] <= pos[:, :, None]
        s = jnp.where(seen[:, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        acc = acc * fade[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vals.dtype), vals,
            preferred_element_type=jnp.float32)
        return m_new, l * fade + p.sum(-1), acc

    m, l, acc = lax.fori_loop(
        0, n_blocks, block,
        (jnp.full((B, H, C), -1e30, jnp.float32),
         jnp.zeros((B, H, C), jnp.float32),
         jnp.zeros((B, H, C, Dh), jnp.float32)))
    return jnp.moveaxis(acc / l[..., None], 1, 2).astype(qn.dtype)


def latent_sweep(args) -> None:
    LATENT, RANK, ROPE, DH = 640, 512, 64, 128
    key_blocks = args.key_blocks or [decode_lib._MLA_CHUNK_BLOCKS
                                     * decode_lib._MLA_KEY_BLOCK]

    for heads in args.heads:
        cfg = TransformerConfig(
            vocab_size=128, d_model=128, n_layers=1, n_heads=heads,
            n_kv_heads=heads, d_head=DH, d_ff=128, layer_types=("mla",),
            mla_kv_rank=RANK, mla_rope_dim=ROPE, dtype=jnp.bfloat16)
        ks = jax.random.split(jax.random.PRNGKey(heads), 4)
        lp = {"w_ukv": (jax.random.normal(ks[0], (RANK, heads * 2 * DH))
                        * RANK ** -0.5).astype(jnp.bfloat16)}
        pool = jax.random.normal(
            ks[1], (max(args.keys) + max(1024, *key_blocks), LATENT)
        ).astype(jnp.bfloat16)
        for c in args.chunks:
            qn = jax.random.normal(ks[2], (1, c, heads, DH)
                                   ).astype(jnp.bfloat16)
            qr = jax.random.normal(ks[3], (1, c, heads, ROPE)
                                   ).astype(jnp.bfloat16)
            for keys in args.keys:
                if keys < c:
                    continue
                pos = (keys - c + jnp.arange(c, dtype=jnp.int32))[None]
                row = {"heads": heads, "C": c, "keys": keys}

                def chained(form, key_block):
                    """(jitted chain of LATENT_LAYERS calls of ``form``, its
                    arguments): the arrays are arguments, not constants
                    of the program."""
                    @jax.jit
                    def chain(qn, qr, lp, pool, n_blocks):
                        def keys_of(j):
                            return (lax.dynamic_slice_in_dim(
                                pool, j * key_block, key_block)[None],
                                j * key_block + jnp.arange(
                                    key_block, dtype=jnp.int32))
                        return lax.scan(
                            lambda q, _: (form(cfg, lp, q, qr, keys_of,
                                               n_blocks, pos), None),
                            qn, None, length=LATENT_LAYERS)[0]
                    return chain, (qn, qr, lp, pool,
                                   jnp.int32(-(-keys // key_block)))

                chain, xs = chained(mla_einsum, 1024)
                row["einsum"] = round(
                    median_ms(chain, xs, args.reps) / LATENT_LAYERS, 4)
                want = chain(*xs).astype(jnp.float32)
                kernel = flash_lib.flash_attention_keys

                def merged_outside(*a, carry=None, **kw):
                    """A key block's call merged with the blocks before
                    it by XLA, outside the kernel (``--merge-outside``)."""
                    o, lse = kernel(*a, **kw)
                    if carry is None:
                        return o, lse
                    both = jnp.logaddexp(carry[1], lse)
                    return (carry[0] * jnp.exp(carry[1] - both)[..., None]
                            + o * jnp.exp(lse - both)[..., None]), both

                if args.merge_outside:
                    decode_lib.flash_attention_keys = merged_outside
                default_tiles = flash_lib._keys_blocks
                for kb in key_blocks:
                    for bq, bk in [(None, None)] + list(map(tuple,
                                                            args.tiles)):
                        flash_lib._keys_blocks = (
                            default_tiles if bq is None
                            else lambda c_, k_: (bq, bk))
                        chain, xs = chained(decode_lib._mla_attend, kb)
                        name = f"kernel_kb{kb}" + (
                            "" if bq is None else f"_{bq}x{bk}")
                        try:
                            row[name] = round(median_ms(
                                chain, xs, args.reps) / LATENT_LAYERS, 4)
                        except Exception as e:  # a tile Mosaic refuses
                            row[name] = f"refused: {str(e)[:80]}"
                            continue
                        if bq is None and kb == key_blocks[0]:
                            got = chain(*xs).astype(jnp.float32)
                            row["rel_err"] = float(
                                jnp.max(jnp.abs(got - want))
                                / jnp.max(jnp.abs(want)))
                flash_lib._keys_blocks = default_tiles
                decode_lib.flash_attention_keys = kernel
                print(json.dumps(row), flush=True)
            # the kernel alone over one expanded key block, all of it
            # seen, 16 calls chained through the carried pair
            k = jax.random.normal(ks[2], (heads, 1024, DH + ROPE)
                                  ).astype(jnp.bfloat16)
            q = jnp.concatenate([qn, qr], -1)[0].swapaxes(0, 1)

            @jax.jit
            def alone(q, k):
                return lax.fori_loop(
                    0, 16, lambda _, seen: flash_lib.flash_attention_keys(
                        q, k, k[..., :DH],
                        4096 + jnp.arange(c, dtype=jnp.int32)[None],
                        jnp.arange(1024, dtype=jnp.int32)[None], scale=0.07,
                        carry=seen),
                    (jnp.zeros((heads, c, DH), jnp.float32),
                     jnp.full((heads, c), flash_lib.NEG_INF, jnp.float32)))
            print(json.dumps({
                "heads": heads, "C": c, "kernel_alone_1024_keys": round(
                    median_ms(alone, (q, k), args.reps) / 16, 4)}),
                flush=True)


def latent_decode_sweep(args) -> None:
    LATENT, RANK, ROPE, DH, PAGE, WIDTH = 640, 512, 64, 128, 16, 1088
    rng = np.random.default_rng(0)
    default_wave = paged_lib._wave_pages
    for heads in args.heads:
        cfg = TransformerConfig(
            vocab_size=128, d_model=128, n_layers=1, n_heads=heads,
            n_kv_heads=heads, d_head=DH, d_ff=128, layer_types=("mla",),
            mla_kv_rank=RANK, mla_rope_dim=ROPE, dtype=jnp.bfloat16)
        ks = jax.random.split(jax.random.PRNGKey(heads), 4)
        lp = {"w_ukv": (jax.random.normal(ks[0], (RANK, heads * 2 * DH))
                        * RANK ** -0.5).astype(jnp.bfloat16)}
        for rows in args.rows:
            n_pages = rows * WIDTH + 1
            pool = jax.jit(lambda k: jnp.pad(jax.random.normal(
                k, (1, n_pages, PAGE, RANK + ROPE), jnp.bfloat16),
                ((0, 0),) * 3 + ((0, LATENT - RANK - ROPE),)))(ks[1])
            tables = jnp.asarray(1 + rng.permutation(rows * WIDTH).reshape(
                rows, WIDTH), jnp.int32)
            qn = jax.random.normal(ks[2], (rows, 1, heads, DH), jnp.bfloat16)
            qr = jax.random.normal(ks[3], (rows, 1, heads, ROPE),
                                   jnp.bfloat16)
            for lengths in args.lengths:
                n = {"even": np.full(rows, 8192),
                     "spread": rng.integers(4096, 16385, rows),
                     "tail": np.concatenate([[16384], rng.integers(
                         256, 3073, rows - 1)])}[lengths]
                positions = jnp.asarray(n - 1, jnp.int32)
                pages_read = int(np.sum(-(-n // PAGE)))
                row = {"heads": heads, "rows": rows, "lengths": lengths,
                       "positions": int(n.sum()), "longest": int(n.max())}

                def chained(form):
                    @jax.jit
                    def chain(qn, qr, lp, pool, tables, positions):
                        return lax.scan(
                            lambda q, _: (form(q, qr, lp, pool, tables,
                                               positions), None),
                            qn, None, length=LATENT_LAYERS)[0]
                    return chain, (qn, qr, lp, pool, tables, positions)

                def xla(qn, qr, lp, pool, tables, positions):
                    keys_of, blocks_to = decode_lib.mla_pages(
                        pool, 0, tables, decode_lib._MLA_KEY_BLOCK)
                    return mla_attend_absorbed(
                        cfg, lp, qn, qr, keys_of, blocks_to(positions.max()),
                        positions[:, None])

                def kernel(qn, qr, lp, pool, tables, positions):
                    return decode_lib._mla_decode(cfg, lp, qn, qr, pool, 0,
                                                  tables, positions)

                chain, xs = chained(xla)
                row["xla"] = round(median_ms(chain, xs, args.reps)
                                   / LATENT_LAYERS, 4)
                want = chain(*xs).astype(jnp.float32)
                for kb in args.key_blocks or [None]:
                    name = "" if kb is None else f"_kb{kb}"
                    paged_lib._wave_pages = (
                        default_wave if kb is None
                        else lambda page, kb=kb: kb // page)
                    chain, xs = chained(kernel)
                    try:
                        row["decode" + name] = round(median_ms(
                            chain, xs, args.reps) / LATENT_LAYERS, 4)
                    except Exception as e:   # a buffer Mosaic refuses
                        row["decode" + name] = f"refused: {str(e)[:80]}"
                        continue
                    got = chain(*xs).astype(jnp.float32)
                    row["rel_err" + name] = round(float(
                        jnp.max(jnp.abs(got - want))
                        / jnp.max(jnp.abs(want))), 5)
                    q = jnp.zeros((rows, heads, LATENT), jnp.bfloat16)

                    @jax.jit
                    def alone(q, pool, tables, positions):
                        return lax.scan(
                            lambda q, _: (jnp.pad(paged_lib.latent_decode(
                                q, pool, 0, tables, positions + 1, rank=RANK,
                                scale=0.07), ((0, 0), (0, 0),
                                              (0, LATENT - RANK))), None),
                            q, None, length=LATENT_LAYERS)[0]
                    ms = median_ms(alone, (q, pool, tables, positions),
                                   args.reps) / LATENT_LAYERS
                    row["alone" + name] = round(ms, 4)
                    row["alone_gb_s" + name] = round(
                        pages_read * PAGE * LATENT * 2 / ms / 1e6, 1)
                paged_lib._wave_pages = default_wave
                print(json.dumps(row), flush=True)


#: cell -> rows, H, Hkv, Dh, heads end to end in a row, table width,
#: shortest and longest row
PAGED_SHAPES = {"lfm2": (128, 32, 8, 64, True, 160, 256, 2560),
                "trinity": (32, 48, 8, 128, False, 536, 1024, 8576),
                "jamba": (256, 20, 1, 128, False, 96, 64, 1536)}


#: a window layer's step at the trinity cell's shapes: rows, H, Hkv, Dh,
#: slots, ring, window, first and last position
RING_SHAPE = (32, 48, 8, 128, 33, 5136, 4096, 1024, 8576)


def ring_decode_sweep(args) -> None:
    rows, H, Hkv, Dh, n_slots, ring, window, lo, hi = RING_SHAPE
    rng = np.random.default_rng(0)
    ks = jax.random.split(jax.random.PRNGKey(rows), 3)
    kr, vr = (jax.jit(lambda k: jax.random.normal(
        k, (1, n_slots, ring, Hkv, Dh), jnp.bfloat16))(k) for k in ks[:2])
    q = jax.random.normal(ks[2], (rows, H, Dh), jnp.bfloat16)
    slots = jnp.asarray(1 + rng.permutation(n_slots - 1)[:rows], jnp.int32)
    n = np.exp(rng.uniform(np.log(lo), np.log(hi), rows)).astype(int)
    positions = jnp.asarray(n - 1, jnp.int32)
    at, seen = paged_lib.ring_reach(n - 1, window, ring)
    row = {"cell": "ring", "rows": rows, "visible": int(seen.sum()),
           "past_the_window": int((n > window).sum()),
           "past_the_ring": int((n > ring).sum())}

    def chained(form):
        @jax.jit
        def chain(q, kr, vr, slots, positions):
            return lax.scan(
                lambda q, _: (form(q, kr, vr, slots, positions), None),
                q, None, length=PAGED_LAYERS)[0]
        return chain, (q, kr, vr, slots, positions)

    def xla(q, kr, vr, slots, positions):
        def by_slot(a):
            return jnp.zeros((n_slots,) + a.shape[1:],
                             a.dtype).at[slots].set(a)
        frontier = by_slot(positions[:, None] + 1)
        return decode_lib._attend_keys(
            by_slot(q[:, None]), kr[0], vr[0],
            decode_lib.ring_positions(frontier[:, 0], ring), frontier - 1,
            window)[slots].reshape(q.shape)

    chain, xs = chained(xla)
    row["xla"] = round(median_ms(chain, xs, args.reps) / PAGED_LAYERS, 4)
    want = jax.jit(xla)(*xs).astype(jnp.float32)
    for page in args.ring_pages:
        def kernel(q, kr, vr, slots, positions, page=page):
            return paged_lib.ring_decode(q, kr, vr, 0, slots, positions,
                                         window=window, page=page)
        name = f"_page{page}"
        pages_read = int(np.sum(-(-(at % page + seen) // page)))
        chain, xs = chained(kernel)
        try:
            ms = median_ms(chain, xs, args.reps) / PAGED_LAYERS
        except Exception as e:       # a buffer Mosaic refuses
            row["kernel" + name] = f"refused: {str(e)[:80]}"
            continue
        row["kernel" + name] = round(ms, 4)
        row["pages_read" + name] = pages_read
        row["gb_s" + name] = round(
            pages_read * page * Hkv * Dh * 2 * 2 / ms / 1e6, 1)
        got = jax.jit(kernel)(*xs).astype(jnp.float32)
        row["rel_err" + name] = round(float(
            jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))), 5)
    print(json.dumps(row), flush=True)


def paged_decode_sweep(args) -> None:
    PAGE = 16
    rng = np.random.default_rng(0)
    default_wave = paged_lib._wave_pages
    if "ring" in args.cells:
        ring_decode_sweep(args)
    for cell in (c for c in args.cells if c != "ring"):
        rows, H, Hkv, Dh, end_to_end, width, lo, hi = PAGED_SHAPES[cell]
        tail = (Hkv * Dh,) if end_to_end else (Hkv, Dh)
        ks = jax.random.split(jax.random.PRNGKey(rows), 3)
        kp, vp = (jax.jit(lambda k: jax.random.normal(
            k, (1, rows * width + 1, PAGE) + tail, jnp.bfloat16))(k)
            for k in ks[:2])
        q = jax.random.normal(ks[2], (rows, H, Dh), jnp.bfloat16)
        tables = jnp.asarray(1 + rng.permutation(rows * width).reshape(
            rows, width), jnp.int32)
        n = np.exp(rng.uniform(np.log(lo), np.log(hi), rows)).astype(int)
        positions = jnp.asarray(n - 1, jnp.int32)
        pages_read = int(np.sum(-(-n // PAGE)))
        row = {"cell": cell, "rows": rows, "positions": int(n.sum()),
               "longest": int(n.max()), "pages_read": pages_read,
               "pages_table": rows * width}

        def chained(form):
            @jax.jit
            def chain(q, kp, vp, tables, positions):
                return lax.scan(
                    lambda q, _: (form(q, kp, vp, tables, positions), None),
                    q, None, length=PAGED_LAYERS)[0]
            return chain, (q, kp, vp, tables, positions)

        def xla(q, kp, vp, tables, positions):
            S = width * PAGE
            # the tables made to follow from the queries (by nothing), or
            # the compiler gathers once for all the chained calls
            tables = tables + (q[0, 0, 0] * 0).astype(jnp.int32)
            keys, vals = (pool[0, tables].reshape(rows, S, Hkv, Dh)
                          for pool in (kp, vp))
            return decode_lib._attend_keys(
                q[:, None], keys, vals, jnp.arange(S, dtype=jnp.int32)[None],
                positions[:, None], None).reshape(q.shape)

        def kernel(q, kp, vp, tables, positions):
            return paged_lib.paged_decode(q, kp, vp, 0, tables,
                                          positions + 1)

        chain, xs = chained(xla)
        row["xla"] = round(median_ms(chain, xs, args.reps) / PAGED_LAYERS, 4)
        want = jax.jit(xla)(*xs).astype(jnp.float32)
        for kb in args.key_blocks or [None]:
            name = "" if kb is None else f"_kb{kb}"
            paged_lib._wave_pages = (
                default_wave if kb is None
                else lambda page, kb=kb: kb // page)
            chain, xs = chained(kernel)
            try:
                ms = median_ms(chain, xs, args.reps) / PAGED_LAYERS
            except Exception as e:       # a buffer Mosaic refuses
                row["kernel" + name] = f"refused: {str(e)[:80]}"
                continue
            row["kernel" + name] = round(ms, 4)
            row["gb_s" + name] = round(
                pages_read * PAGE * Hkv * Dh * 2 * 2 / ms / 1e6, 1)
            got = jax.jit(kernel)(*xs).astype(jnp.float32)
            row["rel_err" + name] = round(float(
                jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))), 5)
        paged_lib._wave_pages = default_wave
        print(json.dumps(row), flush=True)


def sparse_form(layout: str, bq: int, bk: int):
    """``sparse_attend_pages`` with the query layout and the tiles to
    choose: ``heads`` is the programs' own."""
    def form(q, kp, vp, table, pos, allowed):
        C, H, Dh = q.shape[1:]
        Hkv, page = kp.shape[2:4]
        table = jnp.pad(table, (0, -table.shape[0] % (bk // page)))
        keys, vals = (pages[0, table].swapaxes(0, 1).reshape(Hkv, -1, Dh)
                      for pages in (kp, vp))
        mask, r = jnp.moveaxis(allowed, 1, 0), H // Hkv
        if layout == "heads":
            qs, q_pos = jnp.moveaxis(q[0], 1, 0), pos
        else:
            qs = jnp.moveaxis(q[0].reshape(C, Hkv, r, Dh), 1, 0).reshape(
                Hkv, C * r, Dh)
            q_pos, mask = jnp.repeat(pos, r), jnp.repeat(mask, r, 1)
        o, _ = flash_lib.flash_attention_keys(
            qs, keys, vals, q_pos[None],
            jnp.arange(keys.shape[1], dtype=jnp.int32)[None],
            scale=Dh ** -0.5, page_mask=mask, page=page, block_q=bq,
            block_k=bk)
        if layout == "heads":
            o = jnp.moveaxis(o, 0, 1)
        else:
            o = jnp.moveaxis(o.reshape(Hkv, C, r, Dh), 0, 1)
        return o.reshape(1, C, H * Dh).astype(q.dtype)
    return form


def sparse_sweep(args) -> None:
    H, HKV, DH, PAGE, TOPK, DENSE = 32, 2, 128, 64, 64, 8192
    width, layers = args.table_pages, args.sparse_layers
    rng = np.random.default_rng(0)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    kp, vp = (jax.random.normal(k, (1, width + 1, HKV, PAGE, DH),
                                jnp.bfloat16) for k in ks[:2])
    table = jnp.asarray(1 + rng.permutation(width), jnp.int32)
    tables_of = flash_lib._page_tables

    def no_clamp(bits, bounds, bq, window):
        last, given = tables_of(bits, bounds, bq, window)
        return jnp.full_like(last, bits.shape[1] - 1), given

    def no_skip(bits, bounds, bq, window):
        return tables_of(jnp.ones_like(bits), bounds, bq, window)

    def chained(form):
        @jax.jit
        def chain(q, kp, vp, table, pos, allowed):
            return lax.scan(
                lambda q, _: (form(q, kp, vp, table, pos, allowed).reshape(
                    q.shape), None), q, None, length=layers)[0]
        return chain

    def xla(q, kp, vp, table, pos, allowed):
        return decode_lib.sparse_attend_chunk(q, kp, vp, 0, table, pos,
                                              allowed)

    def pages(q, kp, vp, table, pos, allowed):
        return decode_lib.sparse_attend_pages(q, kp, vp, 0, table, pos,
                                              allowed)

    for c in args.chunks:
        q = jax.random.normal(ks[2], (1, c, H, DH), jnp.bfloat16)
        for end in args.keys:
            pos = np.arange(end - c, end)
            at = pos // PAGE                                   # [C]
            for selection in ("independent", "agreeing"):
                draws = rng.random((1 if selection == "agreeing" else c,
                                    HKV, width))
                page = np.arange(width)
                draws = np.where(page <= at[:, None, None], draws, 2.0)
                draws[..., 0] = -1.0                   # the first page
                draws[(page == at[:, None])[:, None].repeat(HKV, 1)] = -1.0
                nth = np.sort(draws, -1)[..., TOPK - 1:TOPK]      # width >= TOPK
                allowed = jnp.asarray(
                    ((draws <= nth) & (draws < 2.0))
                    | (pos < DENSE)[:, None, None]
                    & (page <= at[:, None, None]))
                xs = (q, kp, vp, table, jnp.asarray(pos, jnp.int32), allowed)
                want = jax.jit(xla)(*xs).astype(jnp.float32)
                row = {"C": c, "keys": end, "selection": selection,
                       "xla": round(median_ms(chained(xla), xs, args.reps)
                                    / layers, 4)}

                def measure(name, form, tables=tables_of, tiles=None):
                    flash_lib._page_tables = tables
                    decode_lib.sparse_attend_pages.clear_cache()
                    try:
                        row[name] = round(median_ms(
                            chained(form), xs, args.reps) / layers, 4)
                        got = jax.jit(form)(*xs).astype(jnp.float32)
                        row[name + "_rel_err"] = round(float(
                            jnp.max(jnp.abs(got - want))
                            / jnp.max(jnp.abs(want))), 5)
                    except Exception as e:     # a tile Mosaic refuses
                        row[name] = f"refused: {str(e)[:80]}"
                    finally:
                        flash_lib._page_tables = tables_of
                    if tiles:
                        row[name + "_tiles_pct"] = tiles_share(*tiles)

                def tiles_share(layout, bq, bk):
                    """% of the causally visible tile pairs computed."""
                    r = H // HKV if layout == "group" else 1
                    mask = jnp.repeat(jnp.moveaxis(allowed, 1, 0), r, 1)
                    q_pos = jnp.repeat(xs[4], r)
                    k_pos = jnp.arange(-(-width * PAGE // bk) * bk)
                    bits = flash_lib._page_bits(
                        mask, c * r, k_pos.size // bk, bk // PAGE)
                    bounds = (q_pos.reshape(-1, bq).max(-1),
                              q_pos.reshape(-1, bq).min(-1),
                              k_pos.reshape(-1, bk).min(-1),
                              k_pos.reshape(-1, bk).max(-1))
                    given = tables_of(bits, bounds, bq, None)[1]
                    causal = tables_of(jnp.ones_like(bits), bounds, bq,
                                       None)[1]
                    return round(100 * float(given.sum() / causal.sum()), 2)

                measure("pages", pages, tiles=("heads", min(1024, c), 1024))
                if selection == "independent" and c == max(args.chunks):
                    measure("pages_no_clamp", pages, no_clamp)
                    measure("pages_no_skip", pages, no_skip)
                for layout in ("heads", "group"):
                    for bq, bk in args.tiles or [[1024, 1024], [512, 1024],
                                                 [1024, 512], [512, 512]]:
                        if layout == "heads" and (
                                bq > c or (bq, bk) == (min(1024, c), 1024)):
                            continue          # no such tile, or `pages`
                        if selection == "agreeing" and layout == "heads":
                            continue
                        measure(f"{layout}_{bq}x{bk}",
                                sparse_form(layout, bq, bk),
                                tiles=(layout, bq, bk))
                print(json.dumps(row), flush=True)


def select_sweep(args) -> None:
    H, HKV, DH, PAGE, STRIDE, KERNEL = 32, 2, 128, 64, 16, 32
    per, strides = PAGE // STRIDE, KERNEL // STRIDE
    cfg = types.SimpleNamespace(sparse_init_blocks=1, sparse_window=2048,
                                sparse_block=PAGE, sparse_topk=64)
    width, layers = args.table_pages, args.sparse_layers
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = jax.random.normal(ks[0], (1, width + 1, HKV, per, DH), jnp.bfloat16)
    table = jnp.asarray(
        1 + np.random.default_rng(0).permutation(width), jnp.int32)

    def chained(form):
        """``form(x, offset) -> an array``: a layer's result feeds the
        next one's ``x`` (times 0), so that the calls run in turn."""
        @jax.jit
        def chain(x, offset):
            return lax.scan(
                lambda x, _: (x + (0 * form(x, offset).sum(
                    dtype=jnp.float32)).astype(x.dtype),
                              None), x, None, length=layers)[0]
        return chain

    def xla_scores(q, offset):
        pos = offset + jnp.arange(q.shape[0], dtype=jnp.int32)
        seen = (STRIDE * jnp.arange(width * per, dtype=jnp.int32)
                + KERNEL - 1 <= pos[:, None])
        kernels = pool[0, table].swapaxes(1, 2).reshape(
            1, width * per, HKV, DH)
        return decode_lib.sparse_block_scores(q[None], kernels, seen[None],
                                              per, strides)[0]

    def best_blocks(scores, offset):
        pos = offset + jnp.arange(scores.shape[0], dtype=jnp.int32)
        return decode_lib.sparse_choose(scores, pos // PAGE, cfg)

    def as_pages(blocks, ok):
        """The scatter of ``mixed_programs``' ``sparse_chosen_pages``."""
        c = blocks.shape[0]
        return jnp.zeros((c, HKV, width), bool).at[
            jnp.arange(c)[:, None, None], jnp.arange(HKV)[None, :, None],
            blocks].set(ok)

    def choice(scores, offset):
        return as_pages(*best_blocks(scores, offset))

    def kernel_scores(bq=None):
        def form(q, offset):
            return scores_lib.sparse_scores(
                q, pool[0, table], offset, jnp.int32(q.shape[0]),
                stride=STRIDE, kernel=KERNEL, block_q=bq)
        return form

    for c in args.chunks:
        q = jax.random.normal(ks[1], (c, H, DH), jnp.bfloat16)
        for end in args.keys:
            xs = (q, jnp.int32(end - c))
            want = jax.jit(xla_scores)(*xs)
            row = {"C": c, "keys": end}

            def measure(name, form, xs=xs):
                try:
                    row[name] = round(median_ms(
                        chained(form), xs, args.reps) / layers, 4)
                except Exception as e:     # a tile Mosaic refuses
                    row[name] = f"refused: {str(e)[:80]}"

            measure("xla_scores", xla_scores)
            # the choice over scores that are the program's own argument:
            # XLA then lays them with the queries in the lanes and sorts
            # 1024 of them at a time, as the chunk programs' top-k does
            # (under a producer of its own choosing the same call read
            # 5.97 ms at C = 1024 where this reads 1.20)
            blocks, ok = jax.jit(best_blocks)(want, xs[1])
            measure("top_k", lambda s, offset: best_blocks(s, offset)[0],
                    (want, xs[1]))
            measure("scatter", lambda s, offset: as_pages(
                blocks + (0 * s[..., :1]).astype(jnp.int32), ok),
                (want, xs[1]))
            measure("choice", choice, (want, xs[1]))
            default = scores_lib._PAGES
            # the programs' own tile first: min(C, 1024) x 128
            for bq, pages in args.tiles or [[1024, 128], [512, 128],
                                            [256, 128], [1024, 256]]:
                if bq > c:
                    continue
                scores_lib._PAGES = pages
                scores_lib._scores.clear_cache()
                measure(f"kernel_{bq}x{pages}", kernel_scores(bq))
            scores_lib._PAGES = default
            scores_lib._scores.clear_cache()
            measure("select", lambda q, offset: choice(
                kernel_scores()(q, offset), offset))
            got = jax.jit(kernel_scores())(*xs)
            row["rel_err"] = float(jnp.max(jnp.abs(got - want))
                                   / jnp.max(jnp.abs(want)))
            row["same_choice_pct"] = round(100 * float(jnp.mean(
                jax.jit(choice)(got, xs[1]) == jax.jit(choice)(
                    want, xs[1]))), 4)
            print(json.dumps(row), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--select", action="store_true",
                    help="a sparse layer's selection for a chunk instead")
    ap.add_argument("--sparse", action="store_true",
                    help="a sparse layer's chunk over its pages instead")
    ap.add_argument("--table-pages", type=int, default=520)
    ap.add_argument("--sparse-layers", type=int, default=8,
                    help="calls chained in one program")
    ap.add_argument("--latent", action="store_true",
                    help="the mla layers' chunk instead of the prompt")
    ap.add_argument("--latent-decode", action="store_true",
                    help="the mla layers' decode step instead")
    ap.add_argument("--paged-decode", action="store_true",
                    help="a full layer's decode step instead")
    ap.add_argument("--cells", nargs="+", default=sorted(PAGED_SHAPES),
                    choices=sorted(PAGED_SHAPES) + ["ring"])
    ap.add_argument("--ring-pages", type=int, nargs="+", default=[16, 48],
                    help="positions a page of a ring (--cells ring)")
    ap.add_argument("--rows", type=int, nargs="+", default=[32, 64])
    ap.add_argument("--lengths", nargs="+",
                    default=["even", "spread", "tail"],
                    choices=["even", "spread", "tail"])
    ap.add_argument("--heads", type=int, nargs="+", default=[64, 32])
    ap.add_argument("--chunks", type=int, nargs="+", default=None)
    ap.add_argument("--keys", type=int, nargs="+", default=None)
    ap.add_argument("--key-blocks", type=int, nargs="+", default=None,
                    help="keys a call (default: the programs')")
    ap.add_argument("--merge-outside", action="store_true",
                    help="merge the key blocks by XLA, not in the kernel")
    ap.add_argument("--tiles", type=lambda s: [int(x) for x in s.split("x")],
                    nargs="*", default=[], help="q x kv tiles, as 512x1024")
    ap.add_argument("--buckets", type=int, nargs="+",
                    default=[128, 256, 512, 1024, 1280, 1536, 1792, 2048])
    ap.add_argument("--blocks", type=int, nargs="+",
                    default=[128, 256, 512, 640, 768, 896, 1024])
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    if args.paged_decode:
        return paged_decode_sweep(args)
    if args.sparse or args.select:
        args.chunks = args.chunks or [1024, 512, 256]
        args.keys = args.keys or [8192, 16384, 32768]
        return (sparse_sweep if args.sparse else select_sweep)(args)
    args.chunks = args.chunks or [256, 512, 768, 1024]
    args.keys = args.keys or [1024, 4096, 8192, 17408]
    if args.latent:
        return latent_sweep(args)
    if args.latent_decode:
        return latent_decode_sweep(args)
    for t in args.buckets:
        keys = jax.random.split(jax.random.PRNGKey(t), 3)
        q, k, v = (
            (jax.random.normal(key, (1, t, h, 128)) * 0.5).astype(jnp.bfloat16)
            for key, h in zip(keys, (32, 8, 8)))
        row = {"T": t, "dense": ms_a_layer(dense, q, k, v, args.reps),
               "attend_prompt": ms_a_layer(_attend_prompt, q, k, v,
                                           args.reps)}
        for b in args.blocks:
            if b <= -(-t // 128) * 128:
                row[f"flash_{b}"] = ms_a_layer(
                    lambda q, k, v: flash_attention(
                        q, k, v, causal=True, block_q=b,
                        block_k=b).reshape(*q.shape[:2], -1),
                    q, k, v, args.reps)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(dense)(*(x.astype(jnp.float32) for x in (q, k, v)))
        got = jax.jit(_attend_prompt)(q, k, v).astype(jnp.float32)
        row["rel_err"] = float(jnp.max(jnp.abs(got - want))
                               / jnp.max(jnp.abs(want)))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
