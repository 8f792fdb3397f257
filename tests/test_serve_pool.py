"""The serve programs update the KV pool where it lies (PR 30): the
layer scan of ``serve/decode.py`` carries the whole pool and addresses
it by layer. Held here to the form it replaced, kept below as the
reference: a scan that takes the pool as its ``xs`` (one layer's pool
sliced out a step), writes and attends on that slice, and stacks the
slices back as its ``ys``. For a dense GQA, a dense MHA and a one-hot
MoE model, and for ``prefill``, ``prefill_resume``, ``decode`` and
``verify``, the tokens **and the whole pool** after a call are bitwise
the reference's, from a pool that already holds other sequences' pages
(and garbage in the null block), with addresses that must fall on the
null block: a padded batch row, bucket blocks past the allocation,
chunk positions past the table. Once more on a 2-device mesh with the
pool tp-sharded on the KV heads. (That the compiler then moves no
pool-sized buffer is ``tests/test_tpu_lowering.py``'s.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import TransformerConfig, init_transformer
from horovod_tpu.models import transformer as tf_lib
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import NULL_BLOCK

BS, WIDTH, N_BLOCKS = 8, 3, 9
CONFIGS = {"gqa": {}, "mha": {"n_kv_heads": 4},
           "moe": {"n_experts": 4, "moe_top_k": 2}}
PROGRAMS = ("prefill", "prefill_resume", "decode", "verify")


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def _arguments(program, vocab):
    """What the engine would pass: block 0 is the null block, blocks 4
    and 6 belong to sequences that are not in the call."""
    tokens = np.random.default_rng(7).integers(1, vocab, (4, 24))
    tables = _i32([[5, 2, NULL_BLOCK], [7, NULL_BLOCK, NULL_BLOCK],
                   [1, 3, 8], [NULL_BLOCK] * WIDTH])      # row 3: padding
    return {
        # 11 real tokens in a bucket of three blocks: the third block
        # of the bucket is past the allocation
        "prefill": (_i32(tokens[0]), _i32(11), tables[0]),
        # a chunk of two blocks after two cached ones: its second block
        # is past the table
        "prefill_resume": (_i32(tokens[0, :16]), _i32(16), _i32(5),
                           tables[2]),
        "decode": (_i32(tokens[:, 0]).at[3].set(0), _i32([9, 3, 17, 0]),
                   tables),
        # row 2's third position (24) is past the table
        "verify": (_i32(tokens[:, :3]).at[3].set(0), _i32([9, 3, 22, 0]),
                   tables),
    }[program]


def _reference(cfg, mesh, program):
    """``program`` as PR 29 had it: the pool is the scan's ``xs`` and
    ``ys``, and writing, attending and gathering see one layer's pool
    ``[n_blocks, bs, Hkv, Dh]``."""
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim

    def attend_pages(q, kc_l, vc_l, tables, pos):
        B, C, H, _ = q.shape
        S = tables.shape[1] * BS
        kp = kc_l[tables].reshape(B, S, Hkv, Dh)
        vp = vc_l[tables].reshape(B, S, Hkv, Dh)
        qg = q.reshape(B, C, Hkv, H // Hkv, Dh)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kp,
                       preferred_element_type=jnp.float32) * Dh ** -0.5
        mask = jnp.arange(S, dtype=jnp.int32) <= pos[:, :, None]
        s = jnp.where(mask[:, None, None], s, decode_lib._NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(vp.dtype), vp,
                       preferred_element_type=jnp.float32).astype(q.dtype)
        return o.reshape(B, C, H * Dh)

    def write_blocks(pool_l, new, blks):
        return pool_l.at[blks].set(
            new[0].reshape(-1, BS, Hkv, Dh).astype(pool_l.dtype))

    def write_rows(pool_l, new, pos, tables):
        slot = pos // BS
        blk = jnp.take_along_axis(
            tables, jnp.minimum(slot, WIDTH - 1).reshape(pos.shape[0], -1),
            axis=1).reshape(pos.shape)
        blk = jnp.where(slot < WIDTH, blk, NULL_BLOCK)
        phys = (blk * BS + pos % BS).reshape(-1)
        return pool_l.reshape(-1, Hkv, Dh).at[phys].set(
            new.reshape(-1, Hkv, Dh).astype(pool_l.dtype)).reshape(
                pool_l.shape)

    def run(params, kc, vc, tokens, pos, write, attend, rows):
        x = tf_lib.embed_lookup(params["embed"], tokens, cfg.dtype, mesh,
                                None)

        def body(x, per_layer):
            lp, kc_l, vc_l = per_layer           # layer l, sliced out
            q, k, v = tf_lib.attention_inputs(cfg, lp, x, pos)
            kc_l, vc_l = write(kc_l, k), write(vc_l, v)
            o = attend(q, k, v, kc_l, vc_l)
            x = x + (o @ lp["wo"]).astype(cfg.dtype)
            x, _aux = tf_lib.ffn_block(cfg, lp, x)
            return x, (kc_l, vc_l)               # ... and put back

        x, (kc, vc) = lax.scan(body, x, (params["layers"], kc, vc))
        x = rows(tf_lib._rmsnorm(x, params["final_norm"], cfg.norm_eps))
        logits = (x @ params["lm_head"]).astype(jnp.float32)
        return kc, vc, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def prefill(params, kc, vc, tokens, length, table):
        n_blk = tokens.shape[0] // BS
        return run(params, kc, vc, tokens[None],
                   jnp.arange(tokens.shape[0], dtype=jnp.int32)[None],
                   lambda pool_l, new: write_blocks(pool_l, new,
                                                    table[:n_blk]),
                   lambda q, k, v, kc_l, vc_l:
                       decode_lib._attend_prompt(q, k, v),
                   lambda x: jnp.take(x[0], length - 1, axis=0))

    def prefill_resume(params, kc, vc, tokens, offset, length, table):
        Tc = tokens.shape[0]
        pos = offset + jnp.arange(Tc, dtype=jnp.int32)[None]
        slot = offset // BS + jnp.arange(Tc // BS, dtype=jnp.int32)
        blks = jnp.where(slot < WIDTH,
                         jnp.take(table, jnp.minimum(slot, WIDTH - 1)),
                         NULL_BLOCK)
        return run(params, kc, vc, tokens[None], pos,
                   lambda pool_l, new: write_blocks(pool_l, new, blks),
                   lambda q, k, v, kc_l, vc_l: attend_pages(
                       q, kc_l, vc_l, table[None], pos),
                   lambda x: jnp.take(x[0], length - 1, axis=0))

    def rows_program(params, kc, vc, tokens, positions, tables):
        """``decode`` (tokens [B]) and ``verify`` (tokens [B, C])."""
        chunk = tokens.reshape(tokens.shape[0], -1)
        pos = positions[:, None] + jnp.arange(chunk.shape[1],
                                              dtype=jnp.int32)[None]
        return run(params, kc, vc, chunk, pos,
                   lambda pool_l, new: write_rows(pool_l, new, pos, tables),
                   lambda q, k, v, kc_l, vc_l: attend_pages(
                       q, kc_l, vc_l, tables, pos),
                   (lambda x: x[:, 0]) if tokens.ndim == 1 else (lambda x: x))

    return jax.jit({"prefill": prefill, "prefill_resume": prefill_resume,
                    "decode": rows_program, "verify": rows_program}[program])


def _check(config, program, mesh=None):
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False,
                                 **CONFIGS[config])
    params = init_transformer(cfg, jax.random.PRNGKey(0), mesh)
    shape = (cfg.n_layers, N_BLOCKS, BS, cfg.n_kv_heads, cfg.head_dim)
    sharding = (NamedSharding(mesh, P(None, None, None, "tp", None))
                if mesh is not None else None)

    def pools():
        # made anew for each side: the programs donate them
        return tuple(jax.device_put(jnp.asarray(
            np.random.default_rng(seed).standard_normal(shape), cfg.dtype),
            sharding) for seed in (30, 31))

    args = _arguments(program, cfg.vocab_size)
    before = [np.asarray(p) for p in pools()]
    want = _reference(cfg, mesh, program)(params, *pools(), *args)
    fns = dict(zip(("prefill", "prefill_resume", "decode", "inject",
                    "verify"),
                   decode_lib.make_serve_fns(cfg, mesh, block_size=BS,
                                             table_width=WIDTH)))
    got = fns[program](params, *pools(), *args)
    for name, g, w in zip(("kc", "vc", "tokens"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    if sharding is not None:
        assert got[0].sharding.is_equivalent_to(sharding, len(shape))
        assert got[1].sharding.is_equivalent_to(sharding, len(shape))
    # the call wrote something, and nothing into the pages of the
    # sequences that were not in it
    for pool, was in zip(got[:2], before):
        pool = np.asarray(pool)
        assert not np.array_equal(pool, was)
        np.testing.assert_array_equal(pool[:, [4, 6]], was[:, [4, 6]])


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_tokens_and_pool_are_bitwise_the_sliced_scan_s(config, program):
    _check(config, program)


def test_tp_sharded_pool_is_bitwise_the_sliced_scan_s(devices):
    _check("gqa", "verify", build_mesh(devices=devices[:2], tp=2))
