"""The absorbed form of latent attention in XLA: the tests' reference
for ``serve/decode.py::_mla_attend`` (a chunk, expanded, through the
Pallas forward over keys that carry their positions) and for
``_mla_decode`` (a decode step, through
``ops/paged_decode.py::latent_decode``). No serve program runs it."""

import jax.numpy as jnp
from jax import lax

from horovod_tpu.models import transformer as tf_lib

_NEG_BIG = -1e30


def mla_attend_absorbed(cfg, lp, qn, qr, keys_of, n_blocks, pos):
    """Latent attention of queries ``qn`` [B, C, H, Dh] (no position)
    and ``qr`` [B, C, H, R] (rotated) at positions ``pos`` [B, C] over
    ``n_blocks`` (traced, at least 1) blocks of cached latents.
    ``keys_of(j) -> (latent [B, K, C + R], key_pos [K])`` gives block
    j; a key is seen where ``key_pos <= pos``. ``q W_uk^T`` is scored
    against the latent itself and the latent is summed, then expanded
    once (``(sum p c) W_uv``), with a running softmax over the blocks:
    no ``[K, H, Dh]`` key or value a position. Float32 scores, softmax
    and accumulators over operands in the latents' dtype, ``p`` rounded
    to it for the value sum. Returns [B, C, H, Dh]. A query that sees
    no key reads a mean of the keys."""
    B, C, H, _ = qn.shape
    rank, R = cfg.mla_kv_rank, cfg.mla_rope_dim
    w_uk, w_uv = tf_lib.mla_up(cfg, lp)
    scale = tf_lib.mla_scale(cfg)
    qn = jnp.einsum("bqhd,chd->bqhc", qn, w_uk)

    def block(j, carry):
        m, l, acc = carry
        latent, key_pos = keys_of(j)
        c, r = latent[..., :rank], latent[..., rank:rank + R]
        s = (jnp.einsum("bqhc,bkc->bhqk", qn, c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bkr->bhqk", qr, r,
                          preferred_element_type=jnp.float32)) * scale
        seen = key_pos[None, None, :] <= pos[:, :, None]     # [B, C, K]
        s = jnp.where(seen[:, None], s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        fade = jnp.exp(m - m_new)
        acc = acc * fade[..., None] + jnp.einsum(
            "bhqk,bkd->bhqd", p.astype(c.dtype), c,       # every head's
            preferred_element_type=jnp.float32)
        return m_new, l * fade + p.sum(-1), acc

    m, l, acc = lax.fori_loop(
        0, n_blocks, block,
        (jnp.full((B, H, C), _NEG_BIG, jnp.float32),
         jnp.zeros((B, H, C), jnp.float32),
         jnp.zeros((B, H, C, rank), jnp.float32)))
    o = jnp.moveaxis(acc / l[..., None], 1, 2).astype(qn.dtype)
    return jnp.einsum("bqhc,chd->bqhd", o, w_uv)
