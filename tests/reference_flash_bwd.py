"""The plain reference of the flash-attention backward: what the Pallas
dq / dkv kernels of ``ops/flash_attention.py`` are held to.

This is the backward the module itself ran until PR 33, as it was:
``jnp.einsum`` chains in float32 over the whole ``[B·Hkv, rep, T, T]``
score tensor, recomputing the probabilities from the forward's saved
log-sum-exp, masked after the matmul. O(T²) memory, so for tests' sizes
only.
"""

import jax.numpy as jnp

NEG_INF = -1e30


def _hidden(t, causal, window):
    """[T, T] (query, key): the keys a query does not see, ahead of it
    (``causal``) and, with a ``window``, the ``j <= p - window``."""
    q_pos = jnp.arange(t)[:, None]
    k_pos = jnp.arange(t)[None, :]
    hidden = k_pos > q_pos if causal else jnp.zeros((t, t), bool)
    if window is not None:
        hidden |= k_pos <= q_pos - window
    return hidden


def dense_forward(scale, causal, q, k, v, q_per_kv=1, window=None):
    """(out [B·H, T, D], lse [B·H, T]) in float32 from the whole score
    tensor: what the forward kernel is held to where a window is set."""
    bkv, t, d = k.shape
    qc = q.astype(jnp.float32).reshape(bkv, q_per_kv, t, d)
    s = jnp.einsum("brqd,bkd->brqk", qc, k.astype(jnp.float32)) * scale
    s = jnp.where(_hidden(t, causal, window), NEG_INF, s)
    lse = jnp.log(jnp.sum(jnp.exp(s - s.max(-1, keepdims=True)), -1)
                  ) + s.max(-1)
    out = jnp.einsum("brqk,bkd->brqd", jnp.exp(s - lse[..., None]),
                     v.astype(jnp.float32))
    return out.reshape(q.shape), lse.reshape(q.shape[0], t)


def einsum_backward(scale, causal, residuals, g, g_lse=None, q_per_kv=1,
                    window=None):
    """(dq, dk, dv) in float32 from the forward's residuals
    ``(q, k, v, out, lse)`` (q, out ``[B·H, T, D]``; k, v ``[B·Hkv, T,
    D]``; lse ``[B·H, T]``), the output's cotangent ``g`` and, when the
    caller consumed it, the log-sum-exp's ``g_lse``: d lse/d q =
    (p @ k)·scale and d lse/d k_j = p_j · q · scale.

    GQA (``q_per_kv > 1``): q-side tensors reshape to a [B·Hkv, rep]
    grouping (consecutive query heads share a kv head under the
    batch-major flattening) and dk/dv sum over the group. ``window``:
    the same mask the windowed kernels apply (:func:`_hidden`)."""
    q, k, v, out, lse = residuals
    rep = q_per_kv
    bkv, t, d = k.shape
    grp = lambda x: x.astype(jnp.float32).reshape(bkv, rep, t, d)  # noqa: E731
    qc, doc, outc = grp(q), grp(g), grp(out)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

    s = jnp.einsum("brqd,bkd->brqk", qc, kf) * scale
    if causal or window is not None:
        s = jnp.where(_hidden(t, causal, window), NEG_INF, s)
    p = jnp.exp(s - lse.reshape(bkv, rep, t)[..., None])

    dv = jnp.einsum("brqk,brqd->bkd", p, doc)
    dp = jnp.einsum("brqd,bkd->brqk", doc, vf)
    delta = jnp.sum(doc * outc, axis=-1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("brqk,bkd->brqd", ds, kf)
    dk = jnp.einsum("brqk,brqd->bkd", ds, qc)
    if g_lse is not None:
        glc = g_lse.astype(jnp.float32).reshape(bkv, rep, t)
        dq = dq + glc[..., None] * jnp.einsum("brqk,bkd->brqd", p, kf) * scale
        dk = dk + jnp.einsum("brq,brqk,brqd->bkd", glc, p, qc) * scale
    return dq.reshape(q.shape), dk, dv
