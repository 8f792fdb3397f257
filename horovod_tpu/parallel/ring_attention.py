"""Ring attention — context/sequence parallelism over the ``sp`` mesh axis.

Long-context scaling the TPU way: the sequence dimension is sharded
across the ``sp`` axis and K/V blocks rotate around the ICI ring with
``lax.ppermute`` while each device accumulates its queries' attention
with an online (flash-style) softmax. Communication overlaps with the
block matmuls and no device ever materialises the full [T, T] score
matrix or the full-sequence K/V.

The reference framework (mackrorysd/horovod) has no sequence
parallelism at all (SURVEY.md §5.7; the closest primitive is alltoall,
``horovod/common/operations.cc:1131``). This module is the TPU-native
answer: ring attention (Liu et al., 2023) for block-SP, and
:func:`ulysses_attention` (all-to-all head/sequence exchange) as the
alltoall-based alternative.

Layout convention: ``[batch, seq, heads, head_dim]`` for q/k/v.
Functions here run *inside* ``shard_map`` (manual over ``sp`` at
least); :func:`ring_self_attention` is the shard-local computation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_BIG = -1e30  # finite "-inf": keeps the online-softmax guards NaN-free


def _varying_like(ts, ref, axis_name: str):
    """Declare each accumulator in ``ts`` varying over the ring axis
    AND every other manual axis ``ref`` (the query shard) is varying
    over. Inside a combined manual island (pp+sp pipelining) the
    fori_loop carry mixes in pp-varying activations, so declaring only
    the ring axis would mismatch the carry's VMA types."""
    want = jax.typeof(ref).vma | {axis_name}
    return [lax.pcast(t, tuple(want - jax.typeof(t).vma), to="varying")
            for t in ts]


def _rotate(x, axis_name: str, shift: int = 1):
    """Pass shard-local ``x`` one hop around the ``axis_name`` ring."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm=perm)


def _block_attend(q, k, v, o, l, m, *, scale, mask):
    """One online-softmax accumulation step over a K/V block.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; o: [B, Tq, H, D] f32;
    l, m: [B, H, Tq] f32 running normaliser / running max.
    mask: [Tq, Tk] bool (True = attend) or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_BIG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None], p, 0.0)
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o = o * corr.transpose(0, 2, 1)[..., None] + pv
    return o, l, m_new


def ring_self_attention(q, k, v, *, axis_name: str = "sp",
                        causal: bool = True,
                        scale: Optional[float] = None):
    """Shard-local ring attention body (call under ``shard_map``).

    ``q``/``k``/``v``: ``[B, T_local, H, D]`` — the local sequence chunk
    of a globally ``T_local * sp``-token sequence laid out contiguously
    (chunk ``i`` on sp-rank ``i``). Returns ``[B, T_local, H, D]`` in
    ``q.dtype``.

    Each of the ``sp`` steps attends the local queries to the currently
    held K/V chunk, then rotates K/V one hop (shift −1 so that at step
    ``i`` rank ``r`` holds chunk ``(r + i) % sp``... direction is
    irrelevant to correctness since every rank sees every chunk once;
    causal masking keys off the chunk's global offset).
    """
    B, T, H, D = q.shape
    sp = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    if scale is None:
        scale = D ** -0.5

    q32 = q
    o = jnp.zeros((B, T, H, D), jnp.float32)
    l = jnp.zeros((B, H, T), jnp.float32)
    m = jnp.full((B, H, T), _NEG_BIG, jnp.float32)
    # The accumulators become device-varying inside the loop (they mix
    # in axis_index-dependent masks); declare that up front so the scan
    # carry types line up under shard_map's VMA checking.
    o, l, m = _varying_like((o, l, m), q, axis_name)

    qpos = my * T + jnp.arange(T)

    def step(i, carry):
        o, l, m, k_cur, v_cur = carry
        src = (my + i) % sp  # which global chunk we currently hold
        if causal:
            kpos = src * T + jnp.arange(T)
            mask = qpos[:, None] >= kpos[None, :]
        else:
            mask = None
        o, l, m = _block_attend(q32, k_cur, v_cur, o, l, m,
                                scale=scale, mask=mask)
        # Shift -1: receive the next-higher rank's chunk each step.
        k_nxt = _rotate(k_cur, axis_name, shift=-1)
        v_nxt = _rotate(v_cur, axis_name, shift=-1)
        return o, l, m, k_nxt, v_nxt

    o, l, m, _, _ = lax.fori_loop(0, sp, step, (o, l, m, k, v))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_flash_attention(q, k, v, *, axis_name: str = "sp",
                         causal: bool = True,
                         scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None):
    """Ring attention whose per-chunk block compute is the **flash
    Pallas kernel** (:mod:`horovod_tpu.ops.flash_attention`): each of
    the ``sp`` steps runs fused attention of the local queries against
    the currently held K/V chunk, returning ``(out, lse)``, and chunks
    are merged by logsumexp weighting — the blockwise-parallel
    formulation of the same online softmax :func:`ring_self_attention`
    does in plain XLA. Long-context + sequence-parallel with the MXU
    kernel in the inner loop.

    Causality is per chunk: a chunk strictly before mine is fully
    visible, my own chunk is causal with aligned positions, a later
    chunk contributes nothing (its lse stays -inf so the merge ignores
    it — and under reverse-mode AD its zero weight kills the gradient).
    """
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    B, T, H, D = q.shape
    sp = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    if scale is None:
        scale = D ** -0.5

    def to_bh(x):  # [B, T, H, D] -> [B*H, T, D]
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    # Transform to kernel layout ONCE; K/V rotate in that layout (the
    # ppermute cost is layout-independent).
    qb, kb0, vb0 = to_bh(q), to_bh(k), to_bh(v)

    # Chunk outputs stay f32 until the final merge so bf16 inputs round
    # exactly once, like ring_self_attention's f32 accumulator.
    # Unset blocks pin to 512x1024 (the tier measured on THIS path)
    # rather than the kernel's shape-derived defaults, which were
    # measured on the sp=1 causal path — per-chunk calls here are
    # causal=False over T/sp-length chunks, a different regime.
    blocks = {kk: (vv if vv is not None else dflt) for (kk, vv), dflt in
              zip((("block_q", block_q), ("block_k", block_k)),
                  (512, 1024))}

    def full_chunk(qb, kb, vb):
        return flash_attention_with_lse(qb, kb, vb, causal=False,
                                        scale=scale, out_dtype=jnp.float32,
                                        **blocks)

    def diag_chunk(qb, kb, vb):
        return flash_attention_with_lse(qb, kb, vb, causal=True,
                                        scale=scale, out_dtype=jnp.float32,
                                        **blocks)

    def skip_chunk(qb, kb, vb):
        return (jnp.zeros((B * H, T, D), jnp.float32),
                jnp.full((B * H, T), _NEG_BIG, jnp.float32))

    # Running logsumexp merge: out_i is chunk-normalized, so the global
    # result is Σ_i out_i·exp(lse_i) / Σ_i exp(lse_i). Track the running
    # max m, the weighted sum o = Σ out_i·exp(lse_i − m), and the
    # normalizer l = Σ exp(lse_i − m).
    o = jnp.zeros((B * H, T, D), jnp.float32)
    m = jnp.full((B * H, T), _NEG_BIG, jnp.float32)
    l = jnp.zeros((B * H, T), jnp.float32)
    o, m, l = _varying_like((o, m, l), qb, axis_name)

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        src = (my + i) % sp                     # global chunk index held
        if causal:
            case = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
            out_b, lse_b = lax.switch(
                case, [full_chunk, diag_chunk, skip_chunk],
                qb, k_cur, v_cur)
        else:
            out_b, lse_b = full_chunk(qb, k_cur, v_cur)
        m_new = jnp.maximum(m, lse_b)
        w_old = jnp.exp(m - m_new)
        w_new = jnp.exp(lse_b - m_new)
        o = o * w_old[..., None] + out_b * w_new[..., None]
        l = l * w_old + w_new
        k_nxt = _rotate(k_cur, axis_name, shift=-1)
        v_nxt = _rotate(v_cur, axis_name, shift=-1)
        return o, m_new, l, k_nxt, v_nxt

    o, m, l, _, _ = lax.fori_loop(0, sp, step, (o, m, l, kb0, vb0))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3).astype(q.dtype)


def local_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    window: Optional[int] = None):
    """Plain (single-device-sequence) attention with the same layout,
    used when ``sp == 1`` and as the reference for ring tests. A
    ``window`` (causal only) hides the keys ``j <= p - window`` too."""
    B, T, H, D = q.shape
    if scale is None:
        scale = D ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, k.shape[1]), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones_like(mask), -window)
        s = jnp.where(mask[None, None], s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str = "sp",
                      causal: bool = True,
                      scale: Optional[float] = None):
    """DeepSpeed-Ulysses-style SP: all-to-all so each sp-rank holds the
    FULL sequence for ``H / sp`` heads, attends locally, then
    all-to-alls back to sequence sharding. This is exactly the
    reference's alltoall primitive (``operations.cc:1131``) applied to
    attention heads — the SP design its substrate anticipated
    (SURVEY.md §2.6). Requires ``H % sp == 0``.
    """
    sp = lax.axis_size(axis_name)

    def seq_to_heads(x):  # [B, T/sp, H, D] -> [B, T, H/sp, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):  # [B, T, H/sp, D] -> [B, T/sp, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    if sp == 1:
        return local_attention(q, k, v, causal=causal, scale=scale)
    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = local_attention(qh, kh, vh, causal=causal, scale=scale)
    return heads_to_seq(oh)


def make_sp_attention(mesh, *, axis_name: str = "sp", impl: str = "ring",
                      causal: bool = True, spec=None,
                      block_q=None, block_k=None,
                      window: Optional[int] = None):
    """Build ``attend(q, k, v)``: ring/Ulysses attention as a
    partial-manual ``shard_map`` island inside an outer GSPMD program.

    Inputs are *global* ``[B, T, H, D]`` arrays whose ``T`` dim is
    sharded over ``axis_name``; all other mesh axes stay under GSPMD
    control (``axis_names={axis_name}``). The single construction point
    for the island — the model layer and the functional API both route
    through here.

    ``window`` (causal): a query sees the ``window`` keys up to its own
    (a sliding layer of a stack of several kinds). Built for a sequence
    on one chip, ``flash`` and ``local``; over ``sp`` it is refused.
    """
    from jax.sharding import PartitionSpec as P

    if spec is None:
        spec = P(None, axis_name, None, None)
    sp1 = mesh is None or \
        dict(getattr(mesh, "shape", {})).get(axis_name, 1) == 1
    if window is not None and not (causal and sp1):
        raise NotImplementedError(
            "attention with a window is built for causal attention over a "
            f"sequence on one chip ({axis_name}=1): the ring and Ulysses "
            "islands pass whole chunks and know no window")
    if impl == "flash":
        if not sp1:
            raise NotImplementedError(
                "impl='flash' is the sp=1 kernel; use impl='ring_flash' "
                "for sequence parallelism with the Pallas block kernel")
        from horovod_tpu.ops.flash_attention import flash_attention
        blocks = {k: v for k, v in
                  (("block_q", block_q), ("block_k", block_k))
                  if v is not None}
        if window is not None:
            blocks["window"] = window
        fa = functools.partial(flash_attention, causal=causal, **blocks)
        fa.handles_gqa = True  # native grouped K/V; no pre-tiling needed
        if mesh is None:
            return fa
        # The Pallas kernel is embarrassingly parallel over batch and
        # heads but Mosaic can't be auto-partitioned by GSPMD: run it
        # as a manual island sharded over the batch/head axes. The
        # island must be manual over ALL mesh axes — with a partial
        # manual set, even size-1 leftover axes keep the pallas call
        # under the auto partitioner and Mosaic refuses to lower
        # ("cannot be automatically partitioned"), including on a
        # single real chip.
        bspec = P(("dp", "fsdp"), None, "tp", None)
        mapped = jax.shard_map(fa, mesh=mesh,
                               in_specs=(bspec, bspec, bspec),
                               out_specs=bspec,
                               axis_names=frozenset(mesh.axis_names),
                               check_vma=False)
        tp_size = dict(mesh.shape).get("tp", 1)

        def wrapped(q, k, v):
            # Native grouped K/V needs the kv-head axis shardable over
            # tp; when tp > Hkv (e.g. flagship Hkv=8 with tp=16), tile
            # KV up to H — the pre-GQA behavior — so the island specs
            # still divide.
            if k.shape[2] % tp_size:
                rep = q.shape[2] // k.shape[2]
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            return mapped(q, k, v)
        wrapped.handles_gqa = True
        return wrapped
    if impl == "local" or sp1:
        if window is not None:
            return functools.partial(local_attention, causal=causal,
                                     window=window)
        return functools.partial(local_attention, causal=causal)
    if impl == "ring":
        body = functools.partial(ring_self_attention, axis_name=axis_name,
                                 causal=causal)
    elif impl == "ring_flash":
        blocks = {k: v for k, v in
                  (("block_q", block_q), ("block_k", block_k))
                  if v is not None}
        body = functools.partial(ring_flash_attention, axis_name=axis_name,
                                 causal=causal, **blocks)
    elif impl == "ulysses":
        body = functools.partial(ulysses_attention, axis_name=axis_name,
                                 causal=causal)
    else:
        raise ValueError(f"unknown SP attention impl {impl!r}")
    # VMA checking stays ON for the pure-XLA impls; pallas_call's
    # out_shape carries no varying-manual-axes annotation yet, so the
    # ring_flash island must opt out (a JAX limitation, not a missing
    # pcast — the accumulators are declared varying either way).
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         axis_names=frozenset({axis_name}),
                         check_vma=impl != "ring_flash")


def sequence_sharded_attention(q, k, v, mesh, *, axis_name: str = "sp",
                               impl: str = "ring", causal: bool = True,
                               spec=None):
    """One-shot form of :func:`make_sp_attention`."""
    return make_sp_attention(mesh, axis_name=axis_name, impl=impl,
                             causal=causal, spec=spec)(q, k, v)
