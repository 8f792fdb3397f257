"""Speculative decoding: a draft transformer proposes, the target
verifies.

Decode is latency-bound exactly where a draft model pays (the MLPerf
TPU recipes, PAPERS.md): at small batch each decode step streams the
whole target model's weights to emit ONE token per sequence. A small
draft model proposing ``k`` tokens lets the target *verify* all k in
one chunked step — the weights stream once per k tokens instead of
once per token — and under greedy decoding the accepted stream is
**bitwise identical** to plain decode (pinned by
tests/test_speculative.py), because verification compares the target's
own argmax at every proposed position and keeps exactly the longest
matching prefix.

The draft is an ordinary transformer behind the same serve machinery:

* its jitted step fns come from the same :func:`~horovod_tpu.serve.
  decode.make_serve_fns` memo (a draft sharing the target's config —
  the all-accept test rig — shares the target's compiled programs);
* it keeps its own paged KV pool with the target's block geometry and
  the same bucketed decode discipline — one draft decode call per
  proposed token, batched across the active sequences;
* its params are built deterministically from
  ``init_transformer(draft.model_cfg, PRNGKey(draft.seed))`` — the
  same params-from-seed contract the RPC workers use for the target,
  so a cross-process speculative fleet agrees on the draft by
  construction.

**The verify step is a chunked prefill over reserved pages** (the
PR 4 plumbing): the target runs ``[last_token, d1..d_{k-1}]`` through
:func:`~horovod_tpu.serve.decode.make_serve_fns`'s ``verify`` fn —
token-granularity page writes through the sequence's existing block
table, attention over all its pages under the global-position causal
mask — and emits its own argmax at every chunk position. Acceptance is
host-side and greedy-exact: ``d_{j+1}`` is accepted iff it equals the
target argmax after ``d_j``; the first mismatch contributes the
target's own token instead (the correction token — worst case one
token per round, exactly plain decode's progress). When all k match,
the round emits the k draft tokens and no bonus token: forgoing the
(k+1)-th "free" token keeps the draft's KV cursor in lockstep with the
target's (no catch-up feed next round), which keeps every round's
shape uniform and the whole scheme simple enough to pin.

**Rollback is a cursor rewind.** The verify step wrote K/V for every
chunk position, accepted or not; rejected positions simply stay beyond
the sequence's length cursor (``_Seq.n_cached``) — the block table is
untouched, no page is scrubbed, and the garbage is overwritten by
later writes before the cursor ever reaches it (attention masks by
position, so it is never read meanwhile). The randomized property
test drives exactly this: adversarial drafts that force rejections at
every accept length, with streams pinned bitwise against plain decode
and the allocator's integrity checked every round.

Under sampling, the acceptance rule generalizes to rejection sampling
(accept ``d`` with probability ``min(1, p_target/p_draft)``, resample
from the normalized residual on rejection), which preserves the target
distribution exactly; this engine is greedy-only, where rejection
sampling degenerates to the exact-match rule above — token-for-token
parity, the property the tests pin.

Reference analog: none — the reference framework is training-only.
Design follows the standard speculative-decoding construction
(Leviathan et al.; vLLM/TGI implementations) specialized to greedy.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from horovod_tpu.serve.kv_cache import BlockAllocator, pick_bucket


@dataclasses.dataclass(frozen=True)
class DraftConfig:
    """The ``ServeConfig.draft`` sub-config: which draft transformer to
    run and how to build its params. The draft inherits the engine's
    block geometry (block size, table width, buckets) — only the model
    differs."""

    model_cfg: Any               # TransformerConfig of the draft model
    # Draft params are init_transformer(model_cfg, PRNGKey(seed)) —
    # the same deterministic params-from-seed contract the RPC workers
    # use for the target, so every replica (local or remote) builds
    # the identical draft.
    seed: int = 0
    cache_dtype: Any = None      # draft KV dtype (default: draft dtype)


def accept_greedy(proposals: Sequence[int],
                  verified: Sequence[int]) -> Tuple[int, List[int]]:
    """The greedy acceptance rule, host-side and pure (unit-tested
    directly): ``proposals`` are the draft's k tokens ``d1..dk``,
    ``verified`` the target's argmax after each of
    ``[t0, d1..d_{k-1}]``. Returns ``(n_accepted, emitted)`` where
    ``emitted`` is the longest matching draft prefix plus — on the
    first mismatch — the target's own correction token. All-match
    emits exactly the k draft tokens (no bonus token; see module
    docstring)."""
    k = len(proposals)
    assert len(verified) == k
    emitted: List[int] = []
    for j in range(k):
        if proposals[j] == verified[j]:
            emitted.append(proposals[j])
        else:
            emitted.append(verified[j])
            return j, emitted
    return k, emitted


@dataclasses.dataclass
class _DraftSeq:
    """Draft-side state for one active sequence: its mirror block
    reservation in the draft pool. The KV cursor is not stored — it is
    the engine sequence's ``n_cached`` by the lockstep invariant the
    acceptance rule maintains (module docstring)."""

    blocks: List[int]
    table: np.ndarray            # [table_width] int32, draft pool ids


class SpecDecoder:
    """The speculative side-car of one :class:`ServeEngine`: draft
    params + paged KV pool + the propose/verify/accept round that
    replaces ``_decode_once`` when ``ServeConfig.draft`` is set.

    Owns no scheduling: the engine still admits, prefills, retires and
    exposes results exactly as before — only the decode iteration is
    swapped, which is why every engine invariant (handoff, drain
    export, backpressure) composes with speculation unchanged.
    """

    def __init__(self, engine):
        import jax

        from horovod_tpu.models import init_transformer
        from horovod_tpu.serve import decode as decode_lib
        from horovod_tpu.serve.kv_cache import init_kv_cache

        self._eng = engine
        cfg = engine.cfg
        draft: DraftConfig = cfg.draft
        dm = draft.model_cfg
        tm = engine.model_cfg
        if dm.vocab_size != tm.vocab_size:
            raise ValueError(
                f"draft vocab {dm.vocab_size} != target vocab "
                f"{tm.vocab_size} — proposals are token ids and must "
                "share one vocabulary")
        self.k = cfg.spec_k
        self._params = init_transformer(dm, jax.random.PRNGKey(draft.seed))
        bs = cfg.block_size
        self._bs = bs
        self._width = engine._table_width
        # Mirror pool sized for the draft's OWN worst case: it only
        # ever holds ACTIVE (batch-slotted) sequences' full private
        # reservations — at most max_batch x table_width blocks — so
        # any batch the target admitted is reservable here by
        # construction. Mirroring the target's n_blocks instead would
        # break under prefix caching: the target's pool admits batches
        # whose shared prefix blocks are refcounted once, while the
        # draft (no content index) pays every sequence's full width.
        n_blocks = cfg.max_batch * self._width + 1
        self.allocator = BlockAllocator(n_blocks, bs)
        self.cache = init_kv_cache(dm, n_blocks, bs, mesh=engine.mesh,
                                   dtype=draft.cache_dtype)
        # The draft shares the make_serve_fns memo: a draft configured
        # with the target's own model config (the all-accept rig)
        # reuses the target's compiled programs outright.
        (self._prefill_fn, self._resume_fn, self._decode_fn, _inject,
         _verify) = decode_lib.make_serve_fns(
             dm, engine.mesh, block_size=bs, table_width=self._width,
             compression=cfg.compression)
        self._seqs: Dict[int, _DraftSeq] = {}

    # -- per-sequence lifecycle ---------------------------------------

    def drop(self, rid: int) -> None:
        """Release the draft-side reservation of a retired, exported,
        or migrated sequence. No-op for sequences the draft never saw
        (prefill-only parks, withdrawn queue entries)."""
        st = self._seqs.pop(rid, None)
        if st is not None:
            self.allocator.free(st.blocks)

    def _ensure(self, seq) -> _DraftSeq:
        """Draft-side catch-up for a sequence the draft has no state
        for (fresh prefill completion, or a handoff/migration injected
        mid-decode): reserve mirror blocks and prefill the draft's KV
        for every position the target has cached — the full stream
        ``(prompt + generated)[:n_cached]``, chunked block-aligned
        through the engine's prefill buckets. The draft always
        prefills the whole stream itself (the target's prefix cache
        may have skipped prompt FLOPs the draft never ran)."""
        st = self._seqs.get(seq.rid)
        if st is not None:
            return st
        need = self.allocator.blocks_for_tokens(
            len(seq.prompt) + seq.max_new)
        blocks = self.allocator.alloc(need)
        table = np.zeros(self._width, np.int32)
        table[:len(blocks)] = blocks
        st = _DraftSeq(blocks=blocks, table=table)
        self._seqs[seq.rid] = st
        stream = (list(seq.prompt) + list(seq.generated))[:seq.n_cached]
        buckets = self._eng._prefill_buckets
        chunk_cap = max(buckets)
        off = 0
        while off < len(stream):
            chunk = min(len(stream) - off, chunk_cap)
            if off + chunk < len(stream):
                chunk -= chunk % self._bs   # non-final chunks stay
                #                             block-aligned for resume
            toks = np.zeros(pick_bucket(chunk, buckets), np.int32)
            toks[:chunk] = stream[off:off + chunk]
            kc, vc, _tok = self._resume_fn(
                self._params, self.cache.k, self.cache.v, toks,
                np.int32(off), np.int32(chunk), st.table)
            self.cache.k, self.cache.v = kc, vc
            off += chunk
        return st

    # -- the round ----------------------------------------------------

    def round(self) -> None:
        """One speculative iteration for the engine's active batch:
        k batched draft decode steps propose, one target verify step
        checks, host-side acceptance emits 1..k tokens per sequence
        and rewinds past rejected positions (cursor-only rollback)."""
        eng = self._eng
        active = eng._active
        if not active:
            return
        n = len(active)
        bucket = pick_bucket(n, eng._batch_buckets)
        states = [self._ensure(s) for s in active]

        # -- propose: k draft decode steps, batched over the batch ----
        d_tables = np.zeros((bucket, self._width), np.int32)
        for i, st in enumerate(states):
            d_tables[i] = st.table
        frontier = np.zeros(bucket, np.int32)
        positions = np.zeros(bucket, np.int32)
        for i, seq in enumerate(active):
            frontier[i] = seq.last_token
            positions[i] = seq.n_cached
        proposals = np.zeros((n, self.k), np.int64)
        traces = [s.trace for s in active if s.trace]
        extra = {"traces": traces} if traces else {}
        m = eng.metrics
        with m.phase("serve:spec_draft", device=True, n_active=n,
                     **extra) as draft:
            for step in range(self.k):
                with draft.dispatch():
                    kc, vc, out = self._decode_fn(
                        self._params, self.cache.k, self.cache.v, frontier,
                        positions, d_tables)
                out = draft.read(out, np.asarray)
                self.cache.k, self.cache.v = kc, vc
                proposals[:, step] = out[:n]
                frontier = out.copy()
                positions = positions + 1

        # -- verify: ONE chunked target step over reserved pages ------
        chunk = np.zeros((bucket, self.k), np.int32)
        vpos = np.zeros(bucket, np.int32)
        t_tables = np.zeros((bucket, self._width), np.int32)
        for i, seq in enumerate(active):
            chunk[i, 0] = seq.last_token
            chunk[i, 1:] = proposals[i, :self.k - 1]
            vpos[i] = seq.n_cached
            t_tables[i] = seq.table
        with m.phase("serve:spec_verify", device=True, **extra) as verify:
            with verify.dispatch():
                kc, vc, ver = eng._verify_fn(
                    eng._params, eng.cache.k, eng.cache.v, chunk, vpos,
                    t_tables)
            ver = verify.read(ver, np.asarray)
        eng.cache.k, eng.cache.v = kc, vc

        # -- accept + cursor rollback, host-side ----------------------
        emitted_total = 0
        accepted_total = 0
        proposed_total = 0
        for i, seq in enumerate(active):
            n_acc, emitted = accept_greedy(
                [int(t) for t in proposals[i]],
                [int(t) for t in ver[i]])
            # Plain decode stops at max_new and at the FIRST eos —
            # truncate to match it token-for-token.
            room = seq.max_new - len(seq.generated)
            emitted = emitted[:room]
            if eng.cfg.eos_id is not None and eng.cfg.eos_id in emitted:
                emitted = emitted[:emitted.index(eng.cfg.eos_id) + 1]
            n_acc = min(n_acc, len(emitted))
            # Accept-rate denominator: proposals the target actually
            # JUDGED before the stream ended — the accepted prefix
            # plus, if the round ended on a mismatch that was emitted,
            # the one judged-wrong proposal. Proposals past the
            # max_new cap or past an accepted eos were never
            # emittable: scheduling artifacts, not draft
            # disagreement, and they must not deflate the rate a real
            # draft is judged by.
            proposed_total += n_acc + (1 if n_acc < len(emitted) else 0)
            seq.generated.extend(emitted)
            seq.token_times.extend([verify.end] * len(emitted))
            # The rollback: rejected chunk positions stay past the
            # cursor; table and pool untouched.
            seq.n_cached += len(emitted)
            emitted_total += len(emitted)
            accepted_total += n_acc
        draft.args["proposed"] = proposed_total
        verify.args.update(accepted=accepted_total, emitted=emitted_total)
        m.record_spec_round(
            draft.dur, verify.dur, n, eng.cfg.max_batch,
            proposed=proposed_total, accepted=accepted_total,
            emitted=emitted_total)


# ---------------------------------------------------------------------------
# Example/test rig: a target that agrees with its draft by construction
# ---------------------------------------------------------------------------

def make_draft_target_params(draft_cfg, n_layers: int, seed: int = 0,
                             extra_seed: int = 1):
    """Build ``(target_cfg, target_params)`` such that the target is
    ``n_layers`` deep but computes **exactly** the logits of the
    ``draft_cfg`` model initialized from ``seed``: the first
    ``draft_cfg.n_layers`` layers (plus embed / final norm / lm head)
    are the draft's own params, and every extra layer's residual
    out-projections (``wo``, ``w_down``) are zero — a residual layer
    that contributes nothing but costs its full matmuls.

    This is the speculative benchmark's idealized pair: the draft
    agrees with the target at every position (accept rate 1.0), so
    the measured speedup isolates what speculation buys *per accepted
    token* — weights stream once per k tokens — from model-quality
    effects. Real drafts scale the win by their measured accept rate
    (reported alongside). The pair is deterministic from ``seed``, so
    an engine configured with ``DraftConfig(draft_cfg, seed=seed)``
    rebuilds the matching draft by construction."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import init_transformer

    if n_layers <= draft_cfg.n_layers:
        raise ValueError(
            f"target n_layers {n_layers} must exceed the draft's "
            f"{draft_cfg.n_layers}")
    target_cfg = dataclasses.replace(draft_cfg, n_layers=n_layers)
    draft_params = init_transformer(draft_cfg, jax.random.PRNGKey(seed))
    target_params = init_transformer(target_cfg,
                                     jax.random.PRNGKey(extra_seed))
    Ld = draft_cfg.n_layers
    layers = {}
    for name, extra_leaf in target_params["layers"].items():
        leaf = jnp.asarray(extra_leaf)
        leaf = leaf.at[:Ld].set(draft_params["layers"][name])
        if name in ("wo", "w_down"):
            leaf = leaf.at[Ld:].set(0)
        layers[name] = leaf
    out = dict(target_params)
    out["layers"] = layers
    for name in ("embed", "final_norm", "lm_head"):
        out[name] = draft_params[name]
    return target_cfg, out
