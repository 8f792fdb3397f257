"""Request generators: deterministic traces for the examples and tests.

Each maker returns ``[(prompt_tokens, max_new_tokens), ...]`` drawn from
a ``numpy.random.RandomState(seed)``: the same arguments give the same
trace.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def make_trace(n_requests: int = 40, *, seed: int = 0,
               min_prompt: int = 4, max_prompt: int = 32,
               min_new: int = 8, max_new: int = 64,
               vocab: int = 256) -> List[Tuple[List[int], int]]:
    """Deterministic mixed-length request trace:
    ``[(prompt_tokens, max_new_tokens), ...]``."""
    rng = np.random.RandomState(seed)
    # Callers shrink max_* freely (e.g. a tiny-model demo); the lower
    # bounds follow rather than erroring on an empty range.
    min_prompt = min(min_prompt, max_prompt)
    min_new = min(min_new, max_new)
    trace = []
    for _ in range(n_requests):
        plen = int(rng.randint(min_prompt, max_prompt + 1))
        nnew = int(rng.randint(min_new, max_new + 1))
        prompt = rng.randint(1, vocab, size=plen).astype(np.int32).tolist()
        trace.append((prompt, nnew))
    return trace


def make_shared_prefix_trace(n_requests: int = 32, *, seed: int = 0,
                             prefix_len: int = 64, min_suffix: int = 4,
                             max_suffix: int = 12, min_new: int = 4,
                             max_new: int = 8, vocab: int = 256,
                             ) -> List[Tuple[List[int], int]]:
    """Deterministic multi-tenant-style trace: every request shares one
    ``prefix_len``-token system prompt and appends a short unique
    suffix — the regime where block-level prefix reuse pays (thousands
    of requests, one shared preamble)."""
    rng = np.random.RandomState(seed)
    prefix = rng.randint(1, vocab, size=prefix_len).astype(np.int32).tolist()
    trace = []
    for _ in range(n_requests):
        slen = int(rng.randint(min_suffix, max_suffix + 1))
        nnew = int(rng.randint(min_new, max_new + 1))
        suffix = rng.randint(1, vocab, size=slen).astype(np.int32).tolist()
        trace.append((prefix + suffix, nnew))
    return trace


def make_multi_tenant_trace(n_requests: int = 48, *, seed: int = 0,
                            n_tenants: int = 8, prefix_len: int = 32,
                            min_suffix: int = 2, max_suffix: int = 8,
                            min_new: int = 2, max_new: int = 4,
                            vocab: int = 256,
                            ) -> List[Tuple[List[int], int]]:
    """Deterministic fleet-routing trace: ``n_tenants`` distinct
    system prompts, requests interleaved across tenants. This is the
    regime where PLACEMENT (not just caching) decides the hit rate:
    affinity keeps each tenant's prefix hot on one replica, while
    random placement re-prefills it on every replica it scatters to."""
    rng = np.random.RandomState(seed)
    prefixes = [rng.randint(1, vocab, size=prefix_len).astype(
        np.int32).tolist() for _ in range(n_tenants)]
    trace = []
    for _ in range(n_requests):
        t = int(rng.randint(n_tenants))
        slen = int(rng.randint(min_suffix, max_suffix + 1))
        nnew = int(rng.randint(min_new, max_new + 1))
        suffix = rng.randint(1, vocab, size=slen).astype(np.int32).tolist()
        trace.append((prefixes[t] + suffix, nnew))
    return trace
