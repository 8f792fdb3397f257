"""Request lengths as a fixed multiset, and arrival gaps likewise.

A traffic file names a distribution and its range. The generator takes
its quantiles, stratified, as many as the list is long, deals them into
balanced blocks of the engine's ``max_batch`` and offers the blocks in
one fixed order: every seed meets the same schedule of
``(prompt_len, output_len)`` pairs and of arrival gaps, and decides the
token ids and the weights alone. Two seeds then do the same work.

Why one order: the engine's schedule is a function of the order of the
lengths alone (admission is FIFO, lengths decide when a slot frees), and
on the chip an order of the seed's moved a 40 s rate by 1.2 to 3 % and a mean
token gap by 5 % from seed to seed, while one seed repeated to 0.2 %
(PERF.md, PR 23).
"""

from __future__ import annotations

import itertools
import math
from typing import Any, Dict, List, Tuple

import numpy as np


def quantile(dist: Dict[str, Any], u: float) -> float:
    """The ``u`` quantile of a distribution of a traffic file.

    ``uniform``: between ``min`` and ``max``. ``loguniform``: uniform in
    the logarithm; with ``median``, ``u`` is warped by a power so that
    the median falls there. ``exponential``: of mean ``mean``."""
    kind = dist["dist"]
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    if kind == "loguniform":
        span = math.log(dist["max"] / dist["min"])
        if "median" in dist:
            u = u ** (math.log(math.log(dist["median"] / dist["min"]) / span)
                      / math.log(0.5))
        return dist["min"] * math.exp(u * span)
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-u)
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: Dict[str, Any], n: int) -> List[float]:
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def balanced_deal(values: List[Any], n_blocks: int) -> List[List[Any]]:
    """Deal ``values`` into ``n_blocks`` blocks of equal size and nearly
    equal sums: largest first, each to the block with the smallest sum
    that still has room. Every block then holds values from across the
    whole range, the long tail of an exponential included."""
    size = len(values) // n_blocks
    blocks: List[List[Any]] = [[] for _ in range(n_blocks)]
    sums = [0.0] * n_blocks
    for v in sorted(values, reverse=True):
        b = min((i for i in range(n_blocks) if len(blocks[i]) < size),
                key=lambda i: (sums[i], i))
        blocks[b].append(v)
        sums[b] += v
    return blocks


def length_blocks(traffic: Dict[str, Any]) -> List[List[Tuple[int, int]]]:
    """The multiset of ``(prompt_len, output_len)`` in balanced blocks
    of the engine's ``max_batch`` pairs, the same for every seed, each
    block in one fixed order.

    A window holds some of the list, never all of it, and a rate depends
    on what it holds: short answers cost more prefill a token. So the
    list is cut into blocks that each hold prompts and outputs of
    every part of the range (:func:`balanced_deal`), and any run of consecutive
    requests is then close to the whole list's mix. Within a block,
    prompts and outputs are paired by one fixed permutation, so the two
    lengths are uncorrelated."""
    n, size = traffic["n_lengths"], traffic["engine"]["max_batch"]
    if n % size:
        raise ValueError(f"n_lengths {n} is not a multiple of max_batch "
                         f"{size}")
    prompts = balanced_deal([int(round(x)) for x in
                          stratified(traffic["prompt_len"], n)], n // size)
    outputs = balanced_deal([int(round(x)) for x in
                          stratified(traffic["output_len"], n)], n // size)
    rng = np.random.default_rng(0)
    return [[(p[int(i)], o[int(j)]) for i, j in
             zip(rng.permutation(size), rng.permutation(size))]
            for p, o in zip(prompts, outputs)]


def length_pairs(traffic: Dict[str, Any]) -> List[Tuple[int, int]]:
    return [pair for block in length_blocks(traffic) for pair in block]


def request_stream(traffic: Dict[str, Any], seed: int, vocab: int):
    """Endless ``(prompt tokens, output_len)``: the list over and over.
    Token ids are the seed's; with a vocabulary of tens of thousands no
    two prompts share a block of the cache, so the prefix cache has
    nothing to hit."""
    rng = np.random.default_rng([seed, 1])
    for n_prompt, n_out in itertools.cycle(length_pairs(traffic)):
        yield rng.integers(0, vocab, n_prompt).tolist(), n_out


def arrival_gaps(traffic: Dict[str, Any]):
    """Endless gaps between arrivals, in seconds: the stratified
    quantiles of an exponential of mean ``1 / rate_per_s`` (a Poisson
    process's gaps), as many as ``n_lengths``, in balanced blocks, each
    in one fixed shuffled order. Short and long gaps still follow each
    other at random, so arrivals bunch as a Poisson process's do, while
    every block spans nearly the same time."""
    n, size = traffic["n_lengths"], traffic["engine"]["max_batch"]
    gaps = stratified({"dist": "exponential",
                       "mean": 1.0 / traffic["rate_per_s"]}, n)
    rng = np.random.default_rng([0, 2])
    return itertools.cycle(
        b[int(i)] for b in balanced_deal(gaps, n // size)
        for i in rng.permutation(size))
