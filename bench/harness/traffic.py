"""Seeded request generator, read from a traffic file (``bench/traffic``).

Every seed gets the same multiset of prompt lengths, output lengths and
(Poisson) inter-arrival gaps, in a seed-dependent order, so runs with
different seeds do the same work. Prompt lengths come from a small
declared set: the program compiles one prefill per prompt length, and
every length is warmed before the window. Output lengths are drawn as
stratified quantiles of the declared distribution, rounded to a grid
(``step``), which bounds the number of distinct slice shapes the serve
loop reads back.

Arrival processes:

* ``poisson``: ``round(rate * seconds)`` requests; their gaps are the
  stratified quantiles of an exponential with mean ``1 / rate``, scaled so
  they sum to ``seconds``. Each request carries its due time as an offset
  in wall-clock seconds from the window's start.
* ``backlog``: an endless supply of requests, all due as soon as they
  are handed over (the driver keeps the queue full); blocks of ``block``
  requests each hold the same multiset.

Prompts are random token ids, and no two prompts of a run begin with the
same token (first tokens are a seeded permutation of the vocabulary above
``WARM_TOKENS``, which the warm-up's prompts use). The paged pool's prefix
cache matches prompts token by token, so a shared first token would send
an admission down its prefix-hit path; these mixes are distinct prompts
and bypass the prefix cache.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterator, Optional

import numpy as np

WARM_TOKENS = 16


@dataclasses.dataclass
class Item:
    rid: int
    prompt: np.ndarray        # (P,) int32 token ids
    max_new: int              # tokens to generate
    offset_s: Optional[float]  # due time after the window opens; None: backlog


def _counts(weights: list, n: int) -> list:
    """Largest-remainder split of ``n`` items over ``weights``."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    raw = w * n
    base = np.floor(raw).astype(int)
    rest = n - int(base.sum())
    order = np.argsort(-(raw - base), kind="stable")
    base[order[:rest]] += 1
    return [int(c) for c in base]


def prompt_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lengths = [int(p) for p, _ in spec["prompt_lengths"]]
    counts = _counts([w for _, w in spec["prompt_lengths"]], n)
    out = np.repeat(lengths, counts)
    return rng.permutation(out)


def _quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    kind = dist["dist"]
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    if kind == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        return dist["median"] * np.exp(dist["sigma"] * z)
    raise ValueError(f"unknown output distribution {kind!r}")


def output_levels(dist: dict) -> list:
    """Every output length the distribution can yield."""
    step = int(dist["step"])
    lo = int(math.ceil(dist["min"] / step)) * step
    hi = int(dist["max"]) // step * step
    return list(range(lo, hi + 1, step))


def output_lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    x = _quantile(dist, u)
    step = int(dist["step"])
    x = np.clip(np.round(x / step) * step, min(output_levels(dist)),
                max(output_levels(dist)))
    return rng.permutation(x.astype(int))


def poisson_offsets(rate: float, seconds: float, n: int,
                    rng: np.random.Generator) -> np.ndarray:
    """Due offsets of ``n`` arrivals: exponential-quantile gaps in a
    seeded order, scaled to a mean of exactly ``seconds / n``. The first
    request is due when the window opens, the last one gap before it
    closes."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps = rng.permutation(gaps * (seconds / n) / gaps.mean())
    return np.cumsum(gaps) - gaps


def _prompt(rng: np.random.Generator, first: int, length: int,
            vocab: int) -> np.ndarray:
    p = rng.integers(0, vocab, length, dtype=np.int32)
    p[0] = first
    return p


def warm_prompt(i: int, length: int, vocab: int) -> np.ndarray:
    """The warm-up's ``i``-th prompt, which no prompt of a run shares a
    first token with."""
    return _prompt(np.random.default_rng(i), i % WARM_TOKENS, length, vocab)


def num_requests(spec: dict, seconds: float) -> int:
    return max(1, int(round(spec["rate"] * seconds)))


def generate(spec: dict, seed: int, vocab: int, seconds: float
             ) -> Iterator[Item]:
    """Requests of the mix, in order. Finite for ``poisson``, endless for
    ``backlog``."""
    rng = np.random.default_rng(seed)
    firsts = WARM_TOKENS + rng.permutation(vocab - WARM_TOKENS)
    rid = 0
    if spec["arrival"] == "poisson":
        n = num_requests(spec, seconds)
        offs = poisson_offsets(spec["rate"], seconds, n, rng)
        plen = prompt_lengths(spec, n, rng)
        outs = output_lengths(spec["output"], n, rng)
        for i in range(n):
            yield Item(rid=i, prompt=_prompt(rng, firsts[i % len(firsts)],
                                             plen[i], vocab),
                       max_new=int(outs[i]), offset_s=float(offs[i]))
        return
    if spec["arrival"] != "backlog":
        raise ValueError(f"unknown arrival process {spec['arrival']!r}")
    block = int(spec["block"])
    while True:
        plen = prompt_lengths(spec, block, rng)
        outs = output_lengths(spec["output"], block, rng)
        for i in range(block):
            yield Item(rid=rid, prompt=_prompt(rng, firsts[rid % len(firsts)],
                                               plen[i], vocab),
                       max_new=int(outs[i]), offset_s=None)
            rid += 1


def shapes(spec: dict) -> list:
    """Every (prompt length, output length) pair the mix can produce."""
    return [(int(p), g) for p, _ in spec["prompt_lengths"]
            for g in output_levels(spec["output"])]
