"""The one traffic generator: reads a mix file of ``traffic/`` and a rate.

Arrival processes are copies of the repository's MAF2 surrogate (bursty,
lognormal rate levels held for a period, Poisson within a level) and of its
homogeneous Poisson process. Lengths are lognormal, prompts rounded up to
the mix's buckets (one prefill program per bucket) and answers clipped.

Every seed gets the same work. The schedule (arrival times and each
request's prompt and answer lengths) is drawn from the mix's ``base_seed``
and the rate; the run's seed draws the prompt tokens (and, elsewhere, the
weights and the BE batches). Runs with different seeds then differ in what
the tokens are, not in how much work there is or when it crowds, and the
spread of a cell's runs is the system's own.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import List

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due_s: float            # seconds after the window opens
    prompt_len: int
    max_new_tokens: int


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(w) for w in words]))


def maf2_like_arrivals(rng, seconds: float, mean_rate: float,
                       burstiness: float, level_period: float) -> np.ndarray:
    """Bursty serverless-style arrivals: lognormal rate levels held for
    ``level_period`` seconds, Poisson counts within a level; ``burstiness``
    is about the peak-to-mean rate ratio."""
    n_levels = int(np.ceil(seconds / level_period))
    sigma = np.log(max(burstiness, 1.001)) / 2.0
    levels = rng.lognormal(mean=-0.5 * sigma ** 2, sigma=sigma, size=n_levels)
    levels *= mean_rate / max(levels.mean(), 1e-12)
    chunks: List[np.ndarray] = []
    for i, lam in enumerate(levels):
        n = rng.poisson(lam * level_period)
        chunks.append(i * level_period
                      + rng.uniform(0.0, level_period, size=n))
    arr = np.sort(np.concatenate(chunks)) if chunks else np.empty(0)
    return arr[arr < seconds]


def poisson_arrivals(rng, seconds: float, rate: float) -> np.ndarray:
    """Homogeneous Poisson arrivals at ``rate`` per second."""
    n = rng.poisson(rate * seconds)
    return np.sort(rng.uniform(0.0, seconds, size=n))


def _lengths(rng, spec: dict, n: int, buckets=None) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    if buckets:
        b = np.asarray(sorted(buckets))
        x = b[np.minimum(np.searchsorted(b, np.ceil(x)), len(b) - 1)]
    else:
        x = np.clip(np.round(x), spec["min"], spec["max"])
    return x.astype(np.int64)


def generate(mix: dict, rate: float, seconds: float) -> List[Arrival]:
    """The arrivals due in a window of ``seconds`` at mean ``rate`` per
    second, sorted by due time; the same for every seed."""
    rng = _rng(mix.get("base_seed", 0), int(round(rate * 1000)),
               int(round(seconds * 1000)))
    if mix["arrivals"] == "maf2_like":
        due = maf2_like_arrivals(rng, seconds, rate, mix["burstiness"],
                                 mix["level_period_s"])
    elif mix["arrivals"] == "poisson":
        due = poisson_arrivals(rng, seconds, rate)
    else:
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    n = len(due)
    prompts = _lengths(rng, mix["prompt"], n, mix["prompt"]["buckets"])
    outputs = _lengths(rng, mix["output"], n)
    return [Arrival(float(t), int(p), int(o))
            for t, p, o in zip(due, prompts, outputs)]


def prompt_tokens(seed: int, index: int, length: int, vocab: int,
                  tag: int = 2) -> np.ndarray:
    """Prompt ``index`` of a run: uniform token ids, the same for a seed
    (warm-up prompts use another ``tag``)."""
    return _rng(seed, tag, index).integers(0, vocab, size=length,
                                           dtype=np.int32)


@lru_cache(maxsize=4)
def _unigram(seed: int, vocab: int):
    """The BE data's Zipf-like unigram CDF over ranks, and the seed's
    permutation from rank to token id."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -1.3
    return np.cumsum(p / p.sum()), _rng(seed, 4).permutation(vocab)


def be_batch(seed: int, step: int, batch: int, seq_len: int, vocab: int
             ) -> dict:
    """Best-effort batch ``step``: every row differs. Tokens follow a
    Zipf-like unigram law with short-range repeats, so the loss can fall."""
    rng = _rng(seed, 3, step)
    n = seq_len + 1
    cdf, perm = _unigram(int(seed), vocab)
    u = rng.random((batch, n))
    toks = perm[np.minimum(np.searchsorted(cdf, u), vocab - 1)]
    back = rng.integers(1, 9, size=(batch, n))
    src = np.maximum(np.arange(n)[None, :] - back, 0)
    rep = rng.random((batch, n)) < 0.35
    toks = np.where(rep, np.take_along_axis(toks, src, axis=1), toks)
    toks = toks.astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
