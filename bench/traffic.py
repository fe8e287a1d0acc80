"""The one generator of serving traffic; each mix is a data file under
``bench/traffic/``.

Adapted from ``repro.serve.loadgen`` (seeded open/closed-loop sources) with
two changes that make runs of one cell comparable across seeds:

  * the schedule is fixed by the mix: prompt lengths, output lengths and
    Poisson gaps are the stratified quantiles ``F^-1((i + 1/2) / n)`` of
    the mix's distributions, put in one order drawn from the mix's
    ``order_seed``.  Every run serves the same sizes at the same times: with
    a few dozen requests in a window, a p95 otherwise follows the order in
    which one seed happens to bunch the long prompts;
  * the run's seed draws the prompt tokens, from a seeded copy of the
    repo's ``SyntheticCorpus`` (zipf unigrams, markov bigrams, a copied
    recall span) per request from ``(seed, index)``, and the weights.

A mix file holds ``driver`` (``serve_open`` | ``serve_closed``),
``order_seed``, the prompt and output length distributions
(``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
``{"dist": "uniform", "min", "max"}``), and for open loop ``rate_rps``; for
closed loop ``clients`` and ``pool``.
"""

from __future__ import annotations

import math
from typing import List, NamedTuple

import numpy as np

VOCAB_RESERVED = 2       # ids 0 (pad) and 1 (BOS) as in SyntheticCorpus


class Request(NamedTuple):
    t: float             # scheduled arrival, seconds after the window opens
    prompt: np.ndarray   # int32 token ids
    max_new: int


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """Stratified quantiles of a length distribution, as whole numbers."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if spec["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
        return np.clip(np.round(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def corpus_tokens(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    """``n`` tokens in the manner of ``repro.data.pipeline.SyntheticCorpus``
    (copied here so the benchmark owns its inputs)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), index]))
    ranks = rng.zipf(1.3, size=n)
    toks = (ranks % (vocab - VOCAB_RESERVED)) + VOCAB_RESERVED
    follow = (np.arange(vocab) * 2654435761 % (vocab - VOCAB_RESERVED)) \
        + VOCAB_RESERVED
    chain = rng.random(n) < 0.3
    toks[1:] = np.where(chain[1:], follow[toks[:-1]], toks[1:])
    if n > 64:
        span = max(8, int(n * 0.15 / 2))
        src = rng.integers(0, n - 2 * span)
        dst = rng.integers(src + span, n - span)
        toks[dst:dst + span] = toks[src:src + span]
    toks[0] = 1
    return toks.astype(np.int32)


def n_requests(mix: dict, seconds: float) -> int:
    if mix["driver"] == "serve_open":
        return max(1, int(round(float(mix["rate_rps"]) * seconds)))
    return int(mix["pool"])


def requests(mix: dict, seed: int, seconds: float, vocab: int,
             rate_rps: float | None = None) -> List[Request]:
    """The mix's requests for one run.  Open loop: arrival times in
    ``[0, seconds)`` at ``rate_rps`` (default: the mix's).  Closed loop:
    ``t = 0`` for all; the driver submits them as clients free up."""
    rng = np.random.default_rng(
        np.random.SeedSequence([int(mix.get("order_seed", 0)), 7]))
    if rate_rps is not None:
        mix = dict(mix, rate_rps=rate_rps)
    n = n_requests(mix, seconds)
    plen = rng.permutation(_quantiles(mix["prompt"], n))
    olen = rng.permutation(_quantiles(mix["output"], n))
    if mix["driver"] == "serve_open":
        rate = float(mix["rate_rps"])
        u = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-u) / rate)
        t = np.cumsum(gaps)
        t = t * ((seconds - 0.5 / rate) / t[-1]) if n > 1 else t * 0.0
    else:
        t = np.zeros(n)
    return [Request(float(t[i]), corpus_tokens(seed, i, int(plen[i]), vocab),
                    int(olen[i])) for i in range(n)]


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by the nearest-rank rule; ``inf``
    entries (failed requests) sort last, so they count as late."""
    v = sorted(values)
    if not v:
        return math.nan
    rank = max(1, math.ceil(q / 100.0 * len(v)))
    return float(v[rank - 1])
