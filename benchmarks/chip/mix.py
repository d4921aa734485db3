"""The one traffic generator: every mix is parameters in
``traffic/<name>.json`` read here.

Every seed gets the same sizes and gaps (quantiles of the stated
distributions) in the same order: a replayed schedule, since a tail of a
few dozen requests hangs on which short answer meets which long
prefill.  The run's seed draws only token ids (and, in the harness, the
weights).  The order is stratified: each run of ``STRATUM`` consecutive
requests holds one size (and one gap) from each of ``STRATUM`` equal
bands of the distribution.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_of(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), *stream]))


#: Requests per stratified run, and the seed of the replayed order.
STRATUM = 8
ORDER_SEED = 0


def _u(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified(values: np.ndarray, stratum: int,
               rng: np.random.Generator) -> np.ndarray:
    """``values`` (sorted) reordered so that every ``stratum`` consecutive
    entries take one value from each of ``stratum`` equal bands, the
    choice within a band and the order within a run drawn from ``rng``."""
    n = len(values)
    bands = np.array_split(np.arange(n), stratum)
    picks = [rng.permutation(b) for b in bands]
    out = []
    for j in range(max(len(b) for b in bands)):
        run = [p[j] for p in picks if j < len(p)]
        out += [run[i] for i in rng.permutation(len(run))]
    return values[np.asarray(out)]


def lognormal(dist: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the quantiles of a log-normal with the given
    ``median`` and ``sigma``, clipped to ``[min, max]``, ascending."""
    z = np.array([NormalDist().inv_cdf(u) for u in _u(n)])
    x = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def requests(mix: dict, n: int, seed: int, vocab: int, block: int = 0
             ) -> list[dict]:
    """``n`` requests of the mix: prompt ids and output lengths.  Blocks
    of one seed hold the same sizes in other orders."""
    r_len = rng_of(ORDER_SEED, 1, block)
    r_tok = rng_of(seed, 2, block)
    prompts = stratified(lognormal(mix["prompt"], n), STRATUM, r_len)
    outs = stratified(lognormal(mix["output"], n), STRATUM, r_len)
    return [{"prompt": r_tok.integers(0, vocab, int(p)).astype(np.int32),
             "max_new": int(o)} for p, o in zip(prompts, outs)]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int
              ) -> list[dict]:
    """Requests with due times (seconds from the start of arrivals) over
    the mix's pre-roll and the window: Poisson arrivals at ``rate``, the
    gaps at the quantiles of the exponential, stratified."""
    span = mix["preroll_s"] + seconds
    n = math.ceil(mix["rate"] * span)
    gaps = -np.log1p(-_u(n)) / mix["rate"]
    due = np.cumsum(stratified(gaps, STRATUM, rng_of(ORDER_SEED, 0)))
    reqs = requests(mix, n, seed, vocab)
    for r, d in zip(reqs, due):
        r["due"] = float(d)
    return reqs


def max_positions(mix: dict) -> tuple[int, int]:
    return mix["prompt"]["max"], mix["output"]["max"]
