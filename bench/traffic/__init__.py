"""One general traffic generator; each mix is a data file beside it.

A mix (``bench/traffic/<mix>.json``) gives the loop (``open``: arrivals
at ``rate_per_s`` with exponential gaps, stratified as below; ``closed``:
``clients`` callers that each send their next request as soon as the last
one finished, taking a ``pool`` of requests in order), the prompt and
output length distributions, and the run's phases (``warm_s`` of traffic
before the window, ``tail_s`` at most after it, ``trace_s`` traced at the
window's end).

Every seed gets the same multisets of prompt lengths, output lengths and
inter-arrival gaps -- the distribution's quantiles at ``(i + 0.5) / n`` --
in an order drawn from the seed, and its own token ids.  So two seeds
offer the same work in another order, and the spread between runs is the
system's, not the draw's.  The open loop is therefore not a Poisson
process: each phase holds a fixed count of arrivals, the first due at the
phase's start, and the count variance of real Poisson traffic is left
out.  A closed loop's window serves a stretch of consecutive requests of
the pool, not all of it, so the pool is made of blocks of ``block``
requests (default: the whole pool), each the quantile multiset at
``block`` points, permuted on its own: any stretch holds whole blocks of
the same work and at most two partial ones.  The draw order is fixed, as
in ``repro.scenarios.traffic``: the permutations of each block, then each
request's tokens in order.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

MIXES = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request of the schedule; ``at_s`` is its open-loop due time in
    seconds after traffic starts (closed loop: unused)."""

    index: int
    at_s: float
    prompt: np.ndarray
    max_new: int


def load(name: str) -> dict:
    path = MIXES / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix {path}")
    return json.loads(path.read_text())


def quantile(dist: dict, q: np.ndarray) -> np.ndarray:
    """Whole-number lengths at quantiles ``q`` of a length distribution."""
    lo, hi = dist["lo"], dist["hi"]
    if dist["dist"] == "uniform":
        x = lo + np.floor(q * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def _block(mix: dict, n: int, rng: np.random.Generator):
    """Prompt lengths, output lengths and exponential gaps of ``n``
    requests: each the distribution's quantile multiset, permuted."""
    q = (np.arange(n) + 0.5) / n
    plen = quantile(mix["prompt"], q)[rng.permutation(n)]
    olen = quantile(mix["output"], q)[rng.permutation(n)]
    gaps = -np.log1p(-q)[rng.permutation(n)]
    return plen, olen, gaps


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> List[Spec]:
    """The run's requests in order.  Open loop: one block for each phase
    (warm-up, window, tail), each holding ``rate x phase`` requests whose
    gaps are scaled to span the phase exactly, so every seed offers the
    window the same sizes and the same number of arrivals.  Closed loop:
    ``pool`` requests in blocks of ``block``, taken in order by whichever
    client is free."""
    rng = np.random.default_rng(seed)
    plens, olens, ats = [], [], []
    if mix["loop"] == "open":
        start = 0.0
        for span in (mix["warm_s"], seconds, mix["tail_s"]):
            n = max(1, math.ceil(mix["rate_per_s"] * span))
            plen, olen, gaps = _block(mix, n, rng)
            ats.extend(start + span * (np.cumsum(gaps) - gaps) / gaps.sum())
            plens.extend(plen)
            olens.extend(olen)
            start += span
    elif mix["loop"] == "closed":
        k = mix.get("block", mix["pool"])
        if mix["pool"] % k:
            raise ValueError(f"pool {mix['pool']} is not a multiple of "
                             f"block {k}")
        for _ in range(mix["pool"] // k):
            plen, olen, _ = _block(mix, k, rng)
            plens.extend(plen)
            olens.extend(olen)
        ats = [0.0] * mix["pool"]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return [
        Spec(i, float(ats[i]),
             rng.integers(0, vocab, int(plens[i])).astype(np.int32),
             int(olens[i]))
        for i in range(len(ats))
    ]
