"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` (overridden per cell by the cell file's
``traffic`` object) and turns them into requests from ``--seed``.

Every seed gets the same multiset of lengths and arrival gaps, in another
order: lengths are the distribution's quantiles at (i + 0.5) / n, gaps the
exponential's, and the seed permutes them and draws the token ids.  So two
seeds do the same amount of work, and the spread between seeds is that of
the system, not of the draw.

Parameters:
  n_requests            requests when arrivals are all due at t=0
  arrival               "at_once" (every request due at 0) or "poisson"
  rate_per_s            poisson: mean arrivals per second; the mix then has
                        ceil(rate * seconds) requests, and those due at or
                        after the window's end are dropped
  prompt, output        {"dist": "lognormal", "median", "sigma", "min",
                        "max"} or {"dist": "uniform", "min", "max"}
  order                 "seeded" (default): the seed permutes lengths and
                        gaps; "fixed": one order for every seed, so that
                        a window that sees only the first requests of a
                        mix, or cuts the requests due late, does the same
                        work whatever the seed (the token ids still come
                        from the seed)
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    rid: int
    arrival_s: float
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use of the seed; any integer seed, of any size."""
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, int(seed < 0),
             stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def quantiles(dist: dict, n: int) -> np.ndarray:
    """Lengths at the quantiles (i + 0.5) / n of ``dist``, rounded and
    clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in q])
        vals = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        vals = lo + (hi - lo) * q
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def n_requests(mix: dict, seconds: float) -> int:
    if mix["arrival"] == "poisson":
        return max(1, math.ceil(mix["rate_per_s"] * seconds))
    return int(mix["n_requests"])


def arrivals(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if mix["arrival"] == "at_once":
        return np.zeros(n)
    if mix["arrival"] == "poisson":
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / mix["rate_per_s"]
        return np.cumsum(rng.permutation(gaps))
    raise ValueError(f"unknown arrival process {mix['arrival']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab_size: int
             ) -> list[Spec]:
    """The requests of one run, ordered by arrival."""
    n = n_requests(mix, seconds)
    order = 0 if mix.get("order", "seeded") == "fixed" else seed
    prompts = rng_for(order, 1).permutation(quantiles(mix["prompt"], n))
    outputs = rng_for(order, 2).permutation(quantiles(mix["output"], n))
    due = arrivals(mix, n, rng_for(order, 3))
    ids = rng_for(seed, 4)
    out = []
    for i in range(n):
        if mix["arrival"] == "poisson" and due[i] >= seconds:
            continue
        out.append(Spec(rid=i, arrival_s=float(due[i]),
                        prompt=ids.integers(0, vocab_size, int(prompts[i]),
                                            dtype=np.int32),
                        max_new_tokens=int(outputs[i])))
    return sorted(out, key=lambda s: (s.arrival_s, s.rid))
