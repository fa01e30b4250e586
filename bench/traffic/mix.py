"""The one traffic generator: a mix file's parameters and a seed in, a
request schedule out.

Every seed gets the same requests, (prompt length, output length)
pairs, due at the same times: lengths and gaps are the distribution's
quantiles at evenly spaced probabilities, paired and ordered by fixed
permutations.  The seed draws only the prompt tokens.  So two seeds do
the same work in the same order, and a run's spread comes from the
system, not from a draw of heavier requests or of an order in which
long prompts meet.

Mix file keys (``bench/traffic/<name>.json``):

    generator   "mix" (this module)
    loop        "open": arrivals at ``rate_per_s`` for the window
    rate_per_s  requests due per second
    prompt, output   {"dist": "lognormal", "median", "sigma", "min",
                "max"} or {"dist": "uniform", "min", "max"}: tokens

The arithmetic of Poisson gaps and lognormal lengths follows
``repro.serve.traffic`` (exponential gaps at mean 1/rate; lognormal
parameterised by its median), computed here without importing it.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Optional

import numpy as np

#: fixed pairing of prompt and output quantiles, and fixed orders of
#: the requests and of the gaps between them (seed-independent)
_PAIRING_SEED = 20240716
_ORDER_SEED = 20240717


@dataclasses.dataclass
class Req:
    """One request of a schedule: due time (seconds after the window
    opens), prompt tokens, and output tokens asked for."""

    prompt: list
    max_new: int
    due: Optional[float] = None


@dataclasses.dataclass
class Schedule:
    loop: str                        # "open"
    requests: list                   # sorted by due

    def pairs(self) -> list:
        """Every (prompt length, output length) the schedule can send."""
        return [(len(r.prompt), r.max_new) for r in self.requests]


def _probabilities(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``dist``, clamped to
    [min, max], ascending."""
    u = _probabilities(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        inv = statistics.NormalDist().inv_cdf
        z = np.array([inv(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(int)


def gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential gaps (mean ``1/rate``) at evenly spaced
    quantiles, ascending."""
    return -np.log1p(-_probabilities(n)) / rate


def count(mix: dict, seconds: float) -> int:
    """Requests due in the window."""
    return max(1, int(round(float(mix["rate_per_s"]) * seconds)))


def rng_for(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 64))


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    """The schedule of one run."""
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = count(mix, seconds)
    plen = lengths(mix["prompt"], n)
    olen = lengths(mix["output"], n)[
        np.random.default_rng(_PAIRING_SEED).permutation(n)]
    fixed = np.random.default_rng(_ORDER_SEED)
    order = fixed.permutation(n)
    g = gaps(float(mix["rate_per_s"]), n)[fixed.permutation(n)]
    rng = rng_for(seed)
    reqs = [Req(prompt=rng.integers(0, vocab, int(plen[i])).tolist(),
                max_new=int(olen[i])) for i in order]
    # the last request falls due just inside the window
    due = np.cumsum(g) * (seconds * (n - 0.5) / n) / g.sum()
    for r, t in zip(reqs, due):
        r.due = float(t)
    return Schedule("open", reqs)


def scaled(mix: dict, factor: float) -> dict:
    """The mix with every length scaled by ``factor`` (the CPU
    rehearsal's tiny pool); at least one token each."""
    out = dict(mix)
    for key in ("prompt", "output"):
        d = dict(mix[key])
        for k in ("median", "min", "max"):
            if k in d:
                d[k] = max(1, int(math.floor(d[k] * factor)))
        out[key] = d
    return out
