"""The one traffic generator. A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and a seed into requests.

Every seed gets the same multiset of inter-arrival gaps and request sizes,
drawn on a fixed quantile grid of the mix's distributions, in an order that
the seed permutes. So two seeds do the same amount of work and differ only
in its order and in the token ids or pixels.

Mix keys:

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the replies do)
  or ``"closed"`` (each connection keeps ``pipeline`` requests in flight).
* ``arrival``: ``"poisson"`` (open loop only), at ``rate_per_s``.
* ``connections``: client connections; open-loop request i goes to
  connection i mod connections.
* ``ramp_s``: unmeasured seconds before the window.
* ``request``: ``{"images": n}`` for an image batch, or
  ``{"prompt": dist, "output": dist}`` for generation, where a ``dist`` is
  ``{"dist": "lognormal", "median", "sigma", "min", "max"[, "ladder"]}``;
  a length is clipped to [min, max] and then rounded up to the ladder.
"""
from __future__ import annotations

import bisect
import math
import statistics
from dataclasses import dataclass
from typing import Optional

import numpy as np

_NORMAL = statistics.NormalDist()


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any non-negative whole number) and a
    stream label, so that draws for different purposes never overlap."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng([seed, *stream])


def grid(n: int) -> np.ndarray:
    """n probabilities at the midpoints of n equal bins of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(dist: dict, n: int) -> np.ndarray:
    """The n-point quantile grid of a clipped, laddered lognormal."""
    z = np.array([_NORMAL.inv_cdf(p) for p in grid(n)])
    raw = dist["median"] * np.exp(dist["sigma"] * z)
    out = np.clip(np.ceil(raw), dist["min"], dist["max"]).astype(np.int64)
    ladder = dist.get("ladder")
    if ladder:
        ladder = sorted(ladder)
        if ladder[-1] < dist["max"]:
            raise ValueError(f"ladder {ladder} stops below max {dist['max']}")
        out = np.array([ladder[bisect.bisect_left(ladder, int(v))]
                        for v in out], np.int64)
    return out


def lengths_reachable(dist: dict) -> list:
    """Every length the dist can produce: min..max, rounded up to the
    ladder where there is one."""
    vals = range(int(dist["min"]), int(dist["max"]) + 1)
    ladder = sorted(dist.get("ladder") or [])
    if ladder:
        return sorted({ladder[bisect.bisect_left(ladder, v)] for v in vals})
    return list(vals)


@dataclass
class Spec:
    """One request: its index, due time (seconds after the schedule's
    start; None in a closed loop), connection and sizes."""
    index: int
    due: Optional[float]
    conn: int
    prompt_len: int = 0
    max_new: int = 0


def schedule(mix: dict, seed: int, seconds: float) -> list:
    """Requests for ``seconds`` of the mix from ``seed``: an open loop's
    arrivals in time order, or a closed loop's request sizes in sending
    order (as many as an open loop at ``rate_per_s`` would send, or 4096)."""
    if mix["loop"] == "open":
        if mix.get("arrival", "poisson") != "poisson":
            raise ValueError(f"unknown arrival {mix['arrival']!r}")
        rate = float(mix["rate_per_s"])
        n = max(1, math.ceil(rate * seconds))
        gaps = -np.log1p(-grid(n)) / rate
        gaps = gaps[seed_rng(seed, 1).permutation(n)]
        due = np.cumsum(gaps) - gaps[0]
    elif mix["loop"] == "closed":
        n, due = 4096, None
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    req = mix["request"]
    plen = np.zeros(n, np.int64)
    mnew = np.zeros(n, np.int64)
    if "prompt" in req:
        plen = lognormal_lengths(req["prompt"], n)[
            seed_rng(seed, 2).permutation(n)]
        mnew = lognormal_lengths(req["output"], n)[
            seed_rng(seed, 3).permutation(n)]
    conns = int(mix["connections"])
    return [Spec(i, None if due is None else float(due[i]), i % conns,
                 int(plen[i]), int(mnew[i])) for i in range(n)]


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int) -> np.ndarray:
    """Token ids of request ``index``: uniform over the vocabulary."""
    return seed_rng(seed, 4, index).integers(0, vocab, length,
                                             dtype=np.int32)


def with_rate(mix: dict, rate: float) -> dict:
    """The same mix at another arrival rate (the sweep's knob)."""
    if mix["loop"] != "open":
        raise ValueError("only an open loop has a rate")
    return {**mix, "rate_per_s": float(rate)}
