"""The one traffic generator: a traffic file's parameters and a seed in,
a fixed schedule of requests out.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (stratified quantiles of each distribution), in an
order and with token ids drawn from the seed. So two seeds do the same
amount of work and differ only in how it interleaves. In the open loop
this holds for the warm-in and for the window apart: each is a block of
its own, so every seed sends the window the same requests.

A traffic file (``bench/traffic/<mix>.json``) holds:

- ``loop``: ``"open"`` (arrivals on a schedule) or ``"closed"`` (each of
  ``clients`` clients sends its next request when the last one ends);
- ``arrival`` (open loop): ``{"rate_per_s": r}``, Poisson arrivals at
  rate r (exponential gaps of mean 1/r);
- ``clients`` and ``stagger_s`` (closed loop): client i starts at
  ``i * stagger_s / clients`` seconds;
- ``prompt_len`` and ``output_len``: a distribution ``{"dist":
  "lognormal", "median", "sigma", "min", "max"}`` or ``{"dist":
  "uniform", "min", "max"}``;
- ``warm_in_s``: traffic runs this long before the window opens;
- ``drain_s``: how long after the window closes the run waits for the
  requests sent in it (open loop: to finish; closed loop: for their
  first token).
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Planned:
    """One request of the schedule. ``t`` is its send time in seconds
    from the start of traffic (open loop), or -1 (closed loop: sent when
    its client is free)."""

    idx: int
    t: float
    prompt_len: int
    max_new: int
    client: int = 0


def _quantile(spec: dict, u: float) -> float:
    kind = spec["dist"]
    if kind == "lognormal":
        return spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    if kind == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    raise ValueError(f"unknown length distribution {kind!r}")


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths at the distribution's stratified quantiles
    ``(i + 1/2) / n``, clipped to ``[min, max]``, in an order drawn from
    ``rng``."""
    u = (np.arange(n) + 0.5) / n
    vals = np.array([_quantile(spec, x) for x in u])
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(vals)


def gaps(arrival: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps: stratified quantiles of the exponential
    distribution with mean ``1/rate_per_s``, in an order drawn from
    ``rng``."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-u) / float(arrival["rate_per_s"]))


def schedule(traffic: dict, seconds: float, seed: int) -> List[Planned]:
    """The requests of one run, for ``warm_in_s + seconds`` of traffic.

    Open loop: in each block (the warm-in, the window), ``rate_per_s``
    times its length requests. Closed loop: a queue of requests per
    client, more than any client can finish in that time (each needs at
    least one decode tick per output token)."""
    rng = np.random.default_rng(seed)
    warm = float(traffic["warm_in_s"])
    span = warm + float(seconds)
    if traffic["loop"] == "open":
        rate = float(traffic["arrival"]["rate_per_s"])
        out: List[Planned] = []
        for a, b in ((0.0, warm), (warm, span)):
            n = int(round(rate * (b - a)))
            if n == 0:
                continue
            g = gaps(traffic["arrival"], n, rng)
            # The block's n arrivals fill [a, b): the gaps' multiset is
            # scaled by the same factor for every seed.
            t = a + (b - a) * (np.cumsum(g) - g) / g.sum()
            p = lengths(traffic["prompt_len"], n, rng)
            o = lengths(traffic["output_len"], n, rng)
            out += [Planned(len(out) + i, float(t[i]), int(p[i]), int(o[i]))
                    for i in range(n)]
        return out
    if traffic["loop"] == "closed":
        c = int(traffic["clients"])
        # A decode tick takes more than 2 ms, so no client finishes more.
        min_out = int(traffic["output_len"]["min"])
        per = int(math.ceil(span / (min_out * 2e-3))) + 1
        n = c * per
        p = lengths(traffic["prompt_len"], n, rng)
        o = lengths(traffic["output_len"], n, rng)
        return [Planned(i, -1.0, int(p[i]), int(o[i]), client=i % c)
                for i in range(n)]
    raise ValueError(f"unknown loop {traffic['loop']!r}")


def length_range(spec: dict) -> tuple:
    """(least, most) length a distribution can give."""
    return int(spec["min"]), int(spec["max"])


def client_start(traffic: dict, client: int) -> float:
    """Closed loop: when ``client`` sends its first request."""
    return client * float(traffic.get("stagger_s", 0.0)) / int(
        traffic["clients"])


def prompt(seed: int, idx: int, length: int, vocab: int) -> np.ndarray:
    """Token ids of request ``idx``: uniform over the vocabulary, from
    ``(seed, idx)`` alone."""
    return np.random.default_rng([seed, 1, idx]).integers(
        0, vocab, length, dtype=np.int32)
