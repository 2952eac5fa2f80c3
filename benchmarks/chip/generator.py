"""The one traffic generator.  A traffic mix is a data file
(``traffic/<name>.json``); this module turns it and a seed into requests.

Every seed gets the same work in another order: the same number of
arrivals, the same multiset of gaps between them (in the intensity's
clock), the same multiset of prompt and output lengths.  The seed orders
them and draws the prompts' token ids.  So runs with different seeds differ
in order, not in how much there is to do.  The order is stratified
(``spread_order``): every run of 8 arrivals holds one gap and one length
from each eighth of their range, so no seed bunches the long requests or
the short gaps into one stretch of the window.  A free permutation did:
with the window's ~70 requests it moved the tokens delivered in a window
by 7-8 % and the p95 time per token by 16-19 % from seed to seed, while
two runs of one seed agreed to 1-2 %.

Arrivals follow a piecewise-constant intensity:

* ``{"kind": "poisson", "rate_rps": r}``: one state at ``r``;
* ``{"kind": "mmpp2", "rates_rps": [calm, burst], "dwell_s": [dc, db]}``:
  a two-state Markov-modulated Poisson process that alternates calm and
  burst periods, starting calm.  Dwell times are exponential with means
  ``dc`` and ``db``; the mean rate is ``(calm*dc + burst*db) / (dc + db)``.

Unit-rate exponential gaps, laid out in the cumulative intensity
``Lambda(t)`` and mapped back through its inverse, give the arrival times.

Lengths are lognormal (``median``, ``sigma``), clipped to ``[min, max]``
and rounded up to a ``multiple``.

A mix may ask for a lead-in (``lead_in_s``): arrivals of the same mix over
that many seconds before the window, made apart from the window's with a
stream of their own, with due times below zero.  They bring the server to
its steady load before the window opens; no metric counts them.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import typing

import numpy as np


@dataclasses.dataclass
class Arrival:
    uid: int
    due_s: float            # offset from the start of the window
    prompt: np.ndarray      # (S,) int32
    max_new_tokens: int


def _quantiles(n: int, inv_cdf: typing.Callable[[float], float]) -> np.ndarray:
    """The n mid-point quantiles of a distribution: a sample of size n that
    every seed shares."""
    return np.array([inv_cdf((i + 0.5) / n) for i in range(n)])


def spread_order(x: np.ndarray, rng: np.random.Generator,
                 stratum: int = 8) -> np.ndarray:
    """The values of ``x`` in an order drawn from ``rng`` in which every
    run of ``stratum`` places holds one value from each of ``stratum``
    bands of the sorted values: the seed decides which value of a band goes
    to which run, and the order inside each run.  So each stretch of the
    traffic carries a like share of the long and the short, and a seed
    cannot bunch them."""
    bands = [rng.permutation(b) for b in
             np.array_split(np.sort(x), min(stratum, len(x)))]
    runs = [[b[j] for b in bands if j < len(b)]
            for j in range(len(bands[0]))]
    return np.array([v for r in runs for v in rng.permutation(r)],
                    dtype=x.dtype)


def _exp_inv(mean: float):
    return lambda u: -mean * math.log1p(-u)


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n lengths from the lognormal in ``spec``, ordered by ``rng``."""
    dist = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
    x = np.exp(_quantiles(n, dist.inv_cdf))
    x = np.clip(x, spec["min"], spec["max"])
    m = spec.get("multiple", 1)
    x = np.ceil(x / m) * m
    return spread_order(x.astype(np.int64), rng)


def intensity(spec: dict, seconds: float, rng: np.random.Generator
              ) -> typing.Tuple[np.ndarray, np.ndarray]:
    """Piecewise-constant arrival intensity over ``[0, seconds]``: the
    start of each period and its rate."""
    if spec["kind"] == "poisson":
        return np.zeros(1), np.array([float(spec["rate_rps"])])
    if spec["kind"] != "mmpp2":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    rates = [float(r) for r in spec["rates_rps"]]
    dwell = [float(d) for d in spec["dwell_s"]]
    cycles = max(1, round(seconds / sum(dwell)))
    per = [rng.permutation(_quantiles(cycles, _exp_inv(d))) for d in dwell]
    # scaled so the periods fill the window exactly: each state then holds
    # the same share of it (dc : db) whatever the order
    scale = seconds / sum(float(p.sum()) for p in per)
    durs = np.stack(per, axis=1).reshape(-1) * scale      # calm, burst, ...
    starts = np.concatenate([[0.0], np.cumsum(durs)[:-1]])
    return starts, np.array(rates * cycles)


def arrival_times(spec: dict, seconds: float, rng: np.random.Generator
                  ) -> np.ndarray:
    starts, rates = intensity(spec, seconds, rng)
    ends = np.append(starts[1:], seconds)
    cum = np.concatenate([[0.0], np.cumsum(rates * (ends - starts))])
    n = int(math.floor(cum[-1]))
    if n == 0:
        return np.zeros(0)
    gaps = spread_order(_quantiles(n, _exp_inv(1.0)), rng)
    # the fixed sum of the gaps is scaled onto the whole intensity, so the
    # last arrival falls just before the window's end for every seed
    lam = np.cumsum(gaps) * (cum[-1] / gaps.sum()) * (n / (n + 0.5))
    k = np.searchsorted(cum, lam, side="right") - 1
    return starts[k] + (lam - cum[k]) / rates[k]


def _span(traffic: dict, seconds: float, rng: np.random.Generator,
          vocab: int, uid0: int, offset: float) -> typing.List[Arrival]:
    times = arrival_times(traffic["arrivals"], seconds, rng)
    n = len(times)
    prompts = lengths(traffic["prompt"], n, rng)
    outs = lengths(traffic["output"], n, rng)
    return [Arrival(uid=uid0 + i, due_s=float(t) + offset,
                    prompt=rng.integers(0, vocab, int(p)).astype(np.int32),
                    max_new_tokens=int(o))
            for i, (t, p, o) in enumerate(zip(times, prompts, outs))]


def lead_in_s(traffic: dict) -> float:
    return float(traffic.get("lead_in_s", 0))


def make_arrivals(traffic: dict, seconds: float, seed: int, vocab: int
                  ) -> typing.List[Arrival]:
    """The lead-in's arrivals (due before 0) and the window's, in order of
    due time."""
    window = _span(traffic, seconds, np.random.default_rng(seed), vocab,
                   0, 0.0)
    lead = lead_in_s(traffic)
    if lead <= 0:
        return window
    before = _span(traffic, lead, np.random.default_rng((seed, 3)), vocab,
                   len(window), -lead)
    return before + window


def prompt_buckets(spec: dict) -> typing.List[int]:
    """Every prompt length the mix can send, for the warm-up."""
    m = spec.get("multiple", 1)
    lo = int(math.ceil(spec["min"] / m) * m)
    hi = int(math.ceil(spec["max"] / m) * m)
    return list(range(lo, hi + 1, m))
