"""The one traffic generator: a traffic file's parameters -> a schedule.

Pure python, no framework imports. The multiset of work is fixed by the
traffic file and the window length: lengths and inter-arrival gaps are
taken at evenly spaced quantiles of their distributions, so every seed
offers the same requests and the same gaps; ``--seed`` orders them (and
draws the token ids). A window has to hold enough requests that their
order does not decide what completes: with some tens of heavy-tailed
requests it does (18% in completed tokens/s between orders at 24
attempted requests; chip runs of PR 23).

(The repo's ``benchmarks/loadgen.py::generate_schedule`` draws lengths
and thinned arrivals afresh per seed; its shape parameters — Poisson
arrivals, burst windows, clamped lognormal lengths — are kept here.)
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

_MASK31 = (1 << 31) - 1


def seed_words(seed: int) -> Tuple[int, int]:
    """``--seed`` may exceed 32 signed bits: split it into two words."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be >= 0")
    return seed & _MASK31, seed >> 31


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def draw_lengths(spec: Dict, n: int, rng: random.Random) -> List[int]:
    """``n`` lengths at the evenly spaced quantiles of ``spec``'s
    distribution, clamped to [min, max], in an order drawn from ``rng``."""
    dist = spec["dist"]
    lo, hi = int(spec["min"]), int(spec["max"])
    if dist == "lognormal":
        mu, sigma = math.log(spec["median"]), float(spec["sigma"])
        norm = NormalDist()
        vals = [math.exp(mu + sigma * norm.inv_cdf(u)) for u in _quantiles(n)]
    elif dist == "uniform":
        vals = [lo + (hi - lo) * u for u in _quantiles(n)]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    out = [max(lo, min(hi, int(round(v)))) for v in vals]
    rng.shuffle(out)
    return out


def burst_windows(spec: Dict, horizon: float,
                  rng: random.Random) -> List[Tuple[float, float]]:
    """Disjoint burst episodes covering ``burst_frac`` of the horizon."""
    frac = float(spec.get("burst_frac", 0.0))
    if frac <= 0.0 or float(spec.get("burst_factor", 1.0)) == 1.0:
        return []
    k = int(spec.get("burst_count", 3))
    width = frac * horizon / k
    slot = horizon / k
    starts = [j * slot + rng.uniform(0.0, slot - width) for j in range(k)]
    return [(s, s + width) for s in starts]


def _invert_rate(unit_times: List[float], base: float, factor: float,
                 windows: List[Tuple[float, float]]) -> List[float]:
    """Map unit-rate arrival times through the inverse of the cumulative
    rate: ``base`` outside the windows, ``base * factor`` inside them."""
    cuts = sorted(windows)
    out = []
    for u in unit_times:
        t, need = 0.0, u
        for a, b in cuts:
            room = (a - t) * base
            if need <= room:
                break
            need -= room
            t = a
            room = (b - a) * base * factor
            if need <= room:
                t += need / (base * factor)
                need = 0.0
                break
            need -= room
            t = b
        out.append(t + need / base if need > 0.0 else t)
    return out


def draw_arrivals(spec: Dict, seconds: float,
                  rng: random.Random) -> List[float]:
    """Poisson arrival instants (seconds from the window's start) at
    ``spec['rate']`` requests/s on average over the window: exponential
    gaps at evenly spaced quantiles, ordered by ``rng``; optional burst
    windows multiply the rate by ``burst_factor`` over ``burst_frac`` of
    the window while the mean stays ``rate``."""
    rate = float(spec["rate"])
    n = max(1, int(math.ceil(rate * seconds * 1.05)))
    gaps = [-math.log(1.0 - u) for u in _quantiles(n)]
    rng.shuffle(gaps)
    unit, acc = [], 0.0
    for g in gaps:
        acc += g
        unit.append(acc)
    factor = float(spec.get("burst_factor", 1.0))
    windows = burst_windows(spec, seconds, rng)
    covered = sum(b - a for a, b in windows) / seconds if windows else 0.0
    base = rate / (1.0 + (factor - 1.0) * covered)
    return _invert_rate(unit, base, factor, windows)


def generate(traffic: Dict, seed: int, seconds: float) -> List[Dict]:
    """The schedule for one run: a list of requests, each with ``i``,
    ``t`` (due instant, seconds from the window's start; 0.0 in a closed
    loop, where a client sends its next request when the last returns),
    ``prompt_len`` and ``max_new_tokens``."""
    rng = random.Random(int(seed))
    arr = traffic["arrivals"]
    if arr["kind"] == "poisson":
        times = draw_arrivals(arr, seconds, rng)
    elif arr["kind"] == "closed":
        times = [0.0] * int(arr["pool"])
    else:
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    n = len(times)
    prompts = draw_lengths(traffic["prompt_len"], n, rng)
    outputs = draw_lengths(traffic["output_len"], n, rng)
    return [{"i": i, "t": times[i], "prompt_len": prompts[i],
             "max_new_tokens": outputs[i]} for i in range(n)]


def prompt_tokens(seed: int, item: Dict, vocab: int) -> np.ndarray:
    """The token ids of one request: drawn below ``vocab`` from
    (seed, i)."""
    lo, hi = seed_words(seed)
    return np.random.default_rng([lo, hi, 1, int(item["i"])]).integers(
        0, vocab, int(item["prompt_len"]), dtype=np.int32)


def train_batch(seed: int, step: int, batch: int, seq: int,
                vocab: int) -> Tuple[np.ndarray, np.ndarray]:
    """A fresh batch for one training step, drawn on the host from
    (seed, step): ``ids`` and next-token ``labels``, both [batch, seq];
    every row differs."""
    lo, hi = seed_words(seed)
    tok = np.random.default_rng([lo, hi, 3, int(step)]).integers(
        0, vocab, (batch, seq + 1), dtype=np.int32)
    return tok[:, :-1], tok[:, 1:]
