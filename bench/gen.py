"""The one traffic generator: request schedules from a mix's parameters.

A traffic mix (``bench/traffic/<mix>.json``) gives the prompt and output
length distributions and the order of its trace; the cell
(``bench/cells/<cell>.json``) gives the open loop's fixed ``rate`` in
requests/s and the ramp of load that runs before the window opens.

Every seed offers the same work.  The arrivals fall in three spans, the
ramp, the window and the drain, and each span holds ``round(rate * span)``
requests: the stratified quantiles ``(i + 0.5) / n`` of the prompt length,
of the output length and of an exponential gap, clamped to the stated
bounds, the gaps scaled to fill the span.  Their order is a fixed trace: a
random order of each span's prompts, outputs and gaps drawn from the mix's
``trace`` key, so that gaps cluster as Poisson arrivals do.  The seed
draws the token ids.  So every run offers the same requests at the same
times, and the spread between runs is the system's: with a few dozen
requests in a window, an order drawn from the seed decides which long
prompts meet, and moved the TTFT tail by 15-20% between seeds.

Only stdlib and numpy: the client process imports this module and must
never import JAX.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of a schedule.  ``due`` is seconds from the window's
    opening (negative: part of the ramp before it)."""
    due: float
    prompt: np.ndarray
    max_new: int


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, as ints.

    ``{"dist": "lognormal", "mean": m, "sigma": s, "low": a, "high": b}``
    is parameterised so that the unclamped mean is ``m``; ``"uniform"``
    spans ``[low, high]``; ``"fixed"`` is ``mean`` everywhere."""
    kind = dist["dist"]
    low, high = int(dist.get("low", 1)), int(dist.get("high", 1 << 30))
    u = (np.arange(n) + 0.5) / n
    if kind == "lognormal":
        sigma = float(dist["sigma"])
        mu = math.log(float(dist["mean"])) - 0.5 * sigma ** 2
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.exp(mu + sigma * z)
    elif kind == "uniform":
        x = low + u * (high - low)
    elif kind == "fixed":
        x = np.full(n, float(dist["mean"]))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), low, high).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """Stratified quantiles of Poisson inter-arrival gaps, scaled so that
    the ``n`` gaps span exactly ``n / rate`` seconds."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (n / rate) / g.sum()


def _streams(key, k: int) -> List[np.random.Generator]:
    ss = np.random.SeedSequence(key)
    return [np.random.default_rng(s) for s in ss.spawn(k)]


def schedule(mix: Dict, load: Dict, *, seed: int, seconds: float,
             drain: float, vocab: int) -> List[Planned]:
    """The requests of one run.

    ``load`` is the cell's: ``{"rate": r, "ramp_s": p}``.  Arrivals run
    from ``-ramp_s`` to ``seconds + drain``, so the load is steady over the
    whole window and its drain."""
    ramp = float(load.get("ramp_s", 0.0))
    rate = float(load["rate"])
    r_ids = np.random.default_rng(int(seed) % (1 << 64))
    out: List[Planned] = []
    start = -ramp
    for i, span in enumerate((ramp, seconds, drain)):
        n = int(round(rate * span))
        if n < 1:
            start += span
            continue
        t_prompt, t_out, t_gap = _streams([int(mix["trace"]), i], 3)
        plens = t_prompt.permutation(quantiles(mix["prompt"], n))
        outs = t_out.permutation(quantiles(mix["output"], n))
        gaps = t_gap.permutation(exp_gaps(n / span, n))
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        out += [Planned(float(t), r_ids.integers(0, vocab, int(p),
                                                 dtype=np.int32), int(m))
                for t, p, m in zip(due, plens, outs)]
        start += span
    return out
