"""One open-loop traffic generator for every mix.

A mix is a data file under ``bench/traffic/`` (parameters only); a cell
gives it a rate. The arrival times and every request's lengths are drawn
from the mix's own ``shape_seed``, so every run seed offers the same work
in the same order; the run seed draws the prompts' token ids. (On a TPU
v5e, runs whose seed drew the arrivals and lengths anew read 221-289
tokens/s, and runs whose seed dealt one set of lengths out in its own order
read a queue-wait tail of 0.98-1.53 s.) Shapes follow
``repro.engine.traces`` (``burstgpt_like``, ``_lens``): Poisson arrivals,
Gamma-sized burst episodes, and lognormal lengths, here restricted to the
mix's range (drawn again until inside it, not clipped onto its ends).
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float          # seconds after the schedule starts
    prompt: tuple         # token ids
    max_new_tokens: int


def _lengths(rng, n: int, spec: dict) -> np.ndarray:
    """``n`` lengths from the lognormal restricted to [min, max]."""
    out = np.zeros((0,), np.int64)
    while len(out) < n:
        x = np.round(rng.lognormal(np.log(spec["median"]), spec["sigma"], n))
        x = x[(x >= spec["min"]) & (x <= spec["max"])]
        out = np.concatenate([out, x.astype(np.int64)])
    return out[:n]


def arrival_times(mix: dict, rate_rps: float, horizon_s: float,
                  rng) -> np.ndarray:
    """Poisson arrivals at ``rate_rps``; with ``mix["burst"]``, each arrival
    opens a burst episode with probability ``prob``: Gamma(``shape``,
    ``scale``) more arrivals spread uniformly over the next ``span_s``."""
    burst = mix.get("burst")
    t, out = 0.0, []
    while True:
        t += rng.exponential(1.0 / rate_rps)
        if t >= horizon_s:
            break
        out.append(t)
        if burst and rng.random() < burst["prob"]:
            k = int(rng.gamma(burst["shape"], burst["scale"]))
            out.extend(t + rng.uniform(0.0, burst["span_s"], k))
    return np.sort(np.asarray([a for a in out if a < horizon_s]))


def mean_rate(mix: dict, rate_rps: float) -> float:
    """Expected arrivals per second of the mix at base rate ``rate_rps``."""
    burst = mix.get("burst")
    extra = burst["prob"] * burst["shape"] * burst["scale"] if burst else 0.0
    return rate_rps * (1.0 + extra)


def schedule(mix: dict, rate_rps: float, horizon_s: float, seed: int,
             vocab: int) -> List[Arrival]:
    """The arrivals due in ``[0, horizon_s)`` for run ``seed``."""
    shape = np.random.default_rng(mix["shape_seed"])
    times = arrival_times(mix, rate_rps, horizon_s, shape)
    n = len(times)
    prompts = _lengths(shape, n, mix["prompt"])
    outputs = _lengths(shape, n, mix["output"])
    run = np.random.default_rng(seed)
    return [Arrival(float(t), tuple(int(x) for x in
                                    run.integers(0, vocab, int(p))), int(o))
            for t, p, o in zip(times, prompts, outputs)]
