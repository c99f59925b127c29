"""Open-loop request schedules, made from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

    {"rate": 40.0,                        # mean suggests per second
     "arrivals": {"kind": "poisson"}      # or {"kind": "onoff", "period_s":
                                          #     10, "on_s": 2, "on_factor": 3,
                                          #     "off_factor": 0.5}
     "choice": {"kind": "zipf", "s": 1.1},
     "trial_s": {"kind": "lognormal", "median": 2.0, "sigma": 1.0,
                 "cap": 20.0},
     "timeout_s": 10.0,
     "warmup_s": 8.0,
     "knee": {...}}                       # read by bench/knee.py only

Every seed gets the same work in another order, so that runs with
different seeds differ by arrangement and not by amount:

* the number of suggests is ``round(rate * seconds)``; given that count a
  Poisson process puts its arrivals independently with density
  proportional to the rate, so the times are drawn so (on/off arrivals
  by inverting the cumulative rate);
* the Zipf counts per popularity rank are fixed (largest remainder) and
  only the experiment that holds each rank is drawn from the seed;
* the trial times are evenly spaced quantiles of the lognormal, capped,
  and shuffled.

This module uses the standard library and NumPy only: the load generator
imports it in a process that never touches JAX.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import Dict, List

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def rate_at(arrivals: Dict, t: np.ndarray) -> np.ndarray:
    """Relative arrival rate (mean 1 over a period) at offsets ``t``."""
    kind = arrivals.get("kind", "poisson")
    if kind == "poisson":
        return np.ones_like(t, dtype=float)
    if kind == "onoff":
        on = np.mod(t, arrivals["period_s"]) < arrivals["on_s"]
        return np.where(on, arrivals["on_factor"], arrivals["off_factor"])
    raise ValueError(f"unknown arrival kind {kind!r}")


def arrival_times(arrivals: Dict, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``n`` sorted arrival offsets in [0, seconds) with density
    proportional to ``rate_at`` (inverse of the cumulative rate on a fine
    grid)."""
    grid = np.linspace(0.0, seconds, 20001)
    cum = np.concatenate([[0.0], np.cumsum(
        rate_at(arrivals, grid[:-1]) * np.diff(grid))])
    u = np.sort(rng.uniform(0.0, cum[-1], n))
    return np.interp(u, cum, grid)


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """Requests per popularity rank (rank 0 hottest), summing to ``n``."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    rest = n - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


def trial_quantiles(spec: Dict, n: int) -> np.ndarray:
    if spec.get("kind", "lognormal") != "lognormal":
        raise ValueError(f"unknown trial-time kind {spec.get('kind')!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.minimum(spec["median"] * np.exp(spec["sigma"] * z),
                      spec["cap"])


def schedule(traffic: Dict, n_experiments: int, seconds: float, seed: int,
             stream: int = 0) -> List[Dict]:
    """The suggests due in [0, seconds): ``{"t", "exp", "trial_s"}`` each,
    sorted by ``t``.  ``exp`` indexes the cell's experiments; ``stream``
    separates independent schedules of one seed (warm-up, window)."""
    n = max(1, int(round(traffic["rate"] * seconds)))
    rng = _rng(seed, stream)
    times = arrival_times(traffic.get("arrivals", {}), n, seconds, rng)
    choice = traffic.get("choice", {"kind": "zipf", "s": 1.1})
    if choice.get("kind") != "zipf":
        raise ValueError(f"unknown choice kind {choice.get('kind')!r}")
    counts = zipf_counts(n, n_experiments, choice["s"])
    holder = rng.permutation(n_experiments)      # rank -> experiment
    exps = np.repeat(holder, counts)
    rng.shuffle(exps)
    trial = trial_quantiles(traffic["trial_s"], n)
    rng.shuffle(trial)
    return [{"t": float(t), "exp": int(e), "trial_s": float(d)}
            for t, e, d in zip(times, exps, trial)]


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile of all values (NumPy's default)."""
    return float(np.quantile(np.asarray(values, float), q))
