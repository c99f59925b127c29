"""Open-loop schedules: the same seed gives the same schedule, every seed
the same work in another order, with the stated Zipf, on/off and
lognormal parameters."""
import json
import pathlib

import numpy as np
import pytest

from bench import traffic

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("name", sorted(p.stem for p in
                                        TRAFFIC.glob("*.json")))
def test_schedule_is_deterministic_per_seed(name):
    tr = json.loads((TRAFFIC / f"{name}.json").read_text())
    a = traffic.schedule(tr, 16, 20.0, BIG_SEED)
    b = traffic.schedule(tr, 16, 20.0, BIG_SEED)
    c = traffic.schedule(tr, 16, 20.0, BIG_SEED + 1)
    assert a == b
    assert a != c
    assert len(a) == len(c) == round(tr["rate"] * 20.0)
    # same work in another order: the same multiset of trial times and
    # of per-rank request counts
    assert sorted(s["trial_s"] for s in a) == sorted(s["trial_s"] for s in c)
    ca = sorted(np.bincount([s["exp"] for s in a], minlength=16))
    cc = sorted(np.bincount([s["exp"] for s in c], minlength=16))
    assert ca == cc
    t = [s["t"] for s in a]
    assert t == sorted(t) and 0.0 <= t[0] and t[-1] < 20.0


def test_zipf_counts_follow_the_exponent():
    counts = traffic.zipf_counts(10000, 64, 1.1)
    assert counts.sum() == 10000
    w = 1.0 / np.arange(1, 65) ** 1.1
    np.testing.assert_allclose(counts, 10000 * w / w.sum(), atol=1.0)
    assert list(counts) == sorted(counts, reverse=True)


def test_lognormal_trial_times():
    spec = {"kind": "lognormal", "median": 2.0, "sigma": 1.0, "cap": 20.0}
    q = traffic.trial_quantiles(spec, 2001)
    assert q.max() == 20.0
    assert abs(np.median(q) - 2.0) < 1e-9
    # one sigma above the median: exp(1) times it (84.1st percentile)
    assert abs(np.quantile(q, 0.8413) - 2.0 * np.e) < 0.05
    # the cap binds above exp(sigma * z) = 10, i.e. z > 2.30
    assert abs((q == 20.0).mean() - (1 - 0.98928)) < 1e-3


def test_onoff_bursts_carry_the_stated_rates():
    arr = {"kind": "onoff", "period_s": 10.0, "on_s": 2.0,
           "on_factor": 3.0, "off_factor": 0.5}
    rng = np.random.default_rng(0)
    t = traffic.arrival_times(arr, 100000, 100.0, rng)
    on = np.mod(t, 10.0) < 2.0
    # 2 s at 3x and 8 s at 0.5x: 6 of every 10 units of rate in bursts
    assert abs(on.mean() - 0.6) < 0.01
    assert abs(len(t) / 100.0 - 1000.0) < 1e-9
    rate_on = on.sum() / 20.0
    rate_off = (~on).sum() / 80.0
    assert abs(rate_on / rate_off - 6.0) < 0.3


def test_burst_cell_keeps_the_steady_cells_mean_rate():
    # the on/off mix PERF.md keeps for a later burst cell: the steady
    # cell's traffic with bursts of 3x for 2 s every 10 s and 0.5x
    # between sends the same number of suggests in another order
    steady = json.loads((TRAFFIC / "d8-steady.json").read_text())
    burst = dict(steady, arrivals={"kind": "onoff", "period_s": 10.0,
                                   "on_s": 2.0, "on_factor": 3.0,
                                   "off_factor": 0.5})
    a = traffic.schedule(steady, 64, 30.0, BIG_SEED)
    b = traffic.schedule(burst, 64, 30.0, BIG_SEED)
    assert len(a) == len(b)
    t = np.asarray([s["t"] for s in b])
    on = np.mod(t, 10.0) < 2.0
    assert abs(on.mean() - 0.6) < 0.05
