"""``gp.prewarm_bucket`` warms each program signature once per process.

Every experiment's pump prewarms its shape buckets, and experiments over
one space ask for the same signatures.  This file pins that a repeat
request runs nothing (no warm, no new jit cache entry), that concurrent
requests wait for the one warm in flight instead of racing past it,
that a signature differing in any part still warms, and what the
service reports: ``pump["prewarm"]`` and an unchanged ``prewarmed``."""
import sys
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from repro.api import CreateExperiment, LocalClient
from repro.core import tracing
from repro.core.experiment import ExperimentConfig
from repro.core.space import Param, Space
from repro.core.suggest import gp, make_optimizer

JITTED = ("_fit", "_fit_lanes", "_posterior", "append_point", "append_lie",
          "_select_scan", "_select_lanes")
OPTS = {"candidates": 16, "fit_steps": 5, "warm_fit_steps": 5}


def _space(d):
    return Space([Param(f"x{i}", "double", 0, 1) for i in range(d)])


def _cache_entries():
    return sum(getattr(gp, name)._cache_size() for name in JITTED)


def _wait(predicate, timeout=60.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return predicate()


def test_second_optimizer_over_one_space_warms_nothing():
    first = make_optimizer("gp", _space(3), **OPTS)
    assert first.prewarm(20, batch=2) == 2         # buckets 16 and 32
    stats0, cache0 = gp.prewarm_stats(), _cache_entries()
    second = make_optimizer("gp", _space(3), seed=1, **OPTS)
    assert second.prewarm(20, batch=2) == 2
    stats1 = gp.prewarm_stats()
    assert stats1["warmed"] == stats0["warmed"]
    assert stats1["skipped"] > stats0["skipped"]
    assert _cache_entries() == cache0


def test_concurrent_requests_warm_once_and_return_compiled(monkeypatch):
    """Eight threads ask for the same signatures at once: each is warmed
    by one of them, and every thread returns only once the serial scan's
    variant is in the jit cache."""
    d, bucket, m = 13, 16, 24        # shapes no other test asks for
    real = gp.select_batch
    calls = []

    def slow_select(*a, **kw):       # hold the warm open
        calls.append(threading.get_ident())
        time.sleep(0.5)
        return real(*a, **kw)
    monkeypatch.setattr(gp, "select_batch", slow_select)
    before, stats0 = gp._select_scan._cache_size(), gp.prewarm_stats()
    start = threading.Barrier(8)
    seen = []

    def worker():
        start.wait()
        gp.prewarm_bucket(d, bucket, k_pads=(2,), n_cand=m)
        seen.append(gp._select_scan._cache_size())
    threads = [threading.Thread(target=worker) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)      # interleave the counters' updates
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(calls) == 1
    assert seen == [before + 1] * 8
    stats = gp.prewarm_stats()
    # three signatures: the posterior, the appends, the k_pad-2 scan
    assert stats["warmed"] - stats0["warmed"] == 3
    assert stats["skipped"] - stats0["skipped"] == 7 * 3
    assert stats["waited"] - stats0["waited"] >= 1


@pytest.mark.parametrize("variant, jitted", [
    ({"d": 7}, "_posterior"),
    ({"n_cand": 28}, "_select_scan"),
    ({"n_cand": 36}, "_select_lanes"),
    ({"select_lanes": (2,)}, "_select_lanes"),
    ({"x64": True}, "_posterior"),
])
def test_any_other_signature_still_warms(variant, jitted):
    base = {"d": 6, "bucket": 16, "k_pads": (1,), "n_cand": 20,
            "select_lanes": (1,)}
    gp.prewarm_bucket(**base)
    kw = dict(base, **variant)
    x64 = kw.pop("x64", False)
    stats0 = gp.prewarm_stats()
    before = getattr(gp, jitted)._cache_size()
    with jax.enable_x64(x64):
        gp.prewarm_bucket(**kw)
    assert gp.prewarm_stats()["warmed"] > stats0["warmed"]
    assert getattr(gp, jitted)._cache_size() == before + 1


def test_prewarm_span_names_bucket_and_outcomes():
    gp.prewarm_bucket(4, 16, k_pads=(1,), n_cand=20)
    tracing.enable()
    try:
        gp.prewarm_bucket(4, 16, k_pads=(1,), n_cand=20)
        spans = [r for r in tracing.drain() if r[3] == "gp.prewarm"]
    finally:
        tracing.disable()
    assert len(spans) == 1
    assert spans[0][7] == {"bucket": 16, "warmed": 0, "skipped": 3}


def _cfg(name):
    return ExperimentConfig(
        name=name, optimizer="gp", parallel=2, budget=40, prefetch=2,
        space=_space(2), optimizer_options=dict(OPTS, n_init=2)).to_json()


def test_pump_status_carries_prewarm_counters():
    client = LocalClient(tempfile.mkdtemp())
    exp = client.create_experiment(
        CreateExperiment(config=_cfg("prewarm-status"))).exp_id
    try:
        assert _wait(lambda: client.status(exp).pump["prewarmed"] > 0)
        pw = client.status(exp).pump["prewarm"]
        assert set(pw) == {"warmed", "skipped", "waited"}
        assert pw["warmed"] + pw["skipped"] > 0
    finally:
        client.stop(exp)
        client.close()


def test_prewarmed_counts_buckets_even_when_nothing_warms():
    """``prewarmed`` still counts the buckets an experiment's pump asked
    for: the second experiment over one space warms no signature, yet
    reports what the first does."""
    client = LocalClient(tempfile.mkdtemp())
    a = client.create_experiment(
        CreateExperiment(config=_cfg("prewarm-a"))).exp_id
    try:
        assert _wait(lambda: client.status(a).pump["prewarmed"] > 0)
        warmed = gp.prewarm_stats()["warmed"]
        b = client.create_experiment(
            CreateExperiment(config=_cfg("prewarm-b"))).exp_id
        assert _wait(lambda: client.status(b).pump["prewarmed"] > 0)
        assert gp.prewarm_stats()["warmed"] == warmed
        assert (client.status(b).pump["prewarmed"]
                == client.status(a).pump["prewarmed"])
        client.stop(b)
    finally:
        client.stop(a)
        client.close()
