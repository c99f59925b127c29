"""ISSUE 8 contracts: cross-experiment fit batching.

The FitExecutor coalesces queued batchable fits sharing a
(runner, bucket, steps) group into ONE vmap'd dispatch
(``gp.batched_fit``); this file pins the equivalence (batched and
one-lane params == the serial reference's), the compile discipline (one
XLA compile per lane-pad, zero on re-dispatch), the grouping rule (mixed
buckets never co-batch), the PRIO_MISS latency contract (urgent fits
skip the gather window) and
— under REPRO_CONTENTION — k=16 real concurrent refits through the
executor."""
import os
import threading
import time

import numpy as np
import pytest

from repro.api import pipeline
from repro.api.pipeline import (BatchableFit, FitExecutor, FitLane,
                                PRIO_IDLE, PRIO_MISS, RETRY)
from repro.core.space import Param, Space
from repro.core.suggest import Observation, gp, make_optimizer
from repro.core.suggest.bayesopt import FitSpec, run_fit_lanes

import gp_oracle


def _space():
    return Space([Param("x", "double", 0, 1),
                  Param("y", "double", 1e-4, 1e0, log=True)])


def _f(a):
    return -((a["x"] - 0.62) ** 2 + (np.log10(a["y"]) + 2.0) ** 2)


def _wait(predicate, timeout=10.0, every=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(every)
    return predicate()


def _experiments(k, n=20, d=4, seed=0):
    rng = np.random.default_rng(seed)
    items = []
    for i in range(k):
        x = rng.random((n, d))
        w = rng.random(d)
        y = np.sin(3.0 * x @ w) + 0.1 * rng.standard_normal(n)
        items.append((x, y, None))
    return items


# ------------------------------------------------------- gp.batched_fit
def test_batched_fit_matches_serial_fits():
    """k lanes through one dispatch (``_fit_lanes``) and each lane alone
    (the one-lane view ``_fit``) must land on the hyperparameters of the
    serial reference — Adam over autodiff of the XLA neg-MLL, same steps,
    same start (``tests/gp_oracle.py``): lanes are independent by
    construction."""
    items = _experiments(5)
    batched = gp.batched_fit(items, steps=25, bucket=32)
    for (x, y, p0), bp in zip(items, batched):
        want = gp_oracle.fit_params(x, y, steps=25, params0=p0, bucket=32)
        alone = gp.batched_fit([(x, y, p0)], steps=25, bucket=32)[0]
        for got in (bp, alone):
            for g, w in zip(got, want):
                np.testing.assert_allclose(g, w, atol=1e-4)


def test_lone_fit_is_one_lane_dispatch_of_fit():
    """A one-spec ``run_fit_lanes`` (the executor's lone refit) and
    ``fit_gp`` run one executable, ``_fit``, on the same inputs: the
    hyperparameters are identical, and between them they add one
    ``_fit`` jit entry and no ``_fit_lanes`` one."""
    rng = np.random.default_rng(11)
    x = rng.random((11, 6))                 # d=6, bucket 16, 7 steps:
    y = np.sin(3.0 * x.sum(1))              # shapes no other test fits
    p0 = gp.GPParams(np.full(6, -0.5, np.float32), np.float32(0.1),
                     np.float32(-1.5))
    fits, lanes = gp._fit._cache_size(), gp._fit_lanes._cache_size()
    (got,), _ = run_fit_lanes([FitSpec(16, 7, x, y, p0, None,
                                       run_fit_lanes)])
    want = gp.fit_gp(x, y, steps=7, params0=p0, bucket=16).params
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    assert gp._fit._cache_size() == fits + 1
    assert gp._fit_lanes._cache_size() == lanes


def test_batched_fit_one_dispatch_one_compile():
    """One (bucket, steps, lane-pad) triple costs exactly one XLA
    compile; re-dispatch at any k within the same lane-pad reuses it."""
    items = _experiments(6, seed=3)
    before = gp._fit_lanes._cache_size()
    gp.batched_fit(items[:5], steps=12, bucket=32)      # lane_pad(5) == 8
    mid = gp._fit_lanes._cache_size()
    assert mid == before + 1
    gp.batched_fit(items[:6], steps=12, bucket=32)      # lane_pad(6) == 8
    assert gp._fit_lanes._cache_size() == mid


# --------------------------------------------------- executor co-batching
class _Spec:
    __slots__ = ("bucket", "steps", "runner", "install")

    def __init__(self, bucket, steps, runner):
        self.bucket, self.steps, self.runner = bucket, steps, runner


def _recording_runner(calls):
    def runner(specs):
        calls.append([s.bucket for s in specs])
        return [None] * len(specs), 0.001
    return runner


def test_executor_cobatches_same_group_only():
    """Queued batchable fits sharing (runner, bucket, steps) dispatch
    together; a different bucket must run in its own dispatch."""
    calls, installed = [], []
    runner = _recording_runner(calls)

    def make(bucket):
        spec = _Spec(bucket, 40, runner)
        return BatchableFit(lambda: FitLane(
            spec, lambda p, dt: installed.append(bucket)))

    ex = FitExecutor(workers=1)
    try:
        gate = threading.Event()
        ex.submit("hold", lambda: (gate.wait(5), False)[-1], PRIO_MISS)
        _wait(lambda: ex.backlog() == 0)
        for i in range(4):
            ex.submit(f"e{i}", make(64), PRIO_IDLE)
        ex.submit("odd", make(128), PRIO_IDLE)
        gate.set()
        assert _wait(lambda: len(installed) == 5)
        assert sorted(len(c) for c in calls) == [1, 4]
        assert [64] * 4 in calls and [128] in calls
        snap = ex.snapshot()
        assert snap["lanes"] == 5 and snap["batched"] == 2
        assert snap["mean_batch"] == pytest.approx(2.5)
    finally:
        ex.stop()


def test_executor_caps_batch_at_max_lanes():
    calls, installed = [], []
    runner = _recording_runner(calls)
    ex = FitExecutor(workers=1)
    ex.MAX_LANES = 4        # pin the (normally dynamic) cap
    try:
        gate = threading.Event()
        ex.submit("hold", lambda: (gate.wait(5), False)[-1], PRIO_MISS)
        _wait(lambda: ex.backlog() == 0)
        for i in range(ex.MAX_LANES + 3):
            spec = _Spec(64, 40, runner)
            ex.submit(f"e{i}", BatchableFit(
                lambda s=spec: FitLane(
                    s, lambda p, dt: installed.append(1))), PRIO_IDLE)
        gate.set()
        assert _wait(lambda: len(installed) == ex.MAX_LANES + 3)
        assert max(len(c) for c in calls) == ex.MAX_LANES
    finally:
        ex.stop()


def test_executor_max_lanes_scales_with_backlog():
    """The dynamic cap tracks backlog per worker: idle -> LANES_MIN, a
    deep queue -> more lanes (power of two), never past LANES_CAP."""
    ex = FitExecutor(workers=1)
    try:
        assert ex.max_lanes() == ex.LANES_MIN
        gate = threading.Event()
        ex.submit("hold", lambda: (gate.wait(5), False)[-1], PRIO_MISS)
        _wait(lambda: ex.backlog() == 0)
        for i in range(6):
            ex.submit(f"e{i}", lambda: False, PRIO_IDLE)
        lanes = ex.max_lanes()
        assert lanes == 8                   # 6 queued / 1 worker -> pad to 8
        assert ex.snapshot()["max_lanes"] == lanes
        for i in range(40):
            ex.submit(f"x{i}", lambda: False, PRIO_IDLE)
        assert ex.max_lanes() == ex.LANES_CAP
        gate.set()
    finally:
        ex.stop()


def test_retry_snapshot_requeues_until_lane_appears():
    seen = []
    spec = _Spec(64, 40, _recording_runner([]))

    def snap():
        seen.append(1)
        if len(seen) < 3:
            return RETRY
        return FitLane(spec, lambda p, dt: seen.append("installed"))

    ex = FitExecutor(workers=1)
    try:
        ex.submit("r", BatchableFit(snap), PRIO_IDLE)
        assert _wait(lambda: "installed" in seen)
        assert ex.snapshot()["requeued"] >= 2
    finally:
        ex.stop()


def test_prio_miss_skips_gather_window():
    """A miss-urgent fit must dispatch immediately — the gather window
    is only for fits no request is waiting on.  Pin it by making the
    window pathologically long: the PRIO_MISS fit still installs fast,
    and an idle fit on the same executor waits the window out."""
    ex = FitExecutor(workers=1)
    ex.GATHER_WINDOW = 1.5
    try:
        done = []
        spec = _Spec(64, 40, _recording_runner([]))

        def submit(key, prio):
            t0 = time.monotonic()
            ex.submit(key, BatchableFit(lambda: FitLane(
                spec, lambda p, dt: done.append(
                    (key, time.monotonic() - t0)))), prio)

        submit("miss", PRIO_MISS)
        assert _wait(lambda: len(done) == 1, timeout=1.0)
        assert done[0][1] < 1.0     # never slept the 1.5s window
        submit("idle", PRIO_IDLE)
        assert _wait(lambda: len(done) == 2, timeout=10.0)
        assert done[1][1] >= ex.GATHER_WINDOW
    finally:
        ex.stop()


# ------------------------------------------------ contended real refits
@pytest.mark.contention
@pytest.mark.skipif(not os.environ.get("REPRO_CONTENTION"),
                    reason="set REPRO_CONTENTION=1 (ci.sh tier-2)")
def test_sixteen_concurrent_refits_cobatch_through_executor():
    """16 real GP optimizers owing warm refits, pushed through one
    1-worker executor as batchable lanes: all must install, and the
    executor must have amortized them into multi-lane dispatches."""
    opts, locks = [], []
    for i in range(16):
        opt = make_optimizer("gp", _space(), seed=i, n_init=4,
                             fit_steps=30, warm_fit_steps=10)
        rng = np.random.default_rng(i)
        opt.tell([Observation(a, _f(a))
                  for a in opt.space.sample(rng, 24)])
        assert opt.maintenance_due()
        opts.append(opt)
        locks.append(threading.Lock())

    installed = []

    def make_snapshot(opt, lock):
        def snap():
            if not lock.acquire(timeout=0.05):
                return RETRY
            try:
                spec = opt.fit_spec()
            finally:
                lock.release()
            if spec is None:
                return None

            def install(params, dt):
                with lock:
                    spec.install(params, dt)
                installed.append(opt)
            return FitLane(spec, install)
        return snap

    ex = FitExecutor(workers=1)
    try:
        gate = threading.Event()
        ex.submit("hold", lambda: (gate.wait(5), False)[-1], PRIO_MISS)
        _wait(lambda: ex.backlog() == 0)
        for i, (opt, lock) in enumerate(zip(opts, locks)):
            ex.submit(f"exp{i}", BatchableFit(make_snapshot(opt, lock)),
                      PRIO_IDLE)
        gate.set()
        assert _wait(lambda: len(installed) == 16, timeout=60.0)
        snap = ex.snapshot()
        assert snap["lanes"] >= 16
        assert snap["mean_batch"] > 1.0     # real co-batching happened
        for opt in opts:
            assert opt._params is not None and opt._fits >= 1
            assert not opt.maintenance_due()
    finally:
        ex.stop()


# ----------------------------------------------------- pump integration
def test_pump_routes_gp_fits_through_batchable_path():
    """A live gp experiment's deferred refits must flow through the
    BatchableFit path (executor ``lanes`` counter moves) and still land
    as ``maintained`` installs; the quality readout carries the live
    auto-tuned ``sparse_max``."""
    import tempfile

    from repro.api import CreateExperiment, LocalClient, ObserveRequest
    from repro.core.experiment import ExperimentConfig
    from repro.core.space import strip_internal

    client = LocalClient(tempfile.mkdtemp())
    cfg = ExperimentConfig(
        name="batched-pump", space=_space(), optimizer="gp",
        budget=200, parallel=4,
        optimizer_options={"n_init": 2, "fit_steps": 10,
                           "warm_fit_steps": 5, "refit_every": 4})
    exp = client.create_experiment(
        CreateExperiment(config=cfg.to_json())).exp_id
    before = pipeline.fit_executor().snapshot()["lanes"]
    try:
        for _ in range(16):
            s = client.suggest(exp, 1).suggestions[0]
            client.observe(ObserveRequest(
                exp, s.suggestion_id, s.assignment,
                _f(strip_internal(s.assignment))))
            time.sleep(0.005)
        assert _wait(
            lambda: (client.status(exp).pump.get("maintained", 0) > 0
                     and pipeline.fit_executor().snapshot()["lanes"]
                     > before),
            timeout=30.0), "no batchable lane reached the executor"
        st = client.status(exp)
        assert st.pump["executor"]["mean_batch"] >= 1.0
        assert st.pump["quality"]["sparse_max"] >= 1
    finally:
        client.stop(exp)
        client.close()
