"""ISSUE 10 contracts: the batched ask plane.

``gp.batched_select`` stacks several experiments' q-EI batch selections
on a lane axis and runs them in ONE vmap'd dispatch; the pump publishes
refill demand as ``AskSpec`` snapshots the FitExecutor gathers by
(runner, bucket, k_pad, pool-shape) group.  This file pins the
equivalence (batched and one-lane picks == the serial reference's), the
compile discipline (one XLA compile per (bucket, k_pad, lane-pad)
triple), the variable-step fit-lane merge (frozen-lane params
bit-identical, the steps-free group key co-batches mixed ladder rungs),
the PRIO_MISS latency contract (miss asks never wait out the gather
window) and — under REPRO_CONTENTION — a 16-experiment live-pump run
through the shared executor."""
import os
import tempfile
import threading
import time

import numpy as np
import pytest

from repro.api import (CreateExperiment, LocalClient, ObserveRequest,
                       pipeline)
from repro.api.pipeline import (BatchableAsk, BatchableFit, FitExecutor,
                                FitLane, PRIO_IDLE, PRIO_MISS)
from repro.core.experiment import ExperimentConfig
from repro.core.space import Param, Space, strip_internal
from repro.core.suggest import Observation, gp, make_optimizer
from repro.core.suggest.bayesopt import run_ask_lanes

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


def _posteriors(k, n=14, d=3, bucket=32, seed=0):
    """k fitted GP posteriors over distinct histories, one shape bucket,
    with (candidate pool, incumbent) per lane.  The hyperparameters come
    from the serial reference fit, so the posteriors do not move with
    the fit program under test."""
    rng = np.random.default_rng(seed)
    lanes = []
    for i in range(k):
        x = rng.random((n + i, d))
        w = rng.random(d)
        y = np.sin(3.0 * x @ w) + 0.1 * rng.standard_normal(n + i)
        post = gp.make_posterior(
            gp_oracle.fit_params(x, y, 25, bucket=bucket), x, y,
            bucket=bucket)
        cand = rng.random((64, d)).astype(np.float32)
        lanes.append((post, cand, float(np.max(y))))
    return lanes


# ---------------------------------------------------- gp.batched_select
def test_batched_select_matches_serial_select():
    """k lanes through one q-EI dispatch (``_select_lanes``) and each
    lane alone (the one-lane view ``_select_scan``, through
    ``select_batch`` and a one-item ``batched_select``) must pick the
    *same candidates* as the serial recompute-per-pick scan
    (``tests/gp_oracle.py``) — exact index equality, the suggestion
    parity contract — and land on the same lie-folded posterior up to
    float32 program-order rounding (the incremental factor updates are a
    different XLA program, so alpha/chol drift at the 1e-4 level on
    O(10) magnitudes)."""
    import jax
    lanes = _posteriors(4)
    ks = [2, 5, 8, 3]
    out = gp.batched_select(
        [(post, cand, best, k)
         for (post, cand, best), k in zip(lanes, ks)])
    for (post, cand, best), k, lane in zip(lanes, ks, out):
        want_picks, want_post = gp_oracle.select(post, cand, best, k)
        for picks, lane_post in (
                lane, gp.select_batch(post, cand, best, k),
                gp.batched_select([(post, cand, best, k)])[0]):
            np.testing.assert_array_equal(np.asarray(picks),
                                          np.asarray(want_picks))
            for got, want in zip(jax.tree.leaves(lane_post),
                                 jax.tree.leaves(want_post)):
                np.testing.assert_allclose(np.asarray(got),
                                           np.asarray(want), atol=5e-4)


def test_batched_select_one_dispatch_one_compile():
    """One (bucket, k_pad, lane-pad) triple costs exactly one XLA
    compile: varying per-lane k and lane counts within the same pads
    reuse it."""
    lanes = _posteriors(4, seed=7)
    items = [(post, cand, best, 1 + i)
             for i, (post, cand, best) in enumerate(lanes)]
    before = gp._select_lanes._cache_size()
    gp.batched_select(items[:2])                    # lane_pad(2) == 2
    mid = gp._select_lanes._cache_size()
    assert mid == before + 1
    gp.batched_select(list(reversed(items[:2])))    # different k order
    gp.batched_select(items[2:4])                   # different lanes/k
    assert gp._select_lanes._cache_size() == mid
    scans = gp._select_scan._cache_size()
    gp.batched_select(items[:1])                    # lane_pad(1) == 1
    assert gp._select_lanes._cache_size() == mid    # the one-lane view
    assert gp._select_scan._cache_size() <= scans + 1


def test_prewarm_compiles_select_lanes():
    """Satellite: ``prewarm_bucket(select_lanes=(1, 2))`` compiles the
    batched-select variant per lane pad — lane pad 1 as the one-lane
    ``_select_scan`` at ``SELECT_PAD`` — and a later first dispatch at
    those pads must not add a compile."""
    d, bucket, m = 5, 16, 32         # distinctive shapes: a fresh probe
    before = gp._select_lanes._cache_size()
    scans = gp._select_scan._cache_size()
    gp.prewarm_bucket(d, bucket, fit_steps=(5,), k_pads=(1,),
                      n_cand=m, select_lanes=(1, 2))
    after = gp._select_lanes._cache_size()
    assert after == before + 1       # lane pad 2
    scans_after = gp._select_scan._cache_size()
    assert scans_after == scans + 2  # k_pad 1, and lane pad 1 at SELECT_PAD
    # idempotent: re-warming and real dispatches at those pads reuse it
    gp.prewarm_bucket(d, bucket, fit_steps=(5,), k_pads=(1,),
                      n_cand=m, select_lanes=(1, 2))
    rng = np.random.default_rng(0)
    x = rng.random((6, d))
    y = rng.standard_normal(6)
    post = gp.fit_gp(x, y, steps=5, bucket=bucket)
    cand = rng.random((m, d)).astype(np.float32)
    gp.batched_select([(post, cand, 1.0, 4), (post, cand, 1.0, 2)])
    gp.batched_select([(post, cand, 1.0, 3)])
    assert gp._select_lanes._cache_size() == after
    assert gp._select_scan._cache_size() == scans_after


# ---------------------------------------------- variable-step fit lanes
def test_mixed_step_lanes_bitidentical_to_own_step_count():
    """Tentpole (2): lanes on different step budgets merge into one
    masked max(steps) loop, and a lane frozen at its own budget holds
    exactly the parameters a uniform run at that budget produces —
    bit-identical at matched lane pad, and within float tolerance of a
    true solo fit (whose different lane pad is a different XLA program,
    so only rounding-level drift is allowed)."""
    rng = np.random.default_rng(1)
    d = 3
    x1 = rng.random((10, d)); y1 = np.sin(x1.sum(1))
    x2 = rng.random((13, d)); y2 = np.cos(x2.sum(1))
    mixed = gp.batched_fit([(x1, y1, None), (x2, y2, None)],
                           steps=[15, 45], bucket=16)
    lo = gp.batched_fit([(x1, y1, None), (x2, y2, None)],
                        steps=[15, 15], bucket=16)
    hi = gp.batched_fit([(x1, y1, None), (x2, y2, None)],
                        steps=[45, 45], bucket=16)
    for got, want in ((mixed[0], lo[0]), (mixed[1], hi[1])):
        assert np.array_equal(np.asarray(got.log_ls),
                              np.asarray(want.log_ls))
        assert np.array_equal(np.asarray(got.log_amp),
                              np.asarray(want.log_amp))
        assert np.array_equal(np.asarray(got.log_noise),
                              np.asarray(want.log_noise))
    solo = gp.batched_fit([(x1, y1, None)], steps=15, bucket=16)[0]
    np.testing.assert_allclose(mixed[0].log_ls, solo.log_ls, atol=1e-5)
    np.testing.assert_allclose(mixed[0].log_amp, solo.log_amp, atol=1e-5)


def test_fit_group_key_drops_steps():
    """Tentpole (2): two experiments on different warm-step ladder rungs
    (different ``FitSpec.steps``) share a (runner, bucket) group and
    co-batch into ONE dispatch — ``mean_batch`` > 1 under a mixed-step
    workload, the PR 8 ROADMAP follow-up."""
    opts = []
    for i, steps in enumerate((8, 24)):
        opt = make_optimizer("gp", _space(), seed=i, n_init=4,
                             fit_steps=30, warm_fit_steps=steps)
        rng = np.random.default_rng(i)
        opt.tell([Observation(a, _f(a))
                  for a in opt.space.sample(rng, 20)])
        assert opt.maintain()           # cold fit -> warm-started
        opt.tell([Observation(a, _f(a))
                  for a in opt.space.sample(rng, 8)])
        assert opt.maintenance_due()
        opts.append(opt)
    specs = [opt.fit_spec() for opt in opts]
    assert specs[0].steps != specs[1].steps
    assert specs[0].group_key == specs[1].group_key

    installed = []
    ex = FitExecutor(workers=1)
    try:
        gate = threading.Event()
        ex.submit("hold", lambda: (gate.wait(5), False)[-1], PRIO_MISS)
        _wait(lambda: ex.backlog() == 0)
        for i, spec in enumerate(specs):
            ex.submit(f"e{i}", BatchableFit(
                lambda s=spec: FitLane(
                    s, lambda p, dt, s=s: (s.install(p, dt),
                                           installed.append(p)))),
                PRIO_IDLE)
        gate.set()
        assert _wait(lambda: len(installed) == 2)
        snap = ex.snapshot()
        assert snap["batched"] == 1 and snap["lanes"] == 2
        assert snap["mean_batch"] == pytest.approx(2.0)
    finally:
        ex.stop()
    for opt in opts:
        assert opt._params is not None
        assert np.all(np.isfinite(np.asarray(opt._params.log_ls)))


# -------------------------------------------------- AskSpec + executor
def test_ask_spec_parity_with_inline_ask():
    """Satellite: an ``ask_spec`` snapshot run through ``run_ask_lanes``
    and installed must mint the same suggestions an inline ``ask`` on a
    twin optimizer produces (same seed, same history, same rng path)."""
    twins = []
    for _ in range(2):
        opt = make_optimizer("gp", _space(), seed=5, n_init=4,
                             fit_steps=20, warm_fit_steps=10)
        rng = np.random.default_rng(5)
        opt.tell([Observation(a, _f(a))
                  for a in opt.space.sample(rng, 16)])
        twins.append(opt)
    inline = twins[0].ask(4)
    spec = twins[1].ask_spec(4)
    assert spec is not None and spec.k == 4
    out, dt = run_ask_lanes([spec])
    batched = spec.install(out[0], dt)
    assert len(batched) == len(inline) == 4
    for a, b in zip(inline, batched):
        assert strip_internal(a) == strip_internal(b)
    # both twins registered one lie per suggestion
    assert len(twins[0]._pending) == len(twins[1]._pending)


def test_executor_ask_stats_separate_from_fit_stats():
    """Ask dispatches land on batched_asks/ask_lanes; the fit-side
    batched/lanes/mean_batch stay untouched (tests pin those as a pure
    fit co-batching signal)."""
    calls, installed = [], []

    def runner(specs):
        calls.append(len(specs))
        return [(np.arange(2), None)] * len(specs), 0.001

    class _Fake:
        kind = "ask"
        __slots__ = ("bucket", "k_pad", "cand", "runner", "install")

        def __init__(self):
            self.bucket, self.k_pad = 64, 8
            self.cand = np.zeros((4, 2), np.float32)
            self.runner = runner

        @property
        def group_key(self):
            return (self.runner, self.bucket, self.k_pad,
                    tuple(self.cand.shape))

    ex = FitExecutor(workers=1)
    ex.MAX_LANES = 4        # pin the (normally dynamic) cap
    try:
        gate = threading.Event()
        ex.submit("hold", lambda: (gate.wait(5), False)[-1], PRIO_MISS)
        _wait(lambda: ex.backlog() == 0)
        for i in range(3):
            spec = _Fake()
            ex.submit(f"a{i}", BatchableAsk(
                lambda s=spec: FitLane(
                    s, lambda r, dt: installed.append(r))), PRIO_IDLE)
        gate.set()
        assert _wait(lambda: len(installed) == 3)
        assert calls == [3]
        snap = ex.snapshot()
        assert snap["batched_asks"] == 1 and snap["ask_lanes"] == 3
        assert snap["mean_ask_batch"] == pytest.approx(3.0)
        assert snap["batched"] == 0 and snap["lanes"] == 0
        assert snap["mean_batch"] == 0.0
    finally:
        ex.stop()


# ----------------------------------------------------- live service path
def _cfg(**kw):
    kw.setdefault("name", "batched-ask")
    kw.setdefault("optimizer", "gp")
    kw.setdefault("parallel", 4)
    kw.setdefault("space", _space())
    kw.setdefault("optimizer_options", {"n_init": 2, "fit_steps": 5,
                                        "warm_fit_steps": 5,
                                        "refit_every": 4})
    return ExperimentConfig(**kw)


def test_pump_routes_refills_through_batched_ask_plane():
    """A live gp experiment's queue refills must flow through the
    BatchableAsk path: ``batched_prefilled`` moves, the executor's
    ``batched_asks``/``ask_lanes`` counters move, and the queue still
    serves (hits) — the batched plane is the refill hot path, not a
    side channel."""
    client = LocalClient(tempfile.mkdtemp())
    exp = client.create_experiment(CreateExperiment(
        config=_cfg(budget=200, prefetch=6).to_json())).exp_id
    state = client._exps[exp]
    state.optimizer.prewarm(60, batch=4)
    rng = np.random.default_rng(0)
    try:
        for _ in range(24):
            s = client.suggest(exp, 1).suggestions[0]
            client.observe(ObserveRequest(
                exp, s.suggestion_id, s.assignment,
                _f(strip_internal(s.assignment))))
            time.sleep(0.01)

        def landed():
            st = client.status(exp)
            ex = st.pump.get("executor") or {}
            return (st.pump.get("batched_prefilled", 0) > 0
                    and ex.get("batched_asks", 0) >= 1)
        assert _wait(landed, timeout=60.0), \
            f"no batched refill landed: {client.status(exp).pump}"
        st = client.status(exp)
        assert st.pump["executor"]["ask_lanes"] >= 1
        assert st.pump["executor"]["mean_ask_batch"] >= 1.0
        assert _wait(
            lambda: client.status(exp).pump.get("hits", 0) > 0,
            timeout=30.0), "batched-refilled queue never served a hit"
    finally:
        client.stop(exp)
        client.close()


def test_miss_asks_bypass_gather_window():
    """Tentpole contract: miss serving keeps its exact inline ask —
    PRIO_MISS semantics unchanged.  With the executor's gather window
    pinned pathologically long, a dry-queue suggest must still return
    far sooner than the window: the miss never rides the batched
    plane's gather."""
    ex = pipeline.fit_executor()
    old = ex.GATHER_WINDOW
    ex.GATHER_WINDOW = 5.0
    client = LocalClient(tempfile.mkdtemp())
    try:
        exp = client.create_experiment(CreateExperiment(
            config=_cfg(budget=100, prefetch=2).to_json())).exp_id
        state = client._exps[exp]
        state.optimizer.prewarm(30, batch=4)
        rng = np.random.default_rng(0)
        # leave the random phase so misses hit the model path
        for _ in range(6):
            s = client.suggest(exp, 1).suggestions[0]
            client.observe(ObserveRequest(
                exp, s.suggestion_id, s.assignment, float(rng.normal())))
        misses0 = state.stats["misses"]
        # drain the queue, then time dry-queue suggests: every batched
        # refill is stuck waiting out the 5 s gather window, so these
        # can only be served by the inline miss path
        with state.lock:
            drained = [i.assignment for i in state.queue]
            state.queue = []
        for a in drained:
            with state.opt_lock:
                state.optimizer.forget(a)
        t0 = time.monotonic()
        s = client.suggest(exp, 1).suggestions[0]
        elapsed = time.monotonic() - t0
        assert elapsed < 3.0, \
            f"dry-queue suggest waited the gather window ({elapsed:.2f}s)"
        assert state.stats["misses"] > misses0
        client.observe(ObserveRequest(exp, s.suggestion_id, s.assignment,
                                      float(rng.normal())))
        client.stop(exp)
    finally:
        ex.GATHER_WINDOW = old
        client.close()


# ------------------------------------------------ contended live pumps
@pytest.mark.contention
@pytest.mark.skipif(not os.environ.get("REPRO_CONTENTION"),
                    reason="set REPRO_CONTENTION=1 (ci.sh tier-2)")
def test_sixteen_live_pumps_cobatch_refills():
    """16 live experiments' pumps refilling concurrently through the
    shared executor: refills must actually co-batch (mean_ask_batch
    > 1) while every experiment keeps serving."""
    client = LocalClient(tempfile.mkdtemp())
    exps = []
    try:
        for i in range(16):
            exp = client.create_experiment(CreateExperiment(
                config=_cfg(name=f"c{i}", budget=300,
                            prefetch=6).to_json())).exp_id
            exps.append(exp)
        client._exps[exps[0]].optimizer.prewarm(60, batch=4)
        ask0 = pipeline.fit_executor().snapshot()["ask_lanes"]

        def drive(exp, seed):
            rng = np.random.default_rng(seed)
            for _ in range(20):
                s = client.suggest(exp, 1).suggestions[0]
                client.observe(ObserveRequest(
                    exp, s.suggestion_id, s.assignment,
                    _f(strip_internal(s.assignment))))
                time.sleep(0.005)

        threads = [threading.Thread(target=drive, args=(e, i))
                   for i, e in enumerate(exps)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert _wait(
            lambda: pipeline.fit_executor().snapshot()["ask_lanes"]
            > ask0, timeout=60.0), "no batched ask reached the executor"
        assert _wait(
            lambda: pipeline.fit_executor().snapshot()["mean_ask_batch"]
            > 1.0, timeout=120.0), \
            f"refills never co-batched: {pipeline.fit_executor().snapshot()}"
        for exp in exps:
            assert client.status(exp).observations >= 20
    finally:
        for exp in exps:
            client.stop(exp)
        client.close()
