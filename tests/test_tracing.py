"""The span recorder (``repro.core.tracing``) and the spans the
suggestion service records at its layer boundaries."""
import tempfile
import threading
import time

import numpy as np
import pytest

from repro.api import (CreateExperiment, HTTPClient, LocalClient,
                       ObserveRequest, serve_api)
from repro.core import tracing
from repro.core.experiment import ExperimentConfig
from repro.core.space import Param, Space, strip_internal

ID, PARENT, REQUEST, NAME, THREAD, START, END, ATTRS = range(8)


@pytest.fixture
def on():
    tracing.enable()
    yield
    tracing.disable()
    tracing.drain()


def test_off_returns_the_shared_null_and_records_nothing():
    tracing.disable()
    tracing.drain()
    assert tracing.span("suggest") is tracing.NULL
    assert tracing.request_span("http.request", route="x") is tracing.NULL
    with tracing.span("a", k=1) as sp:
        sp.set(more=2)
        tracing.annotate(h2d_bytes=8)
        tracing.record("exec.queue_wait", 0, 10)
        assert sp.request_id is None
    assert tracing.drain() == []
    assert tracing.dropped() == 0


def test_nesting_sets_parents_and_attrs(on):
    with tracing.span("outer", a=1) as outer:
        with tracing.span("inner") as inner:
            tracing.annotate(h2d_bytes=64)
        tracing.record("outer.queue_wait", 5, 7, prio=0)
    recs = {r[NAME]: r for r in tracing.drain()}
    assert recs["outer"][PARENT] == 0 and recs["outer"][ATTRS] == {"a": 1}
    assert recs["inner"][PARENT] == outer.id == recs["outer"][ID]
    assert recs["inner"][ID] == inner.id
    assert recs["inner"][ATTRS] == {"h2d_bytes": 64}
    assert recs["outer.queue_wait"][PARENT] == outer.id
    assert recs["outer.queue_wait"][START:END + 1] == (5, 7)
    for r in recs.values():
        assert r[START] <= r[END] and r[THREAD] == threading.get_ident()
    assert recs["outer"][START] <= recs["inner"][START] \
        <= recs["inner"][END] <= recs["outer"][END]


def test_request_id_is_inherited_on_its_thread_only(on):
    seen = {}

    def other():
        with tracing.span("elsewhere") as sp:
            seen["other"] = sp.request_id

    with tracing.request_span("http.request") as req:
        with tracing.span("suggest") as sp:
            seen["child"] = sp.request_id
            t = threading.Thread(target=other)
            t.start()
            t.join(5.0)
    with tracing.span("after") as sp:
        seen["after"] = sp.request_id
    assert req.request_id == req.id
    assert seen == {"child": req.id, "other": None, "after": None}
    recs = {r[NAME]: r for r in tracing.drain()}
    assert recs["suggest"][REQUEST] == recs["http.request"][ID]


def test_cap_drops_new_spans_and_counts_them(on, monkeypatch):
    monkeypatch.setattr(tracing, "CAP", 3)
    for i in range(5):
        with tracing.span(f"s{i}"):
            pass
    assert [r[NAME] for r in tracing.drain()] == ["s0", "s1", "s2"]
    assert tracing.dropped() == 2
    tracing.enable()
    assert tracing.dropped() == 0


# --------------------------------------------------- the service's spans
def _space():
    return Space([Param("x", "double", 0, 1),
                  Param("y", "double", 1e-4, 1e0, log=True)])


def _f(a):
    return -((a["x"] - 0.62) ** 2 + (np.log10(a["y"]) + 2.0) ** 2)


def _wait(predicate, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return predicate()


@pytest.fixture(scope="module")
def served():
    """A GP experiment behind the HTTP server, traced from a warm queue:
    one suggest that the queue serves, one that asks for more than the
    queue holds (a miss), and the executor's dispatches meanwhile."""
    depth = 4
    backend = LocalClient(tempfile.mkdtemp())
    server = serve_api(backend).start()
    client = HTTPClient(server.url)
    cfg = ExperimentConfig(
        name="traced", space=_space(), optimizer="gp", budget=400,
        parallel=4, prefetch=depth,
        optimizer_options={"n_init": 4, "fit_steps": 10,
                           "warm_fit_steps": 5, "refit_every": 2,
                           "candidates": 64})
    exp = backend.create_experiment(
        CreateExperiment(config=cfg.to_json())).exp_id
    try:
        for _ in range(12):
            s = backend.suggest(exp, 1).suggestions[0]
            backend.observe(ObserveRequest(
                exp, s.suggestion_id, s.assignment,
                _f(strip_internal(s.assignment))))
        assert _wait(lambda: backend.status(exp).prefetched >= depth)
        tracing.enable()
        hit = client.suggest(exp, 1)
        miss = client.suggest(exp, depth + 2)
        for s in hit.suggestions + miss.suggestions:
            backend.observe(ObserveRequest(
                exp, s.suggestion_id, s.assignment,
                _f(strip_internal(s.assignment))))
        recs = []
        assert _wait(lambda: recs.extend(tracing.drain()) or {
            "exec.install", "exec.gather_wait"} <= {r[NAME] for r in recs}), \
            sorted({r[NAME] for r in recs})
        time.sleep(0.2)
        recs.extend(tracing.drain())
        yield recs
    finally:
        tracing.disable()
        tracing.drain()
        backend.stop(exp)
        client.close()
        server.shutdown()


def _by_id(recs):
    return {r[ID]: r for r in recs}


def _chain(recs, rec):
    """Names from ``rec`` up to its root."""
    ids = _by_id(recs)
    out = [rec[NAME]]
    while rec[PARENT] in ids:
        rec = ids[rec[PARENT]]
        out.append(rec[NAME])
    return out


def test_hit_and_miss_requests_record_the_suggest_path(served):
    reqs = [r for r in served if r[NAME] == "http.request"
            and r[ATTRS] == {"route": "suggestions"}]
    assert len(reqs) == 2
    for req in reqs:
        mine = [r for r in served if r[REQUEST] == req[ID]]
        chains = {tuple(_chain(served, r)) for r in mine}
        assert ("suggest.lock_wait", "suggest", "http.request") in chains
    hit, miss = sorted(reqs, key=lambda r: r[START])
    assert not any(r[NAME] == "suggest.miss_wait" and r[REQUEST] == hit[ID]
                   for r in served)
    waits = [r for r in served if r[NAME] == "suggest.miss_wait"
             and r[REQUEST] == miss[ID]]
    assert len(waits) == 1
    assert _chain(served, waits[0]) == [
        "suggest.miss_wait", "suggest", "http.request"]
    # whichever thread served the parked slot names the request it
    # answered, and did so while the request waited
    serves = [r for r in served if r[NAME] == "miss.serve"
              and miss[ID] in r[ATTRS]["requests"]]
    assert serves and serves[0][ATTRS]["slots"] >= 1
    assert waits[0][START] <= serves[0][START] <= waits[0][END]


def test_executor_dispatch_records_queue_wait_dispatch_install(served):
    """On the worker that ran it, a dispatch follows its job's time in
    the executor queue and precedes the install of its lanes."""
    seen = 0
    for d in (r for r in served if r[NAME] == "exec.dispatch"):
        assert d[ATTRS]["kind"] in ("fit", "ask")
        assert d[ATTRS]["lanes"] >= 1 and d[ATTRS]["bucket"] >= 1
        assert d[ATTRS]["h2d_bytes"] > 0
        same = sorted((r for r in served if r[THREAD] == d[THREAD]),
                      key=lambda r: r[START])
        before = [r[NAME] for r in same if r[END] <= d[START]]
        after = [r[NAME] for r in same if r[START] >= d[END]]
        if "exec.queue_wait" in before:     # popped after tracing began
            assert after[0] == "exec.install"
            seen += 1
    assert seen


@pytest.mark.parametrize("name", ["suggest.lock_wait", "suggest.miss_wait",
                                  "exec.queue_wait", "exec.gather_wait",
                                  "pump.lock_wait"])
def test_waiting_spans_end_in_wait(served, name):
    """The spans at blocking points carry the ``_wait`` suffix, which is
    how readers tell waiting from work; none of the work spans does."""
    names = {r[NAME] for r in served}
    assert name in names
    work = names - {"suggest.lock_wait", "suggest.miss_wait",
                    "exec.queue_wait", "exec.gather_wait", "pump.lock_wait"}
    assert work and not any(n.endswith("_wait") for n in work)
    assert {"http.request", "suggest", "pump.tick", "exec.dispatch",
            "exec.install", "opt.ask"} <= work
