"""Fleet subsystem: consistent-hash routing, heartbeat liveness,
admission control, and the dead-worker / dead-shard fault paths
(requeue-and-serve-exactly-once, config-less failover adoption).

The in-process tests drive the manager's event loop deterministically
(``tick()`` + fake-clock registry); the kill −9 tests use real
subprocesses so the connection-reset path — not a polite shutdown — is
what the router and manager see.
"""
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from repro.api.local import LocalClient
from repro.api.protocol import (ApiError, CreateExperiment, E_FLEET_BUSY,
                                ObserveRequest, ReportRequest)
from repro.core import ExperimentConfig, Orchestrator, Param, Space
from repro.fleet import (FleetClient, FleetManager, HashRing, S_ALIVE,
                         S_DEAD, S_REGISTERED, S_SUSPECT, WorkerRegistry,
                         serve_fleet)

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


def _space():
    return Space([Param("x", "double", 0, 1)])


def _cfg(**kw):
    kw.setdefault("optimizer", "random")
    kw.setdefault("space", _space())
    return ExperimentConfig(**kw)


def _cfg_json(name, budget=6, **kw):
    return dict(_cfg(name=name, budget=budget, **kw).to_json())


def _inproc_fleet(n=3, root=None, **kw):
    """Manager over n in-process LocalClient shards sharing one store."""
    root = root or tempfile.mkdtemp()
    manager = FleetManager(**kw)
    for i in range(n):
        manager.add_shard(LocalClient(root), shard_id=f"shard-{i}")
    return manager, root


# ------------------------------------------------------------------ hashring
def test_hashring_owner_is_stable_and_minimally_disrupted():
    keys = [f"exp-{i}" for i in range(200)]
    r1 = HashRing(["a", "b", "c"])
    r2 = HashRing(["a", "b", "c"])
    # blake2b: two independent rings (≈ two processes) agree on every key
    assert [r1.owner(k) for k in keys] == [r2.owner(k) for k in keys]
    before = {k: r1.owner(k) for k in keys}
    r1.remove("b")
    after = {k: r1.owner(k) for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    # consistent hashing: ONLY b's keys re-home
    assert all(before[k] == "b" for k in moved)
    assert all(after[k] in ("a", "c") for k in keys)
    # balance: every node owns a non-trivial share
    spread = HashRing(["a", "b", "c", "d"]).spread(keys)
    assert all(v > len(keys) / 16 for v in spread.values()), spread


def test_hashring_add_remove_roundtrip():
    ring = HashRing(["a", "b"])
    assert "a" in ring and len(ring) == 2
    ring.add("a")                       # idempotent
    assert len(ring) == 2
    ring.remove("missing")              # no-op
    ring.remove("a")
    assert "a" not in ring
    assert all(ring.owner(f"k{i}") == "b" for i in range(20))
    ring.remove("b")
    assert ring.owner("k") is None


# ------------------------------------------------------------------ registry
def test_registry_state_machine_with_fake_clock():
    reg = WorkerRegistry(period=1.0)    # suspect at 1s, dead at 2s silent
    reg.register("w1", now=0.0)
    assert reg.state("w1") == S_REGISTERED
    assert reg.beat("w1", now=0.5) == S_ALIVE
    assert reg.sweep(now=1.0) == []     # 0.5s silent: still alive
    assert reg.state("w1") == S_ALIVE
    reg.sweep(now=1.8)                  # 1.3s silent: suspect
    assert reg.state("w1") == S_SUSPECT
    assert reg.beat("w1", now=2.0) == S_ALIVE   # beat recovers suspect
    dead = reg.sweep(now=4.5)           # 2.5s silent: dead
    assert [r.worker_id for r in dead] == ["w1"]
    assert reg.state("w1") == S_DEAD
    assert reg.sweep(now=5.0) == []     # dead reported exactly once
    # a dead worker re-registering is a NEW incarnation with clean holdings
    reg.get("w1").holdings = {"e": ["s1"]}
    rec = reg.register("w1", now=6.0)
    assert rec.state == S_REGISTERED and rec.holdings == {}


def test_registry_beat_autoregisters_and_carries_holdings():
    reg = WorkerRegistry(period=1.0)
    # manager restart: an unknown worker's beat must not be dropped
    assert reg.beat("w9", holdings={"e1": ["sA", "sB"]}, now=0.0) == S_ALIVE
    assert reg.get("w9").holdings == {"e1": ["sA", "sB"]}
    dead = reg.sweep(now=10.0)
    assert [r.worker_id for r in dead] == ["w9"]
    assert dead[0].holdings == {"e1": ["sA", "sB"]}


# ------------------------------------------------------------------- routing
def test_fleet_routes_and_spreads_experiments_across_shards():
    manager, _ = _inproc_fleet(3)
    client = FleetClient(manager, heartbeat=False)
    owners = set()
    for i in range(8):
        eid = client.create_experiment(
            CreateExperiment(config=_cfg_json(f"route-{i}", budget=2),
                             exp_id=f"exp-route-{i:02d}")).exp_id
        owners.add(manager.owner_of(eid).shard_id)
        batch = client.suggest(eid, 1)
        assert len(batch) == 1
        s = batch.suggestions[0]
        r = client.observe(ObserveRequest(eid, s.suggestion_id,
                                          s.assignment, value=0.5))
        assert r.accepted
        assert client.status(eid).observations == 1
    # 8 experiments over 3 shards: consistent hashing spreads them
    assert len(owners) > 1
    # the experiment lives ONLY on its owner shard
    eid = "exp-route-00"
    owner = manager.owner_of(eid).shard_id
    for sid, handle in manager._shards.items():
        assert (eid in handle.client._exps) == (sid == owner)
    client.close()


def test_fleet_map_versioning_on_membership_change():
    manager, root = _inproc_fleet(2)
    v0 = manager.shard_map().version
    manager.add_shard(LocalClient(root), shard_id="shard-late")
    m = manager.shard_map()
    assert m.version == v0 + 1 and "shard-late" in m.shards
    manager.remove_shard("shard-late")
    assert manager.shard_map().version == v0 + 2
    client = FleetClient(manager, heartbeat=False)
    assert client.map_version == v0 + 2
    client.close()


# ----------------------------------------------------------------- admission
def test_admission_redirects_create_away_from_saturated_owner():
    manager, _ = _inproc_fleet(3, admit_backlog=4)
    exp_id = "exp-sat-1"
    owner = manager.owner_of(exp_id)
    owner.load = {"backlog": 9, "duty": 0.0, "live": 5}   # saturated
    client = FleetClient(manager, heartbeat=False)
    resp = client.create_experiment(
        CreateExperiment(config=_cfg_json("sat", budget=4), exp_id=exp_id))
    m = manager.shard_map()
    assert m.overrides.get(exp_id) not in (None, owner.shard_id)
    assert manager.stats["redirects"] == 1
    # the override routes ALL later traffic: suggest works via the client
    assert len(client.suggest(resp.exp_id, 1)) == 1
    # redirect target actually hosts it
    target = manager._shards[m.overrides[exp_id]]
    assert exp_id in target.client._exps
    assert exp_id not in owner.client._exps
    client.close()


def test_admission_busy_when_every_shard_is_saturated():
    manager, _ = _inproc_fleet(2, admit_duty=0.5)
    for handle in manager._shards.values():
        handle.load = {"backlog": 0, "duty": 0.9, "live": 4}
    with pytest.raises(ApiError) as ei:
        manager.create_experiment(
            CreateExperiment(config=_cfg_json("busy"), exp_id="exp-busy"))
    assert ei.value.code == E_FLEET_BUSY
    assert manager.stats["busy_rejections"] == 1
    # nothing was created anywhere
    assert all("exp-busy" not in h.client._exps
               for h in manager._shards.values())


def test_shard_load_probe_reports_executor_signal():
    manager, _ = _inproc_fleet(1)
    handle = next(iter(manager._shards.values()))
    assert handle.probe()
    assert {"experiments", "live", "pending", "backlog", "duty"} \
        <= set(handle.load)


# --------------------------------------------------------------- fault paths
def test_dead_worker_holdings_requeued_and_served_exactly_once():
    manager, _ = _inproc_fleet(2)
    client = FleetClient(manager, heartbeat=False)
    eid = client.create_experiment(
        CreateExperiment(config=_cfg_json("dw", budget=6),
                         exp_id="exp-dw")).exp_id
    batch = client.suggest(eid, 3)
    taken = {s.suggestion_id for s in batch.suggestions}
    assert len(taken) == 3
    # worker heartbeats its holdings, then goes silent
    reg = manager.registry
    reg.beat("w-dead", holdings=client.holdings(), now=0.0)
    for rec in reg.sweep(now=10.0):
        manager._on_dead_worker(rec)
    assert manager.stats["requeued"] == 3
    # requeued suggestions keep their ids and are served before fresh ones
    survivor = FleetClient(manager, heartbeat=False)
    got = survivor.suggest(eid, 6)
    ids = [s.suggestion_id for s in got.suggestions]
    assert set(ids[:3]) == taken            # orphans first, same ids
    assert len(ids) == len(set(ids)) == 6   # budget headroom intact
    # ...exactly once: nothing left to serve
    assert len(survivor.suggest(eid, 6)) == 0
    for s in got.suggestions:
        r = survivor.observe(ObserveRequest(eid, s.suggestion_id,
                                            s.assignment, value=0.5))
        assert r.accepted and not r.duplicate
    st = survivor.status(eid)
    assert st.observations == 6 and st.pending == 0
    # no leaked lies: the shard's optimizer has no outstanding pendings
    owner = manager.owner_of(eid)
    state = owner.client._exps[eid]
    assert state.pending == {}
    assert not getattr(state.optimizer, "_pending", {})
    client.close()
    survivor.close()


def test_requeue_tolerates_observed_and_unknown_suggestions():
    manager, _ = _inproc_fleet(1)
    client = FleetClient(manager, heartbeat=False)
    eid = client.create_experiment(
        CreateExperiment(config=_cfg_json("rq", budget=3),
                         exp_id="exp-rq")).exp_id
    s = client.suggest(eid, 1).suggestions[0]
    assert client.requeue(eid, s.suggestion_id) is True
    assert client.requeue(eid, s.suggestion_id) is True   # dedupe, no double
    got = client.suggest(eid, 3)
    assert [x.suggestion_id for x in got.suggestions][0] == s.suggestion_id
    assert len({x.suggestion_id for x in got.suggestions}) == len(got)
    r = client.observe(ObserveRequest(eid, s.suggestion_id, s.assignment,
                                      value=1.0))
    assert r.accepted
    # already observed -> not requeueable; unknown -> not requeueable
    assert client.requeue(eid, s.suggestion_id) is False
    assert client.requeue(eid, "s-never-existed") is False
    client.close()


def test_scheduler_crash_mid_report_through_router_leaves_no_orphans():
    """InjectedCrash after a progress report, with suggestions routed
    through the fleet: no orphaned pending, no stale constant-liar lie."""
    from repro.core.faults import InjectedCrash
    manager, root = _inproc_fleet(2)
    fleet_client = FleetClient(manager, heartbeat=False)
    orch = Orchestrator(root, client=fleet_client)

    def trial(a, ctx):
        ctx.report(1, a["x"])
        raise InjectedCrash("mid-report crash")

    cfg = _cfg(name="fleet-midreport", budget=4, parallel=2, max_retries=0)
    exp = orch.run(cfg, trial_fn=trial)
    for handle in manager._shards.values():
        state = handle.client._exps.get(exp)
        if state is None:
            continue
        assert state.pending == {}, "crashed trials must not hold pending"
        assert not getattr(state.optimizer, "_pending", {})
    obs = orch.store.load_observations(exp)
    assert len(obs) == 4 and all(o.failed for o in obs)
    assert fleet_client.holdings() == {}, "observed holdings must clear"
    fleet_client.close()


def test_fail_nodes_during_pause_resume_through_router():
    """cluster.fail_nodes (via ChaosMonkey) revokes leases while trials
    pause/resume under an early-stopping policy, with every suggestion
    routed through the fleet: the run still completes exactly on budget,
    all leases return to the pool, and no shard is left with orphaned
    pending suggestions or stale constant-liar lies."""
    from repro.core import Resources
    from repro.core.faults import ChaosMonkey
    manager, root = _inproc_fleet(2)
    fleet_client = FleetClient(manager, heartbeat=False)
    orch = Orchestrator(root, client=fleet_client)
    orch.cluster_create({"cluster_name": "f",
                         "pools": [{"name": "tpu", "resource": "tpu",
                                    "chips": 8, "chips_per_node": 2}]})
    cluster = orch.cluster_get("f")

    def trial(a, ctx):
        start = ctx.resume_step or 0
        for step in (1, 2, 4):
            if step <= start:
                continue
            time.sleep(0.005)
            ctx.report(step, a["x"])
        return a["x"]

    monkey = ChaosMonkey(cluster, "tpu", period_s=0.05, heal_s=0.02).start()
    try:
        cfg = _cfg(name="fleet-revoke", budget=6, parallel=3,
                   resources=Resources(pool="tpu", chips=2), max_retries=3,
                   early_stop={"min_steps": 1, "eta": 2, "mode": "pause"})
        exp = orch.run(cfg, trial_fn=trial, cluster="f")
    finally:
        monkey.stop()
    assert monkey.kills >= 1
    obs = orch.store.load_observations(exp)
    assert len(obs) == 6, "work must survive node failures"
    assert orch.cluster_status("f")["pools"]["tpu"]["free"] == 8
    for handle in manager._shards.values():
        state = handle.client._exps.get(exp)
        if state is not None:
            assert state.pending == {}
            assert not state.orphaned
            assert not getattr(state.optimizer, "_pending", {})
    assert fleet_client.holdings() == {}
    fleet_client.close()


def test_dead_shard_failover_adopts_from_shared_store():
    """Kill a shard's listener + sever its connections: the manager drops
    it from the ring, the ring successor adopts the experiment out of the
    shared store, and the router re-homes transparently."""
    root = tempfile.mkdtemp()
    srv = serve_fleet(root, shards=3, period=0.2).start()
    try:
        client = FleetClient(srv.url, heartbeat=True)
        eid = client.create_experiment(CreateExperiment(
            config=_cfg_json("failover", budget=8),
            exp_id="exp-failover")).exp_id
        pre = client.suggest(eid, 2)
        for s in pre.suggestions:
            assert client.observe(ObserveRequest(
                eid, s.suggestion_id, s.assignment, value=0.7)).accepted
        owner = srv.manager.owner_of(eid).shard_id
        victim = next(s for i, s in enumerate(srv.owned_shards)
                      if f"shard-{i}" == owner)
        victim._httpd.shutdown()
        victim._httpd.server_close()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and srv.manager.stats["dead_shards"] < 1:
            time.sleep(0.05)
        assert srv.manager.stats["dead_shards"] == 1
        assert owner not in srv.manager.shard_map().shards
        client.beat()           # pick up the post-death map
        post = client.suggest(eid, 2)
        assert len(post) == 2
        pre_ids = {s.suggestion_id for s in pre.suggestions}
        assert not (pre_ids & {s.suggestion_id for s in post.suggestions}), \
            "suggestion ids must be unique across shard incarnations"
        for s in post.suggestions:
            r = client.observe(ObserveRequest(eid, s.suggestion_id,
                                              s.assignment, value=0.6))
            assert r.accepted and not r.duplicate
        st = client.status(eid)
        assert st.observations == 4 and st.pending == 0
        client.close()
    finally:
        srv.shutdown()


# ---------------------------------------------------------------- kill -9
_SHARD_SCRIPT = """
import sys
sys.path.insert(0, {src!r})
from repro.api.http import serve_api
srv = serve_api({root!r}, port=0)
print(srv.url, flush=True)
srv.serve_forever()
"""

_WORKER_SCRIPT = """
import sys, time
sys.path.insert(0, {src!r})
from repro.fleet import FleetClient
client = FleetClient({fleet_url!r}, worker_id="victim", heartbeat=True)
held = []
for eid in {exp_ids!r}:
    held += [s.suggestion_id for s in client.suggest(eid, 1).suggestions]
client.beat()                     # holdings reach the manager
print("HELD " + " ".join(held), flush=True)
time.sleep(600)                   # wedge until killed
"""


def _spawn(script, **fmt):
    proc = subprocess.Popen([sys.executable, "-c", script.format(**fmt)],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    line = proc.stdout.readline().strip()
    assert line, proc.stderr.read()
    return proc, line


def test_concurrent_beats_leave_the_manager_the_latest_holdings():
    """The heartbeat thread and an explicit ``beat()`` can overlap.  The
    manager keeps the holdings of the beat it applies last, so the beat
    whose snapshot is older must not be applied after the newer one."""
    manager, _ = _inproc_fleet(n=1)
    client = FleetClient(manager, worker_id="w", heartbeat=False)
    first_in, release = threading.Event(), threading.Event()
    real = manager.heartbeat

    def slow_first(req, on_dead=None):
        if not first_in.is_set():
            first_in.set()
            release.wait(5)
        return real(req, on_dead)

    manager.heartbeat = slow_first
    old = threading.Thread(target=client.beat)
    old.start()
    assert first_in.wait(5)
    with client._lock:                      # a suggestion taken meanwhile
        client._holdings["exp-a"] = {"s-1"}
    new = threading.Thread(target=client.beat)
    new.start()
    time.sleep(0.2)
    release.set()
    old.join(5)
    new.join(5)
    assert manager.registry.get("w").holdings == {"exp-a": ["s-1"]}
    client.close()


def test_api_server_loads_optimizers_before_its_first_request():
    """Loading the optimizer modules takes seconds; a shard's first create
    would hold the backend's lock through it, and the fleet's load probes
    would time out and declare a live shard dead."""
    code = ("import sys, tempfile\n"
            "from repro.api import serve_api\n"
            "before = 'repro.core.suggest.bayesopt' in sys.modules\n"
            "serve_api(tempfile.mkdtemp())\n"
            "print(before, 'repro.core.suggest.bayesopt' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [SRC, os.environ.get("PYTHONPATH", "")]), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                         capture_output=True, text=True)
    assert out.stdout.split() == ["False", "True"], out.stderr


def test_kill9_scheduler_requeues_within_two_periods():
    """Acceptance: kill −9 a scheduler holding pending suggestions under
    k=8-experiment load — every held suggestion is requeued and served to
    a survivor within ~2 heartbeat periods, exactly once, with no
    duplicate observes and no leaked lies."""
    root = tempfile.mkdtemp()
    period = 0.5
    srv = serve_fleet(root, shards=2, period=period).start()
    worker = None
    try:
        boss = FleetClient(srv.url, heartbeat=False)
        exp_ids = []
        for i in range(8):
            exp_ids.append(boss.create_experiment(CreateExperiment(
                config=_cfg_json(f"k9-{i}", budget=3),
                exp_id=f"exp-k9-{i}")).exp_id)
        worker, line = _spawn(_WORKER_SCRIPT, src=SRC, fleet_url=srv.url,
                              exp_ids=exp_ids)
        held = set(line.split()[1:])
        assert len(held) == 8
        t_kill = time.monotonic()
        os.kill(worker.pid, signal.SIGKILL)
        deadline = t_kill + 30
        while time.monotonic() < deadline \
                and srv.manager.stats["requeued"] < 8:
            time.sleep(0.05)
        t_requeued = time.monotonic()
        assert srv.manager.stats["requeued"] == 8, srv.manager.stats
        # dead_after defaults to 2 periods; allow scheduling slack on top
        assert t_requeued - t_kill < 2 * period + 3.0
        # survivors get exactly the held suggestions, once each
        survivor = FleetClient(srv.url, heartbeat=False)
        served = []
        for eid in exp_ids:
            got = survivor.suggest(eid, 3)
            ids = [s.suggestion_id for s in got.suggestions]
            assert len(set(ids)) == len(ids)
            served += [(eid, s) for s in got.suggestions]
        assert held <= {s.suggestion_id for _, s in served}
        for eid, s in served:
            r = survivor.observe(ObserveRequest(eid, s.suggestion_id,
                                                s.assignment, value=0.5))
            assert r.accepted and not r.duplicate, (eid, s.suggestion_id)
        for eid in exp_ids:
            st = survivor.status(eid)
            assert st.observations == 3 and st.pending == 0, st.to_json()
        boss.close()
        survivor.close()
    finally:
        if worker is not None and worker.poll() is None:
            worker.kill()
        srv.shutdown()


@pytest.mark.slow
def test_kill9_shard_under_load_survivors_serve_all_experiments():
    """Acceptance: kill −9 one SHARD process under k=8-experiment load;
    survivors adopt its experiments from the shared store and every
    experiment completes exactly on budget — no duplicate observes."""
    root = tempfile.mkdtemp()
    period = 0.5
    shard_a, url_a = _spawn(_SHARD_SCRIPT, src=SRC, root=root)
    shard_b, url_b = _spawn(_SHARD_SCRIPT, src=SRC, root=root)
    srv = serve_fleet(shard_urls=[url_a, url_b], period=period).start()
    try:
        client = FleetClient(srv.url, heartbeat=True)
        exp_ids = []
        for i in range(8):
            exp_ids.append(client.create_experiment(CreateExperiment(
                config=_cfg_json(f"ks-{i}", budget=4),
                exp_id=f"exp-ks-{i}")).exp_id)
        first = {eid: client.suggest(eid, 2) for eid in exp_ids}
        for eid, batch in first.items():
            s = batch.suggestions[0]
            assert client.observe(ObserveRequest(
                eid, s.suggestion_id, s.assignment, value=0.4)).accepted
        os.kill(shard_a.pid, signal.SIGKILL)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline \
                and srv.manager.stats["dead_shards"] < 1:
            time.sleep(0.05)
        assert srv.manager.stats["dead_shards"] == 1
        client.beat()
        # the client still holds each experiment's second suggestion; a
        # real scheduler reports those results after failover.  On the
        # survivor this is the normal path; on an adopted experiment the
        # id is untracked (the pending set died with the shard) and the
        # service accepts it as real data.
        observed = set()
        for eid in exp_ids:
            s = first[eid].suggestions[1]
            r = client.observe(ObserveRequest(eid, s.suggestion_id,
                                              s.assignment, value=0.3))
            assert r.accepted and not r.duplicate, (eid, s.suggestion_id)
            observed.add((eid, s.suggestion_id))
        # drive every experiment to completion: the adopting shard
        # reclaimed the dead shard's pending budget via log replay, so
        # fresh suggests cover the remainder.  Ids never collide.
        for eid in exp_ids:
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                st = client.status(eid)
                if st.observations >= 4:
                    break
                got = client.suggest(eid, 4)
                if not got.suggestions:
                    time.sleep(0.1)
                    continue
                for s in got.suggestions:
                    r = client.observe(ObserveRequest(
                        eid, s.suggestion_id, s.assignment, value=0.5))
                    assert r.accepted and not r.duplicate
                    key = (eid, s.suggestion_id)
                    assert key not in observed, "duplicate observe"
                    observed.add(key)
            st = client.status(eid)
            assert st.observations == 4 and st.pending == 0, \
                (eid, st.to_json())
        client.close()
    finally:
        for p in (shard_a, shard_b):
            if p.poll() is None:
                p.kill()
        srv.shutdown()


# ------------------------------------------------------- graceful shutdown
@pytest.mark.parametrize("verb,extra", [
    ("serve-api", []),
    ("serve-fleet", ["--shards", "1"]),
])
def test_sigterm_shuts_down_serve_processes_cleanly(verb, extra):
    root = tempfile.mkdtemp()
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.cli", "--store", root,
         verb, "--port", "0"] + extra,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, PYTHONPATH=SRC, PYTHONUNBUFFERED="1"))
    line = proc.stdout.readline()
    assert "listening on" in line, proc.stderr.read()
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=20)
    assert proc.returncode == 0, err
    assert "shut down cleanly" in err, err


# -------------------------------------------------- file-handle discipline
def test_terminal_trial_evicts_metric_handle():
    root = tempfile.mkdtemp()
    client = LocalClient(root)
    eid = client.create_experiment(CreateExperiment(
        config=_cfg_json("evict", budget=2))).exp_id
    s = client.suggest(eid, 1).suggestions[0]
    client.report(ReportRequest(eid, "t1", step=1, value=0.5,
                                suggestion_id=s.suggestion_id))
    # the metric stream is keyed by suggestion_id when one is reported
    p = client.store.metric_path(eid, s.suggestion_id)
    assert p in client.store._log_handles, "report keeps the handle warm"
    client.observe(ObserveRequest(eid, s.suggestion_id, s.assignment,
                                  value=0.5, trial_id="t1"))
    assert p not in client.store._log_handles, \
        "terminal observe must evict the trial's metric handle"


def test_open_handles_stay_bounded_at_fleet_scale():
    """Fleet-sized load: many trials across many experiments, every trial
    reaching a terminal state — open handles stay proportional to LIVE
    trials (here: 0), far under the LRU cap."""
    from repro.core.store import LOG_HANDLE_CACHE
    root = tempfile.mkdtemp()
    client = LocalClient(root)
    n_exp, per_exp = 6, 20      # 120 trials > LOG_HANDLE_CACHE (64)
    for e in range(n_exp):
        eid = client.create_experiment(CreateExperiment(
            config=_cfg_json(f"cap-{e}", budget=per_exp))).exp_id
        for t in range(per_exp):
            s = client.suggest(eid, 1).suggestions[0]
            tid = f"t{t:03d}"
            client.report(ReportRequest(eid, tid, step=1, value=0.1,
                                        suggestion_id=s.suggestion_id))
            client.observe(ObserveRequest(eid, s.suggestion_id,
                                          s.assignment, value=0.1,
                                          trial_id=tid))
        assert client.store.open_handles() <= LOG_HANDLE_CACHE
    assert client.store.open_handles() == 0, \
        "all trials terminal -> all metric handles evicted"


# ------------------------------------------------- sparse quality counter
def test_sparse_vs_exact_regret_counters_in_status():
    root = tempfile.mkdtemp()
    client = LocalClient(root)
    eid = client.create_experiment(CreateExperiment(
        config=_cfg_json("quality", budget=8))).exp_id
    state = client._exps[eid]
    # mint two sparse-served and two exact-served suggestions, observe
    # with known regrets against the running best
    with state.lock:
        sugg = [client._mint(state, {"x": 0.5}, sparse=(i % 2 == 0))
                for i in range(4)]
    values = [1.0, 0.9, 0.8, 1.0]   # regrets vs best-so-far: 0, .1, .2, 0
    for s, v in zip(sugg, values):
        client.observe(ObserveRequest(eid, s.suggestion_id, s.assignment,
                                      value=v))
    q = client.status(eid).pump["quality"]
    assert q["sparse_n"] == 2 and q["exact_n"] == 2
    assert q["sparse_mean_regret"] == pytest.approx(0.1)   # (0 + .2) / 2
    assert q["exact_mean_regret"] == pytest.approx(0.05)   # (.1 + 0) / 2


def test_quality_counters_empty_until_observations():
    root = tempfile.mkdtemp()
    client = LocalClient(root)
    eid = client.create_experiment(CreateExperiment(
        config=_cfg_json("quality0", budget=2))).exp_id
    q = client.status(eid).pump["quality"]
    assert q["sparse_n"] == 0 and q["sparse_mean_regret"] is None
    assert q["exact_n"] == 0 and q["exact_mean_regret"] is None


# --------------------------------------------------- transport robustness
def test_http_client_backoff_counters_on_refused_connect():
    """Bounded exponential backoff with full jitter: a refused connect
    retries up to ``retry_attempts`` times (any verb — the server
    provably never saw the request), then surfaces ``service
    unreachable``; every step lands in the per-client counters."""
    from repro.api.http import HTTPClient
    from repro.api.protocol import E_INTERNAL
    # a port nothing listens on -> instant ConnectionRefusedError
    c = HTTPClient("http://127.0.0.1:9", retry_attempts=3,
                   retry_base=0.001, retry_cap=0.002, retry_seed=0)
    with pytest.raises(ApiError) as ei:
        c.load()
    assert ei.value.code == E_INTERNAL
    assert "unreachable" in str(ei.value)
    assert c.stats["refused"] == 3, "one refused connect per attempt"
    assert c.stats["backoffs"] == 2, "every retry but the last slept"
    assert c.stats["gave_up"] == 1
    # non-idempotent verbs retry refused connects too (send-phase failure
    # = never reached the service), with the same bound
    with pytest.raises(ApiError):
        c.suggest("exp-x", 1)
    assert c.stats["refused"] == 6 and c.stats["gave_up"] == 2
    c.close()


def test_http_status_carries_transport_counters():
    from repro.api.http import HTTPClient, serve_api
    root = tempfile.mkdtemp()
    srv = serve_api(root).start()
    try:
        c = HTTPClient(srv.url, retry_seed=0)
        eid = c.create_experiment(CreateExperiment(
            config=_cfg_json("transport", budget=2))).exp_id
        st = c.status(eid)
        assert st.transport is not None
        assert {"retries", "backoffs", "backoff_ms", "refused",
                "gave_up"} <= set(st.transport)
        assert st.transport["gave_up"] == 0
        c.close()
    finally:
        srv.shutdown()


def test_probe_deadline_counts_wedged_shard_toward_death():
    """S2: a shard that accepts the probe but never answers must not
    stall the manager's tick — the shared per-round deadline expires,
    the probe counts as FAILED, and the shard progresses to dead
    instead of hiding behind the slow-not-dead re-beat guard."""
    class WedgedClient:
        def __init__(self):
            self.block = threading.Event()

        def load(self):
            self.block.wait(30)         # wedged: never answers
            return {}

    manager = FleetManager(period=0.05, probe_timeout=0.1)
    wedged = WedgedClient()
    manager.add_shard(wedged, shard_id="shard-wedge")
    handle = manager._shards["shard-wedge"]
    t0 = time.monotonic()
    manager.tick()
    # the tick returned promptly (deadline, not the 30s hang)...
    assert time.monotonic() - t0 < 5.0
    # ...and the timed-out probe counted as a failed probe
    assert handle.probe_timeouts >= 1
    assert handle.probe_failures >= 1
    assert manager.stats["probe_timeouts"] >= 1
    deadline = time.monotonic() + 10
    while manager.stats["dead_shards"] < 1:
        assert time.monotonic() < deadline, "wedged shard never died"
        time.sleep(0.05)
        manager.tick()
    assert manager.registry.state("shard-wedge") == S_DEAD
    wedged.block.set()                  # unwedge the probe threads


def test_heartbeat_errors_audited_with_bounded_dedupe():
    """S6: heartbeat failures must never be swallowed silently — the
    audit trail records the first occurrence and every 32nd repeat,
    with a bounded per-error counter; close() joins the beat thread."""
    manager, _ = _inproc_fleet(1)
    fc = FleetClient(manager, heartbeat=False)
    for _ in range(64):
        fc._audit_beat_error(RuntimeError("boom"))
    assert fc.beat_errors() == {"RuntimeError: boom": 64}
    audited = [e for e in fc.events if e["event"] == "beat_error"]
    assert [e["count"] for e in audited] == [1, 32, 64]
    # the error-key table is bounded: distinct errors evict the oldest
    for i in range(40):
        fc._audit_beat_error(ValueError(f"e{i}"))
    assert len(fc.beat_errors()) <= 32
    t0 = time.monotonic()
    fc.close()
    assert time.monotonic() - t0 < 5.0

    # end-to-end: a live beat thread whose manager edge is partitioned
    # lands the failure in the audit trail instead of dropping it
    from repro.core.faults import FaultPlan
    plan = FaultPlan(seed=1)
    plan.partition("w-audit", "manager", at=0)
    plan.tick()
    fc2 = FleetClient(manager, worker_id="w-audit", heartbeat=False,
                      fault_plan=plan)
    with pytest.raises(Exception):
        fc2.beat()
    fc2._hb_thread = threading.Thread(target=fc2._beat_loop, daemon=True)
    fc2._period = 0.02
    fc2._hb_thread.start()
    deadline = time.monotonic() + 5
    while not fc2.beat_errors():
        assert time.monotonic() < deadline, "beat error never audited"
        time.sleep(0.02)
    assert any("InjectedPartition" in k or "unreachable" in k
               for k in fc2.beat_errors())
    fc2.close()
