"""Driver for configurations of ``"kind": "gp_service"``: many GP
experiments served by one suggestion service on one chip.

Set-up (``setup_s``, from process start to the window's first due
request): seed every experiment's observation history into a fresh
store from ``--seed``, start ``serve_api`` over it, create (resume) the
experiments over HTTP, wait until every experiment has fitted its GP
and filled its prefetch queue, compile the lane and batch variants the
window's dispatches use, then drive ``warmup_s`` of the cell's own
traffic.  The window is ``--seconds`` of open-loop traffic from
``bench/loadgen.py`` in a process of its own.  After it: the device's
peak memory, shutdown, then the comparison of ``bench/check.py``.

``Cell`` keeps set-up and windows apart, so that ``bench/knee.py`` and
``bench/control.py`` can drive several windows after one set-up.
"""
from __future__ import annotations

import json
import math
import pathlib
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from bench import check, harness, loadgen, record, traffic
from bench import trace as tmod

TRACE_S = 10.0      # the profiler traces the window's first seconds: a
                    # longer trace slows the host and its reading would
                    # outlast a run's time limit


def _device_only():
    """Profiler options that record the device's programs and ops and
    nothing of the host: the service is host-bound, and host and Python
    tracing slowed it many times over."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    return opts
def from_unit(space, u):
    """The space's codec from the unit cube (double/int, linear/log)."""
    out = {}
    for p, ui in zip(space, u):
        lo, hi = p["bounds"]
        if p.get("log"):
            v = math.exp(math.log(lo) + ui * (math.log(hi) - math.log(lo)))
        else:
            v = lo + ui * (hi - lo)
        v = min(max(v, lo), hi)
        out[p["name"]] = int(round(v)) if p["type"] == "int" else float(v)
    return out


class Data:
    """The cell's inputs, from the seed alone: each experiment's
    objective (a shifted quadratic with a ripple) and its seeded
    history.  Every seed gets the same set of history sizes, in another
    order."""

    def __init__(self, cfg, seed: int):
        rng = np.random.default_rng([int(seed) & (2**63 - 1), 1])
        e, space = cfg["experiments"], cfg["space"]
        d = len(space)
        self.centers = rng.uniform(0.2, 0.8, (e, d))
        lo, hi = cfg["history"]
        self.sizes = rng.permutation(np.linspace(lo, hi, e).round()
                                     .astype(int))
        self.histories = []
        for i in range(e):
            rows = []
            for u in rng.uniform(size=(int(self.sizes[i]), d)):
                a = from_unit(space, u)
                v = loadgen.objective(loadgen.to_unit(space, a),
                                      self.centers[i],
                                      float(rng.standard_normal()))
                rows.append((a, v))
            self.histories.append(rows)


def latencies(gen, t0, seconds, timeout_s):
    """Per suggest due in the window: seconds from due to answer (a
    failure, or an answer later than the timeout, counts at the
    timeout); how many delivered a suggestion inside the window; how
    many failed."""
    lat, delivered, failed = [], 0, 0
    for r in gen["suggests"]:
        if r["phase"] != "window":
            continue
        ok = r["status"] == 200 and bool(r["suggestions"])
        dt = r["done"] - r["due"]
        if not ok or dt > timeout_s:
            failed += 1
            dt = timeout_s
        lat.append(dt)
        delivered += ok and r["done"] <= t0 + seconds
    return np.asarray(lat), delivered, failed


class RunData:
    """What per-layer metric readers read (``bench/metrics/*.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class Cell:
    def __init__(self, c, seed: int):
        self.c, self.cfg, self.tr = c, c["config"], c["traffic"]
        self.seed = int(seed)
        self.space = self.cfg["space"]
        e = self.cfg["experiments"]
        self.ids = [f"{c['workload']['name']}-{i:03d}" for i in range(e)]
        self.exp_json = {
            "space": self.space, "goal": "max", "budget": self.cfg["budget"],
            "parallel": self.cfg["parallel"],
            "optimizer": self.cfg["optimizer"],
            "optimizer_options": self.cfg["optimizer_options"],
            "prefetch": self.cfg["prefetch"],
            "staleness": self.cfg["staleness"]}
        self.root = tempfile.mkdtemp(prefix="bench-store-")
        self.server = None
        self.gen_proc = self._spawn()
        self.seeded = {}

    # ------------------------------------------------------------ set-up
    @staticmethod
    def _spawn():
        """Start a load generator now, so that its imports are done by
        the time it is given a job."""
        return subprocess.Popen([sys.executable, str(loadgen.__file__)],
                                stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE, text=True)

    def setup(self):
        from repro.api import LocalClient, serve_api
        from repro.core.experiment import ExperimentConfig
        from repro.core.store import Store
        from repro.core.suggest.base import Observation

        self.data = Data(self.cfg, self.seed)
        store = Store(self.root)
        for i, exp_id in enumerate(self.ids):
            store.create_experiment(exp_id, ExperimentConfig.from_json(
                dict(self.exp_json, name=exp_id, seed=i)))
            for j, (a, v) in enumerate(self.data.histories[i]):
                store.append_observation(exp_id, Observation(a, v),
                                         trial_id=f"h{j}",
                                         suggestion_id=f"h-{exp_id}-{j}")
            self.seeded[exp_id] = len(self.data.histories[i])
        self.backend = LocalClient(store)
        self.server = serve_api(self.backend).start()
        conn = loadgen.Conn(self.server.url, 60.0)
        for i, exp_id in enumerate(self.ids):
            status, body = conn.call("/v1/experiments", {
                "config": dict(self.exp_json, name=exp_id, seed=i),
                "exp_id": exp_id})
            if status != 200 or body.get("observations") != \
                    self.seeded[exp_id]:
                raise RuntimeError(f"create {exp_id}: {status} {body}")
        self._wait_ready()
        self._prewarm_variants()

    def _wait_ready(self):
        t_end = time.monotonic() + self.cfg["ready_s"]
        from repro.api import pipeline
        # the executor is process-wide: judge only failures from now on
        failed0 = (pipeline.executor_snapshot() or {}).get("failed", 0)
        while True:
            waiting = []
            for i in self.ids:
                st = self.backend.status(i)
                pump = st.pump or {}
                if "pump_error" in pump:
                    raise RuntimeError(f"{i}: pump error "
                                       f"{pump['pump_error']}")
                ex = pump.get("executor") or {}
                if ex.get("failed", 0) > failed0:
                    raise RuntimeError(f"{i}: executor failed: "
                                       f"{ex.get('last_error')}")
                if (st.prefetched < self.cfg["prefetch"]
                        or (pump.get("refit") or {}).get("fits", 0) < 1):
                    waiting.append(i)
            if not waiting:
                return
            if time.monotonic() > t_end:
                raise RuntimeError(f"{len(waiting)} experiments not ready "
                                   f"after {self.cfg['ready_s']} s")
            time.sleep(0.25)

    def _prewarm_variants(self):
        """Compile what the window's dispatches use beyond the pumps'
        own prewarm, for the cell's buckets: co-batched fits at every
        lane pad and every warm step count the adaptive schedule can
        reach there, co-batched asks at every lane pad,
        and the per-lane reads of their results at every pick count."""
        import types

        import jax.numpy as jnp

        from repro.core.suggest import bayesopt, gp
        warm, opts = self.cfg["warm"], self.cfg["optimizer_options"]
        sched = types.SimpleNamespace(warm_fit_steps=opts["warm_fit_steps"],
                                      adaptive=opts["adaptive"])
        d = len(self.space)
        n_cand = opts["candidates"] * 5 // 4
        lo = gp.bucket_size(self.cfg["history"][0])
        for b in warm["buckets"]:
            steps = sorted({bayesopt.BayesOpt._warm_steps_at(sched, n)
                            for n in range(b // 2 + 1, b + 1)}
                           ) if b >= lo else ()
            gp.prewarm_bucket(d, b, fit_steps=steps,
                              fit_lanes=warm["fit_lanes"],
                              k_pads=warm["k_pads"], n_cand=n_cand,
                              select_lanes=warm["select_lanes"])
            if gp.SELECT_PAD + 2 > b:
                continue
            x = np.zeros((2, d))
            x[1] = 0.5
            post = gp.make_posterior(
                gp.GPParams(jnp.full((d,), -0.7), jnp.zeros(()),
                            jnp.full((), -2.0)), x, np.array([0.0, 1.0]),
                bucket=b)
            cand = np.zeros((n_cand, d), np.float32)
            ks = list(range(1, gp.SELECT_PAD + 1))
            for k in ks:
                gp.select_batch(post, cand, np.float32(1.0), k)
            for lanes in warm["select_lanes"]:
                for i in range(0, len(ks), lanes):
                    chunk = (ks[i:i + lanes] * lanes)[:lanes]
                    gp.batched_select([(post, cand, np.float32(1.0), k)
                                       for k in chunk])

    def totals(self) -> dict:
        """Pump counters summed over the experiments, and the shared
        executor's."""
        tot = {"hits": 0, "misses": 0, "coalesced": 0, "invalidated": 0,
               "fits": 0}
        ex = {}
        for i in self.ids:
            pump = self.backend.status(i).pump or {}
            for k in ("hits", "misses", "coalesced", "invalidated"):
                tot[k] += int(pump.get(k, 0))
            tot["fits"] += int((pump.get("refit") or {}).get("fits", 0))
            ex = pump.get("executor") or ex
        for k in ("batched", "lanes", "batched_asks", "ask_lanes",
                  "failed"):
            tot["ex_" + k] = int(ex.get(k, 0))
        return tot

    # ------------------------------------------------------------ window
    def drive(self, seconds: float, seed: int, counter, trace=False,
              rate=None, warm_s=None) -> dict:
        """Warm-up then one window of open-loop traffic; returns what
        the generator recorded and what the service counted."""
        tr = dict(self.tr) if rate is None else dict(self.tr, rate=rate)
        warm_s = float(tr["warmup_s"] if warm_s is None else warm_s)
        e = len(self.ids)
        rec = record.Recorder(seed)
        record.use(rec)
        if self.gen_proc is None:
            self.gen_proc = self._spawn()
        t0 = time.monotonic() + 0.5 + warm_s
        job = {"url": self.server.url, "exp_ids": self.ids,
               "space": self.space, "centers": self.data.centers.tolist(),
               "t0": t0, "warm_s": warm_s, "seconds": seconds,
               "timeout_s": tr["timeout_s"], "wait_s": 60.0,
               "seed": int(seed), "workers": tr["workers"],
               "warm": traffic.schedule(tr, e, warm_s, seed, stream=1),
               "window": traffic.schedule(tr, e, seconds, seed, stream=2)}
        self.gen_proc.stdin.write(json.dumps(job) + "\n")
        self.gen_proc.stdin.flush()
        marks = {}
        tdir = pathlib.Path(self.root) / f"trace-{seed}"

        def at(t):
            time.sleep(max(0.0, t - time.monotonic()))

        def window():
            import jax
            if trace:
                at(t0 - 1.0)
                jax.profiler.start_trace(str(tdir),
                                         profiler_options=_device_only())
                marks["trace_on"] = time.monotonic()
            at(t0)
            marks["stats0"] = self.totals()
            c0 = counter.mark()
            rec.on = True
            if trace:
                at(t0 + min(seconds, TRACE_S))
                marks["trace_off"] = time.monotonic()
                jax.profiler.stop_trace()
            at(t0 + seconds)
            rec.on = False
            marks["compile"] = counter.since(c0)
            marks["stats1"] = self.totals()

        wt = threading.Thread(target=window, daemon=True)
        wt.start()
        out, _ = self.gen_proc.communicate(timeout=warm_s + seconds + 180)
        self.gen_proc = None
        wt.join()
        gen = json.loads(out.strip().splitlines()[-1])
        lat, delivered, failed = latencies(gen, t0, seconds,
                                           tr["timeout_s"])
        res = dict(marks, gen=gen, rec=rec, t0=t0, seconds=seconds,
                   lat=lat, delivered=delivered, failed=failed)
        if trace:
            path = tmod.find_xplane(tdir)
            if path is None:
                raise RuntimeError(f"no trace written under {tdir}")
            res["xplane"] = path
            res["planes"] = tmod.read(path)
            res["window_s"] = marks["trace_off"] - marks["trace_on"]
        return res

    def close(self):
        if self.server is not None:
            self.server.shutdown()
            self.server = None
        if self.gen_proc is not None:
            self.gen_proc.kill()
            self.gen_proc.wait()
            self.gen_proc = None

    def cleanup(self):
        self.close()
        shutil.rmtree(self.root, ignore_errors=True)


def end_to_end(w, t_start) -> dict:
    return {"setup_s": w["t0"] - t_start,
            "suggest_p50_ms": 1e3 * traffic.quantile(w["lat"], 0.5),
            "suggest_rate": w["delivered"] / w["seconds"]}


def tail(w) -> dict:
    """The window's tail and what sets it, printed beside the result and
    bounded by no metric (its spread over seeds is too wide, PERF.md):
    the 95th, 98th and 99th percentiles, and the queue hits and misses
    over the window."""
    return {"suggest_p95_ms": 1e3 * traffic.quantile(w["lat"], 0.95),
            "suggest_p98_ms": 1e3 * traffic.quantile(w["lat"], 0.98),
            "suggest_p99_ms": 1e3 * traffic.quantile(w["lat"], 0.99),
            "misses": w["stats1"]["misses"] - w["stats0"]["misses"],
            "hits": w["stats1"]["hits"] - w["stats0"]["hits"]}


def run(c, seed: int, seconds: float, trace: bool, t_start: float,
        device: dict, keep_trace=None) -> dict:
    import jax
    harness.enable_cache()
    counter = harness.CompileCounter()
    cell = Cell(c, seed)
    try:
        cell.setup()
        w = cell.drive(seconds, seed, counter, trace=trace)
        mem = jax.devices()[0].memory_stats() or {}
        peak = int(mem.get("peak_bytes_in_use", 0))
        cell.close()
        t_ref = time.monotonic()
        numbers = check.guarantees(w["gen"], cell.root, cell.ids,
                                   cell.space, cell.cfg["budget"],
                                   cell.seeded, w["rec"])
        gp_nums = check.gp_numbers(w["rec"], seed)
        compared = gp_nums.pop("_compared")
        numbers.update(gp_nums)
        limits = check.limits(c["workload"]["name"])
        correct, rows = check.judge(numbers, limits)
        print(f"[bench] reference {time.monotonic() - t_ref:.1f} s over "
              f"{compared}; not compared: "
              f"{ {k: v for k, v in numbers.items() if k not in limits} }; "
              f"window compiles {w['compile']}; counters "
              f"{w['stats0']} -> {w['stats1']}", file=sys.stderr)
        e2e = end_to_end(w, t_start)
        print(f"[bench] {json.dumps(e2e)} {json.dumps(tail(w))}",
              file=sys.stderr)
        dev = dict(device, memory_peak_bytes=peak)
        breakdown = None
        if trace:
            planes = w["planes"]
            busy = tmod.busy_s(planes)
            dev.update(busy_s=busy, window_s=w["window_s"])
            run_data = RunData(
                gen=w["gen"], t0=w["t0"], seconds=seconds,
                stats0=w["stats0"], stats1=w["stats1"],
                compile=w["compile"], planes=planes, busy_s=busy,
                window_s=w["window_s"], rec=w["rec"],
                traced=(w["trace_on"], w["trace_off"]),
                device_kind=device["kind"])
            metrics = harness.read_layer_metrics(c["per_layer"], run_data)
            breakdown = {"device_ops": tmod.top_ops(planes),
                         "idle_gaps": tmod.idle_gaps(planes)}
            if keep_trace:
                pathlib.Path(keep_trace).mkdir(parents=True, exist_ok=True)
                shutil.copy(w["xplane"], pathlib.Path(keep_trace)
                            / f"{c['workload']['name']}-{seed}.xplane.pb")
        else:
            units = {m["name"]: m["unit"] for m in c["end_to_end"]}
            metrics = {k: {"value": v, "unit": units[k]}
                       for k, v in e2e.items() if k in units}
        line = harness.result_line(correct, len(w["lat"]), w["failed"],
                                   metrics, dev, rows, breakdown)
        return {"line": line, "checks": rows, "numbers": numbers,
                "e2e": e2e, "window": w}
    finally:
        cell.cleanup()
