"""Bring-up smoke run of the suggestion service on one TPU chip.

    python chip_smoke.py

Everything runs in this one process, in four phases; any exception or
failed check exits non-zero.

(a) device   — prints platform, device kind and count, and fails unless
               device 0 is a TPU.  The script never sets JAX_PLATFORMS.
(b) kernels  — the Pallas GP kernels (NLL, its gradients, EI) at 8 lanes
               x bucket 256 x 8 dims, and the posterior of one exact
               ``fit_gp`` at bucket 256, against float64 NumPy
               references written here; checks that the compiled NLL
               holds a ``tpu_custom_call``.
(c) service  — ``serve_api`` over a fresh store, driven over HTTP: 8
               ``optimizer: gp`` experiments over an 8-parameter space
               with parallel 4, until each holds 160 observations (GP
               buckets reach 256).  Every experiment must then show no
               pump error, at least one off-path hyperfit, co-batched fit
               and ask dispatches with no executor failure, suggestions
               inside the bounds, and an observation log that matches.
(d) trial    — one ``Orchestrator.run`` experiment (budget 2, parallel 1)
               whose trial trains xlstm-125m at its published widths for
               5 steps: every loss finite, parameters on the TPU.

Timings printed on the way are smoke figures of one run, not metrics.
The last line of stdout is ``{"ok": true, "device": {...}}`` and is
printed only when every phase passed.
"""
from __future__ import annotations

import collections
import json
import math
import pathlib
import sys
import tempfile
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
LANES, BUCKET, DIMS = 8, 256, 8
POOL = 1024 + 1024 // 4          # BayesOpt's default candidate pool
N_EXPERIMENTS, PARALLEL, MIN_OBS, BUDGET = 8, 4, 160, 200
SERVICE_DEADLINE_S = 600.0
SETTLE_DEADLINE_S = 240.0
TRAIN_ARCH, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = "xlstm-125m", 5, 8, 1024

# float64 reference tolerances (|got - want| <= ATOL + RTOL * |want|).
# The floor is the chip's f32 exp (relative error up to ~4e-6 where the
# host's is ~1e-7), amplified by the covariance's conditioning: on a v5e
# the NLL lands ~6e-3 and the posterior mean ~6e-3 (objective std ~0.3)
# off float64.  The bars sit ~4x above that floor and far below what a
# bf16 matmul pass does to the same math (the NLL off by ~30).
TOL = {"nll": (2.5e-2, 1e-5), "grad": (4e-2, 2e-3), "ei": (4e-4, 2e-3),
       "post_mean": (2.5e-2, 0.0), "post_sd": (2.5e-2, 0.0)}


class Failed(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileCounter:
    """Compile seconds and persistent-cache hits/writes, from JAX's own
    monitoring events."""

    def __init__(self):
        self.n = collections.Counter()
        self.secs = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        with self._lock:
            self.n[event] += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.secs += secs
                self.n["compiles"] += 1

    def mark(self):
        with self._lock:
            return self.secs, collections.Counter(self.n)

    def since(self, mark) -> str:
        secs, n = mark
        with self._lock:
            d = self.n - n
            return (f"compile_s={self.secs - secs:.1f} "
                    f"compiles={d['compiles']} "
                    f"cache_hits={d['/jax/compilation_cache/cache_hits']} "
                    f"cache_writes={d['/jax/compilation_cache/cache_misses']}")


def check_close(name: str, got, want) -> None:
    atol, rtol = TOL[name]
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise Failed(f"{name}: shape {got.shape} vs {want.shape} or "
                     f"non-finite values")
    err = np.abs(got - want)
    excess = float(np.max(err - (atol + rtol * np.abs(want))))
    log(f"{name}: max_abs_err={float(np.max(err)):.3e} "
        f"(atol {atol:g}, rtol {rtol:g})")
    if excess > 0:
        raise Failed(f"{name}: off the float64 reference by {excess:.3e} "
                     f"beyond tolerance")


# ---------------------------------------------------------------- (a)
def device_check() -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise Failed(f"no TPU: JAX runs on {dev['platform']}")
    return dev


# ---------------------------------------------------------------- (b)
def _cov64(x, ll, la, ln, m):
    """Masked Matérn-5/2 covariance in float64 (identity on padded rows)."""
    xs = x / np.exp(ll)
    r = np.sqrt(((xs[:, None, :] - xs[None, :, :]) ** 2).sum(-1) + 1e-12)
    k = (np.exp(2 * la) * (1 + math.sqrt(5) * r + 5.0 / 3.0 * r * r)
         * np.exp(-math.sqrt(5) * r))
    k = k + (np.exp(2 * ln) + 1e-5) * np.eye(len(x))
    return k * np.outer(m, m) + np.diag(1.0 - m)


def _cross64(a, x, ll, la):
    d = (a[:, None, :] - x[None, :, :]) / np.exp(ll)
    r = np.sqrt((d ** 2).sum(-1) + 1e-12)
    return (np.exp(2 * la) * (1 + math.sqrt(5) * r + 5.0 / 3.0 * r * r)
            * np.exp(-math.sqrt(5) * r))


def _nll64(ll, la, ln, x, y, m):
    low = np.linalg.cholesky(_cov64(x, ll, la, ln, m))
    z = np.linalg.solve(low, y * m)
    return (0.5 * z @ z + np.log(np.diag(low)).sum()
            + 0.5 * m.sum() * math.log(2 * math.pi))


def _ei64(mu, sd, best, xi=0.01):
    imp = mu - best - xi
    z = imp / sd
    cdf = 0.5 * (1 + np.vectorize(math.erf)(z / math.sqrt(2)))
    return imp * cdf + sd * np.exp(-0.5 * z * z) / math.sqrt(2 * math.pi)


def _lane_case(rng, k, b, d):
    x = rng.random((k, b, d))
    y = rng.standard_normal((k, b))
    m = np.zeros((k, b))
    for i, n in enumerate(np.linspace(b, 2, k).astype(int)):
        m[i, :n] = 1.0
    ll = rng.uniform(-1.5, 0.5, (k, d))
    la = rng.uniform(-0.5, 0.5, k)
    ln = rng.uniform(-3.0, -1.0, k)
    return ll, la, ln, x, y, m


def kernel_phase(lanes=LANES, bucket=BUCKET, dims=DIMS, pool=POOL,
                 n_fit=160, n_query=256) -> None:
    from repro.core.suggest import gp
    from repro.kernels import ops

    rng = np.random.default_rng(SEED)
    ll, la, ln, x, y, m = _lane_case(rng, lanes, bucket, dims)
    f32 = lambda a: jnp.asarray(a, jnp.float32)             # noqa: E731
    args = [f32(a) for a in (ll, la, ln, x, y, m)]

    # NLL and its hyperparameter gradients (custom_vjp of the kernel)
    nll = ops.gp_neg_mll(*args, force_kernel=True)
    want = [_nll64(*(a[i] for a in (ll, la, ln, x, y, m)))
            for i in range(lanes)]
    check_close("nll", nll, want)
    grads = ops.gp_fit_grads(*args, force_kernel=True)
    h = 1e-5
    fd = np.zeros((lanes, dims + 2))
    for i in range(lanes):
        theta = np.concatenate([ll[i], [la[i], ln[i]]])
        for j in range(dims + 2):
            vals = []
            for s in (h, -h):
                t = theta.copy()
                t[j] += s
                vals.append(_nll64(t[:dims], t[dims], t[dims + 1],
                                   x[i], y[i], m[i]))
            fd[i, j] = (vals[0] - vals[1]) / (2 * h)
    got = np.concatenate([np.asarray(grads[0]),
                          np.asarray(grads[1])[:, None],
                          np.asarray(grads[2])[:, None]], axis=1)
    check_close("grad", got, fd)

    # EI over a candidate pool, on float64 posteriors handed in as f32
    cand = rng.random((lanes, pool, dims))
    y_mean = rng.standard_normal(lanes)
    y_std = rng.uniform(0.5, 2.0, lanes)
    best = rng.standard_normal(lanes)
    chol = np.zeros((lanes, bucket, bucket))
    alpha = np.zeros((lanes, bucket))
    want = np.zeros((lanes, pool))
    for i in range(lanes):
        chol[i] = np.linalg.cholesky(_cov64(x[i], ll[i], la[i], ln[i], m[i]))
        alpha[i] = np.linalg.solve(chol[i].T,
                                   np.linalg.solve(chol[i], y[i] * m[i]))
        kq = _cross64(cand[i], x[i], ll[i], la[i]) * m[i]
        v = np.linalg.solve(chol[i], kq.T)
        var = np.maximum(np.exp(2 * la[i]) - (v * v).sum(0), 1e-12)
        want[i] = _ei64(kq @ alpha[i] * y_std[i] + y_mean[i],
                        np.sqrt(var) * y_std[i], best[i])
    ei = ops.gp_ei(f32(ll), f32(la), f32(x), f32(m), f32(chol), f32(alpha),
                   f32(y_mean), f32(y_std), f32(cand), f32(best),
                   force_kernel=True)
    check_close("ei", ei, want)

    # one exact fit_gp at bucket 256 against a float64 posterior at the
    # hyperparameters the fit chose
    xf = rng.random((n_fit, dims))
    yf = (-((xf - 0.4) ** 2).sum(1) + 0.3 * np.sin(6 * xf[:, 0])
          + 0.05 * rng.standard_normal(n_fit))
    post = gp.fit_gp(xf, yf, bucket=bucket)
    xq = rng.random((n_query, dims))
    mu, sd = gp.predict(post, jnp.asarray(xq, post.x.dtype))
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), post.params)
    mean, std = yf.mean(), max(yf.std(), 1e-6)
    kf = _cov64(xf, p.log_ls, p.log_amp, p.log_noise, np.ones(n_fit))
    kq = _cross64(xq, xf, p.log_ls, p.log_amp)
    var = np.exp(2 * p.log_amp) - np.einsum(
        "ij,ji->i", kq, np.linalg.solve(kf, kq.T))
    log(f"fit_gp params: log_ls={np.round(p.log_ls, 3).tolist()} "
        f"log_amp={float(p.log_amp):.3f} log_noise={float(p.log_noise):.3f}")
    check_close("post_mean", mu,
                kq @ np.linalg.solve(kf, (yf - mean) / std) * std + mean)
    check_close("post_sd", sd, np.sqrt(np.maximum(var, 1e-12)) * std)

    text = jax.jit(lambda *a: ops.gp_neg_mll(*a, force_kernel=True)).lower(
        *args).compile().as_text()
    if "tpu_custom_call" not in text:
        raise Failed("gp_nll did not compile to a Pallas TPU kernel")
    log("gp_nll compiled to a Pallas TPU kernel (tpu_custom_call)")


# ---------------------------------------------------------------- (c)
def _space():
    from repro.core.space import Param, Space
    return Space([Param("lr", "double", 1e-5, 1e-1, log=True),
                  Param("momentum", "double", 0.0, 0.99),
                  Param("dropout", "double", 0.0, 0.6),
                  Param("weight_decay", "double", 1e-6, 1e-2, log=True),
                  Param("batch", "int", 16, 512),
                  Param("layers", "int", 1, 12),
                  Param("width", "int", 32, 1024),
                  Param("warmup", "double", 0.0, 0.2)])


def service_phase(n_exp=N_EXPERIMENTS, parallel=PARALLEL, min_obs=MIN_OBS,
                  budget=BUDGET, deadline_s=SERVICE_DEADLINE_S,
                  settle_deadline_s=SETTLE_DEADLINE_S) -> None:
    from repro.api import (CreateExperiment, HTTPClient, ObserveRequest,
                           serve_api)
    from repro.core.experiment import ExperimentConfig
    from repro.core.space import strip_internal

    space = _space()
    centers = np.random.default_rng(SEED).uniform(0.2, 0.8, (n_exp, len(space)))

    def objective(e, a, rng):
        u = space.to_unit(a)
        return float(-((u - centers[e]) ** 2).sum()
                     + 0.1 * math.sin(8.0 * u[0])
                     + 0.01 * rng.standard_normal())

    with tempfile.TemporaryDirectory(prefix="smoke-store-") as root:
        t0 = time.perf_counter()
        server = serve_api(root).start()
        client = HTTPClient(server.url)
        try:
            ids = [client.create_experiment(CreateExperiment(
                config=ExperimentConfig(
                    name=f"smoke-gp-{e}", budget=budget, parallel=parallel,
                    optimizer="gp", space=space, seed=SEED + e).to_json()
            )).exp_id for e in range(n_exp)]
            log(f"service: server up and {n_exp} experiments created in "
                f"{time.perf_counter() - t0:.2f} s")
            lats, bad, errors = [], [], []
            done = collections.Counter()
            lock = threading.Lock()
            t_drive = time.perf_counter()

            def worker(e, w):
                rng = np.random.default_rng([SEED, e, w])
                try:
                    while time.perf_counter() - t_drive < deadline_s:
                        with lock:
                            if done[e] >= min_obs:
                                return
                        t1 = time.perf_counter()
                        batch = client.suggest(ids[e], 1)
                        dt = time.perf_counter() - t1
                        for s in batch.suggestions:
                            a = strip_internal(s.assignment)
                            if not space.validate(a):
                                with lock:
                                    bad.append((ids[e], a))
                            client.observe(ObserveRequest(
                                ids[e], s.suggestion_id, s.assignment,
                                objective(e, a, rng)))
                            with lock:
                                done[e] += 1
                                lats.append(dt)
                        if not batch.suggestions:
                            time.sleep(0.01)
                except Exception as ex:  # noqa: reported below as a failure
                    with lock:
                        errors.append(f"{ids[e]}: {type(ex).__name__}: {ex}")

            threads = [threading.Thread(target=worker, args=(e, w))
                       for e in range(n_exp) for w in range(parallel)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            drive_s = time.perf_counter() - t_drive
            if errors:
                raise Failed(f"service: {len(errors)} client errors, "
                             f"first: {errors[0]}")
            if bad:
                raise Failed(f"service: out-of-bounds suggestions: {bad[:2]}")
            short = {i: done[e] for e, i in enumerate(ids) if done[e] < min_obs}
            if short:
                raise Failed(f"service: under {min_obs} observations after "
                             f"{drive_s:.0f} s: {short}")
            ms = np.asarray(lats) * 1e3
            log(f"service: {len(lats)} suggest->observe cycles in "
                f"{drive_s:.1f} s; smoke suggest latency p50="
                f"{np.percentile(ms, 50):.2f} ms p90="
                f"{np.percentile(ms, 90):.2f} ms (one run, not a metric)")
            # the pumps run asynchronously: give owed hyperfits and queue
            # refills time to pass through the shared executor (a pump
            # spends its first seconds compiling its shape buckets)
            t_settle = time.perf_counter()
            while True:
                problems, waiting, rows = [], [], []
                for i in ids:
                    st = client.status(i)
                    pump = st.pump or {}
                    ex = pump.get("executor") or {}
                    lines = len((pathlib.Path(root) / "experiments" / i
                                 / "observations.jsonl").read_text()
                                .splitlines())
                    rows.append(
                        f"{i} obs={st.observations} log_lines={lines} "
                        f"maintained={pump.get('maintained', 0)} "
                        f"hits={pump.get('hits')} "
                        f"misses={pump.get('misses')} batched_prefilled="
                        f"{pump.get('batched_prefilled', 0)}")
                    if "pump_error" in pump:
                        problems.append(f"{i}: pump_error "
                                        f"{pump['pump_error']}")
                    if ex.get("failed", 0) != 0:
                        problems.append(f"{i}: executor failed="
                                        f"{ex['failed']} last_error="
                                        f"{ex.get('last_error')}")
                    if lines != st.observations:
                        problems.append(f"{i}: {lines} logged observations "
                                        f"vs {st.observations} observed")
                    if pump.get("maintained", 0) < 1:
                        waiting.append(f"{i}: no off-path hyperfit installed")
                    if (ex.get("batched", 0) < 1
                            or ex.get("batched_asks", 0) < 1):
                        waiting.append(f"{i}: executor batched="
                                       f"{ex.get('batched')} batched_asks="
                                       f"{ex.get('batched_asks')}")
                settle_s = time.perf_counter() - t_settle
                if problems or not waiting or settle_s > settle_deadline_s:
                    break
                time.sleep(1.0)
            for row in rows:
                log(f"service: {row}")
            log(f"service: settled in {settle_s:.1f} s; executor "
                f"{json.dumps(ex, sort_keys=True)}")
            if problems or waiting:
                raise Failed("service: " + "; ".join(problems + waiting))
        finally:
            client.close()
            server.shutdown()


# ---------------------------------------------------------------- (d)
def trial_phase(arch=TRAIN_ARCH, steps=TRAIN_STEPS, batch=TRAIN_BATCH,
                seq=TRAIN_SEQ, reduced=False, platform="tpu") -> None:
    from repro.core import (ExperimentConfig, Orchestrator, Param,
                            Resources, Space)
    from repro.launch.train import train

    lines = []

    def trial(a, ctx):
        def record(msg):
            lines.append(msg)
            ctx.log(msg)
        return train(arch, steps=steps, batch=batch, seq=seq,
                     reduced=reduced, lr=a["lr"], warmup=2, log=record,
                     log_every=1, seed=SEED)

    with tempfile.TemporaryDirectory(prefix="smoke-trial-") as root:
        orch = Orchestrator(root)
        orch.cluster_create({"cluster_name": "smoke", "pools": [
            {"name": "chip", "resource": platform, "chips": 1}]})
        cfg = ExperimentConfig(
            name="smoke-train", budget=2, parallel=1, optimizer="random",
            goal="min", max_retries=0,
            space=Space([Param("lr", "double", 1e-4, 1e-3, log=True)]),
            resources=Resources(pool="chip", chips=1))
        exp = orch.run(cfg, trial_fn=trial, cluster="smoke")
        st = orch.status(exp)
        orch.cluster_destroy("smoke")
    for line in lines:
        log(f"trial: {line}")
    losses = [float(s.split("loss=")[1].split()[0])
              for s in lines if " loss=" in s]
    placed = [s for s in lines if "params on" in s]
    if st["observations"] != 2 or st["failures"]:
        raise Failed(f"trial: observations={st['observations']} "
                     f"failures={st['failures']}")
    if len(losses) != 2 * steps or not all(map(math.isfinite, losses)):
        raise Failed(f"trial: losses {losses}")
    if len(placed) != 2 or not all(s.endswith(f"params on {platform}")
                                   for s in placed):
        raise Failed(f"trial: parameters not on {platform}: {placed}")


# ---------------------------------------------------------------- main
def main() -> int:
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    t_start = time.perf_counter()
    try:
        dev = device_check()
        log(f"compile cache: {cache_dir}")
        for name, phase in (("kernels", kernel_phase),
                            ("service", service_phase),
                            ("trial", trial_phase)):
            mark = counter.mark()
            t0 = time.perf_counter()
            phase()
            log(f"phase {name} passed: wall_s="
                f"{time.perf_counter() - t0:.1f} {counter.since(mark)}")
    except Failed as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"all phases passed in {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
