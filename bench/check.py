"""The comparison that decides ``correct``.

Two kinds of number, each printed beside its limit
(``bench/limits/<workload>.json``):

* the service's guarantees, counted exactly (limit 0): suggestion ids
  unique, every acknowledged observe present in the experiment's
  observation log, the budget never overdrawn, no queue entry served
  past the staleness bound K, every served assignment inside the space,
  every request answered;
* the GP math, for a seeded sample of what the window's dispatches
  produced, against ``bench/gpref.py`` in float64.  Select lanes (the
  q-EI asks, batched and serial), scored along the program's own picks:

  ``ask_ei_gap``    widest share by which a pick's expected improvement
                    lies below the best candidate's, each step on the
                    reference's posterior conditioned on the earlier
                    picks (steps whose best candidate lies more than
                    three posterior sds below the incumbent are not
                    ranked: f32 EI is round-off there);
  ``ask_mean_gap``  widest gap of the posterior mean folded in for a pick
                    (its constant lie), in prior standard deviations;
  ``ask_var_gap``   widest gap of the posterior variance at a pick (the
                    new Cholesky pivot less the noise), as a share of
                    the prior variance;
  ``ask_cov_gap``   widest entry of L·Lᵀ − (K + noise) over the output
                    posterior's rows (history and lies), L the factor
                    the program produced and K the float64 covariance
                    of the same rows, as a share of the prior variance:
                    the backward error of the factor the posterior
                    serves from;
  ``ask_diag_gap``  the same on the diagonal alone, where K is the prior
                    variance at every row: there the distance of a row
                    to itself cancels to nought in the distance
                    expansion, so the matmuls' rounding shows most and
                    the exponential's (at a zero argument) least.

  Fitted lanes (the hyperparameter fits, batched and serial), scored
  against Adam replayed in float64 from the same start for the same
  steps:

  ``fit_nll_gap``   widest excess of the float64 neg-MLL at the
                    program's fitted hyperparameters over that at the
                    reference's, per row of the lane's history (nats);
  ``fit_unmoved``   leaves the reference moves (gradient at the start
                    at least a thousandth of the median leaf's) that
                    the program leaves exactly where they started;
  ``nonfinite``     sampled lanes whose posterior, picks or fitted
                    hyperparameters hold a NaN or an infinity.

``fit_gap`` is printed and not compared: by the worst leaf, the gap
between the norms of the program's and the reference's change over the
fit (PERF.md gives its readings and why it is not compared).

The control puts ``gpref.ControlHigh`` in the program's place on the
same inputs and is scored the same way (``bench/control.py``).
"""
from __future__ import annotations

import json
import math
import pathlib

import numpy as np

from bench import gpref

N_SELECT = 8        # select lanes compared per run
N_FIT = 6           # fitted lanes compared per run
Z_FLOOR = -3.0      # best standardised improvement below which f32 EI
                    # cannot rank: Φ(z) = ½(1 + erf(z/√2)) cancels (at
                    # z = -4, 1 + erf is 6.3e-5, 500 f32 ulps of 1) and
                    # EI = imp·Φ + sd·φ cancels again, so the EIs of
                    # the best candidates are round-off by z = -4
LIVE_GRAD = 1e-3    # a leaf whose gradient at the start is under this
                    # share of the median leaf's moves by round-off alone

LIMITS_DIR = pathlib.Path(__file__).resolve().parent / "limits"


def limits(workload: str) -> dict:
    return json.loads((LIMITS_DIR / f"{workload}.json").read_text())


def _theta(params) -> dict:
    return {"log_ls": np.asarray(params.log_ls, np.float64),
            "log_amp": np.asarray(params.log_amp, np.float64),
            "log_noise": np.asarray(params.log_noise, np.float64)}


def _default_theta(d: int) -> dict:
    return {"log_ls": np.full(d, -0.7), "log_amp": np.zeros(()),
            "log_noise": np.full((), -2.0)}


# ------------------------------------------------------------- sampling
def sample(rec, seed: int):
    """Seeded sample of the recorded select lanes and fitted lanes,
    largest histories first in the draw order, pulled to the host."""
    import jax
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 13])
    sel = list(rec.selects)
    order = rng.permutation(len(sel))
    sel = [sel[i] for i in order]
    sel.sort(key=lambda s: -int(np.asarray(s["post"].mask).sum()))
    biggest, rest = sel[:1], sel[1:]
    sel = biggest + rest[:N_SELECT - 1]
    fits = list(rec.fits)
    order = rng.permutation(len(fits))
    fits = [fits[i] for i in order]
    fits.sort(key=lambda f: -np.asarray(f["x"]).shape[0])
    fits = fits[:1] + fits[1:N_FIT]
    sel_h = [jax.device_get({k: v for k, v in s.items()}) for s in sel]
    fit_h = [jax.device_get(f) for f in fits]
    return sel_h, fit_h


def select_inputs(lane) -> dict:
    post = lane["post"]
    mask = np.asarray(post.mask, np.float64)
    n = int(mask.sum())
    if not np.all(mask[:n] == 1.0):
        raise ValueError("posterior rows are not a prefix")
    k = int(lane["k"])
    return {"theta": _theta(post.params),
            "x": np.asarray(post.x, np.float64)[:n],
            "y": np.asarray(post.y, np.float64)[:n],
            "y_mean": float(post.y_mean), "y_std": float(post.y_std),
            "cand": np.asarray(lane["cand"], np.float64),
            "best": float(lane["best"]), "k": k, "n": n,
            "picks": np.asarray(lane["picks"]).astype(int)[:k]}


def program_select(lane, inp) -> dict:
    """What the program produced for one select lane: its picks, the
    mean and variance it folded in for each, and the output posterior's
    factor over the history and the lies."""
    out = lane["out"]
    n, k, th = inp["n"], inp["k"], inp["theta"]
    nz = math.exp(2 * float(th["log_noise"])) + gpref.JITTER
    y_out = np.asarray(out.y, np.float64)
    chol = np.asarray(out.chol, np.float64)
    return {"firsts": inp["picks"],
            "mean": y_out[n:n + k],
            "var": np.diagonal(chol)[n:n + k] ** 2 - nz,
            "chol": chol[:n + k, :n + k]}


def control_select(backend, inp) -> dict:
    """The same, with ``backend`` (the control) in the program's place on
    the same inputs and along the same picks: at each step the candidate
    it puts first, its mean and variance at the program's pick, and its
    factor over the same rows."""
    got = gpref.select(backend, inp["theta"], inp["x"], inp["y"],
                       inp["y_mean"], inp["y_std"], inp["cand"],
                       inp["best"], inp["picks"])
    return {"firsts": np.argmax(got["ei"], axis=1), "mean": got["mean"],
            "var": got["var"], "chol": got["chol"]}


def score_select(inp, produced) -> dict:
    """Gaps of one selector's output (``produced``, as from
    ``program_select``) against the float64 reference along the same
    picks."""
    ref = gpref.select(gpref.F64, inp["theta"], inp["x"], inp["y"],
                       inp["y_mean"], inp["y_std"], inp["cand"],
                       inp["best"], inp["picks"])
    th = inp["theta"]
    amp2 = math.exp(2 * float(th["log_amp"]))
    ei = 0.0
    for s, first in enumerate(produced["firsts"]):
        e = ref["ei"][s]
        e_max = float(np.max(e))
        if ref["zmax"][s] > Z_FLOOR:
            ei = max(ei, (e_max - float(e[int(first)])) / e_max)
    rows = np.concatenate([inp["x"], inp["cand"][inp["picks"]]])
    low = produced["chol"]
    prior = amp2 + float(gpref.noise2(gpref.F64, th["log_noise"]))
    back = np.abs(low @ low.T - gpref.covariance(gpref.F64, th, rows))
    return {"ask_ei_gap": ei,
            "ask_mean_gap": float(np.max(np.abs(produced["mean"]
                                                - ref["mean"])))
            / math.sqrt(amp2),
            "ask_var_gap": float(np.max(np.abs(produced["var"]
                                               - ref["var"]))) / amp2,
            "ask_cov_gap": float(np.max(back)) / prior,
            "ask_diag_gap": float(np.max(np.diagonal(back))) / prior}


def fit_inputs(f) -> dict:
    x = np.asarray(f["x"], np.float64)
    th0 = (_theta(f["p0"]) if f["p0"] is not None
           else _default_theta(x.shape[1]))
    return {"theta0": th0, "x": x, "y": np.asarray(f["y"], np.float64),
            "steps": f["steps"]}


def fit_reference(inp) -> dict:
    """Adam replayed in float64: the fitted point, the gradient at the
    start, and the neg-MLL at both."""
    theta, g0 = gpref.adam_fit(gpref.F64, inp["theta0"], inp["x"],
                               inp["y"], inp["steps"])
    return {"theta": theta, "g0": g0,
            "nll": gpref.nll(theta, inp["x"], inp["y"]),
            "nll0": gpref.nll(inp["theta0"], inp["x"], inp["y"])}


def _live(ref) -> list:
    gn = {k: float(np.linalg.norm(ref["g0"][k])) for k in gpref.LEAVES}
    g_med = float(np.median(list(gn.values())))
    return [k for k in gpref.LEAVES if gn[k] >= LIVE_GRAD * g_med]


def score_fit(inp, ref, theta) -> dict:
    """The numbers of one fitted lane whose hyperparameters came out as
    ``theta``."""
    th0 = inp["theta0"]
    got = {k: np.asarray(theta[k], np.float64) for k in gpref.LEAVES}
    live = _live(ref)
    unmoved = sum(bool(np.any(ref["theta"][k] != th0[k])
                       and np.all(got[k] == th0[k])) for k in live)
    d_ref = {k: float(np.linalg.norm(ref["theta"][k] - th0[k]))
             for k in live}
    d_got = {k: float(np.linalg.norm(got[k] - th0[k])) for k in live}
    med = float(np.median(list(d_ref.values()))) if live else 0.0
    gap = max([abs(d_got[k] - d_ref[k]) / max(d_ref[k], med)
               for k in live if max(d_ref[k], med) > 0] or [0.0])
    excess = gpref.nll(got, inp["x"], inp["y"]) - ref["nll"]
    return {"fit_nll_gap": max(0.0, excess) / inp["x"].shape[0],
            "fit_unmoved": unmoved, "fit_gap": gap}


def _finite(tree) -> bool:
    import jax
    return all(np.all(np.isfinite(np.asarray(a, np.float64)))
               for a in jax.tree.leaves(tree))


def finite_sample(rec, seed: int):
    """``sample``, less the lanes holding a NaN or an infinity (and how
    many those were)."""
    sel, fits = sample(rec, seed)
    ok_sel = [s for s in sel if _finite([s["post"], s["out"], s["picks"]])]
    ok_fit = [f for f in fits if _finite([f["out"], f["p0"]])]
    return ok_sel, ok_fit, len(sel) - len(ok_sel) + len(fits) - len(ok_fit)


SUMMED = ("fit_unmoved",)


def _fold(out: dict, got: dict) -> None:
    for k, v in got.items():
        if k in SUMMED:
            out[k] = (out.get(k) or 0) + v
        elif not math.isfinite(v):
            out[k] = math.inf       # a lane that came out NaN fails
        else:
            out[k] = max(out.get(k) or 0.0, v)


def gp_numbers(rec, seed: int, control=None) -> dict:
    """The GP numbers for this run (None where the window produced
    nothing of that kind): the program's, or with ``control`` a backend
    put in the program's place on the same sampled inputs.
    ``nonfinite`` counts sampled lanes whose posterior, picks or fitted
    hyperparameters hold a NaN or an infinity: the service served from
    them, and they are not compared further."""
    sel, fits, bad = finite_sample(rec, seed)
    out = {k: None for k in ("ask_ei_gap", "ask_mean_gap", "ask_var_gap",
                             "ask_cov_gap", "ask_diag_gap", "fit_nll_gap",
                             "fit_unmoved", "fit_gap")}
    out["nonfinite"] = bad
    for lane in sel:
        inp = select_inputs(lane)
        produced = (program_select(lane, inp) if control is None
                    else control_select(control, inp))
        _fold(out, score_select(inp, produced))
    for f in fits:
        inp = fit_inputs(f)
        ref = fit_reference(inp)
        theta = (_theta(f["out"]) if control is None else
                 gpref.adam_fit(control, inp["theta0"], inp["x"], inp["y"],
                                inp["steps"])[0])
        _fold(out, score_fit(inp, ref, theta))
    out["_compared"] = {"select_lanes": len(sel), "fit_lanes": len(fits),
                        "select_rows_max": max(
                            [int(np.asarray(s["post"].mask).sum())
                             for s in sel] or [0]),
                        "fit_rows_max": max(
                            [np.asarray(f["x"]).shape[0] for f in fits]
                            or [0])}
    return out


def fit_fault_numbers(rec, seed: int) -> dict:
    """The fit numbers of two faults planted in the reference put in the
    program's place, on the same sampled lanes: a fit that returns its
    start unchanged, and one that sees only the first half of each
    history."""
    _, fits, _ = finite_sample(rec, seed)
    out = {"unchanged": {}, "half_history": {}}
    for f in fits:
        inp = fit_inputs(f)
        ref = fit_reference(inp)
        half = len(inp["x"]) // 2
        th_half, _ = gpref.adam_fit(gpref.F64, inp["theta0"],
                                    inp["x"][:half], inp["y"][:half],
                                    inp["steps"])
        _fold(out["unchanged"], score_fit(inp, ref, inp["theta0"]))
        _fold(out["half_history"], score_fit(inp, ref, th_half))
    return out


# ----------------------------------------------------------- guarantees
def _valid(space, a) -> bool:
    names = {p["name"] for p in space}
    if {k for k in a if not k.startswith("__")} != names:
        return False
    for p in space:
        lo, hi = p["bounds"]
        v = a[p["name"]]
        if not (lo <= v <= hi):
            return False
        if p["type"] == "int" and not float(v).is_integer():
            return False
    return True


def guarantees(gen: dict, store_root: str, exp_ids, space, budget: int,
               seeded: dict, rec) -> dict:
    """Exact counts of broken guarantees over every request of the run
    (warm-up and window)."""
    served = {}
    bad = failed = 0
    for r in gen["suggests"]:
        if r["status"] != 200:
            failed += 1
        for s in r["suggestions"]:
            served.setdefault(r["exp"], []).append(s["suggestion_id"])
            bad += not _valid(space, s["assignment"])
    dup = sum(len(v) - len(set(v)) for v in served.values())
    unlogged = overdrawn = 0
    for e, exp_id in enumerate(exp_ids):
        path = pathlib.Path(store_root) / "experiments" / exp_id \
            / "observations.jsonl"
        logged = [json.loads(line) for line in path.read_text().splitlines()
                  if line.strip()]
        ids = {r.get("suggestion_id") for r in logged}
        acked = [o for o in gen["observes"]
                 if o["exp"] == e and o.get("accepted")]
        unlogged += sum(o["suggestion_id"] not in ids for o in acked)
        overdrawn += max(0, len(logged) - budget)
        overdrawn += max(0, seeded[exp_id] + len(served.get(e, []))
                         - budget)
    failed += sum(o["status"] != 200 for o in gen["observes"])
    return {"dup_ids": dup, "unlogged_acks": unlogged,
            "overdrawn": overdrawn, "stale_served": rec.stale,
            "bad_assignments": bad, "failed_replies": failed}


def judge(numbers: dict, limits: dict):
    """(correct, [(name, value, limit)]) — a number over its limit, or
    missing where a limit is set, is not correct."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = numbers.get(name)
        rows.append((name, v, limit))
        if v is None or not math.isfinite(v) or v > limit:
            ok = False
    return ok, rows
