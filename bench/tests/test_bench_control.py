"""The control of the comparison that decides ``correct``, at a size a
test run holds: one ask of the d8 cell's kind (8 dimensions, the cell's
objective, hyperparameters fitted by the program, a pool of uniform and
local candidates), its picks made by the program.  The reference with
one bf16 pass per matmul (a TPU's default precision), put in the
program's place along the same picks, fails the cell's limits, while
the same reference in f32 at full precision, and the program itself,
pass them, and the float64 reference scored against itself reads
nothing.  The control one precision below the configuration's (three
bf16 passes) is read beside them; on the chip's f32 it is not told
apart from the program (PERF.md, "How correct is decided").  On the
chip the controls are read at the cell's own size by
``bench/control.py``."""
import numpy as np
import pytest

from bench import check, gpref, loadgen

LIMITS = check.limits("d8-steady")
ASK = ("ask_ei_gap", "ask_mean_gap", "ask_var_gap", "ask_cov_gap")


class F32Highest(gpref.ControlHigh):
    """The reference in f32 with full-precision matmuls."""

    def dot(self, a, b):
        return self.xp.matmul(a, b, precision="highest")


def _lane(seed, n=120, d=8, m=320, k=8):
    """One ask as the d8 cell's window makes them, with the program's
    own fit and picks (on the host)."""
    import jax
    from repro.core.suggest import gp
    rng = np.random.default_rng(seed)
    center = rng.uniform(0.2, 0.8, d)
    x = rng.uniform(size=(n, d))
    y = np.array([loadgen.objective(u, center, float(rng.standard_normal()))
                  for u in x])
    post = gp.fit_gp(x, y, steps=150)
    best = float(np.max(y))
    cand = np.concatenate([rng.uniform(size=(m - m // 5, d)), np.clip(
        x[np.argmax(y)] + rng.normal(0, 0.08, (m // 5, d)), 0, 1)])
    (picks, out), = gp.batched_select([(post, cand.astype(np.float32),
                                        best, k)])
    return jax.device_get({"post": post, "cand": cand.astype(np.float32),
                           "best": best, "k": k, "picks": picks,
                           "out": out})


@pytest.fixture(scope="module")
def readings():
    lanes = [_lane(s) for s in (1, 2, 3)]
    out = {}
    for name, b in (("control", gpref.ControlHigh()),
                    ("one_pass", gpref.ControlOnePass()),
                    ("f32", F32Highest()), ("f64", gpref.F64),
                    ("program", None)):
        out[name] = []
        for lane in lanes:
            inp = check.select_inputs(lane)
            produced = (check.program_select(lane, inp) if b is None
                        else check.control_select(b, inp))
            out[name].append(check.score_select(inp, produced))
    return out


def test_reference_scores_itself_at_nothing(readings):
    for r in readings["f64"]:
        assert r["ask_ei_gap"] == 0.0
        assert r["ask_mean_gap"] < 1e-9 and r["ask_var_gap"] < 1e-9
        assert r["ask_cov_gap"] < 1e-12


def test_control_fails_a_limit(readings):
    failed = [k for r in readings["one_pass"] for k in ASK
              if r[k] > LIMITS[k]]
    assert failed, readings["one_pass"]
    # three passes: round-off above f32's, below the cell's limits
    for r, f in zip(readings["control"], readings["f32"]):
        assert f["ask_cov_gap"] < r["ask_cov_gap"] <= LIMITS["ask_cov_gap"]


@pytest.mark.parametrize("who", ["f32", "program"])
def test_full_precision_f32_passes_the_limits(readings, who):
    for r in readings[who]:
        for k in ASK:
            assert r[k] <= LIMITS[k], (k, r)
