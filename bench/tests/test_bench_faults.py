"""A whole run of a cell at a tiny size on the CPU (the harness's look
for a chip skipped), once sound and once with each fault the cells can
have planted under the timed path: ``correct`` must come out true for
the sound run and false for every fault.

* ``pick_altered``: the q-EI scan's picks are altered where produced;
* ``mean_altered``: the posterior mean the scan folds in for a pick
  (its constant lie) is altered where produced;
* ``fit_unchanged``: the hyperparameter fits (batched and serial)
  return their starting point unchanged;
* ``fit_half_history``: the fits see only the first half of each
  history's rows, the neg-MLL taken over the rest.

The cells run on one chip, so there is no exchange between chips to
leave out.
"""
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, record
from bench import run as brun

FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2**32 + 77


@pytest.fixture(scope="module")
def driver():
    return harness.load_module(harness.BENCH / "drivers" / "gp_service.py")


@pytest.fixture(autouse=True)
def _no_cache_and_unwrap(monkeypatch):
    # the test process keeps JAX's default compile cache, and the
    # recorder's wrappers come off again afterwards
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    yield
    record.uninstall()


def _tiny(name="d8-steady"):
    c = brun.cell(name)
    cfg = c["config"]
    cfg.update(experiments=3, history=[20, 30], ready_s=300,
               optimizer_options=dict(cfg["optimizer_options"],
                                      candidates=64),
               space=cfg["space"][:3], prefetch=4,
               warm={"buckets": [32, 64], "fit_lanes": [2],
                     "select_lanes": [1, 2], "k_pads": [1, 2, 4]})
    c["traffic"].update(rate=8.0, warmup_s=2.0, workers=4)
    return c


def _alter(gp, monkeypatch, change):
    scan, lanes = gp._select_scan, gp._select_lanes

    def scan_bad(post, cand, best, k, k_pad):
        picks, out = scan(post, cand, best, k, k_pad=k_pad)
        return change(picks[None], np.asarray([int(k)]), cand.shape[0])[0], \
            out

    def lanes_bad(post, cand, best, k, k_pad):
        picks, out = lanes(post, cand, best, k, k_pad=k_pad)
        return change(picks, np.asarray(k), cand.shape[1]), out

    monkeypatch.setattr(gp, "_select_scan", scan_bad)
    monkeypatch.setattr(gp, "_select_lanes", lanes_bad)


def _pick_altered(gp, monkeypatch):
    _alter(gp, monkeypatch, lambda p, k, m: (p + 1) % m)


def _mean_altered(gp, monkeypatch):
    scan, lanes = gp._select_scan, gp._select_lanes

    def shift(out):
        return out._replace(y=out.y + 0.5 * out.mask)

    monkeypatch.setattr(gp, "_select_scan", lambda *a, **k: (
        lambda r: (r[0], shift(r[1])))(scan(*a, **k)))
    monkeypatch.setattr(gp, "_select_lanes", lambda *a, **k: (
        lambda r: (r[0], shift(r[1])))(lanes(*a, **k)))


def _fit_unchanged(gp, monkeypatch):
    monkeypatch.setattr(gp, "_fit_lanes", lambda params0, *a, **k: params0)
    monkeypatch.setattr(gp, "_fit", lambda params0, *a, **k: params0)


def _first_half(mask):
    kept = jnp.floor(jnp.sum(mask, axis=-1, keepdims=True) / 2)
    return mask * (jnp.cumsum(mask, axis=-1) <= kept)


def _fit_half_history(gp, monkeypatch):
    fit, lanes = gp._fit, gp._fit_lanes
    monkeypatch.setattr(gp, "_fit_lanes", lambda p0, x, y, mask, *a, **k:
                        lanes(p0, x, y, _first_half(mask), *a, **k))
    monkeypatch.setattr(gp, "_fit", lambda p0, x, y, mask, *a, **k:
                        fit(p0, x, y, _first_half(mask), *a, **k))


FAULTS = {"pick_altered": _pick_altered, "mean_altered": _mean_altered,
          "fit_unchanged": _fit_unchanged,
          "fit_half_history": _fit_half_history}


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
def test_fault_makes_the_run_incorrect(fault, driver, monkeypatch):
    from repro.core.suggest import gp
    if fault is not None:
        FAULTS[fault](gp, monkeypatch)
    res = driver.run(_tiny(), seed=SEED, seconds=4.0, trace=False,
                     t_start=time.monotonic(), device=FAKE_DEVICE)
    line = json.loads(res["line"])
    assert line["attempted"] > 0
    failing = [n for n, c in line["checks"].items()
               if c["value"] is None or c["value"] > c["limit"]]
    if fault is None:
        assert line["correct"] is True, line["checks"]
    else:
        assert line["correct"] is False, line["checks"]
        assert failing
