"""What the timed path produced, recorded while the window runs.

The benchmark's own spans around the calls into the GP programs layer:
``use()`` wraps the four entry points the optimizer dispatches
through (``batched_fit``, ``fit_gp``, ``batched_select``,
``select_batch``) and the prefetch queue's pop.  While the recorder is
on it keeps

* every fit call's inputs and fitted hyperparameters (host arrays and a
  few scalars), and a seeded reservoir of ``CAP`` select lanes (their
  input posteriors, candidate pools, picks and output posteriors stay
  on the device until the window has closed), for the comparison that
  decides ``correct``;
* the shapes of every fit (real rows, dims, Adam steps per lane), from
  which ``bench/costs.py`` counts the work the fits did;
* the age, in observations, of every queue entry served, against the
  experiment's staleness bound K.

The wrappers call the program's functions unchanged and add no device
work; the recorder is off outside the window.
"""
from __future__ import annotations

import threading
import time

import numpy as np

CAP = 48            # select lanes kept in the reservoir


class Recorder:
    def __init__(self, seed: int):
        self._lock = threading.Lock()
        self._rng = np.random.default_rng([int(seed) & (2**63 - 1), 11])
        self.on = False
        self.fits = []              # one dict per fitted lane
        self.fit_calls = []         # (t, kind, [(n, d, steps)...], bucket, pad)
        self.selects = []           # reservoir of select lanes
        self.select_seen = 0
        self.select_calls = 0
        self.stale = 0

    # ---------------------------------------------------------- records
    def _fit(self, kind, items, steps, outs, bucket, pad):
        lanes = [(int(np.asarray(x).shape[0]), int(np.asarray(x).shape[1]),
                  int(s)) for (x, _, _), s in zip(items, steps)]
        with self._lock:
            self.fit_calls.append((time.monotonic(), kind, lanes,
                                   int(bucket), int(pad)))
            for (x, y, p0), s, out in zip(items, steps, outs):
                self.fits.append({"kind": kind, "x": x, "y": y, "p0": p0,
                                  "steps": int(s), "out": out,
                                  "bucket": int(bucket)})

    def _select(self, kind, lanes):
        with self._lock:
            self.select_calls += 1
            for lane in lanes:
                self.select_seen += 1
                lane["kind"] = kind
                if len(self.selects) < CAP:
                    self.selects.append(lane)
                else:
                    j = int(self._rng.integers(self.select_seen))
                    if j < CAP:
                        self.selects[j] = lane

    def _served(self, state, fresh):
        stale = sum(state.observed - it.born_obs >= state.staleness
                    for it in fresh)
        if stale:
            with self._lock:
                self.stale += stale


_CURRENT = [None]          # the recorder the wrappers report to


def use(rec: "Recorder") -> None:
    """Make ``rec`` the recorder the wrappers report to (installing the
    wrappers on first use)."""
    if _CURRENT[0] is None:
        _install()
    _CURRENT[0] = rec


def _install() -> None:
    """Wrap the program's GP entry points and the queue pop so that they
    report to the current recorder.  Process-wide, once."""
    from repro.api import local
    from repro.core.suggest import gp

    fit_gp, batched_fit = gp.fit_gp, gp.batched_fit
    select_batch, batched_select = gp.select_batch, gp.batched_select
    pop = local.pop_prefetched

    def fit_gp_rec(x, y, steps=150, params0=None, bucket=None):
        post = fit_gp(x, y, steps=steps, params0=params0, bucket=bucket)
        rec = _CURRENT[0]
        if rec is not None and rec.on:
            rec._fit("fit", [(np.asarray(x), np.asarray(y), params0)],
                     [steps], [post.params], post.capacity, 1)
        return post

    def batched_fit_rec(items, steps=150, bucket=None):
        out = batched_fit(items, steps=steps, bucket=bucket)
        rec = _CURRENT[0]
        if rec is not None and rec.on and items:
            st = ([int(steps)] * len(items)
                  if isinstance(steps, (int, np.integer)) else list(steps))
            b = bucket if bucket is not None else gp.bucket_size(
                max(np.asarray(x).shape[0] for x, _, _ in items))
            rec._fit("fit_lanes", [(np.asarray(x), np.asarray(y), p0)
                                   for x, y, p0 in items], st, out, b,
                     gp.lane_pad(len(items)))
        return out

    def select_batch_rec(post, cand, best, k):
        picks, out = select_batch(post, cand, best, k)
        rec = _CURRENT[0]
        if rec is not None and rec.on:
            rec._select("select", [{"post": post, "cand": np.asarray(cand),
                                    "best": float(best), "k": int(k),
                                    "picks": picks, "out": out}])
        return picks, out

    def batched_select_rec(items, k_pad=gp.SELECT_PAD):
        out = batched_select(items, k_pad=k_pad)
        rec = _CURRENT[0]
        if rec is not None and rec.on:
            rec._select("select_lanes", [
                {"post": p, "cand": np.asarray(c), "best": float(b),
                 "k": int(k), "picks": picks, "out": o}
                for (p, c, b, k), (picks, o) in zip(items, out)])
        return out

    def pop_rec(state, want):
        fresh, stale = pop(state, want)
        rec = _CURRENT[0]
        if rec is not None and rec.on:
            rec._served(state, fresh)
        return fresh, stale

    gp.fit_gp, gp.batched_fit = fit_gp_rec, batched_fit_rec
    gp.select_batch, gp.batched_select = select_batch_rec, batched_select_rec
    local.pop_prefetched = pop_rec
    _ORIGINALS[:] = [(gp, "fit_gp", fit_gp), (gp, "batched_fit", batched_fit),
                     (gp, "select_batch", select_batch),
                     (gp, "batched_select", batched_select),
                     (local, "pop_prefetched", pop)]


_ORIGINALS = []


def uninstall() -> None:
    """Put the program's own functions back."""
    for mod, name, fn in _ORIGINALS:
        setattr(mod, name, fn)
    _ORIGINALS.clear()
    _CURRENT[0] = None
