"""Plain reference of the GP math the suggestion service serves.

Written from the model's definition (Matérn-5/2 ARD kernel, Gaussian
likelihood, exact posterior, expected improvement, greedy constant-liar
q-EI, Adam on the negative log marginal likelihood) and importing
nothing of the program.  It runs in float64 on the host (``F64``), one
experiment at a time, on the real rows only: no buckets, no padding,
no lanes, no kernels.

The same code computed in f32 with every matmul at the ``high``
precision (three bf16 passes, ``ControlHigh``) is the control: the
reference put in the program's place one precision below the f32 at
HIGHEST that the configurations state.  ``ControlOnePass`` takes one
bf16 pass per matmul, what a TPU does at its default precision.  The
passes are spelled out here (operands rounded by ``reduce_precision``,
products of bf16 values exact in f32), so a control computes the same
on any device; on a device its two routines (``select`` and
``nll_grad``) run jitted, one compile per shape.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import scipy.linalg
import scipy.special

SQRT5 = math.sqrt(5.0)
JITTER = 1e-5           # noise floor added to exp(2 log_noise)
XI = 0.01               # EI exploration margin, in raw objective units
LR, B1, B2, EPS = 0.05, 0.9, 0.999, 1e-8
CLIP = {"log_ls": (-3.0, 1.5), "log_amp": (-3.0, 2.0),
        "log_noise": (-5.0, 1.0)}
LEAVES = ("log_ls", "log_amp", "log_noise")


class F64:
    """float64 NumPy on the host."""
    xp = np

    @staticmethod
    def arr(a):
        return np.asarray(a, np.float64)

    @staticmethod
    def host(a):
        return np.asarray(a, np.float64)

    @staticmethod
    def run(fn, *args):
        return fn(F64, *args)

    dot = staticmethod(np.matmul)
    chol = staticmethod(np.linalg.cholesky)
    erf = staticmethod(scipy.special.erf)

    @staticmethod
    def solve_lower(low, b):
        return scipy.linalg.solve_triangular(low, b, lower=True)

    @staticmethod
    def solve_upper_t(low, b):
        return scipy.linalg.solve_triangular(low, b, lower=True, trans="T")


class ControlHigh:
    """f32 with every matmul as three bf16 passes (``Precision.HIGH``)."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        import jax.scipy.linalg as jsl
        import jax.scipy.special as jss
        self.jax, self.xp, self._jsl = jax, jnp, jsl
        self.erf = jss.erf
        self._jit = {}

    def arr(self, a):
        return self.xp.asarray(np.asarray(a, np.float32))

    @staticmethod
    def host(a):
        return np.asarray(a, np.float64)

    def run(self, fn, *args):
        """``fn(self, *args)`` compiled once per shape."""
        if fn not in self._jit:
            self._jit[fn] = self.jax.jit(functools.partial(fn, self))
        return self._jit[fn](*args)

    def _bf16(self, x):
        # bfloat16 rounding in an f32 container; a pair of converts is
        # folded away by XLA on the TPU, reduce_precision is not
        return self.jax.lax.reduce_precision(x, exponent_bits=8,
                                             mantissa_bits=7)

    def _mm(self, a, b):
        return self.xp.matmul(a, b,
                              precision=self.jax.lax.Precision.HIGHEST)

    def dot(self, a, b):
        a1, b1 = self._bf16(a), self._bf16(b)
        a2, b2 = self._bf16(a - a1), self._bf16(b - b1)
        return self._mm(a1, b1) + (self._mm(a1, b2) + self._mm(a2, b1))

    def chol(self, a):
        return self.xp.linalg.cholesky(a)

    def solve_lower(self, low, b):
        return self._jsl.solve_triangular(low, b, lower=True)

    def solve_upper_t(self, low, b):
        return self._jsl.solve_triangular(low, b, lower=True, trans=1)


class ControlOnePass(ControlHigh):
    """f32 with every matmul as one bf16 pass (``Precision.DEFAULT`` on a
    TPU)."""

    def dot(self, a, b):
        return self._mm(self._bf16(a), self._bf16(b))


# ------------------------------------------------------------ the model
def sqdist(B, a, b, ls):
    xp = B.xp
    a = a / ls
    b = b / ls
    sq = (xp.sum(a * a, -1)[:, None] - 2.0 * B.dot(a, b.T)
          + xp.sum(b * b, -1)[None, :])
    return xp.maximum(sq, 0.0)


def matern(B, a, b, log_ls, log_amp):
    xp = B.xp
    r = xp.sqrt(sqdist(B, a, b, xp.exp(log_ls)) + 1e-12)
    s5r = SQRT5 * r
    return (xp.exp(2.0 * log_amp) * (1.0 + s5r + (5.0 / 3.0) * r * r)
            * xp.exp(-s5r))


def noise2(B, log_noise):
    return B.xp.exp(2.0 * log_noise) + JITTER


def covariance(B, theta, x):
    """K + noise on the given rows."""
    k = matern(B, x, x, theta["log_ls"], theta["log_amp"])
    return k + noise2(B, theta["log_noise"]) * B.xp.eye(x.shape[0],
                                                        dtype=k.dtype)


def expected_improvement(B, mu, sd, best):
    xp = B.xp
    imp = mu - best - XI
    z = imp / sd
    cdf = 0.5 * (1.0 + B.erf(z / math.sqrt(2.0)))
    pdf = xp.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)
    return imp * cdf + sd * pdf


def _select(B, theta, x, y, cand, picks, y_mean, y_std, best):
    """Greedy constant-liar q-EI, scored along a given pick sequence:
    step ``s`` conditions on the history and the picks before it (each
    folded in at its posterior mean), evaluates EI over ``cand``, and
    takes ``picks[s]``.  The posterior of step ``s`` is that of the
    leading ``n + s`` rows of ``x ∪ cand[picks]``, so one Cholesky factor
    ``L`` of all the rows serves every step: its leading block is the
    step's factor, and the forward solves ``L⁻¹ cov(rows, cand)`` and
    ``L⁻¹ y`` are read off by prefix."""
    xp = B.xp
    n, k = x.shape[0], picks.shape[0]
    rows = xp.concatenate([x, cand[picks]], axis=0)
    low = B.chol(covariance(B, theta, rows))
    v = B.solve_lower(low, matern(B, cand, rows, theta["log_ls"],
                                  theta["log_amp"]).T)        # (n+k, m)
    z = B.solve_lower(low[:n, :n], y)
    ss = xp.cumsum(v * v, axis=0)
    amp2 = xp.exp(2.0 * theta["log_amp"])
    idx = xp.arange(cand.shape[0])
    ei_s, mean_s, var_s, zmax_s = [], [], [], []
    for s in range(k):
        mu_n = B.dot(v[:n + s].T, z[:, None])[:, 0]
        var_n = amp2 - ss[n + s - 1]
        mu = mu_n * y_std + y_mean
        sd = xp.sqrt(xp.maximum(var_n, 1e-12)) * y_std
        ei = expected_improvement(B, mu, sd, best)
        ei = xp.where(xp.isin(idx, picks[:s]), -xp.inf, ei)
        top = xp.argmax(ei)
        j = picks[s]
        ei_s.append(ei)
        mean_s.append(mu_n[j])
        var_s.append(var_n[j])
        zmax_s.append((mu[top] - best - XI) / sd[top])
        lz = B.dot(low[n + s:n + s + 1, :n + s], z[:, None])[0, 0]
        z = xp.concatenate([z, ((mu_n[j] - lz) / low[n + s, n + s])[None]])
    return (xp.stack(ei_s), xp.stack(mean_s), xp.stack(var_s),
            xp.stack(zmax_s), low)


def select(B, theta, x, y, y_mean, y_std, cand, best, picks):
    """One record per step of the q-EI along ``picks``: ``ei`` (k, m)
    over the candidates in raw units (earlier picks at -inf), the
    posterior ``mean`` and ``var`` at the step's pick (normalised units),
    ``zmax`` the standardised improvement of the best candidate; and
    ``chol``, the factor of K + noise over the history and the picks."""
    th = {kk: B.arr(theta[kk]) for kk in LEAVES}
    out = B.run(_select, th, B.arr(x), B.arr(y), B.arr(cand),
                np.asarray(picks, np.int32), B.arr(y_mean), B.arr(y_std),
                B.arr(best))
    return dict(zip(("ei", "mean", "var", "zmax", "chol"),
                    (B.host(a) for a in out)))


def _nll_grad(B, theta, x, y):
    xp = B.xp
    n = x.shape[0]
    ll, la, ln = theta["log_ls"], theta["log_amp"], theta["log_noise"]
    ls = xp.exp(ll)
    amp2 = xp.exp(2.0 * la)
    r = xp.sqrt(sqdist(B, x, x, ls) + 1e-12)
    s5r = SQRT5 * r
    e = xp.exp(-s5r)
    kmat = amp2 * (1.0 + s5r + (5.0 / 3.0) * r * r) * e
    eye = xp.eye(n, dtype=kmat.dtype)
    k = kmat + noise2(B, ln) * eye
    low = B.chol(k)
    z = B.solve_lower(low, y)
    alpha = B.solve_upper_t(low, z)
    nll = (0.5 * xp.sum(z * z) + xp.sum(xp.log(xp.diagonal(low)))
           + 0.5 * n * math.log(2.0 * math.pi))
    linv = B.solve_lower(low, eye)
    s = 0.5 * (B.dot(linv.T, linv) - alpha[:, None] * alpha[None, :])
    coeff = amp2 * (5.0 / 3.0) * (1.0 + s5r) * e
    d2 = ((x[:, None, :] - x[None, :, :]) / ls) ** 2
    g_ll = xp.sum((s * coeff)[:, :, None] * d2, axis=(0, 1))
    g_la = 2.0 * xp.sum(s * kmat)
    g_ln = 2.0 * xp.exp(2.0 * ln) * xp.trace(s)
    return nll, {"log_ls": g_ll, "log_amp": g_la, "log_noise": g_ln}


def nll_grad(B, theta, x, y):
    """Negative log marginal likelihood and its gradient per leaf, by
    the analytic adjoint tr(S dK/dθ) with S = ½(K⁻¹ − ααᵀ)."""
    nll, g = B.run(_nll_grad, theta, x, y)
    return nll, g


def standardise(y_raw):
    y_raw = np.asarray(y_raw, np.float64)
    return (y_raw - y_raw.mean()) / max(float(y_raw.std()), 1e-6)


def nll(theta, x, y_raw) -> float:
    """The neg-MLL of the standardised targets at ``theta``, in float64."""
    th = {kk: np.asarray(theta[kk], np.float64) for kk in LEAVES}
    return float(nll_grad(F64, th, np.asarray(x, np.float64),
                          standardise(y_raw))[0])


def adam_fit(B, theta0, x, y_raw, steps):
    """Adam on the neg-MLL of the standardised targets for ``steps``
    steps from ``theta0``, with the service's clipping and its rule
    that a step that goes non-finite keeps the previous point.  Returns
    (θ after the steps, the gradient at θ0)."""
    xp = B.xp
    x, y = B.arr(x), B.arr(standardise(y_raw))
    p = {kk: B.arr(theta0[kk]) for kk in LEAVES}
    m = {kk: p[kk] * 0.0 for kk in LEAVES}
    v = {kk: p[kk] * 0.0 for kk in LEAVES}
    g0 = None
    for t in range(1, int(steps) + 1):
        _, g = nll_grad(B, p, x, y)
        if g0 is None:
            g0 = {kk: B.host(g[kk]) for kk in LEAVES}
        new = {}
        for kk in LEAVES:
            m[kk] = B1 * m[kk] + (1 - B1) * g[kk]
            v[kk] = B2 * v[kk] + (1 - B2) * g[kk] * g[kk]
            mh = m[kk] / (1 - B1 ** t)
            vh = v[kk] / (1 - B2 ** t)
            new[kk] = xp.clip(p[kk] - LR * mh / (xp.sqrt(vh) + EPS),
                              *CLIP[kk])
        if all(np.all(np.isfinite(B.host(new[kk]))) for kk in LEAVES):
            p = new
    return {kk: B.host(p[kk]) for kk in LEAVES}, g0
