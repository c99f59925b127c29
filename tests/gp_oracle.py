"""Serial references for the GP programs' lane algorithms.

The service runs one Adam fit (``gp._fit_lanes``) and one q-EI scan
(``gp._select_lanes``), each over a lane axis of experiments.  These are
the straightforward serial forms the tests hold them against:

* ``fit_params``: Adam over XLA autodiff of the masked negative log
  marginal likelihood (``jnp.linalg.cholesky``), one experiment;
* ``select``: greedy constant-liar q-EI that recomputes the posterior's
  cross-covariance and whitened solve at every pick, through the
  service's public ``expected_improvement`` and ``append_lie``.

No service path calls them and nothing prewarms them.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.suggest import gp


def neg_mll(params, x, y, mask):
    """Exact negative log marginal likelihood over the masked rows only:
    padded rows carry an identity block, which adds log(1) = 0 to the
    determinant and 0 to the quadratic form."""
    b = x.shape[0]
    k = (gp.matern52(x, x, params)
         + (jnp.exp(2 * params.log_noise) + 1e-5) * jnp.eye(b))
    k = k * mask[:, None] * mask[None, :] + jnp.diag(1.0 - mask)
    chol = jnp.linalg.cholesky(k)
    ym = y * mask
    alpha = jax.scipy.linalg.cho_solve((chol, True), ym)
    return (0.5 * jnp.dot(ym, alpha) + jnp.sum(jnp.log(jnp.diagonal(chol)))
            + 0.5 * jnp.sum(mask) * jnp.log(2 * jnp.pi))


@functools.partial(jax.jit, static_argnames=("steps",))
def _adam(params0, x, y, mask, steps, lr=0.05):
    def step(carry, _):
        p, m, v, t = carry
        g = jax.grad(neg_mll)(p, x, y, mask)
        t = t + 1
        m = jax.tree.map(lambda mm, gg: 0.9 * mm + 0.1 * gg, m, g)
        v = jax.tree.map(lambda vv, gg: 0.999 * vv + 0.001 * gg * gg, v, g)
        p = jax.tree.map(
            lambda pp, mm, vv: pp - lr * (mm / (1 - 0.9 ** t))
            / (jnp.sqrt(vv / (1 - 0.999 ** t)) + 1e-8), p, m, v)
        p = gp.GPParams(jnp.clip(p.log_ls, -3.0, 1.5),
                        jnp.clip(p.log_amp, -3.0, 2.0),
                        jnp.clip(p.log_noise, -5.0, 1.0))
        ok = jnp.all(jnp.stack([jnp.all(jnp.isfinite(a))
                                for a in jax.tree.leaves(p)]))
        p = jax.tree.map(lambda new, old: jnp.where(ok, new, old), p,
                         carry[0])
        return (p, m, v, t), None

    zeros = jax.tree.map(jnp.zeros_like, params0)
    (p, _, _, _), _ = jax.lax.scan(
        step, (params0, zeros, zeros, jnp.zeros((), jnp.int32)), None,
        length=steps)
    return p


def fit_params(x, y, steps, params0=None, bucket=None):
    """Hyperparameters after ``steps`` Adam steps from ``params0`` (or
    the service's default start), with the history normalized and padded
    to ``bucket`` as the service does."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    n, d = x.shape
    b = gp.bucket_size(n) if bucket is None else bucket
    xp = np.zeros((b, d))
    xp[:n] = x
    yp = np.zeros(b)
    yp[:n] = (y - y.mean()) / max(float(y.std()), 1e-6)
    mask = np.zeros(b)
    mask[:n] = 1.0
    if params0 is None:
        params0 = gp.GPParams(np.full(d, -0.7), np.zeros(()),
                              np.full((), -2.0))
    f32 = lambda a: jnp.asarray(a, jnp.float32)              # noqa: E731
    return _adam(jax.tree.map(f32, params0), f32(xp), f32(yp), f32(mask),
                 steps=int(steps))


@functools.partial(jax.jit, static_argnames=("k_pad",))
def _scan(post, cand, best, k, k_pad):
    def step(carry, i):
        p, taken = carry
        ei = jnp.where(taken, -jnp.inf, gp.expected_improvement(p, cand, best))
        j = jnp.argmax(ei)
        live = i < k
        p = jax.tree.map(lambda new, old: jnp.where(live, new, old),
                         gp.append_lie(p, cand[j]), p)
        return (p, jnp.where(live, taken.at[j].set(True), taken)), j

    (post, _), picks = jax.lax.scan(
        step, (post, jnp.zeros((cand.shape[0],), bool)), jnp.arange(k_pad))
    return picks, post


def select(post, cand, best, k):
    """(picks (k,), posterior with the k lies folded in)."""
    picks, out = _scan(post, jnp.asarray(cand, jnp.float32),
                       jnp.asarray(best, post.y_mean.dtype),
                       jnp.asarray(k, jnp.int32), gp.lane_pad(k))
    return picks[:k], out
