"""Gaussian process regression in pure JAX (Matérn-5/2 ARD).

This is the numerical heart of the Bayesian optimizer — the in-repo stand-in
for SigOpt's hosted service.  Hyperparameters (per-dim lengthscales, signal
amplitude, noise) are fit by maximizing the exact log marginal likelihood
with Adam; posteriors use a jitter-stabilized Cholesky.

Hot-path design (the suggestion service calls this once per `ask` batch):

* **Bucketed static shapes** — training sets are padded to power-of-two
  buckets with a 0/1 mask, so every jitted function sees one shape per
  bucket and XLA compiles once per bucket instead of once per observation
  count.  Padded slots carry an identity block in the covariance, which
  makes the masked Cholesky exactly the real Cholesky plus identity rows.
* **Rank-1 appends** — ``append_point`` / ``append_lie`` grow the posterior
  into a free padded slot with a bordered-Cholesky update: O(n²) per point
  instead of a fresh O(steps·n³) hyperparameter fit.  Constant-liar
  batching in ``BayesOpt`` rides on this.
* **Batched q-EI selection** — ``select_batch`` picks a whole batch of
  suggestions in one jitted scan (EI argmax → fold lie → repeat), so the
  per-point Python/dispatch overhead vanishes.
* **Warm starts** — ``fit_gp(..., params0=...)`` resumes Adam from the
  previous optimum so converged posteriors need far fewer steps.
* **Sparse speculative posterior** — ``sparse_posterior`` builds an exact
  GP over a subset-of-data design of at most ``SPARSE_MAX`` inducing
  points (incumbent + recency window + even coverage of the older
  history), so conditioning cost is O(m³) regardless of history size.
  The suggestion service uses it *only* to refill the speculative
  prefetch queue when the exact path is saturated (ISSUE 5) — exact
  posteriors still serve synchronous asks and coalesced misses, and
  queue entries are staleness-bounded, which contains the approximation
  error.  It returns an ordinary ``GPPosterior`` in an ordinary
  power-of-two bucket, so every jitted kernel (EI, rank-1 appends, the
  q-EI scan) and the ``prewarm_bucket`` compile cache apply unchanged.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.kernels import ops as _kops

MIN_BUCKET = 16

#: Cap on lanes per batched fit dispatch (``batched_fit``): beyond this
#: the O(k·b³) Adam loop stops amortizing dispatch overhead and only
#: grows compile variants; callers split larger sets into chunks.
FIT_LANES_MAX = 32

#: Scan-length pad of the *batched* q-EI select (``batched_select``):
#: every batched refill ask runs a ``SELECT_PAD``-step scan with its live
#: pick count traced, so lanes wanting different batch sizes still share
#: one compile per (bucket, SELECT_PAD, lane-pad) — and the service's
#: refill chunk (``pipeline.ASK_CHUNK``) is sized to never exceed it.
#: The solo ``select_batch`` path keeps its natural per-k pads.
SELECT_PAD = 8

#: Cap on the subset-of-data design of the sparse speculative posterior.
#: 64 keeps the sparse Cholesky inside the two smallest non-trivial shape
#: buckets (64/128 once lies and picks are folded in), which ``prewarm``
#: always compiles first — a sparse refill never waits on XLA.
SPARSE_MAX = 64


def bucket_size(n: int, minimum: int = MIN_BUCKET) -> int:
    """Smallest power-of-two bucket >= n (>= minimum)."""
    b = minimum
    while b < n:
        b *= 2
    return b


def _dtype():
    return jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32


class GPParams(NamedTuple):
    log_ls: jnp.ndarray       # (d,) log lengthscales
    log_amp: jnp.ndarray      # () log signal stddev
    log_noise: jnp.ndarray    # () log noise stddev


class GPPosterior(NamedTuple):
    params: GPParams
    x: jnp.ndarray            # (b,d) training inputs, padded to bucket
    mask: jnp.ndarray         # (b,) 1.0 for real rows, 0.0 for padding
    y: jnp.ndarray            # (b,) normalized targets (0 at padding)
    chol: jnp.ndarray         # (b,b) cholesky of masked K + noise
    alpha: jnp.ndarray        # (b,) K^{-1} y
    y_mean: jnp.ndarray       # ()
    y_std: jnp.ndarray        # ()

    @property
    def capacity(self) -> int:
        return int(self.x.shape[0])


def _dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Full-f32 contraction.  On TPU a default-precision f32 matmul
    rounds its operands to bf16; the GP's distance expansion, predictive
    means and bordered-Cholesky updates all subtract nearly equal terms,
    so they run at HIGHEST.  CPU ignores the flag."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _sqdist(a: jnp.ndarray, b: jnp.ndarray, ls: jnp.ndarray) -> jnp.ndarray:
    a = a / ls
    b = b / ls
    return jnp.maximum(
        jnp.sum(a * a, -1)[:, None] - 2 * _dot(a, b.T)
        + jnp.sum(b * b, -1)[None],
        0.0)


def matern52(a, b, params: GPParams) -> jnp.ndarray:
    ls = jnp.exp(params.log_ls)
    amp2 = jnp.exp(2 * params.log_amp)
    r = jnp.sqrt(_sqdist(a, b, ls) + 1e-12)
    s5r = jnp.sqrt(5.0) * r
    return amp2 * (1 + s5r + 5.0 / 3.0 * r * r) * jnp.exp(-s5r)


def _noise2(params: GPParams) -> jnp.ndarray:
    return jnp.exp(2 * params.log_noise) + 1e-5


def _masked_cov(params: GPParams, x: jnp.ndarray,
                mask: jnp.ndarray) -> jnp.ndarray:
    """Covariance with padded rows/cols replaced by an identity block, so
    cholesky(masked K) == blockdiag(cholesky(real K), I)."""
    b = x.shape[0]
    k = matern52(x, x, params) + _noise2(params) * jnp.eye(b)
    mm = mask[:, None] * mask[None, :]
    return k * mm + jnp.diag(1.0 - mask)


@jax.jit
def neg_mll(params: GPParams, x: jnp.ndarray, y: jnp.ndarray,
            mask: jnp.ndarray) -> jnp.ndarray:
    """Exact negative log marginal likelihood over the masked rows only:
    identity padding contributes log(1)=0 to the determinant and 0 to the
    quadratic form, so the value is independent of the bucket size."""
    k = _masked_cov(params, x, mask)
    chol = jnp.linalg.cholesky(k)
    ym = y * mask
    alpha = jax.scipy.linalg.cho_solve((chol, True), ym)
    return (0.5 * _dot(ym, alpha)
            + jnp.sum(jnp.log(jnp.diagonal(chol)))
            + 0.5 * jnp.sum(mask) * jnp.log(2 * jnp.pi))


@functools.partial(jax.jit, static_argnames=("steps",))
def _fit(params0: GPParams, x, y, mask, steps: int = 150, lr: float = 0.05):
    """Adam on the negative MLL."""
    def adam_step(carry, _):
        p, m, v, t = carry
        g = jax.grad(neg_mll)(p, x, y, mask)
        t = t + 1
        m = jax.tree.map(lambda mm, gg: 0.9 * mm + 0.1 * gg, m, g)
        v = jax.tree.map(lambda vv, gg: 0.999 * vv + 0.001 * gg * gg, v, g)
        mh = jax.tree.map(lambda mm: mm / (1 - 0.9 ** t), m)
        vh = jax.tree.map(lambda vv: vv / (1 - 0.999 ** t), v)
        p = jax.tree.map(lambda pp, mm, vv: pp - lr * mm / (jnp.sqrt(vv) + 1e-8),
                         p, mh, vh)
        # clamp to sane ranges to keep the Cholesky healthy; reject any
        # step that went NaN (singular K during the line search)
        p = GPParams(jnp.clip(p.log_ls, -3.0, 1.5),
                     jnp.clip(p.log_amp, -3.0, 2.0),
                     jnp.clip(p.log_noise, -5.0, 1.0))
        ok = jnp.all(jnp.stack([jnp.all(jnp.isfinite(x))
                                for x in jax.tree.leaves(p)]))
        prev = carry[0]
        p = jax.tree.map(lambda new, old: jnp.where(ok, new, old), p, prev)
        return (p, m, v, t), None

    zeros = jax.tree.map(jnp.zeros_like, params0)
    (p, _, _, _), _ = jax.lax.scan(
        adam_step, (params0, zeros, zeros, jnp.zeros((), jnp.int32)),
        None, length=steps)
    return p


def lane_pad(k: int) -> int:
    """Smallest power of two >= k — the lane-count pad of ``batched_fit``
    and ``batched_select`` (one ``_fit_lanes`` compile per
    (bucket, max-steps, lane-pad) triple, one ``_select_lanes`` compile
    per (bucket, k-pad, lane-pad) triple)."""
    return 1 << max(0, int(k) - 1).bit_length()


@functools.partial(jax.jit, static_argnames=("max_steps",))
def _fit_lanes(params0: GPParams, x, y, mask, steps, max_steps: int = 150,
               lr: float = 0.05):
    """Batched ``_fit``: every GPParams leaf and data array carries a
    leading lane axis (k experiments), and one Adam loop advances all
    lanes together — the per-lane gradients come from one batched
    dispatch (``ops.gp_fit_grads``: the fused Pallas neg-MLL's analytic
    custom_vjp on TPU, the GEMM-rich analytic adjoint from kernels/ref
    here on CPU — the latter is why a lane costs less than a serial
    autodiff fit even on one core).  Lanes are independent: the adjoint
    is computed per lane, and the NaN-reject check is per-lane, so one
    ill-conditioned experiment can't stall its batch peers.
    All-zero-mask lanes (the lane padding) see an identity covariance —
    zero gradient, parameters inert.

    ``steps`` is a traced (k,) int32 of per-lane step budgets and
    ``max_steps`` the static scan length (>= every entry): the loop runs
    ``max_steps`` iterations with a per-lane freeze mask that discards a
    lane's parameter update once its own budget is spent.  Lanes on
    different rungs of the adaptive warm-step ladder therefore share one
    dispatch, and because every live lane sees the identical global Adam
    step index ``t``, a lane frozen at ``steps[i]`` holds exactly the
    parameters a solo ``_fit_lanes`` run of length ``steps[i]`` would
    produce — bit-identical, not merely close."""
    def adam_step(carry, _):
        p, m, v, t = carry
        g = GPParams(*_kops.gp_fit_grads(p.log_ls, p.log_amp,
                                         p.log_noise, x, y, mask))
        t = t + 1
        m = jax.tree.map(lambda mm, gg: 0.9 * mm + 0.1 * gg, m, g)
        v = jax.tree.map(lambda vv, gg: 0.999 * vv + 0.001 * gg * gg, v, g)
        mh = jax.tree.map(lambda mm: mm / (1 - 0.9 ** t), m)
        vh = jax.tree.map(lambda vv: vv / (1 - 0.999 ** t), v)
        p = jax.tree.map(lambda pp, mm, vv: pp - lr * mm / (jnp.sqrt(vv) + 1e-8),
                         p, mh, vh)
        p = GPParams(jnp.clip(p.log_ls, -3.0, 1.5),
                     jnp.clip(p.log_amp, -3.0, 2.0),
                     jnp.clip(p.log_noise, -5.0, 1.0))
        ok = (jnp.all(jnp.isfinite(p.log_ls), axis=-1)
              & jnp.isfinite(p.log_amp) & jnp.isfinite(p.log_noise))  # (k,)
        keep = ok & (t <= steps)                 # freeze finished lanes
        prev = carry[0]
        p = GPParams(jnp.where(keep[:, None], p.log_ls, prev.log_ls),
                     jnp.where(keep, p.log_amp, prev.log_amp),
                     jnp.where(keep, p.log_noise, prev.log_noise))
        return (p, m, v, t), None

    zeros = jax.tree.map(jnp.zeros_like, params0)
    (p, _, _, _), _ = jax.lax.scan(
        adam_step, (params0, zeros, zeros, jnp.zeros((), jnp.int32)),
        None, length=max_steps)
    return p


def batched_fit(items, steps=150, bucket: Optional[int] = None) -> list:
    """Fit k experiments' GP hyperparameters in ONE vmap'd dispatch.

    ``items`` is a sequence of ``(x, y, params0)`` triples — x (n,d) in
    the unit cube, y raw objective, params0 a warm start or None — all
    sharing one shape ``bucket`` (default: smallest bucket fitting the
    largest history).  Each lane is normalized and padded exactly as
    ``fit_gp`` would, stacked along a leading lane axis, and the lane
    count is padded to the next power of two with inert all-zero-mask
    lanes, so XLA compiles once per (bucket, max-steps, lane-pad) triple.

    ``steps`` is an int (every lane) or a per-lane sequence: lanes on
    different adaptive-ladder step counts run inside one masked loop of
    ``max(steps)`` iterations (see ``_fit_lanes``) — each lane's result
    is bit-identical to a solo fit at its own step count.  Returns a
    list of k fitted ``GPParams`` (install with ``make_posterior`` /
    the optimizer's recondition, as usual)."""
    if not items:
        return []
    if len(items) > FIT_LANES_MAX:
        raise ValueError(f"{len(items)} lanes > FIT_LANES_MAX "
                         f"({FIT_LANES_MAX}); split the batch")
    dtype = _dtype()
    b = bucket if bucket is not None else bucket_size(
        max(np.asarray(x).shape[0] for x, _, _ in items))
    b = int(b)
    d = np.asarray(items[0][0]).shape[1]
    k = len(items)
    kp = lane_pad(k)
    steps_list = ([int(steps)] * k if isinstance(steps, (int, np.integer))
                  else [int(s) for s in steps])
    if len(steps_list) != k:
        raise ValueError(f"{len(steps_list)} step counts for {k} lanes")
    # one host-side buffer per array and ONE device put each — k small
    # transfers per lane would cost more than the fit at warm step counts
    xs = np.zeros((kp, b, d), np.float64)
    ys = np.zeros((kp, b), np.float64)
    ms = np.zeros((kp, b), np.float64)
    lls = np.full((kp, d), -0.7, np.float64)
    las = np.zeros((kp,), np.float64)
    lns = np.full((kp,), -2.0, np.float64)
    st = np.zeros((kp,), np.int32)
    st[:k] = steps_list
    for i, (x, y, params0) in enumerate(items):
        x = np.asarray(x, np.float64)
        y_raw = np.asarray(y, np.float64)
        n = x.shape[0]
        if b < n:
            raise ValueError(f"bucket {b} smaller than training set {n}")
        mean = np.mean(y_raw)
        std = max(float(np.std(y_raw)), 1e-6)
        xs[i, :n] = x
        ys[i, :n] = (y_raw - mean) / std
        ms[i, :n] = 1.0
        if params0 is not None:
            lls[i] = np.asarray(params0.log_ls)
            las[i] = np.asarray(params0.log_amp)
            lns[i] = np.asarray(params0.log_noise)
    # lanes k..kp-1 stay all-zero-mask (inert) with default params
    tracing.annotate(h2d_bytes=st.nbytes + np.dtype(dtype).itemsize * sum(
        a.size for a in (xs, ys, ms, lls, las, lns)))
    p0 = GPParams(jnp.asarray(lls, dtype), jnp.asarray(las, dtype),
                  jnp.asarray(lns, dtype))
    p = _fit_lanes(p0, jnp.asarray(xs, dtype), jnp.asarray(ys, dtype),
                   jnp.asarray(ms, dtype), jnp.asarray(st),
                   max_steps=max(steps_list))
    jax.block_until_ready(p.log_ls)
    return [GPParams(p.log_ls[i], p.log_amp[i], p.log_noise[i])
            for i in range(k)]


@jax.jit
def _posterior(params: GPParams, x, y, mask, y_mean, y_std) -> GPPosterior:
    k = _masked_cov(params, x, mask)
    chol = jnp.linalg.cholesky(k)
    ym = y * mask
    alpha = jax.scipy.linalg.cho_solve((chol, True), ym)
    return GPPosterior(params, x, mask, ym, chol, alpha, y_mean, y_std)


def _pad(x: np.ndarray, y: np.ndarray, bucket: int, dtype):
    # pad on the host: device-side .at[:n].set would compile a fresh
    # scatter for every distinct n, defeating the bucketing
    n, d = x.shape
    xp = np.zeros((bucket, d), np.float64)
    xp[:n] = x
    yp = np.zeros((bucket,), np.float64)
    yp[:n] = y
    mask = np.zeros((bucket,), np.float64)
    mask[:n] = 1.0
    return (jnp.asarray(xp, dtype), jnp.asarray(yp, dtype),
            jnp.asarray(mask, dtype))


def fit_gp(x: np.ndarray, y: np.ndarray, steps: int = 150,
           params0: Optional[GPParams] = None,
           bucket: Optional[int] = None) -> GPPosterior:
    """x in unit cube (n,d); y raw objective (normalized internally).

    ``bucket`` pads the training set to a static shape (default: smallest
    power-of-two bucket); ``params0`` warm-starts Adam from a previous fit.
    """
    dtype = _dtype()
    x = np.asarray(x, np.float64)
    y_raw = np.asarray(y, np.float64)
    n, d = x.shape
    b = bucket_size(n) if bucket is None else int(bucket)
    if b < n:
        raise ValueError(f"bucket {b} smaller than training set {n}")
    # normalize on the host: device ops on the unpadded (n,) array would
    # compile per history size
    mean = float(np.mean(y_raw))
    std = max(float(np.std(y_raw)), 1e-6)
    y_mean = jnp.asarray(mean, dtype)
    y_std = jnp.asarray(std, dtype)
    xp, ynp, mask = _pad(x, (y_raw - mean) / std, b, dtype)
    tracing.annotate(h2d_bytes=np.dtype(dtype).itemsize * (
        xp.size + ynp.size + mask.size + 2))
    if params0 is None:
        p0 = GPParams(jnp.zeros(d, dtype) - 0.7, jnp.zeros((), dtype),
                      jnp.zeros((), dtype) - 2.0)
    else:
        p0 = jax.tree.map(lambda a: jnp.asarray(a, dtype), params0)
    p = _fit(p0, xp, ynp, mask, steps=steps)
    return _posterior(p, xp, ynp, mask, y_mean, y_std)


def make_posterior(params: GPParams, x: np.ndarray, y: np.ndarray,
                   y_mean=None, y_std=None,
                   bucket: Optional[int] = None) -> GPPosterior:
    """Exact posterior for *given* hyperparameters (no fitting) — the
    reference implementation the rank-1 update path is tested against."""
    dtype = _dtype()
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    b = bucket_size(x.shape[0]) if bucket is None else int(bucket)
    mean = float(np.mean(y) if y_mean is None else y_mean)
    std = max(float(np.std(y) if y_std is None else y_std), 1e-6)
    xp, ynp, mask = _pad(x, (y - mean) / std, b, dtype)
    return _posterior(jax.tree.map(lambda a: jnp.asarray(a, dtype), params),
                      xp, ynp, mask, jnp.asarray(mean, dtype),
                      jnp.asarray(std, dtype))


# ------------------------------------------------------- sparse posterior
def sparse_subset(n: int, best_idx: int, m: int = SPARSE_MAX) -> np.ndarray:
    """Indices of the subset-of-data design over an ``n``-point history:
    the incumbent (``best_idx``), the most recent ``m // 2`` points (the
    region speculation is actively exploring — and the rows the staleness
    bound judges freshness against), and an even stride over the older
    remainder for global coverage.  Deterministic in (n, best_idx, m) so
    repeated reconditions reuse the same design and tests can assert on
    it.  Returns sorted unique indices, ``len <= m``."""
    n = int(n)
    m = max(1, int(m))
    if n <= m:
        return np.arange(n)
    recent = np.arange(n - m // 2, n)
    rest = m - len(recent) - 1                    # slots for old coverage
    old = np.linspace(0, n - m // 2 - 1, num=max(rest, 0)).astype(int) \
        if rest > 0 else np.empty(0, int)
    return np.unique(np.concatenate([[int(best_idx)], old, recent]))


def sparse_posterior(params: GPParams, x: np.ndarray, y: np.ndarray,
                     m: int = SPARSE_MAX, extra: int = 0
                     ) -> Tuple[GPPosterior, np.ndarray]:
    """Sparse speculative posterior: an *exact* GP conditioned on the
    ``sparse_subset`` design only, at the given (already-fit)
    hyperparameters — conditioning is one O(m³) Cholesky independent of
    history size.  ``extra`` reserves padded slots for constant-liar
    folds on top of the subset (the bucket is sized to absorb them), so
    ``append_lie``/``select_batch`` work on the result unchanged.
    Normalization uses the *full* history's mean/std: predicted means and
    the EI ``best`` threshold stay in the same raw units as the exact
    posterior.  Returns (posterior, subset indices)."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    idx = sparse_subset(len(x), int(np.argmax(y)), m)
    bucket = bucket_size(len(idx) + max(0, int(extra)))
    mean = float(np.mean(y))
    std = max(float(np.std(y)), 1e-6)
    post = make_posterior(params, x[idx], y[idx], y_mean=mean, y_std=std,
                          bucket=bucket)
    return post, idx


# ---------------------------------------------------------------- prewarm
def prewarm_bucket(d: int, bucket: int, fit_steps=(), k_pads=(),
                   n_cand: int = 64, fit_lanes=(), select_lanes=()) -> None:
    """Compile every jitted kernel on the ask path for one bucket shape,
    using throwaway data: the hyperparameter fit (one ``_fit`` variant per
    entry in ``fit_steps``), the exact posterior, the rank-1 appends, and
    the q-EI scan (one variant per ``k_pads`` entry, at the real candidate
    pool size ``n_cand``).  XLA caches compilations per shape signature,
    so calling this off the request path moves the first-touch compile
    cost (~0.7 s per bucket on the dev container) out of ``ask`` — the
    dominant term in the cold `gp/h10` and bucket-crossing `gp_batch8`
    latencies.  Idempotent: re-running against warm caches costs only the
    (small) dummy-data compute.

    ``fit_lanes`` is the k-pad ladder of the batched executor path
    (ISSUE 8): for each lane count the ``_fit_lanes`` variant is
    compiled at every ``fit_steps`` entry, so a fleet's first batched
    refit dispatch doesn't pay its (bucket, steps, lane-pad) compile
    under load.  Off by default — batched dispatches already run off
    the request path, so lazy first-touch compiles only delay one
    install.

    ``select_lanes`` is the analogous lane-pad ladder of the batched
    *ask* path (ISSUE 10): for each lane count the ``_select_lanes``
    variant is compiled at the fixed ``SELECT_PAD`` scan length and the
    real pool size ``n_cand``, so a shard's first co-batched refill
    dispatch never XLA-compiles mid-run."""
    x = np.zeros((2, d), np.float64)
    x[1] = 0.5
    y = np.array([0.0, 1.0], np.float64)
    post = None
    for s in sorted({int(s) for s in fit_steps}):
        post = fit_gp(x, y, steps=s, bucket=bucket)
        for lanes in sorted({lane_pad(int(kp)) for kp in fit_lanes}):
            batched_fit([(x, y, None)] * lanes, steps=s, bucket=bucket)
    if post is None:
        post = make_posterior(
            GPParams(jnp.zeros(d, _dtype()), jnp.zeros(()), jnp.zeros(())),
            x, y, bucket=bucket)
    # match the real call signatures exactly (host numpy float32 inputs)
    append_point(post, np.asarray(x[0], np.float32), np.float32(0.5))
    append_lie(post, np.asarray(x[0], np.float32))
    cand = np.zeros((n_cand, d), np.float32)
    for kp in sorted({int(k) for k in k_pads}):
        if kp + 2 <= bucket:    # the scan needs kp free padded slots
            select_batch(post, cand, np.float32(1.0), kp)
    if SELECT_PAD + 2 <= bucket:
        for lanes in sorted({lane_pad(int(s)) for s in select_lanes}):
            batched_select([(post, cand, np.float32(1.0), 1)] * lanes)


# ---------------------------------------------------------------- queries
@jax.jit
def predict(post: GPPosterior, xq: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Posterior mean/stddev at query points (m,d) — in raw y units."""
    kq = matern52(xq, post.x, post.params) * post.mask[None, :]   # (m,b)
    mu = _dot(kq, post.alpha)
    v = jax.scipy.linalg.solve_triangular(post.chol, kq.T, lower=True)
    amp2 = jnp.exp(2 * post.params.log_amp)
    var = jnp.maximum(amp2 - jnp.sum(v * v, axis=0), 1e-12)
    return (mu * post.y_std + post.y_mean,
            jnp.sqrt(var) * post.y_std)


@jax.jit
def expected_improvement(post: GPPosterior, xq: jnp.ndarray,
                         best: jnp.ndarray, xi: float = 0.01) -> jnp.ndarray:
    mu, sd = predict(post, xq)
    z = (mu - best - xi) / sd
    ncdf = 0.5 * (1 + jax.scipy.special.erf(z / jnp.sqrt(2.0)))
    npdf = jnp.exp(-0.5 * z * z) / jnp.sqrt(2 * jnp.pi)
    return (mu - best - xi) * ncdf + sd * npdf


# ---------------------------------------------------------- rank-1 growth
def _append_norm(post: GPPosterior, xn: jnp.ndarray,
                 yn: jnp.ndarray) -> GPPosterior:
    """Grow the posterior into the first free padded slot: bordered
    Cholesky (new row [l12, l22]) + two triangular solves for alpha.
    O(b²); hyperparameters and y-normalization are frozen.  Real rows
    occupy a prefix, so the new point *is* the last real row and the
    identity rows below it stay a valid Cholesky of the masked cov."""
    idx = jnp.sum(post.mask).astype(jnp.int32)
    kvec = (matern52(xn[None], post.x, post.params)[0] * post.mask)
    l12 = jax.scipy.linalg.solve_triangular(post.chol, kvec, lower=True)
    kss = jnp.exp(2 * post.params.log_amp) + _noise2(post.params)
    l22 = jnp.sqrt(jnp.maximum(kss - _dot(l12, l12), 1e-10))
    chol = post.chol.at[idx, :].set(l12.at[idx].set(l22))
    x = post.x.at[idx].set(xn)
    mask = post.mask.at[idx].set(1.0)
    y = post.y.at[idx].set(yn)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    return GPPosterior(post.params, x, mask, y, chol, alpha,
                       post.y_mean, post.y_std)


@jax.jit
def append_point(post: GPPosterior, xn: jnp.ndarray,
                 y_raw: jnp.ndarray) -> GPPosterior:
    """Rank-1 fold of a real observation (raw y units)."""
    return _append_norm(post, xn, (y_raw - post.y_mean) / post.y_std)


@jax.jit
def append_lie(post: GPPosterior, xn: jnp.ndarray) -> GPPosterior:
    """Constant liar: pin a pending suggestion at its posterior mean."""
    kvec = matern52(xn[None], post.x, post.params)[0] * post.mask
    return _append_norm(post, xn, _dot(kvec, post.alpha))


@functools.partial(jax.jit, static_argnames=("k_pad",))
def _select_scan(post: GPPosterior, cand: jnp.ndarray, best: jnp.ndarray,
                 k: jnp.ndarray, k_pad: int):
    """q-EI by sequential constant-liar greedy, fully inside one jitted
    scan: argmax EI over the candidate pool, fold the pick in as a lie,
    repeat.  The scan length is padded to ``k_pad`` (a power of two) with
    the live count ``k`` traced, so varying batch sizes share one compile
    per bucket; steps past ``k`` are computed then reverted wholesale."""
    m = cand.shape[0]

    def step(carry, i):
        p, taken = carry
        ei = expected_improvement(p, cand, best)
        ei = jnp.where(taken, -jnp.inf, ei)
        j = jnp.argmax(ei)
        p2 = append_lie(p, cand[j])
        live = i < k
        p = jax.tree.map(lambda new, old: jnp.where(live, new, old), p2, p)
        taken = jnp.where(live, taken.at[j].set(True), taken)
        return (p, taken), j

    (post, _), picks = jax.lax.scan(
        step, (post, jnp.zeros((m,), bool)), jnp.arange(k_pad))
    return picks, post


def select_batch(post: GPPosterior, cand: jnp.ndarray, best,
                 k: int) -> Tuple[jnp.ndarray, GPPosterior]:
    """Pick k batch points by greedy q-EI with constant-liar updates in
    one jitted pass.  Returns (picked candidate indices (k,), posterior
    with the k lies folded in).  The posterior must have >= k free slots;
    compiles once per (bucket, next-power-of-two(k))."""
    k = int(k)
    k_pad = 1 << max(0, k - 1).bit_length()
    picks, post = _select_scan(post, jnp.asarray(cand),
                               jnp.asarray(best, post.y_mean.dtype),
                               jnp.asarray(k, jnp.int32), k_pad)
    return picks[:k], post


# ----------------------------------------------------- batched q-EI select
@functools.partial(jax.jit, static_argnames=("k_pad",))
def _select_lanes(post: GPPosterior, cand: jnp.ndarray, best: jnp.ndarray,
                  k: jnp.ndarray, k_pad: int):
    """Lane-batched ``_select_scan``: every posterior leaf, the candidate
    pool (kl,m,d), the EI threshold ``best`` (kl,) and the live pick
    count ``k`` (kl,) carry a leading lane axis, and one greedy
    constant-liar scan advances all lanes together.

    Unlike the serial scan — which recomputes the full cross-covariance
    ``kq = cov(cand, X)`` and whitened solve ``v = L⁻¹kqᵀ`` (O(b²m))
    every step — the batched scan pays that factorization ONCE per
    dispatch and extends it incrementally: a lie append adds one bordered
    Cholesky row, so only one new column of ``kq`` (O(md)), one forward-
    substitution row of ``v`` (O(bm)) and a rank-1 update of the
    predictive-variance partials change per step.  The step-0 EI is
    algebraically the same quantity ``ops.gp_ei`` computes (mirrored
    here so the factors stay live in the scan carry); every serial step
    after it drops from O(b²m) to O(bm), which is what makes the batched
    plane cheaper per ask than the serial path even on a single-core CPU
    host where vmap buys no parallelism (see benchmarks/bench_ask.py).

    Lanes are independent: a lane whose own ``k`` is spent (and the
    all-zero-mask lane padding, where k == 0) keeps computing but has
    its posterior and taken-mask updates reverted — the carried
    ``kq/v/ss`` factors are deliberately left hot, since a dead lane's
    later picks and factors are discarded by the caller and never feed
    another lane.  Mixed batch sizes share one compile per (bucket,
    k_pad, lane-pad) triple."""
    kl, m = cand.shape[0], cand.shape[1]
    lanes = jnp.arange(kl)

    def factorize(p, c):
        kq = matern52(c, p.x, p.params) * p.mask[None, :]        # (m,b)
        v = jax.scipy.linalg.solve_triangular(p.chol, kq.T,
                                              lower=True)        # (b,m)
        return kq, v
    kq, v = jax.vmap(factorize)(post, cand)
    ss = jnp.sum(v * v, axis=1)                                  # (kl,m)

    def lane_step(p, kq, v, ss, taken, c, b_inc, k1, i):
        amp2 = jnp.exp(2 * p.params.log_amp)
        mu_n = _dot(kq, p.alpha)                                 # (m,)
        var = jnp.maximum(amp2 - ss, 1e-12)
        mu = mu_n * p.y_std + p.y_mean
        sd = jnp.sqrt(var) * p.y_std
        z = (mu - b_inc - 0.01) / sd
        ncdf = 0.5 * (1 + jax.scipy.special.erf(z / jnp.sqrt(2.0)))
        npdf = jnp.exp(-0.5 * z * z) / jnp.sqrt(2 * jnp.pi)
        ei = (mu - b_inc - 0.01) * ncdf + sd * npdf
        ei = jnp.where(taken, -jnp.inf, ei)
        j = jnp.argmax(ei)
        xn = c[j]
        # bordered-Cholesky append (mirrors _append_norm), reusing the
        # carried factors: l12 = L⁻¹ cov(xn, X) is column j of v and
        # l12·l12 is ss[j] — both already paid for
        idx = jnp.sum(p.mask).astype(jnp.int32)
        l12 = v[:, j]
        kss = amp2 + _noise2(p.params)
        l22 = jnp.sqrt(jnp.maximum(kss - ss[j], 1e-10))
        chol = p.chol.at[idx, :].set(l12.at[idx].set(l22))
        x = p.x.at[idx].set(xn)
        mask = p.mask.at[idx].set(1.0)
        y = p.y.at[idx].set(mu_n[j])                 # constant liar
        alpha = jax.scipy.linalg.cho_solve((chol, True), y)
        p2 = GPPosterior(p.params, x, mask, y, chol, alpha,
                         p.y_mean, p.y_std)
        # extend the factors by the new posterior row: one kernel column,
        # one forward-substitution row, one variance partial
        kq_col = matern52(c, xn[None], p.params)[:, 0]           # (m,)
        kq2 = kq.at[:, idx].set(kq_col)
        v_row = (kq_col - _dot(l12, v)) / l22                    # (m,)
        v2 = v.at[idx, :].set(v_row)
        ss2 = ss + v_row * v_row
        live = i < k1
        p = jax.tree.map(lambda new, old: jnp.where(live, new, old), p2, p)
        taken = jnp.where(live, taken.at[j].set(True), taken)
        return p, kq2, v2, ss2, taken, j

    def step(carry, i):
        p, kq, v, ss, taken = carry
        p, kq, v, ss, taken, j = jax.vmap(
            lane_step, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None))(
            p, kq, v, ss, taken, cand, best, k, i)
        return (p, kq, v, ss, taken), j

    (post, _, _, _, _), picks = jax.lax.scan(
        step, (post, kq, v, ss, jnp.zeros((kl, m), bool)),
        jnp.arange(k_pad))
    return picks.T, post                                     # (kl,k_pad)


def _inert_posterior(b: int, d: int, dtype) -> GPPosterior:
    """Lane padding for ``batched_select``: an empty posterior whose
    masked covariance is the identity — chol = I, alpha = 0, so EI and
    the bordered-Cholesky append stay finite — and whose k == 0 means
    every scan step is reverted anyway."""
    return GPPosterior(
        GPParams(jnp.zeros((d,), dtype), jnp.zeros((), dtype),
                 jnp.zeros((), dtype)),
        jnp.zeros((b, d), dtype), jnp.zeros((b,), dtype),
        jnp.zeros((b,), dtype), jnp.eye(b, dtype=dtype),
        jnp.zeros((b,), dtype), jnp.zeros((), dtype),
        jnp.ones((), dtype))


def batched_select(items, k_pad: int = SELECT_PAD) -> list:
    """Run k experiments' q-EI batch selections in ONE vmap'd dispatch.

    ``items`` is a sequence of ``(post, cand, best, k)`` tuples — post a
    ``GPPosterior``, cand (m,d) candidate pool, best the raw-units EI
    incumbent, k <= ``k_pad`` the live pick count — all sharing one
    posterior bucket and one pool shape.  Posteriors are stacked along a
    leading lane axis, the lane count is padded to the next power of two
    with inert lanes, and the scan length is the fixed ``k_pad`` (default
    ``SELECT_PAD``) with per-lane k traced, so XLA compiles once per
    (bucket, k_pad, lane-pad) triple regardless of each lane's batch
    size.  Returns a list of k ``(picks, post)`` pairs exactly as
    ``select_batch`` would produce — picks (k_i,) candidate indices,
    post the lane's posterior with its k_i lies folded in."""
    if not items:
        return []
    dtype = _dtype()
    kl = len(items)
    klp = lane_pad(kl)
    b = items[0][0].capacity
    d = int(items[0][0].x.shape[1])
    m = int(np.asarray(items[0][1]).shape[0])
    posts = []
    cands = np.zeros((klp, m, d), np.float32)
    bests = np.zeros((klp,), np.float64)
    ks = np.zeros((klp,), np.int32)
    for i, (post, cand, best, k) in enumerate(items):
        if post.capacity != b:
            raise ValueError(f"lane {i}: bucket {post.capacity} != {b}")
        cand = np.asarray(cand, np.float32)
        if cand.shape != (m, d):
            raise ValueError(f"lane {i}: pool {cand.shape} != {(m, d)}")
        if not 0 < int(k) <= k_pad:
            raise ValueError(f"lane {i}: k={k} outside (0, {k_pad}]")
        posts.append(post)
        cands[i] = cand
        bests[i] = float(best)
        ks[i] = int(k)
    posts.extend(_inert_posterior(b, d, dtype) for _ in range(klp - kl))
    tracing.annotate(h2d_bytes=ks.nbytes + np.dtype(dtype).itemsize * (
        cands.size + bests.size))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *posts)
    picks, posts_out = _select_lanes(
        stacked, jnp.asarray(cands, dtype), jnp.asarray(bests, dtype),
        jnp.asarray(ks), int(k_pad))
    jax.block_until_ready(picks)
    return [(picks[i, :int(ks[i])],
             jax.tree.map(lambda a, i=i: a[i], posts_out))
            for i in range(kl)]
