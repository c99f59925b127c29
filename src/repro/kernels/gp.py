"""Pallas TPU kernels for the batched GP fit path (ISSUE 8).

Two kernels, both with a *lane* (= experiment) grid axis so k same-bucket
experiments run in one dispatch:

* ``gp_nll`` — masked batched negative log marginal likelihood: the
  covariance build, Cholesky factorization, forward solve and logdet are
  fused into ONE kernel per lane.  Identity-padding rows are masked
  in-kernel, so a lane's value is independent of its bucket's padding.
  Gradients come from a ``custom_vjp``: the forward kernel also emits its
  (L, z) residuals and the backward pass is the *analytic* adjoint
  tr(S·∂K/∂θ) with S = ½(K⁻¹ − αα') in plain jnp — cheaper than autodiff
  through a Cholesky, and shared by the TPU and interpret paths.

* ``gp_ei`` — batched expected improvement: per lane, the kernel builds
  the cross covariance over the candidate pool and runs the forward
  solve for the predictive variance.  The EI closed form needs ``erf``,
  which has no Pallas TPU lowering, so it runs in XLA on the kernel's
  mean and variance.

Layout.  Mosaic requires a block's last two dimensions to be multiples
of (8, 128) or equal to the array's, and cannot load or store a scalar
from VMEM.  So every per-lane operand is a 3-D array blocked one lane at
a time, ``(1, r, c)``; per-lane scalars are ``(k, 1, 1)`` arrays used as
(1, 1) vectors in the kernel; and where a vector is needed both as a
column and as a row (the mask, the inputs) the wrapper passes both
layouts instead of transposing in-kernel.

Numerics.  Squared distances are summed over dims from direct
differences, not from the |a|² − 2a·b + |b|² expansion, so the
covariance involves no matmul and no cancellation.  The kernels use only
elementwise ops, iota masks and reductions: nothing in them depends on
the MXU's f32 precision.

The Cholesky loop is right-looking: at step j, with A the trailing
matrix, ``l = A[:, j] / √A_jj`` masked to rows ≥ j is column j of L and
``A ← A − l lᵀ`` is the rank-1 update.  The right-hand side rides along
as a residual column (``r ← r − l z_j``), so the forward solve z = L⁻¹y
and the log-determinant come out of the same loop.  Masked (padded) rows
hold an identity block in A, so they factor to unit columns — log det
and the quadratic form see exactly the real rows.

Gradient cotangents are exact for the hyperparameters and y; ``x`` and
``mask`` cotangents are zero (the fit loop never differentiates them).

``ops.gp_neg_mll`` / ``ops.gp_fit_grads`` / ``ops.gp_ei`` run these
kernels compiled on TPU and the jnp oracles of ``ref.py`` elsewhere;
tests run them with ``interpret=True`` on the CPU (parity vs ref) and
compile them for a described v5e (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LOG_2PI = 1.8378770664093453
_HI = jax.lax.Precision.HIGHEST


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _lane(shape):
    """BlockSpec of one lane of a (k, r, c) operand."""
    return pl.BlockSpec((1,) + tuple(shape), lambda i: (i, 0, 0))


def _params(n_f32: int):
    """Scoped-VMEM limit for ``n_f32`` live f32 words per lane.  The
    (b,b) and (pool,b) tiles dominate: at bucket 1024 the default limit
    is too small (Mosaic refuses the kernel), and past 1024 even the cap
    below is — ``gp_nll`` compiles for buckets up to 1024 on a v5e."""
    need = 4 * n_f32 + (8 << 20)
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(need, 32 << 20), 100 << 20)))


def _sqdist(a_ref, bt_ref, ll_ref, d: int):
    """Σ_k ((a_ik − b_jk) / ls_k)² from an (n,d) block and a transposed
    (d,m) block -> (n,m)."""
    acc = None
    for c in range(d):
        inv = jnp.exp(-ll_ref[0, :, c:c + 1])                     # (1,1)
        diff = a_ref[0, :, c:c + 1] * inv - bt_ref[0, c:c + 1, :] * inv
        acc = diff * diff if acc is None else acc + diff * diff
    return acc


def _matern52(sq, amp2):
    r = jnp.sqrt(sq + 1e-12)
    s5r = jnp.sqrt(5.0) * r
    return amp2 * (1.0 + s5r + (5.0 / 3.0) * r * r) * jnp.exp(-s5r)


# ------------------------------------------------------------------ NLL
def _nll_kernel(ll_ref, la_ref, ln_ref, x_ref, xt_ref, y_ref, mc_ref,
                mr_ref, nll_ref, chol_ref, z_ref, *, b: int, d: int):
    amp2 = jnp.exp(2.0 * la_ref[0])                               # (1,1)
    noise2 = jnp.exp(2.0 * ln_ref[0]) + 1e-5
    mc = mc_ref[0]                                                # (b,1)
    rows = _iota((b, 1), 0)
    cols = _iota((1, b), 1)
    eye = _iota((b, b), 0) == _iota((b, b), 1)
    k = _matern52(_sqdist(x_ref, xt_ref, ll_ref, d), amp2)
    k = jnp.where(eye, k + noise2, k) * (mc * mr_ref[0])
    k = jnp.where(eye, k + (1.0 - mc), k)

    def step(j, carry):
        a, low, r, z, logdet = carry
        on_row = rows == j
        on_col = cols == j
        col = jnp.sum(jnp.where(on_col, a, 0.0), axis=1, keepdims=True)
        row = jnp.sum(jnp.where(on_row, a, 0.0), axis=0, keepdims=True)
        ljj = jnp.sqrt(jnp.maximum(
            jnp.sum(jnp.where(on_row, col, 0.0), axis=0, keepdims=True),
            1e-10))                                               # (1,1)
        lc = jnp.where(rows >= j, col / ljj, 0.0)                 # (b,1)
        lr = jnp.where(cols >= j, row / ljj, 0.0)                 # (1,b)
        zj = jnp.sum(jnp.where(on_row, r, 0.0), axis=0,
                     keepdims=True) / ljj                         # (1,1)
        return (a - lc * lr, jnp.where(on_col, lc, low), r - lc * zj,
                jnp.where(on_row, zj, z), logdet + jnp.log(ljj))

    zero_col = jnp.zeros((b, 1), jnp.float32)
    _, low, _, z, logdet = jax.lax.fori_loop(
        0, b, step, (k, jnp.zeros_like(k), y_ref[0] * mc, zero_col,
                     jnp.zeros((1, 1), jnp.float32)))
    nll_ref[0] = (0.5 * jnp.sum(z * z, axis=0, keepdims=True) + logdet
                  + 0.5 * _LOG_2PI * jnp.sum(mc, axis=0, keepdims=True))
    chol_ref[0] = low
    z_ref[0] = z


@functools.partial(jax.jit, static_argnames=("interpret",))
def gp_nll_chol(log_ls, log_amp, log_noise, x, y, mask, *,
                interpret: bool = False):
    """Fused batched NLL; also returns the (chol (k,b,b), z (k,b))
    residuals the analytic backward pass reuses.  Shapes as in
    ``ref.gp_nll_ref``."""
    k, b, d = x.shape
    f32 = jnp.float32
    x = x.astype(f32)
    m = mask.astype(f32)
    nll, chol, z = pl.pallas_call(
        functools.partial(_nll_kernel, b=b, d=d),
        grid=(k,),
        in_specs=[_lane((1, d)), _lane((1, 1)), _lane((1, 1)),
                  _lane((b, d)), _lane((d, b)), _lane((b, 1)),
                  _lane((b, 1)), _lane((1, b))],
        out_specs=[_lane((1, 1)), _lane((b, b)), _lane((b, 1))],
        out_shape=[jax.ShapeDtypeStruct((k, 1, 1), f32),
                   jax.ShapeDtypeStruct((k, b, b), f32),
                   jax.ShapeDtypeStruct((k, b, 1), f32)],
        compiler_params=_params(10 * b * b),
        interpret=interpret,
    )(log_ls.astype(f32).reshape(k, 1, d),
      log_amp.astype(f32).reshape(k, 1, 1),
      log_noise.astype(f32).reshape(k, 1, 1), x, x.transpose(0, 2, 1),
      y.astype(f32).reshape(k, b, 1), m.reshape(k, b, 1),
      m.reshape(k, 1, b))
    return nll[:, 0, 0], chol, z[:, :, 0]


def _nll_bwd_lane(ll, la, ln, xs, ms, L, z, g):
    """Analytic per-lane NLL gradient: dNLL/dθ = tr(S·∂K/∂θ) with
    S = ½(K⁻¹ − αα'), α = L⁻ᵀz — plain jnp, shared by TPU + interpret.
    Its two contractions run at HIGHEST precision: on TPU the default
    would round their f32 operands to bf16."""
    b = xs.shape[0]
    ls = jnp.exp(ll)
    amp2 = jnp.exp(2.0 * la)
    alpha = jax.scipy.linalg.solve_triangular(L, z, lower=True, trans=1)
    linv = jax.scipy.linalg.solve_triangular(L, jnp.eye(b), lower=True)
    S = 0.5 * (jnp.matmul(linv.T, linv, precision=_HI)
               - jnp.outer(alpha, alpha))
    mm = ms[:, None] * ms[None, :]
    smm = S * mm
    diff = xs[:, None, :] - xs[None, :, :]          # (b,b,d)
    sq_k = (diff / ls) ** 2
    r = jnp.sqrt(jnp.maximum(jnp.sum(sq_k, -1), 0.0) + 1e-12)
    s5r = jnp.sqrt(5.0) * r
    e = jnp.exp(-s5r)
    mat = amp2 * (1.0 + s5r + (5.0 / 3.0) * r * r) * e
    # ∂k/∂log_ls_k = amp2·(5/3)(1+√5r)e^{−√5r}·d_k²/ls_k²
    coeff = amp2 * (5.0 / 3.0) * (1.0 + s5r) * e
    g_ll = g * jnp.einsum("ij,ijk->k", smm * coeff, sq_k, precision=_HI)
    g_la = g * 2.0 * jnp.sum(smm * mat)
    g_ln = g * 2.0 * jnp.exp(2.0 * ln) * jnp.sum(jnp.diagonal(S) * ms)
    g_y = g * (alpha * ms)                          # dNLL/dy = K⁻¹(y·m)·m
    return g_ll, g_la, g_ln, g_y


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gp_nll(log_ls, log_amp, log_noise, x, y, mask, interpret):
    nll, _, _ = gp_nll_chol(log_ls, log_amp, log_noise, x, y, mask,
                            interpret=interpret)
    return nll


def _gp_nll_fwd(log_ls, log_amp, log_noise, x, y, mask, interpret):
    nll, chol, z = gp_nll_chol(log_ls, log_amp, log_noise, x, y, mask,
                               interpret=interpret)
    return nll, (log_ls, log_amp, log_noise, x, mask, chol, z)


def _gp_nll_bwd(interpret, res, g):
    log_ls, log_amp, log_noise, x, mask, chol, z = res
    g_ll, g_la, g_ln, g_y = jax.vmap(_nll_bwd_lane)(
        log_ls.astype(jnp.float32), log_amp.astype(jnp.float32),
        log_noise.astype(jnp.float32), x.astype(jnp.float32),
        mask.astype(jnp.float32), chol, z, g.astype(jnp.float32))
    return (g_ll, g_la, g_ln, jnp.zeros_like(x), g_y,
            jnp.zeros_like(mask))


_gp_nll.defvjp(_gp_nll_fwd, _gp_nll_bwd)


def gp_nll(log_ls, log_amp, log_noise, x, y, mask, *,
           interpret: bool = False):
    """Batched masked neg-MLL, Pallas-fused forward + analytic backward.
    Hyperparameter and y cotangents are exact; x/mask cotangents are
    zeros (the fit loop never differentiates them)."""
    return _gp_nll(log_ls, log_amp, log_noise, x, y, mask, interpret)


# ------------------------------------------------------------------- EI
def _ei_kernel(ll_ref, la_ref, xt_ref, mr_ref, lt_ref, ar_ref, cand_ref,
               mu_ref, var_ref, *, b: int, d: int):
    amp2 = jnp.exp(2.0 * la_ref[0])                               # (1,1)
    kq = (_matern52(_sqdist(cand_ref, xt_ref, ll_ref, d), amp2)
          * mr_ref[0])                                            # (mc,b)
    mu_ref[0] = jnp.sum(kq * ar_ref[0], axis=1, keepdims=True)    # (mc,1)
    rows = _iota((b, 1), 0)
    cols = _iota((1, b), 1)
    lt = lt_ref[0]                                                # Lᵀ

    # W = kq L⁻ᵀ column by column (W Lᵀ = kq): the predictive variance
    # only needs Σ_j W[:, j]², so W itself is never stored
    def step(j, carry):
        r, ss = carry
        on_col = cols == j
        lrow = jnp.sum(jnp.where(rows == j, lt, 0.0), axis=0,
                       keepdims=True)                             # L[:, j]ᵀ
        ljj = jnp.sum(jnp.where(on_col, lrow, 0.0), axis=1, keepdims=True)
        wj = jnp.sum(jnp.where(on_col, r, 0.0), axis=1,
                     keepdims=True) / ljj                         # (mc,1)
        return r - wj * lrow, ss + wj * wj

    _, ss = jax.lax.fori_loop(
        0, b, step, (kq, jnp.zeros((kq.shape[0], 1), jnp.float32)))
    var_ref[0] = jnp.maximum(amp2 - ss, 1e-12)


@functools.partial(jax.jit, static_argnames=("xi", "interpret"))
def gp_ei(log_ls, log_amp, x, mask, chol, alpha, y_mean, y_std,
          cand, best, *, xi: float = 0.01, interpret: bool = False):
    """Fused batched EI over per-lane posteriors; shapes as in
    ``ref.gp_ei_ref`` -> ei (k,m) in raw y units."""
    k, b, d = x.shape
    mc = cand.shape[1]
    f32 = jnp.float32
    mu, var = pl.pallas_call(
        functools.partial(_ei_kernel, b=b, d=d),
        grid=(k,),
        in_specs=[_lane((1, d)), _lane((1, 1)), _lane((d, b)),
                  _lane((1, b)), _lane((b, b)), _lane((1, b)),
                  _lane((mc, d))],
        out_specs=[_lane((mc, 1)), _lane((mc, 1))],
        out_shape=[jax.ShapeDtypeStruct((k, mc, 1), f32),
                   jax.ShapeDtypeStruct((k, mc, 1), f32)],
        compiler_params=_params(4 * b * b + 6 * mc * b),
        interpret=interpret,
    )(log_ls.astype(f32).reshape(k, 1, d),
      log_amp.astype(f32).reshape(k, 1, 1),
      x.astype(f32).transpose(0, 2, 1), mask.astype(f32).reshape(k, 1, b),
      chol.astype(f32).transpose(0, 2, 1), alpha.astype(f32).reshape(k, 1, b),
      cand.astype(f32))
    y_std = y_std.astype(f32)[:, None]
    mu = mu[:, :, 0] * y_std + y_mean.astype(f32)[:, None]
    sd = jnp.sqrt(var[:, :, 0]) * y_std
    imp = mu - best.astype(f32)[:, None] - xi
    z = imp / sd
    ncdf = 0.5 * (1.0 + jax.scipy.special.erf(z / jnp.sqrt(2.0)))
    npdf = jnp.exp(-0.5 * z * z) / jnp.sqrt(2.0 * jnp.pi)
    return imp * ncdf + sd * npdf
