"""jit'd public wrappers for the Pallas kernels.

The platform decides the path, at trace time, and callers never branch
on it themselves:

* on TPU every wrapper runs its Pallas kernel compiled (never in
  interpret mode).  There is no fallback: a kernel that fails to compile
  raises to the caller;
* on any other backend the wrappers run the jnp oracles in ``ref.py``,
  unless the caller passes ``force_kernel=True`` (the parity tests and
  ``chip_smoke.py`` do), which runs the kernel in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import gp as _gpk
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.int8_quant import int8_quantize as _quant
from repro.kernels.rglru_scan import rglru_scan as _rglru


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    force_kernel=False):
    if _on_tpu() or force_kernel:
        return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                      interpret=not _on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   softcap=softcap)


def rglru_scan(log_a, b, *, force_kernel=False):
    if _on_tpu() or force_kernel:
        return _rglru(log_a, b, interpret=not _on_tpu())
    return ref.rglru_scan_ref(log_a, b)


def int8_quantize(x, *, force_kernel=False):
    if _on_tpu() or force_kernel:
        return _quant(x, interpret=not _on_tpu())
    return ref.int8_quant_ref(x)


def gp_neg_mll(log_ls, log_amp, log_noise, x, y, mask, *,
               force_kernel=False):
    """Batched masked GP neg-MLL over lanes (ISSUE 8): fused Pallas
    Cholesky+solve+logdet with an analytic custom_vjp on TPU, plain
    differentiable jnp on CPU.  Shapes: log_ls (k,d), log_amp (k,),
    log_noise (k,), x (k,b,d), y (k,b), mask (k,b) -> nll (k,)."""
    if _on_tpu() or force_kernel:
        return _gpk.gp_nll(log_ls, log_amp, log_noise, x, y, mask,
                           interpret=not _on_tpu())
    return ref.gp_nll_ref(log_ls, log_amp, log_noise, x, y, mask)


def gp_fit_grads(log_ls, log_amp, log_noise, x, y, mask, *,
                 force_kernel=False):
    """Per-lane NLL hyperparameter gradients for the batched Adam fit
    loop (``gp._fit_lanes``).  On TPU this differentiates the fused
    Pallas ``gp_nll`` (its custom_vjp reuses the kernel's Cholesky/solve
    residuals); on CPU it runs the GEMM-rich analytic adjoint directly
    — cheaper per lane than autodiff through ``jnp.linalg.cholesky``.
    Returns (g_log_ls (k,d), g_log_amp (k,), g_log_noise (k,))."""
    if _on_tpu() or force_kernel:
        def nll_sum(ll, la, ln):
            return jnp.sum(_gpk.gp_nll(ll, la, ln, x, y, mask,
                                       interpret=not _on_tpu()))
        return jax.grad(nll_sum, argnums=(0, 1, 2))(
            log_ls, log_amp, log_noise)
    return ref.gp_nll_grads_ref(log_ls, log_amp, log_noise, x, y, mask)


def gp_ei(log_ls, log_amp, x, mask, chol, alpha, y_mean, y_std, cand,
          best, *, xi=0.01, force_kernel=False):
    """Batched expected improvement over per-lane posteriors (ISSUE 8).
    Shapes as in ``ref.gp_ei_ref`` -> ei (k,m) in raw y units."""
    if _on_tpu() or force_kernel:
        return _gpk.gp_ei(log_ls, log_amp, x, mask, chol, alpha, y_mean,
                          y_std, cand, best, xi=xi,
                          interpret=not _on_tpu())
    return ref.gp_ei_ref(log_ls, log_amp, x, mask, chol, alpha, y_mean,
                         y_std, cand, best, xi=xi)
