"""Operations and bytes the GP algorithms need, counted from their shapes.

Never taken from ``cost_analysis()``: those counts change when an
implementation swaps a kernel for XLA ops.  These count the algorithm
on its real (unpadded) rows, so padding, recomputation and masked lanes
show as a lower share of the roofline and never as work done.

Conventions: one multiply-add is two operations; a transcendental
(exp, sqrt, log) is one; f32 words are four bytes.
"""
from __future__ import annotations

F32 = 4


def cov_ops(n: int, m: int, d: int) -> int:
    """Matérn-5/2 ARD cross covariance of n by m points in d dims:
    per pair, d scaled differences squared and summed (3d), then
    sqrt, scale, polynomial and exp (10)."""
    return n * m * (3 * d + 10)


def nll_ops(n: int, d: int) -> int:
    """Masked neg-MLL of n real rows: covariance, Cholesky (n³/3),
    forward solve (n²), quadratic form and log-determinant (3n)."""
    return cov_ops(n, n, d) + n ** 3 // 3 + n * n + 3 * n


def nll_bytes(n: int, d: int) -> int:
    """Least HBM traffic of one lane of ``gp_nll``: read the inputs
    (x, y, mask, 2 + d hyperparameters), write the factor L (n²), the
    solve z (n) and the value."""
    return F32 * (n * d + 2 * n + d + 2 + n * n + n + 1)


def nll_grad_ops(n: int, d: int) -> int:
    """Neg-MLL plus its hyperparameter gradient by the analytic adjoint
    tr(S dK/dθ), S = ½(K⁻¹ − ααᵀ): the NLL, L⁻¹ (n³/3), K⁻¹ = L⁻ᵀL⁻¹
    (n³/3), α (n²), S (2n²), and per pair the d lengthscale terms and
    the amplitude term (3d + 6)."""
    return (nll_ops(n, d) + 2 * (n ** 3 // 3) + 3 * n * n
            + n * n * (3 * d + 6) + 2 * n)


def fit_ops(lanes) -> int:
    """Adam fit work: ``lanes`` is an iterable of (n, d, steps)."""
    return sum(steps * nll_grad_ops(n, d) for n, d, steps in lanes)


def roofline_s(ops: float, nbytes: float, peak: dict):
    """(least seconds, bound) on a chip with ``peak``: the larger of the
    compute time at the bf16 peak and the memory time at the HBM
    bandwidth, and which of the two it is."""
    t_ops = ops / peak["flops_bf16"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
