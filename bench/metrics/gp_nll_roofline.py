"""Kernels: the Pallas ``gp_nll`` kernel's share of its roofline, in %.

For each co-batched fit dispatch that ended in the traced window, each
real lane costs its Adam steps times one masked neg-MLL at its real rows
(``bench/costs.py``); the least time of that work on the chip (the
larger of compute at the bf16 peak and traffic at the HBM bandwidth,
``bench/peaks.py``) over the kernel's summed device time in the trace.
Padded rows, padded lanes and the steps of lanes already frozen are
work the kernel does and the algorithm does not need."""
import sys

from bench import costs, peaks, trace

KERNEL = r"gp_nll_chol\S* = .*custom-call"


def read(run):
    evs = trace.matching(run.planes, trace.OPS, KERNEL)
    t_kernel = sum(e - s for _, s, e in evs) / 1e9
    peak = peaks.peaks(run.device_kind)
    t_min, bound = 0.0, {}
    on, off = run.traced
    for t, kind, lanes, _, _ in run.rec.fit_calls:
        if kind != "fit_lanes" or not on <= t <= off:
            continue
        for n, d, steps in lanes:
            t, which = costs.roofline_s(costs.nll_ops(n, d),
                                        costs.nll_bytes(n, d), peak)
            t_min += steps * t
            bound[which] = bound.get(which, 0) + 1
    if not evs or t_min == 0.0:
        return None
    print(f"[bench] gp_nll roofline bound by {bound}", file=sys.stderr)
    return 100.0 * t_min / t_kernel
