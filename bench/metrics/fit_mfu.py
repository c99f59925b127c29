"""Device: the fit programs' share of the chip's peak, in %: the
algorithm's operations of every fit that ended in the traced window
(each real lane's Adam steps times a masked neg-MLL and its gradient at
its real rows, ``bench/costs.py``) over the fit programs' device time
in the trace times the published bf16 peak (the v5e publishes no f32
peak)."""
from bench import costs, peaks, trace

FIT = r"^jit__fit(_lanes)?(?![A-Za-z0-9_])"


def read(run):
    n, secs = trace.module_stats(run.planes, FIT)
    on, off = run.traced
    ops = costs.fit_ops(lane for t, _, lanes, _, _ in run.rec.fit_calls
                        if on <= t <= off for lane in lanes)
    if not n or not ops:
        return None
    return 100.0 * ops / (secs * peaks.peaks(run.device_kind)["flops_bf16"])
