"""GP programs: device ms per execution of the fit programs (``_fit``,
``_fit_lanes``) in the trace."""
from bench import trace

FIT = r"^jit__fit(_lanes)?(?![A-Za-z0-9_])"


def read(run):
    n, secs = trace.module_stats(run.planes, FIT)
    return 1e3 * secs / n if n else None
