"""GP programs: device ms per execution of the ask programs (the q-EI
scans ``_select_lanes`` and ``_select_scan`` and the exact posterior
``_posterior``) in the trace."""
from bench import trace

ASK = r"^jit__(select_lanes|select_scan|posterior)(?![A-Za-z0-9_])"


def read(run):
    n, secs = trace.module_stats(run.planes, ASK)
    return 1e3 * secs / n if n else None
