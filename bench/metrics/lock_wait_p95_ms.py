"""Service and pump: 95th percentile (ms) of the waits for an
experiment's bookkeeping lock in ``suggest``, from the program's
``suggest.lock_wait`` spans begun in the window."""
from bench import spans


def read(run):
    return spans.quantile_ms(spans.durations(run, "suggest.lock_wait"),
                             0.95)
