"""Fit executor: 95th percentile (ms) of a job's time in the executor's
queue, from its first submit to the worker taking it, from the
program's ``exec.queue_wait`` spans begun in the window."""
from bench import spans


def read(run):
    return spans.quantile_ms(spans.durations(run, "exec.queue_wait"),
                             0.95)
