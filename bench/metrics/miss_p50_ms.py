"""Service and pump: median time (ms) a suggest that found the prefetch
queue dry waited for its answer, from parking its slot to the result,
from the program's ``suggest.miss_wait`` spans begun in the window."""
from bench import spans


def read(run):
    return spans.quantile_ms(spans.durations(run, "suggest.miss_wait"),
                             0.5)
