"""Service + pump: share of the window's suggests served from the
prefetch queue, Δhits / (Δhits + Δmisses) over every experiment's pump
counters."""


def read(run):
    hits = run.stats1["hits"] - run.stats0["hits"]
    misses = run.stats1["misses"] - run.stats0["misses"]
    return hits / (hits + misses) if hits + misses else None
