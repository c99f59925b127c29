"""FitExecutor: experiments fitted per fit dispatch in the window,
Δlanes / Δbatched from the executor's counters."""


def read(run):
    n = run.stats1["ex_batched"] - run.stats0["ex_batched"]
    lanes = run.stats1["ex_lanes"] - run.stats0["ex_lanes"]
    return lanes / n if n else None
