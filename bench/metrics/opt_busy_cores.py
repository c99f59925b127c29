"""Optimizer: host cores kept busy by the optimizer, the summed self
time of the program's ``opt.*`` spans inside the window (a span's
duration less what its child spans cover) over the window's seconds."""
from bench import spans


def read(run):
    busy = spans.self_time_s(run, "opt.")
    return busy / run.seconds if busy is not None else None
