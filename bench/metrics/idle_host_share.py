"""Device: of the chip's idle time in the trace (the gaps between its
operations), the share during which some host thread was running a
work span of the program (self time of a span whose name does not end
in ``_wait``), with the spans put on the trace's clock
(``bench/spans.py``).  None where that clock is known to worse than
half a millisecond."""
from bench import spans


def read(run):
    return spans.idle_host_share(run)
