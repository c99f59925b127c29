"""From a profiler trace to device timings.

``jax.profiler`` writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Each TPU is a plane named
``/device:TPU:<i>`` with a line ``XLA Modules`` (one event per program
execution, named after the jitted function, e.g. ``jit__fit_lanes``)
and a line ``XLA Ops`` (one event per operation, a Pallas kernel under
the custom call of its jitted wrapper, e.g. ``%jvp_jit_gp_nll_chol__.32
= ... custom-call(...)``).  Only these names are read from the program.
"""
from __future__ import annotations

import bisect
import pathlib
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)

MODULES, OPS = "XLA Modules", "XLA Ops"


def find_xplane(directory) -> Optional[pathlib.Path]:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    return found[-1] if found else None


def read(path) -> Dict[str, Dict[str, List[Interval]]]:
    """{device plane name: {line name: [(name, start_ns, end_ns)]}} for
    the TPU planes of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (MODULES, OPS):
                lines[line.name] = [(ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns))
                                    for ev in line.events]
        out[plane.name] = lines
    return out


def union_ns(intervals: List[Interval]) -> int:
    """Length of the union of the intervals."""
    total, end = 0, None
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_s(planes) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not planes:
        return 0.0
    return sum(union_ns(p.get(OPS, [])) for p in planes.values()) \
        / len(planes) / 1e9


def matching(planes, line: str, pattern: str) -> List[Interval]:
    rx = re.compile(pattern)
    return [iv for p in planes.values() for iv in p.get(line, [])
            if rx.search(iv[0])]


def module_stats(planes, pattern: str):
    """(executions, summed device seconds) of the programs whose module
    name matches ``pattern``."""
    evs = matching(planes, MODULES, pattern)
    return len(evs), sum(e - s for _, s, e in evs) / 1e9


def top_ops(planes, n: int = 10):
    """The ``n`` operations that took most device time, by name."""
    acc: Dict[str, int] = {}
    for p in planes.values():
        for name, s, e in p.get(OPS, []):
            name = _short(name)
            acc[name] = acc.get(name, 0) + (e - s)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(planes, n: int = 10):
    """The ``n`` longest gaps between device operations, each named by
    the program that ran before it and the one that ended it."""
    gaps = []
    for p in planes.values():
        starts = sorted((s, name) for name, s, _ in p.get(MODULES, []))
        times = [t for t, _ in starts]
        end, prev = None, None
        for name, s, e in sorted(p.get(OPS, []), key=lambda iv: iv[1]):
            if end is not None and s > end:
                i = bisect.bisect_left(times, s - 1)
                after = starts[i][1] if i < len(starts) else "?"
                gaps.append((s - end, f"{_short(prev)} -> {_short(after)}"))
            if end is None or e > end:
                end, prev = e, name
    return [[name, g / 1e9]
            for g, name in sorted(gaps, key=lambda x: -x[0])[:n]]


def _short(name: Optional[str]) -> str:
    """An op's or module's name without its HLO text (TPU op events are
    named ``%fusion.3 = f32[...] fusion(...)``, modules ``jit_f(12)``)."""
    return (name or "?").split(" = ")[0].split("(")[0][:60]
