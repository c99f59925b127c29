"""From the program's spans to per-layer metrics, and onto the device
trace's clock.

A traced run may carry ``spans``: the records of ``repro.core.tracing``,
``(span_id, parent_id, request_id, name, thread_id, start_ns, end_ns,
attrs)`` on the host's ``time.monotonic_ns()`` clock.  Every function
here returns None, or an empty breakdown, where a run has none (a
program that records no spans).

A span whose name ends in ``_wait`` is waiting; any other is work.  A
span's self intervals are its own interval minus those of its children,
so a request parked in a wait is not counted as work by the spans
around it.

The device trace keeps its own clock (``bench/trace.py``: nanoseconds
from the profiler's start).  ``offset`` puts the spans on it: every
co-batched fit or ask dispatch blocks until its program has run, so the
device interval of that program lies inside the dispatch span, which
bounds the offset from both sides.  Where the trace file names the
profiler's start on the host's wall clock, that reading, taken to the
monotonic clock, is the offset, and the dispatches check it.
"""
from __future__ import annotations

import bisect
import collections
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import trace

ID, PARENT, REQUEST, NAME, THREAD, START, END, ATTRS = range(8)
MAX_UNCERTAINTY_NS = 500_000     # past this, the trace metrics are None
NO_SPAN = "no span"


def of(run) -> Optional[list]:
    return getattr(run, "spans", None) or None


def is_wait(name: str) -> bool:
    return name.endswith("_wait")


# ------------------------------------------------------------ the window
def _window_ns(run) -> Tuple[int, int]:
    return int(run.t0 * 1e9), int((run.t0 + run.seconds) * 1e9)


def durations(run, name: str, **attrs) -> List[int]:
    """Durations (ns) of the spans named ``name``, with these attrs,
    that began in the window."""
    lo, hi = _window_ns(run)
    return [r[END] - r[START] for r in of(run) or ()
            if r[NAME] == name and lo <= r[START] < hi
            and all((r[ATTRS] or {}).get(k) == v for k, v in attrs.items())]


def quantile_ms(values, q: float) -> Optional[float]:
    return 1e-6 * float(np.quantile(values, q)) if len(values) else None


def self_intervals(spans) -> Dict[int, List[Tuple[int, int]]]:
    """span_id -> the parts of its interval no child span covers."""
    children = collections.defaultdict(list)
    for r in spans:
        if r[PARENT]:
            children[r[PARENT]].append((r[START], r[END]))
    out = {}
    for r in spans:
        parts, t = [], r[START]
        for s, e in sorted(children.get(r[ID], ())):
            if s > t:
                parts.append((t, min(s, r[END])))
            t = max(t, e)
        if t < r[END]:
            parts.append((t, r[END]))
        out[r[ID]] = parts
    return out


def self_time_s(run, prefix: str) -> Optional[float]:
    """Summed self time (s), inside the window, of the spans whose name
    starts with ``prefix``."""
    spans = of(run)
    if spans is None:
        return None
    lo, hi = _window_ns(run)
    own = self_intervals(spans)
    return 1e-9 * sum(max(0, min(e, hi) - max(s, lo))
                      for r in spans if r[NAME].startswith(prefix)
                      for s, e in own[r[ID]])


# ------------------------------------------------------ clock alignment
def _launched(attrs) -> Optional[str]:
    """The module a dispatch span blocks on, where it blocks: a one-lane
    fit runs the serial fit, which returns before its program ends."""
    attrs = attrs or {}
    if attrs.get("kind") == "ask":
        return "jit__select_lanes"
    if attrs.get("kind") == "fit" and attrs.get("lanes", 0) > 1:
        return "jit__fit_lanes"
    return None


def offset_bounds(planes, spans):
    """(lo, hi) of the offset (trace ns = monotonic ns + offset) that the
    blocking dispatch spans allow, each containing the device interval
    of a program it launched; None where the spans contradict each
    other (one inside the trace that no offset shared by the most of
    them can satisfy) or none of them matches a program."""
    mods = collections.defaultdict(list)
    first, last = None, None
    for p in planes.values():
        for name, s, e in p.get(trace.MODULES, []):
            mods[name.split("(")[0]].append((s, e))
            first = s if first is None else min(first, s)
            last = e if last is None else max(last, e)
    disp = [r for r in spans
            if r[NAME] == "exec.dispatch" and _launched(r[ATTRS])]
    cands = [(e - r[END], s - r[START], i) for i, r in enumerate(disp)
             for s, e in mods.get(_launched(r[ATTRS]), ())
             if e - s <= r[END] - r[START]]
    if not cands:
        return None
    # sweep for the offset the most distinct dispatches agree on
    events = sorted([(lo, 0, i) for lo, _, i in cands]
                    + [(hi, 1, i) for _, hi, i in cands])
    open_n, covered, best, at = collections.Counter(), 0, 0, None
    for x, kind, i in events:
        if kind == 0:
            open_n[i] += 1
            covered += open_n[i] == 1
            if covered > best:
                best, at = covered, x
        else:
            open_n[i] -= 1
            covered -= open_n[i] == 0
    mine = collections.defaultdict(list)
    for a, b, i in cands:
        if a <= at <= b:
            mine[i].append((a, b))
    lo = max(min(a for a, _ in ivs) for ivs in mine.values())
    hi = min(max(b for _, b in ivs) for ivs in mine.values())
    for i, r in enumerate(disp):
        if i not in mine and first <= r[START] + at and r[END] + at <= last:
            return None
    return int(lo), int(hi)


def profile_start_prior(xplane) -> Optional[Tuple[int, int]]:
    """(offset, its error) in ns from the trace file's own start on the
    host's wall clock (its "Task Environment" plane) and this host's
    wall-minus-monotonic difference, read now."""
    from jax.profiler import ProfileData
    start = None
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
    if not start:
        return None
    best = None
    for _ in range(5):
        m0 = time.monotonic_ns()
        w = time.time_ns()
        m1 = time.monotonic_ns()
        if best is None or m1 - m0 < best[1]:
            best = (w - (m0 + m1) // 2, m1 - m0)
    return best[0] - int(start), best[1] // 2 + 1


def offset(run) -> Optional[Tuple[int, int]]:
    """(offset ns, uncertainty ns) putting the run's spans on its trace
    clock, printed with the dispatches' bounds; cached on the run."""
    if hasattr(run, "_span_offset"):
        return run._span_offset
    spans = of(run)
    bounds = offset_bounds(run.planes, spans) if spans else None
    prior = (profile_start_prior(run.xplane)
             if spans and getattr(run, "xplane", None) else None)
    out = None
    if bounds is not None:
        lo, hi = bounds
        if prior is not None and lo <= prior[0] <= hi:
            out = prior
        else:
            out = ((lo + hi) // 2, (hi - lo) // 2)
    print(f"[bench] span clock: offset {out} ns from dispatch bounds "
          f"{bounds} and the trace's own start {prior}", file=sys.stderr)
    run._span_offset = out
    return out


def _aligned(run):
    """Each span with its self intervals on the trace clock, or None
    where the offset is unknown or too uncertain to place them against
    device gaps; cached on the run."""
    if hasattr(run, "_span_aligned"):
        return run._span_aligned
    off = offset(run) if of(run) else None
    out = None
    if off is not None and off[1] <= MAX_UNCERTAINTY_NS:
        d = off[0]
        own = self_intervals(run.spans)
        out = [(r, [(s + d, e + d) for s, e in own[r[ID]]])
               for r in run.spans]
    run._span_aligned = out
    return out


# ------------------------------------------------- device gaps and host
def idle_intervals(planes) -> List[Tuple[int, int]]:
    """Gaps between device operations, from the first to the last."""
    gaps = []
    for p in planes.values():
        end = None
        for _, s, e in sorted(p.get(trace.OPS, []), key=lambda iv: iv[1]):
            if end is not None and s > end:
                gaps.append((end, s))
            end = e if end is None else max(end, e)
    return gaps


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(merged, starts, s, e) -> int:
    total = 0
    for a, b in merged[max(0, bisect.bisect_right(starts, s) - 1):]:
        if a >= e:
            break
        total += max(0, min(b, e) - max(a, s))
    return total


def idle_host_share(run) -> Optional[float]:
    """Of the device's idle time in the trace, the share during which a
    host work span was running (its self time) on some thread."""
    spans = _aligned(run)
    gaps = idle_intervals(run.planes)
    if spans is None or not gaps:
        return None
    work = _merge(iv for r, own in spans if not is_wait(r[NAME])
                  for iv in own)
    starts = [s for s, _ in work]
    idle = sum(e - s for s, e in gaps)
    return sum(_overlap(work, starts, s, e) for s, e in gaps) / idle


def idle_gaps_host(run, n: int = 10) -> list:
    """The ``n`` longest device gaps, each as [span, seconds, share]:
    the span whose self time covers the largest part of the gap (work
    or a wait), else ``no span``; share is the part it covers."""
    spans = _aligned(run)
    if spans is None:
        return []
    gaps = sorted(idle_intervals(run.planes), key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        cover, name = 0, NO_SPAN
        for r, own in spans:
            c = sum(max(0, min(b, e) - max(a, s)) for a, b in own)
            if c > cover:
                cover, name = c, r[NAME]
        out.append([name, (e - s) / 1e9, cover / (e - s)])
    return out


def programs_by_span(run, n: int = 40) -> list:
    """Device programs counted and timed by the span that was running on
    the host when each began: [span path, program, count, device
    seconds], the ``n`` most frequent.  The path names the span and its
    ancestors (root first).  Where several threads ran a work span then,
    the one entered last is taken, and the row's path ends in ``?``."""
    spans = _aligned(run)
    if spans is None:
        return []
    by_id = {r[ID]: r for r, _ in spans}
    # one sweep, in time order, over work self intervals [a, b) closing
    # (0) and opening (1), and device programs starting (2)
    events = [(b, 0, k, r) for k, (r, own) in enumerate(spans)
              if not is_wait(r[NAME]) for _, b in own]
    events += [(a, 1, k, r) for k, (r, own) in enumerate(spans)
               if not is_wait(r[NAME]) for a, _ in own]
    events += [(s, 2, -1, (name, e - s)) for p in run.planes.values()
               for name, s, e in p.get(trace.MODULES, [])]
    events.sort(key=lambda ev: ev[:3])
    running: Dict[int, Tuple[int, tuple]] = {}
    acc = collections.defaultdict(lambda: [0, 0])
    for t, kind, k, obj in events:
        if kind == 0:
            running.pop(k, None)
        elif kind == 1:
            running[k] = (t, obj)
        else:
            if running:
                _, r = max(running.values(), key=lambda tr: tr[0])
                key = _path(r, by_id) + ("?" if len(running) > 1 else "")
            else:
                key = NO_SPAN
            row = acc[(key, trace._short(obj[0]))]
            row[0] += 1
            row[1] += obj[1]
    rows = sorted(acc.items(), key=lambda kv: -kv[1][0])[:n]
    return [[path, prog, c, ns / 1e9] for (path, prog), (c, ns) in rows]


def _path(r, by_id) -> str:
    names = [r[NAME]]
    while r[PARENT] in by_id:
        r = by_id[r[PARENT]]
        names.append(r[NAME])
    return "/".join(reversed(names))
