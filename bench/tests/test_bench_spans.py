"""From the program's spans to metrics: the clock offset solved on the
trace recorded on a TPU v5e (``bench/testdata/``) against dispatch
spans placed at a known offset, and the gap attribution and readers on
hand-made spans and planes."""
import gzip
import json
import pathlib

import numpy as np
import pytest

from bench import harness, spans, trace
from bench.drivers import gp_service

TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"
RECORDED = TESTDATA / "d8-steady-tpu-v5e-window.json.gz"
READERS = ["handler_p50_ms", "lock_wait_p95_ms", "miss_p50_ms",
           "exec_wait_p95_ms", "opt_busy_cores", "idle_host_share"]
MS = 1_000_000


def _recorded():
    planes = json.loads(gzip.decompress(RECORDED.read_bytes()))
    return {p: {line: [tuple(ev) for ev in evs]
                for line, evs in lines.items()}
            for p, lines in planes.items()}


def _span(i, name, start, end, parent=0, request=None, thread=1,
          **attrs):
    return (i, parent, request, name, thread, start, end, attrs or None)


def _dispatches(planes, off):
    """One blocking dispatch span per co-batched fit and ask program of
    the trace, on the host clock (trace ns = host ns + ``off``), opening
    50-438 us ahead of its program and closing 20-203 us behind it."""
    out = []
    for name, s, e in planes["/device:TPU:0"][trace.MODULES]:
        kind = {"jit__fit_lanes": "fit", "jit__select_lanes": "ask"}.get(
            name.split("(")[0])
        if kind:
            k = len(out)
            before, after = 50_000 + 97_000 * (k % 5), 20_000 + 61_000 * (k % 4)
            out.append(_span(k + 1, "exec.dispatch",
                             s - before - off, e + after - off,
                             kind=kind, lanes=2, bucket=512))
    return out


def _run(planes, recs, seconds=1.0):
    return gp_service.RunData(planes=planes, spans=recs, t0=0.0,
                              seconds=seconds)


def test_offset_recovered_from_dispatches_on_the_recorded_trace():
    planes = _recorded()
    off = -7_123_456_789
    recs = _dispatches(planes, off)
    assert len(recs) == 11          # 5 fits and 6 asks co-batched
    lo, hi = spans.offset_bounds(planes, recs)
    assert (lo, hi) == (off - 20_000, off + 50_000)
    got, unc = spans.offset(_run(planes, recs))
    assert (got, unc) == (off + 15_000, 35_000)
    # one dispatch inside the trace that cannot hold its program at the
    # offset the others share: the spans contradict each other
    bad = list(recs)
    i, p, q, n, t, s, e, a = bad[5]
    bad[5] = (i, p, q, n, t, s + 50 * MS, e + 50 * MS, a)
    assert spans.offset_bounds(planes, bad) is None
    assert spans.offset(_run(planes, bad)) is None
    assert spans.idle_host_share(_run(planes, bad)) is None


def _gapped():
    """A device busy over [0,1], [3,4], [6,7] and [9,10] ms (a fit and
    an ask program bracketing each gap), and host spans: work over the
    first gap, only a wait over the second, nothing over the third."""
    mods = [("jit__fit_lanes(1)", 0, 1 * MS), ("jit_append_lie(2)", 3 * MS,
                                               4 * MS),
            ("jit__select_lanes(3)", 6 * MS, 7 * MS),
            ("jit_append_lie(2)", 9 * MS, 10 * MS)]
    ops = [("op", s, e) for _, s, e in mods]
    planes = {"/device:TPU:0": {trace.OPS: ops, trace.MODULES: mods}}
    off = 5 * MS
    recs = [
        # dispatches holding their programs (host = trace - off)
        _span(1, "exec.dispatch", -off, 1 * MS - off,
              thread=2, kind="fit", lanes=2),
        _span(2, "exec.dispatch", 6 * MS - off, 7 * MS - off,
              thread=2, kind="ask", lanes=2),
        # gap 1 (1-3 ms): a tick whose fold runs over all of it and
        # dispatches the append at 3 ms
        _span(3, "pump.tick", 1 * MS - off, 4 * MS - off, thread=3),
        _span(4, "opt.fold_lies", 1 * MS - off, 3 * MS + 10 - off,
              parent=3, thread=3, lies=2),
        # gap 2 (4-6 ms): a request parked on a miss, nothing working
        _span(5, "http.request", 4 * MS - off, 6 * MS - off, thread=4,
              request=5, route="suggestions"),
        _span(6, "suggest", 4 * MS - off, 6 * MS - off, parent=5,
              thread=4, request=5),
        _span(7, "suggest.miss_wait", 4 * MS - off + 1, 6 * MS - off - 1,
              parent=6, thread=4, request=5),
    ]
    return planes, recs, off


def test_gaps_named_by_work_wait_or_no_span():
    planes, recs, off = _gapped()
    run = _run(planes, recs)
    assert spans.offset(run)[0] == off
    # gap 1 worked over, gap 2 only waited on (the request's own spans
    # add 2 ns of self time), gap 3 bare
    assert spans.idle_host_share(run) == pytest.approx((2 * MS + 2)
                                                       / (6 * MS))
    gaps = spans.idle_gaps_host(run)
    assert [g[0] for g in gaps] == ["opt.fold_lies", "suggest.miss_wait",
                                    spans.NO_SPAN]
    assert [g[1] for g in gaps] == [2e-3, 2e-3, 2e-3]
    assert gaps[0][2] == 1.0 and gaps[1][2] == pytest.approx(1.0, abs=1e-5)
    rows = {(path, prog): (c, s)
            for path, prog, c, s in spans.programs_by_span(run)}
    assert rows[("pump.tick/opt.fold_lies", "jit_append_lie")] == (1, 1e-3)
    assert rows[("exec.dispatch", "jit__fit_lanes")] == (1, 1e-3)
    assert rows[("exec.dispatch", "jit__select_lanes")] == (1, 1e-3)
    assert rows[(spans.NO_SPAN, "jit_append_lie")] == (1, 1e-3)


def test_self_time_leaves_out_children():
    recs = [_span(1, "opt.ask", 0, 10 * MS),
            _span(2, "opt.recondition", 2 * MS, 5 * MS, parent=1),
            _span(3, "opt.fold_lies", 3 * MS, 4 * MS, parent=2),
            _span(4, "pump.tick", 0, 20 * MS, thread=2)]
    own = spans.self_intervals(recs)
    assert own[1] == [(0, 2 * MS), (5 * MS, 10 * MS)]
    assert own[2] == [(2 * MS, 3 * MS), (4 * MS, 5 * MS)]
    run = _run({}, recs)
    assert spans.self_time_s(run, "opt.") == pytest.approx(10e-3)
    value = harness.read_layer_metrics(
        [{"name": "opt_busy_cores", "unit": "cores"}], run)
    assert value["opt_busy_cores"]["value"] == pytest.approx(10e-3)


@pytest.mark.parametrize("name,span,attrs,q", [
    ("handler_p50_ms", "http.request", {"route": "suggestions"}, 0.5),
    ("lock_wait_p95_ms", "suggest.lock_wait", {}, 0.95),
    ("miss_p50_ms", "suggest.miss_wait", {}, 0.5),
    ("exec_wait_p95_ms", "exec.queue_wait", {"prio": 1}, 0.95)])
def test_duration_readers_take_their_spans_in_the_window(name, span, attrs,
                                                         q):
    recs = [_span(i + 1, span, i * 10 * MS, i * 10 * MS + (i + 1) * MS,
                  **attrs) for i in range(20)]
    recs += [_span(30, span, -5 * MS, 90 * MS, **attrs),      # before
             _span(31, "http.request", 0, 99 * MS, route="observations")]
    out = harness.read_layer_metrics([{"name": name, "unit": "ms"}],
                                     _run({}, recs, seconds=0.2))
    want = np.quantile(np.arange(1, 21), q)
    assert out[name]["value"] == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_spans(name):
    """A program that records no spans (a run without ``spans``, or
    with none) leaves every span metric out of the result line."""
    planes, _, _ = _gapped()
    for run in (gp_service.RunData(planes=planes, t0=0.0, seconds=1.0),
                _run(planes, [])):
        assert harness.read_layer_metrics(
            [{"name": name, "unit": "x"}], run) == {}
    assert spans.idle_gaps_host(_run(planes, [])) == []
    assert spans.programs_by_span(_run(planes, [])) == []


def test_trace_start_read_onto_the_monotonic_clock(tmp_path):
    """The trace file's own start (wall clock), taken to the monotonic
    clock, falls inside the call that started the profiler."""
    import time

    import jax
    import jax.numpy as jnp
    m0 = time.monotonic_ns()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=gp_service._device_only())
    m1 = time.monotonic_ns()
    (jnp.ones(4) + 1).block_until_ready()
    jax.profiler.stop_trace()
    off, err = spans.profile_start_prior(trace.find_xplane(tmp_path))
    # trace ns 0 is the profiler's start: monotonic ns = -off there
    assert m0 - err <= -off <= m1 + err
    assert err < 1_000_000
