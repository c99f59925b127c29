"""From trace to metrics: the interval arithmetic on hand-made events,
and the whole reduction on a trace recorded on a TPU v5e
(``bench/testdata/``: the device events of a 3 s traced window of the
d8-steady cell, as ``trace.read`` returns them from the xplane file, op
names cut at 400 characters)."""
import gzip
import json
import pathlib

import pytest

from bench import trace
from bench.drivers import gp_service

TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"
RECORDED = TESTDATA / "d8-steady-tpu-v5e-window.json.gz"


def _planes(ops, modules=()):
    return {"/device:TPU:0": {trace.OPS: list(ops),
                              trace.MODULES: list(modules)}}


def test_union_of_overlapping_intervals():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert trace.union_ns(evs) == 25
    assert trace.union_ns([]) == 0


def test_busy_is_averaged_over_devices():
    planes = {"/device:TPU:0": {trace.OPS: [("x", 0, 2_000_000_000)]},
              "/device:TPU:1": {trace.OPS: [("x", 0, 1_000_000_000)]}}
    assert trace.busy_s(planes) == 1.5


def test_module_stats_and_top_ops():
    mods = [("jit__fit_lanes(1)", 0, 3_000_000), ("jit__fit(2)", 5, 10),
            ("jit__fit_lanes_other", 0, 100), ("jit__posterior", 0, 7)]
    planes = _planes([("_nll_kernel", 0, 4), ("fusion.1", 4, 5),
                      ("_nll_kernel", 10, 14)], mods)
    n, secs = trace.module_stats(planes, gp_service_fit_pattern())
    assert n == 2 and secs == pytest.approx((3_000_000 + 5) / 1e9)
    assert trace.top_ops(planes)[0] == ["_nll_kernel", 8e-9]


def test_idle_gaps_are_named_by_neighbours():
    mods = [("jit__a", 0, 10), ("jit__b", 100, 110), ("jit__c", 130, 140)]
    ops = [("op_a", 0, 10), ("op_b", 100, 110), ("op_c", 130, 140)]
    gaps = trace.idle_gaps(_planes(ops, mods))
    assert gaps[0] == ["op_a -> jit__b", 90e-9]
    assert gaps[1] == ["op_b -> jit__c", 20e-9]


def gp_service_fit_pattern():
    from bench.metrics import fit_device_ms
    return fit_device_ms.FIT


def test_recorded_trace_reduces_to_metrics():
    planes = json.loads(gzip.decompress(RECORDED.read_bytes()))
    planes = {p: {line: [tuple(ev) for ev in evs]
                  for line, evs in lines.items()}
              for p, lines in planes.items()}
    assert list(planes) == ["/device:TPU:0"]
    busy = trace.busy_s(planes)
    ops = planes["/device:TPU:0"][trace.OPS]
    span = (max(e for _, _, e in ops) - min(s for _, s, _ in ops)) / 1e9
    assert 0 < busy < 0.2 * span     # the cell's chip is mostly idle
    n_fit, s_fit = trace.module_stats(planes, gp_service_fit_pattern())
    from bench.metrics import ask_device_ms, gp_nll_roofline
    n_ask, s_ask = trace.module_stats(planes, ask_device_ms.ASK)
    assert n_fit == 8 and n_ask > 0
    assert 0 < s_fit + s_ask <= busy
    kernel = trace.matching(planes, trace.OPS, gp_nll_roofline.KERNEL)
    assert len(kernel) == 200   # one per Adam step of the co-batched fits
    top = trace.top_ops(planes)
    assert len(top) == 10 and all(" = " not in name for name, _ in top)
    gaps = trace.idle_gaps(planes)
    assert len(gaps) == 10 and gaps[0][1] >= gaps[-1][1] > 0
    assert gp_service.RunData(planes=planes).planes is planes
