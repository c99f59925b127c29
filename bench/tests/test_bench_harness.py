"""The harness: it refuses a host without a TPU, its last line keeps the
contract's schema, and a new configuration, traffic mix or per-layer
metric is found by name from new files alone."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

from bench import harness, run
from bench.drivers import gp_service

ROOT = pathlib.Path(__file__).resolve().parents[2]
BIG_SEED = 3000000017


def _run_cpu(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_host_without_tpu():
    r = _run_cpu(ROOT, "--workload", "d8-steady", "--seed", str(BIG_SEED),
                 "--seconds", "10", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cpu(tmp_path, "--workload", "d8-steady", "--seed", "1",
                 "--seconds", "10", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_result_line_schema():
    checks = [("dup_ids", 0, 0), ("ask_mean_gap", 0.01, 0.1)]
    line = harness.result_line(
        True, 400, 0, {"suggest_p95_ms": {"value": 12.5, "unit": "ms"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 123, "busy_s": 1.5, "window_s": 31.0},
        checks, {"device_ops": [["fusion", 0.5]], "idle_gaps": []})
    d = json.loads(line)
    assert list(d) == ["correct", "attempted", "failed", "metrics",
                       "device", "breakdown", "checks"]
    assert d["correct"] is True and d["attempted"] == 400
    assert d["metrics"]["suggest_p95_ms"] == {"value": 12.5, "unit": "ms"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        d["device"])
    assert d["checks"]["ask_mean_gap"] == {"value": 0.01, "limit": 0.1}


def test_benchmark_json_names_every_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["command"] == ["python3", "bench/run.py"]
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "bench" / "drivers" / f"{cfg['kind']}.py").exists()
    for w in bench["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert w["chips"] == 1
    for m in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}
    assert {m["name"] for m in bench["end_to_end"]} == {
        "setup_s", "suggest_p50_ms", "suggest_rate"}


def test_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "configs").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    cfg = json.loads((ROOT / "bench/configs/hpo-gp-d8.json").read_text())
    cfg["experiments"] = 5
    (tmp_path / "bench/configs/throwaway.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/throwaway-mix.json").write_text(
        json.dumps({"rate": 3.0, "trial_s": {"kind": "lognormal",
                                             "median": 1.0, "sigma": 0.5,
                                             "cap": 5.0}}))
    (tmp_path / "bench/metrics/throwaway_metric.py").write_text(
        "def read(run):\n    return run.value * 2\n")
    (tmp_path / "bench/metrics/silent_metric.py").write_text(
        "def read(run):\n    return None\n")
    bench["configs"].append({"name": "throwaway", "source": "x",
                             "file": "bench/configs/throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway-cell",
                               "config": "throwaway",
                               "traffic": "throwaway-mix", "chips": 1,
                               "why": "test"})
    new = [{"name": "throwaway_metric", "unit": "x", "better": "lower",
            "source": "program_counter", "layer": "test", "moves": "setup_s",
            "workloads": ["throwaway-cell"]},
           {"name": "silent_metric", "unit": "x", "better": "lower",
            "source": "program_counter", "layer": "test",
            "moves": "setup_s"}]
    bench["per_layer"].extend(new)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = run.cell("throwaway-cell", root=tmp_path)
    assert c["config"]["experiments"] == 5
    assert c["traffic"]["rate"] == 3.0
    names = [m["name"] for m in c["per_layer"]]
    assert "throwaway_metric" in names and "silent_metric" in names
    # a metric restricted to other cells is not asked of this one
    assert not harness.applies(new[0], "d8-steady")
    assert harness.applies(new[1], "d8-steady")
    got = harness.read_layer_metrics(new, type("R", (), {"value": 4.0})(),
                                     directory=tmp_path / "bench/metrics")
    # a reader that finds nothing leaves its metric out of the line
    assert got == {"throwaway_metric": {"value": 8.0, "unit": "x"}}
    data = gp_service.Data(c["config"], BIG_SEED)
    assert sorted(data.sizes) == [80, 100, 120, 140, 160]
