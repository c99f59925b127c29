"""Store persistence + the six CLI verbs (paper §3.1)."""
import json
import pathlib
import sys
import tempfile
import threading

import pytest
import yaml

from repro.core import ExperimentConfig, Observation, Param, Space, Store
from repro.launch.cli import main as cli_main


def test_store_observation_log_roundtrip():
    store = Store(tempfile.mkdtemp())
    cfg = ExperimentConfig(name="x", space=Space([Param("a", "double", 0, 1)]))
    store.create_experiment("e1", cfg)
    store.append_observation("e1", Observation({"a": 0.5}, 1.0), "t1")
    store.append_observation("e1", Observation({"a": 0.1}, None, failed=True),
                             "t2")
    obs = store.load_observations("e1")
    assert len(obs) == 2 and obs[1].failed
    cfg2 = store.load_config("e1")
    assert cfg2.name == "x" and cfg2.space.names == ["a"]


def test_logs_aggregated_per_experiment():
    store = Store(tempfile.mkdtemp())
    cfg = ExperimentConfig(name="x", space=Space([Param("a", "double", 0, 1)]))
    store.create_experiment("e1", cfg)
    store.append_log("e1", "t1", "hello from t1")
    store.append_log("e1", "t2", "hello from t2")
    lines = list(store.iter_logs("e1"))
    assert "[t1] hello from t1" in lines and "[t2] hello from t2" in lines


def test_two_stores_on_one_root_rewrite_one_status_concurrently():
    """Stores sharing a root (an in-process fleet's shards) each write a
    temporary file of their own before the rename, so neither renames the
    other's away and every rewrite lands whole."""
    root = tempfile.mkdtemp()
    cfg = ExperimentConfig(name="x", space=Space([Param("a", "double", 0, 1)]))
    stores = [Store(root), Store(root)]
    stores[0].create_experiment("e1", cfg)
    errors = []

    def writer(store, tag):
        try:
            for i in range(300):
                store.set_status("e1", {"writer": tag, "i": i})
        except OSError as e:
            errors.append(e)
    threads = [threading.Thread(target=writer, args=(s, n))
               for n, s in enumerate(stores)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(interval)
    assert errors == []
    assert Store(root).get_status("e1")["i"] == 299
    exp_dir = pathlib.Path(root) / "experiments" / "e1"
    assert sorted(p.name for p in exp_dir.iterdir() if p.is_file()) == [
        "config.json", "status.json"]


# --- CLI ------------------------------------------------------------------
def objective(assignment, ctx):
    ctx.log(f"x={assignment['x']}")
    return -(assignment["x"] - 0.25) ** 2


def test_cli_full_lifecycle(tmp_path, capsys):
    store = str(tmp_path / "store")
    cluster_yml = tmp_path / "cluster.yml"
    cluster_yml.write_text(yaml.safe_dump({
        "cluster_name": "orchestrate-cluster",
        "cloud_provider": "local",
        "pools": [{"name": "tpu", "resource": "tpu", "chips": 8}],
    }))
    exp_yml = tmp_path / "exp.yml"
    exp_yml.write_text(yaml.safe_dump({
        "name": "cli-exp", "budget": 6, "parallel": 3,
        "optimizer": "random",
        "space": [{"name": "x", "type": "double", "bounds": [0, 1]}],
        "resources": {"pool": "tpu", "chips": 2},
        "entrypoint": "tests.test_store_cli:objective",
    }))
    assert cli_main(["--store", store, "cluster", "create",
                     "-f", str(cluster_yml)]) == 0
    assert cli_main(["--store", store, "run", "-f", str(exp_yml)]) == 0
    out = capsys.readouterr().out
    assert "6 / 6 Observations" in out

    exp_id = sorted((pathlib.Path(store) / "experiments").iterdir())[-1].name
    assert cli_main(["--store", store, "status", exp_id]) == 0
    assert "Observations" in capsys.readouterr().out
    assert cli_main(["--store", store, "logs", exp_id]) == 0
    assert "x=" in capsys.readouterr().out
    assert cli_main(["--store", store, "delete", exp_id]) == 0
    # destroying the cluster keeps experiment records (paper §2.6)
    assert cli_main(["--store", store, "cluster", "destroy",
                     "-n", "orchestrate-cluster"]) == 0
    assert (pathlib.Path(store) / "experiments" / exp_id /
            "observations.jsonl").exists()
