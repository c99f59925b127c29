"""Run one cell of the benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the workload's
configuration file, its traffic file ``bench/traffic/<traffic>.json``,
the driver ``bench/drivers/<kind>.py`` named by the configuration's
``kind``, and each per-layer metric's reader ``bench/metrics/<name>.py``.
A new configuration, traffic mix or metric is new files plus new
entries, never an edit.

The process holds the chip; it exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1``
the per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks``: every number compared, beside its limit.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's xplane.pb here")
    return ap.parse_args(argv)


def cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The workload entry with its configuration and traffic loaded."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    from bench import harness
    return {"workload": wl, "bench": bench,
            "config": json.loads((root / entry["file"]).read_text()),
            "traffic": json.loads(
                (root / "bench" / "traffic" / f"{wl['traffic']}.json")
                .read_text()),
            "end_to_end": [m for m in bench["end_to_end"]
                           if harness.applies(m, name)],
            "per_layer": [m for m in bench["per_layer"]
                          if harness.applies(m, name)]}


def main(argv=None) -> int:
    args = parse(argv)
    c = cell(args.workload)
    from bench import harness
    device = harness.require_devices(c["workload"]["chips"])
    driver = harness.load_module(
        ROOT / "bench" / "drivers" / f"{c['config']['kind']}.py")
    res = driver.run(c, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START, device=device,
                     keep_trace=args.keep_trace)
    for name, value, limit in res["checks"]:
        print(f"[check] {name} = {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(res["line"], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
