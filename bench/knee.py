"""Knee sweep: the highest rate one configuration's traffic sustains.

    python bench/knee.py --workload <name> --seed <n>

Steps the workload's traffic through the ladder of rates in its traffic
file's ``knee`` entry, each on a fresh set-up (so that every step starts
from the same histories), and prints one JSON line per rate.  A rate
holds when the window's ``suggest_p95_ms`` is under the file's
``limit_p95_ms``, no request failed, and the backlog did not grow: the
generator's lag over the last fifth of the window stays under
``lag_ms``.  The last line names the highest rate that held.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", default=None,
                    help="comma-separated rates instead of the file's")
    args = ap.parse_args(argv)
    import numpy as np
    from bench import harness, traffic
    from bench.run import cell
    c = cell(args.workload)
    harness.require_devices(c["workload"]["chips"])
    drv = harness.load_module(
        ROOT / "bench" / "drivers" / f"{c['config']['kind']}.py")
    harness.enable_cache()
    counter = harness.CompileCounter()
    knee = c["traffic"]["knee"]
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else knee["rates"])
    best = None
    for i, rate in enumerate(rates):
        t = time.monotonic()
        run = drv.Cell(c, args.seed + i)
        try:
            run.setup()
            w = run.drive(knee["seconds"], args.seed + i, counter,
                          rate=rate)
        finally:
            run.cleanup()
        win = [r for r in w["gen"]["suggests"] if r["phase"] == "window"]
        win.sort(key=lambda r: r["due"])
        tail = win[int(0.8 * len(win)):]
        lag_tail = 1e3 * max((r["sent"] - r["due"] for r in tail),
                             default=0.0)
        p95 = 1e3 * traffic.quantile(w["lat"], 0.95)
        holds = (p95 <= knee["limit_p95_ms"] and w["failed"] == 0
                 and lag_tail <= knee["lag_ms"])
        print(json.dumps({
            "rate": rate, "p50_ms": 1e3 * float(np.median(w["lat"])),
            "p95_ms": p95, "p99_ms": 1e3 * traffic.quantile(w["lat"], 0.99),
            "failed": w["failed"], "requests": len(w["lat"]),
            "lag_tail_max_ms": lag_tail, "holds": holds,
            "hits": w["stats1"]["hits"] - w["stats0"]["hits"],
            "misses": w["stats1"]["misses"] - w["stats0"]["misses"],
            "compiles": w["compile"]["compiles"],
            "step_s": time.monotonic() - t}), flush=True)
        if holds:
            best = rate
        else:
            break
    print(json.dumps({"workload": args.workload, "knee_rate": best}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
