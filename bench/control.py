"""The control of the comparison that decides ``correct``, on the chip.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

One set-up of the cell, then per seed one window at the cell's own
load.  For the window's sampled dispatches it prints, on one JSON line
per seed:

* ``program``: the program's numbers, judged against the cell's limits;
* ``control``: ``gpref.ControlHigh`` (the reference in f32 with every
  matmul at three bf16 passes, one precision below what the
  configuration states, jitted on the chip) put in the program's place
  on the same inputs, scored against the float64 reference the same
  way and judged with the run's guarantee counts;
* ``one_pass``: the same with one bf16 pass per matmul
  (``gpref.ControlOnePass``, a TPU's default precision);
* ``faults``: the fit numbers of a fit that returns its start unchanged
  and of one that sees half of each history, planted in the float64
  reference put in the program's place.

A limit lies above the program's readings and below the control's (or,
for a fit number, below the faults').  The benchmark's own runs never
run this.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import check, gpref, harness
    from bench.run import cell
    c = cell(args.workload)
    harness.require_devices(c["workload"]["chips"])
    drv = harness.load_module(
        ROOT / "bench" / "drivers" / f"{c['config']['kind']}.py")
    harness.enable_cache()
    counter = harness.CompileCounter()
    limits = check.limits(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {"control": gpref.ControlHigh(),
                "one_pass": gpref.ControlOnePass()}
    run = drv.Cell(c, seeds[0])
    try:
        run.setup()
        for seed in seeds:
            w = run.drive(args.seconds, seed, counter)
            sure = check.guarantees(w["gen"], run.root, run.ids, run.space,
                                    run.cfg["budget"], run.seeded, w["rec"])
            prog = check.gp_numbers(w["rec"], seed)
            compared = prog.pop("_compared")
            got = {"program": prog}
            t = time.monotonic()
            for name, ctl in controls.items():
                got[name] = check.gp_numbers(w["rec"], seed, control=ctl)
                got[name].pop("_compared")
            t_ctl = time.monotonic() - t
            faults = check.fit_fault_numbers(w["rec"], seed)
            out = {"workload": args.workload, "seed": seed,
                   "compared": compared, "control_s": t_ctl}
            for name, nums in got.items():
                ok, rows = check.judge(dict(sure, **nums), limits)
                out[name] = {"correct": ok, "numbers": nums,
                             "over": [r[0] for r in rows
                                      if r[1] is None or r[1] > r[2]]}
            out["faults"] = faults
            print(json.dumps(out, default=float), flush=True)
    finally:
        run.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
