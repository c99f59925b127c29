"""Benchmark harness.

Two modes:

* ``python -m benchmarks.run`` — legacy CSV: one section per paper
  claim/figure + the roofline readout, ``name,us_per_call,derived`` rows.
* ``python -m benchmarks.run --json [FILE] [--quick]`` — machine-readable
  perf trajectory: runs the suggestion/service/scheduler hot-path benches
  and writes ``BENCH_suggest.json`` (schema below), so speedups and
  regressions are tracked across PRs.  ``--quick`` shrinks history sizes
  and repetitions for CI (the tier-2 perf gate — see scripts/bench_check.py
  and ROADMAP.md).

Row reduction (ISSUE 5): every suggest/service bench collects *per-call
samples*; the gated scalar in ``rows`` is the **min of k** samples for
single-path rows (the true cost of the operation — a CPU-contention
hiccup in one call can no longer inflate a committed row ~2x), the
**mean** for the ``*_cycle`` rows (their point is amortizing the
periodic hyperfit — a min would always pick a refit-free cycle), and
the **p50** for the ``suggest_contended_*`` rows (a contended row's
value IS its median; its min is just a queue hit).  The per-row p50/p90
spread is kept alongside in ``stats`` so bimodality stays visible in
the committed baseline.

JSON schema::

  {"schema": 2, "unit": "us", "created": <epoch>, "quick": bool,
   "rows": {"bench_suggest/gp/h150": 7600.0, ...},
   "stats": {"bench_suggest/gp/h150": {"p50": ..., "p90": ..., "n": 10}}}

Schema 1 (scalar rows only, no ``stats``) is still read by
``scripts/bench_check.py`` baselines.
"""
import argparse
import json
import sys
import time
import traceback

import numpy as np


def _reduce(rows, stats, name, samples, gate="min"):
    """Fold one bench's sample list into the gate scalar + p50/p90."""
    samples = list(samples)
    if gate == "min":
        value = min(samples)
    elif gate == "mean":
        # trimmed: drop the single worst sample (a one-off XLA compile or
        # scheduler hiccup would otherwise dominate a small-k mean) while
        # still averaging the genuine periodic-refit share
        kept = sorted(samples)[:-1] if len(samples) >= 8 else samples
        value = sum(kept) / len(kept)
    elif gate == "p90":
        value = float(np.percentile(samples, 90))
    else:
        value = float(np.percentile(samples, 50))
    rows[name] = round(value, 1)
    stats[name] = {"p50": round(float(np.percentile(samples, 50)), 1),
                   "p90": round(float(np.percentile(samples, 90)), 1),
                   "n": len(samples)}


def collect(quick: bool = False) -> dict:
    """Hot-path rows only (suggest / service / scheduler) — the tracked
    perf surface.  Returns {"rows": {row: us}, "stats": {row: spread}}."""
    from benchmarks import bench_scheduler, bench_suggest_latency
    rows, stats = {}, {}
    hist = (10, 50) if quick else (10, 50, 150)
    names = (("random", "gp") if quick
             else ("random", "sobol", "evolution", "pso", "gp"))
    for name, h, us in bench_suggest_latency.run(history_sizes=hist,
                                                 names=names):
        _reduce(rows, stats, f"bench_suggest/{name}/h{h}", us)
    for name, h, us in bench_suggest_latency.run_batched(history_sizes=hist):
        _reduce(rows, stats, f"bench_suggest/{name}_batch8/h{h}", us)
    for name, h, us in bench_suggest_latency.run_cycle(history_sizes=hist):
        # the cycle row exists to amortize the periodic hyperfit into the
        # steady-state cost — min-of-k would always pick a refit-free
        # cycle and a refit regression could never fail the gate
        _reduce(rows, stats, f"bench_suggest/{name}_cycle/h{h}", us,
                gate="mean")
    for backend, us in bench_suggest_latency.run_service(
            n=20 if quick else 100):
        _reduce(rows, stats, f"bench_service/{backend}", us)
    for backend, us in bench_suggest_latency.run_report(
            n=50 if quick else 200):
        _reduce(rows, stats, f"bench_service/{backend}", us)
    for name, us in bench_suggest_latency.run_contended(
            calls=4 if quick else 8, seed_obs=24 if quick else 40):
        # a contended row is its median by definition (min = queue hit)
        _reduce(rows, stats, f"bench_service/{name}", us, gate="p50")
    for p, us, tps in bench_scheduler.throughput_rows(
            parallels=(8,) if quick else (1, 8, 32),
            budget=20 if quick else 40):
        rows[f"bench_scheduler/throughput/p{p}"] = round(us, 1)
    from benchmarks import bench_fleet
    for suffix, us in bench_fleet.run(calls=8 if quick else 25):
        # an SLO row: the gate is the contended median, not a best case
        _reduce(rows, stats, f"bench_fleet/{suffix}", us, gate="p50")
    for suffix, us in bench_fleet.run_rebalance(calls=15 if quick else 40):
        # tracked-not-gated (scripts/bench_check.py UNGATED_ROWS): the
        # tail during a live shard-add rebalance is the row's point, so
        # commit the p90
        _reduce(rows, stats, f"bench_fleet/{suffix}", us, gate="p90")
    from benchmarks import bench_transport
    for suffix, us in bench_transport.run(quick=quick):
        # chunk-amortized stream rows: the p50 chunk is the steady state
        # (a min chunk would just be one that dodged every flush)
        _reduce(rows, stats, f"bench_transport/{suffix}", us, gate="p50")
    from benchmarks import bench_fit
    for suffix, us in bench_fit.run(quick=quick):
        # serial vs batched cross-experiment hyperfit cost (ISSUE 8):
        # µs per fit, so batched/serial reads as the throughput ratio
        _reduce(rows, stats, f"bench_fit/{suffix}", us)
    from benchmarks import bench_ask
    for suffix, us in bench_ask.run(quick=quick):
        # serial vs batched cross-experiment q-EI ask cost (ISSUE 10):
        # µs per ask, so batched/serial reads as the throughput ratio
        _reduce(rows, stats, f"bench_ask/{suffix}", us)
    return {"rows": rows, "stats": stats}


def write_json(path: str, quick: bool = False) -> dict:
    collected = collect(quick=quick)
    payload = {"schema": 2, "unit": "us", "created": time.time(),
               "quick": quick, "rows": collected["rows"],
               "stats": collected["stats"]}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return payload


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_suggest.json",
                    default=None, metavar="FILE",
                    help="write machine-readable rows to FILE "
                         "(default BENCH_suggest.json) instead of CSV")
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweep for CI perf gating")
    args = ap.parse_args(argv)

    if args.json:
        payload = write_json(args.json, quick=args.quick)
        for name, us in sorted(payload["rows"].items()):
            spread = payload["stats"].get(name)
            tail = (f",p50={spread['p50']:.0f},p90={spread['p90']:.0f}"
                    if spread else "")
            print(f"{name},{us:.0f}{tail}")
        print(f"wrote {len(payload['rows'])} rows to {args.json}",
              file=sys.stderr)
        return

    from benchmarks import (bench_ask, bench_fit, bench_fleet,
                            bench_optimizers, bench_parallel,
                            bench_population, bench_roofline,
                            bench_scheduler, bench_suggest_latency)
    for mod in (bench_parallel, bench_optimizers, bench_suggest_latency,
                bench_fit, bench_ask, bench_scheduler, bench_fleet,
                bench_population, bench_roofline):
        print(f"\n===== {mod.__name__} =====")
        try:
            mod.main()
        except Exception:
            traceback.print_exc()
            print(f"{mod.__name__},FAILED,")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
