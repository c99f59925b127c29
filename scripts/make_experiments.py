"""Generate EXPERIMENTS.md from dry-run / perf artifacts.

Static narrative + tables rendered from:
  results/dryrun/        paper-faithful baseline (all 80 cells)
  results/dryrun_final/  beyond-paper optimized (all 80 cells)
  results/perf/iter*/    the hillclimb iteration artifacts
"""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
BASE = ROOT / "results" / "dryrun"
FINAL = ROOT / "results" / "dryrun_final"


def load(d, mesh):
    out = {}
    for p in sorted(d.glob(f"*__{mesh}.json")):
        r = json.loads(p.read_text())
        out[(r["arch"], r["shape"])] = r
    return out


def fmt_row(r, opt=None):
    if r.get("skipped"):
        return None
    if not r.get("ok"):
        return f"| {r['arch']} | {r['shape']} | FAIL | | | | | | |"
    rl = r["roofline"]
    cells = [r["arch"], r["shape"], rl["dominant"],
             f"{rl['t_compute_s']:.3f}", f"{rl['t_memory_s']:.3f}",
             f"{rl['t_collective_s']:.3f}",
             f"{rl.get('useful_ratio', 0):.2f}",
             f"{rl.get('roofline_fraction', 0):.4f}"]
    if opt is not None and opt.get("ok") and not opt.get("skipped"):
        cells.append(f"{opt['roofline'].get('roofline_fraction', 0):.4f}")
    elif opt is not None:
        cells.append("")
    return "| " + " | ".join(cells) + " |"


def roofline_table(mesh, with_final=True):
    base = load(BASE, mesh)
    final = load(FINAL, mesh) if with_final else {}
    hdr = ("| arch | shape | dominant | t_comp (s) | t_mem (s) | t_coll (s) "
           "| useful | frac (base) |" + (" frac (opt) |" if with_final else ""))
    sep = "|" + "---|" * (9 if with_final else 8)
    lines = [hdr, sep]
    skips = []
    for key in sorted(base):
        r = base[key]
        if r.get("skipped"):
            skips.append(key)
            continue
        row = fmt_row(r, final.get(key) if with_final else None)
        if row:
            lines.append(row)
    return "\n".join(lines), skips


def dryrun_summary(mesh):
    base = load(FINAL if FINAL.exists() else BASE, mesh)
    n_ok = sum(1 for r in base.values() if r.get("ok") and not r.get("skipped"))
    n_skip = sum(1 for r in base.values() if r.get("skipped"))
    n_fail = sum(1 for r in base.values() if not r.get("ok"))
    rows = ["| arch | shape | compile (s) | params | args/device (GiB) | "
            "HLO GFLOPs/chip | ICI GB/chip | collective ops |",
            "|---|---|---|---|---|---|---|---|"]
    for key in sorted(base):
        r = base[key]
        if r.get("skipped") or not r.get("ok"):
            continue
        coll = r["collectives"]["counts"]
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r.get('compile_s', 0):.0f} | "
            f"{r['params'] / 1e9:.2f}B | "
            f"{r.get('arg_bytes_per_device', 0) / 2**30:.2f} | "
            f"{r['roofline']['hlo_flops_per_chip'] / 1e9:.0f} | "
            f"{r['roofline']['ici_bytes_per_chip'] / 1e9:.1f} | "
            f"{sum(int(v) for v in coll.values())} |")
    return n_ok, n_skip, n_fail, "\n".join(rows)


def perf_cell(path):
    r = json.loads(path.read_text())
    return r["roofline"]


def perf_table(arch):
    rows = [
        "| iteration | t_comp | t_mem | t_coll | dominant | frac |",
        "|---|---|---|---|---|---|"]
    stages = [("baseline", BASE / f"{arch}__train_4k__16x16.json")]
    for it in ("iter1", "iter2", "iter2b", "iter3", "iter3b"):
        p = ROOT / "results" / "perf" / it / f"{arch}__train_4k__16x16.json"
        if p.exists():
            stages.append((it, p))
    fp = FINAL / f"{arch}__train_4k__16x16.json"
    if fp.exists():
        stages.append(("final", fp))
    for name, p in stages:
        rl = perf_cell(p)
        rows.append(f"| {name} | {rl['t_compute_s']:.3f} | "
                    f"{rl['t_memory_s']:.3f} | {rl['t_collective_s']:.3f} | "
                    f"{rl['dominant']} | "
                    f"{rl.get('roofline_fraction', 0):.4f} |")
    return "\n".join(rows)


def main():
    pod_table, skips = roofline_table("16x16")
    mp_table, _ = roofline_table("2x16x16")
    n_ok, n_skip, n_fail, dsum = dryrun_summary("16x16")
    n_ok2, n_skip2, n_fail2, _ = dryrun_summary("2x16x16")

    text = TEMPLATE.format(
        pod_table=pod_table, mp_table=mp_table, dsum=dsum,
        n_ok=n_ok, n_skip=n_skip, n_fail=n_fail,
        n_ok2=n_ok2, n_skip2=n_skip2, n_fail2=n_fail2,
        skips=", ".join(f"{a}×{s}" for a, s in skips),
        perf_moe=perf_table("granite-moe-3b-a800m"),
        perf_ds=perf_table("deepseek-v2-lite-16b"),
        perf_phi=perf_table("phi3-medium-14b"))
    (ROOT / "EXPERIMENTS.md").write_text(text)
    print("wrote EXPERIMENTS.md")


TEMPLATE = """# EXPERIMENTS — Orchestrate-JAX

All artifacts are reproducible:
- dry-run cells: `PYTHONPATH=src python -m repro.launch.dryrun --all` (JSON per
  cell under `results/dryrun*/`, gzipped compiled HLO under `results/*hlo*/`)
- benchmark (on the chip): `python3 bench/run.py` (cells in BENCHMARK.json)
- tests: `PYTHONPATH=src pytest tests/`

Hardware model (TPU v5e target; this container is CPU-only so the dry-run
numbers are derived from the compiled artifact, not wall clock): 197 TFLOP/s
bf16/chip, 819 GB/s HBM/chip, 50 GB/s/link ICI.

## §Dry-run

Every (architecture × input-shape × mesh) cell is lowered AND compiled with
`jax.jit(step, in_shardings=…).lower(...).compile()` against 512 placeholder
host devices; `memory_analysis()` / `cost_analysis()` are captured in the
JSON artifacts together with a trip-count-aware HLO analysis
(`repro.distributed.hlo` — XLA's own `cost_analysis()` counts `while` bodies
once, which under-reports every scanned model; verified and documented in
`tests/test_hlo.py`).

Results:
- single-pod 16×16 (256 chips): **{n_ok} cells compile OK, {n_skip}
  documented skips, {n_fail} failures**
- multi-pod 2×16×16 (512 chips, `pod` axis): **{n_ok2} OK, {n_skip2} skips,
  {n_fail2} failures** — the pod axis shards (data-parallel across pods with
  sequence-parallel fallback inside each pod when batch < chips).

Documented skips (`long_500k` on pure full-attention archs, per DESIGN.md):
{skips}.

`args/device` below is the exact per-device bytes of the sharded inputs
(params + optimizer state for train; params + KV cache for decode), computed
from the shardings — every cell fits the 16 GiB HBM of a v5e chip.

{dsum}

## §Roofline

Per-chip terms from the compiled artifact (single-pod mesh):
`t_comp = HLO_FLOPs / 197e12`, `t_mem = HLO_bytes / 819e9`,
`t_coll = ring-model ICI bytes / 50e9` (collective bytes parsed per op from
the compiled HLO with replica-group sizes and loop trip multipliers —
`reduce-scatter` charged `in_bytes·(n-1)/n`, `all-reduce` `2·bytes·(n-1)/n`,
etc.).  `useful` = MODEL_FLOPS / HLO_FLOPs where MODEL_FLOPS = 6·N·D (train)
or 2·N·D (prefill/decode), N_active for MoE — it exposes remat recompute
(full-remat trains sit near 0.7 ≈ 3/4.2 passes) and any padding/replication
waste.  `frac` = (MODEL_FLOPS/peak) / max(t_comp, t_mem, t_coll) — the score
we hillclimb.  `frac (base)` is the paper-faithful baseline, `frac (opt)` the
beyond-paper optimized build (same table regenerated after §Perf).

### Single-pod (16×16, 256 chips)

{pod_table}

### Multi-pod (2×16×16, 512 chips)

{mp_table}

Reading the table:
- **train_4k** cells are the meaningful MFU story (the paper's workload is
  parallel *training* trials).  Dense 8-14B archs reach frac 0.21–0.39
  baseline; the gap to 1.0 decomposes into remat recompute (×1.33), the
  memory term (activation + f32-backward traffic — see §Perf iteration 3),
  and FSDP parameter gathers.
- **decode** cells are latency cells: model FLOPs per step are tiny, so frac
  ≈ 0 by construction; the deliverable there is that the KV cache shards
  (batch × sequence) and the per-step collectives are small (see ICI column).
- **whisper / xlstm** are small models on 256 chips — communication floors
  dominate (they would be served/trained on sub-slices in production, which
  the HPO layer's slice allocator does).
- the sLSTM recurrence (xlstm train) performs a per-timestep gradient
  all-reduce for its recurrent weights — a real architectural cost of
  batch-sharded BPTT; the fix (per-device grad accumulation inside a
  shard_map, one psum at exit) is noted as future work in DESIGN.md.

## §Perf — hypothesis → change → measure log

Method: every change is driven by ranking the compiled HLO's instructions by
charged bytes / ICI traffic (`scripts/hlo_top.py`).  The three hillclimbed
cells (chosen per the assignment: worst roofline fraction, most
collective-bound, most representative dense-training workload):

### Cell 1: granite-moe-3b-a800m × train_4k (worst frac: 0.001)

{perf_moe}

- **Iteration 1 — MoE dispatch anchoring.** *Hypothesis*: the top-2 HLO
  collectives (57% of 4.1 TB/chip ICI) are a batch-REPLICATED `(E,B,C,d)`
  f32 dispatch buffer — XLA's scatter partitioner gives up on the vmapped
  scatter and replicates; anchoring scatter operands/results to the batch
  sharding removes it.  Predicted t_coll 82→<2 s.  *Result*: 81.97→1.26 s
  (65×) and t_mem 18.6→2.8 s.  **Confirmed** (`models/moe.py` anchors).
- **Iteration 3b — bf16 probability chain** (shared with cell 3):
  t_mem 2.90→2.67 s.  Confirmed (small).
- **Iteration 4 — sequence-local routing.** *Hypothesis*: under meshes that
  shard the sequence axis (multi-pod train, all prefills), the per-sequence
  routing cumsum crosses shards; gathering S once at MoE entry (one reshard
  in/out) removes it.  *Result*: prefill_32k frac 0.0045→0.006 (16×16) and
  0.0044→0.006 (2×16×16); multi-pod train only 13.9→12.3 s t_coll —
  **partially confirmed**: the multi-pod train residual (58.9% of ICI) is
  the f32 expert-gradient all-reduce over the 32-way batch replicas (the
  same backend artifact as iterations 2/2b, magnified by the replication
  degree — see the HLO breakdown in scripts/hlo_top.py output).
- Residual bound (single-pod): memory (dispatch buffers + expert weight
  reads — real MoE traffic).  frac 0.001 → **0.041** (41×).

### Cell 2: deepseek-v2-lite-16b × train_4k (most collective-bound: 76 s)

{perf_ds}

- **Iteration 1** (same anchoring): t_coll 76.2→5.25 s (14.5×), t_mem
  17.95→3.14 s.  **Confirmed.**
- **Iteration 4** (sequence-local routing, shared with cell 1): prefill_32k
  frac 0.0075→0.016 (2.1×).
- Residual t_coll ≈ 47% per-layer f32 gradient reductions + bf16 expert
  weight FSDP gathers.  Iterations 2/2b below attacked the former and were
  refuted on this backend; true expert parallelism (shard_map + all_to_all
  token routing) is the next lever and is left documented.
- frac 0.004 → **0.063** (16×).

### Cell 3: phi3-medium-14b × train_4k (representative dense train)

{perf_phi}

- **Iteration 2 — bf16 gradient reduction.** *Hypothesis*: 47% of ICI is a
  per-layer f32 all-reduce tuple of weight gradients; differentiating w.r.t.
  the bf16-cast params moves the reduction to bf16 (2×).  *Result*: compiled
  HLO byte-identical — XLA re-converts to f32 before reducing (the consumer
  is f32 Adam).  **Refuted** on XLA:CPU.
- **Iteration 2b — gradient sharding constraints** (reduce-scatter instead
  of all-reduce): also byte-identical — the all-reduce→reduce-scatter
  rewrite does not fire inside `while` bodies on this backend (it does on
  the TPU pipeline; we claim nothing and record the negative result).
- **Iteration 3 — bf16 scores (first attempt).** *Hypothesis*: f32 score
  tensors ≈50% of the 5.4 TB/chip memory traffic; storing scores bf16 halves
  it.  *Result*: t_mem 6.18→6.39 s — **refuted**: the `astype(f32)` inside
  the exp chain forced f32 residuals into the backward.
- **Iteration 3b — full-bf16 probability chain** (max-subtracted exp kept
  entirely in compute dtype, f32 only for the normalizer accumulation):
  t_mem 6.39→5.92 s, decode-consistency tests unchanged.  **Confirmed**
  (the remaining f32 traffic is backward matmul partials and partitioner
  reshard chains; the structural fix is the Pallas flash-attention kernel
  (`kernels/flash_attention.py`), which never materializes scores — it is
  validated in interpret mode but cannot be compiled into the CPU dry-run,
  so no number is claimed for it here).
- Earlier global fixes recorded for completeness (applied before the
  baseline sweep, visible in all tables): bf16 pre-cast of parameters
  outside the layer scan (halves FSDP gather traffic vs naive f32 gathers),
  small-leaf replication (min 1M elements — kills per-timestep gathers of
  recurrent weights), activation/scan-carry sharding anchors (kills
  "involuntary full rematerialization" reshard storms).
- frac 0.279 → **0.309**.

### Stopping criterion

Three consecutive <5% iterations on the dominant term of cell 3 (2, 2b, 3)
against a structural backend limitation; cells 1–2 improved 41×/16× and
their residual is real MoE data movement.  Remaining headroom documented:
expert parallelism via shard_map all_to_all (deepseek), Pallas flash
attention on real TPUs (dense archs), shard_map BPTT gradient accumulation
(xlstm).

## §Paper claims (Orchestrate itself)

The paper's own quantitative surface is §4: 300 evaluations at 15-way
parallelism on a 3-conv/2-fc CNN, plus the workflow (six CLI verbs, status/
logs UX, failed-observation accounting).  Reproduced:

- `examples/hpo_cnn.py --paper` runs 300 evals / 15 parallel of the same
  CNN shape (synthetic stand-in for GTSRB; offline container).
- Straggler speculation (3× median detection, first finisher wins, loser
  cancelled) is asserted in
  `tests/test_scheduler.py::test_straggler_speculation_wins` (beats a 4 s
  straggler tail by >2 s).
- Fig. 4 UX (status screen, aggregated `logs --follow`, failed-observation
  counts) is reproduced by the CLI (`tests/test_store_cli.py` asserts the
  full lifecycle, including cluster-destroy ≠ experiment-delete).
- The beyond-paper vmap population executor trains 8 trials in one
  program; equivalence to sequential training is exact
  (`tests/test_population.py`, diff < 1e-5).
"""


if __name__ == "__main__":
    sys.exit(main())
