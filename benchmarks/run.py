"""Benchmark harness: one module per paper table/figure (+ roofline).

  bench_throughput  — Fig. 8/9 (total processed, throughput trendline+R^2)
  bench_failure     — Fig. 10 (failure sweep p in {0,30,60,90}%)
  bench_completion  — Fig. 11 / Eq. (1)-(2) (+ beyond-paper fix)
  bench_scheduler   — beyond-paper scheduler x capacity sweep
  bench_serving     — elastic serving: admission-policy tails + occupancy
  decode (bench_serving.run_decode) — tokens/tick at saturation across
                      the batching grid (per-request vs continuous+paged)
  bench_training    — elastic training: tokens/sec across DP + recovery
  bench_dataflow    — multi-stage chains: 1 vs 3 stages, mid-chain kill,
                      and the backpressure-throttle lag experiment
  bench_controlplane — scalar vs vectorized dispatch/forward hot loops
                      (checksums bit-identical; speedup is the claim)
  bench_multitenant — multi-tenant fleet A/B: cost-weighted packing +
                      cross-pool preemption vs static partitioning
  bench_kernels     — kernel tiling numbers + CPU reference timings
  bench_roofline    — the 40-cell dry-run roofline table

Usage: PYTHONPATH=src python -m benchmarks.run [--only NAME] [--json OUT]
Prints one CSV-ish line per result row: ``table,key=value,...``.

Whenever the serving, training, dataflow, or failure bench runs, its rows
are also frozen to ``BENCH_<name>.json`` at the repo root — the perf
baselines future PRs regress against (CI smoke-diffs the deterministic
counters).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fmt(row: dict) -> str:
    table = row.get("table", "?")
    rest = ",".join(f"{k}={v}" for k, v in row.items() if k != "table")
    return f"{table},{rest}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single bench (throughput|failure|completion|"
                         "scheduler|serving|training|dataflow|controlplane|"
                         "fleet|multitenant|kernels|roofline)")
    ap.add_argument("--json", default=None, help="also dump rows as JSONL")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (  # deferred: jax import cost
        bench_completion,
        bench_controlplane,
        bench_dataflow,
        bench_failure,
        bench_fleet,
        bench_multitenant,
        bench_kernels,
        bench_roofline,
        bench_scheduler,
        bench_serving,
        bench_throughput,
        bench_training,
    )

    benches = {
        "throughput": bench_throughput.run,
        "failure": bench_failure.run,
        "completion": bench_completion.run,
        "scheduler": bench_scheduler.run,
        "serving": bench_serving.run,
        "decode": bench_serving.run_decode,
        "training": bench_training.run,
        "dataflow": bench_dataflow.run,
        "controlplane": bench_controlplane.run,
        "fleet": bench_fleet.run,
        "multitenant": bench_multitenant.run,
        "kernels": bench_kernels.run,
        "roofline": bench_roofline.run,
    }
    if args.only:
        benches = {args.only: benches[args.only]}

    from repro.telemetry.profile import StepTimer

    timer = StepTimer()
    all_rows = []
    for name, fn in benches.items():
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        with timer.time(name):
            rows = fn()
        for row in rows:
            print(_fmt(row), flush=True)
        all_rows.extend(rows)
        elapsed = time.time() - t0
        print(f"# {name} done in {elapsed:.1f}s", flush=True)
        if name in ("serving", "decode", "training", "dataflow", "failure",
                    "controlplane", "fleet", "multitenant"):
            out = os.path.join(_REPO_ROOT, f"BENCH_{name}.json")
            with open(out, "w") as fh:
                json.dump({"bench": name, "wall_s": round(elapsed, 1),
                           "rows": rows}, fh, indent=1)
            print(f"# {name} baseline written to {out}", flush=True)

    # Where the wall-clock went, one line per bench (StepTimer profile).
    print("# --- profile ---", flush=True)
    for name, stats in timer.snapshot().items():
        print(
            f"# profile,{name},total_s={stats['total_s']:.1f},"
            f"calls={stats['calls']}",
            flush=True,
        )

    if args.json:
        with open(args.json, "w") as fh:
            for row in all_rows:
                fh.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
