#!/usr/bin/env python3
"""Runs one benchmark cell once and prints its result as the last line.

  python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s workload) names a configuration file
(``bench/configs``), a traffic mix (``bench/traffic``) and the limits of
its check (``bench/limits``); its ``kind`` picks the driver
(``bench/drivers``), and each per-layer metric is read by
``bench/metrics/<metric>.py``.  With ``--trace 0`` the result holds the
cell's end-to-end metrics; with ``--trace 1`` the window is traced and
the result holds its per-layer metrics and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
for p in (BENCH, BENCH / "drivers", BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import common  # noqa: E402
from common import BenchError, log  # noqa: E402

TRACE_DIR = common.ROOT / ".bench_traces"


def passes(checks: dict) -> bool:
    ok = True
    for v in checks.values():
        if v["value"] is None:
            ok = False
        elif v.get("at_least"):
            ok &= v["value"] >= v["limit"]
        else:
            ok &= v["value"] <= v["limit"]
    return ok


def device_peak_bytes(chips: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def read_per_layer(c: dict, ctx: dict) -> dict:
    out = {}
    for m in c["per_layer"]:
        v = common.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(c: dict, seed: int, seconds: float, trace: bool, dev: dict) -> dict:
    import traces as T

    chips = c["workload"]["chips"]
    clock = common.CompileClock()
    kind = c["config"]["kind"]
    tdir = str(TRACE_DIR / c["workload"]["name"])
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
    ctx = {"peaks": common.peaks(dev["kind"]) if dev["platform"] == "tpu" else None,
           "chips": chips}

    if kind == "serve":
        import serve as D
        import traffic

        cell = D.ServeCell(c, seed, origin=T_START)
        reqs = traffic.requests(c["traffic"], seed, seconds, cell.s["vocab"])
        setup_s, compile_s, compiles0 = time.perf_counter() - T_START, clock.seconds, clock.compiles
        with (T.capture(tdir) if trace else contextlib.nullcontext()):
            w = cell.window(reqs, seconds)
        compiles_in_window = clock.compiles - compiles0
        peak = device_peak_bytes(chips)
        cell.free()
        del cell
        log(phase="window", requests=w["attempted"], finished=w["finished"],
            failed=w["failed"], tokens=w["tokens"], window_s=w["window_s"],
            decode_steps=w["decode_steps"], compiles_in_window=compiles_in_window,
            generator_late_p50_ms=statistics.median(w["late_s"]) * 1e3,
            generator_late_max_ms=max(w["late_s"]) * 1e3,
            ttft_p50_ms=statistics.median(w["ttft_s"]) * 1e3,
            itl_p50_ms=statistics.median(w["itl_s"]) * 1e3)
        picked = D.sample(w, c["traffic"], seed)
        checks = D.check(w, picked, c, seed)
        e2e = D.end_to_end(w)
        ctx["counters"] = D.counters(w, c)
        attempted, failed = w["attempted"], w["failed"]
    elif kind == "train":
        import train as D

        cell = D.TrainCell(c, seed, seconds)
        setup_s, compile_s, compiles0 = time.perf_counter() - T_START, clock.seconds, clock.compiles
        with (T.capture(tdir) if trace else contextlib.nullcontext()):
            w = cell.window(seconds)
        compiles_in_window = clock.compiles - compiles0
        peak = device_peak_bytes(chips)
        cell.free()
        log(phase="window", steps=w["steps"], tokens=w["tokens"],
            window_s=w["window_s"], compiles_in_window=compiles_in_window,
            first_losses=cell.readings["losses"])
        checks, gaps, _ = D.check(cell, w, c)
        log(phase="check", **gaps)
        e2e = {"train_tokens_per_s": w["tokens"] / w["window_s"]}
        ctx["counters"] = {"steps": w["steps"], "tokens": w["tokens"],
                           "flops_per_token": cell.arch.train_flops_per_token(cell.s, cell.seq)}
        attempted, failed = w["steps"], w["nonfinite"]
    else:
        raise BenchError(f"no driver for configuration kind {kind!r}")

    ctx.update(compile_s=compile_s, memory_peak_bytes=peak, window_s=w["window_s"],
               end_to_end=e2e)
    e2e["setup_s"] = setup_s
    device = dict(dev, memory_peak_bytes=peak)
    result = {"correct": passes(checks), "attempted": attempted, "failed": failed}
    if trace:
        summary = T.reduce(tdir)
        ctx["trace"] = summary
        result["metrics"] = read_per_layer(c, ctx)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_by_span(10)}
        log(phase="trace", longest_gaps=summary.longest_gaps(10))
    else:
        units = {m["name"]: m["unit"] for m in c["end_to_end"]}
        result["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in e2e.items()
                             if k in units}
        result["device"] = device
    log(phase="end_to_end", **e2e)
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        log(check=k, value=v["value"], limit=v["limit"],
            rule="at least" if v.get("at_least") else "at most")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        c = common.cell(args.workload)
        dev = common.devices(c["workload"]["chips"])
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    common.enable_compile_cache()
    result = run(c, args.seed, args.seconds, bool(args.trace), dev)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
