#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

  python bench/control.py --workload <name> --seeds 1,2,... \
      [--control-seeds 1,2,3] [--seconds 15]

For every seed it builds the cell, runs a window at the cell's own load
(serving) or the set-up's first steps (training), and prints the numbers
that the cell compares, as the program gives them.  For the control
seeds it also prints the control's numbers: the float32 reference put in
the program's place with its matrix products in float8, and for
training the fault of half the batch left out (the mean taken over the
rest), planted in the reference.  One JSON line per seed; the benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
for p in (BENCH, BENCH / "drivers", BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import common  # noqa: E402


def serve_seed(c, seed, seconds, control):
    import serve as D
    import traffic

    cell = D.ServeCell(c, seed, origin=T_START)
    w = cell.window(traffic.requests(c["traffic"], seed, seconds, cell.s["vocab"]),
                    seconds)
    cell.free()
    picked = D.sample(w, c["traffic"], seed)
    out = {"program": D.check(w, picked, c, seed)["served_logit_gap"]["value"],
           "finished": w["finished"], "attempted": w["attempted"],
           "sampled": len(picked), "sampled_tokens": sum(len(tr.output) for tr in picked),
           "slots_mean": w["decode_rows"] / max(w["decode_steps"], 1),
           "output_tokens_per_s": w["tokens"] / w["window_s"]}
    if control:
        out["control"] = D.check(w, picked, c, seed, control=True)["served_logit_gap"]["value"]
    return out


def train_seed(c, seed, control):
    import train as D

    cell = D.TrainCell(c, seed, seconds=1.0)
    cell.free()
    w = {"nonfinite": 0}
    _, prog, ref = D.check(cell, w, c)
    out = {"program": prog}
    if control:
        import reference

        batches = cell.batches(D.CHECK_STEPS)
        low = reference.train_steps(seed, cell.s, c["config"]["training"], batches, lowp=True)
        half = reference.train_steps(seed, cell.s, c["config"]["training"], batches, half=True)
        out["control"] = D.gaps(low, ref)
        out["fault_half_batch"] = D.gaps(half, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    c = common.cell(args.workload)
    common.devices(c["workload"]["chips"])
    common.enable_compile_cache()
    ctl = {int(x) for x in args.control_seeds.split(",") if x}
    for seed in [int(x) for x in args.seeds.split(",")]:
        t = time.perf_counter()
        if c["config"]["kind"] == "serve":
            out = serve_seed(c, seed, args.seconds, seed in ctl)
        else:
            out = train_seed(c, seed, seed in ctl)
        out.update(seed=seed, seconds=time.perf_counter() - t)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
