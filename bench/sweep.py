#!/usr/bin/env python3
"""Finds a serving cell's knee once: the same mix at rising rates, one
window each, in one process on the chip.

  python bench/sweep.py --workload <name> --rates 1,2,3 [--seconds 20]

Prints one JSON line per rate: the end-to-end metrics, requests due and
finished, and the queue left when the window closed.  The rate in the
mix's file is written by hand from this output (about four fifths of
the highest rate whose queue stays flat); the benchmark's runs never
search for one.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
for p in (BENCH, BENCH / "drivers", BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    import serve as D
    import traffic

    c = common.cell(args.workload)
    common.devices(c["workload"]["chips"])
    common.enable_compile_cache()
    cell = D.ServeCell(c, args.seed, origin=T_START)
    for rate in [float(x) for x in args.rates.split(",")]:
        reqs = traffic.requests(c["traffic"], args.seed, args.seconds, cell.s["vocab"],
                                rate=rate)
        w = cell.window(reqs, args.seconds)
        backlog = cell.pool.queue_depth()
        out = dict(D.end_to_end(w), rate=rate, attempted=w["attempted"],
                   finished=w["finished"], backlog=backlog,
                   offered_tokens_per_s=sum(r.max_new for r in reqs) / args.seconds,
                   ttft_p50_ms=statistics.median(w["ttft_s"]) * 1e3,
                   queue_wait_p50_ms=statistics.median(w["queue_wait_s"]) * 1e3
                   if w["queue_wait_s"] else None,
                   slots_mean=w["decode_rows"] / max(w["decode_steps"], 1))
        print(json.dumps(out), flush=True)
        while cell.pool.queue_depth() or cell.pool.occupancy():
            cell.pool.step(cell.now())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
