#!/usr/bin/env python3
"""The knee sweep of an open-loop cell: one process runs the cell at each
rate in turn, each a whole run of ``run.py`` (same weights, traffic,
window and comparison) with only the mix's ``rate_per_s`` replaced, and
prints one JSON line per rate. The knee is the highest rate at which the
time to first token stays flat instead of growing through the window;
the mix's file then takes about 0.8 of it. The benchmark's own runs never
sweep.

    python3 benchmarks/chip/sweep.py --workload <cell> --seconds <s> \\
        --seed <n> --rates 6 8 10 12
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    for rate in args.rates:
        ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                                seconds=args.seconds, trace=0,
                                rehearsal=args.rehearsal, keep_trace=None,
                                trace_seconds=run.TRACE_SECONDS, rate=rate)
        result, lines = run.run(ns, since=time.perf_counter())
        for line in lines:
            print(line, file=sys.stderr)
        print(json.dumps({
            "rate": rate, "correct": result["correct"],
            "attempted": result["attempted"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "checks": {k: v["value"] for k, v in result["checks"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
