#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: one process runs a cell on
several seeds, each a whole run of ``run.py`` (same weights, traffic,
window and comparison), and reads beside the program's ``max_gap`` the
float8 control's gap at the same positions (``check.py``). One JSON line
per seed. The benchmark's own runs never run the control.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 101 102 103 ...
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    for seed in args.seeds:
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0,
                                rehearsal=args.rehearsal, keep_trace=None,
                                trace_seconds=run.TRACE_SECONDS)
        result, lines = run.run(ns, control=True, since=time.perf_counter())
        for line in lines:
            print(line, file=sys.stderr)
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "max_gap": result["checks"]["max_gap"]["value"],
            "control_gap": result["control_gap"],
            "control_correct": result["control_correct"],
            "tokens": result["checks"]["tokens_compared"]["value"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "peak": result["device"]["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
