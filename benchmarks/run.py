"""Benchmark harness — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run              # full suite
    PYTHONPATH=src python -m benchmarks.run --quick      # reduced steps
    PYTHONPATH=src python -m benchmarks.run --only table7 kernels

Benchmarks:
    fig1      clustering structure (Figure 1)
    llava     Tables 1–2 (LLaVA parity + router-stress)
    internvl  Tables 4–6 (InternVL parity, hallucination-proxy, routing)
    table7    number of experts K ∈ {2,4,6}
    table8    vision-encoder capacity
    table9    clustering algorithm (1-stage vs 2-stage)
    kernels   Pallas kernel microbenches (CSV: name,us_per_call,derived)
    serve     looped vs stacked mixture decode steps/sec (K=4)
    roofline  aggregate the dry-run roofline artifacts
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced steps (CI-sized)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--out", default="experiments/bench_results.json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from .common import BenchSettings
    s = BenchSettings(steps=60 if args.quick else 240,
                      eval_batches=4 if args.quick else 8,
                      samples=1024 if args.quick else 2048)

    from . import (fig1_clustering, kernels_bench, roofline_report,
                   serve_bench, table7_num_experts, table8_vision_encoder,
                   table9_clustering, tables_internvl, tables_llava,
                   topk_ablation)
    suite = {
        "fig1": lambda: fig1_clustering.run(s),
        "llava": lambda: tables_llava.run(s),
        "internvl": lambda: tables_internvl.run(s),
        "table7": lambda: table7_num_experts.run(s),
        "table8": lambda: table8_vision_encoder.run(s),
        "table9": lambda: table9_clustering.run(s),
        "topk": lambda: topk_ablation.run(s),
        "kernels": lambda: kernels_bench.run(s),
        "serve": lambda: serve_bench.run(s),
        "serve_paged": lambda: serve_bench.run_paged(s),
        "roofline": lambda: roofline_report.run(s),
    }
    selected = args.only or list(suite)
    results = {}
    for name in selected:
        t0 = time.time()
        print(f"\n########## benchmark: {name} ##########", flush=True)
        try:
            results[name] = {"result": suite[name](),
                             "wall_s": round(time.time() - t0, 1),
                             "status": "ok"}
        except Exception as e:  # keep the suite going; report at the end
            import traceback
            traceback.print_exc()
            results[name] = {"status": "error", "error": str(e)}
        print(f"[{name}] {results[name]['status']} "
              f"in {time.time()-t0:.1f}s", flush=True)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=str)
    bad = [n for n, r in results.items() if r["status"] != "ok"]
    print(f"\nbenchmarks complete → {args.out}; "
          f"{len(selected)-len(bad)}/{len(selected)} ok"
          + (f"; FAILED: {bad}" if bad else ""))
    if bad:
        sys.exit(1)


if __name__ == "__main__":
    main()
