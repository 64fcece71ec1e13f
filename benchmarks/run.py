"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only name] [--quick]

Prints ``name,us_per_call,derived`` CSV rows (also written to
results/benchmarks.csv). Paper analogues:
    construction -> Table 2       query  -> Table 3 (times)
    fpr          -> Table 3 (FPR) scaling -> Fig. 6/7
    compaction   -> Fig. 4        kernel -> engineering section 2.3 (SIMD)
    hedging      -> DESIGN.md straggler mitigation
Roofline terms (deliverable g) come from the dry-run artifacts:
    PYTHONPATH=src python -m repro.launch.dryrun --out results/dryrun.jsonl
    PYTHONPATH=src python -m benchmarks.roofline
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="smaller corpora (CI)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import common
    from . import (bulk, compaction, compression, construction, fpr,
                   hedging, kernel_micro, outofcore, pruning, query,
                   scaling, serving)

    n = 128 if args.quick else 512
    suites = {
        "construction": lambda: construction.run(n),
        "query": lambda: query.run(n),
        "fpr": lambda: fpr.run(n, n_probes=100 if args.quick else 300),
        "scaling": lambda: scaling.run((64, 128) if args.quick
                                       else (64, 128, 256, 512)),
        "compaction": lambda: compaction.run(64 if args.quick else 256),
        "kernel": lambda: kernel_micro.run(quick=args.quick),
        "hedging": hedging.run,
        "serving": lambda: serving.run(64 if args.quick else 256,
                                       n_queries=48 if args.quick else 96),
        "serving_multihost": lambda: serving.run_multihost(
            96 if args.quick else 256,
            n_queries=24 if args.quick else 64,
            max_hosts=2 if args.quick else 3),
        "outofcore": lambda: outofcore.run(64 if args.quick else 256,
                                           n_queries=8 if args.quick else 16),
        "compression": lambda: compression.run(
            16 if args.quick else 24,
            n_queries=12 if args.quick else 24,
            reps_levels=(1, 4) if args.quick else (1, 4, 8)),
        "pruning": lambda: pruning.run(
            96 if args.quick else 128,
            n_queries=6 if args.quick else 8,
            thresholds=(0.5, 0.8, 1.0) if args.quick
            else (0.3, 0.5, 0.8, 0.9, 1.0),
            selectivities=(0.0, 0.25) if args.quick else (0.0, 0.05, 0.25),
            chunks=(16,) if args.quick else (16, 32)),
        "bulk": lambda: bulk.run(
            96 if args.quick else 160,
            n_queries=64 if args.quick else 256,
            codecs=("raw",) if args.quick else ("raw", "rowdict"),
            max_batch=8 if args.quick else 32,
            p99_queries=24 if args.quick else 48),
    }
    print("name,us_per_call,derived")
    kernel_report = None
    compression_report = None
    pruning_report = None
    bulk_report = None
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        res = fn()
        if name == "kernel":
            kernel_report = res
        elif name == "compression":
            compression_report = res
        elif name == "pruning":
            pruning_report = res
        elif name == "bulk":
            bulk_report = res

    out = Path("results")
    out.mkdir(exist_ok=True)
    with (out / "benchmarks.csv").open("w") as f:
        f.write("name,us_per_call,derived\n")
        for row in common.ROWS:
            f.write(f"{row[0]},{row[1]:.1f},{row[2]}\n")
    print(f"# wrote results/benchmarks.csv ({len(common.ROWS)} rows)",
          file=sys.stderr)
    if kernel_report is not None:
        import json
        kernel_json = out / "BENCH_kernels.json"
        kernel_json.write_text(json.dumps(kernel_report, indent=2))
        print(f"# wrote {kernel_json} (overlap sweep + DMA accounting)",
              file=sys.stderr)
    if compression_report is not None:
        import json
        comp_json = out / "BENCH_compression.json"
        comp_json.write_text(json.dumps(compression_report, indent=2))
        print(f"# wrote {comp_json} (ratio x decode x e2e sweep)",
              file=sys.stderr)
    if pruning_report is not None:
        import json
        prune_json = out / "BENCH_pruning.json"
        prune_json.write_text(json.dumps(pruning_report, indent=2))
        print(f"# wrote {prune_json} (threshold x selectivity x chunk sweep)",
              file=sys.stderr)
    if bulk_report is not None:
        import json
        bulk_json = out / "BENCH_bulk.json"
        bulk_json.write_text(json.dumps(bulk_report, indent=2))
        print(f"# wrote {bulk_json} (staged-bytes amortization + p99 "
              f"protection)", file=sys.stderr)


if __name__ == "__main__":
    main()
