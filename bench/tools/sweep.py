#!/usr/bin/env python3
"""Offered-rate sweep of an open-loop cell, to find the highest rate the
system sustains (the rate a cell then stores as a number).

    python3 bench/tools/sweep.py CELL SECONDS SEED RATE [RATE ...]

Each rate runs the cell once in its own process (a process keeps what it
staged on the chip) and prints one JSON line: the offered rate, the
requests, p95 and whether every answer was correct. Past the knee the
backlog grows through the window and p95 grows with it.
"""
import json
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve()


def one(cell: str, seconds: float, seed: int, rate: float) -> dict:
    sys.path.insert(0, str(HERE.parents[1]))
    import run
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    # the tail is a per-layer metric; read it in this untraced run too
    spec["end_to_end"] = [*spec["end_to_end"], {
        "name": "p95_ms.interactive", "unit": "ms", "workloads": [cell]}]
    out = run.run_cell(spec, cell, seed, seconds, False,
                       traffic_override={"rate_qps": rate})
    return {"rate_qps": rate, "attempted": out["attempted"],
            "failed": out["failed"], "correct": out["correct"],
            "p95_ms": out["metrics"]["p95_ms.interactive"]["value"],
            "notes": out["_notes"]}


def main(argv) -> int:
    if argv[0] == "--one":
        print(json.dumps(one(argv[1], float(argv[2]), int(argv[3]),
                             float(argv[4]))), flush=True)
        return 0
    cell, seconds, seed = argv[0], argv[1], int(argv[2])
    for i, rate in enumerate(argv[3:]):
        t0 = time.monotonic()
        p = subprocess.run([sys.executable, str(HERE), "--one", cell,
                            seconds, str(seed + i), rate],
                           capture_output=True, text=True, timeout=900)
        lines = p.stdout.strip().splitlines()
        row = json.loads(lines[-1]) if p.returncode == 0 and lines else {
            "rate_qps": float(rate), "rc": p.returncode,
            "stderr": p.stderr[-1500:]}
        row["wall_s"] = time.monotonic() - t0
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
