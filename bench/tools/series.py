#!/usr/bin/env python3
"""Run benchmark cells one after another, each as its own process, and
keep every result line.

    python3 bench/tools/series.py OUT.jsonl CELL:SEED:SECONDS:TRACE ...

Each run is ``bench/run.py`` in a fresh process (this one never touches
JAX, so the child owns the chip). One JSON line per run goes to OUT.jsonl:
the arguments, the exit code, the wall seconds, the result line and the
end of standard error.
"""
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv) -> int:
    out_path = Path(argv[0])
    out_path.parent.mkdir(parents=True, exist_ok=True)
    worst = 0
    for item in argv[1:]:
        cell, seed, seconds, trace = item.split(":")
        cmd = [sys.executable, str(ROOT / "bench" / "run.py"),
               "--workload", cell, "--seed", seed, "--seconds", seconds,
               "--trace", trace]
        t0 = time.monotonic()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=1500)
        wall = time.monotonic() - t0
        lines = p.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except json.JSONDecodeError:
            result = None
        rec = {"cell": cell, "seed": int(seed), "seconds": float(seconds),
               "trace": int(trace), "rc": p.returncode, "wall_s": wall,
               "result": result, "stderr": p.stderr[-8000:]}
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        m = (result or {}).get("metrics", {})
        print(f"{cell} seed={seed} trace={trace} rc={p.returncode} "
              f"wall={wall:.1f}s correct={(result or {}).get('correct')} "
              + " ".join(f"{k}={v['value']:.6g}" for k, v in m.items()),
              flush=True)
        if p.returncode:
            print(p.stderr[-2000:], flush=True)
        worst = max(worst, p.returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
