#!/usr/bin/env python3
"""Spread of each metric over sets of runs, the way bounds are set.

    python3 bench/tools/spread.py RUNS.jsonl [RUNS.jsonl ...]

Reads ``series.py`` output. Runs are grouped by cell and by set (the
n-th run of a seed belongs to set n). For each metric: the median, the
spread (third minus first quartile from ``statistics.quantiles(n=4)``,
over the median) of each set, and five times the wider spread.
"""
import json
import statistics
import sys
from collections import defaultdict


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths) -> int:
    runs = [json.loads(line) for p in paths for line in open(p)]
    sets = defaultdict(lambda: defaultdict(lambda: defaultdict(list)))
    seen = defaultdict(int)
    for r in runs:
        if r["trace"] or not r.get("result"):
            continue
        key = (r["cell"], r["seed"])
        n = seen[key]
        seen[key] += 1
        for m, v in r["result"]["metrics"].items():
            sets[r["cell"]][m][n].append(v["value"])
    for cell, metrics in sets.items():
        for m, by_set in metrics.items():
            parts = []
            worst = 0.0
            for n, vals in sorted(by_set.items()):
                if len(vals) < 2:
                    continue
                s = spread(vals)
                worst = max(worst, s)
                parts.append(f"set{n}: n={len(vals)} median="
                             f"{statistics.median(vals):.6g} spread={s:.4f}")
            print(f"{cell} {m}: " + "; ".join(parts)
                  + f"; 5x widest={5 * worst:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
