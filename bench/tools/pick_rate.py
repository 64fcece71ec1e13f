#!/usr/bin/env python3
"""Set an open-loop cell's rate to a share of the knee a sweep found.

    python3 bench/tools/pick_rate.py SWEEP.jsonl TRAFFIC.json [SHARE]

The knee is the highest swept rate at which every request was answered
and p95 stayed within three times its value at the lowest rate (past the
knee the backlog grows through the window and p95 grows with it). The
traffic file's ``rate_qps`` becomes SHARE (default 0.8) of the knee,
rounded to a multiple of 10.
"""
import json
import sys


def main(argv) -> int:
    rows = [r for r in map(json.loads, open(argv[0])) if "p95_ms" in r]
    share = float(argv[2]) if len(argv) > 2 else 0.8
    rows.sort(key=lambda r: r["rate_qps"])
    base = rows[0]["p95_ms"]
    knee = rows[0]["rate_qps"]
    for r in rows:
        if r["failed"] == 0 and r["correct"] and r["p95_ms"] <= 3 * base:
            knee = r["rate_qps"]
        else:
            break
    traffic = json.load(open(argv[1]))
    traffic["rate_qps"] = int(round(share * knee / 10.0)) * 10
    with open(argv[1], "w") as f:
        json.dump(traffic, f, indent=2)
        f.write("\n")
    print(json.dumps({"knee_qps": knee, "rate_qps": traffic["rate_qps"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
