#!/usr/bin/env python3
"""The correctness control: the reference with one guarantee broken, put
in the program's place, held to the same comparison a run makes.

    python3 bench/tools/control.py CELL SECONDS SEED [SEED ...]

For each seed it makes the cell's data and queries at full size (the
arena on the device, as a run does), answers every query with the
reference scoring only every other distinct k-mer (an approximate sketch
where the configuration promises exact containment), and prints the
numbers ``run.check_answers`` compares, one JSON line per seed. A sound
comparison reads ``mismatched`` well above its limit of 0 here.
"""
import json
import math
import sys
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run                      # noqa: E402  (sets up the import paths)
import datagen                  # noqa: E402
import loadgen                  # noqa: E402
import reference as ref         # noqa: E402


class ControlAnswer:
    """The control's answer to one query, computed when first read (the
    comparison reads only its sample)."""

    status = SimpleNamespace(name="OK")

    def __init__(self, reference, q):
        self._ref, self._q, self._got = reference, q, None

    @property
    def result(self):
        return self

    def _get(self):
        if self._got is None:
            q = self._q
            self._got = self._ref.expect(q.codes, q.threshold, q.top_k,
                                         keep=ref.every_other_term)
        return self._got

    @property
    def doc_ids(self):
        return self._get()[0]

    @property
    def scores(self):
        return self._get()[1]


def control_run(spec: dict, cell_name: str, seed: int, seconds: float,
                *, traffic_dir=run.BENCH / "traffic") -> dict:
    _, config, traffic = run.cell_spec(spec, cell_name, traffic_dir)
    mix = traffic["queries"]
    counts = datagen.term_counts(config)
    layout, order, _ = datagen.layout_of(counts, config)
    if traffic["loop"] == "open":
        n = max(1, round(float(traffic["rate_qps"]) * seconds))
    else:
        n = math.ceil(float(traffic["pool_qps"]) * seconds)
    queries = datagen.make_queries(seed, 1, n, mix, order)
    blocks = datagen.arena_blocks(seed, counts, layout, order)
    datagen.plant(blocks, counts, layout, order, queries, config["kmer"])
    exact = ref.Reference(counts, blocks, kmer=config["kmer"],
                          fpr=config["fpr"])
    rec = loadgen.Records(len(queries))
    for i, q in enumerate(queries):
        rec.begin(i, 0.0)
        rec.finish(i, ControlAnswer(exact, q))
    checks = run.check_answers(rec, queries, exact,
                               int(traffic["check_sample"]), seed)
    return {k: v[0] for k, v in checks.items()}


def main(argv) -> int:
    cell, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    for seed in seeds:
        t0 = time.monotonic()
        out = control_run(spec, cell, seed, seconds)
        print(json.dumps({"cell": cell, "seed": seed, "control": out,
                          "wall_s": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
