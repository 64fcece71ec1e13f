import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
for p in (str(BENCH), str(BENCH / "tools"), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

OPEN, CLOSED = "microbial-8k.search", "microbial-8k-pruned.contain"


@pytest.fixture
def tiny_spec():
    """BENCHMARK.json with its two cells pointed at a tiny configuration
    and traffic (``bench/tests/data``) that the CPU serves in seconds."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] += [
        {"name": "tiny", "source": "test", "reduced": [], "why": "test",
         "file": "bench/tests/data/tiny.json"},
        {"name": "tiny-pruned", "source": "test", "reduced": [], "why": "test",
         "file": "bench/tests/data/tiny-pruned.json"}]
    for w in spec["workloads"]:
        if w["name"] == OPEN:
            w.update(config="tiny", traffic="tiny.open")
        else:
            w.update(config="tiny-pruned", traffic="tiny.closed")
    return spec


@pytest.fixture
def run_tiny(tiny_spec, tmp_path):
    """run_cell on the CPU at the tiny size."""
    import run

    def go(cell, *, seed=7, seconds=1.5, traced=False, **traffic):
        return run.run_cell(tiny_spec, cell, seed, seconds, traced,
                            require_tpu=False, scratch=tmp_path,
                            traffic_dir=DATA, compile_cache=False,
                            traffic_override=traffic)
    return go
