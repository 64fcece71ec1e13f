"""The four-chip cell's configuration form at a tiny, skewed size (one
block over a host's mean share), served through ``run_cell`` on four
forced host devices, traced, with the cell's own metrics. Run in a
subprocess by test_sharded_bytes.py: the rest of the tests see one
device."""
import json
import os
import sys
import tempfile
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from conftest import DATA, ROOT  # noqa: E402

import run  # noqa: E402

CELL = "microbial-100k-4chip.search"
assert len(jax.devices()) == 4, jax.devices()
spec = json.loads((ROOT / "BENCHMARK.json").read_text())
spec["configs"].append({"name": "tiny-sharded-bytes", "source": "test",
                        "reduced": [], "why": "test",
                        "file": "bench/tests/data/tiny-sharded-bytes.json"})
cell = next(w for w in spec["workloads"] if w["name"] == CELL)
cell.update(config="tiny-sharded-bytes", traffic="tiny.open")
config = json.loads((DATA / "tiny-sharded-bytes.json").read_text())

built = []
build_server = run.build_server


def keep(*a, **kw):
    built.append(build_server(*a, **kw))
    return built[-1]


run.build_server = keep
with tempfile.TemporaryDirectory() as tmp:
    out = run.run_cell(spec, CELL, 2**31 + 11, 1.5, True, require_tpu=False,
                       scratch=tmp, traffic_dir=DATA, compile_cache=False,
                       traffic_override={"rate_qps": 60})
assert out["correct"], out["checks"]
assert out["checks"]["mismatched"]["value"] == 0
assert out["checks"]["checked"]["value"] > 10

frontend, = built
n_shards = frontend.placement.n_shards
rows = np.asarray(frontend.placement.weights)
top = int(np.argmax(rows))
assert rows[top] * 4 > rows.sum(), rows          # over a host's mean share
placement = out["device"]["placement"]
alone = [d for d, held in placement.items() if held == [top]]
assert len(alone) == 1, placement                # a chip of its own
for w in frontend.workers.values():
    held_rows = int(rows[list(w.shard_ids)].sum())
    staged = w.tiles.resident_bytes
    assert held_rows * 128 <= staged < (held_rows + 32) * 128, (
        held_rows, staged)
metrics = out["metrics"]
for name in ("shard_readback_ms_per_batch.sharded",
             "readback_copies_per_batch.sharded",
             "shard_critical_ms_per_batch.sharded"):
    assert metrics[name]["value"] > 0, (name, metrics)
assert metrics["readback_copies_per_batch.sharded"]["value"] == n_shards
print("SHARDED-BYTES-OK", json.dumps(placement), json.dumps(metrics))
