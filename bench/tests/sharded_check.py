"""A sharded configuration served through ``run_cell`` on four forced
host devices. Run in a subprocess by test_sharded.py: the rest of the
tests see one device."""
import json
import os
import sys
import tempfile
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402

from conftest import DATA, OPEN, ROOT  # noqa: E402

import run  # noqa: E402

assert len(jax.devices()) == 4, jax.devices()
spec = json.loads((ROOT / "BENCHMARK.json").read_text())
spec["configs"].append({"name": "tiny-sharded", "source": "test",
                        "reduced": [], "why": "test",
                        "file": "bench/tests/data/tiny-sharded.json"})
cell = next(w for w in spec["workloads"] if w["name"] == OPEN)
cell.update(config="tiny-sharded", traffic="tiny.open", chips=4)

built = []
build_server = run.build_server


def keep(*a, **kw):
    built.append(build_server(*a, **kw))
    return built[-1]


run.build_server = keep
# traced, and with more requests than the program's default ring of 256
# traces holds
with tempfile.TemporaryDirectory() as tmp:
    out = run.run_cell(spec, OPEN, 7, 1.5, True, require_tpu=False,
                       scratch=tmp, traffic_dir=DATA, compile_cache=False,
                       traffic_override={"rate_qps": 180})
assert out["correct"], out["checks"]
assert out["attempted"] == 270, out["attempted"]
assert out["checks"]["mismatched"]["value"] == 0
assert out["checks"]["checked"]["value"] > 10

frontend, = built
assert type(frontend).__name__ == "Frontend"
assert len(frontend.tracer.recent()) == frontend.tracer.finished_count > 270
placement = out["device"]["placement"]
held = sorted(g for shards in placement.values() for g in shards)
assert held == list(range(8)), placement
devs = {str(d.id): d for d in jax.devices()}
assert len(placement) == len(frontend.workers) == 4, placement
for w in frontend.workers.values():
    assert sorted(placement[str(w.device.id)]) == sorted(w.shard_ids)
    assert len(w.tiles) == len(w.shard_ids)      # every tile staged
    for local in range(len(w.shard_ids)):
        assert w.tiles.get(local).lines.devices() == {w.device}
assert set(out["device"]["memory_peak_by_device"]) == set(devs)
print("SHARDED-OK", json.dumps(placement))
