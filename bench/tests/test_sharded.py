"""A configuration with a ``frontend`` key: served by the program's
Frontend over one ShardWorker per chip, refused where the cell has fewer
chips than hosts; one without it is served by a QueryServer."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, DATA, OPEN, ROOT


def test_a_sharded_configuration_serves_over_four_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    p = subprocess.run([sys.executable,
                        str(BENCH / "tests" / "sharded_check.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    assert "SHARDED-OK" in p.stdout


@pytest.fixture
def sharded_spec(tiny_spec):
    tiny_spec["configs"].append(
        {"name": "tiny-sharded", "source": "test", "reduced": [],
         "why": "test", "file": "bench/tests/data/tiny-sharded.json"})
    cell = next(w for w in tiny_spec["workloads"] if w["name"] == OPEN)
    cell["config"] = "tiny-sharded"
    return cell


def test_more_hosts_than_chips_is_refused(sharded_spec, run_tiny):
    import run
    assert sharded_spec["chips"] == 1
    with pytest.raises(SystemExit, match="4 hosts"):
        run_tiny(OPEN)
    sharded_spec["chips"] = 4          # the cell asks for them; JAX sees 1
    with pytest.raises(run.NoChip, match="4 hosts"):
        run_tiny(OPEN)


def test_a_configuration_without_frontend_builds_a_query_server(tmp_path):
    import datagen
    import run
    from repro.serve import QueryServer
    config = json.loads((DATA / "tiny.json").read_text())
    assert "frontend" not in config
    counts = datagen.term_counts(config)
    layout, order, params = datagen.layout_of(counts, config)
    blocks = datagen.arena_blocks(3, counts, layout, order)
    index = datagen.write_store(tmp_path / "store", blocks, layout, params,
                                config["store"])
    server = run.build_server(config, index, tmp_path / "store", False, 256)
    assert isinstance(server, QueryServer)
    assert server.config.max_batch == 4
    assert run.placement_of(server) is None
