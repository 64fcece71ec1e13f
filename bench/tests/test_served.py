"""A whole run at a tiny size on the CPU: every sampled answer equals the
copied reference and every planted positive is found."""
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, CLOSED, OPEN, ROOT


@pytest.mark.parametrize("cell,metrics", [
    (OPEN, {"p50_ms", "device_bytes_per_index_byte", "setup_s"}),
    (CLOSED, {"qps", "setup_s"}),
])
def test_served_answers_equal_the_reference(run_tiny, cell, metrics):
    out = run_tiny(cell)
    checks = out["checks"]
    assert out["correct"], checks
    assert checks["checked"]["value"] > 10
    assert checks["mismatched"]["value"] == 0
    assert checks["positives_missed"]["value"] == 0
    assert out["failed"] == 0 and out["attempted"] > 10
    assert set(out["metrics"]) == metrics
    assert list(out)[-2] == "checks"


def test_traced_run_reads_its_per_layer_metrics(run_tiny):
    out = run_tiny(OPEN, traced=True)
    assert out["correct"]
    assert {"p95_ms.interactive", "gen_late_p95_ms", "queue_wait_p95_ms",
            "host_ms_per_batch.interactive"} <= set(out["metrics"])
    assert "kernel_roofline.interactive" not in out["metrics"]  # no chip
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_closed_loop_that_runs_out_of_queries_still_reports(run_tiny):
    out = run_tiny(CLOSED, pool_qps=4)
    assert out["correct"] and out["attempted"] == 6
    assert any("used all 6 queries" in n for n in out["_notes"])


def _no_result(p):
    lines = p.stdout.strip().splitlines()
    assert not lines or not lines[-1].startswith("{"), p.stdout


def test_entry_exits_non_zero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", OPEN,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    _no_result(p)


def test_entry_exits_non_zero_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", OPEN,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    _no_result(p)
