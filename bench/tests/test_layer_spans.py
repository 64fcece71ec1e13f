"""The per-layer metrics that read the program's own spans inside the
wire, the loop's lock, the scoring dispatch and the pruned executor: a
traced tiny run reads each in its cell, and a run whose program records
no such span or counter leaves them out without raising."""
from types import SimpleNamespace

import pytest

from conftest import CLOSED, OPEN

NEW = {
    OPEN: {"lock_wait_p95_ms", "wire_ms_per_request.interactive",
           "kernel_host_ms_per_batch.interactive",
           "readback_ms_per_batch.interactive"},
    CLOSED: {"lock_wait_p95_ms.throughput",
             "prune_host_ms_per_batch.throughput",
             "prune_syncs_per_batch.throughput"},
}


@pytest.mark.parametrize("cell", [OPEN, CLOSED])
def test_traced_run_reads_the_program_spans(run_tiny, cell):
    out = run_tiny(cell, traced=True)
    assert out["correct"]
    got = {k: v["value"] for k, v in out["metrics"].items() if k in NEW[cell]}
    assert set(got) == NEW[cell]
    assert all(v > 0 for v in got.values()), got
    if cell == CLOSED:   # the pruned branch records its interval once
        idle = {name for name, _ in out["breakdown"]["idle_gaps"]}
        assert "kernel_score" not in idle


@pytest.mark.parametrize("name", sorted(NEW[OPEN] | NEW[CLOSED]))
def test_a_program_without_the_spans_reads_nothing(name):
    """The parent program's traces carry no batch tag, lock_wait or wire
    span: every new reader returns None."""
    import run
    from repro.obs import Trace

    t = Trace(1, started_s=0.0)
    t.add("queue_wait", 1.0, 1.5)
    t.add("plan", 1.5, 1.6, {"method": "lookup"})
    t.add("kernel_score", 1.6, 2.0, {"method": "lookup"})
    t.add("prune", 1.6, 2.0, {"blocks_pruned": 3})
    runs = SimpleNamespace(traces=[t], counters=SimpleNamespace(),
                           records=SimpleNamespace(window=(0.0, 10.0)))
    assert run.load_reader(name)(runs) is None
