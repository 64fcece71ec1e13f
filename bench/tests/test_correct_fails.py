"""The comparison that decides ``correct`` is shown to fail: the control
(the reference with a guarantee broken) and faults planted in the timed
path underneath a whole run all come out not correct."""
import numpy as np
import pytest

from conftest import CLOSED, DATA, OPEN


@pytest.mark.parametrize("cell", [OPEN, CLOSED])
def test_control_fails_the_comparison(tiny_spec, cell):
    import control
    got = control.control_run(tiny_spec, cell, 11, 3.0, traffic_dir=DATA)
    assert got["checked"] > 10
    assert got["mismatched"] > 0


def _drop_first_hit(select):
    def altered(*a, **kw):
        r = select(*a, **kw)
        r.doc_ids, r.scores = r.doc_ids[1:], r.scores[1:]
        return r
    return altered


def _half_batch(run_plan):
    def halved(self, *a, **kw):
        slots = np.array(run_plan(self, *a, **kw))
        if slots.ndim == 2 and slots.shape[0] > 1:
            slots[slots.shape[0] // 2:] = 0      # half the batch left out
        return slots
    return halved


@pytest.mark.parametrize("cell,fault", [(OPEN, "answer_altered"),
                                        (OPEN, "half_batch_dropped"),
                                        (CLOSED, "answer_altered")])
def test_a_fault_in_the_served_path_is_not_correct(run_tiny, monkeypatch,
                                                    cell, fault):
    from repro.serve import server
    if fault == "answer_altered":
        monkeypatch.setattr(server, "select_hits",
                            _drop_first_hit(server.select_hits))
        monkeypatch.setattr(server, "select_top_k",
                            _drop_first_hit(server.select_top_k))
    else:
        monkeypatch.setattr(server.QueryServer, "_run_plan",
                            _half_batch(server.QueryServer._run_plan))
    # the open loop at a rate that forms batches of several
    out = (run_tiny(OPEN, seconds=2.0, rate_qps=200) if cell == OPEN
           else run_tiny(CLOSED))
    assert not out["correct"]
    assert out["checks"]["mismatched"]["value"] > 0


def test_a_fault_in_the_pruned_path_is_not_correct(run_tiny, monkeypatch):
    from repro.serve import server
    real = server.run_paged_pruned

    def halved(*a, **kw):
        slots = np.array(real(*a, **kw))
        if slots.shape[0] > 1:
            slots[slots.shape[0] // 2:] = 0
        return slots

    monkeypatch.setattr(server, "run_paged_pruned", halved)
    out = run_tiny(CLOSED)
    assert not out["correct"]
