"""readback_copies_per_batch.interactive: a traced tiny run of the search
cell reads one device-to-host transfer per scored batch, and a program
whose readback span carries no copies tag leaves the metric out."""
from types import SimpleNamespace

from conftest import OPEN

NAME = "readback_copies_per_batch.interactive"


def test_traced_search_run_reads_one_copy_per_batch(run_tiny):
    out = run_tiny(OPEN, traced=True)
    assert out["correct"]
    assert out["metrics"][NAME] == {"value": 1.0, "unit": "count"}


def _run_of(trace):
    return SimpleNamespace(traces=[trace], counters=SimpleNamespace(),
                           records=SimpleNamespace(window=(0.0, 10.0)))


def test_untagged_readback_reads_nothing():
    """A program that times readback without the tag."""
    import run
    from repro.obs import Trace

    t = Trace(1, started_s=0.0)
    t.add("kernel_score", 1.0, 2.0, {"batch": 0})
    t.add("readback", 1.5, 2.0, {"batch": 0, "parent": "kernel_score"})
    assert run.load_reader(NAME)(_run_of(t)) is None


def test_copies_are_summed_per_batch_and_averaged():
    import run
    from repro.obs import Trace

    t = Trace(1, started_s=0.0)
    for b, start, copies in ((0, 1.0, 8), (1, 2.0, 1), (1, 2.5, 1)):
        t.add("readback", start, start + 0.1, {"batch": b, "copies": copies})
    # a set-up batch before the window is left out
    t.add("readback", -1.0, -0.5, {"batch": 9, "copies": 100})
    assert run.load_reader(NAME)(_run_of(t)) == 5.0
