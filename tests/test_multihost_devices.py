"""Runs the multi-DEVICE sharded-serving checks in a subprocess (the rest
of the suite must see exactly ONE device, so the 4-device run is isolated
— same mechanism as test_distributed.py)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

_SCRIPT = Path(__file__).parent / "multihost_check.py"
_SRC = str(Path(__file__).parent.parent / "src")


@pytest.mark.slow
def test_multihost_frontend_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, str(_SCRIPT)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "ALL-MULTIHOST-OK" in proc.stdout


def test_traced_frontend_keeps_its_trace_ring(tmp_path):
    """make_multihost_frontend(trace_ring=n): a traced frontend keeps the
    last n traces, not FrontendConfig's default ring."""
    from repro.core import IndexParams
    from repro.data import make_corpus, make_queries
    from repro.index import build_compact_streaming
    from repro.launch.serve import make_multihost_frontend
    c = make_corpus(64, k=15, mean_length=300, sigma=1.0, seed=12)
    build_compact_streaming(c.doc_terms, tmp_path / "v2",
                            IndexParams(n_hashes=1, fpr=0.3, kmer=15),
                            block_docs=16, row_align=64)
    fe = make_multihost_frontend(tmp_path / "v2", hosts=2, replication=1,
                                 max_batch=4, max_wait_s=0.0,
                                 hedge_after_s=1e9, tracing=True,
                                 trace_ring=7)
    queries, _ = make_queries(c, n_pos=6, n_neg=6, length=60, seed=3)
    for q in queries:
        fe.submit(q, threshold=0.7)
    fe.drain()
    assert len(fe.pop_responses()) == len(queries) > 7
    assert fe.config.trace_ring == 7
    assert len(fe.tracer.recent()) == 7
