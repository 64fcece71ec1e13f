"""chip_smoke.py's phases at a tiny size on the CPU (Pallas interpret
mode), each checked against the script's NumPy reference scorer; and the
script itself refusing to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as smoke  # noqa: E402


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    lengths = smoke.size_lengths(256, 1 << 20, seed=5)
    index, _, _ = smoke.build_store(tmp_path_factory.mktemp("s") / "store",
                                    lengths, seed=5, workers=1)
    return lengths, index, smoke.Reference(index, 0.8)


def test_reference_matches_engine(store):
    from repro.core import QueryEngine
    lengths, index, ref = store
    eng = QueryEngine(index)
    for q in smoke.make_queries(lengths, 5, 6, seed=1):
        want = (eng.top_k(q.codes, k=q.top_k) if q.top_k
                else eng.search(q.codes, threshold=0.8))
        ids, scores = ref.expect(q)
        np.testing.assert_array_equal(want.doc_ids, ids)
        np.testing.assert_array_equal(want.scores, scores)


def test_reference_catches_a_wrong_result(store):
    from repro.core.query import SearchResult
    lengths, _, ref = store
    q = smoke.make_queries(lengths, 5, 1, seed=2)[0]
    ids, scores = ref.expect(q)
    wrong = SearchResult(ids, scores + 1, 0, 0)
    with pytest.raises(smoke.SmokeFailure):
        smoke.verify("t", [q], [wrong], ref)


@pytest.mark.parametrize("label,methods,config", [
    ("a", ("lookup", "dedup", "vertical", "unpack"), {}),
    ("b", ("lookup_p",), {"pruned": True}),
])
def test_server_phases(store, label, methods, config):
    lengths, index, ref = store
    smoke.phase_server(index, smoke.make_queries(lengths, 5, 40, seed=3), ref,
                       label=label, device_methods=methods, **config)


def test_compressed_phase(store, tmp_path):
    lengths, _, _ = store
    docs = np.repeat(np.arange(8), smoke.BLOCK_DOCS // 8)
    cindex, _, _ = smoke.build_store(tmp_path / "rowdict", lengths, seed=5,
                                     workers=1, codec="rowdict", docs=docs)
    assert cindex.storage.dict_ratio() > 1.25
    queries = smoke.make_queries(lengths, 5, 24, seed=4, docs=docs)
    smoke.phase_server(cindex, queries, smoke.Reference(cindex, 0.8),
                       label="c", device_methods=("lookup_c", "dedup_c"),
                       compressed=True)


def test_bulk_and_net_phases(store):
    from repro.serve import QueryServer, ServerConfig
    lengths, index, ref = store
    server = QueryServer(index, ServerConfig())
    smoke.phase_bulk(server, smoke.make_queries(lengths, 5, 12, seed=6), ref)
    smoke.phase_net(server, smoke.make_queries(lengths, 5, 6, seed=7), ref)


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_four_chip_phase_on_forced_devices():
    """The --four-chips path on four forced host devices, in its own
    process: shards spread over the devices, results equal the
    reference."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    p = subprocess.run([sys.executable, str(ROOT / "tests" /
                                            "four_chip_check.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "FOUR-CHIP-OK" in p.stdout
