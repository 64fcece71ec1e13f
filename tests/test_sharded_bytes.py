"""The sharded path with placement by bytes and packed workers, on a
skewed seeded archive whose largest block holds more than a host's mean
share, answers exactly what the benchmark's plain reference scorer
answers, threshold and top-k alike; each worker stages its held rows
once, not its shards padded to the tallest."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import datagen  # noqa: E402
import reference as ref  # noqa: E402

from repro.launch.serve import make_multihost_frontend  # noqa: E402
from repro.serve import Status  # noqa: E402

CONFIG = {"n_docs": 8192, "sigma": 1.25, "mean_terms": 60, "min_terms": 40,
          "order_seed": 0, "fpr": 0.3, "kmer": 31}
MIX = {"lengths": {"31": 0.2, "60": 0.4, "150": 0.4}, "threshold": 0.8,
       "top_k": 10, "top_k_share": 0.3, "positive_share": 0.5}


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    seed = 2**31 + 5
    counts = datagen.term_counts(CONFIG)
    layout, order, params = datagen.layout_of(counts, CONFIG)
    queries = datagen.make_queries(seed, 1, 40, MIX, order)
    datagen.compile_terms(queries, CONFIG["kmer"])
    blocks = datagen.arena_blocks(seed, counts, layout, order)
    datagen.plant(blocks, counts, layout, order, queries, CONFIG["kmer"])
    store = tmp_path_factory.mktemp("bytes") / "store"
    datagen.write_store(store, blocks, layout, params, {"codec": "raw"})
    reference = ref.Reference(counts, blocks, kmer=CONFIG["kmer"],
                              fpr=CONFIG["fpr"])
    return store, queries, reference, [b.shape[0] for b in blocks]


def frontend(store, **kw):
    return make_multihost_frontend(store, hosts=4, replication=1,
                                   max_batch=4, max_wait_s=0.0,
                                   hedge_after_s=1e9, balance="bytes", **kw)


def test_bytes_placed_packed_frontend_matches_reference(archive):
    store, queries, reference, rows = archive
    assert max(rows) * 4 > sum(rows)         # the top block: over a share
    fe = frontend(store)
    top = int(np.argmax(rows))
    assert [w.shard_ids for w in fe.workers.values()
            if top in w.shard_ids] == [(top,)]
    rids = [fe.submit(terms=q.terms, threshold=q.threshold,
                      top_k=q.top_k or None) for q in queries]
    fe.drain()
    got = fe.pop_responses()
    assert {q.top_k for q in queries} == {0, 10}
    for rid, q in zip(rids, queries):
        r = got[rid]
        assert r.status == Status.OK
        ids, scores = reference.expect(q.codes, q.threshold, q.top_k)
        np.testing.assert_array_equal(np.asarray(r.result.doc_ids), ids)
        np.testing.assert_array_equal(np.asarray(r.result.scores), scores)
        if q.origin >= 0 and not q.top_k:
            assert q.origin in r.result.doc_ids


def test_packed_worker_stages_its_rows_once(archive):
    store, queries, _, rows = archive
    fe = frontend(store)
    q = queries[0]
    fe.submit(terms=q.terms, threshold=q.threshold)
    fe.drain()
    tallest = max(rows)
    for w in fe.workers.values():
        held = sum(rows[g] for g in w.shard_ids)
        padded = -(-held // 32) * 32         # whole 128-lane lines of 8
        assert w.tiles.resident_bytes == padded * 128
        assert w.tiles.raw_bytes_staged == padded * 128
        assert len(w.tiles) == len(w.shard_ids)
        if len(w.shard_ids) > 1:
            assert padded < len(w.shard_ids) * tallest
        # one staging for every held shard, one shape
        assert w.tiles.faults == 1
        assert {w.tiles.row_base(i) for i in range(len(w.shard_ids))} == {
            int(s) for s in w.storage.shard_row_starts[:-1]}


@pytest.mark.parametrize("threads", [1, 4])
def test_traced_batch_has_shard_stages(archive, threads):
    """Per shard: shard_dispatch from its own start to its end, and in it
    shard_kernel, shard_readback and shard_select tagged with the shard,
    the node and the device; the batch's scatter counts its copies."""
    store, queries, _, rows = archive
    fe = frontend(store, tracing=True, scatter_threads=threads)
    node_of = {g: n for n, w in fe.workers.items() for g in w.shard_ids}
    for q in queries[:3]:
        fe.submit(terms=q.terms, threshold=q.threshold)
    fe.drain()
    fe.pop_responses()
    trace = fe.tracer.recent()[-1]
    spans = trace.spans()
    scatter, = [s for s in spans if s.name == "scatter"]
    assert scatter.tags["copies"] == len(rows)
    disp = sorted((s for s in spans if s.name == "shard_dispatch"),
                  key=lambda s: s.tags["shard"])
    assert [s.tags["shard"] for s in disp] == list(range(len(rows)))
    for d in disp:
        g, node = d.tags["shard"], d.tags["node"]
        assert node == node_of[g] and d.tags["parent"] == "scatter"
        assert scatter.start_s <= d.start_s <= d.end_s <= scatter.end_s
        stages = [s for s in spans if s.tags.get("shard") == g
                  and s.name != "shard_dispatch"]
        assert [s.name for s in sorted(stages, key=lambda s: s.start_s)] \
            == ["shard_kernel", "shard_readback", "shard_select"]
        for s in stages:
            assert s.tags["node"] == node
            assert s.tags["device"] == fe.workers[node].device_id
            assert s.tags["parent"] == "shard_dispatch"
            assert d.start_s <= s.start_s <= s.end_s <= d.end_s
    if threads == 1:
        # one after another: each dispatch starts where the last ended,
        # not at the scatter's start
        for a, b in zip(disp, disp[1:]):
            assert b.start_s >= a.end_s > scatter.start_s
