"""Multi-DEVICE check for the sharded serving data plane — executed in a
subprocess by test_multihost_devices.py with XLA_FLAGS forcing 4 host
devices (the rest of the suite must see exactly 1 device, the same
isolation mechanism as distributed_check.py / tests/conftest.py).

Each fake host's ShardWorker pins its DeviceTileCache and addressing to a
DISTINCT jax device, so shard tiles genuinely live on separate devices and
the frontend's scatter/gather crosses device boundaries. Asserts:

  * every worker's tiles reside on its own device
  * frontend threshold + top-k results == single-host QueryEngine
  * results stay bit-identical with one host down (replica failover)
"""
import os
import tempfile
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np

assert len(jax.devices()) == 4, jax.devices()

from repro.core import IndexParams, QueryEngine, build_compact
from repro.data import make_corpus, make_queries
from repro.index import ShardPlacement, build_compact_streaming
from repro.serve import Frontend, FrontendConfig, ShardWorker

params = IndexParams(n_hashes=1, fpr=0.3, kmer=15)
corpus = make_corpus(96, k=15, mean_length=400, sigma=1.0, seed=21)
dense = build_compact(corpus.doc_terms, params, block_docs=32, row_align=64)
store = Path(tempfile.mkdtemp()) / "v2"
mapped, _ = build_compact_streaming(corpus.doc_terms, store, params,
                                    block_docs=32, row_align=64)
assert mapped.storage.n_shards >= 3

nodes = ["h0", "h1", "h2"]
devices = jax.devices()[1:4]                   # distinct device per host
place = ShardPlacement.for_store(store, nodes, replication=2)
held = place.replica_assignment()
workers = {n: ShardWorker(n, store, held[n], device=d)
           for n, d in zip(nodes, devices) if held[n]}
fe = Frontend(workers, place, FrontendConfig(max_batch=8, max_wait_s=0.0))
eng = QueryEngine(dense)

queries, _ = make_queries(corpus, n_pos=6, n_neg=3, length=100, seed=5)
tids = [fe.submit(q, threshold=0.7) for q in queries]
kids = [fe.submit(q, top_k=5) for q in queries]
fe.drain()
resp = fe.pop_responses()
for rid, q in zip(tids, queries):
    want = eng.search(q, threshold=0.7)
    np.testing.assert_array_equal(resp[rid].result.doc_ids, want.doc_ids)
    np.testing.assert_array_equal(resp[rid].result.scores, want.scores)
for rid, q in zip(kids, queries):
    want = eng.top_k(q, k=5)
    np.testing.assert_array_equal(resp[rid].result.doc_ids, want.doc_ids)
    np.testing.assert_array_equal(resp[rid].result.scores, want.scores)
print("OK multi-device frontend == engine")

for name, w in workers.items():
    for tile in w.tiles._tiles.values():
        tile_devs = {d for d in tile.lines.devices()}
        assert tile_devs == {w.device}, (name, tile_devs, w.device)
print("OK tiles pinned per host device")

fe.fail_worker(place.owner(0))
assert place.is_covered()
tids = [fe.submit(q, threshold=0.7) for q in queries]
fe.drain()
resp = fe.pop_responses()
for rid, q in zip(tids, queries):
    want = eng.search(q, threshold=0.7)
    np.testing.assert_array_equal(resp[rid].result.doc_ids, want.doc_ids)
assert fe.metrics.snapshot().failovers > 0
print("OK failover across devices bit-identical")

print("ALL-MULTIHOST-OK")
