"""Pruned-scoring tests: branch-and-bound early exit must be LOSSLESS.

The load-bearing invariant mirrors the compression suite's: pruning
changes BYTES (and kernel work), never SCORES. Every pruned path —
engine threshold search, engine top-k, compressed stores, the
QueryServer batch branch, the paged multi-host worker — must return
results bit-identical to the exhaustive oracle, while the PruneStats
accounting proves tiles were actually skipped (a pruned shard performs
ZERO tile-cache faults: nothing staged, nothing promoted).

Satellites covered here too: ratio-aware tile eviction (raw victims
before dict-coded), per-slice popcount sidecars in the v2 manifest
surviving codec migration, per-worker local dispatch-shape padding, and
the planner's break-even gating.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import IndexParams, QueryEngine
from repro.core.arena import DeviceTileCache
from repro.core.query import (PruneStats, compile_pattern, coverage_cutoff,
                              pad_term_batch)
from repro.core.store import migrate_store_codec, open_store
from repro.data import make_corpus
from repro.index import build_compact_streaming

PARAMS = IndexParams(n_hashes=1, fpr=0.03, kmer=15)


def _redundant_terms(n_base=24, reps=6, seed=3):
    c = make_corpus(n_base, k=15, mean_length=160, min_length=120,
                    seed=seed)
    return c, [c.doc_terms[i % n_base] for i in range(n_base * reps)]


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    """paged raw, paged rowdict, and dense (single-shard) stores over the
    same corpus — the three executor regimes the pruned path must match."""
    c, terms = _redundant_terms()
    root = tmp_path_factory.mktemp("prune-stores")
    idx_raw, _ = build_compact_streaming(
        terms, root / "raw", PARAMS, block_docs=32, blocks_per_shard=1,
        codec="raw")
    idx_c, _ = build_compact_streaming(
        terms, root / "comp", PARAMS, block_docs=32, blocks_per_shard=1,
        codec="rowdict")
    idx_dense, _ = build_compact_streaming(
        terms, root / "dense", PARAMS, block_docs=32, blocks_per_shard=64,
        codec="raw")
    assert idx_raw.storage.n_shards > 2
    assert idx_dense.storage.n_shards == 1
    return c, root, idx_raw, idx_c, idx_dense


def _patterns(c, n_random=4, seed=0):
    rng = np.random.default_rng(seed)
    pats = ["".join(rng.choice(list("ACGT"), size=70))
            for _ in range(n_random)]
    pats += [c.documents[i][10:100] for i in range(4)]
    return pats


# --------------------------------------------------------------------------
# Engine: pruned == oracle (property over threshold x store x chunk)
# --------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.sampled_from([0.3, 0.5, 0.9, 1.0]),
       st.sampled_from(["raw", "comp", "dense"]),
       st.sampled_from([8, 32]),
       st.integers(0, 10 ** 6))
def test_pruned_matches_oracle(stores, threshold, kind, chunk, seed):
    c, _, idx_raw, idx_c, idx_dense = stores
    idx = {"raw": idx_raw, "comp": idx_c, "dense": idx_dense}[kind]
    oracle = QueryEngine(idx, method="lookup", compressed=(kind == "comp"))
    eng = QueryEngine(idx, method="lookup", compressed=(kind == "comp"),
                      prune_chunk=chunk)
    pats = _patterns(c, seed=seed)
    stats = PruneStats()
    got = eng.search_batch_pruned(pats, threshold=threshold, stats=stats)
    want = oracle.search_batch(pats, threshold=threshold)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.doc_ids, b.doc_ids)
        np.testing.assert_array_equal(a.scores, b.scores)
    assert stats.blocks_total > 0


def test_pruned_top_k_matches_oracle(stores):
    c, _, idx_raw, idx_c, _ = stores
    for idx, comp in ((idx_raw, False), (idx_c, True)):
        oracle = QueryEngine(idx, method="lookup", compressed=comp)
        eng = QueryEngine(idx, method="lookup", compressed=comp,
                          prune_chunk=16)
        for k in (1, 5, 64):
            for pat in _patterns(c)[:4]:
                a = eng.top_k_pruned(pat, k=k)
                b = oracle.top_k(pat, k=k)
                np.testing.assert_array_equal(a.doc_ids, b.doc_ids)
                np.testing.assert_array_equal(a.scores, b.scores)


def test_k2_pruned_matches_oracle(tmp_path):
    """n_hashes=2: the AND-of-hashes chunk kernel through the pruned
    executor."""
    c, terms = _redundant_terms(n_base=16, reps=4, seed=9)
    p2 = IndexParams(n_hashes=2, fpr=0.05, kmer=15)
    idx, _ = build_compact_streaming(
        terms, tmp_path / "k2", p2, block_docs=32, blocks_per_shard=1)
    oracle = QueryEngine(idx, method="vertical")
    eng = QueryEngine(idx, method="vertical", prune_chunk=16)
    pats = _patterns(c)[:5]
    for thr in (0.5, 1.0):
        for a, b in zip(eng.search_batch_pruned(pats, threshold=thr),
                        oracle.search_batch(pats, threshold=thr)):
            np.testing.assert_array_equal(a.doc_ids, b.doc_ids)
            np.testing.assert_array_equal(a.scores, b.scores)


# --------------------------------------------------------------------------
# The point of pruning: skipped I/O, and ZERO tile fetches when pruned
# --------------------------------------------------------------------------

def test_all_blocks_pruned_negative_query(stores):
    """A pure-negative query at threshold 1.0 must kill every block
    after the first chunk and never stage a single tile."""
    c, _, idx_raw, _, _ = stores
    eng = QueryEngine(idx_raw, method="lookup", prune_chunk=8)
    rng = np.random.default_rng(42)
    neg = "".join(rng.choice(list("ACGT"), size=90))
    stats = PruneStats()
    res = eng.search_batch_pruned([neg], threshold=1.0, stats=stats)[0]
    assert res.doc_ids.size == 0
    assert stats.blocks_pruned > 0
    assert stats.prune_rate > 0.5
    # zero-tile-fetch regression: the pruned run gathers rows host-side
    # only — no demand staging, no prefetch, no promotion
    assert eng.tiles.faults == 0
    assert stats.tiles_promoted == 0
    assert stats.bytes_read < sum(
        int(idx_raw.storage.shard_hbm_nbytes(s))
        for s in range(idx_raw.storage.n_shards))


def test_pruned_reads_fewer_bytes_at_high_threshold(stores):
    c, _, idx_raw, _, _ = stores
    base = sum(int(idx_raw.storage.shard_hbm_nbytes(s))
               for s in range(idx_raw.storage.n_shards))
    eng = QueryEngine(idx_raw, method="lookup", prune_chunk=16)
    stats = PruneStats()
    eng.search_batch_pruned(_patterns(c), threshold=0.9, stats=stats)
    assert stats.bytes_read * 3 <= base          # the >=3x acceptance bar
    assert stats.shard_visits_skipped > 0 or stats.blocks_pruned > 0


# --------------------------------------------------------------------------
# Satellite: ratio-aware tile eviction (raw victims before dict-coded)
# --------------------------------------------------------------------------

def test_ratio_aware_eviction_prefers_raw_victims(tmp_path):
    # wide blocks so rowdict actually finds repeated rows (the pruning
    # stores' 32-doc blocks are too narrow to code)
    _, terms = _redundant_terms(n_base=24, reps=8, seed=3)
    idx_c, _ = build_compact_streaming(
        terms, tmp_path / "evict", PARAMS, block_docs=128,
        blocks_per_shard=1, codec="rowdict")
    storage = idx_c.storage
    dict_shards = [s for s in range(storage.n_shards)
                   if storage.shard_dict_host(s) is not None]
    assert dict_shards
    # smallest dict-coded shard vs the tallest other shard, so one
    # eviction always re-fits the cache (raw(d) < raw(other))
    d = min(dict_shards, key=storage.shard_nbytes)
    other = max((s for s in range(storage.n_shards) if s != d),
                key=storage.shard_nbytes)
    cache = DeviceTileCache(storage)
    cache.get_compressed(d)           # dict entry staged first: LRU head
    # capacity for the dict entry plus exactly one raw tile — staging a
    # second raw tile must evict, and plain LRU would kill the dict
    cache.capacity_bytes = (cache.resident_bytes
                            + cache._tile_nbytes(other) + 64)
    cache.get(other)
    assert not cache.shard_evictions  # both fit
    cache.get(d)                      # raw form of d: independent entry
    # ratio-aware victim selection: the raw tile of ``other`` was
    # evicted; the dict entry outlived it despite being least recently
    # used
    assert cache.shard_evictions == {other: 1}
    assert cache.has_compressed(d)
    assert any(isinstance(k, tuple) for k in cache._tiles)
    assert other not in cache.resident_shards


# --------------------------------------------------------------------------
# Satellite: per-slice popcount sidecars + migration round-trip
# --------------------------------------------------------------------------

def test_popcount_sidecar_values(stores):
    _, _, idx_raw, _, _ = stores
    storage = idx_raw.storage
    assert storage.has_popcounts()
    for s in range(storage.n_shards):
        tile = np.asarray(storage.shard_host(s), dtype=np.uint32)
        want = np.unpackbits(tile.view(np.uint8), axis=1).sum(
            axis=1).astype(np.uint32)
        np.testing.assert_array_equal(storage.shard_popcounts(s), want)
    assert 0.0 < storage.mean_popcount() <= 32 * storage.shape[1]


def test_popcounts_survive_codec_migration(stores, tmp_path):
    _, root, idx_raw, _, _ = stores
    migrate_store_codec(root / "raw", tmp_path / "mig-c", codec="auto")
    migrate_store_codec(tmp_path / "mig-c", tmp_path / "mig-r",
                        codec="raw")
    for name in ("mig-c", "mig-r"):
        _, storage, _ = open_store(tmp_path / name, verify=True)
        assert storage.has_popcounts()
        for s in range(storage.n_shards):
            np.testing.assert_array_equal(
                storage.shard_popcounts(s),
                idx_raw.storage.shard_popcounts(s))
        assert storage.mean_popcount() == idx_raw.storage.mean_popcount()


# --------------------------------------------------------------------------
# Planner: break-even gating (pruned only when predicted to win)
# --------------------------------------------------------------------------

def test_planner_prune_gating(stores):
    from repro.serve.planner import QueryPlanner, predict_prune_rate
    _, _, idx_raw, _, _ = stores
    pl = QueryPlanner(idx_raw, pruned=True, prune_chunk=16,
                      prune_min_rate=0.3)
    # selective coverage clears the break-even -> pruned plan
    p = pl.plan(64, 4, threshold=0.95)
    assert p.pruned and p.chunk_terms == 16 and p.predicted_prune > 0.3
    # no coverage hint (all-top-k batch): static prediction impossible
    assert not pl.plan(64, 4).pruned
    # coverage at/below the slice density: nothing can be pruned
    assert not pl.plan(64, 4, threshold=0.01).pruned
    # bucket no larger than one chunk: nothing to exit early from
    assert not pl.plan(16, 4, threshold=0.95).pruned
    # a break-even the predictor can never clear -> never pruned
    pl2 = QueryPlanner(idx_raw, pruned=True, prune_chunk=16,
                       prune_min_rate=2.0)
    assert not pl2.plan(64, 4, threshold=1.0).pruned
    # disabled planner never prunes
    pl3 = QueryPlanner(idx_raw, pruned=False)
    assert not pl3.plan(64, 4, threshold=0.95).pruned
    # the predictor itself: monotone in threshold, 0 below density
    d = 0.2
    assert predict_prune_rate(0.1, d) == 0.0
    assert predict_prune_rate(0.9, d) > predict_prune_rate(0.5, d)
    assert predict_prune_rate(1.0, d) == 1.0


def test_tuner_lookup_p_entry(stores):
    from repro.kernels.autotune import KernelTuner, TuningCache
    _, _, idx_raw, _, _ = stores
    tuner = KernelTuner.for_index(idx_raw, TuningCache(), enabled=True,
                                  repeats=1, word_blocks=(64,),
                                  grid_orders=("wq",))
    e = tuner.entry("lookup_p", 64, 4)
    assert e is not None and e.method == "lookup_p"
    assert e.term_block and e.term_block >= 1          # chunk size
    assert 0.0 <= e.dedup_threshold <= 2.0             # prune break-even


# --------------------------------------------------------------------------
# Serving: QueryServer pruned branch, mixed batches, metrics
# --------------------------------------------------------------------------

def test_server_pruned_bit_identical_and_metrics(stores):
    from repro.serve.server import QueryServer, ServerConfig
    c, _, idx_raw, _, _ = stores
    engine = QueryEngine(idx_raw, method="lookup")
    srv = QueryServer(idx_raw, ServerConfig(
        pruned=True, prune_chunk=16, prune_min_rate=0.05,
        result_cache=0, row_cache=0, max_wait_s=0.0))
    pats = _patterns(c)
    rids = [srv.submit(p, threshold=0.9) for p in pats]
    srv.drain()
    got = srv.pop_responses()
    methods = {got[r].method for r in rids}
    assert "lookup_p" in methods
    for rid, p in zip(rids, pats):
        want = engine.search(p, threshold=0.9)
        np.testing.assert_array_equal(got[rid].result.doc_ids,
                                      want.doc_ids)
        np.testing.assert_array_equal(got[rid].result.scores,
                                      want.scores)
    snap = srv.metrics.snapshot()
    assert snap.pruned_blocks > 0
    assert snap.pruned_bytes_saved > 0
    assert "prune[" in snap.report()
    from repro.obs import render_prometheus
    text = render_prometheus(srv.metrics.registry)
    assert "serve_pruned_blocks_total" in text
    assert "serve_pruned_bytes_saved_total" in text


def test_server_pruned_mixed_batch(stores):
    from repro.serve.server import QueryServer, ServerConfig
    c, _, idx_raw, _, _ = stores
    engine = QueryEngine(idx_raw, method="lookup")
    srv = QueryServer(idx_raw, ServerConfig(
        pruned=True, prune_chunk=16, prune_min_rate=0.05,
        result_cache=0, row_cache=0, max_wait_s=10.0))
    pats = _patterns(c)
    r1 = srv.submit(pats[0], threshold=0.9)
    r2 = srv.submit(pats[4], top_k=3)
    r3 = srv.submit(pats[5], threshold=0.8)
    srv.drain()
    got = srv.pop_responses()
    assert got[r1].method == "lookup_p"
    for rid, want in ((r1, engine.search(pats[0], threshold=0.9)),
                      (r2, engine.top_k(pats[4], k=3)),
                      (r3, engine.search(pats[5], threshold=0.8))):
        np.testing.assert_array_equal(got[rid].result.doc_ids,
                                      want.doc_ids)
        np.testing.assert_array_equal(got[rid].result.scores,
                                      want.scores)


# --------------------------------------------------------------------------
# Paged multi-host: worker pruned dispatch + local_pad shapes
# --------------------------------------------------------------------------

def test_worker_pruned_candidates_identical_zero_faults(stores):
    from repro.serve.worker import ShardWorker
    c, root, idx_raw, _, _ = stores
    ids = list(range(idx_raw.storage.n_shards))
    w_ref = ShardWorker("w-ref", root / "raw", ids)
    w_p = ShardWorker("w-prune", root / "raw", ids, pruned=True,
                      prune_chunk=16, prune_min_rate=0.05)
    term_sets = [compile_pattern(p, PARAMS) for p in _patterns(c)[:6]]
    buf, ells = pad_term_batch(term_sets, 64)
    cuts = np.array([coverage_cutoff(0.9, int(e)) for e in ells],
                    np.int32)
    topks = np.zeros(len(ells), np.int32)
    td_r, nd_r = w_ref.stage_batch(buf, ells)
    td_p, nd_p = w_p.stage_batch(buf, ells)
    for g in ids:
        # an HBM-resident worker packs its shards into one arena, staged
        # by the first prefetch
        assert w_ref.prefetch_shard(g) == (g == ids[0])
        cand_r, m_r = w_ref.score_candidates(g, td_r, nd_r, cuts, topks,
                                             len(ells))
        cand_p, m_p = w_p.score_candidates(g, td_p, nd_p, cuts, topks,
                                           len(ells))
        for (d0, s0), (d1, s1) in zip(cand_r, cand_p):
            np.testing.assert_array_equal(d0, d1)
            np.testing.assert_array_equal(s0, s1)
    assert w_p.pruned_dispatches == len(ids)
    assert w_p.prune_stats.blocks_total > 0
    # pruned dispatch never touches the device tile cache
    assert w_p.tiles.faults == 0


def test_frontend_pruned_bit_identical(stores):
    from repro.serve.worker import ShardWorker
    from repro.serve.frontend import Frontend, FrontendConfig
    from repro.index.placement import ShardPlacement
    c, root, idx_raw, _, _ = stores
    engine = QueryEngine(idx_raw, method="lookup")
    n_sh = idx_raw.storage.n_shards
    placement = ShardPlacement(["w0", "w1"], n_sh, replication=1)
    workers = {
        node: ShardWorker(node, root / "raw",
                          placement.replica_assignment()[node],
                          pruned=True, prune_chunk=16,
                          prune_min_rate=0.05)
        for node in ("w0", "w1")
        if placement.replica_assignment()[node]}
    fe = Frontend(workers, placement,
                  FrontendConfig(max_wait_s=0.0, scatter_threads=1))
    pats = _patterns(c)
    rids = [fe.submit(p, threshold=0.9) for p in pats]
    fe.drain()
    got = fe.pop_responses()
    methods = {got[r].method for r in rids}
    assert "lookup_p" in methods
    for rid, p in zip(rids, pats):
        want = engine.search(p, threshold=0.9)
        np.testing.assert_array_equal(got[rid].result.doc_ids,
                                      want.doc_ids)
        np.testing.assert_array_equal(got[rid].result.scores,
                                      want.scores)
    snap = fe.metrics.snapshot()
    assert snap.pruned_blocks > 0
    # frontend top-k through pruned workers (shard-local bound soundness)
    rid = fe.submit(pats[5], top_k=4)
    fe.drain()
    r = fe.pop_responses()[rid]
    want = engine.top_k(pats[5], k=4)
    np.testing.assert_array_equal(r.result.doc_ids, want.doc_ids)
    np.testing.assert_array_equal(r.result.scores, want.scores)


def test_worker_local_pad_dispatch_shapes(stores):
    from repro.serve.worker import ShardWorker
    _, root, idx_raw, _, _ = stores
    starts = idx_raw.storage.shard_row_starts
    heights = np.diff(starts)
    short = int(np.argmin(heights))
    assert heights[short] < heights.max()     # last block group is short
    # padding applies to paged workers (an HBM budget); resident ones
    # pack their shards unpadded
    budget = int(idx_raw.storage.nbytes())
    w_local = ShardWorker("w-l", root / "raw", [short], local_pad=True,
                          tile_cache_bytes=budget)
    w_glob = ShardWorker("w-g", root / "raw", [short],
                         tile_cache_bytes=budget)
    # local padding sizes tiles to THIS worker's tallest shard only
    assert w_local.tiles.pad_rows_to == int(heights[short])
    assert w_glob.tiles.pad_rows_to == int(heights.max())
    assert w_local.tiles.pad_rows_to < w_glob.tiles.pad_rows_to
