"""Placement by bytes: largest-first greedy over shards of skewed sizes
keeps the fullest node within max(the largest shard, 1.05 x the
mean share of the shards no larger than a mean share), keeps each
shard's HRW preference order, and leaves count-balanced HRW the
default."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.index import ShardPlacement
from repro.index.placement import _ranking

ROOT = Path(__file__).resolve().parents[1]
SLACK = 1.05


def loads(p: ShardPlacement, weights) -> dict:
    out = dict.fromkeys(p.nodes, 0)
    for g, w in enumerate(weights):
        for n in p.replicas(g):
            out[n] += int(w)
    return out


def bounds(weights, n_nodes: int, r: int) -> tuple[float, float]:
    """(the fullest node's bound, the bound of a node that holds no
    shard larger than a mean share), computed from the shares alone."""
    w = np.asarray(weights, dtype=np.float64)
    share = r * w.sum() / n_nodes
    large = w > share
    rest = SLACK * r * w[~large].sum() / (n_nodes - r * large.sum())
    return max(w.max(), rest), rest


def check(weights, n_nodes: int, r: int) -> ShardPlacement:
    nodes = [f"host{i}" for i in range(n_nodes)]
    p = ShardPlacement(nodes, len(weights), replication=r,
                       weights=list(weights))
    top, rest = bounds(weights, n_nodes, r)
    share = r * float(np.sum(weights)) / n_nodes
    load = loads(p, weights)
    assert max(load.values()) <= top, (load, top)
    for g, w in enumerate(weights):
        reps = p.replicas(g)
        assert len(reps) == r and len(set(reps)) == r
        # preference order is the shard's HRW ranking
        ranked = _ranking(g, nodes)
        assert reps == sorted(reps, key=ranked.index)
    big = {n for g, w in enumerate(weights) if w > share
           for n in p.replicas(g)}
    for n in big:                      # a large shard's node holds it alone
        assert sum(n in p.replicas(g) for g in range(len(weights))) == 1
    for n in set(nodes) - big:
        assert load[n] <= rest, (n, load, rest)
    return p


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("n_nodes,r", [(4, 1), (4, 2), (8, 2), (3, 1)])
def test_bytes_placement_bound_on_random_weights(seed, n_nodes, r):
    rng = np.random.default_rng([seed, n_nodes, r])
    n = int(rng.integers(60, 200))
    weights = np.maximum(1, rng.lognormal(10.0, 0.9, n)).astype(np.int64)
    if seed % 2:                       # one shard above a mean share
        weights[rng.integers(n)] = int(weights.sum() * 0.6 / n_nodes) + \
            int(weights.sum() * r / n_nodes)
    check(weights, n_nodes, r)


def test_bytes_placement_of_the_four_chip_archive():
    """The four-chip cell's 98 blocks: the top block alone on a chip, the
    other three within 1.05 x their mean share."""
    sys.path.insert(0, str(ROOT / "bench"))
    import datagen
    config = json.loads((ROOT / "bench" / "configs" /
                         "cobs-microbial-100k-4chip.json").read_text())
    counts = datagen.term_counts(config)
    layout, _, _ = datagen.layout_of(counts, config)
    rows = [int(x) for x in layout.block_width]
    assert len(rows) == 98
    p = check(rows, 4, 1)
    load = loads(p, [w * 128 for w in rows])
    top = int(np.argmax(rows))
    assert max(load.values()) == rows[top] * 128 == 9_903_931_392
    assert sorted(load.values())[-2] <= 7_741_636_608
    assert config["layout"]["placement_bytes"] == {
        n: load[n] for n in sorted(load)}


def test_bytes_placement_fails_over_in_preference_order():
    rng = np.random.default_rng(3)
    weights = rng.lognormal(8.0, 1.0, 40).astype(np.int64) + 1
    p = check(weights, 4, 2)
    before = {g: p.replicas(g) for g in range(40)}
    victim = p.owner(0)
    moved = p.fail(victim)
    assert 0 in moved and p.is_covered()
    for g in range(40):
        assert p.owner(g) == next(n for n in before[g] if n != victim)
    assert set(p.recover(victim)) == {g for g in range(40)
                                      if victim in before[g]}
    assert {g: p.owner(g) for g in range(40)} == {
        g: before[g][0] for g in range(40)}


def test_shards_balance_stays_plain_hrw(tmp_path):
    from repro.core import IndexParams
    from repro.data import make_corpus
    from repro.index import build_compact_streaming
    c = make_corpus(64, k=15, mean_length=300, sigma=1.2, seed=4)
    build_compact_streaming(c.doc_terms, tmp_path / "s",
                            IndexParams(n_hashes=1, fpr=0.3, kmer=15),
                            block_docs=16, row_align=64)
    nodes = ["a", "b", "c"]
    plain = ShardPlacement.for_store(tmp_path / "s", nodes, replication=1)
    assert plain.weights is None
    for g in range(plain.n_shards):
        assert plain.replicas(g) == _ranking(g, nodes)[:1]
    by_bytes = ShardPlacement.for_store(tmp_path / "s", nodes,
                                        replication=1, balance="bytes")
    manifest = json.loads((tmp_path / "s" / "manifest.json").read_text())
    assert list(by_bytes.weights) == [s["rows"][1] - s["rows"][0]
                                      for s in manifest["shards"]]
    with pytest.raises(ValueError, match="balance"):
        ShardPlacement.for_store(tmp_path / "s", nodes, balance="rows")
