"""The data a run is made of: seed-independent layout, seeded bits and
queries, and a reference that restates the program's index format."""
import json

import numpy as np
import pytest

import datagen
import reference as ref
from conftest import BENCH, DATA


def _config(name):
    return json.loads((BENCH / "configs" / name).read_text())


@pytest.mark.parametrize("name", ["cobs-microbial-8k.json",
                                  "cobs-microbial-8k-pruned.json"])
def test_layout_is_the_same_for_every_seed_and_as_recorded(name):
    cfg = _config(name)
    counts = datagen.term_counts(cfg)
    layout, _, _ = datagen.layout_of(counts, cfg)
    again, _, _ = datagen.layout_of(datagen.term_counts(cfg), cfg)
    assert np.array_equal(layout.block_width, again.block_width)
    assert np.array_equal(layout.doc_slot, again.doc_slot)
    got = datagen.layout_numbers(layout)
    for k, v in got.items():
        assert cfg["layout"][k] == v
    assert cfg["layout"]["block_rows"] == layout.block_width.tolist()


def test_reference_layout_equals_the_programs():
    cfg = json.loads((DATA / "tiny.json").read_text())
    counts = datagen.term_counts(cfg)
    layout, _, _ = datagen.layout_of(counts, cfg)
    slot, widths = ref.compact_layout(counts, cfg["fpr"])
    assert np.array_equal(widths, layout.block_width)
    assert np.array_equal(slot, layout.doc_slot)


def test_reference_restates_the_programs_kmers_and_hash():
    from repro.core import dna, hashing
    codes = np.random.default_rng(0).integers(0, 4, 500, dtype=np.uint8)
    assert np.array_equal(ref.pack_kmers(codes, 31), dna.pack_kmers(codes, 31))
    terms = ref.pack_kmers(codes, 31)
    assert np.array_equal(ref.hash_terms(terms),
                          hashing.hash_terms_np(terms, 1)[:, 0])


def test_seeds_draw_other_bits_and_queries_in_the_same_shapes():
    cfg = json.loads((DATA / "tiny.json").read_text())
    counts = datagen.term_counts(cfg)
    layout, order, _ = datagen.layout_of(counts, cfg)
    a = datagen.arena_blocks(1, counts, layout, order)
    b = datagen.arena_blocks(2**31 + 5, counts, layout, order)
    assert [x.shape for x in a] == [x.shape for x in b]
    assert not all(np.array_equal(x, y) for x, y in zip(a, b))
    again = datagen.arena_blocks(1, counts, layout, order)
    assert all(np.array_equal(x, y) for x, y in zip(a, again))
    mix = json.loads((DATA / "tiny.open.json").read_text())["queries"]
    qa = datagen.make_queries(1, 1, 100, mix, order)
    qb = datagen.make_queries(2, 1, 100, mix, order)
    assert sorted(len(q.codes) for q in qa) == sorted(len(q.codes) for q in qb)
    # the positives reach the blocks in turn, whatever the seed
    for qs in (qa, qb):
        blocks_hit = [int(layout.doc_slot[q.origin]) // ref.BLOCK_DOCS
                      for q in qs if q.origin >= 0]
        assert len(blocks_hit) == 50
        for i in range(len(blocks_hit) - layout.n_blocks + 1):
            assert (sorted(blocks_hit[i:i + layout.n_blocks])
                    == list(range(layout.n_blocks)))


def test_column_density_follows_the_term_count():
    cfg = json.loads((DATA / "tiny.json").read_text())
    counts = datagen.term_counts(cfg)
    layout, order, _ = datagen.layout_of(counts, cfg)
    blocks = datagen.arena_blocks(3, counts, layout, order)
    for b, blk in enumerate(blocks):
        bits = np.unpackbits(blk.view(np.uint8), axis=-1, bitorder="little")
        ids = order[b * ref.BLOCK_DOCS:(b + 1) * ref.BLOCK_DOCS]
        want = -np.expm1(-counts[ids] / blk.shape[0])
        got = bits.mean(axis=0)[:ids.size]
        assert np.abs(got - want).max() < 6 / np.sqrt(blk.shape[0])


def test_planted_positive_has_full_containment():
    cfg = json.loads((DATA / "tiny.json").read_text())
    counts = datagen.term_counts(cfg)
    layout, order, _ = datagen.layout_of(counts, cfg)
    blocks = datagen.arena_blocks(4, counts, layout, order)
    mix = {"lengths": {"150": 1.0}, "threshold": 0.8, "top_k": 10,
           "top_k_share": 0.0, "positive_share": 1.0}
    qs = datagen.make_queries(4, 1, 8, mix, order)
    datagen.plant(blocks, counts, layout, order, qs, cfg["kmer"])
    r = ref.Reference(counts, blocks, kmer=cfg["kmer"], fpr=cfg["fpr"])
    for q in qs:
        s, n = r.scores(q.codes)
        assert s[q.origin] == n
