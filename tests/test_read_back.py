"""One device-to-host read-back per scored batch.

The paged executors join their per-shard slot scores on the device and
copy the joined array to the host once (``read_back``). The answer must
be the very array the per-shard copies gave, concatenated on the host:
the same int32 slot scores in the same order, for a single query (1-D
parts) and for a padded batch ([Q, slots] parts), raw and dict-coded.
"""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import IndexParams
from repro.core import codec as _codec
from repro.core.arena import DeviceTileCache
from repro.core.query import (compile_pattern, make_batch_score_fn,
                              make_comp_batch_score_fn, make_comp_dedup_score_fn,
                              make_comp_score_fn, make_dedup_score_fn,
                              make_score_fn, pad_term_batch, plan_shards,
                              read_back, run_paged, run_paged_compressed,
                              run_paged_dedup)
from repro.data import make_corpus
from repro.index import build_compact_streaming
from repro.obs.trace import BatchRecorder

PARAMS = IndexParams(n_hashes=1, fpr=0.03, kmer=15)
TERM_PAD = 64


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    """A raw store and a dict-coded one, each of several shards; every
    document repeats, so whole signature rows recur for the dictionary."""
    c = make_corpus(24, k=15, mean_length=160, min_length=120, seed=3)
    terms = [c.doc_terms[i % 24] for i in range(24 * 8)]
    root = tmp_path_factory.mktemp("read-back-stores")
    raw, _ = build_compact_streaming(terms, root / "raw", PARAMS,
                                     block_docs=32, blocks_per_shard=1)
    comp, _ = build_compact_streaming(terms, root / "comp", PARAMS,
                                      block_docs=64, blocks_per_shard=1,
                                      codec="rowdict")
    assert raw.storage.n_shards > 2 and comp.storage.n_shards > 2
    assert any(comp.storage.shard_codec(s) in _codec.DICT_CODECS
               for s in range(comp.storage.n_shards))
    return c, {"raw": raw, "comp": comp}


def _batch(c, q: int):
    """``q`` patterns' terms padded to one length, in a batch of the next
    power of two (empty rows past ``q``), as the server pads it."""
    pats = [c.documents[i][5 + 3 * i: 95 + 3 * i] for i in range(q)]
    q_pad = 1 << (q - 1).bit_length()
    buf, ells = pad_term_batch(
        [compile_pattern(p, PARAMS) for p in pats], TERM_PAD)
    terms = np.zeros((q_pad,) + buf.shape[1:], dtype=np.uint32)
    terms[:q] = buf
    n_valid = np.zeros(q_pad, dtype=np.int32)
    n_valid[:q] = ells
    return terms, n_valid


def _recording(fn, parts):
    """``fn``, keeping each shard's device output."""
    def call(*a):
        out = fn(*a)
        parts.append(out)
        return out
    return call


def _run(executor, index, terms, n_valid, parts, rec):
    """Score through one executor, recording its per-shard outputs."""
    tiles = DeviceTileCache(index.storage)
    plans = plan_shards(index.layout, index.storage.shard_row_starts)
    args = [(sp.shard, jnp.asarray(sp.row_offset),
             jnp.asarray(sp.block_width)) for sp in plans]
    single = terms.shape[0] == 1 and executor != "dedup"
    if single:
        dev = (jnp.asarray(terms[0]), jnp.int32(n_valid[0]))
    else:
        dev = (jnp.asarray(terms), jnp.asarray(n_valid))
    if executor == "paged":
        fn = (make_score_fn if single else make_batch_score_fn)(1)
        return run_paged(tiles, args, _recording(fn, parts), *dev, rec=rec)
    if executor == "compressed":
        fn = (make_score_fn if single else make_batch_score_fn)(1)
        fc = (make_comp_score_fn if single else make_comp_batch_score_fn)(1)
        return run_paged_compressed(tiles, args, _recording(fn, parts),
                                    _recording(fc, parts), *dev, rec=rec)
    return run_paged_dedup(tiles, plans, _recording(make_dedup_score_fn(),
                                                    parts),
                           terms, n_valid,
                           fn_comp=_recording(make_comp_dedup_score_fn(),
                                              parts), rec=rec)


@pytest.mark.parametrize("q", [1, 3])
@pytest.mark.parametrize("executor", ["paged", "compressed", "dedup"])
def test_joined_read_back_equals_the_per_shard_copies(stores, executor, q):
    c, idx = stores
    index = idx["comp" if executor != "paged" else "raw"]
    terms, n_valid = _batch(c, q)
    parts: list = []
    rec = BatchRecorder(0, time.monotonic)
    got = _run(executor, index, terms, n_valid, parts, rec)

    assert len(parts) == index.storage.n_shards > 2
    want = np.concatenate([np.asarray(p) for p in parts], axis=-1)
    assert got.dtype == want.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    assert got.shape[-1] == index.layout.n_slots
    assert got.ndim == (1 if q == 1 and executor != "dedup" else 2)
    assert want.any()                     # the queries hit something
    (back,) = [s for s in rec.spans if s.name == "readback"]
    assert back.tags["copies"] == 1


def test_one_part_is_read_back_as_it_is():
    """A lone part (dense scoring) is copied without a join."""
    part = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    rec = BatchRecorder(5, time.monotonic)
    got = read_back([part], rec)
    np.testing.assert_array_equal(got, np.arange(12).reshape(3, 4))
    (back,) = rec.spans
    assert back.tags == {"copies": 1, "batch": 5}
    # untraced: the same answer, nothing recorded
    np.testing.assert_array_equal(read_back([part, part]),
                                  np.concatenate([got, got], axis=-1))
