"""Seeded data for one run: the archive's arena bits, the queries, and the
store the program serves.

No k-mer pipeline and no device Bloom build: every document is a column
of term-count-driven noise, which is what a one-hash Bloom filter of its
k-mers looks like from the outside.

- Term counts are the quantiles of a log-normal fixed by the
  configuration, in a fixed document order, so every seed has the same
  compact layout (the program's ``plan_compact_layout``) and the same
  padded tiles.
- Each column of a block of width m gets the density that inserting n
  distinct k-mers with one hash gives, 1 - exp(-n/m), drawn on the device
  from the seed in one compiled shape.
- ``--seed`` also draws the queries. A positive is a random DNA string
  assigned to a document, the blocks taken in turn; its k-mers are set in
  that document's column before the store is written, so its containment
  there is complete.
- The blocks are written as a v2 shard store with ``ShardStoreWriter`` and
  opened with ``open_store``, the way a deployment opens a built index.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import statistics
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

import reference as ref

ROW_CHUNK = 1 << 16        # rows drawn per device call (one compiled shape)


# -- documents and layout -----------------------------------------------------

def term_counts(config: dict) -> np.ndarray:
    """Distinct k-mers per document: log-normal quantiles (mean
    ``mean_terms``, shape ``sigma``), clipped below at ``min_terms``,
    dealt to document ids by a permutation fixed by ``order_seed``."""
    n, sigma = int(config["n_docs"]), float(config["sigma"])
    mu = np.log(float(config["mean_terms"])) - sigma ** 2 / 2
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    counts = np.maximum(np.exp(mu + sigma * z).astype(np.int64),
                        int(config["min_terms"]))
    perm = np.random.default_rng(int(config["order_seed"])).permutation(n)
    return counts[perm]


def layout_of(counts: np.ndarray, config: dict):
    """The program's compact layout for these counts, and its params."""
    from repro.core import IndexParams
    from repro.core.index import plan_compact_layout
    params = IndexParams(n_hashes=1, fpr=float(config["fpr"]),
                         kmer=int(config["kmer"]))
    layout, order = plan_compact_layout(counts, params, ref.BLOCK_DOCS)
    return layout, order, params


def layout_numbers(layout) -> dict:
    """What a layout costs: logical arena bytes, the tallest block, and
    the tiles the device cache pads every shard to."""
    tall = int(layout.block_width.max())
    return {"arena_bytes": int(layout.total_rows) * ref.DOC_WORDS * 4,
            "tallest_block_rows": tall,
            "padded_tile_bytes": tall * ref.DOC_WORDS * 4 * layout.n_blocks}


def _seed32(seed: int, stream: int) -> int:
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0]
               & 0x7FFFFFFF)


# -- arena bits ---------------------------------------------------------------

@functools.cache
def _bit_rows_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bit_rows(key, chunk, thresh):
        """uint32 [ROW_CHUNK, 32]: bit c of row r set with probability
        thresh[c] / 65536."""
        r = jax.random.bits(jax.random.fold_in(key, chunk),
                            (ROW_CHUNK, ref.BLOCK_DOCS), jnp.uint16)
        on = (r.astype(jnp.uint32) < thresh[None, :]).astype(jnp.uint32)
        on = on.reshape(ROW_CHUNK, ref.DOC_WORDS, 32)
        return jnp.sum(on << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                       dtype=jnp.uint32)

    return bit_rows


def column_thresholds(counts: np.ndarray, layout, order: np.ndarray
                      ) -> list[np.ndarray]:
    """Per block, each column's set-bit probability as a 16-bit
    threshold: 1 - exp(-n/m) for the document in that slot."""
    out = []
    for b in range(layout.n_blocks):
        n = np.zeros(ref.BLOCK_DOCS)
        ids = order[b * ref.BLOCK_DOCS:(b + 1) * ref.BLOCK_DOCS]
        n[:ids.size] = counts[ids]
        p = -np.expm1(-n / float(layout.block_width[b]))
        out.append(np.round(p * 65536).astype(np.uint32))
    return out


def arena_blocks(seed: int, counts, layout, order) -> list[np.ndarray]:
    """The arena as host blocks uint32 [width_b, 32], drawn on the device
    a chunk at a time and brought to the host at once, so that the draw
    holds one chunk of device memory and leaves the served path's peak
    as its own."""
    import jax
    import jax.numpy as jnp
    fn = _bit_rows_fn()
    key = jax.random.key(_seed32(seed, 0), impl="rbg")
    blocks, chunk = [], 0
    for b, thresh in enumerate(column_thresholds(counts, layout, order)):
        w = int(layout.block_width[b])
        t = jnp.asarray(thresh)
        parts = []
        for _ in range(-(-w // ROW_CHUNK)):
            parts.append(np.asarray(fn(key, chunk, t)))
            chunk += 1
        host = np.concatenate(parts)[:w]
        blocks.append(np.ascontiguousarray(host))
    return blocks


# -- queries ------------------------------------------------------------------

@dataclasses.dataclass
class Query:
    codes: np.ndarray        # uint8 2-bit bases
    threshold: float
    top_k: int               # 0 = threshold query
    origin: int              # planted document, -1 for a random string
    terms: np.ndarray | None = None   # distinct packed k-mers, as sent


def _apportion(n: int, shares: list[float]) -> list[int]:
    """Exact counts for ``shares`` of ``n`` (largest remainder)."""
    raw = np.asarray(shares, float) / float(sum(shares)) * n
    got = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - got), kind="stable")[: n - got.sum()]:
        got[i] += 1
    return [int(g) for g in got]


def _interleave(n: int, shares: list[float], rng) -> np.ndarray:
    """Class per position such that every prefix holds each class within
    one of its share (the class furthest behind goes next), rotated by a
    seeded offset: a run that stops early has served the same mix."""
    p = np.asarray(shares, float) / float(sum(shares))
    left = np.asarray(_apportion(n, list(p)), float)
    got = np.zeros(p.size)
    out = np.empty(n, np.int64)
    for i in range(n):
        behind = np.where(got < left, (i + 1) * p - got, -np.inf)
        c = int(np.argmax(behind))
        out[i] = c
        got[c] += 1
    return np.roll(out, int(rng.integers(0, max(1, n))))


def make_queries(seed: int, stream: int, n: int, mix: dict,
                 order: np.ndarray) -> list[Query]:
    """``n`` distinct random queries with exact shares of each length, of
    top-k selection and of planted positives. Lengths and positives are
    interleaved so that every prefix holds their shares; top-k is dealt
    in a seeded order. ``order`` is the layout's document per slot: the
    positives are planted in the blocks in turn, each in a seeded
    document of its block, so that every run of as many positives as
    there are blocks reaches every block and every seed, and every
    batch, carries the same work."""
    rng = np.random.default_rng([seed, stream])
    lengths = [int(k) for k in mix["lengths"]]
    qlen = np.asarray(lengths)[_interleave(
        n, [mix["lengths"][str(k)] for k in lengths], rng)]
    n_top = _apportion(n, [mix["top_k_share"], 1 - mix["top_k_share"]])[0]
    top = rng.permutation(np.arange(n) < n_top)
    pos = _interleave(n, [mix["positive_share"],
                          1 - mix["positive_share"]], rng) == 0
    n_blocks = -(-order.size // ref.BLOCK_DOCS)
    blk = (np.arange(n) + rng.integers(n_blocks)) % n_blocks
    lo = blk * ref.BLOCK_DOCS
    size = np.minimum(lo + ref.BLOCK_DOCS, order.size) - lo
    docs = order[lo + (rng.random(n) * size).astype(np.int64)]
    out, j = [], 0
    for i in range(n):
        codes = rng.integers(0, 4, size=int(qlen[i]), dtype=np.uint8)
        origin = -1
        if pos[i]:
            origin, j = int(docs[j]), j + 1
        out.append(Query(codes, float(mix["threshold"]),
                         int(mix["top_k"]) if top[i] else 0, origin))
    return out


def compile_terms(queries: list[Query], kmer: int) -> None:
    """Each query's distinct packed k-mers in first-seen order, as a
    client compiles them before sending."""
    from repro.core.query import compile_pattern
    from repro.core import IndexParams
    params = IndexParams(n_hashes=1, kmer=kmer)
    for q in queries:
        q.terms = compile_pattern(q.codes, params)


def plant(blocks: list[np.ndarray], counts, layout, order,
          queries: list[Query], kmer: int) -> int:
    """Set every positive's k-mers in its document's column; returns the
    number of bits newly set."""
    slot = np.empty(order.shape[0], np.int64)
    slot[order] = np.arange(order.shape[0])
    newly = 0
    for q in queries:
        if q.origin < 0:
            continue
        s = int(slot[q.origin])
        b, col = divmod(s, ref.BLOCK_DOCS)
        h = ref.hash_terms(ref.distinct_terms(q.codes, kmer)).astype(np.int64)
        rows = h % int(layout.block_width[b])
        word, bit = divmod(col, 32)
        mask = np.uint32(1 << bit)
        newly += int(np.count_nonzero(blocks[b][rows, word] & mask == 0))
        blocks[b][rows, word] |= mask
    return newly


# -- the store ----------------------------------------------------------------

def write_store(path, blocks: list[np.ndarray], layout, params,
                store: dict):
    """One shard per block through the program's writer (``store`` names
    its codec); returns the index as ``open_store`` gives it."""
    from repro.core import BitSlicedIndex
    from repro.core.store import ShardStoreWriter, open_store
    writer = ShardStoreWriter(path, layout, params, blocks_per_shard=1,
                              codec=store["codec"])
    # shards are independent; the program's streaming builder writes them
    # from a thread pool the same way
    with ThreadPoolExecutor(max_workers=len(blocks)) as pool:
        list(pool.map(writer.write_shard, range(len(blocks)), blocks))
    writer.finalize()
    _write_through(path)
    layout2, storage, params2 = open_store(path)
    return BitSlicedIndex(layout=layout2, storage=storage, params=params2)


def _write_through(path) -> None:
    """fsync every file of the store, so that the kernel's write-back of
    its dirty pages runs in set-up and not inside the measured window (a
    deployment opens an index written long before)."""
    for f in sorted(Path(path).iterdir()):
        fd = os.open(f, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
