"""Query processing (paper Fig. 3): HASH -> GATHER rows -> AND -> ADD -> select.

The engine consumes packed terms (uint32 [L, 2]) with a validity count,
produces per-document scores, and applies the coverage threshold K — the
fraction of the query's distinct q-grams that must hit a document for it to
be reported. Single queries and padded batches are supported; scoring runs
through the Pallas kernels (repro.kernels.ops) with a pure-jnp method for
oracle comparisons.

Planning (term compilation, padding, threshold math, hit selection) is kept
in PURE module-level functions so the synchronous QueryEngine and the
serving subsystem (repro.serve) share one implementation — the server's
micro-batcher pads with ``pad_term_batch`` and its planner keys buckets off
``padded_len``, so batched results are byte-identical to ``search``.

Out-of-core indexes (storage with more than one shard — MappedArena over a
cobs-jax-v2 store) run PAGED execution: ``plan_shards`` rebases each
shard's block row offsets to the shard's first row, the engine pages one
shard tile at a time to device (through a DeviceTileCache), scores it with
the same kernels, and the score-combine step concatenates per-shard slot
scores in block order — blocks partition the document slots, so the
combine is exact and results are bit-identical to dense execution.

Distribution (mesh-sharded arenas, psum'd partial scores, distributed top-k)
lives in repro.index.distributed and reuses the same planning functions.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from . import codec as _codec
from . import dna, hashing
from .arena import ArenaLayout, DeviceTileCache, common_tile_rows
from .index import BitSlicedIndex, IndexParams
from ..kernels import ops


# --------------------------------------------------------------------------
# Pure planning helpers (no device state; shared by engine / server / dist)
# --------------------------------------------------------------------------

def plan_rows(
    hashes: jnp.ndarray, row_offset: jnp.ndarray, block_width: jnp.ndarray
) -> jnp.ndarray:
    """Map term hashes to arena rows, per block.

    hashes: uint32 [..., k]; returns int32 [..., k, n_blocks] — the paper's
    'large output range then modulo per sub-index' addressing."""
    w = block_width.astype(jnp.uint32)
    rows = hashes[..., None] % w
    return (rows + row_offset.astype(jnp.uint32)).astype(jnp.int32)


@dataclass(frozen=True)
class ShardPlan:
    """Per-shard query addressing: the shard's blocks with row offsets
    rebased to the shard's first arena row. Scoring shard ``shard`` with
    (row_offset, block_width) against its device tile yields the slot
    scores of blocks [block_start, block_end) — per-shard outputs
    concatenated in shard order ARE the global slot scores."""
    shard: int
    block_start: int
    block_end: int
    row_offset: np.ndarray   # int32 [nb_s], shard-local
    block_width: np.ndarray  # int32 [nb_s]


def plan_shards(layout: ArenaLayout, shard_row_starts: np.ndarray
                ) -> list[ShardPlan]:
    """Map every storage shard to the blocks it holds (pure; shared by the
    QueryEngine and the serving planner). The all-shards special case of
    ``plan_shards_subset`` — one copy of the rebasing arithmetic."""
    return plan_shards_subset(layout, shard_row_starts,
                              range(len(shard_row_starts) - 1))


def plan_shards_subset(layout: ArenaLayout, global_row_starts: np.ndarray,
                       shard_ids) -> list[ShardPlan]:
    """Per-placement variant of ``plan_shards``: addressing for a SUBSET of
    a store's shards, as held by one host's sub-store view.

    ``global_row_starts`` are the parent store's shard boundaries and
    ``shard_ids`` the (sorted) global manifest rows this host holds.
    ``ShardPlan.shard`` is the LOCAL tile index into the sub-store's
    storage; block ranges stay GLOBAL, so a worker's per-shard slot scores
    land at global slots [block_start * block_docs, block_end * block_docs)
    — the frontend's gather is exact by construction."""
    ranges = layout.shard_blocks(np.asarray(global_row_starts, np.int64))
    plans = []
    for local, g in enumerate(shard_ids):
        b0, b1 = ranges[g]
        base = np.int32(global_row_starts[g])
        plans.append(ShardPlan(
            shard=local, block_start=b0, block_end=b1,
            row_offset=layout.row_offset[b0:b1] - base,
            block_width=layout.block_width[b0:b1]))
    return plans


def compile_pattern(pattern, params: IndexParams) -> np.ndarray:
    """Pattern (DNA string or uint8 code array) -> distinct packed terms
    [ell, 2] under the index's k-mer parameters. Host-side and pure."""
    codes = dna.encode_dna(pattern) if isinstance(pattern, str) else pattern
    return dna.unique_terms(
        dna.pack_kmers(codes, params.kmer, params.canonical))


def padded_len(n_terms: int, term_pad: int) -> int:
    """Smallest multiple of ``term_pad`` holding ``n_terms`` (>= term_pad).

    This is the jit-cache key of a query's shape: every query padded to the
    same length shares one compiled scoring executable, which is what the
    serving batcher's shape buckets are built on."""
    return max(term_pad,
               ((n_terms + term_pad - 1) // term_pad) * term_pad)


def pad_terms(terms: np.ndarray, term_pad: int) -> tuple[np.ndarray, int]:
    """Packed terms [L, 2] -> (zero-padded [padded_len, 2], L)."""
    L = terms.shape[0]
    out = np.zeros((padded_len(L, term_pad), 2), dtype=np.uint32)
    out[:L] = terms
    return out, L


def pad_term_batch(term_sets: list[np.ndarray], term_pad: int
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Term sets -> (shared-padding buffer [Q, pad, 2], ells int32 [Q])."""
    ells = np.array([t.shape[0] for t in term_sets], dtype=np.int32)
    pad = padded_len(int(ells.max(initial=1)), term_pad)
    buf = np.zeros((len(term_sets), pad, 2), dtype=np.uint32)
    for i, t in enumerate(term_sets):
        buf[i, : t.shape[0]] = t
    return buf, ells


def coverage_cutoff(threshold: float, n_terms: int) -> int:
    """The paper's K-threshold: minimum score = ceil(threshold * ell),
    never below 1 (a zero cutoff would report every document)."""
    return max(1, math.ceil(threshold * n_terms))


def select_hits(scores: np.ndarray, n_terms: int, threshold: float
                ) -> "SearchResult":
    """Apply the coverage cutoff and order hits best-first (stable)."""
    if n_terms == 0:
        return SearchResult(np.zeros(0, np.int32), np.zeros(0, np.int32), 0, 0)
    cut = coverage_cutoff(threshold, n_terms)
    hits = np.nonzero(scores >= cut)[0]
    order = np.argsort(-scores[hits], kind="stable")
    return SearchResult(hits[order].astype(np.int32),
                        scores[hits][order].astype(np.int32), n_terms, cut)


def select_top_k(scores: np.ndarray, n_terms: int, k: int) -> "SearchResult":
    """Best-k documents by score (the paper's top-k selection). The
    reported threshold is the k-th best score — the effective cutoff.

    Stable sort (not argpartition) so ties — including at the k boundary —
    resolve to ascending doc id deterministically."""
    k = min(k, scores.shape[0])
    if k == 0:
        return SearchResult(np.zeros(0, np.int32), np.zeros(0, np.int32),
                            n_terms, 0)
    order = np.argsort(-scores, kind="stable")[:k]
    top = scores[order].astype(np.int32)
    return SearchResult(order.astype(np.int32), top, n_terms, int(top[-1]))


def run_paged(tiles, shard_args, fn, *args) -> list[np.ndarray]:
    """Dispatch ``fn`` once per shard tile with double-buffered prefetch,
    shared by the QueryEngine and the serving QueryServer.

    While shard i's scoring call is in flight (jax dispatch is async),
    shard i+1 stages host->device through ``tiles.prefetch`` — transfer
    overlaps compute. Results are forced to host only after every dispatch
    is issued. ``shard_args`` is [(shard, row_offset_dev, block_width_dev)]
    and ``fn(tile, offs, widths, *args)`` the planned scorer."""
    parts = []
    for i, (s, offs, widths) in enumerate(shard_args):
        tile = tiles.get(s)
        out = fn(tile, offs, widths, *args)
        if i + 1 < len(shard_args):
            tiles.prefetch(shard_args[i + 1][0])
        parts.append(out)
    return [np.asarray(p) for p in parts]


def run_paged_compressed(tiles, shard_args, fn_raw, fn_comp, *args
                         ) -> list[np.ndarray]:
    """``run_paged`` with per-shard codec dispatch: dict-coded shards stage
    their COMPRESSED (dict, refs) form to device and score through
    ``fn_comp(dict_rows, refs, offs, widths, *args)`` — the fused-decode
    kernels — while raw shards take ``fn_raw`` unchanged. Prefetch is
    codec-aware, so the overlap stages the form that will actually be
    scored. Outputs are bit-identical to the all-raw path."""
    storage = tiles.storage
    comp = [storage.shard_codec(s) in _codec.DICT_CODECS
            for (s, _, _) in shard_args]
    parts = []
    for i, (s, offs, widths) in enumerate(shard_args):
        if comp[i]:
            dict_rows, refs = tiles.get_compressed(s)
            out = fn_comp(dict_rows, refs, offs, widths, *args)
        else:
            out = fn_raw(tiles.get(s), offs, widths, *args)
        if i + 1 < len(shard_args):
            nxt = shard_args[i + 1][0]
            (tiles.prefetch_compressed if comp[i + 1]
             else tiles.prefetch)(nxt)
        parts.append(out)
    return [np.asarray(p) for p in parts]


# --------------------------------------------------------------------------
# Batched row dedup (the serving hot-path bandwidth optimization)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class DedupBatchPlan:
    """Unique-row addressing for one micro-batch.

    Queries in a batch share rows heavily (overlapping k-mers), but the
    fused multi-query kernel re-streams an arena row per (query, block,
    term) cell. This plan collapses the batch's (block, row) pairs into
    ``uniq_rows`` (each arena row listed ONCE, padded to a power of two so
    jit entries stay bounded) plus the ``indir`` indirection that maps
    every cell back to its unique row — the kernels then gather U rows
    from the arena instead of Q*nb*L.

    For k>1 indexes the unit of dedup is the (row-set, AND) TUPLE: one
    term addresses k rows whose AND is scored, so ``uniq_rows`` is
    [U_pad, k] and equal row-sets across cells collapse to one k-row
    gather + AND (``np.unique(axis=0)`` over tuples).
    """
    uniq_rows: np.ndarray   # int32 [U_pad] (k=1) or [U_pad, k] (0-padded)
    indir: np.ndarray       # int32 [Q, nb, L] -> index into uniq_rows
    mask: np.ndarray        # int32 [Q, nb, L] (1 = live term)
    n_unique: int           # live unique rows/row-sets (<= U_pad)
    n_gathers: int          # live (query, block, term) cells

    @property
    def dedup_rate(self) -> float:
        """Fraction of the fused path's row gathers the dedup path saves:
        1 - unique/total. 0 = fully disjoint batch, ->1 = heavy sharing."""
        if self.n_gathers == 0:
            return 0.0
        return 1.0 - self.n_unique / self.n_gathers


def _pad_unique(n: int) -> int:
    """Unique-row count -> padded buffer length: power of two (bounds the
    jit cache at log2(max U) entries per bucket), floor 8 (sublane)."""
    return max(8, 1 << max(0, int(n) - 1).bit_length())


def plan_dedup_batch(terms: np.ndarray, n_valid: np.ndarray,
                     row_offset: np.ndarray, block_width: np.ndarray,
                     n_hashes: int = 1) -> DedupBatchPlan:
    """Host-side dedup planning for one padded micro-batch.

    terms uint32 [Q, L, 2]; n_valid int32 [Q]; (row_offset, block_width)
    the addressing of the arena (or of ONE shard, already rebased — the
    paged path plans per shard). Pure numpy: hashing reuses the
    bit-identical host mirror of the device hash, so the rows the fused
    kernel would gather and the rows planned here are the same set.

    k=1 dedups single rows; k>1 dedups (row-set) tuples — every cell's k
    hash rows, deduped as a unit via ``np.unique(axis=0)``, so the device
    gathers + ANDs each distinct row-set once (see DedupBatchPlan).
    """
    terms = np.asarray(terms)
    n_valid = np.asarray(n_valid, dtype=np.int32)
    Q, L = terms.shape[0], terms.shape[1]
    k = int(n_hashes)
    w = np.asarray(block_width).astype(np.uint32)
    off = np.asarray(row_offset).astype(np.uint32)
    valid = np.arange(L, dtype=np.int32)[None, :] < n_valid[:, None]
    if k == 1:
        h = hashing.hash_terms_np(terms, 1)[..., 0]           # [Q, L]
        rows = (h[..., None] % w[None, None, :] + off)        # [Q, L, nb]
        rows = np.swapaxes(rows, 1, 2).astype(np.int64)       # [Q, nb, L]
        cell_shape = rows.shape
        mask = np.broadcast_to(valid[:, None, :], cell_shape)
        live = rows[mask]                                     # [N]
        uniq, inv = np.unique(live, return_inverse=True)
        uniq_pad = np.zeros(_pad_unique(uniq.size), dtype=np.int32)
        uniq_pad[: uniq.size] = uniq
    else:
        h = hashing.hash_terms_np(terms, k)                   # [Q, L, k]
        rows = (h[..., None] % w + off)                       # [Q, L, k, nb]
        rows = np.transpose(rows, (0, 3, 1, 2)).astype(np.int64)  # [Q,nb,L,k]
        cell_shape = rows.shape[:3]
        mask = np.broadcast_to(valid[:, None, :], cell_shape)
        live = rows[mask]                                     # [N, k]
        uniq, inv = np.unique(live, axis=0, return_inverse=True)
        uniq_pad = np.zeros((_pad_unique(uniq.shape[0]), k), dtype=np.int32)
        uniq_pad[: uniq.shape[0]] = uniq
    indir = np.zeros(cell_shape, dtype=np.int32)
    indir[mask] = np.asarray(inv).reshape(-1).astype(np.int32)
    n_uniq = int(uniq.shape[0])
    return DedupBatchPlan(uniq_rows=uniq_pad, indir=indir,
                          mask=mask.astype(np.int32),
                          n_unique=n_uniq, n_gathers=int(live.shape[0]))


def make_dedup_score_fn(word_block: int | None = None):
    """Returns score(arena, uniq_rows [U], indir [Q,nb,L], mask [Q,nb,L])
    -> int32 [Q, n_slots] — the two-kernel dedup path (unique-row gather +
    indirected Harley-Seal accumulate). Bit-identical to the fused
    multi-query kernel on the expanded indices."""

    def score(arena, uniq_rows, indir, mask):
        return ops.bitslice_lookup_score_dedup(arena, uniq_rows, indir,
                                               mask, word_block=word_block)

    return score


def make_comp_dedup_score_fn(word_block: int | None = None):
    """Compressed twin of ``make_dedup_score_fn``: score(dict_rows, refs,
    uniq_rows, indir, mask) -> int32 [Q, n_slots], decoding each unique
    row (or AND'd row-set) out of the shard dict inside the gather kernel."""

    def score(dict_rows, refs, uniq_rows, indir, mask):
        return ops.bitslice_lookup_score_dedup_comp(
            dict_rows, refs, uniq_rows, indir, mask, word_block=word_block)

    return score


def run_paged_dedup(tiles, shard_plans: list[ShardPlan], fn,
                    terms: np.ndarray, n_valid: np.ndarray,
                    n_hashes: int = 1, fn_comp=None) -> np.ndarray:
    """Dedup-scored batch across shard tiles (one tile = the whole arena
    for dense storage): per shard, plan the unique-row set against the
    shard's REBASED addressing, score through ``fn`` (from
    ``make_dedup_score_fn``), prefetch the next tile while the dispatch is
    in flight, and concatenate per-shard slot scores — the dedup analogue
    of ``run_paged``.

    With ``fn_comp`` (from ``make_comp_dedup_score_fn``) dict-coded shards
    stage compressed and score through the fused-decode kernels; raw
    shards keep ``fn``. ``n_hashes`` > 1 plans row-SET dedup."""
    storage = tiles.storage
    comp = [fn_comp is not None
            and storage.shard_codec(sp.shard) in _codec.DICT_CODECS
            for sp in shard_plans]
    parts = []
    for i, sp in enumerate(shard_plans):
        dp = plan_dedup_batch(terms, n_valid, sp.row_offset, sp.block_width,
                              n_hashes=n_hashes)
        planned = (jnp.asarray(dp.uniq_rows), jnp.asarray(dp.indir),
                   jnp.asarray(dp.mask))
        if comp[i]:
            dict_rows, refs = tiles.get_compressed(sp.shard)
            out = fn_comp(dict_rows, refs, *planned)
        else:
            out = fn(tiles.get(sp.shard), *planned)
        if i + 1 < len(shard_plans):
            nxt = shard_plans[i + 1].shard
            (tiles.prefetch_compressed if comp[i + 1]
             else tiles.prefetch)(nxt)
        parts.append(out)
    return np.concatenate([np.asarray(p) for p in parts], axis=1)


# --------------------------------------------------------------------------
# Pruned scoring (branch-and-bound over the coverage threshold)
# --------------------------------------------------------------------------
#
# The fused path scores every (query, block, term) cell before the threshold
# is consulted. The pruned path executes terms in CHUNKS (rarest first when
# the store recorded popcount stats) and keeps a per-(query, block) running
# count on device; after each chunk any block whose best possible final
# score — running max + terms remaining — cannot reach the required cutoff
# is dropped. Work for dropped blocks (host row reads, device staging,
# kernel cells) is never issued, and a shard whose every block is dropped
# is never touched again. Partial sums in dropped blocks stay strictly
# below the cutoff, so reported hits and scores are bit-identical to the
# exhaustive engine.
#
# I/O model: instead of staging whole shard tiles, each (chunk, shard)
# visit host-gathers only the chunk's unique touched rows out of the mmap
# (dict-coded shards gather decoded rows through their dictionary) and
# stages that small matrix. When a shard's cumulative gathered bytes
# approach its tile size — dense corpora, long queries, low thresholds —
# the executor PROMOTES the shard: the full tile is staged once through
# the DeviceTileCache (prefetched ahead at half the threshold so the H2D
# copy overlaps the remaining gather-fed chunks) and later chunks read it
# on device — the fused in-kernel gather for k=1, a device gather+AND of
# the chunk's unique row sets for k>1. Pruned shards never promote, so
# the tile cache records zero faults for them — "tiles skipped" is
# directly observable.


@dataclass
class PruneStats:
    """Work accounting for one pruned batch (mutated in place).

    ``bytes_read`` is the headline number: host arena bytes actually read
    (row gathers + promoted tile stagings) — the quantity the exhaustive
    path pays ``sum(shard_nbytes)`` for."""
    blocks_total: int = 0        # live (query, block) cells at entry
    blocks_pruned: int = 0       # cells dropped before the final chunk
    chunks: int = 0              # term chunks executed
    shard_visits: int = 0        # (chunk, shard) visits dispatched
    shard_visits_skipped: int = 0  # visits skipped (no live cell)
    tiles_promoted: int = 0      # shards escalated to full-tile staging
    kernel_dispatches: int = 0
    bytes_gathered: int = 0      # host bytes read by row gathers
    bytes_tile_staged: int = 0   # bytes of promoted full tiles

    @property
    def bytes_read(self) -> int:
        return self.bytes_gathered + self.bytes_tile_staged

    @property
    def prune_rate(self) -> float:
        if self.blocks_total == 0:
            return 0.0
        return self.blocks_pruned / self.blocks_total

    def merge(self, other: "PruneStats") -> None:
        """Accumulate another batch's counters (serving aggregates)."""
        for f in ("blocks_total", "blocks_pruned", "chunks", "shard_visits",
                  "shard_visits_skipped", "tiles_promoted",
                  "kernel_dispatches", "bytes_gathered", "bytes_tile_staged"):
            setattr(self, f, getattr(self, f) + getattr(other, f))


def order_terms_rarest(storage, shard_plans: list[ShardPlan],
                       terms: np.ndarray, n_valid: np.ndarray,
                       n_hashes: int = 1, max_blocks: int = 8) -> np.ndarray:
    """Per-query term execution order for pruned scoring: int32 [Q, L]
    permutation, valid terms first, rarest first.

    Rare terms kill blocks early — a block missing a rare term loses score
    headroom immediately — so ascending estimated popcount maximizes
    early-exit leverage. The estimate samples up to ``max_blocks`` blocks
    spread over the arena and sums each term's row popcounts there (min
    over the k hash rows: a term's hits need all k bits), read from the
    store's popcount sidecars. Stores without stats (pre-v2 or external
    arenas) fall back to natural order — the executor stays correct, just
    prunes later."""
    terms = np.asarray(terms)
    n_valid = np.asarray(n_valid, dtype=np.int32)
    Q, L = terms.shape[0], terms.shape[1]
    natural = np.broadcast_to(np.arange(L, dtype=np.int32), (Q, L)).copy()
    has = getattr(storage, "has_popcounts", None)
    if L == 0 or has is None or not has():
        return natural
    starts = np.asarray(storage.shard_row_starts, dtype=np.int64)
    offs = [sp.row_offset.astype(np.int64) + int(starts[sp.shard])
            for sp in shard_plans]
    wids = [sp.block_width.astype(np.int64) for sp in shard_plans]
    off = np.concatenate(offs)
    wid = np.concatenate(wids)
    sel = np.unique(np.linspace(0, off.shape[0] - 1,
                                min(max_blocks, off.shape[0])).astype(np.int64))
    off, wid = off[sel], wid[sel]
    h = hashing.hash_terms_np(terms, n_hashes).astype(np.int64)  # [Q, L, k]
    rows = h[..., None] % wid + off                       # [Q, L, k, S]
    uniq, inv = np.unique(rows.reshape(-1), return_inverse=True)
    pops = np.asarray(storage.row_popcounts(uniq), dtype=np.int64)
    est = pops[inv].reshape(rows.shape).min(axis=2).sum(axis=-1)  # [Q, L]
    est[np.arange(L, dtype=np.int32)[None, :] >= n_valid[:, None]] = (
        np.iinfo(np.int64).max)                           # padding last
    return np.argsort(est, axis=1, kind="stable").astype(np.int32)


def run_paged_pruned(tiles, shard_plans: list[ShardPlan], terms: np.ndarray,
                     n_valid: np.ndarray, required: np.ndarray,
                     topk: np.ndarray, *, n_hashes: int = 1,
                     chunk_terms: int = 32, word_block: int | None = None,
                     promote_ratio: float = 0.5, order: np.ndarray | None = None,
                     stats: PruneStats | None = None) -> np.ndarray:
    """Branch-and-bound batch scoring across shard tiles.

    terms uint32 [Q, L, 2] (shared padding), n_valid int32 [Q];
    ``required`` int32 [Q] is each query's fixed score cutoff
    (``coverage_cutoff`` — use 0 for top-k queries) and ``topk`` int32 [Q]
    the per-query k (0 = threshold query; the cutoff then tightens
    dynamically to the merged k-th largest running count). Returns int32
    [Q, n_slots] slot scores, bit-identical to ``run_paged`` on every slot
    that can meet its query's cutoff — pruned blocks hold partial sums
    that are provably below it, so downstream ``select_hits`` /
    ``select_top_k`` report identical results.

    ``order`` overrides the term execution order ([Q, L] permutation,
    valid-first); default is ``order_terms_rarest``. ``stats`` (a
    PruneStats) is mutated with work/IO accounting."""
    terms = np.asarray(terms)
    n_valid = np.asarray(n_valid, dtype=np.int32)
    required = np.asarray(required, dtype=np.int64).copy()
    topk = np.asarray(topk, dtype=np.int32)
    if stats is None:
        stats = PruneStats()
    storage = tiles.storage
    Q, L = terms.shape[0], terms.shape[1]
    W = int(storage.shape[1])
    k = int(n_hashes)
    ct = max(1, int(chunk_terms))
    n_sh = len(shard_plans)
    nbs = [sp.row_offset.shape[0] for sp in shard_plans]
    l_max = int(n_valid.max(initial=0))
    if l_max == 0 or Q == 0:
        return np.zeros((Q, sum(nbs) * W * 32), dtype=np.int32)

    if order is None:
        order = order_terms_rarest(storage, shard_plans, terms, n_valid,
                                   n_hashes=k)
    h = hashing.hash_terms_np(terms, k)                   # [Q, L, k]
    h_ord = np.take_along_axis(h, np.asarray(order, np.int64)[..., None],
                               axis=1)

    alive = [np.ones((Q, nb), dtype=bool) for nb in nbs]
    acc = [None] * n_sh
    block_max = [np.zeros((Q, nb), dtype=np.int64) for nb in nbs]
    tk_lower = [None] * n_sh                # [Q, kmax] per shard (top-k)
    promoted = [False] * n_sh
    prefetch_issued = [False] * n_sh        # promotion prefetch dispatched
    resident = [None] * n_sh                # device tile or (dict, refs)
    gathered = [0] * n_sh                   # cumulative gather bytes
    decode_counted = [False] * n_sh
    stats.blocks_total += int(Q * sum(nbs))
    kmax = int(topk.max(initial=0))
    is_topk = topk > 0

    n_chunks = -(-l_max // ct)
    offs = [sp.row_offset.astype(np.uint32) for sp in shard_plans]
    wids = [sp.block_width.astype(np.uint32) for sp in shard_plans]
    codecs = [storage.shard_codec(sp.shard) for sp in shard_plans]

    for c in range(n_chunks):
        stats.chunks += 1
        j0 = c * ct
        h_chunk = np.zeros((Q, ct, k), dtype=h_ord.dtype)
        width = min(ct, L - j0)
        h_chunk[:, :width] = h_ord[:, j0:j0 + width]
        valid_chunk = (j0 + np.arange(ct, dtype=np.int32)[None, :]
                       < n_valid[:, None])                # [Q, ct]
        visited = []
        for s, sp in enumerate(shard_plans):
            live = alive[s][:, :, None] & valid_chunk[:, None, :]  # [Q,nb,ct]
            if not live.any():
                stats.shard_visits_skipped += 1
                continue
            stats.shard_visits += 1
            visited.append(s)
            rows = (h_chunk[..., None] % wids[s] + offs[s])  # [Q, ct, k, nb]
            rows = np.transpose(rows, (0, 3, 1, 2)).astype(np.int64)
            if acc[s] is None:
                acc[s] = ops.chunk_acc_init(Q, nbs[s], W,
                                            word_block=word_block)
            hbm = storage.shard_hbm_nbytes(sp.shard)
            if (not promoted[s] and not prefetch_issued[s]
                    and gathered[s] >= 0.5 * promote_ratio * hbm):
                # Double-buffer the promotion: once gathers cross half the
                # promote threshold the full tile is prefetched (a
                # non-blocking H2D dispatch), so it overlaps the remaining
                # gather-fed chunks and is already resident when the
                # threshold trips — promotion never stalls on a staging.
                prefetch_issued[s] = True
                if codecs[s] in _codec.DICT_CODECS:
                    tiles.prefetch_compressed(sp.shard)
                else:
                    tiles.prefetch(sp.shard)
            if not promoted[s] and gathered[s] >= promote_ratio * hbm:
                promoted[s] = True
                if codecs[s] in _codec.DICT_CODECS:
                    resident[s] = tiles.get_compressed(sp.shard)
                else:
                    resident[s] = tiles.get(sp.shard)
                stats.tiles_promoted += 1
                stats.bytes_tile_staged += hbm
            mask = jnp.asarray(live.astype(np.int32))
            if promoted[s] and k == 1:
                idx = jnp.asarray(rows[..., 0].astype(np.int32))
                if codecs[s] in _codec.DICT_CODECS:
                    d, r = resident[s]
                    acc[s], bmax = ops.bitslice_chunk_score_multi_comp(
                        d, r, idx, mask, acc[s], word_block=word_block)
                else:
                    acc[s], bmax = ops.bitslice_chunk_score_multi(
                        resident[s], idx, mask, acc[s], word_block=word_block)
            elif promoted[s]:
                # k>1 promoted path: the chunk's unique row SETS are still
                # planned host-side (np.unique over live cells), but the
                # rows themselves are gathered and ANDed on DEVICE out of
                # the resident tile — no host arena reads after promotion.
                cells = rows[live]                        # [N, k]
                uniq, inv = np.unique(cells, axis=0, return_inverse=True)
                u_idx = np.zeros((_pad_unique(uniq.shape[0]), k),
                                 dtype=np.int32)
                u_idx[: uniq.shape[0]] = uniq
                if codecs[s] in _codec.DICT_CODECS:
                    d, r = resident[s]
                    mat_dev = ops.gather_and_rows_comp(
                        d, r, jnp.asarray(u_idx))
                else:
                    mat_dev = ops.gather_and_rows(
                        resident[s], jnp.asarray(u_idx))
                indir = np.zeros((Q, nbs[s], ct), dtype=np.int32)
                indir[live] = np.asarray(inv).reshape(-1).astype(np.int32)
                acc[s], bmax = ops.bitslice_chunk_score_dedup(
                    mat_dev, jnp.asarray(indir), mask, acc[s],
                    word_block=word_block)
            else:
                cells = rows[live]                        # [N, k]
                if k == 1:
                    uniq, inv = np.unique(cells[:, 0], return_inverse=True)
                else:
                    uniq, inv = np.unique(cells, axis=0, return_inverse=True)
                if codecs[s] in _codec.DICT_CODECS:
                    d_host, r_host = storage.shard_dict_host(sp.shard)
                    refs = np.asarray(r_host)[uniq]       # [U] or [U, k]
                    mat = np.asarray(d_host[refs.reshape(-1)],
                                     dtype=np.uint32)
                    nread = int(np.unique(refs).size)
                else:
                    if (codecs[s] != _codec.CODEC_RAW
                            and not decode_counted[s]):
                        # non-dict compressed shards decode whole on touch
                        decode_counted[s] = True
                        stats.bytes_gathered += storage.shard_nbytes(sp.shard)
                    host = storage.shard_host(sp.shard)
                    mat = np.asarray(host[uniq.reshape(-1)],
                                     dtype=np.uint32)
                    nread = int(uniq.reshape(-1).size)
                if codecs[s] == _codec.CODEC_RAW:
                    stats.bytes_gathered += nread * W * 4
                elif codecs[s] in _codec.DICT_CODECS:
                    stats.bytes_gathered += nread * W * 4
                gathered[s] += mat.shape[0] * W * 4
                if k > 1:
                    mat = mat.reshape(-1, k, W)
                    anded = mat[:, 0]
                    for i in range(1, k):
                        anded = anded & mat[:, i]
                    mat = anded
                u_pad = np.zeros((_pad_unique(mat.shape[0]), W),
                                 dtype=np.uint32)
                u_pad[: mat.shape[0]] = mat
                indir = np.zeros((Q, nbs[s], ct), dtype=np.int32)
                indir[live] = np.asarray(inv).reshape(-1).astype(np.int32)
                acc[s], bmax = ops.bitslice_chunk_score_dedup(
                    jnp.asarray(u_pad), jnp.asarray(indir), mask, acc[s],
                    word_block=word_block)
            stats.kernel_dispatches += 1
            block_max[s] = np.asarray(bmax).astype(np.int64)

        if c == n_chunks - 1:
            break
        if kmax > 0:
            for s in visited:
                tk_lower[s] = np.asarray(ops.chunk_topk_lower(acc[s], kmax))
            have = [t for t in tk_lower if t is not None]
            if have:
                merged = -np.sort(-np.concatenate(have, axis=1), axis=1)
                for q in np.nonzero(is_topk)[0]:
                    kq = int(topk[q])
                    if merged.shape[1] >= kq:
                        required[q] = max(required[q], int(merged[q, kq - 1]))
        executed = np.minimum(n_valid, (c + 1) * ct).astype(np.int64)
        remaining = n_valid.astype(np.int64) - executed
        any_alive = False
        for s in range(n_sh):
            keep = (block_max[s] + remaining[:, None]) >= required[:, None]
            newly = alive[s] & ~keep
            stats.blocks_pruned += int(newly.sum())
            alive[s] &= keep
            any_alive = any_alive or bool(alive[s].any())
        if not any_alive:
            break

    parts = []
    for s in range(n_sh):
        if acc[s] is None:
            parts.append(np.zeros((Q, nbs[s] * W * 32), dtype=np.int32))
        else:
            parts.append(np.asarray(ops.chunk_acc_scores(acc[s], W)))
    return np.concatenate(parts, axis=1)


# --------------------------------------------------------------------------
# Shard-major streaming execution (the offline bulk lane)
# --------------------------------------------------------------------------
#
# The interactive path is query-major: every micro-batch visits every
# shard, so a bounded DeviceTileCache restages tiles once per batch and a
# Q-query workload split into Q/B batches pays Q/B stagings per shard.
# ``run_shard_major`` inverts the loop for deadline-free bulk jobs: each
# shard tile is staged into HBM ONCE (raw or dict form, the next shard
# prefetched while the current one scores), the ENTIRE query set streams
# against it in query-chunks sized by ``ops.bulk_query_chunk``, and
# per-(query, block) running counts accumulate in the same chunk
# machinery ``run_paged_pruned`` uses — rarest-first term order and the
# threshold early-exit both carry over, so a decontamination scan prunes
# within each shard. Results are written into a persistent host slot
# buffer as each shard completes, which is also the resumability story:
# (out, next_shard, required) round-trips through a checkpoint.


@dataclass
class BulkStats:
    """Work accounting for shard-major bulk sweeps (additive: pass the
    same object across resumed calls for cumulative totals).

    ``bytes_staged`` is the headline number — arena bytes actually
    H2D-staged (raw + dict forms, measured off the tile-cache counters),
    the quantity the interactive path pays once per micro-batch sweep."""
    shards_swept: int = 0        # shards fully scored (all queries)
    tiles_staged: int = 0        # H2D stagings issued (demand + prefetch)
    bytes_staged: int = 0        # bytes those stagings moved
    query_chunks: int = 0        # query slabs dispatched
    kernel_dispatches: int = 0
    blocks_total: int = 0        # (query, block) cells entering sweeps
    blocks_pruned: int = 0       # cells retired by threshold early-exit

    @property
    def prune_rate(self) -> float:
        if self.blocks_total == 0:
            return 0.0
        return self.blocks_pruned / self.blocks_total

    def merge(self, other: "BulkStats") -> None:
        for f in ("shards_swept", "tiles_staged", "bytes_staged",
                  "query_chunks", "kernel_dispatches", "blocks_total",
                  "blocks_pruned"):
            setattr(self, f, getattr(self, f) + getattr(other, f))


def run_shard_major(tiles, shard_plans: list[ShardPlan], terms: np.ndarray,
                    n_valid: np.ndarray, required: np.ndarray,
                    topk: np.ndarray, *, n_hashes: int = 1,
                    chunk_terms: int = 32, query_chunk: int | None = None,
                    word_block: int | None = None,
                    order: np.ndarray | None = None,
                    stats: BulkStats | None = None, start_shard: int = 0,
                    out: np.ndarray | None = None,
                    should_yield=None) -> tuple[np.ndarray, int, np.ndarray]:
    """Shard-major streaming scan: one tile staging amortized over Q.

    terms uint32 [Q, L, 2] (shared padding), n_valid int32 [Q];
    ``required`` int64 [Q] per-query score cutoffs (``coverage_cutoff``,
    0 for top-k) and ``topk`` int32 [Q] per-query k (0 = threshold).
    Returns ``(out, next_shard, required)``: int32 [Q, n_slots] slot
    scores (global block addressing — each shard lands at columns
    [block_start, block_end) * W * 32), the index of the first unswept
    shard, and the tightened cutoffs. Slots in pruned (query, block)
    cells hold partial sums provably below the query's cutoff, so
    ``select_hits`` / ``select_top_k`` downstream are bit-identical to
    the exhaustive engine — same soundness argument as
    ``run_paged_pruned``.

    ``tiles`` is one DeviceTileCache or a sequence parallel to
    ``shard_plans`` (the multi-host sweep walks each shard's primary
    worker's cache). ``should_yield()`` is polled at shard boundaries:
    returning True suspends the sweep — the caller checkpoints
    ``(out, next_shard, required)`` and re-enters with ``start_shard`` /
    ``out`` / the returned cutoffs to resume. Top-k cutoffs tighten after
    every completed shard from the k-th largest accumulated count (a
    sound lower bound: unswept slots are zero, pruned slots are partial),
    so later shards prune harder."""
    plans = list(shard_plans)
    n_sh = len(plans)
    caches = (list(tiles) if isinstance(tiles, (list, tuple))
              else [tiles] * n_sh)
    terms = np.asarray(terms)
    n_valid = np.asarray(n_valid, dtype=np.int32)
    required = np.asarray(required, dtype=np.int64).copy()
    topk = np.asarray(topk, dtype=np.int32)
    if stats is None:
        stats = BulkStats()
    Q, L = terms.shape[0], terms.shape[1]
    k = int(n_hashes)
    ct = max(1, int(chunk_terms))
    if not plans:
        return np.zeros((Q, 0), dtype=np.int32), 0, required
    storage0 = caches[0].storage
    W = int(storage0.shape[1])
    ncols = max(sp.block_end for sp in plans) * W * 32
    if out is None:
        out = np.zeros((Q, ncols), dtype=np.int32)
    l_max = int(n_valid.max(initial=0))
    if Q == 0 or l_max == 0:
        return out, n_sh, required

    if order is None:
        # Popcount estimation uses the first cache's storage and only the
        # plans addressed against it (multi-host sweeps mix storages);
        # the order is a heuristic, correctness never depends on it.
        own = [sp for ca, sp in zip(caches, plans) if ca is caches[0]]
        order = order_terms_rarest(storage0, own, terms, n_valid,
                                   n_hashes=k)
    h = hashing.hash_terms_np(terms, k)                   # [Q, L, k]
    h_ord = np.take_along_axis(h, np.asarray(order, np.int64)[..., None],
                               axis=1)
    n_chunks = -(-l_max // ct)
    is_topk = topk > 0
    any_topk = bool(is_topk.any())

    for si in range(start_shard, n_sh):
        if (should_yield is not None and si > start_shard
                and should_yield()):
            return out, si, required
        sp, cache = plans[si], caches[si]
        storage = cache.storage
        dict_coded = storage.shard_codec(sp.shard) in _codec.DICT_CODECS

        def _staged(cache, fn, *a):
            # Under the cache's own (reentrant) lock so the byte-counter
            # delta can't absorb a concurrent interactive staging — the
            # bulk lane runs unserialized against the scoring workers.
            with cache._lock:
                b0 = cache.raw_bytes_staged + cache.comp_bytes_staged
                r = fn(*a)
                moved = (cache.raw_bytes_staged
                         + cache.comp_bytes_staged) - b0
            if moved:
                stats.tiles_staged += 1
                stats.bytes_staged += moved
            return r

        tile = _staged(cache, cache.get_compressed if dict_coded
                       else cache.get, sp.shard)
        if si + 1 < n_sh:                     # double-buffer the next tile
            nsp, ncache = plans[si + 1], caches[si + 1]
            ndict = ncache.storage.shard_codec(nsp.shard) in \
                _codec.DICT_CODECS
            _staged(ncache, ncache.prefetch_compressed if ndict
                    else ncache.prefetch, nsp.shard)

        nb = int(sp.block_end - sp.block_start)
        col0, col1 = sp.block_start * W * 32, sp.block_end * W * 32
        offs = sp.row_offset.astype(np.uint32)
        wids = sp.block_width.astype(np.uint32)
        qc = int(query_chunk) if query_chunk else ops.bulk_query_chunk(
            nb, W, word_block=word_block)
        # never dispatch slabs wider than the (pow2-padded) set itself —
        # the VMEM budget is an upper bound, not a padding target
        qc = min(qc, max(8, 1 << max(0, Q - 1).bit_length()))
        for q0 in range(0, Q, qc):
            qn = min(qc, Q - q0)
            sl = slice(q0, q0 + qn)
            stats.query_chunks += 1
            stats.blocks_total += qn * nb
            # Pad the final slab up to qc so every slab of the sweep
            # shares one compiled kernel shape; padded queries carry
            # n_valid = 0 and are fully masked.
            hv = np.zeros((qc, L, k), dtype=h_ord.dtype)
            hv[:qn] = h_ord[sl]
            nv = np.zeros(qc, dtype=np.int32)
            nv[:qn] = n_valid[sl]
            req = np.zeros(qc, dtype=np.int64)
            req[:qn] = required[sl]
            alive = np.zeros((qc, nb), dtype=bool)
            alive[:qn] = True
            acc = ops.chunk_acc_init(qc, nb, W, word_block=word_block)
            for c in range(n_chunks):
                j0 = c * ct
                valid_chunk = (j0 + np.arange(ct, dtype=np.int32)[None, :]
                               < nv[:, None])
                live = alive[:, :, None] & valid_chunk[:, None, :]
                if not live.any():
                    break
                h_chunk = np.zeros((qc, ct, k), dtype=h_ord.dtype)
                width = min(ct, L - j0)
                h_chunk[:, :width] = hv[:, j0:j0 + width]
                rows = (h_chunk[..., None] % wids + offs)  # [qc, ct, k, nb]
                rows = np.transpose(rows, (0, 3, 1, 2)).astype(np.int64)
                mask = jnp.asarray(live.astype(np.int32))
                if k == 1:
                    idx = jnp.asarray(rows[..., 0].astype(np.int32))
                    if dict_coded:
                        d, r = tile
                        acc, bmax = ops.bitslice_chunk_score_multi_comp(
                            d, r, idx, mask, acc, word_block=word_block)
                    else:
                        acc, bmax = ops.bitslice_chunk_score_multi(
                            tile, idx, mask, acc, word_block=word_block)
                else:
                    # k>1: host-plan the chunk's unique row sets, gather
                    # and AND them on device out of the resident tile.
                    cells = rows[live]                    # [N, k]
                    uniq, inv = np.unique(cells, axis=0,
                                          return_inverse=True)
                    u_idx = np.zeros((_pad_unique(uniq.shape[0]), k),
                                     dtype=np.int32)
                    u_idx[: uniq.shape[0]] = uniq
                    if dict_coded:
                        d, r = tile
                        mat_dev = ops.gather_and_rows_comp(
                            d, r, jnp.asarray(u_idx))
                    else:
                        mat_dev = ops.gather_and_rows(tile,
                                                      jnp.asarray(u_idx))
                    indir = np.zeros((qc, nb, ct), dtype=np.int32)
                    indir[live] = np.asarray(inv).reshape(-1).astype(
                        np.int32)
                    acc, bmax = ops.bitslice_chunk_score_dedup(
                        mat_dev, jnp.asarray(indir), mask, acc,
                        word_block=word_block)
                stats.kernel_dispatches += 1
                if c < n_chunks - 1:
                    executed = np.minimum(nv, (c + 1) * ct).astype(np.int64)
                    remaining = nv.astype(np.int64) - executed
                    keep = (np.asarray(bmax).astype(np.int64)
                            + remaining[:, None]) >= req[:, None]
                    newly = alive & ~keep
                    stats.blocks_pruned += int(newly[:qn].sum())
                    alive &= keep
            out[sl, col0:col1] = np.asarray(
                ops.chunk_acc_scores(acc, W))[:qn]
        stats.shards_swept += 1
        if any_topk:
            # Completed-shard tightening: every accumulated count is a
            # lower bound on some doc's final score (unswept slots are 0,
            # pruned slots partial), so the k-th largest is a sound,
            # monotonically tightening cutoff for the remaining shards.
            ns = out.shape[1]
            for q in np.nonzero(is_topk)[0]:
                kq = int(topk[q])
                if ns >= kq > 0:
                    lb = int(np.partition(out[q], ns - kq)[ns - kq])
                    if lb > required[q]:
                        required[q] = lb
    return out, n_sh, required


def gather_rows(arena: jnp.ndarray, rows: jnp.ndarray, valid: jnp.ndarray
                ) -> jnp.ndarray:
    """Gather + AND + mask: (arena [R, Wb] or PackedArena, rows int32
    [L, k, nb], valid bool [L]) -> uint32 [L, nb * Wb]."""
    L, k, nb = rows.shape
    g = ops.take_rows(arena, rows)                # [L, k, nb, Wb]
    anded = g[:, 0]
    for i in range(1, k):
        anded = anded & g[:, i]
    anded = jnp.where(valid[:, None, None], anded, jnp.uint32(0))
    return anded.reshape(L, nb * arena.shape[1])


def gather_rows_comp(dict_rows: jnp.ndarray, refs: jnp.ndarray,
                     rows: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """``gather_rows`` against a rowdict-compressed tile: the double gather
    ``dict_rows[refs[rows]]`` decodes on the fly — same AND + mask, same
    output, HBM traffic proportional to the dict instead of the tile."""
    L, k, nb = rows.shape
    g = ops.take_rows(dict_rows, refs[rows])      # [L, k, nb, Wb]
    anded = g[:, 0]
    for i in range(1, k):
        anded = anded & g[:, i]
    anded = jnp.where(valid[:, None, None], anded, jnp.uint32(0))
    return anded.reshape(L, nb * dict_rows.shape[1])


# --------------------------------------------------------------------------
# Scoring functions (built per-index: static n_hashes / method keeps the
# jit cache tidy)
# --------------------------------------------------------------------------

def make_score_fn(n_hashes: int, method: str = "vertical",
                  word_block: int | None = None,
                  term_block: int | None = None):
    """Returns score(arena, row_offset, block_width, terms [L,2], n_valid)
    -> int32 [n_slots] scores in slot order. ``word_block``/``term_block``
    override the kernel tile defaults (autotuner choices thread through
    here); None keeps the kernel defaults."""

    @jax.jit
    def score(arena, row_offset, block_width, terms, n_valid):
        L = terms.shape[0]
        h = hashing.hash_terms(terms, n_hashes)            # [L, k]
        rows = plan_rows(h, row_offset, block_width)       # [L, k, nb]
        valid = jnp.arange(L, dtype=jnp.int32) < n_valid
        if method == "lookup" and n_hashes == 1:
            # fused path (k=1): the gather happens inside the kernel.
            if row_offset.shape[0] == 1:
                return ops.bitslice_lookup_score(
                    arena, rows[:, 0, 0], valid.astype(jnp.int32),
                    word_block=word_block)
            idx = rows[:, 0, :].T                          # [nb, L]
            msk = jnp.broadcast_to(valid.astype(jnp.int32)[None, :],
                                   idx.shape)
            return ops.bitslice_lookup_score_blocks(arena, idx, msk,
                                                    word_block=word_block)
        flat = gather_rows(arena, rows, valid)             # [L, nb*Wb]
        return ops.bitslice_score(flat, method=method if method != "lookup"
                                  else "vertical", word_block=word_block,
                                  term_block=term_block)

    return score


def make_batch_score_fn(n_hashes: int, method: str = "vertical",
                        word_block: int | None = None,
                        term_block: int | None = None,
                        grid_order: str = "wq"):
    """Returns score(arena, row_offset, block_width, terms [Q,L,2],
    n_valid [Q]) -> int32 [Q, n_slots].

    method='lookup' with k=1 dispatches the whole batch to the fused
    multi-query kernel (one pallas_call, shared arena tiles) instead of
    vmapping — vmap cannot batch the scalar-prefetch gather, which is why
    the old engine silently fell back to the jnp ref scorer here. Other
    methods vmap the single-query scorer; 'lookup' with k>1 degrades to
    'vertical' (the AND over hash rows needs the materialized gather).

    ``word_block``/``term_block``/``grid_order`` are the autotuner's tile
    and grid knobs; defaults match the untuned kernels exactly.
    """
    if method == "lookup" and n_hashes == 1:
        @jax.jit
        def score_batch(arena, row_offset, block_width, terms, n_valid):
            Q, L = terms.shape[0], terms.shape[1]
            h = hashing.hash_terms(terms, n_hashes)        # [Q, L, 1]
            rows = plan_rows(h, row_offset, block_width)   # [Q, L, 1, nb]
            idx = jnp.swapaxes(rows[:, :, 0, :], 1, 2)     # [Q, nb, L]
            valid = (jnp.arange(L, dtype=jnp.int32)[None, :]
                     < n_valid[:, None])                   # [Q, L]
            msk = jnp.broadcast_to(valid.astype(jnp.int32)[:, None, :],
                                   idx.shape)
            return ops.bitslice_lookup_score_multi(arena, idx, msk,
                                                   word_block=word_block,
                                                   grid_order=grid_order)
        return score_batch

    inner = make_score_fn(
        n_hashes, "vertical" if method == "lookup" else method,
        word_block=word_block, term_block=term_block)
    return jax.jit(jax.vmap(inner, in_axes=(None, None, None, 0, 0)))


def make_comp_score_fn(n_hashes: int, method: str = "vertical",
                       word_block: int | None = None,
                       term_block: int | None = None):
    """Compressed twin of ``make_score_fn``: score(dict_rows, refs,
    row_offset, block_width, terms [L,2], n_valid) -> int32 [n_slots].

    The arena argument splits into the shard's dict + refs staged as-is on
    device; rows decode during the gather (in-kernel for the fused k=1
    lookup path, via the ``dict[refs[row]]`` double gather otherwise), so
    scores are bit-identical to the raw-tile scorer."""

    @jax.jit
    def score(dict_rows, refs, row_offset, block_width, terms, n_valid):
        L = terms.shape[0]
        h = hashing.hash_terms(terms, n_hashes)            # [L, k]
        rows = plan_rows(h, row_offset, block_width)       # [L, k, nb]
        valid = jnp.arange(L, dtype=jnp.int32) < n_valid
        if method == "lookup" and n_hashes == 1:
            idx = rows[:, 0, :].T                          # [nb, L]
            msk = jnp.broadcast_to(valid.astype(jnp.int32)[None, :],
                                   idx.shape)
            return ops.bitslice_lookup_score_blocks_comp(
                dict_rows, refs, idx, msk, word_block=word_block)
        flat = gather_rows_comp(dict_rows, refs, rows, valid)
        return ops.bitslice_score(flat, method=method if method != "lookup"
                                  else "vertical", word_block=word_block,
                                  term_block=term_block)

    return score


def make_comp_batch_score_fn(n_hashes: int, method: str = "vertical",
                             word_block: int | None = None,
                             term_block: int | None = None,
                             grid_order: str = "wq"):
    """Compressed twin of ``make_batch_score_fn``: score(dict_rows, refs,
    row_offset, block_width, terms [Q,L,2], n_valid [Q]) -> int32
    [Q, n_slots]. k=1 'lookup' dispatches the fused decode-in-the-loop
    multi-query kernel; other methods vmap the compressed single-query
    scorer (the decode is a jnp double gather, so vmap batches it fine)."""
    if method == "lookup" and n_hashes == 1:
        @jax.jit
        def score_batch(dict_rows, refs, row_offset, block_width,
                        terms, n_valid):
            Q, L = terms.shape[0], terms.shape[1]
            h = hashing.hash_terms(terms, n_hashes)        # [Q, L, 1]
            rows = plan_rows(h, row_offset, block_width)   # [Q, L, 1, nb]
            idx = jnp.swapaxes(rows[:, :, 0, :], 1, 2)     # [Q, nb, L]
            valid = (jnp.arange(L, dtype=jnp.int32)[None, :]
                     < n_valid[:, None])                   # [Q, L]
            msk = jnp.broadcast_to(valid.astype(jnp.int32)[:, None, :],
                                   idx.shape)
            return ops.bitslice_lookup_score_multi_comp(
                dict_rows, refs, idx, msk, word_block=word_block,
                grid_order=grid_order)
        return score_batch

    inner = make_comp_score_fn(
        n_hashes, "vertical" if method == "lookup" else method,
        word_block=word_block, term_block=term_block)
    return jax.jit(jax.vmap(inner, in_axes=(None, None, None, None, 0, 0)))


@dataclass
class SearchResult:
    """One query's reported documents, best-first.

    Fields:
        doc_ids:   int32 [n_hits] original document ids, descending score
                   (ties keep ascending-id order — the sort is stable).
        scores:    int32 [n_hits] q-gram containment scores, aligned with
                   ``doc_ids``; score <= n_terms, with one-sided Bloom
                   error (never below the true containment count).
        n_terms:   number of DISTINCT query q-grams (the paper's ell);
                   a full-containment hit has score == n_terms.
        threshold: the actual integer score cutoff applied: ceil(K * ell)
                   for ``search``/``search_batch``, the k-th best score
                   for ``top_k``, 0 for an empty result.
    """

    doc_ids: np.ndarray
    scores: np.ndarray
    n_terms: int
    threshold: int


class QueryEngine:
    """High-level search over a BitSlicedIndex.

    method: 'vertical' (default, Harley–Seal kernel), 'unpack'
    (paper-faithful kernel), 'lookup' (fused gather kernel, k=1 indexes),
    or 'ref' (pure jnp oracle).

    Dense storage (one shard) scores in one device call against the
    resident arena. Sharded storage scores shard by shard through
    ``tile_cache`` (default: an unbounded DeviceTileCache, so hot shards
    stay in HBM) and concatenates — bit-identical either way.

    ``compressed=True`` keeps dict-coded shards (codec 'rowdict' /
    'rowdict+rle') in their compressed (dict, refs) form on device and
    scores them through the fused-decode kernels; raw shards are
    unaffected. Results stay bit-identical — only the HBM working set and
    the per-row bandwidth change.
    """

    def __init__(self, index: BitSlicedIndex, method: str = "vertical",
                 term_pad: int = 64,
                 tile_cache: DeviceTileCache | None = None,
                 compressed: bool = False, prune_chunk: int = 32):
        self.index = index
        self.method = method
        self.term_pad = term_pad
        self.prune_chunk = prune_chunk
        self._score = make_score_fn(index.params.n_hashes, method)
        self._score_batch = make_batch_score_fn(index.params.n_hashes, method)
        self._paged = index.storage.n_shards > 1
        self.tiles = (tile_cache if tile_cache is not None
                      else DeviceTileCache(
                          index.storage,
                          pad_rows_to=common_tile_rows(index.storage)))
        self._shard_plans = plan_shards(index.layout,
                                        index.storage.shard_row_starts)
        # device-staged per-shard addressing (one H2D copy, not per query)
        self._shard_args = [(sp.shard, jnp.asarray(sp.row_offset),
                             jnp.asarray(sp.block_width))
                            for sp in self._shard_plans]
        self._host_slot = np.asarray(index.layout.doc_slot)
        self.compressed = bool(compressed) and any(
            index.storage.shard_codec(s) in _codec.DICT_CODECS
            for s in range(index.storage.n_shards))
        if self.compressed:
            self._score_comp = make_comp_score_fn(
                index.params.n_hashes, method)
            self._score_batch_comp = make_comp_batch_score_fn(
                index.params.n_hashes, method)

    # -- scoring -------------------------------------------------------------
    def _score_slots(self, padded: jnp.ndarray, L: jnp.ndarray) -> np.ndarray:
        if not self._paged:
            # tiles.get(0) caches the device copy for every backend
            # (a single-shard MappedArena would otherwise re-upload here)
            if self.compressed:
                dict_rows, refs = self.tiles.get_compressed(0)
                return np.asarray(self._score_comp(
                    dict_rows, refs, self.index.row_offset,
                    self.index.block_width, padded, L))
            return np.asarray(self._score(
                self.tiles.get(0), self.index.row_offset,
                self.index.block_width, padded, L))
        if self.compressed:
            return np.concatenate(run_paged_compressed(
                self.tiles, self._shard_args, self._score, self._score_comp,
                padded, L))
        return np.concatenate(
            run_paged(self.tiles, self._shard_args, self._score, padded, L))

    def _score_slots_batch(self, terms: jnp.ndarray, n_valid: jnp.ndarray
                           ) -> np.ndarray:
        if not self._paged:
            if self.compressed:
                dict_rows, refs = self.tiles.get_compressed(0)
                return np.asarray(self._score_batch_comp(
                    dict_rows, refs, self.index.row_offset,
                    self.index.block_width, terms, n_valid))
            return np.asarray(self._score_batch(
                self.tiles.get(0), self.index.row_offset,
                self.index.block_width, terms, n_valid))
        if self.compressed:
            return np.concatenate(run_paged_compressed(
                self.tiles, self._shard_args, self._score_batch,
                self._score_batch_comp, terms, n_valid), axis=1)
        return np.concatenate(
            run_paged(self.tiles, self._shard_args, self._score_batch,
                      terms, n_valid), axis=1)

    def score_terms(self, terms: np.ndarray) -> np.ndarray:
        """Distinct packed terms [L, 2] -> int32 scores [n_docs] (original
        document order)."""
        padded, L = pad_terms(terms, self.term_pad)
        slots = self._score_slots(jnp.asarray(padded), jnp.int32(L))
        return slots[self._host_slot]

    def score_terms_batch(self, terms: np.ndarray, n_valid: np.ndarray
                          ) -> np.ndarray:
        """terms [Q, L, 2], n_valid [Q] -> scores [Q, n_docs]."""
        slots = self._score_slots_batch(
            jnp.asarray(terms), jnp.asarray(n_valid, dtype=jnp.int32))
        return slots[:, self._host_slot]

    # -- search --------------------------------------------------------------
    def search(self, pattern, threshold: float = 0.8) -> SearchResult:
        """pattern: DNA string or uint8 code array. Reports every document
        whose q-gram score is >= ceil(threshold * ell), best first."""
        terms = compile_pattern(pattern, self.index.params)
        if terms.shape[0] == 0:
            return SearchResult(np.zeros(0, np.int32), np.zeros(0, np.int32), 0, 0)
        scores = self.score_terms(terms)
        return select_hits(scores, terms.shape[0], threshold)

    def search_batch(self, patterns: list, threshold: float = 0.8
                     ) -> list[SearchResult]:
        """Batched search with shared padding (the paper's bulk queries)."""
        term_sets = [compile_pattern(p, self.index.params) for p in patterns]
        buf, ells = pad_term_batch(term_sets, self.term_pad)
        scores = self.score_terms_batch(buf, ells)
        return [select_hits(scores[i], int(ell), threshold)
                for i, ell in enumerate(ells)]

    def top_k(self, pattern, k: int = 10) -> SearchResult:
        """Rank documents by q-gram score, return the top k (paper's partial
        sort selection). ``threshold`` reports the k-th best score."""
        terms = compile_pattern(pattern, self.index.params)
        if terms.shape[0] == 0:
            return SearchResult(np.zeros(0, np.int32), np.zeros(0, np.int32), 0, 0)
        scores = self.score_terms(terms)
        return select_top_k(scores, terms.shape[0], k)

    # -- pruned search (branch-and-bound over the coverage cutoff) -----------
    def _pruned_doc_scores(self, term_sets: list[np.ndarray],
                           required: np.ndarray, topk: np.ndarray,
                           stats: PruneStats | None) -> np.ndarray:
        buf, ells = pad_term_batch(term_sets, self.term_pad)
        slots = run_paged_pruned(
            self.tiles, self._shard_plans, buf, ells, required, topk,
            n_hashes=self.index.params.n_hashes,
            chunk_terms=self.prune_chunk, stats=stats)
        return slots[:, self._host_slot]

    def search_pruned(self, pattern, threshold: float = 0.8,
                      stats: PruneStats | None = None) -> SearchResult:
        """``search`` through the pruned executor — bit-identical results,
        arena I/O and kernel work scaled down by the threshold's kill rate
        (``stats`` receives the accounting)."""
        return self.search_batch_pruned([pattern], threshold, stats=stats)[0]

    def search_batch_pruned(self, patterns: list, threshold: float = 0.8,
                            stats: PruneStats | None = None
                            ) -> list[SearchResult]:
        """Batched ``search_batch`` twin of ``search_pruned``."""
        term_sets = [compile_pattern(p, self.index.params) for p in patterns]
        required = np.array([coverage_cutoff(threshold, t.shape[0])
                             for t in term_sets], dtype=np.int64)
        topk = np.zeros(len(term_sets), dtype=np.int32)
        scores = self._pruned_doc_scores(term_sets, required, topk, stats)
        return [select_hits(scores[i], int(t.shape[0]), threshold)
                for i, t in enumerate(term_sets)]

    def top_k_pruned(self, pattern, k: int = 10,
                     stats: PruneStats | None = None) -> SearchResult:
        """``top_k`` through the pruned executor: the cutoff tightens to
        the merged k-th largest running count as chunks accumulate, so
        blocks provably outside the final top-k stop being scored."""
        terms = compile_pattern(pattern, self.index.params)
        if terms.shape[0] == 0:
            return SearchResult(np.zeros(0, np.int32), np.zeros(0, np.int32), 0, 0)
        scores = self._pruned_doc_scores(
            [terms], np.zeros(1, np.int64), np.array([k], np.int32), stats)
        return select_top_k(scores[0], terms.shape[0], k)
