"""ShardWorker: the per-host half of the sharded serving data plane.

A worker owns a sub-store view (``repro.core.store.open_substore``) of the
shard files its ``ShardPlacement`` replica set assigns to it — it never
maps, stages, or scores any other part of the index. Per dispatch it
receives one micro-batch (padded term buffer + validity counts) and one
GLOBAL shard id from its replica set, scores that shard's tile through the
same Pallas kernels as the single-host engine (kernel choice =
``repro.serve.planner.choose_method``, so the dispatch mix matches), and
compresses the [Q, shard_slots] score plane into per-query CANDIDATES:

* threshold mode — every (doc, score) of its blocks with
  score >= ceil(K * ell) (the paper's coverage cutoff);
* top-k mode    — its k best documents under the engine's exact total
  order (descending score, ties ascending doc id).

Candidate sets are what crosses the host boundary: the frontend gathers
them and runs the final selection exactly like ``index/distributed.py``'s
score-combine, so the gathered result is bit-identical to the single-host
QueryEngine (property-tested in tests/test_multihost.py).

A worker with no HBM budget holds its shards resident as ONE packed arena
(``PackedTileCache``): their rows concatenated, each shard addressed from
its base row, so a host's HBM holds its own rows and nothing more, and
the host compiles one tile shape. A worker with a budget pages per-shard
tiles through a ``DeviceTileCache`` padded to the PARENT store's tallest
shard, so every such worker shares one compiled kernel per (bucket,
method); ``prefetch_shard`` lets the frontend double-buffer the next
planned shard while another worker scores.

Given a tracing recorder (``obs.trace.BatchRecorder``), a dispatch times
its stages into it: ``shard_kernel`` (the jitted call, on the host),
``shard_readback`` (the scores to the host: the wait for the device and
the copy, tagged with its ``copies``) and ``shard_select`` (candidate
selection). The frontend copies them into the batch's trace.

``fail()``/``recover()`` flip a liveness flag: a dead worker raises
``AttemptFailed`` on dispatch, which the frontend's HedgedExecutor turns
into failover to the next replica.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import codec as _codec
from ..core.query import (PruneStats, ShardPlan, make_batch_score_fn,
                          make_comp_batch_score_fn, plan_shards_subset,
                          run_paged_pruned)
from ..core.store import open_substore
from ..core.arena import DeviceTileCache, PackedTileCache
from ..index.hedge import AttemptFailed
from ..obs.trace import span
from .planner import (DEFAULT_PRUNE_MIN_RATE, SHORT_QUERY_TERMS,
                      choose_method, predict_prune_rate)

# One jitted scorer per (n_hashes, method, word_block), shared by EVERY
# worker in the process: paged workers pad tiles to the parent store's
# tallest shard, so their dispatch shapes coincide and recompiling per
# worker would only burn startup time (noticeable across the elasticity
# property sweeps); packed workers compile their own tile shape under it.
_SCORE_FNS: dict[tuple[int, str, Optional[int]], object] = {}
# ... and the fused-decode twins for workers serving compressed shards.
_SCORE_FNS_C: dict[tuple[int, str, Optional[int]], object] = {}


def _shared_score_fn(n_hashes: int, method: str,
                     word_block: Optional[int] = None):
    fn = _SCORE_FNS.get((n_hashes, method, word_block))
    if fn is None:
        fn = make_batch_score_fn(n_hashes, method, word_block=word_block)
        _SCORE_FNS[(n_hashes, method, word_block)] = fn
    return fn


def _shared_comp_score_fn(n_hashes: int, method: str,
                          word_block: Optional[int] = None):
    fn = _SCORE_FNS_C.get((n_hashes, method, word_block))
    if fn is None:
        fn = make_comp_batch_score_fn(n_hashes, method,
                                      word_block=word_block)
        _SCORE_FNS_C[(n_hashes, method, word_block)] = fn
    return fn


class DispatchCancelled(Exception):
    """A dispatch's cancellation flag fired (a hedged duplicate of the
    request already won elsewhere) — the worker stops scoring and the
    RPC plane answers SHARD_CANCELLED instead of a candidate set."""


class ShardWorker:
    """One fake/real host serving a subset of a v2 store's shards."""

    def __init__(self, name: str, store, shard_ids, *,
                 tile_cache_bytes: Optional[int] = None,
                 verify: bool = False, device=None,
                 short_query_terms: int = SHORT_QUERY_TERMS,
                 word_block: Optional[int] = None,
                 compressed: bool = False,
                 pruned: bool = False, prune_chunk: int = 32,
                 prune_min_rate: Optional[float] = None,
                 local_pad: bool = False, tuner=None):
        sub = open_substore(store, shard_ids, verify=verify)
        self.name = name
        self.layout = sub.layout            # FULL store layout (metadata)
        self.storage = sub.storage          # only this host's shard files
        self.params = sub.params
        self.shard_ids = sub.shard_ids
        self.device = device
        self.short_query_terms = short_query_terms
        # kernel tile width for every dispatch (ServerConfig.word_block /
        # the autotuner's choice, threaded from the launcher); None = the
        # kernel default
        self.word_block = word_block
        # Serve dict-coded shards from their compressed (dict, refs)
        # device form through the fused-decode kernels; raw shards on the
        # same worker keep the raw path. Candidates are bit-identical —
        # only this host's HBM working set changes.
        self.compressed = bool(compressed)
        self.compressed_dispatches = 0
        self._local = {g: i for i, g in enumerate(self.shard_ids)}
        self.plans: list[ShardPlan] = plan_shards_subset(
            sub.layout, sub.global_row_starts, sub.shard_ids)
        # a paged worker (an HBM budget) pads tiles to the PARENT store's
        # tallest shard: one kernel shape across every worker, not one per
        # host's local maximum; a resident one packs its shards unpadded.
        # ``local_pad`` instead pads to THIS host's tallest shard — smaller
        # tiles and per-worker dispatch shapes, so a per-worker tuner (the
        # ``tuner`` argument, keyed on the local geometry) can measure each
        # shard height separately instead of one tall-parent tune key
        # covering every worker.
        self.local_pad = bool(local_pad)
        packed = tile_cache_bytes is None and not self.compressed
        if packed or sub.n_shards_total <= 1:
            pad_rows = None
        elif self.local_pad:
            starts = np.asarray(sub.global_row_starts, dtype=np.int64)
            pad_rows = int(max(starts[g + 1] - starts[g]
                               for g in self.shard_ids))
        else:
            pad_rows = int(np.max(np.diff(sub.global_row_starts)))
        # Optional per-worker KernelTuner (repro.kernels.autotune): its
        # key carries this worker's LOCAL row count, so two workers with
        # different shard heights tune (and cache) separately.
        self.tuner = tuner
        # local-pad shapes differ per worker, so compiled score fns live
        # on the instance instead of the module-level shared caches
        self._fns: dict = {}
        self._fns_c: dict = {}
        # -- pruned (chunked early-exit) candidate scoring ------------------
        self.pruned = bool(pruned)
        self.prune_chunk = int(prune_chunk)
        self.prune_min_rate = (DEFAULT_PRUNE_MIN_RATE
                               if prune_min_rate is None
                               else float(prune_min_rate))
        self.prune_stats = PruneStats()     # cumulative across dispatches
        self.pruned_dispatches = 0
        # cumulative HBM bytes of the shards pruned dispatches covered —
        # what exhaustive scoring would have staged; bytes saved =
        # baseline - prune_stats.bytes_read
        self.prune_baseline_bytes = 0
        w = int(self.storage.shape[1])
        mean_fn = getattr(self.storage, "mean_popcount", None)
        has_fn = getattr(self.storage, "has_popcounts", None)
        if callable(has_fn) and has_fn() and callable(mean_fn) and w:
            self.density = float(mean_fn()) / float(32 * w)
        else:
            self.density = float(self.params.fpr)
        self.tiles = (PackedTileCache(self.storage, device=device)
                      if packed else
                      DeviceTileCache(self.storage,
                                      capacity_bytes=tile_cache_bytes,
                                      pad_rows_to=pad_rows, device=device))
        self.device_id = int((device or jax.devices()[0]).id)
        # global slot -> original doc id (-1 for padding slots); workers
        # translate their slot planes to doc candidates host-side
        n_slots = self.layout.n_blocks * self.layout.block_docs
        self._slot_doc = np.full(n_slots, -1, dtype=np.int64)
        self._slot_doc[self.layout.doc_slot] = np.arange(self.layout.n_docs)
        # per-local-shard device-staged addressing, rebased to the shard's
        # first row in the tile that holds it
        self._args = [
            (p.shard,
             self._dev((p.row_offset.astype(np.int64)
                        + self.tiles.row_base(p.shard)).astype(np.int32)),
             self._dev(p.block_width)) for p in self.plans]
        self.failed = False
        self.dispatches = 0
        # dispatches abandoned mid-tile because their cancellation flag
        # fired (a hedged duplicate won) — the RPC plane's headline
        # "the loser was observably cancelled" counter
        self.cancelled_tiles = 0
        # Optional KernelProfiler (repro.obs.profile): the frontend wires
        # its own in so per-shard kernel timings land in the shared
        # metrics registry tagged with this worker's dispatches.
        self.profiler = None
        # One dispatch at a time per worker: the frontend's concurrent
        # scatter may land two shards on the same host in parallel, and
        # the tile cache / counters are not thread-safe. Serializing per
        # worker models one host's device anyway — the overlap win is
        # ACROSS hosts.
        self._lock = threading.Lock()

    def _dev(self, a: np.ndarray):
        x = jnp.asarray(a)
        return x if self.device is None else jax.device_put(x, self.device)

    # -- liveness (control plane / test hook) -------------------------------
    def fail(self) -> None:
        self.failed = True

    def recover(self) -> None:
        self.failed = False

    def holds(self, gshard: int) -> bool:
        return gshard in self._local

    # -- staging -------------------------------------------------------------
    def stage_batch(self, terms: np.ndarray, n_valid: np.ndarray):
        """Place one micro-batch's buffers on this worker's device. The
        frontend calls this once per (batch, device) and reuses the result
        across every shard dispatch that lands here."""
        return (self._dev(np.asarray(terms)),
                self._dev(np.asarray(n_valid, dtype=np.int32)))

    def prefetch_shard(self, gshard: int) -> bool:
        """Double-buffering hook: stage the tile of global shard
        ``gshard`` host->device without blocking (no-op when resident).
        Compressed workers stage the form they will actually score."""
        if self.failed or gshard not in self._local:
            return False
        local = self._local[gshard]
        with self._lock:
            if self._comp_shard(local):
                return self.tiles.prefetch_compressed(local)
            return self.tiles.prefetch(local)

    # -- scoring -------------------------------------------------------------
    def _score_fn(self, method: str, word_block: Optional[int] = None):
        wb = self.word_block if word_block is None else word_block
        if not self.local_pad:
            return _shared_score_fn(self.params.n_hashes, method, wb)
        key = (self.params.n_hashes, method, wb)
        fn = self._fns.get(key)
        if fn is None:
            fn = make_batch_score_fn(self.params.n_hashes, method,
                                     word_block=wb)
            self._fns[key] = fn
        return fn

    def _comp_score_fn(self, method: str, word_block: Optional[int] = None):
        wb = self.word_block if word_block is None else word_block
        if not self.local_pad:
            return _shared_comp_score_fn(self.params.n_hashes, method, wb)
        key = (self.params.n_hashes, method, wb)
        fn = self._fns_c.get(key)
        if fn is None:
            fn = make_comp_batch_score_fn(self.params.n_hashes, method,
                                          word_block=wb)
            self._fns_c[key] = fn
        return fn

    def _comp_shard(self, local: int) -> bool:
        return (self.compressed and
                self.storage.shard_codec(local) in _codec.DICT_CODECS)

    def score_shard(self, gshard: int, terms_dev, n_valid_dev, rec=None
                    ) -> tuple[np.ndarray, ShardPlan, str]:
        """Score one held shard against a staged micro-batch. Returns
        (slot scores int32 [Q, shard_slots], the shard's plan, method)."""
        if self.failed:
            raise AttemptFailed(f"worker {self.name} is down")
        local = self._local.get(gshard)
        if local is None:
            raise AttemptFailed(
                f"worker {self.name} does not hold shard {gshard}")
        self.dispatches += 1
        plan = self.plans[local]
        _, offs, widths = self._args[local]
        q, bucket = int(terms_dev.shape[0]), int(terms_dev.shape[1])
        wb = self.word_block
        if self.tuner is not None:
            # per-worker measured costs (keyed on THIS host's geometry)
            entries = self.tuner.costs(bucket, q)
            if not self.compressed:
                entries.pop("lookup_c", None)
            costs = {m: e.cost_us for m, e in entries.items()}
            method = choose_method(self.params.n_hashes, bucket, q,
                                   self.short_query_terms, costs=costs)
            tuned = entries.get(method)
            if method == "lookup_c":
                method = "lookup"
            if wb is None and tuned is not None:
                wb = tuned.word_block
        else:
            method = choose_method(self.params.n_hashes, bucket, q,
                                   self.short_query_terms)
        t0 = time.perf_counter()
        with span(rec, "shard_kernel"):
            if self._comp_shard(local):
                self.compressed_dispatches += 1
                dict_rows, refs = self.tiles.get_compressed(local)
                fn = self._comp_score_fn(method, wb)
                slots = fn(dict_rows, refs, offs, widths, terms_dev,
                           n_valid_dev)
            else:
                slots = self._score_fn(method, wb)(
                    self.tiles.get(local), offs, widths, terms_dev,
                    n_valid_dev)
        with span(rec, "shard_readback", {"copies": 1}):
            slots = np.asarray(slots)
        if self.profiler is not None:
            self.profiler.record(
                method=method, bucket=bucket, batch=q,
                seconds=time.perf_counter() - t0,
                word_block=wb or 0, shard=gshard)
        return slots, plan, method

    def _check_cancel(self, cancelled) -> None:
        if cancelled is not None and cancelled():
            self.cancelled_tiles += 1
            raise DispatchCancelled(f"worker {self.name}: dispatch "
                                    f"cancelled between tiles")

    def score_candidates(self, gshard: int, terms_dev, n_valid_dev,
                         cutoffs: np.ndarray, topks: np.ndarray,
                         n_live: int, *, cancelled=None, rec=None
                         ) -> tuple[list[tuple[np.ndarray, np.ndarray]], str]:
        """Score + select: per live query, the (doc_ids, scores) candidate
        arrays of this shard's documents — hits >= cutoffs[i] when
        topks[i] == 0, else the local top-k under (-score, doc id). Only
        candidates cross the host boundary, O(hits + k) per query instead
        of O(n_docs) — the scatter/gather contract of the frontend.

        With ``pruned`` enabled and the cost model predicting a win, the
        shard dispatch runs through the chunked early-exit executor
        instead: blocks whose bound cannot reach the cutoff skip all
        further gathers and kernel work, a fully-pruned shard never
        stages its tile, and candidates stay bit-identical (pruned
        partial sums are provably below every cutoff)."""
        # ``cancelled`` (optional zero-arg callable) is the RPC plane's
        # cancellation flag: checked before the tile is scored and again
        # before candidate extraction, so a dispatch whose hedged
        # duplicate already won abandons the remaining work and raises
        # DispatchCancelled instead of staging/scanning further. ``rec``
        # (a tracing BatchRecorder, or None) times the stages (module
        # docstring).
        self._check_cancel(cancelled)
        with self._lock:
            pr = (self._score_pruned(gshard, terms_dev, n_valid_dev,
                                     cutoffs, topks, n_live, rec)
                  if self.pruned else None)
            if pr is not None:
                slots, plan, method = pr
            else:
                slots, plan, method = self.score_shard(
                    gshard, terms_dev, n_valid_dev, rec)
        self._check_cancel(cancelled)
        with span(rec, "shard_select"):
            slot0 = plan.block_start * self.layout.block_docs
            docs = self._slot_doc[slot0: slot0 + slots.shape[1]]
            real = docs >= 0
            docs = docs[real]
            out = []
            for i in range(n_live):
                sc = slots[i][real]
                if topks[i] > 0:
                    order = np.lexsort((docs, -sc))[: int(topks[i])]
                    out.append((docs[order], sc[order].astype(np.int32)))
                else:
                    m = sc >= cutoffs[i]
                    out.append((docs[m], sc[m].astype(np.int32)))
        return out, method

    def _score_pruned(self, gshard: int, terms_dev, n_valid_dev,
                      cutoffs: np.ndarray, topks: np.ndarray, n_live: int,
                      rec=None
                      ) -> Optional[tuple[np.ndarray, ShardPlan, str]]:
        """Chunked early-exit dispatch of one held shard, or None when the
        cost model predicts no win (caller falls back to ``score_shard``).
        Traced as one ``shard_kernel``, its read-backs counted in it.

        Shard-LOCAL top-k pruning is sound here: this worker only reports
        its own shard's top-k candidates, so the dynamic bound needs only
        this shard's running counts. Called under ``self._lock``."""
        if self.failed or gshard not in self._local:
            return None                 # score_shard raises the real error
        bucket = int(terms_dev.shape[1])
        if bucket <= self.prune_chunk:
            return None
        n_valid = np.asarray(n_valid_dev)
        covs = [cutoffs[i] / max(1, int(n_valid[i]))
                for i in range(n_live) if not topks[i]]
        if not covs:
            return None                 # all-top-k: no static prediction
        predicted = predict_prune_rate(float(min(covs)), self.density)
        break_even = self.prune_min_rate
        chunk = min(self.prune_chunk, bucket)
        if self.tuner is not None:
            q = int(terms_dev.shape[0])
            e = self.tuner.entry("lookup_p", bucket, q)
            if e is not None:
                if e.dedup_threshold is not None:
                    break_even = e.dedup_threshold
                chunk = min(e.term_block or chunk, bucket)
        if break_even >= 1.0 or predicted < break_even:
            return None
        local = self._local[gshard]
        plan = self.plans[local]
        self.dispatches += 1
        self.pruned_dispatches += 1
        self.prune_baseline_bytes += int(self.storage.shard_hbm_nbytes(local))
        Q = int(terms_dev.shape[0])
        required = np.full(Q, np.iinfo(np.int32).max, dtype=np.int64)
        for i in range(n_live):
            required[i] = 0 if topks[i] else int(cutoffs[i])
        t0 = time.perf_counter()
        syncs0 = self.prune_stats.syncs
        with span(rec, "shard_kernel") as tags:
            slots = run_paged_pruned(
                self.tiles, [plan], np.asarray(terms_dev), n_valid, required,
                np.asarray(topks, dtype=np.int32),
                n_hashes=self.params.n_hashes, chunk_terms=chunk,
                word_block=self.word_block, stats=self.prune_stats, rec=rec)
            if tags is not None:
                tags["copies"] = self.prune_stats.syncs - syncs0
        if self.profiler is not None:
            self.profiler.record(
                method="lookup_p", bucket=bucket, batch=Q,
                seconds=time.perf_counter() - t0,
                word_block=self.word_block or 0, shard=gshard)
        return slots, plan, "lookup_p"
