"""QueryServer: the serving front-end tying planner, batcher, caches and
metrics together.

Life of a request:

1. ``submit`` compiles the pattern to distinct packed terms, answers
   immediately on a result-cache hit, a single-term row-cache hit, or
   backpressure (queue full), and otherwise enqueues into the
   shape-bucketed micro-batcher.
2. ``step`` (called from the driver's loop) polls the batcher; every due
   micro-batch is planned (kernel choice from index layout x batch shape),
   scored in one device call, split back into per-request results with the
   request's own threshold, and cached.
3. Responses accumulate until ``pop_responses``.

The server is single-threaded and clock-injectable: drivers decide the
cadence (closed-loop benchmarks call ``drain``; open-loop ones call
``step`` on arrival timestamps), and tests run on a virtual clock.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from ..core import codec as _codec
from ..core import hashing
from ..core.arena import DeviceTileCache, common_tile_rows
from ..core.index import BitSlicedIndex
from ..core.query import (PruneStats, SearchResult, compile_pattern,
                          coverage_cutoff, plan_dedup_batch, read_back,
                          run_paged, run_paged_compressed, run_paged_dedup,
                          run_paged_pruned, select_hits, select_top_k)
from ..kernels.autotune import KernelTuner, TuningCache
from ..obs import EventLog, KernelProfiler, Tracer, span
from .base import ServingBackend
from .batcher import MicroBatch, MicroBatcher
from .cache import LRUCache, result_key, term_key
from .metrics import ServingMetrics
from .planner import DEFAULT_DEDUP_MIN_RATE, QueryPlanner
from .request import QueryRequest, QueryResponse, Status


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    term_pad: int = 64          # bucket granularity (multiples of this)
    max_batch: int = 32         # micro-batch cap per bucket
    max_wait_s: float = 0.002   # flush timer for partially-filled buckets
    max_queued: int = 1024      # backpressure cap across all buckets
    # Fit bucket boundaries to the observed term-length histogram instead
    # of the fixed term_pad grid (MicroBatcher adaptive mode): workloads
    # whose query lengths cluster between grid lines batch densely.
    adaptive_buckets: bool = False
    result_cache: int = 1024    # whole-query LRU entries (0 disables)
    row_cache: int = 4096       # single-term row LRU entries (0 disables)
    default_threshold: float = 0.8
    # HBM budget for arena shard tiles when serving an out-of-core
    # (sharded/mmapped) index; None = unbounded, every touched shard stays
    # resident. Ignored for dense single-shard storage.
    tile_cache_bytes: Optional[int] = None
    # Kernel tile width for every dispatched scoring kernel. None = the
    # autotuner's measured choice when tuning is wired in, else the kernel
    # default (kernels.bitslice_score.DEFAULT_WORD_BLOCK).
    word_block: Optional[int] = None
    # Row-dedup path: minimum fraction of a batch's row gathers that must
    # be duplicates before the dedup pair replaces the fused multi-query
    # kernel. None disables dedup; a tuner-measured break-even overrides
    # this default.
    dedup_min_rate: Optional[float] = DEFAULT_DEDUP_MIN_RATE
    # Serve dict-coded shards from their compressed (dict, refs) device
    # form through the fused-decode kernels. The planner still decides
    # per batch shape (measured lookup-vs-lookup_c cost, or the dict
    # ratio heuristic); raw shards and all-raw stores are unaffected.
    compressed: bool = False
    # Threshold-driven pruned scoring: batches whose coverage threshold
    # predicts enough block pruning run through the chunked early-exit
    # executor (rarest-first term chunks, per-block bound, pruned blocks
    # skip all further tile I/O/staging/kernel work). The planner still
    # gates per batch on the tuned (or heuristic) break-even — results
    # stay bit-identical to unpruned scoring either way.
    pruned: bool = False
    prune_chunk: int = 32
    # Minimum predicted block-prune rate before pruned dispatch, when no
    # measured break-even exists (None = planner.DEFAULT_PRUNE_MIN_RATE).
    prune_min_rate: Optional[float] = None
    # Autotune kernel configs on demand per batch shape (measured costs
    # drive the planner; entries persist in tuning_cache). False with a
    # tuning_cache still CONSULTS existing entries — it just never
    # measures in the serving path.
    autotune: bool = False
    # Path of the persisted tuning cache (JSON; by convention
    # repro.core.store.tuning_path(store_dir) = beside the v2 manifest).
    # None keeps tuned entries in memory only.
    tuning_cache: Optional[str] = None
    # -- observability (repro.obs) --
    # Request tracing: every admitted query gets a Trace; layers append
    # spans; finished traces land in a bounded ring. Cheap enough to
    # default on (two clock reads + a locked append per span).
    tracing: bool = True
    # Completed traces slower than this (ms, end to end) go to the
    # slow-query JSONL log. 0 disables the slow sink (ring still fills).
    trace_slow_ms: float = 0.0
    trace_ring: int = 256
    # JSONL slow-query log path; None keeps events in memory only.
    trace_log: Optional[str] = None
    # Per-dispatch kernel wall time, fed to the metrics registry and
    # (when a tuner is wired) back into the tuning cache as live
    # observed-cost entries.
    profile_kernels: bool = True


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


class QueryServer(ServingBackend):
    def __init__(self, index: BitSlicedIndex,
                 config: ServerConfig = ServerConfig(), *,
                 clock: Callable[[], float] = time.monotonic):
        self.index = index
        self.config = config
        self.clock = clock
        # Tuned kernel configs: with a cache path wired in, entries load
        # from disk and serving never re-tunes what is already measured;
        # autotune=True additionally measures misses on demand.
        self.tuner: Optional[KernelTuner] = None
        if config.autotune or config.tuning_cache:
            self.tuner = KernelTuner.for_index(
                index, TuningCache(config.tuning_cache),
                enabled=config.autotune)
        self.planner = QueryPlanner(index, tuner=self.tuner,
                                    word_block=config.word_block,
                                    dedup_min_rate=config.dedup_min_rate,
                                    compressed=config.compressed,
                                    pruned=config.pruned,
                                    prune_chunk=config.prune_chunk,
                                    prune_min_rate=config.prune_min_rate)
        # Whole-arena HBM footprint: the baseline a pruned batch's actual
        # bytes-read is charged against for the bytes-saved metric.
        self._arena_total_bytes = sum(
            int(index.storage.shard_hbm_nbytes(s))
            for s in range(index.storage.n_shards))
        self.batcher = MicroBatcher(
            term_pad=config.term_pad, max_batch=config.max_batch,
            max_wait_s=config.max_wait_s, max_queued=config.max_queued,
            adaptive=config.adaptive_buckets)
        self.metrics = ServingMetrics()
        self.results_cache = LRUCache(config.result_cache)
        self.rows_cache = LRUCache(config.row_cache)
        self._responses: dict[int, QueryResponse] = {}
        self._next_id = 0
        self._host_slot = np.asarray(index.layout.doc_slot)
        # Out-of-core serving state: shard tiles are paged into HBM through
        # a bounded LRU; with dense storage there is exactly one "shard"
        # (the resident arena) and the cache is a pass-through.
        self.tiles = DeviceTileCache(index.storage,
                                     capacity_bytes=config.tile_cache_bytes,
                                     pad_rows_to=common_tile_rows(
                                         index.storage))
        self._shard_args = [(sp.shard, jnp.asarray(sp.row_offset),
                             jnp.asarray(sp.block_width))
                            for sp in self.planner.shard_plans]
        # -- observability ---------------------------------------------------
        self.events = EventLog(config.trace_log,
                               ring=max(64, config.trace_ring))
        self.tracer = Tracer(enabled=config.tracing,
                             ring=config.trace_ring,
                             slow_ms=config.trace_slow_ms,
                             sink=self.events, clock=clock)
        self.metrics.tracer = self.tracer
        self.profiler = KernelProfiler(self.metrics.registry, self.tuner,
                                       enabled=config.profile_kernels)
        # Tile-cache events flow through one observer: per-shard labeled
        # counters always; a traced batch's stagings as tile_fetch spans
        # of the batch being scored.
        self._batch_rec = None
        self.tiles.observer = self._on_tile_event
        # Compressed-arena accounting: host-side decodes land in the
        # decode histogram; staged bytes are read as per-batch deltas of
        # the tile cache's per-form counters in score_batch.
        if hasattr(index.storage, "decode_observer"):
            index.storage.decode_observer = \
                lambda s, codec, sec: self.metrics.record_decode(sec)

    def _on_tile_event(self, shard: int, event: str,
                       seconds: float) -> None:
        self.metrics.record_shard_tile(shard, event)
        rec = self._batch_rec
        if rec is not None and event in ("fault", "prefetch"):
            now = self.clock()
            rec.add("tile_fetch", now - seconds, now, shard=shard,
                    event=event)

    # -- submission ---------------------------------------------------------
    def submit(self, pattern=None, *, terms: Optional[np.ndarray] = None,
               threshold: Optional[float] = None,
               top_k: Optional[int] = None,
               deadline: Optional[float] = None,
               trace_id: int = 0, pre_spans: tuple = ()) -> int:
        """Accept one query (pattern or precompiled terms); returns the
        request id. ``top_k`` switches the request from coverage-threshold
        selection to exact top-k (same total order as QueryEngine.top_k).
        Fast paths answer immediately; everything else lands in the
        micro-batcher until the next ``step``/``drain``. ``trace_id``
        propagates a caller-minted id (the wire layer's) into the
        request's trace; 0 mints a fresh one when tracing is on.
        ``pre_spans`` are the (name, start, end) stages the request passed
        before it got here (``Tracer.begin``)."""
        if (pattern is None) == (terms is None):
            raise ValueError("pass exactly one of pattern / terms")
        if terms is None:
            terms = compile_pattern(pattern, self.index.params)
        threshold = (self.config.default_threshold if threshold is None
                     else threshold)
        top_k = int(top_k) if top_k else 0
        now = self.clock()
        rid = self._next_id
        self._next_id += 1
        ell = terms.shape[0]
        trace = self.tracer.begin(rid, trace_id=trace_id or None,
                                  started_s=now, spans=pre_spans)

        if ell == 0:
            empty = SearchResult(np.zeros(0, np.int32),
                                 np.zeros(0, np.int32), 0, 0)
            if trace is not None:
                trace.add("fast_path", now, self.clock(),
                          {"path": "empty"})
            self._answer(rid, Status.OK, empty, wait=0.0, service=0.0,
                         trace=trace)
            return rid

        key = result_key(terms, threshold, top_k)
        hit = self.results_cache.get(key)
        if hit is not None:
            self.metrics.record_request(wait_s=0.0, service_s=0.0,
                                        cached=True)
            if trace is not None:
                trace.add("cache_lookup", now, self.clock(), {"hit": 1})
            self._responses[rid] = self._finalize(trace, QueryResponse(
                rid, Status.OK, hit, method="cache", batch_size=1,
                cached=True))
            return rid

        if ell == 1 and self.rows_cache.capacity:
            result, row_hit = self._point_query(terms, threshold, top_k)
            service = self.clock() - now
            self.metrics.record_request(wait_s=0.0, service_s=service,
                                        cached=row_hit)
            if trace is not None:
                trace.add("point_query", now, self.clock(),
                          {"row_hit": int(row_hit)})
            self._responses[rid] = self._finalize(trace, QueryResponse(
                rid, Status.OK, result, method="row_cache", batch_size=1,
                wait_s=0.0, service_s=service, cached=row_hit))
            self.results_cache.put(key, result)
            return rid

        req = QueryRequest(rid, terms, ell, threshold,
                           submitted_at=now, deadline=deadline,
                           top_k=top_k, trace=trace)
        if not self.batcher.submit(req):
            self.metrics.record_rejected()
            if trace is not None:
                trace.add("reject", now, self.clock(),
                          {"reason": "backpressure"})
            self._responses[rid] = self._finalize(
                trace, QueryResponse(rid, Status.REJECTED))
            return rid
        return rid

    def _finalize(self, trace, resp: QueryResponse) -> QueryResponse:
        return self.finalize_trace(trace, resp)

    # -- point queries (COBS single-k-mer lookups) via the row cache --------
    def _gather_host_row(self, term: np.ndarray) -> np.ndarray:
        """ANDed arena row for one term, host-side: uint32 [nb * W] in
        slot-word order (mirrors plan_rows + gather exactly). Reads rows
        through the storage backend, so an mmapped index pages in only the
        touched shards — the dense arena is never materialized here."""
        h = hashing.hash_terms_np(term[None, :],
                                  self.index.params.n_hashes)[0]  # [k]
        layout = self.index.layout
        rows = (h[:, None] % layout.block_width.astype(np.uint32)
                + layout.row_offset.astype(np.uint32))            # [k, nb]
        g = self.index.storage.read_rows_host(rows.astype(np.int64))
        anded = g[0]                                              # [nb, W]
        for i in range(1, g.shape[0]):
            anded = anded & g[i]
        return anded.reshape(-1)                                  # [nb * W]

    def _point_query(self, terms: np.ndarray, threshold: float,
                     top_k: int = 0) -> tuple[SearchResult, bool]:
        """Returns (result, served-from-row-cache)."""
        k = term_key(terms[0])
        row = self.rows_cache.get(k)
        hit = row is not None
        if row is None:
            row = self._gather_host_row(terms[0])
            self.rows_cache.put(k, row)
        bits = ((row[:, None] >> np.arange(32, dtype=np.uint32)) & 1)
        scores = bits.astype(np.int32).reshape(-1)[self._host_slot]
        return self._select(scores, 1, threshold, top_k), hit

    @staticmethod
    def _select(scores: np.ndarray, n_terms: int, threshold: float,
                top_k: int) -> SearchResult:
        """Per-request selection: coverage threshold, or exact top-k under
        QueryEngine's (-score, doc id) total order when top_k > 0."""
        if top_k:
            return select_top_k(scores, n_terms, top_k)
        return select_hits(scores, n_terms, threshold)

    # -- batch scoring -------------------------------------------------------
    def _score_dense(self, fn, fn_comp, args, rec) -> np.ndarray:
        """One dispatch against the resident arena (tile 0; its cached
        device copy serves every backend, so a single-shard MappedArena
        is not re-uploaded per batch). With ``fn_comp`` (compressed plans)
        a dict-coded arena scores its (dict, refs) form through the
        fused-decode kernels."""
        comp = (fn_comp is not None and self.index.storage.shard_codec(0)
                in _codec.DICT_CODECS)
        with span(rec, "tile_get"):
            tile = (self.tiles.get_compressed(0) if comp
                    else (self.tiles.get(0),))
        with span(rec, "dispatch"):
            out = (fn_comp if comp else fn)(*tile, *args)
        return read_back([out], rec)

    def _run_plan(self, plan, fn, terms_dev, valid_dev,
                  fn_comp=None, rec=None) -> np.ndarray:
        """Dispatch ``fn`` once against the dense arena, or — for a paged
        plan — once per shard tile (staged through the LRU tile cache),
        concatenating per-shard slot scores along the slot axis. With
        ``fn_comp`` (compressed plans) dict-coded shards stage their
        (dict, refs) form and score through the fused-decode kernels.
        ``rec`` (a tracing BatchRecorder, or None) gets the spans."""
        if not plan.paged:
            return self._score_dense(
                fn, fn_comp, (self.index.row_offset, self.index.block_width,
                              terms_dev, valid_dev), rec)
        if fn_comp is not None:
            return run_paged_compressed(self.tiles, self._shard_args, fn,
                                        fn_comp, terms_dev, valid_dev,
                                        rec=rec)
        return run_paged(self.tiles, self._shard_args, fn, terms_dev,
                         valid_dev, rec=rec)

    def _score_dedup(self, buf: np.ndarray, n_valid: np.ndarray, plan,
                     rec=None) -> Optional[np.ndarray]:
        """Row-dedup dispatch, or None when the batch's measured dedup
        rate is below the plan's break-even threshold. The global-layout
        plan decides; dense execution reuses it directly, paged execution
        re-plans per shard against the rebased addressing."""
        layout = self.index.layout
        with span(rec, "dedup_plan") as tags:
            dp = plan_dedup_batch(buf, n_valid, layout.row_offset,
                                  layout.block_width)
        if tags is not None:
            tags.update(dedup_rate=round(float(dp.dedup_rate), 4),
                        n_unique=int(dp.n_unique))
        if dp.dedup_rate < plan.dedup_threshold:
            return None
        method = "dedup_c" if plan.compressed else "dedup"
        fn = self.planner.dedup_score_fn(plan)
        fn_comp = (self.planner.comp_dedup_score_fn(plan)
                   if plan.compressed else None)
        tk0 = self.clock()
        with self._kernel_span(rec, method, plan):
            if not plan.paged:
                with span(rec, "upload"):
                    planned = (jnp.asarray(dp.uniq_rows),
                               jnp.asarray(dp.indir), jnp.asarray(dp.mask))
                slots = self._score_dense(fn, fn_comp, planned, rec)
            else:
                slots = run_paged_dedup(self.tiles, self.planner.shard_plans,
                                        fn, buf, n_valid, fn_comp=fn_comp,
                                        rec=rec)
        self._profile(method, plan, self.clock() - tk0)
        return slots

    @staticmethod
    def _kernel_span(rec, method: str, plan):
        """The ``kernel_score`` span of one scoring dispatch, from before
        its inputs go to the device until its scores are on the host."""
        if rec is None:
            return span(None, "kernel_score")
        return rec.span("kernel_score", method=method, bucket=plan.bucket,
                        word_block=plan.word_block or 0)

    def _profile(self, method: str, plan, seconds: float) -> None:
        """One kernel dispatch into the profiler's histogram and the live
        cost signal for the autotuner."""
        self.profiler.record(
            method=method, bucket=plan.bucket, batch=plan.batch_size,
            seconds=seconds, word_block=plan.word_block or 0,
            term_block=plan.term_block or 0, grid_order=plan.grid_order)

    def score_batch(self, batch: MicroBatch) -> None:
        """Plan, dispatch, and answer one flushed micro-batch. Public so
        an active serving loop (repro.serve.loop) can pull batches off
        ``poll_batches`` and score them from worker threads."""
        t0 = self.clock()
        Q, B = batch.size, batch.bucket
        rec = self.tracer.batch(batch.requests)
        self._batch_rec = rec
        # The weakest coverage threshold across the batch is the bound
        # every block must clear for at least one request — the planner's
        # basis for predicting the prune rate. All-top-k batches pass
        # None (still correct to prune via the dynamic bound, but with no
        # static prediction the planner stays unpruned).
        thr_hint = min((r.threshold for r in batch.requests if not r.top_k),
                       default=None)
        with span(rec, "plan") as tags:
            plan = self.planner.plan(B, Q, threshold=thr_hint)
        if tags is not None:
            tags.update(method=plan.method, fused=int(plan.fused),
                        paged=int(plan.paged), pruned=int(plan.pruned))
        # compressed fused dispatch reports (and live-profiles) as
        # "lookup_c" — the tuner's cost key for the decode-in-the-loop
        # kernel, keeping observed costs per path
        method = ("lookup_c" if plan.compressed and plan.method == "lookup"
                  else plan.method)
        ells = np.array([r.n_terms for r in batch.requests], dtype=np.int32)
        tiles0 = (self.tiles.hits, self.tiles.faults,
                  self.tiles.prefetched, self.tiles.prefetch_hits)
        bytes0 = (self.tiles.raw_bytes_staged, self.tiles.comp_bytes_staged)
        if plan.pruned:
            # Chunked branch-and-bound executor: rarest-first term chunks
            # against a persistent running-count buffer; blocks whose
            # bound falls below the coverage cutoff (or the running k-th
            # score) skip all further gathers, staging and kernel work.
            # Bit-identical to the unpruned paths by construction.
            q_pad = 1 if Q == 1 else _next_pow2(Q)
            buf = np.zeros((q_pad, B, 2), dtype=np.uint32)
            n_valid = np.zeros(q_pad, dtype=np.int32)
            required = np.full(q_pad, np.iinfo(np.int32).max,
                               dtype=np.int64)
            topks = np.zeros(q_pad, dtype=np.int32)
            for i, r in enumerate(batch.requests):
                buf[i, : r.n_terms] = r.terms
                n_valid[i] = r.n_terms
                topks[i] = r.top_k
                required[i] = (0 if r.top_k else
                               coverage_cutoff(r.threshold, r.n_terms))
            method = "lookup_p"
            pstats = PruneStats()
            tk0 = self.clock()
            with span(rec, "prune") as tags:
                slots = run_paged_pruned(
                    self.tiles, self.planner.shard_plans, buf, n_valid,
                    required, topks, n_hashes=self.index.params.n_hashes,
                    chunk_terms=plan.chunk_terms or self.config.prune_chunk,
                    word_block=plan.word_block, stats=pstats, rec=rec)
            self._profile(method, plan, self.clock() - tk0)
            self.metrics.record_prune(
                blocks_total=pstats.blocks_total,
                blocks_pruned=pstats.blocks_pruned,
                tiles_skipped=pstats.shard_visits_skipped,
                bytes_saved=max(
                    0, self._arena_total_bytes - pstats.bytes_read),
                syncs=pstats.syncs)
            if tags is not None:
                tags.update(
                    blocks_pruned=int(pstats.blocks_pruned),
                    blocks_total=int(pstats.blocks_total),
                    tiles_skipped=int(pstats.shard_visits_skipped),
                    bytes_read=int(pstats.bytes_read),
                    syncs=int(pstats.syncs),
                    predicted=round(float(plan.predicted_prune), 3))
            scores = slots[:Q][:, self._host_slot]
        elif Q == 1:
            buf = np.zeros((B, 2), dtype=np.uint32)
            buf[: ells[0]] = batch.requests[0].terms
            fn = self.planner.single_score_fn(plan)
            fn_comp = (self.planner.comp_single_score_fn(plan)
                       if plan.compressed else None)
            tk0 = self.clock()
            with self._kernel_span(rec, method, plan):
                with span(rec, "upload"):
                    inputs = (jnp.asarray(buf), jnp.int32(ells[0]))
                slots = self._run_plan(plan, fn, *inputs, fn_comp=fn_comp,
                                       rec=rec)
            self._profile(method, plan, self.clock() - tk0)
            scores = slots[None, self._host_slot]
        else:
            # Pad the query axis to a power of two so jit entries stay
            # bounded at (buckets x log2 max_batch) rather than one per
            # observed batch size.
            q_pad = _next_pow2(Q)
            buf = np.zeros((q_pad, B, 2), dtype=np.uint32)
            for i, r in enumerate(batch.requests):
                buf[i, : r.n_terms] = r.terms
            n_valid = np.zeros(q_pad, dtype=np.int32)
            n_valid[:Q] = ells
            slots = None
            if plan.fused and plan.dedup_threshold is not None:
                slots = self._score_dedup(buf, n_valid, plan, rec)
                if slots is not None:
                    method = "dedup_c" if plan.compressed else "dedup"
            if slots is None:
                fn = self.planner.batch_score_fn(plan)
                fn_comp = (self.planner.comp_batch_score_fn(plan)
                           if plan.compressed else None)
                tk0 = self.clock()
                with self._kernel_span(rec, method, plan):
                    with span(rec, "upload"):
                        inputs = (jnp.asarray(buf), jnp.asarray(n_valid))
                    slots = self._run_plan(plan, fn, *inputs,
                                           fn_comp=fn_comp, rec=rec)
                self._profile(method, plan, self.clock() - tk0)
            scores = slots[:Q][:, self._host_slot]
        t1 = self.clock()
        service = t1 - t0
        self._batch_rec = None
        spans = rec.finished() if rec is not None else ()

        self.planner.record(plan, method)
        self.metrics.record_batch(Q, self.batcher.occupancy(batch), method)
        self.metrics.record_arena_bytes(
            raw=self.tiles.raw_bytes_staged - bytes0[0],
            comp=self.tiles.comp_bytes_staged - bytes0[1])
        if plan.paged:
            self.metrics.record_tiles(
                hits=self.tiles.hits - tiles0[0],
                faults=self.tiles.faults - tiles0[1],
                resident=len(self.tiles),
                prefetched=self.tiles.prefetched - tiles0[2],
                prefetch_hits=self.tiles.prefetch_hits - tiles0[3])
        for i, r in enumerate(batch.requests):
            ts0 = self.clock()
            result = self._select(scores[i], r.n_terms, r.threshold,
                                  r.top_k)
            wait = max(0.0, t0 - r.submitted_at)
            self.metrics.record_request(wait_s=wait, service_s=service)
            resp = QueryResponse(
                r.request_id, Status.OK, result, method=method,
                batch_size=Q, wait_s=wait, service_s=service)
            if r.trace is not None:
                r.trace.add("queue_wait", r.submitted_at, t0,
                            {"flush": batch.reason or "direct",
                             "batch_size": Q})
                r.trace.extend(spans)
                r.trace.add("select", ts0, self.clock())
                self.finalize_trace(r.trace, resp)
            self._responses[r.request_id] = resp
            self.results_cache.put(
                result_key(r.terms, r.threshold, r.top_k), result)

    def _answer(self, rid: int, status: Status, result, *, wait: float,
                service: float, trace=None) -> None:
        self.metrics.record_request(wait_s=wait, service_s=service)
        self._responses[rid] = self._finalize(trace, QueryResponse(
            rid, status, result, wait_s=wait, service_s=service))

    # -- serving loop (poll_batches / step / drain / take_response /
    # retract / pop_responses come from ServingBackend) ----------------------
    def reset_metrics(self, *, clear_caches: bool = False) -> None:
        """Fresh counters (drivers call this after jit warmup so compile
        time does not pollute the latency percentiles). clear_caches=True
        also empties the result/row caches — needed when the warmup replays
        the measurement workload, which would otherwise be served entirely
        from cache."""
        self.metrics = ServingMetrics()
        self.metrics.tracer = self.tracer
        self.profiler.bind_registry(self.metrics.registry)
        self.planner.dispatch_counts.clear()
        if clear_caches:
            self.results_cache = LRUCache(self.results_cache.capacity)
            self.rows_cache = LRUCache(self.rows_cache.capacity)
