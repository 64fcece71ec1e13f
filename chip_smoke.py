#!/usr/bin/env python3
"""Smoke test of the COBS serving path on a TPU.

Builds a seeded, deployment-sized index at the paper's parameters (k=31,
one hash, FPR 0.3, 1024-document blocks, log-normal document sizes), stages
it on the chip and serves it through the objects ``repro.launch.serve``
composes:

  (a) closed-loop batches through ``QueryServer`` (default planner,
      threshold and top-k queries, one k-mer up to read length);
  (b) the same with pruned scoring;
  (c) a rowdict-coded store of part of the corpus, served compressed;
  (d) one ``BulkLane`` sweep;
  (e) a few queries over a real socket (``ServingLoop`` + ``NetServer`` +
      ``NetClient``, all in this process).

Every result is compared for equality with a plain NumPy scorer over the
host copy of the same arena. ``--four-chips`` instead shards the index over
four chips in this one process (``make_multihost_frontend`` with one
``ShardWorker`` per chip) and runs only that path and the reference.

    python3 chip_smoke.py                 # needs one TPU
    python3 chip_smoke.py --four-chips    # needs four

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
everything else comes before it. Without a TPU, or on any failure, the
script exits non-zero and prints no such line. The multi-process RPC plane
is not exercised here: one process owns the chip, so it stays a CPU test
path (``tests/test_rpc_plane.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

K, FPR, BLOCK_DOCS = 31, 0.3, 1024
SIGMA = 1.25          # log-normal skew: max/mean ~ 48 at 4096 documents
READ_LEN = 150        # a short-read query; one k-mer is the shortest
QUERY_LENGTHS = (K, 48, 100, READ_LEN)
TOP_K = 10


class SmokeFailure(RuntimeError):
    """A phase served a non-OK status or a result the reference refutes."""


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------------
# corpus and store
# --------------------------------------------------------------------------

def params():
    from repro.core import IndexParams
    return IndexParams(n_hashes=1, fpr=FPR, kmer=K)


def size_lengths(n_docs: int, arena_bytes: int, seed: int) -> np.ndarray:
    """Log-normal document lengths scaled so that the compact layout's
    arena holds at least ``arena_bytes``."""
    from repro.core import bloom
    from repro.data.synthetic import lognormal_lengths
    from repro.index.build_parallel import plan_compact_layout

    def arena_of(lengths):
        layout, _ = plan_compact_layout(np.maximum(lengths - K + 1, 0),
                                        params(), BLOCK_DOCS, bloom.ROW_ALIGN)
        return int(layout.total_rows) * layout.doc_words * 4

    mean = 1000
    for _ in range(8):
        lengths = lognormal_lengths(np.random.default_rng(seed), n_docs,
                                    mean_length=mean, sigma=SIGMA,
                                    min_length=READ_LEN)
        got = arena_of(lengths)
        if got >= arena_bytes:
            return lengths
        mean = int(math.ceil(mean * arena_bytes / max(got, 1) * 1.02))
    raise SmokeFailure(f"could not size a {arena_bytes} B arena")


def build_store(path, lengths: np.ndarray, seed: int, workers: int,
                codec: str = "raw", docs=None, block_docs: int = BLOCK_DOCS):
    """Generate the documents' k-mers (``docs`` picks which, with repeats)
    and stream a v2 store, one block per shard. Returns (index,
    generation s, build s)."""
    from repro.data.synthetic import make_corpus_terms
    from repro.index import build_compact_streaming

    t0 = time.perf_counter()
    if docs is None:
        terms = make_corpus_terms(lengths, k=K, seed=seed, workers=workers)
    else:
        from repro.core.dna import document_terms
        from repro.data.synthetic import doc_genome
        uniq, inv = np.unique(docs, return_inverse=True)
        made = [document_terms([doc_genome(seed, int(d), lengths[d])], K)
                for d in uniq]
        terms = [made[i] for i in inv]
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    index, _ = build_compact_streaming(terms, path, params(),
                                       block_docs=block_docs,
                                       workers=workers, codec=codec)
    return index, gen_s, time.perf_counter() - t0


# --------------------------------------------------------------------------
# queries and the reference scorer
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Query:
    codes: np.ndarray           # 2-bit bases
    threshold: float | None     # None = the server's default
    top_k: int                  # 0 = threshold query
    origin: int                 # source document, -1 for a random string


def make_queries(lengths: np.ndarray, corpus_seed: int, n: int, seed: int,
                 docs=None, top_k_share: float = 0.25) -> list[Query]:
    """Half substrings of documents (positives), half random strings;
    lengths from one k-mer to read length; a share of top-k queries.
    ``docs`` (corpus ids per indexed document) restricts the positives
    to an index over those documents; origins are then its doc ids."""
    from repro.data.synthetic import doc_genome
    rng = np.random.default_rng([seed, 1])
    pool = np.arange(len(lengths)) if docs is None else np.unique(docs)
    out = []
    for i in range(n):
        qlen = int(rng.choice(QUERY_LENGTHS))
        top_k = TOP_K if rng.random() < top_k_share else 0
        if i % 2 == 0:
            d = int(rng.choice(pool[lengths[pool] >= qlen]))
            g = doc_genome(corpus_seed, d, lengths[d])
            s = int(rng.integers(0, len(g) - qlen + 1))
            origin = d if docs is None else int(np.argmax(docs == d))
            out.append(Query(g[s:s + qlen].copy(), None, top_k, origin))
        else:
            codes = rng.integers(0, 4, size=qlen, dtype=np.uint8)
            out.append(Query(codes, None, top_k, -1))
    return out


class Reference:
    """Plain NumPy scorer over the host copy of an index's arena: hash
    each distinct k-mer, read its row in every block, count set bits per
    document slot, select like the paper (coverage cutoff, or the k best
    by score then document id)."""

    def __init__(self, index, default_threshold: float):
        from repro.core import dna, hashing
        self._dna, self._hash = dna, hashing
        self.arena = np.asarray(index.storage.full_host())   # [R, W]
        lay = index.layout
        self.offset = lay.row_offset.astype(np.int64)
        self.width = lay.block_width.astype(np.uint64)
        self.doc_slot = np.asarray(lay.doc_slot, dtype=np.int64)
        self.default_threshold = default_threshold

    def scores(self, codes: np.ndarray) -> tuple[np.ndarray, int]:
        kmers = self._dna.pack_kmers(codes, K)
        if kmers.shape[0] == 0:
            return np.zeros(self.doc_slot.shape[0], np.int64), 0
        terms = np.unique(kmers, axis=0)
        h = self._hash.hash_terms_np(terms, 1)[:, 0].astype(np.uint64)
        rows = (h[:, None] % self.width).astype(np.int64) + self.offset
        words = np.ascontiguousarray(self.arena[rows])      # [n, nb, W]
        bits = np.unpackbits(words.view(np.uint8), axis=-1,
                             bitorder="little")             # [n, nb, 32W]
        slots = bits.sum(axis=0, dtype=np.int64).reshape(-1)
        return slots[self.doc_slot], terms.shape[0]

    def expect(self, q: Query) -> tuple[np.ndarray, np.ndarray]:
        s, n = self.scores(q.codes)
        if n == 0:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        order = np.lexsort((np.arange(s.shape[0]), -s))
        if q.top_k:
            sel = order[:q.top_k]
        else:
            thr = (self.default_threshold if q.threshold is None
                   else q.threshold)
            sel = order[s[order] >= max(1, math.ceil(thr * n))]
        return sel, s[sel]


def verify(phase: str, queries, results, ref: Reference) -> None:
    """Every result OK and equal to the reference; positives must find
    their source document (Bloom filters have no false negatives)."""
    bad = []
    for i, (q, r) in enumerate(zip(queries, results)):
        status = getattr(r, "status", None)
        result = getattr(r, "result", r)
        if status is not None and status.name != "OK":
            bad.append(f"query {i}: status {status.name}")
            continue
        want_ids, want_scores = ref.expect(q)
        if not (np.array_equal(np.asarray(result.doc_ids), want_ids)
                and np.array_equal(np.asarray(result.scores), want_scores)):
            bad.append(f"query {i}: {len(result.doc_ids)} hits != "
                       f"reference {len(want_ids)}")
        elif q.origin >= 0 and not q.top_k and q.origin not in want_ids:
            bad.append(f"query {i}: positive missed doc {q.origin}")
    if len(results) != len(queries):
        bad.append(f"{len(results)} results for {len(queries)} queries")
    if bad:
        raise SmokeFailure(f"{phase}: {len(bad)} failure(s): "
                           + "; ".join(bad[:5]))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

class CompileClock:
    """Seconds JAX spent in backend compiles, read per phase."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds = 0.0
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, duration, **_):
            if name == event:
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


def serve_closed(backend, queries, concurrency: int = 32) -> list:
    """Closed-loop load: submit a window of queries, drain, repeat."""
    ids = []
    for i in range(0, len(queries), concurrency):
        for q in queries[i:i + concurrency]:
            ids.append(backend.submit(q.codes, threshold=q.threshold,
                                      top_k=q.top_k or None))
        backend.drain()
    responses = backend.pop_responses()
    return [responses.get(rid) for rid in ids]


def _mix(counts) -> dict:
    return {m: int(c) for m, c in sorted(counts.items())}


def _need(phase: str, mix: dict, methods) -> None:
    if not any(m in mix for m in methods):
        raise SmokeFailure(f"{phase}: no {'/'.join(methods)} dispatch in "
                           f"{mix}")


def phase_server(index, queries, ref, *, label: str, device_methods,
                 **config):
    """(a)-(c): a QueryServer under closed-loop load; returns it."""
    from repro.serve import QueryServer, ServerConfig
    server = QueryServer(index, ServerConfig(**config))
    verify(label, queries, serve_closed(server, queries), ref)
    mix = _mix(server.planner.dispatch_counts)
    log(f"{label}: {len(queries)} queries, dispatch {mix}")
    _need(label, mix, device_methods)
    return server


def phase_bulk(server, queries, ref) -> None:
    """(d): one shard-major bulk sweep over the whole query set."""
    from repro.serve import BulkLane
    from repro.serve.bulk import BulkStatus
    lane = BulkLane(server)
    job = lane.submit([q.codes for q in queries],
                      threshold=ref.default_threshold)
    lane.drain()
    if job.status is not BulkStatus.DONE:
        raise SmokeFailure(f"bulk: job {job.status.value}: {job.error}")
    threshold_queries = [dataclasses.replace(q, top_k=0) for q in queries]
    verify("bulk", threshold_queries, job.results, ref)
    log(f"bulk: {job.n_queries} queries, {job.stats.shards_swept} shard(s) "
        f"swept, {job.stats.kernel_dispatches} kernel dispatch(es), "
        f"{job.staged_bytes_per_query:.0f} B staged per query")


def phase_net(server, queries, ref) -> None:
    """(e): queries over a real socket, loop and server in this process."""
    from repro.serve import NetClient, NetServer, ServingLoop
    loop = ServingLoop(server, workers=1)
    net = NetServer(loop, host="127.0.0.1", port=0).start()
    try:
        client = NetClient(*net.address, timeout_s=600.0)
        try:
            futs = [client.submit(q.codes, threshold=q.threshold,
                                  top_k=q.top_k or None) for q in queries]
            results = [f.result(600.0) for f in futs]
        finally:
            client.close()
    finally:
        net.close(drain=True)
    verify("net", queries, results, ref)
    log(f"net: {len(queries)} queries over {net.address[0]}:"
        f"{net.address[1]}, methods "
        f"{sorted({r.method for r in results})}")


def _in_use(dev) -> int:
    stats = dev.memory_stats()
    if not stats or "bytes_in_use" not in stats:
        raise SmokeFailure(f"{dev} reports no memory stats")
    return int(stats["bytes_in_use"])


def phase_four_chips(store_dir, queries, ref, *, check_memory=True) -> None:
    """The index sharded over four chips, served in this one process;
    every worker's tiles must sit on its own chip."""
    import jax
    from repro.launch.serve import make_multihost_frontend
    before = ({d.id: _in_use(d) for d in jax.devices()} if check_memory
              else {})
    fe = make_multihost_frontend(store_dir, hosts=4, replication=1,
                                 max_batch=32, max_wait_s=0.002,
                                 hedge_after_s=10.0)
    verify("four-chips", queries, serve_closed(fe, queries), ref)
    log(f"four-chips: {len(queries)} queries, dispatch "
        f"{_mix(fe.metrics.snapshot().methods)}")
    for name, w in sorted(fe.workers.items()):
        dev = w.device
        staged = 0
        for tile in w.tiles._tiles.values():
            parts = tile if isinstance(tile, tuple) else (tile,)
            for t in parts:
                arr = getattr(t, "lines", t)
                if set(arr.devices()) != {dev}:
                    raise SmokeFailure(f"{name}: tile on {arr.devices()}, "
                                       f"not {dev}")
                staged += int(arr.nbytes)
        log(f"four-chips: {name} on {dev}: {len(w.shard_ids)} shard(s), "
            f"{staged} B staged")
        if not check_memory:
            continue
        in_use = _in_use(dev)
        grew = in_use - before[dev.id]
        log(f"four-chips: {dev} bytes_in_use {in_use} (+{grew})")
        if staged == 0 or grew < staged:
            raise SmokeFailure(f"{name}: device {dev} does not hold its "
                               f"shards ({grew} B < {staged} B)")


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="shard the index over four chips; run only that "
                         "path and the reference")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-docs", type=int, default=None,
                    help="documents (default 4096; 8192 with --four-chips "
                         "so that every chip holds shards)")
    ap.add_argument("--arena-gib", type=float, default=1.25,
                    help="least arena size to generate (GiB)")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--workers", type=int,
                    default=max(1, min(12, (os.cpu_count() or 2) - 1)))
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.compile_cache import cache_bytes, enable_compile_cache
    from repro.kernels import ops
    cache = enable_compile_cache()
    if ops._use_interpret():
        raise SmokeFailure("kernels would run in interpret mode")
    dev = devices[0]
    log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")
    log(f"compile cache: {cache} ({cache_bytes(cache)} B before)")
    clock = CompileClock()
    n_docs = args.n_docs or (8192 if args.four_chips else 4096)

    with tempfile.TemporaryDirectory(prefix="cobs-smoke-") as tmp:
        t0 = time.perf_counter()
        lengths = size_lengths(n_docs, int(args.arena_gib * 2**30),
                               args.seed)
        index, gen_s, build_s = build_store(Path(tmp) / "store", lengths,
                                            args.seed, args.workers)
        arena = index.storage.nbytes()
        log(f"corpus: {n_docs} docs, {int(lengths.sum())} bases "
            f"(max/mean {lengths.max() / lengths.mean():.1f}), k={K}, "
            f"FPR {FPR}, {BLOCK_DOCS}-doc blocks; generated in "
            f"{gen_s:.1f}s, built in {build_s:.1f}s")
        log(f"arena: {index.layout.n_blocks} blocks, "
            f"{index.storage.shape[0]} rows x {index.storage.shape[1]} "
            f"words = {arena} B in {index.storage.n_shards} shard(s)")
        ref = Reference(index, 0.8)
        queries = make_queries(lengths, args.seed, args.queries, args.seed + 1)

        def timed(name, fn, *a, **kw):
            c0, t1 = clock.seconds, time.perf_counter()
            out = fn(*a, **kw)
            log(f"{name}: {time.perf_counter() - t1:.1f}s wall, "
                f"{clock.seconds - c0:.1f}s compiling")
            return out

        if args.four_chips:
            timed("four-chips", phase_four_chips, Path(tmp) / "store",
                  queries, ref)
        else:
            device_methods = ("lookup", "dedup", "vertical", "unpack")
            server = timed("a/server", phase_server, index, queries, ref,
                           label="a/server", device_methods=device_methods)
            in_use = _in_use(dev)
            log(f"memory: arena {arena} B logical, device bytes_in_use "
                f"{in_use} ({in_use / arena:.3f}x)")
            timed("b/pruned", phase_server, index, queries, ref,
                  label="b/pruned", device_methods=("lookup_p",),
                  pruned=True)
            rng = np.random.default_rng([args.seed, 3])
            small = (lengths >= READ_LEN) & (lengths <= np.median(lengths))
            picks = rng.choice(np.nonzero(small)[0], 8, replace=False)
            docs = np.repeat(picks, BLOCK_DOCS // len(picks))
            cindex, gen_c, build_c = build_store(
                Path(tmp) / "rowdict", lengths, args.seed, args.workers,
                codec="rowdict", docs=docs)
            log(f"rowdict store: {len(docs)} docs ({len(picks)} samples "
                f"repeated), dict ratio {cindex.storage.dict_ratio():.1f}, "
                f"built in {gen_c + build_c:.1f}s")
            cqueries = make_queries(lengths, args.seed, args.queries // 2,
                                    args.seed + 2, docs=docs)
            timed("c/compressed", phase_server, cindex, cqueries,
                  Reference(cindex, 0.8), label="c/compressed",
                  device_methods=("lookup_c", "dedup_c"), compressed=True)
            timed("d/bulk", phase_bulk, server,
                  make_queries(lengths, args.seed, args.queries // 2,
                                args.seed + 3),
                  ref)
            timed("e/net", phase_net, server,
                  make_queries(lengths, args.seed, 16, args.seed + 4), ref)
        log(f"total: {time.perf_counter() - t0:.1f}s, "
            f"{clock.seconds:.1f}s compiling")
    log(f"compile cache: {cache} ({cache_bytes(cache)} B after)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
