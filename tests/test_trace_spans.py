"""Batch spans inside the serving path: one recorder per scored
micro-batch, on the tracer's clock and on the profiler's.

Every branch of ``QueryServer.score_batch`` (dense, paged, row-dedup,
pruned) records its stages once per batch, tagged with the batch id and
the enclosing stage, and copies them into each member request's trace.
With tracing off nothing is recorded, annotated or timed beyond the
profiler's own clock reads. Inside a ``jax.profiler`` capture each span
is also a host annotation of the same name and length, which is what
puts the program's stages on the device trace's clock. Over the socket
a request's trace also holds the wire's and the loop's spans.
"""
import glob
import os
import time

import numpy as np
import pytest

from repro.core import IndexParams
from repro.data import make_corpus
from repro.index import build_compact_streaming
from repro.obs import trace as trace_mod
from repro.serve import (NetClient, NetServer, QueryServer, ServerConfig,
                         ServingLoop, Status)

PARAMS = IndexParams(n_hashes=1, fpr=0.03, kmer=15)
Q = 4          # one batch's requests (max_batch)

# branch -> (store, config, spans every batch of it records)
KERNEL = {"plan", "kernel_score", "upload", "tile_get", "dispatch",
          "readback"}
BRANCHES = {
    "dense": ("dense", dict(dedup_min_rate=None), KERNEL),
    "paged": ("paged", dict(dedup_min_rate=None), KERNEL),
    "dedup": ("paged", dict(dedup_min_rate=0.0), KERNEL | {"dedup_plan"}),
    "pruned": ("paged", dict(pruned=True, prune_chunk=16, prune_min_rate=0.0),
               {"plan", "prune", "prune_plan", "prune_gather",
                "prune_dispatch", "prune_sync"}),
}


@pytest.fixture(scope="module")
def corpus_stores(tmp_path_factory):
    c = make_corpus(24, k=15, mean_length=160, min_length=120, seed=3)
    terms = [c.doc_terms[i % 24] for i in range(24 * 6)]
    root = tmp_path_factory.mktemp("span-stores")
    paged, _ = build_compact_streaming(terms, root / "paged", PARAMS,
                                       block_docs=32, blocks_per_shard=1)
    dense, _ = build_compact_streaming(terms, root / "dense", PARAMS,
                                       block_docs=32, blocks_per_shard=64)
    assert paged.storage.n_shards > 2 and dense.storage.n_shards == 1
    return c, {"paged": paged, "dense": dense}


def _patterns(c, n):
    return [c.documents[i % len(c.documents)][5 + i: 95 + i]
            for i in range(n)]


def _server(corpus_stores, branch, **extra):
    c, stores = corpus_stores
    store, cfg, _ = BRANCHES[branch]
    return c, QueryServer(stores[store], ServerConfig(
        max_batch=Q, max_wait_s=0.0, result_cache=0, row_cache=0,
        **cfg, **extra))


def _batch_spans(traces) -> dict:
    """batch id -> that batch's spans, each once."""
    out: dict = {}
    for t in traces:
        for s in t.spans():
            if "batch" in s.tags:
                out.setdefault(s.tags["batch"], {})[id(s)] = s
    return {b: list(v.values()) for b, v in out.items()}


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_each_branch_records_its_batch_spans_once(corpus_stores, branch):
    c, srv = _server(corpus_stores, branch)
    pats = _patterns(c, 2 * Q)
    rids = [srv.submit(p, threshold=0.9) for p in pats]
    srv.drain()
    got = srv.pop_responses()
    assert all(got[r].status == Status.OK for r in rids)
    traces = [got[r].trace for r in rids]

    # one batch id per batch: every request's batch spans share one id,
    # the two batches have two ids
    ids = [{s.tags["batch"] for s in t.spans() if "batch" in s.tags}
           for t in traces]
    assert all(len(i) == 1 for i in ids)
    assert len(set().union(*ids)) == 2

    want = BRANCHES[branch][2]
    for spans in _batch_spans(traces).values():
        names = {s.name for s in spans}
        assert want <= names, names
        assert (branch == "pruned") == ("kernel_score" not in names)
        # no two spans of one batch with the same name and interval
        keys = [(s.name, s.start_s, s.end_s) for s in spans]
        assert len(keys) == len(set(keys))
        # every child inside its parent
        for s in spans:
            parent = s.tags.get("parent")
            if parent is None:
                continue
            assert any(p.name == parent and p.start_s <= s.start_s
                       and s.end_s <= p.end_s for p in spans), (s, parent)
        kids = {s.name: s.tags.get("parent") for s in spans}
        if branch == "pruned":
            assert {kids[n] for n in want - {"plan", "prune"}} == {"prune"}
            prune = next(s for s in spans if s.name == "prune")
            assert prune.tags["syncs"] == sum(
                s.name == "prune_sync" for s in spans)
        else:
            assert {kids[n] for n in KERNEL - {"plan", "kernel_score"}} \
                == {"kernel_score"}
            # the shards' scores come back in one transfer, dense or paged
            (back,) = [s for s in spans if s.name == "readback"]
            assert back.tags["copies"] == 1
    if branch == "pruned":
        syncs = [s.tags["syncs"] for spans in _batch_spans(traces).values()
                 for s in spans if s.name == "prune"]
        assert srv.metrics.prune_syncs == sum(syncs) > 0
    # the batch's spans sit between the request's queue_wait and select
    for t in traces:
        by = {s.name: s for s in t.spans()}
        assert by["queue_wait"].end_s <= by["plan"].start_s
        assert by["plan"].end_s <= by["select"].start_s


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_untraced_batch_records_and_times_nothing(corpus_stores, branch,
                                                  monkeypatch):
    c, stores = corpus_stores
    made = {"recorders": 0, "spans": 0, "annotations": 0}

    class CountedSpan(trace_mod.Span):
        def __init__(self, *a, **kw):
            made["spans"] += 1
            super().__init__(*a, **kw)

    def counted_annotation(name):
        made["annotations"] += 1
        raise AssertionError(f"annotation {name!r} with tracing off")

    monkeypatch.setattr(trace_mod, "Span", CountedSpan)
    monkeypatch.setattr(trace_mod.profiler, "TraceAnnotation",
                        counted_annotation)
    monkeypatch.setattr(trace_mod, "BatchRecorder",
                        lambda *a: made.__setitem__("recorders", 1))
    reads = [0]

    def clock():
        reads[0] += 1
        return time.monotonic()

    store, cfg, _ = BRANCHES[branch]
    srv = QueryServer(stores[store], ServerConfig(
        max_batch=Q, max_wait_s=0.0, result_cache=0, row_cache=0,
        tracing=False, **cfg), clock=clock)
    for p in _patterns(c, Q):
        srv.submit(p, threshold=0.9)
    (batch,) = srv.poll_batches(force=True)
    reads[0] = 0
    srv.score_batch(batch)
    assert made == {"recorders": 0, "spans": 0, "annotations": 0}
    # the batch start and end, the kernel's profile and each request's
    # selection: no read per shard or per chunk
    assert reads[0] == 4 + Q
    assert all(r.status == Status.OK and r.trace is None
               for r in srv.pop_responses().values())


def test_profiler_capture_holds_each_span_on_its_clock(corpus_stores,
                                                       tmp_path):
    """Each recorded span is a host annotation of the same name and
    length in the profiler's trace, at one offset between the clocks."""
    import jax
    from jax._src.profiler import ProfileData

    c, srv = _server(corpus_stores, "paged")
    pats = _patterns(c, 2 * Q)
    for p in pats[:Q]:                   # stage every tile beforehand
        srv.submit(p, threshold=0.9)
    srv.drain()
    srv.pop_responses()
    for p in pats[Q:]:
        srv.submit(p, threshold=0.9)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        srv.drain()
    finally:
        jax.profiler.stop_trace()
    traces = [r.trace for r in srv.pop_responses().values()]
    (spans,) = _batch_spans(traces).values()
    assert {s.name for s in spans} == KERNEL     # no tile staged in it

    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    names = {s.name for s in spans}
    marks: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    marks.setdefault(ev.name, []).append(
                        (ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    offsets = []
    for name in names:
        mine = sorted((s.start_s, s.duration_s) for s in spans
                      if s.name == name)
        theirs = sorted(marks.get(name, []))
        assert len(theirs) == len(mine), name
        for (s0, d0), (s1, d1) in zip(mine, theirs):
            assert abs(d1 - d0) < 0.5e-3, (name, d0, d1)
            offsets.append(s1 - s0)
    assert max(offsets) - min(offsets) < 1e-3


def test_socket_round_trip_holds_wire_and_lock_spans(corpus_stores):
    c, srv = _server(corpus_stores, "paged")
    net = NetServer(ServingLoop(srv)).start()
    try:
        with NetClient(*net.address, timeout_s=60.0) as cl:
            r = cl.search(_patterns(c, 1)[0], threshold=0.9)
            assert r.status == Status.OK
        trace, deadline = None, time.monotonic() + 10.0
        while time.monotonic() < deadline:     # the writer adds "write"
            trace = srv.tracer.find(r.trace_id)
            if trace is not None and any(s.name == "write"
                                         for s in trace.spans()):
                break
            time.sleep(0.01)
    finally:
        net.close()
    by = {s.name: s for s in trace.spans()}
    assert {"decode", "lock_wait", "encode", "outbox_wait", "write"} <= set(by)
    assert by["decode"].start_s == trace.started_s   # starts at the frame
    assert (by["decode"].end_s <= by["lock_wait"].start_s
            <= by["lock_wait"].end_s <= by["queue_wait"].start_s)
    assert by["select"].end_s <= by["encode"].start_s
    assert by["encode"].end_s <= by["outbox_wait"].start_s
    assert by["outbox_wait"].end_s <= by["write"].start_s
    # the wire's stage block is what the trace held at the reply
    assert {"decode", "lock_wait", "queue_wait"} <= set(r.stages)
    assert np.isclose(r.stages["decode"], by["decode"].duration_s)
