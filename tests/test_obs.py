"""Observability-plane tests: the metrics registry + Prometheus
exposition, request tracing (spans, slow-query JSONL sink), the kernel
profiler feeding the autotuner live costs, and the end-to-end
acceptance path — a NetClient query whose trace id comes back with a
per-stage breakdown AND shows up, same id and span tree, in the
server-side slow-query log.

The concurrency tests exist because the metrics surface is read by
monitoring threads while socket threads and the scatter pool write it:
pre-registry ServingMetrics iterated bare deques during appends, which
a concurrent reader can turn into ``RuntimeError: deque mutated during
iteration`` — the hammer test pins the lock-guarded fix.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro.core import IndexParams, build_compact
from repro.data import make_corpus, make_queries
from repro.index import ShardPlacement, build_compact_streaming
from repro.kernels.autotune import (LIVE_PREFIX, KernelTuner, TuningCache)
from repro.obs import (EventLog, KernelProfiler, MetricsRegistry, Trace,
                       Tracer, render_prometheus)
from repro.obs.events import read_jsonl
from repro.obs.export import parse_prometheus
from repro.serve import (Frontend, FrontendConfig, NetClient, NetServer,
                         QueryServer, ServerConfig, ServingLoop,
                         ServingMetrics, ShardWorker, Status)

PARAMS = IndexParams(n_hashes=1, fpr=0.3, kmer=15)


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    c = make_corpus(96, k=15, mean_length=400, sigma=1.0, seed=13)
    index = build_compact(c.doc_terms, PARAMS, block_docs=32, row_align=64)
    store = tmp_path_factory.mktemp("obs-store") / "v2"
    mapped, _ = build_compact_streaming(c.doc_terms, store, PARAMS,
                                        block_docs=32, row_align=64)
    assert mapped.storage.n_shards >= 3
    return c, index, store


# --------------------------------------------------------------------------
# Metrics registry + Prometheus exposition
# --------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("depth")
    g.set(3)
    g.inc(-1)
    assert g.value == 2 and g.max == 3
    h = reg.histogram("lat_s", window=8)
    for v in range(10):
        h.observe(float(v))
    # window slid to the last 8 samples; lifetime count/sum exact
    assert len(h) == 8 and h.count == 10 and h.sum == sum(range(10))
    assert h.percentile(100) == 9.0
    assert h.values().min() == 2.0

    # constructors are idempotent: same name -> same object ...
    assert reg.counter("reqs_total") is c
    # ... and kind / label skew fails loudly
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")
    with pytest.raises(ValueError):
        reg.counter("reqs_total", labels=("method",))


def test_registry_labeled_families():
    reg = MetricsRegistry()
    fam = reg.counter("tiles_total", labels=("shard", "event"))
    fam.labels(0, "fault").inc()
    fam.labels(0, "fault").inc()
    fam.labels("1", "hit").inc(3)
    # label values coerce to str; children keyed per tuple
    assert fam.labels("0", "fault").value == 2
    kids = dict(fam.children())
    assert kids[("0", "fault")].value == 2
    assert kids[("1", "hit")].value == 3
    with pytest.raises(ValueError):
        fam.labels("only-one")


def test_prometheus_render_parse_round_trip():
    reg = MetricsRegistry()
    reg.counter("served_total", "requests served").inc(7)
    reg.gauge("conns", "open connections").set(2)
    fam = reg.counter("by_method_total", labels=("method",))
    fam.labels("fused").inc(4)
    fam.labels('we"ird\nname').inc(1)            # escaping survives
    h = reg.histogram("wait_s")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    text = render_prometheus(reg)
    assert "# TYPE served_total counter" in text
    assert "# TYPE wait_s summary" in text
    parsed = parse_prometheus(text)
    assert parsed["served_total"] == 7
    assert parsed["conns"] == 2
    assert parsed['by_method_total{method="fused"}'] == 4
    assert parsed['wait_s{quantile="0.5"}'] == 2.5
    assert parsed["wait_s_count"] == 4 and parsed["wait_s_sum"] == 10


# --------------------------------------------------------------------------
# Event log (JSONL)
# --------------------------------------------------------------------------

def test_event_log_jsonl_round_trip(tmp_path):
    path = tmp_path / "events.jsonl"
    with EventLog(path, ring=4) as log:
        for i in range(6):
            log.emit("slow_query", {"trace_id": i})
        log.emit("other", {"x": 1})
    assert log.emitted == 7
    # the ring is bounded; kind filtering works on the tail
    tail = log.tail(kind="slow_query")
    assert [e["trace_id"] for e in tail] == [3, 4, 5]
    events = read_jsonl(path)
    assert len(events) == 7
    assert all("ts" in e and "kind" in e for e in events)
    # a torn trailing line (crash mid-write) parses around, not over
    with open(path, "a") as fh:
        fh.write('{"kind": "slow_q')
    assert len(read_jsonl(path)) == 7
    # memory-only log never touches disk
    mem = EventLog(None)
    mem.emit("k", {})
    assert mem.path is None and mem.emitted == 1


# --------------------------------------------------------------------------
# Traces
# --------------------------------------------------------------------------

def test_trace_spans_and_stage_totals():
    t = Trace(9, request_id=4, started_s=10.0)
    t.add("queue_wait", 10.0, 10.5)
    t.add("kernel_score", 10.5, 11.0, {"method": "fused"})
    t.add("kernel_score", 11.0, 11.25)
    assert not t.done
    totals = t.stage_totals()
    assert totals == {"queue_wait": 0.5, "kernel_score": 0.75}
    assert list(totals) == ["queue_wait", "kernel_score"]  # causal order
    d = t.to_json()
    assert d["trace_id"] == 9 and len(d["spans"]) == 3
    assert d["spans"][1]["tags"] == {"method": "fused"}


def test_tracer_ring_find_and_slow_sink():
    sink = EventLog(None)
    clock_now = [100.0]
    tracer = Tracer(ring=4, slow_ms=50.0, sink=sink,
                    clock=lambda: clock_now[0])
    fast = tracer.begin(1)
    clock_now[0] += 0.010
    tracer.finish(fast)                       # 10ms: under budget
    slow = tracer.begin(2, trace_id=777)      # wire-minted id honored
    assert slow.trace_id == 777
    slow.add("kernel_score", clock_now[0], clock_now[0] + 0.2)
    clock_now[0] += 0.200
    tracer.finish(slow)
    tracer.finish(slow)                       # idempotent: no double emit
    assert tracer.finished_count == 2 and tracer.slow_count == 1
    assert tracer.find(777) is slow and tracer.find(12345) is None
    (ev,) = sink.tail(kind="slow_query")
    assert ev["trace_id"] == 777
    assert ev["spans"][0]["name"] == "kernel_score"
    assert ev["duration_ms"] == pytest.approx(200.0)
    # disabled tracer: begin is None, finish(None) a no-op
    off = Tracer(enabled=False)
    assert off.begin(1) is None
    off.finish(None)
    assert off.finished_count == 0


# --------------------------------------------------------------------------
# Kernel profiler -> registry, and -> autotuner live costs (satellite)
# --------------------------------------------------------------------------

def test_profiler_records_into_registry():
    reg = MetricsRegistry()
    prof = KernelProfiler(reg, None)
    for i in range(3):
        prof.record(method="fused", bucket=64, batch=8,
                    seconds=0.001 * (i + 1), word_block=8, shard=2)
    assert prof.count == 3
    assert prof.records()[-1]["shard"] == 2
    assert "bytes_moved" not in prof.records()[-1]
    hist = reg.get("kernel_score_seconds").labels("fused", 64, 8)
    assert hist.count == 3
    assert hist.sum == pytest.approx(0.006)
    # arena bytes are counted by the benchmark's roofline, not here
    assert reg.get("kernel_bytes_moved_total") is None
    # disabled profiler is a no-op
    off = KernelProfiler(reg, None, enabled=False)
    off.record(method="fused", bucket=64, batch=8, seconds=1.0)
    assert off.count == 0


def test_profiler_feeds_tuner_observed_costs(tmp_path, built):
    """Live kernel timings promote to observed=True TuningCache entries
    that the planner's cost lookup then PREFERS over synthetic tunes."""
    _, index, _ = built
    cache = TuningCache(tmp_path / "tuning.json")
    tuner = KernelTuner.for_index(index, cache, enabled=False)
    tuner.live_min_samples = 4
    reg = MetricsRegistry()
    prof = KernelProfiler(reg, tuner)
    assert tuner.entry("lookup", 64, 4) is None          # cold, no tune
    for _ in range(4):
        prof.record(method="lookup", bucket=64, batch=4,
                    seconds=0.002, word_block=8, grid_order="qw")
    e = tuner.entry("lookup", 64, 4)
    assert e is not None and e.observed
    assert e.word_block == 8 and e.grid_order == "qw"
    assert e.cost_us == pytest.approx(2000.0)
    # persisted under the live prefix and survives reopen
    key = LIVE_PREFIX + tuner.key("lookup", 64, 4)
    assert key in TuningCache(tmp_path / "tuning.json").entries
    # non-tunable methods (dedup pair) never pollute the live cache
    before = tuner.observations
    prof.record(method="fused_dedup", bucket=64, batch=4,
                seconds=5.0, word_block=8)
    assert tuner.observations == before


# --------------------------------------------------------------------------
# ServingMetrics under concurrency (satellite: lock-guarded reads)
# --------------------------------------------------------------------------

def test_metrics_concurrent_hammer():
    """Writers (request/batch/worker/shard-tile recorders) race readers
    (percentiles, snapshots, the Prometheus renderer) across threads;
    the run must be exception-free and the totals exact."""
    m = ServingMetrics()
    n_writers, per_writer = 4, 400
    errors: list = []
    stop = threading.Event()

    def writer(wi: int) -> None:
        try:
            for i in range(per_writer):
                m.record_request(wait_s=0.001 * (i % 7),
                                 service_s=0.002, cached=False)
                m.record_batch(4, 0.5, "fused")
                m.record_worker(f"h{wi}", 0.001 * (i % 5 + 1))
                m.record_shard_tile(wi, "fault")
                m.set_queue_depth(i % 9)
        except Exception as e:                 # pragma: no cover
            errors.append(("writer", wi, e))

    def reader() -> None:
        try:
            while not stop.is_set():
                m.percentile_ms(99)
                m.worker_recent_s
                m.shard_tile_counts("fault")
                m.snapshot()
                render_prometheus(m.registry)
        except Exception as e:                 # pragma: no cover
            errors.append(("reader", e))

    writers = [threading.Thread(target=writer, args=(i,))
               for i in range(n_writers)]
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join(timeout=120)
    stop.set()
    for t in readers:
        t.join(timeout=120)
    assert not errors, errors
    assert m.served == n_writers * per_writer
    assert m.n_batches == n_writers * per_writer
    assert m.shard_tile_counts("fault") == {
        str(i): per_writer for i in range(n_writers)}
    assert m.percentile_ms(50) >= 0.0
    snap = m.snapshot()
    assert snap.served == n_writers * per_writer


# --------------------------------------------------------------------------
# Per-shard tile counters through the sharded frontend (satellite)
# --------------------------------------------------------------------------

def test_frontend_shard_tile_counters_use_global_ids(built):
    """Worker tile-cache events surface in the frontend registry keyed
    by GLOBAL shard id (workers cache by local substore index — the
    observer must translate), and dispatch spans name the shard."""
    c, _, store = built
    nodes = ["h0", "h1"]
    place = ShardPlacement.for_store(store, nodes, replication=2)
    held = place.replica_assignment()
    workers = {n: ShardWorker(n, store, held[n]) for n in nodes if held[n]}
    fe = Frontend(workers, place,
                  FrontendConfig(max_batch=8, max_wait_s=0.0,
                                 hedge_after_s=1e9))
    qs, _ = make_queries(c, n_pos=3, n_neg=1, length=100, seed=21)
    for q in qs:
        fe.submit(q, threshold=0.7)
    fe.drain()
    assert all(r.status == Status.OK for r in fe.pop_responses().values())

    touched = set()
    for event in ("fault", "prefetch"):
        touched |= set(fe.metrics.shard_tile_counts(event))
    hits = fe.metrics.shard_tile_counts("hit")
    # every global shard was staged once (fault or prefetch), then hit
    assert touched == {str(g) for g in range(place.n_shards)}
    assert set(hits) <= {str(g) for g in range(place.n_shards)}
    assert sum(hits.values()) > 0
    # the trace's dispatch spans carry the same global shard ids
    shards_in_spans = set()
    for trace in fe.tracer.recent():
        for s in trace.spans():
            if s.name == "shard_dispatch":
                shards_in_spans.add(str(s.tags["shard"]))
    assert shards_in_spans == {str(g) for g in range(place.n_shards)}


# --------------------------------------------------------------------------
# Acceptance: socket query -> trace id + breakdown -> server slow log
# --------------------------------------------------------------------------

def test_socket_trace_matches_server_slow_log(built, tmp_path):
    """A NetClient query returns its trace id and per-stage breakdown,
    and the server's slow-query JSONL contains the MATCHING span tree
    for that id — the end-to-end observability acceptance path."""
    c, index, _ = built
    log = tmp_path / "slow.jsonl"
    server = QueryServer(index, ServerConfig(
        max_batch=4, max_wait_s=0.001,
        trace_slow_ms=1e-6,                  # everything is "slow"
        trace_log=str(log)))
    net = NetServer(ServingLoop(server)).start()
    (q,), _ = make_queries(c, n_pos=1, n_neg=0, length=120, seed=51)
    try:
        with NetClient(*net.address, timeout_s=60.0) as cl:
            r = cl.search(q, threshold=0.8)
            assert r.status == Status.OK and r.trace_id != 0
            assert r.stages and "queue_wait" in r.stages
            assert "kernel_score" in r.stages
    finally:
        net.close()

    # the deliver span is added after the RESULT frame is written, so
    # give the loop a beat to seal + flush the trace
    deadline = time.monotonic() + 10.0
    events = []
    while time.monotonic() < deadline:
        events = [e for e in read_jsonl(log)
                  if e.get("kind") == "slow_query"
                  and e.get("trace_id") == r.trace_id]
        if events:
            break
        time.sleep(0.02)
    assert events, f"trace {r.trace_id} never reached {log}"
    (ev,) = events

    # span tree matches the breakdown the wire carried: every wire stage
    # appears with the same total, and the log additionally has the
    # deliver span the loop appends after the frame goes out
    by_stage: dict = {}
    for s in ev["spans"]:
        by_stage[s["name"]] = (by_stage.get(s["name"], 0.0)
                               + s["end_s"] - s["start_s"])
    for name, seconds in r.stages.items():
        assert by_stage.get(name, -1.0) == pytest.approx(seconds)
    assert "deliver" in by_stage
    assert ev["duration_ms"] > 0
    # intervals are sane: every span inside [started_s, ended_s]
    for s in ev["spans"]:
        assert ev["started_s"] <= s["start_s"] <= s["end_s"]
        assert s["end_s"] <= ev["ended_s"] + 1e-9
    # and the server-side ring has the same sealed trace; the writer
    # thread may add the reply's outbox_wait and write after the seal
    trace = server.tracer.find(r.trace_id)
    assert trace is not None and trace.done
    assert trace.stage_totals().keys() - by_stage.keys() <= {
        "outbox_wait", "write"}
    assert by_stage.keys() <= trace.stage_totals().keys()


def test_stats_snapshot_counts_traces(built):
    """MetricsSnapshot surfaces the tracer's finished/slow counters (the
    JSON STATS body clients poll)."""
    c, index, _ = built
    server = QueryServer(index, ServerConfig(max_batch=4, max_wait_s=0.0,
                                             trace_slow_ms=1e-6))
    qs, _ = make_queries(c, n_pos=2, n_neg=1, length=100, seed=53)
    for q in qs:
        server.submit(q, threshold=0.7)
    server.drain()
    assert all(r.status == Status.OK
               for r in server.pop_responses().values())
    snap = server.metrics.snapshot()
    assert snap.traces_finished >= len(qs)
    assert snap.slow_queries >= len(qs)      # threshold is microscopic
    assert json.dumps(snap.__dict__)         # snapshot stays serializable
