#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload CELL --seed N --seconds S --trace 0|1

The cell names a configuration (``bench/configs/<config>.json``: the
documents, the store, the ``ServerConfig`` and, for an index sharded over
the chips of one host, a ``frontend``) and a traffic mix
(``bench/traffic/<cell>.json``: the loop, the transport and the query
mix). Its metrics are the end-to-end ones of ``BENCHMARK.json`` with
``--trace 0`` and the per-layer ones with ``--trace 1``, each computed by
``bench/metrics/<metric>.py``. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``, then ``checks``, the numbers the
correctness comparison held against their limits.

It needs a TPU: anywhere else it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import gc
import importlib.util
import json
import math
import sys
import tempfile
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import datagen                     # noqa: E402
import devtrace                    # noqa: E402
import loadgen                     # noqa: E402
import reference as ref            # noqa: E402
import watch                       # noqa: E402

ANSWER_GRACE_S = 60.0              # how long past the close answers may come


def log(msg: str) -> None:
    """A set-up milestone on standard error, with seconds since start."""
    print(f"bench: {time.monotonic() - T_START:7.2f}s {msg}", file=sys.stderr,
          flush=True)


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# -- the specification --------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(spec: dict, name: str, traffic_dir: Path = BENCH / "traffic"
              ) -> tuple[dict, dict, dict]:
    """(cell, configuration file, traffic file)."""
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return (cell, load_json(ROOT / conf["file"]),
            load_json(traffic_dir / f"{cell['traffic']}.json"))


def cell_metrics(spec: dict, cell: dict, traced: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run."""
    def listed(m):
        return "workloads" not in m or cell["name"] in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if listed(m)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if listed(m) and m["moves"] in names]


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -- the device ---------------------------------------------------------------

def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def memory_peaks(devs) -> dict[int, int]:
    """Each device's ``peak_bytes_in_use``, by device id."""
    return {int(d.id): int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                          0))
            for d in devs}


# -- the server ---------------------------------------------------------------

def check_hosts(config: dict, cell: dict, devs) -> None:
    """A sharded configuration puts each host on a chip of its own: refuse
    one with more hosts than the cell's chips or the devices JAX sees
    (the program would stack several hosts on one chip)."""
    fe = config.get("frontend")
    if fe is None:
        return
    hosts = int(fe["hosts"])
    if hosts > int(cell["chips"]):
        raise SystemExit(f"configuration {cell['config']!r} has {hosts} "
                         f"hosts; cell {cell['name']!r} asks for "
                         f"{cell['chips']} chip(s)")
    if hosts > len(devs):
        raise NoChip(f"configuration {cell['config']!r} has {hosts} hosts; "
                     f"JAX sees {len(devs)} device(s)")


def build_server(config: dict, index, store_dir: Path, traced: bool,
                 trace_ring: int):
    """The configuration's server: a ``QueryServer`` over the index, or,
    with a ``frontend`` key (``hosts``, ``replication``), the program's
    in-process ``Frontend`` over one ``ShardWorker`` per host, host i's
    tiles on device i, with ``config["server"]``'s serving knobs. Whatever
    the configuration leaves unset is the program's default."""
    from repro.serve import QueryServer, ServerConfig
    fe = config.get("frontend")
    if fe is None:
        return QueryServer(index, ServerConfig(
            **config["server"], tracing=traced, trace_ring=trace_ring))
    from repro.launch.serve import make_multihost_frontend
    from repro.obs import Tracer
    from repro.serve.frontend import FrontendConfig
    d = FrontendConfig()
    kw = {"max_batch": d.max_batch, "max_wait_s": d.max_wait_s,
          "hedge_after_s": d.hedge_after_s, **config["server"]}
    frontend = make_multihost_frontend(
        store_dir, hosts=int(fe["hosts"]),
        replication=int(fe["replication"]), tracing=traced, **kw)
    if traced:
        # make_multihost_frontend keeps FrontendConfig's ring of 256
        # traces; the run's readers need every request's
        frontend.tracer = Tracer(enabled=True, ring=trace_ring,
                                 slow_ms=frontend.tracer.slow_ms,
                                 sink=frontend.events, clock=frontend.clock)
        frontend.metrics.tracer = frontend.tracer
    return frontend


def placement_of(server) -> dict[int, list[int]] | None:
    """Device id -> the shard ids whose tiles it holds, for a sharded
    server; None for one ``QueryServer``."""
    workers = getattr(server, "workers", None)
    if workers is None:
        return None
    out: dict[int, list[int]] = {}
    for w in workers.values():
        out.setdefault(int(w.device.id), []).extend(
            int(g) for g in w.shard_ids)
    return out


# -- warm-up ------------------------------------------------------------------

def warm_up(server, index, config: dict, mix: dict, seed: int, order
            ) -> None:
    """Run every (bucket, padded batch) shape this mix can flush through
    the server, then empty its caches and counters. A pruned
    configuration also compiles its chunk kernel at every unique-row
    count a chunk can gather."""
    # a batch of Q pads to the next power of two (max_batch is one)
    sizes = [1 << i for i in range(server.config.max_batch.bit_length())]
    for j, n in enumerate(mix["lengths"]):
        warm = datagen.make_queries(
            seed, 100 + j, sum(sizes),
            {**mix, "lengths": {n: 1.0}, "positive_share": 0.0}, order)
        datagen.compile_terms(warm, config["kmer"])
        pool = iter(warm)
        for q_pad in sizes:
            for _ in range(q_pad):
                q = next(pool)
                server.submit(terms=q.terms, threshold=q.threshold,
                              top_k=q.top_k or None)
            server.drain()
    if server.config.pruned:
        warm_chunk_kernels(index, sizes, server.config.prune_chunk,
                           config["server"].get("word_block"))
    server.pop_responses()
    server.reset_metrics(clear_caches=True)


def warm_chunk_kernels(index, sizes, ct: int, word_block) -> None:
    """The pruned executor pads a chunk's unique rows to a power of two
    (at least 8) and its queries to a power of two: compile each pair."""
    import jax.numpy as jnp
    from repro.kernels import ops
    w = int(index.storage.shape[1])
    for q in sizes:
        u = 8
        while u <= max(8, q * ct):
            acc = ops.chunk_acc_init(q, 1, w)
            cells = jnp.zeros((q, 1, ct), jnp.int32)
            _, bmax = ops.bitslice_chunk_score_dedup(
                jnp.zeros((u, w), jnp.uint32), cells, cells, acc,
                word_block=word_block)
            np.asarray(bmax)
            u *= 2


# -- one run ------------------------------------------------------------------

def check_answers(rec, queries, reference, sample: int, seed: int) -> dict:
    """Hold a seeded sample of the window's OK answers to the reference;
    every request must have been answered."""
    issued = rec.issued
    statuses = [rec.status(i) for i in issued]
    unanswered = sum(s is None for s in statuses)
    errors = sum(s == "FAILED" for s in statuses)
    ok = np.array([i for i, s in zip(issued, statuses) if s == "OK"],
                  dtype=np.int64)
    rng = np.random.default_rng([seed, 4])
    picked = (rng.choice(ok, size=min(sample, ok.size), replace=False)
              if ok.size else ok)
    mismatched = missed = 0
    for i in picked:
        q, res = queries[i], rec.answers[i].result
        ids, scores = reference.expect(q.codes, q.threshold, q.top_k)
        if not (np.array_equal(np.asarray(res.doc_ids), ids)
                and np.array_equal(np.asarray(res.scores), scores)):
            mismatched += 1
        if q.origin >= 0 and not q.top_k and q.origin not in res.doc_ids:
            missed += 1
    return {"checked": [int(picked.size), min(sample, int(ok.size))],
            "mismatched": [mismatched, 0], "positives_missed": [missed, 0],
            "unanswered": [int(unanswered), 0], "server_errors": [errors, 0]}


def run_cell(spec: dict, name: str, seed: int, seconds: float, traced: bool,
             *, require_tpu: bool = True, scratch=None,
             traffic_dir: Path = BENCH / "traffic",
             compile_cache: bool = True, traffic_override=None) -> dict:
    cell, config, traffic = cell_spec(spec, name, traffic_dir)
    traffic = {**traffic, **(traffic_override or {})}
    devs = devices(int(cell["chips"]), require_tpu)
    check_hosts(config, cell, devs)
    peaks = load_json(BENCH / "peaks.json")
    if require_tpu and devs[0].device_kind not in peaks:
        raise SystemExit(f"no peaks for {devs[0].device_kind!r} in "
                         f"bench/peaks.json")
    peak = peaks.get(devs[0].device_kind)
    log(f"{len(devs)} {devs[0].device_kind} device(s)")
    if compile_cache:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
    events = watch.WindowEvents()
    from repro.serve import NetClient, NetServer, ServingLoop
    mix = traffic["queries"]
    counts = datagen.term_counts(config)
    layout, order, params = datagen.layout_of(counts, config)
    if traffic["loop"] == "open":
        n = max(1, round(float(traffic["rate_qps"]) * seconds))
    else:
        n = math.ceil(float(traffic["pool_qps"]) * seconds)
    queries = datagen.make_queries(seed, 1, n, mix, order)
    datagen.compile_terms(queries, config["kmer"])
    log(f"{len(queries)} queries")
    blocks = datagen.arena_blocks(seed, counts, layout, order)
    log("arena drawn")
    datagen.plant(blocks, counts, layout, order, queries, config["kmer"])

    with tempfile.TemporaryDirectory(prefix="cobs-bench-",
                                     dir=scratch) as tmp:
        store_dir = Path(tmp) / "store"
        index = datagen.write_store(store_dir, blocks, layout, params,
                                    config["store"])
        arena_bytes = index.storage.nbytes()
        log(f"store written: {arena_bytes} B")
        server = build_server(config, index, store_dir, traced,
                              n + 4096 if traced else 256)
        placement = placement_of(server)
        warm_up(server, index, config, mix, seed, order)
        log("warmed up")
        loop = ServingLoop(server, workers=1)
        net = client = None
        if traffic["transport"] == "net":
            net = NetServer(loop, host="127.0.0.1", port=0).start()
            client = NetClient(*net.address, timeout_s=600.0, trace=traced)
            transport = loadgen.NetTransport(client)
        else:
            loop.start()
            transport = loadgen.LoopTransport(loop)
        rec = loadgen.Records(len(queries))
        capture = devtrace.Capture(Path(tmp) / "profile") if traced else None
        # set-up's objects (the harness's queries and records, the
        # program's index and compiled calls) live through the window:
        # keep them out of its collections
        gc.collect()
        gc.freeze()
        host = watch.HostWatch()
        setup_s = time.monotonic() - T_START
        events.mark()
        stalls = watch.StallWatch(rec).start()
        try:
            timer = None
            if capture is not None:
                timer = profile_timer(capture, rec,
                                      min(float(traffic["trace_seconds"]),
                                          seconds))
            pool_lasted = True
            if traffic["loop"] == "open":
                offsets = loadgen.arrival_offsets(len(queries), seconds, seed)
                loadgen.run_open(transport, queries, offsets, seconds, rec)
            else:
                pool_lasted = loadgen.run_closed(
                    transport, queries, int(traffic["outstanding"]), seconds,
                    rec)
            rest = rec.window[1] - loadgen.clock()
            if rest > 0:
                time.sleep(rest)
            if timer is not None:
                timer.join()
            rec.wait(ANSWER_GRACE_S)
            rec.closed_at = loadgen.clock()
            mem_by_device = memory_peaks(devs)
            mem_peak = max(mem_by_device.values())
        finally:
            stalls.stop()
            host.stop()
            gc.unfreeze()
            if client is not None:
                client.close()
            if net is not None:
                net.close(drain=True)
            else:
                loop.stop(drain=True)
        traces = server.tracer.recent() if traced else []
        counters = server.metrics
        device = capture.reduce() if capture is not None else None
        del server, loop, net, client, transport, index
        gc.collect()
    log("served")

    reference = ref.Reference(counts, blocks, kmer=config["kmer"],
                              fpr=config["fpr"])
    checks = check_answers(rec, queries, reference,
                           int(traffic["check_sample"]), seed)
    log("checked")
    # everything a metric reader may read (see bench/metrics)
    run = SimpleNamespace(
        cell=cell, config=config, traffic=traffic, queries=queries,
        records=rec, setup_s=setup_s, memory_peak_bytes=mem_peak,
        memory_peak_by_device=mem_by_device, placement=placement,
        arena_bytes=arena_bytes, traces=traces, counters=counters,
        device=device, peak=peak, reference=reference)
    metrics = {}
    for m in cell_metrics(spec, cell, traced):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    issued = rec.issued
    failed = sum(1 for i in issued if rec.status(i) != "OK")
    out = {"correct": all(v <= lim for k, (v, lim) in checks.items()
                          if k != "checked") and checks["checked"][0] > 0,
           "attempted": int(issued.size), "failed": int(failed),
           "metrics": metrics,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs),
                      "memory_peak_bytes": mem_peak}}
    if placement is not None:
        out["device"].update(
            memory_peak_by_device={str(k): v
                                   for k, v in mem_by_device.items()},
            placement={str(k): v for k, v in placement.items()})
    if device is not None:
        out["device"].update(busy_s=device.busy_s, window_s=device.window_s)
        spans = [(s.name, s.start_s, s.end_s) for t in traces
                 for s in t.spans()]
        out["breakdown"] = {"device_ops": device.top_ops(),
                            "idle_gaps": device.idle_by_span(spans)}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    out["_notes"] = [*events.notes(rec.window[0]),
                     *stalls.notes(rec.window[0]),
                     *host.notes(rec.window),
                     *latency_notes(rec, queries)]
    if not pool_lasted:
        out["_notes"].append(f"the closed loop used all {len(queries)} "
                             f"queries before the window closed: raise "
                             f"pool_qps in a new traffic file")
    return out


def latency_notes(rec, queries, n: int = 5) -> list[str]:
    """Where the window's time went, for standard error: latency
    quantiles, the longest stretch without an answer, the slowest
    requests with the server's own split of their time."""
    i = [j for j in rec.issued if rec.status(j) == "OK"]
    if not i:
        return ["no OK answers"]
    lat = (rec.done[i] - rec.due[i]) * 1e3
    q = np.percentile(lat, [50, 90, 99, 100])
    done = np.sort(rec.done[i])
    gaps = np.diff(done)
    at = int(np.argmax(gaps)) if gaps.size else 0
    late = (rec.sent[i] - rec.due[i]) * 1e3
    notes = [f"latency ms p50 {q[0]:.1f} p90 {q[1]:.1f} p99 {q[2]:.1f} "
             f"max {q[3]:.1f}; sent late by at most {late.max():.1f} ms",
             f"longest gap between answers {gaps.max() if gaps.size else 0:.3f}"
             f" s at {done[at] - rec.window[0]:.2f} s into the window"]
    for j in np.asarray(i)[np.argsort(-lat)[:n]]:
        a, qy = rec.answers[j], queries[j]
        notes.append(
            f"slow: due +{rec.due[j] - rec.window[0]:.2f} s, "
            f"{(rec.done[j] - rec.due[j]) * 1e3:.1f} ms, {len(qy.codes)} bp, "
            f"top_k {qy.top_k}, {a.method} x{a.batch_size}, server wait "
            f"{a.wait_s * 1e3:.1f} ms, service {a.service_s * 1e3:.1f} ms")
    return notes


def profile_timer(capture, rec, seconds: float):
    """Trace the last ``seconds`` of the window, so that collecting the
    trace stalls nothing inside it."""
    import threading

    def run():
        while rec.window[1] == 0.0:
            time.sleep(0.001)
        time.sleep(max(0.0, rec.window[1] - seconds - loadgen.clock()))
        capture.start()
        time.sleep(max(0.0, rec.window[1] - loadgen.clock()))
        capture.stop()

    t = threading.Thread(target=run, name="bench-profile", daemon=True)
    t.start()
    return t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_json(ROOT / "BENCHMARK.json")
    try:
        out = run_cell(spec, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for note in out.pop("_notes"):
        print(f"bench: {note}", file=sys.stderr)
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
