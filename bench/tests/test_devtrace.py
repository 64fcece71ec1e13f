"""The trace reduction on a small recorded trace, and the byte count the
roofline is taken against."""
import math

import numpy as np
import pytest
from jax._src.profiler import ProfileData

import devtrace
import readings
import reference as ref

# one chip: ops at [1, 6) ms and [8, 9) ms of a trace whose clock starts
# at 1 us; the host's sync annotation opens at 0.5 us
TRACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 5000000000 }
    events { metadata_id: 2 offset_ps: 8000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 8500000000 duration_ps: 200000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%bitslice_lookup_score_multi.4 = s32[8,1,32,32] custom-call(s32[8,1,128] %p0), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.3 = u32[8,128] fusion(u32[8,128] %p1), kind=kLoop" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_sync" } }
}
'''


def _reduced():
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(TRACE))
    # the annotation opened at monotonic 100.0 s; window 100.0 .. 100.010
    return devtrace.reduce_xspace(pd, (100.0, 100.010), 100.0 - 0.5e-6)


def test_busy_union_idle_and_kernel_time():
    d = _reduced()
    assert math.isclose(d.window_s, 0.010)
    # busy: [100.001, 100.006) and [100.008, 100.009)
    assert math.isclose(d.busy_s, 0.006, rel_tol=1e-9)
    assert math.isclose(d.op_seconds("tpu_custom_call"), 0.0052,
                        rel_tol=1e-9)
    gaps = d.idle_gaps()
    assert len(gaps) == 3
    assert math.isclose(sum(b - a for a, b in gaps), 0.004, rel_tol=1e-9)
    assert [n for n, _ in d.top_ops()] == ["bitslice_lookup_score_multi",
                                           "fusion"]


def test_idle_gaps_are_named_by_the_host_span_over_them():
    d = _reduced()
    # idle: [100, 100.001), [100.006, 100.008), [100.009, 100.010)
    spans = [("plan", 100.0065, 100.0080), ("select", 100.0091, 100.0099)]
    named = dict(d.idle_by_span(spans))
    assert math.isclose(named["plan"], 0.002, rel_tol=1e-6)
    assert math.isclose(named["select"], 0.001, rel_tol=1e-6)
    assert math.isclose(named["no_request"], 0.001, rel_tol=1e-6)


def test_a_gap_goes_to_the_shortest_span_that_covers_it():
    d = _reduced()
    spans = [("queue_wait", 100.0, 100.010), ("prune", 100.0055, 100.0085)]
    named = dict(d.idle_by_span(spans))
    assert math.isclose(named["prune"], 0.002, rel_tol=1e-6)
    assert math.isclose(named["queue_wait"], 0.002, rel_tol=1e-6)


def test_ops_outside_the_window_are_clipped():
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(TRACE))
    d = devtrace.reduce_xspace(pd, (100.002, 100.0085), 100.0 - 0.5e-6)
    assert math.isclose(d.busy_s, (0.006 - 0.002) + (0.0085 - 0.008),
                        rel_tol=1e-9)


def test_distinct_rows_counts_each_block_row_once():
    codes = np.random.default_rng(1).integers(0, 4, 200, dtype=np.uint8)
    n = ref.distinct_terms(codes, 31).shape[0]
    # wide blocks: every distinct k-mer its own row in each block
    assert readings.distinct_rows(codes, [1 << 30, 1 << 30], 31) == 2 * n
    # a one-row block folds them all into one
    assert readings.distinct_rows(codes, [1], 31) == 1
    assert readings.ROW_BYTES == 128


def _scan_idle_by_span(trace, spans, n=10):
    """The reduction as it was: every span that starts before a gap ends,
    scanned for every gap. The oracle of the one-sweep version."""
    spans = sorted(set(spans), key=lambda sp: sp[1])
    tot = {}
    for a, b in trace.idle_gaps():
        best, name = (0.0, 0.0), "no_request"
        for sname, s, e in spans:
            if s >= b:
                break
            key = (min(b, e) - max(a, s), s - e)
            if key[0] > 0 and key > best:
                best, name = key, sname
        tot[name] = tot.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:n]]


def _random_trace(seed):
    """Busy intervals on two chips and host spans on a coarse grid, so
    that overlaps and lengths tie often: nested spans, spans alike but
    for their name, spans of no length, spans over several gaps."""
    rng = np.random.default_rng(seed)
    grid = 0.25e-3
    t_end = 400 * grid
    busy = []
    for _ in range(2):
        starts = np.sort(rng.integers(0, 400, 40)) * grid
        busy.append(devtrace._merge(
            (s, min(t_end, s + rng.integers(1, 6) * grid)) for s in starts))
    trace = devtrace.DeviceTrace((0.0, t_end), busy, [])
    names = ["queue_wait", "kernel_score", "dispatch", "readback", "plan"]
    spans = []
    for _ in range(int(rng.integers(50, 300))):
        s = int(rng.integers(-10, 405))
        length = int(rng.choice([0, 1, 2, 3, 8, 40, 120]))
        spans.append((str(rng.choice(names)), s * grid,
                      (s + length) * grid))
        kind = rng.integers(0, 4)
        if kind == 0 and length > 2:       # a child inside it
            c = s + int(rng.integers(0, length))
            spans.append((str(rng.choice(names)), c * grid,
                          min(s + length, c + int(rng.integers(0, 3)))
                          * grid))
        elif kind == 1:                    # the same interval, renamed
            spans.append((str(rng.choice(names)), s * grid,
                          (s + length) * grid))
        elif kind == 2:                    # the same span twice
            spans.append(spans[-1])
    return trace, spans


@pytest.mark.parametrize("seed", range(40))
def test_one_sweep_names_every_gap_as_the_scan_did(seed):
    trace, spans = _random_trace(seed)
    assert len(trace.idle_gaps()) > 5
    for n in (3, 10, 100):
        assert trace.idle_by_span(spans, n) == \
            _scan_idle_by_span(trace, spans, n)
