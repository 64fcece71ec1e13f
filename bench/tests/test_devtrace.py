"""The trace reduction on a small recorded trace, and the byte count the
roofline is taken against."""
import math

import numpy as np
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
