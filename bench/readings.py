"""Shared arithmetic of the metric readers in ``bench/metrics``.

A reader is ``read(run) -> float | None``: ``None`` when the run holds
nothing for it to read, and the harness then leaves the metric out.
"""
from __future__ import annotations

import math

import numpy as np

import reference as ref

ROW_BYTES = ref.DOC_WORDS * 4      # one arena row: 1024 document bits


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank."""
    v = np.sort(np.asarray(values, dtype=float))
    if v.size == 0:
        return math.nan
    return float(v[max(0, math.ceil(q * v.size) - 1)])


def ok(run, i: int) -> bool:
    return run.records.status(i) == "OK"


def latencies_s(run) -> np.ndarray:
    """Per issued request, done - due; a request that failed or never
    came back counts as late as the run waited for answers."""
    rec = run.records
    out = []
    for i in rec.issued:
        end = rec.done[i] if ok(run, i) else rec.closed_at
        out.append(end - rec.due[i])
    return np.asarray(out)


def spans(run, name: str) -> list:
    """Every span of that name over the run's traces (batch-level spans
    appear once per request of the batch)."""
    return [s for t in run.traces for s in t.spans() if s.name == name]


def batches(run) -> list[list]:
    """The run's scored micro-batches: the traces that share one
    ``plan`` span (same start and end) were scored together."""
    groups: dict = {}
    for t in run.traces:
        for s in t.spans():
            if s.name == "plan":
                groups.setdefault((s.start_s, s.end_s), []).append(t)
                break
    return list(groups.values())


def distinct_rows(codes: np.ndarray, layout_widths, kmer: int) -> int:
    """Distinct (block, row) pairs a query must read: one row per
    distinct k-mer per block, fewer where k-mers share a row."""
    h = ref.hash_terms(ref.distinct_terms(codes, kmer)).astype(np.int64)
    return int(sum(np.unique(h % int(w)).size for w in layout_widths))


def device_idle_pct(run) -> float | None:
    """Share of the traced window in which no operation ran on the
    device, in percent; None untraced."""
    dev = run.device
    if dev is None or not dev.busy or dev.window_s <= 0:
        return None
    return 100.0 * (1.0 - dev.busy_s / dev.window_s)


def queue_wait_p95_ms(run) -> float | None:
    """95th percentile of the program's queue_wait spans (admission to the
    start of the batch's scoring), one per scored request of the traced
    run; None where there are none."""
    waits = [s.duration_s for s in spans(run, "queue_wait")]
    return nearest_rank(waits, 0.95) * 1e3 if waits else None
