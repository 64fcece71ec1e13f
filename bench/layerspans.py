"""Shared arithmetic of the readers of the program's batch spans.

A scored micro-batch times its stages once, tags each span with the
batch's id (``batch``) and the enclosing stage (``parent``), and copies
them into every member request's trace. A program that records no such
spans gives these readers nothing to read, and they return None.
"""
from __future__ import annotations

import readings


def window_batches(run) -> list[list]:
    """The spans of each micro-batch scored in the window, each span once,
    grouped by the batch tag; set-up's (warm-up) batches left out."""
    t0 = run.records.window[0]
    groups: dict = {}
    seen = set()
    for t in run.traces:
        for s in t.spans():
            b = s.tags.get("batch")
            key = (b, s.name, s.start_s, s.end_s)
            if b is None or s.start_s < t0 or key in seen:
                continue
            seen.add(key)
            groups.setdefault(b, []).append(s)
    return list(groups.values())


def ms_per_batch(run, holding: str, names: tuple) -> float | None:
    """Mean, over the window's batches that hold a ``holding`` span, of
    the summed durations of their ``names`` spans, in ms."""
    per = [sum(s.duration_s for s in spans if s.name in names)
           for spans in window_batches(run)
           if any(s.name == holding for s in spans)]
    return 1e3 * sum(per) / len(per) if per else None


def lock_wait_p95_ms(run) -> float | None:
    """95th percentile of the program's lock_wait spans (a request asking
    for the serving loop's lock until it holds it), one per request
    submitted through the loop; None where there are none."""
    waits = [s.duration_s for s in readings.spans(run, "lock_wait")]
    return readings.nearest_rank(waits, 0.95) * 1e3 if waits else None
