"""Device trace: capture a profiler window and reduce it to numbers.

The reduction reads an XSpace (``.xplane.pb``) with nothing but JAX's
``ProfileData``:

- device planes are ``/device:TPU:<n>``; their ops are the events of the
  line ``XLA Ops`` (``XLA Modules`` where a plane has no op line);
- busy time is the union of those intervals inside the window, idle is
  the rest, averaged over the device planes;
- an op's event name is its HLO instruction text; it is reported by the
  instruction's name (``%lookup.3 = ...`` reads ``lookup``), and a
  kernel's time is the sum of the durations of the events whose text
  matches a pattern;
- an idle gap is named by the host span that covers most of it (the
  program's request spans, put on the profiler's clock by one
  ``bench_sync`` annotation whose monotonic start the harness records).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import time

SYNC = "bench_sync"
OP_LINES = ("XLA Ops", "XLA Modules")


@dataclasses.dataclass
class DeviceTrace:
    """A reduced trace; every time in monotonic seconds."""
    window: tuple[float, float]
    busy: list[list[tuple[float, float]]]    # merged intervals per chip
    ops: list[tuple[str, float, float, str]]  # (name, start, end, text)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        return (sum(e - s for chip in self.busy for s, e in chip)
                / max(1, len(self.busy)))

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of the ops whose text matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(e - s for _, s, e, text in self.ops if rx.search(text))

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` op names that took most device time, with seconds."""
        tot: dict[str, float] = {}
        for name, s, e, _ in self.ops:
            tot[name] = tot.get(name, 0.0) + (e - s)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self) -> list[tuple[float, float]]:
        """The window less the union of every chip's busy intervals."""
        gaps, t = [], self.window[0]
        for s, e in _merge([iv for chip in self.busy for iv in chip]):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        return gaps

    def idle_by_span(self, spans, n: int = 10) -> list[list]:
        """Idle seconds per name of the host span covering most of each
        gap, the shortest such span where several cover it alike (a
        batch's ``prune`` over its requests' ``queue_wait``), and
        "no_request" where none does; the ``n`` largest.

        One sweep: the gaps are disjoint and in time order, so the spans
        are taken on in start order as gaps reach them, and dropped once
        they end before a gap starts. The spans still open keep start
        order, so a full tie goes to the first of them."""
        spans = sorted(set(spans), key=lambda sp: sp[1])
        tot: dict[str, float] = {}
        open_, i = [], 0
        for a, b in self.idle_gaps():
            while i < len(spans) and spans[i][1] < b:
                open_.append(spans[i])
                i += 1
            open_ = [sp for sp in open_ if sp[2] > a]
            best, name = (0.0, 0.0), "no_request"
            for sname, s, e in open_:
                key = (min(b, e) - max(a, s), s - e)
                if key[0] > 0 and key > best:
                    best, name = key, sname
            tot[name] = tot.get(name, 0.0) + (b - a)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]


def op_name(text: str) -> str:
    """``%lookup.12 = u32[...] custom-call(...)`` -> ``lookup``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_xspace(pd, window: tuple[float, float], sync_mono: float
                  ) -> DeviceTrace:
    """Reduce a ``ProfileData`` given the monotonic window and the
    monotonic instant at which the ``bench_sync`` annotation opened."""
    sync_ns = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SYNC:
                    sync_ns = ev.start_ns
    if sync_ns is None:
        raise ValueError(f"trace holds no {SYNC} annotation")
    off = sync_mono - sync_ns * 1e-9
    w0, w1 = window
    busy, ops = [], []
    for plane in pd.planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        line = next((lines[n] for n in OP_LINES if n in lines), None)
        if line is None:
            continue
        iv = []
        for ev in line.events:
            s = ev.start_ns * 1e-9 + off
            e = s + ev.duration_ns * 1e-9
            s, e = max(s, w0), min(e, w1)
            if e > s:
                iv.append((s, e))
                ops.append((op_name(ev.name), s, e, ev.name))
        busy.append(_merge(iv))
    return DeviceTrace((w0, w1), busy, ops)


class Capture:
    """A profiler window: ``start()`` / ``stop()``, then ``reduce()``."""

    def __init__(self, out_dir):
        self.out_dir = str(out_dir)
        self.window = (0.0, 0.0)
        self.sync = 0.0

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans only, no call trace
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.sync = time.monotonic()
        with jax.profiler.TraceAnnotation(SYNC):
            pass
        self.window = (time.monotonic(), 0.0)

    def stop(self) -> None:
        import jax
        self.window = (self.window[0], time.monotonic())
        jax.profiler.stop_trace()

    def reduce(self) -> DeviceTrace:
        from jax._src.profiler import ProfileData
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no xplane under {self.out_dir}")
        return reduce_xspace(ProfileData.from_file(found[-1]), self.window,
                             self.sync)
