"""What ran inside the measured window besides the traffic, for standard
error: JAX compiles and persistent-cache hits, JAX trace and lowering
steps, the interpreter's garbage collections, and any stretch in which
no answer came while requests were out, with the serving threads' stacks
taken during it.

None of it is a metric. It is there so that a run whose tail reads far
off says where the time went.
"""
from __future__ import annotations

import gc
import math
import subprocess
import sys
import threading
import time
import traceback

SLOW_S = 0.05          # JAX steps and collections at least this long are listed
STALL_S = 1.0          # no answer for this long, with requests out, is a stall
TICK_S = 0.01
LATE_S = 0.05          # a tick this late is listed, with the CPU spent in it


class WindowEvents:
    """Counts and lists events from ``mark()`` on."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.t0 = math.inf
        self.compiles = self.cache_hits = 0
        self.slow: list[tuple[float, str, float]] = []
        self._gc_start = 0.0

        def on_duration(name, duration, **_):
            if time.monotonic() < self.t0:
                return
            if name == dispatch.BACKEND_COMPILE_EVENT:
                self.compiles += 1
            if duration >= SLOW_S:
                self.slow.append((time.monotonic(), name, duration))

        def on_event(name, **_):
            if (time.monotonic() >= self.t0
                    and name == "/jax/compilation_cache/cache_hits"):
                self.cache_hits += 1

        def on_gc(phase, info):
            now = time.monotonic()
            if phase == "start":
                self._gc_start = now
            elif now >= self.t0 and now - self._gc_start >= SLOW_S:
                self.slow.append((now, f"gc generation {info['generation']}",
                                  now - self._gc_start))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        gc.callbacks.append(on_gc)

    def mark(self) -> None:
        self.t0 = time.monotonic()

    def notes(self, window_start: float) -> list[str]:
        out = [f"{self.compiles} compile(s) and {self.cache_hits} "
               f"compile-cache hit(s) inside the window"]
        for t, name, d in sorted(self.slow, key=lambda e: -e[2])[:5]:
            out.append(f"since the window opened: {name} {d:.3f} s, ending "
                       f"{t - window_start:.2f} s in")
        return out


class StallWatch:
    """A thread that ticks every 10 ms through the window. It keeps its
    own longest oversleep (the whole process, or the interpreter's lock,
    held up), every oversleep of ``LATE_S`` or more with the CPU time the
    process spent meanwhile (about the oversleep: a thread computed with
    the lock held; about nothing: the process was not run), and, the
    first time no answer has come for ``STALL_S`` while requests are out,
    the stacks of the program's serving threads."""

    def __init__(self, rec):
        self.rec = rec
        self.oversleep = (0.0, 0.0)          # (seconds, when)
        self.late: list[tuple[float, float, float]] = []   # (when, s, cpu s)
        self.stall: list[str] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-watch",
                                        daemon=True)

    def start(self) -> "StallWatch":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def _run(self) -> None:
        rec, last, cpu = self.rec, time.monotonic(), time.process_time()
        while not self._stop.wait(TICK_S):
            now, late = time.monotonic(), time.monotonic() - last - TICK_S
            cpu_now = time.process_time()
            last, cpu, spent = now, cpu_now, cpu_now - cpu
            if rec.window[0] == 0.0:
                continue
            if late > self.oversleep[0]:
                self.oversleep = (late, now)
            if late >= LATE_S:
                self.late.append((now, late, spent))
            if self.stall:
                continue
            quiet = now - max(rec.last_done, rec.window[0])
            if quiet >= STALL_S and rec.outstanding:
                self.stall = self._stacks(now, quiet)

    def _stacks(self, now: float, quiet: float) -> list[str]:
        names = {t.ident: t.name for t in threading.enumerate()}
        out = [f"stall: no answer for {quiet:.2f} s at "
               f"{now - self.rec.window[0]:.2f} s into the window, "
               f"{self.rec.outstanding} request(s) out"]
        for ident, frame in sys._current_frames().items():
            name = names.get(ident, str(ident))
            if not name.startswith(("serve-", "netclient-")):
                continue
            where = " < ".join(
                f"{f.filename.rsplit('/', 2)[-1]}:{f.lineno} {f.name}"
                for f in reversed(traceback.extract_stack(frame)[-8:]))
            out.append(f"stall: thread {name}: {where}")
        return out

    def notes(self, window_start: float) -> list[str]:
        late, when = self.oversleep
        return [f"longest oversleep of a 10 ms tick {late:.3f} s at "
                f"{when - window_start:.2f} s into the window",
                *self.stall,
                *(f"oversleep {s:.3f} s ending {t - window_start:.2f} s in, "
                  f"process CPU {c:.3f} s over the tick"
                  for t, s, c in sorted(self.late, key=lambda e: -e[1])[:8])]


_TICKER = """
import select, sys, time
last = time.monotonic()
while not select.select([sys.stdin], [], [], %r)[0]:
    now = time.monotonic()
    if now - last - %r >= %r:
        print(now, now - last - %r, flush=True)
    last = now
"""


class HostWatch:
    """The same 10 ms tick in a process of its own, which shares no
    interpreter lock with the run: an oversleep seen here too is the
    machine standing still, not this process. The monotonic clock is the
    machine's, so its times line up with the run's."""

    def __init__(self):
        code = _TICKER % (TICK_S, TICK_S, LATE_S, TICK_S)
        self.late: list[tuple[float, float]] = []
        self._proc = subprocess.Popen(
            [sys.executable, "-c", code], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)

    def stop(self) -> None:
        """End the ticker (closing its input) and wait for it."""
        try:
            out, _ = self._proc.communicate(input="", timeout=10)
        except subprocess.TimeoutExpired:
            self._proc.kill()
            out, _ = self._proc.communicate()
        for line in out.splitlines():
            t, s = line.split()
            self.late.append((float(t), float(s)))

    def notes(self, window: tuple[float, float]) -> list[str]:
        late = [(t, s) for t, s in self.late if window[0] <= t <= window[1]]
        out = [f"a second process's 10 ms tick overslept {LATE_S} s or more "
               f"{len(late)} time(s) inside the window"]
        for t, s in sorted(late, key=lambda e: -e[1])[:8]:
            out.append(f"second process: oversleep {s:.3f} s ending "
                       f"{t - window[0]:.2f} s in")
        return out
