"""A pause of the serving process names itself (ISSUE 39).

The device's idle gaps have causes that are no phase of the engine's step:
the cyclic collector stopping every thread, the whole process frozen
(one C call holding the interpreter lock, or the machine taking the
process off the CPU), an engine thread that is stuck in one step.  This
module observes the three, always on, with the fleet it is started and
stopped with (``FleetRouter.start`` / ``stop``), and changes nothing: it
calls no ``gc.collect``, freezes no heap, restarts no replica and marks
none unhealthy (``StepWatchdog`` and the supervisor keep that job).

* the collector's callback (``gc.callbacks``): every collection is one
  ``proc.gc`` span on the profiler's clock (``tracer.THREAD_SPANS``; a
  no-op while no profiler runs) and one sample of
  ``serving_gc_pause_seconds{generation}``, observed by the monitor
  thread at its next tick (the callback itself takes no lock); one of
  :data:`GC_PAUSE_S` or more is a ``gc_pause`` event;
* ONE daemon thread a process, ticking every :data:`TICK_S`: a tick that
  comes :data:`FREEZE_S` or more late is a ``process_freeze`` (nothing in
  this process ran Python for that long, so the stall is not the
  engine's); a replica whose ``steps_done`` has not moved for
  :data:`STALL_S` while its scheduler has work is an ``engine_stall``,
  counted once, when it is detected, with the top frames of the engine
  thread's and the loop thread's stacks as they are THEN, and completed
  (its seconds, its line) with its whole length when the replica steps
  again.  Time in which the whole process stood still counts to no
  replica's stall.

An event goes three ways and no further: the three ``serving_pause*``
series by ``kind``, the replica's ring of the fleet's
:class:`~paddle_tpu.observability.flight.FlightRecorder` (so a bundle of
any trigger holds the pauses before it), and ONE ``WARNING`` line of the
logger ``paddle_tpu.serving``, which reaches standard error where no
handler is configured::

    pause kind=engine_stall seconds=5.92 step=1841 launch=1840 \\
engine=<file:line in function> loop=<file:line in function>

A run with no pause logs nothing.
"""

from __future__ import annotations

import gc
import logging
import sys
import threading
import time
import traceback
from collections import deque
from typing import Callable, Dict, Optional, Sequence

from .tracer import SpanTracer

PAUSE_KINDS = ("gc_pause", "process_freeze", "engine_stall")
TICK_S = 0.05       # the monitor's tick
GC_PAUSE_S = 0.05   # a collection this long is an event
FREEZE_S = 0.25     # a tick this late: the process did not run
STALL_S = 0.5       # steps_done still for this long, with work to do
STACK_FRAMES = 3    # innermost frames of a stack that a stall keeps

# sub-millisecond young collections up to a full one over a warm heap
GC_BUCKETS = (0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5,
              1.0, 2.5)

# pre-registered metric names this module owns (tools/check_metrics_docs
# lints that each appears in README's metrics table)
METRIC_NAMES = (
    "serving_gc_pause_seconds",
    "serving_pauses_total",
    "serving_pause_seconds_total",
    "serving_pause_max_seconds",
)

log = logging.getLogger("paddle_tpu.serving")


def top_frames(frame, limit: int = STACK_FRAMES) -> str:
    """The innermost ``limit`` frames of a thread's stack, innermost
    first: ``file:line in function < file:line in function``."""
    if frame is None:
        return "-"
    stack = traceback.extract_stack(frame, limit=limit)
    return " < ".join(f"{fs.filename}:{fs.lineno} in {fs.name}"
                      for fs in reversed(stack))


class PauseMonitor:
    """The collector's callback and the monitor thread of one fleet.

    ``replicas`` is a zero-argument callable that gives the fleet's
    CURRENT replicas (a supervisor may swap one): objects with ``index``,
    ``steps_done``, ``thread`` and ``engine`` (``scheduler.has_work()``,
    ``step_seq``, ``_launch_seq``).  ``flight`` gets every event in the
    replica's ring (``note``); ``loop_thread`` is the ident of the
    thread whose stack a stall shows beside the engine thread's (the
    server's loop).  The thresholds are arguments for the tests alone."""

    def __init__(self, registry, replicas: Callable[[], Sequence],
                 flight=None, loop_thread: Optional[int] = None,
                 tick_s: float = TICK_S, gc_pause_s: float = GC_PAUSE_S,
                 freeze_s: float = FREEZE_S, stall_s: float = STALL_S):
        self._replicas = replicas
        self._flight = flight
        self.loop_thread = loop_thread
        self.tick_s, self.gc_pause_s = tick_s, gc_pause_s
        self.freeze_s, self.stall_s = freeze_s, stall_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._gc_span = None        # the proc.gc annotation in flight
        self._gc_t0 = 0.0
        # what the callback saw and the monitor thread has yet to observe
        self._collections: deque = deque(maxlen=4096)
        # made here and not at a generation's first collection: the
        # registry's lock is not the callback's to take
        self._gc_hist = {
            gen: registry.histogram(
                "serving_gc_pause_seconds",
                "length of every collection of the cyclic collector, by "
                "generation (sum and count give its steady cost)",
                buckets=GC_BUCKETS, generation=str(gen))
            for gen in range(3)}
        # replica index -> [steps_done seen, when it was first seen, the
        # stall's event once detected]
        self._progress: Dict[int, list] = {}
        self._series = {
            kind: (registry.counter(
                       "serving_pauses_total",
                       "pauses of the serving process the pause monitor "
                       "saw: a collection of 50 ms or more (gc_pause), a "
                       "monitor tick 0.25 s or more late (process_freeze),"
                       " an engine thread 0.5 s in one step with work to "
                       "do (engine_stall)", kind=kind),
                   registry.counter(
                       "serving_pause_seconds_total",
                       "seconds in those pauses (an engine_stall counts "
                       "when it ends)", kind=kind),
                   registry.gauge(
                       "serving_pause_max_seconds",
                       "the longest such pause since the start", kind=kind))
            for kind in PAUSE_KINDS}

    # --- lifetime -----------------------------------------------------------
    def start(self) -> "PauseMonitor":
        if self._thread is None:
            # FIRST in the list: JAX's own callback, further down it, may
            # give the interpreter lock away at "stop", before ours ran
            gc.callbacks.insert(0, self._on_gc)
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="serving-pause-monitor", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        """Take the callback off the collector and end the thread."""
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(2.0)
        self._drain_collections()
        now = time.perf_counter()
        for r in self._replicas():      # a stall still open has its line
            seen = self._progress.pop(r.index, None)
            if seen is not None and seen[2] is not None:
                self._stall_over(r, now - seen[1], seen[2])

    # --- the collector ------------------------------------------------------
    def _on_gc(self, phase: str, info: Dict) -> None:
        """``gc.callbacks``: runs on the thread that set the collection
        off, wherever that thread was, with every other thread stopped at
        the interpreter lock.  So it takes NO lock (the code it
        interrupted may hold any of the registry's or the recorder's):
        the span, two clock reads and one ``deque.append``; the monitor
        thread observes the sample at its next tick."""
        if phase == "start":
            self._gc_span = SpanTracer.phase("proc.gc", None,
                                             gen=info["generation"])
            self._gc_span.__enter__()
            self._gc_t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._gc_t0
        span, self._gc_span = self._gc_span, None
        if span is None:        # installed between a start and its stop
            return
        # the sample FIRST: leaving the span may give the interpreter lock
        # away, and the monitor thread, late by this very collection,
        # must find it when it ticks (it read 0.43 s of freeze beside a
        # collection of 0.44 s otherwise: my chip run, PR 39)
        self._collections.append(
            (info["generation"], dt, info.get("collected", 0)))
        span.__exit__(None, None, None)

    def _drain_collections(self) -> float:
        """Observe the collections since the last tick; the seconds they
        took."""
        paused = 0.0
        while self._collections:
            gen, dt, collected = self._collections.popleft()
            paused += dt
            self._gc_hist[gen].observe(dt)
            if dt >= self.gc_pause_s:
                self._record("gc_pause", dt, None, generation=gen,
                             collected=collected)
        return paused

    # --- the monitor thread -------------------------------------------------
    def _run(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(self.tick_s):
            now = time.perf_counter()
            if self.tick(now, now - last):
                last = now

    def tick(self, now: float, since_last: float) -> bool:
        """One tick of the monitor, ``since_last`` seconds after the one
        before it.  Time in which the whole process stood still (a
        collection, a freeze) is no replica's stall.  ``False``: a
        collection is still open (another callback of the collector gave
        the interpreter lock away before its sample was queued), so this
        tick judges nothing and the next one covers its time too."""
        if self._gc_span is not None:
            return False
        stood = self._drain_collections()
        late = since_last - self.tick_s - stood
        if late >= self.freeze_s:
            self._record("process_freeze", late, None)
            stood += late
        for r in self._replicas():
            seen = self._progress.get(r.index)
            if seen is None or seen[0] != r.steps_done:
                if seen is not None and seen[2] is not None:
                    self._stall_over(r, now - seen[1], seen[2])
                self._progress[r.index] = [r.steps_done, now, None]
                continue
            # never past this tick: a collection that ended after ``now``
            # was read is drained here all the same
            seen[1] = min(now, seen[1] + stood)
            if seen[2] is None and now - seen[1] >= self.stall_s \
                    and r.thread is not None and r.thread.is_alive() \
                    and self._has_work(r):
                seen[2] = self._stall_seen(r)
        return True

    @staticmethod
    def _has_work(r) -> bool:
        try:
            return bool(r.engine.scheduler.has_work())
        except RuntimeError:  # swallow-ok: the proxy of a worker process that died raises here (procfleet.WorkerDied); the replica's own death path reports that, and it is no stall
            return False

    def _stall_seen(self, r) -> Dict:
        """A stall is counted and put in the ring when it is detected,
        with what the two threads were in THEN; its line is logged when
        its whole length is known (:meth:`_stall_over`)."""
        frames = sys._current_frames()
        eng = r.engine
        event = dict(
            step=int(getattr(eng, "step_seq", 0)),
            launch=int(getattr(eng, "_launch_seq", 0)),
            engine=top_frames(frames.get(r.thread.ident)),
            loop=top_frames(frames.get(self.loop_thread)))
        self._series["engine_stall"][0].inc()
        self._note(r, "pause", kind="engine_stall", **event)
        return event

    def _stall_over(self, r, seconds: float, event: Dict) -> None:
        """The replica stepped again (or the monitor stops): the stall's
        whole length, and its one line."""
        self._record("engine_stall", seconds, r, count=False, **event)

    # --- where an event goes ------------------------------------------------
    def _record(self, kind: str, seconds: float, replica,
                count: bool = True, **attrs) -> None:
        counter, total, longest = self._series[kind]
        if count:
            counter.inc()
        total.inc(seconds)
        longest.set(max(longest.value, seconds))
        self._note(replica, "pause", kind=kind, seconds=round(seconds, 6),
                   **attrs)
        log.warning("pause kind=%s seconds=%.2f%s", kind, seconds,
                    "".join(f" {k}={v}" for k, v in attrs.items()))

    def _note(self, replica, name: str, **attrs) -> None:
        if self._flight is None:
            return
        if replica is not None:
            self._flight.note(str(replica.index), name, **attrs)
            return
        # a pause of the whole process is in every replica's ring
        for r in self._replicas():
            self._flight.note(str(r.index), name, **attrs)
