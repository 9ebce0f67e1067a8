"""Host span tracer: nestable named spans in a bounded ring buffer.

The host-side half of the reference profiler's ``HostTracer``
(``fluid/platform/profiler/host_tracer.cc``), rebuilt as a standalone
substrate every layer can write to: serving engine steps, jit builds,
collectives, watchdog timeouts.  Design constraints:

* **thread-safe** — the serving engine, DataLoader prefetch threads and
  the watchdog monitor all record concurrently; finished spans go into
  one ring under a lock, per-thread nesting state lives in a
  ``threading.local`` stack.
* **bounded** — the ring is a ``deque(maxlen=capacity)``; a long-lived
  server keeps the most recent ``capacity`` spans and counts the rest in
  ``dropped`` instead of growing without bound.
* **exportable** — :meth:`export_chrome` writes real Chrome trace-event
  JSON (``ph:"X"`` complete events with explicit ``id``/``parent`` args,
  so nesting round-trips exactly through
  :func:`~paddle_tpu.observability.load_profiler_result`).
* **phases on the device trace's clock** — :meth:`SpanTracer.phase`
  marks one phase of the serving engine's step with a
  ``jax.profiler.TraceAnnotation``: a no-op in C++ while no profiler
  session runs, and otherwise an event in the profiler's own host plane,
  beside the device planes.  That annotation is ALL a phase costs on the
  step path; a :class:`Span` is recorded too only while the step
  profiler's capture window is armed (``GET /v1/debug/profile``).

What runs on which thread.  :data:`STEP_PHASES` are all on a replica's
ENGINE thread (``fleet.EngineReplica._loop``), and so is ``ahead.settle``
of :data:`THREAD_SPANS`.  The three ``server.*`` spans are on the
server's asyncio LOOP thread, which shares the interpreter lock with the
engine thread: the walk that wakes the streams, every chunk written and
every request taken in run there while the engine thread plans, builds
and dispatches the next launch.  ``proc.gc`` is on whichever thread the
collector happened to run on and stops them all.  The profiler keeps one
line a thread in its host plane, so a reader tells them apart by what a
line holds (``benchmarks/thread_spans.py``).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

# the phases of one serving-engine step, in the order a synchronous step
# (``EngineCore.step``) runs them.  A step of the serving loop that runs
# ahead (``EngineCore.step_ahead``) runs the same phases in another order:
# plan, admit, build and dispatch of launch N+1 FIRST, then device_wait,
# fetch and emit of launch N, the one dispatched a step earlier.
# The names are a contract: the benchmark's readers
# (benchmarks/host_spans.py) and PERF.md key on them.
# What a model's kinds of layer add to ``engine.build`` / ``engine.fetch``:
# ``ops.paged_attention.LaunchTelemetry`` (a kind's docstring lists them).
STEP_PHASES = (
    "engine.wait",          # engine thread blocked with no work
    "engine.intake",        # server queue -> scheduler (submits, aborts)
    "sched.plan",           # scheduler.schedule()
    "engine.admit",         # per-request admission bookkeeping
    "engine.build",         # numpy routing arrays + the SamplingPack
                            # (rows= on a decode launch)
    "engine.dispatch",      # the step call, until the jit call returns
                            # (rows=, bucket=, ahead= 1 where the launch
                            # went out before the tokens of the launch
                            # before it were read, launch= its number)
    "engine.device_wait",   # blocked until the program whose tokens are
                            # wanted has ended (launch= its number): in a
                            # step that ran ahead that is the launch
                            # BEFORE the one just dispatched
    "engine.fetch",         # host arrays of what the step reads (bytes=):
                            # the int32 tokens; with the audit on its
                            # stats, and a sampled decode / ragged
                            # launch's real rows of logits
    "engine.emit",          # commit, emission, retire; then the stream
                            # hand-off: one callback posted to the
                            # server's loop a step (streams= the
                            # replica's open handles), which wakes the
                            # handlers over there
    "engine.trackers",      # end-of-step trackers and stepprof.end_step
)

# why a step of the serving loop read the launch in flight before it
# planned, instead of running ahead of it (``EngineCore.settle``).  The
# ORDER is a contract: ``ahead.settle`` carries a reason as its index
# here, ``serving_ahead_settles_total{reason}`` as the word.
SETTLE_REASONS = (
    "prefill",      # a running request still has prompt to compute
    "admit",        # a waiting request could be admitted
    "preempt",      # the pool cannot hold every row's next token
    "finish",       # a row of the launch in flight ends with its token
    "audit",        # the numerics audit samples this step
    "fault",        # a fault is planned for this step
    "task",         # a KV export / import / detach between steps
    "bare",         # ``EngineCore.step()`` met the serving loop's launch
    "family",       # an engine that never leaves a launch in flight (the
                    # unified step, bursts, mp > 1, committed outputs):
                    # counted, and never a span -- nothing is in flight
)
(SETTLE_PREFILL, SETTLE_ADMIT, SETTLE_PREEMPT, SETTLE_FINISH, SETTLE_AUDIT,
 SETTLE_FAULT, SETTLE_TASK, SETTLE_BARE, SETTLE_FAMILY) = SETTLE_REASONS

# spans beside the step's phases, made the same way (``SpanTracer.phase(
# name, None, **ints)``: one ``TraceAnnotation``, a no-op in C++ while no
# profiler runs) on the thread named.  No name starts with ``engine.`` or
# ``sched.``: the benchmark's ``host_spans.load_host`` collects those two
# prefixes from every thread's line as phases of the step.  The names and
# integers are a contract with ``benchmarks/thread_spans.py`` and PERF.md.
THREAD_SPANS = (
    "ahead.settle",     # engine thread: ``EngineCore.settle`` reading the
                        # launch in flight for a step that could not run
                        # ahead; CONTAINS that launch's engine.device_wait
                        # / engine.fetch / engine.emit (reason= index into
                        # SETTLE_REASONS, launch= the launch read)
    "server.accept",    # loop thread: a parsed completion request up to
                        # its hand-over to the fleet, no await inside
                        # (req= the n of its id ``cmpl-<n>``,
                        # prompt_tokens=)
    "server.wake",      # loop thread: one walk over the open handles and
                        # every ``event.set()`` (handles= looked at)
    "server.write",     # loop thread: one chunk built and handed to the
                        # socket, not the drain (req= as its
                        # server.accept, tokens=)
    "proc.gc",          # any thread: one collection of the cyclic
                        # collector, from ``gc.callbacks`` (gen=)
)


class Span:
    """One finished (or in-flight) named span."""

    __slots__ = ("name", "cat", "start", "duration", "tid", "attrs",
                 "span_id", "parent_id")

    def __init__(self, name: str, cat: str, start: float, tid: int,
                 span_id: int, parent_id: Optional[int],
                 attrs: Dict[str, Any]):
        self.name = name
        self.cat = cat
        self.start = start          # perf_counter seconds
        self.duration = 0.0         # seconds; 0.0 for instant events
        self.tid = tid
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs

    def set_attribute(self, key: str, value) -> None:
        self.attrs[key] = value

    def __repr__(self):
        return (f"Span({self.name!r}, cat={self.cat!r}, "
                f"dur={self.duration * 1e3:.3f}ms, attrs={self.attrs})")


class _SpanContext:
    """Context manager handed out by :meth:`SpanTracer.span`."""

    __slots__ = ("_tracer", "_span")

    def __init__(self, tracer: "SpanTracer", span: Span):
        self._tracer = tracer
        self._span = span

    def set_attribute(self, key: str, value) -> None:
        self._span.set_attribute(key, value)

    def __enter__(self) -> Span:
        self._tracer._push(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self._span.attrs.setdefault("error", exc_type.__name__)
        self._tracer._pop(self._span)
        return False


class _RecordedPhase:
    """A phase inside an armed capture window: the profiler annotation
    plus one ``[name, start, end, attrs]`` record handed to the step
    profiler, which turns the records of a step into child spans."""

    __slots__ = ("_ann", "_rec", "_sink")

    def __init__(self, ann, sink, name: str, attrs: Dict[str, int]):
        self._ann = ann
        self._sink = sink
        self._rec = [name, 0.0, None, attrs]

    def __enter__(self):
        self._ann.__enter__()
        self._rec[1] = time.perf_counter()
        self._sink.append(self._rec)
        return self

    def __exit__(self, exc_type, exc, tb):
        self._rec[2] = time.perf_counter()
        return self._ann.__exit__(exc_type, exc, tb)


class SpanTracer:
    """Thread-safe span recorder over a bounded ring buffer."""

    def __init__(self, capacity: int = 8192):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._ring = deque(maxlen=capacity)  # finished spans, oldest out
        self._lock = threading.Lock()
        self._tls = threading.local()        # per-thread open-span stack
        self._ids = itertools.count(1)
        self.dropped = 0
        # perf_counter -> wall epoch offset, so exported timestamps are
        # real times comparable across processes
        self.epoch_offset = time.time() - time.perf_counter()

    # --- nesting (per-thread) ----------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_span(self) -> Optional[Span]:
        st = self._stack()
        return st[-1] if st else None

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        span.duration = time.perf_counter() - span.start
        st = self._stack()
        while st and st[-1] is not span:  # tolerate mis-nested exits
            st.pop()
        if st:
            st.pop()
        self._record(span)

    # --- recording ----------------------------------------------------------
    def _record(self, span: Span) -> None:
        with self._lock:
            if len(self._ring) == self.capacity:
                self.dropped += 1
            self._ring.append(span)

    def span(self, name: str, cat: str = "host", **attrs) -> _SpanContext:
        """``with tracer.span("engine_step", step=3) as sp: ...``"""
        parent = self.current_span()
        sp = Span(name, cat, time.perf_counter(),
                  threading.get_ident(), next(self._ids),
                  parent.span_id if parent else None, dict(attrs))
        return _SpanContext(self, sp)

    @staticmethod
    def phase(name: str, recorder=None, **ints):
        """``with tracer.phase("engine.build", prof, rows=B): ...`` —
        one phase of an engine step (:data:`STEP_PHASES`).

        Always, and only, a ``jax.profiler.TraceAnnotation`` with a
        constant name and integer attributes the caller already holds:
        JAX has no public "is a profiler session running", so the
        annotation is what is always made — it costs a fraction of a
        microsecond while no session runs.  ``recorder`` is the
        engine's :class:`~paddle_tpu.observability.StepProfiler`; while
        its capture window is armed the phase is recorded for it too,
        and becomes a child span of the captured step."""
        ann = TraceAnnotation(name, **ints)
        if recorder is None or recorder.phase_sink is None:
            return ann
        return _RecordedPhase(ann, recorder.phase_sink, name, ints)

    def instant(self, name: str, cat: str = "event", **attrs) -> Span:
        """Zero-duration marker (chrome ``ph:"i"``), e.g. a watchdog
        timeout or a preemption decision."""
        parent = self.current_span()
        sp = Span(name, cat, time.perf_counter(),
                  threading.get_ident(), next(self._ids),
                  parent.span_id if parent else None, dict(attrs))
        self._record(sp)
        return sp

    def add_span(self, name: str, start: float, duration: float,
                 cat: str = "host", **attrs) -> Span:
        """Record a span with explicit perf_counter timestamps — used by
        the dispatch bus, which only learns (name, wall_seconds) after the
        op ran."""
        parent = self.current_span()
        sp = Span(name, cat, start, threading.get_ident(), next(self._ids),
                  parent.span_id if parent else None, dict(attrs))
        sp.duration = duration
        self._record(sp)
        return sp

    # --- inspection ---------------------------------------------------------
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0

    # --- export -------------------------------------------------------------
    def export_chrome(self, path: str) -> str:
        """Write the ring as Chrome trace-event JSON; returns ``path``."""
        from .export import export_chrome_trace

        return export_chrome_trace(self.spans(), path,
                                   epoch_offset=self.epoch_offset)


_global_tracer: Optional[SpanTracer] = None
_global_lock = threading.Lock()


def get_tracer() -> SpanTracer:
    """The process-wide default tracer (created on first use)."""
    global _global_tracer
    if _global_tracer is None:
        with _global_lock:
            if _global_tracer is None:
                _global_tracer = SpanTracer()
    return _global_tracer


def set_tracer(tracer: Optional[SpanTracer]) -> Optional[SpanTracer]:
    """Swap the process-wide tracer (tests, custom capacity); returns the
    previous one."""
    global _global_tracer
    with _global_lock:
        prev, _global_tracer = _global_tracer, tracer
    return prev
