"""``paddle_tpu.observability`` — the one telemetry substrate.

Three pieces, shared by the profiler, the serving engine, the jit layer
and user code (ISSUE 2 tentpole):

* :class:`SpanTracer` (``tracer.py``) — thread-safe nestable named spans
  with attributes in a bounded ring buffer, exported as real Chrome
  trace-event JSON (``export.py``) and read back with
  :func:`load_profiler_result`.
* :class:`MetricsRegistry` (``metrics.py``) — Counter / Gauge /
  Histogram with exact streaming aggregates and bounded memory,
  rendered as Prometheus text exposition or a JSON snapshot.
* the **op-observer bus** (``core/dispatch.add_op_timer``) — a
  multi-subscriber replacement for the old single-owner ``_op_timer``
  hook, so a :class:`~paddle_tpu.profiler.Profiler`, a
  :class:`~paddle_tpu.serving.ServingMetrics` and user subscribers all
  see per-op dispatch wall times at the same time.
  :func:`subscribe_ops` / :func:`trace_dispatch` are the public surface.

:func:`start_metrics_server` (``httpd.py``) serves any registry as a
Prometheus ``/metrics`` scrape endpoint from a daemon thread — the same
page the serving frontend exposes — so training jobs are fleet-scrapable
too (closed ROADMAP follow-up (a)); :class:`PushGateway` (``push.py``)
is the inverse for jobs behind NAT — a daemon thread POSTs the registry
to a configured URL with capped exponential backoff.

The per-request layer (ISSUE 8): :class:`LifecycleTracker`
(``lifecycle.py``) keeps a bounded structured event timeline per
serving request — routing, admission, prefill chunks, sampled decode
ITL, preemption, finish — exportable as a single-request chrome trace;
:class:`FlightRecorder` (``flight.py``) mirrors those events into
bounded per-replica rings and dumps atomic post-mortem bundles on
anomaly triggers (engine death, watchdog, preemption storms, 429
bursts, drain overruns).

The step/compiler layer (ISSUE 9): :class:`StepProfiler`
(``stepprof.py``) accounts bucket utilization and padding waste per
bucketed program launch, attributes trace+compile wall time per
(program, bucket), and arms bounded on-demand capture windows —
N annotated engine-step spans as a chrome trace, wrapped in
``jax.profiler`` start/stop on real devices.

The value layer (ISSUE 10): :class:`NumericsAuditor` (``audit.py``)
watches the serving programs' *outputs* — a NaN/Inf sentinel over
in-trace logit reductions on every launch, shadow-oracle differential
re-execution of sampled decode steps through the XLA gather reference
(replicated single-shard under mp>1), and atomic size-capped ``.npz``
repro bundles (:func:`replay_repro`) on divergence via the flight
machinery.

The memory layer (ISSUE 13): :class:`CacheStatTracker`
(``cachestat.py``) watches the serving block pool — per-step pool
timelines with the exact ``free + reuse + allocated == num_blocks``
invariant, decayed prefix-heat tables over the chain hashes, reuse-LRU
hit-depth / park-lifetime telemetry fed by the pool's event-driven
hooks, and per-request cache attribution — served at
``GET /v1/debug/cache``.

The cross-process layer (ISSUE 17): ``distrib.py`` stitches worker
processes into the router's observability — :class:`TelemetryOutbox`
streams sequence-numbered worker lifecycle events over piggybacked
wire deltas, :class:`DeltaMerger` merges them idempotently onto the
router's tracker (offset-corrected by the NTP-style
:class:`ClockSync`, mirrored into the bounded :class:`MirrorRing` for
kill -9 post-mortems), and :class:`WireStats` attributes each step's
wall to host vs wire vs engine — served at ``GET /v1/debug/wire``.

What runs on which thread of a serving process (ISSUE 39;
``tracer.py`` has the names).  A replica's ENGINE thread runs every phase
of the step (``tracer.STEP_PHASES``) and ``ahead.settle``; the server's
asyncio LOOP thread, which shares the interpreter lock with it, runs
``server.accept`` / ``server.wake`` / ``server.write``; ``proc.gc`` is
on whichever thread the collector ran on.  ONE monitor thread a process
(:class:`~paddle_tpu.observability.pauses.PauseMonitor`, ``pauses.py``,
started and stopped with the fleet) and the collector's callback make a
pause name itself, always on: a collection, a frozen process, a stalled
engine thread each become a ``serving_pause*`` sample, an event in the
replica's flight ring and one ``WARNING`` line on standard error.

Process-wide defaults: :func:`get_tracer` / :func:`get_registry` return
one shared instance each, so spans from the serving engine, jit compile
events and watchdog timeouts land in one trace, and compile counters /
KV-occupancy gauges land in one Prometheus page.
"""

from __future__ import annotations

from .alerts import (  # noqa: F401
    AlertEngine,
    AlertRule,
    AlertRuleSet,
    default_rule_set,
)
from .audit import (  # noqa: F401
    AuditConfig,
    NumericsAuditor,
    load_repro,
    logit_stats,
    replay_repro,
)
from .cachestat import (  # noqa: F401
    CacheStatTracker,
)
from .distrib import (  # noqa: F401
    ClockSync,
    DeltaMerger,
    MirrorRing,
    TelemetryOutbox,
    WireStats,
)
from .export import (  # noqa: F401
    ProfilerResult,
    chrome_trace_dict,
    export_chrome_trace,
    load_profiler_result,
)
from .flight import (  # noqa: F401
    FlightConfig,
    FlightRecorder,
)
from .history import (  # noqa: F401
    HistoryConfig,
    HistoryStore,
)
from .httpd import (  # noqa: F401
    PROMETHEUS_CONTENT_TYPE,
    MetricsServer,
    metrics_page,
    start_metrics_server,
)
from .lifecycle import (  # noqa: F401
    LifecycleTracker,
    RequestTimeline,
)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)
from .pauses import (  # noqa: F401
    PauseMonitor,
)
from .push import (  # noqa: F401
    PushGateway,
    start_push_gateway,
)
from .stepprof import (  # noqa: F401
    CaptureBusy,
    CaptureWindow,
    StepProfiler,
)
from .tracer import (  # noqa: F401
    SETTLE_REASONS,
    STEP_PHASES,
    THREAD_SPANS,
    Span,
    SpanTracer,
    get_tracer,
    set_tracer,
)


def subscribe_ops(callback):
    """Attach ``callback(op_name, wall_seconds)`` to the dispatch op bus
    alongside any active Profiler / ServingMetrics subscriber.  Returns a
    zero-arg remover."""
    from ..core import dispatch as _dispatch

    return _dispatch.add_op_timer(callback)


def trace_dispatch(tracer: "SpanTracer" = None, cat: str = "dispatch"):
    """Record every eager op dispatch as a span on ``tracer`` (default:
    the process tracer).  The span is recorded after the fact from the
    bus timing, so the hot path pays only the existing timer cost.
    Returns a zero-arg remover."""
    import time as _time

    tr = tracer if tracer is not None else get_tracer()

    def _on_op(name, dt):
        end = _time.perf_counter()
        tr.add_span(name, end - dt, dt, cat=cat)

    return subscribe_ops(_on_op)


def _telemetry():
    # lazy: telemetry pulls in distributed.auto_tuner, which must not be
    # imported while the package __init__ is still executing.  Import by
    # absolute name — ``from . import telemetry`` would re-enter
    # __getattr__ via the package hasattr check and recurse.
    import importlib

    return importlib.import_module(__name__ + ".telemetry")


def __getattr__(name):
    if name in ("TrainStepTelemetry", "telemetry"):
        mod = _telemetry()
        return mod if name == "telemetry" else mod.TrainStepTelemetry
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
