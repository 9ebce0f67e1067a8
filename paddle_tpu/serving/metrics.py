"""Serving metrics: request-level latency + scheduler/pool health.

Registry-backed (ISSUE 2): every counter / gauge / latency distribution
is a series in a :class:`~paddle_tpu.observability.MetricsRegistry`
(``serving_*`` namespace), so a serving process exposes TTFT/ITL
histograms and KV-occupancy gauges on the same Prometheus page as the
jit compile counters — while the legacy inspection surface
(``metrics.counters`` dict view, ``metrics.latency`` OpStat view, the
profiler-style ``summary()`` tables) is preserved exactly.

Tracked:

* **time-to-first-token** (admission-inclusive: arrival → first emitted
  token) and **inter-token latency** per request;
* **prefill / decode step** wall times;
* **queue depth**, **running-set size**, and **KV-pool occupancy** sampled
  once per engine step;
* counters: admitted, finished-by-reason (eos/length/abort), preemptions,
  recompute prefills, decode/prefill jit traces.

Per-op host times ride the dispatch **op-observer bus**
(``core/dispatch.add_op_timer``): ``install_dispatch_timer`` subscribes
alongside any active Profiler instead of the old first-owner-wins
``_set_op_timer`` slot, so Profiler + ServingMetrics coexist.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Deque, Dict, Optional, Tuple

from ..observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from ..observability.tracer import SpanTracer, get_tracer
from ..profiler.statistic import HostOpRecorder, OpStat, summary_table

# how many raw per-step gauge samples to retain for inspection; the
# summary's avg/max/min come from exact streaming aggregates (registry
# Gauge), so a long-lived server's memory stays constant no matter how
# many steps run
GAUGE_WINDOW = 4096

# sub-second serving latencies: finer low end than the registry default
LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

_COUNTER_NAMES = (
    "requests_admitted",
    "requests_finished_eos",
    "requests_finished_length",
    "requests_finished_abort",
    "requests_finished_timeout",
    # ISSUE 12: quarantine-drain stragglers aborted through the live
    # engine with the supervisor's honest verdict
    "requests_finished_replica_failed",
    "admission_rejected",
    "preemptions",
    "recompute_prefills",
    "engine_steps",
    # prefix cache + chunked prefill (ISSUE 4)
    "prefix_cache_hit_tokens",    # prompt tokens restored by fork (free)
    "prefix_cache_miss_tokens",   # prompt tokens that needed compute
    "prefix_cache_evictions",     # cached blocks clobbered for allocation
    "prefill_tokens_computed",    # tokens the prefill programs actually ran
    "chunked_prefill_steps",      # chunk-program launches (vs one-shot)
    # SLO goodput pair (ISSUE 8): slo counts every finished request that
    # carried a per-request slo_ms; slo_good the subset that met it
    "slo",
    "slo_good",
    # unified ragged step (ISSUE 11): packed program launches + the
    # in-trace retrace counter of the one collapsed program family
    "unified_steps",
    "ragged_jit_traces",
    # device-resident decode bursts (ISSUE 19): the burst family's own
    # in-trace retrace counter (bounded by the burst bucket lattice)
    "burst_jit_traces",
)

_GAUGE_NAMES = ("queue_depth", "num_running", "kv_pool_occupancy",
                "prefix_cached_token_ratio", "mp_shards")

# pre-registered so every latency surface shows on /metrics from the
# first scrape.  The last four are the per-request SLO breakdown
# (ISSUE 8) derived from the lifecycle timestamps: arrival → first
# prefill chunk (queue_wait) → first token (prefill) → finish (e2e),
# with decode_itl the per-token gap (observed alongside the legacy
# inter_token_latency series).
_HISTOGRAM_NAMES = (
    "time_to_first_token",
    "inter_token_latency",
    "prefill_step",
    "decode_step",
    "unified_step",   # ISSUE 11: wall time of one packed ragged launch
    "burst_step",     # ISSUE 19: wall time of one N-step decode burst
    "queue_wait",
    "prefill",
    "decode_itl",
    "e2e",
)

# the SLO breakdown quartet, in pipeline order
SLO_PHASES = ("queue_wait", "prefill", "decode_itl", "e2e")

# mesh-spanning step phases (ISSUE 5): pre-registered so the
# serving_collective_seconds series shows on /metrics even before (or
# without) any multi-chip step running.  "ragged" is the unified packed
# step (ISSUE 11) — the one program family that replaces the other two.
_COLLECTIVE_PHASES = ("prefill", "decode", "ragged", "burst")

# every full metric name this module pre-registers, for the README
# metrics-table lint (tools/check_metrics_docs.py)
METRIC_NAMES = tuple(
    [f"serving_{n}_total" for n in _COUNTER_NAMES]
    + [f"serving_{n}" for n in _GAUGE_NAMES]
    + [f"serving_{n}_seconds" for n in _HISTOGRAM_NAMES]
    + ["serving_collective_seconds"]
)


class ServingMetrics:
    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[SpanTracer] = None,
                 labels: Optional[Dict[str, str]] = None):
        # own registry by default so per-engine counts stay per-engine;
        # pass get_registry() to publish on the process-wide /metrics page.
        # ``labels`` rides EVERY series this object creates — the fleet
        # router (ISSUE 6) builds each replica engine with
        # ``labels={"replica": str(i)}`` on one shared registry, so
        # /metrics exposes per-replica-labeled serving series side by
        # side without name collisions.
        self.registry = (registry if registry is not None
                         else MetricsRegistry(max_series=512))
        self.tracer = tracer if tracer is not None else get_tracer()
        self.labels: Dict[str, str] = dict(labels or {})
        self._counters: Dict[str, Counter] = {}
        for name in _COUNTER_NAMES:
            self._counter(name)
        self._hists: Dict[str, Histogram] = {}
        for name in _HISTOGRAM_NAMES:
            self._hist(name)
        # recent per-step gauge samples (bounded window) for inspection;
        # exact full-history aggregates live on the registry Gauges
        self.queue_depth: Deque[int] = deque(maxlen=GAUGE_WINDOW)
        self.num_running: Deque[int] = deque(maxlen=GAUGE_WINDOW)
        self.kv_occupancy: Deque[float] = deque(maxlen=GAUGE_WINDOW)
        self._gauges: Dict[str, Gauge] = {
            name: self.registry.gauge(f"serving_{name}",
                                      f"per-engine-step {name}",
                                      **self.labels)
            for name in _GAUGE_NAMES
        }
        # wall time of one mesh-spanning jitted step, labelled by phase
        # (observed only when mp > 1; present on /metrics regardless)
        self._collective: Dict[str, Histogram] = {
            phase: self.registry.histogram(
                "serving_collective_seconds",
                "wall time of the mesh-spanning jitted step (mp > 1)",
                buckets=LATENCY_BUCKETS, phase=phase, **self.labels)
            for phase in _COLLECTIVE_PHASES
        }
        self._host_ops: Optional[HostOpRecorder] = None
        self._stepprof = None  # StepProfiler, attached by the engine
        self._wire = None      # distrib.WireStats, attached by a
        # cross-process WorkerEngineProxy (ISSUE 17)

    def attach_step_profiler(self, stepprof) -> None:
        """Bind the engine's :class:`~paddle_tpu.observability.stepprof
        .StepProfiler` so :meth:`summary` can render the per-program
        bucket-utilization / padding-waste table (ISSUE 9)."""
        self._stepprof = stepprof

    def attach_wire_stats(self, wire_stats) -> None:
        """Bind a cross-process replica's
        :class:`~paddle_tpu.observability.distrib.WireStats` so
        :meth:`summary` can render the host-vs-wire-vs-engine share of
        every step's wall time (ISSUE 17)."""
        self._wire = wire_stats

    # --- recording ----------------------------------------------------------
    def _counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            c = self._counters[name] = self.registry.counter(
                f"serving_{name}_total", f"serving {name.replace('_', ' ')}",
                **self.labels)
        return c

    def _hist(self, name: str) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = self.registry.histogram(
                f"serving_{name}_seconds",
                f"serving {name.replace('_', ' ')} (seconds)",
                buckets=LATENCY_BUCKETS, **self.labels)
        return h

    def count(self, name: str, n: int = 1) -> None:
        self._counter(name).inc(n)

    def observe(self, name: str, seconds: float) -> None:
        self._hist(name).observe(seconds)

    def observe_ttft(self, seconds: float) -> None:
        self.observe("time_to_first_token", seconds)

    def observe_inter_token(self, seconds: float) -> None:
        # decode_itl is the SLO-breakdown name for the same measurement
        # (ISSUE 8); the legacy inter_token_latency series is preserved
        self.observe("inter_token_latency", seconds)
        self.observe("decode_itl", seconds)

    def observe_queue_wait(self, seconds: float) -> None:
        """Arrival → first prefill chunk (observed once per request, at
        the moment its first prefill program launches)."""
        self.observe("queue_wait", seconds)

    def observe_prefill_phase(self, seconds: float) -> None:
        """First prefill chunk → first emitted token (the whole prefill
        phase, chunks and recomputes included — distinct from the
        per-program ``prefill_step`` wall time)."""
        self.observe("prefill", seconds)

    def observe_finish(self, e2e_seconds: float,
                       slo_ms: Optional[float] = None) -> None:
        """End-to-end latency + the SLO goodput pair: every finished
        request that carried an ``slo_ms`` counts toward
        ``serving_slo_total``; the ones that met it toward
        ``serving_slo_good_total`` (goodput = good/total).  The pair is
        incremented under the registry lock so any reader that snapshots
        under the same lock (:meth:`slo_counts`, the history sampler's
        burn-rate windows — ISSUE 14) can never observe good > total."""
        self.observe("e2e", e2e_seconds)
        if slo_ms is not None:
            good = e2e_seconds * 1e3 <= slo_ms
            slo_c, good_c = self._counter("slo"), self._counter("slo_good")
            with self.registry.atomic():
                slo_c.inc()
                if good:
                    good_c.inc()

    def slo_counts(self) -> Tuple[int, int]:
        """(good, total) snapshotted under the registry lock — the
        consistent read side of the goodput pair (a reader interleaving
        the two bare counter reads could transiently see good > total)."""
        good_c, slo_c = self._counter("slo_good"), self._counter("slo")
        with self.registry.atomic():
            return int(good_c.value), int(slo_c.value)

    def slo_breakdown(self) -> Dict[str, Dict]:
        """JSON-able per-phase latency breakdown: count/avg/p50/p95/p99
        for each SLO phase plus the goodput pair."""
        out: Dict[str, Dict] = {}
        for name in SLO_PHASES:
            h = self._hist(name)
            out[name] = {
                "count": h.count,
                "avg_s": round(h.avg, 6) if h.count else None,
                "p50_s": _round6(h.quantile(0.50)),
                "p95_s": _round6(h.quantile(0.95)),
                "p99_s": _round6(h.quantile(0.99)),
            }
        good, total = self.slo_counts()  # one consistent pair read
        out["goodput"] = {
            "slo_total": total, "slo_good": good,
            "ratio": round(good / total, 4) if total else None,
        }
        return out

    def observe_collective(self, phase: str, seconds: float) -> None:
        """One mesh-spanning jitted step's wall time (ISSUE 5):
        ``serving_collective_seconds{phase="prefill"|"decode"}``."""
        self._collective[phase].observe(seconds)

    def set_mp_shards(self, mp: int) -> None:
        """Publish the engine's tensor-parallel degree
        (``serving_mp_shards``; 1 = single-chip)."""
        self._gauges["mp_shards"].set(mp)

    def cached_token_ratio(self) -> Optional[float]:
        """hit / (hit + computed) over the whole process life — the
        fraction of prefill-bound tokens the prefix cache served for
        free; ``None`` until any prefill ran.  The fleet's
        ``serving_fleet_cache_imbalance`` gauge (ISSUE 13) is the
        max−min of this value across replicas."""
        hit = self._counter("prefix_cache_hit_tokens").value
        computed = self._counter("prefill_tokens_computed").value
        return hit / (hit + computed) if hit + computed else None

    def set_cached_token_ratio(self) -> None:
        """Publish :meth:`cached_token_ratio` on the gauge.  A no-op
        until any prefill ran."""
        ratio = self.cached_token_ratio()
        if ratio is not None:
            self._gauges["prefix_cached_token_ratio"].set(ratio)

    def sample_gauges(self, queue_depth: int, num_running: int,
                      kv_occupancy: float) -> None:
        for name, window, v in (
                ("queue_depth", self.queue_depth, queue_depth),
                ("num_running", self.num_running, num_running),
                ("kv_pool_occupancy", self.kv_occupancy, kv_occupancy)):
            window.append(v)
            self._gauges[name].set(v)

    # --- legacy inspection views --------------------------------------------
    @property
    def counters(self) -> Dict[str, int]:
        """{legacy_name: count} snapshot over the registry counters."""
        return {name: int(c.value) for name, c in self._counters.items()}

    @property
    def latency(self) -> Dict[str, OpStat]:
        """{name: OpStat} view over the latency histograms (the shape
        ``profiler/statistic.summary_table`` renders)."""
        out: Dict[str, OpStat] = {}
        for name, h in self._hists.items():
            st = OpStat(name)
            st.calls = h.count
            st.total = h.sum
            if h.count:
                st.max = h.max
                st.min = h.min
            out[name] = st
        return out

    # --- dispatch-bus wiring (profiler integration) -------------------------
    def install_dispatch_timer(self):
        """Subscribe per-op dispatch wall times into this metrics object
        via the multi-subscriber op bus — coexists with any active
        Profiler (the old single-owner hook silently no-oped here).
        Returns a zero-arg remover."""
        from ..core import dispatch as _dispatch

        if self._host_ops is None:
            self._host_ops = HostOpRecorder()
        return _dispatch.add_op_timer(self._host_ops)

    # --- exporters ----------------------------------------------------------
    def prometheus_text(self) -> str:
        return self.registry.prometheus_text()

    def snapshot(self) -> Dict:
        return self.registry.snapshot()

    # --- reporting ----------------------------------------------------------
    def _gauge_rows(self):
        rows = []
        for name in _GAUGE_NAMES:
            g = self._gauges[name]
            if g.samples == 0:
                rows.append((name, 0, "-", "-", "-"))
            else:
                rows.append((name, g.samples, f"{g.avg:.2f}",
                             f"{g.max:.2f}", f"{g.min:.2f}"))
        return rows

    def summary(self, time_unit: str = "ms") -> str:
        """Render the serving report in ``profiler/statistic.py`` table
        style (printed AND returned, like ``Profiler.summary``)."""
        parts = []
        latency = self.latency
        if latency:
            parts.append(summary_table(
                latency, "Serving latency summary (request-level)",
                time_unit=time_unit))

        counters = self.counters
        header = f"{'Counter':32s} {'Value':>12s}"
        bar = "-" * len(header)
        lines = [bar, "Serving counters", bar, header, bar]
        for name in sorted(counters):
            lines.append(f"{name:32s} {counters[name]:12d}")
        lines.append(bar)
        parts.append("\n".join(lines))

        header = (f"{'SLO phase':16s} {'Count':>8s} {'Avg(ms)':>10s} "
                  f"{'p50(ms)':>10s} {'p95(ms)':>10s} {'p99(ms)':>10s}")
        bar = "-" * len(header)
        lines = [bar, "SLO breakdown (bucket-quantile estimates)", bar,
                 header, bar]
        for name in SLO_PHASES:
            h = self._hist(name)
            cells = [(f"{q * 1e3:10.3f}" if q is not None else
                      f"{'-':>10s}")
                     for q in (h.avg if h.count else None,
                               h.quantile(0.50), h.quantile(0.95),
                               h.quantile(0.99))]
            lines.append(f"{name:16s} {h.count:8d} " + " ".join(cells))
        good, total = self.slo_counts()
        lines.append(bar)
        lines.append(f"goodput: {int(good)}/{int(total)} requests met "
                     "their slo_ms" if total else
                     "goodput: no request carried an slo_ms")
        lines.append(bar)
        parts.append("\n".join(lines))

        prog_rows = (self._stepprof.program_table()
                     if self._stepprof is not None
                     and self._stepprof.enabled else [])
        if prog_rows:
            header = (f"{'Program/bucket':20s} {'Launches':>8s} "
                      f"{'Sched':>8s} {'Capacity':>8s} {'Util':>7s} "
                      f"{'Waste':>7s} {'Wall(ms)':>10s}")
            bar = "-" * len(header)
            lines = [bar, "Bucket utilization / padding waste "
                          "(per step program)", bar, header, bar]
            for row in prog_rows:
                lines.append(
                    f"{row['program'] + '/' + row['bucket']:20s} "
                    f"{row['launches']:8d} "
                    f"{row['scheduled_tokens']:8d} "
                    f"{row['capacity_tokens']:8d} "
                    f"{row['utilization']:7.3f} "
                    f"{row['padding_ratio']:7.3f} "
                    f"{row['wall_s'] * 1e3:10.3f}")
            comp = self._stepprof.compile_totals()
            lines.append(bar)
            if comp:
                lines.append("compile attribution: " + ", ".join(
                    f"{p}: {t['count']}x {t['seconds'] * 1e3:.1f}ms"
                    for p, t in sorted(comp.items())))
            else:
                lines.append("compile attribution: no traces observed")
            lines.append(bar)
            parts.append("\n".join(lines))

        wire_report = (self._wire.report()
                       if self._wire is not None
                       and self._wire.steps else None)
        if wire_report:
            shares = wire_report["shares"]
            header = (f"{'Program':20s} {'Steps':>8s} {'Wire':>7s} "
                      f"{'Engine':>7s} {'Host':>7s}")
            bar = "-" * len(header)
            lines = [bar, "Cross-process step time shares "
                          "(wire vs engine vs host)", bar, header, bar]
            lines.append(f"{'ALL':20s} {wire_report['steps']:8d} "
                         f"{shares['wire']:7.3f} "
                         f"{shares['engine']:7.3f} "
                         f"{shares['host']:7.3f}")
            for prog, row in wire_report["per_program"].items():
                s = row["shares"]
                lines.append(f"{prog[:20]:20s} {row['steps']:8d} "
                             f"{s['wire']:7.3f} {s['engine']:7.3f} "
                             f"{s['host']:7.3f}")
            lines.append(bar)
            parts.append("\n".join(lines))

        header = (f"{'Gauge':24s} {'Samples':>8s} {'Avg':>10s} "
                  f"{'Max':>10s} {'Min':>10s}")
        bar = "-" * len(header)
        lines = [bar, "Scheduler/pool gauges (per engine step)", bar,
                 header, bar]
        for name, n, avg, mx, mn in self._gauge_rows():
            lines.append(f"{name:24s} {n:8d} {avg:>10s} {mx:>10s} {mn:>10s}")
        lines.append(bar)
        parts.append("\n".join(lines))

        if self._host_ops is not None and self._host_ops.stats:
            parts.append(summary_table(
                self._host_ops.stats,
                "Host operator summary (serving dispatch wall time)",
                time_unit=time_unit))
        report = "\n\n".join(parts)
        print(report)
        return report


def _round6(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 6)


class StepTimer:
    """``with StepTimer(metrics, "decode_step"): ...`` convenience.

    ``collective_phase`` additionally feeds the same wall time into
    ``serving_collective_seconds{phase=...}`` — the engine passes it only
    when the timed step actually spans mesh shards (mp > 1), keeping ONE
    timing path for both series."""

    def __init__(self, metrics: ServingMetrics, name: str,
                 collective_phase: Optional[str] = None):
        self.metrics = metrics
        self.name = name
        self.collective_phase = collective_phase
        self.dt: Optional[float] = None  # wall seconds, set on exit —
        # the engine reads it for the StepProfiler record so step-level
        # introspection shares this ONE timing path

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def start_no_earlier_than(self, t: float) -> None:
        """Move the start up to ``t`` if it lies before it: what was timed
        could not begin until then (a launch queued behind another)."""
        self._t0 = max(self._t0, t)

    def __exit__(self, *exc):
        dt = self.dt = time.perf_counter() - self._t0
        self.metrics.observe(self.name, dt)
        if self.collective_phase is not None:
            self.metrics.observe_collective(self.collective_phase, dt)
        return False
