"""Observability layer tests (ISSUE 2): span tracer ring-buffer
boundedness, chrome-trace export → ``load_profiler_result`` round-trip,
Prometheus exposition format, the multi-subscriber dispatch op bus
(Profiler + ServingMetrics concurrently — no silent no-op), serving
span/metric instrumentation end-to-end, train-step telemetry MFU
accounting, the watchdog's structured timeout event, and the
bounded-metrics lint."""

import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import dispatch as _dispatch
from paddle_tpu.observability import (
    MetricsRegistry,
    SpanTracer,
    get_registry,
    get_tracer,
    load_profiler_result,
    set_registry,
    set_tracer,
    subscribe_ops,
)

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(_HERE), "tools"))


@pytest.fixture
def fresh_globals():
    """Isolate the process-wide tracer/registry per test."""
    prev_tracer = set_tracer(SpanTracer())
    prev_reg = set_registry(MetricsRegistry())
    try:
        yield get_tracer(), get_registry()
    finally:
        set_tracer(prev_tracer)
        set_registry(prev_reg)


# --------------------------------------------------------------------------
# span tracer
# --------------------------------------------------------------------------
class TestSpanTracer:
    def test_ring_bounded_and_counts_dropped(self):
        tr = SpanTracer(capacity=8)
        for i in range(20):
            tr.add_span(f"s{i}", float(i), 0.001)
        assert len(tr) == 8
        assert tr.dropped == 12
        assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(12, 20)]

    def test_ring_bounded_under_many_threads(self):
        tr = SpanTracer(capacity=100)
        n_threads, per = 8, 200

        def work():
            for i in range(per):
                with tr.span("t", i=i):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(tr) == 100
        assert tr.dropped == n_threads * per - 100

    def test_nesting_parent_ids_per_thread(self):
        tr = SpanTracer()
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                assert tr.current_span() is inner
            assert tr.current_span() is outer
        spans = {s.name: s for s in tr.spans()}
        assert spans["inner"].parent_id == spans["outer"].span_id
        assert spans["outer"].parent_id is None
        assert spans["outer"].duration >= spans["inner"].duration

    def test_exception_marks_span_and_unwinds(self):
        tr = SpanTracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        (sp,) = tr.spans()
        assert sp.attrs["error"] == "RuntimeError"
        assert tr.current_span() is None

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            SpanTracer(capacity=0)


class TestChromeRoundTrip:
    def test_export_load_round_trips_names_nesting_attrs(self, tmp_path):
        tr = SpanTracer()
        with tr.span("outer", cat="phase", step=3):
            with tr.span("inner", cat="op"):
                time.sleep(0.001)
            tr.instant("mark", note="x")
        path = tr.export_chrome(str(tmp_path / "trace.json"))
        res = load_profiler_result(path)
        assert sorted(res.span_names()) == ["inner", "mark", "outer"]
        (outer,) = res.find("outer")
        assert {c.name for c in outer.children} == {"inner", "mark"}
        assert [r.name for r in res.roots] == ["outer"]
        assert outer.attrs["step"] == 3
        assert res.find("mark")[0].attrs["note"] == "x"
        (inner,) = res.find("inner")
        assert inner.dur > 0
        assert res.find("mark")[0].dur == 0  # instant event

    def test_output_dir_created(self, tmp_path):
        tr = SpanTracer()
        tr.instant("e")
        path = str(tmp_path / "deep" / "nested" / "t.json")
        tr.export_chrome(path)
        assert os.path.exists(path)

    def test_containment_fallback_without_id_args(self, tmp_path):
        import json

        # a foreign tool's trace: no id/parent args — nesting comes from
        # timestamp containment on the same tid
        events = [
            {"ph": "X", "name": "a", "ts": 0, "dur": 100, "tid": 1, "pid": 0},
            {"ph": "X", "name": "b", "ts": 10, "dur": 20, "tid": 1, "pid": 0},
        ]
        p = tmp_path / "foreign.json"
        p.write_text(json.dumps({"traceEvents": events}))
        res = load_profiler_result(str(p))
        (a,) = res.find("a")
        assert [c.name for c in a.children] == ["b"]


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------
class TestMetricsRegistry:
    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("ops_total", "ops")
        c.inc()
        c.inc(2)
        assert c.value == 3
        with pytest.raises(ValueError):
            c.inc(-1)
        assert c.value == 3

    def test_gauge_exact_streaming_aggregates(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        for v in (5, 1, 9, 3):
            g.set(v)
        assert g.value == 3 and g.samples == 4
        assert g.avg == 4.5 and g.max == 9 and g.min == 1

    def test_histogram_cumulative_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        assert h.bucket_counts() == {"0.01": 1, "0.1": 2, "1": 3, "+Inf": 4}
        assert h.count == 4 and h.sum == pytest.approx(5.555)
        lines = h.expose()
        assert 'lat_bucket{le="+Inf"} 4' in lines
        assert "lat_count 4" in lines

    def test_prometheus_exposition_format_and_escaping(self):
        reg = MetricsRegistry()
        reg.counter("req_total", 'help with \\ and\nnewline',
                    path='a"b\\c\nd').inc(2)
        text = reg.prometheus_text()
        assert "# HELP req_total help with \\\\ and\\nnewline" in text
        assert "# TYPE req_total counter" in text
        assert 'req_total{path="a\\"b\\\\c\\nd"} 2' in text
        assert text.endswith("\n")

    def test_label_series_and_snapshot(self):
        reg = MetricsRegistry()
        reg.counter("hits_total", kind="a").inc()
        reg.counter("hits_total", kind="b").inc(3)
        snap = reg.snapshot()
        assert snap['hits_total{kind="a"}']["value"] == 1
        assert snap['hits_total{kind="b"}']["value"] == 3
        only_counters = reg.snapshot(kinds=("counter",))
        assert all(v["type"] == "counter" for v in only_counters.values())

    def test_get_or_create_is_idempotent_but_kind_conflict_raises(self):
        reg = MetricsRegistry()
        assert reg.counter("x_total") is reg.counter("x_total")
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_series_cardinality_capped(self):
        reg = MetricsRegistry(max_series=2)
        reg.counter("a_total")
        reg.counter("b_total")
        with pytest.raises(RuntimeError):
            reg.counter("c_total")

    def test_invalid_names_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.counter("1starts_with_digit")


# --------------------------------------------------------------------------
# dispatch op bus
# --------------------------------------------------------------------------
def _run_some_ops(n=3):
    a = paddle.to_tensor(np.ones((4, 4), np.float32))
    for _ in range(n):
        a = a + a
    return a


class TestDispatchBus:
    def test_multiple_subscribers_coexist(self):
        seen1, seen2 = [], []
        rm1 = subscribe_ops(lambda name, dt: seen1.append(name))
        rm2 = subscribe_ops(lambda name, dt: seen2.append(name))
        try:
            _run_some_ops()
            assert seen1 and seen2 and seen1 == seen2
        finally:
            rm1()
            rm2()
        n = len(seen1)
        _run_some_ops()
        assert len(seen1) == n  # removed: no more callbacks
        assert _dispatch._op_timer is None

    def test_broken_subscriber_is_dropped_not_fatal(self, capsys):
        good = []

        def bad(name, dt):
            raise RuntimeError("broken subscriber")

        rm_bad = subscribe_ops(bad)
        rm_good = subscribe_ops(lambda name, dt: good.append(name))
        try:
            out = _run_some_ops()  # must not raise
            assert out is not None
            assert good
            assert "unsubscribed" in capsys.readouterr().err
        finally:
            rm_bad()
            rm_good()

    def test_legacy_set_op_timer_single_slot_compat(self):
        calls1, calls2, bus = [], [], []
        rm = subscribe_ops(lambda n, d: bus.append(n))
        try:
            _dispatch._set_op_timer(lambda n, d: calls1.append(n))
            _run_some_ops(1)
            # replacing the legacy slot must not touch bus subscribers
            _dispatch._set_op_timer(lambda n, d: calls2.append(n))
            _run_some_ops(1)
            _dispatch._set_op_timer(None)
            _run_some_ops(1)
            assert calls1 and calls2
            assert len(bus) >= len(calls1) + len(calls2)
        finally:
            _dispatch._set_op_timer(None)
            rm()
        assert _dispatch._op_timer is None

    def test_profiler_and_serving_metrics_concurrently(self):
        """The ISSUE 2 acceptance hook: both subscribe at once, both see
        ops — the old single-owner hook silently no-oped the loser."""
        from paddle_tpu.profiler import Profiler
        from paddle_tpu.serving.metrics import ServingMetrics

        sm = ServingMetrics()
        with Profiler(timer_only=True) as prof:
            rm = sm.install_dispatch_timer()
            try:
                _run_some_ops()
            finally:
                rm()
            assert sm._host_ops.stats  # ServingMetrics saw ops
        assert prof._host_recorder.stats  # Profiler saw the same ops
        assert _dispatch._op_timer is None


# --------------------------------------------------------------------------
# profiler export / serving instrumentation end-to-end
# --------------------------------------------------------------------------
class TestProfilerExport:
    def test_export_writes_loadable_chrome_json(self, tmp_path,
                                                fresh_globals):
        from paddle_tpu.profiler import Profiler

        path = str(tmp_path / "host_trace.json")
        with Profiler(timer_only=True) as prof:
            _run_some_ops()
        assert prof.export(path) == path
        res = load_profiler_result(path)
        assert len(res) > 0
        assert all(e.cat == "dispatch" for e in res.events)

    def test_export_rejects_unknown_format(self, tmp_path):
        from paddle_tpu.profiler import Profiler

        prof = Profiler(timer_only=True)
        with pytest.raises(ValueError):
            prof.export(str(tmp_path / "x.pb"), format="protobuf")

    def test_export_chrome_tracing_creates_dir(self, tmp_path):
        from paddle_tpu.profiler import Profiler, export_chrome_tracing

        target = str(tmp_path / "trace_out")
        handler = export_chrome_tracing(target)
        prof = Profiler(timer_only=True)
        handler(prof)
        assert os.path.isdir(target)
        assert prof._log_dir == target


class TestServingObservability:
    def _engine(self, registry, layers=2):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.serving import EngineCore, SchedulerConfig

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))
        return EngineCore(model, num_blocks=64, block_size=4,
                          scheduler_config=SchedulerConfig(max_num_seqs=2),
                          profile_ops=True, registry=registry)

    def test_serving_run_exports_trace_and_prometheus(self, tmp_path,
                                                      fresh_globals):
        """ISSUE 2 acceptance: one serving run yields (a) a chrome trace
        that round-trips engine/prefill/decode span nesting and (b) a
        Prometheus page with TTFT/ITL histograms, compile-count counters
        and KV-occupancy gauges — with a Profiler attached to dispatch at
        the same time as ServingMetrics."""
        from paddle_tpu.profiler import Profiler
        from paddle_tpu.serving import SamplingParams

        _, reg = fresh_globals
        eng = self._engine(reg)
        with Profiler(timer_only=True) as prof:
            eng.add_request([5, 9, 23, 7], SamplingParams(max_new_tokens=4))
            eng.add_request([40, 2, 11], SamplingParams(max_new_tokens=3))
            eng.run(max_steps=100)
        path = prof.export(str(tmp_path / "serving_trace.json"))

        res = load_profiler_result(path)
        names = set(res.span_names())
        assert {"engine_step", "prefill_step", "decode_step"} <= names
        # nesting round-trips: prefill/decode are children of engine_step
        steps = res.find("engine_step")
        child_names = {c.name for s in steps for c in s.children}
        assert "prefill_step" in child_names
        assert "decode_step" in child_names
        # jit-trace instants recorded (compile events)
        assert "prefill_jit_trace" in names
        assert "decode_jit_trace" in names

        text = reg.prometheus_text()
        assert "serving_time_to_first_token_seconds_bucket" in text
        assert "serving_inter_token_latency_seconds_count" in text
        assert "serving_kv_pool_occupancy" in text
        assert "serving_decode_jit_traces_total" in text
        assert "serving_prefill_jit_traces_total" in text
        # profiler host-op table filled WHILE serving metrics subscribed
        assert prof._host_recorder.stats
        assert eng.metrics._host_ops.stats
        assert _dispatch._op_timer is None

        # trace-count counters agree with the engine's retrace counters
        snap = reg.snapshot()
        assert (snap["serving_decode_jit_traces_total"]["value"]
                == eng.decode_trace_count)
        assert (snap["serving_prefill_jit_traces_total"]["value"]
                == eng.prefill_trace_count)

    def test_serving_metrics_views_backed_by_registry(self):
        from paddle_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        m.count("requests_admitted", 2)
        m.observe_ttft(0.02)
        m.observe_inter_token(0.003)
        m.sample_gauges(3, 1, 0.5)
        assert m.counters["requests_admitted"] == 2
        assert m.latency["time_to_first_token"].calls == 1
        assert m.latency["time_to_first_token"].max == pytest.approx(0.02)
        text = m.prometheus_text()
        assert "serving_requests_admitted_total 2" in text
        assert "serving_queue_depth 3" in text
        snap = m.snapshot()
        assert snap["serving_kv_pool_occupancy"]["value"] == 0.5


# --------------------------------------------------------------------------
# train-step telemetry (MFU accounting shared with auto_tuner)
# --------------------------------------------------------------------------
class TestTrainStepTelemetry:
    def test_mfu_matches_shared_flops_accounting(self):
        from paddle_tpu.distributed.auto_tuner import train_flops_per_token
        from paddle_tpu.observability import TrainStepTelemetry

        reg, tr = MetricsRegistry(), SpanTracer()
        tel = TrainStepTelemetry(n_params=100_000_000, num_layers=6,
                                 seq_len=2048, hidden=1024,
                                 peak_flops=197e12, registry=reg, tracer=tr)
        out = tel.step(tokens=4096, seconds=0.1)
        flops_tok = train_flops_per_token(100_000_000, 6, 2048, 1024)
        assert flops_tok == 600_000_000 + 150_994_944  # pinned formula
        assert out["tokens_per_sec"] == pytest.approx(40960.0)
        assert out["mfu"] == pytest.approx(flops_tok * 40960.0 / 197e12)
        snap = reg.snapshot()
        assert snap["train_tokens_total"]["value"] == 4096
        assert snap["train_mfu"]["value"] == pytest.approx(out["mfu"])
        assert snap["train_step_seconds"]["count"] == 1
        (ev,) = [s for s in tr.spans() if s.name == "train_step"]
        assert ev.attrs["tokens"] == 4096


# --------------------------------------------------------------------------
# watchdog structured event
# --------------------------------------------------------------------------
class TestWatchdogEvent:
    def test_timeout_emits_structured_event_with_thread_dump(
            self, fresh_globals, capsys):
        from paddle_tpu.distributed.watchdog import StepWatchdog

        tracer, _ = fresh_globals
        fired = []
        wd = StepWatchdog(timeout=0.05,
                          on_timeout=lambda lab, t: fired.append(lab))
        try:
            with wd.watch("stuck_step"):
                deadline = time.time() + 5.0
                while not fired and time.time() < deadline:
                    time.sleep(0.01)
        finally:
            wd.shutdown()
        assert fired == ["stuck_step"]
        assert wd.fired == ["stuck_step"]
        events = [s for s in tracer.spans() if s.name == "watchdog_timeout"]
        assert len(events) == 1
        ev = events[0]
        assert ev.cat == "watchdog"
        assert ev.attrs["section"] == "stuck_step"
        assert ev.attrs["timeout_seconds"] == 0.05
        assert "--- thread" in ev.attrs["thread_dump"]
        assert "stuck_step" not in capsys.readouterr().out  # stderr only


# --------------------------------------------------------------------------
# standalone /metrics scrape endpoint (ISSUE 3 satellite)
# --------------------------------------------------------------------------
class TestMetricsServer:
    def test_scrape_shared_page_and_close(self):
        """start_metrics_server serves the same Prometheus exposition the
        serving frontend does, from a daemon thread — training jobs are
        scrapable without the HTTP serving stack."""
        import http.client

        from paddle_tpu.observability import (MetricsRegistry, metrics_page,
                                              start_metrics_server)
        from paddle_tpu.observability import httpd as _httpd

        reg = MetricsRegistry()
        reg.counter("train_steps_total", "train steps").inc(3)
        reg.gauge("tokens_per_second", "throughput").set(1234.5)
        srv = start_metrics_server(reg, port=0)
        try:
            assert srv in _httpd._started      # atexit will close it
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=10)
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 200
            assert resp.getheader("Content-Type").startswith(
                "text/plain; version=0.0.4")
            # byte-identical to the shared page handler
            assert body == metrics_page(reg)
            assert b"train_steps_total 3" in body
            assert b"tokens_per_second 1234.5" in body
            conn.request("GET", "/healthz")
            assert conn.getresponse().read() == b"ok\n"
            conn.request("GET", "/nope")
            assert conn.getresponse().status == 404
            conn.close()
        finally:
            srv.close()
        srv.close()  # idempotent
        with pytest.raises(OSError):
            c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=2)
            c.request("GET", "/metrics")
            c.getresponse()

    def test_close_without_start_does_not_hang(self):
        """Regression: socketserver.shutdown() blocks on a flag only
        serve_forever() sets — close() on a constructed-but-never-started
        server must return (releasing the port), not deadlock."""
        from paddle_tpu.observability import MetricsRegistry, MetricsServer

        srv = MetricsServer(MetricsRegistry(), port=0)
        srv.close()      # must return promptly
        srv.close()      # and stay idempotent


# --------------------------------------------------------------------------
# bounded-metrics lint
# --------------------------------------------------------------------------
class TestBoundedMetricsLint:
    def test_repo_telemetry_layers_are_clean(self):
        import check_bounded_metrics as lint

        assert lint.scan() == []

    def test_flags_unbounded_and_respects_waiver(self, tmp_path):
        import check_bounded_metrics as lint

        bad = tmp_path / "bad.py"
        bad.write_text(
            "from collections import deque\n"
            "import queue\n"
            "a = deque()\n"
            "b = deque(maxlen=4)\n"
            "c = queue.Queue()\n"
            "d = queue.Queue(maxsize=2)\n"
            "e = deque()  # unbounded-ok: test waiver\n")
        hits = lint.check_file(str(bad))
        assert [(line, "deque" in msg or "Queue" in msg)
                for _, line, msg in hits] == [(3, True), (5, True)]

    def test_flags_asyncio_queues_and_simplequeue(self, tmp_path):
        """The server-module extension: asyncio.Queue and the
        Lifo/Priority variants need maxsize=; SimpleQueue (no bound
        parameter at all) always needs a waiver."""
        import check_bounded_metrics as lint

        bad = tmp_path / "srv.py"
        bad.write_text(
            "import asyncio, queue\n"
            "a = asyncio.Queue()\n"
            "b = asyncio.Queue(maxsize=8)\n"
            "c = queue.LifoQueue()\n"
            "d = asyncio.PriorityQueue(4)\n"
            "e = queue.SimpleQueue()\n"
            "f = queue.SimpleQueue()  # unbounded-ok: test waiver\n")
        hits = [(line, msg) for _, line, msg in lint.check_file(str(bad))]
        assert [line for line, _ in hits] == [2, 4, 6]
        assert "cannot be bounded" in hits[2][1]

    def test_flags_prefix_cache_lru_maps(self, tmp_path):
        """The ISSUE 4 extension: OrderedDict/defaultdict (the prefix
        cache's hash-map / reuse-LRU shapes) have no bound parameter, so
        every construction needs a waiver stating the structural bound."""
        import check_bounded_metrics as lint

        bad = tmp_path / "lru.py"
        bad.write_text(
            "import collections\n"
            "from collections import OrderedDict, defaultdict\n"
            "a = OrderedDict()\n"
            "b = OrderedDict()  # unbounded-ok: ≤ num_blocks entries\n"
            "c = defaultdict(list)\n"
            "d = collections.OrderedDict()\n")
        hits = [(line, msg) for _, line, msg in lint.check_file(str(bad))]
        assert [line for line, _ in hits] == [3, 5, 6]
        assert all("cannot be bounded" in msg for _, msg in hits)

    def test_scan_covers_block_pool_module(self):
        """The prefix cache's hash/LRU structures live in
        ops/paged_attention.py — outside the telemetry dirs — and must
        stay under the lint's eye."""
        import check_bounded_metrics as lint

        assert any(p.endswith(os.path.join("ops", "paged_attention.py"))
                   for p in lint.SCAN_FILES)
        # and the module passes as-written (waivers state pool bounds)
        assert [v for v in lint.scan(dirs=(), files=lint.SCAN_FILES)] == []
