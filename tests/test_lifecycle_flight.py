"""Request-lifecycle tracing + fleet flight recorder (ISSUE 8).

Tentpole coverage:

* bounded per-request timelines through the real engine (enqueue →
  admission → prefill chunks → sampled decode ITL → finish) with the
  SLO breakdown histograms and goodput pair fed from the same
  timestamps;
* a dp=2 fleet run whose per-request Chrome trace reconstructs the full
  lifecycle — route (router thread) → queue → prefill chunks → decode →
  finish (engine thread) — from the exported JSON;
* flight-recorder anomaly triggers: an induced engine-thread death and
  a drain-deadline overrun each write exactly one atomic post-mortem
  bundle (last-K ring events of the owning replica, metrics snapshot,
  the dying request's timeline, thread dump);
* HTTP debug surface: ``GET /v1/requests`` / ``/v1/requests/{id}``
  (+ ``?format=chrome``), the ``X-Request-Id`` response header and the
  id-bearing first SSE chunk (satellite bugfix);
* satellites: bucket-quantile estimation, push-gateway export over
  loopback HTTP, and the bounded-metrics / metrics-docs lints.
"""

import http.client
import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import (
    FlightConfig,
    FlightRecorder,
    LifecycleTracker,
    MetricsRegistry,
    PushGateway,
    load_profiler_result,
)
from paddle_tpu.serving import (
    EngineCore,
    FleetConfig,
    FleetRouter,
    SamplingParams,
    SchedulerConfig,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))
try:
    import check_bounded_metrics as bounded_lint
    import check_metrics_docs as docs_lint
finally:
    sys.path.pop(0)

BS = 4


def _model(layers=1):
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))


# --------------------------------------------------------------------------
# LifecycleTracker unit behaviour (no jax work)
# --------------------------------------------------------------------------
class TestTrackerBounds:
    def test_per_request_ring_bounded_with_dropped_counter(self):
        reg = MetricsRegistry()
        lc = LifecycleTracker(registry=reg, max_events_per_request=8)
        for i in range(20):
            lc.event("r1", "custom", i=i)
        tl = lc.get("r1")
        assert len(tl.events) == 8
        assert tl.dropped == 12
        assert reg.counter(
            "serving_lifecycle_events_dropped_total").value == 12
        assert reg.counter("serving_lifecycle_events_total").value == 20

    def test_decode_token_sampling_keeps_exact_aggregates(self):
        lc = LifecycleTracker(decode_sample=4)
        fanned = []
        lc.add_listener(lambda rid, name, ts, tid, attrs:
                        fanned.append(name))
        for i in range(10):
            lc.event("r", "decode_token", itl_s=0.01 * (i + 1))
        tl = lc.get("r")
        # aggregates saw every token; the ring holds only every 4th
        assert tl.decode_tokens == 10
        assert tl.itl_max == pytest.approx(0.10)
        assert sum(1 for e in tl.events if e.name == "decode_token") == 3
        # sampled-out tokens skip the listener fan-out too (the flight
        # ring must not pay per-token cost the knob was set to shed)
        assert fanned.count("decode_token") == 3

    def test_finished_timelines_move_to_bounded_recent_ring(self):
        lc = LifecycleTracker(recent=2)
        for i in range(4):
            lc.event(f"r{i}", "finish", reason="eos")
        assert lc.active() == []
        assert [t.request_id for t in lc.recent()] == ["r2", "r3"]
        assert lc.get("r3") is not None  # queryable after finish
        assert lc.get("r0") is None      # aged out

    def test_rid_none_fans_out_to_listeners_only(self):
        lc = LifecycleTracker()
        seen = []
        lc.add_listener(lambda rid, name, ts, tid, attrs:
                        seen.append((rid, name)))
        lc.event(None, "prefix_cache_eviction", evicted=3)
        assert seen == [(None, "prefix_cache_eviction")]
        assert lc.active() == []

    def test_snapshot_reads_race_free_with_concurrent_appends(self):
        """to_dict()/chrome_spans() snapshot the event deque under the
        writer lock — polling an ACTIVE request while its engine thread
        appends must never raise 'deque mutated during iteration'
        (review finding)."""
        lc = LifecycleTracker(max_events_per_request=64)
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                lc.event("r", "decode_token", itl_s=0.001)

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        try:
            deadline = time.monotonic() + 1.0
            while time.monotonic() < deadline:
                tl = lc.get("r")
                if tl is not None:
                    tl.to_dict(lc.epoch_offset)
                    tl.chrome_spans()
        finally:
            stop.set()
            t.join(5)

    def test_disabled_tracker_records_nothing(self):
        lc = LifecycleTracker(enabled=False)
        lc.event("r", "finish", reason="eos")
        assert lc.get("r") is None

    def test_reused_id_starts_a_fresh_timeline(self):
        """A START event under a reused request id must not resurrect
        the finished timeline from the recent ring (review finding)."""
        lc = LifecycleTracker()
        lc.event("r1", "enqueued")
        lc.event("r1", "finish", reason="eos")
        old = lc.get("r1")
        lc.event("r1", "submitted", prompt_tokens=3)
        fresh = lc.get("r1")
        assert fresh is not old
        assert fresh.state == "active"
        assert [t.request_id for t in lc.active()] == ["r1"]
        # non-start late events still land on the finished timeline
        lc.event("r1", "finish", reason="eos")
        assert lc.get("r1").state == "finished"


# --------------------------------------------------------------------------
# Histogram bucket quantiles (satellite)
# --------------------------------------------------------------------------
class TestHistogramQuantiles:
    def test_uniform_distribution_quantiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("q_test_seconds",
                          buckets=tuple(float(b) for b in
                                        range(10, 101, 10)))
        for v in range(1, 101):   # uniform 1..100
            h.observe(float(v))
        assert 40 <= h.quantile(0.50) <= 60
        assert 85 <= h.quantile(0.95) <= 100
        assert 90 <= h.quantile(0.99) <= 100
        assert h.quantile(0.50) <= h.quantile(0.95) <= h.quantile(0.99)

    def test_quantiles_clamped_to_observed_range_and_empty_none(self):
        reg = MetricsRegistry()
        h = reg.histogram("q_single_seconds", buckets=(1.0, 10.0))
        assert h.quantile(0.5) is None
        h.observe(3.0)
        # one sample: every quantile IS that sample (min==max clamp)
        assert h.quantile(0.01) == pytest.approx(3.0)
        assert h.quantile(0.99) == pytest.approx(3.0)
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_overflow_bucket_falls_back_to_exact_max(self):
        reg = MetricsRegistry()
        h = reg.histogram("q_over_seconds", buckets=(1.0,))
        for v in (5.0, 7.0, 9.0):
            h.observe(v)
        assert h.quantile(0.99) == pytest.approx(9.0)

    def test_snapshot_carries_quantiles_prometheus_text_unchanged(self):
        reg = MetricsRegistry()
        h = reg.histogram("q_snap_seconds", buckets=(1.0, 2.0))
        h.observe(0.5)
        snap = h.snap()
        assert {"p50", "p95", "p99"} <= set(snap)
        assert "p50" not in reg.prometheus_text()


# --------------------------------------------------------------------------
# FlightRecorder unit behaviour
# --------------------------------------------------------------------------
def _bundles(tmp_path, trigger=None):
    names = sorted(f for f in os.listdir(tmp_path)
                   if f.startswith("flight_") and f.endswith(".json"))
    if trigger is not None:
        names = [f for f in names if f.startswith(f"flight_{trigger}_")]
    return [os.path.join(tmp_path, f) for f in names]


class TestFlightRecorderUnit:
    def _recorder(self, tmp_path, **cfg):
        reg = MetricsRegistry()
        lc = LifecycleTracker(registry=reg)
        fr = FlightRecorder(registry=reg, lifecycle=lc,
                            config=FlightConfig(dump_dir=str(tmp_path),
                                                **cfg))
        return reg, lc, fr

    def test_preemption_storm_triggers_exactly_one_bundle(self, tmp_path):
        reg, lc, fr = self._recorder(tmp_path, storm_threshold=3,
                                     storm_window_s=10.0, cooldown_s=60.0)
        lc.event("r1", "enqueued", replica="0")
        for _ in range(6):  # two windows' worth inside the cooldown
            lc.event("r1", "preempted", replica="0")
        paths = _bundles(tmp_path, "preemption_storm")
        assert len(paths) == 1
        bundle = json.load(open(paths[0]))
        assert bundle["trigger"] == "preemption_storm"
        assert bundle["replica"] == "0"
        assert any(ev["name"] == "preempted" for ev in bundle["events"])
        assert "r1" in bundle["in_flight_requests"]
        assert bundle["threads"]  # thread dump present
        assert reg.counter("serving_flight_dumps_total",
                           trigger="preemption_storm").value == 1

    def test_rejection_burst_and_ring_bound(self, tmp_path):
        reg, lc, fr = self._recorder(tmp_path, burst_threshold=4,
                                     burst_window_s=10.0, ring_events=8)
        for _ in range(10):
            fr.note_rejection()
        assert len(_bundles(tmp_path, "rejection_burst")) == 1
        assert len(fr._rings["router"]) == 8  # ring stayed bounded

    def test_replica_less_events_file_under_router_ring(self, tmp_path):
        """Router-thread events (no replica stamp) must not pollute
        replica 0's ring (review finding)."""
        reg, lc, fr = self._recorder(tmp_path)
        lc.event("r1", "submitted", prompt_tokens=4)   # router thread
        lc.event("r1", "enqueued", replica="1")        # engine thread
        assert [e["name"] for e in fr._rings["router"]] == ["submitted"]
        assert [e["name"] for e in fr._rings["1"]] == ["enqueued"]
        assert "0" not in fr._rings

    def test_engine_death_fires_once_per_replica(self, tmp_path):
        reg, lc, fr = self._recorder(tmp_path)
        assert fr.trigger("engine_death", replica="1", detail="boom")
        assert fr.trigger("engine_death", replica="1") is None  # deduped
        assert fr.trigger("engine_death", replica="0")  # other replica ok
        assert len(_bundles(tmp_path, "engine_death")) == 2

    def test_watchdog_attach_chains_and_dumps(self, tmp_path):
        from paddle_tpu.distributed import StepWatchdog

        reg, lc, fr = self._recorder(tmp_path)
        called = []
        wd = StepWatchdog(timeout=600.0,
                          on_timeout=lambda lab, t: called.append(lab))
        fr.attach_watchdog(wd)
        wd.on_timeout("decode_step", 600.0)  # what _fire invokes
        assert called == ["decode_step"]     # original hook preserved
        assert len(_bundles(tmp_path, "watchdog")) == 1

    def test_no_dump_dir_counts_but_writes_nothing(self, tmp_path):
        reg = MetricsRegistry()
        fr = FlightRecorder(registry=reg, config=FlightConfig())
        assert fr.trigger("drain_overrun", detail="x") is None
        assert reg.counter("serving_flight_dumps_total",
                           trigger="drain_overrun").value == 1


# --------------------------------------------------------------------------
# Push-gateway export (satellite, loopback HTTP)
# --------------------------------------------------------------------------
class _CapturingGateway:
    def __init__(self):
        outer = self
        self.bodies = []
        self.types = []

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                outer.bodies.append(self.rfile.read(n))
                outer.types.append(self.headers.get("Content-Type"))
                self.send_response(200)
                self.end_headers()

            def log_message(self, fmt, *args):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class TestPushGateway:
    def test_daemon_loop_posts_exposition(self):
        gw = _CapturingGateway()
        reg = MetricsRegistry()
        reg.counter("push_demo_total", "x").inc(3)
        # a LONG interval: the first push must land immediately (a job
        # shorter than one interval still exports — review finding) ...
        p = PushGateway(f"http://127.0.0.1:{gw.port}/metrics/job/t",
                        registry=reg, interval_s=60.0).start()
        try:
            deadline = time.monotonic() + 30
            while len(gw.bodies) < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert len(gw.bodies) >= 1, "no immediate first push"
            reg.counter("push_demo_total", "x").inc(1)
        finally:
            # ... and close() pushes the FINAL state once more
            p.close()
            gw.close()
        assert len(gw.bodies) >= 2, "close() skipped the final push"
        text = gw.bodies[-1].decode()
        assert "push_demo_total 4" in text   # final state, not stale
        assert "push_total" in text          # self-reporting counters
        assert "0.0.4" in gw.types[-1]
        assert reg.counter("push_failures_total").value == 0

    def test_failure_counter_and_capped_backoff(self):
        gw = _CapturingGateway()
        gw.close()  # nothing listens on that port anymore
        reg = MetricsRegistry()
        p = PushGateway(f"http://127.0.0.1:{gw.port}/x", registry=reg,
                        interval_s=0.5, timeout_s=0.5, max_backoff_s=2.0)
        for _ in range(5):
            assert p.push_now() is False
        assert reg.counter("push_failures_total").value == 5
        assert p.next_delay_s == 2.0  # 0.5 * 2**5 capped at max_backoff
        assert p.push_now() is False  # never raises
        with pytest.raises(ValueError):
            PushGateway("ftp://nope", registry=reg)


# --------------------------------------------------------------------------
# Engine integration: timeline + SLO breakdown (one engine boot)
# --------------------------------------------------------------------------
class TestEngineTimeline:
    def test_full_lifecycle_with_chunks_preemption_and_slo(self):
        m = _model(layers=1)
        eng = EngineCore(m, num_blocks=10, block_size=2,
                         scheduler_config=SchedulerConfig(
                             max_num_seqs=4,
                             max_prefill_tokens_per_step=6))
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=8),
                                slo_ms=60_000.0)
                for p in ([5, 9, 23, 7, 3, 2, 8, 1], [40, 2, 11, 9])]
        eng.run(max_steps=500)
        assert all(r.finished for r in reqs)
        assert eng.metrics.counters["preemptions"] >= 1

        preempted = next(r for r in reqs if r.num_preemptions > 0)
        tl = eng.lifecycle.get(preempted.request_id)
        names = [e.name for e in tl.events]
        for needed in ("enqueued", "admitted", "prefill_chunk",
                       "first_token", "preempted", "finish"):
            assert needed in names, (needed, names)
        # preemption implies re-admission + recompute chunk afterwards
        assert names.index("preempted") < len(names) - 1
        assert tl.preemptions == preempted.num_preemptions
        assert tl.state == "finished"
        assert tl.finish_reason == "length"
        assert [e.ts for e in tl.events] == sorted(e.ts
                                                   for e in tl.events)
        s = tl.summary()
        assert s["generated_tokens"] == 8
        assert s["queue_wait_s"] >= 0 and s["e2e_s"] > 0
        assert s["slo_met"] is True

        # SLO layer: breakdown histograms + goodput pair
        c = eng.metrics.counters
        assert c["slo"] == 2 and c["slo_good"] == 2
        bd = eng.metrics.slo_breakdown()
        assert bd["queue_wait"]["count"] == 2
        assert bd["e2e"]["count"] == 2
        assert bd["decode_itl"]["count"] >= 8
        assert bd["goodput"]["ratio"] == 1.0
        text = eng.metrics.prometheus_text()
        for series in ("serving_queue_wait_seconds_bucket",
                       "serving_prefill_seconds_bucket",
                       "serving_decode_itl_seconds_bucket",
                       "serving_e2e_seconds_bucket",
                       "serving_slo_good_total", "serving_slo_total",
                       "serving_lifecycle_events_total"):
            assert series in text, series

    def test_lifecycle_events_gate_off(self):
        m = _model(layers=1)
        from paddle_tpu.serving import EngineConfig

        eng = EngineCore(m, config=EngineConfig(
            num_blocks=32, block_size=4, lifecycle_events=False))
        r = eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=2))
        eng.run(max_steps=50)
        assert r.finished
        assert eng.lifecycle.get(r.request_id) is None
        # the SLO histograms still observe (independent of the tracker)
        assert eng.metrics.slo_breakdown()["e2e"]["count"] == 1


class TestFleetLifecycleConfig:
    """Fleet/engine lifecycle-config agreement (review findings) —
    build-only, no engine threads started."""

    def _factory(self, **cfg_kw):
        from paddle_tpu.serving import EngineConfig

        def make(i, registry):
            return EngineCore(_model(layers=1), config=EngineConfig(
                num_blocks=32, block_size=BS, **cfg_kw),
                registry=registry,
                metrics_labels={"replica": f"x{i}"})
        return make

    def test_router_respects_engine_gate_no_timeline_leak(self):
        """Engines built with lifecycle_events=False must disable the
        FLEET tracker too — otherwise the router's submitted/route
        events open timelines no engine finish path ever closes."""
        fleet = FleetRouter.build(self._factory(lifecycle_events=False),
                                  dp=2)
        try:
            assert fleet.lifecycle.enabled is False
            fleet.lifecycle.event("r1", "submitted")  # what submit() does
            assert fleet.lifecycle.active() == []     # no-op, no leak
        finally:
            fleet.shutdown(drain_timeout=0.1)

    def test_rebind_pins_replica_identity_to_index(self):
        """Engine events must stamp the replica INDEX (the flight ring /
        engine_death key), not whatever the metrics label says."""
        fleet = FleetRouter.build(self._factory(), dp=2)
        try:
            assert [e._replica_label for e in fleet.engines] == ["0", "1"]
            assert [e.metrics.labels["replica"] for e in fleet.engines] \
                == ["x0", "x1"]  # metrics labels untouched
        finally:
            fleet.shutdown(drain_timeout=0.1)

    def test_decode_event_sample_rides_the_fleet_tracker(self):
        fleet = FleetRouter.build(self._factory(decode_event_sample=0),
                                  dp=2)
        try:
            assert fleet.lifecycle.decode_sample == 0
        finally:
            fleet.shutdown(drain_timeout=0.1)

    def test_disagreeing_lifecycle_knobs_raise(self):
        from paddle_tpu.serving import EngineConfig

        def make(i, registry):
            return EngineCore(_model(layers=1), config=EngineConfig(
                num_blocks=32, block_size=BS,
                lifecycle_events=(i == 0)),
                registry=registry,
                metrics_labels={"replica": str(i)})

        with pytest.raises(ValueError, match="disagree on lifecycle"):
            FleetRouter.build(make, dp=2)

    def test_shared_explicit_tracker_is_adopted(self):
        from paddle_tpu.serving import EngineConfig

        shared = LifecycleTracker(decode_sample=3)

        def make(i, registry):
            return EngineCore(_model(layers=1), config=EngineConfig(
                num_blocks=32, block_size=BS, lifecycle=shared),
                registry=registry,
                metrics_labels={"replica": str(i)})

        fleet = FleetRouter.build(make, dp=2)
        try:
            assert fleet.lifecycle is shared
        finally:
            fleet.shutdown(drain_timeout=0.1)

    def test_enabled_explicit_tracker_with_gated_engines_raises(self):
        """An enabled caller tracker + lifecycle_events=False engines
        would let the router open timelines nothing ever closes
        (review finding) — refused at build."""
        from paddle_tpu.serving import EngineConfig

        shared = LifecycleTracker()  # enabled=True

        def make(i, registry):
            return EngineCore(_model(layers=1), config=EngineConfig(
                num_blocks=32, block_size=BS, lifecycle=shared,
                lifecycle_events=False),
                registry=registry,
                metrics_labels={"replica": str(i)})

        with pytest.raises(ValueError, match="must agree"):
            FleetRouter.build(make, dp=2)


# --------------------------------------------------------------------------
# dp=2 fleet: per-request chrome trace + death/drain bundles (ONE boot)
# --------------------------------------------------------------------------
def _fleet_factory(i, registry):
    paddle.seed(0)
    model = _model(layers=1)
    return EngineCore(model, num_blocks=64, block_size=BS,
                      scheduler_config=SchedulerConfig(
                          max_num_seqs=4, max_prefill_tokens_per_step=8),
                      registry=registry,
                      metrics_labels={"replica": str(i)})


def _prompt_targeting(fleet, replica_index):
    rng_base = 2000
    for seed in range(400):
        rng = np.random.default_rng(rng_base + seed)
        p = rng.integers(0, 256, 16).tolist()
        if fleet.predict_replica(p) == replica_index:
            return p
    raise AssertionError("no prompt found for target replica")


class TestFleetLifecycleAndFlight:
    def test_dp2_chrome_trace_then_death_and_drain_bundles(self, tmp_path):
        """The ISSUE 8 acceptance path, all on one dp=2 fleet boot:
        (1) a finished request's exported chrome trace reconstructs
        route → queue → prefill chunks → decode → finish across the
        router thread and the owning replica's engine thread;
        (2) an induced engine-thread death writes exactly ONE bundle
        carrying the dying request's timeline and the owning replica's
        ring; (3) the drain-deadline overrun writes exactly one more."""
        dump_dir = str(tmp_path)
        fleet = FleetRouter.build(
            _fleet_factory, dp=2,
            config=FleetConfig(flight_dir=dump_dir)).start()
        try:
            # --- (1) lifecycle chrome trace --------------------------------
            rng = np.random.default_rng(7)
            prefix = rng.integers(0, 256, 2 * BS).tolist()
            prompts = [prefix + rng.integers(0, 256, 8).tolist()
                       for _ in range(3)]
            handles = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=4),
                request_id=f"lf-{i}", slo_ms=60_000.0)
                for i, p in enumerate(prompts)]
            fleet.wait(handles, timeout=300)

            h = handles[0]
            tl = fleet.lifecycle.get(h.rid)
            assert tl is not None and tl.state == "finished"
            assert tl.replica == str(h.replica.index)
            path = fleet.lifecycle.export_chrome(
                h.rid, os.path.join(dump_dir, "req.json"))
            res = load_profiler_result(path)
            names = res.span_names()
            for needed in ("submitted", "route", "queue", "prefill",
                           "prefill_chunk", "decode", "finish"):
                assert needed in names, (needed, names)
            # ≥2 prefill chunks: 16-token prompt over an 8-token budget
            assert len(res.find("prefill_chunk")) >= 2
            # causally ordered along the wall clock
            route = res.find("route")[0]
            finish = res.find("finish")[0]
            chunk = res.find("prefill_chunk")[0]
            assert route.ts <= chunk.ts <= finish.ts
            # ...and ACROSS THREADS: routing on the caller/router thread,
            # execution on the owning replica's engine thread
            assert route.tid != chunk.tid
            # one root request span parents the phases
            roots = [e for e in res.events
                     if e.name == f"request {h.rid}"]
            assert len(roots) == 1 and len(roots[0].children) >= 4
            assert roots[0].attrs["trace"] == str(h.rid)

            # --- (2) induced engine-thread death ---------------------------
            victim = fleet.replicas[0]

            def boom():
                raise RuntimeError("induced crash on replica 0")

            victim.engine.step_ahead = boom
            dying = fleet.submit_request(
                _prompt_targeting(fleet, 0),
                SamplingParams(max_new_tokens=4), request_id="dying-1")
            assert dying.replica is victim
            deadline = time.monotonic() + 60
            while victim.alive and time.monotonic() < deadline:
                time.sleep(0.005)
            assert not victim.alive
            paths = _bundles(dump_dir, "engine_death")
            assert len(paths) == 1, "exactly one death bundle"
            bundle = json.load(open(paths[0]))
            assert bundle["replica"] == "0"
            assert "induced crash" in bundle["detail"]
            # the dying request's timeline rode along
            assert "dying-1" in bundle["in_flight_requests"]
            d_events = bundle["in_flight_requests"]["dying-1"]["events"]
            assert any(e["name"] == "route" for e in d_events)
            # the OWNING replica's ring was dumped: every ring event
            # carries replica "0", and the dying rid appears in it
            assert bundle["events"], "ring must not be empty"
            assert all(ev["replica"] == "0" for ev in bundle["events"])
            assert any(ev.get("request") == "dying-1"
                       for ev in bundle["events"])
            assert "serving_fleet_replicas" in bundle["metrics"]
            assert bundle["threads"]

            # --- (3) drain-deadline overrun --------------------------------
            straggler = fleet.submit_request(
                _prompt_targeting(fleet, 1),
                SamplingParams(max_new_tokens=100_000),
                request_id="straggler-1")
            assert straggler.replica.index == 1  # failover works too
            # wait until it is actually running so drain cannot win
            deadline = time.monotonic() + 60
            while not straggler.output_tokens and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            fleet.shutdown(drain_timeout=0.2)
            assert straggler.finish_reason == "timeout"
            paths = _bundles(dump_dir, "drain_overrun")
            assert len(paths) == 1, "exactly one drain bundle"
            bundle = json.load(open(paths[0]))
            assert "straggler-1" in bundle["in_flight_requests"]
            reg_text = fleet.registry.prometheus_text()
            assert ('serving_flight_dumps_total{trigger="engine_death"} 1'
                    in reg_text)
            assert ('serving_flight_dumps_total{trigger="drain_overrun"} 1'
                    in reg_text)
        finally:
            fleet.shutdown(drain_timeout=0.5)


# --------------------------------------------------------------------------
# HTTP debug surface (one server boot)
# --------------------------------------------------------------------------
class TestHttpDebugSurface:
    def test_requests_endpoints_header_and_sse_id(self, tmp_path):
        from test_serving_server import Harness, _request

        m = _model(layers=1)
        eng = EngineCore(m, num_blocks=64, block_size=BS,
                         scheduler_config=SchedulerConfig(max_num_seqs=4))
        h = Harness(eng)
        try:
            status, headers, data = _request(
                h.port, "POST", "/v1/completions",
                {"prompt": [5, 9, 23, 7], "max_tokens": 3,
                 "slo_ms": 60000})
            assert status == 200
            obj = json.loads(data)
            rid = obj["id"]
            # satellite bugfix: the trace id rides the response header
            assert headers["x-request-id"] == rid

            status, _, data = _request(
                h.port, "GET", "/v1/requests?state=recent")
            assert status == 200
            listing = json.loads(data)
            assert rid in [row["id"] for row in listing["data"]]

            status, _, data = _request(h.port, "GET",
                                       f"/v1/requests/{rid}")
            assert status == 200
            body = json.loads(data)
            assert body["summary"]["state"] == "finished"
            assert body["summary"]["slo_met"] is True
            names = [e["name"] for e in body["events"]]
            assert "route" in names and "finish" in names

            status, _, data = _request(
                h.port, "GET", f"/v1/requests/{rid}?format=chrome")
            assert status == 200
            trace = json.loads(data)
            assert any(ev.get("name") == f"request {rid}"
                       for ev in trace["traceEvents"])

            status, _, data = _request(h.port, "GET",
                                       "/v1/requests/nope-404")
            assert status == 404
            status, _, data = _request(h.port, "GET",
                                       "/v1/requests?state=bogus")
            assert status == 400

            # SSE: X-Request-Id header + id-bearing FIRST chunk (before
            # any token is produced)
            conn = http.client.HTTPConnection("127.0.0.1", h.port,
                                              timeout=120)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": [1, 2, 3], "max_tokens": 2,
                                     "stream": True}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            sse_rid = resp.getheader("X-Request-Id")
            assert sse_rid and sse_rid.startswith("cmpl-")
            first = None
            while first is None:
                line = resp.readline().rstrip(b"\n")
                if line.startswith(b"data: "):
                    first = json.loads(line[len(b"data: "):])
            assert first["id"] == sse_rid
            assert first["choices"][0]["token_ids"] == []  # pre-token
            conn.close()

            # new families visible on /metrics
            status, _, data = _request(h.port, "GET", "/metrics")
            for series in (b"serving_e2e_seconds_bucket",
                           b"serving_slo_total",
                           b"serving_lifecycle_events_total",
                           b"serving_flight_dumps_total"):
                assert series in data, series
        finally:
            h.close()


# --------------------------------------------------------------------------
# lint coverage (satellite tooling)
# --------------------------------------------------------------------------
class TestLintCoverage:
    def test_bounded_metrics_scan_covers_new_modules(self):
        covered = {os.path.relpath(p, _REPO)
                   for p in bounded_lint.SCAN_FILES}
        for f in ("paddle_tpu/observability/lifecycle.py",
                  "paddle_tpu/observability/flight.py",
                  "paddle_tpu/observability/push.py"):
            assert f in covered, f
        assert bounded_lint.scan(dirs=(),
                                 files=bounded_lint.SCAN_FILES) == []

    def test_metrics_docs_lint_repo_clean(self):
        assert docs_lint.scan() == []

    def test_metrics_docs_lint_flags_undocumented(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text('METRIC_NAMES = ("serving_demo_total", '
                       '"push_demo_total")\n')
        readme = tmp_path / "README.md"
        readme.write_text("| `serving_demo_total` | demo |\n")
        hits = docs_lint.scan(modules=(str(mod),),
                              readme_path=str(readme))
        assert len(hits) == 1 and "push_demo_total" in hits[0][1]
        readme.write_text("`serving_demo_total` and `push_demo_total`\n")
        assert docs_lint.scan(modules=(str(mod),),
                              readme_path=str(readme)) == []

    def test_metrics_docs_lint_flags_missing_declaration(self, tmp_path):
        mod = tmp_path / "m.py"
        mod.write_text("x = 1\n")
        hits = docs_lint.scan(modules=(str(mod),),
                              readme_path=os.path.join(_REPO, "README.md"))
        assert len(hits) == 1 and "METRIC_NAMES" in hits[0][1]

    def test_metrics_docs_lint_resolves_derived_form(self):
        """serving/metrics.py's METRIC_NAMES is tuple(comprehensions);
        the AST resolver must expand the real vocabulary."""
        path = os.path.join(_REPO, "paddle_tpu", "serving", "metrics.py")
        names = docs_lint.declared_metrics(path)
        from paddle_tpu.serving.metrics import METRIC_NAMES

        assert sorted(names) == sorted(METRIC_NAMES)
        assert "serving_slo_good_total" in names
        assert "serving_e2e_seconds" in names
