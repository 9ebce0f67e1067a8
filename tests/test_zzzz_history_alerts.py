"""Metrics history + SLO burn-rate alerting (ISSUE 14 tentpole).

(Named ``zzzz`` to sort LAST: the tier-1 suite already overruns its
timeout, so new dots must only append — the PR 11/12 convention.)

Covers:

* ``HistoryStore`` contract: ring boundedness under churn, the hard
  ``max_series`` cap with drop counter, counter-reset clamping (a
  rebuilt replica restarting a counter at zero must read as rate 0, the
  PR 12 chaos-phase caveat), histogram-derived ``_count``/``_sum``
  series, engine-step cadence;
* ``MetricsRegistry.add_collect_hook`` (bounded, exception-swallowed)
  and the fleet-gauge freshness it buys: /metrics AND the push gateway
  observe freshly collected ``serving_fleet_*`` values at dp=2 (the
  pre-ISSUE-14 push gateway exported stale fleet gauges);
* the SLO goodput pair's atomicity: a sampler can never observe
  good > total (transient goodput > 1.0 would trip the burn rule);
* ``AlertEngine``: pending→firing→resolved state machine, per-rule
  cooldown, multi-window burn-rate semantics (fast AND slow must both
  burn), deterministic replay (same recorded window → same
  transitions), rule-set JSON round trip;
* integration: history on vs off is token-identical with EQUAL jit
  trace counts; a dp=2 supervised chaos run (PR 11 FaultPlan) drives
  pool / goodput / restart rules through full firing cycles with
  exactly one ``alert`` flight bundle per firing rule embedding the
  triggering series window;
* HTTP: ``/v1/debug/alerts`` + ``/v1/debug/history`` protocol-clean
  (400/404, never 500) at dp=1 and dp=2;
* lint coverage: history.py / alerts.py wired into
  check_bounded_metrics and check_metrics_docs.
"""

import asyncio
import http.client
import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import (
    AlertEngine,
    AlertRule,
    AlertRuleSet,
    HistoryConfig,
    HistoryStore,
    MetricsRegistry,
    PushGateway,
    default_rule_set,
)
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    FaultPlan,
    FaultSpec,
    FleetConfig,
    FleetRouter,
    FleetSupervisor,
    SamplingParams,
    SchedulerConfig,
    ServingMetrics,
    SupervisorConfig,
)
from paddle_tpu.serving.server import CompletionServer, ServerConfig

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))
try:
    import check_bounded_metrics as bounded_lint
    import check_metrics_docs as docs_lint
finally:
    sys.path.pop(0)


def _model(layers=2):
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))


# --------------------------------------------------------------------------
# HistoryStore contract
# --------------------------------------------------------------------------
class TestHistoryStore:
    def test_ring_boundedness_under_churn(self):
        reg = MetricsRegistry()
        c = reg.counter("serving_churn_total", "t")
        g = reg.gauge("serving_churn_gauge", "t")
        hist = HistoryStore(reg, HistoryConfig(ring_len=8, max_series=64))
        for i in range(100):
            c.inc()
            g.set(i)
            hist.sample(step=i)
        for key in hist.keys():
            assert len(hist.window(key)) <= 8, key
        assert hist.stats()["samples"] == 100
        # the ring holds the LAST 8: the newest value is the live one
        assert hist.latest("serving_churn_gauge") == 99.0

    def test_max_series_cap_drops_and_counts(self):
        reg = MetricsRegistry()
        hist = HistoryStore(reg, HistoryConfig(ring_len=4, max_series=5))
        for i in range(12):
            reg.gauge("serving_cap_gauge", "t", idx=str(i)).set(i)
        hist.sample()
        st = hist.stats()
        assert st["series"] == 5                       # hard cap held
        assert st["dropped_series"] >= 7               # rest counted
        dropped = reg.counter("serving_history_series_dropped_total",
                              "x").value
        assert dropped == st["dropped_series"]
        # re-sampling the same dropped keys does not re-count them
        hist.sample()
        assert reg.counter("serving_history_series_dropped_total",
                           "x").value == dropped

    def test_counter_reset_clamps_to_zero(self):
        """A replica rebuild restarts an engine-local counter at zero
        (PR 12 chaos caveat): the windowed increase must clamp the
        negative delta, never report a negative rate."""
        reg = MetricsRegistry()
        c = reg.counter("serving_reset_total", "t")
        hist = HistoryStore(reg, HistoryConfig(ring_len=16))
        for _ in range(4):
            c.inc(5)
            hist.sample()
        assert hist.increase("serving_reset_total", 3) == 15.0
        c._value = 0.0          # the rebuild: counter restarts at zero
        hist.sample()
        # 3 deltas in window: +5, +5, clamp(-15 -> 0)
        assert hist.increase("serving_reset_total", 3) == 10.0
        c.inc(2)
        hist.sample()
        # +5, clamp(0), +2 — accumulation resumes after the reset
        assert hist.increase("serving_reset_total", 3) == 7.0
        # full window: 3 pre-reset deltas (the first sample is the
        # baseline, not a delta) + clamped reset + the post-reset +2
        assert hist.increase("serving_reset_total", 100) == 17.0

    def test_histogram_derives_count_and_sum_series(self):
        reg = MetricsRegistry()
        h = reg.histogram("serving_lat_seconds", "t")
        hist = HistoryStore(reg, HistoryConfig())
        h.observe(0.5)
        h.observe(1.5)
        hist.sample()
        assert hist.latest("serving_lat_seconds:count") == 2.0
        assert hist.latest("serving_lat_seconds:sum") == 2.0
        assert hist.match("serving_lat_seconds_count") == \
            ["serving_lat_seconds:count"]
        assert hist.kind("serving_lat_seconds:count") == "counter"

    def test_name_aggregation_across_label_sets(self):
        reg = MetricsRegistry()
        a = reg.counter("serving_multi_total", "t", replica="0")
        b = reg.counter("serving_multi_total", "t", replica="1")
        hist = HistoryStore(reg, HistoryConfig())
        hist.sample()
        a.inc(3)
        b.inc(4)
        hist.sample()
        assert sorted(hist.match("serving_multi_total")) == [
            'serving_multi_total{replica="0"}',
            'serving_multi_total{replica="1"}']
        assert hist.name_increase("serving_multi_total", 1) == 7.0
        assert hist.name_latest_sum("serving_multi_total") == 7.0

    def test_on_step_cadence(self):
        reg = MetricsRegistry()
        reg.gauge("serving_cad_gauge", "t").set(1)
        hist = HistoryStore(reg, HistoryConfig(sample_every_steps=4))
        taken = [hist.on_step(s) for s in range(1, 13)]
        assert sum(1 for t in taken if t is not None) == 3
        assert hist.stats()["ticks"] == 12

    def test_listener_cap_and_removal(self):
        reg = MetricsRegistry()
        hist = HistoryStore(reg, HistoryConfig())
        seen = []
        remove = hist.add_listener(lambda i, s: seen.append((i, s)))
        hist.sample(step=7)
        assert seen == [(1, 7)]
        remove()
        remove()                      # idempotent
        hist.sample(step=8)
        assert len(seen) == 1
        removers = [hist.add_listener(lambda i, s: None)
                    for _ in range(8 - len(hist._listeners))]
        with pytest.raises(RuntimeError, match="listeners"):
            hist.add_listener(lambda i, s: None)
        for r in removers:
            r()

    def test_broken_listener_is_swallowed_with_report(self, capsys):
        # listeners run on the sampling ENGINE thread — a broken
        # evaluator must be reported, never kill the replica
        reg = MetricsRegistry()
        hist = HistoryStore(reg, HistoryConfig())
        seen = []

        def boom(i, s):
            raise RuntimeError("evaluator bug")

        hist.add_listener(boom)
        hist.add_listener(lambda i, s: seen.append(i))
        idx = hist.sample(step=1)     # must not raise
        assert idx == 1 and seen == [1]
        assert "sample listener failed" in capsys.readouterr().err

    def test_collect_hooks_run_before_sampling(self):
        reg = MetricsRegistry()
        g = reg.gauge("serving_derived_gauge", "t")
        state = {"v": 0}
        reg.add_collect_hook(lambda: g.set(state["v"]))
        hist = HistoryStore(reg, HistoryConfig())
        state["v"] = 42
        hist.sample()
        assert hist.latest("serving_derived_gauge") == 42.0

    def test_config_validation(self):
        with pytest.raises(ValueError):
            HistoryConfig(sample_every_steps=0)
        with pytest.raises(ValueError):
            HistoryConfig(ring_len=1)
        with pytest.raises(ValueError):
            HistoryConfig(max_series=0)


# --------------------------------------------------------------------------
# Collect hooks + SLO pair atomicity (satellite bugfixes)
# --------------------------------------------------------------------------
class TestCollectHooks:
    def test_hooks_run_on_render_and_snapshot(self):
        reg = MetricsRegistry()
        calls = []
        remove = reg.add_collect_hook(lambda: calls.append(1))
        reg.prometheus_text()
        reg.snapshot()
        assert len(calls) == 2
        remove()
        reg.prometheus_text()
        assert len(calls) == 2

    def test_broken_hook_is_swallowed_with_report(self, capsys):
        reg = MetricsRegistry()
        g = reg.gauge("serving_hooked_gauge", "t")

        def boom():
            raise RuntimeError("collector exploded")

        reg.add_collect_hook(boom)
        reg.add_collect_hook(lambda: g.set(5))
        text = reg.prometheus_text()          # must not raise
        assert "serving_hooked_gauge 5" in text
        assert "collect hook failed" in capsys.readouterr().err

    def test_hook_cap_refuses_leak(self):
        reg = MetricsRegistry()
        for _ in range(16):
            reg.add_collect_hook(lambda: None)
        with pytest.raises(RuntimeError, match="collect"):
            reg.add_collect_hook(lambda: None)

    def test_hook_may_render_without_recursion(self):
        reg = MetricsRegistry()
        depth = []

        def hook():
            depth.append(1)
            reg.snapshot()                    # re-entrant render

        reg.add_collect_hook(hook)
        reg.prometheus_text()
        assert len(depth) == 1                # guard stopped recursion


class TestSloPairAtomicity:
    def test_sampler_never_sees_good_above_total(self):
        """Writers hammer observe_finish (all meeting their SLO — the
        worst case: every total inc is immediately followed by a good
        inc) while a reader snapshots; good > total in any snapshot is
        the bug this satellite fixes."""
        reg = MetricsRegistry()
        sm = ServingMetrics(registry=reg)
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                sm.observe_finish(0.001, slo_ms=60_000.0)

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(3000):
                good, total = sm.slo_counts()
                assert good <= total, (good, total)
        finally:
            stop.set()
            for t in threads:
                t.join()

    def test_history_samples_keep_pair_consistent(self):
        reg = MetricsRegistry()
        sm = ServingMetrics(registry=reg)
        hist = HistoryStore(reg, HistoryConfig(ring_len=512))
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                sm.observe_finish(0.001, slo_ms=60_000.0)

        threads = [threading.Thread(target=writer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            for _ in range(300):
                hist.sample()
        finally:
            stop.set()
            for t in threads:
                t.join()
        goods = hist.window("serving_slo_good_total")
        totals = hist.window("serving_slo_total")
        assert len(goods) == len(totals)
        for g, t in zip(goods, totals):
            assert g["i"] == t["i"]
            assert g["v"] <= t["v"], (g, t)


# --------------------------------------------------------------------------
# AlertEngine semantics (no engines — driven registries)
# --------------------------------------------------------------------------
def _threshold_rules(**kw):
    defaults = dict(name="pool", kind="threshold",
                    series="serving_pool_free_blocks", op="lt",
                    threshold=2.0, for_samples=2, cooldown=4)
    defaults.update(kw)
    return AlertRuleSet(rules=(AlertRule(**defaults),))


class TestAlertEngine:
    def test_threshold_pending_firing_resolved(self):
        reg = MetricsRegistry()
        free = reg.gauge("serving_pool_free_blocks", "t")
        hist = HistoryStore(reg, HistoryConfig())
        eng = AlertEngine(hist, rules=_threshold_rules(), registry=reg)
        free.set(10)
        hist.sample()
        assert eng.state("pool")["state"] == "inactive"
        free.set(0)
        hist.sample()                         # breach 1 -> pending
        assert eng.state("pool")["state"] == "pending"
        hist.sample()                         # breach 2 -> firing
        st = eng.state("pool")
        assert st["state"] == "firing"
        assert reg.gauge("serving_alerts_firing", "x",
                         rule="pool").value == 1
        free.set(10)
        hist.sample()                         # clean -> resolved
        st = eng.state("pool")
        assert st["state"] == "inactive"
        assert [t["state"] for t in st["transitions"]] == \
            ["pending", "firing", "resolved"]
        assert reg.gauge("serving_alerts_firing", "x",
                         rule="pool").value == 0
        snap = reg.snapshot()
        assert snap[
            'serving_alert_transitions_total{rule="pool",'
            'state="firing"}']["value"] == 1

    def test_pending_that_clears_is_not_an_incident(self):
        reg = MetricsRegistry()
        free = reg.gauge("serving_pool_free_blocks", "t")
        hist = HistoryStore(reg, HistoryConfig())
        eng = AlertEngine(hist, rules=_threshold_rules(), registry=reg)
        free.set(0)
        hist.sample()                         # pending
        free.set(10)
        hist.sample()                         # clears silently
        st = eng.state("pool")
        assert st["state"] == "inactive"
        # pending counted; firing/resolved never happened
        states = [t["state"] for t in st["transitions"]]
        assert states == ["pending"]

    def test_cooldown_gates_repending(self):
        reg = MetricsRegistry()
        free = reg.gauge("serving_pool_free_blocks", "t")
        hist = HistoryStore(reg, HistoryConfig())
        eng = AlertEngine(hist,
                          rules=_threshold_rules(for_samples=1,
                                                 cooldown=5),
                          registry=reg)
        free.set(0)
        hist.sample()                         # pending+firing
        free.set(10)
        hist.sample()                         # resolved, cooldown starts
        free.set(0)
        for _ in range(4):
            hist.sample()                     # inside cooldown: quiet
        assert eng.state("pool")["state"] == "inactive"
        for _ in range(3):
            hist.sample()                     # past cooldown: refires
        assert eng.state("pool")["state"] == "firing"

    def test_rate_rule_window_delta(self):
        reg = MetricsRegistry()
        c = reg.counter("serving_replica_restarts_total", "t",
                        cause="engine_death")
        hist = HistoryStore(reg, HistoryConfig())
        rules = AlertRuleSet(rules=(AlertRule(
            name="churn", kind="rate",
            series="serving_replica_restarts_total",
            window=4, threshold=1.0, for_samples=1, cooldown=0),))
        eng = AlertEngine(hist, rules=rules, registry=reg)
        for _ in range(3):
            hist.sample()
        assert eng.state("churn")["state"] == "inactive"
        c.inc()                               # the restart
        hist.sample()
        assert eng.state("churn")["state"] == "firing"
        for _ in range(5):                    # window slides past it
            hist.sample()
        st = eng.state("churn")
        assert st["state"] == "inactive"
        assert [t["state"] for t in st["transitions"]] == \
            ["pending", "firing", "resolved"]

    def test_burn_rate_requires_both_windows(self):
        reg = MetricsRegistry()
        good = reg.counter("serving_slo_good_total", "t")
        total = reg.counter("serving_slo_total", "t")
        hist = HistoryStore(reg, HistoryConfig())
        rules = AlertRuleSet(rules=(AlertRule(
            name="burn", kind="burn_rate", objective=0.9,
            threshold=2.0, fast_window=3, slow_window=9,
            for_samples=1, cooldown=0),))
        eng = AlertEngine(hist, rules=rules, registry=reg)
        # a long healthy run fills the slow window with good traffic
        for _ in range(10):
            good.inc()
            total.inc()
            hist.sample()
        # bad traffic starts: the FAST window burns immediately, but
        # the slow window still remembers the good era -> no fire yet
        total.inc()
        hist.sample()
        assert eng.state("burn")["state"] == "inactive", \
            "fast-only burn must not fire (page-vs-ticket split)"
        for _ in range(8):                    # sustained badness
            total.inc()
            hist.sample()
        assert eng.state("burn")["state"] == "firing"
        # recovery: good traffic drains the fast window first
        for _ in range(5):
            good.inc()
            total.inc()
            hist.sample()
        st = eng.state("burn")
        assert st["state"] == "inactive"
        assert [t["state"] for t in st["transitions"]] == \
            ["pending", "firing", "resolved"]

    def test_burn_rate_cold_start_cannot_page(self):
        # two samples after a restart, a "slow" window computed over
        # the only deltas available is the fast window relabeled — the
        # first SLO misses of a warmup must NOT page
        reg = MetricsRegistry()
        good = reg.counter("serving_slo_good_total", "t")
        total = reg.counter("serving_slo_total", "t")
        hist = HistoryStore(reg, HistoryConfig())
        rules = AlertRuleSet(rules=(AlertRule(
            name="burn", kind="burn_rate", objective=0.9,
            threshold=2.0, fast_window=3, slow_window=9,
            for_samples=1, cooldown=0),))
        eng = AlertEngine(hist, rules=rules, registry=reg)
        for _ in range(4):                    # all misses, short history
            total.inc()
            hist.sample()
        assert eng.state("burn")["state"] == "inactive", \
            "burn fired before the slow window was covered"
        for _ in range(6):                    # sustained misses fill it
            total.inc()
            hist.sample()
        assert eng.state("burn")["state"] == "firing"
        assert good.value == 0                # pure-miss stream

    def test_warmup_samples_grace(self):
        reg = MetricsRegistry()
        c = reg.counter("serving_compiles_total", "t")
        hist = HistoryStore(reg, HistoryConfig())
        rules = AlertRuleSet(rules=(AlertRule(
            name="storm", kind="rate", series="serving_compiles_total",
            window=4, threshold=2.0, for_samples=1, cooldown=0,
            warmup_samples=4),))
        eng = AlertEngine(hist, rules=rules, registry=reg)
        hist.sample()                         # boot sample inside grace
        c.inc(10)                             # warmup trace burst —
        # RECORDED in the history, not just pre-dating it
        for _ in range(4):                    # samples 2-5: grace ends
            hist.sample()
        # first post-grace evaluation: the rate window is clamped to
        # the post-warmup era, so the recorded boot burst (a 10-delta
        # inside the unclamped window) cannot fire it
        assert eng.state("storm")["state"] == "inactive", \
            eng.state("storm")
        for _ in range(4):                    # window expands quietly
            hist.sample()
        assert eng.state("storm")["state"] == "inactive"
        c.inc(3)                              # a REAL post-warmup storm
        hist.sample()
        assert eng.state("storm")["state"] == "firing"
        assert default_rule_set() == AlertRuleSet.from_obj(
            default_rule_set().to_obj())      # warmup round-trips

    def test_unrecorded_series_is_no_data_not_inactive(self):
        # a rule whose series is never recorded (source gate off) can
        # never breach — it must say so, not pose as healthy
        reg = MetricsRegistry()
        reg.counter("serving_slo_total", "t")
        hist = HistoryStore(reg, HistoryConfig())
        eng = AlertEngine(hist, rules=_threshold_rules(
            series="serving_pool_available_blocks"), registry=reg)
        hist.sample()
        st = eng.state("pool")
        assert st["has_data"] is False
        assert "no recorded data" in st["last_detail"]
        assert "pool" in eng.snapshot()["no_data"]

    def test_deterministic_replay_same_window_same_transitions(self):
        """The AuditConfig/FaultPlan discipline, proven: running the
        SAME recorded value script through two fresh store+engine pairs
        produces identical transition sequences (samples, states,
        values) — no wall-clock leaks into evaluation."""
        script = ([("free", 10.0, 0)] * 3 + [("free", 0.0, 0)] * 4
                  + [("free", 10.0, 2)] * 6 + [("free", 1.0, 3)] * 3
                  + [("free", 10.0, 5)] * 4)

        def run_once():
            reg = MetricsRegistry()
            free = reg.gauge("serving_pool_free_blocks", "t")
            restarts = reg.counter("serving_replica_restarts_total", "t")
            hist = HistoryStore(reg, HistoryConfig())
            rules = AlertRuleSet(rules=(
                AlertRule(name="pool", kind="threshold",
                          series="serving_pool_free_blocks", op="lt",
                          threshold=2.0, for_samples=2, cooldown=3),
                AlertRule(name="churn", kind="rate",
                          series="serving_replica_restarts_total",
                          window=5, threshold=2.0, for_samples=1,
                          cooldown=2),))
            eng = AlertEngine(hist, rules=rules, registry=reg)
            for _, v, restart_total in script:
                free.set(v)
                if restarts.value < restart_total:
                    restarts.inc(restart_total - restarts.value)
                hist.sample()
            return {name: [(t["state"], t["sample"], t["value"])
                           for t in trs]
                    for name, trs in eng.transitions_report().items()}

        first, second = run_once(), run_once()
        assert first == second
        assert any(first.values()), "script produced no transitions"

    def test_rule_set_json_round_trip_and_validation(self):
        rs = default_rule_set()
        again = AlertRuleSet.from_obj(rs.to_obj())
        assert again == rs                    # frozen value equality
        with pytest.raises(ValueError, match="not valid for a"):
            AlertRuleSet.from_obj([{"name": "x", "kind": "rate",
                                    "series": "s", "windw": 3}])
        # a knob from ANOTHER kind must also raise, not silently
        # evaluate with this kind's defaults
        with pytest.raises(ValueError, match="not valid for a"):
            AlertRuleSet.from_obj([{"name": "x", "kind": "rate",
                                    "series": "s", "fast_window": 4}])
        with pytest.raises(ValueError, match="duplicate"):
            AlertRuleSet(rules=(
                AlertRule(name="a", kind="rate", series="s"),
                AlertRule(name="a", kind="rate", series="s")))
        with pytest.raises(ValueError, match="kind"):
            AlertRule(name="x", kind="nope")
        with pytest.raises(ValueError, match="fast_window"):
            AlertRule(name="x", kind="burn_rate", fast_window=9,
                      slow_window=3)
        with pytest.raises(ValueError, match="op"):
            AlertRule(name="x", kind="threshold", series="s", op="eq")
        # a typo'd/missing top-level 'rules' key must raise, never
        # silently disable every alert
        with pytest.raises(ValueError, match="unknown top-level"):
            AlertRuleSet.from_obj({"Rules": []})
        with pytest.raises(ValueError, match="no 'rules' array"):
            AlertRuleSet.from_obj({})
        assert AlertRuleSet.from_obj({"rules": []}).rules == ()

    def test_default_rules_cover_the_stated_surface(self):
        names = {r.name for r in default_rule_set().rules}
        assert {"pool_exhaustion", "goodput_burn", "rejection_burst",
                "compile_storm", "restart_churn", "quarantine_churn",
                "audit_divergence", "cache_imbalance_high"} <= names
        # the pool floor is on free + reuse, NOT the free list proper: a
        # warm prefix cache parks every refcount-0 block in the reuse
        # LRU, so a free-list floor would page forever on a healthy fleet
        pool = next(r for r in default_rule_set().rules
                    if r.name == "pool_exhaustion")
        assert pool.series == "serving_pool_available_blocks"


# --------------------------------------------------------------------------
# Fleet-gauge freshness: /metrics + push gateway via collect hook (dp=2)
# --------------------------------------------------------------------------
class _CapturingGateway:
    def __init__(self):
        outer = self
        self.bodies = []

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                outer.bodies.append(self.rfile.read(n))
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


def _dp2_fleet(num_blocks=64, config=None):
    def make(i, registry):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
        return EngineCore(model, config=EngineConfig(
            num_blocks=num_blocks, block_size=4),
            registry=registry, metrics_labels={"replica": str(i)})

    return FleetRouter.build(make, dp=2, config=config)


class TestFleetGaugeFreshness:
    def test_push_gateway_exports_fresh_fleet_gauges_at_dp2(self):
        """The satellite regression test: before ISSUE 14 the fleet
        gauges were refreshed only inside the /metrics HTTP handler, so
        a push-gateway export carried whatever the last scrape left.
        Kill a replica between pushes WITHOUT any scrape: the next
        pushed payload must already say alive=1."""
        fleet = _dp2_fleet().start()
        gw = _CapturingGateway()
        pusher = PushGateway(f"http://127.0.0.1:{gw.port}/m",
                             registry=fleet.registry, interval_s=3600.0)
        try:
            assert pusher.push_now()
            text = gw.bodies[-1].decode()
            assert "serving_fleet_replicas_alive 2" in text
            # stop replica 1's engine thread; NOBODY calls
            # sample_gauges or scrapes /metrics in between
            fleet.replicas[1].request_stop()
            fleet.replicas[1].join(10)
            assert not fleet.replicas[1].alive
            assert pusher.push_now()
            text = gw.bodies[-1].decode()
            assert "serving_fleet_replicas_alive 1" in text, \
                "push gateway exported a stale fleet gauge"
            assert 'serving_fleet_replica_alive{replica="1"} 0' in text
        finally:
            gw.close()
            fleet.shutdown(drain_timeout=2.0)

    def test_registry_snapshot_is_fresh_without_explicit_sampling(self):
        fleet = _dp2_fleet().start()
        try:
            fleet.replicas[0].request_stop()
            fleet.replicas[0].join(10)
            snap = fleet.registry.snapshot()
            assert snap["serving_fleet_replicas_alive"]["value"] == 1
        finally:
            fleet.shutdown(drain_timeout=2.0)

    def test_stopped_fleet_unhooks_from_registry(self):
        fleet = _dp2_fleet().start()
        reg = fleet.registry
        fleet.shutdown(drain_timeout=2.0)
        assert reg._collect_hooks == []
        reg.prometheus_text()                 # renders fine post-stop

    def test_heterogeneous_history_gate_refused(self):
        def make(i, registry):
            paddle.seed(0)
            model = LlamaForCausalLM(
                LlamaConfig.tiny(num_hidden_layers=2))
            return EngineCore(model, config=EngineConfig(
                num_blocks=64, block_size=4, history=(i == 0)),
                registry=registry, metrics_labels={"replica": str(i)})

        with pytest.raises(ValueError, match="history"):
            FleetRouter.build(make, dp=2)


# --------------------------------------------------------------------------
# Integration: on/off identity + dp=2 chaos alert cycle + flight bundles
# --------------------------------------------------------------------------
_PROMPT = [5, 9, 23, 7, 11, 3, 17, 29]


class TestHistoryOnOffIdentity:
    def test_token_identical_with_equal_traces(self):
        """History/alerting on vs off is host-side only: same greedy
        tokens, EQUAL jit trace counts, and the off-registry never sees
        a serving_history_*/serving_alerts_* series."""
        outs, traces, regs = [], [], []
        for on in (True, False):
            eng = EngineCore(_model(), config=EngineConfig(
                num_blocks=64, block_size=4, history=on))
            if on:
                hist = HistoryStore(eng.metrics.registry)
                AlertEngine(hist, registry=eng.metrics.registry)
                eng.set_history(hist)
            reqs = [eng.add_request(list(_PROMPT),
                                    SamplingParams(max_new_tokens=6),
                                    request_id=f"r{j}")
                    for j in range(3)]
            eng.run(max_steps=500)
            outs.append([list(r.output_tokens) for r in reqs])
            traces.append((eng.prefill_trace_count,
                           eng.decode_trace_count))
            regs.append(eng.metrics.registry)
        assert outs[0] == outs[1]
        assert traces[0] == traces[1]
        on_text, off_text = (r.prometheus_text() for r in regs)
        assert "serving_history_samples_total" in on_text
        assert "serving_alerts_firing" in on_text
        assert "serving_history" not in off_text
        assert "serving_alerts" not in off_text

    def test_gated_off_engine_ignores_set_history(self):
        eng = EngineCore(_model(), config=EngineConfig(
            num_blocks=64, block_size=4, history=False))
        eng.set_history(HistoryStore(MetricsRegistry()))
        assert eng.history is None


def _chaos_rules():
    """Tuned windows so the full pending→firing→resolved cycle of all
    three acceptance rules completes within a short test run — the
    VALUE-comparable override path (`FleetConfig.alert_rules`)."""
    return AlertRuleSet(rules=(
        AlertRule(name="pool_exhaustion", kind="threshold",
                  series="serving_pool_free_blocks", op="lt",
                  threshold=2.0, for_samples=2, cooldown=4,
                  severity="page"),
        AlertRule(name="goodput_burn", kind="burn_rate",
                  objective=0.9, threshold=2.0, fast_window=4,
                  slow_window=12, for_samples=1, cooldown=4,
                  severity="page"),
        AlertRule(name="restart_churn", kind="rate",
                  series="serving_replica_restarts_total",
                  window=16, threshold=1.0, for_samples=1, cooldown=4,
                  severity="page"),))


class TestChaosAlertCycle:
    def test_dp2_chaos_rules_cycle_with_one_bundle_per_rule(self, tmp_path):
        """The acceptance headline: a dp=2 supervised chaos run (PR 11
        FaultPlan engine death) drives pool / goodput / restart rules
        pending→firing→resolved deterministically, with exactly one
        ``alert`` flight bundle per firing rule embedding the
        triggering series' history window."""
        def make(i, registry):
            paddle.seed(0)
            model = LlamaForCausalLM(
                LlamaConfig.tiny(num_hidden_layers=2))
            # tiny pool + prefix cache OFF: the free list dips under
            # load (pool rule fires) and recovers fully once requests
            # finish (no reuse-parking -> the floor rule can resolve).
            # 10 usable blocks: four admitted 9-token prompts want 12,
            # so the free list sits at 0 for several samples whatever
            # order the re-dispatched stream arrives in (at 14 usable it
            # touched 2 for one sample and the floor rule never fired)
            return EngineCore(model, config=EngineConfig(
                num_blocks=11, block_size=4, prefix_cache=False,
                scheduler=SchedulerConfig(
                    max_num_seqs=4, max_prefill_tokens_per_step=8)),
                registry=registry, metrics_labels={"replica": str(i)})

        # the death must land on the replica the shared prefix actually
        # routes to (prefix affinity concentrates wave 1 there) — a
        # deterministic preview, no engine needed
        from paddle_tpu.serving.fleet import affinity_replica_index

        target = affinity_replica_index(list(_PROMPT) + [0], dp=2,
                                        block_size=4)
        assert target is not None
        plan = FaultPlan(faults=(
            FaultSpec(point="engine_step_raise", step=5,
                      replica=str(target)),))
        fleet = FleetRouter.build(make, dp=2, config=FleetConfig(
            flight_dir=str(tmp_path), fault_plan=plan,
            alert_rules=_chaos_rules()))
        sup = FleetSupervisor(fleet, config=SupervisorConfig(
            backoff_initial_s=0.02, backoff_max_s=0.5,
            poll_interval_s=0.01)).start()
        fleet.start()
        try:
            # wave 1: deliberately unmeetable slo_ms -> every finish is
            # an SLO miss, burning the goodput budget while the death
            # fires the restart rule and the tiny pool starves
            wave1 = [fleet.submit_request(
                list(_PROMPT) + [i], SamplingParams(max_new_tokens=8),
                request_id=f"miss-{i}", slo_ms=0.0001, retryable=True)
                for i in range(6)]
            fleet.wait(wave1, timeout=300)
            # the injected death must have fired + restarted
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if (sup._restarts["engine_death"].value >= 1
                        and all(r.healthy for r in fleet.replicas)):
                    break
                time.sleep(0.02)
            assert sup._restarts["engine_death"].value == 1
            # wave 2: generous slo_ms -> goodput recovers.  Two at a
            # time: a pair holds at most 8 of the 10 blocks, so the
            # pool rule, resolved after wave 1, is not breached again
            for pair in ((0, 1), (2, 3)):
                fleet.wait([fleet.submit_request(
                    list(_PROMPT) + [99, i],
                    SamplingParams(max_new_tokens=4),
                    request_id=f"good-{i}", slo_ms=600_000.0)
                    for i in pair], timeout=300)
            # slide every rule's window past the incident (the
            # step-indexed equivalent of the incident aging out)
            for _ in range(20):
                fleet.history.sample()

            report = fleet.alerts.transitions_report()
            for rule in ("pool_exhaustion", "goodput_burn",
                         "restart_churn"):
                states = [t["state"] for t in report[rule]]
                assert "firing" in states, (rule, report[rule])
                assert states[-1] == "resolved", (rule, report[rule])
                # nothing still firing on the gauge
                assert fleet.registry.gauge(
                    "serving_alerts_firing", "x",
                    rule=rule).value == 0
            # exactly ONE alert bundle per firing rule, each embedding
            # the offending series' history window
            alert_bundles = sorted(
                p for p in os.listdir(str(tmp_path))
                if p.startswith("flight_alert_"))
            by_rule = {}
            for p in alert_bundles:
                with open(os.path.join(str(tmp_path), p)) as f:
                    bundle = json.load(f)
                alert = bundle["alert"]
                name = alert["rule"]["name"]
                by_rule.setdefault(name, []).append(bundle)
                assert alert["state"] == "firing"
                assert alert["offending_series"], name
                assert alert["history"], name
                for key, window in alert["history"].items():
                    assert window and all(
                        set(row) == {"i", "step", "v"}
                        for row in window), key
            assert sorted(by_rule) == ["goodput_burn",
                                       "pool_exhaustion",
                                       "restart_churn"]
            assert all(len(v) == 1 for v in by_rule.values()), {
                k: len(v) for k, v in by_rule.items()}
            # the death ALSO produced its own engine_death bundle —
            # the alert bundles are additional, not replacements
            assert any(p.startswith("flight_engine_death_")
                       for p in os.listdir(str(tmp_path)))
        finally:
            fleet.shutdown(drain_timeout=5.0)


# --------------------------------------------------------------------------
# HTTP debug surface (dp=1 and dp=2): protocol-clean 400/404, never 500
# --------------------------------------------------------------------------
class Harness:
    def __init__(self, engine, cfg=None):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.server = CompletionServer(engine, cfg or ServerConfig())
        self.run(self.server.start())
        self.port = self.server.port

    def run(self, coro, timeout=120):
        return asyncio.run_coroutine_threadsafe(
            coro, self.loop).result(timeout)

    def close(self):
        try:
            self.run(self.server.shutdown(drain_timeout=1.0), timeout=60)
        finally:
            self.loop.call_soon_threadsafe(self.loop.stop)
            self.thread.join(10)
            self.loop.close()


def _request(port, method, path, body=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    payload = None if body is None else json.dumps(body)
    conn.request(method, path, payload,
                 {"Content-Type": "application/json"} if payload else {})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


@pytest.fixture(scope="class")
def dp_servers():
    """One dp=1 and one dp=2 server, each having served one completion
    (so history has samples).  Class-scoped: building engines is the
    expensive part of this file."""
    live = {}
    for dp in (1, 2):
        fleet = _dp2_fleet() if dp == 2 else FleetRouter.build(
            lambda i, registry: EngineCore(
                _model(), config=EngineConfig(num_blocks=64,
                                              block_size=4),
                registry=registry, metrics_labels={"replica": "0"}),
            dp=1)
        h = Harness(fleet)
        status, _ = _request(h.port, "POST", "/v1/completions",
                             {"prompt": list(_PROMPT), "max_tokens": 3})
        assert status == 200
        live[dp] = h
    yield live
    for h in live.values():
        h.close()


class TestHttpSurface:
    @pytest.mark.parametrize("dp", [1, 2])
    def test_alerts_endpoint_ok(self, dp_servers, dp):
        status, data = _request(dp_servers[dp].port, "GET",
                                "/v1/debug/alerts")
        assert status == 200
        obj = json.loads(data)
        assert obj["object"] == "alerts"
        assert obj["status"] in ("ok", "firing")
        assert obj["rules"] == len(default_rule_set().rules)
        assert obj["evaluations"] > 0
        names = [d["rule"]["name"] for d in obj["data"]]
        assert "goodput_burn" in names
        for d in obj["data"]:
            assert d["state"] in ("inactive", "pending", "firing")

    @pytest.mark.parametrize("dp", [1, 2])
    def test_alerts_rule_filter_and_404(self, dp_servers, dp):
        port = dp_servers[dp].port
        status, data = _request(
            port, "GET", "/v1/debug/alerts?rule=goodput_burn")
        assert status == 200
        obj = json.loads(data)
        assert len(obj["data"]) == 1
        assert obj["data"][0]["rule"]["kind"] == "burn_rate"
        status, data = _request(port, "GET",
                                "/v1/debug/alerts?rule=nope")
        assert status == 404
        assert "nope" in json.loads(data)["error"]["message"]

    @pytest.mark.parametrize("dp", [1, 2])
    def test_history_index_and_series(self, dp_servers, dp):
        port = dp_servers[dp].port
        status, data = _request(port, "GET", "/v1/debug/history")
        assert status == 200
        obj = json.loads(data)
        assert "serving_engine_steps_total" in obj["series"]
        assert obj["stats"]["samples"] > 0
        status, data = _request(
            port, "GET",
            "/v1/debug/history?series=serving_engine_steps_total"
            "&window=4")
        assert status == 200
        obj = json.loads(data)
        # per-replica view: one row per label set
        assert len(obj["data"]) == dp
        for row in obj["data"]:
            assert row["kind"] == "counter"
            assert 1 <= len(row["window"]) <= 4
        # fleet view: aggregate across the label sets
        assert obj["fleet"]["latest_sum"] >= 1
        assert "increase" in obj["fleet"]

    @pytest.mark.parametrize("dp", [1, 2])
    @pytest.mark.parametrize("path,want", [
        ("/v1/debug/history?window=abc", 400),
        ("/v1/debug/history?window=0", 400),
        ("/v1/debug/history?series=serving_nope_total", 404),
        ("/v1/debug/alerts?rule=missing", 404),
    ])
    def test_protocol_clean_never_500(self, dp_servers, dp, path, want):
        status, data = _request(dp_servers[dp].port, "GET", path)
        assert status == want, (path, status, data)
        json.loads(data)                      # always a JSON body

    def test_metrics_page_exposes_history_and_alert_series(
            self, dp_servers):
        status, data = _request(dp_servers[2].port, "GET", "/metrics")
        assert status == 200
        text = data.decode()
        assert "serving_history_samples_total" in text
        assert "serving_alerts_firing" in text
        assert "serving_alert_transitions_total" in text


# --------------------------------------------------------------------------
# Lint coverage
# --------------------------------------------------------------------------
class TestLintCoverage:
    def test_history_and_alerts_are_scanned(self):
        scanned = {os.path.basename(p)
                   for p in bounded_lint.SCAN_FILES}
        assert {"history.py", "alerts.py"} <= scanned
        declared = {os.path.basename(p)
                    for p in docs_lint.DECLARING_MODULES}
        assert {"history.py", "alerts.py"} <= declared

    def test_lints_clean(self):
        assert bounded_lint.scan() == []
        assert docs_lint.scan() == []
