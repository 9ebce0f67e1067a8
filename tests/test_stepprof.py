"""Step-level performance introspection (ISSUE 9).

Tentpole coverage:

* bucket-utilization / padding-waste accounting through a real
  preempting chunked-prefill engine run: the StepProfiler's
  scheduled-token sum exactly equals the scheduler's planned-work
  ledger, utilization lives in (0, 1], and the observed bucket sets
  match the engine's asserted jit-trace bounds;
* compile-time attribution: every traced (program, bucket) lands in the
  bounded compile table with positive wall seconds, count equal to the
  engine's retrace counters — and the profiler itself adds ZERO new jit
  traces (on-vs-off runs are token-identical with equal trace counts);
* on-demand capture windows: N engine steps as a loadable Chrome trace,
  each step span annotated with program/bucket/utilization;
* dp=2 × chunked-prefill × preemption: per-replica step profiles are
  disjoint, invariants hold replica-wise, flight bundles embed the
  owning replica's last-K step records;
* HTTP debug surface: ``GET /v1/debug/compiles`` and
  ``GET /v1/debug/profile?steps=N`` (+ the satellite bugfix: JSON
  Content-Type everywhere, 400 for malformed query params, 404 — never
  500 — for unknown ids);
* ``step_profile=False`` leaves ``/metrics`` free of every
  ``serving_step_*`` / ``serving_compile_*`` / ``serving_padding_*``
  series.
"""

import asyncio
import http.client
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import (
    CaptureBusy,
    MetricsRegistry,
    StepProfiler,
    load_profiler_result,
)
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    FleetConfig,
    FleetRouter,
    SamplingParams,
    SchedulerConfig,
)
from paddle_tpu.serving.server import CompletionServer, ServerConfig

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))
try:
    import check_bounded_metrics as bounded_lint
    import check_metrics_docs as docs_lint
finally:
    sys.path.pop(0)

BS = 4


def _model(layers=2):
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))


def _engine(step_profile=True, num_blocks=15, max_num_seqs=4,
            chunk_budget=8, registry=None, metrics_labels=None):
    """Small pool + chunk budget: concurrent 16+10-token sequences
    cannot fit, so the run chunks, preempts, and recomputes."""
    return EngineCore(
        _model(),
        config=EngineConfig(
            num_blocks=num_blocks, block_size=BS,
            scheduler=SchedulerConfig(
                max_num_seqs=max_num_seqs,
                max_prefill_tokens_per_step=chunk_budget),
            step_profile=step_profile),
        registry=registry, metrics_labels=metrics_labels)


def _prompts(n=6, rng_seed=0, prefix_len=8, tail=8):
    rng = np.random.default_rng(rng_seed)
    prefix = rng.integers(0, 256, prefix_len).tolist()
    return [prefix + rng.integers(0, 256, tail).tolist() for _ in range(n)]


def _run(eng, prompts, max_new=10):
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=max_new))
            for p in prompts]
    eng.run(max_steps=4000)
    assert all(r.finished for r in reqs)
    return [list(r.output_tokens) for r in reqs]


def _engine_bucket_strs(buckets):
    """The engine's asserted bucket tuples -> stepprof bucket strings,
    keyed by program family."""
    out = {"prefill": set(), "chunk": set(), "decode": set()}
    for b in buckets:
        out[b[0]].add("x".join(str(int(v)) for v in b[1:]))
    return out


# --------------------------------------------------------------------------
# StepProfiler unit behaviour (no jax work)
# --------------------------------------------------------------------------
class TestStepProfilerUnit:
    def test_record_ring_bounded(self):
        sp = StepProfiler(registry=MetricsRegistry(), last_k=4)
        for i in range(10):
            sp.begin_step()
            sp.record_program("decode", (4, 8), scheduled=3, capacity=4,
                              wall_s=0.001)
            sp.end_step()
        recs = sp.records()
        assert len(recs) == 4
        assert recs[-1]["step"] == 10 and sp.steps == 10
        assert recs[-1]["utilization"] == 0.75

    def test_compile_table_bounded(self):
        sp = StepProfiler(registry=MetricsRegistry(), compile_table_max=8)
        for i in range(20):
            sp.record_compile("decode", (i, 8), 0.5)
        assert len(sp.compile_table()) == 8
        # the counters still saw every event
        assert sp.compile_totals()["decode"]["count"] == 8  # table view
        reg_total = sp._compile_c["decode"].value
        assert reg_total == 20

    def test_bucket_key_cap_collapses_to_other(self):
        sp = StepProfiler(registry=None, enabled=True)
        from paddle_tpu.observability.stepprof import _MAX_BUCKET_KEYS

        for i in range(_MAX_BUCKET_KEYS + 10):
            sp.record_program("decode", (i,), scheduled=1, capacity=1,
                              wall_s=0.0)
        assert len(sp._programs) <= _MAX_BUCKET_KEYS + 1
        assert "other" in sp.bucket_set("decode")

    def test_disabled_registers_nothing_and_refuses_capture(self):
        reg = MetricsRegistry()
        sp = StepProfiler(registry=reg, enabled=False)
        sp.begin_step()
        sp.record_program("decode", (4, 8), 3, 4, 0.001)
        sp.record_compile("decode", (4, 8), 0.5)
        sp.end_step()
        assert sp.records() == [] and sp.compile_table() == []
        text = reg.prometheus_text()
        for banned in ("serving_step_", "serving_compile",
                       "serving_padding", "serving_scheduled",
                       "serving_bucket_utilization"):
            assert banned not in text, banned
        with pytest.raises(RuntimeError):
            sp.arm_capture(4)

    def test_capture_busy_and_cancel_partial(self):
        sp = StepProfiler(registry=MetricsRegistry())
        w = sp.arm_capture(5, device_trace=False)
        with pytest.raises(CaptureBusy):
            sp.arm_capture(2, device_trace=False)
        sp.begin_step()
        sp.record_program("decode", (2, 4), 2, 2, 0.001)
        sp.end_step()
        assert not w.done.is_set()
        sp.cancel_capture(w)
        assert w.done.is_set() and w.complete is False
        assert w.result["captureSteps"] == 1
        assert w.result["complete"] is False
        # a new window can be armed after cancel
        w2 = sp.arm_capture(1, device_trace=False)
        sp.begin_step()
        sp.end_step()
        assert w2.done.is_set() and w2.complete is True

    def test_steps_range_validated(self):
        sp = StepProfiler(registry=MetricsRegistry())
        with pytest.raises(ValueError):
            sp.arm_capture(0)
        with pytest.raises(ValueError):
            sp.arm_capture(sp.max_capture_steps + 1)


# --------------------------------------------------------------------------
# engine integration: invariants on a preempting chunked-prefill run
# --------------------------------------------------------------------------
class TestEngineIntegration:
    def test_scheduled_token_invariant_and_buckets(self):
        eng = _engine()
        _run(eng, _prompts())
        sp = eng.stepprof
        assert eng.metrics.counters["preemptions"] > 0 or \
            eng.metrics.counters["chunked_prefill_steps"] > 0
        # exact invariant: profiler-scheduled == scheduler-planned
        assert (sp.scheduled_tokens("prefill") + sp.scheduled_tokens("chunk")
                == eng.scheduler.tokens_planned_prefill)
        assert sp.scheduled_tokens("decode") == \
            eng.scheduler.tokens_planned_decode
        assert sp.scheduled_tokens() == eng.scheduler.tokens_planned
        # ...and the prefill side equals the tokens-computed counter
        assert (sp.scheduled_tokens("prefill") + sp.scheduled_tokens("chunk")
                == eng.metrics.counters["prefill_tokens_computed"])
        # bucket sets match the engine's asserted jit-trace bounds
        want = _engine_bucket_strs(eng.prefill_buckets | eng.decode_buckets)
        for prog in ("prefill", "chunk", "decode"):
            assert sp.bucket_set(prog) == want[prog], prog
        # utilization in (0, 1] on every aggregate row and step record
        for row in sp.program_table():
            assert 0.0 < row["utilization"] <= 1.0, row
            assert row["padding_ratio"] is not None
        for rec in sp.records():
            if rec["capacity_tokens"]:
                assert 0.0 < rec["utilization"] <= 1.0, rec

    def test_compile_attribution_matches_trace_counters(self):
        eng = _engine()
        _run(eng, _prompts())
        sp = eng.stepprof
        table = sp.compile_table()
        assert len(table) == \
            eng.prefill_trace_count + eng.decode_trace_count
        assert all(row["seconds"] > 0 for row in table)
        # one compile per traced (program, bucket): entries are unique
        keys = [(r["program"], r["bucket"]) for r in table]
        assert len(keys) == len(set(keys))
        totals = sp.compile_totals()
        prefill_count = sum(totals.get(p, {"count": 0})["count"]
                            for p in ("prefill", "chunk"))
        assert prefill_count == eng.prefill_trace_count
        assert totals["decode"]["count"] == eng.decode_trace_count
        assert sp._compile_s["decode"].value > 0

    def test_zero_new_jit_traces_and_token_identity(self):
        prompts = _prompts()
        on = _engine(step_profile=True)
        out_on = _run(on, prompts)
        off = _engine(step_profile=False)
        out_off = _run(off, prompts)
        assert out_on == out_off
        assert on.prefill_trace_count == off.prefill_trace_count
        assert on.decode_trace_count == off.decode_trace_count

    def test_metrics_series_present_when_on_absent_when_off(self):
        on = _engine(step_profile=True)
        _run(on, _prompts(n=2))
        text = on.metrics.prometheus_text()
        for series in ("serving_step_seconds", "serving_bucket_utilization",
                       "serving_scheduled_tokens_total",
                       "serving_padding_tokens_total",
                       "serving_compile_seconds_total",
                       "serving_compiles_total"):
            assert series in text, series
        off = _engine(step_profile=False)
        _run(off, _prompts(n=2))
        text = off.metrics.prometheus_text()
        for banned in ("serving_step_", "serving_compile",
                       "serving_padding", "serving_scheduled",
                       "serving_bucket_utilization"):
            assert banned not in text, banned

    def test_utilization_report_and_summary_table(self):
        eng = _engine()
        _run(eng, _prompts())
        rep = eng.stepprof.utilization_report()
        assert rep["scheduled_tokens"] == eng.scheduler.tokens_planned
        assert rep["padding_tokens"] == \
            rep["capacity_tokens"] - rep["scheduled_tokens"]
        assert rep["padding_ratio"] is not None
        assert set(rep["programs"]) <= {"prefill", "chunk", "decode"}
        for p in rep["programs"].values():
            assert 0.0 < p["utilization"] <= 1.0
        assert rep["compiles"]
        report = eng.metrics.summary()
        assert "Bucket utilization / padding waste" in report
        assert "compile attribution" in report


# --------------------------------------------------------------------------
# capture windows
# --------------------------------------------------------------------------
class TestCaptureWindow:
    def test_capture_n_annotated_steps_loadable(self, tmp_path):
        eng = _engine()
        window = eng.stepprof.arm_capture(5, device_trace=False)
        _run(eng, _prompts())
        assert window.done.is_set() and window.complete
        result = window.result
        assert result["captureSteps"] == 5
        steps = [e for e in result["traceEvents"]
                 if e["name"] == "engine_step"]
        assert len(steps) == 5
        for ev in steps:
            assert ev["ph"] == "X" and ev["args"]["program"]
            assert ev["args"]["bucket"]
            assert 0.0 < ev["args"]["utilization"] <= 1.0
        # program child spans parent onto their step span
        children = [e for e in result["traceEvents"]
                    if e.get("cat") == "stepprof"
                    and e["name"] in ("prefill", "chunk", "decode")]
        assert children
        step_ids = {e["args"]["id"] for e in steps}
        assert all(e["args"]["parent"] in step_ids for e in children)
        # round-trips through the chrome loader
        path = tmp_path / "capture.json"
        path.write_text(json.dumps(result))
        loaded = load_profiler_result(str(path))
        assert len(loaded.find("engine_step")) == 5
        roots = [r for r in loaded.roots if r.name == "engine_step"]
        # a step's children: its program launches, and since ISSUE 25
        # its phases (tests/test_zzzzzzzzzzzz_step_phases.py orders them)
        from paddle_tpu.observability.tracer import STEP_PHASES
        assert roots and all(
            c.name in ("prefill", "chunk", "decode") + STEP_PHASES
            for r in roots for c in r.children)
        assert all(any(c.name in ("prefill", "chunk", "decode")
                       for c in r.children) for r in roots)

    def test_capture_excludes_steps_outside_window(self):
        eng = _engine()
        _run(eng, _prompts(n=2))  # pre-window traffic
        before = eng.stepprof.steps
        window = eng.stepprof.arm_capture(3, device_trace=False)
        _run(eng, _prompts(n=2, rng_seed=1))
        assert window.result["captureSteps"] == 3
        first = min(e["args"]["step"]
                    for e in window.result["traceEvents"]
                    if e["name"] == "engine_step")
        assert first == before + 1


# --------------------------------------------------------------------------
# dp=2 fleet: disjoint per-replica profiles + flight-bundle embedding
# --------------------------------------------------------------------------
class TestFleetStepProfiles:
    def _fleet(self, tmp_path=None, dp=2):
        def make(i, registry):
            return _engine(registry=registry,
                           metrics_labels={"replica": str(i)})
        return FleetRouter.build(
            make, dp=dp,
            config=FleetConfig(
                flight_dir=None if tmp_path is None else str(tmp_path)))

    def test_dp2_profiles_disjoint_and_invariants(self):
        from paddle_tpu.serving.fleet import affinity_replica_index

        rng = np.random.default_rng(0)
        fam_a = rng.integers(0, 256, 8).tolist()
        target_a = affinity_replica_index(fam_a, dp=2, block_size=BS)
        while True:
            fam_b = rng.integers(0, 256, 8).tolist()
            if affinity_replica_index(fam_b, dp=2, block_size=BS) \
                    != target_a:
                break
        prompts = []
        for _ in range(4):
            prompts.append(fam_a + rng.integers(0, 256, 8).tolist())
            prompts.append(fam_b + rng.integers(0, 256, 8).tolist())
        fleet = self._fleet()
        fleet.start()
        try:
            handles = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=10), request_id=f"r{i}")
                for i, p in enumerate(prompts)]
            fleet.wait(handles, timeout=600)
        finally:
            fleet.shutdown(drain_timeout=5.0)
        per_replica_rids = []
        for r in fleet.replicas:
            eng, sp = r.engine, r.engine.stepprof
            assert eng.metrics.counters["preemptions"] > 0
            assert eng.metrics.counters["chunked_prefill_steps"] > 0
            # invariants hold replica-wise
            assert sp.scheduled_tokens() == eng.scheduler.tokens_planned
            want = _engine_bucket_strs(
                eng.prefill_buckets | eng.decode_buckets)
            for prog in ("prefill", "chunk", "decode"):
                assert sp.bucket_set(prog) == want[prog]
            for row in sp.program_table():
                assert 0.0 < row["utilization"] <= 1.0
            # per-replica profiles are disjoint: each profiler only saw
            # requests the router routed to ITS engine
            rids = set()
            for rec in sp.records():
                for prog in rec["programs"]:
                    for rid in str(prog.get("request",
                                            prog.get("requests", ""))
                                   ).split(","):
                        if rid:
                            rids.add(rid)
            per_replica_rids.append(rids)
        assert per_replica_rids[0] and per_replica_rids[1]
        assert not (per_replica_rids[0] & per_replica_rids[1])
        # one shared registry, per-replica-labeled step series
        text = fleet.registry.prometheus_text()
        assert 'serving_bucket_utilization' in text
        assert 'replica="0"' in text and 'replica="1"' in text

    def test_fleet_rejects_heterogeneous_step_profile(self):
        def make(i, registry):
            return _engine(step_profile=(i == 0), registry=registry,
                           metrics_labels={"replica": str(i)})

        with pytest.raises(ValueError, match="step_profile"):
            FleetRouter.build(make, dp=2)

    def test_flight_bundle_embeds_owning_replica_steps(self, tmp_path):
        fleet = self._fleet(tmp_path=tmp_path)
        fleet.start()
        try:
            handles = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=4), request_id=f"s{i}")
                for i, p in enumerate(_prompts(n=4))]
            fleet.wait(handles, timeout=600)
            # find a replica that actually ran steps
            active = [r for r in fleet.replicas
                      if r.engine.stepprof.records()]
            assert active
            owner = active[0]
            path = fleet.flight.trigger("engine_death",
                                        replica=str(owner.index),
                                        detail="induced by test")
            assert path is not None
            bundle = json.loads(open(path).read())
            prof = bundle["step_profile"]
            assert set(prof) == {str(owner.index)}
            recs = prof[str(owner.index)]
            assert recs == owner.engine.stepprof.records()[-len(recs):]
            assert all("programs" in r for r in recs)
        finally:
            fleet.shutdown(drain_timeout=5.0)


# --------------------------------------------------------------------------
# HTTP debug surface
# --------------------------------------------------------------------------
class Harness:
    """A live CompletionServer on an asyncio loop in a daemon thread."""

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
    headers = {k.lower(): v for k, v in resp.getheaders()}
    conn.close()
    return resp.status, headers, data


@pytest.fixture
def harness_factory():
    live = []

    def make(engine, cfg=None):
        h = Harness(engine, cfg)
        live.append(h)
        return h

    yield make
    for h in live:
        h.close()


class TestHTTPDebug:
    def test_debug_compiles_lists_traced_programs(self, harness_factory):
        h = harness_factory(_engine(num_blocks=64))
        status, headers, data = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": list(range(10)), "max_tokens": 4})
        assert status == 200
        status, headers, data = _request(h.port, "GET",
                                         "/v1/debug/compiles")
        assert status == 200
        assert headers["content-type"] == "application/json"
        obj = json.loads(data)
        eng = h.server.engine
        assert len(obj["data"]) == \
            eng.prefill_trace_count + eng.decode_trace_count
        assert all(row["seconds"] > 0 for row in obj["data"])
        assert all(row["replica"] == "0" for row in obj["data"])
        assert obj["step_profile"] is True
        assert sum(t["count"] for t in obj["totals"].values()) == \
            len(obj["data"])

    def test_debug_profile_returns_annotated_chrome_trace(
            self, harness_factory):
        h = harness_factory(_engine(num_blocks=64))
        stop = threading.Event()

        def traffic():
            i = 0
            while not stop.is_set():
                try:
                    _request(h.port, "POST", "/v1/completions",
                             {"prompt": list(range(8)), "max_tokens": 32})
                except Exception:
                    return
                i += 1

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        try:
            status, headers, data = _request(
                h.port, "GET", "/v1/debug/profile?steps=3&timeout_s=60")
        finally:
            stop.set()
        t.join(120)
        assert status == 200
        assert headers["content-type"] == "application/json"
        obj = json.loads(data)
        assert obj["complete"] is True and obj["captureSteps"] == 3
        steps = [e for e in obj["traceEvents"]
                 if e["name"] == "engine_step"]
        assert len(steps) == 3
        for ev in steps:
            assert ev["args"]["program"] and "utilization" in ev["args"]
            assert "bucket" in ev["args"]

    def test_debug_profile_timeout_returns_partial(self, harness_factory):
        h = harness_factory(_engine(num_blocks=64))
        # idle engine: no steps will ever run — the handler must give
        # the window back instead of hanging
        status, headers, data = _request(
            h.port, "GET", "/v1/debug/profile?steps=4&timeout_s=1")
        assert status == 200
        obj = json.loads(data)
        assert obj["complete"] is False and obj["captureSteps"] == 0

    @pytest.mark.parametrize("query,code", [
        ("steps=abc", 400),
        ("steps=0", 400),
        ("steps=-3", 400),
        ("steps=99999", 400),
        ("steps=2&timeout_s=nope", 400),
        ("steps=2&replica=x", 400),
        ("steps=2&replica=7", 404),
    ])
    def test_debug_profile_bad_params_json_4xx(self, harness_factory,
                                               query, code):
        h = harness_factory(_engine(num_blocks=64))
        status, headers, data = _request(
            h.port, "GET", f"/v1/debug/profile?{query}")
        assert status == code, data
        assert headers["content-type"] == "application/json"
        assert "error" in json.loads(data)

    def test_debug_profile_disabled_answers_400(self, harness_factory):
        h = harness_factory(_engine(num_blocks=64, step_profile=False))
        status, headers, data = _request(
            h.port, "GET", "/v1/debug/profile?steps=2")
        assert status == 400
        assert headers["content-type"] == "application/json"
        assert "step_profile" in json.loads(data)["error"]["message"]

    def test_debug_unknown_route_404_json(self, harness_factory):
        h = harness_factory(_engine(num_blocks=64))
        status, headers, data = _request(h.port, "GET", "/v1/debug/nope")
        assert status == 404
        assert headers["content-type"] == "application/json"

    def test_requests_unknown_id_404_json_both_formats(
            self, harness_factory):
        """Satellite bugfix: unknown ids are 404 (not 500 / dropped
        connection) with a JSON body, chrome format included."""
        h = harness_factory(_engine(num_blocks=64))
        for path in ("/v1/requests/ghost",
                     "/v1/requests/ghost?format=chrome"):
            status, headers, data = _request(h.port, "GET", path)
            assert status == 404, path
            assert headers["content-type"] == "application/json"
            assert json.loads(data)["error"]["type"] == "not_found"

    def test_requests_bad_format_param_400_json(self, harness_factory):
        h = harness_factory(_engine(num_blocks=64))
        status, headers, data = _request(
            h.port, "GET", "/v1/requests/any?format=perfetto")
        assert status == 400
        assert headers["content-type"] == "application/json"

    def test_requests_chrome_format_is_json_content_type(
            self, harness_factory):
        h = harness_factory(_engine(num_blocks=64))
        status, headers, data = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": [3, 1, 4, 1, 5], "max_tokens": 3})
        rid = json.loads(data)["id"]
        status, headers, data = _request(
            h.port, "GET", f"/v1/requests/{rid}?format=chrome")
        assert status == 200
        assert headers["content-type"] == "application/json"
        assert json.loads(data)["traceEvents"]


# --------------------------------------------------------------------------
# lint coverage (satellite tooling)
# --------------------------------------------------------------------------
class TestLintCoverage:
    def test_bounded_metrics_scan_covers_stepprof(self):
        covered = {os.path.relpath(p, _REPO)
                   for p in bounded_lint.SCAN_FILES}
        assert "paddle_tpu/observability/stepprof.py" in covered
        assert bounded_lint.scan(dirs=(),
                                 files=bounded_lint.SCAN_FILES) == []

    def test_metrics_docs_lint_covers_stepprof(self):
        covered = {os.path.relpath(p, _REPO)
                   for p in docs_lint.DECLARING_MODULES}
        assert "paddle_tpu/observability/stepprof.py" in covered
        assert docs_lint.scan() == []
