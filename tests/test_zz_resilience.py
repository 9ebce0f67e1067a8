"""Self-healing fleet supervisor + deterministic fault injection
(ISSUE 12).

Tentpole coverage:

* **headline chaos contract** — injected ``engine_step_raise`` on a
  replica mid-stream at dp=2: the router reroutes, the supervisor
  restarts the replica within the backoff bound, ZERO
  queued-but-unstarted requests are lost, and every surviving or
  re-dispatched request's greedy tokens are identical to the fault-free
  run;
* **mid-stream verdicts** — a request that already streamed tokens
  finishes ``replica_failed`` (partial output preserved) unless it
  opted in with ``retryable=true``, in which case greedy recompute
  re-delivers identical tokens;
* **quarantine-and-replace** — injected ``kernel_corrupt`` drives a PR 9
  audit divergence: the degraded replica is quarantined (routing
  stops), drained, and replaced with a clean engine; ``/v1/debug/audit``
  returns to ok; exactly one flight bundle per recovery action;
* **watchdog stall** — injected ``slow_step``: the replica goes
  unhealthy (excluded from routing) the moment the watchdog fires, a
  stall that resolves re-includes it untouched, a stall that persists
  past the grace escalates to a restart;
* **crash loop** — ``max_restarts`` failures in the window → permanent
  exclusion that survives subsequent request waves;
* satellites — 503 **with Retry-After** + ``/readyz restarting=N``
  while the whole fleet is momentarily down but recovering; no
  resurrection of a replica that dies mid-drain; the
  ``check_exception_hygiene`` lint with self-tests; lint-coverage of
  the two new modules; ``FaultPlan`` determinism and exactly-once
  firing.
"""

import http.client
import json
import os
import sys
import tempfile
import textwrap
import threading
import time

import asyncio

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.audit import AuditConfig
from paddle_tpu.observability.flight import FlightConfig, FlightRecorder
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    FaultInjector,
    FaultPlan,
    FaultSpec,
    FleetConfig,
    FleetRouter,
    FleetSupervisor,
    InjectedFault,
    SamplingParams,
    SchedulerConfig,
    SupervisorConfig,
)
from paddle_tpu.serving.fleet import affinity_replica_index
from paddle_tpu.serving.kv_manager import KVCacheManager
from paddle_tpu.serving.protocol import (
    ProtocolError,
    parse_completion_request,
)
from paddle_tpu.serving.server import CompletionServer, ServerConfig

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))
try:
    import check_bounded_metrics as bounded_lint
    import check_exception_hygiene as hygiene_lint
    import check_metrics_docs as docs_lint
finally:
    sys.path.pop(0)

BS = 4


def _factory(num_blocks=64, max_num_seqs=4, audit=None):
    """Deterministic engine factory (seed before build) — the shape the
    supervisor needs to rebuild a replica with identical weights."""

    def make(i, registry):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
        return EngineCore(model, config=EngineConfig(
            num_blocks=num_blocks, block_size=BS,
            scheduler=SchedulerConfig(max_num_seqs=max_num_seqs),
            audit=audit),
            registry=registry, metrics_labels={"replica": str(i)})

    return make


def _prompts(n=6, seed=0, prefix_tokens=8, tail_tokens=8):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, 256, prefix_tokens).tolist()
    return [prefix + rng.integers(0, 256, tail_tokens).tolist()
            for _ in range(n)]


_FAST_SUP = dict(backoff_initial_s=0.01, backoff_max_s=0.2,
                 poll_interval_s=0.01)


def _build(dp=2, plan=None, flight_dir=None, audit=None, sup_cfg=None,
           supervise=True):
    fleet = FleetRouter.build(
        _factory(audit=audit), dp=dp,
        config=FleetConfig(fault_plan=plan, flight_dir=flight_dir))
    sup = None
    if supervise:
        sup = FleetSupervisor(fleet, config=sup_cfg or SupervisorConfig(
            **_FAST_SUP))
        sup.start()
    fleet.start()
    return fleet, sup


_expected_cache = {}


def _expected(max_new=8, n=6, seed=0):
    """Fault-free greedy tokens per prompt index, from a single direct
    engine (batch-composition independence makes these THE reference
    for any fleet placement)."""
    key = (max_new, n, seed)
    if key not in _expected_cache:
        make = _factory()
        eng = make(0, None)
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=max_new),
                                request_id=f"exp-{i}")
                for i, p in enumerate(_prompts(n, seed=seed))]
        eng.run(max_steps=4000)
        assert all(r.finished for r in reqs)
        _expected_cache[key] = [list(r.output_tokens) for r in reqs]
    return _expected_cache[key]


def _affinity_target(prompt):
    """The replica index a dp=2 fleet with default config routes this
    prompt to (pure preview — usable before the fleet exists, so fault
    plans can be aimed at the replica that will actually get traffic)."""
    t = affinity_replica_index(prompt, dp=2, block_size=BS)
    assert t is not None
    return t


def _wait(predicate, timeout=60.0, interval=0.01, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


# --------------------------------------------------------------------------
# fault plans / injector units (no engines)
# --------------------------------------------------------------------------
class TestFaultPlan:
    def test_json_roundtrip_and_equality(self, tmp_path):
        plan = FaultPlan(faults=(
            FaultSpec(point="engine_step_raise", step=6, replica="1"),
            FaultSpec(point="slow_step", step=3, replica="0",
                      duration_s=0.5)), seed=7)
        path = str(tmp_path / "plan.json")
        with open(path, "w") as f:
            json.dump(plan.to_obj(), f)
        loaded = FaultPlan.from_json(path)
        assert loaded == plan  # frozen dataclasses: value equality
        assert loaded.faults[1].duration_s == 0.5
        # integer replica indexes in JSON normalize to strings
        again = FaultPlan.from_obj(
            {"faults": [{"point": "pool_exhaust", "replica": 1,
                         "step": 2}]})
        assert again.faults[0].replica == "1"

    def test_validation(self):
        with pytest.raises(ValueError, match="unknown injection point"):
            FaultSpec(point="meteor_strike", step=1)
        with pytest.raises(ValueError, match="step must be >= 1"):
            FaultSpec(point="slow_step", step=0)
        with pytest.raises(ValueError, match="must be a JSON object"):
            FaultPlan.from_obj("nope")

    def test_injector_fires_exactly_once_at_or_after_step(self):
        plan = FaultPlan(faults=(
            FaultSpec(point="pool_exhaust", step=3, replica="0"),
            FaultSpec(point="pool_exhaust", step=5, replica="0"),
            FaultSpec(point="pool_exhaust", step=1, replica="1")))
        fi = FaultInjector(plan, replica="0")
        fi.begin_step(1)
        assert not fi.pool_exhausted   # scheduled for step 3
        fi.begin_step(4)               # skipped past 3: fires at >= 3
        assert fi.pool_exhausted
        fi.begin_step(4)               # exactly-once: same step re-run
        assert not fi.pool_exhausted   # (entry 1 consumed, entry 2 at 5)
        fi.begin_step(9)
        assert fi.pool_exhausted       # entry 2
        fi.begin_step(9)
        assert not fi.pool_exhausted   # plan exhausted for this replica
        snap = fi.snapshot()
        assert snap["scheduled"] == 2 and snap["fired"] == 2
        # replica 1's entry is invisible to replica 0's view
        assert FaultInjector(plan, replica="1").remaining == 1

    def test_engine_step_raise_raises(self):
        fi = FaultInjector(FaultPlan(faults=(
            FaultSpec(point="engine_step_raise", step=2, replica="0"),)),
            replica="0")
        fi.begin_step(1)
        with pytest.raises(InjectedFault, match="replica 0"):
            fi.begin_step(2)
        fi.begin_step(3)  # consumed: no re-raise

    def test_corrupt_logits_flips_argmax_copy_only(self):
        fi = FaultInjector(FaultPlan(faults=(
            FaultSpec(point="kernel_corrupt", step=1, replica="0"),)),
            replica="0")
        logits = np.array([[0.1, 2.0, -1.0], [0.5, 0.2, 0.9]], np.float32)
        orig = logits.copy()
        out = fi.corrupt_logits(1, logits)
        assert np.array_equal(logits, orig)  # the served copy untouched
        assert out[0].argmax() != orig[0].argmax()
        # consumed: a second launch passes through untouched
        out2 = fi.corrupt_logits(2, logits)
        assert out2 is logits


class TestPoolRefusal:
    def test_refuse_allocations_flag(self):
        kv = KVCacheManager(num_blocks=8, block_size=4)
        assert kv.allocate("a", 4)
        kv.commit("a", 4)  # block full: the next slot needs a NEW block
        avail = kv.num_available
        assert avail > 0
        kv.refuse_allocations = True
        assert kv.num_available == 0
        assert kv.append_slot("a") is None
        assert not kv.allocate("b", 1)
        kv.refuse_allocations = False
        assert kv.num_available == avail
        assert kv.append_slot("a") is not None


class TestProtocolRetryable:
    def test_parse(self):
        req = parse_completion_request(
            json.dumps({"prompt": [1, 2], "retryable": True}).encode())
        assert req.retryable is True
        req = parse_completion_request(json.dumps({"prompt": [1]}).encode())
        assert req.retryable is False
        with pytest.raises(ProtocolError, match="retryable"):
            parse_completion_request(
                json.dumps({"prompt": [1], "retryable": "yes"}).encode())


class TestSupervisorConfig:
    def test_validation_and_single_attach(self):
        with pytest.raises(ValueError, match="max_restarts"):
            SupervisorConfig(max_restarts=0)
        with pytest.raises(ValueError, match="backoff_factor"):
            SupervisorConfig(backoff_factor=0.5)
        fleet = FleetRouter.build(_factory(), dp=1)
        try:
            FleetSupervisor(fleet)  # not started: just attach
            with pytest.raises(ValueError, match="already attached"):
                FleetSupervisor(fleet)
        finally:
            fleet.shutdown(drain_timeout=0.1)

    def test_factory_required(self):
        make = _factory()
        eng = make(0, None)
        fleet = FleetRouter.from_engine(eng)  # no factory remembered
        try:
            with pytest.raises(ValueError, match="engine_factory"):
                FleetSupervisor(fleet)
        finally:
            fleet.shutdown(drain_timeout=0.1)


class TestFlightResetOnce:
    def test_engine_death_rearms(self, tmp_path):
        fr = FlightRecorder(config=FlightConfig(dump_dir=str(tmp_path)))
        assert fr.trigger("engine_death", replica="0") is not None
        assert fr.trigger("engine_death", replica="0") is None  # deduped
        fr.reset_once("engine_death", "0")
        assert fr.trigger("engine_death", replica="0") is not None
        assert len(fr.bundles) == 2


# --------------------------------------------------------------------------
# headline chaos contract (dp=2, injected death mid-stream)
# --------------------------------------------------------------------------
class TestHeadlineChaos:
    def test_death_midstream_restart_zero_lost_token_identical(
            self, tmp_path):
        prompts = _prompts(6)
        # compute the fault-free reference FIRST: the supervisor's
        # rebuild seeds + builds a model on its own thread, and two
        # concurrent model builds interleave the global RNG
        expected = _expected(max_new=8, n=6)
        target = _affinity_target(prompts[0])
        plan = FaultPlan(faults=(
            FaultSpec(point="engine_step_raise", step=4,
                      replica=str(target)),))
        fleet, sup = _build(plan=plan, flight_dir=str(tmp_path))
        try:
            t0 = time.monotonic()
            hs = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=8),
                request_id=f"c{i}", retryable=True)
                for i, p in enumerate(prompts)]
            fleet.wait(hs, timeout=120)
            # ZERO lost: every request finished normally, none aborted
            assert all(h.finish_reason == "length" for h in hs), \
                {h.rid: h.finish_reason for h in hs}
            # greedy token identity vs the fault-free run
            for i, h in enumerate(hs):
                assert h.output_tokens == expected[i], \
                    (h.rid, h.output_tokens, expected[i])
            # the fault fired exactly once, on the scheduled replica
            fi = fleet.fault_injectors[target]
            assert fi.snapshot()["fired"] == 1
            # supervisor restarted the replica within the backoff bound
            _wait(lambda: fleet.replicas[target].alive,
                  msg="replica restart")
            assert time.monotonic() - t0 < 60
            assert int(sup._restarts["engine_death"].value) == 1
            assert int(sup._redis_c.value) >= 1   # rerouted work
            assert int(sup._failed_c.value) == 0  # nothing failed
            assert sup._recovery_h.count == 1
            # exactly ONE engine_death bundle for the one recovery action
            deaths = [f for f in os.listdir(str(tmp_path))
                      if f.startswith("flight_engine_death")]
            assert len(deaths) == 1, sorted(os.listdir(str(tmp_path)))
            # the injection is on the record: counter + flight-ring event
            text = fleet.registry.prometheus_text()
            assert 'serving_faults_injected_total{' in text
            assert 'point="engine_step_raise"' in text
            with open(os.path.join(str(tmp_path), deaths[0])) as f:
                bundle = json.load(f)
            assert any(ev["name"] == "fault_injected"
                       for ev in bundle["events"]), \
                "chaos bundle does not name the injected fault"
            # the restarted replica serves again — route to it directly
            h = fleet.submit_request(prompts[0],
                                     SamplingParams(max_new_tokens=4),
                                     request_id="post-restart")
            fleet.wait([h], timeout=120)
            assert h.finish_reason == "length"
            assert h.output_tokens == expected[0][:4]
        finally:
            fleet.shutdown(drain_timeout=2.0)


class TestMidStreamVerdicts:
    def _one_long(self, retryable, tmp_path):
        prompts = _prompts(1, prefix_tokens=8, tail_tokens=8)
        _expected(max_new=24, n=1)  # cache the reference BEFORE any
        # supervisor rebuild can race the model build (global RNG)
        target = _affinity_target(prompts[0])
        plan = FaultPlan(faults=(
            FaultSpec(point="engine_step_raise", step=10,
                      replica=str(target)),))
        fleet, sup = _build(plan=plan, flight_dir=str(tmp_path))
        try:
            h = fleet.submit_request(
                prompts[0], SamplingParams(max_new_tokens=24),
                request_id="long", retryable=retryable)
            assert h.replica.index == target
            fleet.wait([h], timeout=120)
            return fleet, sup, h
        except BaseException:
            fleet.shutdown(drain_timeout=1.0)
            raise

    def test_non_retryable_midstream_finishes_replica_failed(
            self, tmp_path):
        fleet, sup, h = self._one_long(False, tmp_path)
        try:
            assert h.finish_reason == "replica_failed"
            # the frozen partial output stays readable (tokens were
            # already streamed when the replica died)
            assert 0 < len(h.output_tokens) < 24
            assert h.output_tokens == _expected(
                max_new=24, n=1)[0][:len(h.output_tokens)]
            assert int(sup._failed_c.value) == 1
            assert int(sup._redis_c.value) == 0
        finally:
            fleet.shutdown(drain_timeout=2.0)

    def test_retryable_midstream_token_identical(self, tmp_path):
        fleet, sup, h = self._one_long(True, tmp_path)
        try:
            assert h.finish_reason == "length"
            assert h.output_tokens == _expected(max_new=24, n=1)[0]
            assert int(sup._redis_c.value) == 1
            assert int(sup._failed_c.value) == 0
            # the retry landed on a DIFFERENT (surviving) replica
            assert h.replica.index != _affinity_target(h.prompt_ids)
        finally:
            fleet.shutdown(drain_timeout=2.0)


# --------------------------------------------------------------------------
# pool_exhaust: one step of allocation refusal, token-identical
# --------------------------------------------------------------------------
class TestPoolExhaustInjection:
    def test_refusal_preempts_but_tokens_identical(self):
        prompts = _prompts(4)
        _expected(max_new=8, n=4)
        plan = FaultPlan(faults=(
            FaultSpec(point="pool_exhaust", step=5, replica="0"),))
        fleet, _ = _build(dp=1, plan=plan, supervise=False)
        try:
            hs = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=8), request_id=f"p{i}")
                for i, p in enumerate(prompts)]
            fleet.wait(hs, timeout=120)
            expected = _expected(max_new=8, n=4)
            for i, h in enumerate(hs):
                assert h.finish_reason == "length"
                assert h.output_tokens == expected[i]
            eng = fleet.replicas[0].engine
            # the refusal surfaced as a preemption scheduling event (a
            # 64-block pool never preempts this stream naturally)
            assert eng.metrics.counters["preemptions"] > 0
            assert fleet.fault_injectors[0].snapshot()["fired"] == 1
            assert eng.kv.refuse_allocations is False  # one pass only
        finally:
            fleet.shutdown(drain_timeout=2.0)


# --------------------------------------------------------------------------
# quarantine-and-replace (kernel_corrupt -> audit degraded)
# --------------------------------------------------------------------------
class TestQuarantine:
    @pytest.mark.parametrize("cascade", [False, True],
                             ids=["corrupt", "death_then_corrupt"])
    def test_corrupt_quarantines_replaces_audit_ok(self, tmp_path, cascade):
        """``corrupt``: the replica with the traffic audits a corrupted
        copy and is quarantined and replaced.  ``death_then_corrupt``:
        the cascade — the replica with the traffic dies, its stream is
        re-dispatched onto the OTHER replica, which only then starts
        stepping and meets its own corruption: exactly one restart per
        cause, and every request still finishes with the fault-free
        greedy tokens across BOTH faults."""
        prompts = _prompts(6)
        _expected(max_new=8, n=6)  # reference cached before the rebuild
        target = _affinity_target(prompts[0])
        if cascade:
            faults = (FaultSpec(point="engine_step_raise", step=6,
                                replica=str(target)),
                      FaultSpec(point="kernel_corrupt", step=4,
                                replica=str(1 - target)))
            corrupted = 1 - target
        else:
            faults = (FaultSpec(point="kernel_corrupt", step=5,
                                replica=str(target)),)
            corrupted = target
        fleet, sup = _build(
            plan=FaultPlan(faults=faults), flight_dir=str(tmp_path),
            audit=AuditConfig(enabled=True, sample_every=1),
            sup_cfg=SupervisorConfig(quarantine_drain_s=10.0,
                                     **_FAST_SUP))
        try:
            hs = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=8), request_id=f"q{i}",
                retryable=cascade)
                for i, p in enumerate(prompts)]
            fleet.wait(hs, timeout=120)
            # the corruption hit only the AUDIT copy: every request
            # finished normally with fault-free greedy tokens
            expected = _expected(max_new=8, n=6)
            for i, h in enumerate(hs):
                assert h.finish_reason == "length"
                assert h.output_tokens == expected[i]
            # quarantine completed: replica replaced, audit ok again
            # (a restart's counter moves last, after the swap)
            restarts = {"engine_death": int(cascade), "watchdog": 0,
                        "quarantine": 1}
            _wait(lambda: ({c: int(v.value)
                            for c, v in sup._restarts.items()} == restarts
                           and all(r.healthy for r in fleet.replicas)
                           and fleet.replicas[corrupted].engine.audit.status
                           == "ok"),
                  msg="quarantine + replacement")
            assert all(r.engine.audit.status == "ok"
                       for r in fleet.replicas)
            assert int(sup._quar_c.value) == 1
            assert int(sup._failed_c.value) == 0
            # exactly one flight bundle per action: the audit's
            # divergence dump + the supervisor's quarantine dump (+ the
            # death's own)
            names = sorted(os.listdir(str(tmp_path)))
            assert sum(n.startswith("flight_divergence")
                       for n in names) == 1, names
            assert sum(n.startswith("flight_quarantine")
                       for n in names) == 1, names
            assert sum(n.startswith("flight_engine_death")
                       for n in names) == int(cascade), names
            # the replacement serves
            h = fleet.submit_request(prompts[0],
                                     SamplingParams(max_new_tokens=4),
                                     request_id="post-quarantine")
            fleet.wait([h], timeout=120)
            assert h.finish_reason == "length"
        finally:
            fleet.shutdown(drain_timeout=2.0)


# --------------------------------------------------------------------------
# watchdog: unhealthy on fire, recover or escalate
# --------------------------------------------------------------------------
def _warm(fleet, n=4, max_new=4):
    hs = [fleet.submit_request(p, SamplingParams(max_new_tokens=max_new),
                               request_id=f"warm-{i}-{time.monotonic_ns()}")
          for i, p in enumerate(_prompts(n))]
    fleet.wait(hs, timeout=120)
    return hs


class TestWatchdog:
    def _stall(self, fleet, target, duration, at_offset=1):
        """Arm a slow_step on `target`'s engine at its next step (bound
        post-warmup, so jit-compile steps never race the watchdog)."""
        eng = fleet.replicas[target].engine
        plan = FaultPlan(faults=(
            FaultSpec(point="slow_step", step=eng.step_seq + at_offset,
                      replica=str(target), duration_s=duration),))
        fi = FaultInjector(plan, replica=str(target),
                           lifecycle=fleet.lifecycle,
                           registry=fleet.registry)
        eng.set_fault_injector(fi)
        return fi

    def test_fire_marks_unhealthy_then_reincludes_on_recovery(
            self, tmp_path):
        prompts = _prompts(6)
        target = _affinity_target(prompts[0])
        fleet = FleetRouter.build(
            _factory(), dp=2,
            config=FleetConfig(flight_dir=str(tmp_path)))
        fleet.start()
        sup = None
        try:
            _warm(fleet)  # compile OUTSIDE the watchdog window
            sup = FleetSupervisor(fleet, config=SupervisorConfig(
                watchdog_timeout_s=0.4, watchdog_grace_s=120.0,
                **_FAST_SUP)).start()
            self._stall(fleet, target, duration=2.0)
            h = fleet.submit_request(prompts[0],
                                     SamplingParams(max_new_tokens=6),
                                     request_id="stalled",
                                     retryable=True)
            assert h.replica.index == target
            # watchdog fires mid-stall: replica excluded from routing
            _wait(lambda: fleet.replicas[target].unhealthy,
                  msg="watchdog fire")
            assert not fleet.replicas[target].healthy
            assert fleet.replicas[target].alive  # hung, NOT dead
            # traffic routes around the stalled replica
            h2 = fleet.submit_request(prompts[1],
                                      SamplingParams(max_new_tokens=4),
                                      request_id="around")
            assert h2.replica.index != target
            # exactly one watchdog bundle for the stall (written on the
            # watchdog thread moments after the unhealthy mark — poll)
            _wait(lambda: sum(n.startswith("flight_watchdog")
                              for n in os.listdir(str(tmp_path))) == 1,
                  msg="watchdog bundle on disk")
            # the stall resolves inside the grace: re-included, no
            # restart, the stalled request finishes normally
            fleet.wait([h, h2], timeout=120)
            assert h.finish_reason == "length"
            _wait(lambda: fleet.replicas[target].healthy,
                  msg="re-inclusion after recovery")
            assert int(sup._restarts["watchdog"].value) == 0
        finally:
            fleet.shutdown(drain_timeout=2.0)

    def test_persistent_stall_escalates_to_restart(self, tmp_path):
        prompts = _prompts(6)
        _expected(max_new=6, n=3)  # reference cached before the rebuild
        target = _affinity_target(prompts[0])
        fleet = FleetRouter.build(
            _factory(), dp=2,
            config=FleetConfig(flight_dir=str(tmp_path)))
        fleet.start()
        sup = None
        try:
            _warm(fleet)
            # grace must outlast a rebuilt engine's compile steps (the
            # replacement jits from scratch under its own watchdog) —
            # only a stall LONGER than watchdog+grace escalates
            sup = FleetSupervisor(fleet, config=SupervisorConfig(
                watchdog_timeout_s=0.4, watchdog_grace_s=4.0,
                **_FAST_SUP)).start()
            self._stall(fleet, target, duration=10.0)
            hs = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=6),
                request_id=f"e{i}", retryable=True)
                for i, p in enumerate(prompts[:3])]
            _wait(lambda: int(sup._restarts["watchdog"].value) >= 1,
                  timeout=30, msg="watchdog escalation restart")
            # every request still completes (re-dispatched off the hung
            # replica), token-identical to the fault-free run
            fleet.wait(hs, timeout=120)
            expected = _expected(max_new=6, n=3)
            for i, h in enumerate(hs):
                assert h.finish_reason == "length", h.rid
                assert h.output_tokens == expected[i]
            assert int(sup._restarts["watchdog"].value) == 1
            _wait(lambda: fleet.replicas[target].healthy,
                  msg="replacement serving")
            # let the abandoned stalled thread wake and exit before
            # teardown (it sleeps `duration`, sees _stop, runs dry)
            time.sleep(0.2)
        finally:
            fleet.shutdown(drain_timeout=2.0)


# --------------------------------------------------------------------------
# crash loop: permanent exclusion that survives subsequent waves
# --------------------------------------------------------------------------
class TestCrashLoop:
    def test_exclusion_after_max_restarts_survives_waves(self, tmp_path):
        prompts = _prompts(6)
        target = _affinity_target(prompts[0])
        # three scheduled deaths at step 1: the fresh engine dies the
        # moment it first steps, every incarnation
        plan = FaultPlan(faults=tuple(
            FaultSpec(point="engine_step_raise", step=1,
                      replica=str(target)) for _ in range(3)))
        fleet, sup = _build(
            plan=plan, flight_dir=str(tmp_path),
            sup_cfg=SupervisorConfig(max_restarts=2,
                                     restart_window_s=120.0,
                                     **_FAST_SUP))
        try:
            for wave in range(3):
                h = fleet.submit_request(
                    prompts[0], SamplingParams(max_new_tokens=4),
                    request_id=f"wave{wave}", retryable=True)
                fleet.wait([h], timeout=120)
                assert h.finish_reason == "length", (wave, h.finish_reason)
                if wave < 2:
                    # restarted: wait for the fresh replica before the
                    # next wave targets it
                    _wait(lambda w=wave:
                          int(sup._restarts["engine_death"].value) == w + 1
                          or target in sup.excluded,
                          msg=f"restart after wave {wave}")
            _wait(lambda: target in sup.excluded, msg="crash-loop verdict")
            assert int(sup._restarts["engine_death"].value) == 2
            assert sum(n.startswith("flight_crash_loop")
                       for n in os.listdir(str(tmp_path))) == 1
            # exclusion survives subsequent waves: traffic keeps flowing
            # on the survivor, no resurrection attempts
            for wave in range(3, 5):
                h = fleet.submit_request(
                    prompts[0], SamplingParams(max_new_tokens=4),
                    request_id=f"wave{wave}")
                assert h.replica.index != target
                fleet.wait([h], timeout=120)
                assert h.finish_reason == "length"
            assert int(sup._restarts["engine_death"].value) == 2
            assert target in sup.excluded
            assert not fleet.replicas[target].alive
        finally:
            fleet.shutdown(drain_timeout=2.0)


# --------------------------------------------------------------------------
# drain: a replica dying mid-shutdown is NOT resurrected
# --------------------------------------------------------------------------
class TestDrainNoResurrection:
    def test_death_mid_drain_completes_without_restart(self):
        prompts = _prompts(2)
        target = _affinity_target(prompts[0])
        plan = FaultPlan(faults=(
            FaultSpec(point="engine_step_raise", step=6,
                      replica=str(target)),))
        fleet, sup = _build(plan=plan)
        try:
            h = fleet.submit_request(
                prompts[0], SamplingParams(max_new_tokens=100000),
                request_id="drainer")
            assert h.replica.index == target
            _wait(lambda: h.req is not None and h.req.output_tokens,
                  msg="request streaming")
            fleet.begin_drain()
            dead_replica = fleet.replicas[target]
            # the injected death fires mid-drain; the supervisor must
            # terminate the orphan and NOT rebuild
            _wait(lambda: h.finished, msg="orphan terminated under drain")
            assert h.finish_reason in ("abort", "timeout")
            assert fleet.replicas[target] is dead_replica  # no rebuild
            assert not dead_replica.alive
            assert int(sup._restarts["engine_death"].value) == 0
            fleet.shutdown(drain_timeout=2.0)
            assert fleet.replicas[target] is dead_replica
            # the survivor drained clean
            other = fleet.replicas[1 - target].engine
            assert other.kv.occupancy() == 0.0
        finally:
            fleet.shutdown(drain_timeout=0.5)  # idempotent


# --------------------------------------------------------------------------
# HTTP: 503 + Retry-After while restarting; /readyz restarting=N;
#       /v1/debug/audit returns to ok after quarantine
# --------------------------------------------------------------------------
def _request(port, method, path, body=None, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    payload = None if body is None else json.dumps(body)
    conn.request(method, path, payload,
                 {"Content-Type": "application/json"} if payload else {})
    resp = conn.getresponse()
    data = resp.read()
    status, headers = resp.status, dict(resp.getheaders())
    conn.close()
    return status, headers, data


class Harness:
    """A live CompletionServer on an asyncio loop in a daemon thread."""

    def __init__(self, fleet, cfg=None):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.server = CompletionServer(fleet, cfg or ServerConfig())
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


class TestHTTPRestarting:
    def test_all_dead_503_retry_after_and_readyz_restarting(self):
        prompts = _prompts(4)
        # a supervisor whose backoff is far longer than the test: both
        # replicas stay down, recovery pending — the window the
        # satellite bugfix is about
        fleet, sup = _build(sup_cfg=SupervisorConfig(
            backoff_initial_s=120.0, backoff_max_s=120.0,
            poll_interval_s=0.01))
        harness = Harness(fleet)
        try:
            for idx in (0, 1):
                replica = fleet.replicas[idx]

                def boom():
                    raise RuntimeError(f"induced crash on replica {idx}")

                replica.engine.step_ahead = boom
            # feed each replica work so both engines die
            for i, p in enumerate(prompts):
                try:
                    fleet.submit_request(
                        p, SamplingParams(max_new_tokens=4),
                        request_id=f"kill{i}")
                except Exception:
                    break  # swallow-ok: later submits may race the deaths; the point is both replicas got work
            _wait(lambda: not any(r.alive for r in fleet.replicas),
                  msg="both replicas dead")
            assert fleet.restarting_count == 2
            status, _, data = _request(harness.port, "GET", "/readyz")
            assert status == 503
            assert data == b"restarting=2\n", data
            status, headers, data = _request(
                harness.port, "POST", "/v1/completions",
                {"prompt": [1, 2, 3, 4, 5], "max_tokens": 2})
            assert status == 503
            assert "Retry-After" in headers, headers
            assert b"restarting" in data, data
        finally:
            harness.close()

    def test_debug_audit_returns_ok_after_quarantine(self):
        prompts = _prompts(6)
        target = _affinity_target(prompts[0])
        plan = FaultPlan(faults=(
            FaultSpec(point="kernel_corrupt", step=5,
                      replica=str(target)),))
        fleet, sup = _build(
            plan=plan, audit=AuditConfig(enabled=True, sample_every=1),
            sup_cfg=SupervisorConfig(quarantine_drain_s=10.0,
                                     **_FAST_SUP))
        harness = Harness(fleet)
        try:
            status, _, data = _request(
                harness.port, "POST", "/v1/completions",
                {"prompt": prompts[0], "max_tokens": 8})
            assert status == 200
            _wait(lambda: (int(sup._quar_c.value) == 1
                           and fleet.replicas[target].healthy),
                  msg="quarantine over HTTP fleet")
            status, _, data = _request(harness.port, "GET",
                                       "/v1/debug/audit")
            assert status == 200
            audit = json.loads(data)
            assert audit["status"] == "ok", audit
            # /readyz clean again (no audit=degraded annotation)
            status, _, data = _request(harness.port, "GET", "/readyz")
            assert status == 200
            assert b"degraded" not in data
        finally:
            harness.close()


# --------------------------------------------------------------------------
# lint: exception hygiene + coverage of the new modules
# --------------------------------------------------------------------------
class TestExceptionHygieneLint:
    def test_repo_scans_clean(self):
        assert hygiene_lint.scan() == []

    def test_silent_swallow_flagged(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent("""\
            def f(q):
                try:
                    q.get_nowait()
                except Exception:
                    pass
        """))
        out = hygiene_lint.scan(dirs=(str(tmp_path),))
        assert len(out) == 1
        assert "silent swallow" in out[0][2]

    def test_waiver_and_observable_action_pass(self, tmp_path):
        ok = tmp_path / "ok.py"
        ok.write_text(textwrap.dedent("""\
            def f(q, counter, log):
                try:
                    q.get_nowait()
                except Exception:
                    pass  # swallow-ok: structurally impossible here
                try:
                    q.get_nowait()
                except Exception:
                    counter.inc()
                try:
                    q.get_nowait()
                except Exception:
                    raise RuntimeError("observable")
        """))
        assert hygiene_lint.scan(dirs=(str(tmp_path),)) == []

    def test_waiver_on_body_line(self, tmp_path):
        ok = tmp_path / "body.py"
        ok.write_text(textwrap.dedent("""\
            def f(q):
                try:
                    q.get_nowait()
                except Exception:
                    # swallow-ok: Empty is the loop exit condition
                    return None
        """))
        assert hygiene_lint.scan(dirs=(str(tmp_path),)) == []

    def test_scan_dirs_cover_serving_and_observability(self):
        dirs = {os.path.relpath(d, _REPO) for d in hygiene_lint.SCAN_DIRS}
        assert "paddle_tpu/serving" in dirs
        assert "paddle_tpu/observability" in dirs


class TestLintCoverage:
    def test_new_modules_in_bounded_metrics_scan(self):
        covered = {os.path.relpath(p, _REPO)
                   for p in bounded_lint.SCAN_FILES}
        assert "paddle_tpu/serving/resilience.py" in covered
        assert "paddle_tpu/serving/faultinject.py" in covered
        assert bounded_lint.scan(dirs=(), files=bounded_lint.SCAN_FILES) \
            == []

    def test_new_modules_in_metrics_docs_scan(self):
        covered = {os.path.relpath(p, _REPO)
                   for p in docs_lint.DECLARING_MODULES}
        assert "paddle_tpu/serving/resilience.py" in covered
        assert "paddle_tpu/serving/faultinject.py" in covered
        assert docs_lint.scan() == []
