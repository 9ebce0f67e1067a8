"""AOT serving artifacts (ISSUE 15).

The contract: an engine booted from a saved artifact
(``EngineConfig.aot``/``aot_path``) serves the preempting shared-prefix
stream **token-identical** to the traced engine with every in-trace
retrace counter pinned at **zero** — across preemption-with-recompute,
warm prefix-cache forks and chunked prefill, at mp=1 and mp=2 — and any
manifest mismatch (mp degree, bucket set, model hash, pool geometry,
stale jax version, ...) fails LOUDLY at load/boot instead of silently
retracing.  A dp=2 supervised chaos rerun proves the robustness payoff:
the rebuilt replica reuses the fleet's ONE loaded artifact with zero
post-restart traces.

(Named ``zzzzz`` to sort after ``test_zzzz_history_alerts.py`` — the
tier-1 suite overruns its timeout, so new dots must only append.)
"""

import asyncio
import json
import os
import shutil
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import topology
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    AotArtifact,
    AotBucketMissing,
    AotError,
    AotManifestMismatch,
    EngineConfig,
    EngineCore,
    FaultPlan,
    FaultSpec,
    FleetConfig,
    FleetRouter,
    FleetSupervisor,
    SamplingParams,
    SchedulerConfig,
    SupervisorConfig,
)
from paddle_tpu.serving.aot import enumerate_buckets, model_config_hash

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RNG = np.random.default_rng(0)
PREFIX = _RNG.integers(0, 256, 8).tolist()   # 2 full blocks shared
PROMPTS = [PREFIX + _RNG.integers(0, 256, 8).tolist() for _ in range(6)]

# 14 usable blocks of 4 cannot hold 4 concurrent 16+10-token sequences:
# the stream preempts + recomputes, shares warm prefix forks, and the
# 8-token budget chunks every prefill — the full serving surface
POOL = dict(num_blocks=15, block_size=4)
SCHED = dict(max_num_seqs=4, max_prefill_tokens_per_step=8)


def _engine(aot=None, mp=0, registry=None, labels=None, aot_path=None,
            layers=2, **pool_over):
    """Fresh deterministic engine (same seed → identical weights).
    ``mp``: 0 = leave the global mesh alone (fleet factories), 1 =
    force no mesh, 2 = init an mp=2 mesh."""
    if mp == 1:
        topology.set_mesh(None)
    elif mp > 1:
        topology.init_mesh(mp=mp)
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))
    pool = dict(POOL, **pool_over)
    return EngineCore(model, config=EngineConfig(
        **pool, scheduler=SchedulerConfig(**SCHED),
        aot=aot, aot_path=aot_path),
        registry=registry, metrics_labels=labels)


def _serve(eng, max_new=10):
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=max_new))
            for p in PROMPTS]
    eng.run(max_steps=4000)
    assert all(r.finished for r in reqs)
    return [list(r.output_tokens) for r in reqs]


def _traces(eng) -> int:
    return (eng.prefill_trace_count + eng.decode_trace_count
            + eng.ragged_trace_count)


@pytest.fixture(scope="module")
def artifact_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("aot_artifact"))
    topology.set_mesh(None)
    AotArtifact.save(_engine(), d)
    return d


@pytest.fixture(scope="module")
def artifact(artifact_dir):
    return AotArtifact.load(artifact_dir)


@pytest.fixture(scope="module")
def traced_ref():
    """Fault-free traced reference outputs (built BEFORE any supervised
    fleet — concurrent model builds interleave the global RNG)."""
    topology.set_mesh(None)
    eng = _engine()
    outs = _serve(eng)
    assert _traces(eng) > 0
    assert eng.metrics.counters["preemptions"] > 0
    assert eng.metrics.counters["prefix_cache_hit_tokens"] > 0
    assert eng.metrics.counters["chunked_prefill_steps"] > 0
    return outs


class TestArtifact:
    def test_manifest_fields(self, artifact):
        m = artifact.manifest
        for key in ("artifact_version", "framework_version", "jax_version",
                    "platform", "model_hash", "mp", "dtype", "num_blocks",
                    "block_size", "num_layers", "max_seq_len", "scheduler",
                    "autotune", "programs", "save_seconds"):
            assert key in m, key
        assert m["mp"] == 1 and m["block_size"] == 4
        assert m["autotune"]["unified_step"] is False
        # every enumerated bucket was saved and is loadable
        assert artifact.program_count == len(m["programs"])
        fams = artifact.bucket_sets
        assert set(fams) == {"prefill", "chunk", "decode"}

    def test_enumeration_is_the_closed_universe(self, artifact):
        # the engine's own bucket lattice within the manifest's
        # max_seq_len is exactly what was saved
        eng = _engine(mp=1)
        required = {(p,) + tuple(b) for p, b in enumerate_buckets(
            eng, max_seq_len=artifact.manifest["max_seq_len"])}
        assert required == set(artifact._programs)

    def test_torn_save_refuses_to_load(self, artifact_dir, tmp_path):
        torn = str(tmp_path / "torn")
        shutil.copytree(artifact_dir, torn)
        os.remove(os.path.join(torn, "manifest.json"))
        with pytest.raises(AotError, match="manifest.json missing"):
            AotArtifact.load(torn)

    def test_failed_resave_preserves_old_artifact(self, artifact_dir,
                                                  tmp_path, monkeypatch):
        """A RE-save stages next to the destination and swaps only
        after the manifest commit: a save that dies midway leaves the
        previous good artifact untouched and loadable (and no staging
        garbage behind)."""
        d = str(tmp_path / "resave")
        shutil.copytree(artifact_dir, d)
        before = AotArtifact.load(d).program_count
        from paddle_tpu.serving import aot as aot_mod

        monkeypatch.setattr(
            aot_mod, "_jit_for",
            lambda *a: (_ for _ in ()).throw(RuntimeError("boom")))
        with pytest.raises(RuntimeError, match="boom"):
            AotArtifact.save(_engine(mp=1), d)
        assert AotArtifact.load(d).program_count == before
        assert not os.path.exists(d + ".staging")


class TestZeroTraceServing:
    def test_token_identity_and_zero_traces(self, artifact, traced_ref):
        """The headline: preemption + warm prefix forks + chunked
        prefill, token-identical, retrace counters == 0."""
        eng = _engine(aot=artifact, mp=1)
        outs = _serve(eng)
        assert outs == traced_ref
        assert _traces(eng) == 0
        # the stream exercised the full serving surface under AOT too
        assert eng.metrics.counters["preemptions"] > 0
        assert eng.metrics.counters["prefix_cache_hit_tokens"] > 0
        assert eng.metrics.counters["chunked_prefill_steps"] > 0
        # attribution: hits counted per program, compile table EMPTY
        snap = eng.stepprof.aot_snapshot()
        assert snap["loaded"] and sum(snap["hits"].values()) > 0
        assert eng.stepprof.compile_table() == []

    def test_aot_path_config_form(self, artifact_dir, traced_ref):
        eng = _engine(aot_path=artifact_dir, mp=1)
        assert eng.aot_artifact is not None
        outs = _serve(eng)
        assert outs == traced_ref and _traces(eng) == 0

    def test_aot_metrics_on_registry(self, artifact):
        eng = _engine(aot=artifact, mp=1)
        _serve(eng)
        page = eng.metrics.registry.prometheus_text()
        assert "serving_aot_load_seconds" in page
        assert "serving_aot_hits_total" in page
        hits = eng.stepprof.aot_snapshot()["hits"]
        assert sum(hits.values()) > 0
        # one more engine on the artifact the tests above already ran
        # (the replica-restart shape): nothing left to trace or compile
        assert _traces(eng) == 0
        assert eng.stepprof.compile_table() == []

    def test_mp2_mesh_spanning_round_trip(self, tmp_path):
        """Save under an mp=2 mesh, serve mesh-spanning from the
        artifact: token-identical to the traced mp=2 engine, zero
        traces — jax.export round-trips the GSPMD programs on the
        forced-host-device CPU mesh."""
        try:
            ref_eng = _engine(mp=2)
            ref = _serve(ref_eng)
            assert _traces(ref_eng) > 0
            d = str(tmp_path / "mp2")
            AotArtifact.save(_engine(mp=2), d)
            art = AotArtifact.load(d)
            assert art.manifest["mp"] == 2
            eng = _engine(aot=art, mp=2)
            outs = _serve(eng)
            assert outs == ref
            assert _traces(eng) == 0
            # and the mp=1 engine refuses the mp=2 artifact loudly
            with pytest.raises(AotManifestMismatch, match="mp degree"):
                _engine(aot=art, mp=1)
        finally:
            topology.set_mesh(None)


class TestMismatchMatrix:
    """Every way a stale/foreign artifact must fail loudly at boot."""

    def _tampered(self, artifact_dir, **edits):
        art = AotArtifact.load(artifact_dir)
        for dotted, val in edits.items():
            obj = art.manifest
            *path, leaf = dotted.split(".")
            for p in path:
                obj = obj[p]
            obj[leaf] = val
        return art

    @pytest.mark.parametrize("edits,match", [
        ({"mp": 7}, "mp degree"),
        ({"model_hash": "0" * 64}, "model-config hash"),
        ({"num_blocks": 99}, "pool geometry"),
        ({"block_size": 8}, "pool geometry"),
        ({"num_layers": 5}, "layer count"),
        ({"dtype": "bfloat16"}, "pool dtype"),
        ({"autotune.unified_step": True}, "program family"),
        ({"autotune.use_pallas_paged": True}, "kernel routing"),
    ])
    def test_validate_mismatches(self, artifact_dir, edits, match):
        art = self._tampered(artifact_dir, **edits)
        eng = _engine(mp=1)
        with pytest.raises(AotManifestMismatch, match=match):
            art.validate(eng)
        with pytest.raises(AotManifestMismatch):
            eng.bind_aot(art)
        assert eng.aot_artifact is None  # refused, not half-bound

    def test_bucket_set_mismatch_scheduler_drift(self, artifact_dir):
        # an engine whose caps outgrew the saved universe (max_num_seqs
        # 4 -> 8 needs an 8-row decode bucket that was never saved)
        art = AotArtifact.load(artifact_dir)
        topology.set_mesh(None)
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
        eng = EngineCore(model, config=EngineConfig(
            **POOL, scheduler=SchedulerConfig(
                max_num_seqs=8, max_prefill_tokens_per_step=8)))
        with pytest.raises(AotManifestMismatch, match="bucket set"):
            art.validate(eng)

    @pytest.mark.parametrize("key,val,match", [
        ("jax_version", "0.0.1", "stale artifact"),
        ("artifact_version", 999, "artifact_version"),
        ("platform", "tpu", "platform"),
    ])
    def test_load_time_mismatches(self, artifact_dir, tmp_path, key, val,
                                  match):
        copy = str(tmp_path / "copy")
        shutil.copytree(artifact_dir, copy)
        mpath = os.path.join(copy, "manifest.json")
        with open(mpath) as f:
            m = json.load(f)
        m[key] = val
        with open(mpath, "w") as f:
            json.dump(m, f)
        with pytest.raises(AotManifestMismatch, match=match):
            AotArtifact.load(copy)

    def test_model_hash_ignores_weights_not_architecture(self):
        # same architecture, different weights -> same hash (an
        # artifact serves any checkpoint); different layer count ->
        # different hash
        topology.set_mesh(None)
        a = _engine(mp=1)
        paddle.seed(123)  # different weights
        model_b = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
        b = EngineCore(model_b, config=EngineConfig(
            **POOL, scheduler=SchedulerConfig(**SCHED)))
        c = _engine(mp=1, layers=3)
        assert model_config_hash(a) == model_config_hash(b)
        assert model_config_hash(a) != model_config_hash(c)


class TestBucketMissing:
    def test_oversize_request_rejected_at_admission(self, tmp_path):
        """A request whose target length outgrows the saved max_seq_len
        finishes honestly at admission (finish_reason=abort + error
        naming the artifact bound) — the engine thread survives, a
        within-bound request still serves, and nothing retraced."""
        topology.set_mesh(None)
        d = str(tmp_path / "small")
        AotArtifact.save(_engine(), d, max_seq_len=16)
        art = AotArtifact.load(d)
        eng = _engine(aot=art, mp=1)
        assert eng.scheduler.seq_len_cap == 16
        # 16-token prompt + 10 new tokens = 26 > 16: outside the lattice
        big = eng.add_request(PROMPTS[0],
                              SamplingParams(max_new_tokens=10))
        ok = eng.add_request(PROMPTS[0][:8],
                             SamplingParams(max_new_tokens=4))
        eng.run(max_steps=4000)
        assert big.finished and big.finish_reason.value == "abort"
        assert "max_seq_len=16" in big.error
        assert ok.finished and len(ok.output_tokens) == 4
        assert _traces(eng) == 0  # it REFUSED, it did not retrace

    def test_bucket_outside_universe_backstop(self, artifact):
        """The dispatch-level backstop behind the admission guard: a
        bucket the artifact never saved raises AotBucketMissing naming
        the shape — never a silent retrace."""
        with pytest.raises(AotBucketMissing, match="saved universe"):
            artifact.call("decode", (64, 64))


class TestStepprofAttribution:
    def test_compile_rows_flag_aot(self):
        from paddle_tpu.observability.metrics import MetricsRegistry
        from paddle_tpu.observability.stepprof import StepProfiler

        sp = StepProfiler(registry=MetricsRegistry())
        sp.record_compile("decode", (2, 4), 0.5)
        assert sp.compile_table()[0]["aot"] is False
        assert sp.aot_snapshot() == {"loaded": False}
        sp.record_aot_load(0.123, 39)
        sp.record_aot_hit("decode")
        sp.record_aot_hit("decode")
        sp.record_aot_hit("chunk")
        snap = sp.aot_snapshot()
        assert snap["loaded"] and snap["programs"] == 39
        assert snap["hits"] == {"decode": 2, "chunk": 1}
        # a trace AFTER the load is visibly a bug: the row says so
        sp.record_compile("decode", (4, 4), 0.4)
        assert sp.compile_table()[-1]["aot"] is True

    def test_one_load_sample_per_registry(self, artifact_dir):
        """dp replicas and rebuild factories bind the SAME loaded
        artifact into one shared registry: serving_aot_load_seconds
        must gain exactly one sample — one disk load happened."""
        from paddle_tpu.observability.metrics import MetricsRegistry

        def load_samples(reg):
            return sum(v["count"] for k, v in reg.snapshot().items()
                       if k.startswith("serving_aot_load_seconds"))

        art = AotArtifact.load(artifact_dir)
        reg = MetricsRegistry()
        topology.set_mesh(None)
        for i in range(2):
            _engine(aot=art, registry=reg, labels={"replica": str(i)})
        assert load_samples(reg) == 1
        # a separate registry (a different deployment) observes its own
        reg2 = MetricsRegistry()
        _engine(aot=art, registry=reg2)
        assert load_samples(reg2) == 1

    def test_rebind_skips_load_histogram_sample(self):
        """A supervisor rebind (record_load=False) registers the hit
        counters and flips the snapshot but must not observe a disk
        load that never happened."""
        from paddle_tpu.observability.metrics import MetricsRegistry
        from paddle_tpu.observability.stepprof import StepProfiler

        reg = MetricsRegistry()
        sp = StepProfiler(registry=reg)
        sp.record_aot_load(0.1, 5, observe=False)
        assert sp.aot_snapshot()["loaded"]
        sp.record_aot_hit("decode")
        page = reg.prometheus_text()
        assert "serving_aot_hits_total" in page
        assert "serving_aot_load_seconds" not in page

    def test_disabled_profiler_keeps_registry_clean(self, artifact):
        from paddle_tpu.observability.metrics import MetricsRegistry
        from paddle_tpu.observability.stepprof import StepProfiler

        reg = MetricsRegistry()
        sp = StepProfiler(registry=reg, enabled=False)
        sp.record_aot_load(0.1, 5)
        sp.record_aot_hit("decode")
        assert "serving_aot" not in reg.prometheus_text()
        # the snapshot still reports state for the debug endpoint
        assert sp.aot_snapshot()["loaded"] is True


class TestUnifiedFamily:
    def test_unified_round_trip_zero_traces(self, tmp_path):
        """The ONE packed ragged program family (PR 10) AOTs too: save
        under unified_step=True → the artifact holds only ``ragged``
        buckets, serves token-identical with zero traces."""
        topology.set_mesh(None)

        def mk(aot=None):
            paddle.seed(0)
            model = LlamaForCausalLM(
                LlamaConfig.tiny(num_hidden_layers=2))
            return EngineCore(model, config=EngineConfig(
                **POOL, scheduler=SchedulerConfig(**SCHED),
                unified_step=True, aot=aot))

        ref_eng = mk()
        ref = _serve(ref_eng)
        assert ref_eng.ragged_trace_count > 0
        d = str(tmp_path / "unified")
        AotArtifact.save(mk(), d)
        art = AotArtifact.load(d)
        assert set(art.bucket_sets) == {"ragged"}
        assert art.manifest["autotune"]["unified_step"] is True
        eng = mk(aot=art)
        outs = _serve(eng)
        assert outs == ref
        assert _traces(eng) == 0
        # and a legacy-family engine refuses the ragged artifact loudly
        with pytest.raises(AotManifestMismatch, match="program family"):
            _engine(aot=art, mp=1)


class TestFleetAndRestart:
    def test_fleet_refuses_per_replica_loads(self, artifact_dir):
        topology.set_mesh(None)
        with pytest.raises(ValueError, match="ONE loaded AotArtifact"):
            FleetRouter.build(
                lambda i, registry: _engine(
                    aot=AotArtifact.load(artifact_dir),
                    registry=registry, labels={"replica": str(i)}),
                dp=2)

    def test_chaos_rerun_rebuilt_replica_reuses_artifact(
            self, artifact, traced_ref):
        """The robustness payoff: injected engine death at dp=2 → the
        supervisor rebuilds the replica onto the fleet's ONE artifact
        (even though the rebuild factory 'forgets' it) — zero
        post-restart traces, zero traces anywhere, token identity."""
        from paddle_tpu.serving.fleet import affinity_replica_index

        target = affinity_replica_index(PROMPTS[0], dp=2, block_size=4)
        assert target is not None
        builds = []

        def factory(i, registry):
            # initial dp=2 build shares the artifact; REBUILDS omit it
            # deliberately — the supervisor must rebind the router's
            builds.append(i)
            return _engine(aot=artifact if len(builds) <= 2 else None,
                           registry=registry, labels={"replica": str(i)})

        topology.set_mesh(None)
        plan = FaultPlan(faults=(
            FaultSpec(point="engine_step_raise", step=6,
                      replica=str(target)),))
        fleet = FleetRouter.build(factory, dp=2,
                                  config=FleetConfig(fault_plan=plan))
        assert fleet.aot_artifact is artifact
        sup = FleetSupervisor(fleet, config=SupervisorConfig(
            poll_interval_s=0.01, backoff_initial_s=0.02,
            backoff_max_s=0.5)).start()
        fleet.start()
        try:
            hs = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=10),
                request_id=f"aot-{i}", retryable=True)
                for i, p in enumerate(PROMPTS)]
            fleet.wait(hs, timeout=300)
            lost = [h.rid for h in hs if h.finish_reason != "length"]
            assert not lost, f"requests lost under chaos: {lost}"
            assert [list(h.output_tokens) for h in hs] == traced_ref
            # wait for the restart to complete
            deadline = 300
            import time as _t
            t0 = _t.monotonic()
            while _t.monotonic() - t0 < deadline:
                if all(r.healthy for r in fleet.replicas) \
                        and len(builds) >= 3:
                    break
                _t.sleep(0.02)
            assert len(builds) >= 3, "replica was never rebuilt"
            rebuilt = fleet.replicas[target].engine
            # the supervisor rebound the fleet's artifact onto the
            # replacement engine the factory built WITHOUT one
            assert rebuilt.aot_artifact is artifact
            assert rebuilt.stepprof.aot_snapshot()["loaded"]
            # zero traces fleet-wide, including post-restart
            for eng in fleet.engines:
                assert _traces(eng) == 0
                assert eng.stepprof.compile_table() == []
            assert int(sup._restarts["engine_death"].value) == 1
            # a post-restart wave: affinity routes the shared-prefix
            # family BACK onto the rebuilt replica, which serves it from
            # the artifact's executables — still not one trace
            hs2 = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=10),
                request_id=f"aot2-{i}", retryable=True)
                for i, p in enumerate(PROMPTS)]
            fleet.wait(hs2, timeout=300)
            assert [h.finish_reason for h in hs2] == ["length"] * len(hs2)
            assert [list(h.output_tokens) for h in hs2] == traced_ref
            assert {h.replica.index for h in hs2} == {target}
            assert sum(rebuilt.stepprof.aot_snapshot()["hits"]
                       .values()) > 0
            assert _traces(rebuilt) == 0
            assert rebuilt.stepprof.compile_table() == []
        finally:
            fleet.shutdown(drain_timeout=5.0)


class TestHttpSurface:
    def test_debug_compiles_aot_block(self, artifact):
        from paddle_tpu.serving.server import (
            CompletionServer,
            ServerConfig,
            _http,
        )

        topology.set_mesh(None)
        eng = _engine(aot=artifact, mp=1)

        async def main():
            loop = asyncio.get_running_loop()
            server = CompletionServer(eng, ServerConfig(port=0))
            await server.start()
            try:
                status, data = await loop.run_in_executor(
                    None, _http, server.port, "POST", "/v1/completions",
                    {"prompt": PROMPTS[0], "max_tokens": 4})
                assert status == 200, data
                status, data = await loop.run_in_executor(
                    None, _http, server.port, "GET",
                    "/v1/debug/compiles", None)
                assert status == 200
                obj = json.loads(data)
                # zero compile rows, loaded artifact visible per replica
                assert obj["data"] == []
                assert obj["totals"] == {}
                aot = obj["aot"]["0"]
                assert aot["loaded"] and sum(aot["hits"].values()) > 0
                assert aot["programs"] == artifact.program_count
            finally:
                await server.shutdown(drain_timeout=2.0)

        asyncio.run(main())
        assert _traces(eng) == 0


class TestLintWiring:
    def test_aot_in_lint_scan_lists(self):
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        try:
            import check_bounded_metrics as bounded_lint
            import check_metrics_docs as docs_lint
        finally:
            sys.path.pop(0)
        assert os.path.join(_REPO, "paddle_tpu", "serving", "aot.py") \
            in bounded_lint.SCAN_FILES
        assert os.path.join(_REPO, "paddle_tpu", "serving", "aot.py") \
            in docs_lint.DECLARING_MODULES
        assert docs_lint.scan() == []
