"""Tensor-parallel multi-chip serving (ISSUE 5).

The engine runs its bucketed jitted prefill/decode programs mesh-spanning
over the ``mp`` axis (KV pools head-sharded, routing arrays replicated)
while every scheduler/pool decision stays host-side — so mp=2 must be
**token-identical** to mp=1 under greedy decoding across every serving
behaviour: plain streams, preemption-with-recompute, warm prefix-cache
forks.  Tier-1-safe: the conftest forces 8 virtual CPU devices, so the
mp=2 mesh is real multi-device SPMD without hardware.
"""

import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import topology
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    SamplingParams,
    SchedulerConfig,
)

_RNG = np.random.default_rng(7)
PREFIX = _RNG.integers(0, 256, 8).tolist()
PROMPTS = [PREFIX + _RNG.integers(0, 256, 8).tolist() for _ in range(5)]


@pytest.fixture
def mp2_mesh():
    m = topology.init_mesh(mp=2)
    yield m
    topology.set_mesh(None)


def _engine(mp, num_blocks=64, block_size=4, max_num_seqs=4,
            prefill_budget=None, **engine_kw):
    """Fresh tiny model + engine; ``mp`` controls the global mesh (the
    same seed at both degrees → identical weights)."""
    paddle.seed(0)
    if mp > 1:
        topology.init_mesh(mp=mp)
    else:
        topology.set_mesh(None)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    return EngineCore(
        model, num_blocks=num_blocks, block_size=block_size,
        scheduler_config=SchedulerConfig(
            max_num_seqs=max_num_seqs,
            max_prefill_tokens_per_step=prefill_budget),
        **engine_kw)


def _run(eng, prompts, max_new):
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=max_new))
            for p in prompts]
    eng.run(max_steps=4000)
    assert all(r.finished for r in reqs)
    return [list(r.output_tokens) for r in reqs]


def _both_degrees(scenario):
    """Run ``scenario(mp)`` at mp=1 and mp=2 (mesh cleaned up after)."""
    try:
        r1 = scenario(1)
        r2 = scenario(2)
    finally:
        topology.set_mesh(None)
    return r1, r2


class TestTokenIdentity:
    def test_plain_stream_identical(self):
        def scenario(mp):
            eng = _engine(mp)
            outs = _run(eng, PROMPTS, max_new=6)
            assert eng.mp == mp
            assert eng.kv.occupancy() == 0.0   # pool drained
            return outs

        o1, o2 = _both_degrees(scenario)
        assert o1 == o2

    def test_preemption_recompute_identical(self):
        """Pool pressure preempts + recomputes at both degrees; greedy
        output must not notice."""
        def scenario(mp):
            eng = _engine(mp, num_blocks=12)
            outs = _run(eng, PROMPTS, max_new=8)
            assert eng.metrics.counters["preemptions"] > 0
            assert eng.kv.occupancy() == 0.0
            return outs

        o1, o2 = _both_degrees(scenario)
        assert o1 == o2

    def test_warm_prefix_cache_identical(self):
        """A second wave over a cached prefix forks blocks instead of
        recomputing — the fork must be shard-consistent (same block
        indices route every shard's pool)."""
        def scenario(mp):
            eng = _engine(mp)
            first = _run(eng, [PREFIX + [3, 1, 4, 1]], max_new=4)
            wave = [PREFIX + t for t in ([9, 2, 6], [5, 3, 5], [8, 9, 7])]
            second = _run(eng, wave, max_new=6)
            assert eng.metrics.counters["prefix_cache_hit_tokens"] > 0
            assert eng.kv.occupancy() == 0.0
            return first + second

        o1, o2 = _both_degrees(scenario)
        assert o1 == o2

    def test_chunked_prefill_identical(self):
        """Chunked prefill (token-budgeted) stays identical mesh-spanning
        — the [B, S] slot-routed chunk program is mp-sharded too."""
        def scenario(mp):
            eng = _engine(mp, prefill_budget=8)
            outs = _run(eng, PROMPTS, max_new=6)
            assert eng.metrics.counters["chunked_prefill_steps"] > 0
            return outs

        o1, o2 = _both_degrees(scenario)
        assert o1 == o2


class TestTraceBounds:
    """Six prompts sharing two full blocks through a pool too small for
    four of them (14 usable blocks, 16 + 10 tokens each), chunk budget
    8, prefix cache on: what each degree compiles and preempts."""

    @pytest.fixture(scope="class")
    def runs(self):
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, 256, 8).tolist()
        prompts = [prefix + rng.integers(0, 256, 8).tolist()
                   for _ in range(6)]

        def scenario(mp):
            eng = _engine(mp, num_blocks=15, prefill_budget=8)
            return {"outputs": _run(eng, prompts, max_new=10),
                    "prefill_traces": eng.prefill_trace_count,
                    "decode_traces": eng.decode_trace_count,
                    "prefill_buckets": eng.prefill_buckets,
                    "decode_buckets": eng.decode_buckets,
                    "preemptions": eng.metrics.counters["preemptions"]}

        return dict(zip((1, 2), _both_degrees(scenario)))

    def test_trace_count_bounded_and_mp_invariant(self, runs):
        """jit trace counts stay bounded by the bucket sets at mp=2 and
        equal the mp=1 counts — sharding must not add retraces."""
        for r in runs.values():
            assert r["prefill_traces"] <= len(r["prefill_buckets"])
            assert r["decode_traces"] <= len(r["decode_buckets"])
        assert ({k: v for k, v in runs[1].items() if k != "outputs"}
                == {k: v for k, v in runs[2].items() if k != "outputs"})

    @pytest.mark.parametrize("mp", [1, 2])
    @pytest.mark.parametrize("name,want", [
        ("prefill_traces", 5), ("decode_traces", 3), ("preemptions", 3)])
    def test_count(self, runs, mp, name, want):
        # exact on this fixed stream: one more trace IS the regression
        assert runs[mp][name] == want

    def test_outputs_identical(self, runs):
        assert runs[1]["outputs"] == runs[2]["outputs"]


class TestConfig:
    def test_engine_config_object_form(self, mp2_mesh):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
        eng = EngineCore(model, config=EngineConfig(
            num_blocks=32, block_size=4, mp=2,
            scheduler=SchedulerConfig(max_num_seqs=2)))
        assert eng.mp == 2
        assert eng.num_blocks == 32
        assert eng.scheduler.config.max_num_seqs == 2

    def test_mp_mismatch_raises(self, mp2_mesh):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
        with pytest.raises(ValueError, match="mp=4"):
            EngineCore(model, config=EngineConfig(mp=4))

    def test_mp_without_mesh_raises(self):
        topology.set_mesh(None)
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
        with pytest.raises(ValueError, match="init_mesh"):
            EngineCore(model, config=EngineConfig(mp=2))

    def test_indivisible_heads_raise(self):
        topology.init_mesh(mp=4)
        try:
            paddle.seed(0)
            # tiny() has 2 KV heads: mp=4 cannot shard the KV pools evenly
            model = LlamaForCausalLM(LlamaConfig.tiny(
                num_hidden_layers=1, num_attention_heads=4,
                num_key_value_heads=2))
            with pytest.raises(ValueError, match="num_key_value_heads"):
                EngineCore(model)
        finally:
            topology.set_mesh(None)

    def test_indivisible_mlp_width_replicates_gracefully(self):
        """Heads divide mp but the MLP width doesn't (model built before
        any mesh, so the mp-layer constructor checks ran at degree 1):
        param placement must fit the spec — replicate that weight — not
        crash in device_put, and stay token-identical to mp=1."""
        def scenario(mp):
            paddle.seed(0)
            topology.set_mesh(None)
            model = LlamaForCausalLM(LlamaConfig.tiny(
                num_hidden_layers=1, intermediate_size=127))
            if mp > 1:
                topology.init_mesh(mp=mp)
            eng = EngineCore(model, num_blocks=32, block_size=4)
            assert eng.mp == mp
            return _run(eng, PROMPTS[:2], max_new=4)

        o1, o2 = _both_degrees(scenario)
        assert o1 == o2

    def test_use_pallas_with_mp_raises(self, mp2_mesh):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
        with pytest.raises(ValueError, match="use_pallas_paged"):
            EngineCore(model, use_pallas_paged=True)


class TestPallasConfigFlip:
    def test_forced_pallas_decode_matches_xla(self):
        """ROADMAP follow-up (b): ``use_pallas_paged=True`` routes decode
        through the Pallas kernel (interpret mode on CPU) and stays
        token-identical to the XLA gather path — the on-chip A/B is a
        config flip."""
        from paddle_tpu.ops import paged_attention as pa_mod

        topology.set_mesh(None)

        def run(up):
            eng = _engine(1, num_blocks=32, block_size=8,
                          use_pallas_paged=up)
            outs = _run(eng, PROMPTS[:3], max_new=5)
            return outs, pa_mod.last_path

        o_xla, path_xla = run(False)
        assert path_xla == "xla"
        o_pl, path_pl = run(True)
        assert path_pl == "pallas"
        assert o_xla == o_pl


class TestObservability:
    def test_mp_metrics_exposed(self, mp2_mesh):
        eng = _engine(2, num_blocks=32)
        # reuse the mesh the fixture made (``_engine`` re-inits the same
        # shape; harmless), run a short stream, inspect the registry
        _run(eng, PROMPTS[:2], max_new=3)
        text = eng.metrics.prometheus_text()
        assert "serving_mp_shards 2" in text
        for phase in ("prefill", "decode"):
            m = re.search(
                r'serving_collective_seconds_count\{phase="%s"\} (\d+)'
                % phase, text)
            assert m, f"missing collective histogram for {phase}"
            assert int(m.group(1)) > 0
        topology.set_mesh(None)

    def test_single_chip_collective_silent(self):
        topology.set_mesh(None)
        eng = _engine(1, num_blocks=32)
        _run(eng, PROMPTS[:2], max_new=3)
        text = eng.metrics.prometheus_text()
        assert "serving_mp_shards 1" in text
        # series present (pre-registered) but never observed off-mesh
        m = re.search(
            r'serving_collective_seconds_count\{phase="decode"\} (\d+)',
            text)
        assert m and int(m.group(1)) == 0


class TestServerProbe:
    def test_readyz_reports_mp_degree(self, mp2_mesh, tmp_path):
        """/readyz carries the mesh shape, so a deployment that came up
        single-chip when the operator expected mp=2 is visible from the
        probe alone."""
        import asyncio
        import http.client
        import threading

        from paddle_tpu.serving.server import CompletionServer, ServerConfig

        eng = _engine(2, num_blocks=32)
        loop = asyncio.new_event_loop()
        thread = threading.Thread(target=loop.run_forever, daemon=True)
        thread.start()
        server = CompletionServer(eng, ServerConfig(port=0))
        asyncio.run_coroutine_threadsafe(server.start(), loop).result(60)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                              timeout=60)
            conn.request("GET", "/readyz")
            resp = conn.getresponse()
            body = resp.read()
            conn.close()
            assert resp.status == 200
            assert b"mp=2" in body, body
        finally:
            asyncio.run_coroutine_threadsafe(
                server.shutdown(drain_timeout=1.0), loop).result(60)
            loop.call_soon_threadsafe(loop.stop)
            thread.join(10)
            loop.close()
            topology.set_mesh(None)


class TestBoundedMetricsLint:
    def test_scan_covers_parallel_modules(self):
        """ISSUE 5 tooling: the lint's pinned file list includes the
        tensor-parallel plumbing the mp engine runs through, and those
        files scan clean."""
        import os
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(repo, "tools"))
        try:
            import check_bounded_metrics as lint
        finally:
            sys.path.pop(0)
        covered = {os.path.relpath(p, repo) for p in lint.SCAN_FILES}
        for need in ("paddle_tpu/parallel/mp_layers.py",
                     "paddle_tpu/parallel/utils.py",
                     "paddle_tpu/distributed/topology.py",
                     "paddle_tpu/ops/pallas_paged.py",
                     # ISSUE 11: the unified ragged kernel is hot-path
                     "paddle_tpu/ops/ragged_paged.py",
                     # ISSUE 6: the fleet's per-replica queues/maps are
                     # pinned even if the module leaves the serving dir
                     "paddle_tpu/serving/fleet.py"):
            assert need in covered, f"{need} missing from lint SCAN_FILES"
        assert lint.scan(dirs=(), files=lint.SCAN_FILES) == []
