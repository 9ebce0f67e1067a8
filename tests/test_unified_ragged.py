"""Unified ragged step program (ISSUE 11).

One packed ragged launch per engine step — mixed prefill chunks + decode
rows through ``ops/ragged_paged.py`` (XLA ``ragged_oracle`` ground truth
next to a Pallas kernel expressed through ``shard_map`` over ``mp``) —
must be **token-identical** to the legacy three-family dispatch under
greedy decoding across every serving behaviour (preemption-with-
recompute, warm prefix-cache forks, chunked prefill, mp=1 and mp=2),
with strictly fewer jit traces than the legacy bucket bound, audited
clean by a ``sample_every=1`` NumericsAuditor soak, and with the mp>1
``use_pallas_paged`` auto-pin lifted.  Tier-1-safe: the conftest forces
8 virtual CPU devices and the Pallas kernel runs in interpret mode.
"""

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


# --- kernel-level parity sweep (the PR 9 oracle discipline) -----------------

def _pools(rng, num_blocks=16, bs=4, hkv=2, d=8):
    import jax.numpy as jnp

    k = jnp.asarray(rng.normal(size=(num_blocks, bs, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(num_blocks, bs, hkv, d)), jnp.float32)
    return k, v


def _pack(rows, Tb, Rb, W, bs):
    """Build the packed metadata arrays from ``rows`` =
    [(pages, kv_len, q_positions)] — the same packing the engine does."""
    tables = np.zeros((Rb, W), np.int32)
    lens = np.ones((Rb,), np.int32)
    R = len(rows)
    seg = np.full((Tb,), min(R, Tb - 1), np.int32)
    pos = np.zeros((Tb,), np.int32)
    cursor = 0
    for i, (pages, kv_len, q_positions) in enumerate(rows):
        tables[i, :len(pages)] = pages
        lens[i] = kv_len
        n = len(q_positions)
        seg[cursor:cursor + n] = i
        pos[cursor:cursor + n] = q_positions
        cursor += n
    assert cursor <= Tb
    return tables, lens, seg, pos


@pytest.mark.parametrize("case", ["decode_only", "chunk_only", "mixed",
                                  "padded"])
@pytest.mark.parametrize("width", [2, 4])
def test_ragged_kernel_matches_oracle(case, width):
    """Interpret-mode parity sweep: the Pallas ragged kernel agrees with
    ``ragged_oracle`` over decode-only, chunk-only, mixed and padded
    packed shapes (padding rows hitting the null block) — the ragged
    analog of PR 9's decode bucket sweep, runnable with auditing off."""
    import jax.numpy as jnp

    from paddle_tpu.ops.ragged_paged import (
        ragged_oracle,
        ragged_paged_attention,
    )

    rng = np.random.default_rng(3)
    bs = 4
    kc, vc = _pools(rng, bs=bs)
    H, D = 4, 8
    if case == "decode_only":
        # four decode rows at staggered depths
        rows = [([1 + 2 * i, 2 + 2 * i][:max(1, -(-L // bs))], L,
                 [L - 1])
                for i, L in enumerate((3, 6, 8, 5))]
        Tb = 4
    elif case == "chunk_only":
        rows = [([3, 7], 7, [4, 5, 6]), ([5, 9], 5, [0, 1, 2, 3, 4])]
        Tb = 8
    elif case == "mixed":
        rows = [([3, 7], 6, [5]), ([5, 9], 5, [2, 3, 4]),
                ([2, 11], 8, [7])]
        Tb = 8
    else:  # padded: pad tokens AND pad rows route through the null page
        rows = [([3], 2, [1]), ([5, 9], 5, [3, 4])]
        Tb = 8
    Rb = Tb
    tables, lens, seg, pos = _pack(rows, Tb, Rb, width, bs)
    T_real = sum(len(r[2]) for r in rows)
    q = jnp.asarray(rng.normal(size=(Tb, H, D)), jnp.float32)
    args = (q, kc, vc, jnp.asarray(tables), jnp.asarray(lens),
            jnp.asarray(seg), jnp.asarray(pos))
    ref = np.asarray(ragged_oracle(*args))
    out = np.asarray(ragged_paged_attention(*args, use_pallas=True))
    from paddle_tpu.ops import ragged_paged as rp_mod
    assert rp_mod.last_path == "pallas"
    np.testing.assert_allclose(out[:T_real], ref[:T_real],
                               atol=1e-5, rtol=1e-5)
    # pad outputs are garbage-but-finite (null page attention)
    assert np.isfinite(out).all()


def test_ragged_decode_rows_match_decode_oracle():
    """A packed decode-only step reproduces the legacy per-sequence
    decode oracle exactly: the ragged program is a strict generalization
    of ``pallas_paged.decode_oracle``'s routing semantics."""
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import _xla_paged_attention
    from paddle_tpu.ops.ragged_paged import ragged_oracle

    rng = np.random.default_rng(5)
    bs = 4
    kc, vc = _pools(rng, bs=bs)
    lens_v = [6, 3, 8, 1]
    tables = np.zeros((4, 2), np.int32)
    tables[0, :2] = [3, 7]
    tables[1, :1] = [5]
    tables[2, :2] = [2, 11]
    tables[3, :1] = [9]
    q = jnp.asarray(rng.normal(size=(4, 4, 8)), jnp.float32)
    legacy = np.asarray(_xla_paged_attention(
        q, kc, vc, jnp.asarray(tables), jnp.asarray(lens_v, jnp.int32)))
    seg = np.arange(4, dtype=np.int32)
    pos = np.asarray([l - 1 for l in lens_v], np.int32)
    ragged = np.asarray(ragged_oracle(
        q, kc, vc, jnp.asarray(tables), jnp.asarray(lens_v, jnp.int32),
        jnp.asarray(seg), jnp.asarray(pos)))
    np.testing.assert_allclose(ragged, legacy, atol=1e-6, rtol=1e-6)


def test_ragged_kernel_shard_map_mp2():
    """The kernel dispatch spans a live mp=2 mesh through shard_map
    (heads/pools sharded per KV_POOL_SPEC, metadata replicated) and
    still agrees with the single-device oracle — interpret mode on the
    conftest's virtual CPU devices."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.ragged_paged import (
        ragged_oracle,
        ragged_paged_attention,
    )

    rng = np.random.default_rng(11)
    kc, vc = _pools(rng)
    tables, lens, seg, pos = _pack(
        [([3, 7], 6, [5]), ([5, 9], 5, [2, 3, 4])], 8, 8, 4, 4)
    q = jnp.asarray(rng.normal(size=(8, 4, 8)), jnp.float32)
    try:
        topology.init_mesh(mp=2)
        args = (q, kc, vc, jnp.asarray(tables), jnp.asarray(lens),
                jnp.asarray(seg), jnp.asarray(pos))
        ref = np.asarray(ragged_oracle(*args))
        out = np.asarray(jax.jit(
            lambda *a: ragged_paged_attention(*a, use_pallas=True))(*args))
    finally:
        topology.set_mesh(None)
    np.testing.assert_allclose(out[:4], ref[:4], atol=1e-5, rtol=1e-5)


# --- engine-level token identity --------------------------------------------

def _engine(mp=1, unified=False, num_blocks=64, block_size=4,
            max_num_seqs=4, prefill_budget=None, token_budget=None,
            **engine_kw):
    paddle.seed(0)
    if mp > 1:
        topology.init_mesh(mp=mp)
    else:
        topology.set_mesh(None)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    return EngineCore(model, config=EngineConfig(
        num_blocks=num_blocks, block_size=block_size,
        scheduler=SchedulerConfig(
            max_num_seqs=max_num_seqs,
            max_prefill_tokens_per_step=prefill_budget,
            max_tokens_per_step=token_budget),
        unified_step=unified, **engine_kw))


def _run(eng, prompts, max_new):
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=max_new))
            for p in prompts]
    eng.run(max_steps=4000)
    assert all(r.finished for r in reqs)
    return [list(r.output_tokens) for r in reqs]


def _legacy_vs_unified(scenario):
    """Run ``scenario(unified)`` both ways (mesh cleaned up after) and
    assert the unified engine never touched the legacy programs."""
    try:
        legacy, _ = scenario(False)
        uni, eng = scenario(True)
    finally:
        topology.set_mesh(None)
    assert eng.prefill_trace_count == 0 and eng.decode_trace_count == 0, \
        "unified mode must never dispatch a legacy program family"
    assert eng.ragged_trace_count <= len(eng.ragged_buckets), \
        "ragged program retraced beyond its bucket set"
    assert eng.metrics.counters["unified_steps"] > 0
    return legacy, uni, eng


class TestUnifiedTokenIdentity:
    @pytest.mark.parametrize("mp", [1, 2])
    def test_plain_stream_identical(self, mp):
        def scenario(unified):
            eng = _engine(mp=mp, unified=unified)
            outs = _run(eng, PROMPTS, max_new=6)
            assert eng.kv.occupancy() == 0.0
            return outs, eng

        legacy, uni, _ = _legacy_vs_unified(scenario)
        assert legacy == uni

    @pytest.mark.parametrize("mp", [1, 2])
    def test_preemption_recompute_identical(self, mp):
        """Pool pressure preempts + recomputes; the packed program's
        recompute chunks must replay token-identically."""
        def scenario(unified):
            eng = _engine(mp=mp, unified=unified, num_blocks=12)
            outs = _run(eng, PROMPTS, max_new=8)
            assert eng.metrics.counters["preemptions"] > 0
            assert eng.kv.occupancy() == 0.0
            return outs, eng

        legacy, uni, _ = _legacy_vs_unified(scenario)
        assert legacy == uni

    @pytest.mark.parametrize("mp", [1, 2])
    def test_warm_prefix_cache_identical(self, mp):
        """A second wave forks cached blocks — the packed chunk rows
        start mid-sequence at the fork point."""
        def scenario(unified):
            eng = _engine(mp=mp, unified=unified)
            first = _run(eng, [PREFIX + [3, 1, 4, 1]], max_new=4)
            wave = [PREFIX + t for t in ([9, 2, 6], [5, 3, 5], [8, 9, 7])]
            second = _run(eng, wave, max_new=6)
            assert eng.metrics.counters["prefix_cache_hit_tokens"] > 0
            return first + second, eng

        legacy, uni, _ = _legacy_vs_unified(scenario)
        assert legacy == uni

    @pytest.mark.parametrize("mp", [1, 2])
    def test_chunked_prefill_identical(self, mp):
        """Token-budgeted prefill: in unified mode the chunks pack into
        the same launch as the decode batch under ONE budget."""
        def scenario(unified):
            eng = _engine(mp=mp, unified=unified, prefill_budget=8,
                          token_budget=8 if unified else None)
            outs = _run(eng, PROMPTS, max_new=6)
            assert (eng.metrics.counters["chunked_prefill_steps"] > 0
                    or unified)
            return outs, eng

        legacy, uni, _ = _legacy_vs_unified(scenario)
        assert legacy == uni

    def test_shard_map_kernel_engine_identical(self):
        """mp=2 + use_pallas_paged=True + unified: the interpret-mode
        Pallas kernel runs mesh-spanning through shard_map inside the
        jitted step and greedy tokens match the mp=1 legacy engine."""
        def scenario(unified):
            eng = _engine(mp=2 if unified else 1, unified=unified,
                          use_pallas_paged=True if unified else None)
            return _run(eng, PROMPTS, max_new=6), eng

        legacy, uni, _ = _legacy_vs_unified(scenario)
        assert legacy == uni

    @pytest.fixture(scope="class")
    def collapse(self):
        """Legacy and unified engines after the same preempting,
        chunk-budgeted, prefix-cached stream (six prompts sharing two
        full blocks, 14 usable blocks, one 8-token budget)."""
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, 256, 8).tolist()
        prompts = [prefix + rng.integers(0, 256, 8).tolist()
                   for _ in range(6)]
        engines = {}

        def scenario(unified):
            eng = engines[unified] = _engine(
                unified=unified, num_blocks=15, prefill_budget=8,
                token_budget=8 if unified else None)
            return _run(eng, prompts, max_new=10), eng

        legacy, uni, _ = _legacy_vs_unified(scenario)
        return legacy, uni, engines[False], engines[True]

    def test_bucket_set_collapses(self, collapse):
        """The unified engine's one program family compiles strictly
        fewer shapes than the legacy three on the same stream — the
        compile-count half of the padding-waste claim."""
        legacy, uni, legacy_eng, eng = collapse
        assert legacy == uni
        legacy_buckets = (len(legacy_eng.prefill_buckets)
                          + len(legacy_eng.decode_buckets))
        assert len(eng.ragged_buckets) < legacy_buckets, (
            f"unified bucket set {sorted(eng.ragged_buckets)} is not "
            f"smaller than the legacy three-family set "
            f"({sorted(legacy_eng.prefill_buckets)} + "
            f"{sorted(legacy_eng.decode_buckets)})")

    @pytest.mark.parametrize("unified,name,want", [
        (True, "traces", 6), (False, "traces", 8),
        # padding ratio 14/142 unified against 17/146 legacy, as the
        # integer counts it is made of
        (True, "padding_tokens", 14), (True, "capacity_tokens", 142),
        (False, "padding_tokens", 17), (False, "capacity_tokens", 146),
        (True, "preempted", True), (False, "preempted", True)])
    def test_fixed_stream_count(self, collapse, unified, name, want):
        eng = collapse[3] if unified else collapse[2]
        rep = eng.stepprof.utilization_report()
        # the scheduled-token invariant that makes the padding numbers
        # trustworthy: the profiler's sum is the planner's ledger
        assert rep["scheduled_tokens"] == eng.scheduler.tokens_planned
        got = {"traces": (eng.prefill_trace_count + eng.decode_trace_count
                          + eng.ragged_trace_count),
               "preempted": eng.metrics.counters["preemptions"] > 0,
               **rep}[name]
        assert got == want


# --- audit soak --------------------------------------------------------------

class TestUnifiedAudit:
    def test_sample_every_1_soak_clean(self):
        """The PR 9 oracle harness over the unified path: every packed
        step shadow re-executed through the independently jitted XLA
        ragged reference — zero divergences, zero oracle failures, and
        the auditor actually audited ragged launches."""
        from paddle_tpu.observability.audit import AuditConfig

        eng = _engine(unified=True, num_blocks=15, prefill_budget=8,
                      token_budget=8,
                      audit=AuditConfig(enabled=True, sample_every=1))
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, 256, 8).tolist()
        prompts = [prefix + rng.integers(0, 256, 8).tolist()
                   for _ in range(6)]
        _run(eng, prompts, max_new=10)
        assert eng.metrics.counters["preemptions"] > 0
        snap = eng.audit.snapshot()
        assert snap["status"] == "ok", snap
        assert snap["audited_launches"]["ragged"] > 0, snap
        assert sum(snap["divergences"].values()) == 0, snap
        assert snap["oracle_failures"] == 0, snap

    def test_kernel_divergence_caught_and_replayable(self, tmp_path,
                                                     monkeypatch):
        """A corrupted ragged kernel is caught by the shadow oracle: one
        token divergence, one size-capped .npz repro whose replay
        reproduces the mismatch through ``_reference_ragged``."""
        from paddle_tpu.observability.audit import AuditConfig, replay_repro
        from paddle_tpu.ops import ragged_paged as rp_mod

        real = rp_mod.ragged_paged_attention

        def corrupt(q, *args, use_pallas=None, **kw):
            # the auditor's reference pins use_pallas=False — corrupt
            # only the engine's primary dispatch (auto/None), exactly
            # like a drifting kernel would
            if use_pallas is False:
                return real(q, *args, use_pallas=use_pallas, **kw)
            return real(q + np.float32(0.05), *args,
                        use_pallas=use_pallas, **kw)

        monkeypatch.setattr(rp_mod, "ragged_paged_attention", corrupt)
        eng = _engine(unified=True,
                      audit=AuditConfig(enabled=True, sample_every=1,
                                        repro_dir=str(tmp_path)))
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=4))
                for p in PROMPTS[:2]]
        eng.run(max_steps=400)
        assert all(r.finished for r in reqs)
        snap = eng.audit.snapshot()
        assert snap["status"] == "degraded", snap
        assert sum(snap["divergences"].values()) > 0, snap
        assert len(snap["repros"]) >= 1, snap
        monkeypatch.undo()  # replay must run the REAL reference
        rep = replay_repro(snap["repros"][0], eng)
        assert rep["program"] == "ragged"
        assert rep["reproduced"], rep


# --- the mp>1 auto-pin lift (satellite) --------------------------------------

class TestPallasPinLift:
    def test_forcing_legacy_kernel_at_mp2_raises(self):
        try:
            topology.init_mesh(mp=2)
            paddle.seed(0)
            model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
            with pytest.raises(ValueError, match="unified_step"):
                EngineCore(model, config=EngineConfig(
                    num_blocks=64, block_size=4, use_pallas_paged=True))
        finally:
            topology.set_mesh(None)

    def test_unified_keeps_kernel_routing_at_mp2(self):
        """With the unified step, mp>1 no longer silently forces the
        gather path: the ragged program keeps the configured routing
        (shard_map kernel) while the legacy programs stay pinned."""
        try:
            eng = _engine(mp=2, unified=True, use_pallas_paged=True)
            assert eng._use_pallas_ragged is True
            assert eng._use_pallas is False  # legacy families stay safe
        finally:
            topology.set_mesh(None)

    def test_mp1_unified_kernel_runs(self):
        eng = _engine(unified=True, use_pallas_paged=True)
        outs = _run(eng, PROMPTS[:2], max_new=4)
        from paddle_tpu.ops import ragged_paged as rp_mod
        assert rp_mod.last_path == "pallas"
        legacy = _engine(unified=False)
        assert outs == _run(legacy, PROMPTS[:2], max_new=4)


# --- tooling coverage (satellite) -------------------------------------------

class TestToolingCoverage:
    def test_bounded_lint_covers_ragged_kernel(self):
        import os
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(repo, "tools"))
        try:
            import check_bounded_metrics as lint
        finally:
            sys.path.pop(0)
        covered = {os.path.relpath(p, repo) for p in lint.SCAN_FILES}
        assert "paddle_tpu/ops/ragged_paged.py" in covered
        assert lint.scan(dirs=(), files=lint.SCAN_FILES) == []

    def test_ragged_metrics_documented(self):
        """The new serving_unified_*/serving_ragged_* series are in the
        README metrics table (tools/check_metrics_docs.py passes) and
        declared by serving/metrics.py."""
        import os
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(repo, "tools"))
        try:
            import check_metrics_docs as docs_lint
        finally:
            sys.path.pop(0)
        declared = docs_lint.declared_metrics(os.path.join(
            repo, "paddle_tpu", "serving", "metrics.py"))
        for name in ("serving_unified_steps_total",
                     "serving_ragged_jit_traces_total",
                     "serving_unified_step_seconds"):
            assert name in declared, f"{name} not declared"
        assert docs_lint.scan() == []

    def test_unified_metrics_on_registry(self):
        """The packed launch feeds the program-labelled step-profiler
        series and the unified counters."""
        eng = _engine(unified=True)
        _run(eng, PROMPTS[:2], max_new=4)
        text = eng.metrics.prometheus_text()
        assert "serving_unified_steps_total" in text
        assert "serving_ragged_jit_traces_total" in text
        assert 'serving_scheduled_tokens_total{program="ragged"}' in text
        assert eng.stepprof.bucket_set("ragged")
