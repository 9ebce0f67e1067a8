"""Online numerics auditing (ISSUE 10).

Tentpole coverage:

* NaN/Inf sentinel + logit-stats telemetry: the in-trace reductions are
  part of the program whether auditing is on or off, so audit on
  (``sample_every=1``) vs off is greedy token-identical with EQUAL jit
  trace counts, and ``/metrics`` carries zero ``serving_audit_*`` /
  ``serving_logit_*`` series when disabled;
* shadow-oracle differential execution: the engine's decode steps
  re-executed through the independently jitted XLA gather reference —
  clean on the XLA path, clean with the Pallas interpret kernel, and
  clean at mp=2 (the replicated single-shard re-run of the
  mesh-spanning program);
* forced-corruption paths: a monkeypatched kernel (token divergence)
  and injected NaN logits each fire exactly ONE size-capped ``.npz``
  repro whose replay reproduces the mismatch, increment the matching
  ``{kind}`` counter, degrade the auditor, and (under a fleet) dump
  exactly one flight bundle per affected replica — at dp=1 and dp=2
  with per-replica attribution;
* debug/ops surface: ``GET /v1/debug/audit``, the ``/readyz``
  ``audit=degraded`` annotation (readiness itself never flips), fleet
  rejection of heterogeneous audit configs, lint coverage;
* satellite: direct fast CPU interpret-mode kernel-vs-gather parity
  over every decode bucket shape in the default bucket set — the
  oracle pair is exercised even with auditing off.
"""

import asyncio
import http.client
import json
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.audit import (
    AuditConfig,
    load_repro,
    logit_stats,
    replay_repro,
)
from paddle_tpu.ops import pallas_paged
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    FleetConfig,
    FleetRouter,
    SamplingParams,
    SchedulerConfig,
)
from paddle_tpu.serving.fleet import affinity_replica_index
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


def _engine(audit=None, num_blocks=15, max_num_seqs=4, chunk_budget=8,
            use_pallas=None, registry=None, metrics_labels=None):
    """Small pool + chunk budget: concurrent 16+10-token sequences
    cannot fit, so the run chunks, preempts, and recomputes."""
    return EngineCore(
        _model(),
        config=EngineConfig(
            num_blocks=num_blocks, block_size=BS,
            scheduler=SchedulerConfig(
                max_num_seqs=max_num_seqs,
                max_prefill_tokens_per_step=chunk_budget),
            use_pallas_paged=use_pallas, audit=audit),
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


@pytest.fixture
def corrupt_kernel(monkeypatch):
    """Negate the Pallas decode kernel's output: a drastic, deterministic
    drift that flips greedy tokens — the 'kernel went wrong' injection."""
    real = pallas_paged.paged_attention_decode
    monkeypatch.setattr(pallas_paged, "paged_attention_decode",
                        lambda *a: -real(*a))
    yield


@pytest.fixture
def nan_kernel(monkeypatch):
    """Make the Pallas decode kernel emit NaNs — the 'value corruption'
    injection the sentinel must catch before any comparison runs."""
    import jax.numpy as jnp

    real = pallas_paged.paged_attention_decode
    monkeypatch.setattr(pallas_paged, "paged_attention_decode",
                        lambda *a: jnp.full_like(real(*a), jnp.nan))
    yield


# --------------------------------------------------------------------------
# unit: logit_stats + AuditConfig
# --------------------------------------------------------------------------
class TestUnits:
    def test_logit_stats_rows(self):
        l = np.array([[1.0, 3.0, -2.0, 0.5],
                      [np.nan, 1.0, np.inf, -1.0]], np.float32)
        s = np.asarray(logit_stats(l))
        assert s.shape == (2, 3)
        assert s[0, 0] == 0 and s[1, 0] == 2       # non-finite count
        assert s[0, 1] == 3.0                       # max |logit|
        assert s[0, 2] == pytest.approx(2.0)        # top1 - top2 = 3 - 1
        # non-finite entries masked to 0 before max/top-k: stays finite
        assert np.isfinite(s[1]).all()

    def test_logit_stats_1d_row(self):
        s = np.asarray(logit_stats(np.array([0.0, 5.0, 1.0], np.float32)))
        assert s.shape == (1, 3)
        assert s[0, 1] == 5.0 and s[0, 2] == pytest.approx(4.0)

    def test_config_validation(self):
        with pytest.raises(ValueError):
            AuditConfig(sample_every=0)
        with pytest.raises(ValueError):
            AuditConfig(max_repros=0)
        # frozen: fleets compare configs by value
        assert AuditConfig(enabled=True) == AuditConfig(enabled=True)
        assert AuditConfig(enabled=True) != AuditConfig(enabled=False)


# --------------------------------------------------------------------------
# satellite: direct kernel-vs-gather parity over the default bucket set
# --------------------------------------------------------------------------
class TestKernelOracleParity:
    """The oracle pair must hold even with auditing off: every decode
    bucket shape in the default bucket set (batch buckets up to
    max_num_seqs=8, power-of-two table widths) through the interpret-
    mode Pallas kernel vs ``decode_oracle`` (the XLA gather path)."""

    @pytest.mark.parametrize("B", [1, 2, 4, 8])
    @pytest.mark.parametrize("W", [1, 2, 4, 8])
    def test_decode_bucket_parity(self, B, W):
        import jax.numpy as jnp

        rng = np.random.default_rng(B * 16 + W)
        bs, Hkv, H, D = BS, 2, 4, 16
        num_blocks = W * B + 2
        k = rng.standard_normal((num_blocks, bs, Hkv, D)).astype(np.float32)
        v = rng.standard_normal((num_blocks, bs, Hkv, D)).astype(np.float32)
        q = rng.standard_normal((B, H, D)).astype(np.float32)
        tables = np.zeros((B, W), np.int32)
        lens = np.zeros((B,), np.int32)
        blocks = iter(range(1, num_blocks))
        for i in range(B):
            owned = rng.integers(1, W + 1)
            tables[i, :owned] = [next(blocks) for _ in range(owned)]
            lens[i] = rng.integers(1, owned * bs + 1)
        out_k = np.asarray(pallas_paged.paged_attention_decode(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(lens)))
        out_o = np.asarray(pallas_paged.decode_oracle(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(lens)))
        np.testing.assert_allclose(out_k, out_o, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("bs,rep,Hkv,dtype", [
        (16, 1, 2, "float32"), (16, 4, 2, "float32"), (16, 16, 2, "float32"),
        # the rings' pages: the kernel of a step a (row, page), as before
        (256, 1, 2, "float32"), (256, 16, 2, "float32"),
        (256, 16, 8, "bfloat16"),
        # up to HEADS_UNROLLED heads in one iteration, every index static:
        # float32 heads, PAIRS of bfloat16 heads in a 32-bit word, a count
        # that is no multiple of 8, and the one head that is no word
        (16, 2, 8, "float32"), (16, 4, 8, "bfloat16"), (16, 1, 12, "float32"),
        (16, 4, 1, "bfloat16"),
        # more heads: a LOOP of HEADS_UNROLLED an iteration, both kinds
        (16, 2, 16, "float32"), (16, 1, 32, "bfloat16"),
        (16, 1, 32, "float32"),
        # a pool the group walk cannot take keeps the other kernel
        (16, 2, 3, "bfloat16"),
    ], ids=lambda v: str(v))
    def test_decode_group_walk(self, bs, rep, Hkv, dtype):
        """Small pages are walked in groups of ``P`` and the walk stops at
        the row's length: lengths one under, at and one over a whole
        number of groups, 1 and 0 (bucket padding), one longer than its
        table (read as the whole table), a table width that is no multiple
        of ``P``, and NOTHING past a row's last page read into the result.
        Pages of 16 tokens get tables whose padding names a block of NaN;
        pages of 256 tokens (``P`` = 1, the kernel of a step a (row,
        page)) get the ring's tables (``ops.window_attention
        .ring_decode_attention``), whose padding repeats the row's last
        page."""
        import jax
        import jax.numpy as jnp

        D = 16
        ring = bs == 256
        W = 5 if ring else 19
        P = pallas_paged.pages_per_step(
            bs, Hkv, D, jnp.dtype(dtype).itemsize, W)
        assert P == (1 if ring else 8) and (ring or W % P)
        groups = pallas_paged.kernel_pages(jax.ShapeDtypeStruct(
            (1, bs, Hkv, D), jnp.dtype(dtype)), W) > 1
        step = P * bs
        lens = np.array([n for k in (1, 2) for n in
                         (k * step - 1, k * step, k * step + 1)]
                        + [1, 0, W * bs + 3], np.int32)
        B = len(lens)
        owned = np.minimum(-(-lens // bs), W)   # the last: a full table
        rng = np.random.default_rng(bs + rep)
        num_blocks = B * W + 2 if ring else int(owned.sum()) + 2
        k = rng.standard_normal((num_blocks, bs, Hkv, D)).astype(np.float32)
        v = rng.standard_normal((num_blocks, bs, Hkv, D)).astype(np.float32)
        q = rng.standard_normal((B, Hkv * rep, D)).astype(np.float32)
        if ring:    # slot b's pages in order, clamped to its last live one
            last = (np.maximum(lens, 1) - 1) // bs
            tables = clean = (np.arange(B)[:, None] * W + np.minimum(
                np.arange(W)[None, :], last[:, None])).astype(np.int32)
        else:
            # the kernel of a step a (row, page) COPIES a padded entry's
            # page (and computes nothing on it): its padding names block 0
            poison = num_blocks - 1 if groups else 0
            k[num_blocks - 1] = v[num_blocks - 1] = np.nan
            k[0] = v[0] = 0.0
            tables = np.full((B, W), poison, np.int32)
            clean = np.zeros((B, W), np.int32)
            blocks = iter(rng.permutation(np.arange(1, num_blocks - 1)))
            for i in range(B):
                tables[i, :owned[i]] = clean[i, :owned[i]] = [
                    next(blocks) for _ in range(owned[i])]
        q, k, v = (jnp.asarray(a, dtype) for a in (q, k, v))
        out_k = np.asarray(pallas_paged.paged_attention_decode(
            q, k, v, jnp.asarray(tables), jnp.asarray(lens)), np.float32)
        out_o = np.asarray(pallas_paged.decode_oracle(
            q, k, v, jnp.asarray(clean), jnp.asarray(lens)), np.float32)
        assert np.isfinite(out_k).all()
        live = lens > 0
        tol = 2e-5 if dtype == "float32" else 2e-2   # the output's rounding
        np.testing.assert_allclose(out_k[live], out_o[live],
                                   atol=tol, rtol=tol)
        assert not out_k[~live].any()       # bucket padding: zeros

    @pytest.mark.parametrize("shape,pages", [
        # block size, KV heads, head size, bytes an element, table width
        ((16, 8, 128, 2, 256), 8),      # mistral-7b-v0.3
        ((16, 32, 128, 2, 64), 8),      # deepseek-llm-7b
        ((16, 8, 128, 2, 512), 8),      # command-a-plus, the global layer
        ((256, 8, 128, 2, 16), 1),      # command-a-plus, a ring
    ], ids=["mistral", "deepseek", "command-a-global", "command-a-ring"])
    def test_pages_per_step(self, shape, pages):
        bs, hkv, d, itemsize, width = shape
        assert pallas_paged.pages_per_step(*shape) == pages
        # K and V, two buffers each, of ``pages`` pages
        assert 4 * pages * bs * hkv * d * itemsize \
            <= pallas_paged.PAGE_BUFFER_BYTES
        assert pages == 1 or pages * bs == pallas_paged.STEP_TOKENS
        assert pallas_paged.pages_per_step(bs, hkv, d, itemsize, 3) <= 3
        # pages too large for the budget: fewer a step, one at least
        assert pallas_paged.pages_per_step(16, 8, 128, 2, 512) > \
            pallas_paged.pages_per_step(16, 2048, 128, 2, 512) >= 1

    @pytest.mark.parametrize("bs,width,grid", [
        (16, 24, "(B,)"), (256, 16, "(B, n_pages)")],
        ids=["pages-of-16", "pages-of-256"])
    def test_the_pools_shape_chooses_the_kernel(self, bs, width, grid):
        """Read off the traced program, not off a flag: pages of 16 tokens
        launch the group walk, one grid step a row, the pools left in HBM
        (``ANY``); pages of 256 the kernel of a step a (row, page)."""
        import jax
        import jax.numpy as jnp

        B, H, Hkv, D = 3, 8, 2, 16
        pool = jax.ShapeDtypeStruct((40, bs, Hkv, D), jnp.float32)
        jaxpr = jax.make_jaxpr(pallas_paged.paged_attention_decode)(
            jax.ShapeDtypeStruct((B, H, D), jnp.float32), pool, pool,
            jax.ShapeDtypeStruct((B, width), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
        calls = []

        def walk(j):
            for eqn in j.eqns:
                if eqn.primitive.name == "pallas_call":
                    calls.append(eqn.params["grid_mapping"])
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        assert len(calls) == 1
        assert tuple(calls[0].grid) == \
            ((pallas_paged.ROWS_MIN,) if grid == "(B,)" else (B, width))

    def test_the_group_walk_is_traced_once_a_row_bucket(self, monkeypatch):
        """The walk reads nothing of a table's width, so tables narrower
        than ``TABLE_WIDTH`` go in at that width: programs that differ in
        their table width alone share ONE trace of the kernel (a warm start
        traces it once a row bucket, not once a program); the kernel of a
        step a (row, page), whose grid IS the width, does not."""
        import jax
        import jax.numpy as jnp

        traced = []
        for name in ("_group_walk_kernel", "_decode_kernel"):
            def counting(*a, _kernel=getattr(pallas_paged, name), **kw):
                traced.append(_kernel.__name__)
                return _kernel(*a, **kw)
            monkeypatch.setattr(pallas_paged, name, counting)

        def traces(bs, widths):
            B, H, Hkv, D = 3, 8, 2, 16
            pool = jax.ShapeDtypeStruct((40, bs, Hkv, D), jnp.float32)
            del traced[:]
            for width in widths:
                jax.make_jaxpr(pallas_paged.paged_attention_decode)(
                    jax.ShapeDtypeStruct((B, H, D), jnp.float32), pool, pool,
                    jax.ShapeDtypeStruct((B, width), jnp.int32),
                    jax.ShapeDtypeStruct((B,), jnp.int32))
            return list(traced)

        jax.clear_caches()
        assert traces(16, (24, 40, 56)) == ["_group_walk_kernel"]
        # and fewer than ROWS_MIN rows (B is 3 above) with it
        assert pallas_paged.ROWS_MIN == 8
        pool = jax.ShapeDtypeStruct((40, 16, 2, 16), jnp.float32)
        del traced[:]
        for rows in (1, 8, 16):
            jax.make_jaxpr(pallas_paged.paged_attention_decode)(
                jax.ShapeDtypeStruct((rows, 8, 16), jnp.float32), pool, pool,
                jax.ShapeDtypeStruct((rows, 24), jnp.int32),
                jax.ShapeDtypeStruct((rows,), jnp.int32))
        assert traced == ["_group_walk_kernel"]     # 16 rows: its own
        assert traces(256, (4, 8)) == ["_decode_kernel"] * 2
        # wider than the shared width: its own
        wide = pallas_paged.TABLE_WIDTH
        assert traces(16, (wide + 8, wide + 16)) == ["_group_walk_kernel"] * 2


# --------------------------------------------------------------------------
# engine integration: clean audits
# --------------------------------------------------------------------------
class TestCleanAudit:
    def test_on_vs_off_token_identical_equal_traces(self):
        prompts = _prompts()
        on = _engine(audit=AuditConfig(enabled=True, sample_every=1))
        out_on = _run(on, prompts)
        off = _engine(audit=None)
        out_off = _run(off, prompts)
        assert out_on == out_off
        # the in-trace logit stats are computed unconditionally, so the
        # bucket sets AND trace counts are provably unchanged on-vs-off
        assert on.prefill_trace_count == off.prefill_trace_count
        assert on.decode_trace_count == off.decode_trace_count
        assert on.prefill_buckets == off.prefill_buckets
        assert on.decode_buckets == off.decode_buckets
        # the run preempted/chunked and still audited clean
        assert on.metrics.counters["preemptions"] > 0
        assert off.metrics.counters["preemptions"] > 0
        snap = on.audit.snapshot()
        assert snap["status"] == "ok"
        assert sum(snap["divergences"].values()) == 0
        assert sum(snap["audited_launches"].values()) > 0
        # every audited launch really compared: no crashed oracles
        assert snap["oracle_failures"] == 0

    def test_metrics_present_when_on_absent_when_off(self):
        on = _engine(audit=AuditConfig(enabled=True, sample_every=1),
                     num_blocks=64)
        _run(on, _prompts(n=1), max_new=3)
        text = on.metrics.prometheus_text()
        for series in ("serving_audit_steps_total",
                       "serving_audit_divergence_total",
                       "serving_audit_nonfinite_total",
                       "serving_audit_oracle_failures_total",
                       "serving_audit_logit_absdiff",
                       "serving_logit_absmax", "serving_logit_margin"):
            assert series in text, series
        off = _engine(audit=None, num_blocks=64)
        _run(off, _prompts(n=1), max_new=3)
        text = off.metrics.prometheus_text()
        assert "serving_audit" not in text
        # the auditor's histograms (serving_logit_absmax / _margin); the
        # launch loop's own serving_logits_fetches_total is there, at 0
        assert "serving_logit_" not in text
        assert "serving_logits_fetches_total 0" in text

    def test_sample_schedule_deterministic(self):
        eng = _engine(audit=AuditConfig(enabled=True, sample_every=3),
                      num_blocks=64)
        _run(eng, _prompts(n=2), max_new=6)
        snap = eng.audit.snapshot()
        # steps 1, 4, 7, ... are sampled — a strict subset of steps ran
        # audited, none diverged, and the schedule needed no clock
        assert 0 < sum(snap["audited_launches"].values())
        assert snap["steps"] > sum(snap["audited_launches"].values())
        assert snap["status"] == "ok"

    def test_pallas_kernel_vs_gather_oracle_clean(self):
        eng = _engine(audit=AuditConfig(enabled=True, sample_every=1),
                      num_blocks=64, use_pallas=True)
        _run(eng, _prompts(n=2), max_new=5)
        # (ops.paged_attention.last_path reads "xla" here because the
        # SHADOW reference ran most recently — the corruption tests
        # below prove the primary decode really runs the kernel: a
        # corrupted kernel shows up as divergence)
        snap = eng.audit.snapshot()
        assert snap["status"] == "ok", snap
        assert sum(snap["divergences"].values()) == 0
        assert snap["audited_launches"]["decode"] > 0

    def test_mp2_replicated_single_shard_rerun_clean(self):
        from paddle_tpu.distributed import topology

        topology.init_mesh(mp=2)
        try:
            eng = _engine(audit=AuditConfig(enabled=True, sample_every=1),
                          num_blocks=64)
            assert eng.mp == 2
            _run(eng, _prompts(n=2), max_new=4)
            snap = eng.audit.snapshot()
            assert snap["status"] == "ok", snap
            assert sum(snap["divergences"].values()) == 0
            assert snap["audited_launches"]["decode"] > 0
        finally:
            topology.set_mesh(None)


# --------------------------------------------------------------------------
# forced corruption: token divergence + NaN injection (dp=1, direct engine)
# --------------------------------------------------------------------------
class TestForcedCorruption:
    def test_token_divergence_one_repro_replayable(self, tmp_path,
                                                   corrupt_kernel):
        eng = _engine(audit=AuditConfig(enabled=True, sample_every=1,
                                        repro_dir=str(tmp_path)),
                      num_blocks=64, use_pallas=True)
        _run(eng, _prompts(n=2), max_new=4)
        snap = eng.audit.snapshot()
        assert snap["status"] == "degraded"
        assert snap["divergences"]["token"] > 0
        assert snap["divergences"]["nonfinite"] == 0
        # exactly ONE repro despite every audited step diverging
        assert len(snap["repros"]) == 1
        path = snap["repros"][0]
        assert os.path.getsize(path) <= eng.audit.cfg.max_repro_bytes
        r = load_repro(path)
        assert r["meta"]["kind"] == "token"
        assert r["meta"]["program"] == "decode"
        assert r["meta"]["replica"] == "0"
        for key in ("ids", "tables", "lens", "k_pools", "v_pools",
                    "primary_logits", "reference_logits"):
            assert key in r["arrays"], key
        # replay on a CLEAN engine with the same weights: the reference
        # recomputed from the stored inputs still disagrees with the
        # stored (corrupted) primary logits
        clean = _engine(audit=None, num_blocks=64)
        verdict = replay_repro(path, clean)
        assert verdict["reproduced"] and verdict["replayed"]
        assert verdict["max_abs_diff"] > 0
        # degraded state carries the divergence detail (the LATEST
        # divergence; only the first wrote the repro — fired-once)
        assert snap["last_divergence"]["kind"] == "token"
        assert snap["last_divergence"]["program"] == "decode"

    def test_nan_injection_one_repro_nonfinite_kind(self, tmp_path,
                                                    nan_kernel):
        eng = _engine(audit=AuditConfig(enabled=True, sample_every=1,
                                        repro_dir=str(tmp_path)),
                      num_blocks=64, use_pallas=True)
        _run(eng, _prompts(n=2), max_new=4)
        snap = eng.audit.snapshot()
        assert snap["status"] == "degraded"
        assert snap["divergences"]["nonfinite"] > 0
        # the sentinel claims a non-finite step BEFORE the shadow
        # comparison — it must not double-report as token divergence
        assert snap["divergences"]["token"] == 0
        assert snap["nonfinite_values"] > 0
        assert len(snap["repros"]) == 1
        path = snap["repros"][0]
        assert os.path.getsize(path) <= eng.audit.cfg.max_repro_bytes
        r = load_repro(path)
        assert r["meta"]["kind"] == "nonfinite"
        verdict = replay_repro(path, eng)
        assert verdict["reproduced"]
        # the NaN is in the stored primary output itself
        assert not np.isfinite(r["arrays"]["primary_logits"]).all()

    def test_repro_size_cap_drops_pools(self, tmp_path, corrupt_kernel):
        eng = _engine(audit=AuditConfig(enabled=True, sample_every=1,
                                        repro_dir=str(tmp_path),
                                        max_repro_bytes=16384),
                      num_blocks=64, use_pallas=True)
        _run(eng, _prompts(n=2), max_new=4)
        snap = eng.audit.snapshot()
        assert len(snap["repros"]) == 1
        path = snap["repros"][0]
        assert os.path.getsize(path) <= 16384
        r = load_repro(path)
        assert r["meta"]["dropped"]  # pools were too big for the cap
        assert "v_pools" in r["meta"]["dropped"]
        # replay falls back to the stored logits and still reproduces
        verdict = replay_repro(path, eng)
        assert verdict["reproduced"]

    def test_no_repro_dir_still_degrades_and_counts(self, corrupt_kernel):
        eng = _engine(audit=AuditConfig(enabled=True, sample_every=1),
                      num_blocks=64, use_pallas=True)
        _run(eng, _prompts(n=2), max_new=4)
        snap = eng.audit.snapshot()
        assert snap["status"] == "degraded"
        assert snap["divergences"]["token"] > 0
        assert snap["repros"] == []


# --------------------------------------------------------------------------
# fleet: flight bundles + per-replica attribution (dp=1 and dp=2)
# --------------------------------------------------------------------------
class TestFleetAudit:
    def _fleet(self, tmp_path, dp=2, audit=None, use_pallas=True):
        audit = audit or AuditConfig(enabled=True, sample_every=1)

        def make(i, registry):
            return _engine(audit=audit, num_blocks=64,
                           use_pallas=use_pallas, registry=registry,
                           metrics_labels={"replica": str(i)})
        return FleetRouter.build(
            make, dp=dp, config=FleetConfig(flight_dir=str(tmp_path)))

    def _two_family_prompts(self, dp=2):
        rng = np.random.default_rng(0)
        fam_a = rng.integers(0, 256, 8).tolist()
        target_a = affinity_replica_index(fam_a, dp=dp, block_size=BS)
        while True:
            fam_b = rng.integers(0, 256, 8).tolist()
            if affinity_replica_index(fam_b, dp=dp, block_size=BS) \
                    != target_a:
                break
        out = []
        for _ in range(2):
            out.append(fam_a + rng.integers(0, 256, 8).tolist())
            out.append(fam_b + rng.integers(0, 256, 8).tolist())
        return out

    def test_dp1_corruption_one_flight_bundle(self, tmp_path,
                                              corrupt_kernel):
        fleet = self._fleet(tmp_path, dp=1)
        fleet.start()
        try:
            handles = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=4), request_id=f"a{i}")
                for i, p in enumerate(_prompts(n=2))]
            fleet.wait(handles, timeout=600)
        finally:
            fleet.shutdown(drain_timeout=5.0)
        aud = fleet.replicas[0].engine.audit
        snap = aud.snapshot()
        assert snap["divergences"]["token"] > 0
        assert snap["replica"] == "0"
        # exactly one .npz repro, exactly one flight bundle, both
        # attributed to replica 0
        assert len(snap["repros"]) == 1
        bundles = [b for b in fleet.flight.bundles if "divergence" in b]
        assert len(bundles) == 1
        bundle = json.loads(open(bundles[0]).read())
        assert bundle["trigger"] == "divergence"
        assert bundle["replica"] == "0"
        detail = json.loads(bundle["detail"])
        assert detail["kind"] == "token"
        assert detail["repro"] == snap["repros"][0]
        # the flight bundle carries the registry snapshot alongside
        assert "serving_audit_divergence_total" in json.dumps(
            bundle["metrics"])

    def test_dp2_per_replica_attribution(self, tmp_path, corrupt_kernel):
        fleet = self._fleet(tmp_path, dp=2)
        fleet.start()
        try:
            handles = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=4), request_id=f"b{i}")
                for i, p in enumerate(self._two_family_prompts())]
            fleet.wait(handles, timeout=600)
        finally:
            fleet.shutdown(drain_timeout=5.0)
        diverged = {str(r.index) for r in fleet.replicas
                    if r.engine.audit.snapshot()["divergences"]["token"]}
        assert diverged == {"0", "1"}  # both families decoded corrupt
        # one flight bundle per affected replica, each attributed
        bundles = [json.loads(open(b).read())
                   for b in fleet.flight.bundles if "divergence" in b]
        assert {b["replica"] for b in bundles} == diverged
        assert len(bundles) == 2
        for r in fleet.replicas:
            # the auditor writes one repro per (kind, program): the token
            # divergence always, and a logit one beside it when a launch
            # whose argmax flip was a tie diverged in value first
            repros = r.engine.audit.snapshot()["repros"]
            metas = [load_repro(p)["meta"] for p in repros]
            kinds = sorted(m["kind"] for m in metas)
            assert kinds in (["token"], ["logit", "token"])
            assert {m["program"] for m in metas} == {"decode"}
            assert {m["replica"] for m in metas} == {str(r.index)}
            assert all(f"_r{r.index}_" in path for path in repros)
        # per-replica-labeled divergence series on the shared registry
        text = fleet.registry.prometheus_text()
        assert 'serving_audit_divergence_total' in text
        assert 'replica="0"' in text and 'replica="1"' in text

    def test_fleet_rejects_heterogeneous_audit(self):
        def make(i, registry):
            return _engine(
                audit=(AuditConfig(enabled=True) if i == 0 else None),
                num_blocks=64, registry=registry,
                metrics_labels={"replica": str(i)})

        with pytest.raises(ValueError, match="audit"):
            FleetRouter.build(make, dp=2)

    @pytest.mark.parametrize("dp", [1, 2])
    def test_nan_under_fleet_fires_nonfinite_trigger(self, tmp_path,
                                                     nan_kernel, dp):
        fleet = self._fleet(tmp_path, dp=dp)
        fleet.start()
        try:
            prompts = (_prompts(n=2) if dp == 1
                       else self._two_family_prompts())
            handles = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=4), request_id=f"n{i}")
                for i, p in enumerate(prompts)]
            fleet.wait(handles, timeout=600)
        finally:
            fleet.shutdown(drain_timeout=5.0)
        # exactly one size-capped bundle + one .npz repro per affected
        # replica, each attributed to the replica that saw the NaNs
        affected = {str(r.index) for r in fleet.replicas
                    if r.engine.audit.snapshot()["divergences"]
                    ["nonfinite"]}
        assert affected == {str(i) for i in range(dp)}
        bundles = [json.loads(open(b).read())
                   for b in fleet.flight.bundles if "nonfinite" in b]
        assert len(bundles) == dp
        assert {b["replica"] for b in bundles} == affected
        for r in fleet.replicas:
            snap = r.engine.audit.snapshot()
            assert len(snap["repros"]) == 1
            assert os.path.getsize(snap["repros"][0]) <= \
                r.engine.audit.cfg.max_repro_bytes


# --------------------------------------------------------------------------
# HTTP debug surface + readyz annotation
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


class TestHTTPAudit:
    def test_debug_audit_ok_after_traffic(self, harness_factory):
        h = harness_factory(_engine(
            audit=AuditConfig(enabled=True, sample_every=1),
            num_blocks=64))
        status, _, data = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": list(range(10)), "max_tokens": 4})
        assert status == 200
        status, headers, data = _request(h.port, "GET", "/v1/debug/audit")
        assert status == 200
        assert headers["content-type"] == "application/json"
        obj = json.loads(data)
        assert obj["status"] == "ok"
        row = obj["data"][0]
        assert row["replica"] == "0" and row["enabled"] is True
        assert sum(row["audited_launches"].values()) > 0
        assert sum(row["divergences"].values()) == 0

    def test_debug_audit_disabled_and_bad_replica(self, harness_factory):
        h = harness_factory(_engine(audit=None, num_blocks=64))
        status, _, data = _request(h.port, "GET", "/v1/debug/audit")
        assert status == 200
        obj = json.loads(data)
        assert obj["status"] == "disabled"
        assert obj["data"][0]["enabled"] is False
        status, headers, data = _request(
            h.port, "GET", "/v1/debug/audit?replica=7")
        assert status == 404
        assert headers["content-type"] == "application/json"
        status, _, _ = _request(
            h.port, "GET", "/v1/debug/audit?replica=zap")
        assert status == 400

    def test_readyz_annotates_degraded_never_flips(self, harness_factory,
                                                   corrupt_kernel,
                                                   tmp_path):
        h = harness_factory(_engine(
            audit=AuditConfig(enabled=True, sample_every=1,
                              repro_dir=str(tmp_path)),
            num_blocks=64, use_pallas=True))
        status, _, data = _request(h.port, "GET", "/readyz")
        assert status == 200 and b"audit=degraded" not in data
        status, _, _ = _request(
            h.port, "POST", "/v1/completions",
            {"prompt": list(range(10)), "max_tokens": 4})
        assert status == 200
        # degraded auditor: readiness stays 200, the body says why
        status, _, data = _request(h.port, "GET", "/readyz")
        assert status == 200, "a degraded auditor must NOT flip readiness"
        assert b"audit=degraded" in data
        status, _, data = _request(h.port, "GET", "/v1/debug/audit")
        assert json.loads(data)["status"] == "degraded"


# --------------------------------------------------------------------------
# lint coverage (satellite tooling)
# --------------------------------------------------------------------------
class TestLintCoverage:
    def test_bounded_metrics_scan_covers_audit(self):
        covered = {os.path.relpath(p, _REPO)
                   for p in bounded_lint.SCAN_FILES}
        assert "paddle_tpu/observability/audit.py" in covered
        assert bounded_lint.scan(dirs=(),
                                 files=bounded_lint.SCAN_FILES) == []

    def test_metrics_docs_lint_covers_audit(self):
        covered = {os.path.relpath(p, _REPO)
                   for p in docs_lint.DECLARING_MODULES}
        assert "paddle_tpu/observability/audit.py" in covered
        assert docs_lint.scan() == []
