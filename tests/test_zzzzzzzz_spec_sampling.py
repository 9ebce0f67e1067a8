"""Speculative decoding + first-class sampling (ISSUE 18).

The contract under test: (a) in-trace sampling — temperature / top-k /
top-p with Gumbel-max draws keyed by ``(seed, draw_index)`` — is
deterministic under a seed across reruns, recompute, dp fan-out and
spec-decode; (b) the n-gram draft/verify path is **token-identical** to
the plain engine (greedy AND seeded sampling) while finishing a
decode-heavy stream in **strictly fewer engine steps**; (c) the
protocol rejects malformed ``top_p`` at the HTTP boundary; (d) the
fleet wire's deployment-identity handshake refuses mismatched
mp/spec deployments with a typed ``deploy_mismatch``.
"""

import json
import math
from types import SimpleNamespace

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
from paddle_tpu.serving import wire
from paddle_tpu.serving.fleet import FleetConfig, FleetRouter
from paddle_tpu.serving.protocol import ProtocolError, parse_completion_request
from paddle_tpu.serving.spec import NgramProposer, SpecConfig, SpecDecoder

# repetitive prompts so the n-gram proposer has something to chew on;
# tiny greedy models also settle into cycles, which is the self-spec
# sweet spot
_RNG = np.random.default_rng(7)
LOOP_PROMPT = [5, 6, 7, 8] * 3
# ends mid-repeat: the suffix [5,6,7] already occurred, so the proposer
# drafts on the FIRST decode step — even when sampled output is noisy
MID_PROMPT = [5, 6, 7, 8] * 2 + [5, 6, 7]
PROMPTS = [LOOP_PROMPT,
           [40, 2, 11, 40, 2, 11, 40, 2],
           _RNG.integers(0, 256, 8).tolist()]

SAMPLED = dict(temperature=0.8, top_k=20, top_p=0.9, seed=1234)


# --- protocol: top_p hardening (satellite 1) --------------------------------

def _parse(**over):
    body = {"prompt": [1, 2, 3], "max_tokens": 4}
    body.update(over)
    return parse_completion_request(json.dumps(body).encode())


class TestProtocolTopP:
    @pytest.mark.parametrize("bad", [0, 0.0, -0.5, 1.5, 2,
                                     float("nan"), float("inf")])
    def test_rejects_out_of_range(self, bad):
        with pytest.raises(ProtocolError, match="top_p"):
            _parse(top_p=bad)

    def test_rejects_non_numeric(self):
        with pytest.raises(ProtocolError):
            _parse(top_p="0.9")

    @pytest.mark.parametrize("ok", [0.1, 0.9, 1, 1.0])
    def test_accepts_valid(self, ok):
        req = _parse(top_p=ok)
        assert req.top_p == pytest.approx(float(ok))

    def test_default_and_forwarding(self):
        assert _parse().top_p == 1.0
        sp = _parse(top_p=0.7, temperature=0.8, top_k=5, seed=9).sampling()
        assert (sp.top_p, sp.temperature, sp.top_k, sp.seed) \
            == (pytest.approx(0.7), pytest.approx(0.8), 5, 9)

    @pytest.mark.parametrize("bad_k", [-1, -100])
    def test_rejects_negative_top_k(self, bad_k):
        with pytest.raises(ProtocolError, match="top_k"):
            _parse(top_k=bad_k)


# --- n-gram proposer unit suite ---------------------------------------------

class TestNgramProposer:
    def test_k_zero_and_short_context(self):
        p = NgramProposer()
        assert p.propose([1, 2, 3, 1, 2], 0) == []
        assert p.propose([], 4) == []
        assert p.propose([7], 4) == []

    def test_no_match_stays_plain(self):
        assert NgramProposer().propose(list(range(20)), 4) == []

    def test_basic_match_proposes_continuation(self):
        # suffix [5,6,7] occurred earlier, followed by [8,9]
        ctx = [5, 6, 7, 8, 9, 1, 5, 6, 7]
        assert NgramProposer(max_ngram=3).propose(ctx, 4) == [8, 9, 1, 5]
        assert NgramProposer(max_ngram=3).propose(ctx, 2) == [8, 9]

    def test_longest_suffix_wins(self):
        # 1-gram [3] matches at index 0 (→ would propose 9), but the
        # 2-gram [2,3] matches later and must take priority
        ctx = [3, 9, 2, 3, 7, 2, 3]
        assert NgramProposer(max_ngram=3).propose(ctx, 1) == [7]

    def test_most_recent_occurrence_wins(self):
        ctx = [1, 2, 5, 1, 2, 8, 1, 2]
        assert NgramProposer(max_ngram=2).propose(ctx, 1) == [8]

    def test_min_ngram_gate(self):
        ctx = [4, 1, 9, 4]  # only a 1-gram match exists
        assert NgramProposer(min_ngram=2, window=4).propose(ctx, 2) == []
        assert NgramProposer(min_ngram=1).propose(ctx, 2) == [1, 9]

    def test_window_caps_lookback(self):
        # the only earlier occurrence sits outside the window
        ctx = [7, 8] + list(range(100, 120)) + [7, 8]
        assert NgramProposer(window=10).propose(ctx, 1) == []
        assert NgramProposer(window=len(ctx)).propose(ctx, 1) == [100]

    def test_stateless(self):
        p = NgramProposer()
        ctx = [5, 6, 7, 8] * 3
        assert p.propose(ctx, 3) == p.propose(ctx, 3)


class TestSpecConfig:
    @pytest.mark.parametrize("kw", [dict(k=-1), dict(min_ngram=0),
                                    dict(ngram=2, min_ngram=3),
                                    dict(window=2, ngram=3)])
    def test_rejects_bad_knobs(self, kw):
        with pytest.raises(ValueError):
            SpecConfig(**kw)

    def test_manifest_round_trip(self):
        m = SpecConfig(k=2, window=64).manifest_dict()
        assert m == {"enabled": True, "k": 2, "ngram": 3,
                     "min_ngram": 1, "window": 64}
        assert json.loads(SpecConfig(k=2, window=64).manifest_json()) \
            == {k: int(v) for k, v in m.items()}


# --- SpecDecoder.plan_drafts edges ------------------------------------------

class _FakeKV:
    def __init__(self, grants=None):
        self.grants = grants  # None → always grant
        self.calls = []

    def allocate(self, rid, n, cause=None):
        self.calls.append((rid, n, cause))
        if self.grants is None:
            return True
        return self.grants.pop(0) if self.grants else False


def _decode_row(rid, prompt, out, max_new=16):
    req = SimpleNamespace(
        request_id=rid, prompt_ids=list(prompt), output_tokens=list(out),
        last_token=(out[-1] if out else prompt[-1]),
        sampling=SamplingParams(max_new_tokens=max_new))
    return {"kind": "decode", "req": req}


class TestPlanDrafts:
    def test_budget_zero_packs_nothing(self):
        dec = SpecDecoder(SpecConfig(k=4))
        rows = [_decode_row("a", LOOP_PROMPT, [9])]
        assert dec.plan_drafts(_FakeKV(), rows, 0) == 0
        assert rows[0]["kind"] == "decode"

    def test_upgrades_row_and_allocates(self):
        dec = SpecDecoder(SpecConfig(k=4))
        kv = _FakeKV()
        rows = [_decode_row("a", [5, 6, 7, 8, 5, 6, 7], [8])]
        packed = dec.plan_drafts(kv, rows, 16)
        assert packed > 0
        row = rows[0]
        assert row["kind"] == "verify"
        assert row["tokens"] == [row["req"].last_token] + row["drafts"]
        assert row["n"] == 1 + len(row["drafts"])
        assert kv.calls == [("a", row["n"], "spec_draft")]

    def test_headroom_caps_k(self):
        # max_new=3 with 1 emitted → headroom 1: at most one draft even
        # though the proposer could continue further
        dec = SpecDecoder(SpecConfig(k=4))
        rows = [_decode_row("a", [5, 6, 7, 8] * 3, [5], max_new=3)]
        dec.plan_drafts(_FakeKV(), rows, 16)
        assert rows[0]["kind"] == "verify" and len(rows[0]["drafts"]) == 1

    def test_headroom_zero_stays_decode(self):
        dec = SpecDecoder(SpecConfig(k=4))
        kv = _FakeKV()
        rows = [_decode_row("a", [5, 6, 7, 8] * 3, [5], max_new=2)]
        assert dec.plan_drafts(kv, rows, 16) == 0
        assert rows[0]["kind"] == "decode" and kv.calls == []

    def test_allocation_refusal_is_not_an_error(self):
        dec = SpecDecoder(SpecConfig(k=4))
        rows = [_decode_row("a", [5, 6, 7, 8] * 3, [5])]
        assert dec.plan_drafts(_FakeKV(grants=[False]), rows, 16) == 0
        assert rows[0]["kind"] == "decode"

    def test_budget_spent_across_rows(self):
        dec = SpecDecoder(SpecConfig(k=4))
        rows = [_decode_row("a", [5, 6, 7, 8] * 3, [5]),
                _decode_row("b", [1, 2, 3, 1, 2, 3], [1]),
                _decode_row("c", [4, 5, 6, 4, 5, 6], [4])]
        packed = dec.plan_drafts(_FakeKV(), rows, 5)
        assert packed <= 5
        # budget exhausted → later rows stay plain decode
        kinds = [r["kind"] for r in rows]
        assert kinds.count("verify") >= 1

    def test_accept_ratio_accounting(self):
        dec = SpecDecoder(SpecConfig(k=4))
        rows = [_decode_row("a", [5, 6, 7, 8] * 3, [5])]
        drafted = dec.plan_drafts(_FakeKV(), rows, 16)
        dec.record(drafted, drafted - 1)
        assert dec.accept_ratio == pytest.approx((drafted - 1) / drafted)


# --- wire: deployment-identity handshake (satellite 2) ----------------------

class TestDeployHandshake:
    def test_canonical_collapses_default(self):
        assert wire.canonical_deploy(None) is None
        assert wire.canonical_deploy({"mp": 1, "spec": None}) is None
        assert wire.canonical_deploy({}) is None

    def test_canonical_int_coerces(self):
        d = wire.canonical_deploy(
            {"mp": 2, "spec": {"enabled": True, "k": 4}})
        assert d == {"mp": 2, "spec": {"enabled": 1, "k": 4}}

    def test_default_interop_with_legacy_frames(self):
        # a peer that predates the deploy field sends no deploy key at
        # all — a default deployment must accept it
        frame = {"type": "hello", "version": wire.WIRE_VERSION,
                 "role": "engine", "aot_hash": None}
        assert wire.check_hello(frame, None, deploy=None) == "engine"
        assert wire.check_hello(frame, None,
                                deploy={"mp": 1, "spec": None}) == "engine"

    def test_matching_nondefault_accepts(self):
        dep = {"mp": 2, "spec": SpecConfig(k=4).manifest_dict()}
        frame = wire.hello_frame("engine", None, deploy=dep)
        assert wire.check_hello(frame, None, deploy=dict(dep)) == "engine"

    @pytest.mark.parametrize("theirs", [
        None,
        {"mp": 1, "spec": None},
        {"mp": 4, "spec": None},
        {"mp": 2, "spec": SpecConfig(k=2).manifest_dict()},
    ])
    def test_mismatch_raises_typed(self, theirs):
        mine = {"mp": 2, "spec": SpecConfig(k=4).manifest_dict()}
        frame = wire.hello_frame("engine", None, deploy=theirs)
        with pytest.raises(wire.HandshakeMismatch) as ei:
            wire.check_hello(frame, None, deploy=mine)
        assert ei.value.code == "deploy_mismatch"
        assert "deploy_mismatch" in wire.ERROR_KINDS


# --- engine-level: spec token identity + determinism matrix -----------------

def _engine(unified=True, num_blocks=64, block_size=4, max_num_seqs=4,
            token_budget=16, layers=1, registry=None, labels=None,
            **engine_kw):
    paddle.seed(0)
    topology.set_mesh(None)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))
    return EngineCore(model, config=EngineConfig(
        num_blocks=num_blocks, block_size=block_size,
        scheduler=SchedulerConfig(max_num_seqs=max_num_seqs,
                                  max_tokens_per_step=token_budget),
        unified_step=unified, **engine_kw),
        registry=registry, metrics_labels=labels)


def _run(eng, prompts, max_new=12, sampling=None):
    sp = sampling or {}
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=max_new, **sp))
            for p in prompts]
    eng.run(max_steps=4000)
    assert all(r.finished for r in reqs)
    return [list(r.output_tokens) for r in reqs]


def _steps(eng):
    return eng.metrics.counters["engine_steps"]


class TestSpecEngine:
    def test_spec_requires_unified_and_budget(self):
        with pytest.raises(ValueError):
            _engine(unified=False, spec=SpecConfig(k=4))
        with pytest.raises(ValueError):
            _engine(unified=True, token_budget=None, spec=SpecConfig(k=4))

    def test_disabled_spec_is_off(self):
        eng = _engine(spec=SpecConfig(enabled=False, k=4))
        assert eng.spec is None

    def test_greedy_token_identity_fewer_steps(self):
        """The ISSUE 18 crisp contract: greedy spec-on is token-identical
        to spec-off with STRICTLY fewer engine steps on a decode-heavy
        stream, on the same bucket lattice (no extra traces)."""
        base = _engine()
        plain = _run(base, [LOOP_PROMPT], max_new=16)
        spec_eng = _engine(spec=SpecConfig(k=4))
        specd = _run(spec_eng, [LOOP_PROMPT], max_new=16)
        assert specd == plain
        assert _steps(spec_eng) < _steps(base)
        assert spec_eng.spec.drafted_total > 0
        assert spec_eng.spec.accepted_total > 0
        assert spec_eng.kv.occupancy() == 0.0
        # same closed program universe: bucket-bounded trace count
        assert spec_eng.ragged_trace_count <= len(spec_eng.ragged_buckets)
        assert (spec_eng.prefill_trace_count == 0
                and spec_eng.decode_trace_count == 0)

    def test_greedy_multistream_identity(self):
        """Mixed streams (cyclic + aperiodic): rejected / absent drafts
        must never perturb neighbouring rows in the packed launch."""
        plain = _run(_engine(), PROMPTS, max_new=12)
        specd = _run(_engine(spec=SpecConfig(k=4)), PROMPTS, max_new=12)
        assert specd == plain

    def test_sampled_token_identity_spec_on_off(self):
        """Seeded sampling verifies exactly: spec-on replays the very
        stream spec-off samples, because verify-row position j uses the
        same (seed, draw_index) key as the plain path."""
        prompts = [MID_PROMPT] + PROMPTS[1:]
        plain = _run(_engine(), prompts, max_new=12, sampling=SAMPLED)
        eng = _engine(spec=SpecConfig(k=4))
        specd = _run(eng, prompts, max_new=12, sampling=SAMPLED)
        assert specd == plain
        assert eng.spec.drafted_total > 0

    def test_sampled_deterministic_rerun(self):
        a = _run(_engine(spec=SpecConfig(k=4)), PROMPTS, sampling=SAMPLED)
        b = _run(_engine(spec=SpecConfig(k=4)), PROMPTS, sampling=SAMPLED)
        assert a == b

    def test_sampled_seed_matters(self):
        a = _run(_engine(), [LOOP_PROMPT], sampling=SAMPLED)
        b = _run(_engine(), [LOOP_PROMPT],
                 sampling=dict(SAMPLED, seed=4321))
        assert a != b

    def test_sampled_preemption_recompute_identity(self):
        """Pool pressure preempts + recomputes mid-stream; draw-index
        keys (seed, output_position) make the resampled stream land on
        the identical tokens."""
        calm = _run(_engine(num_blocks=64), PROMPTS, max_new=8,
                    sampling=SAMPLED)
        tight = _engine(num_blocks=12)
        squeezed = _run(tight, PROMPTS, max_new=8, sampling=SAMPLED)
        assert tight.metrics.counters["preemptions"] > 0
        assert squeezed == calm

    def test_spec_preemption_recompute_identity(self):
        # 12 new tokens, not the sampled test's 8: accepted drafts end
        # the streams early, and at 8 all three finish inside the 11
        # usable blocks.  At 12 they need 16 and the pool preempts at
        # every size from 11 to 14, spec on or off.
        calm = _run(_engine(num_blocks=64, spec=SpecConfig(k=4)),
                    PROMPTS, max_new=12)
        tight = _engine(num_blocks=12, spec=SpecConfig(k=4))
        squeezed = _run(tight, PROMPTS, max_new=12)
        assert tight.spec.accepted_total > 0
        assert tight.metrics.counters["preemptions"] > 0
        assert squeezed == calm
        assert tight.kv.occupancy() == 0.0

    def test_mixed_greedy_and_sampled_one_batch(self):
        """One compiled program serves greedy and sampled rows side by
        side: each stream matches its solo-run reference."""
        solo_greedy = _run(_engine(), [PROMPTS[0]], max_new=8)
        solo_sampled = _run(_engine(), [PROMPTS[1]], max_new=8,
                            sampling=SAMPLED)
        eng = _engine()
        r1 = eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=8))
        r2 = eng.add_request(PROMPTS[1],
                             SamplingParams(max_new_tokens=8, **SAMPLED))
        eng.run(max_steps=4000)
        assert [list(r1.output_tokens)] == solo_greedy
        assert [list(r2.output_tokens)] == solo_sampled


class TestSpecFixedStreamCounts:
    """A decode-heavy stream, spec off against spec on (k=4), a greedy
    wave and then a seeded-sampled wave on the same engine.  Three
    cyclic prompts the n-gram proposer can predict carry 24 new tokens;
    one aperiodic prompt rides along with 12, so that absent and
    rejected drafts share the packed launches and a no-accept straggler
    does not pin the step count.  The counts are exact on this stream."""

    @pytest.fixture(scope="class")
    def runs(self):
        rng = np.random.default_rng(0)
        prompts = [([5, 6, 7, 8] * 3, 24), ([40, 2, 11] * 4, 24),
                   ([5, 6, 7, 8] * 2 + [5, 6, 7], 24),
                   (rng.integers(0, 256, 8).tolist(), 12)]
        out = {}
        for spec in (False, True):
            eng = _engine(spec=SpecConfig(k=4) if spec else None)
            waves, lost = [], 0
            for sp in ({}, SAMPLED):
                reqs = [eng.add_request(
                    p, SamplingParams(max_new_tokens=mx, **sp))
                    for p, mx in prompts]
                eng.run(max_steps=4000)
                lost += sum(not r.finished for r in reqs)
                waves.append([list(r.output_tokens) for r in reqs])
            out[spec] = {"outputs": waves, "lost": lost,
                         "engine_steps": _steps(eng),
                         "traces": eng.ragged_trace_count,
                         "drafted": eng.spec.drafted_total if spec else 0,
                         "accepted": (eng.spec.accepted_total
                                      if spec else 0)}
        return out

    def test_token_identity_both_waves(self, runs):
        mismatches = sum(
            a != b for pw, sw in zip(runs[False]["outputs"],
                                     runs[True]["outputs"])
            for a, b in zip(pw, sw))
        assert mismatches == 0

    @pytest.mark.parametrize("spec,name,want", [
        (False, "lost", 0), (True, "lost", 0),
        (False, "engine_steps", 52), (True, "engine_steps", 43),
        (False, "traces", 7), (True, "traces", 9),
        # accept ratio 26/55, as the two counts it is made of
        (True, "drafted", 55), (True, "accepted", 26)])
    def test_count(self, runs, spec, name, want):
        assert runs[spec][name] == want


# --- AOT: the plain unified artifact IS the spec artifact -------------------

class TestSpecAot:
    def test_aot_spec_boot_zero_retraces(self, tmp_path):
        """ISSUE 18 acceptance: an artifact saved from the PLAIN unified
        engine boots the spec engine with ZERO retraces — verify rows
        are prefill-chunk-shaped, so the closed bucket lattice already
        covers them (no new program family, no new bucket axis)."""
        from paddle_tpu.serving import AotArtifact

        # small pool bounds the bucket lattice the save compiles
        ref_eng = _engine(num_blocks=16, spec=SpecConfig(k=4))
        ref = _run(ref_eng, [LOOP_PROMPT], max_new=16)
        assert ref_eng.spec.drafted_total > 0
        d = str(tmp_path / "plain_unified")
        AotArtifact.save(_engine(num_blocks=16), d)  # spec OFF at save
        art = AotArtifact.load(d)
        eng = _engine(num_blocks=16, spec=SpecConfig(k=4), aot=art)
        outs = _run(eng, [LOOP_PROMPT], max_new=16)
        assert outs == ref
        assert (eng.ragged_trace_count == 0
                and eng.prefill_trace_count == 0
                and eng.decode_trace_count == 0)
        assert eng.spec.drafted_total > 0


# --- fleet: dp=1 vs dp=2 sampled identity -----------------------------------

def _fleet(dp, spec=None):
    def make(i, registry):
        return _engine(spec=spec, registry=registry,
                       labels={"replica": str(i)})
    return FleetRouter.build(make, dp=dp,
                             config=FleetConfig(max_queue=64)).start()


class TestFleetSampledIdentity:
    @pytest.mark.parametrize("spec_k", [None, 4])
    def test_dp2_matches_dp1(self, spec_k):
        spec = SpecConfig(k=spec_k) if spec_k else None
        outs = {}
        for dp in (1, 2):
            fleet = _fleet(dp, spec=spec)
            try:
                hs = [fleet.submit_request(
                    p, SamplingParams(max_new_tokens=8, **SAMPLED),
                    request_id=f"r{i}") for i, p in enumerate(PROMPTS)]
                fleet.wait(hs, timeout=600)
                outs[dp] = [list(h.req.output_tokens) for h in hs]
            finally:
                fleet.stop()
        assert outs[1] == outs[2]
        assert all(len(t) == 8 for t in outs[1])


# --- cross-process: mp=2 multi-chip worker (satellite 2 smoke) ---------------

@pytest.mark.slow
class TestMultiChipWorker:
    def test_mp2_worker_spec_over_wire(self):
        """A worker process running mp=2 (forced-host-device CPU) with
        spec decoding: deploy identity over the handshake, greedy +
        seeded-sampled tokens over the wire (deterministic on
        resubmit), spec counters merged at the router, and a
        wrong-deploy dial refused with the typed ``deploy_mismatch``
        while the worker keeps serving."""
        from paddle_tpu.serving.procfleet import (
            ProcessFleet,
            ProcessFleetConfig,
        )

        cfg = ProcessFleetConfig(
            dp=1, layers=1, num_blocks=32, block_size=4, max_num_seqs=4,
            max_prefill_tokens_per_step=8, max_tokens_per_step=16,
            unified=True, mp=2, spec={"k": 4}, boot_timeout_s=300.0)
        pf = ProcessFleet(cfg)
        router = pf.router
        try:
            router.start()
            proxy = router.replicas[0].engine
            assert proxy.mp == 2
            desc = proxy.debug_fetch("describe")
            assert desc["deploy"] == {
                "mp": 2, "spec": {"enabled": 1, "k": 4, "ngram": 3,
                                  "min_ngram": 1, "window": 256}}
            h1 = router.submit_request(
                LOOP_PROMPT, SamplingParams(max_new_tokens=8),
                request_id="greedy")
            h2 = router.submit_request(
                LOOP_PROMPT, SamplingParams(max_new_tokens=8, **SAMPLED),
                request_id="sampled-a")
            router.wait([h1, h2], timeout=600)
            assert len(h1.req.output_tokens) == 8
            assert len(h2.req.output_tokens) == 8
            h3 = router.submit_request(
                LOOP_PROMPT, SamplingParams(max_new_tokens=8, **SAMPLED),
                request_id="sampled-b")
            router.wait([h3], timeout=600)
            assert list(h3.req.output_tokens) \
                == list(h2.req.output_tokens)
            drafted = sum(
                r.get("value", 0) for r in
                wire.dump_registry(router.registry)
                if r["name"] == "serving_spec_draft_tokens_total")
            assert drafted > 0
            # typed refusal: a default-deploy peer must not connect
            port = router.replicas[0].engine.worker.port
            with pytest.raises(wire.HandshakeMismatch) as ei:
                wire.connect("127.0.0.1", port, role="control",
                             aot_hash=None,
                             deploy={"mp": 1, "spec": None})
            assert ei.value.code == "deploy_mismatch"
            # the worker survived the refusal and keeps serving
            h4 = router.submit_request(
                LOOP_PROMPT, SamplingParams(max_new_tokens=2),
                request_id="after-refusal")
            router.wait([h4], timeout=600)
            assert len(h4.req.output_tokens) == 2
        finally:
            router.stop()
            pf.shared.close_all()
