"""Device-resident decode bursts (ISSUE 19).

The contract under test: when the running set is a decode-only resident
cohort, ONE compiled program runs up to N decode steps on-device
(in-trace KV append, per-row position advance, fused sampling, EOS
masking) and the host sees only the ``[B, N]`` token buffer — with
burst-on **bit-identical** to per-step decode for greedy AND
seeded-sampled streams, strictly fewer host round-trips, a bounded
two-axis bucket lattice enumerated into the AOT artifact (zero-retrace
boot), the scheduled-token ledger EXACT, and the headroom clamp fed by
the ONE ``KVCacheManager.burst_capacity`` accessor the scheduler also
plans with.  Cross-process, the ``step_done`` frame's batched
``emitted`` map ships a whole burst in one wire round-trip and the
kill -9 chaos guarantees (zero lost, token identity) must hold with
bursts armed.

(Named ``zzzzzzzzz`` — 9 z's — to sort after
``test_zzzzzzzz_spec_sampling.py``: the tier-1 suite overruns its
timeout, so new dots must only append.)
"""

import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import topology
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.decode_burst import burst_oracle, run_burst
from paddle_tpu.serving import (
    AotArtifact,
    EngineConfig,
    EngineCore,
    ProcessFleet,
    ProcessFleetConfig,
    SamplingParams,
    SchedulerConfig,
    SupervisorConfig,
)
from paddle_tpu.serving import wire
from paddle_tpu.serving.burst import burst_eligible, clamp_burst
from paddle_tpu.serving.kv_manager import KVCacheManager
from paddle_tpu.serving.spec import SpecConfig

_RNG = np.random.default_rng(3)
PREFIX = _RNG.integers(0, 256, 8).tolist()
PROMPTS = [_RNG.integers(0, 256, 6).tolist() for _ in range(3)]
SAMPLED = dict(temperature=0.8, top_k=20, top_p=0.9, seed=1234)


# --- the ONE headroom accessor (satellite bugfix) ----------------------------

class TestBurstCapacity:
    def test_math_matches_worst_case(self):
        kv = KVCacheManager(num_blocks=16, block_size=4)
        # 15 usable blocks (block 0 is the null page)
        assert kv.burst_capacity(1) == 15 * 4 + 1
        assert kv.burst_capacity(3) == 5 * 4 + 1
        assert kv.burst_capacity(0) == 0
        assert kv.burst_capacity(-2) == 0

    def test_scheduler_plan_carries_it(self):
        """The scheduler computes ``plan.burst_capacity`` from the SAME
        accessor AFTER reserving this step's decode slots — the clamp
        can trust it unconditionally."""
        paddle.seed(0)
        topology.set_mesh(None)
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
        eng = EngineCore(model, config=EngineConfig(
            num_blocks=16, block_size=4,
            scheduler=SchedulerConfig(max_num_seqs=2)))
        eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=2))
        eng.step()  # prefill
        plan = eng.scheduler.schedule()
        assert plan.decodes
        assert plan.burst_capacity \
            == eng.kv.burst_capacity(len(plan.decodes))
        assert plan.burst_capacity >= 2


class TestClampAndEligibility:
    class _Req:
        def __init__(self, max_new, emitted):
            from types import SimpleNamespace
            self.sampling = SimpleNamespace(max_new_tokens=max_new)
            self.output_tokens = [0] * emitted

    def test_clamp_is_min_of_three(self):
        rows = [self._Req(16, 4), self._Req(16, 10)]  # remaining: 12, 6
        assert clamp_burst(8, rows, 100) == 6
        assert clamp_burst(4, rows, 100) == 4
        assert clamp_burst(8, rows, 3) == 3
        assert clamp_burst(8, rows, 1) == 0     # < 2: not worth it
        assert clamp_burst(1, rows, 100) == 0   # config below threshold
        assert clamp_burst(8, [], 100) == 0

    def test_eligibility_gates(self):
        from types import SimpleNamespace
        sched = SimpleNamespace(waiting=[], running=[],
                                _needs_prefill=lambda r: False)
        plan = SimpleNamespace(prefills=[])
        rows = [object()]
        assert burst_eligible(sched, plan, rows, None)
        assert not burst_eligible(sched, plan, rows, object())   # spec on
        assert not burst_eligible(sched, plan, [], None)         # no rows
        assert not burst_eligible(
            sched, SimpleNamespace(prefills=[object()]), rows, None)
        sched.waiting = [object()]
        assert not burst_eligible(sched, plan, rows, None)
        sched.waiting = []
        sched.running = [object()]
        sched._needs_prefill = lambda r: True   # deferred chunk pending
        assert not burst_eligible(sched, plan, rows, None)


# --- kernel parity: run_burst vs the eager oracle ----------------------------

_V = 17


def _toy_model_step(ids, pos, lens, sb, so, kp, vp):
    """A stand-in decode forward: writes the input token's 'KV' into the
    routed slot and emits logits that depend on token, position, and the
    written cell — so any drift in the loop's KV routing, position
    advance, or feedback token shows up in the parity diff."""
    k = kp[0].at[sb, so].set(ids[:, 0].astype(jnp.float32) + 0.25
                             * pos.astype(jnp.float32))
    v = vp[0].at[sb, so].set(ids[:, 0].astype(jnp.float32) * 2.0)
    base = (ids[:, 0][:, None].astype(jnp.float32)
            * jnp.arange(_V, dtype=jnp.float32)[None, :] * 0.03
            + pos[:, None].astype(jnp.float32) * 0.011
            + lens[:, None].astype(jnp.float32) * 0.007)
    acc = k[sb, so][:, None] * 0.002
    return jnp.sin(base + acc).astype(jnp.float32), [k], [v]


def _burst_args(B, Nb, rng, sampled_rows=(), eos=None):
    """One lattice point's argument set: every row active, slots routed
    into a [64, 4]-shaped pool, sampling quartet mixing greedy and
    sampled rows."""
    ids = jnp.asarray(rng.integers(1, _V, (B, 1)), jnp.int32)
    pos = jnp.asarray(rng.integers(2, 6, B), jnp.int32)
    lens = pos + 1
    active = jnp.ones((B,), bool)
    eos_ids = jnp.full((B,), -1 if eos is None else eos, jnp.int32)
    blocks = rng.choice(np.arange(1, 64), size=(B, Nb), replace=False) \
        if B * Nb < 63 else rng.integers(1, 64, (B, Nb))
    slot_blocks = jnp.asarray(blocks, jnp.int32)
    slot_offsets = jnp.asarray(rng.integers(0, 4, (B, Nb)), jnp.int32)
    temps = np.zeros(B, np.float32)
    for r in sampled_rows:
        temps[r] = 0.8
    top_ks = jnp.full((B,), 5, jnp.int32)
    top_ps = jnp.full((B,), 0.9, jnp.float32)
    keys = jnp.asarray(
        np.stack([np.full(B, 77, np.uint32),
                  rng.integers(0, 9, B).astype(np.uint32)], axis=1))
    k_pools = [jnp.zeros((64, 4), jnp.float32)]
    v_pools = [jnp.zeros((64, 4), jnp.float32)]
    return (ids, pos, lens, active, eos_ids, slot_blocks, slot_offsets,
            jnp.asarray(temps), top_ks, top_ps, keys, k_pools, v_pools)


class TestKernelParity:
    @pytest.mark.parametrize("B,Nb", [(1, 2), (2, 4), (4, 8)])
    def test_lattice_sweep_vs_oracle(self, B, Nb):
        """Jitted fori_loop burst == eager per-step oracle over the
        (rows x burst-length) lattice, with greedy and sampled rows side
        by side and n_steps clamped below the bucket width."""
        rng = np.random.default_rng(100 * B + Nb)
        args = _burst_args(B, Nb, rng, sampled_rows=range(0, B, 2))
        for n in {2, Nb}:
            fast = jax.jit(
                lambda *a: run_burst(_toy_model_step, *a),
                static_argnums=(1,))(jnp.int32(n), _V, *args)
            slow = burst_oracle(_toy_model_step, n, _V, *args)
            for f, s, what in [(fast[0], slow[0], "tokens"),
                               (fast[2][0], slow[2][0], "k_pool"),
                               (fast[3][0], slow[3][0], "v_pool")]:
                np.testing.assert_array_equal(
                    np.asarray(f), np.asarray(s),
                    err_msg=f"B={B} Nb={Nb} n={n}: {what} diverged")
            # the toy forward's sin() fuses differently under jit —
            # logits agree to float32 ULP, tokens/pools bit-exactly
            np.testing.assert_allclose(
                np.asarray(fast[1]), np.asarray(slow[1]),
                rtol=1e-6, atol=1e-6,
                err_msg=f"B={B} Nb={Nb} n={n}: last_logits diverged")

    def test_eos_emits_then_masks(self):
        """A row that samples its EOS emits it (per-step parity), then
        its remaining buffer lanes stay -1 and its KV stops moving."""
        rng = np.random.default_rng(9)
        args = _burst_args(2, 8, rng)
        probe = burst_oracle(_toy_model_step, 8, _V, *args)
        tok1 = int(np.asarray(probe[0])[0, 1])  # row 0's 2nd emission
        args = _burst_args(2, 8, np.random.default_rng(9), eos=tok1)
        buf, _, k_out, _ = burst_oracle(_toy_model_step, 8, _V, *args)
        fast = jax.jit(
            lambda *a: run_burst(_toy_model_step, *a),
            static_argnums=(1,))(jnp.int32(8), _V, *args)
        np.testing.assert_array_equal(np.asarray(fast[0]),
                                      np.asarray(buf))
        row0 = np.asarray(buf)[0]
        stop = int(np.argmax(row0 == tok1))
        assert (row0[stop + 1:] == -1).all()


# --- engine-level identity ---------------------------------------------------

def _engine(burst=0, unified=False, num_blocks=64, block_size=4,
            max_num_seqs=4, mp=1, **engine_kw):
    paddle.seed(0)
    if mp > 1:
        topology.init_mesh(mp=mp)
    else:
        topology.set_mesh(None)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    kw = {}
    if unified:
        kw["unified_step"] = True
        kw["scheduler"] = SchedulerConfig(max_num_seqs=max_num_seqs,
                                          max_tokens_per_step=16)
    else:
        kw["scheduler"] = SchedulerConfig(max_num_seqs=max_num_seqs)
    return EngineCore(model, config=EngineConfig(
        num_blocks=num_blocks, block_size=block_size,
        burst_steps=burst, **kw, **engine_kw))


def _run(eng, prompts, max_new=12, sampling=None, per_req=None):
    sp = sampling or {}
    reqs = [eng.add_request(
        p, SamplingParams(max_new_tokens=max_new,
                          **(per_req[i] if per_req else sp)))
        for i, p in enumerate(prompts)]
    eng.run(max_steps=4000)
    assert all(r.finished for r in reqs)
    return [list(r.output_tokens) for r in reqs]


def _roundtrips(eng):
    return int(eng._burst_counters["roundtrips"].value)


def _launches(eng):
    return int(eng._burst_counters["launches"].value)


class TestEngineIdentity:
    @pytest.mark.parametrize("unified", [False, True])
    def test_greedy_identity_fewer_roundtrips(self, unified):
        """The crisp ISSUE 19 contract in both engine modes: burst-on is
        token-identical with strictly fewer engine steps AND host
        round-trips, the trace count bounded by the burst bucket set,
        and the scheduled-token ledger EXACT."""
        base = _engine(unified=unified)
        plain = _run(base, PROMPTS, max_new=12)
        eng = _engine(burst=8, unified=unified)
        bursty = _run(eng, PROMPTS, max_new=12)
        assert bursty == plain
        assert _launches(eng) > 0
        assert int(eng._burst_counters["tokens"].value) > 0
        assert eng.metrics.counters["engine_steps"] \
            < base.metrics.counters["engine_steps"]
        assert _roundtrips(eng) < _roundtrips(base)
        assert eng.burst_trace_count <= len(eng.burst_buckets)
        assert eng.stepprof.scheduled_tokens() \
            == eng.scheduler.tokens_planned
        assert eng.kv.occupancy() == 0.0

    def test_sampled_and_mixed_identity(self):
        """Greedy and seeded-sampled rows side by side in one burst:
        each stream replays its burst-off twin bit-for-bit (the in-trace
        key advance lands on the same (seed, output position) draws)."""
        per_req = [{}, SAMPLED, dict(SAMPLED, seed=42)]
        plain = _run(_engine(), PROMPTS, max_new=12, per_req=per_req)
        eng = _engine(burst=8)
        bursty = _run(eng, PROMPTS, max_new=12, per_req=per_req)
        assert bursty == plain
        assert _launches(eng) > 0

    def test_sampled_rerun_deterministic(self):
        a = _run(_engine(burst=8), PROMPTS, sampling=SAMPLED)
        b = _run(_engine(burst=8), PROMPTS, sampling=SAMPLED)
        assert a == b

    def test_preemption_recompute_identity(self):
        """Pool pressure around bursts: preempted rows recompute and the
        stream still matches the calm burst-off run — and the clamp's
        capacity term kept every launch inside the pool (no mid-burst
        exhaustion, pool drained after)."""
        calm = _run(_engine(num_blocks=64), PROMPTS, max_new=8,
                    sampling=SAMPLED)
        tight = _engine(burst=8, num_blocks=10)
        squeezed = _run(tight, PROMPTS, max_new=8, sampling=SAMPLED)
        assert tight.metrics.counters["preemptions"] > 0
        assert squeezed == calm
        assert tight.kv.occupancy() == 0.0

    def test_warm_prefix_fork_identity(self):
        """A second wave forking a cached prefix decodes through bursts
        identically to the burst-off engine."""
        def wave(eng):
            first = _run(eng, [PREFIX + [3, 1, 4, 1]], max_new=4)
            second = _run(eng, [PREFIX + t for t in
                                ([9, 2, 6], [5, 3, 5], [8, 9, 7])],
                          max_new=8)
            assert eng.metrics.counters["prefix_cache_hit_tokens"] > 0
            return first + second

        plain = wave(_engine())
        eng = _engine(burst=8)
        assert wave(eng) == plain
        assert _launches(eng) > 0

    def test_mp2_identity(self):
        """The burst program dispatches through the mesh-spanning
        shardings: mp=2 burst-on equals mp=1 burst-on equals burst-off."""
        try:
            plain = _run(_engine(mp=1), PROMPTS, max_new=8)
            o1 = _run(_engine(burst=8, mp=1), PROMPTS, max_new=8)
            eng2 = _engine(burst=8, mp=2)
            o2 = _run(eng2, PROMPTS, max_new=8)
            assert _launches(eng2) > 0
        finally:
            topology.set_mesh(None)
        assert o1 == plain
        assert o2 == plain

    def test_never_bursts_when_spec_configured(self):
        """Spec drafting wins: an engine with BOTH armed drafts and
        never launches a burst (the proposer needs fresh host-side
        history every step — a resident burst would decode exactly the
        tokens it exists to skip)."""
        loop = [5, 6, 7, 8] * 3
        plain = _run(_engine(unified=True), [loop], max_new=16)
        eng = _engine(burst=8, unified=True, spec=SpecConfig(k=4))
        outs = _run(eng, [loop], max_new=16)
        assert outs == plain
        assert eng.spec.drafted_total > 0
        assert _launches(eng) == 0
        assert not eng.burst_buckets

    def test_never_bursts_with_prefill_pending(self):
        """Admission waves interleave prefills with decodes: every burst
        launch must have happened on a step with NO prefill work, so a
        late joiner is never starved behind a resident burst."""
        eng = _engine(burst=8, max_num_seqs=4)
        r1 = eng.add_request(PROMPTS[0],
                             SamplingParams(max_new_tokens=60))
        for _ in range(4):
            eng.step()
        assert not r1.finished
        assert _launches(eng) > 0   # solo cohort bursts
        launches_before = _launches(eng)
        # a waiting admission pins the engine back to per-step until the
        # newcomer is resident
        r2 = eng.add_request(PROMPTS[1],
                             SamplingParams(max_new_tokens=8))
        eng.step()
        assert _launches(eng) == launches_before
        eng.run(max_steps=4000)
        assert r1.finished and r2.finished


# --- AOT: the burst lattice rides the artifact (v3) --------------------------

class TestBurstFixedStreamCounts:
    """A decode-heavy stream, bursts off against up to 8 decode steps a
    launch, a greedy wave and then a seeded-sampled wave on the same
    engine: short prompts and long continuations, so after the brief
    admission window every step is burstable, and one short stream so
    the cohort shrinks mid-run and the row-bucket axis is exercised.
    The counts are exact on this stream."""

    @pytest.fixture(scope="class")
    def runs(self):
        rng = np.random.default_rng(0)
        prompts = [(rng.integers(0, 256, 6).tolist(), 24),
                   (rng.integers(0, 256, 6).tolist(), 24),
                   (rng.integers(0, 256, 8).tolist(), 24),
                   (rng.integers(0, 256, 8).tolist(), 12)]
        out = {}
        for burst in (0, 8):
            eng = _engine(burst=burst)
            waves, lost = [], 0
            for sp in ({}, SAMPLED):
                reqs = [eng.add_request(
                    p, SamplingParams(max_new_tokens=mx, **sp))
                    for p, mx in prompts]
                eng.run(max_steps=4000)
                lost += sum(not r.finished for r in reqs)
                waves.append([list(r.output_tokens) for r in reqs])
            out[burst] = {
                "outputs": waves, "lost": lost,
                "engine_steps": eng.metrics.counters["engine_steps"],
                "roundtrips": _roundtrips(eng),
                "launches": _launches(eng),
                "burst_tokens": int(eng._burst_counters["tokens"].value),
                "traces": eng.burst_trace_count,
                "buckets": sorted(eng.burst_buckets)}
        return out

    def test_token_identity_both_waves(self, runs):
        mismatches = sum(
            a != b for pw, bw in zip(runs[0]["outputs"],
                                     runs[8]["outputs"])
            for a, b in zip(pw, bw))
        assert mismatches == 0

    @pytest.mark.parametrize("burst,name,want", [
        (0, "lost", 0), (8, "lost", 0),
        (0, "engine_steps", 52), (8, "engine_steps", 20),
        (0, "roundtrips", 58), (8, "roundtrips", 26),
        (8, "launches", 6), (8, "burst_tokens", 136),
        # one trace per bucket of the two-axis lattice this stream meets
        (8, "traces", 2),
        (8, "buckets", [("burst", 4, 4), ("burst", 4, 8)])])
    def test_count(self, runs, burst, name, want):
        assert runs[burst][name] == want


class TestBurstAot:
    def test_save_load_zero_retrace_identity(self, tmp_path):
        """An artifact saved from a burst-armed engine enumerates the
        (rows x burst-length) lattice; a fresh engine booted from it
        bursts with ZERO retraces and bit-identical tokens."""
        ref_eng = _engine(burst=8, num_blocks=16)
        ref = _run(ref_eng, PROMPTS, max_new=12, sampling=SAMPLED)
        assert _launches(ref_eng) > 0
        d = str(tmp_path / "burst_aot")
        art = AotArtifact.save(_engine(burst=8, num_blocks=16), d,
                               max_seq_len=32)
        assert art.describe()["burst_steps"] == 8
        assert "burst" in art.bucket_sets
        eng = _engine(burst=8, num_blocks=16,
                      aot=AotArtifact.load(d))
        outs = _run(eng, PROMPTS, max_new=12, sampling=SAMPLED)
        assert outs == ref
        assert _launches(eng) > 0
        assert (eng.burst_trace_count == 0
                and eng.prefill_trace_count == 0
                and eng.decode_trace_count == 0)

    def test_burst_off_engine_boots_burst_on_artifact(self, tmp_path):
        """The manifest's burst_steps is NOT a validate-mismatch row: a
        burst-off engine just ignores the artifact's extra burst
        programs (the coverage check is one-directional)."""
        d = str(tmp_path / "burst_aot2")
        AotArtifact.save(_engine(burst=4, num_blocks=16), d,
                         max_seq_len=32)
        eng = _engine(burst=0, num_blocks=16, aot=AotArtifact.load(d))
        outs = _run(eng, [PROMPTS[0]], max_new=6)
        assert len(outs[0]) == 6
        assert _launches(eng) == 0


# --- cross-process: one wire round-trip per burst, kill -9 mid-burst ---------

class TestProcfleetBurst:
    def _cfg(self, aot_path, burst, dp=1):
        return ProcessFleetConfig(
            dp=dp, layers=1, num_blocks=32, block_size=4,
            max_num_seqs=4, max_prefill_tokens_per_step=None,
            burst_steps=burst, aot_path=aot_path,
            heartbeat_interval_s=0.1, heartbeat_timeout_s=1.0)

    @pytest.fixture(scope="class")
    def burst_aot(self, tmp_path_factory):
        path = str(tmp_path_factory.mktemp("burstfleet") / "aot")
        AotArtifact.save(_engine(burst=8, num_blocks=32), path,
                         max_seq_len=32)
        return path

    def test_batched_step_done_identity(self, burst_aot):
        """A burst-armed worker ships whole bursts through the
        ``step_done`` frame's ``emitted`` map: token identity with the
        burst-off fleet, fewer engine round-trips, burst counters
        merged at the router, and the describe surface exposes the
        burst trace count (zero off the artifact)."""
        outs = {}
        steps = {}
        for burst in (0, 8):
            pf = ProcessFleet(self._cfg(burst_aot, burst))
            router = pf.router
            try:
                router.start()
                hs = [router.submit_request(
                    p, SamplingParams(max_new_tokens=12, **SAMPLED),
                    request_id=f"r{i}") for i, p in enumerate(PROMPTS)]
                router.wait(hs, timeout=600)
                outs[burst] = [list(h.req.output_tokens) for h in hs]
                steps[burst] = _csum(router.registry,
                                     "serving_engine_steps_total")
                if burst:
                    assert _csum(router.registry,
                                 "serving_burst_launches_total") > 0
                    assert _csum(router.registry,
                                 "serving_burst_tokens_total") > 0
                    desc = pf.proxy(0).debug_fetch("describe")
                    assert desc["traces"]["burst"] == 0
            finally:
                pf.stop()
        assert outs[8] == outs[0]
        assert all(len(t) == 12 for t in outs[8])
        assert steps[8] < steps[0]

    def test_kill9_mid_burst_zero_loss_identity(self, burst_aot):
        """kill -9 a burst-armed worker mid-stream at dp=2: reroute +
        respawn onto the shared artifact, ZERO lost requests, token
        identity with the fault-free burst run — a died-mid-burst
        request recomputes and replays the same stream."""
        prompts = [PREFIX + _RNG.integers(0, 256, 4).tolist()
                   for _ in range(6)]

        def run(kill):
            pf = ProcessFleet(self._cfg(burst_aot, burst=8, dp=2))
            pf.supervise(SupervisorConfig(
                backoff_initial_s=0.02, backoff_max_s=0.5,
                poll_interval_s=0.01))
            pf.start()
            router = pf.router
            try:
                hs = [router.submit_request(
                    p, SamplingParams(max_new_tokens=16),
                    request_id=f"k{i}", retryable=True)
                    for i, p in enumerate(prompts)]
                if kill:
                    time.sleep(0.15)
                    victim = next(r.index for r in router.replicas
                                  if r.in_flight)
                    os.kill(pf.worker_pid(victim), signal.SIGKILL)
                router.wait(hs, timeout=300)
                lost = [h.rid for h in hs
                        if h.finish_reason != "length"]
                assert not lost, f"requests lost: {lost}"
                assert _csum(router.registry,
                             "serving_burst_launches_total") > 0
                return {h.rid: list(h.output_tokens) for h in hs}
            finally:
                pf.stop()

        clean = run(kill=False)
        chaos = run(kill=True)
        mismatched = [rid for rid in clean if chaos[rid] != clean[rid]]
        assert not mismatched, \
            f"token identity broken after kill -9: {mismatched}"


def _csum(registry, name, **match) -> float:
    total = 0.0
    for row in wire.dump_registry(registry):
        if row["name"] != name:
            continue
        lbls = dict(row["labels"])
        if all(lbls.get(k) == v for k, v in match.items()):
            total += row.get("value", 0.0)
    return total
