"""The rules of running ahead (ISSUE 35; the tokens are in
``test_zzzzzzzzzzzzzzzzz_run_ahead.py``, whose opening lists the contract):
every boundary settles first and is counted under its reason, a bare
``step()`` keeps the contract the benchmark's reference check stands on, and
``scheduler.plan_ahead`` says why before it changes anything.  Counts and
identities only, never a time.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.audit import AuditConfig
from paddle_tpu.serving import SamplingParams, SchedulerConfig
from paddle_tpu.serving.faultinject import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)
from run_ahead_common import (  # noqa: F401  (``models`` is a fixture)
    BS,
    KINDS,
    ahead_counts,
    assert_clean,
    drive,
    make_engine,
    models,
    outputs,
    prompt_of,
)

# --- (3) the boundaries: each settles first, under its reason -----------------

class TestBoundaries:
    def test_an_admission_settles_first(self, models):
        eng = make_engine(models("llama"))
        a = eng.add_request(prompt_of(9, 0), SamplingParams(max_new_tokens=12))
        for _ in range(4):
            eng.step_ahead()
        assert eng._inflight is not None
        before = ahead_counts(eng)
        b = eng.add_request(prompt_of(7, 1), SamplingParams(max_new_tokens=3))
        eng.step_ahead()
        after = ahead_counts(eng)
        assert after["settles"].get("admit", 0) == \
            before["settles"].get("admit", 0) + 1
        assert after["launches"] == before["launches"]
        assert len(b.output_tokens) == 1            # prefilled in that step
        # and the decode of that step is in flight again, the new row not
        # yet in it
        assert eng._inflight is not None
        assert eng._inflight.rids == (a.request_id,)
        eng.run()

    def test_a_queue_that_cannot_be_admitted_does_not_stand_in_the_way(
            self, models):
        """The decode cell's shape: ``waiting`` is never empty, the running
        set is full.  The loop runs ahead all the same."""
        eng = make_engine(models("llama"), max_num_seqs=2)
        for i in range(4):
            eng.add_request(prompt_of(8, i), SamplingParams(max_new_tokens=10))
        for _ in range(3):
            eng.step_ahead()
        assert eng.scheduler.queue_depth == 2
        before = ahead_counts(eng)
        for _ in range(4):
            eng.step_ahead()
            assert eng.scheduler.queue_depth == 2
        after = ahead_counts(eng)
        assert after["launches"] == before["launches"] + 4
        assert after["settles"] == before["settles"]
        eng.run()
        assert_clean(eng)

    @pytest.mark.parametrize("kind", KINDS)
    def test_a_preemption_under_a_full_pool_settles_first(self, models, kind):
        """A pool too small for its rows, a queue that is never empty:
        preemption by recompute, and the tokens of the synchronous
        order."""
        arrivals = [(0, prompt_of(8, i), 14) for i in range(5)]
        kw = dict(max_num_seqs=3, num_blocks=14)

        def serve(ahead):
            eng = make_engine(models(kind), kind, **kw)
            return eng, outputs(drive(eng, ahead, arrivals=arrivals))

        ref, want = serve(False)
        eng, got = serve(True)
        assert got == want
        assert ref.metrics.counters["preemptions"] > 0
        assert eng.metrics.counters["preemptions"] == \
            ref.metrics.counters["preemptions"]
        counts = ahead_counts(eng)
        assert counts["settles"]["preempt"] >= 1
        assert counts["launches"] > 0 and counts["dropped"] == 0
        assert_clean(eng)

    def test_a_prompt_still_to_compute_settles_first(self, models):
        eng = make_engine(models("llama"), scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens_per_step=4))
        ref = make_engine(models("llama"), scheduler=SchedulerConfig(
            max_num_seqs=4, max_prefill_tokens_per_step=4))
        arrivals = [(0, prompt_of(6, 0), 12), (5, prompt_of(14, 1), 6)]
        assert outputs(drive(eng, True, arrivals=arrivals)) == \
            outputs(drive(ref, False, arrivals=arrivals))
        # the second prompt takes four chunks: one admission, then a
        # continuation pending in each of the next three steps
        assert ahead_counts(eng)["settles"]["prefill"] == 3
        assert_clean(eng)

    def test_an_abort_of_a_row_in_flight(self, models):
        """Its blocks are free at once, its token is dropped at the read;
        the other rows go on as if nothing had happened."""
        arrivals = [(0, prompt_of(9, i), 12) for i in range(3)]
        want = outputs(drive(make_engine(models("llama")), False,
                             arrivals=arrivals))
        eng = make_engine(models("llama"))
        seen = {}

        def between(n, reqs):
            if n == 6:
                victim = reqs[1]
                assert eng._inflight is not None
                assert victim.request_id in eng._inflight.rids
                seen["had"] = len(victim.output_tokens)
                assert eng.abort_request(victim.request_id)
                assert not eng.kv.has(victim.request_id)    # at once
                assert eng._inflight is not None            # not read

        got = drive(eng, True, arrivals=arrivals, between=between)
        assert got[1].finish_reason.value == "abort"
        assert len(got[1].output_tokens) == seen["had"]     # none after
        assert outputs(got)[0] == want[0] and outputs(got)[2] == want[2]
        assert outputs(got)[1] == want[1][:seen["had"]]
        assert ahead_counts(eng)["dropped"] == 1
        assert_clean(eng)

    def test_aborting_every_row_lets_the_launch_go(self, models):
        eng = make_engine(models("llama"))
        reqs = [eng.add_request(prompt_of(9, i),
                                SamplingParams(max_new_tokens=12))
                for i in range(2)]
        for _ in range(5):
            eng.step_ahead()
        assert eng._inflight is not None
        for r in reqs:
            eng.abort_request(r.request_id)
        assert eng._inflight is None and not eng.scheduler.has_work()
        assert ahead_counts(eng)["dropped"] == 2
        assert_clean(eng)

    def test_a_kv_export_settles_first(self, models):
        """What reads the engine's state between two steps (the posted
        tasks: KV export, import, detach) finds nothing in flight."""
        eng = make_engine(models("llama"))
        req = eng.add_request(prompt_of(13, 0),
                              SamplingParams(max_new_tokens=12))
        for _ in range(4):
            eng.step_ahead()
        assert eng._inflight is not None
        had = len(req.output_tokens)
        run = eng.export_kv_run(req.request_id)
        assert eng._inflight is None
        assert len(req.output_tokens) == had + 1    # the launch was read
        assert run is not None and len(run["blocks"]) == 13 // BS
        assert ahead_counts(eng)["settles"] == {"task": 1}
        assert eng.detach_request(req.request_id)
        assert_clean(eng)

    def test_a_step_the_audit_samples_settles_first(self, models):
        audit = dict(audit=AuditConfig(enabled=True, sample_every=4))
        want = outputs(drive(make_engine(models("llama")), False))
        eng = make_engine(models("llama"), **audit)
        sampled_in_flight = []
        launch = eng._decode_ahead

        def spy(reqs, flying):
            out = launch(reqs, flying)
            if eng.audit.sampled:
                sampled_in_flight.append(eng._inflight)
            return out

        eng._decode_ahead = spy
        assert outputs(drive(eng, True)) == want
        counts = ahead_counts(eng)
        assert counts["settles"]["audit"] >= 3
        assert counts["launches"] > 5
        # a sampled step's decode launch is read in that step
        assert sampled_in_flight and not any(sampled_in_flight)
        snap = eng.audit.snapshot()
        assert sum(snap["audited_launches"].values()) > 0
        assert not any(snap["divergences"].values())
        assert_clean(eng)

    def test_a_planned_fault_settles_first(self, models):
        want = outputs(drive(make_engine(models("llama")), False))
        eng = make_engine(models("llama"))
        eng.set_fault_injector(FaultInjector(FaultPlan(faults=(
            FaultSpec(point="pool_exhaust", step=8, replica="0"),)), "0"))
        assert outputs(drive(eng, True)) == want
        assert ahead_counts(eng)["settles"]["fault"] == 1
        assert_clean(eng)

    @pytest.mark.parametrize("family,eng_kw,sched_kw", [
        ("ragged", {"unified_step": True}, {"max_tokens_per_step": 16}),
        ("burst", {"burst_steps": 4}, {})])
    def test_a_family_without_the_path_never_leaves_a_launch(
            self, models, family, eng_kw, sched_kw):
        def serve(ahead):
            eng = make_engine(models("llama"), scheduler=SchedulerConfig(
                max_num_seqs=8, **sched_kw), **eng_kw)
            seen = []
            got = outputs(drive(eng, ahead, between=lambda n, reqs:
                                seen.append(eng._inflight)))
            return eng, got, seen

        ref, want, _ = serve(False)
        eng, got, seen = serve(True)
        assert got == want
        assert not any(seen)
        counts = ahead_counts(eng)
        assert counts["launches"] == 0 and counts["dropped"] == 0
        # every step that had decode rows, whatever program they rode
        assert set(counts["settles"]) == {"family"}
        assert counts["settles"]["family"] >= 6
        assert_clean(eng)

    def test_tokens_committed_to_a_device_are_read_at_once(self):
        """Weights placed with an explicit device commit every output to
        it; an ids array made from such tokens would re-lower each decode
        program (a host array and an uncommitted one lower alike), so
        such an engine never leaves a launch in flight."""
        import jax

        def engine(commit):
            paddle.seed(0)
            model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
            if commit:
                for p in model.parameters():
                    p._value = jax.device_put(p._value, jax.devices()[0])
            return make_engine(model)

        want = outputs(drive(engine(False), False))
        eng = engine(True)
        seen = []
        assert outputs(drive(eng, True, between=lambda n, reqs:
                             seen.append(eng._inflight))) == want
        assert not any(seen)
        counts = ahead_counts(eng)
        assert counts["launches"] == 0
        assert set(counts["settles"]) == {"family"}
        assert_clean(eng)

    def test_a_bare_step_reads_what_the_loop_left(self, models):
        eng = make_engine(models("llama"))
        req = eng.add_request(prompt_of(9, 0),
                              SamplingParams(max_new_tokens=12))
        for _ in range(4):
            eng.step_ahead()
        assert eng._inflight is not None
        had = len(req.output_tokens)
        emitted = eng.step()
        assert eng._inflight is None
        # the launch it found and its own: two tokens, the last returned
        assert len(req.output_tokens) == had + 2
        assert emitted == {req.request_id: req.output_tokens[-1]}
        assert ahead_counts(eng)["settles"] == {"bare": 1}
        eng.run()
        assert_clean(eng)

    def test_nothing_is_compiled_in_a_step(self, models):
        """``warm_ahead`` compiles the two small programs for every row
        bucket; serving then adds none."""
        from paddle_tpu.serving.engine import _ids_program, _pad_tokens

        eng = make_engine(models("llama"), max_num_seqs=8)
        eng.warm_ahead()
        sizes = _ids_program._cache_size(), _pad_tokens._cache_size()
        # row buckets 1, 2, 4, 8; the widest needs no padding
        assert sizes[0] >= 4 and sizes[1] >= 3
        drive(eng, True)
        assert (_ids_program._cache_size(),
                _pad_tokens._cache_size()) == sizes


# --- (4) the benchmark's reference check: bare step() -------------------------

class TestBareStepContract:
    @pytest.mark.parametrize("kind", KINDS)
    def test_steps_plus_one_launches_for_steps_plus_one_tokens(self, models,
                                                               kind):
        steps = 5
        eng = make_engine(models(kind), kind)
        launches, orig = [], eng._step_call

        def call(program, bucket, fn, *args):
            out = orig(program, bucket, fn, *args)
            # ids, lens and tables as the benchmark's probe reads them
            assert isinstance(args[3], np.ndarray)
            launches.append(program)
            return out

        eng._step_call = call
        req = eng.add_request(prompt_of(10, 0), SamplingParams(
            max_new_tokens=steps + 1, temperature=0.0))
        for _ in range(steps + 8):
            if req.finished:
                break
            before = len(req.output_tokens)
            emitted = eng.step()
            assert eng._inflight is None
            assert len(req.output_tokens) == before + 1
            assert emitted == {req.request_id: req.output_tokens[-1]}
        assert req.finished
        assert launches == ["prefill"] + ["decode"] * steps
        assert ahead_counts(eng) == {"launches": 0, "dropped": 0,
                                     "settles": {}}

    def test_every_launch_passes_step_call_with_host_lens_and_tables(
            self, models):
        """What the benchmark's probe takes from a launch that ran ahead:
        ``ids`` of the bucket's shape (a device array), ``lens`` and
        ``tables`` host arrays, the 5-tuple back."""
        eng = make_engine(models("llama"))
        seen, orig = [], eng._step_call

        def call(program, bucket, fn, *args):
            out = orig(program, bucket, fn, *args)
            if program == "decode":
                ids, _, tables, lens = args[3:7]
                assert np.shape(ids) == (bucket[0], 1)
                assert ids.dtype == np.int64
                assert isinstance(tables, np.ndarray)
                assert isinstance(lens, np.ndarray)
                assert len(out) == 5
                seen.append(isinstance(ids, np.ndarray))
            return out

        eng._step_call = call
        drive(eng, True)
        assert seen.count(False) == ahead_counts(eng)["launches"] > 0
        assert seen.count(True) > 0


# --- the scheduler's half ------------------------------------------------------

class TestPlanAhead:
    def _flying(self, models, **kw):
        eng = make_engine(models("llama"), **kw)
        reqs = [eng.add_request(prompt_of(8, i),
                                SamplingParams(max_new_tokens=10))
                for i in range(3)]
        for _ in range(5):
            eng.step_ahead()
        assert eng._inflight is not None
        return eng, reqs

    def _state(self, eng):
        kv = eng.kv
        return (list(kv._free), {k: list(v) for k, v in kv._tables.items()},
                dict(kv._lens), list(eng.scheduler.running),
                list(eng.scheduler.waiting),
                eng.scheduler.tokens_planned)

    def test_it_says_why_before_it_changes_anything(self, models):
        eng, reqs = self._flying(models)
        eng.add_request(prompt_of(5, 9), SamplingParams(max_new_tokens=2))
        before = self._state(eng)
        plan, why = eng.scheduler.plan_ahead(eng._inflight.reqs)
        assert plan is None and why == "admit"
        assert self._state(eng) == before
        eng.run()

    def test_a_continuation_counts_the_token_in_flight(self, models):
        eng, reqs = self._flying(models)
        lens = {r.request_id: eng.kv.seq_len(r.request_id) for r in reqs}
        # the launch in flight is counted: one past prompt + output - 1
        for r in reqs:
            assert lens[r.request_id] == \
                len(r.prompt_ids) + len(r.output_tokens)
        plan, why = eng.scheduler.plan_ahead(eng._inflight.reqs)
        assert why == "" and plan.decodes == sorted(
            reqs, key=lambda r: r.preempt_key)
        assert not (plan.prefills or plan.admitted or plan.preempted)
        for r in reqs:
            pos = lens[r.request_id]
            table = eng.kv.table(r.request_id)
            assert r._slot == (table[pos // BS], pos % BS)

    def test_a_row_at_its_last_token_gets_no_slot(self, models):
        eng = make_engine(models("llama"))
        short = eng.add_request(prompt_of(8, 0),
                                SamplingParams(max_new_tokens=3))
        long = eng.add_request(prompt_of(8, 1),
                               SamplingParams(max_new_tokens=9))
        while len(short.output_tokens) < 2:
            eng.step_ahead()
        assert short.request_id in eng._inflight.rids
        plan, why = eng.scheduler.plan_ahead(eng._inflight.reqs)
        assert why == "" and plan.decodes == [long]
        eng._inflight = None    # the plan was taken outside a step
