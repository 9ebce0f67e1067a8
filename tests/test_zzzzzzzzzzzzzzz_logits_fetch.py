"""A launch brings back its tokens, not its logits (ISSUE 30).

The contract under test, for ``EngineCore._launch``:

* with the audit off, in each of the five program families, the emitted
  tokens -- greedy and seeded sampling -- equal those of a launch that
  copies the whole ``[rows, vocab]`` float32 output to the host first and
  the tokens after it (the launch this one replaces, kept here as the
  reference); ``_launch`` hands back the device array the program
  returned and no host copy, and ``serving_logits_fetches_total`` stays 0;
* with ``AuditConfig(sample_every=3)`` the counter equals the sampled
  decode / ragged launches, the rows copied are the real rows
  (``4 * vocab * B`` bytes a launch), the shadow compare passes on a clean
  run and the served tokens are those of the audit-off run;
* ``kernel_corrupt`` still trips the divergence net on a sampled step and
  leaves the served tokens alone;
* a non-finite row still yields a bundle whose ``primary_logits`` are that
  launch's real rows, fetched for the bundle alone on a step the schedule
  does not sample;
* nothing keeps the device array past its step.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.audit import AuditConfig, load_repro
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.ops import pallas_paged
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    SamplingParams,
    SchedulerConfig,
)
from paddle_tpu.serving.faultinject import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
)

BS = 4
# family -> (engine settings, scheduler settings)
FAMILIES = {
    "prefill": ({}, {}),
    "chunk": ({}, {"max_prefill_tokens_per_step": 8}),
    "decode": ({}, {}),
    "ragged": ({"unified_step": True}, {"max_tokens_per_step": 16}),
    "burst": ({"burst_steps": 4}, {}),
}
SAMPLINGS = {
    "greedy": {},
    "seeded": dict(temperature=0.8, top_k=20, top_p=0.9, seed=1234),
}
PROMPTS = [[5, 6, 7, 8] * 3, [40, 2, 11, 40, 2, 11, 40, 2], [9, 1, 4]]
SHADOWED = ("decode", "ragged")


def _engine(family="decode", **kw):
    paddle.seed(0)
    eng_kw, sched_kw = FAMILIES[family]
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    return EngineCore(model, config=EngineConfig(
        num_blocks=64, block_size=BS,
        scheduler=SchedulerConfig(max_num_seqs=4, **sched_kw),
        **eng_kw, **kw), registry=MetricsRegistry())


def _serve(eng, sampling=None, max_new=6):
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=max_new,
                                              **(sampling or {})))
            for p in PROMPTS]
    eng.run(max_steps=2000)
    assert all(r.finished for r in reqs)
    return [list(r.output_tokens) for r in reqs]


def _fetch_counts(eng):
    c = eng._burst_counters
    return (int(c["logits_fetches"].value),
            int(c["logits_fetch_bytes"].value))


def _copy_logits_first(eng):
    """Make ``eng`` launch as the engine did before ISSUE 30: the whole
    ``[rows, vocab]`` float32 output is copied to the host as soon as the
    program is dispatched, the tokens only after it has arrived.  Returns
    the list the host copies are appended to."""
    copies = []
    step_call = eng._step_call

    def call(program, bucket, jit_fn, *args):
        toks, logits, stats, k_pools, v_pools = step_call(
            program, bucket, jit_fn, *args)
        copies.append((program, np.asarray(logits, np.float32)))
        return (jnp.asarray(np.asarray(toks, np.int32)), logits, stats,
                k_pools, v_pools)

    eng._step_call = call
    return copies


def _spy_launch(eng):
    """Record ``(program, rows, step sampled?, logits handed back)`` of
    every ``_launch``."""
    seen = []
    launch = eng._launch

    def spy(program, bucket, jit_fn, args, rows):
        out = launch(program, bucket, jit_fn, args, rows)
        seen.append((program, rows, eng.audit.sampled, out[1]))
        return out

    eng._launch = spy
    return seen


class TestAuditOff:
    @pytest.mark.parametrize("sampling", sorted(SAMPLINGS))
    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_tokens_are_those_of_the_launch_that_copied_logits(
            self, family, sampling):
        ref = _engine(family)
        copies = _copy_logits_first(ref)
        want = _serve(ref, SAMPLINGS[sampling])
        eng = _engine(family)
        got = _serve(eng, SAMPLINGS[sampling])
        assert got == want
        assert family in {p for p, _ in copies}
        assert _fetch_counts(eng) == (0, 0)
        assert eng.decode_trace_count == ref.decode_trace_count
        assert eng.prefill_trace_count == ref.prefill_trace_count

    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_launch_hands_back_the_device_array(self, family):
        eng = _engine(family)
        seen = _spy_launch(eng)
        out = _serve(eng)
        vocab = eng.model.config.vocab_size
        assert family in {p for p, *_ in seen}
        for program, rows, sampled, logits in seen:
            assert isinstance(logits, jax.Array), (program, type(logits))
            assert not isinstance(logits, np.ndarray)
            assert logits.shape[-1] == vocab and logits.dtype == jnp.float32
            assert not sampled
        assert _fetch_counts(eng) == (0, 0)
        assert sum(map(len, out)) == 6 * len(PROMPTS)

    def test_greedy_tokens_are_the_argmax_of_the_device_logits(self):
        """The tokens come from the logits the launch no longer copies:
        the argmax of each real row, read here from the array handed
        back, is the token the row was served."""
        eng = _engine("decode")
        seen = _spy_launch(eng)
        served = []
        emit = eng._emit_device
        eng._emit_device = lambda req, tok: (served.append(tok),
                                             emit(req, tok))[1]
        _serve(eng)
        want = []
        for program, rows, _, logits in seen:
            host = np.asarray(logits, np.float32).reshape(-1, logits.shape[-1])
            want += [int(t) for t in host[:rows].argmax(-1)]
        assert served == want

    def test_no_device_array_outlives_its_step(self):
        """``rows x vocab`` float32 of device memory must be free again
        before the next dispatch: after a step nothing -- engine, step
        record, tracker -- refers to the logits any more."""
        eng = _engine("decode")
        refs = []
        launch = eng._launch

        def spy(*a, **kw):
            out = launch(*a, **kw)
            refs.append(weakref.ref(out[1]))
            return out

        eng._launch = spy
        for p in PROMPTS:
            eng.add_request(p, SamplingParams(max_new_tokens=4))
        while eng.scheduler.has_work():
            eng.step()
            gc.collect()
            assert refs and all(r() is None for r in refs)


class TestAuditOn:
    @pytest.mark.parametrize("sampling", sorted(SAMPLINGS))
    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_sampled_launches_copy_their_real_rows(self, family, sampling):
        # 8 tokens a request: the burst family's run then has a plain
        # decode launch on a sampled step (7) beside its bursts
        want = _serve(_engine(family), SAMPLINGS[sampling], max_new=8)
        eng = _engine(family, audit=AuditConfig(enabled=True,
                                                sample_every=3))
        seen = _spy_launch(eng)
        got = _serve(eng, SAMPLINGS[sampling], max_new=8)
        assert got == want                      # audit on or off
        vocab = eng.model.config.vocab_size
        launches = nbytes = 0
        for program, rows, sampled, logits in seen:
            if sampled and program in SHADOWED:
                # the host copy of the real rows, and of no padding row
                assert isinstance(logits, np.ndarray)
                assert logits.shape == (rows, vocab)
                assert logits.dtype == np.float32
                launches += 1
                nbytes += 4 * vocab * rows
            else:
                assert isinstance(logits, jax.Array), (program, sampled)
        assert launches > 0
        assert _fetch_counts(eng) == (launches, nbytes)
        snap = eng.audit.snapshot()
        assert snap["status"] == "ok"
        assert snap["divergences"] == {"token": 0, "logit": 0,
                                       "nonfinite": 0}
        assert snap["oracle_failures"] == 0
        # every launch the oracle compared is one the launch loop counted
        audited = snap["audited_launches"]
        assert audited["decode"] + audited["ragged"] == launches

    @pytest.mark.parametrize("family", ["decode", "ragged"])
    def test_kernel_corrupt_trips_the_net_on_a_sampled_step(self, family):
        want = _serve(_engine(family))
        eng = _engine(family, audit=AuditConfig(enabled=True,
                                                sample_every=3))
        # steps 1, 4, 7 ... are sampled; the plan names an unsampled step
        # BEFORE a sampled one: the entry must wait for the launch the
        # oracle checks, and fire there exactly once
        fi = FaultInjector(FaultPlan(faults=(
            FaultSpec(point="kernel_corrupt", step=3, replica="0"),)),
            replica="0")
        eng.set_fault_injector(fi)
        got = _serve(eng)
        assert got == want      # the audit's copy alone was corrupted
        snap = eng.audit.snapshot()
        assert fi.fired_count == 1
        assert snap["status"] == "degraded"
        assert snap["divergences"]["token"] == 1
        assert snap["last_divergence"]["program"] == family
        assert (snap["last_divergence"]["step"] - 1) % 3 == 0


@pytest.fixture
def nan_kernel(monkeypatch):
    """The Pallas decode kernel emits NaNs: every decode launch has
    non-finite rows, which the in-trace sentinel reports in ``stats``."""
    real = pallas_paged.paged_attention_decode
    monkeypatch.setattr(pallas_paged, "paged_attention_decode",
                        lambda *a: jnp.full_like(real(*a), jnp.nan))
    yield


class TestNonFiniteBundle:
    @pytest.mark.parametrize("sample_every", [1, 1000],
                             ids=["sampled_step", "unsampled_step"])
    def test_primary_holds_that_launchs_real_rows(self, tmp_path,
                                                  nan_kernel, sample_every):
        eng = _engine("decode", use_pallas_paged=True,
                      audit=AuditConfig(enabled=True,
                                        sample_every=sample_every,
                                        repro_dir=str(tmp_path)))
        copies = []
        step_call = eng._step_call

        def call(program, bucket, jit_fn, *args):
            out = step_call(program, bucket, jit_fn, *args)
            copies.append((program, eng.audit.steps,
                           np.asarray(out[1], np.float32)))
            return out

        eng._step_call = call
        seen = _spy_launch(eng)
        _serve(eng, max_new=4)
        snap = eng.audit.snapshot()
        assert snap["divergences"]["nonfinite"] > 0
        assert snap["divergences"]["token"] == 0
        assert len(snap["repros"]) == 1
        bundle = load_repro(snap["repros"][0])
        assert bundle["meta"]["kind"] == "nonfinite"
        assert bundle["meta"]["program"] == "decode"
        step = bundle["meta"]["step"]
        sampled = (step - 1) % sample_every == 0
        assert sampled == (sample_every == 1)
        primary = bundle["arrays"]["primary_logits"]
        (whole,) = [c for p, s, c in copies if p == "decode" and s == step]
        rows = len(bundle["meta"]["requests"])
        assert primary.shape == (rows, whole.shape[-1])
        assert np.array_equal(primary, whole[:rows], equal_nan=True)
        assert not np.isfinite(primary).all()
        # the bundle's fetch is counted; off the schedule it is the only
        # one, however many launches were non-finite after it
        fetches, nbytes = _fetch_counts(eng)
        shadowed = sum(1 for p, _, s, _ in seen if s and p in SHADOWED)
        assert fetches == shadowed + (0 if sampled else 1)
        if not sampled:
            assert nbytes == primary.nbytes

    def test_prefill_bundle_holds_the_one_row(self, tmp_path):
        """A prefill launch's logits are read by the bundle alone: a
        model whose head is poisoned yields a ``[1, vocab]`` primary."""
        eng = _engine("prefill", audit=AuditConfig(
            enabled=True, sample_every=1, repro_dir=str(tmp_path)))
        head = eng._params[-1]
        head._value = jnp.full_like(head._value, jnp.nan)
        eng.add_request(PROMPTS[0], SamplingParams(max_new_tokens=1))
        eng.step()
        snap = eng.audit.snapshot()
        assert snap["divergences"]["nonfinite"] == 1
        bundle = load_repro(snap["repros"][0])
        assert bundle["meta"]["program"] == "prefill"
        primary = bundle["arrays"]["primary_logits"]
        assert primary.shape == (1, eng.model.config.vocab_size)
        assert np.isnan(primary).all()
        assert _fetch_counts(eng) == (1, primary.nbytes)
