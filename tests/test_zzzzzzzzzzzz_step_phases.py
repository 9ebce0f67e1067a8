"""Engine-step phases on the profiler's clock (ISSUE 25).

The contract of ``SpanTracer.phase`` on the serving step path:

* with no capture window armed a phase is ONE ``TraceAnnotation`` with a
  constant name (``tracer.STEP_PHASES``) and integer attributes, and
  nothing else: no ``Span``, no record -- across all five program
  families (one-shot prefill, chunk, decode, the unified ragged step, the
  decode burst);
* with a ``StepProfiler`` window armed the exported step holds its phases
  as child spans, nested, in the order they ran;
* the phases, the wait cut off the fetch and the named scopes add no jit
  trace and leave greedy tokens as they were;
* ``engine.fetch`` carries the bytes the launch copied to the host: its
  int32 tokens, and nothing of the ``[rows, vocab]`` logits unless the
  numerics audit reads them (ISSUE 30);
* ``arm_capture`` holds the step's lock across neither ``start_trace`` nor
  ``stop_trace``: steps complete while a slow profiler starts and stops;
* request ids ride step records and step spans as the tuple the engine
  holds, and become text where they are read.
"""

import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import tracer as tracer_mod
from paddle_tpu.observability.audit import AuditConfig
from paddle_tpu.observability.export import chrome_trace_dict
from paddle_tpu.observability.tracer import STEP_PHASES, SpanTracer
from paddle_tpu.serving import (
    EngineConfig,
    EngineCore,
    SamplingParams,
    SchedulerConfig,
)

BS = 4
# phases of one launch, and of one step around its launches
LAUNCH = ("engine.build", "engine.dispatch", "engine.device_wait",
          "engine.fetch", "engine.emit")
PER_STEP = ("sched.plan", "engine.admit", "engine.trackers")

# family -> (engine settings, scheduler settings)
FAMILIES = {
    "prefill": ({}, {}),
    "chunk": ({}, {"max_prefill_tokens_per_step": 8}),
    "decode": ({}, {}),
    "ragged": ({"unified_step": True}, {"max_tokens_per_step": 16}),
    "burst": ({"burst_steps": 4}, {}),
}


def _engine(family="decode", **kw):
    paddle.seed(0)
    eng_kw, sched_kw = FAMILIES[family]
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    return EngineCore(model, config=EngineConfig(
        num_blocks=64, block_size=BS,
        scheduler=SchedulerConfig(max_num_seqs=4, **sched_kw),
        **eng_kw, **kw))


def _prompts(n=3, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, 11 + i).tolist() for i in range(n)]


def _submit(eng, prompts, max_new=6):
    return [eng.add_request(p, SamplingParams(max_new_tokens=max_new))
            for p in prompts]


def _copied(rec, audit=False, sampled=False, vocab=0):
    """Bytes the launch behind one program record of the step profiler
    copies to the host in ``engine.fetch``: one int32 a token slot of
    its bucket; with the audit on three float32 a logits row of the
    bucket beside them, and on a sampled decode / ragged launch the REAL
    rows of float32 logits."""
    program = rec["program"]
    bucket = [int(b) for b in rec["bucket"].split("x")]
    slots = {"prefill": 1, "chunk": 1, "decode": bucket[0],
             "ragged": bucket[0], "burst": bucket[0] * bucket[-1]}[program]
    n = 4 * slots
    if audit and program != "burst":
        n += 12 * slots
        if sampled and program in ("decode", "ragged"):
            real = rec["rows"] if program == "ragged" \
                else rec["scheduled_tokens"]
            n += 4 * vocab * real
    return n


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what each
    phase site made."""

    made = []

    def __init__(self, name, **kwargs):
        type(self).made.append((name, kwargs))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def recorded(monkeypatch):
    """Patch the annotation with the recording fake, and the recorded
    phase (the only thing that builds anything else) to raise."""
    def boom(*a, **k):
        raise AssertionError("a phase Span/record was built with no "
                             "capture window armed")

    _FakeAnnotation.made = []
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(tracer_mod, "_RecordedPhase", boom)
    return _FakeAnnotation.made


class TestUnarmedStep:
    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_one_constant_integer_annotation_per_phase(self, family,
                                                       recorded):
        eng = _engine(family)
        _submit(eng, _prompts())
        per_step, copied = [], []
        while eng.scheduler.has_work():
            del recorded[:]
            eng.step()
            programs = eng.stepprof.last_record()["programs"]
            per_step.append((len(programs), list(recorded)))
            copied += [_copied(rec) for rec in programs]
            assert len(per_step) < 400
        assert eng.stepprof.bucket_set(family), \
            f"the run never launched a {family} program"
        for launches, made in per_step:
            names = [n for n, _ in made]
            assert set(names) <= set(STEP_PHASES)
            for name, kwargs in made:
                # integers the step already holds, nothing formatted
                assert set(kwargs) <= {"rows", "bucket", "bytes", "ahead",
                                       "launch", "pages_per_step"}
                assert all(type(v) is int for v in kwargs.values()), \
                    (name, kwargs)
            for name in PER_STEP:
                assert names.count(name) == 1, (name, names)
            # one of each a launch, a burst's too
            for name in LAUNCH[:4]:
                assert names.count(name) == launches, (name, names)
            # every launch's emission, and the step's retire
            assert names.count("engine.emit") == launches + 1
        # audit off: a launch brings back its int32 tokens -- one a row
        # of the bucket, a burst's [rows, steps] buffer -- and no logits
        fetches = [kw["bytes"] for _, made in per_step for n, kw in made
                   if n == "engine.fetch"]
        assert fetches and fetches == copied
        assert max(fetches) < 4 * eng.model.config.vocab_size

    @pytest.mark.parametrize("use_pallas", [True, False])
    def test_a_decode_dispatch_carries_the_kernels_pages_per_step(
            self, use_pallas, recorded):
        """What the paged decode kernel of a decode launch's program moves
        a step rides ``engine.dispatch``: what ``kernel_pages`` gives for
        the pool and the launch's table width, written where the program
        was traced (so a bucket's first call still says 0); 0 on the
        gather path and on every launch that is no decode."""
        from paddle_tpu.ops.pallas_paged import kernel_pages

        eng = _engine("decode", use_pallas_paged=use_pallas)
        _submit(eng, _prompts())
        eng.run(max_steps=200)
        assert eng.attention_paths["decode"] == \
            ("pallas" if use_pallas else "xla")
        pool = eng._k_pools[0]
        # what the kernel moves a step at each table width a launch had
        widths = {width for _, _, width in eng.decode_buckets}
        pages = {kernel_pages(pool, width) if use_pallas else 0
                 for width in widths}
        assert widths and set(eng._kernel_pages.values()) == pages
        carried = {kw["pages_per_step"] for n, kw in recorded
                   if n == "engine.dispatch"}
        assert 0 in carried        # the prefill launches
        # a bucket's later calls carry what its trace wrote
        assert carried <= {0} | pages
        assert (max(carried) > 1) == use_pallas

    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_fetch_bytes_with_the_audit_on(self, family, recorded):
        """Audit on: the stats ride every launch the auditor observes,
        and the logits cross on the sampled decode / ragged launches
        alone, cut to their real rows."""
        eng = _engine(family, audit=AuditConfig(enabled=True,
                                                sample_every=2))
        vocab = eng.model.config.vocab_size
        _submit(eng, _prompts())
        fetches, copied, with_logits = [], [], 0
        while eng.scheduler.has_work():
            del recorded[:]
            eng.step()
            sampled = eng.audit.sampled
            for rec in eng.stepprof.last_record()["programs"]:
                copied.append(_copied(rec, audit=True, sampled=sampled,
                                      vocab=vocab))
                with_logits += copied[-1] > 4 * vocab
            fetches += [kw["bytes"] for n, kw in recorded
                        if n == "engine.fetch"]
            assert len(fetches) < 400
        assert eng.stepprof.bucket_set(family)
        assert fetches == copied
        assert eng.audit.snapshot()["divergences"] == \
            {"token": 0, "logit": 0, "nonfinite": 0}
        # the launch loop's own count of the copies agrees
        count = eng.metrics.registry.counter(
            "serving_logits_fetches_total", **eng.metrics.labels).value
        assert count == with_logits > 0

    def test_phase_is_the_annotation_itself(self):
        """Off the capture path the helper hands back the annotation: no
        wrapper object, nothing recorded."""
        from jax.profiler import TraceAnnotation

        eng = _engine()
        assert eng.stepprof.phase_sink is None
        for rec in (None, eng.stepprof):
            ph = SpanTracer.phase("engine.build", rec, rows=3)
            assert type(ph) is TraceAnnotation
        n = len(eng.tracer)
        with eng.tracer.phase("engine.build", eng.stepprof, rows=3):
            pass
        assert len(eng.tracer) == n        # no Span in the ring either


class TestArmedWindow:
    def _steps(self, eng, result):
        evs = result["traceEvents"]
        steps = [e for e in evs if e["name"] == "engine_step"]
        kids = {}
        for e in evs:
            if e.get("cat") == "phase" and "parent" in e["args"]:
                kids.setdefault(e["args"]["parent"], []).append(e)
        return [(s, sorted(kids.get(s["args"]["id"], []),
                           key=lambda e: e["ts"])) for s in steps]

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_exported_step_has_each_phase_nested_in_order(self, program):
        eng = _engine()
        window = eng.stepprof.arm_capture(12, device_trace=False)
        _submit(eng, _prompts(n=2), max_new=5)
        eng.run(max_steps=200)
        eng.stepprof.cancel_capture(window)
        assert window.done.is_set()
        found = 0
        for step, phases in self._steps(eng, window.result):
            if step["args"]["program"] != program:
                continue        # a step of exactly this one launch
            found += 1
            names = [e["name"] for e in phases]
            assert names == ["sched.plan", "engine.admit", "engine.build",
                             "engine.dispatch", "engine.device_wait",
                             "engine.fetch", "engine.emit", "engine.emit",
                             "engine.trackers"], names
            lo, hi = step["ts"], step["ts"] + step["dur"]
            ends = [e["ts"] + e["dur"] for e in phases]
            assert all(lo <= e["ts"] and end <= hi + 1.0     # microseconds
                       for e, end in zip(phases, ends))
            assert all(a <= b["ts"] + 1.0
                       for a, b in zip(ends, phases[1:]))
            fetch = phases[names.index("engine.fetch")]
            dispatch = phases[names.index("engine.dispatch")]["args"]
            # audit off: the int32 tokens alone, one a row of the decode
            # bucket; a prefill's one token
            assert fetch["args"]["bytes"] == \
                4 * (dispatch["bucket"] if program == "decode" else 1)
            assert dispatch["rows"] >= 1
        assert found >= 1

    def test_window_closes_the_sink(self):
        eng = _engine()
        window = eng.stepprof.arm_capture(2, device_trace=False)
        assert eng.stepprof.phase_sink == []
        _submit(eng, _prompts(n=1), max_new=4)
        eng.run(max_steps=100)
        assert window.done.is_set() and window.complete
        assert eng.stepprof.phase_sink is None
        # a second window can be armed once the first is done
        again = eng.stepprof.arm_capture(1, device_trace=False)
        eng.stepprof.cancel_capture(again)

    def test_capture_adds_no_trace_and_changes_no_token(self):
        outs, traces = [], []
        for armed in (False, True):
            eng = _engine()
            if armed:
                eng.stepprof.arm_capture(64, device_trace=False)
            reqs = _submit(eng, _prompts(), max_new=8)
            eng.run(max_steps=400)
            outs.append([list(r.output_tokens) for r in reqs])
            traces.append((eng.prefill_trace_count, eng.decode_trace_count))
        assert outs[0] == outs[1]
        assert traces[0] == traces[1]
        # bounded by the bucket sets, as before the phases
        assert traces[0][1] == len(eng.decode_buckets)


class TestCaptureOffTheStepLock:
    def test_steps_complete_while_the_profiler_starts_and_stops(
            self, monkeypatch, tmp_path):
        """A profiler whose start and stop block until released: the
        engine keeps stepping meanwhile, so neither call is made under
        the lock every step takes."""
        import jax

        entered = {"start": threading.Event(), "stop": threading.Event()}
        release = {"start": threading.Event(), "stop": threading.Event()}

        def fake(which):
            def call(*a, **k):
                entered[which].set()
                assert release[which].wait(30.0)
            return call

        monkeypatch.setattr(jax.profiler, "start_trace", fake("start"))
        monkeypatch.setattr(jax.profiler, "stop_trace", fake("stop"))
        eng = _engine()
        _submit(eng, _prompts(n=2), max_new=64)
        box = {}
        arm = threading.Thread(target=lambda: box.setdefault(
            "w", eng.stepprof.arm_capture(
                2, device_trace=True, log_dir=str(tmp_path))), daemon=True)
        arm.start()
        try:
            assert entered["start"].wait(30.0)
            before = eng.stepprof.steps
            for _ in range(5):
                eng.step()                      # would deadlock under the lock
            assert eng.stepprof.steps == before + 5
            assert eng.stepprof.phase_sink is None      # not armed yet
        finally:
            release["start"].set()
        arm.join(30.0)
        assert not arm.is_alive()
        window = box["w"]
        try:
            for _ in range(3):
                eng.step()                      # fills the 2-step window
            assert entered["stop"].wait(30.0)   # finalizing, off-thread
            before = eng.stepprof.steps
            for _ in range(5):
                eng.step()
            assert eng.stepprof.steps == before + 5
            assert not window.done.is_set()     # stop_trace still blocked
        finally:
            release["stop"].set()
        assert window.wait(30.0)
        assert window.complete and window.result["captureSteps"] == 2
        assert window.result["deviceTraceDir"] == str(tmp_path)


class TestIdsJoinedWhereRead:
    def test_records_and_spans_hold_tuples_until_read(self):
        prev = tracer_mod.set_tracer(SpanTracer(capacity=4096))
        try:
            eng = _engine()
            reqs = _submit(eng, _prompts(n=2), max_new=4)
            eng.run(max_steps=100)
            ids = {str(r.request_id) for r in reqs}
            spans = [s for s in eng.tracer.spans()
                     if s.name == "decode_step"]
            assert spans and all(
                isinstance(s.attrs["requests"], tuple)
                and isinstance(s.attrs["traces"], tuple) for s in spans)
            exported = [e for e in chrome_trace_dict(spans)["traceEvents"]
                        if e["name"] == "decode_step"]
            assert all(isinstance(e["args"]["requests"], str)
                       and set(e["args"]["requests"].split(",")) <= ids
                       for e in exported)
            decodes = [p for rec in eng.stepprof.records()
                       for p in rec["programs"] if p["program"] == "decode"]
            assert decodes and all(
                isinstance(p["requests"], str)
                and set(p["requests"].split(",")) <= ids for p in decodes)
            last = eng.stepprof.last_record()
            assert all(not isinstance(v, tuple)
                       for p in last["programs"] for v in p.values())
        finally:
            tracer_mod.set_tracer(prev)
