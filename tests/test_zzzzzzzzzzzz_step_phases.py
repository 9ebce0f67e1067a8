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
  holds, and become text where they are read;
* the spans beside the phases (``tracer.THREAD_SPANS``, ISSUE 39):
  ``ahead.settle`` carries the reason a step did not run ahead and the
  launch it read; the loop thread's three spans lie on ITS line of a
  profiler trace, one ``req`` across a request's; none of them is a phase
  to ``benchmarks/host_spans.load_host``.
"""

import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import tracer as tracer_mod
from paddle_tpu.observability.audit import AuditConfig
from paddle_tpu.observability.export import chrome_trace_dict
from paddle_tpu.observability.tracer import (
    SETTLE_REASONS,
    STEP_PHASES,
    THREAD_SPANS,
    SpanTracer,
)
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
                                       "launch", "pages_per_step",
                                       "flash_block_q"}
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

    def test_a_latent_decode_dispatch_carries_the_walks_pages(
            self, recorded, monkeypatch):
        """A latent engine's decode launches ride the same integer: the
        pages a step of ``latent_decode_attention`` where the program holds
        that kernel (``latent_kernel_pages`` of the resident pool), so the
        counter that says a page walk engaged reads > 0 there too."""
        import importlib

        from benchmarks import harness
        from paddle_tpu.models import moe_mla
        from paddle_tpu.ops import paged_attention as ops
        from paddle_tpu.ops import pallas_paged

        t = importlib.import_module("tests.test_zzzzzzzzzzzzzz_moe_mla")
        model = harness.load_module("models", "glm_moe_mla").build(
            t.TINY, 7, dtype="float32")
        monkeypatch.setattr(pallas_paged, "LATENT_STEP_TOKENS", 8)
        monkeypatch.setattr(
            moe_mla, "latent_paged_decode_attention",
            lambda *a, use_pallas=None, **kw:
            ops.latent_paged_decode_attention(*a, use_pallas=True, **kw))
        eng = t.make_engine(model)
        t.serve(eng, t.prompt_of(21), 4)
        assert eng.attention_paths["decode"] == "pallas"
        pool = eng._k_pools[0]
        pages = {pallas_paged.latent_kernel_pages(pool, width)
                 for _, _, width in eng.decode_buckets}
        assert pages == {2} == set(eng._kernel_pages.values())
        decodes = [kw["pages_per_step"] for n, kw in recorded
                   if n == "engine.dispatch" and kw["bucket"] < 16]
        # a bucket's first call is traced inside its dispatch: 0, then 2
        assert decodes[0] == 0 and set(decodes[1:]) == {2}

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


class TestSettleSpan:
    """``ahead.settle``: the read of the launch in flight by a step that
    could not run ahead, with its reason and the launch's number."""

    def _settles(self, recorded):
        return [(SETTLE_REASONS[kw["reason"]], kw["launch"])
                for n, kw in recorded if n == "ahead.settle"]

    def _dispatches(self, recorded):
        return [kw for n, kw in recorded if n == "engine.dispatch"]

    def _fly(self, eng, recorded, prompts=1, max_new=6):
        """Steps of the serving loop until a decode launch is in flight."""
        reqs = _submit(eng, _prompts(n=prompts), max_new=max_new)
        for _ in range(20):
            if eng._inflight is not None:
                break
            eng.step_ahead()
        assert eng._inflight is not None
        del recorded[:]
        return reqs

    def test_a_step_that_runs_ahead_settles_nothing(self, recorded):
        eng = _engine()
        self._fly(eng, recorded)
        flying = eng._inflight.flight.seq
        eng.step_ahead()
        assert self._settles(recorded) == []
        [d] = self._dispatches(recorded)
        assert d["ahead"] == 1 and d["launch"] == flying + 1
        names = [n for n, _ in recorded]
        assert set(names) <= set(STEP_PHASES)

    def test_a_prompt_to_compute_settles_for_prefill_or_admit(self, recorded):
        eng = _engine()
        self._fly(eng, recorded)
        flying = eng._inflight.flight.seq
        _submit(eng, _prompts(n=1, seed=3))
        eng.step_ahead()
        # the waiting request can be admitted: the launch is read first
        assert self._settles(recorded) == [("admit", flying)]
        assert all(d["ahead"] == 0 for d in self._dispatches(recorded))

    def test_a_chunked_prompt_settles_for_prefill(self, recorded):
        eng = _engine("chunk")
        self._fly(eng, recorded)
        _submit(eng, [list(range(1, 30))])     # four chunks of 8
        reasons = []
        for _ in range(6):
            del recorded[:]
            eng.step_ahead()
            reasons += [r for r, _ in self._settles(recorded)]
        # admitted once, then a running request still has prompt to compute
        assert reasons[0] == "admit" and "prefill" in reasons

    def test_the_last_token_settles_for_finish(self, recorded):
        eng = _engine()
        self._fly(eng, recorded, max_new=3)
        seen = []
        while eng.scheduler.has_work():
            eng.step_ahead()
            assert len(seen) < 50
            seen = self._settles(recorded)
        [(reason, launch)] = seen
        assert reason == "finish" and launch == eng._launch_seq
        # the settle CONTAINS that launch's wait, fetch and emit
        names = [n for n, _ in recorded]
        at = names.index("ahead.settle")
        assert names[at + 1:at + 4] == ["engine.device_wait",
                                        "engine.fetch", "engine.emit"]
        wait = [kw for n, kw in recorded if n == "engine.device_wait"][-1]
        assert wait["launch"] == launch

    def test_a_bare_step_settles_the_loops_launch(self, recorded):
        eng = _engine()
        self._fly(eng, recorded)
        flying = eng._inflight.flight.seq
        eng.step()
        assert self._settles(recorded) == [("bare", flying)]
        assert eng._inflight is None
        # and with nothing in flight a settle is no span at all
        del recorded[:]
        assert eng.settle("task") == {}
        assert recorded == []

    def test_every_settle_is_counted_under_the_same_word(self, recorded):
        eng = _engine()
        self._fly(eng, recorded, max_new=4)
        while eng.scheduler.has_work():
            eng.step_ahead()
        spans = {}
        for reason, _ in self._settles(recorded):
            spans[reason] = spans.get(reason, 0) + 1
        counted = {
            r: eng.metrics.registry.counter(
                "serving_ahead_settles_total",
                **dict(eng.metrics.labels, reason=r)).value
            for r in spans}
        assert spans and counted == spans

    def test_a_reason_that_is_no_settle_reason_fails(self):
        eng = _engine()
        with pytest.raises(ValueError, match="settle reason"):
            eng._count_settle("because")
        assert "family" in SETTLE_REASONS and len(set(SETTLE_REASONS)) == 9
        from paddle_tpu.serving import engine as engine_mod

        for reason in SETTLE_REASONS:
            assert reason in engine_mod._AHEAD_SETTLES_HELP


class TestLoopThreadSpans:
    """A streamed request through the real server under the CPU profiler:
    which line of the host plane holds which span."""

    @pytest.fixture(scope="class")
    def trace(self, tmp_path_factory):
        import asyncio
        import gc

        import jax

        from benchmarks import thread_spans, trace_reduce
        from paddle_tpu.serving.server import (
            CompletionServer,
            ServerConfig,
            _http,
            _toy_engine,
        )

        log_dir = str(tmp_path_factory.mktemp("loop_trace"))
        body = {"prompt": [5, 6, 7, 8, 9, 10], "max_tokens": 8,
                "stream": True}

        async def drive():
            server = CompletionServer(_toy_engine(), ServerConfig(port=0))
            await server.start()
            loop = asyncio.get_running_loop()
            try:
                # compile everything first: the trace is of warm steps
                await loop.run_in_executor(
                    None, _http, server.port, "POST", "/v1/completions",
                    body)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(log_dir, profiler_options=options)
                try:
                    status, raw = await loop.run_in_executor(
                        None, _http, server.port, "POST",
                        "/v1/completions", body)
                    gc.collect()
                finally:
                    jax.profiler.stop_trace()
            finally:
                await server.shutdown(drain_timeout=1.0)
            return status

        assert asyncio.run(drive()) == 200
        path = trace_reduce.find_xplane(log_dir)
        return path, thread_spans.load_lines(path)[0]

    def test_the_loop_threads_spans_lie_on_one_line(self, trace):
        from benchmarks import thread_spans as ts

        _, lines = trace
        loop = ts.line_of(lines, ts.WAKE)
        names = {p[0] for p in loop}
        assert {ts.ACCEPT, ts.WAKE, ts.WRITE} <= names
        # the loop thread runs no phase of the step, the engine thread no
        # span of the front door
        assert not any(n.startswith(("engine.", "sched.")) for n in names)
        engine = ts.line_of(lines, "engine.dispatch")
        assert engine is not loop
        assert not {p[0] for p in engine} & set(ts.FRONT_DOOR)
        assert "ahead.settle" in {p[0] for p in engine}

    def test_one_req_across_a_requests_spans(self, trace):
        from benchmarks import thread_spans as ts

        loop = ts.line_of(trace[1], ts.WAKE)
        [accept] = [p for p in loop if p[0] == ts.ACCEPT]
        writes = [p for p in loop if p[0] == ts.WRITE]
        # the second request of the server: cmpl-2
        assert accept[3] == {"req": 2, "prompt_tokens": 6}
        assert writes and {w[3]["req"] for w in writes} == {2}
        # the id-bearing first chunk, the tokens, the final chunk
        assert writes[0][3]["tokens"] == 0 == writes[-1][3]["tokens"]
        assert sum(w[3]["tokens"] for w in writes) == 8
        # accepted before anything was written, every write after a wake
        assert accept[2] <= writes[0][1]
        wakes = [p for p in loop if p[0] == ts.WAKE]
        assert all(w[3]["handles"] >= 0 for w in wakes)
        assert wakes[0][1] < writes[1][1]

    def test_a_collection_is_a_span_on_the_thread_it_ran_on(self, trace):
        from benchmarks import thread_spans as ts

        gcs = [p for line in trace[1] for p in line if p[0] == ts.GC]
        assert gcs and all(set(p[3]) == {"gen"} for p in gcs)
        assert 2 in {p[3]["gen"] for p in gcs}     # the forced one

    def test_host_spans_takes_none_of_them_for_a_phase(self, trace):
        from benchmarks import host_spans

        phases, _, _ = host_spans.load_host(trace[0])
        names = {p[0] for p in phases}
        assert names and names <= set(STEP_PHASES)
        assert not names & set(THREAD_SPANS)

    def test_the_readers_read_the_trace(self, trace):
        from benchmarks import thread_spans as ts

        a = ts.analyse(trace[1], {}, {})
        # a CPU trace has no device plane: what needs programs is silent
        assert a["ahead_share"] is None and a["gap_s"] == 0.0
        assert ts.value(None, "frontdoor.loop_busy_share", a) > 0
        assert ts.value(None, "frontdoor.handoff_ms", a) > 0
        assert ts.value(None, "engine.gc_ms_per_s", a) > 0
        assert a["spans"]["server.accept"]["count"] == 1
