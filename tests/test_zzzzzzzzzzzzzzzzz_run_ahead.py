"""The next decode launch goes out before the last one's tokens come back
(ISSUE 35): ``EngineCore.step_ahead``, what the serving loop calls.

Counts and identities only, never a time.  The contract under test:

* the loop that runs ahead serves, request for request, the token ids a
  loop of bare ``step()`` calls serves -- greedy and seeded sampling, rows
  that finish by length at different steps, launches that mix rows in
  flight with rows whose last token the host knows, a row bucket that
  shrinks and grows -- for every layer kind (``llama``, ``moe_mla``,
  ``mamba_hybrid``);
* a row that ends on an EOS token already has a row in the next launch: its
  result is dropped at the read, counted, and no block or slot leaks;
* every boundary of the rule settles first and is counted under its reason;
* a bare ``step()`` keeps its contract: ``steps + 1`` launches for
  ``steps + 1`` tokens, nothing in flight on return;
* ``scheduler.tokens_planned`` equals the step profiler's scheduled sum,
  dropped rows included.
"""

import pytest

from paddle_tpu.serving import SamplingParams
from paddle_tpu.serving.fleet import FleetRouter
from run_ahead_common import (  # noqa: F401  (``models`` is a fixture)
    ARRIVALS,
    KINDS,
    MIXED,
    ahead_counts,
    assert_clean,
    drive,
    make_engine,
    models,
    outputs,
    prompt_of,
)

# --- (1) the tokens are those of the synchronous order ------------------------

class TestTokenIdentity:
    @pytest.mark.parametrize("kind", KINDS)
    def test_step_ahead_serves_the_tokens_of_bare_steps(self, models, kind):
        """Greedy rows and seeded rows, in the same launches."""
        ref = make_engine(models(kind), kind)
        want = outputs(drive(ref, False, sampling=MIXED))
        eng = make_engine(models(kind), kind)
        got = outputs(drive(eng, True, sampling=MIXED))
        assert got == want
        c = eng._sampling_counters
        assert int(c["greedy"].value) == sum(
            a[2] for a in ARRIVALS[0::2])
        assert int(c["sampled"].value) == sum(
            a[2] for a in ARRIVALS[1::2])
        assert [len(t) for t in got] == [a[2] for a in ARRIVALS]
        counts = ahead_counts(eng)
        # it ran ahead, for this layer kind too, and mixed launches: the
        # later arrivals joined rows that were in flight
        assert counts["launches"] > 12
        assert counts["dropped"] == 0
        assert counts["settles"]["admit"] >= 3
        assert set(counts["settles"]) <= {"admit", "finish"}
        # the same step programs: an ids array made on the device traces
        # and compiles nothing the host array did not
        assert eng.decode_trace_count == ref.decode_trace_count
        assert eng.prefill_trace_count == ref.prefill_trace_count
        assert eng.decode_buckets == ref.decode_buckets
        rows = {b[1] for b in eng.decode_buckets}
        assert {1, 2, 4} <= rows            # shrank and grew
        assert_clean(eng)
        assert_clean(ref)
        assert ahead_counts(ref) == {"launches": 0, "dropped": 0,
                                     "settles": {}}

    @pytest.mark.parametrize("kind", KINDS)
    def test_the_served_loop_matches_bare_steps(self, models, kind,
                                                hold_intake):
        """Through the real serving loop (``EngineReplica._loop``), and
        ``/metrics`` carries the three series."""
        arrivals = [(0, p, n) for _, p, n in ARRIVALS]
        want = outputs(drive(make_engine(models(kind), kind), False,
                             arrivals=arrivals))
        eng = make_engine(models(kind), kind)
        fleet = FleetRouter.from_engine(eng)
        gate = hold_intake(fleet.replicas[0])
        fleet.start()
        try:
            handles = [fleet.submit_request(
                p, SamplingParams(max_new_tokens=n), request_id=f"r{i}")
                for i, (_, p, n) in enumerate(arrivals)]
            gate.set()
            fleet.wait(handles, timeout=600)
            got = [h.output_tokens for h in handles]
        finally:
            fleet.shutdown(drain_timeout=2.0)
        assert got == want
        assert ahead_counts(eng)["launches"] > 5
        assert_clean(eng)
        text = eng.metrics.registry.prometheus_text()
        for name in ("serving_ahead_launches_total",
                     "serving_ahead_dropped_rows_total",
                     'serving_ahead_settles_total{reason="admit"}'):
            assert name in text


# --- (2) an EOS token that fires while the next launch is out -----------------

DRAWN = dict(temperature=1.0, top_k=0, top_p=1.0, seed=77)


def eos_case(models, kind):
    """A prompt, the 12 tokens it is served without an EOS id (drawn: the
    tiny models' greedy streams repeat one token), and a
    position past the prefill's token and before the last whose token no
    earlier position holds: the EOS id that fires there and nowhere
    before."""
    for seed in range(3, 40):
        prompt = prompt_of(11, seed)
        free = outputs(drive(make_engine(models(kind), kind), False,
                             arrivals=[(0, prompt, 12)], sampling=DRAWN))[0]
        fresh = [i for i in range(1, len(free) - 1)
                 if free[i] not in free[:i]]
        if fresh:
            return prompt, free, fresh[0]
    raise AssertionError(f"no prompt of {kind} yields a fresh token")


class TestEosMidFlight:
    @pytest.mark.parametrize("kind", KINDS)
    def test_the_extra_row_is_dropped_and_nothing_leaks(self, models, kind):
        prompt, free, k = eos_case(models, kind)
        other = prompt_of(7, 4)

        def serve(ahead):
            eng = make_engine(models(kind), kind)
            reqs = [eng.add_request(prompt, SamplingParams(
                        max_new_tokens=12, eos_token_id=free[k], **DRAWN)),
                    eng.add_request(other, SamplingParams(
                        max_new_tokens=16, **DRAWN))]
            step = eng.step_ahead if ahead else eng.step
            for _ in range(200):
                if not eng.scheduler.has_work():
                    break
                step()
            return eng, reqs

        ref, want = serve(False)
        eng, got = serve(True)
        assert outputs(got) == outputs(want)
        assert outputs(got)[0] == free[:k + 1]      # ends ON the EOS token
        assert got[0].finish_reason.value == "eos"
        assert len(outputs(got)[1]) == 16
        counts = ahead_counts(eng)
        assert counts["dropped"] == 1
        assert counts["launches"] > 5
        assert_clean(eng)
        # the row ran: one more scheduled token than the synchronous order
        assert eng.stepprof.scheduled_tokens() == \
            ref.stepprof.scheduled_tokens() + 1
        # a dropped row is no token
        c = eng._sampling_counters
        assert int(c["sampled"].value) == k + 1 + 16

    def test_the_last_row_ends_on_eos_and_the_launch_is_let_go(self, models):
        """Nothing runs on: the launch that went out for the row alone is
        let go unread, and the engine is idle with nothing in flight."""
        prompt, free, k = eos_case(models, "llama")
        eng = make_engine(models("llama"))
        req = eng.add_request(prompt, SamplingParams(
            max_new_tokens=12, eos_token_id=free[k], **DRAWN))
        while eng.scheduler.has_work():
            eng.step_ahead()
        assert list(req.output_tokens) == free[:k + 1]
        assert ahead_counts(eng)["dropped"] == 1
        assert_clean(eng)
