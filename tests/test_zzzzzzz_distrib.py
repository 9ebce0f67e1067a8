"""Cross-process distributed tracing (ISSUE 17).

The contract under test: `--workers` mode observability reaches INTO
the worker processes.  Workers run their engines with lifecycle events
on and piggyback bounded, sequence-numbered telemetry deltas onto the
replies they already send; the router merges them idempotently into its
ONE ``LifecycleTracker`` (offset-corrected onto the router's monotonic
clock by an NTP-style estimator) and mirrors them host-side so a
kill -9 post-mortem bundle embeds the dead worker's events.  Per-step
timestamps attribute every step's wall to host vs wire vs engine.

(Named ``zzzzzzz`` — seven z's — to sort after
``test_zzzzzz_procfleet.py``: the tier-1 suite overruns its timeout,
so new dots must only append.)
"""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.distrib import (
    ClockSync,
    DeltaMerger,
    MirrorRing,
    TelemetryOutbox,
    WireStats,
)
from paddle_tpu.observability.export import (
    chrome_trace_dict,
    load_profiler_result,
)
from paddle_tpu.observability.lifecycle import LifecycleTracker
from paddle_tpu.serving import (
    AotArtifact,
    EngineConfig,
    EngineCore,
    FleetConfig,
    ProcessFleet,
    ProcessFleetConfig,
    SamplingParams,
    SchedulerConfig,
    SupervisorConfig,
)

POOL = dict(num_blocks=32, block_size=4)
SCHED = dict(max_num_seqs=4, max_prefill_tokens_per_step=8)

_RNG = np.random.default_rng(0)
PREFIX = _RNG.integers(0, 256, 8).tolist()
PROMPTS = [PREFIX + _RNG.integers(0, 256, 4).tolist() for _ in range(6)]

SUP = dict(backoff_initial_s=0.02, backoff_max_s=0.5,
           poll_interval_s=0.01)


@pytest.fixture(scope="module")
def aot_dir(tmp_path_factory):
    """ONE artifact on disk, shared by every worker boot AND respawn."""
    path = str(tmp_path_factory.mktemp("distrib") / "aot")
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    eng = EngineCore(model, config=EngineConfig(
        **POOL, scheduler=SchedulerConfig(**SCHED)))
    art = AotArtifact.save(eng, path, max_seq_len=32)
    assert art.program_count > 0
    return path


def _cfg(aot_dir, dp=2, **kw):
    kw.setdefault("heartbeat_interval_s", 0.1)
    kw.setdefault("heartbeat_timeout_s", 1.0)
    return ProcessFleetConfig(
        dp=dp, layers=2, num_blocks=POOL["num_blocks"],
        block_size=POOL["block_size"],
        max_num_seqs=SCHED["max_num_seqs"],
        max_prefill_tokens_per_step=SCHED["max_prefill_tokens_per_step"],
        aot_path=aot_dir, **kw)


# --- clock sync (pure, no processes) ----------------------------------------

class TestClockSync:
    def test_symmetric_exchange_recovers_exact_offset(self):
        # worker clock runs 5 s ahead; both wire legs take 1 ms
        cs = ClockSync()
        cs.observe(10.0, 15.001, 15.002, 10.003)
        assert cs.offset == pytest.approx(5.0)
        assert cs.rtt == pytest.approx(0.002)
        # to_router maps a worker timestamp back onto the router clock
        assert cs.to_router(15.0015) == pytest.approx(10.0015)

    def test_min_rtt_sample_wins_deterministically(self):
        # asymmetric (noisy) samples bias the offset; the min-RTT
        # sample is trusted.  Feed a noisy burst around a clean probe.
        cs = ClockSync()
        off = 2.0
        cs.observe(0.0, 0.050 + off, 0.051 + off, 0.200)  # rtt .199
        cs.observe(1.0, 1.001 + off, 1.002 + off, 1.003)  # rtt .002 <-
        cs.observe(2.0, 2.090 + off, 2.091 + off, 2.100)  # rtt .099
        assert cs.rtt == pytest.approx(0.002)
        assert cs.offset == pytest.approx(off, abs=1e-9)
        # a WORSE later sample must not move the estimate
        cs.observe(3.0, 3.3 + off, 3.4 + off, 3.9)
        assert cs.offset == pytest.approx(off, abs=1e-9)
        # a BETTER one must
        cs.observe(4.0, 4.0004 + off, 4.0005 + off, 4.0009)
        assert cs.rtt == pytest.approx(0.0008)

    def test_first_minimal_sample_wins_on_ties(self):
        cs = ClockSync()
        cs.observe(0.0, 0.001 + 1.0, 0.002 + 1.0, 0.003)   # offset 1.0
        cs.observe(5.0, 5.001 + 9.0, 5.002 + 9.0, 5.003)   # same rtt
        assert cs.offset == pytest.approx(1.0)

    def test_negative_rtt_sample_is_skipped(self):
        cs = ClockSync()
        cs.observe(0.0, 10.0, 10.5, 0.1)  # server "took" longer than rtt
        assert cs.samples == 0
        assert cs.offset == 0.0 and cs.rtt == 0.0

    def test_window_is_bounded_and_slides(self):
        cs = ClockSync(window=8)
        # best sample first — then slide it out of the window
        cs.observe(0.0, 0.0001, 0.0002, 0.0003)
        for i in range(1, 20):
            t = float(i)
            cs.observe(t, t + 0.01, t + 0.02, t + 0.05)
        assert cs.samples == 20
        assert len(cs._samples) == 8
        # the early min-RTT sample aged out: estimate comes from the
        # surviving window
        assert cs.rtt == pytest.approx(0.04)

    def test_snapshot_shape(self):
        cs = ClockSync()
        snap = cs.snapshot()
        assert snap == {"offset_s": 0.0, "rtt_s": 0.0, "samples": 0}


# --- worker outbox / host mirror (pure) -------------------------------------

class TestTelemetryOutbox:
    def test_seqs_monotonic_and_drain_clears(self):
        ob = TelemetryOutbox(capacity=16)
        for i in range(5):
            ob.on_event(f"r{i}", "enqueued", float(i), 7, {"k": i})
        assert ob.pending == 5
        d = ob.drain()
        assert [e["seq"] for e in d["events"]] == [0, 1, 2, 3, 4]
        assert d["dropped"] == 0
        assert ob.pending == 0
        assert ob.drain()["events"] == []

    def test_flood_drops_oldest_with_exact_count(self):
        ob = TelemetryOutbox(capacity=8)
        for i in range(100):
            ob.on_event("r", "decode_token", float(i), 0, {})
        assert ob.pending == 8
        d = ob.drain()
        assert d["dropped"] == 92
        # survivors are the NEWEST eight, seqs still assigned pre-drop
        assert [e["seq"] for e in d["events"]] == list(range(92, 100))

    def test_drain_limit_slices_oldest_first(self):
        ob = TelemetryOutbox(capacity=16)
        for i in range(10):
            ob.on_event("r", "e", float(i), 0, {})
        d = ob.drain(limit=3)
        assert [e["seq"] for e in d["events"]] == [0, 1, 2]
        assert ob.pending == 7


class TestMirrorRing:
    def test_flood_stays_bounded_with_exact_drop_count(self):
        ring = MirrorRing(capacity=64)
        for i in range(10_000):
            ring.append({"seq": i})
        snap = ring.snapshot()
        assert len(snap["events"]) == 64
        assert snap["dropped"] == 10_000 - 64
        assert snap["events"][-1]["seq"] == 9999


# --- delta merge (pure; real LifecycleTracker) ------------------------------

def _delta(seqs, rid="req-1", name="decode_token", ts=100.0):
    return {"events": [{"seq": s, "rid": rid, "name": name,
                        "ts": ts + s, "tid": 3, "attrs": {}}
                       for s in seqs],
            "dropped": 0}


def _merger(offset=0.0, lc=None, pid=4242):
    clock = ClockSync()
    if offset:
        clock.observe(0.0, 0.001 + offset, 0.002 + offset, 0.003)
    mirror = MirrorRing(capacity=512)
    m = DeltaMerger("0", pid, clock, mirror, lambda: lc)
    return m, mirror


class TestDeltaMerger:
    def test_replay_is_idempotent(self):
        m, mirror = _merger()
        d = _delta(range(5))
        assert m.merge(d) == 5
        assert m.merge(d) == 0        # exact replay: nothing re-applied
        assert m.applied == 5
        assert len(mirror.snapshot()["events"]) == 5
        assert m.snapshot()["intervals"] == 1

    def test_out_of_order_batches_all_apply_once(self):
        # step-reply conn delivers [5..9] before the heartbeat conn
        # delivers [0..4]; then BOTH are replayed
        m, mirror = _merger()
        assert m.merge(_delta(range(5, 10))) == 5
        assert m.merge(_delta(range(0, 5))) == 5
        assert m.merge(_delta(range(0, 10))) == 0
        snap = m.snapshot()
        assert snap["applied"] == 10
        assert snap["last_seq"] == 9
        assert snap["intervals"] == 1  # gap closed -> coalesced
        assert len(mirror.snapshot()["events"]) == 10

    def test_offset_correction_and_stamping(self):
        lc = LifecycleTracker()
        lc.event("req-1", "submitted")  # router-side start
        m, mirror = _merger(offset=50.0, lc=lc)
        m.merge(_delta([0], ts=60.0))   # worker clock: 60.0
        ev = mirror.snapshot()["events"][0]
        assert ev["ts"] == pytest.approx(10.0, abs=1e-6)  # router clock
        assert ev["attrs"]["replica"] == "0"
        assert ev["attrs"]["chrome_pid"] == 4242
        tl = lc.get("req-1")
        merged = [e for e in tl.events if "chrome_pid" in e.attrs]
        assert len(merged) == 1
        assert merged[0].ts == pytest.approx(10.0, abs=1e-6)

    def test_rid_less_events_mirror_but_skip_the_tracker(self):
        lc = LifecycleTracker()
        m, mirror = _merger(lc=lc)
        m.merge({"events": [{"seq": 0, "rid": None, "name": "step_record",
                             "ts": 1.0, "tid": 0, "attrs": {}}],
                 "dropped": 0})
        assert len(mirror.snapshot()["events"]) == 1
        assert lc.get("step_record") is None

    def test_worker_dropped_is_cumulative_max(self):
        m, _ = _merger()
        m.merge({"events": [], "dropped": 7})
        m.merge({"events": [], "dropped": 3})  # reordered older delta
        assert m.worker_dropped == 7

    def test_interval_list_is_capped(self):
        m, _ = _merger()
        # 200 disjoint singleton intervals (every even seq)
        for s in range(0, 400, 2):
            m.merge(_delta([s]))
        assert m.snapshot()["intervals"] <= DeltaMerger._MAX_INTERVALS
        assert m.applied == 200


# --- wire attribution (pure) ------------------------------------------------

class TestWireStats:
    def test_share_math_is_exact(self):
        ws = WireStats()
        # router wall 10 ms; worker processed for 8 ms of it (2 ms
        # wire), queued 1 ms, engine 6 ms -> host = 10 - 2 - 1 - 6 = 1
        stamps = {"recv": 100.000, "eng0": 100.001,
                  "eng1": 100.007, "reply": 100.008}
        ws.observe(50.000, 50.010, stamps, program="decode")
        rep = ws.report()
        assert rep["steps"] == 1
        assert rep["wire_s"] == pytest.approx(0.002)
        assert rep["queue_s"] == pytest.approx(0.001)
        assert rep["engine_s"] == pytest.approx(0.006)
        # wire share folds queue in (both are cross-process overhead)
        assert rep["shares"]["wire"] == pytest.approx(0.3, abs=1e-3)
        assert rep["shares"]["engine"] == pytest.approx(0.6, abs=1e-3)
        assert rep["shares"]["host"] == pytest.approx(0.1, abs=1e-3)
        assert "decode" in rep["per_program"]

    def test_partial_stamps_are_skipped(self):
        ws = WireStats()
        ws.observe(0.0, 1.0, None)
        ws.observe(0.0, 1.0, {"recv": 0.1})  # missing the rest
        assert ws.steps == 0

    def test_per_program_table_is_bounded(self):
        ws = WireStats()
        stamps = {"recv": 0.0, "eng0": 0.0, "eng1": 0.5, "reply": 0.9}
        for i in range(100):
            ws.observe(0.0, 1.0, stamps, program=f"prog-{i}")
        per = ws.report()["per_program"]
        # 64 named rows + the "_other" aggregate for the tail
        assert len(per) == WireStats._MAX_PROGRAMS + 1
        assert per["_other"]["steps"] == 100 - WireStats._MAX_PROGRAMS


# --- stitched chrome export (in-process synthetic) --------------------------

class TestChromeStitch:
    def test_cross_process_trace_roundtrip(self, tmp_path):
        """Router events + merged worker events export as ONE chrome
        trace: worker spans on their own pid row (named metadata),
        offset-corrected INSIDE the router's request span, and the
        stock loader round-trips the nesting."""
        lc = LifecycleTracker()
        rid = "cmpl-stitch"
        lc.event(rid, "submitted")
        lc.event(rid, "route", replica="0")
        # worker is 1000 s "ahead"; merged events must land between
        # the router's submitted..finish bounds after correction.  The
        # zero-RTT probe makes the estimated offset exactly 1000.0.
        clock = ClockSync()
        base = time.perf_counter()
        clock.observe(base, base + 1000.0, base + 1000.0, base)
        mirror = MirrorRing()
        m = DeltaMerger("0", 7777, clock, mirror, lambda: lc)
        m.merge({"events": [
            {"seq": 0, "rid": rid, "name": "enqueued",
             "ts": base + 1000.0 + 1e-4, "tid": 9, "attrs": {}},
            {"seq": 1, "rid": rid, "name": "first_token",
             "ts": base + 1000.0 + 2e-4, "tid": 9, "attrs": {}},
        ], "dropped": 0})
        time.sleep(0.002)  # finish strictly after the corrected stamps
        lc.event(rid, "finish", reason="length")

        tl = lc.get(rid)
        doc = chrome_trace_dict(tl.chrome_spans())
        pids = {ev["pid"] for ev in doc["traceEvents"]
                if ev.get("ph") in ("X", "i")}
        assert 7777 in pids and len(pids) >= 2
        meta = {ev["pid"]: ev["args"]["name"]
                for ev in doc["traceEvents"]
                if ev.get("ph") == "M" and ev["name"] == "process_name"}
        assert meta[7777] == "paddle_tpu worker pid=7777"

        path = str(tmp_path / "stitched.json")
        with open(path, "w") as f:
            json.dump(doc, f)
        res = load_profiler_result(path)
        roots = [r for r in res.roots if r.name.startswith("request ")]
        assert len(roots) == 1
        root = roots[0]
        lo, hi = root.ts, root.ts + root.dur
        worker_evs = [e for e in res.events
                      if e.attrs.get("chrome_pid") == 7777]
        assert {e.name for e in worker_evs} == {"enqueued",
                                                "first_token"}
        for e in worker_evs:
            # offset-corrected: a raw worker timestamp would sit
            # ~1000 s (1e9 us) outside the root span
            assert lo <= e.ts <= hi, (e.name, e.ts, lo, hi)


# --- cross-process integration ----------------------------------------------

def _http(port, method, path, body=None, timeout=120):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    payload = None if body is None else json.dumps(body)
    conn.request(method, path, payload,
                 {"Content-Type": "application/json"} if payload else {})
    resp = conn.getresponse()
    data = resp.read()
    status = resp.status
    conn.close()
    return status, data


def _stream(router, prompts, max_new=12, prefix="d", **kw):
    return [router.submit_request(
        p, SamplingParams(max_new_tokens=max_new),
        request_id=f"{prefix}{i}", retryable=True, **kw)
        for i, p in enumerate(prompts)]


@pytest.mark.slow
class TestProcfleetTracing:
    def test_stitched_tracing_wire_debug_and_kill9_bundle(
            self, aot_dir, tmp_path):
        """ONE dp=2 fleet boot covers the whole ISSUE 17 acceptance
        path: honest /v1/requests, stitched chrome over HTTP,
        /v1/debug/wire attribution, then kill -9 mid-stream -> the
        engine_death bundle embeds the dead worker's mirrored events
        and the SURVIVING fleet still serves + exports."""
        import asyncio

        from paddle_tpu.serving.server import (CompletionServer,
                                               ServerConfig)

        fdir = str(tmp_path / "flight")
        pf = ProcessFleet(_cfg(aot_dir,
                               fleet=FleetConfig(flight_dir=fdir)))
        pf.supervise(SupervisorConfig(**SUP))
        pf.start()
        router = pf.router
        loop = asyncio.new_event_loop()
        threading.Thread(target=loop.run_forever, daemon=True).start()

        def run(coro, timeout=120):
            return asyncio.run_coroutine_threadsafe(
                coro, loop).result(timeout)

        server = CompletionServer(router, ServerConfig())
        run(server.start())
        try:
            # --- fault-free stream over the real wire
            hs = _stream(router, PROMPTS, prefix="t")
            router.wait(hs, timeout=300)
            assert all(h.finish_reason == "length" for h in hs)
            time.sleep(0.3)  # one heartbeat carries trailing deltas

            # --- nothing killed yet: the bounded mirror rings dropped
            # EXACTLY zero events (one drop here means the rings are
            # sized wrong or the piggyback drain starved)
            for i, proxy in sorted(dict(pf.shared.active).items()):
                st = proxy.distrib_state()
                assert st["mirror"]["dropped"] == 0, (i, st["mirror"])
                assert (st["merge"] or {}).get("worker_dropped", 0) == 0

            # --- satellite 3: /v1/requests answers honestly
            status, data = _http(server.port, "GET",
                                 "/v1/requests?state=recent")
            assert status == 200
            listing = json.loads(data)
            assert listing["source"] == "router+workers"
            assert listing["complete"] is True
            status, data = _http(server.port, "GET", "/v1/requests/t0")
            assert status == 200
            one = json.loads(data)
            assert one["source"] == "router+workers"
            assert one["complete"] is True

            # --- merged worker events in the router timeline
            tl = router.lifecycle.get("t0")
            worker_evs = [e for e in tl.events
                          if "chrome_pid" in e.attrs]
            assert worker_evs, "no worker events merged into timeline"
            worker_pids = {e.attrs["chrome_pid"] for e in worker_evs}
            assert worker_pids <= {pf.worker_pid(0), pf.worker_pid(1)}

            # --- stitched chrome export round-trips via the loader
            status, data = _http(server.port, "GET",
                                 "/v1/requests/t0?format=chrome")
            assert status == 200
            path = str(tmp_path / "t0.json")
            with open(path, "wb") as f:
                f.write(data)
            res = load_profiler_result(path)
            roots = [r for r in res.roots
                     if r.name.startswith("request ")]
            assert len(roots) == 1
            lo = roots[0].ts
            hi = lo + roots[0].dur
            stitched = [e for e in res.events
                        if e.attrs.get("chrome_pid") in worker_pids]
            assert stitched, "chrome export lost the worker spans"
            for e in stitched:
                assert lo - 1 <= e.ts <= hi + 1, (e.name, e.ts, lo, hi)
            meta = [ev for ev in res.raw["traceEvents"]
                    if ev.get("ph") == "M"
                    and ev["name"] == "process_name"]
            assert any("worker pid=" in m["args"]["name"]
                       for m in meta)

            # --- wire-latency attribution, HTTP + summary()
            status, data = _http(server.port, "GET", "/v1/debug/wire")
            assert status == 200
            wire = json.loads(data)
            assert wire["enabled"] is True
            assert wire["steps"] >= 1
            shares = wire["shares"]
            assert shares["wire"] + shares["engine"] + shares["host"] \
                == pytest.approx(1.0, abs=0.01)
            live = [st for st in wire["replicas"].values()
                    if "wire" in st]
            assert sum(st["wire"]["steps"] for st in live) >= 1
            assert sum(st["merge"]["applied"] for st in live) > 0
            assert all(st["clock"]["samples"] > 0 for st in live)
            summaries = [pf.proxy(i).metrics.summary()
                         for i in range(2)]
            assert any("wire vs engine vs host" in s
                       for s in summaries), \
                "metrics summary() lost the wire-share table"
            status, data = _http(server.port, "GET", "/metrics")
            assert status == 200
            assert b"serving_wire_rtt_seconds" in data
            assert b"serving_distrib_events_streamed_total" in data

            # --- kill -9 mid-stream: bundle embeds dead worker events
            hs2 = _stream(router, PROMPTS, prefix="u")
            time.sleep(0.2)
            victim = next((r.index for r in router.replicas
                           if r.in_flight), 0)
            vpid = pf.worker_pid(victim)
            os.kill(vpid, signal.SIGKILL)
            router.wait(hs2, timeout=300)
            assert all(h.finish_reason == "length" for h in hs2)
            bundles = [p for p in router.flight.bundles
                       if "engine_death" in p]
            assert len(bundles) == 1
            bundle = json.load(open(bundles[0]))
            dead = bundle["distrib"][str(victim)]
            assert dead["pid"] == vpid
            assert len(dead["mirror"]["events"]) > 0, \
                "engine_death bundle embeds no dead-worker events"
            assert isinstance(dead["stderr_tail"], list)

            # --- surviving fleet still stitches after the respawn
            deadline = time.perf_counter() + 120
            while time.perf_counter() < deadline:
                if (all(r.healthy for r in router.replicas)
                        and pf.worker_pid(victim) != vpid):
                    break
                time.sleep(0.02)
            assert all(r.healthy for r in router.replicas)
            status, data = _http(server.port, "GET",
                                 "/v1/requests/u0?format=chrome")
            assert status == 200
            path2 = str(tmp_path / "u0.json")
            with open(path2, "wb") as f:
                f.write(data)
            assert len(load_profiler_result(path2).events) > 0
        finally:
            run(server.shutdown(drain_timeout=2.0))
            loop.call_soon_threadsafe(loop.stop)
            pf.stop()

    def test_telemetry_off_is_token_identical(self, aot_dir):
        """The passive contract: telemetry on vs off produces the SAME
        greedy tokens with the SAME (zero, AOT-booted) trace counts —
        and off means off: nothing merged, honest router-only rows."""
        def run(telemetry):
            pf = ProcessFleet(_cfg(aot_dir, dp=1, telemetry=telemetry))
            pf.start()
            router = pf.router
            hs = _stream(router, PROMPTS[:3], max_new=8, prefix="i")
            router.wait(hs, timeout=300)
            assert all(h.finish_reason == "length" for h in hs)
            desc = pf.proxy(0).debug_fetch("describe")
            state = pf.proxy(0).distrib_state()
            tokens = [list(h.output_tokens) for h in hs]
            pf.stop()
            return tokens, desc["traces"], state

        on_tokens, on_traces, on_state = run(telemetry=True)
        off_tokens, off_traces, off_state = run(telemetry=False)
        assert on_tokens == off_tokens, \
            "telemetry changed the greedy tokens"
        assert sum(on_traces.values()) == sum(off_traces.values()) == 0
        assert on_state["telemetry"] is True
        assert on_state["merge"]["applied"] > 0
        assert off_state["telemetry"] is False
        assert off_state["merge"]["applied"] == 0
        # wire attribution stays on with streaming off (stamps ride the
        # replies either way); only step records may hit the mirror
        assert all(e["name"] == "step_record"
                   for e in off_state["mirror"]["events"])
