"""A pause of the serving process names itself (ISSUE 39):
``observability/pauses.py`` ``PauseMonitor``, alone on hand-made replicas
and wired into a real fleet with a planted ``slow_step``."""

import gc
import json
import logging
import threading
import time
from types import SimpleNamespace

import pytest

from paddle_tpu.observability.flight import FlightConfig, FlightRecorder
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.pauses import (
    METRIC_NAMES,
    PAUSE_KINDS,
    PauseMonitor,
    top_frames,
)

LOGGER = "paddle_tpu.serving"


def _replica(index=0, work=True):
    return SimpleNamespace(
        index=index, steps_done=0, thread=threading.current_thread(),
        engine=SimpleNamespace(
            scheduler=SimpleNamespace(has_work=lambda: work),
            step_seq=41, _launch_seq=40))


def _monitor(replicas, tmp_path=None, **kw):
    reg = MetricsRegistry()
    flight = FlightRecorder(registry=reg, config=FlightConfig(
        dump_dir=None if tmp_path is None else str(tmp_path)))
    mon = PauseMonitor(reg, lambda: replicas, flight=flight,
                       loop_thread=threading.get_ident(), **kw)
    return mon, reg, flight


def _series(reg, kind):
    return (reg.counter("serving_pauses_total", kind=kind).value,
            reg.counter("serving_pause_seconds_total", kind=kind).value,
            reg.gauge("serving_pause_max_seconds", kind=kind).value)


def _pause_lines(caplog):
    return [r.getMessage() for r in caplog.records
            if r.name == LOGGER and r.getMessage().startswith("pause ")]


def _ring(flight, replica="0"):
    return [e for e in flight._build_bundle("test", replica, None)["events"]
            if e["name"].startswith("pause")]


def test_the_series_exist_before_any_pause():
    mon, reg, _ = _monitor([_replica()])
    page = reg.prometheus_text()
    for name in METRIC_NAMES:
        assert name in page
    assert all(_series(reg, k) == (0.0, 0.0, 0.0) for k in PAUSE_KINDS)


def test_a_delayed_tick_is_a_process_freeze(caplog):
    r = _replica()
    mon, reg, flight = _monitor([r])
    with caplog.at_level(logging.WARNING, logger=LOGGER):
        mon.tick(10.0, 0.05)            # on time
        r.steps_done += 1
        mon.tick(10.4, 0.05 + 0.31)     # 0.31 s late: nothing ran
    assert _series(reg, "process_freeze") == pytest.approx((1, 0.31, 0.31))
    assert _pause_lines(caplog) == ["pause kind=process_freeze seconds=0.31"]
    assert [e["kind"] for e in _ring(flight)] == ["process_freeze"]
    # under the threshold is jitter, not an event
    mon.tick(10.7, 0.05 + 0.2)
    assert _series(reg, "process_freeze")[0] == 1


def test_time_the_process_stood_still_is_no_replicas_stall(caplog):
    r = _replica()
    mon, reg, _ = _monitor([r])
    with caplog.at_level(logging.WARNING, logger=LOGGER):
        mon.tick(10.0, 0.05)
        mon.tick(12.05, 2.05)           # frozen 2 s, steps_done still
        mon.tick(12.1, 0.05)
    assert _series(reg, "process_freeze")[0] == 1
    assert _series(reg, "engine_stall")[0] == 0
    # 0.5 s of the replica's OWN stillness later it is one
    with caplog.at_level(logging.WARNING, logger=LOGGER):
        mon.tick(12.56, 0.05)
    assert _series(reg, "engine_stall")[0] == 1


def test_an_engine_stall_is_counted_when_seen_and_logged_when_over(caplog):
    r = _replica()
    mon, reg, flight = _monitor([r])
    with caplog.at_level(logging.WARNING, logger=LOGGER):
        mon.tick(20.0, 0.05)
        mon.tick(20.45, 0.05)
        assert _series(reg, "engine_stall")[0] == 0
        mon.tick(20.5, 0.05)            # 0.5 s still, with work to do
        mon.tick(21.0, 0.05)            # seen once, however long it lasts
        assert _series(reg, "engine_stall") == (1, 0.0, 0.0)
        assert _pause_lines(caplog) == []
        r.steps_done += 1
        mon.tick(25.92, 0.05)
    assert _series(reg, "engine_stall") == pytest.approx((1, 5.92, 5.92))
    [line] = _pause_lines(caplog)
    assert line.startswith(
        "pause kind=engine_stall seconds=5.92 step=41 launch=40 engine=")
    # this test's own frame: both stacks are this thread's here
    assert "test_pause_monitor.py" in line and " loop=" in line
    names = [(e["name"], e["kind"]) for e in _ring(flight)]
    assert names == [("pause", "engine_stall"), ("pause", "engine_stall")]
    assert "seconds" not in _ring(flight)[0]       # seen: no length yet
    assert _ring(flight)[1]["seconds"] == pytest.approx(5.92)


def test_an_idle_replica_is_no_stall():
    r = _replica(work=False)
    mon, reg, _ = _monitor([r])
    for i in range(30):
        mon.tick(30.0 + 0.05 * i, 0.05)
    assert _series(reg, "engine_stall")[0] == 0


def test_clean_steps_log_nothing(caplog):
    r = _replica()
    mon, reg, flight = _monitor([r])
    with caplog.at_level(logging.WARNING, logger=LOGGER):
        for i in range(200):
            r.steps_done += 1
            mon.tick(40.0 + 0.05 * i, 0.05 + 0.004 * (i % 3))
    assert _pause_lines(caplog) == [] and _ring(flight) == []
    assert all(_series(reg, k)[0] == 0 for k in PAUSE_KINDS)


def test_a_stall_open_at_shutdown_has_its_line(caplog):
    r = _replica()
    mon, reg, _ = _monitor([r])
    mon.tick(50.0, 0.05)
    mon.tick(50.6, 0.05)
    with caplog.at_level(logging.WARNING, logger=LOGGER):
        mon.stop()
    assert len(_pause_lines(caplog)) == 1
    assert _series(reg, "engine_stall")[0] == 1


@pytest.mark.parametrize("threshold, events", [(0.0, 1), (3600.0, 0)])
def test_a_forced_collection_is_a_sample_and_over_the_threshold_an_event(
        threshold, events, caplog):
    mon, reg, flight = _monitor([_replica()], gc_pause_s=threshold,
                                tick_s=3600.0)
    hist = reg.histogram("serving_gc_pause_seconds", generation="2")
    mon.start()
    try:
        assert mon._on_gc in gc.callbacks
        before = hist.count
        with caplog.at_level(logging.WARNING, logger=LOGGER):
            gc.collect()
            mon.tick(60.0, 3600.0)      # the monitor thread's next tick
    finally:
        mon.stop()
    assert hist.count == before + 1 and hist.sum > 0
    assert _series(reg, "gc_pause")[0] == events
    lines = [m for m in _pause_lines(caplog) if "gc_pause" in m]
    assert len(lines) == events
    if events:
        assert "generation=2 collected=" in lines[0]
        assert _ring(flight)[0]["generation"] == 2
    # a collection's seconds are not a freeze's
    assert _series(reg, "process_freeze")[0] == 0


def test_the_callback_and_the_thread_are_gone_after_stop():
    mon, _, _ = _monitor([_replica()])
    n = len(gc.callbacks)
    mon.start()
    mon.start()                          # idempotent
    thread = mon._thread
    assert len(gc.callbacks) == n + 1 and thread.is_alive()
    assert thread.daemon and thread.name == "serving-pause-monitor"
    mon.stop()
    mon.stop()
    assert len(gc.callbacks) == n and mon._on_gc not in gc.callbacks
    assert not thread.is_alive()


def test_top_frames_are_innermost_first():
    import sys

    def inner():
        return top_frames(sys._getframe(), limit=2)

    got = inner()
    first, second = got.split(" < ")
    assert first.endswith("in inner")
    assert second.endswith("in test_top_frames_are_innermost_first")
    assert top_frames(None) == "-"


class TestInAFleet:
    """The monitor as ``FleetRouter.start`` wires it, a ``slow_step``
    planted through the fleet's fault plan."""

    PROMPT = [5, 6, 7, 8, 9, 10]

    def _run(self, fleet, max_new):
        from paddle_tpu.serving import SamplingParams

        h = fleet.submit_request(
            self.PROMPT, sampling=SamplingParams(max_new_tokens=max_new))
        fleet.wait([h], timeout=120.0)
        assert h.finished
        return h

    def test_a_planted_slow_step_names_itself_once(self, caplog, tmp_path):
        from paddle_tpu.serving.faultinject import FaultPlan, FaultSpec
        from paddle_tpu.serving.server import _toy_fleet

        fleet = _toy_fleet(dp=1, flight_dir=str(tmp_path),
                           fault_plan=FaultPlan([FaultSpec(
                               "slow_step", step=110, duration_s=1.5)]))
        mon = fleet.pauses
        # a loaded CPU must not make a warm step of the toy model a stall
        mon.stall_s = 0.75
        fleet.start()
        try:
            assert mon._thread.is_alive() and mon._on_gc in gc.callbacks
            assert mon.loop_thread == threading.get_ident()
            # every program compiled, the prefix cache's resume program
            # (the same prompt again) too: a step that compiles IS a stall
            self._run(fleet, 40)
            self._run(fleet, 40)
            eng = fleet.replicas[0].engine
            assert eng.step_seq < 110
            time.sleep(3 * mon.tick_s)
            stalls = _series(fleet.registry, "engine_stall")[0]
            caplog.clear()      # a cold step that compiled is a stall too
            with caplog.at_level(logging.WARNING, logger=LOGGER):
                self._run(fleet, 40)    # crosses step 110
                time.sleep(3 * mon.tick_s)
            assert eng.step_seq > 110
            n, seconds, longest = _series(fleet.registry, "engine_stall")
            assert n == stalls + 1 and longest >= 1.4
            lines = [m for m in _pause_lines(caplog)
                     if "kind=engine_stall" in m]
            assert len(lines) == 1
            assert "step=110" in lines[0]
            # the engine thread's stack names the sleeping frame
            engine = lines[0].split(" engine=")[1].split(" loop=")[0]
            assert "faultinject.py" in engine and "in begin_step" in engine
            # and a bundle of ANY trigger holds the pause before it
            path = fleet.flight.trigger("watchdog", replica="0")
            events = json.load(open(path))["events"]
            assert [e for e in events if e["name"] == "pause"
                    and e.get("kind") == "engine_stall"
                    and e.get("step") == 110]
            # it restarted nothing and marked nothing unhealthy
            assert fleet.replicas[0].healthy
        finally:
            fleet.shutdown(drain_timeout=2.0)
        assert mon._thread is None and mon._on_gc not in gc.callbacks
        assert "serving-pause-monitor" not in [
            t.name for t in threading.enumerate()]


def test_a_dead_workers_proxy_is_no_stall_and_does_not_end_the_monitor():
    """``procfleet``'s scheduler proxy RAISES from ``has_work`` once its
    worker process died: that is the replica's death path's to handle."""
    def died():
        raise RuntimeError("worker 0 (pid 1) is dead")

    r = _replica()
    r.engine.scheduler.has_work = died
    mon, reg, _ = _monitor([r])
    for i in range(20):
        mon.tick(70.0 + 0.05 * i, 0.05)
    assert _series(reg, "engine_stall")[0] == 0


def test_a_collection_is_not_a_freeze_as_well(monkeypatch):
    """Leaving the ``proc.gc`` span may give the interpreter lock away: the
    monitor thread, late by the collection itself, ticks THEN and must
    already find the sample (on the chip it read a freeze of 0.43 s beside
    every collection of 0.44 s before the sample went first)."""
    from paddle_tpu.observability import pauses

    mon, reg, _ = _monitor([_replica()], gc_pause_s=0.0)

    class Span:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            # the monitor's tick, late by exactly the collection
            _, dt, _ = mon._collections[-1]
            mon.tick(80.0, mon.tick_s + dt)

    monkeypatch.setattr(pauses.SpanTracer, "phase",
                        staticmethod(lambda *a, **k: Span()))
    mon._on_gc("start", {"generation": 2})
    mon._gc_t0 -= 0.44              # as if it had begun 0.44 s ago
    mon._on_gc("stop", {"generation": 2, "collected": 0})
    n, seconds, _ = _series(reg, "gc_pause")
    assert n == 1 and seconds == pytest.approx(0.44, abs=0.01)
    assert _series(reg, "process_freeze")[0] == 0


def test_a_collection_drained_late_cannot_make_a_stall_negative(caplog):
    """A collection that ends between the tick's clock read and its drain
    is counted in that tick: the stall's start never passes the tick."""
    r = _replica()
    mon, reg, _ = _monitor([r])
    mon.tick(90.0, 0.05)
    mon.tick(90.6, 0.05)                 # the stall is seen
    mon._collections.append((2, 5.0, 0))     # "ended" after 90.65 was read
    mon.tick(90.65, 0.05)
    assert mon._progress[0][1] == 90.65      # not 95.0: never past the tick
    r.steps_done += 1
    with caplog.at_level(logging.WARNING, logger=LOGGER):
        mon.tick(90.7, 0.05)
    n, seconds, _ = _series(reg, "engine_stall")
    assert n == 1 and seconds == pytest.approx(0.05)


def test_a_tick_inside_an_open_collection_judges_nothing(caplog):
    """JAX's own collector callback can give the interpreter lock away
    between a collection's end and our "stop": the late monitor ticks
    with the collection still open (on the chip: a freeze of 0.45 s
    beside a collection of 0.48 s).  That tick waits for the sample."""
    mon, reg, _ = _monitor([_replica()], gc_pause_s=0.0)
    mon.start()
    try:
        assert gc.callbacks[0] == mon._on_gc     # before JAX's own
    finally:
        mon.stop()
    mon._on_gc("start", {"generation": 2})
    mon._gc_t0 -= 0.48
    assert mon.tick(100.0, mon.tick_s + 0.48) is False   # still open
    assert _series(reg, "process_freeze")[0] == 0
    mon._on_gc("stop", {"generation": 2, "collected": 0})
    # the next tick covers both intervals, and finds the sample
    assert mon.tick(100.05, 2 * mon.tick_s + 0.48) is True
    assert _series(reg, "gc_pause")[0] == 1
    assert _series(reg, "process_freeze")[0] == 0
