"""Profiler summary statistics (VERDICT r4 missing #8; reference
python/paddle/profiler/profiler_statistic.py sortable per-op tables)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.profiler.statistic import (HostOpRecorder, OpStat,
                                           summary_table)


class TestHostOpStats:
    def test_summary_reports_dispatched_ops(self, tmp_path):
        prof = profiler.Profiler(timer_only=True)
        prof._log_dir = str(tmp_path)
        prof.start()
        x = paddle.to_tensor(np.ones((4, 4), np.float32))
        w = paddle.to_tensor(np.ones((4, 4), np.float32))
        for _ in range(3):
            paddle.matmul(x, w)
            paddle.tanh(x)
            prof.step()
        prof.stop()
        report = prof.summary(time_unit="us")
        assert "Host operator summary" in report
        assert "matmul" in report and "tanh" in report
        assert prof._host_recorder.stats["matmul"].calls == 3
        assert "steps: 3" in report
        # sort by avg puts columns in play without crashing
        rep2 = prof.summary(sorted_by=profiler.SortedKeys.CPUAvg)
        assert "Ratio(%)" in rep2

    def test_timer_hook_uninstalled_after_stop(self):
        from paddle_tpu.core import dispatch

        prof = profiler.Profiler(timer_only=True)
        prof.start()
        assert dispatch._op_timer is not None
        prof.stop()
        assert dispatch._op_timer is None
        paddle.tanh(paddle.to_tensor(np.ones(2, np.float32)))  # no timing
        assert prof._host_recorder.stats.get("tanh") is None

    def test_summary_table_sorting_and_ratio(self):
        a, b = OpStat("aa"), OpStat("bb")
        for dt in (0.002, 0.004):
            a.add(dt)
        b.add(0.010)
        table = summary_table({"aa": a, "bb": b}, "T",
                              sorted_by=profiler.SortedKeys.CPUTotal)
        lines = [ln for ln in table.splitlines() if ln.startswith(("aa", "bb"))]
        assert lines[0].startswith("bb")  # total 10ms > 6ms
        assert "62.50" in lines[0]        # 10/16 ratio
        table_max = summary_table({"aa": a, "bb": b}, "T",
                                  sorted_by=profiler.SortedKeys.CPUMax)
        lines = [ln for ln in table_max.splitlines()
                 if ln.startswith(("aa", "bb"))]
        assert lines[0].startswith("bb")  # max 10ms > 4ms

    def test_recorder_aggregates(self):
        r = HostOpRecorder()
        r("op", 0.5); r("op", 1.5)
        s = r.stats["op"]
        assert s.calls == 2 and s.total == 2.0
        assert s.avg == 1.0 and s.max == 1.5 and s.min == 0.5

    def test_timer_only_summary_never_reads_foreign_traces(self, tmp_path):
        # a timer_only profiler captured no trace: its summary must not
        # pick up a stale run sitting in the (shared) log dir
        import gzip
        import json
        import os

        run = tmp_path / "plugins" / "profile" / "stale_run"
        run.mkdir(parents=True)
        with gzip.open(str(run / "d.trace.json.gz"), "wt") as f:
            json.dump({"traceEvents": [
                {"ph": "M", "name": "process_name", "pid": 1,
                 "args": {"name": "/device:TPU:0"}},
                {"ph": "X", "name": "stale_op", "pid": 1, "tid": 1,
                 "ts": 0, "dur": 10}]}, f)
        prof = profiler.Profiler(timer_only=True)
        prof._log_dir = str(tmp_path)
        prof.start()
        paddle.tanh(paddle.to_tensor(np.ones(2, np.float32)))
        prof.stop()
        report = prof.summary()
        assert "stale_op" not in report

    def test_device_stats_from_trace_fixture(self, tmp_path):
        import gzip
        import json

        from paddle_tpu.profiler.statistic import collect_device_stats

        # one device lane and one host lane, in the chrome format
        # ``jax.profiler`` writes
        run = tmp_path / "plugins" / "profile" / "fixture_run"
        run.mkdir(parents=True)
        with gzip.open(str(run / "device.trace.json.gz"), "wt") as f:
            json.dump({"traceEvents": [
                {"ph": "M", "name": "process_name", "pid": 1,
                 "args": {"name": "/device:TPU:0"}},
                {"ph": "M", "name": "process_name", "pid": 2,
                 "args": {"name": "python host"}},
                {"ph": "X", "name": "python_dispatch", "pid": 2, "tid": 1,
                 "ts": 900, "dur": 5000},
                {"ph": "X", "name": "dot_general.7", "pid": 1, "tid": 1,
                 "ts": 1000, "dur": 300},
                {"ph": "X", "name": "fusion.12", "pid": 1, "tid": 1,
                 "ts": 1300, "dur": 200}]}, f)
        dev = collect_device_stats(str(tmp_path))
        assert dev["dot_general.7"].total == pytest.approx(300e-6)
        assert "python_dispatch" not in dev  # host lane excluded
