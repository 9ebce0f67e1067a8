#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the machine it is started on.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process is the CLIENT: it never imports JAX.  It starts one child,
``launcher.py``, which holds the chip, builds and serves the cell's model
through the program's real server, and is stopped at the end.  Here the
traffic is generated from the seed, sent over loopback HTTP, timed, and
reduced to the cell's metrics: its ``end_to_end`` metrics with ``--trace
0``, its ``per_layer`` metrics with ``--trace 1`` (the profiler runs in the
child for a few seconds in the middle of the window).  The last line of
stdout is the result as one JSON object.  Exits non-zero, and prints no
result, when the child finds no accelerator or too few chips.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import queue        # noqa: E402
import subprocess   # noqa: E402
import sys          # noqa: E402
import threading    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import (harness, records, roofline, stats,   # noqa: E402
                        trace_reduce)

READY_TIMEOUT_S = 1150.0    # a first run compiles; the contract allows 1200
LATE_WARN_MS = 250.0        # every run but one sent within 22 ms (PERF.md)


def child_env(root: str) -> dict:
    """The launcher's environment: JAX's persistent compilation cache at a
    fixed path inside the checkout, whatever the machine has set, and never
    trimmed (a cache that evicts thrashes on a cell's fifty programs), so
    that only the first run of a cell in a checkout compiles.  The program's
    ``configure_compile_cache`` takes the directory from this variable."""
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_compile_cache")
    env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    return env


class Child:
    """The launcher process and the line protocol to it."""

    def __init__(self, argv, env):
        self.proc = subprocess.Popen(argv, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, env=env, text=True)
        self.lines: "queue.Queue" = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()
        self._lock = threading.Lock()

    def _pump(self):
        for line in self.proc.stdout:
            if line.startswith("@@ "):
                self.lines.put(json.loads(line[3:]))
            else:
                sys.stderr.write(line)
        self.lines.put(None)

    def expect(self, event: str, timeout: float):
        deadline = time.monotonic() + timeout
        while True:
            try:
                msg = self.lines.get(timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                raise TimeoutError(f"launcher: no {event!r} in {timeout:.0f}s")
            if msg is None:
                raise RuntimeError(f"launcher exited with code "
                                   f"{self.proc.wait()} before {event!r}")
            if msg.get("event") == event:
                return msg

    def ask(self, cmd: dict, event: str, timeout: float):
        with self._lock:
            self.proc.stdin.write(json.dumps(cmd) + "\n")
            self.proc.stdin.flush()
            return self.expect(event, timeout)

    def stop(self):
        try:
            if self.proc.poll() is None:
                self.proc.stdin.write('{"cmd": "quit"}\n')
                self.proc.stdin.flush()
                self.proc.wait(timeout=15)
        except (OSError, ValueError, subprocess.TimeoutExpired):
            pass
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait()


class RunEnv:
    """What a traffic kind is given."""

    def __init__(self, cell, child, port, seed, seconds, trace_s):
        self.mix, self.seed, self.seconds = cell.traffic, seed, seconds
        self.vocab = cell.config["vocab_size"]
        self.port, self.trace_s = port, trace_s
        self._child = child

    def mark(self, name: str) -> None:
        self._child.ask({"cmd": "mark", "name": name}, "marked", 30.0)


def client_counters(run: dict) -> dict:
    win = stats.counted(run["timelines"])
    late = [(t["sent"] - t["due"]) * 1e3 for t in win]
    ttft = stats.ttfts_ms(win)
    gaps = [g for t in win for g in stats.token_gaps_ms(t)]
    tpot = [v for v in (stats.tpot_ms(t) for t in win) if v is not None]
    shape = {f"itl_p{q}_ms": stats.percentile(gaps, q)
             for q in (50, 90, 95, 97, 98, 99, 99.5, 99.8, 99.9)}
    shape.update(gaps=len(gaps), tpot_p50_ms=stats.percentile(tpot, 50),
                 tpot_p90_ms=stats.percentile(tpot, 90),
                 ttft_p75_ms=stats.percentile(ttft, 75),
                 ttft_p99_ms=stats.percentile(ttft, 99))
    return {"shape": shape, "late_p99_ms": stats.percentile(late, 99),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "requests_in_window": len(win)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = harness.ROOT, platform: str = "tpu",
             out=sys.stdout, records_path: str = "") -> int:
    cell = harness.Cell(workload, root)
    mix = cell.traffic
    trace_s = float(mix["trace_s"]) if trace else 0.0
    kind = cell.module("traffic_kinds", mix["kind"])
    child = Child([sys.executable, os.path.join(HERE, "launcher.py"),
                   "--root", root, "--workload", workload,
                   "--seed", str(seed), "--trace-seconds", str(trace_s),
                   "--platform", platform], child_env(root))
    try:
        try:
            ready = child.expect("ready", READY_TIMEOUT_S)
        except (RuntimeError, TimeoutError) as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return child.proc.poll() or 3
        env = RunEnv(cell, child, ready["port"], seed, seconds, trace_s)
        run = kind.run(env)
        run["setup_s"] = run["t_open"] - T_START
        report = child.ask({"cmd": "report"}, "report", 300.0)
    finally:
        child.stop()
    t_stopped = time.perf_counter()
    if trace:
        # read once, here, after the child has gone: every reader below
        # is given the same parse (``trace_reduce.once_a_file``)
        path = trace_reduce.find_xplane(os.path.join(root, ".bench_trace"))
        red = trace_reduce.reduce(trace_reduce.load(path)) if path else None
        if red is not None:
            report["trace"] = red
            report["breakdown"] = trace_reduce.breakdown(red)

    if records_path:
        records.write(records_path, run, {"workload": workload, "seed": seed,
                                          "seconds": seconds})
    ended = [t for t in run["timelines"] if t["end"] is not None
             and (mix["kind"] != "open_loop" or t["section"] == "window")]
    failed = [t for t in ended if not t["ok"]]
    for t in failed[:5]:
        print(f"benchmark: failed request: {t['error']}", file=sys.stderr)
    if not run["complete"]:
        print("benchmark: the traffic did not run to its end", file=sys.stderr)
    device = dict(report["device"])
    client = client_counters(run)
    if (client["late_p99_ms"] or 0.0) > LATE_WARN_MS:
        # the line still goes out: a run the machine starved reads far off,
        # and whoever compares runs must see it, with its cause beside it
        print(f"benchmark: the generator sent {client['late_p99_ms']:.0f} ms "
              f"late (99th percentile): this run did not offer the cell's "
              f"schedule", file=sys.stderr)
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            v = cell.module("e2e_metrics", m["name"]).compute(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        red = report.get("trace")
        if red is None and platform == "tpu":
            print("benchmark: the traced run left no device trace",
                  file=sys.stderr)
            return 4
        counters = {"client": client, "model": cell.config,
                    "engine": cell.config["engine"], "device": device,
                    "peaks": roofline.peaks(device["kind"])
                    if platform == "tpu" else {},
                    "window": report["window"], "setup": ready["split"]}
        if "traced" in report:
            counters["traced"] = report["traced"]
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(counters, red)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        print(f"benchmark: traced run: {t_stopped - run['t_close']:.1f} s from "
              f"the window's close until the server had stopped, "
              f"{time.perf_counter() - t_stopped:.1f} s reading the trace",
              file=sys.stderr)
    line = {"correct": bool(ready["check"]["ok"]) and not failed
            and bool(run["complete"]),
            "attempted": len(ended), "failed": len(failed),
            "metrics": metrics, "device": device}
    if trace and "breakdown" in report:
        line["breakdown"] = report["breakdown"]
    line["detail"] = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "check": ready["check"], "setup_split": ready["split"],
        "lead_in_s": mix.get("lead_in_s"), "client": client,
        "traced": report.get("traced"),
        "window": {k: report.get("window", {}).get(k) for k in
                   ("rows_hist", "compiles", "preemptions", "pool_peak_share",
                    "probe")}}
    print(json.dumps(line), file=out, flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--records", default="", metavar="FILE",
                   help="also write the window's per-request records "
                        "(benchmarks/records.py) to FILE; off by default")
    args = p.parse_args(argv)
    # the command line measures on the TPU and nowhere else: there is no
    # option that makes it fall back to another platform
    return run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                    records_path=args.records)


if __name__ == "__main__":
    sys.exit(main())
