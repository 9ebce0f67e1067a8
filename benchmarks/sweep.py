#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip.

    python benchmarks/sweep.py --workload <name> --rates 2.5,3.0,... [--seconds 48]

One launcher, one set-up; the cell's own traffic is then offered at each
rate in turn (lead-in, a window of ``--seconds``, drain) and what the
client saw is printed and written to ``chiprun_out/sweep_<traffic>.json``:
requests in flight when the window opened, at its middle and when it
closed, TTFT and TPOT in the first and second half of the window, rows a
decode step.  :func:`knee` reads the knee off those rows; the builder
writes it into the traffic file with its criterion (``--set-rate``) and
keeps the output under ``benchmarks/sweeps/``.  Not part of a measured run.

The knee of a latency cell is where latency leaves its plateau, not where
the backlog grows without bound: the engine pads the decode batch to powers
of two, a step over 16 rows costs half as much again, requests then stay
longer and the batch settles in the next bucket.  In-flight counts grow
through a window on the way to that new level, so "the backlog did not
grow" read from one window cannot tell the two apart (PERF.md section 5).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import harness, stats       # noqa: E402
from benchmarks import run as bench_run      # noqa: E402


def in_flight(timelines, when):
    return sum(1 for t in timelines
               if t["sent"] <= when and (t["end"] is None or t["end"] > when))


def half(timelines, lo, hi, fn, q):
    vals = [fn(t) for t in timelines if t["ok"] and lo <= t["due"] < hi]
    return stats.percentile([v for v in vals if v is not None], q)


PLATEAU = 1.5       # a rate is on the plateau while TPOT <= 1.5 x the lowest rate's


def tpot(row) -> float:
    return sum(row["tpot_p50_ms"]) / 2


def knee(rows) -> dict:
    """The highest swept rate up to which every rate ran clean (nothing
    failed, nothing compiled) with TPOT on the plateau of the lowest rate
    swept, and the criterion in words for the traffic file."""
    rows = sorted(rows, key=lambda r: r["rate_rps"])
    base, best, past = tpot(rows[0]), None, None
    for r in rows:
        if r["failed"] or r["compiles"] or tpot(r) > PLATEAU * base:
            past = r
            break
        best = r
    if best is None:
        raise ValueError("the lowest rate swept is already past the knee")
    why = (f"highest swept rate with TPOT p50 within {PLATEAU} x that of the "
           f"lowest rate swept ({base:.1f} ms at {rows[0]['rate_rps']} req/s): "
           f"{tpot(best):.1f} ms at {best['rate_rps']}")
    if past is not None:
        why += (f", {tpot(past):.1f} ms and {past['rows_per_step']:.1f} rows "
                f"a step at {past['rate_rps']}")
    return {"knee_rps": best["rate_rps"], "knee_criterion": why}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=48.0)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--platform", default="tpu")
    p.add_argument("--set-rate", type=float, default=0.0,
                   help="write the knee, and this share of it as the rate, "
                        "into the cell's traffic file")
    args = p.parse_args(argv)
    cell = harness.Cell(args.workload, harness.ROOT)
    kind = cell.module("traffic_kinds", cell.traffic["kind"])
    child = bench_run.Child(
        [sys.executable, os.path.join(HERE, "launcher.py"), "--root",
         harness.ROOT, "--workload", args.workload, "--seed", str(args.seed),
         "--platform", args.platform], bench_run.child_env(harness.ROOT))
    rows = []
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(harness.ROOT, "chiprun_out",
                        f"sweep_{cell.workload['traffic']}.json")
    try:
        ready = child.expect("ready", bench_run.READY_TIMEOUT_S)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            # a seed of its own: the same prompts twice would hit the prefix
            # cache and compile its resume programs in the window
            env = bench_run.RunEnv(cell, child, ready["port"],
                                   args.seed + 1000 * i, args.seconds, 0.0)
            env.mix = dict(cell.traffic, rate_rps=rate, lead_in_s=10,
                           lead_out_s=0.5)
            res = kind.run(env)
            tl, a, b = res["timelines"], res["t_open"], res["t_close"]
            mid = (a + b) / 2
            win = child.ask({"cmd": "report"}, "report", 60.0)["window"]
            row = {"rate_rps": rate, "seconds": args.seconds,
                   "in_flight_open": in_flight(tl, a),
                   "in_flight_mid": in_flight(tl, mid),
                   "in_flight_close": in_flight(tl, b),
                   "failed": sum(1 for t in tl if t["end"] and not t["ok"]),
                   "ttft_p50_ms": [half(tl, a, mid, stats.ttft_ms, 50),
                                   half(tl, mid, b, stats.ttft_ms, 50)],
                   "ttft_p90_ms": [half(tl, a, mid, stats.ttft_ms, 90),
                                   half(tl, mid, b, stats.ttft_ms, 90)],
                   "tpot_p50_ms": [half(tl, a, mid, stats.tpot_ms, 50),
                                   half(tl, mid, b, stats.tpot_ms, 50)],
                   "rows_per_step": (win["probe"]["decode_rows"]
                                     / max(1, win["probe"]["decode_launches"])),
                   "compiles": win["compiles"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
            with open(path, "w") as f:
                json.dump({"workload": args.workload, "rows": rows,
                           "device": ready["device"],
                           "setup_split": ready["split"]}, f, indent=1)
            time.sleep(5.0)
    finally:
        child.stop()
    found = knee(rows)
    print(json.dumps(found))
    if args.set_rate:
        mix = dict(cell.traffic, **found, rate_share_of_knee=args.set_rate,
                   rate_rps=round(args.set_rate * found["knee_rps"], 2))
        with open(cell.traffic_path, "w") as f:
            json.dump(mix, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
