#!/usr/bin/env python3
"""Run one cell several times in one call, each run its own seed, and keep
every result line and the window's records under ``chiprun_out/<tag>/``:
the sets of runs that ``spreads.py`` reads.  This process never imports
JAX; each run is ``run.py`` as ``BENCHMARK.json``'s command gives it.

    python3 benchmarks/repeat.py --workload W --tag T --cold-seed N \\
        --seeds a,b,c,d,e,f [--spare-seeds g,h] [--trace-seeds i,j] \\
        [--budget-s 3150]

The first run of a checkout compiles (``--cold-seed``) and belongs to no
set.  A run whose generator sent later than ``run.LATE_WARN_MS`` did not
offer the schedule: it is kept in the file, marked ``starved``, and a spare
seed runs in its place.  No run starts after ``--budget-s`` seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import harness                      # noqa: E402
from benchmarks.run import LATE_WARN_MS             # noqa: E402


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--tag", required=True)
    p.add_argument("--cold-seed", type=int)
    p.add_argument("--seeds", type=seeds, default=[])
    p.add_argument("--spare-seeds", type=seeds, default=[])
    p.add_argument("--trace-seeds", type=seeds, default=[])
    p.add_argument("--budget-s", type=float, default=3150.0)
    args = p.parse_args(argv)
    t0 = time.monotonic()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    out = os.path.join(harness.ROOT, "chiprun_out", args.tag)
    os.makedirs(out, exist_ok=True)
    todo = [(s, 0, "cold") for s in [args.cold_seed] if s is not None]
    todo += [(s, 0, "set") for s in args.seeds]
    todo += [(s, 1, "trace") for s in args.trace_seeds]
    spare, rc_all = list(args.spare_seeds), 0
    while todo:
        seed, trace, role = todo.pop(0)
        if time.monotonic() - t0 > args.budget_s:
            print(f"repeat: out of budget before seed {seed} ({role})",
                  flush=True)
            rc_all = rc_all or 5
            continue
        cmd = bench["command"] + [
            "--workload", args.workload, "--seed", str(seed), "--seconds",
            str(bench["run_seconds"]), "--trace", str(trace)]
        if not trace:
            cmd += ["--records", os.path.join(out, f"{seed}.json.gz")]
        t_run = time.monotonic()
        r = subprocess.run(cmd, cwd=harness.ROOT, capture_output=True,
                           text=True)
        with open(os.path.join(out, f"{seed}.stderr"), "w") as f:
            f.write(r.stderr[-40000:])
        last = (r.stdout.strip().splitlines() or [""])[-1]
        try:
            line = json.loads(last)
        except ValueError:
            line = {}
        late = ((line.get("detail") or {}).get("client") or {}).get(
            "late_p99_ms")
        starved = late is not None and late > LATE_WARN_MS
        if starved and role == "set" and spare:
            todo.insert(0, (spare.pop(0), 0, "set"))
        row = {"tag": args.tag, "workload": args.workload, "seed": seed,
               "role": "starved" if starved else role, "trace": trace,
               "rc": r.returncode, "wall_s": time.monotonic() - t_run,
               "line": line}
        with open(os.path.join(out, "lines.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
        vals = {k: v["value"] for k, v in (line.get("metrics") or {}).items()
                if not trace or k.startswith(("frontdoor", "loadgen"))}
        print(f"repeat: {role} seed {seed} rc {r.returncode} "
              f"{time.monotonic() - t_run:.0f}s correct "
              f"{line.get('correct')} failed {line.get('failed')} late "
              f"{late} {json.dumps(vals)}", flush=True)
        rc_all = rc_all or r.returncode or (0 if line.get("correct") else 6)
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
