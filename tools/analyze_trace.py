"""MFU gap analysis from a jax.profiler chrome trace.

Usage: ``python tools/analyze_trace.py [trace_dir] [n_steps]``

Reads the newest ``plugins/profile/*/ *.trace.json.gz`` under ``trace_dir``
(default ``prof_trace``, as written by ``tools/profile_train.py``), buckets
device-lane op time into coarse categories (MXU matmul/fusion, pallas
custom calls, copies/transposes, collectives, host gaps) and prints the
step-time breakdown the MFU gap analysis needs.  Pure stdlib — the
tensorboard_plugin_profile converter in this image has a protobuf version
conflict, and the chrome trace carries everything we need.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
import sys

_CATEGORIES = [
    ("pallas", re.compile(r"pallas|custom-call|mosaic", re.I)),
    ("matmul/conv (MXU)", re.compile(r"^(dot|conv|fusion.*dot)|dot_general", re.I)),
    ("fusion (mixed)", re.compile(r"^(loop_)?fusion", re.I)),
    ("copy/transpose", re.compile(r"copy|transpose|bitcast|reshape", re.I)),
    ("collectives", re.compile(r"all-reduce|all-gather|reduce-scatter|"
                               r"collective|permute", re.I)),
    ("dynamic-update/scatter", re.compile(r"scatter|dynamic-update", re.I)),
    ("infeed/outfeed/host", re.compile(r"infeed|outfeed|transfer", re.I)),
]


def _bucket(name: str) -> str:
    for label, pat in _CATEGORIES:
        if pat.search(name):
            return label
    return "other"


def analyze(trace_dir: str = "prof_trace", n_steps: int = 3) -> dict:
    """Parse the newest chrome trace under ``trace_dir`` into the category
    breakdown.  Returns {run, pids, device_pids, by_cat, by_name, wall,
    busy} (durations in trace microseconds) — the testable core
    (tests/test_mfu_accounting.py pins it against a hand-built fixture)."""
    runs = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*")))
    if not runs:
        raise SystemExit(f"no profile runs under {trace_dir}")
    run = runs[-1]
    traces = glob.glob(os.path.join(run, "*.trace.json.gz"))
    if not traces:
        raise SystemExit(f"no trace.json.gz in {run}")
    events = []
    pids = {}
    for path in traces:
        data = json.load(gzip.open(path))
        for e in data.get("traceEvents", []):
            if e.get("ph") == "M" and e.get("name") == "process_name":
                pids[e["pid"]] = e["args"].get("name", str(e["pid"]))
            elif e.get("ph") == "X":
                events.append(e)

    device_pids = {p for p, n in pids.items()
                   if "TPU" in n.upper() or "/device" in n.lower()}
    if not device_pids:  # CPU smoke: fall back to the busiest process
        device_pids = set(pids)
    dev = [e for e in events if e["pid"] in device_pids]
    if not dev:
        raise SystemExit("no device events")

    # device lanes overlap (compute vs DMA); bucket by self duration
    by_cat = collections.Counter()
    by_name = collections.Counter()
    for e in dev:
        d = e.get("dur", 0)
        by_cat[_bucket(e.get("name", "?"))] += d
        by_name[e.get("name", "?")] += d
    t0 = min(e["ts"] for e in dev)
    t1 = max(e["ts"] + e.get("dur", 0) for e in dev)
    return {"run": run, "pids": pids, "device_pids": device_pids,
            "by_cat": by_cat, "by_name": by_name,
            "wall": t1 - t0, "busy": sum(by_cat.values())}


def main(trace_dir: str = "prof_trace", n_steps: int = 3) -> None:
    res = analyze(trace_dir, n_steps)
    run, pids, device_pids = res["run"], res["pids"], res["device_pids"]
    by_cat, by_name = res["by_cat"], res["by_name"]
    wall, busy = res["wall"], res["busy"]

    print(f"run: {run}")
    print(f"devices: {sorted(pids[p] for p in device_pids)}")
    print(f"wall (first..last device event): {wall/1e3:.2f} ms "
          f"({wall/1e3/max(n_steps,1):.2f} ms/step over {n_steps} steps)")
    print(f"summed op time: {busy/1e3:.2f} ms "
          f"(lanes overlap; > wall is normal)\n")
    print(f"{'category':28s} {'ms':>10s} {'% of ops':>9s}")
    for cat, d in by_cat.most_common():
        print(f"{cat:28s} {d/1e3:10.2f} {100*d/max(busy,1):8.1f}%")
    print(f"\ntop ops:")
    for name, d in by_name.most_common(15):
        print(f"  {d/1e3:9.2f} ms  {name[:90]}")
    print(json.dumps({
        "wall_ms_per_step": round(wall / 1e3 / max(n_steps, 1), 3),
        "categories_ms": {k: round(v / 1e3, 3) for k, v in by_cat.items()},
    }))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "prof_trace",
         int(sys.argv[2]) if len(sys.argv) > 2 else 3)
