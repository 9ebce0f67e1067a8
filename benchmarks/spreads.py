#!/usr/bin/env python3
"""Spreads as the driver takes them, from sets of runs ``repeat.py`` made:
for every set and every statistic the median and the trimmed range (the
range of the six less the run farthest from the median, over the median),
and whether a bound meets PR 27's rule (``stats.meets_rule``).  Every
statistic is re-read from the runs' records, so candidates are compared on
the SAME runs.  No JAX.

    python3 benchmarks/spreads.py DIR [DIR ...]      # markdown on stdout

A DIR holds ``lines.jsonl`` and ``<seed>.json.gz`` (a run without its
records is read from the line it printed); its rows of role ``set`` are cut
into sets of six in the order they ran.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import harness, records, stats      # noqa: E402
from benchmarks import run as bench_run             # noqa: E402

SET = 6


def tail_mean(values, lo_q, hi_q):
    """Arithmetic mean of the order statistics whose zero-based rank lies
    from the ``lo_q``-th to the ``hi_q``-th percentile of the ranks:
    ``ceil(lo_q/100 * (n-1))`` ... ``floor(hi_q/100 * (n-1))``, both ends
    in; ``None`` where no rank lies there.  A candidate's arithmetic, kept
    with the table it fills: no cell reports it."""
    n = len(values)
    xs = sorted(values)
    lo = math.ceil((n - 1) * lo_q / 100.0)
    hi = math.floor((n - 1) * hi_q / 100.0)
    if hi < lo:
        return None
    return float(sum(xs[lo:hi + 1]) / (hi + 1 - lo))


def chat_stats(run):
    """The cell's end-to-end metrics by their own files, the TTFT
    percentiles as ``run.client_counters`` prints them, and the candidates
    beside them: ``ttft_tail_ms`` is ISSUE 27's, the mean of the TTFTs ranked
    from the 80th to the 97.5th percentile (ranks 93-113 of 117),
    ``ttft_top_fifth_ms`` the plain mean from the 80th up."""
    c = bench_run.client_counters(run)
    e2e = {m: harness.load_module("e2e_metrics", m).compute(run)
           for m in ("tpot_p50_ms",)}
    # end to end until PR 42, per-layer since (``frontdoor.itl_p995_ms``)
    e2e["itl_p995_ms"] = c["shape"]["itl_p99.5_ms"]
    e2e["ttft_p90_ms"] = c["ttft_p90_ms"]
    ttft = stats.ttfts_ms(run["timelines"])
    tpot = [v for v in map(stats.tpot_ms, stats.counted(run["timelines"]))
            if v is not None]
    return dict(e2e, ttft_tail_ms=tail_mean(ttft, 80.0, 97.5),
                ttft_top_fifth_ms=tail_mean(ttft, 80.0, 100.0),
                ttft_p75_ms=c["shape"]["ttft_p75_ms"],
                ttft_p50_ms=c["ttft_p50_ms"],
                tpot_mean_ms=statistics.fmean(tpot),
                late_p99_ms=c["late_p99_ms"], requests=len(ttft))


def backlog_stats(run):
    return {"tokens_per_s":
            harness.load_module("e2e_metrics", "tokens_per_s").compute(run)}


def printed_stats(line, kind):
    """What a run printed, for a run whose records were not kept: its
    metrics and, in a chat cell, the TTFT statistics among the client's."""
    vals = {k: v["value"] for k, v in line["metrics"].items()}
    client = (line.get("detail") or {}).get("client") or {}
    if kind == "open_loop":
        found = dict(client, **client.get("shape", {}))
        for k in ("ttft_p90_ms", "ttft_tail_ms", "ttft_p75_ms", "ttft_p50_ms",
                  "late_p99_ms"):
            if found.get(k) is not None:
                vals.setdefault(k, found[k])
    return vals


def read_sets(dirs):
    """[(label, [row, ...])]: a row is a run's seed, statistics, line."""
    sets = []
    for d in dirs:
        rows = []
        with open(os.path.join(d, "lines.jsonl")) as f:
            for text in f:
                r = json.loads(text)
                if r["trace"]:
                    continue
                line = r["line"]
                if not line.get("metrics"):
                    print(f"spreads: seed {r['seed']} left no result "
                          f"(exit code {r['rc']})", file=sys.stderr)
                    continue
                kind = harness.Cell(r["workload"]).traffic["kind"]
                vals = printed_stats(line, kind)
                path = os.path.join(d, f"{r['seed']}.json.gz")
                if os.path.isfile(path):    # else: what the run printed
                    run = records.load(records.read(path))
                    again = (chat_stats if kind == "open_loop"
                             else backlog_stats)(run)
                    for k, v in vals.items():
                        if k in again and abs(again[k] - v) > 2e-3 * max(
                                1.0, abs(v) * 1e-3):
                            raise ValueError(f"seed {r['seed']}: {k} re-read "
                                             f"as {again[k]}, printed {v}")
                    vals = dict(again, setup_s=vals["setup_s"])
                rows.append({"seed": r["seed"], "role": r["role"],
                             "tag": r["tag"], "correct": line["correct"],
                             "failed": line["failed"], "vals": vals})
        good = [r for r in rows if r["role"] == "set"]
        for i in range(0, len(good), SET):
            label = os.path.basename(os.path.normpath(d))
            if len(good) > SET:
                label += f".{i // SET + 1}"
            sets.append((label, good[i:i + SET]))
        sets += [(f"{os.path.basename(os.path.normpath(d))} {r['role']}", [r])
                 for r in rows if r["role"] != "set"]
    return sets


def main(argv=None) -> int:
    sets = read_sets((argv or sys.argv[1:]))
    names = []
    for _, rows in sets:
        for r in rows:
            names += [k for k in r["vals"] if k not in names + ["requests"]]
    print("| set | seed | " + " | ".join(names) + " |")
    print("| --- | --- |" + " --- |" * len(names))
    for label, rows in sets:
        for r in rows:
            print(f"| {label} | {r['seed']} | " + " | ".join(
                f"{r['vals'][k]:.2f}" if k in r["vals"] else "-"
                for k in names) + " |")
    full = [(lab, rows) for lab, rows in sets if len(rows) == SET]
    print()
    print("| statistic | " + " | ".join(
        f"{lab}: median, trimmed range" for lab, _ in full)
        + " | medians apart |")
    print("| --- |" + " --- |" * (len(full) + 1))
    for k in names:
        cols, meds = [], []
        for _, rows in full:
            v = [r["vals"][k] for r in rows if k in r["vals"]]
            if len(v) < SET:        # another cell's, or not printed then
                cols.append("-")
                continue
            meds.append(statistics.median(v))
            cols.append(f"{meds[-1]:.2f}, "
                        f"{100 * stats.trimmed_range_share(v):.2f}%")
        apart = (max(meds) - min(meds)) / statistics.median(meds)
        print(f"| {k} | " + " | ".join(cols) + f" | {100 * apart:.2f}% |")
    print()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for m in bench["end_to_end"]:
        v = [[r["vals"][m["name"]] for r in rows] for _, rows in full
             if all(m["name"] in r["vals"] for r in rows)]
        if m["name"] != "setup_s" and len(v) > 1:
            widest = max(stats.trimmed_range_share(x) for x in v)
            print(f"{m['name']}: bound {m['bound']} "
                  f"{'meets' if stats.meets_rule(v, m['bound']) else 'FAILS'}"
                  f" the rule; twice the widest trimmed range, in whole "
                  f"percent: {math.ceil(200 * widest - 1e-9) / 100}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
