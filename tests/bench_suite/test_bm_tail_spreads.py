"""The spread the driver holds against a bound, the records a run can leave
behind so that it is re-read from runs already made, the candidate that
PR 27 tabulated and did not ship (the mean of the slow fifth's TTFTs, kept
in ``spreads.py`` with the table it fills), and ``ttft_p90_ms`` as the
per-layer ``frontdoor.ttft_p90_ms``."""

import json
import os
import random

import pytest

from benchmarks import harness, records, repeat, run, spreads, stats

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CHAT = "mistral-7b-v0.3.chat-steady"


def test_tail_mean_takes_ranks_93_to_113_of_117():
    # 117 values whose rank is their value's place: 1000 + 10 * rank
    vals = [1000.0 + 10.0 * r for r in range(117)]
    ranks = [93, 94, 95, 96, 97, 98, 99, 100, 101, 102, 103, 104, 105, 106,
             107, 108, 109, 110, 111, 112, 113]
    want = sum(1000.0 + 10.0 * r for r in ranks) / 21
    assert spreads.tail_mean(vals, 80.0, 97.5) == pytest.approx(want) == 2030.0
    # the old 90th percentile (rank 104.4) lies among them; the largest three
    # (ranks 114-116) move nothing
    assert vals[ranks[10]] < stats.percentile(vals, 90) < vals[ranks[12]]
    assert spreads.tail_mean(vals[:114] + [9e9, 9e9, 9e9], 80.0, 97.5) == want


def test_tail_mean_does_not_depend_on_the_order_of_its_input():
    rng = random.Random(5)
    vals = [rng.lognormvariate(4.5, 0.8) for _ in range(117)]
    want = spreads.tail_mean(sorted(vals), 80.0, 97.5)
    for _ in range(5):
        rng.shuffle(vals)
        assert spreads.tail_mean(vals, 80.0, 97.5) == want


@pytest.mark.parametrize("n,lo,hi", [(1, 0, 0), (40, 32, 38), (117, 93, 113),
                                     (400, 320, 389)])
def test_tail_mean_follows_n(n, lo, hi):
    vals = [float(7 * r) for r in range(n)]
    random.Random(n).shuffle(vals)
    assert spreads.tail_mean(vals, 80.0, 97.5) == pytest.approx(
        7.0 * (lo + hi) / 2)
    assert spreads.tail_mean([], 80.0, 97.5) is None
    assert spreads.tail_mean([1.0, 2.0, 3.0], 60.0, 90.0) is None  # no rank there


def _t(due, first, n=4, section="window", ok=True, gap=0.03):
    chunks = [(first + i * gap, 1) for i in range(n)]
    return {"section": section, "due": due, "sent": due + 0.001,
            "chunks": chunks, "end": chunks[-1][0], "ok": ok, "error": None,
            "prompt_len": 10, "max_tokens": n}


def _chat_run(ttfts_ms, shift=0.0):
    tl = [_t(10.0 + 0.4 * i + shift, 10.0 + 0.4 * i + shift + v / 1e3)
          for i, v in enumerate(ttfts_ms)]
    return {"timelines": tl, "t_open": 10.0 + shift, "t_close": 58.0 + shift,
            "complete": True}


def test_ttfts_count_only_whole_requests_due_in_the_window():
    ttfts = [50.0 + 3.0 * r for r in range(117)]
    random.Random(3).shuffle(ttfts)
    r = _chat_run(ttfts)
    # slow requests of the lead-in and lead-out, a failed one and one that
    # sent no token at all: none of them is in any TTFT statistic
    r["timelines"] += [_t(1.0, 9.0, section="lead_in"),
                       _t(59.0, 69.0, section="lead_out"),
                       _t(20.0, 29.0, ok=False)]
    empty = _t(21.0, 30.0, ok=False)
    empty["chunks"] = []
    r["timelines"].append(empty)
    assert sorted(stats.ttfts_ms(r["timelines"])) == pytest.approx(
        sorted(ttfts))
    c = run.client_counters(r)
    assert c["ttft_p90_ms"] == pytest.approx(stats.percentile(ttfts, 90))
    assert c["requests_in_window"] == 117
    vals = spreads.chat_stats(r)
    assert vals["ttft_p90_ms"] == c["ttft_p90_ms"]
    assert vals["ttft_tail_ms"] == pytest.approx(
        sum(50.0 + 3.0 * k for k in range(93, 114)) / 21)


def test_no_metric_file_is_left_without_its_entry():
    for group, sub in (("end_to_end", "e2e_metrics"),):
        files = {f[:-3] for f in os.listdir(os.path.join(harness.HERE, sub))
                 if f.endswith(".py")}
        assert files == {m["name"] for m in BENCH[group]}


def test_every_moves_names_a_metric_every_cell_of_its_reports():
    cells = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]


@pytest.mark.parametrize("name", ["r.json", "deep/er/r.json.gz"])
def test_records_round_trip_a_run(tmp_path, name):
    ttfts = [40.0 + 2.5 * r for r in range(60)]
    r = _chat_run(ttfts, shift=12345.678)
    r["timelines"].append(_t(12345.0, 12346.0, section="lead_in"))
    r["timelines"].append(dict(_t(12360.0, 12361.0, ok=False), end=None))
    path = str(tmp_path / name)
    records.write(path, r, {"workload": CHAT, "seed": 7, "seconds": 48.0})
    rec = records.read(path)
    assert (rec["workload"], rec["seed"], rec["open"]) == (CHAT, 7, 0.0)
    assert rec["close"] == pytest.approx(48.0)
    back = records.load(rec)
    assert len(back["timelines"]) == len(r["timelines"])
    assert back["timelines"][-1]["end"] is None
    for m in ("tpot_p50_ms", "tokens_per_s"):
        compute = harness.load_module("e2e_metrics", m).compute
        assert compute(back) == pytest.approx(compute(r), abs=2e-3)
    assert run.client_counters(back)["shape"]["itl_p99.5_ms"] == \
        pytest.approx(run.client_counters(r)["shape"]["itl_p99.5_ms"],
                      abs=2e-3)
    assert run.client_counters(back)["ttft_p90_ms"] == pytest.approx(
        run.client_counters(r)["ttft_p90_ms"], abs=2e-3)


def test_trimmed_range_leaves_out_the_run_farthest_from_the_median():
    v = [178.0, 176.0, 180.0, 193.0, 177.0, 179.0]
    assert stats.trimmed_range_share(v) == pytest.approx(4.0 / 178.5)
    assert stats.trimmed_range_share([1.0, 2.0]) is None
    steady = [100.0, 101.0, 102.0, 100.5, 101.5, 110.0]
    assert stats.meets_rule([steady, [101.0, 102, 103, 101, 102, 102]], 0.05)
    assert not stats.meets_rule([steady, [100.0, 104, 101, 102, 96, 103]], 0.05)
    # each set steady, but the sets' medians 3% apart
    assert not stats.meets_rule([steady, [v + 3.0 for v in steady]], 0.05)


def _fake_line(r, late=1.0):
    vals = spreads.chat_stats(r)
    return {"correct": True, "failed": 0, "attempted": 117,
            "metrics": {"ttft_p90_ms": {"value": vals["ttft_p90_ms"],
                                        "unit": "ms"},
                        "setup_s": {"value": 115.0, "unit": "s"}},
            "detail": {"client": {"late_p99_ms": late}}}


def test_spreads_reads_sets_of_six_from_a_directory(tmp_path):
    d = tmp_path / "chatX"
    d.mkdir()
    rng = random.Random(11)
    with open(d / "lines.jsonl", "w") as f:
        for i in range(14):
            ttfts = [rng.lognormvariate(4.5, 0.6) for _ in range(117)]
            r = _chat_run(ttfts)
            role = "cold" if i == 0 else "starved" if i == 4 else "set"
            records.write(str(d / f"{900 + i}.json.gz"), r,
                          {"workload": CHAT, "seed": 900 + i, "seconds": 48})
            f.write(json.dumps({"tag": "chatX", "workload": CHAT,
                                "seed": 900 + i, "role": role, "trace": 0,
                                "rc": 0, "wall_s": 190.0,
                                "line": _fake_line(r)}) + "\n")
    sets = spreads.read_sets([str(d)])
    assert [(lab, len(rows)) for lab, rows in sets] == [
        ("chatX.1", 6), ("chatX.2", 6), ("chatX cold", 1),
        ("chatX starved", 1)]
    assert [r["seed"] for r in sets[0][1]] == [901, 902, 903, 905, 906, 907]
    row = sets[0][1][0]["vals"]
    assert row["ttft_top_fifth_ms"] > row["ttft_tail_ms"] > row["ttft_p75_ms"]
    assert spreads.main([str(d)]) == 0


def test_repeat_replaces_a_starved_run_and_stops_at_its_budget(
        tmp_path, monkeypatch):
    calls = []

    class Done:
        returncode, stderr = 0, "late\n"

        def __init__(self, seed):
            late = 3000.0 if seed == 12 else 1.0
            self.stdout = "noise\n" + json.dumps(
                {"correct": True, "failed": 0, "metrics": {
                    "setup_s": {"value": 1.0, "unit": "s"}},
                 "detail": {"client": {"late_p99_ms": late}}}) + "\n"

    def fake_run(cmd, **kw):
        calls.append(cmd)
        return Done(int(cmd[cmd.index("--seed") + 1]))

    monkeypatch.setattr(repeat.subprocess, "run", fake_run)
    monkeypatch.setattr(repeat.harness, "ROOT", str(tmp_path))
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(BENCH, f)
    rc = repeat.main(["--workload", CHAT, "--tag", "t", "--cold-seed", "10",
                      "--seeds", "11,12,13", "--spare-seeds", "99",
                      "--trace-seeds", "20"])
    assert rc == 0
    rows = [json.loads(x) for x in open(tmp_path / "chiprun_out/t/lines.jsonl")]
    assert [(r["seed"], r["role"]) for r in rows] == [
        (10, "cold"), (11, "set"), (12, "starved"), (99, "set"), (13, "set"),
        (20, "trace")]
    assert calls[0][:2] == BENCH["command"] and "--records" in calls[0]
    assert calls[-1][calls[-1].index("--trace") + 1] == "1"
    assert "--records" not in calls[-1]
    assert str(BENCH["run_seconds"]) == calls[0][calls[0].index("--seconds") + 1]
    assert repeat.main(["--workload", CHAT, "--tag", "u", "--seeds", "1",
                        "--budget-s", "-1"]) == 5


def test_ttft_p90_is_read_per_layer_from_what_the_client_prints():
    ttfts = [50.0 + 3.0 * r for r in range(117)]
    c = {"client": run.client_counters(_chat_run(ttfts))}
    reader = harness.load_reader("frontdoor.ttft_p90_ms")
    assert reader.read(c, None) == pytest.approx(stats.percentile(ttfts, 90))
    assert (reader.UNIT, reader.LAYER, reader.SOURCE) == (
        "ms", "front door", "host_clock")
    # a backlog cell's client has no requests due in a window: nothing to read
    assert reader.read({"client": {}}, None) is None
    entry = [m for m in BENCH["per_layer"]
             if m["name"] == "frontdoor.ttft_p90_ms"]
    assert [m["workloads"] for m in entry] == [[CHAT]]
    assert not any("ttft" in m["name"] for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name, q", [("frontdoor.itl_p995_ms", 99.5),
                                     ("frontdoor.itl_p998_ms", 99.8)])
def test_the_gaps_tail_is_read_per_layer_from_what_the_client_prints(name, q):
    """``itl_p995_ms`` was end to end until PR 42: the same arithmetic, a
    percentile of all gaps of the requests due in the window; the 99.8th
    stands beside it as the steadier rank."""
    r = _chat_run([50.0 + 3.0 * k for k in range(117)])
    for k, t in enumerate(r["timelines"]):      # a gap of its own a request
        t["chunks"] = [(t["chunks"][0][0] + i * (0.02 + 0.001 * k), 1)
                       for i in range(9)]
    gaps = [g for t in stats.counted(r["timelines"])
            for g in stats.token_gaps_ms(t)]
    assert stats.percentile(gaps, 99.5) < stats.percentile(gaps, 99.8)
    reader = harness.load_reader(name)
    c = {"client": run.client_counters(r)}
    assert reader.read(c, None) == pytest.approx(stats.percentile(gaps, q))
    assert reader.read(c, None) > 0
    assert (reader.UNIT, reader.LAYER, reader.SOURCE) == (
        "ms", "front door", "host_clock")
    assert reader.read({"client": {}}, None) is None
    entry = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert [(m["workloads"], m["moves"]) for m in entry] == \
        [([CHAT], "tpot_p50_ms")]
    assert not any("itl" in m["name"] for m in BENCH["end_to_end"])
