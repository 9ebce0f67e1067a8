"""``benchmarks/thread_spans.py`` and the six readers on it: hand-made lines,
anchors and programs (every number below is counted by hand).  No TPU
library, no trace file."""

import json
import os

import pytest

from benchmarks import harness, host_spans as hs, thread_spans as ts

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
# the twelve entries PR 39 wrote, in BENCHMARK.json since PR 42: two a
# metric, found BY NAME (a later PR appends after them)
NAMES = [f"{stem}.{suffix}" for stem in ts.METRICS
         for suffix in ("chat", "batch")]


def entries(bench):
    return [m for m in bench["per_layer"] if m["name"] in NAMES]


NEW = entries(BENCH)
CHAT = ["mistral-7b-v0.3.chat-steady"]


def ph(name, start, end, **stats):
    return (name, start, end, stats)


def run_of(skew=0.0):
    """A run that alternates ahead and settled steps, the host's clock
    ``skew`` ahead of the device's.  Device (seconds): decode launch 1 runs
    0-1, launch 2 (ahead) 1.25-2.25, launch 3 (settled, after a prefill was
    admitted: launch 3 is the PREFILL 3-3.5, launch 4 its decode 4-5),
    launch 5 (ahead, late) after the small ids program 5.5-5.6: 5.75-6.75.
    Idle gaps: 1-1.25, 2.25-3, 3.5-4, 5-5.5, 5.6-5.75."""
    k = skew
    engine = [
        ph("engine.dispatch", 0.0 + k, 0.1 + k, ahead=0, launch=1),
        # launch 2 goes out while 1 runs; THEN the wait for 1
        ph("engine.dispatch", 0.5 + k, 0.6 + k, ahead=1, launch=2),
        ph("engine.device_wait", 0.6 + k, 1.0 + k, launch=1),
        ph("engine.emit", 1.0 + k, 1.05 + k, streams=2),
        # the next step cannot run ahead: it settles launch 2 first
        ph("ahead.settle", 1.1 + k, 2.3 + k, reason=1, launch=2),
        ph("engine.device_wait", 1.1 + k, 2.25 + k, launch=2),
        ph("engine.dispatch", 2.9 + k, 3.0 + k, ahead=0, launch=3),
        ph("engine.device_wait", 3.0 + k, 3.5 + k, launch=3),
        ph("engine.dispatch", 3.9 + k, 4.0 + k, ahead=0, launch=4),
        ph("engine.emit", 4.0 + k, 4.05 + k, streams=2),
        ph("engine.dispatch", 5.4 + k, 5.5 + k, ahead=1, launch=5),
        ph("engine.device_wait", 5.5 + k, 5.55 + k, launch=4),
        ph("engine.emit", 5.6 + k, 5.65 + k, streams=2),
    ]
    programs = [("jit__decode_fn", 0.0, 1.0, 11),
                ("jit__decode_fn", 1.25, 2.25, 12),
                ("jit__prefill_fn", 3.0, 3.5, 13),
                ("jit__decode_fn", 4.0, 5.0, 14),
                ("jit__ids_program", 5.5, 5.6, 99),
                ("jit__decode_fn", 5.75, 6.75, 15)]
    # enqueue start (host clock), completion heard (host clock)
    anchors = {11: [0.0 + k, 1.0 + k], 12: [0.55 + k, 2.25 + k],
               13: [2.95 + k, 3.5 + k], 14: [3.95 + k, 5.0 + k],
               99: [5.3 + k, None], 15: [5.45 + k, 6.75 + k]}
    return engine, programs, anchors


def disp(engine):
    return [p for p in engine if p[0] == hs.DISPATCH]


def test_the_names_are_the_programs():
    from paddle_tpu.observability import tracer

    assert ts.THREAD_SPANS == tracer.THREAD_SPANS
    assert ts.SETTLE_REASONS == tracer.SETTLE_REASONS
    # none is a phase of the step to ``host_spans.load_host``
    assert not any(n.startswith(hs.PHASE_PREFIXES) for n in ts.THREAD_SPANS)


def test_programs_pair_with_their_dispatch_by_enqueue_and_run_id():
    engine, programs, anchors = run_of()
    pairs = ts.pair_programs(disp(engine), programs, anchors)
    assert [(d[3]["launch"], p[3]) for d, p in pairs] == \
        [(1, 11), (2, 12), (3, 13), (4, 14), (5, 15)]
    # the ids program's enqueue (5.3) lies in dispatch 4's slot: it is no
    # step program and takes no dispatch from launch 4's decode
    assert all(p[0] in ts.STEP_PROGRAMS for _, p in pairs)
    # a dispatch whose program the trace does not hold is left out
    some = ts.pair_programs(disp(engine), programs[1:], anchors)
    assert [d[3]["launch"] for d, _ in some] == [2, 3, 4, 5]
    # and one with two step programs in its slot (a lost dispatch)
    lost = [d for d in disp(engine) if d[3]["launch"] != 3]
    assert [d[3]["launch"] for d, _ in
            ts.pair_programs(lost, programs, anchors)] == [1, 4, 5]


def test_waits_pair_by_launch_number_not_by_order():
    engine, programs, anchors = run_of()
    pairs = ts.pair_programs(disp(engine), programs, anchors)
    waits = [p for p in engine if p[0] == hs.DEVICE_WAIT]
    got = ts.launches_by_number(pairs, waits)
    # launch 2's own wait ends at 2.25, a step later: "the next wait"
    # after its dispatch is launch 1's and ends at 1.0
    assert (0.5, 2.25, 1.25, 2.25) in got
    assert (0.0, 1.0, 0.0, 1.0) in got
    # launch 5's wait is past the trace's end
    assert len(got) == 4 and all(g[0] != 5.4 for g in got)


@pytest.mark.parametrize("skew", [0.0, 7.0, -3.5])
def test_the_offset_is_pinned_whatever_the_skew(skew):
    engine, programs, anchors = run_of(skew)
    pairs = ts.pair_programs(disp(engine), programs, anchors)
    waits = [p for p in engine if p[0] == hs.DEVICE_WAIT]
    offset, width = ts.pin_offset(pairs, waits, programs, anchors)
    # from above: launch 1's program starts as its dispatch does; from
    # below: completions are heard as the programs end
    assert offset == pytest.approx(-skew) and width == pytest.approx(0.0)


def test_the_wrong_pairing_would_read_a_negative_width():
    """What ``host_spans.match_launches`` does since the loop runs ahead:
    launch 2 with the NEXT wait (launch 1's, ending a program early)."""
    wrong = [(0.5, 1.0, 1.25, 2.25)]
    lo, hi = hs.offset_bounds(wrong)
    assert hi - lo == pytest.approx(0.75 - 1.25)


def test_ahead_share_counts_decode_launches_only():
    engine, programs, anchors = run_of()
    pairs = ts.pair_programs(disp(engine), programs, anchors)
    # decode launches 1, 2, 4, 5: two of four ahead; the prefill is none
    assert ts.ahead_share(pairs) == pytest.approx(50.0)
    assert ts.ahead_share([p for p in pairs if p[1][0] != ts.DECODE]) is None


def test_a_gap_counts_to_the_dispatch_of_the_step_program_it_ends_at():
    engine, programs, anchors = run_of()
    pairs = ts.pair_programs(disp(engine), programs, anchors)
    gaps = hs.module_gaps([(n, s, e - s) for n, s, e, _ in programs])
    assert gaps == pytest.approx([(1.0, 1.25), (2.25, 3.0), (3.5, 4.0),
                                  (5.0, 5.5), (5.6, 5.75)])
    got = ts.split_gaps(gaps, programs, pairs, ts.settle_reasons(engine))
    # ahead: before launch 2 (0.25) and, through the ids program, both
    # gaps before launch 5 (0.5 + 0.15); settled: before the prefill
    # (0.75) and before its decode (0.5), both under the one settle
    assert got["ahead"] == pytest.approx(0.25 + 0.5 + 0.15)
    assert got["settled"] == pytest.approx(0.75 + 0.5)
    assert got["unpaired"] == 0.0
    assert got["by_reason"] == pytest.approx({"admit": 1.25})
    # without launch 4's dispatch its gap is nobody's
    fewer = [p for p in pairs if p[0][3]["launch"] != 4]
    got = ts.split_gaps(gaps, programs, fewer, ts.settle_reasons(engine))
    assert got["unpaired"] == pytest.approx(0.5)
    assert got["settled"] == pytest.approx(0.75)


def test_a_dispatch_not_ahead_with_nothing_settled_has_no_reason():
    engine, _, _ = run_of()
    reasons = ts.settle_reasons(engine)
    # launch 1 had nothing in flight before it; 3 and 4 follow the settle
    assert reasons == {0.0: "none", 2.9: "admit", 3.9: "admit"}
    # a dispatch that ran ahead ends the settle's reach
    later = engine + [ph("engine.dispatch", 7.0, 7.1, ahead=0, launch=6)]
    assert ts.settle_reasons(later)[7.0] == "none"


LOOP = [ph("server.accept", 0.9, 1.0, req=7, prompt_tokens=40),
        ph("server.wake", 1.06, 1.1, handles=2),
        ph("server.write", 1.1, 1.2, req=7, tokens=1),
        ph("server.write", 1.3, 1.45, req=8, tokens=1),
        # a new stream's header, whenever its request came: no token's
        ph("server.write", 2.0, 2.1, req=9, tokens=0),
        ph("server.wake", 4.06, 4.1, handles=2),
        ph("server.write", 4.1, 4.3, req=7, tokens=1),
        ph("server.wake", 5.66, 5.7, handles=2)]


def test_overlap_with_a_second_threads_spans():
    # idle 1-1.25 and 2.25-3: the wake covers 1.06-1.1, the writes
    # 1.1-1.2 and (of 1.3-1.45) nothing past 1.25
    over = hs.attribute([(1.0, 1.25), (2.25, 3.0)], LOOP)
    front = sum(v for k, v in over.items() if k in ts.FRONT_DOOR)
    assert front == pytest.approx(0.04 + 0.1)
    # the loop's spans are busy 0.1 + 0.04 + 0.1 + 0.15 + 0.1 + 0.04 + 0.2
    # + 0.04
    assert ts.union_s(LOOP) == pytest.approx(0.77)


def test_handoff_is_last_write_before_the_next_wake_minus_the_emit():
    engine, _, _ = run_of()
    got = ts.handoffs_s(engine, LOOP)
    # wake 1 (posted by the emit that began at 1.0): last write ends 1.45;
    # the header written at 2.1 carries no token and is not one; wake 2
    # (the emit from 4.0): 4.3; the last wake has no next one
    assert got == pytest.approx([1.45 - 1.0, 4.3 - 4.0])
    assert ts.median([1e3 * v for v in got]) == pytest.approx(375.0)
    assert ts.handoffs_s(engine, []) == [] and ts.median([]) is None
    # a loop thread that has written the chunk before the engine thread
    # closes its span (a busy machine): still a time after the hand-off
    # began, never below nought
    slow = [ph("engine.emit", 1.0, 1.6, streams=2)]
    assert ts.handoffs_s(slow, LOOP)[0] == pytest.approx(0.45)


def _analysed(skew=0.0, extra=()):
    engine, programs, anchors = run_of(skew)
    loop = [ph(n, s + skew, e + skew, **st) for n, s, e, st in LOOP]
    other = [ph("proc.gc", 2.0 + skew, 2.07 + skew, gen=2)] + list(extra)
    return ts.analyse([sorted(engine, key=lambda p: p[1]), loop, other],
                      anchors, {"/device:TPU:0": programs})


@pytest.mark.parametrize("skew", [0.0, 11.0])
def test_the_six_metrics_of_the_hand_made_run(skew):
    a = _analysed(skew)
    v = {m: ts.value(None, m, a) for m in ts.METRICS}
    assert v["engine.ahead_share"] == pytest.approx(50.0)
    assert v["engine.idle_settled_share"] == pytest.approx(
        100 * 1.25 / 2.15)
    # idle 2.15 s; the loop overlaps 1.06-1.2 (0.14) of the first gap and
    # 5.66-5.7 (0.04) of the last
    assert v["engine.idle_frontdoor_share"] == pytest.approx(
        100 * 0.18 / 2.15)
    # the window on the host's clock: 0.0 (first dispatch) to 5.7
    assert v["frontdoor.loop_busy_share"] == pytest.approx(100 * 0.77 / 5.7)
    assert v["frontdoor.handoff_ms"] == pytest.approx(375.0)
    assert v["engine.gc_ms_per_s"] == pytest.approx(70.0 / 5.7)
    assert a["settles"] == {"admit": 1}
    assert a["spans"]["server.write"]["count"] == 4
    with pytest.raises(KeyError):
        ts.value(None, "engine.no_such", a)


def test_none_without_the_new_spans():
    engine, programs, anchors = run_of()
    parent = [p for p in engine if p[0] != ts.SETTLE]
    assert ts.analyse([parent], anchors, {"/device:TPU:0": programs}) is None
    assert ts.analyse([], {}, {}) is None
    assert all(ts.value(None, m) is None for m in ts.METRICS)    # untraced


def test_none_and_a_message_on_a_negative_offset_width(tmp_path, capsys,
                                                       monkeypatch):
    engine, programs, anchors = run_of()
    # a completion "heard" before its program ended: causality broken
    anchors[12][1] = 1.0
    a = ts.analyse([engine, LOOP], anchors, {"/device:TPU:0": programs})
    assert a["offset_width_s"] < 0 and a["frontdoor_s"] is None
    assert ts.value(None, "engine.idle_frontdoor_share", a) is None
    # what needs no offset still reads
    assert ts.value(None, "engine.idle_settled_share", a) is not None
    assert ts.value(None, "engine.ahead_share", a) == pytest.approx(50.0)
    # the reader's path says why on standard error, once
    trace_dir = tmp_path / ".bench_trace"
    trace_dir.mkdir()
    (trace_dir / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(ts, "load", lambda path: a)
    for _ in range(2):
        assert ts.analysis({"busy_s": 1.0}, root=str(tmp_path)) is a
    err = capsys.readouterr().err
    assert err.count("engine.idle_frontdoor_share is not reported") == 1
    assert "negative" in err
    # over 2 ms open is as bad as negative
    wide = dict(a, offset_width_s=0.003)
    assert ts.value(None, "engine.idle_frontdoor_share", wide) is None


def check_entry(bench, m):
    """One of the twelve entries against its reader and against ``bench``:
    a ``.batch`` list is the cells that report the metric it moves, in the
    order ``workloads`` has them, however many a later PR has appended."""
    mod = harness.load_reader(m["name"])
    stem, _, suffix = m["name"].rpartition(".")
    assert stem in ts.METRICS
    assert (mod.UNIT, mod.LAYER, mod.SOURCE) == \
        (m["unit"], m["layer"], "program_span") and m["source"] == mod.SOURCE
    assert mod.read({}, None) is None           # an untraced run
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["layer"] in {e["layer"] for e in bench["per_layer"]
                          if e["name"] not in NAMES}
    moved = [e for e in bench["end_to_end"] if e["name"] == m["moves"]][0]
    if suffix == "chat":
        assert m["workloads"] == CHAT and m["moves"] == "tpot_p50_ms"
        assert set(CHAT) <= set(moved["workloads"])
    else:
        assert suffix == "batch" and m["moves"] == "tokens_per_s"
        assert m["workloads"] == [w["name"] for w in bench["workloads"]
                                  if w["name"] in moved["workloads"]]


def check_the_twelve(bench):
    """Twelve entries, two a metric, together and in the order PR 39 wrote
    them, and none with ``.gap_`` in its name."""
    mine = entries(bench)
    assert len(mine) == 12
    assert sorted(m["name"] for m in mine) == sorted(NAMES)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(mine[0]["name"])
    assert names[first:first + 12] == [m["name"] for m in mine] == NAMES
    # no accepted test's membership of the names with ``.gap_`` moves
    assert not any(".gap_" in m["name"] for m in mine)
    assert json.dumps(mine).count("program_span") == 12
    for m in mine:
        check_entry(bench, m)


@pytest.mark.parametrize("m", NEW, ids=lambda m: m["name"])
def test_every_entry_resolves_to_its_reader_and_fits_the_benchmark(m):
    check_entry(BENCH, m)


def test_twelve_entries_two_a_metric_and_no_name_the_benchmark_has():
    check_the_twelve(BENCH)
    # the file they waited in is gone: its ``for`` is done
    assert not os.path.exists(os.path.join(harness.HERE, "data",
                                           "pr39_per_layer_entries.json"))
