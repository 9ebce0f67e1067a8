"""``benchmarks/host_spans.py`` and the readers on it: hand-made planes
(every number below is counted by hand), a hand-encoded ``.xplane.pb`` for
the metadata parser, one small recorded v5e trace from before the loop ran
ahead, and a hand-made trace of a loop that does (``run_of`` of
``test_bm_thread_spans.py``).  No TPU library."""

import json
import os

import pytest

from benchmarks import harness, host_spans as hs, trace_reduce as tr


def ph(name, start, end, **stats):
    return (name, start, end, stats)


# three programs: busy 0-1, 2-3, 5-6 on the device's clock; idle 1-2, 3-5
MODULES = [("jit__decode_fn(1)", 0.0, 1.0), ("jit__decode_fn(1)", 2.0, 1.0),
           ("jit__prefill_fn(2)", 5.0, 1.0)]
GAPS = [(1.0, 2.0), (3.0, 5.0)]


def test_module_gaps_are_the_gaps_trace_reduce_sums():
    assert hs.module_gaps(MODULES) == GAPS
    assert sum(b - a for a, b in GAPS) == pytest.approx(
        sum(tr.gaps_between_modules(MODULES).values()))
    # back-to-back and overlapping programs leave no gap
    assert hs.module_gaps([("a", 0, 1), ("b", 1, 1), ("c", 1.5, 1)]) == []


def test_a_gap_wholly_inside_a_phase():
    got = hs.attribute([(1.0, 2.0)], [ph("engine.fetch", 0.5, 2.5)])
    assert got == pytest.approx({"engine.fetch": 1.0, "unattributed": 0.0})


def test_a_phase_straddling_a_gap_counts_only_its_idle_part():
    # the phase runs 1.6-2.4: 0.4 s of it are idle, 0.4 s the device is busy
    got = hs.attribute([(1.0, 2.0)], [ph("engine.dispatch", 1.6, 2.4)])
    assert got == pytest.approx({"engine.dispatch": 0.4, "unattributed": 0.6})
    # and one phase over two gaps counts in both
    got = hs.attribute(GAPS, [ph("engine.emit", 1.5, 3.5)])
    assert got == pytest.approx({"engine.emit": 0.5 + 0.5,
                                 "unattributed": 3.0 - 1.0})


def test_two_phases_in_one_gap_and_idle_under_wait():
    phases = [ph("engine.emit", 3.0, 3.25), ph("engine.wait", 3.25, 4.5),
              ph("sched.plan", 4.5, 4.75), ph("engine.build", 4.75, 5.5)]
    got = hs.attribute(GAPS, phases)
    assert got == pytest.approx({
        "engine.emit": 0.25, "engine.wait": 1.25, "sched.plan": 0.25,
        "engine.build": 0.25,           # its other 0.5 s the device is busy
        "unattributed": 1.0})           # all of the first gap
    assert sum(got.values()) == pytest.approx(3.0)


def test_the_offset_shifts_the_host_plane():
    got = hs.attribute([(1.0, 2.0)], [ph("engine.fetch", 11.0, 11.5)],
                       offset=-10.0)
    assert got == pytest.approx({"engine.fetch": 0.5, "unattributed": 0.5})


def launch(t, disp_at, wait_end, skew):
    """The phases of one launch on a host clock that runs ``skew`` ahead of
    the device's: the program runs t .. t+1 on the device."""
    return [ph("engine.dispatch", t - disp_at + skew, t - disp_at + 0.05 + skew,
               rows=4, bucket=4),
            ph("engine.device_wait", t - disp_at + 0.05 + skew,
               t + 1.0 + wait_end + skew),
            ph("engine.fetch", t + 1.0 + wait_end + skew,
               t + 1.0 + wait_end + 0.1 + skew, bytes=1000)]


def test_causality_pins_the_offset_from_both_sides():
    # host clock = device clock + 2 ms (the profiler aligns the planes to
    # within milliseconds): the offset to find is -0.002
    skew = 0.002
    mods = [("jit__decode_fn(1)", float(t), 1.0) for t in (10, 12, 14)]
    # dispatch began 0.30 / 0.10 / 0.20 before its program started; the wait
    # ended 0.05 / 0.25 / 0.02 after its program ended
    phases = (launch(10, 0.30, 0.05, skew) + launch(12, 0.10, 0.25, skew)
              + launch(14, 0.20, 0.02, skew))
    got = hs.match_launches(mods, sorted(phases, key=lambda p: p[1]))
    assert len(got) == 3
    lo, hi = hs.offset_bounds(got)
    # above: the tightest dispatch (0.10); below: the tightest wait (0.02)
    assert hi == pytest.approx(-skew + 0.10)
    assert lo == pytest.approx(-skew - 0.02)
    assert lo <= -skew <= hi
    assert hs.offset_bounds([]) is None


def test_the_runtimes_run_id_anchors_pin_the_offset_more_closely():
    mods = [("jit__decode_fn(1)", float(t), 1.0) for t in (10, 12)]
    phases = sorted(launch(10, 0.30, 0.05, 0.0) + launch(12, 0.25, 0.25, 0.0),
                    key=lambda p: p[1])
    launches = hs.match_launches(mods, phases)
    assert hs.offset_bounds(launches) == pytest.approx((-0.05, 0.25))
    # run 7: handed to the device 0.01 before it started, heard of 0.02
    # after it ended; run 8 has only its enqueue in the trace
    runs = [(10.0, 11.0, 7), (12.0, 13.0, 8)]
    anchors = {7: [9.99, 11.02], 8: [11.5, None], 9: [0.0, 0.0]}
    lo, hi = hs.offset_bounds(launches, runs, anchors)
    assert (lo, hi) == pytest.approx((-0.02, 0.01))
    # the anchors alone are enough, and none at all is no bound
    assert hs.offset_bounds([], runs, anchors) == pytest.approx((-0.02, 0.01))
    assert hs.offset_bounds([], runs, {8: [11.5, None]}) is None


def test_launches_at_the_trace_edges_are_left_out():
    mods = [("jit__decode_fn(1)", 10.0, 1.0), ("jit__decode_fn(1)", 12.0, 1.0)]
    # the first dispatch's program is not in the trace (it began before the
    # profiler did): two programs would fall to the second dispatch's slot
    phases = sorted([ph("engine.dispatch", 5.0, 5.1),
                     ph("engine.device_wait", 5.1, 6.0)]
                    + launch(10, 0.1, 0.1, 0.0) + launch(12, 0.1, 0.1, 0.0),
                    key=lambda p: p[1])
    got = hs.match_launches(mods, phases)
    assert [g[2] for g in got] == [10.0, 12.0]


def test_scope_of_takes_the_outermost_of_nested_scopes():
    assert hs.scope_of("jit(_decode_fn)/jit(main)/attn/mlp/dot_general:") \
        == "attn"
    assert hs.scope_of("jit(_decode_fn)/sampler/jit(sort)/sort:") == "sampler"
    assert hs.scope_of("jit(_decode_fn)/jit(main)/lm_head/dot_general:") \
        == "lm_head"
    assert hs.scope_of("jit(_decode_fn)/jit(main)/add:") == "unscoped"
    assert hs.scope_of("") == "unscoped"


def test_scope_seconds_with_nested_and_unnamed_operations():
    ops = [("%fusion.1", 0.0, 0.4), ("%sort.2", 0.4, 0.3),
           ("%fusion.3", 0.7, 0.2), ("%copy.4", 0.9, 0.1)]
    scopes = {"%fusion.1": "attn", "%sort.2": "sampler",
              "%fusion.3": "sampler"}
    got = hs.scope_seconds(ops, scopes)
    assert got == pytest.approx({"attn": 0.4, "sampler": 0.5,
                                 "unscoped": 0.1})
    assert sum(got.values()) == pytest.approx(sum(e[2] for e in ops))


def test_an_unnamed_operation_takes_the_scope_both_neighbours_share():
    # what the compiler put in carries no path: the copy and the second
    # sort pass between the sampler's own operations are the sampler's;
    # the slice between mlp and attn, and the copy at the edge, are not
    ops = [("%fusion.1", 0.0, 1.0), ("%slice.2", 1.0, 0.25),
           ("%fusion.3", 1.25, 1.0), ("%norm.4", 2.25, 0.5),
           ("%neg.5", 2.75, 0.25), ("%copy.6", 3.0, 0.25),
           ("%sort.7", 3.25, 1.0), ("%sort.8", 4.25, 1.0),
           ("%reduce.9", 5.25, 0.25), ("%copy.10", 5.5, 0.125)]
    scopes = {"%fusion.1": "mlp", "%fusion.3": "attn", "%norm.4": "unscoped",
              "%neg.5": "sampler", "%sort.7": "sampler",
              "%reduce.9": "sampler"}
    got = hs.scope_seconds(ops, scopes)
    assert got == pytest.approx({
        "mlp": 1.0, "attn": 1.0,
        "sampler": 0.25 + 0.25 + 1.0 + 1.0 + 0.25,
        "unscoped": 0.25 + 0.5 + 0.125})
    # a named "unscoped" operation between two of one scope stays unscoped
    got = hs.scope_seconds(
        [("%a", 0, 1), ("%n", 1, 1), ("%b", 2, 1)],
        {"%a": "mlp", "%n": "unscoped", "%b": "mlp"})
    assert got == pytest.approx({"mlp": 2.0, "unscoped": 1.0})


# --- a hand-encoded .xplane.pb ------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num, value):
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def stat(meta_id, value):
    return field(1, meta_id) + (field(5, value) if isinstance(value, str)
                                else field(7, value[1]))


def plane(name, stat_names, events):
    body = field(1, 0) + field(2, name) + field(3, b"\x0a\x02hi")  # a line
    for sid, sname in stat_names.items():
        body += field(5, field(1, sid) + field(2, field(1, sid)
                                               + field(2, sname)))
    for i, (ename, stats) in enumerate(events):
        meta = field(1, i) + field(2, ename)
        for sid, val in stats:
            meta += field(5, stat(sid, val))
        body += field(4, field(1, i) + field(2, meta))
    return field(1, body)


def test_op_scopes_reads_the_event_metadata(tmp_path):
    names = {1: "hlo_category", 2: "tf_op",
             9: "jit(_decode_fn)/jit(main)/mlp/dot_general:"}
    dev = plane("/device:TPU:0", names, [
        ("%fusion.1 = bf16[8] fusion(...)",
         [(1, "convolution fusion"),
          (2, "jit(_decode_fn)/jit(main)/attn/dot_general:")]),
        ("%sort.2 = f32[8] sort(...)",
         [(2, "jit(_decode_fn)/sampler/jit(sort)/sort:")]),
        ("%fusion.3 = bf16[8] fusion(...)", [(2, ("ref", 9))]),
        ("%copy.4 = f32[8] copy(...)", [(1, "data formatting")]),
        ("%add.5 = f32[8] add(...)", [(2, "jit(_decode_fn)/jit(main)/add:")]),
    ])
    host = plane("/host:CPU", {2: "tf_op"},
                 [("engine.dispatch", [(2, "x/attn/y")])])
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(dev + host)
    got = hs.op_scopes(str(path))
    assert got == {"/device:TPU:0": {
        "%fusion.1 = bf16[8] fusion(...)": "attn",
        "%sort.2 = f32[8] sort(...)": "sampler",
        "%fusion.3 = bf16[8] fusion(...)": "mlp",
        "%add.5 = f32[8] add(...)": "unscoped"}}


# --- the whole analysis, and the readers --------------------------------------

def planes_and_phases():
    rows = {"modules": [("jit__decode_fn(1)", 10.0, 1.0),
                        ("jit__decode_fn(1)", 12.0, 1.0),
                        ("jit__decode_fn(1)", 14.0, 1.0)],
            "ops": [("%fusion.1", 10.0, 0.6), ("%sort.2", 10.6, 0.4),
                    ("%fusion.1", 12.0, 0.6), ("%sort.2", 12.6, 0.4),
                    ("%fusion.1", 14.0, 0.6), ("%sort.2", 14.6, 0.3),
                    ("%copy.9", 14.9, 0.1)]}
    phases = []
    for t in (10, 12, 14):
        phases += launch(t, 0.25, 0.125, 0.0)
        phases += [ph("engine.emit", t + 1.225, t + 1.5),
                   ph("engine.build", t + 1.5, t + 1.75)]
    scopes = {"/device:TPU:0": {"%fusion.1": "mlp", "%sort.2": "sampler"}}
    return {"/device:TPU:0": rows}, sorted(phases, key=lambda p: p[1]), scopes


def test_analyse_sums_to_the_idle_time_between_programs():
    planes, phases, scopes = planes_and_phases()
    a = hs.analyse(planes, phases, scopes)
    red = tr.reduce(planes)
    assert a["launches"] == red["launches"] == 3
    assert a["gap_s"] == pytest.approx(red["gap_s"]) == pytest.approx(2.0)
    assert sum(a["gaps"].values()) == pytest.approx(a["gap_s"])
    # causality leaves 0.25 + 0.125 for the offset; the true one (0) inside
    assert a["offset_width_s"] == pytest.approx(0.375)
    assert abs(a["offset_s"]) <= a["offset_width_s"] / 2 + 1e-9
    assert a["matched"] == 3 and a["fetches"] == 3
    assert a["fetch_bytes"] == 3000 and a["has_scopes"]
    assert a["scope_s"] == pytest.approx(
        {"mlp": 1.8, "sampler": 1.1, "unscoped": 0.1})
    # no dispatch in the host plane: the parent of the PR that added them
    assert hs.analyse(planes, [ph("other", 0, 1)], scopes) is None
    assert hs.analyse({}, phases, scopes) is None


READERS = ["engine.gap_intake_ms", "scheduler.gap_plan_ms",
           "engine.gap_admit_ms", "engine.gap_build_ms",
           "engine.gap_dispatch_ms", "engine.gap_fetch_ms",
           "engine.gap_emit_ms", "engine.gap_trackers_ms",
           "engine.gap_unattributed_share", "engine.fetch_mb_per_step",
           "engine.gap_offset_width_ms", "kernels.sampler_scope_share",
           "programs.lm_head_share", "programs.mlp_share",
           "programs.attn_share"]


@pytest.mark.parametrize("name", READERS)
def test_every_new_reader_is_declared_and_reads_nothing_untraced(name):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mod = harness.load_reader(name + ".chat")
    for suffix, moves in ((".chat", "tpot_p50_ms"), (".batch", "tokens_per_s")):
        entry = [m for m in bench["per_layer"] if m["name"] == name + suffix]
        assert len(entry) == 1
        assert entry[0]["unit"] == mod.UNIT and entry[0]["layer"] == mod.LAYER
        assert entry[0]["source"] == mod.SOURCE and entry[0]["moves"] == moves
    # a run that was not traced, and a checkout that holds no trace
    assert mod.read({}, None) is None


@pytest.fixture
def recorded(monkeypatch):
    """The readers on the analysis of the hand-made planes, as if the
    launcher had left that trace."""
    planes, phases, scopes = planes_and_phases()
    a = hs.analyse(planes, phases, scopes)
    monkeypatch.setattr(hs, "analysis", lambda trace, root=None:
                        a if trace else None)
    return tr.reduce(planes), a


def test_readers_on_the_hand_made_planes(recorded):
    red, a = recorded

    def read(name):
        return harness.load_reader(name).read({}, red)

    # per launch, in ms: 3 launches, 2.0 s idle between them
    assert read("engine.gap_emit_ms") == pytest.approx(1e3 * 2 * 0.275 / 3)
    assert read("engine.gap_build_ms") == pytest.approx(1e3 * 2 * 0.25 / 3)
    assert read("engine.gap_fetch_ms") == pytest.approx(1e3 * 2 * 0.1 / 3)
    # dispatch: the 0.05 s of the call and the idle ends of the wait
    want = a["gaps"]["engine.dispatch"] + a["gaps"]["engine.device_wait"]
    assert read("engine.gap_dispatch_ms") == pytest.approx(1e3 * want / 3)
    assert read("engine.gap_intake_ms") == 0.0
    assert read("scheduler.gap_plan_ms") == 0.0
    total = sum(read(n) for n in READERS[:8])
    rest = read("engine.gap_unattributed_share") / 100 * 1e3 * 2.0 / 3
    wait = 1e3 * a["gaps"].get("engine.wait", 0.0) / 3
    assert total + rest + wait == pytest.approx(1e3 * red["gap_s"] / 3)
    assert read("engine.fetch_mb_per_step") == pytest.approx(0.001)
    assert read("engine.gap_offset_width_ms") == pytest.approx(375.0)
    busy = red["busy_s"]
    assert read("kernels.sampler_scope_share") == pytest.approx(110.0 / busy)
    assert read("programs.mlp_share") == pytest.approx(180.0 / busy)
    assert read("programs.attn_share") == 0.0
    # the scope holds the sort, so it cannot read under it
    sort = harness.load_reader("kernels.sampler_share.chat").read({}, red)
    assert read("kernels.sampler_scope_share") >= sort


def test_recorded_trace_reads_the_recorded_numbers():
    """The v5e trace ``record_phase_trace.py`` left: the program's own
    phases and scopes, parsed here as on the day it was recorded."""
    path = os.path.join(harness.HERE, "data", "phase_trace.xplane.pb")
    with open(os.path.join(harness.HERE, "data",
                           "phase_trace.expected.json")) as f:
        want = json.load(f)
    a = hs.load(path)
    red = tr.reduce(tr.load(path))
    assert a["launches"] == want["analysis"]["launches"] == red["launches"]
    assert a["gap_s"] == pytest.approx(red["gap_s"], rel=1e-9)
    for key in ("gap_s", "ops_s", "offset_s", "offset_width_s"):
        assert a[key] == pytest.approx(want["analysis"][key], rel=1e-9)
    assert a["gaps"] == pytest.approx(want["analysis"]["gaps"], rel=1e-9)
    assert a["scope_s"] == pytest.approx(want["analysis"]["scope_s"], rel=1e-9)
    # what the acceptance criteria ask of any trace
    assert sum(a["gaps"].values()) == pytest.approx(a["gap_s"], rel=1e-9)
    assert 0.0 <= a["offset_width_s"] < 0.002
    assert a["matched"] >= a["launches"] - 2
    assert a["fetch_bytes"] > 0 and a["has_scopes"]
    assert set(hs.SCOPES) <= set(a["scope_s"])
    assert a["scope_s"]["sampler"] >= want["sort_s"] > 0.0
    assert a["gaps"]["unattributed"] <= 0.05 * a["gap_s"]


# --- a loop that runs ahead: launches paired by number, steps counted ---------

def run_ahead(skew=0.0, numbered=True, slack=0.0):
    """``test_bm_thread_spans.run_of`` as ``host_spans.analyse`` takes it:
    five step programs and the small ids program on one device plane, the
    engine thread's phases (``ahead.settle`` is no phase of the step), the
    runtime's anchors.  ``numbered=False`` takes ``launch`` and ``ahead``
    off the phases: the trace as a program from before PR 35 would have
    written it, were its loop to run ahead.  ``slack``: every program
    starts that much after the host handed it over and ends that much
    before the host heard of it, as on a chip."""
    from test_bm_thread_spans import run_of

    engine, programs, anchors = run_of(skew)
    programs = [(n, a + slack, b - slack, rid) for n, a, b, rid in programs]
    phases = [p for p in engine if p[0].startswith(hs.PHASE_PREFIXES)]
    if not numbered:
        phases = [(n, a, b, {k: v for k, v in st.items()
                             if k not in ("launch", "ahead")})
                  for n, a, b, st in phases]
    rows = {"modules": [(n, a, b - a) for n, a, b, _ in programs], "ops": []}
    return ({"/device:TPU:0": rows}, phases, {},
            {"/device:TPU:0": programs}, anchors)


@pytest.mark.parametrize("skew", [0.0, 7.0, -3.5])
def test_a_dispatch_is_paired_with_its_own_wait(skew):
    planes, phases, scopes, runs, anchors = run_ahead(skew)
    mods = planes["/device:TPU:0"]["modules"]
    got = hs.match_launches(mods, phases, runs["/device:TPU:0"], anchors)
    # launch 2 went out ahead: its own wait ends with its program at 2.25,
    # the NEXT wait after its dispatch is launch 1's and ends at 1.0
    assert (0.5 + skew, 2.25 + skew, 1.25, 2.25) in got
    assert len(got) == 4            # launch 5's wait is past the trace's end
    a = hs.analyse(planes, phases, scopes, runs, anchors)
    assert a["numbered"] and a["matched"] == 4
    assert a["offset_s"] == pytest.approx(-skew)
    assert a["offset_width_s"] == pytest.approx(0.0, abs=1e-9)
    assert hs.offset_width_ms(a) == pytest.approx(0.0, abs=1e-6)
    # with 0.2 ms between the host's events and the device's on either
    # side the width is what causality leaves: positive, 0.4 ms
    planes, phases, scopes, runs, anchors = run_ahead(skew, slack=0.0002)
    a = hs.analyse(planes, phases, scopes, runs, anchors)
    assert a["offset_s"] == pytest.approx(-skew)
    assert hs.offset_width_ms(a) == pytest.approx(0.4)
    # the same trace paired with the FIRST wait: negative, by a program
    old = hs.analyse(*run_ahead(skew, numbered=False, slack=0.0002))
    assert hs.offset_width_ms(old) == pytest.approx(0.4 - 1250.0)


def test_the_first_wait_pairing_reads_a_negative_width_on_the_same_trace():
    planes, phases, scopes, runs, anchors = run_ahead(numbered=False)
    a = hs.analyse(planes, phases, scopes, runs, anchors)
    # launch 2 with launch 1's wait: "the wait ends after the program
    # ended" asks 2.25 - 1.0 of the offset, the anchors allow 0 at most
    assert not a["numbered"]
    assert a["offset_width_s"] == pytest.approx(0.0 - 1.25)
    # such a trace reads what it read: the width is reported as it is
    assert hs.offset_width_ms(a) == pytest.approx(-1250.0)
    # the same width on a NUMBERED trace is no reading
    assert hs.offset_width_ms(dict(a, numbered=True)) is None
    assert hs.offset_width_ms(dict(a, numbered=True,
                                   offset_width_s=0.0021)) is None
    assert hs.offset_width_ms(dict(a, numbered=True, offset_width_s=0.0004)) \
        == pytest.approx(0.4)


def test_a_launch_is_a_step_program_and_the_ids_program_is_none():
    planes, phases, scopes, runs, anchors = run_ahead()
    red = tr.reduce(planes)
    a = hs.analyse(planes, phases, scopes, runs, anchors)
    assert red["modules"]["jit__ids_program"]["count"] == 1
    assert sum(m["count"] for m in red["modules"].values()) == 6
    assert red["launches"] == a["launches"] == 5
    # the small program stays in the gaps under its name, and the idle
    # seconds are what they were: 0.25 + 0.75 + 0.5 + 0.5 + 0.15
    assert red["gaps"]["jit__decode_fn_-__jit__ids_program"] == \
        pytest.approx(0.5)
    assert red["gaps"]["jit__ids_program_-__jit__decode_fn"] == \
        pytest.approx(0.15)
    assert red["gap_s"] == pytest.approx(a["gap_s"]) == pytest.approx(2.15)
    host = harness.load_reader("engine.host_ms_per_step.batch")
    assert host.read({}, red) == pytest.approx(1e3 * 2.15 / 5)
    assert set(tr.STEP_PROGRAMS) == {
        "jit__decode_fn", "jit__prefill_fn", "jit__chunk_prefill_fn",
        "jit__unified_fn", "jit__burst_fn"}


def test_idle_time_goes_to_the_phase_the_thread_is_in_split_by_ahead(
        monkeypatch):
    planes, phases, scopes, runs, anchors = run_ahead()
    a = hs.analyse(planes, phases, scopes, runs, anchors)
    # gap 1-1.25: emit 1.0-1.05, then the wait for launch 2 (ahead) from
    # 1.1; gap 2.25-3: dispatch 3 (not ahead) 2.9-3.0; gap 3.5-4: dispatch
    # 4 (not ahead) 3.9-4.0; gap 5-5.5: dispatch 5 (AHEAD, and late)
    # 5.4-5.5; gap 5.6-5.75: emit 5.6-5.65
    assert a["gaps"] == pytest.approx({
        "engine.emit": 0.05 + 0.05, "engine.device_wait.ahead": 0.15,
        "engine.dispatch": 0.1 + 0.1, "engine.dispatch.ahead": 0.1,
        "unattributed": 2.15 - 0.55})
    assert sum(a["gaps"].values()) == pytest.approx(a["gap_s"])
    monkeypatch.setattr(hs, "analysis", lambda trace, root=None: a)
    # a launch's round trip, for the launches the step waited for
    read = harness.load_reader("engine.gap_dispatch_ms.batch").read
    assert read({}, {"busy_s": 1.0}) == pytest.approx(1e3 * 0.2 / 5)
    # without the integers nothing is split (the recorded traces)
    old = run_ahead(numbered=False)[1]
    assert hs.by_ahead(old) == old
    assert {p[0] for p in hs.by_ahead(phases)} == {
        "engine.dispatch", "engine.dispatch.ahead", "engine.device_wait",
        "engine.device_wait.ahead", "engine.emit"}


def test_a_width_that_is_no_reading_says_so_on_standard_error(
        tmp_path, capsys, monkeypatch):
    planes, phases, scopes, runs, anchors = run_ahead()
    anchors[12][1] = 1.0    # a completion "heard" before its program ended
    a = hs.analyse(planes, phases, scopes, runs, anchors)
    assert a["offset_width_s"] < 0 and hs.offset_width_ms(a) is None
    trace_dir = tmp_path / ".bench_trace"
    trace_dir.mkdir()
    (trace_dir / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(hs, "load", lambda path: a)
    for _ in range(2):
        assert hs.analysis({"busy_s": 1.0}, root=str(tmp_path)) is a
    err = capsys.readouterr().err
    assert err.count("engine.gap_offset_width_ms is not reported") == 1
    assert "negative" in err


def test_a_trace_is_parsed_once_a_file(tmp_path):
    calls = []

    @tr.once_a_file
    def parse(path):
        calls.append(path)
        return {"n": len(calls)}

    f = tmp_path / "a.xplane.pb"
    f.write_bytes(b"one")
    assert parse(str(f)) is parse(str(f)) and len(calls) == 1
    f.write_bytes(b"another trace")         # a later run's file
    assert parse(str(f)) == {"n": 2} and len(calls) == 2
    # the two loaders every reader shares
    path = os.path.join(harness.HERE, "data", "phase_trace.xplane.pb")
    assert tr.load(path) is tr.load(path)
    assert hs.load_host(path) is hs.load_host(path)
