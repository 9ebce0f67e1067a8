"""``benchmarks/host_spans.py`` and the readers on it: hand-made planes
(every number below is counted by hand), a hand-encoded ``.xplane.pb`` for
the metadata parser, and one small recorded v5e trace.  No TPU library."""

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
