"""The HOST plane of a profiler trace, and the scopes of its device ops.

``trace_reduce`` reduces the device planes: busy and idle time, and the
idle gaps keyed by the programs either side.  This module reads what the
program itself wrote into the same ``.xplane.pb`` (PERF.md section 3):

* the phases of the engine's step -- ``engine.dispatch``, ``sched.plan``,
  ... (``paddle_tpu/observability/tracer.py`` ``STEP_PHASES``) -- which are
  ``jax.profiler.TraceAnnotation`` events in the plane ``/host:CPU``, with
  the integers a phase carries (``engine.fetch``'s ``bytes``) as stats;
* the ``jax.named_scope`` of every device operation (``sampler``,
  ``lm_head``, ``mlp``, ``attn``, ``embed``; ``logit_stats``, the numerics
  audit's in-trace sentinel), which the profiler keeps in
  the operation's *metadata*, where ``jax.profiler.ProfileData`` does not
  look: :func:`op_scopes` reads those few records from the file's bytes.

Every device idle gap between two consecutive programs -- the same gaps
``trace_reduce.gaps_between_modules`` sums into ``engine.host_ms_per_step``
-- is put down to the phases the engine thread was in while the device
stood idle (:func:`attribute`).  Since the serving loop runs one decode
launch ahead of the read (PERF.md section 3) that is whichever phase the
thread is in when the device runs dry, not the phases of "the launch
before": ``engine.dispatch`` and ``engine.device_wait`` carry the launch's
number (``launch=<n>``), a dispatch ``ahead=1`` where it went out before
the launch before it was read, and :func:`by_ahead` keeps the idle time
under such a dispatch (and under the wait for its program) apart as
``engine.dispatch.ahead`` / ``engine.device_wait.ahead``: the launch ran
ahead and still came late, which is no round trip the step waited for.

The two planes are aligned by the profiler only to within a millisecond or
so, so the host plane is first shifted by an offset that causality pins
from both sides (:func:`offset_bounds`): a program cannot start before its
``engine.dispatch`` began, and ``engine.device_wait`` (blocked until the
program's outputs are ready) cannot end before ITS program ended -- the
wait that carries the dispatch's ``launch`` number, which in a step that
ran ahead comes a step later (:func:`launches_by_number`); a trace from
before the numbers (PR 35's parent, the recorded traces of
``tests/bench_suite``) pairs a dispatch with the next wait, which then was
its own (:func:`match_launches`).  The dispatch alone takes milliseconds
before it reaches the device, so where the runtime's own host events carry
the program's ``run_id`` they pin the offset more closely: a program
cannot start before its ``DoEnqueueProgram`` began nor end after its
``CompleteCallbacks`` ended.  The width of what is left
(:func:`pin_offset`, which ``thread_spans`` uses too) is
``engine.gap_offset_width_ms``: a phase shorter than it is not resolved,
and where a numbered trace leaves it negative or over :data:`MAX_WIDTH_S`
the metric is not reported and standard error says so.

A reader calls :func:`analysis`, which finds the launcher's trace under
``<checkout>/.bench_trace``, parses it once a process and returns ``None``
where there is nothing to read: no trace, or a program without phases
(the parent of the PR that added them).  Everything below :func:`load` is
arithmetic on plain tuples, checked by hand in ``tests/bench_suite``.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))       # when run as a script

from benchmarks import harness, trace_reduce    # noqa: E402

Interval = Tuple[float, float]              # start, end; seconds
Phase = Tuple[str, float, float, Dict]      # name, start, end, stats
Program = Tuple[str, float, float, int]     # name, start, end, run id
Pair = Tuple[Phase, Program]                # a dispatch and its program

DISPATCH = "engine.dispatch"
DEVICE_WAIT = "engine.device_wait"
FETCH = "engine.fetch"
AHEAD = ".ahead"        # suffix of a dispatch / wait of a launch that ran ahead
UNATTRIBUTED = "unattributed"
PHASE_PREFIXES = ("engine.", "sched.")
SCOPES = ("sampler", "lm_head", "mlp", "attn", "embed", "logit_stats")
UNSCOPED = "unscoped"
HOST_PLANE = "/host:CPU"
MAX_WIDTH_S = 0.002     # an offset left wider open than this resolves
                        # no span of the loop thread (tens of microseconds)
# the TPU runtime's host events that carry a program's ``run_id``: the
# one that hands the program to the device, the one that hears it ended
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
# the stats of an operation's metadata that may hold its scope path
# (``jit(_decode_fn)/jit(main)/attn/dot_general``), in order of trust
_OP_NAME_STATS = ("tf_op", "op_name", "long_name", "hlo_op_name", "name")


# --- the file's bytes: event metadata -----------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """``(field number, wire type, value)`` of one protobuf message lying
    at ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 1:
            val, i = (i, i + 8), i + 8
        elif wire == 5:
            val, i = (i, i + 4), i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield num, wire, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _metadata_of_plane(buf, lo: int, hi: int):
    """One ``XPlane``: its name, ``{stat id: stat name}`` and, for every
    event metadata record, ``(name, {stat id: str value | ref id})``.  The
    plane's lines (the events themselves) are skipped unread."""
    name, stat_names, events = "", {}, []
    for num, wire, val in _fields(buf, lo, hi):
        if num == 2 and wire == 2:
            name = _text(buf, val)
        elif num == 5 and wire == 2:            # map<int64, XStatMetadata>
            for n2, w2, v2 in _fields(buf, *val):
                if n2 == 2 and w2 == 2:
                    sid, sname = None, ""
                    for n3, w3, v3 in _fields(buf, *v2):
                        if n3 == 1 and w3 == 0:
                            sid = v3
                        elif n3 == 2 and w3 == 2:
                            sname = _text(buf, v3)
                    stat_names[sid] = sname
        elif num == 4 and wire == 2:            # map<int64, XEventMetadata>
            for n2, w2, v2 in _fields(buf, *val):
                if n2 != 2 or w2 != 2:
                    continue
                ename, stats = "", {}
                for n3, w3, v3 in _fields(buf, *v2):
                    if n3 == 2 and w3 == 2:
                        ename = _text(buf, v3)
                    elif n3 == 5 and w3 == 2:   # XStat
                        sid, sval = None, None
                        for n4, w4, v4 in _fields(buf, *v3):
                            if n4 == 1 and w4 == 0:
                                sid = v4
                            elif n4 == 5 and w4 == 2:
                                sval = _text(buf, v4)
                            elif n4 == 7 and w4 == 0:
                                sval = ("ref", v4)
                        if sval is not None:
                            stats[sid] = sval
                events.append((ename, stats))
    return name, stat_names, events


def scope_of(path: str) -> str:
    """The OUTERMOST of the program's scopes on an operation's path
    (``jit(_decode_fn)/jit(main)/attn/mlp/dot`` is ``attn``'s), or
    ``unscoped``."""
    for part in path.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation name: scope}}`` from the event metadata
    of the file at ``path``.  An operation whose metadata names no path is
    left out (and counts as unscoped)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, wire, val in _fields(buf, 0, len(buf)):
        if num != 1 or wire != 2:
            continue
        name, stat_names, events = _metadata_of_plane(buf, *val)
        if not name.startswith("/device:"):
            continue
        # a string stat may be a reference to another stat metadata's name
        wanted = {sid: _OP_NAME_STATS.index(n) for sid, n in
                  stat_names.items() if n in _OP_NAME_STATS}
        scopes: Dict[str, str] = {}
        for ename, stats in events:
            best = None
            for sid, sval in stats.items():
                if sid not in wanted:
                    continue
                if isinstance(sval, tuple):
                    sval = stat_names.get(sval[1], "")
                if "/" in sval and (best is None or wanted[sid] < best[0]):
                    best = (wanted[sid], sval)
            if best is not None:
                scopes[ename] = scope_of(best[1])
        out[name] = scopes
    return out


@trace_reduce.once_a_file
def load_host(path: str) -> Tuple[List[Phase], Dict[int, List], Dict]:
    """One pass over the file: the program's phases in the host plane, by
    start; the runtime's anchors ``{run_id: [enqueue start, complete
    end]}`` (``None`` for a side the trace does not hold); and ``{device
    plane: [program]}`` for every executed program that carries a
    ``run_id``, names normalised."""
    from jax.profiler import ProfileData

    phases: List[Phase] = []
    anchors: Dict[int, List] = {}
    runs: Dict[str, List[Program]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != trace_reduce.MODULE_LINE:
                    continue
                rows = runs.setdefault(plane.name, [])
                for ev in line.events:
                    rid = dict(ev.stats).get("run_id")
                    if rid is not None:
                        start = ev.start_ns * 1e-9
                        rows.append((trace_reduce.norm(ev.name), start,
                                     start + ev.duration_ns * 1e-9, rid))
            continue
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(PHASE_PREFIXES):
                    start = ev.start_ns * 1e-9
                    phases.append((name, start,
                                   start + ev.duration_ns * 1e-9,
                                   dict(ev.stats)))
                elif name in (ENQUEUE, COMPLETE):
                    rid = dict(ev.stats).get("run_id")
                    if rid is None:
                        continue
                    a = anchors.setdefault(rid, [None, None])
                    start = ev.start_ns * 1e-9
                    if name == ENQUEUE:
                        a[0] = start if a[0] is None else min(a[0], start)
                    else:
                        end = start + ev.duration_ns * 1e-9
                        a[1] = end if a[1] is None else max(a[1], end)
    phases.sort(key=lambda p: p[1])
    return phases, anchors, runs


# --- arithmetic on plain tuples -----------------------------------------------

def module_gaps(modules: List[trace_reduce.Event]) -> List[Interval]:
    """The idle intervals between consecutive programs: the ones
    ``trace_reduce.gaps_between_modules`` sums."""
    mods = sorted(modules, key=lambda e: e[1])
    return [(sa + da, sb) for (_, sa, da), (_, sb, _) in zip(mods, mods[1:])
            if sb - (sa + da) > 0]


def attribute(gaps: Iterable[Interval], phases: Iterable[Phase],
              offset: float = 0.0) -> Dict[str, float]:
    """Seconds of the idle ``gaps`` (device clock) overlapped by each
    phase name, the host's clock shifted by ``offset``; what no phase
    overlaps is ``unattributed``.  Phases of one engine thread do not
    overlap one another, so nothing is counted twice."""
    gaps = sorted(gaps)
    spans = sorted((s + offset, e + offset, name)
                   for name, s, e, _ in phases)
    out: Dict[str, float] = {}
    covered = 0.0
    j = 0
    for lo, hi in gaps:
        while j < len(spans) and spans[j][1] <= lo:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < hi:
            over = min(hi, spans[k][1]) - max(lo, spans[k][0])
            if over > 0:
                out[spans[k][2]] = out.get(spans[k][2], 0.0) + over
                covered += over
            k += 1
    out[UNATTRIBUTED] = sum(hi - lo for lo, hi in gaps) - covered
    return out


def is_numbered(phases: Iterable[Phase]) -> bool:
    """Whether the dispatches carry their launch's number (PR 35 on)."""
    return any(p[0] == DISPATCH and "launch" in p[3] for p in phases)


def pair_programs(dispatches: List[Phase], programs: Iterable[Program],
                  anchors: Dict[int, List]) -> List[Pair]:
    """Every ``engine.dispatch`` with the step program it launched: the
    one whose ``DoEnqueueProgram`` (``anchors``, by ``run_id``) began at
    or after that dispatch began and before the next one did, both on the
    host's clock.  The small programs a build runs (``jit__ids_program``)
    are no step programs and pair with nothing.  A dispatch with no step
    program or more than one in its slot (the trace's edges) is left
    out."""
    disp = sorted(dispatches, key=lambda p: p[1])
    progs = sorted(
        ((anchors[p[3]][0], p) for p in programs
         if p[0] in trace_reduce.STEP_PROGRAMS
         and anchors.get(p[3], (None,))[0] is not None),
        key=lambda ep: ep[0])
    out: List[Pair] = []
    m = 0
    for i, d in enumerate(disp):
        nxt = disp[i + 1][1] if i + 1 < len(disp) else float("inf")
        while m < len(progs) and progs[m][0] < d[1]:
            m += 1
        mine = []
        while m < len(progs) and progs[m][0] < nxt:
            mine.append(progs[m][1])
            m += 1
        if len(mine) == 1:
            out.append((d, mine[0]))
    return out


def launches_by_number(pairs: Iterable[Pair], waits: Iterable[Phase]
                       ) -> List[Tuple[float, float, float, float]]:
    """``(dispatch start, wait end, program start, program end)`` for
    every paired dispatch whose OWN ``engine.device_wait`` is in the
    trace: the one that carries the same ``launch`` number.  In a step
    that ran ahead that wait comes a step later, after the next
    dispatch."""
    by_launch = {int(w[3]["launch"]): w for w in waits if "launch" in w[3]}
    out = []
    for d, prog in pairs:
        w = by_launch.get(int(d[3].get("launch", -1)))
        if w is not None:
            out.append((d[1], w[2], prog[1], prog[2]))
    return out


def match_launches(modules: List[trace_reduce.Event], phases: List[Phase],
                   runs: Iterable[Program] = (),
                   anchors: Optional[Dict[int, List]] = None) -> List[Tuple]:
    """Pair every ``engine.dispatch`` with the program it launched and the
    ``engine.device_wait`` that waited for it (one engine thread, one
    program a dispatch).  Returns ``(dispatch start, wait end, program
    start, program end)``, each in its own plane's clock.

    Where the phases carry the launch's number (``launch=<n>``, PR 35 on)
    a dispatch's wait is the one with ITS number, and its program the step
    program of its slot (:func:`pair_programs`, :func:`launches_by_number`).

    A trace from before the numbers pairs as it always did, and reads what
    it read: the wait is the first one after the dispatch.  Where the
    runtime's enqueue events are in the trace that pairing needs no clock
    at all: the program of a dispatch is the one (``runs``) whose enqueue
    (``anchors``) began between that dispatch's start and the next one's,
    both on the host's clock.  Otherwise it goes by time, trusting the
    profiler's alignment to 5 ms: the program that starts between a
    dispatch and the next.  A dispatch with no program or more than one in
    its slot (the trace's edges) is left out."""
    disp = [p for p in phases if p[0] == DISPATCH]
    waits = [p for p in phases if p[0] == DEVICE_WAIT]
    if is_numbered(phases):
        return launches_by_number(pair_programs(disp, runs, anchors or {}),
                                  waits)
    # (what orders a program against the dispatches, its start, its end)
    progs = sorted((anchors[rid][0], start, end) for _, start, end, rid in runs
                   if anchors and anchors.get(rid, (None,))[0] is not None)
    slack = 0.0
    if not progs:
        progs = [(s, s, s + d) for _, s, d in
                 sorted(modules, key=lambda e: e[1])]
        slack = 0.005
    out = []
    m = w = 0
    for i, d in enumerate(disp):
        nxt = disp[i + 1][1] if i + 1 < len(disp) else float("inf")
        while m < len(progs) and progs[m][0] < d[1] - slack:
            m += 1
        while w < len(waits) and waits[w][1] < d[1]:
            w += 1
        if m >= len(progs) or w >= len(waits):
            break
        # exactly one program and one wait belong to this dispatch
        if progs[m][0] >= nxt - slack or waits[w][1] >= nxt:
            continue
        if m + 1 < len(progs) and progs[m + 1][0] < nxt - slack:
            continue
        out.append((d[1], waits[w][2], progs[m][1], progs[m][2]))
    return out


def offset_bounds(launches: List[Tuple], runs: Iterable[Tuple] = (),
                  anchors: Optional[Dict[int, List]] = None
                  ) -> Optional[Tuple[float, float]]:
    """``(lo, hi)`` of the offset to ADD to host times to get device times.
    Causality: a program starts after its dispatch began (``dispatch +
    offset <= program start``, so ``offset <= start - dispatch``: the
    least over all launches bounds it from above) and the wait for its
    tokens ends after the program ended (``wait end + offset >= program
    end``: the most over all launches bounds it from below).  ``runs``
    (program start, end, run id) and the runtime's ``anchors`` of the same
    run id bound it the same way, and more closely: the program starts
    after its enqueue began and ends before its completion was heard."""
    his = [ps - d for d, _, ps, _ in launches]
    los = [pe - w for _, w, _, pe in launches]
    for start, end, rid in runs:
        enq, done = (anchors or {}).get(rid, (None, None))
        if enq is not None:
            his.append(start - enq)
        if done is not None:
            los.append(end - done)
    if not his or not los:
        return None
    return max(los), min(his)


def pin_launches(launches: List[Tuple], programs: Iterable[Program],
                 anchors: Optional[Dict[int, List]]
                 ) -> Optional[Tuple[float, float]]:
    """``(offset, width)``: the middle of :func:`offset_bounds` over the
    matched ``launches`` and every program's runtime anchors, and how far
    causality leaves it open.  ``None`` where nothing bounds it from both
    sides."""
    bounds = offset_bounds(
        launches, [(s, e, rid) for _, s, e, rid in programs
                   if rid is not None], anchors)
    if bounds is None:
        return None
    lo, hi = bounds
    return (lo + hi) / 2.0, hi - lo


def pin_offset(pairs: Iterable[Pair], waits: Iterable[Phase],
               programs: Iterable[Program], anchors: Dict[int, List]
               ) -> Optional[Tuple[float, float]]:
    """``(offset, width)``: what to ADD to host times to get device
    times, and how far causality leaves it open, from the launches
    matched BY NUMBER and every program's runtime anchors."""
    return pin_launches(launches_by_number(pairs, waits), programs, anchors)


def by_ahead(phases: Iterable[Phase]) -> List[Phase]:
    """The phases with ``engine.dispatch`` renamed ``engine.dispatch.ahead``
    where it carries ``ahead=1``, and ``engine.device_wait`` renamed the
    same way where the dispatch of ITS launch (same ``launch`` number) did:
    idle time under those is a launch that ran ahead and still came late,
    not the round trip of a launch the step waited for.  A trace without
    the integers comes back as it is."""
    phases = list(phases)
    ahead = {int(p[3]["launch"]) for p in phases
             if p[0] == DISPATCH and "launch" in p[3]
             and int(p[3].get("ahead", 0))}
    if not ahead:
        return phases
    return [(p[0] + AHEAD,) + tuple(p[1:])
            if p[0] in (DISPATCH, DEVICE_WAIT)
            and int(p[3].get("launch", -1)) in ahead else p
            for p in phases]


def scope_seconds(ops: Iterable[trace_reduce.Event],
                  scopes: Dict[str, str]) -> Dict[str, float]:
    """Device seconds by scope.  ``scopes`` names the operations whose
    metadata holds a path (``unscoped`` where the path passes through none
    of the program's scopes: a norm, a residual).  What the compiler put
    in itself -- layout copies, slices, the second pass of a sort --
    carries no path at all: such an operation counts to the scope of the
    named operations before and after it where the two agree (the copy
    and the sort pass inside the sampler are the sampler's), and is
    ``unscoped`` where they do not."""
    ops = sorted(ops, key=lambda e: e[1])
    named = [scopes.get(name) for name, _, _ in ops]
    after: List[Optional[str]] = [None] * len(ops)
    nxt = None
    for i in range(len(ops) - 1, -1, -1):
        after[i] = nxt
        if named[i] is not None:
            nxt = named[i]
    out: Dict[str, float] = {}
    prev = None
    for i, (_, _, dur) in enumerate(ops):
        key = named[i]
        if key is None:
            key = prev if prev is not None and prev == after[i] else UNSCOPED
        else:
            prev = key
        out[key] = out.get(key, 0.0) + dur
    return out


def analyse(planes: Dict, phases: List[Phase],
            scopes: Dict[str, Dict[str, str]],
            runs: Optional[Dict[str, List[Tuple]]] = None,
            anchors: Optional[Dict[int, List]] = None) -> Optional[Dict]:
    """Everything the readers report, averaged over the chips like
    ``trace_reduce.reduce``.  ``None`` for a trace with no phases."""
    if not planes or not any(p[0] == DISPATCH for p in phases):
        return None
    n = len(planes)
    out = {"gaps": {}, "scope_s": {}, "launches": 0.0, "gap_s": 0.0,
           "ops_s": 0.0, "offset_s": 0.0, "offset_width_s": 0.0,
           "matched": 0, "numbered": is_numbered(phases)}
    split = by_ahead(phases)
    for name, rows in planes.items():
        mine = (runs or {}).get(name, ())
        launches = match_launches(rows["modules"], phases, mine, anchors)
        pinned = pin_launches(launches, mine, anchors)
        if pinned is None:
            return None         # no launch whole inside the trace
        offset, width = pinned
        gaps = module_gaps(rows["modules"])
        for k, v in attribute(gaps, split, offset).items():
            out["gaps"][k] = out["gaps"].get(k, 0.0) + v / n
        for k, v in scope_seconds(rows["ops"], scopes.get(name, {})).items():
            out["scope_s"][k] = out["scope_s"].get(k, 0.0) + v / n
        out["launches"] += sum(
            1 for m in rows["modules"]
            if trace_reduce.norm(m[0]) in trace_reduce.STEP_PROGRAMS) / n
        out["gap_s"] += sum(b - a for a, b in gaps) / n
        out["ops_s"] += sum(e[2] for e in rows["ops"]) / n
        out["offset_s"] += offset / n
        # over the chips the width farthest from nought: one chip's
        # broken pin must not hide in a mean
        if abs(width) > abs(out["offset_width_s"]):
            out["offset_width_s"] = width
        out["matched"] += len(launches)
    fetches = [p for p in phases if p[0] == FETCH]
    out["fetch_bytes"] = sum(int(p[3].get("bytes", 0)) for p in fetches)
    out["fetches"] = len(fetches)
    out["has_scopes"] = any(s != UNSCOPED for per in scopes.values()
                            for s in per.values())
    return out


# --- what the readers call ----------------------------------------------------

_CACHE: Dict[Tuple, Optional[Dict]] = {}


def load(path: str) -> Optional[Dict]:
    phases, anchors, runs = load_host(path)
    return analyse(trace_reduce.load(path), phases, op_scopes(path), runs,
                   anchors)


def analysis(trace: Optional[Dict], root: str = harness.ROOT
             ) -> Optional[Dict]:
    """The analysis of the trace the launcher left under
    ``<root>/.bench_trace`` in this run: ``None`` when the run was not
    traced (``trace`` is the reduced device trace the harness hands every
    reader) or the trace holds no phases.  Parsed once a process."""
    if not trace:
        return None
    path = trace_reduce.find_xplane(os.path.join(root, ".bench_trace"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        try:
            _CACHE[key] = load(path)
        except Exception:
            # a trace this module cannot read costs the run these metrics,
            # and says so; it must not cost the run its result line
            import traceback

            print("benchmark: host_spans could not read the trace:\n"
                  + traceback.format_exc(), file=sys.stderr)
            _CACHE[key] = None
        if _CACHE[key] is not None and offset_width_ms(_CACHE[key]) is None:
            print("benchmark: host_spans: engine.gap_offset_width_ms is not "
                  "reported, and the engine.gap_* of this run are put down "
                  "to the wrong phases by as much: the host plane's offset "
                  f"is left {1e3 * _CACHE[key]['offset_width_s']:.3f} ms "
                  f"open (negative, or over {1e3 * MAX_WIDTH_S:g} ms)",
                  file=sys.stderr)
    return _CACHE[key]


def offset_width_ms(a: Dict) -> Optional[float]:
    """What causality leaves open for the host plane's offset, in ms.  In
    a trace whose launches are paired by number the pin is sound only
    where that is positive and at most :data:`MAX_WIDTH_S`: ``None``
    otherwise.  A trace from before the numbers reads what it read."""
    width = a["offset_width_s"]
    if a.get("numbered") and not 0.0 <= width <= MAX_WIDTH_S:
        return None
    return 1e3 * width


def gap_ms(trace: Optional[Dict], phase: str) -> Optional[float]:
    """Device idle time under ``phase``, per launch, in ms."""
    a = analysis(trace)
    if a is None or not a["launches"]:
        return None
    return 1e3 * a["gaps"].get(phase, 0.0) / a["launches"]


def scope_share(trace: Optional[Dict], scope: str) -> Optional[float]:
    """Share of the device's busy time in operations under ``scope``: the
    denominator of ``kernels.sampler_share``."""
    a = analysis(trace)
    if a is None or not a["has_scopes"] or not trace.get("busy_s"):
        return None
    return 100.0 * a["scope_s"].get(scope, 0.0) / trace["busy_s"]


def main(argv=None) -> int:
    """``python benchmarks/host_spans.py <dir or file>``: a trace's phases,
    gap table and scope shares, by hand."""
    import json

    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    a = load(path)
    print(json.dumps(a, indent=1))
    if a:
        per = {k: round(1e3 * v / a["launches"], 4)
               for k, v in sorted(a["gaps"].items(), key=lambda kv: -kv[1])}
        print("ms of idle per launch:", json.dumps(per))
        print("host ms per launch:", 1e3 * a["gap_s"] / a["launches"])
        print("scope share of op time, %:", json.dumps(
            {k: round(100 * v / a["ops_s"], 3)
             for k, v in a["scope_s"].items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
