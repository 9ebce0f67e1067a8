"""The spans a trace holds beside the engine's step phases, line by line.

``host_spans`` reads the phases of the engine's step, which are all on the
engine thread, and takes them from every line of the host plane at once.
Since the serving loop runs one launch ahead (PERF.md section 3) the device
no longer waits for that thread alone, and the program names four more
things on the profiler's clock (``paddle_tpu/observability/tracer.py``
``THREAD_SPANS``), each on the thread it happens on:

* ``ahead.settle`` (engine thread): a step that could not run ahead reading
  the launch in flight, with the ``reason`` as an index into
  ``SETTLE_REASONS`` and the ``launch`` read;
* ``server.accept``, ``server.wake``, ``server.write`` (the server's loop
  thread, which shares the interpreter lock with the engine thread);
* ``proc.gc`` (whichever thread the collector ran on).

The profiler keeps one line a thread, so this module keeps the lines apart
(:func:`load_lines`): the engine thread's line is the one that holds
``engine.dispatch``, the loop thread's the one that holds ``server.wake``.
A step program (``trace_reduce.STEP_PROGRAMS``) is paired with its
``engine.dispatch`` by the runtime's ``DoEnqueueProgram`` of the same
``run_id`` beginning inside that dispatch's slot on the HOST's clock, which
needs no offset between the planes (``host_spans.pair_programs``); the idle gaps
are ``host_spans.module_gaps`` over ALL programs, the ones
``engine.host_ms_per_step`` sums.  Where host spans are laid over device
gaps the host plane is shifted by an offset pinned from the runtime's
``run_id`` anchors and from dispatch / wait pairs matched BY ``launch``
(``host_spans.pin_offset``, which pins the ``engine.gap_*`` too) -- never
by "the next wait", which since the loop
runs ahead is another launch's; where what causality leaves open is
negative or wider than :data:`MAX_WIDTH_S` the overlap is not reported,
and standard error says so.

A reader calls :func:`value`; it gives ``None`` for a trace that holds
none of the new spans (a program from before them), so such a run's line
is what it was.  Everything below :func:`load_lines` is arithmetic on
plain tuples, checked by hand in ``tests/bench_suite``.

    python benchmarks/thread_spans.py <dir or file>
"""

from __future__ import annotations

import os
import statistics
import sys
from typing import Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))       # when run as a script

from benchmarks import harness, host_spans, trace_reduce    # noqa: E402
# the pairing and the pin are ``host_spans``'s: one implementation for the
# ``engine.gap_*`` and for the overlap with the loop thread's spans
from benchmarks.host_spans import (                          # noqa: E402
    MAX_WIDTH_S, Interval, Pair, Phase, Program, launches_by_number,
    pair_programs, pin_offset)
from benchmarks.trace_reduce import STEP_PROGRAMS           # noqa: E402

SETTLE = "ahead.settle"
ACCEPT, WAKE, WRITE = "server.accept", "server.wake", "server.write"
GC = "proc.gc"
EMIT = "engine.emit"
# the program's ``observability.tracer`` tuples, letter for letter (held
# to them by ``tests/bench_suite/test_bm_thread_spans.py``): this module
# is read by a client that imports neither JAX nor the program
THREAD_SPANS = (SETTLE, ACCEPT, WAKE, WRITE, GC)
SETTLE_REASONS = ("prefill", "admit", "preempt", "finish", "audit", "fault",
                  "task", "bare", "family")
NO_SETTLE = "none"      # not ahead, and no settle before it: nothing flew
FRONT_DOOR = (ACCEPT, WAKE, WRITE)
DECODE = "jit__decode_fn"


def load_lines(path: str) -> Tuple[List[List[Phase]], Dict[int, List],
                                   Dict[str, List[Program]]]:
    """One pass over the file: every line of the host plane that holds a
    phase or one of the new spans, as its own list by start; the runtime's
    anchors ``{run_id: [enqueue start, complete end]}`` as
    ``host_spans.load_host`` gives them; and ``{device plane: [program]}``
    for EVERY executed program, names normalised."""
    from jax.profiler import ProfileData

    wanted = host_spans.PHASE_PREFIXES + THREAD_SPANS
    lines: List[List[Phase]] = []
    anchors: Dict[int, List] = {}
    programs: Dict[str, List[Program]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != trace_reduce.MODULE_LINE:
                    continue
                rows = programs.setdefault(plane.name, [])
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    rows.append((trace_reduce.norm(ev.name), start,
                                 start + ev.duration_ns * 1e-9,
                                 dict(ev.stats).get("run_id")))
                rows.sort(key=lambda p: p[1])
            continue
        if plane.name != host_spans.HOST_PLANE:
            continue
        for line in plane.lines:
            mine: List[Phase] = []
            for ev in line.events:
                name = ev.name
                if name.startswith(wanted):
                    start = ev.start_ns * 1e-9
                    mine.append((name, start,
                                 start + ev.duration_ns * 1e-9,
                                 dict(ev.stats)))
                elif name in (host_spans.ENQUEUE, host_spans.COMPLETE):
                    rid = dict(ev.stats).get("run_id")
                    if rid is None:
                        continue
                    a = anchors.setdefault(rid, [None, None])
                    start = ev.start_ns * 1e-9
                    if name == host_spans.ENQUEUE:
                        a[0] = start if a[0] is None else min(a[0], start)
                    else:
                        end = start + ev.duration_ns * 1e-9
                        a[1] = end if a[1] is None else max(a[1], end)
            if mine:
                lines.append(sorted(mine, key=lambda p: p[1]))
    return lines, anchors, programs


# --- arithmetic on plain tuples -----------------------------------------------

def line_of(lines: Iterable[List[Phase]], name: str) -> List[Phase]:
    """The line that holds most spans called ``name`` (a thread's line is
    known by what it holds); ``[]`` where none holds any."""
    best, most = [], 0
    for line in lines:
        n = sum(1 for p in line if p[0] == name)
        if n > most:
            best, most = line, n
    return best


def ahead_share(pairs: Iterable[Pair]) -> Optional[float]:
    """Of the decode launches, the share (%) that went out ahead."""
    flags = [int(d[3].get("ahead", 0)) for d, prog in pairs
             if prog[0] == DECODE]
    return 100.0 * sum(flags) / len(flags) if flags else None


def reason_of(settle: Phase) -> str:
    """The word for an ``ahead.settle``'s ``reason`` integer."""
    i = int(settle[3].get("reason", -1))
    return SETTLE_REASONS[i] if 0 <= i < len(SETTLE_REASONS) \
        else f"reason_{i}"


def settle_reasons(engine_line: Iterable[Phase]) -> Dict[float, str]:
    """``{dispatch start: reason}`` for every dispatch that did NOT go out
    ahead: the reason of the last ``ahead.settle`` since the last dispatch
    that did (one settle stands before a step's prefills and its decode
    launch alike), or ``none`` where nothing was in flight to settle."""
    out: Dict[float, str] = {}
    reason = NO_SETTLE
    for p in sorted(engine_line, key=lambda p: p[1]):
        if p[0] == SETTLE:
            reason = reason_of(p)
        elif p[0] == host_spans.DISPATCH:
            if int(p[3].get("ahead", 0)):
                reason = NO_SETTLE
            else:
                out[p[1]] = reason
    return out


def split_gaps(gaps: Iterable[Interval], programs: Iterable[Program],
               pairs: Iterable[Pair], reasons: Dict[float, str]) -> Dict:
    """The idle ``gaps`` (device clock) by the dispatch of the step
    program each one ENDS at -- the next step program at or after the
    gap's end, so a gap before the small programs of a build counts to the
    launch they were built for: ``settled`` seconds (``ahead=0``: the
    synchronous bubble), ``ahead`` seconds (the launch ran ahead and still
    came late), ``unpaired`` (a program whose dispatch the trace does not
    hold), and the settled seconds ``by_reason``."""
    steps = sorted((p for p in programs if p[0] in STEP_PROGRAMS),
                   key=lambda p: p[1])
    dispatch_of = {(prog[1], prog[3]): d for d, prog in pairs}
    out = {"settled": 0.0, "ahead": 0.0, "unpaired": 0.0, "by_reason": {}}
    j = 0
    for lo, hi in sorted(gaps):
        while j < len(steps) and steps[j][1] < hi:
            j += 1
        d = dispatch_of.get((steps[j][1], steps[j][3])) \
            if j < len(steps) else None
        if d is None:
            out["unpaired"] += hi - lo
        elif int(d[3].get("ahead", 0)):
            out["ahead"] += hi - lo
        else:
            out["settled"] += hi - lo
            why = reasons.get(d[1], NO_SETTLE)
            out["by_reason"][why] = out["by_reason"].get(why, 0.0) + hi - lo
    return out


def union_s(spans: Iterable[Phase]) -> float:
    return trace_reduce.union_seconds(
        [(name, s, e - s) for name, s, e, _ in spans])


def window_of(lines: Iterable[List[Phase]]) -> float:
    """Seconds from the first span's start to the last one's end over the
    given lines: the traced window on the host's clock."""
    spans = [p for line in lines for p in line]
    if not spans:
        return 0.0
    return max(p[2] for p in spans) - min(p[1] for p in spans)


def handoffs_s(engine_line: Iterable[Phase], loop_line: Iterable[Phase]
               ) -> List[float]:
    """For every ``server.wake`` that has token-bearing writes before the
    next wake: the end of the LAST of those ``server.write`` minus the
    START of the engine thread's stream hand-off that posted the wake (the
    last ``engine.emit`` carrying ``streams=`` that began before the wake
    did): how long a token the engine has waits for the socket.  From the
    span's start, not its end: the wake is posted INSIDE the span, and on a
    busy machine the loop thread has written the chunks before the engine
    thread gets to close it, which read a hand-off below nought (PR 42).  A write
    with ``tokens=0`` (a new stream's header and id chunk, a final chunk)
    is no token's and is not counted: a header follows an accept, not a
    wake."""
    emits = sorted((p for p in engine_line
                    if p[0] == EMIT and "streams" in p[3]),
                   key=lambda p: p[1])
    wakes = sorted((p for p in loop_line if p[0] == WAKE),
                   key=lambda p: p[1])
    writes = sorted((p for p in loop_line
                     if p[0] == WRITE and int(p[3].get("tokens", 0)) > 0),
                    key=lambda p: p[1])
    out: List[float] = []
    e = w = 0
    for i, wake in enumerate(wakes[:-1]):
        nxt = wakes[i + 1][1]
        while e + 1 < len(emits) and emits[e + 1][1] <= wake[1]:
            e += 1
        while w < len(writes) and writes[w][1] < wake[1]:
            w += 1
        last = None
        while w < len(writes) and writes[w][2] <= nxt:
            last = writes[w]
            w += 1
        if last is not None and emits and emits[e][1] <= wake[1]:
            out.append(last[2] - emits[e][1])
    return out


def median(values: List[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def analyse(lines: List[List[Phase]], anchors: Dict[int, List],
            programs: Dict[str, List[Program]]) -> Optional[Dict]:
    """Everything the readers report.  ``None`` for a trace with none of
    the new spans in it, or with no engine thread."""
    if not any(p[0] in THREAD_SPANS for line in lines for p in line):
        return None
    engine = line_of(lines, host_spans.DISPATCH)
    if not engine:
        return None
    loop = [p for p in line_of(lines, WAKE) if p[0] in FRONT_DOOR]
    dispatches = [p for p in engine if p[0] == host_spans.DISPATCH]
    waits = [p for p in engine if p[0] == host_spans.DEVICE_WAIT]
    reasons = settle_reasons(engine)
    window = window_of([engine, loop])
    out = {"window_s": window, "planes": len(programs),
           "pairs": 0, "decode_pairs": 0, "gap_s": 0.0,
           "settled_s": 0.0, "ahead_s": 0.0, "unpaired_s": 0.0,
           "by_reason": {}, "frontdoor_s": None, "offset_s": None,
           "offset_width_s": None, "ahead_share": None,
           "settles": {}, "spans": {}}
    for name in THREAD_SPANS:
        mine = [p for line in lines for p in line if p[0] == name]
        out["spans"][name] = {"count": len(mine),
                              "seconds": sum(e - s for _, s, e, _ in mine)}
    for p in engine:
        if p[0] == SETTLE:
            why = reason_of(p)
            out["settles"][why] = out["settles"].get(why, 0) + 1
    n = max(1, len(programs))
    shares, front, widths, offsets = [], [], [], []
    for rows in programs.values():
        pairs = pair_programs(dispatches, rows, anchors)
        gaps = host_spans.module_gaps([(nm, s, e - s)
                                       for nm, s, e, _ in rows])
        split = split_gaps(gaps, rows, pairs, reasons)
        out["pairs"] += len(pairs)
        out["decode_pairs"] += sum(1 for _, pr in pairs if pr[0] == DECODE)
        out["gap_s"] += sum(b - a for a, b in gaps) / n
        for key in ("settled", "ahead", "unpaired"):
            out[key + "_s"] += split[key] / n
        for why, s in split["by_reason"].items():
            out["by_reason"][why] = out["by_reason"].get(why, 0.0) + s / n
        share = ahead_share(pairs)
        if share is not None:
            shares.append(share)
        pinned = pin_offset(pairs, waits, rows, anchors)
        if pinned is not None:
            offsets.append(pinned[0])
            widths.append(pinned[1])
            over = host_spans.attribute(gaps, loop, pinned[0])
            front.append(sum(v for k, v in over.items() if k in FRONT_DOOR))
    if shares:
        out["ahead_share"] = sum(shares) / len(shares)
    if widths:
        out["offset_s"] = sum(offsets) / len(offsets)
        out["offset_width_s"] = max(widths, key=abs)
        if 0.0 <= min(widths) and max(widths) <= MAX_WIDTH_S:
            out["frontdoor_s"] = sum(front) / n
    out["loop_busy_s"] = union_s(loop)
    out["gc_s"] = out["spans"][GC]["seconds"]
    out["handoff_ms"] = median([1e3 * v for v in handoffs_s(engine, loop)])
    return out


# --- what the readers call ----------------------------------------------------

_CACHE: Dict[Tuple, Optional[Dict]] = {}


def load(path: str) -> Optional[Dict]:
    return analyse(*load_lines(path))


def analysis(trace: Optional[Dict], root: str = harness.ROOT
             ) -> Optional[Dict]:
    """The analysis of the trace the launcher left under
    ``<root>/.bench_trace`` in this run, parsed once a process; ``None``
    when the run was not traced or the trace holds none of the spans."""
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

            print("benchmark: thread_spans could not read the trace:\n"
                  + traceback.format_exc(), file=sys.stderr)
            _CACHE[key] = None
        a = _CACHE[key]
        if a is not None and a["frontdoor_s"] is None:
            width = a["offset_width_s"]
            print("benchmark: thread_spans: engine.idle_frontdoor_share is "
                  "not reported: the host plane's offset is "
                  + ("not pinned from both sides" if width is None else
                     f"left {1e3 * width:.3f} ms open (negative, or over "
                     f"{1e3 * MAX_WIDTH_S:g} ms)"), file=sys.stderr)
    return _CACHE[key]


def share_of(a: Optional[Dict], part: str, whole: str) -> Optional[float]:
    """``100 * a[part] / a[whole]``, or ``None``."""
    if a is None or a.get(part) is None or not a.get(whole):
        return None
    return 100.0 * a[part] / a[whole]


def value(trace: Optional[Dict], metric: str,
          a: Optional[Dict] = None) -> Optional[float]:
    """The value of one of the six metrics (``layer_metrics/<metric>.py``
    without its ``.chat`` / ``.batch``) from the analysis ``a`` (this
    run's where not given)."""
    a = analysis(trace) if a is None else a
    if a is None:
        return None
    if metric == "engine.ahead_share":
        return a["ahead_share"]
    if metric == "engine.idle_settled_share":
        # of the gaps whose dispatch is known
        known = a["settled_s"] + a["ahead_s"]
        return 100.0 * a["settled_s"] / known if known else None
    if metric == "engine.idle_frontdoor_share":
        return share_of(a, "frontdoor_s", "gap_s")
    if metric == "frontdoor.loop_busy_share":
        return share_of(a, "loop_busy_s", "window_s")
    if metric == "frontdoor.handoff_ms":
        return a["handoff_ms"]
    if metric == "engine.gc_ms_per_s":
        return 1e3 * a["gc_s"] / a["window_s"] if a["window_s"] else None
    raise KeyError(metric)


METRICS = ("engine.ahead_share", "engine.idle_settled_share",
           "engine.idle_frontdoor_share", "frontdoor.loop_busy_share",
           "frontdoor.handoff_ms", "engine.gc_ms_per_s")


def main(argv=None) -> int:
    """``python benchmarks/thread_spans.py <dir or file>``: a trace's six
    metrics, its idle gaps by settle reason and its spans, by hand."""
    import json

    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    a = load(path)
    print(json.dumps(a, indent=1))
    if a:
        print("metrics:", json.dumps(
            {m: value(None, m, a) for m in METRICS}))
        print("idle seconds by the dispatch a gap ends at:", json.dumps(
            {"ahead": a["ahead_s"], "unpaired": a["unpaired_s"],
             **{f"settled.{k}": v for k, v in sorted(
                 a["by_reason"].items(), key=lambda kv: -kv[1])}}))
        print("settles by reason:", json.dumps(a["settles"]))
        print("spans:", json.dumps(a["spans"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
