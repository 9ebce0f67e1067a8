"""From a profiler trace (``.xplane.pb``) to device metrics.

The reduction every PR is measured with, kept here so that none can change
it: busy and idle share of the device, the idle gaps attributed to the step
programs on either side, device time per program and per operation, and
the ``breakdown`` the driver copies into the ledger.

Two layers.  :func:`load` reads the file with ``jax.profiler.ProfileData``
(nothing but JAX) into plain tuples; everything after it is arithmetic on
those tuples and is what the tests check by hand:

* a device plane is one whose name starts with ``/device:``; its line
  ``XLA Modules`` holds one event per executed program (``jit__decode_fn``,
  ``jit__prefill_fn``, ...), its line ``XLA Ops`` one event per operation;
* busy time is the UNION of the operation intervals; the traced window
  runs from the first event's start to the last event's end on the device
  planes (the trace itself records no other bounds);
* an operation belongs to the program whose event contains its start;
* a LAUNCH is an executed program of one of the engine's five step
  families (:data:`STEP_PROGRAMS`).  The small programs a step runs beside
  its step program (``jit__ids_program``, ``jit__pad_tokens``) are modules
  and stand in the gaps under their names, but no launch: the idle seconds
  are divided by the steps the device took.

Names are normalised (:func:`norm`) by dropping what changes from compile
to compile: ``fusion.123`` -> ``fusion``, ``jit__decode_fn(987654)`` ->
``jit__decode_fn``.  The program gives its kernels no stable names yet
(PERF.md, Open questions): the paged decode kernel is what starts with
``custom-call`` inside ``jit__decode_fn`` and the sampler's sort is ``sort``.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]        # name, start seconds, duration seconds

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
# the engine's step families, by their programs' normalised names
STEP_PROGRAMS = ("jit__decode_fn", "jit__prefill_fn",
                 "jit__chunk_prefill_fn", "jit__unified_fn",
                 "jit__burst_fn")
_SUFFIX = re.compile(r"(\(\d+\)|[.\d]+)$")
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def norm(name: str) -> str:
    """Drop ids that change between compiles; keep the words.  The
    profiler names an operation by its whole HLO line, ``%fusion.5 =
    bf16[8,128]{1,0} fusion(...)``: that becomes the opcode, joined to the
    instruction's name where the name says more (``custom-call__decode_fn``
    is the Pallas kernel called from ``_decode_fn``,
    ``fusion_convolution_multiply_fusion`` a fusion XLA named by its body)."""
    if " = " in name:
        lhs, rhs = name.split(" = ", 1)
        lhs = norm(lhs)
        m = _OPCODE.search(" " + rhs)
        opcode = m.group(1) if m else lhs
        return opcode if lhs == opcode else f"{opcode}_{lhs}"
    name = name.lstrip("%")
    while True:
        cut = _SUFFIX.sub("", name)
        if cut == name or not cut:
            return name
        name = cut


def find_xplane(log_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def once_a_file(parse):
    """``parse(path)`` kept by the file's path, size and time: a traced
    run's readers each ask for the same parse of the same 80 MB, and read
    what they are given without changing it."""
    kept: Dict[Tuple, object] = {}

    def cached(path: str):
        st = os.stat(path)
        key = (os.path.abspath(path), st.st_size, st.st_mtime_ns)
        if key not in kept:
            kept.clear()        # one trace a process is ever current
            kept[key] = parse(path)
        return kept[key]

    cached.__doc__ = parse.__doc__
    return cached


@once_a_file
def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{device plane: {"modules": [...], "ops": [...]}}``, seconds."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        rows = {"modules": [], "ops": []}
        for line in plane.lines:
            key = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
            if key is None:
                continue
            rows[key] = sorted(
                ((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                 for ev in line.events), key=lambda e: e[1])
        if rows["modules"] or rows["ops"]:
            out[plane.name] = rows
    return out


# --- arithmetic on plain tuples ----------------------------------------------

def union_seconds(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if cur_hi is None or start > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = start, end
        else:
            cur_hi = max(cur_hi, end)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def window_of(rows: Dict[str, List[Event]]) -> Tuple[float, float]:
    evs = rows["modules"] + rows["ops"]
    return (min(e[1] for e in evs), max(e[1] + e[2] for e in evs))


def gaps_between_modules(modules: List[Event]) -> Dict[str, float]:
    """Idle seconds between consecutive programs, keyed by the pair
    ``<program before>_-__<program after>``."""
    out: Dict[str, float] = {}
    mods = sorted(modules, key=lambda e: e[1])
    for (a, sa, da), (b, sb, _) in zip(mods, mods[1:]):
        gap = sb - (sa + da)
        if gap > 0:
            key = f"{norm(a)}_-__{norm(b)}"
            out[key] = out.get(key, 0.0) + gap
    return out


def ops_by_module(rows: Dict[str, List[Event]]) -> Dict[str, Dict[str, float]]:
    """Device seconds per normalised operation name, within the program
    whose event contains the operation's start (``-`` for none)."""
    mods = sorted(rows["modules"], key=lambda e: e[1])
    out: Dict[str, Dict[str, float]] = {}
    i = 0
    for name, start, dur in sorted(rows["ops"], key=lambda e: e[1]):
        while i + 1 < len(mods) and mods[i + 1][1] <= start:
            i += 1
        inside = mods and mods[i][1] <= start < mods[i][1] + mods[i][2]
        mod = norm(mods[i][0]) if inside else "-"
        per = out.setdefault(mod, {})
        per[norm(name)] = per.get(norm(name), 0.0) + dur
    return out


def reduce_plane(rows: Dict[str, List[Event]]) -> Dict:
    lo, hi = window_of(rows)
    busy = union_seconds(rows["ops"] or rows["modules"])
    modules: Dict[str, Dict] = {}
    for name, _, dur in rows["modules"]:
        m = modules.setdefault(norm(name), {"count": 0, "seconds": 0.0})
        m["count"] += 1
        m["seconds"] += dur
    by_mod = ops_by_module(rows)
    ops: Dict[str, float] = {}
    for per in by_mod.values():
        for k, v in per.items():
            ops[k] = ops.get(k, 0.0) + v
    gaps = gaps_between_modules(rows["modules"])
    return {"window_s": hi - lo, "busy_s": busy, "modules": modules,
            "ops": ops, "ops_by_module": by_mod, "gaps": gaps,
            "gap_s": sum(gaps.values()),
            "launches": sum(m["count"] for name, m in modules.items()
                            if name in STEP_PROGRAMS)}


def reduce(planes: Dict[str, Dict[str, List[Event]]]) -> Optional[Dict]:
    """Over the device planes: seconds are averaged over the chips used,
    names are summed and then averaged the same way."""
    per = [reduce_plane(rows) for rows in planes.values()]
    if not per:
        return None
    n = len(per)

    def avg(key):
        return sum(p[key] for p in per) / n

    def merge(key):
        out: Dict[str, float] = {}
        for p in per:
            for k, v in p[key].items():
                out[k] = out.get(k, 0.0) + v / n
        return out

    modules: Dict[str, Dict] = {}
    for p in per:
        for k, v in p["modules"].items():
            m = modules.setdefault(k, {"count": 0.0, "seconds": 0.0})
            m["count"] += v["count"] / n
            m["seconds"] += v["seconds"] / n
    by_mod: Dict[str, Dict[str, float]] = {}
    for p in per:
        for mod, ops in p["ops_by_module"].items():
            d = by_mod.setdefault(mod, {})
            for k, v in ops.items():
                d[k] = d.get(k, 0.0) + v / n
    out = {"chips": n, "window_s": avg("window_s"), "busy_s": avg("busy_s"),
           "gap_s": avg("gap_s"), "launches": avg("launches"),
           "modules": modules, "ops": merge("ops"), "ops_by_module": by_mod,
           "gaps": merge("gaps")}
    out["idle_share"] = 1.0 - out["busy_s"] / out["window_s"]
    return out


def breakdown(red: Dict, top: int = 10) -> Dict:
    """What the driver copies into the ledger: the operations that took
    most device time and the longest idle gaps, by the programs around."""
    def head(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": head(red["ops"]), "idle_gaps": head(red["gaps"])}


def main(argv=None) -> int:
    """``python benchmarks/trace_reduce.py <dir or file>``: look at a
    trace by hand -- planes, lines, and the reduction."""
    import json

    from jax.profiler import ProfileData

    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = find_xplane(path)
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({norm(e.name) for e in evs})
            print(f"  line {line.name!r}: {len(evs)} events; "
                  f"{len(names)} names: {names[:12]}")
    red = reduce(load(path))
    if red:
        red.pop("ops_by_module")
        print(json.dumps(red, indent=1)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
