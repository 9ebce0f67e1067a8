"""Device time under the scopes of chunk-summarised (EVA) attention
(``eva_attn`` with ``eva_local``, ``eva_remote``, ``eva_merge`` under it,
and ``eva_pool``: ``paddle_tpu/ops/eva_attention.py``) and the integers
``engine.build`` carries for it a decode launch (``eva_ring_tokens``,
``eva_summary_rows``, ``eva_windows_closed``, ``eva_rows_held``), for the
per-layer metrics of the cell ``evabyte-6.5b.byte-reasoning-decode``.

``host_spans.SCOPES`` is fixed and takes the OUTERMOST scope, so to the
accepted readers these operations are ``attn``'s.  This reader takes
``eva_pool`` or ``eva_attn`` wherever one is on an operation's path (the
three sub-scopes count to ``eva_attn``), takes times per program, and
leaves out an event that contains other events of its line, with
``moe_mla_spans.py``'s arithmetic (a prompt's windows and query blocks are
``while`` loops, which the TPU writes as one event spanning the body AND
the body's operations).

It repeats ``window_moe_spans.py``'s ``op_paths`` with another choice of
scope, as ``hc_moe_mla_spans.py`` does (a later ``benchmark`` PR can fold
the ``*_spans.py`` files).

Where the trace holds no such scope or integer -- the parent of the PR
that added them, or another model -- every function returns ``None``.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))       # when run as a script

from benchmarks import (harness, host_spans, moe_mla_spans,    # noqa: E402
                        trace_reduce)

SCOPES = ("eva_pool", "eva_attn")
BUILD = "engine.build"
NONE = moe_mla_spans.NONE


def scope_of(path: str) -> str:
    """``eva_pool`` or ``eva_attn`` where one is on the path."""
    parts = path.split("/")
    for scope in SCOPES:
        if scope in parts:
            return scope
    return NONE


def op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation name: scope}}`` from the event metadata
    (``host_spans`` reads the records)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, wire, val in host_spans._fields(buf, 0, len(buf)):
        if num != 1 or wire != 2:
            continue
        name, stat_names, events = host_spans._metadata_of_plane(buf, *val)
        if not name.startswith("/device:"):
            continue
        wanted = {sid: host_spans._OP_NAME_STATS.index(n)
                  for sid, n in stat_names.items()
                  if n in host_spans._OP_NAME_STATS}
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


def ints_of(phases: Iterable[host_spans.Phase]) -> Optional[Dict]:
    """Over the traced ``engine.build`` phases of decode launches that
    carry this model's integers: their number, the sums of
    ``eva_ring_tokens``, ``eva_summary_rows`` and ``eva_windows_closed``,
    and the largest ``eva_rows_held``.  ``None`` when no phase carries
    any."""
    out = {"builds": 0, "ring_tokens": 0, "summary_rows": 0,
           "windows_closed": 0, "rows_held_max": 0}
    for name, _, _, stats in phases:
        if name != BUILD or "eva_ring_tokens" not in stats:
            continue
        out["builds"] += 1
        out["ring_tokens"] += int(stats["eva_ring_tokens"])
        out["summary_rows"] += int(stats["eva_summary_rows"])
        out["windows_closed"] += int(stats.get("eva_windows_closed", 0))
        out["rows_held_max"] = max(out["rows_held_max"],
                                   int(stats.get("eva_rows_held", 0)))
    return out if out["builds"] else None


def analyse(planes: Dict, phases: List[host_spans.Phase],
            scopes: Dict[str, Dict[str, str]]) -> Optional[Dict]:
    """Averaged over the chips like ``trace_reduce.reduce``; ``None`` for
    a trace in which no operation sits under ``eva_attn`` or ``eva_pool``
    and no phase carries one of the integers."""
    if not planes:
        return None
    n = len(planes)
    by_mod: Dict[str, Dict[str, float]] = {}
    launches: Dict[str, float] = {}
    for name, rows in planes.items():
        for mod, per in moe_mla_spans.scope_seconds_by_module(
                rows, scopes.get(name, {})).items():
            d = by_mod.setdefault(mod, {})
            for k, v in per.items():
                d[k] = d.get(k, 0.0) + v / n
        for mname, _, _ in rows["modules"]:
            k = trace_reduce.norm(mname)
            launches[k] = launches.get(k, 0.0) + 1.0 / n
    ints = ints_of(phases)
    mine = any(k in SCOPES for per in by_mod.values() for k in per)
    if ints is None and not mine:
        return None
    return {"scope_s": by_mod, "module_launches": launches, "ints": ints}


# --- what the readers call ----------------------------------------------------

_CACHE: Dict[Tuple, Optional[Dict]] = {}


def load(path: str) -> Optional[Dict]:
    phases, _, _ = host_spans.load_host(path)
    return analyse(trace_reduce.load(path), phases, op_paths(path))


def analysis(trace: Optional[Dict], root: str = harness.ROOT
             ) -> Optional[Dict]:
    """The analysis of the trace the launcher left under
    ``<root>/.bench_trace`` in this run, parsed once a process; ``None``
    when the run was not traced or the trace has nothing of this."""
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
            import traceback

            print("benchmark: eva_spans could not read the trace:\n"
                  + traceback.format_exc(), file=sys.stderr)
            _CACHE[key] = None
    return _CACHE[key]


def scope_s(a: Optional[Dict], scope: str, module: Optional[str] = None
            ) -> Optional[float]:
    """Device seconds under ``scope``, in ``module`` or in every program."""
    if a is None:
        return None
    mods = [module] if module else list(a["scope_s"])
    return sum(a["scope_s"].get(m, {}).get(scope, 0.0) for m in mods)


def _model(c: Dict) -> Optional[Dict]:
    m = c.get("model") or {}
    return m if "window_size" in m and "chunk_size" in m else None


def _itemsize(c: Dict) -> int:
    return 2 if c["engine"]["pool_dtype"] == "bfloat16" else 4


def eva_decode_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read, in every layer, the ring entries and summary
    rows the traced decode launches' rows see (the phases give their mean
    a launch, the device trace the launches), over the device time under
    ``eva_attn`` in the decode program.  Bound: memory."""
    from benchmarks import layer_lib, roofline_eva as rf

    t = scope_s(a, "eva_attn", layer_lib.DECODE)
    m = _model(c)
    i = (a or {}).get("ints")
    if not t or m is None or not c.get("peaks") or not i:
        return None
    rows = (i["ring_tokens"] + i["summary_rows"]) / i["builds"] \
        * a["module_launches"].get(layer_lib.DECODE, 0.0)
    if not rows:
        return None
    need = rf.decode_read_bytes(m, rows, _itemsize(c)) \
        / c["peaks"]["bytes_per_s"]
    return layer_lib.ratio(need, t, 100.0)


def eva_attn_share(trace: Optional[Dict], a: Optional[Dict]
                   ) -> Optional[float]:
    """Everything under ``eva_attn`` and ``eva_pool``, in every program,
    over the device's busy time."""
    if a is None or not (trace or {}).get("busy_s"):
        return None
    t = sum(scope_s(a, s) for s in SCOPES)
    return 100.0 * t / trace["busy_s"] if t else None


def eva_summary_read_share(a: Optional[Dict]) -> Optional[float]:
    """Summary rows over summary rows and ring entries, of what the traced
    decode launches' rows see."""
    i = (a or {}).get("ints")
    if not i or not i["ring_tokens"] + i["summary_rows"]:
        return None
    return 100.0 * i["summary_rows"] / (i["ring_tokens"] + i["summary_rows"])


def eva_summary_peak_share(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """The most summary rows the rows of one traced decode launch held
    (one a whole chunk of each sequence) over the rows allocated a layer
    (``num_blocks`` x ``block_size / chunk_size``)."""
    from benchmarks import roofline_eva as rf

    i = (a or {}).get("ints")
    m = _model(c)
    eng = c.get("engine") or {}
    if not i or m is None or not eng.get("num_blocks"):
        return None
    cap = eng["num_blocks"] * rf.rows_per_block(m, eng["block_size"])
    return 100.0 * i["rows_held_max"] / cap if cap else None


def main(argv=None) -> int:
    import json

    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(json.dumps(load(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
