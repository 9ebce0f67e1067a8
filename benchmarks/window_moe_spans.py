"""Device time under the scopes of the parallel block with window and
global attention and held experts (``attn_window``, ``attn_global``,
``moe_*``: ``paddle_tpu/models/window_moe.py``, ``parallel/moe.py``) and
the integers the engine's phases carry for it (``window_tokens`` on
``engine.build``; ``moe_pairs_held`` and ``moe_held_touched`` on
``engine.fetch``), for the per-layer metrics of the cell
``command-a-plus-05-2026.doc-reasoning-decode``.

``host_spans.SCOPES`` is fixed and takes the OUTERMOST scope, so to the
accepted readers these operations are ``attn``'s and ``mlp``'s.  This
reader looks for a sub-scope anywhere on an operation's path (what is
under ``attn`` / ``mlp`` and in none of them counts to ``attn`` / ``mlp``),
takes times per program, and LEAVES OUT an event that contains other
events of its line, with ``moe_mla_spans.py``'s arithmetic (a prompt's
query blocks and the held pairs' passes are ``while`` loops, which the TPU
writes as one event spanning the body AND the body's operations).

Where the trace holds no such scope or integer -- the parent of the PR
that added them, or another model -- every function returns ``None``.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks import harness, host_spans, moe_mla_spans, trace_reduce

OUTERS = ("attn", "mlp")
SUB_SCOPES = ("attn_window", "attn_global", "moe_router", "moe_dispatch",
              "moe_experts", "moe_combine", "moe_shared")
MLP = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
       "moe_shared", "mlp")
ABSENT = ("moe_dispatch", "moe_combine")
BUILD = "engine.build"
NONE = moe_mla_spans.NONE


def sub_scope_of(path: str) -> str:
    """The first of :data:`SUB_SCOPES` on an operation's path, else the
    outer scope the path is under."""
    parts = path.split("/")
    for part in parts:
        if part in SUB_SCOPES:
            return part
    for outer in OUTERS:
        if outer in parts:
            return outer
    return NONE


def op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation name: scope}}`` from the event
    metadata (``host_spans`` reads the records).  XLA's grouped matmul
    has no path and is known by its name (``moe_mla_spans.KERNELS``)."""
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
                scopes[ename] = sub_scope_of(best[1])
            elif moe_mla_spans.kernel_scope(ename) is not None:
                scopes[ename] = moe_mla_spans.kernel_scope(ename)
        out[name] = scopes
    return out


def ints_of(phases: Iterable[host_spans.Phase]) -> Optional[Dict]:
    """Over the traced phases that carry this model's integers: from
    ``engine.build`` of decode launches the sum and the largest
    ``window_tokens``; from ``engine.fetch`` of decode launches the sums
    of ``moe_assignments``, ``moe_pairs_held``, ``moe_held_touched``.
    ``None`` when no phase carries any."""
    out = {"builds": 0, "window_tokens": 0, "window_tokens_max": 0,
           "fetches": 0, "assignments": 0, "pairs_held": 0,
           "held_touched": 0}
    for name, _, _, stats in phases:
        if name == BUILD and "window_tokens" in stats:
            out["builds"] += 1
            out["window_tokens"] += int(stats["window_tokens"])
            out["window_tokens_max"] = max(out["window_tokens_max"],
                                           int(stats["window_tokens"]))
        elif name == host_spans.FETCH and "moe_pairs_held" in stats \
                and int(stats.get("moe_decode", 0)):
            out["fetches"] += 1
            out["assignments"] += int(stats["moe_assignments"])
            out["pairs_held"] += int(stats["moe_pairs_held"])
            out["held_touched"] += int(stats["moe_held_touched"])
    return out if out["builds"] or out["fetches"] else None


def analyse(planes: Dict, phases: List[host_spans.Phase],
            scopes: Dict[str, Dict[str, str]]) -> Optional[Dict]:
    """Averaged over the chips like ``trace_reduce.reduce``; ``None`` for
    a trace in which no operation sits under ``attn_window`` or
    ``attn_global`` and no phase carries one of the integers."""
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
    mine = any(k in ("attn_window", "attn_global")
               for per in by_mod.values() for k in per)
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

            print("benchmark: window_moe_spans could not read the trace:\n"
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
    return m if "sliding_window" in m and "layer_types" in m else None


def _itemsize(c: Dict) -> int:
    return 2 if c["engine"]["pool_dtype"] == "bfloat16" else 4


def _per_decode_launch(a: Dict, key: str, count: str) -> Optional[float]:
    """The integer ``key`` summed over ALL traced decode programs: the
    phases give its mean a launch, the device trace the launches."""
    from benchmarks import layer_lib

    i = a.get("ints")
    if not i or not i[count]:
        return None
    return i[key] * a["module_launches"].get(layer_lib.DECODE, 0.0) / i[count]


def window_decode_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read, in every window layer, the ring entries the
    traced decode launches' rows see (``min(length, window)`` a row), over
    the device time under ``attn_window`` in the decode program.  Bound:
    memory."""
    from benchmarks import layer_lib, roofline_window_moe as rf

    t = scope_s(a, "attn_window", layer_lib.DECODE)
    m = _model(c)
    if not t or m is None or not c.get("peaks"):
        return None
    tokens = _per_decode_launch(a, "window_tokens", "builds")
    if not tokens:
        return None
    need = rf.window_decode_bytes(m, tokens, _itemsize(c)) \
        / c["peaks"]["bytes_per_s"]
    return layer_lib.ratio(need, t, 100.0)


def global_decode_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read the global layers' pages of the cache tokens
    the traced decode launches had to read, over the device time under
    ``attn_global`` in the decode program.  Bound: memory."""
    from benchmarks import layer_lib, roofline_window_moe as rf

    t = scope_s(a, "attn_global", layer_lib.DECODE)
    m = _model(c)
    if not t or m is None or "traced" not in c or not c.get("peaks"):
        return None
    kv = c["traced"]["probe"]["decode_kv_tokens"]
    need = rf.global_decode_bytes(m, kv, _itemsize(c)) \
        / c["peaks"]["bytes_per_s"]
    return layer_lib.ratio(need, t, 100.0)


def moe_held_experts_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read every held expert a traced decode launch's
    pairs reached (and to multiply them), over the device time under
    ``moe_experts`` in the decode program.  Bound: memory."""
    from benchmarks import layer_lib, roofline_window_moe as rf

    t = scope_s(a, "moe_experts", layer_lib.DECODE)
    m = _model(c)
    if not t or m is None or not c.get("peaks"):
        return None
    touched = _per_decode_launch(a, "held_touched", "fetches")
    pairs = _per_decode_launch(a, "pairs_held", "fetches")
    if not touched:
        return None
    need = rf.roofline_seconds(rf.held_experts_bytes(m, touched),
                               rf.held_experts_flops(m, pairs), c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def window_attn_share(trace: Optional[Dict], a: Optional[Dict]
                      ) -> Optional[float]:
    """Everything under ``attn_window`` over the device's busy time."""
    if a is None or not (trace or {}).get("busy_s"):
        return None
    t = scope_s(a, "attn_window")
    return 100.0 * t / trace["busy_s"] if t else None


def moe_absent_pairs_share(a: Optional[Dict]) -> Optional[float]:
    """Device time under ``moe_dispatch`` and ``moe_combine`` over all
    time under ``mlp``: the sort over ALL routed pairs and the sum back
    per token, which the seven pairs in eight that are another chip's
    still cost here."""
    if a is None:
        return None
    mlp = sum(scope_s(a, s) for s in MLP)
    return 100.0 * sum(scope_s(a, s) for s in ABSENT) / mlp if mlp else None


def window_ring_peak_share(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """The most ring entries the rows of one traced decode launch held
    valid, over the entries of all rings (``max_num_seqs`` x window): a
    short sequence's unused ring is what a ring costs."""
    i = (a or {}).get("ints")
    m = _model(c)
    cap = (c.get("engine") or {}).get("max_num_seqs")
    if not i or not i["builds"] or m is None or not cap:
        return None
    return 100.0 * i["window_tokens_max"] / (cap * m["sliding_window"])


def moe_held_pair_share(a: Optional[Dict]) -> Optional[float]:
    """Pairs routed to experts held here over all pairs routed, over the
    traced decode launches and expert layers."""
    i = (a or {}).get("ints")
    if not i or not i["assignments"]:
        return None
    return 100.0 * i["pairs_held"] / i["assignments"]


def main(argv=None) -> int:
    import json

    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(json.dumps(load(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
