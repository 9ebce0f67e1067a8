"""Device time under the scopes NESTED in ``attn`` and ``mlp`` (``mla_*``,
``moe_*``: ``paddle_tpu/models/moe_mla.py``, ``parallel/moe.py``) and the
routing integers ``engine.fetch`` carries, for the per-layer metrics of
the latent-attention / routed-expert cell.

``host_spans.SCOPES`` is fixed and ``host_spans.scope_of`` takes the
OUTERMOST scope of an operation's path, so an operation under
``attn/mla_decode_core`` is ``attn``'s there.  This reader looks for the
sub-scope anywhere on the path.  Two more differences:

* an event that CONTAINS other events of its line is left out: the TPU
  writes a ``conditional`` or a ``while`` as one event spanning its body
  AND the body's operations as events of their own, and summing durations
  counts the body twice (PERF.md section 7);
* times are taken per program, so that a share of a roofline reads the
  decode program's operations alone.

Everything below :func:`load` is arithmetic on plain tuples.  Where the
trace holds no such scope or integer -- the parent of the PR that added
them, or another model -- every function returns ``None``.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks import harness, host_spans, trace_reduce

SUB_SCOPES = ("mla_q", "mla_kv_down", "mla_decode_core", "mla_prefill_core",
              "mla_out", "moe_router", "moe_dispatch", "moe_experts",
              "moe_combine", "moe_shared")
OVERHEAD = ("moe_router", "moe_dispatch", "moe_combine")
NONE = "-"
# XLA's own grouped-matmul kernel: what ``jax.lax.ragged_dot`` becomes on the
# TPU.  The compiler makes the custom call itself and gives it no path
# (``tf_op`` reads ``ragged-dot-none:``), and its neighbours are of two
# scopes, so it is known by its name, as the paged decode kernel is
KERNELS = {"ragged-dot": "moe_experts"}
Event = trace_reduce.Event


def sub_scope_of(path: str) -> str:
    """The first of :data:`SUB_SCOPES` on an operation's path."""
    for part in path.split("/"):
        if part in SUB_SCOPES:
            return part
    return NONE


def kernel_scope(op_name: str) -> Optional[str]:
    """The sub-scope of a kernel that is known by its NAME (:data:`KERNELS`)
    because its metadata holds no path."""
    for word, scope in KERNELS.items():
        if word in trace_reduce.norm(op_name):
            return scope
    return None


def op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation name: sub-scope}}`` from the event
    metadata (``host_spans`` reads the records; the choice of scope is
    this module's)."""
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
            elif kernel_scope(ename) is not None:
                scopes[ename] = kernel_scope(ename)
        out[name] = scopes
    return out


# --- arithmetic on plain tuples -----------------------------------------------

def leaves(ops: Iterable[Event]) -> List[Event]:
    """The events that contain no other event of the line: one whose
    successor (by start) begins before it has ended is a container."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, dur) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < start + dur \
                and nxt[1] + nxt[2] <= start + dur + 1e-12:
            continue
        out.append((name, start, dur))
    return out


def scope_seconds_by_module(rows: Dict[str, List[Event]],
                            scopes: Dict[str, str]
                            ) -> Dict[str, Dict[str, float]]:
    """``{program: {sub-scope: device seconds}}`` over the leaf operations
    of one device plane.  An operation whose metadata names no path (a
    layout copy the compiler put in) counts to the sub-scope of the named
    operations before and after it where the two agree, as
    ``host_spans.scope_seconds`` has it."""
    mods = sorted(rows["modules"], key=lambda e: e[1])
    ops = leaves(rows["ops"])
    named = [scopes.get(name) for name, _, _ in ops]
    after: List[Optional[str]] = [None] * len(ops)
    nxt = None
    for i in range(len(ops) - 1, -1, -1):
        after[i] = nxt
        if named[i] is not None:
            nxt = named[i]
    out: Dict[str, Dict[str, float]] = {}
    prev = None
    m = 0
    for i, (_, start, dur) in enumerate(ops):
        key = named[i]
        if key is None:
            key = prev if prev is not None and prev == after[i] else NONE
        else:
            prev = key
        while m + 1 < len(mods) and mods[m + 1][1] <= start:
            m += 1
        inside = mods and mods[m][1] <= start < mods[m][1] + mods[m][2]
        mod = trace_reduce.norm(mods[m][0]) if inside else NONE
        per = out.setdefault(mod, {})
        per[key] = per.get(key, 0.0) + dur
    return out


def routing_of(phases: Iterable[host_spans.Phase]) -> Optional[Dict]:
    """Sums of the integers the ``engine.fetch`` phases of DECODE launches
    carry (``moe_assignments``, ``moe_experts_touched``, ``moe_max_load``),
    and how many such phases there were; ``None`` when none carries any."""
    out = {"launches": 0, "assignments": 0, "touched": 0, "max_load": 0}
    for name, _, _, stats in phases:
        if name != host_spans.FETCH or "moe_assignments" not in stats \
                or not int(stats.get("moe_decode", 0)):
            continue
        out["launches"] += 1
        out["assignments"] += int(stats["moe_assignments"])
        out["touched"] += int(stats["moe_experts_touched"])
        out["max_load"] += int(stats["moe_max_load"])
    return out if out["launches"] else None


def analyse(planes: Dict, phases: List[host_spans.Phase],
            scopes: Dict[str, Dict[str, str]]) -> Optional[Dict]:
    """Averaged over the chips like ``trace_reduce.reduce``; ``None`` for
    a trace in which no operation sits under a sub-scope."""
    if not planes:
        return None
    n = len(planes)
    by_mod: Dict[str, Dict[str, float]] = {}
    launches: Dict[str, float] = {}
    for name, rows in planes.items():
        for mod, per in scope_seconds_by_module(
                rows, scopes.get(name, {})).items():
            d = by_mod.setdefault(mod, {})
            for k, v in per.items():
                d[k] = d.get(k, 0.0) + v / n
        for mname, _, _ in rows["modules"]:
            k = trace_reduce.norm(mname)
            launches[k] = launches.get(k, 0.0) + 1.0 / n
    if not any(k != NONE for per in by_mod.values() for k in per):
        return None
    return {"scope_s": by_mod, "module_launches": launches,
            "routing": routing_of(phases)}


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

            print("benchmark: moe_mla_spans could not read the trace:\n"
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


def mla_decode_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read the latent cache the traced decode launches had
    to read (and to do its arithmetic), over the device time under
    ``mla_decode_core`` in the decode program.  Bound: memory."""
    from benchmarks import layer_lib, roofline_moe_mla as rf

    t = scope_s(a, "mla_decode_core", layer_lib.DECODE)
    if not t or "traced" not in c or not c.get("peaks"):
        return None
    kv = c["traced"]["probe"]["decode_kv_tokens"]
    itemsize = 2 if c["engine"]["pool_dtype"] == "bfloat16" else 4
    need = rf.roofline_seconds(rf.decode_latent_bytes(c["model"], kv, itemsize),
                               rf.decode_latent_flops(c["model"], kv),
                               c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def moe_experts_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read every expert a traced decode launch touched (and
    to multiply its tokens), over the device time under ``moe_experts`` in
    the decode program.  The ``engine.fetch`` phases give the mean per
    launch, the device trace the launches.  Bound: memory."""
    from benchmarks import layer_lib, roofline_moe_mla as rf

    t = scope_s(a, "moe_experts", layer_lib.DECODE)
    r = (a or {}).get("routing")
    if not t or not r or not c.get("peaks"):
        return None
    n = a["module_launches"].get(layer_lib.DECODE, 0.0) / r["launches"]
    need = rf.roofline_seconds(
        rf.experts_read_bytes(c["model"], r["touched"] * n),
        rf.experts_flops(c["model"], r["assignments"] * n), c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def moe_overhead_share(trace: Optional[Dict], a: Optional[Dict]
                       ) -> Optional[float]:
    """Router, dispatch and combine over the device's busy time."""
    if a is None or not (trace or {}).get("busy_s"):
        return None
    return 100.0 * sum(scope_s(a, s) for s in OVERHEAD) / trace["busy_s"]


def moe_load_max_over_mean(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """The fullest expert's tokens over the mean expert's, over the traced
    decode launches and expert layers (1.0 is perfect balance)."""
    r = (a or {}).get("routing")
    if not r or not r["assignments"]:
        return None
    return r["max_load"] * c["model"]["n_routed_experts"] / r["assignments"]


def main(argv=None) -> int:
    import json

    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(json.dumps(load(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
