"""Device time under the ``ssm`` scope and the sub-scopes in it
(``ssm_in_proj`` ... ``ssm_out``: ``paddle_tpu/models/mamba_hybrid.py``) and
the slot integers ``engine.build`` carries, for the per-layer metrics of
the selective-scan / attention hybrid cell.

``host_spans.SCOPES`` is fixed, so the mixer is unscoped to the accepted
readers and ``programs.attn_share`` keeps reading attention alone.  This
reader looks for a sub-scope anywhere on an operation's path (and counts
what is under ``ssm`` and in none of them, the block's norm, to ``ssm``),
takes times per program, and LEAVES OUT an event that contains other
events of its line, as ``moe_mla_spans.py`` does and with its arithmetic:
a scan is a ``while``, which the TPU writes as one event spanning its body
AND the body's operations as events of their own.

Where the trace holds no such scope or integer -- the parent of the PR
that added them, or another model -- every function returns ``None``.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks import harness, host_spans, moe_mla_spans, trace_reduce

OUTER = "ssm"
SUB_SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_x_proj", "ssm_scan",
              "ssm_step", "ssm_out")
BUILD = "engine.build"
NONE = moe_mla_spans.NONE


def sub_scope_of(path: str) -> str:
    """The first of :data:`SUB_SCOPES` on an operation's path, else
    :data:`OUTER` where the path is under it."""
    parts = path.split("/")
    for part in parts:
        if part in SUB_SCOPES:
            return part
    return OUTER if OUTER in parts else NONE


def op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation name: scope}}`` from the event
    metadata (``host_spans`` reads the records)."""
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
        out[name] = scopes
    return out


def slots_of(phases: Iterable[host_spans.Phase]) -> Optional[Dict]:
    """Over the ``engine.build`` phases that carry the slot integers: how
    many, the largest ``state_slots_held`` and the sum of ``state_rows``;
    ``None`` when none carries any."""
    out = {"launches": 0, "held_max": 0, "rows": 0}
    for name, _, _, stats in phases:
        if name != BUILD or "state_slots_held" not in stats:
            continue
        out["launches"] += 1
        out["held_max"] = max(out["held_max"], int(stats["state_slots_held"]))
        out["rows"] += int(stats.get("state_rows", 0))
    return out if out["launches"] else None


def analyse(planes: Dict, phases: List[host_spans.Phase],
            scopes: Dict[str, Dict[str, str]]) -> Optional[Dict]:
    """Averaged over the chips like ``trace_reduce.reduce``; ``None`` for
    a trace in which no operation sits under ``ssm`` and no phase carries
    a slot integer."""
    if not planes:
        return None
    n = len(planes)
    by_mod: Dict[str, Dict[str, float]] = {}
    for name, rows in planes.items():
        for mod, per in moe_mla_spans.scope_seconds_by_module(
                rows, scopes.get(name, {})).items():
            d = by_mod.setdefault(mod, {})
            for k, v in per.items():
                d[k] = d.get(k, 0.0) + v / n
    slots = slots_of(phases)
    if slots is None and not any(k != NONE for per in by_mod.values()
                                 for k in per):
        return None
    return {"scope_s": by_mod, "slots": slots}


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

            print("benchmark: ssm_spans could not read the trace:\n"
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


def _mixer_model(c: Dict) -> Optional[Dict]:
    m = c.get("model") or {}
    return m if "mamba_d_state" in m else None


def _itemsize(c: Dict) -> int:
    return 2 if c["engine"]["pool_dtype"] == "bfloat16" else 4


def ssm_decode_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read and write once the state of every real row the
    traced decode launches advanced, over the device time under
    ``ssm_step`` in the decode program.  Bound: memory."""
    from benchmarks import layer_lib, roofline_ssm as rf

    t = scope_s(a, "ssm_step", layer_lib.DECODE)
    m = _mixer_model(c)
    if not t or m is None or "traced" not in c or not c.get("peaks"):
        return None
    rows = c["traced"]["probe"]["decode_rows"]
    need = rf.roofline_seconds(
        rf.decode_state_bytes(m, rows, _itemsize(c)), c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def ssm_scan_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read x and dt and write y for the prompt tokens the
    traced prefills scanned, and one state a prompt, over the device time
    under ``ssm_scan`` in the prefill program.  Bound: memory by this
    count (elementwise arithmetic; the matmul peak does not bound it)."""
    from benchmarks import layer_lib, roofline_ssm as rf

    t = scope_s(a, "ssm_scan", layer_lib.PREFILL)
    m = _mixer_model(c)
    if not t or m is None or "traced" not in c or not c.get("peaks"):
        return None
    p = c["traced"]["probe"]
    need = rf.roofline_seconds(
        rf.scan_bytes(m, p["prefill_launches"], p["prefill_tokens"],
                      _itemsize(c)), c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def ssm_share(trace: Optional[Dict], a: Optional[Dict]) -> Optional[float]:
    """Everything under ``ssm`` over the device's busy time."""
    if a is None or not (trace or {}).get("busy_s"):
        return None
    t = sum(scope_s(a, s) for s in SUB_SCOPES + (OUTER,))
    return 100.0 * t / trace["busy_s"] if t else None


def state_slots_peak_share(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """The most slots held at any traced launch over the slots there are
    (``max_num_seqs``)."""
    s = (a or {}).get("slots")
    cap = (c.get("engine") or {}).get("max_num_seqs")
    if not s or not cap:
        return None
    return 100.0 * s["held_max"] / cap


def main(argv=None) -> int:
    import json

    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(json.dumps(load(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
