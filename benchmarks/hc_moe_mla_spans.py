"""Device time under the scopes of the multi-stream residual path (the
outer ``mhc`` and ``mhc_coeffs``, ``mhc_sinkhorn``, ``mhc_pre``,
``mhc_post`` inside it: ``paddle_tpu/models/hc_moe_mla.py``,
``ops/hyper_connections.py``), under ``mla_prefill_core`` and the ``moe_*``
scopes of a PREFILL launch, and the integers ``engine.fetch`` carries for
them (``hc_res_clamped``, ``hc_entries``, ``hc_sinkhorn_residual_ppb``; the
routing load of the launches that are NOT decode), for the per-layer
metrics of the cell ``xing4.0-29b-a4b.doc-prefill``.

``mhc`` is an outer scope that ``host_spans.SCOPES`` does not know (its
time is "unscoped" there, so ``programs.attn_share`` and
``programs.mlp_share`` keep reading the sublayers alone).  This reader
takes the INNERMOST of its names on an operation's path (``mhc_sinkhorn``
under ``mhc``; ``mhc`` itself for the entry and the exit of the streams),
takes times per program, and leaves out an event that contains other
events of its line, with ``moe_mla_spans.py``'s arithmetic (a prompt's
query blocks are a ``while``, which the TPU writes as one event spanning
the body AND the body's operations).  The accepted ``moe_mla_spans.py``
reads DECODE launches; the rooflines here read the prefill program, which
no accepted reader of this family does.

It repeats ``window_moe_spans.py``'s ``op_paths`` with another choice of
scope (the choice is a module's own there too: PERF.md notes that a later
``benchmark`` PR can fold the ``*_spans.py`` files).

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

MHC = ("mhc", "mhc_coeffs", "mhc_sinkhorn", "mhc_pre", "mhc_post")
SCOPES = MHC + ("mla_prefill_core", "moe_router", "moe_dispatch",
                "moe_experts", "moe_combine")
OVERHEAD = ("moe_router", "moe_dispatch", "moe_combine")
NONE = moe_mla_spans.NONE


def scope_of(path: str) -> str:
    """The innermost of :data:`SCOPES` on an operation's path."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return NONE


def op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation name: scope}}`` from the event metadata
    (``host_spans`` reads the records).  XLA's grouped matmul has no path
    and is known by its name (``moe_mla_spans.KERNELS``)."""
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
            elif moe_mla_spans.kernel_scope(ename) is not None:
                scopes[ename] = moe_mla_spans.kernel_scope(ename)
        out[name] = scopes
    return out


def ints_of(phases: Iterable[host_spans.Phase]) -> Optional[Dict]:
    """Sums over the traced ``engine.fetch`` phases that carry this
    model's integers: the health of the Sinkhorn steps over every launch
    (``clamped`` of ``entries``; the largest residual, parts per billion),
    and the routing load of the launches that are not decode
    (``prefill_fetches``, ``assignments``, ``touched``).  ``None`` when no
    phase carries ``hc_entries``."""
    out = {"fetches": 0, "clamped": 0, "entries": 0, "residual_ppb": 0,
           "prefill_fetches": 0, "assignments": 0, "touched": 0}
    for name, _, _, stats in phases:
        if name != host_spans.FETCH or "hc_entries" not in stats:
            continue
        out["fetches"] += 1
        out["clamped"] += int(stats["hc_res_clamped"])
        out["entries"] += int(stats["hc_entries"])
        out["residual_ppb"] = max(out["residual_ppb"],
                                  int(stats.get("hc_sinkhorn_residual_ppb", 0)))
        if "moe_assignments" in stats and not int(stats.get("moe_decode", 0)):
            out["prefill_fetches"] += 1
            out["assignments"] += int(stats["moe_assignments"])
            out["touched"] += int(stats["moe_experts_touched"])
    return out if out["fetches"] else None


def analyse(planes: Dict, phases: List[host_spans.Phase],
            scopes: Dict[str, Dict[str, str]]) -> Optional[Dict]:
    """Averaged over the chips like ``trace_reduce.reduce``; ``None`` for
    a trace in which no operation sits under ``mhc`` and no phase carries
    the health integers."""
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
    mine = any(k in MHC for per in by_mod.values() for k in per)
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

            print("benchmark: hc_moe_mla_spans could not read the trace:\n"
                  + traceback.format_exc(), file=sys.stderr)
            _CACHE[key] = None
    return _CACHE[key]


def scope_s(a: Optional[Dict], scopes, module: Optional[str] = None
            ) -> Optional[float]:
    """Device seconds under any of ``scopes``, in ``module`` or in every
    program."""
    if a is None:
        return None
    mods = [module] if module else list(a["scope_s"])
    return sum(a["scope_s"].get(m, {}).get(s, 0.0)
               for m in mods for s in scopes)


def _model(c: Dict) -> Optional[Dict]:
    m = c.get("model") or {}
    return m if "hc_mult" in m and "kv_lora_rank" in m else None


def _traced_prefills(c: Dict, a: Dict) -> Optional[Tuple[float, float]]:
    """``(tokens, squared tokens)`` of the prompts of ALL prefill programs
    in the device trace: the probe gives their means a launch (between the
    profiler's start and stop), the trace the launches."""
    from benchmarks import layer_lib

    p = (c.get("traced") or {}).get("probe") or {}
    if not p.get("prefill_launches"):
        return None
    k = a["module_launches"].get(layer_lib.PREFILL, 0.0) \
        / p["prefill_launches"]
    return p["prefill_tokens"] * k, p["prefill_tokens_sq"] * k


def mhc_share(trace: Optional[Dict], a: Optional[Dict]) -> Optional[float]:
    """Everything under ``mhc`` over the device's busy time."""
    if a is None or not (trace or {}).get("busy_s"):
        return None
    t = scope_s(a, MHC)
    return 100.0 * t / trace["busy_s"] if t else None


def mhc_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to move the streams of the traced prompts' tokens
    through every sublayer's hyper-connection (100,352 B a token a
    sublayer), over the device time under ``mhc`` in the prefill program.
    Bound: memory."""
    from benchmarks import layer_lib, roofline_hc_moe_mla as rf

    t = scope_s(a, MHC, layer_lib.PREFILL)
    m = _model(c)
    if not t or m is None or not c.get("peaks"):
        return None
    tok = _traced_prefills(c, a)
    if tok is None:
        return None
    need = rf.hc_bytes(m, tok[0]) / c["peaks"]["bytes_per_s"]
    return layer_lib.ratio(need, t, 100.0)


def mla_prefill_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time for the keys and values rebuilt from the latents and the
    causal scores and weighted sums of the traced prompts, over the device
    time under ``mla_prefill_core`` in the prefill program.  Bound:
    compute."""
    from benchmarks import layer_lib, roofline_hc_moe_mla as rf

    t = scope_s(a, ("mla_prefill_core",), layer_lib.PREFILL)
    m = _model(c)
    if not t or m is None or not c.get("peaks"):
        return None
    tok = _traced_prefills(c, a)
    if tok is None:
        return None
    need = rf.prefill_attention_flops(m, *tok) / c["peaks"]["flops_per_s"]
    return layer_lib.ratio(need, t, 100.0)


def moe_prefill_experts_roofline(c: Dict, a: Optional[Dict]
                                 ) -> Optional[float]:
    """Least time to multiply the routed pairs of the traced prefill
    launches (and to read every expert they touched), over the device time
    under ``moe_experts`` in the prefill program.  Bound: compute at
    thousands of tokens a launch."""
    from benchmarks import layer_lib, roofline_hc_moe_mla as rf

    t = scope_s(a, ("moe_experts",), layer_lib.PREFILL)
    m = _model(c)
    i = (a or {}).get("ints")
    if not t or m is None or not i or not i["prefill_fetches"] \
            or not c.get("peaks"):
        return None
    k = a["module_launches"].get(layer_lib.PREFILL, 0.0) \
        / i["prefill_fetches"]
    need = rf.roofline_seconds(rf.experts_read_bytes(m, i["touched"] * k),
                               rf.experts_flops(m, i["assignments"] * k),
                               c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def moe_prefill_overhead_share(trace: Optional[Dict], a: Optional[Dict]
                               ) -> Optional[float]:
    """Router, dispatch and combine over the device's busy time."""
    if a is None or not (trace or {}).get("busy_s"):
        return None
    if not any(k in SCOPES for per in a["scope_s"].values() for k in per):
        return None
    return 100.0 * scope_s(a, OVERHEAD) / trace["busy_s"]


def hc_clamped_share(a: Optional[Dict]) -> Optional[float]:
    """Entries of the pre-``exp`` matrices that met the clamp, per
    thousand computed, over the traced launches."""
    i = (a or {}).get("ints")
    if not i or not i["entries"]:
        return None
    return 1000.0 * i["clamped"] / i["entries"]


def main(argv=None) -> int:
    import json

    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(json.dumps(load(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
