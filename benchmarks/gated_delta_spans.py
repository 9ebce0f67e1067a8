"""Device time under the ``gdn`` scope and the sub-scopes in it
(``gdn_in_proj`` ... ``gdn_out``: ``paddle_tpu/models/gated_delta_moe_mla.py``),
under ``mla_decode_core`` and ``moe_experts`` of the same model's latent
layer and held experts, and the integers the engine's phases carry for it
(``state_rows`` / ``state_slots_held`` on ``engine.build``,
``moe_pairs_held`` / ``moe_held_touched`` on ``engine.fetch``), for the
per-layer metrics of the cell
``gigachat3.5-432b-a28b.delta-reasoning-decode``.

``host_spans.SCOPES`` is fixed, so the delta-rule mixer is unscoped to the
accepted readers and ``programs.attn_share`` keeps reading the latent
layer alone.  This reader looks for a sub-scope anywhere on an operation's
path (what is under ``gdn`` and in none of them, the block's two norms,
counts to ``gdn``), takes times per program, and LEAVES OUT an event that
contains other events of its line, with ``moe_mla_spans.py``'s arithmetic:
the chunked rule's carry is a ``while``, which the TPU writes as one event
spanning its body AND the body's operations as events of their own.

Where the trace holds no such scope or integer -- the parent of the PR
that added them, or another model -- every function returns ``None``.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks import (harness, host_spans, moe_mla_spans, ssm_spans,
                        trace_reduce)

OUTER = "gdn"
SUB_SCOPES = ("gdn_in_proj", "gdn_conv", "gdn_gates", "gdn_chunk",
              "gdn_step", "gdn_out")
# read in this cell beside the mixer: the latent walk, its gate, the experts
OTHERS = ("mla_decode_core", "mla_gate", "moe_experts")
BUILD = "engine.build"
NONE = moe_mla_spans.NONE


def sub_scope_of(path: str) -> str:
    """The first of :data:`SUB_SCOPES` or :data:`OTHERS` on an operation's
    path, else :data:`OUTER` where the path is under it."""
    parts = path.split("/")
    for part in parts:
        if part in SUB_SCOPES or part in OTHERS:
            return part
    return OUTER if OUTER in parts else NONE


def op_paths(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation name: scope}}`` from the event
    metadata (``host_spans`` reads the records); the grouped matmul is
    known by its name, as ``moe_mla_spans.kernel_scope`` knows it."""
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
    """What the DECODE launches' phases carry for this model.  From
    ``engine.build`` (a decode launch's carries ``rows``): how many and the
    sum of ``state_rows``.  From ``engine.fetch``: how many and the sums of
    ``moe_pairs_held`` and ``moe_held_touched``.  ``None`` when no phase
    carries any.  (The slots held are ``ssm_spans.slots_of``'s.)"""
    out = {"decode_builds": 0, "state_rows": 0,
           "fetches": 0, "pairs_held": 0, "held_touched": 0}
    for name, _, _, stats in phases:
        if name == BUILD and "state_slots_held" in stats and "rows" in stats:
            out["decode_builds"] += 1
            out["state_rows"] += int(stats.get("state_rows", 0))
        elif name == host_spans.FETCH and "moe_pairs_held" in stats \
                and int(stats.get("moe_decode", 0)):
            out["fetches"] += 1
            out["pairs_held"] += int(stats["moe_pairs_held"])
            out["held_touched"] += int(stats["moe_held_touched"])
    return out if out["decode_builds"] or out["fetches"] else None


def analyse(planes: Dict, phases: List[host_spans.Phase],
            scopes: Dict[str, Dict[str, str]]) -> Optional[Dict]:
    """Averaged over the chips like ``trace_reduce.reduce``; ``None`` for
    a trace in which no operation sits under ``gdn``."""
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
    if not any(k in SUB_SCOPES or k == OUTER
               for per in by_mod.values() for k in per):
        return None
    return {"scope_s": by_mod, "module_launches": launches,
            "ints": ints_of(phases), "slots": ssm_spans.slots_of(phases)}


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

            print("benchmark: gated_delta_spans could not read the trace:\n"
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
    return m if "linear_num_value_heads" in m else None


def _itemsize(c: Dict) -> int:
    return 2 if c["engine"]["pool_dtype"] == "bfloat16" else 4


def _over_traced_decodes(a: Dict, key: str, per: str) -> float:
    """The sum ``key`` the host phases carried, as the mean a phase
    (``per`` counts them) times the decode launches of the DEVICE trace:
    the two do not cover exactly the same launches at the trace's edges."""
    from benchmarks import layer_lib

    ints = a.get("ints")
    if not ints or not ints[per]:
        return 0.0
    return (ints[key] / ints[per]
            * a["module_launches"].get(layer_lib.DECODE, 0.0))


def gdn_decode_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read and write once the state of every real row the
    traced decode launches advanced, over the device time under
    ``gdn_step`` in the decode program.  Bound: memory."""
    from benchmarks import layer_lib, roofline_gated_delta as rf

    t = scope_s(a, "gdn_step", layer_lib.DECODE)
    m = _model(c)
    if not t or m is None or not c.get("peaks"):
        return None
    rows = _over_traced_decodes(a, "state_rows", "decode_builds")
    if not rows:
        return None
    need = rf.roofline_seconds(
        rf.decode_state_bytes(m, rows, _itemsize(c)), 0.0, c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def gdn_chunk_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time for the chunked rule's operations over the real tokens
    of the prompts the traced prefills took, over the device time under
    ``gdn_chunk`` in the prefill program; ``None`` where the traced slice
    holds no prefill.  Bound: compute."""
    from benchmarks import layer_lib, roofline_gated_delta as rf

    t = scope_s(a, "gdn_chunk", layer_lib.PREFILL)
    m = _model(c)
    if not t or m is None or "traced" not in c or not c.get("peaks"):
        return None
    tokens = c["traced"]["probe"]["prefill_tokens"]
    if not tokens:
        return None
    need = rf.roofline_seconds(0.0, rf.chunk_flops(m, tokens), c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def gdn_share(trace: Optional[Dict], a: Optional[Dict]) -> Optional[float]:
    """Everything under ``gdn`` over the device's busy time."""
    if a is None or not (trace or {}).get("busy_s"):
        return None
    t = sum(scope_s(a, s) for s in SUB_SCOPES + (OUTER,))
    return 100.0 * t / trace["busy_s"] if t else None


def cell_experts_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read every held expert a traced decode launch's
    pairs reached (and to multiply them), over the device time under
    ``moe_experts`` in the decode program.  Bound: memory."""
    from benchmarks import layer_lib, roofline_gated_delta as rf

    t = scope_s(a, "moe_experts", layer_lib.DECODE)
    m = _model(c)
    if not t or m is None or not c.get("peaks"):
        return None
    touched = _over_traced_decodes(a, "held_touched", "fetches")
    if not touched:
        return None
    need = rf.roofline_seconds(
        rf.held_experts_bytes(m, touched),
        rf.held_experts_flops(m, _over_traced_decodes(a, "pairs_held",
                                                      "fetches")),
        c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def cell_mla_decode_roofline(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """Least time to read the latent rows the traced decode launches' rows
    hold (1,152 B a token, one latent layer), over the device time under
    ``mla_decode_core`` in the decode program.  Bound: memory."""
    from benchmarks import layer_lib, roofline_gated_delta as rf

    t = scope_s(a, "mla_decode_core", layer_lib.DECODE)
    m = _model(c)
    if not t or m is None or "traced" not in c or not c.get("peaks"):
        return None
    kv = c["traced"]["probe"]["decode_kv_tokens"]
    need = rf.roofline_seconds(rf.decode_latent_bytes(m, kv, _itemsize(c)),
                               rf.decode_latent_flops(m, kv), c["peaks"])
    return layer_lib.ratio(need, t, 100.0)


def slots_peak_share(c: Dict, a: Optional[Dict]) -> Optional[float]:
    """``ssm_spans.state_slots_peak_share`` (the most slots held at any
    traced launch over ``max_num_seqs``), for this model's trace alone."""
    if _model(c) is None:
        return None
    return ssm_spans.state_slots_peak_share(c, a)


def main(argv=None) -> int:
    import json

    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(json.dumps(load(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
