"""What the per-layer metric readers share.  No JAX.

A reader is ``read(counters, trace) -> number or None``.  ``counters``::

    {"client":  what the load generator saw (lateness, TTFT percentiles),
     "window":  the program's counters between window open and close,
     "traced":  the same between the profiler's start and stop,
     "model":   the configuration's published sizes, "engine": its settings,
     "device":  as JAX reports it, "peaks": its row of roofline.PEAKS}

``window`` / ``traced`` hold ``probe`` (launches, rows, cache tokens,
prompt tokens of the step programs), ``programs`` (``StepProfiler``'s
scheduled against padded tokens per program and bucket), ``compiles``,
``queue_wait_s`` / ``queue_wait_n``, ``preemptions``, ``pool_peak_share``.
``trace`` is ``trace_reduce.reduce``'s result, or ``None`` in a run that
was not traced.  A reader that finds nothing to read returns ``None`` and
the harness leaves the metric out of the line.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import roofline

DECODE = "jit__decode_fn"
PREFILL = "jit__prefill_fn"


def ratio(a, b, scale: float = 1.0) -> Optional[float]:
    return scale * a / b if b else None


def rows_per_step(c: Dict) -> Optional[float]:
    p = c["window"]["probe"]
    return ratio(p["decode_rows"], p["decode_launches"])


def padding_share(c: Dict) -> Optional[float]:
    sched = sum(v[1] for v in c["window"]["programs"].values())
    cap = sum(v[2] for v in c["window"]["programs"].values())
    return ratio(cap - sched, cap, 100.0)


def host_ms_per_step(trace: Optional[Dict]) -> Optional[float]:
    """Idle gap between consecutive step programs, per launch."""
    if not trace:
        return None
    return ratio(trace["gap_s"], trace["launches"], 1e3)


def module_ms(trace: Optional[Dict], module: str) -> Optional[float]:
    m = (trace or {}).get("modules", {}).get(module)
    return ratio(m["seconds"], m["count"], 1e3) if m else None


def idle_share(trace: Optional[Dict]) -> Optional[float]:
    return 100.0 * trace["idle_share"] if trace else None


def op_share(trace: Optional[Dict], prefix: str) -> Optional[float]:
    """Share of the device's busy time in operations named ``prefix*``."""
    if not trace:
        return None
    t = sum(v for k, v in trace["ops"].items() if k.startswith(prefix))
    return ratio(t, trace["busy_s"], 100.0)


def prefill_flops_share(c: Dict, trace: Optional[Dict]) -> Optional[float]:
    """Model FLOPs of the prompt tokens prefilled while the profiler ran,
    over the prefill programs' device time at the chip's peak."""
    m = (trace or {}).get("modules", {}).get(PREFILL)
    if not m or "traced" not in c:
        return None
    p = c["traced"]["probe"]
    if not p["prefill_launches"]:
        return None
    flops = roofline.prefill_flops_sums(
        c["model"], p["prefill_launches"], p["prefill_tokens"],
        p["prefill_tokens_sq"])
    return ratio(flops, m["seconds"] * c["peaks"]["flops_per_s"], 100.0)


def paged_decode_roofline(c: Dict, trace: Optional[Dict]) -> Optional[float]:
    """Least time the chip needs to read the cache the decode steps had to
    read, over the device time of the decode program's attention kernel."""
    ops = (trace or {}).get("ops_by_module", {}).get(DECODE)
    if not ops or "traced" not in c:
        return None
    kernel_s = sum(v for k, v in ops.items() if k.startswith("custom-call"))
    itemsize = 2 if c["engine"]["pool_dtype"] == "bfloat16" else 4
    need = roofline.decode_kv_bytes(
        c["model"], c["traced"]["probe"]["decode_kv_tokens"], itemsize)
    return ratio(need / c["peaks"]["bytes_per_s"], kernel_s, 100.0)
