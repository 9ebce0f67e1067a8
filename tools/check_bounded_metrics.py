#!/usr/bin/env python
"""Bounded-memory lint for the telemetry layers (ISSUE 2 satellite).

Long-lived serving processes must not let metrics/trace state grow
without bound, so every accumulation container in
``paddle_tpu/observability/`` and ``paddle_tpu/serving/`` has to declare
its bound:

* ``collections.deque(...)`` must pass ``maxlen=``;
* ``queue.Queue(...)`` / ``asyncio.Queue(...)`` (and the Lifo/Priority
  variants of either) must pass ``maxsize=`` (positional or keyword) —
  the HTTP frontend's cross-thread submit/abort queues are the reason
  this rule exists;
* ``SimpleQueue`` has no bound at all, so any use needs a waiver;
* ``OrderedDict`` / ``defaultdict`` — the LRU/map shapes the prefix
  cache introduced (ISSUE 4) — have no bound parameter either, so every
  construction needs a waiver stating the structural bound (e.g. "≤
  num_blocks entries": the block pool caps them);
* a bare-list "reservoir" (``self.x = []`` later ``.append``ed from a
  per-step/per-op path) is caught by the deque rule in practice — the
  repo's convention is that windows/rings are deques.

Besides the telemetry packages, ``SCAN_FILES`` pins individual modules
that host long-lived caches — ``ops/paged_attention.py`` carries the
serving block pool's prefix-hash map and reuse LRU.

A genuinely-unbounded container that holds WORK (not telemetry) is
allowed with an inline waiver comment stating why::

    self.waiting = deque()  # unbounded-ok: live work queue, drained

Run standalone (exits 1 on violations) or from the test suite
(``tests/test_observability.py`` asserts ``scan()`` returns nothing).
"""

from __future__ import annotations

import ast
import os
import sys
from typing import List, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_DIRS = (
    os.path.join(_REPO, "paddle_tpu", "observability"),
    os.path.join(_REPO, "paddle_tpu", "serving"),
)
# single modules outside the telemetry dirs that host long-lived caches
# or sit on the serving hot path (ISSUE 5 widened the net to the
# tensor-parallel plumbing the multi-chip engine runs through)
SCAN_FILES = (
    # serving/ is already walked via SCAN_DIRS; the fleet module is ALSO
    # pinned here (ISSUE 6) so the per-replica submit/abort queues and
    # request→replica maps stay covered even if the module moves out of
    # the package dir — the coverage lint test asserts this entry
    os.path.join(_REPO, "paddle_tpu", "serving", "fleet.py"),
    # likewise pinned (ISSUE 8): the request-timeline rings, flight-
    # recorder rings/windows, and push-gateway loop must stay bounded
    # even if they move out of the observability dir
    os.path.join(_REPO, "paddle_tpu", "observability", "lifecycle.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "flight.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "push.py"),
    # ISSUE 9: the step profiler's record ring, compile table and
    # capture windows must stay bounded (deque maxlen= / explicit caps)
    os.path.join(_REPO, "paddle_tpu", "observability", "stepprof.py"),
    # ISSUE 10: the numerics auditor's repro-path ring and divergence
    # bookkeeping must stay bounded (deque maxlen= / fired-once keys)
    os.path.join(_REPO, "paddle_tpu", "observability", "audit.py"),
    # ISSUE 13: the cache-stat tracker's pool-timeline ring, decayed
    # prefix-heat table and attribution maps must stay bounded
    os.path.join(_REPO, "paddle_tpu", "observability", "cachestat.py"),
    # ISSUE 14: the metrics-history rings are THE memory bound of the
    # alerting layer (hard max_series x ring_len), and the alert
    # engine's per-rule transition rings must stay bounded too
    os.path.join(_REPO, "paddle_tpu", "observability", "history.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "alerts.py"),
    # ISSUE 12: the supervisor's restart-history deques / pending
    # re-dispatch queue and the fault injector's fired-once sets must
    # stay bounded even if the modules move out of the serving dir
    os.path.join(_REPO, "paddle_tpu", "serving", "resilience.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "faultinject.py"),
    # ISSUE 15: the AOT artifact's program map is bounded by the saved
    # manifest (enumerate_buckets is a finite lattice); pinned so the
    # loaded-Exported cache stays covered if the module moves
    os.path.join(_REPO, "paddle_tpu", "serving", "aot.py"),
    # ISSUE 20: the KV hand-off path assembles whole runs in memory —
    # its chunk buffers are bounded by the declared chunk cap and the
    # donor pool size; pinned so that stays covered if the module moves
    os.path.join(_REPO, "paddle_tpu", "serving", "handoff.py"),
    os.path.join(_REPO, "paddle_tpu", "ops", "paged_attention.py"),
    os.path.join(_REPO, "paddle_tpu", "ops", "pallas_paged.py"),
    # ISSUE 11: the unified ragged kernel sits on the serving hot path
    # (its module-level last_path is the only state — keep it that way)
    os.path.join(_REPO, "paddle_tpu", "ops", "ragged_paged.py"),
    # ISSUE 19: the decode-burst device loop sits on the serving hot
    # path (stateless by design — keep it that way; the host half's
    # burst-bucket set is bounded by the AOT lattice)
    os.path.join(_REPO, "paddle_tpu", "ops", "decode_burst.py"),
    os.path.join(_REPO, "paddle_tpu", "parallel", "mp_layers.py"),
    os.path.join(_REPO, "paddle_tpu", "parallel", "utils.py"),
    os.path.join(_REPO, "paddle_tpu", "distributed", "topology.py"),
    # ISSUE 16: the cross-process fleet's wire connections, worker-side
    # live-request mirror, proxy request mirrors / worker log tails and
    # the autoscaler's action queue + replay rings must stay bounded
    # even if the modules move out of the serving dir
    os.path.join(_REPO, "paddle_tpu", "serving", "wire.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "worker.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "procfleet.py"),
    # ISSUE 17: the distributed-tracing layer is ALL rings and windows —
    # worker telemetry outboxes, host-side mirror rings, clock-sync
    # sample windows, seq-interval merge state and per-program wire
    # aggregates must every one stay bounded
    os.path.join(_REPO, "paddle_tpu", "observability", "distrib.py"),
    # ISSUE 18: the spec-decode proposer must stay stateless (any
    # per-request draft history would desynchronize on recompute) and
    # the sampling helpers must not grow per-request key caches
    os.path.join(_REPO, "paddle_tpu", "serving", "spec.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "sampling.py"),
)
WAIVER = "unbounded-ok:"

# call-name suffix -> required bound keyword; matches attribute calls
# too, so queue.Queue and asyncio.Queue hit the same rule
_RULES = {
    "deque": ("maxlen", 1),          # deque(iterable, maxlen) — kw or 2nd pos
    "Queue": ("maxsize", 0),         # Queue(maxsize) — kw or 1st pos
    "LifoQueue": ("maxsize", 0),
    "PriorityQueue": ("maxsize", 0),
}

# constructors with NO bound parameter: always a violation without a
# waiver (the waiver must state the structural bound — e.g. the prefix
# cache's hash map / reuse LRU are capped by the pool's block count)
_UNBOUNDABLE = ("SimpleQueue", "OrderedDict", "defaultdict")


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _bounded(node: ast.Call, kw: str, pos: int) -> bool:
    if any(k.arg == kw for k in node.keywords):
        return True
    return len(node.args) > pos


def check_file(path: str) -> List[Tuple[str, int, str]]:
    with open(path) as f:
        source = f.read()
    lines = source.splitlines()
    out = []
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [(path, e.lineno or 0, f"syntax error: {e.msg}")]
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        line_text = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if name in _UNBOUNDABLE:
            if WAIVER not in line_text:
                out.append((path, node.lineno,
                            f"{name}() cannot be bounded — use "
                            f"Queue(maxsize=...) or add a "
                            f"'# {WAIVER} <reason>' waiver"))
            continue
        rule = _RULES.get(name)
        if rule is None:
            continue
        kw, pos = rule
        if _bounded(node, kw, pos):
            continue
        if WAIVER in line_text:
            continue
        out.append((path, node.lineno,
                    f"{name}() without {kw}= — unbounded accumulation in a "
                    f"long-lived process (add {kw}= or a "
                    f"'# {WAIVER} <reason>' waiver)"))
    return out


def scan(dirs=SCAN_DIRS, files=SCAN_FILES) -> List[Tuple[str, int, str]]:
    out = []
    for d in dirs:
        for root, _, fns in os.walk(d):
            for fn in sorted(fns):
                if fn.endswith(".py"):
                    out.extend(check_file(os.path.join(root, fn)))
    for path in files:
        out.extend(check_file(path))
    return out


def main() -> int:
    violations = scan()
    for path, lineno, msg in violations:
        rel = os.path.relpath(path, _REPO)
        print(f"{rel}:{lineno}: {msg}")
    if violations:
        print(f"{len(violations)} unbounded-accumulation violation(s)")
        return 1
    print("bounded-metrics lint: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
