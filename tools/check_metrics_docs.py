#!/usr/bin/env python
"""Metrics-documentation lint (ISSUE 8 tooling satellite).

Every pre-registered ``serving_*`` / ``push_*`` metric must be
documented in README's metrics table: an operator paging through a 3 am
``/metrics`` scrape should never meet an undocumented series.  Each
module that pre-registers metrics declares them in a module-level
``METRIC_NAMES`` tuple; this lint collects those declarations **by AST**
(no imports — the serving modules pull in jax) and checks each name
appears somewhere in README.md.

``METRIC_NAMES`` may be a literal tuple or the ``tuple([...] + [...])``
comprehension form ``serving/metrics.py`` uses (derived from its
``_COUNTER_NAMES``/``_GAUGE_NAMES``/``_HISTOGRAM_NAMES`` vocabulary) —
both are resolved statically.

Run standalone (exits 1 on violations) or from the test suite
(``tests/test_lifecycle_flight.py`` asserts ``scan()`` returns nothing).
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, List, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(_REPO, "README.md")

# every module that pre-registers serving_*/push_* series declares a
# METRIC_NAMES tuple; a module listed here WITHOUT one is a violation
DECLARING_MODULES = (
    os.path.join(_REPO, "paddle_tpu", "serving", "metrics.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "fleet.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "server.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "resilience.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "faultinject.py"),
    # ISSUE 15: serving/aot.py owns the serving_aot_* names (the
    # StepProfiler registers them once an artifact is bound)
    os.path.join(_REPO, "paddle_tpu", "serving", "aot.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "lifecycle.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "flight.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "push.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "stepprof.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "audit.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "cachestat.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "history.py"),
    os.path.join(_REPO, "paddle_tpu", "observability", "alerts.py"),
    # ISSUE 16: the cross-process fleet's wire/worker/actuator series
    os.path.join(_REPO, "paddle_tpu", "serving", "wire.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "worker.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "procfleet.py"),
    # ISSUE 17: cross-process tracing — wire-latency histograms plus
    # the telemetry-stream / clock-sync series
    os.path.join(_REPO, "paddle_tpu", "observability", "distrib.py"),
    # ISSUE 18: speculative decoding (draft/accept counters, accept
    # ratio/length) and the in-trace sampling path counters
    os.path.join(_REPO, "paddle_tpu", "serving", "spec.py"),
    os.path.join(_REPO, "paddle_tpu", "serving", "sampling.py"),
    # ISSUE 19: decode-burst launch/token/length series plus the
    # host-round-trip counter every step-program launch increments
    os.path.join(_REPO, "paddle_tpu", "serving", "burst.py"),
    # ISSUE 20: prefill/decode disaggregation — the KV hand-off
    # counter/histograms the router registers for every fleet
    os.path.join(_REPO, "paddle_tpu", "serving", "handoff.py"),
    # ISSUE 39: the pause monitor's collector histogram and the three
    # serving_pause* series
    os.path.join(_REPO, "paddle_tpu", "observability", "pauses.py"),
)

_NAME_RE = re.compile(r"\b(?:serving|push)_[a-z0-9_:]+\b")


def _strings_in(node: ast.AST) -> List[str]:
    """Every string constant anywhere under ``node`` — resolves both the
    literal-tuple and the list-comprehension METRIC_NAMES forms without
    executing module code (f-string templates contribute their constant
    parts, which is exactly the prefix/suffix the regex filter needs)."""
    out = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            out.append(sub.value)
    return out


def declared_metrics(path: str) -> List[str]:
    """The module's ``METRIC_NAMES``, statically resolved.  For the
    derived form, vocabulary lists are expanded through the f-string
    templates found in the tuple expression."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    assign = None
    vocab: Dict[str, List[str]] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            name = node.targets[0].id
            if name == "METRIC_NAMES":
                assign = node.value
            else:
                try:
                    v = ast.literal_eval(node.value)
                except (ValueError, SyntaxError):
                    continue
                if isinstance(v, (list, tuple)) and \
                        all(isinstance(x, str) for x in v):
                    vocab[name] = list(v)
    if assign is None:
        return []
    try:  # literal tuple: the common case
        v = ast.literal_eval(assign)
        return [str(x) for x in v]
    except (ValueError, SyntaxError):
        pass
    # derived form: expand each `f"<pre>{n}<post>" for n in VOCAB` piece
    names: List[str] = []
    for comp in ast.walk(assign):
        if not isinstance(comp, (ast.ListComp, ast.GeneratorExp)):
            continue
        gen = comp.generators[0]
        src = gen.iter.id if isinstance(gen.iter, ast.Name) else None
        words = vocab.get(src, [])
        if isinstance(comp.elt, ast.JoinedStr):
            pre = post = ""
            seen_field = False
            for part in comp.elt.values:
                if isinstance(part, ast.Constant):
                    if seen_field:
                        post += str(part.value)
                    else:
                        pre += str(part.value)
                else:
                    seen_field = True
            names.extend(f"{pre}{w}{post}" for w in words)
    for s in _strings_in(assign):  # plain literals mixed into the tuple
        if _NAME_RE.fullmatch(s):
            names.append(s)
    return sorted(set(names))


def readme_metric_tokens(readme_path: str = README) -> set:
    with open(readme_path) as f:
        return set(_NAME_RE.findall(f.read()))


def scan(modules: Tuple[str, ...] = DECLARING_MODULES,
         readme_path: str = README) -> List[Tuple[str, str]]:
    """Returns ``(module_path, message)`` violations: a module without a
    resolvable METRIC_NAMES, or a declared name absent from README."""
    documented = readme_metric_tokens(readme_path)
    out: List[Tuple[str, str]] = []
    for path in modules:
        names = declared_metrics(path)
        if not names:
            out.append((path, "no resolvable METRIC_NAMES declaration"))
            continue
        for name in names:
            if name not in documented:
                out.append((path, f"metric {name!r} is not documented "
                                  "in README's metrics table"))
    return out


def main() -> int:
    violations = scan()
    for path, msg in violations:
        print(f"{os.path.relpath(path, _REPO)}: {msg}")
    if violations:
        print(f"{len(violations)} metrics-documentation violation(s)")
        return 1
    print("metrics-docs lint: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
