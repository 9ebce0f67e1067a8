"""Finding the pieces of a cell by name.  No JAX.

``BENCHMARK.json`` names cells, configurations and metrics; each piece is a
file of its own under ``benchmarks/``, so a later PR adds a cell, a
configuration, a traffic mix or a metric by adding files and entries and
edits nothing that is there:

====================  =====================================================
``configs/<c>.json``       a configuration: published sizes, what was cut,
                           engine settings, its builder and its reference
``traffic/<t>.json``       a traffic mix: distributions, rates, its kind
``traffic_kinds/<k>.py``   WHEN requests go out: ``run(env) -> dict``
``models/<b>.py``          ``build(model_cfg, seed)``, ``reference_weights``
``reference/<r>.py``       ``reference_logits``, ``compare``
``e2e_metrics/<m>.py``     ``compute(run) -> value or None`` (client side)
``layer_metrics/<m>.py``   ``read(counters, trace) -> value or None``
====================  =====================================================

A per-layer metric says in ``BENCHMARK.json`` which end-to-end metric it
should move, so a quantity read in cells that report different end-to-end
metrics has one entry per group of cells (``device.idle_share.chat``,
``device.idle_share.batch``) and ONE reader: a name that has no file of its
own is read by the file of the name without its last part
(``layer_metrics/device.idle_share.py``).  The reader declares what it
reads (``UNIT``, ``LAYER``, ``SOURCE``); ``moves`` is the entry's alone.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str, here: str = HERE):
    """``benchmarks/<kind>/<name>.py`` as a module.  Names may hold dots
    (``scheduler.rows_per_step.chat``), so this goes by path."""
    path = os.path.join(here, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    modname = "benchmarks_%s_%s" % (kind, name.replace(".", "_").replace("-", "_"))
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, here: str = HERE):
    """The reader of the per-layer metric ``name``: the file of that name,
    or of the name without its last dotted part."""
    for stem in (name, name.rpartition(".")[0]):
        if stem and os.path.isfile(os.path.join(here, "layer_metrics",
                                                stem + ".py")):
            return load_module("layer_metrics", stem, here)
    raise FileNotFoundError(f"no layer_metrics file reads {name!r}")


class Cell:
    """One entry of ``workloads`` with everything it names resolved.
    ``bench`` stands in for ``<root>/BENCHMARK.json`` where a test holds
    the benchmark as a ``dict`` (a copy with a later PR's entries)."""

    def __init__(self, name: str, root: str = ROOT,
                 bench: Optional[Dict] = None):
        self.root = root
        self.here = os.path.join(root, "benchmarks")
        self.bench = load_json(root, "BENCHMARK.json") if bench is None \
            else bench
        rows = [w for w in self.bench["workloads"] if w["name"] == name]
        if not rows:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{[w['name'] for w in self.bench['workloads']]}")
        self.workload = rows[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg = [c for c in self.bench["configs"]
               if c["name"] == self.workload["config"]][0]
        self.config_path = os.path.join(root, cfg["file"])
        self.config = load_json(self.config_path)
        self.traffic_path = os.path.join(
            self.here, "traffic", self.workload["traffic"] + ".json")
        self.traffic = load_json(self.traffic_path)

    def _mine(self, group: str) -> List[Dict]:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    @property
    def end_to_end(self) -> List[Dict]:
        return self._mine("end_to_end")

    @property
    def per_layer(self) -> List[Dict]:
        return self._mine("per_layer")

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.here)

    def reader(self, name: str):
        return load_reader(name, self.here)


def traffic_limits(mix: Dict) -> Dict:
    """The lengths and concurrency a mix can reach: what the launcher
    warms up for, and no more."""
    p, o = mix["prompt_len"], mix["output_len"]

    def lo(d):
        return int(d.get("min", d.get("value", 1)))

    def hi(d):
        return int(d.get("max", d.get("value")))

    primed = bool(mix.get("prime_first_wave", False))
    return {
        "min_prompt": lo(p),
        # a primed first wave carries its "generated" part in its prompt
        "max_prompt": hi(p) + (hi(o) - 1 if primed else 0),
        "min_total": lo(p) + 1,
        "max_total": hi(p) + hi(o),
        "in_flight": mix.get("in_flight"),
    }
