"""Chrome trace-event JSON export + read-back.

Writes the `Trace Event Format`_ the Chrome/Perfetto viewer loads
directly: one ``ph:"M"`` process-name metadata record, then ``ph:"X"``
complete events (spans) and ``ph:"i"`` instant events, timestamps in
microseconds.  Each span's stable ``id``/``parent`` ride along in
``args`` (viewers ignore unknown arg keys), so
:func:`load_profiler_result` reconstructs the exact nesting instead of
guessing from timestamp containment.

.. _Trace Event Format:
   https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

_PID = 0  # single-process host trace


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (tuple, list)):
        # a sequence of ids (the request ids of a step's rows): the
        # step path hands over the sequence, the text is made here
        return ",".join(str(x) for x in v)
    return str(v)


def chrome_trace_dict(spans, epoch_offset: float = 0.0) -> Dict:
    """Serialize ``spans`` (``tracer.Span`` objects) to a Chrome
    trace-event dict — the in-memory form behind
    :func:`export_chrome_trace` and the serving frontend's per-request
    ``GET /v1/requests/{id}?format=chrome`` body."""
    events: List[Dict] = [{
        "ph": "M", "name": "process_name", "pid": _PID, "tid": 0,
        "args": {"name": "paddle_tpu host"},
    }]
    # spans merged from another OS process (cross-process telemetry,
    # observability.distrib) carry a ``chrome_pid`` attr: they render
    # as their own chrome process row, named once per distinct pid
    named_pids = {_PID}
    for sp in spans:
        args = {k: _jsonable(v) for k, v in sp.attrs.items()}
        args["id"] = sp.span_id
        if sp.parent_id is not None:
            args["parent"] = sp.parent_id
        try:
            pid = int(sp.attrs.get("chrome_pid", _PID))
        except (TypeError, ValueError):
            pid = _PID  # swallow-ok: chrome_pid is a free-form span attr — a non-numeric value renders on the local process row instead of failing the export
        if pid not in named_pids:
            named_pids.add(pid)
            events.append({
                "ph": "M", "name": "process_name", "pid": pid,
                "tid": 0,
                "args": {"name": f"paddle_tpu worker pid={pid}"},
            })
        ev = {
            "name": sp.name,
            "cat": sp.cat,
            "pid": pid,
            "tid": sp.tid,
            "ts": (sp.start + epoch_offset) * 1e6,  # chrome wants us
            "args": args,
        }
        if sp.duration > 0.0:
            ev["ph"] = "X"
            ev["dur"] = sp.duration * 1e6
        else:
            ev["ph"] = "i"
            ev["s"] = "t"
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome_trace(spans, path: str,
                        epoch_offset: float = 0.0) -> str:
    """Serialize ``spans`` (``tracer.Span`` objects) to ``path``.

    ``epoch_offset`` shifts perf_counter timestamps onto the wall clock;
    output dirs are created as needed.  Returns ``path``."""
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump(chrome_trace_dict(spans, epoch_offset=epoch_offset), f)
    return path


class LoadedSpan:
    """One event read back from a chrome trace file."""

    __slots__ = ("name", "cat", "ts", "dur", "tid", "attrs", "span_id",
                 "parent_id", "children")

    def __init__(self, name, cat, ts, dur, tid, attrs, span_id, parent_id):
        self.name = name
        self.cat = cat
        self.ts = ts          # microseconds
        self.dur = dur        # microseconds (0 for instants)
        self.tid = tid
        self.attrs = attrs    # args minus the id/parent bookkeeping
        self.span_id = span_id
        self.parent_id = parent_id
        self.children: List["LoadedSpan"] = []

    def __repr__(self):
        return (f"LoadedSpan({self.name!r}, dur={self.dur}us, "
                f"children={len(self.children)})")


class ProfilerResult:
    """Parsed chrome trace: flat event list + reconstructed span tree."""

    def __init__(self, events: List[LoadedSpan], raw: Dict):
        self.events = events
        self.raw = raw
        self.roots: List[LoadedSpan] = []
        by_id = {e.span_id: e for e in events if e.span_id is not None}
        for e in events:
            parent = (by_id.get(e.parent_id)
                      if e.parent_id is not None else None)
            if parent is None and e.span_id is None:
                # foreign traces only: an id-bearing event with no parent
                # id IS a root — guessing by containment would fabricate
                # parents (and cost O(n) per root)
                parent = self._containing(e)
            if parent is not None and parent is not e:
                parent.children.append(e)
            else:
                self.roots.append(e)

    def _containing(self, e: LoadedSpan) -> Optional[LoadedSpan]:
        """Timestamp-containment fallback for traces without id args
        (foreign tools): tightest same-tid span strictly containing e."""
        best = None
        for other in self.events:
            if other is e or other.tid != e.tid or other.dur <= 0:
                continue
            if other.ts <= e.ts and e.ts + e.dur <= other.ts + other.dur:
                if best is None or other.dur < best.dur:
                    best = other
        return best

    def span_names(self) -> List[str]:
        return [e.name for e in self.events]

    def find(self, name: str) -> List[LoadedSpan]:
        return [e for e in self.events if e.name == name]

    def __len__(self):
        return len(self.events)


def load_profiler_result(filename: str) -> ProfilerResult:
    """Read a chrome trace-event JSON file back into a
    :class:`ProfilerResult` (the ``paddle.profiler.load_profiler_result``
    analog — previously a ``NotImplementedError`` stub)."""
    with open(filename) as f:
        raw = json.load(f)
    events = []
    for ev in raw.get("traceEvents", []):
        if ev.get("ph") not in ("X", "i"):
            continue
        args = dict(ev.get("args", {}))
        span_id = args.pop("id", None)
        parent_id = args.pop("parent", None)
        events.append(LoadedSpan(
            ev.get("name", "?"), ev.get("cat", ""), ev.get("ts", 0.0),
            ev.get("dur", 0.0), ev.get("tid", 0), args, span_id, parent_id))
    return ProfilerResult(events, raw)
