"""A run's per-request records as a file: what the window's requests were
offered and what came back, so that a statistic can be re-read from runs
already made (``spreads.py``) and a later ``benchmark`` PR can try another
one on them.  No JAX, no numpy: the client process writes it.

    {"workload": w, "seed": n, "seconds": s, "open": 0.0, "close": s,
     "complete": bool,
     "requests": [[section, ok, prompt_len, max_tokens, due, sent, end,
                   [chunk time, ...], [tokens of the chunk, ...]], ...]}

Times are seconds from window open, rounded to the microsecond (the host's
clock is no better).  A path that ends in ``.gz`` is gzipped.
"""

from __future__ import annotations

import gzip
import json
import os
from typing import Dict


def _rel(t, t0):
    return None if t is None else round(t - t0, 6)


def dump(run: Dict, meta: Dict) -> Dict:
    """``run`` is what a traffic kind returns; ``meta`` names the run."""
    t0 = run["t_open"]
    rows = [[t["section"], bool(t["ok"]), t["prompt_len"], t["max_tokens"],
             _rel(t["due"], t0), _rel(t["sent"], t0), _rel(t["end"], t0),
             [_rel(c[0], t0) for c in t["chunks"]],
             [c[1] for c in t["chunks"]]] for t in run["timelines"]]
    return dict(meta, open=0.0, close=_rel(run["t_close"], t0),
                complete=bool(run["complete"]), requests=rows)


def load(rec: Dict) -> Dict:
    """The ``run`` an ``e2e_metrics`` file computes from, window open at 0."""
    tl = [{"section": r[0], "ok": r[1], "prompt_len": r[2],
           "max_tokens": r[3], "due": r[4], "sent": r[5], "end": r[6],
           "chunks": list(zip(r[7], r[8]))} for r in rec["requests"]]
    return {"timelines": tl, "t_open": rec["open"], "t_close": rec["close"],
            "complete": rec["complete"]}


def _open(path: str, mode: str):
    return gzip.open(path, mode + "t") if path.endswith(".gz") \
        else open(path, mode)


def write(path: str, run: Dict, meta: Dict) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with _open(path, "w") as f:
        json.dump(dump(run, meta), f, separators=(",", ":"))


def read(path: str) -> Dict:
    with _open(path, "r") as f:
        return json.load(f)
