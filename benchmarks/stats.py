"""Arithmetic over request timelines.  No JAX, no numpy: the client process
imports this.

A *timeline* is what the load generator records for one request::

    {"section": "window", "due": t, "sent": t, "chunks": [(t, n_tokens), ...],
     "end": t, "prompt_len": n, "max_tokens": n, "ok": bool}

All times are seconds on the client's monotonic clock.  ``chunks`` holds
one entry per streamed event that carried tokens.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule), ``None`` for no values."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def counted(timelines: Iterable[Dict]) -> List[Dict]:
    """Requests due inside the window that came back whole."""
    return [t for t in timelines if t["section"] == "window" and t["ok"]]


def ttft_ms(t: Dict) -> Optional[float]:
    """First streamed token minus the time the request was DUE."""
    if not t["chunks"]:
        return None
    return (t["chunks"][0][0] - t["due"]) * 1e3


def ttfts_ms(timelines: Iterable[Dict]) -> List[float]:
    """TTFTs of the requests due in the window that came back whole."""
    vals = (ttft_ms(t) for t in counted(timelines))
    return [v for v in vals if v is not None]


def tpot_ms(t: Dict) -> Optional[float]:
    """(last token - first token) / (output tokens - 1): a per-request
    mean gap.  ``None`` for a request of fewer than two tokens."""
    n = sum(c[1] for c in t["chunks"])
    if n < 2:
        return None
    return (t["chunks"][-1][0] - t["chunks"][0][0]) * 1e3 / (n - 1)


def token_gaps_ms(t: Dict) -> List[float]:
    """Gaps between consecutive streamed tokens of one request.  Tokens
    that arrived in one event have a gap of 0 between them."""
    gaps: List[float] = []
    prev = None
    for when, n in t["chunks"]:
        if prev is not None:
            gaps.append((when - prev) * 1e3)
            n -= 1
        elif n > 0:
            n -= 1      # the first token has no gap before it
        gaps.extend([0.0] * max(n, 0))
        prev = when
    return gaps


def tokens_in_window(timelines: Iterable[Dict], t_open: float,
                     t_close: float) -> Tuple[int, int]:
    """(prompt tokens, output tokens) that arrived inside [open, close):
    a prompt counts whole when its request's first token arrived inside,
    an output token counts when its own event arrived inside.  Requests
    of every section count: throughput is over all the work of the
    window, whenever the request was submitted."""
    prompt = out = 0
    for t in timelines:
        for i, (when, n) in enumerate(t["chunks"]):
            if t_open <= when < t_close:
                out += n
                if i == 0:
                    prompt += t["prompt_len"]
    return prompt, out


def iqr_share(values: Sequence[float]) -> Optional[float]:
    """Distance between first and third quartile over the median, as the
    driver takes it (``statistics.quantiles(values, n=4)``)."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def trimmed_range_share(values: Sequence[float]) -> Optional[float]:
    """The range of the runs less the one run farthest from their median,
    over the median: the spread the driver's pair check quotes ("leaves out
    the run farthest from its median")."""
    if len(values) < 3:
        return None
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    rest = [v for i, v in enumerate(values) if i != far]
    return (max(rest) - min(rest)) / med if med else None


def meets_rule(sets: Sequence[Sequence[float]], bound: float) -> bool:
    """PR 27's rule: a metric may stand under ``bound`` only if the trimmed
    range of EVERY set is at most half of it and the sets' medians lie
    within half of it of each other."""
    meds = [statistics.median(s) for s in sets]
    apart = (max(meds) - min(meds)) / statistics.median(meds)
    return (all(trimmed_range_share(s) <= 0.5 * bound for s in sets)
            and apart <= 0.5 * bound)
