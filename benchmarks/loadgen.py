"""The one general load generator.  No JAX, no numpy.

A traffic mix is a data file (``traffic/<name>.json``) of distributions
and rates; its ``kind`` names a module of ``traffic_kinds/`` that decides
WHEN requests go out (an arrival schedule, a refilled backlog).  This
module gives every kind the same parts:

* :func:`stratified` -- the ``n`` equal-probability quantile midpoints of
  a distribution, so every seed sees the SAME multiset of lengths and
  gaps; their order and pairing come from the mix's ``layout_seed``, the
  token ids, sampling seeds and weights from the seed.  That keeps the
  heavy tails and removes the draw-to-draw error of independent sampling
  (PERF.md, Findings, PR 23).
* :func:`make_items` -- request bodies from a mix and a seed.
* :class:`Sender` -- one request over loopback HTTP on a thread of its
  own, streamed, every token event time-stamped on arrival; a request
  that returns another count than it asked for, an error or no reply
  counts as failed.
"""

from __future__ import annotations

import http.client
import json
import math
import random
import statistics
import threading
import time
from typing import Callable, Dict, List, Optional

HTTP_TIMEOUT_S = 180.0


# --- distributions -----------------------------------------------------------

def quantile(dist: Dict, p: float) -> float:
    """Inverse CDF of a distribution given as data."""
    kind = dist["dist"]
    if kind == "constant":
        x = float(dist["value"])
    elif kind == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * p
    elif kind == "exponential":
        x = -math.log1p(-p) * dist.get("mean", 1.0)
    elif kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(p)
        x = dist["median"] * math.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        x = max(x, dist["min"])
    if "max" in dist:
        x = min(x, dist["max"])
    return x


def stratified(dist: Dict, n: int) -> List[float]:
    """The ``n`` quantile midpoints ``(i + 0.5) / n``, ascending."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def stratified_gaps(dist: Dict, n: int, total_s: float) -> List[float]:
    """``n`` stratified gaps rescaled so that they sum to ``total_s``
    exactly: every seed's arrivals span the same time."""
    raw = stratified(dist, n)
    scale = total_s / sum(raw)
    return [g * scale for g in raw]


# --- request bodies ----------------------------------------------------------

def layout_rngs(mix: Dict, seed: int):
    """(layout, ids): the generator that orders and pairs the lengths and
    gaps, and the one that draws token ids and sampling seeds.  A mix
    replays ONE schedule (its ``layout_seed``) whatever the seed, and the
    seed decides only the tokens: at a hundred-odd requests a window the
    order alone moved the median TTFT by 25% between seeds, against 1-5%
    between two runs of one seed (my chip run, PR 23).  So a cell's
    metrics are conditional on its mix's one arrival order; another order
    is another mix file."""
    return random.Random(mix["layout_seed"]), random.Random(seed)


def make_items(mix: Dict, n: int, rng: random.Random, section: str,
               ids_rng: random.Random) -> List[Dict]:
    """``n`` requests of one section: stratified prompt and output lengths,
    each shuffled on its own by ``rng`` (so the pairing is the layout's),
    token ids unique to the request, drawn from ``ids_rng``."""
    prompts = [int(round(x)) for x in stratified(mix["prompt_len"], n)]
    outputs = [int(round(x)) for x in stratified(mix["output_len"], n)]
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    samp = mix.get("sampling", {})
    every = int(samp.get("greedy_every", 1))
    greedy = [i % every == 0 for i in range(n)]
    rng.shuffle(greedy)
    items = []
    for i in range(n):
        items.append({
            "section": section, "prompt_len": prompts[i],
            "max_tokens": outputs[i], "greedy": greedy[i],
            "seed": ids_rng.randrange(1, 2 ** 31 - 1),
            "ids_seed": ids_rng.randrange(2 ** 62)})
    return items


def body_of(item: Dict, mix: Dict, vocab: int) -> bytes:
    """The JSON body of one request.  Token ids come from the item's own
    seed, so no two requests share a prefix unless the mix says so."""
    ids_rng = random.Random(item["ids_seed"])
    ids = ids_rng.choices(range(1, vocab), k=item["prompt_len"])
    body = {"prompt": ids, "max_tokens": item["max_tokens"],
            "stream": bool(mix.get("stream", True))}
    samp = mix.get("sampling", {})
    if not item["greedy"]:
        body.update(temperature=samp.get("temperature", 1.0),
                    top_p=samp.get("top_p", 1.0), seed=item["seed"])
    return json.dumps(body, separators=(",", ":")).encode()


# --- sending -----------------------------------------------------------------

class Sender:
    """Sends requests and keeps their timelines (``stats.py``)."""

    def __init__(self, port: int, vocab: int,
                 clock: Callable[[], float] = time.perf_counter):
        self.port = port
        self.vocab = vocab
        self.clock = clock
        self.timelines: List[Dict] = []
        self._lock = threading.Lock()

    def send(self, item: Dict, body: bytes, due: float) -> Dict:
        """Blocking: one request, start to end.  Returns its timeline."""
        t = {"section": item["section"], "due": due, "sent": self.clock(),
             "chunks": [], "end": None, "prompt_len": item["prompt_len"],
             "max_tokens": item["max_tokens"], "ok": False, "error": None}
        with self._lock:
            self.timelines.append(t)
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=HTTP_TIMEOUT_S)
        try:
            conn.request("POST", "/v1/completions", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                t["error"] = f"HTTP {resp.status}"
                resp.read()
                return t
            self._read(resp, t)
        except (OSError, http.client.HTTPException, ValueError) as e:
            t["error"] = f"{type(e).__name__}: {e}"
        finally:
            t["end"] = self.clock()
            conn.close()
        return t

    def _read(self, resp, t: Dict) -> None:
        n_tokens, finish, done, bad = 0, None, False, False
        if resp.getheader("Content-Type", "").startswith("text/event"):
            while True:
                line = resp.fp.readline()
                if not line:
                    break
                if not line.startswith(b"data: "):
                    continue
                when = self.clock()
                if line.startswith(b"data: [DONE]"):
                    done = True
                    break
                choice = json.loads(line[6:])["choices"][0]
                toks = choice["token_ids"]
                if toks:
                    t["chunks"].append((when, len(toks)))
                    n_tokens += len(toks)
                    bad = bad or not all(0 <= x < self.vocab for x in toks)
                finish = choice["finish_reason"] or finish
        else:
            choice = json.loads(resp.read())["choices"][0]
            toks = choice["token_ids"]
            t["chunks"].append((self.clock(), len(toks)))
            n_tokens, finish, done = len(toks), choice["finish_reason"], True
            bad = not all(0 <= x < self.vocab for x in toks)
        if not done:
            t["error"] = "stream ended without [DONE]"
        elif n_tokens != t["max_tokens"] or finish != "length":
            t["error"] = (f"{n_tokens} tokens ({finish}), asked "
                          f"{t['max_tokens']}")
        elif bad:
            t["error"] = "token id outside the vocabulary"
        else:
            t["ok"] = True

    def send_async(self, item: Dict, body: bytes, due: float,
                   then: Optional[Callable[[Dict], None]] = None) -> None:
        def work():
            t = self.send(item, body, due)
            if then is not None:
                then(t)

        threading.Thread(target=work, daemon=True).start()

    def wait(self, timeout_s: float, sections=("window",)) -> bool:
        """Until every request of ``sections`` has ended."""
        deadline = self.clock() + timeout_s
        while self.clock() < deadline:
            with self._lock:
                open_ = [t for t in self.timelines
                         if t["section"] in sections and t["end"] is None]
            if not open_:
                return True
            time.sleep(0.02)
        return False

    def snapshot(self) -> List[Dict]:
        with self._lock:
            return [dict(t, chunks=list(t["chunks"])) for t in self.timelines]


def sleep_until(when: float, clock=time.perf_counter) -> None:
    """Sleep to an absolute time on ``clock``: coarse sleep, then a short
    spin, so a dispatch is late by tens of microseconds, not a timer tick."""
    while True:
        left = when - clock()
        if left <= 0:
            return
        time.sleep(left - 0.0005 if left > 0.001 else 0)
