"""Backlog: offline work, more than the window can finish, kept at a fixed
number of requests in flight and refilled as they complete.

The first wave is primed mid-flight: request ``i`` of it has already
"generated" a stratified share of its output, which is moved into its
prompt, so the server starts with the spread of ages and cache lengths it
would have in steady state instead of a cohort that finishes together.
The window opens after ``lead_in_s``; throughput counts the tokens that
arrive inside the window, whichever request they belong to.

The backlog is a run of CYCLES: each cycle is the same stratified multiset
of ``cycle`` requests in an order of its own.  A window consumes several
whole cycles, so every seed's window holds the same mix of lengths; with
one long stratified list the window would hold whichever third of it the
seed put first, and the padding a prompt wastes depends on its length
(2% between seeds against 0.2% within one, my chip run PR 23).
"""

from __future__ import annotations

import threading
from typing import Dict, List

from benchmarks import loadgen


def sequence(mix: Dict, seed: int) -> List[Dict]:
    """The backlog in submission order.  Pure: the tests call it."""
    rng, ids_rng = loadgen.layout_rngs(mix, seed)
    n, width = int(mix["requests"]), int(mix["in_flight"])
    cycle = int(mix["cycle"])
    items: List[Dict] = []
    while len(items) < n:
        items.extend(loadgen.make_items(mix, cycle, rng, "window", ids_rng))
    if mix.get("prime_first_wave", False):
        done = loadgen.stratified({"dist": "uniform", "min": 0.0,
                                   "max": 1.0}, width)
        rng.shuffle(done)
        for item, share in zip(items, done):
            moved = min(int(item["max_tokens"] * share),
                        item["max_tokens"] - 1)
            item["prompt_len"] += moved
            item["max_tokens"] -= moved
            item["section"] = "lead_in"
    return items


def run(env) -> Dict:
    mix = env.mix
    items = sequence(mix, env.seed)
    sender = loadgen.Sender(env.port, env.vocab)
    lock = threading.Lock()
    state = {"next": 0, "stop": False}

    def worker():
        while True:
            with lock:
                if state["stop"] or state["next"] >= len(items):
                    return
                item = items[state["next"]]
                state["next"] += 1
            body = loadgen.body_of(item, mix, env.vocab)
            sender.send(item, body, sender.clock())

    start = sender.clock()
    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(mix["in_flight"]))]
    for th in threads:
        th.start()
    t_open = start + float(mix["lead_in_s"])
    t_close = t_open + env.seconds
    marks = [(t_open, "open"), (t_close, "close")]
    if env.trace_s:
        marks.append((t_open + 0.4 * env.seconds, "trace"))
    for t, name in sorted(marks):
        loadgen.sleep_until(t)
        env.mark(name)
    with lock:
        state["stop"] = True
        ran_dry = state["next"] >= len(items)
    # requests still in flight stay in: their tokens arrived in the window
    return {"timelines": sender.snapshot(), "t_open": t_open, "t_close": t_close,
            "complete": not ran_dry}
