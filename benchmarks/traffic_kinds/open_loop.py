"""Open loop: independent users on an arrival schedule fixed by the mix.

Three stretches of the same stratified traffic, back to back: a lead-in
that is not counted (it is part of set-up and brings the server to its
steady state), the window, and a lead-out that keeps the load up while the
window's last requests finish.  Each stretch draws its own quantile
midpoints, so the window holds exactly ``rate x seconds`` requests of the
same lengths and gaps whatever the seed.  A request is timed from when it
was DUE, and how late it was sent is reported.
"""

from __future__ import annotations

import threading
from typing import Dict

from benchmarks import loadgen


def schedule(mix: Dict, seconds: float, seed: int):
    """[(due offset from start, item)], the window's open and close
    offsets.  Pure: the tests call it."""
    rng, ids_rng = loadgen.layout_rngs(mix, seed)
    rate = float(mix["rate_rps"])
    out, t0 = [], 0.0
    spans = (("lead_in", float(mix["lead_in_s"])), ("window", float(seconds)),
             ("lead_out", float(mix["lead_out_s"])))
    for section, span in spans:
        n = max(1, int(round(rate * span)))
        gaps = loadgen.stratified_gaps(mix["gap"], n, span)
        rng.shuffle(gaps)
        items = loadgen.make_items(mix, n, rng, section, ids_rng)
        t = t0
        for gap, item in zip(gaps, items):
            t += gap
            out.append((t, item))
        t0 += span
    t_open = float(mix["lead_in_s"])
    return out, t_open, t_open + float(seconds)


def run(env) -> Dict:
    mix = env.mix
    plan, t_open, t_close = schedule(mix, env.seconds, env.seed)
    bodies = [loadgen.body_of(item, mix, env.vocab) for _, item in plan]
    sender = loadgen.Sender(env.port, env.vocab)
    start = sender.clock() + 0.05
    marks = [(t_open, "open"), (t_close, "close")]
    if env.trace_s:
        marks.append((t_open + 0.4 * env.seconds, "trace"))
    events = sorted([(t, "send", i) for i, (t, _) in enumerate(plan)]
                    + [(t, name, -1) for t, name in marks])
    for t, what, i in events:
        loadgen.sleep_until(start + t)
        if what == "send":
            sender.send_async(plan[i][1], bodies[i], start + t)
        else:   # marks talk to the launcher: never on the dispatch thread
            threading.Thread(target=env.mark, args=(what,),
                             daemon=True).start()
    whole = sender.wait(loadgen.HTTP_TIMEOUT_S, sections=("window",))
    timelines = sender.snapshot()
    return {"timelines": timelines, "t_open": start + t_open,
            "t_close": start + t_close, "complete": whole}
