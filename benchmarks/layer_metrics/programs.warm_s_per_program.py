"""Set-up seconds per step program warmed: host tracing, and in a first run
compilation. What only a change to the program shortens (PERF.md section 7)."""

UNIT = "s"
LAYER = "programs"
SOURCE = "host_clock"


def read(counters, trace):
    split = counters.get("setup") or {}
    if not split.get("warm_programs"):
        return None
    return split["warm_s"] / split["warm_programs"]
