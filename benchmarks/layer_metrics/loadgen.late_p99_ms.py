"""99th percentile of sent minus due over the window's requests: a starved
generator must not read as a fast server."""

UNIT = "ms"
LAYER = "load generator"
SOURCE = "host_clock"


def read(counters, trace):
    return counters["client"].get("late_p99_ms")
