"""Median of first token minus due time at the client.  Not end to end:
its runs of unchanged code spread by 8% (PERF.md section 5)."""
UNIT = "ms"
LAYER = "front door"
SOURCE = "host_clock"


def read(counters, trace):
    return counters["client"].get("ttft_p50_ms")
