"""99.8th percentile of all gaps between streamed tokens of the requests
due in the window (18,525 gaps, 37 beyond): the steadier statistic beside
``frontdoor.itl_p995_ms``.  In the chat mix 98 gaps a window stand behind
the long prefills; ranks 20-85 from the top are one plateau (139-143 ms,
the stall behind a prompt of the 4,096-token bucket), and this rank lies
in its middle, where the 99.5th lies on its lower edge: 140.8-141.6 ms
over four runs that read 120.3-128.0 there (my chip runs, PR 42, PERF.md
section 6).  The candidate to bring a tail of the gaps back end to end,
once two sets of six runs have given it a bound."""
UNIT = "ms"
LAYER = "front door"
SOURCE = "host_clock"


def read(counters, trace):
    return counters["client"].get("shape", {}).get("itl_p99.8_ms")
