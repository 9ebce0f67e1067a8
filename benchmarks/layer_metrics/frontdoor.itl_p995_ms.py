"""99.5th percentile of ALL gaps between consecutive streamed tokens of the
requests due in the window (some 18,000 gaps, 90 beyond): the stall a long
prefill puts on everyone else's decoding.  End to end as ``itl_p995_ms``
(bound 0.02, then 0.03) from PR 23 until PR 42: since the loop runs ahead
(PR 35) a settle's tokens wait for the step's prefill, the 92 gaps beyond
this rank all stand behind the few prompts of the 4,096-token prefill
bucket, and the rank falls anywhere in that cluster: 119-133 ms from run
to run of ONE seed, a quartile spread of 6.7 and 7.9% in the driver's two
sets, which no bound the contract allows (0.1) holds at half of it
(PERF.md sections 2 and 6).  Not the 99th: there the stalls behind the
1,024- and 2,048-token buckets meet (``frontdoor.itl_p99_ms``)."""
UNIT = "ms"
LAYER = "front door"
SOURCE = "host_clock"


def read(counters, trace):
    return counters["client"].get("shape", {}).get("itl_p99.5_ms")
