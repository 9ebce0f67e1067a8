"""99th percentile of all gaps between streamed tokens.  Sits on the edge
between two classes of prefill stall in the chat mix, so it is bimodal
from run to run and is not end to end (see ``frontdoor.itl_p995_ms.py``)."""
UNIT = "ms"
LAYER = "front door"
SOURCE = "host_clock"


def read(counters, trace):
    return counters["client"]["shape"].get("itl_p99_ms")
