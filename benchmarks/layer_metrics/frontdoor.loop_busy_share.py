"""Union of the loop thread's ``server.accept`` / ``server.wake`` /
``server.write`` spans over the traced window."""
from benchmarks import thread_spans

UNIT = "%"
LAYER = "front door"
SOURCE = "program_span"


def read(counters, trace):
    return thread_spans.value(trace, "frontdoor.loop_busy_share")
