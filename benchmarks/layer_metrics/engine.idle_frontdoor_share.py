"""Of the device's idle seconds between programs, the share overlapped by a
``server.*`` span of the server's loop thread: the most that thread's hold on
the interpreter lock can cost the device.  ``None``, with the reason on
standard error, where the host plane's offset cannot be pinned to 2 ms."""
from benchmarks import thread_spans

UNIT = "%"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    return thread_spans.value(trace, "engine.idle_frontdoor_share")
