"""Milliseconds under ``proc.gc`` (every collection of the cyclic collector, all
generations, whichever thread it ran on) per second of traced window."""
from benchmarks import thread_spans

UNIT = "ms/s"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    return thread_spans.value(trace, "engine.gc_ms_per_s")
