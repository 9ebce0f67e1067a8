"""Of the device's idle seconds between programs, the share in gaps that END at a
step program whose dispatch had ``ahead=0``: the synchronous bubble of a step
that read the launch in flight first (``ahead.settle``).  The rest is a chain
that came late although it ran ahead.  Gaps whose dispatch the trace does not
hold are left out of both."""
from benchmarks import thread_spans

UNIT = "%"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    return thread_spans.value(trace, "engine.idle_settled_share")
