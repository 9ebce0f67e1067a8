"""The ``bytes`` of ``engine.fetch`` (what a launch copies to the host: its tokens since PR 30), in 1e6
bytes per launch of the traced window."""
from benchmarks import host_spans

UNIT = "MB"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    a = host_spans.analysis(trace)
    if a is None or not a["launches"]:
        return None
    return a["fetch_bytes"] / 1e6 / a["launches"]
