"""Device idle time between step programs under NO phase of the engine (and not
under ``engine.wait``), % of that idle time: what the spans do not explain."""
from benchmarks import host_spans

UNIT = "%"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    a = host_spans.analysis(trace)
    if a is None or not a["gap_s"]:
        return None
    return 100.0 * a["gaps"][host_spans.UNATTRIBUTED] / a["gap_s"]
