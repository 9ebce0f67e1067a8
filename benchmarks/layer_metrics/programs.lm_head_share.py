"""Share of device busy time in operations under the ``lm_head`` scope."""
from benchmarks import host_spans

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return host_spans.scope_share(trace, "lm_head")
