"""Share of device busy time in operations under the ``attn`` scope (projections,
RoPE, cache write and the attention kernel)."""
from benchmarks import host_spans

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return host_spans.scope_share(trace, "attn")
