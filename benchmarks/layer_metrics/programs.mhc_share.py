"""Share of device busy time under the outer ``mhc`` scope (the multi-stream residual
path: coefficients, Sinkhorn rounds, both mixes, entry and exit of the streams)."""
from benchmarks import hc_moe_mla_spans as spans

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.mhc_share(trace, spans.analysis(trace))
