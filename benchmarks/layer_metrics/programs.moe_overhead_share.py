"""Share of device busy time under ``moe_router``, ``moe_dispatch`` and
``moe_combine``: what routing costs beside the experts' matmuls."""
from benchmarks import moe_mla_spans

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return moe_mla_spans.moe_overhead_share(trace,
                                            moe_mla_spans.analysis(trace))
