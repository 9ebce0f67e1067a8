"""Time to read every routed expert the traced decode launches touched (18.9 MB each)
at peak HBM bytes/s, over the device time under ``moe_experts`` in the decode program.
Bound: memory."""
from benchmarks import moe_mla_spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return moe_mla_spans.moe_experts_roofline(counters,
                                              moe_mla_spans.analysis(trace))
