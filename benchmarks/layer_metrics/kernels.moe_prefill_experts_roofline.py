"""The traced prefill launches' routed pairs x 6 x 3,584 x 1,024 FLOP against the bytes
of the experts they touched (22.0 MB each), the larger time, over the device time under
``moe_experts`` in the prefill program.  Bound: compute at thousands of tokens."""
from benchmarks import hc_moe_mla_spans as spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.moe_prefill_experts_roofline(counters, spans.analysis(trace))
