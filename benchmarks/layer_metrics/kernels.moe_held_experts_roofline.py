"""Time to read every HELD expert a pair of the traced decode launches reached (100.7 MB
each) at peak HBM bytes/s, over the device time under ``moe_experts`` in the decode
program.  Bound: memory."""
from benchmarks import window_moe_spans as spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.moe_held_experts_roofline(counters, spans.analysis(trace))
