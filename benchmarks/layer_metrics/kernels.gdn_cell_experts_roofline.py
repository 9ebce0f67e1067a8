"""Time to read every held expert a traced decode step's pairs reached (88.1 MB each, 16
of 256 held at about 4 tokens an expert) at peak HBM bytes/s, over the device time under
``moe_experts`` in the decode program of the delta-rule cell.  Bound: memory."""
from benchmarks import gated_delta_spans as gdn

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return gdn.cell_experts_roofline(counters, gdn.analysis(trace))
