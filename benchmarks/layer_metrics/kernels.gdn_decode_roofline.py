"""Time to read and write once the delta-rule state of every real row the traced decode
steps advanced (4,292,608 B a row a delta-rule layer, each way) at peak HBM bytes/s, over
the device time under ``gdn_step`` in the decode program.  Bound: memory."""
from benchmarks import gated_delta_spans as gdn

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return gdn.gdn_decode_roofline(counters, gdn.analysis(trace))
