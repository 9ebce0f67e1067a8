"""Time to read the latent rows the traced decode steps' rows hold (1,152 B a token, the
one latent layer) at peak HBM bytes/s, over the device time under ``mla_decode_core`` in
the decode program of the delta-rule cell: the page walk at 64 heads.  Bound: memory."""
from benchmarks import gated_delta_spans as gdn

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return gdn.cell_mla_decode_roofline(counters, gdn.analysis(trace))
