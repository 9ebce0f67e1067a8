"""Time for the chunked delta rule's operations over the real tokens of the prompts the
traced prefills took (10,485,760 a chunk of 64 a value head: ``roofline_gated_delta
.chunk_flops_per_head``) at peak bf16 FLOP/s, over the device time under ``gdn_chunk`` in
the prefill program; nothing where the traced slice holds no prefill.  Bound: compute."""
from benchmarks import gated_delta_spans as gdn

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return gdn.gdn_chunk_roofline(counters, gdn.analysis(trace))
