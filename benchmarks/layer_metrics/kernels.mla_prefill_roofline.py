"""FLOPs of the keys and values rebuilt from the latents and of the causal scores and
weighted sums at the traced prompts' lengths, at peak FLOP/s, over the device time under
``mla_prefill_core`` in the prefill program.  Bound: compute."""
from benchmarks import hc_moe_mla_spans as spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.mla_prefill_roofline(counters, spans.analysis(trace))
