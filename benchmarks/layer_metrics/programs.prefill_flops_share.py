"""Model FLOPs of the prompt tokens prefilled over the prefill programs' device
time at peak bf16 FLOP/s. Padding and all-position logits are not model
FLOPs."""
from benchmarks import layer_lib

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return layer_lib.prefill_flops_share(counters, trace)
