"""Time to read x and dt and write y for the prompt tokens the traced prefills scanned
(30,720 B a token a mixer layer) and to write one state a prompt, at peak HBM bytes/s,
over the device time under ``ssm_scan`` in the prefill program.  Bound: memory by this
count; a reading of a few percent is the headroom a kernel has."""
from benchmarks import ssm_spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return ssm_spans.ssm_scan_roofline(counters, ssm_spans.analysis(trace))
