"""Share of device busy time under the ``ssm`` scope (the selective-scan mixers, every
``ssm_*`` sub-scope and the block's norm): whether the mechanism is most of the work."""
from benchmarks import ssm_spans

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return ssm_spans.ssm_share(trace, ssm_spans.analysis(trace))
