"""Share of device busy time under the ``gdn`` scope (the delta-rule mixers, every
``gdn_*`` sub-scope and the block's norms around them): whether the mechanism is most of
the work."""
from benchmarks import gated_delta_spans as gdn

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return gdn.gdn_share(trace, gdn.analysis(trace))
