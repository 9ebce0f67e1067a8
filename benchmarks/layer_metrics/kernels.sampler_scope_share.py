"""Share of device busy time in operations under the ``sampler`` scope (all of
``sample_tokens``): the sort ``kernels.sampler_share`` sees, and the rest."""
from benchmarks import host_spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"


def read(counters, trace):
    return host_spans.scope_share(trace, "sampler")
