"""Share of device busy time under ``attn_window`` (ring write, ring read, the banded
prompt attention of the sliding-window layers)."""
from benchmarks import window_moe_spans as spans

UNIT = "%"
LAYER = "programs"
SOURCE = "device_trace"


def read(counters, trace):
    return spans.window_attn_share(trace, spans.analysis(trace))
