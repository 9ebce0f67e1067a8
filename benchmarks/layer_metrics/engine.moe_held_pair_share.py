"""``moe_pairs_held`` over ``moe_assignments`` of the ``engine.fetch`` phases of the traced
decode launches: the share of routed pairs whose expert is held here (16 of 128 held:
12.5% under uniform routing)."""
from benchmarks import window_moe_spans as spans

UNIT = "%"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    return spans.moe_held_pair_share(spans.analysis(trace))
