"""``moe_max_load`` x experts / ``moe_assignments`` of the ``engine.fetch`` phases of
the traced decode launches: the straggler expert (1.0 is perfect balance)."""
from benchmarks import moe_mla_spans

UNIT = "ratio"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    return moe_mla_spans.moe_load_max_over_mean(counters,
                                                moe_mla_spans.analysis(trace))
