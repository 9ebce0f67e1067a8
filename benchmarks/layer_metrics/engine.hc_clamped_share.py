"""``hc_res_clamped`` over ``hc_entries`` of the traced ``engine.fetch`` phases, per
thousand: entries of the pre-exp residual-mixing matrices that met the clamp (+-30)."""
from benchmarks import hc_moe_mla_spans as spans

UNIT = "permille"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    return spans.hc_clamped_share(spans.analysis(trace))
