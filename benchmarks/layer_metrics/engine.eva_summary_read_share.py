"""Summary rows over summary rows and ring entries, of what the rows of the traced decode
launches see (``eva_summary_rows`` / ``eva_ring_tokens`` on ``engine.build``): whether the
traffic still makes the summaries matter (about half by the cell's arithmetic)."""
from benchmarks import eva_spans as spans

UNIT = "%"
LAYER = "engine host loop"
SOURCE = "program_span"


def read(counters, trace):
    return spans.eva_summary_read_share(spans.analysis(trace))
