"""The most summary rows the rows of one traced decode launch held (``eva_rows_held`` on
``engine.build``: one a whole chunk of each sequence) over the rows allocated a layer
(``num_blocks`` x ``block_size / chunk_size``): how full the rows' memory is."""
from benchmarks import eva_spans as spans

UNIT = "%"
LAYER = "cache"
SOURCE = "program_span"


def read(counters, trace):
    return spans.eva_summary_peak_share(counters, spans.analysis(trace))
