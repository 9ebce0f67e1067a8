"""The largest ``state_slots_held`` of the traced launches' ``engine.build`` phases over
the slots there are (``max_num_seqs``): how full the per-sequence state is."""
from benchmarks import ssm_spans

UNIT = "%"
LAYER = "cache"
SOURCE = "program_span"


def read(counters, trace):
    return ssm_spans.state_slots_peak_share(counters,
                                            ssm_spans.analysis(trace))
