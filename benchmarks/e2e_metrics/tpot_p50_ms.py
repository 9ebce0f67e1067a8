"""Median over requests due in the window of (last token - first token) /
(output tokens - 1): a per-request mean gap, so it blends the batch-bucket
modes a raw median of gaps would jump between."""
from benchmarks import stats


def compute(run):
    vals = [stats.tpot_ms(t) for t in stats.counted(run["timelines"])]
    return stats.percentile([v for v in vals if v is not None], 50)
