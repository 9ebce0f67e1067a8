"""90th percentile, over requests due in the window, of first streamed
token minus due time, at the client (117 requests, 11 beyond).  The tail
and not the median: over six runs of unchanged code the 90th percentile
spread by 1.4% and the median by 8% (PERF.md section 5); the median is the
per-layer ``frontdoor.ttft_p50_ms``."""
from benchmarks import stats


def compute(run):
    vals = [stats.ttft_ms(t) for t in stats.counted(run["timelines"])]
    return stats.percentile([v for v in vals if v is not None], 90)
