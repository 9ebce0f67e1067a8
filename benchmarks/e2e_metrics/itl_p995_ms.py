"""99.5th percentile of ALL gaps between consecutive streamed tokens of the
requests due in the window (some 18,000 gaps, 90 beyond): the stall a long
prefill puts on everyone else's decoding.  Not the 99th: in the chat mix
the stalls behind 1024- and 2048-token prefill buckets meet at 1.0% of the
gaps, and a 99th percentile read 90 or 150 ms from run to run of one seed
(PERF.md section 6); it is the per-layer ``frontdoor.itl_p99_ms``."""
from benchmarks import stats


def compute(run):
    gaps = []
    for t in stats.counted(run["timelines"]):
        gaps.extend(stats.token_gaps_ms(t))
    return stats.percentile(gaps, 99.5)
