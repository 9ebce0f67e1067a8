"""Decode-burst host surface (ISSUE 19).

The device side is :func:`paddle_tpu.ops.decode_burst.run_burst` — one
compiled program chaining up to N decode steps.  This module owns the
host half: the eligibility predicate (WHEN the engine may burst), the
length clamp (HOW FAR it may burst), and the burst metric series.

Eligibility is deliberately conservative — a burst launches only when
the running set is a decode-only resident cohort and the whole horizon
is pre-decided, so every scheduler contract (admission, preemption,
spec drafting) stays a host decision at burst boundaries:

* ``burst_steps >= 2`` configured (1-step bursts are just decode with
  extra padding);
* no prefill work pending: the plan carries no chunks AND the waiting
  queue is empty AND no running request still needs prefill (a chunk
  the budget deferred this step must not be starved for N steps);
* spec decoding off — the n-gram proposer drafts from the freshest
  host-side token history every step, so a resident burst would decode
  exactly the tokens the proposer exists to skip;
* at least 2 decode rows' worth of headroom after the clamp.

The clamp (``clamp_burst``) is the launch-side half of the ONE headroom
accessor ``KVCacheManager.burst_capacity`` — the scheduler computed
``plan.burst_capacity`` from it after reserving this step's decode
slots, so by construction the burst can never hit pool exhaustion or a
``max_new_tokens`` boundary it cannot represent mid-flight.
"""

from __future__ import annotations

# pre-registered by the engine at construction so the series exist from
# the first scrape (tools/check_metrics_docs lints README coverage;
# tools/check_bounded_metrics pins this module's growth discipline)
METRIC_NAMES = (
    "serving_burst_launches_total",
    "serving_burst_tokens_total",
    "serving_burst_length",
    "serving_host_roundtrips_total",
    "serving_logits_fetches_total",
    "serving_logits_fetch_bytes_total",
)

# a burst length is clamped to config.burst_steps, itself bounded by the
# AOT lattice — power-of-two-ish buckets keep the histogram aligned
# with the burst-length bucket axis
_LENGTH_BUCKETS = (2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)


def register_metrics(registry, labels=None):
    """Create the burst series on ``registry`` (get-or-create, so dp
    replicas sharing a registry share per-label series).  ``labels``
    must carry the engine's replica label in fleets: the cross-process
    :class:`~paddle_tpu.serving.wire.RegistryMerger` merges ONLY rows
    labeled with the owning replica."""
    lb = labels or {}
    return {
        "launches": registry.counter(
            "serving_burst_launches_total",
            help="device-resident decode bursts launched", **lb),
        "tokens": registry.counter(
            "serving_burst_tokens_total",
            help="tokens emitted by burst launches (all rows)", **lb),
        "length": registry.histogram(
            "serving_burst_length",
            help="clamped burst length N per launch (decode steps "
                 "covered by one host round-trip)",
            buckets=_LENGTH_BUCKETS, **lb),
        "roundtrips": registry.counter(
            "serving_host_roundtrips_total",
            help="host->device step-program launches (a burst counts "
                 "once; the saving vs per-step decode is this series' "
                 "slope)", **lb),
        # what a launch copies back beside its tokens: nothing, unless
        # the numerics audit reads the logits (EngineCore._launch)
        "logits_fetches": registry.counter(
            "serving_logits_fetches_total",
            help="step-program launches that copied rows of float32 "
                 "logits to the host (the numerics audit's sampled "
                 "decode / ragged launches, and non-finite bundles)",
            **lb),
        "logits_fetch_bytes": registry.counter(
            "serving_logits_fetch_bytes_total",
            help="bytes of logits those launches copied to the host "
                 "(real rows x vocabulary x 4)", **lb),
    }


def clamp_burst(burst_steps: int, decodes, capacity: int) -> int:
    """The host-side burst-length clamp:
    ``N = min(config.burst_steps, min per-row remaining max_new,
    pool headroom per row)`` — every term a quantity the host already
    owns, so the device loop needs no in-trace max_new/pool masking.

    Returns 0 when no burst is worth launching (``N < 2``)."""
    if burst_steps < 2 or not decodes:
        return 0
    remaining = min(r.sampling.max_new_tokens - len(r.output_tokens)
                    for r in decodes)
    n = min(int(burst_steps), int(remaining), int(capacity))
    return n if n >= 2 else 0


def burst_eligible(scheduler, plan, decodes, spec) -> bool:
    """True when this step's running set is a decode-only resident
    cohort (see module docstring) — the gate the tests hold to 'burst
    provably never launched when spec drafting or prefill work is
    pending'."""
    if spec is not None or not decodes:
        return False
    if plan.prefills or scheduler.waiting:
        return False
    # a running request the chunk budget deferred this step still needs
    # prefill — bursting the decode cohort would starve it for N steps
    return not any(scheduler._needs_prefill(r) for r in scheduler.running)
