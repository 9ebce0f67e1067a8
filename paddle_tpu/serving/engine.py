"""EngineCore: request-level continuous-batching serving engine.

The piece VERDICT N31 called missing: above ``ops/paged_attention.py``
(block pool) and ``inference.LLMPredictor`` (single-call API) sits an
engine that owns a request queue, admission control, preemption, and a
**fixed-shape** jitted step program — the Ragged-Paged-Attention serving
shape (PAPERS.md) with MPK's compile-once discipline:

* All sequences share ONE paged KV pool per layer
  (``[num_blocks, block_size, Hkv, D]``); per-step routing arrays (block
  tables, lengths, slot indices) are DATA, so joining/leaving requests
  never change a tensor shape.
* Batch size and block-table width are padded to power-of-two buckets
  (``scheduler.bucket_size``), so the jitted decode step compiles at most
  once per (batch-bucket, width-bucket) pair and the jitted prefill at
  most once per prompt-length bucket — never per request.  ``
  decode_trace_count``/``prefill_trace_count`` count actual retraces
  (incremented inside the traced function, so they move only when JAX
  really traces) and are asserted against the bucket sets in tests.
* Pool exhaustion preempts (lowest priority, newest arrival first) and
  recomputes instead of failing the request: the victim's blocks are
  freed, it re-enqueues at the front of the waiting queue, and its next
  prefill runs over ``prompt + output_tokens`` — token-identical
  continuation under greedy decoding (tested).
* Padding rows of a bucketed batch write into block 0, the reserved null
  page, and carry ``seq_len = 1`` so every attention path stays finite.

The model runs *functionally* inside the jitted step: parameters and KV
pools enter as jit arguments (swapped into the eager module for the trace,
restored after), updated pools return as outputs.  On TPU the pool
arguments are donated, so the decode step updates KV in place in HBM.

**Tensor-parallel serving (ISSUE 5):** when the global mesh
(``distributed.topology``) carries an ``mp`` axis > 1, the engine runs the
same loop mesh-spanning: parameters are placed per their
``PartitionSpec`` annotations (the Megatron column→row pairing of
``parallel/mp_layers.py`` — attention heads and MLP width sharded over
``mp``), the KV pools shard along the **head** dim
(``ops.paged_attention.shard_kv_pool``), and the jitted prefill/decode
programs carry explicit in/out shardings: routing arrays (block tables,
seq lens, slot indices, token ids) enter **replicated**, pools and
activations sharded, and GSPMD inserts the collectives.  Everything
host-side — BlockPool bookkeeping, scheduler state, admission math,
prefix-cache hashes — is untouched: one scheduler decision drives N
shards, and only the per-shard pool byte footprint divides by mp.  The
bucket sets (and therefore the jit trace count) are mp-invariant.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..distributed import topology
from ..observability import lifecycle as _lc
from ..observability.audit import (
    AUDIT_PROGRAMS,
    AuditConfig,
    NumericsAuditor,
    logit_stats,
)
from ..observability.cachestat import CacheStatTracker
from ..observability.lifecycle import LifecycleTracker
from ..observability.stepprof import StepProfiler
from ..observability.tracer import (
    SETTLE_AUDIT,
    SETTLE_BARE,
    SETTLE_FAMILY,
    SETTLE_FAULT,
    SETTLE_REASONS,
    SETTLE_TASK,
)
from ..ops import paged_attention as _paged_ops
from ..ops import ragged_paged as _ragged_ops
from ..ops.paged_attention import (
    KV_POOL_SPEC,
    LaunchView,
    PoolExhausted,
    shard_kv_pool,
)
from ..ops.decode_burst import run_burst
from ..ops.selective_scan import state_step_path
from ..ops.sampling import sample_tokens
from .burst import burst_eligible, clamp_burst
from .burst import register_metrics as _register_burst_metrics
from .kv_manager import KVCacheManager
from .metrics import ServingMetrics, StepTimer
from .request import FinishReason, Request, RequestState, SamplingParams
from .sampling import SamplingPack
from .sampling import register_metrics as _register_sampling_metrics
from .scheduler import (
    ContinuousBatchingScheduler,
    SchedulerConfig,
    bucket_size,
)


# per-step cap on individual prefix_cache_eviction lifecycle events
# (ISSUE 13): counters/histograms/cause series stay exact per eviction,
# but a pool-thrash step (one huge prefill clobbering hundreds of parked
# blocks) must not flood the bounded flight-recorder ring and displace
# the request-lifecycle events a post-mortem needs — evictions past the
# cap collapse into one prefix_cache_eviction_burst summary event.
_EVICT_EVENTS_PER_STEP = 8


_AHEAD_SETTLES_HELP = (
    "steps of the serving loop that read the launch in flight before "
    "planning instead of running ahead of it, by the rule that made them "
    f"({', '.join(r for r in SETTLE_REASONS if r != SETTLE_FAMILY)}), and "
    "steps with decode rows of an engine that never leaves a launch in "
    f"flight ({SETTLE_FAMILY}: the unified step, bursts, mp > 1, or step "
    "programs whose outputs are committed to a device)")


# StepTimer series and collective-phase label of each program family
_STEP_TIMERS = {
    "prefill": ("prefill_step", "prefill"),
    "chunk": ("prefill_step", "prefill"),
    "decode": ("decode_step", "decode"),
    "ragged": ("unified_step", "ragged"),
    "burst": ("burst_step", "burst"),
}


@functools.partial(jax.jit, static_argnums=1)
def _pad_tokens(tokens, rows: int):
    """A launch's ``[bucket]`` tokens at the width of the engine's largest
    row bucket, so that :func:`_ids_program` compiles once a row bucket and
    not once a PAIR of them (81 pairs at 256 sequences took 8.8 s on the
    chip).  A launch of the largest bucket needs none."""
    return jnp.zeros((rows,), tokens.dtype).at[:tokens.shape[0]].set(tokens)


@jax.jit
def _ids_program(prev_tokens, src, host_ids):
    """The input ids of a decode launch built while the launch before it
    is in flight: row ``i`` takes row ``src[i]`` of that launch's int32
    tokens, on the device, or where ``src[i]`` is -1 the token the host
    knows, ``host_ids[i]``.  A program of its own, OUTSIDE the decode step
    program (which keeps its arguments and sees an ``ids`` of the shape
    and dtype the host array has), compiled before the serving loop
    starts (``EngineCore.warm_ahead``)."""
    took = jnp.take(prev_tokens, jnp.maximum(src, 0))
    return jnp.where(src >= 0, took.astype(host_ids.dtype),
                     host_ids)[:, None]


@dataclass(slots=True)
class _Flight:
    """One step-program launch between :meth:`EngineCore._dispatch` and
    :meth:`EngineCore._collect`: what the program returned, still on the
    device, and what the second half needs to finish it."""

    program: str
    bucket: tuple
    seq: int                # the launch's number: rides both of its phases
    timer: StepTimer
    toks: object
    logits: object
    stats: object
    sent: tuple             # what each of the model's telemetry sent
    nbytes: int             # what the host copies asked for at dispatch hold
    audit: bool
    shadow: bool
    traced: bool            # a trace counter moved during the step call


@dataclass(slots=True)
class _DecodeLaunch:
    """One decode launch as the engine built it: its rows in order, the
    step program's arguments after the pools (``ids, pos, tables, lens,
    slot_blocks, slot_offsets`` and the sampling quartet), and, once
    dispatched, its :class:`_Flight`."""

    reqs: List[Request]
    rids: tuple
    bucket: tuple
    width: int
    args: tuple
    pre_pools: object
    flight: Optional[_Flight] = None

    @property
    def rows(self) -> int:
        return len(self.reqs)


@dataclass
class EngineConfig:
    """Engine-level deployment knobs (the config plumb-through of ISSUE 5).

    ``EngineCore(model, config=EngineConfig(...))`` is the one-object
    form; the legacy keyword arguments remain and are folded into one of
    these when no config is passed.
    """

    num_blocks: int = 256
    block_size: int = 16
    dtype: object = None              # pool dtype; None = jnp.float32
    # Automatic prefix caching over the block pool.  REFUSED, by name, for
    # a model whose layers declare per-sequence recurrent state
    # (CacheSpec.state): a state cannot be forked from a block prefix, so
    # such a model is built with prefix_cache=False (as are unified_step,
    # burst_steps, spec, role != "unified", mp > 1, audit and aot for it).
    prefix_cache: bool = True
    profile_ops: bool = False
    scheduler: Optional[SchedulerConfig] = None
    # Pallas paged-decode routing (ROADMAP serving follow-up (b)): None =
    # auto dispatch (kernel when TPU-tileable), True = force the kernel
    # (interpret mode off-TPU — the smoke-test path), False = force the
    # XLA gather path.  The on-chip A/B is now a config flip.
    use_pallas_paged: Optional[bool] = None
    # Expected tensor-parallel degree.  None = use whatever ``mp`` axis
    # the global mesh has (1 when no mesh).  An explicit value that does
    # not match the live mesh raises at engine build — a misconfigured
    # deployment fails loudly instead of silently serving single-chip.
    mp: Optional[int] = None
    # Request-lifecycle tracing (ISSUE 8): per-request bounded event
    # timelines (admission, routing handoff, prefill chunks, sampled
    # decode ITL, preemption, finish), queryable via the serving debug
    # endpoints and exportable as per-request chrome traces.  Off =
    # zero per-event work on the hot path.
    lifecycle_events: bool = True
    # share a tracker across engines (the fleet router rebinds replicas
    # onto ONE tracker so router + engine events land in one timeline);
    # None = the engine builds its own on its metrics registry
    lifecycle: Optional[LifecycleTracker] = None
    # record every Nth decode-token EVENT on the timeline (aggregates
    # and the ITL histograms see every token regardless; sampled-out
    # tokens also skip the flight-ring fan-out, so this knob bounds the
    # per-token cost on the decode hot path); 0 = none
    decode_event_sample: int = 8
    # Step-level performance introspection (ISSUE 9): per-program/bucket
    # utilization + padding-waste metrics, compile-time attribution, and
    # on-demand capture windows (StepProfiler).  Default on — O(1)
    # aggregates per program launch, spans only while a capture window
    # is armed; False keeps /metrics free of every serving_step_* /
    # serving_compile_* / serving_padding_* series.
    step_profile: bool = True
    # Online numerics auditing (ISSUE 10): NaN/Inf sentinel + logit-
    # stats telemetry on every step-program launch, and shadow-oracle
    # differential re-execution of sampled decode steps through the XLA
    # gather reference (single-shard replicated re-run under mp>1),
    # with size-capped .npz repro bundles on divergence.  None/default
    # = disabled: zero serving_audit_*/serving_logit_* series on
    # /metrics and no host-side audit work (the in-trace logit stats
    # are computed unconditionally, so audit on vs off is the SAME
    # compiled program — trace counts provably unchanged).  What a
    # launch copies to the host differs: the tokens alone with the audit
    # off; with it on the stats too, and the logits' real rows on a
    # decode / ragged launch of a sampled step (EngineCore._launch).
    audit: Optional[AuditConfig] = None
    # KV-cache & memory observability (ISSUE 13): per-step pool-timeline
    # sampling (free/reuse/allocated block counts with the exact
    # free+reuse+allocated == num_blocks invariant asserted every
    # sample), prefix-heat analytics over the chain hashes, reuse-LRU
    # hit-depth / park-lifetime telemetry, and per-request cache
    # attribution — all host-side (CacheStatTracker), so on vs off is
    # provably the same compiled program.  Served at /v1/debug/cache.
    cache_stats: bool = True
    # Metrics history + alerting (ISSUE 14): each engine step ticks the
    # fleet's HistoryStore sampler (bounded per-series rings over the
    # shared registry; the AlertEngine evaluates its threshold / rate /
    # SLO burn-rate rules after every sample).  Host-side only, like
    # cache_stats — on vs off is provably the same compiled program.
    # The store itself is owned by the FleetRouter (one fleet-wide
    # history at dp>1); this gate controls whether THIS engine ticks it.
    history: bool = True
    # Unified ragged step program (ISSUE 11): every engine step runs ONE
    # packed ragged launch (ops/ragged_paged.py) serving mixed prefill
    # chunks and decode rows together, instead of picking from the three
    # legacy program families (one-shot prefill / chunked prefill /
    # decode).  The bucket set collapses to (total-token, table-width)
    # pairs — strictly fewer traces — and at mp>1 the Pallas fast path
    # runs mesh-spanning through shard_map instead of being auto-pinned
    # off.  Default off this PR; token-identical to the legacy dispatch
    # under greedy decoding (tested).
    unified_step: bool = False
    # AOT serving artifacts (ISSUE 15): serve from a pre-lowered
    # program set instead of tracing at runtime.  ``aot_path`` loads a
    # saved :class:`~paddle_tpu.serving.aot.AotArtifact` directory at
    # engine build; ``aot`` binds an already-loaded artifact OBJECT and
    # wins over the path — a dp fleet (and the supervisor's replica
    # rebuilds) must share ONE loaded artifact so each program compiles
    # once fleet-wide.  Any manifest mismatch (mp degree, bucket set,
    # model hash, jax version, ...) fails loudly at build, and the
    # in-trace retrace counters provably stay 0 while serving (a bucket
    # outside the saved universe raises AotBucketMissing instead of
    # silently retracing).
    aot_path: Optional[str] = None
    aot: Optional[object] = None
    # Speculative decoding (ISSUE 18): a host-side n-gram proposer
    # drafts k tokens per decode-resident request and the engine packs
    # them as short verify chunks into the SAME unified ragged bucket
    # lattice (no new program family, no new bucket axes) — accepted
    # runs deliver multiple tokens per engine step.  Requires
    # ``unified_step=True`` and a ``max_tokens_per_step`` budget (draft
    # tokens compete for the step's leftover budget).  None = off;
    # greedy spec-decode is token-identical to baseline (bench-gated).
    spec: Optional[object] = None  # serving.spec.SpecConfig
    # Device-resident decode bursts (ISSUE 19): when the running set is
    # a decode-only resident cohort (no pending admissions, prefill
    # continuations, or spec drafts), launch ONE compiled program that
    # runs up to this many decode steps on-device (in-trace KV slot
    # append, per-row position advance, fused sampling, per-row EOS
    # masking) — only the ``[B, N]`` token buffer crosses back to the
    # host.  The launch clamp (serving/burst.py) shrinks N below this
    # cap per launch; 0/1 = off (per-step decode).  Burst-on is
    # token-identical to burst-off for greedy AND sampled rows (the
    # draw keys advance in-trace along the same output positions).
    burst_steps: int = 0
    # Prefill/decode disaggregation (ISSUE 20): the replica's ROLE in a
    # role-aware fleet.  Pure routing policy — any engine can execute
    # anything (the unified fallback depends on that), so role is NOT
    # part of the fleet's homogeneity gates.  ``prefill`` specialists
    # take admissions and compute prompt KV; at the first-token boundary
    # the router migrates the request plus its computed KV blocks to a
    # ``decode`` specialist (serving/handoff.py); ``unified`` replicas
    # do both (the default, and the single-replica fallback).
    role: str = "unified"


class EngineCore:
    """Continuous-batching engine over one causal-LM model.

    High-level loop: ``add_request`` enqueues; each ``step()`` asks the
    scheduler for a plan (decode-slot reservation with preemption, then
    admission), runs at most one bucketed prefill program and one bucketed
    decode program, samples on the host with each request's own RNG
    stream, and retires finished requests.  ``stream()`` exposes a
    per-request generator that drives ``step()`` on demand.

    Construction: pass ``config=EngineConfig(...)`` (the one-object form
    — it then WINS over the legacy keyword arguments) or the individual
    keywords, which are folded into an :class:`EngineConfig`
    (``self.engine_config``).  ``self.mp`` is the resolved
    tensor-parallel degree (1 single-chip).
    """

    def __init__(self, model, num_blocks: int = 256, block_size: int = 16,
                 dtype=jnp.float32, scheduler_config: Optional[SchedulerConfig] = None,
                 profile_ops: bool = False, registry=None,
                 prefix_cache: bool = True,
                 config: Optional[EngineConfig] = None,
                 use_pallas_paged: Optional[bool] = None,
                 metrics_labels: Optional[Dict[str, str]] = None):
        if config is None:
            config = EngineConfig(
                num_blocks=num_blocks, block_size=block_size, dtype=dtype,
                prefix_cache=prefix_cache, profile_ops=profile_ops,
                scheduler=scheduler_config, use_pallas_paged=use_pallas_paged)
        self.engine_config = config
        if config.role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"EngineConfig.role must be 'unified', 'prefill' or "
                f"'decode'; got {config.role!r}")
        num_blocks, block_size = config.num_blocks, config.block_size
        dtype = config.dtype if config.dtype is not None else jnp.float32
        cfg = model.config
        self.model = model
        # --- what each layer keeps, as the model declares it (ROADMAP D4) ---
        # per TOKEN (pages) and per SEQUENCE (slots): the pools, the prefill
        # buffers, the mp check and the mesh shardings below all go by this
        # and never by head counts
        self.cache_specs = list(model.cache_specs())
        sched_cfg = config.scheduler or SchedulerConfig()
        self._has_state = any(spec.state for spec in self.cache_specs)
        self._refuse_state_paths(config)
        # a slot a running sequence: the running set is capped at
        # max_num_seqs, so admission never waits on a slot it cannot get
        self.state_slots = sched_cfg.max_num_seqs if self._has_state else 0
        self.kv = KVCacheManager(num_blocks, block_size,
                                 enable_prefix_cache=config.prefix_cache,
                                 state_slots=self.state_slots)
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.scheduler = ContinuousBatchingScheduler(sched_cfg, self.kv)
        # the table width of a decode launch: its rows' widest table in
        # power-of-two buckets -- but ONE width for a model with
        # ring-and-rows layers (``CacheSpec.tokens_per_row``), the one its
        # positions reach.  Their decode step reads the whole pool of rows
        # where it lies and the tables only say who holds what, so a
        # narrower table saves no read and every width is one more
        # program to compile.  No sequence outgrows that width.  Public:
        # ``aot.enumerate_buckets`` lists the decode programs by it
        self.decode_table_width = None
        if any(spec.ring_and_rows for spec in self.cache_specs):
            reach = min(int(cfg.max_position_embeddings),
                        (num_blocks - 1) * block_size)
            self._cap_seq_len(reach, "max_position_embeddings of a model "
                              "whose decode tables have one width")
            self.decode_table_width = bucket_size(-(-reach // block_size))
        # registry=None keeps counts per-engine; pass
        # observability.get_registry() to publish serving series on the
        # process-wide Prometheus page next to the jit compile counters.
        # metrics_labels (e.g. {"replica": "0"}) lets N fleet replicas
        # share ONE registry with per-replica-labeled serving series.
        self.metrics = ServingMetrics(registry=registry,
                                      labels=metrics_labels)
        self.tracer = self.metrics.tracer
        # in-trace sampling counters (ISSUE 18): every emitted token now
        # comes off the device already sampled; these attribute them to
        # the greedy vs sampled row kinds
        self._sampling_counters = _register_sampling_metrics(
            self.metrics.registry)
        # --- step-level introspection (ISSUE 9) ----------------------------
        # bucket-utilization/padding accounting + compile attribution +
        # capture windows, on the same registry (replica-labeled under a
        # fleet); disabled = the registry never sees a serving_step_*
        # series and every hook below is a cheap early-return
        self.stepprof = StepProfiler(registry=self.metrics.registry,
                                     labels=metrics_labels,
                                     enabled=config.step_profile)
        self.metrics.attach_step_profiler(self.stepprof)
        # --- KV-cache & memory observability (ISSUE 13) --------------------
        # pool timeline + prefix heat + reuse-LRU telemetry + per-request
        # attribution; the pool's event-driven hooks below feed it AND
        # the legacy prefix_cache_evictions counter / lifecycle event
        # (which are no longer lag-batched per step)
        self.cachestat = CacheStatTracker(self.kv,
                                          registry=self.metrics.registry,
                                          labels=metrics_labels,
                                          enabled=config.cache_stats)
        self._evict_events_step = 0  # per-step lifecycle-event budget
        self.kv.on_evict = self._on_pool_evict
        self.kv.on_revive = self._on_pool_revive
        # --- online numerics auditing (ISSUE 10) ---------------------------
        # NaN/Inf sentinel + logit telemetry on every launch, shadow-
        # oracle re-execution of sampled decode steps; the fleet router
        # binds it to the flight recorder keyed by replica index
        self.audit = NumericsAuditor(self, config=config.audit,
                                     registry=self.metrics.registry,
                                     labels=metrics_labels)
        # --- request-lifecycle tracing (ISSUE 8) ---------------------------
        # the fleet router rebinds all replicas onto ONE tracker via
        # set_lifecycle() so router + engine events share a timeline
        self._replica_label = (metrics_labels or {}).get("replica", "0")
        self._lifecycle_on = config.lifecycle_events
        if config.lifecycle is not None:
            self.lifecycle = config.lifecycle
        else:
            self.lifecycle = LifecycleTracker(
                registry=self.metrics.registry,
                enabled=config.lifecycle_events,
                decode_sample=config.decode_event_sample)
        self.requests: Dict[object, Request] = {}
        self._pool_dtype = jnp.dtype(dtype)
        # deterministic fault injection (ISSUE 12): the fleet router
        # binds a per-replica FaultInjector; step_seq is the injector's
        # deterministic clock (counts step() invocations, no wall time)
        self.step_seq = 0
        self._fault = None
        # metrics history (ISSUE 14): the fleet router binds ONE
        # HistoryStore across all replicas via set_history; each step
        # ticks it (gated by EngineConfig.history)
        self.history = None
        # --- tensor-parallel resolution (ISSUE 5) ---------------------------
        mesh = topology.get_mesh()
        from ..parallel.utils import axis_size

        self.mp = axis_size("mp")
        if config.mp is not None and config.mp != self.mp:
            raise ValueError(
                f"EngineConfig.mp={config.mp} but the global mesh has "
                f"mp={self.mp}; call distributed.topology.init_mesh(mp=...) "
                "before building the engine")
        self._unified = bool(config.unified_step)
        self._refuse_latent_paths(config)
        self._use_pallas = config.use_pallas_paged
        # the unified ragged program keeps its own routing: its Pallas
        # kernel is expressed through shard_map over the mp axis, so it
        # is NEVER subject to the legacy single-shard pin below
        self._use_pallas_ragged = config.use_pallas_paged
        if self.mp > 1:
            kv_heads = sorted({spec.k[0] for spec in self.cache_specs
                               if spec.k})
            if any(h % self.mp for h in kv_heads) or \
                    cfg.num_attention_heads % self.mp:
                raise ValueError(
                    f"mp={self.mp} must divide num_key_value_heads="
                    f"{kv_heads[0]} and num_attention_heads="
                    f"{cfg.num_attention_heads} (the KV pools shard along "
                    "the head dim)")
            if self._use_pallas and not self._unified:
                # the ONLY remaining mp>1 kernel restriction (ISSUE 11
                # lifted the silent auto-pin): forcing the LEGACY
                # single-shard decode kernel into a mesh program fails
                # loudly instead of being quietly overridden
                raise ValueError(
                    "use_pallas_paged=True at mp>1 requires "
                    "unified_step=True: the legacy decode kernel is "
                    "single-shard — the unified ragged program runs the "
                    "kernel mesh-spanning via shard_map, or drop the "
                    "force to use the XLA gather path")
            self._use_pallas = False  # legacy three-family programs pin
            # the XLA path inside the mesh program (single-shard kernel);
            # self._use_pallas_ragged keeps the configured routing — the
            # shard_map ragged kernel IS the mp fast path
            from ..parallel.utils import apply_param_shardings

            # place every annotated parameter (column/row/vocab-parallel
            # specs from parallel/mp_layers.py) onto the mesh shard-wise
            apply_param_shardings(model, mesh)
        self.metrics.set_mp_shards(self.mp)
        def pool(row, kind):
            # a layer that keeps nothing on this side holds an empty array
            if row is None:
                return jnp.zeros((0,), dtype)
            if kind == "latent":
                # rows in whole lane tiles, so that the array lies
                # row-major and a page is read and written where it lies
                return jnp.zeros(_paged_ops.latent_pool_shape(
                    num_blocks, block_size, row), dtype)
            return shard_kv_pool(
                jnp.zeros((num_blocks, block_size) + tuple(row), dtype))

        def slots(side):
            # per-sequence state: a slot a running sequence and the null
            # slot 0, in the layer's entry of that side of the pools
            shape, slot_dtype = side
            return jnp.zeros((self.state_slots + 1,) + tuple(shape),
                             jnp.dtype(slot_dtype or dtype))

        def rows(spec, row):
            # a row every ``tokens_per_row`` tokens, in the sequence's blocks
            return jnp.zeros((num_blocks, spec.rows_per_block(block_size))
                             + tuple(row), dtype)

        def side(spec, i):
            # a layer's entry of one side of the pools, by what it declared
            row = (spec.k, spec.v)[i]
            if spec.ring_and_rows:
                return slots(spec.state[i]), rows(spec, row)
            return slots(spec.state[i]) if spec.state \
                else pool(row, spec.kind)

        self._k_pools = tuple(side(spec, 0) for spec in self.cache_specs)
        self._v_pools = tuple(side(spec, 1) for spec in self.cache_specs)
        self.metrics.registry.gauge(
            "serving_kv_bytes_per_token",
            help="bytes one cached token holds over all layers, as the "
                 "model declares its cache",
            **self.metrics.labels).set(
            sum(spec.values_per_token() for spec in self.cache_specs)
            * jnp.dtype(dtype).itemsize)
        # what the model's layers bring to a launch beside their caches
        # (``ops.paged_attention.LaunchTelemetry``), and what it may read
        self._view = LaunchView(self.metrics.registry, self.metrics.labels,
                                self.kv, jnp.dtype(dtype))
        self._telemetry = model.launch_telemetry(self._view)
        self._params = list(model.parameters())
        # retrace counters: += 1 runs only while JAX traces the function,
        # so these count COMPILATIONS, not calls (the N31 acceptance hook)
        self.decode_trace_count = 0
        self.prefill_trace_count = 0
        self.ragged_trace_count = 0
        self.burst_trace_count = 0
        # which attention path each program family was traced through
        # ("pallas" | "xla"), read off the ops modules' ``last_path``
        # while THIS engine traces — the modules' globals are overwritten
        # by any later trace (the auditor's gather reference, another
        # replica), this record is not
        self.attention_paths: Dict[str, str] = {}
        # decode bucket -> pages of a row its paged kernel moves a step
        # (``ops.pallas_paged.kernel_pages``), written where the program
        # is traced; rides ``engine.dispatch`` as ``pages_per_step``
        self._kernel_pages: Dict[tuple, int] = {}
        # (program, *bucket) of a prefill or chunk program -> query rows a
        # grid step of its ``pallas_flash.flash_prefill`` takes, written
        # where the program is traced (:meth:`_note_flash_prefill`); rides
        # ``engine.dispatch`` as ``flash_block_q``
        self._flash_rows: Dict[tuple, int] = {}
        self.decode_buckets = set()
        self.prefill_buckets = set()
        self.ragged_buckets = set()
        self.burst_buckets = set()
        # --- device-resident decode bursts (ISSUE 19) -----------------------
        # the burst program's block-table width is pinned to ONE value
        # (the full pool's width bucket; bind_aot narrows it to the
        # artifact's max_seq_len) so the burst lattice stays two-axis —
        # (rows bucket, burst-length bucket) — with no mid-burst width
        # drift as rows cross block boundaries
        self._burst_steps = max(0, int(config.burst_steps or 0))
        self._burst_width = bucket_size(max(1, num_blocks - 1))
        self._burst_counters = _register_burst_metrics(
            self.metrics.registry, labels=self.metrics.labels)
        donate = (1, 2) if jax.default_backend() == "tpu" else ()
        if self.mp > 1:
            jit_kw = self._mesh_jit_shardings(mesh, cfg)
        else:
            jit_kw = {"decode": {}, "prefill": {}, "chunk": {},
                      "ragged": {}, "burst": {}}
        self._jit_decode = jax.jit(self._decode_fn, donate_argnums=donate,
                                   **jit_kw["decode"])
        self._jit_prefill = jax.jit(self._prefill_fn, donate_argnums=donate,
                                    **jit_kw["prefill"])
        self._jit_chunk_prefill = jax.jit(self._chunk_prefill_fn,
                                          donate_argnums=donate,
                                          **jit_kw["chunk"])
        self._jit_unified = jax.jit(self._unified_fn, donate_argnums=donate,
                                    **jit_kw["ragged"])
        self._jit_burst = jax.jit(self._burst_fn, donate_argnums=donate,
                                  **jit_kw["burst"])
        self._profile_ops = config.profile_ops
        # --- running ahead of the read (ISSUE 35) ---------------------------
        # the serving loop's decode launch may stay on the device while
        # the next one is planned, built and dispatched (step_ahead): the
        # launch in flight, when the last launch was seen to end, the
        # largest row bucket (the width the small programs that hand a
        # launch's tokens to the next are compiled at: _ids_program), and
        # how often it engages.
        # The families with no such path never leave a launch in flight.
        self._inflight: Optional[_DecodeLaunch] = None
        self._last_ready = 0.0
        self._launch_seq = 0
        self._top_rows = bucket_size(sched_cfg.max_num_seqs)
        self._flies = not (self._unified or self._burst_steps >= 2
                           or self.mp > 1)
        reg, labels = self.metrics.registry, self.metrics.labels
        self._ahead_counters = {
            "launches": reg.counter(
                "serving_ahead_launches_total",
                help="decode launches dispatched before the tokens of the "
                     "launch before them were read", **labels),
            "dropped_rows": reg.counter(
                "serving_ahead_dropped_rows_total",
                help="rows of a launch that ran ahead whose request had "
                     "ended (an EOS token, an abort) by the time its "
                     "tokens were read: nothing was emitted for them",
                **labels),
            "settles": {},      # reason -> counter, made on first use
        }
        if self._unified and jax.default_backend() == "tpu" \
                and config.use_pallas_paged is not False:
            self._cap_ragged_context()
        model.eval()
        # --- speculative decoding (ISSUE 18) --------------------------------
        # host-side n-gram proposer + verify-row bookkeeping; packs draft
        # tokens into the unified ragged program as short verify chunks,
        # so spec on vs off is the SAME program family and bucket lattice
        self.spec = None
        if config.spec is not None and \
                getattr(config.spec, "enabled", True):
            if not self._unified:
                raise ValueError(
                    "EngineConfig.spec requires unified_step=True: draft "
                    "verification packs into the unified ragged program "
                    "(there is no legacy-family verify path)")
            sched_cfg = self.scheduler.config
            if sched_cfg.max_tokens_per_step is None:
                raise ValueError(
                    "EngineConfig.spec requires "
                    "SchedulerConfig.max_tokens_per_step: draft tokens "
                    "compete for the step's leftover token budget — an "
                    "unbounded budget would unbound the packed bucket")
            from .spec import SpecDecoder

            self.spec = SpecDecoder(config.spec,
                                    registry=self.metrics.registry,
                                    labels=metrics_labels)
        # --- AOT serving artifacts (ISSUE 15) -------------------------------
        # bound LAST: validate() compares against the fully-resolved
        # engine (mp, pools, unified flag).  A pre-loaded artifact
        # object (config.aot — the fleet-sharing form) wins over a path.
        self._aot = None
        art = config.aot
        if art is None and config.aot_path:
            from .aot import AotArtifact

            art = AotArtifact.load(config.aot_path)
        if art is not None:
            self.bind_aot(art)

    # --- AOT artifact binding ----------------------------------------------
    @property
    def aot_artifact(self):
        """The bound :class:`~paddle_tpu.serving.aot.AotArtifact`, or
        ``None`` when this engine traces at runtime."""
        return self._aot

    def bind_aot(self, artifact, record_load: bool = True) -> None:
        """Validate + bind an AOT artifact: every step program now
        dispatches through the artifact's pre-lowered StableHLO instead
        of the engine's jit entry points — the retrace counters can
        never move again.  The supervisor calls this on rebuilt replicas
        (:meth:`FleetSupervisor._rebuild`, with ``record_load=False`` —
        a rebind reuses an already-loaded artifact, so the load
        histogram must not re-observe a disk load that never happened).
        Raises :class:`~paddle_tpu.serving.aot.AotManifestMismatch` on
        any deployment disagreement."""
        artifact.validate(self)
        self._aot = artifact
        # admission-side guard (the loud backstop stays in
        # AotArtifact.call): a request whose target length outgrows the
        # saved universe is rejected honestly at admission instead of
        # raising AotBucketMissing from the engine thread mid-stream
        self._cap_seq_len(
            int(artifact.manifest["max_seq_len"]),
            "the AOT artifact was saved for max_seq_len="
            f"{artifact.manifest['max_seq_len']}; re-save with a larger "
            "bound")
        # burst programs (ISSUE 19) were exported with the table width
        # derived from the artifact's max_seq_len; the seq_len_cap set
        # above guarantees no admitted sequence can outgrow it, so the
        # launch-side arrays must build at the SAME width
        cap = self.scheduler.seq_len_cap
        self._burst_width = bucket_size(
            max(1, (cap + self.block_size - 1) // self.block_size))
        # AOT attribution (ISSUE 15 satellite): /v1/debug/compiles and
        # /metrics must show "loaded an artifact" instead of fake
        # compile rows — and flag any later trace as the bug it is.
        # ONE disk load = ONE serving_aot_load_seconds sample per
        # registry: the artifact dedups binds of the same loaded object
        # (dp replicas, rebuild factories that thread it through)
        sp = self.stepprof
        observe = record_load
        if observe and sp.enabled and sp.registry is not None:
            observe = artifact.mark_load_observed(sp.registry)
        sp.record_aot_load(artifact.load_seconds,
                           artifact.program_count, observe=observe)

    def _refuse_latent_paths(self, config: "EngineConfig") -> None:
        """A model that declares a LATENT cache (one row a token shared by
        all heads, nothing in ``v_pools``) is served by the prefill, chunk
        / resume and decode programs.  The paths that move or shard
        ``(heads, dim)`` keys and values have no latent form yet: refuse
        them here, by name, rather than run a wrong one."""
        if not any(spec.kind == "latent" for spec in self.cache_specs):
            return
        refused = []
        if config.unified_step:
            refused.append("unified_step (the unified ragged program)")
        if int(config.burst_steps or 0) >= 2:
            refused.append("burst_steps (device-resident decode bursts)")
        if config.spec is not None and getattr(config.spec, "enabled", True):
            refused.append("spec (speculative verify)")
        if config.role != "unified":
            refused.append(f"role={config.role!r} (KV hand-off)")
        if self.mp > 1:
            refused.append(f"mp={self.mp} (head-sharded pools)")
        if config.use_pallas_paged:
            refused.append("use_pallas_paged=True (the paged decode "
                           "kernel reads keys and values)")
        if refused:
            raise ValueError(
                "this model declares a latent KV cache; EngineCore has no "
                "latent path for: " + "; ".join(refused))

    def _refuse_state_paths(self, config: "EngineConfig") -> None:
        """A model with layers that declare per-SEQUENCE state
        (``CacheSpec.state``: a recurrence's state after the last token)
        is served by the prefill, chunk and decode programs, with
        preemption by recompute.  Such state cannot be forked from a block
        prefix, rolled back a token, or moved with pages; the paths that
        would need to have no form for it yet and are refused here, by
        name, rather than run a wrong one.  It goes by the declaration, not
        by a layout name."""
        if not self._has_state:
            return
        from ..parallel.utils import axis_size

        refused = []
        if config.prefix_cache:
            refused.append("prefix_cache (a recurrent state cannot be forked "
                           "from a block prefix; pass prefix_cache=False)")
        if config.unified_step:
            refused.append("unified_step (the unified ragged program)")
        if int(config.burst_steps or 0) >= 2:
            refused.append("burst_steps (device-resident decode bursts)")
        if config.spec is not None and getattr(config.spec, "enabled", True):
            refused.append("spec (speculative verify: no rollback of a "
                           "state)")
        if config.role != "unified":
            refused.append(f"role={config.role!r} (KV hand-off moves pages, "
                           "not slots)")
        mp = axis_size("mp")        # self.mp is set after the pools' manager
        if mp > 1:
            refused.append(f"mp={mp} (slot pools are not sharded)")
        if config.audit is not None and config.audit.enabled:
            refused.append("audit (the shadow re-execution builds a paged "
                           "cache a layer and snapshots every pool)")
        if config.aot is not None or config.aot_path:
            refused.append("aot / aot_path (no artifact was ever saved with "
                           "slot pools)")
        if refused:
            raise ValueError(
                "this model declares per-sequence recurrent state; "
                "EngineCore has no path with such state for: "
                + "; ".join(refused))

    def _build_ints(self, program: str, rows: int, reqs) -> Dict[str, int]:
        """What ``engine.build`` of a ``program`` launch of ``rows`` real
        rows carries for the model's telemetry."""
        self._view.program = program
        ints = {}
        for t in self._telemetry:
            ints.update(t.build_ints(self._view, rows, reqs))
        return ints

    def _layer_caches(self, k_pools, v_pools, *routing, **spans):
        """One cache object a layer for a step program, of the class the
        layer declared (``CacheSpec.cache``), routed by the launch's
        ``tables, lens, slot_blocks, slot_offsets`` (, ``start, n_valid``)."""
        caches = []
        for spec, k, v in zip(self.cache_specs, k_pools, v_pools):
            c = spec.cache.over(k, v)
            c.use_pallas = self._use_pallas  # EngineConfig.use_pallas_paged
            c.route(*routing, **spans)
            caches.append(c)
        return caches

    def _cap_seq_len(self, cap: int, why: str) -> None:
        """Lower the admission cap on prompt + max_new_tokens (never
        raise it: the tightest limit and its reason win)."""
        sched = self.scheduler
        if sched.seq_len_cap is None or cap < sched.seq_len_cap:
            sched.seq_len_cap, sched.seq_len_cap_why = cap, why

    def _cap_ragged_context(self) -> None:
        """Unified step on a TPU: the ragged kernel prefetches one
        block-table row per packed token into scalar memory
        (``ops/ragged_paged.py``), so token bucket x table width is
        bounded by it.  Refuse at build what no launch could take, and
        reject at admission any request whose context outgrows the
        widest table that fits — never at launch, where the error would
        take the engine thread with it."""
        sched = self.scheduler.config
        limit = ("the ragged kernel keeps one block-table row per packed "
                 "token in TPU scalar memory (ROADMAP S4 regrids it)")
        if sched.max_tokens_per_step is None:
            raise ValueError(
                "unified_step=True on a TPU needs SchedulerConfig."
                f"max_tokens_per_step: {limit}, so the packed token "
                "bucket must be bounded")
        tokens = bucket_size(max(sched.max_tokens_per_step,
                                 sched.max_num_seqs))
        width = _ragged_ops.max_table_width(tokens)
        if not width:
            raise ValueError(
                f"unified_step=True on a TPU: max_tokens_per_step="
                f"{sched.max_tokens_per_step} packs {tokens} tokens a "
                f"step, more than fit — {limit}")
        self._cap_seq_len(
            width * self.block_size,
            f"at {tokens} packed tokens a step this engine serves "
            f"contexts up to {width * self.block_size} tokens "
            f"({width} blocks of {self.block_size}): {limit}")

    def _step_call(self, program: str, bucket, jit_fn, *args):
        """THE aot-vs-jit dispatch choice, shared by all five step
        program families: serve from the bound artifact (counting the
        hit) or fall back to the engine's jit entry point.  Every call
        is exactly one host->device round trip — the denominator of the
        burst saving (ISSUE 19), counted here so per-step and burst
        launches share one ledger."""
        self._burst_counters["roundtrips"].inc()
        if self._aot is None:
            return jit_fn(*args)
        out = self._aot.call(program, bucket, *args)
        self.stepprof.record_aot_hit(program)
        return out

    def _count_launch(self, pack: SamplingPack) -> None:
        """Count the launch ``pack`` was built for by the branch its
        in-trace sampler will take: the sort, the masks and the draw run
        only where a row samples (``ops.sampling.sample_tokens``)."""
        kind = "sampling" if pack.any_sampling() else "greedy"
        self._sampling_counters[f"{kind}_launches"].inc()

    def _launch(self, program: str, bucket, jit_fn, args, rows: int):
        """One step-program launch run to its end, shared by all five
        families: :meth:`_dispatch`, then :meth:`_collect` at once.
        Returns ``(tokens, logits, stats, wall seconds)``."""
        return self._collect(
            self._dispatch(program, bucket, jit_fn, args, rows))

    def _dispatch(self, program: str, bucket, jit_fn, args, rows: int,
                  ahead: bool = False) -> "_Flight":
        """The first half of a launch (``observability.tracer
        .STEP_PHASES``): ``engine.dispatch`` is the step call alone, until
        the jit call returns, and asks for the host copy of what the step
        will read and of nothing else.  That is the int32 tokens (a
        burst's ``[rows, steps]`` buffer), with the audit on also the
        three floats a row of ``stats``, and on a decode / ragged launch
        of a step the auditor's schedule samples the ``rows`` real rows of
        the float32 logits, sliced on the device before they cross
        (counted by ``serving_logits_fetches_total``).  ``ahead`` says the
        launch goes out before the tokens of the one before it were read;
        it rides the phase as an integer, as does the launch's number
        (``launch``), which its ``engine.device_wait`` carries too: the
        wait that follows a dispatch is no longer always its own.  A
        decode launch also carries ``pages_per_step``: the pages of a row
        the paged decode kernel of its program moves a step over the paged
        pool, as written where the program was traced (0: the gather path,
        an AOT-served program, a bucket's first call), and a prefill or
        chunk launch ``flash_block_q``: the query rows a grid step of its
        program's ``flash_prefill`` takes (0: the program holds the XLA
        form of the expanded latent attention, or none).  What comes back is
        the launch
        in flight: the device arrays, and what :meth:`_collect` needs to
        finish it, now or a step later."""
        timer, collective = _STEP_TIMERS[program]
        audit = self.audit.enabled and program in AUDIT_PROGRAMS
        shadow = self.audit.wants_logits(program)
        traces0 = self._traces()
        st = StepTimer(self.metrics, timer,
                       self._collective_phase(collective))
        st.__enter__()
        self._launch_seq += 1
        with self.tracer.phase("engine.dispatch", self.stepprof, rows=rows,
                               bucket=bucket[0], ahead=int(ahead),
                               launch=self._launch_seq,
                               pages_per_step=self._kernel_pages.get(
                                   tuple(bucket), 0)
                               if program == "decode" else 0,
                               flash_block_q=self._flash_rows.get(
                                   (program, *bucket), 0)):
            toks, logits, stats, self._k_pools, self._v_pools = \
                self._step_call(program, bucket, jit_fn,
                                self._param_vals(), self._k_pools,
                                self._v_pools, *args)
            sent = ()
            if self._telemetry:     # the sentinel, then what each sent
                stats, *sent = stats
            fetched = [toks]
            if audit:
                fetched.append(stats)
            if shadow:
                logits = logits[:rows]
                fetched.append(logits)
            for arr in fetched:
                arr.copy_to_host_async()
            for arr in sent:
                if arr is not None:
                    arr.copy_to_host_async()
        # a launch during which a trace counter moved IS that bucket's
        # trace+compile: its wall time goes to the compile table
        return _Flight(program, bucket, self._launch_seq, st, toks, logits,
                       stats, tuple(sent), sum(arr.nbytes for arr in fetched),
                       audit, shadow, self._traces() > traces0)

    def _traces(self) -> int:
        return (self.prefill_trace_count + self.decode_trace_count
                + self.ragged_trace_count + self.burst_trace_count)

    def _collect(self, fl: "_Flight"):
        """The second half of a launch: ``engine.device_wait`` waits on
        ITS tokens for ITS program to end (a launch dispatched after it
        may be running by then), ``engine.fetch`` makes the host arrays of
        what :meth:`_dispatch` asked for; its ``bytes`` are what it
        copied.  Returns ``(tokens, logits, stats, wall seconds)``:
        ``logits`` is the device array the program returned, ``[.., vocab]``
        with the bucket's padding rows, except on a launch the audit
        samples, where it is the host copy of the real rows.  A caller
        must not keep the device array past its step: it is ``rows x
        vocab`` float32 of device memory, free again once the step's frame
        drops it.  ``stats`` stays a device array with the audit off:
        nothing reads it.  The wall seconds run from the dispatch, or from
        when the launch before this one was seen to end where that is
        later: a launch that went out ahead spent the time before that in
        the device's queue."""
        phase, prof = self.tracer.phase, self.stepprof
        toks, logits, stats = fl.toks, fl.logits, fl.stats
        with phase("engine.device_wait", prof, launch=fl.seq):
            toks.block_until_ready()
            ints = {}
            for t, arr in zip(self._telemetry, fl.sent):
                ints.update(t.fetch_ints(fl.program, arr))
        fl.timer.start_no_earlier_than(self._last_ready)
        with phase("engine.fetch", prof, bytes=fl.nbytes, **ints):
            toks = np.asarray(toks, np.int32)
            if fl.audit:
                stats = np.asarray(stats, np.float32)
            if fl.shadow:
                logits = self._host_logits(logits)
        fl.timer.__exit__()
        self._last_ready = time.perf_counter()
        if fl.traced:
            self.stepprof.record_compile(fl.program, fl.bucket,
                                         fl.timer.dt)
        return toks, logits, stats, fl.timer.dt

    def _audit_logits(self, out, rows: int):
        """What the auditor gets as the logits of a decode / ragged
        launch (``out`` as ``_launch`` handed it back).  On a sampled
        step that is the host copy of the real rows, through the
        ``kernel_corrupt`` fault where one is planned; on any other a
        callable that fetches them, for the non-finite bundle alone."""
        if not self.audit.sampled:
            return lambda: self._host_logits(out[:rows])
        if self._fault is not None:
            out = self._fault.corrupt_logits(self.step_seq, out)
        return out

    def _host_logits(self, rows) -> np.ndarray:
        """Bring ``rows`` (a launch's logits, already cut to its real
        rows on the device) to the host, and count the copy."""
        host = np.asarray(rows, np.float32)
        self._burst_counters["logits_fetches"].inc()
        self._burst_counters["logits_fetch_bytes"].inc(host.nbytes)
        return host

    def _mesh_jit_shardings(self, mesh, cfg) -> Dict[str, dict]:
        """Explicit in/out shardings for the three mesh-spanning jitted
        programs: parameters per their fitted ``PartitionSpec``
        annotations, KV pools head-sharded over ``mp``, every routing
        array (ids, positions, tables, lens, slots) **replicated** — the
        host keeps one logical view and GSPMD splits the compute.  Being
        explicit (rather than letting propagation guess from committed
        inputs) keeps placement deterministic per bucket."""
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.utils import _fit_spec, param_spec

        repl = NamedSharding(mesh, PartitionSpec())
        kv = NamedSharding(mesh, PartitionSpec(*KV_POOL_SPEC))  # matches
        # shard_kv_pool's placement — same constant, cannot drift
        pools = tuple(kv for _ in self.cache_specs)
        params = tuple(
            NamedSharding(mesh, _fit_spec(param_spec(p), tuple(p.shape), mesh))
            for p in self._params)
        # sampled tokens + logits + audit logit-stats replicated, pools
        # stay sharded.  Every family takes 4 extra replicated inputs —
        # the per-row sampling quartet (temps, top_ks, top_ps, keys) the
        # in-trace sampler consumes (ISSUE 18).
        out = (repl, repl, repl, pools, pools)
        return {
            # (param_vals, k_pools, v_pools, ids, pos, tables, lens,
            #  slot_blocks, slot_offsets, temps, top_ks, top_ps, keys)
            "decode": {"in_shardings": (params, pools, pools) + (repl,) * 10,
                       "out_shardings": out},
            # (param_vals, k_pools, v_pools, ids, last_pos, blocks, offs,
            #  temps, top_ks, top_ps, keys)
            "prefill": {"in_shardings": (params, pools, pools) + (repl,) * 8,
                        "out_shardings": out},
            # (param_vals, k_pools, v_pools, ids, start, last_pos, tables,
            #  lens, slot_blocks, slot_offsets, temps, top_ks, top_ps,
            #  keys)
            "chunk": {"in_shardings": (params, pools, pools) + (repl,) * 11,
                      "out_shardings": out},
            # (param_vals, k_pools, v_pools, ids, pos, seg_ids, last_idx,
            #  tables, lens, slot_blocks, slot_offsets, temps, top_ks,
            #  top_ps, keys) — the unified ragged step (ISSUE 11):
            # packed routing metadata replicated, pools sharded; inside,
            # the ragged kernel re-partitions over mp via shard_map
            "ragged": {"in_shardings": (params, pools, pools) + (repl,) * 12,
                       "out_shardings": out},
            # (param_vals, k_pools, v_pools, ids, pos, tables, lens,
            #  slot_blocks, slot_offsets, n_steps, active, eos_ids,
            #  temps, top_ks, top_ps, keys) — the decode burst
            # (ISSUE 19): the same decode shape looped in-trace; all
            # routing (including the [B, Nb] per-iteration slot arrays
            # and the scalar trip count) replicated, pools sharded
            "burst": {"in_shardings": (params, pools, pools) + (repl,) * 13,
                      "out_shardings": out},
        }

    # --- functional model step (traced) ------------------------------------
    def _call_model(self, ids_val, caches, pos_val, param_vals):
        """Run the eager module under the current trace with parameters
        swapped to the traced ``param_vals`` (and restored after) — the
        same rebinding trick as ``train_batch_1f1b``'s head_apply, so the
        jitted step threads weights as arguments instead of baking them
        in as constants."""
        from .. import no_grad

        saved = [p._value for p in self._params]
        for p, v in zip(self._params, param_vals):
            p._value = v
        try:
            with no_grad():
                out = self.model(Tensor(ids_val), caches=caches,
                                 pos=Tensor(pos_val))
            return out._value
        finally:
            for p, v in zip(self._params, saved):
                p._value = v

    def _launch_stats(self, last):
        """The ``stats`` output of a step program: the numerics audit's
        logit sentinel, and what each telemetry sent to ride beside it."""
        stats = logit_stats(last)
        if not self._telemetry:
            return stats
        return (stats, *(t.traced() for t in self._telemetry))

    def _decode_fn(self, param_vals, k_pools, v_pools, ids, pos,
                   tables, lens, slot_blocks, slot_offsets,
                   temps, top_ks, top_ps, keys):
        """One batched decode step: write each sequence's token KV into
        its (block, offset) slot, attend through the block tables, sample
        each row's next token in-trace (ISSUE 18) and return tokens +
        last-position logits + updated pools.  Shapes fixed per bucket."""
        self.decode_trace_count += 1
        # host side-effects run only while JAX traces: these count
        # COMPILATIONS (bounded by the bucket sets), not calls
        self.metrics.count("decode_jit_traces")
        self.tracer.instant("decode_jit_trace", cat="jit",
                            batch=int(ids.shape[0]), **self._state_stat(k_pools),
                            table_width=int(tables.shape[1]))
        caches = self._layer_caches(k_pools, v_pools, tables, lens,
                                    slot_blocks, slot_offsets)
        logits = self._call_model(ids, caches, pos, param_vals)
        self.attention_paths["decode"] = _paged_ops.last_path
        pages = 0           # the gather path moves no pages a step
        if _paged_ops.last_path == "pallas":
            from ..ops.pallas_paged import kernel_pages, latent_kernel_pages
            # (a ring-and-rows layer's kernels walk no pages: tiles of
            # the pool where it lies, ``ops/pallas_eva.py``; ROADMAP D15)
            for pool, spec in zip(k_pools, self.cache_specs):
                if spec.k is not None and not spec.state:
                    pages = (latent_kernel_pages if spec.kind == "latent"
                             else kernel_pages)(pool, tables.shape[1])
                    break
        self._kernel_pages[tuple(tables.shape)] = pages
        last = logits[:, -1, :].astype(jnp.float32)
        # in-trace sampling epilogue (ISSUE 18): greedy rows (temp 0,
        # padding included) reduce to argmax inside the same program —
        # one compiled program serves greedy and sampled batches
        tokens = sample_tokens(last, temps, top_ks, top_ps, keys)
        # numerics-audit sentinel (ISSUE 10): tiny in-trace reductions
        # over the output logits ride the launch as one extra output —
        # computed unconditionally so audit on/off is the SAME program
        return (tokens, last, self._launch_stats(last),
                tuple(c.k_pool._value for c in caches),
                tuple(c.v_pool._value for c in caches))

    def warm_ahead(self) -> None:
        """Compile the two small programs that hand a launch's tokens to
        the next one, for every row bucket of this engine (a closed set:
        powers of two up to ``max_num_seqs``), so that no step of the
        serving loop ever does.  The loop's owner calls it before the loop
        serves anything (``fleet.EngineReplica.start``); an engine that
        never leaves a launch in flight compiles nothing."""
        if not self._flies:
            return
        for k in range(self._top_rows.bit_length()):
            rows = 1 << k
            ids = _ids_program(
                self._widest(jnp.zeros((rows,), jnp.int32)),
                np.full((rows,), -1, np.int32), np.zeros((rows,), np.int64))
        jax.block_until_ready(ids)

    def _widest(self, tokens):
        """A launch's tokens at the width of the largest row bucket, the
        one width :func:`_ids_program` reads them at."""
        if tokens.shape[0] == self._top_rows:
            return tokens
        return _pad_tokens(tokens, self._top_rows)

    def _burst_fn(self, param_vals, k_pools, v_pools, ids, pos, tables,
                  lens, slot_blocks, slot_offsets, n_steps, active,
                  eos_ids, temps, top_ks, top_ps, keys):
        """Device-resident decode burst (ISSUE 19): up to ``n_steps``
        chained decode steps in ONE program via
        :func:`~paddle_tpu.ops.decode_burst.run_burst` — each iteration
        is exactly the ``_decode_fn`` body (route → forward → fused
        sampling), with the sampled token fed straight back as the next
        input and only the ``[B, Nb]`` token buffer crossing to the
        host.  Output tuple matches the other families (tokens, last
        logits, logit stats, pools) so ``_step_call``/AOT dispatch is
        unchanged."""
        self.burst_trace_count += 1
        self.metrics.count("burst_jit_traces")
        self.tracer.instant("burst_jit_trace", cat="jit",
                            batch=int(ids.shape[0]),
                            burst_bucket=int(slot_blocks.shape[1]))

        def model_step(ids_j, pos_j, lens_j, sb, so, kp, vp):
            caches = self._layer_caches(kp, vp, tables, lens_j, sb, so)
            logits = self._call_model(ids_j, caches, pos_j, param_vals)
            self.attention_paths["burst"] = _paged_ops.last_path
            return (logits[:, -1, :].astype(jnp.float32),
                    tuple(c.k_pool._value for c in caches),
                    tuple(c.v_pool._value for c in caches))

        buf, last, k_out, v_out = run_burst(
            model_step, n_steps, self.model.config.vocab_size, ids, pos,
            lens, active, eos_ids, slot_blocks, slot_offsets, temps,
            top_ks, top_ps, keys, k_pools, v_pools)
        return buf, last, self._launch_stats(last), k_out, v_out

    def _note_flash_prefill(self, program: str, bucket) -> None:
        """Run where a prefill or chunk program is traced, after the
        model: which form of the expanded latent attention the program
        holds (``attention_paths``; nothing where the model has no such
        layer), and the query rows a grid step of its kernel takes."""
        path = _paged_ops.last_latent_prefill_path
        if path is not None:
            self.attention_paths[program] = path
        self._flash_rows[(program, *bucket)] = \
            _paged_ops.last_latent_prefill_block_q if path == "pallas" else 0

    def _prefill_fn(self, param_vals, k_pools, v_pools, ids, last_pos,
                    blocks, offs, temps, top_ks, top_ps, keys):
        """Bucketed prefill: dense-cache forward over the (padded) prompt,
        then scatter every layer's K/V into the sequence's pages.  Pad
        positions scatter into block 0 (the null page).  Returns the
        logits row of the LAST REAL token + updated pools."""
        self.prefill_trace_count += 1
        self.metrics.count("prefill_jit_traces")
        self.tracer.instant("prefill_jit_trace", cat="jit",
                            prompt_bucket=int(ids.shape[1]))
        Tb = ids.shape[1]

        def buffer(row):
            return None if row is None else Tensor(
                jnp.zeros((1, Tb) + tuple(row), self._pool_dtype))

        # a layer with per-sequence state writes it where it lies (the
        # state after the last REAL token, from zero); the blocks of the
        # prompt's tokens give the sequence's table
        table, n_valid = blocks[None, ::self.block_size], last_pos + 1
        dense = []
        for spec, kp, vp in zip(self.cache_specs, k_pools, v_pools):
            if spec.state:
                c = spec.cache.over(kp, vp)
                c.route(table, None, blocks, offs, n_valid=n_valid)
                dense.append(c)
            else:
                dense.append((buffer(spec.k), buffer(spec.v)))
        _paged_ops.last_latent_prefill_path = None
        logits = self._call_model(ids, dense, jnp.int32(0), param_vals)
        self._note_flash_prefill("prefill", (Tb,))
        last = jnp.take(logits[0], last_pos, axis=0).astype(jnp.float32)
        tokens = sample_tokens(last[None], temps, top_ks, top_ps, keys)
        # every k side, then every v side: the order of the parent's program
        new_k = tuple(
            c.k_pool._value if spec.state else
            kp.at[blocks, offs].set(
                _paged_ops.pool_rows(c[0]._value[0], kp))
            for spec, kp, c in zip(self.cache_specs, k_pools, dense))
        new_v = tuple(
            c.v_pool._value if spec.state else
            vp if c[1] is None else
            vp.at[blocks, offs].set(c[1]._value[0].astype(vp.dtype))
            for spec, vp, c in zip(self.cache_specs, v_pools, dense))
        return tokens, last, self._launch_stats(last), new_k, new_v

    def _chunk_prefill_fn(self, param_vals, k_pools, v_pools, ids, start,
                          last_pos, tables, lens, slot_blocks,
                          slot_offsets, temps, top_ks, top_ps, keys):
        """Chunked/resumed prefill: run ``ids`` (one bucketed chunk of a
        prompt, starting at absolute position ``start``) straight through
        the PAGED pool — the chunk's K/V scatters into its (block, offset)
        slots and attention covers the already-computed prefix (cached
        fork or earlier chunks) plus the chunk itself.  Shapes are fixed
        per (chunk-bucket, table-bucket) pair.  Returns the logits row of
        the chunk's LAST REAL token + updated pools."""
        self.prefill_trace_count += 1
        self.metrics.count("prefill_jit_traces")
        self.tracer.instant("prefill_jit_trace", cat="jit",
                            chunk_bucket=int(ids.shape[1]),
                            table_bucket=int(tables.shape[1]))
        # state (a ring, rows) is carried in from where it lies when the
        # chunk starts past 0
        caches = self._layer_caches(
            k_pools, v_pools, tables, lens, slot_blocks, slot_offsets,
            start=start, n_valid=last_pos + 1)
        _paged_ops.last_latent_prefill_path = None
        logits = self._call_model(ids, caches, start, param_vals)
        self._note_flash_prefill("chunk", (ids.shape[1], tables.shape[1]))
        last = jnp.take(logits[0], last_pos, axis=0).astype(jnp.float32)
        tokens = sample_tokens(last[None], temps, top_ks, top_ps, keys)
        return (tokens, last, self._launch_stats(last),
                tuple(c.k_pool._value for c in caches),
                tuple(c.v_pool._value for c in caches))

    def _unified_fn(self, param_vals, k_pools, v_pools, ids, pos, seg_ids,
                    last_idx, tables, lens, slot_blocks, slot_offsets,
                    temps, top_ks, top_ps, keys):
        """ONE packed ragged step (ISSUE 11): ``ids`` is a flat
        ``[1, Tb]`` token batch mixing decode rows (1 token each) and
        prefill chunks, with per-token absolute positions ``pos``
        ([1, Tb]), per-token row routing ``seg_ids`` ([Tb]) and per-ROW
        block tables / KV lengths ([Tb, TWb] / [Tb]; rows past the real
        count are null-page pads).  Every token scatters its K/V into its
        own (block, offset) slot and attends causally over its row's
        pages — the single fused program that replaces the three legacy
        families.  Returns each row's last-real-token logits (gathered
        at ``last_idx``) + updated pools.  Shapes fixed per
        (token-bucket, table-bucket) pair."""
        self.ragged_trace_count += 1
        self.metrics.count("ragged_jit_traces")
        self.tracer.instant("ragged_jit_trace", cat="jit",
                            token_bucket=int(ids.shape[1]),
                            table_bucket=int(tables.shape[1]))
        caches = self._layer_caches(
            k_pools, v_pools, tables, lens, slot_blocks, slot_offsets,
            start=pos[0], seg_ids=seg_ids)
        for c in caches:    # the shard_map kernel: the mp>1 auto-pin does
            c.use_pallas = self._use_pallas_ragged  # NOT apply to it
        logits = self._call_model(ids, caches, pos, param_vals)
        self.attention_paths["ragged"] = _ragged_ops.last_path
        last = jnp.take(logits[0], last_idx, axis=0).astype(jnp.float32)
        # sample at EVERY packed token position (ISSUE 18): the sampling
        # quartet is per-TOKEN here, so a spec-decode verify row gets its
        # per-position target tokens from the very same reduction a plain
        # decode row's single position uses — no new program family
        tokens = sample_tokens(logits[0].astype(jnp.float32),
                               temps, top_ks, top_ps, keys)
        return (tokens, last, self._launch_stats(last),
                tuple(c.k_pool._value for c in caches),
                tuple(c.v_pool._value for c in caches))

    def _state_stat(self, k_pools) -> Dict[str, str]:
        """``state_step`` on the ``decode_jit_trace`` instant: the path a
        decode program's selective-scan layers step their state through
        (``"pallas"`` in the slot, ``"xla"`` gathered), by the predicate
        the layers' dispatch uses; nothing for a model without such
        state.  It stands BELOW the step programs' functions: a line
        shifted above a Pallas call site changes every program that holds
        a kernel (ROADMAP S7 f)."""
        for pool, spec in zip(k_pools, self.cache_specs):
            if spec.state and spec.window is None:
                return {"state_step": state_step_path(pool.shape,
                                                      self._use_pallas)}
        return {}

    # --- request lifecycle --------------------------------------------------
    def set_lifecycle(self, tracker: LifecycleTracker,
                      replica: Optional[str] = None) -> None:
        """Rebind this engine onto a shared lifecycle tracker (the fleet
        router calls this before any request exists, so router-thread
        routing events and engine-thread execution events land in ONE
        timeline per request).  ``replica`` pins the identity this
        engine stamps on every event — the router passes the replica
        INDEX so flight-recorder rings and the ``engine_death`` trigger
        key always agree, regardless of what the metrics labels say.
        The engine's own ``EngineConfig.lifecycle_events`` gate still
        applies."""
        self.lifecycle = tracker
        if replica is not None:
            self._replica_label = str(replica)

    def _lc(self, rid, name: str, **attrs) -> None:
        """One lifecycle event, replica-stamped; no-op when gated off."""
        if self._lifecycle_on:
            self.lifecycle.event(rid, name, replica=self._replica_label,
                                 **attrs)

    def _on_pool_evict(self, block: int, depth: int, lifetime: int,
                       cause: str) -> None:
        """BlockPool eviction hook (ISSUE 13): a reuse-parked cached
        block was clobbered for an allocation.  Event-driven — the
        counter, the lifecycle ``prefix_cache_eviction`` event (with the
        clobbered chain depth and the allocation cause), and the
        eviction-cause series all fire HERE, at the eviction, instead of
        being lag-batched by a per-step counter diff."""
        self.metrics.count("prefix_cache_evictions")
        self.cachestat.record_eviction(depth, lifetime, cause)
        # engine-level event (no single owning request): rid=None goes
        # to the flight-recorder rings only.  Per-step event budget:
        # counters above stay exact, but eviction N+1.. of one step
        # collapse into the burst summary _flush_evict_burst emits —
        # a thrashing step must not wash the flight ring.
        self._evict_events_step += 1
        if self._evict_events_step <= _EVICT_EVENTS_PER_STEP:
            self._lc(None, "prefix_cache_eviction", block=int(block),
                     depth=int(depth), lifetime_steps=int(lifetime),
                     cause=cause)

    def _flush_evict_burst(self) -> None:
        """End-of-step: one summary event for evictions past the
        per-step lifecycle-event budget, then reset the budget."""
        suppressed = self._evict_events_step - _EVICT_EVENTS_PER_STEP
        self._evict_events_step = 0
        if suppressed > 0:
            self._lc(None, "prefix_cache_eviction_burst",
                     suppressed=suppressed,
                     total=suppressed + _EVICT_EVENTS_PER_STEP)

    def _on_pool_revive(self, block: int, depth: int, lru_depth: int,
                        lifetime: int) -> None:
        """BlockPool revive hook (ISSUE 13): a prefix fork revived a
        reuse-parked block — the LRU position it sat at feeds the
        hit-depth histogram (the reuse-LRU saturation early-warning)."""
        self.cachestat.record_revive(lru_depth, lifetime)

    def set_history(self, history) -> None:
        """Bind a :class:`~paddle_tpu.observability.history.HistoryStore`
        (ISSUE 14).  The fleet router owns the store (one fleet-wide
        sampling cadence); each engine step ticks it.  Ignored when
        ``EngineConfig.history`` is off — the fleet refuses
        heterogeneous gates, so a half-sampled fleet cannot exist."""
        if self.engine_config.history:
            self.history = history

    def set_fault_injector(self, injector) -> None:
        """Bind a :class:`~paddle_tpu.serving.faultinject.FaultInjector`
        (ISSUE 12).  The injector is consulted at the named injection
        points inside :meth:`step`; the fleet router owns the instance
        so its exactly-once schedule survives supervisor rebuilds."""
        self._fault = injector

    def add_request(self, prompt_ids, sampling: Optional[SamplingParams] = None,
                    request_id=None, priority: int = 0,
                    trace_id: Optional[str] = None,
                    prefix_hashes: Optional[List[bytes]] = None,
                    slo_ms: Optional[float] = None,
                    resume_tokens: Optional[List[int]] = None) -> Request:
        """Enqueue a request (admission happens inside ``step``).

        ``trace_id`` (defaults to ``str(request_id)``) is attached to every
        span/instant the engine records for this request, so a frontend can
        reconstruct one request's prefill/preempt/decode lifecycle from the
        exported chrome trace.

        ``prefix_hashes`` (ISSUE 6) carries leading-block chain hashes a
        router already computed for prefix-affinity placement
        (``ops.paged_attention.prefix_chain_hashes`` over THIS prompt and
        THIS engine's block size); the admission probe reuses them
        instead of re-hashing the same blocks.

        ``resume_tokens`` (ISSUE 20) seeds already-emitted output tokens
        for a request migrating IN mid-stream (prefill→decode hand-off):
        the prefill target becomes prompt+outputs and the recompute
        discipline continues the stream from the next position — with
        the donor's KV imported first, the seeded tail is a cache hit,
        not a recompute."""
        req = Request(prompt_ids=list(np.asarray(prompt_ids).reshape(-1)),
                      sampling=sampling or SamplingParams(),
                      request_id=request_id, priority=priority,
                      trace_id=trace_id, prefix_hashes=prefix_hashes,
                      slo_ms=slo_ms)
        if req.request_id in self.requests:
            raise ValueError(f"request id {req.request_id!r} already exists")
        if resume_tokens:
            req.output_tokens.extend(int(t) for t in resume_tokens)
        req.arrival_time = time.perf_counter()
        self.requests[req.request_id] = req
        self.scheduler.add(req)
        self.metrics.count("requests_admitted")
        self._lc(req.request_id, _lc.EV_ENQUEUED, trace_id=req.trace_id,
                 prompt_tokens=len(req.prompt_ids), slo_ms=slo_ms,
                 queue_depth=self.scheduler.queue_depth)
        return req

    def abort_request(self, request_id,
                      reason: FinishReason = FinishReason.ABORT) -> bool:
        """Abort: frees blocks immediately, ends any stream with
        ``reason`` (default ABORT; the HTTP frontend passes TIMEOUT for
        deadline/drain aborts).  True if the request was still live."""
        req = self.requests.get(request_id)
        if req is None or req.finished:
            return False
        self._retire(req)
        self._finish(req, reason)
        self._let_go_if_ended()
        return True

    def _let_go_if_ended(self) -> None:
        """If every row of the launch in flight has ended (aborts, EOS
        tokens in the launch before it), nothing of it will ever be
        emitted, and with no running request no step would come to read
        it: it is let go unread, its rows counted as dropped.  No wait:
        this runs on the engine's death path too, which aborts every
        request."""
        launch = self._inflight
        if launch is None or not all(r.finished for r in launch.reqs):
            return
        self._inflight = None
        self._ahead_counters["dropped_rows"].inc(launch.rows)
        self._record_decode(launch, 0.0)

    def _record_decode(self, launch: "_DecodeLaunch", wall_s: float) -> None:
        self.stepprof.record_program(
            "decode", launch.bucket, scheduled=launch.rows,
            capacity=launch.bucket[0], wall_s=wall_s,
            table_width=launch.width, requests=launch.rids)

    def _finish(self, req: Request, reason: FinishReason) -> None:
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        req.finish_time = time.perf_counter()
        self.metrics.count(f"requests_finished_{reason.value}")
        e2e = req.finish_time - req.arrival_time
        self.metrics.observe_finish(e2e, req.slo_ms)
        self._lc(req.request_id, _lc.EV_FINISH, reason=reason.value,
                 e2e_s=round(e2e, 6), generated=len(req.output_tokens),
                 preemptions=req.num_preemptions)
        # park the attribution row in the bounded recent ring (ISSUE 13)
        self.cachestat.close_request(req.request_id)

    def _emit(self, req: Request, tok: int) -> None:
        """Append one sampled token + finish-state bookkeeping."""
        now = time.perf_counter()
        if req.first_token_time is None:
            req.first_token_time = now
            ttft = now - req.arrival_time
            self.metrics.observe_ttft(ttft)
            if req.prefill_start_time is not None:
                # the whole prefill PHASE (chunks + recomputes), the
                # middle leg of the SLO breakdown
                self.metrics.observe_prefill_phase(
                    now - req.prefill_start_time)
            self._lc(req.request_id, _lc.EV_FIRST_TOKEN,
                     ttft_s=round(ttft, 6))
        else:
            itl = now - req._last_emit
            self.metrics.observe_inter_token(itl)
            self._lc(req.request_id, _lc.EV_DECODE_TOKEN,
                     itl_s=round(itl, 6))
        req._last_emit = now
        req.append_token(tok)
        if req.hit_eos(tok):
            self._finish(req, FinishReason.EOS)
        elif len(req.output_tokens) >= req.sampling.max_new_tokens:
            self._finish(req, FinishReason.LENGTH)

    def _emit_device(self, req: Request, tok: int) -> None:
        """Emit one DEVICE-sampled token (ISSUE 18): the step program
        already ran the greedy/sampled reduction in-trace; the host only
        attributes the emission to the right counter.  The request's
        legacy host RNG is never consumed — the device key is the pure
        ``(seed, output_position)`` pair, so determinism needs no host
        stream at all."""
        kind = "greedy" if req.sampling.temperature == 0.0 else "sampled"
        self._sampling_counters[kind].inc()
        self._emit(req, int(tok))

    def _retire(self, req: Request) -> None:
        self.scheduler.remove(req)
        self.kv.free(req.request_id)
        for t in self._telemetry:
            t.forget(req.request_id)
        # drop the engine's handle so a long-lived server never accumulates
        # finished Requests; the caller keeps the object from add_request
        self.requests.pop(req.request_id, None)

    # --- execution ----------------------------------------------------------
    def _param_vals(self):
        return tuple(p._value for p in self._params)

    def _collective_phase(self, phase: str) -> Optional[str]:
        """StepTimer's extra label for the mesh-spanning step: the wall
        time also lands in ``serving_collective_seconds{phase=...}`` —
        only when the step actually spans shards (mp > 1); the series
        itself is pre-registered so it shows on ``/metrics`` either
        way."""
        return phase if self.mp > 1 else None

    def _begin_prefill_chunk(self, req: Request, t0: float):
        """Resolve + reserve this step's prefill chunk for ``req`` — the
        host bookkeeping shared row-for-row by the legacy prefill
        programs and the unified packed step (sharing it is what keeps
        the two paths' metrics and greedy tokens identical).  Returns
        ``(ids_full, target, start, n, recompute)``."""
        rid = req.request_id
        ids_full = req.prompt_ids + req.output_tokens
        target = len(ids_full)
        start = self.kv.seq_len(rid)  # cached fork + earlier chunks
        n = req._chunk_tokens if req._chunk_tokens else target - start
        req._chunk_tokens = None
        self._view.span = (start, n)
        recompute = bool(req.output_tokens
                         and start == req.num_cached_tokens)
        if req.prefill_start_time is None:
            # first prefill work for this request: the queue-wait leg of
            # the SLO breakdown ends here
            req.prefill_start_time = t0
            self.metrics.observe_queue_wait(t0 - req.arrival_time)
        if recompute:
            self.metrics.count("recompute_prefills")  # first chunk only
        if not self.kv.allocate(rid, n, cause="prefill_chunk"):
            raise PoolExhausted(  # scheduler planning guarantees room
                f"prefill chunk of {n} tokens for {rid!r} after admission")
        return ids_full, target, start, n, recompute

    def _finish_prefill_chunk(self, req: Request, ids_full, target: int,
                              start: int, n: int, recompute: bool,
                              t0: float, tok: int) -> None:
        """Post-launch bookkeeping for one prefill chunk, shared by both
        program paths: commit, lifecycle event, counters, prefix-hash
        registration, and the completion emission — ``tok`` is the
        device-sampled token off the final chunk's last-position logits,
        emitted only when the prefill completes."""
        rid = req.request_id
        self.kv.commit(rid, n)
        self._lc(rid, _lc.EV_PREFILL_CHUNK, start=start, tokens=n,
                 target=target, chunk=bool(start or n != target),
                 recompute=recompute,
                 duration_s=round(time.perf_counter() - t0, 6))
        self.metrics.count("prefill_tokens_computed", n)
        if self.kv.prefix_cache_enabled:
            # index the fully-written blocks NOW, so a same-prefix request
            # admitted next step shares them even mid-prefill
            self.kv.record_block_hashes(rid, ids_full, start + n)
        if start + n >= target:
            self._emit_device(req, tok)

    def _prefill(self, req: Request) -> None:
        """Run one bucketed prefill program for ``req`` — the whole
        prompt (cold one-shot), or one chunk of it (token-budgeted
        chunked prefill and/or resume past a prefix-cache hit).  Samples
        the request's next token only when the prefill completes (the
        final chunk's last-position logits ARE that token)."""
        rid = req.request_id
        phase, prof = self.tracer.phase, self.stepprof
        t_chunk0 = time.perf_counter()
        one_shot = False
        with phase("engine.build", prof,
                   **self._build_ints("prefill", 1, (req,))):
            ids, target, start, n, recompute = \
                self._begin_prefill_chunk(req, t_chunk0)
            table = self.kv.table(rid)
            pos = np.arange(start, start + n)
            # one sampling quartet row: the final chunk's last-position
            # draw (output position len(output_tokens) — on recompute the
            # replayed positions are already in output_tokens and never
            # re-drawn)
            pack = SamplingPack(1)
            pack.set_request(0, req)
            self._count_launch(pack)
            if start == 0 and n == target:
                # cold one-shot: dense-cache forward + scatter (the
                # cheapest program when nothing is cached and no budget
                # splits it)
                one_shot = True
                Tb = bucket_size(target)
                ids_arr = np.zeros((1, Tb), np.int64)
                ids_arr[0, :target] = ids
                blocks = np.zeros((Tb,), np.int32)  # pads -> null page
                blocks[:target] = [table[p // self.block_size] for p in pos]
                offs = (np.arange(Tb) % self.block_size).astype(np.int32)
                self.prefill_buckets.add(("prefill", Tb))
            else:
                # chunk / resume: the chunk scatters into its pages and
                # attends over the paged prefix, so earlier chunks and
                # prefix-cache forks need no recompute.  Two buckets
                # bound the trace count: chunk width and block-table
                # width.
                Wb = bucket_size(n)
                TWb = bucket_size(len(table))
                ids_arr = np.zeros((1, Wb), np.int64)
                ids_arr[0, :n] = ids[start:start + n]
                blocks = np.zeros((1, Wb), np.int32)  # pads -> null page
                blocks[0, :n] = [table[p // self.block_size] for p in pos]
                offs = np.zeros((1, Wb), np.int32)
                offs[0, :n] = pos % self.block_size
                tables = np.zeros((1, TWb), np.int32)
                tables[0, :len(table)] = table
                lens = np.array([start + n], np.int32)
                self.prefill_buckets.add(("chunk", Wb, TWb))
                self.metrics.count("chunked_prefill_steps")
        if one_shot:
            with self.tracer.span("prefill_step", cat="serving",
                                  request=str(rid), trace=req.trace_id,
                                  tokens=target, bucket=Tb,
                                  recompute=bool(req.output_tokens)):
                toks, logits, stats, dt = self._launch(
                    "prefill", (Tb,), self._jit_prefill,
                    (ids_arr, np.int32(target - 1), blocks, offs,
                     *pack.arrays()), rows=1)
            program, bucket, capacity, attrs = "prefill", (Tb,), Tb, {}
            inputs = {"ids": ids_arr, "blocks": blocks, "offs": offs}
        else:
            with self.tracer.span("prefill_step", cat="serving",
                                  request=str(rid), trace=req.trace_id,
                                  tokens=n, bucket=Wb, chunk=True,
                                  start=start,
                                  cached=req.num_cached_tokens,
                                  recompute=bool(req.output_tokens)):
                toks, logits, stats, dt = self._launch(
                    "chunk", (Wb, TWb), self._jit_chunk_prefill,
                    (ids_arr, np.int32(start), np.int32(n - 1), tables,
                     lens, blocks, offs, *pack.arrays()), rows=1)
            program, bucket, capacity = "chunk", (Wb, TWb), Wb
            attrs = {"start": start, "table_width": len(table)}
            inputs = {"ids": ids_arr, "start": np.int32(start),
                      "tables": tables, "lens": lens,
                      "slot_blocks": blocks, "slot_offsets": offs}
        with phase("engine.emit", prof):
            self.stepprof.record_program(
                program, bucket, scheduled=n, capacity=capacity,
                wall_s=dt, request=str(rid), **attrs)
            if self.audit.enabled:
                self.audit.observe_program(
                    program, stats, bucket,
                    logits=lambda: self._host_logits(logits[None, :]),
                    inputs=inputs,
                    requests=[{"id": str(rid),
                               "greedy": req.sampling.temperature == 0.0}])
            self._finish_prefill_chunk(req, ids, target, start, n,
                                       recompute, t_chunk0, int(toks[0]))

    def _decode(self, reqs: List[Request]) -> Dict[object, int]:
        """One bucketed decode step for ``reqs`` (slots already reserved
        by the scheduler on ``req._slot``), run to its end."""
        launch = self._build_decode(reqs)
        with self._decode_span(launch):
            out = self._launch("decode", launch.bucket, self._jit_decode,
                               launch.args, rows=launch.rows)
        return self._emit_decode(launch, *out)

    def _decode_span(self, launch: "_DecodeLaunch"):
        Bb, Wb = launch.bucket
        return self.tracer.span(
            "decode_step", cat="serving", batch=launch.rows,
            batch_bucket=Bb, width_bucket=Wb, requests=launch.rids,
            traces=tuple(r.trace_id for r in launch.reqs))

    def _build_decode(self, reqs: List[Request],
                      prev: Optional["_DecodeLaunch"] = None
                      ) -> "_DecodeLaunch":
        """The routing arrays of one decode launch, and the commit of the
        position each row writes: a launch is counted into ``kv.seq_len``
        when it is built, so the next one can be planned and built before
        this one's tokens are read.  Everything but the input token
        follows from lengths; the sampling key is the pure ``(seed,
        output position)`` pair.  With ``prev`` -- the launch in flight --
        a row that is also a row of ``prev`` takes its input token from
        ``prev``'s tokens ON THE DEVICE (``_ids_program``: one small
        gather, the step program is the same) and its output position is
        one further; any other row's last token is on the host."""
        phase, prof = self.tracer.phase, self.stepprof
        B = len(reqs)
        with phase("engine.build", prof, rows=B,
                   **self._build_ints("decode", B, reqs)):
            Bb = bucket_size(B)
            width = max(len(self.kv.table(r.request_id)) for r in reqs)
            Wb = self.decode_table_width or bucket_size(width)
            ids = np.zeros((Bb, 1), np.int64)
            poss = np.zeros((Bb,), np.int32)
            tables = np.zeros((Bb, Wb), np.int32)
            lens = np.ones((Bb,), np.int32)  # pad rows: 1 token of null page
            slot_blocks = np.zeros((Bb,), np.int32)
            slot_offsets = np.zeros((Bb,), np.int32)
            pack = SamplingPack(Bb)  # pad rows stay temp=0 → argmax, ignored
            # the row of ``prev`` each request in flight sits in; -1: the
            # host knows the row's last token
            src = np.full((Bb,), -1, np.int32)
            flying = {} if prev is None else {
                rid: j for j, rid in enumerate(prev.rids)}
            for i, r in enumerate(reqs):
                rid = r.request_id
                t = self.kv.table(rid)
                p = self.kv.seq_len(rid)
                j = flying.get(rid)
                if j is None:
                    ids[i, 0] = r.last_token
                    pack.set_request(i, r)
                else:
                    src[i] = j
                    pack.set_request(i, r, offset=1)
                poss[i] = p
                tables[i, :len(t)] = t
                lens[i] = p + 1           # cache length AFTER this token
                slot_blocks[i], slot_offsets[i] = r._slot
                self.kv.commit(rid, 1)
            self._count_launch(pack)
            self.decode_buckets.add(("decode", Bb, Wb))
            if prev is not None:
                ids = _ids_program(self._widest(prev.flight.toks), src,
                                   ids[:, 0])
            # shadow-oracle capture (ISSUE 10): on sampled audit steps the
            # PRE-step pools are snapshotted so the auditor can re-execute
            # this exact step through the XLA gather reference program
            pre_pools = self.audit.snapshot_pools(self._k_pools,
                                                  self._v_pools)
            # the rows' ids ride the span and the step record as the
            # tuple; whoever reads them joins them (export, records())
            rids = tuple(r.request_id for r in reqs)
        return _DecodeLaunch(
            reqs, rids, (Bb, Wb), width,
            (ids, poss, tables, lens, slot_blocks, slot_offsets,
             *pack.arrays()), pre_pools)

    def _emit_decode(self, launch: "_DecodeLaunch", toks, out, stats,
                     dt: float) -> Dict[object, int]:
        """What follows the read of a decode launch's tokens: the step
        record, the audit, and one emission a row.  A row that ended
        after the launch was built (an EOS token in the launch before it,
        an abort) has no use for its result: nothing is emitted or
        counted as a token for it, its blocks are free already, and what
        the launch wrote for it lies where only later launches can be
        handed room."""
        B, (Bb, Wb), reqs = launch.rows, launch.bucket, launch.reqs
        with self.tracer.phase("engine.emit", self.stepprof, rows=B):
            # token/row accounting only: scheduled = B real rows (one
            # token each) vs the Bb row bucket — this is the axis the
            # scheduler's tokens_planned ledger counts, so the invariant
            # stays exact (a row whose result is dropped was planned and
            # ran).  Width-bucket padding (tables padded `width` -> Wb
            # with null pages) is NOT in these counters; it rides the
            # record as the table_width attr next to the bucket shape.
            self._record_decode(launch, dt)
            if self.audit.enabled:
                # sentinel over the REAL rows (pad rows attend the null
                # page — their logits are not part of the serving
                # contract), plus the shadow re-execution when this step
                # is sampled: ``out`` is then the host copy of the real
                # rows (``_collect``).  kernel_corrupt (ISSUE 12) corrupts
                # ONLY this audit copy — the emitted tokens were sampled
                # on the device from the untouched logits, so served
                # tokens stay correct while the divergence net trips.
                # Only SAMPLED steps run the shadow compare, so the
                # exactly-once plan entry must not be consumed by a
                # launch the oracle never checks.
                self.audit.observe_program(
                    "decode", stats[:B], (Bb, Wb),
                    logits=self._audit_logits(out, B),
                    inputs=dict(zip(
                        ("ids", "pos", "tables", "lens", "slot_blocks",
                         "slot_offsets"), launch.args)),
                    pre_pools=launch.pre_pools,
                    requests=[{"id": str(r.request_id),
                               "greedy": r.sampling.temperature == 0.0}
                              for r in reqs])
            result = {}
            dropped = 0
            for i, r in enumerate(reqs):
                if r.finished:
                    dropped += 1
                    continue
                tok = int(toks[i])
                self._emit_device(r, tok)
                result[r.request_id] = tok
            if dropped:
                self._ahead_counters["dropped_rows"].inc(dropped)
        return result

    def _burst_exec(self, reqs: List[Request],
                    n_steps: int) -> Dict[object, int]:
        """Launch ONE device-resident burst covering ``n_steps`` decode
        steps for a decode-only resident cohort (ISSUE 19).  The host
        pre-extends every row's block table to its worst-case burst
        length (the clamp guaranteed the pool can back it), launches the
        looped program, then reconciles the whole burst after the fact:
        per-token emission through the normal ``_emit`` bookkeeping
        (stream cursor, lifecycle decode_token events, ITL aggregates),
        KV commit of what was actually written, and truncation of the
        unused pre-allocated tail."""
        phase, prof = self.tracer.phase, self.stepprof
        B = len(reqs)
        with phase("engine.build", prof, rows=B):
            Bb = bucket_size(B)
            Nb = bucket_size(n_steps)
            W = self._burst_width
            starts: Dict[object, int] = {}
            for r in reqs:
                rid = r.request_id
                starts[rid] = self.kv.seq_len(rid)
                # positions p..p+n-1 all get slots up front (the decode
                # slot reservation already covers p); exact need is <=
                # the conservative per-row bound burst_capacity promised,
                # so failure here means the shared accessor broke — fail
                # loudly
                if not self.kv.allocate(rid, n_steps, cause="burst"):
                    raise PoolExhausted(
                        f"burst pre-allocation failed for {rid!r}: "
                        f"burst_capacity promised {n_steps} steps "
                        f"x {B} rows")
            ids = np.zeros((Bb, 1), np.int64)
            poss = np.zeros((Bb,), np.int32)
            tables = np.zeros((Bb, W), np.int32)
            lens = np.ones((Bb,), np.int32)  # pad rows: 1 token of null page
            slot_blocks = np.zeros((Bb, Nb), np.int32)
            slot_offsets = np.zeros((Bb, Nb), np.int32)
            active = np.zeros((Bb,), np.bool_)
            eos_ids = np.full((Bb,), -1, np.int32)
            pack = SamplingPack(Bb)
            bs = self.block_size
            for i, r in enumerate(reqs):
                rid = r.request_id
                t = self.kv.table(rid)
                p = starts[rid]
                ids[i, 0] = r.last_token
                poss[i] = p
                tables[i, :len(t)] = t
                lens[i] = p + 1
                for j in range(n_steps):
                    q = p + j
                    slot_blocks[i, j] = t[q // bs]
                    slot_offsets[i, j] = q % bs
                active[i] = True
                if r.sampling.eos_token_id is not None:
                    eos_ids[i] = int(r.sampling.eos_token_id)
                pack.set_request(i, r)
            self._count_launch(pack)
            self.burst_buckets.add(("burst", Bb, Nb))
            rids = tuple(r.request_id for r in reqs)
        with self.tracer.span("burst_step", cat="serving", batch=B,
                              batch_bucket=Bb, burst_len=n_steps,
                              burst_bucket=Nb, requests=rids,
                              traces=tuple(r.trace_id for r in reqs)):
            # only the [Bb, Nb] token buffer crosses to the host
            buf, _out, _stats, dt = self._launch(
                "burst", (Bb, Nb), self._jit_burst,
                (ids, poss, tables, lens, slot_blocks, slot_offsets,
                 np.int32(n_steps), active, eos_ids, *pack.arrays()),
                rows=B)
        with phase("engine.emit", prof, rows=B):
            result = {}
            emitted_total = 0
            for i, r in enumerate(reqs):
                rid = r.request_id
                e = 0
                for j in range(n_steps):
                    tok = int(buf[i, j])
                    if tok < 0:   # -1 sentinel: row went inactive (EOS)
                        break
                    self._emit_device(r, tok)
                    result[rid] = tok
                    e += 1
                    if r.finished:
                        break
                emitted_total += e
                # iteration j wrote the KV of its input token at p+j, so
                # e emissions committed e positions — identical to e
                # per-step decode commits; unfinished rows hand back the
                # unused pre-allocated tail (finished rows free wholesale
                # in retire)
                self.kv.commit(rid, e)
                if not r.finished:
                    self.kv.truncate(rid, starts[rid] + e)
            # scheduled-token ledger (ISSUE 9): the scheduler planned one
            # decode token per row; the burst's extra emissions are
            # decode work the ENGINE added — mirror them into the ledger
            # so the EXACT invariant (profiler scheduled == scheduler
            # planned) holds when one launch covers N steps
            self.scheduler.tokens_planned_decode += emitted_total - B
            self.stepprof.record_program(
                "burst", (Bb, Nb), scheduled=emitted_total,
                capacity=Bb * Nb, wall_s=dt, burst_len=n_steps,
                requests=rids)
            c = self._burst_counters
            c["launches"].inc()
            c["tokens"].inc(emitted_total)
            c["length"].observe(float(n_steps))
        return result

    def _unified_exec(self, prefills: List[Request],
                      decodes: List[Request],
                      draft_budget: int = 0) -> Dict[object, int]:
        """Pack this step's whole plan — decode rows + prefill chunks —
        into ONE ragged program launch (``EngineConfig.unified_step``).
        The token dim buckets on the TOTAL scheduled token count and the
        row/table arrays are padded to the same bucket, so the compile
        bound is (token-bucket × table-bucket) for the one family —
        strictly fewer shapes than the legacy three.  Host bookkeeping
        (allocation, commits, hash registration, sampling, lifecycle
        events) matches the legacy paths row-for-row, which is what
        keeps greedy tokens identical.

        Speculative decoding (ISSUE 18): with ``EngineConfig.spec`` set,
        decode rows may be upgraded to ``verify`` rows — the n-gram
        proposer's k draft tokens ride as a short chunk
        ``[last_token, d1..dk]`` at positions ``p..p+k``, inside the
        step's leftover ``draft_budget``.  The per-position in-trace
        sampler yields target tokens T_j at every position; the longest
        ``d_{j+1} == T_j`` prefix is accepted, ``T_0..T_a`` are emitted
        (a+1 tokens in ONE engine step) and the KV tail past the last
        accepted position rolls back via :meth:`KVCacheManager.truncate`
        (the preemption-recompute slot discipline, pointed at a length
        instead of zero)."""
        phase, prof = self.tracer.phase, self.stepprof
        with phase("engine.build", prof,
                   rows=len(prefills) + len(decodes)):
            rows: List[Dict] = []
            t0 = time.perf_counter()
            for r in decodes:
                p = self.kv.seq_len(r.request_id)
                rows.append({"req": r, "kind": "decode", "start": p, "n": 1,
                             "tokens": [r.last_token], "slot": r._slot})
            drafts_packed = 0
            if self.spec is not None and draft_budget > 0:
                # upgrade decode rows to verify rows in-place (proposer +
                # draft-slot allocation; a row whose slots cannot be covered
                # stays a plain decode row — pool pressure, not an error)
                drafts_packed = self.spec.plan_drafts(self.kv, rows,
                                                      draft_budget)
                if drafts_packed:
                    # keep the scheduled-token ledger exact (ISSUE 9): the
                    # scheduler planned 1 token per decode row; the drafts
                    # the engine packs on top are decode-side work too
                    self.scheduler.tokens_planned_decode += drafts_packed
            for req in prefills:
                # the SAME pre-launch bookkeeping the legacy programs run
                # (queue-wait, recompute accounting, all-or-nothing allocate)
                ids_full, target, start, n, recompute = \
                    self._begin_prefill_chunk(req, t0)
                rows.append({"req": req, "kind": "chunk", "start": start,
                             "n": n, "tokens": ids_full[start:start + n],
                             "target": target, "recompute": recompute,
                             "ids_full": ids_full})
            R = len(rows)
            T = sum(row["n"] for row in rows)
            Tb = bucket_size(T)
            width = max(len(self.kv.table(row["req"].request_id))
                        for row in rows)
            TWb = bucket_size(width)
            ids = np.zeros((1, Tb), np.int64)
            pos = np.zeros((1, Tb), np.int32)
            # pad tokens route to a pad row (all-null table, kv_len 1); when
            # R == Tb every row is real and no pad token exists
            seg = np.full((Tb,), min(R, Tb - 1), np.int32)
            last_idx = np.zeros((Tb,), np.int32)
            tables = np.zeros((Tb, TWb), np.int32)
            lens = np.ones((Tb,), np.int32)   # pad rows: 1 token of null page
            slot_blocks = np.zeros((Tb,), np.int32)  # pad tokens -> null page
            slot_offsets = np.zeros((Tb,), np.int32)
            # per-TOKEN sampling quartet (ISSUE 18): pad positions stay
            # temp=0 (argmax over the null page, discarded); a verify row's
            # k+1 positions each carry their own output-position draw index
            pack = SamplingPack(Tb)
            cursor = 0
            for i, row in enumerate(rows):
                req = row["req"]
                table = self.kv.table(req.request_id)
                n, start = row["n"], row["start"]
                row["cursor"] = cursor
                ids[0, cursor:cursor + n] = row["tokens"]
                pp = np.arange(start, start + n)
                pos[0, cursor:cursor + n] = pp
                seg[cursor:cursor + n] = i
                tables[i, :len(table)] = table
                lens[i] = start + n           # cache length AFTER this step
                if row["kind"] == "decode":
                    slot_blocks[cursor], slot_offsets[cursor] = row["slot"]
                    pack.set_request(cursor, req)
                else:
                    # chunk AND verify rows: every position scatters into its
                    # own table-derived slot (a verify row's draft slots were
                    # just allocated by spec.plan_drafts, so its table covers
                    # start+n like any mid-prefill chunk's does)
                    slot_blocks[cursor:cursor + n] = [
                        table[x // self.block_size] for x in pp]
                    slot_offsets[cursor:cursor + n] = pp % self.block_size
                    if row["kind"] == "verify":
                        for j in range(n):
                            pack.set_request(cursor + j, req, offset=j)
                    else:
                        # only the final chunk's last position is ever read
                        pack.set_request(cursor + n - 1, req)
                cursor += n
                last_idx[i] = cursor - 1
            self._count_launch(pack)
            self.ragged_buckets.add(("ragged", Tb, TWb))
            self.metrics.count("unified_steps")
            pre_pools = self.audit.snapshot_pools(self._k_pools,
                                                  self._v_pools)
            rids = tuple(row["req"].request_id for row in rows)
        with self.tracer.span("unified_step", cat="serving", tokens=T,
                              rows=R, token_bucket=Tb, table_bucket=TWb,
                              requests=rids):
            toks, out, stats, dt = self._launch(
                "ragged", (Tb, TWb), self._jit_unified,
                (ids, pos, seg, last_idx, tables, lens, slot_blocks,
                 slot_offsets, *pack.arrays()), rows=R)
        with phase("engine.emit", prof, rows=R):
            # scheduled = T real tokens (decode rows count 1 each) vs the Tb
            # token bucket — the same axis the scheduler's tokens_planned
            # ledger counts, so the PR 8 invariant stays exact in unified
            # mode.  Table-width padding rides the record as attrs.
            self.stepprof.record_program(
                "ragged", (Tb, TWb), scheduled=T, capacity=Tb, wall_s=dt,
                rows=R, table_width=width, requests=rids)
            if self.audit.enabled:
                # sentinel over the REAL rows; the shadow oracle re-executes
                # sampled packed steps through the independently jitted XLA
                # ragged reference (audit._reference_ragged).  kernel_corrupt
                # corrupts only this audit copy, on sampled steps only — see
                # _decode.
                self.audit.observe_program(
                    "ragged", stats[:R], (Tb, TWb),
                    logits=self._audit_logits(out, R),
                    inputs={"ids": ids, "pos": pos, "seg_ids": seg,
                            "last_idx": last_idx, "tables": tables,
                            "lens": lens, "slot_blocks": slot_blocks,
                            "slot_offsets": slot_offsets},
                    pre_pools=pre_pools,
                    requests=[{"id": str(row["req"].request_id),
                               "greedy":
                               row["req"].sampling.temperature == 0.0}
                              for row in rows])
            emitted: Dict[object, int] = {}
            for i, row in enumerate(rows):
                req = row["req"]
                rid = req.request_id
                n, start = row["n"], row["start"]
                c0 = row["cursor"]
                if row["kind"] == "decode":
                    self.kv.commit(rid, 1)
                    tok = int(toks[c0])
                    self._emit_device(req, tok)
                    emitted[rid] = tok
                    continue
                if row["kind"] == "verify":
                    # spec accept/rollback (ISSUE 18): position j's target
                    # T_j = toks[c0+j] is exactly the token the plain decode
                    # path would have sampled at that output position (same
                    # logits prefix, same (seed, draw) key) — so exact-match
                    # acceptance keeps spec-on token-identical to spec-off
                    # for greedy AND seeded sampling
                    drafts = row["drafts"]
                    accepted = 0
                    for j, d in enumerate(drafts):
                        if int(toks[c0 + j]) == int(d):
                            accepted += 1
                        else:
                            break
                    emitted_n = 0
                    for j in range(accepted + 1):
                        self._emit_device(req, int(toks[c0 + j]))
                        emitted[rid] = int(toks[c0 + j])
                        emitted_n += 1
                        if req.finished:
                            break  # eos/length mid-run: later targets are
                            # tokens the plain path would never have drawn
                    # KV valid prefix: the emitted tokens' consumed inputs
                    # (last_token + the accepted drafts actually consumed) —
                    # the newest emitted token's KV is, as ever, written by
                    # the step that consumes it
                    self.kv.commit(rid, emitted_n)
                    if not req.finished:
                        # roll back the rejected/unconsumed draft tail (the
                        # preemption-recompute slot discipline, aimed at a
                        # length): surplus freshly-allocated blocks go back
                        # to the free list
                        self.kv.truncate(rid, start + emitted_n)
                    self.spec.record(len(drafts), accepted)
                    self._lc(rid, "spec_verify", drafted=len(drafts),
                             accepted=accepted, emitted=emitted_n)
                    continue
                # the SAME post-launch bookkeeping the legacy programs run
                # (commit, lifecycle event, counters, hash registration,
                # completion emission)
                before = len(req.output_tokens)
                self._finish_prefill_chunk(req, row["ids_full"],
                                           row["target"], start, n,
                                           row["recompute"], t0,
                                           int(toks[c0 + n - 1]))
                if len(req.output_tokens) > before:  # prefill completed
                    emitted[rid] = req.output_tokens[-1]
        return emitted

    def step(self) -> Dict[object, int]:
        """One engine iteration, run to its end: schedule → prefill(s) →
        decode batch → wait → emit → retire.  Returns {request_id: token}
        emitted by this call, and on return NOTHING is in flight: this is
        the contract of every direct caller (``run``, ``stream``, the
        benchmark's reference check, the worker process's loop).  Should a
        launch be in flight when it is called (the serving loop's, left by
        :meth:`step_ahead`), it is read first."""
        return self._step(ahead=False)

    def step_ahead(self) -> Dict[object, int]:
        """One engine iteration of the SERVING LOOP (``fleet.EngineReplica
        ._loop``), which may end with its decode launch still on the
        device.  While decode launch N is in flight the next call plans
        step N+1 on the assumption that every row of N yields one token
        (``scheduler.plan_ahead``), builds it with the input tokens taken
        from N's output on the device, and dispatches it; only then does
        it wait for N's tokens, bring them to the host, emit them, retire
        what finished and run the trackers, all while N+1 runs.  The order
        of the phases in such a step is ``sched.plan`` → ``engine.admit``
        → ``engine.build`` → ``engine.dispatch`` (N+1, ``ahead=1``) →
        ``engine.device_wait`` → ``engine.fetch`` → ``engine.emit`` (N) →
        ``engine.trackers``: the wait is for the launch BEFORE the one
        just dispatched.

        It runs ahead only where the plan is a pure continuation, and
        reads the launch in flight FIRST (:meth:`settle`, counted by its
        reason in ``serving_ahead_settles_total``) wherever it is not: a
        prompt to compute, an admission, a preemption, a step the numerics
        audit samples, a fault planned for this step.  The step then runs
        in the order of :meth:`step`, prefills synchronously, and leaves
        only its decode launch in flight.  An engine built with the
        unified step, decode bursts, speculative drafting or ``mp > 1``
        never leaves one (reason ``family``).  A row that ended on an EOS
        token in N has a row in N+1 already; its result is dropped at the
        read (``serving_ahead_dropped_rows_total``).  Returns what this
        call emitted: the tokens of the launch it read, and a prefill's
        first token."""
        return self._step(ahead=True)

    def settle(self, reason: str) -> Dict[object, int]:
        """Read the decode launch in flight, if there is one: wait for its
        tokens, emit them, retire what finished.  Counted under ``reason``
        (one of ``observability.tracer.SETTLE_REASONS``) in
        ``serving_ahead_settles_total``, and one ``ahead.settle`` span on
        the profiler's clock around the read, with the reason's index and
        the number of the launch read.  Whatever reads or moves the
        engine's state between steps (a KV export, an import, a detach)
        calls this first; so does a step that cannot run ahead."""
        launch, self._inflight = self._inflight, None
        if launch is None:
            return {}
        with self.tracer.phase("ahead.settle", None,
                               reason=SETTLE_REASONS.index(reason),
                               launch=launch.flight.seq):
            self._count_settle(reason)
            emitted = self._emit_decode(launch,
                                        *self._collect(launch.flight))
            self._retire_finished()
        return emitted

    def _count_settle(self, reason: str) -> None:
        c = self._ahead_counters["settles"].get(reason)
        if c is None:
            if reason not in SETTLE_REASONS:
                raise ValueError(f"settle reason {reason!r} is none of "
                                 f"{SETTLE_REASONS}")
            c = self._ahead_counters["settles"][reason] = \
                self.metrics.registry.counter(
                    "serving_ahead_settles_total",
                    help=_AHEAD_SETTLES_HELP,
                    **dict(self.metrics.labels, reason=reason))
        c.inc()

    def _retire_finished(self) -> None:
        with self.tracer.phase("engine.emit", self.stepprof):
            for req in list(self.scheduler.running):
                if req.finished:
                    self._retire(req)

    def _admit(self, plan) -> None:
        """Admission bookkeeping of one plan: counters, preemption and
        abort events, cache attribution of what was admitted."""
        phase, prof = self.tracer.phase, self.stepprof
        with phase("engine.admit", prof):
            self.metrics.count("engine_steps")
            self.metrics.count("preemptions", len(plan.preempted))
            for req in plan.preempted:
                self.tracer.instant(
                    "preemption", cat="serving",
                    request=str(req.request_id), trace=req.trace_id,
                    generated=len(req.output_tokens))
                self._lc(req.request_id, _lc.EV_PREEMPTED,
                         generated=len(req.output_tokens))
                for t in self._telemetry:
                    t.forget(req.request_id)
            for req in plan.aborted:
                # unservable at admission: scheduler set state/reason,
                # the engine owns finish bookkeeping (timestamp +
                # counter)
                self._lc(req.request_id, _lc.EV_ADMISSION_REJECTED,
                         reason="abort", error=req.error)
                self._finish(req, FinishReason.ABORT)
                self.requests.pop(req.request_id, None)
            for req in plan.admitted:
                cached = req.num_cached_tokens
                total = len(req.prompt_ids) + len(req.output_tokens)
                self.metrics.count("prefix_cache_hit_tokens", cached)
                self.metrics.count("prefix_cache_miss_tokens",
                                   total - cached)
                if req.prompt_cached_tokens is None:
                    # FIRST admission (output empty, so cached <=
                    # prompt): the client-facing usage attribution
                    req.prompt_cached_tokens = cached
                # per-request attribution (ISSUE 13): accumulated at
                # the SAME points as the counters above, so
                # sum(per-request cached) == prefix_cache_hit_tokens
                # exactly (asserted in tests and bench)
                self.cachestat.record_admission(
                    req.request_id, cached, total - cached,
                    len(req.prompt_ids),
                    recompute=bool(req.output_tokens))
                self._lc(req.request_id, _lc.EV_ADMITTED,
                         cached_tokens=cached,
                         computed_tokens=total - cached,
                         recompute=bool(req.output_tokens))
                if cached:
                    self.tracer.instant(
                        "prefix_cache_hit", cat="serving",
                        request=str(req.request_id),
                        trace=req.trace_id, cached_tokens=cached)
                if cached and self.cachestat.enabled:
                    # prefix-heat (ISSUE 13): keyed by the DEEPEST
                    # matched block's chain hash — it commits to the
                    # whole cached prefix.  Guarded: the table copy
                    # + hash lookup must cost nothing when the
                    # tracker is disabled.
                    depth = cached // self.block_size
                    table = self.kv.table(req.request_id)
                    self.cachestat.record_prefix_hit(
                        self.kv.block_chain_hash(table[depth - 1])
                        if 0 < depth <= len(table) else None,
                        depth, cached, self.step_seq)

    def _step(self, ahead: bool) -> Dict[object, int]:
        remove_timer = (self.metrics.install_dispatch_timer()
                        if self._profile_ops else lambda: None)
        emitted: Dict[object, int] = {}
        fi = self._fault
        self.step_seq += 1
        self.kv.clock = self.step_seq  # park lifetimes tick in steps
        self.stepprof.begin_step()
        phase, prof = self.tracer.phase, self.stepprof
        trackers = None
        try:
            if self._inflight is not None:
                # what stands in the way of running ahead and is known
                # before anything is planned.  The launch in flight is the
                # step before's: it is read before the audit's schedule
                # moves on and before a planned fault fires
                why = (SETTLE_BARE if not ahead else
                       SETTLE_AUDIT if self.audit.next_sampled else
                       SETTLE_FAULT if fi is not None
                       and fi.pending(self.step_seq) else None)
                if why is not None:
                    emitted.update(self.settle(why))
            self.audit.begin_step()
            if fi is not None:
                # named injection points (ISSUE 12): slow_step sleeps
                # here (inside the replica's watchdog-watched section),
                # engine_step_raise raises (the thread dies through the
                # real death path — INSIDE this try, so the finally
                # still unhooks the dispatch timer from the global op
                # bus), pool_exhaust arms one planning pass of
                # allocation refusal consumed just below
                fi.begin_step(self.step_seq)
            with self.tracer.span("engine_step", cat="serving") as sp:
                plan = None
                flying = self._inflight
                if flying is not None:
                    with phase("sched.plan", prof):
                        plan, why = self.scheduler.plan_ahead(flying.reqs)
                    if plan is None:
                        emitted.update(self.settle(why))
                        flying = None
                if plan is None:
                    if fi is not None and fi.pool_exhausted:
                        self.kv.refuse_allocations = True
                    try:
                        with phase("sched.plan", prof):
                            plan = self.scheduler.schedule()
                    finally:
                        # refusal applies to PLANNING only: the launches
                        # below must still allocate the chunks the
                        # (starved) plan actually contains
                        self.kv.refuse_allocations = False
                self._admit(plan)
                decodes = [r for r in plan.decodes
                           if r.state is RequestState.RUNNING]
                # device-resident decode burst (ISSUE 19): a decode-only
                # resident cohort with a clamped horizon >= 2 runs ONE
                # looped launch covering N steps; any pending admission,
                # prefill continuation or spec drafting falls through to
                # the normal per-step paths (host decisions stay at
                # burst boundaries)
                burst_n = 0
                if self._burst_steps >= 2 and burst_eligible(
                        self.scheduler, plan, decodes, self.spec):
                    burst_n = clamp_burst(self._burst_steps, decodes,
                                          plan.burst_capacity)
                if burst_n >= 2:
                    emitted.update(self._burst_exec(decodes, burst_n))
                elif self._unified:
                    # unified ragged step (ISSUE 11): the whole plan —
                    # decode rows + prefill chunks — is ONE packed launch
                    # (draft tokens compete for the leftover budget,
                    # ISSUE 18)
                    if plan.prefills or decodes:
                        emitted.update(self._unified_exec(
                            plan.prefills, decodes, plan.draft_budget))
                else:
                    for req in plan.prefills:
                        before = len(req.output_tokens)
                        self._prefill(req)
                        if len(req.output_tokens) > before:  # done —
                            # a partial chunk emits nothing yet
                            emitted[req.request_id] = req.output_tokens[-1]
                    if decodes and not ahead:
                        emitted.update(self._decode(decodes))
                    elif decodes:
                        emitted.update(self._decode_ahead(decodes, flying))
                if ahead and decodes and not self._flies:
                    self._count_settle(SETTLE_FAMILY)
                self._retire_finished()
                self._let_go_if_ended()
                # the end-of-step trackers, one phase from here to the
                # end of ``stepprof.end_step`` in the ``finally`` below
                trackers = phase("engine.trackers", prof)
                trackers.__enter__()
                # (prefix-cache evictions are event-driven now: the
                # pool's on_evict hook fires the counter, the lifecycle
                # event and the cause/depth series at the eviction;
                # past the per-step event budget they collapse into one
                # burst summary here)
                self._flush_evict_burst()
                self.metrics.set_cached_token_ratio()
                # pool timeline (ISSUE 13): one sample per engine step,
                # invariant-checked inside
                self.cachestat.sample_pool(
                    self.step_seq,
                    promised=self.scheduler.promised_blocks)
                self.metrics.sample_gauges(self.scheduler.queue_depth,
                                           self.scheduler.num_running,
                                           self.kv.occupancy())
                if self.history is not None:
                    # metrics history + alert evaluation (ISSUE 14):
                    # deterministic engine-step cadence, host-side only
                    self.history.on_step(self.step_seq)
                sp.set_attribute(
                    "step", int(self.metrics._counter("engine_steps").value))
                sp.set_attribute("emitted", len(emitted))
                sp.set_attribute("kv_occupancy",
                                 round(self.kv.occupancy(), 4))
            return emitted
        finally:
            # runs on the death path too: the partial step record still
            # reaches the last-K ring the flight bundle embeds
            self.stepprof.end_step()
            if trackers is not None:
                trackers.__exit__(None, None, None)
            remove_timer()

    def _decode_ahead(self, reqs: List[Request],
                      flying: Optional["_DecodeLaunch"]
                      ) -> Dict[object, int]:
        """The serving loop's decode launch.  With ``flying`` -- the
        launch in flight, whose rows ``reqs`` continue -- this one goes
        out first and ``flying`` is read while it runs.  The new launch
        stays in flight for the next step to read, unless this engine or
        this step cannot have one (a family with no such path, a step the
        audit samples): then it is read at once.  Returns what was
        emitted."""
        launch = self._build_decode(reqs, prev=flying)
        with self._decode_span(launch):
            launch.flight = self._dispatch(
                "decode", launch.bucket, self._jit_decode, launch.args,
                rows=launch.rows, ahead=flying is not None)
        emitted: Dict[object, int] = {}
        if flying is not None:
            self._ahead_counters["launches"].inc()
            self._inflight = None
            emitted = self._emit_decode(flying,
                                        *self._collect(flying.flight))
        if launch.flight.toks.committed:
            # weights placed with an explicit device make every output
            # committed to it, and an ids array made from committed tokens
            # would re-lower each decode program once (a host array and an
            # uncommitted one lower alike): such an engine reads at once
            self._flies = False
        if self._flies and not self.audit.sampled:
            if not self.audit.enabled:
                # nothing reads the [rows, vocab] float32 output again
                launch.flight.logits = None
            self._inflight = launch
        else:
            emitted.update(self._emit_decode(
                launch, *self._collect(launch.flight)))
        return emitted

    def run(self, max_steps: Optional[int] = None) -> None:
        """Drive ``step()`` until every request finishes."""
        steps = 0
        while self.scheduler.has_work():
            self.step()
            steps += 1
            if (max_steps is not None and steps >= max_steps
                    and self.scheduler.has_work()):
                raise RuntimeError(
                    f"engine did not drain within {max_steps} steps")

    # --- streaming ----------------------------------------------------------
    def stream(self, request_id) -> Iterator[int]:
        """Per-request token generator: yields tokens as they are
        produced, driving the shared engine loop when it runs dry.  Ends
        when the request finishes (its ``finish_reason`` says why); an
        abort mid-stream simply ends the iteration.  The handle is
        resolved eagerly, so the stream stays valid after the engine
        retires the finished request from ``self.requests``.

        Closing the generator early (``.close()`` / ``GeneratorExit`` /
        garbage collection) aborts the underlying request and frees its
        KV blocks — an abandoned stream must not leak scheduled work."""
        req = self.requests[request_id]

        def _gen():
            cursor = 0
            try:
                while True:
                    while cursor < len(req.output_tokens):
                        yield req.output_tokens[cursor]
                        cursor += 1
                    if req.finished:
                        return
                    self.step()
            finally:
                # reached on GeneratorExit too: a consumer that walks away
                # mid-stream must not leave the request running in the
                # scheduler holding pool blocks
                if not req.finished:
                    self.abort_request(req.request_id)

        return _gen()

    # --- manual (predictor-compat) mode -------------------------------------
    def prefill_now(self, req: Request) -> int:
        """Admission-bypassing immediate prefill (LLMPredictor's
        ``add_request`` contract: the caller owns scheduling).  Raises
        :class:`PoolExhausted` when the prompt cannot be covered."""
        if not self.kv.can_allocate(req.request_id, req.num_computed_tokens):
            raise PoolExhausted(
                f"prompt of {req.num_computed_tokens} tokens needs "
                f"{self.kv.blocks_needed(req.request_id, req.num_computed_tokens)}"
                f" blocks, {self.kv.num_free} free")
        if not req.arrival_time:
            req.arrival_time = time.perf_counter()
        req.state = RequestState.RUNNING
        self.scheduler.running.append(req)
        self._prefill(req)
        return req.output_tokens[-1]

    def decode_ids(self, request_ids: Sequence[object]) -> Dict[object, int]:
        """Manual decode for explicit ids (LLMPredictor's ``step``): the
        caller picked the batch, so exhaustion here raises instead of
        preempting."""
        reqs = []
        for rid in request_ids:
            req = self.requests[rid]
            slot = self.kv.append_slot(rid)
            if slot is None:
                raise PoolExhausted(
                    f"no free block for decode slot of {rid!r}")
            req._slot = slot
            reqs.append(req)
        return self._decode(reqs)

    def release(self, request_id) -> None:
        """Drop a request and free its blocks (no finish bookkeeping —
        the predictor's ``free``).  The timeline IS closed: an active
        timeline with no owner would sit in the tracker forever."""
        req = self.requests.pop(request_id, None)
        if req is not None:
            self.scheduler.remove(req)
            self._lc(request_id, _lc.EV_FINISH, reason="released")
        self.cachestat.close_request(request_id)
        self.kv.free(request_id)

    # --- KV hand-off (ISSUE 20) ---------------------------------------------
    def export_kv_run(self, request_id):
        """Serialize ``request_id``'s computed prompt KV (its hashed
        leading blocks) as a hand-off run; ``None`` when nothing is
        transferable.  Pure read — the request keeps running here until
        :meth:`detach_request`."""
        from . import handoff

        self.settle(SETTLE_TASK)
        return handoff.export_request_run(self, request_id)

    def export_prefix_chain(self, chain_hash, max_blocks=None):
        """Serialize the cached prefix chain addressed by its deepest
        digest (hot-prefix migration); ``None`` on a broken chain."""
        from . import handoff

        self.settle(SETTLE_TASK)
        return handoff.export_prefix_run(self, chain_hash,
                                         max_blocks=max_blocks)

    def hot_prefixes(self, top_k=None):
        """Heat-table-hot cached prefixes with full chain digests
        (hot-prefix migration; see
        :meth:`~paddle_tpu.observability.cachestat.CacheStatTracker.hot_prefixes`).
        Engine-thread callers only."""
        return self.cachestat.hot_prefixes(top_k)

    def import_kv_run(self, run):
        """Admit a hand-off run into this engine's pool (verified,
        atomic; see :func:`~paddle_tpu.serving.handoff.import_run`).
        Returns fresh-block count, or ``None`` on capacity refusal."""
        from . import handoff

        self.settle(SETTLE_TASK)
        return handoff.import_run(self, run)

    def detach_request(self, request_id) -> bool:
        """Drop a request WITHOUT finishing it — the donor half of a
        hand-off: the request migrates (same rid, open timeline) to
        another replica, so no finish event fires here.  Its blocks are
        freed; with the prefix cache on, the hashed prompt blocks park
        WARM in the reuse LRU — a failed migration that re-admits here
        revives them at zero recompute."""
        self.settle(SETTLE_TASK)
        req = self.requests.pop(request_id, None)
        if req is None:
            return False
        self.scheduler.remove(req)
        self.cachestat.close_request(request_id)
        self.kv.free(request_id)
        return True
