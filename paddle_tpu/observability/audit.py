"""Online numerics auditing for the serving engine (ISSUE 10).

PRs 7–8 made the serving stack observable in *time* (request timelines,
step/bucket/compile attribution); this module watches it in *value*: a
NaN that leaked into a KV pool, a drifting Pallas kernel, or a silently
wrong mesh-spanning program would otherwise surface only as garbage
tokens with no telemetry trail.  Three capabilities, all gated by
``EngineConfig.audit`` (an :class:`AuditConfig`; default **off** — zero
``serving_audit_*`` / ``serving_logit_*`` series on ``/metrics``):

* **NaN/Inf sentinel + logit-stats telemetry** — the bucketed
  prefill/chunk/decode programs additionally return cheap in-trace
  reductions over their output logits (:func:`logit_stats`: per-row
  non-finite count, max \\|logit\\|, argmax margin).  The reductions are
  computed unconditionally inside the traced programs, so audit on vs
  off is the SAME compiled program — bucket sets and jit trace counts
  are provably unchanged (tested).  Host side, every launch feeds the
  ``serving_logit_absmax`` / ``serving_logit_margin`` histograms and a
  non-finite row increments ``serving_audit_nonfinite_total{program}``,
  fires the new ``nonfinite`` flight-recorder trigger, and dumps a
  repro bundle.
* **Shadow-oracle differential execution** — on sampled steps (a
  deterministic step-counter schedule, ``sample_every``; no wall clock,
  no randomness) the auditor re-executes the *same captured decode
  inputs* through an independently jitted **reference program**: the
  XLA gather attention path (``use_pallas=False`` — the oracle the
  ROADMAP's ragged-kernel item keeps) traced as a plain single-device
  program, which for mp>1 engines is a replicated single-shard re-run
  of the mesh-spanning step (pools/params gathered to host first).
  Tokens must match exactly (greedy rows: argmax) and logits within
  ``logit_atol``/``logit_rtol``; ``serving_audit_steps_total{program}``
  counts audited launches, ``serving_audit_logit_absdiff`` records the
  max-abs-diff per shadow run, and any mismatch increments
  ``serving_audit_divergence_total{kind=token|logit|nonfinite}``.
* **Repro bundles + degraded state** — a divergence dumps an atomic
  (tmp→rename), size-capped (``max_repro_bytes``) ``.npz`` repro — the
  captured step inputs, pre-step KV pools, primary + reference logits,
  JSON metadata — and fires the ``divergence`` flight trigger so the
  PR 7 machinery captures the request timelines touching that step.
  :func:`replay_repro` re-executes the reference on the stored inputs
  and verifies the mismatch reproduces.  The auditor marks itself
  ``degraded`` (``GET /v1/debug/audit``; ``/readyz`` annotates
  ``audit=degraded`` without ever flipping readiness by itself).

Boundedness (``tools/check_bounded_metrics.py`` lints this module):
repro paths live in a ``deque(maxlen=max_repros)``; at most ONE repro
is written per (kind, program) pair per auditor (a drifting kernel
diverges every audited step — the first bundle is the actionable one);
counters are fixed-key dicts.  Host-side cost when enabled is O(rows)
per launch outside sampled steps; the shadow re-run happens only on
sampled steps.
"""

from __future__ import annotations

import io
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# the bucketed program families the engine dispatches: the legacy three
# (PR 1/4) plus the unified packed ragged step (ISSUE 11)
AUDIT_PROGRAMS = ("prefill", "chunk", "decode", "ragged")
# ... of which the shadow oracle re-executes these on a sampled step, and
# so reads their logits on the host
SHADOW_PROGRAMS = ("decode", "ragged")

# divergence kinds: greedy token flipped / logits outside tolerance /
# non-finite values in the primary output
DIVERGENCE_KINDS = ("token", "logit", "nonfinite")

# pre-registered metric names this module owns (tools/check_metrics_docs
# lints that each appears in README's metrics table)
METRIC_NAMES = (
    "serving_audit_steps_total",
    "serving_audit_divergence_total",
    "serving_audit_nonfinite_total",
    "serving_audit_oracle_failures_total",
    "serving_audit_logit_absdiff",
    "serving_logit_absmax",
    "serving_logit_margin",
)

_ABSMAX_BUCKETS = (0.5, 1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 1e3, 1e4)
_MARGIN_BUCKETS = (0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 5.0, 10.0, 25.0)
_ABSDIFF_BUCKETS = (1e-8, 1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1,
                    1.0, 10.0)

# arrays dropped (biggest first) when a repro would exceed the byte cap
_REPRO_DROP_ORDER = ("v_pools", "k_pools", "reference_logits",
                     "primary_logits")


def logit_stats(logits):
    """In-trace per-row logit reductions: ``[rows, 3]`` float32 of
    (non-finite count, max \\|logit\\|, argmax margin = top1 − top2).

    Pure ``jnp`` — the engine calls this INSIDE its traced step
    programs, so the stats ride the jitted launch as one extra (tiny)
    output.  Non-finite entries are masked to 0 before the maxima so
    absmax/margin stay finite; the non-finite count carries the alarm.
    A 1-D ``[vocab]`` row (the prefill programs' last-token logits) is
    treated as one row."""
    import jax
    import jax.numpy as jnp

    # ``logit_stats`` is the name a device trace knows these operations
    # by (metadata only).  The margin is two max-reductions and not a
    # ``top_k``: the TPU lowers a top-2 to a full-vocabulary sort, and
    # this runs in every step program, audit on or off — as long as the
    # sampler's own sort (PERF.md, PRs 25 and 28).  Only the one lane at
    # the argmax is masked for the second maximum, so a tie at the top
    # still reads margin 0
    with jax.named_scope("logit_stats"):
        l = logits.astype(jnp.float32)
        if l.ndim == 1:
            l = l[None, :]
        finite = jnp.isfinite(l)
        nonfinite = jnp.sum(~finite, axis=-1).astype(jnp.float32)
        safe = jnp.where(finite, l, 0.0)
        absmax = jnp.max(jnp.abs(safe), axis=-1)
        lane = jax.lax.broadcasted_iota(jnp.int32, safe.shape, 1)
        top1_lane = jnp.argmax(safe, axis=-1)[:, None]
        top1 = jnp.max(safe, axis=-1)
        top2 = jnp.max(jnp.where(lane == top1_lane, -jnp.inf, safe), axis=-1)
        margin = top1 - top2
        return jnp.stack([nonfinite, absmax, margin], axis=-1)


@dataclass(frozen=True)
class AuditConfig:
    """Numerics-audit knobs (``EngineConfig.audit``).  Frozen so a fleet
    can compare replica configs by value — the router rejects
    heterogeneous audit configs the same way it rejects mismatched
    lifecycle/step-profile gates."""

    enabled: bool = False
    # deterministic step-counter schedule: engine step k (1-based) is
    # shadow-audited when (k - 1) % sample_every == 0.  1 = every step.
    # No wall-clock, no randomness — audited runs are reproducible.
    sample_every: int = 16
    # logit comparison tolerance for the shadow oracle:
    # |primary - reference| <= atol + rtol * |reference|
    logit_atol: float = 1e-4
    logit_rtol: float = 1e-4
    # hard byte cap per .npz repro bundle: arrays are dropped biggest-
    # first (pools, then logits) until the bundle fits
    max_repro_bytes: int = 4 << 20
    # where .npz repros land; None = next to the flight recorder's
    # bundles (its dump_dir), or nowhere if neither is configured
    repro_dir: Optional[str] = None
    # cap on repros written per auditor (also once per (kind, program))
    max_repros: int = 4

    def __post_init__(self):
        if self.sample_every < 1:
            raise ValueError(
                f"sample_every must be >= 1, got {self.sample_every}")
        if self.max_repros < 1:
            raise ValueError(
                f"max_repros must be >= 1, got {self.max_repros}")


class NumericsAuditor:
    """Per-engine online numerics audit: sentinel, shadow oracle, repro
    bundles, degraded state.

    One instance per :class:`~paddle_tpu.serving.EngineCore` (the fleet
    router binds each to the shared flight recorder keyed by replica
    index).  The engine thread is the only writer; HTTP handler threads
    read :meth:`snapshot` under the auditor lock."""

    def __init__(self, engine, config: Optional[AuditConfig] = None,
                 registry=None, labels: Optional[Dict[str, str]] = None):
        self.engine = engine
        self.cfg = config if config is not None else AuditConfig()
        self.enabled = self.cfg.enabled
        self.labels: Dict[str, str] = dict(labels or {})
        self.registry = registry
        self._replica = self.labels.get("replica", "0")
        self.flight = None  # FlightRecorder, fleet-bound
        self._lock = threading.Lock()
        self._step = 0
        self._sampled = False
        self._degraded = False
        self.last_divergence: Optional[Dict] = None
        self._repros: deque = deque(maxlen=max(1, self.cfg.max_repros))
        self._repro_count = 0
        self._fired: set = set()   # (kind, program): one repro per pair
        # last dump ATTEMPT per key (≤ kinds × programs entries): a
        # persistently failing dump (disk full during the incident) is
        # retried only after a cooldown, never on every diverging launch
        self._attempt_ts: Dict[Tuple[str, str], float] = {}
        self._attempt_cooldown_s = 30.0
        self._seq = 0
        self._jit_ref_decode = None
        self._jit_ref_ragged = None  # unified packed-step reference
        # (ISSUE 11): the XLA ragged_oracle path, independently jitted
        self._ref_params = None  # mp>1: host-gathered params, cached —
        # serving weights are immutable, so the full device-to-host
        # gather happens once, not per sampled step
        # plain-int mirrors for snapshot() (registry counters may be
        # shared/labelled; these are THIS auditor's view) — fixed keys
        self._launches = {p: 0 for p in AUDIT_PROGRAMS}
        self._divergences = {k: 0 for k in DIVERGENCE_KINDS}
        self._nonfinite_values = 0
        self._oracle_failures = 0
        if not self.enabled or registry is None:
            # disabled: never touch the registry, so /metrics stays free
            # of every serving_audit_* / serving_logit_* series (tested)
            self._steps_c = self._div_c = self._nonf_c = None
            self._oracle_fail_c = None
            self._absmax_h = self._margin_h = self._absdiff_h = None
            return
        self._steps_c = {
            p: registry.counter(
                "serving_audit_steps_total",
                "program launches audited on sampled steps",
                **dict(self.labels, program=p))
            for p in AUDIT_PROGRAMS}
        self._div_c = {
            k: registry.counter(
                "serving_audit_divergence_total",
                "numerics-audit divergences by kind",
                **dict(self.labels, kind=k))
            for k in DIVERGENCE_KINDS}
        self._nonf_c = {
            p: registry.counter(
                "serving_audit_nonfinite_total",
                "non-finite values observed in step-program logits",
                **dict(self.labels, program=p))
            for p in AUDIT_PROGRAMS}
        self._oracle_fail_c = registry.counter(
            "serving_audit_oracle_failures_total",
            "shadow re-executions that crashed before comparing — a "
            "non-zero value means the audit net is NOT providing "
            "coverage",
            **self.labels)
        self._absmax_h = registry.histogram(
            "serving_logit_absmax",
            "max |logit| over a step program's output rows",
            buckets=_ABSMAX_BUCKETS, **self.labels)
        self._margin_h = registry.histogram(
            "serving_logit_margin",
            "smallest argmax margin (top1 - top2) over a program's rows",
            buckets=_MARGIN_BUCKETS, **self.labels)
        self._absdiff_h = registry.histogram(
            "serving_audit_logit_absdiff",
            "max |primary - oracle| logit diff per shadow re-execution",
            buckets=_ABSDIFF_BUCKETS, **self.labels)

    # --- wiring -------------------------------------------------------------
    def bind_flight(self, recorder, replica: Optional[str] = None) -> None:
        """Attach the fleet's flight recorder (and pin the replica
        identity divergence triggers/bundles carry — the router passes
        the replica INDEX, matching the flight rings)."""
        self.flight = recorder
        if replica is not None:
            self._replica = str(replica)

    # --- schedule -----------------------------------------------------------
    def begin_step(self) -> None:
        """Engine step opened: advance the deterministic sampling
        schedule."""
        if not self.enabled:
            return
        self._step += 1
        self._sampled = (self._step - 1) % self.cfg.sample_every == 0

    @property
    def sampled(self) -> bool:
        """True while the CURRENT engine step is shadow-audited."""
        return self.enabled and self._sampled

    @property
    def next_sampled(self) -> bool:
        """True if the engine step about to open will be shadow-audited:
        what :meth:`begin_step` is going to set, asked beforehand."""
        return self.enabled and self._step % self.cfg.sample_every == 0

    def wants_logits(self, program: str) -> bool:
        """True where a launch of ``program`` in the CURRENT engine step
        is compared with the shadow oracle: the one launch whose logits
        the engine brings to the host with its tokens.  Any other launch
        hands :meth:`observe_program` a callable, called only if a
        bundle is written."""
        return self.sampled and program in SHADOW_PROGRAMS

    @property
    def degraded(self) -> bool:
        return self._degraded

    @property
    def status(self) -> str:
        if not self.enabled:
            return "disabled"
        return "degraded" if self._degraded else "ok"

    # --- step-input capture -------------------------------------------------
    def snapshot_pools(self, k_pools: Sequence, v_pools: Sequence):
        """Capture the PRE-step KV pools for a shadow re-run.  On CPU
        (no donation) keeping the array references is enough — jax
        arrays are immutable and the step's outputs are NEW arrays.  On
        TPU the step donates the pool buffers, and under mp>1 the pools
        are mesh-sharded, so both gather to host numpy (the replicated
        single-shard form the reference program consumes)."""
        if not self.sampled:
            return None
        import jax

        if self.engine.mp > 1 or jax.default_backend() == "tpu":
            return (tuple(np.asarray(k) for k in k_pools),
                    tuple(np.asarray(v) for v in v_pools))
        return (tuple(k_pools), tuple(v_pools))

    # --- the audit hook (engine thread) -------------------------------------
    def observe_program(self, program: str, stats, bucket: Tuple[int, ...],
                        logits=None,
                        inputs: Optional[Dict[str, np.ndarray]] = None,
                        pre_pools=None,
                        requests: Sequence[Dict] = ()) -> Optional[str]:
        """One bucketed program launch: sentinel over the in-trace
        ``stats`` rows (every launch), plus — for a decode launch on a
        sampled step with captured inputs — the shadow-oracle
        differential re-execution.  ``logits`` is the host array of the
        launch's real rows where :meth:`wants_logits` asked for it, else
        a callable that fetches them: the sentinel needs only ``stats``,
        so the rows cross to the host for a non-finite bundle alone.
        Returns the divergence kind when one fired (``None``
        otherwise)."""
        if not self.enabled:
            return None
        stats = np.asarray(stats, np.float32).reshape(-1, 3)
        if self._absmax_h is not None and stats.size:
            self._absmax_h.observe(float(stats[:, 1].max()))
            self._margin_h.observe(float(stats[:, 2].min()))
        if self.sampled:
            with self._lock:
                self._launches[program] += 1
            if self._steps_c is not None:
                self._steps_c[program].inc()
        nonfinite = int(stats[:, 0].sum())
        if nonfinite:
            with self._lock:
                self._nonfinite_values += nonfinite
            if self._nonf_c is not None:
                self._nonf_c[program].inc(nonfinite)
            self._divergence(
                "nonfinite", program, bucket,
                info={"nonfinite_values": nonfinite,
                      "nonfinite_rows": int((stats[:, 0] > 0).sum()),
                      "requests": [str(r.get("id")) for r in requests]},
                arrays_fn=lambda: self._repro_arrays(
                    inputs, pre_pools,
                    primary=logits() if callable(logits) else logits))
            return "nonfinite"
        if self.wants_logits(program) and pre_pools is not None \
                and logits is not None:
            return self._shadow_step(program, pre_pools, inputs, logits,
                                     bucket, requests)
        return None

    # --- shadow oracle ------------------------------------------------------
    def _shadow_step(self, program, pre_pools, inputs, primary, bucket,
                     requests) -> Optional[str]:
        try:
            if program == "ragged":
                ref = self._reference_ragged(pre_pools, inputs)
            else:
                ref = self._reference_decode(pre_pools, inputs)
        except Exception as e:  # the oracle must never kill the engine —
            # but a crashed oracle means this step was NOT compared, so
            # it is counted loudly: "audited launches > 0 with zero
            # divergences" must never be satisfiable vacuously
            import sys
            import traceback

            with self._lock:
                self._oracle_failures += 1
            if self._oracle_fail_c is not None:
                self._oracle_fail_c.inc()
            sys.stderr.write("[audit] shadow re-execution failed:\n"
                             + traceback.format_exc())
            del e
            return None
        B = primary.shape[0]
        ref = ref[:B]
        diff = np.abs(ref - primary)
        maxdiff = float(diff.max()) if diff.size else 0.0
        if self._absdiff_h is not None:
            self._absdiff_h.observe(maxdiff)
        tok_p = primary.argmax(-1)
        tok_r = ref.argmax(-1)
        greedy = np.array([bool(r.get("greedy", True)) for r in requests]
                          or [True] * B)[:B]
        tol = self.cfg.logit_atol + self.cfg.logit_rtol * np.abs(ref)
        # an argmax flip between two logits the tolerance cannot tell
        # apart is a tie, not a token divergence (random bf16 weights at
        # a 128k vocabulary put top-1 and top-2 that close routinely)
        rows = np.arange(B)
        gap = ref[rows, tok_r] - ref[rows, tok_p]
        tie = gap <= tol[rows, tok_r] + tol[rows, tok_p]
        token_rows = [int(i) for i in range(B)
                      if greedy[i] and tok_p[i] != tok_r[i] and not tie[i]]
        logit_bad = bool((diff > tol).any())
        if token_rows:
            kind = "token"
        elif logit_bad:
            kind = "logit"
        else:
            return None
        self._divergence(
            kind, program, bucket,
            info={"max_abs_diff": round(maxdiff, 8),
                  "token_rows": token_rows,
                  "greedy_rows": [int(i) for i in range(B) if greedy[i]],
                  "primary_tokens": [int(t) for t in tok_p],
                  "reference_tokens": [int(t) for t in tok_r],
                  "requests": [str(r.get("id")) for r in requests]},
            arrays_fn=lambda: self._repro_arrays(
                inputs, pre_pools, primary=primary, reference=ref))
        return kind

    def _reference_decode(self, pre_pools, inputs) -> np.ndarray:
        """Re-execute one decode step through the reference program: the
        XLA gather attention path (``use_pallas=False`` — the oracle the
        Pallas kernel is differentially tested against), traced as a
        plain single-device jit.  For mp>1 engines this is the
        replicated single-shard re-run: pools arrive host-gathered
        (``snapshot_pools``), parameters are gathered here, and the
        trace runs under ``manual_sharding_mode`` so the model's GSPMD
        constraints no-op — one device computes the whole step the mesh
        program computed shard-wise."""
        import jax
        import jax.numpy as jnp

        eng = self.engine
        if self._jit_ref_decode is None:
            from ..core.tensor import Tensor
            from ..ops.paged_attention import PagedCache

            def ref_fn(param_vals, k_pools, v_pools, ids, pos, tables,
                       lens, slot_blocks, slot_offsets):
                caches = []
                for k, v in zip(k_pools, v_pools):
                    c = PagedCache(Tensor(k), Tensor(v))
                    c.route(tables, lens, slot_blocks, slot_offsets)
                    c.use_pallas = False  # the XLA gather oracle
                    caches.append(c)
                logits = eng._call_model(ids, caches, pos, param_vals)
                return logits[:, -1, :].astype(jnp.float32)

            # retraces per decode bucket, exactly like the engine's own
            # program — bounded by the same bucket set
            self._jit_ref_decode = jax.jit(ref_fn)
        return self._run_reference(
            self._jit_ref_decode, pre_pools,
            tuple(inputs[k] for k in ("ids", "pos", "tables", "lens",
                                      "slot_blocks", "slot_offsets")))

    def _reference_ragged(self, pre_pools, inputs) -> np.ndarray:
        """Re-execute one packed ragged step (ISSUE 11) through the
        reference program: the XLA gather path of
        ``ops.ragged_paged.ragged_oracle`` (``use_pallas=False``) with
        the SAME packing metadata, traced as a plain single-device jit —
        for mp>1 engines the replicated single-shard re-run of the
        shard_map kernel program."""
        import jax
        import jax.numpy as jnp

        eng = self.engine
        if self._jit_ref_ragged is None:
            from ..core.tensor import Tensor
            from ..ops.paged_attention import PagedCache

            def ref_fn(param_vals, k_pools, v_pools, ids, pos, seg_ids,
                       last_idx, tables, lens, slot_blocks,
                       slot_offsets):
                caches = []
                for k, v in zip(k_pools, v_pools):
                    c = PagedCache(Tensor(k), Tensor(v))
                    c.route(tables, lens, slot_blocks, slot_offsets,
                            start=pos[0], seg_ids=seg_ids)
                    c.use_pallas = False  # the XLA ragged oracle
                    caches.append(c)
                logits = eng._call_model(ids, caches, pos, param_vals)
                return jnp.take(logits[0], last_idx,
                                axis=0).astype(jnp.float32)

            # retraces per packed bucket — bounded by the collapsed
            # ragged bucket set
            self._jit_ref_ragged = jax.jit(ref_fn)
        return self._run_reference(
            self._jit_ref_ragged, pre_pools,
            tuple(inputs[k] for k in ("ids", "pos", "seg_ids",
                                      "last_idx", "tables", "lens",
                                      "slot_blocks", "slot_offsets")))

    def _run_reference(self, jit_ref, pre_pools, step_args) -> np.ndarray:
        """Shared reference-execution tail: host-gathered params (cached
        — serving weights are immutable) + thread-local manual-sharding
        trace window under mp>1, plain jit call otherwise."""
        eng = self.engine
        if eng.mp > 1:
            if self._ref_params is None:
                self._ref_params = tuple(
                    np.asarray(p._value) for p in eng._params)
            params = self._ref_params
        else:
            params = eng._param_vals()
        k_pools, v_pools = pre_pools
        if eng.mp > 1:
            from ..parallel.utils import manual_sharding_mode

            # manual mode is THREAD-LOCAL (parallel/utils.py), so this
            # trace window cannot leak into another replica's engine
            # thread tracing its own bucket concurrently
            with manual_sharding_mode():
                out = jit_ref(params, k_pools, v_pools, *step_args)
        else:
            out = jit_ref(params, k_pools, v_pools, *step_args)
        return np.asarray(out, np.float32)

    # --- divergence handling ------------------------------------------------
    @staticmethod
    def _repro_arrays(inputs, pre_pools, primary=None,
                      reference=None) -> Dict[str, np.ndarray]:
        arrays: Dict[str, np.ndarray] = {}
        for k, v in (inputs or {}).items():
            arrays[k] = np.asarray(v)
        if pre_pools is not None:
            k_pools, v_pools = pre_pools
            arrays["k_pools"] = np.stack([np.asarray(k) for k in k_pools])
            arrays["v_pools"] = np.stack([np.asarray(v) for v in v_pools])
        if primary is not None:
            arrays["primary_logits"] = np.asarray(primary, np.float32)
        if reference is not None:
            arrays["reference_logits"] = np.asarray(reference, np.float32)
        return arrays

    def _divergence(self, kind: str, program: str, bucket, info: Dict,
                    arrays_fn) -> None:
        entry = {
            "kind": kind, "program": program,
            "bucket": [int(b) for b in bucket],
            "step": self._step, "replica": self._replica,
            "unix": round(time.time(), 6), **info,
        }
        key = (kind, program)
        repro = None
        now = time.perf_counter()
        with self._lock:
            # degraded flips in the SAME critical section the counter
            # moves: a concurrent snapshot() can never read
            # divergences > 0 next to status "ok"
            self._divergences[kind] += 1
            self._degraded = True
            last_try = self._attempt_ts.get(key)
            want = (key not in self._fired
                    and self._repro_count < self.cfg.max_repros
                    and (last_try is None
                         or now - last_try >= self._attempt_cooldown_s))
            if want:
                self._attempt_ts[key] = now
        if self._div_c is not None:
            self._div_c[kind].inc()
        if want and self._repro_dir() is not None:
            # arrays are materialized (full pool copies) ONLY when a
            # dump will actually be attempted — a sustained-degraded
            # state costs no copies once the bundle is written, and a
            # persistently FAILING dump retries on the attempt cooldown,
            # not on every diverging launch
            repro = self._dump_repro(kind, program, entry, arrays_fn())
        if repro is not None:
            entry["repro"] = repro
            with self._lock:
                # fired-once is recorded on SUCCESS, not attempt: a
                # transient dump failure (disk full, dir unwritable)
                # must not permanently suppress the one actionable
                # bundle for this divergence kind
                self._fired.add(key)
                self._repros.append(repro)
                self._repro_count += 1
        with self._lock:
            self.last_divergence = entry
        if self.flight is not None:
            # the PR 7 flight machinery captures the registry snapshot +
            # the request timelines touching this step (the in-flight
            # set of THIS replica) next to the .npz repro
            trigger = "nonfinite" if kind == "nonfinite" else "divergence"
            try:
                self.flight.trigger(
                    trigger, replica=self._replica,
                    detail=json.dumps(entry, default=str))
            except Exception:
                pass  # swallow-ok: telemetry must never take down the engine thread; the divergence itself is already counted + degraded above

    def _repro_dir(self) -> Optional[str]:
        if self.cfg.repro_dir is not None:
            return self.cfg.repro_dir
        if self.flight is not None:
            return self.flight.cfg.dump_dir
        return None

    def _dump_repro(self, kind: str, program: str, meta: Dict,
                    arrays: Dict[str, np.ndarray]) -> Optional[str]:
        """Atomic, size-capped ``.npz`` repro: step inputs + pre-step
        pools + primary/reference logits + JSON metadata.  Arrays are
        dropped biggest-first until the bundle fits
        ``max_repro_bytes``; the metadata records what was dropped."""
        d = self._repro_dir()
        if d is None:
            return None
        eng = self.engine
        self._seq += 1
        path = os.path.join(
            d, f"audit_{kind}_{program}_r{self._replica}_"
               f"{self._seq:03d}.npz")
        arrays = dict(arrays)
        dropped: List[str] = []
        cfg_meta = {
            "sample_every": self.cfg.sample_every,
            "logit_atol": self.cfg.logit_atol,
            "logit_rtol": self.cfg.logit_rtol,
            "block_size": eng.block_size,
            "num_blocks": eng.num_blocks,
            "mp": eng.mp,
            "use_pallas_paged": bool(eng._use_pallas),
        }
        while True:
            m = dict(meta, config=cfg_meta, dropped=list(dropped),
                     bundle="paddle_tpu.audit_repro")
            buf = io.BytesIO()
            np.savez_compressed(buf, meta=np.array(json.dumps(
                m, default=str)), **arrays)
            if buf.tell() <= self.cfg.max_repro_bytes:
                break
            for k in _REPRO_DROP_ORDER:
                if k in arrays:
                    dropped.append(k)
                    del arrays[k]
                    break
            else:
                return None  # even the minimal bundle exceeds the cap
        try:
            os.makedirs(d, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(buf.getvalue())
            os.replace(tmp, path)  # atomic: no torn repro on crash
        except Exception:
            import sys
            import traceback

            sys.stderr.write("[audit] repro dump failed:\n"
                             + traceback.format_exc())
            return None
        return path

    # --- inspection ---------------------------------------------------------
    @property
    def steps(self) -> int:
        return self._step

    @property
    def repros(self) -> List[str]:
        with self._lock:
            return list(self._repros)

    def snapshot(self) -> Dict:
        """JSON-able state for ``GET /v1/debug/audit`` and tests.  Reads
        everything under the auditor lock so the degraded flag and the
        divergence counters are always mutually consistent."""
        with self._lock:
            last = (dict(self.last_divergence)
                    if self.last_divergence is not None else None)
            return {
                "replica": self._replica,
                "enabled": self.enabled,
                "status": self.status,
                "sample_every": self.cfg.sample_every,
                "steps": self._step,
                "audited_launches": dict(self._launches),
                "divergences": dict(self._divergences),
                "nonfinite_values": self._nonfinite_values,
                "oracle_failures": self._oracle_failures,
                "last_divergence": last,
                "repros": list(self._repros),
            }


# --- repro load / replay ----------------------------------------------------

def load_repro(path: str) -> Dict:
    """Read a ``.npz`` repro back: ``{"meta": dict, "arrays": {...}}``."""
    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["meta"]))
        arrays = {k: np.array(z[k]) for k in z.files if k != "meta"}
    return {"meta": meta, "arrays": arrays}


def replay_repro(path: str, engine) -> Dict:
    """Replay a repro bundle against ``engine`` (same model/weights as
    the auditing engine): re-execute the reference program on the stored
    step inputs + pre-step pools and check the recorded mismatch
    reproduces.  For ``nonfinite`` repros (or bundles whose pools were
    size-capped away) the verdict comes from the stored arrays.
    Returns ``{"kind", "program", "reproduced", ...}``."""
    r = load_repro(path)
    meta, a = r["meta"], r["arrays"]
    kind, program = meta["kind"], meta["program"]
    out: Dict = {"kind": kind, "program": program}
    primary = a.get("primary_logits")
    if kind == "nonfinite":
        out["reproduced"] = (primary is not None
                             and not np.isfinite(primary).all())
        return out
    if program == "decode" and "k_pools" in a and "v_pools" in a:
        ref = engine.audit._reference_decode(
            (tuple(a["k_pools"]), tuple(a["v_pools"])),
            {k: a[k] for k in ("ids", "pos", "tables", "lens",
                               "slot_blocks", "slot_offsets")})
        ref = ref[:primary.shape[0]] if primary is not None else ref
        out["replayed"] = True
    elif program == "ragged" and "k_pools" in a and "v_pools" in a:
        ref = engine.audit._reference_ragged(
            (tuple(a["k_pools"]), tuple(a["v_pools"])),
            {k: a[k] for k in ("ids", "pos", "seg_ids", "last_idx",
                               "tables", "lens", "slot_blocks",
                               "slot_offsets")})
        ref = ref[:primary.shape[0]] if primary is not None else ref
        out["replayed"] = True
    else:
        ref = a.get("reference_logits")
        out["replayed"] = False
    if ref is None or primary is None:
        out["reproduced"] = False
        out["note"] = "arrays truncated below the replayable minimum"
        return out
    diff = np.abs(ref - primary)
    out["max_abs_diff"] = float(diff.max()) if diff.size else 0.0
    if kind == "token":
        # compare only the greedy rows the original divergence was
        # allowed to claim — a near-tie argmax flip on a temperature-
        # sampled row must not fake a reproduction
        rows = meta.get("greedy_rows")
        if rows is None:
            rows = list(range(primary.shape[0]))
        rows = [r for r in rows if r < primary.shape[0]]
        out["reproduced"] = bool(rows) and bool(
            (ref[rows].argmax(-1) != primary[rows].argmax(-1)).any())
    else:
        # compare under the tolerances the divergence was DETECTED with
        # (recorded in the bundle) — the replay engine's own audit
        # config may be looser (or auditing disabled entirely)
        rec = meta.get("config", {})
        atol = float(rec.get("logit_atol", engine.audit.cfg.logit_atol))
        rtol = float(rec.get("logit_rtol", engine.audit.cfg.logit_rtol))
        tol = atol + rtol * np.abs(ref)
        out["reproduced"] = bool((diff > tol).any())
    return out
