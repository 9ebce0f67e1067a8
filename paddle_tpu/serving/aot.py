"""AOT serving artifacts: zero-trace engine boot (ISSUE 15 tentpole).

Compile time is the measured majority of cold-phase wall time
(``serving_compile_seconds_total``, ``GET /v1/debug/compiles``), and the
self-healing fleet (PR 11) pays it again on every replica rebuild.  The
bucketed fixed-shape discipline that bounds the compile COUNT also makes
the whole program set **enumerable up front**: every shape the engine
can ever dispatch is a point in a small power-of-two lattice derived
from the deployment config (pool capacity, scheduler caps, chunk
budgets).  This module closes the loop the ROADMAP names — MPK's
compile-once artifact (PAPERS.md #5), the deployment shape the
Julia-to-TPU work (#4) and the repo's own 8B proof
(``tools/aot_lower_8b.py``) already validated:

* :func:`enumerate_buckets` walks that closed universe — the legacy
  three program families (one-shot ``prefill`` / ``chunk``\\ ed prefill /
  batched ``decode``), or the single ``ragged`` family when the engine
  serves ``EngineConfig.unified_step=True``;
* :meth:`AotArtifact.save` lowers each (program, bucket) through
  ``jax.export`` — the engine's OWN jitted entry points, mesh-spanning
  in/out shardings included, traced abstractly (no weights move) — and
  serializes StableHLO programs plus a versioned **manifest** (framework
  + jax versions, platform, model-config hash, mp degree, pool/dtype
  geometry, scheduler caps, bucket sets, kernel-routing/autotune
  decisions) into an artifact directory;
* :meth:`AotArtifact.load` deserializes every program eagerly (a corrupt
  artifact fails at load, not mid-request) and
  :meth:`AotArtifact.validate` applies the **mismatch matrix**: wrong mp
  degree, bucket set, model hash, pool geometry, dtype, kernel routing,
  unified flag, platform or jax version all raise
  :class:`AotManifestMismatch` — a stale artifact fails LOUDLY at boot
  instead of silently retracing;
* :meth:`AotArtifact.call` replaces the engine's jit dispatch: the
  in-trace retrace counters provably never move (tests assert ``== 0``
  end to end), and a bucket outside the saved universe raises
  :class:`AotBucketMissing` naming the shape — never a silent retrace.

The loaded ``Exported`` objects cache their compiled executables
in-process, so ONE artifact shared across a dp fleet
(``EngineConfig.aot``; the router refuses per-replica loads) compiles
each program once fleet-wide — and a supervisor-rebuilt replica
(:meth:`~paddle_tpu.serving.resilience.FleetSupervisor._rebuild` rebinds
the router's artifact) restarts onto warm executables in milliseconds
with zero post-restart traces, instead of re-paying the whole compile
bill mid-incident.

Everything round-trips on CPU meshes (``jax.export`` lowers and replays
mesh-spanning programs with forced host devices), so the contract —
token-identical greedy serving with trace counters pinned at zero — is
tier-1-provable; ``tests/test_zzzzz_aot.py``.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax import export as jax_export

from .scheduler import bucket_size

# v2 (ISSUE 18): every program takes the per-row sampling quartet
# (temps f32, top_ks i32, top_ps f32, keys u32[...,2]) and returns token
# ids as output 0 — v1 artifacts predate in-trace sampling and refuse to
# load rather than serve the wrong signature.
# v3 (ISSUE 19): the "burst" family (device-resident multi-step decode,
# bucketed on (rows, burst-length)) joins the saved universe when
# ``EngineConfig.burst_steps >= 2``, and the manifest records
# ``burst_steps`` — v2 artifacts predate the family and refuse to load.
ARTIFACT_VERSION = 3
MANIFEST_NAME = "manifest.json"
_PROGRAM_DIR = "programs"

# metric names this module owns (registered by the StepProfiler when an
# artifact is bound — tools/check_metrics_docs lints that each appears
# in README's metrics table)
METRIC_NAMES = (
    "serving_aot_hits_total",
    "serving_aot_load_seconds",
    # ISSUE 16: wall seconds spent executing every saved program once
    # (--aot-warm at save time / --warm at worker boot)
    "serving_aot_warm_seconds",
)


class AotError(RuntimeError):
    """Base class for artifact save/load/dispatch failures."""


class AotManifestMismatch(AotError):
    """The artifact was built for a DIFFERENT deployment (mp degree,
    bucket set, model hash, pool geometry, jax version, ...) — loading
    it would silently retrace or serve wrong shapes, so boot fails
    loudly instead."""


class AotBucketMissing(AotError):
    """A serving step needed a (program, bucket) shape outside the
    artifact's saved universe — the zero-trace contract refuses to fall
    back to a silent retrace; re-save with a larger ``max_seq_len`` /
    matching scheduler caps."""


def _pow2_upto(cap: int) -> List[int]:
    """[1, 2, 4, ..., bucket_size(cap)] — the bucket lattice axis."""
    out, b = [], 1
    top = bucket_size(max(1, int(cap)))
    while b <= top:
        out.append(b)
        b <<= 1
    return out


def _max_seq_cap(engine, max_seq_len: Optional[int]) -> int:
    """THE max-seq clamp, shared by :meth:`AotArtifact.save` (manifest
    record) and :func:`enumerate_buckets` (lattice bound) so the two can
    never disagree: the pool capacity ``(num_blocks - 1) * block_size``
    caps whatever the caller asked for — no sequence can outgrow the
    pool."""
    pool_cap = max(1, (engine.num_blocks - 1) * engine.block_size)
    return min(int(max_seq_len), pool_cap) if max_seq_len else pool_cap


def enumerate_buckets(engine, max_seq_len: Optional[int] = None,
                      ) -> List[Tuple[str, Tuple[int, ...]]]:
    """The CLOSED set of (program, bucket) shapes ``engine`` can ever
    dispatch for sequences up to ``max_seq_len`` tokens (default: the
    pool capacity ``(num_blocks - 1) * block_size`` — no sequence can
    outgrow the pool).  Derived from the same bucketing rules the
    dispatch sites use (``scheduler.bucket_size`` over batch / token /
    table-width axes), so a workload within the caps can never step
    outside this universe — which is exactly what makes the zero-trace
    AOT contract provable rather than probabilistic."""
    sched = engine.scheduler.config
    bs = engine.block_size
    max_seq = _max_seq_cap(engine, max_seq_len)
    # table width covers the whole sequence: ceil(max_seq / block_size)
    widths = _pow2_upto((max_seq + bs - 1) // bs)
    out: List[Tuple[str, Tuple[int, ...]]] = []
    # decode-burst family (ISSUE 19): a bounded two-axis lattice —
    # (decode-rows bucket, burst-length bucket) — independent of the
    # unified flag (the burst path runs in both dispatch modes).  The
    # table width is NOT an axis: burst programs pin it to the one
    # max_seq-derived width bucket (engine._burst_width), so bursts
    # never change shape as rows cross block boundaries mid-loop.
    # Length buckets start at 2: the engine never launches a 1-step
    # burst (that is just decode with padding).
    burst_steps = int(getattr(engine, "_burst_steps", 0) or 0)
    if burst_steps >= 2:
        for b in _pow2_upto(sched.max_num_seqs):
            for n in _pow2_upto(burst_steps):
                if n >= 2:
                    out.append(("burst", (b, n)))
    pf_budget = sched.max_prefill_tokens_per_step
    if getattr(engine, "_unified", False):
        # unified ragged family (PR 10): ONE packed launch per step.
        # Decode rows are never split, so the token bucket is bounded by
        # bucket_size(max(budget, max_num_seqs)).  Without a packed
        # budget the launch aggregates EVERY row's prefill work: the
        # per-step prefill total is capped by the chunk budget when one
        # is set (it is a single budget decremented across all planned
        # chunks — and it can exceed one sequence's max_seq by spreading
        # over rows), else only by every running row prefilling its
        # whole remaining prompt at once (max_num_seqs * max_seq — e.g.
        # a preemption-recompute wave packing with fresh admissions).
        total = sched.max_tokens_per_step
        if total is not None:
            tmax = max(int(total), sched.max_num_seqs)
        else:
            pf_cap = sched.max_num_seqs * max_seq
            if pf_budget is not None:
                pf_cap = min(int(pf_budget), pf_cap)
            tmax = sched.max_num_seqs + pf_cap
        for t in _pow2_upto(tmax):
            for w in widths:
                out.append(("ragged", (t, w)))
        return out
    # legacy three families.  One-shot prefill runs only when the whole
    # prompt fits one planning pass (n == target <= the chunk budget).
    oneshot = min(pf_budget or max_seq, max_seq)
    for t in _pow2_upto(oneshot):
        out.append(("prefill", (t,)))
    for c in _pow2_upto(oneshot):
        for w in widths:
            out.append(("chunk", (c, w)))
    # a model whose decode tables have ONE width: its decode step reads
    # the same whatever the width (engine.decode_table_width)
    pinned = engine.decode_table_width
    for b in _pow2_upto(sched.max_num_seqs):
        for w in [pinned] if pinned else widths:
            out.append(("decode", (b, w)))
    return out


def _key_str(program: str, bucket: Tuple[int, ...]) -> str:
    return program + "_" + "x".join(str(int(b)) for b in bucket)


def model_config_hash(engine) -> str:
    """Deterministic digest of the deployment's MODEL IDENTITY: the
    model config's scalar fields plus every parameter's (shape, dtype)
    — the shapes the exported programs were traced over.  Weight VALUES
    are deliberately not hashed (an artifact serves any checkpoint of
    the same architecture; weights enter the programs as arguments)."""
    cfg = engine.model.config
    fields = {k: v for k, v in sorted(vars(cfg).items())
              if isinstance(v, (int, float, str, bool, type(None)))}
    params = [[list(np.shape(p._value)), str(np.dtype(p._value.dtype))]
              for p in engine._params]
    blob = json.dumps({"config": fields, "params": params},
                      sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


def _autotune_decisions(engine) -> Dict:
    """Kernel-routing + autotune decisions baked into the exported
    programs — recorded so a load under DIFFERENT routing fails loudly
    (the StableHLO already committed to a path; the engine config would
    be silently dead otherwise)."""
    dec = {
        "use_pallas_paged": engine.engine_config.use_pallas_paged,
        "unified_step": bool(getattr(engine, "_unified", False)),
    }
    try:  # best-effort snapshot of the committed op-autotune table
        from ..ops import autotune as _at

        table = getattr(_at, "_RESULTS", None)
        if isinstance(table, dict):
            dec["op_autotune_keys"] = sorted(str(k) for k in table)[:64]
    except Exception:
        pass  # swallow-ok: the op-autotune table is informational in the manifest; its absence must not block a save
    return dec


def _arg_specs(engine, program: str, bucket: Tuple[int, ...]):
    """Abstract ``ShapeDtypeStruct`` argument pytree for one (program,
    bucket) — mirrors exactly what the engine's dispatch sites build
    (``_prefill`` / ``_decode`` / ``_unified_exec``), with integer
    routing arrays in their CANONICALIZED int32 form (x64 is off; the
    traced program only ever sees int32)."""
    s = jax.ShapeDtypeStruct
    i32 = np.int32

    def sampling(n):
        # ISSUE 18: the per-row sampling quartet every program family now
        # consumes as its trailing arguments (SamplingPack.arrays()) —
        # (temps f32, top_ks i32, top_ps f32, keys u32[n, 2])
        return (s((n,), np.float32), s((n,), i32), s((n,), np.float32),
                s((n, 2), np.uint32))

    params = tuple(s(np.shape(p._value), np.dtype(p._value.dtype))
                   for p in engine._params)
    pools, v_pools = (tuple(s(tuple(p.shape), np.dtype(p.dtype)) for p in side)
                      for side in (engine._k_pools, engine._v_pools))
    head = (params, pools, v_pools)
    if program == "decode":
        Bb, Wb = bucket
        return head + (s((Bb, 1), i32), s((Bb,), i32), s((Bb, Wb), i32),
                       s((Bb,), i32), s((Bb,), i32), s((Bb,), i32)) \
            + sampling(Bb)
    if program == "prefill":
        (Tb,) = bucket
        return head + (s((1, Tb), i32), s((), i32), s((Tb,), i32),
                       s((Tb,), i32)) + sampling(1)
    if program == "chunk":
        Wb, TWb = bucket
        return head + (s((1, Wb), i32), s((), i32), s((), i32),
                       s((1, TWb), i32), s((1,), i32), s((1, Wb), i32),
                       s((1, Wb), i32)) + sampling(1)
    if program == "ragged":
        Tb, TWb = bucket
        return head + (s((1, Tb), i32), s((1, Tb), i32), s((Tb,), i32),
                       s((Tb,), i32), s((Tb, TWb), i32), s((Tb,), i32),
                       s((Tb,), i32), s((Tb,), i32)) + sampling(Tb)
    if program == "burst":
        # (ids, pos, tables, lens, slot_blocks, slot_offsets, n_steps,
        #  active, eos_ids) + sampling quartet — ISSUE 19.  The table
        # width is the engine's pinned burst width (max_seq-derived;
        # save() aligns it with the manifest's max_seq_len before
        # lowering, bind_aot() re-derives the same value at load).
        Bb, Nb = bucket
        W = engine._burst_width
        return head + (s((Bb, 1), i32), s((Bb,), i32), s((Bb, W), i32),
                       s((Bb,), i32), s((Bb, Nb), i32), s((Bb, Nb), i32),
                       s((), i32), s((Bb,), np.bool_), s((Bb,), i32)) \
            + sampling(Bb)
    raise AotError(f"unknown program family {program!r}")


def _jit_for(engine, program: str):
    return {"decode": engine._jit_decode,
            "prefill": engine._jit_prefill,
            "chunk": engine._jit_chunk_prefill,
            "ragged": engine._jit_unified,
            "burst": engine._jit_burst}[program]


class AotArtifact:
    """One saved-or-loaded serving program set + its manifest.

    Save side: :meth:`save` traces + lowers every bucket of a BUILDER
    engine (its retrace counters advance — that engine is a compile
    host, not a serving replica) and writes ``programs/*.stablehlo``
    first, the manifest last via tmp→rename, so a torn save can never
    load.  Load side: :meth:`load` → :meth:`validate` (engine build
    calls it) → :meth:`call` at every step dispatch.  The deserialized
    ``Exported`` objects cache compiled executables per process, so the
    artifact object is SHARED — across dp replicas and across
    supervisor rebuilds — and each program compiles once fleet-wide."""

    def __init__(self, manifest: Dict, programs: Dict, path: str,
                 load_seconds: float = 0.0):
        self.manifest = manifest
        # (program, bucket...) -> deserialized Exported
        self._programs = programs
        self.path = path
        self.load_seconds = float(load_seconds)
        # registries that already observed this artifact's load wall
        # (WeakSet: a registry's death must not pin it here).  ONE disk
        # load must land as ONE serving_aot_load_seconds sample per
        # registry, however many replicas/rebuilds bind the artifact.
        self._observed_registries = weakref.WeakSet()

    def mark_load_observed(self, registry) -> bool:
        """True exactly once per (this artifact, ``registry``): the
        caller that gets True records ``serving_aot_load_seconds``;
        later binds of the same loaded artifact into the same registry
        (dp replicas, supervisor rebuilds) must not re-observe a disk
        load that happened once."""
        if registry in self._observed_registries:
            return False
        self._observed_registries.add(registry)
        return True

    # --- inspection ---------------------------------------------------------
    @property
    def program_count(self) -> int:
        return len(self._programs)

    @property
    def bucket_sets(self) -> Dict[str, List[Tuple[int, ...]]]:
        out: Dict[str, List] = {}
        for key in self._programs:
            out.setdefault(key[0], []).append(tuple(key[1:]))
        return {p: sorted(v) for p, v in sorted(out.items())}

    def describe(self) -> Dict:
        m = self.manifest
        return {
            "path": self.path,
            "programs": self.program_count,
            "families": {p: len(v) for p, v in self.bucket_sets.items()},
            "mp": m["mp"], "dtype": m["dtype"],
            "num_blocks": m["num_blocks"], "block_size": m["block_size"],
            "max_seq_len": m["max_seq_len"],
            "unified_step": m["autotune"]["unified_step"],
            "burst_steps": m.get("burst_steps", 0),
            "model_hash": m["model_hash"][:16],
            "jax_version": m["jax_version"],
            "load_seconds": round(self.load_seconds, 4),
        }

    # --- save ---------------------------------------------------------------
    @classmethod
    def save(cls, engine, path: str,
             max_seq_len: Optional[int] = None) -> "AotArtifact":
        """Lower + serialize ``engine``'s full bucketed program set into
        the ``path`` directory.  ``max_seq_len`` bounds the universe
        (default: pool capacity).  The saved set is always the full
        :func:`enumerate_buckets` lattice — :meth:`validate` requires
        exactly that coverage at load, so a pruned save could never
        bind."""
        t0 = time.perf_counter()
        sched = engine.scheduler.config
        max_seq = _max_seq_cap(engine, max_seq_len)
        # burst programs (ISSUE 19) pin their table width to ONE
        # max_seq-derived bucket; align the builder engine's width with
        # the universe being saved so the lowered shapes match what
        # bind_aot() re-derives from the manifest at load.  (The builder
        # is a compile host — narrowing its launch width is fine.)
        engine._burst_width = bucket_size(
            max(1, (max_seq + engine.block_size - 1) // engine.block_size))
        buckets = enumerate_buckets(engine, max_seq)
        # the whole artifact is STAGED next to its destination and
        # swapped in only after the manifest commit: a re-save that dies
        # midway (a bucket fails to lower, the process is killed) leaves
        # the previous good artifact untouched and loadable — and a
        # smaller universe can never strand orphaned blobs from the old
        # one, because the staged dir starts empty
        stage = path.rstrip("/") + ".staging"
        if os.path.exists(stage):
            shutil.rmtree(stage)
        prog_dir = os.path.join(stage, _PROGRAM_DIR)
        os.makedirs(prog_dir)
        programs: Dict = {}
        prog_meta: Dict[str, Dict] = {}
        try:
            for program, bucket in buckets:
                bucket = tuple(int(b) for b in bucket)
                exported = jax_export.export(_jit_for(engine, program))(
                    *_arg_specs(engine, program, bucket))
                blob = exported.serialize()
                key = _key_str(program, bucket)
                fname = key + ".stablehlo"
                with open(os.path.join(prog_dir, fname), "wb") as f:
                    f.write(blob)
                programs[(program,) + bucket] = exported
                prog_meta[key] = {"program": program,
                                  "bucket": list(bucket),
                                  "file": _PROGRAM_DIR + "/" + fname,
                                  "bytes": len(blob)}
        except BaseException:
            shutil.rmtree(stage, ignore_errors=True)
            raise
        import paddle_tpu as _p

        manifest = {
            "artifact_version": ARTIFACT_VERSION,
            "framework": "paddle_tpu",
            "framework_version": str(_p.__version__),
            "jax_version": jax.__version__,
            "platform": jax.default_backend(),
            "created_unix": round(time.time(), 3),
            "model_hash": model_config_hash(engine),
            "mp": int(engine.mp),
            "dtype": str(np.dtype(engine._pool_dtype)),
            "num_blocks": int(engine.num_blocks),
            "block_size": int(engine.block_size),
            "num_layers": len(engine._k_pools),
            "max_seq_len": int(max_seq),
            "scheduler": {
                "max_num_seqs": sched.max_num_seqs,
                "max_prefill_tokens_per_step":
                    sched.max_prefill_tokens_per_step,
                "max_tokens_per_step": sched.max_tokens_per_step,
            },
            # ISSUE 19: the burst-length cap the lattice was enumerated
            # under.  Not a validate() mismatch row — a burst-off engine
            # may load a burst-on artifact (superset), and an engine
            # with a LARGER burst_steps fails the bucket-coverage check.
            "burst_steps": int(getattr(engine, "_burst_steps", 0) or 0),
            "autotune": _autotune_decisions(engine),
            # ISSUE 18: recorded for inspection only — deliberately NOT a
            # validate() mismatch row.  Spec decode packs verify chunks
            # into the SAME ragged bucket lattice (no new family, no new
            # axis), so one artifact serves spec-on and spec-off engines
            # alike; refusing on a spec flip would break that contract.
            "spec": (engine.spec.config.manifest_dict()
                     if getattr(engine, "spec", None) is not None
                     else None),
            "programs": prog_meta,
            "save_seconds": round(time.perf_counter() - t0, 4),
        }
        # manifest LAST, atomically: its presence is the commit record —
        # a save killed mid-way leaves programs but no manifest, and
        # load() refuses cleanly instead of serving half a universe
        tmp = os.path.join(stage, MANIFEST_NAME + ".tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)
        os.replace(tmp, os.path.join(stage, MANIFEST_NAME))
        # swap the committed stage into place; the prior artifact (if
        # any) stays loadable right up to this point
        if os.path.exists(path):
            old = path.rstrip("/") + ".old"
            if os.path.exists(old):
                shutil.rmtree(old)
            os.rename(path, old)
            os.rename(stage, path)
            shutil.rmtree(old)
        else:
            os.rename(stage, path)
        return cls(manifest, programs, path)

    # --- load ---------------------------------------------------------------
    @classmethod
    def load(cls, path: str) -> "AotArtifact":
        """Read the manifest + deserialize EVERY program eagerly.
        Environment mismatches (artifact version, jax version, platform)
        fail here; deployment-shape mismatches fail in
        :meth:`validate` once an engine exists to compare against."""
        t0 = time.perf_counter()
        mpath = os.path.join(path, MANIFEST_NAME)
        if not os.path.exists(mpath):
            raise AotError(
                f"no AOT artifact at {path!r}: {MANIFEST_NAME} missing "
                "(unsaved, or a save was torn before commit)")
        with open(mpath) as f:
            manifest = json.load(f)
        mismatches: List[str] = []
        if manifest.get("artifact_version") != ARTIFACT_VERSION:
            mismatches.append(
                f"artifact_version {manifest.get('artifact_version')!r} "
                f"!= supported {ARTIFACT_VERSION}")
        if manifest.get("jax_version") != jax.__version__:
            mismatches.append(
                f"artifact was lowered under jax "
                f"{manifest.get('jax_version')!r} but "
                f"{jax.__version__} is installed (stale artifact — "
                "re-save after upgrading)")
        if manifest.get("platform") != jax.default_backend():
            mismatches.append(
                f"artifact platform {manifest.get('platform')!r} != "
                f"running backend {jax.default_backend()!r}")
        if mismatches:
            raise AotManifestMismatch(
                f"refusing to load AOT artifact {path!r}:\n  - "
                + "\n  - ".join(mismatches))
        programs: Dict = {}
        for key, meta in manifest["programs"].items():
            fpath = os.path.join(path, meta["file"])
            try:
                with open(fpath, "rb") as f:
                    programs[(meta["program"],)
                             + tuple(meta["bucket"])] = jax_export.deserialize(
                                 f.read())
            except Exception as e:
                raise AotError(
                    f"AOT artifact {path!r}: program {key!r} failed to "
                    f"deserialize from {meta['file']!r}: {e}") from e
        return cls(manifest, programs, path,
                   load_seconds=time.perf_counter() - t0)

    # --- validation (the mismatch matrix) -----------------------------------
    def validate(self, engine) -> None:
        """Raise :class:`AotManifestMismatch` naming EVERY way this
        artifact disagrees with ``engine``'s deployment — mp degree,
        model hash, pool geometry, dtype, kernel routing, unified flag,
        and the derived bucket universe.  A mismatch here would
        otherwise surface as a silent retrace (or a shape error deep in
        a step) — failing at boot is the whole point."""
        m = self.manifest
        mm: List[str] = []
        if m["mp"] != engine.mp:
            mm.append(f"mp degree: artifact {m['mp']}, engine {engine.mp}")
        if m["model_hash"] != model_config_hash(engine):
            mm.append("model-config hash: the artifact was lowered for a "
                      "different architecture/parameter layout")
        if m["num_blocks"] != engine.num_blocks \
                or m["block_size"] != engine.block_size:
            mm.append(
                f"pool geometry: artifact {m['num_blocks']}x"
                f"{m['block_size']}, engine {engine.num_blocks}x"
                f"{engine.block_size} (pool tensors are program inputs "
                "— shapes must match exactly)")
        if m["num_layers"] != len(engine._k_pools):
            mm.append(f"layer count: artifact {m['num_layers']}, engine "
                      f"{len(engine._k_pools)}")
        if m["dtype"] != str(np.dtype(engine._pool_dtype)):
            mm.append(f"pool dtype: artifact {m['dtype']}, engine "
                      f"{np.dtype(engine._pool_dtype)}")
        if bool(m["autotune"]["unified_step"]) != bool(engine._unified):
            mm.append(
                f"program family: artifact saved "
                f"unified_step={m['autotune']['unified_step']}, engine "
                f"runs unified_step={engine._unified}")
        if m["autotune"]["use_pallas_paged"] \
                != engine.engine_config.use_pallas_paged:
            mm.append(
                f"kernel routing: artifact baked use_pallas_paged="
                f"{m['autotune']['use_pallas_paged']}, engine configured "
                f"{engine.engine_config.use_pallas_paged} (the StableHLO "
                "already committed to a path — the config flip would be "
                "silently dead)")
        if not mm:
            # bucket-set coverage LAST (it needs an engine whose family
            # flag already matched): everything the engine's caps can
            # dispatch within the artifact's max_seq_len must be saved
            required = set(
                (p,) + tuple(b) for p, b in enumerate_buckets(
                    engine, max_seq_len=m["max_seq_len"]))
            missing = sorted(required - set(self._programs))
            if missing:
                mm.append(
                    f"bucket set: engine scheduler caps need "
                    f"{len(missing)} program shape(s) the artifact never "
                    f"saved (first: {missing[:4]}) — scheduler config "
                    "drifted since the save")
        if mm:
            raise AotManifestMismatch(
                f"AOT artifact {self.path!r} does not match this engine:"
                + "".join(f"\n  - {x}" for x in mm)
                + "\n(re-save the artifact for THIS deployment; a "
                "mismatched artifact would retrace silently)")

    # --- serving dispatch ---------------------------------------------------
    def call(self, program: str, bucket: Tuple[int, ...], *args):
        """Run one saved program.  Host-side integer arrays are
        canonicalized to the exported int32 avals (the engine builds
        int64 token ids; x64-off tracing saw int32) — ``Exported.call``
        is strict where ``jit`` canonicalizes.  Returns the engine's
        step-output tuple ``(tokens, logits, logit_stats, k_pools,
        v_pools)`` with the pool pytrees coerced back to tuples."""
        key = (program,) + tuple(int(b) for b in bucket)
        exported = self._programs.get(key)
        if exported is None:
            saved = self.bucket_sets
            raise AotBucketMissing(
                f"step program {program!r} bucket "
                f"{tuple(int(b) for b in bucket)} is outside the "
                f"artifact's saved universe (max_seq_len="
                f"{self.manifest['max_seq_len']}, saved "
                f"{ {p: len(v) for p, v in saved.items()} }); the "
                "zero-trace contract refuses to retrace — re-save with "
                "a larger max_seq_len / matching scheduler caps")
        flat, tree = jax.tree_util.tree_flatten(args)
        avals = exported.in_avals
        if len(flat) != len(avals):
            raise AotError(
                f"{program} {bucket}: argument count {len(flat)} != "
                f"exported {len(avals)} (framework drift — re-save)")
        coerced = [
            np.asarray(x, aval.dtype)
            if (not isinstance(x, jax.Array)
                and np.dtype(getattr(x, "dtype", aval.dtype))
                != aval.dtype) else x
            for x, aval in zip(flat, avals)]
        out = exported.call(*jax.tree_util.tree_unflatten(tree, coerced))
        return out[0], out[1], out[2], tuple(out[3]), tuple(out[4])

    def warm(self, registry=None, labels: Optional[Dict] = None) -> float:
        """Execute every saved program once with zero-filled arguments of
        the exported shapes (ISSUE 16 warm-boot satellite).  Exported
        programs compile lazily on first ``call`` — warming moves that
        cost from the first request wave to boot/save time, and (because
        this IS the serving-time ``Exported.call`` path, not a jit
        re-wrap) the XLA executables land in the persistent compilation
        cache under the exact keys serving will look up.  Returns the
        wall seconds spent; recorded as ``serving_aot_warm_seconds``
        when a ``registry`` is given."""
        t0 = time.perf_counter()
        for key, exported in sorted(self._programs.items()):
            flat = [np.zeros(a.shape, a.dtype) for a in exported.in_avals]
            args, kwargs = jax.tree_util.tree_unflatten(
                exported.in_tree, flat)
            out = exported.call(*args, **kwargs)
            for leaf in jax.tree_util.tree_leaves(out):
                if hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()
        wall = time.perf_counter() - t0
        if registry is not None:
            registry.gauge(
                "serving_aot_warm_seconds",
                "wall seconds executing every saved AOT program once "
                "(warm boot/save)", **(labels or {})).set(wall)
        return wall
