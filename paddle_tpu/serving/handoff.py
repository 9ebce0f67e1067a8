"""Prefill/decode KV-cache hand-off (ISSUE 20 tentpole).

The block transfer core between two replicas' pools: a **KV run** is the
serialized form of a leading block chain — the PR 4 chain-hash records
(:meth:`~paddle_tpu.ops.paged_attention.BlockPool.export_blocks` /
``export_chain``) plus the gathered device payload of those pages and a
SHA-256 digest over it.  A donor replica builds a run with
:func:`export_request_run` (a migrating request's computed prompt KV) or
:func:`export_prefix_run` (a heat-table-hot prefix, ISSUE 20 satellite);
the recipient admits it with :func:`import_run`, which

* re-checks the pool compatibility header (block size, layer count, KV
  heads, head dim, dtype) — a mismatch raises :class:`HandoffError`;
* re-verifies the payload digest — transport corruption raises
  :class:`HandoffError` before anything mutates;
* hands the block records to ``BlockPool.import_blocks`` (which
  re-verifies the token chain from the hash root and either places every
  fresh block atomically or refuses with ``None``), then scatters the
  payload into exactly the freshly-placed pages.

Everything here is EAGER host/device work — no traced program runs, so
hand-off provably adds zero jit traces, zero new buckets, and leaves AOT
artifacts untouched (the unit tests assert the engine's trace counters
and bucket sets across export+import).

Cross-process, the same run ships as ``wire.py`` block-stream frames
(``kv_run_begin`` + chunked base64 ``kv_run_chunk``), converted by
:func:`run_to_frames` / :func:`run_from_frames`.

A refused or failed import is never a lost request: callers fall back to
re-prefill on the recipient (the prompt tokens always travel with the
request), so hand-off is strictly an optimization layer.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import shard_kv_pool
from . import wire

HANDOFF_VERSION = 1

# metric names this module owns (tools/check_metrics_docs lints that
# each appears in README's metrics table); registered by the fleet
# router / process fleet via register_handoff_metrics
METRIC_NAMES = (
    "serving_handoff_total",
    "serving_handoff_seconds",
    "serving_handoff_blocks",
)

_SECONDS_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                    0.1, 0.25, 0.5, 1.0, 2.5)
_BLOCKS_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)


class HandoffError(RuntimeError):
    """A KV run that cannot be admitted: deployment-shape mismatch,
    digest/content verification failure, or a malformed run.  Typed so
    the fleet/worker layers answer with a typed error and fall back to
    recompute instead of dying — hand-off failures degrade, never lose
    requests."""


def register_handoff_metrics(registry, labels: Optional[Dict] = None):
    """Pre-register the ``serving_handoff_*`` family on ``registry`` and
    return ``{"total", "seconds", "blocks"}`` handles (the router bumps
    them per completed hand-off)."""
    labels = dict(labels or {})
    return {
        "total": registry.counter(
            "serving_handoff_total",
            "completed prefill→decode KV hand-offs (role-aware fleet "
            "migrations at the first-token boundary)", **labels),
        "seconds": registry.histogram(
            "serving_handoff_seconds",
            "end-to-end hand-off duration: export + transfer + verified "
            "import", buckets=_SECONDS_BUCKETS, **labels),
        "blocks": registry.histogram(
            "serving_handoff_blocks",
            "KV blocks shipped per hand-off", buckets=_BLOCKS_BUCKETS,
            **labels),
    }


# --- run construction (donor side) ------------------------------------------
def pool_meta(engine) -> Dict:
    """The pool-compatibility header both ends must agree on before any
    page content moves."""
    cfg = engine.model.config
    if any(spec.state for spec in engine.cache_specs):
        raise HandoffError(
            "KV hand-off moves pages; this engine's model declares "
            "per-sequence recurrent state, and slots have no hand-off "
            "form yet")
    if any(spec.kind != "kv" for spec in engine.cache_specs):
        raise HandoffError(
            "KV hand-off moves a (2, layers, ...) payload of keys and "
            "values; this engine's model declares a latent cache, which "
            "has no hand-off form yet")
    return {
        "version": HANDOFF_VERSION,
        "block_size": int(engine.block_size),
        "layers": int(cfg.num_hidden_layers),
        "kv_heads": int(cfg.num_key_value_heads),
        "head_dim": int(cfg.head_dim),
        "dtype": str(np.dtype(engine._pool_dtype)),
    }


def build_run(engine, records: List[dict]) -> Dict:
    """Gather the device payload for ``records`` (the
    ``BlockPool.export_blocks`` record shape) into one serialized run.
    Pure read on the donor: no pool mutation, no refcount change.  The
    per-layer gathers are eager ``take`` ops — at mp>1 the head-sharded
    pools are device_get-assembled into the GLOBAL (unsharded) payload,
    so donor and recipient need not share a mesh layout."""
    idx = np.asarray([r["block"] for r in records], dtype=np.int32)
    k = np.stack([np.asarray(jax.device_get(p[idx]))
                  for p in engine._k_pools])
    v = np.stack([np.asarray(jax.device_get(p[idx]))
                  for p in engine._v_pools])
    payload = np.ascontiguousarray(np.stack([k, v]))
    run = pool_meta(engine)
    run["blocks"] = [{"hash": r["hash"], "depth": int(r["depth"]),
                      "tokens": tuple(int(t) for t in r["tokens"])}
                     for r in records]
    run["payload"] = payload
    run["digest"] = hashlib.sha256(payload.tobytes()).digest()
    run["tokens_total"] = len(records) * engine.block_size
    return run


def export_request_run(engine, request_id) -> Optional[Dict]:
    """Serialize the hashed leading blocks of ``request_id``'s KV (the
    computed prompt prefix a decode specialist can resume from).
    ``None`` when nothing is transferable (no table, nothing hashed yet)
    — the caller just re-prefills at the destination."""
    kv = engine.kv
    if not kv.has(request_id):
        return None
    hashes = []
    for b in kv.table(request_id):
        h = kv.block_chain_hash(b)
        if h is None:
            break
        hashes.append(h)
    if not hashes:
        return None
    records = kv.export_blocks(hashes)
    if not records:
        return None
    return build_run(engine, records)


def export_prefix_run(engine, chain_hash: bytes,
                      max_blocks: Optional[int] = None) -> Optional[Dict]:
    """Serialize the full leading chain addressed by its DEEPEST digest
    (the prefix-heat table's key) — the hot-prefix migration entry
    point.  ``max_blocks`` bounds the shipped run (leading blocks win:
    the shortest prefixes are the most shareable).  ``None`` when the
    chain is broken (an ancestor was evicted since the heat sample)."""
    records = engine.kv.export_chain(chain_hash)
    if not records:
        return None
    if max_blocks is not None and len(records) > max_blocks:
        records = records[:max_blocks]
    return build_run(engine, records)


# --- run admission (recipient side) -----------------------------------------
def import_run(engine, run: Dict) -> Optional[int]:
    """Admit a KV run into ``engine``'s pool: verify the compatibility
    header and payload digest (:class:`HandoffError` on any mismatch —
    the pool is untouched), place the fresh blocks atomically through
    ``BlockPool.import_blocks``, then scatter the payload into exactly
    those pages and re-apply the pool sharding.  Returns the number of
    freshly-placed blocks (0 = everything was already cached here), or
    ``None`` on a capacity refusal — the caller re-prefills.  Eager ops
    only: trace counters and bucket sets provably do not move."""
    meta = pool_meta(engine)
    if int(run.get("version", -1)) != HANDOFF_VERSION:
        raise HandoffError(
            f"kv run version {run.get('version')!r}, this engine speaks "
            f"{HANDOFF_VERSION}")
    for key in ("block_size", "layers", "kv_heads", "head_dim", "dtype"):
        if run.get(key) != meta[key]:
            raise HandoffError(
                f"kv run {key}={run.get(key)!r} does not match this "
                f"pool's {key}={meta[key]!r} — donor and recipient must "
                "share one deployment shape")
    records = run.get("blocks") or []
    if not records:
        return 0
    payload = np.asarray(run["payload"])
    if hashlib.sha256(payload.tobytes()).digest() != run.get("digest"):
        raise HandoffError(
            "kv run payload fails SHA-256 digest verification — "
            "refusing corrupted content")
    expect = (2, meta["layers"], len(records), meta["block_size"],
              meta["kv_heads"], meta["head_dim"])
    if tuple(payload.shape) != expect:
        raise HandoffError(
            f"kv run payload shape {tuple(payload.shape)} does not "
            f"match its block records (expected {expect})")
    try:
        placed = engine.kv.import_blocks(records)
    except ValueError as e:
        raise HandoffError(f"kv run rejected by the pool: {e}") from e
    if placed is None:
        return None
    if not placed:
        return 0
    src = [i for i, r in enumerate(records) if r["hash"] in placed]
    dst = [placed[records[i]["hash"]] for i in src]
    src_ix = np.asarray(src, dtype=np.int32)
    dst_ix = jnp.asarray(np.asarray(dst, dtype=np.int32))
    dtype = engine._pool_dtype
    engine._k_pools = tuple(
        shard_kv_pool(p.at[dst_ix].set(
            jnp.asarray(payload[0, l][src_ix], dtype=dtype)))
        for l, p in enumerate(engine._k_pools))
    engine._v_pools = tuple(
        shard_kv_pool(p.at[dst_ix].set(
            jnp.asarray(payload[1, l][src_ix], dtype=dtype)))
        for l, p in enumerate(engine._v_pools))
    return len(placed)


# --- wire form ---------------------------------------------------------------
def run_to_frames(run: Dict) -> List[Dict]:
    """A run's ``wire.py`` block-stream frames: ``kv_run_begin`` plus
    chunked ``kv_run_chunk`` frames, each under ``MAX_FRAME_BYTES``."""
    payload = np.ascontiguousarray(np.asarray(run["payload"]))
    meta = {k: run[k] for k in ("version", "block_size", "layers",
                                "kv_heads", "head_dim", "dtype",
                                "tokens_total")}
    meta["shape"] = [int(s) for s in payload.shape]
    blocks = [[r["hash"].hex(), int(r["depth"]),
               [int(t) for t in r["tokens"]]] for r in run["blocks"]]
    return wire.kv_run_frames(meta, blocks, payload.tobytes(),
                              run["digest"].hex())


def run_from_frames(begin: Dict, chunks: List[Dict]) -> Dict:
    """Rebuild a run from its wire frames.  Frame-protocol violations
    (missing/misordered chunks, bad base64, byte shortfall) raise
    :class:`wire.FrameError` with the usual typed kinds; a structurally
    valid run that lies about its own shape raises
    :class:`HandoffError` (and the digest check in :func:`import_run`
    still guards the content)."""
    payload_bytes = wire.kv_run_assemble(begin, chunks)
    meta = begin.get("meta") or {}
    try:
        arr = np.frombuffer(
            payload_bytes, dtype=np.dtype(str(meta["dtype"]))
        ).reshape([int(s) for s in meta["shape"]])
        blocks = [{"hash": bytes.fromhex(h), "depth": int(d),
                   "tokens": tuple(int(t) for t in toks)}
                  for h, d, toks in begin.get("blocks") or []]
        digest = bytes.fromhex(str(begin.get("digest", "")))
    except (KeyError, TypeError, ValueError) as e:
        raise HandoffError(f"undecodable kv run frames: {e}") from e
    run = {k: meta.get(k) for k in ("version", "block_size", "layers",
                                    "kv_heads", "head_dim", "dtype",
                                    "tokens_total")}
    run["blocks"] = blocks
    run["payload"] = arr
    run["digest"] = digest
    return run
