"""Sliding-window attention on the serving path: a per-sequence RING of the
last ``W`` tokens' keys and values, and attention under a mask of
positions for the launches that compute a whole prompt or a chunk of one.

A window layer needs the last ``W`` tokens of a sequence and nothing
older, so what it keeps is of FIXED size a sequence whatever the length:
two arrays ``[W, kv_heads, dim]`` in a slot (``CacheSpec.state``, the slots
``serving/kv_manager.py`` owns beside the pages).  Token ``p`` lives at
ring index ``p mod W``, written after rotation; after token ``p`` index
``j`` holds position ``p - ((p - j) mod W)`` (:func:`ring_positions`),
negative where nothing of this sequence was written yet.  Key ``s`` is
visible to query ``t`` iff ``0 <= t - s < W``.

Three paths, one mathematics:

* **decode** (:func:`ring_decode_attention`): softmax attention does not
  depend on the order of its keys, and the valid entries of a ring are its
  first ``min(p + 1, W)`` indices, so a decode step over rings IS paged
  decode attention (``ops.paged_attention.paged_attention``, the Pallas
  kernel where the shapes tile) over the ring pool seen as pages of
  ``RING_PAGE`` tokens, with a table built from the slot and the length
  ``min(p + 1, W)``.  Table entries past the last page a row needs repeat
  that page: the kernel's index map then asks for no new copy, so a row
  reads no more of its ring than it has written.
* **a whole prompt** (:func:`masked_attention`, ``banded=True``): queries
  in blocks, each over the keys of its own band only.
* **a chunk past position 0**: the ring's entries beside the chunk's own
  keys, each with its position, under the same mask.

:func:`masked_attention` also serves the layers WITHOUT a window (every
``s <= t`` visible): it sizes its query blocks by the head count, so that
128 heads over 8,192 keys never hold more than ``SCORE_BYTES`` of float32
scores (``paged_attention._block_queries``' fixed 1,024 rows would hold
4.3 GB).

The ring WRITES (:func:`ring_write_token`, :func:`ring_write_span`) also
serve a second kind of window: chunk-summarised attention
(``ops/eva_attention.py``) keeps the same ring, token ``p`` at ``p mod W``,
but its window is ALIGNED to multiples of ``W``, so a row there reads the
ring's first ``(p mod W) + 1`` entries (not ``min(p + 1, W)``) beside
summary rows of the closed windows, under one softmax; its reads are that
file's, not :func:`ring_decode_attention`'s, which returns no
log-sum-exp to merge two partial attentions by.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import paged_attention as _paged

#: tokens of a ring the decode path reads at a time: the ring pool
#: ``[slots, W, heads, dim]`` is handed to the paged decode path as
#: ``[slots * W / RING_PAGE, RING_PAGE, heads, dim]`` (the same bytes).  A
#: page of the shared pool is 16 tokens because sequences grow by it; a
#: ring never grows, so its page is sized for the copy engine instead
#: (256 x 8 x 128 bf16 = 512 KB a side)
RING_PAGE = 256

#: float32 scores one query block of :func:`masked_attention` may hold
SCORE_BYTES = 256 * 2 ** 20


class WindowTokens(_paged.LaunchTelemetry):
    """What sliding-window layers bring to a launch: on ``engine.build``
    of a DECODE launch ``window_tokens``, the ring entries its rows read
    (a row of length ``n`` after its token reads ``min(n, window)`` of
    them in every window layer)."""

    def __init__(self, layers, view):
        super().__init__(layers, view)
        self.window = max(layer.cache_spec().window for layer in layers)

    def build_ints(self, view, rows, reqs):
        if view.program != "decode":
            return {}
        return {"window_tokens": sum(
            min(view.kv.seq_len(r.request_id) + 1, self.window)
            for r in reqs)}


def ring_positions(last, window: int):
    """The position each ring index holds once token ``last`` (``[...]``
    int32) is written: ``[..., window]``, negative where nothing is."""
    last = jnp.asarray(last, jnp.int32)[..., None]
    return last - jnp.mod(last - jnp.arange(window, dtype=jnp.int32), window)


def ring_write_token(ring, slots, pos, new):
    """Decode: row ``b``'s token at position ``pos[b]`` into ring
    ``slots[b]``.  ring ``[S, W, h, d]``; new ``[B, h, d]``.  Padding rows
    all write the null slot 0."""
    return ring.at[slots, jnp.mod(pos, ring.shape[1])].set(
        new.astype(ring.dtype))


def ring_write_span(ring, slot, new, start, n_valid):
    """A prompt or chunk of ONE sequence: of ``new`` ``[T, h, d]`` (tokens
    at positions ``start + [0, T)``, the first ``n_valid`` real) the last
    ``min(n_valid, W)`` real ones go to their ring indices; the rest is
    dropped (a scatter index out of range)."""
    W, T = ring.shape[1], new.shape[0]
    i = jnp.arange(T, dtype=jnp.int32)
    keep = (i < n_valid) & (i >= n_valid - W)
    idx = jnp.where(keep, jnp.mod(start + i, W), W)
    return ring.at[slot, idx].set(new.astype(ring.dtype), mode="drop")


def ring_page(window: int) -> int:
    """Tokens a page of the ring's paged view holds: ``RING_PAGE`` where
    it divides the window, else the largest power of two that does."""
    page = math.gcd(window, RING_PAGE)
    return page if page >= 8 else window


def ring_decode_attention(q, k_ring, v_ring, slots, pos,
                          use_pallas: Optional[bool] = None):
    """One decode token a row over its ring (the token already written).
    q ``[B, H, D]``; rings ``[S, W, kv_heads, D]``; ``slots`` / ``pos``
    ``[B]``.  Returns ``[B, H, D]``."""
    S, W, h, d = k_ring.shape
    page = ring_page(W)
    n = W // page
    lens = jnp.minimum(pos + 1, W).astype(jnp.int32)
    last_page = (lens - 1) // page
    tables = slots[:, None] * n + jnp.minimum(
        jnp.arange(n, dtype=jnp.int32)[None, :], last_page[:, None])
    return _paged.paged_attention(
        q, k_ring.reshape(S * n, page, h, d), v_ring.reshape(S * n, page, h, d),
        tables, lens, use_pallas=use_pallas)


def _query_block(S: int, heads: int, keys: int) -> int:
    """Rows of a query block: the largest power of two whose ``[heads,
    rows, keys]`` float32 scores stay under ``SCORE_BYTES`` (at least 8)."""
    rows = max(8, SCORE_BYTES // (4 * heads * max(keys, 1)))
    return min(S, 1 << (rows.bit_length() - 1))


def masked_attention(q, k, v, q_pos, k_pos, window: Optional[int] = None,
                     banded: bool = False):
    """Softmax attention of ``q`` ``[B, S, H, D]`` over ``k`` / ``v``
    ``[B, M, kv_heads, D]`` (grouped: query head ``h`` reads key/value head
    ``h // (H / kv_heads)``), key ``s`` visible to query ``t`` iff ``0 <=
    q_pos[t] - k_pos[s]`` (``< window`` where one is given) and ``k_pos[s]
    >= 0``.  ``q_pos`` ``[S]`` or ``[B, S]``, ``k_pos`` ``[M]`` or ``[B,
    M]``.  Scores and softmax are float32, the weighted sum runs in ``v``'s
    type.  ``banded`` says the keys lie in the order of their positions and
    the queries are ``S`` consecutive positions among them, so a block of
    queries needs only the ``window + block`` keys that end with it.
    Returns ``[B, S, H * D]``."""
    B, S, H, D = q.shape
    M, hkv = k.shape[1], k.shape[2]
    rep = H // hkv
    scale = 1.0 / math.sqrt(D)
    q_pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32), (B, S))
    k_pos = jnp.broadcast_to(jnp.asarray(k_pos, jnp.int32), (B, M))
    blk = _query_block(S, H, M)
    L = M
    if banded and window is not None and window + blk < M:
        L = window + blk
        blk = _query_block(S, H, L)
        L = window + blk
    pad = -S % blk
    if pad:         # a cache-less forward over a length that is no bucket
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)))

    def block(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, blk, 1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, first, blk, 1)
        if L < M:   # the band that ends with this block's last query
            at = jnp.clip(jnp.max(qp[0]) + 1 - L - k_pos[0, 0], 0, M - L)
            kb = jax.lax.dynamic_slice_in_dim(k, at, L, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, at, L, 1)
            kp = jax.lax.dynamic_slice_in_dim(k_pos, at, L, 1)
        else:
            kb, vb, kp = k, v, k_pos
        s = jnp.einsum("bqhrd,bkhd->bhrqk",
                       qb.reshape(B, blk, hkv, rep, D), kb,
                       preferred_element_type=jnp.float32) * scale
        gap = qp[:, :, None] - kp[:, None, :]
        mask = (gap >= 0) & (kp[:, None, :] >= 0)
        if window is not None:
            mask = mask & (gap < window)
        probs = jax.nn.softmax(jnp.where(mask[:, None, None], s, -1e30), -1)
        o = jnp.einsum("bhrqk,bkhd->bqhrd", probs.astype(vb.dtype), vb)
        return o.reshape(B, blk, H * D)

    n = (S + pad) // blk
    if n == 1:
        return block(0)[:, :S].astype(q.dtype)
    out = jax.lax.map(block, jnp.arange(n, dtype=jnp.int32) * blk)
    out = jnp.moveaxis(out, 0, 1).reshape(B, n * blk, H * D)
    return out[:, :S].astype(q.dtype)
