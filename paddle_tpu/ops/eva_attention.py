"""Chunk-summarised attention (EVA: Zheng, Yuan, Wang, Kong, "Efficient
Attention via Control Variates", ICLR 2023, in the form EvaByte's released
reference code gives it) on the serving path: an exact window beside one
pooled key/value row per chunk of every CLOSED window, under one softmax.

**The mathematics**, per head, with window ``W``, chunk ``C`` (``C``
divides ``W``), two learned vectors ``mu, phi`` of the head's size ``d``.
Chunk ``c`` holds positions ``[C c, C c + C)`` and is summarised as::

    kbar_c = sum_j softmax_j(mu . k_j) k_j      vbar_c = sum_j softmax_j(phi . k_j) v_j

(both softmaxes over the chunk's ``C`` ROTATED keys, float32, logits
unscaled).  Query ``t`` in window ``w = t // W`` sees the LOCAL set ``{s :
s // W == w, s <= t}`` exactly and the REMOTE set ``{c : c < (W / C) w}``
(every chunk of every closed window, none of the open one) through its
summaries::

    o_t = (sum_L e^{q.k_s/sqrt d} v_s + sum_R e^{q.kbar_c/sqrt d} vbar_c)
        / (sum_L e^{q.k_s/sqrt d}     + sum_R e^{q.kbar_c/sqrt d})

**What a layer keeps, two lifetimes at once** (``CacheSpec`` with
``tokens_per_row``): a per-sequence RING of the open window's rotated keys
and values (``[slots, W, heads, d]`` a side, ``ops/window_attention.py``'s
ring writes) and ROWS that grow with the sequence at one row a chunk
(``[num_blocks, rows a block, heads, d]`` a side, in the sequence's own
blocks: chunk ``c`` lives at ``(table[c // R], c % R)``, ``R`` the rows a
block).  The window is block-ALIGNED, not sliding: token ``p`` sits at
ring index ``p mod W`` and a row at position ``p`` reads the ring's first
``(p mod W) + 1`` entries (a sliding ring reads ``min(p + 1, W)``), so a
recycled slot's stale entries and the closed window's are never visible.
A chunk's row is written in the launch that completes the chunk and is
VISIBLE once its window has closed: ``(W / C) (p // W)`` rows at position
``p``.

Three paths, one mathematics:

* **decode** (:func:`decode_attention`): ring and rows are read WHERE
  THEY LIE (no gathered copy of either: a gathered copy of just a row's
  blocks costs 7.1 times the read on a v5e, PERF.md section 6, PR 45), as
  two partial attentions.  On a TPU ``ops/pallas_eva.py``'s two kernels,
  at every number of rows: a row's ring slot up to its position, and the
  tiles of the pool in which some row of the launch sees a row (every row
  of the launch against a tile, under a mask of who sees what; the block
  tables only say who holds what, so their width changes no read), each
  carrying ``(weighted sum, max, sum)`` in float32 and merged by their
  log-sum-exp, so no score array over ring and pool is written and a
  launch's cost follows what its rows hold.  Elsewhere (the CPU, shapes
  that do not tile) the XLA form, the kernels' oracle: the queries put in
  SLOT order against every ring whole, every row of the launch against
  the WHOLE pool under the same mask (one matmul a head), both score sets
  under one float32 softmax.
* **a prompt or a chunk of one** (:func:`span_attention`): explicit local
  keys with their positions and remote rows with their chunk ids under
  the masks above, the queries in blocks so that the float32 scores stay
  under :data:`SCORE_BYTES`.
* the layer (``models/eva.py``) carries a prompt longer than a window
  WINDOW BY WINDOW: a window's queries need its own keys and the rows so
  far, nothing else.

Rotation (:func:`rotate`) is computed IN THE TRACE from the positions: no
table of ``max_position_embeddings`` rows is a constant of any program.

Device scopes: ``eva_attn`` around a launch's attention (``eva_local``,
``eva_remote``, ``eva_merge`` under it in decode), ``eva_pool`` around the
summarising of chunks.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import paged_attention as _paged

NEG = -1e30

#: float32 scores one query block of :func:`span_attention` may hold: half
#: ``window_attention.SCORE_BYTES``, because a 32,768-byte prompt is
#: prefilled beside pools that fill three quarters of the chip
SCORE_BYTES = 128 * 2 ** 20


class _Pair:
    """The two arrays a side of the pools holds for a ring-and-rows layer,
    read back by the step programs as ``cache.k_pool._value``."""

    def __init__(self, ring, rows):
        self.ring, self.rows = ring, rows

    @property
    def _value(self):
        return (self.ring._value, self.rows._value)


class EvaCache:
    """Per-layer view of a ring-and-rows layer's memory, handed to it as
    its ``cache``: the ring slots and the summary rows of each side as
    framework Tensors (the in-place updates thread as jit state).
    ``slots`` ``[B]`` is each row's ring slot (0: the null slot of padding
    rows), ``tables`` ``[B, width]`` its blocks (0: the null block).  In
    a prompt or chunk launch ``n_valid`` is the real tokens of the launch
    and ``start`` the absolute position of its first token (``None``: 0);
    ``carried`` says the ring and the rows hold an earlier part of THIS
    sequence's open window that the launch's queries may see (a chunk
    that does not start a window: :meth:`route` sets it where it is given
    a ``start``).  ``n_valid`` is ``None`` in decode."""

    def __init__(self, k_side, v_side):
        from ..core.tensor import Tensor

        self.k_ring, self.k_rows = (Tensor(a) for a in k_side)
        self.v_ring, self.v_rows = (Tensor(a) for a in v_side)
        self.slots = self.tables = self.start = self.n_valid = None
        self.carried = False

    k_pool = property(lambda self: _Pair(self.k_ring, self.k_rows))
    v_pool = property(lambda self: _Pair(self.v_ring, self.v_rows))

    @property
    def tensors(self):
        """Key ring, value ring, key rows, value rows."""
        return self.k_ring, self.v_ring, self.k_rows, self.v_rows

    def rebind(self, *arrays):
        """The four arrays after a launch's writes, in that order."""
        for t, a in zip(self.tensors, arrays):
            t._rebind(a)

    over = classmethod(lambda cls, k, v: cls(k, v))

    def route(self, tables, seq_lens=None, slot_blocks=None,
              slot_offsets=None, start=None, n_valid=None):
        """``PagedCache.route``'s arrays: a row's ring slot is the id of
        its first block; given a ``start``, ring and rows hold the
        sequence's earlier part."""
        self.slots = jnp.asarray(tables[:, 0], jnp.int32)
        self.tables = jnp.asarray(tables, jnp.int32)
        self.start = None if start is None else jnp.asarray(start, jnp.int32)
        self.n_valid = None if n_valid is None \
            else jnp.asarray(n_valid, jnp.int32)
        self.carried = start is not None


class SummaryRows(_paged.LaunchTelemetry):
    """What ring-and-rows layers (window ``W``, chunk ``C``) bring to a
    launch.  On ``engine.build`` of a DECODE launch, summed over its rows
    at positions ``p``: ``eva_ring_tokens`` (ring entries read, ``(p mod
    W) + 1``), ``eva_summary_rows`` (summary rows read, ``(W / C) (p //
    W)``), ``eva_windows_closed`` (this token closes the row's window),
    ``eva_rows_held`` (rows held once it is written, ``(p + 1) // C``);
    and ``eva_pool_tiles_seen`` of ``eva_pool_tiles``: the tiles of a
    layer's pool in which some row of the launch SEES a row -- what the
    decode kernel reads (``ops/pallas_eva.py``; counted from the tables, so
    under the XLA form what the kernel WOULD read).  Also the
    ``serving_eva_*`` series below (a prompt's windows too)."""

    def __init__(self, layers, view):
        super().__init__(layers, view)
        from .pallas_eva import pool_tile_rows

        spec, kv = layers[0].cache_spec(), view.kv
        self.window, self.chunk = spec.window, spec.tokens_per_row
        # the rows of a tile of the pool, and each running row's (windows
        # closed, first block, tiles) as last counted
        self.tile_rows = pool_tile_rows(
            kv.num_blocks * spec.rows_per_block(kv.block_size), *spec.k)
        self.tiles: dict = {}
        reg, labels = view.registry, view.labels
        self.counters = {
            "rows_held": reg.gauge(
                "serving_eva_summary_rows_held", **labels,
                help="chunk-summary rows a layer holds for the rows of the "
                     "last decode launch (one a whole chunk of each "
                     "sequence)"),
            "windows_closed": reg.counter(
                "serving_eva_windows_closed_total", **labels,
                help="windows of chunk-summarised attention that closed "
                     "(their summaries became visible), over sequences"),
            "tiles_seen": reg.counter(
                "serving_eva_pool_tiles_seen_total", **labels,
                help="tiles of a layer's pool of chunk-summary rows in "
                     "which some row of a decode launch saw a row (what "
                     "the decode kernel reads), over launches"),
            "tiles": reg.counter(
                "serving_eva_pool_tiles_total", **labels,
                help="tiles of a layer's pool of chunk-summary rows, over "
                     "decode launches")}

    def build_ints(self, view, rows, reqs):
        if view.program != "decode":
            return {}
        W, C = self.window, self.chunk
        ps = [view.kv.seq_len(r.request_id) for r in reqs]
        closed = sum((p + 1) % W == 0 for p in ps)
        held = sum((p + 1) // C for p in ps)
        # a row sees the rows of its closed windows: its tiles change when a
        # window closes, or its blocks do (a preemption: ``forget`` drops the
        # row's entry, since a last-in-first-out free list may hand it the
        # same first block again before other later ones)
        T, R = self.tile_rows, view.kv.block_size // C
        blocks = view.kv.num_blocks
        tiles, was = {}, self.tiles
        for r, p in zip(reqs, ps):
            table = view.kv.table(r.request_id)
            key = (p // W, table[0] if table else 0)
            old = was.get(r.request_id)
            tiles[r.request_id] = old if old and old[0] == key else (
                key, frozenset((table[c // R] * R + c % R) // T
                               for c in range((W // C) * (p // W))))
        self.tiles = tiles
        # the rows past the pool's last whole tile are read by every launch
        rest = {blocks * R // T} if blocks * R % T else ()
        seen = len(frozenset(rest).union(*(t for _, t in tiles.values())))
        total = -(-blocks * R // T)
        self.counters["windows_closed"].inc(closed)
        self.counters["rows_held"].set(held)
        self.counters["tiles_seen"].inc(seen)
        self.counters["tiles"].inc(total)
        return {"eva_ring_tokens": sum(p % W + 1 for p in ps),
                "eva_summary_rows": sum((W // C) * (p // W) for p in ps),
                "eva_windows_closed": closed, "eva_rows_held": held,
                "eva_pool_tiles_seen": seen, "eva_pool_tiles": total}

    def fetch_ints(self, program, host_array):
        if program != "decode":         # the windows a prompt's launch closes
            start, n, W = *self.view.span, self.window
            self.counters["windows_closed"].inc((start + n) // W - start // W)
        return {}

    def forget(self, request_id):
        self.tiles.pop(request_id, None)


def inv_freq(dim: int, theta: float) -> np.ndarray:
    """RoPE's ``dim / 2`` inverse frequencies, float32, on the host."""
    return (1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
            ).astype(np.float32)


def rotate(x, pos, theta: float):
    """Rotary embedding of ``x`` ``[B, S, H, D]`` at absolute positions
    ``pos`` (``[S]`` or ``[B, S]``), rotate-half pairing over all ``D``
    dimensions, the angles computed here from the positions in float32
    (``position * inv_freq``), the product in ``x``'s type."""
    pos = jnp.asarray(pos, jnp.int32)
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq(x.shape[-1], theta))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    lift = (lambda a: a[:, :, None, :]) if pos.ndim == 2 \
        else (lambda a: a[None, :, None, :])
    cos, sin = lift(cos).astype(x.dtype), lift(sin).astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def pool_chunks(k, v, mu, phi):
    """Summaries of whole chunks.  ``k`` / ``v`` ``[..., C, H, D]`` (a
    chunk's ROTATED keys and its values), ``mu`` / ``phi`` ``[H, D]``:
    ``kbar = sum_j softmax_j(mu . k_j) k_j``, ``vbar = sum_j softmax_j(phi
    . k_j) v_j``, softmaxes and sums in float32.  Returns two ``[..., H,
    D]`` float32 arrays."""
    with jax.named_scope("eva_pool"):
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
        wk = jax.nn.softmax(jnp.einsum(
            "...chd,hd->...ch", kf, mu.astype(jnp.float32)), axis=-2)
        wv = jax.nn.softmax(jnp.einsum(
            "...chd,hd->...ch", kf, phi.astype(jnp.float32)), axis=-2)
        return (jnp.einsum("...ch,...chd->...hd", wk, kf),
                jnp.einsum("...ch,...chd->...hd", wv, vf))


def chunk_blocks(tables, chunk_ids, R: int):
    """The block each of ``chunk_ids`` ``[..., n]`` lives in, by
    ``tables`` ``[..., width]`` (``R`` rows a block; an id past the table
    reads its last entry: the caller does not keep such a chunk)."""
    at = jnp.clip(chunk_ids // R, 0, tables.shape[-1] - 1)
    return jnp.take_along_axis(tables, at, -1)


def rows_write(rows, blocks, chunk_ids, new, keep):
    """Summaries ``new`` ``[n, H, D]`` of the chunks ``chunk_ids`` ``[n]``
    into the rows ``[num_blocks, R, H, D]`` of their ``blocks`` ``[n]``;
    where ``keep`` is false nothing is written (a scatter index out of
    range)."""
    blk = jnp.where(keep, blocks, rows.shape[0])
    return rows.at[blk, chunk_ids % rows.shape[1]].set(
        new.astype(rows.dtype), mode="drop")


# --- decode -------------------------------------------------------------------

def _chunk_of_row(tables, num_blocks: int, R: int):
    """``[B, num_blocks * R]`` int32: the chunk of row ``b``'s sequence
    that each row of the pool holds, a large number where the block is
    not in ``b``'s table (or is the null block 0)."""
    B, width = tables.shape
    far = jnp.int32(1 << 30)
    place = jnp.full((B, num_blocks), far, jnp.int32).at[
        jnp.arange(B, dtype=jnp.int32)[:, None], tables].set(
        jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32)[None],
                         (B, width)))
    place = place.at[:, 0].set(far)[:, :, None]
    chunk = jnp.where(place < far,
                      place * R + jnp.arange(R, dtype=jnp.int32), far)
    return chunk.reshape(B, num_blocks * R)


def decode_attention(q, k_ring, v_ring, k_rows, v_rows, slots, tables, pos,
                     window: int, chunk: int,
                     use_pallas: Optional[bool] = None):
    """One decode token a row (already written to its ring) over the
    ring's first ``(pos mod window) + 1`` entries and the ``(window /
    chunk) (pos // window)`` rows of the closed windows, one softmax.
    ``q`` ``[B, H, D]``; rings ``[S, W, H, D]``; rows ``[num_blocks, R, H,
    D]``; ``slots`` / ``pos`` ``[B]``, ``tables`` ``[B, width]``.  Returns
    ``[B, H * D]`` in ``q``'s type.

    On a TPU, at every number of rows, ``ops/pallas_eva.py``'s two kernels
    where the shapes allow (``pallas_eva.takes``): a row's ring slot up to
    its position and the tiles of the pool in which some row of the launch
    sees a row, both where they lie, as two partial attentions merged by
    their log-sum-exp.  Elsewhere (the CPU, the operator's kill switch,
    shapes that do not tile) the XLA form below, which reads every ring
    and the pool whole and is the kernels' oracle.
    :func:`~paddle_tpu.ops.paged_attention.pallas_dispatch` decides
    (``use_pallas`` forces or pins, a test's) and the form traced is
    published as ``paged_attention.last_path``."""
    from . import pallas_eva as _pk

    B, H, D = q.shape
    W = k_ring.shape[1]

    def kernel():
        with jax.named_scope("eva_attn"):
            with jax.named_scope("eva_local"):
                # padding rows share the null slot 0 and read nothing
                loc = _pk.ring_partials(
                    q, k_ring, v_ring, slots,
                    jnp.where(slots > 0, jnp.mod(pos, W) + 1, 0))
            with jax.named_scope("eva_remote"):
                rem = _pk.pool_partials(
                    q, k_rows, v_rows,
                    _seen_rows(tables, pos, k_rows.shape, W, chunk))
            with jax.named_scope("eva_merge"):
                return _pk.merge(loc, rem).astype(q.dtype).reshape(B, H * D)

    def oracle():
        return _decode_attention_xla(q, k_ring, v_ring, k_rows, v_rows, slots,
                                     tables, pos, chunk)

    out, _paged.last_path = _paged.pallas_dispatch(
        kernel, oracle, use_pallas,
        jax.default_backend() == "tpu" and _pk.takes(q, k_ring, k_rows))
    return out


def _seen_rows(tables, pos, pool_shape, window: int, chunk: int):
    """``[B, num_blocks x R]`` bool: who sees what of the pool ``[num_blocks,
    R, ...]``.  A freed block's stale rows are nobody's, the open window's
    rows are held and not yet seen.  The same for every layer of a step
    (XLA keeps one), and part of the remote half's work: both forms make it
    under ``eva_attn/eva_remote``, where the benchmark's readers time it."""
    n_rem = (window // chunk) * (pos // window)
    return _chunk_of_row(tables, *pool_shape[:2]) < n_rem[:, None]


def _decode_attention_xla(q, k_ring, v_ring, k_rows, v_rows, slots, tables,
                          pos, chunk: int):
    """:func:`decode_attention` in XLA: every ring read whole in slot
    order under its row's ``(pos mod W) + 1`` visible entries, every row
    of the launch against the WHOLE pool under :func:`_seen_rows`, both
    score sets under one float32 softmax."""
    B, H, D = q.shape
    S, W = k_ring.shape[0], k_ring.shape[1]
    scale = 1.0 / math.sqrt(D)
    with jax.named_scope("eva_attn"):
        with jax.named_scope("eva_local"):
            # the queries in SLOT order: the rings are read where they lie.
            # Padding rows share the null slot 0 and read what they like
            q_slot = jnp.zeros((S, H, D), q.dtype).at[slots].set(q)
            s_loc = jnp.einsum("shd,swhd->shw", q_slot, k_ring.astype(q.dtype),
                               preferred_element_type=jnp.float32)[slots]
            n_loc = jnp.mod(pos, W) + 1
            seen = jnp.arange(W, dtype=jnp.int32)[None] < n_loc[:, None]
            s_loc = jnp.where(seen[:, None, :], s_loc * scale, NEG)
        with jax.named_scope("eva_remote"):
            kr, vr = (a.reshape(-1, H, D) for a in (k_rows, v_rows))
            s_rem = jnp.einsum("bhd,nhd->bhn", q, kr.astype(q.dtype),
                               preferred_element_type=jnp.float32)
            seen_rows = _seen_rows(tables, pos, k_rows.shape, W, chunk)
            s_rem = jnp.where(seen_rows[:, None, :], s_rem * scale, NEG)
        with jax.named_scope("eva_merge"):
            probs = jax.nn.softmax(jnp.concatenate([s_loc, s_rem], -1), -1)
            p_loc, p_rem = probs[..., :W], probs[..., W:]
        with jax.named_scope("eva_local"):
            p_slot = jnp.zeros((S, H, W), v_ring.dtype).at[slots].set(
                p_loc.astype(v_ring.dtype))
            o_loc = jnp.einsum("shw,swhd->shd", p_slot, v_ring,
                               preferred_element_type=jnp.float32)[slots]
        with jax.named_scope("eva_remote"):
            o_rem = jnp.einsum("bhn,nhd->bhd", p_rem.astype(vr.dtype), vr,
                               preferred_element_type=jnp.float32)
        with jax.named_scope("eva_merge"):
            return (o_loc + o_rem).astype(q.dtype).reshape(B, H * D)


def decode_pool(k_ring, v_ring, slots, pos, mu, phi, chunk: int):
    """The summary of the chunk each row's token at ``pos`` lies in, from
    the ring (the token already written): whole only where ``pos mod
    chunk == chunk - 1``, which is when the caller writes it.  Returns two
    ``[B, H, D]`` float32 arrays."""
    W = k_ring.shape[1]
    first = (jnp.mod(pos, W) // chunk) * chunk
    idx = first[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None]
    return pool_chunks(k_ring[slots[:, None], idx], v_ring[slots[:, None], idx],
                       mu, phi)


# --- a prompt, or a chunk of one ----------------------------------------------

def span_attention(q, q_pos, k_loc, v_loc, loc_pos, k_rem, v_rem, rem_chunk,
                   window: int, chunk: int):
    """Queries ``q`` ``[T, H, D]`` of ONE sequence at positions ``q_pos``
    ``[T]`` over explicit LOCAL keys ``k_loc`` / ``v_loc`` ``[M, H, D]``
    at positions ``loc_pos`` ``[M]`` (negative: nothing there) and REMOTE
    rows ``k_rem`` / ``v_rem`` ``[N, H, D]`` of chunks ``rem_chunk``
    ``[N]`` (negative: not whole yet), or ``None`` for no rows at all.  A
    local key ``s`` is visible to ``t`` iff ``s // W == t // W`` and ``s
    <= t``; a row of chunk ``c`` iff ``c // (W / C) < t // W``.  One
    float32 softmax over both; the weighted sums run in the values' type.
    Returns ``[T, H * D]`` in ``q``'s type."""
    T, H, D = q.shape
    M = k_loc.shape[0]
    N = 0 if k_rem is None else k_rem.shape[0]
    scale = 1.0 / math.sqrt(D)
    rows = max(8, SCORE_BYTES // (4 * H * (M + N)))
    blk = min(T, 1 << (rows.bit_length() - 1))
    pad = -T % blk
    if pad:         # a cache-less forward over a length that is no bucket
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad))

    def block(first):
        qb = jax.lax.dynamic_slice_in_dim(q, first, blk, 0)
        qw = jax.lax.dynamic_slice_in_dim(q_pos, first, blk, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k_loc.astype(qb.dtype),
                       preferred_element_type=jnp.float32) * scale
        mask = (loc_pos[None] >= 0) & (loc_pos[None] <= qw[:, None]) \
            & (loc_pos[None] // window == qw[:, None] // window)
        s = jnp.where(mask[None], s, NEG)
        if N:
            r = jnp.einsum("qhd,khd->hqk", qb, k_rem.astype(qb.dtype),
                           preferred_element_type=jnp.float32) * scale
            mask = (rem_chunk[None] >= 0) & (
                rem_chunk[None] // (window // chunk) < qw[:, None] // window)
            s = jnp.concatenate([s, jnp.where(mask[None], r, NEG)], -1)
        probs = jax.nn.softmax(s, -1)
        o = jnp.einsum("hqk,khd->qhd", probs[..., :M].astype(v_loc.dtype),
                       v_loc, preferred_element_type=jnp.float32)
        if N:
            o = o + jnp.einsum("hqk,khd->qhd",
                               probs[..., M:].astype(v_rem.dtype), v_rem,
                               preferred_element_type=jnp.float32)
        return o.reshape(blk, H * D).astype(q.dtype)

    with jax.named_scope("eva_attn"):
        n = (T + pad) // blk
        if n == 1:
            return block(0)[:T]
        out = jax.lax.map(block, jnp.arange(n, dtype=jnp.int32) * blk)
        return out.reshape(n * blk, H * D)[:T]


def span_summaries(k_all, v_all, base, start, n_chunks: int, mu, phi,
                   window: int, chunk: int):
    """Summaries of the ``n_chunks`` chunks from chunk ``start // chunk``
    on, their keys taken from ``k_all`` / ``v_all`` ``[base + T, H, D]``:
    a position ``s >= start`` lies at index ``base + s - start`` (the
    launch's own keys), an earlier one at its ring index ``s mod window``
    (``base`` is ``window`` where the ring stands before the launch's keys
    and 0 where ``start`` opens a chunk and nothing earlier is read).
    Returns ``(chunk ids [n], kbar [n, H, D], vbar [n, H, D])``; which of
    them are whole the caller knows."""
    first = start // chunk
    ids = first + jnp.arange(n_chunks, dtype=jnp.int32)
    s = ids[:, None] * chunk + jnp.arange(chunk, dtype=jnp.int32)[None]
    at = jnp.where(s >= start, base + s - start, jnp.mod(s, window))
    at = jnp.clip(at, 0, k_all.shape[0] - 1)
    kbar, vbar = pool_chunks(k_all[at], v_all[at], mu, phi)
    return ids, kbar, vbar


def aligned_ring_positions(start, window: int):
    """The position each ring index holds for a launch whose first token
    is ``start``: those of ``start``'s own window that precede it,
    negative elsewhere (stale, or a closed window's)."""
    j = jnp.arange(window, dtype=jnp.int32)
    return jnp.where(j < jnp.mod(start, window),
                     (start // window) * window + j, -1)
