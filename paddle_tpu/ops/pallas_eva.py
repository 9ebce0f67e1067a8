"""Pallas TPU kernels for the DECODE step of chunk-summarised (EVA)
attention (``ops/eva_attention.py``): a launch's attention read as TWO
partial attentions that each carry ``(weighted sum, max, sum)`` in float32
and are merged by their log-sum-exp (:func:`merge`), so that no score array
over ring and pool is ever written and each half reads what its rows hold.

* :func:`pool_partials` -- the summary rows.  The pool ``[num_blocks, R,
  H, D]`` is read IN PLACE in tiles of :func:`pool_tile_rows` rows (a tile
  is contiguous; 2 MB a side at the served shapes), ALL rows of the launch
  against a tile, under a mask of who sees what that is a tile-sized block
  input made once a step (:func:`pool_sight`).  A row's blocks come off a
  last-in-first-out free list, so held blocks fill a prefix of the pool
  about as long as its peak use: a tile in which NO row of the launch sees
  a row is neither copied nor computed on.  The tiles somebody sees are
  put first in the grid's order by a scalar-prefetched list; the steps
  after them repeat the last one's index (no copy) and do nothing.  No
  block hangs over the array: the rows past the pool's last whole tile
  (fewer than a tile) are a third partial attention, XLA's.
* :func:`ring_partials` -- the open window.  One grid step a ROW of the
  launch, its ring slot by scalar prefetch (no queries scattered into slot
  order, nothing gathered back); the slot's ring ``[W, H, D]`` stays in HBM
  and is copied in tiles of :func:`ring_tile_rows` entries up to ``(pos mod
  W) + 1`` through two VMEM buffers, the next row's first tile in flight
  while this row's last is computed on.  A padding row (length 0) costs a
  grid step and no copy.

**The pool's products are the MXU's, a PAIR of heads at a time**
(:func:`_pair_pass`).  Keys and values lie ``[entries, H, D]`` with two
16-bit heads in a 32-bit word of the second-minor dimension, the even head
low.  One strided read of word ``j`` brings a ``[T, D]`` tile of heads
``2j`` and ``2j + 1``, and seen as bfloat16 it IS ``[2T, D]`` with the two
heads' entries alternating: no shift, mask or convert.  The pair's queries
go in as ``[2 Bq, D]`` (head ``2j``'s ``Bq`` rows, then head ``2j + 1``'s),
one product gives ``[2 Bq, 2T]`` scores of which the half with matching
parity is real and the rest is masked, and the weighted sum over the same
interleaved tile sends each head's weights to its own values.  The tile is
the product's stationary operand, which both heads share, so the crossed
half costs the MXU nothing.  A pool of 32-bit values (a test's, in
interpret mode) has ONE head a word: the same pass with "pairs" of one,
kept because it alone holds the pass's online softmax to float32's digits
(a 16-bit pool rounds the weights to 8 bits before the second product, so
its cases can ask for 2e-2 and no more).

**The ring's are the VPU's** (:func:`_ring_kernel`): at ONE query row a
head the MXU form above is bound by the tiles it loads (0.73 ms a layer at
the served shapes against 0.50; PERF.md section 6, PR 47), while entries as
they lie, ``[chunk, H, D]`` with the heads down the sublanes, want no
relayout at all: a multiply by the queries and a sum over the lanes for the
scores, a multiply by the weights and a sum over the entries for the
values, in float32.

bfloat16 reads, float32 scores, statistics and sums in both: the XLA form's
precision.  Each launch is a ``jax.jit`` of its own (:func:`_pool`,
:func:`_ring`): a step program calls it once a layer at one shape and
traces it once.  Launches of fewer than :data:`ROWS_MIN` rows get empty
rows appended, so the row buckets 1 to 8 share one trace of each kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged import _NEG_INF, _interpret, check_scalar_prefetch
from .pallas_x32 import no_x64

#: bytes of ONE side (keys, or values) of a tile of the summary pool.  The
#: pipeline holds two buffers a side: four tiles in VMEM
POOL_TILE_BYTES = 2 << 20
#: bytes of one side of a tile of a ring, two buffers a side likewise
RING_TILE_BYTES = 1 << 20
#: VMEM a launch may use: the tiles above, the scores and the statistics
VMEM_LIMIT_BYTES = 48 << 20
#: rows a launch has at least (empty ones appended): a pair of heads'
#: queries ``[2 x 8, D]`` fill a 16-bit tile's sublanes
ROWS_MIN = 8
#: entries of a ring tile the ring's half computes on at a time: 16 rows 1.5k
#: to 24k bytes long took 0.73 / 0.54 / 0.51 / 0.50 ms a layer at 8 / 16 / 32
#: / 64 (kernel alone; my chip run, PR 47)
RING_CHUNK = 32


def takes(q, k_ring, k_rows) -> bool:
    """Whether the kernels take these shapes COMPILED: 16-bit pools whose
    heads pair into whole sublane tiles of 32-bit words, head vectors of
    whole lane tiles, a window of whole ring tiles of whole chunks."""
    H, D = k_rows.shape[-2:]
    tile = ring_tile_rows(*k_ring.shape[1:])
    return (q.dtype == k_ring.dtype == k_rows.dtype == jnp.bfloat16
            and H % 16 == 0 and D % 128 == 0
            and k_ring.shape[1] % tile == 0 and tile % RING_CHUNK == 0)


def _tile_rows(budget: int, heads: int, head_dim: int, rows: int) -> int:
    """The largest power of two of bfloat16 rows ``[heads, head_dim]``
    inside ``budget`` bytes, 64 at least (a tile's ``2T`` score columns
    are whole lane tiles), and no more than ``rows`` rounded down to that."""
    fit = max(64, budget // (heads * head_dim * 2))
    fit = 1 << (fit.bit_length() - 1)
    return min(fit, max(64, 1 << (max(rows, 1).bit_length() - 1)))


def pool_tile_rows(pool_rows: int, heads: int, head_dim: int) -> int:
    """Rows of the summary pool (``num_blocks x R`` of them) a grid step of
    :func:`pool_partials` reads; ``eva_attention.SummaryRows`` counts the tiles a
    launch sees by it."""
    return _tile_rows(POOL_TILE_BYTES, heads, head_dim, pool_rows)


def ring_tile_rows(window: int, heads: int, head_dim: int) -> int:
    """Entries of a row's ring a step of :func:`ring_partials` copies."""
    return min(window, _tile_rows(RING_TILE_BYTES, heads, head_dim, window))


def _per_word(dtype) -> int:
    """Heads of ``dtype`` in a 32-bit word of the second-minor dimension."""
    return 4 // jnp.dtype(dtype).itemsize


def _words(ref):
    """A tile's ref ``[T, H, D]`` by 32-bit words, the entries' words in one
    dimension: ``[T x P, D]`` (entry ``t``'s word ``j`` at ``t P + j``)."""
    if _per_word(ref.dtype) > 1:
        ref = ref.bitcast(jnp.uint32)
    return ref.reshape(ref.shape[0] * ref.shape[1], ref.shape[2])


def _pair_pass(q_ref, k_words, v_words, bias, o_ref, m_ref, l_ref, scale):
    """One tile of ``T`` entries against every pair of heads, online.
    ``q_ref`` ``[P, 2 Bq, D]`` in the pool's type (pair ``j``: head
    ``2j``'s rows, then head ``2j + 1``'s); ``k_words`` / ``v_words`` ``[T x
    P, D]`` views of the tile by words (:func:`_words`; word ``j``: heads
    ``2j`` low, ``2j + 1`` high); ``bias`` ``[2 Bq, 2T]`` float32, 0 where
    the row sees the column and -1e30 elsewhere (wherever row and column
    differ in parity among them); ``o_ref`` ``[P, 2 Bq, D]``, ``m_ref`` /
    ``l_ref`` ``[P, 2 Bq, 1]`` float32, the running weighted sum, max and
    sum.  (One head a word: ``[P, Bq, D]``, ``[Bq, T]``.)

    The pairs are unrolled: word ``j`` of every entry is ONE strided read
    only at a static ``j`` (at a traced one the pass took 1.3-2.6 times as
    long, PERF.md section 6, PR 47).  A row that has seen nothing yet has
    ``m = -1e30`` and weighs what it does not see 1: finite, wiped by the
    first entry it does see (``alpha = 0``), and for a row that never sees
    any the caller's to drop (:func:`_nothing_seen`)."""
    P = q_ref.shape[0]
    T = k_words.shape[0] // P

    def heads(words, j):    # word j of every entry: its heads' entries in turn
        tile = words[pl.ds(j, T, stride=P), :]
        return tile if tile.dtype == q_ref.dtype \
            else pltpu.bitcast(tile, q_ref.dtype)

    for j in range(P):
        kk, vv = heads(k_words, j), heads(v_words, j)           # [2T, D]
        s = jax.lax.dot_general(q_ref[j], kk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale + bias                                    # [2Bq, 2T]
        m_prev = m_ref[j]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[j] = l_ref[j] * alpha + jnp.sum(p, -1, keepdims=True)
        m_ref[j] = m_new
        o_ref[j] = o_ref[j] * alpha + jax.lax.dot_general(
            p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _nothing_seen(o, m, l):
    """Partials as the callers give them: a row that saw nothing (its max
    still far below any score) has weights and sums of exactly 0."""
    seen = m > np.float32(-1e29)
    return (jnp.where(seen[..., None], o, 0), jnp.where(seen, m, _NEG_INF),
            jnp.where(seen, l, 0))


def _start(o_ref, m_ref, l_ref):
    o_ref[...] = jnp.zeros_like(o_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


# --- the summary rows -----------------------------------------------------------

def _pool_kernel(tile_ref, n_ref, q_ref, see_ref, k_ref, v_ref,
                 o_ref, m_ref, l_ref, *, scale):
    """One grid step a tile SOMEBODY SEES (``n_ref[0]`` of them, first in
    ``tile_ref``); the outputs are the accumulators, resident all along."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _first():
        _start(o_ref, m_ref, l_ref)

    @pl.when(i < n_ref[0])
    def _tile():
        _pair_pass(q_ref, _words(k_ref), _words(v_ref), see_ref[...],
                   o_ref, m_ref, l_ref, scale)


def pool_sight(seen, tile: int, rows: int, per: int = 2):
    """What :func:`_pool` is steered by, from ``seen`` ``[B, N]`` bool (row
    ``b`` of the launch sees row ``n`` of the pool; ``N`` whole tiles): the
    mask in the kernel's layout, ``[per rows, per N]`` float32 with ``[par
    rows + b, per n + par']`` 0 where ``par == par'`` and ``seen[b, n]`` and
    -1e30 elsewhere (``per`` heads a word); the tiles in which anybody sees
    a row, first and in the pool's order, the last of them repeated after;
    and their number ``[1]``.  The same for every layer of a step: XLA
    keeps one."""
    B, N = seen.shape
    tiles = N // tile
    seen = jnp.pad(seen, ((0, rows - B), (0, 0)))
    same = jnp.eye(per, dtype=bool)
    see = jnp.where(seen[None, :, :, None] & same[:, None, None, :],
                    np.float32(0), _NEG_INF).reshape(per * rows, per * N)
    some = jnp.any(seen.reshape(rows, tiles, tile), axis=(0, 2))
    n = jnp.sum(some, dtype=jnp.int32)
    order = jnp.argsort(~some, stable=True).astype(jnp.int32)
    at = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                     jnp.maximum(n - 1, 0))
    return see, order[at], n[None]


def _pairs(q, rows: int, per: int):
    """``q`` ``[B, H, D]`` as the kernels take it: ``[H / per, per rows,
    D]``, pair ``j`` holding head ``per j``'s ``rows`` rows, then the next
    head's (rows past ``B`` zero)."""
    B, H, D = q.shape
    q = jnp.pad(q, ((0, rows - B), (0, 0), (0, 0)))
    return q.reshape(rows, H // per, per, D).transpose(1, 2, 0, 3).reshape(
        H // per, per * rows, D)


def _unpairs(a, rows: int):
    """The inverse of :func:`_pairs` on a result ``[H / per, per rows,
    X]``: ``[rows, H, X]``."""
    P, n, X = a.shape
    return a.reshape(P, n // rows, rows, X).transpose(2, 0, 1, 3).reshape(
        rows, P * (n // rows), X)


def _rest_partials(q, k, v, seen):
    """:func:`pool_partials`' result over the FEW rows ``k`` / ``v`` ``[t,
    H, D]`` that end a pool past its last whole tile, ``seen`` ``[B, t]``,
    in XLA: the kernel's blocks never hang over the array."""
    s = jnp.einsum("bhd,nhd->bhn", q.astype(k.dtype), k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(seen[:, None], s * np.float32(1.0 / math.sqrt(q.shape[-1])),
                  _NEG_INF)
    m = jnp.max(s, -1)
    p = jnp.where(seen[:, None], jnp.exp(s - m[..., None]), 0)
    return (jnp.einsum("bhn,nhd->bhd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32), m, jnp.sum(p, -1))


def pool_partials(q, k_rows, v_rows, seen, tile: int = None):
    """The launch's rows against the summary rows they see.  ``q`` ``[B, H,
    D]``; pools ``[num_blocks, R, H, D]``; ``seen`` ``[B, num_blocks x R]``
    bool.  Returns ``(o [B, H, D], m [B, H], l [B, H])`` float32: the
    weighted sum of the seen rows' values under ``exp(score - m)``, the
    largest score and the sum of the weights (0, with ``m`` -1e30, for a
    row that sees none).  The kernel takes the pool's whole tiles; the rows
    past the last of them (fewer than a tile: a deployment's ``num_blocks``
    comes from its memory) are XLA's (:func:`_rest_partials`).  ``tile``:
    rows a grid step (a test's; the pool's shape decides)."""
    B, H, D = q.shape
    N = k_rows.shape[0] * k_rows.shape[1]
    tile = tile or pool_tile_rows(N, H, D)
    whole = N // tile * tile
    k_rows, v_rows = k_rows.reshape(N, H, D), v_rows.reshape(N, H, D)
    parts = []
    if whole:
        rows, per = max(ROWS_MIN, B), _per_word(k_rows.dtype)
        see, tile_of, n = pool_sight(seen[:, :whole], tile, rows, per)
        check_scalar_prefetch("eva pool_partials", tile_of[None], n[None])
        o, m, l = _pool(_pairs(q.astype(k_rows.dtype), rows, per), k_rows,
                        v_rows, see, tile_of, n, tile=tile,
                        interpret=_interpret())
        o, m, l = (_unpairs(a, rows)[:B] for a in (o, m, l))
        parts.append(_nothing_seen(o, m[..., 0], l[..., 0]))
    if N > whole:
        parts.append(_rest_partials(q, k_rows[whole:], v_rows[whole:],
                                    seen[:, whole:]))
    return parts[0] if len(parts) == 1 else _sum(*parts)


# A jit of its own: a step program calls the kernel once a layer at one
# shape, and this way traces and lowers it ONCE.  XLA inlines the calls, and
# each copy's ``op_name`` keeps the scope path of its own call site
# (``.../eva_attn/eva_remote/...``: the benchmark's readers)
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _pool(q2, k_rows, v_rows, see, tile_of, n, *, tile, interpret):
    P, rows2, D = q2.shape
    H = k_rows.shape[1]
    tiles = tile_of.shape[0]
    per = H // P

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, t, n: (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # tile_of, n
        grid=(tiles,),
        in_specs=[
            whole(P, rows2, D),
            pl.BlockSpec((rows2, per * tile), lambda i, t, n: (0, t[i])),
            # the scalar-prefetched list steers the copy: a tile nobody
            # sees is not in it, and a repeated index copies nothing
            pl.BlockSpec((tile, H, D), lambda i, t, n: (t[i], 0, 0)),
            pl.BlockSpec((tile, H, D), lambda i, t, n: (t[i], 0, 0)),
        ],
        out_specs=[whole(P, rows2, D), whole(P, rows2, 1),
                   whole(P, rows2, 1)],
    )
    kernel = functools.partial(_pool_kernel,
                               scale=np.float32(1.0 / math.sqrt(D)))
    with no_x64():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((P, rows2, D), jnp.float32),
                       jax.ShapeDtypeStruct((P, rows2, 1), jnp.float32),
                       jax.ShapeDtypeStruct((P, rows2, 1), jnp.float32)],
            # tiles run in order: the outputs accumulate over them
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
            name="eva_pool_attention",      # its name in a device trace
        )(tile_of, n, q2, see, k_rows, v_rows)


# --- the open window --------------------------------------------------------------

def _ring_kernel(slot_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, m_ref, l_ref,
                 k_buf, v_buf, sems, buf_ref, *, scale, tile, chunk):
    """One grid step a ROW: the first ``len_ref[b]`` entries of ring
    ``slot_ref[b]`` in tiles of ``tile``, tile ``g`` computed on while tile
    ``g + 1`` (or the next row's first) is copied.  A tile is computed on
    ``chunk`` entries at a time as they lie, ``[chunk, H, D]`` (heads down
    the sublanes): the scores are a multiply by the row's queries and a sum
    over the lanes, the weighted sum a multiply by the weights and a sum
    over the entries, all float32."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n = len_ref[b]
    n_tiles = pl.cdiv(n, tile)

    def copies(row, g, buf, wait):
        """Start (or wait for) the copies of tile ``g`` of ``row``'s ring."""
        for hbm, vm, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
            copy = pltpu.make_async_copy(
                hbm.at[slot_ref[row], pl.ds(g * tile, tile)], vm.at[buf],
                sems.at[s, buf])
            copy.wait() if wait else copy.start()

    @pl.when(b == 0)
    def _first_row():
        buf_ref[0] = 0

    prev_len = len_ref[jnp.maximum(b - 1, 0)]
    next_len = len_ref[jnp.minimum(b + 1, n_rows - 1)]
    # the row before started this row's first tile, unless it was empty
    @pl.when((n > 0) & ((b == 0) | (prev_len == 0)))
    def _own_first_tile():
        copies(b, 0, buf_ref[0], wait=False)

    _start(o_ref, m_ref, l_ref)
    q = q_ref[...].astype(jnp.float32) * scale                  # [H, D]

    def one(g, buf):
        more = g + 1 < n_tiles

        @pl.when(more | ((b + 1 < n_rows) & (next_len > 0)))
        def _next():
            copies(jnp.where(more, b, b + 1), jnp.where(more, g + 1, 0),
                   1 - buf, wait=False)

        copies(b, g, buf, wait=True)

        def some(c, carry):
            m, l, acc = carry
            rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            k = k_buf[buf, rows].astype(jnp.float32)            # [chunk, H, D]
            v = v_buf[buf, rows].astype(jnp.float32)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)    # [chunk, H, 1]
            at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
                + (g * tile + c * chunk)
            s = jnp.where(at < n, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0))          # [H, 1]
            alpha = jnp.exp(m - m_new)
            # the tile's first entry is seen, so m_new is a score: what is
            # not seen weighs exp(-1e30 - m_new) = 0
            p = jnp.exp(s - m_new[None])
            return (m_new, l * alpha + jnp.sum(p, axis=0),
                    acc * alpha + jnp.sum(p * v, axis=0))

        # (no chunk past the row's last entry)
        m_ref[...], l_ref[...], o_ref[...] = jax.lax.fori_loop(
            0, pl.cdiv(jnp.minimum(n - g * tile, tile), chunk), some,
            (m_ref[...], l_ref[...], o_ref[...]))
        return 1 - buf

    buf_ref[0] = jax.lax.fori_loop(0, n_tiles, one, buf_ref[0])


def ring_partials(q, k_ring, v_ring, slots, lens):
    """Each row against the first ``lens[b]`` entries of its ring slot.
    ``q`` ``[B, H, D]``; rings ``[S, W, H, D]``; ``slots`` / ``lens``
    ``[B]`` (a length of 0: nothing is copied).  Returns ``(o [B, H, D], m
    [B, H], l [B, H])`` float32 as :func:`pool_partials` does."""
    B, H, D = q.shape
    W = k_ring.shape[1]
    rows = max(ROWS_MIN, B)
    q = jnp.pad(q, ((0, rows - B), (0, 0), (0, 0)))
    slots = jnp.pad(slots.astype(jnp.int32), (0, rows - B))
    lens = jnp.pad(jnp.minimum(lens, W).astype(jnp.int32), (0, rows - B))
    check_scalar_prefetch("eva ring_partials", slots[None], lens[None])
    tile = ring_tile_rows(W, H, D)
    o, m, l = _ring(q, k_ring, v_ring, slots, lens, tile=tile,
                    chunk=min(RING_CHUNK, tile), interpret=_interpret())
    return o[:B], m[:B, :, 0], l[:B, :, 0]


# A jit of its own, as :func:`_pool` is (``.../eva_attn/eva_local/...``)
@functools.partial(jax.jit, static_argnames=("tile", "chunk", "interpret"))
def _ring(q, k_ring, v_ring, slots, lens, *, tile, chunk, interpret):
    B, H, D = q.shape

    def row(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda b, s, n: (b,) + (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # slots, lens
        grid=(B,),
        in_specs=[
            row(H, D),
            # the rings stay in HBM: the kernel copies the entries a row
            # sees of the slot the scalar-prefetched ids name
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[row(H, D), row(H, 1), row(H, 1)],
        scratch_shapes=[
            pltpu.VMEM((2, tile, H, D), k_ring.dtype),
            pltpu.VMEM((2, tile, H, D), v_ring.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),    # (K | V, buffer)
            pltpu.SMEM((1,), jnp.int32),        # buffer the next copy fills
        ],
    )
    kernel = functools.partial(
        _ring_kernel, scale=np.float32(1.0 / math.sqrt(D)), tile=tile,
        chunk=chunk)
    with no_x64():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, H, D), jnp.float32),
                       jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                       jax.ShapeDtypeStruct((B, H, 1), jnp.float32)],
            # rows run in order: each starts the next one's first copies
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
            name="eva_ring_attention",      # its name in a device trace
        )(slots, lens, q, k_ring, v_ring)


# --- one softmax over both ------------------------------------------------------

def _sum(a, b):
    """Two partial attentions ``(o, m, l)`` over disjoint score sets as ONE
    over both: each rescaled to the larger max.  A part that saw nothing
    (``l`` 0, ``m`` -1e30) weighs nothing."""
    (o1, m1, l1), (o2, m2, l2) = a, b
    m = jnp.maximum(m1, m2)
    w1, w2 = jnp.exp(m1 - m), jnp.exp(m2 - m)
    return (w1[..., None] * o1 + w2[..., None] * o2, m, w1 * l1 + w2 * l2)


def merge(loc, rem):
    """Two partial attentions ``(o, m, l)`` over disjoint score sets as the
    one softmax over both: ``(e^{m1 - m} o1 + e^{m2 - m} o2) / (e^{m1 - m}
    l1 + e^{m2 - m} l2)`` with ``m = max(m1, m2)``, float32 ``[B, H, D]``.
    A row that saw nothing at all (a bucket's padding) yields zeros."""
    o, _, l = _sum(loc, rem)
    return o / jnp.maximum(l[..., None], np.float32(1e-30))
