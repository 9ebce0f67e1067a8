"""Pallas TPU paged-attention decode kernels.

Capability analog of the reference's
``phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`` (vLLM-style
paged KV attention), re-designed for TPU: the per-sequence block table and
the lengths are **scalar-prefetch** arguments
(``pltpu.PrefetchScalarGridSpec``), so the kernel's copies go straight to
the KV pages a row holds — the gather never materializes a contiguous
[B, S, H, D] copy the way the XLA ``take`` path does.  Online softmax
statistics live in VMEM scratch across a row's pages, exactly like the
flash kernel (``pallas_flash.py``); GQA/MQA is native (query heads grouped
per KV head, KV pages are read once).

There are THREE kernels here.  Two stand behind
:func:`paged_attention_decode` (keys and values, ``[blocks, bs, Hkv, D]``
pools), and the pool's SHAPE chooses (:func:`pages_per_step`, ``P``: about 128 tokens of a row a
step, inside a VMEM budget):

* ``P == 1`` — a page already holds a step's worth of tokens (a window
  layer's ring seen as pages of 256): :func:`_decode_kernel`, grid
  ``(B, n_pages)``, one grid step a (row, page), the index map steering
  each step's HBM→VMEM copy to page ``bt[b, j]``.  Steps past a row's
  length compute nothing; their copy is skipped where the table repeats
  the page before (the ring's clamped tables).
* ``P > 1`` — small pages (16 tokens): :func:`_group_walk_kernel`, grid
  ``(B,)``, one grid step a ROW.  The pools stay in HBM
  (``memory_space=ANY``) and the row's pages are walked in GROUPS of ``P``:
  one DMA a page — a page ``[bs, Hkv, D]`` is contiguous in the pool — into
  one of two VMEM buffers ``[P*bs, Hkv, D]``, so that group ``g + 1`` (or
  the next row's first group) is in flight while group ``g`` is computed
  on.  The walk ends at the row's length: ``ceil(seq_len / (P*bs))``
  groups, no page past ``ceil(seq_len / bs)`` is read (a table's padding
  may name any block) and a row of length 0 costs a grid step and no copy,
  so a launch's cost follows the cache its rows hold, not rows x table
  width.  A group's KV heads are walked ``HEADS_UNROLLED`` (8) an
  iteration, in a loop INSIDE the kernel where there are more: a step's
  code grows with heads x tokens, and every program that holds the kernel
  loads that code at every warm start.

The third, :func:`latent_decode_attention`, is the group walk over a
LATENT pool (``[blocks, bs, lanes]``: one row a token that all query heads
share, the absorbed decode of ``paged_attention
.latent_paged_decode_attention``): the same copies, two buffers and end at
the row's length, groups of :func:`latent_pages_per_step` pages, and the
whole of a group's heads in ONE pair of products, since they all read the
same rows (:func:`_latent_walk_kernel`).

Each launch is a ``jax.jit`` of its own (:func:`_decode`,
:func:`_latent_decode`): a step program
calls it once a layer at the same shapes and so traces and lowers each
kernel once, not once a layer.  And because the group walk reads nothing of
a table's width, tables narrower than ``TABLE_WIDTH`` entries go in padded
to it, and launches of fewer than ``ROWS_MIN`` rows get empty rows
appended (:func:`_one_trace_shapes`): the programs of a process that differ
in their table width alone, or in a row bucket under 8, share one trace of
the kernel.

q: [B, H, D] (one decode token per sequence)
k/v_cache: [num_blocks, block_size, Hkv, D]
block_tables: [B, max_blocks] int32   (page ids per sequence; the group
                                       walk reads none past a row's last)
seq_lens: [B] int32
→ out: [B, H, D]
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_x32 import no_x64

# np.float32 scalar, not a Python float: inside an OUTER jit the
# interpret-mode kernel body is staged and re-evaluated outside the
# no_x64() window, where a bare float would promote to f64 and trip
# the MLIR verifier (same fix as pallas_flash's np-scalar consts)
_NEG_INF = np.float32(-1e30)


# Scalar-prefetch operands live in SMEM for the whole launch, their
# minor dimension padded to 128 words: the v5e compiler reports "Used
# 1.02M of 1.00M smem" for a [2048, 128] int32 table AND for a [2048, 64]
# one (tools/pallas_mosaic_check.py).  A little is kept for its own use.
SMEM_BYTES = (1 << 20) - 4096
SMEM_LANES = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def check_scalar_prefetch(kernel: str, *operands) -> None:
    """Refuse a compiled launch whose scalar-prefetch operands cannot fit
    scalar memory, naming the limit — instead of the compiler's
    RESOURCE_EXHAUSTED dump.  Interpret mode has no such limit."""
    if _interpret():
        return
    need = sum(int(np.prod(o.shape[:-1])) * 4
               * -(-o.shape[-1] // SMEM_LANES) * SMEM_LANES
               for o in operands)
    if need > SMEM_BYTES:
        shapes = ", ".join(str(tuple(o.shape)) for o in operands)
        raise ValueError(
            f"{kernel}: scalar-prefetch operands {shapes} need {need} "
            f"bytes of TPU scalar memory (rows padded to {SMEM_LANES} "
            f"words), limit {SMEM_BYTES} — shrink "
            "the token bucket or the block-table width (a longer "
            "context needs the kernel to walk pages without a "
            "per-token table, ROADMAP S4)")


def _decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, block_size, n_pages,
                   rep):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    seq_len = len_ref[b]
    # pages beyond the sequence are skipped entirely (their DMA still reads
    # page bt[b, j], which is 0-padded — harmless)
    @pl.when(j * block_size < seq_len)
    def _step():
        q = q_ref[0]                         # [H, D]
        k = k_ref[0]                         # [bs, Hkv, D]
        v = v_ref[0]                         # [bs, Hkv, D]
        hkv = k.shape[1]
        # Mosaic's matmul wants plain 2-D dots — unroll the (static, small)
        # KV-head dimension in Python instead of a 3-D batched dot_general.
        # logits[kvh*rep + r, t] = q[kvh*rep + r, :] · k[t, kvh, :]
        parts = []
        for kvh in range(hkv):
            qh = q[kvh * rep:(kvh + 1) * rep, :]         # [rep, D]
            kh = k[:, kvh, :]                            # [bs, D]
            parts.append(jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))     # [rep, bs]
        s2 = (parts[0] if hkv == 1
              else jnp.concatenate(parts, axis=0)) * scale   # [H, bs]
        pos = jax.lax.broadcasted_iota(jnp.int32, s2.shape, 1) + j * block_size
        s2 = jnp.where(pos < seq_len, s2, _NEG_INF)

        m_prev = m_ref[:, 0]                             # [H]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1))
        alpha = jnp.exp(m_prev - m_new)                  # [H]
        p = jnp.exp(s2 - m_new[:, None])                 # [H, bs]
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, -1)
        m_ref[:, 0] = m_new
        # pv[kvh*rep + r, d] = sum_t p[kvh*rep + r, t] v[t, kvh, d]
        pv_parts = []
        for kvh in range(hkv):
            ph = p[kvh * rep:(kvh + 1) * rep, :]         # [rep, bs]
            vh = v[:, kvh, :]                            # [bs, D]
            pv_parts.append(jax.lax.dot_general(
                ph.astype(jnp.float32), vh.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))     # [rep, D]
        pv = pv_parts[0] if hkv == 1 else jnp.concatenate(pv_parts, axis=0)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + pv

    @pl.when(j == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:, 0], np.float32(1e-9))[:, None]
                    ).astype(o_ref.dtype)


#: tokens of a row the group walk copies and computes on a step.  More
#: amortise a step's fixed cost better, but the kernel's code, which every
#: program that holds it loads at a warm start, grows with them
STEP_TOKENS = 128
#: VMEM the group walk's page buffers may hold: K and V, two buffers each
PAGE_BUFFER_BYTES = 8 << 20
#: KV heads one iteration of the group walk's pass over a group's heads
#: computes on, unrolled.  A pool with no more takes the pass in ONE
#: iteration with every index static; one with more loops (all 32 heads of
#: a 128-token group unrolled read 49.6% of the HBM roofline where 8 an
#: iteration read 37.7%, and doubled a 15-layer cell's warm set-up; chip
#: runs of the refused PR 37, ISSUE 38)
HEADS_UNROLLED = 8
#: entries of the table the group walk is launched with where the caller's
#: is narrower (8,192 tokens of 16-token pages), for launches of no more than
#: ``TABLE_WIDTH_ROWS`` rows: 128 x 512 entries are a quarter of scalar memory
TABLE_WIDTH = 512
TABLE_WIDTH_ROWS = 128
#: rows the group walk is launched with at least (empty ones appended)
ROWS_MIN = 8


def pages_per_step(block_size: int, kv_heads: int, head_dim: int,
                   itemsize: int, table_width: int) -> int:
    """Pages of ONE row the decode kernel copies and computes on a step:
    ``STEP_TOKENS`` tokens' worth (one page at least), never more than the
    table is wide or than lets ``2 (K, V) x 2 buffers x P`` pages fit
    ``PAGE_BUFFER_BYTES``.  1 selects the kernel of a step a (row, page),
    more the group walk."""
    page = block_size * kv_heads * head_dim * itemsize
    return max(1, min(STEP_TOKENS // block_size, table_width,
                      PAGE_BUFFER_BYTES // (4 * page)))


def kernel_pages(pool, table_width: int) -> int:
    """What :func:`paged_attention_decode` moves a step over ``pool``
    (``[blocks, bs, Hkv, D]``) at this table width: :func:`pages_per_step`
    of its shape, and 1 for a pool the group walk cannot take at all.
    Mosaic copies a page into a slice of a 16-bit buffer only where its
    heads fill whole sublane tiles (a multiple of 8 heads, or 1, 2 or 4 of
    them), and the walk splits a PAIR of 16-bit heads out of a 32-bit word
    by what a bfloat16 is (the high half of its float32)."""
    _, bs, heads, dim = pool.shape
    walks = pool.dtype == jnp.float32 or (
        pool.dtype == jnp.bfloat16 and (heads % 8 == 0 or heads in (1, 2, 4)))
    return pages_per_step(bs, heads, dim, pool.dtype.itemsize,
                          table_width) if walks else 1


def _group_walk_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, sems, slot_ref, q32_ref, s_ref, pv_ref,
                       acc_ref, m_ref, l_ref,
                       *, scale, block_size, pages, rep):
    """One grid step a ROW.  The row's pages are walked in groups of
    ``pages``: group ``g`` is copied (one DMA a page, K and V) into one of
    two VMEM buffers while group ``g - 1`` is computed on; the walk ends at
    the row's length, and its last step starts the copies of the next
    row's first group.  Nothing past ``ceil(seq_len / block_size)`` pages
    of a row is ever copied."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    bs = block_size
    step_tokens = pages * bs

    seq_len = len_ref[b]    # no more than the table holds: the launch's clamp
    n_groups = pl.cdiv(seq_len, step_tokens)

    def group_copies(row, g, slot, wait):
        """Start (or wait for) the copies of group ``g`` of ``row``."""
        first = g * pages

        def one(i, _):
            page = bt_ref[row, first + i]
            for hbm, buf, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                copy = pltpu.make_async_copy(
                    hbm.at[page], buf.at[slot, pl.ds(i * bs, bs)],
                    sems.at[s, slot])
                copy.wait() if wait else copy.start()
            return 0

        live = jnp.minimum(pages, pl.cdiv(len_ref[row], bs) - first)
        jax.lax.fori_loop(0, live, one, 0)

    # KV heads of 16 bits lie in PAIRS in a 32-bit word of a buffer's
    # second-minor dimension, the even head low: one strided read brings
    # both, and a bfloat16 IS the high half of its float32
    one_head = k_buf.ndim == 3
    paired = (not one_head and k_buf.dtype == jnp.bfloat16
              and k_buf.shape[2] % 2 == 0)
    heads = 1 if one_head else k_buf.shape[2]
    # heads an iteration of the pass: all of them, every index static,
    # unless there are more than HEADS_UNROLLED and Mosaic can read them at
    # a traced index (32-bit words only) into blocks of whole sublanes
    loop = (heads > HEADS_UNROLLED and heads % HEADS_UNROLLED == 0
            and (paired or k_buf.dtype.itemsize == 4))
    heads_it = HEADS_UNROLLED if loop else heads
    words_it = heads_it // (2 if paired else 1)

    def head_pass(buf, slot, lhs_ref, out_ref, contract):
        """``out[h*rep + r] = lhs[h*rep + r] (.) tile_h`` for every KV head
        ``h`` of the group in ``buf[slot]``; ``tile_h``: the head's
        ``[P*bs, D]`` keys or values as float32."""
        words = buf.bitcast(jnp.uint32) if paired else buf

        def tiles(j):           # of the heads in word ``j``
            if one_head:
                return [buf[slot].astype(jnp.float32)]
            w = words[slot, :, j, :]                     # [P*bs, D]
            if not paired:
                return [w.astype(jnp.float32)]
            return [pltpu.bitcast(w << 16, jnp.float32),
                    pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32)]

        def some(i, _):         # heads [i * heads_it, (i + 1) * heads_it)
            rows = pl.ds(pl.multiple_of(i * heads_it * rep, 8)
                         if loop else 0, heads_it * rep)
            lhs = lhs_ref[rows, :]
            out = []
            for u in range(words_it):
                for tile in tiles(i * words_it + u):
                    n = len(out) * rep
                    out.append(jax.lax.dot_general(
                        lhs[n:n + rep], tile,
                        (((1,), (contract,)), ((), ())),
                        preferred_element_type=jnp.float32))
            out_ref[rows, :] = (out[0] if len(out) == 1
                                else jnp.concatenate(out, axis=0))
            return 0

        if loop:
            jax.lax.fori_loop(0, heads // heads_it, some, 0)
        else:
            some(0, 0)

    @pl.when(b == 0)
    def _first_row():
        # what a row's last group leaves unwritten keeps an EARLIER
        # group's tokens (masked, weight 0): never uninitialised memory
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0

    prev_len = len_ref[jnp.maximum(b - 1, 0)]
    next_len = len_ref[jnp.minimum(b + 1, n_rows - 1)]
    # the row before started this row's first group, unless it was empty
    @pl.when((seq_len > 0) & ((b == 0) | (prev_len == 0)))
    def _own_first_group():
        group_copies(b, 0, slot_ref[0], wait=False)

    q32_ref[...] = q_ref[0].astype(jnp.float32)          # [H, D]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def group(g, slot):
        # before computing on this group, start the copies of the next:
        # this row's, or after its last the first of the next row
        more = g + 1 < n_groups

        @pl.when(more | ((b + 1 < n_rows) & (next_len > 0)))
        def _next():
            group_copies(jnp.where(more, b, b + 1), jnp.where(more, g + 1, 0),
                         1 - slot, wait=False)

        group_copies(b, g, slot, wait=True)
        # logits[kvh*rep + r, t] = q[kvh*rep + r, :] · k[t, kvh, :]
        head_pass(k_buf, slot, q32_ref, s_ref, 1)
        s2 = s_ref[...] * scale                          # [H, P*bs]
        pos = (jax.lax.broadcasted_iota(jnp.int32, s2.shape, 1)
               + g * step_tokens)
        s2 = jnp.where(pos < seq_len, s2, _NEG_INF)

        m_prev = m_ref[:, 0]                             # [H]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1))
        alpha = jnp.exp(m_prev - m_new)                  # [H]
        p = jnp.exp(s2 - m_new[:, None])                 # [H, P*bs]
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, -1)
        m_ref[:, 0] = m_new
        s_ref[...] = p
        # pv[kvh*rep + r, d] = sum_t p[kvh*rep + r, t] v[t, kvh, d]
        head_pass(v_buf, slot, s_ref, pv_ref, 0)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv_ref[...]
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, n_groups, group, slot_ref[0])
    # a row of length 0 (bucket padding) copied nothing and yields zeros
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, 0], np.float32(1e-9))[:, None]
                ).astype(o_ref.dtype)


def decode_oracle(q, k_cache, v_cache, block_tables, seq_lens):
    """The kernel's differential-testing oracle: the XLA gather path
    with identical routing semantics (``paged_attention._xla_paged_
    attention``), paired here so kernel and oracle live side by side.
    The fast CPU interpret-mode parity tests run every decode bucket
    shape through both, and the online :class:`~paddle_tpu
    .observability.audit.NumericsAuditor` re-executes sampled serving
    decode steps through the same reference — the standing harness the
    ROADMAP's ragged-kernel rewrite will land against."""
    from .paged_attention import _xla_paged_attention

    return _xla_paged_attention(q, k_cache, v_cache, block_tables,
                                seq_lens)


def _one_trace_shapes(q, block_tables, seq_lens, block_size):
    """A launch of a group walk at the shapes its trace is shared at.  The
    walk reads the first ``ceil(len / bs)`` entries of a row's table and
    nothing of its width, so every narrower table goes in at ONE width: a
    process then traces the kernel once a row bucket, not once a (rows,
    width) program.  Lengths are held to what the table holds."""
    rows, width = block_tables.shape
    seq_lens = jnp.minimum(seq_lens, width * block_size)
    if width < TABLE_WIDTH and rows <= TABLE_WIDTH_ROWS:
        block_tables = jnp.pad(block_tables,
                               ((0, 0), (0, TABLE_WIDTH - width)))
    if rows < ROWS_MIN:
        # and a row of length 0 costs a grid step and no copy: the
        # smallest row buckets share one trace too
        more = ROWS_MIN - rows
        q = jnp.pad(q, ((0, more), (0, 0), (0, 0)))
        block_tables = jnp.pad(block_tables, ((0, more), (0, 0)))
        seq_lens = jnp.pad(seq_lens, (0, more))
    return q, block_tables, seq_lens


def paged_attention_decode(q, k_cache, v_cache, block_tables, seq_lens):
    """Fused paged decode attention; returns [B, H, D]."""
    # Mosaic has no i64: scalar-prefetch operands must be 32-bit
    block_tables = block_tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    rows, width = block_tables.shape
    pages = kernel_pages(k_cache, width)
    if pages > 1:
        q, block_tables, seq_lens = _one_trace_shapes(
            q, block_tables, seq_lens, k_cache.shape[1])
    check_scalar_prefetch("paged_attention_decode", block_tables, seq_lens)
    return _decode(q, k_cache, v_cache, block_tables, seq_lens,
                   pages=pages, interpret=_interpret())[:rows]


# A jit of its own: a step program calls the kernel once a layer at the same
# shapes, and this way traces and lowers it ONCE a shape.  XLA inlines the
# calls, and each copy's ``op_name`` keeps the scope path of its own call site
# (the benchmark's ``attn`` / ``attn_window`` / ``attn_global`` readers).
@functools.partial(jax.jit, static_argnames=("pages", "interpret"))
def _decode(q, k_cache, v_cache, block_tables, seq_lens, *, pages, interpret):
    if pages > 1:
        return _group_walk(q, k_cache, v_cache, block_tables, seq_lens,
                           pages=pages, interpret=interpret)
    B, H, D = q.shape
    num_blocks, bs, Hkv, _ = k_cache.shape
    n_pages = block_tables.shape[1]
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # block_tables, seq_lens
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, bt, ln: (b, 0, 0)),
            # the scalar-prefetched block table drives the page DMA:
            pl.BlockSpec((1, bs, Hkv, D),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, D),
                         lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),    # acc
            pltpu.VMEM((H, 1), jnp.float32),    # running max
            pltpu.VMEM((H, 1), jnp.float32),    # running sum
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_size=bs, n_pages=n_pages, rep=rep)
    with no_x64():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
            interpret=interpret,
            name="paged_decode_attention",   # its name in a device trace
        )(block_tables, seq_lens, q, k_cache, v_cache)


def _group_walk(q, k_cache, v_cache, block_tables, seq_lens, *, pages,
                interpret):
    B, H, D = q.shape
    num_blocks, bs, Hkv, _ = k_cache.shape
    if Hkv == 1:
        # a page of ONE head is [bs, D]: Mosaic cannot slice a dimension
        # of 1 that its tiling pads to 2
        k_cache = k_cache.reshape(num_blocks, bs, D)
        v_cache = v_cache.reshape(num_blocks, bs, D)
    buffers = (2, pages * bs) + k_cache.shape[2:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # block_tables, seq_lens
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, bt, ln: (b, 0, 0)),
            # the pools stay in HBM: the kernel copies the pages the
            # scalar-prefetched block table names, and no others
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM(buffers, k_cache.dtype),
            pltpu.VMEM(buffers, v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),    # (K | V, buffer)
            pltpu.SMEM((1,), jnp.int32),        # buffer the next copy fills
            pltpu.VMEM((H, D), jnp.float32),    # the row's q
            pltpu.VMEM((H, pages * bs), jnp.float32),   # a group's scores
            pltpu.VMEM((H, D), jnp.float32),    # a group's weighted sum
            pltpu.VMEM((H, D), jnp.float32),    # acc
            pltpu.VMEM((H, 1), jnp.float32),    # running max
            pltpu.VMEM((H, 1), jnp.float32),    # running sum
        ],
    )
    kernel = functools.partial(
        _group_walk_kernel, scale=1.0 / math.sqrt(D), block_size=bs,
        pages=pages, rep=H // Hkv)
    with no_x64():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
            # rows run in order: each starts the next one's first copies
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="paged_decode_attention",   # its name in a device trace
        )(block_tables, seq_lens, q, k_cache, v_cache)


# --- the absorbed latent decode: one row a token, shared by every head --------

#: tokens of a row the latent walk copies and computes on a step.  A latent
#: page of 16 tokens is 20 KB, so the step's fixed cost wants many of them
#: under it, and a row's last group is computed on whole, so not too many:
#: 128 rows of ~1,950 tokens under 20 heads took 1.259 / 1.063 / 0.980 /
#: 0.976 ms at 256 / 512 / 1,024 / 2,048 (kernel alone; my chip run, PR 46)
LATENT_STEP_TOKENS = 1024


def latent_pages_per_step(block_size: int, lanes: int, itemsize: int,
                          table_width: int) -> int:
    """Pages of ONE row :func:`latent_decode_attention` copies and computes
    on a step: ``LATENT_STEP_TOKENS`` tokens' worth (one page at least),
    never more than the table is wide or than lets two buffers of them fit
    ``PAGE_BUFFER_BYTES``."""
    page = block_size * lanes * itemsize
    return max(1, min(LATENT_STEP_TOKENS // block_size, table_width,
                      PAGE_BUFFER_BYTES // (2 * page)))


def latent_kernel_pages(pool, table_width: int) -> int:
    """:func:`latent_pages_per_step` of a latent ``pool``
    (``[blocks, bs, lanes]``) at this table width."""
    _, bs, lanes = pool.shape
    return latent_pages_per_step(bs, lanes, pool.dtype.itemsize, table_width)


def _latent_scores(q, lat, k_r):
    """``[H, T]`` float32 scores of a row's ``H`` queries ``q [H, rank +
    rope]`` against a group's ``T`` cache rows, given as their latent part
    ``lat [T, rank]`` and their rope part ``k_r [T, rope]``: two products
    into one tile, the queries the small side of both."""
    rank = lat.shape[1]
    contract = (((1,), (1,)), ((), ()))
    return (jax.lax.dot_general(q[:, :rank], lat, contract,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(q[:, rank:], k_r, contract,
                                  preferred_element_type=jnp.float32))


def _latent_walk_kernel(bt_ref, len_ref, q_ref, pool_hbm, o_ref,
                        buf, sems, slot_ref, acc_ref, m_ref, l_ref,
                        *, scale, block_size, pages, rank):
    """:func:`_group_walk_kernel` over a latent pool: one grid step a ROW,
    its pages walked in groups of ``pages`` through two VMEM buffers, the
    walk ended at the row's length.  All ``H`` heads read the same rows, so
    a group is one pair of score products ``[H, rank + rope] x [T, ...]``
    and one weighted sum over the ``rank`` latent lanes."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    bs = block_size
    step_tokens = pages * bs

    seq_len = len_ref[b]    # no more than the table holds: the launch's clamp
    n_groups = pl.cdiv(seq_len, step_tokens)

    def group_copies(row, g, slot, wait):
        """Start (or wait for) the copies of group ``g`` of ``row``."""
        first = g * pages

        def one(i, _):
            copy = pltpu.make_async_copy(
                pool_hbm.at[bt_ref[row, first + i]],
                buf.at[slot, pl.ds(i * bs, bs)], sems.at[slot])
            copy.wait() if wait else copy.start()
            return 0

        live = jnp.minimum(pages, pl.cdiv(len_ref[row], bs) - first)
        jax.lax.fori_loop(0, live, one, 0)

    @pl.when(b == 0)
    def _first_row():
        # what a row's last group leaves unwritten keeps an EARLIER
        # group's tokens (masked, weight 0): never uninitialised memory
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

    prev_len = len_ref[jnp.maximum(b - 1, 0)]
    next_len = len_ref[jnp.minimum(b + 1, n_rows - 1)]
    # the row before started this row's first group, unless it was empty
    @pl.when((seq_len > 0) & ((b == 0) | (prev_len == 0)))
    def _own_first_group():
        group_copies(b, 0, slot_ref[0], wait=False)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def group(g, slot):
        # before computing on this group, start the copies of the next:
        # this row's, or after its last the first of the next row
        more = g + 1 < n_groups

        @pl.when(more | ((b + 1 < n_rows) & (next_len > 0)))
        def _next():
            group_copies(jnp.where(more, b, b + 1), jnp.where(more, g + 1, 0),
                         1 - slot, wait=False)

        group_copies(b, g, slot, wait=True)
        q = q_ref[0]                                     # [H, rank + rope]
        # a row's latent part and its rope part: lane-aligned slices of the
        # buffer where ``rank`` is a multiple of 128; operands in q's type
        lat = buf[slot, :, :rank].astype(q.dtype)        # [P*bs, rank]
        k_r = buf[slot, :, rank:q.shape[1]].astype(q.dtype)
        s2 = _latent_scores(q, lat, k_r) * scale         # [H, P*bs]
        pos = (jax.lax.broadcasted_iota(jnp.int32, s2.shape, 1)
               + g * step_tokens)
        s2 = jnp.where(pos < seq_len, s2, _NEG_INF)

        m_prev = m_ref[...]                              # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s2 - m_new)                          # [H, P*bs]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        m_ref[...] = m_new
        # the weighted sum runs over the latent lanes alone: the rope part
        # of a row is no value
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(lat.dtype), lat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, n_groups, group, slot_ref[0])
    # a row of length 0 (bucket padding) copied nothing and yields zeros
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], np.float32(1e-9))
                ).astype(o_ref.dtype)


def latent_decode_attention(qc, pool, block_tables, seq_lens, rank: int,
                            scale: float):
    """The core of the absorbed latent decode, pages read where they lie:
    ``u[b, h] = softmax_m(scale * qc[b, h] . row[b, m]) . row[b, m, :rank]``
    over the row's own ``m < seq_lens[b]``.  qc: ``[B, H, rank + rope]``
    (``W_UK`` already folded into its first ``rank`` lanes); pool:
    ``[blocks, bs, lanes]`` with a token's ``rank + rope`` values first in
    its lanes (the rest, a resident pool's padding to whole tiles, is never
    read); returns ``[B, H, rank]`` in ``qc``'s type."""
    block_tables = block_tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    rows, width = block_tables.shape
    pages = latent_kernel_pages(pool, width)
    qc, block_tables, seq_lens = _one_trace_shapes(
        qc, block_tables, seq_lens, pool.shape[1])
    check_scalar_prefetch("latent_decode_attention", block_tables, seq_lens)
    return _latent_decode(qc, pool, block_tables, seq_lens, pages=pages,
                          rank=rank, scale=float(scale),
                          interpret=_interpret())[:rows]


# A jit of its own, as :func:`_decode` is: traced and lowered once a row
# bucket, and each layer's copy keeps the scope path of its call site
# (``.../attn/mla_decode_core/...``: the benchmark's readers)
@functools.partial(jax.jit,
                   static_argnames=("pages", "rank", "scale", "interpret"))
def _latent_decode(qc, pool, block_tables, seq_lens, *, pages, rank, scale,
                   interpret):
    B, H, _ = qc.shape
    _, bs, lanes = pool.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # block_tables, seq_lens
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1,) + qc.shape[1:], lambda b, bt, ln: (b, 0, 0)),
            # the pool stays in HBM: the kernel copies the pages the
            # scalar-prefetched block table names, and no others
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages * bs, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),      # one a buffer
            pltpu.SMEM((1,), jnp.int32),        # buffer the next copy fills
            pltpu.VMEM((H, rank), jnp.float32),     # acc
            pltpu.VMEM((H, 1), jnp.float32),        # running max
            pltpu.VMEM((H, 1), jnp.float32),        # running sum
        ],
    )
    kernel = functools.partial(
        _latent_walk_kernel, scale=np.float32(scale), block_size=bs,
        pages=pages, rank=rank)
    with no_x64():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, rank), qc.dtype),
            # rows run in order: each starts the next one's first copies
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="latent_decode_attention",   # its name in a device trace
        )(block_tables, seq_lens, qc, pool)
