"""Pallas TPU flash attention (forward + backward).

Capability analog of the reference's flash-attn v2 CUDA binding
(``paddle/phi/kernels/gpu/flash_attn_kernel.cu``), re-designed for the TPU
memory hierarchy: Q/K/V stream HBM→VMEM in MXU-aligned blocks, the online
softmax keeps running (max, sum, acc) statistics in VMEM scratch across the
KV grid dimension, and the backward recomputes P from the saved
log-sum-exp instead of materialising the [S, S] probability matrix —
O(S) memory in sequence length, matching FlashAttention-2's structure
but scheduled by the Mosaic pipeline (grid iteration double-buffers the
next KV block's DMA behind the current block's einsums automatically).

Public layout: [B, S, H, D] (paddle flash-attn convention).  Internally the
kernels run on [B, H, S, D]: Mosaic requires the last two dims of every
block to be divisible by (8, 128) or equal to the array dims, so the
blocked dims (seq, head_dim) must be the minor-most two — the wrapper
transposes at entry/exit (a layout change XLA fuses into neighbouring
ops).  Softmax statistics (lse, delta) travel as [B, H, S, 1] so their
(block_q, 1) blocks satisfy the same tiling rule.  All statistics are fp32
regardless of input dtype.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_x32 import no_x64

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = np.float32(-1e30)  # large-negative instead of -inf: keeps masked rows finite

_LANES = 128  # stats are kept (BQ, 128) — min f32 tile is (8, 128)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k,
                n_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: whole block is masked iff q_block_end < k_block_start
    run = True
    if causal:
        run = (qi + 1) * block_q > ki * block_k

    @pl.when(run)
    def _step():
        q = q_ref[0, 0, :, :]                    # [BQ, D]
        k = k_ref[0, 0, :, :]                    # [BK, D]
        v = v_ref[0, 0, :, :]                    # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [BQ, BK]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_k
            s = jnp.where(rows >= cols, s, _NEG_INF)

        m_prev = m_ref[:, :1]                    # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                   # [BQ, BK] f32
        corr = jnp.exp(m_prev - m_new)           # [BQ, 1]
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, jnp.float32(1.0), l)
        o_ref[0, 0, :, :] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0, :, 0] = (m_ref[:, 0] + jnp.log(safe_l[:, 0]))


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    B, S, H, D = q.shape
    Sk = k.shape[1]
    n_q = pl.cdiv(S, block_q)
    n_k = pl.cdiv(Sk, block_k)
    # GQA: query head h reads KV head h // group straight from the BlockSpec
    # index map — no jnp.repeat, no extra KV HBM traffic
    group = H // k.shape[2]

    # kernels run on [B, H, S, D] (Mosaic tiling: blocked dims minor-most)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k)

    with no_x64():
        out, lse = pl.pallas_call(
            kernel,
            grid=(B, H, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, i, j: (b, h // np.int32(group), j, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, i, j: (b, h // np.int32(group), j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
                jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
            interpret=_interpret(),
        )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, block_q, block_k, n_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = (qi + 1) * block_q > ki * block_k

    @pl.when(run)
    def _step():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]                # [BQ, 1]
        delta = delta_ref[0, 0, :, :]            # [BQ, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_k
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)                     # [BQ, BK]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [BQ, BK]
        ds = p * (dp - delta) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0, 0, :, :] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, n_q, group):
    ki = pl.program_id(2)
    gi = pl.program_id(3)
    qi = pl.program_id(4)

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (qi + 1) * block_q > ki * block_k

    @pl.when(run)
    def _step():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, :, :]                # [BQ, 1]
        delta = delta_ref[0, 0, :, :]            # [BQ, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_k
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)                     # [BQ, BK]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [BK, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale            # [BQ, BK]
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [BK, D]

    @pl.when((gi == group - 1) & (qi == n_q - 1))
    def _finish():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(res, g, *, scale, causal, block_q, block_k):
    q, k, v, out, lse = res
    B, S, H, D = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    group = H // Hkv
    n_q = pl.cdiv(S, block_q)
    n_k = pl.cdiv(Sk, block_k)
    do = g

    # delta_i = rowsum(dO_i · O_i)  — tiny elementwise reduce, leave to XLA
    delta = jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                       out.astype(jnp.float32))

    # kernels run on [B, H, S, D]; stats as [B, H, S, 1] (legal (bq, 1) tiles)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    lse4 = lse[..., None]
    delta4 = delta[..., None]

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, i, j: (b, h // np.int32(group), j, 0))
    r_spec = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))

    with no_x64():
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, n_k=n_k),
            grid=(B, H, n_q, n_k),
            in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
            out_specs=[q_spec],
            out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype)],
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=_interpret(),
        )(qt, kt, vt, dot, lse4, delta4)[0]

    # dk/dv: for each KV block, accumulate across the whole query-head group
    # then the q blocks — grid (B, Hkv, n_k, group, n_q), KV block resident
    # in VMEM for the full (group × n_q) sweep
    q_spec2 = pl.BlockSpec((1, 1, block_q, D),
                           lambda b, kh, j, g_, i: (b, kh * group + g_, i, 0))
    k_spec2 = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, kh, j, g_, i: (b, kh, j, 0))
    r_spec2 = pl.BlockSpec((1, 1, block_q, 1),
                           lambda b, kh, j, g_, i: (b, kh * group + g_, i, 0))
    with no_x64():
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, n_q=n_q,
                              group=group),
            grid=(B, Hkv, n_k, group, n_q),
            in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
            out_specs=[k_spec2, k_spec2],
            out_shape=[
                jax.ShapeDtypeStruct((B, Hkv, Sk, D), k.dtype),
                jax.ShapeDtypeStruct((B, Hkv, Sk, D), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
            interpret=_interpret(),
        )(qt, kt, vt, dot, lse4, delta4)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=False,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Fused attention over [B, S, H, D] q and [B, S, Hkv, D] k/v.

    GQA/MQA-native: when Hkv < H (H divisible by Hkv), each query head reads
    its group's KV head directly via the BlockSpec index map — KV is streamed
    from HBM once per group, never materialised repeated."""
    assert q.shape[2] % k.shape[2] == 0, (
        f"query heads {q.shape[2]} not divisible by kv heads {k.shape[2]}")
    scale = np.float32(1.0 / math.sqrt(q.shape[-1]))
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out


def _vjp_fwd(q, k, v, causal, block_q, block_k):
    scale = np.float32(1.0 / math.sqrt(q.shape[-1]))
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, block_q, block_k, res, g):
    scale = np.float32(1.0 / math.sqrt(res[0].shape[-1]))
    return _flash_bwd(res, g, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# serving prefill: the expanded core of latent attention (forward only)
# ---------------------------------------------------------------------------

#: query rows and keys of a grid step, the largest that divide the launch:
#: a step's fixed cost is a few tenths of a microsecond, and a 128 x 128
#: step's arithmetic is less than that
PREFILL_BLOCKS_Q = (1024, 512, 256, 128)
PREFILL_BLOCKS_K = (1024, 512, 256, 128)
#: the float32 scores and probabilities of one step beside two buffers of
#: each operand's block: more than Mosaic's default scope
_PREFILL_VMEM_BYTES = 64 << 20


def prefill_blocks(S: int, M: int):
    """``(query rows, keys)`` of a grid step of :func:`flash_prefill` for
    ``S`` queries over ``M`` keys, ``None`` where either is no whole number
    of the smallest block."""
    bq = next((b for b in PREFILL_BLOCKS_Q if S % b == 0), None)
    bk = next((b for b in PREFILL_BLOCKS_K if M % b == 0), None)
    return (bq, bk) if bq and bk else None


def _prefill_kernel(start_ref, lens_ref, qn_ref, qr_ref, kn_ref, kr_ref,
                    v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, block_q,
                    block_k, n_k):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    first = start_ref[b] + qi * block_q     # position of the block's row 0
    n = lens_ref[b]
    k0 = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def step(masked):
        s = (jax.lax.dot_general(
                qn_ref[0, 0], kn_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
             + jax.lax.dot_general(
                qr_ref[0, 0], kr_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) * scale   # [BQ, BK]
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + first
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k0
            s = jnp.where((cols <= rows) & (cols < n), s, _NEG_INF)
        v = v_ref[0, 0]
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    # a key block that begins past the block's last row, or at or past the
    # sequence's length, is all mask: no arithmetic (and, by the index
    # maps, no copy); one that ends at or before the block's first row and
    # inside the length needs no mask.  Key block 0 of a sequence that has
    # a token always runs and shows every row column 0, so a row's running
    # max is real before any block that masks the whole of it.
    run = (k0 <= first + (block_q - 1)) & (k0 < n)
    inside = (k0 + (block_k - 1) <= first) & (k0 + block_k <= n)

    @pl.when(run & inside)
    def _whole():
        step(masked=False)

    @pl.when(run & jnp.logical_not(inside))
    def _edge():
        step(masked=True)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, jnp.float32(1.0), l)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def flash_prefill(q, k_nope, k_r, v, scale, q_start, lens, blocks):
    """Causal attention of a prefill launch whose keys come in two parts,
    the scores never leaving VMEM (online softmax; float32 scores, running
    max, sum and accumulator; probabilities cast to ``v``'s dtype for the
    weighted sum).

    q: ``[B, S, H, nope + rope]``, the rows at positions ``q_start[b] +
    [0, S)``; k_nope: ``[B, H, M, nope]``; k_r: ``[B, M, rope]``, ONE key
    part shared by every head; v: ``[B, H, M, vd]``; ``q_start``, ``lens``:
    ``[B]`` int32 (scalar prefetch).  Score of row ``i``, column ``j``, head
    ``h``: ``(q[.., :nope] . k_nope[h, j] + q[.., nope:] . k_r[j]) * scale``
    where ``j <= q_start + i`` and ``j < lens``.  Returns ``[B, S, H, vd]``.

    Rows at or past ``lens`` are padding: they hold the weighted sum over
    the columns under ``lens`` (what the XLA form gives them), and all zeros
    where ``lens`` is 0 (the XLA form: the mean of ``v``).

    Grid ``(B, H, S / block_q, M / block_k)``, keys innermost.  A key block
    wholly past a query block's last row or past ``lens`` costs a grid step
    and nothing else: its index is clamped to the last block that query
    block needs, so it is not copied, and the step skips the arithmetic.
    ``blocks``: ``(block_q, block_k)``, divisors of ``S`` and ``M``
    (:func:`prefill_blocks` gives the ones measured best)."""
    return _prefill(q, k_nope, k_r, v, q_start.astype(jnp.int32),
                    lens.astype(jnp.int32), scale=float(scale),
                    blocks=tuple(blocks), interpret=_interpret())


# A jit of its own: a prefill program calls the kernel once a layer at one
# shape, and this way traces and lowers it ONCE (``pallas_ssm._step``); each
# inlined copy's ``op_name`` keeps the scope path of its own call site.
@functools.partial(jax.jit, static_argnames=("scale", "blocks", "interpret"))
def _prefill(q, k_nope, k_r, v, q_start, lens, *, scale, blocks, interpret):
    B, S, H, dq = q.shape
    M, nope = k_nope.shape[2], k_nope.shape[3]
    rope, vd = dq - nope, v.shape[3]
    block_q, block_k = blocks
    n_q, n_k = S // block_q, M // block_k
    # blocked dims minor-most (Mosaic); the two parts of q apart, so that
    # no block is sliced at a lane that is no multiple of 128
    qt = q.transpose(0, 2, 1, 3)
    q_nope, q_rope = qt[..., :nope], qt[..., nope:]

    def last(b, i, start, lens):    # last key block query block i needs
        diag = (start[b] + (i + 1) * block_q - 1) // block_k
        return jnp.minimum(diag, (jnp.maximum(lens[b], 1) - 1) // block_k)

    def q_map(b, h, i, j, start, lens):
        return b, h, i, 0

    def k_map(b, h, i, j, start, lens):
        return b, h, jnp.minimum(j, last(b, i, start, lens)), 0

    def kr_map(b, h, i, j, start, lens):
        return b, jnp.minimum(j, last(b, i, start, lens)), 0

    kernel = functools.partial(
        _prefill_kernel, scale=np.float32(scale), block_q=block_q,
        block_k=block_k, n_k=n_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # q_start, lens
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, nope), q_map),
            pl.BlockSpec((1, 1, block_q, rope), q_map),
            pl.BlockSpec((1, 1, block_k, nope), k_map),
            pl.BlockSpec((1, block_k, rope), kr_map),
            pl.BlockSpec((1, 1, block_k, vd), k_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, vd), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, vd), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
    )
    with no_x64():
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, S, vd), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary"),
                vmem_limit_bytes=_PREFILL_VMEM_BYTES),
            interpret=interpret,
            name="flash_prefill",       # its name in a device trace
        )(q_start, lens, q_nope, q_rope, k_nope, k_r, v)
    return out.transpose(0, 2, 1, 3)
