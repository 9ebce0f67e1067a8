"""Pallas TPU paged-attention decode kernel.

Capability analog of the reference's
``phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`` (vLLM-style
paged KV attention), re-designed for TPU: the per-sequence block table is a
**scalar-prefetch** argument (``pltpu.PrefetchScalarGridSpec``), so the
index map can steer each grid step's HBM→VMEM DMA straight to the right KV
page — the gather never materializes a contiguous [B, S, H, D] copy the
way the XLA ``take`` path does.  Online softmax statistics live in VMEM
scratch across the page dimension, exactly like the flash kernel
(``pallas_flash.py``); GQA/MQA is native (query heads grouped per KV head,
KV pages are read once).

q: [B, H, D] (one decode token per sequence)
k/v_cache: [num_blocks, block_size, Hkv, D]
block_tables: [B, max_blocks] int32   (page ids per sequence, 0-padded)
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


def paged_attention_decode(q, k_cache, v_cache, block_tables, seq_lens):
    """Fused paged decode attention; returns [B, H, D]."""
    B, H, D = q.shape
    num_blocks, bs, Hkv, _ = k_cache.shape
    rep = H // Hkv
    n_pages = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    # Mosaic has no i64: scalar-prefetch operands must be 32-bit
    block_tables = block_tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    check_scalar_prefetch("paged_attention_decode", block_tables, seq_lens)

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
            interpret=_interpret(),
            name="paged_decode_attention",   # its name in a device trace
        )(block_tables, seq_lens, q, k_cache, v_cache)
