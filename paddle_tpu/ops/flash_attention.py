"""Flash attention for TPU.

Capability analog of the reference's flash-attn v2 binding
(``paddle/phi/kernels/gpu/flash_attn_kernel.cu``), built as a Pallas kernel
(block-streamed online-softmax over KV tiles in VMEM) with an XLA composite
fallback for small sequences / non-TPU backends.

Layout: [B, S, H, D] (paddle flash-attn convention).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_FLASH_MIN_SEQ = 1024  # below this, XLA's fused softmax path is already fast
_CHUNKED_MIN_AREA = 1024 * 1024  # Sq*Sk at which S^2 scores become the
                                 # memory bottleneck -> scan recurrence

# Which path the most recent dispatch took: "pallas" | "xla_chunked"
# (lax.scan flash recurrence, long sequences) | "xla" (composite).
# Benchmarks and tests read this to see which path ran.
last_path: str | None = None


def use_flash(q_shape, attn_mask) -> bool:
    import os

    from ..core import flags

    if (os.environ.get("PADDLE_TPU_DISABLE_PALLAS") == "1"
            or flags.flag("disable_pallas_kernels")):
        return False  # kill switch: force the XLA composite path
    if attn_mask is not None:
        return False
    if len(q_shape) != 4:
        return False
    seq, head_dim = q_shape[1], q_shape[3]
    if seq < _FLASH_MIN_SEQ or seq % 128 != 0:
        return False
    # Mosaic tiling: the head_dim block must be lane-aligned (divisible by
    # 128) OR equal to the full array dim with sublane alignment — so 64
    # (BERT/GPT-2 head size; half-wide vregs, still beats the composite)
    # is legal alongside multiples of 128
    if head_dim % 128 != 0 and head_dim != 64:
        return False
    return jax.default_backend() == "tpu"


def _reference_attention(q, k, v, causal: bool):
    """XLA composite attention; GQA-native via grouped einsum (query heads
    reshaped [B,S,Hkv,rep,D] against ungrouped KV — no repeated KV buffer)."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, Hkv, rep, D)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v)
    return out.reshape(B, Sq, H, D)


def flash_attention_fwd(q, k, v, causal: bool = False):
    """Dispatch: Pallas fused kernel on TPU for long sequences, XLA otherwise."""
    global last_path
    if use_flash(q.shape, None):
        # the kernel is chosen from what the code can see (platform,
        # shape, mask) BEFORE the launch; if it then fails it raises —
        # it is never retried on the XLA path
        from ..core import flags as _flags
        from .autotune import cached_flash_blocks, tune_flash_blocks
        from .pallas_flash import flash_attention as pallas_flash

        # cache lookup is a dict get — always consult it, so committed
        # on-chip sweep results pick the block geometry without any
        # flag; live tuning (a measured sweep on first encounter of a
        # new shape) stays opt-in
        blocks = cached_flash_blocks(q.shape, k.shape, str(q.dtype), causal)
        if (blocks is None and _flags.flag("pallas_autotune")
                and not isinstance(q, jax.core.Tracer)):
            blocks = tune_flash_blocks(q, k, v, causal)
        # positional: custom_vjp with nondiff_argnums rejects kwargs
        if blocks is not None:
            out = pallas_flash(q, k, v, causal, blocks[0], blocks[1])
        else:
            out = pallas_flash(q, k, v, causal)
        last_path = "pallas"
        return out
    # XLA path: beyond this area the composite S^2 score matrix dominates
    # memory (it OOMs a 16 GB v5e at batch 8 x seq 2048 backward), so
    # long sequences take the lax.scan flash recurrence
    # (O(S*block_k) live memory) instead
    if q.shape[1] * k.shape[1] >= _CHUNKED_MIN_AREA:
        from .chunked_attention import chunked_attention

        last_path = "xla_chunked"
        return chunked_attention(q, k, v, causal)
    last_path = "xla"
    return _reference_attention(q, k, v, causal)
