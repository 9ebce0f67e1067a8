"""Ring attention — context parallelism over the ``sep`` mesh axis.

Capability analog of the reference's SEP/segment parallelism
(``python/paddle/distributed/fleet/meta_parallel/segment_parallel.py:26`` +
four-direction p2p); the reference has **no** ring attention (SURVEY.md §5),
but SEP's long-context role maps exactly onto it, so this is the TPU-native
upgrade: K/V blocks rotate around the ring with ``ppermute`` over ICI while
each step's blockwise attention accumulates with an online softmax — compute
on block *i* overlaps the transfer of block *i+1* (XLA schedules the
collective-permute concurrently with the einsums).

Sequence layout [B, S, H, D]; each ``sep`` shard holds S/n of the sequence.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.dispatch import run_op
from ..core.tensor import Tensor
from ..distributed import topology
from .utils import manual_sharding_mode

SEP_AXIS = "sep"


def _block_attn(q, k, v, bias_mask, scale):
    """One blockwise attention step in f32: returns (numerator [B,Sq,H,D],
    row-sum [B,H,Sq], row-max [B,H,Sq]).  GQA-native: q [B,Sq,H,D] against
    k/v [B,Sk,Hkv,D] via grouped einsum — KV never repeated."""
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    qg = q.reshape(B, Sq, Hkv, rep, D)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = logits.reshape(B, H, Sq, Sk)
    if bias_mask is not None:
        logits = jnp.where(bias_mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)
    # guard fully-masked rows (future blocks under causal): exp(-inf - -inf)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1)
    pg = p.reshape(B, Hkv, rep, Sq, Sk)
    num = jnp.einsum("bhrqk,bkhd->bqhrd", pg, v.astype(jnp.float32))
    num = num.reshape(B, Sq, H, D)
    return num, l, jnp.where(jnp.isfinite(m), m, -jnp.inf)


def ring_attention_local(q, k, v, axis: str = SEP_AXIS, causal: bool = True):
    """Per-shard body (call inside shard_map): q/k/v are the local sequence
    shard [B, S/n, H, D]."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    B, Sl, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32)
    q_pos = idx * Sl + jnp.arange(Sl)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        o, l, m, k_cur, v_cur = carry
        src = (idx - i) % n  # which global block k_cur/v_cur came from
        if causal:
            k_pos = src * Sl + jnp.arange(Sl)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = jnp.broadcast_to(mask[None, None], (B, H, Sl, Sl))
        else:
            mask = None
        num, l_i, m_i = _block_attn(qf, k_cur.astype(jnp.float32),
                                    v_cur, mask, scale)
        # online softmax merge
        m_new = jnp.maximum(m, m_i)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        c_old = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        c_new = jnp.where(jnp.isfinite(m_i), jnp.exp(m_i - m_safe), 0.0)
        l_new = l * c_old + l_i * c_new
        o_new = (o * jnp.moveaxis(c_old, 1, -1)[..., None]
                 + num * jnp.moveaxis(c_new, 1, -1)[..., None])
        k_next = jax.lax.ppermute(k_cur, axis, perm)
        v_next = jax.lax.ppermute(v_cur, axis, perm)
        return o_new, l_new, m_new, k_next, v_next

    o0 = jnp.zeros((B, Sl, H, D), jnp.float32)
    l0 = jnp.zeros((B, H, Sl), jnp.float32)
    m0 = jnp.full((B, H, Sl), -jnp.inf, jnp.float32)
    o, l, m, _, _ = jax.lax.fori_loop(0, n, step, (o0, l0, m0, k, v))
    l = jnp.where(l > 0, l, 1.0)
    out = o / jnp.moveaxis(l, 1, -1)[..., None]
    return out.astype(q.dtype)


def ring_flash_attention(q: Tensor, k: Tensor, v: Tensor,
                         causal: bool = True, axis: str = SEP_AXIS) -> Tensor:
    """Tensor-level API: global [B, S, H, D] inputs, sequence sharded over
    ``axis`` (the SEP analog of ``SegmentParallel`` forward)."""
    mesh = topology.get_mesh()
    n = 1 if mesh is None else mesh.shape.get(axis, 1)
    if mesh is None or n == 1 or q.shape[1] % n != 0:
        from ..ops.flash_attention import flash_attention_fwd

        return run_op("ring_attention_fallback",
                      functools.partial(flash_attention_fwd, causal=causal),
                      q, k, v)

    dp = mesh.shape.get("dp", 1)
    bspec = "dp" if dp > 1 and q.shape[0] % dp == 0 else None
    spec = P(bspec, axis, None, None)
    body = functools.partial(ring_attention_local, axis=axis, causal=causal)
    mapped = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)

    def f(qv, kv, vv):
        with manual_sharding_mode():
            return mapped(qv, kv, vv)

    return run_op("ring_attention", f, q, k, v)
