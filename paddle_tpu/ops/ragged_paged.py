"""Ragged paged attention: ONE program for mixed prefill chunks + decode.

The serving engine's bounded compile count used to be paid for with three
separate bucketed program families (one-shot prefill / chunked prefill /
decode) and the padding each family's buckets waste.  Following Ragged
Paged Attention (PAPERS.md #1), this module serves the whole step shape
with a single kernel over a **packed token batch**: every scheduled
token — whether it belongs to a 1-token decode row or an n-token prefill
chunk — is one entry of a flat ``[T, H, D]`` query array, routed to its
sequence by per-token segment metadata:

``q``            ``[T, H, D]``   packed new-token queries (pads → null row)
``k/v_cache``    ``[num_blocks, block_size, Hkv, D]`` shared block pools
``block_tables`` ``[R, W]`` int32  per-ROW page tables (pad rows all-null)
``kv_lens``      ``[R]`` int32   total KV length per row AFTER this step
``seg_ids``      ``[T]`` int32   row each packed token belongs to
``q_pos``        ``[T]`` int32   absolute KV position of each token
→ out            ``[T, H, D]``

Token ``t`` attends causally over its row's pages: columns
``< min(kv_lens[seg_ids[t]], q_pos[t] + 1)`` — a decode row (one token at
position ``p``, ``kv_len = p + 1``) and a chunk token (mid-prompt
position) are the SAME predicate, which is what lets one launch fuse
both phases.  Padding tokens point at a pad row whose table is all null
pages (block 0) with ``kv_len = 1``; their output is finite garbage the
engine never reads.

Written twice against this one interface (the PR 9 oracle discipline):

* :func:`ragged_oracle` — the XLA gather/segment reference, the
  CPU-provable ground truth (the ragged analog of
  ``pallas_paged.decode_oracle``).  The interpret-mode parity sweep and
  the online :class:`~paddle_tpu.observability.audit.NumericsAuditor`
  both compare against it.
* :func:`_ragged_attention_kernel` — the Pallas TPU kernel: the block
  table rides scalar prefetch (``pltpu.PrefetchScalarGridSpec``) so the
  per-(token, page) grid step DMAs exactly the KV page it needs, with
  online-softmax state in VMEM scratch — the same shape as
  ``pallas_paged._decode_kernel`` with the per-SEQUENCE length swapped
  for the per-TOKEN causal limit.

**Mesh-spanning (the mp>1 fast path, at last):** :func:`ragged_paged_attention`
dispatches the kernel through ``shard_map`` over the ``mp`` axis — query
heads and KV pools sharded per ``KV_POOL_SPEC`` (the head dim), all
routing metadata replicated — so the Pallas path is no longer pinned off
under tensor parallelism: each shard runs the single-shard kernel on its
head slice and the row-parallel output projection does the psum, exactly
like the XLA path.  Interpret mode keeps the whole arrangement testable
on CPU meshes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged import SMEM_BYTES, SMEM_LANES, check_scalar_prefetch
from .pallas_x32 import no_x64

# np.float32 scalar, not a Python float: inside an OUTER jit the
# interpret-mode kernel body is staged and re-evaluated outside the
# no_x64() window, where a bare float would promote to f64 (same fix as
# pallas_paged / pallas_flash)
_NEG_INF = np.float32(-1e30)

# Which path the most recent dispatch took: "pallas" | "xla".
last_path = None


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def max_table_width(tokens: int) -> int:
    """Widest power-of-two block table a compiled launch packing
    ``tokens`` tokens can prefetch into scalar memory (0: none fits):
    ``tables[tokens, width]`` — each row padded to ``SMEM_LANES`` words —
    plus ``seg_ids``/``q_pos``/``kv_lens`` ``[tokens]``, all int32."""
    width = SMEM_BYTES // (4 * tokens) - 3
    return 1 << (width.bit_length() - 1) if width >= SMEM_LANES else 0


def ragged_oracle(q, k_cache, v_cache, block_tables, kv_lens, seg_ids,
                  q_pos):
    """XLA gather reference for the ragged packed step — the standing
    ground truth the Pallas kernel is differentially tested against
    (interpret-mode parity sweep offline, sampled shadow re-execution
    online via the NumericsAuditor).  Gathers each token's row pages to
    a dense ``[T, K, Hkv, D]`` context and masks with the per-token
    causal limit ``min(kv_lens[seg], q_pos + 1)``."""
    T, H, D = q.shape
    bs = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    rep = H // Hkv
    W = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)

    bt = block_tables[seg_ids]                       # [T, W]
    k = k_cache[bt].reshape(T, W * bs, Hkv, D)
    v = v_cache[bt].reshape(T, W * bs, Hkv, D)

    qg = q.reshape(T, Hkv, rep, D)
    logits = jnp.einsum("thrd,tkhd->thrk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    col = jnp.arange(W * bs)[None, :]
    limit = jnp.minimum(kv_lens[seg_ids], q_pos + 1)  # [T] causal ∧ len
    mask = col < limit[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("thrk,tkhd->thrd", probs, v.astype(jnp.float32))
    return out.reshape(T, H, D).astype(q.dtype)


def _ragged_kernel(seg_ref, pos_ref, bt_ref, len_ref, q_ref, k_ref, v_ref,
                   o_ref, acc_ref, m_ref, l_ref, *, scale, block_size,
                   n_pages, rep):
    """Grid (T, n_pages): token ``t`` walks its row's pages with online
    softmax in VMEM scratch — ``pallas_paged._decode_kernel`` with the
    sequence length replaced by the per-token causal limit."""
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    seg = seg_ref[t]
    # causal ∧ length limit for THIS token; pages beyond it are skipped
    # (their DMA still reads page bt[seg, j], which is 0-padded — harmless)
    limit = jnp.minimum(len_ref[seg], pos_ref[t] + 1)

    @pl.when(j * block_size < limit)
    def _step():
        q = q_ref[0]                         # [H, D]
        k = k_ref[0]                         # [bs, Hkv, D]
        v = v_ref[0]                         # [bs, Hkv, D]
        hkv = k.shape[1]
        # plain 2-D dots for Mosaic: unroll the (static, small) KV-head
        # dim in Python instead of a 3-D batched dot_general
        parts = []
        for kvh in range(hkv):
            qh = q[kvh * rep:(kvh + 1) * rep, :]         # [rep, D]
            kh = k[:, kvh, :]                            # [bs, D]
            parts.append(jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))     # [rep, bs]
        s2 = (parts[0] if hkv == 1
              else jnp.concatenate(parts, axis=0)) * scale   # [H, bs]
        col = jax.lax.broadcasted_iota(jnp.int32, s2.shape, 1) \
            + j * block_size
        s2 = jnp.where(col < limit, s2, _NEG_INF)

        m_prev = m_ref[:, 0]                             # [H]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=-1))
        alpha = jnp.exp(m_prev - m_new)                  # [H]
        p = jnp.exp(s2 - m_new[:, None])                 # [H, bs]
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, -1)
        m_ref[:, 0] = m_new
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
        o_ref[0] = (acc_ref[:]
                    / jnp.maximum(l_ref[:, 0], np.float32(1e-9))[:, None]
                    ).astype(o_ref.dtype)


def _ragged_attention_kernel(q, k_cache, v_cache, block_tables, kv_lens,
                             seg_ids, q_pos):
    """Single-shard Pallas launch over the packed token batch (interpret
    mode off-TPU).  Under ``shard_map`` this runs per mp shard on the
    local head slice — the metadata operands are replicated, so the page
    walk is identical on every shard."""
    T, H, D = q.shape
    num_blocks, bs, Hkv, _ = k_cache.shape
    rep = H // Hkv
    n_pages = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    # Mosaic has no i64: scalar-prefetch operands must be 32-bit
    seg_ids = seg_ids.astype(jnp.int32)
    q_pos = q_pos.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    kv_lens = kv_lens.astype(jnp.int32)
    check_scalar_prefetch("ragged_paged_attention", seg_ids, q_pos,
                          block_tables, kv_lens)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,   # seg_ids, q_pos, block_tables, kv_lens
        grid=(T, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda t, j, seg, qp, bt, ln: (t, 0, 0)),
            # the scalar-prefetched table steers each page DMA through
            # the token's OWN row — the ragged gather never materializes
            pl.BlockSpec((1, bs, Hkv, D),
                         lambda t, j, seg, qp, bt, ln:
                         (bt[seg[t], j], 0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, D),
                         lambda t, j, seg, qp, bt, ln:
                         (bt[seg[t], j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D),
                               lambda t, j, seg, qp, bt, ln: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),    # acc
            pltpu.VMEM((H, 1), jnp.float32),    # running max
            pltpu.VMEM((H, 1), jnp.float32),    # running sum
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, scale=scale, block_size=bs, n_pages=n_pages,
        rep=rep)
    with no_x64():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, H, D), q.dtype),
            interpret=_interpret(),
        )(seg_ids, q_pos, block_tables, kv_lens, q, k_cache, v_cache)


def _mesh_kernel(q, k_cache, v_cache, block_tables, kv_lens, seg_ids,
                 q_pos):
    """The kernel, mesh-spanning when an ``mp`` axis is live: queries and
    pools shard along the head dim (``KV_POOL_SPEC``), routing metadata
    replicated, and each shard runs the single-shard kernel on its local
    head slice — per-head attention needs no collective; the engine's
    row-parallel output projection supplies the psum."""
    from ..distributed import topology

    mesh = topology.get_mesh()
    if (mesh is None or "mp" not in mesh.axis_names
            or mesh.shape["mp"] == 1
            or q.shape[1] % mesh.shape["mp"]
            or k_cache.shape[2] % mesh.shape["mp"]):
        return _ragged_attention_kernel(q, k_cache, v_cache, block_tables,
                                        kv_lens, seg_ids, q_pos)
    from jax.sharding import PartitionSpec as P

    from ..parallel.utils import manual_sharding_mode
    from .paged_attention import KV_POOL_SPEC

    mapped = jax.shard_map(
        _ragged_attention_kernel, mesh=mesh,
        in_specs=(P(None, "mp", None), P(*KV_POOL_SPEC), P(*KV_POOL_SPEC),
                  P(), P(), P(), P()),
        out_specs=P(None, "mp", None), check_vma=False)
    with manual_sharding_mode():
        return mapped(q, k_cache, v_cache, block_tables, kv_lens,
                      seg_ids, q_pos)


def ragged_paged_attention(q, k_cache, v_cache, block_tables, kv_lens,
                           seg_ids, q_pos, use_pallas=None):
    """Packed ragged paged attention; returns ``[T, H, D]``.

    Dispatches to the Pallas kernel (``shard_map`` over ``mp`` when a
    mesh is live — the fast path spans the mesh instead of being pinned
    off at mp>1) when shapes are TPU-tileable, to the XLA gather
    reference otherwise; a kernel failure raises.  ``use_pallas``
    overrides the auto dispatch exactly like
    :func:`~paddle_tpu.ops.paged_attention.paged_attention`: ``True``
    forces the kernel (interpret mode off-TPU — the CPU parity path),
    ``False`` pins :func:`ragged_oracle`.  The operator kill switch
    (``PADDLE_TPU_DISABLE_PALLAS`` / ``disable_pallas_kernels``) still
    wins over ``use_pallas=True``
    (``paged_attention.pallas_dispatch`` is the one policy
    implementation both kernels share)."""
    global last_path
    from .paged_attention import pallas_dispatch

    T, H, D = q.shape
    tileable = D % 128 == 0 and k_cache.shape[1] % 8 == 0
    out, last_path = pallas_dispatch(
        lambda: _mesh_kernel(q, k_cache, v_cache, block_tables, kv_lens,
                             seg_ids, q_pos),
        lambda: ragged_oracle(q, k_cache, v_cache, block_tables, kv_lens,
                              seg_ids, q_pos),
        use_pallas, tileable)
    return out
