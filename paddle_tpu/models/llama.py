"""Llama model family — the flagship (capability-ladder config #4: Llama-3-8B
pretrain with TP+PP+sharding).

Capability analog of PaddleNLP's ``llm/`` Llama stack that the reference's
north-star config targets, built TPU-first on the hybrid-parallel strategy
layer (:mod:`paddle_tpu.parallel`):

* **TP** — q/k/v/gate/up projections are :class:`ColumnParallelLinear`
  (``gather_output=False``), o/down are :class:`RowParallelLinear`
  (``input_is_parallel=True``): the Megatron column→row pairing with zero
  collectives inside the block and one GSPMD-inserted psum at the exit.
* **SP** — with ``config.sequence_parallel``, hidden states between blocks
  are constrained to ``P('dp', 'mp', None)`` (seq dim sharded over ``mp``);
  GSPMD turns the block-entry/exit layout changes into the all-gather /
  reduce-scatter pair of Megatron SP
  (``fleet/utils/sequence_parallel_utils.py`` analog).
* **CP** — attention routes through :func:`ring_flash_attention` whenever the
  ``sep`` axis is >1 (K/V ppermute ring over ICI), the long-context answer to
  the reference's SEP axis.
* **PP** — the decoder stack is homogeneous single-input layers, so it drops
  straight into :class:`PipelineLayer` + :func:`pipeline_forward` (shard_map
  collective-permute microbatch schedule); embedding/head stay outside.
* **recompute** — per-decoder-layer ``jax.checkpoint`` via
  :func:`paddle_tpu.parallel.recompute`.

Architecture follows Llama-3: RMSNorm pre-norm, rotary embeddings, grouped
query attention, SwiGLU MLP, untied LM head (tying supported).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.container import LayerList
from ..nn.initializer import Constant, Normal
from ..nn.layers import Layer
from ..nn.norm import RMSNorm
from ..ops.hyper_connections import pop_health
from ..parallel.moe import clamped_swiglu, pop_load
from ..parallel.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..parallel.pipeline import PipelineLayer, pipeline_forward
from ..parallel.recompute import recompute as _recompute
from ..parallel.ring_attention import ring_flash_attention
from ..parallel.utils import axis_size, sharding_constraint
from ..core.dispatch import run_op


@dataclass
class LlamaConfig:
    """Llama-3 family hyperparameters (defaults = Llama-3-8B)."""

    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    # parallel/perf knobs
    sequence_parallel: bool = False
    recompute: bool = False
    use_flash_attention: bool = True
    scan_layers: bool = False           # lax.scan over the decoder stack:
                                        # ONE compiled layer body instead of
                                        # L inlined copies (~L× faster XLA
                                        # compile; same math, same params)
    dtype: str = "float32"
    virtual_pp_degree: int = 1          # interleaved VPP chunks per device
    attention_bias: bool = False        # q/k/v biases (Qwen2 family)
    use_rope: bool = True               # False: no positional rotation (a
                                        # hybrid whose recurrent layers carry
                                        # the order, models/mamba_hybrid.py)
    swiglu_limit: Optional[float] = None    # clamp of the SwiGLU's branches
                                        # (parallel.moe.clamped_swiglu)
    # MoE knobs (0 experts = dense; DeepSeek/Qwen2-MoE style otherwise)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 0      # per-expert FFN width
    num_shared_experts: int = 0         # always-on experts (DeepSeek-MoE)
    moe_norm_topk_prob: bool = True     # renormalize top-k gate weights
                                        # (GShard/Mixtral); False = raw
                                        # softmax probs (DeepSeek/Qwen2-MoE)
    moe_shared_expert_gated: bool = False  # sigmoid-gate the shared
                                        # expert output (Qwen2-MoE)
    first_k_dense_replace: int = 0      # first k layers use a DENSE MLP
                                        # (DeepSeek-MoE: layer 0 is dense)
    aux_loss_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def llama3_8b(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Test/dry-run config."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=256, rope_theta=10000.0)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny_moe(cls, **kw):
        """Tiny MoE config (DeepSeek-MoE shape: shared + routed experts)."""
        defaults = dict(num_experts=4, num_experts_per_tok=2,
                        moe_intermediate_size=64, num_shared_experts=1)
        defaults.update(kw)
        return cls.tiny(**defaults)

    @classmethod
    def deepseek_moe_16b(cls, **kw):
        """DeepSeekMoE-16B widths (BASELINE config #5): 64 routed + 2
        shared experts, top-6 routing.  A TRAINING preset: its expert layer
        is ``LlamaMoEBlock`` on the GShard dispatch (``parallel/moe.py``
        ``MoELayer``), which has a capacity and DROPS the tokens past it,
        with softmax gates.  It is not served and has never run on the
        chip; the served expert layer is the dropless one
        (``parallel.moe.dropless_experts``, ``models/moe_mla.py``)."""
        defaults = dict(
            vocab_size=102400, hidden_size=2048, intermediate_size=10944,
            num_hidden_layers=28, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=4096,
            num_experts=64, num_experts_per_tok=6,
            moe_intermediate_size=1408, num_shared_experts=2,
            moe_norm_topk_prob=False,   # DeepSeek-MoE: raw softmax gates
            first_k_dense_replace=1)    # layer 0 is a dense MLP
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def qwen2_moe_a14b(cls, **kw):
        """Qwen2-57B-A14B MoE widths (BASELINE config #5): 64 routed +
        shared expert, top-8 routing, GQA 4:1.  A TRAINING preset like
        :meth:`deepseek_moe_16b`: the capacity-dropping GShard layer,
        not served; the served expert layer is the dropless one."""
        defaults = dict(
            vocab_size=151936, hidden_size=3584, intermediate_size=18944,
            num_hidden_layers=28, num_attention_heads=28,
            num_key_value_heads=4, max_position_embeddings=32768,
            num_experts=64, num_experts_per_tok=8,
            # shared_expert_intermediate_size 20480 = 8 x 2560 (ONE gated
            # shared MLP of that width; our sizing is ff x n_shared)
            moe_intermediate_size=2560, num_shared_experts=8,
            moe_norm_topk_prob=False,      # Qwen2-MoE raw softmax gates
            moe_shared_expert_gated=True,  # sigmoid-gated shared expert
            attention_bias=True)           # Qwen2 q/k/v biases
        defaults.update(kw)
        return cls(**defaults)


def _rope_tables(head_dim: int, max_pos: int, theta: float):
    # Host-side numpy: sliced at trace time and embedded as jit constants.
    # Deliberately NOT device buffers — a committed array carries a mesh
    # sharding that conflicts inside shard_map (Manual) pipeline bodies.
    import numpy as np

    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    t = np.arange(max_pos, dtype=np.float32)
    freqs = np.outer(t, inv)                       # [S, D/2]
    return np.cos(freqs), np.sin(freqs)


def _apply_rope(x, cos, sin):
    """x: [B, S, H, D]; cos/sin: [S, D/2], or [B, S, D/2] for per-sequence
    positions (paged batched decode)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    if cos.ndim == 3:
        cos = cos[:, :, None, :].astype(x.dtype)
        sin = sin[:, :, None, :].astype(x.dtype)
    else:
        cos = cos[None, :, None, :].astype(x.dtype)
        sin = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


class LlamaAttention(Layer):
    """Grouped-query attention with rotary embeddings.

    TP: head dim sharded over ``mp`` via column/row parallel projections;
    after reshape the head axis carries the ``mp`` sharding (constraint
    re-pinned below so GSPMD keeps attention fully local per mp shard).
    """

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        init = Normal(0.0, config.initializer_range)
        bias = config.attention_bias
        self.q_proj = ColumnParallelLinear(h, self.num_heads * hd,
                                           has_bias=bias, gather_output=False,
                                           weight_attr=init)
        self.k_proj = ColumnParallelLinear(h, self.num_kv_heads * hd,
                                           has_bias=bias, gather_output=False,
                                           weight_attr=init)
        self.v_proj = ColumnParallelLinear(h, self.num_kv_heads * hd,
                                           has_bias=bias, gather_output=False,
                                           weight_attr=init)
        self.o_proj = RowParallelLinear(self.num_heads * hd, h, has_bias=False,
                                        input_is_parallel=True, weight_attr=init)
        self._rope_cos, self._rope_sin = _rope_tables(
            hd, config.max_position_embeddings, config.rope_theta) \
            if config.use_rope else (None, None)

    def forward(self, x, cache=None, pos=None):
        B, S = x.shape[0], x.shape[1]
        hd = self.config.head_dim
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)

        def shape_heads(t, n):
            out = run_op("reshape_heads",
                         lambda a: a.reshape(B, S, n, hd), t)
            return sharding_constraint(out, "dp", "sep", "mp", None)

        q = shape_heads(q, self.num_heads)
        k = shape_heads(k, self.num_kv_heads)
        v = shape_heads(v, self.num_kv_heads)

        if not self.config.use_rope:
            pass        # the configuration rotates nothing
        elif pos is None:
            cos, sin = self._rope_cos[:S], self._rope_sin[:S]
            q = run_op("rope", lambda a: _apply_rope(a, cos, sin), q)
            k = run_op("rope", lambda a: _apply_rope(a, cos, sin), k)
        else:
            # decode: gather tables at traced positions [pos, pos+S)
            cos_t, sin_t = self._rope_cos, self._rope_sin

            def rope_at(a, p):
                # scalar pos: shared offset; [B] pos: per-sequence
                # offsets; [B, S] pos: absolute per-TOKEN positions (the
                # packed ragged step, where row `t` of the flat token
                # batch sits at an arbitrary position of its own segment)
                if jnp.ndim(p) == 2:
                    idx = p
                else:
                    idx = (p[:, None] if jnp.ndim(p) == 1 else p) \
                        + jnp.arange(S)
                return _apply_rope(a, jnp.asarray(cos_t)[idx],
                                   jnp.asarray(sin_t)[idx])

            q = run_op("rope_at", rope_at, q, pos)
            k = run_op("rope_at", rope_at, k, pos)

        if cache is not None:
            return self._cached_attention(q, k, v, cache, pos, B, S, hd)

        # GQA KV heads are consumed natively by every attention path (pallas
        # index maps / grouped einsums) — never repeated into 4x HBM traffic
        # ring attention when sequence is sep-sharded; per-device flash/XLA
        # attention otherwise (ring_flash_attention falls through itself)
        out = ring_flash_attention(q, k, v, causal=True)
        out = run_op("merge_heads",
                     lambda a: a.reshape(B, S, self.num_heads * hd), out)
        out = sharding_constraint(out, "dp", "sep", "mp")
        return self.o_proj(out)

    def _cached_attention(self, q, k, v, cache, pos, B, S, hd):
        """KV-cached attention for generation: append k/v into the static
        [B, M, Hkv, D] buffers at ``pos`` and attend over the valid prefix
        (fixed shapes + length mask — one compiled decode step serves every
        position; the serving analog of the reference's fused decode path).

        A :class:`~paddle_tpu.ops.paged_attention.PagedCache` routes to the
        block-pool path instead (vLLM-style serving; the reference's
        ``block_multi_head_attention`` kernel)."""
        from ..ops.paged_attention import PagedCache

        if isinstance(cache, PagedCache):
            return self._paged_attention(q, k, v, cache, B, S, hd)
        k_buf, v_buf = cache

        def upd(buf, new, p):
            zero = jnp.zeros((), p.dtype) if hasattr(p, "dtype") else 0
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), (zero, p, zero, zero))

        k_buf._rebind(run_op("kv_write", upd, k_buf, k, pos))
        v_buf._rebind(run_op("kv_write", upd, v_buf, v, pos))

        rep = self.num_heads // self.num_kv_heads
        scale = 1.0 / math.sqrt(hd)

        def attend(qv, kb, vb, p):
            # GQA grouped einsum: q [B,S,Hkv,rep,D] vs KV [B,M,Hkv,D] —
            # the cache is streamed once, not repeated rep× (hot decode path)
            M = kb.shape[1]
            qg = qv.reshape(B, S, self.num_kv_heads, rep, hd)
            logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, kb,
                                preferred_element_type=jnp.float32) * scale
            col = jnp.arange(M)[None, :]
            row = jnp.arange(S)[:, None]
            mask = col <= (p + row)               # causal over written prefix
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhrqk,bkhd->bqhrd", probs.astype(vb.dtype), vb)
            return out.reshape(B, S, self.num_heads * hd)

        out = run_op("cached_attention", attend, q, k_buf, v_buf, pos)
        return self.o_proj(out)

    def _paged_attention(self, q, k, v, cache, B, S, hd):
        """Decode (S=1) over the shared block pool: scatter this step's K/V
        into each sequence's slot (block, offset) then fused paged attention
        (``ops/pallas_paged.py`` on TPU).

        When the cache routes [B, S] slot arrays (chunked prefill), the S
        chunk tokens scatter into their per-token slots instead and attend
        causally over the paged prefix INCLUDING the chunk itself —
        ``cache.q_start`` offsets the causal mask to the chunk's global
        position."""
        from ..ops import paged_attention as pa_mod

        if cache.seg_ids is not None:
            return self._ragged_paged_attention(q, k, v, cache, B, S, hd)
        if cache.slot_blocks is not None and cache.slot_blocks.ndim == 2:
            return self._chunk_paged_attention(q, k, v, cache, B, S, hd)
        assert S == 1, "paged cache path is decode-only (one token per step)"
        kp, vp = cache.k_pool, cache.v_pool
        blocks, offs = cache.slot_blocks, cache.slot_offsets

        def write(pool, new):
            return pool.at[blocks, offs].set(new[:, 0].astype(pool.dtype))

        kp._rebind(run_op("paged_kv_write", write, kp, k))
        vp._rebind(run_op("paged_kv_write", write, vp, v))

        def attend(qv, kpool, vpool):
            return pa_mod.paged_attention(
                qv[:, 0], kpool, vpool, cache.block_tables, cache.seq_lens,
                use_pallas=cache.use_pallas)[:, None]

        out = run_op("paged_attention", attend, q, kp, vp)
        out = run_op("merge_heads",
                     lambda a: a.reshape(B, S, self.num_heads * hd), out)
        return self.o_proj(out)

    def _ragged_paged_attention(self, q, k, v, cache, B, S, hd):
        """Unified ragged step (ISSUE 11): the batch is ONE packed row of
        S tokens spanning many sequences — each token scatters into its
        own (block, offset) slot (pads write the null page), then one
        fused ragged attention launch serves every decode row and prefill
        chunk together (``ops/ragged_paged.py``: Pallas via shard_map
        over ``mp``, or the XLA gather reference)."""
        from ..ops import ragged_paged as rp_mod

        kp, vp = cache.k_pool, cache.v_pool
        blocks, offs = cache.slot_blocks, cache.slot_offsets  # [T]

        def write(pool, new):
            return pool.at[blocks, offs].set(new[0].astype(pool.dtype))

        kp._rebind(run_op("paged_kv_write", write, kp, k))
        vp._rebind(run_op("paged_kv_write", write, vp, v))

        def attend(qv, kpool, vpool):
            return rp_mod.ragged_paged_attention(
                qv[0], kpool, vpool, cache.block_tables, cache.seq_lens,
                cache.seg_ids, cache.q_start,
                use_pallas=cache.use_pallas)[None]

        out = run_op("ragged_paged_attention", attend, q, kp, vp)
        out = run_op("merge_heads",
                     lambda a: a.reshape(B, S, self.num_heads * hd), out)
        return self.o_proj(out)

    def _chunk_paged_attention(self, q, k, v, cache, B, S, hd):
        """Chunked prefill over the shared block pool: scatter the chunk's
        S tokens into their (block, offset) slots — pads write the null
        page — then causal attention over the gathered pages
        (``ops/paged_attention.paged_prefill_attention``)."""
        from ..ops import paged_attention as pa_mod

        kp, vp = cache.k_pool, cache.v_pool
        blocks, offs = cache.slot_blocks, cache.slot_offsets  # [B, S]

        def write(pool, new):
            return pool.at[blocks, offs].set(new.astype(pool.dtype))

        kp._rebind(run_op("paged_kv_write", write, kp, k))
        vp._rebind(run_op("paged_kv_write", write, vp, v))

        def attend(qv, kpool, vpool):
            return pa_mod.paged_prefill_attention(
                qv, kpool, vpool, cache.block_tables, cache.seq_lens,
                cache.q_start)

        out = run_op("paged_prefill_attention", attend, q, kp, vp)
        out = run_op("merge_heads",
                     lambda a: a.reshape(B, S, self.num_heads * hd), out)
        return self.o_proj(out)


class LlamaMLP(Layer):
    """SwiGLU feed-forward, column→row TP pairing.  Under ``swiglu_limit``
    the two branches are clamped before their product
    (``parallel.moe.clamped_swiglu``)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, ff = config.hidden_size, config.intermediate_size
        init = Normal(0.0, config.initializer_range)
        self.gate_proj = ColumnParallelLinear(h, ff, has_bias=False,
                                              gather_output=False, weight_attr=init)
        self.up_proj = ColumnParallelLinear(h, ff, has_bias=False,
                                            gather_output=False, weight_attr=init)
        self.down_proj = RowParallelLinear(ff, h, has_bias=False,
                                           input_is_parallel=True, weight_attr=init)
        self.limit = config.swiglu_limit

    def forward(self, x):
        if self.limit is not None:
            return self.down_proj(run_op(
                "swiglu_clamped",
                lambda g, u: clamped_swiglu(g, u, self.limit).astype(g.dtype),
                self.gate_proj(x), self.up_proj(x)))
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaMoEBlock(Layer):
    """DeepSeek/Qwen2-MoE FFN: optional always-on shared experts + top-k
    routed experts with expert parallelism (ladder config #5; built on
    :class:`paddle_tpu.parallel.MoELayer`'s GShard dispatch — the E-sharded
    buffer's all-to-all rides ICI over the ``sep``/ep axis)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        from ..nn.common import Linear
        from ..parallel.moe import FusedMoEMLP, MoELayer, TopKGate

        ff = config.moe_intermediate_size or config.intermediate_size
        self.moe = MoELayer(
            config.hidden_size,
            FusedMoEMLP(config.num_experts, config.hidden_size, ff,
                        activation="swiglu"),
            # k=1 keeps Switch semantics (raw prob) regardless of the
            # flag — _topk_gating never renormalizes a single gate
            gate=TopKGate(config.hidden_size, config.num_experts,
                          k=config.num_experts_per_tok,
                          normalize=config.moe_norm_topk_prob))
        if config.num_shared_experts > 0:
            shared_cfg = LlamaConfig(**{**config.__dict__})
            shared_cfg.intermediate_size = ff * config.num_shared_experts
            self.shared_experts = LlamaMLP(shared_cfg)
            # Qwen2-MoE: shared-expert output scaled by a learned sigmoid
            # gate (modeling_qwen2_moe shared_expert_gate)
            self.shared_expert_gate = (
                Linear(config.hidden_size, 1, bias_attr=False)
                if config.moe_shared_expert_gated else None)
        else:
            self.shared_experts = None
            self.shared_expert_gate = None

    @property
    def aux_loss(self):
        return self.moe.aux_loss

    def forward(self, x):
        out = self.moe(x)
        if self.shared_experts is not None:
            shared = self.shared_experts(x)
            if self.shared_expert_gate is not None:
                gate = self.shared_expert_gate(x)
                shared = run_op(
                    "shared_expert_gate",
                    lambda s, g: s * jax.nn.sigmoid(
                        g.astype(jnp.float32)).astype(s.dtype),
                    shared, gate)
            out = out + shared
        return out


class LlamaDecoderLayer(Layer):
    """Pre-norm decoder block; single-input forward so the stack is
    pipeline-homogeneous (drops into PipelineLayer unchanged)."""

    def __init__(self, config: LlamaConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        if config.num_experts > 0 and layer_idx >= config.first_k_dense_replace:
            self.mlp = LlamaMoEBlock(config)
        else:
            self.mlp = LlamaMLP(config)

    def cache_spec(self):
        """What a cached token holds in this layer (``EngineCore``
        allocates its pools by it): keys and values, ``num_key_value_heads``
        heads of ``head_dim`` each."""
        from ..ops.paged_attention import CacheSpec

        row = (self.config.num_key_value_heads, self.config.head_dim)
        return CacheSpec(k=row, v=row)

    def _sp(self, x):
        # Megatron-SP layout between blocks: seq sharded over mp (+sep for CP)
        if self.config.sequence_parallel:
            return sharding_constraint(x, "dp", ("sep", "mp"), None)
        return sharding_constraint(x, "dp", "sep", None)

    def forward(self, x, cache=None, pos=None):
        # ``attn`` / ``mlp`` (and ``embed`` / ``lm_head`` below) are the
        # names a device trace knows the parts of a step program by:
        # metadata on the operations, nothing computed differently
        x = self._sp(x)
        with jax.named_scope("attn"):   # projections, RoPE, cache write, kernel
            a = self.self_attn(self.input_layernorm(x), cache=cache, pos=pos)
        h = x + a
        with jax.named_scope("mlp"):
            m = self.mlp(self.post_attention_layernorm(h))
        return self._sp(h + m)


class LlamaModel(Layer):
    """Embedding + decoder stack + final norm (PaddleNLP ``LlamaModel``
    analog).  ``pp_microbatches`` routes the stack through the SPMD pipeline
    schedule when the mesh has a ``pp`` axis."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(0.0, config.initializer_range))
        # the configuration chooses the kind of decoder layer: one that
        # brings ``make_decoder_layer`` (models/moe_mla.py) builds its own
        make = getattr(config, "make_decoder_layer", None) or (
            lambda i: LlamaDecoderLayer(config, layer_idx=i))
        self.layers = LayerList(
            [make(i) for i in range(config.num_hidden_layers)])
        # and its own final norm where it has one (``make_final_norm``)
        make_norm = getattr(config, "make_final_norm", None)
        self.norm = make_norm() if make_norm else \
            RMSNorm(config.hidden_size, config.rms_norm_eps)
        self._pipe: Optional[PipelineLayer] = None
        self._scan_prep = None              # lazy (roles, per_layer, specs)

    def _pipeline(self) -> PipelineLayer:
        if self._pipe is None:
            self._pipe = PipelineLayer(
                list(self.layers), num_stages=axis_size("pp"),
                num_virtual_pipeline_stages=self.config.virtual_pp_degree)
        return self._pipe

    def forward(self, input_ids, pp_microbatches: Optional[int] = None,
                caches=None, pos=None):
        with jax.named_scope("embed"):
            h = self.embed_tokens(input_ids)
        # a residual path that is not one stream: the configuration brings
        # its entry and its exit (``models/hc_moe_mla.py``)
        enter = getattr(self.config, "enter_residual", None)
        if enter is not None:
            h = enter(h)
        if caches is not None:
            for layer, cache in zip(self.layers, caches):
                h = layer(h, cache=cache, pos=pos)
        elif pp_microbatches and axis_size("pp") > 1:
            h = pipeline_forward(self._pipeline(), h, pp_microbatches)
        elif (self.config.scan_layers and self.config.num_experts == 0
                and not self.config.attention_bias and self.config.use_rope
                and axis_size("sep") == 1):
            # biased attention (Qwen2-style) keeps the module loop: the
            # scan body's stacked-weight roles are the bias-free dense set
            h = self._scan_stack(h)
        else:
            for layer in self.layers:
                if self.config.recompute and self.training:
                    h = _recompute(layer, h)
                else:
                    h = layer(h)
        if enter is not None:
            h = self.config.exit_residual(h)
        return self.norm(h)

    def _scan_stack(self, h):
        """``lax.scan`` over the homogeneous decoder stack.

        Python-unrolled layers make XLA compile L copies of the same
        program — the dominant cold-compile cost.  Here the per-layer
        weights are stacked along a leading L axis and the layer body
        compiles ONCE; the whole stack is a single tape op whose backward
        is ``jax.vjp`` through the scan (reverse scan), with per-layer
        rematerialisation via ``jax.checkpoint`` when
        ``config.recompute`` — the standard TPU LLM structure
        (scan-of-layers + remat).  Mirrors LlamaDecoderLayer's math
        exactly (equivalence-tested); MoE / sep-sharded (ring) stacks and
        pipeline mode keep the module loop.
        """
        from ..ops.flash_attention import flash_attention_fwd
        from ..distributed.topology import get_mesh
        from ..parallel.utils import _fit_spec, in_manual_mode, param_spec

        cfg = self.config
        if getattr(self, "_scan_prep", None) is None:
            # one-time python prep (param collection + role check); the
            # in-graph jnp.stack stays per-step by design — stacking from
            # the individual tensors is what routes scan gradients back to
            # the per-layer parameters the optimizer/checkpoint see, at the
            # cost of one transient weight copy per step (~0.1 ms of HBM
            # traffic at bench scale)
            layers = list(self.layers)
            roles = [
                "input_layernorm.weight",
                "self_attn.q_proj.weight", "self_attn.k_proj.weight",
                "self_attn.v_proj.weight", "self_attn.o_proj.weight",
                "post_attention_layernorm.weight",
                "mlp.gate_proj.weight", "mlp.up_proj.weight",
                "mlp.down_proj.weight",
            ]
            per_layer = []
            for layer in layers:
                named = dict(layer.named_parameters())
                if set(named) != set(roles):  # heterogeneous: can't scan
                    raise ValueError(
                        f"scan_layers needs a homogeneous dense stack; "
                        f"layer params {sorted(named)} != {sorted(roles)}")
                per_layer.append([named[r] for r in roles])
            specs = [param_spec(per_layer[0][i]) for i in range(len(roles))]
            self._scan_prep = (roles, per_layer, specs)
        roles, per_layer, specs = self._scan_prep
        n_layers = len(per_layer)

        attn = self.layers[0].self_attn
        nh, nkv, hd = attn.num_heads, attn.num_kv_heads, cfg.head_dim
        cos_t, sin_t = attn._rope_cos, attn._rope_sin
        eps = cfg.rms_norm_eps
        sp_spec = (("dp", ("sep", "mp"), None) if cfg.sequence_parallel
                   else ("dp", "sep", None))
        remat = cfg.recompute and self.training

        from jax.sharding import NamedSharding

        def f(hv, *flat_params):
            mesh = get_mesh()
            manual = in_manual_mode()

            def pin(v, *spec):
                if mesh is None or manual:
                    return v
                sh = NamedSharding(mesh, _fit_spec(spec, jnp.shape(v), mesh))
                return jax.lax.with_sharding_constraint(v, sh)

            B, S = hv.shape[0], hv.shape[1]
            cos = jnp.asarray(cos_t[:S])
            sin = jnp.asarray(sin_t[:S])

            def rms(x, w):
                xf = x.astype(jnp.float32)
                var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w

            def body(carry, xs):
                w_in, wq, wk, wv, wo, w_post, wg, wu, wd = xs
                x = pin(carry, *sp_spec)
                h1 = rms(x, w_in)

                def proj_heads(w, n):
                    t = pin(h1 @ w, "dp", None, "mp")
                    t = t.reshape(B, S, n, hd)
                    return pin(t, "dp", "sep", "mp", None)

                q = _apply_rope(proj_heads(wq, nh), cos, sin)
                k = _apply_rope(proj_heads(wk, nkv), cos, sin)
                v = proj_heads(wv, nkv)
                out = flash_attention_fwd(q, k, v, causal=True)
                out = pin(out.reshape(B, S, nh * hd), "dp", "sep", "mp")
                out = pin(out, "dp", None, "mp")
                hmid = x + pin(out @ wo, "dp")
                h2 = rms(hmid, w_post)
                g = pin(h2 @ wg, "dp", None, "mp")
                u = pin(h2 @ wu, "dp", None, "mp")
                ff = pin(jax.nn.silu(g) * u, "dp", None, "mp")
                outl = hmid + pin(ff @ wd, "dp")
                return pin(outl, *sp_spec), None

            # stack role-major: flat_params[i*n_layers + j] = role i, layer j
            xs = tuple(
                pin(jnp.stack(flat_params[i * n_layers:(i + 1) * n_layers]),
                    None, *specs[i])
                for i in range(len(roles)))
            step = jax.checkpoint(body) if remat else body
            out, _ = jax.lax.scan(step, hv, xs)
            return out

        flat = [per_layer[j][i] for i in range(len(roles))
                for j in range(n_layers)]
        return run_op("llama_scan_stack", f, h, *flat)


class LlamaForCausalLM(Layer):
    """Llama with LM head (PaddleNLP ``LlamaForCausalLM`` analog)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        # a head that is not one [hidden, vocab] matrix: the configuration
        # brings it (``make_lm_head``, models/eva.py)
        make_head = getattr(config, "make_lm_head", None)
        if make_head is not None:
            self.lm_head = make_head()
        elif config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=True,
                weight_attr=Normal(0.0, config.initializer_range))

    def forward(self, input_ids, pp_microbatches: Optional[int] = None,
                caches=None, pos=None):
        h = self.llama(input_ids, pp_microbatches=pp_microbatches,
                       caches=caches, pos=pos)
        with jax.named_scope("lm_head"):
            if self.lm_head is None:
                w = self.llama.embed_tokens.weight
                logits = run_op("tied_head", lambda a, wv: a @ wv.T, h, w)
            else:
                logits = self.lm_head(h)
            scale = getattr(self.config, "logit_scale", 1.0)
            return logits if scale == 1.0 else logits * scale

    def cache_specs(self):
        """Per decoder layer, what a cached token holds there
        (``ops.paged_attention.CacheSpec``): the serving engine sizes its
        pools and its prefill buffers by this and by nothing else."""
        return [layer.cache_spec() for layer in self.llama.layers]

    def launch_telemetry(self, view):
        """``ops.paged_attention.LaunchTelemetry``: one object a class the
        layers name under ``telemetry`` (a dense layer names none), over
        the layers that name it, in the order they first do."""
        kinds = {}
        for layer in self.llama.layers:
            for kind in getattr(layer, "telemetry", ()):
                kinds.setdefault(kind, []).append(layer)
        return [kind(layers, view) for kind, layers in kinds.items()]

    def pop_expert_load(self):      # ``tests/bench_suite`` clears by name
        return pop_load(self.llama.layers)

    def pop_hc_health(self):
        return pop_health(self.llama.layers)

    def train_batch_1f1b(self, input_ids, labels, n_microbatch: int,
                         criterion=None, recompute: bool = False):
        """One true-1F1B pipelined train step (the ``train_batch`` analog of
        the reference's ``PipelineParallel.forward_backward_pipeline``,
        ``pipeline_parallel.py:440``): embedding runs on the tape, the
        decoder stack + final norm + LM head + criterion run inside the 1F1B
        SPMD schedule with per-microbatch loss on the last stage; MoE aux
        losses are accumulated and differentiated per stage.  Returns the
        mean loss; ``loss.backward()`` routes the schedule-computed grads
        onto every parameter.

        The head reuses the REAL layers (``llama.norm``, ``lm_head``/tied
        embedding, the criterion) via parameter rebinding, so pipelined and
        unpipelined runs share one implementation of the loss semantics."""
        from ..core.tensor import Tensor
        from ..parallel.pipeline_1f1b import pipeline_train_1f1b

        cfg = self.config
        if criterion is None:
            criterion = LlamaPretrainingCriterion(cfg)
        h = self.llama.embed_tokens(input_ids)
        pipe = self.llama._pipeline()
        norm = self.llama.norm
        lm_head = self.lm_head
        tied = lm_head is None
        head_params = [norm.weight,
                       self.llama.embed_tokens.weight if tied
                       else lm_head.weight]

        def head_apply(hv, act, tgt):
            nw, hw = hv
            saved_n = norm.weight._value
            norm.weight._value = nw
            saved_h = None if tied else lm_head.weight._value
            if not tied:
                lm_head.weight._value = hw
            try:
                hn = norm(Tensor(act, stop_gradient=True))
                if tied:
                    logits = hn._value @ hw.T
                else:
                    logits = lm_head(hn)._value
                loss = criterion(Tensor(logits, stop_gradient=True),
                                 Tensor(tgt, stop_gradient=True))
                return loss._value if isinstance(loss, Tensor) else loss
            finally:
                norm.weight._value = saved_n
                if not tied:
                    lm_head.weight._value = saved_h

        aux_w = cfg.aux_loss_weight if cfg.num_experts > 0 else 0.0
        return pipeline_train_1f1b(pipe, h, labels, head_params, head_apply,
                                   n_microbatch, aux_weight=aux_w,
                                   recompute=recompute)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None, seed: int = 0):
        """Autoregressive generation with a static KV cache: prefill compiles
        once, then every decode step reuses ONE compiled program (position is
        a traced input, cache buffers are threaded jit state — the serving
        analog of the reference's fused decode kernels).  Greedy when
        ``temperature == 0``."""
        import numpy as np

        from .. import no_grad
        from ..core.tensor import to_tensor
        from ..jit import to_static

        cfg = self.config
        B, T0 = input_ids.shape[0], input_ids.shape[1]
        M = T0 + max_new_tokens
        caches = [
            (Tensor(jnp.zeros((B, M, cfg.num_key_value_heads, cfg.head_dim),
                              self.llama.embed_tokens.weight.dtype)),
             Tensor(jnp.zeros((B, M, cfg.num_key_value_heads, cfg.head_dim),
                              self.llama.embed_tokens.weight.dtype)))
            for _ in cfg.num_hidden_layers * [0]
        ]

        was_training = self.training
        self.eval()

        @to_static
        def prefill(ids, pos):
            with no_grad():
                logits = self(ids, caches=caches, pos=pos)
            return logits[:, -1]

        @to_static
        def decode(tok, pos):
            with no_grad():
                logits = self(tok, caches=caches, pos=pos)
            return logits[:, -1]

        rng = np.random.default_rng(seed)

        def sample(logits_np):
            if temperature == 0.0:
                return logits_np.argmax(-1)
            logits_np = logits_np / max(temperature, 1e-6)
            if top_k > 0:
                kth = np.sort(logits_np, -1)[:, -top_k][:, None]
                logits_np = np.where(logits_np < kth, -1e30, logits_np)
            probs = np.exp(logits_np - logits_np.max(-1, keepdims=True))
            probs /= probs.sum(-1, keepdims=True)
            if top_p < 1.0:
                order = np.argsort(-probs, -1)
                sorted_p = np.take_along_axis(probs, order, -1)
                keep = np.cumsum(sorted_p, -1) - sorted_p < top_p
                mask = np.zeros_like(probs, bool)
                np.put_along_axis(mask, order, keep, -1)
                probs = np.where(mask, probs, 0.0)
                probs /= probs.sum(-1, keepdims=True)
            return np.array([rng.choice(probs.shape[-1], p=p) for p in probs])

        out = [np.asarray(input_ids.numpy(), dtype=np.int64)]
        logits = prefill(input_ids, to_tensor(0, dtype="int32"))
        tok = sample(np.asarray(logits.numpy(), np.float32))
        finished = np.zeros((B,), bool)
        for step in range(max_new_tokens):
            if eos_token_id is not None:
                finished |= tok == eos_token_id
            out.append(tok[:, None])
            if eos_token_id is not None and finished.all():
                break
            if step == max_new_tokens - 1:
                break
            logits = decode(to_tensor(tok[:, None].astype("int64")),
                            to_tensor(T0 + step, dtype="int32"))
            tok = sample(np.asarray(logits.numpy(), np.float32))

        if was_training:
            self.train()
        return to_tensor(np.concatenate(out, axis=1))

    @property
    def aux_loss(self):
        """Sum of MoE load-balance losses from the last forward (add
        ``config.aux_loss_weight * model.aux_loss`` to the training loss)."""
        total = None
        for layer in self.llama.layers:
            al = getattr(layer.mlp, "aux_loss", None)
            if al is not None:
                total = al if total is None else total + al
        return total


class LlamaPretrainingCriterion(Layer):
    """Shifted next-token cross-entropy (PaddleNLP
    ``LlamaPretrainingCriterion`` analog); ignore_index=-100 masks padding."""

    def __init__(self, config: Optional[LlamaConfig] = None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        shifted = logits[:, :-1, :]
        target = labels[:, 1:]
        return F.cross_entropy(shifted, target, reduction="mean",
                               ignore_index=self.ignore_index)
