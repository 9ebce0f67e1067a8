"""A decoder-layer kind: latent attention (MLA) plus routed experts that
drop no token — the DeepSeek-V2 / GLM ``glm4_moe_lite`` block.

It lives under the :class:`~paddle_tpu.models.llama.LlamaModel` /
``LlamaForCausalLM`` skeleton (embedding, stack, final norm, head and the
``embed`` / ``lm_head`` scopes are that file's): a :class:`MoEMLAConfig`
makes the stack build :class:`MLAMoEDecoderLayer` instead of
``LlamaDecoderLayer``.  No flag of ``LlamaAttention`` is involved.

**Latent attention.**  For a token ``x`` at position ``p``::

    c_q        = RMSNorm(W_DQ x)                       [q_lora_rank]
    q_h        = W_UQ c_q  ->  q_nope_h | q_rope_h      [heads, nope + rope]
    c_kv | k_r = W_DKV x                               [kv_lora_rank + rope]
    c_kv       = RMSNorm(c_kv);  k_r = RoPE(k_r, p);  q_rope_h = RoPE(q_rope_h, p)
    k_nope_h | v_h = W_UKV c_kv                        [heads, nope + v]
    score_hs   = (q_nope_h . k_nope_hs + q_rope_h . k_r,s) / sqrt(nope + rope)
    o_h        = sum_s softmax(score)_hs v_hs;   out = W_O concat_h o_h

The cache holds ``(c_kv, k_r)``: ONE row of ``kv_lora_rank + rope`` values
a token a layer, shared by all heads, and no separate V — the layer says
so in :meth:`MLAMoEDecoderLayer.cache_spec` and the engine allocates by
that (the row in whole lane tiles, ``ops.paged_attention
.latent_pool_shape``, so that a page lies contiguous).  Two paths compute the same mathematics: *expanded* (prefill, chunks,
the cache-less forward: keys and values of every cached token are rebuilt
from its latent row) and *absorbed* (decode through the pages: ``W_UK`` is
folded into the query and ``W_UV`` applied after the weighted sum of latent
rows, so a step reads 576 values a token and never builds a key; on a TPU
its core is the page walk ``ops.pallas_paged.latent_decode_attention``).

**Routed experts** are :func:`paddle_tpu.parallel.moe.dropless_experts`
behind :func:`~paddle_tpu.parallel.moe.sigmoid_topk_route`: sigmoid scores
in float32, a selection bias that selects and does not weigh, weights
renormalised over the chosen and scaled, every routed token computed.  A
row's output does not depend on who shares its batch.

Two keys more, each off by default so that a model without it traces to
the same program: ``gated_attention`` (an
OUTPUT GATE, ``o <- o * sigmoid(W_g x)`` elementwise on the heads' values,
after ``W_UV`` and before ``W_O``, on the expanded and the absorbed path
alike) and ``LlamaConfig.swiglu_limit`` (the clamp of the experts'
SwiGLU, ``parallel.moe.clamped_swiglu``).

Device scopes, nested in the ``attn`` / ``mlp`` scopes of the skeleton:
``mla_q``, ``mla_kv_down``, ``mla_decode_core``, ``mla_prefill_core``,
``mla_gate`` (a gated layer only), ``mla_out``; ``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``, ``moe_shared``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.dispatch import run_op
from ..nn.common import Linear
from ..nn.initializer import Constant, Normal
from ..nn.layers import Layer
from ..nn.norm import RMSNorm
from ..ops.paged_attention import (
    CacheSpec,
    PagedCache,
    latent_expanded_attention,
    latent_paged_decode_attention,
    latent_paged_prefill_attention,
    pool_rows,
)
from ..parallel.moe import ExpertLoad, dropless_experts, sigmoid_topk_route
from .llama import LlamaConfig, LlamaMLP, _apply_rope, _rope_tables


@dataclass
class MoEMLAConfig(LlamaConfig):
    """``LlamaConfig`` plus the published keys of the latent-attention /
    routed-expert family (``glm4_moe_lite``, ``deepseek_v2``).  Defaults
    are GLM-4.7-Flash's widths."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240          # the leading dense layers
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    num_key_value_heads: int = 20           # published; the cache has none
    max_position_embeddings: int = 8192
    rope_theta: float = 1e6
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 1
    # the routed experts THIS process holds (ids into n_routed_experts),
    # None = all: the router always scores all of them, the layer computes
    # what its own give (a chip's share of an expert-parallel deployment)
    experts_held: Optional[Tuple[int, ...]] = None
    gated_attention: bool = False           # an output gate on the heads' values

    def __post_init__(self):
        self.num_experts = self.n_routed_experts

    @property
    def head_dim(self) -> int:
        """The query/key head size (what the softmax scale is taken of)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values one cached token holds a layer: ``c_kv`` and ``k_r``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def make_decoder_layer(self, layer_idx: int) -> Layer:
        return MLAMoEDecoderLayer(self, layer_idx)

    @classmethod
    def tiny(cls, **kw):
        """Test config: every mechanism at toy widths."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=256,
            rope_theta=10000.0, q_lora_rank=32, kv_lora_rank=24,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=20,
            n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
            moe_intermediate_size=48, routed_scaling_factor=1.8,
            first_k_dense_replace=1)
        defaults.update(kw)
        return cls(**defaults)


def yarn_range(dim: int, theta: float, original_max: int,
               beta_fast: float, beta_slow: float) -> Tuple[int, int]:
    """``(low, high)``: the frequency indices between which YaRN blends,
    from the rotations a dimension makes over the ORIGINAL positions."""
    def at(rotations):
        return dim * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(at(beta_fast)), 0),
            min(math.ceil(at(beta_slow)), dim - 1))


def yarn_mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def latent_rope_tables(config):
    """THE place the latent layer's RoPE tables are made.  Without
    ``rope_scaling`` they are ``_rope_tables``' own; with YaRN frequency
    ``i`` is ``(1 - g_i) f_i + g_i f_i / factor``, ``g`` a ramp from 0 at
    ``low`` to 1 at ``high`` (:func:`yarn_range`), and cos and sin are
    scaled by ``m(factor, mscale) / m(factor, mscale_all_dim)``."""
    import numpy as np

    c = config
    d, rs = c.qk_rope_head_dim, getattr(c, "rope_scaling", None)
    if rs is None:
        return _rope_tables(d, c.max_position_embeddings, c.rope_theta)
    kind = rs.get("type", rs.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling type {kind!r} is not built (yarn is)")
    factor = float(rs["factor"])
    low, high = yarn_range(d, c.rope_theta,
                           int(rs["original_max_position_embeddings"]),
                           float(rs.get("beta_fast", 32)),
                           float(rs.get("beta_slow", 1)))
    inv = 1.0 / (c.rope_theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    inv = (inv * (1.0 - ramp) + inv / factor * ramp).astype(np.float32)
    freqs = np.outer(np.arange(c.max_position_embeddings, dtype=np.float32),
                     inv)
    m = np.float32(yarn_mscale(factor, float(rs.get("mscale", 1)))
                   / yarn_mscale(factor, float(rs.get("mscale_all_dim", 0))))
    return np.cos(freqs) * m, np.sin(freqs) * m


def softmax_scale(config) -> float:
    """``1 / sqrt(nope + rope)``, times ``m(factor, mscale_all_dim)^2``
    under YaRN: on the expanded and the absorbed path alike."""
    scale = 1.0 / math.sqrt(config.head_dim)
    rs = getattr(config, "rope_scaling", None)
    if rs is not None and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(float(rs["factor"]),
                             float(rs["mscale_all_dim"])) ** 2
    return scale


class LatentAttention(Layer):
    """Multi-head latent attention with decoupled RoPE (module docstring)."""

    def __init__(self, config: MoEMLAConfig):
        super().__init__()
        self.config = config
        c = config
        h, heads = c.hidden_size, c.num_attention_heads
        init = Normal(0.0, c.initializer_range)

        def lin(i, o):
            return Linear(i, o, weight_attr=init, bias_attr=False)

        self.q_a_proj = lin(h, c.q_lora_rank)                       # W_DQ
        self.q_a_layernorm = RMSNorm(c.q_lora_rank, c.rms_norm_eps)
        self.q_b_proj = lin(c.q_lora_rank, heads * c.head_dim)      # W_UQ
        self.kv_a_proj_with_mqa = lin(h, c.latent_dim)              # W_DKV
        self.kv_a_layernorm = RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = lin(c.kv_lora_rank,                        # W_UKV
                             heads * (c.qk_nope_head_dim + c.v_head_dim))
        self.o_proj = lin(heads * c.v_head_dim, h)
        self.g_proj = lin(h, heads * c.v_head_dim) \
            if c.gated_attention else None
        self._rope_cos, self._rope_sin = latent_rope_tables(c)
        self._scale = softmax_scale(c)

    # --- pieces --------------------------------------------------------------
    def _positions(self, pos, B, S):
        """Absolute position of every token, ``[B, S]`` or ``[S]``."""
        if pos is None:
            return jnp.arange(S)
        p = pos._value if hasattr(pos, "_value") else pos
        if jnp.ndim(p) == 2:
            return p
        return (p[:, None] if jnp.ndim(p) == 1 else p) + jnp.arange(S)

    def _rope(self, x, idx):
        cos = jnp.asarray(self._rope_cos)[idx]
        sin = jnp.asarray(self._rope_sin)[idx]
        return _apply_rope(x, cos, sin)

    def _queries(self, x, idx):
        """``q_nope [B,S,heads,nope]``, ``q_rope [B,S,heads,rope]`` (rotated)."""
        c = self.config
        B, S = x.shape[0], x.shape[1]
        with jax.named_scope("mla_q"):
            q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))

            def split(qv):
                qv = qv.reshape(B, S, c.num_attention_heads, c.head_dim)
                return jnp.concatenate(
                    [qv[..., :c.qk_nope_head_dim],
                     self._rope(qv[..., c.qk_nope_head_dim:], idx)], -1)

            return run_op("mla_q_rope", split, q)

    def _latents(self, x, idx):
        """The token's cache row ``[B, S, 1, kv_lora_rank + rope]``:
        normalised ``c_kv`` beside the rotated shared key ``k_r``."""
        c = self.config
        with jax.named_scope("mla_kv_down"):
            kv = self.kv_a_proj_with_mqa(x)
            r = c.kv_lora_rank
            c_kv = self.kv_a_layernorm(
                run_op("mla_latent_split", lambda a: a[..., :r], kv))

            def join(cv, raw):
                k_r = self._rope(raw[..., None, r:], idx)       # one head
                return jnp.concatenate([cv[..., None, :], k_r], -1)

            return run_op("mla_latent_row", join, c_kv, kv)

    def _w_ukv(self, w):
        """``W_UKV`` as ``(W_UK [heads, r, nope], W_UV [heads, r, v])``."""
        c = self.config
        w = w.reshape(c.kv_lora_rank, c.num_attention_heads,
                      c.qk_nope_head_dim + c.v_head_dim)
        w = jnp.transpose(w, (1, 0, 2))
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def _out(self, o, x):
        if self.g_proj is not None:
            with jax.named_scope("mla_gate"):
                o = run_op("mla_gate", lambda ov, gv: ov * jax.nn.sigmoid(
                    gv.astype(jnp.float32)).astype(ov.dtype),
                    o, self.g_proj(x))
        with jax.named_scope("mla_out"):
            return self.o_proj(o)

    # --- forward -------------------------------------------------------------
    def forward(self, x, cache=None, pos=None):
        B, S = x.shape[0], x.shape[1]
        idx = self._positions(pos, B, S)
        q = self._queries(x, idx)
        lat = self._latents(x, idx)
        if isinstance(cache, PagedCache):
            return self._paged(x, q, lat, cache, B, S)
        if cache is not None:           # dense buffer (one-shot prefill)
            buf = cache[0]
            start = pos._value if hasattr(pos, "_value") else pos

            def upd(b, new, p):
                zero = jnp.zeros((), jnp.asarray(p).dtype)
                return jax.lax.dynamic_update_slice(
                    b, new.astype(b.dtype), (zero, p, zero, zero))

            buf._rebind(run_op("kv_write", upd, buf, lat, start))
            lat, q_start = buf, start
        else:
            q_start = 0
        return self._out(self._expanded(q, lat, q_start), x)

    def _expanded(self, q, lat, q_start, lens=None):
        """Attention with keys and values rebuilt from the latent rows
        ``lat [B, M, 1, latent]``: the queries sit at ``q_start + [0, S)``
        and see columns up to their own (and under ``lens``)."""
        c = self.config
        scale = self._scale

        def attend(qv, lv, w):
            with jax.named_scope("mla_prefill_core"):
                return latent_expanded_attention(
                    qv, lv[:, :, 0], self._w_ukv(w.astype(qv.dtype)),
                    c.kv_lora_rank, scale, q_start, lens)

        return run_op("mla_expanded_attention", attend, q, lat,
                      self.kv_b_proj.weight)

    def _paged(self, x, q, lat, cache, B, S):
        c = self.config
        if cache.seg_ids is not None:
            raise NotImplementedError(
                "the unified ragged program has no latent-cache path "
                "(EngineCore refuses unified_step for such a model)")
        pool = cache.k_pool
        blocks, offs = cache.slot_blocks, cache.slot_offsets
        chunk = blocks.ndim == 2

        def write(p, new):
            new = new if chunk else new[:, 0]
            return p.at[blocks, offs].set(pool_rows(new, p))

        pool._rebind(run_op("paged_kv_write", write, pool, lat))
        scale = self._scale
        if chunk:
            def attend(qv, pv, w):
                with jax.named_scope("mla_prefill_core"):
                    return latent_paged_prefill_attention(
                        qv, pv, self._w_ukv(w.astype(qv.dtype)),
                        cache.block_tables, cache.seq_lens, cache.q_start,
                        c.kv_lora_rank, scale)
        else:
            assert S == 1, "paged decode is one token a row a step"

            def attend(qv, pv, w):
                with jax.named_scope("mla_decode_core"):
                    return latent_paged_decode_attention(
                        qv[:, 0], pv, self._w_ukv(w.astype(qv.dtype)),
                        cache.block_tables, cache.seq_lens,
                        c.kv_lora_rank, scale,
                        use_pallas=cache.use_pallas)[:, None]

        o = run_op("mla_paged_attention", attend, q, pool,
                   self.kv_b_proj.weight)
        return self._out(o, x)


class RoutedExperts(Layer):
    """``y = sum_{i in top-k} w_i E_i(x) + E_shared(x)``: sigmoid router
    with a selection bias, experts stacked ``[E_held, ...]``, no capacity
    and no dropped token.  After a forward ``load`` holds the tokens each
    of the ``n_routed_experts`` received (int32, padding rows included)."""

    def __init__(self, config: MoEMLAConfig):
        super().__init__()
        self.config = config
        c = config
        h, f = c.hidden_size, c.moe_intermediate_size
        init = Normal(0.0, c.initializer_range)
        self.held = tuple(range(c.n_routed_experts)) \
            if c.experts_held is None else tuple(c.experts_held)
        n = len(self.held)
        self.gate = Linear(h, c.n_routed_experts, weight_attr=init,
                           bias_attr=False)
        # noaux_tc's ``e_score_correction_bias``: added to the scores to
        # SELECT, never to weigh.  float32 whatever the model's type
        self.e_score_correction_bias = self.create_parameter(
            [c.n_routed_experts], dtype="float32",
            default_initializer=Constant(0.0))
        self.w_gate_up = self.create_parameter([n, h, 2 * f], attr=init)
        self.w_down = self.create_parameter([n, f, h], attr=init)
        if c.n_shared_experts > 0:
            shared_cfg = LlamaConfig(**{k: getattr(c, k) for k in
                                        LlamaConfig.__dataclass_fields__})
            shared_cfg.intermediate_size = f * c.n_shared_experts
            self.shared_experts = LlamaMLP(shared_cfg)
        else:
            self.shared_experts = None
        self.load = None

    def forward(self, x):
        c = self.config
        B, S, H = x.shape

        def routed(xv, wg, bias, w_gu, w_d):
            flat = xv.reshape(B * S, H)
            with jax.named_scope("moe_router"):
                ids, weights = sigmoid_topk_route(
                    flat, wg, bias, c.num_experts_per_tok,
                    scale=c.routed_scaling_factor,
                    normalize=c.norm_topk_prob)
            out, load = dropless_experts(
                flat, ids, weights, w_gu.astype(xv.dtype),
                w_d.astype(xv.dtype), c.n_routed_experts, self.held,
                limit=c.swiglu_limit)
            return out.reshape(B, S, H), load

        out, load = run_op("moe_routed_experts", routed, x, self.gate.weight,
                           self.e_score_correction_bias, self.w_gate_up,
                           self.w_down)
        self.load = load._value
        if self.shared_experts is not None:
            with jax.named_scope("moe_shared"):
                out = out + self.shared_experts(x)
        return out


class MLAMoEDecoderLayer(Layer):
    """Pre-norm block: latent attention, then the dense SwiGLU (the first
    ``first_k_dense_replace`` layers) or the routed experts."""

    telemetry = (ExpertLoad,)

    def __init__(self, config: MoEMLAConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LatentAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        if layer_idx >= config.first_k_dense_replace:
            self.mlp = RoutedExperts(config)
        else:
            self.mlp = LlamaMLP(config)

    def cache_spec(self) -> CacheSpec:
        """What a cached token holds in this layer: one latent row in
        ``k_pools``, nothing in ``v_pools``."""
        return CacheSpec(k=(1, self.config.latent_dim), v=None, kind="latent")

    def forward(self, x, cache=None, pos=None):
        with jax.named_scope("attn"):
            a = self.self_attn(self.input_layernorm(x), cache=cache, pos=pos)
        h = x + a
        with jax.named_scope("mlp"):
            m = self.mlp(self.post_attention_layernorm(h))
        return h + m
