"""A decoder-layer kind: latent attention and routed experts on a residual
path of SEVERAL STREAMS mixed by manifold-constrained hyper-connections —
the ``xing4_0`` block (DeepSeek's mHC, arXiv 2512.24880, around the
``deepseek_v2`` / ``glm4_moe_lite`` sublayers).

It lives under the :class:`~paddle_tpu.models.llama.LlamaModel` /
``LlamaForCausalLM`` skeleton like the other kinds: a
:class:`HCMoEMLAConfig` makes the stack build
:class:`HCMLAMoEDecoderLayer` (``make_decoder_layer``) and brings the two
hooks the skeleton calls where the residual path is not one stream:
``enter_residual`` after the embedding (``n`` copies) and
``exit_residual`` before the final norm (their sum).  The sublayers are
:class:`~paddle_tpu.models.moe_mla.LatentAttention`,
:class:`~paddle_tpu.models.moe_mla.RoutedExperts` and ``LlamaMLP`` as they
are; no flag of ``MLAMoEDecoderLayer`` is involved.

The value handed from layer to layer is ``[batch, tokens, n * hidden]``:
``vec(X)``, stream-major (``ops/hyper_connections.py`` has the equations
and why it is flat).  Every sublayer reads a learned, per-token mix of the
``n`` streams and writes back to all of them through a doubly-stochastic
``n x n`` matrix that ``hc_sinkhorn_iters`` Sinkhorn rounds make, so the
path moves ``(3 n + 2) * hidden`` values a token a sublayer where a plain
residual moves ``4 * hidden``.  Streams are held in the model's type
between sublayers; coefficients and the mixes accumulate in float32.

``rope_scaling`` (YaRN) is read by ``models/moe_mla.py``
(``latent_rope_tables``, ``softmax_scale``): blended frequencies in the one
place the latent layer's tables are made, and ``m(s, mscale_all_dim)^2`` on
the softmax scale of the expanded and the absorbed path alike.

Device scopes: an outer ``mhc`` that is NOT inside ``attn`` or ``mlp`` (as
``ssm`` is not), with ``mhc_coeffs``, ``mhc_sinkhorn``, ``mhc_pre``,
``mhc_post`` inside it.  After a forward every :class:`HyperConnection`
holds the health of its Sinkhorn step (``health``);
``ops.hyper_connections.Health``, which the layer names under
``telemetry``, sums them and carries them on ``engine.fetch`` and
``/metrics``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import run_op
from ..nn.initializer import Assign, Constant, Normal
from ..nn.layers import Layer
from ..nn.norm import RMSNorm
from ..ops import hyper_connections as _hc
from ..ops.paged_attention import CacheSpec
from .llama import LlamaMLP
from ..parallel.moe import ExpertLoad
from .moe_mla import LatentAttention, MoEMLAConfig, RoutedExperts


@dataclass
class HCMoEMLAConfig(MoEMLAConfig):
    """``MoEMLAConfig`` plus the published keys of the multi-stream
    residual path (``hc_*``, ``mhc_*``) and ``rope_scaling``.  Defaults are
    Xing4.0-29B-A4B's widths."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.0
    first_k_dense_replace: int = 2
    hc_mult: int = 4                        # streams
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rope_scaling: Optional[dict] = field(default_factory=lambda: {
        "type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096})

    def make_decoder_layer(self, layer_idx: int) -> Layer:
        return HCMLAMoEDecoderLayer(self, layer_idx)

    def enter_residual(self, h):
        """``[.., hidden]`` after the embedding -> the streams."""
        n = self.hc_mult
        with jax.named_scope("mhc"):
            return run_op("mhc_expand", lambda a: _hc.expand(a, n), h)

    def exit_residual(self, x):
        """The streams after the last layer -> ``[.., hidden]``."""
        n = self.hc_mult
        with jax.named_scope("mhc"):
            return run_op("mhc_collapse", lambda a: _hc.collapse(a, n), x)

    @classmethod
    def tiny(cls, **kw):
        """Test config: every mechanism at toy widths, a YaRN factor whose
        ramp lies inside the four frequencies."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=256,
            rope_theta=10000.0, rms_norm_eps=1e-6, q_lora_rank=32,
            kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=20, n_routed_experts=8, n_shared_experts=1,
            num_experts_per_tok=2, moe_intermediate_size=48,
            routed_scaling_factor=2.0, first_k_dense_replace=1,
            hc_mult=4, hc_sinkhorn_iters=20,
            rope_scaling={"type": "yarn", "factor": 8.0, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 32})
        defaults.update(kw)
        return cls(**defaults)


class HyperConnection(Layer):
    """The hyper-connection around ONE sublayer: ``phi``, the offsets
    (``b_pre | b_post | vec(B_res)``) and the three gains, and the two
    halves of the mix.  Offsets and gains are float32 whatever the model's
    type.  As built: ``H_post = 1``, ``H_res`` near the identity
    (``B_res = 4 I``), small gains — a path that starts as one residual
    stream read at a quarter each."""

    def __init__(self, config: HCMoEMLAConfig):
        super().__init__()
        self.config = config
        n, c = config.hc_mult, config.hidden_size
        self.phi = self.create_parameter(
            [n * c, 2 * n + n * n],
            attr=Normal(0.0, config.initializer_range))
        b_res = 4.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)
        self.offsets = self.create_parameter(
            [2 * n + n * n], dtype="float32", default_initializer=Assign(
                jnp.concatenate([jnp.zeros(2 * n, jnp.float32), b_res])))
        self.gains = self.create_parameter(
            [3], dtype="float32", default_initializer=Constant(0.01))
        self.health = None

    def read(self, x):
        """``(u, coefficients)``: what the sublayer reads, and what
        :meth:`write` needs.  The coefficients stay raw arrays: they are
        this launch's alone."""
        c = self.config

        def f(xv, phi, offsets, gains):
            co = _hc.coefficients(
                xv, phi, offsets, gains, c.hc_mult, c.hc_sinkhorn_iters,
                c.rms_norm_eps, c.hc_eps,
                (c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max))
            entries = float(co.h_res.size)
            return (_hc.mix_in(xv, co.h_pre), co.h_post, co.h_res,
                    jnp.stack([co.clamped, jnp.float32(entries),
                               co.residual]))

        with jax.named_scope("mhc"):
            u, h_post, h_res, health = run_op(
                "mhc_read", f, x, self.phi, self.offsets, self.gains)
        self.health = health._value
        return u, (h_post, h_res)

    def write(self, x, coeffs, y):
        h_post, h_res = coeffs
        with jax.named_scope("mhc"):
            return run_op("mhc_write",
                          lambda xv, hr, hp, yv: _hc.mix_out(xv, hr, hp, yv),
                          x, h_res, h_post, y)


class HCMLAMoEDecoderLayer(Layer):
    """Latent attention, then the dense SwiGLU (the first
    ``first_k_dense_replace`` layers) or the routed experts, each behind
    its own :class:`HyperConnection` and its own pre-norm."""

    telemetry = (ExpertLoad, _hc.Health)    # the order they ride a launch in

    def __init__(self, config: HCMoEMLAConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        self.attn_hc = HyperConnection(config)
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LatentAttention(config)
        self.mlp_hc = HyperConnection(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)
        if layer_idx >= config.first_k_dense_replace:
            self.mlp = RoutedExperts(config)
        else:
            self.mlp = LlamaMLP(config)

    def cache_spec(self) -> CacheSpec:
        return CacheSpec(k=(1, self.config.latent_dim), v=None, kind="latent")

    def forward(self, x, cache=None, pos=None):
        u, co = self.attn_hc.read(x)
        with jax.named_scope("attn"):
            a = self.self_attn(self.input_layernorm(u), cache=cache, pos=pos)
        x = self.attn_hc.write(x, co, a)
        u, co = self.mlp_hc.read(x)
        with jax.named_scope("mlp"):
            m = self.mlp(self.post_attention_layernorm(u))
        return self.mlp_hc.write(x, co, m)
