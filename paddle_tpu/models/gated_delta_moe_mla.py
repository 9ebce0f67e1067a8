"""A decoder-layer kind: gated delta-rule mixers among gated latent
attention, dense SwiGLU or routed experts behind either, sandwich norms --
the ``gigachat3_5`` hybrid block.

It lives under the :class:`~paddle_tpu.models.llama.LlamaModel` /
``LlamaForCausalLM`` skeleton like the other kinds: a
:class:`GatedDeltaMoEMLAConfig` makes the stack build
:class:`GatedDeltaDecoderLayer` (``make_decoder_layer``) and brings the
final norm (``make_final_norm``).  Layer ``i`` has, by two published keys:

* its MIXER: :class:`~paddle_tpu.models.moe_mla.LatentAttention` (with its
  output gate, ``gated_attention``) where ``i in full_attention_layers``,
  else :class:`GatedDeltaMixer`;
* its FEED-FORWARD: ``LlamaMLP`` where ``i < first_k_dense_replace``, else
  :class:`~paddle_tpu.models.moe_mla.RoutedExperts`; the SwiGLU of both,
  and of the shared expert, is clamped at ``swiglu_limit``.

**Block** (``layernorm_type`` ``pre_post``): ``x <- x + n2(Mixer(n1(x)))``,
``x <- x + n4(FFN(n3(x)))``; ``n(x) = x / sqrt(mean(x^2) + eps) * g
sigmoid(w)`` with ``g`` = ``layernorm_gating_weight`` and ``w`` zero as
built, so the scale starts at ``g / 2`` (:class:`ZeroCenteredGatedNorm`).

**The delta-rule mixer**, for token ``t`` with input ``u_t`` (``H_k`` key
heads, ``H_v`` value heads, ``d`` the head size, ``K`` the convolution)::

    q | k | v | z = W_in u_t          [H_k d + H_k d + H_v d + H_v d]
    b | a         = W_ba u_t          [H_v + H_v]
    q | k | v     = silu(causal depthwise conv_K(q | k | v))     no bias
    per value head h (q, k of key head h // (H_v / H_k)), float32:
    q = q / ||q|| / sqrt(d);  k = k / ||k||
    beta = sigmoid(b_h);  alpha = exp(-exp(A_log_h) softplus(a_h + dt_bias_h))
    S_t = alpha S_{t-1} + beta k (v - alpha S_{t-1}^T k)^T;  o = S_t^T q
    y = o / sqrt(mean(o^2) + eps_o) * (1 + w_o) * g_o sigmoid(z_h)
    out = W_out concat_h y

(``ops/gated_delta.py`` has the rule and its three forms).  From the
convolution on, everything is float32.  What a sequence holds after token
``t`` is ``S_t`` of every value head and the ``K - 1`` inputs of the
convolution ending at ``t``: the layer DECLARES that
(:meth:`GatedDeltaDecoderLayer.cache_spec`) and the engine allocates slot
pools from it and hands them in as a :class:`~paddle_tpu.ops
.selective_scan.StateCache`, as it does for ``models/mamba_hybrid.py``.
Three paths: the chunked rule over a (padded) bucket, whose padding is
inert and which starts from zero or from the slot; one step a row for
decode; and the cache-less forward over a whole sequence.  The decode step
runs IN PLACE on the state pool where ``selective_scan.route_state_step``
says the kernel can take it (``ops/pallas_gated_delta.py``: a TPU backend,
``d_k % 8 == 0`` and ``d_v % 128 == 0``, read off the pool's shape): each
row's slot is copied into VMEM, stepped and copied back, and nothing the
size of the rows' states is gathered or scattered.  Elsewhere (a CPU run,
an untileable width, the kill switch) it is gather by slot, step, scatter
on the donated pools in XLA, which stays the oracle.  The convolution's
window is gathered and scattered in XLA on both.

Device scopes, under an outer ``gdn`` that is NOT inside ``attn`` (as
``ssm`` is not): ``gdn_in_proj``, ``gdn_conv``, ``gdn_gates`` (the L2
norms, beta, alpha), ``gdn_chunk`` (prefill and carried chunk: the solve,
the products inside a chunk, the state's carry), ``gdn_step`` (decode:
EVERY operation that reads or writes either slot pool, the kernel
``gdn_state_step`` among them), ``gdn_out`` (the
gated norm and the out-projection).  The latent layer's and the experts'
scopes are ``moe_mla.py``'s.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.dispatch import run_op
from ..nn.common import Linear
from ..nn.initializer import Constant, Normal
from ..nn.layers import Layer
from ..ops.gated_delta import (
    CHUNK,
    gated_delta_chunked,
    gated_delta_step,
    gates,
    l2_normalize,
)
from ..ops.paged_attention import CacheSpec
from ..ops.selective_scan import (
    StateCache,
    StateSlots,
    causal_conv,
    conv_window,
    route_state_step,
)
from ..parallel.moe import ExpertLoad
from .llama import LlamaMLP
from .mamba_hybrid import _carried_state
from .moe_mla import LatentAttention, MoEMLAConfig, RoutedExperts


@dataclass
class GatedDeltaMoEMLAConfig(MoEMLAConfig):
    """``MoEMLAConfig`` plus the published keys of the ``gigachat3_5``
    hybrid.  Defaults are GigaChat3.5-432B-A28B's widths."""

    vocab_size: int = 128256
    hidden_size: int = 7168
    intermediate_size: int = 18432
    num_hidden_layers: int = 40
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 100000.0
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 3
    rope_scaling: Optional[dict] = field(default_factory=lambda: {
        "type": "yarn", "factor": 8, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 32768})
    full_attention_layers: Tuple[int, ...] = tuple(range(3, 40, 4))
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_num_key_heads: int = 32
    linear_num_value_heads: int = 64
    linear_sigmoid_gate_scale: float = 2.0
    linear_attn_o_norm_eps: float = 1e-6
    layernorm_gating_weight: float = 2.0
    gated_attention: bool = True
    swiglu_limit: Optional[float] = 10.0

    @property
    def delta_conv_dim(self) -> int:
        """Channels the short convolution runs over: ``q | k | v``."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def make_norm(self) -> Layer:
        return ZeroCenteredGatedNorm(self.hidden_size, self.rms_norm_eps,
                                     self.layernorm_gating_weight)

    make_final_norm = make_norm

    def make_decoder_layer(self, layer_idx: int) -> Layer:
        return GatedDeltaDecoderLayer(self, layer_idx)

    @classmethod
    def tiny(cls, **kw):
        """Test config: five layers in the served pattern (a dense leading
        layer, the latent layer fourth), every mechanism at toy widths."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=256,
            rope_theta=10000.0, q_lora_rank=32, kv_lora_rank=24,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
            moe_intermediate_size=48, routed_scaling_factor=2.5,
            first_k_dense_replace=1, full_attention_layers=(3,),
            linear_key_head_dim=16, linear_value_head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4,
            rope_scaling={"type": "yarn", "factor": 8.0, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 32})
        defaults.update(kw)
        return cls(**defaults)


class ZeroCenteredGatedNorm(Layer):
    """``x / sqrt(mean(x^2) + eps) * g sigmoid(w)``: an RMS norm whose
    scale is a gate of its own parameter, ``g / 2`` at ``w = 0``."""

    def __init__(self, hidden_size: int, epsilon: float, gating_weight: float):
        super().__init__()
        self.epsilon, self.gating_weight = epsilon, gating_weight
        self.weight = self.create_parameter(
            [hidden_size], default_initializer=Constant(0.0))

    def forward(self, x):
        eps, g = self.epsilon, self.gating_weight

        def norm(xv, w):
            xf = xv.astype(jnp.float32)
            var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
            scale = g * jax.nn.sigmoid(w.astype(jnp.float32))
            return (xf * jax.lax.rsqrt(var + eps) * scale).astype(xv.dtype)

        return run_op("zero_centered_gated_norm", norm, x, self.weight)


class GatedDeltaMixer(Layer):
    """The gated delta-rule mixer (module docstring)."""

    def __init__(self, config: GatedDeltaMoEMLAConfig):
        super().__init__()
        self.config = config
        c = config
        h, hv = c.hidden_size, c.linear_num_value_heads
        if hv % c.linear_num_key_heads:
            raise ValueError("value heads share key heads in whole groups: "
                             f"{hv} is no multiple of {c.linear_num_key_heads}")
        init = Normal(0.0, c.initializer_range)
        d_v = hv * c.linear_value_head_dim

        self.in_proj = Linear(h, c.delta_conv_dim + d_v, weight_attr=init,
                              bias_attr=False)              # q | k | v | z
        self.ba_proj = Linear(h, 2 * hv, weight_attr=init, bias_attr=False)
        self.conv_weight = self.create_parameter(
            [c.linear_conv_kernel_dim, c.delta_conv_dim], attr=init)
        # float32 whatever the model's type, as the recurrence is
        self.A_log = self.create_parameter(
            [hv], dtype="float32", default_initializer=Constant(0.0))
        self.dt_bias = self.create_parameter(
            [hv], dtype="float32", default_initializer=Constant(0.0))
        self.o_norm = self.create_parameter(
            [c.linear_value_head_dim], default_initializer=Constant(0.0))
        self.out_proj = Linear(d_v, h, weight_attr=init, bias_attr=False)

    def forward(self, x, cache=None, pos=None):
        if cache is not None and not isinstance(cache, StateCache):
            raise TypeError(
                "a delta-rule mixer keeps per-sequence state: it takes a "
                f"StateCache (CacheSpec.state), not {type(cache).__name__}")
        core = functools.partial(delta_mixer_core, self.config, cache)
        with jax.named_scope("gdn_in_proj"):
            qkvz, ba = self.in_proj(x), self.ba_proj(x)
        args = [qkvz, ba, self.conv_weight, self.A_log, self.dt_bias,
                self.o_norm]
        if cache is None:
            y = run_op("gated_delta_mixer", core, *args)
        else:
            y, state, conv = run_op("gated_delta_mixer", core, *args,
                                    cache.state_pool, cache.conv_pool)
            cache.state_pool._rebind(state)
            cache.conv_pool._rebind(conv)
        with jax.named_scope("gdn_out"):
            return self.out_proj(y)


def delta_mixer_core(c, cache, qkvz, ba, conv_w, a_log, dt_bias, o_w,
                     *pools):
    """The mixer between its in- and out-projections, on plain arrays:
    ``qkvz`` ``[B, S, conv_dim + H_v d]``, ``ba`` ``[B, S, 2 H_v]``.
    Without a ``cache`` (the cache-less forward) returns ``y [B, S, H_v
    d]``; with one (a routed :class:`StateCache`; ``pools`` its two slot
    pools) ``(y, state pool, conv pool)``, the pools updated in place."""
    hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv, k = (c.linear_key_head_dim, c.linear_value_head_dim,
                 c.linear_conv_kernel_dim)
    conv_dim = c.delta_conv_dim
    decode = cache is not None and cache.n_valid is None
    step_scope = "gdn_step" if decode else "gdn_chunk"
    B, S = qkvz.shape[0], qkvz.shape[1]
    u, z = qkvz[..., :conv_dim], qkvz[..., conv_dim:]
    f32 = jnp.float32
    in_place = False
    if cache is None:
        window = jnp.zeros((B, k - 1, conv_dim), u.dtype)
        s0 = jnp.zeros((B, hv, dk, dv), f32)
    else:
        state_pool, conv_pool = pools
        slots = cache.slots
        # a decode launch the kernel can take steps each row's state in
        # its slot: nothing of the state is gathered
        in_place = route_state_step(cache, state_pool.shape) == "pallas"
        with jax.named_scope(step_scope):
            s0, window = _carried_state(
                cache, None if in_place else state_pool[slots],
                conv_pool[slots].reshape(B, k - 1, conv_dim), decode)
    with jax.named_scope("gdn_conv"):
        xc, padded = causal_conv(u, window, conv_w, None)
    with jax.named_scope("gdn_gates"):
        def heads(a, n, d):
            return a.reshape(B, S, n, d)

        q = l2_normalize(heads(xc[..., :hk * dk], hk, dk)) \
            * (1.0 / math.sqrt(dk))
        kk = l2_normalize(heads(xc[..., hk * dk:2 * hk * dk], hk, dk))
        if not in_place:
            # key head j serves value heads j * rep .. (j + 1) * rep - 1
            # (the kernel indexes the key head itself)
            q = jnp.repeat(q, hv // hk, axis=2)
            kk = jnp.repeat(kk, hv // hk, axis=2)
        v = heads(xc[..., 2 * hk * dk:], hv, dv)
        beta, log_alpha = gates(ba[..., :hv], ba[..., hv:], a_log, dt_bias)
    with jax.named_scope(step_scope):
        if decode:
            step = (q[:, 0], kk[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0])
            if in_place:
                from ..ops.pallas_gated_delta import state_step

                o, state_pool = state_step(*step, state_pool, slots)
            else:
                o, s = gated_delta_step(*step, s0)
            o = o[:, None]
            keep = padded[:, 1:]
        else:
            n_valid = None if cache is None else cache.n_valid
            o, s = gated_delta_chunked(q, kk, v, log_alpha, beta, s0,
                                       n_valid, CHUNK)
            if cache is not None:
                keep = conv_window(padded, n_valid, k)
    with jax.named_scope("gdn_out"):
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        gate = c.linear_sigmoid_gate_scale * jax.nn.sigmoid(
            heads(z, hv, dv).astype(f32))
        y = (o * jax.lax.rsqrt(var + c.linear_attn_o_norm_eps)
             * (1.0 + o_w.astype(f32)) * gate)
        y = y.reshape(B, S, hv * dv).astype(qkvz.dtype)
    if cache is None:
        return y
    with jax.named_scope(step_scope):
        # in place on the donated pools; padding rows all write the null
        # slot 0, which no sequence reads
        if not in_place:
            state_pool = state_pool.at[slots].set(s)
        conv_pool = conv_pool.at[slots].set(
            keep.reshape(B, -1).astype(conv_pool.dtype))
    return y, state_pool, conv_pool


class GatedDeltaDecoderLayer(Layer):
    """Sandwich-norm block: the mixer and the feed-forward the layer's
    index chooses (module docstring).  What it brings to a launch follows
    from the two choices: ``StateSlots`` where it keeps a state,
    ``ExpertLoad`` where it routes."""

    def __init__(self, config: GatedDeltaMoEMLAConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        self.is_attention = layer_idx in tuple(config.full_attention_layers)
        routed = layer_idx >= config.first_k_dense_replace
        self.telemetry = (() if self.is_attention else (StateSlots,)) \
            + ((ExpertLoad,) if routed else ())
        self.input_layernorm = config.make_norm()
        if self.is_attention:
            self.self_attn = LatentAttention(config)
        else:
            self.delta = GatedDeltaMixer(config)
        self.post_mixer_layernorm = config.make_norm()
        self.pre_ff_layernorm = config.make_norm()
        self.mlp = RoutedExperts(config) if routed else LlamaMLP(config)
        self.post_ff_layernorm = config.make_norm()

    def cache_spec(self) -> CacheSpec:
        """The latent layer: one latent row a token, as ``moe_mla.py``'s.
        A delta-rule layer: no per-token row; per sequence the matrix state
        of every value head (float32) and the convolution's last inputs
        (the pool's type)."""
        c = self.config
        if self.is_attention:
            return CacheSpec(k=(1, c.latent_dim), v=None, kind="latent")
        return CacheSpec(state=(
            ((c.linear_num_value_heads, c.linear_key_head_dim,
              c.linear_value_head_dim), "float32"),
            (((c.linear_conv_kernel_dim - 1) * c.delta_conv_dim,), None)),
            cache=StateCache)

    def forward(self, x, cache=None, pos=None):
        mixer = self.self_attn if self.is_attention else self.delta
        with jax.named_scope("attn" if self.is_attention else "gdn"):
            a = self.post_mixer_layernorm(
                mixer(self.input_layernorm(x), cache=cache, pos=pos))
        h = x + a
        with jax.named_scope("mlp"):
            m = self.post_ff_layernorm(self.mlp(self.pre_ff_layernorm(h)))
        return h + m
