"""A decoder-layer kind: the selective-scan (Mamba-1) mixer, among
attention layers — the ``jamba`` hybrid block.

It lives under the :class:`~paddle_tpu.models.llama.LlamaModel` /
``LlamaForCausalLM`` skeleton (embedding, stack, final norm, tied head and
the ``embed`` / ``lm_head`` scopes are that file's): a
:class:`HybridMambaConfig` makes the stack build, layer by layer, either
the dense ``LlamaDecoderLayer`` (where ``i % attn_layer_period ==
attn_layer_offset``; its rotation is switched off by ``use_rope=False``,
the published model has no positional embedding) or
:class:`MambaDecoderLayer`.  No flag of ``LlamaAttention`` chooses the
mixer.  Every layer is followed by the dense SwiGLU.

**The mixer**, for token ``t`` with input ``u_t`` (``D`` = ``mamba_expand
* hidden``, ``N`` = ``mamba_d_state``, ``K`` = ``mamba_d_conv``, ``R`` =
``mamba_dt_rank``)::

    x_t | z_t   = W_in u_t                                   [D + D]
    x_t         = silu(sum_j w_c[j] * x_{t-K+1+j} + b_c)     causal, per channel
    d | B | C   = W_x x_t                                    [R + N + N]
    d, B, C     = RMSNorm_d(d), RMSNorm_B(B), RMSNorm_C(C)
    dt          = softplus(W_dt d + b_dt)                    [D]
    H_t         = exp(dt (x) A) * H_{t-1} + (dt * x_t) (x) B,   A = -exp(A_log)
    y_t         = H_t C + D_skip * x_t;   out = W_out (y_t * silu(z_t))

From the convolution on, everything is float32.  What a sequence holds
after token ``t`` is ``H_t`` and the ``K - 1`` inputs of the convolution
ending at ``t``: the layer DECLARES that (:meth:`MambaDecoderLayer.
cache_spec`: no per-token row, two per-sequence arrays) and the engine
allocates slot pools from it and hands them in as a
:class:`~paddle_tpu.ops.selective_scan.StateCache`.  Three paths: a scan
over a (padded) bucket that stops at the last real token and starts from
zero or from the slot; one step a row for decode (on the chip at tileable
widths one Pallas kernel that steps the state in its slot,
``ops/pallas_ssm.py``; otherwise gather by slot, step, scatter in place);
and the cache-less forward over a whole sequence.

Device scopes, under an outer ``ssm`` that is NOT inside ``attn``:
``ssm_in_proj``, ``ssm_conv``, ``ssm_x_proj`` (projection, the three
norms, dt), ``ssm_scan`` (prefill and chunk), ``ssm_step`` (decode: EVERY
operation that reads or writes either slot pool, the kernel's custom call
included), ``ssm_out`` (gate and out-projection).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.dispatch import run_op
from ..nn.common import Linear
from ..nn.initializer import Constant, Normal
from ..nn.layers import Layer
from ..nn.norm import RMSNorm
from ..ops.paged_attention import CacheSpec
from ..ops.selective_scan import (
    StateCache,
    StateSlots,
    causal_conv,
    conv_window,
    route_state_step,
    selective_scan,
    selective_step,
)
from .llama import LlamaConfig, LlamaDecoderLayer, LlamaMLP


@dataclass
class HybridMambaConfig(LlamaConfig):
    """``LlamaConfig`` plus the published keys of the ``jamba`` family.
    Defaults are AI21-Jamba2-3B's widths."""

    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    use_rope: bool = False                  # no positional embedding
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def is_attention_layer(self, layer_idx: int) -> bool:
        """As the published ``jamba`` code reads the two keys."""
        return layer_idx % self.attn_layer_period == self.attn_layer_offset

    def make_decoder_layer(self, layer_idx: int) -> Layer:
        if self.is_attention_layer(layer_idx):
            return LlamaDecoderLayer(self, layer_idx)
        return MambaDecoderLayer(self, layer_idx)

    @classmethod
    def tiny(cls, **kw):
        """Test config: two periods of a shortened pattern, so both layer
        kinds and their order are present."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=6, num_attention_heads=4,
            num_key_value_heads=1, max_position_embeddings=512,
            attn_layer_period=3, attn_layer_offset=1, mamba_d_state=8,
            mamba_d_conv=4, mamba_dt_rank=8, mamba_expand=2)
        defaults.update(kw)
        return cls(**defaults)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _dot(x, w):
    """``x @ w`` in the weight's type with float32 accumulation."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _carried_state(cache, h_slot, window_slot, decode: bool):
    """What a launch starts from, given what its rows' slots hold: a
    decode step and a chunk past position 0 carry the slot's state on; a
    launch that STARTS a sequence starts from zero and never reads the
    slot, whose last owner's state is still in it."""
    if decode:
        return h_slot, window_slot
    if cache.start is None:         # one-shot prefill: position 0, always
        return jnp.zeros_like(h_slot), jnp.zeros_like(window_slot)
    fresh = cache.start == 0
    return (jnp.where(fresh, 0.0, h_slot),
            jnp.where(fresh, 0, window_slot).astype(window_slot.dtype))


class MambaMixer(Layer):
    """The selective-scan mixer (module docstring)."""

    def __init__(self, config: HybridMambaConfig):
        super().__init__()
        self.config = config
        c = config
        h, d, n = c.hidden_size, c.mamba_d_inner, c.mamba_d_state
        r, k = c.mamba_dt_rank, c.mamba_d_conv
        init = Normal(0.0, c.initializer_range)
        bias = None if c.mamba_proj_bias else False

        self.in_proj = Linear(h, 2 * d, weight_attr=init, bias_attr=bias)
        self.conv_weight = self.create_parameter([k, d], attr=init)
        self.conv_bias = self.create_parameter(
            [d], is_bias=True, default_initializer=Constant(0.0)) \
            if c.mamba_conv_bias else None
        self.x_proj = Linear(d, r + 2 * n, weight_attr=init, bias_attr=False)
        self.dt_layernorm = RMSNorm(r, c.rms_norm_eps)
        self.b_layernorm = RMSNorm(n, c.rms_norm_eps)
        self.c_layernorm = RMSNorm(n, c.rms_norm_eps)
        self.dt_proj = Linear(r, d, weight_attr=init)        # with b_dt
        # A = -exp(A_log), [N, D] (the channel axis minor), and the skip
        # D: float32 whatever the model's type, as the recurrence is
        self.A_log = self.create_parameter(
            [n, d], dtype="float32", default_initializer=Constant(0.0))
        self.D = self.create_parameter(
            [d], dtype="float32", default_initializer=Constant(1.0))
        self.out_proj = Linear(d, h, weight_attr=init, bias_attr=bias)

    def forward(self, x, cache=None, pos=None):
        c = self.config
        d, n, r, k = (c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank,
                      c.mamba_d_conv)
        eps = c.rms_norm_eps
        if cache is not None and not isinstance(cache, StateCache):
            raise TypeError(
                "a selective-scan mixer keeps per-sequence state: it takes a "
                f"StateCache (CacheSpec.state), not {type(cache).__name__}")
        decode = cache is not None and cache.n_valid is None

        def core(xz, conv_w, conv_b, x_w, dt_n, b_n, c_n, dt_w, dt_b, a_log,
                 d_skip, *pools):
            B, S = xz.shape[0], xz.shape[1]
            u, z = xz[..., :d], xz[..., d:]
            f32 = jnp.float32
            in_place = False
            if cache is None:
                window = jnp.zeros((B, k - 1, d), u.dtype)
                h0 = jnp.zeros((B, n, d), f32)
            else:
                state_pool, conv_pool = pools
                slots = cache.slots
                # a decode launch the kernel can take steps each row's
                # state in its slot: nothing of the state is gathered
                in_place = route_state_step(cache,
                                            state_pool.shape) == "pallas"
                with jax.named_scope("ssm_step" if decode else "ssm_scan"):
                    h0, window = _carried_state(
                        cache, None if in_place else state_pool[slots],
                        conv_pool[slots].reshape(B, k - 1, d), decode)
            with jax.named_scope("ssm_conv"):
                xc, padded = causal_conv(u, window, conv_w, conv_b)
            with jax.named_scope("ssm_x_proj"):
                dbc = _dot(xc, x_w)
                delta = _rms(dbc[..., :r], dt_n, eps)
                Bm = _rms(dbc[..., r:r + n], b_n, eps)
                Cm = _rms(dbc[..., r + n:], c_n, eps)
                dt = jax.nn.softplus(_dot(delta, dt_w) + dt_b.astype(f32))
            A = -jnp.exp(a_log.astype(f32))
            if decode:
                with jax.named_scope("ssm_step"):
                    step = (xc[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
                    if in_place:
                        from ..ops.pallas_ssm import state_step

                        y, state_pool = state_step(*step, state_pool, slots)
                    else:
                        y, h = selective_step(*step, h0)
                    y = y[:, None]
                    keep = padded[:, 1:]
            else:
                with jax.named_scope("ssm_scan"):
                    n_valid = S if cache is None else cache.n_valid
                    y, h = selective_scan(xc, dt, A, Bm, Cm, h0, n_valid)
                    if cache is not None:
                        keep = conv_window(padded, n_valid, k)
            with jax.named_scope("ssm_out"):
                y = ((y + d_skip.astype(f32) * xc)
                     * jax.nn.silu(z.astype(f32))).astype(xz.dtype)
            if cache is None:
                return y
            with jax.named_scope("ssm_step" if decode else "ssm_scan"):
                # in place on the donated pools; padding rows all write
                # the null slot 0, which no sequence reads
                if not in_place:
                    state_pool = state_pool.at[slots].set(h)
                conv_pool = conv_pool.at[slots].set(
                    keep.reshape(B, -1).astype(conv_pool.dtype))
            return y, state_pool, conv_pool

        with jax.named_scope("ssm_in_proj"):
            xz = self.in_proj(x)
        args = [xz, self.conv_weight, self.conv_bias, self.x_proj.weight,
                self.dt_layernorm.weight, self.b_layernorm.weight,
                self.c_layernorm.weight, self.dt_proj.weight,
                self.dt_proj.bias, self.A_log, self.D]
        if cache is None:
            y = run_op("mamba_mixer", core, *args)
        else:
            y, state, conv = run_op("mamba_mixer", core, *args,
                                    cache.state_pool, cache.conv_pool)
            cache.state_pool._rebind(state)
            cache.conv_pool._rebind(conv)
        with jax.named_scope("ssm_out"):
            return self.out_proj(y)


class MambaDecoderLayer(Layer):
    """Pre-norm block: the mixer, then the dense SwiGLU."""

    telemetry = (StateSlots,)

    def __init__(self, config: HybridMambaConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mamba = MambaMixer(config)
        self.pre_ff_layernorm = RMSNorm(config.hidden_size,
                                        config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def cache_spec(self) -> CacheSpec:
        """No per-token row; per sequence the recurrent state (float32)
        and the convolution's last inputs (the pool's type)."""
        c = self.config
        return CacheSpec(state=(
            ((c.mamba_d_state, c.mamba_d_inner), "float32"),
            (((c.mamba_d_conv - 1) * c.mamba_d_inner,), None)),
            cache=StateCache)

    def forward(self, x, cache=None, pos=None):
        with jax.named_scope("ssm"):
            a = self.mamba(self.input_layernorm(x), cache=cache, pos=pos)
        h = x + a
        with jax.named_scope("mlp"):
            m = self.mlp(self.pre_ff_layernorm(h))
        return h + m
