"""A decoder-layer kind: a pre-norm block whose attention is
CHUNK-SUMMARISED (EVA) -- an exact window beside one pooled key/value row
per chunk of every closed window -- the ``evabyte`` block.

It lives under the :class:`~paddle_tpu.models.llama.LlamaModel` /
``LlamaForCausalLM`` skeleton (embedding, stack and the ``embed`` /
``lm_head`` scopes are that file's; the feed-forward is its
:class:`~paddle_tpu.models.llama.LlamaMLP` as it stands): an
:class:`EvaConfig` makes the stack build :class:`EvaDecoderLayer`
(``make_decoder_layer``), a unit-offset final norm (``make_final_norm``)
and the stacked multi-byte head (``make_lm_head``).  No flag of
``LlamaDecoderLayer`` is involved and no RoPE table is built: the
rotation is computed in the trace (``ops.eva_attention.rotate``), so
``max_position_embeddings`` costs a step program nothing.

**The block** (``d`` = head size, no biases)::

    x <- x + Attn(norm1(x))                 the sum in float32 (fp32_skip_add)
    x <- x + W_down(silu(W_gate n) * W_up n)        n = norm2(x)
    norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)   (norm_add_unit_offset)

The residual stream is float32 from the first layer on; a norm reads it
in the compute type (the weights' type; ``fp32_ln`` false), its mean of
squares accumulated in float32.  ``q, k, v`` come from ``norm1(x)``; ``q``
and ``k`` are rotated by absolute position (all ``d`` dimensions,
rotate-half pairing); the attention is ``ops/eva_attention.py``'s, with
two learned vectors a head, ``adaptive_mu_k`` (pools keys) and
``adaptive_phi`` (pools values); then ``W_o``.

**What a layer keeps, and says so** (:meth:`EvaDecoderLayer.cache_spec`):
a ring of the open window a SEQUENCE and a row a CHUNK of it, both at
once (``CacheSpec(state=, window=, k=, v=, tokens_per_row=chunk_size)``).
The engine hands it an :class:`~paddle_tpu.ops.eva_attention.EvaCache`.

**A prompt longer than a window is carried window by window**
(:meth:`EvaDecoderLayer._by_windows`): a window's queries need its own
keys and the rows of the windows before it, nothing else, so the WHOLE
layer (norms, projections, attention, feed-forward) runs a window at a
time under one ``lax.scan`` with the layer's ring and rows as its carry,
and no activation of the layer is ever longer than a window.

**The head**: ``num_pred_heads`` stacked heads of ``vocab_size`` rows, one
``[hidden, num_pred_heads * vocab_size]`` matrix.  Head 0, the next byte,
is computed (float32 logits, ``fp32_logits``); the others are held and not
computed (multi-byte self-drafting is ROADMAP R6's).

Device scopes, nested in the block's ``attn``: ``eva_attn`` (``eva_local``,
``eva_remote``, ``eva_merge`` under it in decode) and ``eva_pool``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.dispatch import run_op
from ..core.tensor import Tensor
from ..nn.common import Linear
from ..nn.initializer import Constant, Normal
from ..nn.layers import Layer
from ..ops import eva_attention as _eva
from ..ops import window_attention as _win
from ..ops.paged_attention import CacheSpec
from ..ops.selective_scan import StateSlots
from .llama import LlamaConfig, LlamaMLP
from .window_moe import _positions


@dataclass
class EvaConfig(LlamaConfig):
    """``LlamaConfig`` plus the published keys of the ``evabyte`` family.
    Defaults are EvaByte 6.5B's."""

    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("chunk-summarised attention is built for "
                             "multi-head attention (one key head a query "
                             "head)")
        if self.window_size % self.chunk_size:
            raise ValueError(f"chunk_size {self.chunk_size} does not divide "
                             f"window_size {self.window_size}")

    def make_decoder_layer(self, layer_idx: int) -> Layer:
        return EvaDecoderLayer(self, layer_idx)

    def make_final_norm(self) -> Layer:
        return UnitOffsetRMSNorm(self.hidden_size, self.rms_norm_eps)

    def make_lm_head(self) -> Layer:
        return StackedByteHead(self)

    @classmethod
    def tiny(cls, **kw):
        """Test config: a window of two chunks, so that a short prompt
        closes several."""
        defaults = dict(
            vocab_size=96, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=512,
            rope_theta=10000.0, window_size=32, chunk_size=16,
            num_pred_heads=2)
        defaults.update(kw)
        return cls(**defaults)


class UnitOffsetRMSNorm(Layer):
    """``x / sqrt(mean(x^2) + eps) * (1 + w)`` in the weight's type, the
    mean of squares accumulated in float32."""

    def __init__(self, hidden_size: int, epsilon: float):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter(
            [hidden_size], default_initializer=Constant(0.0))

    def forward(self, x):
        eps = self.epsilon

        def norm(xv, w):
            xc = xv.astype(w.dtype)
            ms = jnp.mean(jnp.square(xc.astype(jnp.float32)), -1,
                          keepdims=True)
            return (xc * jax.lax.rsqrt(ms + eps).astype(w.dtype)) * (1 + w)

        return run_op("unit_offset_rms_norm", norm, x, self.weight)


class StackedByteHead(Layer):
    """``num_pred_heads`` heads of ``vocab_size`` rows in one matrix; the
    forward computes head 0's logits, in float32."""

    def __init__(self, config: EvaConfig):
        super().__init__()
        self.vocab = config.vocab_size
        self.weight = self.create_parameter(
            [config.hidden_size, config.num_pred_heads * config.vocab_size],
            attr=Normal(0.0, config.initializer_range))

    def forward(self, h):
        v = self.vocab
        return run_op("next_byte_head", lambda a, w: jnp.matmul(
            a.astype(w.dtype), w[:, :v],
            preferred_element_type=jnp.float32), h, self.weight)


class EvaAttention(Layer):
    """Multi-head chunk-summarised attention of one layer; module
    docstring and ``ops/eva_attention.py``."""

    def __init__(self, config: EvaConfig):
        super().__init__()
        self.config = config
        c = config
        h, heads, d = c.hidden_size, c.num_attention_heads, c.head_dim
        init = Normal(0.0, c.initializer_range)
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            setattr(self, name, Linear(h, heads * d, weight_attr=init,
                                       bias_attr=False))
        vec = Normal(0.0, d ** -0.5)
        self.adaptive_mu_k = self.create_parameter([heads, d], attr=vec)
        self.adaptive_phi = self.create_parameter([heads, d], attr=vec)

    def forward(self, x, cache=None, pos=None):
        c = self.config
        B, S = x.shape[0], x.shape[1]
        heads, d = c.num_attention_heads, c.head_dim
        W, C = c.window_size, c.chunk_size
        idx = _positions(pos, S)

        def split(qv, kv, vv):
            qv, kv = (_eva.rotate(a.reshape(B, S, heads, d), idx,
                                  c.rope_theta) for a in (qv, kv))
            return qv, kv, vv.reshape(B, S, heads, d)

        q, k, v = run_op("attn_heads", split, self.q_proj(x), self.k_proj(x),
                         self.v_proj(x))
        mu, phi = self.adaptive_mu_k, self.adaptive_phi
        if cache is None:           # the cache-less forward over sequences
            if idx.ndim != 1:
                raise NotImplementedError("the cache-less forward takes one "
                                          "set of positions for all rows")

            def whole_rows(qv, kv, vv, m, f):
                def one(a, b_, c_):
                    ids, kb, vb = _eva.span_summaries(
                        b_, c_, 0, jnp.int32(0), S // C, m, f, W, C)
                    return _eva.span_attention(
                        a, idx, b_, c_, idx, kb.astype(b_.dtype),
                        vb.astype(c_.dtype), ids, W, C)
                return jax.vmap(one)(qv, kv, vv)

            o = run_op("eva_attention", whole_rows, q, k, v, mu, phi)
        elif not isinstance(cache, _eva.EvaCache):
            raise TypeError(
                "a chunk-summarised layer keeps a ring a sequence and a row "
                "a chunk: it takes an EvaCache (CacheSpec.tokens_per_row), "
                f"not {type(cache).__name__}")
        else:
            step = self._decode if cache.n_valid is None else self._span
            o, *written = run_op(
                "eva_cached_attention", lambda *a: step(cache, idx, *a),
                q, k, v, mu, phi, *cache.tensors)
            cache.rebind(*written)
        return self.o_proj(o)

    def _decode(self, cache, idx, q, k, v, mu, phi, kr, vr, krow, vrow):
        """One token a row: into the ring, the chunk it completes into the
        rows, then the ring's visible entries and the closed windows' rows
        under one softmax."""
        c = self.config
        W, C = c.window_size, c.chunk_size
        assert q.shape[1] == 1, "decode is one token a row a step"
        p, slots, tables = idx[:, 0], cache.slots, cache.tables
        kr = _win.ring_write_token(kr, slots, p, k[:, 0])
        vr = _win.ring_write_token(vr, slots, p, v[:, 0])
        kb, vb = _eva.decode_pool(kr, vr, slots, p, mu, phi, C)
        ch = p // C
        whole = (jnp.mod(p, C) == C - 1) & (slots > 0)
        blocks = _eva.chunk_blocks(tables, ch[:, None], krow.shape[1])[:, 0]
        krow = _eva.rows_write(krow, blocks, ch, kb, whole)
        vrow = _eva.rows_write(vrow, blocks, ch, vb, whole)
        o = _eva.decode_attention(q[:, 0], kr, vr, krow, vrow, slots, tables,
                                  p, W, C)
        return o[:, None], kr, vr, krow, vrow

    def _span(self, cache, idx, q, k, v, mu, phi, kr, vr, krow, vrow):
        """A prompt, a window of one, or a chunk of one, of ONE sequence:
        the launch's real tokens into the ring, the chunks it completes
        into the rows, its queries over what they may see."""
        c = self.config
        W, C = c.window_size, c.chunk_size
        T, R = q.shape[1], krow.shape[1]
        slot, table = cache.slots[0], cache.tables[0]
        n_valid = cache.n_valid
        start = jnp.int32(0) if cache.start is None else cache.start
        pos = idx if idx.ndim == 1 else idx[0]
        k_loc, v_loc, loc_pos = k[0], v[0], pos
        if cache.carried:
            # the ring as the launch found it: this window's earlier part
            k_loc = jnp.concatenate([kr[slot].astype(k.dtype), k_loc], 0)
            v_loc = jnp.concatenate([vr[slot].astype(v.dtype), v_loc], 0)
            loc_pos = jnp.concatenate(
                [_eva.aligned_ring_positions(start, W), pos], 0)
        # the chunks this launch completes, from its own keys (and the
        # ring's where a chunk began before it)
        ids, kb, vb = _eva.span_summaries(
            k_loc, v_loc, W if cache.carried else 0, start,
            T // C + (1 if cache.carried else 0), mu, phi, W, C)
        whole = (ids + 1) * C <= start + n_valid
        kb, vb = kb.astype(krow.dtype), vb.astype(vrow.dtype)
        # the rows of closed windows: those written before this launch,
        # and where the launch may cross a window's end, its own
        k_rem = v_rem = rem = None
        if cache.start is not None:
            k_rem = krow[table].reshape(-1, *krow.shape[2:])
            v_rem = vrow[table].reshape(-1, *vrow.shape[2:])
            j = jnp.arange(k_rem.shape[0], dtype=jnp.int32)
            rem = jnp.where(j < start // C, j, -1)
            if cache.carried:
                k_rem = jnp.concatenate([k_rem, kb], 0)
                v_rem = jnp.concatenate([v_rem, vb], 0)
                rem = jnp.concatenate([rem, jnp.where(whole, ids, -1)], 0)
        o = _eva.span_attention(q[0], pos, k_loc, v_loc, loc_pos, k_rem,
                                v_rem, rem, W, C)
        kr = _win.ring_write_span(kr, slot, k[0], start, n_valid)
        vr = _win.ring_write_span(vr, slot, v[0], start, n_valid)
        blocks = _eva.chunk_blocks(table, ids, R)
        krow = _eva.rows_write(krow, blocks, ids, kb, whole)
        vrow = _eva.rows_write(vrow, blocks, ids, vb, whole)
        return o[None], kr, vr, krow, vrow


class EvaDecoderLayer(Layer):
    """``x + Attn(norm1(x))``, then ``+ MLP(norm2(.))``, the sums in
    float32; a prompt longer than a window a window at a time."""

    telemetry = (StateSlots, _eva.SummaryRows)

    def __init__(self, config: EvaConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        self.input_layernorm = UnitOffsetRMSNorm(config.hidden_size,
                                                 config.rms_norm_eps)
        self.self_attn = EvaAttention(config)
        self.post_attention_layernorm = UnitOffsetRMSNorm(
            config.hidden_size, config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def cache_spec(self) -> CacheSpec:
        """A ring of the open window's keys and one of its values a
        sequence, AND a row of pooled keys and one of pooled values a
        chunk, all in the pool's type."""
        c = self.config
        row = (c.num_attention_heads, c.head_dim)
        ring = ((c.window_size,) + row, None)
        return CacheSpec(k=row, v=row, state=(ring, ring),
                         window=c.window_size, tokens_per_row=c.chunk_size,
                         cache=_eva.EvaCache)

    def _block(self, x, cache, pos):
        def add(a, b):
            return a.astype(jnp.float32) + b.astype(jnp.float32)

        with jax.named_scope("attn"):
            a = self.self_attn(self.input_layernorm(x), cache=cache, pos=pos)
        h = run_op("skip_add", add, x, a)
        with jax.named_scope("mlp"):
            m = self.mlp(self.post_attention_layernorm(h))
        return run_op("skip_add", add, h, m)

    def forward(self, x, cache=None, pos=None):
        W = self.config.window_size
        if isinstance(cache, _eva.EvaCache) and cache.n_valid is not None \
                and cache.start is None and x.shape[1] > W:
            return self._by_windows(x, cache)
        return self._block(x, cache, pos)

    def _by_windows(self, x, cache):
        """A whole prompt of ``n`` windows (a bucket: a power of two past
        ``W``): the block a window at a time, ring and rows carried."""
        W = self.config.window_size
        S = x.shape[1]
        assert S % W == 0, f"a prompt bucket of {S} is no multiple of {W}"
        slots, tables, n_valid = cache.slots, cache.tables, cache.n_valid

        def windows(xv, kr, vr, krow, vrow):
            def body(carry, xs):
                xw, i = xs
                part = _eva.EvaCache((carry[0], carry[2]),
                                     (carry[1], carry[3]))
                # a window of a whole prompt: nothing of it is carried
                part.slots, part.tables, part.start = slots, tables, i * W
                part.n_valid = jnp.clip(n_valid - i * W, 0, W)
                y = self._block(Tensor(xw[None]), part, Tensor(i * W))
                return tuple(t._value for t in part.tensors), y._value[0]

            xs = (xv[0].reshape(S // W, W, xv.shape[-1]),
                  jnp.arange(S // W, dtype=jnp.int32))
            carry, ys = jax.lax.scan(body, (kr, vr, krow, vrow), xs)
            return (ys.reshape(1, S, ys.shape[-1]),) + carry

        y, *written = run_op("eva_by_windows", windows, x, *cache.tensors)
        cache.rebind(*written)
        return y
