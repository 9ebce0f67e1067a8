"""A decoder-layer kind: the PARALLEL block with sliding-window and global
attention layers interleaved and a share of the routed experts — the
``cohere2_moe`` block.

It lives under the :class:`~paddle_tpu.models.llama.LlamaModel` /
``LlamaForCausalLM`` skeleton (embedding, stack, tied head and the
``embed`` / ``lm_head`` scopes are that file's): a :class:`WindowMoEConfig`
makes the stack build :class:`ParallelWindowMoELayer` (``make_decoder_layer``)
and a mean-subtracting final norm (``make_final_norm``), and scales the
logits by ``logit_scale``.  No flag of ``LlamaDecoderLayer`` is involved.

**The block**, for layer ``i`` with ``u = LN_i(x)`` (``LN(x) = g * (x -
mean(x)) / sqrt(var(x) + eps)``, no bias)::

    out = x + Attn_i(u) + FFN(u)            one norm, both branches read it

**Attention**: ``q = W_q u`` ``[heads, d]``, ``k, v`` ``[kv_heads, d]``, no
bias, no q/k norm; query head ``h`` reads key/value head ``h // (heads /
kv_heads)``; ``score = q . k / sqrt(d)``.  A WINDOW layer
(``layer_types[i] == "sliding_attention"``) rotates ``q`` and ``k`` (RoPE
over all ``d`` dimensions) and key ``s`` is visible to query ``t`` iff ``0
<= t - s < sliding_window``; a GLOBAL layer (``"full_attention"``) rotates
nothing and sees every ``s <= t``.

**What each kind keeps between steps, and says so** (:meth:`ParallelWindowMoELayer.
cache_spec`): a global layer keys and values a TOKEN, in pages
(``CacheSpec(k=, v=)``, as a dense layer); a window layer two rings
``[sliding_window, kv_heads, d]`` a SEQUENCE, in a slot
(``CacheSpec.state``; ``ops/window_attention.py`` has the ring's
arithmetic), so its memory does not grow past the window.  The engine
allocates both from the declaration and hands a window layer its slots as
a :class:`~paddle_tpu.ops.selective_scan.StateCache` (``k_pool`` the key
ring, ``v_pool`` the value ring).

**Feed-forward**: ``s = sigmoid(W_r u)`` in float32 over ALL
``num_routed_experts``; the ``num_experts_per_tok`` largest are chosen,
their weights ``s_i`` renormalised over the chosen; the layer computes
what the experts IT HOLDS (``experts_held``: a chip's share of an
expert-parallel deployment) give, ``sum_{i chosen, held} w_i E_i(u)``, plus
the MEAN of the ``num_shared_experts`` shared experts — one SwiGLU of
width ``num_shared_experts * intermediate_size`` scaled by ``1 /
num_shared_experts``.  What absent experts would add is another chip's.

Device scopes, nested in the ``attn`` / ``mlp`` scopes of the block:
``attn_window`` (ring write, ring read, the banded prompt attention),
``attn_global`` (page write, paged read, the causal prompt attention);
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``,
``moe_shared``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.dispatch import run_op
from ..nn.common import Linear
from ..nn.initializer import Normal
from ..nn.layers import Layer
from ..nn.norm import LayerNorm
from ..ops import paged_attention as _paged
from ..ops import window_attention as _win
from ..ops.paged_attention import CacheSpec, PagedCache
from ..ops.selective_scan import StateCache, StateSlots
from ..parallel.moe import ExpertLoad, dropless_experts, sigmoid_topk_route
from .llama import LlamaConfig, LlamaMLP, _apply_rope, _rope_tables


@dataclass
class WindowMoEConfig(LlamaConfig):
    """``LlamaConfig`` plus the published keys of the ``cohere2_moe``
    family.  Defaults are command-a-plus-05-2026's widths."""

    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096           # one expert's width
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128                     # published beside the head count
    max_position_embeddings: int = 200000
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    tie_word_embeddings: bool = True
    logit_scale: float = 1.0
    sliding_window: int = 4096
    # "sliding_attention" / "full_attention" a layer; None = three window
    # layers then a global one, repeated
    layer_types: Optional[Tuple[str, ...]] = None
    num_routed_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    # the routed experts THIS process holds (ids into num_routed_experts),
    # None = all: the router always scores all of them
    experts_held: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                "full_attention" if i % 4 == 3 else "sliding_attention"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        if self.experts_held is not None:
            self.experts_held = tuple(int(e) for e in self.experts_held)
        self.num_experts = self.num_routed_experts

    def is_window_layer(self, layer_idx: int) -> bool:
        return self.layer_types[layer_idx] == "sliding_attention"

    def make_decoder_layer(self, layer_idx: int) -> Layer:
        return ParallelWindowMoELayer(self, layer_idx)

    def make_final_norm(self) -> Layer:
        return LayerNorm(self.hidden_size, self.layer_norm_eps,
                         bias_attr=False)

    @classmethod
    def tiny(cls, **kw):
        """Test config: two periods, a window shorter than a prompt, a
        share of the experts."""
        defaults = dict(
            vocab_size=256, hidden_size=64, intermediate_size=48,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=2, head_dim=16,
            max_position_embeddings=256, rope_theta=10000.0,
            sliding_window=8, num_routed_experts=8, num_experts_per_tok=2,
            num_shared_experts=2, experts_held=(2, 3, 5))
        defaults.update(kw)
        return cls(**defaults)


def _positions(pos, S: int):
    """Absolute position of every token: ``[S]`` or ``[B, S]``."""
    if pos is None:
        return jnp.arange(S, dtype=jnp.int32)
    p = pos._value if hasattr(pos, "_value") else pos
    if jnp.ndim(p) == 2:
        return p
    return (p[:, None] if jnp.ndim(p) == 1 else p) + jnp.arange(S)


class WindowedAttention(Layer):
    """Grouped-query attention of one layer: a sliding window over a ring
    (rotated) or global over pages (not rotated); module docstring."""

    def __init__(self, config: WindowMoEConfig, window: Optional[int]):
        super().__init__()
        self.config = config
        self.window = window
        c = config
        h, d = c.hidden_size, c.head_dim
        init = Normal(0.0, c.initializer_range)

        def lin(i, o):
            return Linear(i, o, weight_attr=init, bias_attr=False)

        self.q_proj = lin(h, c.num_attention_heads * d)
        self.k_proj = lin(h, c.num_key_value_heads * d)
        self.v_proj = lin(h, c.num_key_value_heads * d)
        self.o_proj = lin(c.num_attention_heads * d, h)
        self._rope = _rope_tables(d, c.max_position_embeddings,
                                  c.rope_theta) if window else None

    def forward(self, x, cache=None, pos=None):
        c = self.config
        B, S = x.shape[0], x.shape[1]
        heads, hkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        idx = _positions(pos, S)

        def split(qv, kv, vv):
            qv = qv.reshape(B, S, heads, d)
            kv = kv.reshape(B, S, hkv, d)
            if self._rope is not None:      # a window layer rotates
                cos = jnp.asarray(self._rope[0])[idx]
                sin = jnp.asarray(self._rope[1])[idx]
                qv, kv = _apply_rope(qv, cos, sin), _apply_rope(kv, cos, sin)
            return qv, kv, vv.reshape(B, S, hkv, d)

        q, k, v = run_op("attn_heads", split, self.q_proj(x), self.k_proj(x),
                         self.v_proj(x))
        with jax.named_scope("attn_window" if self.window else "attn_global"):
            if self.window:
                o = self._window(q, k, v, cache, idx, S)
            else:
                o = self._global(q, k, v, cache, idx, S)
        return self.o_proj(o)

    # --- a window layer: the ring ---------------------------------------------
    def _window(self, q, k, v, cache, idx, S):
        W = self.window
        if cache is None:       # the cache-less forward over a sequence
            return run_op("window_attention", lambda a, b, c_: (
                _win.masked_attention(a, b, c_, idx, idx, W, banded=True)),
                q, k, v)
        if not isinstance(cache, StateCache):
            raise TypeError(
                "a sliding-window layer keeps a ring a sequence: it takes "
                f"its slots as a StateCache (CacheSpec.state), not "
                f"{type(cache).__name__}")
        slots = cache.slots
        use_pallas = getattr(cache, "use_pallas", None)

        if cache.n_valid is None:           # decode: one token a row
            assert S == 1, "decode is one token a row a step"
            p = idx[:, 0]

            def step(qv, kv, vv, kr, vr):
                kr = _win.ring_write_token(kr, slots, p, kv[:, 0])
                vr = _win.ring_write_token(vr, slots, p, vv[:, 0])
                o = _win.ring_decode_attention(qv[:, 0], kr, vr, slots, p,
                                               use_pallas)
                return o.reshape(o.shape[0], 1, -1), kr, vr
        else:                               # a prompt, or a chunk of one
            n_valid = cache.n_valid
            start = jnp.int32(0) if cache.start is None else cache.start
            carried = cache.start is not None

            def step(qv, kv, vv, kr, vr):
                if carried:
                    # the ring as the chunk found it, each entry with its
                    # position (none of this sequence where start is 0),
                    # beside the chunk's own keys
                    before = _win.ring_positions(start - 1, W)[None]
                    kp = jnp.concatenate([before, idx[None]], 1)
                    ka = jnp.concatenate([kr[slots].astype(kv.dtype), kv], 1)
                    va = jnp.concatenate([vr[slots].astype(vv.dtype), vv], 1)
                    o = _win.masked_attention(qv, ka, va, idx, kp, W)
                else:
                    o = _win.masked_attention(qv, kv, vv, idx, idx, W,
                                              banded=True)
                kr = _win.ring_write_span(kr, slots[0], kv[0], start, n_valid)
                vr = _win.ring_write_span(vr, slots[0], vv[0], start, n_valid)
                return o, kr, vr

        o, kr, vr = run_op("window_ring_attention", step, q, k, v,
                           cache.k_pool, cache.v_pool)
        cache.k_pool._rebind(kr)
        cache.v_pool._rebind(vr)
        return o

    # --- a global layer: pages ---------------------------------------------------
    def _global(self, q, k, v, cache, idx, S):
        if isinstance(cache, PagedCache):
            return self._paged(q, k, v, cache, idx, S)
        # one-shot prefill: the bucket's keys and values go to the dense
        # buffers, which the step program scatters into the sequence's
        # pages; the cache-less forward keeps nothing
        for buf, new in zip(cache or (), (k, v)):
            buf._rebind(run_op("kv_write", lambda b, n: n.astype(b.dtype),
                               buf, new))
        return run_op("global_attention", lambda a, b, c_: (
            _win.masked_attention(a, b, c_, idx, idx)), q, k, v)

    def _paged(self, q, k, v, cache, idx, S):
        if cache.seg_ids is not None:
            raise NotImplementedError(
                "the unified ragged program has no path for a model with "
                "window rings (EngineCore refuses unified_step for it)")
        blocks, offs = cache.slot_blocks, cache.slot_offsets
        chunk = blocks.ndim == 2

        def write(pool, new):
            new = new if chunk else new[:, 0]
            return pool.at[blocks, offs].set(new.astype(pool.dtype))

        kp, vp = cache.k_pool, cache.v_pool
        kp._rebind(run_op("paged_kv_write", write, kp, k))
        vp._rebind(run_op("paged_kv_write", write, vp, v))
        tables, lens = cache.block_tables, cache.seq_lens
        if chunk:
            def attend(qv, kpool, vpool):
                B, Wd = tables.shape
                M = Wd * kpool.shape[1]
                ka = kpool[tables].reshape(B, M, *kpool.shape[2:])
                va = vpool[tables].reshape(B, M, *vpool.shape[2:])
                col = jnp.arange(M, dtype=jnp.int32)[None]
                kpos = jnp.where(col < lens[:, None], col, -1)
                return _win.masked_attention(qv, ka.astype(qv.dtype),
                                             va.astype(qv.dtype), idx, kpos)
        else:
            assert S == 1, "paged decode is one token a row a step"

            def attend(qv, kpool, vpool):
                o = _paged.paged_attention(qv[:, 0], kpool, vpool, tables,
                                           lens, use_pallas=cache.use_pallas)
                return o.reshape(o.shape[0], 1, -1)

        return run_op("paged_attention", attend, q, kp, vp)


class HeldExperts(Layer):
    """``y = sum_{i in top-k, held} w_i E_i(x) + mean_m S_m(x)``: sigmoid
    router over all ``num_routed_experts``, the experts this process holds
    stacked ``[E_held, ...]``, no capacity and no dropped token, the
    shared experts as one SwiGLU scaled by ``1 / num_shared_experts``.
    After a forward ``load`` holds the pairs each of the
    ``num_routed_experts`` received (int32, padding rows included)."""

    def __init__(self, config: WindowMoEConfig):
        super().__init__()
        self.config = config
        c = config
        h, f = c.hidden_size, c.intermediate_size
        init = Normal(0.0, c.initializer_range)
        self.held = tuple(range(c.num_routed_experts)) \
            if c.experts_held is None else c.experts_held
        n = len(self.held)
        self.gate = Linear(h, c.num_routed_experts, weight_attr=init,
                           bias_attr=False)
        self.w_gate_up = self.create_parameter([n, h, 2 * f], attr=init)
        self.w_down = self.create_parameter([n, f, h], attr=init)
        shared_cfg = LlamaConfig(**{k: getattr(c, k) for k in
                                    LlamaConfig.__dataclass_fields__})
        shared_cfg.intermediate_size = f * c.num_shared_experts
        self.shared_experts = LlamaMLP(shared_cfg)
        self.load = None

    def forward(self, x):
        c = self.config
        B, S, H = x.shape

        def routed(xv, wg, w_gu, w_d):
            flat = xv.reshape(B * S, H)
            with jax.named_scope("moe_router"):
                ids, weights = sigmoid_topk_route(
                    flat, wg, jnp.zeros((c.num_routed_experts,), jnp.float32),
                    c.num_experts_per_tok, normalize=c.norm_topk_prob)
            out, load = dropless_experts(
                flat, ids, weights, w_gu.astype(xv.dtype),
                w_d.astype(xv.dtype), c.num_routed_experts, self.held)
            return out.reshape(B, S, H), load

        out, load = run_op("moe_held_experts", routed, x, self.gate.weight,
                           self.w_gate_up, self.w_down)
        self.load = load._value
        with jax.named_scope("moe_shared"):
            mean = self.shared_experts(x) * (1.0 / c.num_shared_experts)
        return out + mean


class ParallelWindowMoELayer(Layer):
    """``x + Attn(LN(x)) + FFN(LN(x))``: one norm, two branches."""

    def __init__(self, config: WindowMoEConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        self.window = config.sliding_window \
            if config.is_window_layer(layer_idx) else None
        self.input_layernorm = LayerNorm(config.hidden_size,
                                         config.layer_norm_eps,
                                         bias_attr=False)
        self.self_attn = WindowedAttention(config, self.window)
        self.mlp = HeldExperts(config)
        self.telemetry = (ExpertLoad,) if self.window is None else (
            StateSlots, _win.WindowTokens, ExpertLoad)

    def cache_spec(self) -> CacheSpec:
        """A global layer: keys and values a token, in pages.  A window
        layer: a ring of keys and one of values a sequence, in the pool's
        type, and nothing a token."""
        c = self.config
        row = (c.num_key_value_heads, c.head_dim)
        if self.window is None:
            return CacheSpec(k=row, v=row)
        ring = ((self.window,) + row, None)
        return CacheSpec(state=(ring, ring), window=self.window,
                         cache=StateCache)

    def forward(self, x, cache=None, pos=None):
        u = self.input_layernorm(x)
        with jax.named_scope("attn"):
            a = self.self_attn(u, cache=cache, pos=pos)
        with jax.named_scope("mlp"):
            m = self.mlp(u)
        return x + a + m
