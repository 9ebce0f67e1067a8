"""Selective scan (the Mamba-1 recurrence) and the per-sequence state it
leaves, in plain ``jax.numpy``.

For a token ``t`` of one sequence, channel ``c`` of ``D`` and state index
``n`` of ``N``::

    x_t   = silu(sum_j w[j, c] * u[t - (K-1) + j, c] + b[c])    causal conv
    H_t   = exp(dt_t[c] * A[n, c]) * H_{t-1} + dt_t[c] * x_t[c] * B_t[n]
    y_t   = sum_n H_t[n, c] * C_t[n]

What a sequence holds after token ``t`` is ``H_t`` (float32, ``[N, D]``:
the channel axis is the minor one, so a TPU tile of 8 x 128 holds no
padding) and the last ``K - 1`` conv INPUTS ``u[t-K+2 .. t]``, flat as
``[(K-1) * D]`` in the pool's type.  Both live in slot pools the engine
allocates from the layer's declaration (``CacheSpec.state``) and hands to
the layer as a :class:`StateCache`.

Four paths, one mathematics:

* :func:`selective_scan` — over a padded bucket of positions, position by
  position with the state as the carry (no ``[T, N, D]`` intermediate: at
  4,096 x 5,120 x 16 float32 that is 1.3 GB).  A position at or past
  ``n_valid`` leaves the state as it was, so what is written is the state
  after the last REAL token.  ``h0`` carries a chunk's state in.
* :func:`selective_step` — one token a row, for decode, on states gathered
  from their slots (the caller scatters the new ones back): the oracle,
  and what a CPU run and an untileable width execute.
* ``pallas_ssm.state_step`` — the same step IN PLACE on the slot pool, one
  Pallas kernel: a decode launch on the chip at ``N % 8 == 0`` and
  ``D % 128 == 0`` (:func:`state_step_path` decides, through the one
  dispatch policy ``paged_attention.pallas_dispatch``; :data:`last_path`
  says which of the two a launch was traced through).  The gated delta
  rule's matrix state a head (``ops/gated_delta.py``) takes the same route
  to a kernel of its own, ``pallas_gated_delta.state_step``.
* :func:`conv_window` — the conv inputs to keep, cut at ``n_valid``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from .paged_attention import LaunchTelemetry

#: positions a compiled loop iteration advances (``lax.scan(unroll=)``):
#: the loop's fixed cost an iteration is what bounds a prefill scan in XLA
SCAN_UNROLL = 8


class StateCache:
    """Per-layer view of the per-sequence slot pools, handed to a mixer
    that keeps a recurrent state as its ``cache``: ``state_pool``
    (float32: ``[slots, N, D]`` of a selective scan, ``[slots, heads, d_k,
    d_v]`` of a gated delta rule, ``ops/gated_delta.py``) and ``conv_pool``
    ``[slots, (K-1) * D]`` are framework Tensors
    so the in-place update threads as jit state like a page write.
    ``slots`` ``[B]`` is each row's slot (0 = the null slot of padding
    rows).  In a prefill or chunk launch ``start`` is the absolute position
    of the first token (the state is read from the slot only when it is
    past 0) and ``n_valid`` the real tokens of the launch; both ``None`` in
    decode."""

    def __init__(self, state_pool, conv_pool):
        self.state_pool = state_pool
        self.conv_pool = conv_pool
        self.slots = None
        self.start = None
        self.n_valid = None
        self.use_pallas = None      # the engine's kernel routing hint
                                    # (``EngineConfig.use_pallas_paged``),
                                    # read by :func:`route_state_step` for
                                    # a decode launch: True forces the
                                    # in-place Pallas step (interpret mode
                                    # off the chip), False pins the XLA
                                    # gather / step / scatter, None leaves
                                    # it to the pool's shape and platform

    # the names the engine's step programs read the pools back by
    k_pool = property(lambda self: self.state_pool)
    v_pool = property(lambda self: self.conv_pool)

    @classmethod
    def over(cls, k, v):
        return cls(Tensor(k), Tensor(v))

    def route(self, tables, seq_lens=None, slot_blocks=None,
              slot_offsets=None, start=None, n_valid=None):
        """``PagedCache.route``'s arrays: a row's slot is the id of its
        first block (``kv_manager.py``; a padding row's the null slot 0)."""
        self.slots = jnp.asarray(tables[:, 0], jnp.int32)
        self.start = None if start is None else jnp.asarray(start, jnp.int32)
        self.n_valid = None if n_valid is None \
            else jnp.asarray(n_valid, jnp.int32)


class StateSlots(LaunchTelemetry):
    """What layers with per-sequence state bring to a launch.  On
    ``engine.build``: ``state_rows`` (the real rows whose state the launch
    advances) and ``state_slots_held``, also a gauge (as of the last launch
    read or row let go) beside the two that are set once."""

    def __init__(self, layers, view):
        super().__init__(layers, view)
        reg, labels = view.registry, view.labels
        self.held = reg.gauge(
            "serving_state_slots_held", **labels,
            help="per-sequence state slots held by running sequences")
        reg.gauge("serving_state_slots_capacity", **labels,
                  help="per-sequence state slots (max_num_seqs; the null "
                       "slot is not counted)").set(view.kv.state_slots)
        reg.gauge("serving_state_bytes_per_sequence", **labels,
                  help="bytes one live sequence holds in slots over all "
                       "layers, whatever its length, as the model declares "
                       "its state").set(sum(
                      layer.cache_spec().state_bytes_per_sequence(
                          view.pool_dtype) for layer in layers))

    def build_ints(self, view, rows, reqs):
        return {"state_rows": rows,
                "state_slots_held": view.kv.state_slots_held}

    def fetch_ints(self, program, host_array):
        self.held.set(self.view.kv.state_slots_held)
        return {}

    def forget(self, request_id):
        self.held.set(self.view.kv.state_slots_held)


# Which path the most recent launch's state update was traced through:
# "pallas" (the in-place kernel) | "xla".
last_path: Optional[str] = None


def state_step_path(state_shape, use_pallas, decode: bool = True) -> str:
    """``"pallas"`` where a launch over a state pool steps the state in its
    slot, ``"xla"`` where it gathers, steps (or scans) and scatters.  The
    kernels are DECODE steps at whole float32 tiles on a TPU backend: of
    the selective scan over ``[slots, N, D]`` (``pallas_ssm.state_step``;
    ``N % 8 == 0``, ``D % 128 == 0``) and of the gated delta rule over
    ``[slots, H, d_k, d_v]`` (``pallas_gated_delta.state_step``; ``d_k % 8
    == 0``, ``d_v % 128 == 0``).  The pool's SHAPE chooses;
    ``use_pallas`` forces or pins as everywhere (``paged_attention
    .pallas_dispatch``, whose kill switch wins)."""
    from .paged_attention import pallas_dispatch

    if not decode or len(state_shape) not in (3, 4):
        return "xla"
    sublanes, lanes = state_shape[-2:]
    tileable = (sublanes % 8 == 0 and lanes % 128 == 0
                and jax.default_backend() == "tpu")
    return pallas_dispatch(lambda: None, lambda: None, use_pallas,
                           tileable)[1]


def route_state_step(cache: StateCache, state_shape) -> str:
    """:func:`state_step_path` of the launch ``cache`` is routed for,
    published as :data:`last_path`."""
    global last_path
    last_path = state_step_path(state_shape, cache.use_pallas,
                                decode=cache.n_valid is None)
    return last_path


def causal_conv(u, window, w, b):
    """``silu(conv(u))`` in float32.  ``u`` ``[B, T, D]``; ``window``
    ``[B, K-1, D]`` the inputs before ``u`` (zeros before a sequence);
    ``w`` ``[K, D]``, ``b`` ``[D]`` or ``None``.  Returns ``(x [B, T, D]
    float32, padded [B, T+K-1, D])``, the second for :func:`conv_window`."""
    K, T = w.shape[0], u.shape[1]
    padded = jnp.concatenate([window.astype(u.dtype), u], axis=1)
    wf = w.astype(jnp.float32)
    acc = sum(padded[:, j:j + T].astype(jnp.float32) * wf[j] for j in range(K))
    if b is not None:
        acc = acc + b.astype(jnp.float32)
    return jax.nn.silu(acc), padded


def conv_window(padded, n_valid, K: int):
    """The ``K - 1`` inputs ending at the last real token: rows
    ``n_valid .. n_valid + K - 2`` of ``padded`` (which leads with the
    ``K - 1`` inputs before the launch).  ``n_valid`` is a scalar."""
    return jax.lax.dynamic_slice_in_dim(padded, n_valid, K - 1, axis=1)


def selective_scan(x, dt, A, Bm, Cm, h0, n_valid, unroll: int = SCAN_UNROLL):
    """``x``, ``dt`` ``[B, T, D]``, ``A`` ``[N, D]``, ``Bm``, ``Cm``
    ``[B, T, N]``, ``h0`` ``[B, N, D]``, all float32; ``n_valid`` a scalar.
    Returns ``(y [B, T, D], H after the first n_valid positions)``."""
    T = x.shape[1]

    def step(h, inp):
        x_t, dt_t, b_t, c_t, t = inp
        decay = jnp.exp(dt_t[:, None, :] * A[None])
        h_new = decay * h + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        y_t = jnp.sum(h_new * c_t[:, :, None], axis=1)
        return jnp.where(t < n_valid, h_new, h), y_t

    def tm(a):
        return jnp.moveaxis(a, 1, 0)

    h, y = jax.lax.scan(step, h0,
                        (tm(x), tm(dt), tm(Bm), tm(Cm), jnp.arange(T)),
                        unroll=min(unroll, T))
    return jnp.moveaxis(y, 0, 1), h


def selective_step(x, dt, A, Bm, Cm, h):
    """One position a row: ``x``, ``dt`` ``[B, D]``, ``Bm``, ``Cm``
    ``[B, N]``, ``h`` ``[B, N, D]``.  Returns ``(y [B, D], new h)``."""
    decay = jnp.exp(dt[:, None, :] * A[None])
    h = decay * h + (dt * x)[:, None, :] * Bm[:, :, None]
    return jnp.sum(h * Cm[:, :, None], axis=1), h
