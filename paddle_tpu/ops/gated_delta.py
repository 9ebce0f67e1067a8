"""The gated delta rule (Yang, Kautz and Hatamizadeh, "Gated Delta
Networks", arXiv:2412.06464) and the per-sequence state it leaves, in plain
``jax.numpy``.

For a token ``t`` of one sequence and one value head, with a key ``k_t``
and a query ``q_t`` of ``d_k`` values (both L2-normalised by the caller,
the query also scaled by ``1 / sqrt(d_k)``), a value ``v_t`` of ``d_v``, a
decay ``alpha_t`` in (0, 1] and a writing strength ``beta_t`` in [0, 1]::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t                                    S in R^{d_k x d_v}

decay, READ what the decayed state holds for ``k_t``, write the correction.
What a sequence holds after token ``t`` is ``S_t`` of every value head
(float32, ``[heads, d_k, d_v]``) and the last ``K - 1`` inputs of the short
convolution before it (``selective_scan.causal_conv`` / ``conv_window``),
in slot pools the engine allocates from the layer's declaration
(``CacheSpec.state``) and hands over as a
:class:`~paddle_tpu.ops.selective_scan.StateCache`.

Three forms, one mathematics:

* :func:`gated_delta_step` -- one token a row, on states GATHERED from
  their slots (the caller scatters the new ones back): the decode step of
  a CPU run and of an untileable width, and under
  :func:`gated_delta_recurrence` (a ``lax.scan`` of it) the ORACLE.  The
  two products with the state are elementwise multiplies and sums in
  float32 (a step is bound by the state's bytes, not by operations), so the
  state is never rounded.  ``pallas_gated_delta.state_step`` is the same
  step IN PLACE on the slot pool, one Pallas kernel: a decode launch on the
  chip at ``d_k % 8 == 0`` and ``d_v % 128 == 0``
  (``selective_scan.state_step_path`` decides from the pool's shape).
* :func:`gated_delta_chunked` -- a prompt, in chunks of ``chunk`` tokens.
  With ``g_i`` the running sum of ``log alpha`` inside a chunk and ``u_i =
  beta_i (v_i - alpha_i S_{i-1}^T k_i)`` the row a token WRITES, the rule
  unrolls to ``S_i = e^{g_i} S_0 + sum_{j<=i} e^{g_i - g_j} k_j u_j^T``, and
  the ``u`` of a chunk solve one unit-lower-triangular system::

      (I + A) U = beta (V - e^g K S_0),   A_ij = beta_i e^{g_i-g_j} k_i.k_j  (j < i)

  whose two right-hand sides do not depend on the state: ``U' = (I+A)^{-1}
  beta V`` and ``W = (I+A)^{-1} beta e^g K`` are made for every chunk at
  once, and ONE ``lax.scan`` carries the state from chunk to chunk with
  three products a chunk (``U = U' - W S``, ``O = e^g Q S + (Q K^T . D) U``,
  ``S <- e^{g_C} S + (e^{g_C - g} K)^T U``).  A 1,024-token prompt is 16
  dependent steps a layer, not 1,024.  Every product is float32 at the
  HIGHEST matmul precision: a TPU's default rounds both factors to bf16,
  and a state that is rounded every chunk is not the float32 state the
  layer declares.
* the carried form is the same function started from a slot's state
  (``S0``), for a chunk past position 0.

A position at or past ``n_valid`` is INERT (``beta = 0``, ``alpha = 1``):
it writes nothing and decays nothing, so what is written to the slot is
the state after the last REAL token of a padded bucket.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: tokens a chunk of :func:`gated_delta_chunked` takes
CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def l2_normalize(x, eps: float = 1e-6):
    """``x / ||x||`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gates(b, a, a_log, dt_bias):
    """``(beta, log alpha)`` of ``b``, ``a`` ``[..., heads]``: ``beta =
    sigmoid(b)``, ``log alpha = -exp(A_log) softplus(a + dt_bias)``."""
    f32 = jnp.float32
    beta = jax.nn.sigmoid(b.astype(f32))
    log_alpha = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32))
    return beta, log_alpha


def gated_delta_step(q, k, v, log_alpha, beta, S):
    """One token a row.  ``q``, ``k`` ``[B, H, d_k]``, ``v`` ``[B, H,
    d_v]``, ``log_alpha``, ``beta`` ``[B, H]``, ``S`` ``[B, H, d_k, d_v]``,
    all float32.  Returns ``(o [B, H, d_v], new S)``."""
    alpha = jnp.exp(log_alpha)[..., None]
    # what the DECAYED state holds for k, read before the write; the decay
    # is applied to the product, so the state is passed over once to read
    # and once to write and no decayed copy of it is made
    held = alpha * jnp.sum(S * k[..., :, None], axis=-2)
    u = beta[..., None] * (v - held)
    S = alpha[..., None] * S + k[..., :, None] * u[..., None, :]
    return jnp.sum(S * q[..., :, None], axis=-2), S


def gated_delta_recurrence(q, k, v, log_alpha, beta, S0, n_valid=None):
    """Token by token over ``[B, T, H, ...]``: the oracle of the chunked
    form.  Returns ``(o [B, T, H, d_v], S after the first n_valid tokens)``."""
    T = q.shape[1]

    def step(S, inp):
        q_t, k_t, v_t, la_t, b_t, t = inp
        o, S_new = gated_delta_step(q_t, k_t, v_t, la_t, b_t, S)
        if n_valid is not None:
            S_new = jnp.where(t < n_valid, S_new, S)
        return S_new, o

    def tm(a):
        return jnp.moveaxis(a, 1, 0)

    S, o = jax.lax.scan(step, S0, (tm(q), tm(k), tm(v), tm(log_alpha),
                                   tm(beta), jnp.arange(T)))
    return jnp.moveaxis(o, 0, 1), S


def gated_delta_chunked(q, k, v, log_alpha, beta, S0,
                        n_valid: Optional[jax.Array] = None,
                        chunk: int = CHUNK):
    """A prompt in chunks (module docstring).  ``q``, ``k`` ``[B, T, H,
    d_k]``, ``v`` ``[B, T, H, d_v]``, ``log_alpha``, ``beta`` ``[B, T, H]``,
    ``S0`` ``[B, H, d_k, d_v]``, float32; ``n_valid`` a scalar (``None``:
    every position is real); ``T`` any length (a last partial chunk is
    padded with inert positions).  Returns ``(o [B, T, H, d_v], S after the
    first n_valid positions)``."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    if n_valid is not None:
        real = (jnp.arange(T) < n_valid)[None, :, None]
        beta = jnp.where(real, beta, 0.0)
        log_alpha = jnp.where(real, log_alpha, 0.0)
    pad = -T % chunk
    n = (T + pad) // chunk

    def chunks(a):
        """``[B, T, H, ...]`` -> ``[n, B, H, chunk, ...]``."""
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, n, chunk) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g = jnp.cumsum(chunks(log_alpha), axis=-1)          # [n, B, H, C]
    beta = chunks(beta)
    i = jnp.arange(chunk)
    lower = i[:, None] >= i[None, :]
    # e^{g_i - g_j} for j <= i: masked BEFORE the exponential, which
    # overflows above the diagonal
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", k, k, precision=_HI)
    A = jnp.where(i[:, None] > i[None, :],
                  beta[..., :, None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(g))[..., None] * k], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(chunk, dtype=A.dtype), rhs, lower=True,
        unit_diagonal=True)
    u0, w = sol[..., :dv], sol[..., dv:]
    qk = jnp.einsum("...id,...jd->...ij", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(g)[..., None]                    # reads the carried state
    g_end = g[..., -1:]
    k_out = k * jnp.exp(g_end - g)[..., None]           # decayed to the chunk's end

    def one(S, c):
        u0_c, w_c, qk_c, q_c, k_c, ge_c = c
        u = u0_c - jnp.einsum("bhck,bhkv->bhcv", w_c, S, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", q_c, S, precision=_HI) \
            + jnp.einsum("bhcj,bhjv->bhcv", qk_c, u, precision=_HI)
        S = jnp.exp(ge_c)[..., None] * S \
            + jnp.einsum("bhck,bhcv->bhkv", k_c, u, precision=_HI)
        return S, o

    S, o = jax.lax.scan(one, S0, (u0, w, qk, q_in, k_out, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)       # [B, n, C, H, dv]
    return o.reshape(B, n * chunk, H, dv)[:, :T], S
