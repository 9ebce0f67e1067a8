"""Manifold-constrained hyper-connections: a residual path of ``n`` streams
mixed, per token and per sublayer, by coefficients computed from the
streams themselves (DeepSeek's mHC, arXiv 2512.24880).

A token's state between sublayers is ``X`` of ``n`` streams of ``C``
values, held FLAT and stream-major: ``x [..., n * C]`` is ``vec(X)`` and
stream ``i`` is ``x[..., i*C:(i+1)*C]`` (at ``C`` a multiple of 128 every
stream is a whole number of lanes, and ``vec(X)`` needs no copy).  Around a
sublayer ``F`` with parameters ``phi [n*C, 2n + n*n]``, ``offsets [2n +
n*n]`` (``b_pre | b_post | vec(B_res)``) and ``gains [3]`` (``a_pre,
a_post, a_res``), coefficients in float32::

    x~ = vec(X) / sqrt(mean(vec(X)^2) + eps);   [p | q | r] = x~ phi
    H_pre  = sigmoid(a_pre p + b_pre)                          [n]
    H_post = 2 sigmoid(a_post q + b_post)                      [n]
    M      = exp(clip(a_res mat(r) + B_res, lo, hi))           [n, n]
    iters times:  M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    H_res  = M                                  (doubly stochastic)
    u      = sum_i H_pre[i] X_i;   y = F(norm(u))
    X'_i   = sum_j H_res[i, j] X_j + H_post[i] y

``colsum`` sums over the first index (``sum_i M[i, j]``), ``rowsum`` over
the second.  The norm before ``phi`` has no learned scale (it folds into
``phi``).  Every iteration runs, whatever a tolerance would forgive.

The functions below are the XLA form and the oracle of whatever kernel
later sits behind them.  The Sinkhorn step keeps the TOKENS on the minor
axis (``M [n, n, T]``): sixteen vectors of ``T`` a sublayer fill the
lanes, where ``[T, n, n]`` would put four values on a lane row of 128.
Device scopes (the caller opens the outer ``mhc``): ``mhc_coeffs``,
``mhc_sinkhorn``, ``mhc_pre``, ``mhc_post``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .paged_attention import LaunchTelemetry


class Coefficients(NamedTuple):
    """What one sublayer's hyper-connection computed for its ``T`` tokens
    (every leading axis of the streams flattened), tokens on the MINOR
    axis: ``h_pre [n, T]``, ``h_post [n, T]``, ``h_res [n, n, T]``
    (float32), and the health of the Sinkhorn step: ``clamped`` (entries
    of the pre-``exp`` matrix that met the clamp, a float32 scalar) and
    ``residual`` (the largest ``|colsum - 1|`` after the last iteration)."""

    h_pre: jax.Array
    h_post: jax.Array
    h_res: jax.Array
    clamped: jax.Array
    residual: jax.Array


def pop_health(layers):
    """float32 ``[3]``: over the hyper-connections of the forward just run
    (``models/hc_moe_mla.py``) the entries of the pre-``exp`` matrices that
    met the clamp, the entries computed, and the largest ``|colsum - 1|`` a
    Sinkhorn step left; ``None`` without any.  Clears what the layers held."""
    found = []
    for layer in layers:
        for hc in (getattr(layer, "attn_hc", None),
                   getattr(layer, "mlp_hc", None)):
            if hc is not None and hc.health is not None:
                found.append(hc.health)
                hc.health = None
    if not found:
        return None
    h = jnp.stack(found)
    return jnp.stack([h[:, 0].sum(), h[:, 1].sum(), h[:, 2].max()])


class Health(LaunchTelemetry):
    """What layers on a residual path of several streams bring to a
    launch.  On ``engine.build``: ``hc_streams`` (how many).  Three floats
    ride the launch (:func:`pop_health`) and become on ``engine.fetch``:
    ``hc_res_clamped`` of ``hc_entries`` entries of the pre-exp
    residual-mixing matrices met the clamp (over sublayers, padding tokens
    included), ``hc_sinkhorn_residual_ppb`` the largest ``|column sum - 1|``
    left, in parts per billion (a phase carries integers); and two series."""

    def __init__(self, layers, view):
        super().__init__(layers, view)
        self.streams = int(layers[0].config.hc_mult)
        reg, labels = view.registry, view.labels
        self.clamped = reg.counter(
            "serving_hc_res_clamped_total", **labels,
            help="entries of the pre-exp residual-mixing matrices that met "
                 "the clamp, over sublayers and launches (padding tokens "
                 "included)")
        self.residual = reg.gauge(
            "serving_hc_sinkhorn_residual", **labels,
            help="last launch: the largest |column sum - 1| a sublayer's "
                 "residual-mixing matrix was left with after its Sinkhorn "
                 "rounds")

    def traced(self):
        return pop_health(self.layers)

    def build_ints(self, view, rows, reqs):
        return {"hc_streams": self.streams}

    def fetch_ints(self, program, hc):
        if hc is None:
            return {}
        clamped, entries, residual = (float(v) for v in np.asarray(hc))
        self.clamped.inc(int(clamped))
        self.residual.set(residual)
        return {"hc_res_clamped": int(clamped), "hc_entries": int(entries),
                "hc_sinkhorn_residual_ppb": int(round(residual * 1e9))}


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of column then row normalisation of the positive
    matrices ``m [n, n, T]`` (first index ``i``, second ``j``, a token a
    column of the last axis).  Unrolled: a ``while`` in a step program is
    an event the trace readers would have to tell from its body.  The
    compiler makes about four small fusions a round of this (936 in a
    six-layer prefill program, 0.5% of its device time on a v5e); written
    over the sixteen vectors with chains of additions it fuses them (12
    fusions a sublayer for 85) and moves no end-to-end number, but traces
    sixteen times the operations: warm set-up 207 s for 113, a cold one
    past the launcher's limit (my chip runs, PR 43), so this form stays."""
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def coefficients(x, phi, offsets, gains, n: int, iters: int,
                 norm_eps: float, hc_eps: float, clamp=(-30.0, 30.0)
                 ) -> Coefficients:
    """The coefficients of one sublayer for the streams ``x [..., n*C]``."""
    f32 = jnp.float32
    with jax.named_scope("mhc_coeffs"):
        xf = x.astype(f32)
        inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + norm_eps)
        # x~ phi = inv * (x phi): the product runs on the streams as they
        # are held (exact in float32 accumulation) and is scaled after
        proj = jnp.matmul(x, phi.astype(x.dtype),
                          preferred_element_type=f32) * inv
        proj = proj.reshape(-1, 2 * n + n * n).T            # [2n + n*n, T]
        offs, g = offsets.astype(f32)[:, None], gains.astype(f32)
        h_pre = jax.nn.sigmoid(g[0] * proj[:n] + offs[:n])
        h_post = 2.0 * jax.nn.sigmoid(g[1] * proj[n:2 * n] + offs[n:2 * n])
        raw = (g[2] * proj[2 * n:] + offs[2 * n:]).reshape(n, n, -1)
        lo, hi = float(clamp[0]), float(clamp[1])
        clamped = jnp.sum(((raw <= lo) | (raw >= hi)).astype(f32))
    with jax.named_scope("mhc_sinkhorn"):
        m = sinkhorn(jnp.exp(jnp.clip(raw, lo, hi)), iters, hc_eps)
        residual = jnp.max(jnp.abs(jnp.sum(m, axis=0) - 1.0))
    return Coefficients(h_pre, h_post, m, clamped, residual)


def _streams(x, n: int):
    c = x.shape[-1] // n
    return [x[..., i * c:(i + 1) * c] for i in range(n)]


def mix_in(x, h_pre):
    """``u = sum_i H_pre[i] X_i``: what the sublayer reads, ``[..., C]`` in
    the streams' type, accumulated in float32.  ``h_pre [n, T]``."""
    n = h_pre.shape[0]
    with jax.named_scope("mhc_pre"):
        flat = x.reshape(-1, x.shape[-1])
        u = sum(h_pre[i][:, None] * s.astype(jnp.float32)
                for i, s in enumerate(_streams(flat, n)))
        return u.astype(x.dtype).reshape(*x.shape[:-1], -1)


def mix_out(x, h_res, h_post, y):
    """``X'_i = sum_j H_res[i, j] X_j + H_post[i] y``, ``[..., n*C]`` in the
    streams' type, accumulated in float32.  ``h_res [n, n, T]``, ``h_post
    [n, T]``."""
    n = h_post.shape[0]
    with jax.named_scope("mhc_post"):
        xs = [s.astype(jnp.float32)
              for s in _streams(x.reshape(-1, x.shape[-1]), n)]
        yf = y.reshape(-1, y.shape[-1]).astype(jnp.float32)
        out = [sum(h_res[i, j][:, None] * xs[j] for j in range(n))
               + h_post[i][:, None] * yf for i in range(n)]
        return jnp.concatenate(out, axis=-1).astype(x.dtype).reshape(x.shape)


def expand(h, n: int):
    """The streams after the embedding: ``n`` copies of ``h [..., C]``."""
    return jnp.concatenate([h] * n, axis=-1)


def collapse(x, n: int):
    """The streams before the final norm: their sum, in float32."""
    return sum(s.astype(jnp.float32) for s in _streams(x, n)).astype(x.dtype)
