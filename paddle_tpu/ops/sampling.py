"""In-trace per-row token sampling (ISSUE 18).

The sampling reduction that turns a ``[rows, vocab]`` logits block into
``[rows]`` token ids **inside** the traced step program, so the host
fetches token ids only — stage (1) of the MPK-style device-resident
decode loop (PAPERS.md #5).  Sits next to the PR 9 logit-stats
reductions: both are cheap row-wise epilogues fused into the step
program's tail, adding no new program family and no new bucket axes.

Design constraints the serving layer relies on:

* **Greedy is the temperature==0 row of the same program.**  Every row
  carries its own ``(temperature, top_k, top_p, key)`` quartet; rows
  with ``temperature <= 0`` reduce to a pure argmax, bit-identical to
  the pre-ISSUE-18 host argmax.  One compiled program serves greedy and
  sampled batches — bucket sets and trace counts are unchanged.
* **A launch pays for the filter pipeline only if one of its rows
  samples.**  The full-vocabulary sort, the top-k / top-p masks and the
  Gumbel hash sit in one branch of a ``lax.cond`` on
  ``any(temperature > 0)``, decided in the trace from the launch's own
  temperatures: a launch of greedy rows (padding rows are temperature
  0) runs one argmax over the vocabulary and nothing else, a launch
  with a sampling row runs the whole pipeline for every row.  Tokens
  are the same either way, row for row.
* **Determinism under seed via counter-keyed Gumbel-max.**  The key for
  a draw is the raw u32 pair ``(seed, draw_index)`` (the request's
  output position) — a pure function of request state, NOT of engine
  step boundaries.  Preemption-recompute, dp placement, spec-decode
  verify packing and server-vs-offline all replay the identical key
  sequence, so the sampled stream is identical everywhere.  The noise
  itself is a counter-based integer-mix hash (murmur3 finalizer chain
  over ``(seed, draw, vocab lane)``), not threefry: the sampling
  epilogue is fused into EVERY bucketed step program, and a threefry
  lowering costs ~0.2s of XLA compile per program where the elementwise
  mix is free.  Gumbel-max only needs iid uniforms per lane; a
  full-avalanche hash of a unique counter triple is exactly that.
* **Filter pipeline order matches the host reference**
  (:meth:`~paddle_tpu.serving.request.SamplingParams.sample`):
  temperature scale -> top-k mask -> top-p nucleus mask -> draw.
  Gumbel-max over the masked scaled logits is distribution-identical to
  softmax-then-categorical, but needs no normalization and stays a pure
  ``argmax`` reduction on device.
* **top_p ∈ (0, 1] can never empty the distribution**: the max-prob
  token's cumsum entry is the first one compared against ``top_p``, so
  it always survives the nucleus cut (``top_p == 1.0`` keeps all).
  ``top_k <= 0`` means "no top-k filter" (protocol validates ``>= 0``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# mask value: finite, so argmax ties stay sane.  A numpy scalar: a jnp
# scalar here would dispatch at import and initialise a JAX backend in
# every process that imports the package (one process per chip)
_NEG = np.float32(-1e30)


def _fmix32(z):
    """murmur3 32-bit finalizer — full avalanche, pure elementwise u32
    ops (wrap-around mul), so it lowers to a handful of instructions."""
    z = z ^ (z >> jnp.uint32(16))
    z = z * jnp.uint32(0x85EBCA6B)
    z = z ^ (z >> jnp.uint32(13))
    z = z * jnp.uint32(0xC2B2AE35)
    z = z ^ (z >> jnp.uint32(16))
    return z


def _gumbel_from_keys(keys, V):
    """``[R, V]`` Gumbel noise from raw ``[R, 2]`` (seed, draw) u32 keys:
    hash the (seed, draw, lane) counter triple through a chained
    avalanche, map the top 24 bits to a strictly-interior uniform, and
    apply the double-log Gumbel transform."""
    seed = keys[:, 0:1]
    draw = keys[:, 1:2]
    lane = jnp.arange(V, dtype=jnp.uint32)[None, :]
    h = _fmix32(lane ^ _fmix32(draw ^ _fmix32(seed ^ jnp.uint32(0x9E3779B9))))
    # top 24 bits -> u in (0, 1) strictly (the +0.5 keeps log() finite)
    u = ((h >> jnp.uint32(8)).astype(jnp.float32) + 0.5) * jnp.float32(
        1.0 / (1 << 24))
    return -jnp.log(-jnp.log(u))


def make_keys(seed_draws, out=None):
    """Pack ``[(seed, draw_index), ...]`` into the raw ``[n, 2]`` u32 key
    array :func:`sample_tokens` consumes (host-side helper, numpy-free of
    jax so schedulers can call it without touching the device)."""
    n = len(seed_draws)
    keys = np.zeros((n, 2), dtype=np.uint32) if out is None else out
    for i, (seed, draw) in enumerate(seed_draws):
        keys[i, 0] = np.uint32(seed & 0xFFFFFFFF)
        keys[i, 1] = np.uint32(draw & 0xFFFFFFFF)
    return keys


def _draw_tokens(x32, greedy, temps, top_ks, top_ps, keys):
    """The filter pipeline and the Gumbel-max draw over ``[R, V]`` f32
    logits: the branch of :func:`sample_tokens` a launch takes when a
    row of it samples.  Greedy rows of such a launch keep ``greedy``."""
    V = x32.shape[-1]
    x = x32 / jnp.maximum(temps[:, None], 1e-6)

    # top-k: mask everything below the k-th largest scaled logit.
    # k_eff == V when the filter is off, so the mask is a no-op then.
    sorted_desc = -jnp.sort(-x, axis=-1)
    k_eff = jnp.where(top_ks <= 0, V, jnp.minimum(top_ks, V))
    kth = jnp.take_along_axis(
        sorted_desc, (k_eff - 1).astype(jnp.int32)[:, None], axis=-1)
    x = jnp.where(x < kth, _NEG, x)

    # top-p: smallest prob mass >= top_p over the top-k-filtered dist.
    # The descending prob vector is softmax of the DESCENDING masked
    # logits (softmax is order-preserving), so the one sort above is
    # reused instead of sorting the probs again — the epilogue is fused
    # into every bucketed step program and each sort lowering is paid
    # per program.
    # unnormalized mass suffices: softmax's denominator cancels out of
    # ``csum/total >= top_p``, and thresholding against the ACTUAL total
    # (instead of a literal 1.0) keeps top_p == 1.0 from collapsing to
    # greedy when f32 rounding lands the full sum at 0.99999994
    sorted_masked = jnp.where(sorted_desc < kth, _NEG, sorted_desc)
    e = jnp.exp(sorted_masked - sorted_masked[:, 0:1])
    csum = jnp.cumsum(e, axis=-1)
    cut = jnp.argmax(csum >= top_ps[:, None] * csum[:, -1:], axis=-1)
    # cut back in LOGIT space: ``sorted_masked`` holds the same bits as
    # ``x`` (a sort is a permutation), so the comparison can never mask
    # the cut token itself — thresholding on a re-softmaxed prob vector
    # can, because the two softmax denominators sum in different orders
    # and drift a ulp apart, emptying the whole row
    pth = jnp.take_along_axis(sorted_masked, cut[:, None], axis=-1)
    x = jnp.where(x < pth, _NEG, x)

    # Gumbel-max draw, keyed per row by the raw (seed, draw_index) data.
    g = _gumbel_from_keys(keys, V)
    sampled = jnp.argmax(x + g, axis=-1).astype(jnp.int32)

    return jnp.where(temps <= 0.0, greedy, sampled)


@jax.named_scope("sampler")
def sample_tokens(logits, temps, top_ks, top_ps, keys):
    """Sample one token per row, in-trace.  Everything here runs under
    the ``sampler`` scope: the name the device trace knows the whole
    epilogue by (its sort, its masks, its draw), metadata only.

    Args:
      logits: ``[R, V]`` float (any float dtype; upcast to f32).
      temps:  ``[R]`` f32 — ``<= 0`` means greedy (pure argmax).
      top_ks: ``[R]`` i32 — ``<= 0`` means no top-k filter.
      top_ps: ``[R]`` f32 — nucleus mass in ``(0, 1]``; ``1.0`` = off.
      keys:   ``[R, 2]`` u32 — raw ``(seed, draw_index)`` PRNG key data.

    Returns:
      ``[R]`` i32 token ids.
    """
    x32 = logits.astype(jnp.float32)
    greedy = jnp.argmax(x32, axis=-1).astype(jnp.int32)
    # the sort is the second-largest device operation of a greedy decode
    # launch at a 100k vocabulary (PERF.md, PR 28), and greedy rows throw
    # its result away: only a launch with a sampling row runs it
    return jax.lax.cond(
        jnp.any(temps > 0.0), _draw_tokens,
        lambda x32, greedy, *quartet: greedy,
        x32, greedy, temps, top_ks, top_ps, keys)
