"""Mixture-of-Experts with expert parallelism (EP).

Capability analog of the reference MoE stack:
``python/paddle/incubate/distributed/models/moe/moe_layer.py:263``
(``MoELayer``), gates under ``moe/gate/`` (naive/gshard/switch), capacity
pruning (``distributed/models/moe/utils.py:20-178``), and the
``global_scatter``/``global_gather`` all-to-all pair
(``python/paddle/distributed/utils/moe_utils.py:20,153``).

TPU-first: the GShard formulation — gating produces dense dispatch/combine
tensors and the token shuffle is two einsums over an expert-sharded buffer;
annotating the ``[E, C, H]`` buffer's E dim over the ``ep`` mesh axis makes
GSPMD emit the all-to-all over ICI (the reference's global_scatter/gather
NCCL calls).  Expert FFNs are *stacked* weights ``[E, H, FF]`` so every
expert's matmul is one big batched MXU contraction.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import run_op
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Constant, XavierNormal
from ..nn.layers import Layer
from ..ops import pallas_moe
from ..ops.paged_attention import LaunchTelemetry, pallas_dispatch
from .utils import annotate_param, axis_size, sharding_constraint

EP_AXIS = "sep"  # expert parallelism rides the sep axis of the 5-axis mesh


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def _topk_gating(logits, capacity, k, normalize=True):
    """Generic top-k gating with GShard capacity semantics (generalizes
    ``moe/gate/gshard_gate.py``): every token routes to its k highest-prob
    experts, all j-th choices take capacity slots before any (j+1)-th
    choice, and tokens beyond an expert's capacity are dropped.

    ``normalize=True`` renormalizes the surviving gate weights to sum 1
    (GShard / Mixtral ``norm_topk_prob``); ``normalize=False`` keeps the
    raw softmax probabilities (Switch top-1, DeepSeek-MoE, Qwen2-MoE).
    k=1 never renormalizes — a single surviving gate would be pinned to
    exactly 1.0, erasing the learned gate magnitude.
    logits: [T, E] float32.

    Fully vectorized over k (one ``lax.top_k`` + one cumsum over the
    [T, k, E] choice tensor — graph size constant in k; the k-unrolled
    argmax/cumsum formulation grew linearly and k=8 presets paid for it).
    The sequential "offset carries KEPT slots of higher-priority choices"
    rule has the closed form ``offset_j(e) = min(capacity,
    Σ_{j'<j} count_{j'}(e))``: round-j positions are contiguous from the
    running offset, so the kept count is ``min(capacity, offset+count) -
    offset`` and the recursion telescopes."""
    normalize = normalize and k > 1
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    # priority-ordered choices: idx[t, j] = token t's j-th expert
    vals, idx = jax.lax.top_k(probs, k)           # [T, k] each
    M = _one_hot(idx, E)                          # [T, k, E]

    # aux loss: mean(prob per expert) * mean(tokens top-1-routed) * E
    density = jnp.mean(M[:, 0, :], axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E

    # capacity accounting (see closed form above): all j-th choices take
    # slots before any (j+1)-th choice; within a round, token order
    counts = jnp.sum(M, axis=0)                   # [k, E] per-round totals
    before = jnp.cumsum(counts, axis=0) - counts  # exclusive prefix
    offset = jnp.minimum(capacity, before)        # [k, E] kept-slot offset
    p = (jnp.cumsum(M, axis=0) + offset[None]) * M - 1.0
    kept = M * (p < capacity)                     # [T, k, E]

    gates = vals * jnp.sum(kept, axis=-1)         # [T, k]; dropped -> 0
    if normalize:
        denom = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / jnp.where(denom > 0, denom, 1.0)

    pi = jnp.sum(p * kept, axis=-1).astype(jnp.int32)   # [T, k] slot index
    slot = _one_hot(pi, capacity)                       # [T, k, C]
    combine = jnp.einsum("tk,tke,tkc->tec", gates, kept, slot)
    dispatch = combine > 0.0
    return combine, dispatch, aux


class BaseGate(Layer):
    def __init__(self, d_model: int, num_experts: int):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.weight = self.create_parameter(
            [d_model, num_experts], default_initializer=XavierNormal())
        self.loss = None

    def logits(self, x):
        # gate math in f32 for routing stability (reference casts likewise)
        return run_op(
            "gate_logits",
            lambda v, w: jnp.matmul(v.astype(jnp.float32), w.astype(jnp.float32)),
            x, self.weight)


class TopKGate(BaseGate):
    """Generic top-k gate: k routed experts per token with GShard capacity
    semantics; ``normalize=False`` keeps raw softmax weights (DeepSeek-MoE
    / Qwen2-MoE ``norm_topk_prob=False``)."""

    def __init__(self, d_model: int, num_experts: int, k: int = 2,
                 normalize: bool = True):
        super().__init__(d_model, num_experts)
        if not 1 <= k <= num_experts:
            # k > E would silently re-select expert 0 once all experts
            # are masked out of the argmax loop
            raise ValueError(
                f"top-k {k} must be in [1, num_experts={num_experts}]")
        self.top_k = k
        self.normalize = normalize

    def gating(self, logits_val, capacity):
        return _topk_gating(logits_val, capacity, self.top_k, self.normalize)


class GShardGate(TopKGate):
    def __init__(self, d_model: int, num_experts: int):
        super().__init__(d_model, num_experts, k=2, normalize=True)


class SwitchGate(TopKGate):
    def __init__(self, d_model: int, num_experts: int):
        super().__init__(d_model, num_experts, k=1, normalize=False)


class NaiveGate(GShardGate):
    """top-2 without aux loss weighting (moe/gate/naive_gate.py)."""


class FusedMoEMLP(Layer):
    """Stacked-expert SwiGLU/GELU FFN: weights [E, H, FF] / [E, FF, H],
    E-dim sharded over the ``ep`` axis.  One einsum per projection keeps
    every expert on the MXU (the reference loops per-expert Linears)."""

    def __init__(self, num_experts: int, d_model: int, d_hidden: int,
                 activation: str = "gelu"):
        super().__init__()
        self.num_experts = num_experts
        self.activation = activation
        self.w_in = self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=XavierNormal())
        self.w_gate = (self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=XavierNormal())
            if activation == "swiglu" else None)
        self.w_out = self.create_parameter(
            [num_experts, d_hidden, d_model], default_initializer=XavierNormal())
        annotate_param(self.w_in, EP_AXIS, None, None)
        if self.w_gate is not None:
            annotate_param(self.w_gate, EP_AXIS, None, None)
        annotate_param(self.w_out, EP_AXIS, None, None)

    def forward(self, dispatched):  # [E, C, H]
        def f(x, w_in, w_out, *rest):
            h = jnp.einsum("ech,ehf->ecf", x, w_in.astype(x.dtype))
            if self.w_gate is not None:
                g = jnp.einsum("ech,ehf->ecf", x, rest[0].astype(x.dtype))
                h = jax.nn.silu(g) * h
            elif self.activation == "gelu":
                h = jax.nn.gelu(h)
            else:
                h = jax.nn.relu(h)
            return jnp.einsum("ecf,efh->ech", h, w_out.astype(x.dtype))

        args = [dispatched, self.w_in, self.w_out]
        if self.w_gate is not None:
            args.append(self.w_gate)
        return run_op("moe_experts", f, *args)


class MoELayer(Layer):
    """(``moe_layer.py:263`` analog) gate → dispatch einsum → expert-sharded
    FFN → combine einsum.  ``experts`` may be a :class:`FusedMoEMLP` (fast
    path) or a list of Layers (generic fallback, python loop over experts)."""

    def __init__(self, d_model: int, experts, gate: Optional[Layer] = None,
                 num_experts: Optional[int] = None, capacity_factor: float = 1.25,
                 moe_group=None, recompute_interval: int = 0):
        super().__init__()
        self.d_model = d_model
        if isinstance(experts, FusedMoEMLP):
            self.experts = experts
            self.num_experts = experts.num_experts
            self._fused = True
        else:
            from ..nn.container import LayerList

            self.experts = experts if isinstance(experts, LayerList) else LayerList(list(experts))
            self.num_experts = len(self.experts)
            self._fused = False
        self.gate = gate if gate is not None else GShardGate(d_model, self.num_experts)
        self.capacity_factor = capacity_factor
        self.aux_loss = None

    def forward(self, x):  # [B, S, H] or [T, H]
        orig_shape = x.shape
        hidden = orig_shape[-1]
        from .. import tensor as ops

        flat = ops.reshape(x, [-1, hidden])
        T = flat.shape[0]
        E = self.num_experts
        capacity = max(1, int(self.capacity_factor * self.gate.top_k * T / E))

        logits = self.gate.logits(flat)

        def gating(lv):
            combine, dispatch, aux = self.gate.gating(lv, capacity)
            return combine, aux

        combine, aux = run_op("moe_gating", gating, logits)
        self.aux_loss = aux
        self.gate.loss = aux

        def dispatch_fn(xv, cv):
            return jnp.einsum("tec,th->ech", (cv > 0).astype(xv.dtype), xv)

        dispatched = run_op("moe_dispatch", dispatch_fn, flat, combine)
        # E over ep → GSPMD all-to-all (global_scatter analog)
        dispatched = sharding_constraint(dispatched, EP_AXIS, None, None)

        if self._fused:
            expert_out = self.experts(dispatched)
        else:
            outs = []
            for e, expert in enumerate(self.experts):
                outs.append(expert(dispatched[e]))
            expert_out = ops.stack(outs, axis=0)
        expert_out = sharding_constraint(expert_out, EP_AXIS, None, None)

        def combine_fn(ov, cv):
            return jnp.einsum("ech,tec->th", ov, cv.astype(ov.dtype))

        out = run_op("moe_combine", combine_fn, expert_out, combine)
        return ops.reshape(out, orig_shape)


# --- routed experts that drop no token (the SERVED expert layer) -------------
#
# ``MoELayer`` above is the training layer: a ``[T, E, C]`` one-hot
# dispatch with a capacity, which DROPS the tokens past it, so a row's
# output depends on who shares its batch.  Serving cannot have that: the
# two functions below route without a capacity and compute every routed
# token, at a cost proportional to the tokens routed.

def sigmoid_topk_route(x, w_gate, select_bias, k: int, scale: float = 1.0,
                       normalize: bool = True):
    """``noaux_tc`` routing with one group: scores ``s = sigmoid(x W_g)``;
    the ``k`` experts of a token are the top ``k`` of ``s + select_bias``
    (the bias SELECTS, it does not weigh); their weights are ``s_i``,
    renormalised over the chosen (``normalize``) and times ``scale``.

    x ``[T, H]``, w_gate ``[H, E]``, select_bias ``[E]`` float32.  Returns
    ``(ids [T, k] int32, weights [T, k] float32)``.  The matmul and the
    scores are float32 at the highest matmul precision whatever ``x`` is:
    two scores a bf16 rounding apart pick another expert, and the output
    then differs by a whole expert's contribution.
    """
    logits = jnp.dot(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(s + select_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * scale


#: (token, expert) pairs up to which a share of the experts is computed in
#: one pass over ALL pairs, the absent ones a tail no group owns (a decode
#: launch: 32 rows x 8 = 256 pairs, two megabytes of rows)
ONE_PASS_PAIRS = 1024
#: and the most pairs one pass of the bounded path holds at a time
MAX_PAIR_CHUNK = 16384


def held_pair_chunk(pairs: int, n_held: int, num_experts: int) -> int:
    """Pairs a pass of the bounded path takes: twice what ``n_held`` of
    ``num_experts`` experts receive under uniform routing, as a power of
    two, so one pass is the rule and a second the exception."""
    want = max(2 * pairs * n_held // num_experts, 8)
    return min(1 << (want - 1).bit_length(), MAX_PAIR_CHUNK)


def clamped_swiglu(gate, up, limit):
    """``silu(min(gate, limit)) * clip(up, -limit, limit)``: the SwiGLU of
    a model that publishes ``swiglu_limit`` (the gate is bounded above, the
    linear branch on both sides)."""
    return jax.nn.silu(jnp.minimum(gate, limit)) * jnp.clip(up, -limit, limit)


# Which implementation the most recent :func:`_grouped_swiglu` was traced
# with: "pallas" (``ops.pallas_moe.grouped_matmul``) | "xla" (``ragged_dot``),
# and the (token, expert) pairs of the :func:`dropless_experts` around it.
last_path: Optional[str] = None
last_pairs: int = 0


def _grouped_swiglu(rows, w_gate_up, w_down, sizes, limit=None):
    """``E_g(row)`` for rows sorted by group, ``sizes`` rows a group; rows
    past the last group are whatever the grouped matmul leaves there.
    ``limit``: :func:`clamped_swiglu`'s (``None``: no clamp).

    The two grouped products have two implementations, chosen by what the
    program can see (``ops.pallas_moe.streams`` through ``paged_attention
    .pallas_dispatch``, whose kill switch wins; published as
    :data:`last_path`): on a TPU, at whole lane tiles and up to
    ``pallas_moe.STREAM_ROWS_PER_EXPERT`` static rows a held expert (a
    decode launch: a handful of rows a group, bound by reading the weights)
    ``pallas_moe.grouped_matmul``, which reads each touched expert's weights
    once; above that, and off the TPU, ``jax.lax.ragged_dot``."""
    global last_path
    f = w_down.shape[1]

    def both(product):
        h = product(rows, w_gate_up, sizes)
        if limit is None:
            h = jax.nn.silu(h[:, :f]) * h[:, f:]
        else:
            h = clamped_swiglu(h[:, :f], h[:, f:], limit)
        return product(h, w_down, sizes)

    with jax.named_scope("moe_experts"):
        out, last_path = pallas_dispatch(
            lambda: both(pallas_moe.grouped_matmul),
            lambda: both(jax.lax.ragged_dot), None,
            pallas_moe.streams(rows, w_gate_up, w_down))
    return out


def dropless_experts(x, ids, weights, w_gate_up, w_down, num_experts: int,
                     held=None, limit=None):
    """``out[t] = sum_j weights[t, j] * E_{ids[t, j]}(x[t])`` over the
    experts THIS process holds, with ``E(x) = W_down(silu(W_gate x) * W_up
    x)`` (under ``limit`` :func:`clamped_swiglu`'s product): the step's (token, expert) pairs are sorted by expert and each
    expert multiplies only the rows routed to it (``jax.lax.ragged_dot``
    over the stacked weights).  No capacity: every routed token is
    computed, so a token's result does not depend on the rest of the batch.

    x ``[T, H]``; ids / weights ``[T, k]``; w_gate_up ``[E_held, H, 2 F]``
    (gate columns, then up); w_down ``[E_held, F, H]``; ``held`` the ids
    (into ``num_experts``) of the stacked experts in order, ``None`` = all.
    A pair routed to an expert that is not held adds nothing here (another
    chip's share).  Returns ``(out [T, H], load [num_experts] int32)``,
    ``load`` the pairs each expert received, held or not.

    With every expert held, and with a share of them up to
    ``ONE_PASS_PAIRS`` pairs, all ``T k`` pairs are gathered and the absent
    ones are a tail of the grouped matmul that no group owns.  Above that a
    share is computed by :func:`_held_pairs_in_chunks`: the work follows the
    pairs that ARE held (:func:`held_pair_chunk` pairs a pass), exactly,
    whatever the routing.

    Scopes: ``moe_dispatch`` (sort and gather), ``moe_experts`` (the
    grouped matmuls, :func:`_grouped_swiglu`: ``ops.pallas_moe``'s kernel
    up to ``pallas_moe.STREAM_ROWS_PER_EXPERT`` static rows a held expert
    on a TPU, ``ragged_dot`` above and elsewhere), ``moe_combine`` (weigh
    and sum per token)."""
    global last_pairs
    T, k = ids.shape
    last_pairs = T * k
    n_held = w_gate_up.shape[0]
    all_held = held is None or tuple(held) == tuple(range(num_experts))
    chunk = T * k
    if not all_held and T * k > ONE_PASS_PAIRS:
        chunk = held_pair_chunk(T * k, n_held, num_experts)
    in_chunks = chunk < T * k
    with jax.named_scope("moe_dispatch"):
        flat_ids = ids.reshape(-1)
        load = jnp.bincount(flat_ids, length=num_experts).astype(jnp.int32)
        if all_held:
            local = flat_ids
        else:
            lut = np.full((num_experts,), n_held, np.int32)
            lut[np.asarray(held)] = np.arange(n_held, dtype=np.int32)
            local = jnp.asarray(lut)[flat_ids]      # n_held = not here
        order = jnp.argsort(local, stable=True)
        if not in_chunks:
            token = order // k
            rows = x[token]
        sizes = jnp.bincount(local, length=n_held + 1)[:n_held] \
            .astype(jnp.int32)
    if in_chunks:
        out = _held_pairs_in_chunks(x, weights.reshape(-1), order, sizes,
                                    w_gate_up, w_down, limit, k, chunk)
        return out.astype(x.dtype), load
    y = _grouped_swiglu(rows, w_gate_up, w_down, sizes, limit)
    with jax.named_scope("moe_combine"):
        w = jnp.where(local < n_held, weights.reshape(-1), 0.0)[order]
        # rows past the last group (pairs of experts not held) are whatever
        # the grouped matmul left there: select, do not multiply by 0
        y = jnp.where(w[:, None] != 0, y.astype(jnp.float32) * w[:, None], 0.0)
        out = y[jnp.argsort(order)].reshape(T, k, -1).sum(axis=1)
    return out.astype(x.dtype), load


def _held_pairs_in_chunks(x, flat_w, order, sizes, w_gate_up, w_down, limit,
                          k: int, chunk: int):
    """The held pairs alone, ``chunk`` of them a pass: ``order`` lists the
    pairs sorted by local expert id, the held ones first, so pass ``i``
    takes ``order[i chunk : (i + 1) chunk]``, gathers THOSE rows of ``x``,
    gives each expert the part of its group that falls in the pass, and
    adds the weighted results to their tokens.  As many passes as the held
    pairs need (a ``while`` in the program), none for the absent ones.
    ``limit`` is :func:`_grouped_swiglu`'s.
    Returns ``[T, H]`` float32."""
    T, H = x.shape
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    n_pairs = ends[-1]
    pad = -order.shape[0] % chunk
    order = jnp.pad(order, (0, pad))
    lane = jnp.arange(chunk, dtype=jnp.int32)

    def one_pass(i, out):
        lo = i * chunk
        with jax.named_scope("moe_dispatch"):
            pair = jax.lax.dynamic_slice_in_dim(order, lo, chunk)
            token = pair // k
            rows = x[token]
            part = (jnp.clip(ends, lo, lo + chunk)
                    - jnp.clip(starts, lo, lo + chunk)).astype(jnp.int32)
        y = _grouped_swiglu(rows, w_gate_up, w_down, part, limit)
        with jax.named_scope("moe_combine"):
            w = jnp.where(lo + lane < n_pairs, flat_w[pair], 0.0)
            y = jnp.where(w[:, None] != 0,
                          y.astype(jnp.float32) * w[:, None], 0.0)
            return out.at[token].add(y)

    passes = (n_pairs + chunk - 1) // chunk
    return jax.lax.fori_loop(0, passes, one_pass,
                             jnp.zeros((T, H), jnp.float32))


def global_scatter(x: Tensor, local_count, global_count, group=None) -> Tensor:
    """``distributed/utils/moe_utils.py:20`` analog — explicit all-to-all for
    shard_map code paths (GSPMD handles the jit path automatically)."""
    return run_op(
        "global_scatter",
        lambda v: jax.lax.all_to_all(v, EP_AXIS, split_axis=0, concat_axis=0),
        x,
    )


def global_gather(x: Tensor, local_count, global_count, group=None) -> Tensor:
    """``moe_utils.py:153`` analog (inverse all-to-all)."""
    return run_op(
        "global_gather",
        lambda v: jax.lax.all_to_all(v, EP_AXIS, split_axis=0, concat_axis=0),
        x,
    )


def pop_load(layers):
    """``[expert layers, experts]`` int32: the tokens each routed expert
    received in the forward just run (``mlp.load`` of the layers that have
    one), or ``None``.  Clears what the layers held."""
    loads = []
    for layer in layers:
        load = getattr(layer.mlp, "load", None)
        if load is not None:
            loads.append(load)
            layer.mlp.load = None
    return jnp.stack(loads) if loads else None


class ExpertLoad(LaunchTelemetry):
    """What layers with routed experts bring to a launch: the routing load
    (:func:`pop_load`, a few hundred integers) rides it and becomes on
    ``engine.fetch`` ``moe_assignments`` ((token, expert) pairs routed,
    padding rows included), ``moe_experts_touched``, ``moe_max_load`` (the
    fullest expert's tokens, summed over expert layers), ``moe_decode`` (1
    on a decode launch), ``moe_streamed`` (1 where the program's expert
    layers were traced through ``ops.pallas_moe``'s kernel: :data:`last_path`
    as of that trace); where this process holds a SHARE of the experts
    (the configuration's ``experts_held``) also ``moe_pairs_held`` (pairs
    routed to them) and ``moe_held_touched``; and the ``serving_moe_*``
    series below."""

    def __init__(self, layers, view):
        super().__init__(layers, view)
        held = getattr(layers[0].config, "experts_held", None)
        self.held = None if held is None else np.asarray(held, int)
        reg, labels = view.registry, view.labels
        # pairs a layer -> the path a program of that many was traced
        # through (the rule reads shapes: buckets fall on both its sides)
        self._paths: dict = {}
        self.counters = {
            "streamed": reg.counter(
                "serving_moe_streamed_launches_total", **labels,
                help="launches whose routed experts ran the kernel that "
                     "reads each touched expert's weights once "
                     "(ops.pallas_moe), not ragged_dot"),
            "assignments": reg.counter(
                "serving_moe_assignments_total", **labels,
                help="(token, expert) pairs routed, over expert layers and "
                     "launches (padding rows included)"),
            "touched": reg.counter(
                "serving_moe_experts_touched_total", **labels,
                help="experts that received a token, summed over expert "
                     "layers and launches"),
            "max_over_mean": reg.gauge(
                "serving_moe_load_max_over_mean", **labels,
                help="last launch: the fullest expert's tokens over the mean "
                     "expert's, averaged over expert layers (1.0 = balanced)")}
        if self.held is not None:
            self.counters.update(
                pairs_held=reg.counter(
                    "serving_moe_pairs_held_total", **labels,
                    help="(token, expert) pairs routed to an expert this "
                         "process holds, over expert layers and launches "
                         "(the rest are another chip's)"),
                held_share=reg.gauge(
                    "serving_moe_held_pair_share", **labels,
                    help="pairs routed to experts held here over all pairs "
                         "routed, since the start"))

    def traced(self):
        load = pop_load(self.layers)
        if load is not None:
            self._paths[last_pairs] = last_path
        return load

    def fetch_ints(self, program, load):
        if load is None:
            return {}
        load = np.asarray(load)
        assignments = int(load.sum())
        touched = int(np.count_nonzero(load))
        max_load = int(load.max(axis=1).sum())
        streamed = int(self._paths.get(int(load[0].sum())) == "pallas")
        c = self.counters
        c["streamed"].inc(streamed)
        c["assignments"].inc(assignments)
        c["touched"].inc(touched)
        if assignments:
            c["max_over_mean"].set(max_load * load.shape[1] / assignments)
        ints = {"moe_assignments": assignments,
                "moe_experts_touched": touched, "moe_max_load": max_load,
                "moe_decode": int(program == "decode"),
                "moe_streamed": streamed}
        if self.held is not None:
            # a share of the experts: the pairs that are this chip's, and
            # how many of its experts a pair reached
            mine = load[:, self.held]
            ints["moe_pairs_held"] = int(mine.sum())
            ints["moe_held_touched"] = int(np.count_nonzero(mine))
            c["pairs_held"].inc(ints["moe_pairs_held"])
            if c["assignments"].value:
                c["held_share"].set(c["pairs_held"].value
                                    / c["assignments"].value)
        return ints
