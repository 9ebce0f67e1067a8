"""Pipeline parallelism over the ``pp`` mesh axis.

Capability analog of the reference PP stack:
``PipelineLayer`` desc-based model split
(``fleet/meta_parallel/parallel_layers/pp_layers.py:261``, ``LayerDesc:56``,
``SharedLayerDesc:76``), the 1F1B runtime
(``fleet/meta_parallel/pipeline_parallel.py:150``, schedule loop
``forward_backward_pipeline:440``) and batched p2p
(``pp_utils/p2p_communication.py:313``).

TPU-first: instead of an actor runtime exchanging NCCL p2p messages per
microbatch, the whole schedule is ONE traced SPMD program (SURVEY.md §7 hard
part (a)): decoder blocks are *stacked* ``[n_stages, layers_per_stage, ...]``
with the stage dim sharded over ``pp``; a ``shard_map`` loop circulates
microbatch activations with ``collective-permute`` over ICI.  The forward
schedule is GPipe-style (fill → steady → drain); because every primitive is
differentiable, ``jax.grad`` of the loop IS the backward pipeline (XLA
reverses the ppermutes), and per-tick ``jax.checkpoint`` bounds activation
memory the way 1F1B's eager-release does.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.dispatch import mark_derived, mark_inputs, run_op
from ..core.tensor import Tensor
from ..distributed import topology
from ..nn.layers import Layer
from .utils import manual_sharding_mode

PP_AXIS = "pp"


# --------------------------------------------------------------------------
# Descriptor API (pp_layers.py analog)
# --------------------------------------------------------------------------

class LayerDesc:
    """Deferred layer construction (``pp_layers.py:56``)."""

    def __init__(self, layer_cls, *args, **kwargs):
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build_layer(self) -> Layer:
        return self.layer_cls(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """Layer shared between stages, e.g. tied embeddings
    (``pp_layers.py:76``).  Single-controller: one instance, weight tying is
    object identity — no cross-stage allreduce needed."""

    def __init__(self, key, layer_cls, forward_func=None, shared_weight_attr="weight",
                 *args, **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer(Layer):
    """Desc-list model container partitioned into pp stages
    (``pp_layers.py:261``).  Segmentation is uniform-by-layer-count
    (``seg_method='uniform'``) or regex-balanced like the reference."""

    def __init__(self, layers: Sequence, num_stages: Optional[int] = None,
                 topology=None, loss_fn=None, seg_method="uniform",
                 recompute_interval: int = 0,
                 num_virtual_pipeline_stages: Optional[int] = None, **kwargs):
        super().__init__()
        from ..nn.container import LayerList

        self._descs = list(layers)
        self.num_virtual_stages = num_virtual_pipeline_stages or 1
        self.num_stages = (num_stages or _pp_degree()) * self.num_virtual_stages
        self.loss_fn = loss_fn
        self.recompute_interval = recompute_interval
        self._shared: dict = {}

        built: List[Layer] = []
        for d in self._descs:
            if isinstance(d, SharedLayerDesc):
                if d.layer_name not in self._shared:
                    self._shared[d.layer_name] = (d.build_layer(), d)
                built.append(self._shared[d.layer_name][0])
            elif isinstance(d, LayerDesc):
                built.append(d.build_layer())
            elif isinstance(d, Layer):
                built.append(d)
            else:  # bare callable (lambda segment boundary fns)
                built.append(d)
        self.run_order = built
        self._layers_list = LayerList([l for l in built if isinstance(l, Layer)])
        # uniform partition bounds per stage
        n = len(built)
        per = [n // self.num_stages + (1 if i < n % self.num_stages else 0)
               for i in range(self.num_stages)]
        self._bounds = []
        s = 0
        for c in per:
            self._bounds.append((s, s + c))
            s += c

    def get_stage_layers(self, stage: int):
        lo, hi = self._bounds[stage]
        return self.run_order[lo:hi]

    def train_batch_1f1b(self, inputs, labels, n_microbatch: int,
                         recompute: bool = False):
        """True 1F1B for this desc-defined stack (auto-segmented into
        prefix / homogeneous block / suffix — see
        :func:`~paddle_tpu.parallel.pipeline_1f1b.pipeline_train_1f1b_auto`);
        lets ``fleet.distributed_model`` pipeline ANY sequential model, not
        just ones with a bespoke schedule hook."""
        from ..observability import get_tracer
        from .pipeline_1f1b import pipeline_train_1f1b_auto

        with get_tracer().span("pipeline_train_1f1b", cat="parallel",
                               n_microbatch=n_microbatch,
                               stages=self.num_stages,
                               recompute=recompute):
            return pipeline_train_1f1b_auto(self, inputs, labels,
                                            n_microbatch,
                                            recompute=recompute)

    def forward(self, x):
        for item, desc in zip(self.run_order, self._descs):
            if isinstance(desc, SharedLayerDesc) and desc.forward_func is not None:
                x = desc.forward_func(item, x)
            elif callable(item):
                x = item(x)
        return x


def _pp_degree() -> int:
    mesh = topology.get_mesh()
    if mesh is None:
        return 1
    return mesh.shape.get(PP_AXIS, 1)


# --------------------------------------------------------------------------
# SPMD pipeline schedule (pipeline_parallel.py:440 analog)
# --------------------------------------------------------------------------

def pipeline_spmd(stage_fn: Callable, stage_params: Any, x: jnp.ndarray,
                  n_microbatch: int, mesh=None, extra: Any = None,
                  axis: str = PP_AXIS):
    """Run ``x`` through ``n_stages`` pipeline stages as one SPMD program.

    ``stage_params``: pytree whose leaves have a leading ``[n_stages, ...]``
    dim (sharded over ``pp``); ``stage_fn(params_slice, act, extra)`` is one
    stage's forward.  ``x``: global batch ``[B, ...]``, split into
    ``n_microbatch`` along dim 0.  Pure-JAX values in/out (used by model
    train steps under jit; Tensor-level callers go through
    :func:`pipeline_forward`).
    """
    mesh = mesh or topology.get_mesh()
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_microbatch == 0, f"batch {B} % microbatches {n_microbatch}"
    mb = B // n_microbatch
    micro = x.reshape((n_microbatch, mb) + x.shape[1:])

    param_specs = jax.tree.map(
        lambda _: P(axis), stage_params,
        is_leaf=lambda l: not isinstance(l, (dict, list, tuple)))

    def body(params_local, micro_local, extra_local):
        # params_local leaves: [1, ...] (this stage's slice)
        params_here = jax.tree.map(lambda p: p[0], params_local)
        idx = jax.lax.axis_index(axis)
        n = jax.lax.axis_size(axis)
        perm = [(j, (j + 1) % n) for j in range(n)]
        T = n_microbatch + n - 1

        act_shape = jax.eval_shape(
            lambda p, a: stage_fn(p, a, extra_local), params_here, micro_local[0])

        def tick(t, carry):
            recv, outs = carry
            inject = micro_local[jnp.minimum(t, n_microbatch - 1)]
            a_in = jnp.where(idx == 0, inject.astype(recv.dtype), recv)
            y = jax.checkpoint(stage_fn)(params_here, a_in, extra_local)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where((idx == n - 1) & (t >= n - 1),
                                y, outs[jnp.maximum(t - n + 1, 0)]),
                jnp.maximum(t - n + 1, 0), 0)
            recv = jax.lax.ppermute(y, axis, perm)
            return recv, outs

        recv0 = jnp.zeros(act_shape.shape, act_shape.dtype)
        outs0 = jnp.zeros((n_microbatch,) + act_shape.shape, act_shape.dtype)
        _, outs = jax.lax.fori_loop(0, T, tick, (recv0, outs0))
        # broadcast final-stage outputs to every rank (replicated result)
        outs = jax.lax.psum(
            jnp.where(idx == n - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, P(), P()),
        out_specs=P(), check_vma=False)
    with manual_sharding_mode():
        outs = mapped(stage_params, micro, extra)
    return outs.reshape((B,) + outs.shape[2:])


def pipeline_forward(layer: PipelineLayer, x: Tensor, n_microbatch: int,
                     extra=None) -> Tensor:
    """Tensor-level pipeline forward for homogeneous stages: every stage must
    hold structurally identical layers (the decoder-stack case; put
    embedding/head outside the pipelined region, see models/llama.py).

    With ``num_virtual_pipeline_stages=v`` > 1 (interleaved VPP,
    ``PipelineParallelWithInterleave`` analog) the stack is cut into n·v
    segments, chunk ``r`` of device ``d`` holding segment ``r·n + d``; the
    microbatch ring runs ``v`` sweeps, one per chunk round.  (The depth-first
    1F1B interleaving that shrinks the bubble further is a scheduling
    refinement on top of this placement.)"""
    from ..observability import get_tracer

    n = _pp_degree()
    if n == 1:
        return layer(x)

    with get_tracer().span("pipeline_forward", cat="parallel",
                           stages=n, n_microbatch=n_microbatch,
                           virtual_stages=layer.num_virtual_stages):
        return _pipeline_forward_dispatch(layer, x, n_microbatch, extra, n)


def _pipeline_forward_dispatch(layer, x, n_microbatch, extra, n):
    v = layer.num_virtual_stages
    stage_layers = [layer.get_stage_layers(s) for s in range(layer.num_stages)]
    homo = layer.__dict__.get("_stages_homo_cache")
    if homo is None:
        # invariant of the partition — computed once, not per train step
        homo = _stages_homogeneous(stage_layers)
        layer.__dict__["_stages_homo_cache"] = homo
    if not homo:
        # Heterogeneous stacks (the reference's arbitrary LayerDesc case,
        # ``pp_layers.py:261``): the stacked-params SPMD ring needs one
        # param structure per stage, so run the microbatched schedule with
        # each stage's own layers instead — under ``to_static`` this still
        # stages to ONE XLA program (stages keep their GSPMD placements);
        # the SPMD ring remains the fast path for homogeneous stacks.
        return _pipeline_forward_hetero(stage_layers, x, n_microbatch)
    if v > 1:
        # run v chained sweeps: sweep r uses segments [r*n, (r+1)*n)
        out = x
        rounds = [stage_layers[r * n:(r + 1) * n] for r in range(v)]
        for round_layers in rounds:
            out = _pipeline_forward_ring(round_layers, out, n_microbatch, extra)
        return out
    return _pipeline_forward_ring(stage_layers, x, n_microbatch, extra)


def _stage_signature(ls):
    # full sublayer type structure, not just the top-level class — stages
    # differing only in parameterless sublayers (ReLU vs Tanh inside a
    # Sequential) must NOT be classified homogeneous
    return tuple(
        (tuple(type(s).__name__ for s in l.sublayers(include_self=True)),
         tuple(tuple(p.shape) for _, p in l.named_parameters()))
        for l in ls)


def _stages_homogeneous(stage_layers) -> bool:
    sig0 = _stage_signature(stage_layers[0])
    return all(_stage_signature(ls) == sig0 for ls in stage_layers[1:])


def _pipeline_forward_hetero(stage_layers, x: Tensor,
                             n_microbatch: int) -> Tensor:
    """Microbatched schedule over per-stage heterogeneous layers; grads flow
    through the ordinary tape."""
    from ..tensor.manipulation import concat

    B = x.shape[0]
    assert B % n_microbatch == 0, (B, n_microbatch)
    mb = B // n_microbatch
    outs = []
    for m in range(n_microbatch):
        cur = x[m * mb:(m + 1) * mb]
        for ls in stage_layers:
            for l in ls:
                cur = l(cur)
        outs.append(cur)
    return concat(outs, axis=0)


def _pipeline_forward_ring(stage_layers, x: Tensor, n_microbatch: int,
                           extra=None) -> Tensor:
    # stack_states reads param values directly (no run_op), and inside the
    # shard_map body params hold manual tracers the recorder must ignore —
    # register them as to_static state here, while values are concrete.
    mark_inputs([p for ls in stage_layers for l in ls
                 for _, p in l.named_parameters()])

    def stack_states():
        states = []
        for ls in stage_layers:
            flat = []
            for l in ls:
                flat.append([p._value for _, p in l.named_parameters()])
            states.append(flat)
        # [n_stages][layers_per_stage][n_params] → stacked leaves
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    stacked = stack_states()
    templates = stage_layers[0]

    def stage_fn(params, act, _extra):
        cur = act
        for li, l in enumerate(templates):
            saved = [p._value for _, p in l.named_parameters()]
            for (pn, p), v in zip(l.named_parameters(), params[li]):
                p._value = v
            try:
                out = l(Tensor(cur, stop_gradient=True))
                cur = out._value if isinstance(out, Tensor) else out
            finally:
                for (pn, p), v in zip(l.named_parameters(), saved):
                    p._value = v
        return cur

    def f(xv, *param_leaves):
        tree = jax.tree.unflatten(jax.tree.structure(stacked), list(param_leaves))
        return pipeline_spmd(stage_fn, tree, xv, n_microbatch, extra=extra)

    leaves = jax.tree.leaves(stacked)
    # leaf order is layer-major then param-index (list-of-lists structure)
    param_groups = []  # leaf i → [param of that slot per stage]
    n_params_per_layer = [len(l.parameters()) for l in templates]
    for li, l in enumerate(templates):
        for pi in range(n_params_per_layer[li]):
            param_groups.append(
                [list(stage_layers[s][li].parameters())[pi]
                 for s in range(len(stage_layers))])

    leaf_tensors = []
    for leaf, group in zip(leaves, param_groups):
        t = Tensor(leaf, stop_gradient=all(p.stop_gradient for p in group))

        def scatter_grad(g, _group=group):
            # route the stacked grad back onto the real Parameters (the
            # analog of the reference's per-stage backward accumulation)
            for s, p in enumerate(_group):
                gs = g._value[s]
                p.grad = Tensor(gs) if p.grad is None else Tensor(p.grad._value + gs)
            return g

        if not t.stop_gradient:
            t.register_hook(scatter_grad)
        leaf_tensors.append(t)

    mark_derived(leaf_tensors)
    return run_op("pipeline_forward", f, x, *leaf_tensors)
