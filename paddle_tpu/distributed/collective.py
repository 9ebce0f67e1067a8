"""Collective communication API
(``python/paddle/distributed/communication/*.py`` capability).

TPU-first, two execution contexts:

* **Inside shard_map / pjit** (the compiled SPMD path): these call
  ``jax.lax`` collectives over named mesh axes — XLA lowers them to ICI/DCN
  collective ops (the NCCL ring analog, but compiler-scheduled).
* **Eager single-controller**: a global jax.Array already holds the logical
  value across devices, so cross-"rank" reductions are plain reductions over
  the sharded axis; the API keeps paddle semantics (mutating dst in place).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import run_op
from ..core.tensor import Tensor


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _in_spmd() -> bool:
    """True when called under shard_map tracing (axis names bound)."""
    try:
        return bool(jax.core.get_axis_env() and jax.core.get_axis_env().axis_sizes)
    except Exception:
        pass
    return False


def _axis_bound(axis: str) -> bool:
    try:
        jax.lax.axis_size(axis)
        return True
    except Exception:
        return False


def _group_axis(group) -> str:
    if group is None:
        for ax in ("dp", "mp", "sharding", "sep", "pp"):
            if _axis_bound(ax):
                return ax
        return "dp"
    if isinstance(group, str):
        return group
    return getattr(group, "axis", "dp")


def all_reduce(tensor: Tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = _group_axis(group)
    if _axis_bound(axis):
        fns = {
            ReduceOp.SUM: jax.lax.psum,
            ReduceOp.MAX: jax.lax.pmax,
            ReduceOp.MIN: jax.lax.pmin,
            ReduceOp.AVG: jax.lax.pmean,
        }
        out = run_op("all_reduce", lambda v: fns[op](v, axis), tensor)
        tensor._rebind(out)
        return None
    # single-controller eager: value already global → identity
    return None


def all_gather(tensor_list, tensor: Tensor, group=None, sync_op=True):
    axis = _group_axis(group)
    if _axis_bound(axis):
        out = run_op(
            "all_gather",
            lambda v: jax.lax.all_gather(v, axis, tiled=False),
            tensor,
        )
        n = jax.lax.axis_size(axis)
        for i in range(n):
            tensor_list.append(out[i])
        return None
    tensor_list.append(tensor)
    return None


def reduce_scatter(tensor: Tensor, tensor_or_tensor_list, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = _group_axis(group)
    src = tensor_or_tensor_list
    if isinstance(src, (list, tuple)):
        from ..tensor import concat

        src = concat(list(src), axis=0)
    if _axis_bound(axis):
        out = run_op(
            "reduce_scatter",
            lambda v: jax.lax.psum_scatter(v, axis, scatter_dimension=0, tiled=True),
            src,
        )
        tensor._rebind(out)
        return None
    tensor._rebind(src)
    return None


def broadcast(tensor: Tensor, src: int = 0, group=None, sync_op=True):
    axis = _group_axis(group)
    if _axis_bound(axis):
        def f(v):
            idx = jax.lax.axis_index(axis)
            sized = jax.lax.psum(jnp.where(idx == src, v, jnp.zeros_like(v)), axis)
            return sized

        out = run_op("broadcast", f, tensor)
        tensor._rebind(out)
    return None


def scatter(tensor: Tensor, tensor_list=None, src: int = 0, group=None, sync_op=True):
    axis = _group_axis(group)
    if tensor_list is None:
        return None
    if _axis_bound(axis):
        from ..tensor import stack

        stacked = stack(list(tensor_list), axis=0)

        def f(v):
            idx = jax.lax.axis_index(axis)
            return jnp.take(v, idx, axis=0)

        out = run_op("scatter", f, stacked)
        tensor._rebind(out)
        return None
    tensor._rebind(tensor_list[0])
    return None


def reduce(tensor: Tensor, dst: int = 0, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    axis = _group_axis(group)
    if _axis_bound(axis):
        from ..tensor import stack, unbind

        stacked = stack(list(in_tensor_list), axis=0)
        out = run_op(
            "alltoall",
            lambda v: jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0, tiled=False),
            stacked,
        )
        out_tensor_list.extend(unbind(out, 0))
        return None
    out_tensor_list.extend(in_tensor_list)
    return None


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None, out_split_sizes=None,
                    group=None, sync_op=True):
    axis = _group_axis(group)
    if _axis_bound(axis):
        out = run_op(
            "alltoall_single",
            lambda v: jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0, tiled=True),
            in_tensor,
        )
        out_tensor._rebind(out)
        return None
    out_tensor._rebind(in_tensor)
    return None


def send(tensor: Tensor, dst: int = 0, group=None, sync_op=True):
    raise NotImplementedError(
        "raw send/recv are not exposed on the XLA runtime; pipeline p2p uses "
        "paddle_tpu.distributed.p2p (ppermute-based)"
    )


def recv(tensor: Tensor, src: int = 0, group=None, sync_op=True):
    raise NotImplementedError(
        "raw send/recv are not exposed on the XLA runtime; pipeline p2p uses "
        "paddle_tpu.distributed.p2p (ppermute-based)"
    )


def barrier(group=None):
    jax.effects_barrier()


def ppermute(tensor: Tensor, axis: str, perm):
    """Neighbor exchange (collective_permute) — the pipeline/ring primitive."""
    out = run_op("ppermute", lambda v: jax.lax.ppermute(v, axis, perm), tensor)
    return out


def new_group(ranks=None, backend=None, timeout=None):
    class _Group:
        def __init__(self, ranks):
            self.ranks = ranks or []
            self.axis = "dp"
            self.nranks = len(self.ranks) or 1

        @property
        def world_size(self):
            return self.nranks

    return _Group(ranks)


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor) and not isinstance(tensor._value, jax.core.Tracer):
        tensor._value.block_until_ready()


def isend(tensor: Tensor, dst: int = 0, group=None):
    send(tensor, dst, group)  # raises with the p2p guidance


def irecv(tensor: Tensor, src: int = 0, group=None):
    recv(tensor, src, group)  # raises with the p2p guidance
