"""True 1F1B and depth-first interleaved-VPP pipeline schedules, SPMD-style.

Capability analog of the reference's runtime pipeline schedulers:
``fleet/meta_parallel/pipeline_parallel.py:440`` (``forward_backward_pipeline``,
1F1B) and ``:906`` (``PipelineParallelWithInterleave``, interleaved VPP).

TPU-first design: instead of an actor runtime exchanging per-microbatch NCCL
p2p messages, the WHOLE forward+backward schedule is one traced XLA program.

* The schedule itself is a static table built in Python
  (:func:`build_1f1b_schedule`): slot × device → {idle | fwd | bwd} with
  microbatch + chunk ids, constructed greedily with backward-priority and a
  per-virtual-stage in-flight cap (``pp·v − vstage``) — the classic 1F1B
  warmup/steady/cooldown emerges from the cap, and chunks interleave
  depth-first (deeper chunks scheduled first) for VPP.
* Execution is a ``shard_map`` + ``fori_loop`` over slots: forward ticks run
  ``stage_fn`` (by default under ``jax.vjp``, ring-buffering the pullback
  residuals so backward never re-runs the forward; with ``recompute=True``
  only stage *inputs* are buffered and backward recomputes, the reference's
  opt-in recompute); activations and cotangents ride two
  ``collective-permute`` rings over ICI.
* Activation memory is bounded: a ``[v, pp, microbatch]`` ring buffer per
  device — in-flight microbatches per stage never exceed the cap,
  **independent of the microbatch count** (GPipe holds all M).
* The loss head runs per-microbatch on the last virtual stage inside the
  schedule (that is what makes true 1F1B possible — backward starts while
  later microbatches are still being forwarded).

The public Tensor-level op (:func:`pipeline_train_1f1b`) wraps the schedule
in ``jax.custom_vjp``: forward returns the mean loss and stashes
(param-grads, input-grad); ``loss.backward()`` just scales and routes them —
the tape never re-differentiates the pipeline loop.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.dispatch import mark_derived, mark_inputs, run_op
from ..core.tensor import Tensor
from ..distributed import topology
from .utils import manual_sharding_mode

PP_AXIS = "pp"

_IDLE, _FWD, _BWD = 0, 1, 2


class Schedule1F1B:
    """Static schedule tables (all numpy, [T, n]) + occupancy stats."""

    def __init__(self, opc, mb, ch, arr_f_mb, arr_f_ch, arr_c_mb, arr_c_ch,
                 peak_in_flight, n_stages, n_micro, v, buf_depth):
        self.opc = opc
        self.mb = mb
        self.ch = ch
        self.arr_f_mb = arr_f_mb
        self.arr_f_ch = arr_f_ch
        self.arr_c_mb = arr_c_mb
        self.arr_c_ch = arr_c_ch
        self.peak_in_flight = peak_in_flight  # per device, max buffered mbs
        self.n_stages = n_stages
        self.n_micro = n_micro
        self.v = v
        self.n_slots = opc.shape[0]
        # ring-buffer depth: >= the max per-VIRTUAL-STAGE occupancy of both
        # the activation and cotangent buffers — slot reuse (m % buf_depth)
        # is only safe when a vstage never holds more than buf_depth entries
        self.buf_depth = buf_depth


@functools.lru_cache(maxsize=64)
def build_1f1b_schedule(n_stages: int, n_micro: int, v: int = 1) -> Schedule1F1B:
    """Greedy 1F1B/VPP scheduler over ``n_stages·v`` virtual stages.

    Virtual stage ``vs`` lives on device ``vs % n_stages`` (depth-first chunk
    placement, ``PipelineParallelWithInterleave`` layout).  Backward has
    priority; forwards are capped at ``n_stages·v − vs`` in flight per
    virtual stage.  The LAST virtual stage schedules no forward op — its
    backward recomputes the stage forward together with the loss head.
    """
    n, nv = n_stages, n_stages * v
    f_slot = [[None] * n_micro for _ in range(nv)]
    b_slot = [[None] * n_micro for _ in range(nv)]
    next_f = [0] * nv
    next_b = [0] * nv

    def cap(vs):
        return max(1, nv - vs)

    rows = []
    t = 0
    t_max = 8 * nv * max(n_micro, n) + 64
    while sum(next_b) < nv * n_micro:
        if t > t_max:
            raise RuntimeError(
                f"1F1B scheduler deadlock: pp={n} micro={n_micro} v={v}")
        row = [(_IDLE, 0, 0)] * n
        busy = [False] * n
        # backward priority, deeper virtual stages first
        for vs in reversed(range(nv)):
            d = vs % n
            if busy[d] or next_b[vs] >= n_micro:
                continue
            m = next_b[vs]
            if vs == nv - 1:
                ready = (nv == 1) or (f_slot[nv - 2][m] is not None
                                      and f_slot[nv - 2][m] < t)
            else:
                ready = b_slot[vs + 1][m] is not None and b_slot[vs + 1][m] < t
            # a mid-stage backward also needs its own forward done
            if vs != nv - 1:
                ready = ready and f_slot[vs][m] is not None and f_slot[vs][m] < t
            if ready:
                row[d] = (_BWD, m, vs // n)
                b_slot[vs][m] = t
                next_b[vs] += 1
                busy[d] = True
        # forwards: deeper chunks first (depth-first interleave)
        for vs in reversed(range(nv - 1)):  # last vstage has no fwd op
            d = vs % n
            if busy[d] or next_f[vs] >= n_micro:
                continue
            m = next_f[vs]
            if m - next_b[vs] >= cap(vs):
                continue  # in-flight cap: the 1F1B memory bound
            ready = (vs == 0) or (f_slot[vs - 1][m] is not None
                                  and f_slot[vs - 1][m] < t)
            if ready:
                row[d] = (_FWD, m, vs // n)
                f_slot[vs][m] = t
                next_f[vs] += 1
                busy[d] = True
        rows.append(row)
        t += 1

    T = len(rows)
    opc = np.zeros((T, n), np.int32)
    mb = np.zeros((T, n), np.int32)
    ch = np.zeros((T, n), np.int32)
    for ti, row in enumerate(rows):
        for d, (c, m, k) in enumerate(row):
            opc[ti, d], mb[ti, d], ch[ti, d] = c, m, k

    # arrival tables: what lands on each ring at the START of slot t
    # (sent at the end of slot t-1)
    arr_f_mb = np.full((T, n), -1, np.int32)
    arr_f_ch = np.zeros((T, n), np.int32)
    arr_c_mb = np.full((T, n), -1, np.int32)
    arr_c_ch = np.zeros((T, n), np.int32)
    for ti in range(1, T):
        for d in range(n):
            pd = (d - 1) % n   # fwd ring source
            c, m, k = rows[ti - 1][pd]
            if c == _FWD:
                vs = k * n + pd
                if vs + 1 <= nv - 1 and (vs + 1) % n == d:
                    arr_f_mb[ti, d] = m
                    arr_f_ch[ti, d] = (vs + 1) // n
            nd = (d + 1) % n   # cotangent ring source
            c, m, k = rows[ti - 1][nd]
            if c == _BWD:
                vs = k * n + nd
                if vs - 1 >= 0 and (vs - 1) % n == d:
                    arr_c_mb[ti, d] = m
                    arr_c_ch[ti, d] = (vs - 1) // n
    # the last vstage's "forward" is a pure arrival (no op): its effective
    # f_slot is the arrival slot, needed for the occupancy accounting below
    for m in range(n_micro):
        if nv >= 2:
            f_slot[nv - 1][m] = f_slot[nv - 2][m] + 1 if f_slot[nv - 2][m] is not None else None

    # peak buffered microbatches per device (forwarded/arrived but not yet
    # backwarded, summed over that device's chunks)
    peak = [0] * n
    for d in range(n):
        for ti in range(T):
            held = 0
            for k in range(v):
                vs = k * n + d
                for m in range(n_micro):
                    fs = f_slot[vs][m]
                    bs = b_slot[vs][m]
                    if fs is not None and fs <= ti and (bs is None or bs > ti):
                        held += 1
            peak[d] = max(peak[d], held)

    # buffer depth: max per-vstage occupancy of (a) saved activations
    # (forward/arrival -> backward) and (b) buffered cotangents
    # (produced at b(m, vs+1) -> consumed at b(m, vs))
    depth = 1
    for vs in range(nv):
        for ti in range(T):
            held_a = sum(
                1 for m in range(n_micro)
                if f_slot[vs][m] is not None and f_slot[vs][m] <= ti
                and (b_slot[vs][m] is None or b_slot[vs][m] > ti))
            held_c = 0
            if vs < nv - 1:
                held_c = sum(
                    1 for m in range(n_micro)
                    if b_slot[vs + 1][m] is not None
                    and b_slot[vs + 1][m] <= ti
                    and (b_slot[vs][m] is None or b_slot[vs][m] > ti))
            depth = max(depth, held_a, held_c)
    # +1 guard: an arrival stored at the start of a slot can coexist with
    # the entry whose backward runs later in that same slot
    depth = min(depth + 1, n_micro)

    from ..observability import get_tracer

    get_tracer().instant("1f1b_schedule_built", cat="parallel",
                         stages=n_stages, n_micro=n_micro, v=v,
                         ticks=len(opc), buffer_depth=depth,
                         peak_in_flight=max(peak) if peak else 0)
    return Schedule1F1B(opc, mb, ch, arr_f_mb, arr_f_ch, arr_c_mb, arr_c_ch,
                        peak, n, n_micro, v, depth)


# --------------------------------------------------------------------------
# SPMD executor
# --------------------------------------------------------------------------

def pipeline_train_spmd(stage_fn: Callable, stage_params: Any,
                        head_fn: Callable, head_params: Any,
                        x: jnp.ndarray, targets: Any, n_microbatch: int,
                        v: int = 1, mesh=None, extra: Any = None,
                        axis: str = PP_AXIS, dp_axis: Optional[str] = "dp",
                        stage_has_aux: bool = False,
                        aux_weight: float = 0.0,
                        recompute: bool = False):
    """Run the full 1F1B train schedule; returns
    ``(mean_loss, dx, stage_grads, head_grads)``.

    ``stage_params``: pytree, leaves ``[n·v, ...]`` in device-major layout —
    row ``d·v + k`` holds virtual stage ``k·n + d`` (use
    :func:`stack_device_major`).  ``stage_fn(params_one_stage, act, extra)``
    is one virtual stage's forward; ``head_fn(head_params, act, target_mb)``
    returns that microbatch's scalar loss.  ``x``: ``[B, ...]`` pipeline
    input (post-embedding); ``targets``: ``[B, ...]`` labels.

    If the mesh has a ``dp`` axis that divides the microbatch size, each
    microbatch is additionally data-sharded over it (grads pmean'd across
    dp groups — pp×dp composition in one program).

    With ``stage_has_aux=True``, ``stage_fn`` returns ``(act, aux_scalar)``
    (e.g. MoE load-balance loss); every stage's aux joins the total loss
    weighted by ``aux_weight`` and is differentiated in that stage's
    backward tick.

    ``recompute=False`` (default) matches the reference's plain 1F1B
    (``pipeline_parallel.py:440``): forward ticks run ``jax.vjp`` once and
    stash the flattened pullback residuals in ring buffers; backward ticks
    rebuild the pullback and never re-run the stage forward — no duplicate
    forward FLOPs, activation memory still bounded by the in-flight cap.
    ``recompute=True`` buffers only stage INPUTS and re-runs the stage
    forward under ``jax.vjp`` at backward ticks — minimal memory, ~1/3
    extra FLOPs (the reference's opt-in ``fleet/recompute/recompute.py``).
    Choose via ``DistributedStrategy.recompute`` at the fleet level.
    """
    mesh = mesh or topology.get_mesh()
    if not stage_has_aux:
        _inner_stage = stage_fn

        def stage_fn(p, a, e):  # noqa: F811 — uniform (act, aux) contract
            return _inner_stage(p, a, e), jnp.zeros((), jnp.float32)
    n = mesh.shape[axis]
    sched = build_1f1b_schedule(n, n_microbatch, v)
    B = x.shape[0]
    assert B % n_microbatch == 0, f"batch {B} % microbatches {n_microbatch}"
    mb_sz = B // n_microbatch
    micro = x.reshape((n_microbatch, mb_sz) + x.shape[1:])
    tgt = jax.tree.map(
        lambda a: a.reshape((n_microbatch, mb_sz) + a.shape[1:]), targets)

    dp = mesh.shape.get(dp_axis, 1) if dp_axis else 1
    use_dp = dp > 1 and mb_sz % dp == 0
    mb_spec = P(None, dp_axis) if use_dp else P()

    # schedule tables as device constants
    OPC = jnp.asarray(sched.opc)
    MBT = jnp.asarray(sched.mb)
    CHT = jnp.asarray(sched.ch)
    AFM = jnp.asarray(sched.arr_f_mb)
    AFC = jnp.asarray(sched.arr_f_ch)
    ACM = jnp.asarray(sched.arr_c_mb)
    ACC = jnp.asarray(sched.arr_c_ch)

    param_specs = jax.tree.map(lambda _: P(axis), stage_params,
                               is_leaf=lambda l: not isinstance(l, (dict, list, tuple)))

    def body(params_local, head_local, micro_local, tgt_local, extra_local):
        idx = jax.lax.axis_index(axis)
        perm_f = [(j, (j + 1) % n) for j in range(n)]
        perm_c = [(j, (j - 1) % n) for j in range(n)]
        nv = n * v

        params_dev = jax.tree.map(lambda p: p, params_local)  # [v, ...] leaves

        def params_at(k):
            return jax.tree.map(
                lambda p: jax.lax.dynamic_index_in_dim(p, k, 0, keepdims=False),
                params_dev)

        act_sds, _ = jax.eval_shape(
            lambda p, a: stage_fn(p, a, extra_local),
            params_at(0), micro_local[0])
        A_shape, A_dtype = act_sds.shape, act_sds.dtype

        def _stage_vjp(p, a):
            return jax.vjp(lambda pp, aa: stage_fn(pp, aa, extra_local), p, a)

        if not recompute:
            # Residual structure of one stage's pullback: the vjp closure is
            # a pytree (jax Partial) whose leaves are the saved values.
            # Classify each leaf ONCE on an abstract trace:
            #   'param' — a passthrough of a stage parameter (identity with
            #     an input tracer): re-fetched from params at the backward
            #     tick, NEVER ring-buffered (buffering would multiply the
            #     per-device weight memory by ~buf_depth);
            #   'const' — a trace constant (e.g. host rope tables): captured
            #     here, re-embedded at backward;
            #   'buf'   — a genuine activation residual: ring-buffered.
            probe: dict = {}

            def _probe(p, a):
                (y, aux), pull = _stage_vjp(p, a)
                leaves, vjp_def = jax.tree.flatten(pull)
                pid2idx = {id(x): i for i, x in enumerate(jax.tree.leaves(p))}
                cls, consts = [], []
                for leaf in leaves:
                    if not isinstance(leaf, jax.core.Tracer):
                        cls.append(("const", len(consts)))
                        consts.append(leaf)
                    elif id(leaf) in pid2idx:
                        cls.append(("param", pid2idx[id(leaf)]))
                    else:
                        cls.append(("buf", None))
                probe.update(cls=cls, consts=consts, vjp_def=vjp_def)
                return aux, leaves

            p_sds = jax.tree.map(
                lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), params_at(0))
            aux_sds, leaf_sds = jax.eval_shape(
                _probe, p_sds, jax.ShapeDtypeStruct(A_shape, A_dtype))
            res_cls, res_consts = probe["cls"], probe["consts"]
            vjp_def = probe["vjp_def"]
            buf_pos = [i for i, c in enumerate(res_cls) if c[0] == "buf"]
            res_sds = [leaf_sds[i] for i in buf_pos]
            aux_dtype = aux_sds.dtype
        else:
            res_sds, vjp_def, buf_pos, res_cls, res_consts = [], None, [], [], []
            aux_dtype = jnp.float32

        def _idx2(k, m, ndim):
            z = jnp.zeros((), jnp.int32)
            return ((jnp.asarray(k, jnp.int32),
                     jnp.asarray(m % sched.buf_depth, jnp.int32))
                    + (z,) * (ndim - 2))

        def buf_set(buf, k, m, val):
            return jax.lax.dynamic_update_slice(
                buf, val[None, None], _idx2(k, m, buf.ndim))

        def buf_get(buf, k, m):
            return jax.lax.dynamic_slice(
                buf, _idx2(k, m, buf.ndim),
                (1, 1) + buf.shape[2:])[0, 0]

        def tgt_at(m):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False),
                tgt_local)

        zero_head_grads = jax.tree.map(jnp.zeros_like, head_local)

        def fwd_branch(op):
            carry, t, m, k = op
            abuf, cbuf, sf, sc, grads, hgrads, dx, loss, rstate = carry
            is_stage0 = (idx == 0) & (k == 0)
            inj = jax.lax.dynamic_index_in_dim(micro_local, m, 0,
                                               keepdims=False).astype(A_dtype)
            a_in = jnp.where(is_stage0, inj, buf_get(abuf, k, m))
            if recompute:
                y, _ = stage_fn(params_at(k), a_in, extra_local)
                abuf = buf_set(abuf, k, m, a_in)
            else:
                (y, aux), pull = _stage_vjp(params_at(k), a_in)
                leaves = jax.tree.leaves(pull)
                rbufs, auxbuf = rstate
                rbufs = tuple(
                    buf_set(b, k, m, leaves[i])
                    for b, i in zip(rbufs, buf_pos))
                auxbuf = buf_set(auxbuf, k, m, aux)
                rstate = (rbufs, auxbuf)
            return (abuf, cbuf, y, jnp.zeros(A_shape, A_dtype), grads,
                    hgrads, dx, loss, rstate)

        def bwd_branch(op):
            carry, t, m, k = op
            abuf, cbuf, sf, sc, grads, hgrads, dx, loss, rstate = carry
            a_in = buf_get(abuf, k, m)
            p_k = params_at(k)
            is_last = (idx == (nv - 1) % n) & (k == v - 1)

            def last_case(_):
                # the last vstage has no forward tick — its stage forward
                # runs fused here in BOTH modes (nothing is duplicated)
                def full(p, hp, a):
                    y, aux = stage_fn(p, a, extra_local)
                    return (head_fn(hp, y, tgt_at(m))
                            + aux_weight * aux.astype(jnp.float32))
                loss_m, pull = jax.vjp(full, p_k, head_local, a_in)
                dp, dh, da = pull(jnp.ones((), loss_m.dtype))
                return dp, dh, da.astype(A_dtype), loss_m

            def mid_case(_):
                g = buf_get(cbuf, k, m).astype(A_dtype)
                if recompute:
                    (_, aux), pull = jax.vjp(
                        lambda p, a: stage_fn(p, a, extra_local), p_k, a_in)
                else:
                    rbufs, auxbuf = rstate
                    p_leaves = jax.tree.leaves(p_k)
                    leaves, bi = [], 0
                    for kind, j in res_cls:
                        if kind == "param":
                            leaves.append(p_leaves[j])
                        elif kind == "const":
                            leaves.append(res_consts[j])
                        else:
                            leaves.append(buf_get(rbufs[bi], k, m))
                            bi += 1
                    pull = jax.tree.unflatten(vjp_def, leaves)
                    aux = buf_get(auxbuf, k, m)
                dp, da = pull((g, jnp.asarray(aux_weight, aux.dtype)))
                return (dp, zero_head_grads, da.astype(A_dtype),
                        aux_weight * aux.astype(jnp.float32))

            dp, dh, da, loss_m = jax.lax.cond(is_last, last_case, mid_case,
                                              None)
            grads = jax.tree.map(lambda g, d: g.at[k].add(d), grads, dp)
            hgrads = jax.tree.map(jnp.add, hgrads, dh)
            loss = loss + loss_m.astype(jnp.float32)
            is_stage0 = (idx == 0) & (k == 0)
            z = jnp.zeros((), jnp.int32)
            dx = jnp.where(
                is_stage0,
                jax.lax.dynamic_update_slice(
                    dx, da[None].astype(dx.dtype),
                    (jnp.asarray(m, jnp.int32),) + (z,) * (dx.ndim - 1)),
                dx)
            return (abuf, cbuf, jnp.zeros(A_shape, A_dtype), da, grads,
                    hgrads, dx, loss, rstate)

        def idle_branch(op):
            carry, t, m, k = op
            abuf, cbuf, sf, sc, grads, hgrads, dx, loss, rstate = carry
            z = jnp.zeros(A_shape, A_dtype)
            return (abuf, cbuf, z, z, grads, hgrads, dx, loss, rstate)

        def slot(t, carry):
            abuf, cbuf, send_f, send_c, grads, hgrads, dx, loss, rstate = carry
            # receive what was sent at the end of the previous slot
            recv_f = jax.lax.ppermute(send_f, axis, perm_f)
            recv_c = jax.lax.ppermute(send_c, axis, perm_c)
            afm = AFM[t, idx]
            afc = AFC[t, idx]
            cur = buf_get(abuf, afc, jnp.maximum(afm, 0))
            abuf = buf_set(abuf, afc, jnp.maximum(afm, 0),
                           jnp.where(afm >= 0, recv_f, cur))
            acm = ACM[t, idx]
            acc_ = ACC[t, idx]
            curc = buf_get(cbuf, acc_, jnp.maximum(acm, 0))
            cbuf = buf_set(cbuf, acc_, jnp.maximum(acm, 0),
                           jnp.where(acm >= 0, recv_c, curc))

            code = OPC[t, idx]
            m = MBT[t, idx]
            k = CHT[t, idx]
            carry2 = (abuf, cbuf, send_f, send_c, grads, hgrads, dx, loss,
                      rstate)
            return jax.lax.switch(code, [idle_branch, fwd_branch, bwd_branch],
                                  (carry2, t, m, k))

        abuf0 = jnp.zeros((v, sched.buf_depth) + A_shape, A_dtype)
        cbuf0 = jnp.zeros((v, sched.buf_depth) + A_shape, A_dtype)
        z = jnp.zeros(A_shape, A_dtype)
        grads0 = jax.tree.map(jnp.zeros_like, params_dev)
        dx0 = jnp.zeros((n_microbatch,) + micro_local.shape[1:], x.dtype)
        if recompute:
            rstate0 = ()
        else:
            rstate0 = (tuple(
                jnp.zeros((v, sched.buf_depth) + s.shape, s.dtype)
                for s in res_sds),
                jnp.zeros((v, sched.buf_depth), aux_dtype))
        init = (abuf0, cbuf0, z, z, grads0, zero_head_grads, dx0,
                jnp.zeros((), jnp.float32), rstate0)
        out = jax.lax.fori_loop(0, sched.n_slots, slot, init)
        _, _, _, _, grads, hgrads, dx, loss, _ = out
        # replicate results: loss/head/dx live on single stages.  The loss is
        # the MEAN over microbatches while each backward used cotangent 1.0,
        # so every gradient is scaled by 1/M to match d(mean)/dθ.
        inv_m = 1.0 / n_microbatch
        loss = jax.lax.psum(loss, axis) * inv_m
        hgrads = jax.tree.map(
            lambda a: jax.lax.psum(a, axis) * inv_m, hgrads)
        dx = jax.lax.psum(dx, axis) * inv_m
        grads = jax.tree.map(lambda a: a * inv_m, grads)
        if use_dp:
            # loss/grads are per-dp-group means; global = mean across groups
            loss = jax.lax.pmean(loss, dp_axis)
            grads = jax.tree.map(lambda a: jax.lax.pmean(a, dp_axis), grads)
            hgrads = jax.tree.map(lambda a: jax.lax.pmean(a, dp_axis), hgrads)
            dx = dx / dp  # stays batch-sharded; d(global mean)/d(local x)
        return loss, dx, grads, hgrads

    grad_specs = jax.tree.map(
        lambda _: P(axis), stage_params,
        is_leaf=lambda l: not isinstance(l, (dict, list, tuple)))
    tgt_specs = jax.tree.map(lambda _: mb_spec, targets)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, P(), mb_spec, tgt_specs, P()),
        out_specs=(P(), mb_spec, grad_specs, P()),
        check_vma=False)
    with manual_sharding_mode():
        loss, dx, sgrads, hgrads = mapped(stage_params, head_params, micro,
                                          tgt, extra)
    dx = dx.reshape(x.shape)
    return loss, dx, sgrads, hgrads


# --------------------------------------------------------------------------
# Tensor-level op (tape integration)
# --------------------------------------------------------------------------

def pipeline_train_1f1b(layer, x: Tensor, targets: Tensor,
                        head_params: Sequence[Tensor],
                        head_apply: Callable, n_microbatch: int,
                        extra: Any = None, axis: str = PP_AXIS,
                        aux_weight: float = 0.0,
                        recompute: bool = False) -> Tensor:
    """Tensor-level 1F1B train step over a :class:`PipelineLayer`.

    Returns the mean loss; ``loss.backward()`` routes the schedule-computed
    gradients onto the stage parameters (via scatter hooks), the head
    parameters, and ``x`` (so embedding backward runs through the tape) —
    the pipeline loop itself is never re-differentiated (``jax.custom_vjp``
    with the grads as residuals).

    ``head_apply(head_values, act, tgt) -> scalar`` is the pure-JAX loss
    head run per microbatch on the last virtual stage (final norm + LM head
    + criterion for the Llama case).
    """
    mesh = topology.get_mesh()
    n = mesh.shape[axis]
    v = layer.num_virtual_stages
    assert layer.num_stages == n * v, (layer.num_stages, n, v)
    stage_layers = [layer.get_stage_layers(s) for s in range(layer.num_stages)]
    order = device_major_order(n, v)

    mark_inputs([p for ls in stage_layers for l in ls
                 for _, p in l.named_parameters()] + list(head_params))

    def state_of(ls):
        return [[p._value for _, p in l.named_parameters()] for l in ls]

    states = [state_of(stage_layers[vs]) for vs in order]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    templates = stage_layers[0]

    def _layer_aux(l):
        """MoE load-balance loss left on the layer by its forward."""
        for holder in (l, getattr(l, "mlp", None)):
            al = getattr(holder, "aux_loss", None) if holder is not None else None
            if al is not None:
                return al._value if isinstance(al, Tensor) else al
        return None

    def stage_fn(params, act, _extra):
        cur = act
        aux = jnp.zeros((), jnp.float32)
        for li, l in enumerate(templates):
            saved = [p._value for _, p in l.named_parameters()]
            for (pn, p), vv in zip(l.named_parameters(), params[li]):
                p._value = vv
            try:
                out = l(Tensor(cur, stop_gradient=True))
                cur = out._value if isinstance(out, Tensor) else out
                al = _layer_aux(l)
                if al is not None:
                    aux = aux + al.astype(jnp.float32)
            finally:
                for (pn, p), vv in zip(l.named_parameters(), saved):
                    p._value = vv
        return cur, aux

    treedef = jax.tree.structure(stacked)
    n_head = len(head_params)

    def f(xv, *pvals, targets=None):
        head_vals = tuple(pvals[:n_head])
        stacked_tree = jax.tree.unflatten(treedef, list(pvals[n_head:]))

        @jax.custom_vjp
        def op(xv, hv, st):
            loss, _, _, _ = pipeline_train_spmd(
                stage_fn, st, head_apply, hv, xv, targets, n_microbatch,
                v=v, mesh=mesh, extra=extra, axis=axis,
                stage_has_aux=True, aux_weight=aux_weight,
                recompute=recompute)
            return loss

        def op_fwd(xv, hv, st):
            loss, dx, sg, hg = pipeline_train_spmd(
                stage_fn, st, head_apply, hv, xv, targets, n_microbatch,
                v=v, mesh=mesh, extra=extra, axis=axis,
                stage_has_aux=True, aux_weight=aux_weight,
                recompute=recompute)
            return loss, (dx, hg, sg)

        def op_bwd(res, g):
            dx, hg, sg = res
            return (dx * g, jax.tree.map(lambda a: a * g, hg),
                    jax.tree.map(lambda a: a * g, sg))

        op.defvjp(op_fwd, op_bwd)
        return op(xv, head_vals, stacked_tree)

    # stacked leaf -> the real Parameters it came from (device-major rows)
    leaves = jax.tree.leaves(stacked)
    param_groups = []
    for li, l in enumerate(templates):
        for pi in range(len(l.parameters())):
            param_groups.append(
                [list(stage_layers[vs][li].parameters())[pi] for vs in order])

    leaf_tensors = []
    for leaf, group in zip(leaves, param_groups):
        t = Tensor(leaf, stop_gradient=all(p.stop_gradient for p in group))

        def scatter_grad(g, _group=group):
            for r, p in enumerate(_group):
                gs = g._value[r]
                p.grad = (Tensor(gs) if p.grad is None
                          else Tensor(p.grad._value + gs))
            return g

        if not t.stop_gradient:
            t.register_hook(scatter_grad)
        leaf_tensors.append(t)

    mark_derived(leaf_tensors)
    return run_op("pipeline_1f1b", f, x, *head_params, *leaf_tensors,
                  targets=targets)


def _layer_sig(obj):
    """Structural signature of one pipeline item: type tree + param shapes +
    per-sublayer scalar config (epsilon, activation names, ...).  Only items
    with equal signatures may share one staged ``stage_fn`` — structural
    equality alone is NOT enough (Block(act='relu') vs Block(act='gelu')
    must not merge, since the schedule runs every stage through stage 0's
    template)."""
    from ..nn.layers import Layer

    if isinstance(obj, Layer):
        def cfg(l):
            return tuple(sorted(
                (k, v) for k, v in vars(l).items()
                if not k.startswith("_") and k != "training"
                and isinstance(v, (int, float, bool, str))))

        return (tuple((type(s).__name__, cfg(s))
                      for s in obj.sublayers(include_self=True)),
                tuple(tuple(p.shape) for _, p in obj.named_parameters()))
    # bare callables: only the SAME object repeated may merge
    return ("callable", id(obj))


class PipelineSegmentationError(RuntimeError):
    """The stack has no homogeneous block divisible into pp·v stages —
    callers fall back to the F-then-B microbatched schedule."""


class _BlockPipe:
    """Adapter exposing a homogeneous layer block with the
    ``num_stages``/``get_stage_layers`` interface of PipelineLayer."""

    def __init__(self, block, n, v):
        assert len(block) % (n * v) == 0
        self.num_virtual_stages = v
        self.num_stages = n * v
        per = len(block) // (n * v)
        self._stages = [block[s * per:(s + 1) * per]
                        for s in range(n * v)]

    def get_stage_layers(self, s):
        return self._stages[s]


def pipeline_train_1f1b_auto(pipe, inputs, labels, n_microbatch: int,
                             recompute: bool = False,
                             axis: str = PP_AXIS) -> Tensor:
    """True 1F1B for an arbitrary sequential stack (``LayerDesc`` case,
    ``pp_layers.py:261`` + ``fleet/model.py:32``).

    The stack is auto-segmented into [prefix | homogeneous block | suffix]:
    the longest run of structurally identical layers becomes the pipelined
    block (its length must divide by ``pp·v``); the prefix (e.g. embedding)
    runs on the autograd tape before the schedule, and the suffix (final
    norm / head) plus ``pipe.loss_fn`` run per-microbatch on the last
    stage inside the schedule — exactly how the Llama path treats
    embedding and LM head.  Raises with guidance when no such block exists
    (callers then use the F-then-B microbatched fallback)."""
    from ..distributed import topology as topo
    from ..nn.layers import Layer
    from .pipeline import SharedLayerDesc

    if pipe.loss_fn is None:
        raise RuntimeError("1F1B needs PipelineLayer(loss_fn=...)")
    mesh = topo.get_mesh()
    n = mesh.shape[axis]
    v = getattr(pipe, "num_virtual_stages", 1)
    items = list(pipe.run_order)
    descs = list(getattr(pipe, "_descs", items))
    # SharedLayerDesc items (tied weights, custom forward_func) never join
    # the staged block — position-unique signature keeps them in
    # prefix/suffix where the desc dispatch below handles them
    sigs = [("shared", i) if isinstance(d, SharedLayerDesc)
            else _layer_sig(o)
            for i, (o, d) in enumerate(zip(items, descs))]

    # longest contiguous run of one signature whose length divides pp·v
    best = None  # (len, start, end)
    i = 0
    while i < len(sigs):
        j = i
        while j < len(sigs) and sigs[j] == sigs[i]:
            j += 1
        run = j - i
        usable = run - run % (n * v)
        if usable >= n * v and (best is None or usable > best[0]):
            best = (usable, i, i + usable)
        i = j
    if best is None:
        raise PipelineSegmentationError(
            f"no homogeneous layer block divisible into {n * v} pipeline "
            "stages; use schedule_mode='F-then-B' for fully heterogeneous "
            "stacks")
    _, lo, hi = best

    def _apply(item, desc, x):
        # SharedLayerDesc dispatch matches PipelineLayer.forward
        if isinstance(desc, SharedLayerDesc) and desc.forward_func is not None:
            return desc.forward_func(item, x)
        return item(x)

    block = items[lo:hi]

    x = inputs
    for item, desc in zip(items[:lo], descs[:lo]):
        x = _apply(item, desc, x)

    suffix = list(zip(items[hi:], descs[hi:]))
    suffix_layers = [o for o, _ in suffix if isinstance(o, Layer)]
    head_layers = suffix_layers + (
        [pipe.loss_fn] if isinstance(pipe.loss_fn, Layer) else [])
    head_params = [p for l in head_layers for _, p in l.named_parameters()]

    def head_apply(head_values, act, tgt):
        flat = list(head_values)
        saved = []
        it = iter(flat)
        for l in head_layers:
            for _, p in l.named_parameters():
                saved.append((p, p._value))
                p._value = next(it)
        try:
            cur = Tensor(act, stop_gradient=True)
            for item, desc in suffix:
                cur = _apply(item, desc, cur)
            loss = pipe.loss_fn(cur, Tensor(tgt, stop_gradient=True))
            return loss._value if isinstance(loss, Tensor) else loss
        finally:
            for p, val in saved:
                p._value = val

    return pipeline_train_1f1b(
        _BlockPipe(block, n, v), x, labels, head_params, head_apply,
        n_microbatch, axis=axis, recompute=recompute)


def stack_device_major(per_vstage: Sequence, n: int, v: int):
    """Stack per-virtual-stage pytrees into device-major ``[n·v, ...]`` rows:
    row ``d·v + k`` ← virtual stage ``k·n + d`` (depth-first placement)."""
    order = [k * n + d for d in range(n) for k in range(v)]
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[per_vstage[i] for i in order])


def device_major_order(n: int, v: int) -> List[int]:
    return [k * n + d for d in range(n) for k in range(v)]
