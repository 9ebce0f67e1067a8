"""Pallas TPU kernel for the gated delta rule's DECODE step over slot pools.

What :func:`~paddle_tpu.ops.gated_delta.gated_delta_step` computes, one
token a row, in float32 (``b`` a row, ``h`` of ``H`` value heads, key head
``h // (H / H_k)``, ``S`` the head's ``[d_k, d_v]`` state)::

    held = alpha[b, h] * sum_dk S * k[:, None]           what S holds for k
    u    = beta[b, h] * (v[b, h] - held)                 the row to write
    S'   = alpha[b, h] * S + k[:, None] * u[None, :]
    o[b, h] = sum_dk S' * q[:, None]

but with ``S`` read from the row's SLOT of the pool (``[slots, H, d_k,
d_v]`` float32) and ``S'`` written to the same slot: the XLA path gathers a
``[B, H, d_k, d_v]`` temporary, passes over it twice and scatters it back
row by row, and the state crosses HBM five to six times a layer; here it
crosses twice.  The two products with the state are elementwise multiplies
and sums over ``d_k`` on the VPU, NOT the MXU (whose default rounds both
factors to bf16): the state is float32 in the pool and is never rounded.
The pool is aliased input to output and stays in HBM (``memory_space=ANY``);
the slot ids, and each (row, head)'s ``alpha`` and ``beta``, are the
scalar-prefetch operands.

A row's state (4.2 MB at 64 heads of 128 x 128) is more than the kernel may
hold at once, so the unit of copy is a CHUNK: :func:`head_group` heads of
one row (1 MB at 16).  A grid step takes :data:`STEP_ROWS` rows; the chunks
of the launch go round a ring of :data:`RING` VMEM buffers: chunk ``c`` is
copied into buffer ``c % RING``, stepped THERE and copied out of it, while
the chunks after it are on their way in and the one before it on its way
out.  The rows of a step, a row's chunks and a chunk's heads are LOOPS
inside the kernel, not unrolled: a step program holds the kernel once a
mixer layer and loads that code at every warm start.

``q`` and ``k`` are wanted DOWN sublanes (``S[d_k, d_v] * k[:, None]``), so
XLA hands both over as one ``[B, d_k, lanes]`` block, a key head a lane
(``q``'s at lanes ``0 .. H_k - 1``, ``k``'s from ``H_k`` on: 64 KB a row
beside the 4.2 MB state), and the kernel takes a head's column by a mask
over the lanes.  Value heads share key heads in groups of ``H / H_k``: the
kernel indexes the key head, and nothing is repeated.

Padding rows all name the null slot 0, the only slot a launch can name
twice: their copies race on it, and nothing reads what it holds.  Every
slot the launch does not name is left bit for bit as it was, and a real
row's result depends on no other row.

The launch is a ``jax.jit`` of its own (:func:`_step`): a step program
calls it once a mixer layer at one shape, and so traces and lowers it once.

q, k: [B, H_k, d_k] float32     v: [B, H, d_v] float32
log_alpha, beta: [B, H] float32
state_pool: [slots, H, d_k, d_v] float32        slots: [B] int32
→ (o [B, H, d_v] float32, state_pool with the rows' slots stepped)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_x32 import no_x64

#: rows a grid step takes (a launch of fewer is one step; one of more, and
#: no multiple, gets null-slot rows appended): a step's blocks of q|k, v and
#: o are copied by the pipeline while the step before is computed on
STEP_ROWS = 8
#: value heads of a row copied in, stepped and copied out TOGETHER (a
#: divisor of the heads, in whole key heads): 8, 16, 32 and 64 heads a
#: chunk read 1.79, 1.71, 1.71 and 1.71 ms a layer at 128 rows of 64 heads
#: of 128 x 128 against a floor of 1.31 (my chip run, PR 50)
HEAD_GROUP = 16
#: buffers of the ring: one chunk stepped, one on its way out, the others on
#: their way in; 3, 4 and 6 read 1.72, 1.71 and 1.70 ms (my chip run, PR 50)
RING = 4
#: VMEM the ring may hold (:func:`head_group` takes fewer heads until it fits)
STATE_BUFFER_BYTES = 8 << 20
LANES = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def head_group(heads: int, rep: int, dk: int, dv: int) -> int:
    """Value heads of a chunk for states of ``[heads, dk, dv]`` float32
    where a key head serves ``rep`` value heads: the largest divisor of
    ``heads`` up to ``HEAD_GROUP`` that is whole key heads and whose ring
    fits ``STATE_BUFFER_BYTES`` (at least one key head's)."""
    fit = [g for g in range(rep, heads + 1, rep)
           if heads % g == 0 and g <= HEAD_GROUP
           and RING * g * dk * dv * 4 <= STATE_BUFFER_BYTES]
    return max(fit, default=rep)


def _div(a, b: int):      # of non-negative int32s (``//`` lowers through a
    return jax.lax.div(a, jnp.int32(b))     # 64-bit ``sign`` under x64)


def _rem(a, b: int):
    return jax.lax.rem(a, jnp.int32(b))


def _state_step_kernel(slots_ref, alpha_ref, beta_ref, qk_ref, v_ref,
                       pool_in, o_ref, pool_out, ring, sems,
                       *, heads, rep, group):
    """One grid step: its rows, ``heads // group`` chunks each.  The
    launch's first chunk starts the copies in of chunks ``0 .. RING - 2``;
    chunk ``c`` waits for its own, steps its heads where they lie, starts
    its copy out, waits for chunk ``c - 1``'s copy out and starts the copy
    in of chunk ``c + RING - 1`` into that buffer; the launch's last chunk
    waits for its own copy out."""
    step_rows = v_ref.shape[0]
    per_row = heads // group
    n_chunks = pl.num_programs(0) * step_rows * per_row
    first_row = pl.program_id(0) * step_rows
    hk = heads // rep
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, qk_ref.shape[-1]), 1)

    def copy(c, out, wait):
        """Start (or wait for) the copy in or out of chunk ``c``."""
        b = _rem(c, RING)
        slot = slots_ref[_div(c, per_row)]
        at = pl.ds(_rem(c, per_row) * group, group)
        dma = (pltpu.make_async_copy(ring.at[b], pool_out.at[slot, at],
                                     sems.at[1, b]) if out else
               pltpu.make_async_copy(pool_in.at[slot, at], ring.at[b],
                                     sems.at[0, b]))
        dma.wait() if wait else dma.start()

    def chunk(i, _):
        r, g = _div(i, per_row), _rem(i, per_row)   # the step's row, its chunk
        c = first_row * per_row + i

        @pl.when(c == 0)
        def _first():
            for ahead in range(RING - 1):
                @pl.when(ahead < n_chunks)
                def _():
                    copy(jnp.int32(ahead), out=False, wait=False)

        copy(c, out=False, wait=True)
        states = ring.at[_rem(c, RING)]         # [group, d_k, d_v]
        qk = qk_ref[r]                          # [d_k, lanes]
        scalars = (first_row + r) * heads       # of this row, in SMEM

        def column(at):         # a lane of q | k, down sublanes: [d_k, 1]
            return jnp.sum(jnp.where(lane == at, qk, np.float32(0)), axis=1,
                           keepdims=True)

        def key_head(j, _):
            kh = g * (group // rep) + j         # the key head, of the row's
            q_col, k_col = column(kh), column(hk + kh)

            def value_head(i, _):
                n = j * rep + i                 # the head, of the chunk's
                h = g * group + n
                alpha = alpha_ref[scalars + h]
                beta = beta_ref[scalars + h]
                S = states[n]                   # [d_k, d_v]
                held = alpha * jnp.sum(S * k_col, axis=0, keepdims=True)
                u = beta * (v_ref[r, pl.ds(h, 1), :] - held)    # [1, d_v]
                S = alpha * S + k_col * u
                states[n] = S
                o_ref[r, pl.ds(h, 1), :] = jnp.sum(S * q_col, axis=0,
                                                   keepdims=True)
                return 0

            jax.lax.fori_loop(0, rep, value_head, 0)
            return 0

        jax.lax.fori_loop(0, group // rep, key_head, 0)
        copy(c, out=True, wait=False)

        @pl.when(c >= 1)
        def _drained():
            copy(c - 1, out=True, wait=True)

        @pl.when(c + RING - 1 < n_chunks)
        def _ahead():
            copy(c + RING - 1, out=False, wait=False)

        @pl.when(c == n_chunks - 1)
        def _last():
            copy(c, out=True, wait=True)

        return 0

    jax.lax.fori_loop(0, step_rows * per_row, chunk, 0)


def state_step(q, k, v, log_alpha, beta, state_pool, slots):
    """The decode step of every row, in place on its slot of
    ``state_pool``; ``q`` and ``k`` by KEY head (``[B, H_k, d_k]``, not
    repeated).  Returns ``(o [B, H, d_v], the pool)``."""
    rows, hk, _ = q.shape
    heads, dk, dv = state_pool.shape[1:]
    f32 = jnp.float32
    slots = slots.astype(jnp.int32)             # Mosaic has no i64
    # q | k down sublanes, a key head a lane, in whole lane tiles
    qk = jnp.concatenate([q, k], axis=1).astype(f32).transpose(0, 2, 1)
    qk = jnp.pad(qk, ((0, 0), (0, 0), (0, -2 * hk % LANES)))
    alpha, beta = jnp.exp(log_alpha.astype(f32)), beta.astype(f32)
    step_rows = min(STEP_ROWS, rows)
    more = -rows % step_rows
    if more:
        # whole steps: the rows appended name the null slot, as a bucket's
        # padding rows do
        qk, v, alpha, beta = (
            jnp.pad(a, ((0, more),) + ((0, 0),) * (a.ndim - 1))
            for a in (qk, v, alpha, beta))
        slots = jnp.pad(slots, (0, more))
    o, pool = _step(qk, v.astype(f32), alpha.reshape(-1), beta.reshape(-1),
                    state_pool, slots, step_rows=step_rows, rep=heads // hk,
                    group=head_group(heads, heads // hk, dk, dv),
                    interpret=_interpret())
    return o[:rows], pool


# A jit of its own: a step program calls the kernel once a mixer layer at one
# shape, and this way traces and lowers it ONCE.  XLA inlines the calls, and
# each copy's ``op_name`` keeps the scope path of its own call site (the
# benchmark's ``gdn_step`` reader).
@functools.partial(jax.jit, static_argnames=("step_rows", "rep", "group",
                                             "interpret"))
def _step(qk, v, alpha, beta, state_pool, slots, *, step_rows, rep, group,
          interpret):
    B, H, dv = v.shape
    dk, lanes = qk.shape[1:]

    def rows_of(*widths):       # a step's rows of a [B, *widths] operand
        return pl.BlockSpec((step_rows,) + widths,
                            lambda i, *prefetched: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # slots, alpha, beta
        grid=(B // step_rows,),
        in_specs=[
            rows_of(dk, lanes), rows_of(H, dv),
            # the pool stays in HBM: the kernel copies the slots the
            # scalar-prefetched ids name, and no others
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[rows_of(H, dv), pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((RING, group, dk, dv), jnp.float32),
            pltpu.SemaphoreType.DMA((2, RING)),         # (in | out, buffer)
        ],
    )
    kernel = functools.partial(_state_step_kernel, heads=H, rep=rep,
                               group=group)
    with no_x64():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                       jax.ShapeDtypeStruct(state_pool.shape, jnp.float32)],
            # operand 5 (after the three prefetched): the pool, in place
            input_output_aliases={5: 1},
            # steps run in order: the ring goes on from one to the next
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="gdn_state_step",      # its name in a device trace
        )(slots, alpha, beta, qk, v, state_pool)
