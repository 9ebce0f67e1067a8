"""Pallas TPU kernel for the selective-scan DECODE step over slot pools.

What :func:`~paddle_tpu.ops.selective_scan.selective_step` computes, one
token a row, in float32 (``b`` a row, ``n`` of ``N`` state indices, ``c`` of
``D`` channels)::

    h'[n, c] = exp(dt[b, c] * A[n, c]) * h[n, c] + (dt[b, c] * x[b, c]) * B[b, n]
    y[b, c]  = sum_n h'[n, c] * C[b, n]

but with ``h`` read from the row's SLOT of the pool (``[slots, N, D]``
float32) and ``h'`` written to the same slot: the XLA path gathers a
``[B, N, D]`` temporary, steps it and scatters it back, and the state
crosses HBM four to six times a layer; here it crosses twice.  The pool is
aliased input to output and stays in HBM (``memory_space=ANY``); the slot
ids are the scalar-prefetch operand.  A grid step takes :data:`STEP_ROWS`
rows (a float32 tile's sublanes: a step's block of x, dt and y is whole
tiles) in groups of :data:`GROUP_ROWS`: one DMA a row into one of two VMEM
buffers while the group before is computed on, one DMA a row out of one of
two more while the next group is computed on.  The groups of a step, the
rows of a group and a row's channels (:data:`CHANNEL_TILE` at a time) are
LOOPS inside the kernel, not unrolled: a step program holds the kernel once
a mixer layer and loads that code at every warm start.

Padding rows all name the null slot 0, the only slot a launch can name
twice: their copies race on it, and nothing reads what it holds.  Every
slot the launch does not name is left bit for bit as it was, and a real
row's result depends on no other row.

The launch is a ``jax.jit`` of its own (:func:`_step`): a step program
calls it once a mixer layer at one shape, and so traces and lowers it once.

x, dt: [B, D] float32     A: [N, D] float32     Bm, Cm: [B, N] float32
state_pool: [slots, N, D] float32               slots: [B] int32
→ (y [B, D] float32, state_pool with the rows' slots stepped)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_x32 import no_x64

#: rows a grid step takes: the sublanes of a float32 tile, so that a step's
#: ``[STEP_ROWS, D]`` block of x, dt and y is whole tiles of the operand as
#: it lies in HBM (launches of other row counts get null-slot rows appended)
STEP_ROWS = 8
#: rows copied in, stepped and copied out TOGETHER, a divisor of
#: ``STEP_ROWS``: one row a group read 0.32 ms a layer at 256 rows of
#: ``[16, 5120]``, two 0.29, four 0.28, eight 0.28 against a floor of 0.20
#: (my chip run, PR 41)
GROUP_ROWS = 4
#: VMEM the state buffers may hold: in and out, two buffers each, of
#: ``group`` states (:func:`group_rows` halves the group until they fit)
STATE_BUFFER_BYTES = 8 << 20
#: channels of a row an iteration of the kernel's inner loop computes on:
#: ``[N, CHANNEL_TILE]`` of h, A and the decay stay in registers
CHANNEL_TILE = 512


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def group_rows(n: int, d: int) -> int:
    """Rows of a group for states of ``[n, d]`` float32: ``GROUP_ROWS``,
    fewer where ``2 (in, out) x 2 buffers`` of them would not fit
    ``STATE_BUFFER_BYTES``."""
    group = GROUP_ROWS
    while group > 1 and 4 * group * n * d * 4 > STATE_BUFFER_BYTES:
        group //= 2
    return group


def _state_step_kernel(slots_ref, x_ref, dt_ref, b_ref, c_ref, a_ref,
                       pool_in, y_ref, pool_out, in_buf, out_buf, sems,
                       *, group, tile):
    """One grid step: ``STEP_ROWS`` rows, in groups of ``group``.  Group
    ``q`` of the launch starts the copies in of group ``q + 1``, waits for
    its own, steps its rows into the buffer that group ``q - 2`` has
    finished copying out of, and starts its copies out; the launch's last
    group waits for what is still in flight."""
    groups = STEP_ROWS // group
    n_groups = pl.num_programs(0) * groups
    first = pl.program_id(0) * groups       # this step's first group
    n, d = a_ref.shape

    def copies(q, out, wait):
        """Start (or wait for) the copies in or out of group ``q``."""
        b = q % 2

        def one(r, _):
            slot = slots_ref[q * group + r]
            copy = (pltpu.make_async_copy(out_buf.at[b, r], pool_out.at[slot],
                                          sems.at[1, b]) if out else
                    pltpu.make_async_copy(pool_in.at[slot], in_buf.at[b, r],
                                          sems.at[0, b]))
            copy.wait() if wait else copy.start()
            return 0

        jax.lax.fori_loop(0, group, one, 0)

    # a row's B and C lie along lanes; the step wants them down sublanes
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

    def column(row):            # [1, N] -> [N, 1], exactly
        return jnp.sum(jnp.where(eye, row, np.float32(0)), axis=1,
                       keepdims=True)

    def step_rows(k, _):
        q = first + k
        buf = q % 2

        @pl.when(q == 0)
        def _first():
            copies(q, out=False, wait=False)

        @pl.when(q + 1 < n_groups)
        def _next():
            copies(q + 1, out=False, wait=False)

        copies(q, out=False, wait=True)

        @pl.when(q >= 2)
        def _drained():
            copies(q - 2, out=True, wait=True)

        def row(r, _):
            one = pl.ds(k * group + r, 1)       # the row, of the step's
            b_col = column(b_ref[one, :])
            c_col = column(c_ref[one, :])

            def channels(j, _):
                cs = pl.ds(pl.multiple_of(j * tile, tile), tile)
                dt = dt_ref[one, cs]                         # [1, tile]
                h = (jnp.exp(dt * a_ref[:, cs]) * in_buf[buf, r, :, cs]
                     + (dt * x_ref[one, cs]) * b_col)        # [N, tile]
                out_buf[buf, r, :, cs] = h
                y_ref[one, cs] = jnp.sum(h * c_col, axis=0, keepdims=True)
                return 0

            jax.lax.fori_loop(0, d // tile, channels, 0)
            return 0

        jax.lax.fori_loop(0, group, row, 0)
        copies(q, out=True, wait=False)

        @pl.when(q == n_groups - 1)
        def _last():
            @pl.when(q >= 1)
            def _before():
                copies(q - 1, out=True, wait=True)

            copies(q, out=True, wait=True)

        return 0

    jax.lax.fori_loop(0, groups, step_rows, 0)


def state_step(x, dt, A, Bm, Cm, state_pool, slots):
    """The decode step of every row, in place on its slot of
    ``state_pool``; returns ``(y [B, D], the pool)``."""
    rows = x.shape[0]
    slots = slots.astype(jnp.int32)             # Mosaic has no i64
    more = -rows % STEP_ROWS
    if more:
        # whole steps: the rows appended name the null slot, as a bucket's
        # padding rows do
        x, dt, Bm, Cm = (jnp.pad(a, ((0, more), (0, 0)))
                         for a in (x, dt, Bm, Cm))
        slots = jnp.pad(slots, (0, more))
    y, pool = _step(x, dt, A, Bm, Cm, state_pool, slots,
                    group=group_rows(*A.shape), interpret=_interpret())
    return y[:rows], pool


# A jit of its own: a step program calls the kernel once a mixer layer at one
# shape, and this way traces and lowers it ONCE.  XLA inlines the calls, and
# each copy's ``op_name`` keeps the scope path of its own call site (the
# benchmark's ``ssm_step`` reader).
@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def _step(x, dt, A, Bm, Cm, state_pool, slots, *, group, interpret):
    B, D = x.shape
    N = A.shape[0]
    # whole lane tiles; a width the kernel was forced past takes one pass
    tile = next((t for t in (CHANNEL_TILE, 256, 128) if D % t == 0), D)

    def rows_of(width):         # a step's rows of a [B, width] operand
        return pl.BlockSpec((STEP_ROWS, width), lambda i, slots: (i, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,   # slots
        grid=(B // STEP_ROWS,),
        in_specs=[
            rows_of(D), rows_of(D), rows_of(N), rows_of(N),
            pl.BlockSpec((N, D), lambda i, slots: (0, 0)),   # A, resident
            # the pool stays in HBM: the kernel copies the slots the
            # scalar-prefetched ids name, and no others
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[rows_of(D), pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((2, group, N, D), jnp.float32),  # states copied in
            pltpu.VMEM((2, group, N, D), jnp.float32),  # states stepped
            pltpu.SemaphoreType.DMA((2, 2)),            # (in | out, buffer)
        ],
    )
    kernel = functools.partial(_state_step_kernel, group=group, tile=tile)
    with no_x64():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, D), jnp.float32),
                       jax.ShapeDtypeStruct(state_pool.shape, jnp.float32)],
            # operand 6 (after the slots): the pool, stepped in place
            input_output_aliases={6: 1},
            # steps run in order: each starts the next one's copies in
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name="ssm_state_step",      # its name in a device trace
        )(slots, x, dt, Bm, Cm, A, state_pool)
