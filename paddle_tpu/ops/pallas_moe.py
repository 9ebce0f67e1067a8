"""Pallas TPU kernel for the routed experts' grouped matmul.

What ``jax.lax.ragged_dot(rows, w, sizes)`` computes: rows ``[M, K]`` sorted
by group, ``sizes`` rows a group, group ``g``'s rows times ``w[g]``
(``[E, K, N]``), float32 accumulation, the result in ``rows.dtype``; rows
past the last group are whatever the output buffer held.  A decode launch
routes a handful of rows to each expert (128 rows x 4 experts over 64 is 8 a
group), so the product is bound by reading the weights, and this kernel
reads each TOUCHED expert's weights once (it is ahead of XLA's
``ragged-dot`` custom call at a prefill's hundreds of rows a group too:
:data:`STREAM_ROWS_PER_EXPERT`):

* the grid is ``(N / tn, visits)``, columns outermost.  A VISIT is a group
  that has rows, within one block of :func:`row_block` rows (a group that
  crosses from one block to the next is two visits, on ONE weight block);
  the list is computed in the program from ``sizes`` (:func:`visit_list`)
  and scalar-prefetched.  The weight block of a step is ``w[group, :, n
  tile]``, whole in the contraction, copied HBM -> VMEM by the pipeline
  while the step before computes.  The static grid is as long as the list
  can get (``E + blocks - 1``); the steps past its end name the block of the
  last visit, so they copy nothing, and compute nothing.  A group with no
  rows is on no list: it costs no copy and no step.
* a block of rows is resident in VMEM (512 x 2,048 bf16 is 2 MB: one block,
  copied once a column tile), and so is its ``[rows, tn]`` block of the
  output.  A visit multiplies its rows in PASSES of ``tm`` rows that start at
  the sublane tile the group starts in, and writes the group's rows alone
  (a select against what the block holds): a pass of 8 real rows costs the
  matrix unit what one of ``tm`` costs, once through the weights.

The two products of an expert layer are two calls of one kernel, the
SwiGLU between them XLA's fusion (``parallel/moe.py`` ``_grouped_swiglu``);
the launch is a ``jax.jit`` of its own, so the expert layers of a step
program trace and lower it once a shape.

rows: [M, K]    w: [E, K, N]    sizes: [E] int32    ->    [M, N] rows.dtype
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import _mesh_mp, _on_tpu    # what the RULE reads
from .pallas_x32 import no_x64

LANES = 128
#: rows of a pass over an expert's weights.  One layer alone, ms (my chip
#: runs, PR 51, ``tools/moe_step0.py``): passes of 16 / 32 / 64 / 128 rows
#: read 1.685 / 1.684 / 1.689 / 1.688 at the latent cell's 8 rows an expert
#: (a pass of 8 real rows costs what one of 128 costs: the weights once
#: through the matrix unit), passes of 32 / 64 / 128 / 256 read 3.18 / 2.78 /
#: 2.93 / 3.42 at 256 rows an expert and 10.78 / 8.89 / 8.94 / 9.42 at 1,024
PASS_ROWS = 64
#: static rows a held expert up to which the kernel is chosen, ``ragged_dot``
#: above.  NO crossing was found as far as was measured.  ``ragged_dot`` /
#: the kernel at passes of 64, ms a layer (both products and the SwiGLU; my
#: chip runs, PR 51) at 8, 128, 256, 512 and 1,024 rows an expert:
#:   64 x ([2048, 3072], [1536, 2048])   4.02 / 1.69, 4.99 / 1.92, 6.20 / 2.78,
#:                                       8.51 / 5.33, 14.29 / 8.89
#:   64 x ([3584, 2048], [1024, 3584])   from 128: 5.82 / 2.31, 7.02 / 3.15,
#:                                       9.80 / 6.20, 15.82 / 10.77
#:   16 x ([4096, 8192], [4096, 4096])   from 128: 6.49 / 2.49, 7.54 / 3.84,
#:                                       11.09 / 7.45, 17.32 / 12.10
#:   16 x ([7168, 4096], [2048, 7168])   from 128: 5.29 / 2.43, 6.77 / 3.28,
#:                                       9.14 / 5.31, 14.29 / 9.91
#: The constant is where the measurement ends, not where the kernel falls
#: behind; its results were bit for bit ``ragged_dot``'s at bf16 throughout
STREAM_ROWS_PER_EXPERT = 1024
#: bytes of rows a block may hold (one block where all rows fit: the rule)
ROW_BLOCK_BYTES = 4 << 20
#: bytes of one weight block ``[K, tn]`` (the pipeline holds two)
WEIGHT_BLOCK_BYTES = 8 << 20
#: what the kernel may take of VMEM (128 MiB a v5e core; Mosaic's default
#: scoped limit of 16 MiB is less than two weight blocks and the rows)
VMEM_LIMIT_BYTES = 64 << 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def sublanes(dtype) -> int:
    """Rows of one packed sublane tile of ``dtype`` (16 of bf16, 8 of
    float32): what a pass's first row is aligned to."""
    return 32 // jnp.dtype(dtype).itemsize


def tileable(rows, w) -> bool:
    """Whether the kernel takes this product at all: rows and weights of
    one dtype, bf16 or float32, both of the weights' dimensions in whole
    lane tiles."""
    K, N = w.shape[1:]
    return (rows.dtype == w.dtype
            and rows.dtype in (jnp.dtype(jnp.bfloat16),
                               jnp.dtype(jnp.float32))
            and K % LANES == 0 and N % LANES == 0)


def streams(rows, w_gate_up, w_down) -> bool:
    """The rule of ``parallel.moe._grouped_swiglu``: on a TPU, no mesh with
    ``mp`` > 1 (a kernel of one shard does not go into a program laid over
    more), both products :func:`tileable`, and at most
    ``STREAM_ROWS_PER_EXPERT`` STATIC rows a held expert (the rows of the
    launch over the experts stacked: what the program can see of the size of
    a group)."""
    return (_on_tpu() and _mesh_mp() == 1 and tileable(rows, w_gate_up)
            and tileable(rows, w_down)
            and rows.shape[0] <= STREAM_ROWS_PER_EXPERT * w_gate_up.shape[0])


def row_block(M: int, K: int, dtype, tm: int = PASS_ROWS) -> int:
    """Rows a block holds: all ``M`` (in whole passes) where that is within
    ``ROW_BLOCK_BYTES``, else the most whole passes within it that divide
    them (no row is then copied to fill a last block)."""
    whole = -(-M // tm) * tm
    fit = ROW_BLOCK_BYTES // (K * jnp.dtype(dtype).itemsize)
    return max((b for b in range(tm, min(whole, fit) + 1, tm)
                if whole % b == 0), default=tm)


def column_tile(K: int, N: int, dtype) -> int:
    """Columns a weight block holds: the largest divisor of ``N`` in whole
    lane tiles whose ``[K, tn]`` block is within ``WEIGHT_BLOCK_BYTES``."""
    per = K * jnp.dtype(dtype).itemsize
    fit = [tn for tn in range(LANES, N + 1, LANES)
           if N % tn == 0 and tn * per <= WEIGHT_BLOCK_BYTES]
    return max(fit, default=LANES)


def visit_list(sizes, block: int, n_blocks: int):
    """The visits of a launch, from ``sizes`` ``[E]``: for each of the ``E +
    n_blocks - 1`` grid steps ``(group, row block, first row, end row)``,
    rows counted within the block, and how many of the steps are visits.
    The steps after the last visit repeat it (no block changes: nothing is
    copied); with no row at all every step names group 0, block 0."""
    i32 = jnp.int32
    sizes = sizes.astype(i32)
    E = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    # blocks a group has rows in: none of an empty one
    first_block = starts // block
    spans = jnp.where(sizes > 0, (ends - 1) // block - first_block + 1, 0)
    upto = jnp.cumsum(spans)
    count = upto[-1]
    step = jnp.minimum(jnp.arange(E + n_blocks - 1, dtype=i32),
                       jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(upto, step, side="right"),
                        E - 1).astype(i32)
    blk = jnp.clip(first_block[group] + step - (upto - spans)[group],
                   0, n_blocks - 1).astype(i32)
    lo = jnp.clip(starts[group] - blk * block, 0, block)
    hi = jnp.clip(ends[group] - blk * block, 0, block)
    return group, blk, lo.astype(i32), hi.astype(i32), count.reshape(1)


def _kernel(group_ref, blk_ref, lo_ref, hi_ref, count_ref, rows_ref, w_ref,
            out_ref, *, tm, align):
    """One grid step: the rows ``[lo, hi)`` of its block times the weight
    block, in passes of ``tm`` rows from the sublane tile ``lo`` lies in."""
    del group_ref, blk_ref             # the index maps' operands
    v = pl.program_id(1)
    block = rows_ref.shape[0]

    @pl.when(v < count_ref[0])
    def _visit():
        lo, hi = lo_ref[v], hi_ref[v]
        first = jax.lax.div(lo, jnp.int32(align)) * align
        passes = jax.lax.div(hi - first + (tm - 1), jnp.int32(tm))

        def one_pass(j, _):
            # the last pass of a block is moved back to end with it: rows
            # covered twice are written twice with the same values
            base = pl.multiple_of(jnp.minimum(first + j * tm, block - tm),
                                  align)
            at = (pl.ds(base, tm), slice(None))
            acc = jnp.dot(rows_ref[at], w_ref[...],
                          preferred_element_type=jnp.float32)
            row = base + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
            mine = jnp.logical_and(row >= lo, row < hi)
            out_ref[at] = jnp.where(
                mine, acc, out_ref[at].astype(jnp.float32)
            ).astype(out_ref.dtype)
            return 0

        jax.lax.fori_loop(0, passes, one_pass, 0)


def grouped_matmul(rows, w, sizes, *, tm: int = None, tn: int = None,
                   block: int = None):
    """``jax.lax.ragged_dot(rows, w, sizes)`` with each touched group's
    weights read once.  ``tm`` / ``tn`` / ``block``: the pass, the column
    tile and the row block (``None``: :data:`PASS_ROWS`,
    :func:`column_tile`, :func:`row_block`)."""
    M, K = rows.shape
    N = w.shape[2]
    tm = tm or PASS_ROWS
    tn = tn or column_tile(K, N, w.dtype)
    block = block or row_block(M, K, rows.dtype, tm)
    if N % tn or tn % LANES or block % tm or tm % sublanes(rows.dtype):
        raise ValueError(f"tiles tm {tm}, tn {tn}, block {block} do not "
                         f"divide [{M}, {K}] x [{K}, {N}]")
    more = -M % block
    if more:        # whole blocks: rows no group owns
        rows = jnp.pad(rows, ((0, more), (0, 0)))
    out = _grouped_matmul(rows, w, sizes, tm=tm, tn=tn, block=block,
                          interpret=_interpret())
    return out[:M] if more else out


# A jit of its own: a step program calls the kernel twice an expert layer at
# two shapes, and this way traces and lowers each ONCE.  XLA inlines the
# calls, and each copy's ``op_name`` keeps the scope path of its own call
# site (the benchmark's ``moe_experts`` reader).
@functools.partial(jax.jit, static_argnames=("tm", "tn", "block",
                                             "interpret"))
def _grouped_matmul(rows, w, sizes, *, tm, tn, block, interpret):
    M, K = rows.shape
    E, _, N = w.shape
    n_blocks = M // block
    visits = visit_list(sizes, block, n_blocks)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,      # group, block, lo, hi, count
        grid=(N // tn, E + n_blocks - 1),
        in_specs=[
            pl.BlockSpec((block, K),
                         lambda n, v, group, blk, *_: (blk[v], 0)),
            pl.BlockSpec((None, K, tn),
                         lambda n, v, group, *_: (group[v], 0, n)),
        ],
        out_specs=pl.BlockSpec((block, tn),
                               lambda n, v, group, blk, *_: (blk[v], n)),
    )
    kernel = functools.partial(_kernel, tm=tm, align=sublanes(rows.dtype))
    with no_x64():
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((M, N), rows.dtype),
            # steps run in order: an output block is written by the visits
            # of its rows one after the other
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
            name="moe_grouped_matmul",      # its name in a device trace
        )(*visits, rows, w)
