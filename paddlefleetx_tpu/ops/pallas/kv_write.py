"""In-place KV-cache column write for TPU, written in Pallas.

The decode tick writes ONE new key/value column per slot (``W`` per
slot in a speculative verify window) into a cache leaf whose minor
dim is the position axis: the paged pool ``[P, h, d, page]`` or the
contiguous slot cache ``[b, h, d, capacity]``. As an XLA scatter
(``leaf.at[rows, :, :, cols].set(new)``) the index dims are the
leaf's outermost AND its lane dim, and the chip's compiler answers
with two transposing copies of the WHOLE leaf around the scatter —
87% of the serving cell's device time (PERF.md, PR 22-24).

This kernel leaves the leaf in the layout the decode kernels read
(``{3,2,1,0}``) and touches only the 128-lane blocks it writes: the
grid walks the rows, each step's ``(1, h, d, 128)`` block is picked
by scalar-prefetched ``(rows, cols // 128)``, comes in through the
ordinary block pipeline, gets its columns replaced, and goes back to
the SAME block of the aliased output. With the leaf donated to the
surrounding jit nothing else of it moves.

What it relies on (``core/serving.py::_page_maintenance`` keeps it):
two different rows never write the same block, except the reserved
``NULL_PAGE`` every free slot points at, whose content is never read
(there the block pipeline may read one row's block before another
row's write-back landed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# LANES: the lanes of one written block (a leaf's minor dim must tile
# by it) and the slots per group of the rows-on-lanes fresh values
from .flash_attention import (
    LANES, VMEM_DEFAULT, VMEM_MOST, _interpret, _sds,
)


def _kv_write_kernel(rows_ref, cols_ref, new_ref, leaf_ref, out_ref, *,
                     window):
    """One grid step = one (row, block) pair: step ``(i, s)`` owns the
    block of row ``i``'s first (``s == 0``) or last (``s == 1``)
    window position and replaces every window column that falls into
    it. A window inside one block visits that block twice and writes
    the same result twice (same block index: no second fetch, no
    write-back in between), so the later column never loses the
    earlier one.

    The fresh values arrive rows-on-lanes (``[W, h, d, 128]``, row
    ``i`` in lane ``i % 128``): a max-reduce over the one unmasked
    lane lifts row ``i``'s ``[h, d]`` column out exactly, the sign of
    a zero included. All of it runs in fp32, the v5e VPU's width and
    exact for bf16, int8 and fp32 alike."""
    i = pl.program_id(0)
    at = pl.program_id(1) * (window - 1)
    row, blk = rows_ref[i, at], cols_ref[i, at] // LANES
    block = leaf_ref[0].astype(jnp.float32)        # [h, d, LANES]
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 2)
    mine = lane == i % LANES

    def write(j, block):
        col = cols_ref[i, j]
        here = (rows_ref[i, j] == row) & (col // LANES == blk)
        new = jnp.max(
            jnp.where(mine, new_ref[j].astype(jnp.float32), -jnp.inf),
            axis=2, keepdims=True)                 # [h, d, 1]
        return jnp.where(lane == jnp.where(here, col % LANES, -1),
                         new, block)
    # a loop, not an unrolled window: unrolled, Mosaic stacks every
    # position's fp32 temporaries (121 MB of VMEM at W = 32)
    block = jax.lax.fori_loop(0, window, write, block)
    if jnp.issubdtype(out_ref.dtype, jnp.integer):
        block = block.astype(jnp.int32)
    out_ref[0] = block.astype(out_ref.dtype)


def kv_write(leaf, rows, cols, new):
    """``leaf.at[rows, :, :, cols].set(new)`` that rewrites only the
    blocks it writes, the leaf as aliased input and output.

    ``leaf [N, h, d, M]`` is a KV cache leaf (values: ``d`` = head
    dim, bf16 / fp32 / int8; int8-KV scale pools: ``d`` = 1, fp32),
    ``rows`` / ``cols`` ``[b, W]`` int32 give every written position's
    major index (physical page, or slot) and its column in the minor
    dim, ``new [b, W, h, d]`` the fresh values in the projections'
    native layout. Row ``i``'s ``W`` positions are consecutive (a
    decode tick's one token or a verify window), so they fall into at
    most two 128-lane blocks; duplicated positions (a window clipped
    at capacity) keep the last.

    Raises NotImplementedError where the caller must fall back to the
    XLA scatter (``ops/attention.py::kv_cache_write``).
    """
    if jax.default_backend() != "tpu" and not _interpret():
        raise NotImplementedError("kv_write kernel targets TPU")
    if leaf.ndim != 4 or new.ndim != 4:
        raise NotImplementedError(
            f"kv_write takes a [N, h, d, M] leaf and [b, W, h, d] "
            f"values, got {leaf.shape} and {new.shape}")
    _, h, d, m = leaf.shape
    b, window = new.shape[:2]
    if new.shape[2:] != (h, d) or rows.shape != (b, window) or \
            cols.shape != (b, window):
        raise NotImplementedError(
            f"values {new.shape} / indices {rows.shape}, {cols.shape} "
            f"do not match leaf {leaf.shape}")
    if m % LANES or not 1 <= window <= LANES:
        raise NotImplementedError(
            f"minor dim {m} must tile by {LANES} and the window "
            f"({window}) fit two blocks")
    if d != 1 and d % 8:
        raise NotImplementedError(f"head_dim {d} unsupported")
    if _vmem_bytes(leaf, window) > VMEM_MOST:
        raise NotImplementedError(
            f"window {window} of [{h}, {d}, {LANES}] blocks does not "
            f"fit the VMEM budget")
    return _kv_write_call(leaf, jnp.asarray(rows, jnp.int32),
                          jnp.asarray(cols, jnp.int32),
                          new.astype(leaf.dtype),
                          interpret=_interpret())


def _vmem_bytes(leaf, window: int) -> int:
    """What one grid step holds in VMEM: the leaf block double-buffered
    in and out, the resident fresh values of the window, three fp32
    working copies of a block."""
    _, h, d, _ = leaf.shape
    return h * max(d, 8) * LANES * (
        (4 + window) * leaf.dtype.itemsize + 12)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_write_call(leaf, rows, cols, new, *, interpret):
    """The ``pallas_call``, jitted so that the 2 (int8 KV: 4) leaves of
    each of a model's layers trace and lower ONE kernel per shape, not
    one per call: unjitted, the 48 calls of a 24-layer tick cost 7 s of
    every server start (my chip run, PR 24)."""
    _, h, d, _ = leaf.shape
    b, window = new.shape[:2]

    def block_of(i, s, rows, cols):
        at = s * (window - 1)
        return (rows[i, at], 0, 0, cols[i, at] // LANES)

    # [b, W, h, d] -> [W, h, d, b]: the rows on the lanes. [b, h, d, W]
    # would be the projections' layout, but a minor dim of W = 1 pads
    # to 128 lanes in HBM: 16 MB a leaf a tick at 64 slots of 16 x 64.
    fresh = jnp.pad(new.transpose(1, 2, 3, 0),
                    ((0, 0),) * 3 + ((0, -b % LANES),))
    return pl.pallas_call(
        functools.partial(_kv_write_kernel, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, min(window, 2)),
            in_specs=[
                # resident: its block changes every 128 rows, so one
                # buffer (a wide window's second would not fit)
                pl.BlockSpec(
                    (window, h, d, LANES),
                    lambda i, s, rows, cols: (0, 0, 0, i // LANES),
                    pipeline_mode=pl.Buffered(1)),
                pl.BlockSpec((1, h, d, LANES), block_of),
            ],
            out_specs=pl.BlockSpec((1, h, d, LANES), block_of),
        ),
        out_shape=_sds(leaf.shape, leaf.dtype, leaf),
        # operands: rows, cols, fresh, leaf -> the leaf IS the output
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(_vmem_bytes(leaf, window) * 5 // 4,
                                 VMEM_DEFAULT)),
        interpret=interpret,
        name="kv_write",
    )(rows, cols, fresh, leaf)
