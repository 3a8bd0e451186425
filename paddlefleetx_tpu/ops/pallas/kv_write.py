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
(``{3,2,1,0}``) and touches only the 128-lane blocks it writes: each
step's ``(1, h, d, 128)`` block is picked by scalar-prefetched
``(rows, cols // 128)``, comes in through the ordinary block
pipeline, gets its columns replaced, and goes back to the SAME block
of the aliased output. With the leaf donated to the surrounding jit
nothing else of it moves.

The launch is shaped by the rows that are live in the tick, not by
the server's capacity (PERF.md, PR 28). The grid's first axis has the
dynamic extent of the LIVE rows (:func:`_live_rows`, a cumulative sum
over ``[b]`` handed over by scalar prefetch beside ``rows`` and
``cols``, as ``flash_attention.py::_paged_walk`` does for the decode
kernel): in the paged pool a row whose every position resolves to
``NULL_PAGE`` is a free slot and takes no step, and ``NULL_PAGE`` is
not written at all; in the contiguous cache every row is live. One
call takes every leaf of one shape a layer writes (K and V; the two
scale leaves of an int8 cache): same walk, same indices, a fresh
operand and an aliased output each.

What it relies on (``core/serving.py::_page_maintenance`` keeps it):
two different live rows never write the same block, and a free slot's
page-table row is all ``NULL_PAGE`` (``_sync_pt``), whose content is
never read and, since PR 28, never written.
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
    LANES, NULL_PAGE, VMEM_DEFAULT, VMEM_MOST, _interpret, _sds,
)


def _live_rows(rows, paged: bool):
    """The grid's walk: ``(order [b], live)``, the first ``live``
    entries of ``order`` the rows that write anywhere, ascending. In
    the paged pool a row is dead when every position of its window
    resolves to ``NULL_PAGE``; in the contiguous slot cache the major
    index is the slot, page 0 is slot 0, and every row is live. A
    handful of integer ops on ``[b]`` (the t-th live row is the count
    of rows whose running total stays at or under ``t``: one ``[b, b]``
    compare, no search loop, no gather); every layer of a tick builds
    the same walk from the same operands, and XLA keeps one
    (tests/test_chip_compile.py)."""
    b = rows.shape[0]
    if not paged:
        return jnp.arange(b, dtype=jnp.int32), jnp.int32(b)
    end = jnp.cumsum(jnp.any(rows != NULL_PAGE, axis=1), dtype=jnp.int32)
    t = jnp.arange(b, dtype=jnp.int32)
    order = jnp.sum(end[None, :] <= t[:, None], axis=1, dtype=jnp.int32)
    return jnp.minimum(order, b - 1), end[-1]


def _kv_write_kernel(rows_ref, cols_ref, order_ref, *refs, window,
                     paged):
    """One grid step = one (live row, block) pair: step ``(t, s)`` owns
    the block of row ``i = order[t]``'s first (``s == 0``) or last
    (``s == 1``) window position and replaces every window column that
    falls into it, in each of the call's leaves (``refs``: their fresh
    values, the leaves, the aliased outputs). A window inside one block
    visits that block twice and writes the same result twice (same
    block index: no second fetch, no write-back in between), so the
    later column never loses the earlier one. A position on
    ``NULL_PAGE`` (a live row's window past its mapped pages; the one
    step a tick with nothing live still takes, on a dead row) writes
    nothing: the block goes back as it came.

    The fresh values arrive rows-on-lanes (``[W, h, d, 128]``, row
    ``i`` in lane ``i % 128``): a max-reduce over the one unmasked
    lane lifts row ``i``'s ``[h, d]`` column out exactly, the sign of
    a zero included. All of it runs in fp32, the v5e VPU's width and
    exact for bf16, int8 and fp32 alike."""
    t = pl.program_id(0)
    i = order_ref[t]
    at = pl.program_id(1) * (window - 1)
    row, blk = rows_ref[i, at], cols_ref[i, at] // LANES

    def one_leaf(new_ref, leaf_ref, out_ref):
        """This step's block of one leaf, its columns replaced."""
        block = leaf_ref[0].astype(jnp.float32)    # [h, d, LANES]
        lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 2)
        mine = lane == i % LANES

        def write(j, block):
            col = cols_ref[i, j]
            here = (rows_ref[i, j] == row) & (col // LANES == blk)
            if paged:
                here &= row != NULL_PAGE
            new = jnp.max(
                jnp.where(mine, new_ref[j].astype(jnp.float32),
                          -jnp.inf),
                axis=2, keepdims=True)             # [h, d, 1]
            return jnp.where(lane == jnp.where(here, col % LANES, -1),
                             new, block)
        # a loop, not an unrolled window: unrolled, Mosaic stacks every
        # position's fp32 temporaries (121 MB of VMEM at W = 32)
        block = jax.lax.fori_loop(0, window, write, block)
        if jnp.issubdtype(out_ref.dtype, jnp.integer):
            block = block.astype(jnp.int32)
        out_ref[0] = block.astype(out_ref.dtype)

    n = len(refs) // 3
    for trio in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        one_leaf(*trio)


def kv_write(leaves, rows, cols, news, paged: bool = True):
    """``leaf.at[rows, :, :, cols].set(new)`` for every ``(leaf, new)``
    of ``zip(leaves, news)`` in ONE kernel launch that rewrites only
    the blocks the live rows write, each leaf an aliased input and
    output. Returns the leaves as a tuple.

    ``leaves`` are KV cache leaves of one shape and dtype ``[N, h, d,
    M]`` (a layer's K and V values: ``d`` = head dim, bf16 / fp32 /
    int8; or its two int8-KV scale pools: ``d`` = 1, fp32), ``rows`` /
    ``cols`` ``[b, W]`` int32 give every written position's major
    index (physical page, or slot with ``paged=False``) and its column
    in the minor dim, ``news`` the fresh values ``[b, W, h, d]`` in the
    projections' native layout. Row ``i``'s ``W`` positions are
    consecutive (a decode tick's one token or a verify window), so
    they fall into at most two 128-lane blocks; duplicated positions
    (a window clipped at capacity) keep the last. With ``paged`` a
    position on ``NULL_PAGE`` is not written and a row with no other
    position costs nothing.

    Raises NotImplementedError where the caller must fall back to the
    XLA scatter (``ops/attention.py::kv_cache_write``).
    """
    if jax.default_backend() != "tpu" and not _interpret():
        raise NotImplementedError("kv_write kernel targets TPU")
    leaves, news = tuple(leaves), tuple(news)
    leaf, new = leaves[0], news[0]
    if leaf.ndim != 4 or new.ndim != 4:
        raise NotImplementedError(
            f"kv_write takes a [N, h, d, M] leaf and [b, W, h, d] "
            f"values, got {leaf.shape} and {new.shape}")
    if len(leaves) != len(news) or \
            any((a.shape, a.dtype) != (leaf.shape, leaf.dtype)
                for a in leaves) or \
            any(a.shape != new.shape for a in news):
        raise NotImplementedError(
            "one call takes leaves of one shape and dtype, and values "
            "of one shape")
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
    if _vmem_bytes(leaf, window, len(leaves)) > VMEM_MOST:
        raise NotImplementedError(
            f"window {window} of {len(leaves)} [{h}, {d}, {LANES}] "
            f"blocks does not fit the VMEM budget")
    return _kv_write_call(leaves, jnp.asarray(rows, jnp.int32),
                          jnp.asarray(cols, jnp.int32),
                          tuple(a.astype(leaf.dtype) for a in news),
                          paged=paged, interpret=_interpret())


def _vmem_bytes(leaf, window: int, leaves: int) -> int:
    """What one grid step holds in VMEM: per leaf its block
    double-buffered in and out and the resident fresh values of the
    window; three fp32 working copies of a block."""
    _, h, d, _ = leaf.shape
    return h * max(d, 8) * LANES * (
        leaves * (4 + window) * leaf.dtype.itemsize + 12)


@functools.partial(jax.jit, static_argnames=("paged", "interpret"))
def _kv_write_call(leaves, rows, cols, news, *, paged, interpret):
    """The walk and the ``pallas_call``, jitted so that a model's
    layers trace and lower ONE kernel per shape, not one per call:
    unjitted, the 48 calls of a 24-layer tick cost 7 s of every server
    start (my chip run, PR 24)."""
    leaf = leaves[0]
    _, h, d, _ = leaf.shape
    b, window = news[0].shape[:2]
    order, live = _live_rows(rows, paged)

    def block_of(t, s, rows, cols, order):
        i, at = order[t], s * (window - 1)
        return (rows[i, at], 0, 0, cols[i, at] // LANES)

    def lane_group(t, s, rows, cols, order):
        return (0, 0, 0, order[t] // LANES)

    # [b, W, h, d] -> [W, h, d, b]: the rows on the lanes. [b, h, d, W]
    # would be the projections' layout, but a minor dim of W = 1 pads
    # to 128 lanes in HBM: 16 MB a leaf a tick at 64 slots of 16 x 64.
    fresh = [jnp.pad(new.transpose(1, 2, 3, 0),
                     ((0, 0),) * 3 + ((0, -b % LANES),))
             for new in news]
    n = len(leaves)
    block = pl.BlockSpec((1, h, d, LANES), block_of)
    return tuple(pl.pallas_call(
        functools.partial(_kv_write_kernel, window=window, paged=paged),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # nothing live still takes one step: a dead row's, which
            # writes nothing
            grid=(jnp.maximum(live, 1), min(window, 2)),
            in_specs=[
                # resident while the walk stays in its group of 128
                # rows: one buffer (a wide window's second would not
                # fit)
                pl.BlockSpec((window, h, d, LANES), lane_group,
                             pipeline_mode=pl.Buffered(1)),
            ] * n + [block] * n,
            out_specs=[block] * n,
        ),
        out_shape=[_sds(leaf.shape, leaf.dtype, leaf)] * n,
        # operands: rows, cols, order, the fresh values, the leaves ->
        # each leaf IS its output
        input_output_aliases={3 + n + j: j for j in range(n)},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(_vmem_bytes(leaf, window, n) * 5 // 4,
                                 VMEM_DEFAULT)),
        interpret=interpret,
        name="kv_write",
    )(rows, cols, order, *fresh, *leaves))
