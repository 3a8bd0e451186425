"""Grouped (per-expert) matmul for sort-based MoE dispatch, in Pallas.

The sort-based MoE dispatch (``models/gpt/moe.py``,
``moe_dispatch="sort*"``) gathers routed tokens into a contiguous
``[E·b, C, h]`` buffer of per-(expert, batch-row) groups, each padded
to the static capacity ``C``. The expert FFN is then G independent
matmuls against per-expert weights — a *grouped* GEMM. XLA expresses
it as one dense batched matmul over all ``G·C`` slots; this kernel
instead iterates the expert group boundaries carried by the routing
counts and **skips groups no token routed to** (their padded rows are
zero, so the skipped matmul is exactly the zero block the dense form
would have produced — bit-identical outputs, less MXU work; at the
shipped ep8 config's load imbalance a third of (expert, row) groups
are routinely empty).

Layout: ``x [G, C, K]`` groups, ``w [Gw, K, N]`` per-expert weights
with ``G == Gw * rep`` (``rep`` batch rows share one expert's weight),
``counts [G]`` int32 live rows per group delivered by scalar prefetch
(``PrefetchScalarGridSpec`` — the counts land in SMEM before the grid
body runs, so the skip predicate costs no HBM traffic). The grid is
``(G, N/bn, K/bk)`` with the K axis innermost-sequential, accumulating
in fp32 VMEM scratch exactly like ``flash_attention.py``; the backward
is wired through ``jax.custom_vjp``: dx reuses the forward kernel with
``w`` transposed, dw is a second kernel accumulating ``xᵀ·dy`` over
each expert's ``rep`` groups. Interpret mode
(``PFX_PALLAS_INTERPRET=1``) lets the CPU test suite validate kernel
semantics (tests/test_grouped_matmul.py) without a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _dot, _interpret, _sds


def _block(dim: int, target: int) -> int:
    """Largest power-of-two-shrunk block <= target dividing ``dim``
    (1 always divides, so the shrink terminates)."""
    b = max(1, min(target, dim))
    while dim % b:
        b //= 2
    return b


def _gmm_kernel(counts_ref, x_ref, w_ref, o_ref, acc_scr, *, num_k):
    """out[g] = x[g] @ w[g // rep], skipping empty groups.

    Scratch accumulates fp32 across the sequential ki axis; a group
    with zero live rows never touches the MXU — its scratch stays the
    zeros ``_init`` wrote, which IS the product of its all-zero padded
    rows, so skipping preserves bitwise output parity with the dense
    batched matmul."""
    g = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(counts_ref[g] > 0)
    def _accumulate():
        acc_scr[:] += _dot(x_ref[0], w_ref[0])

    @pl.when(ki == num_k - 1)
    def _finish():
        o_ref[0] = acc_scr[:].astype(o_ref.dtype)


def _gmm_dw_kernel(counts_ref, x_ref, dy_ref, dw_ref, acc_scr, *,
                   rep):
    """dw[e] = sum over e's ``rep`` groups of x[g]ᵀ @ dy[g].

    The group axis is innermost-sequential so the [K, bn] scratch
    accumulates one expert's contributions before moving on; empty
    groups are skipped (their x rows are zero — no contribution)."""
    e = pl.program_id(0)
    gi = pl.program_id(2)

    @pl.when(gi == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(counts_ref[e * rep + gi] > 0)
    def _accumulate():
        acc_scr[:] += _dot(x_ref[0], dy_ref[0], trans_a=True)

    @pl.when(gi == rep - 1)
    def _finish():
        dw_ref[0] = acc_scr[:].astype(dw_ref.dtype)


def _gmm_forward(x, w, counts, block_n, block_k):
    """One grouped-GEMM pallas_call: ``[G, C, K] @ [Gw, K, N] ->
    [G, C, N]`` with per-group skip from ``counts``."""
    g_groups, c_rows, k_dim = x.shape
    w_groups, _, n_dim = w.shape
    rep = g_groups // w_groups
    bn = _block(n_dim, block_n)
    bk = _block(k_dim, block_k)
    num_n, num_k = n_dim // bn, k_dim // bk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g_groups, num_n, num_k),
        in_specs=[
            pl.BlockSpec((1, c_rows, bk),
                         lambda g, ni, ki, c_ref: (g, 0, ki)),
            pl.BlockSpec((1, bk, bn),
                         lambda g, ni, ki, c_ref, _r=rep:
                         (g // _r, ki, ni)),
        ],
        out_specs=pl.BlockSpec((1, c_rows, bn),
                               lambda g, ni, ki, c_ref: (g, 0, ni)),
        scratch_shapes=[pltpu.VMEM((c_rows, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, num_k=num_k),
        grid_spec=grid_spec,
        out_shape=_sds((g_groups, c_rows, n_dim), x.dtype, x),
        interpret=_interpret(),
    )(counts, x, w)


def _gmm_dw(x, dy, counts, w_groups, block_n):
    """dw pallas_call: fp32 ``[Gw, K, N]`` cotangent of the weights."""
    g_groups, c_rows, k_dim = x.shape
    n_dim = dy.shape[-1]
    rep = g_groups // w_groups
    bn = _block(n_dim, block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w_groups, n_dim // bn, rep),
        in_specs=[
            pl.BlockSpec((1, c_rows, k_dim),
                         lambda e, ni, gi, c_ref, _r=rep:
                         (e * _r + gi, 0, 0)),
            pl.BlockSpec((1, c_rows, bn),
                         lambda e, ni, gi, c_ref, _r=rep:
                         (e * _r + gi, 0, ni)),
        ],
        out_specs=pl.BlockSpec((1, k_dim, bn),
                               lambda e, ni, gi, c_ref: (e, 0, ni)),
        scratch_shapes=[pltpu.VMEM((k_dim, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_dw_kernel, rep=rep),
        grid_spec=grid_spec,
        out_shape=_sds((w_groups, k_dim, n_dim), jnp.float32, x),
        interpret=_interpret(),
    )(counts, x, dy)


def _check_shapes(x, w, counts):
    """Kernel admission: a ``NotImplementedError`` here sends the MoE
    layer to its XLA expert-einsum fallback (counted as
    ``moe/fallback/pallas_rejected`` — docs/moe.md)."""
    if x.ndim != 3 or w.ndim != 3 or counts.ndim != 1:
        raise NotImplementedError(
            f"grouped_matmul wants x[G,C,K] w[Gw,K,N] counts[G], got "
            f"{x.shape} / {w.shape} / {counts.shape}")
    if x.shape[0] != counts.shape[0] or \
            x.shape[0] % w.shape[0] or x.shape[2] != w.shape[1]:
        raise NotImplementedError(
            f"grouped_matmul shape mismatch: x {x.shape}, w {w.shape},"
            f" counts {counts.shape}")
    if not jnp.issubdtype(counts.dtype, jnp.integer):
        raise NotImplementedError("counts must be integer")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(x, w, counts, block_n, block_k):
    return _gmm_forward(x, w, counts, block_n, block_k)


def _grouped_matmul_fwd(x, w, counts, block_n, block_k):
    return (_gmm_forward(x, w, counts, block_n, block_k),
            (x, w, counts))


def _grouped_matmul_bwd(block_n, block_k, res, g):
    x, w, counts = res
    # dx[g] = dy[g] @ w[g // rep]ᵀ — the forward kernel with w
    # transposed; empty groups skip in BOTH directions, so dx is zero
    # exactly where the dense form's zero dy rows would have made it
    dx = _gmm_forward(g, jnp.swapaxes(w, 1, 2), counts, block_k,
                      block_n)
    dw = _gmm_dw(x, g, counts, w.shape[0], block_n)
    return (dx.astype(x.dtype), dw.astype(w.dtype),
            np.zeros(counts.shape, jax.dtypes.float0))


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array, counts: jax.Array,
                   block_n: int = 128, block_k: int = 512) -> jax.Array:
    """Per-group matmul ``out[g] = x[g] @ w[g // (G//Gw)]`` that skips
    groups with ``counts[g] == 0``.

    Args:
      x: ``[G, C, K]`` — G groups of C capacity-padded rows (rows past
        ``counts[g]`` MUST be zero; the sort dispatch guarantees it).
      w: ``[Gw, K, N]`` — per-expert weights, ``Gw`` divides ``G``;
        consecutive blocks of ``G // Gw`` groups share one weight.
      counts: int32 ``[G]`` live rows per group (a trace-time array —
        fresh routing per step must not retrace; delivered to the
        kernels by scalar prefetch).
      block_n / block_k: N/K tile targets (shrunk to divisors).

    Returns ``[G, C, N]`` in ``x.dtype``, accumulated in fp32. The
    custom VJP computes dx with the same kernel (w transposed) and dw
    with a per-expert accumulation kernel — both honor the same
    empty-group skip. The skip is gradient-exact under the MoE
    contract: dw loses nothing (a skipped group's x rows are zero)
    and dx loses nothing because an empty group's outputs are pure
    capacity padding that the combine step zero-weights, so its
    cotangent rows arrive as zeros.
    """
    _check_shapes(x, w, counts)
    return _grouped_matmul(x, w, counts.astype(jnp.int32), block_n,
                           block_k)


# -- the ragged form: groups of uneven, data-dependent size, no capacity --
#
# The dropless expert layer (``models/deepseek_v3/moe.py``) sorts its
# rows by expert into ONE ``[M, K]`` buffer whose groups start on
# ``block_m`` boundaries. A row tile then belongs to exactly one group,
# the tile -> group table and the count of occupied tiles come by
# scalar prefetch, and the grid's row axis is DYNAMIC: it runs over the
# occupied tiles only, so time follows the rows that were routed here
# and not the static bound ``M`` (which has to hold the worst routing).
# Rows past the occupied tiles are never read and never written: what
# the output holds there is unspecified, and callers mask it.


def ragged_layout(group_sizes: jax.Array, block_m: int, num_tiles: int):
    """Where the groups of a ragged buffer lie: ``(tile_group [T],
    tiles_used, row_start [G], group_rows [G])``, the last the rows each
    group occupies with its padding (what ``jax.lax.ragged_dot`` takes
    as ``group_sizes`` on the same buffer).

    Group ``g`` takes ``max(1, ceil(size_g / block_m))`` whole tiles
    (an empty group keeps one tile of zero rows, so its ``dw`` block is
    written as zeros and never left unset) starting at row
    ``row_start[g]``. ``num_tiles`` is the static length ``T`` of the
    table; ``sum(sizes) / block_m + G`` tiles always suffice."""
    sizes = group_sizes.astype(jnp.int32)
    tiles = jnp.maximum(1, -(-sizes // block_m))
    tile_end = jnp.cumsum(tiles)
    tile_group = jnp.searchsorted(
        tile_end, jnp.arange(num_tiles, dtype=jnp.int32), side="right")
    tile_group = jnp.minimum(tile_group, sizes.shape[0] - 1)
    return (tile_group.astype(jnp.int32), tile_end[-1],
            (tile_end - tiles) * block_m, tiles * block_m)


def _ragged_kernel(tg_ref, x_ref, w_ref, o_ref, *, transpose_rhs):
    """One row tile against its group's weight block, whole contraction
    in one step (no accumulator carried across the grid)."""
    del tg_ref
    o_ref[...] = _dot(x_ref[...], w_ref[0],
                      trans_b=transpose_rhs).astype(o_ref.dtype)


def _ragged_dw_kernel(tg_ref, x_ref, dy_ref, dw_ref):
    """dw[g] += x_tile^T @ dy_tile over the tiles of group ``g``, which
    are consecutive on the innermost grid axis: the output block stays
    in VMEM while the group lasts and is zeroed on its first tile."""
    t = pl.program_id(2)
    first = jnp.logical_or(
        t == 0, tg_ref[t] != tg_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[0] += _dot(x_ref[...], dy_ref[...], trans_a=True)


#: what the ragged kernel's double-buffered blocks may take of the 16 MB
#: a v5e core scopes to one kernel; the compiler's own temporaries (the
#: float32 product before its cast) need the rest
RAGGED_VMEM_BUDGET = 14 * 2 ** 20


def _ragged_block_n(block_m, k_dim, n_dim, block_n, itemsize):
    """Columns of one step: ``block_n`` at most, halved while the
    step's blocks (a row tile and a weight block over the WHOLE
    contraction, the output tile, each double-buffered) outgrow
    :data:`RAGGED_VMEM_BUDGET`. Every shape served before hidden 6144
    keeps ``block_n``; a prefill chunk's 128-row tiles against
    ``[6144, 512]`` weight blocks are 16.7 MB, which the chip's
    compiler refuses."""
    bn = _block(n_dim, block_n)
    while bn > 128 and 2 * itemsize * (
            block_m * k_dim + k_dim * bn + block_m * bn) \
            > RAGGED_VMEM_BUDGET:
        bn = _block(n_dim, bn // 2)
    return bn


def _ragged_forward(x, w, tile_group, tiles_used, block_m, block_n,
                    transpose_rhs):
    """``out[rows of g] = x[rows of g] @ w[g]`` (``w[g]^T`` with
    ``transpose_rhs``) over the occupied tiles."""
    m_rows, k_dim = x.shape
    n_dim = w.shape[1] if transpose_rhs else w.shape[2]
    bn = _ragged_block_n(block_m, k_dim, n_dim, block_n,
                         x.dtype.itemsize)
    if transpose_rhs:
        w_spec = pl.BlockSpec((1, bn, k_dim),
                              lambda ni, t, tg: (tg[t], ni, 0))
    else:
        w_spec = pl.BlockSpec((1, k_dim, bn),
                              lambda ni, t, tg: (tg[t], 0, ni))
    # rows innermost: a group's weight block is fetched once per
    # column tile and stays while its row tiles pass
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_dim // bn, tiles_used),
        in_specs=[pl.BlockSpec((block_m, k_dim),
                               lambda ni, t, tg: (t, 0)), w_spec],
        out_specs=pl.BlockSpec((block_m, bn),
                               lambda ni, t, tg: (t, ni)),
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, transpose_rhs=transpose_rhs),
        grid_spec=grid_spec,
        out_shape=_sds((m_rows, n_dim), x.dtype, x),
        interpret=_interpret(), name="moe_gmm",
    )(tile_group, x, w)


def _ragged_dw(x, dy, tile_group, tiles_used, num_groups, block_m,
               block_n, block_k):
    """fp32 ``[G, K, N]`` cotangent of the weights."""
    k_dim, n_dim = x.shape[1], dy.shape[1]
    bk, bn = _block(k_dim, block_k), _block(n_dim, block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k_dim // bk, n_dim // bn, tiles_used),
        in_specs=[
            pl.BlockSpec((block_m, bk), lambda ki, ni, t, tg: (t, ki)),
            pl.BlockSpec((block_m, bn), lambda ki, ni, t, tg: (t, ni)),
        ],
        out_specs=pl.BlockSpec((1, bk, bn),
                               lambda ki, ni, t, tg: (tg[t], ki, ni)),
    )
    return pl.pallas_call(
        _ragged_dw_kernel, grid_spec=grid_spec,
        out_shape=_sds((num_groups, k_dim, n_dim), jnp.float32, x),
        interpret=_interpret(), name="moe_gmm_dw",
    )(tile_group, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ragged_matmul(x, w, tile_group, tiles_used, block_m, block_n,
                   block_k):
    return _ragged_forward(x, w, tile_group, tiles_used, block_m,
                           block_n, False)


def _ragged_matmul_fwd(x, w, tile_group, tiles_used, block_m, block_n,
                       block_k):
    out = _ragged_forward(x, w, tile_group, tiles_used, block_m,
                          block_n, False)
    return out, (x, w, tile_group, tiles_used)


def _ragged_matmul_bwd(block_m, block_n, block_k, res, g):
    x, w, tile_group, tiles_used = res
    # dx rows of g = dy rows of g @ w[g]^T: the same kernel, the weight
    # block read transposed in place (no transposed copy of w)
    dx = _ragged_forward(g, w, tile_group, tiles_used, block_m,
                         block_n, True)
    dw = _ragged_dw(x, g, tile_group, tiles_used, w.shape[0], block_m,
                    block_n, block_k)
    return (dx, dw.astype(w.dtype),
            np.zeros(tile_group.shape, jax.dtypes.float0),
            np.zeros((), jax.dtypes.float0))


_ragged_matmul.defvjp(_ragged_matmul_fwd, _ragged_matmul_bwd)


def ragged_matmul(x: jax.Array, w: jax.Array, tile_group: jax.Array,
                  tiles_used: jax.Array, block_m: int = 128,
                  block_n: int = 512, block_k: int = 1024) -> jax.Array:
    """Grouped matmul over groups of uneven size, with no capacity.

    Args:
      x: ``[M, K]`` rows sorted by group, each group starting on a
        ``block_m`` boundary (:func:`ragged_layout`); rows of a group's
        last tile past its size MUST be zero.
      w: ``[G, K, N]`` per-group weights.
      tile_group: int32 ``[M / block_m]`` group of each row tile.
      tiles_used: int32 scalar, the occupied tiles (the dynamic extent
        of the grid's row axis).

    Returns ``[M, N]`` in ``x.dtype``; rows past ``tiles_used *
    block_m`` are unspecified. The custom VJP gives ``dx`` by the same
    kernel on ``w^T`` and ``dw`` (fp32 accumulation per group over its
    rows) by ``moe_gmm_dw``; ``dy`` rows past the occupied tiles are
    never read. A shape the kernel cannot take raises
    ``NotImplementedError`` (counted by the caller as
    ``moe/fallback/pallas_rejected``)."""
    if x.ndim != 2 or w.ndim != 3 or x.shape[1] != w.shape[1]:
        raise NotImplementedError(
            f"ragged_matmul wants x[M,K] w[G,K,N], got {x.shape} / "
            f"{w.shape}")
    if x.shape[0] % block_m or block_m % 8 or \
            tile_group.shape != (x.shape[0] // block_m,):
        raise NotImplementedError(
            f"ragged_matmul: {x.shape[0]} rows in tiles of {block_m} "
            f"with a table of {tile_group.shape}")
    if jax.default_backend() != "tpu" and not _interpret():
        raise NotImplementedError("ragged_matmul targets TPU")
    return _ragged_matmul(x, w, tile_group.astype(jnp.int32),
                          jnp.asarray(tiles_used, jnp.int32), block_m,
                          block_n, block_k)
