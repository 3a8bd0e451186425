"""What the decode kernels of a recurrent state share (``kda.py``, the
delta rule; ``ssd.py``, a state-space layer): the launch over the LIVE
rows of a state leaf, each row's block read once and written back in
place.

A state leaf ``[R, H, d1, d2]`` holds a row a slot behind the null row
0 (``core/serving.py``: the state class). The grid's first axis has
the dynamic extent of the live rows (``kv_write._live_rows``'s walk,
by scalar prefetch), its second the blocks of ``head_block`` heads;
step ``(t, j)`` picks the state block of row ``rows[order[t]]`` by the
row's id, the pipeline brings it in and takes it back to the SAME
block of the aliased output. A free or still-prefilling slot (row id
0) costs no step and its state is never touched; the one step a tick
with nothing live still takes hands its block back as it came.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import VMEM_DEFAULT, _sds
from .kv_write import _live_rows

#: rows of a live row's operand tile: one float32 sublane tile
TILE_ROWS = 8


def _guarded(step):
    """``step(*operand refs, s_ref, o_ref, s_out_ref)`` run where the
    grid step's row is live; a dead row's block goes back as it came
    and its output block is zeros."""
    def kernel(rows_ref, order_ref, *refs):
        """``refs``: the operands, the state, the output, the state
        again (aliased)."""
        s_ref, o_ref, s_out_ref = refs[-3:]
        live = rows_ref[order_ref[pl.program_id(0)]] != 0

        @pl.when(live)
        def _():
            step(*refs)

        @pl.when(jnp.logical_not(live))
        def _():
            s_out_ref[...] = s_ref[...]
            o_ref[...] = jnp.zeros_like(o_ref)
    return kernel


def live_rows_call(step, name, state, rows, operands, out, *, head_block,
                   interpret, vmem_limit=VMEM_DEFAULT):
    """``(state, o)``: ``step`` on every live row of ``state [R, H, d1,
    d2]`` (``rows [n]`` int32, 0 = no row), ``head_block`` heads a grid
    step, the leaf an aliased input and output.

    ``operands``: ``(array [n, ...], block, at)`` each, ``block`` the
    block's shape behind the row axis and ``at(j)`` its block indices
    there for head block ``j``; ``out`` the same for the float32
    output, its first entry a shape. A dead row's output is zeros."""
    n = rows.shape[0]
    _, heads, d1, d2 = state.shape
    order, live = _live_rows(rows[:, None], True)

    def of_row(at):
        return lambda t, j, rows, order: (order[t],) + tuple(at(j))

    def of_state(t, j, rows, order):
        return (rows[order[t]], j, 0, 0)

    state_spec = pl.BlockSpec((1, head_block, d1, d2), of_state)
    out_shape, out_block, out_at = out
    o, state = pl.pallas_call(
        _guarded(step),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # nothing live still takes one step: a dead row's
            grid=(jnp.maximum(live, 1), heads // head_block),
            in_specs=[pl.BlockSpec((1,) + tuple(block), of_row(at))
                      for _, block, at in operands] + [state_spec],
            out_specs=[
                pl.BlockSpec((1,) + tuple(out_block), of_row(out_at)),
                state_spec],
        ),
        out_shape=[_sds(out_shape, jnp.float32, state),
                   _sds(state.shape, state.dtype, state)],
        # rows, order, the operands, the state -> the state IS the
        # second output
        input_output_aliases={2 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name=name,
    )(rows, order, *(array for array, _, _ in operands), state)
    # a dead row's block of ``o`` was never visited
    live_row = (rows != 0).reshape((n,) + (1,) * (len(out_shape) - 1))
    return state, jnp.where(live_row, o, 0.0)
