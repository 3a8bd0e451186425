"""One decode step of a state-space layer (Mamba-2, arXiv:2405.21060)
for TPU, written in Pallas: every live slot's recurrent state read
once, updated and written back in place.

A state-space layer keeps, a slot and a head, a float32 state ``S [P,
N]`` (``P`` channels of the head, ``N`` state dimensions). A tick's
step, with the head's scalar decay ``a`` in (0, 1), the step ``dt``,
the layer's input ``x [P]`` and the ``B [N]``, ``C [N]`` that every
head of the group shares:

    S = a S + (dt x) B^T;    y = S C + D x

Not the delta rule's step (``kda.py``): no correction by what the
state already holds, one decay a head where that has one a channel,
``B`` and ``C`` shared by the heads. The launch IS that kernel's
(``state_rows.live_rows_call``): a grid over the live rows only, the
state leaf ``[R, H, P, N]`` an aliased input and output.

The state and what forms it are float32 on the VPU. ``B`` and ``C``
lie along the state's lanes and arrive as rows of one ``[8, N]`` tile a
row; the per-channel operands (``dt x``, the decay, ``D x``) have to
lie along its SUBLANES: they arrive as rows of one ``[8, H P]`` tile
and the tile is turned in the kernel, :data:`SUB_HEADS` heads at a
time. The readout ``S C`` would come out of the VPU as a column a
head; it is one product of the ``[8, N]`` tile against the block's
``[heads P, N]`` states contracted over the lanes (the shape of ``q
k^T``), float32 operands in the matrix unit's multi-pass form, whose
row of ``C`` is the output's lane-dense row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import LANES, _interpret
from .state_rows import TILE_ROWS, live_rows_call

#: heads of one grid step: 32 states of 32 KB in, 32 out,
#: double-buffered (4 MB of VMEM)
HEAD_BLOCK = 32
#: heads turned and updated together inside a step
SUB_HEADS = 8


def _ssd_decode_step(hp_ref, bc_ref, s_ref, o_ref, s_out_ref, *, heads):
    """One grid step = ``heads`` heads of one live row."""
    p, n = s_ref.shape[2:]
    bc = bc_ref[0]                                 # [8, N]: B, C, 0...
    b_row = bc[0:1, :]
    sub = SUB_HEADS * p
    for h in range(0, heads, SUB_HEADS):
        tile = hp_ref[0, :, h * p:h * p + sub]     # [8, sub]
        cols = tile.T                              # [sub, 8]
        dtx, a = cols[:, 0:1], cols[:, 1:2]
        s = s_ref[0, h:h + SUB_HEADS].reshape(sub, n) * a + dtx * b_row
        s_out_ref[0, h:h + SUB_HEADS] = s.reshape(SUB_HEADS, p, n)
        y = jax.lax.dot_general(
            bc, s, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)    # [8, sub]
        o_ref[0, :, h * p:h * p + sub] = y[1:2, :] + tile[2:3, :]


def ssd_decode(state, rows, x, dt, a, b, c, d_skip):
    """``(state, y)``: the step above on rows ``rows [n]`` (int32, 0 =
    no row: nothing is read or written and ``y`` is 0) of ``state [R,
    H, P, N]`` float32, in place (the leaf is an aliased input and
    output). ``x [n, H, P]``, ``dt``, ``a`` ``[n, H]``, ``b``, ``c``
    ``[n, N]``, ``d_skip [H]``, any float dtype (the step runs in
    float32); ``y [n, H, P]`` float32. Two live rows never share a row
    id.

    Raises NotImplementedError where the caller must fall back to
    plain XLA (``ops/state_space.py::ssd_step``)."""
    if jax.default_backend() != "tpu" and not _interpret():
        raise NotImplementedError("ssd_decode kernel targets TPU")
    if state.ndim != 4 or state.dtype != jnp.float32:
        raise NotImplementedError(
            f"ssd_decode takes a float32 [R, H, P, N] state, got "
            f"{state.dtype} {state.shape}")
    _, heads, p, n_state = state.shape
    n = rows.shape[0]
    if n_state % LANES or (SUB_HEADS * p) % LANES or heads % SUB_HEADS:
        raise NotImplementedError(
            f"ssd_decode needs N a multiple of {LANES}, {SUB_HEADS} "
            f"heads' channels a multiple of {LANES} and heads a "
            f"multiple of {SUB_HEADS}, got {state.shape}")
    if x.shape != (n, heads, p) or dt.shape != (n, heads) or \
            a.shape != (n, heads) or d_skip.shape != (heads,) or \
            b.shape != (n, n_state) or c.shape != (n, n_state):
        raise NotImplementedError(
            f"operands do not match {n} rows of state {state.shape}")
    return _ssd_decode_call(
        state, jnp.asarray(rows, jnp.int32), x, dt, a, b, c, d_skip,
        head_block=HEAD_BLOCK if heads % HEAD_BLOCK == 0 else SUB_HEADS,
        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def _ssd_decode_call(state, rows, x, dt, a, b, c, d_skip, *, head_block,
                     interpret):
    """The operand tiles and the launch, jitted so that a model's
    layers trace one kernel."""
    _, heads, p, n_state = state.shape
    n = rows.shape[0]
    f32 = jnp.float32
    x, dt, a = x.astype(f32), dt.astype(f32), a.astype(f32)
    chans = heads * p
    hp = jnp.stack(
        [(dt[..., None] * x).reshape(n, chans),
         jnp.broadcast_to(a[..., None], x.shape).reshape(n, chans),
         (d_skip.astype(f32)[:, None] * x).reshape(n, chans)]
        + [jnp.zeros((n, chans), f32)] * (TILE_ROWS - 3), axis=1)
    bc = jnp.stack([b.astype(f32), c.astype(f32)]
                   + [jnp.zeros((n, n_state), f32)] * (TILE_ROWS - 2),
                   axis=1)
    hb = head_block
    state, y = live_rows_call(
        functools.partial(_ssd_decode_step, heads=hb), "ssd_decode",
        state, rows,
        [(hp, (TILE_ROWS, hb * p), lambda j: (0, j)),
         (bc, (TILE_ROWS, n_state), lambda j: (0, 0))],
        ((n, 1, chans), (1, hb * p), lambda j: (0, j)), head_block=hb,
        interpret=interpret)
    return state, y.reshape(n, heads, p)
