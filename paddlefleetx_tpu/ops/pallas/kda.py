"""One decode step of the gated delta rule (Kimi Delta Attention) for
TPU, written in Pallas: every live slot's recurrent state read once,
updated and written back in place.

A linear-attention layer keeps, a slot and a head, a float32 state
``S [d_k, d_v]`` instead of keys and values. A tick's step, with the
decay ``a`` (a channel of ``d_k``), the step size ``b`` and the
layer's ``q``, ``k``, ``v`` of the one new token:

    S' = diag(a) S;  u = b (v - S'^T k);  S = S' + k u^T;  o = S^T q

The state leaf ``[R, H, d_k, d_v]`` holds a row a slot behind the null
row 0 (``core/serving.py``: the state class). As plain XLA the step is
a gather of the live rows, the update and a scatter back: three passes
over states of 4 MB a slot a layer. Here the launch is
``state_rows.live_rows_call``'s, which the state-space layer's kernel
(``ssd.py``) shares: a grid over the LIVE rows only, each step's ``(1,
heads, d_k, d_v)`` block picked by the row's id and handed back to the
SAME block of the aliased output; a free or still-prefilling slot (row
id 0) costs no step and its state is never touched.

All of it is float32 on the VPU (no matrix unit: a product of one row
with a ``128 x 128`` state would leave it idle, and its float32 passes
are not exact). The per-channel operands of ``d_k`` (``a``, ``k``,
``q``) have to lie along the state's SUBLANES: they arrive as rows of
one ``[8, d]`` tile a head (``a, k, q, v, b``) and the tile is turned
in the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import LANES, _interpret
from .state_rows import TILE_ROWS, live_rows_call

#: heads of one grid step: 8 states of 64 KB in, 8 out, double-buffered
HEAD_BLOCK = 8


def _kda_decode_step(ops_ref, s_ref, o_ref, s_out_ref, *, heads):
    """One grid step = ``heads`` heads of one live row."""
    for h in range(heads):
        tile = ops_ref[0, h]                       # [8, d]
        cols = tile.T                              # [d, 8]
        a, k, q = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
        v, b = tile[3:4, :], tile[4:5, :]          # [1, d]
        s = s_ref[0, h] * a
        u = (v - jnp.sum(s * k, axis=0, keepdims=True)) * b
        s = s + k * u
        s_out_ref[0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


def kda_decode(state, rows, q, k, v, a, b):
    """``(state, o)``: the step above on rows ``rows [n]`` (int32, 0 =
    no row: nothing is read or written and ``o`` is 0) of ``state [R,
    H, d, d]`` float32, in place (the leaf is an aliased input and
    output). ``q``, ``k``, ``a`` ``[n, H, d]``, ``v [n, H, d]``, ``b
    [n, H]``, any float dtype (the step runs in float32); ``o [n, H,
    d]`` float32. Two live rows never share a row id.

    Raises NotImplementedError where the caller must fall back to
    plain XLA (``ops/linear_attention.py::kda_step``)."""
    if jax.default_backend() != "tpu" and not _interpret():
        raise NotImplementedError("kda_decode kernel targets TPU")
    if state.ndim != 4 or state.dtype != jnp.float32:
        raise NotImplementedError(
            f"kda_decode takes a float32 [R, H, d_k, d_v] state, got "
            f"{state.dtype} {state.shape}")
    _, heads, dk, dv = state.shape
    n = rows.shape[0]
    if dk != dv or dk % LANES or heads % HEAD_BLOCK:
        raise NotImplementedError(
            f"kda_decode needs d_k = d_v a multiple of {LANES} and "
            f"heads a multiple of {HEAD_BLOCK}, got {state.shape}")
    if any(t.shape != (n, heads, dk) for t in (q, k, v, a)) or \
            b.shape != (n, heads):
        raise NotImplementedError(
            f"operands do not match {n} rows of state {state.shape}")
    return _kda_decode_call(state, jnp.asarray(rows, jnp.int32), q, k, v,
                            a, b, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_decode_call(state, rows, q, k, v, a, b, *, interpret):
    """The walk, the operand tiles and the ``pallas_call``, jitted so
    that a model's layers trace one kernel."""
    _, heads, d, _ = state.shape
    n = rows.shape[0]
    f32 = jnp.float32
    tiles = jnp.stack(
        [a.astype(f32), k.astype(f32), q.astype(f32), v.astype(f32),
         jnp.broadcast_to(b.astype(f32)[..., None], (n, heads, d))]
        + [jnp.zeros((n, heads, d), f32)] * (TILE_ROWS - 5), axis=2)
    hb = HEAD_BLOCK
    return live_rows_call(
        functools.partial(_kda_decode_step, heads=hb), "kda_decode",
        state, rows, [(tiles, (hb, TILE_ROWS, d), lambda j: (j, 0, 0))],
        ((n, heads, d), (hb, d), lambda j: (j, 0)), head_block=hb,
        interpret=interpret)
