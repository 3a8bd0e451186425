"""The recurrence of a state-space layer (Mamba-2 / SSD,
arXiv:2405.21060), in the two forms the serving path needs; the
sibling of ``linear_attention.py`` (the delta rule), with which it
shares the state class and the decode kernel's launch and nothing of
the mathematics.

A head keeps a float32 state ``S [P, N]``. With the head's step ``dt_t
> 0``, its scalar decay ``a_t = exp(dt_t A)`` (``A < 0``), its input
``x_t [P]`` and the ``B_t``, ``C_t`` ``[N]`` every head shares:

    S_t = a_t S_{t-1} + (dt_t x_t) B_t^T;    y_t = S_t C_t + D x_t

* :func:`ssd_step`: ONE token a row, on the rows of a state leaf that
  a decode tick's slots own. The Pallas kernel (``ops/pallas/ssd.py``,
  ``attention/ssd_decode``) reads each live row's state once and
  writes it back in place; where it cannot run plain XLA gathers,
  updates and scatters the rows, counted
  ``attention/fallback/ssd_rejected``, never silent.
* :func:`ssd_chunk`: a prefill chunk's ``L`` tokens in the chunkwise
  (SSD) form, ``attention/ssd_chunk``: within a block of ``block``
  tokens ``y = ((C B^T) * decay mask) (dt x)`` is matrix products, the
  blocks follow each other through ``S`` alone. Every decay enters as
  ``exp`` of a difference of cumulative log decays that is ``<= 0``:
  nothing is divided by a cumulative product that may have
  underflowed.

Everything here is float32 with ``Precision.HIGHEST`` products: the
state compounds its rounding over a whole sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..observability import metrics
from .ring_attention import kernel_mesh

_HI = jax.lax.Precision.HIGHEST


def _mm(eq, *ops):
    return jnp.einsum(eq, *ops, precision=_HI,
                      preferred_element_type=jnp.float32)


def _step(s, x, dt, a, b, c, d_skip):
    """The recurrence's one step on states ``s [n, H, P, N]``."""
    s = s * a[..., None, None] + (dt[..., None] * x)[..., None] \
        * b[:, None, None, :]
    return s, jnp.sum(s * c[:, None, None, :], axis=-1) \
        + d_skip[:, None] * x


def ssd_step(state, rows, x, dt, a, b, c, d_skip, use_kernel: bool = True):
    """``(state, y)``: one token on rows ``rows [n]`` (int32; 0 is the
    null row: a slot that is free or still prefilling, whose ``y`` is
    0) of ``state [R, H, P, N]`` float32. ``x [n, H, P]``, ``dt``, ``a``
    ``[n, H]``, ``b``, ``c`` ``[n, N]``, ``d_skip [H]`` float32; ``y [n,
    H, P]`` float32. Under a jit that donates ``state`` the kernel's
    update is in place."""
    if use_kernel and kernel_mesh() is not None:
        metrics.inc("attention/fallback/mesh_sharded")
    elif use_kernel:
        try:
            from .pallas.ssd import ssd_decode
            out = ssd_decode(state, rows, x, dt, a, b, c, d_skip)
            metrics.inc("attention/ssd_decode")
            return out
        except (ImportError, NotImplementedError):
            metrics.inc("attention/fallback/ssd_rejected")
    f32 = jnp.float32
    s, y = _step(state[rows], *(t.astype(f32)
                                for t in (x, dt, a, b, c, d_skip)))
    live = rows != 0
    # dead rows all land on the null row, whose content nobody reads
    return state.at[rows].set(s), jnp.where(live[:, None, None], y, 0.0)


def ssd_chunk(x, dt, a_log, b, c, d_skip, s0, block: int = 256):
    """``(y [n, L, H, P], s [n, H, P, N])``: the recurrence over ``L``
    tokens from state ``s0``, chunkwise. ``x [n, L, H, P]``, ``dt [n,
    L, H]``, ``a_log [H]`` (``log`` of ``-A``: the decay of a step is
    ``exp(-dt exp(a_log))``), ``b``, ``c`` ``[n, L, N]``, ``d_skip
    [H]``; all float32. A position with ``dt = 0`` leaves the state as
    it was (a padded tail; ``L`` is padded so to a multiple of
    ``block`` here)."""
    metrics.inc("attention/ssd_chunk")
    f32 = jnp.float32
    n, length, heads, p = x.shape
    block = min(block, -(-length // 8) * 8)
    pad = -length % block
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    skip = d_skip.astype(f32)[:, None] * x
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad))
                               + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (length + pad) // block

    def blocks(t):                  # [n, L, ...] -> [nc, n, block, ...]
        return jnp.moveaxis(t.reshape((n, nc, block) + t.shape[2:]), 1, 0)
    # g: the inclusive cumulative log decay inside a block, <= 0 and
    # non-increasing
    g = jnp.cumsum(blocks(-dt * jnp.exp(a_log.astype(f32))), axis=2)
    xs, bs, cs = blocks(dt[..., None] * x), blocks(b), blocks(c)
    t_i = jnp.arange(block)
    seen = t_i[None, :] <= t_i[:, None]                  # i <= t
    g_h = jnp.moveaxis(g, -1, 2)                         # [nc, n, H, t]
    # mask[.., h, t, i] = exp(g_t - g_i) for i <= t
    mask = jnp.exp(jnp.where(seen, g_h[..., :, None] - g_h[..., None, :],
                             -jnp.inf))
    inside = _mm("cnhti,cnihp->cnthp",
                 _mm("cntk,cnik->cnti", cs, bs)[:, :, None] * mask, xs)
    end = g[:, :, -1]                                    # [nc, n, H]
    # what a block hands on: its inputs decayed to the block's end
    fresh = _mm("cnihp,cnik->cnhpk",
                jnp.exp(end[:, :, None] - g)[..., None] * xs, bs)
    grow = jnp.exp(g)                                    # [nc, n, t, H]

    def step(s, per_block):
        cs, grow, keep, fresh = per_block
        y = _mm("ntk,nhpk->nthp", cs, s) * grow[..., None]
        return keep[..., None, None] * s + fresh, y

    s, carried = jax.lax.scan(step, s0.astype(f32),
                              (cs, grow, jnp.exp(end), fresh))
    y = jnp.moveaxis(inside + carried, 0, 1).reshape(
        n, nc * block, heads, p)[:, :length]
    return y + skip, s
