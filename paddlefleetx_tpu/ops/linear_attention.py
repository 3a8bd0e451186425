"""The gated delta rule of a linear-attention layer (Kimi Delta
Attention, arXiv:2510.26692), in the two forms the serving path needs.

A head keeps a float32 state ``S [d_k, d_v]``. With the decay ``a_t``
(a channel of ``d_k``, in (0, 1)), the step size ``b_t`` and the
layer's ``q_t``, ``k_t``, ``v_t``:

    S' = diag(a_t) S_{t-1};  u_t = b_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T;    o_t = S_t^T q_t

* :func:`kda_step`: ONE token a row, on the rows of a state leaf that
  a decode tick's slots own. The Pallas kernel
  (``ops/pallas/kda.py``, ``attention/kda_decode``) reads each live
  row's state once and writes it back in place; where it cannot run
  plain XLA gathers, updates and scatters the rows, counted
  ``attention/fallback/kda_rejected``, never silent.
* :func:`kda_chunk`: a prefill chunk's ``L`` tokens in the chunkwise
  (WY) form, ``attention/kda_chunk``: within a block of ``CHUNK``
  tokens the ``u_t`` solve one unit-lower-triangular system, the
  blocks follow each other through ``S`` alone, and everything but
  that system's 16 x 16 diagonal blocks is matrix products. Every
  decay enters as ``exp(g_t - g_i)`` with ``i <= t``, a number in (0,
  1], whatever the decays are: nothing is divided by a cumulative
  product that may have underflowed.

Everything here is float32 with ``Precision.HIGHEST`` products: the
state compounds its rounding over a whole sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..observability import metrics
from .ring_attention import kernel_mesh

#: tokens of one block of the chunkwise form
CHUNK = 64
#: tokens of a diagonal block, computed pair by pair
SUB = 16
_HI = jax.lax.Precision.HIGHEST


def _mm(eq, *ops):
    return jnp.einsum(eq, *ops, precision=_HI,
                      preferred_element_type=jnp.float32)


def _step(s, q, k, v, a, b):
    """The recurrence's one step on states ``s [..., d_k, d_v]``."""
    s = s * a[..., None]
    u = (v - jnp.sum(s * k[..., None], axis=-2)) * b[..., None]
    s = s + k[..., None] * u[..., None, :]
    return s, jnp.sum(s * q[..., None], axis=-2)


def kda_step(state, rows, q, k, v, a, b, use_kernel: bool = True):
    """``(state, o)``: one token on rows ``rows [n]`` (int32; 0 is the
    null row: a slot that is free or still prefilling, whose ``o`` is
    0) of ``state [R, H, d_k, d_v]`` float32. ``q``, ``k``, ``a`` ``[n,
    H, d_k]``, ``v [n, H, d_v]``, ``b [n, H]`` float32; ``o [n, H,
    d_v]`` float32. Under a jit that donates ``state`` the kernel's
    update is in place."""
    if use_kernel and kernel_mesh() is not None:
        metrics.inc("attention/fallback/mesh_sharded")
    elif use_kernel:
        try:
            from .pallas.kda import kda_decode
            out = kda_decode(state, rows, q, k, v, a, b)
            metrics.inc("attention/kda_decode")
            return out
        except (ImportError, NotImplementedError):
            metrics.inc("attention/fallback/kda_rejected")
    f32 = jnp.float32
    s, o = _step(state[rows], *(t.astype(f32) for t in (q, k, v, a, b)))
    live = rows != 0
    # dead rows all land on the null row, whose content nobody reads
    return state.at[rows].set(s), jnp.where(live[:, None, None], o, 0.0)


def _decay_scores(x, y, g, strict: bool):
    """``M[t, i] = sum_c x[t, c] y[i, c] exp(g[t, c] - g[i, c])`` for
    ``i < t`` (``strict``) or ``i <= t``, else 0; ``x``, ``y``, ``g``
    ``[..., C, d]`` with ``g`` the inclusive cumulative log decay
    (non-increasing along ``C``). Diagonal blocks of :data:`SUB` pair
    by pair; a block row below the diagonal as one product, both
    factors scaled against the row's first ``g``, so that each
    exponent is at most 0."""
    *lead, c, d = x.shape
    nb = c // SUB
    xb, yb, gb = (t.reshape(*lead, nb, SUB, d) for t in (x, y, g))
    t_i = jnp.arange(SUB)
    seen = t_i[None, :] < t_i[:, None] if strict \
        else t_i[None, :] <= t_i[:, None]
    expo = jnp.where(seen[:, :, None],
                     gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf)
    diag = jnp.sum(xb[..., :, None, :] * yb[..., None, :, :]
                   * jnp.exp(expo), axis=-1)          # [.., nb, SUB, SUB]
    ref = gb[..., :1, :]                              # [.., nb, 1, d]
    xs = xb * jnp.exp(gb - ref)
    ys = y[..., None, :, :] * jnp.exp(jnp.minimum(
        ref - g[..., None, :, :], 0.0))               # [.., nb, C, d]
    below = _mm("...tc,...jc->...tj", xs, ys)         # [.., nb, SUB, C]
    blk = jnp.arange(c) // SUB
    below = jnp.where(blk[None, None, :] < jnp.arange(nb)[:, None, None],
                      below, 0.0).reshape(*lead, c, c)
    # block-diagonal placement of ``diag``
    placed = jnp.einsum(
        "...bti,bB->...btBi", diag, jnp.eye(nb, dtype=diag.dtype)
    ).reshape(*lead, c, c)
    return below + placed


def _unit_lower_inverse(n):
    """``(I + n)^-1`` for strictly lower-triangular ``n [..., C, C]``:
    forward substitution on the :data:`SUB` diagonal blocks (row ``i``
    of the inverse is ``e_i - n[i] @ rows before it``), then ``[[A,
    0], [-B n21 A, B]]`` by halves."""
    c = n.shape[-1]
    if c <= SUB:
        inv = jnp.broadcast_to(jnp.eye(c, dtype=n.dtype), n.shape)
        for i in range(1, c):
            row = inv[..., i, :] - _mm("...j,...jk->...k", n[..., i, :],
                                       inv)
            inv = inv.at[..., i, :].set(row)
        return inv
    h = c // 2
    a = _unit_lower_inverse(n[..., :h, :h])
    b = _unit_lower_inverse(n[..., h:, h:])
    low = -_mm("...ij,...jk,...kl->...il", b, n[..., h:, :h], a)
    return jnp.concatenate([
        jnp.concatenate([a, jnp.zeros_like(low).swapaxes(-1, -2)], -1),
        jnp.concatenate([low, b], -1)], -2)


def kda_chunk(q, k, v, g, b, s0):
    """``(o [n, L, H, d_v], s [n, H, d_k, d_v])``: the recurrence over
    ``L`` tokens from state ``s0``, chunkwise. ``q``, ``k``, ``g`` ``[n,
    L, H, d_k]`` with ``g = log a <= 0``; ``v [n, L, H, d_v]``; ``b [n,
    L, H]``; all float32. A position with ``g = 0`` and ``b = 0``
    leaves the state as it was (a padded tail; ``L`` is padded so to a
    multiple of :data:`CHUNK` here)."""
    metrics.inc("attention/kda_chunk")
    n, length, heads, _ = q.shape
    dv = v.shape[-1]
    pad = -length % CHUNK
    if pad:
        q, k, v, g = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for t in (q, k, v, g))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    nc = (length + pad) // CHUNK

    def blocks(t):                  # [n, L, H, d] -> [nc, n, H, C, d]
        return t.reshape(n, nc, CHUNK, heads, -1).transpose(1, 0, 3, 2, 4)
    q, k, v, g = (blocks(t.astype(jnp.float32)) for t in (q, k, v, g))
    b = blocks(b.astype(jnp.float32)[..., None])          # [.., C, 1]
    g = jnp.cumsum(g, axis=-2)
    end = g[..., -1:, :]
    grow = jnp.exp(g)
    # u = (I + diag(b) A)^-1 diag(b) (v - (k grow) s): the part that
    # does not know s, and what multiplies s
    inv = _unit_lower_inverse(b * _decay_scores(k, k, g, True))
    u0 = _mm("...ti,...iv->...tv", inv, b * v)
    w = _mm("...ti,...ik->...tk", inv, b * k * grow)
    read = _decay_scores(q, k, g, False)
    q_in, k_out = q * grow, k * jnp.exp(end - g)
    keep = jnp.exp(end)[..., 0, :, None]                  # [.., d_k, 1]

    def block(s, xs):
        u0, w, q_in, read, k_out, keep = xs
        u = u0 - _mm("...tk,...kv->...tv", w, s)
        o = _mm("...tk,...kv->...tv", q_in, s) \
            + _mm("...ti,...iv->...tv", read, u)
        return keep * s + _mm("...tk,...tv->...kv", k_out, u), o

    s, o = jax.lax.scan(block, s0.astype(jnp.float32),
                        (u0, w, q_in, read, k_out, keep))
    o = o.transpose(1, 0, 3, 2, 4).reshape(n, nc * CHUNK, heads, dv)
    return o[:, :length], s
