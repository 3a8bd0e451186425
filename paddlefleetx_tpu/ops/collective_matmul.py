"""Overlapped tensor-parallel matmuls: decomposed collective rings.

GSPMD lowers the Megatron column/row-parallel linears to a blocking
collective glued to a matmul: under sequence parallelism the column
projection waits for a full seq all-gather over ``mp`` before the MXU
starts, and the row projection's reduce-scatter waits on the full
product. Following "On Optimizing the Communication of Model
Parallelism" (arxiv 2211.05322) and the GSPMD paper's decomposed
collectives (arxiv 2105.04663 §3.4), each collective is decomposed
here into a **bidirectional ppermute ring** whose per-hop transfers
overlap the per-shard matmul chunks:

- :func:`all_gather_matmul` (column-parallel, qkv / fc1): the local
  seq shard of ``x`` circulates both ways around the ``mp`` ring; at
  every hop the chunk that just arrived multiplies the resident weight
  shard, so after ``ceil((mp-1)/2)`` hops every device holds its
  ``[b, s, n/mp]`` output column without ever materializing a blocking
  all-gather.
- :func:`matmul_reduce_scatter` (row-parallel, out-proj / fc2): the
  dual — partial products accumulate into two counter-rotating
  accumulators that arrive fully reduced at their destination shard.

Both carry a custom VJP so the backward pass overlaps too: the
transpose of an all-gather-matmul is a matmul-reduce-scatter and vice
versa, and the weight gradient streams through the same ring
(:func:`_ring_visit`). The ring/ppermute idiom and jax-version shims
follow ``ops/ring_attention.py``.

Dispatch lives in the model (`models/gpt/model.py::_CollectiveDense`
behind ``use_collective_matmul``); :func:`mp_ring_viable` is the
single shape gate, pinned by ``tests/test_collective_matmul.py``. The
matrix is documented in ``docs/tensor_parallel.md``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .ring_attention import _axis_size, _shard_map


def _smap(fn, mesh, in_specs, out_specs):
    """shard_map with vma checking off: the checker has no rewrite
    rule for ``custom_vjp_call`` in transposed rings, and the specs
    below are exact by construction."""
    return _shard_map(fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)


def _ring_visit(shard, axis_name, fold, init):
    """Bidirectionally circulate ``shard`` over the ring; call
    ``fold(acc, shard_from_src, src)`` exactly once per ring position
    — the local shard first, then one hop each way per step, so both
    ICI directions carry traffic while the previous chunks compute.
    """
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    acc = fold(init, shard, idx)
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_bwd = [(i, (i - 1) % n) for i in range(n)]
    hops_fwd, hops_bwd = n // 2, (n - 1) // 2
    fwd = bwd = shard
    for i in range(1, hops_fwd + 1):
        fwd = jax.lax.ppermute(fwd, axis_name, perm_fwd)
        acc = fold(acc, fwd, (idx - i) % n)
        if i <= hops_bwd:
            bwd = jax.lax.ppermute(bwd, axis_name, perm_bwd)
            acc = fold(acc, bwd, (idx + i) % n)
    return acc


def _zero_like_varying(shape, dtype, ref):
    """A zeros array carrying ``ref``'s device-varying type (the
    ring_attention accumulator trick — required if a future jax build
    re-enables vma tracking for these rings)."""
    z = jnp.sum(ref.astype(jnp.float32)) * 0.0
    return jnp.zeros(shape, dtype) + z.astype(dtype)


# -- per-shard kernels (call under shard_map) ---------------------------

def _ag_matmul_ring(x, w, axis_name):
    """Per-shard all-gather-matmul: ``x [b, s/n, k]`` (one seq shard),
    ``w [k, n_l]`` (one output-column shard) -> ``y [b, s, n_l]``."""
    n = _axis_size(axis_name)
    b, s_l, _ = x.shape
    n_l = w.shape[-1]

    def fold(buf, blk, src):
        chunk = jnp.einsum("bsk,kn->bsn", blk, w)
        return jax.lax.dynamic_update_slice(buf, chunk,
                                            (0, src * s_l, 0))

    return _ring_visit(
        x, axis_name, fold,
        _zero_like_varying((b, n * s_l, n_l), x.dtype, x))


def _matmul_rs_ring(x, w, axis_name):
    """Per-shard matmul-reduce-scatter: ``x [b, s, k_l]`` (full seq,
    one contraction shard), ``w [k_l, n]`` -> ``y [b, s/n, n]`` fully
    reduced for this device's seq shard.

    Two counter-rotating fp32 accumulators: the forward one starts
    ``n//2`` ring positions before its destination and collects a
    partial product at every hop; the backward one covers the
    remaining ``(n-1)//2`` positions from the other side. Each arrives
    at its destination having visited a disjoint device set, so their
    sum is the exact psum — in half the hops of a one-way ring.
    """
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s, k_l = x.shape
    s_l = s // n
    n_out = w.shape[-1]
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_bwd = [(i, (i - 1) % n) for i in range(n)]
    hops_fwd, hops_bwd = n // 2, (n - 1) // 2

    def partial_for(dst):
        xc = jax.lax.dynamic_slice(x, (0, dst * s_l, 0), (b, s_l, k_l))
        return jnp.einsum("bsk,kn->bsn", xc, w,
                          preferred_element_type=jnp.float32)

    acc_f = _zero_like_varying((b, s_l, n_out), jnp.float32, x)
    acc_b = _zero_like_varying((b, s_l, n_out), jnp.float32, x)
    for t in range(hops_fwd + 1):
        acc_f = acc_f + partial_for((idx + hops_fwd - t) % n)
        if t < hops_fwd:
            acc_f = jax.lax.ppermute(acc_f, axis_name, perm_fwd)
        if t < hops_bwd:
            acc_b = acc_b + partial_for((idx - hops_bwd + t) % n)
            acc_b = jax.lax.ppermute(acc_b, axis_name, perm_bwd)
    return (acc_f + acc_b).astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ag_matmul(x, w, axis_name):
    return _ag_matmul_ring(x, w, axis_name)


def _ag_matmul_fwd(x, w, axis_name):
    return _ag_matmul_ring(x, w, axis_name), (x, w)


def _ag_matmul_bwd(axis_name, res, dy):
    # dy [b, s, n_l]: the cotangent of the seq-gathered, column-sharded
    # output. dx contracts the mp-sharded n_l dim -> partial sums whose
    # seq-sharded reduction is exactly the matmul-reduce-scatter ring
    # (the transpose duality the module docstring states).
    x, w = res
    dx = _matmul_rs_ring(dy, w.T, axis_name).astype(x.dtype)

    # dw [k, n_l] = AG(x)^T @ dy: stream the x shards through the same
    # bidirectional ring, contracting each against its dy rows
    b, s_l, k = x.shape
    n_l = dy.shape[-1]

    def fold(acc, x_blk, src):
        dyc = jax.lax.dynamic_slice(dy, (0, src * s_l, 0),
                                    (b, s_l, n_l))
        return acc + jnp.einsum("bsk,bsn->kn", x_blk, dyc,
                                preferred_element_type=jnp.float32)

    dw = _ring_visit(
        x, axis_name, fold,
        _zero_like_varying((k, n_l), jnp.float32, x))
    return dx, dw.astype(w.dtype)


_ag_matmul.defvjp(_ag_matmul_fwd, _ag_matmul_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _matmul_rs(x, w, axis_name):
    return _matmul_rs_ring(x, w, axis_name)


def _matmul_rs_fwd(x, w, axis_name):
    return _matmul_rs_ring(x, w, axis_name), (x, w)


def _matmul_rs_bwd(axis_name, res, dy):
    # dy [b, s/n, n]: seq-sharded cotangent. dx needs the full seq of
    # dy against w^T -> the all-gather-matmul ring (dual of fwd).
    x, w = res
    n = _axis_size(axis_name)
    dx = _ag_matmul_ring(dy, w.T, axis_name).astype(x.dtype)

    # dw [k_l, n] = x^T @ AG(dy): circulate the dy shards, contract
    # each against the matching seq rows of the resident x
    b, s, k_l = x.shape
    s_l = s // n
    n_out = dy.shape[-1]

    def fold(acc, dy_blk, src):
        xc = jax.lax.dynamic_slice(x, (0, src * s_l, 0), (b, s_l, k_l))
        return acc + jnp.einsum("bsk,bsn->kn", xc, dy_blk,
                                preferred_element_type=jnp.float32)

    dw = _ring_visit(
        dy, axis_name, fold,
        _zero_like_varying((k_l, n_out), jnp.float32, dy))
    return dx, dw.astype(w.dtype)


_matmul_rs.defvjp(_matmul_rs_fwd, _matmul_rs_bwd)


# -- global-view wrappers ----------------------------------------------

def mp_ring_viable(mesh, batch: int, seq: int,
                   sharded_dims: Sequence[int] = (),
                   axis_name: Optional[str] = None,
                   batch_axes=None) -> bool:
    """True iff the decomposed rings can run these global shapes: a
    live mesh with mp >= 2, batch divisible over the dataflow axes,
    seq divisible by mp (equal ring chunks), and every mp-sharded
    weight dim divisible by mp. Exactly the fallback gate of the model
    wiring — pinned by the dispatch probes in
    ``tests/test_collective_matmul.py``."""
    from ..parallel.mesh import DATA_AXES, MP_AXIS
    axis_name = axis_name or MP_AXIS
    batch_axes = batch_axes or DATA_AXES
    if mesh is None:
        return False
    mp = mesh.shape.get(axis_name, 1)
    if mp < 2:
        return False
    bsz = int(np.prod([mesh.shape[a] for a in batch_axes]))
    if batch % bsz or seq % mp:
        return False
    return all(d % mp == 0 for d in sharded_dims)


def all_gather_matmul(x: jax.Array, w: jax.Array, mesh, *,
                      w_shard_dim: int = 0,
                      axis_name: Optional[str] = None,
                      batch_axes=None) -> jax.Array:
    """Column-parallel ``x @ w`` with the seq all-gather decomposed
    into the overlapped ring.

    ``x``: global ``[b, s, k]`` with s sharded over ``axis_name``
    (the Megatron-SP layout); ``w``: global ``[k, *feat]`` with
    ``feat[w_shard_dim]`` sharded over ``axis_name``. Returns global
    ``[b, s, *feat]`` — seq gathered, ``feat[w_shard_dim]`` sharded —
    the exact sharding the plain GSPMD path produces. Weight dims
    sharded over *other* axes (ZeRO-3's fsdp on k) are gathered by
    GSPMD outside the shard_map, as in the plain lowering.
    """
    from ..parallel.mesh import DATA_AXES, MP_AXIS
    axis_name = axis_name or MP_AXIS
    batch_axes = batch_axes or DATA_AXES
    feat = w.shape[1:]
    feat_spec = [axis_name if i == w_shard_dim else None
                 for i in range(len(feat))]

    def body(xl, wl):
        y = _ag_matmul(xl, wl.reshape(wl.shape[0], -1), axis_name)
        return y.reshape(y.shape[:2] + wl.shape[1:])

    return _smap(
        body, mesh,
        in_specs=(P(batch_axes, axis_name, None), P(None, *feat_spec)),
        out_specs=P(batch_axes, None, *feat_spec))(x, w)


def matmul_reduce_scatter(x: jax.Array, w: jax.Array, mesh, *,
                          contract_ndim: int = 1,
                          axis_name: Optional[str] = None,
                          batch_axes=None) -> jax.Array:
    """Row-parallel ``x @ w`` with the output reduce-scatter
    decomposed into the overlapped ring.

    ``x``: global ``[b, s, *c]`` where ``c = w.shape[:contract_ndim]``
    and ``c[0]`` is sharded over ``axis_name`` (the row-parallel input
    layout: attention heads for out-proj, the ffn dim for fc2);
    ``w``: global ``[*c, n]`` with ``c[0]`` sharded. Returns global
    ``[b, s, n]`` with s sharded over ``axis_name`` — the
    sequence-parallel layout the plain GSPMD reduce-scatter produces.
    """
    from ..parallel.mesh import DATA_AXES, MP_AXIS
    axis_name = axis_name or MP_AXIS
    batch_axes = batch_axes or DATA_AXES
    rest = [None] * (contract_ndim - 1)

    def body(xl, wl):
        xl2 = xl.reshape(xl.shape[0], xl.shape[1], -1)
        return _matmul_rs(xl2, wl.reshape(-1, wl.shape[-1]), axis_name)

    return _smap(
        body, mesh,
        in_specs=(P(batch_axes, None, axis_name, *rest),
                  P(axis_name, *rest, None)),
        out_specs=P(batch_axes, axis_name, None))(x, w)
