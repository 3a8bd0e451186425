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
  An accumulator travels in the operands' dtype (bf16 in training, as
  the all-reduce it replaces) and is added in float32 on arrival; the
  product a device adds to it is kept out of that add's fusion
  (:func:`_matmul_rs_ring`), so it runs while the hop is in flight.

Both carry a custom VJP so the backward pass overlaps too: the
transpose of an all-gather-matmul is a matmul-reduce-scatter and vice
versa, and the weight gradient streams through the same ring
(:func:`_ring_visit`); where input and weight gradient circulate the
same operand (the row-parallel backward) one ring folds both. The
ring/ppermute idiom follows ``ops/ring_attention.py``.

At mp = 2 the ring is one hop between two neighbours: the two
``ppermute`` directions are the same pair of transfers, 0 -> 1 and
1 -> 0, which already load both directions of the one link, so there is
no second direction to split a shard over (v5e 2x2, PR 40: one 16 MB
hop 0.419 ms, two of 8 MB 0.416, four of 4 MB 0.412).

Dispatch lives in the model (`models/gpt/model.py::_CollectiveDense`):
a sequence-parallel layer on a live mesh with mp >= 2 takes the rings
at every site :func:`mp_ring_viable` admits, without being asked. That
function is the single shape gate, pinned by
``tests/test_collective_matmul.py``. The matrix is documented in
``docs/tensor_parallel.md``.
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
    ``fold(acc, shard_from_src, src, offset)`` exactly once per ring
    position — the local shard first, then one hop each way per step,
    so both ICI directions carry traffic while the previous chunks
    compute. ``src`` is the (traced) ring position the shard came
    from, ``offset = (src - idx) % n`` the same as a python int.
    """
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    acc = fold(init, shard, idx, 0)
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_bwd = [(i, (i - 1) % n) for i in range(n)]
    hops_fwd, hops_bwd = n // 2, (n - 1) // 2
    fwd = bwd = shard
    for i in range(1, hops_fwd + 1):
        fwd = jax.lax.ppermute(fwd, axis_name, perm_fwd)
        acc = fold(acc, fwd, (idx - i) % n, n - i)
        if i <= hops_bwd:
            bwd = jax.lax.ppermute(bwd, axis_name, perm_bwd)
            acc = fold(acc, bwd, (idx + i) % n, i)
    return acc


def _in_sequence(chunks, axis_name):
    """``{offset: [b, s/n, f]}`` (the chunk of ring position
    ``(idx + offset) % n``) -> ``[b, s, f]`` in sequence order.

    Where a chunk belongs depends on ``axis_index``. Written into a
    zeroed buffer with ``dynamic_update_slice`` each chunk cost a
    stand-alone copy, 0.52-0.55 ms a site at the 1.3B cell's widths
    and 48 ms of its 418 ms step; a dynamic ``jnp.roll`` of the
    concatenation 0.22 ms. Selected by position it is elementwise and
    its consumer's fusion (the bias add, gelu's derivative, the head
    transposes) reads the chunks through it: 0.005-0.009 ms a site
    over a concatenation at a known place (chip, PR 40).
    """
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    by_offset = [chunks[o] for o in range(n)]
    return jnp.concatenate(
        [jax.lax.select_n((p - idx) % n, *by_offset) for p in range(n)],
        axis=1)


# -- per-shard kernels (call under shard_map) ---------------------------

def _ag_matmul_ring(x, w, axis_name):
    """Per-shard all-gather-matmul: ``x [b, s/n, k]`` (one seq shard),
    ``w [k, n_l]`` (one output-column shard) -> ``y [b, s, n_l]``."""

    def fold(chunks, blk, src, offset):
        return {**chunks, offset: jnp.einsum("bsk,kn->bsn", blk, w)}

    return _in_sequence(_ring_visit(x, axis_name, fold, {}), axis_name)


def _matmul_rs_ring(x, w, axis_name):
    """Per-shard matmul-reduce-scatter: ``x [b, s, k_l]`` (full seq,
    one contraction shard), ``w [k_l, n]`` -> ``y [b, s/n, n]`` fully
    reduced for this device's seq shard.

    Two counter-rotating accumulators: the forward one starts
    ``n//2`` ring positions before its destination and collects a
    partial product at every hop; the backward one covers the
    remaining ``(n-1)//2`` positions from the other side. Each arrives
    at its destination having visited a disjoint device set, so their
    sum is the exact psum — in half the hops of a one-way ring.

    An accumulator and a product have the operands' dtype (a product
    accumulates in float32 on the MXU and is rounded once, like each
    operand of the all-reduce this replaces); their add is float32.
    The product a device adds to an arriving accumulator does not
    depend on it, and has to stay so in the compiled program: fused
    into the add (XLA's convolution+add output fusion) the matmul
    would wait for the wire. The barrier keeps the two apart, so the
    hop runs under the product.
    """
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s, k_l = x.shape
    s_l = s // n
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_bwd = [(i, (i - 1) % n) for i in range(n)]
    hops_fwd, hops_bwd = n // 2, (n - 1) // 2

    def partial_for(dst):
        xc = jax.lax.dynamic_slice(x, (0, dst * s_l, 0), (b, s_l, k_l))
        return jnp.einsum("bsk,kn->bsn", xc, w)

    def add(u, v):
        return (u.astype(jnp.float32)
                + v.astype(jnp.float32)).astype(x.dtype)

    def hop(acc, perm, dst):
        return add(*jax.lax.optimization_barrier(
            (jax.lax.ppermute(acc, axis_name, perm), partial_for(dst))))

    acc_f = partial_for((idx + hops_fwd) % n)
    acc_b = partial_for((idx - hops_bwd) % n) if hops_bwd else None
    for t in range(1, hops_fwd + 1):
        acc_f = hop(acc_f, perm_fwd, (idx + hops_fwd - t) % n)
        if t < hops_bwd:
            acc_b = hop(acc_b, perm_bwd, (idx - hops_bwd + t) % n)
    if hops_bwd:
        # the last backward hop lands on the destination, whose own
        # product the forward accumulator already holds
        acc_f = add(acc_f, jax.lax.ppermute(acc_b, axis_name, perm_bwd))
    return acc_f


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
    dx = _matmul_rs_ring(dy, w.T, axis_name)

    # dw [k, n_l] = AG(x)^T @ dy: stream the x shards through the same
    # bidirectional ring, contracting each against its dy rows
    b, s_l, k = x.shape
    n_l = dy.shape[-1]

    def fold(acc, x_blk, src, offset):
        dyc = jax.lax.dynamic_slice(dy, (0, src * s_l, 0),
                                    (b, s_l, n_l))
        return acc + jnp.einsum("bsk,bsn->kn", x_blk, dyc,
                                preferred_element_type=jnp.float32)

    dw = _ring_visit(x, axis_name, fold,
                     jnp.zeros((k, n_l), jnp.float32))
    return dx, dw.astype(w.dtype)


_ag_matmul.defvjp(_ag_matmul_fwd, _ag_matmul_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _matmul_rs(x, w, axis_name):
    return _matmul_rs_ring(x, w, axis_name)


def _matmul_rs_fwd(x, w, axis_name):
    return _matmul_rs_ring(x, w, axis_name), (x, w)


def _matmul_rs_bwd(axis_name, res, dy):
    # dy [b, s/n, n]: seq-sharded cotangent. dx needs the full seq of
    # dy against w^T (the all-gather-matmul ring, dual of fwd) and
    # dw [k_l, n] = x^T @ AG(dy) the same shards against the matching
    # seq rows of the resident x: one circulation of dy folds both
    x, w = res
    n = _axis_size(axis_name)
    b, s, k_l = x.shape
    s_l = s // n

    def fold(carry, dy_blk, src, offset):
        dx, dw = carry
        xc = jax.lax.dynamic_slice(x, (0, src * s_l, 0), (b, s_l, k_l))
        return ({**dx, offset: jnp.einsum("bsn,kn->bsk", dy_blk, w)},
                dw + jnp.einsum("bsk,bsn->kn", xc, dy_blk,
                                preferred_element_type=jnp.float32))

    dx, dw = _ring_visit(
        dy, axis_name, fold,
        ({}, jnp.zeros((k_l, dy.shape[-1]), jnp.float32)))
    return _in_sequence(dx, axis_name), dw.astype(w.dtype)


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
