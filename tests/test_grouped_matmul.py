"""Pallas grouped expert GEMM semantics (ops/pallas/grouped_matmul.py),
validated on CPU via the Pallas interpreter — forward/backward against
the dense batched-matmul reference, the empty-group skip, the
weight-replication (rep > 1) indexing, and the kernel-admission
(fallback) contract. The MoE-layer-level parity matrix lives in
tests/test_moe.py."""

import os

os.environ["PFX_PALLAS_INTERPRET"] = "1"

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.ops.pallas.grouped_matmul import (
    grouped_matmul, ragged_layout, ragged_matmul,
)


def _case(g=6, gw=3, c=8, k=16, n=24, seed=0, fill=0.6):
    """Random [G, C, K] groups with capacity-padded (zeroed) rows and
    a per-group live count; rep = G // Gw rows share each weight."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, c + 1, size=g).astype(np.int32)
    counts[: max(1, int(g * (1 - fill)))] = 0  # guarantee empty groups
    rng.shuffle(counts)
    x = rng.normal(size=(g, c, k)).astype(np.float32)
    mask = np.arange(c)[None, :, None] < counts[:, None, None]
    x = x * mask  # rows past counts[g] are zero (the kernel contract)
    w = rng.normal(size=(gw, k, n)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(w), jnp.asarray(counts)


def _dense_ref(x, w):
    rep = x.shape[0] // w.shape[0]
    wg = jnp.repeat(w, rep, axis=0)
    return jnp.einsum("gck,gkn->gcn", x, wg)


@pytest.mark.parametrize("g,gw", [(4, 4), (6, 3), (8, 2)])
def test_forward_matches_dense(g, gw):
    x, w, counts = _case(g=g, gw=gw)
    got = grouped_matmul(x, w, counts)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_ref(x, w)),
                               atol=1e-5, rtol=1e-5)


def test_empty_groups_produce_zero_blocks():
    x, w, counts = _case(fill=0.3)
    got = np.asarray(grouped_matmul(x, w, counts))
    for gi in np.nonzero(np.asarray(counts) == 0)[0]:
        np.testing.assert_array_equal(got[gi], 0.0)


def test_all_groups_empty_is_all_zero():
    x, w, counts = _case()
    zero = jnp.zeros_like(counts)
    got = grouped_matmul(jnp.zeros_like(x), w, zero)
    np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_gradients_match_dense():
    """The custom VJP (dx via the transposed forward kernel, dw via the
    per-expert accumulation kernel) must match autodiff through the
    dense reference — including zero dx/dw contributions from the
    skipped empty groups, whose cotangent rows are zero under the MoE
    combine contract."""
    x, w, counts = _case(g=6, gw=3, fill=0.5)
    live = (jnp.arange(x.shape[1])[None, :, None]
            < counts[:, None, None]).astype(x.dtype)

    def loss(fn):
        # cube to make the grads weight-dependent; mask the padded
        # rows exactly as the gate-weighted combine does
        return lambda xx, ww: ((fn(xx, ww) * live) ** 3).sum()

    ref_l, (ref_dx, ref_dw) = jax.value_and_grad(
        loss(_dense_ref), argnums=(0, 1))(x, w)
    got_l, (got_dx, got_dw) = jax.value_and_grad(
        loss(lambda xx, ww: grouped_matmul(xx, ww, counts)),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_dx), np.asarray(ref_dx),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_dw), np.asarray(ref_dw),
                               atol=1e-4, rtol=1e-4)


def test_fp32_accumulation_under_bf16_inputs():
    """bf16 in, bf16 out, but the contraction accumulates in fp32
    scratch: the result must track the fp32 reference to bf16
    resolution, not drift with K."""
    x, w, counts = _case(k=256, n=8)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    got = grouped_matmul(xb, wb, counts)
    assert got.dtype == jnp.bfloat16
    ref = _dense_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref),
        atol=0.1, rtol=0.05)


def test_runs_under_jit():
    x, w, counts = _case()
    got = jax.jit(grouped_matmul)(x, w, counts)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_ref(x, w)),
                               atol=1e-5, rtol=1e-5)


def test_block_shrink_handles_indivisible_dims():
    # n=24, k=16 don't divide the 128/512 defaults — _block shrinks
    x, w, counts = _case(c=5, k=12, n=20)
    got = grouped_matmul(x, w, counts)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_ref(x, w)),
                               atol=1e-5, rtol=1e-5)


def test_shape_rejection_is_notimplemented():
    """Kernel admission failures must raise NotImplementedError — the
    MoE layer catches exactly that to fall back to its XLA expert
    einsums (counted moe/fallback/pallas_rejected)."""
    x, w, counts = _case(g=6, gw=3)
    with pytest.raises(NotImplementedError):
        grouped_matmul(x[0], w, counts)             # x not 3D
    with pytest.raises(NotImplementedError):
        grouped_matmul(x, jnp.concatenate([w, w[:1]]),
                       counts)                      # Gw does not divide G
    with pytest.raises(NotImplementedError):
        grouped_matmul(x, w, counts[:-1])           # counts length
    with pytest.raises(NotImplementedError):
        grouped_matmul(x, jnp.swapaxes(w, 1, 2), counts)  # K mismatch
    with pytest.raises(NotImplementedError):
        grouped_matmul(x, w, counts.astype(jnp.float32))  # counts dtype


# -- the ragged form (no capacity): forward, dx, dw against einsums ----

def _ragged_case(sizes, k=32, n=48, block_m=8, seed=0):
    sizes = np.asarray(sizes, np.int32)
    tiles = int(sizes.sum()) // block_m + 1 + len(sizes)
    table, used, start, rows = ragged_layout(jnp.asarray(sizes), block_m,
                                             tiles)
    rng = np.random.default_rng(seed)
    x = np.zeros((tiles * block_m, k), np.float32)
    group_of_row = np.full(tiles * block_m, -1)
    for g, size in enumerate(sizes):
        lo = int(start[g])
        x[lo:lo + size] = rng.normal(size=(size, k))
        group_of_row[lo:lo + size] = g
    w = rng.normal(size=(len(sizes), k, n)).astype(np.float32)
    dy = rng.normal(size=(tiles * block_m, n)).astype(np.float32)
    return (jnp.asarray(x), jnp.asarray(w), table, used, rows,
            group_of_row, jnp.asarray(dy), block_m)


@pytest.mark.parametrize("sizes", [
    [5, 0, 17, 8],          # uneven, one empty, none tile-aligned
    [0, 0, 0, 0],           # nothing routed here
    [30, 0, 0, 0],          # every row on one expert
    [8, 16, 8, 24]])        # all tile-aligned
def test_ragged_forward_dx_dw_match_einsums(sizes):
    x, w, table, used, rows, group_of_row, dy, bm = _ragged_case(sizes)
    live = jnp.asarray(group_of_row >= 0)[:, None]
    assert int(used) == sum(max(1, -(-s // bm)) for s in sizes)
    assert [int(r) for r in rows] == [bm * max(1, -(-s // bm))
                                      for s in sizes]

    def kernel(x, w):
        out = ragged_matmul(x, w, table, used, block_m=bm, block_n=16,
                            block_k=16)
        return jnp.where(live, out, 0)   # rows past `used` are unspecified

    def dense(x, w):
        out = jnp.einsum("mk,mkn->mn", x, w[np.maximum(group_of_row, 0)])
        return jnp.where(live, out, 0)
    got, vjp = jax.vjp(kernel, x, w)
    want, vjp_ref = jax.vjp(dense, x, w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    (dx, dw), (dx_ref, dw_ref) = vjp(dy), vjp_ref(dy)
    np.testing.assert_allclose(jnp.where(live, dx, 0), dx_ref, atol=1e-5)
    np.testing.assert_allclose(dw, dw_ref, atol=1e-5)
    # XLA's ragged_dot on the same buffer: the counted stand-in
    np.testing.assert_allclose(
        jnp.where(live, jax.lax.ragged_dot(x, w, rows), 0), want,
        atol=1e-5)


def test_ragged_matmul_refuses_what_it_cannot_take(monkeypatch):
    x, w, table, used, *_ = _ragged_case([5, 3])
    with pytest.raises(NotImplementedError):
        ragged_matmul(x[:-1], w, table, used, block_m=8)
    with pytest.raises(NotImplementedError):
        ragged_matmul(x, w[:, :-1], table, used, block_m=8)
    monkeypatch.delenv("PFX_PALLAS_INTERPRET")
    with pytest.raises(NotImplementedError):
        ragged_matmul(x, w, table, used, block_m=8)
