"""Flash-attention kernel semantics, validated on CPU via the Pallas
interpreter (the real-TPU path is exercised by the benchmark's cells,
``chipbench/run.py``, and compiled for the described chip in
``tests/test_chip_compile.py``)."""

import os

os.environ["PFX_PALLAS_INTERPRET"] = "1"

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.ops.attention import _xla_attention
from paddlefleetx_tpu.ops.pallas.flash_attention import (
    check_shapes, flash_attention,
)


def _rand(b=1, s=256, h=2, d=64, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        for _ in range(3))


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_xla(causal):
    q, k, v = _rand()
    ref = _xla_attention(q, k, v, None, causal, 0, 0.0, None, True, True)
    got = flash_attention(q, k, v, causal=causal, block_q=128,
                          block_kv=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_grads_match_xla():
    q, k, v = _rand(s=256)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=128,
                                block_kv=128) ** 2).sum()

    def loss_ref(q, k, v):
        return (_xla_attention(q, k, v, None, True, 0, 0.0, None, True,
                               True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("s,blocks", [(256, None), (1024, 256)])
def test_latent_attention_widths_match_xla(s, blocks):
    """q/k scored at 192, values of 128 (MLA), the scale an argument:
    forward and backward against the XLA path, through the combined
    (one q block) and the fused / split (several) backward."""
    from paddlefleetx_tpu.ops.attention import _xla_attention
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (1, s, 2, 192), jnp.float32)
    k = jax.random.normal(ks[1], (1, s, 2, 192), jnp.float32)
    v = jax.random.normal(ks[2], (1, s, 2, 128), jnp.float32)
    g = jax.random.normal(ks[3], (1, s, 2, 128), jnp.float32)
    scale = 0.05

    def flash(q, k, v):
        out = flash_attention(q, k, v, block_q=blocks, block_kv=blocks,
                              sm_scale=scale)
        assert out.shape == v.shape
        return jnp.sum(out * g)

    def dense(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, True, 0, 0.0, None,
                                      True, True, sm_scale=scale) * g)
    got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_widths_the_kernel_cannot_take_are_refused():
    for d, d_v in [(192, 192), (96, 64), (192, 64), (160, 128)]:
        with pytest.raises(NotImplementedError):
            check_shapes(256, 256, d, d_v=d_v)
    assert check_shapes(256, 256, 192, d_v=128) == (256, 256)
    assert check_shapes(256, 256, 128, d_v=128) == (256, 256)


@pytest.mark.parametrize("causal", [True, False])
def test_fused_multiblock_backward_grads_match_xla(causal):
    """sq=1024 with 512 blocks routes the backward through the fused
    q-resident one-pass kernel (num_q=2, within the VMEM budget);
    its gradients must match the XLA oracle like the split pair's."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa

    q, k, v = _rand(s=1024)
    # the shape gate really selects the fused path (dispatch helper
    # takes [bh, s, d] arrays) ...
    qq = jnp.zeros((2, 1024, 64), jnp.float32)
    assert fa._flash_backward_fused(
        qq, qq, qq, qq, jnp.zeros((2, 1024, 1), jnp.float32),
        jnp.zeros((2, 1024, 1), jnp.float32), 1.0, causal, 0) \
        is not None
    # ... and beyond the resident budget it declines
    big = jnp.zeros((1, 16384, 64), jnp.float32)
    assert fa._flash_backward_fused(
        big, big, big, big, jnp.zeros((1, 16384, 1), jnp.float32),
        jnp.zeros((1, 16384, 1), jnp.float32), 1.0, causal, 0) is None

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=512,
                                block_kv=512) ** 2).sum()

    def loss_ref(q, k, v):
        return (_xla_attention(q, k, v, None, causal, 0, 0.0, None,
                               True, True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def test_with_lse_matches_dense_including_lse_grads():
    """flash_attention_with_lse: the lse output matches a dense
    logsumexp, and gradients flow correctly through BOTH outputs (the
    lse cotangent folds into the backward kernels' delta term)."""
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_attention_with_lse,
    )
    q, k, v = _rand(s=256)
    d = q.shape[-1]

    def dense_out_lse(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask, s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)      # [b,h,q]
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return out, lse

    out, lse = flash_attention_with_lse(q, k, v, block_q=128,
                                        block_kv=128)
    ref_out, ref_lse = dense_out_lse(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        o, s = flash_attention_with_lse(q, k, v, block_q=128,
                                        block_kv=128)
        return (o ** 2).sum() + (jnp.sin(s)).sum()

    def loss_ref(q, k, v):
        o, s = dense_out_lse(q, k, v)
        return (o ** 2).sum() + (jnp.sin(s)).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_with_flash_blocks_matches_dense(causal):
    """The flash-per-block ring path == dense attention, fwd and bwd
    (diagonal/full/dead block dispatch + lse streaming combination)."""
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    from paddlefleetx_tpu.ops.ring_attention import (
        ring_attention_sharded,
    )
    from paddlefleetx_tpu.parallel import TopologyConfig, build_mesh

    rng = np.random.default_rng(9)
    b, s, h, d = 1, 512, 2, 64              # 128-token blocks on cp=4
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
               for _ in range(3))
    topo = TopologyConfig(cp_degree=4)
    mesh = build_mesh(topo, devices=jax.devices()[:4])

    want = dot_product_attention(q, k, v, causal=causal)
    got = ring_attention_sharded(q, k, v, mesh, causal=causal,
                                 use_flash=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    def loss(fn):
        def f(q, k, v):
            return (fn(q, k, v) ** 2).sum()
        return jax.grad(f, argnums=(0, 1, 2))

    gf = loss(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh, causal=causal, use_flash=True))(q, k, v)
    gr = loss(lambda q, k, v: dot_product_attention(
        q, k, v, causal=causal))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_combined_backward_multi_kv_blocks_matches_xla():
    """The num_q==1 combined backward kernel (single q block, several
    kv blocks — the training hot path's regime) must reproduce XLA
    gradients: exercises dq accumulation across kv blocks and the
    per-ki direct dk/dv writes, which the split-kernel tests never
    reach."""
    q, k, v = _rand(s=256)

    def loss_flash(q, k, v):
        # block_q=256 -> num_q=1, block_kv=128 -> num_kv=2
        return (flash_attention(q, k, v, block_q=256,
                                block_kv=128) ** 2).sum()

    def loss_ref(q, k, v):
        return (_xla_attention(q, k, v, None, True, 0, 0.0, None, True,
                               True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_bf16_training_dtype_matches_xla_within_tolerance():
    """Kernel vs XLA path at the TRAINING dtype (bf16 q/k/v, fp32
    accumulation in both): the kernel pre-scales q in bf16 (one extra
    rounding vs scaling fp32 scores), so the paths are close but not
    bit-equal. Tolerances are set from the real-chip measurement
    (v5e, b=4/s=1024/h=8/d=64: fwd max |diff| 0.016 at |out|~0.08
    mean, dq max |diff| 0.17 at sum-of-squares loss) with ~3x
    headroom; a regression in the scaling scheme would blow well
    past them."""
    rng = np.random.default_rng(5)
    shape = (2, 256, 2, 64)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
               for _ in range(3))
    ref = _xla_attention(q, k, v, None, True, 0, 0.0, None, True, True)
    got = flash_attention(q, k, v, causal=True, block_q=128,
                          block_kv=128)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5e-2, rtol=5e-2)

    def loss_flash(q):
        return (flash_attention(q, k, v, block_q=128,
                                block_kv=128).astype(jnp.float32)
                ** 2).sum()

    def loss_ref(q):
        return (_xla_attention(q, k, v, None, True, 0, 0.0, None, True,
                               True).astype(jnp.float32) ** 2).sum()

    gf = np.asarray(jax.grad(loss_flash)(q), np.float32)
    gr = np.asarray(jax.grad(loss_ref)(q), np.float32)
    np.testing.assert_allclose(gf, gr, atol=0.5, rtol=0.1)


def test_uneven_blocks_fall_back():
    q, k, v = _rand(s=100)
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, block_q=64, block_kv=64)


def test_dispatch_falls_back_to_xla_on_unsupported():
    """ops.dot_product_attention must not crash when flash refuses."""
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    q, k, v = _rand(s=100)
    out = dot_product_attention(q, k, v, use_flash=True)
    ref = _xla_attention(q, k, v, None, True, 0, 0.0, None, True, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6)


def test_decode_matches_xla_and_ignores_garbage():
    """flash_decode == XLA cached-decode attention, and cache contents
    past the index never leak into the output."""
    from paddlefleetx_tpu.ops.pallas.flash_attention import flash_decode
    rng = np.random.default_rng(3)
    b, S, h, d = 2, 256, 2, 64
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    # tile-exact cache layout [b, h, d, S]
    k = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    for off in (0, 5, 130, 255):
        ref = _xla_attention(q, k, v, None, True, off, 0.0, None, True,
                             True, kv_cache_layout=True)
        got = flash_decode(q, k, v, jnp.int32(off), block_kv=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)
        # garbage independence: mutate the cache beyond the offset
        k2 = k.at[..., off + 1:].set(1e3)
        v2 = v.at[..., off + 1:].set(-1e3)
        got2 = flash_decode(q, k2, v2, jnp.int32(off), block_kv=128)
        np.testing.assert_allclose(np.asarray(got2), np.asarray(got),
                                   atol=2e-6, rtol=2e-6)


def test_decode_works_under_jit_with_traced_offset():
    from paddlefleetx_tpu.ops.pallas.flash_attention import flash_decode
    rng = np.random.default_rng(4)
    b, S, h, d = 1, 128, 2, 64
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)

    @jax.jit
    def step(off):
        return flash_decode(q, k, v, off)

    a = step(jnp.int32(7))
    bb = step(jnp.int32(100))          # same trace, new offset
    ref_a = _xla_attention(q, k, v, None, True, 7, 0.0, None, True, True,
                           kv_cache_layout=True)
    ref_b = _xla_attention(q, k, v, None, True, 100, 0.0, None, True,
                           True, kv_cache_layout=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(ref_a),
                               atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(bb), np.asarray(ref_b),
                               atol=2e-6, rtol=2e-6)


def test_decode_dispatch_from_dot_product_attention():
    """dot_product_attention routes single-token cached decode to the
    kernel (use_flash) and falls back cleanly on odd shapes."""
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    rng = np.random.default_rng(5)
    b, S, h, d = 1, 256, 2, 64
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    out = dot_product_attention(q, k, v, causal=True,
                                query_offset=jnp.int32(17),
                                use_flash=True, kv_cache_layout=True)
    ref = _xla_attention(q, k, v, None, True, 17, 0.0, None, True, True,
                         kv_cache_layout=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # head_dim the kernel rejects (not a sublane multiple) -> XLA
    # fallback, still correct
    q2 = q[..., :44]
    k2 = k[:, :, :44, :]
    v2 = v[:, :, :44, :]
    out2 = dot_product_attention(q2, k2, v2, causal=True,
                                 query_offset=jnp.int32(3),
                                 use_flash=True, kv_cache_layout=True)
    ref2 = _xla_attention(q2, k2, v2, None, True, 3, 0.0, None, True,
                          True, kv_cache_layout=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               atol=2e-6, rtol=2e-6)


def test_decode_with_leftpad_bias_matches_xla():
    """The decode kernel honors the generation loop's [b,1,1,S]
    additive left-pad bias."""
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    rng = np.random.default_rng(6)
    b, S, h, d = 2, 256, 2, 64
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    # row 0 pads the first 3 slots, row 1 the first 120
    valid = np.ones((b, S), bool)
    valid[0, :3] = False
    valid[1, :120] = False
    bias = jnp.where(jnp.asarray(valid), 0.0, -1e9)[:, None, None, :]
    off = jnp.int32(130)
    out = dot_product_attention(q, k, v, bias=bias, causal=True,
                                query_offset=off, use_flash=True,
                                kv_cache_layout=True)
    ref = _xla_attention(q, k, v, bias, True, off, 0.0, None, True, True,
                         kv_cache_layout=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def _decode_batch(b=4, S=256, h=2, d=64, seed=11):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    return q, k, v


def test_flash_decode_ragged_matches_xla_per_row():
    """flash_decode_ragged with per-row cache lengths == the XLA
    per-row-offset oracle, and garbage past EACH row's length never
    leaks (the continuous-batching invariant: a fresh slot shares the
    tick with deep slots whose cache tails it must not read)."""
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_decode, flash_decode_ragged,
    )
    q, k, v = _decode_batch()
    offs = jnp.asarray([0, 5, 130, 255], jnp.int32)
    ref = _xla_attention(q, k, v, None, True, offs, 0.0, None, True,
                         True, kv_cache_layout=True)
    got = flash_decode_ragged(q, k, v, offs, block_kv=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # garbage independence per row
    mask = np.arange(256)[None, :] > np.asarray(offs)[:, None]
    k2 = jnp.where(jnp.asarray(mask)[:, None, None, :], 1e3, k)
    v2 = jnp.where(jnp.asarray(mask)[:, None, None, :], -1e3, v)
    got2 = flash_decode_ragged(q, k2, v2, offs, block_kv=128)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(got),
                               atol=2e-6, rtol=2e-6)
    # all-equal lengths degenerate to the scalar kernel exactly
    uni = jnp.full((4,), 130, jnp.int32)
    np.testing.assert_allclose(
        np.asarray(flash_decode_ragged(q, k, v, uni, block_kv=128)),
        np.asarray(flash_decode(q, k, v, jnp.int32(130),
                                block_kv=128)),
        atol=2e-6, rtol=2e-6)


def test_flash_decode_ragged_under_jit_with_traced_offsets():
    """One compiled tick serves any slot-length vector (the serving
    decode loop retraces nothing as slots churn)."""
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_decode_ragged,
    )
    q, k, v = _decode_batch(b=2, S=128, seed=12)

    @jax.jit
    def step(offs):
        return flash_decode_ragged(q, k, v, offs)

    for offs in ([3, 100], [127, 0]):
        offs = jnp.asarray(offs, jnp.int32)
        ref = _xla_attention(q, k, v, None, True, offs, 0.0, None,
                             True, True, kv_cache_layout=True)
        np.testing.assert_allclose(np.asarray(step(offs)),
                                   np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)


def test_flash_decode_ragged_rejects_bad_offset_shapes():
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_decode_ragged,
    )
    q, k, v = _decode_batch(b=2, S=128, seed=13)
    with pytest.raises(NotImplementedError):
        flash_decode_ragged(q, k, v, jnp.zeros((3,), jnp.int32))
    with pytest.raises(NotImplementedError):
        flash_decode_ragged(q, k, v, jnp.zeros((2, 2), jnp.int32))


@pytest.mark.parametrize("window", [1, 3])
def test_contiguous_decode_kernels_refuse_grouped_heads(window):
    """Only the paged kernel knows groups: a contiguous cache of fewer
    K/V heads than query heads is refused by name, single token and
    verify window alike, and the caller's dense path serves."""
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_decode, flash_decode_ragged,
    )
    q, k, v = _decode_batch(b=2, S=128, seed=13)
    q = jnp.concatenate([q] * window, axis=1)
    offs = jnp.asarray([5, 100], jnp.int32)
    with pytest.raises(NotImplementedError, match="grouped"):
        flash_decode_ragged(q, k[:, :1], v[:, :1], offs)
    if window == 1:
        with pytest.raises(NotImplementedError, match="grouped"):
            flash_decode(q, k[:, :1], v[:, :1], 5)


def test_ragged_decode_dispatch_and_counter():
    """dot_product_attention routes a [b] query_offset to the ragged
    kernel (counter `attention/flash_decode_ragged`), falls back to
    the identically-masked dense path on kernel-rejected shapes, and
    honors the [b,1,1,S] left-pad bias — the docs/inference.md decode
    dispatch matrix rows for ragged offsets."""
    from paddlefleetx_tpu.observability import metrics
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    q, k, v = _decode_batch(b=2, S=256, seed=14)
    offs = jnp.asarray([17, 200], jnp.int32)
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    reg.reset()
    try:
        out = dot_product_attention(q, k, v, causal=True,
                                    query_offset=offs, use_flash=True,
                                    kv_cache_layout=True)
        assert reg.counter("attention/flash_decode_ragged") == 1
        assert reg.counter("attention/dense") == 0
        ref = _xla_attention(q, k, v, None, True, offs, 0.0, None,
                             True, True, kv_cache_layout=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)
        # left-pad bias rides along (row 1 pads its first 120 slots)
        valid = np.ones((2, 256), bool)
        valid[1, :120] = False
        bias = jnp.where(jnp.asarray(valid), 0.0, -1e9)[:, None, None, :]
        outb = dot_product_attention(q, k, v, bias=bias, causal=True,
                                     query_offset=offs, use_flash=True,
                                     kv_cache_layout=True)
        refb = _xla_attention(q, k, v, bias, True, offs, 0.0, None,
                              True, True, kv_cache_layout=True)
        np.testing.assert_allclose(np.asarray(outb), np.asarray(refb),
                                   atol=2e-6, rtol=2e-6)
        # head_dim the kernel rejects -> dense fallback, same per-row
        # masking
        reg.reset()
        q2, k2, v2 = q[..., :44], k[:, :, :44, :], v[:, :, :44, :]
        out2 = dot_product_attention(q2, k2, v2, causal=True,
                                     query_offset=offs, use_flash=True,
                                     kv_cache_layout=True)
        assert reg.counter("attention/fallback/kernel_rejected") == 1
        assert reg.counter("attention/dense") == 1
        ref2 = _xla_attention(q2, k2, v2, None, True, offs, 0.0, None,
                              True, True, kv_cache_layout=True)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                                   atol=2e-6, rtol=2e-6)
    finally:
        metrics.set_enabled(False)
        reg.reset()


def _paged_batch(b=4, h=4, d=64, page=128, pool=14, max_pages=3,
                 seed=21):
    """Random paged-decode inputs: global KV pool + a page table whose
    rows map distinct non-null pages (the allocator never maps page 0
    under a live position)."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(pool, h, d, page)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(pool, h, d, page)), jnp.float32)
    ids = rng.permutation(np.arange(1, pool))[:b * max_pages]
    pt = jnp.asarray(ids.reshape(b, max_pages), jnp.int32)
    return q, k, v, pt


def test_flash_decode_paged_matches_xla_gather():
    """flash_decode_paged (scalar-prefetch page-table walk) == the XLA
    oracle run on the gathered contiguous view — including rows whose
    live length stops mid-page, and rows sharing a physical page."""
    from paddlefleetx_tpu.ops.attention import _gather_kv_pages
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_decode_paged,
    )
    q, k, v, pt = _paged_batch()
    # row 3 shares row 0's first page (the COW/prefix-sharing shape)
    pt = pt.at[3, 0].set(pt[0, 0])
    offs = jnp.asarray([0, 130, 255, 383], jnp.int32)
    kg, vg = _gather_kv_pages(k, pt), _gather_kv_pages(v, pt)
    ref = _xla_attention(q, kg, vg, None, True, offs, 0.0, None, True,
                         True, kv_cache_layout=True)
    got = flash_decode_paged(q, k, v, offs, pt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # pages past each row's length are never read: poison every pool
    # page the rows' live prefixes don't reach
    live = np.zeros(k.shape[0], bool)
    for i, off in enumerate(np.asarray(offs)):
        for j in range(int(off) // 128 + 1):
            live[int(pt[i, j])] = True
    poison = jnp.asarray(~live)[:, None, None, None]
    got2 = flash_decode_paged(q, jnp.where(poison, 1e3, k),
                              jnp.where(poison, -1e3, v), offs, pt)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(got),
                               atol=2e-6, rtol=2e-6)


def test_flash_decode_paged_identity_table_matches_ragged():
    """A pool laid out contiguously with an identity page table is the
    SAME logical cache as the PR-5 contiguous layout, so the paged
    kernel must reproduce flash_decode_ragged bit-for-tolerance."""
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_decode_paged, flash_decode_ragged,
    )
    b, S, page = 4, 256, 128
    m = S // page
    q, k, v = _decode_batch(b=b, S=S, seed=22)
    offs = jnp.asarray([0, 5, 130, 255], jnp.int32)
    # pool[1 + bi*m + j] holds row bi's logical page j
    def to_pool(t):
        t = np.asarray(t)                      # [b, h, d, S]
        pages = t.reshape(*t.shape[:3], m, page)
        pool = np.zeros((1 + b * m, t.shape[1], t.shape[2], page),
                        t.dtype)
        pool[1:] = pages.transpose(0, 3, 1, 2, 4).reshape(
            b * m, t.shape[1], t.shape[2], page)
        return jnp.asarray(pool)
    pt = jnp.asarray(
        1 + np.arange(b * m).reshape(b, m), jnp.int32)
    got = flash_decode_paged(q, to_pool(k), to_pool(v), offs, pt)
    ref = flash_decode_ragged(q, k, v, offs, block_kv=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


# -- the paged kernel's walk over live (slot, block) pairs --------------
#
# Each case is (slots, {live row: offset}, pages some rows share). A row
# not named is dead: its page-table row is all NULL_PAGE, as
# ``GenerationServer._sync_pt`` leaves every slot that is not decoding.

_WALK_PAGES, _WALK_PAGE = 3, 128
_WALK_CASES = {
    "none_live": (4, {}, {}),
    "last_row_only": (4, {3: 200}, {}),
    "all_live_at_capacity": (4, {i: _WALK_PAGES * _WALK_PAGE - 1
                                 for i in range(4)}, {}),
    # on a page's last position, on the next page's first
    "page_edges": (4, {0: 127, 1: 128, 2: 255, 3: 256}, {}),
    # (row, logical page) -> the (row, logical page) whose physical
    # page it maps too: prefix sharing / COW before the split
    "shared_pages": (4, {0: 300, 1: 140, 2: 130, 3: 5},
                     {(2, 0): (0, 0), (1, 1): (0, 1)}),
    "nulled_between": (6, {0: 10, 2: 257, 5: 129}, {}),
    "over_128_slots": (131, {0: 3, 5: 260, 127: 128, 128: 127,
                             130: 381}, {}),
    # row 128 opens the second lane group and is dead: its one step
    # zeroes that group's output block and computes nothing
    "lane_group_head_dead": (131, {3: 130, 129: 255}, {}),
}


def _walk_inputs(case, window, int8, seed=41):
    b, offs_of, shared = _WALK_CASES[case]
    h, d, page, mp = 2, 64, _WALK_PAGE, _WALK_PAGES
    # a verify window's last query still has to fit the table
    offs_of = {i: min(o, mp * page - window)
               for i, o in offs_of.items()}
    rng = np.random.default_rng(seed)
    pool = 1 + mp * max(len(offs_of), 1)
    pt = np.zeros((b, mp), np.int32)           # NULL_PAGE everywhere
    ids = iter(rng.permutation(np.arange(1, pool)))
    for i in offs_of:
        pt[i] = [next(ids) for _ in range(mp)]
    for (i, j), (i2, j2) in shared.items():
        pt[i, j] = pt[i2, j2]
    offs = rng.integers(0, mp * page, size=b).astype(np.int32)
    for i, o in offs_of.items():
        offs[i] = o
    q = jnp.asarray(rng.normal(size=(b, window, h, d)), jnp.float32)
    if int8:
        k = jnp.asarray(rng.integers(-127, 128, (pool, h, d, page)),
                        jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, (pool, h, d, page)),
                        jnp.int8)
        ks = jnp.asarray(rng.uniform(0.002, 0.02, (pool, h, 1, page)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.002, 0.02, (pool, h, 1, page)),
                         jnp.float32)
    else:
        k = jnp.asarray(rng.normal(size=(pool, h, d, page)),
                        jnp.float32)
        v = jnp.asarray(rng.normal(size=(pool, h, d, page)),
                        jnp.float32)
        ks = vs = None
    return q, k, v, ks, vs, jnp.asarray(offs), jnp.asarray(pt), \
        sorted(offs_of)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16kv", "int8kv"])
@pytest.mark.parametrize("window", [1, 2, 5])
@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_flash_decode_paged_walks_live_pairs_only(case, window, int8):
    """The grid over live (slot, block) pairs: every live row reads
    what the XLA oracle reads on the gathered view, every dead row
    reads zeros, the walk holds exactly the live pairs (and one
    zeroing step for a dead head of a lane group), and nothing outside
    the live pairs is ever read — poison in every page past a live
    length and in NULL_PAGE changes no output."""
    from paddlefleetx_tpu.ops.attention import _gather_kv_pages
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    q, k, v, ks, vs, offs, pt, live = _walk_inputs(case, window, int8)
    b, page, mp = q.shape[0], _WALK_PAGE, _WALK_PAGES
    dead = np.setdiff1d(np.arange(b), live)

    def run(k, v, ks, vs):
        return np.asarray(fa.flash_decode_paged(
            q, k, v, offs, pt, k_scale=ks, v_scale=vs))
    got = run(k, v, ks, vs)
    assert np.isfinite(got).all()
    assert not got[dead].any()                  # zeros, written
    if live:
        kf, vf = (k, v) if not int8 else (
            k.astype(jnp.float32) * ks, v.astype(jnp.float32) * vs)
        rows = jnp.asarray(live)
        ref = _xla_attention(
            q[rows], _gather_kv_pages(kf, pt[rows]),
            _gather_kv_pages(vf, pt[rows]), None, True, offs[rows],
            0.0, None, True, True, kv_cache_layout=True)
        np.testing.assert_allclose(got[live], np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
    # the walk: a live row's blocks 0 .. (off + W - 1) // page in
    # order, rows ascending; a dead row none, unless it heads a group
    want = []
    for i in range(b):
        n = (int(offs[i]) + window - 1) // page + 1 if i in live else 0
        want += [(i, kb) for kb in range(max(n, i % fa.LANES == 0))]
    rows_, blocks_, steps = fa._paged_walk(offs, pt, window, page, mp)
    assert int(steps) == len(want)
    assert list(zip(np.asarray(rows_)[:len(want)].tolist(),
                    np.asarray(blocks_)[:len(want)].tolist())) == want
    # poison whatever no live pair reaches, NULL_PAGE included
    reached = np.zeros(k.shape[0], bool)
    for i in live:
        for j in range((int(offs[i]) + window - 1) // page + 1):
            reached[int(pt[i, j])] = True
    assert not reached[fa.NULL_PAGE]
    bad = jnp.asarray(~reached)[:, None, None, None]
    if int8:
        got2 = run(jnp.where(bad, jnp.int8(127), k),
                   jnp.where(bad, jnp.int8(-127), v),
                   jnp.where(bad, 1e3, ks), jnp.where(bad, 1e3, vs))
    else:
        got2 = run(jnp.where(bad, 1e3, k), jnp.where(bad, -1e3, v),
                   None, None)
    np.testing.assert_array_equal(got2, got)


def test_null_page_is_the_allocators():
    """The kernel reads a slot's deadness off the page the allocator
    reserves; the two modules cannot import each other, so the number
    is pinned here."""
    from paddlefleetx_tpu.core import paging
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    assert fa.NULL_PAGE == paging.NULL_PAGE


def test_flash_decode_paged_rejects_bad_shapes():
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_decode_paged,
    )
    q, k, v, pt = _paged_batch(b=2, pool=5, max_pages=2, seed=23)
    offs = jnp.zeros((2,), jnp.int32)
    with pytest.raises(NotImplementedError):  # bias unsupported
        flash_decode_paged(q, k, v, offs, pt,
                           bias=jnp.zeros((2, 1, 1, 256)))
    with pytest.raises(NotImplementedError):  # bias + verify window
        flash_decode_paged(jnp.concatenate([q, q], 1), k, v, offs, pt,
                           bias=jnp.zeros((2, 1, 1, 256)))
    with pytest.raises(NotImplementedError):  # empty window
        flash_decode_paged(q[:, :0], k, v, offs, pt)
    with pytest.raises(NotImplementedError):  # offsets batch mismatch
        flash_decode_paged(q, k, v, jnp.zeros((3,), jnp.int32), pt)
    with pytest.raises(NotImplementedError):  # page_table not [b, m]
        flash_decode_paged(q, k, v, offs, pt[0])
    with pytest.raises(NotImplementedError):  # pool heads do not divide
        flash_decode_paged(q, k[:, :3], v[:, :3], offs, pt)
    with pytest.raises(NotImplementedError):  # page not 128-tileable
        flash_decode_paged(q, k[..., :64], v[..., :64], offs, pt)
    with pytest.raises(NotImplementedError):  # the walk outgrows SMEM
        flash_decode_paged(jnp.zeros((1024, 1) + q.shape[2:]), k, v,
                           jnp.zeros((1024,), jnp.int32),
                           jnp.zeros((1024, 64), jnp.int32))


def test_paged_decode_dispatch_and_counter():
    """dot_product_attention routes (ragged offsets + page_table) to
    the paged kernel (counter `attention/flash_decode_paged`) and the
    kernel-rejected shapes to the dense gather fallback with identical
    per-row masking — the docs/inference.md paged dispatch row."""
    from paddlefleetx_tpu.observability import metrics
    from paddlefleetx_tpu.ops.attention import (
        _gather_kv_pages, dot_product_attention,
    )
    q, k, v, pt = _paged_batch(b=2, pool=7, max_pages=2, seed=24)
    offs = jnp.asarray([17, 200], jnp.int32)
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    reg.reset()
    try:
        out = dot_product_attention(q, k, v, causal=True,
                                    query_offset=offs, use_flash=True,
                                    kv_cache_layout=True,
                                    page_table=pt)
        assert reg.counter("attention/flash_decode_paged") == 1
        assert reg.counter("attention/dense") == 0
        kg, vg = _gather_kv_pages(k, pt), _gather_kv_pages(v, pt)
        ref = _xla_attention(q, kg, vg, None, True, offs, 0.0, None,
                             True, True, kv_cache_layout=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)
        # head_dim the kernel rejects -> gather + dense, same masking
        reg.reset()
        q2, k2, v2 = q[..., :44], k[:, :, :44], v[:, :, :44]
        out2 = dot_product_attention(q2, k2, v2, causal=True,
                                     query_offset=offs, use_flash=True,
                                     kv_cache_layout=True,
                                     page_table=pt)
        assert reg.counter("attention/fallback/kernel_rejected") == 1
        assert reg.counter("attention/dense") == 1
        kg2, vg2 = _gather_kv_pages(k2, pt), _gather_kv_pages(v2, pt)
        ref2 = _xla_attention(q2, kg2, vg2, None, True, offs, 0.0,
                              None, True, True, kv_cache_layout=True)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                                   atol=2e-6, rtol=2e-6)
    finally:
        metrics.set_enabled(False)
        reg.reset()


def test_flash_decode_ragged_verify_window_matches_xla():
    """The speculative k-token VERIFY window: sq > 1 ragged decode ==
    the XLA per-row-offset oracle (query j of row i sees keys <=
    offs[i] + j — the within-window causal mask), garbage past each
    row's window never leaks, and a window of 1 degenerates to the
    single-token kernel exactly."""
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_decode_ragged,
    )
    rng = np.random.default_rng(31)
    b, S, h, d, W = 4, 256, 2, 64, 4
    q = jnp.asarray(rng.normal(size=(b, W, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    # rows whose windows start at 0, mid-block, a block edge, and the
    # last admissible start (offs + W - 1 == S - 1)
    offs = jnp.asarray([0, 5, 127, S - W], jnp.int32)
    ref = _xla_attention(q, k, v, None, True, offs, 0.0, None, True,
                         True, kv_cache_layout=True)
    got = flash_decode_ragged(q, k, v, offs, block_kv=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # nothing past each row's LAST window position is ever read
    mask = np.arange(S)[None, :] > (np.asarray(offs)[:, None] + W - 1)
    k2 = jnp.where(jnp.asarray(mask)[:, None, None, :], 1e3, k)
    v2 = jnp.where(jnp.asarray(mask)[:, None, None, :], -1e3, v)
    got2 = flash_decode_ragged(q, k2, v2, offs, block_kv=128)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(got),
                               atol=2e-6, rtol=2e-6)
    # W = 1 is the original single-token kernel, column for column
    np.testing.assert_allclose(
        np.asarray(flash_decode_ragged(q[:, :1], k, v, offs,
                                       block_kv=128)),
        np.asarray(got[:, :1]), atol=2e-6, rtol=2e-6)


def test_flash_decode_paged_verify_window_matches_xla():
    """The verify window over the PAGED pool: same within-window
    causal mask through the page-table walk, validated against the
    XLA oracle on the gathered contiguous view — including a window
    that CROSSES a page boundary mid-run."""
    from paddlefleetx_tpu.ops.attention import _gather_kv_pages
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_decode_paged,
    )
    rng = np.random.default_rng(32)
    b, h, d, page, pool, mp, W = 4, 4, 64, 128, 14, 3, 4
    q = jnp.asarray(rng.normal(size=(b, W, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(pool, h, d, page)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(pool, h, d, page)), jnp.float32)
    ids = rng.permutation(np.arange(1, pool))[:b * mp]
    pt = jnp.asarray(ids.reshape(b, mp), jnp.int32)
    # row 1's window spans the page-0/page-1 boundary (126..129); row
    # 3 ends exactly at the table's last position
    offs = jnp.asarray([0, 126, 200, mp * page - W], jnp.int32)
    kg, vg = _gather_kv_pages(k, pt), _gather_kv_pages(v, pt)
    ref = _xla_attention(q, kg, vg, None, True, offs, 0.0, None, True,
                         True, kv_cache_layout=True)
    got = flash_decode_paged(q, k, v, offs, pt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # pages no row's window reaches are never read
    live = np.zeros(pool, bool)
    for i, off in enumerate(np.asarray(offs)):
        for j in range((int(off) + W - 1) // page + 1):
            live[int(pt[i, j])] = True
    poison = jnp.asarray(~live)[:, None, None, None]
    got2 = flash_decode_paged(q, jnp.where(poison, 1e3, k),
                              jnp.where(poison, -1e3, v), offs, pt)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(got),
                               atol=2e-6, rtol=2e-6)


def test_verify_window_dispatch_and_counters():
    """dot_product_attention routes a short multi-token window with
    per-row offsets to the verify kernels (`attention/*_verify`
    counters), and a window past MAX_VERIFY_WINDOW — chunked
    prefill's shape — to the dense path, never the kernel."""
    from paddlefleetx_tpu.observability import metrics
    from paddlefleetx_tpu.ops.attention import (
        MAX_VERIFY_WINDOW, _gather_kv_pages, dot_product_attention,
    )
    rng = np.random.default_rng(33)
    b, S, h, d, W = 2, 256, 2, 64, 3
    q = jnp.asarray(rng.normal(size=(b, W, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, d, S)), jnp.float32)
    offs = jnp.asarray([17, 200], jnp.int32)
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    reg.reset()
    try:
        out = dot_product_attention(q, k, v, causal=True,
                                    query_offset=offs, use_flash=True,
                                    kv_cache_layout=True)
        assert reg.counter("attention/flash_decode_ragged_verify") == 1
        assert reg.counter("attention/dense") == 0
        ref = _xla_attention(q, k, v, None, True, offs, 0.0, None,
                             True, True, kv_cache_layout=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)
        # paged edition
        reg.reset()
        qp, kp, vp, pt = _paged_batch(b=2, pool=7, max_pages=2,
                                      seed=34)
        qp = jnp.concatenate([qp] * W, axis=1)
        outp = dot_product_attention(qp, kp, vp, causal=True,
                                     query_offset=offs,
                                     use_flash=True,
                                     kv_cache_layout=True,
                                     page_table=pt)
        assert reg.counter("attention/flash_decode_paged_verify") == 1
        assert reg.counter("attention/dense") == 0
        kg, vg = _gather_kv_pages(kp, pt), _gather_kv_pages(vp, pt)
        refp = _xla_attention(qp, kg, vg, None, True, offs, 0.0, None,
                              True, True, kv_cache_layout=True)
        np.testing.assert_allclose(np.asarray(outp), np.asarray(refp),
                                   atol=2e-6, rtol=2e-6)
        # a chunked-prefill-sized window stays OFF the verify kernel
        reg.reset()
        big = MAX_VERIFY_WINDOW + 1
        qb = jnp.asarray(rng.normal(size=(b, big, h, d)), jnp.float32)
        dot_product_attention(qb, k, v, causal=True,
                              query_offset=jnp.zeros((b,), jnp.int32),
                              use_flash=True, kv_cache_layout=True)
        assert reg.counter("attention/flash_decode_ragged_verify") == 0
        assert reg.counter("attention/dense") == 1
    finally:
        metrics.set_enabled(False)
        reg.reset()


def test_kernel_dropout_gate_and_fallback(monkeypatch):
    """The in-kernel dropout dispatch (PFX_FLASH_DROPOUT=1) must fall
    back to the XLA dense path on CPU (prng has no interpret
    lowering), and with the gate off behave exactly as before. The
    on-chip certification lives in scripts/validate_flash_dropout.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.ops.attention import dot_product_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 64, 2, 64)),
                           jnp.float32) for _ in range(3))
    key = jax.random.key(0)
    kw = dict(causal=True, dropout_rate=0.2, dropout_rng=key,
              deterministic=False, use_flash=True)
    monkeypatch.delenv("PFX_FLASH_DROPOUT", raising=False)
    off = dot_product_attention(q, k, v, **kw)
    monkeypatch.setenv("PFX_FLASH_DROPOUT", "1")
    on = dot_product_attention(q, k, v, **kw)
    # same platform, same rng -> the CPU fallback path is identical
    np.testing.assert_allclose(np.asarray(off), np.asarray(on),
                               rtol=1e-6)
    assert np.isfinite(np.asarray(on)).all()


def test_flash_dropout_requires_rng(monkeypatch):
    """Under interpret mode the backend check passes, so the missing-
    rng check is the one that fires — pin its message (a bare
    NotImplementedError would also come from the CPU-backend check,
    making the assertion vacuous)."""
    import jax.numpy as jnp
    import numpy as np
    import pytest

    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )
    monkeypatch.setenv("PFX_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.float32)
    with pytest.raises(NotImplementedError, match="dropout_rng"):
        flash_attention(q, q, q, causal=True, dropout_rate=0.1)
    # with an rng, interpret mode RUNS the dropout kernel (a stateless
    # hash stands in for the TPU prng): finite output, and really
    # dropping — it must differ from the rate-0 result
    import jax
    out = flash_attention(q, q, q, causal=True, dropout_rate=0.1,
                          dropout_rng=jax.random.key(0))
    base = flash_attention(q, q, q, causal=True)
    assert np.isfinite(np.asarray(out)).all()
    assert not np.allclose(np.asarray(out), np.asarray(base))


def test_flash_dropout_traces_offline():
    """The dropout custom_vjp cannot COMPILE off-TPU (Mosaic-only
    prng), but it must TRACE: jax.eval_shape exercises kernel ref
    counts, scalar-prefetch index-map arity, grid/spec plumbing, and
    the float0 seed cotangent — catching structural regressions
    without a chip. Numerics are certified on-chip by
    scripts/validate_flash_dropout.py."""
    from paddlefleetx_tpu.ops.pallas.flash_attention import (
        _flash_lse_dropout, _to_bh, check_shapes,
    )

    d = 64
    seed = jnp.zeros((1,), jnp.int32)

    def run(s, rate):
        q = jnp.zeros((2, s, 4, d), jnp.float32)
        bq, bkv = check_shapes(s, s, d)

        def loss(q_, k_, v_, s_):
            o, lse = _flash_lse_dropout(
                _to_bh(q_), _to_bh(k_), _to_bh(v_), s_, d ** -0.5,
                True, bq, bkv, rate)
            return jnp.sum(o) + jnp.sum(lse)

        return jax.eval_shape(
            lambda a, b, c, s_: jax.grad(loss, argnums=(0, 1, 2))(
                a, b, c, s_), q, q, q, seed)

    # combined-backward regime (num_q == 1) and split-pair regime
    for s in (512, 2048):
        grads = run(s, 0.2)
        assert all(g.shape == (2, s, 4, d) for g in grads)


def test_kernel_dropout_gate_self_certifying(monkeypatch, tmp_path):
    """The gate is ON iff the chip-cert artifact exists (written by
    scripts/validate_flash_dropout.py on a passing live-chip run)
    AND its device_kind matches the attached TPU — certification is
    per TPU generation, and off-TPU (this CPU test platform) the
    artifact can never enable the kernel. PFX_FLASH_DROPOUT
    overrides in both directions; empty/garbage values fall through
    to the artifact; a truncated/invalid artifact is OFF."""
    import json

    import jax

    from paddlefleetx_tpu.ops import attention

    cert = tmp_path / "dropout_cert.json"
    monkeypatch.setattr(attention, "DROPOUT_CERT_PATH", str(cert))
    monkeypatch.delenv("PFX_FLASH_DROPOUT", raising=False)
    assert not attention._kernel_dropout_enabled()  # no artifact
    cert.write_text("{\"devi")  # truncated write
    assert not attention._kernel_dropout_enabled()
    cert.write_text("{}")  # no device_kind recorded
    assert not attention._kernel_dropout_enabled()
    # kind matches the attached device, but this platform is cpu —
    # still off (the kernel cannot run here at all)
    cert.write_text(json.dumps(
        {"device_kind": jax.devices()[0].device_kind}))
    assert not attention._kernel_dropout_enabled()
    cert.write_text(json.dumps({"device_kind": "TPU v5 lite"}))
    assert not attention._kernel_dropout_enabled()  # platform != tpu
    # env forces both ways regardless of artifact state
    monkeypatch.setenv("PFX_FLASH_DROPOUT", "0")
    assert not attention._kernel_dropout_enabled()
    monkeypatch.setenv("PFX_FLASH_DROPOUT", "1")
    assert attention._kernel_dropout_enabled()
    cert.unlink()
    assert attention._kernel_dropout_enabled()  # env=1 needs no file
    # unrecognized/empty env falls through to the (absent) artifact
    monkeypatch.setenv("PFX_FLASH_DROPOUT", "")
    assert not attention._kernel_dropout_enabled()


def test_kernel_dropout_gate_matches_tpu_device(monkeypatch,
                                                tmp_path):
    """On a TPU whose device_kind matches the artifact the gate is
    on; on a different TPU generation it stays off (simulated — the
    test platform is CPU, so jax.devices is stubbed)."""
    import json

    from paddlefleetx_tpu.ops import attention

    class _Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    cert = tmp_path / "dropout_cert.json"
    monkeypatch.setattr(attention, "DROPOUT_CERT_PATH", str(cert))
    monkeypatch.delenv("PFX_FLASH_DROPOUT", raising=False)
    monkeypatch.setattr(attention.jax, "devices", lambda: [_Dev()])
    cert.write_text(json.dumps({"device_kind": "TPU v5 lite"}))
    assert attention._kernel_dropout_enabled()
    cert.write_text(json.dumps({"device_kind": "TPU v4"}))
    assert not attention._kernel_dropout_enabled()


# -- additive bias on the fused path ------------------------------------

def _bias_of(shape, seed=7):
    rng = np.random.default_rng(seed)
    # mix smooth values with -1e9 padding-style entries so the test
    # covers both relative-position bias and hard masks
    b = rng.normal(size=shape).astype(np.float32)
    b[..., -5:] = -1e9
    return jnp.asarray(b)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bias_shape", [
    (2, 2, 256, 256),   # full per-head bias (GPT attn_mask)
    (2, 1, 1, 256),     # ERNIE padding mask, broadcast over h and sq
    (1, 1, 256, 256),   # shared relative-position bias
])
def test_bias_forward_and_grads_match_xla(causal, bias_shape):
    q, k, v = _rand(b=2, s=256)
    bias = _bias_of(bias_shape)
    ref = _xla_attention(q, k, v, bias, causal, 0, 0.0, None, True,
                         True)
    got = flash_attention(q, k, v, causal=causal, bias=bias,
                          block_q=128, block_kv=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, bias=bias,
                                block_q=128, block_kv=128) ** 2).sum()

    def loss_ref(q, k, v):
        return (_xla_attention(q, k, v, bias, causal, 0, 0.0, None,
                               True, True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def test_bias_with_dropout_matches_dropout_only_at_zero_bias():
    """The bias+dropout path folds the SAME in-kernel keep masks as
    the dropout-only path (the seed fold ignores the bias operand), so
    a zero bias must reproduce dropout-only bit-for-bit — and a real
    bias must still produce finite grads through the combined path."""
    q, k, v = _rand(b=2, s=256, seed=3)
    key = jax.random.key(5)
    kw = dict(causal=True, dropout_rate=0.2, dropout_rng=key,
              block_q=128, block_kv=128)
    plain = flash_attention(q, k, v, **kw)
    zeroed = flash_attention(q, k, v,
                             bias=jnp.zeros((2, 1, 1, 256)), **kw)
    np.testing.assert_array_equal(np.asarray(plain),
                                  np.asarray(zeroed))

    bias = _bias_of((2, 1, 1, 256))

    def loss(q, k, v):
        return (flash_attention(q, k, v, bias=bias, **kw) ** 2).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
    # dropout really fires on the biased path
    nodrop = flash_attention(q, k, v, causal=True, bias=bias,
                             block_q=128, block_kv=128)
    withdrop = flash_attention(q, k, v, bias=bias, **kw)
    assert not np.allclose(np.asarray(nodrop), np.asarray(withdrop))


def test_unsupported_bias_shape_falls_back():
    """Shapes the kernel cannot tile (non-4D, partial broadcast on the
    key axis) raise NotImplementedError from the kernel wrapper, and
    dot_product_attention silently lands on the XLA path with correct
    numerics."""
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    q, k, v = _rand(b=2, s=256)
    for bad in (jnp.zeros((2, 256, 256)),        # 3D
                jnp.zeros((2, 2, 256, 1))):      # broadcast key axis
        with pytest.raises(NotImplementedError, match="bias"):
            flash_attention(q, k, v, bias=bad)
    bias3 = jnp.zeros((2, 256, 256))
    out = dot_product_attention(q, k, v, bias=bias3, causal=True,
                                use_flash=True)
    ref = _xla_attention(q, k, v, bias3, True, 0, 0.0, None, True,
                         True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_training_bias_dropout_dispatches_to_kernel(monkeypatch):
    """ISSUE acceptance probe: with a non-None bias AND
    dropout_rate > 0 (the ERNIE/GPT masked-training shape),
    dot_product_attention(use_flash=True) must dispatch to the Pallas
    kernel, not the dense fallback."""
    from paddlefleetx_tpu.ops import attention
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setenv("PFX_FLASH_DROPOUT", "1")
    calls = []
    real = fa.flash_attention

    def probe(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(fa, "flash_attention", probe)
    q, k, v = _rand(b=2, s=256)
    bias = _bias_of((2, 1, 1, 256))
    out = attention.dot_product_attention(
        q, k, v, bias=bias, causal=True, dropout_rate=0.1,
        dropout_rng=jax.random.key(0), deterministic=False,
        use_flash=True)
    assert calls, "dispatch skipped the Pallas kernel"
    assert calls[-1]["bias"] is bias
    assert calls[-1]["dropout_rate"] == 0.1
    assert np.isfinite(np.asarray(out)).all()
    # deterministic (eval) with bias also stays on the kernel,
    # causal or not
    calls.clear()
    attention.dot_product_attention(q, k, v, bias=bias, causal=True,
                                    use_flash=True)
    assert calls and calls[-1]["bias"] is bias


# -- under a multi-device mesh ----------------------------------------
#
# Mosaic kernels cannot be partitioned by GSPMD: on real chips a bare
# pallas_call under a sharded jit fails at lowering (pinned by the
# described-chip compile in tests/test_chip_compile.py). Interpret
# mode would partition fine and hide it, so these tests pin the
# DISPATCH: with the mesh active the training kernel runs per device
# under shard_map, and the kernels that are not wrapped take their
# counted XLA path.

def _mesh_2x2():
    import flax.linen as nn
    from paddlefleetx_tpu.parallel.mesh import (
        TopologyConfig, build_mesh, set_mesh,
    )
    from paddlefleetx_tpu.parallel.sharding import make_sharding_rules
    topo = TopologyConfig(mp_degree=2, sharding_degree=2,
                          sharding_stage=3)
    mesh = build_mesh(topo, devices=jax.devices()[:4])
    set_mesh(mesh)
    return mesh, nn.logical_axis_rules(list(make_sharding_rules(topo)))


@pytest.mark.parametrize("bias", [False, True],
                         ids=["plain", "bias"])
def test_flash_runs_per_device_under_a_mesh(bias):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddlefleetx_tpu.observability import metrics
    from paddlefleetx_tpu.ops import ring_attention
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    q, k, v = _rand(b=4, s=256, h=4)
    mask = None
    if bias:
        keep = np.ones((4, 1, 1, 256), np.float32)
        keep[:, ..., 200:] = 0.0
        mask = jnp.asarray((keep - 1.0) * 1e9)

    def loss(q, k, v, flash):
        out = dot_product_attention(q, k, v, bias=mask, causal=True,
                                    use_flash=flash)
        return (out ** 2).sum()
    ref_l, ref_g = jax.value_and_grad(
        lambda *a: loss(*a, False), argnums=(0, 1, 2))(q, k, v)

    mesh, rules = _mesh_2x2()
    sharded = []
    real = ring_attention._shard_map

    def spy(fn, **kw):
        sharded.append(kw["in_specs"])
        return real(fn, **kw)
    ring_attention._shard_map = spy
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    reg.reset()
    try:
        sh = NamedSharding(mesh, P(("dp", "fsdp"), None, "mp", None))
        args = [jax.device_put(t, sh) for t in (q, k, v)]
        with mesh, rules:
            got_l, got_g = jax.jit(jax.value_and_grad(
                lambda *a: loss(*a, True), argnums=(0, 1, 2)))(*args)
        assert reg.counter("attention/flash") == 1
        assert reg.counter("attention/dense") == 0
    finally:
        ring_attention._shard_map = real
        metrics.set_enabled(False)
    # batch over the data axes, heads over mp — q's own sharding
    assert sharded and sharded[0][0] == P(("dp", "fsdp"), None, "mp",
                                          None)
    np.testing.assert_allclose(float(got_l), float(ref_l), rtol=1e-5)
    for a, b in zip(got_g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-4)


def test_mesh_indivisible_and_decode_take_the_counted_dense_path():
    """The batch-1 abstract-init sample does not divide fsdp=2, and
    the decode kernels are not shard_map-wrapped: both go dense under
    ``attention/fallback/mesh_sharded`` — never to a lowering crash,
    never counted as a kernel rejection."""
    from paddlefleetx_tpu.observability import metrics
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    mesh, rules = _mesh_2x2()
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    reg.reset()
    try:
        q, k, v = _rand(b=1, s=256, h=4)
        with mesh, rules:
            out = dot_product_attention(q, k, v, use_flash=True)
            assert out.shape == q.shape
            cache = jnp.zeros((4, 4, 64, 256), jnp.float32)
            dot_product_attention(
                _rand(b=4, s=1, h=4)[0], cache, cache,
                query_offset=jnp.array([5, 9, 0, 3], jnp.int32),
                use_flash=True, kv_cache_layout=True)
        assert reg.counter("attention/fallback/mesh_sharded") == 2
        assert reg.counter("attention/dense") == 2
        assert reg.counter("attention/fallback/kernel_rejected") == 0
        assert reg.counter("attention/flash_decode_ragged") == 0
    finally:
        metrics.set_enabled(False)


# -- the causal staircase ------------------------------------------------
#
# A diagonal-crossing causal block is walked as a staircase of
# CAUSAL_SUBTILE sub-tiles (flash_attention.py::_staircase): every case
# holds forward, log-sum-exp and dq / dk / dv to the dense XLA path.

def _dense_lse(q, k, sm_scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    keep = jnp.tril(jnp.ones(s.shape[-2:], bool))
    return jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1)


def _dense_dropout_oracle(q, k, v, seed, rate, block):
    """Causal attention with the keep mask the interpret-mode kernels
    draw: block (b, qi, ki) of head-row ``b`` is
    ``_interpret_random_bits(seed, (b * n + qi) * n + ki)``, a lane
    kept iff its bits fall under the threshold."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    b, s, h, d = q.shape
    n = s // block
    keep = jnp.concatenate([jnp.concatenate([
        jnp.stack([fa._interpret_random_bits(
            seed[0], (bh * n + qi) * n + ki, block, block)
            for bh in range(b * h)]) for ki in range(n)], axis=2)
        for qi in range(n)], axis=1) < fa._dropout_threshold(rate)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(keep.reshape(b, h, s, s), p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


_STAIR_CASES = {
    # s, heads, d, d_v, dtype, kwargs, tile share of forward + backward
    # one 1024 block a head: whole forward, staircase in the one-pass
    # backward (_bwd_combined_kernel)
    "s1024_one_block": (1024, 2, 64, 64, jnp.float32, {}, 26 / 32),
    # auto blocks at s=2048: diagonal and interior forward blocks, the
    # forward's staircase, _bwd_fused_kernel at its 512 blocks
    "s2048_auto_blocks": (2048, 1, 64, 64, jnp.float32, {},
                          (36 + 36) / (48 + 40)),
    # q/k of 192 over v of 128 (latent attention)
    "s1024_mla": (1024, 1, 192, 128, jnp.float32, {}, 26 / 32),
    "s1024_bf16": (1024, 2, 64, 64, jnp.bfloat16, {}, 26 / 32),
    # unequal blocks keep the masked whole block
    "unequal_blocks": (1024, 1, 64, 64, jnp.float32,
                       dict(block_q=1024, block_kv=512), 1.0),
    # a bias sends several q blocks to the split pair (_bwd_dkv_kernel,
    # _bwd_dq_kernel): staircase in the forward and in both
    "s2048_bias_split_pair": (2048, 1, 64, 64, jnp.float32,
                              dict(bias=True), 36 / 48),
}


@pytest.mark.parametrize("case", list(_STAIR_CASES) + ["s2048_dropout"])
def test_causal_staircase_matches_dense(case, monkeypatch):
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    masks = []
    diagonal_mask = fa._diagonal_mask
    monkeypatch.setattr(fa, "_diagonal_mask", lambda *a: (
        masks.append(a), diagonal_mask(*a))[1])
    rng = np.random.default_rng(23)
    if case == "s2048_dropout":
        # forward and backward (the split pair) slice ONE mask a block:
        # the gradients of a fixed seed are the dense oracle's
        s, rate = 2048, 0.25
        q, k, v = (jnp.asarray(rng.normal(size=(1, s, 1, 64)),
                               jnp.float32) for _ in range(3))
        key = jax.random.key(5)
        seed = jax.random.randint(key, (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)

        def flash(q, k, v):
            return fa.flash_attention(q, k, v, dropout_rate=rate,
                                      dropout_rng=key)

        def dense(q, k, v):
            return _dense_dropout_oracle(q, k, v, seed, rate, 1024)
        atol, gtol = 2e-5, 1e-3
    else:
        s, h, d, d_v, dtype, kw, share = _STAIR_CASES[case]
        kw = dict(kw)
        q, k, v = (jnp.asarray(rng.normal(size=(1, s, h, w)), dtype)
                   for w in (d, d, d_v))
        bias = None
        if kw.pop("bias", False):
            valid = np.ones((1, 1, 1, s), bool)
            valid[..., 700:900] = False
            bias = jnp.where(jnp.asarray(valid), 0.0, -1e9)
            kw["bias"] = bias
        blocks = fa.check_shapes(s, s, d, kw.get("block_q"),
                                 kw.get("block_kv"), d_v=d_v)
        executed, whole = fa.causal_step_elements(
            s, s, d, d_v, q.dtype.itemsize, *blocks, plain=bias is None)
        assert executed / whole == pytest.approx(share)

        def flash(q, k, v):
            return fa.flash_attention(q, k, v, **kw)

        def dense(q, k, v):
            return _xla_attention(q, k, v, bias, True, 0, 0.0, None,
                                  True, True, sm_scale=d ** -0.5)
        # bf16: the tolerances of
        # test_bf16_training_dtype_matches_xla_within_tolerance
        atol, gtol = (5e-2, 0.5) if dtype == jnp.bfloat16 \
            else (2e-5, 1e-3)
        if d == d_v and bias is None:
            _, lse = fa.flash_attention_with_lse(
                q, k, v, **{n: kw[n] for n in kw})
            np.testing.assert_allclose(
                np.asarray(lse), np.asarray(_dense_lse(q, k, d ** -0.5)),
                atol=5e-2 if dtype == jnp.bfloat16 else 2e-5,
                rtol=1e-2 if dtype == jnp.bfloat16 else 2e-5)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                ** 2).sum()
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(dense(q, k, v), np.float32), atol=atol, rtol=atol)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=gtol, rtol=0.1 if gtol > 0.1 else gtol)
    # the staircase's sub-tile mask was built iff the case engages it
    assert bool(masks) == (case != "unequal_blocks")


def test_causal_tile_counts():
    """The pure helper the kernels' loops and the counters share."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    tile = fa.CAUSAL_SUBTILE ** 2
    steps = fa._staircase(1024, 1024)
    assert steps == ((0, 256), (256, 512), (512, 768), (768, 1024))
    assert fa.causal_score_elements(1024, 1024, 1024, 1024, steps) \
        == (10 * tile, 16 * tile)
    # s=4096 in 1024 blocks: 6 interior blocks whole, 4 diagonal ones
    # as staircases
    assert fa.causal_score_elements(
        4096, 4096, 1024, 1024, fa._forward_staircase(1024, 1024, 4)) \
        == (136 * tile, 160 * tile)
    # a head that is one block keeps the whole block in the forward
    assert fa._forward_staircase(1024, 1024, 1) == ()
    # where it cannot be static it does not engage: unequal blocks, a
    # query offset, a block that is one sub-tile, no causal mask
    assert fa._staircase(1024, 512) == fa._staircase(256, 256) == ()
    assert fa._staircase(1024, 1024, True, 128) == ()
    assert fa._staircase(1024, 1024, False) == ()
    executed, whole = fa.causal_score_elements(
        1024, 1024, 1024, 512, fa._staircase(1024, 512))
    assert executed == whole == 1024 * 1024
    # forward + backward of a step: one block a head, only the one-pass
    # backward engages; s=4096 at 192/128, the fused backward's 512
    assert fa.causal_step_elements(1024, 1024, 64, 64, 2, 1024, 1024) \
        == (26 * tile, 32 * tile)
    assert fa.causal_step_elements(4096, 4096, 192, 128, 2, 1024, 1024) \
        == ((136 + 136) * tile, (160 + 144) * tile)
    # in-kernel dropout or a bias keeps several q blocks on the split
    # pair, at the forward's blocks
    assert fa.causal_step_elements(4096, 4096, 192, 128, 2, 1024, 1024,
                                   plain=False) \
        == (2 * 136 * tile, 2 * 160 * tile)


def test_causal_staircase_counters():
    from paddlefleetx_tpu.observability import metrics
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    reg.reset()
    try:
        q, k, v = _rand(s=1024, h=1)
        jax.eval_shape(lambda *a: dot_product_attention(
            *a, causal=True, use_flash=True), q, k, v)
        assert reg.counter("attention/flash") == 1
        assert reg.counter("attention/flash_causal_staircase") == 1
        assert reg.counter("attention/flash_causal_whole_block") == 0
        assert reg.gauge("attention/flash_causal_tile_share") == 26 / 32
        # a non-causal call has no diagonal: it counts neither
        q, k, v = _rand(s=2048, h=1)
        jax.eval_shape(lambda *a: dot_product_attention(
            *a, causal=False, use_flash=True), q, k, v)
        assert reg.counter("attention/flash") == 2
        assert reg.counter("attention/flash_causal_staircase") == 1
        assert reg.counter("attention/flash_causal_whole_block") == 0
        # a block of one sub-tile is computed whole
        q, k, v = _rand(s=256, h=1)
        jax.eval_shape(lambda *a: dot_product_attention(
            *a, causal=True, use_flash=True), q, k, v)
        assert reg.counter("attention/flash_causal_whole_block") == 1
        assert reg.gauge("attention/flash_causal_tile_share") == 1.0
    finally:
        metrics.set_enabled(False)
        reg.reset()
