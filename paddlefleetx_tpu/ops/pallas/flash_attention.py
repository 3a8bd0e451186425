"""Causal flash attention for TPU, written in Pallas.

Replaces the reference's fused CUDA causal softmax
(``incubate.softmax_mask_fuse_upper_triangle``, reference
``single_model.py:198`` / ``hybrid_model.py:277``) — and goes further:
the reference still materializes the full ``[b, h, s, s]`` score
matrix (SURVEY.md §5.7); this kernel never does. FlashAttention-2
style: online softmax over KV blocks with running max / sum / output
accumulator held in VMEM scratch, fp32 accumulation, bf16 block
matmuls on the MXU. Forward saves the per-row logsumexp; backward is
two more Pallas kernels (dKV over the KV-block grid, dQ over the
Q-block grid) wired through ``jax.custom_vjp``.

Layout: ``[b, s, h, d]`` at the API, ``[b*h, s, d]`` internally; the
TPU grid is ``(bh, outer_block, inner_block)`` — the innermost axis
runs sequentially on-core, so VMEM scratch persists across the inner
loop.

Measured forward throughput on one v5e (b=2, h=16, d=64, causal, r2):
``s=2048`` 6 TF/s (1.3x the dense XLA path), ``s=4096`` 16 TF/s
(4.1x dense), ``s=8192`` 23 TF/s (dense materializes [b,h,s,s] and
stops being viable). Utilization grows with s because the fraction of
fully-live interior blocks (which skip mask arithmetic) grows and the
per-program overhead amortizes. A block that crosses the causal
diagonal is walked as a staircase of ``CAUSAL_SUBTILE`` sub-tiles
where that is static (:func:`_staircase`), so the masked half of it
is mostly not computed: at s=1024, one block a head, the backward
executes 10 of 16 tiles (PR 37: 0.947 -> 0.652 ms for 128 heads of 64
on one v5e), while the one-block forward, whose time follows its rows
and not the keys they score, keeps the whole block
(:func:`_forward_staircase`).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret() -> bool:
    """Interpreter mode lets CPU tests validate kernel semantics
    (``PFX_PALLAS_INTERPRET=1``)."""
    return os.environ.get("PFX_PALLAS_INTERPRET") == "1"


def _bf16_exp() -> bool:
    """Opt-in bf16 exp in the online softmax (perf playbook lever #2):
    halves the VPU transcendental work that bounds the kernel at
    d=64/short-s. Numerics: the exp argument ``s - m_new`` is in
    [-inf, 0] where bf16's 8-bit mantissa costs ~2^-8 relative — the
    fp32 accumulation of l/acc is unchanged. Only enable with
    TPU-validated tolerances (tests/test_flash_attention.py on chip);
    interpret mode cannot certify TPU VPU numerics."""
    return os.environ.get("PFX_FLASH_BF16_EXP") == "1"
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_KV = 1024
#: rows (and keys) of one sub-tile of the staircase a diagonal-crossing
#: causal block is walked as (:func:`_staircase`)
CAUSAL_SUBTILE = 256


def _dropout_threshold(rate: float):
    """uint32 comparison threshold: keep a lane iff its random bits
    fall below ``(1-rate) * 2^32`` (clamped — a tiny nonzero rate must
    keep nearly everything, not wrap to zero)."""
    return jnp.uint32(min(4294967295,
                          int(round((1.0 - rate) * 4294967296.0))))


def _interpret_random_bits(seed, fold, block_q, block_kv):
    """Counter-based uint32 bits for INTERPRET mode only: pltpu's
    per-core PRNG has no CPU lowering, so off-TPU the keep mask comes
    from a stateless murmur3-style finalizer over (seed, block fold,
    lane coordinates). Same regenerability contract as the TPU path —
    a pure function of the same inputs, so forward and backward
    rebuild identical masks — but a DIFFERENT bit pattern: interpret
    runs validate dropout semantics and plumbing, never TPU numerics
    (those are certified on-chip by scripts/validate_flash_dropout.py).
    Module-level so tests can rebuild the exact mask for a dense
    oracle."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_kv), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (block_q, block_kv), 1)
    x = (jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
         * jnp.uint32(0x9E3779B1)
         + jnp.asarray(fold, jnp.int32).astype(jnp.uint32)
         * jnp.uint32(0x85EBCA77)
         + r * jnp.uint32(0xC2B2AE3D) + c * jnp.uint32(0x27D4EB2F))
    x = (x ^ (x >> 15)) * jnp.uint32(0x2C1B3C6D)
    x = (x ^ (x >> 12)) * jnp.uint32(0x297A2D39)
    return x ^ (x >> 15)


def _block_random_bits(seed_ref, b, qi, ki, n_q, n_kv, block_q,
                       block_kv):
    """Regenerable [block_q, block_kv] uint32 bits for score block
    (b, qi, ki), a lane kept iff its bits fall below
    :func:`_dropout_threshold`: the per-core PRNG is reseeded from (run
    seed, block coordinates) so forward and every backward kernel
    reproduce the SAME mask for the same block regardless of their grid
    iteration order (the backward grids iterate (ki, qi)). The bits are
    always drawn for the WHOLE block; a causal staircase compares its
    sub-tile's static slice of them, so both directions still see one
    mask a block.

    The coordinates are folded mixed-radix into ONE value — Mosaic's
    ``prng_set_seed_32`` rejects more than two seed operands on v5e
    libtpu ("Setting seed with more than 2 values is not supported",
    r5 chip cert) — using the STATIC block counts (n_q, n_kv) shared
    by the forward and backward pallas_calls, so the fold is injective
    and kernel-order independent. Callers guard the fold against i32
    overflow. Interpret mode substitutes the stateless hash above for
    the (TPU-only) hardware PRNG."""
    fold = (b * n_q + qi) * n_kv + ki
    if _interpret():
        return _interpret_random_bits(seed_ref[0], fold, block_q,
                                      block_kv)
    pltpu.prng_seed(seed_ref[0], fold)
    return pltpu.bitcast(pltpu.prng_random_bits((block_q, block_kv)),
                         jnp.uint32)


def _dropped(x, keep, rate):
    """``x`` with its dropped lanes zeroed and the kept ones rescaled
    by ``1 / keep_prob``."""
    return jnp.where(keep, x * (1.0 / (1.0 - rate)), jnp.zeros_like(x))


def _auto_block(s: int, target: int, align: int) -> int:
    """Largest power-of-two-shrunk block <= target that divides s.
    One 1024 block a head at the training shapes keeps the backward on
    its one-pass kernel and the grid at one step a head (a 256 x 256
    grid is 16 steps of ~0.35 us, dead ones included); what causality
    saves inside such a block is the staircase's (:func:`_staircase`),
    not the grid's. Halving keeps odd lengths (1536, 2560, ...) on the
    kernel instead of falling back to the dense path."""
    b = min(target, s)
    while b > align and (s % b or b % align):
        b //= 2
    return b


def _causal_mask(qi, ki, block_q, block_kv, offset):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0) + offset
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    return k_pos <= q_pos


def _diagonal_mask(shape, row0=0):
    """Key ``j`` of row ``i`` is seen iff ``j <= i + row0``: the causal
    mask of a score tile whose first row is ``row0`` rows below its
    corner on the diagonal."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) \
        <= jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0


def _staircase(block_q, block_kv, causal=True, query_offset=0):
    """``((lo, hi), ...)``: the sub-tile bounds, rows and keys alike,
    of a diagonal-crossing causal block walked as a staircase, or
    ``()`` where such a block is computed whole and masked.

    The backward scores keys ``lo:hi`` against rows ``lo:`` only, the
    forward rows ``lo:hi`` against keys ``:hi`` only; the masked upper
    triangle shrinks from half the block to half of each sub-tile on
    the diagonal. It engages where that is exact and static: square
    blocks at no query offset, so a block crosses the diagonal iff
    ``qi == ki``, its corner lies ON the diagonal and no extent
    depends on a program id. Any other masked block (``block_q !=
    block_kv``, a sub-tile that does not divide the block or is the
    block) keeps the whole-block path.

    On one v5e at ``CAUSAL_SUBTILE`` 128 / 256 / 512 against the whole
    block (bf16, device time of the kernel alone, PR 37): the one-block
    backward at s=1024 0.947 -> 0.717 / 0.652 / 0.724 ms (128 heads of
    64) and 0.454 -> 0.339 / 0.309 / 0.344 ms (64 heads of 128)."""
    sub = CAUSAL_SUBTILE
    if not causal or not isinstance(query_offset, int) or query_offset \
            or block_q != block_kv or block_q % sub or block_q == sub:
        return ()
    return tuple((lo, lo + sub) for lo in range(0, block_q, sub))


def _forward_staircase(block_q, block_kv, num_kv, causal=True,
                       query_offset=0):
    """The forward's :func:`_staircase`: where a head's keys come in
    several blocks. The time of a head that is ONE block follows its
    rows, not the keys they score (the per-row softmax bookkeeping on
    ``[bq, 1]`` columns, not the products and not the operands'
    traffic: the same BlockSpecs with no products take 0.134 ms), and
    on the chip every staircase tried there took longer than the masked
    whole block although it skips 6 of 16 tiles (s=1024, one v5e, PR
    37: 0.464 -> 0.572 ms at d=64, 0.210 -> 0.278 ms at d=128; two row
    strips of 512 that skip nothing already take 0.523, and 0.521 with
    the dead quadrant skipped). With the keys in several blocks the
    same walk pays: s=2048 0.868 -> 0.807 ms at d=64, 0.452 -> 0.426
    at d=128; s=4096 at 192/128 8.29 -> 7.66."""
    return _staircase(block_q, block_kv, causal, query_offset) \
        if num_kv > 1 else ()


def causal_score_elements(sq, skv, block_q, block_kv, steps):
    """``(executed, whole)`` score elements of one causal head over
    the ``(sq // block_q, skv // block_kv)`` grid: what a kernel
    computes when it walks its diagonal-crossing blocks by ``steps``
    (:func:`_staircase`; ``()`` computes them whole), and what it
    would with every live block computed whole."""
    executed = whole = 0
    for qi in range(sq // block_q):
        for ki in range(skv // block_kv):
            live, interior = _live_interior(qi, ki, block_q, block_kv,
                                            True, 0)
            if not live:
                continue
            whole += block_q * block_kv
            executed += sum((hi - lo) * hi for lo, hi in steps) \
                if steps and not interior else block_q * block_kv
    return executed, whole


def causal_step_elements(sq, skv, d, d_v, itemsize, block_q, block_kv,
                         plain=True):
    """``(executed, whole)`` score elements of one causal head through
    the forward kernel and the backward it gets (``plain``: neither
    in-kernel dropout nor a bias), staircases counted where they
    engage: what ``attention/flash_causal_tile_share`` reports."""
    fwd = causal_score_elements(
        sq, skv, block_q, block_kv,
        _forward_staircase(block_q, block_kv, skv // block_kv))
    _, bq, bkv = _backward_plan(sq, skv, d, d_v, itemsize, block_q,
                                block_kv, plain)
    bwd = causal_score_elements(sq, skv, bq, bkv, _staircase(bq, bkv))
    return fwd[0] + bwd[0], fwd[1] + bwd[1]


def _dot(a, b, trans_a=False, trans_b=False):
    dims = ((0,) if trans_a else (1,), (1,) if trans_b else (0,))
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# -- forward -----------------------------------------------------------


def _online_update(s, v, m_scr, l_scr, acc_scr, drop_fn=None,
                   rows=slice(None)):
    """One online-softmax accumulator step on ``rows`` of the scratch
    over their masked scores ``s [rows, keys]`` against ``v [keys,
    d_v]`` (the training forward's MXU formulation; the decode kernel
    vectorizes the same recurrence over heads with VPU reduces —
    semantic parity between the two is pinned by
    ``tests/test_flash_attention.py`` decode-vs-XLA cases).

    ``drop_fn`` (in-kernel attention dropout): the normalizer ``l``
    accumulates the FULL ``p`` — dropout multiplies the normalized
    probabilities, and the row division by ``l`` is uniform, so
    ``dropout(softmax(s)) @ v == (sum keep*p/keep_prob @ v) / l`` —
    while only the value-matmul operand is masked+rescaled."""
    m_prev = m_scr[rows]                           # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    if _bf16_exp():
        # bf16 transcendental, fp32 accumulate (lever #2; opt-in)
        p = jnp.exp((s - m_new).astype(jnp.bfloat16))
        l_scr[rows] = l_scr[rows] * alpha + jnp.sum(
            p.astype(jnp.float32), axis=1, keepdims=True)
    else:
        p = jnp.exp(s - m_new)
        l_scr[rows] = l_scr[rows] * alpha + jnp.sum(p, axis=1,
                                                    keepdims=True)
    pv = p if drop_fn is None else drop_fn(p)
    acc_scr[rows] = acc_scr[rows] * alpha + _dot(pv.astype(v.dtype), v)
    m_scr[rows] = m_new


def _live_interior(qi, ki, block_q, block_kv, causal, query_offset):
    """(live, interior): whether the (qi, ki) score block has any
    unmasked entry, and whether it is FULLY unmasked (strictly below
    the causal diagonal). Interior blocks skip the iota/compare/where
    mask arithmetic entirely; a block that crosses the diagonal is
    masked, whole or as a staircase (:func:`_staircase`). With the
    default 1024 blocks s=1024 is one block a head and it crosses;
    at s=4096 6 of the 10 live blocks are interior, and 28 of the 36
    the fused backward walks at its 512."""
    if not causal:
        return ki >= 0, True
    live = qi * block_q + block_q - 1 + query_offset >= ki * block_kv
    interior = ki * block_kv + block_kv - 1 <= qi * block_q + query_offset
    return live, interior


def _masked_dispatch(block_fn, qi, ki, block_q, block_kv, causal,
                     query_offset):
    """Run ``block_fn(masked)`` under ``pl.when``: the masked variant
    on diagonal-crossing blocks, the mask-free variant on fully-live
    interior blocks, nothing on dead blocks. Single definition so the
    three kernels cannot diverge."""
    live, interior = _live_interior(qi, ki, block_q, block_kv, causal,
                                    query_offset)
    if causal:
        pl.when(live & jnp.logical_not(interior))(
            lambda: block_fn(True))
        pl.when(interior)(lambda: block_fn(False))
    else:
        pl.when(live)(lambda: block_fn(False))


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, sm_scale, causal, block_q,
                block_kv, num_kv, query_offset, dropout_rate=0.0,
                seed_ref=None, num_q=None, has_bias=False):
    if has_bias:
        bias_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        bias_ref = None
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    # hoisted OUTSIDE the pl.when blocks: interpret mode cannot
    # substitute program_id inside a cond closure
    bhi = pl.program_id(0)
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    steps = _forward_staircase(block_q, block_kv, num_kv, causal,
                               query_offset)

    def _block(masked: bool):
        # sm_scale rides on q ([bq, d]) instead of on the [bq, bkv]
        # score block — 1/8th the multiplies at d=64/bkv=512
        q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(q_ref.dtype)
        bits = None
        if dropout_rate > 0.0:
            bits = _block_random_bits(seed_ref, bhi, qi, ki, num_q,
                                      num_kv, block_q, block_kv)
        stairs = steps if masked else ()
        # a staircase's rows lo:hi see no key past hi
        spans = [(slice(lo, hi), slice(0, hi)) for lo, hi in stairs] \
            or [(slice(None), slice(None))]
        for rows, cols in spans:
            s = _dot(q[rows], k_ref[0, cols, :], trans_b=True)  # f32
            if stairs:
                s = jnp.where(_diagonal_mask(s.shape, rows.start), s,
                              NEG_INF)
            elif masked:
                s = jnp.where(
                    _causal_mask(qi, ki, block_q, block_kv,
                                 query_offset), s, NEG_INF)
            if has_bias:
                # additive bias tile ([bq|1, bkv] broadcasts over rows
                # for the [b,1,1,sk] padding-mask form), AFTER the
                # causal mask like the XLA path — -1e9-style mask
                # values on top of the -1e30 causal fill stay very
                # negative
                b_rows = rows if bias_ref.shape[2] > 1 else slice(None)
                s = s + bias_ref[0, 0, b_rows, cols].astype(jnp.float32)
            drop_fn = None
            if bits is not None:
                keep = bits[rows, cols] < _dropout_threshold(dropout_rate)
                drop_fn = functools.partial(_dropped, keep=keep,
                                            rate=dropout_rate)
            _online_update(s, v_ref[0, cols, :], m_scr, l_scr, acc_scr,
                           drop_fn, rows)

    _masked_dispatch(_block, qi, ki, block_q, block_kv, causal,
                     query_offset)

    @pl.when(ki == num_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:] + jnp.log(l))


def _sds(shape, dtype, ref):
    """ShapeDtypeStruct carrying ``ref``'s varying-across-mesh axes:
    pallas out_shapes must carry them for shard_map's vma checker to
    accept the call (outputs vary exactly where ``ref`` does)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(ref).vma)


def _bias_spec(bias, num_heads, block_q, block_kv, qk_of_ids):
    """BlockSpec for a canonical ``[b0, h0, q0, skv]`` additive bias
    (each leading dim 1 or full — ``_canon_bias``) on a bh-flattened
    grid: broadcast dims pin their block index to 0 so the SAME tile
    is re-referenced (Pallas elides the redundant copies), full dims
    follow the program's (batch, head, q-block, kv-block) coordinates.
    ``qk_of_ids`` maps the grid ids to (qi, ki) — the three backward
    grids iterate in different orders."""
    b0, h0, q0, _ = bias.shape
    bq_b = block_q if q0 > 1 else 1

    def idx(*ids):
        qi, ki = qk_of_ids(*ids)
        return ((ids[0] // num_heads) if b0 > 1 else 0,
                (ids[0] % num_heads) if h0 > 1 else 0,
                qi if q0 > 1 else 0,
                ki)

    return pl.BlockSpec((1, 1, bq_b, block_kv), idx)


def _flash_forward(q, k, v, sm_scale, causal, query_offset, block_q,
                   block_kv, dropout_rate=0.0, seed=None, bias=None,
                   num_heads=None):
    # q and k share the score width d; v (and so the output) may be
    # narrower (latent attention: 192 against 128)
    bh, sq, d = q.shape
    skv, dv = k.shape[1], v.shape[2]
    num_q, num_kv = sq // block_q, skv // block_kv
    out_shape = [
        _sds((bh, sq, dv), q.dtype, q),
        _sds((bh, sq, 1), jnp.float32, q),
    ]
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, dv), jnp.float32),
    ]
    # ONE spec set for both paths (the dropout path lifts the index
    # maps for the prefetched scalar, _lift_spec)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_kv, d), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, block_kv, dv), lambda b, qi, ki: (b, ki, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, block_q, dv), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
    ]
    operands = [q, k, v]
    if bias is not None:
        in_specs.append(_bias_spec(bias, num_heads, block_q, block_kv,
                                   lambda b, qi, ki: (qi, ki)))
        operands.append(bias)
    if dropout_rate > 0.0:
        # the mixed-radix (b, qi, ki) seed fold must stay within i32
        if bh * num_q * num_kv >= 2 ** 31:
            raise NotImplementedError(
                "dropout seed fold overflows i32 for this grid")
        kernel = functools.partial(
            _seeded(_fwd_kernel), sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_kv=block_kv, num_kv=num_kv,
            query_offset=query_offset, dropout_rate=dropout_rate,
            num_q=num_q, has_bias=bias is not None)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, num_q, num_kv),
            in_specs=[_lift_spec(s) for s in in_specs],
            out_specs=[_lift_spec(s) for s in out_specs],
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=_interpret(),
        )(seed, *operands)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_kv=block_kv, num_kv=num_kv, query_offset=query_offset,
        has_bias=bias is not None)
    return pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_kv),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=_interpret(),
    )(*operands)


# -- backward ----------------------------------------------------------


def _bwd_strips(q_s, k, v, do, lse, delta, steps, mask, bias_ref=None,
                bits=None, dropout_rate=0.0):
    """Score recomputation shared by all backward kernels, over one
    ``[bq, bkv]`` block given as values (``q_s`` pre-scaled, so dk =
    ds^T @ q_s absorbs one sm_scale factor and the OTHER stays pending
    on dq — the caller applies it once on [bq, d]). Yields ``(rows,
    cols, q_s[rows], do[rows], k[cols], p_dv, ds)`` a strip of the
    block, ``p_dv`` and ``ds`` being ``[rows, cols]``: the whole block
    as one strip under ``mask`` (None on an interior block), or, with
    the ``steps`` of :func:`_staircase` on a diagonal-crossing one,
    keys ``lo:hi`` against rows ``lo:`` only. Single definition so the
    backward kernels cannot diverge (same contract as
    ``_masked_dispatch``).

    With dropout the SAME per-block random ``bits`` as the forward's
    are regenerated from (seed, b, qi, ki). Writing the dropped
    probabilities p~ = keep*p/keep_prob, the chain rule gives
    ``dv = p~^T @ do`` and ``ds = p * (keep*dp/keep_prob - delta)``
    with ``delta = rowsum(do*o) = rowsum(p~ * dp)`` — the caller's
    delta needs no change."""
    spans = [(slice(lo, None), slice(lo, hi)) for lo, hi in steps] \
        or [(slice(None), slice(None))]
    for rows, cols in spans:
        q_r, do_r, k_c = q_s[rows], do[rows], k[cols]
        s = _dot(q_r, k_c, trans_b=True)                # [rows, cols]
        if steps:
            # the strip's corner lies on the diagonal
            s = jnp.where(_diagonal_mask(s.shape), s, NEG_INF)
        elif mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        if bias_ref is not None:
            # same post-mask position as the forward: lse was computed
            # on the biased scores, so p = exp(s + bias - lse)
            # reconstructs the forward's probabilities exactly
            b_rows = rows if bias_ref.shape[2] > 1 else slice(None)
            s = s + bias_ref[0, 0, b_rows, cols].astype(jnp.float32)
        p = jnp.exp(s - lse[rows])
        dp = _dot(do_r, v[cols], trans_b=True)
        p_dv = p
        if bits is not None:
            keep = bits[rows, cols] < _dropout_threshold(dropout_rate)
            p_dv = _dropped(p, keep, dropout_rate)
            dp = _dropped(dp, keep, dropout_rate)
        yield rows, cols, q_r, do_r, k_c, p_dv, p * (dp - delta[rows])


def _bwd_block_strips(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      masked, qi, ki, sm_scale, block_q, block_kv,
                      causal, query_offset, dropout_rate=0.0,
                      seed_ref=None, num_q=None, num_kv=None,
                      bias_ref=None, bhi=None):
    """:func:`_bwd_strips` of the grid's score block (qi, ki), read
    from the kernels' block refs."""
    q = q_ref[0]
    steps = _staircase(block_q, block_kv, causal, query_offset) \
        if masked else ()
    mask = _causal_mask(qi, ki, block_q, block_kv, query_offset) \
        if masked and not steps else None
    bits = None
    if dropout_rate > 0.0:
        bits = _block_random_bits(seed_ref, bhi, qi, ki, num_q, num_kv,
                                  block_q, block_kv)
    return _bwd_strips(
        (q.astype(jnp.float32) * sm_scale).astype(q.dtype), k_ref[0],
        v_ref[0], do_ref[0], lse_ref[0], delta_ref[0], steps, mask,
        bias_ref, bits, dropout_rate)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, sm_scale, causal, block_q, block_kv, num_q,
                    query_offset, dropout_rate=0.0, seed_ref=None,
                    num_kv=None, has_bias=False):
    if has_bias:
        bias_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        bias_ref = None
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    bhi = pl.program_id(0)
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _block(masked: bool):
        for _, cols, q_s, do, _, p_dv, ds in _bwd_block_strips(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, masked,
                qi, ki, sm_scale, block_q, block_kv, causal,
                query_offset, dropout_rate, seed_ref, num_q, num_kv,
                bias_ref, bhi):
            dv_scr[cols] += _dot(p_dv.astype(do.dtype), do, trans_a=True)
            dk_scr[cols] += _dot(ds.astype(q_s.dtype), q_s, trans_a=True)

    _masked_dispatch(_block, qi, ki, block_q, block_kv, causal,
                     query_offset)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *refs, sm_scale, causal, block_q, block_kv, num_kv,
                   query_offset, dropout_rate=0.0, seed_ref=None,
                   num_q=None, has_bias=False):
    if has_bias:
        bias_ref, dq_ref, dq_scr = refs
    else:
        bias_ref = None
        dq_ref, dq_scr = refs
    bhi = pl.program_id(0)
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _block(masked: bool):
        for rows, _, _, _, k, _, ds in _bwd_block_strips(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, masked,
                qi, ki, sm_scale, block_q, block_kv, causal,
                query_offset, dropout_rate, seed_ref, num_q, num_kv,
                bias_ref, bhi):
            dq_scr[rows] += _dot(ds.astype(k.dtype), k)

    _masked_dispatch(_block, qi, ki, block_q, block_kv, causal,
                     query_offset)

    @pl.when(ki == num_kv - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


def _bwd_combined_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, *refs, sm_scale, causal, block_q,
                         block_kv, num_kv, query_offset,
                         dropout_rate=0.0, seed_ref=None,
                         has_bias=False):
    """Combined backward for the ``num_q == 1`` regime (the training
    hot path: s <= block_q, and every ring-attention shard): ONE pass
    over the ki blocks produces dq, dk, AND dv — the split kernel
    pair recomputes each score block and its exp twice (the pair
    measured 33.7 ms of the 345M microbatch backward; combined 24).
    With a single q block, dq accumulates in VMEM scratch exactly
    like the split dq kernel, while each ki's dk/dv block is visited
    once and written directly."""
    if has_bias:
        bias_ref, dq_ref, dk_ref, dv_ref, dq_scr = refs
    else:
        bias_ref = None
        dq_ref, dk_ref, dv_ref, dq_scr = refs
    bhi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _block(masked: bool):
        for rows, cols, q_s, do, k, p_dv, ds in _bwd_block_strips(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, masked,
                0, ki, sm_scale, block_q, block_kv, causal,
                query_offset, dropout_rate, seed_ref, 1, num_kv,
                bias_ref, bhi):
            dv_ref[0, cols] = _dot(p_dv.astype(do.dtype), do,
                                   trans_a=True).astype(dv_ref.dtype)
            dk_ref[0, cols] = _dot(ds.astype(q_s.dtype), q_s,
                                   trans_a=True).astype(dk_ref.dtype)
            dq_scr[rows] += _dot(ds.astype(k.dtype), k)

    _masked_dispatch(_block, 0, ki, block_q, block_kv, causal,
                     query_offset)

    # a dead kv block (possible only with query_offset < block math
    # bounds; defensive — with sq == skv and one q block every kv
    # block is live) must still define its dk/dv output
    live, _ = _live_interior(0, ki, block_q, block_kv, causal,
                             query_offset)

    @pl.when(jnp.logical_not(live))
    def _dead():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    @pl.when(ki == num_kv - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


#: fused multi-q-block backward: bytes of VMEM the resident tensors
#: (q, do at input dtype; dq f32; lse, delta f32) may claim. 6 MB
#: leaves ~10 MB of the ~16 MB/core for the streamed k/v blocks and
#: the [bq, bkv] score/exp temporaries. At bf16/d=64 this admits
#: sq <= 11776, covering the s=8192 long-context bench point; at
#: bf16/d=128, sq <= 5632, covering the 6.7B s=2048 geometry.
FUSED_BWD_RESIDENT_BUDGET = 6 * 1024 * 1024
#: internal block sizes of the fused backward's qi loop / ki grid —
#: inside one kernel there are no per-block launch overheads, so
#: small blocks only shrink the [bq, bkv] score temporaries that
#: compete with the resident tensors for VMEM
FUSED_BWD_BLOCK_Q = 512
FUSED_BWD_BLOCK_KV = 512
#: scoped VMEM asked for where q/k are wider than one lane multiple
#: and narrower than two (the v5e holds 128 MiB; the default scope is 16)
FUSED_BWD_WIDE_VMEM_LIMIT = 32 * 1024 * 1024


def _stacked(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _lanes(width: int) -> int:
    return -(-width // 128) * 128


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, sm_scale, causal,
                      block_q, block_kv, num_q, query_offset):
    """One-pass backward for the multi-q-block regime with q RESIDENT:
    grid (bh, ki); q/do/lse/delta/dq map to the same block for every
    ki, so they are fetched once per bh and stay in VMEM, dq (fp32)
    accumulating in place; k/v stream per ki; an inner fori_loop over
    qi computes each score block exactly once and emits its dk/dv and
    dq contributions together. The split kernel pair computes every
    score block twice — this path removes that recomputation for
    1024 < sq <= the VMEM budget (``FUSED_BWD_RESIDENT_BUDGET``),
    which is exactly the long-context operating point."""
    ki = pl.program_id(1)
    k, v = k_ref[0], v_ref[0]

    @pl.when(ki == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    steps = _staircase(block_q, block_kv, causal, query_offset)

    def _compute(qi, dk_acc, dv_acc, masked):
        sl = pl.ds(qi * block_q, block_q)
        q = q_ref[0, sl, :]
        q_s = (q.astype(jnp.float32) * sm_scale).astype(q.dtype)
        mask = _causal_mask(qi, ki, block_q, block_kv, query_offset) \
            if masked and not steps else None
        dks, dvs, dq_blk = [], [], None
        for rows, _, q_r, do, k_c, p, ds in _bwd_strips(
                q_s, k, v, do_ref[0, sl, :], lse_ref[0, sl, :],
                delta_ref[0, sl, :], steps if masked else (), mask):
            dks.append(_dot(ds.astype(q_r.dtype), q_r, trans_a=True))
            dvs.append(_dot(p.astype(do.dtype), do, trans_a=True))
            dq = _dot(ds.astype(k_c.dtype), k_c)        # rows lo:
            lo = rows.start or 0
            dq_blk = dq if dq_blk is None else jnp.concatenate(
                [dq_blk[:lo], dq_blk[lo:] + dq])
        # a staircase's strips stack to the block's keys
        return (dk_acc + _stacked(dks), dv_acc + _stacked(dvs), dq_blk)

    def _body(qi, carry):
        dk_acc, dv_acc = carry
        if causal:
            live, interior = _live_interior(qi, ki, block_q, block_kv,
                                            causal, query_offset)
            dk_acc, dv_acc, dq_blk = jax.lax.cond(
                interior,
                lambda: _compute(qi, dk_acc, dv_acc, False),
                # diagonal-crossing: masked math; dead (possible only
                # off the fori_loop start estimate): the mask zeroes p
                # and ds, so contributions are exactly zero anyway
                lambda: _compute(qi, dk_acc, dv_acc, True))
        else:
            dk_acc, dv_acc, dq_blk = _compute(qi, dk_acc, dv_acc, False)
        cur = dq_ref[0, pl.ds(qi * block_q, block_q), :]
        dq_ref[0, pl.ds(qi * block_q, block_q), :] = cur + dq_blk
        return dk_acc, dv_acc

    # first possibly-live qi block: its end must reach the kv block
    qi_start = ((ki * block_kv - query_offset) // block_q) if causal \
        else 0
    qi_start = jnp.maximum(qi_start, 0) if causal else 0
    dk_acc, dv_acc = jax.lax.fori_loop(
        qi_start, num_q, _body,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)))
    dk_ref[0] = dk_acc.astype(dk_ref.dtype)
    dv_ref[0] = dv_acc.astype(dv_ref.dtype)


def _fused_backward_fits(sq, skv, d, d_v, itemsize):
    """Whether ``_bwd_fused_kernel`` can hold the shape's resident
    tensors in its VMEM budget."""
    if sq % FUSED_BWD_BLOCK_Q or skv % FUSED_BWD_BLOCK_KV:
        return False
    # q and do (the out-cotangent) are resident at the input dtype,
    # dq at fp32, lse+delta at fp32 — fp32 inputs must not sneak past
    # a bf16-sized estimate into a Mosaic allocation failure
    if d % 128 and d > 128:
        # a width between lane multiples (192) sits in VMEM padded to
        # the next one; the chip's compiler counted 16.55 MB for
        # s=4096 at 192/128 against its default 16 MB of scoped VMEM,
        # so this case reckons with padded lanes and asks for more
        return sq * (_lanes(d) * (itemsize + 4) + d_v * itemsize + 8) \
            <= FUSED_BWD_WIDE_VMEM_LIMIT // 4
    return sq * (d * (itemsize + 4) + d_v * itemsize + 8) \
        <= FUSED_BWD_RESIDENT_BUDGET


def _backward_plan(sq, skv, d, d_v, itemsize, block_q, block_kv, plain):
    """``(fused, block_q, block_kv)``: whether the backward is the
    fused kernel's (several q blocks that fit its VMEM budget,
    ``plain``: it regenerates no dropout mask and has no bias
    plumbing), and the blocks it scores at: that kernel's own, else
    the forward's (the one-pass kernel of a single q block, the split
    pair)."""
    if sq // block_q > 1 and plain and \
            _fused_backward_fits(sq, skv, d, d_v, itemsize):
        return True, FUSED_BWD_BLOCK_Q, FUSED_BWD_BLOCK_KV
    return False, block_q, block_kv


def _flash_backward_fused(q, k, v, g, lse, delta, sm_scale, causal,
                          query_offset):
    """Dispatch wrapper for ``_bwd_fused_kernel``; returns None when
    the shape doesn't fit the resident-VMEM budget (caller falls back
    to the split kernel pair)."""
    bh, sq, d = q.shape
    skv, d_v = k.shape[1], v.shape[2]
    bq, bkv = FUSED_BWD_BLOCK_Q, FUSED_BWD_BLOCK_KV
    if not _fused_backward_fits(sq, skv, d, d_v,
                                jnp.dtype(q.dtype).itemsize):
        return None
    params = {}
    if d % 128 and d > 128:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_BWD_WIDE_VMEM_LIMIT)
    # the resident tensors' block index never changes within one bh —
    # single-buffer them so the pipeline does not allocate a useless
    # second copy of the largest VMEM tenants
    mode_kw = {"pipeline_mode": pl.Buffered(buffer_count=1)}

    def res_spec(width):
        return pl.BlockSpec((1, sq, width), lambda b, i: (b, 0, 0),
                            **mode_kw)

    def kv_spec(width):
        return pl.BlockSpec((1, bkv, width), lambda b, i: (b, i, 0))
    dq32, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_kv=bkv, num_q=sq // bq,
            query_offset=query_offset),
        grid=(bh, skv // bkv),
        in_specs=[res_spec(d), kv_spec(d), kv_spec(d_v), res_spec(d_v),
                  res_spec(1), res_spec(1)],
        out_specs=[res_spec(d), kv_spec(d), kv_spec(d_v)],
        out_shape=[_sds((bh, sq, d), jnp.float32, q),
                   _sds((bh, skv, d), k.dtype, q),
                   _sds((bh, skv, d_v), v.dtype, q)],
        interpret=_interpret(), **params,
    )(q, k, v, g, lse, delta)
    return (dq32 * sm_scale).astype(q.dtype), dk, dv


def _seeded(kernel):
    """Scalar-prefetch adapter: reorder the leading seed ref into the
    kernel's ``seed_ref`` kwarg."""
    def wrapped(seed_ref, *refs, **kw):
        kernel(*refs, seed_ref=seed_ref, **kw)
    return wrapped


def _lift_spec(spec):
    """BlockSpec adapter for PrefetchScalarGridSpec: the index map
    gains a trailing scalar-ref arg it ignores. Shared by the forward
    and backward dropout paths so specs cannot diverge from their
    non-dropout twins."""
    f = spec.index_map
    return pl.BlockSpec(spec.block_shape,
                        lambda *idx, _f=f: _f(*idx[:-1]))


def _flash_backward(res, g, sm_scale, causal, query_offset, block_q,
                    block_kv, g_lse=None, dropout_rate=0.0, seed=None,
                    bias=None, num_heads=None):
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    skv, d_v = k.shape[1], v.shape[2]
    num_q, num_kv = sq // block_q, skv // block_kv
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)             # [bh, sq, 1]
    if g_lse is not None:
        # cotangent of the returned logsumexp: d lse_i / d s_ij = p_ij,
        # so it folds into the kernels' existing ds = p * (dp - delta)
        # as delta' = delta - g_lse — no kernel change needed
        delta = delta - g_lse.astype(jnp.float32)
    dropout = dropout_rate > 0.0
    if dropout and bh * num_q * num_kv >= 2 ** 31:
        # the mixed-radix (b, qi, ki) seed fold must stay within i32
        raise NotImplementedError(
            "dropout seed fold overflows i32 for this grid")
    bias_ops = () if bias is None else (bias,)

    def _call(kernel_fn, grid, in_specs, out_specs, out_shape,
              scratch_shapes, qk_of_ids, **kernel_kw):
        """One backward pallas_call; the bias (if any) rides as a
        trailing operand with a per-grid index map; with dropout the
        seed rides as a prefetched scalar and every index map gains
        the trailing scalar-ref arg."""
        if bias is not None:
            in_specs = in_specs + [_bias_spec(
                bias, num_heads, block_q, block_kv, qk_of_ids)]
            kernel_kw["has_bias"] = True
        if dropout:
            kernel = functools.partial(
                _seeded(kernel_fn), dropout_rate=dropout_rate,
                **kernel_kw)
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=[_lift_spec(s) for s in in_specs],
                out_specs=([_lift_spec(s) for s in out_specs]
                           if isinstance(out_specs, list)
                           else _lift_spec(out_specs)),
                scratch_shapes=scratch_shapes)
            return pl.pallas_call(
                kernel, grid_spec=grid_spec, out_shape=out_shape,
                interpret=_interpret(),
            )(seed, q, k, v, g, lse, delta, *bias_ops)
        kernel = functools.partial(kernel_fn, **kernel_kw)
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch_shapes,
            interpret=_interpret(),
        )(q, k, v, g, lse, delta, *bias_ops)

    if num_q == 1:
        def q_spec(width):
            return pl.BlockSpec((1, block_q, width),
                                lambda b, i: (b, 0, 0))

        def kv_spec(width):
            return pl.BlockSpec((1, block_kv, width),
                                lambda b, i: (b, i, 0))
        dq, dk, dv = _call(
            _bwd_combined_kernel,
            grid=(bh, num_kv),
            in_specs=[q_spec(d), kv_spec(d), kv_spec(d_v), q_spec(d_v),
                      q_spec(1), q_spec(1)],
            out_specs=[q_spec(d), kv_spec(d), kv_spec(d_v)],
            qk_of_ids=lambda b, i: (0, i),
            out_shape=[_sds((bh, sq, d), q.dtype, q),
                       _sds((bh, skv, d), k.dtype, q),
                       _sds((bh, skv, d_v), v.dtype, q)],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            sm_scale=sm_scale, causal=causal, block_q=block_q,
            block_kv=block_kv, num_kv=num_kv,
            query_offset=query_offset)
        return dq, dk, dv

    if not dropout and bias is None:
        # the fused kernel tiles at its own internal block sizes, so
        # its regenerated dropout masks could not match the forward's
        # (and it has no bias plumbing) — those cases use the split
        # pair below instead (:func:`_backward_plan` says the same)
        fused = _flash_backward_fused(q, k, v, g, lse, delta, sm_scale,
                                      causal, query_offset)
        if fused is not None:
            return fused

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda b, i, j: (b, j, 0))

    def kv_spec(width):
        return pl.BlockSpec((1, block_kv, width),
                            lambda b, i, j: (b, i, 0))
    dk, dv = _call(
        _bwd_dkv_kernel,
        grid=(bh, num_kv, num_q),
        in_specs=[q_spec(d), kv_spec(d), kv_spec(d_v), q_spec(d_v),
                  q_spec(1), q_spec(1)],
        out_specs=[kv_spec(d), kv_spec(d_v)],
        qk_of_ids=lambda b, i, j: (j, i),
        out_shape=[_sds((bh, skv, d), k.dtype, q),
                   _sds((bh, skv, d_v), v.dtype, q)],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d_v), jnp.float32)],
        sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_kv=block_kv, num_q=num_q, num_kv=num_kv,
        query_offset=query_offset)

    def q_spec2(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda b, i, j: (b, i, 0))

    def kv_spec2(width):
        return pl.BlockSpec((1, block_kv, width),
                            lambda b, i, j: (b, j, 0))
    dq = _call(
        _bwd_dq_kernel,
        grid=(bh, num_q, num_kv),
        in_specs=[q_spec2(d), kv_spec2(d), kv_spec2(d_v), q_spec2(d_v),
                  q_spec2(1), q_spec2(1)],
        out_specs=q_spec2(d),
        qk_of_ids=lambda b, i, j: (i, j),
        out_shape=_sds((bh, sq, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_kv=block_kv, num_kv=num_kv, num_q=num_q,
        query_offset=query_offset)
    return dq, dk, dv


# -- public API --------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, sm_scale, causal, block_q, block_kv):
    return _flash_forward(q, k, v, sm_scale, causal, 0, block_q,
                          block_kv)


def _flash_lse_fwd(q, k, v, sm_scale, causal, block_q, block_kv):
    out, lse = _flash_forward(q, k, v, sm_scale, causal, 0, block_q,
                              block_kv)
    # Tag the residuals that only this kernel can produce with the same
    # checkpoint name the model puts on q/k/v ("attn"): under a remat
    # policy that saves "attn" (save_dots, core_attn) the backward can
    # then reconstruct ALL residuals without re-running the forward
    # kernel — without the tag the untagged lse forces a full forward
    # re-run just to regenerate it (measured 19 ms of the 224 ms 345M
    # microbatch, ~8%). lse is [bh, sq, 1] fp32 = 0.5 MB per 345M
    # layer. Policies that exclude "attn" (full_attn) recompute
    # exactly as before.
    out = checkpoint_name(out, "attn")
    lse = checkpoint_name(lse, "attn")
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(sm_scale, causal, block_q, block_kv, res, g):
    g_out, g_lse = g
    return _flash_backward(res, g_out, sm_scale, causal, 0, block_q,
                           block_kv, g_lse=g_lse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_lse_dropout(q, k, v, seed, sm_scale, causal, block_q,
                       block_kv, dropout_rate):
    """Dropout twin of ``_flash_lse``: the [1] int32 ``seed`` is a
    TRACED operand (a fresh dropout pattern per step must not
    retrace), delivered to the kernels by scalar prefetch; the keep
    mask is regenerated per score block from (seed, b, qi, ki) in
    both directions, so nothing beyond the standard residuals is
    saved."""
    return _flash_forward(q, k, v, sm_scale, causal, 0, block_q,
                          block_kv, dropout_rate, seed)


def _flash_lse_dropout_fwd(q, k, v, seed, sm_scale, causal, block_q,
                           block_kv, dropout_rate):
    out, lse = _flash_forward(q, k, v, sm_scale, causal, 0, block_q,
                              block_kv, dropout_rate, seed)
    out = checkpoint_name(out, "attn")
    lse = checkpoint_name(lse, "attn")
    return (out, lse), (q, k, v, out, lse, seed)


def _flash_lse_dropout_bwd(sm_scale, causal, block_q, block_kv,
                           dropout_rate, res, g):
    q, k, v, out, lse, seed = res
    g_out, g_lse = g
    dq, dk, dv = _flash_backward(
        (q, k, v, out, lse), g_out, sm_scale, causal, 0, block_q,
        block_kv, g_lse=g_lse, dropout_rate=dropout_rate, seed=seed)
    import numpy as np
    return dq, dk, dv, np.zeros(seed.shape, jax.dtypes.float0)


_flash_lse_dropout.defvjp(_flash_lse_dropout_fwd,
                          _flash_lse_dropout_bwd)


def _canon_bias(bias, b, h, sq, skv):
    """Validate an additive attention bias for the kernel: 4D
    ``[b0, h0, q0, skv]`` with every leading dim either 1 or full (the
    padding-mask ``[b, 1, 1, skv]`` and dense ``[b, h, sq, skv]``
    forms both qualify) and the LAST dim full — a size-1 key dim would
    add the same value to every score in a row, which softmax's shift
    invariance makes a no-op, so refusing it costs nothing.
    NotImplementedError sends the caller to the XLA fallback."""
    if bias.ndim != 4:
        raise NotImplementedError(
            f"bias must be 4D broadcastable, got shape {bias.shape}")
    b0, h0, q0, k0 = bias.shape
    if k0 != skv:
        raise NotImplementedError(
            f"bias key dim {k0} must equal kv length {skv}")
    if b0 not in (1, b) or h0 not in (1, h) or q0 not in (1, sq):
        raise NotImplementedError(
            f"bias shape {bias.shape} not broadcastable to "
            f"[{b}, {h}, {sq}, {skv}]")
    return bias


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_lse_biased(q, k, v, bias, seed, sm_scale, causal, block_q,
                      block_kv, dropout_rate, num_heads):
    """Biased twin of ``_flash_lse`` / ``_flash_lse_dropout``: an
    additive ``[b0, h0, q0, skv]`` bias rides into every kernel as a
    tiled operand (``_bias_spec``). The bias is treated as an
    attention MASK, not a trained tensor — its cotangent is defined
    as ZERO (learned ALiBi-style biases must use the XLA path; see
    docs/attention_dispatch.md). ``seed`` is ignored when
    ``dropout_rate == 0`` (callers pass a dummy)."""
    return _flash_forward(q, k, v, sm_scale, causal, 0, block_q,
                          block_kv, dropout_rate, seed, bias=bias,
                          num_heads=num_heads)


def _flash_lse_biased_fwd(q, k, v, bias, seed, sm_scale, causal,
                          block_q, block_kv, dropout_rate, num_heads):
    out, lse = _flash_forward(q, k, v, sm_scale, causal, 0, block_q,
                              block_kv, dropout_rate, seed, bias=bias,
                              num_heads=num_heads)
    out = checkpoint_name(out, "attn")
    lse = checkpoint_name(lse, "attn")
    return (out, lse), (q, k, v, out, lse, bias, seed)


def _flash_lse_biased_bwd(sm_scale, causal, block_q, block_kv,
                          dropout_rate, num_heads, res, g):
    q, k, v, out, lse, bias, seed = res
    g_out, g_lse = g
    dq, dk, dv = _flash_backward(
        (q, k, v, out, lse), g_out, sm_scale, causal, 0, block_q,
        block_kv, g_lse=g_lse, dropout_rate=dropout_rate, seed=seed,
        bias=bias, num_heads=num_heads)
    import numpy as np
    return (dq, dk, dv, jnp.zeros_like(bias),
            np.zeros(seed.shape, jax.dtypes.float0))


_flash_lse_biased.defvjp(_flash_lse_biased_fwd, _flash_lse_biased_bwd)


def check_shapes(sq, skv, d, block_q: int = None,
                 block_kv: int = None, d_v: int = None):
    """(block_q, block_kv) after clamping, or NotImplementedError —
    shared by the public wrappers and by callers (ring attention) that
    must decide statically whether the kernel can take their shapes.
    ``None`` blocks auto-pick the largest aligned divisor <= 1024.
    ``d`` is the width q and k are scored at, ``d_v`` the width of v
    and of the output where it differs (latent attention)."""
    block_q = _auto_block(sq, DEFAULT_BLOCK_Q, 8) if block_q is None \
        else min(block_q, sq)
    block_kv = _auto_block(skv, DEFAULT_BLOCK_KV, 128) \
        if block_kv is None else min(block_kv, skv)
    if sq % block_q or skv % block_kv:
        raise NotImplementedError(
            f"sequence ({sq}, {skv}) not divisible by blocks "
            f"({block_q}, {block_kv})")
    if block_q % 8 or block_kv % 128:
        # clamped blocks (short sequences) must still be TPU
        # tile-aligned — sublane 8 for q rows, lane 128 for kv columns;
        # Mosaic would reject unaligned blocks with a compile error
        # that the NotImplementedError fallback can't catch
        raise NotImplementedError(
            f"blocks ({block_q}, {block_kv}) not tile-aligned")
    if d % 128 and d not in (64,):
        # a narrower v leaves q/k a whole number of 64-lane halves
        # (192 = 128 + 64); one width for all three keeps the old rule
        if d_v in (None, d) or d % 64:
            raise NotImplementedError(f"head_dim {d} unsupported")
    if d_v not in (None, d) and d_v % 128:
        raise NotImplementedError(f"v head_dim {d_v} unsupported")
    return block_q, block_kv


def _to_bh(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def flash_attention(q, k, v, causal: bool = True, query_offset=0,
                    block_q: int = None, block_kv: int = None,
                    dropout_rate: float = 0.0, dropout_rng=None,
                    bias=None, sm_scale: float = None):
    """``[b, s, h, d]`` attention; raises NotImplementedError when the
    shape/backend can't take the kernel (caller falls back to the XLA
    path in ``ops.attention``).

    ``v`` may be ``[b, s, h, d_v]`` with ``d_v != d`` (the output then
    has v's width); ``sm_scale`` defaults to ``d ** -0.5`` of the q/k
    width.

    ``bias`` is an additive score bias broadcastable to
    ``[b, h, sq, skv]`` (each leading dim 1 or full — ERNIE padding
    masks ``[b, 1, 1, skv]``, GPT attn_mask) tiled into every kernel;
    it is treated as a non-differentiable MASK (zero cotangent).

    ``dropout_rate > 0`` runs IN-KERNEL attention-probs dropout (the
    reference's fused softmax-with-dropout training path,
    ``hybrid_model.py:277-285``): the per-core PRNG generates the keep
    mask inside each score block from (seed, block coords) — no
    [b, h, s, s] mask tensor ever exists, in either direction. In
    interpret mode a stateless hash substitutes for the (TPU-only)
    hardware PRNG so CPU tests can validate the plumbing."""
    if jax.default_backend() != "tpu" and not _interpret():
        raise NotImplementedError("flash kernel targets TPU")
    if not isinstance(query_offset, int) or query_offset != 0:
        raise NotImplementedError("cached decode uses the XLA path")
    b, sq, h, d = q.shape
    d_v = v.shape[-1]
    block_q, block_kv = check_shapes(sq, k.shape[1], d, block_q,
                                     block_kv, d_v=d_v)
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    if dropout_rate > 0.0 and dropout_rng is None:
        raise NotImplementedError(
            "flash dropout needs a dropout_rng")
    if bias is not None:
        bias = _canon_bias(bias, b, h, sq, k.shape[1])
        # the kernels add the bias in f32 and its (zero) cotangent
        # must be float-typed; one cast here covers bool/int masks
        if bias.dtype != jnp.float32:
            bias = bias.astype(jnp.float32)
        if dropout_rate > 0.0:
            seed = jax.random.randint(dropout_rng, (1,), 0,
                                      2 ** 31 - 1, dtype=jnp.int32)
        else:
            seed = jnp.zeros((1,), jnp.int32)   # ignored
        out, _ = _flash_lse_biased(
            _to_bh(q), _to_bh(k), _to_bh(v), bias, seed, sm_scale,
            causal, block_q, block_kv, float(dropout_rate), h)
        return out.reshape(b, h, sq, d_v).transpose(0, 2, 1, 3)
    if dropout_rate > 0.0:
        seed = jax.random.randint(dropout_rng, (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)
        out, _ = _flash_lse_dropout(
            _to_bh(q), _to_bh(k), _to_bh(v), seed, sm_scale, causal,
            block_q, block_kv, float(dropout_rate))
        return out.reshape(b, h, sq, d_v).transpose(0, 2, 1, 3)
    # lse discarded: its cotangent is then symbolically zero and the
    # backward's delta adjustment is a no-op — one custom_vjp serves
    # both the plain and the with-lse surface
    out, _ = _flash_lse(_to_bh(q), _to_bh(k), _to_bh(v), sm_scale,
                        causal, block_q, block_kv)
    return out.reshape(b, h, sq, d_v).transpose(0, 2, 1, 3)


def flash_attention_with_lse(q, k, v, causal: bool = True,
                             sm_scale=None,
                             block_q: int = None, block_kv: int = None):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp of the (scaled) scores, ``[b, h, sq]`` fp32 — the
    streaming-combination state ring attention needs to merge exact
    softmax results across KV blocks held on other devices. Fully
    differentiable: the lse cotangent folds into the backward kernels'
    delta term."""
    if jax.default_backend() != "tpu" and not _interpret():
        raise NotImplementedError("flash kernel targets TPU")
    b, sq, h, d = q.shape
    block_q, block_kv = check_shapes(sq, k.shape[1], d, block_q,
                                     block_kv)
    sm_scale = d ** -0.5 if sm_scale is None else sm_scale
    out, lse = _flash_lse(_to_bh(q), _to_bh(k), _to_bh(v), sm_scale,
                          causal, block_q, block_kv)
    return (out.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
            lse.reshape(b, h, sq))


# -- cached decode -----------------------------------------------------


def _group_dot(a, b, contract):
    """``a [g, r, x] . b [g, y, z]`` contracted over ``contract`` (one
    dim of each), a product a pooled head on the MXU, fp32 out.
    bfloat16 operands multiply exactly into the fp32 accumulator;
    fp32 operands take Mosaic's multi-pass product, which keeps their
    24 bits."""
    precision = jax.lax.Precision.HIGHEST \
        if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, ((contract[0], contract[1]), ((0,), (0,))),
        precision=precision, preferred_element_type=jnp.float32)


def _group_pv(p, v):
    """``p [g, r, bkv]`` (fp32) against ``v [g, d, bkv]``, both
    contracted over their lanes: ``[g, r, d]`` fp32. The probabilities
    keep fp32's 24 bits against a bfloat16 block too: ``p = hi + mid +
    lo`` exactly, three bfloat16 parts of 8 bits each, stacked as ``3
    * r`` rows of ONE product, so the resident V block is the MXU's
    weight once."""
    if v.dtype != jnp.bfloat16:
        return _group_dot(p, v, ((2,), (2,)))
    r = p.shape[1]
    hi = p.astype(jnp.bfloat16).astype(jnp.float32)
    rest = p - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    parts = jnp.concatenate([hi, mid, rest - mid], axis=1)
    o = _group_dot(parts.astype(jnp.bfloat16), v, ((2,), (2,)))
    return o[:, :r] + (o[:, r:2 * r] + o[:, 2 * r:])


def _decode_block(q, k, v, live, sm_scale, m_prev, l_prev, acc_prev,
                  bias=None):
    """One key block of the cached-decode online softmax, every head
    in one vectorized pass (a per-head loop would issue ~6x num_heads
    small VPU ops and dominate the call). Takes and returns the
    running ``m [h, 1]``, ``l [h, 1]``, ``acc [h, d]``, all fp32, and
    the softmax between the two products is fp32 on the VPU over ``[h,
    bkv]`` whatever formed them. Shared by the contiguous kernels and
    the paged ones, so a row's number does not depend on which of them
    walked its blocks.

    One K/V head a query head (``k`` / ``v`` ``[h, d, bkv]`` fp32):
    ONE query token, ``q [h, d, 1]`` (or its column on every lane),
    ``live [1, bkv]`` the keys it may see. A head's scores are a
    matvec, so both products are VPU broadcast-multiply-reduces over
    the cache's native ``[d, bkv]`` tiles; the MXU would have one row
    to share a weight load with.

    Grouped-query heads come by shape: ``k`` / ``v`` ``[g, d, bkv]``
    of ``g`` pooled heads under ``h = g * r`` query rows, ``q [g, r,
    d]`` (``d`` on the lanes, the operands in one dtype: the pool's
    bfloat16, or fp32), ``live [1 | h, bkv]``. The ``r`` rows of a
    group (its query heads padded to whole sublane tiles, times the
    verify window's positions, each with its own row of ``live``)
    share the resident block as the MXU's weight: ``q_group [r, d] .
    k [d, bkv]`` and ``p [r, bkv] . v [d, bkv]^T``
    (:func:`_group_dot`, :func:`_group_pv`), two products a pooled
    head a block where the VPU multiplied and reduced ``[g, r, d,
    bkv]`` element by element."""
    h, g = m_prev.shape[0], k.shape[0]
    if g == h:
        s = jnp.sum(q * k, axis=1) * sm_scale      # [h, bkv] f32
    else:
        s = _group_dot(q, k, ((2,), (1,))).reshape(h, -1) * sm_scale
    if bias is not None:
        s = s + bias                               # [1, bkv] broadcasts
    s = jnp.where(live, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                         # [h, bkv]
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    if g == h:
        # output: broadcast p over d, reduce over the key lanes
        pv = jnp.sum(p[:, None, :] * v, axis=2)
    else:
        pv = _group_pv(p.reshape(g, h // g, -1), v).reshape(h, -1)
    return m_new, l_new, acc_prev * alpha + pv


def _decode_kernel(off_ref, q_ref, k_ref, v_ref, *refs, sm_scale,
                   block_kv, num_kv, has_bias, ragged=False,
                   quantized=False):
    """Single-token decode over the fixed-capacity KV cache.

    With one K/V head a query head, decode attention is a matvec, not
    a matmul — per (head, key-block) the scores are ``sum_d q[d] *
    k[d, S]`` and the output is ``sum_S p[S] * v[d, S]``, both VPU
    broadcast-multiply-reduces over the cache's native ``[d, S]``
    tiles. On the MXU a head's product would be ONE row against a
    ``[d, S]`` weight load, the load's fixed latency paid for nothing
    (measured 2026-07-30, commit 49ad5b5, at one row a product: 512
    matmuls/call = ~370 us); this kernel folds ALL heads into one
    program per (batch, key-block) so the grid is ``b * num_kv``
    programs of pure VPU streaming. That note is about one-row
    products only: where the heads of a group SHARE a K/V head the
    rows of a product are the group's heads, and the paged kernel
    takes the MXU (:func:`_paged_gqa_kernel`, PR 32). The contiguous
    kernels know no groups (``ops/attention.py`` sends grouped
    operands without a page table to the dense path).

    The live length is DYNAMIC (the decode loop's cache index), so it
    arrives as a prefetched scalar: blocks wholly past the last valid
    position move no bytes and do no math (the index map re-references
    the resident block, the body is ``pl.when``-ed off) — short
    prefixes only STREAM the cache they have actually filled — and the
    straddling block is masked. They are still grid steps: the grid is
    ``(b, num_kv)`` whatever is filled, at ~0.4 us a step on a v5e
    (ledger, PR 25), so this kernel's TIME has a floor set by the
    capacity. Only the paged kernel (:func:`_paged_kernel`) walks the
    live pairs alone. With ``has_bias`` a per-key additive bias tile
    rides along (the generation loop's left-pad mask).

    ``ragged``: the prefetched offsets are PER ROW (``[b]``, the
    continuous-batching slot lengths) instead of one shared scalar —
    each batch row masks and block-skips against its OWN last valid
    position, so a short slot never streams a long slot's cache.

    ``quantized``: the cache tiles are int8 and two extra operands
    carry the per-(row, head, position) fp32 scales (``[h, 1, bkv]``
    blocks riding the same index maps as K/V minus the d axis);
    dequant happens HERE, on the VMEM-resident block — the widened
    f32 copy never exists in HBM, so the streamed bytes stay int8.
    """
    refs = list(refs)
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    if has_bias:
        bias_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        bias_ref = None
        o_ref, m_scr, l_scr, acc_scr = refs
    ki = pl.program_id(1)
    # last valid key position: shared (lockstep decode) or this row's
    offset = off_ref[pl.program_id(0)] if ragged else off_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_kv <= offset)
    def _block():
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        live = k_pos <= offset                     # [1, bkv]
        q = q_ref[0].astype(jnp.float32)           # [h, d, 1]
        k = k_ref[0].astype(jnp.float32)           # [h, d, bkv]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0]                      # [h, 1, bkv] bcast
            v = v * vs_ref[0]
        m_scr[:], l_scr[:], acc_scr[:] = _decode_block(
            q, k, v, live, sm_scale, m_scr[:], l_scr[:], acc_scr[:],
            bias_ref[0] if has_bias else None)     # [1, bkv] bias

    @pl.when(ki == num_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] /
                    jnp.maximum(l_scr[:], 1e-30))[..., None].astype(
            o_ref.dtype)


def _verify_kernel(off_ref, q_ref, k_ref, v_ref, *refs, sm_scale,
                   block_kv, num_kv, window, ragged=True,
                   quantized=False):
    """Speculative k-token VERIFY over the KV cache: ``window`` query
    tokens per row, where query ``j`` sits at cache position
    ``offset + j`` and attends keys ``<= offset + j`` — the
    within-window causal mask speculative decoding needs to score a
    drafted token run in ONE pass (docs/inference.md).

    Per query the math is exactly :func:`_decode_kernel`'s matvec +
    online softmax (a static Python loop over ``j`` unrolls into
    ``window`` independent VPU passes sharing each resident KV block),
    so greedy verification is bit-compatible with sequential
    single-token decode: a block wholly past query ``j``'s last live
    position contributes masked-out scores only (``alpha == 1``,
    ``p == 0`` — block 0 is always live, so ``m`` is finite before any
    dead block arrives) and the running ``m/l/acc`` state passes
    through unchanged. Scratch carries one ``[h, 1]`` / ``[h, d]``
    state row per window position. No bias operand (serving decode
    carries none — per-slot validity lives in the offsets). With
    ``quantized`` the int8 cache block dequantizes ONCE per resident
    block (``[h, 1, bkv]`` fp32 scale operands, same contract as
    :func:`_decode_kernel`) and all ``window`` queries share the
    widened copy."""
    refs = list(refs)
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    o_ref, m_scr, l_scr, acc_scr = refs
    ki = pl.program_id(1)
    offset = off_ref[pl.program_id(0)] if ragged else off_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # a block participates when ANY window query can see it; per-query
    # liveness is the mask below
    @pl.when(ki * block_kv <= offset + (window - 1))
    def _block():
        k_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        k = k_ref[0].astype(jnp.float32)           # [h, d, bkv]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0]                      # [h, 1, bkv] bcast
            v = v * vs_ref[0]
        for j in range(window):
            live = k_pos <= offset + j             # [1, bkv]
            qj = q_ref[0, :, :, j].astype(jnp.float32)   # [h, d]
            m_scr[j], l_scr[j], acc_scr[j] = _decode_block(
                qj[:, :, None], k, v, live, sm_scale, m_scr[j],
                l_scr[j], acc_scr[j])

    @pl.when(ki == num_kv - 1)
    def _finish():
        o = acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)   # [W, h, d]
        o_ref[0] = o.transpose(1, 2, 0).astype(o_ref.dtype)


def _check_kv_scales(k, v, k_scale, v_scale, h, skv):
    """Admission for the int8-KV operand pair: scales come BOTH or
    NEITHER, the cache must actually be int8, and each scale is the
    cache minus its d axis (``[..., h, 1, S]`` fp32 — one scale per
    (row, head, position), written by the cache-update path in
    ``models/gpt/model.py``)."""
    if (k_scale is None) != (v_scale is None):
        raise NotImplementedError(
            "int8 KV wants both k_scale and v_scale (or neither)")
    if k_scale is None:
        return False
    if k.dtype != jnp.int8 or v.dtype != jnp.int8:
        raise NotImplementedError(
            f"KV scales given but cache is {k.dtype}/{v.dtype}, "
            "not int8")
    want = k.shape[:1] + (h, 1, skv)
    if k_scale.shape != want or v_scale.shape != want:
        raise NotImplementedError(
            f"KV scales must be {want}, got {k_scale.shape} / "
            f"{v_scale.shape}")
    return True


def _flash_decode_call(q, k, v, off, bias, block_kv: int, ragged: bool,
                       k_scale=None, v_scale=None):
    """Shared shape-check + ``pallas_call`` builder behind
    :func:`flash_decode` (``off [1]``, one shared cache index) and
    :func:`flash_decode_ragged` (``off [b]``, per-slot lengths). With
    ``sq > 1`` the queries are a speculative VERIFY window — query
    ``j`` of row ``i`` sits at position ``off[i] + j`` and the
    windowed kernel (:func:`_verify_kernel`) applies the within-window
    causal mask; bias is single-token only. ``k_scale``/``v_scale``
    (``[b, h, 1, S]`` fp32) switch the kernels to the int8-KV
    dequant-in-kernel variants. Raises NotImplementedError where the
    caller must fall back to XLA."""
    if jax.default_backend() != "tpu" and not _interpret():
        raise NotImplementedError("flash kernel targets TPU")
    b, sq, h, d = q.shape
    window = sq
    if window < 1:
        raise NotImplementedError("empty decode window")
    if window > 1 and bias is not None:
        raise NotImplementedError(
            "verify window (sq > 1) takes no bias (per-slot validity "
            "is the offsets')")
    skv = k.shape[3]
    if k.shape[1] != h:
        raise NotImplementedError(
            f"the contiguous decode kernels know no grouped heads "
            f"({h} query heads over {k.shape[1]}): only the paged "
            f"kernel does")
    quantized = _check_kv_scales(k, v, k_scale, v_scale, h, skv)
    # largest 128-aligned divisor <= block_kv: capacities that are
    # 128-multiples but not block_kv-multiples (e.g. 1280) stay on the
    # kernel instead of tripping the skv % block_kv rejection below
    block_kv = _auto_block(skv, block_kv, 128)
    # all heads ride in one block, so k/v blocks are h-times larger
    # than a per-head grid's: shrink block_kv until the kernel's VMEM
    # footprint fits comfortably in the ~16M scoped limit (a Mosaic
    # allocation failure would crash instead of falling back). The
    # single-token kernel holds the double-buffered k+v blocks; the
    # verify kernel additionally widens the resident K and V blocks
    # to f32, holds one [h, d, bkv] product at a time and a pair of
    # [h, bkv] score/prob rows per window position — uncounted, those
    # put w >= 3 at 18-23M on a v5e (described-chip compile, PR 21).
    def footprint(bkv):
        n = 4 * h * d * bkv * k.dtype.itemsize
        if window > 1:
            n += 3 * h * d * bkv * 4 + window * 2 * h * bkv * 4
        return n

    budget = (8 if window == 1 else 12) * 1024 * 1024
    while block_kv > 128 and footprint(block_kv) > budget:
        block_kv //= 2
    if skv % block_kv or block_kv % 128 or \
            footprint(block_kv) > budget:
        raise NotImplementedError(
            f"cache length {skv} not tileable by {block_kv} "
            f"within VMEM budget (h={h}, d={d}, window={window})")
    if d % 8:
        raise NotImplementedError(f"head_dim {d} unsupported")
    num_kv = skv // block_kv

    # [b, W, h, d] -> [b, h, d, W]: the query token(s) as lane
    # column(s) per head, matching the cache's d-major tiles
    qp = q.transpose(0, 2, 3, 1)

    # clamp the kv block index once past the live length: skipped
    # iterations re-reference the already-resident block, so the
    # HBM->VMEM copy is elided and a short prefix streams only the
    # cache it has actually filled (the compute skip alone would
    # still stream the full capacity; the skipped iterations remain
    # grid steps). Ragged, each ROW clamps against its own length —
    # the per-slot byte model of the continuous-batching server. A
    # verify window's LAST query
    # (position off + window - 1) sets the walk bound; earlier
    # queries just mask the tail blocks out.
    def kv_block(bi, ki, off):
        row = (off[bi] if ragged else off[0]) + (window - 1)
        return jnp.minimum(ki, row // block_kv)

    in_specs = [
        pl.BlockSpec((1, h, d, window),
                     lambda bi, ki, off: (bi, 0, 0, 0)),
        pl.BlockSpec((1, h, d, block_kv),
                     lambda bi, ki, off: (bi, 0, 0,
                                          kv_block(bi, ki, off))),
        pl.BlockSpec((1, h, d, block_kv),
                     lambda bi, ki, off: (bi, 0, 0,
                                          kv_block(bi, ki, off))),
    ]
    operands = [qp, k, v]
    if quantized:
        # fp32 scale blocks ride the SAME clamped index maps as their
        # K/V tiles (the d axis collapsed to 1), so a skipped block's
        # scale copy is elided right along with it
        for _ in range(2):
            in_specs.append(pl.BlockSpec(
                (1, h, 1, block_kv),
                lambda bi, ki, off: (bi, 0, 0, kv_block(bi, ki, off))))
        operands += [k_scale, v_scale]
    if bias is not None:
        # per-key additive bias (the generation loop's left-pad mask),
        # [b, skv] or broadcastable [b, 1, 1, skv]; a [1, bkv] row
        # broadcasts against each head's [1, bkv] scores
        operands.append(jnp.reshape(bias.astype(jnp.float32),
                                    (b, 1, skv)))
        in_specs.append(pl.BlockSpec(
            (1, 1, block_kv),
            lambda bi, ki, off: (bi, 0, kv_block(bi, ki, off))))

    if window == 1:
        kernel = functools.partial(_decode_kernel, sm_scale=d ** -0.5,
                                   block_kv=block_kv, num_kv=num_kv,
                                   has_bias=bias is not None,
                                   ragged=ragged, quantized=quantized)
        scratch = [
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ]
    else:
        kernel = functools.partial(_verify_kernel, sm_scale=d ** -0.5,
                                   block_kv=block_kv, num_kv=num_kv,
                                   window=window, ragged=ragged,
                                   quantized=quantized)
        scratch = [
            pltpu.VMEM((window, h, 1), jnp.float32),
            pltpu.VMEM((window, h, 1), jnp.float32),
            pltpu.VMEM((window, h, d), jnp.float32),
        ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, num_kv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, h, d, window), lambda bi, ki, off: (bi, 0, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=_sds((b, h, d, window), q.dtype, q),
        interpret=_interpret(),
    )(off, *operands)
    # [b, h, d, W] -> [b, W, h, d]
    return out.transpose(0, 3, 1, 2)


def flash_decode(q, k, v, query_offset, bias=None,
                 block_kv: int = DEFAULT_BLOCK_KV,
                 k_scale=None, v_scale=None):
    """One decode step through the cache: ``q [b, 1, h, d]`` attends to
    ``k/v [b, h, d, S]`` positions ``<= query_offset`` (a traced
    scalar — the fixed-capacity cache index of ``models/gpt/model.py``).

    Inference-only (no VJP). Raises NotImplementedError when the
    shape/backend can't take the kernel; the caller falls back to the
    XLA path. The cache arrives in its NATIVE ``[b, h, d, S]`` layout
    — minor tile dims (d, S) fill TPU (8,128) tiles exactly (zero
    padding; any d=64-minor layout wastes 2x HBM). One program per
    (batch, key-block) streams every head's ``[d, bkv]`` tiles and
    runs the matvec attention on the VPU (see ``_decode_kernel``).
    """
    off = jnp.reshape(jnp.asarray(query_offset, jnp.int32), (1,))
    return _flash_decode_call(q, k, v, off, bias, block_kv,
                              ragged=False, k_scale=k_scale,
                              v_scale=v_scale)


def flash_decode_ragged(q, k, v, query_offsets, bias=None,
                        block_kv: int = DEFAULT_BLOCK_KV,
                        k_scale=None, v_scale=None):
    """Per-row decode through the cache: row ``i`` of ``q [b, 1, h, d]``
    attends to ``k/v [b, h, d, S]`` positions ``<= query_offsets[i]``
    (a traced ``[b]`` int vector — the continuous-batching server's
    per-slot cache lengths minus one, i.e. each slot's just-written
    position).

    Same kernel body and layout contract as :func:`flash_decode`; the
    offsets prefetch as a ``[b]`` scalar operand so both the in-kernel
    masking and the block-skip index maps read the PER-ROW length —
    a freshly admitted slot streams only its own short cache while a
    long-running neighbour streams its full one. That holds for the
    bytes, not for the grid: every row still takes ``S // block_kv``
    steps (see :func:`_decode_kernel`); the paged kernel
    (:func:`flash_decode_paged`) is the one whose grid follows the
    live slots and blocks.

    ``sq > 1`` is the speculative VERIFY window (no bias): query ``j``
    of row ``i`` sits at position ``query_offsets[i] + j`` and masks
    keys ``<= query_offsets[i] + j`` (:func:`_verify_kernel`) — one
    pass scores a whole drafted token run. Inference-only; raises
    NotImplementedError where the caller must fall back to the XLA
    per-row-offset path (``ops/attention.py::_xla_attention``).

    ``k_scale``/``v_scale`` (``[b, h, 1, S]`` fp32, one scale per
    (slot, head, position)) switch both the single-token and the
    verify-window kernel to their int8-KV dequant-in-kernel variants
    — the cache streams as int8 and widens on the VMEM-resident
    block (docs/quantization.md).
    """
    b = q.shape[0]
    offs = jnp.asarray(query_offsets, jnp.int32)
    if offs.ndim != 1 or offs.shape[0] != b:
        raise NotImplementedError(
            f"ragged offsets must be [b={b}], got {offs.shape}")
    return _flash_decode_call(q, k, v, offs, bias, block_kv,
                              ragged=True, k_scale=k_scale,
                              v_scale=v_scale)


# -- paged decode: a walk over the live (slot, block) pairs -------------

#: the reserved page of ``core/paging.py``: a slot whose page-table row
#: starts with it is not decoding (``core/serving.py::_sync_pt`` nulls
#: every non-ACTIVE slot's row; an active slot's first page is never
#: this one)
NULL_PAGE = 0
#: slots per lane group of the rows-on-lanes operands (``q`` and the
#: output here, the fresh values of ``kv_write.py``)
LANES = 128
#: what Mosaic scopes for a kernel unless told otherwise, and the most
#: the cache kernels count for themselves before they refuse a shape
#: (a wide verify window's ``q`` / output here and fresh values there)
VMEM_DEFAULT = 16 * 1024 * 1024
VMEM_MOST = 48 * 1024 * 1024
#: of the chip's 1 MiB of SMEM, what the paged kernel's prefetched
#: scalars (offsets, page table, the walk's two lists) may take
SMEM_MOST = 768 * 1024


def _reach_first(offs, reach, block_kv: int):
    """First block a query at ``offs`` reads: block 0, or with a
    sliding window of ``reach`` keys (key ``j`` visible iff ``off -
    reach < j <= off``) the block of key ``off + 1 - reach``."""
    if reach is None:
        return 0
    return jnp.maximum((offs + (1 - reach)) // block_kv, 0)


def _paged_walk(offs, pt, window: int, block_kv: int, num_kv: int,
                reach=None):
    """The grid of one paged decode call: ``(rows [T], blocks [T],
    steps)`` with ``T = b * num_kv``, of which the first ``steps``
    entries are the (slot, logical block) pairs the kernel visits —
    slots ascending, each slot's blocks ascending.

    A live slot (its page-table row does not start with
    :data:`NULL_PAGE`) takes blocks ``first .. (off + W - 1) //
    block_kv``, ``first`` by :func:`_reach_first` (0 without a sliding
    window); a dead one takes none, except that the FIRST slot of every
    group of :data:`LANES` always takes one step, dead or not: the step that
    zeroes the group's output block, so no output block is left
    unvisited and ``steps >= 1``. A handful of integer ops on ``[b]``
    and ``[T]``; every layer of a tick builds the same walk from the
    same operands, and XLA keeps one (tests/test_chip_compile.py)."""
    b = offs.shape[0]
    last = jnp.minimum((offs + (window - 1)) // block_kv, num_kv - 1)
    first = _reach_first(offs, reach, block_kv)
    n = jnp.where(pt[:, 0] != NULL_PAGE,
                  jnp.maximum(last + 1 - first, 0), 0)
    n = jnp.maximum(
        n, (jnp.arange(b, dtype=jnp.int32) % LANES == 0).astype(
            jnp.int32))
    end = jnp.cumsum(n, dtype=jnp.int32)
    t = jnp.arange(b * num_kv, dtype=jnp.int32)
    # step t belongs to the first slot whose steps end past t: one
    # [T, b] compare, counted for the slot and summed for the step its
    # blocks began at (no search loop, no gather: nothing XLA would
    # leave un-merged across the layers)
    before = end[None, :] <= t[:, None]
    rows = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32), b - 1)
    began = jnp.sum(jnp.where(before, n[None, :], 0), axis=1)
    blocks = t - began
    if reach is not None:
        blocks = blocks + first[rows]
    return rows, jnp.clip(blocks, 0, num_kv - 1), end[-1]


def _paged_pair(off_ref, pt_ref, row_ref, blk_ref, window, block_kv,
                num_kv, reach):
    """This grid step's pair of :func:`_paged_walk`: ``(slot, block,
    the slot's offset, its first and last block, whether it is
    live)``, the walk's own rule read back from the prefetched
    scalars."""
    t = pl.program_id(0)
    row, kb = row_ref[t], blk_ref[t]
    offset = off_ref[row]
    last = jnp.minimum((offset + (window - 1)) // block_kv, num_kv - 1)
    first = _reach_first(offset, reach, block_kv)
    alive = jnp.logical_and(pt_ref[row, 0] != NULL_PAGE, last >= first)
    return row, kb, offset, first, last, alive


def _paged_kernel(off_ref, pt_ref, row_ref, blk_ref, q_ref, k_ref,
                  v_ref, *refs, sm_scale, block_kv, num_kv, window,
                  quantized, reach=None):
    """One grid step = one live (slot, block) pair of
    :func:`_paged_walk`: the math of :func:`_decode_kernel` (``window``
    1) and :func:`_verify_kernel` per pair, with the slot taken from
    the walk instead of a grid axis. A slot's blocks come in ascending
    order on consecutive steps, so its fp32 ``m / l / acc`` state
    lives in scratch from its block 0 to its last block exactly as it
    did under the ``(slot, block)`` grid.

    ``q`` and the output are rows-on-lanes (``[W, h, d, 128]`` blocks,
    slot ``i`` in lane ``i % 128`` of group ``i // 128``, resident
    while the walk stays inside the group): at a slot's first block a
    max-reduce over its one unmasked lane lifts its ``[h, d]`` query
    column(s) out (exact, the sign of a zero included) and scratch
    keeps them copied onto every lane, so a block's product reads
    whole vregs and broadcasts nothing (my chip runs, PR 26: 0.90 us a
    step against 0.98 with ``[h, d, 1]`` columns, a full server's call
    503 us against 561); at its last block the finished column goes
    into its lane of the output block. The group's first step zeroes
    the block, so a slot the walk never reaches reads zeros, not what
    the buffer held.

    With ``reach`` (a sliding window of that many keys) a slot's walk
    begins at :func:`_reach_first` instead of block 0, that block is
    where its state is initialised, and a key at or behind ``offset +
    j - reach`` is masked inside it. This is the kernel of one K/V
    head a query head; grouped-query heads take
    :func:`_paged_gqa_kernel` over the same walk.
    """
    refs = list(refs)
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    o_ref, q_scr, m_scr, l_scr, acc_scr = refs
    row, kb, offset, first, last, alive = _paged_pair(
        off_ref, pt_ref, row_ref, blk_ref, window, block_kv, num_kv,
        reach)
    mine = jax.lax.broadcasted_iota(
        jnp.int32, q_ref.shape[1:], 2) == row % LANES    # [h, d, 128]

    @pl.when(jnp.logical_and(row % LANES == 0, kb == first))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_and(alive, kb == first))
    def _first():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        # loops, not unrolled windows: unrolled, Mosaic stacks every
        # position's [h, d, 128] fp32 temporaries (kv_write, PR 24)
        def lift(j, _):
            col = jnp.max(
                jnp.where(mine, q_ref[j].astype(jnp.float32), -jnp.inf),
                axis=2, keepdims=True)                   # [h, d, 1]
            q_scr[j] = jnp.broadcast_to(col, q_scr.shape[1:])
            return _
        jax.lax.fori_loop(0, window, lift, 0)

    @pl.when(alive)
    def _block():
        k_pos = kb * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        k = k_ref[0].astype(jnp.float32)           # [h, d, bkv]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0]                      # [h, 1, bkv] bcast
            v = v * vs_ref[0]
        # a block participates when ANY window query can see it (the
        # walk's bound); per-query liveness is the mask
        for j in range(window):
            # the column on every lane: one copy per 128 keys of the
            # block, whole vregs side by side
            q = jnp.concatenate([q_scr[j]] * (block_kv // LANES), axis=2)
            live = k_pos <= offset + j
            if reach is not None:
                live = jnp.logical_and(live, k_pos > offset + j - reach)
            m_scr[j], l_scr[j], acc_scr[j] = _decode_block(
                q, k, v, live, sm_scale, m_scr[j], l_scr[j], acc_scr[j])

    @pl.when(jnp.logical_and(alive, kb == last))
    def _finish():
        def put(j, _):
            o = acc_scr[j] / jnp.maximum(l_scr[j], 1e-30)    # [h, d]
            o_ref[j] = jnp.where(mine, o[..., None].astype(o_ref.dtype),
                                 o_ref[j])
            return _
        jax.lax.fori_loop(0, window, put, 0)


def _paged_gqa_kernel(off_ref, pt_ref, row_ref, blk_ref, q_ref, k_ref,
                      v_ref, *refs, sm_scale, block_kv, num_kv, window,
                      quantized, reach=None):
    """:func:`_paged_kernel` for grouped-query heads: the same walk,
    the same state from a slot's first block to its last, the block
    math on the MXU (:func:`_decode_block`'s grouped form).

    ``q`` and the output are a block a SLOT, ``[g, W * m8, d]`` with
    ``d`` on the lanes: the rows of pooled head ``i`` are its ``m8``
    query heads (``m`` padded to whole sublane tiles) at each of the
    ``W`` window positions, which is the left operand of both
    products as it stands, so nothing is lifted out of a lane and no
    copy of the query is kept: the block is resident while the walk
    stays in the slot, fetched and written back once a slot by the
    pipeline. All ``W`` positions go through ONE pair of products a
    pooled head (a wider window only adds rows under the same weight
    load); row ``r`` of a group sits at ``offset + r // m8`` and
    masks for itself. A slot the walk never reaches has no output
    block written: the caller zeroes dead rows.

    My chip runs, PR 32 (one v5e, pages of 128 keys, bfloat16 pool,
    a block a page; the VPU form this replaced beside each): at 4
    pooled heads x 7 query heads (SmallThinker) a step costs 0.61 us
    (1.76-1.83) for 256 KB of K and V, 0.32 us at 819 GB/s; at 8 x 8
    (Solar-Open2) 0.80 us (3.12-3.17) for 512 KB, 0.64 us. By pooled
    heads a step is 0.44 / 0.50 / 0.61 / 0.80 us at 1 / 2 / 4 / 8:
    ~0.4 us of grid step and ~0.05 us a pooled head (two weight
    loads, and its bytes from 4 heads up). The rows are free: 16 or
    32 query heads a group cost 0.62 and 0.64 us a step (3.07 and
    5.83), a verify window of 5 0.65 (7.52). A live row adds 0.09 us
    (2.2: the lift and the masked put are gone), a call 13 us with
    the walk's XLA ops either way. The three-part split of the
    probabilities costs 0.01-0.02 us a step over one cast; fp32
    probabilities against a widened block 0.05-0.18 more, three
    products of one part each 0.00-0.06 more. Pages of 256 and 512
    keys (a step 2 and 4 times the bytes) run at 0.81 and 1.41 us a
    step, 79% and 91% of the bytes' floor at 4 x 7.
    """
    refs = list(refs)
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    o_ref, m_scr, l_scr, acc_scr = refs
    row, kb, offset, first, last, alive = _paged_pair(
        off_ref, pt_ref, row_ref, blk_ref, window, block_kv, num_kv,
        reach)
    g, rows, d = q_ref.shape[1:]
    h = g * rows

    @pl.when(jnp.logical_and(alive, kb == first))
    def _first():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(alive)
    def _block():
        k_pos = kb * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_kv), 1)
        q = q_ref[0]                               # [g, W * m8, d]
        k = k_ref[0].astype(q.dtype)               # [g, d, bkv]
        v = v_ref[0].astype(q.dtype)
        if quantized:
            k = k * ks_ref[0]                      # [g, 1, bkv] bcast
            v = v * vs_ref[0]
        at = offset
        if window > 1:
            # a block participates when ANY window query can see it
            # (the walk's bound); each row masks at its own position
            at = offset + jax.lax.broadcasted_iota(
                jnp.int32, (g, window, rows // window, 1), 1
            ).reshape(h, 1)
        live = k_pos <= at
        if reach is not None:
            live = jnp.logical_and(live, k_pos > at - reach)
        m_scr[...], l_scr[...], acc_scr[...] = _decode_block(
            q, k, v, live, sm_scale, m_scr[...], l_scr[...], acc_scr[...])

    @pl.when(jnp.logical_and(alive, kb == last))
    def _finish():
        o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)    # [h, d]
        o_ref[0] = o.reshape(g, rows, d).astype(o_ref.dtype)


def _paged_vmem_bytes(window, h, d, block_kv, q_item, kv_item,
                      quantized, kv_heads=None) -> int:
    """What one grid step of the paged kernels holds in VMEM; ``h``
    counts the query rows a window position (a group's heads padded
    to whole sublane tiles), ``q_item`` the bytes of the ``q``
    operand's dtype, which grouped heads multiply in.

    One K/V head a query head (:func:`_paged_kernel`): the ``q``
    block (one buffer) and the output block (two), the K and V blocks
    double-buffered (with their scale blocks), the lifted query
    columns (fp32, on all 128 lanes), and the block math's widened K
    and V, one ``[h, d, bkv]`` product and a score / prob pair per
    window position.

    Grouped (:func:`_paged_gqa_kernel`, ``kv_heads`` pooled heads):
    the K and V blocks double-buffered, the ``q`` and output blocks of
    one slot, two buffers each, the state (``m`` and ``l`` a lane
    tile wide), and three ``[h * W, bkv]`` fp32 rows of the softmax
    (scores, probabilities, their stacked parts: at one window
    position they live in registers, a wide window spills them). A
    pooled head's widened or dequantised block goes through registers
    and is not held. Within 1.1-1.6x of what the chip's compiler
    scopes at ``W`` 1, 5 and 32 (tests/test_chip_compile.py)."""
    dd = max(d, 8)
    g = kv_heads or h
    n = 4 * g * dd * block_kv * kv_item
    if quantized:
        n += 4 * g * 8 * block_kv * 4
    if g != h:
        return n + window * h * (
            4 * dd * q_item + (2 * LANES + dd + 3 * block_kv) * 4)
    n += 3 * window * h * dd * LANES * q_item
    n += window * h * dd * LANES * 4
    n += 3 * h * dd * block_kv * 4 + window * 2 * h * block_kv * 4
    return n


def flash_decode_paged(q, k, v, query_offsets, page_table, bias=None,
                       block_kv: int = DEFAULT_BLOCK_KV,
                       k_scale=None, v_scale=None, reach=None):
    """Per-row decode through a PAGED KV pool: row ``i`` of
    ``q [b, 1, h, d]`` attends to positions ``<= query_offsets[i]`` of
    its logical cache, whose physical storage is scattered across the
    global pool ``k/v [num_pages, h, d, page_size]`` according to
    ``page_table [b, max_pages]`` (int32 physical page ids;
    ``core/paging.py``).

    The launch is shaped by the live work of the tick, not by the
    server's capacity. The grid is ONE axis of dynamic extent over the
    live (slot, block) pairs (:func:`_paged_walk`, built from the
    offsets and the table by a few XLA integer ops and handed over by
    scalar prefetch with them): a slot whose row starts with
    :data:`NULL_PAGE` is dead and takes no step, a live slot takes its
    blocks up to ``(query_offsets[i] + W - 1) // block_kv`` and none
    past it — so time, and not only the bytes streamed, follows the
    cache that is filled. The K/V index map redirects logical block
    ``kb`` of row ``i`` to block ``kb % blocks_per_page`` of physical
    page ``page_table[i, kb // blocks_per_page]``; block size is the
    largest 128-aligned divisor of the page size that fits the VMEM
    budget, so a block never straddles two (physically unrelated)
    pages. With one K/V head a query head ``q`` goes in and the
    output comes out rows-on-lanes (``[W, h, d, b]`` padded to 128
    slots a group, one VMEM-resident block per group:
    :func:`_paged_kernel`) — a ``[b, h, d, W]`` operand pads its
    minor dim of ``W`` to 128 lanes in HBM. A dead row's output is
    zeros.

    ``sq > 1`` is the speculative VERIFY window: query ``j`` of row
    ``i`` sits at ``query_offsets[i] + j`` and sees keys up to there
    (the within-window causal mask of :func:`flash_decode_ragged`).

    Both of these are read off the operands, one walk and one block
    math (:func:`_decode_block`) either way. Grouped-query heads: a
    pool of ``g`` heads under ``h = g * m`` query heads (query head
    ``m * i + j`` reads K/V head ``i``); nothing is repeated in HBM,
    the ``m`` heads of a group (padded to whole sublane tiles: zero
    query rows, dropped from the output) are the rows of two products
    a pooled head a block on the MXU, the resident K and V block
    their weight, and ``q`` / the output are a ``[g, W * m8, d]``
    block a slot (:func:`_paged_gqa_kernel`). The operands multiply
    in the pool's bfloat16 when the queries are bfloat16 too and in
    fp32 otherwise (an fp32 pool, an int8 one); the probabilities
    keep fp32's 24 bits in both (:func:`_group_pv`). ``reach``: a
    sliding window of that many keys (key ``j`` visible iff ``off -
    reach < j <= off``); a row's walk then starts at the window's
    first block, which is masked inside.

    Inference-only; no bias operand (serving decode carries none —
    per-slot validity lives in the offsets and the table). Raises
    NotImplementedError where the caller must fall back to the XLA
    gather path (``ops/attention.py::_gather_kv_pages``).

    ``k_scale``/``v_scale`` (``[num_pages, h, 1, page_size]`` fp32
    scale POOLS, page-parallel with the int8 K/V pools) switch the
    kernel to its int8-KV dequant-in-kernel variant; the scale blocks
    redirect through the same page-table index map as their K/V tiles.
    """
    if jax.default_backend() != "tpu" and not _interpret():
        raise NotImplementedError("flash kernel targets TPU")
    if bias is not None:
        raise NotImplementedError(
            "flash_decode_paged takes no bias (per-slot validity is "
            "the offsets')")
    b, window, h, d = q.shape
    if window < 1:
        raise NotImplementedError("empty decode window")
    if d % 8:
        raise NotImplementedError(f"head_dim {d} unsupported")
    if k.ndim != 4 or h % k.shape[1] or k.shape[2] != d:
        raise NotImplementedError(
            f"paged pool must be [P, g, {d}, page] with g dividing "
            f"{h}, got {k.shape}")
    page, g = k.shape[3], k.shape[1]
    quantized = _check_kv_scales(k, v, k_scale, v_scale, g, page)
    q_item, rows = q.dtype.itemsize, h
    if g != h:
        # a group's query heads on whole sublane tiles, in the dtype
        # of the products
        rows = g * _group_rows(h // g)
        q_item = jnp.dtype(_group_dtype(q, k)).itemsize
    offs = jnp.asarray(query_offsets, jnp.int32)
    if offs.ndim != 1 or offs.shape[0] != b:
        raise NotImplementedError(
            f"ragged offsets must be [b={b}], got {offs.shape}")
    pt = jnp.asarray(page_table, jnp.int32)
    if pt.ndim != 2 or pt.shape[0] != b:
        raise NotImplementedError(
            f"page_table must be [b={b}, max_pages], got {pt.shape}")
    # block the PAGE, not the logical capacity: a kv block must stay
    # inside one physical page for the redirection to be a pure index
    # remap
    block_kv = _auto_block(page, block_kv, 128)

    def vmem(bkv):
        return _paged_vmem_bytes(window, rows, d, bkv, q_item,
                                 k.dtype.itemsize, quantized, g)

    while block_kv > 128 and page % (block_kv // 2) == 0 and \
            vmem(block_kv) > VMEM_DEFAULT // 2:
        block_kv //= 2
    if page % block_kv or block_kv % 128 or vmem(block_kv) > VMEM_MOST:
        raise NotImplementedError(
            f"page size {page} not tileable by {block_kv} within "
            f"VMEM budget (h={h}, d={d}, window={window})")
    smem = 4 * (b + pt.size + 2 * b * pt.shape[1] * (page // block_kv))
    if smem > SMEM_MOST:
        raise NotImplementedError(
            f"{b} slots x {pt.shape[1]} pages: the walk's {smem} bytes "
            f"of prefetched scalars do not fit SMEM")
    operands = (q, k, v, offs, pt)
    if quantized:
        operands += (k_scale, v_scale)
    return _flash_decode_paged_call(
        *operands, block_kv=block_kv,
        vmem_limit=max(vmem(block_kv) * 5 // 4, VMEM_DEFAULT),
        interpret=_interpret(), reach=reach)


def _group_rows(m: int) -> int:
    """The rows a pooled head's ``m`` query heads take: whole sublane
    tiles (zero query rows, dropped from the output)."""
    return m + -m % 8


def _group_dtype(q, k):
    """What grouped heads multiply in: the pool's bfloat16 as it
    stands when the queries are bfloat16 too, else fp32 (an fp32 pool;
    an int8 one, dequantised in VMEM)."""
    both = q.dtype == k.dtype == jnp.bfloat16
    return jnp.bfloat16 if both else jnp.float32


@functools.partial(
    jax.jit,
    static_argnames=("block_kv", "vmem_limit", "interpret", "reach"),
    inline=True)
def _flash_decode_paged_call(q, k, v, offs, pt, *scales, block_kv,
                             vmem_limit, interpret, reach=None):
    """The walk and the ``pallas_call``, jitted so that a model's
    layers trace ONE kernel per shape (the 24-layer tick lowers in 1.1
    s where 24 traces took 1.9 s), and ``inline`` with no ``name=`` on
    the call: a Mosaic call is named after the innermost scope, and
    the chip's op line has to stay ``self_attn.<n> custom-call``,
    which the benchmark's roofline shares read (a plain inner jit
    would make it ``_flash_decode_paged_call.<n>``)."""
    b, window, h, d = q.shape
    page, g = k.shape[3], k.shape[1]
    bpp = page // block_kv                     # blocks per page
    num_kv = pt.shape[1] * bpp                 # a row's capacity walk
    rows, blocks, steps = _paged_walk(offs, pt, window, block_kv,
                                      num_kv, reach)

    def kv_block(t, off, pt, rows, blocks):
        kb = blocks[t]
        return (pt[rows[t], kb // bpp], 0, 0, kb % bpp)

    def lane_group(t, off, pt, rows, blocks):
        return (0, 0, 0, rows[t] // LANES)

    def slot(t, off, pt, rows, blocks):
        return (rows[t], 0, 0, 0)

    if g != h:
        # [b, W, g * m, d] -> [b, g, W * m8, d]: a slot's block is the
        # left operand of its products
        m, m8 = h // g, _group_rows(h // g)
        qp = jnp.pad(q.reshape(b, window, g, m, d),
                     ((0, 0),) * 3 + ((0, m8 - m), (0, 0)))
        qp = qp.transpose(0, 2, 1, 3, 4).reshape(
            b, g, window * m8, d).astype(_group_dtype(q, k))
        kernel, q_spec = _paged_gqa_kernel, pl.BlockSpec(
            (1, g, window * m8, d), slot)
        out_spec = q_spec
        scratch = [pltpu.VMEM((window * g * m8, 1), jnp.float32)] * 2 + [
            pltpu.VMEM((window * g * m8, d), jnp.float32)]
    else:
        # [b, W, h, d] -> [W, h, d, b]: the slots on the lanes, whole
        # groups of 128
        qp = jnp.pad(q.transpose(1, 2, 3, 0),
                     ((0, 0),) * 3 + ((0, -b % LANES),))
        # resident while the walk stays in its group of 128 slots: one
        # buffer (a wide window's second would not fit)
        kernel, q_spec = _paged_kernel, pl.BlockSpec(
            (window, h, d, LANES), lane_group,
            pipeline_mode=pl.Buffered(1))
        out_spec = pl.BlockSpec((window, h, d, LANES), lane_group)
        scratch = [
            pltpu.VMEM((window, h, d, LANES), jnp.float32),
            pltpu.VMEM((window, h, 1), jnp.float32),
            pltpu.VMEM((window, h, 1), jnp.float32),
            pltpu.VMEM((window, h, d), jnp.float32),
        ]
    in_specs = [
        q_spec,
        pl.BlockSpec((1, g, d, block_kv), kv_block),
        pl.BlockSpec((1, g, d, block_kv), kv_block),
    ]
    # scale pools redirect through the SAME page-table index map as
    # their K/V tiles (d axis collapsed to 1)
    in_specs += [pl.BlockSpec((1, g, 1, block_kv), kv_block)
                 for _ in scales]
    out = pl.pallas_call(
        functools.partial(kernel, sm_scale=d ** -0.5,
                          block_kv=block_kv, num_kv=num_kv,
                          window=window, quantized=bool(scales),
                          reach=reach),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        out_shape=_sds(qp.shape, qp.dtype, q),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(offs, pt, rows, blocks, qp, k, v, *scales)
    if g != h:
        # a slot the walk never reached has no block written: zeros;
        # [b, g, W * m8, d] -> [b, W, g * m, d]
        out = jnp.where((pt[:, 0] != NULL_PAGE)[:, None, None, None],
                        out, 0).astype(q.dtype)
        return out.reshape(b, g, window, m8, d)[:, :, :, :m].transpose(
            0, 2, 1, 3, 4).reshape(b, window, h, d)
    # [W, h, d, b] -> [b, W, h, d]
    return out[..., :b].transpose(3, 0, 1, 2)
