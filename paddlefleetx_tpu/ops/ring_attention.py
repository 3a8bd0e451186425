"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context scaling beyond the reference (SURVEY §5.7: the reference
has no ring/blockwise/context parallelism — its only sequence scaling
is Megatron-SP, which still materializes full attention per rank and
tops out at ~1k tokens). Here each device holds a sequence block; KV
blocks rotate around the ``cp`` mesh axis with ``jax.lax.ppermute``
(one ICI-neighbor hop per step — compute on the current block overlaps
the transfer of the next) while a streaming log-sum-exp accumulator
(the flash-attention recurrence) combines per-block partial outputs
into the *exact* softmax result. Peak memory per device is
O(s/N * s/N) score blocks instead of O(s * s).

Layout: ``[b, s/N, h, d]`` per device, batch over dp x fsdp, heads
over mp, sequence over cp — composes with every other axis.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..observability import metrics

_shard_map = jax.shard_map
_axis_size = jax.lax.axis_size


class MeshIndivisible(NotImplementedError):
    """An operand dim does not divide the mesh axes it is sharded by
    (e.g. the batch-1 abstract-init sample under fsdp=2)."""


def kernel_mesh():
    """The active mesh when it spans more than one device, else None.

    Mosaic kernels cannot be partitioned by GSPMD: a ``pallas_call``
    whose operands are sharded over a multi-device mesh fails at
    lowering on real chips (interpret mode lowers to plain HLO and
    hides it). Call sites ask this at trace time and either wrap the
    kernel with :func:`shard_kernel` or take their counted XLA path."""
    from ..parallel.mesh import get_mesh
    mesh = get_mesh()
    return mesh if mesh is not None and mesh.devices.size > 1 else None


def require_one_device(kernel: str) -> None:
    """Admission check of the kernels that are NOT wrapped with
    :func:`shard_kernel` yet (int8 GEMM, grouped LoRA): under a
    multi-device mesh they raise like any other kernel rejection, so
    the site takes its counted XLA path instead of reaching Mosaic's
    lowering error past the site's try/except."""
    if kernel_mesh() is not None:
        raise NotImplementedError(
            f"{kernel} is not shard_map-wrapped: Mosaic cannot "
            f"partition it over a multi-device mesh")


def shard_kernel(fn, args, in_axes, out_axes):
    """``fn(*args)`` with every device running the (Pallas) call on
    its own block of the operands.

    ``in_axes`` (one tuple per operand) and ``out_axes`` (the single
    output's) name each dim by its LOGICAL axis
    (``parallel/sharding.py`` rule table: "batch" -> dp x fsdp,
    "act_heads" -> mp, "act_expert" -> the ep plane, ...), so the
    blocks are exactly what the surrounding GSPMD program already
    holds per device and nothing is gathered to feed the kernel. With
    no mesh or a one-device mesh the call is made directly (same HLO
    as without this helper). Raises :class:`MeshIndivisible` (a
    ``NotImplementedError``) when a sharded dim does not divide its
    mesh axes — callers fall back to their XLA path, like any other
    kernel rejection."""
    mesh = kernel_mesh()
    if mesh is None:
        return fn(*args)
    import flax.linen as nn

    def spec(axes):
        return nn.logical_to_mesh_axes(tuple(axes))

    in_specs = tuple(spec(a) for a in in_axes)
    for x, sp in zip(args, in_specs):
        for dim, axes in zip(x.shape, sp):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            if dim % n:
                raise MeshIndivisible(
                    f"operand {x.shape} does not divide mesh axes "
                    f"{axes} (x{n}) for a per-device kernel call")
    # vma checking off: the specs are exact by construction, and the
    # Pallas interpreter (CPU tests) cannot type its scalar-prefetch
    # slices under the checker
    return _shard_map(fn, mesh=mesh, in_specs=in_specs,
                      out_specs=spec(out_axes), check_vma=False)(*args)


NEG_INF = -1e30


def _block_attn(q, k, v, scale, causal, q_start, k_start):
    """Scores + masked row-max/row-sum for one (q-block, kv-block)
    pair; returns (out_block, row_max, row_sum) in fp32."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_start + jnp.arange(sq)[:, None]
        k_pos = k_start + jnp.arange(sk)[None, :]
        scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                          # [b,h,q]
    # rows with no visible key (fully masked) must not produce
    # exp(NEG_INF - NEG_INF) = 1 garbage
    safe_m = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)                               # noqa: E741
    out = jnp.einsum("bhqk,bkhd->bqhd", p,
                     v.astype(jnp.float32))
    return out, m, l


def _ring_flash(q, k, v, axis_name, causal, scale):
    """Ring with the Pallas flash kernel on each local block.

    Ring blocks are aligned and equal-sized, so every (q-block,
    kv-block) pair is exactly one of: the diagonal (``src == idx`` —
    plain causal flash), fully visible (``src < idx`` — non-causal
    flash), or fully masked (dead). No masked-offset arithmetic ever
    reaches the kernel. Per-block results merge through the logsumexp
    the kernel already returns — the same streaming combination the
    kernel itself performs across its internal KV blocks, lifted one
    level up the memory hierarchy (VMEM blocks -> ring neighbors).
    """
    from .pallas.flash_attention import flash_attention_with_lse

    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    # admission is hoisted: ring_attention only selects this path after
    # _flash_block_ok proved the block shape admissible, and the merge
    # needs the kernel's raw lse — no per-block try/fallback possible
    def blk_diag(kv):
        return flash_attention_with_lse(  # pfxlint: disable=PFX205
            q, kv[0], kv[1], causal=True, sm_scale=scale)

    def blk_full(kv):
        return flash_attention_with_lse(  # pfxlint: disable=PFX205
            q, kv[0], kv[1], causal=False, sm_scale=scale)

    def blk_dead(kv):
        # constants must carry q's device-varying type or the cond
        # branches disagree under shard_map's vma checker
        zq = jnp.sum(q.astype(jnp.float32)) * 0.0
        return (jnp.zeros((b, sq, h, d), q.dtype) + zq.astype(q.dtype),
                jnp.full((b, h, sq), NEG_INF, jnp.float32) + zq)

    def step(carry, i):
        """One ring hop: flash the resident KV block (diag/full/dead
        by ring position), merge via logsumexp, rotate KV."""
        k_blk, v_blk, out, lse = carry
        src = (idx - i) % n
        if causal:
            blk_out, blk_lse = jax.lax.cond(
                src == idx, blk_diag,
                lambda kv: jax.lax.cond(src < idx, blk_full, blk_dead,
                                        kv),
                (k_blk, v_blk))
        else:
            blk_out, blk_lse = blk_full((k_blk, v_blk))
        new_lse = jnp.logaddexp(lse, blk_lse)
        dead = new_lse <= NEG_INF / 2
        alpha = jnp.where(dead, 0.0, jnp.exp(lse - new_lse))
        beta = jnp.where(dead, 0.0, jnp.exp(blk_lse - new_lse))
        out = out * alpha[..., None].swapaxes(1, 2) + \
            blk_out.astype(jnp.float32) * beta[..., None].swapaxes(1, 2)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, out, new_lse), None

    zero_q = jnp.sum(q.astype(jnp.float32)) * 0.0
    out0 = jnp.zeros((b, sq, h, d), jnp.float32) + zero_q
    lse0 = jnp.full((b, h, sq), NEG_INF, jnp.float32) + zero_q
    (_, _, out, _), _ = jax.lax.scan(step, (k, v, out0, lse0),
                                     jnp.arange(n))
    return out.astype(q.dtype)


def _flash_block_ok(sq, d) -> bool:
    from .pallas.flash_attention import check_shapes
    try:
        check_shapes(sq, sq, d)
        return True
    except NotImplementedError:
        return False


@partial(jax.jit,
         static_argnames=("axis_name", "causal", "scale", "use_flash"))
def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   *, axis_name: str, causal: bool = True,
                   scale: Optional[float] = None,
                   use_flash: Optional[bool] = None) -> jax.Array:
    """Exact attention with KV blocks rotating over ``axis_name``.

    Call under ``shard_map`` (or use :func:`ring_attention_sharded`):
    arguments are the per-device blocks ``[b, s_local, h, d]``.

    ``use_flash=None`` auto-selects the Pallas per-block kernel on real
    TPU backends when the block shapes allow (never materializing the
    ``[b, h, s/N, s/N]`` score blocks the dense path builds); pass
    ``True``/``False`` to force either path.
    """
    b, sq, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    if use_flash is None:
        use_flash = (jax.default_backend() == "tpu"
                     and _flash_block_ok(sq, d))
    metrics.inc("attention/ring/flash" if use_flash
                else "attention/ring/dense")
    if use_flash:
        return _ring_flash(q, k, v, axis_name, causal, scale)

    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    q_start = idx * sq

    perm = [(i, (i + 1) % n) for i in range(n)]  # send KV to the right

    def step(carry, i):
        """One ring hop of the dense path: streaming-softmax merge of
        the resident KV block, then rotate KV."""
        k_blk, v_blk, out, m, l = carry  # noqa: E741
        # after i rotations, this device holds the KV block that
        # originated at ring position idx - i
        src = (idx - i) % n
        blk_out, blk_m, blk_l = _block_attn(
            q, k_blk, v_blk, scale, causal, q_start, src * sq)
        new_m = jnp.maximum(m, blk_m)
        # renormalize both accumulators onto the new running max
        safe = lambda x: jnp.where(  # noqa: E731
            new_m <= NEG_INF / 2, 0.0, x)
        alpha = jnp.exp(safe(m - new_m))
        beta = jnp.exp(safe(blk_m - new_m))
        out = out * alpha[..., None].swapaxes(1, 2) + \
            blk_out * beta[..., None].swapaxes(1, 2)
        l = l * alpha + blk_l * beta  # noqa: E741
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, out, new_m, l), None

    # fresh accumulators must carry the same device-varying type as
    # the loop outputs under shard_map; deriving them from q (a
    # varying input) gives them that type on any jax version
    zero_q = jnp.sum(q.astype(jnp.float32)) * 0.0
    out0 = jnp.zeros((b, sq, h, d), jnp.float32) + zero_q
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32) + zero_q
    l0 = jnp.zeros((b, h, sq), jnp.float32) + zero_q
    (_, _, out, _, l), _ = jax.lax.scan(
        step, (k, v, out0, m0, l0), jnp.arange(n))
    out = out / jnp.maximum(l, 1e-30)[..., None].swapaxes(1, 2)
    return out.astype(q.dtype)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh, *, axis_name: str = None,
                           batch_axes=None, heads_axis: str = None,
                           causal: bool = True,
                           use_flash: Optional[bool] = None) -> jax.Array:
    """A ``shard_map`` wrapper: global ``[b, s, h, d]`` -> global attention
    output, with s sharded over ``axis_name`` and the ring running
    inside. Axis defaults come from the mesh convention
    (``parallel/mesh.py``), not re-spelled strings."""
    from ..parallel.mesh import CP_AXIS, DATA_AXES, MP_AXIS, PP_AXIS
    axis_name = axis_name or CP_AXIS
    batch_axes = batch_axes or DATA_AXES
    heads_axis = heads_axis or MP_AXIS
    if use_flash is None:
        s_local = q.shape[1] // mesh.shape[axis_name]
        use_flash = (jax.default_backend() == "tpu"
                     and _flash_block_ok(s_local, q.shape[-1]))
    spec = P(batch_axes, axis_name, heads_axis, None)
    fn = partial(ring_attention, axis_name=axis_name, causal=causal,
                 use_flash=use_flash)
    # under pp > 1 this call sits inside the pipeline's stage vmap,
    # which names the pp axis (parallel/pipeline.py::_slot_vmap): its
    # batching rule replays the ring body with pp added to the varying
    # axes of the batched operands only, which the vma checker cannot
    # type (JAX's own message names check_vma=False as the way out).
    # The ring is type-checked wherever pp == 1.
    return _shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec,
                      check_vma=mesh.shape.get(PP_AXIS, 1) == 1)(q, k, v)
