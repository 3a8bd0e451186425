"""TPU-native GPT: one sharding-annotated flax model for every topology.

The reference maintains three GPT implementations — single-card
(``gpt/dygraph/single_model.py``), hybrid TP/PP/SP
(``gpt/dygraph/hybrid_model.py``) and auto-parallel
(``gpt/auto/auto_model.py``). Under GSPMD one definition covers all of
them: parameters and activations carry *logical* axis names
(``parallel/sharding.py``) and the partitioner inserts the collectives
the hybrid model wrote by hand (ColumnParallelLinear all-reduces,
sequence-parallel all-gather/reduce-scatter, vocab-parallel logits).

Architecture parity (reference ``single_model.py``):
  - learned word + position embeddings, dropout (:435-473)
  - pre-LayerNorm decoder blocks, eps 1e-5, tanh-approx GELU (:340-427)
  - fused QKV projection option (:86-87), causal fused-mask softmax
    (:198), attention-prob dropout
  - final LayerNorm (:278-279); logits tied to the word embedding
    (:608-611); masked cross-entropy criterion (:619-653)

TPU-first choices: batch-major ``[b, s, h]`` activations; compute in
bf16 with fp32 params/softmax; ``nn.scan`` over layers (one compiled
block, weights stacked on a ``layers`` axis — compile time independent
of depth); ``jax.checkpoint`` policies reproduce the reference's
recompute granularities full / full_attn / core_attn
(``hybrid_model.py:406-408,537-539,332-333``).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ...ops.attention import dot_product_attention, kv_cache_write
from ...parallel.sharding import with_logical_constraint
from ..language_utils import chunked_nll_sums, masked_nll_sums
from .config import GPTConfig

Dtype = Any


def _dense_init(cfg: GPTConfig):
    return nn.initializers.normal(stddev=cfg.initializer_range)


class _CollectiveDense(nn.Module):
    """The four mp linears of a decoder layer (qkv, out-proj, fc1,
    fc2): ``nn.DenseGeneral``'s parameters and product, with the
    product dispatched to the overlapped mp rings
    (``ops/collective_matmul.py``) wherever they are viable. Nothing
    asks for the rings: a sequence-parallel layer on a live mesh with
    mp >= 2 takes them at every site whose shapes
    :func:`mp_ring_viable` admits.

    Parameters are created exactly as ``nn.DenseGeneral`` creates
    them — same names ("kernel"/"bias"), shapes, logical axes and
    init streams — so checkpoints and the abstract-init parameter
    tree do not depend on whether a given call takes the rings (the
    engine's batch-1 abstract-init sample never does). Only the
    compute dispatches:

    - ``mode="column"`` ("embed" contraction, qkv / fc1):
      :func:`all_gather_matmul` — x arrives sequence-sharded
      (Megatron-SP layout), output feature-sharded over mp.
    - ``mode="row"`` (mp-sharded contraction, out-proj / fc2):
      :func:`matmul_reduce_scatter` — output arrives sequence-sharded.

    The fallback is the DenseGeneral ``dot_general`` + bias with the
    usual GSPMD lowering — numerically identical (the dispatch matrix
    lives in docs/tensor_parallel.md; conditions pinned by
    tests/test_collective_matmul.py).
    """
    config: GPTConfig
    features: Tuple[int, ...]
    kernel_axes: Tuple[Optional[str], ...]
    mode: str                       # "column" | "row"
    contract_ndim: int = 1

    @nn.compact
    def __call__(self, x):
        from flax.linen.dtypes import promote_dtype
        cfg = self.config
        cn = self.contract_ndim
        kshape = tuple(x.shape[-cn:]) + tuple(self.features)
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(_dense_init(cfg),
                                         self.kernel_axes),
            kshape, jnp.dtype(cfg.param_dtype))
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(),
                                         self.kernel_axes[cn:]),
            tuple(self.features), jnp.dtype(cfg.param_dtype))
        x, kernel, bias = promote_dtype(x, kernel, bias,
                                        dtype=jnp.dtype(cfg.dtype))

        from ...parallel.mesh import MP_AXIS, get_mesh
        mesh = get_mesh() if cfg.sequence_parallel else None
        y = None
        if mesh is not None and mesh.shape.get(MP_AXIS, 1) >= 2:
            from ...ops.collective_matmul import (
                all_gather_matmul, matmul_reduce_scatter, mp_ring_viable,
            )
            from ...parallel.sharding import MP_WEIGHT_AXES
            from ...observability import metrics
            if self.mode == "column":
                shard_idx = next(
                    (i for i, a in enumerate(self.kernel_axes[cn:])
                     if a in MP_WEIGHT_AXES), None)
                if shard_idx is not None and cn == 1 and x.ndim == 3 \
                        and mp_ring_viable(
                            mesh, x.shape[0], x.shape[1],
                            (self.features[shard_idx],)):
                    y = all_gather_matmul(x, kernel, mesh,
                                          w_shard_dim=shard_idx)
            elif self.kernel_axes[0] in MP_WEIGHT_AXES \
                    and x.ndim == 2 + cn and mp_ring_viable(
                        mesh, x.shape[0], x.shape[1], (kshape[0],)):
                y = matmul_reduce_scatter(x, kernel, mesh,
                                          contract_ndim=cn)
            # a sequence-parallel mp site that fell off the ring
            # conditions (docs/tensor_parallel.md) is counted, so a
            # "rings expected but silently all-GSPMD" run is visible
            # (not the batch-1 init sample, whose product never runs)
            if y is not None:
                metrics.inc("mp_linear/rings")
            elif not self.is_initializing():
                metrics.inc("mp_linear/gspmd_fallback")
        if y is None:
            y = jax.lax.dot_general(
                x, kernel,
                ((tuple(range(x.ndim - cn, x.ndim)), tuple(range(cn))),
                 ((), ())))
        # nn.DenseGeneral's own bias add, op for op
        return y + jnp.reshape(
            bias, (1,) * (y.ndim - len(self.features)) + bias.shape)


class _QuantDense(nn.Module):
    """Weight-only int8 twin of the DenseGeneral/_CollectiveDense call
    sites (``quant_execution="weight_only_int8"``,
    docs/quantization.md).

    Parameter contract: ``kernel`` keeps the fp sites' name, shape and
    logical axes but stores int8 — the frozen PTQ artifact
    ``core/quantize.py`` emits; ``kernel_scale`` is its fp32
    per-output-channel dequant scale (shape = the kernel's output
    dims, axes = the kernel axes past the contraction); ``bias`` is
    unchanged. A fresh ``init()`` therefore yields zero weights and
    unit scales — real values come from quantizing a trained
    checkpoint (scripts/quantize_checkpoint.py), and the abstract
    tree this init builds is exactly what the quantized checkpoint
    restores into.

    Dispatch: flatten the site to ``[M, K] @ [K, N]``, try the Pallas
    weight-only GEMM (``quant/matmul``), fall back PER SITE to the
    XLA dequantize-then-dot (``quant/fallback/kernel_rejected``) —
    the same per-site contract as the attention/moe/mp_linear
    families. This module replaces ``_CollectiveDense`` at the shared
    sites: the rings stream fp weight chunks and cannot consume
    frozen int8 kernels, so quantization wins (dispatch matrix in
    docs/quantization.md).
    """
    config: GPTConfig
    features: Tuple[int, ...]
    kernel_axes: Tuple[Optional[str], ...]
    contract_ndim: int = 1

    @nn.compact
    def __call__(self, x):
        from ...observability import metrics
        cfg = self.config
        cn = self.contract_ndim
        kshape = tuple(x.shape[-cn:]) + tuple(self.features)
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(nn.initializers.zeros_init(),
                                         self.kernel_axes),
            kshape, jnp.int8)
        scale = self.param(
            "kernel_scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(),
                                         self.kernel_axes[cn:]),
            tuple(self.features), jnp.float32)
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(),
                                         self.kernel_axes[cn:]),
            tuple(self.features), jnp.dtype(cfg.param_dtype))
        dtype = jnp.dtype(cfg.dtype)
        x = x.astype(dtype)
        k_dim = int(np.prod(kshape[:cn]))
        n_dim = int(np.prod(self.features))
        x2 = x.reshape(-1, k_dim)
        w2 = kernel.reshape(k_dim, n_dim)
        s = scale.reshape(n_dim)
        try:
            from ...ops.pallas.quantized_matmul import quantized_matmul
            from ...ops.ring_attention import require_one_device
            require_one_device("quantized_matmul")
            y = quantized_matmul(x2, w2, s)
            metrics.inc("quant/matmul")
        except (ImportError, NotImplementedError):
            # XLA dequantize-then-dot: numerically the kernel's oracle
            # (same int8 grid, scale applied outside the contraction)
            metrics.inc("quant/fallback/kernel_rejected")
            w_deq = (w2.astype(jnp.float32) * s[None, :]).astype(dtype)
            y = jax.lax.dot_general(x2, w_deq, (((1,), (0,)), ((), ())))
        y = y.reshape(x.shape[:-cn] + tuple(self.features))
        return y + bias.astype(dtype)


class _LoRADelta(nn.Module):
    """Stacked multi-adapter LoRA delta for one dense site
    (``lora_rank > 0``, docs/lora.md).

    Parameter contract — the additive twin of the ``_CollectiveDense``
    convention: the base site's ``kernel``/``bias`` (and the
    int8 ``kernel_scale``) are created by the base modules exactly as
    ever, so knob-off is param-tree-identical; this module adds ONLY
    the sibling pair ``lora_a [A, K, r]`` (normal init) / ``lora_b
    [A, r, N]`` (zero init — a fresh bank is a zero delta for every
    adapter). A = ``lora_num_adapters`` resident bank rows; row 0 is
    the reserved zero adapter and is masked structurally, so adapter
    id 0 reproduces the base model token-exactly whatever the bank
    holds. Adapter checkpoints save exactly these ``*_lora`` subtrees
    (core/checkpoint.py ``save_adapter``), base weights absent.

    Compute dispatch mirrors ``_QuantDense``: flatten the site to
    ``[M, K]`` rows keyed by per-row adapter ids, try the grouped
    Pallas GEMM pair (sort by id → scalar-prefetched group boundaries
    → grouped A/B GEMMs — adapters instead of experts; counted
    ``lora/grouped``), fall back PER SITE to the XLA gather-einsum
    form (``lora/fallback``). ``adapter_ids=None`` (training the base
    model, abstract init, export) skips the compute entirely and
    returns a zero delta — the params still materialize so the tree
    shape never depends on the call.
    """
    config: GPTConfig
    features: Tuple[int, ...]
    contract_ndim: int = 1

    @nn.compact
    def __call__(self, x, adapter_ids=None):
        from ...observability import metrics
        cfg = self.config
        cn = self.contract_ndim
        num_adapters, rank = cfg.lora_num_adapters, cfg.lora_rank
        k_dim = int(np.prod(x.shape[-cn:]))
        n_dim = int(np.prod(self.features))
        lora_a = self.param(
            "lora_a",
            nn.with_logical_partitioning(
                _dense_init(cfg), ("adapters", "lora_in", "lora_rank")),
            (num_adapters, k_dim, rank), jnp.dtype(cfg.param_dtype))
        lora_b = self.param(
            "lora_b",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init(),
                ("adapters", "lora_rank", "lora_out")),
            (num_adapters, rank, n_dim), jnp.dtype(cfg.param_dtype))
        out_shape = x.shape[:-cn] + tuple(self.features)
        if adapter_ids is None:
            return jnp.zeros(out_shape, x.dtype)
        dtype = jnp.dtype(cfg.dtype)
        x2 = x.astype(dtype).reshape(-1, k_dim)        # [M, K]
        # one id per leading batch row, repeated over the flattened
        # row-major positions (M = batch * seq)
        ids = jnp.repeat(jnp.asarray(adapter_ids, jnp.int32),
                         x2.shape[0] // x.shape[0])
        live = ids != 0
        x2 = jnp.where(live[:, None], x2, 0)
        a = lora_a.astype(dtype)
        b = lora_b.astype(dtype)
        try:
            from ...ops.lora import grouped_lora_delta
            from ...ops.ring_attention import require_one_device
            require_one_device("grouped_lora_delta")
            d = grouped_lora_delta(x2, ids, a, b)
            metrics.inc("lora/grouped")
        except (ImportError, NotImplementedError):
            from ...ops.lora import fallback_lora_delta
            metrics.inc("lora/fallback")
            d = fallback_lora_delta(x2, ids, a, b)
        d = d * jnp.asarray(cfg.lora_scale, dtype)
        d = jnp.where(live[:, None], d, 0)
        return d.reshape(out_shape).astype(x.dtype)


def _quantize_kv(t):
    """Symmetric per-(row, token, head) abs-max int8 quantization of a
    ``[b, W, h, d]`` K/V tensor: ``(int8 values, [b, W, h, 1] fp32
    scales)``. Per-token scales keep every cache write independent —
    a page- or slot-granular scale would force requantizing already
    written positions on each incremental decode write. The scale is
    clamped away from zero so all-zero rows round-trip exactly."""
    f = t.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=-1, keepdims=True)
    sc = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(f / sc), -127, 127).astype(jnp.int8)
    return q, sc


def _window_positions(lengths, window: int, capacity: int):
    """``[b, W]`` cache positions of a decode write: row ``i``'s ``W``
    new tokens sit at ``lengths[i] .. lengths[i] + W - 1``, clipped
    into the cache."""
    return jnp.clip(
        jnp.asarray(lengths, jnp.int32)[:, None]
        + jnp.arange(window, dtype=jnp.int32)[None, :], 0,
        capacity - 1)


def _remat_policy(granularity: str):
    """Map reference recompute granularities onto checkpoint policies.

    ``full`` recomputes the whole block; ``full_attn`` saves everything
    except attention internals (tagged "attn"/"core_attn"); ``core_attn``
    saves everything except the softmax(QK)V internals ("core_attn").
    """
    cp = jax.checkpoint_policies
    if granularity == "full":
        return None  # nothing saveable
    if granularity == "full_attn":
        return cp.save_anything_except_these_names("attn", "core_attn")
    if granularity == "core_attn":
        return cp.save_anything_except_these_names("core_attn")
    if granularity == "save_dots":
        # TPU-native granularity (no reference analogue): keep only the
        # named matmul outputs — qkv/core-attn ("attn"), out_proj
        # ("attn_out"), both MLP projections ("mlp1"/"mlp2") — and
        # recompute the elementwise rest (norms, gelu, residuals) in
        # backward. Near-zero recompute FLOPs at a fraction of
        # full_attn's residency: the middle ground the 16G v5e needs
        # between "full" (33% FLOP overhead) and policies that OOM.
        return cp.save_only_these_names("attn", "attn_out", "mlp1",
                                        "mlp2")
    raise ValueError(granularity)


class MultiHeadAttention(nn.Module):
    """Self-attention with fused QKV and a fixed-capacity decode cache.

    The reference grows its KV cache by concatenation
    (``single_model.py:179-184``), which would retrace under jit; here
    the cache is a preallocated ``[b, max_len, h, d]`` buffer updated
    with ``dynamic_update_slice`` — the dy2static-friendly design the
    reference approximates in ``hybrid_model.py:1322-1347``.
    """
    config: GPTConfig

    @nn.compact
    def __call__(self, x, attn_bias=None, use_cache: bool = False,
                 deterministic: bool = True, cache_lengths=None,
                 page_table=None, chunk_start=None, adapter_ids=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        h, nh, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
        dense = lambda feats, name, axes: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, name=name, dtype=dtype,
            param_dtype=jnp.dtype(cfg.param_dtype),
            kernel_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed",) + axes),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros_init(), axes))

        quant = cfg.quant_execution == "weight_only_int8"
        if cfg.fuse_attn_qkv:
            if quant:
                # quantization wins over the rings at shared sites
                # (docs/quantization.md matrix)
                qkv = _QuantDense(
                    cfg, features=(3, nh, hd),
                    kernel_axes=("embed", None, "heads", "kv"),
                    name="qkv_proj")(x)
            else:
                qkv = _CollectiveDense(
                    cfg, features=(3, nh, hd),
                    kernel_axes=("embed", None, "heads", "kv"),
                    mode="column", name="qkv_proj")(x)
            if cfg.lora_rank:
                qkv = qkv + _LoRADelta(
                    cfg, features=(3, nh, hd),
                    name="qkv_proj_lora")(x, adapter_ids)
            q, k, v = (qkv[..., i, :, :] for i in range(3))
        elif quant:
            q = _QuantDense(cfg, features=(nh, hd),
                            kernel_axes=("embed", "heads", "kv"),
                            name="q_proj")(x)
            k = _QuantDense(cfg, features=(nh, hd),
                            kernel_axes=("embed", "heads", "kv"),
                            name="k_proj")(x)
            v = _QuantDense(cfg, features=(nh, hd),
                            kernel_axes=("embed", "heads", "kv"),
                            name="v_proj")(x)
        else:
            # non-fused qkv stays on the plain GSPMD path: three
            # narrow column projections are not worth three rings
            # (docs/tensor_parallel.md fallback matrix)
            q = dense((nh, hd), "q_proj", ("heads", "kv"))(x)
            k = dense((nh, hd), "k_proj", ("heads", "kv"))(x)
            v = dense((nh, hd), "v_proj", ("heads", "kv"))(x)
        q = checkpoint_name(q, "attn")
        k = checkpoint_name(k, "attn")
        v = checkpoint_name(v, "attn")
        q, k, v = (with_logical_constraint(
            t, ("batch", None, "act_heads", None)) for t in (q, k, v))

        query_offset = 0
        kv_cache_layout = False
        page_table_arg = None
        k_scale = v_scale = None
        # int8 KV cache (kv_cache_dtype="int8", docs/quantization.md):
        # values quantize per (row, token, head) on the way into the
        # cache; fp32 scales live in rank-4 lookalike variables whose
        # feature axis is a dummy 1 ([b, h, 1, S] / [P, h, 1, page]) so
        # every write expression, page gather and slot helper
        # (generation.py) applies to scales exactly as to values.
        kv_int8 = cfg.kv_cache_dtype == "int8"
        if use_cache and page_table is not None:
            # Paged KV (core/paging.py): the cache variables hold the
            # GLOBAL page pool [kv_pool_pages, h, d, kv_page_size] —
            # one pool shared by every slot — and each batch row
            # reaches its tokens through its page_table row (logical
            # page j of row i lives in physical page page_table[i, j]).
            # Page layout keeps the [h, d, S-minor] tiling of the
            # contiguous cache, just cut into kv_page_size columns.
            # Two write modes:
            #   - ragged decode (cache_lengths): one token per row at
            #     that row's position (a window of them in a verify
            #     tick) — look up the physical page of
            #     position//page_size and write the column at
            #     position%page_size in place (kv_cache_write).
            #     Inactive slots' page-table rows are all NULL_PAGE,
            #     so their dead writes land in the reserved garbage
            #     page.
            #   - chunked prefill (chunk_start): the chunk is
            #     page-aligned and spans whole pages, so the fresh
            #     chunk KV drops straight into its physical pages with
            #     one scatter — no gather/modify/scatter round trip.
            # Reads go through ops/attention.py's page_table
            # indirection (flash_decode_paged walks the table via
            # scalar prefetch; the dense fallback gathers).
            page = cfg.kv_page_size
            if not page or not cfg.kv_pool_pages:
                raise ValueError(
                    "page_table passed but kv_page_size/kv_pool_pages "
                    "are not configured (GPTConfig)")
            cache_k = self.variable(
                "cache", "cached_key", jnp.zeros,
                (cfg.kv_pool_pages, nh, hd, page),
                jnp.int8 if kv_int8 else dtype)
            cache_v = self.variable(
                "cache", "cached_value", jnp.zeros,
                (cfg.kv_pool_pages, nh, hd, page),
                jnp.int8 if kv_int8 else dtype)
            writes = [(cache_k, k), (cache_v, v)]
            if kv_int8:
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                cache_ks = self.variable(
                    "cache", "cached_key_scale", jnp.zeros,
                    (cfg.kv_pool_pages, nh, 1, page), jnp.float32)
                cache_vs = self.variable(
                    "cache", "cached_value_scale", jnp.zeros,
                    (cfg.kv_pool_pages, nh, 1, page), jnp.float32)
                writes = [(cache_k, kq), (cache_v, vq),
                          (cache_ks, ks), (cache_vs, vs)]
            pt = jnp.asarray(page_table, jnp.int32)
            if cache_lengths is not None:
                # row i's W tokens (1 per decode tick, k+1 in a
                # speculative verify window) land at positions
                # lengths[i] .. lengths[i] + W - 1, each resolved
                # through the page table (the server pre-maps/COWs
                # every page the window touches —
                # _page_maintenance(window)). Positions clipped at
                # capacity land in the last column, which is never
                # read before eviction (commit clamp). The value is
                # k/v's native [b, W, h, d]; kv_cache_write rewrites
                # only the pages written and leaves the pool in the
                # layout flash_decode_paged reads.
                wpos = _window_positions(cache_lengths, x.shape[1],
                                         cfg.cache_capacity)
                pid = jnp.take_along_axis(pt, wpos // page, axis=1)
                written = kv_cache_write(
                    [(var.value, t) for var, t in writes], pid,
                    wpos % page, use_flash=cfg.use_flash_attention)
                for (var, _), leaf in zip(writes, written):
                    var.value = leaf
                query_offset = wpos[:, 0]               # [b]
            elif chunk_start is not None:
                c = x.shape[1]
                if c % page:
                    raise ValueError(
                        f"chunked prefill length {c} must be a "
                        f"multiple of kv_page_size {page}")
                cp = c // page
                c0 = jnp.asarray(chunk_start, jnp.int32)
                pids = jnp.take_along_axis(
                    pt, (c0 // page)[:, None] +
                    jnp.arange(cp, dtype=jnp.int32)[None, :], axis=1)
                # [b, h, dd, c] -> [b, cp, h, dd, page] page-major
                # blocks (dd = head_dim for values, 1 for scales)
                def chunk_kv(t):
                    tt = t.transpose(0, 2, 3, 1)
                    return tt.reshape(
                        x.shape[0], nh, tt.shape[2], cp,
                        page).transpose(0, 3, 1, 2, 4)
                for var, t in writes:
                    var.value = var.value.at[pids].set(chunk_kv(t))
                query_offset = c0                       # [b]
            else:
                raise ValueError(
                    "page_table requires cache_lengths (ragged decode)"
                    " or chunk_start (chunked prefill)")
            k, v = cache_k.value, cache_v.value
            if kv_int8:
                k_scale, v_scale = cache_ks.value, cache_vs.value
            kv_cache_layout = True
            page_table_arg = pt
        elif use_cache:
            # Decode: roll the new keys/values into the preallocated
            # cache. Capacity is cache_capacity (max_position_embeddings
            # rounded up to a 128 multiple so the minor dim always
            # tiles — config.py); the caller (generation loop / serving
            # server) must bound prompt+decode length by
            # max_position_embeddings — dynamic_update_slice clamps
            # rather than raises on overrun.
            # Layout [b, h, d, S]: the minor tile dims (d, S) =
            # (64, capacity) fill TPU (8,128) tiles exactly. The
            # alternatives both waste 2x HBM to lane padding (any
            # layout with d=64 minor) — measured: the padded cache
            # additionally provokes XLA into per-step compress/
            # uncompress copies of the whole stacked cache, which OOMs
            # at batch 64. As a bonus k arrives pre-transposed for the
            # q @ k^T decode matmul.
            capacity = cfg.cache_capacity
            cache_k = self.variable(
                "cache", "cached_key", jnp.zeros,
                (x.shape[0], nh, hd, capacity),
                jnp.int8 if kv_int8 else dtype)
            cache_v = self.variable(
                "cache", "cached_value", jnp.zeros,
                (x.shape[0], nh, hd, capacity),
                jnp.int8 if kv_int8 else dtype)
            writes = [(cache_k, k), (cache_v, v)]
            if kv_int8:
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                cache_ks = self.variable(
                    "cache", "cached_key_scale", jnp.zeros,
                    (x.shape[0], nh, 1, capacity), jnp.float32)
                cache_vs = self.variable(
                    "cache", "cached_value_scale", jnp.zeros,
                    (x.shape[0], nh, 1, capacity), jnp.float32)
                writes = [(cache_k, kq), (cache_v, vq),
                          (cache_ks, ks), (cache_vs, vs)]
            cache_index = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((), jnp.int32))
            if cache_lengths is not None:
                # Ragged slot decode (continuous batching): each batch
                # row is a server slot advancing at its OWN length, so
                # the single dynamic_update_slice index cannot serve —
                # scatter every row's new key/value column at that
                # row's position and hand the per-row offsets to the
                # attention dispatch (flash_decode_ragged or the XLA
                # per-row-offset fallback). cache_index is left
                # untouched: the slot lengths live with the server's
                # SlotState, not in the cache collection.
                # A verify window (x.shape[1] > 1) writes row i's W
                # columns at lengths[i] .. lengths[i] + W - 1; rejected
                # columns are overwritten by the next window before
                # any read (the next tick's window starts at the
                # accepted length). Same write as the paged branch:
                # the slot is the "page", its capacity the page size.
                wpos = _window_positions(cache_lengths, x.shape[1],
                                         capacity)
                rows = jnp.broadcast_to(
                    jnp.arange(x.shape[0], dtype=jnp.int32)[:, None],
                    wpos.shape)
                written = kv_cache_write(
                    [(var.value, t) for var, t in writes], rows, wpos,
                    use_flash=cfg.use_flash_attention, paged=False)
                for (var, _), leaf in zip(writes, written):
                    var.value = leaf
                query_offset = wpos[:, 0]               # [b]
            else:
                idx = cache_index.value
                for var, t in writes:
                    var.value = jax.lax.dynamic_update_slice(
                        var.value, t.transpose(0, 2, 3, 1),
                        (0, 0, 0, idx))
                query_offset = idx
                cache_index.value = idx + x.shape[1]
            k, v = cache_k.value, cache_v.value
            if kv_int8:
                k_scale, v_scale = cache_ks.value, cache_vs.value
            kv_cache_layout = True

        dropout_rng = None
        if cfg.attention_probs_dropout_prob > 0.0 and not deterministic:
            dropout_rng = self.make_rng("dropout")

        # Ulysses all-to-all CP (beyond-reference; DeepSpeed-Ulysses
        # semantics expressed as GSPMD reshards): for the attention
        # itself the seq dim gathers while heads shard over cp x mp —
        # the two constraints below make XLA emit the token
        # all-to-alls. Exact attention per head-shard, so dropout and
        # biases work unchanged (unlike the ring path).
        use_ulysses = (cfg.context_parallel and not use_cache
                       and cfg.context_parallel_algo == "ulysses")
        if use_ulysses:
            q, k, v = (with_logical_constraint(
                t, ("batch", None, "act_heads_cp", None))
                for t in (q, k, v))

        ring_mesh = None
        if cfg.context_parallel and not use_cache and attn_bias is None \
                and cfg.context_parallel_algo == "ring" \
                and (deterministic
                     or cfg.attention_probs_dropout_prob == 0.0):
            from ...parallel.mesh import (
                CP_AXIS, DATA_AXES, MP_AXIS, get_mesh,
            )
            mesh = get_mesh()
            if mesh is not None and mesh.shape.get(CP_AXIS, 1) > 1:
                # shard_map needs exact divisibility; undersized
                # shapes (e.g. the batch-1 abstract-init sample) take
                # the dense path — parameters are unaffected
                bsz = int(np.prod([mesh.shape[a] for a in DATA_AXES]))
                if q.shape[0] % bsz == 0 and \
                        q.shape[1] % mesh.shape[CP_AXIS] == 0 and \
                        q.shape[2] % mesh.shape[MP_AXIS] == 0:
                    ring_mesh = mesh
        if ring_mesh is not None:
            from ...ops.ring_attention import ring_attention_sharded
            out = ring_attention_sharded(q, k, v, ring_mesh,
                                         causal=True)
        else:
            out = dot_product_attention(
                q, k, v, bias=attn_bias, causal=True,
                query_offset=query_offset,
                dropout_rate=cfg.attention_probs_dropout_prob,
                dropout_rng=dropout_rng, deterministic=deterministic,
                use_flash=cfg.use_flash_attention,
                kv_cache_layout=kv_cache_layout,
                page_table=page_table_arg,
                k_scale=k_scale, v_scale=v_scale,
                heads_axis="act_heads_cp" if use_ulysses
                else "act_heads")
        if use_ulysses:
            # all-to-all back: seq re-shards over cp, heads gather
            out = with_logical_constraint(
                out, ("batch", "seq", "act_heads", None))
        out = checkpoint_name(out, "attn")

        attn_inner = out
        if quant:
            out = _QuantDense(
                cfg, features=(h,),
                kernel_axes=("heads", "kv", "embed"),
                contract_ndim=2, name="out_proj")(out)
        else:
            out = _CollectiveDense(
                cfg, features=(h,),
                kernel_axes=("heads", "kv", "embed"),
                mode="row", contract_ndim=2, name="out_proj")(out)
        if cfg.lora_rank:
            out = out + _LoRADelta(
                cfg, features=(h,), contract_ndim=2,
                name="out_proj_lora")(attn_inner, adapter_ids)
        return checkpoint_name(out, "attn_out")


class TransformerDecoderLayer(nn.Module):
    """Pre-LN decoder block (reference ``single_model.py:340-427``).

    With ``scanned=True`` the call returns ``(x, aux)`` — the
    ``(carry, ys)`` pair ``nn.scan`` requires, where ``aux`` is the
    MoE router auxiliary loss (None for the dense FFN). Non-scanned,
    the return is the bare ``x`` for dense configs and ``(x, aux)``
    when ``moe_num_experts > 0``.
    """
    config: GPTConfig
    scanned: bool = False

    @nn.compact
    def __call__(self, x, attn_bias=None, use_cache: bool = False,
                 deterministic: bool = True, cache_lengths=None,
                 page_table=None, chunk_start=None, adapter_ids=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=1e-5, dtype=dtype, param_dtype=pdtype, name=name,
            scale_init=nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("norm",)),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("norm",)))

        residual = x
        y = ln("norm1")(x)
        y = MultiHeadAttention(cfg, name="self_attn")(
            y, attn_bias, use_cache, deterministic, cache_lengths,
            page_table, chunk_start, adapter_ids)
        y = nn.Dropout(cfg.hidden_dropout_prob, name="dropout1")(
            y, deterministic=deterministic)
        x = residual + y
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"))

        residual = x
        y = ln("norm2")(x)
        moe_aux = None
        if cfg.moe_num_experts:
            from .moe import MoEMLP
            y, moe_aux = MoEMLP(cfg, name="moe_mlp")(y, deterministic)
        else:
            mlp_in = y
            if cfg.quant_execution == "weight_only_int8":
                y = _QuantDense(cfg, features=(cfg.ffn_hidden_size,),
                                kernel_axes=("embed", "mlp"),
                                name="linear1")(y)
            else:
                y = _CollectiveDense(
                    cfg, features=(cfg.ffn_hidden_size,),
                    kernel_axes=("embed", "mlp"), mode="column",
                    name="linear1")(y)
            if cfg.lora_rank:
                y = y + _LoRADelta(
                    cfg, features=(cfg.ffn_hidden_size,),
                    name="linear1_lora")(mlp_in, adapter_ids)
            y = checkpoint_name(y, "mlp1")
            y = nn.gelu(y, approximate=True)
            y = with_logical_constraint(y, ("batch", None, "act_mlp"))
            mlp_mid = y
            if cfg.quant_execution == "weight_only_int8":
                y = _QuantDense(cfg, features=(cfg.hidden_size,),
                                kernel_axes=("mlp", "embed"),
                                name="linear2")(y)
            else:
                y = _CollectiveDense(
                    cfg, features=(cfg.hidden_size,),
                    kernel_axes=("mlp", "embed"), mode="row",
                    name="linear2")(y)
            if cfg.lora_rank:
                y = y + _LoRADelta(
                    cfg, features=(cfg.hidden_size,),
                    name="linear2_lora")(mlp_mid, adapter_ids)
            y = checkpoint_name(y, "mlp2")
        y = nn.Dropout(cfg.hidden_dropout_prob, name="dropout2")(
            y, deterministic=deterministic)
        x = residual + y
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"))
        if self.scanned:
            return x, moe_aux
        return (x, moe_aux) if cfg.moe_num_experts else x


class GPTEmbeddings(nn.Module):
    """Word + learned position embeddings (reference :435-473)."""
    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, position_ids, deterministic: bool = True):
        cfg = self.config
        word_emb = self.param(
            "word_embeddings",
            nn.with_logical_partitioning(_dense_init(cfg),
                                         ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), jnp.dtype(cfg.param_dtype))
        pos_emb = self.param(
            "position_embeddings",
            nn.with_logical_partitioning(_dense_init(cfg),
                                         ("pos", "embed")),
            (cfg.max_position_embeddings, cfg.hidden_size),
            jnp.dtype(cfg.param_dtype))
        dtype = jnp.dtype(cfg.dtype)
        x = jnp.take(word_emb, input_ids, axis=0).astype(dtype) + \
            jnp.take(pos_emb, position_ids, axis=0).astype(dtype)
        x = nn.Dropout(cfg.hidden_dropout_prob)(
            x, deterministic=deterministic)
        return with_logical_constraint(x, ("batch", "seq", "act_embed"))


class GPTModel(nn.Module):
    """Embeddings -> N decoder blocks -> final LayerNorm."""
    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None, attn_bias=None,
                 use_cache: bool = False, deterministic: bool = True,
                 position_offset=0, cache_lengths=None,
                 page_table=None, chunk_start=None, adapter_ids=None):
        cfg = self.config
        static_offset = position_offset if isinstance(position_offset, int) \
            else 0
        if input_ids.shape[-1] + static_offset > \
                cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {input_ids.shape[-1]} (+offset "
                f"{static_offset}) exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings}; with a traced offset the "
                f"generation loop must bound prompt+decode length itself")
        if position_ids is None:
            position_ids = position_offset + jnp.arange(
                input_ids.shape[-1], dtype=jnp.int32)[None, :]
            position_ids = jnp.broadcast_to(position_ids, input_ids.shape)
        x = GPTEmbeddings(cfg, name="embeddings")(
            input_ids, position_ids, deterministic)

        block = TransformerDecoderLayer
        if cfg.use_recompute:
            block = nn.remat(
                block, policy=_remat_policy(cfg.recompute_granularity),
                prevent_cse=not cfg.scan_layers,
                static_argnums=(3, 4))
        if cfg.scan_layers:
            x, aux_stack = nn.scan(
                block,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=nn.broadcast,
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, scanned=True, name="decoder")(
                x, attn_bias, use_cache, deterministic, cache_lengths,
                page_table, chunk_start, adapter_ids)
            moe_aux = aux_stack.sum() if cfg.moe_num_experts else None
        else:
            moe_aux = jnp.zeros((), jnp.float32) \
                if cfg.moe_num_experts else None
            for i in range(cfg.num_layers):
                x = block(cfg, name=f"decoder_{i}")(
                    x, attn_bias, use_cache, deterministic,
                    cache_lengths, page_table, chunk_start,
                    adapter_ids)
                if cfg.moe_num_experts:
                    x, aux = x
                    moe_aux = moe_aux + aux

        if moe_aux is not None:
            # picked up by loss paths via mutable=["losses"]; silently
            # dropped (flax sow semantics) by eval/generation/export
            # applies that don't request the collection
            self.sow("losses", "moe_aux", moe_aux)
        return _final_norm(cfg, name="final_norm")(x)


def _final_norm(cfg: GPTConfig, name: Optional[str] = None) -> nn.LayerNorm:
    """The decoder-output LayerNorm — single definition shared by the
    plain and pipelined forward paths."""
    return nn.LayerNorm(
        epsilon=1e-5, dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype), name=name,
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("norm",)),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), ("norm",)))


class GPTForPretraining(nn.Module):
    """GPT with tied-embedding LM head (reference :577-616).

    The hybrid reference computes tied logits through
    ``parallel_matmul`` with an mp all-gather (``hybrid_model.py:45-66``);
    here the einsum against the vocab-sharded embedding produces
    vocab-sharded logits and GSPMD inserts the same collective exactly
    where needed (only if the consumer demands replication).
    """
    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None, attn_bias=None,
                 use_cache: bool = False, deterministic: bool = True,
                 position_offset=0, cache_lengths=None,
                 page_table=None, chunk_start=None, adapter_ids=None,
                 chunk_valid=None):
        # chunk_valid: a K/V cache masks a padded tail by position
        del chunk_valid
        x = GPTModel(self.config, name="gpt")(
            input_ids, position_ids, attn_bias, use_cache, deterministic,
            position_offset, cache_lengths, page_table, chunk_start,
            adapter_ids)
        word_emb = _word_embedding(
            self.variables["params"]["gpt"]["embeddings"])
        return tied_logits(x, word_emb)


def _word_embedding(emb_params) -> jax.Array:
    """The (possibly Partitioned-boxed) tied embedding table from an
    embeddings param subtree — single unboxing point for the LM head,
    the pipelined loss, and the chunked loss."""
    word_emb = emb_params["word_embeddings"]
    if isinstance(word_emb, nn.Partitioned):
        word_emb = word_emb.value
    return word_emb


def tied_logits(x: jax.Array, word_emb: jax.Array) -> jax.Array:
    """LM head against the (vocab-sharded) embedding table; GSPMD
    keeps the logits vocab-sharded (reference ``parallel_matmul``,
    ``hybrid_model.py:45-66``)."""
    logits = jnp.einsum("bsh,vh->bsv", x, word_emb.astype(x.dtype))
    return with_logical_constraint(logits, ("batch", "seq", "act_vocab"))


def _pipeline_parts(cfg: GPTConfig, input_ids, position_ids,
                    deterministic: bool, rng):
    """Shared setup for the pipelined loss paths: embedding output,
    the per-layer apply fn (remat-wrapped), final norm + tied head
    pieces, the split rngs, and whether each layer emits an aux loss
    (MoE router aux — the pipeline schedules thread it through as an
    explicit output with its own cotangent)."""
    if not cfg.scan_layers:
        raise ValueError("pipeline parallelism requires scan_layers=True "
                         "(stacked decoder params)")
    has_aux = bool(cfg.moe_num_experts)
    if position_ids is None:
        position_ids = jnp.broadcast_to(
            jnp.arange(input_ids.shape[-1], dtype=jnp.int32)[None, :],
            input_ids.shape)
    rng = rng if rng is not None else jax.random.key(0)
    emb_rng, pipe_rng = jax.random.split(rng)

    def emb_fwd(ep):
        return GPTEmbeddings(cfg).apply(
            {"params": ep}, input_ids, position_ids, deterministic,
            rngs=None if deterministic else {"dropout": emb_rng})

    def layer_apply(lp, h, key):
        return TransformerDecoderLayer(cfg, scanned=False).apply(
            {"params": lp}, h, None, False, deterministic,
            rngs=None if deterministic else {"dropout": key})
    if cfg.use_recompute:
        layer_apply = jax.checkpoint(
            layer_apply, policy=_remat_policy(cfg.recompute_granularity))

    return emb_fwd, layer_apply, pipe_rng, has_aux


def pipelined_lm_loss(cfg: GPTConfig, params, input_ids, labels,
                      loss_mask, *, pp: int, num_microbatches: int,
                      vpp: int = 1, rng=None, position_ids=None,
                      deterministic: bool = True) -> jax.Array:
    """Masked-CE pretraining loss with the decoder stack pipelined
    over the ``pp`` mesh axis.

    The pipe twin of ``GPTForPretraining`` — but unlike the
    reference's ``GPTForPretrainingPipe`` (a different module class
    with per-rank ``LayerDesc`` params, ``hybrid_model.py:862-962``)
    this consumes the *same* parameter tree as the non-pipe model:
    embeddings and final norm run replicated over ``pp``, the stacked
    ``[L, ...]`` decoder params are pipelined, and the LM head + loss
    run per-microbatch on the last stage's output (the reference
    computes per-microbatch loss inside ``train_batch`` the same way).
    The tied-embedding logits need no ``SharedLayerDesc``: the single
    embedding table serves both ends.
    """
    from ...parallel.pipeline import pipeline_forward

    emb_fwd, layer_apply, pipe_rng, has_aux = _pipeline_parts(
        cfg, input_ids, position_ids, deterministic, rng)
    emb_params = params["gpt"]["embeddings"]
    x = emb_fwd(emb_params)

    ln = _final_norm(cfg)
    fn_params = params["gpt"]["final_norm"]
    word_emb = _word_embedding(emb_params)

    def head_and_loss(acc, y, ex):
        # per-microbatch masked mean, averaged over microbatches below —
        # the same weighting as the engine's accumulation scan and the
        # reference's 1F1B micro-loss averaging (masks that vary across
        # microbatches weight identically with and without pp)
        labels_mb, mask_mb = ex
        h = ln.apply({"params": fn_params}, y)
        nll, msum = masked_nll_sums(tied_logits(h, word_emb),
                                    labels_mb, mask_mb)
        return acc + nll / jnp.maximum(msum, 1.0)

    # forward-only path drops the MoE router aux (pure CE — matching
    # the non-pipelined eval criterion, which also excludes aux)
    loss_sum = pipeline_forward(
        layer_apply, params["gpt"]["decoder"], x,
        pp=pp, num_microbatches=num_microbatches, vpp=vpp,
        out_fn=head_and_loss, out_init=jnp.zeros((), jnp.float32),
        extras=(labels, loss_mask), rng=pipe_rng,
        layer_has_aux=has_aux)
    return loss_sum / num_microbatches


def pipelined_lm_loss_and_grad(
        cfg: GPTConfig, params, input_ids, labels, loss_mask, *,
        pp: int, num_microbatches: int, vpp: int = 1, rng=None,
        position_ids=None, deterministic: bool = True,
        schedule: str = "1F1B", h2_depth: int = -1):
    """Loss AND parameter gradients under the explicit 1F1B (or
    zero-bubble ``"zb"``/``"zb_h2"``; ``h2_depth`` is the ZB-H2
    warm-up depth, -1 = full) schedule.

    ``jax.grad(pipelined_lm_loss)`` differentiates through the GPipe
    scan, which stashes every microbatch's stage activations before any
    backward runs; this path drives ``pipeline_value_and_grad`` so the
    activation ring holds at most ``2*pp*vpp`` microbatch slots — the
    1F1B memory profile the reference defaults to
    (``hybrid_model.py:962`` area, ``eager_engine.py:406-415``).

    Returns ``(loss, grads)`` where ``grads`` matches the
    ``{"gpt": {embeddings, decoder, final_norm}}`` parameter tree and
    both are per-microbatch-mean averaged — exactly what
    ``jax.value_and_grad(pipelined_lm_loss)`` would return.
    """
    from ...parallel.pipeline import pipeline_value_and_grad

    emb_fwd, layer_apply, pipe_rng, has_aux = _pipeline_parts(
        cfg, input_ids, position_ids, deterministic, rng)
    emb_params = params["gpt"]["embeddings"]
    extra = set(params["gpt"]) - {"embeddings", "decoder", "final_norm"}
    if extra:
        raise ValueError(f"unexpected GPT param subtrees: {extra}")
    x, emb_pull = jax.vjp(emb_fwd, emb_params)

    ln = _final_norm(cfg)
    fn_params = params["gpt"]["final_norm"]
    word_emb = _word_embedding(emb_params)

    def head_loss_and_grad(y, ex):
        """LM-head loss and its grads w.r.t. hidden states + head params."""
        labels_mb, mask_mb = ex

        def head(hp, yy):
            h = ln.apply({"params": hp["fn"]}, yy)
            nll, msum = masked_nll_sums(tied_logits(h, hp["we"]),
                                        labels_mb, mask_mb)
            return nll / jnp.maximum(msum, 1.0)

        loss_mb, pull = jax.vjp(head, {"fn": fn_params, "we": word_emb}, y)
        dhp, dy = pull(jnp.ones((), jnp.float32))
        return loss_mb, dy, dhp

    loss_sum, d_stacked, dhead, dx = pipeline_value_and_grad(
        layer_apply, params["gpt"]["decoder"], x,
        pp=pp, num_microbatches=num_microbatches, vpp=vpp,
        loss_and_grad=head_loss_and_grad,
        extras=(labels, loss_mask), rng=pipe_rng,
        schedule=schedule, h2_depth=h2_depth, layer_has_aux=has_aux)

    (demb,) = emb_pull(dx.astype(x.dtype))
    # fold the tied LM head's word-embedding gradient into the
    # embedding-table gradient (the reference ties them through
    # SharedLayerDesc's allreduce; here it is a plain add)
    we_leaf = demb["word_embeddings"]
    dwe = dhead["we"]
    if isinstance(we_leaf, nn.Partitioned):
        we_leaf = we_leaf.replace(
            value=we_leaf.value + dwe.astype(we_leaf.value.dtype))
    else:
        we_leaf = we_leaf + dwe.astype(we_leaf.dtype)
    demb = dict(demb)
    demb["word_embeddings"] = we_leaf

    inv = 1.0 / num_microbatches
    scale = lambda t: jax.tree.map(  # noqa: E731
        lambda g: (g * inv).astype(g.dtype), t)
    grads = {"gpt": {"embeddings": scale(demb),
                     "decoder": scale(d_stacked),
                     "final_norm": scale(dhead["fn"])}}
    return loss_sum * inv, grads


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       loss_mask: jax.Array) -> jax.Array:
    """Masked LM criterion (reference ``GPTPretrainingCriterion``,
    ``single_model.py:619-653``): mean NLL over unmasked positions.

    Computed in fp32 regardless of compute dtype (``masked_nll_sums``).
    """
    nll_sum, mask_sum = masked_nll_sums(logits, labels, loss_mask)
    return nll_sum / jnp.maximum(mask_sum, 1.0)


def chunked_lm_loss(model: "GPTForPretraining", params, input_ids,
                    labels, loss_mask, *, chunks: int,
                    position_ids=None, deterministic: bool = True,
                    rngs=None, include_moe_aux: bool = True) -> jax.Array:
    """Masked-CE pretraining loss with the LM head + softmax computed
    over ``chunks`` sequence chunks inside a rematerialized scan.

    Under ``deterministic=True`` this is numerically identical to
    ``cross_entropy_loss(model.apply(...))`` — the per-token NLL sums
    are exact, not chunk-mean-of-means. (With dropout the two paths
    draw different masks: flax folds the module path into dropout
    keys, and here ``GPTModel`` is the top-level module.) But
    the ``[b, s, V]`` logits — the largest single activation of
    GPT-class training (1.6 GB fp32 at bs8/s1024/V50304) — never
    materialize beyond ``[b, s/chunks, V]``. ``jax.checkpoint`` makes
    the backward recompute each chunk's logits instead of saving them:
    one extra head matmul per chunk buys O(s/chunks) logits memory.
    """
    cfg = model.config
    moe_aux = jnp.zeros((), jnp.float32)
    if cfg.moe_num_experts and include_moe_aux:
        h, mods = GPTModel(cfg).apply(
            {"params": params["gpt"]}, input_ids, position_ids, None,
            False, deterministic, rngs=rngs, mutable=["losses"])
        moe_aux = sum(jax.tree.leaves(mods["losses"]))
    else:
        h = GPTModel(cfg).apply({"params": params["gpt"]}, input_ids,
                                position_ids, None, False,
                                deterministic, rngs=rngs)
    word_emb = _word_embedding(params["gpt"]["embeddings"])
    nll, msum = chunked_nll_sums(
        h, lambda hh: tied_logits(hh, word_emb), labels, loss_mask,
        chunks)
    return nll / jnp.maximum(msum, 1.0) + moe_aux
