"""Scaled dot-product attention with fused-causal-softmax semantics.

The reference fuses mask+softmax through a CUDA kernel
(``incubate.softmax_mask_fuse_upper_triangle``, reference
``single_model.py:198``) and otherwise materializes the full
``[b, heads, s, s]`` score matrix. On TPU the XLA path below already
fuses mask+softmax into the matmul epilogue; the Pallas flash-attention
kernel (``ops/pallas/flash_attention.py``) replaces it on real TPU
devices for long sequences, never materializing the score matrix.

Layout: ``q [b, sq, h, d]``, ``k/v [b, skv, h, d]`` (batch-major,
head-split), output ``[b, sq, h, d]``. With ``kv_cache_layout`` the
keys/values arrive as ``[b, h, d, skv]`` — the decode cache's native
TPU tiling (see ``models/gpt/model.py`` cache comment) — and no
relayout of the (large) cache happens on this path.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..observability import metrics
from .ring_attention import MeshIndivisible, kernel_mesh, shard_kernel

NEG_INF = -1e9


#: certification artifact written by scripts/validate_flash_dropout.py
#: on a PASSING live-chip run (rate-0 bit-equivalence, determinism,
#: dropped-mass fraction, finite-difference fwd/bwd mask identity) and
#: committed as evidence — its presence flips the gate default on
DROPOUT_CERT_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "pallas",
    "dropout_cert.json")


def _dropout_env_force():
    """The ``PFX_FLASH_DROPOUT`` tri-state: True/False when forced,
    None to fall through to the certification artifact."""
    env = os.environ.get("PFX_FLASH_DROPOUT")
    if env is not None:
        v = env.strip().lower()
        if v in ("1", "true", "yes", "on"):
            return True
        if v in ("0", "false", "no", "off"):
            return False
        # unrecognized (including empty) must not silently veto a
        # valid certification — fall through to the artifact
    return None


#: mtime-keyed cache of the certification artifact read, so the gate
#: decision does not re-read the file on every dispatch trace; tests
#: that rewrite the artifact invalidate it naturally via mtime
_cert_cache: dict = {}


def _dropout_cert_kind():
    """``device_kind`` recorded in the certification artifact, or None
    when absent/unreadable. Pure file I/O — never touches the jax
    backend."""
    try:
        mtime = os.path.getmtime(DROPOUT_CERT_PATH)
    except OSError:
        return None
    hit = _cert_cache.get(DROPOUT_CERT_PATH)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    try:
        import json
        with open(DROPOUT_CERT_PATH) as f:
            kind = json.load(f).get("device_kind") or None
    except (OSError, ValueError):
        kind = None
    _cert_cache[DROPOUT_CERT_PATH] = (mtime, kind)
    return kind


def _kernel_dropout_configured() -> bool:
    """Whether in-kernel dropout is CONFIGURED on: the env force, else
    the certification artifact's presence. Checks only the env var and
    artifact — no ``jax.devices()`` probe — so config-construction
    warning paths (``models/gpt/config.py``) can call it without
    initializing the PJRT backend as a side effect. The device-kind
    match is deferred to ``_kernel_dropout_enabled`` at
    kernel-dispatch time, where the backend is up anyway."""
    forced = _dropout_env_force()
    if forced is not None:
        return forced
    return _dropout_cert_kind() is not None


def _kernel_dropout_enabled() -> bool:
    """Gate for IN-KERNEL flash attention dropout. Self-certifying:

    - ``PFX_FLASH_DROPOUT=1`` / ``=0`` force it on / off;
    - otherwise it is on iff the chip-certification artifact
      (``DROPOUT_CERT_PATH``) exists AND its recorded ``device_kind``
      matches the attached TPU. Certification is per TPU generation —
      Mosaic PRNG semantics differ across libtpu/device kinds (the r5
      session hit a v5e-specific two-operand ``prng_seed`` limit), so
      a v5e cert must not flip the default on a v3/v4 fleet; mismatch
      falls back to dense with the documented warning. Only called at
      kernel-dispatch time — config-construction paths use
      ``_kernel_dropout_configured`` and never probe the backend."""
    forced = _dropout_env_force()
    if forced is not None:
        return forced
    kind = _dropout_cert_kind()
    if not kind:
        return False
    try:
        d = jax.devices()[0]
    except Exception:  # backend unavailable — claim nothing
        return False
    return d.platform == "tpu" and d.device_kind == kind

# Non-causal dispatch crossover: below this KV length the dense XLA
# batched matmul beats the flash kernel (measured on a v5e at ERNIE
# shapes h=768/s=512/d=64: 10.9 vs 16.7 ms fwd — no causal-mask work
# to save and too few blocks to amortize program overhead). The
# break-even moves with TPU generation and head dim; retune here.
DENSE_NONCAUSAL_MAX_SKV = 2048

# Widest multi-token window the VERIFY decode kernels take
# (speculative k-token verification, k+1 <= this). Chunked paged
# prefill also arrives as per-row-offset multi-token attention but in
# page-sized chunks (>= 128 tokens), far past any sane draft length —
# this bound keeps it on the gather + dense path the kernels were
# never shaped for (the verify kernel unrolls its window statically,
# so a huge window would also explode the program).
MAX_VERIFY_WINDOW = 32


def _gather_kv_pages(pool, page_table):
    """Resolve a paged KV pool back to per-row contiguous layout: the
    XLA-side mirror of the ``flash_decode_paged`` index-map
    indirection. ``pool [num_pages, h, d, page]`` gathered by
    ``page_table [b, max_pages]`` becomes ``[b, h, d,
    max_pages * page]`` with each row's logical positions back in
    order — after which the ordinary per-row-offset causal masking of
    :func:`_xla_attention` applies unchanged (positions past a row's
    offset are masked whatever garbage its unwritten/null pages hold).
    Materializes every row at full capacity, so it is the parity
    oracle and fallback, not the fast path."""
    g = jnp.take(pool, page_table, axis=0)     # [b, m, h, d, page]
    b, m, h, d, p = g.shape
    return g.transpose(0, 2, 3, 1, 4).reshape(b, h, d, m * p)


#: pages one step of :func:`paged_prefill_attention` gathers a row
PREFILL_SPAN_PAGES = 8


def paged_prefill_attention(q, k, v, chunk_start, page_table,
                            sliding_window=None):
    """A prefill chunk's attention through the page table, walking only
    the pages its queries can see. ``q [n, c, h, d]`` sits at positions
    ``chunk_start[i] .. + c - 1`` of row ``i``; ``k`` / ``v`` are the
    pool ``[P, g, d, page]`` (``g`` dividing ``h``: grouped-query heads
    share the gathered pages, nothing is repeated), the chunk's own
    keys already written; ``page_table [n, max_pages]``.

    An online softmax over spans of :data:`PREFILL_SPAN_PAGES` pages
    in a ``fori_loop`` of dynamic length: from page 0, or with
    ``sliding_window`` from the page of key ``chunk_start + 1 -
    sliding_window`` (no page behind the window is gathered), to the
    chunk's last page. Work and memory follow the context that is
    there, not the table's capacity (the whole-table gather of
    :func:`dot_product_attention`'s dense path costs a 16 k-position
    row ``[h, c, 16384]`` float32 scores whatever the prompt's
    length). Scores, softmax statistics and the accumulator are
    float32; probabilities meet ``v`` in its dtype, as on the dense
    path. Plain XLA: gathers and einsums, no kernel."""
    n, c, h, d = q.shape
    g, page = k.shape[1], k.shape[3]
    m = h // g
    span = PREFILL_SPAN_PAGES
    pt = jnp.asarray(page_table, jnp.int32)
    c0 = jnp.asarray(chunk_start, jnp.int32)
    q_pos = c0[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    last = (c0 + (c - 1)) // page                          # [n]
    first = jnp.zeros_like(c0) if sliding_window is None else \
        jnp.maximum((c0 + (1 - sliding_window)) // page, 0)
    steps = jnp.max(-(-(last + 1 - first) // span))
    qg = q.reshape(n, c, g, m, d)
    metrics.inc("attention/paged_prefill_walk")

    def body(i, carry):
        """Span ``i`` of every row: gather, mask, online update."""
        m_prev, l_prev, acc = carry
        pages = first[:, None] + i * span + \
            jnp.arange(span, dtype=jnp.int32)[None, :]     # [n, span]
        pids = jnp.take_along_axis(
            pt, jnp.minimum(pages, pt.shape[1] - 1), axis=1)

        def rows(pool):                     # -> [n, g, d, span * page]
            return jnp.take(pool, pids, axis=0).transpose(
                0, 2, 3, 1, 4).reshape(n, g, d, span * page)
        k_pos = (pages[:, :, None] * page + jnp.arange(
            page, dtype=jnp.int32)[None, None, :]).reshape(n, -1)
        live = (k_pos[:, None, :] <= q_pos[:, :, None]) & \
            (pages <= last[:, None]).repeat(page, axis=1)[:, None, :]
        if sliding_window is not None:
            live &= k_pos[:, None, :] > q_pos[:, :, None] - sliding_window
        s = jnp.einsum("bqgmd,bgdk->bgmqk", qg, rows(k),
                       preferred_element_type=jnp.float32) * d ** -0.5
        s = jnp.where(live[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live[:, None, None], jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bgmqk,bgdk->bgmqd", p.astype(v.dtype), rows(v),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((n, g, m, c, 1), NEG_INF, jnp.float32),
            jnp.zeros((n, g, m, c, 1), jnp.float32),
            jnp.zeros((n, g, m, c, d), jnp.float32))
    _, l_fin, acc = jax.lax.fori_loop(0, steps, body, init)
    out = acc / jnp.maximum(l_fin, 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(n, c, h, d).astype(q.dtype)


def kv_cache_write(writes, rows, cols, use_flash: bool = True,
                   paged: bool = True):
    """Write fresh key/value columns into the decode cache leaves of
    one layer: ``leaf.at[rows, :, :, cols].set(new)`` for every
    ``(leaf, new)`` of ``writes``, the leaves returned in that order.
    ``leaf [N, h, d, M]`` is the paged pool (``N`` physical pages of
    ``M`` columns) or, with ``paged=False``, the contiguous slot cache
    (``N`` slots of ``M = capacity``); ``d`` is 1 for an int8 cache's
    fp32 scale leaves; ``rows`` / ``cols`` are ``[b, W]`` and ``new
    [b, W, h, d]`` (``W`` = 1 for a decode tick, the window of a
    speculative verify tick).

    The Pallas kernel (``ops/pallas/kv_write.py``) takes the leaves of
    one shape and dtype in ONE launch (a layer's K and V; the two
    scale leaves of an int8 cache in a second), rewrites only the
    128-column blocks the live rows write and leaves each leaf in the
    layout the decode kernels read, so under a jit that donates the
    cache the write is in place. Its grid follows the rows that write:
    of the paged pool, those with a position off ``NULL_PAGE`` (a free
    slot costs no step and the reserved page is never written); of the
    contiguous cache, every row. ``attention/kv_write_paged`` /
    ``attention/kv_write_ragged`` count the leaves it wrote, at trace
    time like the attention dispatch. The XLA scatter is the fallback
    and the parity oracle: where the kernel refuses
    (``attention/fallback/kernel_rejected``: off the TPU, an untiled
    minor dim) and, by decision, under a multi-device mesh
    (``attention/fallback/mesh_sharded``: the kernel is not
    ``shard_map``-wrapped) or with ``use_flash=False``. The chip's
    compiler brackets that scatter with two copies of the whole leaf
    (PERF.md, PR 24)."""
    writes = list(writes)
    # one launch per shape: K and V values, then an int8 cache's scales
    groups = {}
    for at, (leaf, _) in enumerate(writes):
        groups.setdefault((leaf.shape, leaf.dtype), []).append(at)
    out = [None] * len(writes)
    for group in groups.values():
        leaves, news = zip(*(writes[at] for at in group))
        for at, leaf in zip(group, _kv_write_leaves(
                leaves, news, rows, cols, use_flash, paged)):
            out[at] = leaf
    return out


def _kv_write_leaves(leaves, news, rows, cols, use_flash, paged):
    """:func:`kv_cache_write` for leaves of one shape and dtype: one
    kernel launch, or a scatter a leaf."""
    if use_flash and kernel_mesh() is not None:
        metrics.inc("attention/fallback/mesh_sharded", len(leaves))
    elif use_flash:
        try:
            from .pallas.kv_write import kv_write
            out = kv_write(leaves, rows, cols, news, paged=paged)
            metrics.inc("attention/kv_write_paged" if paged
                        else "attention/kv_write_ragged", len(leaves))
            return out
        except (ImportError, NotImplementedError):
            metrics.inc("attention/fallback/kernel_rejected",
                        len(leaves))
    return [leaf.at[rows, :, :, cols].set(new)
            for leaf, new in zip(leaves, news)]


def _xla_attention(q, k, v, bias, causal, query_offset, dropout_rate,
                   dropout_rng, deterministic, softmax_in_fp32,
                   kv_cache_layout=False, sm_scale=None,
                   sliding_window=None):
    scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    k_eq = "bhdk" if kv_cache_layout else "bkhd"
    nh, g = q.shape[2], k.shape[1 if kv_cache_layout else 2]
    if g != nh:
        # grouped-query heads: query head m * i + j reads K/V head i;
        # the group axis rides the einsum, K/V are never repeated
        qg = (q * scale).reshape(q.shape[:2] + (g, nh // g, q.shape[3]))
        scores = jnp.einsum(
            f"bqgmd,{k_eq.replace('h', 'g')}->bgmqk", qg, k).reshape(
                q.shape[0], nh, q.shape[1], -1)
    else:
        scores = jnp.einsum(f"bqhd,{k_eq}->bhqk", q * scale, k)
    if softmax_in_fp32:
        scores = scores.astype(jnp.float32)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        # query i attends to keys <= i + query_offset (offset > 0 during
        # cached decode where keys include the past). A [b] offset
        # vector masks PER ROW — the XLA oracle/fallback for the
        # ragged slot decode (flash_decode_ragged): row i's mask
        # broadcasts as [b, 1, sq, sk] against the [b, h, sq, sk]
        # scores, so each slot sees exactly its own cache prefix.
        off = jnp.asarray(query_offset)
        if off.ndim == 1:
            q_pos = (jnp.arange(sq)[:, None]
                     + off[:, None, None, None])   # [b, 1, sq, 1]
        else:
            q_pos = jnp.arange(sq)[:, None] + off  # [sq, 1]
        k_pos = jnp.arange(sk)[None, :]
        live = k_pos <= q_pos
        if sliding_window is not None:
            live &= k_pos > q_pos - sliding_window
        scores = jnp.where(live, scores, NEG_INF)
    if bias is not None:
        scores = scores + bias.astype(scores.dtype)
    weights = jax.nn.softmax(scores, axis=-1)
    weights = checkpoint_name(weights, "core_attn")
    if dropout_rate > 0.0 and not deterministic:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    weights.shape)
        weights = weights * keep / (1.0 - dropout_rate)
    weights = weights.astype(v.dtype)
    v_eq = "bhdk" if kv_cache_layout else "bkhd"
    if g != nh:
        out = jnp.einsum(
            f"bgmqk,{v_eq.replace('h', 'g')}->bqgmd",
            weights.reshape(weights.shape[0], g, nh // g,
                            *weights.shape[2:]), v).reshape(q.shape)
    else:
        out = jnp.einsum(f"bhqk,{v_eq}->bqhd", weights, v)
    return checkpoint_name(out, "core_attn")


def _flash_per_device(q, k, v, bias, causal, query_offset,
                      dropout_rate, dropout_rng, heads_axis,
                      sm_scale=None):
    """Training flash attention with each device of the active mesh
    running the kernel on its own ``[b/data, s, h/mp, d]`` block
    (``ring_attention.shard_kernel``; a direct call when no
    multi-device mesh is active). All three custom-VJP surfaces of
    ``flash_attention`` (plain, in-kernel dropout, biased) pass
    through here, so their backward kernels run per device too."""
    from .pallas import flash_attention as fa
    b, _, h, _ = q.shape
    qkv_axes = ("batch", None, heads_axis, None)
    args, in_axes = [q, k, v], [qkv_axes] * 3
    if bias is not None:
        # canonical [b0, h0, q0, skv] form (each leading dim 1 or
        # full): full dims shard with q's, broadcast dims replicate
        bias = fa._canon_bias(bias, b, h, q.shape[1], k.shape[1])
        args.append(bias)
        in_axes.append(("batch" if bias.shape[0] > 1 else None,
                        heads_axis if bias.shape[1] > 1 else None,
                        None, None))
    dropout = dropout_rate > 0.0 and dropout_rng is not None
    mesh_axes = ()
    if dropout:
        args.append(jax.random.key_data(dropout_rng))
        in_axes.append((None,))
        if kernel_mesh() is not None:
            import flax.linen as nn
            mesh_axes = [
                a for axes in nn.logical_to_mesh_axes(qkv_axes)
                if axes for a in
                ((axes,) if isinstance(axes, str) else axes)]

    def per_device(q, k, v, *rest):
        """One device's block; counter + fallback live in the one
        caller, :func:`dot_product_attention`."""
        rest = list(rest)
        rng = None
        if dropout:
            rng = jax.random.wrap_key_data(rest.pop())
            # the kernel seeds its masks from LOCAL block coordinates;
            # fold the device's mesh position in so shards holding
            # different batch rows / heads draw different masks
            for axis in mesh_axes:
                rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        return fa.flash_attention(  # pfxlint: disable=PFX205
            q, k, v, causal=causal, query_offset=query_offset,
            dropout_rate=dropout_rate if dropout else 0.0,
            dropout_rng=rng, bias=rest.pop() if rest else None,
            sm_scale=sm_scale)

    return shard_kernel(per_device, args, in_axes, qkv_axes)


def _count_causal_tiles(q, k, v, causal, plain):
    """Trace-time counters of what a causal training-flash call's
    kernels do with the blocks that cross the diagonal:
    ``attention/flash_causal_staircase`` where the forward or the
    backward it gets (``plain``: no in-kernel dropout, no bias) walks
    them as a staircase of sub-tiles, ``flash_causal_whole_block``
    where both compute them whole, and the gauge
    ``flash_causal_tile_share``: the score elements the two execute
    over what whole blocks would (the sequence is never sharded, so
    the global lengths are each device's)."""
    if not causal:
        return
    from .pallas import flash_attention as fa
    sq, skv, d, d_v = q.shape[1], k.shape[1], q.shape[-1], v.shape[-1]
    try:
        blocks = fa.check_shapes(sq, skv, d, d_v=d_v)
    except NotImplementedError:
        # the call above succeeded, so only a stand-in kernel gets
        # here; counting must never steer the dispatch
        return
    executed, whole = fa.causal_step_elements(
        sq, skv, d, d_v, q.dtype.itemsize, *blocks, plain=plain)
    metrics.inc("attention/flash_causal_staircase" if executed < whole
                else "attention/flash_causal_whole_block")
    metrics.get_registry().set_gauge(
        "attention/flash_causal_tile_share", executed / whole)


def dot_product_attention(
        q: jax.Array, k: jax.Array, v: jax.Array,
        bias: Optional[jax.Array] = None,
        causal: bool = True,
        query_offset=0,
        dropout_rate: float = 0.0,
        dropout_rng: Optional[jax.Array] = None,
        deterministic: bool = True,
        softmax_in_fp32: bool = True,
        use_flash: bool = False,
        kv_cache_layout: bool = False,
        page_table: Optional[jax.Array] = None,
        k_scale: Optional[jax.Array] = None,
        v_scale: Optional[jax.Array] = None,
        heads_axis: str = "act_heads",
        sm_scale: Optional[float] = None,
        sliding_window: Optional[int] = None) -> jax.Array:
    """Causal attention; dispatches to the Pallas flash kernel on TPU.

    ``v`` may be narrower than ``q``/``k`` (latent attention scores at
    192 and mixes values of 128); the output has v's width, the
    training flash kernel then counts as ``attention/flash_mla``.
    ``sm_scale`` defaults to ``q.shape[-1] ** -0.5``; the cached-decode
    kernels take neither (one width, their own scale).

    ``bias`` is an additive mask broadcastable to ``[b, h, sq, sk]``
    (the reference's ``attn_mask`` convention, additive -1e4 style).

    ``page_table`` (requires ``kv_cache_layout``): ``k``/``v`` are the
    PAGED pool ``[num_pages, h, d, page]`` and each row's logical
    cache is ``page_table[row]``'s pages in order (``core/paging.py``).
    Single-token ragged decode takes ``flash_decode_paged``
    (``attention/flash_decode_paged`` counter); a short multi-token
    window (``1 < sq <= MAX_VERIFY_WINDOW``, per-row offsets, no
    bias) is the speculative k-token VERIFY and takes the same kernel
    with the within-window causal mask
    (``attention/flash_decode_paged_verify`` /
    ``attention/flash_decode_ragged_verify``); everything else —
    chunked prefill, kernel rejection, ``use_flash=False`` — gathers
    the rows contiguous (:func:`_gather_kv_pages`) and rides the
    per-row-offset dense path (dispatch matrix: docs/inference.md).

    ``k_scale``/``v_scale`` (require ``kv_cache_layout``): the cache
    is int8 (``GPTConfig.kv_cache_dtype="int8"``) and these are its
    per-(row, head, position) fp32 dequant scales, shaped like the
    cache minus its d axis (``[b, h, 1, S]``, or the page-parallel
    pool ``[P, h, 1, page]``). Every kernel branch takes its
    dequant-in-kernel variant (``attention/*_int8`` counters); the
    dense fallback dequantizes the gathered rows up front and is the
    parity oracle (dispatch matrix: docs/quantization.md).

    Grouped-query heads and a sliding window (cached decode only;
    the training flash kernels take neither): ``k``/``v`` may hold
    ``g`` heads under ``h = g * m`` query heads, query head ``m * i +
    j`` reading K/V head ``i`` (``attention/paged_gqa``), and with
    ``sliding_window`` key ``j`` is visible to the query at ``i`` iff
    ``i - sliding_window < j <= i`` (``attention/window_layers``).
    ``flash_decode_paged`` takes both by shape; so does the dense
    fallback.

    ``heads_axis`` is the logical axis the heads dim is sharded by
    ("act_heads", or "act_heads_cp" under Ulysses): under a
    multi-device mesh the training flash kernel runs per device on
    its own batch/heads block (:func:`_flash_per_device`); the decode
    kernels are not wrapped yet, so there a multi-device mesh takes
    the dense path (``attention/fallback/mesh_sharded``).
    """
    if (k_scale is None) is not (v_scale is None):
        raise ValueError("k_scale and v_scale come together")
    if k_scale is not None and not kv_cache_layout:
        raise ValueError("KV scales require kv_cache_layout (the "
                         "int8 cache is decode-only)")
    skv = k.shape[3] if kv_cache_layout else k.shape[1]
    if page_table is not None:
        if not kv_cache_layout:
            raise ValueError("page_table requires kv_cache_layout")
        skv = page_table.shape[1] * k.shape[3]
    grouped = k.shape[1 if kv_cache_layout else 2] != q.shape[2]
    if (grouped or sliding_window is not None) and \
            not (kv_cache_layout and page_table is not None):
        # one dense expression serves the uncached forward; the
        # contiguous-cache and training kernels know neither
        use_flash = False
    if grouped and page_table is not None:
        metrics.inc("attention/paged_gqa")
    if sliding_window is not None:
        metrics.inc("attention/window_layers")
    # training dropout on the kernel path: in-kernel philox masks
    # (reference fused softmax-with-dropout, hybrid_model.py:277-285).
    # Bias (ERNIE padding masks, GPT attn_mask) rides into the kernel
    # as a tiled operand, causal or not; no DENSE_NONCAUSAL crossover
    # here — the dense path pays the [b, h, sq, sk] dropout-mask
    # traffic on top of the score materialization, so the kernel wins
    # at every training shape
    # dispatch counters fire at trace time (once per compiled shape,
    # not per step) into the process-global registry — free when
    # telemetry is off, and they let the flight recorder / summary
    # attest which lowering each run actually took
    if (use_flash and dropout_rate > 0.0 and not deterministic
            and dropout_rng is not None
            and not kv_cache_layout):
        if _kernel_dropout_enabled():
            try:
                out = _flash_per_device(
                    q, k, v, bias, causal, query_offset, dropout_rate,
                    dropout_rng, heads_axis, sm_scale)
                metrics.inc("attention/flash_dropout")
                _count_causal_tiles(q, k, v, causal, plain=False)
                return out
            except MeshIndivisible:
                metrics.inc("attention/fallback/mesh_sharded")
            except (ImportError, NotImplementedError):
                metrics.inc("attention/fallback/kernel_rejected")
        else:
            metrics.inc("attention/fallback/dropout_gate_off")
    if use_flash and kv_cache_layout and kernel_mesh() is not None:
        # the decode kernels are not shard_map-wrapped: under a
        # multi-device mesh Mosaic would refuse to partition them at
        # lowering, past any try/except here — dense by decision
        metrics.inc("attention/fallback/mesh_sharded")
    # deterministic makes a configured dropout_rate inert, so eval and
    # generation may take the kernel even when training cannot
    elif use_flash and (deterministic or dropout_rate == 0.0):
        # the decode kernel takes a per-key additive bias (generation's
        # left-pad mask: [b, 1, 1, skv]); the training kernel takes
        # any bias broadcastable to [b, h, sq, skv]
        decode_bias_ok = causal and q.shape[1] == 1 and (
            bias is None or
            (bias.ndim == 4 and bias.shape[1] == bias.shape[2] == 1
             and bias.shape[0] == q.shape[0]
             and bias.shape[-1] == skv))
        try:
            from .pallas import flash_attention as fa
            if kv_cache_layout and page_table is not None:
                if causal and q.shape[1] == 1 and bias is None and \
                        getattr(query_offset, "ndim", 0) == 1:
                    # paged ragged decode: the kernel's scalar
                    # prefetch walks the slot->page indirection table
                    # (flash_decode_paged) — each row streams only its
                    # own pages
                    out = fa.flash_decode_paged(q, k, v, query_offset,
                                                page_table,
                                                k_scale=k_scale,
                                                v_scale=v_scale,
                                                reach=sliding_window)
                    if k_scale is not None:
                        metrics.inc("attention/flash_decode_paged_int8")
                    else:
                        metrics.inc("attention/flash_decode_paged")
                    return out
                if causal and 1 < q.shape[1] <= MAX_VERIFY_WINDOW \
                        and bias is None \
                        and getattr(query_offset, "ndim", 0) == 1:
                    # speculative k-token verify over the paged pool:
                    # same table walk, within-window causal mask
                    # (docs/inference.md, speculative decoding)
                    out = fa.flash_decode_paged(q, k, v, query_offset,
                                                page_table,
                                                k_scale=k_scale,
                                                v_scale=v_scale,
                                                reach=sliding_window)
                    if k_scale is not None:
                        metrics.inc(
                            "attention/flash_decode_paged_verify_int8")
                    else:
                        metrics.inc(
                            "attention/flash_decode_paged_verify")
                    return out
                # chunked prefill (page-sized sq) and other paged
                # shapes fall through to the shared kv_cache_layout
                # fallback counter and the gather + dense path below
            elif decode_bias_ok and kv_cache_layout:
                if getattr(query_offset, "ndim", 0) == 1:
                    # ragged slot decode: a [b] offset vector (the
                    # continuous-batching server's per-slot lengths) —
                    # each row masks and block-skips against its OWN
                    # last valid position
                    out = fa.flash_decode_ragged(q, k, v, query_offset,
                                                 bias=bias,
                                                 k_scale=k_scale,
                                                 v_scale=v_scale)
                    if k_scale is not None:
                        metrics.inc(
                            "attention/flash_decode_ragged_int8")
                    else:
                        metrics.inc("attention/flash_decode_ragged")
                    return out
                # cached decode: single query token, dynamic cache
                # index — the kernel skips blocks past the index
                out = fa.flash_decode(q, k, v, query_offset,
                                      bias=bias, k_scale=k_scale,
                                      v_scale=v_scale)
                if k_scale is not None:
                    metrics.inc("attention/flash_decode_int8")
                else:
                    metrics.inc("attention/flash_decode")
                return out
            elif kv_cache_layout and causal and bias is None \
                    and 1 < q.shape[1] <= MAX_VERIFY_WINDOW \
                    and getattr(query_offset, "ndim", 0) == 1:
                # speculative k-token verify over the contiguous slot
                # cache: window query j of row i masks keys
                # <= query_offset[i] + j (within-window causal mask)
                out = fa.flash_decode_ragged(q, k, v, query_offset,
                                             k_scale=k_scale,
                                             v_scale=v_scale)
                if k_scale is not None:
                    metrics.inc(
                        "attention/flash_decode_ragged_verify_int8")
                else:
                    metrics.inc("attention/flash_decode_ragged_verify")
                return out
            # non-causal at short seq: the dense XLA batched matmul
            # beats the kernel (measured on ERNIE h=768/s=512/d=64:
            # 10.9 vs 16.7 ms fwd — no causal-mask work to save and
            # too few blocks to amortize program overhead); the kernel
            # wins causally (mask never materializes) and at long
            # sequences in either mode
            flash_worthwhile = causal or skv >= DENSE_NONCAUSAL_MAX_SKV
            if not kv_cache_layout and flash_worthwhile:
                out = _flash_per_device(q, k, v, bias, causal,
                                        query_offset, 0.0, None,
                                        heads_axis, sm_scale)
                metrics.inc("attention/flash_mla"
                            if v.shape[-1] != q.shape[-1]
                            else "attention/flash")
                _count_causal_tiles(q, k, v, causal, plain=bias is None)
                return out
            metrics.inc("attention/fallback/kv_cache_layout"
                        if kv_cache_layout
                        else "attention/fallback/short_noncausal")
        except MeshIndivisible:
            metrics.inc("attention/fallback/mesh_sharded")
        except (ImportError, NotImplementedError):
            metrics.inc("attention/fallback/kernel_rejected")
    elif not use_flash:
        metrics.inc("attention/fallback/flash_disabled")
    metrics.inc("attention/dense")
    if page_table is not None:
        # matching indirection for the dense path: gather each row's
        # pages back into contiguous [b, h, d, capacity] order, after
        # which the per-row offset masking below needs no page
        # awareness at all
        k = _gather_kv_pages(k, page_table)
        v = _gather_kv_pages(v, page_table)
        if k_scale is not None:
            k_scale = _gather_kv_pages(k_scale, page_table)
            v_scale = _gather_kv_pages(v_scale, page_table)
    if k_scale is not None:
        # dense oracle for the int8 cache: widen up front with the
        # same per-(row, head, position) scales the kernels apply
        # in-VMEM, then attend exactly as bf16 would
        k = (k.astype(jnp.float32) * k_scale).astype(q.dtype)
        v = (v.astype(jnp.float32) * v_scale).astype(q.dtype)
    return _xla_attention(q, k, v, bias, causal, query_offset, dropout_rate,
                          dropout_rng, deterministic, softmax_in_fp32,
                          kv_cache_layout=kv_cache_layout,
                          sm_scale=sm_scale, sliding_window=sliding_window)
