"""SmallThinker-style decoder on the serving path: RMSNorm, grouped-
query attention whose layers are either global without any position
encoding or windowed with rotate-half RoPE, and in every layer a top-k
ReGLU expert layer whose router reads the layer's INPUT, before the
norm and before attention.

Equations: ``reference.py`` (the plain float32 reference the tests hold
this file to) and ``docs/smallthinker.md``. bfloat16 weights and
activations where the config says so; the router (logits, top-k, the
softmax over the picked logits), every attention softmax and every
RMSNorm statistic are float32.

The module honours the apply protocol of ``models/gpt/generation.py``
(``use_cache``, ``cache_lengths``, ``page_table``, ``chunk_start``,
``position_ids``, a ``cache`` collection), so ``GenerationServer``
serves it through the entry points it serves GPT through: chunked
paged prefill (``prefill_chunk_paged``), ``decode_step``. Paged only:
there is no contiguous slot cache for this family.

Two page classes (``docs/smallthinker.md``). A global layer's K/V
leaves are ``cached_key`` / ``cached_value`` ``[kv_pool_pages, g, d,
page]``, reached through the server's page table as GPT's are. A
window layer's are ``window_key`` / ``window_value``
``[window_pool_pages, g, d, page]``: one ring of ``window_ring_pages``
pages a slot; logical page ``j`` of a row lives in the row's ring page
``j % ring``, so a page that has fallen wholly behind the window is
the one the next page is written over. The ring's page ids ride behind
the ``max_kv_pages`` global columns of the page table the server hands
over; the window layers' table is made of them here, ``NULL_PAGE``
wherever the global table has it (a free slot stays dead in both).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...observability import metrics
from ...ops.attention import (
    dot_product_attention, kv_cache_write, paged_prefill_attention,
)
from ...ops.pallas.flash_attention import NULL_PAGE
from ..deepseek_v3.moe import routed_experts
from .config import SmallThinkerConfig


def _init(cfg: SmallThinkerConfig):
    return nn.initializers.normal(stddev=cfg.initializer_range)


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * scale``, statistics in float32."""
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],), jnp.dtype(cfg.param_dtype))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
            + cfg.rms_norm_eps)
        return (y * scale.astype(jnp.float32)).astype(jnp.dtype(cfg.dtype))


def apply_rope(x, positions, theta: float):
    """Rotate-half rotary embedding over the whole last axis of ``x
    [b, s, h, d]`` at ``positions [b, s]``: pairs ``(i, i + d/2)``,
    angle ``position * theta^(-2i/d)``. Computed in float32."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def window_table(page_table, cfg: SmallThinkerConfig):
    """``(global [n, max_kv_pages], window [n, max_kv_pages])`` from
    the server's table: its first ``max_kv_pages`` columns are the
    global class's, the ``window_ring_pages`` behind them the row's
    ring (absent when only shapes are asked for: ``init_page_pool``)."""
    pt = jnp.asarray(page_table, jnp.int32)
    pages, ring = cfg.max_kv_pages, cfg.window_ring_pages
    glob = pt[:, :pages]
    if pt.shape[1] == pages:
        return glob, jnp.zeros_like(glob)
    cols = jnp.take(pt[:, pages:], jnp.arange(pages) % ring, axis=1)
    return glob, jnp.where(glob != NULL_PAGE, cols, NULL_PAGE)


class Attention(nn.Module):
    """Grouped-query attention of one layer; ``rope`` and ``window``
    are the layer's entries of ``rope_layout`` and
    ``sliding_window_layout``. With ``gate`` (``models/solar_open2``;
    any config with this one's attention keys) the heads' output meets
    ``sigmoid(h W_gate)``, one a channel, before ``W_o``. With
    ``query_scale`` (``models/granite_hybrid``, whose scores are scaled
    by a multiplier of its own) the queries are multiplied by it before
    any path reads them, so that the paths' ``head_dim ** -0.5`` makes
    the model's scale of it: every path, dense, paged prefill and the
    decode kernel, then scores alike with no argument threaded through
    them; the config keeps the factor a power of two, which no float
    dtype rounds. With ``qk_norm`` (``models/exaone_moe``) each
    head's channels of ``q`` and of ``k`` pass an RMSNorm of their own
    weight vector (one a layer for the queries, one for the keys)
    before the rotation, float32 statistics as every norm's."""
    config: SmallThinkerConfig
    rope: bool
    window: bool
    gate: bool = False
    query_scale: float = 1.0
    qk_norm: bool = False

    @nn.compact
    def __call__(self, h, positions, use_cache=False, cache_lengths=None,
                 tables=None, chunk_start=None):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        nh, g, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)

        def dense(features, name, axis=-1):
            return nn.DenseGeneral(
                features, axis=axis, use_bias=False, name=name,
                dtype=dtype, param_dtype=pdtype, kernel_init=_init(cfg))

        q = dense((nh, d), "q_proj")(h)
        k = dense((g, d), "k_proj")(h)
        v = dense((g, d), "v_proj")(h)
        if self.qk_norm:
            metrics.inc("attention/qk_norm_layers")
            q = RMSNorm(cfg, name="q_norm")(q)
            k = RMSNorm(cfg, name="k_norm")(k)
        if self.rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        if self.query_scale != 1.0:
            q = q * jnp.asarray(self.query_scale, q.dtype)
        reach = cfg.sliding_window_size if self.window else None
        if not use_cache:
            out = dot_product_attention(
                q, k, v, causal=True, deterministic=True,
                sliding_window=reach)
        else:
            out = self._paged(q, k, v, reach, cache_lengths, tables,
                              chunk_start)
        if self.gate:
            out = out * jax.nn.sigmoid(dense((nh, d), "gate_proj")(
                h).astype(jnp.float32)).astype(out.dtype)
        return dense(cfg.hidden_size, "o_proj", axis=(-2, -1))(out)

    def _paged(self, q, k, v, reach, cache_lengths, tables, chunk_start):
        """Write the fresh K/V into this layer's class of pages, then
        attend through that class's table."""
        cfg = self.config
        if tables is None:
            raise NotImplementedError(
                "the SmallThinker family is served through the paged "
                "pool only (GenerationServer(page_size=...))")
        page, g, d = cfg.kv_page_size, cfg.num_key_value_heads, cfg.head_dim
        names, pool, pt = (("window_key", "window_value"),
                           cfg.window_pool_pages, tables[1]) \
            if self.window else (("cached_key", "cached_value"),
                                 cfg.kv_pool_pages, tables[0])
        leaves = [self.variable("cache", name, jnp.zeros,
                                (pool, g, d, page), jnp.dtype(cfg.dtype))
                  for name in names]
        n, w = q.shape[:2]
        if cache_lengths is not None:
            wpos = jnp.clip(
                jnp.asarray(cache_lengths, jnp.int32)[:, None]
                + jnp.arange(w, dtype=jnp.int32)[None, :], 0,
                cfg.cache_capacity - 1)
            pid = jnp.take_along_axis(pt, wpos // page, axis=1)
            written = kv_cache_write(
                [(leaf.value, t) for leaf, t in zip(leaves, (k, v))],
                pid, wpos % page, use_flash=cfg.use_flash_attention)
            for leaf, new in zip(leaves, written):
                leaf.value = new
            return dot_product_attention(
                q, leaves[0].value, leaves[1].value, causal=True,
                query_offset=wpos[:, 0], deterministic=True,
                use_flash=cfg.use_flash_attention, kv_cache_layout=True,
                page_table=pt, sliding_window=reach)
        if chunk_start is None:
            raise ValueError("page_table requires cache_lengths (decode)"
                             " or chunk_start (chunked prefill)")
        if w % page:
            raise ValueError(f"chunked prefill length {w} must be a "
                             f"multiple of kv_page_size {page}")
        c0 = jnp.asarray(chunk_start, jnp.int32)
        pids = jnp.take_along_axis(
            pt, (c0 // page)[:, None]
            + jnp.arange(w // page, dtype=jnp.int32)[None, :], axis=1)
        for leaf, t in zip(leaves, (k, v)):
            # [n, w, g, d] -> [n, w / page, g, d, page] whole pages
            leaf.value = leaf.value.at[pids].set(
                t.transpose(0, 2, 3, 1).reshape(
                    n, g, d, w // page, page).transpose(0, 3, 1, 2, 4))
        return paged_prefill_attention(
            q, leaves[0].value, leaves[1].value, c0, pt,
            sliding_window=reach)


class SparseExperts(nn.Module):
    """``sum_{e in T} w_e W_down,e (relu(W_gate,e u) * (W_up,e u))`` with
    ``T = top_k(r)``, ``w = softmax(r_T)``, ``r`` the router's logits
    handed in (they were formed from the layer's input): the dropless
    lowering of ``models/deepseek_v3/moe.py`` over all the experts,
    which this chip holds. ``live [N]`` (a decode tick's rows that hold
    a request) keeps dead rows out of the dispatch. Returns ``(out,
    [picks dispatched, distinct experts touched])``."""
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, u, logits, live=None):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        e, k = (cfg.moe_num_primary_experts,
                cfg.moe_num_active_primary_experts)
        h, f = cfg.hidden_size, cfg.moe_ffn_hidden_size
        # gate | up side by side on the last axis, the layout the
        # grouped product reads: a [e, h, 2, f] leaf would be re-laid
        # out by a copy of all the experts' weights in every program
        # (my chip run, PR 29: 2.2 ms a layer, half the tick)
        w_gate_up = self.param("experts_gate_up", _init(cfg),
                               (e, h, 2 * f), pdtype)
        w_down = self.param("experts_down", _init(cfg), (e, f, h), pdtype)
        picked, idx = jax.lax.top_k(logits, k)
        weights = jax.nn.softmax(picked, axis=-1)
        if live is not None:
            idx = jnp.where(live[:, None], idx, e)
        routed, plan = routed_experts(
            u.reshape(-1, h).astype(dtype), idx, weights,
            w_gate_up.astype(dtype), w_down.astype(dtype), 0, e,
            activation=jax.nn.relu)
        sizes = plan["sizes"]
        return routed.reshape(u.shape).astype(dtype), jnp.stack(
            [jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(jnp.int32)


class DecoderLayer(nn.Module):
    """``r = x W_r``; ``x' = x + Attention(RMSNorm(x))``; ``y = x' +
    Experts(RMSNorm(x'), r)``."""
    config: SmallThinkerConfig
    index: int

    @nn.compact
    def __call__(self, x, positions, live=None, **cache):
        cfg = self.config
        router = self.param(
            "router", _init(cfg),
            (cfg.hidden_size, cfg.moe_num_primary_experts),
            jnp.dtype(cfg.param_dtype))
        logits = jnp.dot(
            x.reshape(-1, cfg.hidden_size).astype(jnp.float32),
            router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        x = x + Attention(
            cfg, rope=bool(cfg.rope_layout[self.index]),
            window=bool(cfg.sliding_window_layout[self.index]),
            name="self_attn")(
                RMSNorm(cfg, name="input_layernorm")(x), positions, **cache)
        y, stats = SparseExperts(cfg, name="block_sparse_moe")(
            RMSNorm(cfg, name="post_attention_layernorm")(x), logits, live)
        return x + y, stats


class SmallThinkerForCausalLM(nn.Module):
    """Embedding -> layers -> RMSNorm -> an untied head; logits ``[b,
    s, V]``. The ``cache`` collection's ``moe_stats`` ``[4]`` int32
    grows by the picks dispatched and the distinct experts touched (a
    layer, summed over layers): entries 0-1 in a decode tick
    (``cache_lengths``), 2-3 in a prefill chunk. The server reads it
    outside its ticks (``moe/decode_picks``, ``moe/experts_touched``)."""
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None,
                 use_cache: bool = False, deterministic: bool = True,
                 cache_lengths=None, page_table=None, chunk_start=None,
                 chunk_valid=None, adapter_ids=None):
        # no dropout, no adapters; a padded tail is masked by position
        del deterministic, adapter_ids, chunk_valid
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        table = self.param("embed_tokens", _init(cfg),
                           (cfg.vocab_size, cfg.hidden_size), pdtype)
        head = self.param("lm_head", _init(cfg),
                          (cfg.hidden_size, cfg.vocab_size), pdtype)
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None, :],
                input_ids.shape)
        cache = {}
        live = None
        if use_cache:
            tables = None if page_table is None else \
                window_table(page_table, cfg)
            cache = dict(use_cache=True, cache_lengths=cache_lengths,
                         tables=tables, chunk_start=chunk_start)
            if cache_lengths is not None and tables is not None:
                # a free slot's row is all NULL_PAGE (_sync_pt)
                live = tables[0][:, 0] != NULL_PAGE
        x = jnp.take(table, input_ids, axis=0).astype(dtype)
        stats = jnp.zeros((2,), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            x, st = DecoderLayer(cfg, index=i, name=f"layers_{i}")(
                x, position_ids, live, **cache)
            stats = stats + st
        if use_cache:
            total = self.variable("cache", "moe_stats", jnp.zeros, (4,),
                                  jnp.int32)
            zero = jnp.zeros_like(stats)
            total.value = total.value + jnp.concatenate(
                [stats, zero] if cache_lengths is not None
                else [zero, stats])
        x = RMSNorm(cfg, name="norm")(x)
        return jnp.einsum("bsh,hv->bsv", x, head.astype(dtype))
