"""Solar-Open2-style hybrid decoder on the serving path: one layer in
four (``gqa_layers``) is softmax grouped-query attention with no
position encoding and a sigmoid output gate; the others are Kimi Delta
Attention, a gated delta rule whose memory is a float32 state a head
instead of keys and values; every layer ends in a sigmoid-routed
expert layer with a shared expert, of which this chip holds a share.

Equations: ``reference.py`` (the plain float32 reference the tests hold
this file to) and ``docs/solar_open2.md``. bfloat16 weights and
activations where the config says so; float32: the recurrent state and
everything that forms it (the convolution's output, the L2 norms, the
decay, the step size, the delta), the router, every softmax and every
RMSNorm statistic.

The module honours the apply protocol of ``models/gpt/generation.py``
(``use_cache``, ``cache_lengths``, ``page_table``, ``chunk_start``,
``chunk_valid``, a ``cache`` collection), so ``GenerationServer``
serves it through the entry points it serves GPT through. Paged only.

Two kinds of cache (``docs/solar_open2.md``). A softmax layer's K/V
leaves are ``cached_key`` / ``cached_value`` ``[kv_pool_pages, g, d,
page]``, reached through the server's page table
(``models/smallthinker``'s global layers' path). A delta layer's are
``kda_state [state_rows, H, d, d]`` float32 and ``conv_tail
[state_rows, (taps - 1) 3 H d]``: one row a slot behind the null row 0,
not paged, not growing. The row's id rides behind the ``max_kv_pages``
columns of the table the server hands over (``core/serving.py::
_sync_pt``): ``1 + slot`` in a prefill chunk and for an active slot of
a decode tick, 0 for a slot that is free or still prefilling, whose
rows a tick therefore leaves alone. A chunk that starts a sequence
(``chunk_start == 0``) starts from zeros whatever the row held;
positions at or past ``chunk_valid`` (the padded tail of a prompt's
last chunk) leave state and tail as they were.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...observability import metrics
from ...ops.linear_attention import kda_chunk, kda_step
from ...ops.pallas.flash_attention import NULL_PAGE
from ..deepseek_v3.moe import route, routed_experts
from ..smallthinker.model import Attention, RMSNorm
from .config import SolarOpen2Config

_HI = jax.lax.Precision.HIGHEST


def _init(cfg: SolarOpen2Config):
    return nn.initializers.normal(stddev=cfg.initializer_range)


def state_rows(page_table, cfg: SolarOpen2Config):
    """``(pages [n, max_kv_pages], rows [n])`` from the server's table:
    its first ``max_kv_pages`` columns are the page class's, the one
    behind them each row's state row (absent when only shapes are
    asked for, ``init_page_pool``: the null row then)."""
    pt = jnp.asarray(page_table, jnp.int32)
    pages = cfg.max_kv_pages
    if pt.shape[1] == pages:
        return pt, jnp.zeros((pt.shape[0],), jnp.int32)
    return pt[:, :pages], pt[:, pages]


def short_conv(seq, weight, bias=None):
    """``c_t = silu(b + sum_j w_j x_{t - (taps - 1) + j})`` over ``seq
    [n, taps - 1 + L, C]`` (the tail before the sequence, then its
    ``L`` inputs) with ``weight [taps, C]`` and, where the layer has
    one (``models/granite_hybrid``), ``bias [C]``; float32 ``[n, L,
    C]``."""
    taps = weight.shape[0]
    length = seq.shape[1] - (taps - 1)
    seq, weight = seq.astype(jnp.float32), weight.astype(jnp.float32)
    out = sum(weight[j] * seq[:, j:j + length] for j in range(taps))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return jax.nn.silu(out)


def carried_in(state, tail, rows, taps, chunk_start=None):
    """``(before [n, taps - 1, C], s0)``: what rows ``rows [n]`` of a
    recurrent layer's two leaves (``state [R, ...]`` float32, ``tail
    [R, (taps - 1) C]``) carry into this call. A prefill chunk
    (``chunk_start [n]``) that starts a sequence starts from nothing,
    whatever the slot's last tenant left in the row; a decode tick
    (``chunk_start`` None) reads its state in the kernel: ``s0`` is
    None."""
    n = rows.shape[0]
    before = tail.value[rows].reshape(n, taps - 1, -1)
    if chunk_start is None:
        return before, None
    start = jnp.asarray(chunk_start, jnp.int32) == 0
    lead = (n,) + (1,) * (state.value.ndim - 1)
    return (jnp.where(start[:, None, None], 0, before),
            jnp.where(start.reshape(lead), 0.0, state.value[rows]))


def keep_tick_tail(tail, rows, seq):
    """A tick's batch row i is slot i, whose row is 1 + i where it is
    live: a select over the slots' rows where they lie (a scatter by
    ``rows`` is one serial write a slot: 0.65 ms a layer at 96 slots;
    my chip run, PR 31)."""
    n = rows.shape[0]
    mine = tail.value[1:1 + n]
    tail.value = tail.value.at[1:1 + n].set(jnp.where(
        (rows != 0)[:, None], seq[:, 1:].reshape(n, -1), mine))


def keep_chunk(state, tail, rows, s_end, seq, valid, taps):
    """A chunk's end: the state after its last real token, and the
    ``taps - 1`` last inputs at or before it (``seq [n, taps - 1 + L,
    C]``, ``valid [n]`` real tokens). A chunk's rows are slots' own:
    no two alike."""
    n = rows.shape[0]
    state.value = state.value.at[rows].set(s_end, unique_indices=True)
    tail.value = tail.value.at[rows].set(jax.vmap(
        lambda s, at: jax.lax.dynamic_slice_in_dim(
            s, at, taps - 1))(seq, valid).reshape(n, -1),
        unique_indices=True)


def _l2_normalize(x):
    return x * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-12)


class DeltaAttention(nn.Module):
    """Kimi Delta Attention of one layer on ``h [n, L, hidden]``."""
    config: SolarOpen2Config

    @nn.compact
    def __call__(self, h, use_cache=False, cache_lengths=None, rows=None,
                 chunk_start=None, chunk_valid=None):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        heads, d = cfg.linear_num_heads, cfg.linear_head_dim
        taps, chans = cfg.short_conv_kernel_size, cfg.conv_channels
        n, length, _ = h.shape
        f32 = jnp.float32

        def dense(features, name, axis=-1, bias=False):
            return nn.DenseGeneral(
                features, axis=axis, use_bias=bias, name=name,
                dtype=dtype, param_dtype=pdtype, kernel_init=_init(cfg))

        def low_rank(name, bias=False):
            """``(h W_1) W_2`` through the rank of a head, float32."""
            w1 = self.param(name + "_a", _init(cfg),
                            (cfg.hidden_size, d), pdtype)
            w2 = self.param(name + "_b", _init(cfg), (d, heads, d), pdtype)
            mid = jnp.einsum("nlh,hr->nlr", h, w1.astype(dtype),
                             preferred_element_type=f32)
            return jnp.einsum("nlr,rkd->nlkd", mid, w2.astype(f32),
                              precision=_HI)

        fresh = jnp.concatenate(
            [dense(heads * d, name)(h) for name in
             ("q_proj", "k_proj", "v_proj")], axis=-1)     # [n, L, 3 H d]
        conv_w = self.param("conv_weight", _init(cfg), (taps, chans),
                            pdtype)
        a_log = self.param("A_log", _init(cfg), (heads,), pdtype)
        dt_bias = self.param("dt_bias", _init(cfg), (heads, d), pdtype)
        w_b = self.param("b_proj", _init(cfg), (cfg.hidden_size, heads),
                         pdtype)
        gate_bias = self.param("g_proj_bias", nn.initializers.zeros_init(),
                               (heads, d), pdtype)
        # log a = -exp(A_log) softplus(low_rank + dt_bias) < 0
        log_a = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            low_rank("f_proj") + dt_bias.astype(f32))
        beta = 2.0 * jax.nn.sigmoid(jnp.einsum(
            "nlh,hk->nlk", h, w_b.astype(dtype),
            preferred_element_type=f32))

        decode = use_cache and cache_lengths is not None
        if use_cache:
            if rows is None:
                raise NotImplementedError(
                    "the Solar-Open2 family is served through the paged "
                    "pool only (GenerationServer(page_size=...))")
            metrics.inc("attention/kda_layers")
            state = self.variable(
                "cache", "kda_state", jnp.zeros,
                (cfg.state_rows, heads, d, d), f32)
            # a row's taps - 1 last inputs side by side: a 2-D leaf,
            # which the compiler has no second layout for (as [rows,
            # taps - 1, C] it re-laid the leaf out around every write)
            tail = self.variable(
                "cache", "conv_tail", jnp.zeros,
                (cfg.state_rows, (taps - 1) * chans), dtype)
            before, s0 = carried_in(
                state, tail, rows, taps,
                None if decode else chunk_start)
        else:
            before = jnp.zeros((n, taps - 1, chans), dtype)
            s0 = jnp.zeros((n, heads, d, d), f32)
        seq = jnp.concatenate([before, fresh], axis=1)
        conv = short_conv(seq, conv_w).reshape(n, length, 3, heads, d)
        q = _l2_normalize(conv[:, :, 0]) * d ** -0.5
        k = _l2_normalize(conv[:, :, 1])
        v = conv[:, :, 2]

        if decode:
            keep_tick_tail(tail, rows, seq)
            state.value, out = kda_step(
                state.value, rows, q[:, 0], k[:, 0], v[:, 0],
                jnp.exp(log_a[:, 0]), beta[:, 0],
                use_kernel=cfg.use_flash_attention)
            out = out[:, None]
        else:
            valid = jnp.full((n,), length, jnp.int32) \
                if chunk_valid is None \
                else jnp.asarray(chunk_valid, jnp.int32)
            real = jnp.arange(length)[None, :] < valid[:, None]
            out, s_end = kda_chunk(
                q, k, v, jnp.where(real[..., None, None], log_a, 0.0),
                jnp.where(real[..., None], beta, 0.0), s0)
            if use_cache:
                keep_chunk(state, tail, rows, s_end, seq, valid, taps)
        out = RMSNorm(cfg, name="o_norm")(out).astype(f32) \
            * jax.nn.sigmoid(low_rank("g_proj") + gate_bias.astype(f32))
        return dense(cfg.hidden_size, "o_proj", axis=(-2, -1))(
            out.astype(dtype))


class SharedAndRoutedExperts(nn.Module):
    """``sum_{e in T, e held} w_e E_e(u) + E_shared(u)``: the router of
    ``models/deepseek_v3/moe.py`` (sigmoid scores over ALL
    ``n_routed_experts``, a selection bias, top-k, normalised), its
    dropless lowering over the ``experts_held`` this chip holds, the
    shared expert. ``live [N]`` (a tick's rows that hold a request, a
    chunk's real tokens) keeps dead rows out of the dispatch. Returns
    ``(out, [picks dispatched, distinct experts touched])``."""
    config: SolarOpen2Config

    @nn.compact
    def __call__(self, u, live=None):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        lo, hi = cfg.held_experts
        e, k = cfg.n_routed_experts, cfg.num_experts_per_tok
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        w_router = self.param("gate", _init(cfg), (h, e), pdtype)
        bias = self.param("e_score_correction_bias",
                          nn.initializers.zeros_init(), (e,), pdtype)
        # gate | up side by side on the last axis, the layout the
        # grouped product reads (models/smallthinker)
        w_gate_up = self.param("experts_gate_up", _init(cfg),
                               (hi - lo, h, 2 * f), pdtype)
        w_down = self.param("experts_down", _init(cfg), (hi - lo, f, h),
                            pdtype)
        x = u.reshape(-1, h)
        idx, weights = route(
            x.astype(jnp.float32), w_router.astype(jnp.float32),
            bias.astype(jnp.float32), k, cfg.routed_scaling_factor)
        if live is not None:
            idx = jnp.where(live[:, None], idx, e)
        routed, plan = routed_experts(
            x.astype(dtype), idx, weights, w_gate_up.astype(dtype),
            w_down.astype(dtype), lo, hi)
        sizes = plan["sizes"]

        def dense(features, name):
            return nn.DenseGeneral(
                features, use_bias=False, name=name, dtype=dtype,
                param_dtype=pdtype, kernel_init=_init(cfg))
        # models/deepseek_v3/moe.py::GatedMLP's sum without its
        # partitioning boxes: a served tree holds plain arrays
        gu = dense(2 * f * cfg.n_shared_experts, "shared_gate_up")(u)
        gate, up = jnp.split(gu, 2, axis=-1)
        shared = dense(h, "shared_down")(jax.nn.silu(gate) * up)
        return routed.reshape(u.shape).astype(dtype) + shared, jnp.stack(
            [jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(jnp.int32)


class DecoderLayer(nn.Module):
    """``x' = x + Mixer(RMSNorm(x))``; ``y = x' + Experts(RMSNorm(x'))``
    with the mixer the layer's kind."""
    config: SolarOpen2Config
    index: int

    @nn.compact
    def __call__(self, x, positions, live=None, use_cache=False,
                 cache_lengths=None, pages=None, rows=None,
                 chunk_start=None, chunk_valid=None):
        cfg = self.config
        h = RMSNorm(cfg, name="input_layernorm")(x)
        if cfg.is_gqa(self.index):
            x = x + Attention(cfg, rope=False, window=False, gate=True,
                              name="self_attn")(
                h, positions, use_cache=use_cache,
                cache_lengths=cache_lengths,
                tables=None if pages is None else (pages, None),
                chunk_start=chunk_start)
        else:
            x = x + DeltaAttention(cfg, name="linear_attn")(
                h, use_cache=use_cache, cache_lengths=cache_lengths,
                rows=rows, chunk_start=chunk_start,
                chunk_valid=chunk_valid)
        y, stats = SharedAndRoutedExperts(cfg, name="mlp")(
            RMSNorm(cfg, name="post_attention_layernorm")(x), live)
        return x + y, stats


class SolarOpen2ForCausalLM(nn.Module):
    """Embedding -> layers -> RMSNorm -> an untied head; logits ``[b,
    s, V]``. ``cache/moe_stats`` as ``models/smallthinker`` has it."""
    config: SolarOpen2Config

    @nn.compact
    def __call__(self, input_ids, position_ids=None,
                 use_cache: bool = False, deterministic: bool = True,
                 cache_lengths=None, page_table=None, chunk_start=None,
                 chunk_valid=None, adapter_ids=None):
        del deterministic, adapter_ids          # no dropout, no adapters
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        table = self.param("embed_tokens", _init(cfg),
                           (cfg.vocab_size, cfg.hidden_size), pdtype)
        head = self.param("lm_head", _init(cfg),
                          (cfg.hidden_size, cfg.vocab_size), pdtype)
        n, length = input_ids.shape
        cache = {}
        live = None
        if use_cache:
            pages = rows = None
            if page_table is not None:
                pages, rows = state_rows(page_table, cfg)
            cache = dict(use_cache=True, cache_lengths=cache_lengths,
                         pages=pages, rows=rows, chunk_start=chunk_start,
                         chunk_valid=chunk_valid)
            if cache_lengths is not None and pages is not None:
                # a free slot's row is all NULL_PAGE (_sync_pt)
                live = pages[:, 0] != NULL_PAGE
            elif chunk_valid is not None:
                live = (jnp.arange(length)[None, :] < jnp.asarray(
                    chunk_valid, jnp.int32)[:, None]).reshape(-1)
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
