"""Granite-4.0-H-style hybrid decoder on the serving path: nine layers
in ten are Mamba-2 state-space layers, whose memory is a float32 state
a head instead of keys and values; one in ten (``layer_types``) is
softmax grouped-query attention with no position encoding; every layer
ends in a dense gated MLP; the stream carries four multipliers and the
head is the embedding.

Equations: ``reference.py`` (the plain float32 reference the tests hold
this file to) and ``docs/granite_hybrid.md``. bfloat16 weights and
activations where the config says so; float32: the recurrent state and
everything that forms it (the convolution's output, ``dt`` after its
softplus, the decay, ``dt x B^T``), every softmax and every RMSNorm
statistic (the gated norm's too).

The module honours the apply protocol of ``models/gpt/generation.py``
(``use_cache``, ``cache_lengths``, ``page_table``, ``chunk_start``,
``chunk_valid``, a ``cache`` collection), so ``GenerationServer``
serves it through the entry points it serves GPT through. Paged only.

Two kinds of cache, the server's page class and state class as
``models/solar_open2`` has them (whose row column, convolution and
leaf bookkeeping are imported, not copied). A softmax layer's K/V
leaves are ``cached_key`` / ``cached_value`` ``[kv_pool_pages, g, d,
page]``. A state-space layer's are ``ssm_state [state_rows, H, P, N]``
float32 and ``conv_tail [state_rows, (taps - 1) C]`` with ``C`` the
convolution's channels (x, B and C side by side): one row a slot behind
the null row 0. A chunk that starts a sequence (``chunk_start == 0``)
starts from zeros whatever the row held; positions at or past
``chunk_valid`` get ``dt = 0`` (a decay of 1 and nothing added) and
leave state and tail as they were.

The scores' scale is ``attention_multiplier`` (1/64), not ``head_dim
** -0.5`` (1/8): the queries are multiplied by their ratio 1/8, a power
of two and so exact in bfloat16, and every attention path the family
shares with the others keeps its own ``head_dim ** -0.5``
(``Attention.query_scale``).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...observability import metrics
from ...ops.state_space import ssd_chunk, ssd_step
from ..smallthinker.model import Attention, RMSNorm
from ..solar_open2.model import (
    carried_in, keep_chunk, keep_tick_tail, short_conv, state_rows,
)
from .config import GraniteHybridConfig


def _init(cfg: GraniteHybridConfig):
    return nn.initializers.normal(stddev=cfg.initializer_range)


def _dense(cfg, features, name):
    return nn.DenseGeneral(
        features, use_bias=False, name=name, dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype), kernel_init=_init(cfg))


class StateSpaceMixer(nn.Module):
    """The Mamba-2 mixer of one layer on ``h [n, L, hidden]``."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, h, use_cache=False, cache_lengths=None, rows=None,
                 chunk_start=None, chunk_valid=None):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        heads, p, ns = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        inner, taps, chans = (cfg.mamba_d_inner, cfg.mamba_d_conv,
                              cfg.conv_channels)
        n, length, _ = h.shape
        f32 = jnp.float32
        # [z | xBC | dt] side by side: one product
        z, fresh, dt = jnp.split(
            _dense(cfg, inner + chans + heads, "in_proj")(h),
            [inner, inner + chans], axis=-1)
        conv_w = self.param("conv_weight", _init(cfg), (taps, chans),
                            pdtype)
        conv_b = self.param("conv_bias", _init(cfg), (chans,), pdtype)
        a_log = self.param("A_log", _init(cfg), (heads,), pdtype)
        dt_bias = self.param("dt_bias", _init(cfg), (heads,), pdtype)
        d_skip = self.param("D", nn.initializers.ones_init(), (heads,),
                            pdtype)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))

        decode = use_cache and cache_lengths is not None
        if use_cache:
            if rows is None:
                raise NotImplementedError(
                    "the Granite hybrid family is served through the "
                    "paged pool only (GenerationServer(page_size=...))")
            metrics.inc("attention/ssm_layers")
            state = self.variable("cache", "ssm_state", jnp.zeros,
                                  (cfg.state_rows, heads, p, ns), f32)
            # a 2-D leaf, as models/solar_open2 keeps its tail
            tail = self.variable(
                "cache", "conv_tail", jnp.zeros,
                (cfg.state_rows, (taps - 1) * chans), dtype)
            before, s0 = carried_in(state, tail, rows, taps,
                                    None if decode else chunk_start)
        else:
            before = jnp.zeros((n, taps - 1, chans), dtype)
            s0 = jnp.zeros((n, heads, p, ns), f32)
        seq = jnp.concatenate([before, fresh], axis=1)
        x, b, c = jnp.split(short_conv(seq, conv_w, conv_b),
                            [inner, inner + ns], axis=-1)
        x = x.reshape(n, length, heads, p)

        if decode:
            keep_tick_tail(tail, rows, seq)
            state.value, y = ssd_step(
                state.value, rows, x[:, 0], dt[:, 0],
                jnp.exp(-dt[:, 0] * jnp.exp(a_log.astype(f32))),
                b[:, 0], c[:, 0], d_skip.astype(f32),
                use_kernel=cfg.use_flash_attention)
            y = y[:, None]
        else:
            valid = jnp.full((n,), length, jnp.int32) \
                if chunk_valid is None \
                else jnp.asarray(chunk_valid, jnp.int32)
            real = jnp.arange(length)[None, :] < valid[:, None]
            y, s_end = ssd_chunk(
                x, jnp.where(real[..., None], dt, 0.0), a_log, b, c,
                d_skip, s0, block=cfg.mamba_chunk_size)
            if use_cache:
                keep_chunk(state, tail, rows, s_end, seq, valid, taps)
        # the gate folded into a norm over all the heads' channels
        y = y.reshape(n, length, inner) * jax.nn.silu(z.astype(f32))
        return _dense(cfg, cfg.hidden_size, "out_proj")(
            RMSNorm(cfg, name="norm")(y))


class GatedMLP(nn.Module):
    """``W_2 (silu(g) * u)`` with ``[g | u] = h W_1``, one fused
    kernel, no bias: ``models/deepseek_v3/moe.py::GatedMLP``'s sum
    without its partitioning boxes (a served tree holds plain arrays),
    as ``models/solar_open2`` writes its shared expert."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        gate, up = jnp.split(_dense(
            cfg, 2 * cfg.shared_intermediate_size, "input_linear")(h),
            2, axis=-1)
        return _dense(cfg, cfg.hidden_size, "output_linear")(
            jax.nn.silu(gate) * up)


class DecoderLayer(nn.Module):
    """``x' = x + r Mixer(RMSNorm(x))``; ``y = x' + r
    MLP(RMSNorm(x'))`` with ``r`` the residual multiplier and the mixer
    the layer's kind."""
    config: GraniteHybridConfig
    index: int

    @nn.compact
    def __call__(self, x, positions, use_cache=False, cache_lengths=None,
                 pages=None, rows=None, chunk_start=None,
                 chunk_valid=None):
        cfg = self.config
        r = jnp.asarray(cfg.residual_multiplier, x.dtype)
        h = RMSNorm(cfg, name="input_layernorm")(x)
        if cfg.is_attention(self.index):
            mixed = Attention(cfg, rope=False, window=False,
                              query_scale=cfg.query_scale,
                              name="self_attn")(
                h, positions, use_cache=use_cache,
                cache_lengths=cache_lengths,
                tables=None if pages is None else (pages, None),
                chunk_start=chunk_start)
        else:
            mixed = StateSpaceMixer(cfg, name="mamba")(
                h, use_cache=use_cache, cache_lengths=cache_lengths,
                rows=rows, chunk_start=chunk_start,
                chunk_valid=chunk_valid)
        x = x + r * mixed
        return x + r * GatedMLP(cfg, name="shared_mlp")(
            RMSNorm(cfg, name="post_attention_layernorm")(x))


class GraniteHybridForCausalLM(nn.Module):
    """Embedding times its multiplier -> layers -> RMSNorm -> the
    embedding as the head, over ``logits_scaling``; logits ``[b, s,
    V]``."""
    config: GraniteHybridConfig

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
        cache = {}
        if use_cache:
            pages = rows = None
            if page_table is not None:
                pages, rows = state_rows(page_table, cfg)
            cache = dict(use_cache=True, cache_lengths=cache_lengths,
                         pages=pages, rows=rows, chunk_start=chunk_start,
                         chunk_valid=chunk_valid)
        x = jnp.take(table, input_ids, axis=0).astype(dtype) \
            * jnp.asarray(cfg.embedding_multiplier, dtype)
        for i in range(cfg.num_hidden_layers):
            x = DecoderLayer(cfg, index=i, name=f"layers_{i}")(
                x, position_ids, **cache)
        x = RMSNorm(cfg, name="norm")(x)
        logits = jnp.einsum("bsh,vh->bsv", x, table.astype(dtype))
        return logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
