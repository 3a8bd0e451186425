"""K-EXAONE-style decoder on the serving path: post-norm blocks
(``x + RMSNorm(f(x))``), grouped-query attention with an RMSNorm over
every head of ``q`` and ``k``, layers that are either windowed with
rotary positions or global with no position encoding at all, a dense
gated MLP in the leading layer and sigmoid-routed experts with a shared
one in the rest (this chip holds a share of them), and behind the last
layer a multi-token-prediction block that DRAFTS for the server's
verify tick, on the device.

Equations: ``reference.py`` (the plain float32 reference the tests hold
this file to) and ``docs/exaone_moe.md``. bfloat16 weights and
activations where the config says so; float32: the router (scores,
selection, weights), every attention softmax and every RMSNorm
statistic, the head norms' too.

Nothing here is a layer of its own making: attention is
``models/smallthinker``'s ``Attention`` (rotary, window and head norms
are its arguments), the expert layer ``models/solar_open2``'s
(``models/deepseek_v3/moe.py``'s router and dropless lowering over the
``experts_held``), the dense MLP ``models/granite_hybrid``'s, the
window class and its table SmallThinker's.

The module honours the apply protocol of ``models/gpt/generation.py``
(``use_cache``, ``cache_lengths``, ``page_table``, ``chunk_start``,
``chunk_valid``, a ``cache`` collection), so ``GenerationServer``
serves it through the entry points it serves GPT through. Paged only.
Two page classes (``docs/exaone_moe.md``): a full layer's K/V live in
the allocator's pages (``cached_key`` / ``cached_value``), the
multi-token-prediction block's too (it is one more full layer); a
sliding layer's in a ring of ``window_ring_pages`` a slot
(``window_key`` / ``window_value``).

The draft source (``GenerationConfig.spec_method="mtp"``). What the
block needs never leaves the chip, so it lives in the ``cache``
collection: ``mtp_hidden [slots, 2, hidden]``, the final-norm outputs
``h`` of the positions the last launch wrote for each slot;
``mtp_len [slots]``, how many positions of the slot the block has
folded into its K/V; ``mtp_next [slots]``, its last draft. Position
``i`` of the block reads ``h_i`` and the NEXT token ``t_{i+1}``, so it
trails the main model by one token:

  * a prefill chunk with ``draft_inputs = (next tokens [n, chunk],
    slots [n])`` runs the block over the chunk's own positions with the
    tokens shifted by one (the host knows the prompt, so the chunk's
    last position has its next token too; only the prompt's last
    position has none, and waits for the first tick), writes its K/V
    into its layer of the page class and keeps ``h`` of the last real
    position;
  * ``draft=True`` is a verify tick's first half: ``input_ids`` is the
    token ``t0`` the tick has just sampled; the block folds the ``c =
    lengths - mtp_len`` positions (1 or 2) the last tick committed,
    whose next tokens are known now that ``t0`` is, and its argmax at
    the last of them is the draft for the position after ``t0``;
  * the tick's main forward over ``[t0, draft]`` then keeps both
    positions' ``h`` for the next tick.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...observability import metrics
from ...ops.pallas.flash_attention import NULL_PAGE
from ..granite_hybrid.model import GatedMLP
from ..smallthinker.model import Attention, RMSNorm, window_table
from ..solar_open2.model import SharedAndRoutedExperts
from .config import ExaoneMoeConfig


def _init(cfg: ExaoneMoeConfig):
    return nn.initializers.normal(stddev=cfg.initializer_range)


class DecoderLayer(nn.Module):
    """``x' = x + RMSNorm(Attention(x))``; ``y = x' + RMSNorm(FFN(x'))``
    with ``window`` and ``sparse`` the layer's kinds."""
    config: ExaoneMoeConfig
    window: bool
    sparse: bool

    @nn.compact
    def __call__(self, x, positions, live=None, **cache):
        cfg = self.config
        x = x + RMSNorm(cfg, name="post_attention_layernorm")(Attention(
            cfg, rope=self.window, window=self.window, qk_norm=True,
            name="self_attn")(x, positions, **cache))
        if self.sparse:
            y, stats = SharedAndRoutedExperts(cfg, name="mlp")(x, live)
        else:
            y, stats = GatedMLP(cfg, name="mlp")(x), \
                jnp.zeros((2,), jnp.int32)
        return x + RMSNorm(cfg, name="post_feedforward_layernorm")(y), \
            stats


class MTPBlock(nn.Module):
    """``u_i = W_p [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(h_i)]``, one full
    (global, NoPE) sparse decoder layer over ``u``, a final RMSNorm;
    the caller applies the main model's head. Returns ``(normed output,
    the layer's expert counts)``."""
    config: ExaoneMoeConfig

    @nn.compact
    def __call__(self, h, next_emb, positions, live=None, **cache):
        cfg = self.config
        metrics.inc("attention/mtp_layers")
        u = nn.Dense(
            cfg.hidden_size, use_bias=False, name="eh_proj",
            dtype=jnp.dtype(cfg.dtype),
            param_dtype=jnp.dtype(cfg.param_dtype),
            kernel_init=_init(cfg))(jnp.concatenate(
                [RMSNorm(cfg, name="enorm")(next_emb),
                 RMSNorm(cfg, name="hnorm")(h)], axis=-1))
        y, stats = DecoderLayer(cfg, window=False, sparse=True,
                                name="layer")(u, positions, live, **cache)
        return RMSNorm(cfg, name="norm")(y), stats


class ExaoneMoeForCausalLM(nn.Module):
    """Embedding -> layers -> RMSNorm -> an untied head; logits ``[b,
    s, V]``. With ``return_mtp`` (no cache) also the block's logits,
    teacher-forced: row ``i`` from ``h_i`` and token ``i + 1``, the
    distribution of token ``i + 2`` (the last row has no next token
    and means nothing). ``draft=True`` returns the drafts ``[slots,
    1]`` instead (module docstring). ``cache/moe_stats`` as
    ``models/smallthinker`` has it, the block's expert layer counted
    with the ticks'."""
    config: ExaoneMoeConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None,
                 use_cache: bool = False, deterministic: bool = True,
                 cache_lengths=None, page_table=None, chunk_start=None,
                 chunk_valid=None, adapter_ids=None, draft: bool = False,
                 draft_inputs=None, return_mtp: bool = False):
        del deterministic, adapter_ids          # no dropout, no adapters
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        table = self.param("embed_tokens", _init(cfg),
                           (cfg.vocab_size, cfg.hidden_size), pdtype)
        head = self.param("lm_head", _init(cfg),
                          (cfg.hidden_size, cfg.vocab_size), pdtype)
        n, length = input_ids.shape
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(length, dtype=jnp.int32)[None, :], (n, length))
        cache = {}
        live = rows_live = tables = None
        if use_cache:
            tables = None if page_table is None else \
                window_table(page_table, cfg)
            cache = dict(use_cache=True, cache_lengths=cache_lengths,
                         tables=tables, chunk_start=chunk_start)
            if cache_lengths is not None and tables is not None:
                # a free slot's row is all NULL_PAGE (_sync_pt)
                rows_live = tables[0][:, 0] != NULL_PAGE
                live = jnp.repeat(rows_live, length)
            elif chunk_valid is not None:
                live = (jnp.arange(length)[None, :] < jnp.asarray(
                    chunk_valid, jnp.int32)[:, None]).reshape(-1)
        blocks = cfg.num_nextn_predict_layers
        if blocks and tables is not None:
            width = cfg.verify_window
            # init_page_pool's trace: the cache is being shaped
            shaping = not self.has_variable("cache", "mtp_hidden")
            hidden = self.variable("cache", "mtp_hidden", jnp.zeros,
                                   (n, width, cfg.hidden_size), dtype)
            folded = self.variable("cache", "mtp_len", jnp.zeros, (n,),
                                   jnp.int32)
            drafted = self.variable("cache", "mtp_next", jnp.zeros, (n,),
                                    jnp.int32)

        def block(h, next_ids, positions, live, **cache):
            return MTPBlock(cfg, name="mtp")(
                h, jnp.take(table, next_ids, axis=0).astype(dtype),
                positions, live, **cache)

        def count(stats, tick):
            total = self.variable("cache", "moe_stats", jnp.zeros, (4,),
                                  jnp.int32)
            zero = jnp.zeros_like(stats)
            total.value = total.value + jnp.concatenate(
                [stats, zero] if tick else [zero, stats])

        if draft:
            # a verify tick's first half: fold what the last tick
            # committed, draft for the position after ``t0``
            held = jnp.asarray(cache_lengths, jnp.int32)
            done = folded.value
            due = jnp.clip(held - done, 1, width)
            cols = jnp.arange(width, dtype=jnp.int32)[None, :]
            next_ids = jnp.where(cols == (due - 1)[:, None],
                                 input_ids[:, :1], drafted.value[:, None])
            y, stats = block(
                hidden.value, next_ids,
                jnp.clip(done[:, None] + cols, 0,
                         cfg.max_position_embeddings - 1),
                (rows_live[:, None] & (cols < due[:, None])).reshape(-1),
                **dict(cache, cache_lengths=done))
            last = jnp.take_along_axis(
                y, (due - 1)[:, None, None], axis=1)[:, 0]
            token = jnp.argmax(jnp.einsum(
                "sh,hv->sv", last, head.astype(dtype),
                preferred_element_type=jnp.float32),
                axis=-1).astype(jnp.int32)
            folded.value = jnp.where(rows_live, held, done)
            drafted.value = jnp.where(rows_live, token, drafted.value)
            count(stats, tick=True)
            return token[:, None]

        x = jnp.take(table, input_ids, axis=0).astype(dtype)
        stats = jnp.zeros((2,), jnp.int32)
        for i in range(cfg.num_hidden_layers):
            x, st = DecoderLayer(
                cfg, window=cfg.is_window(i), sparse=cfg.is_sparse(i),
                name=f"layers_{i}")(x, position_ids, live, **cache)
            stats = stats + st
        if use_cache:
            count(stats, tick=cache_lengths is not None)
        x = RMSNorm(cfg, name="norm")(x)
        logits = jnp.einsum("bsh,hv->bsv", x, head.astype(dtype))
        if blocks and rows_live is not None:
            # a tick's positions, for the block's next draft
            hidden.value = hidden.value.at[:, :length].set(jnp.where(
                rows_live[:, None, None], x, hidden.value[:, :length]))
            if shaping:
                # the block's K/V leaves belong to the pool too
                block(x, input_ids, position_ids, live, **cache)
        if draft_inputs is not None:
            # a prefill chunk: the block's K/V of the chunk's positions
            # (its output is not needed: the first tick drafts), and
            # the last real position's ``h`` for that tick
            next_ids, slots = draft_inputs
            block(x, jnp.asarray(next_ids, jnp.int32), position_ids,
                  live, **cache)
            valid = jnp.full((n,), length, jnp.int32) \
                if chunk_valid is None \
                else jnp.asarray(chunk_valid, jnp.int32)
            hidden.value = hidden.value.at[slots, 0].set(
                jnp.take_along_axis(
                    x, (valid - 1)[:, None, None], axis=1)[:, 0])
            folded.value = folded.value.at[slots].set(
                jnp.asarray(chunk_start, jnp.int32) + valid - 1)
        if blocks and not use_cache and \
                (return_mtp or self.is_initializing()):
            y, _ = block(x, jnp.roll(input_ids, -1, axis=1), position_ids,
                         None)
            if return_mtp:
                return logits, jnp.einsum("bsh,hv->bsv", y,
                                          head.astype(dtype))
        return logits
