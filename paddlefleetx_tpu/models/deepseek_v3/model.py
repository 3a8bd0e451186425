"""DeepSeek-V3-style decoder for pretraining: RMSNorm, multi-head latent
attention with interleaved RoPE on a slice of each head, SwiGLU MLPs, a
number of leading dense layers, then layers of routed experts
(``moe.py``), an untied head; embedding and head over the vocabulary
rows this process holds.

Equations: ISSUE 25 / ``reference.py`` (the plain float32 reference the
tests hold this file to). Logical axes are the GPT model's
(``parallel/sharding.py``); the expert layers are one ``nn.scan`` stack
(``expert_layers``) with the dense ones apart (``dense_layers_<i>``).

Training path only: no cache, no decode (a latent paged cache and an
absorbed decode path are ROADMAP's).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...ops.attention import dot_product_attention
from ...parallel.sharding import with_logical_constraint
from ..gpt.model import _remat_policy
from .config import DeepSeekV3Config
from .moe import DroplessMoE, GatedMLP, _init


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * scale``, statistics in float32."""
    config: DeepSeekV3Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = self.param(
            "scale", nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("norm",)),
            (x.shape[-1],), jnp.dtype(cfg.param_dtype))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
            + cfg.rms_norm_eps)
        return (y * scale.astype(jnp.float32)).astype(jnp.dtype(cfg.dtype))


def apply_rope(x, theta: float, interleave: bool):
    """Rotary embedding over the last axis of ``x [b, s, ..., d]``,
    position = index in the sequence. With ``interleave`` the pairs
    ``(2i, 2i+1)`` are first brought to the half-split layout, as the
    family's modelling code does; the rotation itself is the half-split
    one. Computed in float32."""
    d = x.shape[-1]
    x32 = x.astype(jnp.float32)
    if interleave:
        x32 = jnp.concatenate([x32[..., 0::2], x32[..., 1::2]], axis=-1)
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x1, x2 = x32[..., :d // 2], x32[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class MLA(nn.Module):
    """Multi-head latent attention (no query compression): q/k scored
    at ``qk_nope + qk_rope``, values of ``v_head_dim``, the rotary key
    one for all heads, k and v expanded from a normalised latent."""
    config: DeepSeekV3Config

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        nh, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                          cfg.qk_rope_head_dim)
        dv, rank = cfg.v_head_dim, cfg.kv_lora_rank

        def dense(features, name, axes, axis=-1):
            return nn.DenseGeneral(
                features, axis=axis, use_bias=False, name=name,
                dtype=dtype, param_dtype=pdtype,
                kernel_init=nn.with_logical_partitioning(_init(cfg), axes))

        q = dense((nh, nope + rope), "q_proj", ("embed", "heads", "kv"))(u)
        latent = dense(rank + rope, "kv_a_proj_with_mqa",
                       ("embed", None))(u)
        c_kv, k_rope = latent[..., :rank], latent[..., rank:]
        kv = dense((nh, nope + dv), "kv_b_proj", (None, "heads", "kv"))(
            RMSNorm(cfg, name="kv_a_layernorm")(c_kv))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q = jnp.concatenate(
            [q[..., :nope],
             apply_rope(q[..., nope:], cfg.rope_theta, cfg.rope_interleave)],
            axis=-1)
        k_rope = apply_rope(k_rope, cfg.rope_theta, cfg.rope_interleave)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                k_rope[:, :, None, :], k_nope.shape[:-1] + (rope,))],
            axis=-1)
        q, k, v = (with_logical_constraint(
            checkpoint_name(t, "attn"), ("batch", None, "act_heads", None))
            for t in (q, k, v))
        out = dot_product_attention(
            q, k, v, causal=True, deterministic=True,
            use_flash=cfg.use_flash_attention,
            sm_scale=(nope + rope) ** -0.5)
        out = checkpoint_name(out, "attn")
        out = dense(cfg.hidden_size, "o_proj", ("heads", "kv", "embed"),
                    axis=(-2, -1))(out)
        return checkpoint_name(out, "attn_out")


class DecoderLayer(nn.Module):
    """``h = x + MLA(norm(x))``, ``y = h + FFN(norm(h))``; returns
    ``(y, stats)``, ``stats`` the expert layer's float32 ``[3]`` (zeros
    for a dense layer), the ``(carry, ys)`` pair ``nn.scan`` wants."""
    config: DeepSeekV3Config
    dense: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = x + MLA(cfg, name="self_attn")(
            RMSNorm(cfg, name="input_layernorm")(x))
        u = RMSNorm(cfg, name="post_attention_layernorm")(h)
        if self.dense:
            y = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(u)
            stats = jnp.zeros((3,), jnp.float32)
        else:
            y, stats = DroplessMoE(cfg, name="mlp")(u)
        y = with_logical_constraint(h + y, ("batch", "seq", "act_embed"))
        return y, stats


class DeepSeekV3Model(nn.Module):
    """Embedding -> dense layers -> expert layers -> final RMSNorm.
    Sows the step's routing statistics (``stats/moe``: held picks summed
    over layers, the worst layer's largest-over-mean held group, all
    picks) for a caller that asks for the ``stats`` collection."""
    config: DeepSeekV3Config

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        lo, hi = cfg.held_vocab
        table = self.param(
            "embed_tokens", nn.with_logical_partitioning(
                _init(cfg), ("vocab", "embed")),
            (hi - lo, cfg.hidden_size), jnp.dtype(cfg.param_dtype))
        x = jnp.take(table, input_ids - lo, axis=0).astype(
            jnp.dtype(cfg.dtype))
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"))

        block = DecoderLayer
        if cfg.use_recompute:
            block = nn.remat(
                block, policy=_remat_policy(cfg.recompute_granularity),
                prevent_cse=not cfg.scan_layers)
        for i in range(cfg.first_k_dense_replace):
            x, _ = block(cfg, dense=True, name=f"dense_layers_{i}")(x)
        n_expert = cfg.num_expert_layers
        if n_expert and cfg.scan_layers:
            x, stats = nn.scan(
                block, variable_axes={"params": 0},
                split_rngs={"params": True}, length=n_expert,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="expert_layers")(x)
        elif n_expert:
            per_layer = []
            for i in range(n_expert):
                x, st = block(cfg, name=f"expert_layers_{i}")(x)
                per_layer.append(st)
            stats = jnp.stack(per_layer)
        if n_expert:
            self.sow("stats", "moe", jnp.stack(
                [jnp.sum(stats[:, 0]), jnp.max(stats[:, 1]),
                 jnp.sum(stats[:, 2])]))
        return RMSNorm(cfg, name="norm")(x)


def head_logits(x: jax.Array, head: jax.Array) -> jax.Array:
    """The untied head over the held vocabulary rows."""
    logits = jnp.einsum("bsh,hv->bsv", x, head.astype(x.dtype))
    return with_logical_constraint(logits, ("batch", "seq", "act_vocab"))


class DeepSeekV3ForPretraining(nn.Module):
    """The decoder with its own (untied) head: logits over the held
    vocabulary slice, label ``id - lo``."""
    config: DeepSeekV3Config

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        x = DeepSeekV3Model(cfg, name="model")(input_ids)
        lo, hi = cfg.held_vocab
        head = self.param(
            "lm_head", nn.with_logical_partitioning(
                _init(cfg), ("embed", "vocab")),
            (cfg.hidden_size, hi - lo), jnp.dtype(cfg.param_dtype))
        return head_logits(x, head)
