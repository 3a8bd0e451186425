"""Plain float32 reference of the SmallThinker-style decoder: the
forward pass in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, cache, paging,
batching trick or sort, every expert computed densely and combined by
the top-k weights, and nothing imported from the model it checks (it
shares only the LAYOUT of the parameter tree).

``cfg`` is any mapping with the architecture's keys (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``moe_num_primary_experts``,
``moe_num_active_primary_experts``, ``rms_norm_eps``, ``rope_theta``,
``rope_layout``, ``sliding_window_layout``, ``sliding_window_size``).

Equations, with ``x`` the residual stream entering layer ``l``:

1. router, before attention and before the norm: ``r = x W_r`` (float32).
2. attention: ``h = RMSNorm(x)``; ``q = h W_q`` (heads x d), ``k = h
   W_k``, ``v = h W_v`` (K/V heads x d), no bias, no q/k norm. Where
   ``rope_layout[l]`` is 1, rotate-half RoPE over all ``d`` dims (pairs
   ``(i, i + d/2)``, angle ``p theta^(-2i/d)``); where it is 0, no
   position encoding at all. Query head ``m g + j`` reads K/V head
   ``g``. Scale ``d^-1/2``, causal; where ``sliding_window_layout[l]``
   is 1, key ``j`` is visible to query ``i`` iff ``i - window < j <=
   i``. ``x' = x + concat(heads) W_o``.
3. experts: ``u = RMSNorm(x')``; ``T = top_k(r)``; ``w = softmax(r_T)``
   over the picked logits; ``E_e(u) = W_down,e (relu(W_gate,e u) *
   (W_up,e u))``; ``y = x' + sum_{e in T} w_e E_e(u)``.
4. after the last layer ``RMSNorm``, then an untied head.

Departures from the published modelling code, each deliberate: the
expert sum is a dense loop over all experts with a 0/w mask (the
published code gathers tokens per expert; same sum);
``norm_topk_prob`` is not applied (the softmax over the picked logits
already sums to 1); the secondary experts the family's description
mentions have no key in the published config and do not exist here.
Assumed, not checkable against the config alone: the router reads the
un-normalised stream; RoPE pairs by halves (rotate-half).
"""

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """``x [b, s, h, d]``; position = index along axis 1."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(cfg, p, h, layer):
    """Equation 2 for layer ``layer`` on ``h [b, s, hidden]``."""
    nh, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    b, s, _ = h.shape
    q = jnp.einsum("bsh,hnd->bsnd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", h, p["v_proj"]["kernel"])
    if cfg["rope_layout"][layer]:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    # query head m * i + j reads K/V head i
    k = jnp.repeat(k, nh // g, axis=2)
    v = jnp.repeat(v, nh // g, axis=2)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    if cfg["sliding_window_layout"][layer]:
        seen &= j > i - cfg["sliding_window_size"]
    scores = jnp.where(seen, scores, -jnp.inf)
    out = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", out, p["o_proj"]["kernel"])


def route(cfg, logits):
    """``(idx [.., k], weights [.., k])``: top-k of the logits, softmax
    over the picked ones."""
    picked, idx = jax.lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    return idx, jax.nn.softmax(picked, axis=-1)


def experts(cfg, p, u, logits):
    """Equation 3's sum: every expert for every token, masked by the
    picks' weights."""
    idx, weights = route(cfg, logits)
    out = jnp.zeros_like(u)
    for e in range(cfg["moe_num_primary_experts"]):
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        width = p["experts_down"].shape[1]
        gate = u @ p["experts_gate_up"][e, :, :width]
        up = u @ p["experts_gate_up"][e, :, width:]
        out += w_e[..., None] * ((jax.nn.relu(gate) * up)
                                 @ p["experts_down"][e])
    return out


def layer(cfg, p, x, index):
    """Equations 1-3 for one layer."""
    eps = cfg["rms_norm_eps"]
    logits = x @ p["router"]
    x = x + attention(cfg, p["self_attn"], rms_norm(
        x, p["input_layernorm"]["scale"], eps), index)
    u = rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    return x + experts(cfg, p["block_sparse_moe"], u, logits)


def forward(cfg, params, input_ids):
    """Logits ``[b, s, V]`` of ``input_ids [b, s]``; ``params`` a
    float32 tree in the module's layout."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed_tokens"], input_ids, axis=0)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, params[f"layers_{i}"], x, i)
        x = rms_norm(x, params["norm"]["scale"], cfg["rms_norm_eps"])
        return x @ params["lm_head"]
