"""Plain float32 reference of the K-EXAONE-style decoder and of its
multi-token-prediction block: the forward pass in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, cache, paging,
chunking, batching trick or speculation, dense masks, the held experts
by a loop, and nothing imported from the model it checks (it shares
only the LAYOUT of the parameter tree).

``cfg`` is any mapping with the architecture's keys (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``layer_types``, ``mlp_layer_types``, ``sliding_windows``,
``num_experts``, ``num_experts_per_tok``, ``routed_scaling_factor``,
``rms_norm_eps``, ``rope_theta``, ``experts_held``).

Equations, with ``x`` the residual stream entering layer ``l``; what
the config's keys do not spell out is the EXAONE 4.0 family's published
convention (arXiv:2507.11407), marked (a)-(e):

0. (a) post-norm: ``x' = x + RMSNorm(Attn(x))``, ``x'' = x' +
   RMSNorm(FFN(x'))``, each norm with its own weight; after the last
   layer ``logits = RMSNorm(x) W_head`` (an untied head).
1. attention: ``q = x W_q`` (heads x d), ``k = x W_k``, ``v = x W_v``
   (K/V heads x d), no bias; (b) RMSNorm over each head's ``d``
   channels of ``q`` and of ``k``, one weight vector each a layer;
   query head ``m g + j`` reads K/V head ``g``; scores ``q k^T /
   sqrt(d)``, softmax, ``Attn = concat(heads) W_o``.
   (c) a "sliding_attention" layer rotates ``q`` and ``k`` AFTER their
   norms (rotate-half over all ``d`` channels, pairs ``(i, i + d/2)``,
   angle ``p theta^(-2i/d)``) and shows key ``j`` to query ``i`` iff
   ``i - window < j <= i``; a "full_attention" layer has no position
   encoding and is causal over the whole context.
2. FFN: a "dense" layer ``W_d (silu(x W_g) * (x W_u))``; a "sparse"
   layer ``Shared(x) + sum_{e in top-k, e held} w_e Expert_e(x)``,
   every expert and the shared one the same gated MLP. Router (the
   DeepSeek-V3 key set): ``s = sigmoid(x W_r)`` over ALL experts; the
   ``k`` largest of ``s + b`` (``b`` selects and never weighs); ``w_e =
   s_e / sum of the picked s * routed_scaling_factor``.
3. (d) the multi-token-prediction block, in the DeepSeek-V3 form
   (arXiv:2412.19437, section 2.2): at position ``i``, with ``h_i`` the
   main model's output after its final norm and ``t_{i+1}`` the next
   token, ``u_i = W_p [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(h_i)]``, one
   decoder layer of the form above over ``u_0..u_i`` (a full layer;
   (e) its FFN sparse, as the last main layer's), its own final
   RMSNorm, the main model's ``W_head`` and ``E``: the logits of token
   ``i + 2``.

Departures from the published modelling code, each deliberate: the
expert sum is a dense loop over the HELD experts with a 0/w mask (the
published code gathers tokens per expert; same sum), and what experts
that are not held would add is left out, as in the program (one chip's
share: ``experts_held``); ``n_group`` = ``topk_group`` = 1 make the
grouped selection the plain top-k, which is what is written.
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


def attention(cfg, p, x, window):
    """Equation 1 on ``x [b, s, hidden]``; ``window`` 0 = a full
    layer."""
    nh, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    s = x.shape[1]
    q = jnp.einsum("bsh,hnd->bsnd", x, p["q_proj"]["kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", x, p["k_proj"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", x, p["v_proj"]["kernel"])
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    if window:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, nh // g, axis=2)
    v = jnp.repeat(v, nh // g, axis=2)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    if window:
        seen &= j > i - window
    scores = jnp.where(seen, scores, -jnp.inf)
    out = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", out, p["o_proj"]["kernel"])


def gated_mlp(x, gate_up, down):
    """``(silu(x W_g) * (x W_u)) W_d`` with ``[W_g | W_u]`` side by
    side on the last axis."""
    gate, up = jnp.split(x @ gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ down


def route(cfg, p, x):
    """``(idx [.., k], weights [.., k])`` of equation 2's router."""
    scores = jax.nn.sigmoid(x @ p["gate"])
    _, idx = jax.lax.top_k(scores + p["e_score_correction_bias"],
                           cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx, weights * cfg["routed_scaling_factor"]


def experts(cfg, p, x, held=None):
    """Equation 2's sparse layer: the shared expert and the held
    experts' part of the routed sum (``held`` defaults to the
    config's)."""
    idx, weights = route(cfg, p, x)
    lo, hi = held or cfg.get("experts_held") or (0, cfg["num_experts"])
    out = gated_mlp(x, p["shared_gate_up"]["kernel"],
                    p["shared_down"]["kernel"])
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        out += w_e[..., None] * gated_mlp(
            x, p["experts_gate_up"][e - lo], p["experts_down"][e - lo])
    return out


def layer(cfg, p, x, window, sparse):
    """Equations 0-2 for one layer."""
    eps = cfg["rms_norm_eps"]
    x = x + rms_norm(attention(cfg, p["self_attn"], x, window),
                     p["post_attention_layernorm"]["scale"], eps)
    y = experts(cfg, p["mlp"], x) if sparse else gated_mlp(
        x, p["mlp"]["input_linear"]["kernel"],
        p["mlp"]["output_linear"]["kernel"])
    return x + rms_norm(y, p["post_feedforward_layernorm"]["scale"], eps)


def hidden(cfg, params, input_ids):
    """``h [b, s, hidden]``: the stream after the final norm."""
    x = jnp.take(params["embed_tokens"], input_ids, axis=0)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(cfg, params[f"layers_{i}"], x,
                  cfg["sliding_windows"][i],
                  cfg["mlp_layer_types"][i] == "sparse")
    return rms_norm(x, params["norm"]["scale"], cfg["rms_norm_eps"])


def forward(cfg, params, input_ids):
    """Logits ``[b, s, V]`` of ``input_ids [b, s]``; ``params`` a
    float32 tree in the module's layout."""
    with jax.default_matmul_precision("highest"):
        return hidden(cfg, params, input_ids) @ params["lm_head"]


def mtp_logits(cfg, params, input_ids):
    """Equation 3, teacher-forced over ``input_ids [b, s]``: ``[b,
    s - 1, V]``, row ``i`` from ``h_i`` and token ``i + 1``, the
    distribution of token ``i + 2``."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        p = params["mtp"]
        h = hidden(cfg, params, input_ids)[:, :-1]
        emb = jnp.take(params["embed_tokens"], input_ids[:, 1:], axis=0)
        u = jnp.concatenate(
            [rms_norm(emb, p["enorm"]["scale"], eps),
             rms_norm(h, p["hnorm"]["scale"], eps)],
            axis=-1) @ p["eh_proj"]["kernel"]
        y = layer(cfg, p["layer"], u, 0, True)
        return rms_norm(y, p["norm"]["scale"], eps) @ params["lm_head"]
