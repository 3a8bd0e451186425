"""Plain float32 reference of the Solar-Open2-style decoder: the
forward pass in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, cache, paging,
chunking, batching trick or sort; the linear-attention layer as the
token-by-token recurrence (a ``lax.scan`` over positions), its
convolution as four shifted sums, every held expert computed densely
and combined by the top-k weights; nothing imported from the model it
checks (it shares only the LAYOUT of the parameter tree).

``cfg`` is any mapping with the architecture's keys (``hidden_size``,
``num_hidden_layers``, ``gqa_layers``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``linear_num_heads``,
``linear_head_dim``, ``short_conv_kernel_size``, ``n_routed_experts``,
``num_experts_per_tok``, ``routed_scaling_factor``, ``rms_norm_eps``).

Equations, with ``x`` the residual stream entering layer ``l`` and ``h =
RMSNorm(x)``:

1. ``l`` not in ``gqa_layers`` — Kimi Delta Attention (arXiv:2510.26692),
   ``H`` heads of ``d``: ``q~ = h W_q``, ``k~ = h W_k``, ``v~ = h W_v``;
   a causal depthwise convolution of ``taps`` over time on every
   channel, zeros before the sequence, then SiLU: ``c_t = silu(sum_j
   w_j c~_{t - taps + 1 + j})``; a head: ``q_t = c^q_t / |c^q_t| d^-1/2``,
   ``k_t = c^k_t / |c^k_t|``, ``v_t = c^v_t``. Decay, a channel: ``a_t =
   exp(-exp(A_log) softplus((h W_f1) W_f2 + dt_bias))``. Step size
   ``b_t = 2 sigmoid(h W_b)``. State ``S [d, d]`` a head, zero at the
   start: ``S' = diag(a_t) S_{t-1}``; ``u_t = v_t - S'^T k_t``; ``S_t =
   S' + b_t k_t u_t^T``; ``o_t = S_t^T q_t``. ``x' = x + W_o
   [RMSNorm_d(o_t) * sigmoid((h W_g1) W_g2 + bias_g)]``.
2. ``l`` in ``gqa_layers`` — softmax grouped-query attention with no
   position encoding: ``q = h W_q`` (heads x d), ``k = h W_k``, ``v = h
   W_v`` (K/V heads x d); query head ``m g + j`` reads K/V head ``g``;
   scale ``d^-1/2``, causal. ``x' = x + W_o [attention * sigmoid(h
   W_gate)]``, element by element.
3. every layer: ``u = RMSNorm(x')``; ``s = sigmoid(u W_r)`` over all
   ``n_routed_experts``; ``T = top_k(s + bias)``; ``w_e = s_e / sum_{j
   in T} s_j * routed_scaling_factor``; ``E(u) = W_down (silu(W_gate
   u) * (W_up u))``; ``y = x' + sum_{e in T, lo <= e < hi} w_e E_e(u) +
   E_shared(u)``: the share ``[lo, hi)`` of the routed experts (the
   tree holds ``hi - lo`` of them) is an argument; what the absent
   experts would add is left out.
4. after the last layer ``RMSNorm``, then an untied head.

Settled by the layers the config names, not by the config (listed as
``assumed`` in ``chipbench/configs/solar-open2-250b.json``): (a) the
softmax layer's gate (``use_gqa_gate``) is a sigmoid of the layer's
normed input on the attention output before ``W_o``, one a CHANNEL
(arXiv:2505.06708); (b) ``kda_use_full_proj: false`` is the report's
low-rank decay and gate projections, of rank ``d``; (c) the router is
the DeepSeek-V3 key set's: sigmoid scores, a selection bias, one
group. Departures from the public implementation, each deliberate: the
recurrence token by token (it runs chunks); the L2 norms add 1e-12
under the root; the expert sum is a dense loop over the held experts
with a 0/w mask.
"""

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def l2_normalize(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + 1e-12)


def short_conv(x, weight):
    """``x [b, s, C]``, ``weight [taps, C]``: the sum of ``taps``
    shifted copies, zeros before the sequence, then SiLU."""
    taps = weight.shape[0]
    s = x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        shift = taps - 1 - j
        out = out + weight[j] * jnp.pad(
            x, ((0, 0), (shift, 0), (0, 0)))[:, :s]
    return jax.nn.silu(out)


def delta_rule(q, k, v, a, b):
    """The recurrence of equation 1 over ``[b, s, H, d]`` operands
    (``b [b, s, H]``), one position at a time; ``o [b, s, H, d]``."""
    def step(s, xs):
        q, k, v, a, b = xs
        s = s * a[..., None]                                # diag(a) S
        u = v - jnp.einsum("bhkv,bhk->bhv", s, k)
        s = s + b[..., None, None] * k[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q)
    batch, _, heads, d = q.shape
    s0 = jnp.zeros((batch, heads, d, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, b)))
    return jnp.moveaxis(o, 0, 1)


def linear_attention(cfg, p, h):
    """Equation 1 on ``h [b, s, hidden]``."""
    heads, d = cfg["linear_num_heads"], cfg["linear_head_dim"]
    b, s, _ = h.shape
    fresh = jnp.concatenate(
        [h @ p[name]["kernel"] for name in ("q_proj", "k_proj", "v_proj")],
        axis=-1)
    conv = short_conv(fresh, p["conv_weight"]).reshape(b, s, 3, heads, d)
    q = l2_normalize(conv[:, :, 0]) / math.sqrt(d)
    k = l2_normalize(conv[:, :, 1])
    v = conv[:, :, 2]
    decay = jnp.einsum("bsr,rkd->bskd", h @ p["f_proj_a"], p["f_proj_b"])
    a = jnp.exp(-jnp.exp(p["A_log"])[:, None]
                * jax.nn.softplus(decay + p["dt_bias"]))
    beta = 2.0 * jax.nn.sigmoid(h @ p["b_proj"])
    o = delta_rule(q, k, v, a, beta)
    gate = jnp.einsum("bsr,rkd->bskd", h @ p["g_proj_a"], p["g_proj_b"]) \
        + p["g_proj_bias"]
    o = rms_norm(o, p["o_norm"]["scale"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate)
    return jnp.einsum("bskd,kdh->bsh", o, p["o_proj"]["kernel"])


def attention(cfg, p, h):
    """Equation 2 on ``h [b, s, hidden]``."""
    nh, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    s = h.shape[1]
    q = jnp.einsum("bsh,hnd->bsnd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", h, p["v_proj"]["kernel"])
    k = jnp.repeat(k, nh // g, axis=2)
    v = jnp.repeat(v, nh // g, axis=2)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    out = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    out = out * jax.nn.sigmoid(
        jnp.einsum("bsh,hnd->bsnd", h, p["gate_proj"]["kernel"]))
    return jnp.einsum("bqnd,ndh->bqh", out, p["o_proj"]["kernel"])


def route(cfg, p, u):
    """``(idx [.., k], weights [.., k])`` of equation 3's router."""
    scores = jax.nn.sigmoid(u @ p["gate"])
    _, idx = jax.lax.top_k(scores + p["e_score_correction_bias"],
                           cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return idx, weights * cfg.get("routed_scaling_factor", 1.0)


def experts(cfg, p, u, lo, hi):
    """Equation 3's sum: every held expert for every token, masked by
    the picks' weights, and the shared expert."""
    idx, weights = route(cfg, p, u)
    width = p["experts_down"].shape[1]
    out = jnp.zeros_like(u)
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        gate = u @ p["experts_gate_up"][e - lo, :, :width]
        up = u @ p["experts_gate_up"][e - lo, :, width:]
        out += w_e[..., None] * ((jax.nn.silu(gate) * up)
                                 @ p["experts_down"][e - lo])
    gate, up = jnp.split(u @ p["shared_gate_up"]["kernel"], 2, axis=-1)
    return out + (jax.nn.silu(gate) * up) @ p["shared_down"]["kernel"]


def layer(cfg, p, x, index, lo, hi):
    """Equations 1-3 for one layer."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["input_layernorm"]["scale"], eps)
    if index in cfg["gqa_layers"]:
        x = x + attention(cfg, p["self_attn"], h)
    else:
        x = x + linear_attention(cfg, p["linear_attn"], h)
    u = rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    return x + experts(cfg, p["mlp"], u, lo, hi)


def forward(cfg, params, input_ids, lo=0, hi=None):
    """Logits ``[b, s, V]`` of ``input_ids [b, s]``; ``params`` a
    float32 tree in the module's layout holding the routed experts
    ``[lo, hi)`` (all of them by default)."""
    hi = cfg["n_routed_experts"] if hi is None else hi
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed_tokens"], input_ids, axis=0)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, params[f"layers_{i}"], x, i, lo, hi)
        x = rms_norm(x, params["norm"]["scale"], cfg["rms_norm_eps"])
        return x @ params["lm_head"]
