"""Plain float32 reference of the DeepSeek-V3-style decoder: forward,
loss, and (by ``jax.grad``) gradients. Straight ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, sort, scan,
remat or chunked loss, and nothing imported from the model it checks
(it shares only the LAYOUT of the parameter tree).

``cfg`` is any mapping with the architecture's keys (``hidden_size``,
``num_attention_heads``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``kv_lora_rank``, ``rope_theta``, ``rope_interleave``,
``rms_norm_eps``, ``n_routed_experts``, ``num_experts_per_tok``,
``routed_scaling_factor``) plus ``experts_held`` ``[lo, hi)`` and
``vocab_lo`` (the first vocabulary row held).

Equations (with ``u = RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``):

* block: ``h = x + MLA(RMSNorm_1(x))``, ``y = h + FFN(RMSNorm_2(h))``;
  after the last block ``RMSNorm_f`` and ``logits = z W_head``.
* MLA (no bias): ``q = u W_q`` -> heads x (nope | rope); ``c = u W_kva``
  -> ``c_kv | k_rope`` (one rotary key for all heads); ``[k_nope | v] =
  RMSNorm_kv(c_kv) W_kvb``; RoPE (theta, position = index) on ``q_rope``
  and ``k_rope``, pairs ``(2i, 2i+1)`` brought to the half-split layout
  first where ``rope_interleave``; ``k = [k_nope | k_rope]``;
  ``P = softmax_causal(q k^T / sqrt(nope + rope))``; ``o = P v``;
  ``MLA = concat_heads(o) W_o``.
* MLP: ``(silu(u W_gate) * (u W_up)) W_down``.
* expert layer: ``s = sigmoid(u W_g)``; ``T = top_k(s + b)``;
  ``w_e = s_e / (sum_{j in T} s_j + 1e-20) * routed_scaling_factor``;
  ``FFN(u) = sum_{e in T and H} w_e MLP_e(u) + MLP_shared(u)`` for the
  held experts ``H``: every selected held expert is computed for every
  token that selected it.

Departures from the published modelling code, each deliberate: the
expert sum is a dense loop over the held experts with a 0/w mask (the
published code gathers tokens per expert; same sum); only the held
experts' terms are formed (the share of one chip of an expert-parallel
deployment; with ``experts_held`` everything it is the whole layer);
the embedding and the head hold ``vocab`` rows from ``vocab_lo`` on; no
balance loss and no update of ``b`` (the config gives neither).
"""

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta, interleave):
    """``x [b, s, ..., d]``; position = index along axis 1."""
    d = x.shape[-1]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


def mla(u, p, cfg):
    """Latent attention of ``u [b, s, h]``; the no-position and the
    rotary score terms are formed apart and added."""
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, s = cfg["kv_lora_rank"], u.shape[1]
    q = jnp.einsum("bsh,hnd->bsnd", u, p["q_proj"]["kernel"])
    c = jnp.einsum("bsh,hr->bsr", u, p["kv_a_proj_with_mqa"]["kernel"])
    c_kv, k_rope = c[..., :rank], c[..., rank:]
    kv = jnp.einsum(
        "bsr,rnd->bsnd",
        rms_norm(c_kv, p["kv_a_layernorm"]["scale"], cfg["rms_norm_eps"]),
        p["kv_b_proj"]["kernel"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = rope(q[..., nope:], cfg["rope_theta"], cfg["rope_interleave"])
    k_rope = rope(k_rope, cfg["rope_theta"], cfg["rope_interleave"])
    scores = (jnp.einsum("bqnd,bknd->bnqk", q[..., :nope], k_nope)
              + jnp.einsum("bqnd,bkd->bnqk", q_rope, k_rope)
              ) / math.sqrt(nope + rp)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("bqnd,ndh->bqh", out, p["o_proj"]["kernel"])


def gated_mlp(u, gate_up, down):
    """``gate_up [h, 2, f]``, ``down [f, h]``."""
    g = jnp.einsum("...h,hf->...f", u, gate_up[:, 0])
    up = jnp.einsum("...h,hf->...f", u, gate_up[:, 1])
    return jnp.einsum("...f,fh->...h", jax.nn.silu(g) * up, down)


def router(u, p, cfg):
    """``(idx [.., k], weights [.., k])`` over all experts."""
    s = jax.nn.sigmoid(jnp.einsum("...h,he->...e", u, p["gate"]))
    _, idx = jax.lax.top_k(s + p["e_score_correction_bias"],
                           cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def expert_ffn(u, p, cfg, shared=True):
    """The held experts' part of the routed sum, plus (``shared``) the
    shared expert."""
    lo, hi = cfg["experts_held"]
    idx, w = router(u, p, cfg)
    out = jnp.zeros_like(u)
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        out = out + w_e[..., None] * gated_mlp(
            u, p["experts_gate_up"][e - lo], p["experts_down"][e - lo])
    if shared:
        sh = p["shared_experts"]
        out = out + gated_mlp(u, sh["gate_up_proj"]["kernel"],
                              sh["down_proj"]["kernel"])
    return out


def block(x, p, cfg, dense):
    eps = cfg["rms_norm_eps"]
    h = x + mla(rms_norm(x, p["input_layernorm"]["scale"], eps),
                p["self_attn"], cfg)
    u = rms_norm(h, p["post_attention_layernorm"]["scale"], eps)
    if dense:
        return h + gated_mlp(u, p["mlp"]["gate_up_proj"]["kernel"],
                             p["mlp"]["down_proj"]["kernel"])
    return h + expert_ffn(u, p["mlp"], cfg)


def layers_of(model):
    """``[(layer params, is dense)]`` of either layout: stacked
    ``expert_layers`` or ``expert_layers_<i>`` children."""
    out, i = [], 0
    while f"dense_layers_{i}" in model:
        out.append((model[f"dense_layers_{i}"], True))
        i += 1
    if "expert_layers" in model:
        stack = model["expert_layers"]
        n = jax.tree.leaves(stack)[0].shape[0]
        out += [(jax.tree.map(lambda a, j=j: a[j], stack), False)
                for j in range(n)]
    i = 0
    while f"expert_layers_{i}" in model:
        out.append((model[f"expert_layers_{i}"], False))
        i += 1
    return out


def logits(params, tokens, cfg):
    """``[rows, s]`` token ids -> ``[rows, s, held vocabulary]``."""
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = jnp.take(model["embed_tokens"], tokens - cfg["vocab_lo"],
                     axis=0)
        for p, dense in layers_of(model):
            x = block(x, p, cfg, dense)
        x = rms_norm(x, model["norm"]["scale"], cfg["rms_norm_eps"])
        return jnp.einsum("bsh,hv->bsv", x, params["lm_head"])


def loss(params, tokens, labels, mask, cfg):
    """Masked mean token cross-entropy over the held slice."""
    lg = logits(params, tokens, cfg)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, (labels - cfg["vocab_lo"])[..., None], axis=-1)[..., 0]
    mask = mask.astype(jnp.float32)
    return jnp.sum((logz - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
