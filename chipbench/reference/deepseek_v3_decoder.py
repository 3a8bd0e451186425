"""Plain float32 reference of the DeepSeek-V3-style decoder the cell
``kanana2.pretrain-ep8share`` trains: the benchmark's own copy, which
imports nothing from ``paddlefleetx_tpu`` (its twin for the tier-1 tests
is ``paddlefleetx_tpu/models/deepseek_v3/reference.py``; a test holds
the two to the same numbers).

With ``u = RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * g``:

* block: ``h = x + MLA(RMSNorm_1(x))``, ``y = h + FFN(RMSNorm_2(h))``;
  the leading ``first_k_dense_replace`` blocks have a dense MLP, the
  rest the expert layer; then ``RMSNorm_f`` and ``logits = z W_head``.
* MLA, no bias: ``q = u W_q`` -> heads x (nope | rope); ``c = u W_kva``
  -> ``c_kv | k_rope`` (one rotary key for all heads); ``[k_nope | v] =
  RMSNorm_kv(c_kv) W_kvb``; RoPE (theta, position = index) on q_rope and
  k_rope, pairs ``(2i, 2i+1)`` first brought to the half-split layout
  (``rope_interleave``); ``P = softmax_causal(q k^T / sqrt(nope+rope))``;
  ``MLA = concat_heads(P v) W_o``.
* MLP: ``(silu(u W_gate) * (u W_up)) W_down``.
* expert layer: ``s = sigmoid(u W_g)`` over all ``n_routed_experts``;
  ``T = top_k(s + b)`` (``b`` selects, never weighs);
  ``w_e = s_e / (sum_{j in T} s_j + 1e-20) * routed_scaling_factor``;
  ``FFN(u) = sum_{e in T and H} w_e MLP_e(u) + MLP_shared(u)`` over the
  held experts ``H``: no capacity, every pick of a held expert counts.
* loss: mean token cross-entropy over the held vocabulary rows; then
  the global-norm clip and AdamW of ``gpt2_decoder.py`` (the same
  functions, imported).

Departures, each deliberate: everything in float32 at ``highest``; the
expert sum is a loop over the held experts with a 0/w mask over all
tokens (the published code gathers tokens per expert: same sum);
attention is the dense s x s softmax, taken a few heads at a time under
``jax.checkpoint`` so that one row of 4096 fits beside 9.2 GB of
float32 state (same numbers, less memory); rows in blocks, layer by
layer, as ``gpt2_decoder.py`` does; no balance loss, ``b`` a constant
with zero gradient (the config gives neither); ``precision`` other than
"float32" is the control's handle, as in ``gpt2_decoder.py``.
"""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference import gpt2_decoder as base

clip_by_global_norm = base.clip_by_global_norm
leaf_norms, leaf_diff_norms = base.leaf_norms, base.leaf_diff_norms
_mm = base._mm

# gpt2_decoder.py's AdamW step with its arguments donated: at 576 M
# parameters the old and the new float32 master, both moments and the
# gradient are 16.1 GB together, and the chip holds 15.75
_adamw_step = jax.jit(base._adamw_step.__wrapped__,
                      donate_argnums=(0, 1, 2, 3))


def adamw_init(params):
    """Zero moments, each its own buffers (both are donated later)."""
    return {"mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params), "count": 0}


def adamw_update(params, grads, state, opt):
    """One AdamW update on already-clipped ``grads`` (all four trees
    are consumed)."""
    t = state["count"] + 1
    params, mu, nu = _adamw_step(
        params, grads, state["mu"], state["nu"], float(t),
        base.lr_at(state["count"], opt), opt["beta1"], opt["beta2"],
        opt["epsilon"], opt["weight_decay"])
    return params, {"mu": mu, "nu": nu, "count": t}


#: heads scored at a time (their s x s float32 scores are what is large)
HEADS_PER_PASS = 4


def geometry(config, experts_held=None, vocab_lo=0):
    """The hashable tuple of sizes the jitted pieces close over, from a
    configuration file's keys."""
    held = tuple(experts_held or (0, config["n_routed_experts"]))
    return (("nope", config["qk_nope_head_dim"]),
            ("rope", config["qk_rope_head_dim"]),
            ("rank", config["kv_lora_rank"]),
            ("theta", float(config["rope_theta"])),
            ("interleave", bool(config["rope_interleave"])),
            ("eps", float(config["rms_norm_eps"])),
            ("top_k", config["num_experts_per_tok"]),
            ("scaling", float(config["routed_scaling_factor"])),
            ("held", held), ("vocab_lo", int(vocab_lo)))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta, interleave):
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


def _mla(u, p, g, precision):
    nope, s = g["nope"], u.shape[1]
    q = _mm("bsh,hnd->bsnd", u, p["q_proj"]["kernel"], precision)
    c = _mm("bsh,hr->bsr", u, p["kv_a_proj_with_mqa"]["kernel"], precision)
    c_kv, k_rope = c[..., :g["rank"]], c[..., g["rank"]:]
    kv = _mm("bsr,rnd->bsnd",
             _rms_norm(c_kv, p["kv_a_layernorm"]["scale"], g["eps"]),
             p["kv_b_proj"]["kernel"], precision)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], g["theta"], g["interleave"])],
        axis=-1)
    k_rope = _rope(k_rope, g["theta"], g["interleave"])
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(
            k_rope[:, :, None, :], kv.shape[:-1] + (k_rope.shape[-1],))],
        axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def some_heads(qkv):
        qh, kh, vh = qkv                              # [b, s, heads, d]
        scores = _mm("bqnd,bknd->bnqk", qh, kh, precision) \
            / math.sqrt(qh.shape[-1])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return _mm("bnqk,bknd->bqnd", probs, vh, precision)

    heads = q.shape[2]
    per = HEADS_PER_PASS if heads % HEADS_PER_PASS == 0 else heads

    def split(t):                      # [b, s, n, d] -> [n/per, b, s, per, d]
        return jnp.moveaxis(
            t.reshape(t.shape[:2] + (heads // per, per, t.shape[-1])), 2, 0)
    out = jax.lax.map(some_heads, (split(q), split(k), split(v)))
    out = jnp.moveaxis(out, 0, 2).reshape(v.shape)
    return _mm("bqnd,ndh->bqh", out, p["o_proj"]["kernel"], precision)


def _gated_mlp(u, gate_up, down, precision):
    gate = _mm("...h,hf->...f", u, gate_up[:, 0], precision)
    up = _mm("...h,hf->...f", u, gate_up[:, 1], precision)
    return _mm("...f,fh->...h", jax.nn.silu(gate) * up, down, precision)


def _route(u, p, g, precision):
    s = jax.nn.sigmoid(_mm("...h,he->...e", u, p["gate"], precision))
    _, idx = jax.lax.top_k(
        s + jax.lax.stop_gradient(p["e_score_correction_bias"]),
        g["top_k"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    w = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return idx, w * g["scaling"]


def _expert_ffn(u, p, g, precision):
    lo, hi = g["held"]
    idx, w = _route(u, p, g, precision)

    def one_expert(acc, xs):
        e, gate_up, down = xs
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        return acc + w_e[..., None] * _gated_mlp(u, gate_up, down,
                                                 precision), None
    # a loop over the held experts, written as a scan so that the body
    # compiles once (16 unrolled copies took the chip's compiler 2 min)
    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (jnp.arange(lo, hi), p["experts_gate_up"], p["experts_down"]))
    sh = p["shared_experts"]
    return out + _gated_mlp(u, sh["gate_up_proj"]["kernel"],
                            sh["down_proj"]["kernel"], precision)


def _block(x, p, g, precision):
    """One decoder block, dense or expert by what ``p["mlp"]`` holds;
    ``x`` is [rows, s, h] float32."""
    h = x + _mla(_rms_norm(x, p["input_layernorm"]["scale"], g["eps"]),
                 p["self_attn"], g, precision)
    u = _rms_norm(h, p["post_attention_layernorm"]["scale"], g["eps"])
    if "gate" in p["mlp"]:
        return h + _expert_ffn(u, p["mlp"], g, precision)
    return h + _gated_mlp(u, p["mlp"]["gate_up_proj"]["kernel"],
                          p["mlp"]["down_proj"]["kernel"], precision)


def _nll_sum(x, norm, head, labels, mask, g, precision):
    logits = _mm("bsh,hv->bsv", _rms_norm(x, norm["scale"], g["eps"]),
                 head, precision)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, (labels - g["vocab_lo"])[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - picked) * mask)


def layers_of(model):
    """The per-layer parameter dicts in order, of either layout
    (stacked ``expert_layers`` or ``expert_layers_<i>`` children)."""
    out, i = [], 0
    while f"dense_layers_{i}" in model:
        out.append(model[f"dense_layers_{i}"])
        i += 1
    if "expert_layers" in model:
        stack = model["expert_layers"]
        n = jax.tree.leaves(stack)[0].shape[0]
        out += [jax.tree.map(lambda a, j=j: a[j], stack) for j in range(n)]
    i = 0
    while f"expert_layers_{i}" in model:
        out.append(model[f"expert_layers_{i}"])
        i += 1
    return out


def _relayer(model, layers):
    """Per-layer trees back into the layout ``model`` has."""
    out, layers = {}, list(layers)
    i = 0
    while f"dense_layers_{i}" in model:
        out[f"dense_layers_{i}"] = layers.pop(0)
        i += 1
    if "expert_layers" in model:
        out["expert_layers"] = jax.tree.map(lambda *a: jnp.stack(a),
                                            *layers)
    else:
        out.update({f"expert_layers_{j}": t for j, t in enumerate(layers)})
    return out


_static = functools.partial(jax.jit, static_argnames=("geo", "precision"))
_static_geo = functools.partial(jax.jit, static_argnames=("geo",))


@_static_geo
def _embed_fwd(table, tokens, geo):
    return jnp.take(table, tokens - dict(geo)["vocab_lo"], axis=0)


@_static_geo
def _embed_bwd(table, tokens, dx, geo):
    return jax.vjp(lambda t: jnp.take(
        t, tokens - dict(geo)["vocab_lo"], axis=0), table)[1](dx)[0]


@_static
def _block_fwd(x, p, geo, precision):
    with jax.default_matmul_precision("highest"):
        return _block(x, p, dict(geo), precision)


@_static
def _block_bwd(x, p, dy, geo, precision):
    with jax.default_matmul_precision("highest"):
        return jax.vjp(lambda x, p: _block(x, p, dict(geo), precision),
                       x, p)[1](dy)


@_static
def _head_bwd(x, norm, head, labels, mask, geo, precision):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_nll_sum, argnums=(0, 1, 2))(
            x, norm, head, labels, mask, dict(geo), precision)


@_static
def _head_logits(x, norm, head, geo, precision):
    with jax.default_matmul_precision("highest"):
        return _mm("bsh,hv->bsv",
                   _rms_norm(x, norm["scale"], dict(geo)["eps"]), head,
                   precision)


@_static
def _picks(x, p, geo, precision):
    """Of one expert layer's picks on ``x``: how many change when the
    router reads its input rounded to bfloat16, how many land on held
    experts, and how many there are."""
    with jax.default_matmul_precision("highest"):
        g = dict(geo)
        lo, hi = g["held"]
        h = x + _mla(_rms_norm(x, p["input_layernorm"]["scale"], g["eps"]),
                     p["self_attn"], g, precision)
        u = _rms_norm(h, p["post_attention_layernorm"]["scale"], g["eps"])
        idx, _ = _route(u, p["mlp"], g, precision)
        low, _ = _route(u.astype(jnp.bfloat16).astype(jnp.float32),
                        p["mlp"], g, precision)
        same = jnp.any(idx[..., :, None] == low[..., None, :], axis=-1)
        return (jnp.sum(~same), jnp.sum((idx >= lo) & (idx < hi)),
                same.size)


def logits(params, tokens, geo, precision="float32"):
    """[rows, s] token ids -> [rows, s, held vocabulary] float32."""
    model = params["model"]
    x = _embed_fwd(model["embed_tokens"], tokens, geo)
    for p in layers_of(model):
        x = _block_fwd(x, p, geo, precision)
    return _head_logits(x, model["norm"], params["lm_head"], geo, precision)


def routing(params, tokens, geo):
    """What the float32 router does with ``tokens``, a row at a time:
    ``(flipped, held, picks)`` summed over rows and expert layers.
    ``flipped`` are the picks that change when bfloat16 activations feed
    the router (top-k is discontinuous, so a near-tie can fall the other
    way in the program); ``held`` those that land on held experts, the
    program's ``moe_held_picks``."""
    model = params["model"]
    flipped = held = total = 0
    for r in range(tokens.shape[0]):
        x = _embed_fwd(model["embed_tokens"], tokens[r:r + 1], geo)
        for p in layers_of(model):
            if "gate" in p["mlp"]:
                f, h, n = _picks(x, p, geo, "float32")
                flipped, held, total = \
                    flipped + int(f), held + int(h), total + int(n)
            x = _block_fwd(x, p, geo, "float32")
    return flipped, held, total


def loss_and_grad(params, tokens, labels, mask, geo, rows_per_block=1,
                  precision="float32"):
    """Masked mean token cross-entropy of the batch and its gradient,
    rows taken ``rows_per_block`` at a time, each block forward through
    the layers (keeping every layer's input) and back again."""
    model = params["model"]
    table, layers = model["embed_tokens"], layers_of(model)
    mask = mask.astype(jnp.float32)
    total, g_tab, g_norm, g_head = 0.0, None, None, None
    g_layers = [None] * len(layers)
    for r in range(0, tokens.shape[0], rows_per_block):
        sl = slice(r, r + rows_per_block)
        xs = [_embed_fwd(table, tokens[sl], geo)]
        for p in layers:
            xs.append(_block_fwd(xs[-1], p, geo, precision))
        nll, (dx, d_norm, d_head) = _head_bwd(
            xs.pop(), model["norm"], params["lm_head"], labels[sl],
            mask[sl], geo, precision)
        total = total + nll
        for i in reversed(range(len(layers))):
            dx, dp = _block_bwd(xs.pop(), layers[i], dx, geo, precision)
            g_layers[i] = dp if g_layers[i] is None \
                else base._add(g_layers[i], dp)
        d_tab = _embed_bwd(table, tokens[sl], dx, geo)
        g_tab = d_tab if g_tab is None else g_tab + d_tab
        g_norm = d_norm if g_norm is None else base._add(g_norm, d_norm)
        g_head = d_head if g_head is None else g_head + d_head
    count = jnp.maximum(jnp.sum(mask), 1.0)
    grads = {"model": dict(_relayer(model, g_layers), embed_tokens=g_tab,
                           norm=g_norm),
             "lm_head": g_head}
    return total / count, base._scale(grads, 1.0 / count)
