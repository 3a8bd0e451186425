"""Plain float32 reference of the Granite-4.0-H-style decoder: the
forward pass in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, cache, paging,
chunking or batching trick; the state-space layer as the token-by-token
recurrence (a ``lax.scan`` over positions), its convolution as four
shifted sums plus bias; nothing imported from the model it checks (it
shares only the LAYOUT of the parameter tree).

``cfg`` is any mapping with the architecture's keys (``hidden_size``,
``num_hidden_layers``, ``layer_types``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``attention_multiplier``,
``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``,
``rms_norm_eps``).

Equations, with ``x`` the residual stream, ``r`` the residual
multiplier and ``h = RMSNorm(x)``:

0. ``x_0 = E[token] * embedding_multiplier``; every layer ``x' = x + r
   Mixer(h)``, ``x'' = x' + r MLP(RMSNorm(x'))``; after the last layer
   ``logits = RMSNorm(x) E^T / logits_scaling`` (a tied head).
1. ``layer_types[l] == "mamba"`` — Mamba-2 (arXiv:2405.21060), ``H``
   heads of ``P``, state ``N``, one group: ``[z | xBC | dt] = h W_in``;
   a causal depthwise convolution of ``taps`` with bias over time on
   the ``H P + 2 N`` channels of ``xBC``, zeros before the sequence,
   then SiLU: ``c_t = silu(b + sum_j w_j xBC_{t - taps + 1 + j})``,
   ``c_t = [x_t | B_t | C_t]``. A head: ``dt_t = softplus(dt_t +
   dt_bias)``, ``a_t = exp(-dt_t exp(A_log))``. State ``S [P, N]`` a
   head, zero at the start: ``S_t = a_t S_{t-1} + dt_t x_t B_t^T``;
   ``y_t = S_t C_t + D x_t``. Output ``W_out RMSNorm_{H P}(y_t *
   silu(z_t))``: the gated norm over all heads' channels.
2. ``"attention"`` — softmax grouped-query attention with no position
   encoding: query head ``m g + j`` reads K/V head ``g``; the scores
   times ``attention_multiplier`` (NOT ``head_dim ** -0.5``), causal.
3. MLP: ``[g | u] = h W_1``; ``W_2 (silu(g) * u)``.

Departures from the public implementation, each deliberate: the
recurrence token by token (it runs the chunkwise SSD form, the same
recurrence); ``dt`` is not clamped (the published ``time_step_limit``
is (0, inf)).
"""

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def short_conv(x, weight, bias):
    """``x [b, s, C]``, ``weight [taps, C]``, ``bias [C]``: the sum of
    ``taps`` shifted copies plus bias, zeros before the sequence, then
    SiLU."""
    taps = weight.shape[0]
    s = x.shape[1]
    out = jnp.zeros_like(x) + bias
    for j in range(taps):
        shift = taps - 1 - j
        out = out + weight[j] * jnp.pad(
            x, ((0, 0), (shift, 0), (0, 0)))[:, :s]
    return jax.nn.silu(out)


def state_space(x, dt, a, b, c):
    """The recurrence of equation 1 over ``x [b, s, H, P]``, ``dt``,
    ``a`` ``[b, s, H]``, ``b``, ``c`` ``[b, s, N]``, one position at a
    time; ``S C`` ``[b, s, H, P]``."""
    def step(state, xs):
        x, dt, a, b, c = xs
        state = a[..., None, None] * state + (dt[..., None] * x)[
            ..., None] * b[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c)
    batch, _, heads, p = x.shape
    s0 = jnp.zeros((batch, heads, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, a, b, c)))
    return jnp.moveaxis(y, 0, 1)


def mamba(cfg, p, h):
    """Equation 1 on ``h [b, s, hidden]``."""
    heads, d, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"])
    inner = heads * d
    batch, s, _ = h.shape
    z, xbc, dt = jnp.split(h @ p["in_proj"]["kernel"],
                           [inner, 2 * inner + 2 * n], axis=-1)
    conv = short_conv(xbc, p["conv_weight"], p["conv_bias"])
    x, b, c = jnp.split(conv, [inner, inner + n], axis=-1)
    x = x.reshape(batch, s, heads, d)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(p["A_log"]))
    y = state_space(x, dt, a, b, c) + p["D"][:, None] * x
    y = y.reshape(batch, s, inner) * jax.nn.silu(z)
    return rms_norm(y, p["norm"]["scale"], cfg["rms_norm_eps"]) \
        @ p["out_proj"]["kernel"]


def attention(cfg, p, h):
    """Equation 2 on ``h [b, s, hidden]``."""
    nh, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    s = h.shape[1]
    q = jnp.einsum("bsh,hnd->bsnd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", h, p["v_proj"]["kernel"])
    k = jnp.repeat(k, nh // g, axis=2)
    v = jnp.repeat(v, nh // g, axis=2)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) \
        * cfg["attention_multiplier"]
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    out = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqnd,ndh->bqh", out, p["o_proj"]["kernel"])


def mlp(p, h):
    gate, up = jnp.split(h @ p["input_linear"]["kernel"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["output_linear"]["kernel"]


def layer(cfg, p, x, index):
    """Equations 0-3 for one layer."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = rms_norm(x, p["input_layernorm"]["scale"], eps)
    if cfg["layer_types"][index] == "attention":
        x = x + r * attention(cfg, p["self_attn"], h)
    else:
        x = x + r * mamba(cfg, p["mamba"], h)
    return x + r * mlp(p["shared_mlp"], rms_norm(
        x, p["post_attention_layernorm"]["scale"], eps))


def forward(cfg, params, input_ids):
    """Logits ``[b, s, V]`` of ``input_ids [b, s]``; ``params`` a
    float32 tree in the module's layout."""
    with jax.default_matmul_precision("highest"):
        table = params["embed_tokens"]
        x = jnp.take(table, input_ids, axis=0) * cfg["embedding_multiplier"]
        for i in range(cfg["num_hidden_layers"]):
            x = layer(cfg, params[f"layers_{i}"], x, i)
        x = rms_norm(x, params["norm"]["scale"], cfg["rms_norm_eps"])
        return x @ table.T / cfg["logits_scaling"]
