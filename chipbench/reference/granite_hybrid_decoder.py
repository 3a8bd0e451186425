"""Plain float32 reference of the Granite-4.0-H-style decoder the cell
``granite4h.serve-chat-bursty`` serves: a copy of
``paddlefleetx_tpu/models/granite_hybrid/reference.py`` (it imports
nothing from ``paddlefleetx_tpu``; it shares only the LAYOUT of the
parameter tree) made to fit the whole model's 3,191 M parameters beside
a 3,328-token request.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, cache, paging,
chunking or batching. Equations (``x`` in R^2048 the residual stream,
``h = RMSNorm(x)``, eps 1e-5):

0. ``x_0 = E[token] * 12`` (``embedding_multiplier``); every layer ``x'
   = x + 0.22 Mixer(h)``, ``x'' = x' + 0.22 MLP(RMSNorm(x'))``
   (``residual_multiplier``); after layer 39 ``logits = RMSNorm(x) E^T
   / 8`` (``logits_scaling``; the head is the embedding, 100,352 rows).
1. ``layer_types[l] == "mamba"`` — Mamba-2 (arXiv:2405.21060), 64 heads
   of 64, state 128, one group: ``[z | xBC | dt] = h W_in`` (2048 x
   8512, no bias); a causal depthwise convolution of 4 taps with bias
   over time on the 4352 channels of ``xBC``, zeros before the
   sequence, then SiLU; ``c_t = [x_t (64 x 64) | B_t (128) | C_t
   (128)]``. A head: ``dt_t = softplus(dt_t + dt_bias)``, ``a_t =
   exp(-dt_t exp(A_log))``. State ``S [64, 128]`` a head, zero at the
   start, ONE POSITION AT A TIME (a ``lax.scan``): ``S_t = a_t S_{t-1}
   + dt_t x_t B_t^T``; ``y_t = S_t C_t + D x_t``. ``Mixer = W_out
   RMSNorm_4096(y_t * silu(z_t))`` (the gated norm over all heads).
2. ``"attention"`` (layers 5, 15, 25, 35) — softmax grouped-query
   attention with NO position encoding: 32 query heads over 8 K/V heads
   of 64, query head ``4 g + m`` reads K/V head ``g``; the scores times
   ``attention_multiplier`` = 1/64 (NOT 64^-1/2), causal.
3. MLP, every layer: ``[g | u] = h W_1`` (2048 x 16,384); ``W_2
   (silu(g) * u)``.

Departures from the published description, each deliberate (each also
under ``assumed`` in ``configs/granite-4.0-h-micro.json``):
  * the weights arrive in the dtype they are served in (bfloat16) and
    are widened to float32 a layer at a time (3,191 M float32
    parameters are 12.8 GB). The values are the same;
  * :func:`spread_decays` is the benchmark's, not the model's: weights
    drawn N(0, 0.02) would put every ``exp(A_log)`` near 1 and every
    ``dt`` near 0.7, a decay of ~0.5 a step and a memory of three
    tokens, and a state carried wrongly from chunk to chunk would read
    the same. It maps the drawn ``A_log`` and ``dt_bias`` leaves, value
    by value through the normal CDF, onto the Mamba-2 initialisation
    (``exp(A_log)`` uniform in 1..16, ``softplus(dt_bias)`` log-uniform
    in 0.001..0.1) and sets ``D`` to its initial 1. The driver gives
    the served weights the same map;
  * one request at a time, layer by layer (a jitted layer, a Python
    loop); attention one K/V group (4 query heads) and one block of
    query rows at a time over ALL keys with the mask applied to the
    scores: the dense softmax in pieces, not an online one;
  * ``dt`` is not clamped (the published ``time_step_limit`` is (0,
    inf));
  * the head is computed for the rows that are judged only;
  * sequences are right-padded to a multiple of ``PAD_TO``; causality
    (attention, convolution and recurrence alike) keeps the pad out of
    every judged row;
  * ``precision`` other than "float32" exists for the *control* only:
    it rounds both operands of every matmul
    (``gpt2_decoder._round_operand``, imported) the way a tempting
    "speed-up" would. The recurrence's own sums stay float32 there.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import gpt2_decoder as base

PAD_TO = 1024
ROW_BLOCK = 512
INIT_STD = 0.02          # chipbench/weights.py
A_RANGE = (1.0, 16.0)    # exp(A_log)
DT_RANGE = (1e-3, 0.1)   # softplus(dt_bias)


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, base._round_operand(a, precision),
                      base._round_operand(b, precision),
                      preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


@jax.jit
def _spread(a_log, dt_bias, d_skip):
    def quantile(leaf):
        return jnp.clip(jax.scipy.stats.norm.cdf(
            leaf.astype(jnp.float32) / INIT_STD), 1e-3, 1 - 1e-3)
    a = A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * quantile(a_log)
    dt = jnp.exp(math.log(DT_RANGE[0]) + quantile(dt_bias)
                 * math.log(DT_RANGE[1] / DT_RANGE[0]))
    # softplus^-1
    return (jnp.log(a).astype(a_log.dtype),
            (dt + jnp.log(-jnp.expm1(-dt))).astype(dt_bias.dtype),
            jnp.ones_like(d_skip))


def spread_decays(params):
    """``params`` with every state-space layer's ``A_log``, ``dt_bias``
    and ``D`` mapped as the module's docstring says, in the leaves' own
    dtype (the reference then reads the very values the program
    serves)."""
    out = dict(params)
    for name, layer in params.items():
        if isinstance(layer, dict) and "mamba" in layer:
            mixer = dict(layer["mamba"])
            mixer["A_log"], mixer["dt_bias"], mixer["D"] = _spread(
                mixer["A_log"], mixer["dt_bias"], mixer["D"])
            out[name] = dict(layer, mamba=mixer)
    return out


def _short_conv(x, weight, bias):
    """``x [s, C]``: the sum of 4 shifted copies plus bias, zeros
    before the sequence, then SiLU."""
    taps, s = weight.shape[0], x.shape[0]
    out = jnp.zeros_like(x) + bias
    for j in range(taps):
        out = out + weight[j] * jnp.pad(
            x, ((taps - 1 - j, 0), (0, 0)))[:s]
    return jax.nn.silu(out)


def _state_space(x, dt, a, b, c):
    """Equation 1's recurrence over ``x [s, H, P]``, ``dt``, ``a`` ``[s,
    H]``, ``b``, ``c`` ``[s, N]``, one position at a time."""
    def step(state, xs):
        x, dt, a, b, c = xs
        state = a[:, None, None] * state \
            + (dt[:, None] * x)[..., None] * b
        return state, jnp.sum(state * c, axis=-1)
    heads, p = x.shape[1:]
    _, y = jax.lax.scan(step, jnp.zeros((heads, p, b.shape[-1]),
                                        jnp.float32), (x, dt, a, b, c))
    return y


def _mamba(h, p, heads, d, n, eps, precision):
    s = h.shape[0]
    inner = heads * d
    z, xbc, dt = jnp.split(
        _mm("sh,hc->sc", h, p["in_proj"]["kernel"], precision),
        [inner, 2 * inner + 2 * n], axis=-1)
    x, b, c = jnp.split(_short_conv(xbc, p["conv_weight"], p["conv_bias"]),
                        [inner, inner + n], axis=-1)
    x = x.reshape(s, heads, d)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(-dt * jnp.exp(p["A_log"]))
    y = _state_space(x, dt, a, b, c) + p["D"][:, None] * x
    y = _rms_norm(y.reshape(s, inner) * jax.nn.silu(z),
                  p["norm"]["scale"], eps)
    return _mm("sc,ch->sh", y, p["out_proj"]["kernel"], precision)


def _attention(h, p, heads, groups, multiplier, precision):
    s = h.shape[0]
    d = p["q_proj"]["kernel"].shape[-1]
    m = heads // groups
    q = _mm("sh,hnd->snd", h, p["q_proj"]["kernel"], precision)
    k = _mm("sh,hnd->snd", h, p["k_proj"]["kernel"], precision)
    v = _mm("sh,hnd->snd", h, p["v_proj"]["kernel"], precision)
    q, k, v = (base._round_operand(t, precision) for t in (q, k, v))
    rows = min(ROW_BLOCK, s)
    q = q.reshape(s // rows, rows, groups, m, d)
    j = jnp.arange(s)[None, :]

    def block(args):
        """One block of query rows against all keys."""
        qb, start = args                        # [rows, g, m, d]
        seen = j <= start + jnp.arange(rows)[:, None]

        def group(g):
            """The 4 query heads of K/V head ``g``."""
            scores = jnp.einsum(
                "qmd,kd->mqk", qb[:, g], k[:, g],
                preferred_element_type=jnp.float32) * multiplier
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                                   axis=-1)
            return _mm("mqk,kd->qmd", probs, v[:, g], precision)
        return jnp.stack([group(g) for g in range(groups)], axis=1)

    out = jax.lax.map(block, (q, jnp.arange(s // rows) * rows))
    return _mm("snd,ndh->sh", out.reshape(s, heads, d),
               p["o_proj"]["kernel"], precision)


def _mlp(h, p, precision):
    gate, up = jnp.split(_mm("sh,hf->sf", h, p["input_linear"]["kernel"],
                             precision), 2, axis=-1)
    return _mm("sf,fh->sh", jax.nn.silu(gate) * up,
               p["output_linear"]["kernel"], precision)


@functools.partial(jax.jit, static_argnames=(
    "softmax", "heads", "groups", "multiplier", "ssm_heads", "ssm_d",
    "ssm_n", "eps", "residual", "precision"))
def _layer(x, p, *, softmax, heads, groups, multiplier, ssm_heads, ssm_d,
           ssm_n, eps, residual, precision):
    """One layer on ``x [s, hidden]``, its weights widened here."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
        h = _rms_norm(x, p["input_layernorm"]["scale"], eps)
        if softmax:
            mixed = _attention(h, p["self_attn"], heads, groups,
                               multiplier, precision)
        else:
            mixed = _mamba(h, p["mamba"], ssm_heads, ssm_d, ssm_n, eps,
                           precision)
        x = x + residual * mixed
        u = _rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
        return x + residual * _mlp(u, p["shared_mlp"], precision)


@functools.partial(jax.jit, static_argnames=("eps", "scaling",
                                             "precision"))
def _head(x, norm, table, *, eps, scaling, precision):
    with jax.default_matmul_precision("highest"):
        return _mm("sh,vh->sv", _rms_norm(x, norm.astype(jnp.float32), eps),
                   table.astype(jnp.float32), precision) / scaling


def logits(cfg, params, tokens, rows, precision="float32"):
    """``(logits [hi - lo, V] float32, 0.0)`` of positions ``rows = (lo,
    hi)`` of ONE request ``tokens`` (a list of ids); the second entry
    is the share of top-k picks a bfloat16 stream flips, which a model
    without a router does not have. ``cfg`` is the configuration file's
    mapping, ``params`` a tree in the module's layout in any float
    dtype, as ``chipbench/weights.py`` drew it (:func:`spread_decays` is
    applied here)."""
    params = spread_decays(params)
    n = len(tokens)
    padded = -(-n // PAD_TO) * PAD_TO
    ids = np.zeros((padded,), np.int32)
    ids[:n] = tokens
    table = params["embed_tokens"]
    x = jnp.take(table, jnp.asarray(ids), axis=0).astype(jnp.float32) \
        * float(cfg["embedding_multiplier"])
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"layers_{i}"]
        x = _layer(
            x, p, softmax="self_attn" in p,
            heads=cfg["num_attention_heads"],
            groups=cfg["num_key_value_heads"],
            multiplier=float(cfg["attention_multiplier"]),
            ssm_heads=cfg["mamba_n_heads"], ssm_d=cfg["mamba_d_head"],
            ssm_n=cfg["mamba_d_state"], eps=float(cfg["rms_norm_eps"]),
            residual=float(cfg["residual_multiplier"]),
            precision=precision)
    lo, hi = rows
    out = _head(x[lo:hi], params["norm"]["scale"], table,
                eps=float(cfg["rms_norm_eps"]),
                scaling=float(cfg["logits_scaling"]), precision=precision)
    return out, 0.0
