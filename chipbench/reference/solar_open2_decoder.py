"""Plain float32 reference of the Solar-Open2-style decoder the cell
``solaropen2.serve-reasoning`` serves: a copy of
``paddlefleetx_tpu/models/solar_open2/reference.py`` (it imports
nothing from ``paddlefleetx_tpu``; it shares only the LAYOUT of the
parameter tree) made to fit a 31,488-token request beside the weights.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, cache, paging,
chunking, batching or sort. Equations (``x`` the residual stream
entering layer ``l``, ``h = RMSNorm(x)``, eps from the config):

1. ``l`` not in ``gqa_layers`` — Kimi Delta Attention
   (arXiv:2510.26692), 64 heads of 128: ``q~ = h W_q``, ``k~ = h W_k``,
   ``v~ = h W_v`` (4096 -> 8192 each, no bias); a causal depthwise
   convolution of 4 taps over time on each of the 3 x 8192 channels,
   zeros before the sequence, then SiLU: ``c_t = silu(sum_j w_j c~_{t -
   3 + j})``; a head: ``q_t = c^q_t / |c^q_t| 128^-1/2``, ``k_t = c^k_t
   / |c^k_t|``, ``v_t = c^v_t``. Decay, a channel: ``a_t =
   exp(-exp(A_log) softplus((h W_f1) W_f2 + dt_bias))`` (``W_f1`` 4096
   x 128, ``W_f2`` 128 x 8192, ``A_log`` a head, ``dt_bias`` 8192).
   Step size ``b_t = 2 sigmoid(h W_b)``. State ``S [128, 128]`` a head,
   zero at the start, ONE POSITION AT A TIME (a ``lax.scan``): ``S' =
   diag(a_t) S_{t-1}``; ``u_t = v_t - S'^T k_t``; ``S_t = S' + b_t k_t
   u_t^T``; ``o_t = S_t^T q_t``. ``x' = x + W_o [RMSNorm_128(o_t) *
   sigmoid((h W_g1) W_g2 + bias_g)]``.
2. ``l`` in ``gqa_layers`` — softmax grouped-query attention with NO
   position encoding: ``q = h W_q`` (64 x 128), ``k = h W_k``, ``v = h
   W_v`` (8 x 128); query head ``8 g + m`` reads K/V head ``g``; scale
   ``128^-1/2``, causal. ``x' = x + W_o [attention * sigmoid(h
   W_gate)]``, ``W_gate`` 4096 x 8192, element by element.
3. every layer: ``u = RMSNorm(x')``; ``s = sigmoid(u W_r)`` over all
   320; ``T = top_8(s + bias)``; ``w_e = s_e / sum_{j in T} s_j``;
   ``E(u) = W_down (silu(W_gate u) * (W_up u))`` of width 1280; ``y =
   x' + sum_{e in T, e held} w_e E_e(u) + E_shared(u)``: the tree holds
   the chip's share of the routed experts (``experts_held`` of the
   configuration, 40 of 320) and what the absent ones would add is
   left out, as in the program.
4. after the last layer ``RMSNorm``, then an untied head.

Settled by the layers the config names, not by the config (each also
under ``assumed`` in ``configs/solar-open2-250b.json``):
  (a) ``use_gqa_gate``: a sigmoid of the layer's normed input on the
      attention output before ``W_o`` (arXiv:2505.06708), one a
      CHANNEL (8192) rather than one a head: a width the config does
      not give; the parameter total is 250 B either way;
  (b) ``kda_use_full_proj: false``: the report's low-rank decay and
      gate projections, of rank 128 (the head size);
  (c) the router is the DeepSeek-V3 key set's (sigmoid scores, a
      selection bias that selects and never weighs, one group,
      ``norm_topk_prob``, ``routed_scaling_factor`` 1).
Departures from the published description, each deliberate:
  * the weights arrive in the dtype they are served in (bfloat16) and
    are widened to float32 a layer (an expert) at a time. The values
    are the same;
  * :func:`spread_decays` is the benchmark's, not the model's: weights
    drawn N(0, 0.02) would put every decay near 0.5, a memory of three
    tokens, and a state carried wrongly from chunk to chunk would
    read the same. It maps the drawn ``A_log`` and ``dt_bias`` leaves,
    value by value through the normal CDF, onto the layer's published
    initialisation (``exp(A_log)`` uniform in 1..16, ``softplus(dt_bias)``
    log-uniform in 0.001..0.1): decays from 0.2 to 0.999 a step. The
    driver gives the served weights the same map;
  * one request at a time, layer by layer (a jitted layer, a Python
    loop); the delta layer :data:`HEAD_BLOCK` heads at a time (the
    recurrence is a head's own); attention one K/V group (8 query
    heads) and one block of query rows at a time over ALL keys with
    the mask applied to the scores: the dense softmax in pieces, not
    an online one; every held expert for every token (a ``scan`` over
    the 40) with a 0/w mask;
  * the L2 norms add 1e-12 under the root;
  * the head is computed for the rows that are judged only;
  * sequences are right-padded to a multiple of ``PAD_TO``; causality
    (attention, convolution and recurrence alike) keeps the pad out of
    every judged row;
  * ``precision`` other than "float32" exists for the *control* only:
    it rounds both operands of every matmul
    (``gpt2_decoder._round_operand``, imported) the way a tempting
    "speed-up" would.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import gpt2_decoder as base

PAD_TO = 2048
ROW_BLOCK = 512
HEAD_BLOCK = 16
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


def _l2_normalize(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + 1e-12)


@jax.jit
def _spread(a_log, dt_bias):
    def quantile(leaf):
        return jnp.clip(jax.scipy.stats.norm.cdf(
            leaf.astype(jnp.float32) / INIT_STD), 1e-3, 1 - 1e-3)
    a = A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * quantile(a_log)
    dt = jnp.exp(math.log(DT_RANGE[0]) + quantile(dt_bias)
                 * math.log(DT_RANGE[1] / DT_RANGE[0]))
    # softplus^-1
    return (jnp.log(a).astype(a_log.dtype),
            (dt + jnp.log(-jnp.expm1(-dt))).astype(dt_bias.dtype))


def spread_decays(params):
    """``params`` with every delta layer's ``A_log`` and ``dt_bias``
    mapped as the module's docstring says, in the leaves' own dtype
    (the reference then reads the very values the program serves)."""
    out = dict(params)
    for name, layer in params.items():
        if isinstance(layer, dict) and "linear_attn" in layer:
            mixer = dict(layer["linear_attn"])
            mixer["A_log"], mixer["dt_bias"] = _spread(
                mixer["A_log"], mixer["dt_bias"])
            out[name] = dict(layer, linear_attn=mixer)
    return out


def _short_conv(x, weight):
    """``x [s, C]``: the sum of 4 shifted copies, zeros before the
    sequence, then SiLU."""
    taps, s = weight.shape[0], x.shape[0]
    out = jnp.zeros_like(x)
    for j in range(taps):
        out = out + weight[j] * jnp.pad(
            x, ((taps - 1 - j, 0), (0, 0)))[:s]
    return jax.nn.silu(out)


def _delta_rule(q, k, v, a, b):
    """Equation 1's recurrence over ``[s, H, d]`` operands (``b [s,
    H]``), one position at a time."""
    def step(state, xs):
        q, k, v, a, b = xs
        state = state * a[..., None]
        u = v - jnp.sum(state * k[..., None], axis=-2)
        state = state + b[..., None, None] * k[..., None] * u[..., None, :]
        return state, jnp.sum(state * q[..., None], axis=-2)
    heads, d = q.shape[1:]
    _, o = jax.lax.scan(step, jnp.zeros((heads, d, v.shape[-1]),
                                        jnp.float32), (q, k, v, a, b))
    return o


def _linear_attention(h, p, heads, d, eps, precision):
    s = h.shape[0]
    decay_in = _mm("sh,hr->sr", h, p["f_proj_a"], precision)
    gate_in = _mm("sh,hr->sr", h, p["g_proj_a"], precision)
    beta = 2.0 * jax.nn.sigmoid(_mm("sh,hk->sk", h, p["b_proj"], precision))
    conv_w = p["conv_weight"].reshape(-1, 3, heads, d)
    out = jnp.zeros_like(h)
    for lo in range(0, heads, HEAD_BLOCK):
        hi = min(lo + HEAD_BLOCK, heads)

        def stream(i, name):
            x = _mm("sh,hc->sc", h, p[name]["kernel"][:, lo * d:hi * d],
                    precision)
            return _short_conv(x, conv_w[:, i, lo:hi].reshape(-1, (hi - lo)
                                                              * d)
                               ).reshape(s, hi - lo, d)
        q = _l2_normalize(stream(0, "q_proj")) / math.sqrt(d)
        k = _l2_normalize(stream(1, "k_proj"))
        v = stream(2, "v_proj")
        a = jnp.exp(-jnp.exp(p["A_log"][lo:hi])[:, None] * jax.nn.softplus(
            _mm("sr,rkd->skd", decay_in, p["f_proj_b"][:, lo:hi], precision)
            + p["dt_bias"][lo:hi]))
        o = _delta_rule(q, k, v, a, beta[:, lo:hi])
        gate = _mm("sr,rkd->skd", gate_in, p["g_proj_b"][:, lo:hi],
                   precision) + p["g_proj_bias"][lo:hi]
        o = _rms_norm(o, p["o_norm"]["scale"], eps) * jax.nn.sigmoid(gate)
        out = out + _mm("skd,kdh->sh", o, p["o_proj"]["kernel"][lo:hi],
                        precision)
    return out


def _attention(h, p, heads, groups, precision):
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
            """The 8 query heads of K/V head ``g``."""
            scores = jnp.einsum(
                "qmd,kd->mqk", qb[:, g], k[:, g],
                preferred_element_type=jnp.float32) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                                   axis=-1)
            return _mm("mqk,kd->qmd", probs, v[:, g], precision)
        return jnp.stack([group(g) for g in range(groups)], axis=1)

    out = jax.lax.map(block, (q, jnp.arange(s // rows) * rows))
    out = out.reshape(s, heads, d) * jax.nn.sigmoid(
        _mm("sh,hnd->snd", h, p["gate_proj"]["kernel"], precision))
    return _mm("snd,ndh->sh", out, p["o_proj"]["kernel"], precision)


def _route(u, p, top_k, precision):
    scores = jax.nn.sigmoid(_mm("sh,he->se", u, p["gate"], precision))
    _, idx = jax.lax.top_k(scores + p["e_score_correction_bias"], top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, picked / jnp.sum(picked, axis=-1, keepdims=True)


def _experts(u, p, experts, lo, top_k, scaling, precision):
    idx, weights = _route(u, p, top_k, precision)
    weights = weights * scaling
    u_r = base._round_operand(u, precision)

    def one(acc, args):
        """Held expert ``e`` for every token, weighted where picked."""
        e, gate_up, down = args
        gate_up, down = (t.astype(jnp.float32) for t in (gate_up, down))
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        width = down.shape[0]              # gate | up on the last axis
        gate = _mm("sh,hf->sf", u_r, gate_up[:, :width], precision)
        up = _mm("sh,hf->sf", u_r, gate_up[:, width:], precision)
        y = _mm("sf,fh->sh", jax.nn.silu(gate) * up, down, precision)
        return acc + w_e[:, None] * y, None
    n = experts["experts_down"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        lo + jnp.arange(n), experts["experts_gate_up"],
        experts["experts_down"]))
    gate, up = jnp.split(_mm("sh,hf->sf", u, p["shared_gate_up"]["kernel"],
                             precision), 2, axis=-1)
    return out + _mm("sf,fh->sh", jax.nn.silu(gate) * up,
                     p["shared_down"]["kernel"], precision), idx


@functools.partial(jax.jit, static_argnames=(
    "softmax", "heads", "groups", "linear_heads", "linear_d", "eps",
    "top_k", "lo", "scaling", "precision"))
def _layer(x, p, *, softmax, heads, groups, linear_heads, linear_d, eps,
           top_k, lo, scaling, precision):
    """One layer on ``x [s, hidden]``: ``(y, the stream the router read,
    its picks)``."""
    with jax.default_matmul_precision("highest"):
        big = ("experts_gate_up", "experts_down")
        experts = {k: p["mlp"][k] for k in big}
        p = jax.tree.map(
            lambda t: t.astype(jnp.float32),
            dict(p, mlp={k: v for k, v in p["mlp"].items()
                         if k not in big}))
        h = _rms_norm(x, p["input_layernorm"]["scale"], eps)
        if softmax:
            x = x + _attention(h, p["self_attn"], heads, groups, precision)
        else:
            x = x + _linear_attention(h, p["linear_attn"], linear_heads,
                                      linear_d, eps, precision)
        u = _rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
        y, idx = _experts(u, p["mlp"], experts, lo, top_k, scaling,
                          precision)
        return x + y, u, idx


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, head, *, eps, precision):
    with jax.default_matmul_precision("highest"):
        return _mm("sh,hv->sv", _rms_norm(x, norm.astype(jnp.float32), eps),
                   head.astype(jnp.float32), precision)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _flipped(u, router, bias, idx, *, top_k):
    """Share of the picks ``idx`` that are not among the top-k of the
    scores formed from the router's input rounded to bfloat16 (top-k is
    discontinuous, and the program feeds its float32 router a bfloat16
    stream): what the limits have to live with."""
    with jax.default_matmul_precision("highest"):
        low = jax.nn.sigmoid(jnp.dot(
            u.astype(jnp.bfloat16).astype(jnp.float32),
            router.astype(jnp.float32))) + bias.astype(jnp.float32)
    _, idx_low = jax.lax.top_k(low, top_k)
    same = jnp.any(idx[:, :, None] == idx_low[:, None, :], axis=-1)
    return 1.0 - jnp.mean(same.astype(jnp.float32))


def logits(cfg, params, tokens, rows, precision="float32"):
    """``(logits [hi - lo, V] float32, flipped share)`` of positions
    ``rows = (lo, hi)`` of ONE request ``tokens`` (a list of ids).
    ``cfg`` is the configuration file's mapping, ``params`` a tree in
    the module's layout in any float dtype, as ``chipbench/weights.py``
    drew it (:func:`spread_decays` is applied here)."""
    params = spread_decays(params)
    n = len(tokens)
    padded = -(-n // PAD_TO) * PAD_TO
    ids = np.zeros((padded,), np.int32)
    ids[:n] = tokens
    x = jnp.take(params["embed_tokens"], jnp.asarray(ids),
                 axis=0).astype(jnp.float32)
    linear = cfg["linear_attn_config"]
    top_k = cfg["num_experts_per_tok"]
    flips = []
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"layers_{i}"]
        x, u, idx = _layer(
            x, p, softmax="self_attn" in p,
            heads=cfg["num_attention_heads"],
            groups=cfg["num_key_value_heads"],
            linear_heads=linear["num_heads"], linear_d=linear["head_dim"],
            eps=float(cfg["rms_norm_eps"]), top_k=top_k,
            lo=int(cfg["experts_held"][0]),
            scaling=float(cfg["routed_scaling_factor"]),
            precision=precision)
        flips.append(_flipped(
            u[:n], p["mlp"]["gate"], p["mlp"]["e_score_correction_bias"],
            idx[:n], top_k=top_k))
    lo, hi = rows
    out = _head(x[lo:hi], params["norm"]["scale"], params["lm_head"],
                eps=float(cfg["rms_norm_eps"]), precision=precision)
    return out, float(np.mean([float(f) for f in flips]))
