"""Plain float32 reference of the K-EXAONE-style decoder and of its
multi-token-prediction block, as the cell ``kexaone.serve-reasoning-mtp``
serves them: a copy of ``paddlefleetx_tpu/models/exaone_moe/reference.py``
(it imports nothing from ``paddlefleetx_tpu``; it shares only the LAYOUT
of the parameter tree) made to fit a 9,216-token request beside the
weights.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, cache, paging,
chunking, batching, sort or speculation. Equations (``x`` the residual
stream entering layer ``l``; (a)-(e) are the EXAONE 4.0 family's
convention, arXiv:2507.11407, where the config's keys do not say):

0. (a) post-norm: ``x' = x + RMSNorm(Attn(x))``, ``x'' = x' +
   RMSNorm(FFN(x'))``, eps from the config; after the last layer
   ``RMSNorm``, then an untied head.
1. ``q = x W_q`` (64 heads x 128), ``k = x W_k``, ``v = x W_v`` (8
   heads x 128), no bias; (b) RMSNorm over each head's 128 channels of
   ``q`` and ``k`` (one weight vector each a layer); query head ``8 g +
   m`` reads K/V head ``g``; scale ``128^-1/2``, causal. (c) where
   ``sliding_windows[l]`` is 128: rotate-half RoPE over all 128 dims
   (pairs ``(i, i + 64)``, angle ``p theta^(-2i/128)``) AFTER the
   norms, and key ``j`` visible to query ``i`` iff ``i - 128 < j <= i``;
   where it is 0: no position encoding, the whole context.
2. ``mlp_layer_types[l]`` "dense": ``W_d (silu(x W_g) * (x W_u))``;
   "sparse": ``Shared(x) + sum_{e in top-8, e held} w_e Expert_e(x)``
   with ``s = sigmoid(x W_r)`` over all 128, the 8 largest of ``s + b``,
   ``w_e = 2.5 s_e / sum of the picked s``.
3. (d) the multi-token-prediction block, DeepSeek-V3's form
   (arXiv:2412.19437 section 2.2): ``u_i = W_p [RMSNorm_e(E[t_{i+1}]) ;
   RMSNorm_h(h_i)]`` with ``h_i`` the output of the final norm, one
   full layer ((e) its FFN sparse) over ``u``, its own RMSNorm, the
   main model's head: the logits of token ``i + 2``.

Departures from the published description, each deliberate:
  * the weights arrive in the dtype they are served in (bfloat16) and
    are widened to float32 a layer at a time (an expert at a time in
    the expert sum): 4,543 M parameters in float32 would be 18.2 GB.
    The values are the same;
  * one request at a time, layer by layer (a jitted layer, a Python
    loop); attention one K/V group (8 query heads) and one block of
    query rows at a time over ALL keys with the mask applied to the
    scores: the dense softmax in pieces, not an online one;
  * every HELD expert is computed for every token (a ``scan`` over the
    16) and combined with a 0/w mask: the published code gathers tokens
    per expert, same sum; what the 112 experts another chip holds would
    add is left out, as in the program (``experts_held``);
  * the head is computed for the rows that are judged only;
  * sequences are right-padded to a multiple of ``PAD_TO`` so that six
    requests compile a few shapes; causality keeps the pad out of every
    judged row;
  * the stream after the final norm of the last request is kept, so
    that ``mtp_argmax`` after ``logits`` of the same request does not
    run the five layers again;
  * ``precision`` other than "float32" exists for the *control* only: it
    rounds both operands of every matmul (``gpt2_decoder._round_operand``,
    imported) the way a tempting "speed-up" would.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import gpt2_decoder as base

PAD_TO = 2048
ROW_BLOCK = 1024


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, base._round_operand(a, precision),
                      base._round_operand(b, precision),
                      preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """``x [s, h, d]``; position = index along axis 0."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(x, p, heads, groups, window, theta, eps, precision):
    s = x.shape[0]
    d = p["q_proj"]["kernel"].shape[-1]
    m = heads // groups
    q = _mm("sh,hnd->snd", x, p["q_proj"]["kernel"], precision)
    k = _mm("sh,hnd->snd", x, p["k_proj"]["kernel"], precision)
    v = _mm("sh,hnd->snd", x, p["v_proj"]["kernel"], precision)
    q = _rms_norm(q, p["q_norm"]["scale"], eps)
    k = _rms_norm(k, p["k_norm"]["scale"], eps)
    if window:
        q, k = _rope(q, theta), _rope(k, theta)
    q, k, v = (base._round_operand(t, precision) for t in (q, k, v))
    rows = min(ROW_BLOCK, s)
    q = q.reshape(s // rows, rows, groups, m, d)
    j = jnp.arange(s)[None, :]

    def block(args):
        """One block of query rows against all keys."""
        qb, start = args                        # [rows, g, m, d]
        i = start + jnp.arange(rows)[:, None]
        seen = j <= i
        if window:
            seen &= j > i - window

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
    return _mm("snd,ndh->sh", out.reshape(s, heads, d),
               p["o_proj"]["kernel"], precision)


def _gated(x, gate_up, down, precision):
    """``(silu(x W_g) * (x W_u)) W_d``, gate | up on the last axis."""
    width = down.shape[0]
    gate = _mm("sh,hf->sf", x, gate_up[:, :width], precision)
    up = _mm("sh,hf->sf", x, gate_up[:, width:], precision)
    return _mm("sf,fh->sh", jax.nn.silu(gate) * up, down, precision)


def _route(x, p, top_k, scaling, precision):
    scores = jax.nn.sigmoid(_mm("sh,he->se", x, p["gate"], precision))
    _, idx = jax.lax.top_k(scores + p["e_score_correction_bias"], top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scaling * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def _experts(x, p, experts, lo, top_k, scaling, precision):
    """Shared expert + the held experts' part of the routed sum;
    ``experts`` the two stacked leaves in the served dtype."""
    idx, weights = _route(x, p, top_k, scaling, precision)
    x_r = base._round_operand(x, precision)

    def one(acc, args):
        """Held expert ``e`` for every token, weighted where it was
        picked."""
        e, gate_up, down = args
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        y = _gated(x_r, gate_up.astype(jnp.float32),
                   down.astype(jnp.float32), precision)
        return acc + w_e[:, None] * y, None
    held = experts[1].shape[0]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lo + jnp.arange(held),) + experts)
    return routed + _gated(x_r, p["shared_gate_up"]["kernel"],
                           p["shared_down"]["kernel"], precision)


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "window", "sparse", "theta", "eps", "lo", "top_k",
    "scaling", "precision"))
def _layer(x, p, *, heads, groups, window, sparse, theta, eps, lo, top_k,
           scaling, precision):
    """One layer on ``x [s, hidden]``."""
    with jax.default_matmul_precision("highest"):
        stacked = ("experts_gate_up", "experts_down")
        experts = tuple(p["mlp"][k] for k in stacked) if sparse else ()
        p = jax.tree.map(
            lambda t: t.astype(jnp.float32),
            dict(p, mlp={k: v for k, v in p["mlp"].items()
                         if k not in stacked}))
        x = x + _rms_norm(
            _attention(x, p["self_attn"], heads, groups, window, theta,
                       eps, precision),
            p["post_attention_layernorm"]["scale"], eps)
        if sparse:
            y = _experts(x, p["mlp"], experts, lo, top_k, scaling,
                         precision)
        else:
            y = _gated(x, p["mlp"]["input_linear"]["kernel"],
                       p["mlp"]["output_linear"]["kernel"], precision)
        return x + _rms_norm(
            y, p["post_feedforward_layernorm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(x, head, *, precision):
    with jax.default_matmul_precision("highest"):
        return _mm("sh,hv->sv", x, head.astype(jnp.float32), precision)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, scale, *, eps):
    return _rms_norm(x, scale.astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _mtp_input(h, emb, p, *, eps, precision):
    """``W_p [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(h_i)]``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), p)
        return _mm("sc,ch->sh", jnp.concatenate(
            [_rms_norm(emb, p["enorm"]["scale"], eps),
             _rms_norm(h, p["hnorm"]["scale"], eps)], axis=-1),
            p["eh_proj"]["kernel"], precision)


def _kinds(cfg, precision):
    lo = (cfg.get("experts_held") or (0, cfg["num_experts"]))[0]
    return dict(heads=cfg["num_attention_heads"],
                groups=cfg["num_key_value_heads"],
                theta=float(cfg["rope_parameters"]["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]), lo=int(lo),
                top_k=cfg["num_experts_per_tok"],
                scaling=float(cfg["routed_scaling_factor"]),
                precision=precision)


def _padded(tokens):
    n = len(tokens)
    ids = np.zeros((-(-n // PAD_TO) * PAD_TO,), np.int32)
    ids[:n] = tokens
    return jnp.asarray(ids)


#: the last request's stream after the final norm: ``[key, h]``
_kept = [None, None]


def _stream(cfg, params, tokens, precision):
    """``h [padded, hidden]``: the stream after the final norm of ONE
    request."""
    key = (id(params), precision, len(tokens), hash(tuple(tokens)))
    if _kept[0] != key:
        x = jnp.take(params["embed_tokens"], _padded(tokens),
                     axis=0).astype(jnp.float32)
        kinds = _kinds(cfg, precision)
        for i in range(cfg["num_hidden_layers"]):
            x = _layer(x, params[f"layers_{i}"],
                       window=int(cfg["sliding_windows"][i]),
                       sparse=cfg["mlp_layer_types"][i] == "sparse",
                       **kinds)
        _kept[:] = [key, _final_norm(x, params["norm"]["scale"],
                                     eps=kinds["eps"])]
    return _kept[1]


def logits(cfg, params, tokens, rows, precision="float32"):
    """``(logits [hi - lo, V] float32, None)`` of positions ``rows =
    (lo, hi)`` of ONE request ``tokens`` (a list of ids). ``cfg`` is
    the configuration file's mapping, ``params`` a tree in the module's
    layout in any float dtype. The second value is where
    ``smallthinker_decoder`` reports its picks flipped by a bfloat16
    stream; not reckoned for this family."""
    lo, hi = rows
    return _head(_stream(cfg, params, tokens, precision)[lo:hi],
                 params["lm_head"], precision=precision), None


def mtp_argmax(cfg, params, tokens, rows, precision="float32"):
    """``[hi - lo]`` int: the multi-token-prediction block's argmax at
    positions ``rows = (lo, hi)`` of ONE request ``tokens``,
    teacher-forced: position ``i`` reads ``h_i`` and token ``i + 1`` and
    names token ``i + 2`` (``hi <= len(tokens) - 1``)."""
    h = _stream(cfg, params, tokens, precision)
    n = len(tokens)
    lo, hi = rows
    if hi > n - 1:
        raise ValueError(f"position {hi - 1} of {n} tokens has no next "
                         f"token")
    kinds = _kinds(cfg, precision)
    p = params["mtp"]
    emb = jnp.take(params["embed_tokens"],
                   _padded(list(tokens[1:]) + [0]),
                   axis=0).astype(jnp.float32)
    u = _mtp_input(h, emb, {k: p[k] for k in ("enorm", "hnorm",
                                               "eh_proj")},
                   eps=kinds["eps"], precision=precision)
    y = _layer(u, p["layer"], window=0, sparse=True, **kinds)
    y = _final_norm(y, p["norm"]["scale"], eps=kinds["eps"])
    out = _head(y[lo:hi], params["lm_head"], precision=precision)
    return np.asarray(jnp.argmax(out, axis=-1))
