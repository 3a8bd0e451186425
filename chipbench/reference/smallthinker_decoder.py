"""Plain float32 reference of the SmallThinker-style decoder the cell
``smallthinker.serve-mixed-len`` serves: a copy of
``paddlefleetx_tpu/models/smallthinker/reference.py`` (it imports
nothing from ``paddlefleetx_tpu``; it shares only the LAYOUT of the
parameter tree) made to fit a 12,288-token request beside the weights.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, cache, paging,
batching or sort. Equations (``x`` the residual stream entering layer
``l``):

1. router, before attention and before the norm: ``r = x W_r``.
2. ``h = RMSNorm(x)`` (eps from the config); ``q = h W_q`` (28 heads x
   128), ``k = h W_k``, ``v = h W_v`` (4 heads x 128), no bias, no q/k
   norm; where ``rope_layout[l]`` is 1 rotate-half RoPE over all 128
   dims (pairs ``(i, i + 64)``, angle ``p theta^(-2i/128)``), where it
   is 0 no position encoding at all; query head ``7 g + m`` reads K/V
   head ``g``; scale ``128^-1/2``, causal; where
   ``sliding_window_layout[l]`` is 1, key ``j`` is visible to query
   ``i`` iff ``i - window < j <= i``; ``x' = x + concat(heads) W_o``.
3. ``u = RMSNorm(x')``; ``T = top_k(r)``; ``w = softmax(r_T)``;
   ``E_e(u) = W_down,e (relu(W_gate,e u) * (W_up,e u))``;
   ``y = x' + sum_{e in T} w_e E_e(u)``.
4. after the last layer ``RMSNorm``, then an untied head.

Departures from the published description, each deliberate:
  * the weights arrive in the dtype they are served in (bfloat16) and
    are widened to float32 a layer at a time: 3,967 M parameters in
    float32 would be 15.9 GB of a 16 GB chip. The values are the same;
  * one request at a time, layer by layer (a jitted layer, a Python
    loop); attention one K/V group (7 query heads) and one block of
    query rows at a time over ALL keys with the mask applied to the
    scores: the dense softmax in pieces, not an online one;
  * every expert is computed for every token (a ``scan`` over the 64)
    and combined with a 0/w mask: the published code gathers tokens per
    expert, same sum;
  * ``norm_topk_prob`` is not applied: the softmax over the picked
    logits already sums to 1;
  * the head is computed for the rows that are judged only;
  * sequences are right-padded to a multiple of ``PAD_TO`` so that six
    requests compile a few shapes; causality keeps the pad out of every
    judged row;
  * ``precision`` other than "float32" exists for the *control* only: it
    rounds both operands of every matmul (``gpt2_decoder._round_operand``,
    imported) the way a tempting "speed-up" would.
Assumed where the catalog row does not say: the router reads the
un-normalised stream; RoPE pairs by halves.
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


def _attention(h, p, heads, groups, rope, window, theta, precision):
    s = h.shape[0]
    d = p["q_proj"]["kernel"].shape[-1]
    m = heads // groups
    q = _mm("sh,hnd->snd", h, p["q_proj"]["kernel"], precision)
    k = _mm("sh,hnd->snd", h, p["k_proj"]["kernel"], precision)
    v = _mm("sh,hnd->snd", h, p["v_proj"]["kernel"], precision)
    if rope:
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
            """The 7 query heads of K/V head ``g``."""
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


def _experts(u, p, logits, top_k, precision):
    picked, idx = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(picked, axis=-1)
    u_r = base._round_operand(u, precision)

    def one(acc, args):
        """Expert ``e`` for every token, weighted where it was picked."""
        e, gate_up, down = args
        gate_up, down = (t.astype(jnp.float32) for t in (gate_up, down))
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        width = down.shape[0]              # gate | up on the last axis
        gate = _mm("sh,hf->sf", u_r, gate_up[:, :width], precision)
        up = _mm("sh,hf->sf", u_r, gate_up[:, width:], precision)
        y = _mm("sf,fh->sh", jax.nn.relu(gate) * up, down, precision)
        return acc + w_e[:, None] * y, None
    n = p["experts_down"].shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(n), p["experts_gate_up"], p["experts_down"]))
    return out, idx


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "rope", "window", "theta", "eps", "top_k",
    "precision"))
def _layer(x, p, *, heads, groups, rope, window, theta, eps, top_k,
           precision):
    """One layer on ``x [s, hidden]``: ``(y, the router's picks)``."""
    with jax.default_matmul_precision("highest"):
        experts = p["block_sparse_moe"]
        p = jax.tree.map(
            lambda t: t.astype(jnp.float32),
            {k: v for k, v in p.items() if k != "block_sparse_moe"})
        logits = _mm("sh,he->se", x, p["router"], precision)
        x = x + _attention(
            _rms_norm(x, p["input_layernorm"]["scale"], eps),
            p["self_attn"], heads, groups, rope, window, theta, precision)
        y, idx = _experts(
            _rms_norm(x, p["post_attention_layernorm"]["scale"], eps),
            experts, logits, top_k, precision)
        return x + y, idx


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, head, *, eps, precision):
    with jax.default_matmul_precision("highest"):
        return _mm("sh,hv->sv", _rms_norm(x, norm.astype(jnp.float32), eps),
                   head.astype(jnp.float32), precision)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _flipped(x, router, idx, *, top_k):
    """Share of the picks ``idx`` that are not among the top-k of the
    logits formed from the stream rounded to bfloat16 (top-k is
    discontinuous, and the program feeds its float32 router a bfloat16
    stream): what the limits have to live with."""
    with jax.default_matmul_precision("highest"):
        low = jnp.dot(x.astype(jnp.bfloat16).astype(jnp.float32),
                      router.astype(jnp.float32))
    _, idx_low = jax.lax.top_k(low, top_k)
    same = jnp.any(idx[:, :, None] == idx_low[:, None, :], axis=-1)
    return 1.0 - jnp.mean(same.astype(jnp.float32))


def logits(cfg, params, tokens, rows, precision="float32"):
    """``(logits [hi - lo, V] float32, flipped share)`` of positions
    ``rows = (lo, hi)`` of ONE request ``tokens`` (a list of ids).
    ``cfg`` is the configuration file's mapping, ``params`` a tree in
    the module's layout in any float dtype."""
    n = len(tokens)
    padded = -(-n // PAD_TO) * PAD_TO
    ids = np.zeros((padded,), np.int32)
    ids[:n] = tokens
    x = jnp.take(params["embed_tokens"], jnp.asarray(ids),
                 axis=0).astype(jnp.float32)
    flips = []
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"layers_{i}"]
        x_in = x
        x, idx = _layer(
            x, p, heads=cfg["num_attention_heads"],
            groups=cfg["num_key_value_heads"],
            rope=bool(cfg["rope_layout"][i]),
            window=cfg["sliding_window_size"]
            if cfg["sliding_window_layout"][i] else 0,
            theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
            top_k=cfg["moe_num_active_primary_experts"],
            precision=precision)
        flips.append(_flipped(x_in[:n], p["router"], idx[:n],
                              top_k=cfg["moe_num_active_primary_experts"]))
    lo, hi = rows
    out = _head(x[lo:hi], params["norm"]["scale"], params["lm_head"],
                eps=float(cfg["rms_norm_eps"]), precision=precision)
    return out, float(np.mean([float(f) for f in flips]))
