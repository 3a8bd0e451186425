"""Plain float32 reference of the GPT-2/GPT-3 style decoder the cells run.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: learned word + position
embeddings, pre-LayerNorm blocks (eps 1e-5), biased multi-head attention
under a causal mask, tanh-approximate GELU MLP, final LayerNorm, logits
tied to the word embedding, masked mean token cross-entropy, its
gradient by ``jax.grad``, and the AdamW step the recipes state (global
norm clip, bias-corrected moments, decoupled weight decay on everything
but biases and norms, linear warm-up into a cosine). No kernel, cache,
scan, remat or chunked loss: a Python loop over the layers drives one
jitted block forward and one jitted block backward (``jax.vjp`` of the
same plain function), so the reference compiles in seconds and holds one
layer's activations at a time. It imports nothing from
``paddlefleetx_tpu``; it shares only the *layout* of the parameter tree
(``gpt/decoder_<i>/...`` or a stacked ``gpt/decoder``), and the values
in that tree are made by ``chipbench/weights.py`` from the seed.

Departures from the program's math (``models/gpt/model.py``), each
deliberate:
  * every matmul, softmax and LayerNorm runs in float32 (the program
    computes in bfloat16 over float32 master weights);
  * attention is the dense s x s softmax (the program runs flash
    kernels / paged decode kernels);
  * the loss is one logsumexp over the whole [rows, s, V] block (the
    program chunks the sequence and rematerialises);
  * rows are processed in blocks, layer by layer, and the blocks'
    gradients summed, so the float32 activations fit (summation order
    differs from one fused batch; float32 makes that ~1e-7 relative);
  * ``precision`` other than "float32" exists for the *control* only
    (``chipbench/tests/test_control.py``): it rounds both operands of
    every matmul to a lower precision the way a tempting "speed-up"
    would.
"""

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


# -- precision of the matmul operands (the control's handle) -------------

def _round_operand(x, precision):
    """``x`` as a float32 array holding values a ``precision`` matmul
    would see. "float32" is the reference itself."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        # e4m3 with one scale per tensor, as an fp8 training or
        # serving recipe would apply it
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = 448.0 / amax
        return (x * scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) / scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, _round_operand(a, precision),
                      _round_operand(b, precision),
                      preferred_element_type=jnp.float32)


# -- the model ------------------------------------------------------------

def _layer_norm(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _block(x, p, precision):
    """One pre-LN decoder block; ``x`` is [rows, s, h] float32."""
    s = x.shape[1]
    y = _layer_norm(x, p["norm1"])
    att = p["self_attn"]
    # fused projection, kernel [h, 3, heads, d]
    qkv = _mm("bsh,hcnd->bscnd", y, att["qkv_proj"]["kernel"],
              precision) + att["qkv_proj"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    d = q.shape[-1]
    scores = _mm("bqnd,bknd->bnqk", q, k, precision) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("bnqk,bknd->bqnd", probs, v, precision)
    y = _mm("bqnd,ndh->bqh", ctx, att["out_proj"]["kernel"],
            precision) + att["out_proj"]["bias"]
    x = x + y
    y = _layer_norm(x, p["norm2"])
    y = _mm("bsh,hf->bsf", y, p["linear1"]["kernel"], precision) \
        + p["linear1"]["bias"]
    y = jax.nn.gelu(y, approximate=True)
    y = _mm("bsf,fh->bsh", y, p["linear2"]["kernel"], precision) \
        + p["linear2"]["bias"]
    return x + y


def layers_of(gpt):
    """The per-layer parameter dicts of either layout: ``decoder_<i>``
    children, or one ``decoder`` whose leaves carry a leading layer
    axis."""
    if "decoder" in gpt:
        n = jax.tree.leaves(gpt["decoder"])[0].shape[0]
        return [jax.tree.map(lambda a, i=i: a[i], gpt["decoder"])
                for i in range(n)]
    n = sum(1 for k in gpt if k.startswith("decoder_"))
    return [gpt[f"decoder_{i}"] for i in range(n)]


def _relayer(gpt, layers):
    """Per-layer trees back into the layout ``gpt`` has."""
    if "decoder" in gpt:
        return {"decoder": jax.tree.map(lambda *a: jnp.stack(a), *layers)}
    return {f"decoder_{i}": g for i, g in enumerate(layers)}


def _embed(emb, tokens):
    return jnp.take(emb["word_embeddings"], tokens, axis=0) \
        + emb["position_embeddings"][None, :tokens.shape[1]]


def _nll_sum(x, final_norm, word_emb, labels, mask, precision):
    """Final LayerNorm, logits tied to the word embedding, and the sum
    of the masked token negative log-likelihoods."""
    logits = _mm("bsh,vh->bsv", _layer_norm(x, final_norm), word_emb,
                 precision)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - picked) * mask)


# Layer by layer: one small program per kind of piece, compiled once
# and driven from a Python loop over the layers, so that compiling the
# reference costs seconds (a whole unrolled float32 forward + backward
# of 24 layers took the chip's compiler 85-95 s in every run). Each
# piece is the plain function above under jit; the backward of a piece
# is jax.vjp of that same function.

_static = functools.partial(jax.jit, static_argnames=("precision",))


@jax.jit
def _embed_fwd(emb, tokens):
    return _embed(emb, tokens)


@jax.jit
def _embed_bwd(emb, tokens, dx):
    return jax.vjp(lambda e: _embed(e, tokens), emb)[1](dx)[0]


@_static
def _block_fwd(x, p, precision):
    with jax.default_matmul_precision("highest"):
        return _block(x, p, precision)


@_static
def _block_bwd(x, p, dy, precision):
    with jax.default_matmul_precision("highest"):
        return jax.vjp(lambda x, p: _block(x, p, precision), x, p)[1](dy)


@_static
def _head_bwd(x, final_norm, word_emb, labels, mask, precision):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_nll_sum, argnums=(0, 1, 2))(
            x, final_norm, word_emb, labels, mask, precision)


@_static
def _head_logits(x, final_norm, word_emb, precision):
    with jax.default_matmul_precision("highest"):
        return _mm("bsh,vh->bsv", _layer_norm(x, final_norm), word_emb,
                   precision)


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def _scale(tree, factor):
    return jax.tree.map(lambda g: g * factor, tree)


def logits(params, tokens, precision="float32"):
    """[rows, s] int tokens -> [rows, s, V] float32 logits."""
    gpt = params["gpt"]
    x = _embed_fwd(gpt["embeddings"], tokens)
    for p in layers_of(gpt):
        x = _block_fwd(x, p, precision)
    return _head_logits(x, gpt["final_norm"],
                        gpt["embeddings"]["word_embeddings"], precision)


def loss_and_grad(params, tokens, labels, mask, rows_per_block=1,
                  precision="float32"):
    """Masked mean token cross-entropy of the batch and its gradient,
    rows taken ``rows_per_block`` at a time, each block forward through
    the layers (keeping every layer's input) and back again."""
    gpt = params["gpt"]
    emb, layers = gpt["embeddings"], layers_of(gpt)
    mask = mask.astype(jnp.float32)
    total, g_emb, g_fn, g_layers = 0.0, None, None, [None] * len(layers)
    for r in range(0, tokens.shape[0], rows_per_block):
        sl = slice(r, r + rows_per_block)
        xs = [_embed_fwd(emb, tokens[sl])]
        for p in layers:
            xs.append(_block_fwd(xs[-1], p, precision))
        nll, (dx, d_fn, d_wte) = _head_bwd(
            xs.pop(), gpt["final_norm"], emb["word_embeddings"],
            labels[sl], mask[sl], precision)
        total = total + nll
        for i in reversed(range(len(layers))):
            dx, dp = _block_bwd(xs.pop(), layers[i], dx, precision)
            g_layers[i] = dp if g_layers[i] is None \
                else _add(g_layers[i], dp)
        d_emb = _embed_bwd(emb, tokens[sl], dx)
        d_emb = dict(d_emb, word_embeddings=d_emb["word_embeddings"]
                     + d_wte)
        g_emb = d_emb if g_emb is None else _add(g_emb, d_emb)
        g_fn = d_fn if g_fn is None else _add(g_fn, d_fn)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    grads = {"gpt": dict(_relayer(gpt, g_layers), embeddings=g_emb,
                         final_norm=g_fn)}
    return total / count, _scale(grads, 1.0 / count)


# -- the optimizer the recipes state ---------------------------------------

def lr_at(count, opt):
    """Linear warm-up over ``warmup_rate * decay_steps`` updates to
    ``max_lr``, cosine to ``min_lr`` at ``decay_steps``; ``count`` is
    the number of updates already applied (0 for the first)."""
    warm = opt["warmup_rate"] * opt["decay_steps"]
    if warm > 0 and count <= warm:
        return opt["max_lr"] * count / max(warm, 1.0)
    if count > opt["decay_steps"]:
        return opt["min_lr"]
    ratio = (count - warm) / max(opt["decay_steps"] - warm, 1.0)
    return opt["min_lr"] + 0.5 * (math.cos(math.pi * ratio) + 1.0) * (
        opt["max_lr"] - opt["min_lr"])


def _decays(path):
    names = [str(getattr(k, "key", k)).lower() for k in path]
    return not any("bias" in n or "norm" in n for n in names)


@jax.jit
def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, clip):
    """The gradient as the optimizer gets it."""
    norm = _global_norm(grads)
    factor = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-30)) \
        if clip else 1.0
    return _scale(grads, factor), norm


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros, "count": 0}


@jax.jit
def _adamw_step(params, grads, mu, nu, t, lr, b1, b2, eps, wd):
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def new(path, p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if _decays(path):
            u = u + wd * p
        return p - lr * u
    return jax.tree_util.tree_map_with_path(new, params, mu, nu), mu, nu


def adamw_update(params, grads, state, opt):
    """One AdamW update on already-clipped ``grads``."""
    t = state["count"] + 1
    params, mu, nu = _adamw_step(
        params, grads, state["mu"], state["nu"], float(t),
        lr_at(state["count"], opt), opt["beta1"], opt["beta2"],
        opt["epsilon"], opt["weight_decay"])
    return params, {"mu": mu, "nu": nu, "count": t}


@jax.jit
def leaf_norms(tree):
    """The L2 norm of every leaf, as a tree of float32 scalars."""
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree)


@jax.jit
def leaf_diff_norms(a, b):
    return jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)
