"""Real-chip step-time smoke for the ViT, Imagen, and ERNIE families.

Ad hoc: python scripts/smoke_family_tpu.py [vit|imagen|ernie] —
measures a bf16 train step (fwd+bwd+adamw) at a production-shaped
operating point on the attached chip. Numbers are recorded in
projects/{vit,imagen}/README.md and projects/ernie/README.md.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root, from any cwd

import jax
import jax.numpy as jnp
import numpy as np
import optax


def _sync(x):
    float(jnp.ravel(jax.tree.leaves(x)[0])[0].astype(jnp.float32))


def _step_time(step, state, *batch, n=10):
    state = step(state, *batch)
    _sync(state)
    t0 = time.perf_counter()
    for _ in range(n):
        state = step(state, *batch)
    _sync(state)
    return (time.perf_counter() - t0) / n


def smoke_vit(batch=128):
    """One jitted ViT train step on chip; returns the images/s
    record."""
    from paddlefleetx_tpu.models.vit.vit import VISION_MODELS
    from paddlefleetx_tpu.models.vit.loss import ViTCELoss

    model = VISION_MODELS["ViT_base_patch16_224"](dtype="bfloat16")
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.uniform(-1, 1, (batch, 224, 224, 3)),
                         jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), images[:1])["params"]
    tx = optax.adamw(1e-3, weight_decay=0.05, mu_dtype=jnp.bfloat16)
    opt = tx.init(params)
    criterion = ViTCELoss(epsilon=0.1)

    def loss_fn(p, x, y):
        return criterion(model.apply({"params": p}, x,
                                     deterministic=True), y)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x, y):
        p, o = state
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        up, o = tx.update(g, o, p)
        return optax.apply_updates(p, up), o

    dt = _step_time(step, (params, opt), images, labels)
    print(f"ViT-base/16 224 bf16 train step, bs={batch}: "
          f"{dt * 1e3:.1f} ms = {batch / dt:.0f} images/s")
    return {"metric": "vit_base16_224_train_images_per_sec",
            "value": round(batch / dt, 1), "unit": "images/s",
            "vs_baseline": None, "batch": batch}


def smoke_imagen(batch=16):
    """One jitted Imagen train step on chip; returns the images/s
    record."""
    from paddlefleetx_tpu.models.imagen.modeling import (
        build_imagen_model, imagen_criterion,
    )

    model = build_imagen_model("imagen_397M_text2im_64",
                               dtype="bfloat16")
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.uniform(0, 1, (batch, 3, 64, 64)),
                         jnp.float32)
    emb = jnp.asarray(rng.normal(size=(batch, 77, model.config.text_embed_dim)),
                      jnp.bfloat16)
    mask = jnp.ones((batch, 77), jnp.int32)
    variables = jax.jit(functools.partial(
        model.init))({"params": jax.random.key(0),
                      "diffusion": jax.random.key(1)},
                     images[:1], emb[:1], mask[:1])
    params = variables["params"]
    tx = optax.adamw(1e-4, mu_dtype=jnp.bfloat16)
    opt = tx.init(params)

    def loss_fn(p, x, e, m, key):
        pred, target, log_snr, gamma = model.apply(
            {"params": p}, x, e, m, rngs={"diffusion": key})
        return imagen_criterion(pred, target, log_snr, gamma)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x, e, m):
        p, o, key = state
        key, sub = jax.random.split(key)
        loss, g = jax.value_and_grad(loss_fn)(p, x, e, m, sub)
        up, o = tx.update(g, o, p)
        return optax.apply_updates(p, up), o, key

    dt = _step_time(step, (params, opt, jax.random.key(2)),
                    images, emb, mask)
    print(f"Imagen base U-Net 397M text2im 64x64 bf16 train step, "
          f"bs={batch}: {dt * 1e3:.1f} ms = {batch / dt:.0f} images/s")
    return {"metric": "imagen_397M_text2im64_train_images_per_sec",
            "value": round(batch / dt, 1), "unit": "images/s",
            "vs_baseline": None, "batch": batch}


def smoke_ernie(batch=32, seq=512):
    """ERNIE-345M-class encoder MLM train step (the reference's
    ``pretrain_ernie_345M_single_card.yaml`` geometry: h=1024, 24
    layers, s=512)."""
    from paddlefleetx_tpu.models.ernie.config import ErnieConfig
    from paddlefleetx_tpu.models.ernie.model import (
        ErnieForPretraining, ernie_pretraining_loss,
    )
    from paddlefleetx_tpu.models.ernie.modules import apply_mlm_masking

    cfg = ErnieConfig(
        vocab_size=50304, hidden_size=1024, num_hidden_layers=24,
        num_attention_heads=16, max_position_embeddings=seq,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype="bfloat16", use_flash_attention=True, scan_layers=False)
    model = ErnieForPretraining(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    params = jax.jit(model.init)(
        {"params": jax.random.key(0)}, tokens[:1])["params"]
    tx = optax.adamw(1e-4, mu_dtype=jnp.bfloat16)
    opt = tx.init(params)

    def loss_fn(p, masked, labels):
        scores, _ = model.apply({"params": p}, masked,
                                deterministic=True)
        return ernie_pretraining_loss(scores, labels,
                                      with_nsp_loss=False)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, tokens):
        p, o, key = state
        key, sub = jax.random.split(key)
        masked, labels = apply_mlm_masking(sub, tokens, cfg)
        loss, g = jax.value_and_grad(loss_fn)(p, masked, labels)
        up, o = tx.update(g, o, p)
        return optax.apply_updates(p, up), o, key

    dt = _step_time(step, (params, opt, jax.random.key(2)), tokens)
    print(f"ERNIE-345M MLM bf16 train step, bs={batch}/s={seq}: "
          f"{dt * 1e3:.1f} ms = {batch * seq / dt:.0f} tokens/s")
    return {"metric": "ernie_345M_mlm_train_tokens_per_sec",
            "value": round(batch * seq / dt, 1), "unit": "tokens/s",
            "vs_baseline": None, "batch": batch, "seq": seq}


if __name__ == "__main__":
    from paddlefleetx_tpu.utils.env import setup_compilation_cache
    setup_compilation_cache()  # the unrolled 24-layer ERNIE compiles slowly
    which = sys.argv[1:] or ["vit", "imagen", "ernie"]
    print("device:", jax.devices()[0].device_kind)
    if "vit" in which:
        print(json.dumps(smoke_vit()))
    if "imagen" in which:
        print(json.dumps(smoke_imagen()))
    if "ernie" in which:
        print(json.dumps(smoke_ernie()))
