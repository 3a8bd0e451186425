"""Ring attention == dense attention (exact), fwd and bwd, plus the
context-parallel GPT end-to-end path on the CPU mesh."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlefleetx_tpu.ops.attention import dot_product_attention
from paddlefleetx_tpu.ops.ring_attention import (
    ring_attention, ring_attention_sharded,
)
from paddlefleetx_tpu.parallel import (
    TopologyConfig, build_mesh, make_sharding_rules,
)
from paddlefleetx_tpu.parallel.mesh import set_mesh


def _qkv(b=2, s=32, h=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, s, h, d)), jnp.float32)
    return mk(), mk(), mk()


def _cp_mesh(n=4):
    topo = TopologyConfig(dp_degree=2 if n <= 4 else 1, cp_degree=n)
    return build_mesh(topo, devices=jax.devices()[:topo.world_size])


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense(causal):
    q, k, v = _qkv()
    mesh = _cp_mesh(4)
    want = dot_product_attention(q, k, v, causal=causal)
    got = ring_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_ring_grads_match_dense():
    q, k, v = _qkv(s=16)
    mesh = _cp_mesh(4)

    def dense_loss(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    def ring_loss(q, k, v):
        return jnp.sum(
            ring_attention_sharded(q, k, v, mesh, causal=True) ** 2)

    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-5, rtol=1e-4)


def test_ring_under_the_pipeline_stage_vmap():
    """pp2 x cp2: the pipeline names the stage dim of its vmap after
    the pp axis, so the ring's shard_map is batched over a mesh axis —
    forward and grads still match dense attention stage by stage."""
    from paddlefleetx_tpu.parallel.pipeline import _slot_vmap
    topo = TopologyConfig(pp_degree=2, cp_degree=2, dp_degree=2)
    mesh = build_mesh(topo)
    set_mesh(mesh)
    # [vpp, stage, b, s, h, d]
    q, k, v = (jnp.stack([a, a[::-1]])[None] for a in _qkv(s=16))

    def dense_loss(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    def ring_loss(q, k, v):
        return jnp.sum(
            ring_attention_sharded(q, k, v, mesh, causal=True) ** 2)

    grads = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2))  # noqa: E731
    want = jax.vmap(jax.vmap(grads(dense_loss)))(q, k, v)
    got = jax.jit(_slot_vmap(grads(ring_loss), 2))(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-5, rtol=1e-4)


def test_ring_single_block_degenerate():
    """cp group of size 1 == plain attention."""
    q, k, v = _qkv(s=8)
    mesh = _cp_mesh(1)
    got = ring_attention_sharded(q, k, v, mesh, causal=True)
    want = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_ring_bf16_inputs():
    q, k, v = _qkv()
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    mesh = _cp_mesh(4)
    got = ring_attention_sharded(qb, kb, vb, mesh, causal=True)
    assert got.dtype == jnp.bfloat16
    want = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=3e-2,
        rtol=3e-2)


def test_context_parallel_gpt_matches_single_device():
    """GPT forward+grads with cp=4 (ring attention + seq-sharded
    activations) == single-device."""
    from paddlefleetx_tpu.models.gpt import (
        GPTConfig, GPTForPretraining, cross_entropy_loss,
    )
    import dataclasses

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    ffn_hidden_size=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
    mask = jnp.ones((2, 32), jnp.float32)

    model = GPTForPretraining(cfg)
    params = nn.meta.unbox(model.init(
        {"params": jax.random.key(0)}, ids))["params"]

    def loss_fn(m):
        def f(p, i, l, msk):
            logits = m.apply({"params": p}, i)
            return cross_entropy_loss(logits, l, msk)
        return f

    ref_loss, ref_grads = jax.value_and_grad(loss_fn(model))(
        params, ids, labels, mask)

    topo = TopologyConfig(dp_degree=2, cp_degree=4)
    mesh = build_mesh(topo)
    set_mesh(mesh)
    rules = make_sharding_rules(topo)
    cp_model = GPTForPretraining(
        dataclasses.replace(cfg, context_parallel=True))
    logical = nn.get_partition_spec(
        jax.eval_shape(cp_model.init, {"params": jax.random.key(0)},
                       ids))
    shardings = nn.logical_to_mesh_sharding(logical, mesh, list(rules))
    params_s = jax.device_put({"params": params},
                              nn.meta.unbox(shardings))["params"]
    data_sharding = NamedSharding(mesh, P(("dp", "fsdp"), "cp"))
    ids_s, labels_s, mask_s = (jax.device_put(x, data_sharding)
                               for x in (ids, labels, mask))
    with mesh, nn.logical_axis_rules(list(rules)):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn(cp_model)))(
            params_s, ids_s, labels_s, mask_s)
    set_mesh(None)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3),
        ref_grads, grads)


def test_cp_excludes_megatron_sp():
    with pytest.raises(ValueError):
        TopologyConfig(cp_degree=2, mp_degree=2, sequence_parallel=True)


def _ulysses_golden(topo, cfg_kw, ids_seed=1):
    """Shared harness: GPT loss+grads under Ulysses cp vs single-device."""
    import dataclasses

    from paddlefleetx_tpu.models.gpt import (
        GPTConfig, GPTForPretraining, cross_entropy_loss,
    )

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    ffn_hidden_size=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0, **cfg_kw)
    rng = np.random.default_rng(ids_seed)
    ids = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
    mask = jnp.ones((2, 32), jnp.float32)

    model = GPTForPretraining(cfg)
    params = nn.meta.unbox(model.init(
        {"params": jax.random.key(0)}, ids))["params"]

    def loss_fn(m):
        def f(p, i, l, msk):
            logits = m.apply({"params": p}, i)
            return cross_entropy_loss(logits, l, msk)
        return f

    ref_loss, ref_grads = jax.value_and_grad(loss_fn(model))(
        params, ids, labels, mask)

    mesh = build_mesh(topo)
    set_mesh(mesh)
    rules = make_sharding_rules(topo)
    cp_model = GPTForPretraining(dataclasses.replace(
        cfg, context_parallel=True, context_parallel_algo="ulysses"))
    logical = nn.get_partition_spec(
        jax.eval_shape(cp_model.init, {"params": jax.random.key(0)},
                       ids))
    shardings = nn.logical_to_mesh_sharding(logical, mesh, list(rules))
    params_s = jax.device_put({"params": params},
                              nn.meta.unbox(shardings))["params"]
    data_sharding = NamedSharding(mesh, P(("dp", "fsdp"), "cp"))
    ids_s, labels_s, mask_s = (jax.device_put(x, data_sharding)
                               for x in (ids, labels, mask))
    with mesh, nn.logical_axis_rules(list(rules)):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn(cp_model)))(
            params_s, ids_s, labels_s, mask_s)
    set_mesh(None)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3),
        ref_grads, grads)


def test_ulysses_cp_gpt_matches_single_device():
    """cp4 all-to-all (Ulysses): heads shard over cp during attention,
    seq gathers — loss/grads == single-device."""
    _ulysses_golden(TopologyConfig(dp_degree=2, cp_degree=4), {})


def test_ulysses_composes_with_tp():
    """cp2 x mp2: heads shard over cp*mp=4 during attention while the
    MLP stays tensor-parallel."""
    _ulysses_golden(TopologyConfig(dp_degree=2, cp_degree=2,
                                   mp_degree=2), {})


def test_ulysses_allows_attention_dropout():
    """The ring guard must not fire for the Ulysses algorithm (exact
    attention per head shard supports dropout)."""
    from paddlefleetx_tpu.models import build_module
    from paddlefleetx_tpu.utils.config import AttrDict, process_configs

    cfg = AttrDict({
        "Global": AttrDict({"seed": 1, "local_batch_size": 8,
                            "micro_batch_size": 8,
                            "global_batch_size": None}),
        "Engine": AttrDict({"max_steps": 1,
                            "mix_precision": AttrDict({})}),
        "Model": AttrDict({
            "module": "GPTModule", "name": "GPT", "vocab_size": 64,
            "hidden_size": 32, "num_layers": 2,
            "num_attention_heads": 4, "ffn_hidden_size": 64,
            "max_position_embeddings": 32,
            "hidden_dropout_prob": 0.1,
            "attention_probs_dropout_prob": 0.1,
            "context_parallel_algo": "ulysses",
        }),
        "Distributed": AttrDict({"dp_degree": 2, "cp_degree": 4,
                                 "sharding": AttrDict({})}),
        "Optimizer": AttrDict({
            "name": "FusedAdamW",
            "lr": AttrDict({"name": "CosineAnnealingWithWarmupDecay",
                            "decay_steps": 10, "warmup_rate": 0.1,
                            "max_lr": 1e-3, "min_lr": 1e-4}),
        }),
    })
    process_configs(cfg, nranks=8)
    module = build_module(cfg)  # must not raise the ring-dropout guard
    assert module.model_config.context_parallel_algo == "ulysses"


def test_ulysses_heads_divisibility_guard():
    from paddlefleetx_tpu.utils.config import AttrDict
    from paddlefleetx_tpu.models.language_utils import (
        process_model_configs,
    )
    cfg = AttrDict({
        "Global": AttrDict({"local_batch_size": 8,
                            "micro_batch_size": 8}),
        "Model": AttrDict({"hidden_size": 32, "num_layers": 2,
                           "num_attention_heads": 6,
                           "context_parallel_algo": "ulysses"}),
        "Distributed": AttrDict({"pp_degree": 1, "mp_degree": 1,
                                 "dp_degree": 2, "cp_degree": 4}),
    })
    with pytest.raises(ValueError, match="divisible by"):
        process_model_configs(cfg)
