"""Golden tests: sharded forward/backward == single-device (SURVEY §4).

The reference could only validate hybrid parallelism by running on a
GPU pod; here every strategy (TP, TP+SP, FSDP/ZeRO-3, DP composites)
is checked for exact numerical agreement with the single-device model
on the 8-device CPU mesh.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddlefleetx_tpu.models.gpt import (
    GPTConfig, GPTForPretraining, cross_entropy_loss,
)
from paddlefleetx_tpu.parallel import (
    TopologyConfig, build_mesh, make_sharding_rules,
)
from paddlefleetx_tpu.parallel.mesh import set_mesh

CFG = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                num_attention_heads=4, max_position_embeddings=32,
                hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)


def _data(batch=8, seq=16):
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 64, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 64, (batch, seq)), jnp.int32)
    mask = jnp.ones((batch, seq), jnp.float32)
    return ids, labels, mask


def _loss_and_grads(cfg, variables, ids, labels, mask):
    model = GPTForPretraining(cfg)

    def f(params):
        logits = model.apply({"params": params}, ids)
        return cross_entropy_loss(logits, labels, mask)

    return jax.value_and_grad(f)(variables["params"])


@pytest.fixture(scope="module")
def golden():
    variables = GPTForPretraining(CFG).init(
        {"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32))
    ids, labels, mask = _data()
    loss, grads = _loss_and_grads(CFG, variables, ids, labels, mask)
    return variables, ids, labels, mask, loss, grads


@pytest.mark.parametrize("topo_kw, cfg_kw", [
    ({"mp_degree": 4, "dp_degree": 2}, {}),
    ({"mp_degree": 4, "dp_degree": 2}, {"sequence_parallel": True}),
    ({"sharding_degree": 4, "sharding_stage": 3, "dp_degree": 2}, {}),
    ({"dp_degree": 2, "mp_degree": 2, "sharding_degree": 2,
      "sharding_stage": 3}, {}),
    ({"mp_degree": 4, "dp_degree": 2},
     {"sequence_parallel": True, "rings": False}),
], ids=["tp4xdp2", "tp4xdp2-sp", "zero3x4xdp2", "dp2xtp2xfsdp2",
        "tp4xdp2-sp-plain"])
def test_sharded_matches_single_device(golden, topo_kw, cfg_kw,
                                       plain_gspmd):
    variables, ids, labels, mask, ref_loss, ref_grads = golden
    cfg_kw = dict(cfg_kw)
    if not cfg_kw.pop("rings", True):
        # a sequence-parallel layer takes the mp rings unasked; with
        # the gate closed it is the plain GSPMD lowering
        plain_gspmd()
    topo = TopologyConfig(**topo_kw,
                          sequence_parallel=cfg_kw.get(
                              "sequence_parallel", False))
    cfg = GPTConfig(**{**vars(CFG), **cfg_kw})
    mesh = build_mesh(topo)
    # the collective-matmul dispatch (and ring attention) key off the
    # process-global mesh, as under the engine; the conftest autouse
    # fixture resets it after each test
    set_mesh(mesh)
    rules = make_sharding_rules(topo)

    model = GPTForPretraining(cfg)
    logical_specs = nn.get_partition_spec(
        jax.eval_shape(model.init, {"params": jax.random.key(0)},
                       jnp.zeros((1, 8), jnp.int32)))
    shardings = nn.logical_to_mesh_sharding(logical_specs, mesh,
                                            list(rules))

    params = jax.device_put(nn.meta.unbox(variables),
                            shardings)["params"]
    data_sharding = NamedSharding(mesh, P(("dp", "fsdp"), None))
    ids_s, labels_s, mask_s = (jax.device_put(x, data_sharding)
                               for x in (ids, labels, mask))

    def f(p, i, l, m):
        logits = model.apply({"params": p}, i)
        return cross_entropy_loss(logits, l, m)

    with mesh, nn.logical_axis_rules(list(rules)):
        loss, grads = jax.jit(jax.value_and_grad(f))(
            params, ids_s, labels_s, mask_s)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3),
        nn.meta.unbox(ref_grads), grads)


def test_param_layout_under_tp_fsdp():
    """Spot-check that weights actually land sharded on the mesh."""
    topo = TopologyConfig(mp_degree=2, sharding_degree=2, dp_degree=2,
                          sharding_stage=3)
    mesh = build_mesh(topo)
    rules = make_sharding_rules(topo)
    model = GPTForPretraining(CFG)
    logical_specs = nn.get_partition_spec(
        jax.eval_shape(model.init, {"params": jax.random.key(0)},
                       jnp.zeros((1, 8), jnp.int32)))
    shardings = nn.logical_to_mesh_sharding(logical_specs, mesh,
                                            list(rules))
    p = shardings["params"]["gpt"]
    emb = p["embeddings"]["word_embeddings"]
    assert emb.spec == P("mp", "fsdp")           # vocab x embed
    qkv = p["decoder"]["self_attn"]["qkv_proj"]["kernel"]
    assert qkv.spec == P(None, "fsdp", None, "mp", None)  # layers,embed,3,heads,kv
    mlp1 = p["decoder"]["linear1"]["kernel"]
    assert mlp1.spec == P(None, "fsdp", "mp")    # layers, embed, mlp
