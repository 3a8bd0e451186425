"""Parity and dispatch probes for the overlapped tensor-parallel
collective matmuls (ops/collective_matmul.py).

The ISSUE-2 acceptance contract: `all_gather_matmul` /
`matmul_reduce_scatter` match the plain GSPMD lowering — forward AND
grads through the custom VJPs — to fp32 tolerance for mp in {2, 4},
and a non-divisible shape exercises the model-level fallback. The
dispatch rows mirror docs/tensor_parallel.md: nothing asks for the
rings, a sequence-parallel layer on a mesh with mp >= 2 takes them
(ISSUE 40), so the plain GSPMD twin of a model is built by closing the
gate (`conftest.py::plain_gspmd`).
"""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddlefleetx_tpu.models.gpt import (
    GPTConfig, GPTForPretraining, cross_entropy_loss,
)
from paddlefleetx_tpu.ops.collective_matmul import (
    all_gather_matmul, matmul_reduce_scatter, mp_ring_viable,
)
from paddlefleetx_tpu.parallel import (
    TopologyConfig, build_mesh, make_sharding_rules,
)
from paddlefleetx_tpu.parallel.mesh import set_mesh


def _mesh(mp):
    # 8 CPU devices: mp4 x dp2 and mp2 x dp2 x fsdp2
    kw = {"mp_degree": mp, "dp_degree": 2}
    if mp == 2:
        kw["sharding_degree"] = 2
    return build_mesh(TopologyConfig(**kw, sequence_parallel=True))


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


@pytest.fixture
def counters():
    from paddlefleetx_tpu.observability import metrics
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    return reg


# -- op-level parity: forward and grads vs the plain lowering ---------

@pytest.mark.parametrize("mp", [2, 4])
def test_all_gather_matmul_parity(mp):
    mesh = _mesh(mp)
    rng = np.random.default_rng(0)
    x, w = _rand(rng, 4, 8, 6), _rand(rng, 6, 12)

    def ring(x, w):
        y = all_gather_matmul(x, w, mesh)
        return jnp.sum(jnp.sin(y)), y

    def plain(x, w):
        y = jnp.einsum("bsk,kn->bsn", x, w)
        return jnp.sum(jnp.sin(y)), y

    with mesh:
        (loss, y), grads = jax.jit(jax.value_and_grad(
            ring, argnums=(0, 1), has_aux=True))(x, w)
    (ref_loss, ref_y), ref_grads = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True))(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               atol=1e-5)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for g, ref in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                   atol=1e-4)


@pytest.mark.parametrize("mp", [2, 4])
def test_all_gather_matmul_multidim_feature(mp):
    # the fused-qkv shape: w [k, 3, heads, hd], ring shard on heads
    mesh = _mesh(mp)
    rng = np.random.default_rng(1)
    x, w = _rand(rng, 4, 8, 6), _rand(rng, 6, 3, 4, 5)

    def ring(x, w):
        return jnp.sum(jnp.sin(
            all_gather_matmul(x, w, mesh, w_shard_dim=1)))

    def plain(x, w):
        return jnp.sum(jnp.sin(jnp.einsum("bsk,kthd->bsthd", x, w)))

    with mesh:
        loss, grads = jax.jit(jax.value_and_grad(
            ring, argnums=(0, 1)))(x, w)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1)))(x, w)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for g, ref in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                   atol=1e-4)


@pytest.mark.parametrize("mp", [2, 4])
@pytest.mark.parametrize("contract_ndim", [1, 2])
def test_matmul_reduce_scatter_parity(mp, contract_ndim):
    mesh = _mesh(mp)
    rng = np.random.default_rng(2)
    if contract_ndim == 1:
        x, w = _rand(rng, 4, 8, 8), _rand(rng, 8, 10)
        ref_eq = "bsk,kn->bsn"
    else:
        # the out-proj shape: x [b, s, heads, hd] contracting both
        x, w = _rand(rng, 4, 8, 4, 3), _rand(rng, 4, 3, 10)
        ref_eq = "bshd,hdn->bsn"

    def ring(x, w):
        return jnp.sum(jnp.cos(matmul_reduce_scatter(
            x, w, mesh, contract_ndim=contract_ndim)))

    def plain(x, w):
        return jnp.sum(jnp.cos(jnp.einsum(ref_eq, x, w)))

    with mesh:
        loss, grads = jax.jit(jax.value_and_grad(
            ring, argnums=(0, 1)))(x, w)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1)))(x, w)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for g, ref in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                   atol=1e-4)


# -- dispatch probes: the docs/tensor_parallel.md fallback rows -------

def test_mp_ring_viable_rows():
    mesh = _mesh(4)                       # mp4 x dp2: dataflow size 2
    assert mp_ring_viable(mesh, 4, 8, (4,))
    assert not mp_ring_viable(None, 4, 8, (4,))          # no mesh
    assert not mp_ring_viable(mesh, 4, 7, (4,))          # seq % mp
    assert not mp_ring_viable(mesh, 3, 8, (4,))          # batch % df
    assert not mp_ring_viable(mesh, 4, 8, (6,))          # dim % mp
    assert not mp_ring_viable(mesh, 1, 8, (4,))          # init sample
    assert not mp_ring_viable(mesh, 4, 1, (4,))          # decode step
    mp1 = build_mesh(TopologyConfig(dp_degree=8))
    assert not mp_ring_viable(mp1, 8, 8, (4,))           # mp == 1


def test_param_tree_identical_with_and_without_knob():
    """_CollectiveDense must create the exact DenseGeneral tree —
    names, shapes, logical axes — whether or not the layer is one that
    dispatches to the rings (there is no knob any more: a
    sequence-parallel layer is the one that does), so checkpoints and
    abstract init are dispatch-independent."""
    base = dict(vocab_size=64, hidden_size=16, num_layers=2,
                num_attention_heads=4, max_position_embeddings=32)
    ids = jnp.zeros((1, 8), jnp.int32)

    def shapes(cfg):
        v = jax.eval_shape(GPTForPretraining(cfg).init,
                           {"params": jax.random.key(0)}, ids)
        return jax.tree.map(
            lambda x: (x.value.shape, x.names)
            if isinstance(x, nn.Partitioned) else x.shape,
            v, is_leaf=lambda x: isinstance(x, nn.Partitioned))

    set_mesh(_mesh(4))
    on = shapes(GPTConfig(**base, sequence_parallel=True))
    off = shapes(GPTConfig(**base))
    assert jax.tree.structure(on) == jax.tree.structure(off)
    assert jax.tree.leaves(on) == jax.tree.leaves(off)
    # and it is DenseGeneral's tree, kernel by kernel
    x = jnp.zeros((2, 8, 16))
    dense = jax.eval_shape(
        nn.DenseGeneral((3, 4, 4)).init, jax.random.key(0), x)
    qkv = off["params"]["gpt"]["decoder"]["self_attn"]["qkv_proj"]
    assert qkv["kernel"][0][1:] == dense["params"]["kernel"].shape
    assert qkv["bias"][0][1:] == dense["params"]["bias"].shape


def test_model_falls_back_on_indivisible_seq(counters):
    """seq=14 does not divide mp=4: every site must take the plain
    path, say so, and still match the single-device reference
    exactly."""
    kw = dict(vocab_size=64, hidden_size=16, num_layers=2,
              num_attention_heads=4, max_position_embeddings=32,
              hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, 64, (8, 14)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 64, (8, 14)), jnp.int32)
    mask = jnp.ones((8, 14), jnp.float32)

    ref_model = GPTForPretraining(GPTConfig(**kw))
    variables = ref_model.init({"params": jax.random.key(0)},
                               jnp.zeros((1, 8), jnp.int32))
    params = nn.meta.unbox(variables)["params"]
    ref_loss = cross_entropy_loss(
        ref_model.apply({"params": params}, ids), labels, mask)

    cfg = GPTConfig(**kw, sequence_parallel=True)
    topo = TopologyConfig(mp_degree=4, dp_degree=2,
                          sequence_parallel=True)
    mesh = build_mesh(topo)
    set_mesh(mesh)
    model = GPTForPretraining(cfg)
    with mesh, nn.logical_axis_rules(list(make_sharding_rules(topo))):
        p = jax.device_put(params)
        loss = jax.jit(lambda p: cross_entropy_loss(
            model.apply({"params": p}, ids), labels, mask))(p)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    assert counters.counter("mp_linear/rings") == 0
    # four sites in each of the two unrolled layers
    assert counters.counter("mp_linear/gspmd_fallback") == 8


# -- engagement: what the program observes, not a recipe line ---------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell_module():
    """The 1.3B cell's own recipe and override list
    (``chipbench/configs/gpt-1.3b.json``: mp2 x fsdp2 ZeRO-3,
    sequence parallel, save_dots, loss_chunks 8, the YAML's scanned
    layers) at two small layers, float32."""
    from paddlefleetx_tpu.models import build_module
    from paddlefleetx_tpu.utils.config import get_config
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "gpt-1.3b.json")) as f:
        cell = json.load(f)
    small = ["Model.hidden_size=32", "Model.num_layers=2",
             "Model.num_attention_heads=4", "Model.ffn_hidden_size=64",
             "Model.vocab_size=128", "Model.max_position_embeddings=32",
             "Model.hidden_dropout_prob=0.0",
             "Model.attention_probs_dropout_prob=0.0",
             "Engine.mix_precision.use_pure_fp16=False",
             "Global.local_batch_size=4", "Global.micro_batch_size=4"]
    cfg = get_config(os.path.join(ROOT, cell["yaml"]),
                     overrides=cell["overrides"] + small, nranks=4)
    return cfg, build_module(cfg)


def test_rings_engage_at_the_cells_layout_from_its_overrides_alone(
        counters, plain_gspmd):
    """mp2 x fsdp2 (ZeRO-3) + sequence parallel + save_dots over
    scanned layers on four devices: the cell's override list names no
    ring, the four sites take them, and loss and every gradient leaf
    match the plain GSPMD twin."""
    cfg, module = _cell_module()
    mcfg = module.model_config
    assert mcfg.sequence_parallel and mcfg.scan_layers
    assert mcfg.recompute_granularity == "save_dots"
    assert mcfg.dtype == "float32"
    assert not hasattr(mcfg, "use_collective_matmul")
    topo = TopologyConfig.from_config(cfg)
    assert (topo.mp_degree, topo.sharding_degree,
            topo.sharding_stage) == (2, 2, 3)
    mesh = build_mesh(topo, devices=jax.devices()[:4])
    set_mesh(mesh)
    rules = list(make_sharding_rules(topo))

    variables = module.model.init({"params": jax.random.key(0)},
                                  jnp.zeros((1, 8), jnp.int32))
    shardings = nn.logical_to_mesh_sharding(
        nn.get_partition_spec(variables), mesh, rules)
    params = jax.device_put(nn.meta.unbox(variables),
                            shardings)["params"]
    rng = np.random.default_rng(4)
    rows = NamedSharding(mesh, P(("dp", "fsdp"), None))
    tokens, labels = (jax.device_put(
        jnp.asarray(rng.integers(0, 128, (4, 16)), jnp.int32), rows)
        for _ in range(2))
    batch = (tokens, jnp.broadcast_to(jnp.arange(16), (4, 16)), labels,
             jax.device_put(jnp.ones((4, 16), jnp.float32), rows))

    def step():
        with mesh, nn.logical_axis_rules(rules):
            return jax.jit(lambda p: module.loss_and_grad(
                p, batch, jax.random.key(1)))(params)

    def sites(name):
        # the scanned layer's four sites, once per trace of its body
        n = counters.counter("mp_linear/" + name)
        assert n % 4 == 0
        return n

    counters.reset()
    loss, grads = step()
    assert sites("rings") >= 4 and sites("gspmd_fallback") == 0
    plain_gspmd()
    counters.reset()
    ref_loss, ref_grads = step()
    assert sites("rings") == 0 and sites("gspmd_fallback") >= 4
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3),
        grads, ref_grads)


@pytest.mark.parametrize("mp, sp", [(1, True), (2, False)],
                         ids=["mp1-sp", "mp2-no-sp"])
def test_no_ring_without_an_mp_axis_or_sequence_parallel(
        mp, sp, counters, plain_gspmd):
    """Off the rings' ground the layer is the plain one: no
    ``ppermute`` in its jaxpr, no site counted either way, and the
    same jaxpr as with the gate closed."""
    topo = TopologyConfig(mp_degree=mp, dp_degree=8 // mp,
                          sequence_parallel=sp)
    mesh = build_mesh(topo)
    set_mesh(mesh)
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=32,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    sequence_parallel=sp)
    model = GPTForPretraining(cfg)
    ids = jnp.zeros((8, 16), jnp.int32)
    variables = model.init({"params": jax.random.key(0)}, ids[:1, :8])
    params = nn.meta.unbox(variables)["params"]

    def jaxpr():
        with mesh, nn.logical_axis_rules(
                list(make_sharding_rules(topo))):
            return str(jax.make_jaxpr(jax.grad(
                lambda p: jnp.sum(model.apply({"params": p}, ids))))(
                    params))

    here = jaxpr()
    assert "ppermute" not in here and "shard_map" not in here
    assert counters.counter("mp_linear/rings") == 0
    assert counters.counter("mp_linear/gspmd_fallback") == 0
    plain_gspmd()
    assert jaxpr() == here
