"""MoE / expert parallelism tests (beyond-reference; SURVEY §2.2 EP row).

Covers: routing against a brute-force oracle, capacity-overflow drops,
single-expert degeneration to the dense FFN, the sharded-vs-single-
device golden under EP meshes, the engine train step, decode-with-
cache, and the config guards.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddlefleetx_tpu.models.gpt import (
    GPTConfig, GPTForPretraining, cross_entropy_loss,
)
from paddlefleetx_tpu.models.gpt.moe import (
    MoEMLP, expert_capacity, router_dispatch, sort_routing,
)
from paddlefleetx_tpu.parallel import (
    TopologyConfig, build_mesh, make_sharding_rules,
)
from paddlefleetx_tpu.parallel.mesh import set_mesh

MOE_CFG = GPTConfig(
    vocab_size=64, hidden_size=16, num_layers=2,
    num_attention_heads=4, max_position_embeddings=32,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    moe_num_experts=4, moe_top_k=2, moe_capacity_factor=2.0,
    moe_z_loss_weight=1e-3)


def _routing_oracle(probs, top_k, capacity):
    """Brute-force per-token routing: returns (expert, slot, gate)
    triples per (b, s, k), with -1 for dropped choices."""
    b, s, E = probs.shape
    out = np.full((b, s, top_k, 3), -1.0)
    for bi in range(b):
        fill = np.zeros(E, np.int64)
        for si in range(s):
            order = np.argsort(-probs[bi, si], kind="stable")[:top_k]
            gates = probs[bi, si, order]
            gates = gates / gates.sum() if top_k > 1 else gates
            for ki, (e, g) in enumerate(zip(order, gates)):
                if fill[e] < capacity:
                    out[bi, si, ki] = (e, fill[e], g)
                    fill[e] += 1
    return out


def test_router_dispatch_matches_oracle():
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(2, 12, 4)).astype(np.float32)
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    C = 4
    dispatch, combine, aux_frac = router_dispatch(probs, 2, C)
    oracle = _routing_oracle(np.asarray(probs), 2, C)

    expect_d = np.zeros(dispatch.shape)
    expect_c = np.zeros(combine.shape)
    for bi in range(oracle.shape[0]):
        for si in range(oracle.shape[1]):
            for ki in range(oracle.shape[2]):
                e, c, g = oracle[bi, si, ki]
                if e >= 0:
                    expect_d[bi, si, int(e), int(c)] = 1.0
                    expect_c[bi, si, int(e), int(c)] = g
    np.testing.assert_array_equal(np.asarray(dispatch), expect_d)
    np.testing.assert_allclose(np.asarray(combine), expect_c,
                               atol=1e-6)
    # aux fraction: distribution of first choices
    first = np.asarray(probs).argmax(axis=-1)
    expect_f = np.bincount(first.ravel(), minlength=4) / first.size
    np.testing.assert_allclose(np.asarray(aux_frac), expect_f,
                               atol=1e-6)


def test_dispatch_conservation_and_overflow():
    rng = np.random.default_rng(5)
    probs = jax.nn.softmax(
        jnp.asarray(rng.normal(size=(1, 16, 4)), jnp.float32), axis=-1)
    # ample capacity: every token keeps all k choices; combine sums to 1
    d, c, _ = router_dispatch(probs, 2, 32)
    np.testing.assert_array_equal(
        np.asarray(d.sum(axis=(2, 3))), np.full((1, 16), 2.0))
    np.testing.assert_allclose(np.asarray(c.sum(axis=(2, 3))),
                               np.ones((1, 16)), atol=1e-6)
    # capacity 1: each expert accepts exactly one token per batch row
    d1, _, _ = router_dispatch(probs, 2, 1)
    per_expert = np.asarray(d1.sum(axis=(1, 3)))
    assert per_expert.max() <= 1.0
    assert d1.sum() <= 4  # at most E slots filled


def test_single_expert_degenerates_to_dense_ffn():
    """E=1, k=1: gate prob is softmax over one logit == 1.0, ample
    capacity — MoE output must equal the plain gelu MLP."""
    cfg = dataclasses.replace(
        MOE_CFG, moe_num_experts=1, moe_top_k=1,
        moe_capacity_factor=1.0, moe_aux_loss_weight=0.0,
        moe_z_loss_weight=0.0)
    layer = MoEMLP(cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 16)),
                    jnp.float32)
    variables = layer.init({"params": jax.random.key(0)}, x)
    y, aux = layer.apply(variables, x)
    p = nn.meta.unbox(variables)["params"]
    expect = nn.gelu(x @ p["wi"][0] + p["wi_bias"][0],
                     approximate=True) @ p["wo"][0] + p["wo_bias"][0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                               atol=1e-5)
    assert float(aux) == 0.0


def test_expert_capacity():
    cfg = dataclasses.replace(MOE_CFG, moe_top_k=2,
                              moe_capacity_factor=1.25,
                              moe_num_experts=4)
    assert expert_capacity(cfg, 16) == 10  # ceil(2*16*1.25/4)
    assert expert_capacity(cfg, 1) == 1


def _moe_data(batch=8, seq=16):
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(0, 64, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 64, (batch, seq)), jnp.int32)
    mask = jnp.ones((batch, seq), jnp.float32)
    return ids, labels, mask


def _moe_loss(model, params, ids, labels, mask):
    logits, mods = model.apply({"params": params}, ids,
                               mutable=["losses"])
    return cross_entropy_loss(logits, labels, mask) \
        + sum(jax.tree.leaves(mods["losses"]))


@pytest.fixture(scope="module")
def moe_golden():
    model = GPTForPretraining(MOE_CFG)
    variables = model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32))
    ids, labels, mask = _moe_data()
    loss, grads = jax.value_and_grad(
        lambda p: _moe_loss(model, p, ids, labels, mask))(
            variables["params"])
    return variables, ids, labels, mask, loss, grads


@pytest.mark.parametrize("topo_kw", [
    {"dp_degree": 2, "sharding_degree": 2, "mp_degree": 2,
     "sharding_stage": 3, "ep_degree": 4},
    {"dp_degree": 4, "mp_degree": 2, "ep_degree": 4},
    {"sharding_degree": 4, "dp_degree": 2, "ep_degree": 4},
], ids=["ep4-over-dpxfsdp-zero3-tp2", "ep4xtp2", "ep4-over-fsdp"])
def test_ep_sharded_matches_single_device(moe_golden, topo_kw):
    """Expert-parallel loss/grads == single-device (same routing, same
    numbers) under EP x TP x ZeRO composites on the 8-device mesh."""
    variables, ids, labels, mask, ref_loss, ref_grads = moe_golden
    topo = TopologyConfig(**topo_kw)
    mesh = build_mesh(topo)
    rules = make_sharding_rules(topo)
    model = GPTForPretraining(MOE_CFG)

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

    with mesh, nn.logical_axis_rules(list(rules)):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: _moe_loss(model, p, ids_s, labels_s, mask_s)))(
                params)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3),
        nn.meta.unbox(ref_grads), grads)


def test_expert_weights_land_sharded():
    topo = TopologyConfig(dp_degree=2, sharding_degree=2,
                          mp_degree=2, sharding_stage=1, ep_degree=4)
    mesh = build_mesh(topo)
    rules = make_sharding_rules(topo)
    model = GPTForPretraining(MOE_CFG)
    logical_specs = nn.get_partition_spec(
        jax.eval_shape(model.init, {"params": jax.random.key(0)},
                       jnp.zeros((1, 8), jnp.int32)))
    shardings = nn.logical_to_mesh_sharding(logical_specs, mesh,
                                            list(rules))
    wi = shardings["params"]["gpt"]["decoder"]["moe_mlp"]["wi"]
    # stacked [layers, E, h, m]: expert dim over the dp x fsdp plane,
    # inner FFN dim over mp (EP x TP)
    assert wi.spec == P(None, ("dp", "fsdp"), None, "mp"), wi.spec


def _moe_engine_cfg(**model_overrides):
    from paddlefleetx_tpu.utils.config import AttrDict

    cfg = AttrDict({
        "Global": AttrDict({"seed": 11, "local_batch_size": 8,
                            "micro_batch_size": 8,
                            "global_batch_size": None}),
        "Engine": AttrDict({"max_steps": 3,
                            "mix_precision": AttrDict({})}),
        "Model": AttrDict({
            "module": "GPTModule", "name": "GPT", "vocab_size": 64,
            "hidden_size": 32, "num_layers": 2,
            "num_attention_heads": 4, "ffn_hidden_size": 64,
            "max_position_embeddings": 32,
            "hidden_dropout_prob": 0.0,
            "attention_probs_dropout_prob": 0.0,
            "moe_num_experts": 4, "moe_top_k": 2,
        }),
        "Distributed": AttrDict({"dp_degree": 4, "mp_degree": 2,
                                 "ep_degree": 4,
                                 "sharding": AttrDict({})}),
        "Optimizer": AttrDict({
            "name": "FusedAdamW", "weight_decay": 0.01,
            "lr": AttrDict({"name": "CosineAnnealingWithWarmupDecay",
                            "decay_steps": 20, "warmup_rate": 0.1,
                            "max_lr": 5e-3, "min_lr": 1e-4}),
            "grad_clip": AttrDict({"clip_norm": 1.0}),
        }),
    })
    cfg["Model"].update(model_overrides)
    return cfg


def _moe_engine(**model_overrides):
    from paddlefleetx_tpu.core import Engine
    from paddlefleetx_tpu.models import build_module
    from paddlefleetx_tpu.utils.config import process_configs

    cfg = _moe_engine_cfg(**model_overrides)
    process_configs(cfg, nranks=8)
    module = build_module(cfg)
    return Engine(cfg, module, mode="train")


def test_moe_engine_train_step_decreases_loss():
    engine = _moe_engine()

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int64)
    batch = (tokens, np.tile(np.arange(16), (8, 1)),
             np.roll(tokens, -1, 1), np.ones((8, 16), np.float32))
    losses = []
    state = engine.state
    with engine.mesh, nn.logical_axis_rules(engine.rules):
        for _ in range(3):
            state, metrics = engine._train_step(
                state, engine._put_batch(batch))
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_moe_generation_decodes():
    """Routing at s=1 through the KV-cache decode path."""
    from paddlefleetx_tpu.models.gpt.generation import (
        GenerationConfig, generate,
    )
    cfg = dataclasses.replace(MOE_CFG, max_position_embeddings=32)
    model = GPTForPretraining(cfg)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 62, (2, 8)), jnp.int32)
    params = model.init({"params": jax.random.key(0)},
                        prompt)["params"]
    out = generate(model, params, prompt, None, jax.random.key(1),
                   GenerationConfig(max_dec_len=4,
                                    decode_strategy="greedy_search",
                                    eos_token_id=63, pad_token_id=63))
    out = np.asarray(out)
    assert out.shape == (2, 4)
    assert ((out >= 0) & (out < 64)).all()


def test_moe_pp_gpipe_rejected():
    """MoE + pp trains through the explicit 1F1B/zb schedules (the
    stage scan threads the router aux loss, docs/pipeline.md); only
    GPipe is refused — autodiff through the forward-only schedule
    would silently drop the aux loss."""
    from paddlefleetx_tpu.utils.config import AttrDict
    from paddlefleetx_tpu.models.language_utils import (
        process_model_configs,
    )

    def _cfg(**model_kw):
        return AttrDict({
            "Global": AttrDict({"local_batch_size": 8,
                                "micro_batch_size": 4}),
            "Model": AttrDict({"hidden_size": 32, "num_layers": 4,
                               "moe_num_experts": 4, **model_kw}),
            "Distributed": AttrDict({"pp_degree": 2, "mp_degree": 1,
                                     "dp_degree": 1}),
        })

    with pytest.raises(ValueError, match="MoE.*pipeline"):
        process_model_configs(_cfg(pipeline_schedule="GPipe"))
    # the default (1F1B) and the zb schedule family compose with MoE
    process_model_configs(_cfg())
    for sched in ("zb", "zb_h2", "zb_auto"):
        process_model_configs(_cfg(pipeline_schedule=sched))


def test_ep_must_divide_experts():
    from paddlefleetx_tpu.utils.config import AttrDict
    from paddlefleetx_tpu.models.language_utils import (
        process_model_configs,
    )
    cfg = AttrDict({
        "Global": AttrDict({"local_batch_size": 8,
                            "micro_batch_size": 8}),
        "Model": AttrDict({"hidden_size": 32, "num_layers": 4,
                           "moe_num_experts": 6}),
        "Distributed": AttrDict({"pp_degree": 1, "mp_degree": 1,
                                 "dp_degree": 4, "ep_degree": 4}),
    })
    with pytest.raises(ValueError, match="divisible by"):
        process_model_configs(cfg)


def test_bad_ep_degree_rejected():
    topo = TopologyConfig(dp_degree=4, mp_degree=2, ep_degree=3)
    with pytest.raises(ValueError, match="ep_degree"):
        make_sharding_rules(topo)


def test_moe_config_validation():
    with pytest.raises(ValueError, match="moe_top_k"):
        GPTConfig(moe_num_experts=2, moe_top_k=3)
    with pytest.raises(ValueError, match="capacity_factor"):
        GPTConfig(moe_num_experts=2, moe_capacity_factor=0.0)
    with pytest.raises(ValueError, match="moe_dispatch"):
        GPTConfig(moe_num_experts=2, moe_dispatch="argsort")


# -- dispatch-mode parity matrix (ISSUE 4 tentpole) --------------------
#
# sort/sort_pallas must reproduce the einsum reference bit-for-policy:
# identical outputs, identical dropped-token sets, fp32-tolerance
# gradients — under ep in {1, 2, 4} and top_k in {1, 2} (docs/moe.md).

EP_TOPOS = {
    1: dict(dp_degree=8),
    2: dict(dp_degree=2, mp_degree=4, ep_degree=2),
    4: dict(dp_degree=4, mp_degree=2, ep_degree=4),
}


def _parity_cfg(top_k, mode):
    # capacity_factor < 1 forces real capacity drops into the matrix
    return dataclasses.replace(
        MOE_CFG, moe_top_k=top_k, moe_capacity_factor=0.75,
        moe_dispatch=mode)


@pytest.fixture(scope="module")
def dispatch_golden():
    """einsum-mode layer outputs/loss/grads per top_k, no mesh."""
    out = {}
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(4, 16, 16)), jnp.float32)
    for top_k in (1, 2):
        layer = MoEMLP(_parity_cfg(top_k, "einsum"))
        params = nn.meta.unbox(
            layer.init({"params": jax.random.key(2)}, x))["params"]

        def loss(p, layer=layer):
            y, aux = layer.apply({"params": p}, x)
            return (y ** 2).sum() + aux
        l, g = jax.value_and_grad(loss)(params)
        y, _ = layer.apply({"params": params}, x)
        out[top_k] = (x, params, l, g, y)
    return out


@pytest.mark.parametrize("ep", [1, 2, 4])
@pytest.mark.parametrize("top_k", [1, 2])
@pytest.mark.parametrize("mode", ["sort", "sort_pallas"])
def test_dispatch_modes_match_einsum(dispatch_golden, monkeypatch,
                                     mode, top_k, ep):
    monkeypatch.setenv("PFX_PALLAS_INTERPRET", "1")
    x, params, ref_l, ref_g, ref_y = dispatch_golden[top_k]
    topo = TopologyConfig(**EP_TOPOS[ep])
    mesh = build_mesh(topo)
    # as the Engine does: with the mesh active, sort_pallas runs its
    # grouped GEMMs per device under shard_map (ops/ring_attention.py
    # shard_kernel) — what real chips require
    set_mesh(mesh)
    rules = make_sharding_rules(topo)
    layer = MoEMLP(_parity_cfg(top_k, mode))

    def loss(p):
        y, aux = layer.apply({"params": p}, x)
        return (y ** 2).sum() + aux
    with mesh, nn.logical_axis_rules(list(rules)):
        l, g = jax.jit(jax.value_and_grad(loss))(params)
        y, _ = jax.jit(layer.apply)({"params": params}, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(float(l), float(ref_l), rtol=2e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3),
        ref_g, g)


def test_sort_and_dense_drop_identical_tokens():
    """The acceptance bar's sharpest edge: not just close outputs but
    the very same (token, choice) set surviving capacity — compared
    slot-for-slot between the one-hot dispatch tensor and the sort
    plan's destination map."""
    rng = np.random.default_rng(23)
    b, s, E, k, C = 2, 32, 4, 2, 3  # C far under s*k/E: heavy drops
    probs = jax.nn.softmax(
        jnp.asarray(rng.normal(size=(b, s, E)) * 3, jnp.float32), -1)
    d, _, _ = router_dispatch(probs, k, C)
    gate, dest, src, counts, _ = sort_routing(probs, k, C)

    idx = np.asarray(jax.lax.top_k(probs, k)[1])        # [b, s, k]
    kept_choice = np.asarray(dest).reshape(b, s, k) < E * C
    sort_kept = np.zeros((b, s, E))
    for bi in range(b):
        for si in range(s):
            for ki in range(k):
                if kept_choice[bi, si, ki]:
                    sort_kept[bi, si, idx[bi, si, ki]] += 1.0
    np.testing.assert_array_equal(np.asarray(d.sum(axis=3)), sort_kept)
    # per-expert occupancy used as the Pallas group boundaries must
    # equal the dense tensor's slot usage
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(d.sum(axis=(1, 3))))
    # every occupied slot maps back to a real token, every empty slot
    # to the zero pad row
    occupied = np.asarray(src) < s
    assert occupied.sum() == np.asarray(counts).sum()


@pytest.mark.parametrize("mode", ["einsum", "sort"])
def test_all_tokens_dropped_is_pure_residual(monkeypatch, mode):
    """Every token overflowing (capacity forced to 0) must yield an
    exactly-zero MoE output — at the decoder layer only the residual
    stream survives — identically in both dispatch lowerings."""
    import paddlefleetx_tpu.models.gpt.moe as moe_mod
    monkeypatch.setattr(moe_mod, "expert_capacity", lambda cfg, s: 0)
    layer = MoEMLP(dataclasses.replace(MOE_CFG, moe_dispatch=mode))
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 8, 16)),
                    jnp.float32)
    variables = layer.init({"params": jax.random.key(0)}, x)
    y, aux = layer.apply(variables, x)
    np.testing.assert_array_equal(np.asarray(y), 0.0)
    assert np.isfinite(float(aux))  # router losses still train


def test_top_k_equals_num_experts_all_modes(monkeypatch):
    """k == E with ample capacity: every token reaches every expert
    (soft-MoE limit), nothing drops, and all three lowerings agree."""
    monkeypatch.setenv("PFX_PALLAS_INTERPRET", "1")
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 8, 16)),
                    jnp.float32)
    cfg = dataclasses.replace(MOE_CFG, moe_top_k=4,
                              moe_capacity_factor=1.0)
    # C = ceil(4*8*1.0/4) = 8 = s: every expert can host every token
    d, c, _ = router_dispatch(
        jax.nn.softmax(jnp.asarray(
            np.random.default_rng(9).normal(size=(2, 8, 4)),
            jnp.float32), -1), 4, expert_capacity(cfg, 8))
    np.testing.assert_array_equal(np.asarray(d.sum(axis=(2, 3))), 4.0)
    np.testing.assert_allclose(np.asarray(c.sum(axis=(2, 3))), 1.0,
                               atol=1e-6)
    ys = {}
    params = None
    for mode in ("einsum", "sort", "sort_pallas"):
        layer = MoEMLP(dataclasses.replace(cfg, moe_dispatch=mode))
        if params is None:
            params = layer.init({"params": jax.random.key(1)}, x)
        ys[mode], _ = layer.apply(params, x)
    np.testing.assert_allclose(np.asarray(ys["sort"]),
                               np.asarray(ys["einsum"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ys["sort_pallas"]),
                               np.asarray(ys["einsum"]), atol=1e-5)


def test_expert_capacity_rounding():
    cfg = dataclasses.replace(MOE_CFG, moe_top_k=1,
                              moe_capacity_factor=1.0,
                              moe_num_experts=3)
    assert expert_capacity(cfg, 16) == 6   # ceil(16/3), rounds UP
    assert expert_capacity(cfg, 15) == 5   # exact divisor: no pad
    cfg = dataclasses.replace(cfg, moe_capacity_factor=0.1)
    assert expert_capacity(cfg, 2) == 1    # floor-clamped to 1 slot


# -- moe/* dispatch counters (trace-time, docs/moe.md) -----------------


@pytest.fixture
def _registry():
    from paddlefleetx_tpu.observability import metrics as obs_metrics
    reg = obs_metrics.get_registry()
    prior = reg.enabled
    reg.reset()
    obs_metrics.set_enabled(True)
    yield reg
    obs_metrics.set_enabled(prior)
    reg.reset()


def test_moe_dispatch_counters(_registry, monkeypatch):
    monkeypatch.setenv("PFX_PALLAS_INTERPRET", "1")
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 8, 16)),
                    jnp.float32)
    for mode in ("einsum", "sort", "sort_pallas"):
        layer = MoEMLP(dataclasses.replace(MOE_CFG,
                                           moe_dispatch=mode))
        variables = layer.init({"params": jax.random.key(0)}, x)
        layer.apply(variables, x)
        assert _registry.counter("moe/" + mode) >= 1, mode
    assert _registry.counter("moe/fallback/pallas_rejected") == 0


def test_moe_pallas_rejection_counts_and_falls_back(
        _registry, monkeypatch):
    """A kernel-rejected shape must land on the sort-mode XLA expert
    einsums with identical numbers, counting the rejection."""
    import paddlefleetx_tpu.ops.pallas.grouped_matmul as gm
    monkeypatch.setenv("PFX_PALLAS_INTERPRET", "1")

    def refuse(*a, **k):
        raise NotImplementedError("forced rejection")
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 8, 16)),
                    jnp.float32)
    layer = MoEMLP(dataclasses.replace(MOE_CFG,
                                       moe_dispatch="sort_pallas"))
    variables = layer.init({"params": jax.random.key(0)}, x)
    y_ref, _ = MoEMLP(dataclasses.replace(
        MOE_CFG, moe_dispatch="sort")).apply(variables, x)
    monkeypatch.setattr(gm, "grouped_matmul", refuse)
    _registry.reset()
    y, _ = layer.apply(variables, x)
    assert _registry.counter("moe/fallback/pallas_rejected") >= 1
    assert _registry.counter("moe/sort") >= 1
    assert _registry.counter("moe/sort_pallas") == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-6)


def test_moe_engine_logs_dispatch_lowering(_registry):
    """Engine init must announce the configured MoE lowering (counted
    moe/config/<mode>) exactly as mp_linear/config/* does — here with
    moe_dispatch plumbed through the Model YAML section. The project
    logger has propagate=False, so assert on the call itself."""
    from unittest import mock

    from paddlefleetx_tpu.utils.log import logger
    with mock.patch.object(logger, "info", wraps=logger.info) as info:
        _moe_engine(moe_dispatch="sort")
    assert _registry.counter("moe/config/sort") == 1
    moe_lines = [c for c in info.call_args_list
                 if "MoE dispatch" in c.args[0]]
    assert moe_lines, info.call_args_list
    assert "counting-sort" in (moe_lines[0].args[0]
                               % moe_lines[0].args[1:])
