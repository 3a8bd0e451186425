"""DeepSeek-V3-style decoder (``models/deepseek_v3``) against its plain
float32 reference (``models/deepseek_v3/reference.py``, which imports
nothing of the model) at a small size on the CPU: logits, loss, every
gradient leaf, three AdamW steps through ``Engine.fit``; the share test
(the shares' routed parts plus the shared expert once = the uncut
layer); dropless exactness with every token on one pair of experts and
with an empty held set; the benchmark's copy of the reference against
the in-tree one; the published YAML through ``cli.build_trainer``."""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.models.deepseek_v3 import (
    DeepSeekV3Config, DeepSeekV3ForPretraining,
)
from paddlefleetx_tpu.models.deepseek_v3 import reference as ref
from paddlefleetx_tpu.models.deepseek_v3.moe import DroplessMoE



@pytest.fixture
def interpret(monkeypatch):
    """The Pallas kernels in interpret mode (the layer-level tests; the
    model-level ones take the counted XLA stand-in, ``ragged_dot``)."""
    monkeypatch.setenv("PFX_PALLAS_INTERPRET", "1")


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YAML = os.path.join(ROOT, "configs", "nlp", "deepseek_v3",
                    "pretrain_kanana2_30b_a3b.yaml")

SMALL = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=3,
             num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
             n_shared_experts=1, num_experts_per_tok=2,
             max_position_embeddings=64)


def _ref_cfg(cfg):
    return dict(SMALL, rms_norm_eps=cfg.rms_norm_eps,
                rope_theta=cfg.rope_theta,
                rope_interleave=cfg.rope_interleave,
                routed_scaling_factor=cfg.routed_scaling_factor,
                experts_held=cfg.held_experts, vocab_lo=cfg.held_vocab[0])


def _seeded(model, tokens, seed=3):
    """Seeded weights: N(0, 0.02), norm scales 1, and a selection bias
    with values (a zero bias would never decide a selection)."""
    shapes = nn.meta.unbox(jax.eval_shape(
        model.init, jax.random.key(0), tokens)["params"])

    def leaf(path, a):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            return jnp.ones(a.shape, a.dtype)
        key = jax.random.fold_in(jax.random.key(seed),
                                 hash(jax.tree_util.keystr(path)) % 2 ** 31)
        std = 0.2 if name == "e_score_correction_bias" else 0.02
        return std * jax.random.normal(key, a.shape, a.dtype)
    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _batch(lo=0, hi=256, rows=3, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(lo, hi, (rows, seq)), jnp.int32),
            jnp.asarray(rng.integers(lo, hi, (rows, seq)), jnp.int32),
            jnp.asarray(rng.integers(0, 2, (rows, seq)), jnp.float32))


def _worst(a, b):
    rel = jax.tree.map(lambda x, y: float(
        jnp.max(jnp.abs(x - y)) / (jnp.max(jnp.abs(y)) + 1e-9)), a, b)
    return max(jax.tree.leaves(rel))


@pytest.mark.parametrize("scan,held,vocab", [
    (True, None, None), (False, (2, 6), (64, 192))])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(
        scan, held, vocab):
    cfg = DeepSeekV3Config(scan_layers=scan, experts_held=held,
                           vocab_held=vocab, **SMALL)
    model = DeepSeekV3ForPretraining(cfg)
    tokens, labels, mask = _batch(*cfg.held_vocab)
    params = _seeded(model, tokens)
    rc = _ref_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.apply)({"params": params}, tokens)
    want = jax.jit(lambda p: ref.logits(p, tokens, rc))(params)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6

    def loss(p):
        with jax.default_matmul_precision("highest"):
            lg = model.apply({"params": p}, tokens)
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(
            lg, (labels - rc["vocab_lo"])[..., None], -1)[..., 0]
        return jnp.sum((logz - picked) * mask) / jnp.sum(mask)
    l1, g1 = jax.jit(jax.value_and_grad(loss))(params)
    l2, g2 = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, labels, mask, rc)))(params)
    assert abs(float(l1) - float(l2)) < 1e-5
    assert _worst(g1, g2) < 1e-4
    # the selection bias selects and never weighs: no gradient
    flat = jax.tree_util.tree_flatten_with_path(g1)[0]
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for path, g in flat
               if "e_score_correction_bias" in jax.tree_util.keystr(path))


def _full_layer_params(seed=2):
    cfg = DeepSeekV3Config(**SMALL)
    u = jax.random.normal(jax.random.key(1), (2, 24, 64), jnp.float32)
    return cfg, _seeded(DroplessMoE(cfg), u, seed), u


def _share_of(params, lo, hi):
    return dict(params, experts_gate_up=params["experts_gate_up"][lo:hi],
                experts_down=params["experts_down"][lo:hi])


def test_the_shares_routed_parts_plus_the_shared_expert_once_are_the_layer(
        interpret):
    """4 shares of 2 experts: selection and weights over all 8 in each;
    what every share computes alike (the shared expert) counted once."""
    cfg, params, u = _full_layer_params()
    with jax.default_matmul_precision("highest"):
        whole, stats = DroplessMoE(cfg).apply({"params": params}, u)
        sh = params["shared_experts"]
        shared = ref.gated_mlp(u, sh["gate_up_proj"]["kernel"],
                               sh["down_proj"]["kernel"])
        routed, held_picks = 0.0, 0.0
        for r in range(4):
            lo, hi = 2 * r, 2 * r + 2
            part, st = DroplessMoE(
                DeepSeekV3Config(experts_held=(lo, hi), **SMALL)).apply(
                {"params": _share_of(params, lo, hi)}, u)
            routed = routed + (part - shared)
            held_picks += float(st[0])
            want = ref.expert_ffn(u, _share_of(params, lo, hi),
                                  _ref_cfg(DeepSeekV3Config(
                                      experts_held=(lo, hi), **SMALL)))
            np.testing.assert_allclose(part, want, atol=2e-6)
    np.testing.assert_allclose(routed + shared, whole, atol=3e-6)
    assert held_picks == float(stats[2]) == u.shape[0] * u.shape[1] * 2


@pytest.mark.parametrize("held,picked", [
    ((2, 6), (3, 5)),      # every token on the same two held experts
    ((2, 6), (3, 7)),      # one of the two is another chip's
    ((6, 8), (3, 5)),      # an empty held set: the shared expert alone
    (None, (0, 7))])
def test_dropless_is_exact_for_any_routing(held, picked, interpret):
    cfg, params, u = _full_layer_params(seed=4)
    lo, hi = held or (0, 8)
    bias = jnp.zeros((8,)).at[jnp.asarray(picked)].set(10.0)
    p = dict(_share_of(params, lo, hi), e_score_correction_bias=bias)
    cfg = DeepSeekV3Config(experts_held=held, **SMALL)

    def f(p, u):
        with jax.default_matmul_precision("highest"):
            return DroplessMoE(cfg).apply({"params": p}, u)

    def g(p, u):
        with jax.default_matmul_precision("highest"):
            return ref.expert_ffn(u, p, _ref_cfg(cfg))
    ct = jax.random.normal(jax.random.key(9), u.shape)

    @jax.jit
    def both(p, u):
        (out, stats), vjp = jax.vjp(f, p, u)
        want, vjp_ref = jax.vjp(g, p, u)
        return (out, stats, vjp((ct, jnp.zeros_like(stats))), want,
                vjp_ref(ct))
    out, stats, got, want, want_grads = both(p, u)
    np.testing.assert_allclose(out, want, atol=2e-6)
    n_held = sum(lo <= e < hi for e in picked) * u.shape[0] * u.shape[1]
    assert float(stats[0]) == n_held
    assert _worst(got, want_grads) < 1e-4


def test_three_adamw_steps_through_engine_fit_match_the_reference(tmp_path):
    """``cli.build_trainer`` on the published YAML (cut to the small
    size by overrides) -> ``Engine.fit``, float32, against the
    benchmark's reference stepping its own AdamW on the same batches;
    the step_window records carry the routing statistics."""
    import sys
    sys.path.insert(0, ROOT)
    from chipbench import traffic_gen, weights
    from chipbench.reference import deepseek_v3_decoder as cref
    from paddlefleetx_tpu import cli
    from paddlefleetx_tpu.parallel.mesh import set_mesh
    corpus = str(tmp_path / "corpus")
    traffic_gen.make_corpus(corpus, 128, 40, 4 * 32 + 3, 11, 1.1)
    over = [f"Model.{k}={v}" for k, v in SMALL.items()] + [
        "Model.experts_held=[2,6]", "Model.vocab_held=[0,128]",
        "Model.loss_chunks=2", "Model.use_recompute=True",
        "Engine.mix_precision.use_pure_fp16=False",
        "Global.local_batch_size=2", "Global.micro_batch_size=2",
        "Data.Train.dataset.max_seq_len=32",
        "Data.Eval.dataset.max_seq_len=32", "Engine.max_steps=3",
        "Engine.logging_freq=1", "Engine.eval_freq=1000",
        "Engine.save_load.save_steps=1000",
        f"Engine.save_load.output_dir={tmp_path}/out",
        f"Data.Train.dataset.input_dir={corpus}",
        f"Data.Eval.dataset.input_dir={corpus}",
        "Optimizer.lr.decay_steps=1000", "Optimizer.lr.warmup_rate=0.002",
        "Optimizer.lr.max_lr=3.0e-4", "Telemetry.enable=True"]
    cfg, engine, loader, _ = cli.build_trainer(
        ["-c", YAML] + [x for o in over for x in ("-o", o)],
        devices=jax.devices()[:1])
    assert type(engine.module).__name__ == "DeepSeekV3Module"
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        engine.state["params"])
    start = weights.seeded_params(abstract, 17)
    engine.state = dict(engine.state, params=weights.seeded_params(
        abstract, 17, shardings=engine.state_shardings["params"]))
    kept = []

    class Keep:
        batch_sampler = loader.batch_sampler

        def __iter__(self):
            for batch in loader:
                kept.append([np.array(x) for x in batch])
                yield batch
    set_mesh(engine.mesh)
    with jax.default_matmul_precision("highest"):
        engine.fit(epoch=1, train_data_loader=Keep())
    events = [json.loads(ln) for ln in open(tmp_path / "out" / "events.jsonl")]
    windows = [e for e in events if e.get("event") == "step_window"]
    assert len(windows) == 3
    for w in windows:
        assert w["moe_picks"] == 2 * 32 * 2 * 2      # rows x seq x k x layers
        assert 0 <= w["moe_held_picks"] <= w["moe_picks"]
        assert w["moe_load_max_over_mean"] >= 1.0 or w["moe_held_picks"] == 0

    o = cfg.Optimizer
    opt = {"beta1": o.beta1, "beta2": o.beta2, "epsilon": o.epsilon,
           "weight_decay": o.weight_decay, "clip_norm": 1.0,
           "max_lr": 3.0e-4, "min_lr": float(o.lr.min_lr),
           "warmup_rate": 0.002, "decay_steps": 1000.0}
    model = dict(SMALL, rms_norm_eps=1e-6, rope_theta=1e6,
                 rope_interleave=True, routed_scaling_factor=2.448)
    geo = cref.geometry(model, (2, 6), 0)
    # the reference's update consumes its arguments: step a copy
    params = jax.tree.map(jnp.copy, start)
    state = cref.adamw_init(params)
    for k, (tokens, _pos, labels, mask) in enumerate(kept[:3]):
        loss, grads = cref.loss_and_grad(
            params, jnp.asarray(tokens), jnp.asarray(labels),
            jnp.asarray(mask), geo, rows_per_block=1)
        assert abs(float(loss) - windows[k]["loss"]) < 2e-5
        grads, _ = cref.clip_by_global_norm(grads, opt["clip_norm"])
        params, state = cref.adamw_update(params, grads, state, opt)
    moved = cref.leaf_diff_norms(engine.state["params"], start)
    want = cref.leaf_diff_norms(params, start)
    gaps = jax.tree.map(lambda a, b: abs(float(a) - float(b))
                        / max(float(b), 1e-12), moved, want)
    frozen = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, b), a in zip(frozen, jax.tree.leaves(gaps)):
        if "e_score_correction_bias" in jax.tree_util.keystr(path):
            assert float(b) == 0.0        # the optimizer never moves b
        else:
            assert a < 5e-3, (jax.tree_util.keystr(path), a)


def test_the_benchmarks_reference_and_the_in_tree_one_give_the_same_numbers():
    import sys
    sys.path.insert(0, ROOT)
    from chipbench.reference import deepseek_v3_decoder as cref
    src = open(cref.__file__).read() + open(ref.__file__).read()
    assert "paddlefleetx_tpu" not in src.replace(
        "``paddlefleetx_tpu", "").replace("paddlefleetx_tpu/", "")
    cfg = DeepSeekV3Config(scan_layers=False, experts_held=(2, 6),
                           vocab_held=(64, 192), **SMALL)
    model = DeepSeekV3ForPretraining(cfg)
    tokens, labels, mask = _batch(64, 192)
    params = _seeded(model, tokens)
    rc = _ref_cfg(cfg)
    geo = cref.geometry(rc, (2, 6), 64)
    assert float(jnp.max(jnp.abs(
        jax.jit(lambda p: ref.logits(p, tokens, rc))(params)
        - cref.logits(params, tokens, geo)))) < 1e-6
    l1, g1 = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, labels, mask, rc)))(params)
    l2, g2 = cref.loss_and_grad(params, tokens, labels, mask, geo, 2)
    assert abs(float(l1) - float(l2)) < 1e-6
    assert _worst(g1, g2) < 1e-4
    low, _ = cref.loss_and_grad(params, tokens, labels, mask, geo, 2, "fp8")
    assert abs(float(low) - float(l2)) > 10 * abs(float(l1) - float(l2))


def test_the_yaml_holds_the_published_sizes():
    from paddlefleetx_tpu.utils.config import get_config
    cfg = DeepSeekV3Config.from_config(get_config(YAML, nranks=1))
    catalog = dict(
        hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768,
        num_hidden_layers=48, num_attention_heads=32, kv_lora_rank=512,
        q_lora_rank=None, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, n_routed_experts=128, n_shared_experts=2,
        num_experts_per_tok=6, first_k_dense_replace=1,
        routed_scaling_factor=2.448, rope_theta=1000000.0,
        rope_interleave=True, rms_norm_eps=1e-6, vocab_size=128256,
        scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
        topk_group=1, norm_topk_prob=True)
    assert {k: getattr(cfg, k) for k in catalog} == catalog
    assert cfg.held_experts == (0, 128) and cfg.held_vocab == (0, 128256)
    assert cfg.qk_head_dim == 192
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        bench = json.load(f)
    assert {k: bench["published"][k] for k in catalog
            if k in bench["published"]} == \
        {k: v for k, v in catalog.items() if k in bench["published"]}


@pytest.mark.parametrize("bad", [
    dict(q_lora_rank=1536), dict(scoring_func="softmax"),
    dict(n_group=8, topk_group=4), dict(experts_held=(4, 12)),
    dict(vocab_held=(0, 300))])
def test_what_is_not_implemented_is_refused_by_name(bad):
    with pytest.raises(ValueError):
        DeepSeekV3Config(**dict(SMALL, **bad))


def test_step_statistics_are_optional_in_the_modules_contract():
    """A GPT module defines neither hook, so the Engine's step returns
    what it returned; the DeepSeek module names its three statistics."""
    from paddlefleetx_tpu.models.deepseek_v3.modules import (
        STEP_STATS, DeepSeekV3Module,
    )
    from paddlefleetx_tpu.models.gpt.modules import GPTModule
    assert not hasattr(GPTModule, "loss_and_stats")
    assert not hasattr(GPTModule, "reduce_step_stats")
    assert STEP_STATS == ("moe_held_picks", "moe_load_max_over_mean",
                          "moe_picks")
    stacked = {k: jnp.asarray([1.0, 3.0]) for k in STEP_STATS}
    assert {k: float(v) for k, v in
            DeepSeekV3Module.reduce_step_stats(stacked).items()} == {
        "moe_held_picks": 4.0, "moe_load_max_over_mean": 3.0,
        "moe_picks": 4.0}
