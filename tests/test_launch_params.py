"""What a launch passes (``models/gpt/generation.py``): a server keeps
its parameters as a few stacked arrays and its programs slice the
per-layer tree out of them. Packing must be invisible (the same tree
bit for bit, the same logits), must follow what the leaves show
(shape, dtype, sharding, bytes) and nothing else, and a launch must
pass few leaves."""

import dataclasses
import os

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlefleetx_tpu.core.adapters import extract_adapter
from paddlefleetx_tpu.core.serving import GenerationServer
from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddlefleetx_tpu.models.gpt import generation as g
from paddlefleetx_tpu.models.granite_hybrid import (
    GraniteHybridConfig, GraniteHybridForCausalLM,
)
from paddlefleetx_tpu.models.smallthinker import (
    SmallThinkerConfig, SmallThinkerForCausalLM,
)
from paddlefleetx_tpu.observability import metrics

EOS = PAD = 95
GPT = GPTConfig(vocab_size=96, hidden_size=32, num_layers=3,
                num_attention_heads=4, max_position_embeddings=256,
                hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                scan_layers=False)
FAMILIES = {
    "gpt": (GPTForPretraining, GPT),
    "gpt-scan": (GPTForPretraining,
                 dataclasses.replace(GPT, scan_layers=True)),
    # grouped-query heads, window rings, routed experts
    "smallthinker": (SmallThinkerForCausalLM, SmallThinkerConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=14, num_key_value_heads=2, head_dim=16,
        moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3, sliding_window_size=160,
        max_position_embeddings=2048)),
    # state leaves: A_log / dt_bias rows, convolution tails
    "granite": (GraniteHybridForCausalLM, GraniteHybridConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        layer_types=("mamba", "attention", "mamba", "mamba"),
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        attention_multiplier=1 / 16, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=128, mamba_chunk_size=64,
        shared_intermediate_size=128, max_position_embeddings=2048)),
}


def _init(family):
    cls, cfg = FAMILIES[family]
    model = cls(cfg)
    return model, nn.meta.unbox(model.init(
        {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"])


def _greedy(max_dec=6):
    return g.GenerationConfig(max_dec_len=max_dec,
                              decode_strategy="greedy_search",
                              eos_token_id=EOS, pad_token_id=PAD)


def _same(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_pack_then_unpack_is_the_same_tree(family):
    """Out of the stacks (``tree()``, and ``launch_tree`` inside a
    traced function) comes the per-layer tree the parent handed its
    programs, bit for bit; and fewer arrays than leaves went in."""
    model, params = _init(family)
    twin, want = g._unrolled_twin(model, params)
    packed_model, packed = g.pack_launch_params(model, params)
    assert packed_model.config == twin.config
    _same(packed.tree(), want)
    _same(jax.jit(g.launch_tree)(packed), want)
    assert len(packed.arrays) < len(jax.tree.leaves(want))
    # a tree that is not packed passes through as it is
    assert g.launch_tree(want) is want


def test_a_scan_stacked_model_keeps_the_stacks_it_came_with():
    """No round trip through per-layer leaves: the scan's own
    ``[layers, ...]`` arrays are the launch's arrays."""
    model, params = _init("gpt-scan")
    _, packed = g.pack_launch_params(model, params)
    came = {id(a) for a in jax.tree.leaves(params)}
    kept = [a for a in packed.arrays if id(a) in came]
    stacks = [a for a in jax.tree.leaves(params["gpt"]["decoder"])]
    assert len(kept) >= len(stacks)


def test_a_stack_that_arrived_is_stacked_again_in_its_own_order():
    """Twelve layers flatten as decoder_0, _1, _10, _11, _2, ...: an
    assignment must put row i of a stack that arrived back in row i."""
    cls, cfg = FAMILIES["gpt-scan"]
    model = cls(dataclasses.replace(cfg, num_layers=12))
    params = nn.meta.unbox(model.init(
        {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"])
    _, packed = g.pack_launch_params(model, params)
    want = jax.tree.map(lambda x: x * 3, packed.tree())
    _same(packed.assign(want).tree(), want)


def test_what_is_never_stacked(monkeypatch):
    """Mixed dtypes never share a stack, a leaf on more than one
    device stays its own, and so does one over the cap or alone in
    its group; what is not a device array passes through."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("x",))
    spread = NamedSharding(mesh, P("x"))
    whole = NamedSharding(mesh, P())
    tree = {
        "f32": [jnp.ones((4,), jnp.float32), jnp.zeros((4,), jnp.float32)],
        "bf16": [jnp.ones((4,), jnp.bfloat16),
                 jnp.zeros((4,), jnp.bfloat16)],
        "int8": [jnp.ones((4,), jnp.int8), jnp.zeros((4,), jnp.int8)],
        "alone": jnp.ones((5,), jnp.float32),
        "spread": [jax.device_put(jnp.ones((8,)), spread),
                   jax.device_put(jnp.zeros((8,)), spread)],
        "whole": [jax.device_put(jnp.ones((6,)), whole),
                  jax.device_put(jnp.zeros((6,)), whole)],
        "big": [jnp.ones((64,), jnp.float32), jnp.zeros((64,), jnp.float32)],
        "host": np.ones((4,), np.float32),
    }
    monkeypatch.setattr(g, "STACK_LEAF_BYTES", 128)
    _, packed = g.pack_launch_params(None, tree)
    stacks = [a for a, rows in zip(packed.arrays, packed.plan.rows) if rows]
    assert sorted((str(a.dtype), a.shape) for a in stacks) == [
        ("bfloat16", (2, 4)), ("float32", (2, 4)), ("int8", (2, 4))]
    # 3 stacks + alone + 2 spread + 2 whole + 2 big + host
    assert len(packed.arrays) == 11
    leaves = jax.tree.leaves(tree)
    for name in ("spread", "whole", "big"):
        for leaf in tree[name]:
            assert any(a is leaf for a in packed.arrays), name
    assert any(a is tree["host"] for a in packed.arrays)
    _same(packed.tree(), tree)
    assert len(jax.tree.leaves(packed.tree())) == len(leaves)


def _counter(name):
    return metrics.get_registry().snapshot()["counters"].get(name, 0)


def test_a_paged_gpt_launch_passes_at_most_80_leaves():
    """24 layers deep, 292 parameter leaves and 48 pool leaves as the
    345M cell has them: a decode launch and a chunk launch each pass
    at most 80 array leaves, read as the benchmark's ``counters`` line
    gives them (``serving/launch_leaves/*`` over the launches)."""
    cfg = dataclasses.replace(GPT, num_layers=24)
    model = GPTForPretraining(cfg)
    params = nn.meta.unbox(model.init(
        {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"])
    assert len(jax.tree.leaves(params)) == 292
    prior = metrics.get_registry().enabled
    metrics.set_enabled(True)
    try:
        before = {k: _counter(k) for k in (
            "serving/launch_leaves/decode", "serving/device_ticks",
            "serving/launch_leaves/prefill", "serving/prefill_chunks")}
        srv = GenerationServer(model, params, _greedy(4), num_slots=2,
                               page_size=128)
        assert len(jax.tree.leaves(srv._cache)) == 48
        srv.run([[5, 9, 2, 7, 1], [11, 3]])
        after = {k: _counter(k) for k in before}
    finally:
        metrics.set_enabled(prior)
    d = {k: after[k] - before[k] for k in before}
    assert d["serving/device_ticks"] >= 4 and d["serving/prefill_chunks"] == 2
    per_tick = d["serving/launch_leaves/decode"] / d["serving/device_ticks"]
    per_chunk = d["serving/launch_leaves/prefill"] / \
        d["serving/prefill_chunks"]
    assert per_tick <= 80 and per_chunk <= 80
    # and the count is what the launch's arguments hold
    args = (srv._launch_params, srv._cache, srv._state, srv._rng,
            srv._pt_dev_dec)
    assert len(jax.tree.leaves(args)) == per_tick
    assert len(jax.tree.leaves((srv._launch_params, srv._cache))) + 4 \
        == per_chunk
    assert len(jax.tree.leaves(srv.params)) == 292


def _logits_after(srv, prompt, ticks=3):
    srv.submit(prompt)
    for _ in range(ticks + 2):
        srv.step()
    return np.asarray(srv._state.last_logits)


def test_assigning_params_changes_the_next_ticks_logits_exactly():
    """The drivers' ``srv.params = f(srv.params)``: what the next
    ticks compute is what a server built from ``f``'s tree computes,
    exactly; and only the stacks ``f`` changed were stacked again."""
    model, params = _init("granite")

    def spread(tree):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x * 0.5 + 0.25
            if path[-1].key in ("A_log", "dt_bias") else x, tree)
    srv = GenerationServer(model, params, _greedy(), num_slots=2,
                           page_size=128, pool_pages=12)
    plain = _logits_after(srv, [5, 9, 2, 7, 1])
    fresh = GenerationServer(model, spread(params), _greedy(), num_slots=2,
                             page_size=128, pool_pages=12)
    want = _logits_after(fresh, [5, 9, 2, 7, 1])
    assert not np.array_equal(plain, want)

    srv = GenerationServer(model, params, _greedy(), num_slots=2,
                           page_size=128, pool_pages=12)
    before = srv._launch_params
    srv.params = spread(srv.params)
    after = srv._launch_params
    assert after.plan is before.plan          # no program compiles again
    changed = [i for i, (a, b) in enumerate(zip(before.arrays,
                                                after.arrays)) if a is not b]
    touched = {a for pos, (a, _) in enumerate(before.plan.where)
               if jax.tree_util.tree_flatten_with_path(params)[0][pos][0][
                   -1].key in ("A_log", "dt_bias")}
    assert changed and set(changed) == touched
    np.testing.assert_array_equal(
        _logits_after(srv, [5, 9, 2, 7, 1]), want)
    _same(srv.params, spread(params))
    # a tree none of whose leaves was read from the server is stacked whole
    srv.params = jax.tree.map(lambda x: x + 0, srv.params)
    assert srv._launch_params.plan is before.plan
    np.testing.assert_array_equal(
        _logits_after(srv, [4, 4, 8]), _logits_after(fresh, [4, 4, 8]))


def test_servers_of_one_model_share_a_plan_and_their_programs():
    """Equal layouts get the SAME plan (it is hashed by identity), so
    a second server of the model launches the first one's compiled
    programs and traces nothing anew."""
    model, params = _init("gpt")
    first = GenerationServer(model, params, _greedy(), num_slots=2)
    first.run([[5, 9, 2, 7, 1]])
    traced = g.decode_step._cache_size()
    second = GenerationServer(model, jax.tree.map(lambda x: x * 2, params),
                              _greedy(), num_slots=2)
    assert second._launch_params.plan is first._launch_params.plan
    second.run([[5, 9, 2, 7, 1]])
    assert g.decode_step._cache_size() == traced


def test_a_matrix_over_the_cap_stays_its_own_leaf(monkeypatch):
    """The cap is read from the leaf's bytes: at real widths a layer's
    matrices pass through, at these tiny ones a lowered cap shows
    it; a scan-stacked arrival's matrices are then sliced out once,
    as the parent did."""
    model, params = _init("gpt-scan")
    monkeypatch.setattr(g, "STACK_LEAF_BYTES", 32 * 4)   # one [32] row
    twin, want = g._unrolled_twin(model, params)
    _, packed = g.pack_launch_params(model, params)
    for a, rows in zip(packed.arrays, packed.plan.rows):
        assert (a.ndim == 2 and a.shape[1] <= 32) if rows else True
    _same(packed.tree(), want)
    big = [x for x in jax.tree.leaves(want) if x.nbytes > 32 * 4]
    assert len(packed.arrays) >= len(big)


def test_leaves_that_no_longer_fit_their_stacks_are_packed_afresh():
    """A tree whose leaves changed dtype cannot go into the stacks
    that are there: it is packed as a new server's would be, and
    serves."""
    model, params = _init("gpt")
    srv = GenerationServer(model, params, _greedy(), num_slots=2)
    wide = jax.tree.map(lambda x: x.astype(jnp.bfloat16), srv.params)
    srv.params = wide
    assert {a.dtype for a in srv._launch_params.arrays} == {
        jnp.dtype(jnp.bfloat16)}
    _same(srv.params, wide)
    assert srv.run([[5, 9, 2, 7, 1]])[0].finish_reason in ("eos", "length")


def test_insert_adapter_takes_effect_and_restacks_its_tables_only():
    """A lease that writes a bank row goes through ``srv.params``: the
    tokens change as they did, and of the launch's arrays only the
    adapter tables' stacks are new."""
    cfg = dataclasses.replace(GPT, fuse_attn_qkv=True, lora_rank=4,
                              lora_num_adapters=4,
                              max_position_embeddings=128)
    model = GPTForPretraining(cfg)
    params = nn.meta.unbox(model.init(
        {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"])
    base = extract_adapter(params, 0)
    rng = np.random.default_rng(3)
    source = {1: {k: jnp.asarray(rng.normal(0, 0.5, v.shape), v.dtype)
                  for k, v in base.items()}}
    srv = GenerationServer(model, params, _greedy(), num_slots=2,
                           adapter_source=source.__getitem__)
    before = srv._launch_params
    plain = [c.tokens for c in srv.run([[5, 9, 2, 7, 1]], adapter_ids=[0])]
    tinted = [c.tokens for c in srv.run([[5, 9, 2, 7, 1]], adapter_ids=[1])]
    assert tinted != plain
    after = srv._launch_params
    assert after.plan is before.plan
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(srv.params)[0]]
    for i, (a, b) in enumerate(zip(before.arrays, after.arrays)):
        held = [paths[pos] for pos, (arr, _) in
                enumerate(before.plan.where) if arr == i]
        assert (a is not b) == all("_lora" in p for p in held), held
