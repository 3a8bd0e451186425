"""Solar-Open2-style hybrid decoder on the serving path: the module
against its plain float32 reference, the chunkwise delta rule against
the token recurrence, the decode kernel against a ``jax.numpy`` oracle,
the state class of the slot server (a recurrent state a slot beside
the page pool), the chip's share of the routed experts, and what a
recurrent state refuses. Small sizes on the CPU, seeded random weights,
Pallas in interpret mode.

Tolerances. Everything here runs in float32, where the module (a
chunkwise pass, a kernel, pages) and the reference (a token recurrence,
a dense forward) differ only by the order of their sums: logits of size
~6 agree to 5e-4 (readings: 4e-5 .. 1.6e-4; the recurrence compounds
over 700 tokens what one sum's order costs). ``initializer_range`` 0.2
instead of 0.02 makes the logits large enough that a wrong state row, a
stale convolution tail, a padded token folded in or a wrong expert moves
them by far more than that (the tests that plant such a fault read
0.05 and more). The linear heads keep the published 128 x 128 state, so
the decode KERNEL (not its fallback) serves every tick.
"""

import dataclasses
import os

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from _served_rows import ServedRows  # noqa: E402
from paddlefleetx_tpu.core.paging import NULL_PAGE, pool_bytes  # noqa: E402
from paddlefleetx_tpu.core.serving import GenerationServer  # noqa: E402
from paddlefleetx_tpu.models.gpt.generation import (  # noqa: E402
    GenerationConfig,
)
from paddlefleetx_tpu.models.solar_open2 import (  # noqa: E402
    SolarOpen2Config, SolarOpen2ForCausalLM, reference as ref,
)
from paddlefleetx_tpu.models.solar_open2.model import (  # noqa: E402
    SharedAndRoutedExperts, short_conv, state_rows,
)
from paddlefleetx_tpu.observability import metrics  # noqa: E402
from paddlefleetx_tpu.ops import linear_attention as la  # noqa: E402
from paddlefleetx_tpu.ops.pallas import kda  # noqa: E402

TOL = 5e-4          # float32 against float32, sums in another order
PAGE = 128

CFG = SolarOpen2Config(
    vocab_size=512, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=16, num_key_value_heads=2, head_dim=16,
    linear_num_heads=8, linear_head_dim=128, n_routed_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=32,
    max_position_embeddings=2048, initializer_range=0.2)


def _spread_decays(params, cfg):
    """Decays over (0.2, 0.999) a step instead of the ~0.5 everywhere
    that N(0, sigma) leaves give: a state that forgets in ten tokens
    would hide a wrong carry between chunks."""
    rng = np.random.default_rng(5)
    heads, d = cfg.linear_num_heads, cfg.linear_head_dim
    out = jax.tree.map(lambda x: x, params)
    for i in range(cfg.num_hidden_layers):
        if cfg.is_gqa(i):
            continue
        p = dict(out[f"layers_{i}"]["linear_attn"])
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (heads, d)))
        p["A_log"] = jnp.asarray(np.log(rng.uniform(1, 16, heads)),
                                 jnp.float32)
        p["dt_bias"] = jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
        out[f"layers_{i}"] = dict(out[f"layers_{i}"], linear_attn=p)
    return out


@pytest.fixture(scope="module")
def params():
    raw = SolarOpen2ForCausalLM(CFG).init(
        {"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32))["params"]
    return _spread_decays(raw, CFG)


@pytest.fixture(scope="module")
def ref_forward():
    return jax.jit(lambda p, ids: ref.forward(
        dataclasses.asdict(CFG), p, ids))


# -- the module against the reference -----------------------------------

def test_one_period_holds_a_softmax_and_three_delta_layers(params):
    assert [CFG.is_gqa(i) for i in range(4)] == [True, False, False, False]
    assert (CFG.kv_layers, CFG.state_layers) == (1, 3)
    assert "self_attn" in params["layers_0"]
    assert all("linear_attn" in params[f"layers_{i}"] for i in (1, 2, 3))
    full = SolarOpen2Config()
    assert (full.kv_layers, full.state_layers) == (12, 36)
    # 64 heads x 128 x 128 float32 + 3 x 24,576 inputs: 4.19 MB + 147 KB
    assert dataclasses.replace(full, dtype="bfloat16").state_row_bytes \
        == 64 * 128 * 128 * 4 + 3 * 24576 * 2


@pytest.mark.parametrize("length", [64, 150, 300])
def test_module_matches_the_reference(params, ref_forward, length):
    """Logits of a full forward, a softmax and three delta layers: the
    chunkwise pass (blocks of 64; 150 and 300 do not divide) against
    the reference's token recurrence. (Top-3 of 16 is discontinuous:
    a seed on which two scores tie to within a sum's rounding flips a
    pick and moves that token's row by ~0.4; these seeds have none.)"""
    ids = jax.random.randint(jax.random.key(length + 1), (2, length), 0,
                             512)
    out = SolarOpen2ForCausalLM(CFG).apply({"params": params}, ids)
    want = ref_forward(params, ids)
    assert float(jnp.max(jnp.abs(want))) > 3.0
    np.testing.assert_allclose(out, want, atol=TOL, rtol=0)


def test_the_state_the_gate_and_the_convolution_matter(params):
    """The reference itself moves when the delta layers forget at once,
    when the softmax layer's gate is lifted or when the convolution
    sees no past: the parity above is not vacuous."""
    ids = jax.random.randint(jax.random.key(5), (1, 200), 0, 512)
    cfg = dataclasses.asdict(CFG)
    base = ref.forward(cfg, params, ids)

    def with_leaf(layer, module, name, value):
        p = dict(params)
        mod = dict(p[layer][module], **{name: value})
        p[layer] = dict(p[layer], **{module: mod})
        return ref.forward(cfg, p, ids)
    la_ = params["layers_1"]["linear_attn"]
    forgets = with_leaf("layers_1", "linear_attn", "A_log",
                        la_["A_log"] + 5.0)
    gate = params["layers_0"]["self_attn"]["gate_proj"]
    ungated = with_leaf("layers_0", "self_attn", "gate_proj",
                        {"kernel": jnp.zeros_like(gate["kernel"])})
    now_only = with_leaf("layers_1", "linear_attn", "conv_weight",
                         la_["conv_weight"].at[:3].set(0.0))
    for other in (forgets, ungated, now_only):
        assert float(jnp.max(jnp.abs(base - other))) > 0.05


def test_router_matches_the_reference(params):
    """Sigmoid scores over all experts, top-3, normalised: the
    program's ``route`` against the reference's, on one layer's
    weights."""
    from paddlefleetx_tpu.models.deepseek_v3.moe import route
    p = params["layers_2"]["mlp"]
    u = jax.random.normal(jax.random.key(2), (40, 64), jnp.float32)
    idx, w = route(u, p["gate"], p["e_score_correction_bias"], 3, 1.0)
    idx_r, w_r = ref.route(dataclasses.asdict(CFG), p, u)
    assert (np.sort(idx, -1) == np.sort(idx_r, -1)).all()
    np.testing.assert_allclose(np.sort(w, -1), np.sort(w_r, -1), atol=1e-6)
    np.testing.assert_allclose(np.sum(w, -1), 1.0, atol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer(params):
    """Each chip's routed part, with the shared expert counted once,
    adds up to what the uncut reference gives for the whole layer."""
    cfg = dataclasses.asdict(CFG)
    p = params["layers_1"]["mlp"]
    u = jax.random.normal(jax.random.key(3), (2, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(cfg, p, u, 0, 16)
        gate, up = jnp.split(u @ p["shared_gate_up"]["kernel"], 2, -1)
        shared = (jax.nn.silu(gate) * up) @ p["shared_down"]["kernel"]
    total = shared
    for lo in range(0, 16, 2):
        held = dataclasses.replace(CFG, experts_held=(lo, lo + 2))
        share = dict(p, experts_gate_up=p["experts_gate_up"][lo:lo + 2],
                     experts_down=p["experts_down"][lo:lo + 2])
        out, stats = SharedAndRoutedExperts(held).apply(
            {"params": share}, u)
        with jax.default_matmul_precision("highest"):
            want = ref.experts(cfg, share, u, lo, lo + 2)
        np.testing.assert_allclose(out, want, atol=TOL, rtol=0)
        total = total + (out - shared)
        assert int(stats[1]) <= 2
    np.testing.assert_allclose(total, whole, atol=TOL, rtol=0)
    assert float(jnp.max(jnp.abs(whole - shared))) > 0.05


# -- the chunkwise pass against the token recurrence ---------------------

def _delta_case(length, n=2, heads=3, dk=32, dv=16, decay=-0.3, seed=0):
    rng = np.random.default_rng(seed)

    def unit(shape):
        x = rng.normal(size=shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    case = dict(
        q=unit((n, length, heads, dk)), k=unit((n, length, heads, dk)),
        v=rng.normal(size=(n, length, heads, dv)),
        g=decay * rng.uniform(size=(n, length, heads, dk)),
        b=2 * rng.uniform(size=(n, length, heads)),
        s0=rng.normal(size=(n, heads, dk, dv)))
    return {k: jnp.asarray(v, jnp.float32) for k, v in case.items()}


def _recurrence(q, k, v, g, b, s0):
    def step(s, xs):
        q, k, v, g, b = xs
        return la._step(s, q, k, v, jnp.exp(g), b)
    s, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1), s


@pytest.mark.parametrize("length,decay", [
    (64, -0.3), (128, -0.3), (100, -0.3), (200, -6.0), (37, -0.01)])
def test_chunkwise_pass_matches_the_recurrence(length, decay):
    """Lengths that do and do not divide the block of 64; a decay of
    e^-6 a step, whose cumulative product underflows float32 inside one
    block (every exponent the pass takes is <= 0, so nothing
    overflows); a state carried in."""
    case = _delta_case(length, decay=decay)
    o, s = jax.jit(la.kda_chunk)(**case)
    want_o, want_s = _recurrence(**case)
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=0)


def test_chunkwise_pass_with_repeated_keys():
    """Fifty equal keys with step sizes up to 2: the triangular system
    is far from the identity, and forward substitution still solves
    it."""
    case = _delta_case(128, decay=-0.01, seed=1)
    case["k"] = case["k"].at[:, 10:60].set(case["k"][:, 10:11])
    o, s = la.kda_chunk(**case)
    want_o, want_s = _recurrence(**case)
    np.testing.assert_allclose(o, want_o, atol=5e-5, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=5e-5, rtol=0)


def test_a_padded_tail_leaves_the_state_where_it_was():
    """Positions with ``g = 0`` and ``b = 0`` (what the model feeds for
    a last chunk's padding) move nothing: the state after 128 fed
    positions of which 77 are real is the state after 77."""
    case = _delta_case(128, seed=2)
    real = jnp.arange(128) < 77
    padded = dict(case, g=jnp.where(real[None, :, None, None],
                                    case["g"], 0.0),
                  b=jnp.where(real[None, :, None], case["b"], 0.0))
    o, s = la.kda_chunk(**padded)
    short = {k: (v if k == "s0" else v[:, :77]) for k, v in case.items()}
    want_o, want_s = _recurrence(**short)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=0)
    np.testing.assert_allclose(o[:, :77], want_o, atol=2e-5, rtol=0)
    # unmasked, the tail counts
    assert float(jnp.max(jnp.abs(la.kda_chunk(**case)[1] - want_s))) > 0.05


def test_short_conv_is_four_shifted_sums():
    x = jax.random.normal(jax.random.key(0), (2, 9, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    got = short_conv(jnp.pad(x, ((0, 0), (3, 0), (0, 0))), w)
    np.testing.assert_allclose(got, ref.short_conv(x, w), atol=1e-6)


# -- the decode kernel ----------------------------------------------------

def _step_case(n=5, heads=8, d=128, rows=(3, 0, 1, 0, 6), seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        state=f(7, heads, d, d), rows=jnp.asarray(rows, jnp.int32),
        q=f(n, heads, d) / 11, k=f(n, heads, d) / 11, v=f(n, heads, d),
        a=jnp.asarray(rng.uniform(size=(n, heads, d)), jnp.float32),
        b=jnp.asarray(2 * rng.uniform(size=(n, heads)), jnp.float32))


def test_kda_decode_matches_the_oracle_and_leaves_dead_rows_alone():
    """Interpret mode against plain ``jax.numpy``: live rows 3, 1 and 6
    are updated where they lie, rows 2, 4, 5 (other slots') and the
    null row are bit for bit what they were, a dead row reads zeros."""
    case = _step_case()
    state = case["state"]
    got_s, got_o = kda.kda_decode(**case)
    want_s, want_o = la._step(
        state[case["rows"]], case["q"], case["k"], case["v"], case["a"],
        case["b"])
    live = np.asarray(case["rows"]) != 0
    np.testing.assert_allclose(
        np.asarray(got_s)[np.asarray(case["rows"])[live]],
        np.asarray(want_s)[live], atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got_o)[live],
                               np.asarray(want_o)[live], atol=1e-5, rtol=0)
    assert not np.asarray(got_o)[~live].any()
    for row in (0, 2, 4, 5):
        assert (np.asarray(got_s)[row] == np.asarray(state)[row]).all()


def test_kda_decode_with_nothing_live_touches_nothing():
    case = _step_case(rows=(0, 0, 0, 0, 0))
    got_s, got_o = kda.kda_decode(**case)
    assert (np.asarray(got_s) == np.asarray(case["state"])).all()
    assert not np.asarray(got_o).any()


def test_kda_step_counts_its_kernel_and_its_fallback():
    prior = metrics.get_registry().enabled
    metrics.set_enabled(True)
    metrics.get_registry().reset()
    case = _step_case()
    s1, o1 = la.kda_step(**case)
    small = _step_case(heads=2, d=16)        # a shape the kernel refuses
    s2, o2 = la.kda_step(**small)
    want_s, want_o = la.kda_step(**small, use_kernel=False)
    c = metrics.get_registry().snapshot()["counters"]
    metrics.set_enabled(prior)
    assert c["attention/kda_decode"] == 1
    assert c["attention/fallback/kda_rejected"] == 1
    np.testing.assert_allclose(o2, want_o, atol=1e-6)
    np.testing.assert_allclose(s2[1:], want_s[1:], atol=1e-6)
    with pytest.raises(NotImplementedError):
        kda.kda_decode(**small)


# -- three kinds of cache in one server -----------------------------------

LENGTHS = (700, 40, 300, 9, 130)   # 700 = 3 chunks, the last 188 real
DEC = 6


def _drive(srv, prompts, params, ref_forward, steps):
    ids = [srv.submit(p) for p in prompts]
    done = {}
    rows = ServedRows(srv)
    while srv.work_pending():
        for c in srv.step():
            done[c.request_id] = c
        srv.check_alloc()
        for req, seq, got in rows.after_step():
            pad = -len(seq) % 256            # a few compiled lengths
            want = np.asarray(ref_forward(
                params, jnp.asarray([seq + [0] * pad])))[0, len(seq) - 1]
            steps.append((req["id"], len(seq), got, want))
    return ids, done


@pytest.fixture(scope="module")
def served(params, ref_forward):
    """One server of 2 slots, five prompts of different lengths in one
    queue (so three are admitted into a slot another request just
    left), driven through ``submit`` / ``step``; after every step the
    logits the next token is sampled from, beside the reference's full
    forward of the same sequence."""
    prior = metrics.get_registry().enabled
    metrics.set_enabled(True)
    metrics.get_registry().reset()
    gen = GenerationConfig(max_dec_len=DEC, decode_strategy="greedy_search",
                           eos_token_id=511, pad_token_id=511)
    srv = GenerationServer(SolarOpen2ForCausalLM(CFG), params, gen,
                           num_slots=2, page_size=PAGE,
                           prefill_chunk_pages=2, pool_pages=20)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, n).tolist() for n in LENGTHS]
    steps = []
    ids, done = _drive(srv, prompts, params, ref_forward, steps)
    out = dict(srv=srv, ids=ids, prompts=prompts, steps=steps, done=done,
               summary=srv.summary(),
               counters=dict(metrics.get_registry().snapshot()["counters"]))
    metrics.set_enabled(prior)
    yield out
    srv.close()


def test_served_logits_match_the_full_forward(served):
    """Chunked paged prefill, then decode through pages and state,
    against the reference's full forward pass: logits, at every step of
    every request. The 700-token prompt's state crosses two chunk
    boundaries and its last chunk is 188 real tokens and 68 of
    padding; the 130-token one is a chunk of 2 real tokens past a
    page. Requests 2, 3 and 4 start in a slot that another request's
    state was just left in."""
    assert {rid for rid, *_ in served["steps"]} == set(served["ids"])
    assert max(n for _, n, _, _ in served["steps"]) >= 700 + DEC - 1
    worst = max(float(np.max(np.abs(got - want)))
                for _, _, got, want in served["steps"])
    assert worst < TOL, worst
    for rid in served["ids"]:
        c = served["done"][rid]
        assert c.finish_reason in ("length", "eos")


def test_the_state_leaves_hold_a_row_a_slot_behind_the_null_row(served):
    cfg = served["srv"].model.config
    assert cfg.state_rows == 3
    leaves = jax.tree_util.tree_leaves_with_path(served["srv"]._cache)
    shapes = {}
    for path, leaf in leaves:
        shapes.setdefault(getattr(path[-1], "key", ""), []).append(
            (leaf.shape, leaf.dtype))
    assert shapes["kda_state"] == [((3, 8, 128, 128), jnp.float32)] * 3
    assert shapes["conv_tail"] == [((3, 3 * 3 * 8 * 128), jnp.float32)] * 3
    assert shapes["cached_key"] == [((20, 2, 16, PAGE), jnp.float32)]
    s = served["summary"]
    assert s["pool_bytes"] == pool_bytes(1, 2, 16, PAGE, 20)
    assert s["state_bytes"] == 3 * 3 * cfg.state_row_bytes == sum(
        leaf.nbytes for path, leaf in leaves
        if getattr(path[-1], "key", "") in ("kda_state", "conv_tail"))
    assert s["state_rows_held"] == 0 and s["pages_in_use"] == 0


def test_state_class_counters(served):
    c = served["counters"]
    live = c["serving/decode_rows_live"]
    assert c["attention/kda_decode"] > 0 and c["attention/kda_chunk"] > 0
    assert c["attention/kda_layers"] > 0
    assert c.get("attention/fallback/kda_rejected", 0) == 0
    assert c["attention/flash_decode_paged"] > 0
    assert c["attention/paged_gqa"] > 0
    assert c.get("attention/dense", 0) == 0
    assert c["serving/state_rows_held"] == 3 * live
    assert c["serving/state_resets"] == 3 * len(LENGTHS)
    assert c["serving/pages_global_held"] >= live      # one K/V layer
    s = served["summary"]
    assert s["moe_decode_picks"] == 3 * CFG.num_hidden_layers * live
    assert 0 < s["moe_experts_touched"] <= s["moe_decode_picks"]
    # a chunk's padded tail is kept out of the dispatch
    assert s["moe_prefill_picks"] == 3 * CFG.num_hidden_layers * sum(LENGTHS)


def test_a_prefix_hit_is_refused_on_a_model_with_recurrent_state(served):
    """A page registry cannot hand over a state: nothing is registered
    or shared, and every admission that would have looked a prefix up
    is counted."""
    assert served["summary"]["prefix_refused_recurrent"] is True
    assert served["counters"]["serving/prefix_refused_recurrent"] \
        == len(LENGTHS)
    assert served["summary"]["prefix_hits"] == 0
    assert served["summary"]["prompt_hits"] == 0
    assert not served["srv"]._prefix_sharing
    assert served["srv"].prefix_affinity(served["prompts"][0]) == 0


def test_kv_handoff_is_refused_on_a_model_with_recurrent_state(served):
    srv = served["srv"]
    before = metrics.get_registry().enabled
    metrics.set_enabled(True)
    n0 = metrics.get_registry().counter("serving/kv_handoff_refused_recurrent")
    assert srv.kv_export(served["prompts"][1]) is None
    assert srv.kv_import(served["prompts"][1], None, None, 1) is False
    assert metrics.get_registry().counter(
        "serving/kv_handoff_refused_recurrent") == n0 + 2
    metrics.set_enabled(before)


def test_spec_method_is_refused_at_construction(params):
    gen = GenerationConfig(max_dec_len=4, decode_strategy="greedy_search",
                           eos_token_id=511, pad_token_id=511,
                           spec_method="ngram", spec_tokens=2)
    with pytest.raises(ValueError, match="recurrent state"):
        GenerationServer(SolarOpen2ForCausalLM(CFG), params, gen,
                         num_slots=2, page_size=PAGE, pool_pages=20)


def test_a_decode_tick_leaves_free_and_prefilling_slots_alone(
        params, ref_forward):
    """Slot 0 decodes while slot 1 is two chunks into a three-chunk
    prefill and slot 2 is free (something planted in its rows): a
    decode tick on the server's own cache and table moves slot 0's
    state and tail and leaves the others' bit for bit; slot 1 then
    finishes with the reference's logits."""
    from paddlefleetx_tpu.models.gpt.generation import decode_step
    gen = GenerationConfig(max_dec_len=DEC, decode_strategy="greedy_search",
                           eos_token_id=511, pad_token_id=511)
    srv = GenerationServer(SolarOpen2ForCausalLM(CFG), params, gen,
                           num_slots=3, page_size=PAGE,
                           prefill_chunk_pages=1, pool_pages=20)
    rng = np.random.default_rng(1)
    srv.submit(rng.integers(0, 500, 20).tolist())
    srv.step()                                 # slot 0 prefilled, ticking
    srv.submit(rng.integers(0, 500, 300).tolist())
    srv.step()
    srv.step()                                 # slot 1: chunks 1 and 2
    assert srv._slots[1] is not None and not srv._slots[1]["active"]
    assert srv._slots[0]["active"] and srv._slots[2] is None

    def is_state(path):
        return getattr(path[-1], "key", "") in ("kda_state", "conv_tail")
    srv._cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf.at[3].set(7.0) if is_state(path) else leaf,
        srv._cache)
    srv._sync_pt()
    assert list(np.asarray(srv._pt_dev_dec)[:, -1]) == [1, 0, 0]
    assert list(np.asarray(srv._pt_dev)[:, -1]) == [1, 2, 3]
    ticked, _, _ = decode_step(
        srv.model, srv.params, jax.tree.map(jnp.copy, srv._cache),
        srv._state, srv._rng, srv.gen_cfg, srv._pt_dev_dec, None)
    pairs = [(np.asarray(a), np.asarray(b)) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(srv._cache),
        jax.tree.leaves(ticked)) if is_state(path)]
    assert len(pairs) == 6
    for before, after in pairs:
        assert (before[2] == after[2]).all() and np.abs(before[2]).max() > 0
        assert (after[3] == 7.0).all()
        assert np.abs(before[1] - after[1]).max() > 0
    worst = []
    rows, mine = ServedRows(srv), srv._slots[1]
    while srv.work_pending():
        srv.step()
        for req, seq, got in rows.after_step():
            if req is mine:
                want = np.asarray(ref_forward(params, jnp.asarray(
                    [seq + [0] * (-len(seq) % 256)])))[0, len(seq) - 1]
                worst.append(float(np.max(np.abs(got - want))))
    assert worst and max(worst) < TOL
    srv.close()


def test_a_preempted_request_resumes_with_the_logits_it_would_have_had(
        params, ref_forward):
    """A pool too small for both: the younger request is preempted
    (pages released, re-queued), re-prefills prompt + tokens, which
    rebuilds its state, and every logit it is served from agrees with
    the reference's full forward."""
    gen = GenerationConfig(max_dec_len=12, min_dec_len=12,
                           decode_strategy="greedy_search",
                           eos_token_id=511, pad_token_id=511)
    srv = GenerationServer(SolarOpen2ForCausalLM(CFG), params, gen,
                           num_slots=2, page_size=PAGE,
                           prefill_chunk_pages=1, pool_pages=5)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 500, n).tolist() for n in (250, 240)]
    steps = []
    ids, done = _drive(srv, prompts, params, ref_forward, steps)
    assert srv.summary()["preempted"] >= 1
    assert all(done[i].finish_reason in ("length", "eos") for i in ids)
    assert {rid for rid, *_ in steps} == set(ids)
    assert max(float(np.max(np.abs(g - w))) for *_, g, w in steps) < TOL
    srv.close()


def test_state_rows_ride_behind_the_page_columns():
    cfg = dataclasses.replace(CFG, kv_page_size=PAGE,
                              kv_pool_pages=20).state_class(2)
    assert cfg.state_rows == 3 and cfg.max_kv_pages == 16
    glob = np.full((2, 16), NULL_PAGE, np.int32)
    glob[0, :3] = [9, 3, 4]
    table = np.concatenate([glob, [[1], [0]]], axis=1)
    pages, rows = state_rows(jnp.asarray(table), cfg)
    assert (np.asarray(pages) == glob).all()
    assert list(np.asarray(rows)) == [1, 0]
    pages, rows = state_rows(jnp.asarray(glob), cfg)   # init_page_pool
    assert list(np.asarray(rows)) == [0, 0]


def test_the_family_is_served_paged_only(params):
    with pytest.raises(NotImplementedError, match="paged"):
        SolarOpen2ForCausalLM(CFG).apply(
            {"params": params}, jnp.zeros((1, 4), jnp.int32),
            use_cache=True, mutable=["cache"])


def test_what_the_published_config_does_not_say_is_refused():
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("kda_use_full_proj", True),
                       ("kda_allow_neg_eigval", False)):
        with pytest.raises(ValueError, match="not implemented"):
            dataclasses.replace(CFG, **{key: value})
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(CFG, experts_held=(4, 20))
