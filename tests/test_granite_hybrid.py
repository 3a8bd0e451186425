"""Granite-4.0-H-style hybrid decoder on the serving path: the module
against its plain float32 reference, the chunkwise (SSD) pass against
the token recurrence, the decode kernel against a ``jax.numpy`` oracle,
the state class of the slot server with state-space layers nine in ten,
the attention scale, what a recurrent state refuses, and the bursty
arrivals of the family's cell. Small sizes on the CPU, seeded random
weights, Pallas in interpret mode.

Tolerances. Everything here runs in float32, where the module (a
chunkwise pass, a kernel, pages) and the reference (a token recurrence,
a dense forward) differ only by the order of their sums: logits of size
~2-4 agree to 2e-4 (readings: 7e-7 on a whole forward, 2e-5 .. 6e-5
served; the recurrence compounds over 700 tokens what one sum's order
costs). ``initializer_range`` 0.2 instead of 0.02 and decays spread over
(0.2, 0.999) make a wrong state row, a stale convolution tail, a padded
token folded in or a wrong scale move the logits by far more than that
(the tests that plant such a fault read 0.02 and more). The state-space
heads keep the published 128 state dimensions (8 heads of 16 x 128), so
the decode KERNEL (not its fallback) serves every tick.
"""

import dataclasses
import os

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from _served_rows import (  # noqa: E402
    ServedRows, ends_a_prompt_without_a_read,
)
from paddlefleetx_tpu.core.paging import pool_bytes  # noqa: E402
from paddlefleetx_tpu.core.serving import GenerationServer  # noqa: E402
from paddlefleetx_tpu.models.gpt.generation import (  # noqa: E402
    GenerationConfig,
)
from paddlefleetx_tpu.models.granite_hybrid import (  # noqa: E402
    GraniteHybridConfig, GraniteHybridForCausalLM, reference as ref,
)
from paddlefleetx_tpu.models.solar_open2.model import (  # noqa: E402
    short_conv,
)
from paddlefleetx_tpu.observability import metrics  # noqa: E402
from paddlefleetx_tpu.ops import state_space as ss  # noqa: E402
from paddlefleetx_tpu.ops.pallas import ssd  # noqa: E402

TOL = 2e-4          # float32 against float32, sums in another order
PAGE = 128
#: one period's worth of kinds at a tenth of its length
KINDS = ("mamba", "attention", "mamba", "mamba")

CFG = GraniteHybridConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=4, layer_types=KINDS,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    attention_multiplier=1 / 16, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=128, mamba_chunk_size=64, shared_intermediate_size=128,
    max_position_embeddings=2048, initializer_range=0.2)


def _spread_decays(params, cfg):
    """Decays over (0.2, 0.999) a step instead of the ~0.5 everywhere
    that N(0, sigma) leaves give: a state that forgets in ten tokens
    would hide a wrong carry between chunks."""
    rng = np.random.default_rng(5)
    heads = cfg.mamba_n_heads
    out = jax.tree.map(lambda x: x, params)
    for i in range(cfg.num_hidden_layers):
        if cfg.is_attention(i):
            continue
        p = dict(out[f"layers_{i}"]["mamba"])
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), heads))
        p["A_log"] = jnp.asarray(np.log(rng.uniform(1, 16, heads)),
                                 jnp.float32)
        p["dt_bias"] = jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)
        p["D"] = jnp.asarray(rng.uniform(0.5, 1.5, heads), jnp.float32)
        out[f"layers_{i}"] = dict(out[f"layers_{i}"], mamba=p)
    return out


@pytest.fixture(scope="module")
def params():
    raw = GraniteHybridForCausalLM(CFG).init(
        {"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32))["params"]
    return _spread_decays(raw, CFG)


@pytest.fixture(scope="module")
def ref_forward():
    return jax.jit(lambda p, ids: ref.forward(
        dataclasses.asdict(CFG), p, ids))


# -- the module against the reference -----------------------------------

def test_the_layers_are_of_two_kinds_and_the_head_is_tied(params):
    assert (CFG.kv_layers, CFG.state_layers) == (1, 3)
    assert "self_attn" in params["layers_1"]
    assert all("mamba" in params[f"layers_{i}"] for i in (0, 2, 3))
    assert "lm_head" not in params
    full = GraniteHybridConfig()
    assert (full.kv_layers, full.state_layers) == (4, 36)
    assert [i for i in range(40) if full.is_attention(i)] == [5, 15, 25, 35]
    assert (full.mamba_d_inner, full.conv_channels) == (4096, 4352)
    assert full.query_scale == 0.125
    # 64 heads x 64 x 128 float32 + a tail of 3 x 4352 inputs: 2.10 MB
    # + 26 KB
    assert dataclasses.replace(full, dtype="bfloat16").state_row_bytes \
        == 64 * 64 * 128 * 4 + 3 * 4352 * 2
    shapes = jax.eval_shape(
        GraniteHybridForCausalLM(full).init, {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"]
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 3_191_396_096            # 3,191.4 M, the whole model
    assert shapes["layers_0"]["mamba"]["in_proj"]["kernel"].shape \
        == (2048, 8512)


@pytest.mark.parametrize("length", [64, 150, 300])
def test_module_matches_the_reference(params, ref_forward, length):
    """Logits of a full forward, three state-space layers and a softmax
    layer, the multipliers and the tied scaled head: the chunkwise pass
    (blocks of 64; 150 and 300 do not divide) against the reference's
    token recurrence."""
    ids = jax.random.randint(jax.random.key(length + 1), (2, length), 0,
                             512)
    out = GraniteHybridForCausalLM(CFG).apply({"params": params}, ids)
    want = ref_forward(params, ids)
    assert float(jnp.max(jnp.abs(want))) > 1.5
    np.testing.assert_allclose(out, want, atol=TOL, rtol=0)


def test_the_state_the_convolution_and_the_multipliers_matter(params):
    """The reference itself moves when a state-space layer forgets at
    once, when the convolution sees no past or has no bias, when the
    skip is lifted, and with each of the four multipliers: the parity
    above is not vacuous."""
    ids = jax.random.randint(jax.random.key(5), (1, 200), 0, 512)
    cfg = dataclasses.asdict(CFG)
    base = ref.forward(cfg, params, ids)

    def with_leaf(name, value):
        p = dict(params)
        p["layers_0"] = dict(p["layers_0"], mamba=dict(
            p["layers_0"]["mamba"], **{name: value}))
        return ref.forward(cfg, p, ids)
    m = params["layers_0"]["mamba"]
    others = [with_leaf("A_log", m["A_log"] + 5.0),
              with_leaf("conv_weight", m["conv_weight"].at[:3].set(0.0)),
              with_leaf("conv_bias", jnp.zeros_like(m["conv_bias"])),
              with_leaf("D", jnp.zeros_like(m["D"]))]
    for key, value in (("attention_multiplier", 0.25),   # 16 ** -0.5
                       ("embedding_multiplier", 1.0),
                       ("residual_multiplier", 1.0),
                       ("logits_scaling", 1.0)):
        others.append(ref.forward(dict(cfg, **{key: value}), params, ids))
    for other in others:
        assert float(jnp.max(jnp.abs(base - other))) > 0.02


def test_the_scores_are_scaled_by_the_multiplier_not_by_the_head_size(
        params, ref_forward):
    """``attention_multiplier`` is 1/16 where ``head_dim ** -0.5`` is
    1/4: the module under the published rule agrees with the reference,
    and a module that scaled by the head size (a ``query_scale`` of 1)
    does not."""
    ids = jax.random.randint(jax.random.key(9), (1, 96), 0, 512)
    want = ref_forward(params, ids)
    got = GraniteHybridForCausalLM(CFG).apply({"params": params}, ids)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert CFG.query_scale == 0.25
    wrong = GraniteHybridForCausalLM(dataclasses.replace(
        CFG, attention_multiplier=0.25)).apply({"params": params}, ids)
    assert float(jnp.max(jnp.abs(wrong - want))) > 0.02
    with pytest.raises(ValueError, match="power of two"):
        dataclasses.replace(CFG, attention_multiplier=0.1)


# -- the chunkwise pass against the token recurrence ---------------------

def _ssd_case(length, n=2, heads=3, p=8, ns=16, rate=1.0, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        x=f(n, length, heads, p),
        dt=jnp.asarray(rate * rng.uniform(0.001, 0.5, (n, length, heads)),
                       jnp.float32),
        a_log=jnp.asarray(np.log(rng.uniform(1, 16, heads)), jnp.float32),
        b=f(n, length, ns), c=f(n, length, ns),
        d_skip=jnp.asarray(rng.uniform(0.5, 1.5, heads), jnp.float32),
        s0=f(n, heads, p, ns))


def _recurrence(x, dt, a_log, b, c, d_skip, s0):
    def step(s, xs):
        x, dt, b, c = xs
        return ss._step(s, x, dt, jnp.exp(-dt * jnp.exp(a_log)), b, c,
                        d_skip)
    s, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), s


@pytest.mark.parametrize("length,block,rate", [
    (256, 256, 1.0), (512, 256, 1.0), (300, 256, 1.0), (100, 256, 1.0),
    (37, 16, 1.0), (200, 64, 40.0)])
def test_chunkwise_pass_matches_the_recurrence(length, block, rate):
    """Lengths that do and do not divide the published block of 256 (and
    smaller blocks); steps of up to 20 x 16, whose decay underflows
    float32 inside one block (every exponent the pass takes is <= 0, so
    nothing overflows); a state carried in."""
    case = _ssd_case(length, rate=rate)
    y, s = jax.jit(ss.ssd_chunk, static_argnames="block")(
        **case, block=block)
    want_y, want_s = _recurrence(**case)
    assert bool(jnp.isfinite(y).all())
    # a sum's rounding against the largest term (steps of 20 make
    # outputs of several hundred; readings up to 3e-5 of it)
    scale = float(jnp.max(jnp.abs(want_y)))
    np.testing.assert_allclose(y, want_y, atol=5e-5 * scale, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=5e-5 * scale, rtol=0)


def test_a_padded_tail_leaves_the_state_where_it_was():
    """Positions with ``dt = 0`` (what the model feeds for a last
    chunk's padding) move nothing: the state after 128 fed positions of
    which 77 are real is the state after 77."""
    case = _ssd_case(128, seed=2)
    real = jnp.arange(128) < 77
    padded = dict(case, dt=jnp.where(real[None, :, None], case["dt"], 0.0))
    y, s = ss.ssd_chunk(**padded, block=64)
    short = {k: (v[:, :77] if k in ("x", "dt", "b", "c") else v)
             for k, v in case.items()}
    want_y, want_s = _recurrence(**short)
    np.testing.assert_allclose(s, want_s, atol=2e-5, rtol=0)
    np.testing.assert_allclose(y[:, :77], want_y, atol=5e-5, rtol=0)
    # unmasked, the tail counts
    unmasked = ss.ssd_chunk(**case, block=64)[1]
    assert float(jnp.max(jnp.abs(unmasked - want_s))) > 0.05


def test_short_conv_takes_a_bias():
    x = jax.random.normal(jax.random.key(0), (2, 9, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    b = jax.random.normal(jax.random.key(2), (6,))
    seq = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    np.testing.assert_allclose(short_conv(seq, w, b),
                               ref.short_conv(x, w, b), atol=1e-6)
    assert float(jnp.max(jnp.abs(short_conv(seq, w, b)
                                 - short_conv(seq, w)))) > 0.1


# -- the decode kernel ----------------------------------------------------

def _step_case(n=5, heads=8, p=16, ns=128, rows=(3, 0, 1, 0, 6), seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        state=f(7, heads, p, ns), rows=jnp.asarray(rows, jnp.int32),
        x=f(n, heads, p),
        dt=jnp.asarray(rng.uniform(0.001, 0.1, (n, heads)), jnp.float32),
        a=jnp.asarray(rng.uniform(size=(n, heads)), jnp.float32),
        b=f(n, ns), c=f(n, ns) / 11,
        d_skip=jnp.asarray(rng.uniform(0.5, 1.5, heads), jnp.float32))


@pytest.mark.parametrize("heads,p", [(8, 16), (64, 64)])
def test_ssd_decode_matches_the_oracle_and_leaves_dead_rows_alone(heads, p):
    """Interpret mode against plain ``jax.numpy``, at the test model's
    heads and at the published 64 x 64 x 128 (two blocks of 32 heads):
    live rows 3, 1 and 6 are updated where they lie, rows 2, 4, 5 (other
    slots') and the null row are bit for bit what they were, a dead row
    reads zeros."""
    case = _step_case(heads=heads, p=p)
    state, rows = case["state"], np.asarray(case["rows"])
    got_s, got_y = ssd.ssd_decode(**case)
    want_s, want_y = ss._step(state[case["rows"]], *(
        case[k] for k in ("x", "dt", "a", "b", "c", "d_skip")))
    live = rows != 0
    np.testing.assert_allclose(np.asarray(got_s)[rows[live]],
                               np.asarray(want_s)[live], atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               np.asarray(want_y)[live], atol=1e-5, rtol=0)
    assert not np.asarray(got_y)[~live].any()
    for row in (0, 2, 4, 5):
        assert (np.asarray(got_s)[row] == np.asarray(state)[row]).all()


def test_ssd_decode_with_nothing_live_touches_nothing():
    case = _step_case(rows=(0, 0, 0, 0, 0))
    got_s, got_y = ssd.ssd_decode(**case)
    assert (np.asarray(got_s) == np.asarray(case["state"])).all()
    assert not np.asarray(got_y).any()


def test_ssd_step_counts_its_kernel_and_its_fallback():
    prior = metrics.get_registry().enabled
    metrics.set_enabled(True)
    metrics.get_registry().reset()
    case = _step_case()
    ss.ssd_step(**case)
    small = _step_case(heads=2, p=4, ns=16)  # a shape the kernel refuses
    s2, y2 = ss.ssd_step(**small)
    want_s, want_y = ss.ssd_step(**small, use_kernel=False)
    c = metrics.get_registry().snapshot()["counters"]
    metrics.set_enabled(prior)
    assert c["attention/ssd_decode"] == 1
    assert c["attention/fallback/ssd_rejected"] == 1
    np.testing.assert_allclose(y2, want_y, atol=1e-6)
    np.testing.assert_allclose(s2[1:], want_s[1:], atol=1e-6)
    with pytest.raises(NotImplementedError):
        ssd.ssd_decode(**small)


# -- two kinds of cache in one server, the state the larger ---------------

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


def _server(params, gen, **kw):
    return GenerationServer(GraniteHybridForCausalLM(CFG), params, gen,
                            page_size=PAGE, **kw)


def _greedy(max_dec_len=DEC, **kw):
    return GenerationConfig(max_dec_len=max_dec_len,
                            decode_strategy="greedy_search",
                            eos_token_id=511, pad_token_id=511, **kw)


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
    srv = _server(params, _greedy(), num_slots=2, prefill_chunk_pages=2,
                  pool_pages=20)
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
    boundaries (and six SSD blocks) and its last chunk is 188 real
    tokens and 68 of padding; the 130-token one is a chunk of 2 real
    tokens past a page. Requests 2, 3 and 4 start in a slot that
    another request's state was just left in: zero again."""
    assert {rid for rid, *_ in served["steps"]} == set(served["ids"])
    assert max(n for _, n, _, _ in served["steps"]) >= 700 + DEC - 1
    worst = max(float(np.max(np.abs(got - want)))
                for _, _, got, want in served["steps"])
    assert worst < TOL, worst
    for rid in served["ids"]:
        assert served["done"][rid].finish_reason in ("length", "eos")


def test_the_state_leaves_hold_a_row_a_slot_behind_the_null_row(served):
    cfg = served["srv"].model.config
    assert cfg.state_rows == 3
    leaves = jax.tree_util.tree_leaves_with_path(served["srv"]._cache)
    shapes = {}
    for path, leaf in leaves:
        shapes.setdefault(getattr(path[-1], "key", ""), []).append(
            (leaf.shape, leaf.dtype))
    assert shapes["ssm_state"] == [((3, 8, 16, 128), jnp.float32)] * 3
    assert shapes["conv_tail"] == [((3, 3 * (128 + 256)), jnp.float32)] * 3
    assert shapes["cached_key"] == [((20, 2, 16, PAGE), jnp.float32)]
    s = served["summary"]
    assert s["pool_bytes"] == pool_bytes(1, 2, 16, PAGE, 20)
    assert s["state_bytes"] == 3 * 3 * cfg.state_row_bytes == sum(
        leaf.nbytes for path, leaf in leaves
        if getattr(path[-1], "key", "") in ("ssm_state", "conv_tail"))
    assert s["state_bytes"] > s["pool_bytes"]
    assert s["state_rows_held"] == 0 and s["pages_in_use"] == 0


def test_state_class_counters(served):
    c = served["counters"]
    live = c["serving/decode_rows_live"]
    assert c["attention/ssd_decode"] > 0 and c["attention/ssd_chunk"] > 0
    assert c["attention/ssm_layers"] > 0
    assert not any(k.startswith("attention/fallback/") for k in c)
    assert c["attention/flash_decode_paged"] > 0
    assert c["attention/paged_gqa"] > 0
    assert c.get("attention/dense", 0) == 0
    assert c["serving/state_rows_held"] == 3 * live
    assert c["serving/state_resets"] == 3 * len(LENGTHS)
    assert c["serving/pages_global_held"] >= live      # one K/V layer
    # five requests over two slots: some step ended with one queued and
    # no slot free
    assert 0 < c["serving/slots_full_steps"] < c["serving/device_ticks"]


def test_a_prefix_hit_is_refused_on_a_model_with_recurrent_state(served):
    assert served["summary"]["prefix_refused_recurrent"] is True
    assert served["counters"]["serving/prefix_refused_recurrent"] \
        == len(LENGTHS)
    assert served["summary"]["prefix_hits"] == 0
    assert not served["srv"]._prefix_sharing


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
    with pytest.raises(ValueError, match="recurrent state"):
        _server(params, _greedy(4, spec_method="ngram", spec_tokens=2),
                num_slots=2, pool_pages=20)


def test_a_decode_tick_leaves_free_and_prefilling_slots_alone(
        params, ref_forward):
    """Slot 0 decodes while slot 1 is two chunks into a three-chunk
    prefill and slot 2 is free (something planted in its rows): a
    decode tick on the server's own cache and table moves slot 0's
    state and tail and leaves the others' bit for bit; slot 1 then
    finishes with the reference's logits."""
    from paddlefleetx_tpu.models.gpt.generation import decode_step
    srv = _server(params, _greedy(), num_slots=3, prefill_chunk_pages=1,
                  pool_pages=20)
    rng = np.random.default_rng(1)
    srv.submit(rng.integers(0, 500, 20).tolist())
    srv.step()                                 # slot 0 prefilled, ticking
    srv.submit(rng.integers(0, 500, 300).tolist())
    srv.step()
    srv.step()                                 # slot 1: chunks 1 and 2
    assert srv._slots[1] is not None and not srv._slots[1]["active"]
    assert srv._slots[0]["active"] and srv._slots[2] is None

    def is_state(path):
        return getattr(path[-1], "key", "") in ("ssm_state", "conv_tail")
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
    # slot 0's own rows move: every state, and every tail but layer
    # 0's, whose input is the token's embedding alone (greedy decoding
    # on random weights repeats a token, and four equal inputs shift
    # into the tail they find)
    moved = [bool(np.abs(before[1] - after[1]).max() > 0)
             for before, after in pairs]
    assert sum(moved) >= 5, moved
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
    srv = _server(params, _greedy(12, min_dec_len=12), num_slots=2,
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


def test_the_family_is_served_paged_only(params):
    with pytest.raises(NotImplementedError, match="paged"):
        GraniteHybridForCausalLM(CFG).apply(
            {"params": params}, jnp.zeros((1, 4), jnp.int32),
            use_cache=True, mutable=["cache"])


def test_what_the_published_config_does_not_say_is_refused():
    for key, value in (("position_embedding_type", "rope"),
                       ("mamba_n_groups", 8), ("mamba_conv_bias", False),
                       ("mamba_proj_bias", True), ("num_local_experts", 8),
                       ("tie_word_embeddings", False),
                       ("attention_bias", True)):
        with pytest.raises(ValueError, match="not implemented"):
            dataclasses.replace(CFG, **{key: value})
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(CFG, layer_types=("mamba", "window") * 2)


# -- the cell's arrivals ---------------------------------------------------

def test_bursty_gaps_are_gamma_with_a_coefficient_of_variation_of_two():
    """The stratified quantiles of a gamma distribution of shape 0.25:
    their count and mean are the rate's, their coefficient of variation
    2 less what 224 quantiles cut off the tail, and the schedule is one
    fixed pattern whatever ``--seed`` is."""
    from chipbench import traffic_bursty, traffic_gen
    gaps = traffic_bursty.gamma_gaps(224, 5.6, 0.25)
    assert gaps.shape == (224,) and (gaps > 0).all()
    np.testing.assert_allclose(gaps.mean(), 1 / 5.6, rtol=1e-9)
    cv = gaps.std() / gaps.mean()
    assert 1.85 < cv < 2.0, cv
    smooth = traffic_gen.poisson_gaps(224, 5.6)
    assert 0.9 < smooth.std() / smooth.mean() < 1.0
    mix = {"rate_per_s": 5.6, "ramp_s": 20.0, "schedule_seed": 36,
           "gap_shape": 0.25,
           "prompt_len": {"median": 384, "sigma": 0.9, "min": 32,
                          "max": 3072}}

    def first(seed, n=400):
        it = traffic_bursty.open_loop_blocks(mix, seed, 100352, 40.0)
        return [next(it) for _ in range(n)]
    one, two = first(1), first(2 ** 31 + 5)
    assert [t for t, _ in one] == [t for t, _ in two]
    assert [len(p) for _, p in one] == [len(p) for _, p in two]
    assert one[0][1] != two[0][1]                # the ids follow --seed
    due = np.array([t for t, _ in one])
    assert (np.diff(due) > 0).all() and due[0] < -19.0
    inside = due[(due >= 0) & (due < 40.0)]
    assert len(inside) == 224
    lengths = np.array([len(p) for _, p in one])
    assert lengths.min() >= 32 and lengths.max() <= 3072
    assert 300 < np.median(lengths) < 480
    assert max(max(p) for _, p in one[:50]) < 100352 - 1
    # a burst: some second of the window holds three times the rate
    assert np.histogram(inside, bins=40, range=(0, 40))[0].max() >= 16


def test_the_step_that_ends_a_prompt_reads_nothing(params):
    """A recurrent-state model's prompt, three chunks long: the last
    logits row goes from the chunk to the slot's state on the device,
    and the first tick is launched behind the chunk unread."""
    prior = metrics.get_registry().enabled
    metrics.set_enabled(True)
    srv = _server(params, _greedy(), num_slots=2, prefill_chunk_pages=2,
                  pool_pages=20)
    try:
        prompt = np.random.default_rng(3).integers(0, 500, 600).tolist()
        ends_a_prompt_without_a_read(srv, prompt)
    finally:
        srv.close()
        metrics.set_enabled(prior)
