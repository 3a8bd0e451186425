"""SmallThinker-style decoder on the serving path: the module against
its plain float32 reference, the paged kernels at grouped-query heads
and a sliding window against ``jax.numpy`` oracles, the two page
classes of the slot server, and the expert layer routed before
attention. Small sizes on the CPU, seeded random weights, Pallas in
interpret mode.

Tolerances. Everything here runs in float32, where the module and the
reference differ only by the order of their sums: logits of size ~7
agree to 2e-4 (readings: 2e-5 .. 6e-5). ``initializer_range`` 0.2
instead of 0.02 makes the logits large enough that a wrong mask, a
wrong page or a wrong expert moves them by far more than that.
"""

import dataclasses
import os

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from _served_rows import ServedRows  # noqa: E402
from paddlefleetx_tpu.core.paging import (  # noqa: E402
    NULL_PAGE, kv_page_bytes, pool_bytes, pool_pages_for_bytes,
)
from paddlefleetx_tpu.core.serving import GenerationServer  # noqa: E402
from paddlefleetx_tpu.models.deepseek_v3.moe import (  # noqa: E402
    block_rows, routed_experts,
)
from paddlefleetx_tpu.models.gpt.generation import (  # noqa: E402
    GenerationConfig,
)
from paddlefleetx_tpu.models.smallthinker import (  # noqa: E402
    SmallThinkerConfig, SmallThinkerForCausalLM, reference as ref,
)
from paddlefleetx_tpu.models.smallthinker.model import (  # noqa: E402
    window_table,
)
from paddlefleetx_tpu.observability import metrics  # noqa: E402
from paddlefleetx_tpu.ops import attention as attn  # noqa: E402
from paddlefleetx_tpu.ops.pallas import flash_attention as fa  # noqa: E402

TOL = 2e-4          # float32 against float32, sums in another order
WINDOW, PAGE = 160, 128

CFG = SmallThinkerConfig(
    vocab_size=512, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=14, num_key_value_heads=2, head_dim=16,
    moe_ffn_hidden_size=32, moe_num_primary_experts=8,
    moe_num_active_primary_experts=3, sliding_window_size=WINDOW,
    max_position_embeddings=2048, initializer_range=0.2)


@pytest.fixture(scope="module")
def params():
    return SmallThinkerForCausalLM(CFG).init(
        {"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def ref_forward():
    return jax.jit(lambda p, ids: ref.forward(
        dataclasses.asdict(CFG), p, ids))


# -- the module against the reference -----------------------------------

def test_layer_pattern_has_both_kinds():
    assert CFG.rope_layout[:4] == (0, 1, 1, 1)
    assert CFG.sliding_window_layout[:4] == (0, 1, 1, 1)
    assert CFG.window_layers == 3 and CFG.num_kv_heads == 2


@pytest.mark.parametrize("length", [96, 300])
def test_module_matches_the_reference(params, ref_forward, length):
    """Logits of a full forward; at 300 the window (160) is smaller than
    the sequence, and a NoPE-global and three RoPE-window layers are all
    present."""
    ids = jax.random.randint(jax.random.key(length), (2, length), 0, 512)
    out = SmallThinkerForCausalLM(CFG).apply({"params": params}, ids)
    want = ref_forward(params, ids)
    assert float(jnp.max(jnp.abs(want))) > 3.0
    np.testing.assert_allclose(out, want, atol=TOL, rtol=0)


def test_the_window_and_the_positions_matter(params):
    """The reference itself moves when the window is lifted or RoPE is
    taken off: the parity above is not vacuous."""
    ids = jax.random.randint(jax.random.key(5), (1, 300), 0, 512)
    cfg = dataclasses.asdict(CFG)
    base = ref.forward(cfg, params, ids)
    wide = ref.forward(dict(cfg, sliding_window_size=4096), params, ids)
    nope = ref.forward(dict(cfg, rope_layout=(0,) * 52), params, ids)
    assert float(jnp.max(jnp.abs(base - wide)[0, 200:])) > 0.05
    assert float(jnp.max(jnp.abs(base - wide)[0, :WINDOW])) < TOL
    assert float(jnp.max(jnp.abs(base - nope))) > 0.05


# -- the paged decode kernel: grouped heads, the window's walk ----------

def _decode_case(groups, heads, d=16, slots=5, pages=6, window=1, seed=0,
                 dtype=jnp.float32):
    """Slot 0 walks one block, slot 3 is free between live ones, slot 2
    ends on the table's last key."""
    rng = np.random.default_rng(seed)
    pool = 1 + slots * pages
    k = jnp.asarray(rng.normal(size=(pool, groups, d, PAGE)), dtype)
    v = jnp.asarray(rng.normal(size=(pool, groups, d, PAGE)), dtype)
    q = jnp.asarray(rng.normal(size=(slots, window, heads, d)), dtype)
    table = 1 + np.arange(slots * pages, dtype=np.int32).reshape(
        slots, pages)
    rng.shuffle(table.reshape(-1))
    table[3] = NULL_PAGE                      # a free slot
    offs = np.array([0, 200, 767 - window, 50, 383], np.int32)
    return q, k, v, jnp.asarray(offs), jnp.asarray(table)


def _dense_oracle(q, k, v, offs, table, reach):
    """The gather path in float32, whatever the operands came in."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    return attn._xla_attention(
        q, attn._gather_kv_pages(k, table), attn._gather_kv_pages(v, table),
        None, True, offs, 0.0, None, True, True, kv_cache_layout=True,
        sliding_window=reach)


@pytest.mark.parametrize("window", [1, 3, 5])
@pytest.mark.parametrize("reach", [None, WINDOW, 128, 1])
@pytest.mark.parametrize("groups,heads", [
    (4, 4), (2, 14), (2, 16), (4, 28), (8, 64)])
def test_flash_decode_paged_gqa_window(groups, heads, reach, window):
    """Interpret mode against the dense oracle: one query head a K/V
    head (the GPT shape, the VPU form) and 7 or 8 a head over 2, 4 and
    8 pooled heads (the two served shapes; the MXU form); no window, a
    window that starts inside a block (its first block masked within),
    one of exactly a block, one of a single key; verify windows of 3
    and 5, whose positions are rows of one product."""
    q, k, v, offs, table = _decode_case(groups, heads, window=window)
    got = fa.flash_decode_paged(q, k, v, offs, table, reach=reach)
    want = _dense_oracle(q, k, v, offs, table, reach)
    live = np.asarray(table[:, 0] != NULL_PAGE)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=0)
    assert not np.asarray(got)[~live].any()     # a dead row reads zeros


@pytest.mark.parametrize("window", [1, 5])
@pytest.mark.parametrize("reach", [None, WINDOW])
@pytest.mark.parametrize("groups,heads", [(2, 14), (4, 28), (8, 64)])
def test_flash_decode_paged_gqa_bfloat16_pool(groups, heads, reach, window):
    """The served dtype: bfloat16 queries against a bfloat16 pool, the
    operands of both products as they stand. Against the float32 oracle
    on the same values nothing is left but the output's own rounding to
    bfloat16 (half an ulp: at most 2**-8 of the value)."""
    q, k, v, offs, table = _decode_case(groups, heads, d=128, window=window,
                                        dtype=jnp.bfloat16)
    got = fa.flash_decode_paged(q, k, v, offs, table, reach=reach)
    assert got.dtype == jnp.bfloat16
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(_dense_oracle(q, k, v, offs, table, reach))
    live = np.asarray(table[:, 0] != NULL_PAGE)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5,
                               rtol=2.0 ** -8)
    assert not got[~live].any()


@pytest.mark.parametrize("window", [1, 5])
def test_flash_decode_paged_gqa_int8_pool(window):
    """Grouped heads over an int8 pool: dequantised in VMEM, float32
    products."""
    q, k, v, offs, table = _decode_case(2, 14, window=window)
    rng = np.random.default_rng(3)
    k8, v8 = (jnp.asarray(rng.integers(-127, 128, k.shape), jnp.int8)
              for _ in range(2))
    ks, vs = (jnp.asarray(rng.uniform(0.002, 0.02, k.shape[:2] + (1, PAGE)),
                          jnp.float32) for _ in range(2))
    got = fa.flash_decode_paged(q, k8, v8, offs, table, k_scale=ks,
                                v_scale=vs, reach=WINDOW)
    want = _dense_oracle(q, k8.astype(jnp.float32) * ks,
                         v8.astype(jnp.float32) * vs, offs, table, WINDOW)
    live = np.asarray(table[:, 0] != NULL_PAGE)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5, rtol=0)
    assert not np.asarray(got)[~live].any()


@pytest.mark.parametrize("rows", [8, 24, 40])
def test_group_pv_keeps_the_probabilities_24_bits(rows):
    """``p`` against a bfloat16 V block: the three-part split is exact
    (``hi + mid + lo == p`` bit for bit, each part a bfloat16), so the
    product differs from float64's by float32's sums alone; one cast of
    ``p`` to bfloat16, which this is not, would be off by 2**-9 or so."""
    rng = np.random.default_rng(rows)
    p = jnp.asarray(rng.uniform(0, 1, (2, rows, PAGE)) *
                    10.0 ** rng.uniform(-6, 0, (2, rows, 1)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 16, PAGE)), jnp.bfloat16)
    hi = p.astype(jnp.bfloat16).astype(jnp.float32)
    mid = (p - hi).astype(jnp.bfloat16).astype(jnp.float32)
    lo = p - hi - mid
    assert jnp.array_equal(lo.astype(jnp.bfloat16).astype(jnp.float32), lo)
    assert jnp.array_equal(hi + (mid + lo), p)
    want = np.einsum("grk,gdk->grd", np.asarray(p, np.float64),
                     np.asarray(v.astype(jnp.float32), np.float64))
    scale = np.abs(want).max(axis=(1, 2), keepdims=True)
    got = np.asarray(fa._group_pv(p, v), np.float64)
    assert np.max(np.abs(got - want) / scale) < 2e-6
    cast = np.einsum("grk,gdk->grd", np.asarray(
        p.astype(jnp.bfloat16).astype(jnp.float32), np.float64),
        np.asarray(v.astype(jnp.float32), np.float64))
    assert np.max(np.abs(cast - want) / scale) > 2e-4


@pytest.mark.parametrize("reach", [None, WINDOW])
def test_paged_walk_starts_at_the_windows_first_block(reach):
    offs = jnp.asarray([0, 200, 700, 50, 383], jnp.int32)
    table = jnp.ones((5, 6), jnp.int32).at[3].set(NULL_PAGE)
    rows, blocks, steps = fa._paged_walk(offs, table, 1, PAGE, 6, reach)
    rows, blocks = (np.asarray(a)[:int(steps)] for a in (rows, blocks))
    for slot, off in enumerate(np.asarray(offs)):
        mine = blocks[rows == slot]
        if slot == 3:
            assert mine.size == 0
            continue
        first = 0 if reach is None else max(0, (off + 1 - reach) // PAGE)
        assert list(mine) == list(range(first, off // PAGE + 1))


@pytest.mark.parametrize("reach", [None, WINDOW])
@pytest.mark.parametrize("groups,heads", [(2, 2), (2, 14)])
def test_paged_prefill_walk_matches_the_dense_gather(groups, heads, reach):
    """A chunk's queries through the page table in spans, against the
    whole-table gather; with a window no page behind it is read (the
    pages there hold NaN)."""
    rng = np.random.default_rng(1)
    d, pages, chunk = 16, 24, 256
    k = jnp.asarray(rng.normal(size=(1 + 2 * pages, groups, d, PAGE)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=k.shape), jnp.float32)
    table = jnp.asarray(1 + np.arange(2 * pages, dtype=np.int32).reshape(
        2, pages))
    starts = jnp.asarray([0, 2304], jnp.int32)
    q = jnp.asarray(rng.normal(size=(2, chunk, heads, d)), jnp.float32)
    want = attn._xla_attention(
        q, attn._gather_kv_pages(k, table), attn._gather_kv_pages(v, table),
        None, True, starts, 0.0, None, True, True, kv_cache_layout=True,
        sliding_window=reach)
    if reach is not None:
        behind = np.asarray(table)[1, :(2304 + 1 - reach) // PAGE]
        k = k.at[behind].set(jnp.nan)
        v = v.at[behind].set(jnp.nan)
    got = attn.paged_prefill_attention(q, k, v, starts, table,
                                       sliding_window=reach)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# -- the expert layer ---------------------------------------------------

@pytest.mark.parametrize("rows,live", [(48, None), (48, 20), (256, None)])
def test_grouped_experts_match_every_expert_dense(rows, live):
    """Softmax over the picked logits and ``relu(g) * u`` through the
    dropless lowering, against every expert computed for every row; a
    decode tick's dead rows (their picks on no expert) add nothing."""
    rng = np.random.default_rng(rows)
    h, f, e, k = 64, 32, 8, 3
    x = jnp.asarray(rng.normal(size=(rows, h)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(rows, e)), jnp.float32)
    gate_up = jnp.asarray(rng.normal(size=(e, h, 2 * f)) * 0.2, jnp.float32)
    down = jnp.asarray(rng.normal(size=(e, f, h)) * 0.2, jnp.float32)
    picked, idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(picked, axis=-1)
    alive = np.ones(rows, bool)
    if live is not None:
        alive[live:] = False
        idx = jnp.where(jnp.asarray(alive)[:, None], idx, e)
    got, plan = routed_experts(x, idx, weights, gate_up, down, 0, e,
                               activation=jax.nn.relu)
    want = ref.experts({"moe_num_active_primary_experts": k,
                        "moe_num_primary_experts": e},
                       {"experts_gate_up": gate_up, "experts_down": down},
                       x, logits)
    want = jnp.where(jnp.asarray(alive)[:, None], want, 0.0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert int(jnp.sum(plan["sizes"])) == k * int(alive.sum())


def test_tile_rows_follow_the_mean_group():
    assert block_rows(48 * 6, 64) == 16       # a decode tick
    assert block_rows(256 * 6, 64) == 32      # a prefill chunk
    assert block_rows(16384 * 6, 16) == 128   # the Kanana training step


def test_the_router_reads_the_layers_input(params):
    """Moving the router's logits to the post-attention stream changes
    the output: the module follows the reference, which routes before
    attention and before the norm."""
    ids = jax.random.randint(jax.random.key(9), (1, 64), 0, 512)
    cfg = dataclasses.asdict(CFG)
    want = ref.forward(cfg, params, ids)

    def late_layer(cfg, p, x, index):
        eps = cfg["rms_norm_eps"]
        x = x + ref.attention(cfg, p["self_attn"], ref.rms_norm(
            x, p["input_layernorm"]["scale"], eps), index)
        u = ref.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
        return x + ref.experts(cfg, p["block_sparse_moe"], u,
                               x @ p["router"])
    x = jnp.take(params["embed_tokens"], ids, axis=0)
    for i in range(CFG.num_hidden_layers):
        x = late_layer(cfg, params[f"layers_{i}"], x, i)
    late = ref.rms_norm(x, params["norm"]["scale"], 1e-6) @ params["lm_head"]
    assert float(jnp.max(jnp.abs(late - want))) > 0.05
    got = SmallThinkerForCausalLM(CFG).apply({"params": params}, ids)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


# -- two page classes in one server --------------------------------------

LENGTHS = (1500, 40, 700, 9)      # 1500 = 12 pages: the ring (5) wraps twice
DEC = 6


@pytest.fixture(scope="module")
def served(params, ref_forward):
    """One server, short and long prompts in one queue, driven through
    ``submit`` / ``step``; after every step the logits the next token is
    sampled from, beside the reference's full forward of the same
    sequence, and the page accounting."""
    prior = metrics.get_registry().enabled
    metrics.set_enabled(True)
    metrics.get_registry().reset()
    gen = GenerationConfig(max_dec_len=DEC, decode_strategy="greedy_search",
                           eos_token_id=511, pad_token_id=511)
    srv = GenerationServer(SmallThinkerForCausalLM(CFG), params, gen,
                           num_slots=3, page_size=PAGE,
                           prefill_chunk_pages=2, pool_pages=40)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, n).tolist() for n in LENGTHS]
    ids = [srv.submit(p) for p in prompts]
    steps, done, most_global = [], {}, 0
    rows = ServedRows(srv)
    while srv.work_pending():
        for c in srv.step():
            done[c.request_id] = c
        srv.check_alloc()
        for req, seq, got in rows.after_step():
            pad = -len(seq) % 512            # a few compiled lengths
            want = np.asarray(ref_forward(
                params, jnp.asarray([seq + [0] * pad])))[0, len(seq) - 1]
            steps.append((len(seq), got, want))
        most_global = max([most_global] + [
            req["num_pages"] for req in srv._slots if req is not None])
    out = dict(srv=srv, ids=ids, prompts=prompts, steps=steps, done=done,
               most_global=most_global, summary=srv.summary(),
               counters=dict(metrics.get_registry().snapshot()["counters"]))
    metrics.set_enabled(prior)
    yield out
    srv.close()


def test_served_logits_match_the_full_forward(served):
    """Chunked paged prefill, then decode through the pool, against the
    reference's full forward pass: logits, at every step of every
    request, the 1500-token one past two laps of its ring."""
    assert max(n for n, _, _ in served["steps"]) >= 1500 + DEC - 1
    worst = max(float(np.max(np.abs(got - want)))
                for _, got, want in served["steps"])
    assert worst < TOL, worst


def test_short_and_long_finish_with_the_references_argmax(served):
    """Wherever the reference's best logit leads its second by more
    than the tolerance, the served token is that one."""
    checked = 0
    for rid, prompt in zip(served["ids"], served["prompts"]):
        c = served["done"][rid]
        assert c.finish_reason in ("length", "eos")
        assert len(c.tokens) == DEC or c.finish_reason == "eos"
    for n, got, want in served["steps"]:
        top2 = np.sort(want)[-2:]
        if top2[1] - top2[0] > 2 * TOL:
            assert int(np.argmax(got)) == int(np.argmax(want))
            checked += 1
    assert checked >= len(served["steps"]) // 2


def test_window_layers_hold_their_ring_and_global_layers_the_sequence(
        served):
    cfg = served["srv"].model.config
    assert cfg.window_ring_pages == 5         # ceil((160 + 256) / 128) + 1
    assert cfg.window_pool_pages == 1 + 3 * 5
    # a slot at 3x the ring's tokens held whole sequences on the global
    # class (12 pages) and, by the pool's size, at most its ring on the
    # window class
    assert served["most_global"] == -(-(1500 + DEC) // PAGE)
    cache = served["srv"]._cache
    leaves = jax.tree_util.tree_leaves_with_path(cache)
    shapes = {getattr(p[-1], "key", ""): leaf.shape for p, leaf in leaves}
    assert shapes["cached_key"] == (40, 2, 16, PAGE)
    assert shapes["window_key"] == (16, 2, 16, PAGE)
    kinds = [getattr(p[-1], "key", "") for p, _ in leaves]
    assert kinds.count("cached_key") == 1 and kinds.count("window_key") == 3


def test_pages_are_returned_on_finish(served):
    srv = served["srv"]
    srv.check_alloc()
    assert served["summary"]["pages_in_use"] == 0
    assert srv._alloc.free_pages == 39
    assert (srv._pt == NULL_PAGE).all()


def test_page_class_counters(served):
    c = served["counters"]
    assert c["attention/paged_gqa"] > 0 and c["attention/window_layers"] > 0
    assert c["attention/flash_decode_paged"] > 0
    assert c["attention/paged_prefill_walk"] > 0
    assert c.get("attention/dense", 0) == 0     # no dense fallback served
    assert c["serving/kv_blocks_walked"] < c["serving/kv_blocks_whole"]
    # three window layers of four: held pages stop at the ring
    assert c["serving/pages_window_held"] < 3 * c["serving/pages_global_held"]
    assert c["serving/window_pages_reused"] >= 3 * (12 - 5)
    s = served["summary"]
    assert s["window_ring_pages"] == 5
    assert s["pool_bytes"] == pool_bytes(1, 2, 16, PAGE, 40)
    assert s["window_pool_bytes"] == pool_bytes(3, 2, 16, PAGE, 16)


def test_decode_ticks_dispatch_six_picks_a_live_row(served):
    c, s = served["counters"], served["summary"]
    live = c["serving/decode_rows_live"]
    assert s["moe_decode_picks"] == 3 * CFG.num_hidden_layers * live
    assert 0 < s["moe_experts_touched"] <= s["moe_decode_picks"]
    assert s["moe_prefill_picks"] == 3 * CFG.num_hidden_layers * 256 * \
        c["serving/prefill_chunks"]
    assert c["moe/decode_picks"] == s["moe_decode_picks"]


def test_a_prefix_hit_is_refused_on_a_model_with_window_layers(served):
    """The ring is a slot's own, so the global class alone could not
    serve a hit: nothing is registered or shared, and every admission
    that would have looked a prefix up is counted."""
    assert served["summary"]["prefix_refused_window"] is True
    assert served["counters"]["serving/prefix_refused_window"] == len(LENGTHS)
    assert served["summary"]["prefix_hits"] == 0
    assert served["summary"]["prompt_hits"] == 0
    assert not served["srv"]._prefix_sharing


def test_window_table_is_the_ring_under_the_global_table():
    cfg = dataclasses.replace(CFG, kv_page_size=PAGE,
                              kv_pool_pages=40).window_class(3, 256)
    glob = np.full((2, cfg.max_kv_pages), NULL_PAGE, np.int32)
    glob[0, :7] = [9, 3, 4, 8, 2, 7, 5]
    ring = np.array([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10]], np.int32)
    g, w = window_table(jnp.asarray(np.concatenate([glob, ring], 1)), cfg)
    assert (np.asarray(g) == glob).all()
    assert list(np.asarray(w)[0, :8]) == [1, 2, 3, 4, 5, 1, 2, NULL_PAGE]
    assert (np.asarray(w)[1] == NULL_PAGE).all()


def test_the_family_is_served_paged_only(params):
    with pytest.raises(NotImplementedError, match="paged"):
        SmallThinkerForCausalLM(CFG).apply(
            {"params": params}, jnp.zeros((1, 4), jnp.int32),
            use_cache=True, mutable=["cache"])


# -- pool sizing by K/V heads ---------------------------------------------

def test_pool_bytes_count_the_pooled_heads():
    """A grouped-query pool is sized by its K/V heads; a multi-head
    model passes the count it always passed."""
    from paddlefleetx_tpu.models.gpt import GPTConfig
    gpt = GPTConfig(hidden_size=1024, num_attention_heads=16)
    assert gpt.num_kv_heads == 16
    assert kv_page_bytes(gpt.num_kv_heads, 64, 128) == 16 * 64 * 2 * 128
    assert pool_bytes(24, 16, 64, 128, 513) == 6455033856   # the GPT cell's
    full = SmallThinkerConfig()
    assert full.num_kv_heads == 4
    assert 2 * kv_page_bytes(4, 128, 128) == 256 * 1024     # a K+V page
    assert pool_pages_for_bytes(pool_bytes(2, 4, 128, 128, 2881),
                                2, 4, 128, 128) == 2881
    assert dataclasses.replace(full, kv_page_size=128).window_class(
        48, 256).window_ring_pages == 35
