"""K-EXAONE-style decoder on the serving path: the module and its
multi-token-prediction block against the plain float32 reference, one
chip's share of the experts against the whole layer, and the slot
server speculating with the model's own block (drafts, hidden states
and the block's cache on the device) against the same server without
speculation. Small sizes on the CPU, seeded random weights, Pallas in
interpret mode.

Tolerances. Everything here runs in float32, where the module and the
reference differ only by the order of their sums: logits of size ~8
agree to 2e-4 (readings: 9e-6 .. 3e-5). ``initializer_range`` 0.2
instead of 0.02 makes the logits large enough that a wrong mask, a
rotated global layer, a norm on the wrong side or a wrong expert moves
them by far more than that (each > 0.05 below). Tokens are compared
exactly: greedy verification commits what the plain tick would.
"""

import dataclasses
import functools
import os

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from _served_rows import ends_a_prompt_without_a_read  # noqa: E402
from paddlefleetx_tpu.core.serving import GenerationServer  # noqa: E402
from paddlefleetx_tpu.core.spec import make_draft_source  # noqa: E402
from paddlefleetx_tpu.models.exaone_moe import (  # noqa: E402
    ExaoneMoeConfig, ExaoneMoeForCausalLM, reference as ref,
)
from paddlefleetx_tpu.models.gpt.generation import (  # noqa: E402
    GenerationConfig,
)
from paddlefleetx_tpu.models.smallthinker import (  # noqa: E402
    SmallThinkerConfig, SmallThinkerForCausalLM,
)
from paddlefleetx_tpu.models.solar_open2.model import (  # noqa: E402
    SharedAndRoutedExperts,
)
from paddlefleetx_tpu.observability import metrics  # noqa: E402
from paddlefleetx_tpu.ops.pallas.grouped_matmul import (  # noqa: E402
    RAGGED_VMEM_BUDGET, _ragged_block_n,
)

TOL = 2e-4          # float32 against float32, sums in another order
PAGE = 128
SIZES = dict(
    hidden_size=64, num_hidden_layers=5, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
    max_position_embeddings=2048, initializer_range=0.2)
#: layers 0-4 in kind: dense + sparse, sliding x3 + full + sliding
CFG = ExaoneMoeConfig(vocab_size=512, experts_held=(0, 8), **SIZES)
#: a vocabulary small enough that the block's drafts are accepted
TINY = ExaoneMoeConfig(vocab_size=8, experts_held=(0, 8), **SIZES)


def _params(cfg):
    return jax.jit(ExaoneMoeForCausalLM(cfg).init)(
        {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def params():
    return _params(CFG)


@pytest.fixture(scope="module")
def tiny_params():
    return _params(TINY)


def _cfg(cfg=CFG, **changes):
    return dict(dataclasses.asdict(cfg), **changes)


@functools.lru_cache(maxsize=None)
def _ref(fn="forward", **changes):
    """A jitted ``reference.<fn>(cfg with changes, params, ids)``."""
    cfg = _cfg(**changes)
    return jax.jit(lambda p, ids: getattr(ref, fn)(cfg, p, ids))


_module = jax.jit(lambda p, ids: ExaoneMoeForCausalLM(CFG).apply(
    {"params": p}, ids, return_mtp=True))


# -- the module against the reference -----------------------------------

def test_layer_kinds_and_page_classes():
    assert [CFG.is_window(i) for i in range(5)] == [1, 1, 1, 0, 1]
    assert [CFG.is_sparse(i) for i in range(5)] == [0, 1, 1, 1, 1]
    assert CFG.window_layers == 4 and CFG.kv_layers == 6
    paged = dataclasses.replace(CFG, kv_page_size=PAGE).window_class(3, 512)
    assert paged.window_ring_pages == 6       # ceil((128 + 512) / 128) + 1
    assert paged.window_pool_pages == 1 + 3 * 6
    # a verify tick's two columns size the ring where no chunk does
    assert dataclasses.replace(
        CFG, kv_page_size=PAGE, prefill_chunk=1).window_ring_pages == 3
    with pytest.raises(ValueError, match="sliding layers take"):
        dataclasses.replace(CFG, sliding_windows=(128, 128, 128, 128) * 12)


@pytest.mark.parametrize("length", [96, 300])
def test_module_matches_the_reference(params, length):
    """Logits of a full forward and of the block, teacher-forced; at
    300 the window (128) is smaller than the sequence."""
    ids = jax.random.randint(jax.random.key(length), (2, length), 0, 512)
    out, block = _module(params, ids)
    want = _ref()(params, ids)
    assert float(jnp.max(jnp.abs(want))) > 3.0
    np.testing.assert_allclose(out, want, atol=TOL, rtol=0)
    want = _ref("mtp_logits")(params, ids)
    assert float(jnp.max(jnp.abs(want))) > 3.0
    np.testing.assert_allclose(block[:, :-1], want, atol=TOL, rtol=0)


def test_a_rotated_full_layer_or_a_lifted_window_is_another_model(params):
    """The reference itself moves when the full layer is rotated (a
    window wider than the sequence rotates and masks nothing), when the
    window is lifted off a sliding layer's mask, or when the head norms
    are dropped: the parity above is not vacuous."""
    ids = jax.random.randint(jax.random.key(5), (1, 300), 0, 512)
    got, _ = _module(params, ids)
    base = _ref()(params, ids)
    np.testing.assert_allclose(got, base, atol=TOL, rtol=0)
    rotated = _ref(sliding_windows=(128, 128, 128, 10 ** 6) * 12)(
        params, ids)
    assert float(jnp.max(jnp.abs(got - rotated))) > 0.05
    wide = _ref(sliding_windows=(10 ** 6, 128, 128, 0) * 12)(params, ids)
    assert float(jnp.max(jnp.abs(got - wide)[0, 200:])) > 0.05
    assert float(jnp.max(jnp.abs(got - wide)[0, :128])) < TOL
    plain = jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.full_like(a, 3.0)
        if any(getattr(k, "key", "") in ("q_norm", "k_norm")
               for k in path) else a, params)
    assert float(jnp.max(jnp.abs(base - _ref()(plain, ids)))) > 0.05


def test_pre_norm_blocks_are_another_model(params):
    """The same weights under pre-norm (``x + f(RMSNorm(x))``) give
    other logits: the module is post-norm, as the reference."""
    ids = jax.random.randint(jax.random.key(6), (1, 64), 0, 512)
    cfg = _cfg()

    @jax.jit
    def pre_norm(params, ids):
        x = jnp.take(params["embed_tokens"], ids, axis=0)
        for i in range(CFG.num_hidden_layers):
            p = params[f"layers_{i}"]
            x = x + ref.attention(cfg, p["self_attn"], ref.rms_norm(
                x, p["post_attention_layernorm"]["scale"], 1e-5),
                CFG.sliding_windows[i])
            u = ref.rms_norm(
                x, p["post_feedforward_layernorm"]["scale"], 1e-5)
            x = x + (ref.experts(cfg, p["mlp"], u) if CFG.is_sparse(i)
                     else ref.gated_mlp(
                         u, p["mlp"]["input_linear"]["kernel"],
                         p["mlp"]["output_linear"]["kernel"]))
        return ref.rms_norm(x, params["norm"]["scale"], 1e-5) \
            @ params["lm_head"]
    got, _ = _module(params, ids)
    assert float(jnp.max(jnp.abs(got - pre_norm(params, ids)))) > 0.05
    np.testing.assert_allclose(got, _ref()(params, ids), atol=TOL, rtol=0)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One sparse layer over 8 chips, 2 of 16 experts each: the routed
    parts summed and the shared expert counted once are the uncut
    reference's layer."""
    whole = dataclasses.replace(CFG, experts_held=None)
    x = jax.random.normal(jax.random.key(2), (3, 40, 64)) * 2.0
    p = SharedAndRoutedExperts(whole).init(jax.random.key(3), x)["params"]
    p = dict(p, e_score_correction_bias=0.1 * jax.random.normal(
        jax.random.key(4), (16,)))
    want = jax.jit(lambda p, x: ref.experts(_cfg(whole), p, x))(p, x)
    shared = ref.gated_mlp(x, p["shared_gate_up"]["kernel"],
                           p["shared_down"]["kernel"])
    total, picks = shared, 0
    for chip in range(8):
        lo, hi = 2 * chip, 2 * chip + 2
        share = dict(p, experts_gate_up=p["experts_gate_up"][lo:hi],
                     experts_down=p["experts_down"][lo:hi])
        out, stats = jax.jit(SharedAndRoutedExperts(dataclasses.replace(
            CFG, experts_held=(lo, hi))).apply)({"params": share}, x)
        if chip in (0, 5):
            np.testing.assert_allclose(
                out, ref.experts(_cfg(), share, x, held=(lo, hi)),
                atol=TOL, rtol=0)
        total = total + (out - shared)
        picks += int(stats[0])
    assert picks == 3 * 40 * 4            # every pick is some chip's
    assert float(jnp.max(jnp.abs(want - shared))) > 0.5
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)


# -- the slot server, speculating with the model's own block -------------

LENGTHS = (700, 40, 300, 9)     # 700 = 6 pages: the ring (4) laps
DEC = 10
POOL = 12           # 11 pages to hand out: two 5-page prompts cannot grow


def _serve(cfg, params, prompts, method, synchronous=False, check=None,
           source=None, **kw):
    """``(completions by submission, summary, counters)`` of one
    server; ``check(srv)`` after every step."""
    prior = metrics.get_registry().enabled
    metrics.set_enabled(True)
    metrics.get_registry().reset()
    eos = cfg.vocab_size - 1
    gen = GenerationConfig(
        max_dec_len=kw.pop("dec", DEC), decode_strategy="greedy_search",
        eos_token_id=kw.pop("eos", eos), pad_token_id=eos,
        spec_method=method, spec_tokens=1)
    srv = GenerationServer(
        ExaoneMoeForCausalLM(cfg), params, gen,
        num_slots=kw.pop("slots", 2), page_size=PAGE,
        prefill_chunk_pages=2, pool_pages=POOL, **kw)
    if synchronous:
        srv._read_now = lambda: "test"
    if source is not None:
        srv._draft = source
    try:
        ids = [srv.submit(p) for p in prompts]
        done = {}
        while srv.work_pending():
            for c in srv.step():
                done[c.request_id] = c
            srv.check_alloc()
            if check is not None:
                check(srv)
        summary = srv.summary()
        assert summary["pages_in_use"] == 0
    finally:
        srv.close()
    counters = dict(metrics.get_registry().snapshot()["counters"])
    metrics.set_enabled(prior)
    return [done[i] for i in ids], summary, counters


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 500, n).tolist() for n in LENGTHS]


@pytest.fixture(scope="module")
def plain(params, prompts):
    return _serve(CFG, params, prompts, None)


@pytest.fixture(scope="module")
def speculative(params, prompts):
    """The same queue with ``spec_method="mtp"``, held to the
    synchronous order so that after every step the device's logits
    belong to the sequence the host knows: each live row's, beside the
    reference's full forward of that sequence."""
    forward = _ref()
    rows = []

    def check(srv):
        logits = np.asarray(srv._state.last_logits)
        for slot, req in enumerate(srv._slots):
            if req is None or not req.get("active"):
                continue
            seq = req["prompt"] + req["tokens"]
            pad = -len(seq) % 256            # a few compiled lengths
            want = np.asarray(forward(
                params, jnp.asarray([seq + [0] * pad])))[0, len(seq) - 1]
            rows.append((len(seq), logits[slot], want))
    out = _serve(CFG, params, prompts, "mtp", synchronous=True,
                 check=check)
    return out + (rows,)


def test_speculative_ticks_commit_the_plain_servers_tokens(
        plain, speculative):
    """Chunked paged prefill of both caches (window 128 under a chunk
    of 256, a 700-token prompt lapping its 4-page ring), then verify
    ticks: every request, two lengths a slot and a re-used slot among
    them, ends with the tokens of the server that does not speculate."""
    assert [(c.tokens, c.finish_reason) for c in speculative[0]] == \
        [(c.tokens, c.finish_reason) for c in plain[0]]
    assert all(len(c.tokens) == DEC for c in plain[0])
    assert plain[0][0].drafts is None
    assert speculative[1]["window_ring_pages"] == 4
    assert speculative[1]["admitted"] == 4 and speculative[1]["slots"] == 2


def test_speculative_logits_match_the_full_forward(speculative):
    """The logits the next token is sampled from, at every step of
    every request, against the reference's full forward pass."""
    rows = speculative[3]
    assert max(n for n, _, _ in rows) >= 700 + DEC - 1
    assert max(float(np.max(np.abs(got - want)))
               for _, got, want in rows) < TOL


def test_served_drafts_are_the_reference_blocks_argmax(params,
                                                       speculative):
    """A ``Completion`` carries every tick's draft; each is the
    reference block's argmax at its position, teacher-forced over what
    was served, wherever that argmax leads by more than the
    tolerance."""
    checked = 0
    for c in speculative[0]:
        assert [i for i, _ in c.drafts] == list(range(1, len(c.drafts) + 1))
        seq = c.prompt + c.tokens
        want = np.asarray(_ref("mtp_logits")(
            params, jnp.asarray([seq + [0] * (-len(seq) % 256)])))[0]
        for i, d in c.drafts:
            if i >= len(c.tokens):
                continue                    # nothing was served after it
            row = want[len(c.prompt) + i - 2]
            top = np.sort(row)[-2:]
            if top[1] - top[0] > 2 * TOL:
                assert d == int(np.argmax(row)), (i, d)
                checked += 1
    assert checked >= 4 * (DEC - 1) - 4


def test_the_source_on_the_device_defers_its_harvest(params, prompts,
                                                     plain, speculative):
    """Nothing of the tick in flight is needed to draft: launches are
    read a step late, as a plain server's, with pages mapped for what
    the unread tick may have committed; the host source's flush is
    counted where it remains."""
    done, summary, c = _serve(CFG, params, prompts, "mtp")
    assert [x.tokens for x in done] == [x.tokens for x in plain[0]]
    assert c["serving/harvest_deferred"] >= summary["decode_ticks"] - 4
    assert c.get("serving/harvest_flushed/spec", 0) == 0
    assert c["serving/spec_source/mtp"] == 1
    assert c["serving/spec_drafted"] + c.get(
        "serving/harvest_rows_void", 0) == c["serving/decode_rows_live"]
    assert c["serving/spec_rollback_columns"] == \
        2 * c["serving/spec_drafted"] - summary["decode_tokens"]
    # every position but each prompt's last is folded by a chunk, every
    # committed token's by the tick after it
    assert c["serving/mtp_positions/prefill"] == sum(LENGTHS) - 4
    assert c["serving/mtp_positions/tick"] == summary["decode_tokens"]
    assert c["serving/prefix_refused_window"] == 4
    # counted where the programs were traced: the first such server
    t = speculative[2]
    assert t["attention/flash_decode_paged_verify"] == CFG.kv_layers
    assert t["attention/mtp_layers"] > 0
    assert t["attention/qk_norm_layers"] > 0
    assert t["attention/window_layers"] > 0 and t["attention/paged_gqa"] > 0
    assert t.get("attention/dense", 0) == 0
    assert not any(k.startswith("attention/fallback/") for k in t)
    _, _, host = _serve(CFG, params, prompts[1:2], "ngram")
    assert host["serving/harvest_flushed/spec"] > 0
    assert host.get("serving/harvest_deferred", 0) == 0


class Oracle:
    """A host draft source that knows the answer: the continuation a
    plain server gave."""

    def __init__(self, answers):
        self.answers = answers

    def propose(self, history, k):
        for prompt, tokens in self.answers:
            if list(history[:len(prompt)]) == prompt:
                at = len(history) - len(prompt) + 1
                return (tokens + [0] * (k + 1))[at:at + k]
        raise AssertionError("a history no request has")


def test_oracle_drafts_commit_two_a_tick_and_stay_exact(params, prompts,
                                                        plain):
    """The draft ARRAY the host fills (the same verify program, its
    drafts from outside): with the plain server's own continuation as
    the draft every tick commits 2, through window rings and global
    pages alike."""
    done, summary, c = _serve(
        CFG, params, prompts, "ngram",
        source=Oracle([(x.prompt, x.tokens) for x in plain[0]]))
    assert [x.tokens for x in done] == [x.tokens for x in plain[0]]
    assert summary["decode_tokens"] == 4 * DEC
    assert summary["decode_ticks"] < plain[1]["decode_ticks"] * 0.6
    assert c["serving/spec_accepted"] == c["serving/spec_drafted"] == \
        4 * DEC // 2
    assert c["serving/spec_rollback_columns"] == 0


def test_own_drafts_are_accepted_now_and_then(tiny_params):
    """Over a vocabulary of 8 the block's argmax and the model's agree
    now and then: rows advance by different counts, accepted drafts are
    the tokens the plain server commits, a request ends on ``eos``
    inside a verify window, and a budget that an accepted draft fills a
    tick early ends the request all the same."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 7, n).tolist() for n in (150, 30, 260, 12)]
    want, want_sum, _ = _serve(TINY, tiny_params, prompts, None, dec=24,
                               eos=3)
    got, summary, c = _serve(TINY, tiny_params, prompts, "mtp", dec=24,
                             eos=3)
    assert [(x.tokens, x.finish_reason) for x in got] == \
        [(x.tokens, x.finish_reason) for x in want]
    assert {x.finish_reason for x in want} == {"eos", "length"}
    assert 0 < c["serving/spec_accepted"] < c["serving/spec_drafted"]
    assert summary["decode_ticks"] < want_sum["decode_ticks"]
    for x in got:
        accepted = [x.tokens[i] for i, d in x.drafts
                    if i < len(x.tokens) and x.tokens[i] == d]
        assert len(accepted) >= len(x.tokens) - len(x.drafts)


def test_a_preempted_request_prefills_both_caches_again(params, prompts,
                                                        plain):
    """Two prompts that end four tokens short of their fifth page's
    end, in a pool with one page to spare: each wants a sixth page a
    few ticks in, the other request is preempted for it and
    re-admitted, its prompt and its tokens prefilled into the model's
    cache and the block's, and ends as it would have."""
    rng = np.random.default_rng(2)
    pair = [rng.integers(0, 500, 5 * PAGE - 4).tolist() for _ in range(2)]
    done, summary, _ = _serve(CFG, params, pair, "mtp")
    want, want_sum, _ = _serve(CFG, params, pair, None)
    assert summary["preempted"] >= 1 and want_sum["preempted"] >= 1
    assert [x.tokens for x in done] == [x.tokens for x in want]
    assert all(x.drafts[-1][0] >= len(x.tokens) - 1 for x in done)


def test_mtp_is_refused_where_the_model_has_no_block(params):
    gen = GenerationConfig(max_dec_len=4, decode_strategy="greedy_search",
                           spec_method="mtp", spec_tokens=1)
    small = SmallThinkerConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_ffn_hidden_size=16, moe_num_primary_experts=4,
        moe_num_active_primary_experts=2, max_position_embeddings=512)
    model = SmallThinkerForCausalLM(small)
    with pytest.raises(ValueError, match="multi-token-prediction block"):
        make_draft_source("mtp", model=model)
    with pytest.raises(ValueError, match="multi-token-prediction block"):
        GenerationServer(model, model.init(
            {"params": jax.random.key(0)},
            jnp.zeros((1, 8), jnp.int32))["params"], gen, num_slots=2,
            page_size=PAGE, pool_pages=POOL)
    bare = dataclasses.replace(CFG, num_nextn_predict_layers=0)
    with pytest.raises(ValueError, match="multi-token-prediction block"):
        GenerationServer(ExaoneMoeForCausalLM(bare), params, gen,
                         num_slots=2, page_size=PAGE, pool_pages=POOL)
    with pytest.raises(ValueError, match="spec_tokens"):
        GenerationServer(
            ExaoneMoeForCausalLM(CFG), params,
            dataclasses.replace(gen, spec_tokens=2), num_slots=2,
            page_size=PAGE, pool_pages=POOL)
    with pytest.raises(ValueError, match="one tick a launch"):
        GenerationServer(ExaoneMoeForCausalLM(CFG), params, gen,
                         num_slots=2, page_size=PAGE, pool_pages=POOL,
                         device_loop_ticks=2)


# -- the grouped product at this family's width ---------------------------

@pytest.mark.parametrize("block_m,k,n,want", [
    (128, 6144, 4096, 256),     # a chunk's tiles: 16.7 MB at 512
    (32, 6144, 4096, 512),      # a tick's tiles
    (128, 2048, 6144, 512),     # the down product
    (128, 4096, 2560, 512),     # Solar-Open2's chunk
    (128, 2048, 2816, 256),     # Kanana's training step (2816 = 11 x 256)
])
def test_ragged_columns_fit_the_scoped_vmem(block_m, k, n, want):
    bn = _ragged_block_n(block_m, k, n, 512, 2)
    assert bn == want and n % bn == 0
    assert bn == 128 or 4 * (block_m * k + k * bn + block_m * bn) \
        <= RAGGED_VMEM_BUDGET


def test_the_step_that_ends_a_prompt_reads_nothing(params, prompts):
    """A source on the device: the chunk that ends a prompt hands its
    last logits row to the slot's state and its hidden rows to the
    block without the host reading either, and the first verify tick
    goes down behind it."""
    prior = metrics.get_registry().enabled
    metrics.set_enabled(True)
    eos = CFG.vocab_size - 1
    srv = GenerationServer(
        ExaoneMoeForCausalLM(CFG), params, GenerationConfig(
            max_dec_len=DEC, decode_strategy="greedy_search",
            eos_token_id=eos, pad_token_id=eos, spec_method="mtp",
            spec_tokens=1),
        num_slots=2, page_size=PAGE, prefill_chunk_pages=2,
        pool_pages=POOL)
    try:
        assert srv._read_now() is None
        ends_a_prompt_without_a_read(srv, prompts[2])   # two chunks
    finally:
        srv.close()
        metrics.set_enabled(prior)
