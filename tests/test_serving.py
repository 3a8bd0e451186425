"""GenerationServer: slot-for-slot parity vs lockstep ``generate()``.

The acceptance bar for the continuous-batching path: greedy
completions out of the server must equal the lockstep rows EXACTLY —
whatever the slot count, admission order, or prompt-length mix — and
the parity matrix below pins it. Interpret mode
(``PFX_PALLAS_INTERPRET=1``) lets the smoke test drive the ragged
Pallas kernel on CPU; the rest of the suite runs the XLA per-row
fallback (same masking, the kernels' oracle).
"""

import dataclasses
import json
import os
import re
import threading
import urllib.error
import urllib.request

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.core.paging import pool_bytes
from paddlefleetx_tpu.core.serving import (
    SLOW_STEP_SECONDS, GenerationServer, RequestShed,
    default_prefill_buckets,
)
from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddlefleetx_tpu.models.gpt.generation import (
    GenerationConfig, generate, left_pad_batch,
)
from paddlefleetx_tpu.observability import metrics
from paddlefleetx_tpu.observability import server as obs_server
from paddlefleetx_tpu.observability.recorder import read_events

CFG = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                num_attention_heads=4, max_position_embeddings=48,
                hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0)
EOS = PAD = 95

# mixed prompt lengths: spans multiple prefill buckets, includes a
# length-1 prompt and dupes (two requests may share a slot history)
PROMPTS = [[5, 9, 2, 7, 1], [11, 3], [4, 4, 8, 1, 2, 6, 9],
           [13, 2, 2], [1], [7, 8]]


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTForPretraining(CFG)
    variables = model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32))
    return model, variables["params"]


def _greedy_cfg(max_dec=8):
    return GenerationConfig(max_dec_len=max_dec,
                            decode_strategy="greedy_search",
                            eos_token_id=EOS, pad_token_id=PAD)


def _snapshot(cache):
    """A copy of the cache tree a test may hand to a cache jit and
    still hold the original: ``decode_step`` / ``verify_step`` /
    ``decode_loop`` ... DONATE their cache argument (the in-place KV
    write, docs/inference.md), so an array passed to one is deleted —
    on the CPU too."""
    return jax.tree.map(jnp.copy, cache)


def _harvest(harvest, ticks=1, k=0):
    """A tick program's third output, unpacked on the host (the slot
    count follows from the array's length and the static ``T, k``)."""
    from paddlefleetx_tpu.models.gpt.generation import unpack_harvest
    flat = np.asarray(harvest)
    slots = (flat.size - 2) // (ticks * (k + 2) + 2)
    return unpack_harvest(flat, slots, ticks, k)


def _lockstep(model, params, prompts, gen_cfg):
    """Reference rows from the lockstep path, truncated at EOS
    (inclusive) — exactly what a Completion.tokens should hold."""
    ids, mask = left_pad_batch(prompts, PAD)
    out = np.asarray(generate(model, params, jnp.asarray(ids),
                              jnp.asarray(mask), jax.random.key(0),
                              gen_cfg))
    rows = []
    for row in out:
        toks = []
        for t in row:
            toks.append(int(t))
            if int(t) == EOS:
                break
        rows.append(toks)
    return rows


@pytest.mark.parametrize("num_slots,order", [
    (1, list(range(6))),            # fully sequential
    (2, list(range(6))),            # staggered turnover
    (2, [5, 4, 3, 2, 1, 0]),        # reversed admission
    (3, [2, 0, 4, 1, 5, 3]),        # shuffled admission
    (6, list(range(6))),            # everything admitted at once
])
def test_parity_matrix_greedy(model_and_params, num_slots, order):
    """The parity matrix: for every (slot count, admission order)
    cell, each request's served completion equals its lockstep row —
    slot assignment, bucket choice, and neighbors must be invisible."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(model, params, gen_cfg,
                           num_slots=num_slots)
    prompts = [PROMPTS[i] for i in order]
    comps = srv.run(prompts)
    assert [c.tokens for c in comps] == [ref[i] for i in order]
    assert all(c.finish_reason in ("eos", "length") for c in comps)


def test_mid_run_admission_parity(model_and_params):
    """Requests submitted while the server is mid-decode (slots at
    ragged depths) still complete to their lockstep rows — the
    write-before-read slot reuse and per-row masking at work."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2)
    done = {}
    ids = [srv.submit(p) for p in PROMPTS[:2]]
    for _ in range(3):                      # decode a few ticks first
        for c in srv.step():
            done[c.request_id] = c
    ids += [srv.submit(p) for p in PROMPTS[2:]]
    while srv.pending or srv.occupancy:
        for c in srv.step():
            done[c.request_id] = c
    got = [done[i].tokens for i in ids]
    assert got == ref


def test_sampling_is_slot_and_order_independent(model_and_params):
    """Sampled completions are a function of (server rng, submission
    index), not of slot assignment or admission timing: the same
    trace served with 1 slot and 3 slots draws identical tokens."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_dec_len=6,
                               decode_strategy="sampling",
                               top_k=8, top_p=0.9, temperature=0.7,
                               eos_token_id=EOS, pad_token_id=PAD)
    runs = []
    for num_slots in (1, 3):
        srv = GenerationServer(model, params, gen_cfg,
                               num_slots=num_slots,
                               rng=jax.random.key(5))
        runs.append([c.tokens for c in srv.run(PROMPTS[:4])])
    assert runs[0] == runs[1]


def test_serving_smoke_interpret_kernel(model_and_params, tmp_path):
    """CI smoke (`-k smoke`): 3 staggered mixed-length requests over
    2 slots with the RAGGED PALLAS KERNEL in interpret mode, flight
    recorder on. Pins that the kernel path (not just the XLA
    fallback) carries the server, and that the events.jsonl trail CI's
    failure-diagnostics artifact collects is written."""
    _, params = model_and_params
    kcfg = GPTConfig(**{**CFG.__dict__, "use_flash_attention": True})
    model = GPTForPretraining(kcfg)
    gen_cfg = _greedy_cfg(max_dec=4)
    ref = _lockstep(model, params, PROMPTS[:3], gen_cfg)
    events = tmp_path / "events.jsonl"
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               events_path=str(events))
        comps = srv.run(PROMPTS[:3])
        assert [c.tokens for c in comps] == ref
        assert reg.counter("attention/flash_decode_ragged") >= 1
        assert reg.counter("serving/admitted") == 3
        assert reg.counter("serving/evicted") == 3
        assert reg.gauge("serving/slot_occupancy") == 0
        assert reg.counter("serving/device_ticks") == \
            srv.summary()["decode_ticks"]
        kinds = [json.loads(l)["event"] for l in
                 events.read_text().splitlines()]
        assert kinds[0] == "serving_start"
        assert "serving_admit" in kinds and "serving_evict" in kinds
        summ = srv.summary()
        assert summ["tokens_per_sec"] > 0
        assert summ["decode_tokens"] == sum(
            len(c.tokens) for c in comps)
        kinds = [json.loads(l)["event"] for l in
                 events.read_text().splitlines()]
        assert kinds[-1] == "serving_summary"
    finally:
        metrics.set_enabled(False)
        reg.reset()


def test_preempt_returns_partial_and_frees_slot(model_and_params):
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    srv = GenerationServer(model, params, gen_cfg, num_slots=1)
    a = srv.submit(PROMPTS[0])
    b = srv.submit(PROMPTS[1])     # queued behind a
    srv.step()
    srv.step()
    part = srv.preempt(a)
    assert part.request_id == a
    assert part.finish_reason == "preempted"
    assert len(part.tokens) == 2
    assert srv.preempt(a) is None          # already gone
    # the freed slot admits b, whose completion is unperturbed
    ref = _lockstep(model, params, [PROMPTS[1]], gen_cfg)
    done = {}
    while srv.pending or srv.occupancy:
        for c in srv.step():
            done[c.request_id] = c
    assert done[b].tokens == ref[0]
    assert srv.summary()["preempted"] == 1
    # preempting a still-QUEUED request drops it without a slot
    srv2 = GenerationServer(model, params, gen_cfg, num_slots=1)
    x = srv2.submit(PROMPTS[0])
    y = srv2.submit(PROMPTS[1])
    part = srv2.preempt(y)
    assert part.finish_reason == "preempted" and part.tokens == []
    assert srv2.pending == 1 and x is not None  # x still queued


def test_submit_validation_and_beam_rejection(model_and_params):
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    srv = GenerationServer(model, params, gen_cfg, num_slots=1)
    with pytest.raises(ValueError, match="empty"):
        srv.submit([])
    with pytest.raises(ValueError, match="max_position_embeddings"):
        srv.submit([1] * (CFG.max_position_embeddings
                          - gen_cfg.max_dec_len + 1))
    with pytest.raises(ValueError, match="beam"):
        GenerationServer(model, params, GenerationConfig(
            max_dec_len=4, decode_strategy="beam_search", num_beams=2,
            eos_token_id=EOS, pad_token_id=PAD))
    with pytest.raises(ValueError, match="num_slots"):
        GenerationServer(model, params, gen_cfg, num_slots=0)
    with pytest.raises(ValueError, match="no room"):
        GenerationServer(model, params, GenerationConfig(
            max_dec_len=CFG.max_position_embeddings,
            decode_strategy="greedy_search",
            eos_token_id=EOS, pad_token_id=PAD))


def test_default_prefill_buckets():
    assert default_prefill_buckets(40) == (16, 32, 40)
    assert default_prefill_buckets(16) == (16,)
    assert default_prefill_buckets(8) == (8,)
    assert default_prefill_buckets(200) == (16, 32, 64, 128, 200)


def test_inference_engine_surface(model_and_params):
    """InferenceEngine.serve_generation is the serving entry point."""
    from paddlefleetx_tpu.core.inference_engine import InferenceEngine
    model, params = model_and_params
    srv = InferenceEngine.serve_generation(model, params,
                                           _greedy_cfg(), num_slots=2)
    assert isinstance(srv, GenerationServer)
    comps = srv.run(PROMPTS[:2])
    ref = _lockstep(model, params, PROMPTS[:2], _greedy_cfg())
    assert [c.tokens for c in comps] == ref


# -- paged KV cache ----------------------------------------------------
#
# Same acceptance bar as above, but the server runs the paged cache:
# global page pool + page-table indirection, chunked prefill
# interleaved with decode, refcounted COW prefix sharing, and
# pool-exhaustion preemption. Parity must survive ALL of it.

# one page per slot: the degenerate paged layout (every slot still
# goes through the page table and the pool)
PCFG = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                 num_attention_heads=4, max_position_embeddings=128,
                 hidden_dropout_prob=0.0,
                 attention_probs_dropout_prob=0.0)
# multi-page: 512-capacity slots over 128-token pages, long shared
# prefixes span pages and chunked prefill takes several ticks
PCFG512 = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                    num_attention_heads=4,
                    max_position_embeddings=512,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)


@pytest.fixture(scope="module")
def paged_model_and_params():
    model = GPTForPretraining(PCFG)
    variables = model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32))
    return model, variables["params"]


@pytest.fixture(scope="module")
def paged512_model_and_params():
    model = GPTForPretraining(PCFG512)
    variables = model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32))
    return model, variables["params"]


def _drain(srv, done):
    while srv.pending or srv.occupancy:
        for c in srv.step():
            done[c.request_id] = c
    return done


@pytest.mark.parametrize("num_slots,order", [
    (1, list(range(6))),            # fully sequential
    (2, [5, 4, 3, 2, 1, 0]),        # reversed admission
    (3, [2, 0, 4, 1, 5, 3]),        # shuffled admission
    (6, list(range(6))),            # everything admitted at once
])
def test_paged_parity_matrix_greedy(paged_model_and_params, num_slots,
                                    order):
    """The parity matrix, paged edition: page-table indirection,
    chunked prefill, and prompt-registry sharing (PROMPTS has dupes)
    must all be invisible in the tokens."""
    model, params = paged_model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(model, params, gen_cfg,
                           num_slots=num_slots, page_size=128,
                           prefill_chunk_pages=1)
    prompts = [PROMPTS[i] for i in order]
    comps = srv.run(prompts)
    assert [c.tokens for c in comps] == [ref[i] for i in order]
    srv._alloc.check()
    assert srv._alloc.pages_in_use == 0  # drained pool is whole


def test_paged_mid_run_admission_parity(paged512_model_and_params):
    """Requests submitted mid-decode — including one sharing a
    multi-page prefix with a live slot and one identical to a live
    prompt — still complete to their lockstep rows."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=6)
    rng = np.random.default_rng(3)
    base = rng.integers(0, EOS, 300).tolist()
    shared = base[:256] + rng.integers(0, EOS, 20).tolist()
    prompts = [base, shared, list(base), [7, 8, 9]]
    ref = _lockstep(model, params, prompts, gen_cfg)
    srv = GenerationServer(model, params, gen_cfg, num_slots=3,
                           page_size=128, pool_pages=24,
                           prefill_chunk_pages=1)
    done = {}
    ids = [srv.submit(base)]
    for _ in range(6):          # prefill (3 chunks) + a few ticks
        for c in srv.step():
            done[c.request_id] = c
    ids += [srv.submit(p) for p in prompts[1:]]
    _drain(srv, done)
    assert [done[i].tokens for i in ids] == ref
    # the staggered trace actually exercised both registries
    assert srv._alloc.stats["prefix_hits"] >= 1
    assert srv._alloc.stats["prompt_hits"] >= 1
    srv._alloc.check()
    assert srv._alloc.pages_in_use == 0


def test_paged_sampling_is_slot_and_pool_independent(
        paged_model_and_params):
    """Sampled tokens are a function of (server rng, submission
    index) — not of slot count, pool size, or chunk size."""
    model, params = paged_model_and_params
    gen_cfg = GenerationConfig(max_dec_len=6,
                               decode_strategy="sampling",
                               top_k=8, top_p=0.9, temperature=0.7,
                               eos_token_id=EOS, pad_token_id=PAD)
    runs = []
    for num_slots, pool in ((1, 3), (3, 9)):
        srv = GenerationServer(model, params, gen_cfg,
                               num_slots=num_slots, page_size=128,
                               pool_pages=pool,
                               prefill_chunk_pages=1,
                               rng=jax.random.key(5))
        runs.append([c.tokens for c in srv.run(PROMPTS[:4])])
    assert runs[0] == runs[1]


def test_paged_cow_refcounts_on_shared_prompt(
        paged512_model_and_params):
    """The COW ledger, step by step: an identical prompt admits by
    sharing EVERY page of the live producer (refcount 2, zero prefill
    compute), and the first decode write splits the partial last page
    — refcounts back to 1, one `cow_splits`, tokens unperturbed."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=6)
    rng = np.random.default_rng(4)
    base = rng.integers(0, EOS, 140).tolist()   # full page + partial
    ref = _lockstep(model, params, [base, base], gen_cfg)
    srv = GenerationServer(model, params, gen_cfg, num_slots=3,
                           page_size=128, pool_pages=12,
                           prefill_chunk_pages=1)
    done = {}
    a = srv.submit(base)
    for _ in range(3):                  # 2 prefill chunks + activate
        for c in srv.step():
            done[c.request_id] = c
    a_pages = [int(p) for p in srv._pt[0, :2]]
    assert all(srv._alloc.refcount(p) == 1 for p in a_pages)
    chunks_before = srv.summary()["prefill_chunks"]
    c_id = srv.submit(base)             # identical -> prompt hit
    srv._admit()                        # admit WITHOUT a decode tick
    assert srv._alloc.stats["prompt_hits"] == 1
    # BEFORE the split: every page shared, including the partial one
    assert all(srv._alloc.refcount(p) == 2 for p in a_pages)
    assert srv.summary()["prefill_chunks"] == chunks_before  # no work
    for c in srv.step():                # first write -> COW split
        done[c.request_id] = c
    assert srv._alloc.stats["cow_splits"] >= 1
    # the full prefix page stays shared; the split page unwound
    assert srv._alloc.refcount(a_pages[0]) == 2
    assert srv._alloc.refcount(a_pages[1]) == 1
    _drain(srv, done)
    # AFTER: the divergent-write page was split, refcounts unwound,
    # the pool drained whole, and both rows match lockstep
    assert srv._alloc.stats["cow_splits"] >= 1
    assert done[a].tokens == ref[0] and done[c_id].tokens == ref[1]
    srv._alloc.check()
    assert srv._alloc.pages_in_use == 0
    assert srv._alloc.stats["allocs"] == srv._alloc.stats["frees"]


def test_paged_pool_exhaustion_preempts_then_readmits(
        paged512_model_and_params):
    """Pool-exhaustion preemption end to end: a slot that cannot grow
    preempts its neighbor (pages released mid-flight), the victim
    requeues at the FRONT with its generated tokens, readmits after
    the survivor drains, and still completes its lockstep row — no
    leaked pages, no corrupted state."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=10)
    rng = np.random.default_rng(5)
    # lengths tuned so both slots must grow a page mid-decode while
    # the pool (4 usable pages) only has one spare
    pa = rng.integers(0, EOS, 250).tolist()     # 2 pages, grows @256
    pb = rng.integers(0, EOS, 124).tolist()     # 1 page, grows @128
    ref = _lockstep(model, params, [pa, pb], gen_cfg)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           page_size=128, pool_pages=5,
                           prefill_chunk_pages=1)
    done = {}
    ids = [srv.submit(pa), srv.submit(pb)]
    _drain(srv, done)
    assert srv.summary()["preempted"] >= 1  # somebody got bumped
    assert [done[i].tokens for i in ids] == ref
    srv._alloc.check()
    assert srv._alloc.pages_in_use == 0
    assert srv._alloc.stats["allocs"] == srv._alloc.stats["frees"]


def test_paged_serving_smoke_interpret_kernel(
        paged512_model_and_params, tmp_path):
    """CI smoke (`-k smoke`), paged edition: a shared system-prompt
    prefix and one LONG chunked prefill interleaved with live decode
    ticks, on the PAGED PALLAS KERNEL in interpret mode with the
    flight recorder on — the events.jsonl trail feeds CI's
    failure-diagnostics artifact."""
    _, params = paged512_model_and_params
    kcfg = GPTConfig(**{**PCFG512.__dict__,
                        "use_flash_attention": True})
    model = GPTForPretraining(kcfg)
    gen_cfg = _greedy_cfg(max_dec=4)
    rng = np.random.default_rng(6)
    system = rng.integers(0, EOS, 130).tolist()
    p_short = [5, 9, 2]
    p_long = system + rng.integers(0, EOS, 170).tolist()   # 3 chunks
    p_follow = system + rng.integers(0, EOS, 20).tolist()
    ref = _lockstep(model, params, [p_short, p_long, p_follow],
                    gen_cfg)
    events = tmp_path / "events.jsonl"
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        srv = GenerationServer(model, params, gen_cfg, num_slots=3,
                               page_size=128, pool_pages=16,
                               prefill_chunk_pages=1,
                               events_path=str(events))
        done = {}
        ids = [srv.submit(p_short), srv.submit(p_long)]
        # p_long's prefill chunks interleave p_short's decode ticks;
        # step until p_long finishes prefilling and publishes its
        # system-prefix page (at most 3 chunks + slack)
        from paddlefleetx_tpu.core.paging import page_prefix_keys
        sys_key = page_prefix_keys(p_long, 128)[0]
        for _ in range(8):
            for c in srv.step():
                done[c.request_id] = c
            if srv._alloc.lookup_prefix(sys_key) is not None:
                break
        assert srv._alloc.lookup_prefix(sys_key) is not None
        ids.append(srv.submit(p_follow))   # shares system[0:128]
        _drain(srv, done)
        assert [done[i].tokens for i in ids] == ref
        assert reg.counter("attention/flash_decode_paged") >= 1
        assert reg.counter("serving/prefill_chunks") >= 4
        assert reg.counter("serving/prefix_hits") >= 1
        assert reg.counter("serving/cow_splits") == \
            srv._alloc.stats["cow_splits"]
        kinds = [json.loads(l)["event"] for l in
                 events.read_text().splitlines()]
        assert kinds[0] == "serving_start"
        assert "serving_prefill_chunk" in kinds
        assert "serving_admit" in kinds and "serving_evict" in kinds
        summ = srv.summary()
        assert summ["paged"] is True and summ["page_size"] == 128
        assert summ["pages_in_use"] == 0
        assert summ["prefill_chunks"] >= 4
        assert summ["ttft_p50_ms"] > 0
        srv._alloc.check()
    finally:
        metrics.set_enabled(False)
        reg.reset()


def test_paged_shared_prefix_chunk_alignment(paged512_model_and_params):
    """Regression: a shared prefix whose page count is NOT a multiple
    of ``prefill_chunk_pages`` used to leave the chunked-prefill start
    mid-chunk, so the chunk-rounded allocation outgrew the page table
    (IndexError in admission) or wedged the queue head on a tight
    pool. Sharing must truncate to a chunk boundary instead — and a
    chunk-ALIGNED prefix must still share every page."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=4)
    rng = np.random.default_rng(7)
    sys1 = rng.integers(0, EOS, 130).tolist()
    # 1-page prefix + tail rounding to full capacity: 398 tokens over
    # 256-token chunks from start=128 is 5 pages > max_kv_pages=4
    p_over = sys1[:128] + rng.integers(0, EOS, 270).tolist()
    sys2 = rng.integers(0, EOS, 260).tolist()
    p_aligned = sys2[:256] + rng.integers(0, EOS, 44).tolist()
    prompts = [sys1, sys2, p_over, p_aligned]
    ref = _lockstep(model, params, prompts, gen_cfg)
    srv = GenerationServer(model, params, gen_cfg, num_slots=4,
                           page_size=128, pool_pages=16,
                           prefill_chunk_pages=2)
    done = {}
    ids = [srv.submit(sys1), srv.submit(sys2)]
    for _ in range(3):      # 1 + 2 chunks: both prefixes registered
        for c in srv.step():
            done[c.request_id] = c
    ids += [srv.submit(p_over), srv.submit(p_aligned)]
    _drain(srv, done)
    assert [done[i].tokens for i in ids] == ref
    # p_aligned mapped both sys2 pages; p_over's lone-page hit was
    # dropped at the chunk boundary rather than overflowing the table
    assert srv._alloc.stats["prefix_hits"] == 2
    srv._alloc.check()
    assert srv._alloc.pages_in_use == 0
    assert srv._alloc.stats["allocs"] == srv._alloc.stats["frees"]


def test_paged_final_chunk_pad_pages_released(paged512_model_and_params):
    """The final prefill chunk's pad-only pages return to the pool the
    moment prefill completes instead of staying pinned until evict: a
    120-token prompt admitted over 256-token chunks holds
    ceil(120/128)=1 page while decoding, not the 2 it was chunk-
    rounded to at admission."""
    from paddlefleetx_tpu.core.paging import NULL_PAGE
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=4)
    rng = np.random.default_rng(8)
    p = rng.integers(0, EOS, 120).tolist()
    ref = _lockstep(model, params, [p], gen_cfg)
    srv = GenerationServer(model, params, gen_cfg, num_slots=1,
                           page_size=128, pool_pages=16,
                           prefill_chunk_pages=2)
    rid = srv.submit(p)
    srv.step()              # one 256-token chunk completes prefill
    assert srv._slots[0]["num_pages"] == 1
    assert srv._alloc.pages_in_use == 1
    assert all(int(x) == NULL_PAGE for x in srv._pt[0, 1:])
    done = _drain(srv, {})
    assert done[rid].tokens == ref[0]
    srv._alloc.check()
    assert srv._alloc.pages_in_use == 0
    assert srv._alloc.stats["allocs"] == srv._alloc.stats["frees"]


def test_slot_cache_sharded_under_mp_mesh(model_and_params):
    """Under an mp mesh with the ``cache_slots`` rule active, served
    greedy completions still equal the single-device lockstep rows —
    the slot axis rides the dataflow plane while mp shards heads."""
    import flax.linen as nn

    from paddlefleetx_tpu.parallel import (
        TopologyConfig, build_mesh, make_sharding_rules,
    )
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS[:4], gen_cfg)
    topo = TopologyConfig(mp_degree=4, dp_degree=2)
    mesh = build_mesh(topo)
    rules = make_sharding_rules(topo)
    logical = nn.get_partition_spec(
        jax.eval_shape(model.init, {"params": jax.random.key(0)},
                       jnp.zeros((1, 8), jnp.int32)))
    shardings = nn.logical_to_mesh_sharding(logical, mesh,
                                            list(rules))
    params_s = jax.device_put({"params": params},
                              nn.meta.unbox(shardings))["params"]
    with mesh, nn.logical_axis_rules(list(rules)):
        srv = GenerationServer(model, params_s, gen_cfg, num_slots=2)
        comps = srv.run(PROMPTS[:4])
    assert [c.tokens for c in comps] == ref


# -- speculative decoding ----------------------------------------------
#
# Drafted k-token verify (verify_step + core/spec.py): greedy output
# must equal the NON-speculative server token-exactly — whatever the
# drafts propose, the slot count, the admission timing, or the cache
# layout — because the teacher-forced verify logits are the sequential
# logits and greedy acceptance is exact argmax match. Sampling keeps
# the spec-off distribution via the standard rejection rule (salted
# per-step uniforms + the residual's rejected-token exclusion).


def _spec_cfg(base, k=3):
    return dataclasses.replace(base, spec_method="ngram",
                               spec_tokens=k)


class _OracleDraft:
    """Drafts the request's true continuation from a reference map —
    every draft accepted under greedy (the tick-compression ceiling)."""

    def __init__(self, ref_by_prompt):
        self.ref = ref_by_prompt

    def propose(self, history, k):
        h = tuple(history)
        for p, toks in self.ref.items():
            full = list(p) + toks
            if h == tuple(full[:len(h)]) and len(h) >= len(p):
                tail = full[len(h) + 1:len(h) + 1 + k]
                return tail + [0] * (k - len(tail))
        return [0] * k


class _WrongDraft:
    """Always drafts an in-vocab token run the model never emits at
    temperature 0 — every draft rejected, t0 still commits."""

    def propose(self, history, k):
        return [(history[-1] + 31) % 90] * k


@pytest.mark.parametrize("num_slots,order,spec_tokens", [
    (1, list(range(6)), 3),         # fully sequential
    (2, list(range(6)), 1),         # minimal window
    (2, [5, 4, 3, 2, 1, 0], 3),     # reversed admission
    (3, [2, 0, 4, 1, 5, 3], 4),     # shuffled admission
    (6, list(range(6)), 3),         # everything admitted at once
])
def test_spec_parity_matrix_greedy(model_and_params, num_slots, order,
                                   spec_tokens):
    """The speculative parity matrix: greedy spec-on == spec-off ==
    lockstep, over slot counts x admission orders x draft widths."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(model, params,
                           _spec_cfg(gen_cfg, spec_tokens),
                           num_slots=num_slots)
    prompts = [PROMPTS[i] for i in order]
    comps = srv.run(prompts)
    assert [c.tokens for c in comps] == [ref[i] for i in order]
    assert all(c.finish_reason in ("eos", "length") for c in comps)


@pytest.mark.parametrize("num_slots,order", [
    (1, list(range(6))),
    (3, [2, 0, 4, 1, 5, 3]),
    (6, list(range(6))),
])
def test_paged_spec_parity_matrix_greedy(paged_model_and_params,
                                         num_slots, order):
    """The speculative parity matrix, PAGED edition: the k+1-token
    window maintenance, multi-token page writes, and rejected-page
    rollback must all be invisible in the tokens — and the drained
    pool must be whole (every rolled-back page found its way home)."""
    model, params = paged_model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(model, params, _spec_cfg(gen_cfg),
                           num_slots=num_slots, page_size=128,
                           prefill_chunk_pages=1)
    prompts = [PROMPTS[i] for i in order]
    comps = srv.run(prompts)
    assert [c.tokens for c in comps] == [ref[i] for i in order]
    srv._alloc.check()
    assert srv._alloc.pages_in_use == 0


def test_spec_mid_run_admission_parity(model_and_params):
    """Requests admitted while speculative slots sit at RAGGED depths
    (different per-slot accepted counts) still complete to their
    lockstep rows."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(model, params, _spec_cfg(gen_cfg),
                           num_slots=2)
    done = {}
    ids = [srv.submit(p) for p in PROMPTS[:2]]
    for _ in range(2):
        for c in srv.step():
            done[c.request_id] = c
    ids += [srv.submit(p) for p in PROMPTS[2:]]
    _drain(srv, done)
    assert [done[i].tokens for i in ids] == ref


def test_spec_oracle_drafts_compress_ticks(model_and_params):
    """With an oracle draft source (the true continuation), every
    draft is accepted: the whole trace finishes in ~max_dec_len/(k+1)
    ticks, accept rate 1.0 in telemetry AND the summary, and the
    tokens still match lockstep — committed tokens, not ticks, is
    what serving/decode_tokens counts."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS[:3], gen_cfg)
    ref_map = {tuple(p): t for p, t in zip(PROMPTS[:3], ref)}
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        srv = GenerationServer(model, params, _spec_cfg(gen_cfg, 3),
                               num_slots=3)
        srv._draft = _OracleDraft(ref_map)
        comps = srv.run(PROMPTS[:3])
        assert [c.tokens for c in comps] == ref
        summ = srv.summary()
        assert summ["spec_accept_rate"] == 1.0
        assert summ["spec_drafted"] == summ["spec_accepted"] > 0
        # 8 tokens/request at 4 tokens/tick = 2 ticks per request
        assert summ["decode_ticks"] == 2
        assert summ["decode_tokens"] == sum(len(t) for t in ref)
        assert reg.counter("serving/decode_tokens") == \
            summ["decode_tokens"]
        assert reg.counter("serving/spec_accepted") == \
            summ["spec_accepted"]
        assert reg.gauge("serving/spec_accept_rate") == 1.0
    finally:
        metrics.set_enabled(False)
        reg.reset()


def test_spec_wrong_drafts_still_exact(model_and_params):
    """The adversarial floor: a draft source that is ALWAYS wrong
    commits exactly one token per tick (the t0 sample), accept rate
    0.0, output still lockstep-exact — drafts can only ever cost
    throughput, never correctness."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS[:3], gen_cfg)
    srv = GenerationServer(model, params, _spec_cfg(gen_cfg, 3),
                           num_slots=2)
    srv._draft = _WrongDraft()
    comps = srv.run(PROMPTS[:3])
    assert [c.tokens for c in comps] == ref
    summ = srv.summary()
    assert summ["spec_accepted"] == 0
    assert summ["spec_accept_rate"] == 0.0


def test_spec_greedy_chain_stops_at_first_mismatch(model_and_params):
    """The commit chain rule on one verify tick: drafts
    [t1, t2, WRONG, t4] commit exactly [t0, t1, t2] — a correct draft
    AFTER a rejection must not commit (its context was wrong)."""
    from paddlefleetx_tpu.models.gpt.generation import (
        decode_step, verify_step,
    )
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    srv = GenerationServer(model, params, gen_cfg, num_slots=2)
    for p in PROMPTS[:2]:
        srv.submit(p)
    srv._admit()
    model_u, params_u = srv.model, srv.params
    # sequential oracle: four plain ticks from a snapshot
    cache, state = srv._cache, srv._state
    seq = []
    c, s = _snapshot(cache), state
    for _ in range(4):
        c, s, h = decode_step(model_u, params_u, c, s,
                              srv._rng, gen_cfg)
        seq.append(_harvest(h).window[:, 0, 0])
    seq = np.stack(seq, 1)                    # [slots, 4]
    drafts = seq[:, 1:].copy()
    drafts[:, 2] = (seq[:, 3] + 7) % 90       # wrong at j=3
    _, s2, h = verify_step(
        model_u, params_u, cache, state,
        jnp.asarray(drafts, jnp.int32), srv._rng, gen_cfg)
    got = _harvest(h, k=3)
    assert got.counts[:, 0].tolist() == [3, 3]
    np.testing.assert_array_equal(got.window[:, 0, :3], seq[:, :3])
    # lengths/dec_count advanced by the per-slot committed counts
    assert (np.asarray(s2.lengths) - np.asarray(state.lengths)
            ).tolist() == [3, 3]
    assert np.asarray(s2.dec_count).tolist() == [3, 3]


def test_spec_sampling_accept_rule(model_and_params):
    """The rejection-sampling rule, pinned at its deterministic
    limits: at near-zero temperature the filtered distribution is a
    point mass, so drafting the sequential continuation accepts
    everything and drafting anything else rejects at the first draft
    — and the rejected draft lands in SlotState.rejected so the next
    tick's draw excludes it."""
    from paddlefleetx_tpu.models.gpt.generation import (
        decode_step, verify_step,
    )
    model, params = model_and_params
    gen_cfg = GenerationConfig(
        max_dec_len=8, decode_strategy="sampling", top_k=4,
        top_p=1.0, temperature=1e-4, eos_token_id=EOS,
        pad_token_id=PAD)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2)
    for p in PROMPTS[:2]:
        srv.submit(p)
    srv._admit()
    model_u, params_u = srv.model, srv.params
    cache, state = srv._cache, srv._state
    seq = []
    c, s = _snapshot(cache), state
    for _ in range(3):
        c, s, h = decode_step(model_u, params_u, c, s,
                              srv._rng, gen_cfg)
        seq.append(_harvest(h).window[:, 0, 0])
    seq = np.stack(seq, 1)                    # [slots, 3]
    # (a) true continuation -> all accepted (p(draft) ~ 1)
    _, s_ok, h = verify_step(
        model_u, params_u, _snapshot(cache), state,
        jnp.asarray(seq[:, 1:], jnp.int32), srv._rng, gen_cfg)
    got = _harvest(h, k=2)
    assert got.counts[:, 0].tolist() == [3, 3]
    np.testing.assert_array_equal(got.window[:, 0], seq)
    assert np.asarray(s_ok.rejected).tolist() == [-1, -1]
    # (b) wrong first draft -> rejected (p(draft) ~ 0), only t0
    # commits, and the reject is recorded for the next tick's draw
    wrong = (seq[:, 1:].copy() + 11) % 90
    _, s_rej, h2 = verify_step(
        model_u, params_u, cache, state,
        jnp.asarray(wrong, jnp.int32), srv._rng, gen_cfg)
    got2 = _harvest(h2, k=2)
    assert got2.counts[:, 0].tolist() == [1, 1]
    np.testing.assert_array_equal(got2.window[:, 0, 0], seq[:, 0])
    assert np.asarray(s_rej.rejected).tolist() == \
        wrong[:, 0].tolist()


def test_spec_rejected_token_excluded_from_next_draw(model_and_params):
    """The residual exclusion: when SlotState.rejected holds the very
    token the filtered distribution concentrates on, the next tick
    must sample something ELSE — without the mask the rejected draft
    would be re-drawn and the output distribution would double-count
    it."""
    from paddlefleetx_tpu.models.gpt.generation import verify_step
    model, params = model_and_params
    gen_cfg = GenerationConfig(
        max_dec_len=8, decode_strategy="sampling", top_k=4,
        top_p=1.0, temperature=1e-4, eos_token_id=EOS,
        pad_token_id=PAD)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2)
    for p in PROMPTS[:2]:
        srv.submit(p)
    srv._admit()
    cache, state = srv._cache, srv._state
    k = 2
    zeros = jnp.zeros((2, k), jnp.int32)
    _, _, h = verify_step(srv.model, srv.params, _snapshot(cache),
                          state, zeros, srv._rng, gen_cfg)
    t0 = _harvest(h, k=k).window[:, 0, 0]     # the point-mass tokens
    state_rej = state._replace(
        rejected=jnp.asarray(t0, jnp.int32))
    _, _, h2 = verify_step(srv.model, srv.params, cache, state_rej,
                           zeros, srv._rng, gen_cfg)
    t0_excl = _harvest(h2, k=k).window[:, 0, 0]
    assert all(a != b for a, b in zip(t0_excl, t0))


def test_spec_sampling_is_slot_and_order_independent(model_and_params):
    """Speculative sampling draws stay a function of (server rng,
    submission index): the same trace served with 1 and 3 slots —
    different tick groupings, different accept patterns — emits
    identical tokens."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(
        max_dec_len=6, decode_strategy="sampling", top_k=8,
        top_p=0.9, temperature=0.7, eos_token_id=EOS,
        pad_token_id=PAD, spec_method="ngram", spec_tokens=3)
    runs = []
    for num_slots in (1, 3):
        srv = GenerationServer(model, params, gen_cfg,
                               num_slots=num_slots,
                               rng=jax.random.PRNGKey(7))
        runs.append([c.tokens for c in srv.run(PROMPTS)])
    assert runs[0] == runs[1]


def test_paged_spec_serving_smoke_interpret_kernel(
        paged_model_and_params, tmp_path):
    """CI smoke (`-k smoke`), speculative edition: staggered admits
    over the PAGED pool with the interpret-mode VERIFY kernel
    (`attention/flash_decode_paged_verify`) carrying every tick, the
    flight recorder streaming `serving_spec` events, and greedy
    parity holding through it all."""
    _, params = paged_model_and_params
    kcfg = GPTConfig(**{**PCFG.__dict__, "use_flash_attention": True})
    model = GPTForPretraining(kcfg)
    gen_cfg = _greedy_cfg(max_dec=4)
    ref = _lockstep(model, params, PROMPTS[:3], gen_cfg)
    events = tmp_path / "events.jsonl"
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        srv = GenerationServer(model, params, _spec_cfg(gen_cfg, 3),
                               num_slots=2, page_size=128,
                               prefill_chunk_pages=1,
                               events_path=str(events))
        done = {}
        ids = [srv.submit(p) for p in PROMPTS[:2]]
        srv.step()                       # stagger the third admit
        ids.append(srv.submit(PROMPTS[2]))
        _drain(srv, done)
        assert [done[i].tokens for i in ids] == ref
        assert reg.counter("attention/flash_decode_paged_verify") >= 1
        assert reg.counter("serving/spec_drafted") > 0
        assert reg.counter("serving/decode_tokens") == \
            srv.summary()["decode_tokens"]
        recs = [json.loads(l) for l in
                events.read_text().splitlines()]
        start = next(r for r in recs if r["event"] == "serving_start")
        assert start["spec"] is True and start["spec_tokens"] == 3
        spec_events = [r for r in recs if r["event"] == "serving_spec"]
        assert spec_events
        assert all(e["committed"] >= e["accepted"] >= 0
                   for e in spec_events)
        srv._alloc.check()
        assert srv._alloc.pages_in_use == 0
    finally:
        metrics.set_enabled(False)
        reg.reset()


def test_ngram_draft_source_prompt_lookup():
    """NgramDraftSource proposes the shifted continuation of the most
    recent (longest-n-first) suffix match, pads with zeros past the end
    of history, and falls back to all-zeros when nothing matches."""
    from paddlefleetx_tpu.core.spec import (
        NgramDraftSource, make_draft_source)
    src = NgramDraftSource(max_ngram=3)
    # suffix [2,3] matched at i=1; continuation [4,2,3] -> first token
    # guesses the tick's own t0, so drafts are [2,3] padded to k=3
    assert src.propose([1, 2, 3, 4, 2, 3], 3) == [2, 3, 0]
    # longest n wins: trailing [7,8,9] matches earlier despite the
    # shorter [9] also matching elsewhere
    assert src.propose([7, 8, 9, 5, 6, 9, 7, 8, 9], 2) == [6, 9]
    # no earlier occurrence of any suffix -> zeros
    assert src.propose([1, 2, 3, 4], 2) == [0, 0]
    # degenerate histories never index out of range
    assert src.propose([], 2) == [0, 0]
    assert src.propose([5], 2) == [0, 0]
    # factory: the spec_method switch, and its error path
    assert isinstance(make_draft_source("ngram", max_ngram=2),
                      NgramDraftSource)
    with pytest.raises(ValueError, match="spec_method"):
        make_draft_source("draft_model")
    with pytest.raises(ValueError, match="max_ngram"):
        NgramDraftSource(max_ngram=0)


def test_paged_spec_pool_exhaustion_preempts_mid_tick(
        paged512_model_and_params):
    """A speculative tick's page maintenance (k+1-position window) can
    preempt a slot that is IN the tick's live set — the commit loop
    must skip the victim (nothing committed for it), the victim
    requeues with its rejected-residual state intact, and the final
    tokens stay lockstep-exact with no leaked pages."""
    model, params = paged512_model_and_params
    gen_cfg = _spec_cfg(_greedy_cfg(max_dec=10), k=3)
    rng = np.random.default_rng(5)
    pa = rng.integers(0, EOS, 250).tolist()     # 2 pages, grows @256
    pb = rng.integers(0, EOS, 124).tolist()     # 1 page, grows @128
    ref = _lockstep(model, params, [pa, pb], _greedy_cfg(max_dec=10))
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           page_size=128, pool_pages=5,
                           prefill_chunk_pages=1)
    done = {}
    ids = [srv.submit(pa), srv.submit(pb)]
    _drain(srv, done)
    assert srv.summary()["preempted"] >= 1  # somebody got bumped
    assert [done[i].tokens for i in ids] == ref
    srv._alloc.check()
    assert srv._alloc.pages_in_use == 0
    assert srv._alloc.stats["allocs"] == srv._alloc.stats["frees"]


# -- graceful degradation: deadlines, shedding, drain -------------------
#
# docs/robustness.md: expiry/shedding/drain are RESULTS the client
# sees (deadline_exceeded / RequestShed / preempted partials), never
# silent drops — and a drained paged server's partials re-enter a
# fresh server via submit(resume_tokens=...) with no committed token
# lost.


def test_deadline_exceeded_in_queue(model_and_params):
    """A queued request whose deadline passes completes as
    deadline_exceeded with no tokens; its neighbors are unaffected."""
    import time as _time
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, [PROMPTS[0]], gen_cfg)
    srv = GenerationServer(model, params, gen_cfg, num_slots=1)
    a = srv.submit(PROMPTS[0])
    b = srv.submit(PROMPTS[1], deadline_s=0.01)  # stuck behind a
    _time.sleep(0.05)
    done = {}
    _drain(srv, done)
    assert done[b].finish_reason == "deadline_exceeded"
    assert done[b].tokens == []
    assert done[a].tokens == ref[0]
    assert srv.summary()["deadline_exceeded"] == 1


def test_deadline_exceeded_mid_decode_returns_partial(model_and_params):
    """An in-flight request past its deadline is evicted with its
    committed tokens — the deadline is checked against wall time, so
    the test rewinds the slot's deadline instead of sleeping."""
    model, params = model_and_params
    srv = GenerationServer(model, params, _greedy_cfg(),
                           num_slots=1, request_ttl_s=3600.0)
    a = srv.submit(PROMPTS[0])
    for _ in range(3):      # the first launches, the next two commit
        srv.step()
    (slot,) = [i for i, r in enumerate(srv._slots) if r is not None]
    srv._slots[slot]["deadline"] = 1.0          # long expired
    (c,) = srv.step()
    assert c.request_id == a
    assert c.finish_reason == "deadline_exceeded"
    assert len(c.tokens) == 2                   # partial kept
    assert srv.occupancy == 0                   # slot freed
    # the tick that was in flight for it was read in the same step,
    # with no one to launch for, and committed nothing: a void row
    assert not srv.work_pending() and srv._inflight is None
    assert srv.summary()["decode_ticks"] == 3


def test_queue_depth_shedding(model_and_params):
    model, params = model_and_params
    srv = GenerationServer(model, params, _greedy_cfg(),
                           num_slots=1, max_queue_depth=2)
    srv.submit(PROMPTS[0])
    srv.submit(PROMPTS[1])
    with pytest.raises(RequestShed, match="queue_depth"):
        srv.submit(PROMPTS[2])
    assert srv.summary()["shed"] == 1
    assert srv.pending == 2                     # shed never queued


def test_injected_admit_fail_sheds(model_and_params):
    from paddlefleetx_tpu.core.resilience import FaultInjector
    model, params = model_and_params
    srv = GenerationServer(
        model, params, _greedy_cfg(), num_slots=1,
        fault_injector=FaultInjector("admit_fail@req=2",
                                     kill_mode="raise"))
    srv.submit(PROMPTS[0])
    with pytest.raises(RequestShed, match="fault"):
        srv.submit(PROMPTS[1])
    srv.submit(PROMPTS[2])                      # one-shot fault
    assert srv.summary()["shed"] == 1


def test_resume_tokens_validation(paged_model_and_params):
    pmodel, pparams = paged_model_and_params
    psrv = GenerationServer(pmodel, pparams, _greedy_cfg(max_dec=4),
                            num_slots=1, page_size=128, pool_pages=2,
                            prefill_chunk_pages=1)
    with pytest.raises(ValueError, match="max_dec_len"):
        psrv.submit(PROMPTS[0], resume_tokens=[1, 2, 3, 4])


def test_unpaged_drain_restart_token_exactness(model_and_params):
    """The fleet-failover satellite pin: resume_tokens works on a
    CONTIGUOUS (unpaged) server too — drain mid-flight, feed every
    preempted partial into a fresh unpaged server, and the stitched
    completions equal the uninterrupted lockstep rows. Router
    failover must not depend on the paged layout."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2)
    ids = [srv.submit(p) for p in PROMPTS]
    done = {}
    for _ in range(3):                          # mid-flight drain
        for c in srv.step():
            done[c.request_id] = c
    for c in srv.drain(max_ticks=0):
        done[c.request_id] = c
    assert set(done) == set(ids)
    partials = [c for c in done.values()
                if c.finish_reason == "preempted"]
    assert partials
    assert any(c.tokens for c in partials)      # real mid-decode state

    srv2 = GenerationServer(model, params, gen_cfg, num_slots=2)
    remap = {}
    for c in partials:
        remap[srv2.submit(c.prompt,
                          resume_tokens=c.tokens or None)] = \
            c.request_id
    done2 = {}
    _drain(srv2, done2)
    final = {rid: done[rid] for rid in ids}
    for nid, rid in remap.items():
        final[rid] = done2[nid]
    assert [final[i].tokens for i in ids] == ref
    assert all(final[i].finish_reason in ("eos", "length")
               for i in ids)


def test_drain_returns_queued_and_inflight_partials(model_and_params):
    model, params = model_and_params
    srv = GenerationServer(model, params, _greedy_cfg(), num_slots=1)
    a = srv.submit(PROMPTS[0])
    b = srv.submit(PROMPTS[1])
    srv.step()
    srv.step()
    out = {c.request_id: c for c in srv.drain(max_ticks=0)}
    assert out[a].finish_reason == "preempted"
    assert len(out[a].tokens) == 2              # committed kept
    assert out[b].finish_reason == "preempted"
    assert out[b].tokens == []                  # never admitted
    with pytest.raises(RequestShed, match="draining"):
        srv.submit(PROMPTS[2])


def test_sigterm_flips_drain_mode_and_close_restores(model_and_params):
    import os as _os
    import signal as _signal
    model, params = model_and_params
    prev = _signal.getsignal(_signal.SIGTERM)
    srv = GenerationServer(model, params, _greedy_cfg(),
                           num_slots=1, drain_on_sigterm=True)
    ids = [srv.submit(p) for p in PROMPTS[:3]]
    srv.step()
    _os.kill(_os.getpid(), _signal.SIGTERM)
    assert srv._draining
    done = {c.request_id: c for c in srv.drain(max_ticks=0)}
    assert set(done) == set(ids)
    assert all(c.finish_reason == "preempted" for c in done.values())
    srv.close()
    assert _signal.getsignal(_signal.SIGTERM) is prev
    srv.close()                                 # idempotent


def test_paged_drain_restart_token_exactness(paged512_model_and_params):
    """The satellite pin: drain a paged server mid-flight, feed every
    preempted partial into a FRESH server via resume_tokens, and the
    stitched completions equal the uninterrupted lockstep rows — no
    committed token lost, none replayed."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           page_size=128, pool_pages=24)
    ids = [srv.submit(p) for p in PROMPTS]
    done = {}
    for _ in range(3):                          # mid-flight drain
        for c in srv.step():
            done[c.request_id] = c
    for c in srv.drain(max_ticks=0):
        done[c.request_id] = c
    assert set(done) == set(ids)
    partials = [c for c in done.values()
                if c.finish_reason == "preempted"]
    assert partials
    assert any(c.tokens for c in partials)      # real mid-decode state

    srv2 = GenerationServer(model, params, gen_cfg, num_slots=2,
                            page_size=128, pool_pages=24)
    remap = {}
    for c in partials:
        remap[srv2.submit(c.prompt, resume_tokens=c.tokens)] = \
            c.request_id
    done2 = {}
    _drain(srv2, done2)
    final = {rid: done[rid] for rid in ids}
    for nid, rid in remap.items():
        final[rid] = done2[nid]
    assert [final[i].tokens for i in ids] == ref
    assert all(final[i].finish_reason in ("eos", "length")
               for i in ids)
    srv2._alloc.check()
    assert srv2._alloc.pages_in_use == 0


# -- request tracing ---------------------------------------------------


def test_paged_preemption_trace_timeline(paged512_model_and_params,
                                         tmp_path):
    """The PR-10 acceptance pin: a preempted-and-readmitted request's
    COMPLETE span timeline reconstructs from events.jsonl alone —
    one trace, time-ordered, exactly one open phase at a time
    (queue -> prefill -> decode -> queue -> prefill -> decode), one
    first-token point, and the root close carrying the final token
    count that matches the Completion."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=10)
    rng = np.random.default_rng(5)
    # same geometry as the pool-exhaustion test: both slots must grow
    # mid-decode with one spare page, so somebody gets preempted
    pa = rng.integers(0, EOS, 250).tolist()
    pb = rng.integers(0, EOS, 124).tolist()
    events = tmp_path / "events.jsonl"
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           page_size=128, pool_pages=5,
                           prefill_chunk_pages=1,
                           events_path=str(events))
    done = {}
    ids = [srv.submit(pa), srv.submit(pb)]
    _drain(srv, done)
    assert srv.summary()["preempted"] >= 1

    evs = read_events(str(events))
    # the stream as a whole is time-ordered
    assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)

    # every completion carries its trace id; ids are distinct
    assert len({done[i].trace_id for i in ids}) == 2
    pre = next(e for e in evs if e["event"] == "serving_preempt")
    tid = pre["trace"]
    victim = pre["request"]
    assert done[victim].trace_id == tid

    mine = [e for e in evs
            if e.get("trace") == tid and e["event"].startswith("span")]
    roots = [e for e in mine if e["event"] == "span_begin"
             and e["name"] == "serving/request"]
    assert len(roots) == 1        # preemption never re-roots the trace
    root = roots[0]
    assert root["prompt_len"] == len(pa if victim == ids[0] else pb)

    # phase children of the root, in emission order
    phases = [e for e in mine if e["event"] == "span_begin"
              and e.get("parent") == root["span"]]
    names = [e["name"] for e in phases]
    assert names[0] == "serving/queue"
    assert names.count("serving/queue") >= 2     # submit + requeue
    assert names.count("serving/prefill") >= 2   # admitted twice
    assert names.count("serving/decode") >= 1
    assert any(e["name"] == "serving/queue" and e.get("requeued")
               for e in phases)

    # every begun span on the trace ends exactly once
    begun = sorted(e["span"] for e in mine if e["event"] == "span_begin")
    ends = [e for e in mine if e["event"] == "span_end"]
    assert sorted(e["span"] for e in ends) == begun

    # one open phase at a time: in file order, phase i ends before
    # phase i+1 begins, and the root end closes the whole timeline
    pos = {(e["event"], e["span"]): i for i, e in enumerate(evs)
           if e["event"] in ("span_begin", "span_end")
           and e.get("trace") == tid}
    for a, b in zip(phases, phases[1:]):
        assert pos[("span_end", a["span"])] < \
            pos[("span_begin", b["span"])]
    assert pos[("span_end", root["span"])] == max(pos.values())

    # the first token fired once, despite the preemption round-trip
    points = [e for e in mine if e["event"] == "span_point"]
    assert [e["name"] for e in points] == ["serving/first_token"]
    assert points[0]["ttft_ms"] > 0

    root_end = next(e for e in ends if e["span"] == root["span"])
    assert root_end["tokens"] == len(done[victim].tokens)
    assert done[victim].finish_reason in ("eos", "length")


def test_paged_resume_links_trace_across_restart(
        paged512_model_and_params, tmp_path):
    """Drain-then-restart keeps the timeline: feeding
    ``trace_id=partial.trace_id`` back with ``resume_tokens`` makes
    the fresh server's spans CONTINUE the original trace — two
    request lifetimes, one trace id, resumed one marked."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg()
    events = tmp_path / "events.jsonl"
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           page_size=128, pool_pages=24,
                           events_path=str(events))
    ids = [srv.submit(p) for p in PROMPTS]
    done = {}
    for _ in range(3):                          # mid-flight drain
        for c in srv.step():
            done[c.request_id] = c
    for c in srv.drain(max_ticks=0):
        done[c.request_id] = c
    partials = [c for c in done.values()
                if c.finish_reason == "preempted"]
    assert partials
    assert all(c.trace_id for c in partials)

    # the restarted server appends to the SAME event stream
    srv2 = GenerationServer(model, params, gen_cfg, num_slots=2,
                            page_size=128, pool_pages=24,
                            events_path=str(events))
    remap = {}
    for c in partials:
        remap[srv2.submit(c.prompt, resume_tokens=c.tokens,
                          trace_id=c.trace_id)] = c
    done2 = {}
    _drain(srv2, done2)

    evs = read_events(str(events))
    for nid, c in remap.items():
        assert done2[nid].trace_id == c.trace_id    # continued trace
        roots = [e for e in evs if e["event"] == "span_begin"
                 and e["name"] == "serving/request"
                 and e["trace"] == c.trace_id]
        assert len(roots) == 2          # original + resumed lifetime
        assert roots[0]["span"] != roots[1]["span"]
        if c.tokens:                    # mid-decode partials carry it
            assert roots[1]["resumed"] is True
        req_ends = [e for e in evs if e["event"] == "span_end"
                    and e["name"] == "serving/request"
                    and e["trace"] == c.trace_id]
        assert len(req_ends) == 2
        assert req_ends[1]["tokens"] == len(done2[nid].tokens)


#: one Prometheus 0.0.4 sample line (# TYPE comments aside)
_PROM_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_][a-zA-Z0-9_]*(\{le="[^"]+"\})? [-+0-9.einfE]+$')


def test_serving_metrics_endpoint_smoke(paged512_model_and_params,
                                        tmp_path, monkeypatch):
    """CI smoke (`-k smoke`), live-export edition: PFX_METRICS_PORT=0
    starts the HTTP server on an ephemeral port; /metrics scraped
    MID-RUN parses as Prometheus text exposition, /healthz answers 200
    ok and flips to 503 draining after ``drain()``, and /trace serves
    the request spans as Chrome trace JSON. Scraped bodies land as
    metrics_scrape_* files for CI's failure-diagnostics artifact."""
    model, params = paged512_model_and_params
    monkeypatch.setenv("PFX_METRICS_PORT", "0")
    obs_server.stop()              # a fresh singleton for this test
    events = tmp_path / "events.jsonl"
    gen_cfg = _greedy_cfg(max_dec=6)

    def get(url_path):
        try:
            with urllib.request.urlopen(msrv.url(url_path),
                                        timeout=10) as resp:
                return resp.status, resp.read().decode("utf-8")
        except urllib.error.HTTPError as err:
            return err.code, err.read().decode("utf-8")

    try:
        srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               page_size=128, pool_pages=8,
                               prefill_chunk_pages=1,
                               events_path=str(events))
        msrv = obs_server.get_server()
        assert msrv is not None and msrv.port > 0
        done = {}
        ids = [srv.submit([3, 1, 4, 1, 5]),
               srv.submit([2, 7, 1, 8, 2, 8])]
        for _ in range(4):            # prefill + first decode ticks
            for c in srv.step():
                done[c.request_id] = c

        # mid-run: the exposition must parse line by line
        code, mbody = get("/metrics")
        assert code == 200
        for line in mbody.splitlines():
            assert line.startswith("# TYPE ") or \
                _PROM_SAMPLE_RE.match(line), \
                f"bad exposition line: {line!r}"
        assert "pfx_serving_ttft_ms_bucket" in mbody
        assert 'le="+Inf"' in mbody
        code, hbody = get("/healthz")
        assert code == 200
        health = json.loads(hbody)
        assert health["status"] == "ok" and health["slots"] == 2
        (tmp_path / "metrics_scrape_metrics.txt").write_text(mbody)
        (tmp_path / "metrics_scrape_healthz.json").write_text(hbody)

        _drain(srv, done)
        assert set(done) == set(ids)
        srv.drain()                   # idle drain: just the flip
        code, hbody = get("/healthz")
        assert code == 503
        assert json.loads(hbody)["status"] == "draining"
        (tmp_path / "metrics_scrape_healthz_draining.json"
         ).write_text(hbody)

        code, tbody = get("/trace")
        assert code == 200
        names = {e.get("name")
                 for e in json.loads(tbody)["traceEvents"]}
        assert "serving/request" in names
        assert "serving/queue" in names
    finally:
        obs_server.stop()
    assert obs_server.get_server() is None


# -- device-resident decode: T ticks per host round-trip ---------------
#
# The fused decode_loop/verify_loop (generation.py) must be INVISIBLE
# in the tokens: T=1 through the loop equals decode_step, and T>1
# equals T=1 on every strategy x layout x spec combination — while
# strictly reducing host round-trips per committed token. The matrix
# below runs 6 requests over 2 slots so every run exercises a
# host-signaled admission exit (queue pending behind full slots), and
# the budget-expiry exit (requests hitting max_dec_len).


class _ConstDraft:
    """Drafts a fixed token regardless of history: propose(h, k*T)
    reshaped [T, k] equals T separate propose(h, k) calls, so the
    draft stream is identical at any loop_ticks — the deterministic
    source the sampling+spec T-parity leg needs (history-dependent
    sources like ngram draft from the PRE-loop history at T>1, which
    changes accept patterns, not tokens, under greedy only)."""

    def propose(self, history, k):
        return [17] * k


def _loop_run(model, params, gen_cfg, loop_ticks, *, paged=False,
              seed=11, draft=None):
    paged_kw = dict(page_size=128, prefill_chunk_pages=1) if paged \
        else {}
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           rng=jax.random.key(seed),
                           device_loop_ticks=loop_ticks, **paged_kw)
    if draft is not None:
        srv._draft = draft
    toks = [c.tokens for c in srv.run(PROMPTS)]
    if paged:
        srv._alloc.check()
        assert srv._alloc.pages_in_use == 0
    return toks, srv.summary()


@pytest.mark.parametrize("loop_ticks", [4, 16])
@pytest.mark.parametrize("strategy", ["greedy", "sampling"])
def test_device_loop_parity_unpaged(model_and_params, loop_ticks,
                                    strategy):
    """T in {4,16} == T=1, token-exact, greedy and seeded sampling,
    contiguous cache — with strictly fewer host round-trips per
    committed token at T>1."""
    model, params = model_and_params
    if strategy == "greedy":
        gen_cfg = _greedy_cfg()
    else:
        gen_cfg = GenerationConfig(
            max_dec_len=8, decode_strategy="sampling", top_k=8,
            top_p=0.9, temperature=0.7, eos_token_id=EOS,
            pad_token_id=PAD)
    ref, ref_summ = _loop_run(model, params, gen_cfg, 1)
    out, summ = _loop_run(model, params, gen_cfg, loop_ticks)
    assert out == ref
    assert summ["decode_tokens"] == ref_summ["decode_tokens"]
    assert summ["host_roundtrips"] < ref_summ["host_roundtrips"]
    # the T = 1 server reads a launch after the next: a slot is freed,
    # and taken again, a step later, and an EOS it had not seen yet
    # may cost it a tick whose rows are all void
    assert 0 <= ref_summ["device_ticks"] - summ["device_ticks"] <= \
        len(out)


@pytest.mark.parametrize("loop_ticks", [4, 16])
@pytest.mark.parametrize("strategy", ["greedy", "sampling"])
def test_device_loop_parity_paged(paged_model_and_params, loop_ticks,
                                  strategy):
    """The paged edition of the T-parity matrix: page pre-mapping for
    the loop window and the past-commit rollback must leave the pool
    whole (checked inside _loop_run) and the tokens untouched."""
    model, params = paged_model_and_params
    if strategy == "greedy":
        gen_cfg = _greedy_cfg()
    else:
        gen_cfg = GenerationConfig(
            max_dec_len=8, decode_strategy="sampling", top_k=8,
            top_p=0.9, temperature=0.7, eos_token_id=EOS,
            pad_token_id=PAD)
    ref, ref_summ = _loop_run(model, params, gen_cfg, 1, paged=True)
    out, summ = _loop_run(model, params, gen_cfg, loop_ticks,
                          paged=True)
    assert out == ref
    assert summ["host_roundtrips"] < ref_summ["host_roundtrips"]


@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("loop_ticks", [4, 16])
def test_device_loop_spec_greedy_parity(request, paged, loop_ticks):
    """Spec-on greedy at T in {4,16}: ngram drafting proposes k*T
    tokens from the pre-loop history, acceptance re-scores every
    draft, and the argmax chain keeps the output token-identical to
    both spec-on T=1 and spec-off lockstep."""
    model, params = request.getfixturevalue(
        "paged_model_and_params" if paged else "model_and_params")
    gen_cfg = _spec_cfg(_greedy_cfg(), 3)
    ref = _lockstep(model, params, PROMPTS, _greedy_cfg())
    t1, _ = _loop_run(model, params, gen_cfg, 1, paged=paged)
    out, summ = _loop_run(model, params, gen_cfg, loop_ticks,
                          paged=paged)
    assert out == t1 == ref
    assert summ["spec_accepted"] >= 0


@pytest.mark.parametrize("paged", [False, True])
def test_device_loop_spec_sampling_const_draft_parity(request, paged):
    """Seeded sampling + spec-on T-parity needs a draft source whose
    proposals don't depend on WHEN they were proposed (_ConstDraft):
    then the per-(nonce, dec_count) rng streams line up tick for tick
    and T=4 replays T=1 exactly, rejection sampling included."""
    model, params = request.getfixturevalue(
        "paged_model_and_params" if paged else "model_and_params")
    gen_cfg = GenerationConfig(
        max_dec_len=8, decode_strategy="sampling", top_k=8,
        top_p=0.9, temperature=0.7, eos_token_id=EOS,
        pad_token_id=PAD, spec_method="ngram", spec_tokens=3)
    ref, _ = _loop_run(model, params, gen_cfg, 1, paged=paged,
                       draft=_ConstDraft())
    out, _ = _loop_run(model, params, gen_cfg, 4, paged=paged,
                       draft=_ConstDraft())
    assert out == ref


def test_device_loop_mid_loop_eos_parity(model_and_params):
    """A slot finishing MID-loop (eos on an interior tick of a T=4
    launch) must exit the loop that tick, evict on time, and leave
    every row token-identical to T=1. The eos id is picked from the
    T=1 reference so one row provably finishes early."""
    model, params = model_and_params
    probe = _lockstep(model, params, PROMPTS, _greedy_cfg())
    eos = probe[0][3]                    # row 0 finishes at tick 4
    gen_cfg = _greedy_cfg()
    gen_cfg = dataclasses.replace(gen_cfg, eos_token_id=eos)
    ref, ref_summ = _loop_run(model, params, gen_cfg, 1)
    out, summ = _loop_run(model, params, gen_cfg, 4)
    assert out == ref
    assert any(len(r) < gen_cfg.max_dec_len for r in ref)  # eos hit
    assert summ["decode_tokens"] == ref_summ["decode_tokens"]


def test_device_loop_t1_step_path_unchanged(model_and_params):
    """device_loop_ticks=1 is one host round-trip per device tick,
    token-exact with the lockstep path."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           device_loop_ticks=1)
    ref = _lockstep(model, params, PROMPTS[:2], gen_cfg)
    assert [c.tokens for c in srv.run(PROMPTS[:2])] == ref
    summ = srv.summary()
    assert summ["device_loop_ticks"] == 1
    assert summ["host_roundtrips"] == summ["decode_ticks"]


def test_device_loop_ticks_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="device_loop_ticks"):
        GenerationServer(model, params, _greedy_cfg(),
                         num_slots=2, device_loop_ticks=0)


def test_decode_loop_t1_matches_decode_step(model_and_params):
    """The loop at loop_ticks=1 is decode_step: same token, same
    state (field for field), same carry pytree STRUCTURE (the jit
    contract — a structure change would silently recompile every
    launch)."""
    from paddlefleetx_tpu.models.gpt.generation import (
        LOOP_EXIT_BUDGET, LOOP_EXIT_NONE, decode_loop, decode_step,
    )
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    srv = GenerationServer(model, params, gen_cfg, num_slots=2)
    for p in PROMPTS[:2]:
        srv.submit(p)
    srv._admit()
    model_u, params_u = srv.model, srv.params
    cache, state = srv._cache, srv._state
    c1, s1, h1 = decode_step(model_u, params_u, _snapshot(cache),
                             state, srv._rng, gen_cfg)
    c2, s2, h2 = decode_loop(
        model_u, params_u, cache, state, srv._rng, gen_cfg,
        jnp.int32(0), loop_ticks=1)
    one, loop = _harvest(h1), _harvest(h2)
    assert loop.ticks_run == 1
    # full-T run, nothing else
    assert loop.exit_code == LOOP_EXIT_BUDGET
    # the one-tick program has no loop to exit; otherwise field for
    # field the same harvest
    assert one.exit_code == LOOP_EXIT_NONE
    for a, b in zip(one[:-1], loop[:-1]):
        np.testing.assert_array_equal(a, b)
    assert jax.tree_util.tree_structure(s2) == \
        jax.tree_util.tree_structure(state)
    assert jax.tree_util.tree_structure(c2) == \
        jax.tree_util.tree_structure(cache)
    for a, b in zip(jax.tree_util.tree_leaves(s1),
                    jax.tree_util.tree_leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(c1),
                    jax.tree_util.tree_leaves(c2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_loop_host_flag_exits_after_one_tick(model_and_params):
    """host_flag != 0 at launch -> exactly one tick runs and the exit
    reason says LOOP_EXIT_HOST (the host asked for control back); the
    one tick still matches decode_step."""
    from paddlefleetx_tpu.models.gpt.generation import (
        LOOP_EXIT_HOST, decode_loop, decode_step,
    )
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    srv = GenerationServer(model, params, gen_cfg, num_slots=2)
    for p in PROMPTS[:2]:
        srv.submit(p)
    srv._admit()
    cache, state = srv._cache, srv._state
    _, _, h1 = decode_step(srv.model, srv.params, _snapshot(cache),
                           state, srv._rng, gen_cfg)
    _, _, h8 = decode_loop(
        srv.model, srv.params, cache, state, srv._rng, gen_cfg,
        jnp.int32(1), loop_ticks=8)
    got = _harvest(h8, ticks=8)
    assert got.ticks_run == 1
    assert got.exit_code == LOOP_EXIT_HOST
    np.testing.assert_array_equal(got.window[:, 0, 0],
                                  _harvest(h1).window[:, 0, 0])
    # columns past ticks_run stay at the pad sentinel, and commit
    # nothing
    assert (got.window[:, 1:] == PAD).all()
    assert got.counts.tolist() == [[1] + [0] * 7] * 2


def test_decode_loop_budget_exit(model_and_params):
    """max_dec_len=3 with a 16-tick budget: the loop stops itself
    after exactly 3 ticks (dec_count hit the budget) and reports
    LOOP_EXIT_BUDGET — the host's length eviction fires next."""
    from paddlefleetx_tpu.models.gpt.generation import (
        LOOP_EXIT_BUDGET, decode_loop,
    )
    model, params = model_and_params
    gen_cfg = _greedy_cfg(max_dec=3)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2)
    for p in PROMPTS[:2]:
        srv.submit(p)
    srv._admit()
    _, s2, h = decode_loop(
        srv.model, srv.params, srv._cache, srv._state, srv._rng,
        gen_cfg, jnp.int32(0), loop_ticks=16)
    got = _harvest(h, ticks=16)
    assert got.ticks_run == 3
    assert got.exit_code == LOOP_EXIT_BUDGET
    assert np.asarray(s2.dec_count).tolist() == [3, 3]
    assert got.dec_count.tolist() == [3, 3]


def test_device_loop_exit_counters(model_and_params):
    """One T=4 run over the 6-request trace books every loop launch
    under exactly one serving/loop_exit/* reason, counts device ticks
    apart from round-trips, and sees at least one admission exit
    (queue pending behind full slots) plus the final budget/finish
    exits."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               device_loop_ticks=4)
        srv.run(PROMPTS)
        summ = srv.summary()
        exits = {r: reg.counter(f"serving/loop_exit/{r}")
                 for r in ("finished", "admission", "budget", "drain")}
        assert sum(exits.values()) == summ["host_roundtrips"]
        assert exits["admission"] >= 1       # 6 requests > 2 slots
        assert exits["budget"] >= 1          # rows run to max_dec_len
        assert exits["drain"] == 0
        assert reg.counter("serving/device_ticks") == \
            summ["device_ticks"] == summ["decode_ticks"]
        assert summ["host_roundtrips"] < summ["device_ticks"]
        assert summ["host_roundtrip_p99_ms"] >= \
            summ["host_roundtrip_p50_ms"] > 0
    finally:
        metrics.set_enabled(False)
        reg.reset()


def test_device_loop_serving_smoke_interpret_kernel(model_and_params,
                                                    tmp_path):
    """CI smoke (`-k smoke`), device-loop edition: the T=4 fused loop
    with the RAGGED PALLAS KERNEL in interpret mode, a mid-run
    admission forcing a host-signaled early exit, and the events.jsonl
    trail CI's failure-diagnostics artifact collects."""
    _, params = model_and_params
    kcfg = GPTConfig(**{**CFG.__dict__, "use_flash_attention": True})
    model = GPTForPretraining(kcfg)
    # max_dec (6) > T (4): the first fused launch leaves both slots
    # live, so the mid-run submit below finds them busy and forces
    # host-signaled 1-tick exits until one frees
    gen_cfg = _greedy_cfg(max_dec=6)
    ref = _lockstep(model, params, PROMPTS[:3], gen_cfg)
    events = tmp_path / "events.jsonl"
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               device_loop_ticks=4,
                               events_path=str(events))
        done = {}
        ids = [srv.submit(p) for p in PROMPTS[:2]]
        for c in srv.step():             # first fused launch: 4 ticks
            done[c.request_id] = c
        ids.append(srv.submit(PROMPTS[2]))   # mid-run admission
        _drain(srv, done)
        assert [done[i].tokens for i in ids] == ref
        assert reg.counter("attention/flash_decode_ragged") >= 1
        assert reg.counter("serving/admitted") == 3
        assert reg.counter("serving/evicted") == 3
        assert reg.counter("serving/device_ticks") == \
            srv.summary()["decode_ticks"]
        # the pending admit forced at least one 1-tick host exit
        assert reg.counter("serving/loop_exit/admission") >= 1
        kinds = [json.loads(l)["event"] for l in
                 events.read_text().splitlines()]
        assert kinds[0] == "serving_start"
        assert "serving_admit" in kinds and "serving_evict" in kinds
        start = json.loads(events.read_text().splitlines()[0])
        assert start["loop_ticks"] == 4
    finally:
        metrics.set_enabled(False)
        reg.reset()


# -- int8 KV cache -----------------------------------------------------
#
# kv_cache_dtype="int8" swaps the decode cache storage (int8 K/V +
# per-token fp32 scales, dequant-in-kernel — docs/quantization.md) and
# NOTHING else: the acceptance bar is the bf16 parity matrices passing
# unchanged, greedy token-exact against the bf16 lockstep reference.

ICFG = GPTConfig(**{**CFG.__dict__, "kv_cache_dtype": "int8"})


@pytest.mark.parametrize("num_slots,order", [
    (2, [5, 4, 3, 2, 1, 0]),        # reversed admission
    (6, list(range(6))),            # everything admitted at once
])
def test_int8_kv_parity_matrix_greedy(model_and_params, num_slots,
                                      order):
    """Spec-off greedy parity matrix under the int8 KV cache: every
    served completion equals the BF16 lockstep row — per-token abs-max
    KV quantization is argmax-invisible."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(GPTForPretraining(ICFG), params, gen_cfg,
                           num_slots=num_slots)
    comps = srv.run([PROMPTS[i] for i in order])
    assert [c.tokens for c in comps] == [ref[i] for i in order]


def test_int8_kv_spec_parity_greedy(model_and_params):
    """Spec-on greedy under int8 KV: drafting, the k+1 verify window,
    and rejected-token rollback all read the quantized cache — tokens
    still match the bf16 spec-OFF lockstep reference."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    ref = _lockstep(model, params, PROMPTS, gen_cfg)
    srv = GenerationServer(GPTForPretraining(ICFG), params,
                           _spec_cfg(gen_cfg, 3), num_slots=2)
    comps = srv.run(PROMPTS)
    assert [c.tokens for c in comps] == ref


@pytest.mark.parametrize("strategy", ["greedy", "sampling"])
def test_int8_kv_device_loop_t16_parity(model_and_params, strategy):
    """T=16 fused decode loop under int8 KV == the T=1 int8 server ==
    (greedy) the bf16 lockstep rows: multi-token quantized cache
    writes inside the loop body are tick-order invariant."""
    model, params = model_and_params
    if strategy == "greedy":
        gen_cfg = _greedy_cfg()
    else:
        gen_cfg = GenerationConfig(
            max_dec_len=8, decode_strategy="sampling", top_k=8,
            top_p=0.9, temperature=0.7, eos_token_id=EOS,
            pad_token_id=PAD)
    imodel = GPTForPretraining(ICFG)
    ref, _ = _loop_run(imodel, params, gen_cfg, 1)
    out, summ = _loop_run(imodel, params, gen_cfg, 16)
    assert out == ref
    if strategy == "greedy":
        assert ref == [
            r for r in _lockstep(model, params, PROMPTS, gen_cfg)]


def test_paged_int8_kv_spec_serving_smoke_interpret_kernel(
        paged512_model_and_params, tmp_path):
    """CI smoke (`-k smoke`), int8-KV edition: a SHARED-PREFIX paged
    pool in int8 with the interpret-mode dequant-in-kernel VERIFY
    kernel (`attention/flash_decode_paged_verify_int8`) carrying the
    speculative ticks, COW prefix pages (values AND scales) shared
    across rows, greedy parity vs the bf16 lockstep rows, and the
    drained pool whole."""
    model, params = paged512_model_and_params
    kcfg = GPTConfig(**{**PCFG512.__dict__,
                        "use_flash_attention": True,
                        "kv_cache_dtype": "int8"})
    imodel = GPTForPretraining(kcfg)
    gen_cfg = _greedy_cfg(max_dec=4)
    rng = np.random.default_rng(9)
    sys_prompt = rng.integers(0, EOS, 130).tolist()
    p_shared = sys_prompt[:128] + rng.integers(0, EOS, 40).tolist()
    prompts = [sys_prompt, p_shared]
    ref = _lockstep(model, params, prompts, gen_cfg)
    events = tmp_path / "events.jsonl"
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        srv = GenerationServer(imodel, params,
                               _spec_cfg(gen_cfg, 3), num_slots=2,
                               page_size=128, pool_pages=12,
                               prefill_chunk_pages=1,
                               events_path=str(events))
        done = {}
        ids = [srv.submit(sys_prompt)]
        for _ in range(2):            # sys prompt's pages registered
            for c in srv.step():
                done[c.request_id] = c
        ids.append(srv.submit(p_shared))
        _drain(srv, done)
        assert [done[i].tokens for i in ids] == ref
        assert reg.counter(
            "attention/flash_decode_paged_verify_int8") >= 1
        assert reg.counter("attention/flash_decode_paged_verify") == 0
        assert srv._alloc.stats["prefix_hits"] >= 1
        summ = srv.summary()
        assert summ["kv_cache_dtype"] == "int8"
        assert summ["pool_bytes"] == pool_bytes(
            kcfg.num_layers, kcfg.num_attention_heads, kcfg.head_dim,
            128, 12, "int8")
        srv._alloc.check()
        assert srv._alloc.pages_in_use == 0
    finally:
        metrics.set_enabled(False)
        reg.reset()


# -- hierarchical KV cache (host spill tier) ---------------------------
#
# host_pool_bytes adds a bounded pinned-host tier under the paged pool
# (docs/inference.md, "Hierarchical KV cache"): registered pages spill
# HBM->host at refcount zero instead of dying, registry hits rehydrate
# them into fresh page ids instead of re-prefilling, and the store
# survives a restart through core/checkpoint.py. The acceptance bar:
# on traces whose KV footprint exceeds the HBM pool, the tier must be
# invisible in the tokens and visible in the prefill counters.

ICFG512 = GPTConfig(**{**PCFG512.__dict__, "kv_cache_dtype": "int8"})


@pytest.fixture(scope="module")
def tiered_int8_model_and_params():
    model = GPTForPretraining(ICFG512)
    variables = model.init({"params": jax.random.key(0)},
                           jnp.zeros((1, 8), jnp.int32))
    return model, variables["params"]


def _conv_trace(seed=11, users=3, turns=2, sys_len=130):
    """Seeded multi-turn conversations: one shared system prompt, each
    turn resubmitting a user's grown history — every turn's KV is a
    chain-prefix of the next, the trace the spill tier exists for."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, EOS, sys_len).tolist()
    hist = [list(system) for _ in range(users)]
    waves = []
    for _ in range(turns):
        wave = []
        for u in range(users):
            hist[u] = hist[u] + rng.integers(
                0, EOS, 12 + 7 * u).tolist()
            wave.append(list(hist[u]))
        waves.append(wave)
    return waves


def _serve_tiered_trace(model, params, gen_cfg, waves, **kw):
    """Run the waves one at a time (between waves every conversation's
    refcounts hit zero — the spill window) and return (tokens, summary)."""
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           rng=jax.random.key(5), page_size=128,
                           prefill_chunk_pages=1, prefix_sharing=True,
                           **kw)
    out = [[c.tokens for c in srv.run(w)] for w in waves]
    summ = srv.summary()
    srv._alloc.check()
    srv.close()
    return out, summ


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("spec", [False, True])
@pytest.mark.parametrize("strategy", ["greedy", "sampling"])
def test_tiered_parity_matrix(paged512_model_and_params,
                              tiered_int8_model_and_params,
                              strategy, spec, kv):
    """The hierarchical-cache acceptance pin: on a multi-turn trace
    whose KV footprint exceeds the tiered server's HBM pool (5 pages
    against 10+ pages of conversations), tiered output is
    token-identical to an untiered server with an unlimited pool —
    greedy and sampled, bf16 and int8 KV, spec on and off — while
    re-prefilling strictly fewer chunks (the rehydrate win)."""
    model, params = (paged512_model_and_params if kv == "bf16"
                     else tiered_int8_model_and_params)
    if strategy == "greedy":
        gen_cfg = _greedy_cfg(max_dec=4)
    else:
        gen_cfg = GenerationConfig(
            max_dec_len=4, decode_strategy="sampling", top_k=8,
            top_p=0.9, temperature=0.7, eos_token_id=EOS,
            pad_token_id=PAD)
    if spec:
        gen_cfg = _spec_cfg(gen_cfg, 2)
    waves = _conv_trace()
    tiered, ts = _serve_tiered_trace(
        model, params, gen_cfg, waves,
        pool_pages=5, host_pool_bytes=1 << 20)
    untiered, us = _serve_tiered_trace(
        model, params, gen_cfg, waves, pool_pages=64)
    assert tiered == untiered
    assert ts["tiered"] is True and ts["spills"] > 0
    assert ts["rehydrates"] > 0
    assert ts["prefill_chunks"] < us["prefill_chunks"]


def test_tiered_spill_rehydrate_batched_dispatch(
        paged512_model_and_params, monkeypatch):
    """Pinned dispatch-count contract: spilling N pages at a yield
    point is ONE stacked ``gather_kv_pages`` dispatch and
    rehydrating N pages at admission is ONE stacked
    ``scatter_kv_pages`` dispatch — never a per-page device loop.
    Counted by wrapping the entry points serving.py actually calls;
    the totals must still reconcile with the spill/rehydrate
    counters, so a batch can't hide dropped pages."""
    import paddlefleetx_tpu.core.serving as serving_mod
    model, params = paged512_model_and_params
    gathers, scatters = [], []
    real_gather = serving_mod.gather_kv_pages
    real_scatter = serving_mod.scatter_kv_pages

    def counting_gather(cache, pids):
        gathers.append(int(pids.shape[0]))
        return real_gather(cache, pids)

    def counting_scatter(cache, data, pids):
        scatters.append(int(pids.shape[0]))
        return real_scatter(cache, data, pids)

    monkeypatch.setattr(serving_mod, "gather_kv_pages",
                        counting_gather)
    monkeypatch.setattr(serving_mod, "scatter_kv_pages",
                        counting_scatter)
    # exact-repeat waves: wave 2 resubmits wave 1's prompts verbatim,
    # so each admission is a whole-prompt registry hit that must
    # rehydrate BOTH of the prompt's spilled pages at once
    rng = np.random.default_rng(11)
    wave = [rng.integers(0, EOS, n).tolist() for n in (260, 270, 280)]
    waves = [wave, [list(p) for p in wave]]
    _, ts = _serve_tiered_trace(model, params, _greedy_cfg(max_dec=4),
                                waves, pool_pages=7,
                                host_pool_bytes=1 << 20)
    assert ts["spills"] >= 2 and ts["rehydrates"] >= 2
    # every spilled/rehydrated page went through a counted dispatch
    assert sum(gathers) == ts["spills"]
    assert sum(scatters) == ts["rehydrates"]
    # batching is real: strictly fewer dispatches than pages, and at
    # least one dispatch moved several pages at once
    assert len(gathers) < ts["spills"] and max(gathers) >= 2
    assert len(scatters) < ts["rehydrates"] and max(scatters) >= 2


def test_tiered_cow_divergent_write_splits_in_hbm(
        paged512_model_and_params):
    """COW across tiers: two requests admitting the SAME prompt off a
    rehydrated page share it refcount-2; their divergent sampled
    decode writes must split in HBM (cow_splits), never mutate the
    host copy — proven by a third admission after everything spilled
    again still matching the untiered server token-for-token."""
    model, params = paged512_model_and_params
    gen_cfg = GenerationConfig(
        max_dec_len=4, decode_strategy="sampling", top_k=8,
        top_p=0.9, temperature=0.7, eos_token_id=EOS, pad_token_id=PAD)
    rng = np.random.default_rng(17)
    prompt = rng.integers(0, EOS, 140).tolist()
    waves = [[prompt], [list(prompt), list(prompt)], [list(prompt)]]
    tiered, ts = _serve_tiered_trace(
        model, params, gen_cfg, waves,
        pool_pages=5, host_pool_bytes=1 << 20)
    untiered, _ = _serve_tiered_trace(
        model, params, gen_cfg, waves, pool_pages=64)
    assert tiered == untiered
    assert ts["rehydrates"] > 0
    assert ts["cow_splits"] >= 1


def test_tiered_spill_rehydrate_serving_smoke_interpret_kernel(
        paged512_model_and_params, tmp_path):
    """CI smoke (`-k smoke`), tiered edition: the spill->rehydrate
    cycle on a deliberately tiny HBM pool under the interpret-mode
    paged kernel, with the flight recorder proving spills drain ONLY
    at the device-loop yield point (every `serving_spill` shares its
    tick/round-trip stamp with a `serving_yield`)."""
    _, params = paged512_model_and_params
    kcfg = GPTConfig(**{**PCFG512.__dict__,
                        "use_flash_attention": True})
    model = GPTForPretraining(kcfg)
    gen_cfg = _greedy_cfg(max_dec=4)
    waves = _conv_trace(seed=9)
    ref = _lockstep(model, params, [p for w in waves for p in w],
                    gen_cfg)
    events = tmp_path / "events.jsonl"
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               rng=jax.random.key(5), page_size=128,
                               pool_pages=5, prefill_chunk_pages=1,
                               prefix_sharing=True,
                               host_pool_bytes=1 << 20,
                               events_path=str(events))
        toks = []
        for w in waves:
            toks.extend(c.tokens for c in srv.run(w))
        assert toks == ref
        assert reg.counter("attention/flash_decode_paged") >= 1
        assert reg.counter("serving/spill") == \
            srv._alloc.stats["spills"] > 0
        assert reg.counter("serving/rehydrate") == \
            srv._alloc.stats["rehydrates"] > 0
        summ = srv.summary()
        assert summ["tiered"] is True
        assert summ["host_pages_cap"] >= 1
        assert summ["rehydrate_p99_ms"] > 0
        srv._alloc.check()
        srv.close()
        evs = [json.loads(l) for l in events.read_text().splitlines()]
        start = [e for e in evs if e["event"] == "serving_start"]
        assert start and start[0]["host_pages"] >= 1
        spills = [e for e in evs if e["event"] == "serving_spill"]
        yields = {(e["ticks"], e["roundtrips"]) for e in evs
                  if e["event"] == "serving_yield"}
        assert spills and yields
        for e in spills:  # drained only at the yield point
            assert (e["ticks"], e["roundtrips"]) in yields
        assert any(e["event"] == "serving_rehydrate" for e in evs)
        assert any(e.get("rehydrated") for e in evs
                   if e["event"] == "serving_admit")
    finally:
        metrics.set_enabled(False)
        reg.reset()


def test_prefix_store_persistence_roundtrip(paged512_model_and_params,
                                            tmp_path):
    """export -> save (manifest-committed) -> load (verified) ->
    import into a FRESH server: the adopter serves the same trace with
    rehydrates instead of prefill chunks, token-identically; a corrupt
    store is refused on load and the server just starts cold."""
    from paddlefleetx_tpu.core.checkpoint import (
        load_prefix_store, save_prefix_store,
    )
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=4)
    waves = _conv_trace(seed=13)
    kw = dict(num_slots=2, rng=jax.random.key(5), page_size=128,
              pool_pages=5, prefill_chunk_pages=1, prefix_sharing=True,
              host_pool_bytes=1 << 20)
    srv1 = GenerationServer(model, params, gen_cfg, **kw)
    ref = [[c.tokens for c in srv1.run(w)] for w in waves]
    store = srv1.export_prefix_store()
    s1 = srv1.summary()
    srv1.close()
    assert store and store["pages"] and store["page_size"] == 128
    path = str(tmp_path / "store")
    save_prefix_store(path, store)
    loaded = load_prefix_store(path)
    assert loaded is not None
    srv2 = GenerationServer(model, params, gen_cfg, **kw)
    adopted = srv2.import_prefix_store(loaded)
    assert adopted > 0
    warm = [[c.tokens for c in srv2.run(w)] for w in waves]
    ws = srv2.summary()
    srv2.close()
    assert warm == ref
    assert ws["rehydrates"] > 0
    # the cold run's first wave prefilled everything; the warm run's
    # first wave rehydrated the adopted store instead
    assert ws["prefill_chunks"] < s1["prefill_chunks"]
    # a flipped byte in the page store must fail verification closed
    with open(os.path.join(path, "host_pages.npz"), "r+b") as f:
        f.seek(64)
        b = f.read(1)
        f.seek(64)
        f.write(bytes([b[0] ^ 0xFF]))
    assert load_prefix_store(path) is None
    srv3 = GenerationServer(model, params, gen_cfg, **kw)
    assert srv3.import_prefix_store(load_prefix_store(path)) == 0
    srv3.close()


def test_tiered_stale_host_generation_never_rehydrated(
        paged512_model_and_params):
    """The recycled-host-id race, pinned at the mechanism level: when
    the LRU evicts and reuses a host id whose previous spill is still
    in the writer queue, the OLD residency's bytes may publish under
    the reused id. Generation tags must keep them from ever serving a
    rehydrate (`_pop_host_bytes`) and keep an eviction drain from
    clobbering the NEW residency's bytes (`_drop_evicted`)."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=4)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           rng=jax.random.key(5), page_size=128,
                           pool_pages=5, prefill_chunk_pages=1,
                           prefix_sharing=True, host_pool_bytes=1 << 20)
    for w in _conv_trace(seed=3, users=2, turns=1):
        srv.run(w)
    with srv._surface_lock:
        srv._tier.collect(srv._ticks, srv._roundtrips)
    srv._tier.ship()
    srv._tier.await_writer()
    assert srv._alloc.host_pages_resident > 0
    hpid = next(iter(srv._alloc._hosted))
    gen = srv._alloc.host_generation(hpid)
    live = srv._tier._pop_host_bytes(hpid, gen)
    assert live is not None
    # a dead residency's bytes: discarded on pop, never returned
    with srv._tier._lock:
        srv._tier._host_data[hpid] = (gen - 1, "stale")
    assert srv._tier._pop_host_bytes(hpid, gen) is None
    with srv._tier._lock:
        assert hpid not in srv._tier._host_data
    # the live residency's bytes survive a drain of the id's EARLIER
    # eviction (the recycled-id case)...
    with srv._tier._lock:
        srv._tier._host_data[hpid] = (gen, live)
    srv._alloc._host_evicted.append(hpid)
    srv._tier._drop_evicted()
    with srv._tier._lock:
        assert srv._tier._host_data[hpid][0] == gen
    # ...while a dead generation's bytes are dropped by the same drain
    with srv._tier._lock:
        srv._tier._host_data[hpid] = (gen - 1, "stale")
    srv._alloc._host_evicted.append(hpid)
    srv._tier._drop_evicted()
    with srv._tier._lock:
        assert hpid not in srv._tier._host_data
        srv._tier._host_data[hpid] = (gen, live)   # restore for close()
    srv._alloc.check()
    srv.close()


def test_tiered_spill_writer_failure_never_hangs_or_corrupts(
        paged512_model_and_params, monkeypatch):
    """Injected ``jax.device_get`` failure on the kv-spill-writer:
    every spill stage dies, yet the server neither deadlocks waiting
    on the writer (export still returns — the outstanding count drops
    and the spill condition notifies on every path) nor serves wrong
    tokens — failed pages are reaped (evicted, registrations dropped)
    and their prompts re-prefill cold, token-identical to the
    untiered reference."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=4)
    waves = _conv_trace(seed=7)
    untiered, _ = _serve_tiered_trace(model, params, gen_cfg, waves,
                                      pool_pages=64)
    real = jax.device_get

    def boom(x):
        if threading.current_thread().name == "kv-spill-writer":
            raise RuntimeError("injected spill-stage failure")
        return real(x)

    monkeypatch.setattr(jax, "device_get", boom)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           rng=jax.random.key(5), page_size=128,
                           pool_pages=5, prefill_chunk_pages=1,
                           prefix_sharing=True, host_pool_bytes=1 << 20)
    out = [[c.tokens for c in srv.run(w)] for w in waves]
    assert out == untiered
    assert srv._alloc.stats["spills"] > 0   # spills were attempted
    store = srv.export_prefix_store()   # writer wait must return
    assert store is not None and store["pages"] == {}
    assert srv._alloc.host_pages_resident == 0  # every failure reaped
    # rehydrates may still happen through the spill-outbox fast path
    # (the device-side gather is live before the writer's failing
    # device_get ever runs) — those bytes are real, and the parity
    # assert above proves nothing fake was served from a failed stage
    assert srv._tier._writer_thread.is_alive()  # writer survived
    srv._alloc.check()
    srv.close()


def test_spill_rehydrate_batched_single_dispatch(
        paged512_model_and_params, monkeypatch):
    """Spill/rehydrate batching pin: one yield's spill drain issues
    ONE stacked ``gather_kv_pages`` covering every pinned page, and a
    batched rehydrate issues ONE ``scatter_kv_pages`` for all its
    pages — never a device dispatch per page."""
    from paddlefleetx_tpu.core import serving as serving_mod
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=4)
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           rng=jax.random.key(5), page_size=128,
                           pool_pages=7, prefill_chunk_pages=1,
                           prefix_sharing=True,
                           host_pool_bytes=1 << 20)
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, EOS, 260).tolist()      # spans 3 pages
    srv.run([prompt])
    # eviction left the request's registered pages spill-pinned
    calls = {"gather": 0, "scatter": 0}
    real_gather = serving_mod.gather_kv_pages
    real_scatter = serving_mod.scatter_kv_pages

    def gather(cache, pids):
        calls["gather"] += 1
        return real_gather(cache, pids)

    def scatter(cache, data, pids):
        calls["scatter"] += 1
        return real_scatter(cache, data, pids)

    monkeypatch.setattr(serving_mod, "gather_kv_pages", gather)
    monkeypatch.setattr(serving_mod, "scatter_kv_pages", scatter)
    assert srv._tier.pinned >= 2
    with srv._surface_lock:
        srv._tier.collect(srv._ticks, srv._roundtrips)
    srv._tier.ship()
    srv._tier.await_writer()
    assert srv._alloc.stats["spills"] >= 2
    assert calls["gather"] == 1          # N pages, ONE stacked gather
    # the same prompt re-admits as a registry hit: every host page
    # comes back through a single stacked scatter
    calls["gather"] = calls["scatter"] = 0
    out = srv.run([list(prompt)])
    assert out[0].finish_reason in ("eos", "length")
    assert srv._alloc.stats["rehydrates"] >= 2
    assert calls["scatter"] == 1         # N pages, ONE stacked scatter
    srv._alloc.check()
    srv.close()


def test_prefix_store_import_refuses_model_fingerprint_mismatch(
        paged512_model_and_params, tmp_path):
    """KV persisted under one deploy's weights must never warm-start
    different weights with the same geometry — the store carries a
    model fingerprint, it survives the disk round trip, and import
    refuses a mismatch (starting cold) while identical weights on a
    fresh server still adopt."""
    from paddlefleetx_tpu.core.checkpoint import (
        load_prefix_store, save_prefix_store,
    )
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=4)
    kw = dict(num_slots=2, rng=jax.random.key(5), page_size=128,
              pool_pages=5, prefill_chunk_pages=1, prefix_sharing=True,
              host_pool_bytes=1 << 20)
    srv1 = GenerationServer(model, params, gen_cfg, **kw)
    for w in _conv_trace(seed=13, users=2, turns=1):
        srv1.run(w)
    store = srv1.export_prefix_store()
    srv1.close()
    assert store["pages"] and store["model_fingerprint"]
    path = str(tmp_path / "store")
    save_prefix_store(path, store)
    loaded = load_prefix_store(path)
    assert loaded["model_fingerprint"] == store["model_fingerprint"]
    # same config and geometry, DIFFERENT weights: refused
    other = model.init({"params": jax.random.key(42)},
                       jnp.zeros((1, 8), jnp.int32))["params"]
    srv2 = GenerationServer(model, other, gen_cfg, **kw)
    assert srv2.import_prefix_store(loaded) == 0
    assert srv2._alloc.host_pages_resident == 0
    srv2.close()
    # identical weights on a fresh server: adopted as before
    srv3 = GenerationServer(model, params, gen_cfg, **kw)
    assert srv3.import_prefix_store(loaded) > 0
    srv3.close()


def test_tiered_requires_paged_prefix_sharing(model_and_params):
    """host_pool_bytes without a paged pool (or without prefix
    sharing — nothing registered means nothing can ever spill) is a
    configuration error, not a silent no-op."""
    model, params = model_and_params
    gen_cfg = _greedy_cfg()
    with pytest.raises(ValueError):
        GenerationServer(model, params, gen_cfg, num_slots=2,
                         host_pool_bytes=1 << 20)
    with pytest.raises(ValueError):
        GenerationServer(model, params, gen_cfg, num_slots=2,
                         page_size=128, pool_pages=8,
                         prefill_chunk_pages=1, prefix_sharing=False,
                         host_pool_bytes=1 << 20)


# -- host phases and the slow-step record ------------------------------


def _phase_server(paged512_model_and_params, **kw):
    model, params = paged512_model_and_params
    return GenerationServer(model, params, _greedy_cfg(max_dec=12),
                            num_slots=2, page_size=128,
                            prefill_chunk_pages=1, **kw)


#: what a paged, untiered decoding step with no prefill chunk leaves
#: in its record, in order, whatever ``device_loop_ticks`` is
#: (speculative servers add ``draft``)
_DECODING_PHASES = ("expire", "spill_drain", "admit", "prefill_pump",
                    "table_sync", "page_maintenance", "draft",
                    "decode_dispatch", "decode_harvest", "commit",
                    "ship_spills")


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("loop_ticks", [1, 4])
def test_one_step_body_launches_the_modes_own_program(
        paged512_model_and_params, monkeypatch, loop_ticks, spec):
    """``step()`` has one body: each decoding step makes exactly ONE
    launch — ``decode_step`` / ``verify_step`` at T = 1 and never a
    loop, ``decode_loop`` / ``verify_loop`` at T = 4 and never a
    one-tick program — and leaves the same phases in the same order in
    every mode. The plain T = 1 server reads a launch after the next,
    so its first launching step commits no tick and its last
    committing step launches none: there the launches are held to the
    ticks over the run."""
    import paddlefleetx_tpu.core.serving as serving_mod
    calls = dict.fromkeys(
        ("decode_step", "verify_step", "decode_loop", "verify_loop"), 0)
    for name in calls:
        def counted(*a, _real=getattr(serving_mod, name), _name=name,
                    **kw):
            calls[_name] += 1
            return _real(*a, **kw)
        monkeypatch.setattr(serving_mod, name, counted)
    mine = ("verify" if spec else "decode") + \
        ("_step" if loop_ticks == 1 else "_loop")
    gen_cfg = _greedy_cfg(max_dec=12)
    if spec:
        gen_cfg = _spec_cfg(gen_cfg, 2)
    model, params = paged512_model_and_params
    srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                           page_size=128, prefill_chunk_pages=1,
                           device_loop_ticks=loop_ticks)
    srv.submit([5, 9, 2])
    srv.submit([7, 1])
    orders = set()
    try:
        while srv.pending or srv.occupancy:
            before = dict(calls)
            srv.step()
            launched = {n: calls[n] - before[n] for n in calls
                        if calls[n] != before[n]}
            rec = srv.last_step
            if spec or loop_ticks > 1:
                assert launched == ({mine: 1} if rec.ticks else {})
            else:
                assert launched in ({mine: 1}, {})
            if rec.ticks and launched and not rec.chunks:
                orders.add(tuple(rec.phases_ms()))
        assert srv.summary()["decode_ticks"] >= 4
        assert srv.summary()["host_roundtrips"] == calls[mine]
    finally:
        srv.close()
    assert orders == {tuple(p for p in _DECODING_PHASES
                            if spec or p != "draft")
                      + ("unaccounted",)}


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("loop_ticks", [1, 4])
def test_the_launchs_harvest_holds_what_the_program_computed(
        model_and_params, loop_ticks, spec):
    """The ONE array a launch hands the host (``pack_harvest``, asked
    home by ``_launch``) unpacks to exactly the tokens and counts the
    tick body computes, ``finished`` and ``dec_count`` as the
    ``SlotState`` the program returned holds them, the ticks run and
    the exit code — for each of the four programs; first over a full
    launch, then with a finished slot, a slot that reaches
    ``max_dec_len`` and a free slot among the rows."""
    from paddlefleetx_tpu.models.gpt import generation as g
    model, params = model_and_params
    T, k, slots, max_dec = loop_ticks, (2 if spec else 0), 4, 30
    # EOS is held off so that the full launch runs its T ticks
    gen_cfg = dataclasses.replace(_greedy_cfg(max_dec),
                                  min_dec_len=max_dec)
    if spec:
        gen_cfg = _spec_cfg(gen_cfg, k)
    srv = GenerationServer(model, params, gen_cfg, num_slots=slots,
                           device_loop_ticks=T)
    for p in PROMPTS[:3]:
        srv.submit(p)
    srv._admit()                              # slot 3 stays free
    if spec:
        body = jax.jit(g._verify_tick_impl,
                       static_argnames=("model", "gen_cfg"))
    else:
        body = jax.jit(g._decode_tick_impl,
                       static_argnames=("model", "gen_cfg"))
    drafts = np.arange(slots * T * k, dtype=np.int32).reshape(
        slots, T, k) % 90 if spec else None

    def launch(want_ticks, want_exit):
        """One ``_launch`` beside ``want_ticks`` runs of the tick body
        from a copy of the same cache and state."""
        cache, state = _snapshot(srv._cache), srv._state
        window = np.full((slots, T, k + 1), PAD, np.int32)
        counts = np.zeros((slots, T), np.int32)
        for j in range(want_ticks):
            if spec:
                cache, state, w, c = body(
                    srv.model, srv.params, cache, state,
                    jnp.asarray(drafts[:, j]), srv._rng, gen_cfg)
                window[:, j], counts[:, j] = np.asarray(w), np.asarray(c)
            else:
                cache, state, tok = body(srv.model, srv.params, cache,
                                         state, srv._rng, gen_cfg)
                window[:, j, 0], counts[:, j] = np.asarray(tok), 1
        got = g.unpack_harvest(srv._launch(drafts, False), slots, T, k)
        np.testing.assert_array_equal(got.window, window)
        np.testing.assert_array_equal(got.counts, counts)
        # the body's state and the state the program handed back
        for st in (state, srv._state):
            np.testing.assert_array_equal(got.finished,
                                          np.asarray(st.finished))
            np.testing.assert_array_equal(got.dec_count,
                                          np.asarray(st.dec_count))
        assert got.finished.dtype == bool
        assert (got.ticks_run, got.exit_code) == (want_ticks, want_exit)
        return got

    try:
        full = launch(T, g.LOOP_EXIT_BUDGET if T > 1
                      else g.LOOP_EXIT_NONE)
        assert not full.finished.any() and (full.counts[:3] >= 1).all()
        assert full.dec_count[3] == 0
        # slot 1 has emitted EOS and awaits its eviction, slot 2 has
        # one token of budget left, slot 3 is free: a loop hands
        # control back after one tick
        srv._state = srv._state._replace(
            finished=srv._state.finished.at[1].set(True),
            dec_count=srv._state.dec_count.at[2].set(max_dec - 1))
        mixed = launch(1, g.LOOP_EXIT_FINISHED if T > 1
                       else g.LOOP_EXIT_NONE)
        assert mixed.finished.tolist() == [False, True, False, False]
        assert mixed.dec_count[2] == max_dec and mixed.dec_count[3] == 0
        assert mixed.window[1, 0, 0] == PAD == mixed.window[3, 0, 0]
        assert mixed.counts[2, 0] == 1        # t0 alone fits the budget
    finally:
        srv.close()


def _steady_server(paged512_model_and_params, max_dec=40):
    """A paged T = 1 server whose requests never hit EOS."""
    model, params = paged512_model_and_params
    gen_cfg = dataclasses.replace(_greedy_cfg(max_dec),
                                  min_dec_len=max_dec)
    return GenerationServer(model, params, gen_cfg, num_slots=2,
                            page_size=128, prefill_chunk_pages=1)


def test_a_decoding_step_reads_the_device_once(paged512_model_and_params):
    """``serving/d2h_reads`` beside ``serving/device_ticks``: a paged
    server in steady decode at T = 1 pulls ONE array to the host a
    step (the harvest of the launch before its own), and the step
    that ends a prompt's last chunk reads what a plain one reads: the
    logits row stays on the device."""
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    srv = _steady_server(paged512_model_and_params)

    def step():
        before = (reg.counter("serving/d2h_reads"),
                  reg.counter("serving/device_ticks"))
        srv.step()
        return (reg.counter("serving/d2h_reads") - before[0],
                reg.counter("serving/device_ticks") - before[1],
                srv.last_step.chunks)
    try:
        srv.submit([5, 9, 2])
        # its only chunk is its last: nothing is read, and the first
        # launch is read by the NEXT step
        assert step() == (0, 0, 1)
        assert [step() for _ in range(21)] == [(1, 1, 0)] * 21
        assert reg.counter("serving/d2h_reads") == \
            reg.counter("serving/device_ticks") == 21
        # a second prompt, two chunks long: the first chunk's step
        # reads the harvest alone, and so does the last chunk's
        srv.submit(list(range(1, 90)) * 2)
        assert step() == (1, 1, 1)
        assert step() == (1, 1, 1)
        assert step() == (1, 1, 0)
        assert reg.counter("serving/d2h_reads") == \
            reg.counter("serving/device_ticks")
    finally:
        srv.close()
        metrics.set_enabled(False)
        reg.reset()


def test_the_step_that_ends_a_prompt_reads_nothing(
        paged512_model_and_params):
    """No ``jax.Array`` still running is brought home by the steps
    that carry a prompt's chunks, the last one included: its logits
    row goes from the chunk's output to the slot's state inside the
    activation's program, and the first tick goes down behind it."""
    from _served_rows import ends_a_prompt_without_a_read
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    srv = _steady_server(paged512_model_and_params)
    try:
        ends_a_prompt_without_a_read(srv, list(range(1, 90)) * 2)
    finally:
        srv.close()
        metrics.set_enabled(False)
        reg.reset()


def test_prompt_lengths_compile_one_activation(paged512_model_and_params):
    """The row a prompt ends on is a traced scalar of the activation's
    one jitted call: ten prompts of ten lengths (one and two chunks)
    through one server compile it once, and every one of them decodes
    to its lockstep row."""
    from paddlefleetx_tpu.models.gpt.generation import activate_slot
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(4)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 90, n).tolist()
               for n in (1, 2, 5, 17, 64, 127, 128, 129, 200, 250)]
    ref = _lockstep(model, params, prompts, gen_cfg)
    # five slots: a state no other test's server has compiled for
    srv = GenerationServer(model, params, gen_cfg, num_slots=5,
                           page_size=128, prefill_chunk_pages=1,
                           prefix_sharing=False)
    try:
        before = activate_slot._cache_size()
        out = srv.run(prompts)
        assert activate_slot._cache_size() == before + 1
    finally:
        srv.close()
    assert [c.tokens for c in out] == ref


def _hold_rows(srv):
    """Keep the registry's rows where the chunk left them until
    something outside ``step()`` asks for them (``wait``)."""
    land = srv._land_rows
    srv._land_rows = lambda wait=False: land(True) if wait else None
    return lambda: srv.__dict__.pop("_land_rows")


def test_the_registrys_row_comes_home_behind_the_step(
        paged512_model_and_params):
    """A registered prompt's row is the chunk's, on the device, until
    the step after the one that made it, and the host's copy from
    then on: the same float32 either way, so an identical prompt
    admitted BEFORE the swap and one admitted AFTER it both decode to
    the producer's tokens; ``kv_export`` hands out whichever the
    registry holds, and ``np.asarray`` takes either. The two
    activation counters say which kind each admission was handed."""
    from paddlefleetx_tpu.core.paging import prompt_key
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=6)
    base = np.random.default_rng(4).integers(0, EOS, 140).tolist()
    ref = _lockstep(model, params, [base], gen_cfg)[0]
    srv = GenerationServer(model, params, gen_cfg, num_slots=3,
                           page_size=128, pool_pages=12,
                           prefill_chunk_pages=1)
    release = _hold_rows(srv)
    done = {}

    def step():
        for c in srv.step():
            done[c.request_id] = c

    def row():
        return srv._alloc.lookup_prompt(prompt_key(base))[1]
    try:
        ids = [srv.submit(base)]
        step(), step()                  # two chunks, then the launch
        assert isinstance(row(), jax.Array) and len(srv._rows_out) == 1
        pages, exported = srv.kv_export(base)
        srv.kv_export_release(pages)
        assert exported is row()
        want = np.asarray(exported)     # what a read of the device gives
        assert want.dtype == np.float32 and want.shape == (PCFG512.vocab_size,)
        ids.append(srv.submit(base))    # a hit on the device's row
        step()
        assert srv._alloc.stats["prompt_hits"] == 1
        assert (reg.counter("serving/activations/device_row"),
                reg.counter("serving/activations/host_row")) == (2, 0)
        release()
        step()                          # its commit lands the row
        assert not srv._rows_out
        home = row()
        assert isinstance(home, np.ndarray) and home.dtype == np.float32
        np.testing.assert_array_equal(home, want)
        assert srv.kv_export(base)[1] is home
        srv.kv_export_release(pages)
        ids.append(srv.submit(base))    # a hit on the host's row
        _drain(srv, done)
        assert srv._alloc.stats["prompt_hits"] == 2
        assert (reg.counter("serving/activations/device_row"),
                reg.counter("serving/activations/host_row")) == (2, 1)
        assert reg.counter("serving/d2h_reads") == \
            reg.counter("serving/device_ticks")
    finally:
        srv.close()
        metrics.set_enabled(False)
        reg.reset()
    assert [done[i].tokens for i in ids] == [ref] * 3
    srv._alloc.check()
    assert srv._alloc.pages_in_use == 0


def test_a_prefix_store_is_exported_with_host_rows(
        paged512_model_and_params):
    """``export_prefix_store`` is where a registry is serialised, so
    it waits for the rows still out (outside ``step()``): every prompt
    entry of the store carries a numpy row, whenever the server last
    stepped, and a server that adopts the store serves the same
    tokens."""
    model, params = paged512_model_and_params
    gen_cfg = _greedy_cfg(max_dec=4)
    waves = _conv_trace(seed=13)
    kw = dict(num_slots=2, rng=jax.random.key(5), page_size=128,
              pool_pages=5, prefill_chunk_pages=1, prefix_sharing=True,
              host_pool_bytes=1 << 20)
    srv = GenerationServer(model, params, gen_cfg, **kw)
    _hold_rows(srv)
    try:
        ref = [[c.tokens for c in srv.run(w)] for w in waves]
        assert srv._rows_out            # held: no step landed one
        store = srv.export_prefix_store()
        assert not srv._rows_out
    finally:
        srv.close()
    assert store["prompts"]
    for pages, payload in store["prompts"].values():
        assert isinstance(payload, np.ndarray)
        assert payload.dtype == np.float32
    warm = GenerationServer(model, params, gen_cfg, **kw)
    try:
        assert warm.import_prefix_store(store) > 0
        assert [[c.tokens for c in warm.run(w)] for w in waves] == ref
        assert warm.summary()["rehydrates"] > 0
    finally:
        warm.close()


def test_activation_counters_tell_the_rows_apart(
        paged512_model_and_params):
    """One of ``serving/activations/{device_row,host_row}`` a call of
    ``_activate``, both there from the first (a reader tells "never"
    from "no such counter"): chunked completions and a resume after a
    preemption are handed the chunk's row on the device; a peer's
    prefill adopted through ``kv_import`` is a host row."""
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    model, params = paged512_model_and_params
    gen_cfg = dataclasses.replace(_greedy_cfg(8), min_dec_len=8)
    prompts = [[5, 9, 2], list(range(1, 90)) * 2]
    ref = _lockstep(model, params, prompts, gen_cfg)

    def kinds():
        c = reg.snapshot()["counters"]
        return (c["serving/activations/device_row"],
                c["serving/activations/host_row"])
    a = GenerationServer(model, params, gen_cfg, num_slots=2,
                         page_size=128, prefill_chunk_pages=1)
    b = GenerationServer(model, params, gen_cfg, num_slots=2,
                         page_size=128, prefill_chunk_pages=1)
    done = {}
    try:
        ids = [a.submit(p) for p in prompts]
        while not a.prompt_ready(prompts[1]):
            a.step()
        assert kinds() == (2, 0)
        # the second request leaves mid-answer and comes back: its
        # prompt and tokens are prefilled again, to a device row
        part = a.preempt(ids[1])
        assert part is not None and part.finish_reason == "preempted"
        back = a.submit(prompts[1], resume_tokens=part.tokens)
        _drain(a, done)
        assert kinds() == (3, 0)
        assert done[ids[0]].tokens == ref[0]
        assert done[back].tokens == ref[1]
        # a peer adopts a finished prefill: the row crosses as numpy
        rid = a.submit(prompts[1])
        while not a.prompt_ready(prompts[1]):
            a.step()
        pages, last = a.kv_export(prompts[1])
        assert b.kv_import(prompts[1], a.kv_page_data(pages), last,
                           len(pages))
        a.kv_export_release(pages)
        bid = b.submit(prompts[1])
        got = _drain(b, {})
        assert got[bid].tokens == ref[1]
        assert kinds() == (4, 1)
        b.kv_import_release(prompts[1])
        assert _drain(a, {})[rid].tokens == ref[1]
    finally:
        a.close()
        b.close()
        metrics.set_enabled(False)
        reg.reset()


def test_step_record_has_no_state_fetch_phase(paged512_model_and_params):
    """The reads of ``finished`` and ``dec_count`` came home with the
    tokens: no ``serving/step/state_fetch`` in a decoding step's
    record, and ``tick_seconds()`` is still the dispatch plus the
    harvest (of the launch before; the last committing step has no
    launch of its own)."""
    srv = _steady_server(paged512_model_and_params, max_dec=6)
    try:
        srv.submit([5, 9, 2])
        seen = 0
        while srv.pending or srv.occupancy:
            srv.step()
            rec = srv.last_step
            if not rec.ticks:
                continue
            seen += 1
            assert "serving/step/state_fetch" not in rec.phases
            assert "state_fetch" not in rec.phases_ms()
            assert rec.tick_seconds() == pytest.approx(
                rec.phases.get("serving/step/decode_dispatch", 0.0)
                + rec.phases["serving/step/decode_harvest"])
            assert 0.0 < rec.tick_seconds() <= rec.seconds
        assert seen == 6
    finally:
        srv.close()


def test_health_snapshot_follows_a_t1_servers_steps(
        paged512_model_and_params):
    """``/healthz`` is at most one step stale whatever
    ``device_loop_ticks`` is: a T = 1 server's ``ticks`` and
    ``occupancy`` advance across ``step()`` calls with no ``submit()``
    between them."""
    srv = _phase_server(paged512_model_and_params)
    try:
        srv.submit([5, 9, 2])
        assert srv.health_snapshot()["ticks"] == 0
        seen = []
        while srv.pending or srv.occupancy:
            srv.step()
            snap = srv.health_snapshot()
            assert snap["ticks"] == srv.summary()["decode_ticks"]
            assert snap["occupancy"] == srv.occupancy
            seen.append(snap["ticks"])
        assert seen[-1] > seen[0] and seen[-1] >= 8
        assert srv.health_snapshot()["pending"] == 0
    finally:
        srv.close()


def test_pinned_spill_page_goes_back_before_anyone_is_preempted(
        paged512_model_and_params):
    """Pool pressure on a tiered server reclaims a pinned
    to-be-spilled page (idle KV: one lost spill) before it preempts a
    running request — here with no one to preempt at all, where the
    untiered server could only raise."""
    from paddlefleetx_tpu.core.paging import (
        NULL_PAGE, PagePoolExhausted,
    )
    model, params = paged512_model_and_params
    srv = GenerationServer(model, params, _greedy_cfg(max_dec=4),
                           num_slots=2, page_size=128, pool_pages=7,
                           prefill_chunk_pages=1, prefix_sharing=True,
                           host_pool_bytes=1 << 20)
    try:
        srv.run([np.random.default_rng(23).integers(
            0, EOS, 260).tolist()])
        pinned = srv._tier.pinned
        assert pinned >= 2
        while srv._alloc.try_alloc() is not None:
            pass
        with srv._surface_lock:
            for left in range(pinned - 1, -1, -1):
                assert srv._alloc_or_preempt(0) != NULL_PAGE
                assert srv._tier.pinned == left
            with pytest.raises(PagePoolExhausted):
                srv._alloc_or_preempt(0)
        assert srv.summary()["preempted"] == 0
    finally:
        srv.close()


@pytest.mark.parametrize("loop_ticks", [1, 4])
def test_step_record_phases_sum_to_the_root(paged512_model_and_params,
                                            loop_ticks):
    """Every ``step()`` leaves one record whose per-phase seconds,
    with ``unaccounted``, are the root's duration — phases never
    overlap, so what they leave uncovered is small and not negative —
    and the record, not a second clock, feeds the kept series."""
    srv = _phase_server(paged512_model_and_params,
                        device_loop_ticks=loop_ticks)
    rng = np.random.default_rng(1)
    for n in (5, 200, 9):
        srv.submit(rng.integers(0, EOS, n).tolist())
    records = []
    try:
        while srv.pending or srv.occupancy:
            srv.step()
            records.append(srv.last_step)
        summ = srv.summary()
    finally:
        srv.close()
    assert len({id(r) for r in records}) == len(records)
    for rec in records:
        d = rec.as_dict()
        ms = d["phases_ms"]
        assert sum(ms.values()) == pytest.approx(d["dur_ms"], abs=0.02)
        assert -0.01 <= ms["unaccounted"] < 0.25 * d["dur_ms"] + 0.5
        assert all(v >= 0.0 for k, v in ms.items()
                   if k != "unaccounted")
        assert (rec.ticks > 0) == ("decode_harvest" in ms)
        assert (rec.chunks > 0) == ("prefill_dispatch" in ms)
        assert rec.live <= 2 and rec.chunks in (0, 1)
        # the thread's CPU clock is read for a step over the slow-step
        # floor alone (here the ones that compiled), over the step and
        # the lesser span back to the thread's last reading; it lags
        # the wall clock by up to a scheduler tick at each end
        slow = rec.seconds > SLOW_STEP_SECONDS
        assert (d["cpu_ms"] is None) == (d["cpu_span_ms"] is None) \
            == (not slow)
        if slow:
            assert d["dur_ms"] <= d["cpu_span_ms"] < \
                d["dur_ms"] + SLOW_STEP_SECONDS * 1e3 + 5
            assert 0.0 <= d["cpu_ms"] <= d["cpu_span_ms"] + 10
    assert sum(r.chunks for r in records) == summ["prefill_chunks"]
    assert sum(r.ticks for r in records) == summ["decode_ticks"]
    assert sum(r.tokens for r in records) == summ["decode_tokens"]
    decoding = [r for r in records if r.ticks]
    assert len(decoding) == summ["host_roundtrips"]
    # one interval, clocked once: the series hold what the records do
    assert srv._metrics.histogram("serving/tick_ms").count == \
        summ["decode_ticks"]
    h = srv._metrics.histogram("serving/host_roundtrip_ms")
    assert h.count == len(decoding)
    assert h.sum == pytest.approx(
        sum(r.seconds for r in decoding) * 1e3, rel=1e-6)
    assert summ["decode_time_sec"] == pytest.approx(
        sum(r.tick_seconds() for r in decoding), abs=1e-4)


def _quiet_floor(monkeypatch):
    """Six test workers share this host's cores, and a step of a toy
    server that lost its core for 50 ms is no finding: a test that
    asserts NO slow step puts the floor where only its own stall (or a
    fault) reaches it."""
    import paddlefleetx_tpu.core.serving as serving_mod
    monkeypatch.setattr(serving_mod, "SLOW_STEP_SECONDS", 0.25)


def test_slow_step_names_its_phase(paged512_model_and_params, tmp_path,
                                   monkeypatch, caplog):
    """A ``step()`` slowed by one stubbed phase raises
    ``serving/slow_steps`` and ``serving/slow_step/<that phase>`` and
    leaves the whole per-phase record in the log and the event
    stream; the steps around it raise neither."""
    import logging
    import time as _time
    _quiet_floor(monkeypatch)
    events = tmp_path / "events.jsonl"
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    srv = _phase_server(paged512_model_and_params,
                        events_path=str(events))
    try:
        srv.submit([5, 9, 2])
        srv.submit([7, 1])
        for _ in range(11):                 # the history to judge by:
            srv.step()                      # two chunk steps, nine plain
        assert srv.last_step.ticks == 1
        assert reg.counter("serving/slow_steps") == 0
        plain = srv._page_maintenance

        def stalled(*a, **kw):
            _time.sleep(0.4)
            return plain(*a, **kw)
        monkeypatch.setattr(srv, "_page_maintenance", stalled)
        from paddlefleetx_tpu.utils.log import logger as pfx_logger
        monkeypatch.setattr(pfx_logger, "propagate", True)
        with caplog.at_level(logging.WARNING):
            srv.step()
        monkeypatch.setattr(srv, "_page_maintenance", plain)
        slow = srv.last_step
        assert slow.seconds > 0.4
        assert reg.counter("serving/slow_steps") == 1
        assert reg.counter("serving/slow_step/page_maintenance") == 1
        srv.step()                          # and a normal one again
        assert reg.counter("serving/slow_steps") == 1
        snap = reg.snapshot()["counters"]
        assert [k for k in snap if k.startswith("serving/slow_step/")] \
            == ["serving/slow_step/page_maintenance"]
    finally:
        srv.close()
        metrics.set_enabled(False)
        reg.reset()
    (ev,) = [e for e in read_events(str(events))
             if e["event"] == "serving_slow_step"]
    assert ev["worst"] == "page_maintenance"
    assert ev["phases_ms"]["page_maintenance"] >= 400.0
    assert ev["dur_ms"] > 5 * ev["median_ms"]
    assert sum(ev["phases_ms"].values()) == \
        pytest.approx(ev["dur_ms"], abs=0.02)
    for key in ("start", "live", "queued", "chunks", "ticks", "tokens",
                "cpu_ms", "cpu_span_ms", "cause"):
        assert key in ev, key
    (line,) = [r.getMessage() for r in caplog.records
               if "slow step()" in r.getMessage()]
    assert "page_maintenance" in line and '"phases_ms"' in line


def test_a_normal_run_has_no_slow_step(paged512_model_and_params,
                                       monkeypatch):
    """Compilation in a server's first steps is not a slow step (too
    little history to judge by), and nothing after it is."""
    _quiet_floor(monkeypatch)
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    srv = _phase_server(paged512_model_and_params)
    rng = np.random.default_rng(2)
    try:
        srv.run([rng.integers(0, EOS, n).tolist()
                 for n in (5, 140, 9, 30)])
        assert srv.summary()["decode_ticks"] > 8
        assert reg.counter("serving/slow_steps") == 0
        assert not [k for k in reg.snapshot()["counters"]
                    if k.startswith("serving/slow_step")]
    finally:
        srv.close()
        metrics.set_enabled(False)
        reg.reset()


def _busy(seconds):
    import time as _time
    end = _time.perf_counter() + seconds
    while _time.perf_counter() < end:
        pass


@pytest.mark.parametrize("stall, cause", [
    ("sleep", "host_waiting"), ("busy", "host_busy")])
def test_slow_step_says_whether_the_host_was_busy_or_waiting(
        paged512_model_and_params, tmp_path, monkeypatch, stall, cause):
    """The floor is 50 ms: a step stalled for 0.2 s is caught, and the
    thread's CPU time names the cause beside the phase: a sleep (the
    thread blocked: next to no CPU) counts
    ``serving/slow_step_cause/host_waiting``, a busy loop
    ``host_busy``, although the CPU reading reaches back before the
    step, to a reading the steps before it shared: that reach is under
    the floor and the step over it. Record and event carry ``cpu_ms``
    and the ``cpu_span_ms`` it was spent in."""
    import statistics
    import time as _time

    import paddlefleetx_tpu.core.serving as serving_mod
    assert serving_mod.SLOW_STEP_SECONDS == 0.05
    events = tmp_path / "events.jsonl"
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    srv = _phase_server(paged512_model_and_params,
                        events_path=str(events))
    try:
        srv.submit([5, 9, 2])
        srv.submit([7, 1])
        for _ in range(11):                 # the history to judge by:
            srv.step()                      # two chunk steps, nine plain
        reg.reset()                         # a loaded host's own, if any
        plain = srv._page_maintenance

        def stalled(*a, **kw):
            (_time.sleep if stall == "sleep" else _busy)(0.2)
            return plain(*a, **kw)
        monkeypatch.setattr(srv, "_page_maintenance", stalled)
        # a fifth of a second at five times a median well under 40 ms
        median = statistics.median(srv._recent_steps[False])
        assert median < 0.04, median
        # the worst a step can meet: a reading nearly as old as it
        # may be, and the thread at work (before a sleep) or idle
        # (before a busy loop) ever since
        del serving_mod._thread.cpu_mark
        serving_mod._cpu_mark()
        (_busy if stall == "sleep" else _time.sleep)(
            SLOW_STEP_SECONDS - 0.012)
        srv.step()
        slow = srv.last_step
        # read before close(): its read of the launch in flight is a
        # step of its own and takes a whole tick, 50-60 ms on a slow
        # host, which is over the floor too
        counters = reg.snapshot()["counters"]
        slow_events = [e for e in read_events(str(events))
                       if e["event"] == "serving_slow_step"]
    finally:
        srv.close()
        metrics.set_enabled(False)
    reg.reset()
    assert counters["serving/slow_steps"] == 1
    assert counters["serving/slow_step/page_maintenance"] == 1
    assert [k for k in counters
            if k.startswith("serving/slow_step_cause/")] \
        == ["serving/slow_step_cause/" + cause]
    assert slow.seconds >= 0.2
    assert slow.seconds <= slow.cpu_span < slow.seconds + SLOW_STEP_SECONDS
    if stall == "sleep":
        assert slow.cpu_seconds < 0.5 * slow.cpu_span
    else:
        assert slow.cpu_seconds >= 0.5 * slow.cpu_span
    (ev,) = slow_events
    assert ev["cause"] == cause and ev["worst"] == "page_maintenance"
    assert ev["cpu_ms"] == round(slow.cpu_seconds * 1e3, 3)
    assert ev["cpu_span_ms"] == round(slow.cpu_span * 1e3, 3)


def test_a_step_is_judged_against_steps_of_its_own_kind(
        paged512_model_and_params):
    """A step that carries a prefill chunk is a few times one that does
    not, so each kind has its own history: a prompt's last chunk after
    64 short decoding steps (70 ms against a 13 ms median: the first
    request on an empty server) is no slow step, whether the chunk
    steps' own history is still too short to judge by or holds 45 ms
    steps; a 70 ms step WITHOUT a chunk is one, and so is a chunk step
    of five times its own kind's median."""
    from paddlefleetx_tpu.core.serving import (SLOW_STEP_HISTORY, STEP,
                                               StepRecord, _cpu_mark)
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    srv = _phase_server(paged512_model_and_params)

    def step(ms, chunks, worst):
        rec = StepRecord()
        rec.ticks, rec.chunks, rec.live = 1, chunks, 1
        rec.phases = {STEP: ms / 1e3, STEP + "/" + worst: 0.9 * ms / 1e3}
        with srv._surface_lock:
            srv._account_step(rec, _cpu_mark())
        return reg.counter("serving/slow_steps")
    try:
        for _ in range(SLOW_STEP_HISTORY):
            assert step(13, 0, "decode_harvest") == 0
        assert step(70, 1, "prefill_dispatch") == 0      # no history yet
        for _ in range(8):
            assert step(45, 1, "decode_harvest") == 0
        assert step(70, 1, "prefill_dispatch") == 0      # under 5 x 45
        assert len(srv._recent_steps[True]) == 10
        assert len(srv._recent_steps[False]) == SLOW_STEP_HISTORY
        assert step(70, 0, "decode_harvest") == 1       # over 5 x 13
        assert step(240, 1, "prefill_dispatch") == 2     # over 5 x 45
        counters = reg.snapshot()["counters"]
    finally:
        srv.close()
        metrics.set_enabled(False)
        reg.reset()
    assert counters["serving/slow_step/decode_harvest"] == 1
    assert counters["serving/slow_step/prefill_dispatch"] == 1
    assert sum(v for k, v in counters.items()
               if k.startswith("serving/slow_step_cause/")) == 2


class _RecordedTraceMe:
    """Stands in for ``jax.profiler.TraceAnnotation`` while a session
    records: every begin and end, in order."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("B", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("E", self.name))

    @staticmethod
    def is_enabled():
        return True


def _account_of(name):
    head, *fields = name.split(" ")
    assert head == "serving/step_account", name
    return {k: int(v) for k, v in (f.split("=") for f in fields)}


@pytest.mark.parametrize("drive", ["step", "prefill_step"])
def test_every_step_puts_its_account_after_its_root(
        paged512_model_and_params, monkeypatch, drive):
    """While a profiler session records, every ``step()`` and every
    ``prefill_step()`` writes ONE ``serving/step_account`` point,
    directly after the root it belongs to, whose counts are
    ``last_step``'s; a step has the phase ``prefill_dispatch`` exactly
    when it launched a chunk, and the phases still sum to the root."""
    import paddlefleetx_tpu.observability.trace as trace_mod
    monkeypatch.setattr(_RecordedTraceMe, "log", [])
    monkeypatch.setattr(trace_mod, "TraceAnnotation", _RecordedTraceMe)
    srv = _phase_server(paged512_model_and_params)
    rng = np.random.default_rng(4)
    for n in (5, 300, 9):
        srv.submit(rng.integers(0, EOS, n).tolist())
    records = []
    try:
        if drive == "step":
            while srv.pending or srv.occupancy:
                srv.step()
                records.append(srv.last_step)
        else:
            progress = True
            while progress:                 # the last makes none: a root
                progress = srv.prefill_step()
                records.append(srv.last_step)
    finally:
        srv.close()
    log = _RecordedTraceMe.log
    ends = [i for i, ev in enumerate(log) if ev == ("E", "serving/step")]
    assert len(ends) == len(records) >= 4
    for i, rec in zip(ends, records):
        # the point opens and closes straight after the root closes
        (b, name), (e, same) = log[i + 1], log[i + 2]
        assert (b, e) == ("B", "E") and name == same
        assert _account_of(name) == {
            "ticks": rec.ticks, "chunks": rec.chunks, "live": rec.live,
            "committed": rec.tokens}
        ms = rec.phases_ms()
        assert (rec.chunks > 0) == ("prefill_dispatch" in ms)
        assert sum(ms.values()) == pytest.approx(
            rec.seconds * 1e3, abs=0.02)
        assert ms["unaccounted"] >= -0.01
    assert sum(n.startswith("serving/step_account")
               for b, n in log if b == "B") == len(records)
    # neither the root's pattern nor its children's matches the point
    assert not [n for _, n in log if n.startswith("serving/step_account")
                and (n == "serving/step" or n.startswith("serving/step/"))]
    chunks = sum(r.chunks for r in records)
    if drive == "step":
        assert chunks == 5                  # 1 + 3 + 1 chunks of 128
        assert sum(r.ticks for r in records) > 8
        assert any(r.ticks and r.chunks for r in records)
    else:
        # two slots and nothing decodes: the third prompt stays queued
        assert chunks == 4 and records[-1].queued == 1
        assert not any(r.ticks or r.tokens for r in records)


# -- the deferred harvest -------------------------------------------------
#
# A plain T = 1 server launches tick n+1 before it reads tick n
# (core/serving.py, "Deferred harvest"). The reference below is the
# SAME server held to the synchronous order through the one thing that
# decides it, ``_read_now``: one step body, two orders, equal tokens.

def _synchronous(monkeypatch):
    """Every server built from here on reads each launch in the step
    that made it (the order before the harvest was deferred)."""
    monkeypatch.setattr(GenerationServer, "_read_now",
                        lambda self: "test")


def _small_family(name):
    """``(model, params, server kwargs, vocab)`` of a tiny member of
    each family the server holds a cache for: contiguous rows, the
    page pool, rings of window pages beside it (SmallThinker), rows of
    recurrent state beside it (Solar-Open2)."""
    if name in ("gpt", "gpt-paged"):
        cfg = dataclasses.replace(CFG, max_position_embeddings=512) \
            if name == "gpt-paged" else CFG
        model = GPTForPretraining(cfg)
        kw = dict(page_size=128, prefill_chunk_pages=1, pool_pages=9) \
            if name == "gpt-paged" else {}
    elif name == "smallthinker":
        from paddlefleetx_tpu.models.smallthinker import (
            SmallThinkerConfig, SmallThinkerForCausalLM)
        model = SmallThinkerForCausalLM(SmallThinkerConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_ffn_hidden_size=16, moe_num_primary_experts=4,
            moe_num_active_primary_experts=2, sliding_window_size=160,
            max_position_embeddings=1024, initializer_range=0.2))
        kw = dict(page_size=128, prefill_chunk_pages=1, pool_pages=12)
    else:
        from paddlefleetx_tpu.models.solar_open2 import (
            SolarOpen2Config, SolarOpen2ForCausalLM)
        model = SolarOpen2ForCausalLM(SolarOpen2Config(
            vocab_size=96, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_num_heads=8, linear_head_dim=128, n_routed_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=16,
            max_position_embeddings=1024, initializer_range=0.2))
        kw = dict(page_size=128, prefill_chunk_pages=1, pool_pages=12)
    params = model.init({"params": jax.random.key(0)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, kw


@pytest.fixture(scope="module", params=["gpt", "gpt-paged",
                                        "smallthinker", "solar-open2"])
def family(request):
    return (request.param,) + _small_family(request.param)


def _staggered(srv, prompts, after=()):
    """Two prompts at once, the rest one a step from the third step
    on (slots at ragged depths, each freed slot taken at once);
    ``after(srv)`` after every step. Tokens by submission order."""
    done, ids = {}, [srv.submit(p) for p in prompts[:2]]
    rest = list(prompts[2:])
    steps = 0
    while srv.work_pending() or rest:
        if rest and steps >= 2:
            ids.append(srv.submit(rest.pop(0)))
        for c in srv.step():
            done[c.request_id] = c
        steps += 1
        for check in after:
            check(srv)
        assert steps < 2000
    assert srv._inflight is None
    return [done[i] for i in ids]


def _unread_write_is_mapped(srv):
    """Hazard 2: the page the launch in flight writes into is still
    the slot's, whatever the commit trimmed."""
    srv.check_alloc()
    if not srv.paged:
        return
    for slot, req in enumerate(srv._slots):
        if req is not None and req.get("active") and req["ahead"]:
            j = req["cur_len"] // srv._page
            assert j < req["num_pages"], (req["cur_len"], j)
            assert srv._pt[slot, j] != 0


@pytest.mark.parametrize("strategy", ["greedy", "sampling"])
def test_deferred_order_commits_the_synchronous_servers_tokens(
        family, strategy, monkeypatch):
    """The staggered-admission matrix, each family of cache: a server
    that reads a launch after the next one completes every request
    with the tokens, and for the reason, of the same server held to
    the synchronous order. EOS is a token the run really emits, so
    some requests end on a read the next launch did not wait for."""
    name, model, params, kw = family
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 90, n).tolist()
               for n in (5, 2, 130, 3, 1, 9)]
    if name == "gpt":
        prompts[2] = prompts[2][:30]

    def run(eos):
        if strategy == "greedy":
            gen_cfg = dataclasses.replace(_greedy_cfg(12),
                                          eos_token_id=eos,
                                          pad_token_id=eos)
        else:
            gen_cfg = GenerationConfig(
                max_dec_len=12, decode_strategy="sampling", top_k=8,
                top_p=0.9, temperature=0.9, eos_token_id=eos,
                pad_token_id=eos)
        srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               rng=jax.random.key(7), **kw)
        try:
            return _staggered(srv, prompts, (_unread_write_is_mapped,)), \
                srv.summary()
        finally:
            srv.close()
    # an EOS that cuts some answers short: the fourth token of the
    # first answer of a run that has none
    plain, _ = run(95)
    eos = plain[0].tokens[3]
    deferred, summ = run(eos)
    _synchronous(monkeypatch)
    want, want_summ = run(eos)
    assert [(c.tokens, c.finish_reason) for c in deferred] == \
        [(c.tokens, c.finish_reason) for c in want]
    assert {c.finish_reason for c in want} == {"eos", "length"}
    assert summ["decode_tokens"] == want_summ["decode_tokens"]
    assert all(c.ttft_ms is not None and c.ttft_ms > 0 for c in deferred)


@pytest.mark.parametrize("case", ["length_on_a_boundary",
                                  "eos_on_a_boundary",
                                  "slot_taken_at_once"])
def test_a_page_boundary_and_a_slot_taken_at_once(
        paged512_model_and_params, monkeypatch, case):
    """Hazards 1-3 of the deferred read, ``check_alloc()`` after every
    step. A request whose tokens fill a page to its last column: the
    commit that brings ``cur_len`` to the boundary must not trim the
    page the launch in flight is writing into (its first column), or
    the remapped page lacks that token's K/V. One that ends exactly
    there on EOS: its void row wrote into a page the eviction has
    released. And a pool so small that the next request is admitted
    into the freed slot AND pages in the very next step, under the
    void row's write."""
    model, params = paged512_model_and_params
    if case == "slot_taken_at_once":
        # 381 + 3 = 384: three pages full, and 4 pages in the pool
        lens, slots, pool = (381, 3, 382, 2), 1, 5
    else:
        lens, slots, pool = (125, 250, 3), 2, 9

    def run(prompts, eos):
        gen_cfg = dataclasses.replace(
            _greedy_cfg(10), eos_token_id=eos, pad_token_id=eos)
        srv = GenerationServer(model, params, gen_cfg, num_slots=slots,
                               page_size=128, prefill_chunk_pages=1,
                               pool_pages=pool)
        try:
            out = _staggered(srv, prompts, (_unread_write_is_mapped,))
            assert srv._alloc.pages_in_use == 0
            return out
        finally:
            srv.close()
    # prompts whose first answer's third token is new in it: as EOS
    # it ends that request with its last page full to the last column
    for seed in range(40):
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(1, 90, n).tolist() for n in lens]
        plain = run(prompts, 95)
        first = plain[0].tokens
        if first[2] not in first[:2]:
            break
    assert all(len(c.tokens) == 10 for c in plain)
    eos = 95 if case == "length_on_a_boundary" else first[2]
    got = run(prompts, eos)
    _synchronous(monkeypatch)
    want = run(prompts, eos)
    assert [(c.tokens, c.finish_reason) for c in got] == \
        [(c.tokens, c.finish_reason) for c in want]
    if case != "length_on_a_boundary":
        assert got[0].finish_reason == "eos" and len(got[0].tokens) == 3
        assert (lens[0] + 3) % 128 == 0


def _launches_made(srv):
    """Decode launches a T = 1 server has made: read, or in flight."""
    return srv._roundtrips + (srv._inflight is not None)


@pytest.mark.parametrize("how", ["drain_now", "drain_two_ticks",
                                 "preempt", "close"])
def test_a_forced_read_loses_no_token_and_commits_none_twice(
        paged512_model_and_params, how):
    """drain(), preempt() and close() with a launch in flight read it
    first: the partial holds one token for every launch made, each
    once, it is a prefix of the uninterrupted answer, and a resumed
    request ends on that answer."""
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    model, params = paged512_model_and_params
    gen_cfg = dataclasses.replace(_greedy_cfg(16), min_dec_len=16)
    prompts = [[5, 9, 2, 7], [11, 3, 8]]

    def server():
        return GenerationServer(model, params, gen_cfg, num_slots=2,
                                page_size=128, prefill_chunk_pages=1)
    ref = server()
    want = [c.tokens for c in ref.run(prompts)]
    ref.close()
    srv = server()
    try:
        ids = [srv.submit(p) for p in prompts]
        for _ in range(6):
            srv.step()
        assert srv._inflight is not None and srv.work_pending()
        made = _launches_made(srv)
        held = [len(r["tokens"]) for r in srv._slots]
        assert held[0] == held[1] + 1 == made - 1   # one launch unread
        if how == "preempt":
            part = srv.preempt(ids[0])
            assert reg.counter("serving/harvest_flushed/preempt") == 1
            assert part.finish_reason == "preempted"
            assert part.tokens == want[0][:made]
            # the other request's row of the same read was committed
            assert len(srv._slots[1]["tokens"]) == made - 1
            done = {}
            while srv.work_pending():
                for c in srv.step():
                    done[c.request_id] = c
            assert done[ids[1]].tokens == want[1]
        elif how == "close":
            srv.close()
            assert reg.counter("serving/harvest_flushed/close") == 1
            assert srv._inflight is None
            assert [len(r["tokens"]) for r in srv._slots] == \
                [made, made - 1]
        else:
            ticks = 0 if how == "drain_now" else 2
            parts = {c.request_id: c
                     for c in srv.drain(max_ticks=ticks)}
            assert reg.counter("serving/harvest_flushed/drain") == \
                1 + ticks
            assert srv._inflight is None and not srv.occupancy
            for i, rid in enumerate(ids):
                assert parts[rid].finish_reason == "preempted"
                assert parts[rid].tokens == \
                    want[i][:made + ticks - i]
            srv.close()
            again = server()
            new = [again.submit(prompts[i],
                                resume_tokens=parts[rid].tokens)
                   for i, rid in enumerate(ids)]
            done = {}
            while again.work_pending():
                for c in again.step():
                    done[c.request_id] = c
            assert [done[n].tokens for n in new] == want
            again.close()
    finally:
        srv.close()
        metrics.set_enabled(False)
        reg.reset()


def test_pool_exhaustion_reads_the_launch_in_flight_before_it_preempts(
        paged512_model_and_params):
    """Where the launch's page needs outrun the free pages the launch
    in flight is read first (``harvest_flushed/preempt``), so the
    victim goes back to the queue with its newest token and the
    answers are the roomy pool's."""
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    model, params = paged512_model_and_params
    gen_cfg = dataclasses.replace(_greedy_cfg(40), min_dec_len=40)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 90, n).tolist() for n in (250, 240)]

    def run(pool):
        srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               page_size=128, prefill_chunk_pages=1,
                               pool_pages=pool, prefix_sharing=False)
        try:
            out = _staggered(srv, prompts, (_unread_write_is_mapped,))
            return [c.tokens for c in out], srv.summary()
        finally:
            srv.close()
    want, _ = run(9)
    assert reg.counter("serving/harvest_flushed/preempt") == 0
    got, summ = run(5)              # 4 pages, and each wants a third
    assert summ["preempted"] >= 1
    assert reg.counter("serving/harvest_flushed/preempt") >= 1
    assert got == want
    metrics.set_enabled(False)
    reg.reset()


def test_the_deferred_harvests_counters(paged512_model_and_params):
    """What the mechanism counts: nearly every decoding step's read
    came after the next launch; the device is read once a launch
    and never for a prompt; a void row at most once a request that
    ended, and never for one that ended on its budget (its row is not
    launched past it: the device's ``dec_count`` never passes
    ``max_dec_len``)."""
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    model, params = paged512_model_and_params
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 90, n).tolist() for n in (4, 140, 7, 2)]

    def budget_kept(srv):
        assert int(np.asarray(srv._state.dec_count).max()) <= 10
    try:
        gen_cfg = dataclasses.replace(_greedy_cfg(10), min_dec_len=10)
        srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               page_size=128, prefill_chunk_pages=1)
        out = _staggered(srv, prompts, (budget_kept,))
        srv.close()
        assert all(c.finish_reason == "length" for c in out)
        c = reg.counter
        steps = c("serving/device_ticks")      # T = 1: a tick a read
        flushed = c("serving/harvest_flushed/idle")
        assert steps == srv.summary()["host_roundtrips"] > 20
        assert c("serving/harvest_deferred") == steps - flushed
        assert c("serving/harvest_deferred") >= \
            steps - (len(prompts) + flushed)
        assert 1 <= flushed <= len(prompts)
        assert c("serving/d2h_reads") == steps
        assert c("serving/harvest_rows_void") == 0
        assert c("serving/decode_tokens") == 10 * len(prompts)
        # answers that end on EOS: a void row for some, one at most
        reg.reset()
        eos = out[0].tokens[4]
        srv = GenerationServer(
            model, params, dataclasses.replace(
                _greedy_cfg(10), eos_token_id=eos, pad_token_id=eos),
            num_slots=2, page_size=128, prefill_chunk_pages=1)
        out = _staggered(srv, prompts)
        srv.close()
        ended = sum(x.finish_reason == "eos" for x in out)
        assert ended >= 1
        assert 1 <= c("serving/harvest_rows_void") <= ended
        assert c("serving/d2h_reads") == c("serving/device_ticks")
    finally:
        metrics.set_enabled(False)
        reg.reset()


@pytest.mark.parametrize("workers", ["lockstep", "async"])
def test_a_fleet_of_deferred_servers_completes_every_request(
        model_and_params, workers):
    """Replicas behind a ``FleetRouter`` hand their completions out a
    ``step()`` late and park on ``work_pending()``, which a launch not
    read yet holds true: every request still completes, with a single
    server's tokens, and the replicas' reads really were deferred."""
    from paddlefleetx_tpu.core.fleet import FleetRouter
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    model, params = model_and_params
    gen_cfg = _greedy_cfg(8)

    def factory(name):
        return GenerationServer(model, params, gen_cfg, num_slots=2,
                                rng=jax.random.PRNGKey(7))
    try:
        single = factory("single")
        want = [c.tokens for c in single.run(PROMPTS)]
        single.close()
        reg.reset()
        fleet = FleetRouter(factory, 2, async_workers=workers == "async")
        got = fleet.run(PROMPTS)
        fleet.close()
        assert [c.tokens for c in got] == want
        assert all(c.finish_reason in ("eos", "length") for c in got)
        assert reg.counter("serving/harvest_deferred") > 0
        assert reg.counter("serving/harvest_deferred") + sum(
            reg.counter("serving/harvest_flushed/" + why)
            for why in ("idle", "close")) == \
            reg.counter("serving/device_ticks")
    finally:
        metrics.set_enabled(False)
        reg.reset()
