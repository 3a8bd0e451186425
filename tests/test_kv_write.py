"""The in-place KV write (``ops/pallas/kv_write.py``) against the XLA
scatter it replaces, bit for bit, in interpret mode on the CPU; the
model's cache branches through it; and a paged server whose greedy
tokens still equal ``generate()``.
"""

import dataclasses
import os

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from paddlefleetx_tpu.core.paging import NULL_PAGE  # noqa: E402
from paddlefleetx_tpu.core.serving import GenerationServer  # noqa: E402
from paddlefleetx_tpu.models.gpt import (  # noqa: E402
    GPTConfig, GPTForPretraining,
)
from paddlefleetx_tpu.models.gpt.generation import (  # noqa: E402
    GenerationConfig, generate, left_pad_batch,
)
from paddlefleetx_tpu.observability import metrics  # noqa: E402
from paddlefleetx_tpu.ops import attention  # noqa: E402
from paddlefleetx_tpu.ops.pallas import kv_write as kw  # noqa: E402

PAGE, H, D = 128, 4, 16
#: page tables of four rows over a 12-page pool; page 0 is NULL_PAGE
TABLE = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [10, 11, 0]])


def _leaf(rng, shape, dtype):
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, shape), dtype)
    return jnp.asarray(rng.normal(size=shape), dtype)


def _bits(a):
    return np.asarray(a).view(np.uint8)


# name -> (leaf dtypes and widths, window, lengths, page table)
CASES = {
    # one token a row: first and last column of a page, a later page
    "decode": ([(jnp.bfloat16, D)], 1, [0, 127, 128, 300], TABLE),
    "window_in_one_page": ([(jnp.bfloat16, D)], 5,
                           [0, 60, 123, 256], TABLE),
    # 126..130 and 254..258 cross a page boundary; 127..131 starts on
    # a page's last column
    "window_straddles_two_pages": ([(jnp.bfloat16, D)], 5,
                                   [126, 254, 127, 3], TABLE),
    # kv_cache_dtype="int8": int8 values and [P, h, 1, page] fp32 scales
    "int8_values_fp32_scales": ([(jnp.int8, D), (jnp.float32, 1)], 1,
                                [5, 127, 128, 300], TABLE),
    "int8_values_fp32_scales_window": (
        [(jnp.int8, D), (jnp.float32, 1)], 4, [126, 3, 255, 200],
        TABLE),
    # rows 1 and 2 are free slots: every position on NULL_PAGE
    "free_rows_on_null_page": (
        [(jnp.bfloat16, D)], 3, [126, 40, 41, 7],
        np.array([[1, 2, 3], [0, 0, 0], [0, 0, 0], [10, 11, 0]])),
    "fp32_values": ([(jnp.float32, D)], 2, [127, 0, 382, 200], TABLE),
    # row 3 is live and 254..258 runs off its two mapped pages: the
    # columns on page 11 are written, those on NULL_PAGE are not
    "window_runs_onto_null_page": ([(jnp.bfloat16, D)], 5,
                                   [0, 60, 123, 254], TABLE),
}


def _write_and_check(leaves, rows, cols, window, b, pages, rng):
    """K and V of every ``(dtype, d)`` through ONE donated call: off
    ``NULL_PAGE`` the scatter's bits, ``NULL_PAGE`` as it was."""
    write = jax.jit(kw.kv_write, donate_argnums=0)
    for dtype, d in leaves:
        pools = [_leaf(rng, (pages, H, d, PAGE), dtype) for _ in "kv"]
        news = [_leaf(rng, (b, window, H, d), dtype) for _ in "kv"]
        want = [pool.at[rows, :, :, cols].set(new)
                for pool, new in zip(pools, news)]
        null = [_bits(pool[NULL_PAGE]) for pool in pools]
        got = write(pools, jnp.asarray(rows), jnp.asarray(cols), news)
        assert len(got) == 2
        for g, w, was in zip(got, want, null):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(_bits(g)[NULL_PAGE + 1:],
                                          _bits(w)[NULL_PAGE + 1:])
            np.testing.assert_array_equal(_bits(g)[NULL_PAGE], was)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kv_write_is_the_scatter_bit_for_bit(case):
    """Every page but NULL_PAGE holds exactly what
    ``leaf.at[rows, :, :, cols].set(new)`` leaves there, K and V in
    one call, the leaves donated as the cache jits donate them;
    NULL_PAGE (the scatter writes the free rows' garbage there) is not
    written at all."""
    leaves, window, lengths, table = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    wpos = np.asarray(lengths)[:, None] + np.arange(window)[None, :]
    rows = np.take_along_axis(table, wpos // PAGE, axis=1)
    _write_and_check(leaves, rows, wpos % PAGE, window, 4, 12, rng)


#: which of 130 rows (two lane groups of the fresh values) are live
MIXES = {
    "none_live": [],
    "one_live": [5],
    "first_and_last_of_a_lane_group": [0, 127, 128],
    "past_one_lane_block": [3, 129],
    "all_live": list(range(130)),
}
KINDS = {
    "bf16": [(jnp.bfloat16, D)],
    "fp32": [(jnp.float32, D)],
    "int8_with_scale_pools": [(jnp.int8, D), (jnp.float32, 1)],
}


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("window", [1, 5])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_kv_write_walks_the_live_rows_only(mix, window, kind):
    """130 slots of two pages each, some free (their table rows all
    NULL_PAGE): the live rows' columns are the scatter's, wherever
    they sit in the walk and in their lane group, and a free row
    writes nothing, NULL_PAGE included."""
    b = 130
    live = np.zeros(b, bool)
    live[MIXES[mix]] = True
    table = np.where(live[:, None],
                     1 + 2 * np.arange(b)[:, None] + np.arange(2), 0)
    lengths = np.arange(b) * 37 % 250         # some windows straddle
    wpos = lengths[:, None] + np.arange(window)[None, :]
    rows = np.take_along_axis(table, wpos // PAGE, axis=1)
    order, n = kw._live_rows(jnp.asarray(rows), True)
    assert int(n) == live.sum()
    assert np.asarray(order)[:int(n)].tolist() == MIXES[mix]
    rng = np.random.default_rng(len(mix) + window)
    _write_and_check(KINDS[kind], rows, wpos % PAGE, window, b,
                     1 + 2 * b, rng)


@pytest.mark.parametrize("window", [1, 5])
def test_kv_write_takes_the_contiguous_slot_cache(window):
    """``[b, h, d, capacity]`` with ``paged=False``: the slot is the
    major index, the 128-column block of its position the "page", and
    index 0 is slot 0, not a null page: every row is written."""
    rng = np.random.default_rng(11)
    caches = [_leaf(rng, (4, H, D, 3 * PAGE), jnp.bfloat16)
              for _ in "kv"]
    news = [_leaf(rng, (4, window, H, D), jnp.bfloat16) for _ in "kv"]
    cols = np.asarray([0, 126, 255, 379])[:, None] + np.arange(window)
    rows = np.broadcast_to(np.arange(4)[:, None], cols.shape)
    order, n = kw._live_rows(jnp.asarray(rows), False)
    assert int(n) == 4 and np.asarray(order).tolist() == [0, 1, 2, 3]
    got = kw.kv_write(caches, jnp.asarray(rows), jnp.asarray(cols),
                      news, paged=False)
    for g, cache, new in zip(got, caches, news):
        want = cache.at[rows, :, :, cols].set(new)
        assert (_bits(want[0]) != _bits(cache[0])).any()
        np.testing.assert_array_equal(_bits(g), _bits(want))


def test_kv_write_rows_past_one_lane_block():
    """The fresh values ride rows-on-lanes, 128 rows a block: row 129
    of 130 finds its column in the second block."""
    rng = np.random.default_rng(12)
    pool = _leaf(rng, (131, H, D, PAGE), jnp.bfloat16)
    new = _leaf(rng, (130, 2, H, D), jnp.bfloat16)
    rows = np.broadcast_to(np.arange(1, 131)[:, None], (130, 2))
    cols = (np.arange(130) * 7 % 127)[:, None] + np.arange(2)
    want = pool.at[rows, :, :, cols].set(new)
    got, = kw.kv_write([pool], jnp.asarray(rows), jnp.asarray(cols),
                       [new])
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("leaf,new,why", [
    ((12, H, D, 100), (4, 1, H, D), "minor dim"),
    ((12, H, D, PAGE), (4, 129, H, D), "window"),
    ((12, H, 12, PAGE), (4, 1, H, 12), "head_dim"),
    ((12, H, D, PAGE), (4, 1, H + 1, D), "do not match"),
])
def test_kv_write_refuses_what_it_cannot_tile(leaf, new, why):
    idx = jnp.zeros(new[:2], jnp.int32)
    with pytest.raises(NotImplementedError, match=why):
        kw.kv_write([jnp.zeros(leaf, jnp.bfloat16)], idx, idx,
                    [jnp.zeros(new, jnp.bfloat16)])


def test_kv_write_refuses_leaves_of_two_shapes():
    """One call walks one block shape: values and an int8 cache's
    scale pools go in two calls (``kv_cache_write`` groups them)."""
    idx = jnp.zeros((4, 1), jnp.int32)
    with pytest.raises(NotImplementedError, match="one shape"):
        kw.kv_write([jnp.zeros((12, H, D, PAGE), jnp.int8),
                     jnp.zeros((12, H, 1, PAGE), jnp.float32)],
                    idx, idx, [jnp.zeros((4, 1, H, D), jnp.int8),
                               jnp.zeros((4, 1, H, 1), jnp.float32)])


def test_dispatch_falls_back_to_the_scatter_and_counts(monkeypatch):
    """Off the TPU (no interpret mode) the kernel refuses: the write
    is today's scatter, counted as a kernel rejection; with
    ``use_flash=False`` it is the scatter by decision, uncounted. The
    counters count leaves; int8 values and their scales are grouped
    into a call each."""
    rng = np.random.default_rng(3)
    pool = _leaf(rng, (12, H, D, PAGE), jnp.bfloat16)
    new = _leaf(rng, (4, 1, H, D), jnp.bfloat16)
    rows = jnp.asarray([[1], [4], [7], [10]])
    cols = jnp.asarray([[0], [5], [127], [64]])
    want = pool.at[rows, :, :, cols].set(new)
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        got, = attention.kv_cache_write([(pool, new)], rows, cols)
        assert reg.counter("attention/kv_write_paged") == 1
        np.testing.assert_array_equal(_bits(got), _bits(want))
        attention.kv_cache_write([(pool, new)], rows, cols,
                                 paged=False)
        assert reg.counter("attention/kv_write_ragged") == 1
        # K, V, K's scales, V's scales: two calls, each leaf where it
        # was handed over
        scale = jnp.asarray(rng.normal(size=(12, H, 1, PAGE)),
                            jnp.float32)
        s_new = jnp.asarray(rng.normal(size=(4, 1, H, 1)), jnp.float32)
        calls = []
        real = kw.kv_write

        def counted(leaves, *a, **k):
            calls.append(len(leaves))
            return real(leaves, *a, **k)
        monkeypatch.setattr(kw, "kv_write", counted)
        got = attention.kv_cache_write(
            [(pool, new), (pool + 1, new), (scale, s_new),
             (scale + 1, s_new)], rows, cols)
        assert calls == [2, 2]
        assert reg.counter("attention/kv_write_paged") == 5
        for g, (leaf, fresh) in zip(got, [
                (pool, new), (pool + 1, new), (scale, s_new),
                (scale + 1, s_new)]):
            np.testing.assert_array_equal(
                _bits(g), _bits(leaf.at[rows, :, :, cols].set(fresh)))
        monkeypatch.setattr(kw, "kv_write", real)
        monkeypatch.delenv("PFX_PALLAS_INTERPRET")
        got, = attention.kv_cache_write([(pool, new)], rows, cols)
        assert reg.counter("attention/fallback/kernel_rejected") == 1
        np.testing.assert_array_equal(_bits(got), _bits(want))
        got, = attention.kv_cache_write([(pool, new)], rows, cols,
                                        use_flash=False)
        np.testing.assert_array_equal(_bits(got), _bits(want))
        assert reg.counter("attention/kv_write_paged") == 5
        assert reg.counter("attention/fallback/kernel_rejected") == 1
    finally:
        metrics.set_enabled(False)
        reg.reset()


# -- the model's cache branches ----------------------------------------

CFG = GPTConfig(vocab_size=96, hidden_size=64, num_layers=2,
                num_attention_heads=4, max_position_embeddings=256,
                hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0,
                use_flash_attention=True)


@pytest.fixture(scope="module")
def params():
    return GPTForPretraining(CFG).init(
        {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"]


def _no_kernel(*a, **k):
    raise NotImplementedError("today's scatter")


@pytest.mark.parametrize("paged", [True, False])
@pytest.mark.parametrize("window,kv_dtype", [
    (1, "bf16"), (4, "bf16"), (1, "int8"), (4, "int8")])
def test_model_cache_write_matches_the_scatter(params, monkeypatch,
                                               paged, window,
                                               kv_dtype):
    """A decode tick / verify window through the model with the
    kernel and with the scatter (same attention kernels either way):
    the same logits and, outside NULL_PAGE, the same cache bits —
    values and, for int8 KV, the scale leaves; the free row 1 writes
    nothing through the kernel."""
    cfg = dataclasses.replace(
        CFG, kv_cache_dtype=kv_dtype,
        **(dict(kv_page_size=PAGE, kv_pool_pages=8) if paged else {}))
    model = GPTForPretraining(cfg)
    lengths = jnp.asarray([126, 0, 200], jnp.int32)   # row 1 is free
    table = jnp.asarray([[1, 2], [0, 0], [3, 4]], jnp.int32)
    ids = jnp.asarray(np.random.default_rng(5).integers(
        0, 90, (3, window)), jnp.int32)
    kwargs = dict(use_cache=True, deterministic=True,
                  cache_lengths=lengths, mutable=["cache"],
                  position_ids=lengths[:, None] + jnp.arange(window))
    if paged:
        kwargs["page_table"] = table
    shapes = jax.eval_shape(
        lambda: model.apply({"params": params}, ids, **kwargs)[1])
    rng = np.random.default_rng(9)
    cache = jax.tree.map(
        lambda s: _leaf(rng, s.shape, s.dtype), shapes["cache"])

    def tick():
        metrics.get_registry().reset()
        return model.apply({"params": params, "cache": cache}, ids,
                           **kwargs)

    metrics.set_enabled(True)
    try:
        logits, out = tick()
        name = "attention/kv_write_" + ("paged" if paged else "ragged")
        leaves = 4 if kv_dtype == "int8" else 2
        assert metrics.get_registry().counter(name) == \
            leaves * cfg.num_layers
        monkeypatch.setattr(kw, "kv_write", _no_kernel)
        ref_logits, ref = tick()
        assert metrics.get_registry().counter(name) == 0
    finally:
        metrics.set_enabled(False)
        metrics.get_registry().reset()
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(ref_logits))
    first = NULL_PAGE + 1 if paged else 0
    for a, b, was in zip(jax.tree.leaves(out["cache"]),
                         jax.tree.leaves(ref["cache"]),
                         jax.tree.leaves(cache)):
        if a.ndim >= 4:          # [layers,] pages or slots, h, d, M
            if paged:            # the kernel leaves NULL_PAGE alone
                np.testing.assert_array_equal(
                    _bits(a[..., NULL_PAGE, :, :, :]),
                    _bits(was[..., NULL_PAGE, :, :, :]))
            a, b = (x[..., first:, :, :, :] for x in (a, b))
        np.testing.assert_array_equal(_bits(a), _bits(b))


# -- the paged server --------------------------------------------------

def test_paged_server_greedy_equals_generate_through_kv_write(params):
    """Staggered admissions over a paged pool, every tick writing
    through the kernel: the tokens are ``generate()``'s, the write
    engaged and nothing was rejected."""
    model = GPTForPretraining(CFG)
    gen_cfg = GenerationConfig(max_dec_len=6,
                               decode_strategy="greedy_search",
                               eos_token_id=95, pad_token_id=0)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 90, n).tolist()
               for n in (3, 125, 40, 130, 9)]
    ids, mask = left_pad_batch(prompts, 0)
    ref = np.asarray(generate(model, params, jnp.asarray(ids),
                              jnp.asarray(mask), jax.random.key(0),
                              gen_cfg))
    want = []
    for row in ref:
        row = row.tolist()
        want.append(row[:row.index(95) + 1] if 95 in row else row)
    metrics.set_enabled(True)
    reg = metrics.get_registry()
    reg.reset()
    try:
        srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               page_size=PAGE, pool_pages=9)
        got = [c.tokens for c in srv.run(prompts)]
        assert got == want
        assert reg.counter("attention/kv_write_paged") > 0
        assert reg.counter("attention/flash_decode_paged") > 0
        assert reg.counter("attention/fallback/kernel_rejected") == 0
    finally:
        metrics.set_enabled(False)
        reg.reset()
