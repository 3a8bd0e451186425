"""Compiles for a DESCRIBED (not attached) TPU v5e: the chip's own
compiler run over the main path's kernels at GPT-345M widths, on one
chip and on the 2x2 mesh.

These are rehearsals (on-chip-measurement guide, section 2): they
show what interpret mode cannot — a kernel Mosaic refuses (VMEM
budget, tiling), a kernel GSPMD cannot partition — at no chip time.
Nothing runs, so a pass here is never a chip run.

Rules this file keeps: the topology is described inside a
module-scoped fixture (never at import), nothing is ``autouse``, no
child process is started (the worker that described the topology
holds libtpu's lock), and the persistent compilation cache is off
around the compiles (a described-chip entry cannot be read back).
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

# GPT-345M widths: local batch 8, 16 heads of 64, s = S = 1024, bf16
B, H, D, S = 8, 16, 64, 1024
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or its lock is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """The Engine's 5-axis mesh at fsdp2 x mp2 over the described
    chips."""
    import numpy as np

    from paddlefleetx_tpu.parallel.mesh import MESH_AXES
    return Mesh(np.asarray(topo.devices).reshape(1, 1, 1, 2, 2),
                MESH_AXES)


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the kernels' backend gates onto their TPU branch and keep
    the persistent compilation cache out of the way."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv("PFX_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _mosaic_calls(compiled):
    """``(ops, scoped VMEM bytes)`` of every Mosaic call in a compiled
    program: the operation names its body holds (the serialized MLIR
    module keeps them as plain strings) and what the chip's compiler
    says the call uses of its scoped VMEM."""
    import base64
    out = []
    for line in re.findall(r"[^\n]*custom_call_target=\"tpu_custom_call\""
                           r"[^\n]*", compiled.as_text()):
        body = base64.b64decode(
            re.search(r'"body":"([^"]*)"', line).group(1))
        used = re.search(
            r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
            r'"offset":"0","size":"(\d+)"', line)
        out.append((set(re.findall(rb"matmul|multi_reduction", body)),
                    int(used.group(1))))
    return out


def _qkv(sh, b=B, s=S, h=H, d=D):
    return [_sds((b, s, h, d), BF16, sh)] * 3


def _grad_sum(fn, argnums):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=argnums)


# -- one chip: training flash -----------------------------------------

def _flash_cases():
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa

    def dropout(q, k, v):
        return fa.flash_attention(q, k, v, dropout_rate=0.1,
                                  dropout_rng=jax.random.key(0))

    def biased(q, k, v, bias):
        return fa.flash_attention(q, k, v, causal=False, bias=bias)

    def causal_biased(q, k, v, bias):
        return fa.flash_attention(q, k, v, causal=True, bias=bias)

    def with_lse(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v)
        return out.astype(jnp.float32).sum() + lse.sum()

    return {
        "fwd": (fa.flash_attention, {}),
        "fwd_bwd": (_grad_sum(fa.flash_attention, (0, 1, 2)), {}),
        "dropout_fwd_bwd": (_grad_sum(dropout, (0, 1, 2)), {}),
        # several q blocks with dropout: each block's bits sliced by
        # the forward's staircase and by the split backward pair's
        "dropout_s2048": (_grad_sum(dropout, (0, 1, 2)),
                          dict(b=2, s=2048)),
        "d128_s2048": (_grad_sum(fa.flash_attention, (0, 1, 2)),
                       dict(b=2, s=2048, h=8, d=128)),
        "s8192": (_grad_sum(fa.flash_attention, (0, 1, 2)),
                  dict(b=1, s=8192)),
        "noncausal_bias": (_grad_sum(biased, (0, 1, 2)),
                           dict(bias=True)),
        # several q blocks with a bias: the causal staircase in the
        # forward and in the split backward pair, the bias tile sliced
        "causal_bias_s2048": (_grad_sum(causal_biased, (0, 1, 2)),
                              dict(bias=True, b=2, s=2048)),
        "with_lse": (jax.grad(with_lse, (0, 1, 2)), {}),
    }


@pytest.mark.parametrize("case", [
    "fwd", "fwd_bwd", "dropout_fwd_bwd", "dropout_s2048", "d128_s2048",
    "s8192",
    "noncausal_bias", "causal_bias_s2048", "with_lse"])
def test_flash_training_compiles_on_one_chip(case, one_chip, as_tpu):
    fn, kw = _flash_cases()[case]
    kw = dict(kw)
    has_bias = kw.pop("bias", False)
    args = _qkv(one_chip, **kw)
    if has_bias:
        args.append(_sds((kw.get("b", B), 1, 1, kw.get("s", S)),
                         jnp.float32, one_chip))
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


# -- one chip: decode family ------------------------------------------

def _cache(sh, dtype=BF16):
    return [_sds((B, H, D, S), dtype, sh)] * 2


def _scales(sh, shape=(B, H, 1, S)):
    return [_sds(shape, jnp.float32, sh)] * 2


def test_flash_decode_compiles(one_chip, as_tpu):
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    q = _sds((B, 1, H, D), BF16, one_chip)
    off = _sds((), jnp.int32, one_chip)
    txt = _compile(fa.flash_decode, q, *_cache(one_chip),
                   off).as_text()
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("window,int8", [
    (1, False), (1, True), (2, False), (3, False), (5, False),
    (8, False), (32, False), (5, True)])
def test_flash_decode_ragged_compiles(window, int8, one_chip, as_tpu):
    """Contiguous slot cache: single-token decode and the speculative
    verify windows. w >= 3 used to ask Mosaic for 18-20 MB of scoped
    VMEM against a 16 MB limit (the budget counted only the
    double-buffered K/V blocks, not the window's widened copies)."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    q = _sds((B, window, H, D), BF16, one_chip)
    off = _sds((B,), jnp.int32, one_chip)
    if int8:
        fn = lambda q, k, v, off, ks, vs: fa.flash_decode_ragged(  # noqa: E731
            q, k, v, off, k_scale=ks, v_scale=vs)
        args = (q, *_cache(one_chip, jnp.int8), off,
                *_scales(one_chip))
    else:
        fn = fa.flash_decode_ragged
        args = (q, *_cache(one_chip), off)
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


@pytest.mark.parametrize("slots,pages", [(64, 8), (256, 64)])
@pytest.mark.parametrize("window,int8", [
    (1, False), (5, False), (9, False), (32, False), (1, True),
    (5, True)])
def test_flash_decode_paged_compiles(window, int8, slots, pages,
                                     one_chip, as_tpu):
    """The walk over live (slot, block) pairs at the serving cell's
    shape (64 slots, 8 pages of 128) and at 256 slots x 64 pages,
    where the walk's two lists, the table and the offsets are 192 KB
    of the 1 MiB of SMEM: a dynamic grid extent, four prefetched
    scalars, the rows-on-lanes ``q`` / output blocks."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    page, pool = 128, 513
    q = _sds((slots, window, H, D), BF16, one_chip)
    off = _sds((slots,), jnp.int32, one_chip)
    table = _sds((slots, pages), jnp.int32, one_chip)
    kv = [_sds((pool, H, D, page), jnp.int8 if int8 else BF16,
               one_chip)] * 2
    if int8:
        fn = lambda q, k, v, off, t, ks, vs: fa.flash_decode_paged(  # noqa: E731
            q, k, v, off, t, k_scale=ks, v_scale=vs)
        args = (q, *kv, off, table,
                *_scales(one_chip, (pool, H, 1, page)))
    else:
        fn = fa.flash_decode_paged
        args = (q, *kv, off, table)
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    # one K/V head a query head: a matvec a head, nothing for the MXU
    (ops, _), = _mosaic_calls(compiled)
    assert ops == {b"multi_reduction"}


# -- one chip: the serving cell's cache programs, in place -------------
#
# gpt345m.serve-chat: 64 slots over a 513-page pool of 128-token pages,
# [513, 16, 64, 128] a leaf. One decoder layer at those widths through
# the REAL jits (``decode_step``, ``verify_step``,
# ``prefill_chunk_paged``): the compiled program may hold no ``copy`` /
# ``transpose`` that produces a pool-shaped array, and the donated pool
# must come back aliased.

SLOTS, POOL, PAGE = 64, 513, 128


def _pool_copies(compiled):
    """Instructions that copy a whole pool leaf: a ``copy`` /
    ``transpose`` (or a fusion named after one) whose result has a
    leaf's element count — values ``513 x 16 x 64 x 128`` or int8-KV
    scales ``513 x 16 x 1 x 128`` — in whatever order of dims (the
    scatter's own layout is ``[513,128,16,64]``)."""
    sizes = {POOL * H * D * PAGE, POOL * H * PAGE}
    n = 0
    for name, dims, op in re.findall(
            r"%(\S+) = \w+\[([\d,]+)\]\S* (copy|transpose|fusion)\(",
            compiled.as_text()):
        if math.prod(map(int, dims.split(","))) in sizes and (
                op != "fusion" or "copy" in name or "transpose" in name):
            n += 1
    return n


def _kv_write_calls(compiled):
    """Instructions named after the write kernel (``name="kv_write"``
    on its ``pallas_call``), which keeps them out of the
    ``*self_attn* custom-call`` lines ``paged_decode_roofline``
    reads."""
    return len(re.findall(r"%kv_write[.\d]* = [^\n]*\) custom-call\(",
                          compiled.as_text()))


def _serving_program(sh, kv_dtype="bf16", layers=1):
    """(model, params, pool, slot state, rng key, page table,
    generation config) of the cell at ``layers`` layers, as shapes on
    ``sh``."""
    import flax.linen as nn

    from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddlefleetx_tpu.models.gpt import generation as g
    cfg = GPTConfig(
        vocab_size=50304, hidden_size=H * D, num_layers=layers,
        num_attention_heads=H, max_position_embeddings=S,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        scan_layers=False, use_flash_attention=True, dtype="bfloat16",
        kv_page_size=PAGE, kv_pool_pages=POOL, kv_cache_dtype=kv_dtype)
    model = GPTForPretraining(cfg)

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: _sds(a.shape, dtype or a.dtype, sh), tree)
    params = on_chip(nn.meta.unbox(jax.eval_shape(
        model.init, {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"]), BF16)
    pool = on_chip(jax.eval_shape(
        lambda p: g.init_page_pool(model, p, SLOTS), params))
    state = on_chip(jax.eval_shape(
        lambda: g.init_slot_state(SLOTS, cfg.vocab_size)))
    rng = on_chip(jax.eval_shape(lambda: jax.random.key(1)))
    table = _sds((SLOTS, cfg.max_kv_pages), jnp.int32, sh)
    gen_cfg = g.GenerationConfig(
        max_dec_len=128, decode_strategy="greedy_search",
        eos_token_id=50303, pad_token_id=50303)
    return model, params, pool, state, rng, table, gen_cfg


def _pool_bytes(pool):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(pool) if a.ndim == 4)


def _decode_tick(sh, window, kv_dtype, layers=1):
    from paddlefleetx_tpu.models.gpt import generation as g
    model, params, pool, state, rng, table, gen_cfg = \
        _serving_program(sh, kv_dtype, layers)
    if window == 1:
        lowered = g.decode_step.lower(
            model, params, pool, state, rng, gen_cfg, page_table=table)
    else:
        drafts = _sds((SLOTS, window - 1), jnp.int32, sh)
        lowered = g.verify_step.lower(
            model, params, pool, state, drafts, rng, gen_cfg,
            page_table=table)
    return lowered.compile(), _pool_bytes(pool)


@pytest.mark.parametrize("window,kv_dtype", [
    (1, "bf16"), (5, "bf16"), (1, "int8"), (5, "int8"), (32, "bf16")])
def test_decode_tick_updates_the_pool_in_place(window, kv_dtype,
                                               one_chip, as_tpu):
    """The KV write kernel, then ``flash_decode_paged``, the pool
    donated: one write call a layer for K and V (one more for an int8
    cache's two scale pools), no pool-shaped copy, every pool byte
    aliased."""
    compiled, pool_bytes = _decode_tick(one_chip, window, kv_dtype)
    assert _kv_write_calls(compiled) == (2 if kv_dtype == "int8" else 1)
    assert _pool_copies(compiled) == 0
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


@pytest.mark.parametrize("window,kv_dtype,paged", [
    (1, "bf16", True), (5, "bf16", True), (32, "bf16", True),
    (5, "int8", True), (1, "bf16", False), (5, "int8", False)])
def test_kv_write_compiles(window, kv_dtype, paged, one_chip, as_tpu):
    """The write kernel alone at the cell's widths: a grid whose first
    axis is the count of live rows (dynamic), three prefetched scalars,
    K and V in one call with both leaves aliased; the paged pool and
    the contiguous slot cache ``[64, 16, 64, 1024]``."""
    from paddlefleetx_tpu.ops.pallas import kv_write as kw
    dtype, d = {"bf16": (BF16, D), "int8": (jnp.int8, D)}[kv_dtype]
    shape = (POOL, H, d, PAGE) if paged else (SLOTS, H, d, S)
    leaves = [_sds(shape, dtype, one_chip)] * 2
    news = [_sds((SLOTS, window, H, d), dtype, one_chip)] * 2
    idx = _sds((SLOTS, window), jnp.int32, one_chip)
    compiled = jax.jit(
        functools.partial(kw.kv_write, paged=paged),
        donate_argnums=0).lower(leaves, idx, idx, news).compile()
    assert _kv_write_calls(compiled) == 1
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        2 * math.prod(shape) * jnp.dtype(dtype).itemsize


def _paged_decode_calls(compiled):
    """The HLO text of every paged decode kernel in the program: the
    unnamed Mosaic call the chip's op line shows as ``self_attn.<n>
    custom-call`` (what ``paged_decode_roofline`` matches)."""
    return re.findall(r"%self_attn[.\d]* = [^\n]* custom-call\([^\n]*",
                      compiled.as_text())


@pytest.mark.parametrize("window,kv_dtype", [
    (1, "bf16"), (5, "bf16"), (1, "int8")])
def test_decode_tick_walks_once_and_pads_no_lanes(window, kv_dtype,
                                                  one_chip, as_tpu):
    """Two layers of the cell's tick. The paged kernel is launched
    once a layer under the name the benchmark reads; the program holds
    no ``[64, 16, 64, W]`` array and the kernel touches none whose
    minor dim is 1 (``q`` and the output went in and out that way: ``W``
    padded to 128 lanes in HBM, 16.8 MB for 128 KB, and two copies a
    layer to make and unmake it); and
    the walk over live (slot, block) pairs and the write kernel's
    over the live rows are each built once a tick, not once a layer:
    the second layer adds no cumulative sum."""
    def scans(compiled):
        return len(re.findall(r" reduce-window\(", compiled.as_text()))
    one, _ = _decode_tick(one_chip, window, kv_dtype, layers=1)
    two, _ = _decode_tick(one_chip, window, kv_dtype, layers=2)
    assert len(_paged_decode_calls(one)) == 1
    calls = _paged_decode_calls(two)
    assert len(calls) == 2
    assert f"[{SLOTS},{H},{D},{window}]" not in two.as_text()
    for text in calls:                   # operands and result alike
        assert not re.search(r"\[[\d,]+,1\]", text), text[:400]
    # GPT's heads are not grouped: the decode kernel's products stay
    # VPU reductions, and no Mosaic call of the tick holds a matmul
    ops = [o for o, _ in _mosaic_calls(two)]
    assert len(ops) == 2 + _kv_write_calls(two)
    assert all(o <= {b"multi_reduction"} for o in ops), ops
    assert scans(one) >= 2
    assert scans(two) == scans(one)
    assert _kv_write_calls(two) == 2 * _kv_write_calls(one)


def test_decode_tick_with_the_scatter_copies_the_pool(one_chip, as_tpu,
                                                      monkeypatch):
    """The same program with the write as the XLA scatter it was: the
    chip's compiler brackets every leaf's scatter with two copies of
    the whole leaf (to the scatter's layout and back to the decode
    kernel's), donated or not. This is the case the test above must
    be able to see."""
    from paddlefleetx_tpu.ops.pallas import kv_write as kw

    def refuse(*a, **k):
        raise NotImplementedError("the scatter")
    monkeypatch.setattr(kw, "kv_write", refuse)
    # decode_step's trace cache does not see the patch: drop the trace
    # the in-place case left, and this one's before the patch is undone
    jax.clear_caches()
    try:
        compiled, _ = _decode_tick(one_chip, 1, "bf16")
    finally:
        jax.clear_caches()
    assert _kv_write_calls(compiled) == 0
    assert _pool_copies(compiled) == 4          # K and V, in and out


@pytest.mark.parametrize("donated", [True, False])
def test_prefill_chunk_scatters_pages_in_place(donated, one_chip,
                                               as_tpu):
    """The chunk's whole-page scatter (index on dim 0 only) keeps the
    pool's layout: in place when the pool is donated, one copy a leaf
    when it is not (the jit without its ``donate_argnames``)."""
    from paddlefleetx_tpu.models.gpt import generation as g
    model, params, pool, _, _, _, _ = _serving_program(one_chip)
    chunk = _sds((1, 2 * PAGE), jnp.int32, one_chip)
    start = _sds((1,), jnp.int32, one_chip)
    table = _sds((1, model.config.max_kv_pages), jnp.int32, one_chip)
    fn = g.prefill_chunk_paged if donated else jax.jit(
        g.prefill_chunk_paged.__wrapped__, static_argnames=("model",))
    compiled = fn.lower(model, params, pool, chunk, start,
                        table).compile()
    if donated:
        assert _pool_copies(compiled) == 0
        assert compiled.memory_analysis().alias_size_in_bytes >= \
            _pool_bytes(pool)
    else:
        assert _pool_copies(compiled) == 2


@pytest.mark.parametrize("logits", [(1, 512, 100352), (100352,)])
def test_the_activation_picks_its_row_in_place(logits, one_chip, as_tpu):
    """``activate_slot`` at Granite's sizes (64 slots, 100,352 rows of
    vocabulary, a 512-token chunk's float32 output of 205 MB, or a
    registry's lone row): the row a traced index names is read where
    the slot's state is written, so the program holds no temporary
    and nothing the size of the chunk's output but its argument."""
    from paddlefleetx_tpu.models.gpt import generation as g
    slots, vocab = 64, logits[-1]
    state = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: g.init_slot_state(slots, vocab)))
    i32 = _sds((), jnp.int32, one_chip)
    compiled = g.activate_slot.lower(
        state, i32, i32, i32, i32, _sds((vocab,), jnp.bool_, one_chip),
        _sds(logits, jnp.float32, one_chip), i32, i32).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0
    row = 4 * vocab
    state_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    assert mem.output_size_in_bytes <= state_bytes + row + 4096


def _weight_sized(compiled):
    """``(op, dims)`` of every instruction of the ENTRY computation
    whose result holds a weight matrix's elements or more (2 MiB in
    bfloat16: the smallest of a 345M layer's four), sorted: a slice
    that was NOT fused into its product would show up here, and so
    do XLA's prefetches of a weight into the fast memory space
    (``slice-done`` / ``copy-done`` and the ``ConcatBitcast`` that
    joins their pieces), which a weight must not lose."""
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    return sorted(
        (op, dims) for dims, op in re.findall(
            r"\n  (?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(", entry)
        if math.prod(map(int, dims.split(","))) >= H * D * H * D
        and op not in ("parameter", "get-tuple-element", "bitcast"))


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_stacked_parameters_leave_the_programs_as_they_were(
        program, one_chip, as_tpu, monkeypatch):
    """What a launch passes: two layers' 28 leaves go down as 13
    arrays (``pack_launch_params``: the vectors stacked, every matrix
    over ``STACK_LEAF_BYTES`` its own) and ``launch_tree`` slices them
    apart inside the program. The compiled tick and chunk hold the
    weight-sized instructions of the plain tree's, prefetches
    included, and no other; the tick needs no temporary more."""
    from paddlefleetx_tpu.models.gpt import generation as g
    model, params, pool, state, rng, table, gen_cfg = \
        _serving_program(one_chip, layers=2)
    monkeypatch.setattr(g, "_stack_groups", lambda groups: tuple(
        _sds((len(x),) + x[0].shape, x[0].dtype, one_chip)
        for x in groups))
    _, packed = g.pack_launch_params(None, params)
    assert len(jax.tree.leaves(params)) == 28
    assert len(packed.arrays) == 13

    def compiled(p):
        if program == "tick":
            return g.decode_step.lower(
                model, p, pool, state, rng, gen_cfg,
                page_table=table).compile()
        return g.prefill_chunk_paged.lower(
            model, p, pool, _sds((1, 2 * PAGE), jnp.int32, one_chip),
            _sds((1,), jnp.int32, one_chip),
            _sds((1, model.config.max_kv_pages), jnp.int32,
                 one_chip)).compile()
    plain, stacked = compiled(params), compiled(packed)
    assert _weight_sized(stacked) == _weight_sized(plain)
    if program == "tick":
        assert stacked.memory_analysis().temp_size_in_bytes <= \
            plain.memory_analysis().temp_size_in_bytes
    assert _pool_copies(stacked) == 0


# -- one chip: grouped / quantized GEMMs, grouped LoRA ----------------

@pytest.mark.parametrize("backward", [False, True])
def test_grouped_matmul_compiles(backward, one_chip, as_tpu):
    from paddlefleetx_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul,
    )
    x = _sds((8, 1024, 1024), BF16, one_chip)
    w = _sds((8, 1024, 4096), BF16, one_chip)
    counts = _sds((8,), jnp.int32, one_chip)
    fn = _grad_sum(grouped_matmul, (0, 1)) if backward \
        else grouped_matmul
    assert "tpu_custom_call" in _compile(fn, x, w, counts).as_text()


@pytest.mark.parametrize("split_backward", [False, True])
def test_flash_at_latent_attention_widths_compiles(split_backward,
                                                   one_chip, as_tpu,
                                                   monkeypatch):
    """q/k of 192 against v of 128 at the Kanana cell's b4 x s4096 x 32
    heads, forward and backward: the fused backward with its larger
    VMEM scope, and the split pair it falls back to."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    if split_backward:
        monkeypatch.setattr(fa, "FUSED_BWD_WIDE_VMEM_LIMIT", 0)
    q = _sds((4, 4096, 32, 192), BF16, one_chip)
    v = _sds((4, 4096, 32, 128), BF16, one_chip)
    text = _compile(_grad_sum(fa.flash_attention, (0, 1, 2)),
                    q, q, v).as_text()
    assert text.count("tpu_custom_call") == (3 if split_backward else 2)


@pytest.mark.parametrize("k,n", [(2048, 1536), (768, 2048)])
def test_ragged_matmul_compiles(k, n, one_chip, as_tpu):
    """The dropless expert layer's grouped products at the Kanana
    cell's worst-case buffer (16,384 x 6 picks + 16 tiles), forward,
    dx and dw, the row axis of the grid dynamic."""
    from paddlefleetx_tpu.ops.pallas.grouped_matmul import ragged_matmul
    rows = 16384 * 6 + 16 * 128
    x = _sds((rows, k), BF16, one_chip)
    w = _sds((16, k, n), BF16, one_chip)
    table = _sds((rows // 128,), jnp.int32, one_chip)
    used = _sds((), jnp.int32, one_chip)
    def loss_and_grads(*a):
        return jax.value_and_grad(
            lambda *a: jnp.sum(ragged_matmul(*a).astype(jnp.float32)),
            (0, 1))(*a)
    text = _compile(loss_and_grads, x, w, table, used).as_text()
    assert text.count("tpu_custom_call") == 3


@pytest.mark.parametrize("m", [8, 1024])
def test_quantized_matmul_compiles(m, one_chip, as_tpu):
    from paddlefleetx_tpu.ops.pallas.quantized_matmul import (
        quantized_matmul,
    )
    x = _sds((m, 1024), BF16, one_chip)
    w = _sds((1024, 4096), jnp.int8, one_chip)
    s = _sds((4096,), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compile(quantized_matmul, x, w,
                                         s).as_text()


def test_grouped_lora_compiles(one_chip, as_tpu):
    from paddlefleetx_tpu.ops.lora import grouped_lora_delta
    x = _sds((64, 1024), BF16, one_chip)
    ids = _sds((64,), jnp.int32, one_chip)
    a = _sds((4, 1024, 16), BF16, one_chip)
    b = _sds((4, 16, 3072), BF16, one_chip)
    assert "tpu_custom_call" in _compile(grouped_lora_delta, x, ids,
                                         a, b).as_text()


# -- meshes ------------------------------------------------------------

def test_flash_compiles_under_one_device_mesh(topo, as_tpu):
    """What the Engine builds on one chip: the helper must step aside
    (direct call, same HLO as with no mesh)."""
    import numpy as np

    from paddlefleetx_tpu.ops.attention import dot_product_attention
    from paddlefleetx_tpu.parallel.mesh import MESH_AXES, set_mesh
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape((1,) * 5),
                MESH_AXES)
    set_mesh(mesh)
    sh = NamedSharding(mesh, P())
    fn = _grad_sum(functools.partial(dot_product_attention,
                                     use_flash=True), (0, 1, 2))
    txt = _compile(fn, *_qkv(sh)).as_text()
    assert "tpu_custom_call" in txt and "all-gather" not in txt


def _mesh_rules():
    from paddlefleetx_tpu.parallel.mesh import TopologyConfig
    from paddlefleetx_tpu.parallel.sharding import make_sharding_rules
    return list(make_sharding_rules(TopologyConfig(
        mp_degree=2, sharding_degree=2, sharding_stage=3)))


def test_flash_compiles_on_2x2_mesh(mesh4, as_tpu):
    """Batch over fsdp, heads over mp: every chip runs the fwd and bwd
    kernels on its own block — no all-gather of q/k/v feeds them."""
    import flax.linen as nn

    from paddlefleetx_tpu.ops.attention import dot_product_attention
    from paddlefleetx_tpu.parallel.mesh import set_mesh
    set_mesh(mesh4)
    sh = NamedSharding(mesh4, P(("dp", "fsdp"), None, "mp", None))
    fn = _grad_sum(functools.partial(dot_product_attention,
                                     use_flash=True), (0, 1, 2))
    with mesh4, nn.logical_axis_rules(_mesh_rules()):
        txt = _compile(fn, *_qkv(sh)).as_text()
    assert txt.count("tpu_custom_call") >= 2
    assert "all-gather" not in txt


def test_flash_under_the_stage_vmap_runs_one_stage_per_chip(topo,
                                                           as_tpu):
    """pp2 x mp2: the pipeline maps its stages with the pp axis named
    (``parallel/pipeline.py::_slot_vmap``), so each chip's kernel
    holds its OWN stage's block — no stage dim inside the custom call,
    nothing gathered over pp. Unnamed, shard_map takes the stage dim
    as replicated and every chip computes both stages."""
    import flax.linen as nn
    import numpy as np

    from paddlefleetx_tpu.ops.attention import dot_product_attention
    from paddlefleetx_tpu.parallel.mesh import (
        MESH_AXES, TopologyConfig, set_mesh,
    )
    from paddlefleetx_tpu.parallel.pipeline import _slot_vmap
    from paddlefleetx_tpu.parallel.sharding import make_sharding_rules
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 1, 1, 1, 2),
                MESH_AXES)
    set_mesh(mesh)
    rules = list(make_sharding_rules(TopologyConfig(pp_degree=2,
                                                    mp_degree=2)))
    # [vpp, stage, microbatch, s, h, d]
    sh = NamedSharding(mesh, P(None, "pp", None, None, "mp", None))
    qkv = [_sds((1, 2, 2, S, H, D), BF16, sh)] * 3
    fn = _slot_vmap(_grad_sum(functools.partial(
        dot_product_attention, use_flash=True), (0, 1, 2)), 2)
    with mesh, nn.logical_axis_rules(rules):
        txt = _compile(fn, *qkv).as_text()
    assert txt.count("tpu_custom_call") >= 2
    assert "all-gather" not in txt
    per_chip = f"{2 * H // 2},{S},{D}]"       # microbatch x heads/mp
    assert f"bf16[{per_chip}" in txt
    assert f"bf16[2,{per_chip}" not in txt    # both stages on a chip


def test_unwrapped_flash_is_refused_on_2x2_mesh(mesh4, as_tpu):
    """The failure the helper exists for, pinned: a bare pallas_call
    under a sharded jit does not lower on real chips."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    sh = NamedSharding(mesh4, P(("dp", "fsdp"), None, "mp", None))
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(fa.flash_attention, *_qkv(sh))


def test_grouped_matmul_compiles_on_2x2_mesh(mesh4, as_tpu):
    """The MoE expert FFN (sort_pallas) with experts over fsdp and the
    FFN dim over mp: both grouped GEMMs and their backward lower per
    device."""
    import flax.linen as nn

    from paddlefleetx_tpu.models.gpt.config import GPTConfig
    from paddlefleetx_tpu.models.gpt.moe import MoEMLP
    from paddlefleetx_tpu.parallel.mesh import set_mesh
    set_mesh(mesh4)
    cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=1,
                    num_attention_heads=16, ffn_hidden_size=4096,
                    moe_num_experts=8, moe_top_k=2,
                    moe_dispatch="sort_pallas", dtype="bfloat16")
    layer = MoEMLP(cfg)
    rules = _mesh_rules()
    x = _sds((B, S, 1024), BF16,
             NamedSharding(mesh4, P(("dp", "fsdp"), None, None)))
    with mesh4, nn.logical_axis_rules(rules):
        abstract = jax.eval_shape(
            lambda: layer.init(jax.random.key(0),
                               jnp.zeros((2, 128, 1024), BF16)))
        shardings = nn.logical_to_mesh_sharding(
            nn.get_partition_spec(abstract), mesh4, rules)
        params = jax.tree.map(
            lambda a, s: _sds(a.shape, a.dtype, s),
            nn.meta.unbox(abstract), shardings)

        def loss(p, x):
            y, aux = layer.apply(p, x)
            return jnp.sum(y.astype(jnp.float32)) + aux

        txt = _compile(jax.grad(loss), params, x).as_text()
    # fwd: 2 GEMMs; bwd: dw for each + dx of the second (x itself is
    # not differentiated here)
    assert txt.count("tpu_custom_call") >= 5


# -- the 2x2 mesh: the mp rings of the 1.3B cell ------------------------
#
# gpt1p3b.pretrain-mp2-fsdp2: global b16 x s1024 over fsdp2, hidden
# 2048, 16 heads of 128, ffn 8192, mp 2. What the chip's scheduler
# made of a ring is the point: a hop that no product covers is a chip
# waiting on a wire.

def _schedule(compiled):
    """``[(opcode-or-"matmul", name)]`` of the entry computation in
    scheduled order: collective starts and dones, and every fusion
    whose body holds a convolution."""
    txt = compiled.as_text()
    comps = {m.group(1): m.start() for m in re.finditer(
        r"\n(?:ENTRY )?%?([\w.\-]+) \([^\n]*\) -> [^\n]*\{\n", txt)}
    names = sorted(comps, key=comps.get)
    body = {n: txt[comps[n]:comps[names[i + 1]] if i + 1 < len(names)
                   else len(txt)] for i, n in enumerate(names)}
    matmul = {n for n in names if "convolution(" in body[n]}
    entry = body[re.search(r"\nENTRY %?([\w.\-]+) ", txt).group(1)]
    out = []
    for line in entry.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z\-]+)\(",
                     line)
        if not m:
            continue
        name, op = m.groups()
        calls = re.search(r"calls=%([\w.\-]+)", line)
        if op == "fusion" and calls and calls.group(1) in matmul:
            out.append(("matmul", name))
        elif op.startswith(("collective-permute", "all-reduce",
                            "all-gather", "reduce-scatter")):
            out.append((op, name))
    return out


def _ring_site(site, mesh4):
    from paddlefleetx_tpu.ops.collective_matmul import (
        all_gather_matmul, matmul_reduce_scatter,
    )
    rows = ("dp", "fsdp")
    seq = NamedSharding(mesh4, P(rows, "mp", None))
    if site == "fc1":
        return (lambda x, w: all_gather_matmul(x, w, mesh4),
                _sds((16, 1024, 2048), BF16, seq),
                _sds((2048, 8192), BF16,
                     NamedSharding(mesh4, P(None, "mp"))))
    if site == "fc2":
        return (lambda x, w: matmul_reduce_scatter(x, w, mesh4),
                _sds((16, 1024, 8192), BF16,
                     NamedSharding(mesh4, P(rows, None, "mp"))),
                _sds((8192, 2048), BF16,
                     NamedSharding(mesh4, P("mp", None))))
    return (lambda x, w: matmul_reduce_scatter(x, w, mesh4,
                                               contract_ndim=2),
            _sds((16, 1024, 16, 128), BF16,
                 NamedSharding(mesh4, P(rows, None, "mp", None))),
            _sds((16, 128, 2048), BF16,
                 NamedSharding(mesh4, P("mp", None, None))))


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("site", ["fc1", "fc2", "out_proj"])
def test_mp_ring_hops_run_under_a_product(site, backward, mesh4,
                                          as_tpu):
    """Every hop of a ring at the cell's widths has a product between
    its ``collective-permute-start`` and its ``-done`` in the chip's
    schedule, bf16 on the wire (16 MB a hop: the float32 accumulator
    PR 2 circulated was the 32 MB of the all-reduce it replaced), and
    no activation all-reduce is left. The reduce-scatter ring fails
    this without its ``optimization_barrier``: XLA fuses the own-shard
    product into the add of the arriving accumulator and the matmul
    waits for the wire."""
    fn, x, w = _ring_site(site, mesh4)
    if backward:
        fn = _grad_sum(fn, (0, 1))
    with mesh4:
        compiled = _compile(fn, x, w)
    sched = _schedule(compiled)
    ops = [op for op, _ in sched]
    # the one all-reduce left is the weight gradient's over the rows
    assert "all-gather" not in ops
    assert ops.count("all-reduce") == (1 if backward else 0)
    starts = [i for i, op in enumerate(ops)
              if op == "collective-permute-start"]
    assert len(starts) == (2 if backward and site == "fc1" else 1)
    for i in starts:
        done = ops.index("collective-permute-done", i)
        assert "matmul" in ops[i:done], sched
    wire = re.findall(r"= \((\w+)\[8,512,\d+\]\S* ?, [^\n]*? "
                      r"collective-permute-start\(", compiled.as_text())
    assert wire and set(wire) == {"bf16"}, wire


# -- one chip: the SmallThinker serving cell's kernels ------------------
#
# smallthinker.serve-mixed-len: 48 slots, 28 query heads over 4 pooled
# K/V heads of 128, pages of 128, a table of 128 pages; the window
# class's leaf is one ring of 35 pages a slot; 64 experts of 2560 x 768.

ST_SLOTS, ST_H, ST_G, ST_D, ST_PAGES = 48, 28, 4, 128, 128


@pytest.mark.parametrize("reach", [None, 4096])
@pytest.mark.parametrize("window", [1, 5])
def test_flash_decode_paged_gqa_window_compiles(window, reach, one_chip,
                                                as_tpu):
    """Grouped-query heads (7 query heads a pooled head, padded to a
    sublane tile) and the walk that starts at the window's first
    block: Mosaic takes both, the two products of a block are matmuls
    (the softmax between them keeps its lane reductions), and the
    kernel's own count of its VMEM covers what the compiler scopes
    without doubling it."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    pool = 1 + ST_SLOTS * 35
    q = _sds((ST_SLOTS, window, ST_H, ST_D), BF16, one_chip)
    off = _sds((ST_SLOTS,), jnp.int32, one_chip)
    table = _sds((ST_SLOTS, ST_PAGES), jnp.int32, one_chip)
    kv = [_sds((pool, ST_G, ST_D, 128), BF16, one_chip)] * 2
    fn = functools.partial(fa.flash_decode_paged, reach=reach)
    (ops, used), = _mosaic_calls(_compile(fn, q, *kv, off, table))
    assert ops == {b"matmul", b"multi_reduction"}
    counted = fa._paged_vmem_bytes(window, ST_G * 8, ST_D, 128, 2, 2,
                                   False, ST_G)
    assert used <= counted <= 2 * used, (used, counted)


def test_kv_write_compiles_at_four_pooled_heads(one_chip, as_tpu):
    """The write kernel takes a pool of 4 heads of 128 by shape."""
    from paddlefleetx_tpu.ops.pallas import kv_write as kw
    shape = (1 + ST_SLOTS * 35, ST_G, ST_D, 128)
    leaves = [_sds(shape, BF16, one_chip)] * 2
    news = [_sds((ST_SLOTS, 1, ST_G, ST_D), BF16, one_chip)] * 2
    idx = _sds((ST_SLOTS, 1), jnp.int32, one_chip)
    compiled = jax.jit(kw.kv_write, donate_argnums=0).lower(
        leaves, idx, idx, news).compile()
    assert _kv_write_calls(compiled) == 1


@pytest.mark.parametrize("rows", [ST_SLOTS, 256])
def test_ragged_matmul_compiles_at_decode_tiles(rows, one_chip, as_tpu):
    """The grouped products of a decode tick (48 rows x 6 picks over
    64 experts: tiles of 16 rows) and of a prefill chunk (256 rows:
    tiles of 32), gate|up and down."""
    from paddlefleetx_tpu.models.deepseek_v3.moe import block_rows
    from paddlefleetx_tpu.ops.pallas.grouped_matmul import ragged_matmul
    block = block_rows(rows * 6, 64)
    tiles = -(-rows * 6 // block) + 64
    for k, n in ((2560, 1536), (768, 2560)):
        x = _sds((tiles * block, k), BF16, one_chip)
        w = _sds((64, k, n), BF16, one_chip)
        table = _sds((tiles,), jnp.int32, one_chip)
        used = _sds((), jnp.int32, one_chip)
        fn = functools.partial(ragged_matmul, block_m=block)
        assert "tpu_custom_call" in _compile(
            fn, x, w, table, used).as_text()


# -- one chip: the Solar-Open2 serving cell's kernels --------------------
#
# solaropen2.serve-reasoning: 96 slots; 64 linear-attention heads with a
# 128 x 128 float32 state each, one row a slot behind the null row; 64
# query heads over 8 pooled K/V heads of 128 (a whole sublane tile a
# group); 40 held experts of 4096 x 1280 picked 8 of 320.

SO_SLOTS, SO_H, SO_D = 96, 64, 128


def test_kda_decode_compiles_and_updates_the_state_in_place(one_chip,
                                                            as_tpu):
    """The delta rule's decode kernel at the published widths: Mosaic
    takes the operand tile's turn and the dynamic grid, and the donated
    state leaf (407 MB) comes back aliased, not copied."""
    from paddlefleetx_tpu.ops.pallas import kda
    state = _sds((1 + SO_SLOTS, SO_H, SO_D, SO_D), jnp.float32, one_chip)
    rows = _sds((SO_SLOTS,), jnp.int32, one_chip)
    vec = _sds((SO_SLOTS, SO_H, SO_D), jnp.float32, one_chip)
    beta = _sds((SO_SLOTS, SO_H), jnp.float32, one_chip)
    compiled = jax.jit(kda.kda_decode, donate_argnums=0).lower(
        state, rows, vec, vec, vec, vec, beta).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%kda_decode\S* = .*tpu_custom_call", text)) == 1
    leaf = math.prod(state.shape) * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= leaf
    assert compiled.memory_analysis().temp_size_in_bytes < leaf // 8


def test_flash_decode_paged_compiles_at_eight_heads_a_group(one_chip,
                                                            as_tpu):
    """64 query heads over 8 pooled heads, no window, a table of 256
    pages: by shape, the kernel SmallThinker's global layers run, its
    products on the MXU."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    q = _sds((SO_SLOTS, 1, SO_H, SO_D), BF16, one_chip)
    off = _sds((SO_SLOTS,), jnp.int32, one_chip)
    table = _sds((SO_SLOTS, 256), jnp.int32, one_chip)
    kv = [_sds((6001, 8, SO_D, 128), BF16, one_chip)] * 2
    (ops, used), = _mosaic_calls(_compile(
        fa.flash_decode_paged, q, *kv, off, table))
    assert ops == {b"matmul", b"multi_reduction"}
    counted = fa._paged_vmem_bytes(1, SO_H, SO_D, 128, 2, 2, False, 8)
    assert used <= counted <= 2 * used, (used, counted)


@pytest.mark.parametrize("rows", [SO_SLOTS, 512])
def test_ragged_matmul_compiles_at_a_share_of_the_experts(rows, one_chip,
                                                          as_tpu):
    """The grouped products over 40 held experts when 8 of 320 are
    picked a token: a tick's 96 rows and a chunk's 512 size their
    tiles by ALL the picks (most land on absent experts), gate|up and
    down."""
    from paddlefleetx_tpu.models.deepseek_v3.moe import block_rows
    from paddlefleetx_tpu.ops.pallas.grouped_matmul import ragged_matmul
    block = block_rows(rows * 8, 40)
    tiles = -(-rows * 8 // block) + 40
    for k, n in ((4096, 2560), (1280, 4096)):
        x = _sds((tiles * block, k), BF16, one_chip)
        w = _sds((40, k, n), BF16, one_chip)
        table = _sds((tiles,), jnp.int32, one_chip)
        used = _sds((), jnp.int32, one_chip)
        fn = functools.partial(ragged_matmul, block_m=block)
        assert "tpu_custom_call" in _compile(
            fn, x, w, table, used).as_text()


# -- one chip: the Granite hybrid serving cell's kernels -----------------
#
# granite4h.serve-chat-bursty: 64 slots; 64 state-space heads with a
# 64 x 128 float32 state each, one row a slot behind the null row, on
# 36 of 40 layers; 32 query heads over 8 pooled K/V heads of 64 (groups
# of 4 on a sublane tile of 8) on the other 4.

GH_SLOTS, GH_H, GH_P, GH_N = 64, 64, 64, 128


def test_ssd_decode_compiles_and_updates_the_state_in_place(one_chip,
                                                            as_tpu):
    """The state-space decode kernel at the published widths: Mosaic
    takes the turn of the [8, 512] operand tile, the float32 readout
    on the matrix unit and the dynamic grid, and the donated state
    leaf (136 MB) comes back aliased, not copied."""
    from paddlefleetx_tpu.ops.pallas import ssd
    state = _sds((1 + GH_SLOTS, GH_H, GH_P, GH_N), jnp.float32, one_chip)
    rows = _sds((GH_SLOTS,), jnp.int32, one_chip)
    x = _sds((GH_SLOTS, GH_H, GH_P), jnp.float32, one_chip)
    head = _sds((GH_SLOTS, GH_H), jnp.float32, one_chip)
    bc = _sds((GH_SLOTS, GH_N), jnp.float32, one_chip)
    skip = _sds((GH_H,), jnp.float32, one_chip)
    compiled = jax.jit(ssd.ssd_decode, donate_argnums=0).lower(
        state, rows, x, head, head, bc, bc, skip).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ssd_decode\S* = .*tpu_custom_call", text)) == 1
    (ops, _), = _mosaic_calls(compiled)
    assert ops == {b"matmul"}
    leaf = math.prod(state.shape) * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= leaf
    assert compiled.memory_analysis().temp_size_in_bytes < leaf // 8


def test_flash_decode_paged_compiles_at_four_heads_of_64_a_group(one_chip,
                                                                 as_tpu):
    """32 query heads over 8 pooled heads of 64, a table of 64 pages:
    by shape, the grouped kernel the other served families run at
    heads of 128; a group's 4 query heads pad to a sublane tile."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    q = _sds((GH_SLOTS, 1, 32, 64), BF16, one_chip)
    off = _sds((GH_SLOTS,), jnp.int32, one_chip)
    table = _sds((GH_SLOTS, 64), jnp.int32, one_chip)
    kv = [_sds((1665, 8, 64, 128), BF16, one_chip)] * 2
    (ops, used), = _mosaic_calls(_compile(
        fa.flash_decode_paged, q, *kv, off, table))
    assert ops == {b"matmul", b"multi_reduction"}
    counted = fa._paged_vmem_bytes(1, 8 * 8, 64, 128, 2, 2, False, 8)
    assert used <= counted <= 2 * used, (used, counted)


def test_granite_tick_keeps_both_cache_classes_in_place(one_chip, as_tpu):
    """``decode_step`` over one state-space and one softmax layer at
    the published widths and the cell's 64 slots: one kernel each for
    the state step, the K/V write and the paged decode, and every byte
    of the state rows and of the page pool aliased to the outputs."""
    from paddlefleetx_tpu.models.gpt import generation as g
    from paddlefleetx_tpu.models.granite_hybrid import (
        GraniteHybridConfig, GraniteHybridForCausalLM,
    )
    cfg = GraniteHybridConfig(
        num_hidden_layers=2, layer_types=("mamba", "attention"),
        max_position_embeddings=8192, dtype="bfloat16", kv_page_size=128,
        kv_pool_pages=1665).state_class(GH_SLOTS)
    model = GraniteHybridForCausalLM(cfg)

    def on_chip(tree, dtype=None):
        return jax.tree.map(
            lambda a: _sds(a.shape, dtype or a.dtype, one_chip), tree)
    params = on_chip(jax.eval_shape(
        model.init, {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"], BF16)
    cache = on_chip(jax.eval_shape(
        lambda p: g.init_page_pool(model, p, GH_SLOTS), params))
    state = on_chip(jax.eval_shape(
        lambda: g.init_slot_state(GH_SLOTS, cfg.vocab_size)))
    rng = on_chip(jax.eval_shape(lambda: jax.random.key(1)))
    table = _sds((GH_SLOTS, cfg.max_kv_pages + 1), jnp.int32, one_chip)
    gen_cfg = g.GenerationConfig(
        max_dec_len=256, decode_strategy="greedy_search",
        eos_token_id=cfg.vocab_size - 1, pad_token_id=cfg.vocab_size - 1)
    compiled = g.decode_step.lower(
        model, params, cache, state, rng, gen_cfg,
        page_table=table).compile()
    text = compiled.as_text()
    for name in ("ssd_decode", "kv_write", "self_attn"):
        assert len(re.findall(
            rf"%{name}\S* = [^\n]*custom-call\(", text)) == 1, name
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert held == (1 + GH_SLOTS) * cfg.state_row_bytes \
        + 2 * 1665 * 8 * 64 * 128 * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= held


# -- one chip: the K-EXAONE serving cell's kernels -----------------------
#
# kexaone.serve-reasoning-mtp: 64 slots, every tick a verify tick of
# two columns; 64 query heads over 8 pooled K/V heads of 128, windows of
# 128 keys in rings of 6 pages; 16 held experts of 6144 x 2048 picked 8
# of 128.

KE_SLOTS, KE_H, KE_G, KE_D = 64, 64, 8, 128


@pytest.mark.parametrize("reach,pool", [(None, 2305), (128, 1 + 64 * 6)])
def test_flash_decode_paged_verifies_two_columns_at_eight_heads_a_group(
        reach, pool, one_chip, as_tpu):
    """The verify branch at ``W`` = 2 on grouped heads, a global layer's
    pool and a window layer's ring (the walk starts at the window's
    first block): by shape, what every layer of the cell's tick runs."""
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    q = _sds((KE_SLOTS, 2, KE_H, KE_D), BF16, one_chip)
    off = _sds((KE_SLOTS,), jnp.int32, one_chip)
    table = _sds((KE_SLOTS, 128), jnp.int32, one_chip)
    kv = [_sds((pool, KE_G, KE_D, 128), BF16, one_chip)] * 2
    fn = functools.partial(fa.flash_decode_paged, reach=reach)
    (ops, used), = _mosaic_calls(_compile(fn, q, *kv, off, table))
    assert ops == {b"matmul", b"multi_reduction"}
    counted = fa._paged_vmem_bytes(2, KE_H, KE_D, 128, 2, 2, False, KE_G)
    assert used <= counted <= 2 * used, (used, counted)


def test_kv_write_compiles_at_two_columns_of_eight_pooled_heads(
        one_chip, as_tpu):
    """A verify tick's two columns a row into a pool of 8 heads of 128,
    K and V in one call, both leaves aliased."""
    from paddlefleetx_tpu.ops.pallas import kv_write as kw
    shape = (2305, KE_G, KE_D, 128)
    leaves = [_sds(shape, BF16, one_chip)] * 2
    news = [_sds((KE_SLOTS, 2, KE_G, KE_D), BF16, one_chip)] * 2
    idx = _sds((KE_SLOTS, 2), jnp.int32, one_chip)
    compiled = jax.jit(kw.kv_write, donate_argnums=0).lower(
        leaves, idx, idx, news).compile()
    assert _kv_write_calls(compiled) == 1
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        2 * math.prod(shape) * 2


@pytest.mark.parametrize("rows", [2 * KE_SLOTS, 512])
def test_ragged_matmul_compiles_at_hidden_6144(rows, one_chip, as_tpu):
    """The grouped products over 16 held experts at hidden 6144: a
    verify tick's 128 rows (tiles of 64) and a chunk's 512 (tiles of
    128, whose ``[6144, 512]`` weight blocks the chip's compiler refused
    at 16.7 MB of scoped VMEM until the column block adapted)."""
    from paddlefleetx_tpu.models.deepseek_v3.moe import block_rows
    from paddlefleetx_tpu.ops.pallas.grouped_matmul import ragged_matmul
    block = block_rows(rows * 8, 16)
    tiles = -(-rows * 8 // block) + 16
    for k, n in ((6144, 4096), (2048, 6144)):
        x = _sds((tiles * block, k), BF16, one_chip)
        w = _sds((16, k, n), BF16, one_chip)
        table = _sds((tiles,), jnp.int32, one_chip)
        used = _sds((), jnp.int32, one_chip)
        fn = functools.partial(ragged_matmul, block_m=block)
        assert "tpu_custom_call" in _compile(
            fn, x, w, table, used).as_text()
