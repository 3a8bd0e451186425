"""Real int8 execution: weight-only Pallas GEMM parity + VJP +
admission, the PTQ pass and its checkpoint script, the int8 KV cache's
token stability, and the pool-density accounting
(docs/quantization.md)."""

import os
import subprocess
import sys

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.core.paging import (
    kv_page_bytes, pool_bytes, pool_pages_for_bytes,
)
from paddlefleetx_tpu.core.quantize import (
    QUANT_SITES, dequantize_kernel, dequantize_param_tree,
    quantization_meta, quantize_kernel, quantize_param_tree,
)
from paddlefleetx_tpu.models.gpt.config import GPTConfig
from paddlefleetx_tpu.models.gpt.generation import (
    GenerationConfig, generate,
)
from paddlefleetx_tpu.models.gpt.model import GPTModel
from paddlefleetx_tpu.observability import metrics
from paddlefleetx_tpu.ops.pallas.quantized_matmul import quantized_matmul

# pinned parity tolerances (ISSUE acceptance): kernel vs its XLA
# dequantize-then-dot oracle is rounding-level (both accumulate fp32);
# a quantized MODEL vs its fp source is bounded by the int8 grid
KERNEL_RTOL = 1e-5
KERNEL_ATOL = 1e-4
MODEL_REL_TOL = 0.05

# big enough for kernel admission (K, N multiples of 128; M of 8),
# small enough for the CPU interpreter
BASE = dict(vocab_size=96, hidden_size=128, ffn_hidden_size=512,
            num_layers=2, num_attention_heads=4,
            max_position_embeddings=48, dtype="float32",
            param_dtype="float32", fuse_attn_qkv=True,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
EOS = PAD = 95


def _rand_qmm(m, k, n, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((m, k)), dtype)
    w = jnp.asarray(r.integers(-127, 128, (k, n)), jnp.int8)
    s = jnp.asarray(r.uniform(0.001, 0.02, (n,)), jnp.float32)
    return x, w, s


def _oracle(x, w, s):
    wd = w.astype(jnp.float32) * s[None, :]
    return (x.astype(jnp.float32) @ wd).astype(x.dtype)


@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (24, 256, 384)])
def test_kernel_matches_dequant_oracle(m, k, n):
    """The Pallas GEMM equals XLA dequantize-then-dot to rounding —
    the scale-at-write-out factorization is exact, not approximate."""
    x, w, s = _rand_qmm(m, k, n)
    got = quantized_matmul(x, w, s)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(_oracle(
        x, w, s)), rtol=KERNEL_RTOL, atol=KERNEL_ATOL)


def test_kernel_bf16_activation_dtype_roundtrip():
    """bf16 activations stay bf16 on the way out; the fp32 accumulator
    keeps the K-sum tighter than a pure-bf16 dot."""
    x, w, s = _rand_qmm(8, 256, 128, seed=1, dtype=jnp.bfloat16)
    got = quantized_matmul(x, w, s)
    assert got.dtype == jnp.bfloat16
    ref = _oracle(x.astype(jnp.float32), w, s)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref),
        rtol=0.02, atol=0.25)


def test_kernel_vjp_dx_exact_dw_frozen():
    """dx flows through the same kernel (== the oracle's dx); the int8
    weight and its scale are frozen PTQ artifacts with zero/float0
    cotangents — nothing ever tries to train through the grid."""
    x, w, s = _rand_qmm(16, 128, 256, seed=2)
    g = jnp.asarray(
        np.random.default_rng(3).standard_normal((16, 256)),
        jnp.float32)
    dx = jax.grad(lambda a: jnp.sum(quantized_matmul(a, w, s) * g))(x)
    dx_ref = jax.grad(lambda a: jnp.sum(_oracle(a, w, s) * g))(x)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=1e-4, atol=1e-3)
    ds = jax.grad(
        lambda sc: jnp.sum(quantized_matmul(x, w, sc) * g))(s)
    np.testing.assert_allclose(np.asarray(ds), 0.0)


def test_kernel_admission_rejections(monkeypatch):
    """Every admission failure is a NotImplementedError — the signal
    `_QuantDense` converts into the counted XLA fallback."""
    x, w, s = _rand_qmm(8, 128, 128)
    for bad in [
            (x[:7], w, s),                      # M % 8
            (x[:, :100], w[:100], s),           # K % 128
            (x, w[:, :96], s[:96]),             # N % 128
            (x, w.astype(jnp.float32), s),      # not int8
            (x, w, s[:64]),                     # scale mismatch
            (x[0], w, s),                       # rank
    ]:
        with pytest.raises(NotImplementedError):
            quantized_matmul(*bad)
    # off-TPU without interpret mode the kernel refuses outright
    monkeypatch.delenv("PFX_PALLAS_INTERPRET", raising=False)
    with pytest.raises(NotImplementedError, match="TPU"):
        quantized_matmul(x, w, s)


def test_quantize_kernel_grid_and_stacked_ranks():
    """Per-output-channel abs-max on the fake_quant grid: dequant
    error bounded by half a level PER CHANNEL, scan-stacked leaves
    keep independent per-layer scales, wrong ranks refuse."""
    r = np.random.default_rng(4)
    w = jnp.asarray(r.standard_normal((32, 16)) *
                    r.uniform(0.01, 10.0, (1, 16)), jnp.float32)
    q, s = quantize_kernel(w, 1, 2)
    assert q.dtype == jnp.int8 and s.shape == (16,)
    np.testing.assert_allclose(
        np.asarray(s), np.max(np.abs(np.asarray(w)), 0) / 127.0,
        rtol=1e-6)
    err = np.abs(np.asarray(dequantize_kernel(q, s, 1, 2) - w))
    assert (err <= np.asarray(s)[None, :] / 2 + 1e-7).all()
    # stacked [L, in, out]: layer 1's tiny magnitudes keep resolution
    big = np.full((8, 4), 100.0, np.float32)
    small = np.full((8, 4), 0.01, np.float32)
    qs, ss = quantize_kernel(jnp.asarray(np.stack([big, small])), 1, 2)
    assert ss.shape == (2, 4)
    assert int(jnp.max(jnp.abs(qs[1]))) == 127   # not starved to 0
    with pytest.raises(ValueError, match="rank"):
        quantize_kernel(jnp.zeros((2, 2, 8, 4)), 1, 2)


def test_quantize_param_tree_sites_and_report():
    """Site selection is by NAME: every QUANT_SITES kernel gains an
    int8 body + fp32 `kernel_scale` sibling; embeddings/norms/biases
    pass through untouched; the report rows carry the compression."""
    r = np.random.default_rng(5)
    tree = {
        "embeddings": {"word_embeddings": {
            "embedding": jnp.asarray(r.standard_normal((96, 8)),
                                     jnp.float32)}},
        "decoder": {"layers": {
            "linear1": {"kernel": jnp.asarray(
                r.standard_normal((2, 8, 16)), jnp.float32),
                "bias": jnp.zeros((2, 16))},
            "norm1": {"scale": jnp.ones((2, 8))},
        }},
    }
    qtree, report = quantize_param_tree(tree)
    flat = flax.traverse_util.flatten_dict(qtree, sep="/")
    assert flat["decoder/layers/linear1/kernel"].dtype == jnp.int8
    assert flat["decoder/layers/linear1/kernel_scale"].shape == (2, 16)
    assert flat["embeddings/word_embeddings/embedding"].dtype == \
        jnp.float32
    assert flat["decoder/layers/norm1/scale"].dtype == jnp.float32
    assert [r_["path"] for r_ in report] == \
        ["decoder/layers/linear1/kernel"]
    assert report[0]["stacked"] is True
    assert report[0]["bytes_int8"] < report[0]["bytes_fp"]
    # idempotent: already-int8 kernels pass through
    qtree2, report2 = quantize_param_tree(qtree)
    assert report2 == []
    # meta payload names the sites
    meta = quantization_meta(report, {"act": 1.5})
    assert meta["format"] == "weight_only_int8"
    assert meta["sites"] == ["decoder/layers/linear1/kernel"]
    assert meta["activation_absmax"] == {"act": 1.5}
    # dequantize folds the scale back within half a level
    back = flax.traverse_util.flatten_dict(
        dequantize_param_tree(qtree), sep="/")
    assert "decoder/layers/linear1/kernel_scale" not in back
    err = np.abs(np.asarray(back["decoder/layers/linear1/kernel"]) -
                 np.asarray(tree["decoder"]["layers"]["linear1"]
                            ["kernel"]))
    assert err.max() <= float(jnp.max(
        flat["decoder/layers/linear1/kernel_scale"])) / 2 + 1e-7


@pytest.fixture(scope="module")
def fp_model_and_params():
    model = GPTModel(GPTConfig(**BASE))
    ids = jnp.zeros((2, 8), jnp.int32)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), ids)["params"])
    return model, params


def test_gpt_quant_execution_end_to_end(fp_model_and_params):
    """The tentpole, end to end: PTQ an fp tree, run it through the
    `quant_execution` model — every dense site takes the Pallas kernel
    (no fallback), logits within the pinned grid tolerance."""
    model_fp, params = fp_model_and_params
    qmodel = GPTModel(GPTConfig(**{
        **BASE, "quant_execution": "weight_only_int8"}))
    qparams, report = quantize_param_tree(params)
    assert {r["path"].split("/")[-2] for r in report} == \
        {"qkv_proj", "out_proj", "linear1", "linear2"}
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 96)
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    reg.reset()
    try:
        out_fp = model_fp.apply({"params": params}, ids)
        out_q = qmodel.apply({"params": qparams}, ids)
        assert reg.counter("quant/matmul") >= 4
        assert reg.counter("quant/fallback/kernel_rejected") == 0
    finally:
        metrics.set_enabled(False)
        reg.reset()
    rel = float(jnp.max(jnp.abs(out_fp - out_q)) /
                jnp.max(jnp.abs(out_fp)))
    assert rel < MODEL_REL_TOL
    # the quantized tree IS the quant model's init tree (restore needs
    # no special casing): same names, shapes, dtypes
    abstract = flax.traverse_util.flatten_dict(nn.meta.unbox(
        qmodel.init(jax.random.PRNGKey(0),
                    jnp.zeros((2, 8), jnp.int32))["params"]), sep="/")
    got = flax.traverse_util.flatten_dict(qparams, sep="/")
    assert set(abstract) == set(got)
    for k in abstract:
        assert abstract[k].shape == got[k].shape
        assert abstract[k].dtype == got[k].dtype


def test_gpt_quant_fallback_on_small_hidden():
    """hidden 32 fails K%128 admission at every site: the model still
    runs, every site counted as the XLA dequantize-then-dot fallback —
    rejection changes bytes, not availability."""
    cfg = GPTConfig(**{**BASE, "hidden_size": 32,
                       "ffn_hidden_size": 128,
                       "quant_execution": "weight_only_int8"})
    model = GPTModel(cfg)
    ids = jnp.zeros((1, 4), jnp.int32)
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    reg.reset()
    try:
        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), ids)["params"])
        out = model.apply({"params": params}, ids)
        assert reg.counter("quant/fallback/kernel_rejected") >= 4
        assert reg.counter("quant/matmul") == 0
    finally:
        metrics.set_enabled(False)
        reg.reset()
    assert bool(jnp.isfinite(out).all())


def test_ptq_checkpoint_script_roundtrip(fp_model_and_params,
                                         tmp_path):
    """scripts/quantize_checkpoint.py on a saved checkpoint: the
    output restores through the ordinary manifest-verified machinery
    into exactly the quant model's tree, opt_state dropped, meta
    stamped, logits within tolerance."""
    from paddlefleetx_tpu.core.checkpoint import save_checkpoint
    model_fp, params = fp_model_and_params
    src, dst = str(tmp_path / "src"), str(tmp_path / "dst")
    save_checkpoint(src, 0, 3,
                    {"params": params,
                     "step": jnp.zeros((), jnp.int32)},
                    {"epoch": 0, "step": 3})
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable,
         os.path.join(repo, "scripts", "quantize_checkpoint.py"),
         "--checkpoint", src, "--output", dst],
        cwd=repo, text=True, capture_output=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "QUANTIZE CHECKPOINT OK" in r.stdout
    sys.path.insert(0, repo)
    from scripts.quantize_checkpoint import load_raw_state
    qstate, qmeta = load_raw_state(
        os.path.join(dst, "epoch_0_step_3"))
    assert qmeta["quantization"]["format"] == "weight_only_int8"
    assert qmeta["quantization"]["report"]
    assert "opt_state" not in qstate
    qmodel = GPTModel(GPTConfig(**{
        **BASE, "quant_execution": "weight_only_int8"}))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 96)
    out_fp = model_fp.apply({"params": params}, ids)
    out_q = qmodel.apply({"params": qstate["params"]}, ids)
    rel = float(jnp.max(jnp.abs(out_fp - out_q)) /
                jnp.max(jnp.abs(out_fp)))
    assert rel < MODEL_REL_TOL


@pytest.mark.parametrize("use_flash", [False, True])
def test_int8_kv_greedy_tokens_stable(fp_model_and_params, use_flash):
    """Greedy decode with the int8 KV cache emits the SAME tokens as
    the bf16 cache, on both the dequant-in-kernel path and the dense
    fallback — per-token abs-max KV quantization is argmax-invisible
    on the test model."""
    _, params = fp_model_and_params
    gcfg = GenerationConfig(max_dec_len=6, min_dec_len=1,
                            decode_strategy="greedy_search",
                            eos_token_id=EOS, pad_token_id=PAD)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 96)
    toks = {}
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    try:
        for kvd in ("bf16", "int8"):
            cfg = GPTConfig(**{**BASE, "kv_cache_dtype": kvd,
                               "use_flash_attention": use_flash})
            reg.reset()
            toks[kvd] = np.asarray(generate(
                GPTModel(cfg), params, ids, None, jax.random.key(1),
                gcfg)).tolist()
            if use_flash:
                want = "attention/flash_decode" + (
                    "_int8" if kvd == "int8" else "")
                assert reg.counter(want) >= 1
                other = "attention/flash_decode" + (
                    "" if kvd == "int8" else "_int8")
                assert reg.counter(other) == 0
    finally:
        metrics.set_enabled(False)
        reg.reset()
    assert toks["int8"] == toks["bf16"]


def test_int8_kv_pool_density_accounting():
    """ISSUE acceptance at head_dim 64: an int8 pool sized to the SAME
    byte budget as bf16 holds >= 1.8x the pages, hence >= 1.8x the
    full-capacity slots ((pages-1)//cap_pages, one page held back as
    the chunked-prefill scratch)."""
    heads, d, page, layers = 16, 64, 128, 4
    assert kv_page_bytes(heads, d, page, "int8") == \
        heads * (d + 4) * page
    assert kv_page_bytes(heads, d, page, "bf16") == \
        heads * d * 2 * page
    bf16_pages = 64
    budget = pool_bytes(layers, heads, d, page, bf16_pages, "bf16")
    int8_pages = pool_pages_for_bytes(budget, layers, heads, d, page,
                                      "int8")
    assert pool_bytes(layers, heads, d, page, int8_pages,
                      "int8") <= budget
    cap_pages = 4                       # 512-token slots
    slots_bf16 = (bf16_pages - 1) // cap_pages
    slots_int8 = (int8_pages - 1) // cap_pages
    assert slots_int8 >= 1.8 * slots_bf16
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        kv_page_bytes(heads, d, page, "fp8")


def test_quant_config_validation():
    """The two knobs reject unknown values at construction."""
    with pytest.raises(ValueError, match="quant_execution"):
        GPTConfig(**{**BASE, "quant_execution": "int4"})
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        GPTConfig(**{**BASE, "kv_cache_dtype": "fp8"})


def test_quant_and_lora_sites_take_xla_under_a_multi_device_mesh(
        fp_model_and_params):
    """The int8 GEMM and the grouped LoRA GEMM are not shard_map-
    wrapped: with a multi-device mesh active (what the Engine and a
    dp8 generation config set) a bare pallas_call would be refused by
    Mosaic at lowering on real chips, past the site's try/except. The
    sites see the mesh at trace time and take their counted XLA path
    instead — same numbers, no crash reachable."""
    from paddlefleetx_tpu.parallel.mesh import (
        TopologyConfig, build_mesh, set_mesh,
    )
    _, params = fp_model_and_params
    cfg = GPTConfig(**{**BASE, "quant_execution": "weight_only_int8"})
    qparams, _ = quantize_param_tree(params)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 96)
    ref = GPTModel(cfg).apply({"params": qparams}, ids)
    lcfg = GPTConfig(**{**BASE, "lora_rank": 4, "lora_num_adapters": 3})
    lmodel = GPTModel(lcfg)
    lparams = nn.meta.unbox(lmodel.init(jax.random.PRNGKey(0),
                                        ids)["params"])
    aids = jnp.asarray([1, 2], jnp.int32)
    lref = lmodel.apply({"params": lparams}, ids, adapter_ids=aids)
    reg = metrics.get_registry()
    metrics.set_enabled(True)
    reg.reset()
    try:
        set_mesh(build_mesh(TopologyConfig(dp_degree=8)))
        out = GPTModel(cfg).apply({"params": qparams}, ids)
        lout = lmodel.apply({"params": lparams}, ids, adapter_ids=aids)
        assert reg.counter("quant/matmul") == 0
        assert reg.counter("quant/fallback/kernel_rejected") >= 4
        assert reg.counter("lora/grouped") == 0
        assert reg.counter("lora/fallback") >= 4
    finally:
        metrics.set_enabled(False)
        reg.reset()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(lout), np.asarray(lref),
                               atol=1e-4, rtol=1e-4)
