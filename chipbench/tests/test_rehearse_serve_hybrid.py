"""The cell ``solaropen2.serve-reasoning`` (driver
``serve_open_loop_hybrid``) end to end with ``run.py --rehearse`` (CPU,
interpret mode, the tiny sizes of the mix's own ``rehearse`` block), and
what is particular to it: a long request (two chunks, the second
padded) is inside the checked sample, the state class is counted, the
readers return nothing where there is nothing to read, the bytes
arithmetic gives the sizes the configuration states, the decays the
benchmark draws spread over (0, 1), and the chipbench reference agrees
with the repository's own.

    python -m pytest chipbench/tests/test_rehearse_serve_hybrid.py -q
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _run import ROOT, run_cell  # noqa: E402

sys.path.insert(0, ROOT)
CELL = "solaropen2.serve-reasoning"


def _load(*parts):
    path = os.path.join(ROOT, "chipbench", *parts) + ".py"
    spec = importlib.util.spec_from_file_location("_".join(parts), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("seed,trace", [(3, 0), (2 ** 31 + 4, 1)])
def test_the_cell_rehearses(seed, trace):
    rc, lines, last, err = run_cell(CELL, seed=seed, trace=trace)
    assert rc == 0, err[-2000:]
    result = json.loads(last)
    assert result["correct"] is True and result["failed"] == 0
    assert "rehearsal" in result["device"]
    sound = {x["compared"]: x for x in lines
             if isinstance(x.get("compared"), str)}
    for name in ("kda_decode_kernel_ran", "paged_gqa_kernel_ran",
                 "attention_fallbacks", "moe_kernel_ran",
                 "requests_not_completed", "requests_shed",
                 "prefix_refused_recurrent", "long_requests_checked",
                 "served_logit_gap", "off_argmax_share"):
        assert sound[name]["ok"], sound[name]
    check = next(x for x in lines if x.get("check") == "reference")
    assert check["long_requests"] >= 1
    assert max(check["lengths"]) >= 700      # two chunks of 512
    counters = next(x["counters"] for x in lines if "counters" in x)
    assert counters["serving/state_resets"] == 3 * counters[
        "serving/admitted"]
    assert counters["serving/state_rows_held"] == 3 * counters[
        "serving/decode_rows_live"]
    if trace:
        assert {"experts_touched_per_tick", "state_cache_share_pct",
                "server_host_self_ms"} <= set(result["metrics"])
        assert 0 < result["metrics"]["state_cache_share_pct"]["value"] < 100
    else:
        assert set(result["metrics"]) == {"tpot_p95_ms", "setup_s"}


def test_the_mix_is_what_the_issue_names():
    mix = _json("traffic", "reasoning-open-loop.json")
    gen = _load("traffic_mixed")
    spec = mix["prompt_len"]
    assert spec == {"long_share": 0.2,
                    "short": {"median": 768, "sigma": 0.8, "min": 64,
                              "max": 4096},
                    "long": {"min": 8192, "max": 30720}}
    s = mix["server"]
    assert (s["num_slots"], s["page_size"], s["pool_pages"],
            s["prefill_chunk_pages"], s["max_dec_len"],
            s["device_loop_ticks"], s["prefix_sharing"]) == (
                96, 128, 6001, 4, 768, 1, True)
    assert mix["ramp_s"] == 30.0 and 0.5 <= mix["trace_s"] <= 1.0
    assert spec["long"]["max"] + s["max_dec_len"] == 31488 <= 32768
    it = gen.open_loop_blocks(mix, 1, 24576, 40.0)
    window = [next(it) for _ in range(400)]
    assert max(max(p) for _, p in window) < 24576 - 1


def test_readers_return_nothing_without_their_counters():
    value = _load("readers", "data_value")
    assert value.read({"key": "state_cache_share_pct"}, {}) is None
    roof = _load("readers", "kernel_roofline_in")
    run = {"trace": {"devices": [{"ops": [
        ("kda_decode.1 custom-call", 0, 2e6),
        ("moe_gmm.1 custom-call", 0, 1e7)]}], "busy_s": 1.0},
           "config": _json("configs", "solar-open2-250b.json"),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    for name, data in (
            ("kda_decode_roofline", {"kda_rows_traced": 50,
                                     "linear_layers": 3}),
            ("moe_share_decode_gmm_roofline", {
                "moe_picks_traced": 50 * 4, "moe_touched_traced": 27 * 4})):
        spec = _json("layer_metrics", name + ".json")
        assert roof.read(spec["params"], run) is None   # no such counter
        got = roof.read(spec["params"], dict(run, **data))
        assert 0 < got[0] <= 105, (name, got)


def test_the_bytes_arithmetic_gives_the_configurations_sizes():
    f = _load("flops_solar_open2")
    c = _json("configs", "solar-open2-250b.json")
    # a live row's states on one layer: 64 x 128 x 128 float32, read and
    # written once: 8.39 MB, and 295 KB of operands
    ops, nbytes = f.kda_decode_step(1, 1, c["linear_num_heads"],
                                    c["linear_head_dim"])
    assert nbytes == 2 * 64 * 128 * 128 * 4 + 9 * 64 * 128 * 4
    assert ops == 7 * 64 * 128 * 128
    # one held expert 3 x 4096 x 1280 = 15.73 M parameters, 31.5 MB; 40
    # a layer over 4 layers: 5.03 GB
    served = _load("flops_smallthinker").moe_gmm_served
    _, nbytes = served(0, 1, c["hidden_size"], c["moe_intermediate_size"])
    assert nbytes == 3 * 4096 * 1280 * 2
    _, nbytes = served(0, 40 * 4, 4096, 1280)
    assert round(nbytes / 1e9, 2) == 5.03
    tick = f.tick_model_bytes(55, 27, 4096, 1280, 64, 8, 128, 64, 128,
                              24576, 1, 3)
    # mixers 1.05 GB, router + shared + 27 touched experts a layer
    # 3.5 GB, head 0.2 GB
    assert 4.6e9 < tick < 5.0e9


def test_every_width_of_the_configuration_is_the_published_one():
    c = _json("configs", "solar-open2-250b.json")
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Solar-Open2-250B"' in line) if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else None
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size", "max_position_embeddings"]
    assert c["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320,
        "vocab_size": 196608, "max_position_embeddings": 1048576}
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"],
            c["max_position_embeddings"]) == (4, 40, 24576, 32768)
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (4096, 64, 8, 128)
    assert c["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["n_shared_experts"]) == (1280, 8, 1)
    assert c["gqa_layers"] == list(range(0, 48, 4))
    if row is not None:
        for key, value in row["config"].items():
            if key not in c["reduced"]:
                assert c[key] == value, key
        assert c["source"] == row["source_url"]


def test_the_drawn_decays_spread_over_the_unit_interval():
    import jax
    import jax.numpy as jnp
    ref = _load("reference", "solar_open2_decoder")
    key = jax.random.key(0)
    layer = {"linear_attn": {
        "A_log": 0.02 * jax.random.normal(key, (64,)).astype(jnp.bfloat16),
        "dt_bias": 0.02 * jax.random.normal(
            jax.random.fold_in(key, 1), (64, 128)).astype(jnp.bfloat16)}}
    out = ref.spread_decays({"layers_1": layer, "norm": {"scale": 1}})
    mixer = out["layers_1"]["linear_attn"]
    assert mixer["A_log"].dtype == jnp.bfloat16
    a = np.exp(-np.exp(np.asarray(mixer["A_log"], np.float32))[:, None]
               * np.log1p(np.exp(np.asarray(mixer["dt_bias"], np.float32))))
    assert 0.15 < a.min() < 0.5 and 0.995 < a.max() < 1.0
    assert 0.9 < np.median(a) < 0.99
    assert out["norm"] == {"scale": 1}


def test_the_chipbench_reference_agrees_with_the_repositorys():
    """Blocks of heads, of query rows and a scan over experts against
    the plain one, on the rehearsal's sizes; the decays spread in
    both."""
    import jax
    import jax.numpy as jnp
    from paddlefleetx_tpu.models.solar_open2 import (
        SolarOpen2Config, SolarOpen2ForCausalLM, reference,
    )
    ref = _load("reference", "solar_open2_decoder")
    tiny = _json("traffic", "reasoning-open-loop.json")["rehearse"]["config"]
    cfg = dict(_json("configs", "solar-open2-250b.json"), **tiny)
    mcfg = SolarOpen2Config(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=16, num_key_value_heads=2, head_dim=16,
        linear_num_heads=8, linear_head_dim=128, n_routed_experts=16,
        experts_held=(0, 4), num_experts_per_tok=3,
        moe_intermediate_size=32, max_position_embeddings=2048,
        initializer_range=0.2)
    params = SolarOpen2ForCausalLM(mcfg).init(
        {"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32))["params"]
    tokens = np.random.default_rng(0).integers(0, 500, 300).tolist()
    got, flipped = ref.logits(cfg, params, tokens, (200, 300))
    import dataclasses
    want = reference.forward(
        dataclasses.asdict(mcfg), ref.spread_decays(params),
        jnp.asarray([tokens]), 0, 4)[0, 200:300]
    assert float(jnp.max(jnp.abs(want))) > 3.0
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=0)
    assert 0.0 <= flipped < 0.05
