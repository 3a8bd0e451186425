"""The cell ``granite4h.serve-chat-bursty`` (driver
``serve_open_loop_ssm``) end to end with ``run.py --rehearse`` (CPU,
interpret mode, the tiny sizes of the mix's own ``rehearse`` block), and
what is particular to it: a long request (two chunks, the second
padded) is inside the checked sample, the state class is counted and is
most of what live slots hold, the readers return nothing where there is
nothing to read, the bytes arithmetic gives the sizes the configuration
states, the decays the benchmark draws spread over (0, 1), the mix is
what the issue names, and the chipbench reference agrees with the
repository's own.

    python -m pytest chipbench/tests/test_rehearse_serve_ssm.py -q
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
CELL = "granite4h.serve-chat-bursty"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    for name in ("ssd_decode_kernel_ran", "paged_gqa_kernel_ran",
                 "attention_fallbacks", "requests_not_completed",
                 "requests_shed", "prefix_refused_recurrent",
                 "long_requests_checked", "served_logit_gap",
                 "off_argmax_share"):
        assert sound[name]["ok"], sound[name]
    check = next(x for x in lines if x.get("check") == "reference")
    assert check["long_requests"] >= 1
    assert max(check["lengths"]) >= 600      # two chunks of 512
    counters = next(x["counters"] for x in lines if "counters" in x)
    assert counters["serving/state_resets"] == 3 * counters[
        "serving/admitted"]
    assert counters["serving/state_rows_held"] == 3 * counters[
        "serving/decode_rows_live"]
    assert counters["serving/slots_full_steps"] > 0   # 4 slots, bursts
    if trace:
        assert {"state_cache_share_pct", "slots_full_step_pct",
                "server_host_self_ms", "live_rows_per_tick"} <= set(
                    result["metrics"])
        assert 50 < result["metrics"]["state_cache_share_pct"]["value"] < 100
        # reported even at 0: two seconds of window may hold no such
        # step (the run as a whole does, above)
        assert 0 <= result["metrics"]["slots_full_step_pct"]["value"] < 100
    else:
        assert set(result["metrics"]) == {"tpot_p95_ms", "setup_s"}


def test_the_mix_is_what_the_issue_names():
    mix = _json("traffic", "chat-bursty-open-loop.json")
    gen = _load("traffic_bursty")
    assert mix["prompt_len"] == {"median": 384, "sigma": 0.9, "min": 32,
                                 "max": 3072}
    assert (mix["gap_shape"], mix["schedule_seed"]) == (0.25, 36)
    s = mix["server"]
    assert (s["num_slots"], s["page_size"], s["pool_pages"],
            s["prefill_chunk_pages"], s["max_dec_len"],
            s["device_loop_ticks"], s["prefix_sharing"]) == (
                64, 128, 1665, 4, 256, 1, True)
    assert s["pool_pages"] == 1 + 64 * 26      # 26 pages hold 3,328
    assert mix["ramp_s"] == 20.0 and mix["trace_s"] == 0.5
    assert mix["prompt_len"]["max"] + s["max_dec_len"] == 3328 <= 8192
    assert mix["check_long_requests"] == 2 and \
        mix["check_long_from"] == 2048       # four or more chunks
    it = gen.open_loop_blocks(mix, 1, 100352, 40.0)
    window = [next(it) for _ in range(400)]
    assert max(max(p) for _, p in window) < 100352 - 1
    due = np.array([t for t, _ in window])
    inside = (due >= 0) & (due < 40.0)
    assert inside.sum() == round(mix["rate_per_s"] * 40)
    longs = sorted(len(p) for (_, p), i in zip(window, inside) if i)[-2:]
    assert min(longs) > 2048                 # the two longest due: > 4 chunks
    gaps = gen.gamma_gaps(200, mix["rate_per_s"], mix["gap_shape"])
    assert 1.8 < gaps.std() / gaps.mean() < 2.0


def test_readers_return_nothing_without_their_counters():
    value = _load("readers", "data_value")
    assert value.read({"key": "slots_full_step_pct"}, {}) is None
    roof = _load("readers", "kernel_roofline_in")
    run = {"trace": {"devices": [{"ops": [
        ("ssd_decode.1 custom-call", 0, 7e6)]}], "busy_s": 1.0},
           "config": _json("configs", "granite-4.0-h-micro.json"),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    spec = _json("layer_metrics", "ssd_decode_roofline.json")
    assert roof.read(spec["params"], run) is None       # no such counter
    got = roof.read(spec["params"], dict(run, ssd_rows_traced=30,
                                         ssm_layers=36))
    assert 0 < got[0] <= 105, got
    share = _load("readers", "kernel_share")
    spec = _json("layer_metrics", "ssd_decode_share_pct.json")
    assert share.read(spec["params"], dict(run, trace={
        "devices": [{"ops": []}], "busy_s": 1.0})) is None


def test_the_bytes_arithmetic_gives_the_configurations_sizes():
    f = _load("flops_granite_hybrid")
    c = _json("configs", "granite-4.0-h-micro.json")
    # a live row's states on one layer: 64 x 64 x 128 float32, read and
    # written once: 4.19 MB, and 66.6 KB of operands and output
    ops, nbytes = f.ssd_decode_step(1, 1, c["mamba_n_heads"],
                                    c["mamba_d_head"], c["mamba_d_state"])
    assert nbytes == 2 * 64 * 64 * 128 * 4 + 4 * (4 * 4096 + 2 * 128)
    assert ops == 6 * 64 * 64 * 128
    # a tick streams the whole model: 6.38 GB
    tick = f.tick_model_bytes(
        38, c["hidden_size"], c["shared_intermediate_size"],
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
        c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
        c["mamba_d_conv"], c["vocab_size"], 4, 36)
    assert round(tick / 1e9, 2) == 6.38
    s = _json("traffic", "chat-bursty-open-loop.json")["server"]
    pages, state = f.cache_bytes(
        s["num_slots"], s["pool_pages"], s["page_size"], 4,
        c["num_key_value_heads"], c["head_dim"], 36, c["mamba_n_heads"],
        c["mamba_d_head"], c["mamba_d_state"], c["mamba_d_conv"])
    assert (round(pages / 1e9, 2), round(state / 1e9, 2)) == (1.75, 4.97)


def test_every_width_of_the_configuration_is_the_published_one():
    c = _json("configs", "granite-4.0-h-micro.json")
    assert c["reduced"] == ["max_position_embeddings"]
    assert c["published"] == {"max_position_embeddings": 131072}
    assert c["max_position_embeddings"] == 8192
    assert (c["num_hidden_layers"], c["vocab_size"], c["hidden_size"]) == (
        40, 100352, 2048)
    assert (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["attention_multiplier"]) == (32, 8, 64, 1 / 64)
    assert (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_n_groups"], c["mamba_d_conv"], c["mamba_chunk_size"],
            c["mamba_expand"]) == (64, 64, 128, 1, 4, 256, 2)
    assert (c["shared_intermediate_size"], c["intermediate_size"]) == (
        8192, 8192)
    assert (c["embedding_multiplier"], c["residual_multiplier"],
            c["logits_scaling"]) == (12, 0.22, 8)
    assert [i for i, kind in enumerate(c["layer_types"])
            if kind == "attention"] == [5, 15, 25, 35]
    if os.path.exists(CATALOG):
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"granite-4.0-h-micro"' in line)
        for key, value in row["config"].items():
            if key not in c["reduced"]:
                assert c[key] == value, key
        assert c["source"] == row["source_url"]


def test_the_drawn_decays_spread_over_the_unit_interval():
    import jax
    import jax.numpy as jnp
    ref = _load("reference", "granite_hybrid_decoder")
    key = jax.random.key(0)

    def drawn(i):
        return (0.02 * jax.random.normal(jax.random.fold_in(key, i),
                                         (64,))).astype(jnp.bfloat16)
    layer = {"mamba": {"A_log": drawn(0), "dt_bias": drawn(1),
                       "D": drawn(2)}}
    out = ref.spread_decays({"layers_1": layer, "norm": {"scale": 1}})
    mixer = out["layers_1"]["mamba"]
    assert mixer["A_log"].dtype == jnp.bfloat16
    assert (np.asarray(mixer["D"], np.float32) == 1.0).all()
    a_neg = np.exp(np.asarray(mixer["A_log"], np.float32))
    dt = np.log1p(np.exp(np.asarray(mixer["dt_bias"], np.float32)))
    assert 1.0 <= a_neg.min() < 3 and 13 < a_neg.max() <= 16.1
    assert 0.0009 < dt.min() < 0.003 and 0.03 < dt.max() < 0.11
    # a step's decay, any head's A with any head's dt
    decay = np.exp(-a_neg[:, None] * dt[None, :])
    assert 0.15 < decay.min() < 0.6 and 0.995 < decay.max() < 1.0
    assert out["norm"] == {"scale": 1}


def test_the_chipbench_reference_agrees_with_the_repositorys():
    """A layer at a time with widened weights, attention a K/V group
    and a block of query rows at a time, against the plain one, on the
    rehearsal's sizes; the decays spread in both."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from paddlefleetx_tpu.models.granite_hybrid import (
        GraniteHybridConfig, GraniteHybridForCausalLM, reference,
    )
    ref = _load("reference", "granite_hybrid_decoder")
    tiny = _json("traffic", "chat-bursty-open-loop.json")["rehearse"][
        "config"]
    cfg = dict(_json("configs", "granite-4.0-h-micro.json"), **tiny)
    mcfg = GraniteHybridConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        layer_types=tiny["layer_types"], num_attention_heads=8,
        num_key_value_heads=2, head_dim=16, attention_multiplier=1 / 16,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=128,
        mamba_chunk_size=64, shared_intermediate_size=128,
        max_position_embeddings=2048, initializer_range=0.2)
    params = GraniteHybridForCausalLM(mcfg).init(
        {"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32))["params"]
    tokens = np.random.default_rng(0).integers(0, 500, 300).tolist()
    got, flipped = ref.logits(cfg, params, tokens, (200, 300))
    want = reference.forward(
        dataclasses.asdict(mcfg), ref.spread_decays(params),
        jnp.asarray([tokens]))[0, 200:300]
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert flipped == 0.0
