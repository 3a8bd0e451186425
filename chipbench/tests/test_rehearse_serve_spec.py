"""The cell ``kexaone.serve-reasoning-mtp`` (driver
``serve_open_loop_spec``) end to end with ``run.py --rehearse`` (CPU,
interpret mode, the tiny sizes of the mix's own ``rehearse`` block), and
what is particular to it: the server speculates with the model's own
block and reads its launches a step late, a request's ticks are
counted from what each committed, the served drafts are judged against
the reference block's argmax, the readers return nothing where there is
nothing to read, the bytes arithmetic gives the sizes the configuration
states, the mix is what the issue names, and the chipbench reference
agrees with the repository's own, main model and block.

    python -m pytest chipbench/tests/test_rehearse_serve_spec.py -q
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
CELL = "kexaone.serve-reasoning-mtp"
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
    for name in ("speculative_with_the_models_source", "spec_drafted",
                 "paged_gqa_window_verify_kernel_ran",
                 "attention_fallbacks", "moe_kernel_ran",
                 "requests_not_completed", "requests_shed",
                 "requests_preempted", "prefix_refused_window",
                 "window_ring_pages", "long_requests_checked",
                 "served_logit_gap", "off_argmax_share",
                 "draft_off_argmax_share"):
        assert sound[name]["ok"], sound[name]
    check = next(x for x in lines if x.get("check") == "reference")
    assert check["long_requests"] >= 1
    assert max(check["lengths"]) >= 300      # two chunks of 256
    # a draft a tick a request, but the one after its last token
    assert check["served_tokens"] - 2 * check["requests"] \
        <= check["served_drafts"] <= check["served_tokens"]
    counters = next(x["counters"] for x in lines if "counters" in x)
    assert counters["serving/spec_source/mtp"] == 1
    assert counters.get("serving/harvest_flushed/spec", 0) == 0
    assert counters["serving/harvest_deferred"] > 0
    assert counters["serving/mtp_positions/tick"] == \
        counters["serving/decode_tokens"]
    assert counters["serving/spec_rollback_columns"] == \
        2 * counters["serving/spec_drafted"] \
        - counters["serving/decode_tokens"]
    if trace:
        assert {"spec_accept_pct", "spec_rollback_per_tick",
                "experts_touched_per_tick", "window_pages_held_pct",
                "server_host_self_ms", "live_rows_per_tick"} <= set(
                    result["metrics"])
        assert 0 <= result["metrics"]["spec_accept_pct"]["value"] < 50
        rows = result["metrics"]["live_rows_per_tick"]["value"]
        assert 0 < result["metrics"]["spec_rollback_per_tick"][
            "value"] <= 4 and 0 < rows <= 4          # 4 slots
    else:
        assert set(result["metrics"]) == {"tpot_p95_ms", "setup_s"}


def test_the_mix_is_what_the_issue_names():
    mix = _json("traffic", "reasoning-mtp-open-loop.json")
    gen = _load("traffic_gen")
    assert mix["generator"] == "traffic_gen"
    assert mix["prompt_len"] == {"median": 1024, "sigma": 1.0, "min": 64,
                                 "max": 8192}
    assert mix["schedule_seed"] == 38
    s = mix["server"]
    assert (s["num_slots"], s["page_size"], s["pool_pages"],
            s["prefill_chunk_pages"], s["max_dec_len"],
            s["device_loop_ticks"], s["prefix_sharing"],
            s["spec_method"], s["spec_tokens"]) == (
                64, 128, 2305, 4, 1024, 1, True, "mtp", 1)
    assert s["pool_pages"] == 1 + 64 * 36      # 36 pages hold 4,608
    assert mix["ramp_s"] == 25.0 and mix["trace_s"] == 0.5
    assert mix["prompt_len"]["max"] + s["max_dec_len"] == 9216 <= 16384
    assert mix["check_long_requests"] == 2 and \
        mix["check_long_from"] == 4096       # nine or more chunks
    it = gen.open_loop_blocks(mix, 1, 19200, 40.0)
    window = [next(it) for _ in range(300)]
    assert max(max(p) for _, p in window) < 19200 - 1
    due = np.array([t for t, _ in window])
    inside = (due >= 0) & (due < 40.0)
    assert inside.sum() == round(mix["rate_per_s"] * 40)
    longs = sorted(len(p) for (_, p), i in zip(window, inside) if i)[-2:]
    assert min(longs) > 4096                 # the two longest due


def test_a_requests_ticks_are_counted_from_what_each_committed():
    """Three requests: one that committed a token a tick, one whose
    every second tick committed two, one with no completion; the ticks
    in the span are the server's, one after another."""
    from types import SimpleNamespace
    drv = _load("drivers", "serve_open_loop_spec")

    def completion(starts):
        return SimpleNamespace(ttft_ms=1000.0, tokens=[0] * 99,
                               drafts=[(i + 1, 7) for i in starts])
    loop = SimpleNamespace(tick_ends=[10.0 + j for j in range(8)], reqs={
        1: {"submitted": 9.0, "prompt": [0] * 100,
            "completion": completion([0, 1, 2, 3])},
        2: {"submitted": 11.0, "prompt": [0] * 200,
            "completion": completion([0, 2, 3, 5])},
        3: {"submitted": 9.0, "prompt": [0] * 50, "completion": None}})
    whole, cut = drv.kv_tokens_read(loop, 10.0, 18.0, 128)
    assert whole == (100 + 101 + 102 + 103) + (200 + 202 + 203 + 205)
    assert cut == (100 + 101 + 102 + 103) + 4 * 128
    # a span that opens two ticks later reads each request's later ticks
    whole, _ = drv.kv_tokens_read(loop, 12.0, 18.0, 128)
    assert whole == (102 + 103) + (200 + 202 + 203 + 205)


def test_readers_return_nothing_without_their_counters():
    value = _load("readers", "data_value")
    for name in ("spec_accept_pct", "spec_rollback_per_tick"):
        spec = _json("layer_metrics", name + ".json")
        assert value.read(spec["params"], {}) is None
        assert value.read(spec["params"], {spec["params"]["key"]: 1.5}) \
            == 1.5
    tick = _load("readers", "module_time")
    spec = _json("layer_metrics", "verify_tick_device_ms.json")
    trace = {"devices": [{"modules": [
        ("jit_decode_step(1)", 0, 3e6)]}]}
    assert tick.read(spec["params"], {"trace": trace}) is None
    trace["devices"][0]["modules"] += [
        ("jit_verify_step(2)", 0, 17e6), ("jit_verify_step(2)", 0, 15e6)]
    assert tick.read(spec["params"], {"trace": trace})[0] == 16.0


def test_the_bytes_arithmetic_gives_the_configurations_sizes():
    f = _load("flops_exaone_moe")
    c = _json("configs", "k-exaone-236b-a23b.json")
    gb = {k: round(v / 1e9, 3) for k, v in f.tick_bytes(
        c, 36, 16, 36 * 2500, 36 * 128).items()}
    # the held weights, all 16 experts of every sparse layer touched:
    # layer 0 + four sparse layers, the block, the head's slice twice
    assert (gb["main_layers"], gb["mtp_block"], gb["head"]) == (
        6.952, 1.663, 0.472)
    assert gb["pages_global"] == round(36 * 2500 * 2 * 4096 / 1e9, 3)
    assert gb["pages_window"] == round(36 * 128 * 4 * 4096 / 1e9, 3)
    # nothing touched: attention, router, shared expert, norms
    bare = f.layer_weight_bytes(c, 0) / 2
    assert bare == 113246208 + 128 * 2 + 2 * 6144 + 6144 * 128 + 37748736


def test_every_width_of_the_configuration_is_the_published_one():
    c = _json("configs", "k-exaone-236b-a23b.json")
    assert c["reduced"] == ["num_hidden_layers", "num_experts",
                            "vocab_size", "max_position_embeddings"]
    assert c["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600,
        "max_position_embeddings": 262144}
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["max_position_embeddings"], c["experts_held"]) == (
                5, 16, 19200, 16384, [0, 16])
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (6144, 64, 8, 128)
    assert (c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["num_shared_experts"],
            c["routed_scaling_factor"]) == (18432, 2048, 8, 1, 2.5)
    assert (c["sliding_window"], c["num_nextn_predict_layers"],
            c["rope_parameters"]["rope_theta"]) == (128, 1, 1000000)
    assert c["layer_types"][:5] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert len(c["layer_types"]) == len(c["mlp_layer_types"]) == 48
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(e for e in bench["configs"] if e["name"] == c["name"])
    assert entry["reduced"] == c["reduced"]
    if os.path.exists(CATALOG):
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"K-EXAONE-236B-A23B"' in line)
        for key, value in row["config"].items():
            if key not in c["reduced"]:
                assert c[key] == value, key
        assert c["source"] == entry["source"] == row["source_url"]


def test_the_chipbench_reference_agrees_with_the_repositorys():
    """A layer at a time with widened weights, attention a K/V group
    and a block of query rows at a time, against the plain one, on the
    rehearsal's sizes: the main model's logits and the block's
    argmax, past the window and past one block of rows."""
    import jax
    import jax.numpy as jnp
    from paddlefleetx_tpu.models.exaone_moe import (
        ExaoneMoeConfig, ExaoneMoeForCausalLM, reference,
    )
    ref = _load("reference", "exaone_moe_decoder")
    tiny = _json("traffic", "reasoning-mtp-open-loop.json")["rehearse"][
        "config"]
    cfg = dict(_json("configs", "k-exaone-236b-a23b.json"), **tiny)
    mcfg = ExaoneMoeConfig(
        vocab_size=64, hidden_size=64, num_hidden_layers=5,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16,
        intermediate_size=96, moe_intermediate_size=32, num_experts=16,
        num_experts_per_tok=4, experts_held=(0, 8),
        max_position_embeddings=2048, initializer_range=0.2)
    params = jax.jit(ExaoneMoeForCausalLM(mcfg).init)(
        {"params": jax.random.key(0)}, jnp.zeros((1, 8), jnp.int32))["params"]
    tokens = np.random.default_rng(0).integers(0, 60, 1300).tolist()
    got, _ = ref.logits(cfg, params, tokens, (1200, 1300))
    plain = dict(cfg, experts_held=(0, 8), rope_theta=1e6)
    want = jax.jit(lambda p, ids: reference.forward(plain, p, ids))(
        params, jnp.asarray([tokens]))[0, 1200:1300]
    assert float(jnp.max(jnp.abs(want))) > 1.0
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    best = ref.mtp_argmax(cfg, params, tokens, (1200, 1299))
    want = np.asarray(jax.jit(
        lambda p, ids: reference.mtp_logits(plain, p, ids))(
            params, jnp.asarray([tokens])))[0, 1200:1299]
    top = np.sort(want, axis=-1)[:, -2:]
    sure = top[:, 1] - top[:, 0] > 4e-4
    assert sure.mean() > 0.9
    assert (best == want.argmax(-1))[sure].all()
    with pytest.raises(ValueError, match="no next token"):
        ref.mtp_argmax(cfg, params, tokens, (1200, 1300))
