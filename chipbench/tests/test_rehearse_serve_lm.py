"""The cell ``smallthinker.serve-mixed-len`` (driver
``serve_open_loop_lm``) end to end with ``run.py --rehearse`` (CPU,
interpret mode, the tiny sizes of the mix's own ``rehearse`` block), and
what is particular to it: a long request is inside the checked sample, a
program that lacks the family is refused at once, the mixture's
generator keeps ``traffic_gen``'s contract, the readers return nothing
where there is nothing to read, and the bytes arithmetic gives the sizes
the configuration states.

    python -m pytest chipbench/tests/test_rehearse_serve_lm.py -q
"""

import importlib.util
import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _run import ROOT, run_cell  # noqa: E402

sys.path.insert(0, ROOT)
CELL = "smallthinker.serve-mixed-len"


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
    for name in ("paged_gqa_window_kernel_ran", "moe_kernel_ran",
                 "window_ring_pages", "long_requests_checked",
                 "served_logit_gap", "off_argmax_share"):
        assert sound[name]["ok"], sound[name]
    check = next(x for x in lines if x.get("check") == "reference")
    assert check["long_requests"] >= 1
    assert max(check["lengths"]) >= 700      # past the rehearsal's window
    if trace:
        assert {"experts_touched_per_tick", "window_pages_held_pct",
                "server_host_self_ms"} <= set(result["metrics"])
        assert result["metrics"]["window_pages_held_pct"]["value"] < 100
    else:
        assert set(result["metrics"]) == {"tpot_p95_ms", "setup_s"}


def test_a_program_without_the_family_is_refused_at_once():
    driver = _load("drivers", "serve_open_loop_lm")
    config = dict(_json("configs", "smallthinker-21b-a3b.json"),
                  model="no_such_family:Config:Model")
    ctx = types.SimpleNamespace(config=config, mix={}, rehearse=False)
    with pytest.raises(SystemExit) as e:
        driver.build(ctx)
    assert e.value.code == 2


def test_the_mixture_keeps_the_generators_contract():
    gen = _load("traffic_mixed")
    mix = _json("traffic", "mixed-len-open-loop.json")
    spec = mix["prompt_len"]

    def take(seed, n=400):
        out, it = [], gen.open_loop_blocks(mix, seed, 151936, 40.0)
        for _ in range(n):
            out.append(next(it))
        return out
    a, b = take(1), take(2)
    # one fixed pattern: the seed changes ids only
    assert [(t, len(p)) for t, p in a] == [(t, len(p)) for t, p in b]
    assert a[0][1] != b[0][1]
    rate = mix["rate_per_s"]
    n_ramp, n_win = round(rate * mix["ramp_s"]), round(rate * 40.0)
    assert a[0][0] < 0 <= a[n_ramp][0]
    window = [len(p) for t, p in a[n_ramp:n_ramp + n_win]]
    assert all(0 <= t < 40.0 for t, _ in a[n_ramp:n_ramp + n_win])
    longs = [n for n in window if n >= spec["long"]["min"]]
    assert len(longs) == round(spec["long_share"] * n_win)
    assert max(window) <= spec["long"]["max"]
    assert all(spec["short"]["min"] <= n <= spec["short"]["max"]
               for n in window if n < spec["long"]["min"])
    assert max(window) + mix["server"]["max_dec_len"] <= 12288


def test_readers_return_nothing_without_their_counters():
    value = _load("readers", "data_value")
    assert value.read({"key": "experts_touched_per_tick"}, {}) is None
    assert value.read({"key": "k"}, {"k": 2.0}) == 2.0
    roof = _load("readers", "kernel_roofline_in")
    spec = _json("layer_metrics", "moe_decode_gmm_roofline.json")
    run = {"trace": {"devices": [{"ops": [("moe_gmm.1 custom-call", 0, 1e7)]}],
                     "busy_s": 1.0},
           "config": _json("configs", "smallthinker-21b-a3b.json"),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert roof.read(spec["params"], run) is None     # no device counter
    got = roof.read(spec["params"], dict(
        run, moe_picks_traced=288 * 8, moe_touched_traced=61 * 8))
    assert 0 < got[0] <= 105, got


def test_the_bytes_arithmetic_gives_the_configurations_sizes():
    f = _load("flops_smallthinker")
    c = _json("configs", "smallthinker-21b-a3b.json")
    # one expert 3 x 2560 x 768 = 5.898 M parameters, 11.8 MB
    _, nbytes = f.moe_gmm_served(0, 1, c["hidden_size"],
                                 c["moe_ffn_hidden_size"])
    assert nbytes == 3 * 2560 * 768 * 2
    # 64 touched experts a layer, 8 layers: 6.04 GB a tick
    _, nbytes = f.moe_gmm_served(0, 64 * 8, 2560, 768)
    assert round(nbytes / 1e9, 2) == 6.04
    # a K row and a V row of 4 x 128 in bfloat16: 2,048 B a token a layer
    ops, nbytes = f.paged_decode_gqa(1, 0, 4, 28, 128, 1, 0)
    assert nbytes == 2048 and ops == 4 * 28 * 128
    ops, nbytes = f.paged_decode_gqa(10000, 4096, 4, 28, 128, 2, 6)
    assert nbytes == (2 * 10000 + 6 * 4096) * 2048
    tick = f.tick_model_bytes(32, 61, 2560, 768, 28, 4, 128, 151936, 8)
    assert 6.5e9 < tick < 7.2e9


def test_every_width_of_the_configuration_is_the_published_one():
    c = _json("configs", "smallthinker-21b-a3b.json")
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 52}
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (2560, 28, 4, 128)
    assert (c["moe_num_primary_experts"], c["moe_ffn_hidden_size"],
            c["moe_num_active_primary_experts"]) == (64, 768, 6)
    assert (c["sliding_window_size"], c["rope_theta"], c["vocab_size"],
            c["max_position_embeddings"]) == (4096, 1500000, 151936, 16384)
    assert c["rope_layout"] == [0, 1, 1, 1] * 13 == c["sliding_window_layout"]
