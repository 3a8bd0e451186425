"""The cell ``kanana2.pretrain-ep8share`` (driver ``train_lm``) beyond
what ``test_rehearse.py`` runs for every cell: its control comes out as
not correct, a program that lacks the configuration is refused at once,
the new readers return nothing where there is nothing to read, and the
FLOPs arithmetic gives the shares the cell's ``why`` states.

    python -m pytest chipbench/tests/test_rehearse_lm.py -q
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
CELL = "kanana2.pretrain-ep8share"


def _load(*parts):
    path = os.path.join(ROOT, "chipbench", *parts) + ".py"
    spec = importlib.util.spec_from_file_location("_".join(parts), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4])
def test_the_fp8_control_is_not_correct(seed):
    rc, lines, last, err = run_cell(CELL, seed=seed,
                                    extra=["--control", "fp8"])
    assert rc == 0, err[-2000:]
    control = next(x for x in lines if x.get("control") == "fp8")
    sound = {x["compared"]: x for x in lines
             if isinstance(x.get("compared"), str)}
    # each number reads three times the sound program's, or more, and is
    # past its limit
    for c in control["compared"]:
        assert c["value"] >= 3 * sound[c["name"]]["value"], (c, sound)
    failed = {c["name"] for c in control["compared"] if not c["ok"]}
    assert {"loss_gap", "grad_norm_gap", "dparam_norm_gap"} <= failed, \
        control
    assert sound["flash_mla_carried_the_step"]["ok"]
    assert sound["moe_kernel_carried_the_step"]["ok"]
    assert json.loads(last)["correct"] is True


def test_a_program_without_the_configuration_is_refused_at_once():
    driver = _load("drivers", "train_lm")
    ctx = types.SimpleNamespace(
        rehearse=False, root=ROOT, mix={}, config={"yaml": "no/such.yaml"})
    with pytest.raises(SystemExit) as e:
        driver.run(ctx)
    assert e.value.code == 2


def test_the_arithmetic_gives_the_shares_the_cell_states():
    flops = _load("flops_deepseek_v3")
    driver = _load("drivers", "train_lm")
    model = driver.model_of(_config())
    per_token = flops.model_flops_per_token(model, 4096)
    assert abs(per_token / 3 - 0.720e9) < 0.002e9
    one_layer = dict(model, num_hidden_layers=2, vocab_held=(0, 0))
    dense_only = dict(one_layer, num_hidden_layers=1)
    expert = (flops.model_flops_per_token(one_layer, 4096)
              - flops.model_flops_per_token(dense_only, 4096)) / 3
    mla = 2 * 26.35e6 + 32 * 4096 * 320
    assert abs(mla / expert - 0.78) < 0.01
    ops, nbytes = flops.flash_mla_train(4, 32, 4096, 192, 128, 5)
    assert ops == (320 + 832) * 4 * 32 * 4096 ** 2 * 5
    ops, _ = flops.moe_gmm_train(1000, 2048, 768, 16, 4)
    assert ops == 9 * 2 * 1000 * 2048 * 768


def test_the_new_readers_read_nothing_where_nothing_is():
    reader = _load("readers", "kernel_roofline_in")
    share = _load("readers", "kernel_share")
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           "moe_gmm_roofline.json")) as f:
        spec = json.load(f)["params"]
    trace = {"devices": [{"ops": [], "modules": []}], "busy_s": 1.0}
    run = {"trace": trace, "peaks": {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9},
           "config": _config(), "events": [{"loss": 1.0}],
           "trace_events": [{"loss": 1.0}],
           "trace_steps": 10, "experts_held_count": 16,
           "expert_layers": 4}
    assert reader.read(spec, run) is None          # no such op
    assert share.read({"patterns": spec["patterns"]}, run) is None
