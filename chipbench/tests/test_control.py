"""The comparison that decides ``correct`` is one that has been shown to
fail: (1) the reference at the next lower precision (fp8 for a bfloat16
configuration), put in the program's place, comes out as not correct at
a size a test run can hold; (2) a run whose timed path is broken
underneath comes out with ``correct`` false. The readings at the cells'
own sizes, on the chip, are in PERF.md section 2.
"""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _run import ROOT, run_cell  # noqa: E402

BROKEN = os.path.join(ROOT, "chipbench", "tests", "broken_run.py")


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 5])
def test_training_control_in_fp8_is_not_correct(seed):
    rc, lines, last, err = run_cell("gpt345m.pretrain", seed=seed,
                                    extra=["--control", "fp8"])
    assert rc == 0, err[-2000:]
    control = next(x for x in lines if x.get("control") == "fp8")
    sound = {x["compared"]: x for x in lines
             if isinstance(x.get("compared"), str)}
    failed = [c["name"] for c in control["compared"] if not c["ok"]]
    assert failed, control
    # and by a wide margin, not by luck: each number the control moves
    # reads three times what the sound program reads, or more
    for c in control["compared"]:
        if c["name"] in ("loss_gap", "grad_norm_gap"):
            assert c["value"] >= 3 * sound[c["name"]]["value"], (c, sound)
    assert json.loads(last)["correct"] is True


def test_serving_control_in_fp8_is_not_correct():
    """At a test's size the served rows are too few and too easy to
    separate precisions, so the control reads every position of seeded
    random sequences: the token fp8 puts first lies further under the
    reference's best than the one bfloat16 puts first, three times or
    more, on three seeds."""
    code = r'''
import os, sys, types
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %r)
import importlib.util, jax, jax.numpy as jnp, numpy as np
spec = importlib.util.spec_from_file_location(
    "drv", os.path.join(%r, "chipbench", "drivers", "serve_open_loop.py"))
drv = importlib.util.module_from_spec(spec); spec.loader.exec_module(drv)
shapes = {"gpt": {"embeddings": {"word_embeddings": (2048, 128),
                                  "position_embeddings": (256, 128)},
                  "final_norm": {"scale": (128,), "bias": (128,)}}}
for i in range(2):
    shapes["gpt"][f"decoder_{i}"] = {
        "norm1": {"scale": (128,), "bias": (128,)},
        "norm2": {"scale": (128,), "bias": (128,)},
        "linear1": {"kernel": (128, 512), "bias": (512,)},
        "linear2": {"kernel": (512, 128), "bias": (128,)},
        "self_attn": {"qkv_proj": {"kernel": (128, 3, 2, 64),
                                   "bias": (3, 2, 64)},
                      "out_proj": {"kernel": (2, 64, 128),
                                   "bias": (128,)}}}
abstract = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                        shapes, is_leaf=lambda x: isinstance(x, tuple))
worst = {"bfloat16": [], "fp8": []}
for seed in (1, 2 ** 31 + 2, 3):
    ctx = types.SimpleNamespace(
        seed=seed, config={"max_position_embeddings": 256})
    rng = np.random.default_rng(seed)
    rows = [rng.integers(0, 2047, 256).tolist() for _ in range(3)]
    sample = [(r[:1], r[1:]) for r in rows]
    for prec in worst:
        gaps, _ = drv.served_gaps(ctx, abstract, jnp.bfloat16, sample,
                                  control=prec)
        worst[prec].append(float(gaps.max()))
print("WORST", worst)
assert min(worst["fp8"]) >= 3 * max(worst["bfloat16"]), worst
''' % (ROOT, ROOT)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, (p.stdout[-500:], p.stderr[-2000:])


@pytest.mark.parametrize("cell,fault,number", [
    ("gpt345m.pretrain", "frozen_step", "dparam_norm_gap"),
    ("gpt345m.serve-chat", "wrong_token", "served_logit_gap"),
])
def test_a_broken_timed_path_is_not_correct(cell, fault, number):
    cmd = [sys.executable, BROKEN, fault, "--workload", cell, "--seed",
           "9", "--seconds", "2", "--trace", "0", "--rehearse"]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    assert lines[-1]["correct"] is False
    bad = [x["compared"] for x in lines
           if isinstance(x.get("compared"), str) and not x["ok"]]
    assert number in bad, bad
