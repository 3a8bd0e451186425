"""Every cell's driver end to end on the CPU, as the driver would run it
but with ``--rehearse`` (interpret mode, tiny sizes from
``rehearse.json``). Run by hand and in rehearsal: tier-1 collects
``tests/`` only.

    python -m pytest chipbench/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _run import RESULT_KEYS, ROOT, bench, run_cell  # noqa: E402

CELLS = [w["name"] for w in bench()["workloads"]]


def _metric_names(kind, cell):
    b = bench()
    out = []
    for m in b[kind]:
        cells = m.get("workloads")
        if cells is None and "moves" in m:
            cells = next(e for e in b["end_to_end"]
                         if e["name"] == m["moves"]).get("workloads")
        if cells is None or cell in cells:
            out.append(m["name"])
    return out


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contract_line(cell, trace):
    rc, lines, last, err = run_cell(cell, seed=2 ** 31 + 11, trace=trace)
    assert rc == 0, err[-2000:]
    line = json.loads(last)
    keys = set(line)
    assert keys == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert line["device"]["platform"] == "cpu"          # and says so
    assert "rehearsal" in line["device"]
    chips = next(w["chips"] for w in bench()["workloads"]
                 if w["name"] == cell)
    assert line["device"]["count"] == chips
    assert line["correct"] is True, [x for x in lines if "compared" in x]
    assert line["attempted"] > 0 and line["failed"] == 0
    kind = "per_layer" if trace else "end_to_end"
    allowed = set(_metric_names(kind, cell))
    assert set(line["metrics"]) <= allowed
    if not trace:
        # every end-to-end metric of the cell, set-up among them
        assert set(line["metrics"]) == allowed
        assert all(v["value"] > 0 for v in line["metrics"].values())
    else:
        assert line["metrics"], "a traced run reports per-layer metrics"
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] > line["device"]["busy_s"] * 0.5
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    compared = [x for x in lines if "compared" in x and "limit" in x]
    assert compared and all("value" in x for x in compared)


def test_a_platform_that_is_not_a_tpu_is_refused():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0
    assert not any(ln.startswith('{"correct"')
                   for ln in p.stdout.splitlines())
    assert "not 'tpu'" in p.stderr


def test_an_unknown_cell_is_refused():
    rc, lines, last, err = run_cell("no-such-cell")
    assert rc != 0 and "unknown workload" in err


def test_no_cell_configuration_mix_or_metric_is_named_in_run_py():
    src = open(os.path.join(ROOT, "chipbench", "run.py")).read()
    b = bench()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in b[k]]
    names += [w["traffic"] for w in b["workloads"]]
    named = [n for n in names if n != "setup_s" and n in src]
    assert not named, named


def test_reference_agrees_with_the_programs_model_in_float32():
    """Same seeded weights, float32 compute on both sides: logits, loss
    and gradient agree to float32 rounding, for the unrolled and the
    stacked parameter layout."""
    code = r'''
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %r)
import flax.linen as nn, jax, jax.numpy as jnp, numpy as np
from chipbench import weights
from chipbench.reference import gpt2_decoder as ref
from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
from paddlefleetx_tpu.models.gpt.model import cross_entropy_loss
for scan in (False, True):
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_attention_heads=2, ffn_hidden_size=512,
                    max_position_embeddings=256, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0, dtype="float32",
                    scan_layers=scan, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    abstract = nn.meta.unbox(jax.eval_shape(
        model.init, {"params": jax.random.key(0)},
        jnp.zeros((1, 128), jnp.int32))["params"])
    params = weights.seeded_params(abstract, 2 ** 31 + 5)
    params = jax.tree.map(lambda x: x + 0.01 * jax.random.normal(
        jax.random.key(1), x.shape), params)
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, 512, (3, 128)), jnp.int32)
    lab = jnp.asarray(rng.integers(0, 512, (3, 128)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (3, 128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        lg = model.apply({"params": params}, tok)
        l, g = jax.value_and_grad(lambda p: cross_entropy_loss(
            model.apply({"params": p}, tok), lab, mask))(params)
    assert float(jnp.max(jnp.abs(lg - ref.logits(params, tok)))) < 1e-5
    l2, g2 = ref.loss_and_grad(params, tok, lab, mask)
    assert abs(float(l) - float(l2)) < 1e-5, (float(l), float(l2))
    rel = jax.tree.map(lambda a, b: float(
        jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-12)), g, g2)
    assert max(jax.tree.leaves(rel)) < 1e-4, rel
print("agree")
''' % ROOT
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0 and "agree" in p.stdout, p.stderr[-2000:]


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "chipbench", "reference",
                            "gpt2_decoder.py")).read()
    assert "import paddlefleetx_tpu" not in src
    assert "from paddlefleetx_tpu" not in src
