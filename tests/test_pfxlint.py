"""pfxlint: call-graph reachability, rule fixtures, suppression and
baseline round-trips, and the tier-1 gate over the real tree.

Every fixture runs through ``LintContext.from_sources`` (in-memory,
no tmp files) and targets one rule family via ``run_rules(select=)``
so docstring findings never leak into hazard assertions. The final
tests run the real engine over the real repository — the acceptance
criterion that ``python -m codestyle.pfxlint`` exits 0 — and pin the
docs/counter/knob contract by deleting one row and watching the gate
trip.
"""

import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from codestyle.pfxlint import engine  # noqa: E402
from codestyle.pfxlint.engine import (Finding, LintContext,  # noqa: E402
                                      run_lint, run_rules)

MOD = '"""Fixture module."""\n'


def _ctx(sources, docs=None):
    return LintContext.from_sources(sources, docs)


def _codes(sources, select, docs=None):
    findings = run_rules(_ctx(sources, docs), select=set(select))
    return [f.code for f in findings]


# -- call graph --------------------------------------------------------

def test_decorated_jit_function_is_direct_root():
    src = MOD + (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item()\n")
    ctx = _ctx({"paddlefleetx_tpu/a.py": src})
    fn = ctx.callgraph.functions["paddlefleetx_tpu.a:f"]
    assert fn.direct_traced and fn.jit_reachable
    assert "x" in fn.tracer_params


def test_wrapped_assignment_marks_root():
    src = MOD + (
        "import jax\n"
        "def f(x):\n"
        "    return x\n"
        "g = jax.jit(f)\n")
    ctx = _ctx({"paddlefleetx_tpu/a.py": src})
    assert ctx.callgraph.functions["paddlefleetx_tpu.a:f"].direct_traced


def test_static_argnames_are_not_tracers():
    src = MOD + (
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnames=('mode',))\n"
        "def f(x, mode):\n"
        "    return x\n")
    ctx = _ctx({"paddlefleetx_tpu/a.py": src})
    fn = ctx.callgraph.functions["paddlefleetx_tpu.a:f"]
    assert "mode" not in fn.tracer_params
    assert "x" in fn.tracer_params


def test_transitive_reachability_via_call_and_import_alias():
    kernel = MOD + (
        "def helper(x, y):\n"
        "    return x + y\n")
    entry = MOD + (
        "import jax\n"
        "from paddlefleetx_tpu.b import helper\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return helper(x, 1)\n")
    ctx = _ctx({"paddlefleetx_tpu/a.py": entry,
                "paddlefleetx_tpu/b.py": kernel})
    h = ctx.callgraph.functions["paddlefleetx_tpu.b:helper"]
    assert h.jit_reachable and not h.direct_traced
    # transitively reachable + unannotated params -> NOT assumed tracers
    assert h.tracer_params == set()


def test_transitive_array_annotation_is_tracer():
    helper = MOD + (
        "import jax\n"
        "def helper(x: jax.Array, n: int):\n"
        "    return x\n")
    entry = MOD + (
        "import jax\n"
        "from paddlefleetx_tpu.b import helper\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return helper(x, 1)\n")
    ctx = _ctx({"paddlefleetx_tpu/a.py": entry,
                "paddlefleetx_tpu/b.py": helper})
    h = ctx.callgraph.functions["paddlefleetx_tpu.b:helper"]
    assert h.tracer_params == {"x"}


def test_flax_compact_method_is_root():
    src = MOD + (
        "import flax.linen as nn\n"
        "class Block(nn.Module):\n"
        '    """Doc."""\n'
        "    @nn.compact\n"
        "    def __call__(self, x):\n"
        "        return x\n")
    ctx = _ctx({"paddlefleetx_tpu/a.py": src})
    fn = ctx.callgraph.functions["paddlefleetx_tpu.a:Block.__call__"]
    assert fn.jit_reachable


# -- hazard rules ------------------------------------------------------

def test_pfx101_item_in_traced_function():
    src = MOD + (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item()\n")
    assert _codes({"paddlefleetx_tpu/a.py": src},
                  ["PFX101"]) == ["PFX101"]


def test_pfx101_clean_outside_traced_context():
    src = MOD + (
        "def f(x):\n"
        "    return x.item()\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, ["PFX101"]) == []


def test_pfx101_shape_access_is_exempt():
    src = MOD + (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x.shape[0])\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, ["PFX101"]) == []


def test_pfx102_wall_clock_in_traced_function():
    src = MOD + (
        "import jax\n"
        "import time\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    t = time.time()\n"
        "    return x + t\n")
    assert _codes({"paddlefleetx_tpu/a.py": src},
                  ["PFX102"]) == ["PFX102"]


def test_pfx102_jax_random_is_clean():
    src = MOD + (
        "import jax\n"
        "from jax import random\n"
        "@jax.jit\n"
        "def f(key, x):\n"
        "    return x + random.normal(key, x.shape)\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, ["PFX102"]) == []


def test_pfx103_branch_on_tracer():
    src = MOD + (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n")
    assert _codes({"paddlefleetx_tpu/a.py": src},
                  ["PFX103"]) == ["PFX103"]


def test_pfx103_branch_on_static_is_clean():
    src = MOD + (
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnames=('n',))\n"
        "def f(x, n):\n"
        "    if n > 0:\n"
        "        return x\n"
        "    return -x\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, ["PFX103"]) == []


# -- contract rules ----------------------------------------------------

_COUNTER_SRC = MOD + (
    "from paddlefleetx_tpu.observability import metrics\n"
    "def f(flag):\n"
    "    metrics.inc('testns/a' if flag else 'testns/b')\n"
    "    metrics.inc('testns/undocumented')\n")


def test_pfx201_undocumented_counter_fires():
    docs = {"docs/observability.md": "- `testns/{a,b}` — the pair\n"}
    findings = run_rules(
        _ctx({"paddlefleetx_tpu/m.py": _COUNTER_SRC}, docs),
        select={"PFX201"})
    assert [f.key for f in findings] == ["testns/undocumented"]


def test_pfx202_stale_docs_row_fires():
    docs = {"docs/observability.md":
            "- `testns/{a,b,gone}` and `testns/undocumented` — rows\n"}
    findings = run_rules(
        _ctx({"paddlefleetx_tpu/m.py": _COUNTER_SRC}, docs),
        select={"PFX202"})
    assert [f.key for f in findings] == ["testns/gone"]


def test_counter_glob_counts_for_neither_direction():
    # a surviving glob row must NOT satisfy the deleted concrete row
    docs = {"docs/observability.md":
            "- `testns/*` series plus `testns/undocumented`\n"}
    findings = run_rules(
        _ctx({"paddlefleetx_tpu/m.py": _COUNTER_SRC}, docs),
        select={"PFX201", "PFX202"})
    assert sorted(f.key for f in findings) == ["testns/a", "testns/b"]


def test_timer_synthesizes_docs_optional_calls_row():
    src = MOD + (
        "from paddlefleetx_tpu.observability import metrics\n"
        "def f():\n"
        "    with metrics.get_registry().timer('testns/t'):\n"
        "        pass\n")
    docs = {"docs/observability.md":
            "- `testns/t` timer + `testns/t/calls`\n"}
    findings = run_rules(_ctx({"paddlefleetx_tpu/m.py": src}, docs),
                         select={"PFX201", "PFX202"})
    assert findings == []


def test_pfx203_undocumented_knob_and_glob_does_not_satisfy():
    src = MOD + (
        "import os\n"
        "V = os.environ.get('PFX_TESTONLY_KNOB', '0')\n")
    docs = {"docs/observability.md": "see the `PFX_TESTONLY_*` knobs\n"}
    findings = run_rules(_ctx({"paddlefleetx_tpu/m.py": src}, docs),
                         select={"PFX203"})
    assert [f.key for f in findings] == ["PFX_TESTONLY_KNOB"]


def test_pfx204_stale_documented_knob():
    src = MOD + "X = 1\n"
    docs = {"docs/observability.md": "set `PFX_TESTONLY_GONE` to 1\n"}
    findings = run_rules(_ctx({"paddlefleetx_tpu/m.py": src}, docs),
                         select={"PFX204"})
    assert [f.key for f in findings] == ["PFX_TESTONLY_GONE"]


_KERNEL_SRC = MOD + (
    "from jax.experimental import pallas as pl\n"
    "def kern(ref):\n"
    "    pass\n"
    "def launch(x):\n"
    "    return pl.pallas_call(kern)(x)\n"
    "def probe(s):\n"
    "    if s % 8:\n"
    "        raise NotImplementedError('bad shape')\n"
    "    return s\n")


def test_pfx205_unguarded_kernel_launch_fires_twice():
    caller = MOD + (
        "from paddlefleetx_tpu.ops.pallas.kern import launch\n"
        "def f(x):\n"
        "    return launch(x)\n")
    findings = run_rules(
        _ctx({"paddlefleetx_tpu/ops/pallas/kern.py": _KERNEL_SRC,
              "paddlefleetx_tpu/models/m.py": caller}),
        select={"PFX205"})
    assert sorted(f.key.rsplit(":", 1)[1] for f in findings) == \
        ["counter", "try"]


def test_pfx205_guarded_and_counted_is_clean():
    caller = MOD + (
        "from paddlefleetx_tpu.observability import metrics\n"
        "from paddlefleetx_tpu.ops.pallas.kern import launch\n"
        "def f(x):\n"
        "    try:\n"
        "        out = launch(x)\n"
        "        metrics.inc('attention/flash')\n"
        "        return out\n"
        "    except (ImportError, NotImplementedError):\n"
        "        metrics.inc('attention/dense')\n"
        "        return x\n")
    findings = run_rules(
        _ctx({"paddlefleetx_tpu/ops/pallas/kern.py": _KERNEL_SRC,
              "paddlefleetx_tpu/models/m.py": caller}),
        select={"PFX205"})
    assert findings == []


def test_pfx205_admission_probe_is_exempt():
    caller = MOD + (
        "from paddlefleetx_tpu.ops.pallas.kern import probe\n"
        "def ok(s):\n"
        "    try:\n"
        "        probe(s)\n"
        "        return True\n"
        "    except NotImplementedError:\n"
        "        return False\n"
        "def bare(s):\n"
        "    return probe(s)\n")
    findings = run_rules(
        _ctx({"paddlefleetx_tpu/ops/pallas/kern.py": _KERNEL_SRC,
              "paddlefleetx_tpu/models/m.py": caller}),
        select={"PFX205"})
    assert findings == []   # probe never reaches pallas_call


def test_pfx206_silent_handlers_fire_in_core_only():
    src = MOD + (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except ValueError:\n"
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        x = 1\n")
    findings = run_rules(
        _ctx({"paddlefleetx_tpu/core/m.py": src,
              "paddlefleetx_tpu/models/m.py": src}),   # out of scope
        select={"PFX206"})
    assert [(f.path, f.key) for f in findings] == [
        ("paddlefleetx_tpu/core/m.py", "ValueError:0"),
        ("paddlefleetx_tpu/core/m.py", "bare:0"),
    ]


def test_pfx206_trace_reraise_and_sentinel_are_clean():
    src = MOD + (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except ValueError:\n"
        "        logger.warning('g failed')\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        raise RuntimeError('translated')\n"
        "    try:\n"
        "        return g()\n"
        "    except OSError:\n"
        "        return None\n")
    findings = run_rules(_ctx({"paddlefleetx_tpu/core/m.py": src}),
                         select={"PFX206"})
    assert findings == []


def test_docstring_rule_matches_standalone_checker():
    src = "def f():\n    pass\n"   # no module docstring
    codes = _codes({"paddlefleetx_tpu/a.py": src},
                   ["D001", "D002", "D003", "D004", "D005", "D006"])
    assert codes == ["D001"]
    sys.path.insert(0, os.path.join(REPO, "codestyle"))
    from docstring_checker import check_source
    assert [f.code for f in check_source(src)
            if f.code.startswith("D00") and f.code <= "D006"] == codes


# -- suppression and baseline ------------------------------------------

def test_inline_suppression_and_file_suppression():
    src = MOD + (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item()  # pfxlint: disable=PFX101\n")
    ctx = _ctx({"paddlefleetx_tpu/a.py": src})
    raw = run_rules(ctx, select={"PFX101"})
    kept, suppressed = engine.apply_suppressions(ctx, raw)
    assert kept == [] and [f.code for f in suppressed] == ["PFX101"]

    src2 = MOD.rstrip("\n") + "  # pfxlint: disable-file=PFX101\n" + (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.item()\n")
    ctx2 = _ctx({"paddlefleetx_tpu/a.py": src2})
    kept2, sup2 = engine.apply_suppressions(
        ctx2, run_rules(ctx2, select={"PFX101"}))
    assert kept2 == [] and len(sup2) == 1


def test_baseline_round_trip(tmp_path):
    f = Finding("paddlefleetx_tpu/a.py", 4, "PFX101",
                "host sync", key="a.py:f:item")
    path = str(tmp_path / "baseline.txt")
    engine.write_baseline(path, [f], header="why: legacy")
    entries = engine.load_baseline(path)
    assert entries == [f.fingerprint()]
    # fingerprints are line-independent
    f2 = Finding("paddlefleetx_tpu/a.py", 99, "PFX101",
                 "host sync", key="a.py:f:item")
    assert f2.fingerprint() in set(entries)


def test_run_lint_baseline_carries_and_reports_stale(tmp_path):
    root = tmp_path / "repo"
    (root / "paddlefleetx_tpu").mkdir(parents=True)
    (root / "paddlefleetx_tpu" / "a.py").write_text(
        MOD + "import jax\n@jax.jit\ndef f(x):\n    return x.item()\n")
    res = run_lint(str(root), select={"PFX101"}, use_baseline=False)
    assert [f.code for f in res.findings] == ["PFX101"]

    bl = root / "baseline.txt"
    engine.write_baseline(str(bl), res.findings)
    res2 = run_lint(str(root), select={"PFX101"},
                    baseline_path=str(bl))
    assert res2.findings == [] and len(res2.baselined) == 1
    assert res2.exit_code == 0

    # stale entries are reported once the finding is fixed
    (root / "paddlefleetx_tpu" / "a.py").write_text(
        MOD + "import jax\n@jax.jit\ndef f(x):\n    return x\n")
    res3 = run_lint(str(root), select={"PFX101"},
                    baseline_path=str(bl))
    assert res3.findings == [] and len(res3.unused_baseline) == 1


# -- the real tree (tier-1 acceptance) ---------------------------------

def test_real_tree_is_clean():
    res = run_lint(REPO)
    msgs = "\n".join(str(f) for f in res.findings)
    assert res.findings == [], f"unbaselined pfxlint findings:\n{msgs}"


def test_real_tree_counter_contract_trips_on_deleted_row():
    # deleting any one concrete docs row must fail the gate (PFX201)
    obs = open(os.path.join(REPO, "docs", "observability.md"),
               encoding="utf-8").read()
    assert "`attention/ring/{flash,dense}`" in obs
    pruned = obs.replace("`attention/ring/{flash,dense}`", "`x`")
    ring = open(os.path.join(
        REPO, "paddlefleetx_tpu", "ops", "ring_attention.py"),
        encoding="utf-8").read()
    findings = run_rules(
        _ctx({"paddlefleetx_tpu/ops/ring_attention.py": ring},
             {"docs/observability.md": pruned}),
        select={"PFX201"})
    assert {f.key for f in findings} >= {"attention/ring/flash",
                                         "attention/ring/dense"}


def test_real_tree_knob_contract_trips_on_deleted_line():
    obs = open(os.path.join(REPO, "docs", "observability.md"),
               encoding="utf-8").read()
    pruned = "\n".join(ln for ln in obs.splitlines()
                       if "PFX_VOCAB_DIR" not in ln)
    tok = open(os.path.join(
        REPO, "paddlefleetx_tpu", "data", "tokenizers",
        "gpt_tokenizer.py"), encoding="utf-8").read()
    findings = run_rules(
        _ctx({"paddlefleetx_tpu/data/tokenizers/gpt_tokenizer.py": tok},
             {"docs/observability.md": pruned}),
        select={"PFX203"})
    assert [f.key for f in findings] == ["PFX_VOCAB_DIR"]


def test_inference_counter_names_reconciled():
    """Pin the singular/plural pairing between code and docs."""
    code = open(os.path.join(
        REPO, "paddlefleetx_tpu", "core", "inference_engine.py"),
        encoding="utf-8").read()
    docs = open(os.path.join(REPO, "docs", "observability.md"),
                encoding="utf-8").read()
    for name in ("inference/loads", "inference/load",
                 "inference/predict_calls", "inference/predict",
                 "inference/output_tokens"):
        assert f'"{name}"' in code, name
        assert f"`{name}`" in docs, name
    # and the wrong spellings stay dead in code
    assert '"inference/predicts"' not in code
    assert '"inference/load_calls"' not in code


def test_cli_list_rules_and_clean_exit():
    from codestyle.pfxlint.__main__ import main
    assert main(["--list-rules"]) == 0
    assert main(["--root", REPO]) == 0
    assert main(["--root", REPO, "--select", "NOPE"]) == 2


# -- jit dataflow: PFX104 use-after-donation ---------------------------

DONATE_MOD = MOD + (
    "import jax\n"
    "def train_step(state, batch):\n"
    '    """Step."""\n'
    "    return state, 1.0\n"
    "class Engine:\n"
    '    """E."""\n'
    "    def __init__(self):\n"
    "        self._step = jax.jit(train_step, donate_argnums=(0,))\n")


def test_pfx104_read_after_donation_fires():
    src = DONATE_MOD + (
        "    def bad(self, state, batch):\n"
        '        """Loses the rebind."""\n'
        "        m = self._step(state, batch)\n"
        "        return state.params, m\n")
    assert _codes({"paddlefleetx_tpu/a.py": src},
                  {"PFX104"}) == ["PFX104"]


def test_pfx104_rebind_on_call_statement_is_clean():
    src = DONATE_MOD + (
        "    def good(self, state, batch):\n"
        '        """The rebind idiom."""\n'
        "        state, m = self._step(state, batch)\n"
        "        return state.params, m\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, {"PFX104"}) == []


def test_pfx104_partial_decorator_form():
    src = MOD + (
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, donate_argnums=(0,))\n"
        "def step(state, batch):\n"
        '    """Step."""\n'
        "    return state\n"
        "def drive(state, batch):\n"
        '    """Caller."""\n'
        "    out = step(state, batch)\n"
        "    return state, out\n")
    assert _codes({"paddlefleetx_tpu/a.py": src},
                  {"PFX104"}) == ["PFX104"]


@pytest.mark.parametrize("rebinds,want", [(False, ["PFX104"]),
                                          (True, [])])
def test_pfx104_follows_a_donor_imported_from_another_module(rebinds,
                                                             want):
    """``decode_step`` donates its cache by NAME in generation.py; the
    server calls it positionally from serving.py."""
    donor = MOD + (
        "import jax\n"
        "from functools import partial\n"
        '@partial(jax.jit, static_argnames=("model",),\n'
        '         donate_argnames=("cache",))\n'
        "def decode_step(model, params, cache, state):\n"
        '    """Tick."""\n'
        "    return cache, state\n")
    target = "self._cache" if rebinds else "_"
    caller = MOD + (
        "from .generation import decode_step\n"
        "class Server:\n"
        '    """S."""\n'
        "    def step(self):\n"
        '        """One tick."""\n'
        f"        {target}, st = decode_step(self.model, self.params,\n"
        "                                   self._cache, self._state)\n"
        "        return self._cache, st\n")
    assert _codes({"paddlefleetx_tpu/generation.py": donor,
                   "paddlefleetx_tpu/serving.py": caller},
                  {"PFX104"}) == want


# -- jit dataflow: PFX105 tracer escape --------------------------------

def test_pfx105_store_to_self_fires():
    src = MOD + (
        "import jax\n"
        "class Model:\n"
        '    """M."""\n'
        "    @jax.jit\n"
        "    def step(self, x):\n"
        '        """Traced."""\n'
        "        y = x * 2\n"
        "        self._cache = y\n"
        "        return y\n")
    assert _codes({"paddlefleetx_tpu/a.py": src},
                  {"PFX105"}) == ["PFX105"]


def test_pfx105_global_container_fires():
    src = MOD + (
        "import jax\n"
        "_CACHE = {}\n"
        "@jax.jit\n"
        "def step(x):\n"
        '    """Traced."""\n'
        "    global _CACHE\n"
        "    _CACHE['y'] = x + 1\n"
        "    return x\n")
    assert _codes({"paddlefleetx_tpu/a.py": src},
                  {"PFX105"}) == ["PFX105"]


def test_pfx105_shape_store_and_untraced_are_clean():
    src = MOD + (
        "import jax\n"
        "class Model:\n"
        '    """M."""\n'
        "    @jax.jit\n"
        "    def step(self, x):\n"
        '        """Shape is concrete at trace time."""\n'
        "        self._shape = x.shape\n"
        "        self._n = len(x)\n"
        "        return x\n"
        "    def eager(self, x):\n"
        '        """Not traced: storing is fine."""\n'
        "        self._last = x\n"
        "        return x\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, {"PFX105"}) == []


# -- thread-entry graph ------------------------------------------------

def test_thread_root_from_target_bound_method():
    src = MOD + (
        "import threading\n"
        "class Server:\n"
        '    """S."""\n'
        "    def start(self):\n"
        '        """Spawn."""\n'
        "        t = threading.Thread(target=self._run, daemon=True)\n"
        "        t.start()\n"
        "    def _run(self):\n"
        '        """Body."""\n'
    )
    tg = _ctx({"paddlefleetx_tpu/a.py": src}).threadgraph
    q = "paddlefleetx_tpu.a:Server._run"
    assert q in tg.thread_roots
    assert any(c.startswith("thread:") for c in tg.contexts_of(q))


def test_thread_root_from_lambda_target_and_timer():
    src = MOD + (
        "import threading\n"
        "def work():\n"
        '    """Body."""\n'
        "def tick():\n"
        '    """Timer body."""\n'
        "def main():\n"
        '    """Main."""\n'
        "    threading.Thread(target=lambda: work()).start()\n"
        "    threading.Timer(1.0, tick).start()\n")
    tg = _ctx({"paddlefleetx_tpu/a.py": src}).threadgraph
    assert "paddlefleetx_tpu.a:work" in tg.thread_roots
    assert "paddlefleetx_tpu.a:tick" in tg.thread_roots
    assert "main" in tg.contexts_of("paddlefleetx_tpu.a:main")


def test_http_handler_methods_are_roots_and_callbacks_flow():
    src = MOD + (
        "import threading\n"
        "from http.server import BaseHTTPRequestHandler, "
        "ThreadingHTTPServer\n"
        "class Srv:\n"
        '    """S."""\n'
        "    def __init__(self):\n"
        "        self._health = None\n"
        "        outer = self\n"
        "        class _H(BaseHTTPRequestHandler):\n"
        '            """H."""\n'
        "            def do_GET(self):\n"
        '                """Handle."""\n'
        "                outer._handle(self)\n"
        "        self._httpd = ThreadingHTTPServer(('', 0), _H)\n"
        "    def set_health(self, fn):\n"
        '        """Install."""\n'
        "        self._health = fn\n"
        "    def _handle(self, h):\n"
        '        """Dispatch."""\n'
        "        if self._health is not None:\n"
        "            return self._health()\n"
        "class App:\n"
        '    """A."""\n'
        "    def __init__(self):\n"
        "        self.ticks = 0\n"
        "        srv = Srv()\n"
        "        srv.set_health(self._health_state)\n"
        "    def _health_state(self):\n"
        '        """Callback."""\n'
        "        return {'ticks': self.ticks}\n"
        "    def step(self):\n"
        '        """Main loop."""\n'
        "        self.ticks += 1\n")
    ctx = _ctx({"paddlefleetx_tpu/a.py": src})
    tg = ctx.threadgraph
    # handler method is a root with an http context label
    assert any(q.endswith("._H.do_GET") for q in tg.thread_roots)
    # the callback registered through set_health inherits that context
    cb = tg.contexts_of("paddlefleetx_tpu.a:App._health_state")
    assert any(c.startswith("http:") for c in cb)
    # and the unlocked shared counter is a PFX301 race
    keys = {f.key for f in run_rules(ctx, select={"PFX301"})}
    assert "paddlefleetx_tpu.a:App.ticks" in keys


# -- lock scopes -------------------------------------------------------

RACE_MOD = MOD + (
    "import threading\n"
    "class Server:\n"
    '    """S."""\n'
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.count = 0\n"
    "        self.status = 'idle'\n"
    "        threading.Thread(target=self._run).start()\n")


def test_pfx301_with_block_guard_is_clean_unguarded_fires():
    src = RACE_MOD + (
        "    def _run(self):\n"
        '        """Thread body."""\n'
        "        with self._lock:\n"
        "            self.count += 1\n"
        "        self.status = 'ran'\n"
        "    def read(self):\n"
        '        """Main side."""\n'
        "        with self._lock:\n"
        "            c = self.count\n"
        "        return c, self.status\n")
    findings = run_rules(_ctx({"paddlefleetx_tpu/a.py": src}),
                         select={"PFX301"})
    assert [f.key for f in findings] == \
        ["paddlefleetx_tpu.a:Server.status"]


def test_pfx301_try_finally_acquire_release_scopes():
    src = MOD + (
        "import threading\n"
        "lk = threading.Lock()\n"
        "state = 0\n"
        "bad = 0\n"
        "def worker():\n"
        '    """Thread body."""\n'
        "    global state, bad\n"
        "    lk.acquire()\n"
        "    try:\n"
        "        state = 1\n"
        "    finally:\n"
        "        lk.release()\n"
        "    bad = 1\n"
        "def main():\n"
        '    """Main."""\n'
        "    global state, bad\n"
        "    threading.Thread(target=worker).start()\n"
        "    lk.acquire()\n"
        "    try:\n"
        "        state = 2\n"
        "    finally:\n"
        "        lk.release()\n"
        "    bad = 2\n")
    findings = run_rules(_ctx({"paddlefleetx_tpu/a.py": src}),
                         select={"PFX301"})
    assert [f.key for f in findings] == ["paddlefleetx_tpu.a:bad"]


def test_pfx301_nested_locks_share_common_guard():
    src = MOD + (
        "import threading\n"
        "a = threading.Lock()\n"
        "b = threading.Lock()\n"
        "x = 0\n"
        "def worker():\n"
        '    """Holds a then b."""\n'
        "    global x\n"
        "    with a:\n"
        "        with b:\n"
        "            x = 1\n"
        "def main():\n"
        '    """Holds only b — still a common lock."""\n'
        "    global x\n"
        "    threading.Thread(target=worker).start()\n"
        "    with b:\n"
        "        x = 2\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, {"PFX301"}) == []


def test_pfx301_init_writes_and_event_objects_exempt():
    src = MOD + (
        "import threading\n"
        "class Dog:\n"
        '    """Watchdog."""\n'
        "    def __init__(self):\n"
        "        self._stop = threading.Event()\n"
        "        self.name = 'dog'\n"
        "        threading.Thread(target=self._run).start()\n"
        "    def _run(self):\n"
        '        """Thread body: Event methods are internally '
        'locked."""\n'
        "        while not self._stop.wait(0.1):\n"
        "            pass\n"
        "    def stop(self):\n"
        '        """Main side."""\n'
        "        self._stop.set()\n"
        "        self._stop.clear()\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, {"PFX301"}) == []


def test_helper_inherits_caller_locks_meet_over_callers():
    src = RACE_MOD + (
        "    def _run(self):\n"
        '        """Thread body."""\n'
        "        with self._lock:\n"
        "            self._bump()\n"
        "    def _bump(self):\n"
        '        """Only ever called under the lock."""\n'
        "        self.count += 1\n"
        "    def read(self):\n"
        '        """Main side."""\n'
        "        with self._lock:\n"
        "            return self.count\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, {"PFX301"}) == []


# -- PFX302 / PFX303 ---------------------------------------------------

def test_pfx302_lock_order_inversion_fires():
    src = MOD + (
        "import threading\n"
        "a = threading.Lock()\n"
        "b = threading.Lock()\n"
        "def one():\n"
        '    """a -> b."""\n'
        "    with a:\n"
        "        with b:\n"
        "            pass\n"
        "def two():\n"
        '    """b -> a."""\n'
        "    with b:\n"
        "        with a:\n"
        "            pass\n")
    findings = run_rules(_ctx({"paddlefleetx_tpu/a.py": src}),
                         select={"PFX302"})
    assert len(findings) == 1 and findings[0].key.startswith("order:")


def test_pfx302_consistent_order_is_clean():
    src = MOD + (
        "import threading\n"
        "a = threading.Lock()\n"
        "b = threading.Lock()\n"
        "def one():\n"
        '    """a -> b."""\n'
        "    with a:\n"
        "        with b:\n"
        "            pass\n"
        "def two():\n"
        '    """Also a -> b."""\n'
        "    with a:\n"
        "        with b:\n"
        "            pass\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, {"PFX302"}) == []


def test_pfx303_blocking_call_under_lock_fires():
    src = MOD + (
        "import queue\n"
        "import threading\n"
        "_q = queue.Queue()\n"
        "_lock = threading.Lock()\n"
        "def drain():\n"
        '    """Blocks the lock on queue IO."""\n'
        "    with _lock:\n"
        "        return _q.get()\n")
    assert _codes({"paddlefleetx_tpu/a.py": src},
                  {"PFX303"}) == ["PFX303"]


def test_pfx303_condition_wait_is_exempt():
    src = MOD + (
        "import threading\n"
        "_cv = threading.Condition()\n"
        "def waiter():\n"
        '    """Condition.wait releases the lock — its whole '
        'job."""\n'
        "    with _cv:\n"
        "        _cv.wait()\n")
    assert _codes({"paddlefleetx_tpu/a.py": src}, {"PFX303"}) == []


# -- real-tree gates for the new substrate -----------------------------

THREAD_CODES = {"PFX104", "PFX105", "PFX301", "PFX302", "PFX303"}


def test_real_tree_clean_under_new_rules():
    res = run_lint(REPO, select=THREAD_CODES)
    msgs = "\n".join(str(f) for f in res.findings)
    assert res.findings == [], f"thread/dataflow findings:\n{msgs}"


def test_tests_and_scripts_clean_under_portable_rules():
    res = run_lint(REPO, paths=["tests", "scripts"],
                   select={"PFX101", "PFX102", "PFX103"}
                   | THREAD_CODES)
    msgs = "\n".join(str(f) for f in res.findings)
    assert res.findings == [], f"tests/scripts findings:\n{msgs}"


def test_serving_health_lock_mutation_trips_gate():
    """Deleting the lock guard around the health-snapshot write in
    core/serving.py must fail the suite — the PFX301 mutation pin."""
    srv = open(os.path.join(REPO, "paddlefleetx_tpu", "core",
                            "serving.py"), encoding="utf-8").read()
    obs = open(os.path.join(REPO, "paddlefleetx_tpu",
                            "observability", "server.py"),
               encoding="utf-8").read()
    sources = {"paddlefleetx_tpu/core/serving.py": srv,
               "paddlefleetx_tpu/observability/server.py": obs}
    assert run_rules(_ctx(sources), select={"PFX301"}) == []
    mutated = srv.replace("with self._health_lock:", "if True:")
    assert mutated != srv, "serving.py lost its _health_lock guard?"
    sources["paddlefleetx_tpu/core/serving.py"] = mutated
    keys = {f.key for f in run_rules(_ctx(sources),
                                     select={"PFX301"})}
    assert any("_health_snapshot" in k for k in keys), keys


def test_serving_spill_lock_mutation_trips_gate():
    """Same pin for the hierarchical KV cache (core/host_tier.py):
    the spill writer thread publishes staged host bytes into
    ``_host_data`` under the tier's ``_lock`` while the server's loop
    pops them on rehydrate — dropping the writer-side guard must
    re-race them (PFX301)."""
    def read(*parts):
        return open(os.path.join(REPO, "paddlefleetx_tpu", *parts),
                    encoding="utf-8").read()
    tier = read("core", "host_tier.py")
    sources = {"paddlefleetx_tpu/core/host_tier.py": tier,
               "paddlefleetx_tpu/core/serving.py":
                   read("core", "serving.py"),
               "paddlefleetx_tpu/observability/server.py":
                   read("observability", "server.py")}
    assert run_rules(_ctx(sources), select={"PFX301"}) == []
    guarded = ("            with self._lock:\n"
               "                for (hpid, gen), page in "
               "zip(entries, pages):\n")
    assert guarded in tier, "spill writer lost its _lock guard?"
    mutated = tier.replace(
        guarded,
        "            if True:\n"
        "                for (hpid, gen), page in "
        "zip(entries, pages):\n")
    sources["paddlefleetx_tpu/core/host_tier.py"] = mutated
    keys = {f.key for f in run_rules(_ctx(sources),
                                     select={"PFX301"})}
    assert any("_host_data" in k for k in keys), keys


def test_fleet_snapshot_lock_mutation_trips_gate():
    """Async-fleet pin: worker threads read replica slots through
    ``_snapshot``/``_replica`` under ``_health_lock`` while
    ``restart_replica`` swaps entries under the same lock on the
    router thread — dropping the guards must re-race ``replicas``
    (PFX301)."""
    flt = open(os.path.join(REPO, "paddlefleetx_tpu", "core",
                            "fleet.py"), encoding="utf-8").read()
    srv = open(os.path.join(REPO, "paddlefleetx_tpu", "core",
                            "serving.py"), encoding="utf-8").read()
    obs = open(os.path.join(REPO, "paddlefleetx_tpu",
                            "observability", "server.py"),
               encoding="utf-8").read()
    sources = {"paddlefleetx_tpu/core/fleet.py": flt,
               "paddlefleetx_tpu/core/serving.py": srv,
               "paddlefleetx_tpu/observability/server.py": obs}
    assert run_rules(_ctx(sources), select={"PFX301"}) == []
    mutated = flt.replace("with self._health_lock:", "if True:")
    assert mutated != flt, "fleet.py lost its _health_lock guards?"
    sources["paddlefleetx_tpu/core/fleet.py"] = mutated
    keys = {f.key for f in run_rules(_ctx(sources),
                                     select={"PFX301"})}
    assert any("replicas" in k for k in keys), keys


def test_metrics_registry_lock_mutation_trips_gate():
    """Same pin for the registry: dropping its lock re-races the
    watchdog/HTTP readers against the main loop."""
    met = open(os.path.join(REPO, "paddlefleetx_tpu",
                            "observability", "metrics.py"),
               encoding="utf-8").read()
    obs = open(os.path.join(REPO, "paddlefleetx_tpu",
                            "observability", "server.py"),
               encoding="utf-8").read()
    exp = open(os.path.join(REPO, "paddlefleetx_tpu",
                            "observability", "export.py"),
               encoding="utf-8").read()
    res = open(os.path.join(REPO, "paddlefleetx_tpu", "core",
                            "resilience.py"), encoding="utf-8").read()
    sources = {"paddlefleetx_tpu/observability/metrics.py": met,
               "paddlefleetx_tpu/observability/server.py": obs,
               "paddlefleetx_tpu/observability/export.py": exp,
               "paddlefleetx_tpu/core/resilience.py": res}
    mutated = met.replace("with self._lock:", "if True:")
    assert mutated != met
    sources["paddlefleetx_tpu/observability/metrics.py"] = mutated
    findings = run_rules(_ctx(sources), select={"PFX301"})
    assert any("MetricsRegistry" in f.message for f in findings)


# -- CLI: --format github and --stats suppression counts ---------------

def test_cli_github_format_emits_error_annotations(tmp_path, capsys):
    root = tmp_path
    (root / "codestyle").mkdir()
    (root / "bad.py").write_text(
        '"""Fixture."""\n'
        "import time\n"
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        '    """Traced."""\n'
        "    return x * time.time()\n")
    from codestyle.pfxlint.__main__ import main
    rc = main(["--root", str(root), "--no-baseline",
               "--select", "PFX102", "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "::error file=bad.py," in out
    assert "title=PFX102::" in out
    assert main(["--format", "nope"]) == 2


def test_real_tree_suppression_counts_pinned():
    """Exactly one documented inline PFX301 suppression: the
    `enabled` fast-path flag in observability/metrics.py (the server's
    adapter-insert params write needs none any more: its one unlocked
    reader, the model fingerprint, reads the params inside __init__);
    growth here means a new unjustified disable crept in."""
    res = run_lint(REPO)
    counts = res.suppression_counts()
    assert counts.get("PFX301") == 1, counts
    # and every suppressed thread finding lives where documented
    where = {f.path for f in res.suppressed if f.code == "PFX301"}
    assert where == {"paddlefleetx_tpu/observability/metrics.py"}


def test_cli_stats_prints_per_rule_suppressions(capsys):
    from codestyle.pfxlint.__main__ import main
    assert main(["--root", REPO, "--stats"]) == 0
    err = capsys.readouterr().err
    assert "pfxlint: suppressed[PFX301]=1" in err
