"""chip_smoke.py on the CPU: the rehearsal reaches the final line, a
failed phase never ends in exit 0, and nothing but --rehearse accepts
a device that is not a TPU. The script runs as a child process, as the
driver runs it (JAX on the CPU platform only — the children never
touch the TPU's library)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, code=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"))
    env.pop("PFX_CPU_DEVICES", None)
    env.pop("XLA_FLAGS", None)       # the parent's 8 virtual devices
    cmd = [sys.executable, "-c", code] if code else \
        [sys.executable, SMOKE]
    return subprocess.run(
        cmd + args + ["--workdir", str(tmp_path / "work")],
        capture_output=True, text=True, timeout=600, cwd=REPO, env=env)


def _json_lines(stdout):
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("smoke")
    return _run(["--rehearse"], tmp), tmp


def test_rehearse_reaches_the_final_line(rehearsal):
    proc, _ = rehearsal
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": last["device"]["kind"], "count": 1}}
    assert sorted(last["device"]) == ["count", "kind", "platform"]


def test_rehearse_runs_every_phase_with_its_checks(rehearsal):
    proc, tmp = rehearsal
    lines = _json_lines(proc.stdout)
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert list(by_phase) == [
        "setup", "train", "train_flash", "serve_reference", "serve",
        "serve_spec", "serve_loop", "compile_cache"]
    train = by_phase["train"]
    assert train["losses"][-1] < train["losses"][0]
    assert train["resumed_step"] == train["steps"] + 1
    flash = by_phase["train_flash"]["counters"]
    assert flash["attention/flash"] > 0
    assert "attention/dense" not in flash
    assert by_phase["serve"]["counters"][
        "attention/flash_decode_paged"] > 0
    assert by_phase["serve_spec"]["counters"][
        "attention/flash_decode_paged_verify"] > 0
    loop = by_phase["serve_loop"]
    assert loop["host_roundtrips"] < loop["decode_ticks"]
    for name in ("serve", "serve_spec", "serve_loop"):
        # float32 on the CPU: no tie is tolerated, every row is exact
        assert by_phase[name]["exact_rows"] == \
            by_phase[name]["requests"]
    # the cache went where JAX_COMPILATION_CACHE_DIR placed it
    cache = by_phase["compile_cache"]
    assert cache["directory"] == str(tmp / "xla_cache")
    assert cache["entries_after"] > 0


def test_a_raised_phase_is_a_nonzero_exit_without_final_line(tmp_path):
    code = (
        "import sys; sys.argv = ['chip_smoke.py'] + sys.argv[1:]\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import chip_smoke\n"
        "def boom(*a, **k):\n"
        "    raise chip_smoke.SmokeFailure('injected')\n"
        "chip_smoke.phase_train_flash = boom\n"
        "sys.exit(chip_smoke.main())\n")
    proc = _run(["--rehearse"], tmp_path, code=code)
    assert proc.returncode not in (0, None)
    assert "injected" in proc.stderr
    lines = _json_lines(proc.stdout)
    assert lines and lines[-1].get("phase") == "train"   # got that far
    assert not any("ok" in ln for ln in lines)


def test_without_rehearse_a_cpu_host_is_refused_at_once(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode not in (0, None)
    assert proc.stdout == ""
    assert "not 'tpu'" in proc.stderr
    assert not (tmp_path / "work").exists()      # no work was done


def test_multichip_rehearsal_runs_only_the_mesh_phase(tmp_path):
    proc = _run(["--rehearse", "--multichip"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = _json_lines(proc.stdout)
    assert [ln.get("phase") for ln in lines] == [
        "setup", "multichip", "multichip_pp", "compile_cache", None]
    for mesh in lines[1:3]:
        assert mesh["max_abs_diff"] <= mesh["tolerance"]
        assert len(mesh["state_bytes_per_device"]) == 4
        assert mesh["counters"]["attention/flash"] > 0
    # fsdp2: the batch-1 init trace is the only dense one (2 layers);
    # pp2 x mp2 shards no batch, so nothing goes dense at all
    assert lines[1]["counters"]["attention/dense"] == 2
    assert "attention/dense" not in lines[2]["counters"]
    assert lines[-1]["device"]["count"] == 4


def test_the_sizes_are_not_options(tmp_path):
    """The start-up proof cannot be shrunk from the command line."""
    proc = _run(["--rehearse", "--train-steps", "1"], tmp_path)
    assert proc.returncode == 2
    assert "unrecognized arguments" in proc.stderr
    assert proc.stdout == ""


# -- the serving judge, on a table of logits ---------------------------

class _TableModel:
    """``apply`` returns the parameters as the logits of one row: the
    judge's teacher-forced forward, with every logit chosen here."""

    class config:
        max_position_embeddings = 16

    @staticmethod
    def apply(variables, ids, deterministic):
        return variables["params"][None]


ULP = 2.0 ** -6              # one bf16 ulp of a logit in [2, 4)
PROMPT = [1, 2, 3]
WANT = [4, 5, 6, 7]          # generate()'s row


def _judge(served_rows, under_top, exact=False):
    """Judge ``served_rows`` against WANT rows. The table makes WANT
    the argmax (logit 3.0) at every position, except that from row
    0's first divergent token on, row 0's served tokens sit
    ``under_top[j]`` ulps under a 3.0 top."""
    import numpy as np
    sys.path.insert(0, REPO)
    import chip_smoke
    table = np.zeros((16, 8), np.float32)
    for j, tok in enumerate(WANT):
        table[len(PROMPT) - 1 + j, tok] = 3.0
    for j, ulps in under_top.items():
        table[len(PROMPT) - 1 + j, served_rows[0][j]] = 3.0 - ulps * ULP
    judge = chip_smoke.TieJudge(_TableModel, table, pad=0, exact=exact)
    return chip_smoke, lambda: judge.compare(
        [PROMPT] * len(served_rows), served_rows,
        [WANT] * len(served_rows))


def test_judge_admits_one_tie_and_follows_the_served_row():
    # token 1 leaves WANT at 1 ulp; the rest of the served row is the
    # teacher-forced argmax (token 2) or within the bound (token 3)
    _, compare = _judge([[4, 2, 1, 3], WANT], {1: 1, 2: 0, 3: 2})
    n_exact, ties = compare()
    assert n_exact == 1
    assert ties == [{"request": 0, "token": 1,
                     "ulps_under_top": {"served": 1.0, "lockstep": 0.0},
                     "rest_tokens": 2, "rest_off_argmax": 1}]


@pytest.mark.parametrize("served,under_top,exact,why", [
    ([[4, 2, 1, 3], WANT], {1: 3, 2: 0, 3: 0}, False, "no tie"),
    ([[4, 2, 1, 3], WANT], {1: 1, 2: 0, 3: 3}, False,
     "after the tie at token 1"),
    ([[4, 2, 6, 7], [4, 2, 6, 7]], {1: 1}, False, "2 rows left"),
    ([[4, 2, 6, 7], WANT], {1: 0}, True, "served"),
    ([WANT[:3], WANT], {}, False, "lengths differ"),
], ids=["wrong-token", "wrong-after-tie", "two-tied-rows",
        "exact-mode", "short-row"])
def test_judge_refuses(served, under_top, exact, why):
    chip_smoke, compare = _judge(served, under_top, exact)
    with pytest.raises(chip_smoke.SmokeFailure, match=why):
        compare()
