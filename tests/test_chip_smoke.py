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
        "setup", "multichip", "compile_cache", None]
    mesh = lines[1]
    assert mesh["max_abs_diff"] <= mesh["tolerance"]
    assert len(mesh["state_bytes_per_device"]) == 4
    assert mesh["counters"]["attention/flash"] > 0
    assert lines[-1]["device"]["count"] == 4
