"""download/check/version utils + benchmark driver parsing."""

import json
import os
import threading
import time

import pytest


_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def jax_cache_config(monkeypatch):
    """Run with JAX_COMPILATION_CACHE_DIR unset and put jax's cache
    options back afterwards."""
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield jax.config
    for k, v in prev.items():
        jax.config.update(k, v)


def test_compilation_cache_knob(tmp_path, jax_cache_config):
    """Global.compilation_cache_dir points jax's persistent cache at
    shared storage (restart-after-preemption skips recompiles) while
    JAX_COMPILATION_CACHE_DIR is unset."""
    from paddlefleetx_tpu.utils.env import setup_compilation_cache
    target = str(tmp_path / "xla-cache")
    assert setup_compilation_cache(target) == target
    assert jax_cache_config.jax_compilation_cache_dir == target
    assert jax_cache_config.jax_persistent_cache_min_entry_size_bytes \
        == 0


def test_compilation_cache_placed_from_outside(tmp_path, monkeypatch,
                                               jax_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set our code sets no directory:
    whatever jax.config holds (JAX reads the variable itself) stays
    untouched, even against an explicit YAML key."""
    from paddlefleetx_tpu.utils.env import setup_compilation_cache
    jax_cache_config.update("jax_compilation_cache_dir", "/as/jax/read/it")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert setup_compilation_cache() == "/some/dir"
    assert setup_compilation_cache(str(tmp_path)) == "/some/dir"
    assert jax_cache_config.jax_compilation_cache_dir == \
        "/as/jax/read/it"
    # the thresholds are still ours: every program is cached
    assert jax_cache_config.jax_persistent_cache_min_compile_time_secs \
        == 0.0


def test_compilation_cache_default_is_a_fixed_checkout_path(
        tmp_path, monkeypatch, jax_cache_config):
    """Unset -> <checkout>/.xla_cache, resolved from the package's own
    location: identical across calls and from another cwd (the path
    is part of the cache key, so a directory that moves never hits)."""
    from paddlefleetx_tpu.utils.env import setup_compilation_cache
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    first = setup_compilation_cache()
    monkeypatch.chdir(tmp_path)
    assert setup_compilation_cache() == first == \
        os.path.join(checkout, ".xla_cache")
    assert jax_cache_config.jax_compilation_cache_dir == first


def test_engine_enables_the_cache_without_the_yaml_key(
        monkeypatch, jax_cache_config):
    """Engine.__init__ turns the cache on whether or not
    Global.compilation_cache_dir is set (it used to be a no-op
    without the key, so every chip-tool call compiled cold)."""
    from paddlefleetx_tpu.core import engine as engine_mod
    from paddlefleetx_tpu.utils import env
    from paddlefleetx_tpu.utils.config import AttrDict
    seen = []
    monkeypatch.setattr(env, "setup_compilation_cache",
                        lambda d=None: seen.append(d))

    class Stop(Exception):
        pass

    def stop(*a, **k):
        raise Stop
    monkeypatch.setattr(engine_mod.TopologyConfig, "from_config", stop)
    cfg = AttrDict({"Engine": AttrDict({}), "Global": AttrDict({})})
    with pytest.raises(Stop):
        engine_mod.Engine(cfg, module=None)
    assert seen == [None]


def test_cached_path(tmp_path, monkeypatch):
    from paddlefleetx_tpu.utils import download
    f = tmp_path / "x.bin"
    f.write_text("hi")
    assert download.cached_path(str(f)) == str(f)
    monkeypatch.setattr(download, "CACHE_HOME", str(tmp_path))
    sub = tmp_path / "weights"
    sub.mkdir()
    (sub / "w.bin").write_text("w")
    assert download.cached_path("http://host/w.bin", "weights") == \
        str(sub / "w.bin")
    assert download.cached_path("missing.bin") is None
    with pytest.raises(FileNotFoundError):
        download.get_weights_path_from_url("http://host/nope.bin")


def test_wait_for_file(tmp_path):
    from paddlefleetx_tpu.utils.download import wait_for_file
    path = tmp_path / "artifact"

    def produce():
        path.write_text("done")

    # producer writes
    assert wait_for_file(str(path), True, produce) == str(path)
    os.remove(path)

    # waiter sees the file once the producer thread lands it
    t = threading.Thread(
        target=lambda: (time.sleep(0.2), path.write_text("ok")))
    t.start()
    assert wait_for_file(str(path), False, timeout=10) == str(path)
    t.join()


def test_check_config():
    from paddlefleetx_tpu.utils.check import check_config
    check_config({"Global": {"local_batch_size": 8,
                             "micro_batch_size": 4},
                  "Distributed": {"dp_degree": 8, "world_size": 8}})
    with pytest.raises(ValueError):
        check_config({"Global": {"local_batch_size": 8,
                                 "micro_batch_size": 3},
                      "Distributed": {"world_size": 8}})
    with pytest.raises(ValueError):
        check_config({"Global": {},
                      "Distributed": {"dp_degree": 2,
                                      "world_size": 8}})


def test_version_line():
    from paddlefleetx_tpu.utils.version import show
    assert "paddlefleetx_tpu" in show()


def test_benchmark_driver_end_to_end(tmp_path):
    """The TIPC driver runs a tiny topology on the CPU mesh and parses
    ips/loss from the logs."""
    import subprocess
    import sys
    sys.path.insert(0, "tests")
    from test_data import make_corpus
    make_corpus(tmp_path, n_docs=60, doc_len_range=(20, 60), vocab=128,
                eos=127)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, os.path.join(repo, "benchmarks",
                                        "run_benchmark.py"),
           "--config", "configs/nlp/gpt/pretrain_gpt_base.yaml",
           "--max_steps", "6", "--cpu-devices", "8",
           "--model_item", "tipc_smoke",
           "--overrides",
           "Global.device=cpu", "Global.local_batch_size=4",
           "Global.micro_batch_size=4",
           "Model.vocab_size=128", "Model.hidden_size=32",
           "Model.num_layers=2", "Model.num_attention_heads=4",
           "Model.ffn_hidden_size=64",
           "Model.max_position_embeddings=64",
           "Model.hidden_dropout_prob=0.0",
           "Model.attention_probs_dropout_prob=0.0",
           "Distributed.dp_degree=4", "Distributed.mp_degree=2",
           "Engine.logging_freq=2", "Engine.eval_freq=1000",
           f"Engine.save_load.output_dir={tmp_path}/out",
           f"Data.Train.dataset.input_dir={tmp_path}",
           "Data.Train.dataset.split=[80,20,0]",
           "Data.Train.dataset.max_seq_len=32",
           "Data.Train.dataset.eos_id=127",
           f"Data.Eval.dataset.input_dir={tmp_path}",
           "Data.Eval.dataset.split=[80,20,0]",
           "Data.Eval.dataset.max_seq_len=32",
           "Data.Eval.dataset.eos_id=127"]
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS",)}
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=repo,
                          env=env, timeout=420)
    out = proc.stdout.strip().splitlines()[-1]
    result = json.loads(out)
    assert result["ok"], result
    assert result["ips"] > 0
    assert result["last_loss"] is not None


def test_download_file_url_with_md5(tmp_path, monkeypatch):
    """_download fetches file:// URLs, verifies md5, moves atomically
    (reference download.py:71-114)."""
    import hashlib
    from paddlefleetx_tpu.utils import download
    src = tmp_path / "src" / "w.bin"
    src.parent.mkdir()
    src.write_bytes(b"weights-payload")
    md5 = hashlib.md5(b"weights-payload").hexdigest()
    dest = tmp_path / "cache"
    got = download._download(src.as_uri(), str(dest), md5sum=md5)
    assert got == str(dest / "w.bin")
    assert (dest / "w.bin").read_bytes() == b"weights-payload"
    assert not (dest / "w.bin_tmp").exists()


def test_download_bad_cache_refetches(tmp_path, monkeypatch):
    """A cached file failing its md5 is re-fetched from source."""
    import hashlib
    from paddlefleetx_tpu.utils import download
    monkeypatch.setattr(download, "CACHE_HOME", str(tmp_path / "home"))
    src = tmp_path / "srv" / "w.bin"
    src.parent.mkdir()
    src.write_bytes(b"good")
    md5 = hashlib.md5(b"good").hexdigest()
    stale = tmp_path / "home" / "weights" / "w.bin"
    stale.parent.mkdir(parents=True)
    stale.write_bytes(b"corrupt")
    got = download.get_weights_path_from_url(src.as_uri(), md5sum=md5)
    assert open(got, "rb").read() == b"good"


def test_download_retries_then_raises(tmp_path):
    from paddlefleetx_tpu.utils import download
    missing = (tmp_path / "absent.bin").as_uri()
    with pytest.raises(RuntimeError, match="after 2 attempts"):
        download._download(missing, str(tmp_path / "out"), retries=2,
                           backoff=0.01)


def test_download_nonzero_rank_waits(tmp_path, monkeypatch):
    from paddlefleetx_tpu.utils import download
    monkeypatch.setenv("PFX_RANK", "1")
    src = tmp_path / "w.bin"
    target = tmp_path / "cache" / "w.bin"

    def land():
        time.sleep(0.2)
        target.parent.mkdir(exist_ok=True)
        target.write_bytes(b"x")

    t = threading.Thread(target=land)
    t.start()
    got = download.download(src.as_uri(), str(tmp_path / "cache"))
    t.join()
    assert got == str(target) and os.path.exists(got)


def test_download_corrupt_fetch_never_lands_in_cache(tmp_path):
    """md5 is checked on the temp file BEFORE the cache move."""
    import hashlib
    from paddlefleetx_tpu.utils import download
    src = tmp_path / "srv" / "w.bin"
    src.parent.mkdir()
    src.write_bytes(b"truncated")
    wrong = hashlib.md5(b"full-content").hexdigest()
    dest = tmp_path / "cache"
    with pytest.raises(RuntimeError, match="failed after"):
        download._download(src.as_uri(), str(dest), md5sum=wrong,
                           retries=2, backoff=0.01)
    assert not (dest / "w.bin").exists()         # nothing corrupt cached
    assert (dest / "w.bin.failed").exists()      # failure sentinel


def test_download_waiter_sees_rank0_failure(tmp_path, monkeypatch):
    """A sentinel written MID-WAIT (rank 0 just failed) fails the
    waiter fast; a pre-existing stale sentinel alone must not."""
    from paddlefleetx_tpu.utils import download
    monkeypatch.setenv("PFX_RANK", "1")

    def fail_rank0():
        time.sleep(1.5)
        (tmp_path / "w.bin.failed").write_text("url")

    t = threading.Thread(target=fail_rank0)
    t.start()
    t0 = time.time()
    with pytest.raises(RuntimeError, match="rank 0 failed"):
        download.download("file:///nope/w.bin", str(tmp_path))
    t.join()
    assert time.time() - t0 < 30            # fail-fast, not timeout


def test_download_waiter_ignores_stale_sentinel(tmp_path, monkeypatch):
    """A leftover sentinel from a previous run is ignored — the waiter
    keeps waiting and picks up the file rank 0 lands."""
    import os as _os
    from paddlefleetx_tpu.utils import download
    monkeypatch.setenv("PFX_RANK", "1")
    sentinel = tmp_path / "w.bin.failed"
    sentinel.write_text("old run")
    past = time.time() - 3600
    _os.utime(sentinel, (past, past))        # stale by an hour

    def rank0_lands_file():
        time.sleep(1.5)
        (tmp_path / "w.bin").write_bytes(b"fresh")

    t = threading.Thread(target=rank0_lands_file)
    t.start()
    got = download.download("file:///srv/w.bin", str(tmp_path))
    t.join()
    assert open(got, "rb").read() == b"fresh"


_LAUNCHER_PARENTS = {
    # tools/launch.py / pfx-launch: two local ranks of a stub child
    "launch": (
        "from paddlefleetx_tpu.tools import launch\n"
        "rc = launch.launch([sys.executable, '-c', 'print(1)'],\n"
        "                   nprocs=2)\n"
        "assert rc == 0, rc\n"),
    # benchmarks/run_benchmark.py: the train child stubbed out
    "run_benchmark": (
        "sys.path.insert(0, os.path.join(repo, 'benchmarks'))\n"
        "import subprocess\n"
        "import run_benchmark\n"
        "class Done:\n"
        "    returncode = 0\n"
        "    stdout = 'ips: 1200 tokens/s, loss: 9.5\\n'\n"
        "    stderr = ''\n"
        "calls = []\n"
        "subprocess.run = lambda cmd, **k: calls.append(cmd) or Done()\n"
        "run_benchmark.run(run_benchmark.get_args(\n"
        "    ['--config', 'configs/nlp/gpt/pretrain_gpt_345M_single_card"
        ".yaml']))\n"
        "assert len(calls) == 1 and 'train.py' in calls[0][1], calls\n"),
}


@pytest.mark.parametrize("launcher", sorted(_LAUNCHER_PARENTS))
def test_launcher_parent_stays_off_jax(launcher):
    """A chip belongs to one process: a parent that has touched JAX
    holds it and the child it starts then fails or hangs. Each
    launcher's parent path runs in a fresh interpreter with a stub
    child and must never import jax."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import os, sys\n"
        f"repo = {repo!r}\n"
        "sys.path.insert(0, repo)\n"
        + _LAUNCHER_PARENTS[launcher] +
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
        "print('parent-off-jax')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "parent-off-jax" in proc.stdout
