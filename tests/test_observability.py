"""Structured-telemetry tests: metrics registry, flight recorder,
FLOPs single-sourcing, HBM sampling, telemetry-enabled fit (events
survive SIGTERM), summary without a profiler window, and the
attention / mp-linear dispatch counters."""

import json
import logging
import os
import signal as _signal

import jax.numpy as jnp
import numpy as np
import pytest

from paddlefleetx_tpu.observability import flops as obs_flops
from paddlefleetx_tpu.observability import metrics as obs_metrics
from paddlefleetx_tpu.observability.memory import (
    device_memory_stats, format_bytes,
)
from paddlefleetx_tpu.observability.metrics import MetricsRegistry
from paddlefleetx_tpu.observability.recorder import (
    FlightRecorder, read_tail,
)
from paddlefleetx_tpu.utils.log import logger

from test_engine import _build


@pytest.fixture
def global_registry():
    """Enable the process-global registry for a test, restoring the
    disabled default (and zeroed counters) afterwards."""
    reg = obs_metrics.get_registry()
    prior = reg.enabled
    reg.reset()
    obs_metrics.set_enabled(True)
    yield reg
    obs_metrics.set_enabled(prior)
    reg.reset()


# -- registry ----------------------------------------------------------


def test_registry_counters_gauges_timers_series():
    r = MetricsRegistry()
    r.inc("a")
    r.inc("a", 2)
    assert r.counter("a") == 3
    assert r.counter("missing") == 0
    r.set_gauge("g", 7)
    assert r.gauge("g") == 7
    r.add_time("t", 0.5)
    with r.timer("t"):
        pass
    assert r.timed("t") >= 0.5
    assert r.counter("t/calls") == 1
    s = r.series("s")
    s.append(1.0)
    assert r.series("s") is s  # alias, not a copy
    snap = r.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["series"]["s"] == [1.0]
    snap["series"]["s"].append(2.0)  # snapshot is detached
    assert r.series("s") == [1.0]


def test_registry_disabled_is_inert_and_reset_keeps_aliases():
    r = MetricsRegistry(enabled=False)
    r.inc("a")
    r.set_gauge("g", 1)
    r.add_time("t", 1.0)
    assert r.counter("a") == 0 and r.gauge("g") is None
    assert r.timed("t") == 0.0

    r2 = MetricsRegistry()
    s = r2.series("s")
    s.append(1.0)
    r2.inc("a")
    r2.reset()
    assert r2.counter("a") == 0
    assert s == [] and r2.series("s") is s  # cleared IN PLACE


def test_global_inc_respects_enable(global_registry):
    obs_metrics.inc("x")
    assert global_registry.counter("x") == 1
    obs_metrics.set_enabled(False)
    obs_metrics.inc("x")
    assert global_registry.counter("x") == 1
    obs_metrics.set_enabled(True)


# -- flight recorder ---------------------------------------------------


def test_recorder_emits_durable_json_lines(tmp_path):
    path = str(tmp_path / "sub" / "events.jsonl")  # parent created
    rec = FlightRecorder(path)
    rec.emit("fit_start", step=0, epochs=1)
    rec.emit("step_window", step=5, loss=4.2)
    # tail() re-reads the file: a DIFFERENT reader sees flushed events
    # without the writer closing
    assert [e["event"] for e in read_tail(path)] == \
        ["fit_start", "step_window"]
    tail = rec.tail(1)
    assert tail[0]["event"] == "step_window"
    assert tail[0]["loss"] == 4.2
    assert isinstance(tail[0]["ts"], float)
    rec.close()
    rec.emit("after_close")  # must not raise
    assert len(read_tail(path, 10)) == 2


def test_read_tail_tolerates_missing_and_malformed(tmp_path):
    assert read_tail(str(tmp_path / "nope.jsonl")) == []
    assert read_tail(None) == []
    p = tmp_path / "bad.jsonl"
    p.write_text('not json\n{"event": "ok"}\n[1,2]\n')
    recs = read_tail(str(p))
    assert recs == [{"event": "ok"}]


def test_recorder_unwritable_path_is_silent(tmp_path):
    rec = FlightRecorder("/proc/definitely/not/writable/e.jsonl")
    rec.emit("x")  # no raise
    assert rec.tail() == []


# -- flops single source ----------------------------------------------


@pytest.mark.parametrize("L,h,V,s,gflops", [
    (24, 1024, 50304, 1024, 2.4230), (24, 2048, 50304, 1024, 8.4699)],
    ids=["gpt-345m", "gpt-1.3b"])
def test_model_flops_against_a_hand_count(L, h, V, s, gflops):
    """The Engine's MFU line at the shapes of the two GPT cells,
    counted by hand: a layer's matmuls are 24 h^2 FLOPs a token forward
    (QKV 6, out 2, the two MLP products 16), its attention scores and
    values 4 s h, the head 2 h V; backward is twice forward. The
    GFLOPs a token are the figures PERF.md 2 quotes."""
    forward = L * (24 * h * h + 4 * s * h) + 2 * h * V
    assert obs_flops.model_flops_per_token(L, h, V, s) == \
        pytest.approx(3 * forward, rel=1e-12)
    assert 3 * forward == pytest.approx(gflops * 1e9, rel=1e-4)


def test_disabled_registry_overhead_under_one_percent_of_step():
    """The only telemetry on the engine's hot path is one disabled
    global-counter increment per dispatch; pin its cost far below 1%
    of a host step (the fastest observed steady-state CPU-mesh step
    in this suite is ~10 ms; TPU steps are slower)."""
    import timeit
    from paddlefleetx_tpu.observability import metrics
    assert not metrics.get_registry().enabled
    n = 10_000
    # best-of-5 to dodge scheduler jitter on shared CI hosts
    per_call = min(
        timeit.timeit(lambda: metrics.inc("hot"), number=n)
        for _ in range(5)) / n
    step_budget_s = 0.010
    assert per_call < 0.01 * step_budget_s, per_call
    assert metrics.get_registry().counter("hot") == 0


def test_flops_formula_values():
    # 72*L*h^2*(1 + s/6h + V/12Lh), hand-checked at L=1,h=6,V=72,s=36
    assert obs_flops.model_flops_per_token(1, 6, 72, 36) == \
        72 * 36 * (1 + 1 + 1)
    assert obs_flops.causal_attn_flops(2, 3, 8, 4) == \
        4.0 * 2 * 3 * 8 * 8 * 4 * 0.5


def test_mfu_and_peak_on_cpu():
    assert obs_flops.peak_flops() is None  # CPU test platform
    assert obs_flops.mfu(1000.0, 1e9, None) is None
    assert obs_flops.mfu(1000.0, 1e9, 197e12, 1) == \
        pytest.approx(1000.0 * 1e9 / 197e12)
    assert obs_flops.mfu(0.0, 1e9, 197e12) is None


# -- device memory -----------------------------------------------------


def test_device_memory_stats_none_on_cpu():
    # the CPU backend keeps no allocator stats; the sampler must say
    # so with None, not raise or fabricate zeros
    assert device_memory_stats() is None


def test_format_bytes():
    assert format_bytes(3.5 * 2**30) == "3.50G"
    assert format_bytes(None) == "?"
    assert format_bytes("x") == "?"


# -- telemetry-enabled fit --------------------------------------------


def _telemetry_build(tmp_path, **overrides):
    cfg, engine, loader = _build(
        tmp_path, **{"Telemetry": {"enable": True}, **overrides})
    return cfg, engine, loader


def test_telemetry_fit_writes_events(tmp_path, global_registry):
    cfg, engine, loader = _telemetry_build(tmp_path)
    engine.fit(epoch=1, train_data_loader=loader)
    path = str(tmp_path / "out" / "events.jsonl")
    assert engine._recorder is not None and engine._recorder.path == path
    with open(path) as f:
        events = [json.loads(line) for line in f]  # every line parses
    names = [e["event"] for e in events]
    assert names[0] == "fit_start"
    assert names[-1] == "fit_end"
    assert names.count("step_window") == 2  # 10 steps, logging_freq 5
    assert "compile" in names

    start = events[0]
    assert start["global_batch_size"] == cfg.Global.global_batch_size
    mesh = start["mesh"]
    assert mesh["dp"] == 2 and mesh["mp"] == 2
    assert int(np.prod(list(mesh.values()))) == 8

    win = next(e for e in events if e["event"] == "step_window")
    for key in ("step", "loss", "lr", "grad_norm", "step_time",
                "h2d_wait"):
        assert key in win, key
    assert win["hbm"] is None  # CPU backend keeps no stats

    end = events[-1]
    assert end["n_windows"] == 2
    assert end["tokens_per_sec"] > 0
    assert end["model_flops_per_token"] > 0
    assert end["mfu"] is None  # no calibrated CPU peak
    assert 0 <= end["goodput_pct"] <= 100
    assert end["bucket_compile_s"] > 0
    # the engine-init mp-linear config counter rode into the stats
    assert end["dispatch_counters"]["mp_linear/config/gspmd"] >= 1


def test_telemetry_fit_survives_sigterm(tmp_path, global_registry):
    """Preemption mid-epoch: the recorder's final records are durable
    (every emit fsyncs) and the sigterm lifecycle event lands before
    the grace-window checkpoint."""
    cfg, engine, loader = _telemetry_build(
        tmp_path, **{"Engine.max_steps": 50})

    def kicking(loader, after):
        for i, b in enumerate(loader):
            yield b
            if i == after - 1:
                os.kill(os.getpid(), _signal.SIGTERM)

    prev = _signal.getsignal(_signal.SIGTERM)
    engine.fit(epoch=1, train_data_loader=kicking(
        loader, 2 + engine.prefetch_depth))
    assert _signal.getsignal(_signal.SIGTERM) is prev

    path = str(tmp_path / "out" / "events.jsonl")
    with open(path) as f:
        lines = f.readlines()
    events = [json.loads(line) for line in lines]  # incl. the LAST one
    names = [e["event"] for e in events]
    assert "sigterm" in names
    assert "preemption" in names
    # ordering: the handler's durable event precedes the checkpoint's
    sig = names.index("sigterm")
    assert "save" in names[sig:]
    assert events[names.index("preemption")]["step"] == \
        int(engine.state["step"])


def test_print_summary_without_profiler_window(tmp_path, capsys):
    """Satellite: `Engine.print_summary: True` prints the host-time
    summary with MFU / goodput / HBM lines on a run with NO profiler
    window and NO telemetry."""
    cfg, engine, loader = _build(
        tmp_path, **{"Engine.print_summary": True})
    assert engine._prof_window is None

    lines = []
    h = logging.Handler()
    h.emit = lambda rec: lines.append(rec.getMessage())
    logger.addHandler(h)
    try:
        engine.fit(epoch=1, train_data_loader=loader)
    finally:
        logger.removeHandler(h)
    text = "\n".join(lines)
    assert "Profiler summary" in text
    assert "steady state" in text
    assert "tokens/s" in text
    assert "MFU n/a" in text  # language module, CPU → no peak
    assert "goodput:" in text
    assert "HBM watermark: unavailable" in text

    # and the default stays mute without profiler/telemetry/knob
    cfg2, engine2, loader2 = _build(tmp_path)
    assert engine2._summary_enabled() is False


def test_step_costs_recorded_without_profiler(tmp_path):
    """The summary samples no longer require a profiler window."""
    cfg, engine, loader = _build(tmp_path)
    engine.fit(epoch=1, train_data_loader=loader)
    assert len(engine._step_costs) == 2
    assert engine._metrics.series("host/step_cost") is engine._step_costs


# -- dispatch counters -------------------------------------------------


def _qkv(sq=4, skv=4, h=2, d=4, cache=False):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, sq, h, d)), jnp.float32)
    kv_shape = (1, h, d, skv) if cache else (1, skv, h, d)
    k = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(kv_shape), jnp.float32)
    return q, k, v


def test_attention_counter_flash_disabled(global_registry):
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    q, k, v = _qkv()
    dot_product_attention(q, k, v, use_flash=False)
    assert global_registry.counter(
        "attention/fallback/flash_disabled") == 1
    assert global_registry.counter("attention/dense") == 1


def test_attention_counter_short_noncausal(global_registry):
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    q, k, v = _qkv()
    dot_product_attention(q, k, v, causal=False, use_flash=True)
    assert global_registry.counter(
        "attention/fallback/short_noncausal") == 1
    assert global_registry.counter("attention/dense") == 1
    assert global_registry.counter("attention/flash") == 0


def test_attention_counter_kv_cache_layout(global_registry):
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    # multi-token query in cache layout: no decode kernel, no training
    # kernel (it does not take the cache layout) → dense + reason
    q, k, v = _qkv(sq=2, cache=True)
    dot_product_attention(q, k, v, use_flash=True,
                          kv_cache_layout=True)
    assert global_registry.counter(
        "attention/fallback/kv_cache_layout") == 1
    assert global_registry.counter("attention/dense") == 1


def test_attention_counter_dropout_gate_off(global_registry,
                                            monkeypatch):
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    monkeypatch.setenv("PFX_FLASH_DROPOUT", "0")
    import jax
    q, k, v = _qkv()
    dot_product_attention(q, k, v, use_flash=True, dropout_rate=0.1,
                          dropout_rng=jax.random.key(0),
                          deterministic=False)
    assert global_registry.counter(
        "attention/fallback/dropout_gate_off") == 1
    assert global_registry.counter("attention/dense") == 1


def test_attention_counter_flash_success(global_registry, monkeypatch):
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa
    calls = []

    def fake_flash(q, k, v, causal=True, query_offset=0, bias=None,
                   **kw):
        calls.append(kw)
        return jnp.zeros_like(q)

    monkeypatch.setattr(fa, "flash_attention", fake_flash)
    q, k, v = _qkv()
    dot_product_attention(q, k, v, use_flash=True)
    assert calls
    assert global_registry.counter("attention/flash") == 1
    assert global_registry.counter("attention/dense") == 0


def test_attention_counter_kernel_rejected(global_registry,
                                           monkeypatch):
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    from paddlefleetx_tpu.ops.pallas import flash_attention as fa

    def raising(*a, **kw):
        raise NotImplementedError("no TPU")

    monkeypatch.setattr(fa, "flash_attention", raising)
    q, k, v = _qkv()
    dot_product_attention(q, k, v, use_flash=True)
    assert global_registry.counter(
        "attention/fallback/kernel_rejected") == 1
    assert global_registry.counter("attention/dense") == 1


def test_counters_are_free_when_disabled():
    """With the global registry disabled (the default), dispatch
    counting must leave no trace."""
    from paddlefleetx_tpu.ops.attention import dot_product_attention
    reg = obs_metrics.get_registry()
    assert not reg.enabled
    before = dict(reg.snapshot()["counters"])
    q, k, v = _qkv()
    dot_product_attention(q, k, v, use_flash=False)
    assert reg.snapshot()["counters"] == before
