"""Driver ``serve_open_loop_lm``: the open loop of ``serve_open_loop``
(its ``Loop``, its warm-up, its clocks: imported, not copied) for a
model family named by the configuration, with the generator, the
reference and the bytes file named by the mix, as ``train_lm`` did for
training:

  * ``config["model"]``: ``<module>:<config class>:<model class>`` under
    ``paddlefleetx_tpu.models``; a program that lacks the module (the
    parent of the PR that adds it) is refused at once, exit 2;
  * ``mix["generator"]``: a module under ``chipbench/`` with
    ``open_loop_blocks(mix, seed, vocab, window_s)``;
  * ``mix["reference"]``: a module under ``chipbench/reference/`` with
    ``logits(config, params, tokens, rows, precision)``;
  * ``mix["rehearse"]``: what ``--rehearse`` lays over configuration
    and mix (``tests/rehearse.json`` is the GPT cells').

``correct``: the GPT serving check's two numbers (``served_logit_gap``,
``off_argmax_share``) against the reference's full forward pass, on a
sample that holds the ``check_long_requests`` longest of the finished
long requests (positions past the window and the ring's reuse are then
inside the comparison) beside seeded others; exact: the paged decode
kernel ran with grouped heads and window layers and nothing fell back
(attention or expert products), no slot held more than its ring on a
window layer, every due request finished.
"""

import bisect
import gc
import importlib
import importlib.util
import os
import sys
import time

import numpy as np


def _sibling(name):
    """``drivers/<name>.py`` as a module, by path: run.py loads a driver
    before the checkout is on ``sys.path``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench.drivers." + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _sibling("serve_open_loop")
Loop, warm = base.Loop, base.warm


def _rehearse(ctx):
    tiny = ctx.mix["rehearse"]
    ctx.config.update(tiny["config"])
    ctx.extra_overrides = list(tiny["overrides"])
    for key, value in tiny["traffic"].items():
        if isinstance(value, dict) and isinstance(ctx.mix.get(key), dict):
            ctx.mix[key].update(value)
        else:
            ctx.mix[key] = value


def generator(mix):
    """The mix's ``open_loop_blocks``."""
    return importlib.import_module(
        "chipbench." + mix["generator"]).open_loop_blocks


def build(ctx):
    """Model, seeded weights in the served dtype and the server of the
    mix; the signature ``serve_open_loop.build`` has."""
    # the sweep script's context does not say whether it rehearses; off
    # the TPU nothing but a rehearsal gets this far (run.gate_devices)
    rehearse = getattr(ctx, "rehearse", None)
    if rehearse is None:
        rehearse = ctx.devices[0].platform != "tpu"
    if rehearse and "rehearsed" not in ctx.mix:
        _rehearse(ctx)
        ctx.mix["rehearsed"] = True
    module, config_cls, model_cls = ctx.config["model"].split(":")
    try:
        family = importlib.import_module(
            "paddlefleetx_tpu.models." + module)
    except ModuleNotFoundError as e:
        if e.name != "paddlefleetx_tpu.models." + module:
            raise
        # a program that lacks this family: fail at once, before any
        # device work
        sys.stderr.write(f"chipbench: the program has no model family "
                         f"{module!r}\n")
        sys.exit(2)
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig
    from paddlefleetx_tpu.observability import metrics
    from paddlefleetx_tpu.utils.config import get_config
    cfg = get_config(os.path.join(ctx.root, ctx.config["yaml"]),
                     overrides=list(ctx.config.get("overrides", []))
                     + list(ctx.extra_overrides), nranks=1)
    mcfg = getattr(family, config_cls).from_config(cfg)
    model = getattr(family, model_cls)(mcfg)
    abstract = jax.eval_shape(
        model.init, {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"]
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), abstract)
    served_dtype = jnp.dtype(mcfg.dtype)
    params = weights.seeded_params(abstract, ctx.seed, dtype=served_dtype)
    s = ctx.mix["server"]
    eos = mcfg.vocab_size - 1
    gen_cfg = GenerationConfig(max_dec_len=s["max_dec_len"],
                               decode_strategy="greedy_search",
                               eos_token_id=eos, pad_token_id=eos)
    metrics.set_enabled(True)
    metrics.get_registry().reset()
    srv = GenerationServer(
        model, params, gen_cfg, num_slots=s["num_slots"],
        page_size=s["page_size"], pool_pages=s.get("pool_pages"),
        prefill_chunk_pages=s["prefill_chunk_pages"],
        prefix_sharing=s["prefix_sharing"],
        rng=jax.random.key(ctx.seed % (2 ** 31 - 1) + 1),
        device_loop_ticks=s["device_loop_ticks"])
    del params
    return srv, mcfg, abstract, served_dtype


def served_gaps(ctx, abstract, served_dtype, sample, control=None):
    """Teacher-force each sampled request (prompt + served tokens)
    through the reference; per served token, how far its reference
    logit lies under the reference's best. With ``control`` the token
    judged at each position is the one THAT precision puts first.
    Also the share of top-k picks that fall the other way under a
    bfloat16 stream, per request."""
    from chipbench import weights
    ref = importlib.import_module(
        "chipbench.reference." + ctx.mix["reference"])
    params = weights.seeded_params(abstract, ctx.seed, dtype=served_dtype)
    gaps, tops, flips = [], [], []
    for prompt, tokens in sample:
        seq = list(prompt) + list(tokens)
        rows = (len(prompt) - 1, len(prompt) - 1 + len(tokens))
        logits, flipped = ref.logits(ctx.config, params, seq, rows)
        logits = np.asarray(logits)
        judged = np.asarray(tokens)
        if control is not None:
            low, _ = ref.logits(ctx.config, params, seq, rows, control)
            judged = np.asarray(low).argmax(-1)
        top = logits.max(-1)
        gaps.append(top - logits[np.arange(len(judged)), judged])
        tops.append(top)
        flips.append(flipped)
    del params
    return gaps, tops, flips


def pick_sample(ctx, finished, n, n_long, long_from):
    """The ``n_long`` longest finished requests (prompts of
    ``long_from`` tokens or more) and ``n - n_long`` more drawn from the
    seed."""
    by_len = sorted(finished, key=lambda r: -(len(r["prompt"])
                                              + len(r["completion"].tokens)))
    longs = [r for r in by_len if len(r["prompt"]) >= long_from][:n_long]
    rest = [r for r in by_len if all(r is not x for x in longs)]
    rng = np.random.default_rng(ctx.seed + 7)
    idx = rng.permutation(len(rest))[:max(0, n - len(longs))]
    chosen = longs + [rest[i] for i in idx]
    return [(r["prompt"], r["completion"].tokens) for r in chosen], \
        len(longs)


def kv_tokens_read(loop, t0, t1, reach):
    """``(global, window)``: over the decode ticks that ended in [t0,
    t1) and the requests live in each, the context a tick attends to
    and that context cut at ``reach`` keys. A request with prompt P
    commits its j-th token in its j-th tick, which reads P + j cached
    tokens (``serve_open_loop.kv_tokens_read``, by class)."""
    ticks = loop.tick_ends
    lo, hi = bisect.bisect_left(ticks, t0), bisect.bisect_left(ticks, t1)
    whole = cut = 0
    for r in loop.reqs.values():
        c = r["completion"]
        if c is None or c.ttft_ms is None:
            continue
        first = bisect.bisect_left(
            ticks, r["submitted"] + c.ttft_ms / 1e3 - 1e-4)
        j0 = max(lo, first) - first
        j1 = min(hi, first + len(c.tokens)) - first
        for j in range(j0, max(j0, j1)):
            whole += len(r["prompt"]) + j
            cut += min(len(r["prompt"]) + j, reach)
    return whole, cut


def _delta(after, before, key):
    return after.get(key, 0) - before.get(key, 0)


def run(ctx):
    """Warm up, ramp, measure one window, drain, then judge a sample of
    what was served against the reference."""
    import jax
    from chipbench import trace_reduce, traffic_gen
    srv, mcfg, abstract, served_dtype = build(ctx)
    mix = ctx.mix
    try:
        warm(ctx, srv, mcfg.vocab_size)
        ramp = float(mix["ramp_s"])
        t_open = time.time() + ramp + 0.2
        loop = Loop(srv, generator(mix)(
            mix, ctx.seed, mcfg.vocab_size, ctx.seconds), t_open)
        reg = loop.reg
        t_trace0 = t_trace1 = None
        moe0 = moe1 = None
        if ctx.trace:
            loop.run_until(lambda now: now >= t_open - 2.0)
            trace_reduce.start(ctx.trace_dir)
        loop.run_until(lambda now: now >= t_open)
        # -- the measured window --------------------------------------
        ctx.setup_done(t_open)
        tokens0 = reg.counter("serving/decode_tokens")
        count0 = dict(reg.snapshot()["counters"], **srv.summary())
        t_close = t_open + ctx.seconds
        if ctx.trace:
            # the device counters at the traced span's two ends: one
            # read each, between steps (summary() is the server's own)
            moe0 = srv.summary()
            t_trace0 = trace_reduce.mark(trace_reduce.BEGIN_MARK)
            loop.run_until(lambda now: now >= t_open + mix["trace_s"])
            t_trace1 = trace_reduce.mark(trace_reduce.END_MARK)
            moe1 = srv.summary()
            jax.profiler.stop_trace()
        end = loop.run_until(lambda now: now >= t_close)
        window_s = end - t_open
        tokens = reg.counter("serving/decode_tokens") - tokens0
        count1 = dict(reg.snapshot()["counters"], **srv.summary())
        memory = ctx.memory_peak()
        in_window = [r for r in loop.reqs.values()
                     if t_open <= r["due"] < t_close]
        limit = end + float(mix["drain_limit_s"])
        loop.run_until(lambda now: now >= limit or all(
            r["completion"] is not None or r["shed"] for r in in_window))
        counters = {k: int(v) for k, v in reg.snapshot()["counters"].items()
                    if k.split("/")[0] in ("attention", "serving", "moe")}
        summary = srv.summary()
        ring = summary.get("window_ring_pages", 0)
    finally:
        srv.close()
    del srv
    loop.srv = None
    gc.collect()

    ok = [r for r in in_window if r["completion"] is not None
          and r["completion"].finish_reason in ("length", "eos")
          and r["completion"].ttft_ms is not None]
    failed = len(in_window) - len(ok)
    late = [r["submitted"] - r["due"] for r in in_window]
    ttft = [(r["submitted"] - r["due"]) * 1e3 + r["completion"].ttft_ms
            for r in ok]
    tpot = [(r["seen"] - r["submitted"]
             - r["completion"].ttft_ms / 1e3) * 1e3
            / (len(r["completion"].tokens) - 1)
            for r in ok if len(r["completion"].tokens) > 1]
    pct = traffic_gen.percentile
    layers = mcfg.num_layers
    n_window = getattr(mcfg, "window_layers", 0)
    ticks = _delta(count1, count0, "serving/device_ticks")
    held_w = _delta(count1, count0, "serving/pages_window_held")
    held_g = _delta(count1, count0, "serving/pages_global_held")
    data = {"window_s": window_s, "global_layers": layers - n_window,
            "window_layers": n_window}
    if ticks and "moe_experts_touched" in count1:
        data["experts_touched_per_tick"] = _delta(
            count1, count0, "moe_experts_touched") / (ticks * layers)
    if held_g and n_window:
        data["window_pages_held_pct"] = 100.0 * held_w / (
            held_g * n_window / (layers - n_window))
    ctx.log({"window": {"seconds": window_s, "requests_due": len(in_window),
                        "completed": len(ok), "shed": loop.shed,
                        "decode_tokens": tokens,
                        "backlog_at_close": summary["pending"],
                        "long_requests": sum(
                            len(r["prompt"]) >= mix["check_long_from"]
                            for r in in_window)},
             "samples": {"ttft": len(ttft), "tpot": len(tpot)},
             "generator_lateness_ms": {
                 "p50": pct(late, 50) * 1e3, "p95": pct(late, 95) * 1e3,
                 "max": max(late) * 1e3} if late else None,
             "ttft_ms": {"p50": pct(ttft, 50), "p95": pct(ttft, 95)}
             if ttft else None,
             "tpot_ms": {"p50": pct(tpot, 50), "p95": pct(tpot, 95)}
             if tpot else None,
             "compiles_in_window": ctx.compiles_between(t_open, end),
             "compiles_in_drain": ctx.compiles_between(end, time.time()),
             "longest_steps": [
                 {"at_s": round(t - t_open, 2), "ms": round(d * 1e3, 1)}
                 for t, d in sorted(loop.steps, key=lambda x: -x[1])[:5]],
             "per_tick": {
                 "live_rows": _delta(count1, count0,
                                     "serving/decode_rows_live")
                 / max(ticks, 1),
                 "decode_picks": _delta(count1, count0, "moe_decode_picks")
                 / max(ticks, 1),
                 "experts_touched_a_layer": data.get(
                     "experts_touched_per_tick"),
                 "kv_blocks_walked": _delta(
                     count1, count0, "serving/kv_blocks_walked")
                 / max(ticks, 1),
                 "kv_blocks_whole": _delta(
                     count1, count0, "serving/kv_blocks_whole")
                 / max(ticks, 1)},
             "window_pages_held_pct": data.get("window_pages_held_pct"),
             "server_summary": {k: summary[k] for k in (
                 "decode_ticks", "host_roundtrips", "admitted", "evicted",
                 "preempted", "shed", "prefill_chunks", "pages_in_use",
                 "pool_pages", "pool_bytes", "window_ring_pages",
                 "window_pool_bytes", "moe_decode_picks",
                 "moe_experts_touched") if k in summary}})
    checks = []
    c = counters.get
    kernel = c("attention/flash_decode_paged", 0) > 0 and \
        c("attention/paged_gqa", 0) > 0 and \
        c("attention/window_layers", 0) > 0 and \
        c("attention/dense", 0) == 0 and \
        c("attention/fallback/kernel_rejected", 0) == 0
    checks.append(("paged_gqa_window_kernel_ran", 0 if kernel else 1, 0,
                   kernel))
    moe = c("moe/dropless", 0) > 0 and \
        c("moe/fallback/pallas_rejected", 0) == 0
    checks.append(("moe_kernel_ran", 0 if moe else 1, 0, moe))
    checks.append(("requests_not_completed", failed, 0, failed == 0))
    # what a slot may hold on a window layer, reckoned here from the
    # configuration and the mix, against the ring the server built (its
    # window pool IS slots x ring pages: no slot can hold more)
    s = mix["server"]
    most = -(-(ctx.config.get("sliding_window_size", 0)
               + s["page_size"] * s["prefill_chunk_pages"])
             // s["page_size"]) + 1
    checks.append(("window_ring_pages", ring, most, 0 < ring <= most))
    ctx.log({"counters": counters})
    # -- the reference, once the server is gone -------------------------
    t_ref = time.time()
    lim = mix["limits"]
    if ok:
        sample, n_long = pick_sample(
            ctx, ok, mix["check_requests"], mix["check_long_requests"],
            mix["check_long_from"])
        gaps, tops, flips = served_gaps(ctx, abstract, served_dtype, sample)
        flat, top = np.concatenate(gaps), np.concatenate(tops)
        widest = float(flat.max())
        off = float((flat > 0).mean())
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top), 1e-30))) - 7)
        ctx.log({"check": "reference", "requests": len(sample),
                 "long_requests": n_long,
                 "lengths": [len(p) + len(t) for p, t in sample],
                 "served_tokens": int(flat.size),
                 "widest_gap_bf16_ulps": float((flat / ulp).max()),
                 "per_request": [
                     {"len": len(p) + len(t), "widest": float(g.max()),
                      "off_argmax": float((g > 0).mean()),
                      "picks_flipped_by_bf16_input": f}
                     for (p, t), g, f in zip(sample, gaps, flips)],
                 "reference_seconds": time.time() - t_ref})
        checks.append(("long_requests_checked", n_long,
                       mix["check_long_requests"],
                       n_long >= mix["check_long_requests"]))
        checks.append(("served_logit_gap", widest, lim["served_logit_gap"],
                       widest <= lim["served_logit_gap"]))
        checks.append(("off_argmax_share", off, lim["off_argmax_share"],
                       off <= lim["off_argmax_share"]))
        if ctx.control:
            low = np.concatenate(served_gaps(
                ctx, abstract, served_dtype, sample,
                control=ctx.control)[0])
            ctx.log({"control": ctx.control, "compared": [
                {"name": "served_logit_gap", "value": float(low.max()),
                 "limit": lim["served_logit_gap"],
                 "ok": bool(low.max() <= lim["served_logit_gap"])},
                {"name": "off_argmax_share",
                 "value": float((low > 0).mean()),
                 "limit": lim["off_argmax_share"],
                 "ok": bool((low > 0).mean() <= lim["off_argmax_share"])}]})
    metrics = {"serve_tokens_per_s": tokens / window_s}
    if tpot:
        metrics["tpot_p95_ms"] = pct(tpot, 95)
    if ctx.trace:
        data["kv_tokens_global"], data["kv_tokens_window"] = \
            kv_tokens_read(loop, t_trace0, t_trace1,
                           ctx.config.get("sliding_window_size", 0))
        if "moe_decode_picks" in moe1:
            data["moe_picks_traced"] = sum(
                _delta(moe1, moe0, k)
                for k in ("moe_decode_picks", "moe_prefill_picks"))
            data["moe_touched_traced"] = sum(
                _delta(moe1, moe0, k)
                for k in ("moe_experts_touched", "moe_prefill_touched"))
    return {"metrics": metrics, "attempted": len(in_window),
            "failed": failed, "checks": checks,
            "memory_peak_bytes": memory, "data": data}
