"""Driver ``serve_open_loop_ssm``: ``serve_open_loop_lm`` (its
``build``, ``served_gaps``, ``pick_sample``, ``kv_tokens_read``;
``serve_open_loop``'s ``Loop`` and ``warm`` through it: imported, not
copied) for a dense model whose cache is of two kinds, a recurrent
state a slot on its state-space layers (most of them, and most of what
a live slot holds) and pages on its few softmax layers; no experts, no
window layers. What differs from ``serve_open_loop_hybrid`` is what it
checks and counts:

``correct``: ``served_logit_gap`` and ``off_argmax_share`` against the
reference's full forward pass, on a sample that holds the
``check_long_requests`` longest finished long requests (a state carried
through every chunk of a long prompt, a padded last chunk and every
decode tick is then inside the comparison); exact: the state-space
decode kernel ran on every state-space layer and the paged decode
kernel on every softmax layer with grouped heads, nothing fell back
(any ``attention/fallback/*``), nothing went dense, prefix sharing was
asked for and refused on every admission, every due request finished
``length`` or ``eos``, none was shed.

Counted into ``data``: ``state_cache_share_pct`` (the hybrid driver's
arithmetic) and ``slots_full_step_pct`` (growth of the program's
``serving/slots_full_steps`` over the window's steps: how often a step
ended with a request queued and every slot taken, which is what a
burst does to a server whose slots are bounded by state rows).

A traced run traces the window's LAST ``trace_s`` seconds and stops the
profiler after the window has closed: the stop holds the loop for
seconds, which an open loop at 4/5 of the knee does not work off inside
a window (my chip runs, PR 36: with the span at the window's opening
the traced runs closed with every slot full, a queue of 24-37 and
``slots_full_step_pct`` 77, where the untraced runs read no queue and
0). The window's counters then describe the cell; a traced run's
``tpot`` still holds the stop for the requests live at the close, and
is not the end-to-end number.

Weights: ``chipbench/weights.py`` draws them; the reference's
``spread_decays`` (``reference/granite_hybrid_decoder.py``) then maps
the state-space layers' ``A_log``, ``dt_bias`` and ``D`` onto the
Mamba-2 initialisation, for the served weights here and for the
reference's own copy alike.
"""

import gc
import importlib
import importlib.util
import os
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


lm = _sibling("serve_open_loop_lm")
Loop, warm, generator = lm.Loop, lm.warm, lm.generator
served_gaps, pick_sample, kv_tokens_read = \
    lm.served_gaps, lm.pick_sample, lm.kv_tokens_read


def build(ctx):
    """``serve_open_loop_lm.build``, then the decays spread (module
    docstring): the signature ``serve_open_loop.build`` has."""
    srv, mcfg, abstract, served_dtype = lm.build(ctx)
    ref = importlib.import_module(
        "chipbench.reference." + ctx.mix["reference"])
    srv.params = ref.spread_decays(srv.params)
    return srv, mcfg, abstract, served_dtype


def _delta(after, before, key):
    return after.get(key, 0) - before.get(key, 0)


def _counted(reg, srv):
    """The program's counters and the server's own summary, one read,
    between steps."""
    return dict(reg.snapshot()["counters"], **srv.summary())


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
        t_trace0 = t_trace1 = span0 = span1 = None
        loop.run_until(lambda now: now >= t_open)
        # -- the measured window --------------------------------------
        ctx.setup_done(t_open)
        tokens0 = reg.counter("serving/decode_tokens")
        count0 = _counted(reg, srv)
        steps0 = len(loop.steps)
        t_close = t_open + ctx.seconds
        if ctx.trace:
            # the traced span is the window's LAST trace_s seconds and
            # the profiler stops once the window has closed: its stop
            # holds the loop for seconds, and an open loop at 4/5 of
            # the knee spends the rest of a window working that off
            # (every slot full, a queue of tens), so the window's own
            # counters would describe the stall and not the cell
            t_span = t_close - float(mix["trace_s"])
            loop.run_until(lambda now: now >= t_span - 2.0)
            trace_reduce.start(ctx.trace_dir)
            loop.run_until(lambda now: now >= t_span)
            t_trace0 = trace_reduce.mark(trace_reduce.BEGIN_MARK)
            # a deferring server's first step inside the span commits
            # the tick launched before it, whose device time the span
            # does not hold: the rows are counted from after that step
            first = len(loop.steps)
            loop.run_until(lambda now: len(loop.steps) > first
                           or now >= t_close)
            span0 = _counted(reg, srv)
        end = loop.run_until(lambda now: now >= t_close)
        if ctx.trace:
            t_trace1 = trace_reduce.mark(trace_reduce.END_MARK)
        window_s = end - t_open
        tokens = reg.counter("serving/decode_tokens") - tokens0
        count1 = _counted(reg, srv)
        steps = len(loop.steps) - steps0
        memory = ctx.memory_peak()
        if ctx.trace:
            span1 = count1
            jax.profiler.stop_trace()
        in_window = [r for r in loop.reqs.values()
                     if t_open <= r["due"] < t_close]
        limit = end + float(mix["drain_limit_s"])
        loop.run_until(lambda now: now >= limit or all(
            r["completion"] is not None or r["shed"] for r in in_window))
        counters = {k: int(v) for k, v in reg.snapshot()["counters"].items()
                    if k.split("/")[0] in ("attention", "serving")}
        summary = srv.summary()
        state_row_bytes = getattr(srv.model.config, "state_row_bytes", 0)
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
    kv_layers = getattr(mcfg, "kv_layers", mcfg.num_layers)
    ssm_layers = getattr(mcfg, "state_layers", 0)
    ticks = _delta(count1, count0, "serving/device_ticks")
    rows_held = _delta(count1, count0, "serving/state_rows_held")
    pages_held = _delta(count1, count0, "serving/pages_global_held")
    page_bytes = summary.get("pool_bytes", 0) / max(
        summary.get("pool_pages", 1) * max(kv_layers, 1), 1)
    # the GQA metric file the other grouped cells read, with no window
    # class
    data = {"window_s": window_s, "global_layers": kv_layers,
            "window_layers": 0, "ssm_layers": ssm_layers}
    if rows_held and state_row_bytes:
        # bytes of cache the live slots held, by class, summed over the
        # window's decode ticks
        state_b = rows_held * state_row_bytes
        data["state_cache_share_pct"] = 100.0 * state_b / (
            state_b + pages_held * page_bytes)
    if steps and "serving/slots_full_steps" in count1:
        data["slots_full_step_pct"] = 100.0 * _delta(
            count1, count0, "serving/slots_full_steps") / steps
    ctx.log({"window": {"seconds": window_s, "requests_due": len(in_window),
                        "completed": len(ok), "shed": loop.shed,
                        "decode_tokens": tokens, "steps": steps,
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
                 "pages_held_a_kv_layer": pages_held
                 / max(ticks * kv_layers, 1),
                 "state_bytes_held": rows_held * state_row_bytes
                 / max(ticks, 1),
                 "page_bytes_held": pages_held * page_bytes
                 / max(ticks, 1)},
             "state_cache_share_pct": data.get("state_cache_share_pct"),
             "slots_full_step_pct": data.get("slots_full_step_pct"),
             "server_summary": {k: summary[k] for k in (
                 "decode_ticks", "host_roundtrips", "admitted", "evicted",
                 "preempted", "shed", "prefill_chunks", "pages_in_use",
                 "pool_pages", "pool_bytes", "state_bytes",
                 "state_rows_held") if k in summary}})
    checks = []
    c = counters.get
    fallbacks = sum(v for k, v in counters.items()
                    if k.startswith("attention/fallback/"))
    ssd = c("attention/ssd_decode", 0) > 0 and \
        c("attention/ssd_chunk", 0) > 0 and \
        c("attention/ssm_layers", 0) > 0 and \
        c("attention/fallback/ssd_rejected", 0) == 0
    checks.append(("ssd_decode_kernel_ran", 0 if ssd else 1, 0, ssd))
    paged = c("attention/flash_decode_paged", 0) > 0 and \
        c("attention/paged_gqa", 0) > 0 and \
        c("attention/window_layers", 0) == 0 and \
        c("attention/dense", 0) == 0
    checks.append(("paged_gqa_kernel_ran", 0 if paged else 1, 0, paged))
    checks.append(("attention_fallbacks", fallbacks, 0, fallbacks == 0))
    checks.append(("requests_not_completed", failed, 0, failed == 0))
    checks.append(("requests_shed", loop.shed, 0, loop.shed == 0))
    refused = c("serving/prefix_refused_recurrent", 0)
    checks.append(("prefix_refused_recurrent", refused,
                   summary["admitted"],
                   bool(summary.get("prefix_refused_recurrent"))
                   and refused >= summary["admitted"]
                   and summary.get("prefix_hits", 0) == 0))
    ctx.log({"counters": counters})
    # -- the reference, once the server is gone -------------------------
    t_ref = time.time()
    lim = mix["limits"]
    if ok:
        sample, n_long = pick_sample(
            ctx, ok, mix["check_requests"], mix["check_long_requests"],
            mix["check_long_from"])
        gaps, tops, _ = served_gaps(ctx, abstract, served_dtype, sample)
        flat, top = np.concatenate(gaps), np.concatenate(tops)
        widest = float(flat.max())
        off = float((flat > 0).mean())
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top), 1e-30))) - 7)
        ctx.log({"check": "reference", "requests": len(sample),
                 "long_requests": n_long,
                 "lengths": [len(p) + len(t) for p, t in sample],
                 # a tied head over seeded random weights answers with
                 # few distinct ids: said, so the comparison is read
                 # for what it holds
                 "distinct_served_ids": [len(set(t)) for _, t in sample],
                 "served_tokens": int(flat.size),
                 "top_logit": {"median": float(np.median(top)),
                               "max": float(top.max())},
                 "widest_gap_bf16_ulps": float((flat / ulp).max()),
                 "per_request": [
                     {"len": len(p) + len(t), "widest": float(g.max()),
                      "off_argmax": float((g > 0).mean())}
                     for (p, t), g in zip(sample, gaps)],
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
            kv_tokens_read(loop, t_trace0, t_trace1, 0)
        data["ssd_rows_traced"] = _delta(
            span1, span0, "serving/decode_rows_live")
    return {"metrics": metrics, "attempted": len(in_window),
            "failed": failed, "checks": checks,
            "memory_peak_bytes": memory, "data": data}
