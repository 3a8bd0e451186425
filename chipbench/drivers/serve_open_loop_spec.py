"""Driver ``serve_open_loop_spec``: ``serve_open_loop_lm`` (its
``build``, ``served_gaps``, ``pick_sample``; ``serve_open_loop``'s
``Loop`` and ``warm`` through it: imported, not copied) for a server
that SPECULATES with a draft source of the model's own: every tick is a
verify tick over ``[t0, draft]``, a request advances by 1 or 2 tokens a
tick, and the model's multi-token-prediction block drafts inside the
tick program. What differs from ``serve_open_loop_lm`` is how it
counts and what it checks:

A request's ticks are counted from what each tick COMMITTED, not from
its tokens: a ``Completion`` carries the server's drafts as ``(i, d)``,
one a tick, and the tick that drafted for ``tokens[i]`` read ``prompt +
i - 1`` cached tokens (``kv_tokens_read`` below;
``serve_open_loop_lm.kv_tokens_read`` assumes one token a tick).

``correct``: ``served_logit_gap`` and ``off_argmax_share`` against the
reference's full forward pass (greedy verification is token-exact, so
the speculative path is judged as the plain one is), on a sample that
holds the ``check_long_requests`` longest finished long requests;
``draft_off_argmax_share``: over the same requests, the share of the
served drafts that are not the reference block's argmax at their
position (``<reference>.mtp_argmax``, teacher-forced); exact: the run
was speculative at ``k = 1`` with the model's source and drafted once
for every live row of every tick, the paged decode kernel's verify
branch carried every layer of both page classes and the block's, with
grouped heads, window layers and head norms, nothing fell back (any
``attention/fallback/*``, the expert products), nothing went dense, no
slot held more than its ring, prefix sharing was asked for and refused
on every admission, every due request finished ``length`` or ``eos``,
none was shed, none preempted.

Counted into ``data``: ``spec_accept_pct`` and
``spec_rollback_per_tick`` (growth of the program's
``serving/spec_accepted`` over ``serving/spec_drafted``, and of
``serving/spec_rollback_columns`` over the ticks, in the window),
``experts_touched_per_tick`` (a layer WITH experts: four of the five
and the block), ``window_pages_held_pct``, the class counts and, over
the traced span, the tokens read by class and the device's expert
counters.

A traced run traces the window's LAST ``trace_s`` seconds and stops the
profiler after the window has closed, as ``serve_open_loop_ssm`` does
and for its reason: the stop holds the loop for seconds.
"""

import bisect
import dataclasses
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
served_gaps, pick_sample = lm.served_gaps, lm.pick_sample


def build(ctx):
    """``serve_open_loop_lm.build``, then the same server again with
    the mix's draft source (``server.spec_method`` /
    ``server.spec_tokens``, which that ``build`` does not read): the
    first server's caches are freed before the second's are made, the
    model and the seeded weights are the first one's. The signature
    ``serve_open_loop.build`` has."""
    import jax
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.observability import metrics
    plain, mcfg, abstract, served_dtype = lm.build(ctx)
    s = ctx.mix["server"]
    model, params = plain.model, plain.params
    gen_cfg = dataclasses.replace(
        plain.gen_cfg, spec_method=s["spec_method"],
        spec_tokens=s["spec_tokens"])
    plain.close()
    del plain
    gc.collect()
    metrics.get_registry().reset()
    srv = GenerationServer(
        model, params, gen_cfg, num_slots=s["num_slots"],
        page_size=s["page_size"], pool_pages=s.get("pool_pages"),
        prefill_chunk_pages=s["prefill_chunk_pages"],
        prefix_sharing=s["prefix_sharing"],
        rng=jax.random.key(ctx.seed % (2 ** 31 - 1) + 1),
        device_loop_ticks=s["device_loop_ticks"])
    return srv, mcfg, abstract, served_dtype


def kv_tokens_read(loop, t0, t1, reach):
    """``(global, window)``: over the verify ticks that ended in [t0,
    t1) and the requests live in each, the context a tick attends to
    and that context cut at ``reach`` keys. A request's ticks are the
    server's, one after another from its first; its j-th tick is the
    one its j-th draft came from, which read the prompt and the ``i -
    1`` tokens committed before it."""
    ticks = loop.tick_ends
    lo, hi = bisect.bisect_left(ticks, t0), bisect.bisect_left(ticks, t1)
    whole = cut = 0
    for r in loop.reqs.values():
        c = r["completion"]
        if c is None or c.ttft_ms is None or not c.drafts:
            continue
        first = bisect.bisect_left(
            ticks, r["submitted"] + c.ttft_ms / 1e3 - 1e-4)
        j0 = max(lo, first) - first
        j1 = min(hi, first + len(c.drafts)) - first
        for i, _ in c.drafts[j0:max(j0, j1)]:
            whole += len(r["prompt"]) + i - 1
            cut += min(len(r["prompt"]) + i - 1, reach)
    return whole, cut


def draft_misses(ctx, abstract, served_dtype, sample, control=None):
    """Per sampled request ``(prompt, tokens, drafts)``, a bool a
    served draft: it is not the reference block's argmax at its
    position. The draft for ``tokens[i]`` is the block's output at
    sequence position ``len(prompt) + i - 2``, teacher-forced. With
    ``control`` what is judged is the argmax THAT precision gives in
    the draft's place."""
    from chipbench import weights
    ref = importlib.import_module(
        "chipbench.reference." + ctx.mix["reference"])
    params = weights.seeded_params(abstract, ctx.seed, dtype=served_dtype)
    out = []
    for prompt, tokens, drafts in sample:
        seq = list(prompt) + list(tokens)
        # a draft past the last served token has nothing to be forced by
        kept = [(i, d) for i, d in drafts if i < len(tokens)]
        lo = len(prompt) - 1
        hi = lo + max(i for i, _ in kept)
        best = ref.mtp_argmax(ctx.config, params, seq, (lo, hi))
        judged = dict(kept)
        if control is not None:
            low = ref.mtp_argmax(ctx.config, params, seq, (lo, hi),
                                 control)
            judged = {i: int(low[i - 1]) for i in judged}
        out.append(np.array([judged[i] != int(best[i - 1])
                             for i in sorted(judged)]))
    del params
    return out


def _delta(after, before, key):
    return after.get(key, 0) - before.get(key, 0)


def _counted(reg, srv):
    """The program's counters and the server's own summary, one read,
    between steps."""
    return dict(reg.snapshot()["counters"], **srv.summary())


def run(ctx):
    """Warm up, ramp, measure one window, drain, then judge a sample of
    what was served, tokens and drafts, against the reference."""
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
        t_close = t_open + ctx.seconds
        if ctx.trace:
            # the window's LAST trace_s seconds; the profiler stops
            # once the window has closed (module docstring)
            t_span = t_close - float(mix["trace_s"])
            loop.run_until(lambda now: now >= t_span - 2.0)
            trace_reduce.start(ctx.trace_dir)
            loop.run_until(lambda now: now >= t_span)
            t_trace0 = trace_reduce.mark(trace_reduce.BEGIN_MARK)
            span0 = _counted(reg, srv)
        end = loop.run_until(lambda now: now >= t_close)
        if ctx.trace:
            t_trace1 = trace_reduce.mark(trace_reduce.END_MARK)
        window_s = end - t_open
        tokens = reg.counter("serving/decode_tokens") - tokens0
        count1 = _counted(reg, srv)
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
    n_window = mcfg.window_layers
    n_global = mcfg.kv_layers - n_window
    # layers with routed experts: the sparse ones and the block's
    n_expert = sum(mcfg.is_sparse(i) for i in range(mcfg.num_layers)) \
        + mcfg.num_nextn_predict_layers
    ticks = _delta(count1, count0, "serving/device_ticks")
    drafted = _delta(count1, count0, "serving/spec_drafted")
    held_w = _delta(count1, count0, "serving/pages_window_held")
    held_g = _delta(count1, count0, "serving/pages_global_held")
    data = {"window_s": window_s, "global_layers": n_global,
            "window_layers": n_window}
    if drafted:
        data["spec_accept_pct"] = 100.0 * _delta(
            count1, count0, "serving/spec_accepted") / drafted
    if ticks:
        data["spec_rollback_per_tick"] = _delta(
            count1, count0, "serving/spec_rollback_columns") / ticks
        if "moe_experts_touched" in count1:
            data["experts_touched_per_tick"] = _delta(
                count1, count0, "moe_experts_touched") / (ticks * n_expert)
    if held_g and n_window:
        data["window_pages_held_pct"] = 100.0 * held_w / (
            held_g * n_window / n_global)
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
                 "committed": tokens / max(ticks, 1),
                 "decode_picks": _delta(count1, count0, "moe_decode_picks")
                 / max(ticks, 1),
                 "experts_touched_a_layer": data.get(
                     "experts_touched_per_tick"),
                 "rollback_columns": data.get("spec_rollback_per_tick"),
                 "kv_blocks_walked": _delta(
                     count1, count0, "serving/kv_blocks_walked")
                 / max(ticks, 1),
                 "kv_blocks_whole": _delta(
                     count1, count0, "serving/kv_blocks_whole")
                 / max(ticks, 1)},
             "spec_accept_pct": data.get("spec_accept_pct"),
             "window_pages_held_pct": data.get("window_pages_held_pct"),
             "server_summary": {k: summary[k] for k in (
                 "decode_ticks", "host_roundtrips", "admitted", "evicted",
                 "preempted", "shed", "prefill_chunks", "pages_in_use",
                 "pool_pages", "pool_bytes", "window_ring_pages",
                 "window_pool_bytes", "moe_decode_picks",
                 "moe_experts_touched", "spec_method", "spec_tokens",
                 "spec_drafted", "spec_accepted") if k in summary}})
    checks = []
    c = counters.get
    source = summary.get("spec_method") == "mtp" and \
        summary.get("spec_tokens") == 1 and \
        c("serving/spec_source/mtp", 0) > 0
    checks.append(("speculative_with_the_models_source",
                   0 if source else 1, 0, source))
    # one draft for every live row of every tick (a row whose request
    # had gone by the time its tick was read is void and drafted for
    # no one)
    rows = c("serving/decode_rows_live", 0) \
        - c("serving/harvest_rows_void", 0)
    checks.append(("spec_drafted", c("serving/spec_drafted", 0), rows,
                   0 < rows == c("serving/spec_drafted", 0)))
    kernel = c("attention/flash_decode_paged_verify", 0) \
        >= mcfg.kv_layers and \
        c("attention/paged_gqa", 0) > 0 and \
        c("attention/window_layers", 0) > 0 and \
        c("attention/mtp_layers", 0) > 0 and \
        c("attention/qk_norm_layers", 0) > 0 and \
        c("attention/dense", 0) == 0
    checks.append(("paged_gqa_window_verify_kernel_ran",
                   0 if kernel else 1, 0, kernel))
    fallbacks = sum(v for k, v in counters.items()
                    if k.startswith("attention/fallback/"))
    checks.append(("attention_fallbacks", fallbacks, 0, fallbacks == 0))
    moe = c("moe/dropless", 0) > 0 and \
        c("moe/fallback/pallas_rejected", 0) == 0
    checks.append(("moe_kernel_ran", 0 if moe else 1, 0, moe))
    checks.append(("requests_not_completed", failed, 0, failed == 0))
    checks.append(("requests_shed", loop.shed, 0, loop.shed == 0))
    checks.append(("requests_preempted", summary["preempted"], 0,
                   summary["preempted"] == 0))
    refused = c("serving/prefix_refused_window", 0)
    checks.append(("prefix_refused_window", refused, summary["admitted"],
                   bool(summary.get("prefix_refused_window"))
                   and refused >= summary["admitted"]
                   and summary.get("prefix_hits", 0) == 0))
    # what a slot may hold on a window layer, reckoned here from the
    # configuration and the mix, against the ring the server built (its
    # window pool IS slots x ring pages: no slot can hold more)
    s = mix["server"]
    most = -(-(ctx.config["sliding_window"]
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
        # pick_sample hands back each request's own token list
        by_tokens = {id(r["completion"].tokens): r["completion"].drafts
                     for r in ok}
        drafts = [(p, t, by_tokens[id(t)]) for p, t in sample]
        gaps, tops, _ = served_gaps(ctx, abstract, served_dtype, sample)
        flat, top = np.concatenate(gaps), np.concatenate(tops)
        widest = float(flat.max())
        off = float((flat > 0).mean())
        missed = draft_misses(ctx, abstract, served_dtype, drafts)
        draft_off = float(np.concatenate(missed).mean())
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top), 1e-30))) - 7)
        ctx.log({"check": "reference", "requests": len(sample),
                 "long_requests": n_long,
                 "lengths": [len(p) + len(t) for p, t in sample],
                 "served_tokens": int(flat.size),
                 "served_drafts": int(sum(m.size for m in missed)),
                 "top_logit": {"median": float(np.median(top)),
                               "max": float(top.max())},
                 "widest_gap_bf16_ulps": float((flat / ulp).max()),
                 "per_request": [
                     {"len": len(p) + len(t), "widest": float(g.max()),
                      "off_argmax": float((g > 0).mean()),
                      "draft_off_argmax": float(m.mean())}
                     for (p, t), g, m in zip(sample, gaps, missed)],
                 "reference_seconds": time.time() - t_ref})
        checks.append(("long_requests_checked", n_long,
                       mix["check_long_requests"],
                       n_long >= mix["check_long_requests"]))
        checks.append(("served_logit_gap", widest, lim["served_logit_gap"],
                       widest <= lim["served_logit_gap"]))
        checks.append(("off_argmax_share", off, lim["off_argmax_share"],
                       off <= lim["off_argmax_share"]))
        checks.append(("draft_off_argmax_share", draft_off,
                       lim["draft_off_argmax_share"],
                       draft_off <= lim["draft_off_argmax_share"]))
        if ctx.control:
            low = np.concatenate(served_gaps(
                ctx, abstract, served_dtype, sample,
                control=ctx.control)[0])
            low_draft = float(np.concatenate(draft_misses(
                ctx, abstract, served_dtype, drafts,
                control=ctx.control)).mean())
            ctx.log({"control": ctx.control, "compared": [
                {"name": "served_logit_gap", "value": float(low.max()),
                 "limit": lim["served_logit_gap"],
                 "ok": bool(low.max() <= lim["served_logit_gap"])},
                {"name": "off_argmax_share",
                 "value": float((low > 0).mean()),
                 "limit": lim["off_argmax_share"],
                 "ok": bool((low > 0).mean() <= lim["off_argmax_share"])},
                {"name": "draft_off_argmax_share", "value": low_draft,
                 "limit": lim["draft_off_argmax_share"],
                 "ok": bool(low_draft
                            <= lim["draft_off_argmax_share"])}]})
    metrics = {"serve_tokens_per_s": tokens / window_s}
    if tpot:
        metrics["tpot_p95_ms"] = pct(tpot, 95)
    if ctx.trace:
        data["kv_tokens_global"], data["kv_tokens_window"] = \
            kv_tokens_read(loop, t_trace0, t_trace1,
                           ctx.config["sliding_window"])
        if "moe_decode_picks" in span1:
            data["moe_picks_traced"] = sum(
                _delta(span1, span0, k)
                for k in ("moe_decode_picks", "moe_prefill_picks"))
            data["moe_touched_traced"] = sum(
                _delta(span1, span0, k)
                for k in ("moe_experts_touched", "moe_prefill_touched"))
    return {"metrics": metrics, "attempted": len(in_window),
            "failed": failed, "checks": checks,
            "memory_peak_bytes": memory, "data": data}
