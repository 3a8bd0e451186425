"""Driver ``serve_open_loop``: one ``GenerationServer`` under open-loop
load, through ``submit`` / ``step`` only.

One thread plays both roles the product's own serving loop plays: it
submits every request whose due time has come, then calls ``step()``
while the server has work, and sleeps to the next due time when it has
none. Requests are due on a seeded schedule fixed in the traffic file
whatever the server does (open loop); a request that is submitted late
because a ``step()`` was running is timed from when it was DUE, and the
lateness is printed.

Clocks (every percentile is nearest-rank over exact per-request samples,
the sample counts are printed). ``ttft`` = (submitted - due) + ``Completion.ttft_ms`` (the
server's own exact per-request sample, submit -> first committed token).
``tpot`` = (completion seen by the harness - first token) / (tokens - 1).
``queue wait`` = due -> the end of the ``step()`` in which the server's
``serving/admitted`` counter passed this request (admission is FIFO).

After the window the server is closed and freed; a seeded sample of the
requests it finished, the longest among them, is teacher-forced through
the float32 reference, and the widest gap by which a served token's
logit lies under the reference's best is compared with its limit.
"""

import bisect
import gc
import time

import numpy as np


def build(ctx):
    """Model, seeded bf16 weights and the server of the mix."""
    import os

    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig
    from paddlefleetx_tpu.observability import metrics
    from paddlefleetx_tpu.utils.config import get_config
    cfg = get_config(os.path.join(ctx.root, ctx.config["yaml"]),
                     overrides=list(ctx.config.get("overrides", []))
                     + list(ctx.extra_overrides), nranks=1)
    mcfg = GPTConfig.from_config(cfg)
    model = GPTForPretraining(mcfg)
    abstract = nn.meta.unbox(jax.eval_shape(
        model.init, {"params": jax.random.key(0)},
        jnp.zeros((1, 8), jnp.int32))["params"])
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
        page_size=s["page_size"],
        prefill_chunk_pages=s["prefill_chunk_pages"],
        prefix_sharing=s["prefix_sharing"],
        rng=jax.random.key(ctx.seed % (2 ** 31 - 1) + 1),
        device_loop_ticks=s["device_loop_ticks"])
    del params
    return srv, mcfg, abstract, served_dtype


class Loop:
    """The submit/step loop and every sample it takes."""

    def __init__(self, srv, schedule, t_open):
        from paddlefleetx_tpu.observability import metrics
        self.srv = srv
        self.reg = metrics.get_registry()
        self.schedule = schedule          # yields (offset_s, prompt)
        self.t_open = t_open              # wall time of offset 0
        self.next = next(schedule)
        self.reqs = {}                    # request id -> record
        self.order = []                   # ids in submission order
        self.admitted = 0
        self.tick_ends = []               # end times of decoding steps
        self.roundtrips = []              # (end time, seconds) of those
        self.steps = []                   # (end time, seconds) of every step
        self.shed = 0

    def submit_due(self, now):
        """Submit every request whose due time has come by ``now``."""
        import jax
        from paddlefleetx_tpu.core.serving import RequestShed
        while self.t_open + self.next[0] <= now:
            offset, prompt = self.next
            self.next = next(self.schedule)
            with jax.profiler.TraceAnnotation("server/submit"):
                try:
                    rid = self.srv.submit(prompt)
                except RequestShed:
                    self.shed += 1
                    rid = f"shed-{self.shed}"
            t = time.time()
            self.reqs[rid] = {"offset": offset, "due": self.t_open + offset,
                              "submitted": t, "prompt": prompt,
                              "admitted": None, "completion": None,
                              "seen": None, "shed": isinstance(rid, str)}
            if not isinstance(rid, str):
                self.order.append(rid)

    def step(self):
        """One ``srv.step()`` and the samples it yields."""
        import jax
        ticks0 = self.reg.counter("serving/device_ticks")
        t0 = time.time()
        with jax.profiler.TraceAnnotation("server/step"):
            finished = self.srv.step()
        t1 = time.time()
        self.steps.append((t1, t1 - t0))
        if self.reg.counter("serving/device_ticks") > ticks0:
            self.tick_ends.append(t1)
            self.roundtrips.append((t1, t1 - t0))
        adm = int(self.reg.counter("serving/admitted"))
        while self.admitted < min(adm, len(self.order)):
            self.reqs[self.order[self.admitted]]["admitted"] = t1
            self.admitted += 1
        for c in finished:
            r = self.reqs.get(c.request_id)
            if r is not None:
                r["completion"], r["seen"] = c, t1

    def run_until(self, stop):
        """Submit and step until ``stop(now)`` says so."""
        import jax
        while True:
            now = time.time()
            if stop(now):
                return now
            self.submit_due(now)
            if self.srv.work_pending():
                self.step()
            else:
                wait = self.t_open + self.next[0] - time.time()
                if wait > 0:
                    with jax.profiler.TraceAnnotation(
                            "harness/wait_for_arrival"):
                        time.sleep(min(wait, 0.05))


def warm(ctx, srv, vocab):
    """Every program the window will drive, compiled and run once: the
    prefill chunk, slot activation, the decode tick (a two-chunk and a
    one-chunk prompt, decoded to their end)."""
    rng = np.random.default_rng(ctx.seed)
    ids = [srv.submit(rng.integers(0, vocab - 2, n).tolist())
           for n in ctx.mix["warm_prompts"]]
    got, guard = set(), 0
    while srv.work_pending():
        got.update(c.request_id for c in srv.step())
        guard += 1
        if guard > 100000:
            raise RuntimeError("warm-up did not drain")
    if got != set(ids):
        raise RuntimeError("a warm-up request never completed")


def served_gaps(ctx, abstract, served_dtype, sample, precision="float32",
                control=None):
    """Teacher-force each sampled request (prompt + served tokens) through
    the reference; per served token, how far its reference logit lies
    under the reference's best. With ``control`` (a lower precision) the
    token judged at each position is the one THAT precision puts first
    instead of the served one."""
    import jax
    import jax.numpy as jnp

    from chipbench import weights
    from chipbench.reference import gpt2_decoder as ref
    params = weights.seeded_params(abstract, ctx.seed, dtype=served_dtype)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    width = ctx.config["max_position_embeddings"]
    gaps, tops = [], []
    for prompt, tokens in sample:
        ids = np.zeros((1, width), np.int32)
        seq = list(prompt) + list(tokens)
        ids[0, :len(seq)] = seq
        rows = slice(len(prompt) - 1, len(prompt) - 1 + len(tokens))
        logits = np.asarray(ref.logits(params, jnp.asarray(ids))[0, rows])
        judged = np.asarray(tokens)
        if control is not None:
            low = np.asarray(ref.logits(params, jnp.asarray(ids),
                                        control)[0, rows])
            judged = low.argmax(-1)
        top = logits.max(-1)
        gaps.extend((top - logits[np.arange(len(judged)), judged]).tolist())
        tops.extend(top.tolist())
    del params
    return np.array(gaps), np.array(tops)


def pick_sample(ctx, finished, n):
    """The longest finished request and ``n - 1`` more drawn from the
    seed."""
    by_len = sorted(finished, key=lambda r: -(len(r["prompt"])
                                              + len(r["completion"].tokens)))
    rng = np.random.default_rng(ctx.seed + 7)
    rest = by_len[1:]
    idx = rng.permutation(len(rest))[:max(0, n - 1)]
    chosen = by_len[:1] + [rest[i] for i in idx]
    return [(r["prompt"], r["completion"].tokens) for r in chosen]


def run(ctx):
    """Warm up, ramp, measure one window, drain, then judge a sample of
    what was served against the reference."""
    import jax
    from chipbench import trace_reduce, traffic_gen
    mix = ctx.mix
    srv, mcfg, abstract, served_dtype = build(ctx)
    try:
        warm(ctx, srv, mcfg.vocab_size)
        ramp = float(mix["ramp_s"])
        t_open = time.time() + ramp + 0.2
        loop = Loop(srv, traffic_gen.open_loop_blocks(
            mix, ctx.seed, mcfg.vocab_size, ctx.seconds), t_open)
        t_trace0 = t_trace1 = None
        if ctx.trace:
            # starting and stopping the profiler each stall this thread
            # for a second or so: the start falls in the ramp, and the
            # host-clock samples of a traced run are taken only from
            # requests due once the stop has settled
            loop.run_until(lambda now: now >= t_open - 2.0)
            trace_reduce.start(ctx.trace_dir)
        loop.run_until(lambda now: now >= t_open)
        # -- the measured window --------------------------------------
        ctx.setup_done(t_open)
        reg = loop.reg
        tokens0 = reg.counter("serving/decode_tokens")
        t_close = t_open + ctx.seconds
        if ctx.trace:
            t_trace0 = trace_reduce.mark(trace_reduce.BEGIN_MARK)
            loop.run_until(lambda now: now >= t_open + mix["trace_s"])
            t_trace1 = trace_reduce.mark(trace_reduce.END_MARK)
            jax.profiler.stop_trace()
        end = loop.run_until(lambda now: now >= t_close)
        window_s = end - t_open
        tokens = reg.counter("serving/decode_tokens") - tokens0
        memory = ctx.memory_peak()
        in_window = [r for r in loop.reqs.values()
                     if t_open <= r["due"] < t_close]
        limit = end + float(mix["drain_limit_s"])
        loop.run_until(lambda now: now >= limit or all(
            r["completion"] is not None or r["shed"] for r in in_window))
        counters = {k: int(v) for k, v in reg.snapshot()["counters"].items()
                    if k.split("/")[0] in ("attention", "serving")}
        summary = srv.summary()
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
    qwait = [(r["admitted"] - r["due"]) * 1e3 for r in in_window
             if r["admitted"] is not None]
    # per-layer readings on the host's clock: in a traced run only from
    # requests due 5 s or more after the profiler stopped
    settled = t_trace1 + 5.0 if ctx.trace else t_open
    late_ok = [r for r in ok if r["due"] >= settled]
    pct = traffic_gen.percentile
    ctx.log({"window": {"seconds": window_s, "requests_due": len(in_window),
                        "completed": len(ok), "shed": loop.shed,
                        "decode_tokens": tokens,
                        "backlog_at_close": summary["pending"]},
             "samples": {"ttft": len(ttft), "tpot": len(tpot),
                         "queue_wait": len(qwait)},
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
             "server_summary": {k: summary[k] for k in (
                 "decode_ticks", "host_roundtrips", "admitted", "evicted",
                 "preempted", "shed", "prefill_chunks", "pages_in_use",
                 "pool_pages") if k in summary}})
    checks = []
    kernel = counters.get("attention/flash_decode_paged", 0) > 0 and \
        counters.get("attention/fallback/kernel_rejected", 0) == 0
    checks.append(("paged_decode_kernel_ran", 0 if kernel else 1, 0, kernel))
    checks.append(("requests_not_completed", failed, 0, failed == 0))
    ctx.log({"counters": counters})
    # -- the reference, once the server is gone -------------------------
    t_ref = time.time()
    lim = mix["limits"]
    if ok:
        sample = pick_sample(ctx, ok, mix["check_requests"])
        gaps, tops = served_gaps(ctx, abstract, served_dtype, sample)
        widest = float(gaps.max())
        off = float((gaps > 0).mean())
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(tops), 1e-30))) - 7)
        ctx.log({"check": "reference", "requests": len(sample),
                 "served_tokens": int(gaps.size),
                 "widest_gap_bf16_ulps": float((gaps / ulp).max()),
                 "reference_seconds": time.time() - t_ref})
        checks.append(("served_logit_gap", widest, lim["served_logit_gap"],
                       widest <= lim["served_logit_gap"]))
        checks.append(("off_argmax_share", off, lim["off_argmax_share"],
                       off <= lim["off_argmax_share"]))
        if ctx.control:
            low, _ = served_gaps(ctx, abstract, served_dtype, sample,
                                 control=ctx.control)
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
    data = {"queue_wait_ms": [
                (r["admitted"] - r["due"]) * 1e3 for r in late_ok
                if r["admitted"] is not None]
            if not summary.get("preempted") else [],
            "ttft_ms": [(r["submitted"] - r["due"]) * 1e3
                        + r["completion"].ttft_ms for r in late_ok],
            "host_roundtrip_ms": [s * 1e3 for t, s in loop.roundtrips
                                  if settled <= t < t_close],
            "window_s": window_s, "heads": mcfg.num_attention_heads,
            "head_dim": mcfg.head_dim, "layers": mcfg.num_layers}
    if ctx.trace:
        data["kv_tokens_read"] = kv_tokens_read(
            loop, t_trace0, t_trace1)
    return {"metrics": metrics, "attempted": len(in_window),
            "failed": failed, "checks": checks,
            "memory_peak_bytes": memory, "data": data}


def kv_tokens_read(loop, t0, t1):
    """Sum, over the decode ticks that ended in [t0, t1) and the requests
    live in each, of the context length the tick attended to: a request
    with prompt P commits its j-th token (j = 0..) in its j-th tick, which
    reads P + j cached tokens. Its first tick is the one whose end time
    is its first-token time."""
    ticks = loop.tick_ends
    lo, hi = bisect.bisect_left(ticks, t0), bisect.bisect_left(ticks, t1)
    total = 0
    for r in loop.reqs.values():
        c = r["completion"]
        if c is None or c.ttft_ms is None:
            continue
        first = bisect.bisect_left(
            ticks, r["submitted"] + c.ttft_ms / 1e3 - 1e-4)
        j0, j1 = max(lo, first) - first, min(hi, first + len(c.tokens)) \
            - first
        if j1 > j0:
            p = len(r["prompt"])
            total += (j1 - j0) * p + (j0 + j1 - 1) * (j1 - j0) // 2
    return total
