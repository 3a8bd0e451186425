"""Driver ``train``: one pretraining job through the product path,
``cli.build_trainer`` -> ``Engine.fit``, for a fixed number of seconds.

The Engine is built once. The benchmark puts weights made from the seed
into its state, and feeds it through ``Feed``, a wrapper round the real
loader that (a) keeps the batches of the first steps for the reference,
(b) ends an ``Engine.fit`` call after a count of batches or at a deadline
and (c) brackets the traced steps. The same Engine object takes the check
steps, the warm-up and the measured window, through the same call and
the same feed:

  fit #1   1 step          -> per-leaf norm of the first gradient as the
                              optimizer got it (first AdamW moment / (1-b1))
  fit #2   check_steps - 1 -> per-leaf norm of the parameters' change
  fit #3   warm_steps      -> shapes warm, the loss at ``band_step``
  fit #4   the window      -> ends when the deadline has passed, closed by
                              ``block_until_ready`` on the state

After the window the Engine is freed and the float32 reference
(``chipbench/reference/gpt2_decoder.py``) follows the same first steps
on the kept batches from the same seeded weights.
"""

import gc
import json
import math
import os
import shutil
import time

import numpy as np


class Feed:
    """An iterator over the real loader that outlives ``Engine.fit``
    calls: each ``fit`` iterates it afresh and gets the NEXT batches,
    not the first ones again."""

    def __init__(self, loader, engine, keep):
        self._it = iter(loader)
        self._engine = engine
        self._keep = keep          # how many first batches to keep
        self.kept = []
        self.handed = 0
        self._left = 0
        self._deadline = None
        self._trace = None         # (start_at_handed, stop_at_handed, dir)
        self.window = None         # (t_end, steps_done) once closed
        self.trace_span = None     # (step_at_begin, step_at_end)
        # anything else the Engine asks of a loader is the loader's
        self.batch_sampler = getattr(loader, "batch_sampler", None)

    def __iter__(self):
        return self

    def take(self, n):
        self._left, self._deadline = n, None

    def until(self, deadline, trace=None):
        self._left, self._deadline, self._trace = None, deadline, trace

    def _sync_step(self):
        import jax
        jax.block_until_ready(self._engine.state)
        return int(self._engine.state["step"])

    def __next__(self):
        import jax

        from chipbench import trace_reduce
        if self._deadline is None:
            if self._left <= 0:
                raise StopIteration
            self._left -= 1
        else:
            if self.window is not None:
                raise StopIteration
            if self._trace is not None:
                start, stop, directory = self._trace
                if self.handed == start:
                    step = self._sync_step()
                    trace_reduce.start(directory)
                    trace_reduce.mark(trace_reduce.BEGIN_MARK)
                    self.trace_span = [step, None]
                elif self.handed == stop:
                    step = self._sync_step()
                    trace_reduce.mark(trace_reduce.END_MARK)
                    jax.profiler.stop_trace()
                    self.trace_span[1] = step
                    self._trace = None
            if time.time() >= self._deadline:
                steps = self._sync_step()
                self.window = (time.time(), steps)
                if self._trace is not None and self.trace_span and \
                        self.trace_span[1] is None:
                    jax.profiler.stop_trace()
                    self.trace_span = None
                raise StopIteration
        with jax.profiler.TraceAnnotation("data/loader_next"):
            batch = next(self._it)
        if len(self.kept) < self._keep:
            self.kept.append([np.array(x) for x in batch])
        self.handed += 1
        return batch


def _argv(ctx, out, corpus):
    cfg, mix = ctx.config, ctx.mix
    overrides = list(cfg.get("overrides", [])) + list(mix["overrides"]) + [
        f"Global.local_batch_size={mix['local_batch_size']}",
        f"Global.micro_batch_size={mix['micro_batch_size']}",
        f"Data.Train.dataset.max_seq_len={mix['seq']}",
        f"Data.Eval.dataset.max_seq_len={mix['seq']}",
        f"Engine.max_steps={mix['max_steps']}",
        f"Engine.logging_freq={mix['logging_freq']}",
        "Engine.eval_freq=100000000",
        "Engine.save_load.save_steps=100000000",
        "Engine.save_load.save_epoch=100000000",
        f"Engine.save_load.output_dir={out}",
        f"Data.Train.dataset.input_dir={corpus}",
        f"Data.Eval.dataset.input_dir={corpus}",
        "Telemetry.enable=True",
    ] + list(ctx.extra_overrides)
    return ["-c", os.path.join(ctx.root, cfg["yaml"])] + [
        x for o in overrides for x in ("-o", o)]


def _window_events(path, first_step):
    """``step_window`` records of the flight recorder after
    ``first_step``."""
    out = []
    with open(path) as f:
        for line in f:
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            if ev.get("event") == "step_window":
                out.append(ev)
    return [e for e in out if e["step"] > first_step], out


def _fit(engine, feed):
    from paddlefleetx_tpu.parallel.mesh import set_mesh
    set_mesh(engine.mesh)       # fit() clears the process-wide mesh
    engine.fit(epoch=1, train_data_loader=feed, valid_data_loader=None)


def _worst_gap(prog, ref):
    """Worst leaf of |program's norm - reference's norm| over the larger
    of the reference's norm of that leaf and of the median leaf."""
    import jax
    p = np.array([float(x) for x in jax.tree.leaves(prog)])
    r = np.array([float(x) for x in jax.tree.leaves(ref)])
    floor = float(np.median(r))
    gaps = np.abs(p - r) / np.maximum(np.maximum(r, floor), 1e-30)
    return float(gaps.max()), int(gaps.argmax())


def program_steps(ctx, engine, feed):
    """The check steps through ``Engine.fit``: per-step losses come from
    the flight recorder, the first gradient from the AdamW state after
    one step, the parameters' change after ``check_steps``."""
    import jax
    from chipbench import weights
    from chipbench.reference import gpt2_decoder as ref
    n = ctx.mix["check_steps"]
    engine.logging_freq = 1
    feed.take(1)
    _fit(engine, feed)
    jax.block_until_ready(engine.state)
    adam = [s for s in jax.tree.leaves(
        engine.state["opt_state"], is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")]
    b1 = float(ctx.optimizer["beta1"])
    grad_norms = jax.tree.map(lambda x: x / (1.0 - b1),
                              ref.leaf_norms(adam[0].mu))
    feed.take(n - 1)
    _fit(engine, feed)
    jax.block_until_ready(engine.state)
    start = weights.seeded_params(
        engine.state["params"], ctx.seed,
        shardings=engine.state_shardings["params"])
    dparam_norms = ref.leaf_diff_norms(engine.state["params"], start)
    dparam_norms = jax.device_get(dparam_norms)
    del start
    engine.logging_freq = ctx.mix["logging_freq"]
    return jax.device_get(grad_norms), dparam_norms


def reference_steps(ctx, abstract, batches, devices, precision="float32"):
    """The reference (or, at another ``precision``, the control) over
    the same first steps: ``(losses, grad leaf norms, dparam leaf
    norms)``."""
    import jax
    import jax.numpy as jnp
    from chipbench import weights
    from chipbench.reference import gpt2_decoder as ref
    f32 = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), abstract)
    sh = weights.spread(f32, devices)
    params = weights.seeded_params(f32, ctx.seed, shardings=sh)
    opt = ctx.optimizer
    state = ref.adamw_init(params)
    losses, grad_norms = [], None
    for k, (tokens, _pos, labels, mask) in enumerate(batches):
        t_k = time.time()
        loss, grads = ref.loss_and_grad(
            params, jnp.asarray(tokens), jnp.asarray(labels),
            jnp.asarray(mask), ctx.mix["reference_rows_per_block"],
            precision)
        losses.append(float(loss))
        grads, _ = ref.clip_by_global_norm(grads, opt["clip_norm"])
        if k == 0:
            grad_norms = jax.device_get(ref.leaf_norms(grads))
        params, state = ref.adamw_update(params, grads, state, opt)
        del grads
        ctx.log({"reference_step": k + 1, "precision": precision,
                 "seconds": time.time() - t_k})
    start = weights.seeded_params(f32, ctx.seed, shardings=sh)
    dparam = jax.device_get(ref.leaf_diff_norms(params, start))
    return losses, grad_norms, dparam


def optimizer_of(cfg):
    o = cfg.Optimizer
    return {"beta1": float(o.beta1), "beta2": float(o.beta2),
            "epsilon": float(o.epsilon),
            "weight_decay": float(o.weight_decay),
            "clip_norm": float((o.get("grad_clip") or {}).get(
                "clip_norm", 0.0) or 0.0),
            "max_lr": float(o.lr.max_lr), "min_lr": float(o.lr.min_lr),
            "warmup_rate": float(o.lr.warmup_rate),
            "decay_steps": float(o.lr.decay_steps)}


def compare(ctx, prog, refr, who="reference"):
    """The numbers compared, each beside its limit."""
    lim = ctx.mix["limits"]
    p_losses, p_grad, p_dparam = prog
    r_losses, r_grad, r_dparam = refr
    checks = []
    loss_gap = max(abs(a - b) for a, b in zip(p_losses, r_losses))
    checks.append(("loss_gap", loss_gap, lim["loss_gap"],
                   loss_gap <= lim["loss_gap"]))
    g, gi = _worst_gap(p_grad, r_grad)
    checks.append(("grad_norm_gap", g, lim["grad_norm_gap"],
                   g <= lim["grad_norm_gap"]))
    d, di = _worst_gap(p_dparam, r_dparam)
    checks.append(("dparam_norm_gap", d, lim["dparam_norm_gap"],
                   d <= lim["dparam_norm_gap"]))
    ctx.log({"check": who, "program_losses": p_losses,
             "reference_losses": r_losses, "worst_grad_leaf": gi,
             "worst_dparam_leaf": di})
    return checks


def setup(ctx):
    """Corpus, Engine, seeded weights, feed."""
    import jax
    from chipbench import traffic_gen, weights
    from paddlefleetx_tpu import cli
    from paddlefleetx_tpu.observability import metrics
    mix = ctx.mix
    out = os.path.join(ctx.workdir, "train")
    corpus = os.path.join(ctx.workdir, "corpus")
    shutil.rmtree(ctx.workdir, ignore_errors=True)
    c = mix["corpus"]
    traffic_gen.make_corpus(corpus, ctx.config["vocab_size"], c["docs"],
                            c["doc_len"], ctx.seed, c["zipf"])
    metrics.set_enabled(True)
    metrics.get_registry().reset()
    cfg, engine, loader, _valid = cli.build_trainer(
        _argv(ctx, out, corpus), devices=ctx.devices)
    ctx.optimizer = optimizer_of(cfg)
    ctx.global_batch = int(cfg.Global.global_batch_size)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        engine.state["params"])
    engine.state = dict(engine.state, params=weights.seeded_params(
        abstract, ctx.seed, shardings=engine.state_shardings["params"]))
    gc.collect()
    feed = Feed(loader, engine, keep=mix["check_steps"])
    return engine, feed, abstract, os.path.join(out, "events.jsonl")


def counters():
    from paddlefleetx_tpu.observability import metrics
    return {k: int(v) for k, v in
            metrics.get_registry().snapshot()["counters"].items()
            if k.split("/")[0] in ("attention", "moe", "quant", "lora")}


def spread_over(state, n_devices):
    """Leaves of the state that do not span every chip, and the bytes
    each chip holds (``chip_smoke.py::shard_report``)."""
    import jax
    per_device, bad = {}, []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        for s in leaf.addressable_shards:
            per_device[s.device.id] = per_device.get(s.device.id, 0) \
                + s.data.nbytes
        if len(leaf.sharding.device_set) != n_devices:
            bad.append(jax.tree_util.keystr(path))
    return per_device, bad


def run(ctx):
    """Check steps, warm-up, one measured window through ``Engine.fit``,
    then the reference over the same first steps."""
    import jax
    from chipbench import flops
    mix = ctx.mix
    engine, feed, abstract, events_path = setup(ctx)
    prog_grad, prog_dparam = program_steps(ctx, engine, feed)
    feed.take(mix["warm_steps"])
    _fit(engine, feed)
    jax.block_until_ready(engine.state)
    step0 = int(engine.state["step"])
    trace = None
    if ctx.trace:
        a = mix["trace_after_steps"]
        trace = (feed.handed + a, feed.handed + a + mix["trace_steps"],
                 ctx.trace_dir)
    # -- the measured window ------------------------------------------
    t0 = time.time()
    ctx.setup_done(t0)
    feed.until(t0 + ctx.seconds, trace)
    _fit(engine, feed)
    t_end, step_end = feed.window
    jax.block_until_ready(engine.state)
    steps = step_end - step0
    window_s = t_end - t0
    tokens_per_step = ctx.global_batch * mix["seq"]
    tokens_per_s = steps * tokens_per_step / window_s
    memory = ctx.memory_peak()
    in_window, all_events = _window_events(events_path, step0)
    geo = ctx.config
    fpt = flops.model_flops_per_token(geo["num_layers"], geo["hidden_size"],
                                      geo["vocab_size"], mix["seq"])
    ctx.log({"compiles_in_window": ctx.compiles_between(t0, t_end),
             "window": {"steps": steps, "seconds": window_s,
                        "tokens_per_step": tokens_per_step,
                        "first_step": step0 + 1},
             "mfu": flops.mfu(tokens_per_s, fpt,
                              ctx.peaks["bf16_flops_per_s"], ctx.chips)
             if ctx.peaks else "not measured (no TPU)",
             "mfu_base": {"model_flops_per_token": fpt, "chips": ctx.chips,
                          "peak_flops_per_chip":
                          ctx.peaks["bf16_flops_per_s"]
                          if ctx.peaks else None}})
    # -- what the run must also have been ------------------------------
    checks = []
    c = counters()
    mesh_shape = dict(engine.mesh.shape)
    # counters tick per traced layer. Where the batch is split over
    # chips the Engine's abstract init traces a batch-1 sample that
    # cannot divide it, so that trace, and no other, takes the counted
    # XLA path (mesh_sharded -> dense). A train step gone dense would
    # leave no flash trace, or fewer than two (forward, backward) to
    # each dense one.
    dense = c.get("attention/dense", 0)
    lowered = (c.get("attention/flash", 0) > 0
               and dense == c.get("attention/fallback/mesh_sharded", 0)
               and c.get("attention/flash", 0) >= 2 * dense
               and c.get("attention/fallback/kernel_rejected", 0) == 0)
    checks.append(("flash_carried_the_step", 0 if lowered else 1, 0,
                   lowered))
    if ctx.chips > 1:
        per_device, bad = spread_over(engine.state, ctx.chips)
        checks.append(("state_leaves_not_on_every_chip", len(bad), 0,
                       not bad))
        ctx.log({"state_bytes_per_device": per_device})
    by_step = {e["step"]: e["loss"] for e in all_events}
    prog_losses = [by_step.get(k + 1, float("nan"))
                   for k in range(mix["check_steps"])]
    bad_losses = [e["step"] for e in in_window
                  if not math.isfinite(e["loss"])]
    band = by_step.get(mix["band_step"], float("nan"))
    lim = mix["limits"]
    checks.append(("band_loss", band,
                   [lim["band_loss_lo"], lim["band_loss_hi"]],
                   lim["band_loss_lo"] <= band <= lim["band_loss_hi"]))
    ctx.log({"counters": c, "mesh": mesh_shape,
             "logged_losses_in_window": [round(e["loss"], 4)
                                         for e in in_window]})
    kept, trace_span = feed.kept, feed.trace_span
    engine.state = None         # the reference needs the room
    del engine, feed
    gc.collect()
    # -- the reference, once the program's state is gone ----------------
    t_ref = time.time()
    refr = reference_steps(ctx, abstract, kept, ctx.devices)
    checks += compare(ctx, (prog_losses, prog_grad, prog_dparam), refr)
    ctx.log({"reference_seconds": time.time() - t_ref})
    if ctx.control:
        low = reference_steps(ctx, abstract, kept, ctx.devices, ctx.control)
        ctx.log({"control": ctx.control, "compared": [
            {"name": n, "value": v, "limit": lim, "ok": bool(ok)}
            for n, v, lim, ok in compare(ctx, low, refr, "control")]})
    return {
        "metrics": {"train_tokens_per_s": tokens_per_s},
        "attempted": steps, "failed": len(bad_losses),
        "checks": checks, "memory_peak_bytes": memory,
        "data": {"events": in_window, "steps": steps,
                 "window_s": window_s,
                 "tokens_per_step": tokens_per_step,
                 "trace_steps": trace_span[1] - trace_span[0]
                 if trace_span and trace_span[1] is not None else None,
                 "model_flops_per_token": fpt,
                 "batch_per_chip": ctx.global_batch // max(
                     1, mesh_shape.get("dp", 1) * mesh_shape.get("fsdp", 1)),
                 "heads_per_chip": geo["num_attention_heads"] // max(
                     1, mesh_shape.get("mp", 1)),
                 "seq": mix["seq"]},
    }
