"""Driver ``train_lm``: driver ``train``'s flow (the same ``Feed``, check
steps, warm-up, measured window and ``compare``, taken from
``drivers/train.py`` loaded as a module) for a language model whose
plain reference and FLOPs arithmetic are NAMED BY THE MIX
(``"reference": "<file under reference/>"``, ``"flops": "<file beside
flops.py>"``) and not written into the driver.

The reference module gives ``geometry(config, experts_held, vocab_lo)``,
``loss_and_grad(params, tokens, labels, mask, geo, rows_per_block,
precision)``, ``routing(params, tokens, geo)``, and the optimizer pieces
``gpt2_decoder.py`` has. The FLOPs module gives
``model_flops_per_token(cfg, seq)``.

What the run must also have been: the flash kernel at the latent
attention's two widths carried every attention (``attention/flash_mla``
> 0, ``attention/dense`` and ``kernel_rejected`` 0) and the ragged
grouped kernel every expert product (``moe/dropless`` > 0,
``moe/fallback/pallas_rejected`` 0).

``--rehearse`` lays the mix's own ``rehearse`` key (``config``,
``overrides``, ``traffic``) over configuration and mix:
``tests/rehearse.json`` is the GPT cells'.
"""

import gc
import importlib
import importlib.util
import math
import os
import sys
import time


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


base = _sibling("train")


def _rehearse(ctx):
    tiny = ctx.mix["rehearse"]
    ctx.config.update(tiny["config"])
    ctx.extra_overrides = list(tiny["overrides"])
    for key, value in tiny["traffic"].items():
        if isinstance(value, dict) and isinstance(ctx.mix.get(key), dict):
            ctx.mix[key].update(value)
        else:
            ctx.mix[key] = value


def model_of(config):
    """The sizes as the arithmetic and the reference want them: the
    published row with this chip's depth and shares laid over it."""
    return dict(config["published"],
                num_hidden_layers=config["num_hidden_layers"],
                experts_held=tuple(config["experts_held"]),
                vocab_held=tuple(config["vocab_held"]),
                **config.get("rehearsal_sizes", {}))


def program_steps(ctx, ref, engine, feed):
    """The check steps through ``Engine.fit`` (``drivers/train.py``'s,
    with the reference module a parameter)."""
    import jax
    from chipbench import weights
    n = ctx.mix["check_steps"]
    engine.logging_freq = 1
    feed.take(1)
    base._fit(engine, feed)
    jax.block_until_ready(engine.state)
    adam = [s for s in jax.tree.leaves(
        engine.state["opt_state"], is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")]
    b1 = float(ctx.optimizer["beta1"])
    grad_norms = jax.tree.map(lambda x: x / (1.0 - b1),
                              ref.leaf_norms(adam[0].mu))
    feed.take(n - 1)
    base._fit(engine, feed)
    jax.block_until_ready(engine.state)
    start = weights.seeded_params(
        engine.state["params"], ctx.seed,
        shardings=engine.state_shardings["params"])
    dparam_norms = jax.device_get(
        ref.leaf_diff_norms(engine.state["params"], start))
    del start
    engine.logging_freq = ctx.mix["logging_freq"]
    return jax.device_get(grad_norms), dparam_norms


def reference_steps(ctx, ref, geo, abstract, batches, precision="float32"):
    """The reference (or the control) over the same first steps."""
    import jax
    import jax.numpy as jnp
    from chipbench import weights
    f32 = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), abstract)
    sh = weights.spread(f32, ctx.devices)
    params = weights.seeded_params(f32, ctx.seed, shardings=sh)
    opt = ctx.optimizer
    state = ref.adamw_init(params)
    losses, grad_norms = [], None
    for k, (tokens, _pos, labels, mask) in enumerate(batches):
        t_k = time.time()
        if precision == "float32":
            # the reference's own routing of this step's batch, to set
            # beside the program's ``moe_held_picks`` of the same step
            flipped, held, picks = ref.routing(
                params, jnp.asarray(tokens), geo)
            ctx.log({"reference_routing": {
                "step": k + 1, "held_picks": held, "picks": picks,
                "flipped_by_bf16_input_share": flipped / max(picks, 1)}})
        loss, grads = ref.loss_and_grad(
            params, jnp.asarray(tokens), jnp.asarray(labels),
            jnp.asarray(mask), geo, ctx.mix["reference_rows_per_block"],
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


def leaf_gaps(abstract, side, refr):
    """``compare``'s gap (``drivers/train.py::_worst_gap``) of every leaf
    of ``side`` (the program or the control) against the reference, by
    name: the worst leaf, what the median leaf reads, and the leaves
    whose norm on ``side`` is exactly zero (they read 1.0)."""
    import jax
    import numpy as np
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(abstract)[0]]
    out = {}
    for what, a, b in (("grad_norm_gap", side[1], refr[1]),
                       ("dparam_norm_gap", side[2], refr[2])):
        p = np.array([float(x) for x in jax.tree.leaves(a)])
        r = np.array([float(x) for x in jax.tree.leaves(b)])
        gaps = np.abs(p - r) / np.maximum(
            np.maximum(r, float(np.median(r))), 1e-30)
        out[what] = {"worst_leaf": names[int(gaps.argmax())],
                     "worst": float(gaps.max()),
                     "median_leaf": float(np.median(gaps)),
                     "zero_norm_leaves": [n for n, x in zip(names, p)
                                          if x == 0.0]}
    return out


def run(ctx):
    """Check steps, warm-up, one measured window through ``Engine.fit``,
    then the reference over the same first steps."""
    if ctx.rehearse:
        _rehearse(ctx)
    yaml = os.path.join(ctx.root, ctx.config["yaml"])
    if not os.path.isfile(yaml):
        # a program that lacks this configuration (the parent of the PR
        # that adds it): fail at once, before any device work
        sys.stderr.write(f"chipbench: the program has no {yaml}\n")
        sys.exit(2)
    import jax
    mix = ctx.mix
    ref = importlib.import_module("chipbench.reference." + mix["reference"])
    flops_of = importlib.import_module("chipbench." + mix["flops"])
    model = model_of(ctx.config)
    geo = ref.geometry(model, model["experts_held"], model["vocab_held"][0])
    engine, feed, abstract, events_path = base.setup(ctx)
    prog_grad, prog_dparam = program_steps(ctx, ref, engine, feed)
    feed.take(mix["warm_steps"])
    base._fit(engine, feed)
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
    base._fit(engine, feed)
    t_end, step_end = feed.window
    jax.block_until_ready(engine.state)
    steps = step_end - step0
    window_s = t_end - t0
    tokens_per_step = ctx.global_batch * mix["seq"]
    tokens_per_s = steps * tokens_per_step / window_s
    memory = ctx.memory_peak()
    in_window, all_events = base._window_events(events_path, step0)
    fpt = flops_of.model_flops_per_token(model, mix["seq"])
    peak = ctx.peaks["bf16_flops_per_s"] if ctx.peaks else None
    ctx.log({"compiles_in_window": ctx.compiles_between(t0, t_end),
             "window": {"steps": steps, "seconds": window_s,
                        "tokens_per_step": tokens_per_step,
                        "first_step": step0 + 1},
             "mfu": tokens_per_s * fpt / (peak * ctx.chips)
             if peak else "not measured (no TPU)",
             "mfu_base": {"model_flops_per_token": fpt, "chips": ctx.chips,
                          "peak_flops_per_chip": peak}})
    # -- what the run must also have been ------------------------------
    c = base.counters()
    attn = (c.get("attention/flash_mla", 0) > 0
            and c.get("attention/dense", 0) == 0
            and c.get("attention/fallback/kernel_rejected", 0) == 0)
    moe = (c.get("moe/dropless", 0) > 0
           and c.get("moe/fallback/pallas_rejected", 0) == 0)
    checks = [("flash_mla_carried_the_step", 0 if attn else 1, 0, attn),
              ("moe_kernel_carried_the_step", 0 if moe else 1, 0, moe)]
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
    ctx.log({"counters": c, "mesh": dict(engine.mesh.shape),
             "logged_losses_in_window": [round(e["loss"], 4)
                                         for e in in_window],
             "routing_by_step": [
                 [e["step"], e.get("moe_held_picks"),
                  e.get("moe_load_max_over_mean"), e.get("moe_picks")]
                 for e in all_events]})
    kept, trace_span = feed.kept, feed.trace_span
    engine.state = None         # the reference needs the room
    del engine, feed
    gc.collect()
    # -- the reference, once the program's state is gone ----------------
    t_ref = time.time()
    refr = reference_steps(ctx, ref, geo, abstract, kept)
    prog = (prog_losses, prog_grad, prog_dparam)
    checks += base.compare(ctx, prog, refr)
    ctx.log({"reference_seconds": time.time() - t_ref,
             "leaves": leaf_gaps(abstract, prog, refr)})
    if ctx.control:
        low = reference_steps(ctx, ref, geo, abstract, kept, ctx.control)
        ctx.log({"control": ctx.control, "compared": [
            {"name": n, "value": v, "limit": limit, "ok": bool(ok)}
            for n, v, limit, ok in base.compare(ctx, low, refr, "control")],
            "leaves": leaf_gaps(abstract, low, refr)})
    lo, hi = model["experts_held"]
    return {
        "metrics": {"train_tokens_per_s": tokens_per_s},
        "attempted": steps, "failed": len(bad_losses),
        "checks": checks, "memory_peak_bytes": memory,
        "data": {"events": in_window,
                 # the records of the traced steps alone
                 "trace_events": [
                     e for e in in_window if trace_span
                     and trace_span[1] is not None
                     and trace_span[0] < e["step"] <= trace_span[1]],
                 "steps": steps,
                 "window_s": window_s,
                 "tokens_per_step": tokens_per_step,
                 "trace_steps": trace_span[1] - trace_span[0]
                 if trace_span and trace_span[1] is not None else None,
                 "model_flops_per_token": fpt,
                 "batch_per_chip": ctx.global_batch,
                 "seq": mix["seq"],
                 "experts_held_count": hi - lo,
                 "expert_layers": model["num_hidden_layers"]
                 - model["first_k_dense_replace"]},
    }
