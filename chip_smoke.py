#!/usr/bin/env python3
"""Chip smoke: the trainer and the slot server, once, on the TPU.

    python chip_smoke.py               one chip (what the driver runs)
    python chip_smoke.py --multichip   four chips: mp2 x fsdp2, pp2 x mp2
                                       vs one device
    python chip_smoke.py --rehearse    CPU, tiny sizes, interpret mode (tests)

The quickest proof that the system still starts on the chip. One
process; GPT-345M at its published width and depth
(configs/nlp/gpt/pretrain_gpt_345M_single_card.yaml), random weights
from ``--seed``, a corpus generated here. Phases:

  train           ``cli.train_main`` on the shipped recipe (dropout 0.1
                  -> dense attention), one checkpoint save, a second
                  Engine that restores it and takes one more step
  train_flash     the same with both dropouts 0: the flash fwd/bwd
                  kernels carry a real Engine step
  serve           ``GenerationServer`` (paged, page 128) vs ``generate()``
  serve_spec      the same requests, n-gram speculative decoding
  serve_loop      the same requests, device_loop_ticks > 1
  multichip       (--multichip only) train_main on mp2 x ZeRO-3 fsdp2
                  + sequence parallel vs the same job on one device
  multichip_pp    (--multichip only) the same for pp2 x mp2, 1F1B

Each phase prints one JSON line; any failed check, exception, non-TPU
device (without --rehearse) or rejected kernel is a nonzero exit with
no final line. The final line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
Timings printed here are set-up/compile or "smoke, not a measurement".
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
YAML = os.path.join(HERE, "configs", "nlp", "gpt",
                    "pretrain_gpt_345M_single_card.yaml")

#: --rehearse only: the same control flow at a size the CPU and the
#: Pallas interpreter finish in seconds (s=128 is the flash kernel's
#: smallest tile-aligned sequence, d=64 its smallest head)
TINY = ["Model.hidden_size=128", "Model.num_layers=2",
        "Model.num_attention_heads=2", "Model.ffn_hidden_size=512",
        "Model.vocab_size=512", "Model.max_position_embeddings=256",
        "Data.Train.dataset.max_seq_len=128",
        "Data.Eval.dataset.max_seq_len=128",
        "Global.local_batch_size=4", "Global.micro_batch_size=4"]

#: the shipped schedule warms up over 3,600 steps (lr ~1e-8 at step
#: 1): no loss could be seen to fall in a handful of steps. The smoke
#: keeps the optimizer and swaps in a schedule that reaches a working
#: lr at once; nothing else of the recipe is overridden.
LR = ["Optimizer.lr.decay_steps=1000", "Optimizer.lr.warmup_rate=0.002",
      "Optimizer.lr.max_lr=3.0e-4"]

#: sizes of the run: the same in --rehearse (TINY shrinks the model,
#: not the control flow). Constants, not options: a start-up proof
#: that the command line can shrink proves less than it prints.
TRAIN_STEPS = 6
FLASH_STEPS = 4
MULTICHIP_STEPS = 4
SLOTS = 4
DEC_LEN = 12
#: prompt lengths as shares of the room left beside DEC_LEN: one-chunk
#: and multi-chunk prefills, a tiny prompt
PROMPT_SHARES = (0.02, 0.45, 0.1, 0.3, 0.01, 0.2)

#: two next-token candidates count as a numerical tie between bf16
#: lowerings when both logits are within this many bf16 ulps of the
#: top one, and at most MAX_TIED_ROWS rows of a serving leg may leave
#: ``generate()``'s row at such a tie — what the chip showed (one row
#: per leg, 1 ulp). --rehearse (float32 math) tolerates none.
TIE_ULPS = 2
MAX_TIED_ROWS = 1
#: per-step loss agreement, 4 chips vs 1 device (bf16 compute, mp-split
#: reductions in another order; losses here are ~10)
LOSS_TOL = 0.05


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def emit(phase, t0, **fields):
    print(json.dumps({"phase": phase,
                      "seconds": round(time.time() - t0, 1), **fields}),
          flush=True)


def make_corpus(directory, vocab, n_docs, doc_len, seed):
    """``smoke_ids.npy`` + ``smoke_idx.npz``: a Zipf unigram stream —
    learnable at once (the loss falls from ln V toward the unigram
    entropy as soon as the output bias moves)."""
    import numpy as np
    os.makedirs(directory, exist_ok=True)
    rng = np.random.default_rng(seed)
    ranks = rng.permutation(vocab - 1)
    p = 1.0 / np.arange(1, vocab) ** 1.1
    ids = ranks[rng.choice(vocab - 1, size=n_docs * doc_len,
                           p=p / p.sum())].astype(np.int32)
    lens = np.full(n_docs, doc_len, np.int32)
    ids[np.cumsum(lens) - 1] = vocab - 1          # document ends
    np.save(os.path.join(directory, "smoke_ids.npy"), ids)
    np.savez(os.path.join(directory, "smoke_idx.npz"), lens=lens)


def cache_entries(cache_dir):
    """Names in the compile-cache directory (empty when absent)."""
    try:
        return set(os.listdir(cache_dir))
    except OSError:
        return set()


def counters():
    from paddlefleetx_tpu.observability import metrics
    return {k: int(v) for k, v in
            metrics.get_registry().snapshot()["counters"].items()
            if k.split("/")[0] in ("attention", "moe", "quant", "lora")}


def reset_counters():
    from paddlefleetx_tpu.observability import metrics
    metrics.set_enabled(True)
    metrics.get_registry().reset()


def fingerprint(params):
    """Exact, order-free: the wrapping uint32 sum of every parameter's
    bit pattern, computed where the parameters live."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(p):
        return sum(jnp.sum(jax.lax.bitcast_convert_type(
            x.astype(jnp.float32), jnp.uint32), dtype=jnp.uint32)
            for x in jax.tree.leaves(p))
    return int(f(params))


def step_losses(events_path):
    from paddlefleetx_tpu.observability.recorder import read_events
    return [e["loss"] for e in read_events(events_path)
            if e.get("event") == "step_window"]


def train_argv(out, corpus, steps, extra, save=False):
    return ["-c", YAML] + [x for o in [
        f"Engine.max_steps={steps}", "Engine.logging_freq=1",
        "Engine.eval_freq=1000000",
        f"Engine.save_load.save_steps={steps if save else 1000000}",
        "Engine.save_load.save_epoch=1000000",
        f"Engine.save_load.output_dir={out}",
        f"Data.Train.dataset.input_dir={corpus}",
        f"Data.Eval.dataset.input_dir={corpus}",
        "Telemetry.enable=True", *LR, *extra] for x in ("-o", o)]


def run_fit(argv, out, platform, devices=None):
    """One ``train_main`` run; returns (engine, losses, compile_s)."""
    import math

    from paddlefleetx_tpu import cli
    events = os.path.join(out, "events.jsonl")
    if os.path.exists(events):
        os.remove(events)
    engine = cli.train_main(argv, devices=devices)
    losses = step_losses(events)
    check(losses and all(math.isfinite(x) for x in losses),
          f"non-finite or missing losses: {losses}")
    on = {d.platform for d in engine.state["step"].devices()}
    check(on == {platform}, f"step ran on {on}, expected {platform}")
    return engine, losses, round(engine._time_buckets["compile"], 1)


def phase_train(work, corpus, extra, platform, cache_dir):
    """The shipped recipe through ``train_main``: train, save, restore
    in a second Engine, one more step."""
    from paddlefleetx_tpu import cli
    from paddlefleetx_tpu.data.data_tools import index_helpers
    t0 = time.time()
    n = TRAIN_STEPS
    out = os.path.join(work, "train")
    reset_counters()
    engine, losses, compile_s = run_fit(
        train_argv(out, corpus, n, extra, save=True), out, platform)
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    step, fp = int(engine.state["step"]), fingerprint(
        engine.state["params"])
    check(step == n, f"trained {step} steps, expected {n}")
    del engine
    gc.collect()
    # a second Engine restores the checkpoint and takes one more step
    entries = cache_entries(cache_dir)
    cfg, engine, train_loader, valid_loader = cli.build_trainer(
        train_argv(out, corpus, n + 1, extra
                   + [f"Engine.save_load.ckpt_dir={out}"]))
    check(int(engine.state["step"]) == n,
          f"restored step {int(engine.state['step'])} != {n}")
    check(fingerprint(engine.state["params"]) == fp,
          "restored parameters differ from the saved ones")
    engine.fit(epoch=1, train_data_loader=train_loader,
               valid_data_loader=valid_loader)
    # both Engines append to the one flight record of this output dir
    resumed = step_losses(os.path.join(out, "events.jsonl"))[n:]
    check(int(engine.state["step"]) == n + 1 and len(resumed) == 1,
          f"resume took {len(resumed)} steps")
    check(resumed[0] < losses[0],
          f"resumed loss {resumed[0]} not below the first {losses[0]}")
    c = counters()
    mcfg = engine.module.model.config
    if mcfg.attention_probs_dropout_prob > 0:
        # no dropout_cert.json in this tree: the documented lowering
        # for the shipped recipe is the dense path, by the gate
        check(c.get("attention/fallback/dropout_gate_off", 0) > 0
              and c.get("attention/dense", 0) > 0,
              f"expected the dropout gate's dense lowering: {c}")
    check(c.get("attention/fallback/kernel_rejected", 0) == 0,
          f"a kernel was rejected: {c}")
    emit("train", t0, compile_seconds=compile_s,
         resume_compile_seconds=round(
             engine._time_buckets["compile"], 1),
         resume_new_cache_entries=len(cache_entries(cache_dir)
                                      - entries),
         steps=n, losses=[round(x, 4) for x in losses],
         resumed_step=n + 1, resumed_loss=round(resumed[0], 4),
         param_fingerprint=fp,
         index_builder="native" if index_helpers.have_native()
         else "python", counters=c,
         checked="finite losses, last < first, restored step + param "
                 "fingerprint, one more step, platform, lowering")
    del engine
    gc.collect()


def phase_train_flash(work, corpus, extra, platform):
    """The same recipe with both dropouts 0: flash fwd/bwd carry it."""
    t0 = time.time()
    out = os.path.join(work, "train_flash")
    reset_counters()
    engine, losses, compile_s = run_fit(
        train_argv(out, corpus, FLASH_STEPS, extra + [
            "Model.hidden_dropout_prob=0.0",
            "Model.attention_probs_dropout_prob=0.0"]),
        out, platform)
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    c = counters()
    check(c.get("attention/flash", 0) > 0
          and c.get("attention/dense", 0) == 0
          and c.get("attention/fallback/kernel_rejected", 0) == 0,
          f"flash did not carry the step: {c}")
    emit("train_flash", t0, compile_seconds=compile_s,
         steps=FLASH_STEPS, losses=[round(x, 4) for x in losses],
         counters=c,
         checked="finite losses, last < first, attention/flash > 0, "
                 "no dense, no kernel_rejected")
    del engine
    gc.collect()


# -- serving -----------------------------------------------------------

def serve_setup(seed, extra):
    """Model, seeded random bf16-compute params, mixed-length prompts
    and the greedy generation config of the serving phases."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig
    from paddlefleetx_tpu.utils.config import get_config
    cfg = get_config(YAML, overrides=extra, nranks=1)
    mcfg = GPTConfig.from_config(cfg)
    model = GPTForPretraining(mcfg)
    params = nn.meta.unbox(jax.jit(model.init)(
        {"params": jax.random.key(seed)},
        jnp.zeros((1, 128), jnp.int32))["params"])
    rng = np.random.default_rng(seed)
    room = mcfg.max_position_embeddings - DEC_LEN
    lengths = [max(1, int(room * f)) for f in PROMPT_SHARES]
    prompts = [rng.integers(0, mcfg.vocab_size - 2, n).tolist()
               for n in lengths]
    eos = mcfg.vocab_size - 1
    gen = dict(max_dec_len=DEC_LEN,
               decode_strategy="greedy_search", eos_token_id=eos,
               pad_token_id=eos)
    return model, params, prompts, GenerationConfig(**gen)


def lockstep_rows(model, params, prompts, gen_cfg):
    """The reference rows: ``generate()`` over the left-padded batch,
    each cut at its EOS (inclusive)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddlefleetx_tpu.models.gpt.generation import (
        generate, left_pad_batch,
    )
    ids, mask = left_pad_batch(prompts, gen_cfg.pad_token_id)
    out = np.asarray(generate(model, params, jnp.asarray(ids),
                              jnp.asarray(mask), jax.random.key(0),
                              gen_cfg))
    rows = []
    for row in out.tolist():
        if gen_cfg.eos_token_id in row:
            row = row[:row.index(gen_cfg.eos_token_id) + 1]
        rows.append(row)
    return rows


class TieJudge:
    """Token-for-token comparison that knows what bf16 can and cannot
    promise: two lowerings of the same math (lockstep prefill + dense
    cache vs chunked prefill + paged kernels) may pick different
    argmaxes only where the model's own top logits are a numerical
    tie (``TIE_ULPS``). Where a served row leaves the lockstep row, a
    plain teacher-forced forward over the SERVED row must show that
    tie at the first divergent token and must keep every later served
    token within the same bound of its position's top logit; at most
    ``MAX_TIED_ROWS`` rows of a leg may do so. Anything else is a
    wrong token."""

    def __init__(self, model, params, pad, exact):
        import jax
        self.model, self.params, self.pad = model, params, pad
        self.exact = exact
        self.width = model.config.max_position_embeddings
        self._fwd = jax.jit(lambda p, ids: model.apply(
            {"params": p}, ids, deterministic=True))

    @staticmethod
    def _behind_top_ulps(logits, token):
        """How many bf16 ulps (at the top logit's magnitude)
        ``token``'s logit lies under the row's top one."""
        import math
        top = float(logits.max())
        ulp = 2.0 ** (math.floor(math.log2(max(abs(top), 2.0 ** -100)))
                      - 7)
        return (top - float(logits[token])) / ulp

    def compare(self, prompts, served, ref):
        """``(exact rows, ties)``; raises on a wrong token."""
        import jax.numpy as jnp
        import numpy as np
        n_exact, ties = 0, []
        for i, (p, got, want) in enumerate(zip(prompts, served, ref)):
            if got == want:
                n_exact += 1
                continue
            check(not self.exact,
                  f"request {i}: served {got} != lockstep {want}")
            t = next((j for j, (a, b) in enumerate(zip(got, want))
                      if a != b), None)
            check(t is not None,
                  f"request {i}: lengths differ with equal tokens: "
                  f"{got} vs {want}")
            ids = np.full((1, self.width), self.pad, np.int32)
            ids[0, :len(p) + len(got)] = list(p) + got
            # row len(p)-1+j of the causal forward predicts got[j]
            logits = np.asarray(self._fwd(
                self.params, jnp.asarray(ids))[0], np.float32)[
                    len(p) - 1:len(p) - 1 + len(got)]
            at = {"served": self._behind_top_ulps(logits[t], got[t]),
                  "lockstep": self._behind_top_ulps(logits[t], want[t])}
            check(max(at.values()) <= TIE_ULPS,
                  f"request {i} token {t}: served {got[t]} vs "
                  f"lockstep {want[t]} lie {at} bf16 ulps under the "
                  f"top logit: no tie")
            rest = [self._behind_top_ulps(logits[j], got[j])
                    for j in range(t + 1, len(got))]
            check(all(x <= TIE_ULPS for x in rest),
                  f"request {i}: after the tie at token {t} the served "
                  f"row leaves the teacher-forced top logit by {rest} "
                  f"ulps")
            ties.append({"request": i, "token": t,
                         "ulps_under_top": at,
                         "rest_tokens": len(rest),
                         "rest_off_argmax": sum(x > 0 for x in rest)})
        check(len(ties) <= MAX_TIED_ROWS,
              f"{len(ties)} rows left the lockstep rows: {ties}")
        return n_exact, ties


def phase_serve(name, seed, setup, ref, judge, spec=False,
                loop_ticks=1, same_as=None):
    """One paged ``GenerationServer`` answering the requests through
    ``submit``/``step`` (two at once, the rest admitted mid-run).
    Returns the served rows. ``same_as`` names an earlier leg and its
    rows where this leg runs the same lowerings: then the rows must be
    identical, tie or no tie."""
    import dataclasses

    import jax

    from paddlefleetx_tpu.core.serving import GenerationServer
    t0 = time.time()
    model, params, prompts, gen_cfg = setup
    if spec:
        gen_cfg = dataclasses.replace(gen_cfg, spec_method="ngram",
                                      spec_tokens=4)
    reset_counters()
    pages = model.config.cache_capacity // 128
    srv = GenerationServer(model, params, gen_cfg,
                           num_slots=SLOTS, page_size=128,
                           prefill_chunk_pages=2 if pages % 2 == 0
                           else 1,
                           rng=jax.random.key(seed + 1),
                           device_loop_ticks=loop_ticks)
    try:
        done, ids = {}, [srv.submit(p) for p in prompts[:2]]
        for _ in range(3):                  # decode a little, then
            for c in srv.step():            # admit the rest mid-run
                done[c.request_id] = c
        ids += [srv.submit(p) for p in prompts[2:]]
        guard = 0
        while srv.work_pending():
            for c in srv.step():
                done[c.request_id] = c
            guard += 1
            check(guard < 10000, "server did not drain")
        summary = srv.summary()
    finally:
        srv.close()
    check(set(done) == set(ids), "a request never completed")
    served = [done[i].tokens for i in ids]
    n_exact, ties = judge.compare(prompts, served, ref)
    if same_as is not None:
        check(served == same_as[1],
              f"{name} and {same_as[0]} run the same kernels but "
              f"served different tokens: {served} vs {same_as[1]}")
    c = counters()
    kernel = "attention/flash_decode_paged" + ("_verify" if spec
                                               else "")
    # chunked prefill attends a page-sized chunk over gathered pages
    # by design: it counts attention/fallback/kv_cache_layout and
    # attention/dense together. Every dense lowering must be one of
    # those — none may come from a decode tick.
    check(c.get(kernel, 0) > 0
          and c.get("attention/fallback/kernel_rejected", 0) == 0
          and c.get("attention/fallback/mesh_sharded", 0) == 0
          and c.get("attention/dense", 0)
          == c.get("attention/fallback/kv_cache_layout", 0),
          f"decode did not run on {kernel}: {c}")
    check(summary["decode_tokens"] >= sum(len(r) for r in served)
          - len(served), f"server committed too few tokens: {summary}")
    if spec:
        check(summary.get("spec_drafted", 0) > 0,
              f"no speculative tick ran: {summary}")
    if loop_ticks > 1:
        check(summary["host_roundtrips"] < summary["decode_ticks"],
              f"device loop saved no round-trip: {summary}")
    emit(name, t0, requests=len(prompts),
         prompt_lengths=[len(p) for p in prompts],
         tokens=sum(len(r) for r in served), exact_rows=n_exact,
         bf16_ties=ties, decode_ticks=summary["decode_ticks"],
         host_roundtrips=summary["host_roundtrips"], counters=c,
         note="seconds include compiles; smoke, not a measurement",
         checked=f"tokens vs generate() (<= {MAX_TIED_ROWS} row "
                 f"leaving at a tie <= {TIE_ULPS} bf16 ulps, its rest "
                 f"teacher-forced)"
                 + (f", rows identical to {same_as[0]}'s" if same_as
                    else "")
                 + f", {kernel} > 0, no kernel_rejected, dense == "
                 f"chunked prefill's kv_cache_layout fallback")
    return served


# -- four chips --------------------------------------------------------

def shard_report(state):
    """Bytes each device holds of the train state, and the leaves that
    fail to span every device their sharding names."""
    per_device, bad = {}, []
    import jax
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        n_dev = len(leaf.sharding.device_set)
        for s in leaf.addressable_shards:
            per_device[s.device.id] = per_device.get(s.device.id, 0) \
                + s.data.nbytes
        if n_dev != len(jax.devices()):
            bad.append(jax.tree_util.keystr(path))
    return per_device, bad


#: the four-chip legs: (phase, topology, overrides on the four chips,
#: overrides of the same job on one device of the host, mesh axes the
#: Engine must build). Same seed, corpus and global batch of 8 on both
#: sides; dropout 0 so the flash kernels carry the step.
MESH_LEGS = (
    ("multichip", "mp2 x fsdp2 (ZeRO-3) + sp",
     ["Distributed.mp_degree=2", "Distributed.dp_degree=1",
      "Distributed.sharding.sharding_degree=2",
      "Distributed.sharding.sharding_stage=3",
      "Model.sequence_parallel=True",
      "Global.local_batch_size=4", "Global.micro_batch_size=4"],
     ["Global.local_batch_size=8", "Global.micro_batch_size=8"],
     {"mp": 2, "fsdp": 2}),
    ("multichip_pp", "pp2 x mp2 (1F1B, 4 microbatches, scanned layers)",
     ["Distributed.pp_degree=2", "Distributed.mp_degree=2",
      "Distributed.dp_degree=1", "Model.scan_layers=True",
      "Global.local_batch_size=8", "Global.micro_batch_size=2",
      "Engine.accumulate_steps=4"],
     ["Model.scan_layers=True", "Global.local_batch_size=8",
      "Global.micro_batch_size=2", "Engine.accumulate_steps=4"],
     {"pp": 2, "mp": 2}),
)


def phase_mesh_leg(leg, work, corpus, extra, platform):
    """``train_main`` on one four-device topology, against the same
    job on one device of the host."""
    import jax
    name, topology, on_four, on_one, axes = leg
    t0 = time.time()
    n = MULTICHIP_STEPS
    flash = ["Model.hidden_dropout_prob=0.0",
             "Model.attention_probs_dropout_prob=0.0",
             "Global.global_batch_size=8"]
    out4 = os.path.join(work, name + "_4")
    reset_counters()
    engine, losses4, compile4 = run_fit(
        train_argv(out4, corpus, n, extra + flash + on_four), out4,
        platform)
    shape = dict(engine.mesh.shape)
    check(all(shape[a] == k for a, k in axes.items())
          and engine.mesh.devices.size == 4,
          f"mesh is {shape}, expected {axes}")
    per_device, bad = shard_report(engine.state)
    total = sum(x.nbytes for x in jax.tree.leaves(engine.state))
    check(not bad, f"leaves not spanning all devices: {bad[:5]}")
    check(len(per_device) == 4
          and max(per_device.values()) < 0.5 * total,
          f"state is not spread: {per_device} of {total} bytes")
    c4 = counters()
    # counters tick per traced layer. The Engine's abstract init
    # traces the model once on a batch-1 sample; where the batch is
    # sharded (fsdp=2) that sample cannot divide it, so that one
    # trace — and no other — takes the counted XLA path (mesh_sharded
    # -> dense), once per layer. A train step whose attention went
    # dense under the mesh would add its own layers to both.
    init_dense = engine.module.model.config.num_layers \
        if shape["dp"] * shape["fsdp"] > 1 else 0
    check(c4.get("attention/flash", 0) > 0
          and c4.get("attention/fallback/mesh_sharded", 0) == init_dense
          and c4.get("attention/dense", 0) == init_dense
          and c4.get("attention/fallback/kernel_rejected", 0) == 0,
          f"flash did not carry every train-step trace under the "
          f"mesh (init-only dense traces expected: {init_dense}): {c4}")
    del engine
    gc.collect()
    out1 = os.path.join(work, name + "_1")
    reset_counters()
    engine, losses1, compile1 = run_fit(
        train_argv(out1, corpus, n, extra + flash + on_one),
        out1, platform, devices=jax.devices()[:1])
    del engine
    gc.collect()
    diffs = [abs(a - b) for a, b in zip(losses4, losses1)]
    check(len(losses4) == len(losses1) == n and max(diffs) <= LOSS_TOL,
          f"losses disagree: {losses4} vs {losses1}")
    check(losses4[-1] < losses4[0], f"loss did not fall: {losses4}")
    emit(name, t0, topology=topology,
         compile_seconds=[compile4, compile1], steps=n,
         losses_4chip=[round(x, 4) for x in losses4],
         losses_1device=[round(x, 4) for x in losses1],
         max_abs_diff=round(max(diffs), 5), tolerance=LOSS_TOL,
         state_bytes_total=total,
         state_bytes_per_device=per_device, counters=c4,
         checked=f"mesh axes {axes}, per-step losses within "
                 f"tolerance, every state leaf spans 4 devices, max "
                 f"per-device bytes < half the state, attention/flash "
                 f"> 0 under shard_map, mesh_sharded == dense == "
                 f"{init_dense} (the batch-1 init trace only), no "
                 f"kernel_rejected")


def main(argv=None):
    """Gate on the device, run the phases, print the final line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the mesh phase and its "
                         "one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU + interpret mode at tiny sizes (tests); "
                         "the only way a non-TPU device is accepted")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--workdir", default=os.path.join(
        HERE, "output", "chip_smoke"))
    args = ap.parse_args(argv)

    want = 4 if args.multichip else 1
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PFX_PALLAS_INTERPRET"] = "1"
    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", want)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.stderr.write(
            f"chip_smoke: JAX found platform {platform!r}, not 'tpu' "
            f"(--rehearse is the CPU run for tests)\n")
        return 2
    if len(devices) != want:
        sys.stderr.write(
            f"chip_smoke: {len(devices)} devices visible, this run "
            f"needs {want}\n")
        return 2

    sys.path.insert(0, HERE)
    import shutil

    from paddlefleetx_tpu.utils.env import setup_compilation_cache
    t_start = time.time()
    cache_dir = setup_compilation_cache()
    entries0 = cache_entries(cache_dir)
    shutil.rmtree(args.workdir, ignore_errors=True)
    corpus = os.path.join(args.workdir, "corpus")
    extra = TINY if args.rehearse else []
    vocab, seq = (512, 128) if args.rehearse else (50304, 1024)
    make_corpus(corpus, vocab, n_docs=200, doc_len=2 * seq + 3,
                seed=args.seed)
    emit("setup", t_start, device_kind=devices[0].device_kind,
         devices=len(devices), compile_cache=cache_dir,
         compile_cache_entries=len(entries0), jax=jax.__version__)

    if args.multichip:
        for leg in MESH_LEGS:
            phase_mesh_leg(leg, args.workdir, corpus, extra, platform)
    else:
        phase_train(args.workdir, corpus, extra, platform, cache_dir)
        phase_train_flash(args.workdir, corpus, extra, platform)
        t0 = time.time()
        setup = serve_setup(args.seed, extra)
        reset_counters()
        ref = lockstep_rows(*setup)
        emit("serve_reference", t0, rows=len(ref),
             tokens=sum(len(r) for r in ref), counters=counters())
        judge = TieJudge(setup[0], setup[1], setup[3].pad_token_id,
                         exact=args.rehearse)
        rows = phase_serve("serve", args.seed, setup, ref, judge)
        # the verify kernel (window 5) is another lowering than the
        # w=1 decode kernel, so serve_spec answers to generate() only;
        # the device loop runs serve's own kernels
        phase_serve("serve_spec", args.seed, setup, ref, judge,
                    spec=True)
        phase_serve("serve_loop", args.seed, setup, ref, judge,
                    loop_ticks=4, same_as=("serve", rows))
    entries1 = cache_entries(cache_dir)
    wrote = len(entries1 - entries0)
    emit("compile_cache", t_start, directory=cache_dir,
         entries_before=len(entries0), entries_after=len(entries1),
         new_entries=wrote, evicted_entries=len(entries0 - entries1),
         verdict="hit (nothing new written)" if not wrote
         else f"wrote {wrote} new entries"
         + (" (cold)" if not entries0 else ""),
         wall_seconds_total=round(time.time() - t_start, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
