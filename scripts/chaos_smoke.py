"""End-to-end chaos drill: SIGKILL a real training run, resume, prove it.

The in-process resilience tests (tests/test_resilience.py) drill the
save -> die -> restore loop with ``PFX_FAULTS_MODE=raise``; this
script is the full-fidelity version the CI ``chaos-smoke`` job runs
(docs/robustness.md): three ``tools/train.py`` subprocesses on a tiny
CPU config with per-step telemetry —

1. **baseline** — runs to ``--steps``, recording every step's loss
   from the flight recorder's ``step_window`` events;
2. **chaos** — the same run with ``PFX_FAULTS=kill@step=K``: a real
   ``SIGKILL`` mid-training, after the checkpoint cadence has
   committed at least one manifest;
3. **resume** — the same command pointed back at the chaos output
   dir, no fault spec.

Asserted: the killed run durably recorded ``fault_injected``; resume
restores the last committed checkpoint (step continuity, no gap and
no replayed step windows); the resumed loss curve is IDENTICAL to the
baseline from the restore point on; the resumed event log contains no
``ckpt_fallback`` (the kill landed between saves, so the newest
checkpoint must verify). Exit 0 on success, 1 with a diagnosis on any
violation.

With ``--ptq`` a fourth leg runs ``scripts/quantize_checkpoint.py``
on the resumed output and drills the quantized artifact the same way
the training checkpoints are drilled: the int8 checkpoint must
verify, a single flipped byte in a payload file (the fp32
``kernel_scale`` arrays ride in the same ocdbt payload as the int8
kernels) must fail manifest verification AND drop the step dir out of
``latest_checkpoint`` (the resume fallback path), and restoring the
byte must verify again — proving the scale arrays are covered as
payload, not sidecar metadata (docs/quantization.md).

With ``--fleet`` a serving leg drills the fleet's availability story
(docs/fleet_serving.md) in-process: two paged interpret-mode
GenerationServer replicas — tiered, with a pinned-host spill pool and
the router's ``prefix_store_dir`` round-tripping each dying replica's
prefix store through disk — behind an ``async_workers=True``
FleetRouter (each replica served from its own worker thread,
docs/fleet_serving.md "Async router") serve a shared-prefix trace
while EVERY replica is rolling-restarted mid-stream under the
overlapped load. Asserted: every completion is token-identical to the
single-batch lockstep reference (zero dropped committed tokens),
nothing was shed (the peer always had capacity), at least one request
actually failed over, and events.jsonl ALONE reconstructs one trace
id per request — with two ``serving/request`` lifetimes bridged by a
``fleet/failover`` span for each failed-over stream. A second wave of
the same prompts then proves the warm restart: the restarted replicas
serve it with at least one ``serving_rehydrate``, and in the
post-restart event stream the first rehydrate precedes the first
``serving_prefill_chunk`` — host-DRAM hits beat re-prefill
(docs/inference.md "Hierarchical KV cache").

With ``--adapters`` a multi-tenant LoRA leg (docs/lora.md) rolling-
restarts a 2-replica fleet under MIXED-ADAPTER load: six requests
striped across adapter ids {1,2,3} while every replica goes down in
turn. Asserted: zero dropped tokens (every completion, first wave and
a warm second wave, token-identical to a single-server reference),
nothing shed, at least one request failed over, and the post-restart
adapter-cache re-warm reconstructs from events.jsonl ALONE — the
``serving_adapter_load`` events after the restart cover the full
adapter working set, proving the restarted replicas' cold banks
re-warmed rather than silently serving base weights. Run from the
repo root:

  python scripts/chaos_smoke.py [--workdir DIR] [--steps 12]
                                [--kill-step 7] [--save-steps 4]
                                [--ptq] [--fleet] [--adapters]
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_CONFIG = """\
Global:
  device: cpu
  seed: 1024
  global_batch_size: null
  local_batch_size: 8
  micro_batch_size: 8
Engine:
  max_steps: {steps}
  num_train_epochs: 1
  logging_freq: 1
  eval_freq: 1000
  eval_iters: 1
  mix_precision:
    use_pure_fp16: False
  save_load:
    save_steps: {save_steps}
    output_dir: {out}
Model:
  module: GPTModule
  name: GPT
  vocab_size: 128
  hidden_size: 32
  num_layers: 2
  num_attention_heads: 4
  ffn_hidden_size: 64
  max_position_embeddings: 64
  hidden_dropout_prob: 0.0
  attention_probs_dropout_prob: 0.0
Distributed:
  dp_degree: 1
  mp_degree: 1
  pp_degree: 1
  sharding:
    sharding_degree: 1
    sharding_stage: 1
Optimizer:
  name: FusedAdamW
  weight_decay: 0.01
  beta1: 0.9
  beta2: 0.999
  epsilon: 1.0e-8
  lr:
    name: CosineAnnealingWithWarmupDecay
    decay_steps: 100
    warmup_rate: 0.1
    max_lr: 1.0e-2
    min_lr: 1.0e-3
  grad_clip:
    name: ClipGradByGlobalNorm
    clip_norm: 1.0
Data:
  Train:
    dataset:
      name: GPTDataset
      input_dir: {data}
      split: [1, 0, 0]
      max_seq_len: 32
      num_samples: 400
      mode: Train
      eos_id: 127
      build_data_file: True
    sampler:
      name: GPTBatchSampler
      batch_size: 8
      shuffle: False
      drop_last: True
    loader:
      collate_fn: gpt_collate_fn
Telemetry:
  enable: True
"""


def make_corpus(data_dir):
    """Synthetic corpus_ids.npy + corpus_idx.npz (quick_start shape)."""
    rng = np.random.default_rng(0)
    lens = rng.integers(20, 60, 80).astype(np.int32)
    ids = rng.integers(0, 128, int(lens.sum())).astype(np.int32)
    ids[np.cumsum(lens) - 1] = 127
    os.makedirs(data_dir, exist_ok=True)
    np.save(os.path.join(data_dir, "corpus_ids.npy"), ids)
    np.savez(os.path.join(data_dir, "corpus_idx.npz"), lens=lens)


def run_train(cfg_path, out_dir, faults=None, resume=False, timeout=600):
    """One tools/train.py subprocess; returns its returncode."""
    cmd = [sys.executable, os.path.join(REPO, "tools", "train.py"),
           "-c", cfg_path,
           "-o", f"Engine.save_load.output_dir={out_dir}"]
    if resume:
        cmd += ["-o", f"Engine.save_load.ckpt_dir={out_dir}"]
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("PFX_FAULTS", None)
    if faults:
        env["PFX_FAULTS"] = faults
    proc = subprocess.run(cmd, cwd=REPO, env=env, timeout=timeout,
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    tag = "chaos" if faults else ("resume" if resume else "baseline")
    sys.stdout.write(f"--- {tag} run: rc={proc.returncode} ---\n")
    if proc.returncode not in (0, -signal.SIGKILL):
        sys.stdout.write(proc.stdout[-4000:] + "\n")
    return proc.returncode


def read_events(out_dir, skip_lines=0):
    """Parsed events.jsonl records, optionally past a line offset."""
    path = os.path.join(out_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        lines = f.readlines()
    out = []
    for line in lines[skip_lines:]:
        try:
            out.append(json.loads(line))
        except ValueError:
            pass  # torn tail line of a killed run
    return out


def count_lines(out_dir):
    """Line count of events.jsonl (0 when absent)."""
    path = os.path.join(out_dir, "events.jsonl")
    if not os.path.exists(path):
        return 0
    with open(path) as f:
        return sum(1 for _ in f)


def losses_by_step(events):
    """Map step -> loss from the step_window events."""
    return {e["step"]: e["loss"] for e in events
            if e.get("event") == "step_window"}


def fail(msg):
    """Print the diagnosis and exit nonzero."""
    sys.stdout.write(f"CHAOS SMOKE FAILED: {msg}\n")
    sys.exit(1)


def ptq_leg(work, chaos_out, cfg_path):
    """Quantize the resumed checkpoint and drill the int8 artifact:
    byte-flip a scale payload -> verify fails and latest_checkpoint
    falls back; restore the byte -> verifies again."""
    ptq_out = os.path.join(work, "ptq_out")
    cmd = [sys.executable,
           os.path.join(REPO, "scripts", "quantize_checkpoint.py"),
           "--checkpoint", chaos_out, "--output", ptq_out,
           "--config", cfg_path, "--max-rel-dev", "0.05"]
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(cmd, cwd=REPO, env=env, timeout=600,
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    sys.stdout.write(f"--- ptq run: rc={proc.returncode} ---\n")
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout[-4000:] + "\n")
        fail(f"quantize_checkpoint.py exited {proc.returncode}")
    if "QUANTIZE CHECKPOINT OK" not in proc.stdout:
        fail("quantize run missing its OK line")

    sys.path.insert(0, REPO)
    from paddlefleetx_tpu.core.checkpoint import (
        latest_checkpoint, verify_checkpoint,
    )
    step_dir = latest_checkpoint(ptq_out)
    if step_dir is None:
        fail(f"no verified quantized checkpoint under {ptq_out}")

    # pick a payload file holding the fp32 kernel scales if the store
    # names arrays in its paths, else the largest non-manifest payload
    payload = [os.path.join(root, name)
               for root, _, files in os.walk(step_dir)
               for name in files if name != "pfx_manifest.json"]
    if not payload:
        fail(f"quantized step dir {step_dir} holds no payload files")
    scales = [p for p in payload
              if "kernel_scale" in os.path.relpath(p, step_dir)]
    target = max(scales or payload, key=os.path.getsize)

    size = os.path.getsize(target)
    with open(target, "r+b") as f:
        f.seek(size // 2)
        orig = f.read(1)
        f.seek(size // 2)
        f.write(bytes([orig[0] ^ 0xFF]))
    rel = os.path.relpath(target, step_dir)
    reason = verify_checkpoint(step_dir)
    if reason is None:
        fail(f"flipped byte in {rel} still passed verification — "
             f"scale arrays are not covered as payload")
    if latest_checkpoint(ptq_out) == step_dir:
        fail(f"latest_checkpoint still resolves the corrupted "
             f"{step_dir} (resume would load a torn artifact)")
    with open(target, "r+b") as f:
        f.seek(size // 2)
        f.write(orig)
    if verify_checkpoint(step_dir) is not None:
        fail(f"restored byte in {rel} no longer verifies")
    sys.stdout.write(
        f"PTQ LEG OK: quantized {os.path.basename(chaos_out)} -> "
        f"{step_dir}; corrupting {rel} failed verify and fallback "
        f"skipped it; restored artifact verifies\n")


def _interpret_off_tpu(jax):
    """The in-process drills run their Pallas kernels in interpret
    mode on the CPU platform only — never where a TPU is attached,
    which would report success without the chip doing the work."""
    if jax.default_backend() != "tpu":
        os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")


def fleet_leg(work):
    """In-process fleet drill: rolling-restart a 2-replica tiered
    ASYNC fleet mid-stream — each replica serving from its own worker
    thread, so the restart happens under genuinely overlapped load —
    and prove zero token loss + trace continuity from the event log
    alone, then a warm second wave that must rehydrate from the
    restart-persisted prefix store before it prefills anything."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    import jax
    _interpret_off_tpu(jax)
    import jax.numpy as jnp

    from paddlefleetx_tpu.core.fleet import FleetRouter
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddlefleetx_tpu.models.gpt.generation import (
        GenerationConfig, generate, left_pad_batch,
    )

    vocab, eos = 96, 95
    cfg = GPTConfig(vocab_size=vocab, hidden_size=32, num_layers=2,
                    num_attention_heads=4,
                    max_position_embeddings=256,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    params = model.init({"params": jax.random.key(0)},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    gen_cfg = GenerationConfig(max_dec_len=8,
                               decode_strategy="greedy_search",
                               eos_token_id=eos, pad_token_id=eos)

    # the fleet workload shape: a few shared system prompts, many tails
    rng = np.random.default_rng(2)
    prefixes = [rng.integers(0, eos, 130).tolist() for _ in range(2)]
    prompts = [prefixes[i % 2] + rng.integers(0, eos, 8 + i).tolist()
               for i in range(6)]

    ids_arr, mask = left_pad_batch(prompts, eos)
    out = np.asarray(generate(model, params, jnp.asarray(ids_arr),
                              jnp.asarray(mask), jax.random.key(0),
                              gen_cfg))
    ref = []
    for row in out:
        toks = []
        for t in row:
            toks.append(int(t))
            if int(t) == eos:
                break
        ref.append(toks)

    events = os.path.join(work, "fleet_events.jsonl")

    def factory(name):
        return GenerationServer(model, params, gen_cfg, num_slots=2,
                                rng=jax.random.PRNGKey(7),
                                page_size=128, pool_pages=17,
                                prefill_chunk_pages=1,
                                prefix_sharing=True,
                                host_pool_bytes=4 << 20,
                                events_path=events)

    stores = os.path.join(work, "fleet_stores")
    fleet = FleetRouter(factory, 2, events_path=events,
                        prefix_store_dir=stores,
                        async_workers=True)
    gids = [fleet.submit(p) for p in prompts]
    done = {}
    # commit some tokens first — with async workers the router tick
    # commits nothing itself, so poll until the worker threads have
    # decoded mid-stream state worth failing over (~1 token/request)
    deadline = time.monotonic() + 120.0
    while (fleet.summary()["decode_tokens"] < len(prompts)
           and len(done) < len(prompts)
           and time.monotonic() < deadline):
        for c in fleet.step():
            done[c.request_id] = c
    # the drill: EVERY replica goes down in turn while serving
    for c in fleet.rolling_restart():
        done[c.request_id] = c
    while fleet.busy:
        for c in fleet.step():
            done[c.request_id] = c
    summ = fleet.summary()

    missing = [g for g in gids if g not in done]
    if missing:
        fail(f"fleet leg lost requests {missing}")
    got = [done[g].tokens for g in gids]
    if got != ref:
        bad = [i for i, (a, b) in enumerate(zip(got, ref)) if a != b]
        fail(f"fleet leg dropped committed tokens: requests {bad} "
             f"diverged from the lockstep reference after the "
             f"rolling restart")
    if summ["shed"] != 0:
        fail(f"fleet leg shed {summ['shed']} requests while the peer "
             f"had capacity")
    if summ["failovers"] < 1:
        fail("fleet leg exercised no failover — the restart landed "
             "on an idle replica, drill geometry is broken")
    if summ["restarts"] != 2:
        fail(f"expected 2 replica restarts, recorded "
             f"{summ['restarts']}")
    if not summ.get("async_workers"):
        fail("fleet leg ran lockstep — the drill must restart "
             "replicas under overlapped worker-thread load")

    # trace continuity, reconstructed from events.jsonl ALONE
    with open(events) as f:
        evs = [json.loads(line) for line in f if line.strip()]
    routes = {e["request"]: e["trace"] for e in evs
              if e.get("event") == "fleet_route"}
    if sorted(routes) != sorted(gids):
        fail(f"fleet_route events cover requests {sorted(routes)}, "
             f"expected {sorted(gids)}")
    if len(set(routes.values())) != len(gids):
        fail("trace ids are not unique per request")
    begins = [e for e in evs if e.get("event") == "span_begin"]
    for e in [e for e in evs if e.get("event") == "fleet_failover"]:
        tid = e["trace"]
        lives = [b for b in begins if b["name"] == "serving/request"
                 and b["trace"] == tid]
        bridges = [b for b in begins if b["name"] == "fleet/failover"
                   and b["trace"] == tid]
        if len(lives) < 2:
            fail(f"failed-over trace {tid} shows {len(lives)} "
                 f"serving/request lifetimes, expected >= 2")
        if not bridges:
            fail(f"failed-over trace {tid} has no fleet/failover span")

    # warm second wave: the restarted replicas carry the dying
    # replicas' prefix stores (round-tripped through prefix_store_dir
    # on disk), so resubmitting the SAME prompts must be served by
    # rehydrating spilled prefix pages from host DRAM — and the first
    # serving_rehydrate in the post-restart stream must land BEFORE
    # the first serving_prefill_chunk (docs/fleet_serving.md
    # "Warm starts").
    for i in range(2):
        if not os.path.exists(os.path.join(
                stores, f"replica{i}_prefix_store",
                "pfx_manifest.json")):
            fail(f"replica{i} left no committed prefix store under "
                 f"{stores}")
    mark = sum(1 for _ in open(events))
    gids2 = [fleet.submit(p) for p in prompts]
    done2 = {}
    while fleet.busy:
        for c in fleet.step():
            done2[c.request_id] = c
    summ2 = fleet.summary()
    fleet.close()
    got2 = [done2[g].tokens for g in gids2 if g in done2]
    if got2 != ref:
        fail("warm wave diverged from the lockstep reference — the "
             "imported prefix store corrupted decoding")
    rehydrates = sum(r.get("rehydrates", 0)
                     for r in summ2["per_replica"])
    if rehydrates < 1:
        fail("warm wave rehydrated nothing — the restarted replicas "
             "started cold despite the persisted prefix store")
    with open(events) as f:
        warm_evs = [json.loads(line)
                    for line in list(f)[mark:] if line.strip()]
    kinds = [e["event"] for e in warm_evs
             if e.get("event") in ("serving_rehydrate",
                                   "serving_prefill_chunk")]
    if "serving_rehydrate" not in kinds:
        fail("no serving_rehydrate event in the warm wave")
    if kinds.index("serving_rehydrate") != 0:
        fail(f"warm wave prefilled before it rehydrated "
             f"(event order {kinds[:4]}) — registry hits must be "
             f"served from the host tier first")

    sys.stdout.write(
        f"FLEET LEG OK: rolling restart of 2 tiered ASYNC replicas "
        f"under overlapped load — {len(gids)} requests "
        f"lockstep-exact, shed=0, "
        f"failovers={summ['failovers']}, per-request traces "
        f"reconstruct from {os.path.basename(events)}; warm wave "
        f"re-served {len(gids2)} prompts with {rehydrates} "
        f"rehydrates, first rehydrate ahead of any prefill chunk\n")


def adapters_leg(work):
    """Multi-tenant LoRA drill (docs/lora.md): rolling-restart a
    2-replica fleet under mixed-adapter load — zero dropped tokens,
    and the restarted replicas' adapter-cache re-warm proven from
    ``serving_adapter_load`` events alone."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    import jax
    _interpret_off_tpu(jax)
    import jax.numpy as jnp
    import flax.linen as nn

    from paddlefleetx_tpu.core.adapters import extract_adapter
    from paddlefleetx_tpu.core.fleet import FleetRouter
    from paddlefleetx_tpu.core.serving import GenerationServer
    from paddlefleetx_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddlefleetx_tpu.models.gpt.generation import GenerationConfig

    vocab, eos = 96, 95
    cfg = GPTConfig(vocab_size=vocab, hidden_size=32, num_layers=2,
                    num_attention_heads=4,
                    max_position_embeddings=128,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    fuse_attn_qkv=True, lora_rank=4,
                    lora_num_adapters=4)
    model = GPTForPretraining(cfg)
    params = nn.meta.unbox(
        model.init({"params": jax.random.key(0)},
                   jnp.zeros((1, 8), jnp.int32))["params"])
    gen_cfg = GenerationConfig(max_dec_len=6,
                               decode_strategy="greedy_search",
                               eos_token_id=eos, pad_token_id=eos)
    shapes = {k: np.asarray(v).shape
              for k, v in extract_adapter(params, 0).items()}

    def source(aid):
        rng = np.random.default_rng(1000 + int(aid))
        return {k: rng.normal(0.0, 0.2, s).astype(np.float32)
                for k, s in shapes.items()}

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, eos, 6 + i).tolist() for i in range(6)]
    aids = [1, 2, 3, 1, 2, 3]    # the adapter working set, striped

    # greedy decode is deterministic whatever the batching, so one
    # reference server's completions are the fleet's token oracle
    ref_srv = GenerationServer(model, params, gen_cfg, num_slots=2,
                               adapter_source=source)
    ref = [c.tokens for c in ref_srv.run(prompts, adapter_ids=aids)]

    events = os.path.join(work, "adapter_events.jsonl")

    def factory(name):
        return GenerationServer(model, params, gen_cfg, num_slots=2,
                                adapter_source=source,
                                events_path=events)

    fleet = FleetRouter(factory, 2, events_path=events)
    gids = [fleet.submit(p, adapter_id=a)
            for p, a in zip(prompts, aids)]
    done = {}
    # commit mid-stream state worth restarting under
    while fleet.summary()["decode_tokens"] < 2 and len(done) < len(gids):
        for c in fleet.step():
            done[c.request_id] = c
    mark = sum(1 for _ in open(events))
    # the drill: EVERY replica goes down in turn under adapter load
    for c in fleet.rolling_restart():
        done[c.request_id] = c
    while fleet.busy:
        for c in fleet.step():
            done[c.request_id] = c
    summ = fleet.summary()

    missing = [g for g in gids if g not in done]
    if missing:
        fail(f"adapter leg lost requests {missing}")
    bad_reason = [g for g in gids
                  if done[g].finish_reason not in ("eos", "length")]
    if bad_reason:
        fail(f"adapter leg requests {bad_reason} finished "
             f"{[done[g].finish_reason for g in bad_reason]} — the "
             f"restart dropped adapters on the floor")
    got = [done[g].tokens for g in gids]
    if got != ref:
        bad = [i for i, (a, b) in enumerate(zip(got, ref)) if a != b]
        fail(f"adapter leg dropped committed tokens: requests {bad} "
             f"diverged from the single-server reference after the "
             f"rolling restart")
    if summ["shed"] != 0:
        fail(f"adapter leg shed {summ['shed']} requests while the "
             f"peer had capacity")
    if summ["failovers"] < 1:
        fail("adapter leg exercised no failover — the restart landed "
             "on an idle replica, drill geometry is broken")
    if summ["restarts"] != 2:
        fail(f"expected 2 replica restarts, recorded "
             f"{summ['restarts']}")

    # warm second wave: the same mixed-adapter trace again, served by
    # the restarted replicas
    gids2 = [fleet.submit(p, adapter_id=a)
             for p, a in zip(prompts, aids)]
    done2 = {}
    while fleet.busy:
        for c in fleet.step():
            done2[c.request_id] = c
    fleet.close()
    got2 = [done2[g].tokens for g in gids2 if g in done2]
    if got2 != ref:
        fail("adapter leg warm wave diverged from the single-server "
             "reference — the re-warmed banks served wrong weights")

    # the re-warm evidence must reconstruct from events ALONE: the
    # restarted replicas start with cold banks, so the post-restart
    # stream (failover re-admissions + the warm wave) must show
    # serving_adapter_load events covering the full working set — a
    # fleet that silently served base weights would show none
    with open(events) as f:
        warm_evs = [json.loads(line)
                    for line in list(f)[mark:] if line.strip()]
    reloaded = {e["adapter"] for e in warm_evs
                if e.get("event") == "serving_adapter_load"}
    if reloaded != set(aids):
        fail(f"post-restart stream re-warmed adapters "
             f"{sorted(reloaded)}, expected the full working set "
             f"{sorted(set(aids))} — the restarted banks stayed cold")

    sys.stdout.write(
        f"ADAPTER LEG OK: rolling restart of 2 LoRA replicas under "
        f"mixed-adapter load — {len(gids)} + {len(gids2)} requests "
        f"token-exact vs the single-server reference, shed=0, "
        f"failovers={summ['failovers']}, post-restart re-warm of "
        f"adapters {sorted(reloaded)} reconstructed from "
        f"{os.path.basename(events)}\n")


def main():
    """Run the baseline/chaos/resume triple and assert continuity."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--kill-step", type=int, default=7)
    ap.add_argument("--save-steps", type=int, default=4)
    ap.add_argument("--ptq", action="store_true",
                    help="also PTQ the resumed checkpoint and drill "
                         "the int8 artifact's manifest verification")
    ap.add_argument("--fleet", action="store_true",
                    help="also rolling-restart an in-process "
                         "2-replica serving fleet mid-stream and "
                         "assert zero token loss + trace continuity")
    ap.add_argument("--adapters", action="store_true",
                    help="also rolling-restart a 2-replica LoRA "
                         "fleet under mixed-adapter load and assert "
                         "zero token loss + adapter-cache re-warm "
                         "from events alone")
    args = ap.parse_args()

    work = args.workdir or tempfile.mkdtemp(prefix="pfx_chaos_")
    os.makedirs(work, exist_ok=True)
    data = os.path.join(work, "data")
    base_out = os.path.join(work, "base_out")
    chaos_out = os.path.join(work, "chaos_out")
    make_corpus(data)
    cfg_path = os.path.join(work, "chaos_smoke.yaml")
    with open(cfg_path, "w") as f:
        f.write(_CONFIG.format(steps=args.steps,
                               save_steps=args.save_steps,
                               out=base_out, data=data))
    last_save = (args.kill_step // args.save_steps) * args.save_steps
    if not 0 < last_save < args.kill_step:
        fail(f"bad drill geometry: kill step {args.kill_step} must "
             f"land strictly between save-cadence multiples of "
             f"{args.save_steps}")

    # 1. baseline
    rc = run_train(cfg_path, base_out)
    if rc != 0:
        fail(f"baseline run exited {rc}")
    base_losses = losses_by_step(read_events(base_out))
    missing = [s for s in range(1, args.steps + 1)
               if s not in base_losses]
    if missing:
        fail(f"baseline missing step_window for steps {missing}")

    # 2. chaos: a real SIGKILL at --kill-step
    rc = run_train(cfg_path, chaos_out,
                   faults=f"kill@step={args.kill_step}")
    if rc != -signal.SIGKILL:
        fail(f"chaos run expected SIGKILL exit, got rc={rc}")
    chaos_events = read_events(chaos_out)
    injected = [e for e in chaos_events
                if e.get("event") == "fault_injected"]
    if not injected:
        fail("killed run did not durably record fault_injected")
    chaos_losses = losses_by_step(chaos_events)
    for s in range(1, args.kill_step + 1):
        if chaos_losses.get(s) != base_losses[s]:
            fail(f"pre-kill divergence at step {s}: "
                 f"{chaos_losses.get(s)} != {base_losses[s]}")
    mark = count_lines(chaos_out)

    # 3. resume from the chaos output dir
    rc = run_train(cfg_path, chaos_out, resume=True)
    if rc != 0:
        fail(f"resume run exited {rc}")
    resumed = read_events(chaos_out, skip_lines=mark)
    fallbacks = [e for e in resumed if e.get("event") == "ckpt_fallback"]
    if fallbacks:
        fail(f"resume fell back past the newest checkpoint (the kill "
             f"landed between saves, so step {last_save} must "
             f"verify): {fallbacks}")
    res_losses = losses_by_step(resumed)
    expect = list(range(last_save + 1, args.steps + 1))
    if sorted(res_losses) != expect:
        fail(f"resume step continuity broken: trained steps "
             f"{sorted(res_losses)}, expected {expect} (restore at "
             f"step {last_save})")
    diverged = {s: (res_losses[s], base_losses[s]) for s in expect
                if res_losses[s] != base_losses[s]}
    if diverged:
        fail(f"resumed loss curve diverged from baseline: {diverged}")

    # 4. optional: PTQ the resumed checkpoint, drill the artifact
    if args.ptq:
        ptq_leg(work, chaos_out, cfg_path)

    # 5. optional: rolling-restart a serving fleet under load
    if args.fleet:
        fleet_leg(work)

    # 6. optional: rolling-restart a LoRA fleet under adapter load
    if args.adapters:
        adapters_leg(work)

    sys.stdout.write(
        f"CHAOS SMOKE OK: killed at step {args.kill_step}, restored "
        f"step {last_save}, steps {expect[0]}..{expect[-1]} "
        f"loss-identical to baseline ({work})\n")


if __name__ == "__main__":
    main()
