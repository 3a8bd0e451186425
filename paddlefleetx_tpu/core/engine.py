"""The unified training engine: one GSPMD code path for every topology.

Replaces both reference engines (SURVEY.md §7 design stance):
  - ``EagerEngine`` (reference ``eager_engine.py:42-743``): config
    parsing, AMP policy, optimizer build, model wrapping, train loop
    with logging/eval/save cadence, checkpoint/resume.
  - ``AutoEngine`` (``auto_engine.py:37-132``): annotate-then-partition
    — which is literally jit + NamedSharding here.

The reference wraps models in ``fleet.distributed_model`` /
``group_sharded_parallel`` per strategy (``eager_engine.py:226-253``);
here strategy is data: the topology's rule table maps the model's
logical axes onto the mesh, jit partitions the whole step, and XLA
emits/overlaps the collectives (DP grad all-reduce, ZeRO
reduce-scatter/all-gather, TP identity/all-reduce) that
``_fit_impl``/``_optim_update_params`` (``:388-450``) issued by hand.

The whole optimizer step — microbatch grad accumulation included —
is ONE jitted program: no per-step Python between forward, backward,
collective, and update.
"""

from __future__ import annotations

import os
import signal
import sys
import time
from collections import deque
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observability import flops as obs_flops
from ..observability import metrics as obs_metrics
from ..observability import server as obs_server
from ..observability import timeline as obs_timeline
from ..observability.memory import device_memory_stats, format_bytes
from ..observability.recorder import FlightRecorder
from ..observability.spans import NULL_SPAN, Tracer
from ..observability.trace import annotate, annotate_step
from ..optims import build_lr_scheduler, build_optimizer
from ..parallel.mesh import (
    TopologyConfig, build_mesh, set_mesh, DATA_AXES,
)
from ..parallel.sharding import make_sharding_rules
from ..utils.log import logger
from . import checkpoint as ckpt
from . import resilience


class BasicEngine:
    """Abstract engine contract (reference ``basic_engine.py:16-39``)."""

    def fit(self, *a, **k):
        raise NotImplementedError

    def evaluate(self, *a, **k):
        raise NotImplementedError

    def predict(self, *a, **k):
        raise NotImplementedError

    def save(self, *a, **k):
        raise NotImplementedError

    def load(self, *a, **k):
        raise NotImplementedError


class Engine(BasicEngine):
    """Trainer for modules implementing the BasicModule contract."""

    def __init__(self, configs, module, mode: str = "train",
                 devices=None):
        self.configs = configs
        self.module = module
        self.mode = mode

        eng = configs.Engine
        # max_steps <= 0 means unlimited (epoch-mode configs set -1)
        raw_max_steps = eng.get("max_steps", None)
        self.max_steps = raw_max_steps \
            if raw_max_steps and raw_max_steps > 0 else sys.maxsize
        self.logging_freq = eng.get("logging_freq", 1)
        # 'step' gates mid-epoch eval on step % eval_freq; 'epoch'
        # evaluates at epoch end on epoch % eval_freq (reference
        # eager_engine.py:296-372)
        self.run_mode = eng.get("run_mode", "step")
        self.eval_freq = eng.get("eval_freq", sys.maxsize)
        # eval_iters <= 0 means "walk the whole loader" (the vis
        # configs set -1 for full-validation epochs)
        eval_iters = eng.get("eval_iters", 10)
        self.eval_iters = eval_iters if eval_iters and eval_iters > 0 \
            else None
        test_iters = eng.get("test_iters",
                             eval_iters * 10 if eval_iters else 0)
        self.test_iters = test_iters if test_iters and test_iters > 0 \
            else sys.maxsize
        self.accumulate_steps = eng.get("accumulate_steps", 1) or 1
        save_load = eng.get("save_load", {})
        self.save_steps = save_load.get("save_steps", sys.maxsize)
        self.save_epoch = save_load.get("save_epoch", 1)
        # TPU-native extra (reference paddle.save blocks training):
        # overlap the TensorStore write with the next steps
        self.async_save = bool(save_load.get("async_save", False))
        # TPU-native extra: TPU VMs get maintenance/preemption SIGTERM
        # with a grace window; save at the next step boundary and stop
        # cleanly so the restarted job resumes instead of losing the
        # save_steps tail (the reference recovers only from its last
        # periodic checkpoint, SURVEY.md §5.3)
        self.save_on_preemption = bool(
            save_load.get("save_on_preemption", True))
        # TPU-native extra: retention. 0/unset = unlimited (the
        # reference's behavior); k >= 1 keeps the newest k VERIFIED
        # checkpoints — the manifest gates deletion, so an in-flight
        # async save or a torn dir is never GC'd (core/checkpoint.py)
        self.keep_last_k = int(save_load.get("keep_last_k", 0) or 0)
        # TPU-native extra: batches staged ahead of the consuming step
        # (host->device transfer overlapped with compute; 2 = classic
        # double buffering, 0 = synchronous _put_batch between steps).
        # See _prefetch_iter and docs/standard.md.
        self.prefetch_depth = int(eng.get("prefetch_depth", 2))
        self.output_dir = save_load.get("output_dir", "./output")
        self.ckpt_dir = save_load.get("ckpt_dir")

        from ..utils.env import setup_compilation_cache
        setup_compilation_cache(
            configs.Global.get("compilation_cache_dir"))

        self.topo = TopologyConfig.from_config(configs)
        self.mesh = build_mesh(self.topo, devices=devices)
        set_mesh(self.mesh)
        self.rules = list(make_sharding_rules(self.topo))
        self.module.nranks = self.mesh.devices.size

        self.global_batch_size = configs.Global.global_batch_size
        self.micro_batch_size = configs.Global.micro_batch_size
        seed = configs.Global.get("seed", 1024)
        self.root_rng = jax.random.key(seed)

        self._load_recovery = {"epoch": 0, "step": 0,
                               "consumed_samples": 0}
        self._host_step = 0
        self._preempt_signum = None

        # config-gated profiler window (reference
        # ``eager_engine.py:202-224``: paddle.profiler over a
        # [start, stop] scheduler window, chrome-trace export; here
        # jax.profiler -> TensorBoard/XProf trace in profiler_log)
        prof = configs.get("Profiler", {}) or {}
        self._prof_window = None
        if prof.get("enable", False):
            start, stop = (prof.get("scheduler") or [1, 5])[:2]
            self._prof_window = (int(start), int(stop))
            self._prof_dir = prof.get("profiler_log", "./profiler_log")
            self._prof_active = False
            logger.warning("Profiler is enabled, do not enable it in "
                           "production.")

        # structured telemetry (docs/observability.md): the
        # engine-local registry absorbs the loop's sample series and
        # wall-time buckets; Telemetry.enable additionally turns on
        # the process-global dispatch-counter registry and the
        # crash-surviving flight recorder (events.jsonl, every record
        # flushed+fsynced so an OOM-killed run keeps its last state)
        tele = configs.get("Telemetry", {}) or {}
        self._tele_enabled = bool(tele.get("enable", False))
        self._metrics = obs_metrics.MetricsRegistry(enabled=True)
        self._recorder = None
        events_path = None
        if self._tele_enabled:
            obs_metrics.set_enabled(True)
            events_path = tele.get("events_path") or \
                os.path.join(self.output_dir, "events.jsonl")
            self._recorder = FlightRecorder(events_path)
        # span tracing rides the same recorder: engine/fit owns the
        # rare, durable compile and save children
        # (docs/observability.md); per-step phases are profiler
        # annotations and in-memory seconds (observability/trace.py),
        # never recorder lines. A recorder-less tracer hands out
        # NULL_SPAN and costs nothing
        self._tracer = Tracer(self._recorder)
        self._fit_span = NULL_SPAN
        # live /metrics when PFX_METRICS_PORT is set (no-op otherwise)
        obs_server.start_from_env(registry=self._metrics,
                                  events_path=events_path)
        # resilience (docs/robustness.md): chaos faults only exist
        # when PFX_FAULTS is set; the stall watchdog only when
        # PFX_WATCHDOG is on — both None on the production default
        self._faults = resilience.FaultInjector.from_env(
            recorder=self._recorder)
        self._watchdog = resilience.StepWatchdog.from_env(
            name="train_step", recorder=self._recorder)
        self._save_count = 0
        # host-time summary gate: explicit Engine.print_summary wins;
        # by default the summary prints whenever profiling OR
        # telemetry asked for it (unprofiled telemetry runs must not
        # report nothing)
        self._print_summary_cfg = eng.get("print_summary", None)
        #: logged step costs for the post-run summary (reference
        #: ``_print_summary``, eager_engine.py:684-721 — device-time
        #: tables live in the XProf trace; this is the host view).
        #: An alias into the registry's sample series.
        self._step_costs = self._metrics.series("host/step_cost")
        #: per-step host time spent staging the NEXT batch's
        #: host->device transfer (_prefetch_iter); near-zero means the
        #: transfer is fully hidden behind the jitted step
        self._h2d_waits = self._metrics.series("host/h2d_wait")
        #: goodput buckets: host wall time NOT spent in productive
        #: steps (h2d waits live in the series above). pipeline_bubble
        #: is the analytic schedule-idle share of clean step windows
        #: (pp > 1 only; see _build_steps)
        self._time_buckets = {"compile": 0.0, "eval": 0.0, "save": 0.0,
                              "pipeline_bubble": 0.0}
        self._fit_t0 = None
        self._hbm_watermark = None
        self._compile_pending = True
        self._init_state()
        self._build_steps()
        if self.ckpt_dir:
            self.load()

    # -- state ----------------------------------------------------------

    def _maybe_lora_tx(self, tx):
        """LoRA fine-tune (docs/lora.md): a training model carrying
        adapter banks (``lora_rank > 0``) updates ONLY the ``*_lora``
        leaves. ``optax.multi_transform`` routes base weights through
        ``set_to_zero`` — they stay frozen bit-for-bit and carry NO
        optimizer state, so Adam moments exist for the tiny A/B banks
        alone (the reference freezes via ``stop_gradient`` flags and
        still allocates full-size moments)."""
        mcfg = getattr(getattr(self.module, "model", None), "config",
                       None)
        if not getattr(mcfg, "lora_rank", 0):
            return tx

        def labels(params):
            def lab(path, _leaf):
                keys = [str(getattr(k, "key", k)) for k in path]
                return "lora" if any(k.endswith("_lora")
                                     for k in keys) else "frozen"
            return jax.tree_util.tree_map_with_path(lab, params)

        logger.info(
            "LoRA fine-tune: base weights frozen (zero optimizer "
            "state), training only *_lora adapter leaves")
        return optax.multi_transform(
            {"lora": tx, "frozen": optax.set_to_zero()}, labels)

    def _abstract_state(self):
        model = self.module.model
        spec = self.module.input_spec() or [((1, 8), "int32")]
        samples = []
        for shape, dtype in spec:
            shape = tuple(1 if d is None else int(d) for d in shape)
            # a full-size dummy is wasteful for abstract init; shrink
            # the batch dim (weights don't depend on it)
            samples.append(((1,) + shape[1:], jnp.dtype(dtype)))

        extra_rngs = getattr(self.module, "init_rng_collections", ())

        def init_fn(rng):
            """Initialize model variables from a single PRNG key."""
            rngs = {"params": rng}
            for i, name in enumerate(extra_rngs):
                rngs[name] = jax.random.fold_in(rng, i + 1)
            variables = self.module.init_model_variables(
                model, rngs, [jnp.zeros(s, d) for s, d in samples])
            params = variables["params"]
            state = {"params": params, "step": jnp.zeros((), jnp.int32)}
            if self.mode == "train":
                state["opt_state"] = self.tx.init(
                    nn.meta.unbox(params))
            return state

        return init_fn, jax.eval_shape(init_fn, jax.random.key(0))

    def _state_shardings(self, abstract):
        logical = nn.get_partition_spec(abstract)
        mesh_shardings = nn.logical_to_mesh_sharding(
            logical, self.mesh, self.rules)

        # opt-state leaves mirror param specs (moments) or are scalars;
        # StandardNames: resolved leaf-wise against the param tree
        from ..parallel.sharding import optimizer_state_shardings
        param_specs = nn.logical_to_mesh(
            nn.get_partition_spec(abstract["params"]), self.rules)
        out = dict(mesh_shardings)
        out["step"] = NamedSharding(self.mesh, P())
        self._opt_offload = False
        if "opt_state" in abstract:
            out["opt_state"] = optimizer_state_shardings(
                abstract["opt_state"], param_specs, self.mesh, self.topo)
            if self.topo.sharding_offload:
                # ZeRO offload (reference eager_engine.py:233-247):
                # optimizer state lives in pinned host memory and
                # streams through HBM only during the update. In-jit
                # host placement is a TPU feature — the CPU test
                # platform's partitioner rejects it, so there the flag
                # downgrades loudly instead of failing.
                from ..parallel.sharding import (
                    device_memory_kinds, offload_to_host,
                )
                if self.mesh.devices.flat[0].platform == "tpu":
                    out["opt_state"] = offload_to_host(
                        out["opt_state"], abstract["opt_state"])
                    self._opt_device_shardings = device_memory_kinds(
                        out["opt_state"])
                    self._opt_offload = True
                else:
                    logger.warning(
                        "sharding_offload requested but host offload "
                        "under jit is unsupported on platform %r; "
                        "optimizer state stays in device memory",
                        self.mesh.devices.flat[0].platform)
        return out

    def _init_state(self):
        if self.mode == "train":
            opt_cfg = self.configs.Optimizer
            self._vit_lr_pending = False
            if "lr" in opt_cfg and \
                    opt_cfg.lr.get("name") == "ViTLRScheduler" and \
                    "step_each_epoch" not in opt_cfg.lr:
                # the reference injects step_each_epoch from the
                # dataloader length, known only at fit() time; build
                # a placeholder now and rebuild in fit()
                self._vit_lr_pending = True
                opt_cfg.lr.setdefault(
                    "epochs", self.configs.Engine.get(
                        "num_train_epochs", 1))
                opt_cfg.lr["step_each_epoch"] = 1
            self.lr_schedule = build_lr_scheduler(opt_cfg.lr) \
                if "lr" in opt_cfg else (
                    lambda step: opt_cfg.get("learning_rate", 1e-4))
            self.tx = self._maybe_lora_tx(
                build_optimizer(opt_cfg, self.lr_schedule))
        else:
            self.lr_schedule = lambda step: 0.0
            self.tx = None

        init_fn, abstract = self._abstract_state()
        self.state_shardings = self._state_shardings(abstract)
        with jax.transfer_guard("allow"):
            jit_init = jax.jit(init_fn,
                               out_shardings=self.state_shardings)
            with self.mesh, nn.logical_axis_rules(self.rules):
                state = jit_init(self.root_rng)
        self.state = nn.meta.unbox(state)
        # shardings of the unboxed tree, for jit dataflow
        self.state_shardings = jax.tree.map(
            lambda x: x.sharding, self.state)
        n_params = sum(x.size for x in jax.tree.leaves(
            self.state["params"]))
        logger.info("initialized model: %.1fM params on mesh %s",
                    n_params / 1e6, dict(self.mesh.shape))
        from ..parallel.mesh import MP_AXIS
        mp = self.mesh.shape.get(MP_AXIS, 1)
        mcfg = getattr(getattr(self.module, "model", None), "config",
                       None)
        if mp > 1 and hasattr(mcfg, "sequence_parallel"):
            # what the four mp linears of a layer dispatch to
            # (models/gpt/model.py::_CollectiveDense); a site whose
            # shapes the rings cannot divide still falls back and
            # counts mp_linear/gspmd_fallback when it is traced
            rings = bool(mcfg.sequence_parallel)
            obs_metrics.inc("mp_linear/config/"
                            + ("rings" if rings else "gspmd"))
            logger.info(
                "tensor-parallel linears (mp=%d): %s", mp,
                "decomposed collective-matmul rings (overlapped) "
                "wherever the shapes divide over mp"
                if rings
                else "plain GSPMD collectives (the layer is not "
                     "sequence-parallel, so there is no seq shard to "
                     "stream; docs/tensor_parallel.md)")
        if getattr(mcfg, "moe_num_experts", 0):
            mode = mcfg.moe_dispatch
            obs_metrics.inc("moe/config/" + mode)
            logger.info(
                "MoE dispatch (%d experts, top-%d, ep=%d): %s",
                mcfg.moe_num_experts, mcfg.moe_top_k,
                self.topo.ep_degree,
                {"einsum": "dense one-hot dispatch/combine einsums "
                           "(parity reference)",
                 "sort": "counting-sort gather/scatter dispatch",
                 "sort_pallas": "counting-sort dispatch + Pallas "
                                "grouped expert GEMM"}[mode]
                + " (docs/moe.md)")

    # -- jitted steps ---------------------------------------------------

    def _build_steps(self):
        module = self.module
        # with pipeline parallelism the module's loss_fn microbatches
        # internally (the pipeline IS the accumulation loop, as in the
        # reference's train_batch, eager_engine.py:406-415)
        if self.topo.pp_degree > 1 and \
                not getattr(module, "supports_pipeline", False):
            raise ValueError(
                f"{type(module).__name__} does not implement internal "
                f"pipeline microbatching (supports_pipeline); pp_degree "
                f"must be 1 for this module")
        if self.topo.cp_degree > 1 and \
                not getattr(module, "supports_context_parallel", False):
            raise ValueError(
                f"{type(module).__name__} has no context-parallel "
                f"(ring) attention; cp_degree must be 1 for this "
                f"module")
        acc = 1 if self.topo.pp_degree > 1 else self.accumulate_steps
        # analytic share of each step's wall time that is pipeline
        # schedule idle (bubble): slot-ticks with no scheduled work
        # over total slot-ticks of the (M, K) grid. Static per config,
        # so clean step windows are apportioned into the
        # pipeline_bubble goodput bucket by this fraction.
        self._pipeline_bubble_share = 0.0
        mcfg = getattr(getattr(self.module, "model", None), "config",
                       None)
        if self.topo.pp_degree > 1 and mcfg is not None:
            from ..parallel import pp_memory
            from ..parallel.pipeline import pipeline_tick_stats
            cfg_sched = getattr(mcfg, "pipeline_schedule", "1F1B")
            h2_depth = 0
            if cfg_sched in ("zb_h2", "zb_auto"):
                # schedule decision: the budget-aware resolution (live
                # param count + batch shape) happens in the module at
                # step-build time; this engine-side pick uses the same
                # ladder without byte inputs — optimistic full depth —
                # purely for the bubble-share estimate and the log line
                pick = pp_memory.resolve_pipeline_schedule(
                    cfg_sched, pp=self.topo.pp_degree,
                    vpp=getattr(mcfg, "virtual_pp_degree", 1),
                    requested_depth=getattr(mcfg, "zb_h2_depth", -1))
                h2_depth = pick["h2_depth"]
                logger.info(
                    "[engine] pipeline schedule %s -> %s "
                    "(h2_depth=%d): %s", cfg_sched, pick["schedule"],
                    h2_depth, pick["reason"])
                cfg_sched = pick["schedule"]
            sched = {"1F1B": "1f1b", "zb": "zb",
                     "zb_h2": "zb_h2"}.get(cfg_sched, "gpipe")
            k_total = self.topo.pp_degree * getattr(
                mcfg, "virtual_pp_degree", 1)
            ts = pipeline_tick_stats(max(1, self.accumulate_steps),
                                     k_total, schedule=sched,
                                     h2_depth=h2_depth)
            self._pipeline_bubble_share = (
                ts["bubble_ticks"] / ts["total_slot_ticks"])
        tx, schedule = self.tx, self.lr_schedule
        root_rng = self.root_rng
        param_shardings = self.state_shardings["params"]

        offload = getattr(self, "_opt_offload", False)
        opt_device_shardings = getattr(self, "_opt_device_shardings",
                                       None)

        def train_step(state, batch):
            """One optimizer step: grad-accum scan + update, jitted."""
            params, opt_state = state["params"], state["opt_state"]
            if offload:
                # host -> HBM for the update; out_shardings put the
                # new state back in pinned host memory (XLA overlaps
                # both DMA legs with compute)
                opt_state = jax.device_put(opt_state,
                                           opt_device_shardings)
            step = state["step"]
            rng = jax.random.fold_in(root_rng, step)

            # optional in the module's contract: ``loss_and_stats``
            # returns ``(loss, {name: scalar})``, the step's own
            # statistics (an expert layer's routing), which leave the
            # jitted step beside the loss and are fetched at the
            # logging sync; ``reduce_step_stats`` merges micro-batches'
            las = getattr(module, "loss_and_stats", None)
            stats = {}

            def loss_for(p, mb, key=rng):
                if las is not None:
                    return las(p, mb, key, train=True)
                return module.loss_fn(p, mb, key, train=True), {}

            if acc == 1:
                # modules may fuse loss+grad into one pass (GPT's 1F1B
                # pipeline schedule computes both in a single scan);
                # default is plain autodiff
                lag = getattr(module, "loss_and_grad", None)
                if lag is not None:
                    loss, grads = lag(params, batch, rng)
                else:
                    (loss, stats), grads = jax.value_and_grad(
                        loss_for, has_aux=True)(params, batch)
            else:
                micro = jax.tree.map(
                    lambda x: x.reshape(acc, x.shape[0] // acc,
                                        *x.shape[1:]), batch)
                # the fp32 grad_sum carry inherits the param
                # PartitionSpecs: left unconstrained the partitioner
                # replicates the whole fp32 gradient tree per chip,
                # which at mp/fsdp > 1 costs more HBM than the sharded
                # params themselves
                zero = jax.tree.map(
                    lambda p, s: jax.lax.with_sharding_constraint(
                        jnp.zeros(p.shape, jnp.float32), s),
                    params, param_shardings)

                def body(carry, mb_with_idx):
                    """Accumulate one microbatch's loss and grads."""
                    mb_idx, mb = mb_with_idx
                    loss_sum, grad_sum = carry
                    # fresh dropout stream per microbatch (the single
                    # step-level rng would repeat masks across the
                    # accumulation scan)
                    mb_rng = jax.random.fold_in(rng, mb_idx)
                    (loss, mb_stats), grads = jax.value_and_grad(
                        loss_for, has_aux=True)(params, mb, mb_rng)
                    grad_sum = jax.tree.map(jnp.add, grad_sum, grads)
                    return (loss_sum + loss, grad_sum), mb_stats

                (loss, grads), stats = jax.lax.scan(
                    body, (jnp.zeros((), jnp.float32), zero),
                    (jnp.arange(acc), micro))
                if stats:
                    stats = module.reduce_step_stats(stats)
                loss = loss / acc
                grads = jax.tree.map(lambda g: g / acc, grads)

            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            metrics = {"loss": loss, "lr": schedule(step),
                       "grad_norm": optax.global_norm(grads), **stats}
            new_state = {"params": new_params, "opt_state": new_opt,
                         "step": step + 1}
            return new_state, metrics

        def eval_step(state, batch):
            # modules may expose a combined jitted eval fn returning
            # {"loss": ..., metric-name: ...} from ONE forward (the
            # classification module's loss + TopkAcc); default is
            # loss_fn alone
            outputs_fn = getattr(module, "eval_outputs_fn", None)
            if outputs_fn is not None:
                return outputs_fn(state["params"], batch)
            return {"loss": module.loss_fn(state["params"], batch,
                                           root_rng, train=False)}

        if self.mode == "train":
            self._train_step = jax.jit(
                train_step, donate_argnums=(0,),
                out_shardings=(self.state_shardings, None))
        self._eval_step = jax.jit(eval_step)

        def predict_step(state, batch):
            return module.predict_step(state["params"], batch, root_rng)

        self._predict_step = jax.jit(predict_step)

    def _put_batch(self, batch):
        """Collated numpy tuple -> global device arrays sharded over the
        dataflow axis (multi-host: each process contributes its slice).
        """
        from ..parallel.mesh import data_world_size, \
            process_data_loader_count
        data_size = data_world_size(self.mesh)
        n_loaders = process_data_loader_count(self.mesh)

        from ..parallel.mesh import CP_AXIS
        cp = self.mesh.shape.get(CP_AXIS, 1)

        def put(x):
            """Shard one host batch array onto the device mesh."""
            x = np.asarray(x)
            # batches indivisible by the dataflow axis (small offline
            # eval sets) are replicated instead of sharded; the check
            # uses the GLOBAL batch dim (local rows x distinct loader
            # ranks), not the process-local one
            global_rows = x.shape[0] * n_loaders
            if global_rows % data_size == 0:
                # context parallel: the sequence dim (axis 1 of token/
                # label/mask arrays) shards over cp at the source.
                # Single-process only: every loader yields the FULL
                # sequence, so under multi-host assembly
                # (make_array_from_process_local_data) a cp-sharded
                # seq spec would stitch wrong halves together — let
                # GSPMD reshard at the first constraint instead.
                rest = [None] * (x.ndim - 1)
                if cp > 1 and x.ndim >= 2 and x.shape[1] % cp == 0 \
                        and jax.process_count() == 1:
                    rest[0] = CP_AXIS
                spec = P(DATA_AXES, *rest)
            else:
                spec = P()
            sharding = NamedSharding(self.mesh, spec)
            if jax.process_count() == 1:
                return jax.device_put(x, sharding)
            return jax.make_array_from_process_local_data(sharding, x)

        return jax.tree.map(put, batch)

    def _prefetch_iter(self, loader, depth=None):
        """Double-buffered device staging: yields
        ``(device_batch, staged)`` with up to ``depth`` batches'
        host->device transfers in flight ahead of the consumer, so
        batch N+1's transfer is ISSUED before the consumer ever blocks
        on step N's result — the transfer rides under the jitted step
        instead of serializing after it (``jax.device_put``
        dispatches asynchronously).

        ``staged`` holds the host seconds this iterator spent staging
        per yielded batch, by phase: ``h2d`` (the whole of it — the
        step loop's observable input stall) and its children
        ``h2d/loader_next`` (the loader's ``next``: collation),
        ``h2d/pretreat`` (``pretreating_batch``) and
        ``h2d/device_put`` (``_put_batch``: the transfer's dispatch,
        and the runtime's back-pressure where it sits there). The
        first yield's record also carries the pipeline fill: it is
        real input latency the first step pays.

        Correctness notes:

        - ``pretreating_batch`` and ``_put_batch`` move inside the
          iterator and keep the loader's order (a FIFO deque), so the
          multi-host collective assembly in ``_put_batch``
          (``make_array_from_process_local_data``) happens in the
          SAME sequence on every process.
        - Preemption/resume accounting is untouched: batches staged
          but never consumed are simply dropped, and
          ``consumed_samples`` is derived from the trained step count
          (``save()``: step * global_batch_size), never from loader
          position — a resume replays the staged-but-untrained
          batches.
        - ``depth <= 0`` degrades to the synchronous per-step put.
        """
        if depth is None:
            depth = self.prefetch_depth
        buf = deque()
        it = iter(loader)
        end = object()

        def stage(staged):
            with annotate("h2d", staged):
                with annotate("h2d/loader_next", staged):
                    batch = next(it, end)
                if batch is end:
                    return False
                with annotate("h2d/pretreat", staged):
                    batch = self.module.pretreating_batch(batch)
                with annotate("h2d/device_put", staged):
                    buf.append(self._put_batch(batch))
            return True

        if depth <= 0:
            while True:
                staged = {}
                if not stage(staged):
                    return
                yield buf.popleft(), staged
        staged = {}
        for _ in range(depth):
            if not stage(staged):
                break
        while buf:
            stage(staged)    # issue batch N+depth before handing out N
            yield buf.popleft(), staged
            staged = {}

    # -- loops ----------------------------------------------------------

    def _finalize_vit_schedule(self, train_data_loader) -> None:
        """Rebuild the ViT LR schedule with the true steps-per-epoch
        (reference computes it from the dataloader at build time).
        Safe before the first step: the optimizer state layout does
        not depend on the schedule."""
        if not getattr(self, "_vit_lr_pending", False):
            return
        self._vit_lr_pending = False
        try:
            steps = len(train_data_loader)
        except TypeError:
            return
        if not steps:
            return
        opt_cfg = self.configs.Optimizer
        opt_cfg.lr["step_each_epoch"] = steps
        self.lr_schedule = build_lr_scheduler(opt_cfg.lr)
        self.tx = self._maybe_lora_tx(
            build_optimizer(opt_cfg, self.lr_schedule))
        self._build_steps()

    def _on_sigterm(self, signum, frame):
        """Preemption notice: set the flag the step loop polls and put
        the signal on the flight record NOW — the grace window may not
        outlast the save at the next step boundary."""
        self._preempt_signum = signum
        if self._recorder is not None:
            self._recorder.emit("sigterm", signum=signum,
                                step=self._host_step)

    def fit(self, epoch: int = 1, train_data_loader=None,
            valid_data_loader=None):
        """Train for ``epoch`` epochs (or ``max_steps``), with eval,
        checkpointing and telemetry per the run config."""
        self._finalize_vit_schedule(train_data_loader)
        del self._step_costs[:]   # per-fit summary samples (registry
        del self._h2d_waits[:]    # aliases — clear, don't rebind)
        self._time_buckets = {"compile": 0.0, "eval": 0.0, "save": 0.0,
                              "pipeline_bubble": 0.0}
        self._fit_t0 = time.time()
        self._compile_pending = True
        self._preempt_signum = None
        if self._recorder is not None:
            self._recorder.emit(
                "fit_start", step=self._host_step, epochs=epoch,
                global_batch_size=self.global_batch_size,
                mesh={str(k): int(v)
                      for k, v in dict(self.mesh.shape).items()})
        self._fit_span = self._tracer.start_trace(
            "engine/fit", start_step=self._host_step, epochs=epoch)
        prev_handler, installed = None, False
        if self.save_on_preemption:
            try:
                prev_handler = signal.signal(signal.SIGTERM,
                                             self._on_sigterm)
                installed = True
            except ValueError:
                # not the main thread: Python only installs signal
                # handlers there, so preemption saves are unavailable
                # in this fit() — worth a line in the log, not a crash
                logger.warning(
                    "save_on_preemption: cannot install SIGTERM "
                    "handler outside the main thread; preemption "
                    "will not checkpoint")
        try:
            self._fit_epochs(epoch, train_data_loader,
                             valid_data_loader)
        finally:
            if installed:   # prev_handler may legitimately be None
                signal.signal(signal.SIGTERM, prev_handler)
            if self._watchdog is not None:
                self._watchdog.disarm()
            self._fit_span.end()   # idempotent: no-op on clean exit

    def _fit_epochs(self, epoch, train_data_loader, valid_data_loader):
        start_epoch = self._load_recovery["epoch"]
        consumed = self._load_recovery["consumed_samples"]
        for ep in range(start_epoch, epoch):
            if train_data_loader is not None and hasattr(
                    train_data_loader, "batch_sampler"):
                train_data_loader.batch_sampler.set_epoch(ep, consumed)
            t0 = time.time()
            self._train_one_epoch(ep, train_data_loader,
                                  valid_data_loader)
            if self._preempt_signum is not None:
                # the signal may also have landed after the epoch's
                # last per-batch check (loader exhaustion) — save
                # here, the single preemption exit path. Before the
                # epoch-end hook: the epoch did NOT complete, and a
                # slow hook would eat the preemption grace window
                step = int(self.state["step"])
                logger.warning(
                    "signal %d (preemption) received: saving "
                    "checkpoint at step %d and stopping cleanly",
                    self._preempt_signum, step)
                if self._recorder is not None:
                    self._recorder.emit("preemption",
                                        signum=self._preempt_signum,
                                        step=step)
                self.save(ep)
                ckpt.wait_for_pending_save()
                break
            self.module.training_epoch_end(
                {"epoch": ep, "train_cost": time.time() - t0})
            if self.run_mode == "epoch" and \
                    (ep + 1) % self.eval_freq == 0 and \
                    valid_data_loader is not None:
                with self.mesh, nn.logical_axis_rules(self.rules):
                    self._evaluate_impl(ep, valid_data_loader,
                                        max_iters=self.eval_iters)
            if (ep + 1) % self.save_epoch == 0 and \
                    int(self.state["step"]) % self.save_steps != 0:
                self.save(ep + 1)
            consumed = 0
            if self._host_step >= self.max_steps:
                # stop the epoch loop too — otherwise an
                # epoch-mode run (num_train_epochs >> steps) spins
                # through empty epochs re-saving checkpoints
                break
        if self._prof_window is not None and self._prof_active:
            jax.block_until_ready(self.state["step"])
            jax.profiler.stop_trace()
            self._prof_active = False
        stats = self._summary_stats()
        if self._summary_enabled():
            self._print_summary(stats)
        # the fit trace closes BEFORE fit_end: the recorder contract
        # pins fit_end as the stream's last fit-scoped record
        self._fit_span.end(step=self._host_step)
        if self._recorder is not None:
            self._recorder.emit(
                "fit_end", step=self._host_step,
                n_windows=len(stats.get("windows", ())),
                **{k: v for k, v in stats.items() if k != "windows"})
        set_mesh(None)

    def _train_one_epoch(self, epoch: int, train_data_loader,
                         valid_data_loader=None):
        step_start = time.time()
        window_clean = True
        # the host's seconds by phase, summed over the steps since the
        # last step_window record (filled by ``annotate``)
        window: Dict[str, float] = {}
        window_steps = 0
        # the training loop's own timeline track — "main" next to the
        # watchdog/loader/server rows in the merged Perfetto view
        tl = obs_timeline.track("main")
        # host-side mirror of state["step"]: reading the device scalar
        # every iteration would sync and kill async dispatch
        step = self._host_step
        with self.mesh, nn.logical_axis_rules(self.rules):
            for batch, staged in self._prefetch_iter(
                    train_data_loader):
                if step >= self.max_steps:
                    return
                self._profiler_step(step)
                if self._watchdog is not None:
                    # armed across the whole host-side body: the jitted
                    # step dispatches async, so a device hang surfaces
                    # at the logging sync / next donation — still
                    # inside this window
                    self._watchdog.arm(tag=f"step {step + 1}")
                for name, seconds in staged.items():
                    window[name] = window.get(name, 0.0) + seconds
                self._h2d_waits.append(staged["h2d"])
                tl_t0 = tl.begin()
                with annotate_step("train", step + 1):
                    with annotate("train_step", window) as dispatch:
                        self.state, metrics = self._train_step(
                            self.state, batch)
                    if self._compile_pending:
                        # the first call traces + compiles before its
                        # async dispatch returns; charge that host time to
                        # the compile bucket and sample memory right after
                        # (the compile-time peak is what OOMs big configs)
                        self._compile_pending = False
                        compile_s = dispatch.seconds
                        self._time_buckets["compile"] += compile_s
                        self._fit_span.complete_span("engine/compile",
                                                     compile_s)
                        if self._recorder is not None:
                            self._recorder.emit(
                                "compile", step=step,
                                seconds=round(compile_s, 4),
                                hbm=self._sample_memory())
                    step += 1
                    self._host_step = step
                    window_steps += 1
                    if step % self.logging_freq == 0:
                        with annotate("engine/log_sync"):
                            metrics = jax.device_get(metrics)
                        cost = (time.time() - step_start) / self.logging_freq
                        mem = self._sample_memory()
                        log_dict = {
                            "epoch": epoch, "batch": step,
                            "loss": float(metrics["loss"]),
                            "lr": float(metrics["lr"]),
                            "grad_norm": float(metrics["grad_norm"]),
                            "train_cost": cost,
                        }
                        if mem is not None:
                            log_dict["hbm_bytes_in_use"] = \
                                mem.get("bytes_in_use")
                            log_dict["hbm_peak_bytes"] = \
                                mem.get("peak_bytes_in_use")
                        self.module.training_step_end(log_dict)
                        # summary samples: only clean windows (a mid-window
                        # eval/save resets step_start, which would skew the
                        # per-step quotient)
                        if window_clean:
                            self._step_costs.append(cost)
                            self._metrics.observe("engine/step_time_ms",
                                                  cost * 1000.0)
                            # steady-state windows only (the first clean
                            # window still holds compile, which the
                            # summary likewise skips via costs[0])
                            if self._pipeline_bubble_share and \
                                    len(self._step_costs) > 1:
                                self._time_buckets["pipeline_bubble"] += (
                                    cost * self.logging_freq *
                                    self._pipeline_bubble_share)
                        if self._recorder is not None:
                            # the host's seconds per step by phase,
                            # as means over the window's steps
                            per_step = {
                                k: round(v / window_steps, 5)
                                for k, v in window.items()}.get
                            self._recorder.emit(
                                "step_window", step=step,
                                loss=log_dict["loss"], lr=log_dict["lr"],
                                grad_norm=log_dict["grad_norm"],
                                step_time=round(cost, 5),
                                h2d_wait=per_step("h2d", 0.0),
                                loader_next=per_step("h2d/loader_next", 0.0),
                                pretreat=per_step("h2d/pretreat", 0.0),
                                device_put=per_step("h2d/device_put", 0.0),
                                dispatch=per_step("train_step", 0.0),
                                hbm=mem,
                                # the module's own step statistics
                                **{k: float(v) for k, v in metrics.items()
                                   if k not in log_dict})
                        window.clear()
                        window_steps = 0
                        window_clean = True
                        step_start = time.time()
                    tl.add("step", tl_t0)
                if self.run_mode == "step" and \
                        step % self.eval_freq == 0 and \
                        valid_data_loader is not None:
                    self._evaluate_impl(epoch, valid_data_loader,
                                        max_iters=self.eval_iters)
                    step_start = time.time()
                    window_clean = False
                if step % self.save_steps == 0:
                    self.save(epoch)
                    step_start = time.time()
                    window_clean = False
                if self._faults is not None:
                    # after the save cadence: kill@step=N dies with
                    # every save <= N durable, the shape chaos tests
                    # assert resume-determinism against
                    self._faults.fire("step", step)
                if self._watchdog is not None:
                    self._watchdog.disarm()
                if self._preempt_signum is not None:
                    return   # _fit_epochs saves, then stops

    def _summary_enabled(self) -> bool:
        """Whether fit() ends with the host-time summary: an explicit
        ``Engine.print_summary`` wins; otherwise on iff profiling or
        telemetry is on (the pre-observability behavior gated it on
        the profiler window alone, leaving unprofiled runs mute)."""
        if self._print_summary_cfg is not None:
            return bool(self._print_summary_cfg)
        return self._prof_window is not None or self._tele_enabled

    def _sample_memory(self):
        """HBM sample at a window edge / after compile; tracks the run
        watermark for the summary. None where the backend keeps no
        allocator stats (CPU) or telemetry is off."""
        if not self._tele_enabled:
            return None
        mem = device_memory_stats(self.mesh.devices.flat[0])
        if mem:
            keep = dict(self._hbm_watermark or {})
            for k, v in mem.items():
                keep[k] = v if k == "bytes_limit" else \
                    max(keep.get(k, 0), v)
            self._hbm_watermark = keep
            self._metrics.set_gauge("hbm/peak_bytes_in_use",
                                    keep.get("peak_bytes_in_use"))
        return mem

    def _summary_stats(self) -> Dict[str, Any]:
        """The machine-readable run summary: step-time windows, h2d
        waits, throughput, model FLOPs + MFU (single source:
        ``observability.flops``), goodput buckets, HBM watermark and
        the global dispatch counters. ``_print_summary`` renders it;
        the flight recorder's ``fit_end`` event carries it."""
        costs = list(self._step_costs)
        stats: Dict[str, Any] = {"windows": costs,
                                 "logging_freq": self.logging_freq}
        mean = 0.0
        if costs:
            # skip the first window: it usually contains the compile
            steady = costs[1:] or costs
            mean = sum(steady) / len(steady)
            stats["first_window_s_per_step"] = costs[0]
            stats["steady_mean_s_per_step"] = mean
            stats["steady_min_s_per_step"] = min(steady)
            stats["steady_max_s_per_step"] = max(steady)
        if self._h2d_waits:
            # first wait carries the pipeline fill; report it apart
            waits = self._h2d_waits[1:] or self._h2d_waits
            stats["h2d_fill_s"] = self._h2d_waits[0]
            stats["h2d_mean_s"] = sum(waits) / len(waits)
            stats["h2d_max_s"] = max(waits)
        from .module import LanguageModule
        seq = self.configs.get("Data", {}).get("Train", {}).get(
            "dataset", {}).get("max_seq_len", 0)
        tokens = self.global_batch_size * seq
        # tokens/s only means something for language modules (vision/
        # multimodal step logs already carry images/sec)
        if tokens and mean > 0 and isinstance(self.module,
                                              LanguageModule):
            tps = tokens / mean
            stats["tokens_per_sec"] = tps
            mcfg = getattr(getattr(self.module, "model", None),
                           "config", None)
            L = getattr(mcfg, "num_layers", 0)
            h = getattr(mcfg, "hidden_size", 0)
            V = getattr(mcfg, "vocab_size", 0)
            if L and h and V:
                fpt = obs_flops.model_flops_per_token(L, h, V, seq)
                n_dev = int(self.mesh.devices.size)
                peak = obs_flops.peak_flops(self.mesh.devices.flat[0])
                stats["model_flops_per_token"] = fpt
                stats["achieved_tflops"] = tps * fpt / 1e12
                stats["mfu"] = obs_flops.mfu(tps, fpt, peak, n_dev)
        if self._fit_t0 is not None:
            total = max(time.time() - self._fit_t0, 1e-9)
            h2d = sum(self._h2d_waits)
            b = self._time_buckets
            bubble = b.get("pipeline_bubble", 0.0)
            productive = max(
                total - b["compile"] - b["eval"] - b["save"] - h2d
                - bubble,
                0.0)
            stats["wall_total_s"] = total
            stats["bucket_compile_s"] = b["compile"]
            stats["bucket_eval_s"] = b["eval"]
            stats["bucket_save_s"] = b["save"]
            stats["bucket_h2d_s"] = h2d
            stats["bucket_pipeline_bubble_s"] = bubble
            stats["goodput_pct"] = 100.0 * productive / total
        if self._hbm_watermark:
            stats["hbm_bytes_in_use"] = \
                self._hbm_watermark.get("bytes_in_use")
            stats["hbm_peak_bytes"] = \
                self._hbm_watermark.get("peak_bytes_in_use")
            stats["hbm_bytes_limit"] = \
                self._hbm_watermark.get("bytes_limit")
        g = obs_metrics.get_registry()
        if g.enabled:
            counters = g.snapshot()["counters"]
            if counters:
                stats["dispatch_counters"] = counters
        return stats

    def _print_summary(self, stats: Optional[Dict[str, Any]] = None) \
            -> None:
        """Post-run host-time summary (reference ``_print_summary``
        prints device-time tables; the device view here lives in the
        XProf trace — this prints the step-time overview)."""
        if stats is None:
            stats = self._summary_stats()
        costs = stats.get("windows") or []
        if not costs:
            return
        mean = stats["steady_mean_s_per_step"]
        logger.info("-" * 60)
        logger.info("Profiler summary (host step times, %d windows of "
                    "%d steps)", len(costs), self.logging_freq)
        logger.info("  first window (incl. compile): %.4f s/step",
                    costs[0])
        logger.info("  steady state: mean %.4f / min %.4f / max %.4f "
                    "s/step (%.2f step/s)", mean,
                    stats["steady_min_s_per_step"],
                    stats["steady_max_s_per_step"],
                    1.0 / mean if mean else 0.0)
        if "h2d_mean_s" in stats:
            logger.info("  h2d input wait: mean %.4f / max %.4f s/step "
                        "after fill %.4f s (prefetch depth %d)",
                        stats["h2d_mean_s"], stats["h2d_max_s"],
                        stats["h2d_fill_s"], self.prefetch_depth)
        try:
            probe = self._mp_collective_probe()
        except Exception as exc:   # the probe must never kill the
            logger.info("  mp collective: probe failed (%s)", exc)
            probe = None           # summary it decorates
        if probe is not None:
            pair_t, path, n_layers = probe
            logger.info(
                "  mp collective: %.4f s per column+row linear pair "
                "(%s); ~%.4f s/step forward estimate (%d layers x 2 "
                "pairs)", pair_t, path, pair_t * 2 * n_layers,
                n_layers)
        if (self.configs.get("Profiler", {}) or {}).get("detailed"):
            # reference Profiler.detailed prints the full table views;
            # the host-side analogue is every window's timing
            for i, c in enumerate(costs):
                logger.info("    window %3d: %.4f s/step", i, c)
        if "tokens_per_sec" in stats:
            logger.info("  throughput: %.0f tokens/s (global batch %d)",
                        stats["tokens_per_sec"], self.global_batch_size)
        if "model_flops_per_token" in stats:
            mfu = stats.get("mfu")
            logger.info(
                "  model FLOPs: %.3e /token; achieved %.2f TFLOP/s; "
                "MFU %s", stats["model_flops_per_token"],
                stats["achieved_tflops"],
                "%.4f of aggregate bf16 peak" % mfu if mfu is not None
                else "n/a (no calibrated peak for this device)")
        if "goodput_pct" in stats:
            logger.info(
                "  goodput: %.1f%% productive step time of %.1f s "
                "wall (compile %.2f / eval %.2f / save %.2f / h2d "
                "%.2f / pipeline_bubble %.2f s)", stats["goodput_pct"],
                stats["wall_total_s"], stats["bucket_compile_s"],
                stats["bucket_eval_s"], stats["bucket_save_s"],
                stats["bucket_h2d_s"],
                stats.get("bucket_pipeline_bubble_s", 0.0))
        logger.info(
            "  HBM watermark: %s",
            "%s in use / %s peak of %s" % (
                format_bytes(stats["hbm_bytes_in_use"]),
                format_bytes(stats["hbm_peak_bytes"]),
                format_bytes(stats.get("hbm_bytes_limit")))
            if "hbm_peak_bytes" in stats
            else "unavailable (backend keeps no memory stats)")
        if "dispatch_counters" in stats:
            logger.info("  dispatch counters: %s",
                        stats["dispatch_counters"])
        prof_dir = getattr(self, "_prof_dir", None)
        if prof_dir:
            logger.info("  device-time breakdown: open %s with "
                        "TensorBoard's profile plugin", prof_dir)
        if self._recorder is not None:
            logger.info("  flight record: %s", self._recorder.path)
        logger.info("-" * 60)

    def _mp_collective_probe(self):
        """Time one column+row tensor-parallel linear pair
        (``[b, s, h] @ [h, ffn] @ [ffn, h]``) on the live mesh — the
        decomposed rings when the model dispatches to them, the plain
        GSPMD all-gather/reduce-scatter lowering otherwise — so the
        profiler summary records what the mp collectives cost this
        run. Returns ``(seconds_per_pair, path, num_layers)`` or None
        when mp is not in play (mp < 2, or no GPT-shaped config)."""
        from ..parallel.mesh import DATA_AXES, MP_AXIS
        mesh = self.mesh
        mp = mesh.shape.get(MP_AXIS, 1)
        mcfg = getattr(getattr(self.module, "model", None), "config",
                       None)
        hidden = getattr(mcfg, "hidden_size", 0)
        if mp < 2 or not hidden:
            return None
        ffn = getattr(mcfg, "ffn_hidden_size", None) or 4 * hidden
        n_layers = getattr(mcfg, "num_layers", 1)
        bsz = int(np.prod([mesh.shape[a] for a in DATA_AXES]))
        b = max(self.micro_batch_size, bsz)
        b -= b % bsz
        seq = self.configs.get("Data", {}).get("Train", {}).get(
            "dataset", {}).get("max_seq_len", 0) or getattr(
            mcfg, "max_position_embeddings", mp)
        seq = max(seq - seq % mp, mp)
        dtype = jnp.dtype(getattr(mcfg, "dtype", "float32"))

        from ..ops.collective_matmul import (
            all_gather_matmul, matmul_reduce_scatter, mp_ring_viable,
        )
        use_rings = (getattr(mcfg, "sequence_parallel", False)
                     and mp_ring_viable(mesh, b, seq, (ffn,)))
        seq_s = NamedSharding(mesh, P(DATA_AXES, MP_AXIS, None))
        col_s = NamedSharding(mesh, P(DATA_AXES, None, MP_AXIS))
        x = jax.device_put(jnp.ones((b, seq, hidden), dtype), seq_s)
        w1 = jax.device_put(jnp.ones((hidden, ffn), dtype),
                            NamedSharding(mesh, P(None, MP_AXIS)))
        w2 = jax.device_put(jnp.ones((ffn, hidden), dtype),
                            NamedSharding(mesh, P(MP_AXIS, None)))

        if use_rings:
            path = "decomposed overlapped rings"

            def pair(x, w1, w2):
                y = all_gather_matmul(x, w1, mesh)
                return matmul_reduce_scatter(y, w2, mesh)
        else:
            path = "plain GSPMD all-gather/reduce-scatter"

            def pair(x, w1, w2):
                y = jax.lax.with_sharding_constraint(x @ w1, col_s)
                return jax.lax.with_sharding_constraint(y @ w2, seq_s)

        fn = jax.jit(pair)
        reps = 3
        with mesh, annotate("mp_collective_probe"):
            jax.block_until_ready(fn(x, w1, w2))   # compile outside
            t0 = time.time()                       # the timed window
            for _ in range(reps):
                out = fn(x, w1, w2)
            jax.block_until_ready(out)
        return (time.time() - t0) / reps, path, n_layers

    def _profiler_step(self, step: int) -> None:
        """Start/stop the jax.profiler trace at the configured window
        edges; the trace lands in ``profiler_log`` for TensorBoard /
        XProf (the reference's chrome-trace export + VisualDL pointer,
        ``eager_engine.py:684-743``)."""
        if self._prof_window is None:
            return
        start, stop = self._prof_window
        # range check, not equality: a resume landing past `start`
        # still traces the remaining window
        if start <= step < stop and not self._prof_active:
            jax.profiler.start_trace(self._prof_dir)
            self._prof_active = True
        elif step >= stop and self._prof_active:
            # block on the last dispatched step so its device activity
            # is inside the trace
            jax.block_until_ready(self.state["step"])
            jax.profiler.stop_trace()
            self._prof_active = False
            logger.info(
                "profiler trace written to %s (view with TensorBoard's "
                "profile plugin / XProf)", self._prof_dir)

    def _evaluate_impl(self, epoch: int, valid_data_loader,
                       max_iters: Optional[int] = None):
        """Mid-train eval caps at ``eval_iters``; offline ``evaluate``
        walks the whole loader (reference ``_evaluate_one_epoch``)."""
        losses = []
        t0 = time.time()
        if self._recorder is not None:
            self._recorder.emit("eval_start", step=self._host_step,
                                epoch=epoch)
        with annotate("eval"):
            for i, (batch, _staged) in enumerate(
                    self._prefetch_iter(valid_data_loader)):
                if max_iters is not None and i >= max_iters:
                    break
                if self._preempt_signum is not None:
                    # preemption grace windows are short; don't let a
                    # long eval pass outlive them — the preemption
                    # checkpoint in _fit_epochs is what matters
                    break
                with annotate("eval_step"):
                    out = self._eval_step(self.state, batch)
                losses.append(float(out["loss"]))
                extra = {k: float(v) for k, v in out.items()
                         if k != "loss"}
                self.module.validation_step_end({
                    "epoch": epoch, "batch": i, "loss": losses[-1],
                    "eval_cost": (time.time() - t0) / (i + 1), **extra})
        mean = float(np.mean(losses)) if losses else float("nan")
        eval_s = time.time() - t0
        self._time_buckets["eval"] += eval_s
        self._metrics.add_time("eval", eval_s)
        if self._recorder is not None:
            self._recorder.emit("eval_end", step=self._host_step,
                                epoch=epoch, loss=mean,
                                n_batches=len(losses),
                                eval_s=round(eval_s, 4))
        self.module.validation_epoch_end(
            {"epoch": epoch, "loss": mean,
             "eval_cost": eval_s})
        return mean

    def evaluate(self, epoch: int = 1, valid_data_loader=None):
        with self.mesh, nn.logical_axis_rules(self.rules):
            return self._evaluate_impl(epoch, valid_data_loader)

    def predict(self, epoch: int = 1, test_data_loader=None):
        """Test-set walk (reference ``eager_engine.py:531-583``): each
        batch runs ``module.predict_step`` (default: eval-mode loss),
        host hooks fire via ``test_step_end``, capped at test_iters."""
        outs = []
        t0 = time.time()
        with self.mesh, nn.logical_axis_rules(self.rules):
            for i, (batch, _staged) in enumerate(
                    self._prefetch_iter(test_data_loader)):
                if i >= self.test_iters:
                    logger.info("The predicting process is complete.")
                    break
                out = jax.device_get(
                    self._predict_step(self.state, batch))
                outs.append(out)
                arr = out.get("loss") if isinstance(out, dict) else out
                self.module.test_step_end({
                    "epoch": epoch, "batch": i,
                    # dict outputs without a loss entry log nan
                    "loss": float(np.mean(arr)) if arr is not None
                    else float("nan"),
                    "test_cost": (time.time() - t0) / (i + 1)})
        return outs

    # -- checkpoint -----------------------------------------------------

    def save(self, epoch: int = 0):
        """Checkpoint the train state (+ resume metadata) via orbax."""
        # every process participates: orbax coordinates multi-host
        # saves internally (unlike the reference's dp_rank-0-only
        # writes, eager_engine.py:590-592)
        step = int(self.state["step"])
        meta = {
            "epoch": epoch, "step": step,
            "consumed_samples": step * self.global_batch_size,
            "seed": int(self.configs.Global.get("seed", 1024)),
        }
        with annotate("save") as saving:
            path = ckpt.save_checkpoint(self.output_dir, epoch, step,
                                        self.state, meta,
                                        async_save=self.async_save)
        save_s = saving.seconds
        self._time_buckets["save"] += save_s
        self._metrics.add_time("save", save_s)
        self._fit_span.complete_span("engine/save", save_s, step=step)
        if self._recorder is not None:
            self._recorder.emit("save", step=step, epoch=epoch,
                                save_s=round(save_s, 4),
                                async_save=bool(self.async_save))
        self._save_count += 1
        if self._faults is not None:
            # kill@save=N dies mid-async-save (manifest uncommitted —
            # resolve must skip the torn dir); corrupt_ckpt@save=N
            # garbles the committed artifact (restore must fall back)
            self._faults.fire("save", self._save_count, path=path)
        if self.keep_last_k:
            ckpt.gc_checkpoints(self.output_dir, self.keep_last_k,
                                recorder=self._recorder)

    def load(self):
        """Restore the latest VERIFIED checkpoint under ``ckpt_dir``,
        if any; a corrupt newest falls back to its predecessor with a
        ``ckpt_fallback`` event (docs/robustness.md)."""
        path = ckpt.latest_checkpoint(self.ckpt_dir,
                                      recorder=self._recorder)
        if path is None:
            logger.warning("no checkpoint found under %s; starting fresh",
                           self.ckpt_dir)
            return
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            self.state)
        fallback = self.ckpt_dir if os.path.isdir(self.ckpt_dir) and \
            not ckpt._STEP_DIR.search(self.ckpt_dir) else \
            os.path.dirname(path)
        self.state, meta = ckpt.load_checkpoint(
            path, abstract, fallback_dir=fallback,
            recorder=self._recorder)
        self._load_recovery = {
            "epoch": meta.get("epoch", 0),
            "step": meta.get("step", 0),
            "consumed_samples": meta.get("consumed_samples", 0),
        }
        self._host_step = self._load_recovery["step"]
        logger.info("resumed at epoch %s step %s",
                    self._load_recovery["epoch"],
                    self._load_recovery["step"])

    # -- export / inference --------------------------------------------

    def export(self) -> str:
        """AOT-export the module's inference function + params
        (reference ``engine.export`` -> ``paddle.jit.to_static`` +
        per-rank save, ``eager_engine.py:667-674``; here one portable
        ``jax.export`` artifact, ``utils/export.py``)."""
        from ..utils.export import export_inference_model
        export_fn = getattr(self.module, "export_fn", None)
        if export_fn is not None:
            fn, spec, metadata = export_fn()
        else:
            model = self.module.model
            fn = lambda p, *inputs: model.apply(  # noqa: E731
                {"params": p}, *inputs, deterministic=True)
            spec = self.module.input_spec()[:1]
            metadata = {}
        out_dir = os.path.join(self.output_dir, "export")
        param_shardings = self.state_shardings["params"]

        def _really_split(entry):
            # a spec entry only partitions if its mesh axis size > 1
            axes = entry if isinstance(entry, tuple) else (entry,)
            return any(a is not None and self.mesh.shape[a] > 1
                       for a in axes)

        partitioned = any(
            any(_really_split(e) for e in s.spec)
            for s in jax.tree.leaves(param_shardings))
        export_mesh = self.mesh
        if self.mesh.devices.size > 1 and not partitioned:
            # dp/replicated-only training (mp=pp=fsdp=1): every rank
            # holds the full model, so export a SINGLE-device artifact
            # — exporting under the dp mesh would bake its device
            # count into the StableHLO and a 1-chip serving box could
            # never load it (the dp inference mode is one such
            # artifact per rank). Same axis names, all sizes 1, so the
            # model's logical constraints still resolve.
            export_mesh = jax.sharding.Mesh(
                np.asarray([self.mesh.devices.flat[0]]).reshape(
                    (1,) * len(self.mesh.axis_names)),
                self.mesh.axis_names)
        elif partitioned:
            # record how to re-partition the artifact: the exported
            # StableHLO bakes the mesh SIZE (jax.export nr_devices) but
            # not parameter placement — the loader rebuilds
            # NamedShardings from these specs on ITS mesh, which must
            # have the same axis names/sizes (the TPU-native analogue
            # of the reference's per-rank model dirs,
            # ``core/engine/inference_engine.py:60-131``)
            from ..utils.export import serialize_param_specs
            metadata = dict(metadata or {})
            metadata["num_export_devices"] = int(self.mesh.devices.size)
            metadata["mesh_axes"] = {
                name: int(size) for name, size in
                zip(self.mesh.axis_names, self.mesh.devices.shape)}
            metadata["param_specs"] = serialize_param_specs(
                param_shardings)
        with export_mesh, nn.logical_axis_rules(self.rules):
            return export_inference_model(
                fn, self.state["params"], spec, out_dir,
                metadata=metadata)

    def inference(self, data):
        """Run the exported artifact (reference
        ``eager_engine.py:676-682`` builds an ``InferenceEngine`` from
        the ``Inference`` config section)."""
        if not hasattr(self, "_inference_engine"):
            from .inference_engine import InferenceEngine
            inf_cfg = dict(self.configs.get("Inference", {}))
            model_dir = inf_cfg.get("model_dir", self.output_dir)
            candidate = os.path.join(model_dir, "export")
            if os.path.isdir(candidate):
                model_dir = candidate
            self._inference_engine = InferenceEngine(
                model_dir, mp_degree=inf_cfg.get("mp_degree", 1))
        return self._inference_engine.predict(data)
