"""Language-model config derivations.

Parity: reference ``ppfleetx/models/language_model/utils.py:39-150``:
  - ``process_data_configs`` (:117-141): per-mode ``num_samples``
    (train = gbs * max_steps; eval = gbs * (max_steps/eval_freq + 1) *
    eval_iters; test = gbs * test_iters), seed and batch-size plumbing.
  - ``process_model_configs`` (:56-110): ffn defaults to 4*hidden,
    recompute granularity default, virtual-pp divisibility checks.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp


def masked_nll_sums(logits: jax.Array, labels: jax.Array,
                    loss_mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Fp32 masked token NLL: ``(sum of nll over unmasked, mask sum)``.

    The shared core of the pretraining criterion and the offline-eval
    scorer; with vocab-sharded logits GSPMD turns the log-sum-exp and
    gather into the psum-based sharded softmax the reference's
    ``ParallelCrossEntropy`` (``hybrid_model.py:799``) hand-writes.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    mask = loss_mask.astype(jnp.float32).reshape(logz.shape)
    return jnp.sum((logz - label_logits) * mask), jnp.sum(mask)


def chunked_nll_sums(h: jax.Array, logits_fn: Callable, labels: jax.Array,
                     loss_mask: jax.Array,
                     chunks: int) -> Tuple[jax.Array, jax.Array]:
    """``masked_nll_sums(logits_fn(h), ...)`` over ``chunks`` sequence
    chunks inside a rematerialized scan: the ``[b, s, V]`` logits never
    exist beyond ``[b, s/chunks, V]``, and the backward recomputes each
    chunk's logits instead of saving them. The per-token NLL sums are
    exact, not chunk-mean-of-means. ``h`` is ``[b, s, hidden]``."""
    b, s = labels.shape
    if s % chunks:
        raise ValueError(
            f"loss_chunks ({chunks}) must divide the sequence length "
            f"({s})")
    csz = s // chunks
    hc = h.reshape(b, chunks, csz, h.shape[-1]).swapaxes(0, 1)
    lc = labels.reshape(b, chunks, csz).swapaxes(0, 1)
    mc = loss_mask.reshape(b, chunks, csz).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, xs):
        hh, ll, mm = xs
        nll, msum = masked_nll_sums(logits_fn(hh), ll, mm)
        return (carry[0] + nll, carry[1] + msum), None

    sums, _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc, mc))
    return sums


def process_model_configs(config) -> None:
    """Derive/validate model-section defaults in place — ffn=4h,
    recompute granularity, virtual-pp divisibility (reference
    ``models/language_model/utils.py:39-110``)."""
    model = config.Model
    if model.get("ffn_hidden_size") is None:
        model["ffn_hidden_size"] = 4 * model["hidden_size"]
    if model.get("use_recompute"):
        if not model.get("recompute_granularity"):
            model["recompute_granularity"] = "full"
    vpp = model.get("virtual_pp_degree") or 1
    pp = config.Distributed.pp_degree
    if pp > 1:
        if model["num_layers"] % pp != 0:
            raise ValueError(
                f"num_layers {model['num_layers']} must be divisible by "
                f"pp_degree {pp}")
        if model.get("scan_layers") is False:
            # same policy as loss_chunks below: the single-chip recipe
            # sets scan_layers False for throughput, and a -o
            # pp_degree override on top of it must not be fatal —
            # pipeline stages need the stacked decoder params, so the
            # knob flips back with a log line
            from ..utils.log import logger
            logger.info("pp_degree > 1 needs scan-stacked decoder "
                        "params; overriding scan_layers False -> True")
            model["scan_layers"] = True
        if (model.get("loss_chunks") or 1) > 1:
            # the pipeline computes the loss per microbatch, which IS
            # the logits-memory property loss_chunks exists for — the
            # knob is subsumed, not silently dropped (a base config
            # default must not make every pp override fatal)
            from ..utils.log import logger
            logger.info("pp_degree > 1 computes per-microbatch logits; "
                        "loss_chunks=%s is subsumed and reset to 1",
                        model["loss_chunks"])
            model["loss_chunks"] = 1
    if vpp > 1:
        local_batch_size = config.Global.local_batch_size
        micro_batch_size = config.Global.micro_batch_size
        if local_batch_size // micro_batch_size % pp != 0:
            raise ValueError(
                "micro-batch count must divide pp_degree with virtual "
                "pipeline stages")
        if model["num_layers"] % (vpp * pp) != 0:
            raise ValueError(
                f"num_layers {model['num_layers']} must be divisible by "
                f"virtual_pp_degree*pp_degree {vpp * pp}")
    if model.get("sequence_parallel") and \
            config.Distributed.mp_degree <= 1:
        # reference forces SP off when mp<=1 (hybrid_model.py:649-652)
        model["sequence_parallel"] = False
    cp = config.Distributed.get("cp_degree") or 1
    if cp > 1 and model.get("context_parallel_algo") == "ulysses":
        mp = config.Distributed.mp_degree or 1
        heads = model["num_attention_heads"]
        if heads % (cp * mp):
            raise ValueError(
                f"Ulysses context parallelism shards attention heads "
                f"over cp*mp: num_attention_heads ({heads}) must be "
                f"divisible by cp_degree*mp_degree ({cp * mp})")
    n_experts = model.get("moe_num_experts") or 0
    if n_experts:
        if pp > 1 and str(
                model.get("pipeline_schedule", "1F1B")).lower() == \
                "gpipe":
            raise ValueError(
                "MoE with pipeline parallelism requires "
                "pipeline_schedule '1F1B', 'zb', 'zb_h2' or 'zb_auto' "
                "(GPipe trains via autodiff through the forward-only "
                "schedule, which drops the per-layer router aux loss)")
        ep = config.Distributed.get("ep_degree") or 1
        if n_experts % ep != 0:
            raise ValueError(
                f"moe_num_experts ({n_experts}) must be divisible by "
                f"ep_degree ({ep})")


def process_data_configs(config) -> None:
    """Derive per-mode ``num_samples`` from the step/eval cadence
    (reference ``models/language_model/utils.py:113-150``)."""
    g = config.Global
    engine = config.Engine
    max_steps = engine.get("max_steps", 500000)
    eval_freq = engine.get("eval_freq") or max(max_steps, 1)
    eval_iters = engine.get("eval_iters", 10)
    test_iters = engine.get("test_iters", eval_iters * 10)
    mode_to_num_samples = {
        "Train": g.global_batch_size * max_steps,
        "Eval": g.global_batch_size *
        (max_steps // eval_freq + 1) * eval_iters,
        "Test": g.global_batch_size * test_iters,
    }
    for mode, num in mode_to_num_samples.items():
        if mode in config.get("Data", {}):
            dataset = config.Data[mode]["dataset"]
            dataset.setdefault("num_samples", num)
            dataset.setdefault("mode", mode)
            dataset.setdefault("seed", g.get("seed", 1024))
            sampler = config.Data[mode].setdefault("sampler", {})
            sampler.setdefault("batch_size", g.local_batch_size)


def process_configs(config):
    process_model_configs(config)
    process_data_configs(config)
    return config
