"""Distributed environment init and the seed/RNG policy.

Parity with reference ``ppfleetx/utils/env.py``:
  - ``set_seed`` (:27-46): python/numpy seeds offset by the dataflow
    (dp x sharding) rank; a *global* dropout stream shared across mp
    ranks and a *local* stream offset by ``mp_rank*10 + pp_rank*1000``.
    On TPU the same guarantees come from ``jax.random`` key folding:
    dropout on TP-sharded activations is computed from one global key
    (so it is replicated-consistent by construction under GSPMD), and
    per-shard streams are derived with ``fold_in``.
  - ``init_dist_env`` (:49-69): builds the communicate topology; here
    that is mesh construction (see ``parallel.mesh``) plus optional
    ``jax.distributed.initialize`` for multi-host pods.
"""

from __future__ import annotations

import os
import random
from typing import Optional

import jax
import numpy as np

from .log import logger

GLOBAL_STREAM = "global_seed"
LOCAL_STREAM = "local_seed"


def init_dist_env(coordinator: Optional[str] = None,
                  num_processes: Optional[int] = None,
                  process_id: Optional[int] = None) -> None:
    """Initialize multi-host JAX if launched as part of a pod.

    Single-process runs (one host owning all chips, or CPU tests) need
    no rendezvous. On Cloud TPU pods ``jax.distributed.initialize()``
    auto-discovers peers from the metadata server.
    """
    if num_processes is None and os.environ.get("PFX_NUM_PROCESSES"):
        num_processes = int(os.environ["PFX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("PFX_PROCESS_ID"):
        process_id = int(os.environ["PFX_PROCESS_ID"])
    if num_processes is not None and num_processes > 1 or \
            os.environ.get("PFX_COORDINATOR") or coordinator:
        jax.distributed.initialize(
            coordinator_address=coordinator or os.environ.get(
                "PFX_COORDINATOR"),
            num_processes=num_processes, process_id=process_id)
        logger.info("jax.distributed initialized: process %d / %d",
                    jax.process_index(), jax.process_count())


#: the checkout (or install prefix) this package lives in — the
#: default cache sits beside it at a path that never moves, because
#: the path is part of the cache key
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup_compilation_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Every entry point that compiles calls this (Engine,
    the serving/inference tasks, chipbench/run.py, chip_smoke.py): a cold
    compile of the unrolled 24-layer step is minutes, and both
    preempted-and-restarted jobs and every chip-tool call start cold.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that is the cache and
    this function sets no directory of its own (JAX reads the
    variable itself) — the cache can be placed from outside. Otherwise
    ``cache_dir`` (``Global.compilation_cache_dir``), else
    ``<checkout>/.xla_cache``: a fixed path, never a temp name.
    """
    # cache every program: the default thresholds skip fast compiles,
    # but a restart replays *all* of them, so small entries pay too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    cache_dir = os.path.abspath(os.path.expanduser(cache_dir)) \
        if cache_dir else os.path.join(_CHECKOUT, ".xla_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    logger.info("persistent compilation cache at %s", cache_dir)
    return cache_dir


def set_seed(seed: int, data_rank: int = 0) -> jax.Array:
    """Seed host RNGs (offset by dataflow rank) and return the root key.

    The returned key is the single source of device-side randomness;
    the engine folds in step counts and stream names from it.
    """
    random.seed(seed + data_rank)
    np.random.seed(seed + data_rank)
    return jax.random.key(seed + data_rank)


def local_stream_key(root: jax.Array, mp_rank: int = 0,
                     pp_rank: int = 0) -> jax.Array:
    """Per-shard dropout stream, mirroring ``seed+123+mp*10+pp*1000``."""
    return jax.random.fold_in(root, 123 + mp_rank * 10 + pp_rank * 1000)


def get_local_rank() -> int:
    return jax.process_index()


def device_kind() -> str:
    return jax.devices()[0].device_kind


def is_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"
