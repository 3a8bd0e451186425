"""Analytic per-stage HBM model for the pipeline schedule family.

ZB-H2 (``parallel/pipeline.py``) spends memory to kill the fill-phase
bubble: each extra warm-up forward is one more stashed microbatch
activation, and the deferred-dW FIFO grows a cotangent ring row per
depth step. This module prices that spend — dtype-aware byte
accounting per *physical* pipeline stage (the unit one device along
the ``pp`` mesh axis holds), in the same spirit as the byte math in
paging/quantization — validates a requested depth against a device
memory budget BEFORE anything is traced (a clean ``ValueError``
instead of an OOM deep inside XLA), and powers the ``zb_auto``
schedule chooser: pick the deepest feasible point on the
``1F1B -> zb -> zb_h2@depth`` ladder and say why.

The model counts the schedule-dependent residents of one stage:

  - parameters: ``param_count / pp`` in ``param_dtype`` (the stacked
    decoder dominates; embeddings/head are compute-replicated),
  - gradients: the same count in fp32 (the schedules accumulate
    microbatch grads in fp32),
  - activation ring: ``vpp * 2K`` microbatch activations in the
    compute dtype (depth 2K for every schedule in the family — the
    just-in-time dW pops keep it so, ``zb_dw_schedule``),
  - cotangent ring (zb family only): ``vpp * (K + depth + 1)``
    microbatch cotangents in the compute dtype — the term that grows
    with ZB-H2 depth,
  - wave buffers: the forward state plus two fp32 backward-wave
    buffers.

Optimizer state is deliberately out of scope (it is schedule-
independent; the planner of ROADMAP item 5 owns that axis). The
budget defaults to the device's ``bytes_limit`` from
``observability.memory.device_memory_stats`` and can be pinned with
``PFX_PP_HBM_BUDGET_BYTES`` (docs/observability.md) — useful both for
tests and for reserving headroom below the physical limit.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

__all__ = [
    "dtype_bytes",
    "stage_memory_bytes",
    "hbm_budget_bytes",
    "max_feasible_h2_depth",
    "resolve_pipeline_schedule",
]

_DTYPE_BYTES = {
    "float64": 8, "fp64": 8,
    "float32": 4, "fp32": 4,
    "bfloat16": 2, "bf16": 2,
    "float16": 2, "fp16": 2,
    "int8": 1, "uint8": 1, "fp8": 1,
}


def dtype_bytes(dtype) -> int:
    """Bytes per element for a dtype name or numpy-like dtype."""
    name = getattr(dtype, "name", None) or str(dtype)
    try:
        return _DTYPE_BYTES[name.lower()]
    except KeyError:
        raise ValueError(f"unknown dtype {dtype!r} for byte "
                         f"accounting") from None


def stage_memory_bytes(*, schedule: str, pp: int, vpp: int = 1,
                       microbatch_tokens: int, hidden_size: int,
                       param_count: int, h2_depth: int = 0,
                       compute_dtype: str = "float32",
                       param_dtype: str = "float32") -> dict:
    """Analytic HBM residents of ONE physical pipeline stage.

    ``microbatch_tokens`` is ``batch / M * seq_len`` — the activation
    unit every ring row holds. Returns a per-component breakdown plus
    ``total_bytes``; see the module docstring for what is (and is
    deliberately not) counted.
    """
    sched = str(schedule).lower().replace("-", "_")
    K = pp * vpp
    d = max(int(h2_depth), 0) if sched == "zb_h2" else 0
    cdb = dtype_bytes(compute_dtype)
    mb_act = microbatch_tokens * hidden_size * cdb
    mb_f32 = microbatch_tokens * hidden_size * 4
    params_b = param_count // pp * dtype_bytes(param_dtype)
    grads_b = param_count // pp * 4
    act_ring_b = vpp * 2 * K * mb_act
    gstash_b = vpp * (K + d + 1) * mb_act \
        if sched in ("zb", "zb_h2") else 0
    wave_b = vpp * (mb_act + 2 * mb_f32)
    return {
        "schedule": sched,
        "h2_depth": d,
        "microbatch_act_bytes": mb_act,
        "params_bytes": params_b,
        "grads_bytes": grads_b,
        "act_ring_bytes": act_ring_b,
        "gstash_bytes": gstash_b,
        "wave_bytes": wave_b,
        "total_bytes": (params_b + grads_b + act_ring_b + gstash_b
                        + wave_b),
    }


def hbm_budget_bytes(device=None) -> Optional[int]:
    """Per-device HBM budget for depth validation, or ``None`` off-TPU
    (CPU/interpret runs); on a TPU an unknown budget is an error. ``PFX_PP_HBM_BUDGET_BYTES`` pins it
    explicitly (<= 0 disables budget checking); otherwise the
    device's allocator ``bytes_limit`` is used."""
    env = os.environ.get("PFX_PP_HBM_BUDGET_BYTES")
    if env is not None:
        try:
            val = int(env)
        except ValueError:
            raise ValueError(
                f"PFX_PP_HBM_BUDGET_BYTES={env!r} is not an integer")
        return val if val > 0 else None
    from ..observability.memory import device_memory_stats
    stats = device_memory_stats(device)
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "tpu":
        # a TPU that reports no limit must not silently drop the
        # budget check the schedule choice rests on
        raise RuntimeError(
            f"TPU {device.device_kind!r} reports no HBM bytes_limit; "
            f"set PFX_PP_HBM_BUDGET_BYTES (<= 0 to disable the "
            f"budget check explicitly)")
    return None


def max_feasible_h2_depth(budget_bytes: int, K: int,
                          bytes_at: Callable[[int], int]) -> int:
    """Deepest ``d`` in ``[0, K - 1]`` with ``bytes_at(d) <=
    budget_bytes``, or ``-1`` when even depth 0 (plain zb) does not
    fit. ``bytes_at`` is monotone in ``d`` so the scan walks down."""
    for d in range(K - 1, -1, -1):
        if bytes_at(d) <= budget_bytes:
            return d
    return -1


def resolve_pipeline_schedule(schedule: str, *, pp: int, vpp: int = 1,
                              requested_depth: int = -1,
                              budget_bytes: Optional[int] = None,
                              mem_kwargs: Optional[dict] = None) -> dict:
    """Resolve a configured ``pipeline_schedule`` into the concrete
    ``(schedule, h2_depth)`` the scan should run, with a reason.

    ``mem_kwargs`` carries the ``stage_memory_bytes`` inputs other
    than ``schedule``/``pp``/``vpp``/``h2_depth``; with both it and
    ``budget_bytes`` present the choice is budget-aware, otherwise it
    is optimistic (full depth) and the reason says so.

    - ``1F1B`` / ``GPipe`` / ``zb`` pass through unchanged.
    - ``zb_h2`` with an explicit ``requested_depth`` that does NOT fit
      the budget raises ``ValueError`` — the configured schedule is
      rejected up front instead of OOMing at trace time. A negative
      ``requested_depth`` asks for the deepest feasible depth.
    - ``zb_auto`` picks the deepest feasible point on the
      ``1F1B -> zb -> zb_h2@d`` ladder.

    Returns ``{"schedule", "h2_depth", "reason",
    "predicted_stage_bytes", "budget_bytes"}`` with ``schedule`` in
    the canonical config spelling (``"1F1B"``, ``"GPipe"``, ``"zb"``,
    ``"zb_h2"``).
    """
    sched = str(schedule).lower().replace("-", "_")
    K = pp * vpp
    full = max(K - 1, 0)

    def bytes_for(s, d):
        if mem_kwargs is None:
            return None
        return stage_memory_bytes(schedule=s, pp=pp, vpp=vpp,
                                  h2_depth=d, **mem_kwargs)["total_bytes"]

    def out(s, d, reason):
        canon = {"1f1b": "1F1B", "gpipe": "GPipe", "zb": "zb",
                 "zb_h2": "zb_h2"}[s]
        return {"schedule": canon, "h2_depth": d, "reason": reason,
                "predicted_stage_bytes": bytes_for(s, d),
                "budget_bytes": budget_bytes}

    if sched in ("1f1b", "gpipe", "zb"):
        return out(sched, 0, "configured explicitly")
    if sched not in ("zb_h2", "zb_auto"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")

    blind = budget_bytes is None or mem_kwargs is None
    if sched == "zb_h2":
        want = full if requested_depth < 0 else min(int(requested_depth),
                                                   full)
        if blind:
            return out("zb_h2", want,
                       "no HBM budget information; assuming depth fits")
        need = bytes_for("zb_h2", want)
        if need <= budget_bytes:
            return out("zb_h2", want,
                       f"depth {want} fits: {need} <= {budget_bytes} "
                       f"bytes per stage")
        if requested_depth >= 0:
            raise ValueError(
                f"pipeline_schedule zb_h2 at depth {want} needs {need} "
                f"bytes per stage but the HBM budget is {budget_bytes} "
                f"(use zb_auto, lower zb_h2_depth, or raise "
                f"PFX_PP_HBM_BUDGET_BYTES)")
        feas = max_feasible_h2_depth(budget_bytes, K,
                                     lambda d: bytes_for("zb_h2", d))
        if feas < 0:
            raise ValueError(
                f"pipeline_schedule zb_h2 does not fit at any depth: "
                f"even depth 0 needs {bytes_for('zb_h2', 0)} bytes per "
                f"stage against a budget of {budget_bytes}")
        return out("zb_h2", feas,
                   f"deepest feasible depth under {budget_bytes} "
                   f"bytes per stage")

    # zb_auto: deepest feasible rung of 1F1B -> zb -> zb_h2@d
    if blind:
        return out("zb_h2", full,
                   "zb_auto without HBM budget information; assuming "
                   "full depth fits")
    feas = max_feasible_h2_depth(budget_bytes, K,
                                 lambda d: bytes_for("zb_h2", d))
    if feas >= 1:
        return out("zb_h2", feas,
                   f"zb_auto: deepest feasible depth under "
                   f"{budget_bytes} bytes per stage")
    if feas == 0 or bytes_for("zb", 0) <= budget_bytes:
        return out("zb", 0,
                   f"zb_auto: zb_h2 depth >= 1 exceeds {budget_bytes} "
                   f"bytes per stage; zb fits")
    return out("1f1b", 0,
               f"zb_auto: the zb cotangent ring exceeds "
               f"{budget_bytes} bytes per stage; falling back to 1F1B")
