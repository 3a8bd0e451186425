"""Pipeline parallelism: SPMD microbatch pipelines over the ``pp`` axis.

The reference's PP stack is bespoke machinery inside Paddle —
``PipelineLayer`` flattens the model into ``LayerDesc`` lists
(reference ``hybrid_model.py:895-961``), a 1F1B scheduler drives
``train_batch`` with NCCL P2P send/recv between stage ranks
(``eager_engine.py:406-415``), interleaved stages come from
``virtual_pp_degree`` chunk assignment (``hybrid_model.py:962``,
validation ``models/language_model/utils.py:76-100``), and shared
embeddings are tied across first/last stages via ``SharedLayerDesc``.

TPU-native design: none of that machinery is rank-local here. The
whole pipeline is ONE jitted SPMD program:

  - layer parameters stay in the same stacked ``[L, ...]`` layout the
    scan-over-layers model already uses, sharded over ``pp`` on the
    leading axis, so checkpoints are topology-portable — unlike the
    reference's per-rank ``pdparams`` dirs. With ``virtual_pp_degree
    = vpp > 1`` the reshape to ``[vpp, S, L/(S*vpp), ...]`` (sharded
    over ``pp`` on axis 1) gives physical stage ``s`` the
    non-contiguous layer chunks ``{s, S+s, 2S+s, ...}`` — exactly the
    reference's interleaved assignment;
  - a ``[vpp, S, microbatch, ...]`` slot buffer is sharded over
    ``pp``; each pipeline tick runs every virtual stage's local
    layers in parallel (a ``vmap`` over slots of a ``lax.scan`` over
    the slot's layers) and advances the buffer with a roll along the
    virtual-stage order, which GSPMD lowers to a collective-permute
    between ICI neighbors — the NCCL P2P of the reference;
  - two schedules are provided. ``pipeline_forward`` is the
    forward-only GPipe fill/drain (``M + S*vpp - 1`` ticks); taking
    ``jax.grad`` through it yields a GPipe-memory-profile backward.
    ``pipeline_value_and_grad`` is an explicit 1F1B: each tick runs
    one forward slot-wave and one backward slot-wave (per-slot
    ``jax.vjp`` with recompute, the reference 1F1B's memory story),
    so the activation stash holds at most ``2*S*vpp`` microbatch
    activations per slot-ring instead of all ``M`` — peak activation
    memory is bounded by pipeline depth, not microbatch count;
  - embeddings and the LM head are compute-replicated over ``pp``
    (their FLOPs are negligible next to the decoder stack), which
    makes the reference's ``SharedLayerDesc`` embedding tying
    (``hybrid_model.py:934-945``) trivial: there is only one
    embedding table, visible to both ends of the pipeline.

Schedule timing (K = S*vpp virtual stages): forward of microbatch
``m`` at virtual stage ``k`` happens at tick ``m + k``; its loss (and
output cotangent) at tick ``m + K - 1``; its backward at stage ``k``
at tick ``m + 2K - 1 - k``. An activation stashed at the forward tick
is consumed ``2(K - 1 - k) + 1 < 2K`` ticks later, so a depth-``2K``
ring buffer never collides. The 1F1B bubble is the same ``(K-1)``-tick
fill/drain as GPipe's; the win is memory (the reference's motivation
for defaulting to 1F1B).

Zero-bubble schedule (``schedule="zb"``, after the ZB-H1 family of
arXiv:2412.14374): each stage's backward splits into dX (the input
cotangent, which stays on the critical path — the next stage's
backward needs it one tick later) and dW (the weight gradient, which
nothing downstream consumes until the optimizer). dX runs at the same
tick 1F1B runs the combined backward; the dW job is pushed into a
bounded per-slot FIFO and drained during ticks where that slot's
backward wave is otherwise idle — virtual stage ``k`` has exactly
``k`` such drain-bubble ticks at the end of the schedule, so its
queue capacity is ``min(k, M)`` and every deferred dW lands in a
formerly-empty slot-tick. The drain order is FIFO, so per-slot weight
gradients accumulate in the same microbatch order as 1F1B and the
results match bitwise up to XLA scheduling. Because the whole
schedule is a static function of ``(M, K)``, the pop timetable is
precomputed host-side (``zb_dw_schedule``) and fed to the scan as
per-tick indices; the same host math yields the
``pipeline/{fwd,bwd_dx,bwd_dw,bubble}_ticks`` trace-time counters
that make the occupancy win auditable (docs/pipeline.md).

ZB-H2 schedule (``schedule="zb_h2"``, same family): spend HBM
headroom to also kill the *fill-phase* bubble. Virtual stage ``k``
runs up to ``h2_depth`` extra warm-up forwards ahead of the 1F1B
pattern — its in-flight forward cap rises from ``K - k`` to
``min(2(K - k) - 1, (K - k) + h2_depth)`` — so the fill-phase ticks
1F1B leaves idle are filled with real forward work, while the dW FIFO
(its capacity raised to ``min(k + h2_depth, M)``) drains into
whatever bubble remains. In the decoupled-stage occupancy model
(``pipeline_tick_stats``) the bubble at depth ``d`` is
``(K-1-d)(K-d)/2`` once ``M >= 2K - 1`` — zero at the full depth
``d = K - 1``. The lockstep SPMD scan cannot literally run ahead
(stage ``k`` has no input before tick ``k``), so the scan's zb_h2
branch replays the *deferred-dW half* of the schedule: the deeper
FIFO timetable (with forced just-in-time pops so nothing leaks past
the last tick) and the deeper cotangent ring (``K + h2_depth + 1``
rows — the HBM the schedule spends) — proving the numerics and the
queue machinery an MPMD runtime (ROADMAP item 4) would execute for
the wall-clock win. Gradients stay bitwise-equal to 1F1B: pops are
FIFO in microbatch order, so the fp32 accumulation order never
changes. The analytic per-stage byte model and the ``zb_auto``
schedule chooser live in ``parallel/pp_memory.py``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observability import metrics
from .mesh import DATA_AXES, PP_AXIS, get_mesh


def _constrain(x, spec: P):
    """Sharding constraint against the active mesh (no-op without one)."""
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _slot_vmap(fn: Callable, S: int) -> Callable:
    """``fn`` over the ``[vpp, S]`` slot grid. The stage dim IS the
    mesh's ``pp`` axis: naming it (``spmd_axis_name``) lets whatever a
    stage runs per device — sharding constraints, ``shard_map``-wrapped
    kernels (``ops/ring_attention.py::shard_kernel``) — see the stage
    dim sharded over ``pp``. Unnamed, a ``shard_map`` inside the stage
    takes it as replicated and every pp device computes every stage's
    kernel. Without a mesh whose ``pp`` axis is ``S`` wide this is the
    plain double vmap."""
    mesh = get_mesh()
    named = mesh is not None and S > 1 \
        and dict(mesh.shape).get(PP_AXIS, 1) == S
    return jax.vmap(jax.vmap(
        fn, spmd_axis_name=PP_AXIS if named else None))


def _slot_params(stacked_params: Any, S: int, vpp: int) -> Tuple[Any, int]:
    """``[L, ...]`` stacked params -> ``[vpp, S, L/(S*vpp), ...]``
    sharded over ``pp`` on the physical-stage axis. Virtual stage
    ``k = v*S + s`` owns the contiguous layer block ``[k*Lc, (k+1)*Lc)``
    — i.e. physical stage ``s`` owns interleaved chunks."""
    leaves = jax.tree.leaves(stacked_params)
    if not leaves:
        raise ValueError("stacked_params has no leaves")
    L = leaves[0].shape[0]
    K = S * vpp
    if L % K != 0:
        raise ValueError(
            f"num_layers {L} not divisible by pp*vpp {K}")
    Lc = L // K
    slotted = jax.tree.map(
        lambda p: _constrain(p.reshape(vpp, S, Lc, *p.shape[1:]),
                             P(None, PP_AXIS)), stacked_params)
    return slotted, Lc


def _advance(processed: jax.Array, vpp: int) -> jax.Array:
    """Forward roll along the virtual-stage order: slot k's output
    becomes slot k+1's next input. The s-axis roll is the inter-stage
    collective-permute; chunk wrap (s=S-1 -> next chunk's s=0) moves
    within the same device ring."""
    nxt = jnp.roll(processed, 1, axis=1)
    if vpp > 1:
        wrapped = jnp.roll(processed[:, -1], 1, axis=0)
        nxt = nxt.at[:, 0].set(wrapped)
    return nxt


def _retreat(b_out: jax.Array, dy_prev: jax.Array, vpp: int) -> jax.Array:
    """Backward roll: slot k's next cotangent is slot k+1's backward
    output; the last virtual stage ingests the loss cotangent."""
    g = jnp.roll(b_out, -1, axis=1)
    if vpp > 1:
        wrapped = jnp.roll(b_out[:, 0], -1, axis=0)
        g = g.at[:, -1].set(wrapped)
    return g.at[-1, -1].set(dy_prev)


def _slot_keys(base_rng: jax.Array, m_arr: jax.Array,
               K: int) -> jax.Array:
    """Per-slot dropout keys folded by (microbatch, virtual stage) so
    a 1F1B backward recompute reproduces the forward's masks exactly
    (tick-based folding would not: F and B of the same microbatch
    happen at different ticks)."""
    k_arr = jnp.arange(K)

    def key_for(m, k):
        return jax.random.fold_in(jax.random.fold_in(base_rng, m), k)

    return jax.vmap(key_for)(m_arr, k_arr)


def zb_queue_bound(num_microbatches: int, num_virtual_stages: int,
                   h2_depth: int = 0) -> int:
    """Upper bound on the zb/zb_h2 per-slot dW-queue depth: virtual
    stage ``k`` defers at most ``min(k + h2_depth, M)`` weight-grad
    jobs (``h2_depth = 0`` is plain zb: stage ``k`` has exactly ``k``
    drain-bubble ticks to spend them in), so no slot ever queues more
    than ``min(K - 1 + h2_depth, M)`` microbatch cotangents."""
    return min(num_virtual_stages - 1 + max(int(h2_depth), 0),
               num_microbatches)


def zb_dw_schedule(num_microbatches: int, num_virtual_stages: int,
                   h2_depth: int = 0):
    """Static dW drain timetable for the zero-bubble schedule family.

    Pure host math — the 1F1B tick grid is a fixed function of
    ``(M, K)``, so *when* each deferred weight-grad job runs is decided
    here, not inside the scan. Per virtual stage ``k`` a FIFO of
    capacity ``min(k + h2_depth, M)`` receives one job at each dX
    tick; a job pops (and its dW runs) when the push would overflow
    the capacity (steady state — the same tick, exactly like 1F1B, for
    ``k = 0`` at depth 0), at a tick where the slot's backward wave is
    idle (the former drain-bubble ticks, which the deferred jobs now
    fill), or — with ``h2_depth > 0``, whose deeper FIFOs can outlast
    the ``k`` trailing idle ticks — just in time: whenever the jobs
    still outstanding (queued or yet to be pushed) need every
    remaining tick to drain one-per-tick, a pop runs alongside that
    tick's dX. At depth 0 the JIT rule fires exactly when the
    overflow rule already does, so the zb timetable is bit-identical
    with and without it;
    at any depth it keeps every pop of microbatch ``m`` at or before
    tick ``m + 2K - 1`` (pops are FIFO, one per tick, and all land by
    ``T - 1``), which is what lets the activation ring stay at depth
    ``2K``: the forward entry for ``(m, k)`` is overwritten at tick
    ``m + k + 2K``, strictly later.

    Returns ``(dw_m, max_depth)``: ``dw_m`` is an int ``[T, K]`` array
    (``T = M + 2K - 1``) whose entry is the microbatch whose dW runs at
    that (tick, virtual stage), or ``-1``; ``max_depth`` is the deepest
    any FIFO ever got (``<= zb_queue_bound(M, K, h2_depth)``).
    """
    M, K = num_microbatches, num_virtual_stages
    d = int(h2_depth)
    if d < 0:
        raise ValueError(f"h2_depth must be >= 0, got {h2_depth}")
    T = M + 2 * K - 1
    dw_m = np.full((T, K), -1, np.int32)
    max_depth = 0
    for k in range(K):
        cap = min(k + d, M)
        fifo: list = []
        npop = 0
        for t in range(T):
            m_b = t - (2 * K - 1 - k)
            pushed = 0 <= m_b < M
            if pushed:
                fifo.append(m_b)
            if fifo and (len(fifo) > cap or not pushed
                         or M - npop >= T - t):
                dw_m[t, k] = fifo.pop(0)
                npop += 1
            max_depth = max(max_depth, len(fifo))
        if fifo:   # every job must drain within the schedule
            raise AssertionError(
                f"zb schedule leaked {len(fifo)} dW jobs at stage {k}")
    return dw_m, max_depth


def h2_fwd_caps(num_microbatches: int, num_virtual_stages: int,
                h2_depth: int) -> list:
    """Per-virtual-stage in-flight forward caps (forwards done minus
    dXs done) for the schedule family. 1f1b/zb warm up ``K - k``
    forwards at stage ``k``; zb_h2 at depth ``d`` warms up
    ``min(2(K - k) - 1, (K - k) + d)`` — each extra in-flight forward
    is one more stashed microbatch activation (the HBM the schedule
    spends, priced by ``parallel/pp_memory.py``)."""
    M, K, d = num_microbatches, num_virtual_stages, h2_depth
    return [min(min(2 * (K - k) - 1, (K - k) + d), M) for k in range(K)]


def pipeline_tick_stats(num_microbatches: int, num_virtual_stages: int,
                        schedule: str = "1f1b",
                        h2_depth: Optional[int] = None) -> dict:
    """Analytic per-stage occupancy of a pipeline schedule.

    For the training schedules (1f1b / zb / zb_h2) this simulates the
    *decoupled-stage unit model*: each virtual stage executes at most
    one work unit (forward, dX, or dW — all unit-cost) per tick, dX
    has priority (critical path), forwards run work-conserving up to
    the stage's in-flight cap (``h2_fwd_caps``), and deferred dW jobs
    drain FIFO into ticks the stage would otherwise idle. A stage's
    ``total`` is its active span (first to last unit), its ``bubble``
    the idle ticks inside that span — so
    ``fwd + bwd_dx + bwd_dw + bubble == total_slot_ticks`` holds
    exactly (the conservation identity the property tests pin). This
    models what each schedule buys on a decoupled MPMD runtime
    (ROADMAP item 4); the lockstep scan replays the matching dW
    timetable to prove the numerics. Closed forms at ``M >= K``:
    1f1b bubble ``K(K-1)``, zb ``K(K-1)/2``, and zb_h2 at depth ``d``
    ``(K-1-d)(K-d)/2`` once ``M >= 2K - 1`` — zero at ``d = K - 1``.

    ``schedule="gpipe"`` keeps the lockstep forward-only fill/drain
    grid (that IS what ``pipeline_forward`` executes): ``M*K`` forward
    slot-ticks inside a ``(M + K - 1) * K`` grid, the rest bubble —
    the same conservation identity, different accounting basis.

    ``h2_depth`` (zb_h2 only): extra warm-up forwards per stage;
    ``None`` or negative picks the full depth ``K - 1``.

    This is the single source for the
    ``pipeline/{fwd,bwd_dx,bwd_dw,bubble}_ticks`` counters and the
    engine's ``pipeline_bubble`` goodput bucket.
    """
    M, K = num_microbatches, num_virtual_stages
    sched = str(schedule).lower().replace("-", "_")
    if sched == "gpipe":
        T = M + K - 1
        return {"fwd_ticks": M * K, "bwd_dx_ticks": 0,
                "bwd_dw_ticks": 0,
                "bubble_ticks": T * K - M * K,
                "total_slot_ticks": T * K,
                "makespan_ticks": T,
                "per_stage_bubble_ticks": [K - 1] * K,
                "h2_depth": 0,
                "dw_queue_peak": 0}
    if sched not in ("1f1b", "zb", "zb_h2"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    d = 0
    if sched == "zb_h2":
        d = (K - 1) if (h2_depth is None or h2_depth < 0) \
            else min(int(h2_depth), K - 1)
    if sched == "zb_h2":
        cap = h2_fwd_caps(M, K, d)
    else:
        cap = [min(K - k, M) for k in range(K)]

    t_first = [None] * K
    t_last = [0] * K
    nF = [0] * K            # forwards done per stage
    nD = [0] * K            # dXs done
    nW = [0] * K            # dWs done
    fin_F = [[-1] * M for _ in range(K)]   # completion tick of F(m, k)
    fin_D = [[-1] * M for _ in range(K)]
    pend_W: list = [[] for _ in range(K)]  # FIFO of mbs whose dX ran
    pair_W = [-1] * K       # 1f1b: dW bound to the dX one tick earlier
    q_peak = 0
    done, total_units = 0, 3 * M * K
    t = 0
    limit = 4 * (M + K) + 8 * K + 8
    while done < total_units and t < limit:
        for k in range(K):
            ran = -1
            # 1f1b's combined backward: dW immediately follows its dX
            if sched == "1f1b" and pair_W[k] >= 0:
                pair_W[k] = -1
                nW[k] += 1
                ran = t
            else:
                m = nD[k]
                d_ready = m < M and (
                    fin_D[k + 1][m] >= 0 and fin_D[k + 1][m] < t
                    if k < K - 1
                    else fin_F[k][m] >= 0 and fin_F[k][m] < t)
                m_f = nF[k]
                f_ready = m_f < M and (nF[k] - nD[k]) < cap[k] and (
                    k == 0 or (fin_F[k - 1][m_f] >= 0
                               and fin_F[k - 1][m_f] < t))
                if d_ready:
                    fin_D[k][m] = t
                    nD[k] += 1
                    ran = t
                    if sched == "1f1b":
                        pair_W[k] = m
                    else:
                        pend_W[k].append(m)
                        q_peak = max(q_peak, len(pend_W[k]))
                elif f_ready:
                    fin_F[k][m_f] = t
                    nF[k] += 1
                    ran = t
                elif pend_W[k]:
                    pend_W[k].pop(0)
                    nW[k] += 1
                    ran = t
            if ran >= 0:
                done += 1
                if t_first[k] is None:
                    t_first[k] = t
                t_last[k] = t
        t += 1
    if done != total_units:
        raise AssertionError(
            f"pipeline unit-model deadlock: {done}/{total_units} units "
            f"at (M={M}, K={K}, schedule={sched!r}, depth={d})")
    spans = [t_last[k] - t_first[k] + 1 for k in range(K)]
    per_stage_bubble = [spans[k] - 3 * M for k in range(K)]
    return {"fwd_ticks": M * K,
            "bwd_dx_ticks": M * K,
            "bwd_dw_ticks": M * K,
            "bubble_ticks": sum(per_stage_bubble),
            "total_slot_ticks": sum(spans),
            "makespan_ticks": max(t_last) + 1,
            "per_stage_bubble_ticks": per_stage_bubble,
            "h2_depth": d,
            "dw_queue_peak": q_peak}


def pipeline_forward(
    layer_apply: Callable[[Any, jax.Array, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    *,
    pp: int,
    num_microbatches: int,
    vpp: int = 1,
    out_fn: Optional[Callable[[Any, jax.Array, Any], Any]] = None,
    out_init: Any = None,
    extras: Any = None,
    rng: Optional[jax.Array] = None,
    layer_has_aux: bool = False,
) -> Any:
    """Run ``x`` through ``L`` stacked layers with a GPipe-scheduled
    ``pp``-stage (optionally ``vpp``-way interleaved) pipeline.

    Args:
      layer_apply: ``(layer_params, h, rng_key) -> h`` — one decoder
        layer as a pure function (wrap with ``jax.checkpoint`` for
        recompute before passing).
      stacked_params: pytree whose leaves have leading dim ``L``
        (``nn.scan`` layout), ``L % (pp * vpp) == 0``.
      x: ``[B, ...]`` input activations, ``B % num_microbatches == 0``.
      pp: number of physical pipeline stages (mesh ``pp`` axis size).
      num_microbatches: M; the reference's ``accumulate_steps``
        (``utils/config.py:117``).
      vpp: interleaved virtual stages per physical stage (the
        reference's ``virtual_pp_degree``).
      out_fn: optional per-microbatch reducer ``(acc, y_mb, extras_mb)
        -> acc`` applied to the last stage's output (e.g. LM head +
        loss). When given, the full ``[B, ...]`` output is never
        materialized — the pipelined analogue of the reference
        computing loss per microbatch inside ``train_batch``.
      out_init: initial reducer carry (required with ``out_fn``).
      extras: pytree of ``[B, ...]`` arrays sliced per-microbatch and
        fed to ``out_fn`` (labels, loss masks).
      rng: base dropout key; folded per (microbatch, virtual stage,
        layer).
      layer_has_aux: ``layer_apply`` returns ``(h, aux_scalar)`` (MoE
        layers: the router aux loss). This forward-only schedule
        DISCARDS the aux — eval reports pure CE (docs/moe.md); the
        training aux flows through ``pipeline_value_and_grad``.

    Returns the reducer carry, or the ``[B, ...]`` outputs when
    ``out_fn`` is None.
    """
    S, M = pp, num_microbatches
    K = S * vpp
    B = x.shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    ts = pipeline_tick_stats(M, K, schedule="gpipe")
    metrics.inc("pipeline/fwd_ticks", ts["fwd_ticks"])
    metrics.inc("pipeline/bubble_ticks", ts["bubble_ticks"])
    slot_params, Lc = _slot_params(stacked_params, S, vpp)

    x_mb = x.reshape(M, B // M, *x.shape[1:])
    x_mb = _constrain(x_mb, P(None, DATA_AXES))
    extras_mb = None
    if extras is not None:
        extras_mb = jax.tree.map(
            lambda e: e.reshape(M, B // M, *e.shape[1:]), extras)

    state0 = _constrain(
        jnp.zeros((vpp, S) + x_mb.shape[1:], x.dtype),
        P(None, PP_AXIS, DATA_AXES))
    collect = out_fn is None
    acc0 = jnp.zeros_like(x_mb) if collect else out_init
    base_rng = rng if rng is not None else jax.random.key(0)

    def stage_fn(sp, h, key):
        def body(h, xs):
            lp, k = xs
            out = layer_apply(lp, h, k)
            return (out[0] if layer_has_aux else out), None
        h, _ = jax.lax.scan(body, h, (sp, jax.random.split(key, Lc)))
        return h

    slot_stage = _slot_vmap(stage_fn, S)

    def tick(carry, t):
        """One pipeline clock: every virtual stage computes, then
        activations rotate one hop."""
        state, acc = carry
        # virtual stage 0 ingests microbatch t (clamped past the fill
        # phase — drain ticks feed it a stale microbatch whose output
        # is never collected)
        inp = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.minimum(t, M - 1), 0, keepdims=False)
        state = _constrain(state.at[0, 0].set(inp),
                           P(None, PP_AXIS, DATA_AXES))

        m_arr = jnp.clip(t - jnp.arange(K), 0, M - 1)
        keys = _slot_keys(base_rng, m_arr, K).reshape(vpp, S)
        processed = slot_stage(slot_params, state, keys)
        processed = _constrain(processed, P(None, PP_AXIS, DATA_AXES))

        # collect the last virtual stage's output for microbatch
        # t-(K-1); ticks before the pipeline is full carry warmup
        # garbage — the cond skips the collection (and the reducer's
        # head/loss FLOPs) entirely on those ticks
        y = processed[-1, -1]
        idx = jnp.clip(t - (K - 1), 0, M - 1)
        valid = t >= K - 1
        if collect:
            acc = jax.lax.cond(
                valid,
                lambda a: jax.lax.dynamic_update_index_in_dim(
                    a, y, idx, 0),
                lambda a: a, acc)
        else:
            def reduce(a):
                ex = None
                if extras_mb is not None:
                    ex = jax.tree.map(
                        lambda e: jax.lax.dynamic_index_in_dim(
                            e, idx, 0, keepdims=False), extras_mb)
                return out_fn(a, y, ex)
            acc = jax.lax.cond(valid, reduce, lambda a: a, acc)

        state = _advance(processed, vpp)
        return (state, acc), None

    (_, acc), _ = jax.lax.scan(tick, (state0, acc0),
                               jnp.arange(M + K - 1))
    if collect:
        return acc.reshape(B, *x.shape[1:])
    return acc


def pipeline_value_and_grad(
    layer_apply: Callable[[Any, jax.Array, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    *,
    pp: int,
    num_microbatches: int,
    vpp: int = 1,
    loss_and_grad: Callable[[jax.Array, Any],
                            Tuple[jax.Array, jax.Array, Any]],
    extras: Any = None,
    rng: Optional[jax.Array] = None,
    schedule: str = "1f1b",
    h2_depth: int = -1,
    layer_has_aux: bool = False,
) -> Tuple[jax.Array, Any, Any, jax.Array]:
    """Explicit 1F1B (or zero-bubble) schedule: loss AND gradients in
    one pass.

    Unlike ``jax.grad(pipeline_forward)`` — which structurally runs
    all forwards before any backward and therefore stashes every
    microbatch's activations (the GPipe memory profile) — each tick
    here runs one forward slot-wave and one backward slot-wave. A
    microbatch's backward starts ``1`` tick after its loss, so the
    activation ring holds at most ``2K`` entries per slot regardless
    of ``M`` (the 1F1B property; reference default schedule,
    ``hybrid_model.py:962`` area). The per-slot backward is
    ``jax.vjp`` of the slot forward — recompute-from-stashed-input,
    i.e. full recompute granularity, matching how the reference runs
    PP with recompute enabled.

    Args:
      layer_apply / stacked_params / x / pp / vpp / extras / rng: as
        in ``pipeline_forward``.
      num_microbatches: M (gradient accumulation happens inside the
        schedule).
      loss_and_grad: ``(y_mb, extras_mb) -> (loss_mb, dy_mb,
        dhead_mb)`` — per-microbatch loss, its cotangent wrt ``y_mb``,
        and the gradient pytree for any head/criterion parameters
        closed over by the caller (summed over microbatches here).
      schedule: ``"1f1b"`` (the combined backward above), ``"zb"``
        (zero-bubble: dX-only vjp on the critical path, dW replayed
        from the stashed input at the statically precomputed drain
        tick — see the module docstring), or ``"zb_h2"`` (the same
        machinery with the dW FIFO deepened by ``h2_depth``: the
        timetable an MPMD runtime running ``h2_depth`` extra warm-up
        forwards would drain, priced by the deeper cotangent ring).
        Gradients are identical across all three: the dW FIFO drains
        in microbatch order, so even the fp32 accumulation order
        matches.
      h2_depth: zb_h2 only — extra warm-up forwards per virtual
        stage, ``0 <= h2_depth <= K - 1`` (``-1`` picks the full
        depth ``K - 1``; depth 0 degenerates to plain zb). Raises the
        per-slot dW FIFO capacity to ``min(k + h2_depth, M)`` and the
        cotangent ring to ``K + h2_depth + 1`` rows — the HBM spend
        ``parallel/pp_memory.py`` prices and validates.
      layer_has_aux: ``layer_apply`` returns ``(h, aux_scalar)`` (MoE
        router aux loss). The aux of every valid (microbatch, virtual
        stage) is added to ``loss_sum`` at its forward tick, and a
        unit aux cotangent rides the matching dX/dW pulls so router
        gradients flow through both schedules.

    Returns ``(loss_sum, d_stacked, dhead_sum, dx)`` where
    ``d_stacked`` matches ``stacked_params``' ``[L, ...]`` layout,
    ``dhead_sum`` sums ``dhead_mb`` over microbatches, and ``dx`` is
    the ``[B, ...]`` cotangent wrt ``x`` (feed it to the embedding
    vjp). All sums are over microbatches — divide by M for a mean.
    """
    S, M = pp, num_microbatches
    K = S * vpp
    D = 2 * K  # activation ring depth; see module docstring
    B = x.shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    sched = str(schedule).lower().replace("-", "_")
    if sched not in ("1f1b", "zb", "zb_h2"):
        raise ValueError(
            f"unknown pipeline schedule {schedule!r} (expected '1f1b', "
            f"'zb' or 'zb_h2'; GPipe routes through pipeline_forward)")
    h2 = 0
    if sched == "zb_h2":
        h2 = (K - 1) if h2_depth < 0 else min(int(h2_depth), K - 1)
    # trace-time occupancy counters: the tick grid is a static function
    # of (M, K), so one inc per compilation records the whole schedule
    ts = pipeline_tick_stats(M, K, schedule=sched, h2_depth=h2)
    metrics.inc("pipeline/fwd_ticks", ts["fwd_ticks"])
    metrics.inc("pipeline/bwd_dx_ticks", ts["bwd_dx_ticks"])
    metrics.inc("pipeline/bwd_dw_ticks", ts["bwd_dw_ticks"])
    metrics.inc("pipeline/bubble_ticks", ts["bubble_ticks"])
    if sched == "zb_h2":
        metrics.inc("pipeline/h2_depth", h2)
    slot_params, Lc = _slot_params(stacked_params, S, vpp)

    x_mb = x.reshape(M, B // M, *x.shape[1:])
    x_mb = _constrain(x_mb, P(None, DATA_AXES))
    extras_mb = jax.tree.map(
        lambda e: e.reshape(M, B // M, *e.shape[1:]), extras) \
        if extras is not None else None
    base_rng = rng if rng is not None else jax.random.key(0)
    mb_shape = x_mb.shape[1:]

    def stage_fn(sp, h, key):
        def body(h, xs):
            lp, k = xs
            if layer_has_aux:
                h, aux = layer_apply(lp, h, k)
                return h, aux
            return layer_apply(lp, h, k), None
        h, auxs = jax.lax.scan(body, h, (sp, jax.random.split(key, Lc)))
        if layer_has_aux:
            return h, jnp.sum(auxs)
        return h

    slot_stage = _slot_vmap(stage_fn, S)

    # The combined pull (1f1b) extracts dW and dX from one backward;
    # the zb pulls split them — dX on the critical path, dW replayed
    # later from the stashed input. With layer_has_aux the aux
    # cotangent (1.0 on valid work, else 0.0) rides along so router
    # aux gradients flow at exactly the ticks the matching dX/dW run.
    def slot_vjp(sp, h, key, g):
        _, pull = jax.vjp(lambda p, hh: stage_fn(p, hh, key), sp, h)
        return pull(g)

    def slot_vjp_aux(sp, h, key, g, a_ct):
        _, pull = jax.vjp(lambda p, hh: stage_fn(p, hh, key), sp, h)
        return pull((g, a_ct))

    def slot_dx(sp, h, key, g):
        _, pull = jax.vjp(lambda hh: stage_fn(sp, hh, key), h)
        return pull(g)[0]

    def slot_dx_aux(sp, h, key, g, a_ct):
        _, pull = jax.vjp(lambda hh: stage_fn(sp, hh, key), h)
        return pull((g, a_ct))[0]

    def slot_dw(sp, h, key, g):
        _, pull = jax.vjp(lambda p: stage_fn(p, h, key), sp)
        return pull(g)[0]

    def slot_dw_aux(sp, h, key, g, a_ct):
        _, pull = jax.vjp(lambda p: stage_fn(p, h, key), sp)
        return pull((g, a_ct))[0]

    slot_backward = _slot_vmap(slot_vjp, S)
    slot_backward_aux = _slot_vmap(slot_vjp_aux, S)
    slot_backward_dx = _slot_vmap(slot_dx, S)
    slot_backward_dx_aux = _slot_vmap(slot_dx_aux, S)
    slot_backward_dw = _slot_vmap(slot_dw, S)
    slot_backward_dw_aux = _slot_vmap(slot_dw_aux, S)

    # zero templates for the loss head's outputs
    y_abs = jax.ShapeDtypeStruct(mb_shape, x.dtype)
    ex_abs = jax.tree.map(
        lambda e: jax.ShapeDtypeStruct(e.shape[1:], e.dtype), extras_mb) \
        if extras_mb is not None else None
    _, dy_abs, dhead_abs = jax.eval_shape(loss_and_grad, y_abs, ex_abs)
    zeros_of = lambda ab: jax.tree.map(  # noqa: E731
        lambda a: jnp.zeros(a.shape, a.dtype), ab)

    fstate0 = _constrain(jnp.zeros((vpp, S) + mb_shape, x.dtype),
                         P(None, PP_AXIS, DATA_AXES))
    # cotangents ride in fp32 regardless of the compute dtype (the
    # backward wave accumulates them into fp32 param grads)
    bstate0 = _constrain(jnp.zeros((vpp, S) + mb_shape, jnp.float32),
                         P(None, PP_AXIS, DATA_AXES))
    stash0 = _constrain(jnp.zeros((vpp, S, D) + mb_shape, x.dtype),
                        P(None, PP_AXIS, None, DATA_AXES))
    dparams0 = jax.tree.map(
        lambda p: _constrain(jnp.zeros(p.shape, jnp.float32),
                             P(None, PP_AXIS)), slot_params)
    dhead0 = zeros_of(dhead_abs)
    dy0 = zeros_of(dy_abs)
    dx0 = _constrain(jnp.zeros((M,) + mb_shape, jnp.float32),
                     P(None, DATA_AXES))
    loss0 = jnp.zeros((), jnp.float32)

    k_arr = jnp.arange(K)

    def _gather_ring(ring, depths):
        """Per-slot dynamic read of a ``[vpp, S, depth, ...]`` ring."""
        return jax.vmap(jax.vmap(
            lambda st, d: jax.lax.dynamic_index_in_dim(
                st, d, 0, keepdims=False)))(ring,
                                            depths.reshape(vpp, S))

    def _accumulate(dparams, dp, mask):
        return jax.tree.map(
            lambda acc, g: acc + jnp.where(
                mask.reshape(mask.shape + (1,) * (g.ndim - 2)),
                g.astype(jnp.float32), 0.0),
            dparams, dp)

    def _forward_wave(fstate, stash, loss_sum, t):
        inp = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        # .at[0, 0].set is a two-dim-index scatter; with dim 1 sharded
        # over pp the SPMD partitioner mis-broadcasts the index
        # concatenation (hlo-verifier RET_CHECK). A
        # dynamic_update_slice at a constant origin partitions cleanly
        # and is the same write.
        fstate = jax.lax.dynamic_update_slice(
            fstate, inp[None, None], (0,) * fstate.ndim)
        fstate = _constrain(fstate, P(None, PP_AXIS, DATA_AXES))
        stash = _constrain(stash.at[:, :, t % D].set(fstate),
                           P(None, PP_AXIS, None, DATA_AXES))
        m_f = jnp.clip(t - k_arr, 0, M - 1)
        f_keys = _slot_keys(base_rng, m_f, K).reshape(vpp, S)
        if layer_has_aux:
            processed, aux_f = slot_stage(slot_params, fstate, f_keys)
            valid_f = jnp.logical_and(t - k_arr >= 0, t - k_arr < M)
            loss_sum = loss_sum + jnp.sum(
                jnp.where(valid_f.reshape(vpp, S), aux_f, 0.0))
        else:
            processed = slot_stage(slot_params, fstate, f_keys)
        processed = _constrain(processed, P(None, PP_AXIS, DATA_AXES))
        return processed, stash, loss_sum

    def _loss_head(processed, t, loss_sum, dhead):
        m_l = t - (K - 1)
        y_last = processed[-1, -1]
        ex = jax.tree.map(
            lambda e: jax.lax.dynamic_index_in_dim(
                e, jnp.clip(m_l, 0, M - 1), 0, keepdims=False),
            extras_mb) if extras_mb is not None else None

        def do_loss(_):
            return loss_and_grad(y_last, ex)

        def no_loss(_):
            return loss0, dy0, zeros_of(dhead_abs)

        valid_l = jnp.logical_and(m_l >= 0, m_l < M)
        loss_mb, dy_new, dhead_mb = jax.lax.cond(valid_l, do_loss,
                                                 no_loss, None)
        loss_sum = loss_sum + loss_mb
        dhead = jax.tree.map(jnp.add, dhead, dhead_mb)
        return loss_sum, dy_new, dhead

    def _dx_capture(dx, dh, t):
        # cotangent wrt the pipeline input, for the embedding backward
        m_b0 = t - (2 * K - 1)
        return jax.lax.cond(
            jnp.logical_and(m_b0 >= 0, m_b0 < M),
            lambda d: jax.lax.dynamic_update_index_in_dim(
                d, dh[0, 0].astype(jnp.float32),
                jnp.clip(m_b0, 0, M - 1), 0),
            lambda d: d, dx)

    if sched == "1f1b":
        def tick(carry, t):
            """One 1F1B clock: forward wave + combined backward wave
            (dW and dX in a single pull)."""
            fstate, b_out, dy_prev, stash, loss_sum, dparams, dhead, \
                dx = carry
            processed, stash, loss_sum = _forward_wave(
                fstate, stash, loss_sum, t)
            loss_sum, dy_new, dhead = _loss_head(
                processed, t, loss_sum, dhead)

            # ---- backward wave --------------------------------------
            m_b = t - (2 * K - 1 - k_arr)
            valid_b = jnp.logical_and(m_b >= 0, m_b < M)
            g_in = _retreat(b_out, dy_prev, vpp)
            g_in = _constrain(g_in, P(None, PP_AXIS, DATA_AXES))
            depth = (t - (2 * K - 1) + 2 * k_arr) % D  # fwd-tick slot
            x_in = _gather_ring(stash, depth)
            b_keys = _slot_keys(base_rng, jnp.clip(m_b, 0, M - 1),
                                K).reshape(vpp, S)
            g_cast = g_in.astype(x.dtype)
            if layer_has_aux:
                dp, dh = slot_backward_aux(
                    slot_params, x_in, b_keys, g_cast,
                    valid_b.astype(jnp.float32).reshape(vpp, S))
            else:
                dp, dh = slot_backward(slot_params, x_in, b_keys,
                                       g_cast)
            dparams = _accumulate(dparams, dp, valid_b.reshape(vpp, S))
            b_out_new = _constrain(dh.astype(jnp.float32),
                                   P(None, PP_AXIS, DATA_AXES))
            dx = _dx_capture(dx, dh, t)

            fstate = _advance(processed, vpp)
            return (fstate, b_out_new, dy_new, stash, loss_sum,
                    dparams, dhead, dx), None

        carry0 = (fstate0, bstate0, dy0, stash0, loss0, dparams0,
                  dhead0, dx0)
        (_, _, _, _, loss_sum, dparams, dhead, dx), _ = jax.lax.scan(
            tick, carry0, jnp.arange(M + 2 * K - 1))
    else:
        # ---- zero-bubble: dX on the critical path, dW drained at the
        # statically precomputed tick (module docstring). zb_h2 is the
        # same scan with the FIFO deepened by h2 — only the cotangent
        # ring grows; the activation ring stays 2K because the forced
        # just-in-time pops keep every drain of microbatch m at or
        # before tick m + 2K - 1 (zb_dw_schedule docstring) ----------
        dw_np, _ = zb_dw_schedule(M, K, h2_depth=h2)
        dw_rows = jnp.asarray(dw_np.reshape(len(dw_np), vpp, S))
        # cotangent ring: the dW queue holds at most min(k + h2, M)
        # entries per slot (<= K + h2 - 1), indexed m % (K + h2) plus
        # the in-flight push; row K + h2 is scratch so masked writes
        # never clobber a live entry
        Rg = K + h2
        gstash0 = _constrain(
            jnp.zeros((vpp, S, Rg + 1) + mb_shape, x.dtype),
            P(None, PP_AXIS, None, DATA_AXES))

        def tick(carry, xs):
            """One zb clock: forward wave + dX wave + dW drain."""
            t, dw_m = xs
            fstate, b_out, dy_prev, stash, gstash, loss_sum, dparams, \
                dhead, dx = carry
            processed, stash, loss_sum = _forward_wave(
                fstate, stash, loss_sum, t)
            loss_sum, dy_new, dhead = _loss_head(
                processed, t, loss_sum, dhead)

            # ---- dX wave (critical path) ----------------------------
            m_b = t - (2 * K - 1 - k_arr)
            valid_b = jnp.logical_and(m_b >= 0, m_b < M)
            g_in = _retreat(b_out, dy_prev, vpp)
            g_in = _constrain(g_in, P(None, PP_AXIS, DATA_AXES))
            depth = (t - (2 * K - 1) + 2 * k_arr) % D
            x_in = _gather_ring(stash, depth)
            b_keys = _slot_keys(base_rng, jnp.clip(m_b, 0, M - 1),
                                K).reshape(vpp, S)
            g_cast = g_in.astype(x.dtype)
            if layer_has_aux:
                dh = slot_backward_dx_aux(
                    slot_params, x_in, b_keys, g_cast,
                    valid_b.astype(jnp.float32).reshape(vpp, S))
            else:
                dh = slot_backward_dx(slot_params, x_in, b_keys,
                                      g_cast)
            b_out_new = _constrain(dh.astype(jnp.float32),
                                   P(None, PP_AXIS, DATA_AXES))
            dx = _dx_capture(dx, dh, t)

            # enqueue the cotangent for the deferred dW. The write
            # happens before the drain read on purpose: the k=0 slot
            # (capacity 0) pops the entry it pushed this very tick.
            gdepth = jnp.where(valid_b, jnp.clip(m_b, 0, M - 1) % Rg,
                               Rg)
            gstash = jax.vmap(jax.vmap(
                lambda gs, d, gg:
                jax.lax.dynamic_update_index_in_dim(gs, gg, d, 0)))(
                gstash, gdepth.reshape(vpp, S), g_cast)
            gstash = _constrain(gstash,
                                P(None, PP_AXIS, None, DATA_AXES))

            # ---- dW drain at the precomputed tick -------------------
            dw_flat = dw_m.reshape(K)
            valid_w = dw_flat >= 0
            w_m = jnp.clip(dw_flat, 0, M - 1)
            # forward of mb m at slot k ran at tick m + k, so its
            # stashed input lives at ring depth (m + k) % D
            x_w = _gather_ring(stash, (w_m + k_arr) % D)
            g_w = _gather_ring(gstash, jnp.where(valid_w, w_m % Rg, Rg))
            w_keys = _slot_keys(base_rng, w_m, K).reshape(vpp, S)
            if layer_has_aux:
                dp = slot_backward_dw_aux(
                    slot_params, x_w, w_keys, g_w,
                    valid_w.astype(jnp.float32).reshape(vpp, S))
            else:
                dp = slot_backward_dw(slot_params, x_w, w_keys, g_w)
            dparams = _accumulate(dparams, dp, valid_w.reshape(vpp, S))

            fstate = _advance(processed, vpp)
            return (fstate, b_out_new, dy_new, stash, gstash,
                    loss_sum, dparams, dhead, dx), None

        carry0 = (fstate0, bstate0, dy0, stash0, gstash0, loss0,
                  dparams0, dhead0, dx0)
        (_, _, _, _, _, loss_sum, dparams, dhead, dx), _ = \
            jax.lax.scan(tick, carry0,
                         (jnp.arange(M + 2 * K - 1), dw_rows))

    d_stacked = jax.tree.map(
        lambda g, p: g.reshape(p.shape).astype(p.dtype),
        dparams, stacked_params)
    return loss_sum, d_stacked, dhead, dx.reshape(B, *x.shape[1:])
