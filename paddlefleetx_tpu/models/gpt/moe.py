"""Mixture-of-Experts FFN with expert parallelism (beyond-reference).

The reference has no MoE (SURVEY §2.2: "EP … not present"); this adds
it the TPU-native way — the GShard/Switch design expressed so that
GSPMD partitions it:

  - a fp32 router picks top-k experts per token;
  - tokens reach their per-expert capacity slots through one of the
    ``Config.moe_dispatch`` lowerings (matrix in docs/moe.md):

    * ``"einsum"`` — the one-hot *dispatch* / gate-weighted *combine*
      tensors ``[b, s, E, C]`` of the original GShard formulation.
      All static shapes and dense batched matmuls, but the pack and
      unpack einsums cost ``O(b·s·E·C·h)`` FLOPs — at the shipped ep8
      config that dwarfs the expert GEMMs themselves. Kept as the
      parity/fallback reference.
    * ``"sort"`` — counting-sort routing: each kept (token, choice)
      gets a destination slot ``e·C + position``; a static-shape
      inverse-permutation gather packs tokens into the contiguous
      ``[E, b, C, h]`` grouped buffer, and a second gather + gate
      weighting combines the expert outputs back. ``O(b·s·k·h)`` data
      movement, no ``[b, s, E, C]`` tensor ever materializes, and the
      dropped-token set is IDENTICAL to the einsum path's by
      construction (same cumsum slot positions).
    * ``"sort_pallas"`` — ``"sort"`` dispatch with the expert matmuls
      lowered to the Pallas grouped GEMM
      (``ops/pallas/grouped_matmul.py``), which skips (expert, row)
      groups no token routed to using the routing counts.

  - expert weights are stacked on a leading ``expert`` logical axis.
    Expert parallelism = sharding that axis over the dataflow mesh
    axes (``Distributed.ep_degree`` → dp/fsdp; a *dedicated* mesh
    axis would replicate the attention compute ep-fold, which is why
    EP classically rides the data-parallel groups). XLA inserts the
    token all-to-alls at the dispatch/combine boundaries — the einsum
    contraction or the sort path's ``[b, E, C, h] → [E, b, C, h]``
    resharding transpose; either way the sharding constraints, not
    hand-written collectives, place the communication.
    The ``expert_mlp`` inner dim still shards over mp, composing
    EP x TP.

Load balancing follows Switch/GShard: an auxiliary loss
``E * sum_e f_e * P_e`` (f = fraction of tokens whose top-1 choice is
expert e, P = mean router probability) plus an optional router z-loss
``mean(logsumexp(logits)^2)``. The layer returns the already-weighted
auxiliary total; the model sows it into the ``losses`` collection and
the training loss adds it.

Each compiled shape records its chosen lowering in the trace-time
dispatch counters (``moe/einsum``, ``moe/sort``, ``moe/sort_pallas``,
``moe/fallback/pallas_rejected`` — same contract as ``attention/*``
and ``mp_linear/*``, docs/observability.md).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...observability import metrics
from ...parallel.sharding import with_logical_constraint
from .config import GPTConfig


def _dense_init(cfg: GPTConfig):
    # single source of truth lives in model.py (which imports this
    # module lazily, so the import is cycle-safe)
    from .model import _dense_init as impl
    return impl(cfg)


def expert_capacity(cfg: GPTConfig, seq_len: int) -> int:
    """Per-expert capacity slots for one routing group (= one batch
    row): ``ceil(top_k * seq * capacity_factor / num_experts)``."""
    return max(1, int(math.ceil(
        cfg.moe_top_k * seq_len * cfg.moe_capacity_factor
        / cfg.moe_num_experts)))


def _routing_plan(probs: jax.Array, top_k: int, capacity: int):
    """Routing decisions shared by EVERY dispatch lowering.

    Single source of truth for which (token, choice) keeps its slot —
    the einsum and sort paths both consume these exact positions, so
    their dropped-token sets cannot diverge.

    Returns ``(gate, idx, pos, keep, flat, aux_frac)``:
      gate: fp32 ``[b, s, k]`` top-k gate probabilities (renormalized
        for k>1).
      idx: int32 ``[b, s, k]`` chosen expert ids.
      pos: int32 ``[b, s*k]`` position of each flat (token, choice) in
        its expert's slot queue — lexicographic (s, k) priority, all
        of a token's choices adjacent, earlier tokens win slots
        (the reference-free GShard formulation).
      keep: bool ``[b, s*k]`` — position fits under ``capacity``.
      flat: int32 ``[b, s*k, E]`` one-hot expert choice.
      aux_frac: fp32 ``[E]`` fraction of tokens whose *first* choice
        is each expert (the f_e of the Switch load-balance loss,
        computed before capacity drops, as in GShard).
    """
    b, s, E = probs.shape
    gate, idx = jax.lax.top_k(probs, top_k)            # [b, s, k]
    if top_k > 1:
        gate = gate / jnp.maximum(
            gate.sum(axis=-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)   # [b, s, k, E]
    flat = onehot.reshape(b, s * top_k, E)
    pos = jnp.sum((jnp.cumsum(flat, axis=1) - flat) * flat,
                  axis=-1)                             # [b, s*k]
    keep = pos < capacity
    aux_frac = onehot[:, :, 0, :].astype(jnp.float32).mean(axis=(0, 1))
    return gate, idx, pos, keep, flat, aux_frac


def router_dispatch(probs: jax.Array, top_k: int, capacity: int):
    """Token-choice routing as dense one-hot tensors (einsum path).

    Args:
      probs: fp32 router probabilities ``[b, s, E]``.
      top_k: experts per token.
      capacity: slots per expert per batch row.

    Returns ``(dispatch, combine, aux_frac)``:
      dispatch: 0/1 ``[b, s, E, C]`` — token (b,s) occupies slot c of
        expert e. Tokens overflowing an expert's capacity are dropped
        (their dispatch row is zero → they pass through the residual
        only, the standard Switch overflow behavior).
      combine: fp32 ``[b, s, E, C]`` — dispatch weighted by the
        (renormalized, for k>1) gate probabilities.
      aux_frac: fp32 ``[E]`` — see :func:`_routing_plan`.
    """
    b, s, E = probs.shape
    gate, _, pos, keep, flat, aux_frac = _routing_plan(
        probs, top_k, capacity)
    kept = keep[..., None] * flat                      # [b, s*k, E]
    slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
    dispatch = jnp.einsum("bte,btc->btec", kept.astype(jnp.float32),
                          slot)
    dispatch = dispatch.reshape(b, s, top_k, E, capacity)
    combine = jnp.einsum("bskec,bsk->bsec", dispatch, gate)
    dispatch = dispatch.sum(axis=2)                    # [b, s, E, C]
    return dispatch, combine, aux_frac


def sort_routing(probs: jax.Array, top_k: int, capacity: int):
    """Counting-sort routing plan (sort / sort_pallas paths).

    The cumsum slot positions of :func:`_routing_plan` ARE a counting
    sort of the token→expert assignment: ``dest = e·C + pos`` is a
    unique grouped-buffer slot per kept choice, and scattering the
    choice index through it yields the inverse permutation ``src`` a
    static-shape gather needs. No ``[b, s, E, C]`` one-hot tensor is
    ever built.

    Returns ``(gate, dest, src, counts, aux_frac)``:
      gate: fp32 ``[b, s, k]`` renormalized gates.
      dest: int32 ``[b, s*k]`` grouped-buffer slot of each (token,
        choice); ``E*C`` (one past the end) for capacity-dropped
        choices — the combine gather reads the zero pad row there.
      src: int32 ``[b, E*C]`` source token row feeding each slot;
        ``s`` (the zero pad row) for unoccupied slots.
      counts: int32 ``[b, E]`` kept tokens per (batch row, expert) —
        the group boundaries the Pallas grouped GEMM iterates.
      aux_frac: fp32 ``[E]`` — see :func:`_routing_plan`.
    """
    b, s, E = probs.shape
    C = capacity
    gate, idx, pos, keep, flat, aux_frac = _routing_plan(
        probs, top_k, C)
    T = s * top_k
    flat_e = idx.reshape(b, T)
    dest = jnp.where(keep, flat_e * C + pos, E * C).astype(jnp.int32)
    # inverse permutation: which flat choice occupies each slot. The
    # in-range dest values are unique (one choice per slot), so the
    # scatter is deterministic; dropped choices aim one past the end
    # and mode="drop" discards them.
    src_choice = jnp.full((b, E * C), T, jnp.int32)
    src_choice = src_choice.at[
        jnp.arange(b)[:, None], dest].set(
        jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (b, T)),
        mode="drop")
    # choice t came from token t // k; empty slots hold T, and
    # T // k == s is exactly the zero pad row the gather wants
    src = src_choice // top_k
    counts = jnp.minimum(flat.sum(axis=1), C).astype(jnp.int32)
    return gate, dest, src, counts, aux_frac


class MoEMLP(nn.Module):
    """Drop-in replacement for the decoder block's dense FFN.

    Returns ``(y, aux)`` where ``aux`` is the weighted auxiliary loss
    (load balance + router z-loss) as an fp32 scalar. The parameter
    tree ("router_kernel"/"wi"/"wi_bias"/"wo"/"wo_bias", shapes,
    logical axes, init streams) is identical across every
    ``moe_dispatch`` mode — checkpoints move freely between them.
    """
    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        E, k = cfg.moe_num_experts, cfg.moe_top_k
        b, s, h = x.shape
        m = cfg.ffn_hidden_size
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)

        # router runs in fp32 (bf16 logits make top-k ties and the
        # z-loss noisy); its params are tiny and stay replicated
        wr = self.param(
            "router_kernel",
            nn.with_logical_partitioning(_dense_init(cfg),
                                         ("embed", None)),
            (h, E), pdtype)
        logits = jnp.einsum("bsh,he->bse", x.astype(jnp.float32),
                            wr.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)

        w1 = self.param(
            "wi", nn.with_logical_partitioning(
                _dense_init(cfg), ("expert", "expert_embed",
                                   "expert_mlp")),
            (E, h, m), pdtype)
        b1 = self.param(
            "wi_bias", nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("expert", "expert_mlp")),
            (E, m), pdtype)
        w2 = self.param(
            "wo", nn.with_logical_partitioning(
                _dense_init(cfg), ("expert", "expert_mlp",
                                   "expert_embed")),
            (E, m, h), pdtype)
        b2 = self.param(
            "wo_bias", nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("expert",
                                               "expert_embed")),
            (E, h), pdtype)

        C = expert_capacity(cfg, s)
        mode = cfg.moe_dispatch
        if mode == "einsum":
            metrics.inc("moe/einsum")
            dispatch, combine, aux_frac = router_dispatch(probs, k, C)
            # pack tokens into expert slots: [b,s,h] -> [E,b,C,h]; the
            # E axis is ep-sharded, so this einsum IS the all-to-all
            xe = jnp.einsum("bsec,bsh->ebch", dispatch.astype(dtype),
                            x)
            xe = with_logical_constraint(
                xe, ("act_expert", "act_expert_batch", None, None))
            y = self._expert_ffn(xe, w1, b1, w2, b2, None,
                                 deterministic)
            # unpack + gate-weight: the return all-to-all
            out = jnp.einsum("ebch,bsec->bsh", y,
                             combine.astype(dtype))
        else:
            gate, dest, src, counts, aux_frac = sort_routing(
                probs, k, C)
            # gather the routed tokens into per-(row, expert) groups:
            # [b, s, h] -> [b, E*C, h]; the appended zero row feeds
            # every unoccupied capacity slot
            x_pad = jnp.concatenate(
                [x, jnp.zeros((b, 1, h), x.dtype)], axis=1)
            xs = jnp.take_along_axis(x_pad, src[..., None], axis=1)
            xs = with_logical_constraint(
                xs, ("batch", "act_expert_slot", None))
            # reshard to expert-major: with E ep-sharded this
            # transpose is where GSPMD places the token all-to-all
            xe = xs.reshape(b, E, C, h).transpose(1, 0, 2, 3)
            xe = with_logical_constraint(
                xe, ("act_expert", "act_expert_batch", None, None))
            y = self._expert_ffn(
                xe, w1, b1, w2, b2,
                counts if mode == "sort_pallas" else None,
                deterministic)
            if mode == "sort":
                metrics.inc("moe/sort")
            # combine: per-choice gather of the expert outputs, gate
            # weighted; dropped choices read the zero pad slot, so a
            # fully-dropped token contributes nothing (pure residual)
            yf = y.transpose(1, 0, 2, 3).reshape(b, E * C, h)
            yf = jnp.concatenate(
                [yf, jnp.zeros((b, 1, h), y.dtype)], axis=1)
            yc = jnp.take_along_axis(yf, dest[..., None], axis=1)
            out = jnp.einsum("bskh,bsk->bsh", yc.reshape(b, s, k, h),
                             gate.astype(y.dtype))
        out = with_logical_constraint(out, ("batch", None, "act_embed"))

        aux = jnp.asarray(0.0, jnp.float32)
        if cfg.moe_aux_loss_weight:
            load_balance = E * jnp.sum(aux_frac * probs.mean(axis=(0, 1)))
            aux = aux + cfg.moe_aux_loss_weight * load_balance
        if cfg.moe_z_loss_weight:
            z = jnp.mean(
                jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
            aux = aux + cfg.moe_z_loss_weight * z
        return out, aux

    def _expert_ffn(self, xe, w1, b1, w2, b2, counts, deterministic):
        """Expert MLP over the grouped ``[E, b, C, *]`` buffer.

        ``counts`` (int32 ``[b, E]``, sort_pallas only) routes the two
        matmuls to the Pallas grouped GEMM, which skips empty
        (expert, row) groups; ``None`` keeps the XLA batched einsums.
        Biases, gelu and dropout stay OUTSIDE the kernel, so every
        mode shares one definition of the non-matmul math and the
        kernel's group-skip zeros are exactly the zeros the einsum
        produces for unrouted slots.
        """
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        E, bb, C, h = xe.shape
        m = cfg.ffn_hidden_size
        from jax.ad_checkpoint import checkpoint_name
        gmm = None
        if counts is not None:
            try:
                from ...ops.pallas.grouped_matmul import grouped_matmul
                from ...ops.ring_attention import (
                    kernel_mesh, shard_kernel,
                )

                def gmm(x4, w, w_axes):
                    """Grouped GEMM over the ``[E, b, C, K]`` buffer,
                    each device on its own (expert, row, channel)
                    block. Groups ordered (e, row): group e*b + i
                    holds batch row i's slice of expert e's capacity
                    block — locally too, since E and b shard whole.
                    A contraction dim sharded over mp (the second
                    GEMM) leaves per-device partial sums: psum them."""
                    k_mesh = nn.logical_to_mesh_axes(w_axes[1:2])[0] \
                        if kernel_mesh() is not None else None

                    def per_device(x4, w, cnt):
                        e, b_, c, kd = x4.shape
                        # counter + fallback live in _expert_ffn
                        y = grouped_matmul(  # pfxlint: disable=PFX205
                            x4.reshape(e * b_, c, kd), w,
                            cnt.T.reshape(e * b_))
                        if k_mesh:
                            y = jax.lax.psum(y, k_mesh)
                        return y.reshape(e, b_, c, w.shape[2])
                    x_axes = ("act_expert", "act_expert_batch", None)
                    return shard_kernel(
                        per_device, (x4, w, counts),
                        (x_axes + w_axes[1:2], w_axes,
                         ("act_expert_batch", "act_expert")),
                        x_axes + w_axes[2:3])

                y = gmm(xe, w1.astype(dtype),
                        ("expert", "expert_embed", "expert_mlp"))
                metrics.inc("moe/sort_pallas")
            except (ImportError, NotImplementedError):
                # kernel rejected the shape — expert compute falls
                # back to the XLA einsums on the same grouped buffer
                # (the dispatch stays sort-based; docs/moe.md)
                gmm = None
                metrics.inc("moe/fallback/pallas_rejected")
                metrics.inc("moe/sort")
        if gmm is None:
            y = jnp.einsum("ebch,ehm->ebcm", xe, w1.astype(dtype))
        y = y + b1.astype(dtype)[:, None, None, :]
        y = checkpoint_name(y, "mlp1")
        y = nn.gelu(y, approximate=True)
        # hidden dropout inside the expert MLP (the dense FFN's
        # hidden_dropout_prob; parity note in docs/parity_matrix.md).
        # nn.Dropout folds the "dropout" rng on the module path, and
        # flax replays lifted rngs across a remat recompute, so the
        # keys are stable under use_recompute; the mask rides on the
        # mode-independent [E, b, C, m] slot layout, so all three
        # dispatch modes drop the same activations for the same rng.
        if cfg.hidden_dropout_prob > 0.0:
            y = nn.Dropout(cfg.hidden_dropout_prob,
                           name="expert_dropout")(
                y, deterministic=deterministic)
        y = with_logical_constraint(
            y, ("act_expert", "act_expert_batch", None, "act_mlp"))
        if gmm is not None:
            # padding rows here are gelu(b1), not zero — safe because
            # the kernel's skipped-group outputs are never combined
            # (zero gate weight) so their cotangents arrive as zeros
            y = gmm(y, w2.astype(dtype),
                    ("expert", "expert_mlp", "expert_embed"))
        else:
            y = jnp.einsum("ebcm,emh->ebch", y, w2.astype(dtype))
        y = y + b2.astype(dtype)[:, None, None, :]
        y = checkpoint_name(y, "mlp2")
        return y
