"""The dropless expert layer of the DeepSeek-V3 family, as one chip of
an expert-parallel deployment runs it.

Router (float32): ``s = sigmoid(u W_g)`` over ALL ``n_routed_experts``;
``T = top_k(s + b)`` with ``b`` the selection bias (``noaux_tc``: it
selects and never weighs; a leaf the optimizer never moves: its
gradient is cut and its name keeps weight decay off);
``w_e = s_e / (sum_{j in T} s_j + 1e-20) * routed_scaling_factor``.
The layer returns ``sum_{e in T and H} w_e MLP_e(u) + MLP_shared(u)``
for the experts ``H = [lo, hi)`` it holds: what the absent experts
would add is another chip's to compute. With ``H`` everything it is the
whole layer. No capacity: every pick of a held expert is computed.

One lowering (:func:`routed_experts`; the router's rule and the gate's
activation are its arguments, so the softmax-over-picked ReLU layer of
``models/smallthinker`` on the serving path takes it too):

  1. a stable sort of the ``N x k`` picks by expert, picks of experts
     not held behind the held ones;
  2. the held picks' rows gathered into ONE static ``[M, h]`` buffer,
     ``M = N k + G block`` rows (``block`` by :func:`block_rows`), each
     group starting on a row-tile boundary
     (``ops/pallas/grouped_matmul.py::ragged_layout``). ``M`` holds the
     worst routing (every pick held), so no routing is ever
     cut; the group sizes and the count of occupied tiles are data;
  3. gate|up as one ragged grouped product, ``silu(g) * u``, down as a
     second: the kernels' grids run over the occupied tiles only;
  4. the weighted combine by the inverse permutation.

Dispatch and combine are a pair of gathers whose transposes are again
gathers (the sort is a bijection between held picks and occupied rows),
so neither direction scatters. Rows past the occupied tiles are never
written; both gathers mask them.

Counters (trace time, docs/observability.md): ``moe/dropless``,
``moe/experts_held``. Where the Pallas kernel cannot run (no TPU and no
interpret mode, a shape it refuses) XLA's ``ragged_dot`` takes the same
buffer in its place, counted ``moe/fallback/pallas_rejected``: the same
algorithm, never silent, and a chip cell holds the counter at 0.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ...observability import metrics
from ...ops.pallas.grouped_matmul import ragged_layout, ragged_matmul
from .config import DeepSeekV3Config

#: rows of one tile of the grouped product at most: small enough that
#: the padding of 16 groups to whole tiles stays a few percent of ~12 k
#: routed rows, a full MXU pass of 128 all the same
BLOCK_M = 128


def block_rows(picks: int, groups: int) -> int:
    """Rows of one tile for ``picks`` rows over ``groups`` experts: the
    power of two at or above the mean group, between a bf16 sublane
    tile (16) and :data:`BLOCK_M`. A training step's groups hold
    thousands of rows and take 128; a decode tick's hold a handful, and
    a tile of 128 would be nine tenths padding that the products read
    and write all the same."""
    mean = max(1, -(-picks // groups))
    return min(BLOCK_M, max(16, 1 << (mean - 1).bit_length()))


def route(u32, w_gate, bias, top_k: int, scaling: float):
    """``(idx [N, k], weights [N, k] float32)`` of the sigmoid router;
    ``u32`` ``[N, h]`` and ``w_gate`` ``[h, E]`` float32."""
    scores = jax.nn.sigmoid(jnp.dot(
        u32, w_gate, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx, weights * scaling


def plan_dispatch(idx, lo: int, hi: int, block_m: int):
    """Everything the two gathers need, from the picks ``idx [N, k]``:

    ``sizes [G]`` rows per held expert; ``tile_group``, ``tiles_used``
    for the kernels; per buffer row ``row_pick [M]`` (flat pick it
    holds) and ``row_valid [M]``; per pick ``pick_row [N, k]`` (its
    buffer row) and ``pick_held [N, k]``."""
    n, k = idx.shape
    groups = hi - lo
    picks = n * k
    tiles = -(-picks // block_m) + groups
    flat = idx.reshape(-1)
    held = (flat >= lo) & (flat < hi)
    key = jnp.where(held, flat - lo, groups).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)      # inverse permutation
    sizes = jnp.sum(key[:, None] == jnp.arange(groups)[None, :], axis=0,
                    dtype=jnp.int32)
    sorted_start = jnp.cumsum(sizes) - sizes
    tile_group, tiles_used, row_start, group_rows = ragged_layout(
        sizes, block_m, tiles)
    rows = jnp.arange(tiles * block_m, dtype=jnp.int32)
    g = tile_group[rows // block_m]
    within = rows - row_start[g]
    row_valid = (within < sizes[g]) & (rows // block_m < tiles_used)
    row_pick = order[jnp.clip(sorted_start[g] + within, 0, picks - 1)]
    kk = jnp.minimum(key, groups - 1)
    pick_row = row_start[kk] + rank - sorted_start[kk]
    return dict(sizes=sizes, tile_group=tile_group, tiles_used=tiles_used,
                group_rows=group_rows,
                row_pick=row_pick, row_valid=row_valid,
                pick_row=jnp.where(held, pick_row, 0).reshape(n, k),
                pick_held=held.reshape(n, k))


def _rows_of_tokens(x, row_pick, row_valid, k):
    return jnp.where(row_valid[:, None], x[row_pick // k], 0)


def _tokens_of_rows(y, pick_row, pick_held, weights=None):
    got = y[pick_row]                                     # [N, k, h]
    if weights is not None:
        got = got * weights[..., None].astype(got.dtype)
    return jnp.sum(jnp.where(pick_held[..., None], got, 0), axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def dispatch(x, row_pick, row_valid, pick_row, pick_held, k):
    """``xs[r] = x[token of the pick row r holds]``, zero rows where the
    buffer is unoccupied. Transpose: each token sums the cotangents of
    its held picks' rows (a gather by ``pick_row``)."""
    return _rows_of_tokens(x, row_pick, row_valid, k)


def _dispatch_fwd(x, row_pick, row_valid, pick_row, pick_held, k):
    return (_rows_of_tokens(x, row_pick, row_valid, k),
            (row_pick, row_valid, pick_row, pick_held))


def _int_zero(a):
    return np.zeros(a.shape, jax.dtypes.float0)


def _dispatch_bwd(k, res, g):
    row_pick, row_valid, pick_row, pick_held = res
    return (_tokens_of_rows(g, pick_row, pick_held), _int_zero(row_pick),
            _int_zero(row_valid), _int_zero(pick_row),
            _int_zero(pick_held))


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def combine(y, weights, row_pick, row_valid, pick_row, pick_held, k):
    """``out[t] = sum over t's held picks of w * y[row of the pick]``.
    Transpose: ``dy[r] = w[pick of r] * dout[token of r]`` on occupied
    rows (a gather by ``row_pick``), ``dw = <y[row], dout[t]>``."""
    return _tokens_of_rows(y, pick_row, pick_held, weights)


def _combine_fwd(y, weights, row_pick, row_valid, pick_row, pick_held, k):
    return (_tokens_of_rows(y, pick_row, pick_held, weights),
            (y, weights, row_pick, row_valid, pick_row, pick_held))


def _combine_bwd(k, res, g):
    y, weights, row_pick, row_valid, pick_row, pick_held = res
    w_row = weights.reshape(-1)[row_pick].astype(g.dtype)
    dy = _rows_of_tokens(g, row_pick, row_valid, k) * w_row[:, None]
    dw = jnp.sum(y[pick_row].astype(jnp.float32)
                 * g[:, None, :].astype(jnp.float32), axis=-1)
    dw = jnp.where(pick_held, dw, 0).astype(weights.dtype)
    return (dy.astype(y.dtype), dw, _int_zero(row_pick),
            _int_zero(row_valid), _int_zero(pick_row),
            _int_zero(pick_held))


combine.defvjp(_combine_fwd, _combine_bwd)


def grouped_product(x, w, plan):
    """Rows of group ``g`` times ``w[g]`` over the planned buffer."""
    try:
        out = ragged_matmul(x, w, plan["tile_group"], plan["tiles_used"],
                            block_m=x.shape[0] // plan["tile_group"].shape[0])
        metrics.inc("moe/dropless")
        return out
    except NotImplementedError:
        metrics.inc("moe/fallback/pallas_rejected")
        return jax.lax.ragged_dot(x, w, plan["group_rows"])


def routed_experts(x, idx, weights, w_gate_up, w_down, lo: int, hi: int,
                   activation=jax.nn.silu):
    """Steps 1-4 of the lowering, for any router and any gate
    activation: ``x [N, h]`` in the compute dtype, the router's picks
    ``idx [N, k]`` (a pick outside ``[lo, hi)`` is another chip's, or
    nobody's: a dead decode row's sentinel) and their float32
    ``weights [N, k]``, the held experts' ``w_gate_up [G, h, 2 f]`` and
    ``w_down [G, f, h]``. Returns ``(sum over held picks of w_e
    (activation(x W_gate,e) * (x W_up,e)) W_down,e  [N, h], plan)``."""
    k = idx.shape[1]
    f = w_down.shape[1]
    plan = plan_dispatch(idx, lo, hi, block_rows(idx.size, hi - lo))
    gathers = (plan["row_pick"], plan["row_valid"], plan["pick_row"],
               plan["pick_held"])
    xs = dispatch(x, *gathers, k)
    gu = grouped_product(xs, w_gate_up, plan)
    hidden = activation(gu[:, :f]) * gu[:, f:]
    y = grouped_product(hidden, w_down, plan)
    metrics.inc("moe/experts_held", hi - lo)
    return combine(y, weights, *gathers, k), plan


def _init(cfg: DeepSeekV3Config):
    return nn.initializers.normal(stddev=cfg.initializer_range)


class GatedMLP(nn.Module):
    """``(silu(u W_gate) * (u W_up)) W_down`` without biases; gate and
    up are one ``[h, 2, f]`` kernel."""
    config: DeepSeekV3Config
    width: int

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        gu = nn.DenseGeneral(
            (2, self.width), use_bias=False, name="gate_up_proj",
            dtype=dtype, param_dtype=pdtype,
            kernel_init=nn.with_logical_partitioning(
                _init(cfg), ("embed", None, "mlp")))(u)
        gu = checkpoint_name(gu, "mlp1")
        hidden = jax.nn.silu(gu[..., 0, :]) * gu[..., 1, :]
        out = nn.DenseGeneral(
            cfg.hidden_size, use_bias=False, name="down_proj",
            dtype=dtype, param_dtype=pdtype,
            kernel_init=nn.with_logical_partitioning(
                _init(cfg), ("mlp", "embed")))(hidden)
        return checkpoint_name(out, "mlp2")


class DroplessMoE(nn.Module):
    """Router over all experts, the held experts' part of the result,
    the shared expert. Returns ``(out, stats)`` with ``stats`` float32
    ``[3]``: picks that landed on held experts, largest held group over
    the mean held group, picks in all."""
    config: DeepSeekV3Config

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        lo, hi = cfg.held_experts
        groups, k = hi - lo, cfg.num_experts_per_tok
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        x = u.reshape(-1, h)
        w_gate = self.param(
            "gate", nn.with_logical_partitioning(_init(cfg),
                                                 ("embed", None)),
            (h, cfg.n_routed_experts), pdtype)
        bias = self.param(
            "e_score_correction_bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(),
                                         (None,)),
            (cfg.n_routed_experts,), pdtype)
        w_gate_up = self.param(
            "experts_gate_up",
            nn.with_logical_partitioning(
                _init(cfg), ("expert", "expert_embed", None,
                             "expert_mlp")),
            (groups, h, 2, f), pdtype)
        w_down = self.param(
            "experts_down",
            nn.with_logical_partitioning(
                _init(cfg), ("expert", "expert_mlp", "expert_embed")),
            (groups, f, h), pdtype)

        idx, weights = route(x.astype(jnp.float32),
                             w_gate.astype(jnp.float32),
                             bias.astype(jnp.float32), k,
                             cfg.routed_scaling_factor)
        routed, plan = routed_experts(
            x.astype(dtype), idx, weights,
            w_gate_up.reshape(groups, h, 2 * f).astype(dtype),
            w_down.astype(dtype), lo, hi)

        sizes = plan["sizes"].astype(jnp.float32)
        held = jnp.sum(sizes)
        stats = jnp.stack([
            held, jnp.max(sizes) * groups / jnp.maximum(held, 1.0),
            jnp.float32(idx.size)])
        shared = GatedMLP(
            cfg, cfg.moe_intermediate_size * cfg.n_shared_experts,
            name="shared_experts")(u)
        return routed.reshape(u.shape).astype(dtype) + shared, stats
