"""Jit-compiled autoregressive generation with a fixed-capacity cache.

Parity: reference ``GPTForGeneration(Hybrid).forward/sample``
(``hybrid_model.py:1208-1433``): left-padded prompts, temperature /
top-k / top-p sampling, min-length + repetition-penalty processors,
KV-cached decode. The reference fights dygraph-to-static conversion
with a growing cache and a Python while-loop (:1322-1347); here the
whole generate is ONE compiled program: prefill + ``lax.scan`` over a
static number of decode steps, cache preallocated at
``max_position_embeddings`` slots, finished rows emit ``pad`` tokens.
"""

from __future__ import annotations

import dataclasses
import math
import weakref
from functools import partial
from collections.abc import Mapping
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...parallel.sharding import with_logical_constraint
from .config import GPTConfig
from .processors import (
    hamming_diversity_processor, min_length_processor,
    repetition_penalty_processor, top_k_top_p_filter, NEG_INF,
)


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Knobs named as in the reference YAML ``Generation`` section."""
    max_dec_len: int = 20
    min_dec_len: int = 0
    #: sampling | greedy_search | beam_search — beam search goes
    #: BEYOND the reference, whose generation raises for any strategy
    #: but sampling (``hybrid_model.py:1432``)
    decode_strategy: str = "sampling"
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    num_beams: int = 1
    #: diverse (group) beam search: beams split into this many groups,
    #: decoded group-by-group within each step; later groups pay a
    #: Hamming penalty on tokens earlier groups just chose (drives
    #: ``hamming_diversity_processor``; the reference carries the
    #: processor, ``gpt/dygraph/processor.py:106-155``, but nothing
    #: invokes it). 1 = vanilla beam search.
    num_beam_groups: int = 1
    #: Hamming penalty strength for ``num_beam_groups > 1`` (the
    #: reference processor's ``diversity_rate``)
    diversity_rate: float = 0.0
    #: GNMT length penalty exponent (0 = pure log-prob)
    length_penalty: float = 0.0
    repetition_penalty: float = 1.0
    #: sampling/greedy: tile each prompt this many times before
    #: sampling — every copy samples an independent continuation
    #: (reference ``expand_inputs_for_generation``,
    #: ``hybrid_model.py:1422-1426``). beam_search: return this many
    #: best beams per prompt (must be <= num_beams).
    num_return_sequences: int = 1
    eos_token_id: int = 50256
    pad_token_id: int = 50256
    #: TPU-native: sample with the binned approximate top-k kernel
    #: instead of the full-vocab sort XLA:TPU lowers exact top_k to
    #: (~6x the rest of the sampling math at V=50k). Recall 0.99 — a
    #: bin miss lowers the k-th-value cutoff, so the candidate set
    #: can only WIDEN by a few tail tokens, never lose a
    #: high-probability one; temperature sampling cannot distinguish
    #: that from its own noise. Set False for sort-exact candidate
    #: sets. Beam search ignores this and always scores exactly.
    approx_top_k: bool = True
    #: speculative decoding on the slot server (core/serving.py):
    #: None = off; "ngram" = draft-model-free self-speculation — each
    #: request's own emitted history proposes ``spec_tokens`` draft
    #: tokens by suffix match (core/spec.py) and ONE verify forward
    #: scores the whole run (verify_step). The interface is a draft
    #: SOURCE, so a small draft-model method can slot in later.
    #: "mtp" = the served model's own multi-token-prediction block
    #: drafts inside the tick program (models/exaone_moe); the server
    #: refuses it on a model without one.
    spec_method: Optional[str] = None
    #: drafted tokens per verify tick (k); each tick commits
    #: 1..k+1 tokens. Only read when spec_method is set.
    spec_tokens: int = 4

    def __post_init__(self):
        if self.spec_method is not None:
            if self.spec_method not in ("ngram", "mtp"):
                raise ValueError(
                    f"unknown spec_method {self.spec_method!r} "
                    f"(supported: 'ngram', 'mtp')")
            if self.spec_tokens < 1:
                raise ValueError(
                    f"spec_tokens must be >= 1, got "
                    f"{self.spec_tokens}")
            if self.decode_strategy == "beam_search":
                raise ValueError(
                    "speculative decoding (spec_method) serves "
                    "sampling/greedy_search only; beam search scores "
                    "every candidate exactly and stays on the "
                    "lockstep generate() path")
        if self.num_return_sequences < 1:
            raise ValueError(
                f"num_return_sequences must be >= 1, got "
                f"{self.num_return_sequences}")
        if self.decode_strategy not in ("sampling", "greedy_search",
                                        "beam_search"):
            raise ValueError(
                f"unknown decode_strategy {self.decode_strategy!r}")
        if self.decode_strategy == "beam_search":
            if self.num_beams < 1:
                raise ValueError("num_beams must be >= 1")
            if self.num_return_sequences > self.num_beams:
                raise ValueError(
                    f"num_return_sequences ({self.num_return_sequences})"
                    f" cannot exceed num_beams ({self.num_beams})")
            if self.num_beam_groups < 1:
                raise ValueError("num_beam_groups must be >= 1")
            if self.num_beams % self.num_beam_groups:
                raise ValueError(
                    f"num_beams ({self.num_beams}) must be divisible "
                    f"by num_beam_groups ({self.num_beam_groups})")
            if self.num_beam_groups > 1 and self.diversity_rate <= 0.0:
                raise ValueError(
                    "num_beam_groups > 1 requires diversity_rate > 0 "
                    "(otherwise the groups search identically)")
            # YAML integers ("diversity_rate: 1") must not crash the
            # processor's strict float check at trace time
            object.__setattr__(self, "diversity_rate",
                               float(self.diversity_rate))

    @classmethod
    def from_config(cls, section) -> "GenerationConfig":
        import dataclasses as dc
        fields = {f.name for f in dc.fields(cls)}
        kwargs = {k: v for k, v in dict(section or {}).items()
                  if k in fields and v is not None}
        return cls(**kwargs)


def _decode_bias(valid_keys: jax.Array, dtype=jnp.float32) -> jax.Array:
    """[b, kv] validity -> additive [b, 1, 1, kv] bias."""
    return jnp.where(valid_keys, 0.0, NEG_INF)[:, None, None, :].astype(
        dtype)


def _unstack_layer_params(tree, num_layers: int):
    """Expand every ``decoder`` nn.scan stack (leaves with a leading
    ``num_layers`` axis) into ``decoder_0 .. decoder_{L-1}`` subtrees
    — the parameter layout the unrolled (``scan_layers=False``) model
    expects."""
    if not isinstance(tree, Mapping):
        return tree
    out = {}
    for key, sub in tree.items():
        if key == "decoder":
            for i in range(num_layers):
                out[f"decoder_{i}"] = jax.tree.map(
                    lambda x, i=i: x[i], dict(sub))
        else:
            out[key] = _unstack_layer_params(sub, num_layers)
    return out


def _has_decoder_stack(tree) -> bool:
    if not isinstance(tree, Mapping):
        return False
    return any(k == "decoder" or _has_decoder_stack(v)
               for k, v in tree.items())


def _unrolled_twin(model, params):
    """Decode-path twin with the layer loop UNROLLED.

    Training wants ``nn.scan`` over layers (one compiled layer body).
    Cached decode wants the opposite: under the scan, each step must
    dynamic-slice every layer's [b, h, d, capacity] K/V out of the
    stacked cache carry and dynamic-update-slice it back, and XLA
    materializes those as full-buffer copies — measured ~40% of decode
    step time at 345M/bs8 (projects/gpt/docs/inference analysis).
    Unrolled, each layer owns a plain cache buffer that XLA updates in
    place.

    It is the CACHE that is unrolled. The parameters are only
    re-indexed: ``decoder_i``'s leaf is row ``i`` of the stack the
    scan came with, a static slice that feeds the layer's product.
    :func:`generate` takes the rows at trace time; a server never
    takes them apart on the host (:func:`pack_launch_params` hands
    this function marks for arrays, keeps the stacks as the launch's
    leaves and slices inside the program), because a launch pays for
    every leaf it is handed, not for what is in it."""
    cfg = model.config
    if not cfg.scan_layers or not _has_decoder_stack(params):
        return model, params
    twin = type(model)(dataclasses.replace(cfg, scan_layers=False))
    return twin, _unstack_layer_params(params, cfg.num_layers)


# -- what a launch passes ---------------------------------------------
#
# A jitted call handles its arguments leaf by leaf (a hold and an event
# a buffer, whatever its size: ~1.2 us a read-only leaf on the chip's
# host, PERF.md 6, PR 45), so a server keeps its parameters as FEWER
# arrays: leaves that agree in shape, dtype and sharding ride stacked
# in one ``[n, ...]`` array, and the per-layer tree the model wants is
# put together again by static slices INSIDE the program
# (:func:`launch_tree`, the first line of every slot primitive), where
# XLA fuses each slice into what reads it. What is NOT stacked: a leaf
# over ``STACK_LEAF_BYTES`` (every matrix), a leaf alone in its group,
# a leaf spread over more than one device; and nothing of the cache
# (the Pallas writes and decode kernels alias each pool leaf in place:
# a stacked pool is the scan's carry again) nor of the slot state.

#: the largest leaf that joins a stack. Slicing a stack is free where
#: XLA fuses the slice into the product that reads it, and it does;
#: but a weight that is a row of a stack is no longer a buffer of its
#: own that XLA prefetches into fast memory ahead of its product, and
#: on the chip GPT-345M's tick ran 1.34 ms for 1.15 with its 2-8 MiB
#: matrices stacked (PERF.md 6, PR 45). Norm scales, biases, router
#: and state rows lie under this; no matrix of a served model does
STACK_LEAF_BYTES = 1 << 20


class _Arrived:
    """Stands for one array of the tree a server was given while
    :func:`_unrolled_twin` lays the per-layer tree out: indexing it
    gives a mark of the row, not a slice."""

    __slots__ = ("array",)

    def __init__(self, array):
        self.array = array

    def __getitem__(self, row: int):
        return _Row(self, row)


class _Row:
    """Row ``row`` of the array ``stack`` stands for."""

    __slots__ = ("stack", "row")

    def __init__(self, stack: "_Arrived", row: int):
        self.stack, self.row = stack, row


class _LaunchPlan:
    """The static half of :class:`LaunchParams`: the per-layer tree's
    structure and, leaf by leaf, which array holds it and in which row
    (None: the array IS the leaf). Hashed by identity and made through
    :meth:`of`, which hands equal layouts the same plan: servers of
    one model share their compiled programs."""

    __slots__ = ("treedef", "where", "rows")
    _made: dict = {}

    @classmethod
    def of(cls, treedef, where) -> "_LaunchPlan":
        """THE plan of this layout (``where``: ``(array, row)`` a
        leaf of ``treedef``)."""
        where = tuple(where)
        plan = cls._made.get((treedef, where))
        if plan is None:
            plan = cls._made[treedef, where] = cls()
            plan.treedef, plan.where = treedef, where
            #: array -> positions of the leaves it holds, in row order
            plan.rows = [[] for _ in range(
                1 + max((a for a, _ in where), default=-1))]
            for row, pos, a in sorted(
                    (row, pos, a) for pos, (a, row) in enumerate(where)
                    if row is not None):
                plan.rows[a].append(pos)
        return plan


def _stack_key(array, stacked: bool = False):
    """What leaves must share to ride in one stack, or None for a
    leaf that stays its own: observed from the leaf alone (from one
    row of it, where ``array`` is a stack already)."""
    sharding = getattr(array, "sharding", None)
    if sharding is None or len(sharding.device_set) > 1:
        return None
    shape = array.shape[1:] if stacked else array.shape
    dtype = jnp.dtype(array.dtype)
    if math.prod(shape) * dtype.itemsize > STACK_LEAF_BYTES:
        return None
    return shape, dtype, sharding


@jax.jit
def _stack_groups(groups):
    """Every group of leaves as one ``[n, ...]`` array: one program
    for all of a packing's groups, not one a group."""
    return tuple(jnp.stack(g) for g in groups)


@jax.jit
def _unstack_groups(stacks):
    return tuple(tuple(s[i] for i in range(s.shape[0])) for s in stacks)


@jax.tree_util.register_pytree_node_class
class LaunchParams:
    """A server's parameters as its launches pass them: ``arrays``
    (the pytree's children) under a static plan (its auxiliary data).
    :meth:`tree` and :meth:`assign` are the per-layer tree's way out
    and back in, for set-up and tests; a step uses neither."""

    def __init__(self, plan: _LaunchPlan, arrays):
        self.plan = plan
        self.arrays = tuple(arrays)
        #: weak references to the stacked leaves :meth:`tree` last
        #: handed out, by leaf position
        self._read = {}

    def tree_flatten(self):
        return self.arrays, self.plan

    @classmethod
    def tree_unflatten(cls, plan, arrays):
        return cls(plan, arrays)

    def tree(self):
        """The per-layer tree, the stacks sliced apart again in one
        jitted call (a copy of every stacked leaf while the caller
        holds it)."""
        stacked = [a for a, rows in enumerate(self.plan.rows) if rows]
        parts = dict(zip(stacked, _unstack_groups(
            tuple(self.arrays[a] for a in stacked)))) if stacked else {}
        leaves = [self.arrays[a] if row is None else parts[a][row]
                  for a, row in self.plan.where]
        self._read = {pos: weakref.ref(leaves[pos])
                      for a in stacked for pos in self.plan.rows[a]}
        return self.plan.treedef.unflatten(leaves)

    def assign(self, tree) -> "LaunchParams":
        """``tree`` packed under this plan. A stack none of whose
        leaves is another array than :meth:`tree` last handed out is
        kept as it is; the others are stacked again, in one jitted
        call. A tree of another structure, or a leaf that no longer
        fits its stack, is packed afresh (and its programs compile
        again)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.plan.treedef:
            return pack_launch_params(None, tree)[1]
        arrays, again = list(self.arrays), []
        for a, rows in enumerate(self.plan.rows):
            if not rows:
                continue
            if all(pos in self._read and self._read[pos]() is leaves[pos]
                   for pos in rows):
                continue
            key = _stack_key(self.arrays[a], stacked=True)
            if any(_stack_key(leaves[pos]) != key for pos in rows):
                return pack_launch_params(None, tree)[1]
            again.append(a)
        for pos, (a, row) in enumerate(self.plan.where):
            if row is None:
                arrays[a] = leaves[pos]
        if again:
            for a, new in zip(again, _stack_groups(tuple(
                    tuple(leaves[pos] for pos in self.plan.rows[a])
                    for a in again))):
                arrays[a] = new
        return LaunchParams(self.plan, arrays)


def pack_launch_params(model, params):
    """``(model's unrolled twin, params as a server launches them)``.

    The per-layer tree is laid out over marks (:func:`_unrolled_twin`
    with an :class:`_Arrived` for every array), so a model that arrives
    scan-stacked keeps the stacks it came with: a stack whose rows may
    be stacked is an array of the launch as it is, the rows of one that
    may not are sliced out here, once. Every other leaf joins the
    leaves of its shape, dtype and sharding in one ``jnp.stack``, all
    groups in one jitted call; a leaf with no such peer, or over
    ``STACK_LEAF_BYTES``, or on more than one device, passes through
    as it is. ``model`` None: ``params`` is a per-layer tree already."""
    marked = jax.tree.map(_Arrived, params)
    if model is not None:
        model, marked = _unrolled_twin(model, marked)
    marks, treedef = jax.tree_util.tree_flatten(marked)
    arrays, where, groups = [], [None] * len(marks), {}

    def own(pos, array):
        key = _stack_key(array)
        if key is None:
            where[pos] = (len(arrays), None)
            arrays.append(array)
        else:
            groups.setdefault(key, []).append((pos, array))

    came = {}
    for pos, m in enumerate(marks):
        if isinstance(m, _Row):
            came.setdefault(id(m.stack), (m.stack.array, []))[1].append(
                (m.row, pos))
        else:
            own(pos, m.array)
    for stack, rows in came.values():
        if len(rows) == stack.shape[0] and \
                _stack_key(stack, stacked=True) is not None:
            for row, pos in rows:
                where[pos] = (len(arrays), row)
            arrays.append(stack)
        else:
            for row, pos in rows:
                own(pos, stack[row])
    alone = [g[0] for g in groups.values() if len(g) == 1]
    groups = [g for g in groups.values() if len(g) > 1]
    for pos, array in alone:
        where[pos] = (len(arrays), None)
        arrays.append(array)
    stacks = _stack_groups(tuple(
        tuple(array for _, array in g) for g in groups)) if groups else ()
    for g, stack in zip(groups, stacks):
        for row, (pos, _) in enumerate(g):
            where[pos] = (len(arrays), row)
        arrays.append(stack)
    return model, LaunchParams(_LaunchPlan.of(treedef, where), arrays)


def launch_tree(params):
    """The per-layer tree of a :class:`LaunchParams`, by static slices
    (inside a traced function: the first line of every slot
    primitive); any other tree as it is."""
    if not isinstance(params, LaunchParams):
        return params
    return params.plan.treedef.unflatten(
        [params.arrays[a] if row is None else
         jax.lax.index_in_dim(params.arrays[a], row, 0, keepdims=False)
         for a, row in params.plan.where])


def _compute_params(params, compute_dtype):
    """``params`` cast once to the decode path's compute dtype. flax
    casts fp32 params inside every op, so a decode loop would stream
    fp32 bytes each token; one up-front cast is numerically identical
    and halves the per-token parameter bandwidth (the decode
    bottleneck). int8 kernels (non-floating) and their fp32
    ``kernel_scale`` dequant grids (quant_execution,
    docs/quantization.md) pass through: the scale grid is part of the
    PTQ artifact's numerics."""
    if compute_dtype == jnp.float32:
        return params

    def _cast(path, p):
        name = getattr(path[-1], "key", "")
        if name == "kernel_scale" or not jnp.issubdtype(
                p.dtype, jnp.floating):
            return p
        return p.astype(compute_dtype)
    return jax.tree_util.tree_map_with_path(_cast, params)


@partial(jax.jit, static_argnames=("model", "gen_cfg"))
def generate(model, params, input_ids: jax.Array,
             attention_mask: Optional[jax.Array], rng: jax.Array,
             gen_cfg: GenerationConfig) -> jax.Array:
    """Returns generated token ids ``[b * num_return_sequences,
    max_dec_len]`` — prompt-major when ``num_return_sequences > 1``
    (rows ``i*n .. i*n + n - 1`` are prompt ``i``'s copies).

    ``input_ids`` is left-padded ``[b, prompt_len]``;
    ``attention_mask`` marks real tokens (1) vs pads (0), or None for
    unpadded prompts.
    """
    model, params = _unrolled_twin(model, params)
    cfg: GPTConfig = model.config
    beam = gen_cfg.decode_strategy == "beam_search"
    # beam search keeps num_beams rows per prompt live; sampling tiles
    # by num_return_sequences (reference expand_inputs_for_generation,
    # hybrid_model.py:1422-1426 — tile BEFORE prefill: the copies
    # prefill redundantly, the reference's cost profile; re-tiling the
    # scan-stacked cache after one prefill would be fragile)
    tile = gen_cfg.num_beams if beam else gen_cfg.num_return_sequences
    if tile > 1:
        input_ids = jnp.repeat(input_ids, tile, axis=0)
        if attention_mask is not None:
            attention_mask = jnp.repeat(attention_mask, tile, axis=0)
    b, prompt_len = input_ids.shape
    # the cache allocates cache_capacity slots (max_position_embeddings
    # rounded up to a 128 multiple — config.py) so the decode-kernel
    # tiling never rejects the cache length; the validity map must
    # cover every allocated slot, while the LENGTH bound below stays
    # at max_position_embeddings (the position-embedding table size)
    capacity = cfg.cache_capacity
    params = _compute_params(params, jnp.dtype(cfg.dtype))
    if prompt_len + gen_cfg.max_dec_len > cfg.max_position_embeddings:
        raise ValueError(
            f"prompt ({prompt_len}) + max_dec_len "
            f"({gen_cfg.max_dec_len}) exceeds the cache capacity "
            f"(max_position_embeddings "
            f"{cfg.max_position_embeddings})")
    if attention_mask is None:
        attention_mask = jnp.ones((b, prompt_len), jnp.int32)
    attention_mask = attention_mask.astype(jnp.int32)
    lengths = attention_mask.sum(axis=-1)                      # [b]
    position_ids = jnp.clip(
        jnp.cumsum(attention_mask, axis=-1) - 1, 0)

    # key-slot validity over the cache: prompt slots follow the pad
    # mask, decode slots become valid as they are written
    pad_cols = jnp.zeros((b, capacity - prompt_len), jnp.int32)
    base_valid = jnp.concatenate([attention_mask, pad_cols], axis=-1)

    # -- prefill -------------------------------------------------------
    # keys span the full preallocated cache during cached prefill, so
    # the pad bias covers all capacity slots (causality masks the rest)
    logits, mutated = model.apply(
        {"params": params}, input_ids, position_ids=position_ids,
        attn_bias=_decode_bias(base_valid.astype(bool)),
        use_cache=True, deterministic=True, mutable=["cache"])
    cache = mutated["cache"]
    last_logits = logits[:, -1, :].astype(jnp.float32)

    appeared0 = jnp.zeros((b, cfg.vocab_size), bool)
    appeared0 = appeared0.at[
        jnp.arange(b)[:, None], input_ids].set(attention_mask > 0)

    def sample_token(logits, appeared, step_idx, step_rng):
        """Pick the next token per row (greedy or filtered sample)."""
        logits = repetition_penalty_processor(
            logits, appeared, gen_cfg.repetition_penalty)
        # step_idx == tokens generated before this sample: EOS stays
        # banned until min_dec_len tokens exist (reference
        # MinLengthLogitsProcessor counts the same way)
        logits = min_length_processor(
            logits, step_idx, gen_cfg.min_dec_len,
            gen_cfg.eos_token_id)
        if gen_cfg.decode_strategy == "greedy_search":
            return jnp.argmax(logits, axis=-1)
        logits = logits / jnp.maximum(gen_cfg.temperature, 1e-6)
        logits = top_k_top_p_filter(logits, gen_cfg.top_k,
                                    gen_cfg.top_p,
                                    approx=gen_cfg.approx_top_k)
        return jax.random.categorical(step_rng, logits, axis=-1)

    def body(carry, step_idx):
        """One greedy/sampling decode step of the scan."""
        cache, logits, appeared, finished, valid = carry
        step_rng = jax.random.fold_in(rng, step_idx)
        token = sample_token(logits, appeared, step_idx, step_rng)
        token = jnp.where(finished, gen_cfg.pad_token_id, token)
        finished = finished | (token == gen_cfg.eos_token_id)
        appeared = appeared.at[jnp.arange(b), token].set(True)

        # the new key lands at slot prompt_len + step_idx
        slot = prompt_len + step_idx
        valid = valid.at[:, slot].set(1)
        step_pos = (lengths + step_idx)[:, None]               # [b, 1]
        logits, mutated = model.apply(
            {"params": params, "cache": cache}, token[:, None],
            position_ids=step_pos,
            attn_bias=_decode_bias(valid.astype(bool)),
            use_cache=True, deterministic=True, mutable=["cache"])
        cache = mutated["cache"]
        next_logits = logits[:, -1, :].astype(jnp.float32)
        return (cache, next_logits, appeared, finished, valid), token

    if beam:
        return _beam_search(model, params, cache, last_logits,
                            base_valid, lengths, prompt_len, gen_cfg,
                            appeared0)

    finished0 = jnp.zeros((b,), bool)
    (_, _, _, _, _), tokens = jax.lax.scan(
        body, (cache, last_logits, appeared0, finished0, base_valid),
        jnp.arange(gen_cfg.max_dec_len))
    return tokens.T  # [b, max_dec_len]


def _gather_cache(cache, gidx):
    """Reorder the decode cache's batch axis to beam assignments.

    The KV leaves are ``[b, h, d, S]`` (or ``[L, b, h, d, S]`` under
    the layer scan) — the batch axis is always ``ndim - 4``;
    ``cache_index`` is batch-free and passes through."""
    def g(path, leaf):
        name = getattr(path[-1], "key", "")
        if name in ("cached_key", "cached_value",
                    "cached_key_scale", "cached_value_scale"):
            return jnp.take(leaf, gidx, axis=leaf.ndim - 4)
        return leaf
    return jax.tree_util.tree_map_with_path(g, cache)


def _length_penalty(length, alpha):
    """GNMT: ``((5 + len) / 6) ** alpha`` (alpha 0 = pure log-prob)."""
    return ((5.0 + length.astype(jnp.float32)) / 6.0) ** alpha


def _beam_search(model, params, cache, last_logits, base_valid,
                 lengths, prompt_len, gen_cfg, appeared0):
    """Beam search over the tiled ``b0 * k`` batch (beyond the
    reference, which supports sampling only — its processor file
    carries beam machinery the model never drives).

    Two-pool fixed-width search inside one ``lax.scan`` (the t5x
    shape): per step the top ``2k`` of the ``k * V`` candidates per
    prompt split into EOS hypotheses — inserted, length-penalized,
    into a separate finished pool they can never be evicted from by
    live beams — and the ``k`` best non-EOS continuations, which the
    KV cache is reordered to follow. The final ranking merges the
    finished pool with the length-penalized live beams and returns the
    ``num_return_sequences`` best per prompt, prompt-major. Applies
    min-length and repetition-penalty processing like the sampling
    path.

    NOTE: beam scores accumulate the PROCESSED log-probs (after
    repetition-penalty / min-length / Hamming shaping), matching the
    reference's and HF's beam semantics — so with
    ``repetition_penalty != 1.0`` the ranking deviates from raw model
    likelihood by design. Pinned at k=1 by
    ``test_beam_search_repetition_penalty_k1_equals_greedy`` and at
    k>1 by ``test_beam_search_processed_score_semantics_k_gt_1``
    (an independent teacher-forced replay of the processor pipeline
    must reproduce the returned beam ordering).

    With ``num_beam_groups > 1`` this becomes diverse (group) beam
    search: each group of ``k/G`` beams runs the same two-pool update,
    but groups are scored sequentially within a step and every group
    after the first pays ``hamming_diversity_processor``'s penalty on
    the tokens earlier groups just chose. One ``model.apply`` still
    serves all ``k`` beams per step — only the selection loop is
    per-group.
    """
    k = gen_cfg.num_beams
    G = gen_cfg.num_beam_groups
    kg = k // G
    V = last_logits.shape[-1]
    b = last_logits.shape[0]
    b0 = b // k
    eos, pad = gen_cfg.eos_token_id, gen_cfg.pad_token_id
    dec = gen_cfg.max_dec_len

    # only the first beam OF EACH GROUP is live at step 0 (all k rows
    # are prompt copies; a dead group would never start)
    alive0 = jnp.tile(
        jnp.asarray(([0.0] + [NEG_INF] * (kg - 1)) * G, jnp.float32),
        (b0, 1))
    seqs0 = jnp.full((b, dec), pad, jnp.int32)
    fin_scores0 = jnp.full((b0, G, kg), NEG_INF, jnp.float32)
    fin_seqs0 = jnp.full((b0, G, kg, dec), pad, jnp.int32)
    # appeared0 carries the prompt tokens (same repetition-penalty
    # seeding as the sampling path)

    def body(carry, step_idx):
        """One beam-search expansion step of the scan."""
        (cache, logits, alive, seqs, appeared, fin_scores,
         fin_seqs, valid) = carry
        logits = repetition_penalty_processor(
            logits.astype(jnp.float32), appeared,
            gen_cfg.repetition_penalty)
        logits = min_length_processor(logits, step_idx,
                                      gen_cfg.min_dec_len, eos)
        logp = jax.nn.log_softmax(logits, -1).reshape(b0, k, V)

        cur_tokens = jnp.zeros((b0, k), jnp.int32)
        galive, gtokens, gsrc = [], [], []
        gfin_scores, gfin_seqs = [], []
        for g in range(G):
            sl = slice(g * kg, (g + 1) * kg)
            glogp = logp[:, sl]                        # [b0, kg, V]
            if g > 0 and gen_cfg.diversity_rate > 0.0:
                shaped = hamming_diversity_processor(
                    glogp.reshape(b0 * kg, V),
                    cur_tokens.reshape(-1), g,
                    gen_cfg.diversity_rate, k, G)
                glogp = shaped.reshape(b0, kg, V)
            cand = alive[:, sl][..., None] + glogp
            n_top = min(2 * kg, kg * V)
            top_scores, top_idx = jax.lax.top_k(
                cand.reshape(b0, kg * V), n_top)
            src_beam = top_idx // V + g * kg           # absolute beam
            token = (top_idx % V).astype(jnp.int32)
            is_eos = token == eos

            # group finished pool: EOS candidates enter
            # length-penalized and compete only against other finished
            # hypotheses of the same group
            cand_fin = jnp.where(
                is_eos,
                top_scores / _length_penalty(
                    jnp.full_like(top_scores, step_idx + 1.0),
                    gen_cfg.length_penalty),
                NEG_INF)
            # materialize each candidate's sequence (prefix + eos)
            cand_rows = jnp.arange(b0)[:, None] * k + src_beam
            cand_seqs = seqs[cand_rows.reshape(-1)].reshape(
                b0, n_top, dec)
            cand_seqs = cand_seqs.at[:, :, step_idx].set(token)
            merged_scores = jnp.concatenate(
                [fin_scores[:, g], cand_fin], axis=1)
            merged_seqs = jnp.concatenate(
                [fin_seqs[:, g], cand_seqs], axis=1)
            fs, keep = jax.lax.top_k(merged_scores, kg)
            gfin_scores.append(fs)
            gfin_seqs.append(jnp.take_along_axis(
                merged_seqs, keep[..., None], axis=1))

            # group alive pool: best kg non-EOS continuations
            alive_cand = jnp.where(is_eos, NEG_INF, top_scores)
            al, pick = jax.lax.top_k(alive_cand, kg)   # [b0, kg]
            tok = jnp.take_along_axis(token, pick, axis=1)
            galive.append(al)
            gtokens.append(tok)
            gsrc.append(jnp.take_along_axis(src_beam, pick, axis=1))
            cur_tokens = cur_tokens.at[:, sl].set(tok)

        alive = jnp.concatenate(galive, axis=1)        # [b0, k]
        token_k = jnp.concatenate(gtokens, axis=1)
        src_k = jnp.concatenate(gsrc, axis=1)
        fin_scores = jnp.stack(gfin_scores, axis=1)    # [b0, G, kg]
        fin_seqs = jnp.stack(gfin_seqs, axis=1)
        gidx = (jnp.arange(b0)[:, None] * k + src_k).reshape(-1)

        seqs = seqs[gidx].at[:, step_idx].set(token_k.reshape(-1))
        appeared = appeared[gidx].at[
            jnp.arange(b), token_k.reshape(-1)].set(True)
        cache = _gather_cache(cache, gidx)
        valid = valid[gidx].at[:, prompt_len + step_idx].set(1)
        step_pos = (lengths + step_idx)[:, None]     # equal per group
        logits, mutated = model.apply(
            {"params": params, "cache": cache},
            token_k.reshape(-1)[:, None], position_ids=step_pos,
            attn_bias=_decode_bias(valid.astype(bool)),
            use_cache=True, deterministic=True, mutable=["cache"])
        return (mutated["cache"], logits[:, -1].astype(jnp.float32),
                alive, seqs, appeared, fin_scores, fin_seqs,
                valid), None

    (_, _, alive, seqs, _, fin_scores, fin_seqs, _), _ = jax.lax.scan(
        body, (cache, last_logits, alive0, seqs0, appeared0,
               fin_scores0, fin_seqs0, base_valid), jnp.arange(dec))
    fin_scores = fin_scores.reshape(b0, k)
    fin_seqs = fin_seqs.reshape(b0, k, dec)

    # merge live beams (length-penalized at full length) with the
    # finished pool and pick the n best per prompt
    alive_final = alive / _length_penalty(
        jnp.full_like(alive, float(dec)), gen_cfg.length_penalty)
    all_scores = jnp.concatenate([fin_scores, alive_final], axis=1)
    all_seqs = jnp.concatenate(
        [fin_seqs, seqs.reshape(b0, k, dec)], axis=1)
    _, best = jax.lax.top_k(all_scores,
                            gen_cfg.num_return_sequences)
    out = jnp.take_along_axis(all_seqs, best[..., None], axis=1)
    return out.reshape(b0 * gen_cfg.num_return_sequences, dec)


# -- continuous-batching slot primitives -------------------------------
#
# The lockstep generate() above advances every row at one shared cache
# index. The serving path (core/serving.py) instead keeps a persistent
# [slots, ...] KV cache whose rows are independent requests at
# independent lengths: prefill_into_slots admits new requests into free
# slot rows (one compiled shape per prompt-length bucket), decode_step
# advances ALL slots one token with per-slot lengths/sampling state via
# the ragged attention dispatch (cache_lengths -> flash_decode_ragged
# or the XLA per-row-offset fallback — docs/inference.md).


class SlotState(NamedTuple):
    """Per-slot decode state carried across serving ticks.

    One row per KV-cache slot; a pytree so the whole state threads
    through the jitted ``decode_step`` unchanged in structure.
    """
    #: [slots] int32 — valid cache positions (the slot's token count)
    lengths: jax.Array
    #: [slots] int32 — tokens generated so far (the per-request
    #: step_idx of the lockstep loop)
    dec_count: jax.Array
    #: [slots] int32 — per-request rng stream id (folded into the
    #: server rng so a request's sample stream is independent of slot
    #: assignment and neighbours)
    nonce: jax.Array
    #: [slots, V] bool — repetition-penalty token set
    appeared: jax.Array
    #: [slots] bool — emitted EOS
    finished: jax.Array
    #: [slots] bool — slot holds a live request
    active: jax.Array
    #: [slots, V] f32 — logits the next tick samples from
    last_logits: jax.Array
    #: [slots] int32 — draft token the previous verify tick REJECTED
    #: under sampling (-1 = none): the standard rejection-sampling
    #: residual excludes it, so the next tick's sample from
    #: ``last_logits`` masks it out post-filter (verify_step). Always
    #: -1 under greedy and with speculation off.
    rejected: jax.Array


def init_slot_state(num_slots: int, vocab_size: int) -> SlotState:
    """All-free slot state (no request admitted anywhere)."""
    z = jnp.zeros((num_slots,), jnp.int32)
    f = jnp.zeros((num_slots,), bool)
    return SlotState(
        lengths=z, dec_count=z, nonce=z,
        appeared=jnp.zeros((num_slots, vocab_size), bool),
        finished=f, active=f,
        last_logits=jnp.zeros((num_slots, vocab_size), jnp.float32),
        rejected=jnp.full((num_slots,), -1, jnp.int32))


def init_slot_cache(model, params, num_slots: int):
    """Zeroed persistent ``[slots, ...]`` KV-cache tree, shaped by
    ``jax.eval_shape`` over a cached apply (no compile, no FLOPs)."""
    shapes = jax.eval_shape(
        lambda p: model.apply(
            {"params": launch_tree(p)}, jnp.zeros((num_slots, 1), jnp.int32),
            use_cache=True, deterministic=True,
            mutable=["cache"])[1]["cache"],
        params)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _constrain_slot_cache(cache):
    """Pin the serving cache's logical layout: slots over the dataflow
    plane, heads over mp (``cache_slots`` rule in parallel/sharding.py).
    A no-op without an active mesh/rules context."""
    def g(path, leaf):
        name = getattr(path[-1], "key", "")
        if name in ("cached_key", "cached_value",
                    "cached_key_scale", "cached_value_scale"):
            axes = (None,) * (leaf.ndim - 4) + (
                "cache_slots", "act_heads", None, None)
            return with_logical_constraint(leaf, axes)
        return leaf
    return jax.tree_util.tree_map_with_path(g, cache)


def _scatter_slot_rows(cache, rows, slot_ids):
    """Write per-request cache rows (batch = len(slot_ids)) into the
    persistent slot cache at ``slot_ids``. KV leaves are
    ``[..., b, h, d, S]`` with the batch axis at ``ndim - 4`` (matching
    ``_gather_cache``); the scalar ``cache_index`` leaves keep the
    persistent cache's value — slot lengths live in ``SlotState``."""
    def put(path, pleaf, rleaf):
        name = getattr(path[-1], "key", "")
        if name in ("cached_key", "cached_value",
                    "cached_key_scale", "cached_value_scale"):
            ax = pleaf.ndim - 4
            idx = (slice(None),) * ax + (slot_ids,)
            return pleaf.at[idx].set(rleaf.astype(pleaf.dtype))
        return pleaf
    return jax.tree_util.tree_map_with_path(put, cache, rows)


@partial(jax.jit, static_argnames=("model",),
         donate_argnames=("cache",))
def prefill_into_slots(model, params, cache, state: SlotState,
                       slot_ids: jax.Array, input_ids: jax.Array,
                       true_lengths: jax.Array,
                       nonce: jax.Array, adapter_ids=None):
    """Admit requests into free slots: prefill + scatter.

    ``input_ids`` is RIGHT-padded ``[n, bucket]`` (prompts start at
    cache position 0 of their slot; the pad tail past each row's
    ``true_lengths`` is never read — causality masks it during prefill
    and the per-slot length masks it during decode, so bucketing
    prompt lengths to a few compiled shapes costs nothing but the
    padded prefill FLOPs). Runs the ordinary scalar-cache-index
    prefill over the ``n`` new requests, gathers each row's
    last-real-token logits, and scatters the fresh cache rows and
    sampling state into the persistent ``[slots, ...]`` cache /
    ``SlotState`` at ``slot_ids``. One compiled shape per
    ``(n, bucket)`` pair.
    """
    params = launch_tree(params)
    n, bucket = input_ids.shape
    pos = jnp.broadcast_to(
        jnp.arange(bucket, dtype=jnp.int32)[None, :], (n, bucket))
    logits, mutated = model.apply(
        {"params": params}, input_ids, position_ids=pos,
        use_cache=True, deterministic=True, adapter_ids=adapter_ids,
        mutable=["cache"])
    last = jnp.take_along_axis(
        logits.astype(jnp.float32),
        jnp.maximum(true_lengths, 1)[:, None, None] - 1, axis=1)[:, 0]
    real = pos < true_lengths[:, None]                    # [n, bucket]
    appeared = jnp.zeros((n, model.config.vocab_size), bool)
    # scatter-max: True (a real occurrence) wins over the pad tail's
    # False even when a token id shows up in both regions
    appeared = appeared.at[jnp.arange(n)[:, None], input_ids].max(real)

    cache = _scatter_slot_rows(cache, mutated["cache"], slot_ids)
    cache = _constrain_slot_cache(cache)
    state = SlotState(
        lengths=state.lengths.at[slot_ids].set(true_lengths),
        dec_count=state.dec_count.at[slot_ids].set(0),
        nonce=state.nonce.at[slot_ids].set(nonce),
        appeared=state.appeared.at[slot_ids].set(appeared),
        finished=state.finished.at[slot_ids].set(False),
        active=state.active.at[slot_ids].set(True),
        last_logits=state.last_logits.at[slot_ids].set(last),
        rejected=state.rejected.at[slot_ids].set(-1))
    return cache, state


def _decode_tick_impl(model, params, cache, state: SlotState,
                      rng: jax.Array, gen_cfg: GenerationConfig,
                      page_table=None, adapter_ids=None):
    """Trace-level body of one plain decode tick — the SHARED step
    function of the standalone :func:`decode_step` jit and the fused
    :func:`decode_loop` ``lax.while_loop``; both paths trace exactly
    this code, so the loop at any T commits the same tokens the
    one-tick-per-round-trip server does."""
    slots = state.lengths.shape[0]
    logits = repetition_penalty_processor(
        state.last_logits, state.appeared, gen_cfg.repetition_penalty)
    logits = min_length_processor(
        logits, state.dec_count[:, None], gen_cfg.min_dec_len,
        gen_cfg.eos_token_id)
    if gen_cfg.decode_strategy == "greedy_search":
        token = jnp.argmax(logits, axis=-1)
    elif gen_cfg.decode_strategy == "sampling":
        logits = logits / jnp.maximum(gen_cfg.temperature, 1e-6)
        logits = top_k_top_p_filter(logits, gen_cfg.top_k,
                                    gen_cfg.top_p,
                                    approx=gen_cfg.approx_top_k)
        # per-slot streams: (request nonce, request step) fold so a
        # request samples the same continuation whichever slot it
        # lands in and whenever it was admitted
        step_keys = jax.vmap(
            lambda n, c: jax.random.fold_in(
                jax.random.fold_in(rng, n), c))(
            state.nonce, state.dec_count)
        token = jax.vmap(
            lambda kk, lg: jax.random.categorical(kk, lg))(
            step_keys, logits)
    else:
        raise ValueError(
            f"decode_step supports sampling/greedy_search, got "
            f"{gen_cfg.decode_strategy!r} (beam search stays on the "
            f"lockstep generate() path)")
    token = jnp.where(state.finished | ~state.active,
                      gen_cfg.pad_token_id, token).astype(jnp.int32)
    finished = state.finished | (
        state.active & (token == gen_cfg.eos_token_id))
    appeared = state.appeared.at[jnp.arange(slots), token].set(True)

    step_pos = jnp.clip(state.lengths, 0,
                        model.config.max_position_embeddings - 1)
    logits2, mutated = model.apply(
        {"params": params, "cache": cache}, token[:, None],
        position_ids=step_pos[:, None], use_cache=True,
        deterministic=True, cache_lengths=state.lengths,
        page_table=page_table, adapter_ids=adapter_ids,
        mutable=["cache"])
    cache = _constrain_slot_cache(mutated["cache"])
    new_state = SlotState(
        lengths=jnp.where(state.active, state.lengths + 1,
                          state.lengths),
        dec_count=jnp.where(state.active, state.dec_count + 1,
                            state.dec_count),
        nonce=state.nonce,
        appeared=appeared,
        finished=finished,
        active=state.active,
        last_logits=logits2[:, -1].astype(jnp.float32),
        rejected=state.rejected)
    return cache, new_state, token


@partial(jax.jit, static_argnames=("model", "gen_cfg"),
         donate_argnames=("cache",))
def decode_step(model, params, cache, state: SlotState,
                rng: jax.Array, gen_cfg: GenerationConfig,
                page_table=None, adapter_ids=None):
    """One shared decode tick over the whole slot batch.

    Mirrors the lockstep ``body`` of :func:`generate` slot-for-slot —
    sample from ``last_logits`` through the same processor pipeline
    (repetition penalty over ``appeared``, min-length over the
    PER-SLOT ``dec_count``), then advance the model one token with
    per-slot cache writes and ragged attention (``cache_lengths``).
    Greedy decoding therefore reproduces ``generate()`` exactly,
    whatever mix of lengths/admission times the slots hold. Inactive
    (free) slots ride along as pad tokens with frozen lengths; their
    writes land at their stale position and are overwritten before any
    later read (prefill rewrites the full row at admission).

    Returns ``(cache, state, harvest)`` — :func:`pack_harvest` at
    ``T = 1, k = 0``: its ``window[:, 0, 0]`` is what each slot
    emitted this tick (pad for finished/inactive slots).
    """
    params = launch_tree(params)
    cache, state, token = _decode_tick_impl(
        model, params, cache, state, rng, gen_cfg, page_table,
        adapter_ids)
    return cache, state, pack_harvest(token[:, None, None], None, state)


#: fold_in salt separating a verify tick's ACCEPT uniform at request
#: step c+j from the categorical the NEXT tick draws at the same step
#: when that draft is rejected (the correction token) — without it the
#: two draws would share a key and correlate, breaking the
#: rejection-sampling guarantee.
SPEC_ACCEPT_SALT = 7919


def _verify_tick_impl(model, params, cache, state: SlotState,
                      drafts: jax.Array, rng: jax.Array,
                      gen_cfg: GenerationConfig, page_table=None,
                      adapter_ids=None):
    """Trace-level body of one speculative verify tick — the SHARED
    step function of the standalone :func:`verify_step` jit and the
    fused :func:`verify_loop`; see :func:`verify_step` for the full
    commit semantics. ``drafts`` None: the model drafts itself, from
    the ``t0`` this tick samples (``draft=True`` of its apply
    protocol, ``models/exaone_moe``)."""
    slots = state.lengths.shape[0]
    vocab = model.config.vocab_size
    eos, pad = gen_cfg.eos_token_id, gen_cfg.pad_token_id
    arange_s = jnp.arange(slots)

    def processed(raw, appeared, dec_count):
        lg = repetition_penalty_processor(
            raw, appeared, gen_cfg.repetition_penalty)
        return min_length_processor(
            lg, dec_count[:, None], gen_cfg.min_dec_len, eos)

    def step_keys(dec_count, salt=None):
        def one(n, c):
            kk = jax.random.fold_in(jax.random.fold_in(rng, n), c)
            return kk if salt is None else jax.random.fold_in(kk, salt)
        return jax.vmap(one)(state.nonce, dec_count)

    # -- t0: decode_step's sampling pipeline, residual-masked ---------
    logits = processed(state.last_logits, state.appeared,
                       state.dec_count)
    if gen_cfg.decode_strategy == "greedy_search":
        t0 = jnp.argmax(logits, axis=-1)
    elif gen_cfg.decode_strategy == "sampling":
        lg = logits / jnp.maximum(gen_cfg.temperature, 1e-6)
        lg = top_k_top_p_filter(lg, gen_cfg.top_k, gen_cfg.top_p,
                                approx=gen_cfg.approx_top_k)
        # rejection-sampling residual: the draft the PREVIOUS tick
        # rejected is excluded from this draw (-1 matches nothing, so
        # spec-off slots sample bit-identically to decode_step)
        lg = jnp.where(
            jnp.arange(vocab)[None, :] == state.rejected[:, None],
            NEG_INF, lg)
        t0 = jax.vmap(
            lambda kk, row: jax.random.categorical(kk, row))(
            step_keys(state.dec_count), lg)
    else:
        raise ValueError(
            f"verify_step supports sampling/greedy_search, got "
            f"{gen_cfg.decode_strategy!r}")
    t0 = jnp.where(state.finished | ~state.active,
                   pad, t0).astype(jnp.int32)

    if drafts is None:
        drafts, mutated = model.apply(
            {"params": params, "cache": cache}, t0[:, None],
            use_cache=True, deterministic=True,
            cache_lengths=state.lengths, page_table=page_table,
            draft=True, mutable=["cache"])
        cache = mutated["cache"]
    k = drafts.shape[1]

    # -- one forward over the [slots, k+1] window ---------------------
    window = jnp.concatenate(
        [t0[:, None], jnp.asarray(drafts, jnp.int32)], axis=1)
    mpe = model.config.max_position_embeddings
    pos = jnp.clip(
        state.lengths[:, None] +
        jnp.arange(k + 1, dtype=jnp.int32)[None, :], 0, mpe - 1)
    logits2, mutated = model.apply(
        {"params": params, "cache": cache}, window,
        position_ids=pos, use_cache=True, deterministic=True,
        cache_lengths=state.lengths, page_table=page_table,
        adapter_ids=adapter_ids, mutable=["cache"])
    cache = _constrain_slot_cache(mutated["cache"])
    logits_w = logits2.astype(jnp.float32)     # [slots, k+1, V]

    # -- vectorized accept/reject, left to right ----------------------
    fin = state.finished | (state.active & (t0 == eos))
    appeared = state.appeared.at[arange_s, t0].set(True)
    commit = jnp.ones((slots,), bool)          # t0 always emits
    counts = jnp.ones((slots,), jnp.int32)
    rejected_new = jnp.full((slots,), -1, jnp.int32)
    mmax = gen_cfg.max_dec_len - state.dec_count
    for j in range(1, k + 1):
        dj = window[:, j]
        lg = processed(logits_w[:, j - 1], appeared,
                       state.dec_count + j)
        if gen_cfg.decode_strategy == "greedy_search":
            ok = dj == jnp.argmax(lg, axis=-1)
        else:
            lg = lg / jnp.maximum(gen_cfg.temperature, 1e-6)
            lg = top_k_top_p_filter(lg, gen_cfg.top_k, gen_cfg.top_p,
                                    approx=gen_cfg.approx_top_k)
            p = jax.nn.softmax(lg, axis=-1)
            pj = jnp.take_along_axis(p, dj[:, None], axis=1)[:, 0]
            u = jax.vmap(jax.random.uniform)(
                step_keys(state.dec_count + j, SPEC_ACCEPT_SALT))
            ok = u < pj
        can = commit & ~fin & state.active & (j < mmax)
        cj = can & ok
        if gen_cfg.decode_strategy == "sampling":
            # at most one (can & ~ok) per slot — commit chains stop at
            # the first rejection
            rejected_new = jnp.where(can & ~ok, dj, rejected_new)
        commit = cj
        counts = counts + cj
        appeared = appeared.at[arange_s, dj].max(cj)
        fin = fin | (cj & (dj == eos))

    new_state = SlotState(
        lengths=jnp.where(state.active, state.lengths + counts,
                          state.lengths),
        dec_count=jnp.where(state.active, state.dec_count + counts,
                            state.dec_count),
        nonce=state.nonce,
        appeared=appeared,
        finished=fin,
        active=state.active,
        # the logits AFTER the last committed token — the next tick's
        # t0 distribution (on a rejection this is the residual's
        # source distribution; combined with the `rejected` mask it
        # completes the rejection-sampling rule)
        last_logits=jnp.take_along_axis(
            logits_w, (counts - 1)[:, None, None], axis=1)[:, 0],
        rejected=rejected_new)
    return cache, new_state, window, counts


@partial(jax.jit, static_argnames=("model", "gen_cfg"),
         donate_argnames=("cache",))
def verify_step(model, params, cache, state: SlotState,
                drafts: jax.Array, rng: jax.Array,
                gen_cfg: GenerationConfig, page_table=None,
                adapter_ids=None):
    """One SPECULATIVE tick: score ``k`` drafted tokens per slot in a
    single forward and commit the accepted prefix (+1 sampled token).

    ``drafts [slots, k]`` are the host draft source's guesses for each
    request's NEXT k tokens AFTER the one this tick samples
    (``core/spec.py``; draft content only affects throughput, never
    output), or None where the model drafts itself between steps 1
    and 2 (a source on the device). The tick:

    1. samples ``t0`` from ``last_logits`` through exactly
       :func:`decode_step`'s processor/sampling pipeline (same
       ``(nonce, dec_count)`` key fold — the spec-off stream), with
       the previous tick's ``rejected`` draft masked out post-filter
       (the rejection-sampling residual);
    2. runs the model ONCE over the ``[slots, k+1]`` window
       ``[t0, d_1..d_k]`` at positions ``lengths .. lengths + k``
       (ragged multi-token cache writes + the within-window causal
       verify mask — ``flash_decode_ragged``/``flash_decode_paged``
       or the XLA fallback, docs/inference.md);
    3. walks the drafts left to right: draft ``d_j`` is committed iff
       every earlier window token committed, none of them was EOS,
       the per-request budget allows it (``dec_count + j <
       max_dec_len`` — the sequential server would have evicted), and
       it passes the accept test — greedy: ``d_j`` equals the argmax
       of the processed logits at its position (teacher-forced logits
       are the sequential logits, so greedy output is token-exact
       spec-off); sampling: a salted per-step uniform under the
       draft's model probability (deterministic draft proposal ⇒ the
       standard rejection rule accepts with prob ``p(d_j)`` and the
       residual excludes ``d_j``, recorded in ``rejected`` for the
       next tick).

    Rejected KV needs no device-side undo: lengths only advance by the
    committed count, so the next window overwrites the stale columns
    before any masked read reaches them (paged: the server frees/nulls
    pages past the accepted point).

    Returns ``(cache, state, harvest)`` — :func:`pack_harvest` at
    ``T = 1``: ``window[:, 0] [slots, k+1]`` holds the tick's token
    run (entry 0 = ``t0``), ``counts[:, 0] [slots]`` how many of them
    committed (1..k+1; the host appends
    ``window[slot, 0, :counts[slot, 0]]``).
    """
    params = launch_tree(params)
    cache, state, window, counts = _verify_tick_impl(
        model, params, cache, state, drafts, rng, gen_cfg, page_table,
        adapter_ids)
    return cache, state, pack_harvest(window[:, None], counts[:, None],
                                      state)


# -- device-resident decode: T ticks per host round-trip ---------------
#
# decode_step/verify_step return control to Python after every tick, so
# small-batch decode pays host->device dispatch, result fetch, and host
# scheduling per committed token group — the latency-bound (not
# FLOP-bound) regime. The fused loops below wrap the SAME tick bodies
# (_decode_tick_impl/_verify_tick_impl) in a lax.while_loop that runs
# up to `loop_ticks` ticks on-device, buffering each tick's committed
# tokens in a [slots, T]-shaped ring the host replays afterwards, and
# exits early the moment host scheduling actually has work to do:
# any active slot finished (eviction pending), any slot's decode budget
# expired, or the host flagged pending work (admission / drain /
# preemption risk) at launch. Exit reasons are reported so the server
# can count serving/loop_exit/{finished,admission,budget,drain}
# (docs/inference.md "Device-resident decode").

#: what a one-tick program reports: it has no loop to exit
LOOP_EXIT_NONE = 0
#: a slot emitted EOS — the host must evict before the next tick
LOOP_EXIT_FINISHED = 1
#: a slot's decode budget expired (dec_count hit max_dec_len), or the
#: loop ran its full `loop_ticks` tick budget with nothing else to do
LOOP_EXIT_BUDGET = 2
#: the host-signaled flag was set at launch (pending admission, drain,
#: or page-pool preemption risk) — the loop ran exactly one tick
LOOP_EXIT_HOST = 3


# -- the harvest: what the host reads after a launch, in ONE array -----
#
# A launch's host-read outputs (tokens, counts, ``finished`` and
# ``dec_count`` as they stand after it, the loop's ticks and exit code)
# are ready at the same moment, and each device-to-host read is a round
# trip of pure latency. The four tick programs therefore hand them over
# as one flat int32 array whose layout depends on the static
# ``(slots, T, k)`` alone; the server asks for its copy at the launch
# and reads it once (core/serving.py, docs/inference.md "The harvest").

class Harvest(NamedTuple):
    """One launch as the host reads it (:func:`unpack_harvest`)."""
    #: [slots, T, k+1] int32 — tick ``j``'s token run per slot (pad
    #: beyond ``ticks_run``)
    window: np.ndarray
    #: [slots, T] int32 — how many of them committed (without
    #: speculation 1 in each tick run; 0 beyond ``ticks_run``)
    counts: np.ndarray
    #: [slots] bool — ``SlotState.finished`` after the launch
    finished: np.ndarray
    #: [slots] int32 — ``SlotState.dec_count`` after the launch
    dec_count: np.ndarray
    #: ticks executed (1..T)
    ticks_run: int
    #: a ``LOOP_EXIT_*`` code; ``LOOP_EXIT_NONE`` from a one-tick program
    exit_code: int


def _harvest_fields(slots: int, ticks: int, k: int):
    """``((name, shape), ...)`` in the array's order — the layout's
    one definition, shared by :func:`pack_harvest` and
    :func:`unpack_harvest`."""
    return (("window", (slots, ticks, k + 1)),
            ("counts", (slots, ticks)),
            ("finished", (slots,)),
            ("dec_count", (slots,)),
            ("ticks_run", ()),
            ("exit_code", ()))


def pack_harvest(window: jax.Array, counts, state: SlotState,
                 ticks_run=1, exit_code=LOOP_EXIT_NONE) -> jax.Array:
    """The launch's harvest array (trace-level; one small
    concatenation). ``window`` is ``[slots, T, k+1]``; ``counts``
    ``[slots, T]``, or None without speculation (every tick run
    commits its one token)."""
    slots, ticks, width = window.shape
    if counts is None:
        counts = jnp.broadcast_to(
            jnp.arange(ticks, dtype=jnp.int32)[None, :] < ticks_run,
            (slots, ticks))
    parts = dict(window=window, counts=counts, finished=state.finished,
                 dec_count=state.dec_count,
                 ticks_run=jnp.asarray(ticks_run),
                 exit_code=jnp.asarray(exit_code))
    flat = []
    for name, shape in _harvest_fields(slots, ticks, width - 1):
        if parts[name].shape != shape:
            raise ValueError(f"harvest field {name!r} is "
                             f"{parts[name].shape}, its layout {shape}")
        flat.append(jnp.ravel(parts[name]).astype(jnp.int32))
    return jnp.concatenate(flat)


def unpack_harvest(flat, slots: int, ticks: int, k: int) -> Harvest:
    """The host copy of a :func:`pack_harvest` array, by field."""
    flat = np.asarray(flat)
    out, at = {}, 0
    for name, shape in _harvest_fields(slots, ticks, k):
        n = math.prod(shape)
        out[name] = flat[at:at + n].reshape(shape)
        at += n
    if flat.shape != (at,):
        raise ValueError(f"a harvest of {flat.shape} is not the layout "
                         f"of {slots} slots x {ticks} ticks, k = {k}")
    return Harvest(out["window"], out["counts"],
                   out["finished"].astype(bool), out["dec_count"],
                   int(out["ticks_run"]), int(out["exit_code"]))


def _ring_write(buf: jax.Array, vals: jax.Array, tick: jax.Array,
                loop_ticks: int) -> jax.Array:
    """Write one tick's row block into the per-tick ring buffer at
    position ``tick % loop_ticks`` along axis 1 (``buf`` is
    ``[slots, T]`` or ``[slots, T, k+1]``; ``vals`` drops the T axis).
    The fused loops never wrap (they run at most ``loop_ticks`` ticks
    per launch), but the modulo keeps the helper total for any tick
    counter a caller carries across launches."""
    return jax.lax.dynamic_update_index_in_dim(
        buf, vals, jnp.mod(tick, loop_ticks), axis=1)


def _loop_exit_flags(state: SlotState, gen_cfg: GenerationConfig):
    """``(fin_any, bud_any)`` — does any ACTIVE slot need host
    attention: emitted EOS (eviction), or decode budget spent
    (``dec_count >= max_dec_len``, the server's length eviction)."""
    fin_any = jnp.any(state.active & state.finished)
    bud_any = jnp.any(state.active & ~state.finished &
                      (state.dec_count >= gen_cfg.max_dec_len))
    return fin_any, bud_any


def _loop_exit_reason(state: SlotState, gen_cfg: GenerationConfig,
                      host_flag: jax.Array) -> jax.Array:
    """Why the fused loop stopped, by priority: a finished slot beats
    a spent budget beats the host flag; a full-T run with none of the
    above reads as the tick budget expiring (LOOP_EXIT_BUDGET)."""
    fin_any, bud_any = _loop_exit_flags(state, gen_cfg)
    return jnp.where(
        fin_any, LOOP_EXIT_FINISHED,
        jnp.where(bud_any, LOOP_EXIT_BUDGET,
                  jnp.where(host_flag != 0, LOOP_EXIT_HOST,
                            LOOP_EXIT_BUDGET))).astype(jnp.int32)


@partial(jax.jit, static_argnames=("model", "gen_cfg", "loop_ticks"),
         donate_argnames=("cache",))
def decode_loop(model, params, cache, state: SlotState,
                rng: jax.Array, gen_cfg: GenerationConfig,
                host_flag: jax.Array, page_table=None,
                adapter_ids=None, *, loop_ticks: int = 1):
    """Up to ``loop_ticks`` plain decode ticks in ONE device program.

    Each iteration runs exactly :func:`decode_step`'s tick body, so
    the committed token stream is identical to ``loop_ticks``
    sequential ``decode_step`` calls (the T=1/T>1 parity pin in
    tests/test_serving.py). The ``lax.while_loop`` always executes at
    least one tick, then keeps going while ticks remain AND no exit
    condition holds: an active slot finished, a slot's budget expired,
    or ``host_flag`` (a traced int32 scalar — nonzero means the host
    has pending admission/drain/preemption work and wants control back
    after one tick; traced so flag flips never recompile).

    Returns ``(cache, state, harvest)`` — :func:`pack_harvest` at
    ``T = loop_ticks, k = 0``: ``window[:, j, 0]`` holds tick ``j``'s
    emitted token per slot (pad beyond ``ticks_run``), ``ticks_run``
    how many ticks executed (1..loop_ticks), and ``exit_code`` one of
    the ``LOOP_EXIT_*`` codes.
    """
    params = launch_tree(params)
    if loop_ticks < 1:
        raise ValueError(f"loop_ticks must be >= 1, got {loop_ticks}")
    slots = state.lengths.shape[0]
    tokens_buf = jnp.full((slots, loop_ticks), gen_cfg.pad_token_id,
                          jnp.int32)
    host_flag = jnp.asarray(host_flag, jnp.int32)

    def cond(carry):
        _, st, _, tick = carry
        fin_any, bud_any = _loop_exit_flags(st, gen_cfg)
        return (tick == 0) | ((tick < loop_ticks) & ~fin_any &
                              ~bud_any & (host_flag == 0))

    def body(carry):
        cache, st, buf, tick = carry
        cache, st, tok = _decode_tick_impl(
            model, params, cache, st, rng, gen_cfg, page_table,
            adapter_ids)
        buf = _ring_write(buf, tok, tick, loop_ticks)
        return cache, st, buf, tick + 1

    cache, state, tokens_buf, ticks = jax.lax.while_loop(
        cond, body, (cache, state, tokens_buf, jnp.int32(0)))
    return cache, state, pack_harvest(
        tokens_buf[:, :, None], None, state, ticks,
        _loop_exit_reason(state, gen_cfg, host_flag))


@partial(jax.jit, static_argnames=("model", "gen_cfg", "loop_ticks"),
         donate_argnames=("cache",))
def verify_loop(model, params, cache, state: SlotState,
                drafts: jax.Array, rng: jax.Array,
                gen_cfg: GenerationConfig, host_flag: jax.Array,
                page_table=None, adapter_ids=None, *,
                loop_ticks: int = 1):
    """Up to ``loop_ticks`` speculative verify ticks in ONE device
    program — the spec twin of :func:`decode_loop`.

    ``drafts [slots, loop_ticks, k]`` carries k·T host-proposed draft
    tokens per slot per round-trip; tick ``j`` verifies slice
    ``drafts[:, j]`` through exactly :func:`verify_step`'s tick body.
    Drafts for every tick are proposed from the PRE-loop history (the
    host cannot see mid-loop commits), which never affects correctness
    — acceptance re-scores every draft against the model — only the
    accept rate; greedy output stays token-exact vs spec-off at any T.
    Exit conditions and the ``host_flag`` contract match
    :func:`decode_loop`.

    Returns ``(cache, state, harvest)`` — :func:`pack_harvest` at
    ``T = loop_ticks``: tick ``j``'s token run is
    ``window[:, j] [slots, k+1]`` of which ``counts[:, j]`` committed
    per slot (0 beyond ``ticks_run``).
    """
    params = launch_tree(params)
    if loop_ticks < 1:
        raise ValueError(f"loop_ticks must be >= 1, got {loop_ticks}")
    slots, t_axis, k = drafts.shape
    if t_axis != loop_ticks:
        raise ValueError(
            f"drafts tick axis ({t_axis}) != loop_ticks "
            f"({loop_ticks})")
    window_buf = jnp.full((slots, loop_ticks, k + 1),
                          gen_cfg.pad_token_id, jnp.int32)
    counts_buf = jnp.zeros((slots, loop_ticks), jnp.int32)
    host_flag = jnp.asarray(host_flag, jnp.int32)
    drafts = jnp.asarray(drafts, jnp.int32)

    def cond(carry):
        _, st, _, _, tick = carry
        fin_any, bud_any = _loop_exit_flags(st, gen_cfg)
        return (tick == 0) | ((tick < loop_ticks) & ~fin_any &
                              ~bud_any & (host_flag == 0))

    def body(carry):
        cache, st, wbuf, cbuf, tick = carry
        d = jax.lax.dynamic_index_in_dim(
            drafts, jnp.mod(tick, loop_ticks), axis=1, keepdims=False)
        cache, st, window, counts = _verify_tick_impl(
            model, params, cache, st, d, rng, gen_cfg, page_table,
            adapter_ids)
        wbuf = _ring_write(wbuf, window, tick, loop_ticks)
        cbuf = _ring_write(cbuf, counts, tick, loop_ticks)
        return cache, st, wbuf, cbuf, tick + 1

    cache, state, window_buf, counts_buf, ticks = jax.lax.while_loop(
        cond, body,
        (cache, state, window_buf, counts_buf, jnp.int32(0)))
    return cache, state, pack_harvest(
        window_buf, counts_buf, state, ticks,
        _loop_exit_reason(state, gen_cfg, host_flag))


# -- paged KV primitives (core/paging.py owns the host bookkeeping) ----
#
# With cfg.kv_page_size/kv_pool_pages set, the serving cache stops
# being [slots, h, d, capacity] rows and becomes ONE global page pool
# [kv_pool_pages, h, d, kv_page_size] per layer that every slot reaches
# through a [slots, max_kv_pages] page table (model.py paged branch).
# The jitted pieces below are deliberately dumb — shape-stable scatter/
# copy/activate kernels — while allocation, refcounts, COW decisions
# and prefix sharing stay host-side in core/serving.py + core/paging.py.


def init_page_pool(model, params, num_slots: int):
    """Zeroed global KV page-pool tree for a paged server, shaped by
    ``jax.eval_shape`` over a paged decode apply (no compile, no
    FLOPs). ``model.config`` must carry ``kv_page_size`` /
    ``kv_pool_pages`` (the server builds that twin config)."""
    cfg = model.config
    shapes = jax.eval_shape(
        lambda p: model.apply(
            {"params": launch_tree(p)}, jnp.zeros((num_slots, 1), jnp.int32),
            use_cache=True, deterministic=True,
            cache_lengths=jnp.zeros((num_slots,), jnp.int32),
            page_table=jnp.zeros((num_slots, cfg.max_kv_pages),
                                 jnp.int32),
            mutable=["cache"])[1]["cache"],
        params)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


@partial(jax.jit, static_argnames=("model",),
         donate_argnames=("cache",))
def prefill_chunk_paged(model, params, cache, input_chunk: jax.Array,
                        chunk_start: jax.Array, page_table: jax.Array,
                        adapter_ids=None, chunk_valid=None,
                        draft_inputs=None):
    """One page-aligned chunk of a chunked prefill.

    ``input_chunk`` is ``[n, chunk]`` token ids (the tail past the
    prompt right-padded with any token — its KV lands beyond the
    prompt length, where the per-slot ragged masking never reads and
    the first decode writes overwrite); ``chunk_start`` ``[n]`` is each
    row's absolute position of the chunk's first token (a multiple of
    ``kv_page_size``); ``page_table`` ``[n, max_kv_pages]`` carries
    just the prefilling rows; ``chunk_valid`` ``[n]`` counts each
    row's real tokens (the rest is that padding). A model whose cache
    is keys and values ignores it; one with a recurrent state
    (``models/solar_open2``, ``models/granite_hybrid``) reads
    everything it is fed, and leaves its state where the last real
    token put it. ``draft_inputs`` (a draft source on the device only:
    ``(next tokens [n, chunk], slots [n])``) has the model prefill its
    multi-token-prediction block's cache beside its own
    (``models/exaone_moe``). The chunk's KV scatters straight into its physical
    pages (model.py ``chunk_start`` branch) while the
    queries attend every earlier position through the page-table
    gather. Returns ``(cache, logits)`` with fp32 ``[n, chunk, V]``
    logits — the server picks row ``prompt_len - 1 - chunk_start`` of
    the final chunk as the first sampling distribution. One compiled
    shape per ``(n, chunk)``.
    """
    params = launch_tree(params)
    n, c = input_chunk.shape
    mpe = model.config.max_position_embeddings
    pos = jnp.clip(
        jnp.asarray(chunk_start, jnp.int32)[:, None] +
        jnp.arange(c, dtype=jnp.int32)[None, :], 0, mpe - 1)
    logits, mutated = model.apply(
        {"params": params, "cache": cache}, input_chunk,
        position_ids=pos, use_cache=True, deterministic=True,
        chunk_start=chunk_start, page_table=page_table,
        chunk_valid=chunk_valid, adapter_ids=adapter_ids,
        mutable=["cache"],
        **({} if draft_inputs is None else {"draft_inputs": draft_inputs}))
    return (_constrain_slot_cache(mutated["cache"]),
            logits.astype(jnp.float32))


@partial(jax.jit, donate_argnames=("cache",))
def copy_kv_pages(cache, src: jax.Array, dst: jax.Array):
    """Device-side copy of physical pages ``src -> dst`` (both
    ``[k]`` int32) in every KV pool leaf — the copy half of a
    copy-on-write split; the host (server) rewires the page table and
    refcounts around it."""
    def cp(path, leaf):
        name = getattr(path[-1], "key", "")
        if name in ("cached_key", "cached_value",
                    "cached_key_scale", "cached_value_scale"):
            ax = leaf.ndim - 4
            sel = (slice(None),) * ax
            return leaf.at[sel + (dst,)].set(leaf[sel + (src,)])
        return leaf
    return jax.tree_util.tree_map_with_path(cp, cache)


@jax.jit
def gather_kv_pages(cache, pids: jax.Array):
    """Pull physical pages ``pids`` (``[k]`` int32) out of every KV
    pool leaf — the export half of a cross-server KV handoff
    (``core/fleet.py``) and of the hierarchical-cache spill path
    (``core/serving.py`` issues this gather asynchronously at the
    yield point; the writer thread ``device_get``\\ s the result into
    the host tier). Non-pool leaves pass through untouched, so the
    result has the cache's own tree structure and
    :func:`scatter_kv_pages` consumes it directly; int8 pools carry
    their fp32 ``cached_*_scale`` pages alongside automatically (the
    same four leaf names :func:`copy_kv_pages` copies)."""
    def g(path, leaf):
        name = getattr(path[-1], "key", "")
        if name in ("cached_key", "cached_value",
                    "cached_key_scale", "cached_value_scale"):
            ax = leaf.ndim - 4
            sel = (slice(None),) * ax
            return leaf[sel + (pids,)]
        return leaf
    return jax.tree_util.tree_map_with_path(g, cache)


def split_kv_pages(page_data, num_pages: int):
    """Split an N-page :func:`gather_kv_pages` tree into ``num_pages``
    single-page trees (page axis ``ndim - 4`` of every KV leaf,
    non-pool leaves shared). Pure indexing — it works on device
    arrays and ``device_get``'d numpy alike, so the spill writer can
    carve one batched host transfer back into per-page byte-store
    entries (``core/serving.py``)."""
    def cut(i):
        def g(path, leaf):
            name = getattr(path[-1], "key", "")
            if name in ("cached_key", "cached_value",
                        "cached_key_scale", "cached_value_scale"):
                ax = leaf.ndim - 4
                sel = (slice(None),) * ax
                return leaf[sel + (slice(i, i + 1),)]
            return leaf
        return jax.tree_util.tree_map_with_path(g, page_data)
    return [cut(i) for i in range(num_pages)]


def stack_kv_pages(page_trees):
    """Concatenate single-page trees back into one N-page tree along
    the page axis — the inverse of :func:`split_kv_pages`, built so a
    batched rehydrate issues ONE :func:`scatter_kv_pages` dispatch
    for all N pages instead of N. Host-side concatenation (numpy):
    the inputs are staged host pages and the single scatter uploads
    the stacked result."""
    if len(page_trees) == 1:
        return page_trees[0]
    def cat(path, *leaves):
        name = getattr(path[-1], "key", "")
        if name in ("cached_key", "cached_value",
                    "cached_key_scale", "cached_value_scale"):
            ax = leaves[0].ndim - 4
            return np.concatenate(
                [np.asarray(leaf) for leaf in leaves], axis=ax)
        return leaves[0]
    return jax.tree_util.tree_map_with_path(cat, *page_trees)


@partial(jax.jit, donate_argnames=("cache",))
def scatter_kv_pages(cache, page_data, pids: jax.Array):
    """Write gathered page contents into pages ``pids`` of THIS pool —
    the import half of a cross-server KV handoff, and the rehydrate
    half of the hierarchical cache (host-tier numpy pages re-enter
    HBM under fresh page ids). ``page_data`` is a
    :func:`gather_kv_pages` result: device arrays for a same-devices
    transfer, or host-staged numpy (``jax.device_get`` of the gather)
    when the two pools' meshes don't share devices. The destination's
    page ids are free to differ from the source's — the host page
    table remap happens in the importer's allocator, this op only
    moves bytes."""
    def s(path, pleaf, dleaf):
        name = getattr(path[-1], "key", "")
        if name in ("cached_key", "cached_value",
                    "cached_key_scale", "cached_value_scale"):
            ax = pleaf.ndim - 4
            sel = (slice(None),) * ax
            return pleaf.at[sel + (pids,)].set(
                jnp.asarray(dleaf, pleaf.dtype))
        return pleaf
    return jax.tree_util.tree_map_with_path(s, cache, page_data)


@jax.jit
def activate_slot(state: SlotState, slot: jax.Array,
                  length: jax.Array, dec_count: jax.Array,
                  nonce: jax.Array, appeared_row: jax.Array,
                  logits: jax.Array, row: jax.Array,
                  rejected: jax.Array) -> tuple[SlotState, jax.Array]:
    """Flip one slot live from host-computed state — the paged
    admission paths (chunked-prefill completion, whole-prompt registry
    hit, preempted-request resume) activate through here instead of
    ``prefill_into_slots``'s scatter. ``dec_count`` is nonzero only
    for resumes, so a requeued request's min-length processing and
    sampling stream continue exactly where they stopped; ``rejected``
    (-1 outside resumes of a speculative sampling server) likewise
    restores a pending rejection-residual exclusion (verify_step).

    The slot's first sampling logits are row ``row`` of ``logits``
    taken as ``[rows, vocabulary]``: a last chunk's ``[1, chunk, V]``
    output as it left ``prefill_chunk_paged`` with the prompt's last
    position in the chunk, or a lone ``[V]`` row with 0. ``row`` is
    traced, so no prompt length compiles anything and the row is
    picked where it is consumed; it comes back beside the state, a
    device array, for whoever keeps it (the server's prompt
    registry)."""
    slot = jnp.asarray(slot, jnp.int32)
    last = jax.lax.dynamic_index_in_dim(
        logits.reshape(-1, logits.shape[-1]),
        jnp.asarray(row, jnp.int32), keepdims=False).astype(jnp.float32)
    return SlotState(
        lengths=state.lengths.at[slot].set(
            jnp.asarray(length, jnp.int32)),
        dec_count=state.dec_count.at[slot].set(
            jnp.asarray(dec_count, jnp.int32)),
        nonce=state.nonce.at[slot].set(jnp.asarray(nonce, jnp.int32)),
        appeared=state.appeared.at[slot].set(appeared_row),
        finished=state.finished.at[slot].set(False),
        active=state.active.at[slot].set(True),
        last_logits=state.last_logits.at[slot].set(last),
        rejected=state.rejected.at[slot].set(
            jnp.asarray(rejected, jnp.int32))), last


def left_pad_batch(sequences, pad_id: int):
    """Left-pad a list of id lists to the max length
    (reference ``language_module.py:221-243`` left_padding)."""
    import numpy as np
    max_len = max(len(s) for s in sequences)
    ids = np.full((len(sequences), max_len), pad_id, np.int32)
    mask = np.zeros((len(sequences), max_len), np.int32)
    for i, s in enumerate(sequences):
        if len(s) == 0:
            raise ValueError("empty prompt")
        ids[i, max_len - len(s):] = s
        mask[i, max_len - len(s):] = 1
    return ids, mask
