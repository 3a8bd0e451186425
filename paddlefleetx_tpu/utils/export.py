"""AOT model export: the TPU-native replacement for the reference's
dygraph-to-static pipeline.

The reference exports with ``paddle.jit.to_static`` + program pruning
(reference ``utils/export.py:27-59``) into per-rank
``rank_{i}/model.pdmodel|pdiparams`` dirs consumed by the
``paddle.inference`` runtime (``core/engine/inference_engine.py``).
Here the jitted function itself is the deployable artifact: the traced
computation is serialized with ``jax.export`` (StableHLO, weights NOT
baked in), parameters are saved as an Orbax checkpoint next to it, and
a ``spec.json`` records the input signature. The artifact is
topology-portable — one directory regardless of the training mesh,
unlike the reference's per-rank dirs.

Layout::

    <dir>/model.jaxexport   serialized StableHLO computation
    <dir>/params/           Orbax checkpoint of the parameter pytree
    <dir>/spec.json         input shapes/dtypes + metadata
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.export
import numpy as np
import orbax.checkpoint as ocp

from .log import logger

_MODEL_FILE = "model.jaxexport"
_SPEC_FILE = "spec.json"
_PARAMS_DIR = "params"


def _symbolic_abstract_inputs(input_spec):
    """``None`` dims become symbolic dimensions (shape polymorphism):
    the artifact then serves ANY size on those axes — the reference's
    ``InputSpec(shape=[None, ...])`` dynamic-batch semantics.

    ``None`` dims at the SAME axis index share one symbol across
    inputs: tokens+mask both shaped ``(None, s)`` trace as
    ``(b, s), (b, s)`` — distinct symbols would make their equality
    comparisons inconclusive and kill the symbolic export for every
    multi-input model (batch/sequence axes are shared in practice;
    the constraint is also enforced at call time, where it catches
    mismatched inputs early). Returns None when no dim is dynamic."""
    if not any(d is None for shape, _ in input_spec for d in shape):
        return None
    scope = jax.export.SymbolicScope()
    out = []
    for shape, dtype in input_spec:
        dims = [f"d{i}" if d is None else str(int(d))
                for i, d in enumerate(shape)]
        out.append(jax.ShapeDtypeStruct(
            jax.export.symbolic_shape(",".join(dims), scope=scope),
            jax.numpy.dtype(dtype)))
    return out


def export_inference_model(fn: Callable, params,
                           input_spec: Sequence[Tuple[Sequence, str]],
                           output_dir: str,
                           metadata: Dict[str, Any] = None) -> str:
    """Serialize ``fn(params, *inputs)`` + ``params`` to ``output_dir``.

    ``input_spec`` is the module contract's ``[(shape, dtype), ...]``.
    ``None`` dims export as SYMBOLIC dimensions where the traced
    computation allows it (plain forwards do; value-dependent loops
    like the generation scan may not) — the served artifact then
    accepts any size on those axes. When symbolic tracing fails — or
    for partitioned artifacts, where jax.export's polymorphism does
    not compose with baked shardings — ``None`` dims are concretized
    to 1 and the runtime pads to spec (``pad_to_spec``).
    """
    os.makedirs(output_dir, exist_ok=True)
    abstract_params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    exported = None
    dynamic_dims: List[List[int]] = []
    # partitioned params (any leaf actually SPLIT across devices —
    # dp-replicated leaves live on many devices but are not split;
    # the same replication-aware predicate engine.export uses to pick
    # its export mesh): jax export polymorphism does not compose with
    # baked shardings — derived from the params themselves, not a
    # caller convention
    def _split(x):
        s = getattr(x, "sharding", None)
        return (s is not None
                and getattr(s, "num_devices", 1) > 1
                and not s.is_fully_replicated)

    partitioned = any(_split(x) for x in jax.tree.leaves(params))
    has_dynamic = any(d is None for shape, _ in input_spec
                      for d in shape)
    symbolic = _symbolic_abstract_inputs(input_spec) \
        if has_dynamic and not partitioned else None
    if partitioned and has_dynamic:
        logger.warning(
            "partitioned export: dynamic (None) input dims are baked "
            "to 1 (jax export polymorphism does not compose with "
            "baked shardings); the artifact pads to spec instead of "
            "accepting any size")
    if symbolic is not None:
        try:
            exported = jax.export.export(jax.jit(fn))(
                abstract_params, *symbolic)
            dynamic_dims = [
                [i for i, d in enumerate(shape) if d is None]
                for shape, _ in input_spec]
        except Exception as e:
            # a capability downgrade of the shipped artifact (it will
            # only accept the concretized sizes) — say so loudly
            logger.warning(
                "symbolic-shape export unsupported for this function; "
                "baking dynamic dims to 1 (the artifact pads to spec "
                "instead of accepting any size). %s: %s",
                type(e).__name__, e)
    abstract_inputs = [
        jax.ShapeDtypeStruct(
            tuple(1 if d is None else int(d) for d in shape),
            jax.numpy.dtype(dtype))
        for shape, dtype in input_spec]
    if exported is None:
        exported = jax.export.export(jax.jit(fn))(
            abstract_params, *abstract_inputs)
        dynamic_dims = [[] for _ in input_spec]
    with open(os.path.join(output_dir, _MODEL_FILE), "wb") as f:
        f.write(exported.serialize())

    params_path = os.path.abspath(os.path.join(output_dir, _PARAMS_DIR))
    with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as ckptr:
        ckptr.save(params_path, jax.device_get(params), force=True)

    spec = {
        # dynamic axes record null: the runtime accepts any size there
        "inputs": [
            [[None if i in dyn else int(d)
              for i, d in enumerate(s.shape)], s.dtype.name]
            for s, dyn in zip(abstract_inputs, dynamic_dims)],
        "metadata": metadata or {},
    }
    with open(os.path.join(output_dir, _SPEC_FILE), "w") as f:
        json.dump(spec, f, indent=2)
    logger.info("exported inference model to %s", output_dir)
    return output_dir


def serialize_param_specs(shardings) -> Dict[str, list]:
    """Flatten a params-tree of ``NamedSharding``s (or
    ``PartitionSpec``s) to ``{"a/b/c": [None, "mp", ["dp", "fsdp"]]}``
    — JSON-able, mesh-free; :func:`deserialize_param_specs` rebuilds
    ``NamedSharding``s against the *loader's* mesh."""
    import jax.sharding as js

    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(shardings)[0]:
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        spec = leaf.spec if isinstance(leaf, js.NamedSharding) else leaf
        flat[key] = [list(e) if isinstance(e, tuple) else e
                     for e in tuple(spec)]
    return flat


def deserialize_param_specs(flat: Dict[str, list], params, mesh):
    """``{"a/b/c": serialized spec}`` -> params-shaped tree of
    ``NamedSharding`` on ``mesh`` (replicated for paths the artifact
    does not list)."""
    import jax.sharding as js
    P = js.PartitionSpec

    def build(path, _leaf):
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        entries = flat.get(key)
        if entries is None:
            return js.NamedSharding(mesh, P())
        return js.NamedSharding(mesh, P(*[
            tuple(e) if isinstance(e, list) else e for e in entries]))

    return jax.tree_util.tree_map_with_path(build, params)


def load_spec(model_dir: str) -> Dict[str, Any]:
    """The artifact's ``spec.json`` (input shapes + metadata) alone —
    cheap; callers use it to resolve a mesh BEFORE loading weights."""
    with open(os.path.join(model_dir, _SPEC_FILE)) as f:
        return json.load(f)


def load_inference_model(model_dir: str, mesh=None):
    """Returns ``(call, params, spec)``; ``call(params, *inputs)``
    executes the deserialized computation on the current backend.

    With ``mesh`` and a spec that records ``param_specs``, each
    parameter is restored DIRECTLY into its ``NamedSharding`` (Orbax
    sharded read) — a model that only fits partitioned must never
    materialize whole in host RAM just to be re-sharded."""
    with open(os.path.join(model_dir, _MODEL_FILE), "rb") as f:
        exported = jax.export.deserialize(f.read())
    spec = load_spec(model_dir)
    params_path = os.path.abspath(os.path.join(model_dir, _PARAMS_DIR))
    flat_specs = spec["metadata"].get("param_specs")
    with ocp.Checkpointer(ocp.StandardCheckpointHandler()) as ckptr:
        if mesh is not None and flat_specs:
            meta = ckptr.metadata(params_path)
            # newer orbax wraps the metadata tree; 0.7.x returns the
            # pytree of ArrayMetadata (with .shape/.dtype) directly
            meta_tree = getattr(
                getattr(meta, "item_metadata", None), "tree", meta)
            shardings = deserialize_param_specs(flat_specs, meta_tree,
                                                mesh)
            abstract = jax.tree.map(
                lambda m, s: jax.ShapeDtypeStruct(m.shape, m.dtype,
                                                  sharding=s),
                meta_tree, shardings)
            params = ckptr.restore(
                params_path, args=ocp.args.StandardRestore(abstract))
        else:
            params = ckptr.restore(params_path)

    def call(p, *inputs):
        return exported.call(p, *inputs)

    return call, params, spec


def pad_to_spec(arrays: List[np.ndarray], spec: Dict[str, Any],
                pad_values: Sequence[float],
                pad_sides: Sequence[str] = None) -> List[np.ndarray]:
    """Pad each input up to the exported static shape (the exported
    program cannot accept smaller batches/sequences).

    ``pad_sides[i]`` is "right" (default) or "left"; left applies to
    the LAST axis only (the sequence axis — generation prompts must be
    left-padded so the final slot holds the last real token, matching
    ``generate()``'s contract). Batch and leading axes always pad
    right.
    """
    out = []
    sides = pad_sides or ["right"] * len(arrays)
    for arr, (shape, dtype), pad, side in zip(arrays, spec["inputs"],
                                              pad_values, sides):
        arr = np.asarray(arr)
        # None = symbolic (dynamic) axis: any size passes through
        target = [a if s is None else s
                  for a, s in zip(arr.shape, shape)] \
            if arr.ndim == len(shape) else shape
        if list(arr.shape) == target:
            out.append(arr.astype(dtype))
            continue
        if arr.ndim != len(shape) or any(
                a > s for a, s in zip(arr.shape, target)):
            raise ValueError(
                f"input shape {arr.shape} incompatible with exported "
                f"spec {shape}")
        widths = [(0, s - a) for a, s in zip(arr.shape, target)]
        if side == "left" and arr.ndim >= 1:
            widths[-1] = (widths[-1][1], 0)
        out.append(np.pad(arr, widths,
                          constant_values=pad).astype(dtype))
    return out
