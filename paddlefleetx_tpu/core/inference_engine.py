"""Inference engine over an exported AOT artifact.

Parity: reference ``core/engine/inference_engine.py:34-158`` — loads
per-rank static-graph models, writes a comm-topology CSV and drives
``paddle.inference`` with a distributed config. TPU-native: the
artifact is one ``jax.export`` directory (see ``utils/export.py``).

Distribution modes:

- **Model/tensor parallel**: an artifact exported under an ``mp > 1``
  mesh records its device count and parameter partition specs
  (``spec.json`` metadata); loading it requires an active mesh (see
  ``parallel.mesh.set_mesh``) with the same axis names and total size,
  onto which the parameters are re-partitioned and the computation
  jitted — one directory replaces the reference's per-rank
  ``rank_{i}`` model files, and the loader's mesh may be a different
  physical device assignment than the exporter's.
- **Data parallel** (reference ``inference_gpt_345M_dp8.yaml``): every
  rank constructs its own ``InferenceEngine`` over the same
  single-device artifact and serves its shard of the requests —
  embarrassingly parallel, no collectives (this is also what the
  reference's dp inference does: one predictor per rank).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import numpy as np

from ..observability import metrics
from ..observability.trace import annotate
from ..utils.export import (
    load_inference_model, load_spec, pad_to_spec,
)
from ..utils.log import logger


class InferenceEngine:
    """Loads a ``jax.export`` artifact and serves ``predict`` calls,
    re-partitioned onto the requested mesh."""

    def __init__(self, model_dir: str, mp_degree: int = 1, mesh=None):
        self.model_dir = model_dir
        from ..utils.env import setup_compilation_cache
        setup_compilation_cache()
        t_load = time.time()
        meta = load_spec(model_dir)["metadata"]

        n_export = int(meta.get("num_export_devices", 1))
        axes = {k: int(v) for k, v in
                (meta.get("mesh_axes") or {}).items()}
        if mesh is None and n_export > 1:
            from ..parallel.mesh import get_mesh
            mesh = get_mesh()
            if mesh is None:
                mesh = self._build_mesh_from_metadata(axes, n_export)
        if n_export > 1:
            if mesh is None or mesh.devices.size != n_export:
                have = "no mesh" if mesh is None else \
                    f"a {mesh.devices.size}-device mesh"
                raise ValueError(
                    f"artifact {model_dir} was exported for {n_export} "
                    f"devices (mesh axes {axes}); the caller must "
                    f"activate a matching mesh (parallel.mesh."
                    f"set_mesh), but {have} is active")
            # size alone is not enough: a dp4 mesh has 4 devices too,
            # but loading an mp4 artifact on it would silently
            # replicate every parameter the export partitioned
            mismatched = {
                name: (size, mesh.shape.get(name))
                for name, size in axes.items()
                if mesh.shape.get(name) != size}
            if mismatched:
                raise ValueError(
                    f"artifact {model_dir} was exported on mesh axes "
                    f"{axes}; the active mesh {dict(mesh.shape)} "
                    f"differs on {sorted(mismatched)}")
        else:
            if mp_degree != 1:
                logger.info(
                    "mp_degree=%d requested but the artifact was "
                    "exported single-device; run tools/export.py under "
                    "the mp mesh to bake a partitioned artifact",
                    mp_degree)
            mesh = None

        # params restore sharded directly when a mesh is resolved — no
        # full-tree host materialization followed by a re-shard
        self.call, self.params, self.spec = \
            load_inference_model(model_dir, mesh=mesh)
        self.pad_values = meta.get("pad_values")
        self.pad_sides = meta.get("pad_sides")
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            replicated = NamedSharding(mesh, PartitionSpec())
            exported_call = self.call
            self.call = jax.jit(
                lambda p, *inputs: exported_call(p, *inputs),
                out_shardings=replicated)
            self._input_sharding = replicated
            logger.info(
                "inference artifact re-partitioned onto %d-device mesh "
                "%s", n_export, axes)
        else:
            self._input_sharding = None
        metrics.inc("inference/loads")
        metrics.get_registry().add_time("inference/load",
                                        time.time() - t_load)

    @staticmethod
    def _build_mesh_from_metadata(axes: Dict[str, int], n_export: int):
        """When no mesh is active, rebuild one from the artifact's own
        recorded axis names/sizes over the first ``n_export`` local
        devices — the serving entry points (``tasks/gpt/inference.py``)
        need no topology plumbing to load an mp artifact."""
        if not axes or n_export > len(jax.devices()):
            return None
        from jax.sharding import Mesh
        devs = np.asarray(jax.devices()[:n_export]).reshape(
            tuple(axes.values()))
        logger.info("no active mesh; rebuilding %s from artifact "
                    "metadata", axes)
        return Mesh(devs, tuple(axes))

    def predict(self, data: List[Any]) -> Dict[str, np.ndarray]:
        """Feed ``data`` (one array-like per exported input), run, and
        return outputs keyed by position (the reference returns the
        predictor's named output handles; positions are the stable
        equivalent here). Each call accumulates wall time under the
        ``inference/predict`` timer, the ``inference/predict_ms``
        latency histogram (p50/p99 on ``/metrics``), and bumps
        ``inference/predict_calls`` and ``inference/output_tokens``
        (total output elements) — docs/observability.md."""
        metrics.inc("inference/predict_calls")
        t_call = time.time()
        pads = self.pad_values or [0] * len(data)
        inputs = pad_to_spec([np.asarray(d) for d in data], self.spec,
                             pads, self.pad_sides)
        if self._input_sharding is not None:
            inputs = [jax.device_put(x, self._input_sharding)
                      for x in inputs]
        with metrics.get_registry().timer("inference/predict"):
            with annotate("predict"):
                outputs = self.call(self.params, *inputs)
            if not isinstance(outputs, (tuple, list)):
                outputs = (outputs,)
            # np.asarray blocks on the device result, so the transfer
            # lands inside the per-call latency timer
            result = {str(i): np.asarray(o)
                      for i, o in enumerate(outputs)}
        metrics.observe("inference/predict_ms",
                        (time.time() - t_call) * 1000.0)
        metrics.inc("inference/output_tokens",
                    sum(o.size for o in result.values()))
        return result

    @staticmethod
    def serve_generation(model, params, gen_cfg, num_slots: int = 4,
                         **kwargs):
        """Build a continuous-batching :class:`~paddlefleetx_tpu.core.
        serving.GenerationServer` over a live model (slot-managed KV
        cache + ragged flash decode) — the serving counterpart of the
        artifact-driven ``predict`` path. Extra ``kwargs`` pass through
        to the server (``prefill_buckets``, ``rng``, ``events_path``,
        the paged-KV knobs ``page_size`` / ``pool_pages`` /
        ``prefill_chunk_pages`` / ``prefix_sharing`` —
        docs/inference.md, "Paged KV cache" — the fused-decode knob
        ``device_loop_ticks`` (up to T ticks per host round-trip —
        docs/inference.md, "Device-resident decode") and the
        graceful-degradation knobs ``request_ttl_s`` /
        ``max_queue_depth`` / ``drain_on_sigterm`` —
        docs/robustness.md). With
        ``events_path`` the server traces every request
        (docs/observability.md, "Request tracing"); with
        ``PFX_METRICS_PORT`` set it serves live ``/metrics`` +
        ``/healthz``."""
        from .serving import GenerationServer
        return GenerationServer(model, params, gen_cfg,
                                num_slots=num_slots, **kwargs)
