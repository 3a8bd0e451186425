"""Device-mesh topology: the TPU-native HybridCommunicateGroup.

The reference builds a 4D NCCL HybridCommunicateGroup from
``strategy.hybrid_configs{dp,mp,pp,sharding}`` (reference
``ppfleetx/utils/env.py:49-69``) and queries per-axis ranks throughout.
On TPU the HCG *is* a ``jax.sharding.Mesh`` with named axes — XLA/GSPMD
emits the collectives that Fleet issued by hand, and they ride the ICI
torus because the mesh is laid out with ``mesh_utils`` so neighboring
mesh coordinates are ICI neighbors.

Axis convention (outermost to innermost):
  ``pp``   pipeline stages          (slowest-varying; DCN-friendly)
  ``dp``   pure data parallel
  ``fsdp`` sharding/ZeRO axis       (reference ``sharding_degree``)
  ``mp``   tensor parallel          (innermost; highest-bandwidth ICI)

The dataflow axis of the reference — ``dp_degree * sharding_degree``
(``env.py:76-96``), used for batch sharding, seeds, and checkpoint
dedup — is ``("dp", "fsdp")`` here.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PP_AXIS = "pp"
DP_AXIS = "dp"
CP_AXIS = "cp"
FSDP_AXIS = "fsdp"
MP_AXIS = "mp"
MESH_AXES = (PP_AXIS, DP_AXIS, CP_AXIS, FSDP_AXIS, MP_AXIS)
#: the reference's dp x sharding composite dataflow axis (env.py:76-96)
DATA_AXES = (DP_AXIS, FSDP_AXIS)


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Parsed ``Distributed`` section; mirrors reference degree names."""
    dp_degree: int = 1
    mp_degree: int = 1
    pp_degree: int = 1
    cp_degree: int = 1          # context parallel (ring attention) —
    #                             beyond-reference (SURVEY §5.7)
    ep_degree: int = 1          # expert parallel (MoE) — beyond-
    #                             reference. Rides the dataflow axes
    #                             (dp x fsdp): a dedicated mesh axis
    #                             would replicate non-MoE compute
    #                             ep-fold, so ep does NOT multiply
    #                             world_size; it must equal dp, fsdp,
    #                             or dp*fsdp (parallel/sharding.py)
    sharding_degree: int = 1
    sharding_stage: int = 1
    sharding_offload: bool = False
    sequence_parallel: bool = False

    def __post_init__(self):
        if self.cp_degree > 1 and self.sequence_parallel:
            raise ValueError(
                "cp_degree (ring attention) and sequence_parallel "
                "(Megatron-SP seq-over-mp) both shard the sequence "
                "axis; enable at most one")

    @classmethod
    def from_config(cls, config) -> "TopologyConfig":
        """Build the topology from a parsed YAML config's
        ``Distributed``/``Model`` sections (degree semantics of
        reference ``utils/config.py:30-65``)."""
        dist = config.get("Distributed", {}) if hasattr(config, "get") else {}
        sharding = dist.get("sharding", {}) or {}
        model = config.get("Model", {}) if hasattr(config, "get") else {}
        return cls(
            dp_degree=dist.get("dp_degree") or 1,
            mp_degree=dist.get("mp_degree") or 1,
            pp_degree=dist.get("pp_degree") or 1,
            cp_degree=dist.get("cp_degree") or 1,
            ep_degree=dist.get("ep_degree") or 1,
            sharding_degree=sharding.get("sharding_degree") or 1,
            sharding_stage=sharding.get("sharding_stage") or 1,
            sharding_offload=bool(sharding.get("sharding_offload", False)),
            sequence_parallel=bool(model.get("sequence_parallel", False)),
        )

    @property
    def world_size(self) -> int:
        return (self.dp_degree * self.mp_degree * self.pp_degree
                * self.cp_degree * self.sharding_degree)

    @property
    def data_world_size(self) -> int:
        return self.dp_degree * self.sharding_degree


#: Axes allowed to span the DCN (inter-slice) network, in preference
#: order. dp first — its gradient allreduce happens once per step and
#: pipelines over DCN well; pp next — stage boundaries transfer one
#: activation per microbatch; fsdp last — its per-layer param
#: all-gathers tolerate DCN only with generous compute to hide them.
#: cp/mp issue per-layer (or per-block) latency-bound collectives and
#: must stay inside a slice's ICI torus.
DCN_AXIS_PREFERENCE = (DP_AXIS, PP_AXIS, FSDP_AXIS)


def dcn_factorization(num_slices: int, shape: Sequence[int]) -> tuple:
    """Split ``num_slices`` multiplicatively across the DCN-tolerant
    axes of ``shape`` (ordered as ``MESH_AXES``), greedily in
    ``DCN_AXIS_PREFERENCE`` order. Returns the per-axis DCN degrees
    (the ``Mesh`` axis degree = dcn_degree * per-slice ICI degree).

    Raises if the topology cannot be laid out with mp/cp intact inside
    a slice — e.g. 4 slices but dp*pp*fsdp only has a factor of 2
    across DCN-tolerant axes.
    """
    import math
    dcn = {a: 1 for a in MESH_AXES}
    remaining = num_slices
    for axis in DCN_AXIS_PREFERENCE:
        f = math.gcd(remaining, shape[MESH_AXES.index(axis)])
        dcn[axis] = f
        remaining //= f
    if remaining != 1:
        raise ValueError(
            f"cannot lay topology {dict(zip(MESH_AXES, shape))} across "
            f"{num_slices} slices: dp/pp/fsdp degrees leave a factor "
            f"of {remaining} that would force mp/cp collectives onto "
            f"DCN; make dp (or pp) divisible by the slice count")
    return tuple(dcn[a] for a in MESH_AXES)


def _compose_slices(slice_arrays, dcn_shape) -> np.ndarray:
    """Tile per-slice device arrays (all of the same ICI shape) into
    the full mesh array so each slice occupies one contiguous block:
    full-mesh index along axis k = dcn_coord * ici_degree + ici_coord.
    Walking any axis therefore stays on ICI until a slice-block
    boundary, and only dcn_degree-1 of the hops cross DCN.

    Deliberately hand-rolled rather than delegating to
    ``mesh_utils.create_hybrid_device_mesh``: the library helper
    detects granules from real device attrs (slice_index /
    process_index), which virtual CPU test devices don't carry, so it
    cannot be exercised by the 8-device CPU suite. One small composed
    path that every test runs beats a library path the tests can't
    reach (the per-slice ICI layout still comes from
    ``create_device_mesh`` on real TPU)."""
    ici_shape = slice_arrays[0].shape
    full = np.empty(
        tuple(d * i for d, i in zip(dcn_shape, ici_shape)), object)
    for k, arr in enumerate(slice_arrays):
        coords = np.unravel_index(k, dcn_shape)
        full[tuple(slice(c * i, (c + 1) * i)
                   for c, i in zip(coords, ici_shape))] = arr
    return full


def build_mesh(topo: TopologyConfig,
               devices: Optional[Sequence[jax.Device]] = None,
               slice_id_fn=None) -> Mesh:
    """Build the 5-axis mesh ``(pp, dp, cp, fsdp, mp)``.

    On a single real TPU slice ``mesh_utils.create_device_mesh`` maps
    mesh coordinates onto the physical ICI torus. On a multi-slice
    (Multislice/multi-pod) platform — detected via the devices'
    ``slice_index`` — each slice gets its own ICI-optimised sub-array
    and slices are tiled along the DCN-tolerant axes only (dp, then
    pp, then fsdp; never mp/cp), so per-layer collectives ride ICI and
    only the once-per-step dataflow traffic crosses DCN
    (``dcn_factorization``). Elsewhere (CPU test meshes) a plain
    reshape is used. ``slice_id_fn`` overrides slice detection (tests
    inject a fake slice id over CPU devices).
    """
    shape = (topo.pp_degree, topo.dp_degree, topo.cp_degree,
             topo.sharding_degree, topo.mp_degree)
    n = int(np.prod(shape))
    if devices is None:
        if n != jax.device_count():
            raise ValueError(
                f"topology {dict(zip(MESH_AXES, shape))} covers {n} devices "
                f"but {jax.device_count()} are available; set Distributed "
                f"degrees to use every device (reference asserts the same, "
                f"utils/config.py:54)")
        devices = jax.devices()
        on_tpu = devices[0].platform == "tpu"
    else:
        if len(devices) != n:
            raise ValueError(
                f"topology {shape} needs exactly {n} devices, "
                f"got {len(devices)}")
        if slice_id_fn is None:
            # caller-supplied order is authoritative (tests, sub-meshes)
            return Mesh(np.asarray(list(devices)).reshape(shape),
                        MESH_AXES)
        on_tpu = False
    if slice_id_fn is None:
        slice_id_fn = (lambda d: getattr(d, "slice_index", None)) \
            if on_tpu else (lambda d: None)
    by_slice = {}
    for d in devices:
        by_slice.setdefault(slice_id_fn(d), []).append(d)
    if len(by_slice) > 1:
        dcn_shape = dcn_factorization(len(by_slice), shape)
        ici_shape = tuple(s // d for s, d in zip(shape, dcn_shape))
        per = n // len(by_slice)
        slice_arrays = []
        for sid in sorted(by_slice):
            devs = by_slice[sid]
            if len(devs) != per:
                raise ValueError(
                    f"uneven slices: slice {sid} has {len(devs)} "
                    f"devices, expected {per}")
            if on_tpu:
                from jax.experimental import mesh_utils
                slice_arrays.append(mesh_utils.create_device_mesh(
                    ici_shape, devices=devs))
            else:
                slice_arrays.append(
                    np.asarray(devs).reshape(ici_shape))
        dev_array = _compose_slices(slice_arrays, dcn_shape)
    elif on_tpu:
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(shape)
    else:
        dev_array = np.asarray(list(devices)).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


_ACTIVE_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def batch_spec(extra_dims: int = 0) -> P:
    """PartitionSpec for a batch-leading array, sharded over dp x fsdp."""
    return P(DATA_AXES, *([None] * extra_dims))


def batch_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(extra_dims))


def data_world_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    if mesh is None:
        return 1
    return mesh.shape[DP_AXIS] * mesh.shape[FSDP_AXIS]


def _process_data_groups(mesh: Mesh):
    """Group processes by the set of dataflow coordinates they own.

    Processes whose devices cover the same dataflow (dp x fsdp) slice
    (e.g. two hosts split along mp or pp) are *replicas* of the same
    data stream and must load identical batches; distinct coordinate
    sets are distinct loader ranks. Returns (groups, my_group_index)
    with groups ordered by their first dataflow coordinate.
    """
    dp_axis = mesh.axis_names.index(DP_AXIS)
    fsdp_axis = mesh.axis_names.index(FSDP_AXIS)
    coords = {}
    for idx, dev in np.ndenumerate(mesh.devices):
        pos = int(idx[dp_axis] * mesh.shape[FSDP_AXIS]
                  + idx[fsdp_axis])
        coords.setdefault(dev.process_index, set()).add(pos)
    groups = {}
    for proc, pos_set in coords.items():
        groups.setdefault(frozenset(pos_set), []).append(proc)
    ordered = sorted(groups, key=min)
    me = jax.process_index()
    mine = next(i for i, g in enumerate(ordered) if me in groups[g])
    return ordered, mine


def process_data_rank(mesh: Optional[Mesh] = None) -> int:
    """This process's data-loader rank: the index of its dataflow
    coordinate group. Processes that are mp/pp replicas of the same
    batch slice share a rank (and must load identical data)."""
    mesh = mesh or get_mesh()
    if mesh is None or jax.process_count() == 1:
        return 0
    return _process_data_groups(mesh)[1]


def process_data_loader_count(mesh: Optional[Mesh] = None) -> int:
    """Number of distinct data-loader ranks (== distinct dataflow
    coordinate groups across processes)."""
    mesh = mesh or get_mesh()
    if mesh is None or jax.process_count() == 1:
        return 1
    return len(_process_data_groups(mesh)[0])


def cpu_mesh_env(n: int = 8) -> None:
    """Force an ``n``-device CPU platform for mesh tests/dry-runs.

    Sets the env vars (for child processes) *and* jax.config (for this
    one, whether or not jax is already imported). Must run before the
    first backend initialization.
    """
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)
