"""Hybrid-parallel topology as a named TPU device mesh.

Reference: python/paddle/distributed/fleet/base/topology.py:54,140
(``CommunicateTopology`` / ``HybridCommunicateGroup`` — the 4-D
[mp, sharding, pp, dp] rank bookkeeping over NCCL groups).

TPU-first redesign: the topology IS a ``jax.sharding.Mesh``.  Where the
reference materialises one NCCL communicator per (axis, peer-set), here every
"communication group" is just a named mesh axis — XLA lowers collectives over
that axis onto the ICI torus (and DCN across hosts) when a pjit program runs.
Axis order is chosen so model-parallel is innermost (fastest-varying →
neighbouring chips on the ICI ring), then sharding, then dp, then pp
outermost — the standard layout that keeps TP/SP collectives on-chip-adjacent
links (cf. the scaling-book mesh recipe).
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis names, outermost → innermost.
HYBRID_AXES = ("pp", "dp", "sharding", "sep", "ep", "mp")

_CURRENT_HCG: Optional["HybridCommunicateGroup"] = None
_CURRENT_MESH: Optional[Mesh] = None


def create_hybrid_mesh(dp: int = 1, mp: int = 1, pp: int = 1,
                       sharding: int = 1, sep: int = 1, ep: int = 1,
                       devices: Optional[Sequence] = None) -> Mesh:
    """Build the hybrid mesh [pp, dp, sharding, sep, ep, mp] over the devices.

    ``sep`` is the sequence-parallel ("sep"/context-parallel) degree — absent
    from the reference (SURVEY.md §5.7) and designed fresh here.
    """
    devices = list(devices if devices is not None else jax.devices())
    degrees = {"pp": pp, "dp": dp, "sharding": sharding, "sep": sep,
                "ep": ep, "mp": mp}
    total = int(np.prod(list(degrees.values())))
    if total < len(devices):
        devices = devices[:total]   # smaller job than the slice: use a subset
    if total != len(devices):
        raise ValueError(
            f"mesh degrees product {degrees} = {total} != device count "
            f"{len(devices)}")
    shape = tuple(degrees[a] for a in HYBRID_AXES)
    try:
        # mesh_utils lays the logical mesh onto the physical ICI topology.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except Exception:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, HYBRID_AXES)


def axis_if_divides(mesh, axis: str, dim: int) -> Optional[str]:
    """``axis`` when the mesh has it with size > 1 AND it divides ``dim``
    — else None (replicate).  The one gating rule for every serving-side
    sharding decision (params, pools, kernels, feeds)."""
    size = dict(mesh.shape).get(axis, 1)
    return axis if (size > 1 and dim % size == 0) else None


def shard_map_norep(fn, mesh, in_specs, out_specs):
    """shard_map without replication checking."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class CommunicateTopology:
    """Axis-name ↔ coordinate bookkeeping over an n-D processor grid
    (reference: fleet/base/topology.py:54).  Kept as plain index math so unit
    tests can exercise group construction without devices."""

    def __init__(self, hybrid_group_names: Sequence[str],
                 dims: Sequence[int]):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = None
        self._world = [tuple(c) for c in np.ndindex(*self._dims)]
        self._coord2rank = {c: i for i, c in enumerate(self._world)}

    def get_hybrid_group_names(self) -> List[str]:
        return list(self._parallel_names)

    def get_dim(self, axis_name: str) -> int:
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self) -> int:
        return int(np.prod(self._dims))

    def get_rank(self, **axes) -> int:
        coord = tuple(axes[name] for name in self._parallel_names)
        return self._coord2rank[coord]

    def get_coord(self, rank: int):
        return self._world[rank]

    def get_axis_list(self, axis_name: str, index: int) -> List[int]:
        """All ranks whose coordinate on ``axis_name`` equals ``index``."""
        axis = self._parallel_names.index(axis_name)
        return [r for r, c in enumerate(self._world) if c[axis] == index]

    def get_comm_list(self, axis_name: str) -> List[List[int]]:
        """Peer groups along ``axis_name``: for each setting of the other
        axes, the ranks that vary only in ``axis_name`` (the reference's
        per-axis communicator sets)."""
        axis = self._parallel_names.index(axis_name)
        other_dims = [d for i, d in enumerate(self._dims) if i != axis]
        groups = []
        for other in np.ndindex(*other_dims):
            ranks = []
            for k in range(self._dims[axis]):
                coord = list(other)
                coord.insert(axis, k)
                ranks.append(self._coord2rank[tuple(coord)])
            groups.append(ranks)
        return groups


class HybridCommunicateGroup:
    """The fleet topology facade (reference: fleet/base/topology.py:140).

    Holds the mesh + per-axis degree/rank queries.  ``rank`` here is the
    *process* rank (multi-host) combined with the position of the process's
    first addressable device in the mesh — under single-controller SPMD all
    mesh coordinates exist in-process and collectives are compiled, so the
    rank accessors exist for API parity and for launch/logging logic.
    """

    def __init__(self, dp_degree: int = 1, mp_degree: int = 1,
                 pp_degree: int = 1, sharding_degree: int = 1,
                 sep_degree: int = 1, ep_degree: int = 1,
                 devices: Optional[Sequence] = None):
        self.mesh = create_hybrid_mesh(dp=dp_degree, mp=mp_degree,
                                       pp=pp_degree,
                                       sharding=sharding_degree,
                                       sep=sep_degree, ep=ep_degree,
                                       devices=devices)
        self._degrees: Dict[str, int] = {
            "pp": pp_degree, "dp": dp_degree, "sharding": sharding_degree,
            "sep": sep_degree, "ep": ep_degree, "mp": mp_degree}
        self._topo = CommunicateTopology(list(HYBRID_AXES),
                                         [self._degrees[a] for a in HYBRID_AXES])
        self.global_rank = self._infer_global_rank()
        self._coord = self._topo.get_coord(self.global_rank)

    def _infer_global_rank(self) -> int:
        env = os.environ.get("PADDLE_TRAINER_ID")
        if env is not None:
            return int(env)
        if jax.process_count() > 1:
            # first addressable device's linear index in the mesh
            flat = list(self.mesh.devices.flat)
            local = jax.local_devices()[0]
            for i, d in enumerate(flat):
                if d == local:
                    return i
        return 0

    # --- degree / rank / group accessors (reference API surface) ---------
    def _axis_index(self, name):
        return HYBRID_AXES.index(name)

    def get_parallel_mode(self) -> str:
        if self._degrees["pp"] > 1:
            return "pipeline"
        if self._degrees["sharding"] > 1:
            return "sharding_parallel"
        if self._degrees["mp"] > 1:
            return "model_parallel"
        return "data_parallel"

    def topology(self) -> CommunicateTopology:
        return self._topo

    def get_global_rank(self) -> int:
        return self.global_rank

    # per-axis:
    def _ws(self, a):
        return self._degrees[a]

    def _rank(self, a):
        return self._coord[self._axis_index(a)]

    def get_data_parallel_world_size(self):
        return self._ws("dp")

    def get_data_parallel_rank(self):
        return self._rank("dp")

    def get_model_parallel_world_size(self):
        return self._ws("mp")

    def get_model_parallel_rank(self):
        return self._rank("mp")

    def get_pipe_parallel_world_size(self):
        return self._ws("pp")

    def get_stage_id(self):
        return self._rank("pp")

    def get_sharding_parallel_world_size(self):
        return self._ws("sharding")

    def get_sharding_parallel_rank(self):
        return self._rank("sharding")

    def get_sep_parallel_world_size(self):
        return self._ws("sep")

    def get_sep_parallel_rank(self):
        return self._rank("sep")

    # group objects = named axes of the one mesh
    def get_data_parallel_group(self):
        from .collective import Group

        return Group(self.mesh, "dp")

    def get_model_parallel_group(self):
        from .collective import Group

        return Group(self.mesh, "mp")

    def get_pipe_parallel_group(self):
        from .collective import Group

        return Group(self.mesh, "pp")

    def get_sharding_parallel_group(self):
        from .collective import Group

        return Group(self.mesh, "sharding")

    def get_sep_parallel_group(self):
        from .collective import Group

        return Group(self.mesh, "sep")

    def get_check_parallel_group(self):
        from .collective import Group

        return Group(self.mesh, HYBRID_AXES)

    # pipeline neighbours (reference topology.py is_first_stage etc.)
    def is_first_stage(self):
        return self.get_stage_id() == 0

    def is_last_stage(self):
        return self.get_stage_id() == self._ws("pp") - 1


def set_hybrid_communicate_group(hcg: HybridCommunicateGroup):
    global _CURRENT_HCG, _CURRENT_MESH
    _CURRENT_HCG = hcg
    _CURRENT_MESH = hcg.mesh


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _CURRENT_HCG


def set_current_mesh(mesh: Optional[Mesh]):
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def get_current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH


# Opt-in quantized wire format for the mp all-reduces traced while the
# flag is set (row-parallel serving matmuls check it at trace time).
# Scoped, not sticky: generation._MeshContext sets it for the engine that
# owns the trace and restores the previous value on exit.
_QUANTIZED_ALLREDUCE: Optional[str] = None


def set_quantized_allreduce(mode: Optional[str]):
    if mode not in (None, "int8"):
        raise ValueError(
            f"unsupported quantized all-reduce mode {mode!r}; "
            "expected None or 'int8'")
    global _QUANTIZED_ALLREDUCE
    _QUANTIZED_ALLREDUCE = mode


def get_quantized_allreduce() -> Optional[str]:
    return _QUANTIZED_ALLREDUCE


def named_sharding(*spec) -> Optional[NamedSharding]:
    mesh = get_current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, P(*spec))
