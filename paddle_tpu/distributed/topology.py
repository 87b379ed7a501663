"""Hybrid-parallel topology → jax device mesh.

Mirrors `python/paddle/distributed/fleet/base/topology.py`
(`CommunicateTopology:36` N-D rank mesh, `HybridCommunicateGroup:117`
per-axis comm groups). The reference materializes one NCCL ring per axis
slice; on TPU a single `jax.sharding.Mesh` with named axes replaces every
ring — XLA derives the communicator groups from the axis being reduced.

Axis order follows the reference: ["data", "pipe", "sharding", "model"]
(+ optional "sequence" beyond-reference for context parallelism).
"""
from __future__ import annotations

import collections
import contextlib
import threading
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_HYBRID_GROUP: Optional["HybridCommunicateGroup"] = None
_GLOBAL_MESH: Optional[Mesh] = None
_SCOPED = threading.local()     # .stack: meshes of the steps being traced


class CommunicateTopology:
    """Reference: topology.py:36 — pure rank-coordinate arithmetic, kept
    verbatim in spirit for launcher/debug parity."""

    def __init__(self,
                 hybrid_group_names: Sequence[str] = ("data", "pipe",
                                                      "sharding", "model"),
                 dims: Sequence[int] = (1, 1, 1, 1)):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(int(d) for d in dims)
        self.coordinate = collections.namedtuple(
            "Coordinate", self._parallel_names)
        self._world_size = int(np.prod(self._dims))
        ranks = np.arange(self._world_size).reshape(self._dims)
        self._coord2rank = {}
        self._rank2coord = {}
        for coord in np.ndindex(*self._dims):
            r = int(ranks[coord])
            c = self.coordinate(*coord)
            self._coord2rank[c] = r
            self._rank2coord[r] = c

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self):
        return self._world_size

    def get_rank(self, **kwargs):
        return self._coord2rank[self.coordinate(
            *(kwargs[n] for n in self._parallel_names))]

    def get_coord(self, rank):
        return self._rank2coord[rank]

    def get_axis_list(self, axis_name, index):
        axis = self._parallel_names.index(axis_name)
        return sorted(r for c, r in self._coord2rank.items()
                      if c[axis] == index)

    def get_comm_list(self, axis_name):
        """All rank-groups along `axis_name` (the reference builds one NCCL
        ring per entry; we keep it for tests/launch bookkeeping)."""
        axis = self._parallel_names.index(axis_name)
        other = [n for i, n in enumerate(self._parallel_names) if i != axis]
        groups = []
        other_dims = [self._dims[self._parallel_names.index(n)]
                      for n in other]
        for coord in np.ndindex(*other_dims):
            fixed = dict(zip(other, coord))
            group = []
            for i in range(self._dims[axis]):
                fixed[axis_name] = i
                group.append(self.get_rank(**fixed))
            groups.append(group)
        return groups


def build_mesh(dp: int = 1, pp: int = 1, sharding: int = 1, mp: int = 1,
               sp: int = 1, devices: Optional[list] = None) -> Mesh:
    """Create the global hybrid mesh.

    Reference: `HybridCommunicateGroup` ring construction → here one Mesh
    with axes (data, pipe, sharding, model[, sequence]). Collectives ride
    ICI when the inner axes (model/sequence) map to physically-adjacent
    chips — jax orders mesh axes innermost-last over the device list, so we
    put 'model' last exactly for that.
    """
    devices = devices if devices is not None else jax.devices()
    shape = [dp, pp, sharding, mp] + ([sp] if sp > 1 else [])
    names = ["data", "pipe", "sharding", "model"] + \
        (["sequence"] if sp > 1 else [])
    n = int(np.prod(shape))
    assert n <= len(devices), \
        f"mesh needs {n} devices, have {len(devices)}"
    arr = np.asarray(devices[:n]).reshape(shape)
    mesh = Mesh(arr, axis_names=tuple(names))
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh
    return mesh


def get_mesh() -> Mesh:
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        _GLOBAL_MESH = build_mesh(dp=len(jax.devices()))
    return _GLOBAL_MESH


def scoped_mesh_or_none() -> Optional[Mesh]:
    """The mesh of the step being traced (`mesh_scope`); None outside."""
    stack = getattr(_SCOPED, "stack", None)
    return stack[-1] if stack else None


def get_mesh_or_none() -> Optional[Mesh]:
    """The mesh model code shards for: the one a step is being traced
    for (`mesh_scope`), else the process-global one."""
    scoped = scoped_mesh_or_none()
    return _GLOBAL_MESH if scoped is None else scoped


@contextlib.contextmanager
def mesh_scope(mesh: Mesh):
    """Trace model code for `mesh`, whatever the global mesh is by now.

    A step is built for one mesh but traced at its first call; by then
    another `build_mesh` may have replaced the global one. The step
    builders enter this scope around their forward/backward so that the
    sharding hints (`mp_layers._constrain`) and the attention dispatch
    see the step's own mesh."""
    stack = getattr(_SCOPED, "stack", None)
    if stack is None:
        stack = _SCOPED.stack = []
    stack.append(mesh)
    try:
        yield mesh
    finally:
        stack.pop()


def set_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


class HybridCommunicateGroup:
    """Reference: topology.py:117. Exposes per-axis rank/world-size plus the
    Mesh; the *_group() handles of the reference (NCCL comm objects) are the
    axis names themselves."""

    def __init__(self, topology: CommunicateTopology,
                 mesh: Optional[Mesh] = None):
        self._topo = topology
        names = topology.get_hybrid_group_names()
        dims = {n: topology.get_dim(n) for n in names}
        self._mesh = mesh if mesh is not None else build_mesh(
            dp=dims.get("data", 1), pp=dims.get("pipe", 1),
            sharding=dims.get("sharding", 1), mp=dims.get("model", 1),
            sp=dims.get("sequence", 1))
        self.global_rank = 0  # single-controller SPMD: rank==process idx
        from .env import get_rank
        self.global_rank = get_rank()
        self.nranks = topology.world_size()
        global _HYBRID_GROUP
        _HYBRID_GROUP = self

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    def _coord(self):
        return self._topo.get_coord(self.global_rank)

    def get_data_parallel_rank(self):
        return self._coord().data

    def get_data_parallel_world_size(self):
        return self._topo.get_dim("data")

    def get_model_parallel_rank(self):
        return self._coord().model

    def get_model_parallel_world_size(self):
        return self._topo.get_dim("model")

    def get_stage_id(self):
        return self._coord().pipe

    def get_pipe_parallel_world_size(self):
        return self._topo.get_dim("pipe")

    def get_sharding_parallel_rank(self):
        return self._coord().sharding

    def get_sharding_parallel_world_size(self):
        return self._topo.get_dim("sharding")

    # group handles == axis names (feed to collective ops / PartitionSpec)
    def get_data_parallel_group(self):
        return "data"

    def get_model_parallel_group(self):
        return "model"

    def get_pipe_parallel_group(self):
        return "pipe"

    def get_sharding_parallel_group(self):
        return "sharding"

    def get_check_parallel_group(self):
        return None

    def get_p2p_next_rank(self):
        stages = self._topo.get_dim("pipe")
        c = self._coord()._asdict()
        c["pipe"] = (c["pipe"] + 1) % stages
        return self._topo.get_rank(**c)

    def get_p2p_prev_rank(self):
        stages = self._topo.get_dim("pipe")
        c = self._coord()._asdict()
        c["pipe"] = (c["pipe"] - 1) % stages
        return self._topo.get_rank(**c)

    def topology(self):
        return self._topo


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _HYBRID_GROUP


def named_sharding(*spec) -> NamedSharding:
    return NamedSharding(get_mesh(), PartitionSpec(*spec))
