"""Device mesh / ProcessMesh.

Reference: paddle.distributed.ProcessMesh
(python/paddle/distributed/auto_parallel/process_mesh.py:85) and the fleet
hybrid topology (fleet/base/topology.py:70 CommunicateTopology /
HybridCommunicateGroup, axis order pp->mp->sep->sharding->dp at :298).

TPU-native: one jax.sharding.Mesh is the single source of truth for every
parallelism axis; "comm groups" are mesh axes, and collectives lower to XLA
ops over ICI. A process-global current mesh makes layer construction
sharding-aware (create_parameter picks up PartitionSpecs).
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

_current_mesh: Optional[Mesh] = None

# canonical axis order, hybrid topology style: dp outermost (slowest-varying,
# maps across hosts/DCN), then pp, then tp innermost (fastest, rides ICI) —
# mirrors the reference's pp->mp->...->dp ordering rationale reversed for
# TPU: tp wants the tightest ICI neighborhood.
AXIS_ORDER = ("dp", "pp", "ep", "sp", "tp")


def init_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Create + install the global mesh. axes e.g. {"dp": 2, "pp": 2, "tp": 2}.

    Axis sizes must multiply to the device count. Axes of size 1 are kept (so
    sharding specs can always name them).
    """
    global _current_mesh
    if devices is None:
        devices = jax.devices()
    names = [a for a in AXIS_ORDER if a in axes] + [
        a for a in axes if a not in AXIS_ORDER
    ]
    sizes = [axes[a] for a in names]
    n = int(np.prod(sizes))
    if n != len(devices):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {n} devices, "
            f"have {len(devices)}"
        )
    arr = np.asarray(devices).reshape(sizes)
    _current_mesh = Mesh(arr, tuple(names))
    return _current_mesh


def current_mesh() -> Optional[Mesh]:
    return _current_mesh


def set_mesh(mesh: Optional[Mesh]):
    global _current_mesh
    _current_mesh = mesh


@contextmanager
def mesh_scope(mesh: Mesh):
    global _current_mesh
    prev = _current_mesh
    _current_mesh = mesh
    try:
        yield mesh
    finally:
        _current_mesh = prev


_program_mesh: Optional[Mesh] = None


@contextmanager
def program_mesh_scope(mesh: Optional[Mesh]):
    """Mark `mesh` as the one the program traced inside the scope computes
    on. Set by entry points that place their state on a mesh and compile
    one program for it (jit.TrainStep). current_mesh() only says a mesh
    is installed — eager ops and single-device programs run under it on
    one device — so code that must know whether ITS operands live on the
    mesh (a Pallas kernel GSPMD cannot partition) asks program_mesh()."""
    global _program_mesh
    prev, _program_mesh = _program_mesh, mesh
    try:
        yield mesh
    finally:
        _program_mesh = prev


def program_mesh() -> Optional[Mesh]:
    return _program_mesh


def serving_mesh(data: int = 1, model: int = 1,
                 devices: Optional[Sequence] = None,
                 data_axis: str = "data",
                 model_axis: str = "model") -> Mesh:
    """Build the serving `(data, model)` mesh (ISSUE 7) WITHOUT
    installing it globally: the serving engine owns its mesh explicitly
    (runner.shard(mesh)), so a training mesh in the same process is
    never clobbered. Uses the first data*model devices when `devices`
    is not given — on the 8-way CPU test mesh that makes tp=2/4
    sub-meshes cheap to build."""
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got data={data} "
                         f"model={model}")
    if devices is None:
        devices = jax.devices()
    n = data * model
    if n > len(devices):
        raise ValueError(f"serving mesh ({data_axis}={data}, "
                         f"{model_axis}={model}) needs {n} devices, "
                         f"have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(data, model)
    return Mesh(arr, (data_axis, model_axis))


def replica_submeshes(mesh: Mesh, data_axis: str = "data",
                      model_axis: str = "model") -> List[Mesh]:
    """Split a serving `(data, model)` mesh into data-many
    `(data=1, model)` sub-meshes — one per engine replica (ISSUE 8).
    This is what finally puts the data axis to work: PR 7's tensor-
    parallel engine shards weights and K/V pools over the model axis
    but left data idle; the router tier maps replica i onto sub-mesh i,
    so a (data=2, model=4) mesh carries two independent tp=4 engines.
    Each sub-mesh keeps every other axis of the parent and a size-1
    data axis (runner.shard and the SpecLayout placements name both
    axes), so a replica's runner shards exactly like a standalone
    (data=1, model=tp) engine."""
    names = list(mesh.axis_names)
    if data_axis not in names:
        raise ValueError(f"mesh axes {tuple(names)} have no "
                         f"{data_axis!r} axis to split replicas over")
    axis = names.index(data_axis)
    devs = np.moveaxis(np.asarray(mesh.devices), axis, 0)
    rest = (data_axis,) + tuple(n for n in names if n != data_axis)
    return [Mesh(devs[i][None, ...], rest) for i in range(devs.shape[0])]


class ProcessMesh:
    """paddle.distributed.ProcessMesh-compatible facade over jax Mesh."""

    def __init__(self, mesh=None, dim_names: Optional[List[str]] = None,
                 shape: Optional[List[int]] = None):
        if isinstance(mesh, Mesh):
            self._mesh = mesh
        else:
            arr = np.asarray(mesh if mesh is not None else
                             range(len(jax.devices())))
            if shape is not None:
                arr = arr.reshape(shape)
            names = tuple(dim_names or [f"d{i}" for i in range(arr.ndim)])
            devs = np.asarray(jax.devices())[arr]
            self._mesh = Mesh(devs, names)

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def shape(self) -> List[int]:
        return [self._mesh.shape[n] for n in self._mesh.axis_names]

    @property
    def dim_names(self) -> List[str]:
        return list(self._mesh.axis_names)

    @property
    def process_ids(self) -> List[int]:
        return [d.id for d in self._mesh.devices.flat]

    def get_dim_size(self, name: str) -> int:
        return self._mesh.shape[name]

    def __eq__(self, other):
        return isinstance(other, ProcessMesh) and self._mesh == other._mesh

    def __repr__(self):
        return f"ProcessMesh(shape={self.shape}, dim_names={self.dim_names})"
