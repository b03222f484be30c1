"""Auto-parallel user API: placements, shard_tensor, reshard, constraints.

Reference: python/paddle/distributed/auto_parallel/api.py (shard_tensor:220,
reshard:797, shard_layer:908) with Shard/Replicate/Partial placements
(C++ placement_types.h), DistTensor = local tensor + TensorDistAttr
(phi/core/distributed/auto_parallel/dist_tensor.h:39), and the reshard
function library (auto_parallel/reshard/ — 30 files of r_to_s/s_to_r/p_to_r
transitions).

TPU-native collapse: DistTensor == jax.Array with a NamedSharding; the entire
reshard library == jax.device_put / with_sharding_constraint (GSPMD inserts
the collectives); SPMD rules == GSPMD propagation. Partial materializes as a
pending-psum representation only inside shard_map blocks.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.parallel.mesh import ProcessMesh, current_mesh

P = PartitionSpec


class Placement:
    pass


class Shard(Placement):
    def __init__(self, dim: int):
        self.dim = dim

    def __repr__(self):
        return f"Shard(dim={self.dim})"

    def __eq__(self, o):
        return isinstance(o, Shard) and o.dim == self.dim


class Replicate(Placement):
    def __repr__(self):
        return "Replicate()"

    def __eq__(self, o):
        return isinstance(o, Replicate)


class Partial(Placement):
    def __init__(self, reduce_type: str = "sum"):
        self.reduce_type = reduce_type

    def __repr__(self):
        return f"Partial({self.reduce_type})"

    def __eq__(self, o):
        return isinstance(o, Partial) and o.reduce_type == self.reduce_type


def _resolve_mesh(mesh) -> Mesh:
    if mesh is None:
        m = current_mesh()
        if m is None:
            raise RuntimeError("no mesh: call paddle_tpu.parallel.init_mesh() "
                               "or pass a mesh/ProcessMesh")
        return m
    if isinstance(mesh, ProcessMesh):
        return mesh.mesh
    return mesh


def placements_to_spec(placements: Sequence[Placement], mesh: Mesh,
                       ndim: int) -> PartitionSpec:
    """[Shard(0), Replicate(), ...] (one per MESH axis, paddle convention)
    -> PartitionSpec over TENSOR dims."""
    dims: List[Optional[tuple]] = [None] * ndim
    for axis_name, pl in zip(mesh.axis_names, placements):
        if isinstance(pl, Shard):
            if dims[pl.dim] is None:
                dims[pl.dim] = (axis_name,)
            else:
                dims[pl.dim] = dims[pl.dim] + (axis_name,)
        elif isinstance(pl, Partial):
            raise ValueError("Partial placement cannot be materialized on a "
                             "stored tensor outside shard_map")
    flat = [d[0] if (d is not None and len(d) == 1) else d for d in dims]
    return PartitionSpec(*flat)


def spec_to_placements(spec: PartitionSpec, mesh: Mesh, ndim: int):
    out = [Replicate() for _ in mesh.axis_names]
    name_to_idx = {n: i for i, n in enumerate(mesh.axis_names)}
    for tdim, entry in enumerate(tuple(spec) + (None,) * (ndim - len(tuple(spec)))):
        if entry is None:
            continue
        entries = entry if isinstance(entry, tuple) else (entry,)
        for name in entries:
            out[name_to_idx[name]] = Shard(tdim)
    return out


def shard_tensor(tensor, mesh=None, placements=None, spec=None,
                 stop_gradient=None) -> Tensor:
    """paddle.distributed.shard_tensor (api.py:220): place a tensor on the
    mesh with the given placements. Accepts either paddle-style placements or
    a raw PartitionSpec."""
    m = _resolve_mesh(mesh)
    if spec is None:
        spec = placements_to_spec(placements or [], m, tensor._value.ndim)
    v = jax.device_put(tensor._value, NamedSharding(m, spec))
    out = Tensor(v, stop_gradient=tensor.stop_gradient
                 if stop_gradient is None else stop_gradient)
    return out


def dtensor_from_local(tensor, mesh=None, placements=None) -> Tensor:
    return shard_tensor(tensor, mesh, placements)


def reshard(tensor, mesh=None, placements=None, spec=None) -> Tensor:
    """paddle.distributed.reshard (api.py:797). All 30 reference reshard
    functions collapse into one device_put: XLA emits the collective
    (allgather for s->r, slice for r->s, ...)."""
    return shard_tensor(tensor, mesh, placements, spec)


def shard_layer(layer, mesh=None, shard_fn=None, input_fn=None,
                output_fn=None):
    """paddle.distributed.shard_layer (api.py:908): apply shard_fn(name,
    layer, mesh) to every sublayer to place its params."""
    m = _resolve_mesh(mesh)
    if shard_fn is None:
        def shard_fn(name, sublayer, mesh):  # replicate by default
            for pname, p in sublayer._parameters.items():
                if p is not None:
                    p._value = jax.device_put(
                        p._value, NamedSharding(mesh, PartitionSpec()))
    for name, sub in layer.named_sublayers(include_self=True):
        shard_fn(name, sub, m)
    if input_fn is not None:
        layer.register_forward_pre_hook(
            lambda l, args: input_fn(args, m))
    if output_fn is not None:
        layer.register_forward_post_hook(
            lambda l, args, out: output_fn(out, m))
    return layer


def sharding_constraint(x: Tensor, spec: PartitionSpec, mesh=None) -> Tensor:
    """Annotate intermediate activations (the TPU analogue of inserting a
    reshard op mid-program). Inside jit this is lax.with_sharding_constraint;
    outside it's a device_put. No-op when no mesh is active."""
    m = mesh if mesh is not None else current_mesh()
    if m is None:
        return x
    from paddle_tpu.ops.registry import dispatch

    return dispatch("_sharding_constraint", (x,),
                    {"spec": spec, "mesh": m})


_static_trace_depth = 0


class static_trace:
    """Active while paddle_tpu.jit traces a whole program. Sharding
    constraints only materialize inside compiled programs (GSPMD); in eager
    mode they are no-ops (eager TP correctness doesn't need them, and eager
    resharding goes through shard_tensor/reshard explicitly)."""

    def __enter__(self):
        global _static_trace_depth
        _static_trace_depth += 1
        return self

    def __exit__(self, *exc):
        global _static_trace_depth
        _static_trace_depth -= 1
        return False


def in_static_trace() -> bool:
    return _static_trace_depth > 0


def _register_constraint_op():
    from paddle_tpu.ops.registry import OPS, OpDef

    def _impl(x, spec=None, mesh=None):
        if in_static_trace():
            # inside shard_map an abstract mesh with Manual/Auto axis types
            # is ambient; a bare PartitionSpec resolves against it (a concrete
            # NamedSharding would mis-type the manual axes). Plain jit has an
            # empty abstract mesh -> use the concrete mesh.
            if jax.sharding.get_abstract_mesh().axis_names:
                return jax.lax.with_sharding_constraint(x, spec)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        return x

    # dynamic=True skips the per-op jit wrapper so the flag is read at the
    # actual trace time, not baked into a jit cache entry.
    OPS["_sharding_constraint"] = OpDef("_sharding_constraint", _impl,
                                        diff=True, dynamic=True, method=False)


_register_constraint_op()
