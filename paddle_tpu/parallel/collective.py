"""Collective communication API.

Reference, three levels that all collapse onto XLA collectives here:
  - python API: python/paddle/distributed/communication/ (all_reduce,
    all_gather, all_to_all, reduce_scatter, broadcast, send/recv, barrier)
  - dygraph ProcessGroup (paddle/phi/core/distributed/collective/
    process_group.h:48, ProcessGroupNCCL process_group_nccl.h:37)
  - static-graph c_* ops (paddle/fluid/operators/collective/)

TPU-native: inside a shard_map/jit region these are jax.lax collectives over
mesh axes (psum / all_gather / all_to_all / ppermute / psum_scatter) riding
ICI. Outside a compiled region, "collectives" over a sharded jax.Array are
resharding operations (device_put), which XLA implements with the same
collectives — so the eager API works on DistTensors like the reference's
eager ProcessGroup path. The ReduceOp/group surface mirrors paddle's.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.parallel.mesh import current_mesh


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group == a mesh axis (reference: new_group building an
    NCCL ring; here rings are mesh axes with ICI neighbors)."""

    def __init__(self, axis: str, mesh=None):
        self.axis = axis
        self.mesh = mesh

    @property
    def nranks(self):
        m = self.mesh or current_mesh()
        return m.shape[self.axis] if m else 1

    world_size = nranks

    def __repr__(self):
        return f"Group(axis={self.axis!r}, nranks={self.nranks})"


def new_group(ranks=None, axis: str = "dp") -> Group:
    return Group(axis)


def _axis_of(group) -> str:
    if group is None:
        return "dp"
    if isinstance(group, Group):
        return group.axis
    return str(group)


# ---------------------------------------------------------------------------
# In-jit functional collectives (for shard_map regions: pipeline, custom TP).
# These are the direct analogues of the reference's c_* kernels.
# ---------------------------------------------------------------------------


def psum(x, axis: str):
    return lax.psum(x, axis)


def pmean(x, axis: str):
    return lax.pmean(x, axis)


def pmax(x, axis: str):
    return lax.pmax(x, axis)


def all_gather_in(x, axis: str, tensor_axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis, axis=tensor_axis, tiled=tiled)


def reduce_scatter_in(x, axis: str, tensor_axis: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=tensor_axis, tiled=True)


def all_to_all_in(x, axis: str, split_axis: int, concat_axis: int):
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def ppermute(x, axis: str, perm):
    return lax.ppermute(x, axis, perm)


def axis_index(axis: str):
    return lax.axis_index(axis)


# ---------------------------------------------------------------------------
# Eager API over sharded arrays (paddle.distributed.* surface).
# Semantics: the tensor is interpreted per mesh sharding; op == reshard.
# ---------------------------------------------------------------------------


def _mesh_or_raise():
    m = current_mesh()
    if m is None:
        raise RuntimeError("no mesh active; call init_mesh() first")
    return m


def all_reduce(tensor: Tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """On a replicated-view tensor this is an identity (values equal across
    the axis); on a Partial-view it completes the psum. Eager single-process
    semantics: sum over the shards along the group axis if the tensor is
    sharded there, else identity."""
    m = _mesh_or_raise()
    axis = _axis_of(group)
    spec = _spec_of(tensor._value, m)
    if spec is None or axis not in _axes_in_spec(spec):
        return tensor  # replicated along the axis: allreduce is identity
    # sharded along axis: interpret shards as partial contributions
    n = m.shape[axis]
    parts = _unshard_axis(tensor._value, m, axis)
    red = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min,
           "prod": jnp.prod, "avg": jnp.mean}[op](parts, axis=0)
    out = jax.device_put(red, NamedSharding(m, _drop_axis(spec, axis)))
    tensor._inplace_update(out)
    return tensor


def all_gather(tensor_list, tensor: Tensor, group=None, sync_op=True):
    """Gather shards along the group axis (reference
    communication/all_gather.py)."""
    m = _mesh_or_raise()
    axis = _axis_of(group)
    parts = _unshard_axis(tensor._value, m, axis)
    for i in range(parts.shape[0]):
        tensor_list.append(Tensor._wrap(parts[i]))
    return tensor_list


def broadcast(tensor: Tensor, src=0, group=None, sync_op=True):
    # single-process SPMD: data is already consistent; replicate sharding
    m = _mesh_or_raise()
    axis = _axis_of(group)
    spec = _spec_of(tensor._value, m)
    if spec is not None and axis in _axes_in_spec(spec):
        v = _unshard_axis(tensor._value, m, axis)[src]
        tensor._inplace_update(
            jax.device_put(v, NamedSharding(m, _drop_axis(spec, axis))))
    return tensor


_BARRIER_SEQ = [0]


def barrier(group=None):
    """Fence local device work; in a multi-process world, additionally
    rendezvous every rank through the global TCPStore (an arrival
    counter per barrier sequence). Store requests are request/response
    on one ordered connection per rank, so a rank's pre-barrier
    `store.set` is server-applied before its arrival mark — every
    rank's pre-barrier writes are visible to every rank after barrier()
    returns (pinned by test_cross_process_barrier_orders_effects; the
    old local-fence-only spelling only held by timing luck)."""
    jax.block_until_ready(jnp.zeros(()))
    from paddle_tpu.parallel import env as _env

    if not _env.is_initialized() or _env.get_world_size() <= 1:
        return
    import time as _time

    store, _rank = _p2p_store()
    world = _env.get_world_size()
    seq = _BARRIER_SEQ[0]
    _BARRIER_SEQ[0] += 1
    key = f"barrier/{seq}"
    if store.add(key, 1) < world:
        while store.add(key, 0) < world:
            _time.sleep(0.001)


def get_rank(group=None) -> int:
    from paddle_tpu.parallel.env import get_rank as _gr

    return _gr()


def get_world_size(group=None) -> int:
    from paddle_tpu.parallel.env import get_world_size as _gw

    return _gw()


# ----------------------------------------------------------------- helpers


def _spec_of(value, mesh) -> Optional[PartitionSpec]:
    sh = getattr(value, "sharding", None)
    if isinstance(sh, NamedSharding):
        return sh.spec
    return None


def _axes_in_spec(spec: PartitionSpec):
    out = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        for e in (entry if isinstance(entry, tuple) else (entry,)):
            out.add(e)
    return out


def _drop_axis(spec: PartitionSpec, axis: str) -> PartitionSpec:
    new = []
    for entry in tuple(spec):
        if entry is None:
            new.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(e for e in entry if e != axis)
            new.append(kept if kept else None)
        else:
            new.append(None if entry == axis else entry)
    return PartitionSpec(*new)


def _unshard_axis(value, mesh, axis: str):
    """Materialize the per-shard views along `axis` as a stacked array."""
    spec = _spec_of(value, mesh)
    if spec is None or axis not in _axes_in_spec(spec):
        n = mesh.shape[axis]
        return jnp.stack([value] * n)
    # find tensor dim sharded by axis
    for tdim, entry in enumerate(tuple(spec)):
        entries = entry if isinstance(entry, tuple) else (entry,)
        if entry is not None and axis in entries:
            n = mesh.shape[axis]
            full = jax.device_put(value, NamedSharding(mesh, _drop_axis(spec, axis)))
            parts = jnp.split(full, n, axis=tdim)
            return jnp.stack(parts)
    raise AssertionError


# ---------------------------------------------------------------------------
# p2p API (reference: paddle.distributed.{send,recv,isend,irecv,
# batch_isend_irecv} + P2pHelper pp_utils/p2p_communication.py). In the
# compiled universe these are ppermute edges over a mesh axis.
# ---------------------------------------------------------------------------


class P2POp:
    """One edge of a batched p2p exchange (reference batch_isend_irecv)."""

    def __init__(self, op, tensor, peer, group=None):
        self.op = op  # "isend" | "irecv"
        self.tensor = tensor
        self.peer = peer
        self.group = group


def send_in(x, axis: str, dst_offset: int = 1):
    """In-jit: send this rank's block `dst_offset` ranks forward along the
    axis ring; returns what this rank RECEIVES (collective_permute
    semantics — every rank participates)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + dst_offset) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


# Eager multi-process p2p: the DATA rides the PjRt cross-host transfer
# fabric (jax.experimental.transfer — DCN/ICI device-buffer pulls, the
# NCCL-p2p analogue; reference process_group_nccl.h:37), with the
# TCPStore carrying only the rendezvous metadata (address + uuid). When
# the transfer API is unavailable (or PADDLE_P2P_TRANSPORT=store), the
# payload falls back to pickle-over-TCPStore — the Gloo-class host
# channel. Inside compiled programs p2p is lax.ppermute on a mesh axis
# (`send_in`; the pipeline module shows the pattern).

_P2P_SEQ: dict = {}
_XFER = {"server": None, "conns": {}, "tried": False}


def _transfer_server():
    """Lazy per-process PjRt TransferServer (None = unavailable). The bind
    address comes from PADDLE_P2P_BIND (set a routable IP for multi-host;
    default loopback covers single-host worlds and tests)."""
    import os

    if os.environ.get("PADDLE_P2P_TRANSPORT") == "store":
        return None
    if _XFER["server"] is None and not _XFER["tried"]:
        _XFER["tried"] = True
        try:
            from jax.experimental import transfer as jt

            bind = os.environ.get("PADDLE_P2P_BIND", "127.0.0.1:0")
            host = bind.rsplit(":", 1)[0]
            # explicit socket transport addresses: the default local
            # (same-host shm) bulk transport assumes one process and
            # aborts on a cross-process pull
            _XFER["server"] = jt.start_transfer_server(
                jax.local_devices()[0].client, bind, [f"{host}:0"])
        except Exception:
            _XFER["server"] = None
    return _XFER["server"]


def _transfer_conn(addr):
    conn = _XFER["conns"].get(addr)
    if conn is None:
        conn = _XFER["conns"][addr] = _XFER["server"].connect(addr)
    return conn


def _p2p_store():
    from paddle_tpu.parallel import env as _env
    from paddle_tpu.parallel.store import create_or_get_global_tcp_store

    if not _env.is_initialized() or _env.get_world_size() <= 1:
        raise RuntimeError(
            "eager send/recv needs a multi-process launch world "
            "(paddle_tpu.parallel.launch + init_parallel_env); inside "
            "compiled programs use parallel.collective.send_in "
            "(lax.ppermute — see parallel/pipeline.py)")
    return create_or_get_global_tcp_store(), _env.get_rank()


def send(tensor, dst=0, group=None, sync_op=True):
    """Eager p2p (reference distributed.send / isend).

    Data path: the device buffer is scheduled for a PULL over the PjRt
    transfer fabric (device-bandwidth DCN/ICI — the NCCL-p2p analogue);
    only {address, uuid, shape, dtype} metadata crosses the TCPStore.
    Falls back to pickle-over-store (host sockets) when the transfer API
    is unavailable or PADDLE_P2P_TRANSPORT=store. For data movement
    INSIDE a compiled step, use the mesh collectives (`send_in` /
    lax.ppermute) — the compiled program never touches this channel."""
    import pickle

    store, rank = _p2p_store()
    seq = _P2P_SEQ.setdefault(("s", rank, dst), 0)
    _P2P_SEQ[("s", rank, dst)] = seq + 1
    key = f"p2p/{rank}->{dst}/{seq}"
    srv = _transfer_server()
    if srv is not None:
        val = (tensor._value if isinstance(tensor, Tensor)
               else jnp.asarray(tensor))
        # 10/10/44-bit uid: seq wraps after ~17T messages per channel,
        # beyond any run; rank/dst disambiguate channels on one server
        uid = (((rank & 0x3FF) << 54) | ((dst & 0x3FF) << 44)
               | (seq & 0xFFFFFFFFFFF))
        srv.await_pull(uid, [val])
        store.set(key, pickle.dumps(
            ("xfer", srv.address(), uid, str(val.dtype),
             tuple(val.shape), bool(sync_op))))
        if sync_op:
            # block (bounded) until the receiver pulled: the offered
            # buffer lives in THIS process's transfer server, so a
            # fire-and-forget sender exiting early would strand the
            # receiver's pull. Bounded so a receiver-side failure surfaces
            # as a TimeoutError here instead of a permanent hang. isend
            # (sync_op=False) keeps fire-and-forget for batch exchanges.
            import os as _os
            import time as _time

            deadline = _time.time() + float(
                _os.environ.get("PADDLE_P2P_ACK_TIMEOUT_S", "600"))
            while not store.check(key + "/ack"):
                if _time.time() > deadline:
                    raise TimeoutError(
                        f"send({rank}->{dst}, seq {seq}): receiver never "
                        "pulled within PADDLE_P2P_ACK_TIMEOUT_S — peer "
                        "failed or mis-configured transport?")
                _time.sleep(0.01)
            try:
                store.delete_key(key + "/ack")
            except Exception:
                pass
        return
    arr = np.asarray(tensor._value if isinstance(tensor, Tensor)
                     else tensor)
    store.set(key,
              pickle.dumps(("host", arr.dtype.str, arr.shape,
                            arr.tobytes())))


def recv(tensor, src=0, group=None, sync_op=True):
    """Blocking receive; writes into `tensor` and returns it. Pulls the
    device buffer over the transfer fabric when the sender offered one
    (see send)."""
    import pickle

    store, rank = _p2p_store()
    seq = _P2P_SEQ.setdefault(("r", src, rank), 0)
    _P2P_SEQ[("r", src, rank)] = seq + 1
    key = f"p2p/{src}->{rank}/{seq}"
    store.wait([key])
    msg = pickle.loads(store.get(key))   # peek — delete only on success
    if msg[0] == "xfer":
        from jax.sharding import SingleDeviceSharding

        # an in-flight xfer message must complete with any LIVE server
        # even if the env flag has since flipped to 'store'; check BEFORE
        # popping the key so a mixed-config error leaves the message
        # retrievable (and the seq re-tryable)
        if _XFER["server"] is None and _transfer_server() is None:
            _P2P_SEQ[("r", src, rank)] = seq    # un-consume the seq
            raise RuntimeError(
                "peer sent a device-buffer transfer but the local PjRt "
                "transfer server is unavailable; set "
                "PADDLE_P2P_TRANSPORT=store on ALL ranks to force the "
                "host channel")
        _, addr, uid, dtype, shape, want_ack = msg
        sds = jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype),
            sharding=SingleDeviceSharding(jax.local_devices()[0]))
        (val,) = _transfer_conn(addr).pull(uid, [sds])
        try:
            store.delete_key(key)  # bounded store: pop after success
        except Exception:
            pass
        if want_ack:
            store.set(key + "/ack", b"1")   # sender awaits + deletes
        if isinstance(tensor, Tensor):
            tensor._value = val
            return tensor
        return val
    _, dtype, shape, raw = msg
    try:
        store.delete_key(key)  # bounded store; stale keys can't resurrect
    except Exception:
        pass
    arr = np.frombuffer(raw, dtype=np.dtype(dtype)).reshape(shape)
    if isinstance(tensor, Tensor):
        tensor._value = jnp.asarray(arr)
        return tensor
    return jnp.asarray(arr)


def batch_isend_irecv(p2p_op_list):
    """Execute a batch of P2POps over the store channel (reference
    batch_isend_irecv). Sends run first so paired recvs can't deadlock
    within one rank's batch."""
    for op in p2p_op_list:
        if op.op in ("isend", "send"):
            send(op.tensor, op.peer, sync_op=False)
    for op in p2p_op_list:
        if op.op in ("irecv", "recv"):
            recv(op.tensor, op.peer)
    return []


def isend(tensor, dst=0, group=None):
    """Non-blocking send: fire-and-forget offer (no ack rendezvous) — the
    canonical isend/irecv exchange must not block before the recvs."""
    return send(tensor, dst, group=group, sync_op=False)


irecv = recv
