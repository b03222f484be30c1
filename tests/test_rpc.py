"""paddle.distributed.rpc: p2p RPC between named workers.

Reference: python/paddle/distributed/rpc/rpc.py (init_rpc/rpc_sync/
rpc_async/shutdown over the brpc agent). Here: parallel/rpc.py socket
agents with TCPStore rendezvous.
"""

import subprocess
import sys
import threading

import numpy as np
import pytest

from paddle_tpu.parallel.rpc import RpcAgent, WorkerInfo
from paddle_tpu.parallel.store import TCPStore


def _add(a, b):
    return a + b


def _mul_np(x, y):
    return (np.asarray(x) * y).tolist()


def _boom():
    raise ValueError("remote failure")


@pytest.fixture()
def agents():
    store = TCPStore("127.0.0.1", 0, is_master=True, world_size=2)
    peer = TCPStore("127.0.0.1", store.port, is_master=False, world_size=2)
    a = RpcAgent("alice", 0, 2, store)
    b = RpcAgent("bob", 1, 2, peer)
    yield a, b
    a._stop()
    b._stop()


def test_rpc_sync_roundtrip(agents):
    a, b = agents
    assert a.rpc_sync("bob", _add, args=(2, 3)) == 5
    assert b.rpc_sync("alice", _add, args=(10, -4)) == 6
    # self-call is allowed (reference permits to == current worker)
    assert a.rpc_sync("alice", _add, args=(1, 1)) == 2


def test_rpc_async_futures(agents):
    a, _ = agents
    futs = [a.rpc_async("bob", _mul_np, args=([1, 2, 3], k))
            for k in range(5)]
    results = [f.result(timeout=30) for f in futs]
    assert results[3] == [3, 6, 9]


def test_rpc_remote_exception_propagates(agents):
    a, _ = agents
    with pytest.raises(ValueError, match="remote failure"):
        a.rpc_sync("bob", _boom)


def test_worker_infos(agents):
    a, b = agents
    infos = a.get_all_worker_infos()
    assert [w.name for w in infos] == ["alice", "bob"]
    bi = a._worker_info("bob")
    assert isinstance(bi, WorkerInfo) and bi.port == b.port


def test_rpc_concurrent_callers(agents):
    a, _ = agents
    out = []
    errs = []

    def worker(k):
        try:
            out.append(a.rpc_sync("bob", _add, args=(k, k)))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert not errs and sorted(out) == [2 * i for i in range(8)]


def test_rpc_timeout_tears_down_connection(agents):
    """A hung peer must raise TimeoutError and free the per-conn lock."""
    import pickle
    import socket as pysocket

    a, _ = agents
    # fake worker: accepts, never replies
    lst = pysocket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    a._infos["zombie"] = WorkerInfo("zombie", 9, "127.0.0.1",
                                    lst.getsockname()[1])
    with pytest.raises(TimeoutError):
        a.rpc_sync("zombie", _add, args=(1, 1), timeout=0.5)
    assert "zombie" not in a._conns  # torn down, next call would redial
    lst.close()


_TWO_PROC_SCRIPT = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")   # never reach for the chip
import paddle_tpu.parallel.rpc as rpc

rank = int(sys.argv[1])
port = sys.argv[2]
name = f"w{rank}"
agent = rpc.init_rpc(name, rank=rank, world_size=2,
                     master_endpoint=f"127.0.0.1:{port}")


def square(x):
    return x * x


peer = f"w{1 - rank}"
val = rpc.rpc_sync(peer, square, args=(rank + 2,))
assert val == (rank + 2) ** 2, val
rpc.shutdown()
print(f"RANK{rank}_OK")
"""


@pytest.mark.slow
def test_rpc_two_processes(tmp_path):
    """Real process isolation: two workers, store-rendezvous, cross calls,
    graceful barrier shutdown."""
    import socket as pysocket

    s = pysocket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    script = tmp_path / "rpc_worker.py"
    script.write_text(_TWO_PROC_SCRIPT)
    from _helpers import child_env

    env = child_env()
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd="/root/repo", env=env) for r in range(2)]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, out
        assert f"RANK{r}_OK" in out
