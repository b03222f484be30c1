"""Ring attention + distributed checkpoint + profiler tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import parallel as dist
from paddle_tpu.parallel.checkpoint import load_state_dict, save_state_dict
from paddle_tpu.parallel.ring_attention import ring_attention
from paddle_tpu.ops.impl import scaled_dot_product_attention

rng = np.random.default_rng(9)


@pytest.fixture
def mesh_sp():
    mesh = dist.init_mesh({"dp": 2, "sp": 4})
    yield mesh
    dist.set_mesh(None)


def _qkv(b=2, s=32, h=4, d=16):
    return tuple(jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
                 for _ in range(3))


def test_ring_attention_causal_parity(mesh_sp):
    q, k, v = _qkv()
    out = ring_attention(q, k, v, mesh_sp, axis="sp", causal=True)
    ref = scaled_dot_product_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_ring_attention_full_parity(mesh_sp):
    q, k, v = _qkv()
    out = ring_attention(q, k, v, mesh_sp, axis="sp", causal=False)
    ref = scaled_dot_product_attention(q, k, v, is_causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_ring_attention_grad_parity(mesh_sp):
    q, k, v = _qkv(b=1, s=16, h=2, d=8)

    def ring_loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh_sp, axis="sp",
                                      causal=True) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(scaled_dot_product_attention(q, k, v,
                                                    is_causal=True) ** 2)

    g = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4)


def test_ring_attention_under_jit_with_sharded_inputs(mesh_sp):
    q, k, v = _qkv(b=2, s=64, h=4, d=16)
    spec = NamedSharding(mesh_sp, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(t, spec) for t in (q, k, v))
    f = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh_sp, axis="sp"))
    out = f(qs, ks, vs)
    ref = scaled_dot_product_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


# ------------------------------------------------------------- checkpoint


def test_checkpoint_roundtrip_replicated(tmp_path):
    net = nn.Linear(4, 3)
    sd = net.state_dict()
    save_state_dict(sd, str(tmp_path / "ckpt"))
    net2 = nn.Linear(4, 3)
    sd2 = net2.state_dict()
    load_state_dict(sd2, str(tmp_path / "ckpt"))
    np.testing.assert_allclose(net2.weight.numpy(), net.weight.numpy())


def test_checkpoint_reshard_on_load(tmp_path):
    """Save sharded one way, load into a different sharding (the reference's
    load-time automatic resharding, load_state_dict.py:526)."""
    mesh = dist.init_mesh({"dp": 2, "tp": 4})
    try:
        w = paddle.to_tensor(rng.standard_normal((8, 16)).astype(np.float32))
        ws = dist.shard_tensor(w, placements=[dist.Shard(0), dist.Replicate()])
        save_state_dict({"w": ws}, str(tmp_path / "ck2"))

        # target: sharded along the other dim
        target = dist.shard_tensor(
            paddle.zeros([8, 16]), placements=[dist.Replicate(), dist.Shard(1)])
        load_state_dict({"w": target}, str(tmp_path / "ck2"))
        np.testing.assert_allclose(np.asarray(target._value), w.numpy())
        assert target._value.sharding.spec == P(None, "tp")
    finally:
        dist.set_mesh(None)


def test_checkpoint_dedup_shards(tmp_path):
    """Replicated tensors write one shard file, not one per device."""
    mesh = dist.init_mesh({"dp": 8})
    try:
        w = dist.shard_tensor(paddle.ones([4, 4]),
                              placements=[dist.Replicate()])
        save_state_dict({"w": w}, str(tmp_path / "ck3"))
        files = [f for f in os.listdir(tmp_path / "ck3")
                 if f.endswith(".npy")]
        assert len(files) == 1
    finally:
        dist.set_mesh(None)


# ------------------------------------------------------------- profiler


def test_profiler_host_events(tmp_path):
    from paddle_tpu import profiler as prof_mod
    from paddle_tpu.profiler import Profiler, ProfilerTarget, RecordEvent

    # the session's .xplane.pb goes where the constructor says; the
    # program's spans are read from the one ring (ISSUE 25)
    p = Profiler(targets=[ProfilerTarget.CPU],
                 trace_dir=str(tmp_path / "xplane"))
    with RecordEvent("before_start"):
        pass                                 # no session: not recorded
    p.start()
    with RecordEvent("my_region"):
        paddle.matmul(paddle.ones([8, 8]), paddle.ones([8, 8]))
    p.stop()
    path = p.export_chrome_tracing(str(tmp_path / "trace.json"))
    import json

    with open(path) as f:
        trace = json.load(f)
    names = [e["name"] for e in trace["traceEvents"]]
    assert names == ["my_region"]
    assert [s[0] for s in p.spans()] == ["my_region"]
    assert any(f.endswith(".xplane.pb") for _, _, fs in
               os.walk(tmp_path / "xplane") for f in fs)
    table = p.summary()
    assert "my_region" in table


def test_profiler_scheduler():
    from paddle_tpu.profiler import ProfilerState, make_scheduler

    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1)
    states = [sched(i) for i in range(5)]
    assert states[0] == ProfilerState.CLOSED
    assert states[1] == ProfilerState.READY
    assert states[2] == ProfilerState.RECORD
    assert states[3] == ProfilerState.RECORD_AND_RETURN
    assert states[4] == ProfilerState.CLOSED


def test_resharding_load_never_assembles_full_tensor(tmp_path, monkeypatch):
    """Weak-#7 fix: loading into a sharded target reads only per-device
    regions — the full tensor must never be assembled host-side."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu.parallel.checkpoint as ck

    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("a", "b"))
    from paddle_tpu.core.tensor import Tensor

    w = jax.device_put(np.arange(64 * 16, dtype=np.float32).reshape(64, 16),
                       NamedSharding(mesh, P("a", None)))
    save_state_dict({"w": Tensor._wrap(w)}, str(tmp_path / "ck3"))

    sizes = []
    orig = ck._assemble

    def spy(entry, path, want_index=None):
        out = orig(entry, path, want_index)
        sizes.append(out.size)
        return out

    monkeypatch.setattr(ck, "_assemble", spy)
    target = Tensor._wrap(
        jax.device_put(np.zeros((64, 16), np.float32),
                       NamedSharding(mesh, P(None, "b"))))
    sd = {"w": target}
    load_state_dict(sd, str(tmp_path / "ck3"))
    assert sizes, "region reader never used"
    assert max(sizes) <= 64 * 16 // 2, sizes   # only half-tensor columns
    np.testing.assert_allclose(
        np.asarray(sd["w"]._value),
        np.arange(64 * 16, dtype=np.float32).reshape(64, 16))
