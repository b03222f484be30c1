"""Cost model: roofline over XLA cost analysis, alpha-beta comm costs,
measured op-latency table (reference auto_parallel/static/cost/)."""

import jax.numpy as jnp
import numpy as np

import pytest

from paddle_tpu.utils.cost_model import (
    DEVICE_SPECS, CostEstimator, DeviceSpec, OpLatencyTable, comm_cost_ms,
    roofline_estimate,
)


def test_device_spec_is_looked_up_by_device_kind(monkeypatch):
    """Peaks come from one table keyed by device_kind; a kind that is not
    in it raises instead of borrowing the v5e's numbers."""
    import jax

    assert DeviceSpec.current() is DEVICE_SPECS["cpu"]
    v5e = DEVICE_SPECS["TPU v5 lite"]
    assert (v5e.peak_flops, v5e.hbm_gbps) == (197e12, 819.0)

    class Unknown:
        device_kind = "TPU v9 hypothetical"

    monkeypatch.setattr(jax, "devices", lambda *a: [Unknown()])
    with pytest.raises(KeyError, match="TPU v9 hypothetical"):
        DeviceSpec.current()


def test_roofline_matmul_is_compute_or_memory_bound():
    a = jnp.ones((512, 512), jnp.float32)
    r = roofline_estimate(lambda a: a @ a, a)
    # 2n^3 flops give-or-take fusion accounting
    assert r["flops"] >= 2 * 512 ** 3 * 0.5
    assert r["est_ms"] > 0 and r["bound"] in ("compute", "memory")
    # elementwise op must be memory-bound with tiny intensity
    r2 = roofline_estimate(lambda a: a + 1.0, a)
    assert r2["bound"] == "memory"
    assert r2["arithmetic_intensity"] < r["arithmetic_intensity"]


def test_comm_cost_scaling():
    spec = DEVICE_SPECS["TPU v5 lite"]
    mb = 64 * 2 ** 20
    ar8 = comm_cost_ms("allreduce", mb, 8, spec)
    ag8 = comm_cost_ms("allgather", mb, 8, spec)
    assert ar8 > ag8                       # allreduce moves ~2x the bytes
    assert comm_cost_ms("allreduce", mb, 1, spec) == 0.0
    assert comm_cost_ms("allreduce", 2 * mb, 8, spec) > ar8


def test_op_latency_table_measure_and_persist(tmp_path):
    t = OpLatencyTable(str(tmp_path / "lat.json"))
    a = jnp.ones((128, 128), jnp.float32)
    ms = t.measure("matmul", lambda a: a @ a, a)
    assert ms > 0
    assert t.get("matmul", a) == ms
    assert t.get("matmul", jnp.ones((64, 64))) is None   # different sig
    t.save()
    t2 = OpLatencyTable(str(tmp_path / "lat.json"))
    assert t2.get("matmul", a) == ms


def test_estimator_adds_discounted_comm():
    a = jnp.ones((256, 256), jnp.float32)
    est = CostEstimator(overlap=0.5)
    r1 = est.estimate_step(lambda a: a @ a, a)
    r2 = est.estimate_step(lambda a: a @ a, a, grad_bytes=1e9, dp=8)
    assert r2["comm_ms"] > 0 and r2["total_ms"] > r1["total_ms"]
