"""Cost model: roofline estimates, collective alpha-beta costs, and a
measured op-latency table.

Reference: python/paddle/distributed/auto_parallel/static/cost/
(comp_op_cost.py — per-op latency classes; comm_op_cost.py — alpha-beta
collective models; estimate_cost over a program) and tools/ op-benchmark.

TPU-native design: per-op hand-maintained latency constants are replaced
by two first-class sources XLA already has —
  * the compiled executable's cost analysis (FLOPs + bytes accessed)
    pushed through a device roofline (MXU peak / HBM bandwidth): the
    compute-op cost model;
  * an alpha-beta ICI model for collectives (ring all-reduce moves
    2(n-1)/n of the bytes, etc.): the comm-op cost model;
plus an optional MEASURED table (OpLatencyTable) for calibration, which
persists to JSON like the reference's op-benchmark rolling baseline.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple


@dataclass
class DeviceSpec:
    """Per-chip roofline numbers of one entry of DEVICE_SPECS."""
    name: str
    peak_flops: float                 # FLOP/s, bf16
    hbm_gbps: float                   # GB/s
    ici_gbps: float                   # GB/s, chip-to-chip
    launch_us: float = 3.0            # per-executable dispatch overhead

    @classmethod
    def current(cls) -> "DeviceSpec":
        """The spec of jax.devices()[0], by its device_kind. A kind that
        is not in DEVICE_SPECS is an error, not a default: a roofline
        against another chip's peaks is a wrong number."""
        import jax

        kind = jax.devices()[0].device_kind
        if kind not in DEVICE_SPECS:
            raise KeyError(
                f"no peaks recorded for device kind {kind!r}; add it to "
                f"DEVICE_SPECS with its source (have: {sorted(DEVICE_SPECS)})")
        return DEVICE_SPECS[kind]


# Peaks keyed by jax's device_kind, each with its source.
DEVICE_SPECS: Dict[str, DeviceSpec] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s, 1,600 Gbit/s (= 200 GB/s) of chip-to-chip interconnect
    "TPU v5 lite": DeviceSpec("tpu-v5e", peak_flops=197e12, hbm_gbps=819.0,
                              ici_gbps=200.0),
    # not a device anyone deploys: round numbers so the estimator's
    # arithmetic can be tested where there is no chip. Nothing computed
    # from them is a device metric.
    "cpu": DeviceSpec("cpu-proxy", peak_flops=2e11, hbm_gbps=20.0,
                      ici_gbps=5.0, launch_us=20.0),
}


def roofline_estimate(fn: Callable, *args, spec: Optional[DeviceSpec] = None,
                      **kwargs) -> Dict[str, Any]:
    """AOT cost analysis of jit(fn)(*args) pushed through the roofline:
    est time = max(flops/peak, bytes/bandwidth) + launch overhead.
    Returns {flops, bytes, est_ms, bound, arithmetic_intensity}."""
    import jax

    spec = spec or DeviceSpec.current()
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    cost = jitted.lower(*args, **kwargs).compile().cost_analysis()
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    t_flops = flops / spec.peak_flops
    t_mem = bytes_ / (spec.hbm_gbps * 1e9)
    est = max(t_flops, t_mem) + spec.launch_us * 1e-6
    return {
        "flops": flops, "bytes": bytes_,
        "est_ms": est * 1e3,
        "bound": "compute" if t_flops >= t_mem else "memory",
        "arithmetic_intensity": flops / bytes_ if bytes_ else float("inf"),
        "device": spec.name,
    }


# -------------------------------------------------------------- comm costs

def _ring_factor(op: str, n: int) -> float:
    """Bytes-on-wire multiplier for ring algorithms over n devices."""
    if n <= 1:
        return 0.0
    return {
        "allreduce": 2.0 * (n - 1) / n,
        "allgather": (n - 1) / n,
        "reduce_scatter": (n - 1) / n,
        "alltoall": (n - 1) / n,
        "broadcast": 1.0,
        "p2p": 1.0,
    }[op]


def comm_cost_ms(op: str, nbytes: float, n_devices: int,
                 spec: Optional[DeviceSpec] = None,
                 alpha_us: float = 1.0) -> float:
    """Alpha-beta collective time (reference comm_op_cost.py classes
    collapsed to one formula): alpha (per-hop latency) + moved-bytes /
    ICI bandwidth, ring algorithms assumed (what XLA emits over ICI)."""
    spec = spec or DeviceSpec.current()
    if n_devices <= 1:
        return 0.0
    hops = n_devices - 1 if op != "p2p" else 1
    wire = nbytes * _ring_factor(op, n_devices)
    return (alpha_us * hops) * 1e-3 + wire / (spec.ici_gbps * 1e9) * 1e3


# ------------------------------------------------------- measured latencies

class OpLatencyTable:
    """Measured per-(op, signature) latencies, persisted to JSON — the
    reference op-benchmark rolling-baseline analogue. measure() times a
    callable up to block_until_ready; get() serves the cache."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.table: Dict[str, float] = {}
        if path and os.path.exists(path):
            with open(path) as f:
                self.table = json.load(f)

    @staticmethod
    def _key(name: str, args) -> str:
        sig = tuple((tuple(getattr(a, "shape", ())),
                     str(getattr(a, "dtype", type(a).__name__)))
                    for a in args)
        return f"{name}{sig}"

    def measure(self, name: str, fn: Callable, *args, iters: int = 5,
                warmup: int = 2) -> float:
        import jax

        key = self._key(name, args)
        jitted = jax.jit(fn)
        out = jitted(*args)
        jax.block_until_ready(out)
        for _ in range(warmup):
            out = jitted(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jitted(*args)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / iters * 1e3
        self.table[key] = ms
        return ms

    def get(self, name: str, *args) -> Optional[float]:
        return self.table.get(self._key(name, args))

    def save(self, path: Optional[str] = None) -> None:
        with open(path or self.path, "w") as f:
            json.dump(self.table, f, indent=1, sort_keys=True)


# ------------------------------------------------------------ estimator

class CostEstimator:
    """Estimate a hybrid-parallel training step (reference
    cost_estimator.py estimate_cost): compute via the roofline on the
    compiled step, collectives via the alpha-beta model for the given
    parallel config. The two add because XLA overlaps imperfectly; an
    `overlap` factor (0..1) discounts comm hidden under compute."""

    def __init__(self, spec: Optional[DeviceSpec] = None,
                 overlap: float = 0.5):
        self.spec = spec or DeviceSpec.current()
        self.overlap = overlap

    def estimate_step(self, fn: Callable, *args,
                      grad_bytes: float = 0.0, dp: int = 1,
                      tp: int = 1, activation_bytes: float = 0.0,
                      **kwargs) -> Dict[str, Any]:
        comp = roofline_estimate(fn, *args, spec=self.spec, **kwargs)
        comm_ms = 0.0
        if dp > 1:
            comm_ms += comm_cost_ms("allreduce", grad_bytes, dp, self.spec)
        if tp > 1:
            comm_ms += 2 * comm_cost_ms("allreduce", activation_bytes, tp,
                                        self.spec)
        total = comp["est_ms"] + comm_ms * (1.0 - self.overlap)
        return {**comp, "comm_ms": comm_ms, "total_ms": total}
