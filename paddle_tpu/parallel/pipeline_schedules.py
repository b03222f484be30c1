"""1F1B and interleaved (VPP) pipeline schedules over the 'pp' mesh axis.

Reference: fleet/meta_parallel/pipeline_parallel.py:684
(forward_backward_pipeline, Megatron 1F1B), :1308
(PipelineParallelWithInterleave), and the static multi-Job Plan passes
(distributed/passes/pipeline_scheduler_pass/__init__.py:32-38 — FThenB /
1F1B / VPP / ZBH1).

TPU-native design — the whole schedule is ONE compiled XLA program:
a host-side simulator lays out the static (tick, device) -> work tables,
which are baked into a lax.scan whose body every device executes SPMD,
selecting its work by table lookup and rotating activations/grads around
the ring with lax.ppermute over ICI.

Three schedules:
  * gpipe       (parallel/pipeline.py): fwd scan, autodiff backward.
                Bubble (pp-1)/(m+pp-1); activation stash O(m).
  * interleave  (this file): v chunks of the layer stack per device at
                virtual stages c*pp+d. Differentiable like gpipe.
                Bubble ~ (pp-1)/(v*m+pp-1) — the schedule that beats
                GPipe's bubble. Stash O(m) (autodiff).
  * 1f1b        (this file): FUSED forward+backward — warmup / steady
                1F1B / cooldown, backward by per-stage recompute+vjp, loss
                computed at the last stage so backward starts while
                forwards continue. Activation stash 2*pp-1 micro-batches
                instead of m: the 1F1B memory profile. Not composable with
                outer autodiff (it IS the derivative) — returns grads.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



# ----------------------------------------------------------------- simulators

class Schedule(NamedTuple):
    """Static (tick, device) work tables produced by a simulator."""
    tables: dict          # name -> np.ndarray [T, pp] int32
    total_ticks: int
    busy_slots: int       # stage-compute work items actually scheduled
    total_slots: int      # tick slots available (incl. idle)
    stash_size: int       # activation stash per device (micro-batches)
    arrival_slots: int


def simulate_interleave(pp: int, v: int, m: int) -> Schedule:
    """Greedy forward schedule for v chunks/device (virtual stages
    j = c*pp + d). Each tick a device runs ONE virtual stage on one
    micro-batch; activations always permute +1 around the ring. Priority:
    highest virtual stage first (drains late chunks so early micro-batches
    finish; reproduces the Megatron interleave bubble ~(pp-1)/(v*m))."""
    V = v * pp
    done = {}                      # (j, i) -> finish tick
    remaining = {(j, i) for j in range(V) for i in range(m)}
    # arrival buffer bookkeeping per device: (j, i) -> slot
    arr_slot = {}
    free_slots = [list() for _ in range(pp)]
    max_slots = [0] * pp
    rows = {k: [] for k in ("work_j", "work_mb", "valid", "from_x",
                            "rd_slot", "wr_valid", "wr_slot")}
    incoming = [None] * pp         # payload in flight: (j_next, i) arriving
    t = 0
    while remaining or any(incoming):
        row = {k: [0] * pp for k in rows}
        # 1) arrivals land in each device's buffer
        for d in range(pp):
            if incoming[d] is not None:
                j, i = incoming[d]
                if free_slots[d]:
                    s = free_slots[d].pop()
                else:
                    s = max_slots[d]
                    max_slots[d] += 1
                arr_slot[(j, i)] = s
                row["wr_valid"][d] = 1
                row["wr_slot"][d] = s
            incoming[d] = None
        # 2) each device picks the ready item with the highest virtual stage
        for d in range(pp):
            ready = [
                (j, i) for (j, i) in remaining
                if j % pp == d and (j == 0 or done.get((j - 1, i), t) < t)
            ]
            if not ready:
                row["valid"][d] = 0
                continue
            j, i = max(ready, key=lambda w: (w[0], -w[1]))
            remaining.discard((j, i))
            done[(j, i)] = t
            row["valid"][d] = 1
            row["work_j"][d] = j
            row["work_mb"][d] = i
            if j == 0:
                row["from_x"][d] = 1
            else:
                s = arr_slot.pop((j, i))
                row["rd_slot"][d] = s
                free_slots[d].append(s)
            if j < V - 1:
                incoming[(d + 1) % pp] = (j + 1, i)
        for k in rows:
            rows[k].append(row[k])
        t += 1
        assert t < 4 * (V * m + pp), "interleave schedule did not converge"
    tables = {k: np.asarray(vv, np.int32) for k, vv in rows.items()}
    return Schedule(tables, t, V * m, t * pp, m, max(max_slots or [1]) or 1)


def simulate_1f1b(pp: int, m: int) -> Schedule:
    """Closed-form 1F1B timeline with dual work slots per tick (one F and
    one B per device per tick; both are real work in the steady state):

      F on device d, micro-batch i : tick i + d
      B on device d, micro-batch i : tick i + 2*(pp-1) - d
        (last stage backs up the same tick it forwards: loss is local)

    Stash in flight on device d = 2*(pp-1-d)+1  ->  stash 2*pp-1."""
    T = m + 2 * pp - 2
    ft = -np.ones((T, pp), np.int32)
    bt = -np.ones((T, pp), np.int32)
    for d in range(pp):
        for i in range(m):
            ft[i + d, d] = i
            bt[i + 2 * (pp - 1) - d, d] = i
    S = 2 * pp - 1
    tables = {
        "f_mb": ft, "b_mb": bt,
        "f_slot": np.where(ft >= 0, ft % S, 0).astype(np.int32),
        "b_slot": np.where(bt >= 0, bt % S, 0).astype(np.int32),
    }
    return Schedule(tables, T, 2 * m * pp, 2 * T * pp, S, 1)


def simulate_zbh1(pp: int, m: int) -> Schedule:
    """Zero-bubble H1 schedule (reference
    distributed/passes/pipeline_scheduler_pass/pipeline_zero_bubble.py,
    after Qi et al., "Zero Bubble Pipeline Parallelism").

    Backward splits into B (input-grad dL/dx — the inter-stage critical
    path) and W (weight-grad dL/dw — device-local, deferrable). One op per
    device per tick; greedy priorities B > F > W with two memory caps that
    force the paper's uniform-cost timeline:

      * pipeline-depth cap: F may run ahead of B by < pp - d micro-batches
        (the 1F1B warmup profile);
      * stash cap: activations alive F->W stay < 2*(pp-d) - 1 (exactly the
        1F1B per-device stash), so deferring W never costs extra memory.

    Steady state per device is the f,B,W cycle of the ZB-H1 figure; the
    bubble drops to 2*(pp-1) ticks/device vs 1F1B's 3*(pp-1) at equal
    activation memory (uniform op costs; schedule_stats pins both).

    Tables (all [T, pp] int32): op (0 idle / 1 F / 2 B / 3 W), f_mb /
    f_from_x / f_rd / f_st, b_mb / b_rd_h / b_rd_g / b_st_g, w_rd_h /
    w_rd_g, and the arrival writes h_wr_valid/h_wr_slot (activations from
    d-1) + g_wr_valid/g_wr_slot (grads from d+1)."""
    f_end: dict = {}
    b_end: dict = {}
    w_end: dict = {}
    # slot state per device: free lists + high-water marks
    harr_free = [[] for _ in range(pp)]
    harr_max = [0] * pp
    hst_free = [[] for _ in range(pp)]
    hst_max = [0] * pp
    garr_free = [[] for _ in range(pp)]
    garr_max = [0] * pp
    gst_free = [[] for _ in range(pp)]
    gst_max = [0] * pp
    harr_slot: dict = {}    # (d, i) -> h arrival slot on device d
    hst_slot: dict = {}     # (d, i) -> stashed stage-input slot
    garr_slot: dict = {}    # (d, i) -> grad arrival slot
    gst_slot: dict = {}     # (d, i) -> stashed output-grad slot
    # payloads in flight: land at start of tick t+1
    h_incoming: list = [None] * pp
    g_incoming: list = [None] * pp

    names = ("op", "f_mb", "f_from_x", "f_rd", "f_st", "b_mb", "b_rd_h",
             "b_rd_g", "b_st_g", "w_rd_h", "w_rd_g", "h_wr_valid",
             "h_wr_slot", "g_wr_valid", "g_wr_slot")
    rows = {k: [] for k in names}

    def alloc(free, mx, d):
        if free[d]:
            return free[d].pop(), mx
        s = mx[d]
        mx[d] += 1
        return s, mx

    t = 0
    while len(w_end) < pp * m:
        assert t < 10 * (3 * m + 3 * pp), "zbh1 schedule did not converge"
        row = {k: [0] * pp for k in names}
        # 1) arrivals land
        new_h = [None] * pp
        new_g = [None] * pp
        for d in range(pp):
            if h_incoming[d] is not None:
                i = h_incoming[d]
                s, _ = alloc(harr_free, harr_max, d)
                harr_slot[(d, i)] = s
                row["h_wr_valid"][d] = 1
                row["h_wr_slot"][d] = s
                h_incoming[d] = None
            if g_incoming[d] is not None:
                i = g_incoming[d]
                s, _ = alloc(garr_free, garr_max, d)
                garr_slot[(d, i)] = s
                row["g_wr_valid"][d] = 1
                row["g_wr_slot"][d] = s
                g_incoming[d] = None
        # 2) one op per device, priority B > F > W under the two caps
        for d in range(pp):
            fi = sum(1 for (dd, _) in f_end if dd == d)
            bi = sum(1 for (dd, _) in b_end if dd == d)
            wi = sum(1 for (dd, _) in w_end if dd == d)
            # ---- B
            if bi < m:
                i = bi
                grad_ready = (d == pp - 1) or (d, i) in garr_slot
                if (d, i) in f_end and f_end[(d, i)] < t and grad_ready:
                    b_end[(d, i)] = t
                    row["op"][d] = 2
                    row["b_mb"][d] = i
                    row["b_rd_h"][d] = hst_slot[(d, i)]
                    if d < pp - 1:
                        s = garr_slot.pop((d, i))
                        row["b_rd_g"][d] = s
                        garr_free[d].append(s)
                    s, _ = alloc(gst_free, gst_max, d)
                    gst_slot[(d, i)] = s
                    row["b_st_g"][d] = s
                    if d > 0:
                        g_incoming[d - 1] = i
                    continue
            # ---- F
            if fi < m:
                i = fi
                arrived = (d == 0) or (d, i) in harr_slot
                if (fi - bi < pp - d and fi - wi < 2 * (pp - d) - 1
                        and arrived):
                    f_end[(d, i)] = t
                    row["op"][d] = 1
                    row["f_mb"][d] = i
                    if d == 0:
                        row["f_from_x"][d] = 1
                    else:
                        s = harr_slot.pop((d, i))
                        row["f_rd"][d] = s
                        harr_free[d].append(s)
                    s, _ = alloc(hst_free, hst_max, d)
                    hst_slot[(d, i)] = s
                    row["f_st"][d] = s
                    if d < pp - 1:
                        h_incoming[d + 1] = i
                    continue
            # ---- W
            if wi < bi:
                i = wi
                if b_end[(d, i)] < t:
                    w_end[(d, i)] = t
                    row["op"][d] = 3
                    row["w_rd_h"][d] = hst_slot.pop((d, i))
                    hst_free[d].append(row["w_rd_h"][d])
                    row["w_rd_g"][d] = gst_slot.pop((d, i))
                    gst_free[d].append(row["w_rd_g"][d])
        for k in names:
            rows[k].append(row[k])
        t += 1
    tables = {k: np.asarray(v, np.int32) for k, v in rows.items()}
    tables["_sizes"] = np.asarray(
        [max(harr_max) or 1, max(hst_max) or 1, max(garr_max) or 1,
         max(gst_max) or 1], np.int32)
    return Schedule(tables, t, 3 * m * pp, t * pp, max(hst_max), 1)


def simulate_zbvpp(pp: int, v: int, m: int, mem_limit=None) -> Schedule:
    """Zero-bubble virtual-pipeline (ZB-VPP) schedule: the reference's last
    pipeline schedule (distributed/passes/pipeline_scheduler_pass/
    pipeline_zero_bubble.py:150 PipelineZeroBubbleVirtualPipelinePass,
    VScheduleCreator:343 with memory-aware placement
    _estimate_program_mem_usagess:269).

    Combines the interleave topology (v chunks per device at virtual
    stages j = c*pp + d, ring +1 activations / ring -1 grads) with the
    zero-bubble B/W backward split of ZB-H1. Greedy one-op-per-tick
    scheduler, priority B > F > W, with the memory-aware rule: F is gated
    by a per-device stash cap (activations alive F->W), default v*pp
    micro-chunks — a SOFT cap: when a device would otherwise idle (no B,
    no W ready) the F runs anyway, which keeps the schedule deadlock-free
    for every (pp, v, m) while W placement absorbs memory pressure
    everywhere else (the TPU-native analogue of the reference's
    insert-W-to-free-memory pass).

    Bubble fraction 1 - 3*v*m/T is <= ZB-H1's at equal m for every tested
    config (see test_zbvpp.py): the V-topology cuts the fill/drain ramps
    by ~v while the W ops fill the remaining idle ticks.

    Tables (all [T, pp] int32): op (0 idle/1 F/2 B/3 W); F: f_mb, f_c
    (local chunk), f_from_x, f_rd, f_st; B: b_mb, b_c, b_is_head,
    b_is_x, b_rd_h, b_rd_g, b_st_g; W: w_c, w_rd_h, w_rd_g;
    arrival writes h_wr_valid/h_wr_slot + g_wr_valid/g_wr_slot.
    tables['_sizes'] = [n_harr, n_hst, n_garr, n_gst]."""
    V = v * pp
    if mem_limit is None:
        mem_limit = lambda d: v * pp
    elif not callable(mem_limit):
        _ml = int(mem_limit)
        mem_limit = lambda d: _ml
    cap = [mem_limit(d) for d in range(pp)]

    f_end: dict = {}
    b_end: dict = {}
    w_end: dict = {}
    f_next = [0] * V
    b_next = [0] * V
    w_next = [0] * V
    harr_slot: dict = {}    # (j, i) -> arrival slot on device j%pp
    hst_slot: dict = {}     # (j, i) -> stashed stage-input slot
    garr_slot: dict = {}
    gst_slot: dict = {}
    harr_free = [[] for _ in range(pp)]
    harr_max = [0] * pp
    hst_free = [[] for _ in range(pp)]
    hst_max = [0] * pp
    garr_free = [[] for _ in range(pp)]
    garr_max = [0] * pp
    gst_free = [[] for _ in range(pp)]
    gst_max = [0] * pp
    stash_live = [0] * pp
    h_incoming: list = [None] * pp   # (j, i) landing at start of next tick
    g_incoming: list = [None] * pp

    names = ("op", "f_mb", "f_c", "f_from_x", "f_rd", "f_st",
             "b_mb", "b_c", "b_is_head", "b_is_x", "b_rd_h",
             "b_rd_g", "b_st_g", "w_c", "w_rd_h", "w_rd_g",
             "h_wr_valid", "h_wr_slot", "g_wr_valid", "g_wr_slot")
    rows = {k: [] for k in names}

    def alloc(free, mx, d):
        if free[d]:
            return free[d].pop()
        s = mx[d]
        mx[d] += 1
        return s

    t = 0
    while len(w_end) < V * m:
        assert t < 20 * (3 * V * m + 10 * pp), \
            f"zbvpp schedule did not converge (pp={pp}, v={v}, m={m})"
        row = {k: [0] * pp for k in names}
        # 1) payloads permuted last tick land in arrival buffers
        for d in range(pp):
            if h_incoming[d] is not None:
                j, i = h_incoming[d]
                s = alloc(harr_free, harr_max, d)
                harr_slot[(j, i)] = s
                row["h_wr_valid"][d] = 1
                row["h_wr_slot"][d] = s
                h_incoming[d] = None
            if g_incoming[d] is not None:
                j, i = g_incoming[d]
                s = alloc(garr_free, garr_max, d)
                garr_slot[(j, i)] = s
                row["g_wr_valid"][d] = 1
                row["g_wr_slot"][d] = s
                g_incoming[d] = None
        # 2) one op per device: B > F (memory-gated, soft) > W
        for d in range(pp):
            stages = range(d, V, pp)
            Bs = [j for j in stages if b_next[j] < m
                  and f_end.get((j, b_next[j]), t) < t
                  and (j == V - 1 or (j, b_next[j]) in garr_slot)]
            if Bs:
                j = max(Bs)
                i = b_next[j]
                b_end[(j, i)] = t
                b_next[j] += 1
                row["op"][d] = 2
                row["b_mb"][d] = i
                row["b_c"][d] = j // pp
                row["b_rd_h"][d] = hst_slot[(j, i)]
                if j == V - 1:
                    row["b_is_head"][d] = 1
                else:
                    s = garr_slot.pop((j, i))
                    row["b_rd_g"][d] = s
                    garr_free[d].append(s)
                if j == 0:
                    row["b_is_x"][d] = 1
                s = alloc(gst_free, gst_max, d)
                gst_slot[(j, i)] = s
                row["b_st_g"][d] = s
                if j > 0:
                    g_incoming[(d - 1) % pp] = (j - 1, i)
                continue
            Fs = [j for j in stages if f_next[j] < m
                  and (j == 0 or (j, f_next[j]) in harr_slot)]
            Ws = [j for j in stages if w_next[j] < b_next[j]
                  and b_end[(j, w_next[j])] < t]
            if Fs and (stash_live[d] < cap[d] or not Ws):
                j = max(Fs)
                i = f_next[j]
                f_end[(j, i)] = t
                f_next[j] += 1
                stash_live[d] += 1
                row["op"][d] = 1
                row["f_mb"][d] = i
                row["f_c"][d] = j // pp
                if j == 0:
                    row["f_from_x"][d] = 1
                else:
                    s = harr_slot.pop((j, i))
                    row["f_rd"][d] = s
                    harr_free[d].append(s)
                s = alloc(hst_free, hst_max, d)
                hst_slot[(j, i)] = s
                row["f_st"][d] = s
                if j < V - 1:
                    h_incoming[(d + 1) % pp] = (j + 1, i)
                continue
            if Ws:
                j = min(Ws, key=lambda jj: (w_next[jj], jj))
                i = w_next[j]
                w_end[(j, i)] = t
                w_next[j] += 1
                stash_live[d] -= 1
                row["op"][d] = 3
                row["w_c"][d] = j // pp
                row["w_rd_h"][d] = hst_slot.pop((j, i))
                hst_free[d].append(row["w_rd_h"][d])
                row["w_rd_g"][d] = gst_slot.pop((j, i))
                gst_free[d].append(row["w_rd_g"][d])
        for k in names:
            rows[k].append(row[k])
        t += 1
    tables = {k: np.asarray(val, np.int32) for k, val in rows.items()}
    tables["_sizes"] = np.asarray(
        [max(harr_max) or 1, max(hst_max) or 1, max(garr_max) or 1,
         max(gst_max) or 1], np.int32)
    return Schedule(tables, t, 3 * V * m, t * pp, max(hst_max), 1)


def schedule_stats(pp: int, m: int, schedule: str = "gpipe", v: int = 1):
    """Step-count accounting used by the bubble tests: slots are uniform
    stage-compute units; bubble = idle fraction of the fwd+bwd timeline."""
    if schedule == "gpipe":
        ticks = 2 * (m + pp - 1)        # fwd scan + autodiff mirror
        busy = 2 * m
        return {"total_ticks": ticks, "bubble": 1 - busy / ticks,
                "stash_micro_batches": m}
    if schedule == "interleave":
        sim = simulate_interleave(pp, v, m)
        busy_per_dev = v * m            # fwd; autodiff mirrors the timeline
        return {"total_ticks": 2 * sim.total_ticks,
                "bubble": 1 - busy_per_dev / sim.total_ticks,
                # autodiff saves one stage-input residual per tick: ~v*m
                # per device (chunks are 1/v the layers, so in LAYER units
                # this is ~m, same as gpipe — but in micro-batch-input
                # units it is v*m)
                "stash_micro_batches": v * m}
    if schedule == "1f1b":
        sim = simulate_1f1b(pp, m)
        return {"total_ticks": sim.total_ticks,
                "bubble": 1 - m / sim.total_ticks,
                "stash_micro_batches": sim.stash_size}
    if schedule == "zbh1":
        sim = simulate_zbh1(pp, m)
        # single-op ticks: busy = 3m of T per device
        return {"total_ticks": sim.total_ticks,
                "bubble": 1 - 3 * m / sim.total_ticks,
                "bubble_ticks_per_device": sim.total_ticks - 3 * m,
                "stash_micro_batches": sim.stash_size}
    if schedule == "zbvpp":
        sim = simulate_zbvpp(pp, v, m)
        # busy = 3 ops per micro-chunk: 3*v*m of T per device
        return {"total_ticks": sim.total_ticks,
                "bubble": 1 - 3 * v * m / sim.total_ticks,
                "bubble_ticks_per_device": sim.total_ticks - 3 * v * m,
                "stash_micro_batches": sim.stash_size}
    raise ValueError(f"unknown schedule {schedule!r}")


from paddle_tpu.parallel.pipeline import (  # noqa: E402
    chain_stages, manual_shard_map,
)


# ----------------------------------------------------------- interleave apply

def interleave_permutation(pp: int, v: int) -> list:
    """Device-major stacking order for interleaved params: position
    p = d*v + c holds virtual stage j = c*pp + d. Stored this way, a
    P('pp')-sharded [V,...] stack keeps each device's v chunks LOCAL —
    no per-step resharding (layer-order storage would move nearly every
    block parameter over ICI each step)."""
    return [c * pp + d for d in range(pp) for c in range(v)]


def pipeline_apply_interleave(stage_fn: Callable[[Any, Any], Any],
                              stacked_params, x_micro, mesh: Mesh,
                              v: int = 2, num_micro: int | None = None,
                              remat: bool = False, layout: str = "layer"):
    """Differentiable interleaved-VPP pipeline: like
    pipeline.pipeline_apply but each device owns v chunks of the stage
    stack at virtual stages c*pp+d, cutting the bubble by ~v.

    stacked_params leaves have leading dim V = v*pp; layout='layer' means
    index L = virtual stage L (convenient, but pays a reshard per step on a
    P('pp')-sharded stack), layout='device' means the caller pre-permuted
    with interleave_permutation (device-major; sharded stacks stay local).
    Stage output shape must equal its input shape.
    Returns [num_micro, ...] last-stage outputs."""
    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    npp = mesh.shape["pp"]
    if num_micro is None:
        num_micro = x_micro.shape[0]
    leaf = jax.tree_util.tree_leaves(stacked_params)[0]
    V = leaf.shape[0]
    assert V == v * npp, f"stage count {V} != v*pp = {v}*{npp}"
    sim = simulate_interleave(npp, v, num_micro)
    T = sim.total_ticks
    A = max(sim.arrival_slots, 1)
    tab = {k: jnp.asarray(val) for k, val in sim.tables.items()}

    if layout == "layer":
        perm = np.asarray(interleave_permutation(npp, v))
        re = jax.tree_util.tree_map(lambda a: a[perm], stacked_params)
    elif layout == "device":
        re = stacked_params
    else:
        raise ValueError(f"unknown layout {layout!r}")

    def per_device(params_local, x):
        d = lax.axis_index("pp")
        # local slice of the device-major [V,...] stack = this device's v
        # chunks, chunk c at local index c
        mb_shape = x.shape[1:]

        def tick(carry, trow):
            arr_buf, outbuf, incoming = carry
            # land last tick's permuted payload
            wr = jnp.where(trow["wr_valid"][d] > 0,
                           lax.dynamic_update_index_in_dim(
                               arr_buf, incoming, trow["wr_slot"][d], 0),
                           arr_buf)
            arr_buf = wr
            j = trow["work_j"][d]
            mb = trow["work_mb"][d]
            valid = trow["valid"][d] > 0
            h_x = lax.dynamic_index_in_dim(x, jnp.clip(mb, 0, num_micro - 1),
                                           0, keepdims=False)
            h_a = lax.dynamic_index_in_dim(arr_buf, trow["rd_slot"][d], 0,
                                           keepdims=False)
            h = jnp.where(trow["from_x"][d] > 0, h_x, h_a)
            chunk = jnp.clip(j // npp, 0, v - 1)
            p_c = jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, chunk, 0,
                                                   keepdims=False),
                params_local)
            y = stage_fn(p_c, h)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last virtual stage writes its output
            is_out = valid & (j == V - 1)
            upd = lax.dynamic_update_index_in_dim(
                outbuf, y, jnp.clip(mb, 0, num_micro - 1), 0)
            outbuf = jnp.where(is_out, upd, outbuf)
            nxt = lax.ppermute(y, "pp", [(i, (i + 1) % npp)
                                         for i in range(npp)])
            return (arr_buf, outbuf, nxt), None

        z = jnp.zeros(mb_shape, x.dtype)
        init = (jnp.zeros((A,) + mb_shape, x.dtype),
                jnp.zeros((num_micro,) + mb_shape, x.dtype),
                z)
        (_, outbuf, _), _ = lax.scan(tick, init, tab)
        return outbuf

    mapped = manual_shard_map(
        per_device, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), re), P()),
        out_specs=P("pp"),
        axis_names=frozenset({"pp"}),
    )
    out_all = mapped(re, x_micro)
    # P('pp') concatenation: only the last device's block holds outputs
    return out_all[(npp - 1) * num_micro:]


# ------------------------------------------------------------- fused 1F1B

def pipeline_1f1b(stage_fn: Callable[[Any, Any], Any], stacked_params,
                  x_micro, labels_micro,
                  head_fn: Callable[[Any, Any, Any], Any], head_params,
                  mesh: Mesh, num_micro: int | None = None):
    """Fused forward+backward with the Megatron 1F1B schedule
    (reference pipeline_parallel.py:684 warmup/steady/cooldown).

    Per tick every device runs one F and one B work slot (masked outside
    the steady state). Backward recomputes the stage under jax.vjp from a
    stashed stage input — the stash holds at most 2*pp-1 micro-batches (the
    1F1B memory profile; GPipe autodiff stashes all m). The last stage
    computes loss locally (head_fn) so backward starts while earlier
    micro-batches are still forwarding.

    head_fn(head_params, y, labels) -> scalar mean loss for ONE micro-batch.
    Returns (mean_loss, grads_stacked, grads_head, dx_micro). NOT
    differentiable — it already IS the backward (use its outputs directly).
    """
    npp = mesh.shape["pp"]
    if num_micro is None:
        num_micro = x_micro.shape[0]
    m = num_micro
    total_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert total_stages % npp == 0
    sim = simulate_1f1b(npp, m)
    S = sim.stash_size
    tab = {k: jnp.asarray(val) for k, val in sim.tables.items()}
    fwd_perm = [(i, (i + 1) % npp) for i in range(npp)]
    bwd_perm = [(i, (i - 1) % npp) for i in range(npp)]

    def per_device(params_local, head_p, x, labels):
        d = lax.axis_index("pp")
        is_first = d == 0
        is_last = d == npp - 1
        mb_shape = x.shape[1:]
        z = jnp.zeros(mb_shape, x.dtype)

        def dev_fn(pl, h):
            """This device's stage = chain of its s_local blocks."""
            return chain_stages(stage_fn, pl, h)

        def tick(carry, trow):
            (stash, f_in, g_in, gparams, ghead, loss_acc, dx_buf) = carry

            # ---------------- F slot
            f_mb = trow["f_mb"][d]
            f_valid = f_mb >= 0
            mb_c = jnp.clip(f_mb, 0, m - 1)
            h_x = lax.dynamic_index_in_dim(x, mb_c, 0, keepdims=False)
            h = jnp.where(is_first, h_x, f_in)
            stash = jnp.where(
                f_valid,
                lax.dynamic_update_index_in_dim(stash, h, trow["f_slot"][d],
                                                0),
                stash)
            y = dev_fn(params_local, h)
            y = jnp.where(f_valid, y, jnp.zeros_like(y))

            # ---------------- B slot (recompute + vjp from stashed input)
            b_mb = trow["b_mb"][d]
            b_valid = b_mb >= 0
            bmb_c = jnp.clip(b_mb, 0, m - 1)
            h_b = lax.dynamic_index_in_dim(stash, trow["b_slot"][d], 0,
                                           keepdims=False)
            y_b, stage_vjp = jax.vjp(dev_fn, params_local, h_b)
            lbl = lax.dynamic_index_in_dim(labels, bmb_c, 0, keepdims=False)

            # head fwd+bwd only where it contributes: last device, valid B.
            # Inside shard_map the predicate is device-local, so lax.cond
            # genuinely skips the head (often the most expensive op —
            # vocab-sized logits) on the other pp-1 devices every tick.
            def head_branch(op):
                hp, yy, ll = op
                loss_i, (ghp, gyl) = jax.value_and_grad(
                    lambda hp_, yy_: head_fn(hp_, yy_, ll),
                    argnums=(0, 1))(hp, yy)
                # 1/m: the pipeline loss is the mean over micro-batches
                return loss_i / m, jax.tree_util.tree_map(
                    lambda g: g / m, ghp), gyl / m

            def skip_branch(op):
                hp, yy, ll = op
                return (jnp.zeros((), jnp.float32),
                        jax.tree_util.tree_map(jnp.zeros_like, hp),
                        jnp.zeros_like(yy))

            loss_i, g_head_i, gy_last = lax.cond(
                b_valid & is_last, head_branch, skip_branch,
                (head_p, y_b, lbl))
            gy = jnp.where(is_last, gy_last, g_in)
            gp_i, gh = stage_vjp(gy)
            mask = jnp.where(b_valid, 1.0, 0.0)
            gparams = jax.tree_util.tree_map(
                lambda acc, g: acc + mask * g, gparams, gp_i)
            ghead = jax.tree_util.tree_map(jnp.add, ghead, g_head_i)
            loss_acc = loss_acc + loss_i
            gh = jnp.where(b_valid, gh, jnp.zeros_like(gh))
            dx_upd = lax.dynamic_update_index_in_dim(dx_buf, gh, bmb_c, 0)
            dx_buf = jnp.where(b_valid & is_first, dx_upd, dx_buf)

            f_in_next = lax.ppermute(y, "pp", fwd_perm)
            g_in_next = lax.ppermute(gh, "pp", bwd_perm)
            return (stash, f_in_next, g_in_next, gparams, ghead, loss_acc,
                    dx_buf), None

        init = (
            jnp.zeros((S,) + mb_shape, x.dtype),                # stash
            z,                                                  # f_in
            z,                                                  # g_in
            jax.tree_util.tree_map(jnp.zeros_like, params_local),
            jax.tree_util.tree_map(jnp.zeros_like, head_p),
            jnp.zeros((), jnp.float32),
            jnp.zeros((m,) + mb_shape, x.dtype),
        )
        (stash, _, _, gparams, ghead, loss_acc, dx_buf), _ = lax.scan(
            tick, init, tab)
        # replicate the cross-device results: loss/ghead live on the last
        # device, dx on the first — psum of masked values replicates them
        last_mask = jnp.where(is_last, 1.0, 0.0)
        first_mask = jnp.where(is_first, 1.0, 0.0)
        loss = lax.psum(loss_acc * last_mask, "pp")
        ghead = jax.tree_util.tree_map(
            lambda g: lax.psum(g * last_mask, "pp"), ghead)
        dx = lax.psum(dx_buf * first_mask, "pp")
        return loss, gparams, ghead, dx

    mapped = manual_shard_map(
        per_device, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), stacked_params),
                  jax.tree_util.tree_map(lambda _: P(), head_params),
                  P(), P()),
        out_specs=(P(),
                   jax.tree_util.tree_map(lambda _: P("pp"), stacked_params),
                   jax.tree_util.tree_map(lambda _: P(), head_params),
                   P()),
        axis_names=frozenset({"pp"}),
    )
    return mapped(stacked_params, head_params, x_micro, labels_micro)


# ------------------------------------------------------------- zero-bubble H1

def pipeline_zbh1(stage_fn: Callable[[Any, Any], Any], stacked_params,
                  x_micro, labels_micro,
                  head_fn: Callable[[Any, Any, Any], Any], head_params,
                  mesh: Mesh, num_micro: int | None = None):
    """Fused pipeline step with the ZB-H1 zero-bubble schedule (reference
    distributed/passes/pipeline_scheduler_pass/pipeline_zero_bubble.py).

    Same contract as pipeline_1f1b: returns (mean_loss, grads_stacked,
    grads_head, dx_micro) and is NOT differentiable (it IS the backward).

    Backward is split at the vjp level: the B op computes only dL/dx
    (jax.vjp w.r.t. the stage input — the inter-device critical path; its
    output-grad cotangent is stashed), and the W op computes dL/dw later
    from the stashed (input, cotangent) pair, filling what 1F1B leaves as
    bubble. Each of B and W re-linearizes the stage from the stashed
    input (one recompute each — the fused-schedule analogue of
    recompute-everything 1F1B, which pays one; the extra forward is the
    price of O(1) inter-op state, and the schedule's 1/3 bubble reduction
    is the win when pp is deep). One op runs per tick via lax.switch with
    a device-varying index — real branching, so a tick costs its op, not
    the sum of all three."""
    npp = mesh.shape["pp"]
    if num_micro is None:
        num_micro = x_micro.shape[0]
    m = num_micro
    total_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    assert total_stages % npp == 0
    sim = simulate_zbh1(npp, m)
    sizes = sim.tables["_sizes"]
    n_harr, n_hst, n_garr, n_gst = (int(x) for x in sizes)
    tab = {k: jnp.asarray(val) for k, val in sim.tables.items()
           if k != "_sizes"}
    fwd_perm = [(i, (i + 1) % npp) for i in range(npp)]
    bwd_perm = [(i, (i - 1) % npp) for i in range(npp)]

    def per_device(params_local, head_p, x, labels):
        d = lax.axis_index("pp")
        is_first = d == 0
        is_last = d == npp - 1
        mb_shape = x.shape[1:]
        z = jnp.zeros(mb_shape, x.dtype)

        def dev_fn(pl, h):
            return chain_stages(stage_fn, pl, h)

        def tick(carry, trow):
            (h_arr, h_st, g_arr, g_st, gparams, ghead, loss_acc, dx_buf,
             h_in, g_in) = carry
            # arrivals land first (payloads permuted last tick)
            h_arr = jnp.where(
                trow["h_wr_valid"][d] > 0,
                lax.dynamic_update_index_in_dim(h_arr, h_in,
                                                trow["h_wr_slot"][d], 0),
                h_arr)
            g_arr = jnp.where(
                trow["g_wr_valid"][d] > 0,
                lax.dynamic_update_index_in_dim(g_arr, g_in,
                                                trow["g_wr_slot"][d], 0),
                g_arr)

            op = trow["op"][d]

            def f_branch(c):
                (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb) = c
                mb = jnp.clip(trow["f_mb"][d], 0, m - 1)
                h_x = lax.dynamic_index_in_dim(x, mb, 0, keepdims=False)
                h_a = lax.dynamic_index_in_dim(h_arr, trow["f_rd"][d], 0,
                                               keepdims=False)
                h = jnp.where(trow["f_from_x"][d] > 0, h_x, h_a)
                h_st = lax.dynamic_update_index_in_dim(
                    h_st, h, trow["f_st"][d], 0)
                y = dev_fn(params_local, h)
                return (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb,
                        y, jnp.zeros_like(y))

            def b_branch(c):
                (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb) = c
                mb = jnp.clip(trow["b_mb"][d], 0, m - 1)
                h_b = lax.dynamic_index_in_dim(h_st, trow["b_rd_h"][d], 0,
                                               keepdims=False)
                y_b, vjp_h = jax.vjp(lambda hh: dev_fn(params_local, hh),
                                     h_b)
                lbl = lax.dynamic_index_in_dim(labels, mb, 0,
                                               keepdims=False)

                def head_branch(op_):
                    hp, yy, ll = op_
                    loss_i, (ghp, gyl) = jax.value_and_grad(
                        lambda hp_, yy_: head_fn(hp_, yy_, ll),
                        argnums=(0, 1))(hp, yy)
                    return loss_i / m, jax.tree_util.tree_map(
                        lambda g: g / m, ghp), gyl / m

                def skip_branch(op_):
                    hp, yy, _ = op_
                    return (jnp.zeros((), jnp.float32),
                            jax.tree_util.tree_map(jnp.zeros_like, hp),
                            jnp.zeros_like(yy))

                loss_i, g_head_i, gy_last = lax.cond(
                    is_last, head_branch, skip_branch, (head_p, y_b, lbl))
                g_a = lax.dynamic_index_in_dim(g_arr, trow["b_rd_g"][d], 0,
                                               keepdims=False)
                gy = jnp.where(is_last, gy_last, g_a)
                # stash the cotangent for this micro-batch's W op
                g_st = lax.dynamic_update_index_in_dim(
                    g_st, gy, trow["b_st_g"][d], 0)
                (gh,) = vjp_h(gy)
                gh_new = jax.tree_util.tree_map(jnp.add, gh_, g_head_i)
                la = la + loss_i
                dx_upd = lax.dynamic_update_index_in_dim(dxb, gh, mb, 0)
                dxb = jnp.where(is_first, dx_upd, dxb)
                return (h_arr, h_st, g_arr, g_st, gp, gh_new, la, dxb,
                        jnp.zeros_like(gh), gh)

            def w_branch(c):
                (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb) = c
                h_w = lax.dynamic_index_in_dim(h_st, trow["w_rd_h"][d], 0,
                                               keepdims=False)
                gy_w = lax.dynamic_index_in_dim(g_st, trow["w_rd_g"][d], 0,
                                                keepdims=False)
                _, vjp_p = jax.vjp(lambda pp_: dev_fn(pp_, h_w),
                                   params_local)
                (gp_i,) = vjp_p(gy_w)
                gp = jax.tree_util.tree_map(jnp.add, gp, gp_i)
                return (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb,
                        z, z)

            def idle_branch(c):
                return c + (z, z)

            (h_arr, h_st, g_arr, g_st, gparams, ghead, loss_acc, dx_buf,
             y_send, gh_send) = lax.switch(
                jnp.clip(op, 0, 3),
                [idle_branch, f_branch, b_branch, w_branch],
                (h_arr, h_st, g_arr, g_st, gparams, ghead, loss_acc,
                 dx_buf))

            h_in_next = lax.ppermute(y_send, "pp", fwd_perm)
            g_in_next = lax.ppermute(gh_send, "pp", bwd_perm)
            return (h_arr, h_st, g_arr, g_st, gparams, ghead, loss_acc,
                    dx_buf, h_in_next, g_in_next), None

        zeros_like_local = lambda tree: jax.tree_util.tree_map(
            jnp.zeros_like, tree)
        init = (
            jnp.zeros((n_harr,) + mb_shape, x.dtype),
            jnp.zeros((n_hst,) + mb_shape, x.dtype),
            jnp.zeros((n_garr,) + mb_shape, x.dtype),
            jnp.zeros((n_gst,) + mb_shape, x.dtype),
            zeros_like_local(params_local),
            zeros_like_local(head_p),
            jnp.zeros((), jnp.float32),
            jnp.zeros((m,) + mb_shape, x.dtype),
            z,
            z,
        )
        (_, _, _, _, gparams, ghead, loss_acc, dx_buf, _, _), _ = lax.scan(
            tick, init, tab)
        last_mask = jnp.where(is_last, 1.0, 0.0)
        first_mask = jnp.where(is_first, 1.0, 0.0)
        loss = lax.psum(loss_acc * last_mask, "pp")
        ghead = jax.tree_util.tree_map(
            lambda g: lax.psum(g * last_mask, "pp"), ghead)
        dx = lax.psum(dx_buf * first_mask, "pp")
        return loss, gparams, ghead, dx

    mapped = manual_shard_map(
        per_device, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), stacked_params),
                  jax.tree_util.tree_map(lambda _: P(), head_params),
                  P(), P()),
        out_specs=(P(),
                   jax.tree_util.tree_map(lambda _: P("pp"), stacked_params),
                   jax.tree_util.tree_map(lambda _: P(), head_params),
                   P()),
        axis_names=frozenset({"pp"}),
    )
    return mapped(stacked_params, head_params, x_micro, labels_micro)


# ----------------------------------------------------- zero-bubble VPP (ZBVPP)

def pipeline_zbvpp(stage_fn: Callable[[Any, Any], Any], stacked_params,
                   x_micro, labels_micro,
                   head_fn: Callable[[Any, Any, Any], Any], head_params,
                   mesh: Mesh, v: int = 2, num_micro: int | None = None,
                   mem_limit=None, layout: str = "layer"):
    """Fused pipeline step with the zero-bubble virtual-pipeline schedule
    (reference pipeline_zero_bubble.py:150 ZBVPP — the interleave topology
    of VPP crossed with the B/W backward split of ZB-H1).

    stacked_params leaves have leading dim V = v*pp: virtual stage j runs
    on device j % pp as that device's chunk j // pp. layout='layer' means
    index L = virtual stage L (grads returned in the same order);
    layout='device' means the caller pre-permuted with
    interleave_permutation. Stage output shape must equal its input shape
    (activations ride one ring). head_fn(head_params, y, labels) -> scalar
    mean loss for ONE micro-batch, evaluated on the last device only.

    Same contract as pipeline_zbh1: returns (mean_loss, grads_stacked,
    grads_head, dx_micro) and is NOT differentiable (it IS the backward).
    The B op computes dL/dx (inter-device critical path), the W op fills
    bubble ticks with the deferred dL/dw from the stashed (input,
    cotangent) pair — each re-linearizes its chunk from the stash, so the
    schedule trades one extra chunk forward per op for the ~v-fold
    shorter ramps AND the W-filled steady state (bubble fraction <=
    ZB-H1's at equal m; see simulate_zbvpp)."""
    npp = mesh.shape["pp"]
    if num_micro is None:
        num_micro = x_micro.shape[0]
    m = num_micro
    leaf = jax.tree_util.tree_leaves(stacked_params)[0]
    V = leaf.shape[0]
    assert V == v * npp, f"stage count {V} != v*pp = {v}*{npp}"
    sim = simulate_zbvpp(npp, v, m, mem_limit=mem_limit)
    sizes = sim.tables["_sizes"]
    n_harr, n_hst, n_garr, n_gst = (int(s) for s in sizes)
    tab = {k: jnp.asarray(val) for k, val in sim.tables.items()
           if k != "_sizes"}
    fwd_perm = [(i, (i + 1) % npp) for i in range(npp)]
    bwd_perm = [(i, (i - 1) % npp) for i in range(npp)]

    if layout == "layer":
        perm = np.asarray(interleave_permutation(npp, v))
        re = jax.tree_util.tree_map(lambda a: a[perm], stacked_params)
    elif layout == "device":
        re = stacked_params
    else:
        raise ValueError(f"unknown layout {layout!r}")

    def per_device(params_local, head_p, x, labels):
        d = lax.axis_index("pp")
        is_first = d == 0
        is_last = d == npp - 1
        mb_shape = x.shape[1:]
        z = jnp.zeros(mb_shape, x.dtype)

        def chunk_params(pl, c):
            return jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
                pl)

        def acc_chunk(acc_tree, g_tree, c):
            return jax.tree_util.tree_map(
                lambda acc, g: lax.dynamic_update_index_in_dim(
                    acc,
                    lax.dynamic_index_in_dim(acc, c, 0, keepdims=False) + g,
                    c, 0),
                acc_tree, g_tree)

        def tick(carry, trow):
            (h_arr, h_st, g_arr, g_st, gparams, ghead, loss_acc, dx_buf,
             h_in, g_in) = carry
            # arrivals land first (payloads permuted last tick)
            h_arr = jnp.where(
                trow["h_wr_valid"][d] > 0,
                lax.dynamic_update_index_in_dim(h_arr, h_in,
                                                trow["h_wr_slot"][d], 0),
                h_arr)
            g_arr = jnp.where(
                trow["g_wr_valid"][d] > 0,
                lax.dynamic_update_index_in_dim(g_arr, g_in,
                                                trow["g_wr_slot"][d], 0),
                g_arr)

            op = trow["op"][d]

            def f_branch(c):
                (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb) = c
                mb = jnp.clip(trow["f_mb"][d], 0, m - 1)
                h_x = lax.dynamic_index_in_dim(x, mb, 0, keepdims=False)
                h_a = lax.dynamic_index_in_dim(h_arr, trow["f_rd"][d], 0,
                                               keepdims=False)
                h = jnp.where(trow["f_from_x"][d] > 0, h_x, h_a)
                h_st = lax.dynamic_update_index_in_dim(
                    h_st, h, trow["f_st"][d], 0)
                p_c = chunk_params(params_local, trow["f_c"][d])
                y = stage_fn(p_c, h)
                return (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb,
                        y, jnp.zeros_like(y))

            def b_branch(c):
                (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb) = c
                mb = jnp.clip(trow["b_mb"][d], 0, m - 1)
                h_b = lax.dynamic_index_in_dim(h_st, trow["b_rd_h"][d], 0,
                                               keepdims=False)
                p_c = chunk_params(params_local, trow["b_c"][d])
                y_b, vjp_h = jax.vjp(lambda hh: stage_fn(p_c, hh), h_b)
                lbl = lax.dynamic_index_in_dim(labels, mb, 0,
                                               keepdims=False)

                def head_branch(op_):
                    hp, yy, ll = op_
                    loss_i, (ghp, gyl) = jax.value_and_grad(
                        lambda hp_, yy_: head_fn(hp_, yy_, ll),
                        argnums=(0, 1))(hp, yy)
                    return loss_i / m, jax.tree_util.tree_map(
                        lambda g: g / m, ghp), gyl / m

                def skip_branch(op_):
                    hp, yy, _ = op_
                    return (jnp.zeros((), jnp.float32),
                            jax.tree_util.tree_map(jnp.zeros_like, hp),
                            jnp.zeros_like(yy))

                loss_i, g_head_i, gy_head = lax.cond(
                    trow["b_is_head"][d] > 0, head_branch, skip_branch,
                    (head_p, y_b, lbl))
                g_a = lax.dynamic_index_in_dim(g_arr, trow["b_rd_g"][d], 0,
                                               keepdims=False)
                gy = jnp.where(trow["b_is_head"][d] > 0, gy_head, g_a)
                # stash the cotangent for this micro-chunk's W op
                g_st = lax.dynamic_update_index_in_dim(
                    g_st, gy, trow["b_st_g"][d], 0)
                (gh,) = vjp_h(gy)
                gh_new = jax.tree_util.tree_map(jnp.add, gh_, g_head_i)
                la = la + loss_i
                dx_upd = lax.dynamic_update_index_in_dim(dxb, gh, mb, 0)
                dxb = jnp.where(trow["b_is_x"][d] > 0, dx_upd, dxb)
                return (h_arr, h_st, g_arr, g_st, gp, gh_new, la, dxb,
                        jnp.zeros_like(gh), gh)

            def w_branch(c):
                (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb) = c
                h_w = lax.dynamic_index_in_dim(h_st, trow["w_rd_h"][d], 0,
                                               keepdims=False)
                gy_w = lax.dynamic_index_in_dim(g_st, trow["w_rd_g"][d], 0,
                                                keepdims=False)
                p_c = chunk_params(params_local, trow["w_c"][d])
                _, vjp_p = jax.vjp(lambda pc: stage_fn(pc, h_w), p_c)
                (gp_i,) = vjp_p(gy_w)
                gp = acc_chunk(gp, gp_i, trow["w_c"][d])
                return (h_arr, h_st, g_arr, g_st, gp, gh_, la, dxb,
                        z, z)

            def idle_branch(c):
                return c + (z, z)

            (h_arr, h_st, g_arr, g_st, gparams, ghead, loss_acc, dx_buf,
             y_send, gh_send) = lax.switch(
                jnp.clip(op, 0, 3),
                [idle_branch, f_branch, b_branch, w_branch],
                (h_arr, h_st, g_arr, g_st, gparams, ghead, loss_acc,
                 dx_buf))

            h_in_next = lax.ppermute(y_send, "pp", fwd_perm)
            g_in_next = lax.ppermute(gh_send, "pp", bwd_perm)
            return (h_arr, h_st, g_arr, g_st, gparams, ghead, loss_acc,
                    dx_buf, h_in_next, g_in_next), None

        zeros_like_local = lambda tree: jax.tree_util.tree_map(
            jnp.zeros_like, tree)
        init = (
            jnp.zeros((n_harr,) + mb_shape, x.dtype),
            jnp.zeros((n_hst,) + mb_shape, x.dtype),
            jnp.zeros((n_garr,) + mb_shape, x.dtype),
            jnp.zeros((n_gst,) + mb_shape, x.dtype),
            zeros_like_local(params_local),
            zeros_like_local(head_p),
            jnp.zeros((), jnp.float32),
            jnp.zeros((m,) + mb_shape, x.dtype),
            z,
            z,
        )
        (_, _, _, _, gparams, ghead, loss_acc, dx_buf, _, _), _ = lax.scan(
            tick, init, tab)
        last_mask = jnp.where(is_last, 1.0, 0.0)
        first_mask = jnp.where(is_first, 1.0, 0.0)
        loss = lax.psum(loss_acc * last_mask, "pp")
        ghead = jax.tree_util.tree_map(
            lambda g: lax.psum(g * last_mask, "pp"), ghead)
        dx = lax.psum(dx_buf * first_mask, "pp")
        return loss, gparams, ghead, dx

    mapped = manual_shard_map(
        per_device, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), re),
                  jax.tree_util.tree_map(lambda _: P(), head_params),
                  P(), P()),
        out_specs=(P(),
                   jax.tree_util.tree_map(lambda _: P("pp"), re),
                   jax.tree_util.tree_map(lambda _: P(), head_params),
                   P()),
        axis_names=frozenset({"pp"}),
    )
    loss, g_dev, ghead, dx = mapped(re, head_params, x_micro, labels_micro)
    if layout == "layer":
        # device-major grads back to layer order: stage perm[p] sits at
        # position p, so scatter back with the inverse permutation
        inv = np.argsort(perm)
        g_dev = jax.tree_util.tree_map(lambda a: a[inv], g_dev)
    return loss, g_dev, ghead, dx
