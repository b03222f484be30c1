"""Pipeline parallelism over the 'pp' mesh axis.

Reference: python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py
(PipelineParallel:242, 1F1B forward_backward_pipeline:684, interleave :1308),
p2p via batch_isend_irecv (pp_utils/p2p_communication.py:52), and the static
multi-Job Plan schedules (passes/pipeline_scheduler_pass/).

TPU-native design: the whole pipeline — all stages, all micro-batches — is ONE
compiled XLA program. Stage parameters are stacked on a leading axis sharded
over 'pp'; the schedule is a lax.scan whose per-tick body computes every
stage in parallel (SPMD) and rotates activations to the next stage with
lax.ppermute over ICI (collective_permute). Autodiff through scan+ppermute
yields the backward pipeline automatically — no hand-written 1F1B state
machine, no p2p bookkeeping, and XLA overlaps the permute with compute.
Schedule shape = GPipe (fill + steady + drain in one scan); the activation
working set is bounded by num_micro live micro-batch buffers per stage.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

def manual_shard_map(f, **kw):
    """The one spelling of every manually-mapped region of the repo
    (pipeline schedules, ring attention, the serving TP kernels):
    jax.shard_map with the varying-manual-axes check off. `axis_names`
    are the manual axes (the rest stay GSPMD-auto). Jitted, because JAX
    0.9 refuses the eager call of a partially-manual map whose out_specs
    leave an auto axis unnamed, and accepts the same map under jit."""
    return jax.jit(jax.shard_map(f, check_vma=False, **kw))


def chain_stages(stage_fn, stacked_local, h):
    """Run h through stage_fn once per leading-axis entry of stacked_local
    (scan; length-1 fast path). Shared by pipeline_apply, the 1F1B dev_fn,
    and the GPT interleave chunk chain."""
    n = jax.tree_util.tree_leaves(stacked_local)[0].shape[0]
    if n == 1:
        return stage_fn(jax.tree_util.tree_map(lambda a: a[0],
                                               stacked_local), h)
    h, _ = lax.scan(lambda c, p: (stage_fn(p, c), None), h, stacked_local)
    return h


def stack_stage_params(param_dicts):
    """[{name: array}, ...] per stage -> {name: array[S, ...]} stacked."""
    keys = list(param_dicts[0].keys())
    return {k: jnp.stack([d[k] for d in param_dicts]) for k in keys}


def pipeline_apply(stage_fn: Callable[[Any, Any], Any], stacked_params,
                   x_micro, mesh: Mesh, num_micro: int | None = None,
                   remat: bool = False):
    """Run micro-batches through the stage pipeline.

    stage_fn(stage_params, h) -> h : one stage's computation (may itself be
        tp/dp-sharded; those mesh axes stay in GSPMD-auto mode).
    stacked_params: pytree with leading stage axis on every leaf
        (total_stages = npp * stages_per_device).
    x_micro: [num_micro, micro_batch, ...] inputs (replicated w.r.t. 'pp').

    remat=True rematerializes each stage call in backward (the reference's
    recompute-in-pipeline combination), bounding activation memory to one
    micro-batch per stage — the GPipe memory profile with recompute, which
    is what 1F1B buys; the schedule itself stays GPipe-shaped.

    Returns [num_micro, micro_batch, ...] last-stage outputs.
    """
    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    npp = mesh.shape["pp"]
    if num_micro is None:
        num_micro = x_micro.shape[0]
    auto_axes = frozenset(n for n in mesh.axis_names if n != "pp")

    leaf = jax.tree_util.tree_leaves(stacked_params)[0]
    total_stages = leaf.shape[0]
    assert total_stages % npp == 0, (
        f"stage count {total_stages} must divide pp={npp}")

    def per_device(params_local, x):
        pp = lax.axis_index("pp")

        def chain(h):
            return chain_stages(stage_fn, params_local, h)

        # probe output structure once to size buffers
        mb_shape = x.shape[1:]
        out_aval = jax.eval_shape(chain, jax.ShapeDtypeStruct(mb_shape, x.dtype))
        total_ticks = num_micro + npp - 1
        perm = [(i, (i + 1) % npp) for i in range(npp)]

        def tick(carry, t):
            recv_buf, outbuf = carry
            inp = jnp.where(
                pp == 0,
                lax.dynamic_index_in_dim(
                    x, jnp.clip(t, 0, num_micro - 1), 0, keepdims=False),
                recv_buf,
            )
            y = chain(inp)
            widx = t - (npp - 1)
            valid = (pp == npp - 1) & (widx >= 0)
            upd = lax.dynamic_update_index_in_dim(
                outbuf, y, jnp.clip(widx, 0, num_micro - 1), 0)
            outbuf = jnp.where(valid, upd, outbuf)
            nxt = lax.ppermute(y, "pp", perm)
            return (nxt, outbuf), None

        init = (
            jnp.zeros(out_aval.shape, out_aval.dtype),
            jnp.zeros((num_micro,) + out_aval.shape, out_aval.dtype),
        )
        (_, outbuf), _ = lax.scan(tick, init, jnp.arange(total_ticks))
        return outbuf

    mapped = manual_shard_map(
        per_device,
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), stacked_params),
                  P()),
        out_specs=P("pp"),
        axis_names=frozenset({"pp"}),
    )
    out_all = mapped(stacked_params, x_micro)
    # out_specs P('pp') concatenates the per-stage buffers on axis 0; only the
    # last stage's block holds real outputs.
    return out_all[(npp - 1) * num_micro:]
