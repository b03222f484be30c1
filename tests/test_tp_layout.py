"""Where a tensor-parallel layer leaves the batch (CPU, 4 virtual devices).

A `tp` layer constrains the `tp` placement of its feature dimension and
nothing else (parallel/mp_layers.py). Written with `None` on the batch
dimension, the same constraints made every data-parallel replica gather the
whole batch after every layer and run its row-parallel matmuls on all of it;
the losses were right all along, so only the compiled program shows it.
These tests read the partitioned HLO (`parallel.debug.collectives`).
"""

import contextlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import parallel as dist
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn
from paddle_tpu.models.llama import Llama, LlamaConfig, llama_loss_fn
from paddle_tpu.parallel.api import static_trace
from paddle_tpu.parallel.debug import collectives
from paddle_tpu.parallel.mesh import program_mesh_scope

B, S, VOCAB = 8, 80, 256            # B x S = 640 is no width of the models
WIDTHS = dict(vocab_size=VOCAB, hidden_size=128, num_layers=2, num_heads=4,
              ffn_hidden=512, max_seq_len=S, dropout=0.0)

# name -> (model, loss, the three losses the parent's program gave: seed 0,
# AdamW 1e-3, O1, tokens = labels = default_rng(0).integers(0, 256, (8, 80)))
PARENT_GPT = [4.5, 4.0, 3.671875]
CASES = {
    "gpt": (lambda: GPT(GPTConfig(**WIDTHS, tensor_parallel=True)),
            gpt_loss_fn, PARENT_GPT),
    "gpt-sp": (lambda: GPT(GPTConfig(**WIDTHS, tensor_parallel=True,
                                     sequence_parallel=True)),
               gpt_loss_fn, PARENT_GPT),
    "llama": (lambda: Llama(LlamaConfig(**WIDTHS, tensor_parallel=True)),
              llama_loss_fn, [5.5625, 5.0625, 4.59375]),
}


@contextlib.contextmanager
def _installed(axes):
    """The process-wide mesh of these axes (None: no mesh), then none."""
    mesh = dist.init_mesh(axes, devices=jax.devices()[
        :math.prod(axes.values())]) if axes else None
    try:
        yield mesh
    finally:
        dist.set_mesh(None)


def _train(build, loss_fn, mesh_axes):
    """Three O1 steps; (losses, mesh, text of the compiled step)."""
    with _installed(mesh_axes) as mesh:
        paddle.seed(0)
        model = build()
        opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                     learning_rate=1e-3)
        step = paddle.jit.TrainStep(model, loss_fn, opt, amp_level="O1")
        tokens = np.random.default_rng(0).integers(
            0, VOCAB, (B, S)).astype(np.int32)
        losses = [float(step(tokens, tokens)) for _ in range(3)]
        _, args = step._stage_inputs((tokens, tokens))
        with program_mesh_scope(mesh):
            text = step._compiled.lower(
                step.params, step.buffers, step.opt_state,
                *args).compile().as_text()
        return losses, mesh, text


@pytest.mark.parametrize("case", list(CASES))
def test_train_step_keeps_the_batch_on_dp(case):
    build, loss_fn, parent_losses = CASES[case]
    losses, mesh, text = _train(build, loss_fn, {"dp": 2, "tp": 2})
    found = collectives(text, mesh)
    assert any(op == "all-reduce" and axes == ("tp",) for op, axes, _, _
               in found), "no tp all-reduce found: is this the tp program?"

    # over dp: the gradients' all-reduce and scalars, never an activation
    # (no all-gather, no all-to-all, no permute of a [b, s, ...] array)
    over_dp = [c for c in found if "dp" in c[1] and len(c[3]) >= 3]
    assert not over_dp, f"activations cross dp: {over_dp}"

    # an activation reduced over tp is this replica's half of the batch
    rows = {shape[0] for op, axes, _, shape in found
            if op == "all-reduce" and axes == ("tp",) and len(shape) == 3}
    assert rows == {B // 2}, rows

    # no matmul over the whole batch: neither B x S rows nor B sequences
    dots = [tuple(int(n) for n in dims.split(","))
            for dims in re.findall(r"= \w+\[([0-9,]+)\]\S* dot\(", text)]
    assert dots and not [d for d in dots if d[0] in (B * S, B)], dots

    assert losses == parent_losses
    one_device, _, _ = _train(build, loss_fn, None)
    # O1 hands back a bfloat16 loss: one step of it at 4..8 is 2 ** -5
    np.testing.assert_allclose(losses, one_device, atol=2 ** -5, rtol=0)


def test_collectives_reads_groups_against_the_mesh():
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    text = """
  %ag = f32[8,16,32]{2,1,0} all-gather(f32[4,16,32]{2,1,0} %x), channel_id=1, replica_groups={{0,2},{1,3}}, dimensions={0}
  %ag.clone = f32[8,16,32]{2,1,0} all-gather(f32[4,16,32]{2,1,0} %x), channel_id=1, replica_groups={{0,2},{1,3}}, dimensions={0}
  %ar = (bf16[4,16]{1,0}, f32[]) all-reduce(%a, %b), channel_id=2, replica_groups=[2,2]<=[4], to_apply=%add
  %a2a = bf16[4,16,2,8]{3,2,1,0} all-to-all(%c), channel_id=3, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={2}
  %all = f32[] all-reduce(%d), channel_id=4, replica_groups=[1,4]<=[4], to_apply=%add
  %ags = (f32[4,8]{1,0}, f32[8,8]{1,0}) all-gather-start(%e), channel_id=5, replica_groups={{0,1},{2,3}}, dimensions={0}
  %cp = f32[4,8]{1,0} collective-permute(%f), channel_id=6, source_target_pairs={{0,3},{3,0}}
  %dot = f32[4,8]{1,0} dot(%g, %h), lhs_contracting_dims={1}
"""
    assert collectives(text, mesh) == [
        ("all-gather", ("dp",), "f32", (8, 16, 32)),
        ("all-reduce", ("tp",), "bf16", (4, 16)),
        ("all-reduce", ("tp",), "f32", ()),
        ("all-to-all", ("dp",), "bf16", (4, 16, 2, 8)),
        ("all-reduce", ("dp", "tp"), "f32", ()),
        ("all-gather", ("tp",), "f32", (8, 8)),
        ("collective-permute", ("dp", "tp"), "f32", (4, 8)),
    ]


# ---- each layer alone, inside jit: what it does to a dp-sharded batch

_x = lambda: jnp.ones((B, S, 32), jnp.float32)
# name -> (build the callable, its input, the parent's output spec under a
#          mesh that has tp alone)
LAYERS = {
    "column-sharded": (lambda: dist.ColumnParallelLinear(
        32, 64, gather_output=False), _x, P(None, None, "tp")),
    "column-gathered": (lambda: dist.ColumnParallelLinear(
        32, 64, gather_output=True), _x, P()),
    "row-parallel-input": (lambda: dist.RowParallelLinear(
        32, 64, input_is_parallel=True), _x, P()),
    "row-whole-input": (lambda: dist.RowParallelLinear(
        32, 64, input_is_parallel=False), _x, P()),
    "vocab-embedding": (lambda: dist.VocabParallelEmbedding(64, 32),
                        lambda: jnp.zeros((B, S), jnp.int32), P()),
    "cross-entropy": (
        lambda: (lambda x, ce=dist.ParallelCrossEntropy(): ce(
            x, paddle.Tensor(jnp.zeros((B, S), jnp.int32)))), _x, P()),
    "scatter": (lambda: dist.ScatterOp.apply, _x, P(None, "tp")),
    "gather": (lambda: dist.GatherOp.apply, _x, P()),
}


def _out_spec(build, x, mesh, spec):
    """The layer under jit on an input placed by `spec`: its output's spec,
    padded to three dimensions."""
    layer = build()

    def f(v):
        with static_trace():
            return layer(paddle.Tensor(v))._value

    out = jax.jit(f)(jax.device_put(x, NamedSharding(mesh, spec)))
    return (tuple(out.sharding.spec) + (None,) * 3)[:3]


@pytest.mark.parametrize("case", list(LAYERS))
def test_tp_layer_leaves_the_batch_where_it_was(case):
    build, make_x, tp_only_spec = LAYERS[case]
    with _installed({"dp": 2, "tp": 2}) as mesh:
        assert _out_spec(build, make_x(), mesh, P("dp"))[0] == "dp"
    with _installed({"tp": 4}) as mesh:
        assert _out_spec(build, make_x(), mesh, P()) == (
            tuple(tp_only_spec) + (None,) * 3)[:3]
