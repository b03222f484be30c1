"""Where a tensor-parallel layer leaves the batch (CPU, 4 virtual devices).

A `tp` layer constrains the `tp` placement of its feature dimension and
nothing else (parallel/mp_layers.py). Written with `None` on the batch
dimension, the same constraints made every data-parallel replica gather the
whole batch after every layer and run its row-parallel matmuls on all of it;
the losses were right all along, so only the compiled program shows it.
These tests read the partitioned HLO (`parallel.debug.collectives`).
"""

import contextlib
import hashlib
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
from paddle_tpu.parallel.debug import collective_forms, collectives
from paddle_tpu.parallel.mesh import program_mesh_scope

B, S, VOCAB = 8, 80, 256            # B x S = 640 is no width of the models
WIDTHS = dict(vocab_size=VOCAB, hidden_size=128, num_layers=2, num_heads=4,
              ffn_hidden=512, max_seq_len=S, dropout=0.0)

# name -> (model, loss, the three losses the parent's program gave: seed 0,
# AdamW 1e-3, O1, tokens = labels = default_rng(0).integers(0, 256, (8, 80));
# a digest of the parameters those three steps left, bit for bit: PR 45's
# parent, e2e7483. Both guard the CPU's path alone, where `TrainStep` hands
# the compiler no option: what the TPU's options do to the sums is read on
# the chip, by the four-chip cell's `correct`)
PARENT_GPT = [4.5, 4.0, 3.671875]
CASES = {
    "gpt": (lambda: GPT(GPTConfig(**WIDTHS, tensor_parallel=True)),
            gpt_loss_fn, PARENT_GPT, "4d9541a82260e7f1"),
    "gpt-sp": (lambda: GPT(GPTConfig(**WIDTHS, tensor_parallel=True,
                                     sequence_parallel=True)),
               gpt_loss_fn, PARENT_GPT, "ea63bf284a4a4d9b"),
    "llama": (lambda: Llama(LlamaConfig(**WIDTHS, tensor_parallel=True)),
              llama_loss_fn, [5.5625, 5.0625, 4.59375], "2c2148d094e64512"),
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


def _digest(params) -> str:
    h = hashlib.sha256()
    for name in sorted(params):
        h.update(name.encode())
        h.update(np.asarray(params[name]).tobytes())
    return h.hexdigest()[:16]


def _train(build, loss_fn, mesh_axes):
    """Three O1 steps; (losses, mesh, text of the compiled step, digest of
    the parameters)."""
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
        return losses, mesh, text, _digest(step.params)


@pytest.mark.parametrize("case", list(CASES))
def test_train_step_keeps_the_batch_on_dp(case):
    build, loss_fn, parent_losses, parent_params = CASES[case]
    losses, mesh, text, params = _train(build, loss_fn, {"dp": 2, "tp": 2})
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
    assert params == parent_params
    one_device, _, _, _ = _train(build, loss_fn, None)
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


def test_collective_forms_tells_a_fused_all_reduce_from_a_plain_one():
    """Lines as the TPU compiler wrote them for the toy step on a described
    v5e 2x2 (cut to what is read): an all-reduce it overlaps is printed in
    its start's and its done's fused computations and in an
    `async_collective_fusion` a product it runs under, all on one channel;
    one it waits at is an op of the program itself."""
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    meta = 'metadata={op_name="jit(step)/loss/%s/mlp/jit(f)/dot_general"}'
    bwd, fwd = meta % "transpose(jvp(block))", meta % "jvp(block)"
    text = f"""HloModule jit_step, is_scheduled=true

%fused_computation.501 (param_0.1: bf16[4,80,128]) -> bf16[4,80,128] {{
  %all-reduce.125 = bf16[4,80,128]{{2,1,0}} all-reduce(%param_0.1), channel_id=22, replica_groups=[2,2]<=[4], to_apply=%add, {bwd}
}}

%async_collective_fusion.370 (param_0.2: bf16[4,80,128]) -> (bf16[128,256], bf16[4,80,128]) {{
  %all-reduce.127 = bf16[4,80,128]{{2,1,0}} all-reduce(%param_0.2), channel_id=22, replica_groups=[2,2]<=[4], to_apply=%add, {bwd}
}}

%fused_computation.505 (param_0.3: bf16[4,80,128]) -> bf16[4,80,128] {{
  %all-reduce.133 = bf16[4,80,128]{{2,1,0}} all-reduce(%param_0.3), channel_id=22, replica_groups=[2,2]<=[4], to_apply=%add, {bwd}
}}

ENTRY %main.32_spmd (param.35: f32[128]) -> f32[128] {{
  %all-reduce.147 = bf16[4,80,128]{{2,1,0}} all-reduce(%fusion.209), channel_id=9, replica_groups=[2,2]<=[4], frontend_attributes={{async_collective_name="all-reduce-start.1"}}, to_apply=%add, {fwd}
  %async-collective-start = (bf16[4,80,128]{{2,1,0}}, bf16[4,80,128]{{2,1,0}}) fusion(%fusion.155), kind=kCustom, calls=%fused_computation.501
  %fusion.370 = (bf16[128,256]{{1,0}}, bf16[4,80,128]{{2,1,0}}) fusion(%gte.380), kind=kOutput, calls=%async_collective_fusion.370
  %async-collective-done = bf16[4,80,128]{{2,1,0}} fusion(%gte.424), kind=kCustom, calls=%fused_computation.505
  %all-reduce.156 = (bf16[64,128]{{1,0}}, f32[128]{{0}}) all-reduce(%a, %b), channel_id=25, replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add, {bwd}
  %cps = (bf16[4,80,64]{{2,1,0}}, bf16[4,80,64]{{2,1,0}}, u32[], u32[]) collective-permute-start(%c), channel_id=7, source_target_pairs={{{{0,1}},{{1,0}},{{2,3}},{{3,2}}}}
}}
"""
    assert collective_forms(text, mesh) == [
        ("all-reduce", ("tp",), "backward", "async", 4 * 80 * 128 * 2),
        ("all-reduce", ("tp",), "forward", "sync", 4 * 80 * 128 * 2),
        ("all-reduce", ("dp",), "backward", "sync", 64 * 128 * 2 + 128 * 4),
        ("collective-permute", ("tp",), "", "async", 4 * 80 * 64 * 2),
    ]
    # one entry an array, whatever the form: what `collectives` always gave
    assert [c[0] for c in collectives(text, mesh)] == [
        "all-reduce"] * 4 + ["collective-permute"]


def test_train_step_on_the_cpu_passes_no_compiler_option(monkeypatch):
    """The options are the TPU compiler's: a mesh of CPU devices (these
    tests' eight) gets none, nor does a step without a mesh or a mesh of
    one chip; a mesh of more than one TPU device gets the constant."""
    from paddle_tpu.jit import api

    seen = []
    real_jit = jax.jit
    monkeypatch.setattr(jax, "jit", lambda f, **kw: (
        seen.append(kw.get("compiler_options")), real_jit(f, **kw))[1])
    _train(CASES["gpt"][0], gpt_loss_fn, {"dp": 2, "tp": 2})
    assert seen and all(opts is None for opts in seen)

    class Chip:
        platform = "tpu"

    def mesh_of(n):
        return type("M", (), {"devices": np.array(
            [Chip()] * n, object).reshape(n // 2 or 1, -1)})()

    assert api._mesh_compiler_options(None) is None
    assert api._mesh_compiler_options(mesh_of(1)) is None
    assert api._mesh_compiler_options(mesh_of(4)) == \
        api._MESH_COMPILER_OPTIONS
    assert all(k.startswith("xla_") for k in api._MESH_COMPILER_OPTIONS)


def test_collective_forms_counts_the_cpu_step_by_family():
    """`collective_forms` on the step the CPU's compiler made: ops by mesh
    axes, phase and form, one an op. The CPU overlaps no all-reduce, so
    every one is an op its program waits at; what `TrainStep` reaches on a
    mesh of TPU devices is `tests/test_chip_compile.py`'s to say."""
    import collections

    _, mesh, text, _ = _train(CASES["gpt"][0], gpt_loss_fn,
                              {"dp": 2, "tp": 2})
    counts = collections.Counter(
        (axes, phase, form)
        for op, axes, phase, form, _ in collective_forms(text, mesh)
        if op == "all-reduce")
    assert counts[("tp",), "forward", "sync"] >= 4       # two a layer
    assert counts[("tp",), "backward", "sync"] >= 4
    assert counts[("dp",), "backward", "sync"] >= 1
    assert not [k for k in counts if k[2] == "async"]


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
