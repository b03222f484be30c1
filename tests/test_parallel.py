"""Distributed tests on the 8-device virtual CPU mesh (reference:
test/collective/ + test/auto_parallel/, which need real GPUs — here N fake
devices in one process, SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import parallel as dist
from paddle_tpu.models.gpt import GPTConfig, GPT, build_pipeline_train_step, gpt_loss_fn

rng = np.random.default_rng(4)


@pytest.fixture
def mesh2x2x2():
    mesh = dist.init_mesh({"dp": 2, "pp": 2, "tp": 2})
    yield mesh
    dist.set_mesh(None)


@pytest.fixture
def mesh_dp_tp():
    mesh = dist.init_mesh({"dp": 2, "tp": 4})
    yield mesh
    dist.set_mesh(None)


def _f(*shape):
    return rng.standard_normal(shape).astype(np.float32)


def test_shard_tensor_placements(mesh_dp_tp):
    x = paddle.to_tensor(_f(8, 16))
    st = dist.shard_tensor(x, placements=[dist.Shard(0), dist.Shard(1)])
    assert st._value.sharding.spec == P("dp", "tp")
    # reshard to replicated
    r = dist.reshard(st, placements=[dist.Replicate(), dist.Replicate()])
    np.testing.assert_allclose(np.asarray(r._value), x.numpy())
    assert r._value.sharding.spec == P(None, None)


def test_placement_spec_roundtrip(mesh_dp_tp):
    from paddle_tpu.parallel.api import placements_to_spec, spec_to_placements

    mesh = dist.current_mesh()
    pl = [dist.Shard(1), dist.Replicate()]
    spec = placements_to_spec(pl, mesh, 3)
    assert spec == P(None, "dp", None)
    back = spec_to_placements(spec, mesh, 3)
    assert back[0] == dist.Shard(1) and back[1] == dist.Replicate()


def test_column_row_parallel_parity(mesh_dp_tp):
    """TP Column->Row pair must equal a dense two-layer MLP."""
    paddle.seed(3)
    col = dist.ColumnParallelLinear(16, 32, gather_output=False)
    row = dist.RowParallelLinear(32, 16, input_is_parallel=True)
    x = paddle.to_tensor(_f(4, 16))
    out = row(col(x))
    ref = (x.numpy() @ col.weight.numpy() + col.bias.numpy()) \
        @ row.weight.numpy() + row.bias.numpy()
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=1e-4)


def test_vocab_parallel_embedding(mesh_dp_tp):
    emb = dist.VocabParallelEmbedding(32, 8)
    ids = paddle.to_tensor(np.array([[1, 5, 31]]))
    out = emb(ids)
    np.testing.assert_allclose(out.numpy(),
                               emb.weight.numpy()[[1, 5, 31]][None],
                               rtol=1e-6)


def test_collective_allgather_allreduce(mesh_dp_tp):
    mesh = dist.current_mesh()
    x = paddle.to_tensor(_f(8, 4))
    xs = dist.shard_tensor(x, placements=[dist.Shard(0), dist.Replicate()])
    parts = []
    dist.all_gather(parts, xs, group=dist.new_group(axis="dp"))
    assert len(parts) == 2
    np.testing.assert_allclose(
        np.concatenate([p.numpy() for p in parts], 0), x.numpy(), rtol=1e-6)

    # allreduce over dp-sharded partials sums the shards
    y = dist.all_reduce(
        dist.shard_tensor(paddle.to_tensor(_f(4, 4)),
                          placements=[dist.Shard(0), dist.Replicate()]),
        group=dist.new_group(axis="dp"))
    assert y.shape == [2, 4]


def test_in_jit_collectives(mesh2x2x2):
    """shard_map functional collectives (the c_* op analogues)."""
    from paddle_tpu.parallel import collective as C

    mesh = dist.current_mesh()
    from paddle_tpu.parallel.pipeline import manual_shard_map

    x = jnp.arange(8.0).reshape(8, 1)

    f = manual_shard_map(lambda a: C.psum(a, "dp"), mesh=mesh,
                         in_specs=P("dp"), out_specs=P(),
                         axis_names=frozenset({"dp"}))
    out = f(x)
    # psum over dp sums the two (4,1) shards; output replicated
    assert out.shape == (4, 1)
    np.testing.assert_allclose(np.asarray(out).ravel(),
                               np.asarray([4.0, 6.0, 8.0, 10.0]), rtol=1e-6)


def test_dataparallel_wrapper(mesh_dp_tp):
    net = nn.Linear(8, 4)
    dp = dist.DataParallel(net)
    x = paddle.to_tensor(_f(8, 8))
    out = dp(x)
    ref = net(x)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-5)


def test_group_sharded_marks_params(mesh_dp_tp):
    net = nn.Linear(8, 8)
    opt = paddle.optimizer.Adam(parameters=net.parameters())
    net, opt, _ = dist.group_sharded_parallel(net, opt, level="p_g_os")
    assert net.weight._sharding is not None
    assert opt._zero_stage == 3


def test_fleet_init_topology():
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
                               "sharding_degree": 1}
    dist.fleet.init(is_collective=True, strategy=strategy)
    try:
        hcg = dist.fleet.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_pipe_parallel_world_size() == 2
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_global_world_size() == 8
    finally:
        dist.set_mesh(None)


def test_gpt_tp_matches_dense(mesh_dp_tp):
    """The flagship under tp must compute the same function as dense."""
    cfg = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
               max_seq_len=16, dropout=0.0)
    paddle.seed(21)
    dense = GPT(GPTConfig(**cfg))
    paddle.seed(21)
    tp = GPT(GPTConfig(**cfg, tensor_parallel=True, sequence_parallel=True))
    x = paddle.to_tensor(rng.integers(0, 64, (2, 8)))
    dense.eval(), tp.eval()
    np.testing.assert_allclose(tp(x).numpy(), dense(x).numpy(),
                               rtol=2e-3, atol=2e-3)


def test_moe_layer_forward(mesh_dp_tp):
    dist.set_mesh(None)
    moe = dist.MoELayer(16, 32, num_experts=4, capacity_factor=2.0)
    x = paddle.to_tensor(_f(2, 8, 16), stop_gradient=False)
    out = moe(x)
    assert out.shape == [2, 8, 16]
    out.sum().backward()
    assert moe.w1.grad is not None
    assert moe.gate.grad is not None  # routing is differentiable


def test_pipeline_parity_vs_sequential(mesh2x2x2):
    """pipeline_apply over pp=2 must equal running stages sequentially."""
    from paddle_tpu.parallel.pipeline import pipeline_apply, stack_stage_params

    mesh = dist.current_mesh()
    d = 16
    ws = [_f(d, d) * 0.3 for _ in range(4)]

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    stacked = stack_stage_params([{"w": w} for w in ws])
    x = _f(4, 2, d)  # [micro, mb, d]
    out = pipeline_apply(stage_fn, stacked, jnp.asarray(x), mesh)
    ref = x
    for w in ws:
        ref = np.tanh(ref @ w)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_pipeline_grad_flows(mesh2x2x2):
    from paddle_tpu.parallel.pipeline import pipeline_apply, stack_stage_params

    mesh = dist.current_mesh()
    d = 8
    stacked = {"w": jnp.stack([jnp.eye(d) * 0.5 for _ in range(2)])}
    x = jnp.asarray(_f(2, 2, d))

    def loss(params):
        out = pipeline_apply(lambda p, h: h @ p["w"], params, x, mesh)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(stacked)
    assert np.isfinite(np.asarray(g["w"])).all()
    assert np.abs(np.asarray(g["w"])).sum() > 0


def test_gpt_pipeline_train_step(mesh2x2x2):
    mesh = dist.current_mesh()
    cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=2, num_heads=2,
                    max_seq_len=8, dropout=0.0)
    step, state = build_pipeline_train_step(cfg, mesh, num_micro=2, lr=1e-2)
    tokens = jnp.asarray(rng.integers(0, 32, (2, 2, 8)))
    losses = []
    for _ in range(3):
        state, loss = step(state, tokens, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
