"""Quantized collectives + the fp8 KV ladder (ISSUE 15).

Two-tier contract, same as ISSUE 9. The DEFAULT paths stay
exactness-pinned: fp32 comm_dtype keeps the GSPMD psum (tp engine
bit-identical to the single-device engine), fp32 pools keep (k, v)
pairs. The QUANTIZED rungs are accuracy-gated vs fp32 but — because
both are batch-shape invariant (per-row chunk scales for the psum,
per-element casts for fp8 pages) — stay TOKEN-EXACT against the
engine's own naive oracle:

  * `quantized_psum` under shard_map matches the numpy oracle
    bit-for-bit, bounds its error vs the fp32 psum, never clips
    (pmax-shared scales are per-shard-honest), and is row-independent;
  * fp8 kernel-vs-reference sweep over q_len / GQA / page count /
    padded buckets;
  * engine e2e: int8-psum tp=2 and fp8 pools vs naive (exact) and vs
    the fp32 engine (top-5 >= 0.99, greedy agreement >= 99%);
  * mixed-precision tenants share ONE pool geometry under the armed
    auditor (tag bijection; fp8 tenants bit-identical to a native fp8
    engine, fp32 tenants bit-identical to the default engine);
  * snapshot round-trips comm_dtype/fp8 knobs; fp8 without support is
    a loud RuntimeError; the auditor rejects scale rows on fp8 pools.
"""

import numpy as np
import pytest
from _helpers import assert_dequantized_equal

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.models.llama import Llama, LlamaConfig
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention, ragged_reference,
)
from paddle_tpu.parallel.mesh import serving_mesh
from paddle_tpu.parallel.pipeline import manual_shard_map
from paddle_tpu.quantization.qcomm import (
    allreduce_bytes, quantized_allreduce_reference, quantized_psum,
)
from paddle_tpu.serving import (
    InvariantViolation, KVCachePool, LlamaRunner, SamplingParams,
    ServingEngine, audit_engine, naive_generate,
)
from paddle_tpu.serving import kv_cache as kvc

rng = np.random.default_rng(15)


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


@pytest.fixture(scope="module")
def llama_model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=96,
                      dropout=0.0)
    model = Llama(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def fp32_runner(llama_model):
    return LlamaRunner(llama_model, block_size=8, max_model_len=96)


@pytest.fixture(scope="module")
def fp8_runner(llama_model):
    return LlamaRunner(llama_model, block_size=8, max_model_len=96,
                       kv_dtype="fp8")


@pytest.fixture(scope="module")
def prompts():
    r = np.random.default_rng(7)
    return [list(r.integers(1, 97, int(r.integers(6, 24))))
            for _ in range(3)]


def _psum_shard_map(mesh, fn_reduce, chunk=None):
    """Run the quantized psum over explicit per-shard partials: the
    parts stack on a leading shard axis, shard_map hands each shard
    its slice, and the reduce runs over the model axis."""
    def f(part):
        if chunk is None:
            return fn_reduce(part[0], "model")
        return fn_reduce(part[0], "model", chunk=chunk)

    def run(parts):
        stacked = jnp.asarray(np.stack(parts))      # [S, ...]
        spec = P(*(("model",) + (None,) * (stacked.ndim - 1)))
        return manual_shard_map(
            f, mesh=mesh, in_specs=(spec,), out_specs=P(),
            axis_names=frozenset({"model"}))(stacked)

    return run


# ------------------------------------------------ qcomm primitive


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("chunk", [4, 128])
def test_quantized_psum_matches_numpy_oracle(tp, chunk):
    mesh = serving_mesh(data=1, model=tp)
    parts = [rng.standard_normal((3, 5, 16)).astype(np.float32) * (i + 1)
             for i in range(tp)]
    run = _psum_shard_map(mesh, quantized_psum, chunk=chunk)
    out = np.asarray(run(parts))
    ref = quantized_allreduce_reference(parts, chunk=chunk)
    assert_dequantized_equal(out, ref)


@pytest.mark.parametrize("tp", [2, 4])
def test_quantized_psum_error_bound_vs_fp32(tp):
    """Quantization error per element is bounded by tp * half a code
    step at the shared scale — the honest-scale (never-clip) bound."""
    mesh = serving_mesh(data=1, model=tp)
    parts = [rng.standard_normal((4, 64)).astype(np.float32)
             for _ in range(tp)]
    out = np.asarray(_psum_shard_map(mesh, quantized_psum, chunk=16)(parts))
    exact = np.sum(parts, axis=0)
    # shared scale per (row, chunk) = max over shards of absmax/127
    chunks = np.stack([p.reshape(4, 4, 16) for p in parts])
    scale = (np.abs(chunks).max(axis=-1) / 127.0).max(axis=0)  # [4, 4]
    bound = (tp * 0.5 + 1e-3) * np.repeat(scale, 16, axis=1).reshape(4, 64)
    assert (np.abs(out - exact) <= bound + 1e-6).all()
    # and it is close in aggregate: a few percent of the signal
    assert np.abs(out - exact).max() <= 0.05 * np.abs(exact).max() + 1e-3


def test_quantized_psum_shard_count_invariance():
    """The same GLOBAL sum quantized over 2 vs 4 shards stays within
    the combined error bound — scales are honest at any tp."""
    global_parts = [rng.standard_normal((2, 32)).astype(np.float32)
                    for _ in range(4)]
    out4 = np.asarray(_psum_shard_map(
        serving_mesh(1, 4), quantized_psum, chunk=8)(global_parts))
    merged = [global_parts[0] + global_parts[1],
              global_parts[2] + global_parts[3]]
    out2 = np.asarray(_psum_shard_map(
        serving_mesh(1, 2), quantized_psum, chunk=8)(merged))
    exact = np.sum(global_parts, axis=0)
    scale = max(np.abs(p).max() for p in global_parts) / 127.0
    assert np.abs(out4 - exact).max() <= 5 * scale
    assert np.abs(out2 - exact).max() <= 4 * scale


def test_quantized_psum_row_independence():
    """Per-row chunk scales: a row's reduced value is bit-identical no
    matter what other rows ride the same call — the batch-shape
    invariance the engine's token-exactness leans on."""
    mesh = serving_mesh(1, 2)
    row = rng.standard_normal((1, 24)).astype(np.float32)
    noise = rng.standard_normal((3, 24)).astype(np.float32) * 100.0
    parts_solo = [row, row * 0.5]
    parts_batch = [np.concatenate([row, noise]),
                   np.concatenate([row * 0.5, noise * 2.0])]
    run = _psum_shard_map(mesh, quantized_psum, chunk=8)
    solo = np.asarray(run(parts_solo))
    batch = np.asarray(run(parts_batch))
    np.testing.assert_array_equal(solo[0], batch[0])


def test_quantized_psum_zeros_and_outlier_honesty():
    mesh = serving_mesh(1, 2)
    run = _psum_shard_map(mesh, quantized_psum, chunk=8)
    zeros = [np.zeros((2, 16), np.float32)] * 2
    np.testing.assert_array_equal(np.asarray(run(zeros)), zeros[0])
    # a huge outlier on ONE shard must not clip the other shard's
    # contribution (pmax-shared scale covers both)
    a = np.zeros((1, 8), np.float32)
    a[0, 0] = 1000.0
    b = np.ones((1, 8), np.float32) * 3.0
    out = np.asarray(run([a, b]))
    assert abs(out[0, 0] - 1003.0) <= 1000.0 / 127.0 + 1e-3


def test_allreduce_bytes_accounting():
    assert allreduce_bytes(10, 64, "fp32") == 10 * 64 * 4
    # int8: 1 byte/element + 4 bytes per (row, chunk) scale
    assert allreduce_bytes(10, 64, "int8", chunk=64) == 10 * 64 + 10 * 4
    assert allreduce_bytes(1, 130, "int8", chunk=64) == 130 + 3 * 4
    with pytest.raises(ValueError):
        allreduce_bytes(1, 1, "bf16")


# ------------------------------------------------ fp8 kernel sweep


def _fp8_pools(B=2, n_kv=2, d=16, ps=8, pages=6, n_rep=1, T=8):
    nb = 1 + B * pages
    kp = jnp.asarray(rng.standard_normal((nb, ps, n_kv, d)),
                     jnp.float32).astype(jnp.float8_e4m3fn)
    vp = jnp.asarray(rng.standard_normal((nb, ps, n_kv, d)),
                     jnp.float32).astype(jnp.float8_e4m3fn)
    tbl = jnp.asarray(rng.permutation(np.arange(1, nb))
                      .reshape(B, pages).astype(np.int32))
    q = jnp.asarray(rng.standard_normal((B, T, n_kv * n_rep, d)),
                    jnp.float32)
    return q, kp, vp, tbl


@pytest.mark.parametrize("q_len,start_pos", [
    (1, 0), (1, 7), (1, 37),                 # decode at page boundaries
    (8, 0),                                  # fresh prefill
    (3, 13), (6, 40),                        # offset chunks
])
@pytest.mark.parametrize("n_rep", [1, 4])
def test_fp8_kernel_vs_reference_sweep(q_len, start_pos, n_rep):
    """Kernel and gather oracle read the SAME fp8 pages cast to fp32 —
    the outputs agree to fp32 softmax tolerance."""
    q, kp, vp, tbl = _fp8_pools(n_rep=n_rep)
    starts = jnp.asarray([start_pos, max(0, start_pos - 2)], jnp.int32)
    qlens = jnp.asarray([q_len, max(1, q_len - 1)], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                 interpret=True)
    ref = ragged_reference(q, kp, vp, tbl, starts, qlens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_fp8_kernel_dead_slot_and_bucket_invariance():
    q, kp, vp, tbl = _fp8_pools(B=3, n_rep=2, T=4)
    starts = jnp.asarray([33, 8, 0], jnp.int32)
    qlens = jnp.asarray([1, 4, 0], jnp.int32)
    tight = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                   interpret=True)
    assert bool((np.asarray(tight[2]) == 0.0).all()), "dead slot must be 0"
    q_wide = jnp.concatenate(
        [q, jnp.asarray(rng.standard_normal(q.shape), jnp.float32)], axis=1)
    wide = ragged_paged_attention(q_wide, kp, vp, tbl, starts, qlens,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(tight[1, :4]),
                                  np.asarray(wide[1, :4]))


def test_fp8_page_write_is_pure_cast():
    pool = jnp.zeros((3, 4, 2, 8), jnp.float8_e4m3fn)
    x = jnp.asarray(rng.standard_normal((1, 2, 2, 8)), jnp.float32)
    wp = jnp.asarray([[1, 1]], jnp.int32)
    wo = jnp.asarray([[0, 1]], jnp.int32)
    out = kvc.fp8_page_write(pool, wp, wo, x)
    np.testing.assert_array_equal(
        np.asarray(out[1, :2].astype(jnp.float32)),
        np.asarray(x[0].astype(jnp.float8_e4m3fn).astype(jnp.float32)))
    # idempotent: re-running the same write is bit-identical
    np.testing.assert_array_equal(
        np.asarray(kvc.fp8_page_write(out, wp, wo, x)), np.asarray(out))


# ------------------------------------------------ engine e2e


def _run_engine(runner, prompts, kv_dtypes=None, **kw):
    eng = ServingEngine(runner, num_blocks=64, max_batch_size=4,
                        max_model_len=96,
                        max_prefill_tokens_per_step=16, **kw)
    ids = []
    for i, p in enumerate(prompts):
        sp = SamplingParams(
            max_tokens=8,
            kv_dtype=None if kv_dtypes is None else kv_dtypes[i])
        ids.append(eng.add_request(p, sp))
    outs = eng.run()
    return [outs[r].output_tokens for r in ids], eng


def test_fp8_engine_token_exact_vs_naive_and_gated_vs_fp32(
        fp8_runner, fp32_runner, prompts):
    toks, eng = _run_engine(fp8_runner, prompts, enable_prefix_cache=True)
    assert eng.metrics.snapshot()["kv_bytes_reduction_x"] == 4.0
    # per-element casts are batch-shape invariant: engine == its own
    # naive oracle, token-exact, even with chunking + prefix cache on
    for t, p in zip(toks, prompts):
        assert t == naive_generate(fp8_runner, p,
                                   SamplingParams(max_tokens=8),
                                   max_model_len=96)
    # accuracy gate vs fp32: >= 99% greedy agreement
    agree = total = 0
    for t, p in zip(toks, prompts):
        ref = naive_generate(fp32_runner, p, SamplingParams(max_tokens=8),
                             max_model_len=96)
        agree += sum(int(a == b) for a, b in zip(t, ref))
        total += len(ref)
    assert agree / total >= 0.99


def test_fp8_pool_layout_and_bytes():
    pool = KVCachePool(2, 9, 8, 2, 16, kv_dtype="fp8")
    for layer in pool.pools:
        assert len(layer) == 2          # NO scale rows on fp8 pools
        assert str(layer[0].dtype) == "float8_e4m3fn"
    assert pool.kv_bytes_reduction_x() == 4.0
    assert pool.page_bytes() == 2 * 2 * 8 * 2 * 16


@pytest.mark.parametrize("tp", [2])
def test_qcomm_engine_token_exact_and_gated(llama_model, fp32_runner,
                                            prompts, tp):
    mesh = serving_mesh(data=1, model=tp)
    rq = LlamaRunner(llama_model, block_size=8, max_model_len=96
                     ).shard(mesh, comm_dtype="int8")
    toks, eng = _run_engine(rq, prompts)
    snap = eng.metrics.snapshot()
    # measured comm-bytes reduction, scale bytes counted: >= 2x
    assert snap["tp_comm_bytes"] > 0
    assert snap["tp_comm_bytes_reduction_x"] >= 2.0
    # per-row chunk scales are batch-shape invariant: token-exact vs
    # the engine's OWN oracle (same quantized runner)
    for t, p in zip(toks, prompts):
        assert t == naive_generate(rq, p, SamplingParams(max_tokens=8),
                                   max_model_len=96)
    # accuracy gate vs the fp32 engine
    agree = total = 0
    for t, p in zip(toks, prompts):
        ref = naive_generate(fp32_runner, p, SamplingParams(max_tokens=8),
                             max_model_len=96)
        agree += sum(int(a == b) for a, b in zip(t, ref))
        total += len(ref)
    assert agree / total >= 0.99


def test_qcomm_teacher_forced_top5_overlap(llama_model, fp32_runner):
    """Teacher-forced accuracy gate (the PR 9 methodology): top-5
    overlap >= 0.99 vs the fp32 engine over a replayed greedy stream,
    with the int8 psum AND fp8 pools both on."""
    mesh = serving_mesh(data=1, model=2)
    rq = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                     kv_dtype="fp8").shard(mesh, comm_dtype="int8")
    p = list(np.random.default_rng(5).integers(1, 97, 20))
    pools, tbls = [], []
    for r in (fp32_runner, rq):
        pool = KVCachePool(r.num_layers, 13, 8, r.n_kv_heads, r.head_dim,
                           r.dtype, mesh=r.mesh, model_axis=r.model_axis,
                           kv_dtype=r.kv_dtype)
        pages = pool.allocator.alloc(12)
        tbls.append(pool.pad_table(pages, 12))
        pools.append(pool.pools)
    l_ref, pools[0] = fp32_runner.prefill(p, tbls[0], pools[0])
    l_q, pools[1] = rq.prefill(p, tbls[1], pools[1])
    toks, overlaps, dl = list(p), [], []
    for _ in range(16):
        a, b = np.asarray(l_ref), np.asarray(l_q)
        dl.append(np.abs(a - b).mean())
        overlaps.append(len(set(np.argsort(a)[-5:].tolist())
                            & set(np.argsort(b)[-5:].tolist())) / 5.0)
        tok = int(np.argmax(a))
        pos = np.asarray([len(toks)], np.int32)
        toks.append(tok)
        l_ref, pools[0] = fp32_runner.decode(
            np.asarray([tok], np.int32),
            np.asarray(tbls[0], np.int32)[None], pos, pools[0])
        l_q, pools[1] = rq.decode(
            np.asarray([tok], np.int32),
            np.asarray(tbls[1], np.int32)[None], pos, pools[1])
        l_ref, l_q = l_ref[0], l_q[0]
    assert np.mean(overlaps) >= 0.99
    assert np.mean(dl) < 0.05


def test_tp_fp32_default_bit_exact_pin(llama_model, fp32_runner, prompts):
    """comm_dtype default: the sharded fp32 engine stays bit-identical
    to the single-device engine — the quantized-comm plumbing must not
    perturb the default path."""
    mesh = serving_mesh(data=1, model=2)
    rtp = LlamaRunner(llama_model, block_size=8, max_model_len=96
                      ).shard(mesh)
    assert rtp.comm_dtype == "fp32"
    t_tp, _ = _run_engine(rtp, prompts[:2])
    t_1, _ = _run_engine(fp32_runner, prompts[:2])
    assert t_tp == t_1


# ------------------------------------------------ mixed tenancy


def test_mixed_tenant_engine_e2e(llama_model, fp32_runner, fp8_runner,
                                 prompts):
    """One pool geometry, two precisions: fp8 tenants match the NATIVE
    fp8 engine bit-for-bit (the mixed write path rounds through the
    same cast), fp32 tenants match the default engine — all under the
    armed auditor's tag bijection."""
    rm = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                     kv_dtype="mixed")
    dtypes = ["fp8", "fp32", None]
    toks, eng = _run_engine(rm, prompts, kv_dtypes=dtypes,
                            enable_prefix_cache=True)
    for t, p, d in zip(toks, prompts, dtypes):
        oracle = fp8_runner if d == "fp8" else fp32_runner
        assert t == naive_generate(oracle, p, SamplingParams(max_tokens=8),
                                   max_model_len=96), d
    audit_engine(eng)                       # zero leaks, tags clean
    assert eng.pool.allocator.check_no_leaks() or eng.pool.prefix_cache


def test_mixed_tenants_never_share_prefix_pages(llama_model):
    """Equal tokens, different precision -> different KV bytes: the
    dtype-seeded hash chains keep the prefix cache partitioned."""
    rm = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                     kv_dtype="mixed")
    shared = list(range(1, 20))
    eng = ServingEngine(rm, num_blocks=64, max_batch_size=2,
                        max_model_len=96, enable_prefix_cache=True)
    a = eng.add_request(shared, SamplingParams(max_tokens=4,
                                               kv_dtype="fp32"))
    eng.run()
    b = eng.add_request(shared, SamplingParams(max_tokens=4,
                                               kv_dtype="fp8"))
    eng.run()
    outs = eng.outputs()
    assert outs[a].finish_reason and outs[b].finish_reason
    # the fp8 tenant must NOT have hit the fp32 tenant's cached pages
    assert eng.metrics.prefix_hit_tokens.value == 0


def test_mixed_pool_tag_bijection_audited(llama_model, prompts):
    rm = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                     kv_dtype="mixed")
    eng = ServingEngine(rm, num_blocks=64, max_batch_size=2,
                        max_model_len=96, audit=True)
    eng.add_request(prompts[0], SamplingParams(max_tokens=6,
                                               kv_dtype="fp8"))
    eng.step()
    # corrupt one owned page's device tag bit -> the auditor trips
    req = eng.scheduler.running[0]
    page = req.kv.pages[0]
    eng.pool.pools = [
        (k, v, t.at[page].set(False)) for (k, v, t) in eng.pool.pools]
    with pytest.raises(InvariantViolation, match="tag"):
        audit_engine(eng)


def test_kv_dtype_validation_loud(llama_model, fp32_runner, fp8_runner):
    eng = ServingEngine(fp32_runner, num_blocks=16, max_batch_size=2,
                        max_model_len=96)
    with pytest.raises(ValueError, match="mixed"):
        eng.add_request([1, 2, 3], SamplingParams(max_tokens=2,
                                                  kv_dtype="fp8"))
    eng8 = ServingEngine(fp8_runner, num_blocks=16, max_batch_size=2,
                         max_model_len=96)
    with pytest.raises(ValueError, match="not servable"):
        eng8.add_request([1, 2, 3], SamplingParams(max_tokens=2,
                                                   kv_dtype="fp32"))
    # fp8 override on an fp8 pool is a no-op, accepted
    eng8.add_request([1, 2, 3], SamplingParams(max_tokens=2,
                                               kv_dtype="fp8"))
    with pytest.raises(ValueError, match="kv_dtype"):
        SamplingParams(max_tokens=2, kv_dtype="fp16")


# ------------------------------------------------ auditor + knobs


def test_auditor_rejects_scale_rows_on_fp8_pool(fp8_runner):
    eng = ServingEngine(fp8_runner, num_blocks=16, max_batch_size=2,
                        max_model_len=96)
    # sneak int8-style scale rows into an fp8 pool: fp8 is scale-free,
    # the auditor must assert their ABSENCE
    eng.pool.pools = [layer + (jnp.zeros((16, 2), jnp.float32),
                               jnp.zeros((16, 2), jnp.float32))
                      for layer in eng.pool.pools]
    with pytest.raises(InvariantViolation, match="entries"):
        audit_engine(eng)


def test_auditor_rejects_non_fp8_pages_on_fp8_pool(fp8_runner):
    eng = ServingEngine(fp8_runner, num_blocks=16, max_batch_size=2,
                        max_model_len=96)
    eng.pool.pools = [(layer[0].astype(jnp.float32),
                       layer[1].astype(jnp.float32))
                      for layer in eng.pool.pools]
    with pytest.raises(InvariantViolation, match="float8"):
        audit_engine(eng)


def test_snapshot_roundtrip_comm_and_fp8_knobs(llama_model, fp8_runner,
                                               prompts):
    mesh = serving_mesh(data=1, model=2)
    rq = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                     kv_dtype="fp8").shard(mesh, comm_dtype="int8")
    eng = ServingEngine(rq, num_blocks=64, max_batch_size=4,
                        max_model_len=96)
    ids = [eng.add_request(p, SamplingParams(max_tokens=6))
           for p in prompts[:2]]
    eng.step()                               # mid-flight snapshot
    state = eng.snapshot()
    assert state["config"]["kv_dtype"] == "fp8"
    assert state["config"]["comm_dtype"] == "int8"
    twin = ServingEngine.restore(rq, state)
    twin_outs = twin.run()
    outs = eng.run()
    for rid in ids:
        assert outs[rid].output_tokens == twin_outs[rid].output_tokens


def test_fp8_without_support_is_loud(monkeypatch):
    monkeypatch.setattr(kvc, "fp8_supported", lambda: False)
    with pytest.raises(RuntimeError, match="float8_e4m3fn"):
        KVCachePool(2, 9, 8, 2, 16, kv_dtype="fp8")
    with pytest.raises(RuntimeError, match="float8_e4m3fn"):
        KVCachePool(2, 9, 8, 2, 16, kv_dtype="mixed")


def test_comm_dtype_validation(llama_model, fp32_runner):
    mesh = serving_mesh(data=1, model=2)
    with pytest.raises(ValueError, match="comm_dtype"):
        LlamaRunner(llama_model, block_size=8,
                    max_model_len=96).shard(mesh, comm_dtype="fp8")
    from paddle_tpu.serving import create_engine

    with pytest.raises(ValueError, match="mesh"):
        create_engine(llama_model, num_blocks=16, block_size=8,
                      comm_dtype="int8")


def test_metrics_aggregation_of_comm_counters():
    from paddle_tpu.serving.metrics import aggregate_snapshots

    a = {"tp_comm_bytes": 100.0, "tp_comm_bytes_fp32": 400.0,
         "tokens_generated": 1.0}
    b = {"tp_comm_bytes": 50.0, "tp_comm_bytes_fp32": 200.0,
         "tokens_generated": 1.0}
    agg = aggregate_snapshots([a, b])
    assert agg["tp_comm_bytes"] == 150.0
    assert agg["tp_comm_bytes_fp32"] == 600.0
    assert agg["tp_comm_bytes_reduction_x"] == 4.0
