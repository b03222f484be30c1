"""Quantized KV cache + weight-only int8 serving (ISSUE 9).

Two-tier contract. The DEFAULT (fp32) path stays exactness-pinned:
engine streams are bit-identical to naive_generate, pools are the same
(k, v) pairs as before. The QUANTIZED path is accuracy-gated instead:

  * kernel vs ragged_reference is EXACT IN THE INT8 DOMAIN — both
    dequantize the same codes with the same per-page-per-head scales,
    swept over q_len / start_pos / GQA / page count / padded buckets;
  * quantize-append round-trips are bounded by the page scale (decode
    single-token appends, chunk writes, page-restart recycling);
  * engine e2e on the real Llama config: top-5 logit overlap >= 0.99
    (teacher-forced) and greedy-token agreement >= 99% vs the fp32
    oracle;
  * COW / prefix cache / truncate operate on int8 pools under the
    armed auditor (which learns the scale-pool shape invariant: one
    scale per page per kv-head, sharded like its pool at tp > 1);
  * snapshot/restore round-trips both dtype knobs;
  * the byte accounting is honest: page bytes count int8 codes PLUS
    scale bytes, and the reduction is >= 1.8x with block_size 8+.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.models.llama import Llama, LlamaConfig
from paddle_tpu.ops.pallas.ragged_paged_attention import (
    ragged_paged_attention, ragged_reference,
)
from paddle_tpu.parallel.mesh import serving_mesh
from paddle_tpu.serving import (
    GPTRunner, InvariantViolation, KVCachePool, LlamaRunner, SamplingParams,
    ServingEngine, audit_engine, naive_generate,
)
from paddle_tpu.serving.kv_cache import quantized_page_write

rng = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


@pytest.fixture(scope="module")
def llama_model():
    """The real serving config in miniature: GQA (4 q-heads over 2
    kv-heads), RMSNorm + RoPE + SwiGLU — every quantized code path the
    engine ships (k/v append, ragged spans, COW) runs through it."""
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
def int8_runner(llama_model):
    return LlamaRunner(llama_model, block_size=8, max_model_len=96,
                       kv_dtype="int8")


@pytest.fixture(scope="module")
def prompts():
    r = np.random.default_rng(3)
    return [list(r.integers(1, 97, int(r.integers(6, 24))))
            for _ in range(6)]


@pytest.fixture(scope="module")
def fp32_oracle(fp32_runner, prompts):
    return [naive_generate(fp32_runner, p, SamplingParams(max_tokens=10),
                           max_model_len=96) for p in prompts]


def _int8_pools(B=2, n_kv=2, d=16, ps=8, pages=6, n_rep=1, T=8):
    nb = 1 + B * pages
    kp = jnp.asarray(rng.integers(-127, 128, (nb, ps, n_kv, d)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (nb, ps, n_kv, d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(1e-3, 5e-2, (nb, n_kv)), jnp.float32)
    vs = jnp.asarray(rng.uniform(1e-3, 5e-2, (nb, n_kv)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(np.arange(1, nb))
                      .reshape(B, pages).astype(np.int32))
    q = jnp.asarray(rng.standard_normal((B, T, n_kv * n_rep, d)),
                    jnp.float32)
    return q, kp, vp, ks, vs, tbl


# -------------------------------------------------- kernel int8 sweep


@pytest.mark.parametrize("q_len,start_pos", [
    (1, 0), (1, 7), (1, 8), (1, 37),        # decode at page boundaries
    (5, 0), (8, 0),                          # fresh prefill
    (3, 13), (8, 16), (6, 40),               # offset chunks
])
@pytest.mark.parametrize("n_rep", [1, 2, 4])
def test_int8_kernel_vs_reference_sweep(q_len, start_pos, n_rep):
    """Kernel-vs-oracle stays exact IN THE INT8 DOMAIN: both read the
    same codes and the same per-page-per-head scales."""
    q, kp, vp, ks, vs, tbl = _int8_pools(n_rep=n_rep)
    starts = jnp.asarray([start_pos, max(0, start_pos - 2)], jnp.int32)
    qlens = jnp.asarray([q_len, max(1, q_len - 1)], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                 interpret=True, k_scale=ks, v_scale=vs)
    ref = ragged_reference(q, kp, vp, tbl, starts, qlens,
                           k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_int8_kernel_mixed_spans_and_dead_slot():
    q, kp, vp, ks, vs, tbl = _int8_pools(B=3, n_rep=2)
    starts = jnp.asarray([33, 8, 0], jnp.int32)
    qlens = jnp.asarray([1, 8, 0], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                 interpret=True, k_scale=ks, v_scale=vs)
    ref = ragged_reference(q, kp, vp, tbl, starts, qlens,
                           k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert bool((np.asarray(out[2]) == 0.0).all()), "dead slot must be 0"
    assert np.isfinite(np.asarray(out)).all()


def test_int8_kernel_bucket_invariance():
    """The same spans in a 2x-wider padded bucket give bit-identical
    live rows — bucket padding never leaks into the int8 dequant."""
    q, kp, vp, ks, vs, tbl = _int8_pools(T=4)
    starts = jnp.asarray([5, 17], jnp.int32)
    qlens = jnp.asarray([4, 3], jnp.int32)
    tight = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                   interpret=True, k_scale=ks, v_scale=vs)
    q_wide = jnp.concatenate(
        [q, jnp.asarray(rng.standard_normal(q.shape), jnp.float32)], axis=1)
    wide = ragged_paged_attention(q_wide, kp, vp, tbl, starts, qlens,
                                  interpret=True, k_scale=ks, v_scale=vs)
    np.testing.assert_array_equal(np.asarray(tight[0, :4]),
                                  np.asarray(wide[0, :4]))
    np.testing.assert_array_equal(np.asarray(tight[1, :3]),
                                  np.asarray(wide[1, :3]))
    assert bool((np.asarray(wide[:, 4:]) == 0.0).all())


def test_int8_kernel_page_count_invariance():
    """3x more (dead) table pages change nothing: the walk and its
    per-page scale lookup only ever touch live pages."""
    q, kp, vp, ks, vs, tbl = _int8_pools(pages=4)
    starts = jnp.asarray([9, 21], jnp.int32)
    qlens = jnp.asarray([4, 1], jnp.int32)
    out = ragged_paged_attention(q, kp, vp, tbl, starts, qlens,
                                 interpret=True, k_scale=ks, v_scale=vs)
    wide_tbl = jnp.concatenate([tbl, tbl[:, :1].repeat(8, 1)], axis=1)
    out_w = ragged_paged_attention(q, kp, vp, wide_tbl, starts, qlens,
                                   interpret=True, k_scale=ks, v_scale=vs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_w))


# ------------------------------------------- quantize-append round trip


def test_quantized_append_roundtrip_decode_and_chunk():
    """Decode-style (one token at a time) and chunk-style (whole page in
    one write) appends both dequantize back within the page's scale —
    the requant-on-grow path loses at most one extra rounding step."""
    P, ps, H, d = 5, 4, 2, 8
    codes = jnp.zeros((P, ps, H, d), jnp.int8)
    scales = jnp.zeros((P, H), jnp.float32)
    vals = rng.standard_normal((ps, H, d)).astype(np.float32)
    for t in range(ps):          # decode-style into page 2
        codes, scales = quantized_page_write(
            codes, scales, jnp.asarray([[2]], jnp.int32),
            jnp.asarray([[t]], jnp.int32), jnp.asarray(vals[t][None, None]))
    wp = jnp.full((1, ps), 3, jnp.int32)
    wo = jnp.arange(ps, dtype=jnp.int32)[None]
    codes, scales = quantized_page_write(codes, scales, wp, wo,
                                         jnp.asarray(vals[None]))
    for page in (2, 3):
        deq = (np.asarray(codes[page]).astype(np.float32)
               * np.asarray(scales[page])[None, :, None])
        bound = np.asarray(scales[page])[None, :, None] * 1.01 + 1e-7
        assert (np.abs(deq - vals) <= bound).all(), f"page {page} drifted"
    # untouched pages' codes stay zero and their scales stay zero
    assert not np.asarray(codes[1]).any() and not np.asarray(scales[1]).any()


def test_quantized_append_page_restart_resets_scale():
    """A write landing on slot 0 restarts the page's scale: a page
    recycled from the free list must not inherit its previous tenant's
    (possibly huge) range — quantization quality cannot ratchet away."""
    P, ps, H, d = 3, 4, 1, 4
    codes = jnp.zeros((P, ps, H, d), jnp.int8)
    scales = jnp.zeros((P, H), jnp.float32)
    big = jnp.full((1, 1, H, d), 100.0, jnp.float32)
    codes, scales = quantized_page_write(
        codes, scales, jnp.asarray([[1]], jnp.int32),
        jnp.asarray([[0]], jnp.int32), big)
    assert float(scales[1, 0]) == pytest.approx(100.0 / 127.0)
    tiny = jnp.full((1, 1, H, d), 0.01, jnp.float32)
    codes, scales = quantized_page_write(
        codes, scales, jnp.asarray([[1]], jnp.int32),
        jnp.asarray([[0]], jnp.int32), tiny)
    assert float(scales[1, 0]) == pytest.approx(0.01 / 127.0)
    deq = float(codes[1, 0, 0, 0]) * float(scales[1, 0])
    assert deq == pytest.approx(0.01, rel=0.02)


def test_copy_page_copies_scales():
    """COW's data move: a forked page carries codes AND its scale row."""
    pool = KVCachePool(2, 6, 4, 2, 8, kv_dtype="int8")
    k, v, ks, vs = pool.pools[0]
    pool.pools[0] = (k.at[1].set(7), v, ks.at[1].set(0.25), vs)
    pool.copy_page(1, 4)
    k2, _, ks2, _ = pool.pools[0]
    assert int(k2[4, 0, 0, 0]) == 7
    assert float(ks2[4, 0]) == pytest.approx(0.25)


# ------------------------------------------------------ byte accounting


def test_pool_bytes_count_scales_and_hit_reduction_floor():
    pool32 = KVCachePool(2, 10, 8, 2, 16)
    pool8 = KVCachePool(2, 10, 8, 2, 16, kv_dtype="int8")
    per_kv = 8 * 2 * 16
    assert pool32.page_bytes() == 2 * 2 * per_kv * 4
    assert pool8.page_bytes() == 2 * 2 * (per_kv + 2 * 4)
    assert pool8.memory_bytes() == 10 * pool8.page_bytes()
    assert pool32.kv_bytes_reduction_x() == 1.0
    assert pool8.kv_bytes_reduction_x() >= 1.8     # acceptance floor
    assert pool8.memory_bytes() < pool32.memory_bytes() / 1.8


def test_runner_attn_bytes_use_quantized_page_bytes(fp32_runner,
                                                    int8_runner):
    assert int8_runner._kv_page_bytes() < fp32_runner._kv_page_bytes() / 1.8
    fp32_runner.reset_attn_counters()
    int8_runner.reset_attn_counters()
    fp32_runner._account_attn("ragged", np.asarray([16]), np.asarray([1]), 4)
    int8_runner._account_attn("ragged", np.asarray([16]), np.asarray([1]), 4)
    assert (fp32_runner.attn_kv_bytes_read
            >= 1.8 * int8_runner.attn_kv_bytes_read)
    fp32_runner.reset_attn_counters()
    int8_runner.reset_attn_counters()


def test_engine_snapshot_reports_reduction_gauges(int8_runner):
    eng = ServingEngine(int8_runner, num_blocks=20, max_batch_size=2,
                        max_model_len=96)
    snap = eng.metrics.snapshot()
    assert snap["kv_bytes_reduction_x"] >= 1.8
    assert snap["sessions_per_pool_x"] >= 1.8


# ------------------------------------------------------- engine e2e gate


def _run_engine(runner, prompts, **kw):
    kw.setdefault("num_blocks", 40)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_model_len", 96)
    eng = ServingEngine(runner, audit=True, **kw)
    rids = [eng.add_request(p, SamplingParams(max_tokens=10))
            for p in prompts]
    outs = eng.run()
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()
    return eng, [outs[r].output_tokens for r in rids]


def test_fp32_default_regression_pin(fp32_runner, prompts, fp32_oracle):
    """The default path stays bit-exact vs naive_generate — quantization
    landing must not perturb a single fp32 token."""
    _, toks = _run_engine(fp32_runner, prompts, enable_prefix_cache=True,
                          max_prefill_tokens_per_step=16, ragged_batch=True)
    assert toks == fp32_oracle


def _agreement(streams, oracle):
    match = sum(int(a == b) for s, o in zip(streams, oracle)
                for a, b in zip(s, o))
    total = sum(len(o) for o in oracle)
    return match / total


@pytest.mark.slow
def test_int8_kv_engine_greedy_agreement(int8_runner, prompts, fp32_oracle):
    """The tentpole accuracy gate: int8-KV engine streams agree with the
    fp32 oracle >= 99% greedy tokens on the real Llama config."""
    _, toks = _run_engine(int8_runner, prompts, enable_prefix_cache=True,
                          max_prefill_tokens_per_step=16, ragged_batch=True)
    assert _agreement(toks, fp32_oracle) >= 0.99


def test_int8_kv_teacher_forced_top5_overlap(llama_model, fp32_runner,
                                             int8_runner, prompts):
    """Teacher-forced per-step logits: mean |Δlogit| small and top-5
    overlap >= 0.99 vs the fp32 oracle over the same token stream."""
    overlaps, dl = [], []
    for p in prompts[:2]:
        pools, tbls = [], []
        for r in (fp32_runner, int8_runner):
            pool = KVCachePool(r.num_layers, 13, 8, r.n_kv_heads,
                               r.head_dim, r.dtype, kv_dtype=r.kv_dtype)
            pages = pool.allocator.alloc(12)
            tbls.append(pool.pad_table(pages, 12))
            pools.append(pool.pools)
        l_ref, pools[0] = fp32_runner.prefill(p, tbls[0], pools[0])
        l_q, pools[1] = int8_runner.prefill(p, tbls[1], pools[1])
        toks = list(p)
        for _ in range(12):
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
            l_q, pools[1] = int8_runner.decode(
                np.asarray([tok], np.int32),
                np.asarray(tbls[1], np.int32)[None], pos, pools[1])
            l_ref, l_q = l_ref[0], l_q[0]
    assert np.mean(overlaps) >= 0.99
    assert np.mean(dl) < 0.05


def test_int8_kv_forced_ragged_kernel_engine(llama_model, prompts,
                                             fp32_oracle):
    """The kernel path itself (interpret mode) under the engine: int8
    pools + forced ragged dispatch, accuracy-gated like auto."""
    runner = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                         kv_dtype="int8", attn_impl="ragged")
    _, toks = _run_engine(runner, prompts[:3], ragged_batch=True)
    assert _agreement(toks, fp32_oracle[:3]) >= 0.99


@pytest.mark.slow
def test_int8_weights_engine_agreement(llama_model, fp32_runner, prompts,
                                       fp32_oracle):
    """Weight-only int8 (per-output-channel scales, dequant in the
    matmul epilogue) composes with int8 KV. The engine must run clean
    (audited, leak-free); the accuracy gate is PER-DECISION (teacher-
    forced >= 95% argmax agreement): weight quantization may flip a
    near-tie argmax on a random-init model, after which a free-running
    stream legitimately cascades — per-decision agreement is the
    measure that doesn't punish the cascade."""
    runner = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                         kv_dtype="int8", weight_dtype="int8")
    _run_engine(runner, prompts, enable_prefix_cache=True,
                max_prefill_tokens_per_step=16, ragged_batch=True)
    agree = total = 0
    for p in prompts:
        pools, tbls = [], []
        for r in (fp32_runner, runner):
            pool = KVCachePool(r.num_layers, 13, 8, r.n_kv_heads,
                               r.head_dim, r.dtype, kv_dtype=r.kv_dtype)
            pages = pool.allocator.alloc(12)
            tbls.append(pool.pad_table(pages, 12))
            pools.append(pool.pools)
        la, pools[0] = fp32_runner.prefill(p, tbls[0], pools[0])
        lb, pools[1] = runner.prefill(p, tbls[1], pools[1])
        toks = list(p)
        for _ in range(10):
            a, b = np.asarray(la), np.asarray(lb)
            agree += int(np.argmax(a) == np.argmax(b))
            total += 1
            tok = int(np.argmax(a))
            pos = np.asarray([len(toks)], np.int32)
            toks.append(tok)
            la, pools[0] = fp32_runner.decode(
                np.asarray([tok], np.int32),
                np.asarray(tbls[0], np.int32)[None], pos, pools[0])
            lb, pools[1] = runner.decode(
                np.asarray([tok], np.int32),
                np.asarray(tbls[1], np.int32)[None], pos, pools[1])
            la, lb = la[0], lb[0]
    assert agree / total >= 0.95, (agree, total)


def test_naive_generate_builds_int8_pool(int8_runner, prompts):
    """The oracle helper follows the runner's kv_dtype (self-consistent
    quantized generation, used by the smoke drills)."""
    out = naive_generate(int8_runner, prompts[0],
                         SamplingParams(max_tokens=6), max_model_len=96)
    assert len(out) == 6


# ------------------------------- COW / prefix cache / rollback on int8


def test_int8_cow_prefix_cache_truncate_under_auditor(int8_runner):
    """Shared headers + chunked prefill + speculation: prefix-cache
    adoption and rejected-tail truncate run on the quantized pools with
    the auditor armed; drained engine leaks nothing."""
    r = np.random.default_rng(5)
    header = list(r.integers(1, 97, 17))
    prompts = [header + list(r.integers(1, 97, int(r.integers(3, 8))))
               for _ in range(5)]
    # periodic tails so the n-gram proposer actually fires (rollback path)
    prompts += [(header * 3)[:30] for _ in range(2)]
    eng, _ = _run_engine(int8_runner, prompts, enable_prefix_cache=True,
                         max_prefill_tokens_per_step=16, ragged_batch=True,
                         num_speculative_tokens=3)
    m = eng.metrics.snapshot()
    assert m["prefix_hit_tokens"] > 0, "prefix cache never hit"
    assert m["spec_proposed_tokens"] > 0, "speculation never proposed"


def test_int8_cow_fork_copies_codes_and_scales():
    """ensure_writable on a SHARED int8 page forks it — codes AND scale
    row travel to the fork, the shared original is never mutated."""
    from paddle_tpu.serving.kv_cache import SequenceKV

    pool = KVCachePool(1, 8, 4, 2, 8, kv_dtype="int8")
    kv = SequenceKV(pool)
    kv.pages = pool.allocator.alloc(1)
    kv.num_tokens = 2
    page = kv.pages[0]
    k, v, ks, vs = pool.pools[0]
    pool.pools[0] = (k.at[page].set(5), v, ks.at[page].set(0.5), vs)
    pool.allocator.incref(page)            # simulate a second owner
    forked = kv.ensure_writable(1, 2)
    assert forked == 1 and kv.pages[0] != page
    k2, _, ks2, _ = pool.pools[0]
    assert int(k2[kv.pages[0], 0, 0, 0]) == 5
    assert float(ks2[kv.pages[0], 0]) == pytest.approx(0.5)
    assert pool.allocator.refcount(page) == 1   # original kept one owner
    kv.release()
    pool.allocator.decref(page)
    assert pool.allocator.check_no_leaks()


def test_int8_decode_horizon_under_auditor(int8_runner, prompts):
    eng, toks = _run_engine(int8_runner, prompts[:4], decode_horizon=4)
    assert eng.metrics.snapshot()["decode_horizon_steps"] > 0
    assert all(len(t) == 10 for t in toks)


# --------------------------------------------------- tp=2 scale sharding


def test_tp2_per_shard_scale_pool_pin(llama_model, prompts, fp32_oracle):
    """Every model shard holds ALL pages' scale rows at n_kv/tp heads —
    the scale pool shards exactly like its code pool."""
    runner = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                         kv_dtype="int8")
    runner.shard(serving_mesh(data=1, model=2))
    eng, toks = _run_engine(runner, prompts[:3])
    pool = eng.pool
    assert pool.per_shard_memory_bytes() == pool.memory_bytes() // 2
    for layer in pool.pools:
        assert len(layer) == 4
        k, v, ks, vs = layer
        for arr in (ks, vs):
            shapes = {tuple(s.data.shape) for s in arr.addressable_shards}
            assert shapes == {(pool.num_blocks, pool.n_kv_heads // 2)}
    assert _agreement(toks, fp32_oracle[:3]) >= 0.99


def test_auditor_catches_broken_scale_pool(int8_runner):
    """The scale-pool invariant is ENFORCED, not documentation: an int8
    pool whose layer tuple lost its scales fails the audit loudly."""
    eng = ServingEngine(int8_runner, num_blocks=20, max_batch_size=2,
                        max_model_len=96, audit=False)
    k, v, ks, vs = eng.pool.pools[0]
    eng.pool.pools[0] = (k, v)                    # drop the scale pools
    with pytest.raises(InvariantViolation, match="kv_dtype=int8"):
        audit_engine(eng)
    eng.pool.pools[0] = (k, v, ks[:, :1], vs)     # wrong scale shape
    with pytest.raises(InvariantViolation, match="one scale per page"):
        audit_engine(eng)


# ------------------------------------------------- snapshot / restore


def test_snapshot_restore_roundtrips_dtype_knobs(llama_model, int8_runner,
                                                 prompts):
    eng = ServingEngine(int8_runner, num_blocks=40, max_batch_size=4,
                        max_model_len=96)
    for p in prompts[:3]:
        eng.add_request(p, SamplingParams(max_tokens=8))
    for _ in range(3):
        eng.step()
    snap = eng.snapshot()
    assert snap["config"]["kv_dtype"] == "int8"
    assert snap["config"]["weight_dtype"] == "fp32"
    # restore onto a FRESH runner with the same knobs: the continued
    # streams equal an uninterrupted run of the same quantized engine
    fresh = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                        kv_dtype="int8")
    restored = ServingEngine.restore(fresh, snap)
    assert restored.kv_dtype == "int8"
    outs = restored.run()
    twin = ServingEngine(fresh, num_blocks=40, max_batch_size=4,
                         max_model_len=96)
    t_ids = [twin.add_request(p, SamplingParams(max_tokens=8))
             for p in prompts[:3]]
    t_outs = twin.run()
    got = sorted((o.request_id, tuple(o.output_tokens))
                 for o in outs.values())
    want = sorted((rid, tuple(t_outs[rid].output_tokens))
                  for rid in t_ids)
    assert [t for _, t in got] == [t for _, t in want]


# ------------------------------------ weight-quant layout satellite


def test_weight_quantize_rejects_fused_qkv_3d_layout():
    """(3, nh, d) fused-QKV layouts mis-scale silently if quantized raw
    (scales would reduce over the qkv axis, not the in-dim) — the
    helper now fails loudly naming the layout and the fix."""
    from paddle_tpu.quantization.int8 import _weight_quantize

    w = jnp.asarray(rng.standard_normal((3, 4, 8)), jnp.float32)
    with pytest.raises(ValueError, match=r"\(3, num_heads, head_dim\)"):
        _weight_quantize(w)
    # the 2-D flat spelling of the same fused weight quantizes fine
    q, s = _weight_quantize(w.reshape(3 * 4, 8).T.reshape(8, 12))
    assert q.dtype == jnp.int8 and s.shape == (12,)


def test_gpt_weight_int8_serves_and_agrees():
    """GPT's fused QKV is stored FLAT [H, 3*nh*d], so weight_dtype=int8
    quantizes per fused output column correctly end to end."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=96, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPT(cfg)
    model.eval()
    r32 = GPTRunner(model, block_size=8, max_model_len=64)
    r8 = GPTRunner(model, block_size=8, max_model_len=64,
                   kv_dtype="int8", weight_dtype="int8")
    assert any(k.endswith("::scale") for k in r8.params)
    pr = np.random.default_rng(7)
    prompts = [list(pr.integers(1, 96, int(pr.integers(5, 15))))
               for _ in range(4)]
    oracle = [naive_generate(r32, p, SamplingParams(max_tokens=8),
                             max_model_len=64) for p in prompts]
    eng = ServingEngine(r8, num_blocks=40, max_batch_size=4,
                        max_model_len=64, audit=True)
    rids = [eng.add_request(p, SamplingParams(max_tokens=8))
            for p in prompts]
    outs = eng.run()
    assert eng.pool.allocator.check_no_leaks()
    toks = [outs[r].output_tokens for r in rids]
    assert _agreement(toks, oracle) >= 0.99
