"""The Olmo-Hybrid block (three Gated DeltaNet layers to one full-attention
layer) at toy widths, two periods, each part against the plain reference of
bench/reference_olmo_hybrid.py on seeded weights: the Layer, the three forms
of the gated delta rule, state slots beside pages (prefill, chunks, decode,
padding and frozen rows), the engine with more requests than slots,
preemption, the options a recurrent state refuses, the pool's bytes, the
counters."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import create_serving_engine
from paddle_tpu.models.olmo_hybrid import (
    OlmoHybridConfig, OlmoHybridForCausalLM,
)
from paddle_tpu.ops import gated_delta as gd
from paddle_tpu.ops.pallas import gated_delta_decode as gk
from paddle_tpu.serving import KVCachePool, SamplingParams, naive_generate
from paddle_tpu.serving.model_runner import build_runner
from paddle_tpu.serving.runners.olmo_hybrid import OlmoHybridRunner

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
import reference_olmo_hybrid as R  # noqa: E402

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# hidden 64, 4 heads; a state of 8 x 16 a head; two periods
CFG = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
           num_hidden_layers=8, num_attention_heads=4, layer_types=PERIOD * 2,
           linear_num_key_heads=4, linear_num_value_heads=4,
           linear_key_head_dim=8, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
           rms_norm_eps=1e-6, rope_parameters={"rope_theta": None},
           max_seq_len=128)
MAX_LEN = 96


def build(seed=0, **over):
    cfg = dict(CFG, **over)
    weights = R.init_weights(cfg, R.seed_key(seed))
    model = OlmoHybridForCausalLM(OlmoHybridConfig(**cfg))
    missing, unexpected = model.set_state_dict(
        {k: paddle.Tensor(v) for k, v in weights.items()})
    assert not missing and not unexpected
    model.eval()
    return cfg, weights, model


@pytest.fixture(scope="module")
def toy():
    return build()


_REF = {}


def ref_logits(cfg, weights, tokens):
    """The reference's logits at every position of `tokens`: one compiled
    forward a configuration, over the sequence padded to MAX_LEN (causal:
    what follows a position does not reach it)."""
    key = repr(sorted(cfg.items()))
    if key not in _REF:
        _REF[key] = jax.jit(lambda w, t: R.logits_at(cfg, w, t, 0, MAX_LEN))
    padded = np.zeros(MAX_LEN, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(_REF[key](weights, jnp.asarray(padded)))[:len(tokens)]


def ref_generate(cfg, weights, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(ref_logits(cfg, weights, seq)[-1])))
    return seq[len(prompt):]


def prompts_of(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n).tolist() for n in lengths]


# ------------------------------------------------------------------ Layer


@pytest.mark.parametrize("over", [{}, {"rope_parameters": {
    "rope_theta": 500000.0}}, {"linear_allow_neg_eigval": False}],
    ids=["published", "rope-500000", "no-neg-eigval"])
def test_layer_equals_the_reference(over):
    cfg, weights, model = build(seed=3, **over)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 37))
    got = np.asarray(model(paddle.Tensor(jnp.asarray(tokens)))._value)
    for row, ids in zip(got, tokens):
        np.testing.assert_allclose(row, ref_logits(cfg, weights, ids),
                                   atol=2e-5)


def test_published_pattern_cut_in_depth_runs_its_first_kinds():
    cfg = OlmoHybridConfig(**dict(CFG, num_hidden_layers=4,
                                  layer_types=PERIOD * 8))
    assert cfg.layer_types == tuple(PERIOD)
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridConfig(**dict(CFG, layer_types=PERIOD))


def test_deferred_layer_is_a_vessel():
    """`init="deferred"`: placeholders on the host until `set_state_dict`,
    and again once a runner has taken the weights."""
    cfg, weights, _ = build()
    model = OlmoHybridForCausalLM(OlmoHybridConfig(**cfg, init="deferred"))
    assert all(isinstance(p._value, np.ndarray)
               for _, p in model.named_parameters())
    model.set_state_dict({k: paddle.Tensor(v) for k, v in weights.items()})
    runner = build_runner(model)
    assert all(isinstance(p._value, np.ndarray)
               for _, p in model.named_parameters())
    prompt = prompts_of([11])[0]
    assert naive_generate(runner, prompt, SamplingParams(max_tokens=4),
                          max_model_len=MAX_LEN) == ref_generate(
                              cfg, weights, prompt, 4)


# --------------------------------------------- the rule's three forms


def draw(T, H=3, dk=8, dv=16, seed=0, neg=True, decay=(-1.0, 0.0)):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = R.l2_normalize(jax.random.normal(ks[0], (T, H, dk))) * dk ** -0.5
    k = R.l2_normalize(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (T, H))) * (2 if neg
                                                               else 1)
    g = jax.random.uniform(ks[4], (T, H), minval=decay[0], maxval=decay[1])
    S0 = 0.1 * jax.random.normal(ks[5], (H, dk, dv))
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("neg", [True, False], ids=["neg-eigval", "plain"])
@pytest.mark.parametrize("decay", [(-1.0, 0.0), (-1e-4, 0.0), (-20.0, -5.0)],
                         ids=["decay-mid", "decay-near-1", "decay-near-0"])
@pytest.mark.parametrize("T", [100, 8], ids=["100-tokens", "8-tokens"])
def test_three_forms_agree(T, decay, neg):
    """Token by token (the reference's scan), one token at a time through
    the single-token update, and the chunked form on a length that is no
    multiple of the chunk (padded: beta = 0, no decay), from a state that
    is not zero."""
    q, k, v, g, beta, S0 = draw(T, neg=neg, decay=decay)
    o_ref = np.asarray(R.delta_rule_scan(q, k, v, g, beta))
    o_rec, _ = gd.gated_delta_recurrence(q, k, v, g, beta,
                                         jnp.zeros_like(S0))
    np.testing.assert_allclose(np.asarray(o_rec), o_ref, atol=1e-6)
    o_rec, S_rec = gd.gated_delta_recurrence(q, k, v, g, beta, S0)
    S, outs = S0[None], []
    for t in range(T):
        o, S = gd.gated_delta_step(S, q[t][None], k[t][None], v[t][None],
                                   g[t][None], beta[t][None])
        outs.append(o[0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs)),
                               np.asarray(o_rec), atol=1e-5)
    np.testing.assert_allclose(np.asarray(S[0]), np.asarray(S_rec),
                               atol=1e-5)
    bucket = 128 if T > 8 else 8
    pad = lambda x, fill=0.0: jnp.concatenate(
        [x, jnp.full((bucket - T,) + x.shape[1:], fill, x.dtype)])
    o_ch, S_ch = gd.gated_delta_chunked(pad(q, 0.3), pad(k, 0.3), pad(v, 7.0),
                                        pad(g), pad(beta), S0)
    # the inverse of a chunk's unit triangle costs float32 digits where
    # nothing decays and keys of 8 values repeat: 1.2e-4 at the worst
    np.testing.assert_allclose(np.asarray(o_ch[:T]), np.asarray(o_rec),
                               atol=5e-4)
    np.testing.assert_allclose(np.asarray(S_ch), np.asarray(S_rec),
                               atol=5e-4)


def test_decode_kernel_is_the_single_token_update():
    """The Pallas form over the pool (interpret mode here), rows that are
    not live and the scratch slot untouched to the bit."""
    B, H, dk, dv = 5, 4, 8, 16
    q, k, v, g, beta, _ = draw(B, H, dk, dv, seed=3)
    S = jax.random.normal(jax.random.key(9), (B + 1, H, dk, dv))
    live = jnp.array([1, 1, 0, 1, 1], bool)
    o_want, S_want = gd.gated_delta_step(S[:B], q, k, v, g, beta)
    o, pool = gk.gated_delta_decode(gk.pool_form(S), q, k, v, g, beta, live,
                                    interpret=True)
    got = gk.head_form(pool, H)
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(o)[on], np.asarray(o_want)[on],
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[:B])[on],
                               np.asarray(S_want)[on], atol=1e-6)
    assert np.array_equal(np.asarray(got[2]), np.asarray(S[2]))
    assert np.array_equal(np.asarray(got[B]), np.asarray(S[B]))
    assert gk.heads_per_block(30, 96, 192) == 10      # the published widths
    with pytest.raises(ValueError, match="pairs"):
        gk.heads_per_block(3, 8, 16)


# ------------------------------------------- state slots beside pages


def runner_of(model, impl="reference", **kw):
    return build_runner(model, block_size=8, max_model_len=MAX_LEN,
                        attn_impl=impl, **kw)


@pytest.mark.parametrize("impl", ["reference", "ragged"])
def test_prefill_then_decode_equals_the_full_forward(toy, impl):
    """Logits of a prefill and of every decode step after it, through a
    state slot and pages, against the reference's one forward over the
    whole sequence."""
    cfg, weights, model = toy
    runner = runner_of(model, impl)
    pool = KVCachePool.for_runner(runner, 16, state_slots=3)
    table = pool.pad_table(pool.allocator.alloc(12), 12)
    seq = prompts_of([29], seed=5)[0]
    want = ref_logits(cfg, weights, seq)
    logits, pools = runner.prefill_chunk(seq[:21], 0, table, pool.pools,
                                         slot=0)
    np.testing.assert_allclose(np.asarray(logits), want[20], atol=3e-5)
    tables = np.asarray([table, [0] * 12], np.int32)
    for t in range(21, 29):
        logits, pools = runner.decode(np.asarray([seq[t], 0], np.int32),
                                      tables, np.asarray([t, 0], np.int32),
                                      pools)
        np.testing.assert_allclose(np.asarray(logits)[0], want[t], atol=3e-5)


def test_chunked_prefill_continues_from_the_slots_state(toy):
    cfg, weights, model = toy
    runner = runner_of(model)
    pool = KVCachePool.for_runner(runner, 16, state_slots=3)
    table = pool.pad_table(pool.allocator.alloc(12), 12)
    seq = prompts_of([43], seed=6)[0]
    want = ref_logits(cfg, weights, seq)
    pools = pool.pools
    # someone else's state in the slot first: position 0 resets it
    _, pools = runner.prefill_chunk(prompts_of([9], seed=7)[0], 0, table,
                                    pools, slot=1)
    for lo, hi in ((0, 17), (17, 30), (30, 43)):
        logits, pools = runner.prefill_chunk(seq[lo:hi], lo, table, pools,
                                             slot=1)
        np.testing.assert_allclose(np.asarray(logits), want[hi - 1],
                                   atol=3e-5)


def test_padding_and_frozen_rows_leave_state_untouched(toy):
    """A dead slot of the batch (an all-scratch table) and a frozen row of
    a horizon (`write_mask`) write back the state they read, to the bit;
    the scratch slot is never written; a prefill's padding changes
    nothing (a prompt in two buckets ends in the same state)."""
    cfg, weights, model = toy
    runner = runner_of(model)
    pool = KVCachePool.for_runner(runner, 16, state_slots=4)
    table = pool.pad_table(pool.allocator.alloc(12), 12)
    seq = prompts_of([13], seed=8)[0]
    _, pools = runner.prefill_chunk(seq, 0, table, pool.pools, slot=1)
    _, pools = runner.prefill_chunk(seq[:5], 0, table, pools, slot=2)
    before = jax.tree_util.tree_map(np.asarray, pools[1])
    tables = np.asarray([[0] * 12, table, table], np.int32)
    args = (runner.params, np.asarray([[1], [2], [3]], np.int32), tables,
            np.asarray([0, 13, 5], np.int32), pools)
    _, after, _ = jax.jit(runner._decode_step)(
        *args, write_mask=jnp.array([True, True, False]))
    for (s0, c0), (s1, c1) in zip(before, jax.tree_util.tree_map(
            np.asarray, after[1])):
        for old, new in ((s0, s1), (c0, c1)):
            assert np.array_equal(old[0], new[0])        # dead row
            assert np.array_equal(old[2], new[2])        # frozen row
            assert np.array_equal(old[3], new[3])        # scratch slot
            assert not np.array_equal(old[1], new[1])    # the live row
    # nine tokens in a bucket of 16 (seven rows of padding) against eight
    # in a bucket of 8 and the ninth as a decode step: the same state
    _, padded = runner.prefill_chunk(seq[:9], 0, table, pool.pools, slot=0)
    _, exact = runner.prefill_chunk(seq[:8], 0, table, pool.pools, slot=0)
    _, exact = runner.decode(np.asarray([seq[8]], np.int32),
                             np.asarray([table], np.int32),
                             np.asarray([8], np.int32), exact)
    for (s0, c0), (s1, c1) in zip(*(jax.tree_util.tree_map(np.asarray, x[1])
                                    for x in (padded, exact))):
        np.testing.assert_allclose(s0[0], s1[0], atol=1e-5)
        np.testing.assert_allclose(c0[0], c1[0], atol=1e-6)


# ----------------------------------------------------------- the engine


def serve(model, prompts, max_tokens=10, **kw):
    kw = {"num_blocks": 64, "block_size": 8, "max_batch_size": 3,
          "max_model_len": MAX_LEN, "audit": True, **kw}
    eng = create_serving_engine(model, **kw)
    rids = [eng.add_request(p, SamplingParams(max_tokens=max_tokens))
            for p in prompts]
    outs = eng.run()
    return eng, [outs[r].output_tokens for r in rids]


@pytest.mark.parametrize("options", [
    {}, {"attn_impl": "ragged"}, {"max_prefill_tokens_per_step": 8},
    {"decode_horizon": 4}, {"decode_horizon": 4, "horizon_early_stop": True},
    {"pipelined": True}],
    ids=["default", "kernels", "chunked-prefill", "horizon",
         "horizon-early-stop", "pipelined"])
def test_engine_serves_token_for_token_with_more_requests_than_slots(
        toy, options):
    """Seven requests through three slots: every slot is taken again by a
    request that must not see its last holder's state."""
    cfg, weights, model = toy
    prompts = prompts_of((5, 17, 33, 9, 21, 40, 13))
    eng, got = serve(model, prompts, **options)
    for p, tokens in zip(prompts, got):
        assert tokens == ref_generate(cfg, weights, p, 10)
    assert eng.pool.allocator.check_no_leaks()
    snap = eng.metrics.snapshot()
    assert snap["state_slot_resets"] == 7
    assert snap["state_slots_live"] == 0


def test_a_skipped_reset_serves_wrong_tokens(toy, monkeypatch):
    """The test above has teeth: with the reset taken out, a slot's second
    holder starts from its first holder's state and the tokens differ."""
    cfg, weights, model = toy
    monkeypatch.setattr(OlmoHybridRunner, "_starts_fresh",
                        staticmethod(lambda pos_q: jnp.bool_(False)))
    prompts = prompts_of((5, 17, 33, 9, 21, 40, 13))
    _, got = serve(model, prompts)
    want = [ref_generate(cfg, weights, p, 10) for p in prompts]
    assert got[:3] == want[:3]          # first holders: the slots were zero
    assert got != want


def test_preemption_by_recompute_gives_the_same_tokens(toy):
    cfg, weights, model = toy
    prompts = prompts_of((20, 26, 23, 18, 25), seed=4)
    eng, got = serve(model, prompts, max_tokens=24, num_blocks=14)
    assert eng.metrics.preemptions.value >= 1, "pool must force preemption"
    for p, tokens in zip(prompts, got):
        assert tokens == ref_generate(cfg, weights, p, 24)
    assert eng.pool.allocator.check_no_leaks()


@pytest.mark.parametrize("option", [
    {"enable_prefix_cache": True}, {"host_tier_pages": 8},
    {"kv_store": "store"}, {"num_speculative_tokens": 2},
    {"ragged_batch": True}, {"role": "prefill"}],
    ids=lambda o: next(iter(o)))
def test_options_that_copy_or_roll_back_state_are_refused_by_name(toy,
                                                                   option):
    _, _, model = toy
    name = next(iter(option))
    if name == "kv_store":
        from paddle_tpu.serving.kv_cache import SharedKVStore

        probe = KVCachePool.for_runner(runner_of(model), 4)
        option = {"kv_store": SharedKVStore(
            [[(tuple(a.shape[1:]), str(a.dtype)) for a in layer]
             for layer in probe.page_pools], 4)}
    with pytest.raises(ValueError, match=name):
        create_serving_engine(model, num_blocks=32, block_size=8,
                              max_batch_size=2, max_model_len=MAX_LEN,
                              **option)


def test_quantized_weights_and_fp8_pages_serve(toy):
    """The benchmark's controls: the runner's own int8 matrices through
    `_mm`, and float8 pages on the layers that page; both close to the
    sound logits and not equal to them."""
    cfg, weights, model = toy
    seq = prompts_of([25], seed=2)[0]
    want = ref_logits(cfg, weights, seq)[-1]
    spread = float(np.std(want))
    for kw in ({"weight_dtype": "int8"}, {"kv_dtype": "fp8"}):
        runner = runner_of(model, **kw)
        pool = KVCachePool.for_runner(runner, 16)
        table = pool.pad_table(pool.allocator.alloc(12), 12)
        logits, _ = runner.prefill_chunk(seq, 0, table, pool.pools)
        err = float(np.sqrt(np.mean((np.asarray(logits) - want) ** 2)))
        assert 1e-5 < err < 0.5 * spread, (kw, err, spread)
    for kw in ({"kv_dtype": "int8"}, {"kv_dtype": "mixed"},
               {"weight_dtype": "int4"}):
        with pytest.raises(ValueError):
            runner_of(model, **kw)


# ----------------------------------------------------- the pool's bytes


def test_pool_bytes_equal_the_configurations_arithmetic(toy):
    _, _, model = toy
    eng = create_serving_engine(model, num_blocks=20, block_size=8,
                                max_batch_size=5, max_model_len=MAX_LEN)
    pool, c = eng.pool, model.cfg
    # pages for the two full layers only: K and V of 4 heads x 16
    assert pool.num_layers == 2 and len(pool.page_pools) == 2
    assert pool.memory_bytes() == 20 * 2 * 2 * 8 * 4 * 16 * 4
    # max_batch_size slots of every linear layer's state and window (no
    # scratch slot: a dead row writes back what it read at its own row)
    assert pool.state_slots == 5 and len(pool.state_pools) == 6
    per_seq = c.state_bytes_per_sequence(4)
    assert per_seq == 6 * (4 * 8 * 16 * 4 + 3 * (32 + 32 + 64) * 4)
    assert pool.state_bytes() == 5 * per_seq
    assert pool.state_bytes() == sum(
        a.nbytes for layer in pool.state_pools for a in layer)
    pages, states = pool.pools
    assert pages is pool.page_pools and states is pool.state_pools
    # the published widths: 26.5 MB of state and 0.83 MB of rows a sequence
    pub = OlmoHybridConfig(num_hidden_layers=16)
    assert pub.state_bytes_per_sequence(2) == 12 * (2_211_840 + 69_120)


def test_state_pool_alloc_is_a_span_inside_kv_pool_alloc(toy):
    """Set-up's spans record without a profiler session: the state arrays
    are made inside the pool's own span, the pool inside the engine's."""
    from paddle_tpu import profiler

    _, _, model = toy
    profiler.clear()
    eng = create_serving_engine(model, num_blocks=20, block_size=8,
                                max_batch_size=5, max_model_len=MAX_LEN)
    name, sid, parent = 0, 3, 4
    by = {s[name]: s for s in profiler.spans()}
    assert by["state_pool.alloc"][parent] == by["kv_pool.alloc"][sid]
    assert by["kv_pool.alloc"][parent] == by["engine.build"][sid]
    assert by["state_pool.alloc"][7]["slots"] == 5        # max_batch_size
    # nothing a step records while no session is live
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=3))
    eng.run()
    assert not {s[name] for s in profiler.spans()} & {
        "engine.step", "runner.launch", "engine.drain"}


def test_older_runners_pools_are_what_they_were():
    """GPT, Llama and DeepSeek-V3: `pools` is the list of page arrays and
    nothing else, layer for layer."""
    from paddle_tpu.models import (
        GPT, GPTConfig, DeepseekV3Config, DeepseekV3ForCausalLM, Llama,
        LlamaConfig,
    )

    models = [
        GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                      num_heads=4, max_seq_len=32)),
        Llama(LlamaConfig(vocab_size=64, hidden_size=32, num_layers=2,
                          num_heads=4, num_kv_heads=2, ffn_hidden=64,
                          max_seq_len=32)),
        DeepseekV3ForCausalLM(DeepseekV3Config(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=2,
            first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=8, n_routed_experts=8, n_shared_experts=1,
            num_experts_per_tok=2, experts_held=4, first_expert=0,
            max_seq_len=32)),
    ]
    shapes = [[(6, 8, 4, 8)] * 2, [(6, 8, 2, 8)] * 2, [(6, 8, 128)]]
    for model, want in zip(models, shapes):
        runner = build_runner(model, block_size=8)
        assert runner.state_layout() is None
        pool = KVCachePool.for_runner(runner, 6, state_slots=9)
        assert isinstance(pool.pools, list) and pool.pools is pool.page_pools
        assert len(pool.pools) == 2 and pool.state_bytes() == 0
        assert pool.state_slots == 0 and pool.state_pools == []
        assert [tuple(a.shape) for a in pool.pools[0]] == want
        pool.pools = [tuple(a + 1 for a in layer) for layer in pool.pools]
        assert float(pool.page_pools[1][0].ravel()[0]) == 1.0


# --------------------------------------------------------- the counters


def test_counters_equal_a_numpy_twin(toy):
    """What the steps count on the device against the same quantities from
    the requests alone: a prompt of n tokens is one prefill of n real
    tokens in its bucket, then max_tokens - 1 decode rows in each of the
    six linear layers."""
    from paddle_tpu.serving.model_runner import bucket_len

    _, _, model = toy
    lengths, out = (5, 17, 33, 9, 21, 40, 13), 10
    eng, _ = serve(model, prompts_of(lengths), max_tokens=out)
    snap = eng.metrics.snapshot()
    assert snap["delta_prefill_tokens"] == sum(lengths)
    assert snap["delta_prefill_positions"] == sum(map(bucket_len, lengths))
    assert snap["delta_decode_seq_steps"] == 6 * len(lengths) * (out - 1)
    assert snap["state_slot_resets"] == len(lengths)
