"""The DeepSeek-V3 block (latent attention + routed and shared experts), one
rank's share, at toy widths with the published ratios, each part against
the plain reference of bench/reference_deepseek.py on seeded weights:
the Layer, the latent paged cache (absorbed = expanded), the engine, the
share, the router's bias, droplessness, the kernel, YaRN, the pool."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import create_serving_engine
from paddle_tpu.models import deepseek_v3 as dsv3
from paddle_tpu.models.deepseek_v3 import (
    DeepseekV3Config, DeepseekV3ForCausalLM,
)
from paddle_tpu.ops.pallas import latent_paged_attention as lpa
from paddle_tpu.ops.pallas.latent_paged_attention import (
    latent_paged_attention, latent_reference, latent_walk_counts, page_runs,
    walked_groups,
)
from paddle_tpu.parallel.moe import (
    _swiglu, held_experts_ffn, sigmoid_topk_route,
)
from paddle_tpu.serving import KVCachePool, SamplingParams, naive_generate

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
import reference_deepseek as R  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
# hidden 64, 4 heads of 16 + 8, latent 32, 16 experts top-4, 4 held
CFG = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
           moe_intermediate_size=32, num_hidden_layers=3,
           first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=48,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, n_routed_experts=16, n_shared_experts=1,
           num_experts_per_tok=4, routed_scaling_factor=2.827,
           norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=50000,
           rope_scaling=YARN, experts_held=4, first_expert=4,
           max_seq_len=96, dtype="float32")
# float32 sums in another order: the program's and the reference's logits
# (of size 0.6) agree to a few float32 ulps of the largest partial sum
TOL = 2e-6


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


@pytest.fixture(scope="module")
def seeded():
    """(model, reference weights) on one seed, float32."""
    weights = R.init_weights(CFG, R.seed_key(3))
    model = DeepseekV3ForCausalLM(DeepseekV3Config(**CFG))
    missing, unexpected = model.set_state_dict(
        {k: paddle.Tensor(v) for k, v in R.program_names(weights).items()})
    assert not missing and not unexpected
    model.eval()
    return model, weights


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG["vocab_size"], n)


# ------------------------------------------------------ (a) the Layer


def test_layer_logits_equal_the_reference(seeded):
    model, weights = seeded
    toks = np.stack([_tokens(24, 1), _tokens(24, 2)])
    with jax.default_matmul_precision("highest"):
        out = model(paddle.Tensor(jnp.asarray(toks)))._value
    ref = jnp.stack([R.logits_at(CFG, weights, jnp.asarray(t), 0, 24)
                     for t in toks])
    assert out.shape == (2, 24, CFG["vocab_size"])
    assert float(jnp.abs(out - ref).max()) < TOL


def test_layer_builds_in_its_stated_dtype():
    model = DeepseekV3ForCausalLM(DeepseekV3Config(**dict(CFG,
                                                          dtype="bfloat16")))
    assert {str(p.dtype) for _, p in model.named_parameters()} == {
        "bfloat16"}


# ---------------------------- (b) latent pages: absorbed = expanded


@pytest.mark.parametrize("attn_impl", ["reference", "ragged"])
def test_prefill_then_decode_through_latent_pages(seeded, attn_impl):
    """Prefill (expanded form) then 8 decode steps (absorbed form, through
    the gather path or the kernel in interpret mode) give the logits of
    the reference's one full forward."""
    model, weights = seeded
    runner = create_serving_engine(model, num_blocks=8,
                                   attn_impl=attn_impl).runner
    pool = KVCachePool.for_runner(runner, 8)
    table = pool.pad_table(pool.allocator.alloc(6), 6)
    toks = _tokens(40, 5).tolist()
    with jax.default_matmul_precision("highest"):
        logits, pools = runner.prefill(toks[:32], table, pool.pools)
        rows = [np.asarray(logits)]
        for i in range(8):
            logits, pools = runner.decode(
                np.asarray([toks[32 + i]]), np.asarray(table)[None],
                np.asarray([32 + i]), pools)
            rows.append(np.asarray(logits)[0])
    ref = np.asarray(R.logits_at(CFG, weights, jnp.asarray(toks), 31, 9))
    assert np.abs(np.stack(rows) - ref).max() < TOL


def test_chunked_prefill_sees_earlier_chunks(seeded):
    """A second chunk's expanded form reads the first chunk's latent rows
    through the table."""
    model, weights = seeded
    runner = create_serving_engine(model, num_blocks=8).runner
    pool = KVCachePool.for_runner(runner, 8)
    table = pool.pad_table(pool.allocator.alloc(6), 6)
    toks = _tokens(40, 7).tolist()
    with jax.default_matmul_precision("highest"):
        _, pools = runner.prefill_chunk(toks[:16], 0, table, pool.pools)
        logits, _ = runner.prefill_chunk(toks[16:40], 16, table, pools)
    ref = np.asarray(R.logits_at(CFG, weights, jnp.asarray(toks), 39, 1))
    assert np.abs(np.asarray(logits) - ref[0]).max() < TOL


# ------------------------------------------------------- (c) the engine


def test_engine_serves_token_for_token(seeded):
    model, _ = seeded
    eng = create_serving_engine(model, num_blocks=64, max_batch_size=4)
    prompts = [_tokens(n, n).tolist() for n in (5, 19, 33)]
    params = SamplingParams(max_tokens=12)
    rids = [eng.add_request(p, params) for p in prompts]
    outs = eng.run()
    for rid, p in zip(rids, prompts):
        assert outs[rid].output_tokens == naive_generate(eng.runner, p,
                                                         params)
    assert eng.pool.allocator.check_no_leaks()
    snap = eng.metrics.snapshot()
    # one sync a step: the expert counters ride the drain the step makes
    assert snap["host_syncs"] <= snap["decode_steps"] + len(prompts)
    # none lost: every prefilled token and every slot of every decode step
    # (a dead slot's row goes through the router too), 2 expert layers
    assert snap["moe_tokens_routed"] == 2 * (
        snap["prefill_tokens"] + 4 * snap["decode_steps"])
    assert 0 < snap["moe_experts_touched"] <= snap["moe_local_pairs"]


def test_expert_counters_count_a_decode_step(seeded):
    """One decode step of 3 slots: tokens x expert layers routed, the
    pairs among them whose expert is held, the held experts they touch:
    an output of the step's program, handed to whoever listens."""
    model, _ = seeded
    runner = create_serving_engine(model, num_blocks=8).runner
    heard = []
    runner.on_step_counts = heard.append
    pool = KVCachePool.for_runner(runner, 8)
    tables = np.zeros((3, 6), np.int32)
    tables[0] = pool.allocator.alloc(6)
    logits, _ = runner.decode(np.asarray([3, 4, 0]), tables,
                              np.asarray([0, 0, 0]), pool.pools)
    assert logits.shape == (3, CFG["vocab_size"])     # the entry's own pair
    (counts,) = heard
    routed, pairs, touched, *walked = (int(n) for n in counts)
    assert walked == [0, 0]      # the gather path here: no kernel, no walk
    assert routed == 3 * 2       # every slot of a decode step, 2 expert layers
    assert 0 < touched <= pairs <= routed * CFG["num_experts_per_tok"]
    assert touched <= 2 * CFG["experts_held"]
    runner.on_step_counts = None                      # nobody asks: dropped
    runner.decode(np.asarray([3, 4, 0]), tables, np.asarray([1, 1, 1]),
                  pool.pools)
    assert len(heard) == 1


# --------------------------------------------- (d)(e)(f) the expert layer


def _expert_layer(T=37, d=64, f=32, E=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (T, d)),
        gate=jax.random.normal(ks[1], (d, E)) * 0.3,
        bias=jax.random.normal(ks[2], (E,)) * 0.3,
        wg=jax.random.normal(ks[3], (E, d, f)) * 0.1,
        wu=jax.random.normal(ks[4], (E, d, f)) * 0.1,
        wd=jax.random.normal(ks[5], (E, f, d)) * 0.1)


def _uncut(p, idx, w):
    """Every selected expert of every token, dense."""
    y = jnp.zeros_like(p["x"])
    for e in range(p["gate"].shape[1]):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        y = y + w_e[:, None] * _swiglu(p["x"], p["wg"][e], p["wu"][e],
                                       p["wd"][e])
    return y


@pytest.mark.parametrize("held", [4, 8, 16])
def test_all_shares_add_up_to_the_uncut_layer(held):
    """The routed parts of all E / held shares are the uncut layer (the
    shared expert, whole on every rank, is counted once by whoever adds
    the shares)."""
    p = _expert_layer()
    idx, w = sigmoid_topk_route(p["x"], p["gate"], p["bias"], 4, scale=2.827)
    total, pairs = jnp.zeros_like(p["x"]), 0
    for first in range(0, 16, held):
        sl = slice(first, first + held)
        y, n, _ = held_experts_ffn(p["x"], idx, w, p["wg"][sl], p["wu"][sl],
                                   p["wd"][sl], first)
        total, pairs = total + y, pairs + int(n)
    assert pairs == 37 * 4                      # every pair, exactly once
    assert float(jnp.abs(total - _uncut(p, idx, w)).max()) < 1e-5


def test_the_share_ties_to_the_model(seeded):
    """The model's expert layer on its share = the reference's."""
    model, weights = seeded
    x = jax.random.normal(jax.random.key(1), (21, CFG["hidden_size"]))
    params = {k: v for k, v in weights.items()}
    y, counts = dsv3.moe_ffn(model.cfg, params, "layers.1.mlp.", x)
    ref = R.moe(CFG, lambda v: v, x, R._layer(weights, 1))
    assert float(jnp.abs(y - ref).max()) < 1e-5
    assert int(counts[0]) == 21


def test_bias_moves_the_selection_and_not_the_weights():
    p = _expert_layer()
    idx0, w0 = sigmoid_topk_route(p["x"], p["gate"], jnp.zeros(16), 4)
    idx1, w1 = sigmoid_topk_route(p["x"], p["gate"], p["bias"], 4)
    assert not np.array_equal(np.sort(idx0, -1), np.sort(idx1, -1))
    s = jax.nn.sigmoid(p["x"] @ p["gate"])
    picked = jnp.take_along_axis(s, idx1, -1)
    # weights from the scores alone, normalised over ALL the selected
    assert np.allclose(w1, picked / picked.sum(-1, keepdims=True), atol=1e-6)
    assert np.allclose(w1.sum(-1), 1.0, atol=1e-5)
    # the selection is the top-4 of score + bias
    want = jax.lax.top_k(s + p["bias"][None], 4)[1]
    assert np.array_equal(np.sort(idx1, -1), np.sort(want, -1))
    _, w_scaled = sigmoid_topk_route(p["x"], p["gate"], p["bias"], 4,
                                     scale=2.827)
    assert np.allclose(w_scaled, 2.827 * w1, atol=1e-6)


@pytest.mark.parametrize("tokens", [37, 1200])
def test_nothing_is_dropped_under_total_imbalance(tokens):
    """Every token picks the same held expert (and three that lie
    elsewhere): all of them are computed, in either row-block size."""
    p = _expert_layer(T=tokens)
    idx = jnp.broadcast_to(jnp.array([[9, 1, 2, 3]]), (tokens, 4))
    w = jnp.full((tokens, 4), 0.25)
    y, pairs, touched = jax.jit(held_experts_ffn, static_argnums=6)(
        p["x"], idx, w, p["wg"][8:12], p["wu"][8:12], p["wd"][8:12], 8)
    want = 0.25 * _swiglu(p["x"], p["wg"][9], p["wu"][9], p["wd"][9])
    assert (int(pairs), int(touched)) == (tokens, 1)
    assert float(jnp.abs(y - want).max()) < 1e-5


def test_padding_rows_route_nowhere():
    p = _expert_layer()
    idx, w = sigmoid_topk_route(p["x"], p["gate"], p["bias"], 4)
    valid = jnp.arange(37) < 20
    y, pairs, _ = held_experts_ffn(p["x"], idx, w, p["wg"][:8], p["wu"][:8],
                                   p["wd"][:8], 0, valid)
    assert int(pairs) == int(((idx < 8) & valid[:, None]).sum())
    assert not np.asarray(y[20:]).any()


# ------------------------------------------------------- (g) the kernel


@pytest.mark.parametrize("contexts", [[1, 16, 17, 100], [37], [64, 5, 33]])
def test_latent_kernel_equals_the_gather_path(contexts):
    """Interpret mode against the gather oracle: ragged contexts, one that
    ends mid-page, one of a single key."""
    B, n_q, lanes, v_lanes, page = len(contexts), 8, 128, 64, 16
    ks = jax.random.split(jax.random.key(len(contexts)), 3)
    pool = jax.random.normal(ks[0], (40, page, lanes), jnp.float32)
    q = jax.random.normal(ks[1], (B, n_q, lanes), jnp.float32)
    perm = np.asarray(jax.random.permutation(ks[2], 39)) + 1
    table = np.zeros((B, 8), np.int32)
    for b, n in enumerate(contexts):
        pages = -(-n // page)
        table[b, :pages] = perm[b * 8:b * 8 + pages]
    pos = jnp.asarray(contexts, jnp.int32) - 1
    got = latent_paged_attention(q, pool, jnp.asarray(table), pos,
                                 v_lanes=v_lanes, scale=0.2, interpret=True)
    want = latent_reference(q, pool, jnp.asarray(table), pos,
                            v_lanes=v_lanes, scale=0.2)
    assert got.shape == (B, n_q, v_lanes)
    assert float(jnp.abs(got - want).max()) < 2e-5


WALK_PAGE = 16


def _walk_tables(kind, ppb, group, rng):
    """(table [3, 5 * ppb + group], contexts) of three sequences of 1, 3
    and 5 blocks whose page ids are laid out as `kind` says. Ids ascend
    from 1, sequences back to back, as the allocator hands them out."""
    n_pages = [ppb, 3 * ppb, 5 * ppb]
    contexts = [n * WALK_PAGE - off for n, off in zip(n_pages, (0, 5, 11))]
    width = 5 * ppb + group
    table = np.zeros((3, width), np.int32)
    first = 1
    for b, n in enumerate(n_pages):
        ids = np.arange(first, first + n)
        if kind == "permuted":
            ids = rng.permutation(ids)
            while np.any(np.diff(ids) == 1):     # no two left in order
                ids = rng.permutation(ids)
        elif kind == "broken_mid_group":
            # the run breaks one page into every second group
            for g0 in range(0, n, 2 * group):
                ids[g0 + max(group // 2, 1):] += 9
        elif kind == "starts_mid_group":
            ids[:max(group // 2, 1)] += 500         # strays, then the run
        elif kind == "permuted_range":
            for g0 in range(0, n, group):           # right range, wrong order
                ids[g0:g0 + group] = ids[g0:g0 + group][::-1]
        table[b, :n] = ids
        first = int(ids.max()) + 1
    if kind == "ends_inside_flagged_group":
        # pages held past the context's end (a reservation): the last
        # group is a run in the table and only partly live
        for b, n in enumerate(n_pages):
            table[b, :n + group] = np.arange(table[b, 0],
                                             table[b, 0] + n + group)
        contexts = [ppb * WALK_PAGE - 3,                     # mid-page, whole
                    (3 * ppb - 1) * WALK_PAGE - 3,           # partial block
                    5 * ppb * WALK_PAGE + 2]     # one page into the next
    if kind == "dead_slot":
        table[1], contexts[1] = 0, 1             # scratch row, position 0
    return table, contexts


@pytest.mark.parametrize("ppb,group", [(2, 1), (2, 2), (4, 2), (4, 4)])
@pytest.mark.parametrize("kind", [
    "ascending", "permuted", "broken_mid_group", "starts_mid_group",
    "permuted_range", "ends_inside_flagged_group", "dead_slot"])
def test_latent_walk_across_blocks_and_run_kinds(kind, ppb, group):
    """The walk over 1, 3 and 5 blocks (the unmasked fold, the buffer swap,
    the hand-over to the next sequence) with pages copied as runs where the
    table has them: equal to the oracle, and BIT-equal to the same kernel
    with every flag forced to 0 (same pages, same fold)."""
    rng = np.random.default_rng(ppb * 10 + group)
    table, contexts = _walk_tables(kind, ppb, group, rng)
    B, n_q, lanes, v_lanes = 3, 8, 128, 64
    ks = jax.random.split(jax.random.key(7), 2)
    pool = jax.random.normal(ks[0], (int(table.max()) + 2, WALK_PAGE, lanes),
                             jnp.float32)
    q = jax.random.normal(ks[1], (B, n_q, lanes), jnp.float32)
    pos = jnp.asarray(contexts, jnp.int32) - 1
    kw = dict(v_lanes=v_lanes, scale=0.2, interpret=True,
              pages_per_block=ppb, group=group)
    got = latent_paged_attention(q, pool, jnp.asarray(table), pos, **kw)
    want = latent_reference(q, pool, jnp.asarray(table), pos,
                            v_lanes=v_lanes, scale=0.2)
    assert float(jnp.abs(got - want).max()) < 2e-5
    runs = page_runs(table, group)
    plain = latent_paged_attention(q, pool, jnp.asarray(table), pos,
                                   runs=jnp.zeros_like(runs), **kw)
    assert np.array_equal(np.asarray(got), np.asarray(plain))
    # the device's count of the walk = the numpy twin's
    groups, as_run, descriptors = latent_walk_counts(table, pos, WALK_PAGE,
                                                     group)
    assert [int(n) for n in walked_groups(
        runs, pos, WALK_PAGE, group, table.shape[1])] == [groups, as_run]
    live = [int(p) // WALK_PAGE + 1 for p in pos]
    assert groups == sum(-(-n // group) for n in live)
    if kind == "ascending":
        assert as_run == groups and descriptors == groups
    if group > 1 and kind in ("permuted", "permuted_range"):
        assert as_run == 0 and descriptors == sum(live)
    if group > 1 and kind == "broken_mid_group":
        assert 0 < as_run < groups
    if group > 1 and kind == "starts_mid_group":
        assert as_run == groups - 3             # each sequence's first group
    if kind == "dead_slot":
        assert groups == ppb // group + 1 + 5 * ppb // group


def test_page_runs_checks_the_order_and_not_the_range():
    table = np.asarray([[4, 5, 6, 7, 7, 6, 5, 4, 9, 10, 12, 11, 0, 0]])
    assert page_runs(table, 4).tolist() == [[1, 0, 0, 0]]
    assert page_runs(table, 2).tolist() == [[1, 1, 0, 0, 1, 0, 0]]
    # a group the table's width cuts short is never a run
    assert page_runs(np.arange(1, 7)[None], 4).tolist() == [[1, 0]]


def test_a_block_of_runs_is_four_descriptors():
    """64 consecutive pages, a full block: 4 copies of 16 pages where a
    walk that copies page by page issues 64."""
    table, pos = np.arange(1, 65)[None], np.asarray([64 * 16 - 1])
    assert latent_walk_counts(table, pos, 16, 16) == (4, 4, 4)
    assert latent_walk_counts(table[:, ::-1], pos, 16, 16) == (4, 0, 64)
    # the rule at the Kimi cell's pages (bf16, 16 x 640): blocks of 64
    # pages, runs of 16 = 320 KiB a copy
    pool = jax.ShapeDtypeStruct((50752, 16, 640), jnp.bfloat16)
    assert lpa.walk_shape(64, pool, 512) == (64, 16)
    with pytest.raises(ValueError, match="whole groups"):
        lpa.walk_shape(64, pool, 512, 4, 8)


def test_engine_counts_the_walk_as_the_numpy_twin_does(seeded, monkeypatch):
    """`latent_copy_groups` / `latent_run_groups`, outputs of each decode
    step's program read at the step's drain, against `latent_walk_counts`
    over the tables and positions the engine launched (3 layers). The run
    size is steered to 2 pages here (toy pages are 8 KB and a table 6
    entries wide: the rule's 32 would leave no group to count)."""
    monkeypatch.setattr(lpa, "RUN_COPY_BYTES", 2 * 16 * 128 * 4)
    model, _ = seeded
    eng = create_serving_engine(model, num_blocks=64, max_batch_size=4,
                                attn_impl="ragged")
    launched = []
    decode = eng.runner.decode

    def spy(tokens, tables, pos, pools, *a, **k):
        launched.append((np.array(tables), np.array(pos)))
        return decode(tokens, tables, pos, pools, *a, **k)

    monkeypatch.setattr(eng.runner, "decode", spy)
    prompts = [_tokens(n, n).tolist() for n in (40, 70, 9)]
    params = SamplingParams(max_tokens=12)
    rids = [eng.add_request(p, params) for p in prompts]
    outs = eng.run()
    for rid, p in zip(rids, prompts):
        assert outs[rid].output_tokens == naive_generate(eng.runner, p,
                                                         params)
    launched = launched[:int(eng.metrics.snapshot()["decode_steps"])]
    want = np.sum([latent_walk_counts(t, p, 16, 2)[:2]
                   for t, p in launched], axis=0) * CFG["num_hidden_layers"]
    snap = eng.metrics.snapshot()
    assert [snap["latent_copy_groups"], snap["latent_run_groups"]] == \
        want.tolist()
    assert 0 < snap["latent_run_groups"] < snap["latent_copy_groups"]


def test_latent_kernel_refuses_pages_that_are_not_whole_tiles():
    with pytest.raises(ValueError, match="multiples of 128"):
        latent_paged_attention(jnp.zeros((1, 4, 96)), jnp.zeros((4, 16, 96)),
                               jnp.zeros((1, 2), jnp.int32),
                               jnp.zeros((1,), jnp.int32), v_lanes=64,
                               scale=1.0, interpret=True)


# ---------------------------------------------------------- (h) YaRN


def test_yarn_on_hand_worked_values():
    """The published rotary part: 64 dims, theta 50000, factor 64 over
    4096, beta 32 / 1. Correction dims: 32 ln(4096 / (beta 2 pi)) /
    ln(50000) = 8.91 and 19.17 -> ramp from 8 to 20."""
    cfg = DeepseekV3Config(qk_rope_head_dim=64, rope_theta=50000,
                           rope_scaling=YARN, qk_nope_head_dim=128)
    assert dsv3.yarn_correction_range(32, 1, 64, 50000, 4096) == (8, 20)
    inv = np.asarray(dsv3.yarn_inv_freq(cfg))
    plain = 50000.0 ** (-np.arange(32) / 32)
    assert np.allclose(inv[:9], plain[:9], rtol=1e-6)            # kept
    assert np.allclose(inv[20:], plain[20:] / 64, rtol=1e-6)     # interpolated
    # halfway up the ramp (dim 14): the mean of the two
    assert math.isclose(inv[14], plain[14] * (0.5 + 0.5 / 64), rel_tol=1e-5)
    m = 0.1 * math.log(64) + 1
    assert math.isclose(m, 1.41589, abs_tol=1e-5)
    assert math.isclose(dsv3.softmax_scale(cfg), 192 ** -0.5 * m * m,
                        rel_tol=1e-9)
    cos, sin = dsv3.rope_tables(cfg, 4)
    assert np.allclose(cos[1, :32], np.cos(inv)) and np.allclose(
        cos[:, :32], cos[:, 32:])                    # mscale ratio is 1
    assert np.allclose(np.asarray(R.yarn_inv_freq(
        {"qk_rope_head_dim": 64, "rope_theta": 50000,
         "rope_scaling": YARN})), inv)


def test_rope_takes_its_pairs_interleaved():
    x = jnp.arange(8, dtype=jnp.float32)
    ang = jnp.asarray([0.1, 0.2, 0.3, 0.4])
    ang = jnp.concatenate([ang, ang])
    got = np.asarray(dsv3.rope_interleaved(x, jnp.cos(ang), jnp.sin(ang)))
    for i in range(4):                    # pair (x[2i], x[2i+1]) by angle i
        a, b, c, s = 2 * i, 2 * i + 1, math.cos(ang[i]), math.sin(ang[i])
        assert math.isclose(got[i], a * c - b * s, abs_tol=1e-5)
        assert math.isclose(got[4 + i], b * c + a * s, abs_tol=1e-5)


# ------------------------------------------------------- (i)(j) the pool


def test_latent_pool_is_one_array_a_layer(seeded):
    model, _ = seeded
    runner = create_serving_engine(model, num_blocks=8).runner
    assert runner.page_layout() == [((128,), jnp.dtype("float32"))]
    pool = KVCachePool.for_runner(runner, 8)
    assert [tuple(a.shape) for a in pool.pools[0]] == [(8, 16, 128)]
    assert len(pool.pools) == 3 and all(len(l) == 1 for l in pool.pools)
    # 3 layers x 16 tokens x 128 lanes (40 values padded to a lane tile) x 4
    assert pool.page_bytes() == 3 * 16 * 128 * 4 == runner._kv_page_bytes()
    assert pool.memory_bytes() == 8 * pool.page_bytes()
    pool.copy_page(1, 2)                   # geometry-blind, as before
    assert len(pool.read_pages([1, 2])[0]) == 1
    assert (pool.n_kv_heads, pool.head_dim) == (None, None)
    with pytest.raises(ValueError, match="stated dtype"):
        KVCachePool(3, 8, 16, kv_dtype="int8",
                    page_layout=runner.page_layout())


def test_latent_runner_refuses_other_page_rungs(seeded):
    model, _ = seeded
    with pytest.raises(ValueError, match="stated dtype only"):
        create_serving_engine(model, num_blocks=8, kv_dtype="int8")


@pytest.mark.parametrize("kv_dtype,arrays", [
    ("fp32", 2), ("int8", 4), ("mixed", 3)])
def test_kv_pools_are_what_they_were(kv_dtype, arrays):
    """(k, v) runners pass what they passed and get what they got: the
    arrays, shapes, dtypes and bytes of the pools before the layout came
    from the runner, from the constructor and from for_runner alike."""
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.serving.model_runner import runner_for

    pool = KVCachePool(num_layers=2, num_blocks=6, block_size=4,
                       n_kv_heads=2, head_dim=8, kv_dtype=kv_dtype)
    assert pool.page_layout == [((2, 8), jnp.dtype("float32"))] * 2
    assert (pool.n_kv_heads, pool.head_dim) == (2, 8)
    shapes = [tuple(a.shape) for a in pool.pools[0]]
    assert len(shapes) == arrays and shapes[:2] == [(6, 4, 2, 8)] * 2
    per_kv = 4 * 2 * 8
    want = {"fp32": 2 * 2 * per_kv * 4, "int8": 2 * 2 * (per_kv + 2 * 4),
            # the tag plane is counted as allocated, one a layer (it was
            # counted once: the only byte of these pools that moved)
            "mixed": 2 * (2 * per_kv * 4 + 1)}[kv_dtype]
    assert pool.page_bytes() == want
    assert sum(a.nbytes for a in pool.pools[0][:2]) == (
        6 * 2 * per_kv * (1 if kv_dtype == "int8" else 4))
    gpt = GPT(GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                        num_heads=2, max_seq_len=32))
    runner = runner_for(gpt, block_size=4, kv_dtype=kv_dtype)
    assert runner.page_layout() == pool.page_layout
    mine = KVCachePool.for_runner(runner, 6)
    assert [(tuple(a.shape), a.dtype) for a in mine.pools[0]] == [
        (tuple(a.shape), a.dtype) for a in pool.pools[0]]
    assert mine.page_bytes() == pool.page_bytes()


def test_runner_for_names_the_third_runner():
    with pytest.raises(TypeError, match="DeepseekV3ForCausalLM"):
        from paddle_tpu.serving.model_runner import runner_for
        runner_for(object())
