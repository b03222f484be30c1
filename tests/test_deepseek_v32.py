"""The DeepSeek-V3.2 block (the DeepSeek-V3 block under a lightning indexer's
selection, a group-limited router, a multi-token-prediction module), one
rank's share, at toy widths (index_topk 8, pages of 4, 4 groups of 4
experts, 2 dense + 2 expert layers), each part against the plain reference
of bench/reference_deepseek_v32.py on seeded weights: the Layer, prefill then
decode through latent + index pages, the engine (chunks, a shared prefix, a
re-taken slot), the selected SETS, the two forms, the kernels, the route, the
shares, the module; and Kimi's programs, which may not move."""

import hashlib
import json
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
from paddle_tpu.ops.pallas import sparse_latent_attention as sla
from paddle_tpu.parallel.moe import sigmoid_topk_route
from paddle_tpu.serving import KVCachePool, SamplingParams, naive_generate
from paddle_tpu.serving import model_runner as mr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
import reference_deepseek_v32 as R  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "yarn"}
TOPK, PAGE = 8, 4
CFG = dict(vocab_size=96, hidden_size=64, intermediate_size=160,
           moe_intermediate_size=32, num_hidden_layers=4,
           first_k_dense_replace=2, num_attention_heads=4, q_lora_rank=48,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, n_routed_experts=16, n_shared_experts=1,
           num_experts_per_tok=4, routed_scaling_factor=2.5,
           norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=10000,
           rope_scaling=YARN, experts_held=4, first_expert=4,
           max_seq_len=96, n_group=4, topk_group=2, index_n_heads=4,
           index_head_dim=16, index_topk=TOPK, num_nextn_predict_layers=1)
# float32 sums in another order: the program's and the reference's logits
# (of size 0.2) agree to a few float32 ulps of the largest partial sum. A
# selection that differed in ONE key would move a row by 1e-3 and more
TOL = 2e-6


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


@pytest.fixture(scope="module")
def seeded():
    """(model, reference weights) on one seed, float32."""
    weights = R.init_weights(CFG, R.seed_key(3))
    model = DeepseekV3ForCausalLM(DeepseekV3Config(**CFG, dtype="float32"))
    missing, unexpected = model.set_state_dict(
        {k: paddle.Tensor(v) for k, v in R.program_names(weights).items()})
    assert not missing and not unexpected
    model.eval()
    return model, weights


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG["vocab_size"], n)


def _engine(model, **kw):
    kw.setdefault("num_blocks", 64)
    return create_serving_engine(model, block_size=PAGE, **kw)


def _ref_logits(weights, toks, first, count):
    with jax.default_matmul_precision("highest"):
        return np.asarray(R.logits_at(CFG, weights, jnp.asarray(toks), first,
                                      count))


# ------------------------------------------------------ (a) the Layer


def test_layer_logits_equal_the_reference(seeded):
    """40 tokens against index_topk 8: every row past the eighth attends
    over a selection; the expert layers route inside 2 of 4 groups."""
    model, weights = seeded
    toks = np.stack([_tokens(40, 1), _tokens(40, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(jnp.asarray(toks))._value)
    ref = np.stack([_ref_logits(weights, t, 0, 40) for t in toks])
    assert np.abs(got - ref).max() < TOL


def test_mtp_module_equals_the_reference(seeded):
    model, weights = seeded
    toks = np.stack([_tokens(24, 3), _tokens(24, 4)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.mtp_forward(jnp.asarray(toks))._value)
        ref = np.stack([np.asarray(R.mtp_logits(CFG, weights, jnp.asarray(t)))
                        for t in toks])
    assert got.shape == (2, 23, CFG["vocab_size"])
    assert np.abs(got - ref).max() < TOL


def test_module_and_indexer_are_built_by_the_keys_alone():
    """No indexer, no group, no module set: Kimi's parameters."""
    plain = {k: v for k, v in CFG.items() if k not in (
        "index_topk", "n_group", "topk_group", "num_nextn_predict_layers")}
    names = {k for k, _ in DeepseekV3ForCausalLM(
        DeepseekV3Config(**plain)).named_parameters()}
    assert not [n for n in names if "indexer" in n or "layers.4." in n]
    full = {k for k, _ in DeepseekV3ForCausalLM(
        DeepseekV3Config(**CFG)).named_parameters()}
    assert set(R._shapes(CFG)) == full
    assert {n.split("indexer.")[1] for n in full if "layers.0" in n
            and "indexer" in n} == {"wq_b.weight", "wk.weight",
                                    "k_norm.weight", "k_norm.bias",
                                    "weights_proj.weight"}
    served = {k: v for k, v in CFG.items()
              if k != "num_nextn_predict_layers"}
    assert set(R._shapes(served)) == {k for k, _ in DeepseekV3ForCausalLM(
        DeepseekV3Config(**served)).named_parameters()}


# ------------------- (b) latent + index pages: prefill, then decode


@pytest.mark.parametrize("attn_impl", ["reference", "ragged"])
@pytest.mark.parametrize("n_prefill", [3, 8, 29],
                         ids=["under-topk", "at-topk", "pages-over-topk"])
def test_prefill_then_decode_through_both_page_arrays(seeded, attn_impl,
                                                      n_prefill):
    """Prefill (expanded form under `selection_mask`) then 8 decode steps
    (absorbed form: the gather path, or the scan kernel and the masked walk
    in interpret mode) give the logits of the reference's one full forward:
    contexts that end under index_topk, cross it, and lie several pages
    past it."""
    model, weights = seeded
    runner = _engine(model, attn_impl=attn_impl).runner
    pool = KVCachePool.for_runner(runner, 16)
    table = pool.pad_table(pool.allocator.alloc(10), 10)
    toks = _tokens(n_prefill + 8, 5).tolist()
    with jax.default_matmul_precision("highest"):
        logits, pools = runner.prefill(toks[:n_prefill], table, pool.pools)
        rows = [np.asarray(logits)]
        for i in range(8):
            logits, pools = runner.decode(
                np.asarray([toks[n_prefill + i]]), np.asarray(table)[None],
                np.asarray([n_prefill + i]), pools)
            rows.append(np.asarray(logits)[0])
    ref = _ref_logits(weights, toks, n_prefill - 1, 9)
    assert np.abs(np.stack(rows) - ref).max() < TOL


def test_a_long_table_is_walked_under_the_selection(seeded):
    """A table of 25 pages, twelve selections' worth of keys and more: the
    scan and the masked walk still, whatever the table's length; the same
    logits."""
    model, weights = seeded
    runner = _engine(model, attn_impl="ragged").runner
    pool = KVCachePool.for_runner(runner, 32)
    table = pool.pad_table(pool.allocator.alloc(8), 25)
    toks = _tokens(30, 6).tolist()
    with jax.default_matmul_precision("highest"):
        _, pools = runner.prefill(toks[:26], table, pool.pools)
        rows = []
        for i in range(4):
            logits, pools = runner.decode(
                np.asarray([toks[26 + i]]), np.asarray(table)[None],
                np.asarray([26 + i]), pools)
            rows.append(np.asarray(logits)[0])
    assert np.abs(np.stack(rows) - _ref_logits(weights, toks, 26, 4)
                  ).max() < TOL


def test_chunked_prefill_scores_earlier_chunks_index_keys(seeded):
    """A second chunk's queries select among the first chunk's keys, read
    through the table from the index pool."""
    model, weights = seeded
    runner = _engine(model).runner
    pool = KVCachePool.for_runner(runner, 16)
    table = pool.pad_table(pool.allocator.alloc(10), 10)
    toks = _tokens(40, 7).tolist()
    with jax.default_matmul_precision("highest"):
        _, pools = runner.prefill_chunk(toks[:16], 0, table, pool.pools)
        logits, _ = runner.prefill_chunk(toks[16:40], 16, table, pools)
    ref = _ref_logits(weights, toks, 39, 1)
    assert np.abs(np.asarray(logits) - ref[0]).max() < TOL


def test_expanded_and_absorbed_forms_agree_under_selection(seeded):
    """Two sequences' spans through the absorbed gather path (a ragged
    batch) against each alone through the expanded form (a prefill)."""
    model, _ = seeded
    runner = _engine(model).runner
    pool = KVCachePool.for_runner(runner, 32)
    tables = np.stack([pool.pad_table(pool.allocator.alloc(8), 8)
                       for _ in range(2)])
    spans = [_tokens(24, 8), _tokens(19, 9)]
    toks = np.zeros((2, 32), np.int32)
    for b, sp in enumerate(spans):
        toks[b, :len(sp)] = sp
    with jax.default_matmul_precision("highest"):
        both, _ = runner.ragged_step(toks, tables, np.zeros(2, np.int32),
                                     np.asarray([24, 19]), pool.pools)
        for b, sp in enumerate(spans):
            alone, _ = runner.prefill(sp.tolist(), tables[b].tolist(),
                                      pool.pools)
            assert np.abs(np.asarray(both)[b] - np.asarray(alone)
                          ).max() < TOL


# ------------------------------------------------------- (c) the engine


def _serve_tapped(eng, prompts, max_tokens):
    """Serve `prompts`; returns ({request id: output tokens}, {request id:
    [(k, logits row that chose output token k)]}) from what the runner's
    decode entry returned to the engine (as bench/serve.py taps it)."""
    runner, calls = eng.runner, []
    entry = runner.decode

    def tapped(tokens, tables, pos, pools):
        logits, pools = entry(tokens, tables, pos, pools)
        calls.append((np.array(tokens).ravel(), np.array(pos),
                      np.asarray(logits)))
        return logits, pools

    runner.decode = tapped
    try:
        rids = [eng.add_request(p, SamplingParams(max_tokens=max_tokens))
                for p in prompts]
        outs = eng.run()
    finally:
        del runner.decode
    rows = {}
    for rid, p in zip(rids, prompts):
        o = outs[rid].output_tokens
        for k in range(1, len(o)):
            at, fed = len(p) + k - 1, o[k - 1]
            # (another request may have stood at this position with this
            # token: every such row is kept, the request's own among them)
            hits = [lg[r] for t, ps, lg in calls for r in range(len(ps))
                    if ps[r] == at and t[r] == fed]
            rows.setdefault(rid, []).append((k, np.stack(hits)))
    return {rid: outs[rid].output_tokens for rid in rids}, rows, rids


def _assert_rows_are_the_references(weights, prompts, outs, rows, rids):
    for rid, p in zip(rids, prompts):
        seq = list(p) + outs[rid]
        ref = _ref_logits(weights, seq, len(p) - 1, len(outs[rid]))
        for k, hits in rows[rid]:
            assert np.abs(hits - ref[k]).max(-1).min() < TOL, (rid, k)


def test_engine_serves_the_references_logits_through_retaken_slots(seeded):
    """Five requests through two slots, prompts under, at and past
    index_topk: every decode row is the reference's full forward's, tokens
    are the oracle's, the counters count."""
    model, weights = seeded
    eng = _engine(model, max_batch_size=2)
    prompts = [_tokens(n, n).tolist() for n in (5, 19, 33, 8, 12)]
    with jax.default_matmul_precision("highest"):
        outs, rows, rids = _serve_tapped(eng, prompts, 10)
        for rid, p in zip(rids, prompts):
            assert outs[rid] == naive_generate(
                eng.runner, p, SamplingParams(max_tokens=10))
    _assert_rows_are_the_references(weights, prompts, outs, rows, rids)
    assert eng.pool.allocator.check_no_leaks()
    snap = eng.metrics.snapshot()
    assert snap["host_syncs"] <= snap["decode_steps"] + len(prompts)
    assert 0 < snap["dsa_keys_selected"] < snap["dsa_keys_scored"]
    assert snap["moe_tokens_routed"] == 2 * (
        snap["prefill_tokens"] + 2 * snap["decode_steps"])


def test_a_shared_prefix_shares_both_arrays_of_its_pages(seeded):
    """A second request whose prompt starts with the first's: its prefix
    pages are the first's pages, latent rows and index keys alike, and its
    decode rows are still the reference's."""
    model, weights = seeded
    eng = _engine(model, max_batch_size=2, enable_prefix_cache=True)
    head = _tokens(24, 11).tolist()
    prompts = [head + _tokens(5, 12).tolist(), head + _tokens(9, 13).tolist()]
    with jax.default_matmul_precision("highest"):
        first = _serve_tapped(eng, prompts[:1], 6)
        second = _serve_tapped(eng, prompts[1:], 6)
    assert eng.metrics.snapshot()["prefix_hit_tokens"] >= 24 - 24 % PAGE
    for (outs, rows, rids), p in zip((first, second), prompts):
        _assert_rows_are_the_references(weights, [p], outs, rows, rids)


def test_counts_of_one_decode_step(seeded):
    """Three slots at contexts 5, 12 and 1 (a dead slot): each scored its
    context and kept min(context, 8), in 4 layers."""
    model, _ = seeded
    runner = _engine(model).runner
    assert runner.COUNTS[-2:] == ("dsa_keys_scored", "dsa_keys_selected")
    heard = []
    runner.on_step_counts = heard.append
    pool = KVCachePool.for_runner(runner, 16)
    tables = np.zeros((3, 6), np.int32)
    tables[0] = pool.allocator.alloc(6)
    tables[1] = pool.allocator.alloc(6)
    runner.decode(np.asarray([3, 4, 0]), tables, np.asarray([4, 11, 0]),
                  pool.pools)
    (counts,) = heard
    *_, scored, kept = (int(n) for n in counts)
    assert len(counts) == 7
    assert scored == 4 * (5 + 12 + 1) and kept == 4 * (5 + 8 + 1)


# --------------------------------------------- (d) the selected SETS


def test_the_programs_selected_sets_are_the_references(seeded, monkeypatch):
    """Every set the program selects while it decodes 10 tokens past a
    20-token prompt, in every layer, is the reference's set for that query
    row (float32, no jit: the masks are read as they are made)."""
    model, weights = seeded
    toks = _tokens(30, 14).tolist()
    want = []                                  # per layer [T, T]
    real_sel = R.selection
    monkeypatch.setattr(R, "selection", lambda *a: want.append(
        np.asarray(real_sel(*a))) or jnp.asarray(want[-1]))
    with jax.default_matmul_precision("highest"), jax.disable_jit():
        R.residual(CFG, weights, jnp.asarray(toks))
    assert len(want) == CFG["num_hidden_layers"]
    got = []                                   # per call [rows, L]
    real_mask = dsv3.topk_mask
    monkeypatch.setattr(dsv3, "topk_mask", lambda s, k: got.append(
        (np.asarray(s), np.asarray(real_mask(s, k)))) or jnp.asarray(
            got[-1][1]))
    runner = _engine(model).runner
    pool = KVCachePool.for_runner(runner, 16)
    table = pool.pad_table(pool.allocator.alloc(10), 10)
    with jax.default_matmul_precision("highest"), jax.disable_jit():
        _, pools = runner.prefill(toks[:20], table, pool.pools)
        got.clear()
        for i in range(10):
            _, pools = runner.decode(
                np.asarray([toks[20 + i]]), np.asarray(table)[None],
                np.asarray([20 + i]), pools)
    assert len(got) == 10 * CFG["num_hidden_layers"]
    for n, (scores, mask) in enumerate(got):
        step, layer = divmod(n, CFG["num_hidden_layers"])
        t = 20 + step
        visible = np.isfinite(scores[0])
        assert visible.sum() == t + 1
        assert np.array_equal((mask[0] & visible)[:t + 1],
                              want[layer][t, :t + 1]), (step, layer)
        assert (mask[0] & visible).sum() == TOPK


@pytest.mark.parametrize("k", [1, 5, 8, 64, 99])
def test_topk_mask_is_lax_top_k_with_ties_to_the_lower_index(k):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 100)).astype(np.float32)
    x[:, 10:30] = np.round(x[:, 10:30])                # many ties
    x[3, :] = 0.0                                      # all ties
    x[4, :50] = -np.inf
    x[5, ::2], x[5, 1::2] = -0.0, 0.0                  # -0.0 counts as 0.0
    x[6, :] = -np.abs(x[6, :])
    mask = np.asarray(dsv3.topk_mask(jnp.asarray(x), k))
    _, idx = jax.lax.top_k(jnp.asarray(x) + 0.0, k)
    want = np.zeros_like(mask)
    for r in range(8):
        want[r, np.asarray(idx[r])] = True
    assert np.array_equal(mask, want)
    assert dsv3.topk_mask(jnp.asarray(x[:, :k]), k).all()


# ------------------------------------------------- (e) the two kernels


def _pages(B, P, page, n_pages, seed=0, run_first=True):
    rng = np.random.default_rng(seed)
    table = np.stack([rng.permutation(np.arange(1, n_pages))[:P]
                      for _ in range(B)]).astype(np.int32)
    if run_first:
        table[0] = np.arange(5, 5 + P)                 # one row of runs
    return rng, table


@pytest.mark.parametrize("ppb,group", [(4, 2), (8, 4), (2, 1), (None, None)])
def test_scan_kernel_equals_its_gather_form(ppb, group):
    """`paged_index_scores` in interpret mode over scattered pages, runs
    and a partial tail, across block shapes: the gather oracle's scores,
    -inf at exactly the positions past each query's."""
    rng, table = _pages(3, 12, 4, 64)
    ipool = jnp.asarray(rng.standard_normal((64, 4, 128)), jnp.float32)
    pos = np.array([37, 5, 47], np.int32)
    q = jnp.asarray(rng.standard_normal((3, 4, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
    got = np.asarray(sla.paged_index_scores(
        q, w, ipool, table, pos, pages_per_block=ppb, group=group))
    want = np.asarray(sla.index_scores_reference(q, w, ipool, table, pos))
    seen = np.isfinite(want)
    assert np.array_equal(np.isfinite(got), seen)
    assert np.array_equal(seen, np.arange(48)[None] <= pos[:, None])
    # float32 products summed in another order
    assert np.abs(got[seen] - want[seen]).max() < 1e-4


@pytest.mark.parametrize("shape", [{}, dict(pages_per_block=2, group=1),
                                   dict(pages_per_block=4, group=2)],
                         ids=["default-blocks", "small-blocks", "run-copies"])
def test_attention_over_the_selection_equals_its_gather_form(shape):
    """The latent kernel's walk under `topk_threshold`, interpret mode,
    across block shapes, against gather + dense softmax over the
    `lax.top_k` rows; one sequence holds fewer keys than are chosen."""
    rng, table = _pages(3, 12, 4, 64, seed=1)
    pool = jnp.asarray(rng.standard_normal((64, 4, 256)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((3, 8, 256)), jnp.float32)
    pos = np.array([37, 5, 47], np.int32)
    scores = jnp.where(jnp.arange(48)[None] <= pos[:, None], jnp.round(
        jnp.asarray(rng.standard_normal((3, 48)), jnp.float32) * 4) / 4,
        -jnp.inf)                                      # ties among them
    _, sel = jax.lax.top_k(scores, 16)
    want = sla.sparse_latent_reference(q, pool, table, pos, sel,
                                       v_lanes=128, scale=0.1)
    value, last = dsv3.topk_threshold(scores, 16)
    got = lpa.latent_paged_attention(
        q, pool, table, pos, v_lanes=128, scale=0.1,
        select=(scores, value, last), **shape)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_scan_refuses_index_pages_that_are_not_whole_tiles():
    with pytest.raises(ValueError, match="whole tiles"):
        sla.paged_index_scores(
            jnp.zeros((1, 4, 64)), jnp.zeros((1, 4)), jnp.zeros((8, 4, 64)),
            np.zeros((1, 2), np.int32), np.zeros(1, np.int32))


# --------------------------------------------------- (f) the router


def test_group_limited_route_on_a_hand_worked_case():
    """8 experts in 4 groups of 2, 2 groups kept, top-3. score + bias:
    group 0 (.9, .1) = 1.0, group 1 (.6, .5) = 1.1, group 2 (.8, .0) = .8,
    group 3 (.7, .45) = 1.15: groups 3 and 1 stay, their best three are
    experts 6 (.7), 2 (.6), 3 (.5); expert 0 (.9), best of all, is out."""
    choice = np.array([[.9, .1, .6, .5, .8, .0, .7, .45]], np.float32)
    bias = np.array([.2, 0, 0, 0, 0, -.1, 0, 0], np.float32)
    s = choice - bias                         # the sigmoid's values
    x = jnp.asarray(np.log(s / (1 - s)))      # its logits, gate = identity
    idx, w = sigmoid_topk_route(x, jnp.eye(8), jnp.asarray(bias), 3,
                                scale=2.5, n_group=4, topk_group=2)
    assert np.asarray(idx).tolist() == [[6, 2, 3]]
    picked = s[0, [6, 2, 3]]
    assert np.allclose(np.asarray(w)[0], 2.5 * picked / picked.sum(),
                       atol=1e-6)
    ridx, rw = R.route(dict(CFG, n_group=4, topk_group=2,
                            num_experts_per_tok=3), x, jnp.eye(8),
                       jnp.asarray(bias))
    assert np.array_equal(np.asarray(ridx), np.asarray(idx))
    assert np.allclose(np.asarray(rw), np.asarray(w), atol=1e-6)


def test_one_group_is_todays_route():
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], (37, 64))
    gate = jax.random.normal(ks[1], (64, 16)) * 0.3
    bias = jax.random.normal(ks[2], (16,)) * 0.3
    idx, w = sigmoid_topk_route(x, gate, bias, 4, scale=2.5)
    idx1, w1 = sigmoid_topk_route(x, gate, bias, 4, scale=2.5, n_group=1,
                                  topk_group=1)
    assert np.array_equal(idx, idx1) and np.array_equal(w, w1)
    # all groups kept: the limit is none
    idx4, w4 = sigmoid_topk_route(x, gate, bias, 4, scale=2.5, n_group=4,
                                  topk_group=4)
    assert np.array_equal(idx, idx4) and np.array_equal(w, w4)
    # and a real limit changes some token's set
    idx2, _ = sigmoid_topk_route(x, gate, bias, 4, scale=2.5, n_group=4,
                                 topk_group=2)
    assert not np.array_equal(np.sort(idx, -1), np.sort(idx2, -1))
    kept_groups = np.sort(np.asarray(idx2) // 4, -1)
    assert all(len(set(r)) <= 2 for r in kept_groups.tolist())


def test_all_ranks_shares_add_up_to_the_uncut_references_layer(seeded):
    """Experts 0-3, 4-7, 8-11, 12-15 on four ranks under the group-limited
    router, the shared expert counted once: the uncut reference's layer."""
    _, weights = seeded
    x = jax.random.normal(jax.random.key(1), (21, CFG["hidden_size"]))
    p = R.layer(weights, 2)
    keep = lambda v: v
    ks = jax.random.split(jax.random.key(2), 3)
    full = {n: 0.1 * jax.random.normal(k, (16,) + p[n].shape[1:])
            for n, k in zip(("mlp.experts.gate_proj", "mlp.experts.up_proj",
                             "mlp.experts.down_proj"), ks)}
    with jax.default_matmul_precision("highest"):
        uncut = R.moe(dict(CFG, experts_held=16, first_expert=0), keep, x,
                      {**p, **full})
        shared = R.v3._swiglu(
            keep, x, p["mlp.shared_experts.gate_proj.weight"],
            p["mlp.shared_experts.up_proj.weight"],
            p["mlp.shared_experts.down_proj.weight"])
        total = shared
        for first in range(0, 16, 4):
            part = {n: v[first:first + 4] for n, v in full.items()}
            # the program's layer on this rank's share, less the shared
            # expert that every rank computes alike
            y, _ = dsv3.moe_ffn(
                DeepseekV3Config(**dict(CFG, first_expert=first)),
                {"mlp." + k[4:] if k.startswith("mlp.") else k: v
                 for k, v in {**p, **part}.items()}, "mlp.", x)
            total = total + (y - shared)
    assert float(jnp.abs(total - uncut).max()) < 1e-5


# --------------------------------------------------- (g) the runner


def test_pool_is_two_arrays_a_layer_behind_one_table(seeded):
    model, _ = seeded
    runner = _engine(model).runner
    assert runner.page_layout() == [((128,), jnp.float32),
                                    ((128,), jnp.float32)]
    pool = KVCachePool.for_runner(runner, 8)
    assert [tuple(a.shape) for a in pool.pools[0]] == [(8, 4, 128),
                                                       (8, 4, 128)]
    assert len(pool.pools) == CFG["num_hidden_layers"]
    # a copy-on-write fork carries both
    pages = [tuple(a.at[3].set(1.0) for a in layer)
             for layer in pool.page_pools]
    pool.page_pools = pages
    pool.copy_page(3, 5)
    assert all(float(a[5].min()) == 1.0 for layer in pool.page_pools
               for a in layer)


def test_int8_rung_converts_the_indexers_matrices(seeded):
    model, _ = seeded
    runner = _engine(model, weight_dtype="int8").runner
    for n in ("wq_b", "wk", "weights_proj"):
        name = f"layers.1.self_attn.indexer.{n}.weight"
        assert runner.params[name].dtype == jnp.int8
        assert name + mr.SCALE_SUFFIX in runner.params
    assert runner.params["layers.1.self_attn.indexer.k_norm.bias"
                         ].dtype == jnp.float32
    with pytest.raises(ValueError, match="stated dtype"):
        _engine(model, kv_dtype="fp8")


@pytest.mark.parametrize("attn_impl", ["reference", "ragged"])
def test_a_selection_need_not_be_whole_pages(attn_impl):
    """index_topk 6 on pages of 4: nothing reads the chosen rows as pages,
    so the count is free; decode logits against the reference's forward."""
    cfg = dict(CFG, index_topk=6, num_nextn_predict_layers=0)
    weights = R.init_weights(cfg, R.seed_key(4))
    model = DeepseekV3ForCausalLM(DeepseekV3Config(**cfg, dtype="float32"))
    model.set_state_dict(
        {k: paddle.Tensor(v) for k, v in R.program_names(weights).items()})
    model.eval()
    runner = _engine(model, attn_impl=attn_impl).runner
    pool = KVCachePool.for_runner(runner, 16)
    table = pool.pad_table(pool.allocator.alloc(6), 6)
    toks = _tokens(21, 12).tolist()
    with jax.default_matmul_precision("highest"):
        _, pools = runner.prefill(toks[:17], table, pool.pools)
        rows = []
        for i in range(4):
            logits, pools = runner.decode(
                np.asarray([toks[17 + i]]), np.asarray(table)[None],
                np.asarray([17 + i]), pools)
            rows.append(np.asarray(logits)[0])
        ref = np.asarray(R.logits_at(cfg, weights, jnp.asarray(toks), 17, 4))
    assert np.abs(np.stack(rows) - ref).max() < TOL


# ------------------------------- (h) Kimi's programs, which may not move

# sha256 of the StableHLO text that bench/tests/data/toy-mla lowers to, as
# the commit before the indexer (bea75ec) lowered it: the decode step
# through the gather path and through the latent kernel, and the prefill
# step. A change that means to move Kimi's programs re-pins them.
KIMI_PROGRAMS = {
    ("reference", "decode"): "fbe354c621a252a9",
    ("ragged", "decode"): "e81cb62791db3d26",
    ("reference", "prefill"): "e16113fed2612711",
    ("ragged", "prefill"): "e16113fed2612711",
}


@pytest.mark.parametrize("attn_impl", ["reference", "ragged"])
def test_kimis_toy_lowers_to_the_programs_it_lowered_to(attn_impl):
    with open(os.path.join(ROOT, "bench", "tests", "data", "toy-mla",
                           "configs", "toy-mla.json")) as f:
        c = json.load(f)
    cfg = {a: c[k] for a, k in c["program"]["args"].items()}
    model = DeepseekV3ForCausalLM(DeepseekV3Config(**cfg)).bfloat16()
    model.eval()
    r = mr.build_runner(model, dtype=jnp.bfloat16, attn_impl=attn_impl,
                        max_model_len=256)
    assert not r.sparse and len(r.COUNTS) == 5
    pool = KVCachePool.for_runner(r, num_blocks=40)
    assert [(tuple(a.shape), str(a.dtype)) for a in pool.pools[0]] == [
        ((40, 16, 128), "bfloat16")]
    B = 4
    texts = {
        "decode": jax.jit(r._decode_step).lower(
            r.params, jnp.zeros((B, 1), jnp.int32),
            jnp.zeros((B, 16), jnp.int32), jnp.zeros((B,), jnp.int32),
            pool.pools).as_text(),
        "prefill": jax.jit(r._prefill_step).lower(
            r.params, jnp.zeros((1, 32), jnp.int32),
            jnp.zeros((1, 16), jnp.int32), jnp.int32(20), jnp.int32(0),
            pool.pools).as_text()}
    for step, text in texts.items():
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            KIMI_PROGRAMS[attn_impl, step], (attn_impl, step)
