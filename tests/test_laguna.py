"""Laguna at toy width on the CPU (nine layers: the dense first layer and two
whole periods of full, sliding, sliding, sliding, so a ninth full layer;
6 and 8 query heads over 2 key/value heads of 16; 64 experts, 4 a token;
window 8, pages of 4): the model against the plain reference, each assumed
item of the configuration pinned by a case of its own, prefill-then-decode
through the engine against the reference's full forward (LOGITS, past the
window, with a request ending and its slot, pages and ring re-taken), the
ragged kernel at the PUBLISHED head geometry in interpret mode, and the
expert layer at 256 held experts in both of its walks.

Tolerances. Everything here is float32 on the CPU, so two orders of the
same sums differ by rounding only: a logit row's spread is about 0.1 and
its rounding noise through 9 layers a few 1e-6; `TIGHT` (2e-5 absolute) is
some five times that and a thousandth of what any missing term moves (a
gate left out, a window one key too wide, a wrong slot each read 1e-2 or
more)."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import create_serving_engine
from paddle_tpu.models import laguna as M
from paddle_tpu.models.laguna import (
    FULL, SLIDING, LagunaConfig, LagunaForCausalLM, forward_plain,
)
from paddle_tpu.parallel import moe
from paddle_tpu.serving import SamplingParams, naive_generate

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")
sys.path.insert(0, BENCH)
import opcount_laguna  # noqa: E402
import reference_laguna as R  # noqa: E402

TIGHT = 2e-5
WINDOW, PAGE, MAX_LEN, VOCAB = 8, 4, 64, 96
ROPE = {FULL: {"rope_theta": 10000, "rope_type": "yarn", "factor": 4,
               "original_max_position_embeddings": 16, "beta_slow": 1,
               "beta_fast": 8, "attention_factor": 1.1386294361119891,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 100,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 16}
ARGS = dataclasses.asdict(LagunaConfig(
    vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
    num_hidden_layers=9, num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, num_experts=64, num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    sliding_window=WINDOW, rope_parameters=ROPE, max_seq_len=MAX_LEN))


@pytest.fixture(scope="module")
def toy():
    """(config, the reference's weights, the model holding them)."""
    cfg = LagunaConfig(**ARGS)
    weights = jax.jit(lambda k: R.init_weights(ARGS, k))(R.seed_key(2**31 + 7))
    model = LagunaForCausalLM(cfg)
    missing, unexpected = model.set_state_dict(
        {k: paddle.Tensor(v) for k, v in R.program_names(weights).items()})
    assert not missing and not unexpected
    model.eval()
    return cfg, weights, model


def _engine(model, **kw):
    kw = {"num_blocks": 64, "block_size": PAGE, "max_batch_size": 3,
          "max_model_len": MAX_LEN, "audit": True, **kw}
    return create_serving_engine(model, **kw)


def _reference_logits(weights, tokens):
    toks = jnp.asarray(tokens, jnp.int32)
    return np.asarray(jax.jit(lambda w, t: R.logits_at(
        ARGS, w, t, 0, len(tokens)))(weights, toks))


def _tap(runner):
    """Keeps what the runner's two entries return to the engine: (position
    of the row that made the logits, the token fed there or the prompt,
    logits row)."""
    rows, decode, prefill = [], runner.decode, runner.prefill_chunk

    def tapped_decode(tokens, tables, pos, pools):
        logits, pools = decode(tokens, tables, pos, pools)
        for b, p in enumerate(np.asarray(pos)):
            if np.asarray(tables)[b, 0]:       # a dead slot's is scratch
                rows.append((int(p), int(np.asarray(tokens).ravel()[b]),
                             np.asarray(logits[b], np.float32)))
        return logits, pools

    def tapped_prefill(tokens, start, table, pools, **at):
        logits, pools = prefill(tokens, start, table, pools, **at)
        rows.append((start + len(tokens) - 1, tuple(tokens),
                     np.asarray(logits, np.float32)))
        return logits, pools

    runner.decode, runner.prefill_chunk = tapped_decode, tapped_prefill
    return rows


# ------------------------------------------------------------- the model


def test_layer_map_of_the_published_depth():
    cfg = LagunaConfig()
    assert [cfg.kind(i) for i in range(5)] == [FULL] + [SLIDING] * 3 + [FULL]
    assert cfg.layers_of(FULL) == list(range(0, 40, 4))
    assert [cfg.heads(i) for i in range(5)] == [48, 64, 64, 64, 48]
    assert cfg.is_dense(0) and not any(cfg.is_dense(i) for i in range(1, 40))
    assert cfg.rotary_dim(FULL) == 64 and cfg.rotary_dim(SLIDING) == 128


def test_the_published_lists_are_read_up_to_the_depth():
    """The configuration file keeps the three per-layer lists whole (40
    entries); a model of 5 layers reads their first 5."""
    file = json.load(open(os.path.join(BENCH, "configs", "laguna-xs.2.json")))
    args = {a: file[k] for a, k in file["program"]["args"].items()}
    cfg = LagunaConfig(**args)
    assert len(cfg.layer_types) == 40 and cfg.num_hidden_layers == 5
    assert [cfg.kind(i) for i in range(5)] == [FULL] + [SLIDING] * 3 + [FULL]
    assert file["published"]["layer_types"] == file["layer_types"]
    # every number of the published config but the depth, as published
    for key, value in file["published"].items():
        assert file[key] == value or key == "num_hidden_layers"
    assert file["num_experts"] == 256 and file["num_experts_per_tok"] == 8


@pytest.mark.parametrize("length", [40, 256])
def test_model_matches_the_plain_reference(toy, length):
    """Two whole periods and the dense layer; 40 rows are one block of the
    reference's attention, 256 two blocks of 128, a sliding layer's block
    against its own slice of 128 + 7 keys."""
    cfg, weights, model = toy
    tokens = np.random.default_rng(1).integers(0, VOCAB, length)
    got = np.asarray(model.forward(jnp.asarray(tokens)[None])._value[0])
    want = _reference_logits(weights, tokens)
    assert np.abs(got - want).max() < TIGHT
    assert np.std(want) > 0.05            # the rows say something


def test_parameters_are_counted_as_built(toy):
    cfg, _, model = toy
    built = sum(int(np.prod(p.shape)) for _, p in model.named_parameters())
    assert built == opcount_laguna.total_parameters(ARGS) \
        == R.parameter_count(ARGS)


def test_the_configuration_files_counts_are_the_shapes():
    file = json.load(open(os.path.join(BENCH, "configs", "laguna-xs.2.json")))
    args = {a: file[k] for a, k in file["program"]["args"].items()}
    per, said = opcount_laguna.parameters(args), file["parameters"]
    assert per["layers"] == [said["layer_0_full_dense"]] \
        + [said["layer_sliding_sparse"]] * 3 + [said["layer_full_sparse"]]
    assert per["embedding"] + per["head"] == said["embedding_and_head"]
    assert opcount_laguna.total_parameters(args) \
        == said["this_chip_5_layers"] == 3869857792
    assert opcount_laguna.total_parameters(
        dict(args, num_hidden_layers=40)) == said["published_40_layers"]


def test_both_streams_come_of_one_forward(toy):
    """serve.py asks for "float32", then for "bfloat16", on the same
    operands: the second is the pair's other half, and rounding what a
    block hands on moves the logits by bfloat16's grain, not by more."""
    _, weights, _ = toy
    toks = jnp.asarray(np.random.default_rng(2).integers(0, VOCAB, 24))
    f32 = R.logits_at(ARGS, weights, toks, 3, 8, "float32")
    pair = R._pair
    b16 = R.logits_at(ARGS, weights, toks, 3, 8, "bfloat16")
    assert R._pair is pair
    err = float(jnp.abs(f32 - b16).max())
    assert 1e-4 < err < 3e-2


# ------------------------------------------------ the assumed items, each


def test_assumed_gate_is_a_sigmoid_a_head_on_the_heads_output(toy):
    """W_g = 0 gates every head by a half; a column of W_g far below zero
    takes that one head out and leaves the others."""
    cfg, _, model = toy
    params = {k: p._value for k, p in model.named_parameters()}
    pre, rng = "layers.1.self_attn.", np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
    o = jnp.asarray(rng.normal(size=(5, cfg.heads(1), 16)), jnp.float32)
    w_o = params[pre + "o_proj.weight"]
    ungated = o.reshape(5, -1) @ w_o
    zero = dict(params, **{pre + "g_proj.weight": jnp.zeros((64, 8))})
    g = M.head_gate(zero, pre, u)
    assert np.allclose(g, 0.5)
    assert np.abs(M.gated_output(zero, pre, o, g, jnp.float32)
                  - 0.5 * ungated).max() < 1e-6
    shut = jnp.zeros((64, 8)).at[:, 2].set(-1e4 * jnp.sign(u[0]))
    g = M.head_gate(dict(params, **{pre + "g_proj.weight": shut}), pre,
                    u[:1])
    want = (o[:1] * 0.5).at[:, 2].set(0.0).reshape(1, -1) @ w_o
    assert np.abs(M.gated_output(params, pre, o[:1], g, jnp.float32)
                  - want).max() < 1e-6
    assert np.allclose(R.head_gate(u @ jnp.zeros((64, 8))), 0.5)


def test_assumed_window_holds_the_query_and_the_window_less_one_before():
    cfg = LagunaConfig(sliding_window=4)
    pos = jnp.arange(9)
    seen = np.asarray(M.window_mask(cfg, pos, pos))
    for i in range(9):
        assert list(np.flatnonzero(seen[i])) == list(range(max(0, i - 3),
                                                           i + 1))
    assert (np.asarray(R.window_seen(pos, pos, 4)) == seen).all()


def test_assumed_rotary_is_partial_yarn_on_full_and_default_on_sliding():
    """Full layers: the FIRST half of a head turns, by YaRN's blended
    frequencies, cos and sin times attention_factor; sliding layers: the
    whole head, theta^(-2i/d). Pairs are (x[i], x[i + rot/2])."""
    cfg = LagunaConfig(max_seq_len=64)
    cos, sin = M.rope_tables(cfg, FULL, 64)
    rp = cfg.rope_parameters[FULL]
    assert cos.shape == (64, 64)
    assert np.allclose(cos[0], rp["attention_factor"]) and np.allclose(
        sin[0], 0.0)
    plain = 1.0 / rp["rope_theta"] ** (np.arange(0, 64, 2) / 64)
    inv = np.arctan2(np.asarray(sin[1, :32]), np.asarray(cos[1, :32]))
    # fast dims kept, slow dims divided by the factor, a ramp between
    assert np.isclose(inv[0], plain[0], rtol=1e-5)
    assert np.isclose(inv[-1], plain[-1] / rp["factor"], rtol=1e-4)
    assert ((inv <= plain * 1.00001) & (inv >= plain / rp["factor"]
                                         * 0.99999)).all()
    cos_s, sin_s = M.rope_tables(cfg, SLIDING, 64)
    assert cos_s.shape == (64, 128) and np.allclose(cos_s[0], 1.0)
    assert np.allclose(np.arctan2(sin_s[1, :64], cos_s[1, :64]),
                       1.0 / 10000 ** (np.arange(0, 128, 2) / 128),
                       rtol=1e-5)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(64, 3, 128)),
                    jnp.float32)
    y = M.apply_rope(x, cos, sin)
    assert (y[..., 64:] == x[..., 64:]).all()            # the half left
    i, t = 5, 7
    a, b = x[t, 0, i], x[t, 0, i + 32]
    assert np.isclose(y[t, 0, i], a * cos[t, i] - b * sin[t, i], atol=1e-5)
    assert np.isclose(y[t, 0, i + 32], b * cos[t, i] + a * sin[t, i],
                      atol=1e-5)
    for kind in (FULL, SLIDING):
        for got, want in zip(M.rope_tables(cfg, kind, 64), R.rope_tables(
                dataclasses.asdict(cfg), kind, 64)):
            assert np.abs(got - want).max() < 1e-6


def test_assumed_router_weights_are_normalised_sigmoids_times_2_5(toy):
    cfg, _, model = toy
    params = {k: p._value for k, p in model.named_parameters()}
    h = jnp.asarray(np.random.default_rng(5).normal(size=(7, 64)),
                    jnp.float32)
    gate = params["layers.1.mlp.gate.weight"]
    idx, w = moe.sigmoid_topk_route(h, gate, None, 4, scale=2.5)
    s = np.asarray(jax.nn.sigmoid(h @ gate))
    assert (np.sort(np.asarray(idx)) == np.sort(
        np.argsort(-s, axis=-1)[:, :4])).all()
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    assert np.allclose(w, 2.5 * picked / picked.sum(-1, keepdims=True),
                       rtol=1e-5)
    assert np.allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    r_idx, r_w = R.route(ARGS, h, gate)
    assert (np.asarray(r_idx) == np.asarray(idx)).all()
    assert np.abs(r_w - w).max() < 1e-6


def test_assumed_shared_expert_is_added_ungated(toy):
    """The expert layer's output less its routed part is the shared
    SwiGLU's, as it is; and the routed part is the dense sum over every
    expert of weight times SwiGLU."""
    cfg, _, model = toy
    params = {k: p._value for k, p in model.named_parameters()}
    pre = "layers.2.mlp."
    h = jnp.asarray(np.random.default_rng(6).normal(size=(10, 64)),
                    jnp.float32)
    y, counts = M.moe_ffn(cfg, params, pre, h)
    idx, w = moe.sigmoid_topk_route(h, params[pre + "gate.weight"], None, 4,
                                    scale=2.5)
    dense = jnp.zeros_like(h)
    for e in range(64):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        dense += w_e[:, None] * moe._swiglu(
            h, params[pre + "experts.gate_proj"][e],
            params[pre + "experts.up_proj"][e],
            params[pre + "experts.down_proj"][e])
    shared = M.dense_ffn(params, pre + "shared_experts.", h)
    assert np.abs(y - dense - shared).max() < 1e-5
    assert np.abs(shared).max() > 1e-3
    assert counts.tolist()[:2] == [10, 40]


def test_assumed_norms_come_before_both_sublayers(toy):
    """A block is x + Attn(norm(x)) then + FFN(norm(.)): with the input
    norm's gain at zero the attention sublayer adds nothing."""
    cfg, _, model = toy
    params = {k: p._value for k, p in model.named_parameters()}
    one = LagunaConfig(**dict(ARGS, num_hidden_layers=1))
    zero = dict(params, **{"layers.0.input_layernorm.weight":
                           jnp.zeros((64,))})
    toks = jnp.asarray([[3, 9, 27, 81]])
    x = jnp.take(params["embed_tokens.weight"], toks[0], axis=0)
    h = M.rms_norm(x, params["layers.0.post_attention_layernorm.weight"],
                   cfg.rms_norm_eps)
    y = x + M.dense_ffn(params, "layers.0.mlp.", h)
    want = M.rms_norm(y, params["norm.weight"], cfg.rms_norm_eps) \
        @ params["lm_head.weight"]
    assert np.abs(forward_plain(one, zero, toks)[0] - want).max() < 1e-5


# ------------------------------------ the ragged kernel, published geometry


@pytest.mark.parametrize("bounded", [False, True])
@pytest.mark.parametrize("span", [1, 16])
@pytest.mark.parametrize("n_q", [48, 64])
def test_kernel_at_the_published_heads_equals_dense_attention(n_q, span,
                                                              bounded):
    """48 and 64 query heads over 8 key/value heads of 128 (groups of 6 and
    of 8): a decode step's row a sequence (the few-rows layout) and a span
    of 16 rows (the prefill layout, tiles of 16 rows x the group), with a
    window's lower bound and without, in interpret mode against the dense
    masked form."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        _flat, ragged_paged_attention, ragged_reference,
    )

    assert _flat(span, n_q) == (span == 1)
    rng = np.random.default_rng(n_q + span)
    B, pages, ps, kv, d = 2, 12, 16, 8, 128
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, kp, vp = f(B, span, n_q, d), f(pages, ps, kv, d), f(pages, ps, kv, d)
    table = jnp.asarray(rng.permutation(pages - 1)[:B * 5].reshape(B, 5) + 1,
                        jnp.int32)
    start = jnp.asarray([37, 50], jnp.int32)
    qlen = jnp.asarray([span, max(1, span - 3)], jnp.int32)
    lower = jnp.asarray([21, 0], jnp.int32) if bounded else None
    got = ragged_paged_attention(q, kp, vp, table, start, qlen, lower=lower,
                                 interpret=True)
    want = ragged_reference(q, kp, vp, table, start, qlen, lower=lower)
    assert np.abs(got - want).max() < 2e-4
    # and the reference is the definition: head j on key/value head j // rep
    b, t, j = 0, 0, n_q - 1
    keys = kp[table[b]].reshape(-1, kv, d)[:, j // (n_q // kv)]
    vals = vp[table[b]].reshape(-1, kv, d)[:, j // (n_q // kv)]
    lo = int(lower[b]) if bounded else 0
    s = (keys[lo:int(start[b]) + t + 1] @ q[b, t, j]) * d ** -0.5
    assert np.abs(jax.nn.softmax(s) @ vals[lo:int(start[b]) + t + 1]
                  - want[b, t, j]).max() < 1e-4


# ------------------------------------------- the expert layer, 256 experts


@pytest.fixture(scope="module")
def layer256():
    rng = np.random.default_rng(9)
    f = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    T, K, G, d, w = 64, 8, 256, 32, 16
    x, gate = f(T, d), f(d, G)
    idx, wts = moe.sigmoid_topk_route(x, gate, None, K, scale=2.5)
    return x, idx, wts, f(G, d, w), f(G, d, w), f(G, w, d)


def _walks(x, idx, wts, wg, wu, wd, bm=16):
    """Both walks over one layout: {grouped: (y, blocks)}."""
    out = {g: moe._walk(g, bm, x, idx, wts, wg, wu, wd, 0)
           for g in (False, True)}
    return {g: o[0] for g, o in out.items()}, out[True][1], out[True][2]


def test_grouped_walk_equals_the_loop_and_a_dense_sum(layer256):
    x, idx, wts, wg, wu, wd = layer256
    assert moe.grouped_walk(wg, wd, 16)
    ys, _, _ = _walks(*layer256)
    dense = jnp.zeros_like(x)
    for e in range(256):
        w_e = jnp.sum(jnp.where(idx == e, wts, 0.0), -1)
        dense += w_e[:, None] * moe._swiglu(x, wg[e], wu[e], wd[e])
    assert np.abs(ys[True] - ys[False]).max() < 1e-5
    assert np.abs(ys[True] - dense).max() < 1e-5
    y, pairs, touched = moe.held_experts_ffn(x, idx, wts, wg, wu, wd, 0)
    assert np.abs(y - dense).max() < 1e-5


def test_both_walks_count_the_same(layer256):
    x, idx, wts, wg, wu, wd = layer256
    _, counts, blocks = _walks(*layer256)
    y, pairs, touched, walked, rows = moe.held_experts_ffn(
        x, idx, wts, wg, wu, wd, 0, walk=True)
    assert int(pairs) == 64 * 8 == int(counts.sum())
    assert int(touched) == int((counts > 0).sum())
    assert int(walked) == int(blocks) == int(np.ceil(
        np.asarray(counts) / 16).sum())
    assert int(rows) == 16 * int(walked)
    # rows of padding route nowhere, in the counts as in the sums
    valid = jnp.arange(64) < 40
    y2, pairs2, *_ = moe.held_experts_ffn(x, idx, wts, wg, wu, wd, 0, valid)
    assert int(pairs2) == 40 * 8
    assert np.abs(y2[:40] - y[:40]).max() < 1e-5 and not y2[40:].any()


@pytest.mark.parametrize("grouped", [False, True])
def test_an_untouched_expert_costs_no_block_and_is_not_read(layer256,
                                                            grouped):
    """Tokens sent to the first 40 experts only: the walk has a block for
    each of those that got a pair and none for the other 216, whose
    matrices may hold anything."""
    x, idx, wts, wg, wu, wd = layer256
    idx = idx % 40
    nan = lambda w: w.at[40:].set(jnp.nan)
    ys, counts, blocks = _walks(x, idx, wts, nan(wg), nan(wu), nan(wd))
    assert int(blocks) == int(np.ceil(np.asarray(counts) / 16).sum()) \
        and not np.asarray(counts)[40:].any()
    clean, _, _ = _walks(x, idx, wts, wg, wu, wd)
    assert np.isfinite(ys[grouped]).all()
    assert np.abs(ys[grouped] - clean[grouped]).max() < 1e-6


def test_serving_form_has_no_gradient_in_either_walk(layer256):
    x, idx, wts, wg, wu, wd = layer256
    for mats in ((wg, wu, wd), (wg[:8], wu[:8], wd[:8])):
        with pytest.raises(TypeError, match="held_experts_ffn_train"):
            jax.grad(lambda x: jnp.sum(moe.held_experts_ffn(
                x, idx % mats[0].shape[0], wts, *mats, 0)[0]))(x)


@pytest.mark.parametrize("cell,rows,k,routed,block", [
    ("kimi-k2.7-code.decode-16k decode", 48, 8, 384, 16),
    ("kimi-k2.7-code.decode-16k prefill", 16384, 8, 384, 256),
    ("deepseek-v3.2.decode-sparse-16k decode", 36, 8, 256, 16),
    ("deepseek-v3.2.decode-sparse-16k prefill", 16384, 8, 256, 256),
    ("laguna-xs.2.agent-8k decode", 64, 8, 256, 16),
    ("laguna-xs.2.agent-8k piece", 2048, 8, 256, 64),
    ("laguna-xs.2.agent-8k oracle's decode", 1, 8, 256, 16),
    ("a piece on few experts", 2048, 8, 16, 256),
])
def test_block_rows_come_of_the_pairs_an_expert_can_expect(cell, rows, k,
                                                           routed, block):
    """Kimi's and DeepSeek's launches keep the blocks they had before PR 49
    (16 under 4096 pairs, 256 over): their programs do not move."""
    assert moe.block_rows(rows * k, routed) == block
    if "laguna" not in cell and "few" not in cell:
        assert block == (16 if rows * k <= 4096 else 256)


@pytest.mark.parametrize("cell,held,d,f,grouped", [
    ("kimi-k2.7-code", 12, 7168, 2048, False),
    ("deepseek-v3.2", 8, 7168, 2048, False),
    ("laguna-xs.2", 256, 2048, 512, True),
    ("many experts, each too large for a tile", 256, 7168, 2048, False),
])
def test_the_walk_is_chosen_from_shapes(cell, held, d, f, grouped):
    bf = jnp.bfloat16
    wg = jax.ShapeDtypeStruct((held, d, f), bf)
    wd = jax.ShapeDtypeStruct((held, f, d), bf)
    assert moe.grouped_walk(wg, wd, 16) == grouped


def test_router_without_a_bias_selects_by_the_scores_alone():
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    a = moe.sigmoid_topk_route(x, gate, None, 4, scale=2.5)
    b = moe.sigmoid_topk_route(x, gate, jnp.zeros(32), 4, scale=2.5)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()


# ------------------------------ prefill then decode, against the reference


@pytest.mark.parametrize("attn_impl", ["reference", "ragged"])
def test_engine_logits_match_the_reference_past_the_window(toy, attn_impl):
    """Contexts under (5 + 2), at (6 + 2) and several pages past (30 + 20
    = 50 tokens, 12 pages) the window of 8; five requests on three slots,
    so two slots, their pages and their rings are re-taken, and the first
    request ends early. Every logits row the engine sampled from, against
    the reference's one full forward of that request's final sequence.
    "ragged": both kernels in interpret mode (the ragged one at the toy's 6
    and 8 heads over 2, the grouped product at 64 experts)."""
    _, weights, model = toy
    eng = _engine(model, attn_impl=attn_impl)
    rows = _tap(eng.runner)
    rng = np.random.default_rng(0)
    asks = ((5, 2), (6, 2), (30, 20), (23, 12), (3, 12))
    rids = [eng.add_request(rng.integers(0, VOCAB, n).tolist(),
                            SamplingParams(max_tokens=m)) for n, m in asks]
    outs = eng.run()
    snap = eng.metrics.snapshot()
    assert snap["window_rings_taken"] == snap["window_rings_released"] == 5
    compared = 0
    tol = TIGHT if attn_impl == "reference" else 2e-4
    for rid in rids:
        prompt, out = outs[rid].prompt_tokens, outs[rid].output_tokens
        seq = list(prompt) + list(out)
        ref = _reference_logits(weights, seq)
        assert list(ref.argmax(-1)[len(prompt) - 1:len(seq) - 1]) == out
        for pos, fed, logits in rows:
            mine = (fed == tuple(prompt)) if isinstance(fed, tuple) else (
                len(prompt) <= pos < len(seq) and fed == seq[pos])
            if mine and (isinstance(fed, tuple) or np.abs(
                    logits - ref[pos]).max() < 1e-2):
                assert np.abs(logits - ref[pos]).max() < tol
                compared += 1
    assert compared == sum(m for _, m in asks)
    assert eng.pool.window.check_no_leaks()
    assert eng.pool.allocator.check_no_leaks()


@pytest.mark.parametrize("span,budget", [(2048, None), (8, None), (8, 5)])
def test_prefill_in_pieces_equals_the_whole_forward(toy, span, budget,
                                                    monkeypatch):
    """A prompt of 27 as one piece of its bucket, in pieces of 8 rows (the
    ring's tail handed from piece to piece), and under an engine that
    chunks at 5 tokens (the ring loaded and stored around every chunk):
    the logits are the dense forward's last row, and the decode steps that
    follow read caches as good as its own."""
    cfg, weights, model = toy
    from paddle_tpu.serving.runners.laguna import LagunaRunner
    monkeypatch.setattr(LagunaRunner, "PREFILL_SPAN", span)
    eng = _engine(model, max_prefill_tokens_per_step=budget)
    rows = _tap(eng.runner)
    prompt = np.random.default_rng(11).integers(0, VOCAB, 27).tolist()
    rid = eng.add_request(prompt, SamplingParams(max_tokens=6))
    out = eng.run()[rid].output_tokens
    dense = np.asarray(forward_plain(
        cfg, eng.runner.params, jnp.asarray(prompt + out)[None])[0])
    checked = 0
    for pos, fed, logits in rows:
        if isinstance(fed, tuple) and pos != len(prompt) - 1:
            continue                      # an intermediate chunk: unread
        assert np.abs(logits - dense[pos]).max() < TIGHT
        checked += 1
    assert checked == 6
    snap = eng.metrics.snapshot()
    assert snap["moe_tokens_routed"] == 8 * (27 + 5)      # 8 expert layers
    assert snap["moe_local_pairs"] == 4 * snap["moe_tokens_routed"]
    assert snap["moe_decode_pairs"] == 4 * 8 * 5
    assert snap["moe_rows_multiplied"] >= snap["moe_local_pairs"]
    assert snap["moe_blocks_walked"] >= snap["moe_experts_touched"]


@pytest.mark.parametrize("options", [
    {"decode_horizon": 4}, {"pipelined": True},
    {"num_blocks": 22, "max_batch_size": 3}])
def test_engine_options_serve_the_oracles_tokens(toy, options):
    """A horizon of 4 (the ring then covers 4 more positions a launch), the
    pipelined loop, and a pool so small that requests are preempted and
    prefilled again: token for token `naive_generate`'s."""
    _, _, model = toy
    eng = _engine(model, **options)
    rng = np.random.default_rng(4)
    asks = [(rng.integers(0, VOCAB, n).tolist(), m)
            for n, m in ((12, 14), (25, 20), (4, 9), (19, 16))]
    rids = [eng.add_request(p, SamplingParams(max_tokens=m)) for p, m in asks]
    outs = eng.run()
    if "num_blocks" in options:
        assert eng.metrics.snapshot()["preemptions"] > 0
    if "decode_horizon" in options:
        assert eng.pool.window.pages_per_seq == -(-(7 + 4) // PAGE) + 1
    for rid, (p, m) in zip(rids, asks):
        assert outs[rid].output_tokens == naive_generate(
            eng.runner, p, SamplingParams(max_tokens=m), max_model_len=MAX_LEN)
    assert eng.pool.window.check_no_leaks()


def test_pool_names_two_groups_and_no_state(toy):
    """Pages of the three full layers under the allocator, a ring for the
    six sliding ones sized by the program, no state slot; the snapshot
    holds the runner's counters and the group's, under their names."""
    _, _, model = toy
    eng = _engine(model)
    pool, runner = eng.pool, eng.runner
    assert runner.page_groups() == {"full": 3, "window": (6, WINDOW)}
    pages, states, ring = pool.pools
    assert len(pages) == 3 and states == [] and len(ring) == 6
    assert pool.window.pages_per_seq == -(-(WINDOW - 1 + 1) // PAGE) + 1
    assert ring[0][0].shape == (1 + 3 * pool.window.pages_per_seq, PAGE, 2,
                                16)
    snap = eng.metrics.snapshot()
    for name in runner.COUNTS + ("attn_full_page_reads",
                                 "attn_window_page_reads",
                                 "window_pages_held",
                                 "window_pages_whole_context",
                                 "window_rings_taken",
                                 "window_rings_released"):
        assert name in snap
    assert "state_slots_live" not in snap


def test_page_reads_are_counted_by_group(toy):
    _, _, model = toy
    eng = _engine(model)
    eng.add_request(list(range(1, 22)), SamplingParams(max_tokens=4))
    eng.run()
    snap = eng.metrics.snapshot()
    # three decode steps at positions 21, 22, 23: a full layer walks pages
    # 0..pos // 4, a sliding layer those of positions pos - 7 .. pos
    full = sum(p // PAGE + 1 for p in (21, 22, 23))
    ring = sum(p // PAGE - (p - 7) // PAGE + 1 for p in (21, 22, 23))
    assert snap["attn_full_page_reads"] == 3 * full
    assert snap["attn_window_page_reads"] == 6 * ring


@pytest.mark.parametrize("option", [
    {"enable_prefix_cache": True}, {"host_tier_pages": 8},
    {"num_speculative_tokens": 2}, {"ragged_batch": True},
    {"role": "prefill"}])
def test_options_that_copy_or_roll_back_are_refused_by_name(toy, option):
    _, _, model = toy
    with pytest.raises(ValueError) as e:
        _engine(model, **option)
    assert next(iter(option)) in str(e.value)
    assert "window group" in str(e.value)


@pytest.mark.parametrize("quant", [{"kv_dtype": "fp8"},
                                   {"weight_dtype": "int8"}])
def test_the_controls_lower_precision_paths_run_and_differ(toy, quant):
    """What `--probe fp8-kv` and `--probe int8-weights` switch on: both
    serve, and both move the logits by far more than rounding."""
    _, weights, model = toy
    eng = _engine(model, audit=False, **quant)
    rows = _tap(eng.runner)
    prompt = np.random.default_rng(8).integers(0, VOCAB, 20).tolist()
    rid = eng.add_request(prompt, SamplingParams(max_tokens=5))
    out = eng.run()[rid].output_tokens
    ref = _reference_logits(weights, prompt + out)
    worst = max(np.abs(logits - ref[pos]).max() for pos, _, logits in rows)
    assert 20 * TIGHT < worst < 0.1


def test_a_deferred_model_hands_its_weights_to_the_runner():
    cfg = LagunaConfig(**dict(ARGS, num_hidden_layers=2, init="deferred"))
    model = LagunaForCausalLM(cfg)
    assert all(isinstance(p._value, np.ndarray)
               for _, p in model.named_parameters())
    weights = R.init_weights(dict(ARGS, num_hidden_layers=2), R.seed_key(3))
    model.set_state_dict({k: paddle.Tensor(v)
                          for k, v in R.program_names(weights).items()})
    eng = _engine(model)
    assert all(isinstance(p._value, np.ndarray)
               for _, p in model.named_parameters())
    assert float(jnp.abs(eng.runner.params["lm_head.weight"]).sum()) > 0
