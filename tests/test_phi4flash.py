"""Phi-4-mini-flash-reasoning at toy width on the CPU (8 layers, so that
every kind of layer is there: mamba, window, mamba, window, mamba, full,
gmu, cross; window 8, pages of 4): the model against the plain reference,
the three forms of the selective scan, the lower-bounded kernel, the cache
by layer type (state slots, a ring of window pages, one shared cache), the
prefill that stops before the cross-decoder, and prefill-then-decode
through the engine against the reference's full forward, LOGITS not tokens.

Tolerances. Everything here is float32 on the CPU, so two orders of the
same sums differ by rounding only: a logit row's spread is about 0.1 and
its rounding noise through 8 layers a few 1e-6; `TIGHT` (2e-5 absolute) is
some five times that and a thousandth of what any missing term moves (a
dropped `D x`, a window one key too wide, a wrong slot each read 1e-2 or
more)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import create_serving_engine
from paddle_tpu.models.phi4flash import (
    Phi4FlashConfig, Phi4FlashForCausalLM, forward_plain,
)
from paddle_tpu.serving import SamplingParams, naive_generate

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
import reference_phi4flash as R  # noqa: E402

TIGHT = 2e-5
WINDOW, PAGE, MAX_LEN, VOCAB = 8, 4, 64, 96
ARGS = dict(vocab_size=VOCAB, hidden_size=32, intermediate_size=64,
            num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=2, sliding_window=WINDOW, mb_per_layer=2,
            layer_norm_eps=1e-5, tie_word_embeddings=True, mamba_d_state=4,
            mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=None,
            max_seq_len=MAX_LEN)


@pytest.fixture(scope="module")
def toy():
    """(config, the reference's weights, the model holding them)."""
    cfg = Phi4FlashConfig(**ARGS)
    weights = jax.jit(lambda k: R.init_weights(ARGS, k))(R.seed_key(2**31 + 7))
    model = Phi4FlashForCausalLM(cfg)
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
    of the row that made the logits, the token fed there or None, logits
    row)."""
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
    cfg = Phi4FlashConfig()
    kinds = [cfg.kind(i) for i in range(32)]
    assert kinds[:17:2] == ["mamba"] * 9 and kinds[1:16:2] == ["window"] * 8
    assert kinds[17] == "full" and cfg.memory_layer == 16
    assert kinds[18::2] == ["gmu"] * 7 and kinds[19::2] == ["cross"] * 7
    assert cfg.mamba_dt_rank == 160 and cfg.d_inner == 5120
    # what a sequence keeps of the nine scan layers, bfloat16 rows
    assert cfg.state_bytes_per_sequence(2) == 9 * 358400


@pytest.mark.parametrize("length", [40, 256])
def test_model_matches_the_plain_reference(toy, length):
    """40 rows are one block of the reference's attention; 256 are four
    blocks of 64 in four causal groups, a window layer's block against its
    own slice of 64 + 7 keys."""
    cfg, weights, model = toy
    tokens = np.random.default_rng(1).integers(0, VOCAB, length)
    got = np.asarray(model.forward(jnp.asarray(tokens)[None])._value[0])
    want = _reference_logits(weights, tokens)
    assert np.abs(got - want).max() < TIGHT
    assert np.std(want) > 0.05            # the rows say something


def test_stacked_leaves_are_the_layers_own(toy):
    """A group's leaves are drawn in one loop and kept stacked beside the
    layers' own: the same numbers, which are also what one layer drawn
    alone gets (to an ulp: outside the loop the compiler may fuse the
    scale into the draw another way); the program is handed the layers'
    own only, and the bfloat16 form keeps the stacks and the top leaves
    only."""
    _, weights, _ = toy
    for group, (what, layers) in R.groups(ARGS).items():
        alone = R.draw_layer(ARGS, R.seed_key(2**31 + 7), layers[-1], what)
        for n in R.layer_shapes(ARGS, what):
            stacked = np.asarray(weights[f"{R.STACK}{group}.{n}"])
            assert stacked.shape[0] == len(layers)
            for j, l in enumerate(layers):
                assert np.array_equal(stacked[j],
                                      np.asarray(weights[f"layers.{l}.{n}"]))
            assert np.allclose(stacked[-1], np.asarray(alone[n]), rtol=1e-6,
                               atol=0)
    assert not any(k.startswith(R.STACK) for k in R.program_names(weights))
    held = R.round_weights(weights, "bfloat16")
    assert not any(k.startswith("layers.") for k in held)
    assert all(v.dtype == jnp.bfloat16 for v in held.values())


@pytest.mark.parametrize("blocks", [None, (16, 8, 16, 8)])
def test_exact_products_equal_highest_precision(toy, blocks, monkeypatch):
    """Over bfloat16 weights every product is made of bfloat16 pieces in
    float32 (`_mm_rows`, `_six`, `_three`); over the same numbers held as
    float32 it is `precision="highest"`. Both streams agree to float32
    rounding (a few 1e-7 of logits of spread 0.1), whole and by blocks of
    rows (16 of the products, 8 of the logits, 16 and 8 query rows)."""
    _, weights, _ = toy
    if blocks:
        for name, n in zip(("ROW_BLOCK", "HEAD_BLOCK", "QUERY_BLOCK",
                            "CAUSAL_BLOCK"), blocks):
            monkeypatch.setattr(R, name, n)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, VOCAB, 64))
    fn = jax.jit(lambda w, t: tuple(R.logits_at(ARGS, w, t, 3, 32, stored)
                                    for stored in R.PRECISIONS))
    rounded = {k: R._round(v) for k, v in weights.items()}
    want, got = fn(rounded, tokens), fn(R.round_weights(weights, "bfloat16"),
                                        tokens)
    assert got[1].dtype == jnp.bfloat16     # what stream 1 hands on
    for w, g in zip(want, got):
        assert np.abs(np.asarray(w, np.float32)
                      - np.asarray(g, np.float32)).max() < 1e-6
    # the streams differ by what rounding the activations costs
    assert np.abs(np.asarray(got[0]) - np.asarray(got[1], np.float32)
                  ).max() > 1e-4


def test_rows_asked_for_equal_every_layer_on_every_row(toy):
    """The reference's layers after the full one run on the rows it is
    asked for; `hidden` of every row runs every layer on every row. The
    same numbers, in both streams."""
    _, weights, _ = toy
    tokens = jnp.asarray(np.random.default_rng(4).integers(0, VOCAB, 48))
    whole = jax.jit(lambda w, t: R.hidden(ARGS, w, t))(weights, tokens)
    part = jax.jit(lambda w, t, first: R.hidden(ARGS, w, t, first, 16))(
        weights, tokens, 21)
    assert whole.shape == (2, 48, 32) and part.shape == (2, 16, 32)
    assert np.abs(np.asarray(whole[:, 21:37]) - np.asarray(part)).max() < 1e-6


def test_both_streams_come_of_one_forward(toy, monkeypatch):
    """serve.py's jitted function asks for "float32", then for "bfloat16",
    of the same operands: the second costs nothing."""
    _, weights, _ = toy
    calls, hidden = [], R.hidden
    monkeypatch.setattr(R, "hidden", lambda *a, **k: calls.append(1)
                        or hidden(*a, **k))
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, VOCAB, 24))
    jax.jit(lambda w, t, first: tuple(
        R.logits_at(ARGS, w, t, first, 16, stored)
        for stored in R.PRECISIONS))(weights, tokens, 3)
    assert len(calls) == 1
    # other operands: another forward
    jax.jit(lambda w, t: R.logits_at(ARGS, w, t, 0, 16))(weights, tokens[:20])
    assert len(calls) == 2


# -------------------------------------------------------------- the scan


def _scan_inputs(T=37, c=64, n=4, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (T, c)),
            jax.nn.softplus(jax.random.normal(k[1], (T, c))),
            -jnp.exp(jax.random.normal(k[2], (n, c))),
            jax.random.normal(k[3], (T, n)), jax.random.normal(k[4], (T, n)),
            jax.random.normal(k[5], (n, c)))


@pytest.mark.parametrize("form", ["chunked", "step", "kernel"])
def test_scan_forms_equal_the_recurrence(form):
    """Same products in the same order, so equal to float32 rounding of a
    sum of 4 terms of size 1: 1e-5."""
    from paddle_tpu.ops import selective_scan as ss
    from paddle_tpu.ops.pallas.selective_scan_decode import \
        selective_scan_decode

    x, dt, A, B, C, h0 = _scan_inputs()
    y, h = ss.selective_scan_recurrence(x, dt, A, B, C, h0)
    if form == "chunked":
        y2, h2 = ss.selective_scan_chunked(x, dt, A, B, C, h0)
        # a padding row (dt = 0) leaves the state alone
        y3, h3 = ss.selective_scan_chunked(
            jnp.concatenate([x, x[:5]]), jnp.concatenate(
                [dt, jnp.zeros_like(dt[:5])]), A, jnp.concatenate([B, B[:5]]),
            jnp.concatenate([C, C[:5]]), h0)
        assert np.abs(h3 - h).max() < 1e-5 and np.abs(y3[:37] - y).max() < 1e-5
    else:
        # token by token over a pool of 3 slots; slot 1 is dead throughout
        pool = jnp.stack([h0, 7.0 * h0, h0])
        live = jnp.asarray([True, False, True])
        ys = []
        for t in range(x.shape[0]):
            row = lambda a: jnp.stack([a[t]] * 3)
            if form == "kernel":
                yt, pool = selective_scan_decode(pool, row(x), row(dt), A,
                                                 row(B), row(C), live)
            else:
                yt, new = ss.selective_scan_step(
                    pool, row(x), jnp.where(live[:, None], row(dt), 0.0), A,
                    row(B), row(C))
                pool = new
            ys.append(yt[2])
        y2, h2 = jnp.stack(ys), pool[0]
        assert np.array_equal(np.asarray(pool[1]), np.asarray(7.0 * h0))
        assert np.abs(pool[2] - h).max() < 1e-5
    assert np.abs(y2 - y).max() < 1e-5 and np.abs(h2 - h).max() < 1e-5


# ------------------------------------------------------------ the kernel


@pytest.mark.parametrize("rows", [True, False])
def test_kernel_with_a_lower_bound_equals_dense_masked_attention(rows):
    """A bound INSIDE a page (22 = 5 * 4 + 2), one at 0, a dead row; row
    pools and the 4-D pools alike. Against attention written out here, not
    the module's own gather oracle. Float32 in interpret mode: 2e-6."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention

    k = jax.random.split(jax.random.key(3), 4)
    B, n_q, n_kv, d, ps, P, N = 3, 8, 2, 128, 4, 12, 40
    q = jax.random.normal(k[0], (B, 1, n_q, d))
    kp = jax.random.normal(k[1], (N, ps, n_kv, d))
    vp = jax.random.normal(k[2], (N, ps, n_kv, d))
    table = jax.random.permutation(k[3], jnp.arange(1, N))[:B * P].reshape(
        B, P)
    start, qlen = jnp.asarray([37, 5, 20]), jnp.asarray([1, 1, 0])
    lower = jnp.asarray([22, 0, 3])
    pools = (kp.reshape(N, ps * n_kv, d), vp.reshape(N, ps * n_kv, d)) \
        if rows else (kp, vp)
    got = ragged_paged_attention(q, *pools, table, start, qlen, scale=0.125,
                                 lower=lower, kv_heads=n_kv if rows else None)
    for b in range(B):
        if not int(qlen[b]):
            assert not np.asarray(got[b]).any()
            continue
        keys = kp[table[b]].reshape(P * ps, n_kv, d)
        vals = vp[table[b]].reshape(P * ps, n_kv, d)
        lo, hi = int(lower[b]), int(start[b])
        for h in range(n_q):
            g = h // (n_q // n_kv)
            s = keys[lo:hi + 1, g] @ q[b, 0, h] * 0.125
            want = jax.nn.softmax(s) @ vals[lo:hi + 1, g]
            assert np.abs(got[b, 0, h] - want).max() < 2e-6


# --------------------------------------------------- the cache, by groups


def test_pool_names_three_caches_and_counts_their_bytes(toy):
    cfg, _, model = toy
    eng = _engine(model, num_blocks=20, max_batch_size=5)
    pool = eng.pool
    pages, states, ring = pool.pools
    # one layer keeps its whole context: K and V rows of 1 pair of 16 lanes
    assert pool.num_layers == 1 and len(pages) == 1
    assert pages[0][0].shape == (20, PAGE * 1, 16)
    assert pool.memory_bytes() == 20 * 2 * PAGE * 16 * 4
    # two window layers, ceil(8 / 4) + 1 = 3 pages a sequence, and scratch
    assert pool.window.pages_per_seq == 3 and len(ring) == 2
    assert ring[0][0].shape == (1 + 5 * 3, PAGE, 16)
    assert pool.window_bytes() == 16 * 2 * 2 * PAGE * 16 * 4
    # three scan layers, a slot a sequence and none for scratch
    assert pool.state_slots == 5 and len(states) == 3
    assert pool.state_bytes() == 5 * cfg.state_bytes_per_sequence(4)
    assert pool.state_bytes() == sum(a.nbytes for layer in states
                                     for a in layer)


def test_window_group_gives_back_what_fell_behind(toy):
    """Never more than ceil(W / page) + 1 pages a sequence, whatever the
    context; all of them back when the requests have ended."""
    _, _, model = toy
    eng = _engine(model)
    ring = eng.pool.window
    rng = np.random.default_rng(5)
    for n, out in ((30, 20), (5, 30), (17, 9), (9, 12)):
        eng.add_request(rng.integers(0, VOCAB, n).tolist(),
                        SamplingParams(max_tokens=out))
    most = 0
    while eng.has_work():
        eng.step()
        for req in eng.scheduler.running:
            assert ring.held(req.slot) <= ring.pages_per_seq == 3
            most = max(most, ring.held(req.slot))
        assert ring.num_held == sum(ring.held(r.slot)
                                    for r in eng.scheduler.running)
    assert most == 3 and ring.pages_returned == ring.pages_taken > 20
    assert ring.check_no_leaks() and eng.pool.allocator.check_no_leaks()
    snap = eng.metrics.snapshot()
    # a whole-context cache would hold several times the ring's pages
    assert 0 < snap["window_pages_held"] < 0.6 * snap[
        "window_pages_whole_context"]
    assert snap["window_pages_returned"] == ring.pages_returned


@pytest.mark.parametrize("model_kind", ["gpt", "olmo"])
def test_a_runner_without_groups_gets_the_parents_pool_and_tables(model_kind):
    """The block tables a launch is built from are, byte for byte, what the
    engine built before page groups existed: SCRATCH-padded rows of the
    requests' own pages, `max_pages_per_seq` wide."""
    from paddle_tpu.serving.kv_cache import SCRATCH_PAGE

    if model_kind == "gpt":
        from paddle_tpu.models.gpt import GPT, GPTConfig
        model = GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                              num_heads=4, max_seq_len=48))
    else:
        from paddle_tpu.models.olmo_hybrid import (
            OlmoHybridConfig, OlmoHybridForCausalLM,
        )
        model = OlmoHybridForCausalLM(OlmoHybridConfig(
            vocab_size=64, hidden_size=32, intermediate_size=48,
            num_hidden_layers=4, num_attention_heads=4,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8, max_seq_len=48))
    model.eval()
    eng = create_serving_engine(model, num_blocks=40, block_size=4,
                                max_batch_size=3, max_model_len=48)
    assert eng.pool.window is None and eng.pool.window_pools == []
    assert isinstance(eng.pool.pools, tuple if model_kind == "olmo"
                      else list)
    if model_kind == "olmo":
        assert len(eng.pool.pools) == 2 and eng.pool.state_slots == 3
    rng = np.random.default_rng(0)
    for n in (5, 9, 14):
        eng.add_request(rng.integers(0, 64, n).tolist(),
                        SamplingParams(max_tokens=6))
    eng.step()
    rows = eng._decode_rows()
    assert len(rows) == 3
    tokens, tables, pos = eng._build_batch(rows)
    want = np.full((3, eng.max_pages_per_seq), SCRATCH_PAGE, np.int32)
    for req, sl, *_ in rows:
        want[sl, :len(req.kv.pages)] = req.kv.pages
    assert tables.dtype == want.dtype and tables.tobytes() == want.tobytes()


# ------------------------------------ prefill that stops before the cross


@pytest.mark.parametrize("span,budget", [(2048, None), (8, None), (8, 5)])
def test_prefill_that_skips_equals_the_unskipped_forward(toy, span, budget,
                                                         monkeypatch):
    """The chunk's last row only goes through the full layer's attention
    and the cross-decoder; in pieces of 8 rows, and under an engine that
    chunks at 5 tokens, the logits are the dense forward's last row, and
    the decode steps that follow read a cache as good as its own."""
    cfg, weights, model = toy
    from paddle_tpu.serving.runners.phi4flash import Phi4FlashRunner
    monkeypatch.setattr(Phi4FlashRunner, "PREFILL_SPAN", span)
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
    assert snap["cross_rows_skipped"] == 27 - (1 if budget is None else 6)
    assert snap["ssm_prefill_tokens"] == 27


# ------------------------------ prefill then decode, against the reference


def test_engine_logits_match_the_reference_past_the_window(toy):
    """Contexts under (5 + 2), at (6 + 2) and several pages past (30 + 20
    = 50 tokens, 12 pages) the window of 8; five requests on three slots,
    so two slots are re-taken, and the first request ends early. Every
    logits row the engine sampled from, against the reference's one full
    forward of that request's final sequence."""
    _, weights, model = toy
    eng = _engine(model)
    rows = _tap(eng.runner)
    rng = np.random.default_rng(0)
    asks = ((5, 2), (6, 2), (30, 20), (23, 12), (3, 12))
    rids = [eng.add_request(rng.integers(0, VOCAB, n).tolist(),
                            SamplingParams(max_tokens=m)) for n, m in asks]
    outs = eng.run()
    slots_taken = eng.metrics.snapshot()["state_slot_resets"]
    assert slots_taken == 5
    compared = 0
    for rid in rids:
        prompt, out = outs[rid].prompt_tokens, outs[rid].output_tokens
        seq = list(prompt) + list(out)
        ref = _reference_logits(weights, seq)
        assert list(ref.argmax(-1)[len(prompt) - 1:len(seq) - 1]) == out
        for pos, fed, logits in rows:
            # a row is this request's by its position and the token fed
            # there; where two requests are alike in both, the row has to
            # agree with one of them, which the count below holds it to
            mine = (fed == tuple(prompt)) if isinstance(fed, tuple) else (
                len(prompt) <= pos < len(seq) and fed == seq[pos])
            if mine and (isinstance(fed, tuple) or np.abs(
                    logits - ref[pos]).max() < 1e-2):
                assert np.abs(logits - ref[pos]).max() < TIGHT
                compared += 1
    # every request's prefill row and every decode row found its reference
    assert compared == sum(m for _, m in asks)
    assert eng.pool.window.check_no_leaks()
    assert eng.pool.allocator.check_no_leaks()


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


def test_engine_counts_the_edge_blocks_of_both_groups_walks(toy, monkeypatch):
    """`ragged_blocks` / `ragged_edge_blocks` after a toy serve are the pure
    function's counts over every decode launch's own operands: the full
    group's walk at the contexts, the window group's at the ring's own
    positions under its bound. (The toy's pages are too narrow for the
    kernel on the CPU, so the programs keep the gather path and only the
    host's accounting is told the kernel runs: it is arithmetic on what a
    launch was given.)"""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_block_counts

    _, _, model = toy
    eng = _engine(model)
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=3))
    eng.run()                                   # the programs exist
    runner = eng.runner
    monkeypatch.setattr(runner, "_attn_impl_for", lambda span: "ragged")
    ppb = 2                                     # blocks of 8 keys
    runner._fold_pages = {1: ppb}
    runner.reset_attn_counters()
    launches, real = [], runner._account_decode

    def logged(pos, tables):
        launches.append((np.array(pos), np.array(tables)))
        real(pos, tables)

    monkeypatch.setattr(runner, "_account_decode", logged)
    rng = np.random.default_rng(1)
    for n, m in ((5, 4), (30, 21), (17, 9)):
        eng.add_request(rng.integers(0, VOCAB, n).tolist(),
                        SamplingParams(max_tokens=m))
    eng.run()
    want = np.zeros(2, np.int64)
    shares = []
    for pos, tables in launches:
        rel = pos - tables[:, -1] * PAGE
        for start, lower in ((pos, None),
                             (rel, np.maximum(rel - (WINDOW - 1), 0))):
            got = ragged_block_counts(start, np.ones_like(pos), PAGE, ppb,
                                      lower)
            want += [got[0].sum(), got[1].sum()]
            shares.append(got[1].sum() / got[0].sum())
    snap = eng.metrics.snapshot()
    assert launches and 0 < want[1] < want[0]
    assert [snap["ragged_blocks"], snap["ragged_edge_blocks"]] == want.tolist()
    assert snap["ragged_blocks"] == runner.ragged_blocks
    # the rings hold little but edges; the whole contexts' walks less
    assert np.mean(shares[1::2]) > np.mean(shares[0::2])


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
