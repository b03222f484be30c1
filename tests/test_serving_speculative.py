"""Speculative decoding (ISSUE 5): n-gram prompt-lookup drafts, fused
token-exact ragged verification, rejected-tail KV rollback, batched
device-side sampling, and the streaming detokenization shim.

The acceptance contract mirrors PRs 3-4: the speculative engine must be
token-for-token identical to `naive_generate` — speculation is a pure
launch-count optimization, never a sampling change — across a 200-trial
fuzz with the invariant auditor armed (zero page leaks, speculated pages
never survive rejection), and the repetition-heavy workload must show a
>= 1.5x reduction in engine steps per generated token.

Most tests drive the numpy stubs (StubPagedRunner for adversarial
low-acceptance streams, PeriodicStubRunner for repetition-heavy ones —
both gather history from the real pool, so block-table/rollback bugs
break oracle equality); the end-to-end pin runs the real Llama runner.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from _helpers import PeriodicStubRunner, StubPagedRunner
from paddle_tpu.serving import (
    EngineMetrics, FaultInjector, KVCachePool, NgramProposer, Request,
    SamplingParams, SequenceKV, ServingEngine, StreamDetokenizer,
    complete_utf8_prefix, greedy_grid, naive_generate,
)
from paddle_tpu.serving.scheduler import FCFSScheduler


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    """Every speculative test runs under the invariant auditor — the
    ISSUE-5 rollback guarantees are checked after every step."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


def _engine(runner, num_blocks=24, max_batch=3, max_model_len=64, **kw):
    kw.setdefault("num_speculative_tokens", 4)
    return ServingEngine(runner, num_blocks=num_blocks,
                         max_batch_size=max_batch,
                         max_model_len=max_model_len, **kw)


# ------------------------------------------------------------- proposer


def test_ngram_proposer_longest_suffix_first():
    p = NgramProposer(max_ngram=3, min_ngram=1)
    # suffix [1, 2] recurs at the head; the continuation there is [3, 1]
    assert p.propose([1, 2, 3, 1, 2], 2) == [3, 1]
    # longest n-gram wins over a shorter, more recent one
    assert p.propose([5, 1, 2, 3, 9, 1, 2, 3], 1) == [9]


def test_ngram_proposer_most_recent_occurrence_wins():
    p = NgramProposer(max_ngram=2, min_ngram=2)
    assert p.propose([7, 8, 9, 7, 8, 5, 7, 8], 2) == [5, 7]


def test_ngram_proposer_no_match_and_validation():
    p = NgramProposer()
    assert p.propose([1, 2, 3, 4], 4) == []          # no repeated n-gram
    assert p.propose([1], 4) == []                   # too short to match
    assert p.propose([1, 1, 1], 0) == []             # k=0
    with pytest.raises(ValueError):
        NgramProposer(max_ngram=1, min_ngram=2)
    with pytest.raises(ValueError):
        ServingEngine(StubPagedRunner(), num_blocks=8,
                      num_speculative_tokens=-1)


# ------------------------------------------------- acceptance edge cases


class ZeroAcceptStub(StubPagedRunner):
    """The first two generated tokens continue the prompt's period-3
    pattern — so n-gram proposals FIRE once verification starts (the
    very first decode rides the prefill step, before speculation can
    engage) — but every later token is a fresh position-keyed value the
    context never contained, so no draft is ever accepted."""

    def __init__(self, prompt_len, **kw):
        super().__init__(**kw)
        self.prompt_len = prompt_len

    def _logits(self, history):
        L = len(history)
        if L < 3:                      # dead batch slots / tiny history
            nxt = (7 * (L + 1)) % self.vocab_size
        elif L < self.prompt_len + 2:
            nxt = int(history[-3]) % self.vocab_size
        else:
            nxt = (13 + 4 * L) % self.vocab_size
        row = np.zeros((self.vocab_size,), np.float32)
        row[nxt] = 1.0
        return row


def test_zero_acceptance_stays_token_exact():
    prompt = [1, 2, 3, 1, 2, 3]
    runner = ZeroAcceptStub(len(prompt), vocab_size=31, block_size=4,
                            max_model_len=64)
    eng = _engine(runner)
    sp = SamplingParams(max_tokens=8)
    rid = eng.add_request(prompt, sp)
    outs = eng.run()
    m = eng.metrics
    assert m.spec_proposed_tokens.value > 0, "drafts never fired"
    assert m.spec_accepted_tokens.value == 0
    assert outs[rid].output_tokens == naive_generate(runner, prompt, sp,
                                                     max_model_len=64)
    assert eng.pool.allocator.check_no_leaks()


def test_full_acceptance_and_step_collapse():
    runner = PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                max_model_len=64)
    eng = _engine(runner)
    prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    sp = SamplingParams(max_tokens=12)
    rid = eng.add_request(prompt, sp)
    outs = eng.run()
    m = eng.metrics
    assert m.spec_proposed_tokens.value > 0
    assert m.spec_accepted_tokens.value == m.spec_proposed_tokens.value
    assert m.ratio("spec_acceptance_rate") == 1.0
    # full acceptance: far fewer engine steps than generated tokens
    assert m.decode_steps.value < m.tokens_generated.value
    assert outs[rid].output_tokens == naive_generate(runner, prompt, sp,
                                                     max_model_len=64)
    assert eng.pool.allocator.check_no_leaks()


def test_steps_per_token_reduction_acceptance_pin():
    """ISSUE-5 acceptance: >= 1.5x fewer engine steps per generated
    token on the repetition-heavy workload, token streams identical."""

    def run(spec):
        runner = PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                    max_model_len=64)
        eng = ServingEngine(runner, num_blocks=40, max_batch_size=4,
                            max_model_len=64, num_speculative_tokens=spec,
                            enable_prefix_cache=True,
                            max_prefill_tokens_per_step=8)
        work = []
        for i in range(6):
            prompt = ([1 + i, 2, 3] * 4)[:8 + (i % 3)]
            work.append((eng.add_request(prompt, SamplingParams(
                max_tokens=16), request_id=f"r{i}"), prompt))
        outs = eng.run()
        toks = {rid: outs[rid].output_tokens for rid, _ in work}
        snap = eng.metrics.snapshot()
        eng.release_prefix_cache()
        assert eng.pool.allocator.check_no_leaks()
        return toks, snap, work, runner

    base_toks, base, work, runner = run(0)
    spec_toks, spec, _, _ = run(4)
    assert base_toks == spec_toks, "speculation changed the token stream"
    for rid, prompt in work:
        assert spec_toks[rid] == naive_generate(
            runner, prompt, SamplingParams(max_tokens=16), max_model_len=64)
    assert base["steps_per_token"] >= 1.5 * spec["steps_per_token"], (
        f"steps/token only improved {base['steps_per_token']:.3f} -> "
        f"{spec['steps_per_token']:.3f} (< 1.5x)")
    assert spec["spec_acceptance_rate"] > 0.5


def test_rejected_tail_pages_roll_back():
    """A rejected speculative span that crossed a page boundary must
    return its pages the same step (the auditor would also catch a
    leak, but the rollback counter proves the path actually ran)."""
    prompt = [1, 2, 3, 1, 2, 3]
    runner = ZeroAcceptStub(len(prompt), vocab_size=31, block_size=2,
                            max_model_len=64)
    eng = ServingEngine(runner, num_blocks=40, max_batch_size=1,
                        max_model_len=64, num_speculative_tokens=4)
    rid = eng.add_request(prompt, SamplingParams(max_tokens=8))
    outs = eng.run()
    assert eng.metrics.spec_rollback_pages.value > 0
    assert outs[rid].output_tokens == naive_generate(
        runner, prompt, SamplingParams(max_tokens=8), max_model_len=64)
    assert eng.pool.allocator.check_no_leaks()


# ------------------------------------------------ budget + pool pressure


def test_verify_spans_count_against_prefill_budget():
    def run(budget):
        runner = PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                    max_model_len=64)
        eng = ServingEngine(runner, num_blocks=24, max_batch_size=2,
                            max_model_len=64, num_speculative_tokens=4,
                            max_prefill_tokens_per_step=budget)
        prompt = [1, 2, 3, 1, 2, 3, 1, 2]
        rid = eng.add_request(prompt, SamplingParams(max_tokens=12))
        outs = eng.run()
        assert outs[rid].output_tokens == naive_generate(
            runner, prompt, SamplingParams(max_tokens=12), max_model_len=64)
        return eng.metrics.spec_proposed_tokens.value

    assert run(1) < run(None), \
        "a 1-token step budget must throttle speculative span tokens"


def test_scheduler_speculation_budget():
    pool = KVCachePool(1, 8, 4, 1, 1)
    s = FCFSScheduler(pool, 1, 4, max_prefill_tokens_per_step=8)
    assert s.speculation_budget(5) == 3
    assert s.speculation_budget(8) == 0
    assert s.speculation_budget(11) == 0
    s2 = FCFSScheduler(pool, 1, 4)
    assert s2.speculation_budget(100) is None


def test_reserve_speculation_degrades_instead_of_preempting():
    pool = KVCachePool(1, 4, 4, 1, 1)          # 3 usable pages
    sched = FCFSScheduler(pool, 1, 3)
    req = Request(prompt_tokens=[1] * 7, sampling=SamplingParams(max_tokens=8))
    sched.add(req)
    assert sched.admit() == [req]              # holds blocks(8) = 2 pages
    req.phase = "decode"
    req.output_tokens = [5]
    req.kv.num_tokens = 7                      # decode state: C-1 covered
    pool.allocator.alloc(1)                    # someone takes the last page
    prop = {req: [9, 9, 9, 9]}
    sched.reserve_speculation(prop)
    assert prop[req] == [], "speculation must shrink, not preempt"
    assert len(req.kv.pages) == 2              # nothing grown
    # with a free page back, the span fits again
    pool.allocator.free(sorted(pool.allocator.allocated_pages
                               - set(req.kv.pages)))
    prop = {req: [9, 9, 9, 9]}
    sched.reserve_speculation(prop)
    assert prop[req] == [9, 9, 9, 9]
    assert len(req.kv.pages) == 3              # blocks(8 + 4) = 3


# ------------------------------------------------- fault tolerance


def test_fault_injected_verify_retries_token_exact():
    runner = PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                max_model_len=64)
    inj = FaultInjector(runner, error_every=3, error_target="decode")
    eng = ServingEngine(inj, num_blocks=24, max_batch_size=2,
                        max_model_len=64, num_speculative_tokens=4,
                        retry_backoff_s=0.0)
    work = []
    for i, p in enumerate([[1, 2, 3, 1, 2, 3, 1, 2], [5, 6, 5, 6, 5, 6]]):
        work.append((eng.add_request(p, SamplingParams(max_tokens=10),
                                     request_id=f"r{i}"), p))
    outs = eng.run()
    assert eng.metrics.step_retries.value > 0
    assert inj.injected["error"] > 0
    for rid, p in work:
        assert outs[rid].output_tokens == naive_generate(
            runner, p, SamplingParams(max_tokens=10), max_model_len=64)
    assert eng.pool.allocator.check_no_leaks()


def test_nan_on_verify_abort_and_greedy_policies():
    for policy in ("abort", "greedy"):
        runner = PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                    max_model_len=64)
        inj = FaultInjector(runner, nan_every=2, nan_target="decode",
                            nan_fraction=0.5)
        eng = ServingEngine(inj, num_blocks=24, max_batch_size=2,
                            max_model_len=64, num_speculative_tokens=3,
                            nan_policy=policy)
        rid = eng.add_request([1, 2, 3, 1, 2, 3], SamplingParams(max_tokens=8))
        outs = eng.run()
        assert eng.metrics.nan_logit_events.value > 0
        assert outs[rid].finish_reason == ("error" if policy == "abort"
                                           else "length")
        assert eng.pool.allocator.check_no_leaks(), policy


def test_kill_and_restore_mid_speculation_token_exact():
    def mk():
        return PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                  max_model_len=64)

    prompts = [[1, 2, 3, 1, 2, 3, 1, 2], [5, 6, 5, 6, 5, 6],
               [9, 8, 7, 9, 8, 7]]
    sp = SamplingParams(max_tokens=12)
    eng = _engine(mk(), enable_prefix_cache=True)
    for i, p in enumerate(prompts):
        eng.add_request(p, sp, request_id=f"r{i}")
    for _ in range(3):                 # kill mid-flight, drafts in play
        eng.step()
    assert eng.metrics.spec_proposed_tokens.value > 0
    state = json.loads(json.dumps(eng.snapshot()))     # crash-safe wire
    assert state["config"]["num_speculative_tokens"] == 4
    assert state["config"]["spec_max_ngram"] == 3
    eng2 = ServingEngine.restore(mk(), state)
    assert eng2.num_speculative_tokens == 4
    outs = {**eng.outputs(), **eng2.run()}
    for i, p in enumerate(prompts):
        assert outs[f"r{i}"].output_tokens == naive_generate(
            mk(), p, sp, max_model_len=64), f"r{i} diverged after restore"
    eng2.release_prefix_cache()
    assert eng2.pool.allocator.check_no_leaks()


# -------------------------------------------- batched device-side sampling


def test_greedy_grid_matches_host_argmax_on_ties_and_negatives():
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((6, 17)).astype(np.float32)
    rows[0] = 0.0                      # all-tie row
    rows[1, 3] = rows[1, 9] = rows[1].max() + 1.0    # two-way tie
    rows[2] = -np.abs(rows[2]) - 1.0   # all-negative
    am, fin = greedy_grid(jnp.asarray(rows))
    assert fin.all()
    assert [int(x) for x in am] == [int(np.argmax(r)) for r in rows]
    rows[4, 5] = np.nan
    am, fin = greedy_grid(jnp.asarray(rows))
    assert not fin[4] and fin[0]


def test_seeded_temperature_streams_bit_identical():
    """The vectorized greedy pass must leave per-request seeded streams
    untouched: temperature > 0 requests (batched with greedy ones) still
    reproduce naive_generate bit-for-bit."""
    runner = PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                                max_model_len=64)
    eng = _engine(runner, max_batch=3)
    work = []
    for i, temp in enumerate((0.0, 0.9, 0.4)):
        p = [1 + i, 2, 3, 1 + i, 2, 3]
        sp = SamplingParams(max_tokens=10, temperature=temp, seed=100 + i)
        work.append((eng.add_request(p, sp, request_id=f"r{i}"), p, sp))
    outs = eng.run()
    for rid, p, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            runner, p, sp, max_model_len=64), rid


# ------------------------------------------------------------ detokenizer


class ByteTableTokenizer:
    """Byte-level stub: id -> raw bytes, including PARTIAL UTF-8 pieces."""

    def __init__(self, table):
        self.table = table

    def id_to_bytes(self, tok):
        return self.table[int(tok) % len(self.table)]


def test_complete_utf8_prefix_boundaries():
    euro = "€".encode()                       # b'\xe2\x82\xac'
    assert complete_utf8_prefix(b"abc") == 3
    assert complete_utf8_prefix(b"ab" + euro[:1]) == 2
    assert complete_utf8_prefix(b"ab" + euro[:2]) == 2
    assert complete_utf8_prefix(b"ab" + euro) == 5
    emoji = "🎉".encode()                     # 4-byte sequence
    for cut in range(1, 4):
        assert complete_utf8_prefix(emoji[:cut]) == 0
    assert complete_utf8_prefix(emoji) == 4
    assert complete_utf8_prefix(b"") == 0
    # malformed tails are treated as complete (decode() replaces them)
    assert complete_utf8_prefix(b"\x82\x82") == 2


def test_stream_detokenizer_buffers_split_multibyte_tokens():
    euro = "€".encode()
    tok = ByteTableTokenizer({0: b"hi ", 1: euro[:1], 2: euro[1:2],
                              3: euro[2:], 4: b"!"})
    d = StreamDetokenizer(tok)
    assert d.push(0) == "hi "
    assert d.push(1) == ""            # lead byte only: buffered
    assert d.push(2) == ""            # still incomplete
    assert d.push(3) == "€"           # continuation completes the char
    assert d.push(4) == "!"
    assert d.text == "hi €!"
    # dangling partial sequence at end-of-stream -> replacement char
    d2 = StreamDetokenizer(tok)
    d2.push(1)
    assert d2.finish() == "�"
    with pytest.raises(ValueError):
        d2.push(0)


def test_stream_detokenizer_decode_fallback_and_events():
    class StrTok:
        def decode(self, toks):
            return "".join(f"<{t}>" for t in toks)

    from paddle_tpu.serving import TokenEvent

    d = StreamDetokenizer(StrTok())
    assert d.push_event(TokenEvent("r", 7, 0)) == "<7>"
    assert d.push_event(TokenEvent("r", 8, 1, finished=True,
                                   finish_reason="stop")) == "<8>"
    assert d.finished and d.text == "<7><8>"


def test_engine_stream_text_incremental():
    euro = "€".encode()
    table = {i: bytes([65 + i]) for i in range(31)}   # ascii letters
    table[3] = euro[:2]                # partial euro: buffers...
    table[4] = euro[2:]                # ...completed by the next token
    runner = PeriodicStubRunner(period=2, vocab_size=31, block_size=4,
                                max_model_len=64)
    eng = _engine(runner, tokenizer=ByteTableTokenizer(table))
    rid = eng.add_request([3, 4, 3, 4], SamplingParams(max_tokens=8))
    seen = ""
    while eng.has_work():
        eng.step()
        cur = eng.stream_text(rid)
        assert cur.startswith(seen), "streamed text must only append"
        seen = cur
    final = eng.stream_text(rid)
    # the period-2 stream decodes greedily to 3,4,3,4,... — each (3, 4)
    # pair assembles one euro sign across a buffered split
    assert eng.outputs()[rid].output_tokens == [3, 4] * 4
    assert final == "€" * 4
    # and it equals a one-shot incremental decode of the token list
    ref = StreamDetokenizer(ByteTableTokenizer(table))
    for t in eng.outputs()[rid].output_tokens:
        ref.push(t)
    ref.finish()
    assert final == ref.text
    with pytest.raises(ValueError):
        _engine(runner).stream_text(rid)     # no tokenizer knob
    with pytest.raises(KeyError):
        eng.stream_text("nope")


# ----------------------------------------------------- kv-cache rollback


def test_sequence_kv_truncate_refuses_registered_pages():
    pool = KVCachePool(1, 8, 4, 1, 1)
    kv = SequenceKV(pool)
    kv.grow(12)                        # 3 pages
    kv.num_tokens = 12
    kv.registered_pages = 2            # pretend the cache indexed two
    with pytest.raises(ValueError):
        kv.truncate(3)                 # would drop a registered page
    assert kv.truncate(9) == 0         # keeps 3 pages (blocks(9) == 3)
    kv.registered_pages = 0
    assert kv.truncate(5) == 1         # 3 -> 2 pages, one freed
    assert pool.allocator.num_free == pool.allocator.num_usable - 2


# ------------------------------------------------------ real-model pin


@pytest.mark.slow
def test_real_llama_speculative_matches_naive():
    """End-to-end on the real runner: GQA Llama, chunked prefill, prefix
    cache, fused ragged verify — bit-exact vs the sequential oracle."""
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=2, num_kv_heads=1, max_seq_len=64,
                      dropout=0.0)
    model = Llama(cfg)
    model.eval()
    runner = LlamaRunner(model, block_size=8, max_model_len=64,
                         attn_impl="reference")
    eng = ServingEngine(runner, num_blocks=32, max_batch_size=3,
                        max_model_len=64, num_speculative_tokens=3,
                        enable_prefix_cache=True,
                        max_prefill_tokens_per_step=12, ragged_batch=True)
    rng = np.random.default_rng(7)
    work = []
    for i in range(4):
        pattern = list(map(int, rng.integers(1, 97, 3)))
        prompt = (pattern * 4)[:int(rng.integers(6, 12))]
        sp = SamplingParams(max_tokens=int(rng.integers(4, 9)))
        work.append((eng.add_request(prompt, sp, request_id=f"r{i}"),
                     prompt, sp))
    outs = eng.run()
    for rid, prompt, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            runner, prompt, sp, max_model_len=64), rid
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()


# ------------------------------------------------------------------ fuzz


@pytest.mark.slow
def test_fuzz_speculative_oracle_equivalence():
    """ISSUE-5 acceptance: 200 seeded trials of random pools, batches,
    chunk budgets, speculation depths, temperatures, prefix cache +
    ragged fusing — with the auditor armed on every step, every trial
    must drain token-for-token equal to the naive oracle with zero
    page/slot leaks, and the totals must prove the interesting paths
    (acceptance, rejection, rollback, preemption) actually ran."""
    tot_acc = tot_rej = tot_preempt = tot_rollback = 0
    for trial in range(200):
        wl = np.random.default_rng(7000 + trial)
        block_size = int(wl.integers(2, 5))
        num_blocks = int(wl.integers(6, 15))
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
        budget = (None if int(wl.integers(0, 4)) == 0
                  else int(wl.integers(1, 9)))
        eng = ServingEngine(runner, num_blocks=num_blocks,
                            max_batch_size=max_batch,
                            max_model_len=max_model_len,
                            max_prefill_tokens_per_step=budget,
                            num_speculative_tokens=int(wl.integers(1, 6)),
                            spec_max_ngram=int(wl.integers(1, 4)),
                            ragged_batch=bool(wl.integers(0, 2)),
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
            temp = 0.8 if int(wl.integers(0, 4)) == 0 else 0.0
            pending.append((p, SamplingParams(max_tokens=mt,
                                              temperature=temp,
                                              seed=int(wl.integers(0, 99)))))
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
        tot_acc += m.spec_accepted_tokens.value
        tot_rej += m.spec_proposed_tokens.value - m.spec_accepted_tokens.value
        tot_preempt += m.preemptions.value
        tot_rollback += m.spec_rollback_pages.value
        for rid, p, sp in work:
            assert outs[rid].finish_reason == "length"
            assert outs[rid].output_tokens == naive_generate(
                runner, p, sp, max_model_len=max_model_len), \
                f"trial {trial}: {rid} diverged from the oracle"
    assert tot_acc > 0, "fuzz never accepted a draft"
    assert tot_rej > 0, "fuzz never rejected a draft"
    assert tot_preempt > 0, "fuzz never exercised preemption churn"
    assert tot_rollback > 0, "fuzz never rolled back a speculative page"
