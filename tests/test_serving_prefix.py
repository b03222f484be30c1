"""ISSUE 3: chunked prefill + shared-prefix KV page cache (copy-on-write).

Pins the tentpole acceptance criteria on CPU:
  * chunked prefill is token-for-token identical to naive generation for
    any per-step budget, and a long-prompt arrival never stalls running
    decodes for more than one chunk budget per step;
  * the shared-prefix workload computes >= 2x fewer prefill tokens
    (metrics.prefill_tokens vs prefix_hit_tokens) with identical tokens;
  * a shared page is never mutated in place (copy-on-write fork);
  * refcount accounting is leak-free under the invariant auditor,
    including a 200-trial fuzz with shared prefixes and random budgets;
  * snapshot() deliberately drops the prefix-cache hash index (device KV
    does not survive a crash) and restore stays token-exact;
  * the runner's jit cache buckets chunk lengths and honors the
    PADDLE_TPU_MAX_JIT_CACHE cap.
"""

import json

import numpy as np
import pytest

import paddle_tpu as paddle
from _helpers import StubPagedRunner
from paddle_tpu.serving import (
    BlockAllocator, KVCachePool, SamplingParams, SequenceKV, ServingEngine,
    naive_generate,
)


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    """Refcounts armed: the invariant auditor runs after every step."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


def _stub_engine(num_blocks=16, block_size=4, max_batch=4, max_model_len=32,
                 **kw):
    runner = StubPagedRunner(vocab_size=31, block_size=block_size,
                             max_model_len=max_model_len)
    return ServingEngine(runner, num_blocks=num_blocks,
                         max_batch_size=max_batch,
                         max_model_len=max_model_len, **kw)


# ------------------------------------------------------- chunked prefill


@pytest.mark.parametrize("budget", [1, 3, 7, None])
def test_chunked_prefill_token_equivalence(budget):
    """Any per-step prefill budget must reproduce naive generation
    token-for-token — chunk boundaries change schedules, never tokens."""
    runner = StubPagedRunner(vocab_size=31, block_size=4, max_model_len=64)
    eng = ServingEngine(runner, num_blocks=40, max_batch_size=3,
                        max_model_len=64,
                        max_prefill_tokens_per_step=budget)
    wl = np.random.default_rng(11)
    work = []
    for i in range(6):
        p = list(map(int, wl.integers(0, 31, int(wl.integers(1, 20)))))
        sp = SamplingParams(max_tokens=int(wl.integers(1, 6)))
        work.append((eng.add_request(p, sp), p, sp))
    outs = eng.run()
    for rid, p, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            runner, p, sp, max_model_len=64), f"budget={budget}: {rid}"
    assert eng.pool.allocator.check_no_leaks()
    if budget == 1:
        # 1-token chunks: every context token is its own prefill call
        assert eng.metrics.prefill_chunks.value == \
            eng.metrics.prefill_tokens.value


def test_long_prompt_arrival_does_not_stall_decode():
    """ISSUE-3 acceptance pin: with a chunk budget, a long-prompt arrival
    costs running decodes at most one budget of prefill per step — the
    running request keeps producing exactly one token every step."""
    eng = _stub_engine(num_blocks=40, block_size=4, max_batch=2,
                       max_model_len=64, max_prefill_tokens_per_step=4)
    r1 = eng.add_request([1, 2, 3], SamplingParams(max_tokens=30))
    eng.step()              # r1: prefill token + same-step decode token
    req1 = eng._requests[r1]
    assert len(req1.output_tokens) == 2

    long_prompt = list(range(1, 25))        # 24 tokens -> 6 chunks of 4
    r2 = eng.add_request(long_prompt, SamplingParams(max_tokens=2))
    req2 = eng._requests[r2]
    steps_to_first_token = 0
    while not req2.output_tokens:
        before = len(req1.output_tokens)
        eng.step()
        steps_to_first_token += 1
        assert len(req1.output_tokens) == before + 1, \
            "running decode stalled during a chunked prefill"
    assert steps_to_first_token == 6        # ceil(24 / 4) chunk steps
    assert eng.metrics.prefill_chunks.value >= 7
    outs = eng.run()
    for rid, p in ((r1, [1, 2, 3]), (r2, long_prompt)):
        sp = SamplingParams(max_tokens=len(outs[rid].output_tokens))
        assert outs[rid].output_tokens == naive_generate(
            eng.runner, p, sp, max_model_len=64)


def test_chunk_budget_validation():
    with pytest.raises(ValueError):
        _stub_engine(max_prefill_tokens_per_step=0)


# --------------------------------------------------------- prefix cache


def test_shared_prefix_cache_saves_prefill_compute():
    """ISSUE-3 acceptance: N requests sharing a long header compute >=2x
    fewer prefill tokens than the total context, token streams unchanged,
    and zero pages leak once the cache is released."""
    header = list(range(1, 25))             # 24 tokens = 6 full pages
    eng = _stub_engine(num_blocks=60, block_size=4, max_batch=2,
                       max_model_len=64, enable_prefix_cache=True)
    wl = np.random.default_rng(3)
    work = []
    for i in range(8):
        p = header + list(map(int, wl.integers(0, 31, 3)))
        sp = SamplingParams(max_tokens=4)
        work.append((eng.add_request(p, sp), p, sp))
    outs = eng.run()
    total_ctx = sum(len(p) for _, p, _ in work)
    computed = eng.metrics.prefill_tokens.value
    hits = eng.metrics.prefix_hit_tokens.value
    assert computed + hits == total_ctx     # nothing skipped, nothing doubled
    assert computed * 2 <= total_ctx, \
        f"only {total_ctx - computed}/{total_ctx} tokens saved"
    for rid, p, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            eng.runner, p, sp, max_model_len=64)
    assert eng.release_prefix_cache() > 0
    assert eng.pool.allocator.check_no_leaks()


def test_prefix_match_always_leaves_one_token_to_compute():
    """A fully-cached context must still compute >= 1 token — admission
    needs logits to sample from (the strictly-below-len cap)."""
    eng = _stub_engine(num_blocks=30, block_size=4, max_model_len=32,
                       max_batch=1, enable_prefix_cache=True)
    p = list(range(1, 9))                   # 8 tokens: exactly 2 pages
    r1 = eng.add_request(p, SamplingParams(max_tokens=2))
    outs1 = eng.run()
    r2 = eng.add_request(p, SamplingParams(max_tokens=2))  # identical
    outs2 = eng.run()
    assert outs2[r2].output_tokens == outs1[r1].output_tokens
    # second request hit one full page (4 tokens), computed the rest
    assert eng.metrics.prefix_hit_tokens.value == 4
    assert eng.metrics.prefill_tokens.value == 8 + 4


def test_preemption_resume_is_mostly_cache_hits():
    """Recompute-on-resume re-matches the victim's own registered pages:
    the resume prefill is mostly cache hits (ISSUE-3 motivation)."""
    eng = _stub_engine(num_blocks=10, block_size=4, max_batch=3,
                       max_model_len=36, enable_prefix_cache=True)
    wl = np.random.default_rng(9)
    work = []
    for i in range(6):
        p = list(map(int, wl.integers(0, 31, int(wl.integers(6, 14)))))
        sp = SamplingParams(max_tokens=int(wl.integers(4, 9)))
        work.append((eng.add_request(p, sp), p, sp))
    outs = eng.run()
    assert eng.metrics.preemptions.value >= 1, \
        "workload must exercise preemption"
    assert eng.metrics.prefix_hit_tokens.value > 0, \
        "resume never hit the prefix cache"
    for rid, p, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            eng.runner, p, sp, max_model_len=32)
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()


# ------------------------------------------------- refcounts + eviction


def test_refcounted_allocator_unit():
    a = BlockAllocator(8)
    pages = a.alloc(3)
    assert pages == [1, 2, 3]
    assert a.refcount(1) == 1
    assert a.incref(1) == 2
    assert a.decref(1) == 1
    assert 1 in a.allocated_pages           # still held
    assert a.decref(1) == 0
    assert 1 not in a.allocated_pages       # back on the free list
    assert a.alloc(1) == [1]                # lowest-id-first, deterministic
    with pytest.raises(ValueError):
        a.decref(7)                         # never allocated
    with pytest.raises(ValueError):
        a.incref(7)
    a.free([1, 2, 3])
    with pytest.raises(ValueError):
        a.free([2])                         # double free still loud
    assert a.check_no_leaks()


def test_prefix_cache_eviction_lru_and_headroom():
    pool = KVCachePool(num_layers=1, num_blocks=6, block_size=2,
                       n_kv_heads=1, head_dim=1)
    cache = pool.enable_prefix_cache()
    seq = SequenceKV(pool)
    tokens = [1, 2, 3, 4, 5]
    seq.grow(len(tokens))                   # 3 pages
    seq.num_tokens = 4                      # two FULL pages
    cache.register_seq(seq, tokens)
    assert len(cache) == 2
    seq.release()                           # cache alone holds pages 1, 2
    assert cache.evictable_count() == 2
    assert pool.allocator.num_free == 3
    assert pool.allocator.can_alloc(5)      # 3 free + 2 evictable
    got = pool.allocator.alloc(4)           # must evict the LRU page only
    assert len(got) == 4
    assert cache.evictions == 1 and len(cache) == 1
    pool.allocator.free(got)
    cache.clear()
    assert pool.allocator.check_no_leaks()


def test_cow_shared_page_never_mutated_in_place():
    """ISSUE-3 satellite: a write that would land on a shared page forks
    it first — the original page's KV bytes are bit-identical before and
    after, and only the writer's block table changes."""
    pool = KVCachePool(num_layers=2, num_blocks=8, block_size=4,
                       n_kv_heads=1, head_dim=2)
    cache = pool.enable_prefix_cache()
    tokens = [5, 6, 7, 8, 9]
    a = SequenceKV(pool)
    a.grow(len(tokens) + 1)                 # pages [1, 2]
    # simulate the runner having written page 0's KV
    k0, v0 = pool.pools[0]
    k0 = k0.at[a.pages[0]].set(np.arange(8, dtype=np.float32).reshape(4, 1, 2))
    pool.pools[0] = (k0, v0)
    a.num_tokens = len(tokens)
    cache.register_seq(a, tokens)           # page 1 is now cached (full)

    b = SequenceKV(pool)
    matched = cache.match(tokens)
    assert [p for _, p in matched] == [a.pages[0]]
    cache.acquire(matched)
    b.adopt_prefix(matched, pool.block_size)
    b.grow(len(tokens) + 1 - b.num_tokens)
    shared = b.pages[0]
    assert shared == a.pages[0]
    assert pool.allocator.refcount(shared) == 3      # a + b + cache

    before = np.asarray(pool.pools[0][0][shared]).copy()
    forked = b.ensure_writable(0, 4)                 # b wants to write it
    assert forked == 1
    assert b.pages[0] != shared                      # b got a private fork
    assert a.pages[0] == shared                      # a untouched
    assert pool.allocator.refcount(shared) == 2
    assert pool.allocator.refcount(b.pages[0]) == 1
    np.testing.assert_array_equal(                   # fork carried the KV
        np.asarray(pool.pools[0][0][b.pages[0]]), before)
    np.testing.assert_array_equal(                   # original unmutated
        np.asarray(pool.pools[0][0][shared]), before)
    # a second write hits the now-private fork: no further forking
    assert b.ensure_writable(0, 4) == 0
    b.release()
    a.release()
    cache.clear()
    assert pool.allocator.check_no_leaks()


# ---------------------------------------------------- snapshot / restore


def test_snapshot_drops_prefix_cache_and_restores_token_exact():
    """The snapshot deliberately DROPS the prefix-cache hash index (the
    cached device KV does not survive a crash); restore recomputes and
    REBUILDS the cache, staying token-exact — the ISSUE-3 pin."""
    header = list(range(1, 13))             # 12 tokens = 3 full pages
    eng = _stub_engine(num_blocks=40, block_size=4, max_batch=2,
                       max_model_len=32, enable_prefix_cache=True,
                       max_prefill_tokens_per_step=5)
    wl = np.random.default_rng(4)
    work = []
    for i in range(6):
        p = header + list(map(int, wl.integers(0, 31, 2)))
        sp = SamplingParams(max_tokens=5)
        work.append((eng.add_request(p, sp), p, sp))
    for _ in range(4):                      # mid-workload kill (some
        eng.step()                          # requests mid-chunked-prefill)
    assert len(eng.pool.prefix_cache) > 0
    state = json.loads(json.dumps(eng.snapshot()))
    assert "prefix" not in json.dumps(state["config"]).lower() or \
        state["config"]["enable_prefix_cache"] is True

    fresh = StubPagedRunner(vocab_size=31, block_size=4, max_model_len=32)
    eng2 = ServingEngine.restore(fresh, state)
    assert eng2.enable_prefix_cache is True
    assert eng2.max_prefill_tokens_per_step == 5
    assert len(eng2.pool.prefix_cache) == 0          # index dropped
    outs = eng2.run()
    assert len(outs) == 6
    for rid, p, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            fresh, p, sp, max_model_len=32), f"{rid} diverged after restore"
    # the rebuilt cache was hit again by the still-shared headers
    assert eng2.metrics.prefix_hit_tokens.value > 0
    eng2.release_prefix_cache()
    assert eng2.pool.allocator.check_no_leaks()


# -------------------------------------------------------- jit-cache cap


@pytest.mark.slow
def test_jit_cache_buckets_chunks_and_honors_cap(monkeypatch):
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.serving import GPTRunner

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=37, hidden_size=16, num_layers=1,
                    num_heads=1, max_seq_len=64, dropout=0.0)
    model = GPT(cfg)
    model.eval()
    runner = GPTRunner(model, block_size=4, max_model_len=64)
    pool = KVCachePool(num_layers=1, num_blocks=17, block_size=4,
                       n_kv_heads=1, head_dim=16)
    table = pool.pad_table(pool.allocator.alloc(16), 16)

    # odd chunk lengths 5, 2, 7 share one power-of-2 bucket (8): chunked
    # prefill cannot recompile per odd-length chunk
    runner.prefill_chunk([1, 2, 3, 4, 5], 0, table, pool.pools)
    runner.prefill_chunk([6, 7], 5, table, pool.pools)
    runner.prefill_chunk([1] * 7, 0, table, pool.pools)
    assert list(runner._jit_cache) == [("prefill", 8)]

    monkeypatch.setenv("PADDLE_TPU_MAX_JIT_CACHE", "2")
    runner.prefill_chunk([1] * 9, 0, table, pool.pools)    # bucket 16
    assert len(runner._jit_cache) == 2
    runner.prefill_chunk([1] * 17, 0, table, pool.pools)   # bucket 32
    assert len(runner._jit_cache) == 2                     # capped
    assert ("prefill", 8) not in runner._jit_cache         # LRU evicted
    assert ("prefill", 32) in runner._jit_cache


# --------------------------------------------------- real-model numerics


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


def test_llama_chunked_prefix_matches_naive(llama_runner):
    """The real-numerics pin: chunked prefill attending over prefix-cache
    pages reproduces monolithic-prefill tokens bit-exactly on the actual
    Llama runner (rope + GQA + RMSNorm, gather attention path) — chunk
    and sharing boundaries change schedules, never logits."""
    runner = llama_runner
    eng = ServingEngine(runner, num_blocks=40, max_batch_size=3,
                        max_model_len=64, max_prefill_tokens_per_step=5,
                        enable_prefix_cache=True)
    header = [7, 3, 9, 1, 4, 4, 2, 8, 6, 5, 1]     # > one full page
    wl = np.random.default_rng(13)
    work = []
    for i in range(6):
        p = header + list(map(int, wl.integers(1, 97, int(
            wl.integers(1, 8)))))
        sp = SamplingParams(max_tokens=int(wl.integers(2, 7)))
        work.append((eng.add_request(p, sp), p, sp))
    outs = eng.run()
    for rid, p, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            runner, p, sp, max_model_len=64), f"{rid} diverged"
    assert eng.metrics.prefix_hit_tokens.value > 0
    assert eng.metrics.prefill_chunks.value > 6     # chunking engaged
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()


# ------------------------------------------------------------------ fuzz


@pytest.mark.slow
def test_fuzz_chunked_prefix_no_leaks_and_oracle_equivalence():
    """ISSUE-3 satellite: 200 seeded trials of random pools, arrivals,
    shared-prefix prompts, and chunk budgets — with the prefix cache and
    the refcount auditor armed on every step, every trial must drain
    token-for-token equal to the naive oracle with zero page/slot leaks
    once the cache is released."""
    total_preemptions = total_hits = total_chunked = 0
    for trial in range(200):
        wl = np.random.default_rng(5000 + trial)
        block_size = int(wl.integers(2, 5))
        num_blocks = int(wl.integers(5, 15))
        usable = num_blocks - 1
        max_batch = int(wl.integers(1, 5))
        max_model_len = usable * block_size
        runner = StubPagedRunner(vocab_size=31, block_size=block_size,
                                 max_model_len=max_model_len)
        budget = (None if int(wl.integers(0, 4)) == 0
                  else int(wl.integers(1, 9)))
        eng = ServingEngine(runner, num_blocks=num_blocks,
                            max_batch_size=max_batch,
                            max_model_len=max_model_len,
                            max_prefill_tokens_per_step=budget,
                            enable_prefix_cache=True)
        assert eng.audit, "fuzz must run under the invariant auditor"
        header = list(map(int, wl.integers(0, 31, int(wl.integers(0, 10)))))
        n_req = int(wl.integers(2, 9))
        pending = []
        for i in range(n_req):
            plen = int(wl.integers(1, min(14, max_model_len - 1) + 1))
            p = list(map(int, wl.integers(0, 31, plen)))
            if header and int(wl.integers(0, 2)) == 0:
                h = header[:max(0, plen - 1)]    # shared prefix, len kept
                p[:len(h)] = h
            mt = int(wl.integers(1, min(6, max_model_len - plen) + 1))
            pending.append((p, SamplingParams(max_tokens=mt)))
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
        total_preemptions += eng.metrics.preemptions.value
        total_hits += eng.metrics.prefix_hit_tokens.value
        total_chunked += (budget is not None
                          and eng.metrics.prefill_chunks.value
                          > eng.metrics.requests_added.value)
        for rid, p, sp in work:
            assert outs[rid].finish_reason == "length"
            assert outs[rid].output_tokens == naive_generate(
                runner, p, sp, max_model_len=max_model_len), \
                f"trial {trial}: {rid} diverged from the oracle"
    assert total_preemptions > 0, "fuzz never exercised preemption churn"
    assert total_hits > 0, "fuzz never exercised prefix-cache hits"
    assert total_chunked > 0, "fuzz never split a prefill into chunks"
