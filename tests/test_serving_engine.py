"""Serving subsystem tests: allocator/scheduler determinism, preemption
with zero page leaks, and the end-to-end continuous-batching oracle —
engine output must equal naive sequential generation token-for-token
(ISSUE-1 acceptance criterion).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from _helpers import CountingStubRunner, StubPagedRunner
from paddle_tpu.serving import (
    BlockAllocator, EngineMetrics, FCFSScheduler, Histogram, KVCachePool,
    Request, RequestState, SamplingParams, ServingEngine, naive_generate,
)

rng = np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    """ISSUE-2 contract: the invariant auditor (resilience.audit_engine)
    runs after every engine step under every serving test."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


# ------------------------------------------------------------- allocator


def test_allocator_alloc_free_deterministic():
    a = BlockAllocator(8)
    assert a.num_usable == 7          # page 0 is scratch
    first = a.alloc(3)
    assert first == [1, 2, 3]         # lowest-id-first
    a.free([2])
    assert a.alloc(1) == [2]          # freed page reused deterministically
    a.free([1, 2, 3])
    assert a.check_no_leaks()


def test_allocator_exhaustion_and_double_free():
    a = BlockAllocator(4)
    pages = a.alloc(3)
    with pytest.raises(MemoryError):
        a.alloc(1)
    a.free(pages)
    with pytest.raises(ValueError):
        a.free([pages[0]])


def test_pool_sizing_and_scratch_padding():
    pool = KVCachePool(num_layers=2, num_blocks=8, block_size=4,
                       n_kv_heads=2, head_dim=8)
    assert pool.blocks_for_tokens(1) == 1
    assert pool.blocks_for_tokens(4) == 1
    assert pool.blocks_for_tokens(5) == 2
    row = pool.pad_table([3, 5], 4)
    assert row == [3, 5, 0, 0]        # scratch-page padding
    with pytest.raises(ValueError):
        pool.pad_table([1, 2, 3], 2)


@pytest.mark.parametrize("change", ["append", "truncate_regrow", "fork",
                                    "release_regrow", "outside_append"])
def test_pages_array_follows_the_page_list(change):
    """SequenceKV.pages_array is `pages` as int32 whatever happened to the
    list since the last call: only appends are converted incrementally."""
    from paddle_tpu.serving.kv_cache import SequenceKV

    pool = KVCachePool(num_layers=1, num_blocks=100, block_size=4,
                       n_kv_heads=1, head_dim=8)
    kv = SequenceKV(pool)
    kv.grow(12)
    assert kv.pages_array().dtype == np.int32
    assert kv.pages_array().tolist() == kv.pages and len(kv.pages) == 3
    if change == "append":
        kv.num_tokens = 12
        kv.grow(300 - 12)             # past the mirror's first capacity
    elif change == "truncate_regrow":
        kv.num_tokens = 12
        kv.truncate(5)                # drops the third page ...
        other = pool.allocator.alloc(1)
        kv.num_tokens = 8
        kv.grow(4)                    # ... and a different one takes its place
        assert len(kv.pages) == 3 and other
    elif change == "fork":
        pool.allocator.incref(kv.pages[1])      # shared: a write forks it
        assert kv.ensure_writable(4, 8) == 1
    elif change == "release_regrow":
        kv.release()
        pool.allocator.alloc(2)
        kv.grow(12)
    else:                             # the scheduler appends to the list
        kv.pages.append(pool.allocator.alloc(1)[0])
    assert kv.pages_array().tolist() == kv.pages
    assert kv.pages_array().tolist() == kv.pages      # and again, unchanged


# ------------------------------------------------------------- scheduler


def _sched(num_blocks=9, block_size=4, max_batch=2, max_pages=4):
    pool = KVCachePool(num_layers=1, num_blocks=num_blocks,
                       block_size=block_size, n_kv_heads=1, head_dim=8)
    return FCFSScheduler(pool, max_batch, max_pages), pool


def test_admission_is_fcfs_with_head_of_line_blocking():
    sched, pool = _sched(num_blocks=5, max_batch=4)  # 4 usable pages
    big = Request(prompt_tokens=list(range(12)))     # needs 4 pages (12+1)
    small = Request(prompt_tokens=[1, 2])            # needs 1 page
    sched.add(big)
    sched.add(small)
    assert [r is big for r in sched.admit()] == [True]
    # big took all 4 pages: small must NOT be admitted out of order
    assert sched.admit() == []
    assert sched.queue_depth == 1
    sched.finish(big, "length")
    assert sched.admit() == [small]


def test_preemption_evicts_youngest_and_requeues_front():
    # 8 usable pages, two admitted 6-token seqs (2 pages each incl. the
    # +1 decode page); grow both to page boundaries until the pool dries
    sched, pool = _sched(num_blocks=9, block_size=4, max_batch=2,
                         max_pages=8)
    a = Request(prompt_tokens=list(range(6)))
    b = Request(prompt_tokens=list(range(6)))
    sched.add(a)
    sched.add(b)
    assert sched.admit() == [a, b]
    for r in (a, b):
        r.kv.num_tokens = r.num_context
    assert pool.allocator.num_free == 4
    # grow both sequences until reservation must preempt: simulate decode
    # appends (each +4 tokens crosses a page boundary)
    victims = []
    for _ in range(12):
        for r in sched.running_in_order():
            r.kv.num_tokens += 1
            r.output_tokens.append(0)
        victims = sched.reserve_decode()
        if victims:
            break
    assert victims == [b]                      # youngest evicted
    assert b.state is RequestState.WAITING
    assert b.num_preemptions == 1
    assert sched.waiting[0] is b               # queue-front recycle
    assert b.kv is None
    # a keeps running; finishing it releases every page
    sched.finish(a, "length")
    admitted = sched.admit()                   # b resumes
    assert admitted == [b]
    sched.finish(b, "length")
    assert pool.allocator.check_no_leaks()


def test_scheduler_rejects_unservable_config():
    pool = KVCachePool(num_layers=1, num_blocks=4, block_size=4,
                       n_kv_heads=1, head_dim=8)
    with pytest.raises(ValueError):
        FCFSScheduler(pool, max_batch_size=1, max_pages_per_seq=8)


# --------------------------------------------------------------- metrics


def test_histogram_percentiles_exact():
    h = Histogram("t")
    for v in [5.0, 1.0, 9.0, 3.0, 7.0]:
        h.observe(v)
    assert h.percentile(0) == 1.0
    assert h.percentile(50) == 5.0
    assert h.percentile(100) == 9.0
    assert h.count == 5 and h.mean == 5.0


# ---------------------------------------------------------- end-to-end


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


def test_engine_matches_naive_with_preemption(llama_runner):
    """The ISSUE-1 acceptance workload: 16 requests, mixed prompt/output
    lengths, pool tight enough to force preemption; the engine's
    continuous-batching output must equal naive sequential generation
    token-for-token and every page must come back to the free list."""
    runner = llama_runner
    # 9 usable pages vs 4 slots x up to 8 pages/seq -> guaranteed pressure
    eng = ServingEngine(runner, num_blocks=10, max_batch_size=4,
                        max_model_len=64)
    wl = np.random.default_rng(7)
    prompts, params, ids = [], [], []
    for i in range(16):
        p = list(wl.integers(1, 97, int(wl.integers(3, 25))))
        sp = SamplingParams(max_tokens=int(wl.integers(2, 11)))
        prompts.append(p)
        params.append(sp)
        ids.append(eng.add_request(p, sp))
    outs = eng.run()
    assert len(outs) == 16
    assert eng.metrics.preemptions.value >= 1, \
        "workload must exercise preemption"
    for rid, p, sp in zip(ids, prompts, params):
        ref = naive_generate(runner, p, sp, max_model_len=64)
        assert outs[rid].output_tokens == ref, \
            f"{rid}: engine {outs[rid].output_tokens} != naive {ref}"
        assert outs[rid].finish_reason == "length"
    assert eng.pool.allocator.check_no_leaks(), "leaked KV pages"
    snap = eng.metrics.snapshot()
    assert snap["requests_finished"] == 16
    assert snap["tokens_generated"] == sum(sp.max_tokens for sp in params)


def test_engine_stop_tokens_and_streaming(llama_runner):
    runner = llama_runner
    eng = ServingEngine(runner, num_blocks=20, max_batch_size=2,
                        max_model_len=64)
    ref = naive_generate(runner, [5, 6, 7], SamplingParams(max_tokens=8),
                         max_model_len=64)
    stop = ref[2]                     # stop exactly at the third token
    sp = SamplingParams(max_tokens=8, stop_token_ids=(stop,))
    rid = eng.add_request([5, 6, 7], sp)
    events = []
    while eng.has_work():
        events.extend(eng.step())
    out = eng.outputs()[rid]
    assert out.finish_reason == "stop"
    assert out.output_tokens == ref[:3]
    # streaming surface delivered every token exactly once, in order
    assert [e.token for e in events] == out.output_tokens
    assert [e.index for e in events] == [0, 1, 2]
    assert events[-1].finished
    assert eng.pool.allocator.check_no_leaks()


def test_engine_seeded_sampling_matches_naive(llama_runner):
    runner = llama_runner
    eng = ServingEngine(runner, num_blocks=20, max_batch_size=3,
                        max_model_len=64)
    sp = SamplingParams(max_tokens=5, temperature=0.8, top_k=20, seed=11)
    rid = eng.add_request([9, 8, 7, 6], sp)
    outs = eng.run()
    assert outs[rid].output_tokens == naive_generate(
        runner, [9, 8, 7, 6], sp, max_model_len=64)


def test_gpt_runner_and_inference_bridge():
    from paddle_tpu.inference import create_serving_engine
    from paddle_tpu.models.gpt import GPT, GPTConfig

    paddle.seed(1)
    cfg = GPTConfig(vocab_size=89, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, dropout=0.0)
    model = GPT(cfg)
    model.eval()
    eng = create_serving_engine(model, block_size=8, max_model_len=32,
                                attn_impl="reference", num_blocks=16,
                                max_batch_size=2)
    ids = [eng.add_request([3, 1, 4, 1, 5], SamplingParams(max_tokens=4)),
           eng.add_request([2, 7, 1, 8], SamplingParams(max_tokens=6))]
    outs = eng.run()
    for rid, prompt in zip(ids, ([3, 1, 4, 1, 5], [2, 7, 1, 8])):
        ref = naive_generate(eng.runner, prompt,
                             SamplingParams(max_tokens=len(
                                 outs[rid].output_tokens)),
                             max_model_len=32)
        assert outs[rid].output_tokens == ref
    assert eng.pool.allocator.check_no_leaks()


def test_engine_pallas_decode_path_matches_reference():
    """The engine drives the Pallas paged-decode kernel (interpret mode
    on CPU) and reproduces the gather-path tokens exactly — the same
    dual dispatch contract ops/pallas kernels promise."""
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=2, max_seq_len=32, dropout=0.0)  # d=16, MHA
    model = Llama(cfg)
    model.eval()
    r_pallas = LlamaRunner(model, block_size=8, max_model_len=32,
                           attn_impl="ragged")
    r_ref = LlamaRunner(model, block_size=8, max_model_len=32,
                        attn_impl="reference")
    eng = ServingEngine(r_pallas, num_blocks=12, max_batch_size=2,
                        max_model_len=32)
    prompts = ([5, 3, 8, 2], [9, 1, 1])
    ids = [eng.add_request(p, SamplingParams(max_tokens=4))
           for p in prompts]
    outs = eng.run()
    for rid, p in zip(ids, prompts):
        ref = naive_generate(r_ref, p, SamplingParams(max_tokens=4),
                             max_model_len=32)
        assert outs[rid].output_tokens == ref


@pytest.mark.slow
def test_scheduler_fuzz_no_leaks_and_oracle_equivalence():
    """ISSUE-2 satellite: ~200 seeded trials of random arrivals, prompt
    lengths, pool sizes, and batch limits — every trial must drain with
    zero page leaks, zero slot leaks, and token-for-token equality vs the
    naive oracle, under whatever preemption churn the tight pools force.
    The StubPagedRunner routes all history through the real KV pool and
    block tables, so allocator/scheduler bugs change tokens."""
    total_preemptions = 0
    for trial in range(200):
        wl = np.random.default_rng(1000 + trial)
        block_size = int(wl.integers(2, 5))
        num_blocks = int(wl.integers(4, 14))
        usable = num_blocks - 1
        max_batch = int(wl.integers(1, 5))
        max_model_len = usable * block_size
        runner = StubPagedRunner(vocab_size=31, block_size=block_size,
                                 max_model_len=max_model_len)
        eng = ServingEngine(runner, num_blocks=num_blocks,
                            max_batch_size=max_batch,
                            max_model_len=max_model_len)
        assert eng.audit, "fuzz must run under the invariant auditor"
        n_req = int(wl.integers(2, 9))
        pending = []
        for i in range(n_req):
            plen = int(wl.integers(1, min(12, max_model_len - 1) + 1))
            mt = int(wl.integers(1, min(6, max_model_len - plen) + 1))
            pending.append((list(map(int, wl.integers(0, 31, plen))),
                            SamplingParams(max_tokens=mt)))
        work = []
        while pending or eng.has_work():
            # random arrival staggering: 0-2 new requests per step
            for _ in range(int(wl.integers(0, 3))):
                if pending:
                    p, sp = pending.pop(0)
                    work.append((eng.add_request(p, sp), p, sp))
            eng.step()
        outs = eng.outputs()
        assert len(outs) == n_req, f"trial {trial}: lost requests"
        assert eng.pool.allocator.check_no_leaks(), \
            f"trial {trial}: leaked pages"
        assert sorted(eng.scheduler._free_slots) == list(range(max_batch)), \
            f"trial {trial}: leaked slots"
        total_preemptions += eng.metrics.preemptions.value
        for rid, p, sp in work:
            assert outs[rid].finish_reason == "length"
            assert outs[rid].output_tokens == naive_generate(
                runner, p, sp, max_model_len=max_model_len), \
                f"trial {trial}: {rid} diverged from the oracle"
    assert total_preemptions > 0, "fuzz never exercised preemption churn"


# ------------------------------------------ the step's book-keeping, put off

# the gauges a step's end mirrors, and where the engine's state holds each
STEP_GAUGES = {
    "attn_kv_bytes_read": lambda e: e.runner.attn_kv_bytes_read,
    "attn_kv_bytes_gather": lambda e: e.runner.attn_kv_bytes_gather,
    "queue_depth": lambda e: len(e.scheduler.waiting),
    "running": lambda e: len(e.scheduler.running),
    "pool_used_pages": lambda e: (e.pool.allocator.num_usable
                                  - e.pool.allocator.num_free),
    "pool_utilization": lambda e: e.pool.utilization(),
}


def bookkeeping_scenario(pipelined: bool):
    """40 steps of a tight pool: staggered admissions, finishes, an abort, a
    preemption and a decode call that fails once. Yields, after every
    observable point, (what, engine, events so far): the caller reads."""
    runner = CountingStubRunner(fail_decode_calls={7}, vocab_size=31,
                                block_size=4, max_model_len=32)
    eng = ServingEngine(runner, num_blocks=9, max_batch_size=3,
                        max_model_len=32, pipelined=pipelined,
                        sleep_fn=lambda s: None)
    wl = np.random.default_rng(41)
    work, events = [], []

    def add():
        p = list(map(int, wl.integers(0, 31, int(wl.integers(2, 9)))))
        sp = SamplingParams(max_tokens=int(wl.integers(3, 12)))
        work.append((eng.add_request(p, sp), p, sp))

    add(), add()
    yield "added", eng, events, work
    for step in range(40):
        if step in (1, 2, 4, 7, 11, 16, 22, 29):
            add()
            yield "added", eng, events, work
        if step == 9:
            victim = next(r for r in eng.scheduler.running)
            assert eng.abort(victim.request_id)
            yield "aborted", eng, events, work
        events.extend(eng.step())
        yield "stepped", eng, events, work
    events.extend(eng.flush())
    yield "flushed", eng, events, work
    while eng.has_work():
        events.extend(eng.step())
        yield "stepped", eng, events, work
    yield "done", eng, events, work


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["default", "pipelined"])
def test_bookkeeping_put_off_is_exact_at_every_observation(pipelined):
    """What a step puts off until the device is busy again (its gauges) and
    what it reads without a round trip (a counting runner's counts) is, at
    every point a caller can look, what a step that did it all at once
    shows: after every step(), add_request(), abort(), after flush() and at
    the end, `snapshot()`, each gauge's `value` and `peak`, the runner's
    byte counters and the counts against the engine's own state, the
    events so far and the sum of the launches' counts."""
    peaks = dict.fromkeys(STEP_GAUGES, 0.0)
    seen = set()
    for n, (what, eng, events, work) in enumerate(
            bookkeeping_scenario(pipelined)):
        seen.add(what)
        m = eng.metrics
        # either door settles what is owed: the snapshot first at every
        # other point, a gauge first at the rest
        snap = m.snapshot() if n % 2 else None
        want = {name: float(read(eng)) for name, read in STEP_GAUGES.items()}
        if what in ("added", "aborted"):
            # between steps only the queue's gauge is written
            want = {"queue_depth": want["queue_depth"]}
        for name, v in want.items():
            peaks[name] = max(peaks[name], v)
            assert getattr(m, name).value == v, (what, name)
        for name in STEP_GAUGES:
            assert getattr(m, name).peak == peaks[name], (what, name)
        snap = snap or m.snapshot()
        assert snap["queue_depth_peak"] == peaks["queue_depth"]
        assert snap["pool_utilization_peak"] == peaks["pool_utilization"]
        assert snap["tokens_generated"] == len(events)
        assert snap["requests_finished"] == sum(e.finished for e in events)
        # the counts: every launch whose drain is behind us, no other
        inflight = eng._inflight is not None
        handed = eng.runner.handed[:len(eng.runner.handed) - inflight]
        assert snap["moe_tokens_routed"] == sum(t for t, _ in handed), what
        assert snap["moe_local_pairs"] == sum(p for _, p in handed), what
        assert not eng._step_counts or inflight
        if what in ("flushed", "done"):
            assert not inflight and m.owed is None
    assert seen == {"added", "aborted", "stepped", "flushed", "done"}
    snap = eng.metrics.snapshot()
    assert snap["preemptions"] >= 1 and snap["step_retries"] == 1
    assert snap["requests_aborted"] == 1 and snap["requests_finished"] >= 5
    assert snap["host_syncs"] <= snap["decode_steps"] + snap["prefill_chunks"]
    outs = eng.outputs()
    assert len(outs) == len(work)
    eng.runner.on_step_counts = None          # the oracle's launches: nobody's
    for rid, p, sp in work:
        ref = naive_generate(eng.runner, p, sp, max_model_len=32)
        got = outs[rid].output_tokens
        if outs[rid].finish_reason == "aborted":
            assert got == ref[:len(got)]
        else:
            assert got == ref and outs[rid].finish_reason == "length"
    assert eng.pool.allocator.check_no_leaks()


def test_a_failed_call_is_accounted_like_one_that_ran(llama_runner,
                                                     monkeypatch):
    """The runner counts a call's bytes once the call is on its way; a call
    that raised counts as it did when the accounting came first."""
    runner = llama_runner
    pool = KVCachePool.for_runner(runner, 8)
    tables = np.zeros((2, 8), np.int32)
    tables[0, :2] = pool.allocator.alloc(2)
    args = (np.asarray([3, 0]), tables, np.asarray([9, 0]), pool.pools)
    before = runner.attn_kv_bytes_gather
    runner.decode(*args)
    once = runner.attn_kv_bytes_gather - before
    assert once > 0

    def broken(kind, key):
        def fn(*a):
            raise RuntimeError("device lost")
        return fn

    monkeypatch.setattr(runner, "_jitted", broken)
    with pytest.raises(RuntimeError, match="device lost"):
        runner.decode(*args)
    assert runner.attn_kv_bytes_gather - before == 2 * once
