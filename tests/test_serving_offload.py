"""Tiered KV: host-RAM offload with async page-in (ISSUE 10).

The contract under test: with `host_tier_pages > 0`, preemption spills
the victim's exclusively-owned pages to pinned host buffers and resume
restores them by copy (page-in) instead of recompute — and NOTHING about
the token streams changes. fp32 engines stay bit-exact vs
`naive_generate`; an int8 engine with the tier matches the int8 naive
oracle even across preemptions (page-in restores the exact codes +
scales, which recompute could not). Every miss — an evicted prefix page
the tier dropped, a tier-cap overflow, a crash-restore — falls back to
the existing recompute path, pinned here explicitly. A 200-trial fuzz
(random pools, preemption storms, host-tier caps, mid-flight
kill-and-restore) runs under the armed invariant auditor, which now
owns the host tier too: slot accounting, single ownership,
device-XOR-host residency, and content-hash spot checks of spilled
bytes.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from _helpers import StubPagedRunner
from paddle_tpu.serving import (
    EngineMetrics, FCFSScheduler, InvariantViolation, KVCachePool,
    OffloadRecord, PrefixCache, Request, SamplingParams, ServingEngine,
    audit_engine, naive_generate,
)

rng = np.random.default_rng(0)

VOCAB, BLOCK, MAXLEN = 31, 4, 40


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    """ISSUE-10 contract: the tier-aware invariant auditor runs under
    every offload test (engines pick it up via the env default)."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


def _runner():
    return StubPagedRunner(vocab_size=VOCAB, block_size=BLOCK,
                           max_model_len=MAXLEN)


def _engine(runner=None, num_blocks=11, max_batch=3, **kw):
    kw.setdefault("host_tier_pages", 32)
    return ServingEngine(runner or _runner(), num_blocks=num_blocks,
                         max_batch_size=max_batch, max_model_len=MAXLEN,
                         **kw)


def _workload(eng, n=6, seed=0, max_tokens=(4, 12)):
    wl = np.random.default_rng(seed)
    work = []
    for _ in range(n):
        p = list(map(int, wl.integers(0, VOCAB, int(wl.integers(3, 12)))))
        sp = SamplingParams(max_tokens=int(wl.integers(*max_tokens)))
        work.append((eng.add_request(p, sp), p, sp))
    return work


def _assert_oracle(runner, work, outs, max_model_len=MAXLEN):
    for rid, p, sp in work:
        ref = naive_generate(runner, p, sp, max_model_len=max_model_len)
        assert outs[rid].output_tokens == ref, rid


# -------------------------------------------------- spill round-trips


def test_spill_pagein_roundtrip_fp32_bit_exact():
    """HostKVTier unit: spilling device pages and paging them back in
    restores the exact bytes across every layer's pools."""
    import jax.numpy as jnp

    pool = KVCachePool(num_layers=2, num_blocks=8, block_size=4,
                       n_kv_heads=2, head_dim=3)
    r = np.random.default_rng(1)
    pool.pools = [tuple(jnp.asarray(r.normal(size=a.shape)
                                    .astype(np.float32))
                        for a in layer) for layer in pool.pools]
    before = pool.read_pages([2, 5])
    tier = pool.enable_host_tier(4)
    slots = tier.spill_pages([2, 5])
    assert slots == [0, 1]
    # clobber the device pages, then restore from host
    pool.write_pages([2, 5], [tuple(np.zeros((2,) + a.shape[1:],
                                             np.float32) for a in layer)
                              for layer in pool.pools])
    data = [tier.read_slot(s) for s in slots]
    stacked = [tuple(np.stack([d[li][j] for d in data])
                     for j in range(len(pool.pools[li])))
               for li in range(2)]
    pool.write_pages([2, 5], stacked)
    after = pool.read_pages([2, 5])
    for b_layer, a_layer in zip(before, after):
        for b, a in zip(b_layer, a_layer):
            np.testing.assert_array_equal(b, a)
    tier.free_slots(slots)
    assert tier.used_count == 0 and tier.free_count == 4


def test_spill_pagein_roundtrip_int8_codes_and_scales_bit_exact():
    """ISSUE-10 satellite pin: on an int8 pool the spill carries the
    code pages AND the per-page-per-head scale rows, and the round-trip
    is bit-exact — the property that makes offloaded int8 resume
    identical to the non-offloaded int8 engine (recompute could not
    guarantee that: re-chunked writes re-round the codes)."""
    import jax.numpy as jnp

    pool = KVCachePool(num_layers=2, num_blocks=8, block_size=4,
                       n_kv_heads=2, head_dim=3, kv_dtype="int8")
    r = np.random.default_rng(2)
    pool.pools = [
        (jnp.asarray(r.integers(-127, 128, pool.pools[0][0].shape)
                     .astype(np.int8)),
         jnp.asarray(r.integers(-127, 128, pool.pools[0][1].shape)
                     .astype(np.int8)),
         jnp.asarray(r.random(pool.pools[0][2].shape).astype(np.float32)),
         jnp.asarray(r.random(pool.pools[0][3].shape).astype(np.float32)))
        for _ in range(2)]
    before = pool.read_pages([1, 3, 6])
    tier = pool.enable_host_tier(8)
    slots = tier.spill_pages([1, 3, 6])
    # host buffers mirror the device layout: int8 codes + fp32 scales
    assert tier._bufs[0][0].dtype == np.int8
    assert tier._bufs[0][2].dtype == np.float32
    zero = [tuple(np.zeros((3,) + a.shape[1:], a.dtype) for a in layer)
            for layer in tier._bufs]
    pool.write_pages([1, 3, 6], zero)
    data = [tier.read_slot(s) for s in slots]
    stacked = [tuple(np.stack([d[li][j] for d in data])
                     for j in range(4)) for li in range(2)]
    pool.write_pages([1, 3, 6], stacked)
    after = pool.read_pages([1, 3, 6])
    for b_layer, a_layer in zip(before, after):
        for b, a in zip(b_layer, a_layer):
            assert b.dtype == a.dtype
            np.testing.assert_array_equal(b, a)


def test_host_tier_validation_and_accounting():
    pool = KVCachePool(num_layers=1, num_blocks=6, block_size=4,
                       n_kv_heads=1, head_dim=2)
    with pytest.raises(ValueError):
        pool.enable_host_tier(0)
    tier = pool.enable_host_tier(2)
    assert pool.enable_host_tier(99) is tier      # idempotent
    assert tier.capacity_bytes == 2 * pool.page_bytes()
    slots = tier.spill_pages([1, 2, 3])           # cap 2: one drops
    assert len(slots) == 2 and tier.dropped_pages == 1
    assert tier.bytes_used == 2 * pool.page_bytes()
    with pytest.raises(ValueError):
        tier.free_slots([slots[0], slots[0]])     # double free guard


# ------------------------------------------- preempt -> spill -> resume


def test_preemption_resumes_by_pagein_token_exact():
    """The headline path: a tight pool forces preemptions; victims spill
    to host, wait with phase='offloaded', and resume by page-in — token
    streams stay exactly naive_generate's, and the resume is paid in
    copied bytes, not recomputed prefill tokens."""
    runner = _runner()
    eng = _engine(runner, num_blocks=11, max_batch=3)
    work = _workload(eng, n=6, seed=0)
    saw_offloaded = False
    while eng.has_work():
        eng.step()
        saw_offloaded = saw_offloaded or any(
            r.phase == "offloaded" and r.offload is not None
            for r in eng.scheduler.waiting)
    outs = eng.outputs()
    m = eng.metrics.snapshot()
    assert m["preemptions"] > 0, "workload never preempted"
    assert saw_offloaded, "no victim ever waited in the offloaded phase"
    assert m["offload_spill_pages"] > 0
    assert m["pagein_pages"] > 0
    assert m["offload_resumes"] > 0
    assert m["offload_recompute_fallbacks"] == 0
    _assert_oracle(runner, work, outs)
    assert eng.pool.allocator.check_no_leaks()
    assert eng.pool.host_tier.used_count == 0


def test_resume_compute_at_least_3x_cheaper_than_recompute():
    """ISSUE-10 acceptance: resume-from-preemption costs >= 3x fewer
    computed prefill tokens with the tier than without, on the same
    trace (the headroom knob stays off so both engines preempt
    identically), and a healthy share of the page-in transfers were
    issued ahead of their fence (pagein_hidden_ratio)."""
    def run(tier_pages):
        runner = _runner()
        eng = ServingEngine(runner, num_blocks=11, max_batch_size=3,
                            max_model_len=MAXLEN,
                            host_tier_pages=tier_pages)
        work = _workload(eng, n=6, seed=3, max_tokens=(8, 14))
        outs = eng.run()
        _assert_oracle(runner, work, outs)
        m = eng.metrics.snapshot()
        initial = sum(len(p) for _, p, _ in work)
        return m, m["prefill_tokens"] - initial

    m_recompute, resume_recompute = run(0)
    m_pagein, resume_pagein = run(32)
    assert m_recompute["preemptions"] == m_pagein["preemptions"] > 0
    assert resume_recompute > 0
    # every resumed request still computes its one outstanding token, so
    # the page-in arm's resume cost is ~1 token per resume
    assert resume_recompute >= 3 * max(resume_pagein, 1), (
        resume_recompute, resume_pagein)
    assert m_pagein["pagein_hidden_ratio"] > 0.0
    assert m_pagein["pagein_hidden_ratio"] <= 1.0


def test_offload_record_dropped_on_abort_of_waiting_request():
    """Aborting (or shedding / timing out) an offloaded waiter releases
    its host slots — a dead request never pins host RAM."""
    runner = _runner()
    eng = _engine(runner, num_blocks=11, max_batch=3)
    work = _workload(eng, n=6, seed=0)
    victim = None
    while eng.has_work() and victim is None:
        eng.step()
        for r in eng.scheduler.waiting:
            if r.offload is not None:
                victim = r
                break
    assert victim is not None, "no request was ever offloaded"
    held = len(victim.offload.slots)
    used_before = eng.pool.host_tier.used_count
    assert eng.abort(victim.request_id)
    assert victim.offload is None
    assert eng.pool.host_tier.used_count == used_before - held
    eng.run()
    assert eng.pool.allocator.check_no_leaks()
    assert eng.pool.host_tier.used_count == 0


# --------------------------------------------------- recompute fallback


def test_recompute_fallback_on_connection_hole():
    """An offload record whose leading (prefix-cache) pages are gone —
    start_page not covered by any device/host match — must fall back to
    the recompute path: slots freed, fallback counted, request served
    exactly as before the tier existed."""
    pool = KVCachePool(num_layers=1, num_blocks=12, block_size=BLOCK,
                       n_kv_heads=1, head_dim=1)
    pool.enable_prefix_cache()
    tier = pool.enable_host_tier(8)
    sched = FCFSScheduler(pool, max_batch_size=2, max_pages_per_seq=10)
    # hand-build a spilled state whose registered prefix no longer exists
    pages = pool.allocator.alloc(2)
    slots = tier.spill_pages(pages)
    pool.allocator.free(pages)
    req = Request(prompt_tokens=list(range(1, 14)),
                  sampling=SamplingParams(max_tokens=2))
    req.offload = OffloadRecord(start_page=2, covered_tokens=12,
                                slots=slots)
    req.phase = "offloaded"
    sched.add(req)
    admitted = sched.admit()
    assert admitted == [req]
    assert req.offload is None
    assert req.pending_pagein == []          # nothing restorable
    assert req.kv.num_tokens == 0            # full recompute
    assert tier.used_count == 0              # slots released
    assert tier.fallbacks == 1


def test_tier_cap_overflow_degrades_to_recompute_token_exact():
    """A 1-page tier cannot hold most spills: drops happen, some resumes
    recompute — and the streams still match the oracle (the
    recompute-fallback-on-miss pin)."""
    runner = _runner()
    eng = _engine(runner, num_blocks=11, max_batch=3, host_tier_pages=1)
    work = _workload(eng, n=6, seed=3, max_tokens=(8, 14))
    outs = eng.run()
    m = eng.metrics.snapshot()
    assert m["preemptions"] > 0
    assert m["host_tier_drops"] > 0, "cap never overflowed"
    _assert_oracle(runner, work, outs)
    assert eng.pool.allocator.check_no_leaks()
    assert eng.pool.host_tier.used_count == 0


# ------------------------------------------------ prefix-cache demotion


def test_evict_hook_fires_on_evict_and_clear():
    """ISSUE-10 satellite: evict_hook intercepts BOTH LRU eviction and
    clear() — same signature, reason distinguishes them — while the
    page is still allocated."""
    pool = KVCachePool(num_layers=1, num_blocks=8, block_size=2,
                       n_kv_heads=1, head_dim=1)
    cache = pool.enable_prefix_cache()
    calls = []
    cache.evict_hook = lambda page, h, reason: calls.append(
        (page, h, reason, pool.allocator.refcount(page)))
    pages = pool.allocator.alloc(3)
    for i, p in enumerate(pages):
        h = 1000 + i
        cache._index[h] = p
        cache._page_hash[p] = h
        pool.allocator.incref(p)
        cache._touch(p)
    pool.allocator.free(pages)               # cached-free (rc 1)
    assert cache.evict(1) == 1
    assert len(calls) == 1 and calls[0][2] == "evict"
    assert calls[0][3] == 1                  # fired before the decref
    assert cache.clear() == 2
    assert len(calls) == 3
    assert {c[2] for c in calls[1:]} == {"clear"}
    assert pool.allocator.check_no_leaks()


def test_prefix_demotion_then_host_hit_pages_back_in():
    """LRU-evicted (and clear()-dropped) prefix pages demote to the host
    tier; a later request with the same header hits the HOST index, gets
    fresh device pages, and the engine pages the content in — counted as
    prefix hits, token-exact."""
    runner = _runner()
    eng = _engine(runner, num_blocks=11, max_batch=2,
                  enable_prefix_cache=True)
    header = list(range(5, 5 + 2 * BLOCK))   # two full pages
    sp = SamplingParams(max_tokens=4)
    work = []
    p1 = header + [1, 2, 3]
    work.append((eng.add_request(p1, sp), p1, sp))
    eng.run()
    cache = eng.pool.prefix_cache
    tier = eng.pool.host_tier
    demoted = cache.evict(10)
    assert demoted > 0 and tier.prefix_count == demoted
    p2 = header + [9, 9, 9]
    work.append((eng.add_request(p2, sp), p2, sp))
    eng.run()
    m = eng.metrics.snapshot()
    assert m["pagein_pages"] >= 2            # the demoted header pages
    assert m["prefix_hit_tokens"] >= 2 * BLOCK
    _assert_oracle(runner, work, eng.outputs())
    # promoted hashes left the host index: device-live XOR host-resident
    assert tier.prefix_count == demoted - 2
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()


def test_clear_demotes_to_host_no_silent_leak():
    """release_prefix_cache() (the clear() path) demotes through the
    SAME hook as eviction, so the tier's view stays consistent — and
    every host slot is still owned by exactly one party (the auditor's
    accounting, asserted directly)."""
    runner = _runner()
    eng = _engine(runner, num_blocks=11, max_batch=2,
                  enable_prefix_cache=True)
    p = list(range(1, 1 + 3 * BLOCK))
    eng.add_request(p, SamplingParams(max_tokens=2))
    eng.run()
    assert len(eng.pool.prefix_cache) > 0
    eng.release_prefix_cache()
    tier = eng.pool.host_tier
    assert tier.prefix_count == tier.used_count > 0
    assert eng.pool.allocator.check_no_leaks()
    audit_engine(eng)                        # tier accounting green


# ------------------------------------------------- watermark headroom


def test_watermark_counts_host_headroom_when_knob_on():
    """ISSUE-10 knob: free host-tier slots count as near-headroom above
    the admission watermark — the same pool admits more concurrent
    sessions with the knob on, and none without it."""
    def build(knob):
        pool = KVCachePool(num_layers=1, num_blocks=11, block_size=BLOCK,
                           n_kv_heads=1, head_dim=1)
        pool.enable_host_tier(16)
        sched = FCFSScheduler(pool, max_batch_size=4, max_pages_per_seq=10,
                              admission_watermark=0.5,
                              count_host_headroom=knob)
        for i in range(3):
            sched.add(Request(prompt_tokens=[1] * 7,   # 2 pages + 1 -> 2
                              sampling=SamplingParams(max_tokens=2)))
        return sched

    # watermark 0.5 of 10 usable = 5 pages; each request needs 2
    off = build(False)
    assert len(off.admit()) == 2             # 3rd would cross 5 pages
    on = build(True)
    assert len(on.admit()) == 3              # host headroom lifts the cap


def test_auditor_catches_corrupted_host_slot_and_double_owner():
    runner = _runner()
    eng = _engine(runner, num_blocks=11, max_batch=3)
    work = _workload(eng, n=6, seed=0)
    victim = None
    while eng.has_work() and victim is None:
        eng.step()
        victim = next((r for r in eng.scheduler.waiting
                       if r.offload is not None), None)
    assert victim is not None
    tier = eng.pool.host_tier
    slot = victim.offload.slots[0]
    tier._bufs[0][0][slot] += 1.0            # corrupt the spilled bytes
    with pytest.raises(InvariantViolation, match="content-hash"):
        audit_engine(eng)
    tier._hash[slot] = tier.content_hash(slot)   # heal
    audit_engine(eng)
    other = eng.scheduler.waiting[0]
    saved = other.offload
    other.offload = OffloadRecord(0, 4, [slot])  # double ownership
    with pytest.raises(InvariantViolation):
        audit_engine(eng)
    other.offload = saved
    eng.run()
    _ = eng.outputs()                        # drains clean after healing


# --------------------------------------------------- snapshot / restore


def test_snapshot_restore_roundtrips_tier_config_host_pages_die():
    """Crash-restore semantics (pinned): the tier KNOBS survive the
    snapshot round-trip, the host PAGES do not — every restored request
    re-enters through recompute, token-exact, and the new tier refills
    from fresh spills."""
    runner = _runner()
    eng = _engine(runner, num_blocks=11, max_batch=3,
                  host_tier_headroom=True, pagein_prefetch=3)
    work = _workload(eng, n=6, seed=0)
    for _ in range(4):                       # mid-flight, offload likely
        eng.step()
    snap = eng.snapshot()
    assert snap["config"]["host_tier_pages"] == 32
    assert snap["config"]["host_tier_headroom"] is True
    assert snap["config"]["pagein_prefetch"] == 3
    restored = ServingEngine.restore(runner, snap)
    assert restored.pool.host_tier is not None
    assert restored.pool.host_tier.used_count == 0   # pages died, pinned
    assert restored.scheduler.count_host_headroom is True
    outs = restored.run()
    _assert_oracle(runner, work, outs)
    assert restored.pool.allocator.check_no_leaks()
    assert restored.pool.host_tier.used_count == 0


# ---------------------------------------------------- int8 composition


@pytest.mark.slow
def test_int8_offload_resume_matches_int8_naive_oracle():
    """ISSUE-10 acceptance, int8 half: with monolithic prefill (no
    chunking, no prefix sharing) the int8 engine is token-exact vs the
    int8 naive oracle — and stays so ACROSS preemptions when the host
    tier restores the exact codes + scales. Recompute-on-resume could
    not pin this: re-chunked writes re-round the codes."""
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=2, num_kv_heads=1, max_seq_len=64,
                      dropout=0.0)
    model = Llama(cfg)
    model.eval()
    runner = LlamaRunner(model, block_size=8, max_model_len=64,
                         attn_impl="reference", kv_dtype="int8")
    eng = ServingEngine(runner, num_blocks=9, max_batch_size=2,
                        max_model_len=64, host_tier_pages=16)
    wl = np.random.default_rng(7)
    work = []
    for _ in range(2):
        p = list(map(int, wl.integers(1, 97, 20)))
        sp = SamplingParams(max_tokens=16)
        work.append((eng.add_request(p, sp), p, sp))
    outs = eng.run()
    m = eng.metrics.snapshot()
    assert m["preemptions"] >= 1, "pool never tightened"
    assert m["offload_resumes"] >= 1, "resume never took the page-in path"
    for rid, p, sp in work:
        ref = naive_generate(runner, p, sp, max_model_len=64)
        assert outs[rid].output_tokens == ref, rid
    assert eng.pool.allocator.check_no_leaks()
    assert eng.pool.host_tier.used_count == 0


@pytest.mark.slow
def test_tp2_sharded_offload_spill_pagein_token_exact():
    """Offload composes with tensor parallelism (ISSUE 7): on a tp=2
    CPU mesh the spill gathers each shard's kv-head slice, the staging
    hook device_puts the page back kv-head-sharded, and the streams
    stay exactly the oracle's."""
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.parallel.mesh import serving_mesh
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=2, num_kv_heads=2, max_seq_len=64,
                      dropout=0.0)
    model = Llama(cfg)
    model.eval()
    runner = LlamaRunner(model, block_size=8, max_model_len=64,
                         attn_impl="reference")
    runner.shard(serving_mesh(data=1, model=2))
    eng = ServingEngine(runner, num_blocks=9, max_batch_size=2,
                        max_model_len=64, host_tier_pages=16)
    wl = np.random.default_rng(1)
    work = []
    for _ in range(2):
        p = list(map(int, wl.integers(1, 97, 20)))
        sp = SamplingParams(max_tokens=16)
        work.append((eng.add_request(p, sp), p, sp))
    outs = eng.run()
    m = eng.metrics.snapshot()
    assert m["preemptions"] >= 1 and m["offload_resumes"] >= 1
    for rid, p, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            runner, p, sp, max_model_len=64), rid
    assert eng.pool.allocator.check_no_leaks()
    assert eng.pool.host_tier.used_count == 0


# ------------------------------------------------------------------ fuzz


@pytest.mark.slow
def test_fuzz_spill_pagein_200_trials_token_exact_no_leaks():
    """ISSUE-10 satellite: 200 seeded trials of random pools, preemption
    storms, host-tier caps (tiny caps force drop-and-recompute), random
    chunk budgets, the prefix cache on half the trials, and mid-flight
    kill-and-restore — all under the armed tier-aware auditor. Every
    trial must drain token-for-token equal to the naive oracle with
    zero device-page, slot, or host-slot leaks."""
    totals = {"preempt": 0, "spill": 0, "pagein": 0, "resume": 0,
              "drops": 0, "hidden": 0, "restores": 0}
    for trial in range(200):
        wl = np.random.default_rng(9000 + trial)
        block_size = int(wl.integers(2, 5))
        num_blocks = int(wl.integers(5, 15))
        usable = num_blocks - 1
        max_batch = int(wl.integers(1, 5))
        max_model_len = usable * block_size
        tier_pages = int(wl.choice([1, 2, 4, 8, 32]))
        runner = StubPagedRunner(vocab_size=VOCAB, block_size=block_size,
                                 max_model_len=max_model_len)
        budget = (None if int(wl.integers(0, 4)) == 0
                  else int(wl.integers(1, 9)))
        kw = dict(num_blocks=num_blocks, max_batch_size=max_batch,
                  max_model_len=max_model_len,
                  max_prefill_tokens_per_step=budget,
                  enable_prefix_cache=bool(wl.integers(0, 2)),
                  host_tier_pages=tier_pages,
                  host_tier_headroom=bool(wl.integers(0, 2)),
                  pagein_prefetch=int(wl.integers(0, 4)))
        eng = ServingEngine(runner, **kw)
        assert eng.audit, "fuzz must run under the invariant auditor"
        header = list(map(int, wl.integers(0, VOCAB,
                                           int(wl.integers(0, 10)))))
        n_req = int(wl.integers(2, 9))
        pending = []
        for i in range(n_req):
            plen = int(wl.integers(1, min(14, max_model_len - 1) + 1))
            p = list(map(int, wl.integers(0, VOCAB, plen)))
            if header and int(wl.integers(0, 2)) == 0:
                h = header[:max(0, plen - 1)]
                p[:len(h)] = h
            mt = int(wl.integers(1, min(8, max_model_len - plen) + 1))
            pending.append((p, SamplingParams(max_tokens=mt)))
        work = []
        kill_at = (int(wl.integers(2, 10))
                   if int(wl.integers(0, 4)) == 0 else None)
        steps = 0
        snap_totals = {"spill": 0, "pagein": 0, "hidden": 0, "drops": 0,
                       "resume": 0, "preempt": 0}

        def bank(m):
            snap_totals["spill"] += m["offload_spill_pages"]
            snap_totals["pagein"] += m["pagein_pages"]
            snap_totals["hidden"] += m["pagein_hidden_pages"]
            snap_totals["drops"] += m["host_tier_drops"]
            snap_totals["resume"] += m["offload_resumes"]
            snap_totals["preempt"] += m["preemptions"]

        while pending or eng.has_work():
            for _ in range(int(wl.integers(0, 3))):
                if pending:
                    p, sp = pending.pop(0)
                    work.append((eng.add_request(p, sp), p, sp))
            eng.step()
            steps += 1
            if kill_at is not None and steps == kill_at:
                # mid-flight crash: host pages die with the process,
                # the restored engine recomputes — exactness untouched
                bank(eng.metrics.snapshot())
                eng = ServingEngine.restore(runner, eng.snapshot())
                assert eng.pool.host_tier is not None
                assert eng.pool.host_tier.used_count == 0
                totals["restores"] += 1
        outs = eng.outputs()
        assert len(outs) == n_req, f"trial {trial}: lost requests"
        eng.release_prefix_cache()
        assert eng.pool.allocator.check_no_leaks(), \
            f"trial {trial}: leaked device pages"
        tier = eng.pool.host_tier
        # after the drain every surviving host slot belongs to the
        # tier's own prefix index (clear() demotions included) — an
        # orphan slot is a host-RAM leak
        assert set(tier._hash) == set(tier._prefix.values()), \
            f"trial {trial}: leaked host slots"
        m = eng.metrics.snapshot()
        bank(m)
        totals["preempt"] += snap_totals["preempt"]
        totals["spill"] += snap_totals["spill"]
        totals["pagein"] += snap_totals["pagein"]
        totals["hidden"] += snap_totals["hidden"]
        totals["drops"] += snap_totals["drops"]
        totals["resume"] += snap_totals["resume"]
        for rid, p, sp in work:
            assert outs[rid].finish_reason == "length"
            assert outs[rid].output_tokens == naive_generate(
                runner, p, sp, max_model_len=max_model_len), \
                f"trial {trial}: {rid} diverged from the oracle"
    assert totals["preempt"] > 0, "fuzz never preempted"
    assert totals["spill"] > 0, "fuzz never spilled"
    assert totals["pagein"] > 0, "fuzz never paged in"
    assert totals["resume"] > 0, "fuzz never resumed from host"
    assert totals["hidden"] > 0, "prefetch never hid a transfer"
    assert totals["drops"] > 0, "tiny caps never overflowed"
    assert totals["restores"] > 0, "fuzz never killed-and-restored"
