"""Cluster-wide KV: shared content-addressed page store (ISSUE 14).

The contract under test: `SharedKVStore` replaces N private host tiers
with ONE router-owned, content-addressed host pool — spills and prefix
demotions from any engine publish into it (dedup by chain hash: a
second spill of a resident chain is a refcount bump, not a copy),
admission on ANY replica resolves its prefix chain against it and takes
the ordinary async page-in path, and handoffs/migrations move slot
REFERENCES instead of page bytes. Nothing about token streams changes:
fp32 stays bit-exact vs `naive_generate`, int8 migrations restore the
exact codes + scale rows (records always carry the sequence's own
bytes — chain dedup is fp32-only by design). Ownership is refcount
arithmetic audited tier-wide: slot rc == index ref + live engines'
refs, dead replicas are reaped by refcount (shared content survives
them), generations invalidate stale references, and a rotating CRC
spot check catches corrupted segment bytes before they serve.
"""

import os
import threading

import numpy as np
import pytest

from _helpers import StubPagedRunner
from paddle_tpu.serving import (
    InvariantViolation, KVCachePool, SamplingParams, ServingEngine,
    SharedKVStore, audit_engine, audit_store, naive_generate,
)
from paddle_tpu.serving.resilience import audit_router
from paddle_tpu.serving.router import ServingRouter

VOCAB, BLOCK, MAXLEN = 31, 4, 48


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    """ISSUE-14 contract: the store-aware invariant auditor runs under
    every test here (engines pick it up via the env default)."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


def _runner():
    return StubPagedRunner(vocab_size=VOCAB, block_size=BLOCK,
                           max_model_len=MAXLEN)


def _store(pages=64, **kw):
    return SharedKVStore.for_runner(_runner(), pages, **kw)


def _engine(store, owner, num_blocks=24, max_batch=4, **kw):
    kw.setdefault("enable_prefix_cache", True)
    return ServingEngine(_runner(), num_blocks=num_blocks,
                         max_batch_size=max_batch, max_model_len=MAXLEN,
                         kv_store=store, kv_store_owner=owner, **kw)


def _oracle(prompt, sp, runner=None):
    return naive_generate(runner or _runner(), prompt, sp,
                          max_model_len=MAXLEN)


def _pump(eng, cond, limit=500):
    """Step until cond() — bounded, so a broken path fails instead of
    hanging the suite."""
    for _ in range(limit):
        if cond():
            return
        eng.step()
    raise AssertionError("condition never reached "
                         f"(queue={eng.scheduler.queue_depth}, "
                         f"running={len(eng.scheduler.running)})")


class Int8StubRunner(StubPagedRunner):
    """StubPagedRunner over an int8 pool: the engine births 4-array
    layer tuples (codes + scale rows); the stub writes token ids as
    codes directly (ids < 127 need no scale math) and threads the
    scale arrays through untouched — the byte paths under test
    (spill/adopt/page-in) are dtype-blind, and the scale rows must
    survive every transfer verbatim."""

    kv_dtype = "int8"

    def _wrap(self, pools):
        (layer,) = pools
        return [layer[:2]], layer[2:]

    def prefill_chunk(self, tokens, start_pos, table, pools):
        kv, rest = self._wrap(pools)
        logits, new = super().prefill_chunk(tokens, start_pos, table, kv)
        return logits, [tuple(new[0]) + tuple(rest)]

    def decode(self, tokens, tables, pos, pools):
        kv, rest = self._wrap(pools)
        logits, new = super().decode(tokens, tables, pos, kv)
        return logits, [tuple(new[0]) + tuple(rest)]


# ------------------------------------------------------ store units


def test_store_refcount_dedup_units():
    st = _store(8)
    a = st.alloc(3, "e0")
    assert a == [0, 1, 2] and st.free_count == 5
    st.set_hash(a[0], 0xAB)
    # publish: the index takes its own ref on top of e0's
    assert st.index_prefix(111, a[0])
    assert st.refcount(a[0]) == 2
    # a second publication of the same chain is a DEDUP, not a copy
    assert not st.index_prefix(111, a[1])
    assert st.stats()["store_dedup_pages"] == 1
    # acquire from another engine: refcount bump on the one copy
    assert st.acquire_prefix(111, "e1") == a[0]
    assert st.refcount(a[0]) == 3
    # releasing every owner ref leaves the index ref: slot stays
    st.release(a, "e0")
    st.release([a[0]], "e1")
    assert st.refcount(a[0]) == 1 and not st.has_prefix(999)
    assert st.free_count == 7
    # dropping the index entry frees the slot and bumps its generation
    g = st.generation(a[0])
    assert st.drop_prefix(111)
    assert st.free_count == 8 and st.generation(a[0]) == g + 1
    # over-release raises (tier-wide double-free guard)
    with pytest.raises(ValueError):
        st.release([a[0]], "e0")


def test_store_retag_reap_and_lru_eviction():
    st = _store(4)
    a = st.alloc(2, "e0")
    st.set_hash(a[0], 1)
    st.set_hash(a[1], 2)
    # retag moves exactly one ref (the handoff ownership transfer)
    st.retag([a[0]], "e0", "xfer:r1")
    assert st.owner_count(a[0], "e0") == 0
    assert st.owner_count(a[0], "xfer:r1") == 1
    # reaping a dead owner frees only ITS refs
    assert st.reap_owner("e0") == 1          # a[1] freed
    assert st.free_count == 3
    assert st.reap_owner("xfer:r1") == 1     # a[0] freed
    assert st.free_count == 4
    # LRU: index-only slots are evicted oldest-tick-first when dry
    slots = st.alloc(4, "pub")
    for i, s in enumerate(slots):
        st.set_hash(s, i)
        assert st.index_prefix(1000 + i, s)
    st.release(slots, "pub")                 # all index-only now
    st.acquire_prefix(1000, "e9")            # touch chain 1000 (LRU-hot)
    st.release([st._prefix[1000]], "e9")
    got = st.alloc(2, "e2")                  # needs 2 evictions
    assert len(got) == 2
    assert st.has_prefix(1000)               # hot entry survived
    assert not st.has_prefix(1001) and not st.has_prefix(1002)
    assert st.stats()["store_evictions"] == 2


def test_store_layout_mismatch_is_loud():
    st = _store(8)
    other = StubPagedRunner(vocab_size=VOCAB, block_size=8,
                            max_model_len=MAXLEN)
    with pytest.raises(ValueError, match="layout mismatch"):
        ServingEngine(other, num_blocks=8, max_batch_size=2,
                      max_model_len=MAXLEN, kv_store=st)


# ------------------------------------- cross-engine page-in (fp32)


def test_spill_on_a_pagein_on_b_bit_exact_fp32():
    """A demotes its prefix cache into the store; B — a different
    engine with a different device pool — admits the same prompt,
    resolves the chain against the store, and pages the SAME bytes
    into its own pool: token streams bit-exact, and the restored
    device pages byte-equal the store's copies."""
    st = _store()
    prompt = list(range(1, 13))             # 3 page-aligned chains
    sp = SamplingParams(max_tokens=6)
    A = _engine(st, "rA")
    A.add_request(prompt, sp)
    outsA = A.run()
    assert A.release_prefix_cache() > 0     # demote -> publish
    assert st.prefix_count >= 2
    B = _engine(st, "rB")
    rid = B.add_request(prompt, sp)
    outsB = B.run()
    ref = _oracle(prompt, sp)
    assert list(outsA.values())[0].output_tokens == ref
    assert outsB[rid].output_tokens == ref
    m = B.metrics.snapshot()
    assert m["store_hit_pages"] >= 2
    assert m["pagein_pages"] >= 2
    # B computed only the unmatched tail of the prompt
    assert m["prefill_tokens"] < len(prompt)
    # byte-exactness: B's paged-in device pages == the store's bytes.
    # match_tiered re-derives the chain, so compare through the index
    from paddle_tpu.serving.kv_cache import _CHAIN_SEED, page_content_hash

    h0 = page_content_hash(_CHAIN_SEED, prompt[:BLOCK])
    cacheB = B.pool.prefix_cache
    pageB = cacheB._index[h0]
    got = [tuple(np.asarray(a[pageB]) for a in layer)
           for layer in B.pool.pools]
    slot0 = st._prefix[h0] if st.has_prefix(h0) else None
    if slot0 is not None:
        want = st.read_slot(slot0)
        for ga, wa in zip(got, want):
            for g, w in zip(ga, wa):
                np.testing.assert_array_equal(g, w)
    audit_engine(A)
    audit_engine(B)


def test_handoff_by_slot_reference_zero_payload_bytes():
    """A prefill-role engine stages a request, the handoff payload is
    slot REFERENCES (no page-byte arrays), and the importing engine
    continues token-exact — `handoff_bytes_out` stays 0."""
    st = _store()
    A = _engine(st, "rA", role="prefill", host_tier_pages=0)
    B = _engine(st, "rB")
    prompt = list(range(2, 11))
    sp = SamplingParams(max_tokens=8)
    rid = A.add_request(prompt, sp)
    _pump(A, A.handoff_ready)
    state, payload = A.extract_handoff(rid)
    assert payload is not None and payload.get("slot_refs")
    assert "layers" not in payload
    assert A.metrics.handoff_bytes_out.value == 0
    B.import_handoff(state, payload)
    outs = B.run()
    assert outs[rid].output_tokens == _oracle(prompt, sp)
    assert B.metrics.handoff_pages_in.value == len(payload["slot_refs"])
    audit_engine(B)


def test_second_handoff_of_same_prefix_is_refcount_bump():
    """The dedup acceptance: two requests sharing a registered prefix
    hand off through the store — the second spill references the
    already-resident chain pages instead of copying them."""
    st = _store()
    A = _engine(st, "rA", role="prefill", host_tier_pages=0,
                max_prefill_tokens_per_step=None)
    shared = list(range(1, 9))              # 2 full pages
    p1 = shared + [9, 10]
    p2 = shared + [11, 12]
    sp = SamplingParams(max_tokens=4)
    r1 = A.add_request(p1, sp)
    r2 = A.add_request(p2, sp)
    _pump(A, lambda: len(A.handoff_ready()) >= 2)
    published_before = st.stats()["store_published_pages"]
    assert A.pool.host_tier.store_dedups >= 1
    assert A.metrics.store_dedup_pages.value >= 1
    B = _engine(st, "rB")
    for rid in (r1, r2):
        state, payload = A.extract_handoff(rid)
        B.import_handoff(state, payload)
    outs = B.run()
    assert outs[r1].output_tokens == _oracle(p1, sp)
    assert outs[r2].output_tokens == _oracle(p2, sp)
    assert st.stats()["store_published_pages"] == published_before
    audit_engine(A)
    audit_engine(B)


# --------------------------------------------- int8 migrations exact


def test_int8_migration_restores_exact_codes_and_scales():
    """Slot-reference migration of an int8 sequence: the decode side
    continues from the SAME codes + scale rows the prefill side wrote
    (dedup is deliberately fp32-only — the record carries this
    sequence's exact bytes), matching the int8 naive oracle."""
    def int8_runner():
        return Int8StubRunner(vocab_size=VOCAB, block_size=BLOCK,
                              max_model_len=MAXLEN)

    st = SharedKVStore.for_runner(int8_runner(), 64)

    def mk(owner, role="mixed"):
        return ServingEngine(int8_runner(), num_blocks=24,
                             max_batch_size=4, max_model_len=MAXLEN,
                             kv_store=st, kv_store_owner=owner,
                             role=role, enable_prefix_cache=True)

    A = mk("rA", role="prefill")
    B = mk("rB")
    prompt = list(range(3, 12))
    sp = SamplingParams(max_tokens=6)
    rid = A.add_request(prompt, sp)
    _pump(A, A.handoff_ready)
    state, payload = A.extract_handoff(rid)
    assert payload is not None and payload.get("slot_refs")
    # int8: every page is a fresh copy, never a dedup reference
    assert A.pool.host_tier.store_dedups == 0
    # the store slots carry codes AND scale rows (4 arrays per layer);
    # capture them — B must page in these exact bytes
    snap = [st.read_slot(s) for s in payload["slot_refs"]]
    assert all(len(layer) == 4 for rec in snap for layer in rec)
    B.import_handoff(state, payload)
    outs = B.run()
    assert outs[rid].output_tokens == _oracle(prompt, sp, int8_runner())
    audit_engine(B)


def test_int8_real_pool_slot_roundtrip_bit_exact():
    """Pool-level pin with a REAL int8 pool (4-array layer tuples):
    store slots hold codes + scale rows verbatim, and read_slot
    returns them bit-identically — the byte contract every migration
    above leans on."""
    pool = KVCachePool(num_layers=2, num_blocks=8, block_size=4,
                       n_kv_heads=2, head_dim=3, kv_dtype="int8")
    layout = [tuple((tuple(a.shape[1:]), str(np.dtype(str(a.dtype))))
                    for a in layer) for layer in pool.pools]
    st = SharedKVStore(layout, 8)
    tier = pool.enable_host_tier(8, store=st, owner="e0")
    r = np.random.default_rng(7)
    import jax.numpy as jnp

    pool.pools = [tuple(
        jnp.asarray(r.integers(-127, 127, a.shape).astype(np.int8))
        if str(a.dtype) == "int8"
        else jnp.asarray(r.random(a.shape).astype(np.float32))
        for a in layer) for layer in pool.pools]
    pages = pool.allocator.alloc(3)
    slots = tier.spill_pages(pages)
    want = pool.read_pages(pages)
    for s, j in zip(slots, range(3)):
        got = tier.read_slot(s)
        for gl, wl in zip(got, want):
            for ga, wa in zip(gl, wl):
                np.testing.assert_array_equal(ga, wa[j])
    # CRC recorded == recomputed (the spot-check baseline)
    for s in slots:
        assert tier.slot_hash(s) == st.content_hash(s)
    tier.free_slots(slots)
    pool.allocator.free(pages)
    assert st.free_count == st.max_pages


# ------------------------------------------- satellite: stale drops


def test_recomputed_registration_drops_store_copy_tierwide():
    """The store analogue of the device-XOR-host fix: a chain the
    match()'s strict cap left UNMATCHED is recomputed on device; its
    registration must decref the stale store copy tier-wide (while a
    PROMOTED registration keeps the copy serving siblings)."""
    st = _store()
    A = _engine(st, "rA")
    prompt = list(range(1, 9))              # exactly 2 pages
    sp = SamplingParams(max_tokens=8)
    A.add_request(prompt, sp)
    A.run()
    A.release_prefix_cache()                # publish chains incl. page 2
    hashes_before = st.prefix_count
    assert hashes_before >= 2
    B = _engine(st, "rB")
    rid = B.add_request(prompt, sp)         # match cap: (8-1)//4 = 1 page
    outs = B.run()
    assert outs[rid].output_tokens == _oracle(prompt, sp)
    m = B.metrics.snapshot()
    assert m["store_hit_pages"] == 1        # page 0 promoted (kept!)
    # page 1 was recomputed and registered -> its store copy dropped
    assert st.prefix_count < hashes_before
    from paddle_tpu.serving.kv_cache import _CHAIN_SEED, page_content_hash

    h0 = page_content_hash(_CHAIN_SEED, prompt[:BLOCK])
    h1 = page_content_hash(h0, prompt[BLOCK:2 * BLOCK])
    assert st.has_prefix(h0)                # promoted: still serving
    assert not st.has_prefix(h1)            # recomputed: dropped
    audit_engine(B)


def test_fuzz_caught_case_drop_while_sibling_pages_in():
    """The refcount race the tier-wide drop must survive: engine B
    acquires a chain for page-in, engine A's recomputed registration
    drops the index entry mid-flight — B's ref keeps the bytes alive
    until its fence releases, and the slot frees only then."""
    st = _store(8)
    s = st.alloc(1, "pub")[0]
    st.set_hash(s, st.content_hash(s))
    assert st.index_prefix(42, s)
    st.release([s], "pub")                  # index-only
    got = st.acquire_prefix(42, "rB")       # B's page-in in flight
    assert got == s
    assert st.drop_prefix(42)               # A recomputed: tier-wide drop
    assert st.free_count == 7               # B's ref pins the bytes
    assert st.refcount(s) == 1
    st.release([s], "rB")                   # B's fence
    assert st.free_count == 8


# -------------------------------------- corruption + staleness guards


def test_corrupted_segment_spot_check_trips_auditor():
    st = _store(8)
    pool = KVCachePool(num_layers=1, num_blocks=8, block_size=BLOCK,
                       n_kv_heads=1, head_dim=1)
    tier = pool.enable_host_tier(8, store=st, owner="e0")
    pages = pool.allocator.alloc(2)
    slots = tier.spill_pages(pages)
    audit_store(st)                         # clean
    st.bufs[0][0][slots[0]] += 1.0          # flip segment bytes
    with pytest.raises(InvariantViolation, match="content-hash"):
        audit_store(st)


def test_adopt_refuses_corrupt_and_degrades_on_stale():
    st = _store(8)
    pool = KVCachePool(num_layers=1, num_blocks=8, block_size=BLOCK,
                       n_kv_heads=1, head_dim=1)
    tierA = pool.enable_host_tier(8, store=st, owner="eA")
    poolB = KVCachePool(num_layers=1, num_blocks=8, block_size=BLOCK,
                        n_kv_heads=1, head_dim=1)
    tierB = poolB.enable_host_tier(8, store=st, owner="eB")
    pages = pool.allocator.alloc(2)
    slots = tierA.spill_pages(pages)
    hashes = [tierA.slot_hash(s) for s in slots]
    gens = [st.generation(s) for s in slots]
    # corrupt transfer: CRC re-verify refuses, refs released
    tierA.retag_out(slots, "xfer:r1")
    st.bufs[0][0][slots[0]] += 1.0
    with pytest.raises(ValueError, match="content-hash"):
        tierB.adopt_slots(slots, gens, hashes, "xfer:r1")
    assert st.free_count == st.max_pages    # nothing leaked
    # stale generation: adopt returns None (recompute fallback)
    pages2 = pool.allocator.alloc(1)
    slots2 = tierA.spill_pages(pages2)
    g2 = [st.generation(slots2[0])]
    h2 = [tierA.slot_hash(slots2[0])]
    tierA.retag_out(slots2, "xfer:r2")
    st.retag(slots2, "xfer:r2", "tmp")      # simulate reuse: free + realloc
    st.release(slots2, "tmp")
    s3 = st.alloc(1, "other")
    assert s3 == slots2                     # recycled, new generation
    st.incref(slots2, "xfer:r2")
    assert tierB.adopt_slots(slots2, g2, h2, "xfer:r2") is None
    assert tierB.fallbacks == 1


# ---------------------------------------------- satellite: async spill


def test_preempt_spill_never_blocks_loop_thread():
    """The async-spill pin (ISSUE 14 satellite): with spill_async=True
    a preemption storm performs ZERO synchronous device->host reads on
    the engine loop thread — the counting stub proves the np.asarray
    happens on the worker. The sync path (spill_async=False) is the
    positive control. Holds for store-backed tiers too."""
    from paddle_tpu.serving import kv_cache as kvmod

    loop = threading.current_thread()

    def run(spill_async, store):
        counts = {"loop_reads": 0}
        orig = kvmod.KVCachePool.read_pages

        def counting(self, pages):
            if threading.current_thread() is loop:
                counts["loop_reads"] += 1
            return orig(self, pages)

        kvmod.KVCachePool.read_pages = counting
        try:
            mm = 32                 # tight pool: preemption must fire
            runner = StubPagedRunner(vocab_size=VOCAB, block_size=BLOCK,
                                     max_model_len=mm)
            kw = dict(num_blocks=10, max_batch_size=4, max_model_len=mm,
                      enable_prefix_cache=True, spill_async=spill_async)
            if store:
                eng = ServingEngine(
                    runner,
                    kv_store=SharedKVStore.for_runner(runner, 64),
                    kv_store_owner="rX", **kw)
            else:
                eng = ServingEngine(runner, host_tier_pages=32, **kw)
            for i in range(6):
                eng.add_request([1 + i, 2, 3, 4, 5, 6, 7],
                                SamplingParams(max_tokens=8))
            eng.run()
            m = eng.metrics.snapshot()
            assert m["preemptions"] > 0, "workload must preempt"
            tier = eng.pool.host_tier
            return counts["loop_reads"], tier.sync_spill_reads
        finally:
            kvmod.KVCachePool.read_pages = orig

    for store in (False, True):
        loop_reads, sync_reads = run(True, store)
        assert loop_reads == 0, (store, loop_reads)
        assert sync_reads == 0, (store, sync_reads)
        loop_reads, sync_reads = run(False, store)
        assert sync_reads > 0, store        # positive control


def test_async_store_spill_publishes_after_bytes_land():
    """Async demotions publish from the worker strictly AFTER the copy
    lands: once has_prefix is observable the bytes are final (CRC
    recorded), so a sibling can never page in a half-written slot."""
    st = _store()
    A = _engine(st, "rA", spill_async=True)
    prompt = list(range(1, 13))
    sp = SamplingParams(max_tokens=6)
    A.add_request(prompt, sp)
    outs = A.run()
    A.release_prefix_cache()
    A.pool.host_tier.sync()
    assert st.prefix_count >= 2
    for h, s in list(st._prefix.items()):
        assert st.slot_hash(s) is not None
        assert st.content_hash(s) == st.slot_hash(s)
    B = _engine(st, "rB", spill_async=True)
    rid = B.add_request(prompt, sp)
    outsB = B.run()
    assert outsB[rid].output_tokens == _oracle(prompt, sp)


# -------------------------------------------------- router integration


def _router(tmp_path=None, replicas=2, **kw):
    def factory(idx=0):
        return _runner()

    kw.setdefault("num_blocks", 24)
    kw.setdefault("block_size", BLOCK)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_model_len", MAXLEN)
    kw.setdefault("enable_prefix_cache", True)
    kw.setdefault("shared_kv_pages", 64)
    return ServingRouter(factory, replicas=replicas, **kw)


def test_rolling_restart_resumes_from_store_zero_recompute():
    """Migration + rolling restart via the store: draining replicas
    demote their device caches tier-wide, so follow-up session turns
    page in on WHICHEVER replica they land on — token-exact, with the
    turn-2 prefix compute collapsing to store hits instead of
    recompute."""
    r = _router()
    try:
        sessions = {}
        for i in range(3):
            p = list(range(1 + i, 13 + i))
            sp = SamplingParams(max_tokens=6, session_id=f"s{i}")
            rid = r.submit(p, sp)
            sessions[rid] = (p, sp)
        outs = r.drain(timeout_s=60)
        for rid, (p, sp) in sessions.items():
            assert outs[rid].output_tokens == _oracle(p, sp)
        r.rolling_restart()
        audit_router(r)
        base = r.metrics_snapshot()["engines"]
        turn2 = {}
        for rid, (p, sp) in sessions.items():
            p2 = p + outs[rid].output_tokens
            sp2 = SamplingParams(max_tokens=4,
                                 session_id=sp.session_id)
            turn2[r.submit(p2, sp2)] = (p2, sp2)
        outs2 = r.drain(timeout_s=60)
        for rid, (p2, sp2) in turn2.items():
            assert outs2[rid].output_tokens == _oracle(p2, sp2)
        audit_router(r)
        m = r.metrics_snapshot()["engines"]
        hits = m["store_hit_pages"] - base["store_hit_pages"]
        computed = m["prefill_tokens"] - base["prefill_tokens"]
        total_ctx = sum(len(p2) for p2, _ in turn2.values())
        assert hits >= 6                     # turn 2 resumed from store
        assert computed < total_ctx / 2      # not a recompute
        assert m["offload_recompute_fallbacks"] == \
            base["offload_recompute_fallbacks"]
    finally:
        r.shutdown()


def test_dead_replica_slots_reaped_never_leaked():
    """A replica killed with store-resident pages: the supervisor's
    recovery reaps its refs by refcount — request-owned slots free,
    INDEX-owned content survives for the siblings — and the tier-wide
    audit (which knows the live owner set) stays green."""
    r = _router(snapshot_every_steps=1, heartbeat_timeout_s=2.0,
                poll_interval_s=0.05)
    try:
        rids = []
        work = {}
        for i in range(4):
            p = list(range(1 + i, 12))
            sp = SamplingParams(max_tokens=8)
            rid = r.submit(p, sp)
            rids.append(rid)
            work[rid] = (p, sp)
        # let some steps run, then kill a replica holding store state
        import time as _t

        deadline = _t.monotonic() + 10
        while _t.monotonic() < deadline:
            if any(rep.steps_done for rep in r._replicas):
                break
            _t.sleep(0.01)
        dead = r._replicas[0]
        dead_owner = dead.store_owner
        r.kill_replica(0)
        outs = r.drain(timeout_s=60)
        for rid, (p, sp) in work.items():
            assert outs[rid].output_tokens == _oracle(p, sp), rid
        audit_router(r)                      # checks live-owner set
        owners = r.kv_store.owners_snapshot()
        for own in owners.values():
            assert dead_owner not in own
        r.release_prefix_caches()
        assert r.check_no_leaks()
    finally:
        r.shutdown()


def test_router_kill_recovery_with_journaled_store_index(tmp_path):
    """Router SIGKILL with a shm-backed store: the segments survive,
    recover() reattaches them and revives the journaled content index
    (CRC-verified per entry) — the next session turn pages in from the
    store a dead router published to."""
    jpath = str(tmp_path / "router.jsonl")

    def factory(idx=0):
        return _runner()

    r = _router(journal_path=jpath, journal_fsync="always",
                shared_kv_shm=True, snapshot_every_steps=1)
    prompt = list(range(1, 13))
    sp = SamplingParams(max_tokens=6)
    rid = r.submit(prompt, sp)
    outs = r.drain(timeout_s=60)
    r.drain_replica(0)                      # demote + journal store_idx
    assert r.kv_store.prefix_count >= 2
    # simulate the SIGKILL: no shutdown — journal handle closed, store
    # segments left mapped (the dead router can't unlink them)
    r._journal.close()
    for rep in r._replicas:
        rep.stop = True
        rep.wake.set()
    if r.supervisor:
        r.supervisor.stop()

    r2 = ServingRouter.recover(
        factory, jpath, replicas=2, num_blocks=24, block_size=BLOCK,
        max_batch_size=4, max_model_len=MAXLEN,
        enable_prefix_cache=True, shared_kv_pages=64,
        shared_kv_shm=True, snapshot_every_steps=1)
    try:
        assert r2.kv_store.prefix_count >= 2     # index revived
        p2 = prompt + outs[rid].output_tokens
        sp2 = SamplingParams(max_tokens=4)
        rid2 = r2.submit(p2, sp2)
        outs2 = r2.drain(timeout_s=60)
        assert outs2[rid2].output_tokens == _oracle(p2, sp2)
        audit_router(r2)
        m = r2.metrics_snapshot()["engines"]
        assert m["store_hit_pages"] >= 2
        assert m["prefill_tokens"] < len(p2)
    finally:
        r2.shutdown()


def test_recover_skips_corrupted_journaled_index_entries(tmp_path):
    """An index entry whose segment bytes no longer CRC-verify is
    silently skipped at recovery — corruption recomputes, never
    serves."""
    jpath = str(tmp_path / "router.jsonl")

    def factory(idx=0):
        return _runner()

    r = _router(journal_path=jpath, journal_fsync="always",
                shared_kv_shm=True, snapshot_every_steps=1)
    prompt = list(range(1, 13))
    sp = SamplingParams(max_tokens=6)
    rid = r.submit(prompt, sp)
    outs = r.drain(timeout_s=60)
    r.drain_replica(0)
    npages = r.kv_store.prefix_count
    assert npages >= 2
    # corrupt ONE published slot's bytes in the shared segment
    victim = next(iter(r.kv_store._prefix.values()))
    r.kv_store.bufs[0][0][victim] += 1.0
    r._journal.close()
    for rep in r._replicas:
        rep.stop = True
        rep.wake.set()
    if r.supervisor:
        r.supervisor.stop()
    r2 = ServingRouter.recover(
        factory, jpath, replicas=2, num_blocks=24, block_size=BLOCK,
        max_batch_size=4, max_model_len=MAXLEN,
        enable_prefix_cache=True, shared_kv_pages=64,
        shared_kv_shm=True, snapshot_every_steps=1)
    try:
        assert r2.kv_store.prefix_count == npages - 1
        p2 = prompt + outs[rid].output_tokens
        rid2 = r2.submit(p2, SamplingParams(max_tokens=4))
        outs2 = r2.drain(timeout_s=60)
        assert outs2[rid2].output_tokens == _oracle(
            p2, SamplingParams(max_tokens=4))
        audit_router(r2)
    finally:
        r2.shutdown()


@pytest.mark.slow
def test_process_backend_store_handoff_zero_wire_bytes():
    """Process replicas share the store through shared memory: the
    prefill->decode handoff ships slot references (handoff_bytes_out
    == 0) and streams stay token-exact under the remote auditor."""
    from _helpers import child_env

    spec = {"factory": "_helpers:stub_runner_factory",
            "factory_kw": {"block_size": BLOCK, "max_model_len": MAXLEN,
                           "vocab_size": VOCAB},
            "sys_path": [os.path.dirname(os.path.abspath(__file__))]}
    geom = {"num_layers": 1, "block_size": BLOCK, "n_kv_heads": 1,
            "head_dim": 1}
    r = ServingRouter(spec, replicas=2, backend="process",
                      prefill_replicas=1, num_blocks=24,
                      block_size=BLOCK, max_batch_size=4,
                      max_model_len=MAXLEN, enable_prefix_cache=True,
                      shared_kv_pages=64, shared_kv_geometry=geom,
                      child_env=child_env(),
                      rendezvous_timeout_s=90, command_timeout_s=90)
    try:
        work = {}
        for i in range(3):
            p = list(range(1, 13)) if i < 2 else [5, 6, 7, 8, 9]
            sp = SamplingParams(max_tokens=6)
            work[r.submit(p, sp)] = (p, sp)
        outs = r.drain(timeout_s=90)
        for rid, (p, sp) in work.items():
            assert outs[rid].output_tokens == _oracle(p, sp), rid
        audit_router(r)
        snap = r.metrics_snapshot()
        assert snap["router"]["handoffs"] == 3
        assert snap["router"]["handoff_fallbacks"] == 0
        assert snap["engines"]["handoff_bytes_out"] == 0
        assert snap["store"]["store_prefix_hits"] > 0
    finally:
        r.shutdown()


# ----------------------------------------------------- 200-trial fuzz


@pytest.mark.slow
def test_fuzz_multi_replica_200_trials_token_exact_no_leaks():
    """200 randomized trials over two engines sharing one store:
    random workloads, tight pools (preemption spills), random
    demotions (release_prefix_cache), random slot-reference migrations
    between the engines, async and sync spill — every stream
    token-exact vs naive, auditors green throughout (autouse env), and
    at teardown the store holds ONLY index-owned content: zero device,
    host, or segment leaks."""
    rng = np.random.default_rng(1234)
    for trial in range(200):
        st = SharedKVStore.for_runner(
            _runner(), int(rng.integers(8, 40)))
        nb = int(rng.integers(13, 22))    # >= max_pages_per_seq (12),
        #                                   tight enough to preempt
        kw = dict(spill_async=bool(rng.integers(0, 2)),
                  host_tier_headroom=bool(rng.integers(0, 2)))
        A = _engine(st, f"A{trial}", num_blocks=nb,
                    max_batch=int(rng.integers(2, 5)), **kw)
        B = _engine(st, f"B{trial}", num_blocks=nb,
                    max_batch=int(rng.integers(2, 5)), **kw)
        engines = [A, B]
        work = []
        for i in range(int(rng.integers(2, 6))):
            eng = engines[int(rng.integers(0, 2))]
            p = list(map(int, rng.integers(
                0, VOCAB, int(rng.integers(3, 12)))))
            sp = SamplingParams(max_tokens=int(rng.integers(2, 8)))
            work.append((eng, eng.add_request(p, sp), p, sp))
        outs = {}
        guard = 0
        while any(e.has_work() for e in engines):
            guard += 1
            assert guard < 4000
            for eng in engines:
                eng.step()
            act = int(rng.integers(0, 12))
            if act == 0:
                engines[int(rng.integers(0, 2))].release_prefix_cache()
            elif act == 1:
                # random slot-reference migration of a running decode
                src = engines[int(rng.integers(0, 2))]
                dst = engines[1 - engines.index(src)]
                cands = [q for q in src.scheduler.running
                         if q.phase == "decode" and q.output_tokens]
                if cands:
                    rid = cands[0].request_id
                    if src.stage_migration(rid):
                        state, payload = src.extract_handoff(rid)
                        dst.import_handoff(state, payload)
                        for j, (e0, r0, p0, s0) in enumerate(work):
                            if r0 == rid:
                                work[j] = (dst, r0, p0, s0)
        for eng in engines:
            outs.update(eng.outputs())
        for eng, rid, p, sp in work:
            assert outs[rid].output_tokens == _oracle(p, sp), \
                (trial, rid)
        for eng in engines:
            eng.release_prefix_cache()
            eng.pool.host_tier.sync()
            assert eng.pool.allocator.check_no_leaks(), trial
        # only index-owned content may remain; no engine refs survive
        assert not st.owners_snapshot(), (trial, st.owners_snapshot())
        audit_store(st)
        st.close()
