"""Router tier (ISSUE 8): multi-engine ServingRouter with prefix-
affinity routing and the crash-restarting Supervisor.

The contract under test: per-request token streams through the router
are EXACTLY the single-engine (and naive-oracle) streams no matter how
requests are spread over replicas, shed between queues, or moved by a
mid-run replica kill + supervisor restore — zero lost requests, zero
duplicated tokens, every replica's invariant audit green. Most tests
drive the numpy StubPagedRunner (fast, pool-faithful); the routing /
at-most-once / supervisor machinery being exercised is exactly the
production code path.
"""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from _helpers import StubPagedRunner
from paddle_tpu.serving import (
    EngineMetrics, FaultInjector, QueueFullError, ReplicaCrashError,
    SamplingParams, ServingEngine, ServingRouter, StreamDetokenizer,
    TokenizerAdapter, audit_router, naive_generate, replica_submeshes,
    serving_mesh,
)
from paddle_tpu.serving.engine import TokenEvent
from paddle_tpu.serving.metrics import aggregate_snapshots

VOCAB, BLOCK, MAXLEN = 31, 4, 64


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    """Every replica engine audits its invariants after every step."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


def stub_factory(idx=0):
    return StubPagedRunner(vocab_size=VOCAB, block_size=BLOCK,
                           max_model_len=MAXLEN)


ORACLE = StubPagedRunner(vocab_size=VOCAB, block_size=BLOCK,
                         max_model_len=MAXLEN)


def oracle(prompt, sp):
    return naive_generate(ORACLE, prompt, sp, max_model_len=MAXLEN)


def make_router(**kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("max_batch_size", 3)
    kw.setdefault("max_model_len", MAXLEN)
    kw.setdefault("poll_interval_s", 0.02)
    return ServingRouter(kw.pop("factory", stub_factory), **kw)


def tenant_workload(n, seed=0, tenants=3, header_pages=2):
    """Skewed multi-tenant prompts: half the traffic on tenant 0, each
    tenant sharing a page-aligned few-shot header."""
    rng = np.random.default_rng(seed)
    headers = [list(rng.integers(1, VOCAB, header_pages * BLOCK))
               for _ in range(tenants)]
    prompts = []
    for i in range(n):
        t = 0 if i % 2 == 0 else 1 + (i // 2) % (tenants - 1)
        prompts.append(headers[t]
                       + list(rng.integers(1, VOCAB,
                                           int(rng.integers(2, 8)))))
    return prompts


# ------------------------------------------------------- token exactness


def test_router_token_exact_vs_single_engine_greedy():
    prompts = tenant_workload(14)
    sp = SamplingParams(max_tokens=10)
    single = ServingEngine(stub_factory(), num_blocks=24, max_batch_size=3,
                           max_model_len=MAXLEN, enable_prefix_cache=True,
                           max_prefill_tokens_per_step=8)
    for i, p in enumerate(prompts):
        single.add_request(p, sp, request_id=f"s{i}")
    single_outs = single.run()
    with make_router(enable_prefix_cache=True,
                     max_prefill_tokens_per_step=8) as router:
        for i, p in enumerate(prompts):
            router.submit(p, sp, request_id=f"s{i}")
        outs = router.drain(timeout_s=60)
        audit_router(router)
        for i, p in enumerate(prompts):
            assert outs[f"s{i}"].output_tokens == \
                single_outs[f"s{i}"].output_tokens == oracle(p, sp)
        assert all(o.finish_reason == "length" for o in outs.values())
        router.release_prefix_caches()
        assert router.check_no_leaks()


def test_router_token_exact_seeded_temperature():
    prompts = tenant_workload(10, seed=3)
    sps = [SamplingParams(max_tokens=8, temperature=0.7, top_k=12,
                          seed=100 + i) for i in range(len(prompts))]
    with make_router() as router:
        for i, (p, sp) in enumerate(zip(prompts, sps)):
            router.submit(p, sp, request_id=f"t{i}")
        outs = router.drain(timeout_s=60)
        audit_router(router)
    for i, (p, sp) in enumerate(zip(prompts, sps)):
        assert outs[f"t{i}"].output_tokens == oracle(p, sp)


# --------------------------------------------------------------- routing


def test_affinity_routes_same_tenant_to_same_replica():
    header = list(range(1, 1 + 2 * BLOCK))
    with make_router(enable_prefix_cache=True) as router:
        rid0 = router.submit(header + [20, 21],
                             SamplingParams(max_tokens=2))
        home = router._reqs[rid0].owner_idx
        for k in range(4):
            rid = router.submit(header + [22 + k],
                                SamplingParams(max_tokens=2))
            assert router._reqs[rid].owner_idx == home
        assert router.metrics.routed_affinity.value == 4
        router.drain(timeout_s=30)


def test_session_stickiness_routes_repeat_turns_home():
    """ISSUE 10 satellite: SamplingParams.session_id pins repeat turns
    to the replica that served the session, AHEAD of prefix affinity —
    even when the turns share no token prefix at all (multi-turn chat
    whose context diverges per turn)."""
    with make_router(enable_prefix_cache=True) as router:
        rid0 = router.submit([7, 8, 9], SamplingParams(
            max_tokens=2, session_id="chat-a"))
        home = router._reqs[rid0].owner_idx
        for k in range(4):
            # disjoint prompts: prefix affinity alone could not pin these
            rid = router.submit([10 + 3 * k, 11 + 3 * k],
                                SamplingParams(max_tokens=2,
                                               session_id="chat-a"))
            assert router._reqs[rid].owner_idx == home
        assert router.metrics.session_sticky_hits.value == 4
        assert router.metrics.snapshot()["session_sticky_hits"] == 4
        # a different session is free to land elsewhere; stickiness must
        # not leak across session ids
        router.submit([1, 2], SamplingParams(max_tokens=2,
                                             session_id="chat-b"))
        assert router.metrics.session_sticky_hits.value == 4
        outs = router.drain(timeout_s=30)
        audit_router(router)
        assert all(o.finish_reason == "length" for o in outs.values())


def test_session_pin_purged_when_replica_restarts():
    """A restarted replica's pool lost the session's pages: the pin is
    purged with the affinity entries, and the next turn re-pins to
    wherever it lands."""
    with make_router(enable_prefix_cache=True) as router:
        rid = router.submit([5, 6, 7], SamplingParams(
            max_tokens=2, session_id="chat-x"))
        home = router._reqs[rid].owner_idx
        router.drain(timeout_s=30)
        assert router._sessions["chat-x"] == home
        router.kill_replica(home)
        deadline = time.monotonic() + 30
        while (router._replicas[home].status != "live"
               and time.monotonic() < deadline):
            router.supervisor.poll()
            time.sleep(0.01)
        assert "chat-x" not in router._sessions
        rid2 = router.submit([5, 6, 7], SamplingParams(
            max_tokens=2, session_id="chat-x"))
        assert router._sessions["chat-x"] == router._reqs[rid2].owner_idx
        router.drain(timeout_s=30)


def test_prefix_affinity_hit_rate_beats_random_and_matches_single():
    prompts = tenant_workload(20, seed=5)
    sp = SamplingParams(max_tokens=4)

    def run_router(policy):
        with make_router(policy=policy, enable_prefix_cache=True,
                         max_prefill_tokens_per_step=8) as router:
            for i, p in enumerate(prompts):
                router.submit(p, sp, request_id=f"p{i}")
                # tenant traffic trickles in: hits need registered pages
                router.drain(timeout_s=60) if i == len(prompts) - 1 \
                    else time.sleep(0.002)
            outs = router.drain(timeout_s=60)
            audit_router(router)
            agg = router.metrics_snapshot()["engines"]
            assert len(outs) == len(prompts)
            return agg["prefix_hit_tokens"]

    single = ServingEngine(stub_factory(), num_blocks=24, max_batch_size=3,
                           max_model_len=MAXLEN, enable_prefix_cache=True,
                           max_prefill_tokens_per_step=8)
    for i, p in enumerate(prompts):
        single.add_request(p, sp, request_id=f"p{i}")
        single.step()
    single.run()
    single_hits = single.metrics.snapshot()["prefix_hit_tokens"]

    affinity_hits = run_router("prefix")
    random_hits = run_router("random")
    # affinity keeps tenants where their pages live: the tier hit count
    # must at least match ONE engine's (never dilute 1/N) and beat
    # scatter routing on the same trace
    assert affinity_hits >= single_hits > 0
    assert affinity_hits > random_hits


def test_hot_affinity_target_sheds_to_sibling():
    header = list(range(1, 1 + 2 * BLOCK))
    sp = SamplingParams(max_tokens=2)
    stop = threading.Event()

    def slow_factory(idx):
        # per-call stalls stretch the decoys below so queue depths stay
        # deterministic across the burst (the batch slot is occupied,
        # so every burst request WAITS where it was routed)
        return FaultInjector(stub_factory(idx), stall_every=1,
                             stall_target="both",
                             on_stall=lambda: stop.wait(0.01))

    router = make_router(factory=slow_factory, max_queue_depth=2,
                         max_batch_size=1, enable_prefix_cache=True,
                         supervise=False)
    try:
        # occupy both replicas with long decoys, and wait until both
        # are ADMITTED (running) so the burst sees empty queues
        for d in ([9, 9, 9], [8, 8, 8]):
            router.submit(d, SamplingParams(max_tokens=40))
        deadline = time.monotonic() + 5
        while (sum(len(r.engine.scheduler.running)
                   for r in router._replicas) < 2
                and time.monotonic() < deadline):
            time.sleep(0.002)
        # ...then burst one tenant: the first burst request pins the
        # tenant's affinity, the next fills that replica's queue, and
        # the third SHEDS to the sibling instead of rejecting
        rids = [router.submit(header + [10 + k], sp) for k in range(3)]
        owners = [router._reqs[r].owner_idx for r in rids]
        assert owners[0] == owners[1]
        assert owners[2] != owners[0]
        assert router.metrics.shed_reroutes.value > 0
        assert router.metrics.tier_rejections.value == 0
        stop.set()
        outs = router.drain(timeout_s=30)
        assert len(outs) == 5
    finally:
        stop.set()
        router.shutdown()


def test_tier_queue_full_reject_and_drop_oldest():
    stop = threading.Event()

    def slow_factory(idx):
        runner = stub_factory(idx)
        return FaultInjector(runner, stall_every=1, stall_target="both",
                             on_stall=lambda: stop.wait(0.05))

    sp = SamplingParams(max_tokens=2)
    # reject: once every replica queue is full, submit raises
    router = make_router(factory=slow_factory, max_queue_depth=1,
                         shed_policy="reject", supervise=False,
                         max_batch_size=1, replicas=2)
    try:
        with pytest.raises(QueueFullError):
            for k in range(12):
                router.submit([1, 2, 3 + k], sp)
        assert router.metrics.tier_rejections.value >= 1
    finally:
        stop.set()
        router.shutdown()
    # drop_oldest: the tier overflows into the least-loaded engine,
    # whose own gate sheds its oldest — nothing is ever LOST
    stop2 = threading.Event()

    def slow_factory2(idx):
        return FaultInjector(stub_factory(idx), stall_every=1,
                             stall_target="both",
                             on_stall=lambda: stop2.wait(0.05))

    router = make_router(factory=slow_factory2, max_queue_depth=1,
                         shed_policy="drop_oldest", supervise=False,
                         max_batch_size=1, replicas=2)
    try:
        rids = [router.submit([1, 2, 3 + k], sp) for k in range(10)]
        assert router.metrics.tier_overflow.value > 0
        stop2.set()
        outs = router.drain(timeout_s=30)
        audit_router(router)
        assert set(rids) == set(outs)
        reasons = {o.finish_reason for o in outs.values()}
        assert "shed" in reasons
        assert reasons <= {"shed", "length", "stop"}
    finally:
        stop2.set()
        router.shutdown()


# ---------------------------------------------- supervisor: kill / crash


def _assert_exact(outs, prompts, sp, prefix="k"):
    for i, p in enumerate(prompts):
        o = outs[f"{prefix}{i}"]
        assert o.output_tokens == oracle(p, sp), \
            f"{prefix}{i}: {o.output_tokens} != oracle"
        assert o.finish_reason in ("stop", "length")


def test_kill_replica_mid_run_zero_lost_token_exact():
    prompts = tenant_workload(12, seed=7)
    sp = SamplingParams(max_tokens=16)
    with make_router(enable_prefix_cache=True) as router:
        for i, p in enumerate(prompts):
            router.submit(p, sp, request_id=f"k{i}")
        # let the tier make progress so the kill lands mid-generation
        deadline = time.monotonic() + 10
        while (router.metrics.tokens_delivered.value < 12
                and time.monotonic() < deadline):
            time.sleep(0.002)
        assert router.kill_replica(0)
        outs = router.drain(timeout_s=60)
        audit_router(router)
        _assert_exact(outs, prompts, sp)
        assert len(outs) == len(prompts)            # zero lost
        m = router.metrics
        assert m.replica_restarts.value >= 1
        # at-most-once: every delivered stream has exactly cursor tokens
        for rec in router._reqs.values():
            assert rec.cursor == len(rec.tokens)
        router.release_prefix_caches()
        assert router.check_no_leaks()


def test_kill_recovery_from_registry_alone():
    """snapshot_every_steps=0: the dead replica has NO snapshot, so the
    supervisor rebuilds purely from the router registry (fresh engine +
    inject_request with the delivered prefix) — still token-exact."""
    prompts = tenant_workload(8, seed=9)
    sp = SamplingParams(max_tokens=12)
    with make_router(snapshot_every_steps=0) as router:
        for i, p in enumerate(prompts):
            router.submit(p, sp, request_id=f"k{i}")
        deadline = time.monotonic() + 10
        while (router.metrics.tokens_delivered.value < 8
                and time.monotonic() < deadline):
            time.sleep(0.002)
        router.kill_replica(1)
        outs = router.drain(timeout_s=60)
        audit_router(router)
        _assert_exact(outs, prompts, sp)
        assert router.metrics.resubmitted_requests.value >= 1


def test_injected_replica_crash_escapes_engine_and_recovers():
    crashed = []

    def crash_factory(idx):
        runner = stub_factory(idx)
        if idx == 0 and not crashed:
            crashed.append(1)
            return FaultInjector(runner, crash_calls=[4],
                                 crash_target="decode")
        return runner

    prompts = tenant_workload(10, seed=11)
    sp = SamplingParams(max_tokens=12)
    with make_router(factory=crash_factory) as router:
        for i, p in enumerate(prompts):
            router.submit(p, sp, request_id=f"k{i}")
        outs = router.drain(timeout_s=60)
        audit_router(router)
        _assert_exact(outs, prompts, sp)
        m = router.metrics
        assert m.replica_crashes.value == 1
        assert m.replica_restarts.value == 1


def test_replica_crash_error_not_absorbed_by_engine_retries():
    """The engine's transient-failure recovery must NOT catch a replica
    crash: step() lets it escape (that is what makes it a replica death
    rather than a step fault)."""
    inj = FaultInjector(stub_factory(), crash_calls=[1],
                        crash_target="decode")
    eng = ServingEngine(inj, num_blocks=20, max_batch_size=2,
                        max_model_len=MAXLEN, max_step_retries=3,
                        retry_backoff_s=0.0)
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=4))
    with pytest.raises(ReplicaCrashError):
        while eng.has_work():
            eng.step()
    assert eng.metrics.step_retries.value == 0


def test_replica_hang_detected_and_restored():
    stalled = []

    def stall_factory(idx):
        runner = stub_factory(idx)
        if idx == 0 and not stalled:
            stalled.append(1)
            return FaultInjector(runner, stall_calls=[3],
                                 stall_target="decode", stall_s=0.8)
        return runner

    prompts = tenant_workload(10, seed=13)
    sp = SamplingParams(max_tokens=12)
    with make_router(factory=stall_factory,
                     heartbeat_timeout_s=0.2) as router:
        for i, p in enumerate(prompts):
            router.submit(p, sp, request_id=f"k{i}")
        outs = router.drain(timeout_s=60)
        audit_router(router)
        _assert_exact(outs, prompts, sp)
        assert router.metrics.replica_hangs.value >= 1
        assert router.metrics.replica_restarts.value >= 1
        # the un-hung zombie thread must stay fenced: give it time to
        # wake and (wrongly) finish its step, then re-audit
        time.sleep(1.0)
        audit_router(router)
        for i, p in enumerate(prompts):
            assert outs[f"k{i}"].output_tokens == oracle(p, sp)


def test_redistribution_spreads_dead_replicas_backlog():
    header = list(range(1, 1 + 2 * BLOCK))
    sp = SamplingParams(max_tokens=6)
    with make_router(replicas=3, max_batch_size=2,
                     enable_prefix_cache=True) as router:
        # pin ALL traffic to one replica via affinity...
        rids = [router.submit(header + [10 + k], sp,
                              request_id=f"k{k}") for k in range(12)]
        home = router._reqs[rids[0]].owner_idx
        assert all(router._reqs[r].owner_idx == home for r in rids)
        # ...then kill it: the supervisor restores from snapshot and
        # redistributes the backlog over the idle siblings
        router.kill_replica(home)
        outs = router.drain(timeout_s=60)
        audit_router(router)
        assert len(outs) == 12
        assert router.metrics.redistributed_requests.value > 0
        owners = {o.replica for o in outs.values()}
        assert len(owners) > 1
        prompts = [header + [10 + k] for k in range(12)]
        _assert_exact(outs, prompts, sp)


# ------------------------------------------------- at-most-once delivery


def test_stale_replay_is_deduplicated():
    """A retired execution re-saying delivered history (stale snapshot
    restore, un-hung zombie) is dropped by the cursor, token by token."""
    with make_router(replicas=1, supervise=False) as router:
        rid = router.submit([1, 2, 3, 4, 5],
                            SamplingParams(max_tokens=6))
        outs = router.drain(timeout_s=30)
        rec = router._reqs[rid]
        before = list(rec.tokens)
        rep = router._replicas[0]
        replay = [TokenEvent(rid, t, i) for i, t in enumerate(before)]
        # a finished record is skipped outright (done wins over cursor)
        with rep.lock:
            router._deliver(rep, rep.epoch, replay)
        assert rec.tokens == before
        assert router.metrics.duplicate_tokens_dropped.value == 0
        # re-arm the record as in-flight: the cursor now drops the
        # replayed history token by token
        rec.done = False
        with rep.lock:
            router._deliver(rep, rep.epoch, replay)
        rec.done = True
        assert rec.tokens == before
        assert router.metrics.duplicate_tokens_dropped.value == len(before)
        # a fenced replica delivers NOTHING, novel or not
        rep.fenced = True
        with rep.lock:
            router._deliver(rep, rep.epoch,
                            [TokenEvent(rid, 9, len(before))])
        assert rec.tokens == before


def test_abort_through_router():
    stop = threading.Event()

    def slow_factory(idx):
        return FaultInjector(stub_factory(idx), stall_every=1,
                             stall_target="both",
                             on_stall=lambda: stop.wait(0.03))

    with make_router(factory=slow_factory, supervise=False) as router:
        rid = router.submit([1, 2, 3], SamplingParams(max_tokens=50))
        assert router.abort(rid)
        stop.set()
        outs = router.drain(timeout_s=30)
        assert outs[rid].finish_reason == "aborted"
        assert not router.abort(rid)       # already finished
        assert not router.abort("nope")


# -------------------------------------------------------- fuzz the tier


def test_tier_backpressure_and_kill_fuzz():
    """Randomized arrivals over small pools and bounded queues, with a
    replica kill mid-trial on odd seeds: every request must end with an
    explicit reason, nothing lost or duplicated, every replica's audit
    green, zero leaked pages after the caches release."""
    for seed in range(6):
        rng = np.random.default_rng(200 + seed)
        with make_router(replicas=int(rng.integers(2, 4)),
                         num_blocks=int(rng.integers(14, 24)),
                         max_batch_size=int(rng.integers(2, 4)),
                         max_queue_depth=int(rng.integers(2, 5)),
                         shed_policy="drop_oldest",
                         enable_prefix_cache=bool(seed % 2),
                         max_prefill_tokens_per_step=(
                             int(rng.integers(4, 12)) if seed % 3 else None),
                         ) as router:
            n = int(rng.integers(8, 16))
            rids = []
            for i in range(n):
                plen = int(rng.integers(2, 12))
                rids.append(router.submit(
                    list(rng.integers(1, VOCAB, plen)),
                    SamplingParams(max_tokens=int(rng.integers(2, 10)))))
                if rng.random() < 0.2:
                    time.sleep(0.002)
            if seed % 2:
                router.kill_replica(int(rng.integers(
                    len(router._replicas))))
            outs = router.drain(timeout_s=60)
            audit_router(router)
            assert set(outs) == set(rids), f"seed {seed}: lost requests"
            assert all(o.finish_reason for o in outs.values())
            for rec in router._reqs.values():
                assert rec.cursor == len(rec.tokens)
            router.release_prefix_caches()
            assert router.check_no_leaks(), f"seed {seed}: leaked pages"


# ----------------------------------------------------- metrics / meshes


def test_metrics_aggregation():
    snaps = [EngineMetrics().snapshot() for _ in range(2)]
    snaps[0]["tokens_generated"] = 10.0
    snaps[1]["tokens_generated"] = 6.0
    snaps[0]["decode_steps"] = 5.0
    snaps[1]["decode_steps"] = 3.0
    snaps[0]["queue_depth_peak"] = 2.0
    snaps[1]["queue_depth_peak"] = 4.0
    agg = aggregate_snapshots(snaps)
    assert agg["tokens_generated"] == 16.0
    assert agg["decode_steps"] == 8.0
    assert agg["queue_depth_peak"] == 4.0     # peaks take the max
    assert agg["steps_per_token"] == 0.5
    assert "ttft_s_p99" not in agg            # percentiles don't merge

    with make_router(supervise=False) as router:
        router.submit([1, 2, 3], SamplingParams(max_tokens=3))
        router.drain(timeout_s=30)
        snap = router.metrics_snapshot()
        assert snap["router"]["requests_completed"] == 1.0
        assert snap["engines"]["tokens_generated"] == 3.0
        assert len(snap["per_replica"]) == 2


def test_replica_submeshes_partition():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    mesh = serving_mesh(data=2, model=2)
    subs = replica_submeshes(mesh)
    assert len(subs) == 2
    for sub in subs:
        assert dict(sub.shape) == {"data": 1, "model": 2}
    all_devs = {d for s in subs for d in np.asarray(s.devices).ravel()}
    assert all_devs == set(np.asarray(mesh.devices).ravel())
    with pytest.raises(ValueError):
        replica_submeshes(serving_mesh(data=1, model=2), data_axis="nope")


def test_router_tp_submeshes_token_exact():
    """2 replicas x tp=2 on a (data=2, model=2) CPU mesh through the
    inference bridge: the data axis finally maps to replicas, and token
    streams stay exact vs the naive oracle."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    from paddle_tpu.inference import create_serving_router
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=2, num_kv_heads=2, max_seq_len=64,
                      dropout=0.0)
    model = Llama(cfg)
    model.eval()
    mesh = serving_mesh(data=2, model=2)
    router = create_serving_router(
        model, replicas=2, mesh=mesh, block_size=8, max_model_len=64,
        num_blocks=16, max_batch_size=2, attn_impl="reference")
    try:
        for rep in router._replicas:
            assert rep.runner.mesh is not None
            assert dict(rep.runner.mesh.shape)["model"] == 2
        rng = np.random.default_rng(0)
        prompts = [list(rng.integers(1, 97, int(rng.integers(4, 10))))
                   for _ in range(4)]
        sp = SamplingParams(max_tokens=4)
        for i, p in enumerate(prompts):
            router.submit(p, sp, request_id=f"m{i}")
        outs = router.drain(timeout_s=300)
        audit_router(router)
        ref_runner = LlamaRunner(model, block_size=8, max_model_len=64,
                                 attn_impl="reference")
        for i, p in enumerate(prompts):
            assert outs[f"m{i}"].output_tokens == naive_generate(
                ref_runner, p, sp, max_model_len=64)
    finally:
        router.shutdown()


# ------------------------------------------- engine migration primitives


def test_inject_request_continues_token_exact():
    sp = SamplingParams(max_tokens=10)
    prompt = [3, 1, 4, 1, 5]
    full = oracle(prompt, sp)
    # generate the first 4 tokens on engine A...
    a = ServingEngine(stub_factory(), num_blocks=20, max_batch_size=2,
                      max_model_len=MAXLEN)
    rid = a.add_request(prompt, sp)
    while len(a._requests[rid].output_tokens) < 4:
        a.step()
    partial = list(a._requests[rid].output_tokens[:4])
    arrival = a._requests[rid].arrival_index
    # ...and continue on engine B from the partial state
    b = ServingEngine(stub_factory(), num_blocks=20, max_batch_size=2,
                      max_model_len=MAXLEN)
    b.inject_request(prompt, sp, request_id=rid, output_tokens=partial,
                     arrival_index=arrival)
    outs = b.run()
    assert outs[rid].output_tokens == full
    with pytest.raises(ValueError):            # duplicate id
        b.inject_request(prompt, sp, request_id=rid)
    with pytest.raises(ValueError):            # over max_model_len
        b.inject_request(list(range(1, 60)),
                         SamplingParams(max_tokens=30))


def test_extract_request_roundtrip_and_running_guard():
    sp = SamplingParams(max_tokens=5)
    eng = ServingEngine(stub_factory(), num_blocks=20, max_batch_size=1,
                        max_model_len=MAXLEN)
    r1 = eng.add_request([1, 2, 3], sp)
    r2 = eng.add_request([4, 5, 6], sp)        # waits behind r1
    eng.step()
    with pytest.raises(ValueError):
        eng.extract_request(r1)                # RUNNING holds pages
    state = eng.extract_request(r2)
    assert state["prompt_tokens"] == [4, 5, 6]
    assert r2 not in eng._requests
    with pytest.raises(KeyError):
        eng.extract_request(r2)
    other = ServingEngine(stub_factory(), num_blocks=20, max_batch_size=1,
                          max_model_len=MAXLEN)
    other.inject_request(state["prompt_tokens"], state["sampling"],
                         request_id=state["request_id"],
                         output_tokens=state["output_tokens"],
                         arrival_index=state["arrival_index"])
    outs = other.run()
    assert outs[r2].output_tokens == oracle([4, 5, 6], sp)
    eng.run()                                  # r1 unaffected


# ------------------------------------------------------ tokenizer shim


class _HFByteLevelStub:
    """HF-style byte-level BPE stub: no id_to_bytes, only decode /
    convert_ids_to_tokens returning strings over the bytes_to_unicode
    alphabet — exactly the GPT-2 tokenizer surface."""

    def __init__(self, table):
        # table: tok id -> raw bytes; spelled in the unicode alphabet
        from paddle_tpu.serving.detokenize import _byte_decoder

        enc = {b: c for c, b in _byte_decoder().items()}
        self._pieces = {t: "".join(enc[b] for b in bs)
                        for t, bs in table.items()}

    def convert_ids_to_tokens(self, tok):
        return self._pieces[int(tok)]

    def decode(self, ids):
        from paddle_tpu.serving.detokenize import _byte_decoder

        dec = _byte_decoder()
        return b"".join(
            bytes(dec[c] for c in self._pieces[int(t)])
            for t in ids).decode("utf-8", errors="replace")


def test_tokenizer_adapter_byte_level_split_character():
    # "→" is e2 86 92; split its bytes across two tokens — a naive
    # per-token decode() would emit replacement characters
    stub = _HFByteLevelStub({1: b"ok ", 2: b"\xe2\x86", 3: b"\x92",
                             4: b"!"})
    assert not hasattr(stub, "id_to_bytes")
    adapted = TokenizerAdapter.wrap(stub)
    assert adapted.id_to_bytes(2) == b"\xe2\x86"
    d = StreamDetokenizer(stub)               # auto-wraps
    assert d.push(1) == "ok "
    assert d.push(2) == ""                    # buffered: incomplete UTF-8
    assert d.push(3) == "→"
    assert d.push(4) == "!"
    assert d.text == "ok →!"
    # objects that already speak bytes pass through unwrapped
    class Raw:
        def id_to_bytes(self, t):
            return b"x"
    raw = Raw()
    assert TokenizerAdapter.wrap(raw) is raw
    # sentencepiece-style pieces map the word marker to a space
    class SP:
        def convert_ids_to_tokens(self, t):
            return "▁hi"
    assert TokenizerAdapter.wrap(SP()).id_to_bytes(0) == b" hi"


def test_engine_stream_text_with_hf_style_tokenizer():
    table = {t: f"<{t}>".encode() for t in range(VOCAB)}
    stub = _HFByteLevelStub(table)
    eng = ServingEngine(stub_factory(), num_blocks=20, max_batch_size=2,
                        max_model_len=MAXLEN, tokenizer=stub)
    rid = eng.add_request([1, 2, 3], SamplingParams(max_tokens=5))
    eng.run()
    toks = eng._requests[rid].output_tokens
    assert eng.stream_text(rid) == "".join(f"<{t}>" for t in toks)
