"""Multi-step decode (ISSUE 6): the device-resident sampling loop that
kills the per-token host round-trip.

Contract mirrored from PRs 3-5: `decode_horizon=s` is a pure
transfer-count optimization, never a sampling change — a pure-greedy
decode batch runs s device steps per ONE host sync (runner.decode_multi,
a lax.scan feeding each argmax token back on device) and must stay
token-for-token identical to `naive_generate`, including stop-condition
overshoot rollback, deadlines, aborts, fault-injected retries, and
kill-and-restore mid-horizon — all under the invariant auditor. The
satellite pins ride along: greedy_grid now drains ONE packed transfer
(not two), the s=1 path performs exactly one blocking sync per sampled
token, and `host_syncs` <= ceil(tokens/s) + prefill_steps on a
pure-greedy workload with a >= 4x syncs-per-token drop at s=8.
"""

import json
import math

import numpy as np
import pytest

from _helpers import PeriodicStubRunner, StubPagedRunner
from paddle_tpu.serving import (
    FaultInjector, SamplingParams, ServingEngine, naive_generate,
)
from paddle_tpu.serving import engine as engine_mod


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    """Every multi-step test runs under the invariant auditor — the
    horizon page pre-commit/reclaim guarantees are checked post-step."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


def _drain(eng, pending=None, rng=None):
    work = []
    pending = list(pending or [])
    while pending or eng.has_work():
        if pending:
            n = 1 if rng is None else int(rng.integers(0, 3))
            for _ in range(n):
                if pending:
                    p, sp = pending.pop(0)
                    work.append((eng.add_request(p, sp), p, sp))
        eng.step()
    return work


# ----------------------------------------------------------- unit: knob


def test_decode_horizon_knob_validation():
    with pytest.raises(ValueError):
        ServingEngine(StubPagedRunner(), num_blocks=8, decode_horizon=0)


def test_snapshot_roundtrips_decode_horizon():
    eng = ServingEngine(StubPagedRunner(), num_blocks=20, decode_horizon=6)
    state = json.loads(json.dumps(eng.snapshot()))
    assert state["config"]["decode_horizon"] == 6
    eng2 = ServingEngine.restore(StubPagedRunner(), state)
    assert eng2.decode_horizon == 6


# ------------------------------------------- satellite: one-sync drains


def _count_to_host(monkeypatch):
    calls = {"n": 0}
    real = engine_mod._to_host

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(engine_mod, "_to_host", counting)
    return calls


def test_greedy_grid_is_one_transfer(monkeypatch):
    """ISSUE 6 satellite: the argmax ids and finite flags ride ONE
    packed pull (this used to be two separate np.asarray syncs), and
    tie-breaking still matches np.argmax."""
    import jax.numpy as jnp

    calls = _count_to_host(monkeypatch)
    rows = np.zeros((3, 7), np.float32)
    rows[0, 2] = rows[0, 5] = 1.0          # tie: first max must win
    rows[1, 6] = 3.0
    rows[2, 1] = np.nan
    am, fin = engine_mod.greedy_grid(jnp.asarray(rows))
    assert calls["n"] == 1
    assert list(am) == [int(np.argmax(r)) for r in rows]
    assert list(fin) == [True, True, False]


def test_one_host_sync_per_sampled_token_on_s1(monkeypatch):
    """The s=1 pin: a pure-greedy single-request run blocks on the
    device exactly once per sampled token (one prefill sample + one
    per decode step), counted both at the _to_host funnel and in the
    host_syncs metric."""
    calls = _count_to_host(monkeypatch)
    eng = ServingEngine(StubPagedRunner(block_size=4, max_model_len=64),
                        num_blocks=20, max_batch_size=2, max_model_len=64)
    eng.add_request([3, 1, 4, 1, 5], SamplingParams(max_tokens=9))
    while eng.has_work():
        eng.step()
    m = eng.metrics.snapshot()
    assert m["tokens_generated"] == 9
    assert m["host_syncs"] == calls["n"] == 9
    assert m["host_syncs_per_token"] == 1.0


def test_multi_step_one_sync_per_horizon(monkeypatch):
    """With decode_horizon=s the same workload drains one transfer per
    HORIZON: 1 prefill sample (token 1) + 1 per-step decode in the
    admission step (chunks in flight there, token 2) + ceil(7/4) = 2
    horizon drains for the remaining 7 tokens — 4 total, vs 9 at s=1."""
    calls = _count_to_host(monkeypatch)
    eng = ServingEngine(StubPagedRunner(block_size=4, max_model_len=64),
                        num_blocks=20, max_batch_size=2, max_model_len=64,
                        decode_horizon=4)
    eng.add_request([3, 1, 4, 1, 5], SamplingParams(max_tokens=9))
    while eng.has_work():
        eng.step()
    m = eng.metrics.snapshot()
    assert m["tokens_generated"] == 9
    assert m["host_syncs"] == calls["n"] == 2 + math.ceil(7 / 4)
    assert m["decode_horizon_steps"] == 7


# ----------------------------------------------- exactness + fallbacks


def test_multi_step_matches_per_step_and_naive():
    """Same workload at s=1 and s=5: identical streams, both equal to
    the sequential oracle."""
    outs = {}
    for s in (1, 5):
        runner = StubPagedRunner(block_size=4, max_model_len=64)
        eng = ServingEngine(runner, num_blocks=30, max_batch_size=3,
                            max_model_len=64, decode_horizon=s)
        rng = np.random.default_rng(11)
        pending = [(list(map(int, rng.integers(0, 31,
                                               int(rng.integers(2, 9))))),
                    SamplingParams(max_tokens=int(rng.integers(2, 14))))
                   for _ in range(6)]
        work = _drain(eng, pending)
        outs[s] = {rid: eng.outputs()[rid].output_tokens
                   for rid, _, _ in work}
        assert eng.pool.allocator.check_no_leaks()
        if s == 5:
            for rid, p, sp in work:
                assert outs[s][rid] == naive_generate(
                    runner, p, sp, max_model_len=64)
    assert list(outs[1].values()) == list(outs[5].values())


def test_stop_token_mid_horizon_rolls_back_overshoot():
    """A stop token landing mid-horizon discards the drained tail and
    reclaims its pre-committed pages (the 'mirrors speculative
    rollback' clause) — token-exact vs naive, zero leaks."""
    runner = StubPagedRunner(block_size=4, max_model_len=64)
    sp = SamplingParams(max_tokens=24)
    ref = naive_generate(runner, [5, 9], sp, max_model_len=64)
    stop = ref[3]                      # force a stop on the 4th token
    sp_stop = SamplingParams(max_tokens=24, stop_token_ids=(int(stop),))
    eng = ServingEngine(runner, num_blocks=30, max_batch_size=2,
                        max_model_len=64, decode_horizon=8)
    rid = eng.add_request([5, 9], sp_stop)
    while eng.has_work():
        eng.step()
    out = eng.outputs()[rid]
    assert out.finish_reason == "stop"
    assert out.output_tokens == naive_generate(runner, [5, 9], sp_stop,
                                               max_model_len=64)
    m = eng.metrics.snapshot()
    assert m["horizon_overshoot_tokens"] > 0
    assert eng.pool.allocator.check_no_leaks()


def test_temperature_request_falls_back_to_per_step():
    """A temperature > 0 request in the batch disables the horizon (its
    [V] rows must reach the host) — streams still match naive."""
    runner = StubPagedRunner(block_size=4, max_model_len=64)
    eng = ServingEngine(runner, num_blocks=30, max_batch_size=2,
                        max_model_len=64, decode_horizon=8)
    work = [(eng.add_request([2, 3, 4], sp), [2, 3, 4], sp) for sp in
            (SamplingParams(max_tokens=8),
             SamplingParams(max_tokens=8, temperature=0.7, seed=5))]
    while eng.has_work():
        eng.step()
    assert eng.metrics.snapshot()["decode_horizon_steps"] == 0
    for rid, p, sp in work:
        assert eng.outputs()[rid].output_tokens == naive_generate(
            runner, p, sp, max_model_len=64)


def test_chunks_in_flight_fall_back_then_horizon_resumes():
    """While chunked prefill is feeding a long prompt the step takes the
    per-step path (completing chunks sample host-side); once the batch
    is chunk-free the horizon engages. Token-exact either way."""
    runner = StubPagedRunner(block_size=4, max_model_len=64)
    eng = ServingEngine(runner, num_blocks=40, max_batch_size=2,
                        max_model_len=64, decode_horizon=4,
                        max_prefill_tokens_per_step=4)
    sp = SamplingParams(max_tokens=10)
    r0 = eng.add_request(list(range(1, 21)), sp)     # 5 chunks of 4
    while eng.has_work():
        eng.step()
    m = eng.metrics.snapshot()
    assert m["prefill_chunks"] >= 5
    assert m["decode_horizon_steps"] > 0
    assert eng.outputs()[r0].output_tokens == naive_generate(
        runner, list(range(1, 21)), sp, max_model_len=64)


def test_plan_decode_horizon_trims_never_preempts():
    """Scheduler unit: under pool pressure the horizon shrinks instead
    of evicting anyone — preemption stays reserve_decode()'s business."""
    runner = StubPagedRunner(block_size=4, max_model_len=28)
    # 7 usable pages, two requests: tight but decodable
    eng = ServingEngine(runner, num_blocks=8, max_batch_size=2,
                        max_model_len=28, decode_horizon=8)
    sp = SamplingParams(max_tokens=20)
    for p in ([1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13]):
        eng.add_request(p, sp)
    eng.step()                                   # admit + prefill both
    sched = eng.scheduler
    for _ in sched.reserve_decode():
        pass
    before = [r.num_preemptions for r in sched.running]
    s = sched.plan_decode_horizon(8)
    assert 1 <= s < 8, f"tight pool must trim the horizon (got {s})"
    assert [r.num_preemptions for r in sched.running] == before
    for r in sched.decode_ready():               # pages really committed
        assert r.kv.pages_short(s) == 0


def test_horizon_engine_under_pool_pressure_token_exact():
    """End-to-end with a pool too small for the full horizon: trims and
    preemption churn still reproduce the oracle."""
    runner = StubPagedRunner(block_size=4, max_model_len=40)
    eng = ServingEngine(runner, num_blocks=11, max_batch_size=3,
                        max_model_len=40, decode_horizon=8)
    rng = np.random.default_rng(3)
    pending = [(list(map(int, rng.integers(0, 31,
                                           int(rng.integers(2, 8))))),
                SamplingParams(max_tokens=int(rng.integers(4, 12))))
               for _ in range(6)]
    work = _drain(eng, pending)
    for rid, p, sp in work:
        assert eng.outputs()[rid].output_tokens == naive_generate(
            runner, p, sp, max_model_len=40), rid
    assert eng.pool.allocator.check_no_leaks()


# --------------------------------------------------- faults mid-horizon


def test_fault_injected_decode_multi_retries_exactly():
    """Injected device errors on the decode op schedule hit the
    decode_multi launch; bounded-backoff retries must be invisible in
    the token streams (a failed attempt never half-commits a horizon)."""
    runner = StubPagedRunner(block_size=4, max_model_len=64)
    inj = FaultInjector(runner, error_every=3, error_target="decode")
    eng = ServingEngine(inj, num_blocks=30, max_batch_size=2,
                        max_model_len=64, decode_horizon=4,
                        retry_backoff_s=0.0, sleep_fn=lambda _t: None)
    rng = np.random.default_rng(4)
    pending = [(list(map(int, rng.integers(0, 31, 5))),
                SamplingParams(max_tokens=12)) for _ in range(4)]
    work = _drain(eng, pending)
    m = eng.metrics.snapshot()
    assert m["step_retries"] > 0 and m["decode_horizon_steps"] > 0
    for rid, p, sp in work:
        assert eng.outputs()[rid].finish_reason == "length"
        assert eng.outputs()[rid].output_tokens == naive_generate(
            runner, p, sp, max_model_len=64)
    assert eng.pool.allocator.check_no_leaks()


def test_nan_mid_horizon_abort_policy():
    """Flags dropped by the injector = non-finite logits surfacing
    inside the device loop: nan_policy='abort' ends the requests with
    finish_reason='error' and reclaims every pre-committed page."""
    runner = StubPagedRunner(block_size=4, max_model_len=64)
    inj = FaultInjector(runner, nan_calls=(2,), nan_target="decode")
    eng = ServingEngine(inj, num_blocks=30, max_batch_size=2,
                        max_model_len=64, decode_horizon=4)
    rid = eng.add_request([1, 2, 3], SamplingParams(max_tokens=12))
    while eng.has_work():
        eng.step()
    out = eng.outputs()[rid]
    assert out.finish_reason == "error"
    assert eng.metrics.snapshot()["nan_logit_events"] > 0
    assert eng.pool.allocator.check_no_leaks()


def test_nan_mid_horizon_greedy_defers_and_recovers():
    """nan_policy='greedy': the horizon can't rescue without the [V]
    row, so it rolls back its tail and defers ONE per-step decode that
    refetches real logits — a transient injected NaN therefore costs a
    step, never a token."""
    runner = StubPagedRunner(block_size=4, max_model_len=64)
    inj = FaultInjector(runner, nan_calls=(2,), nan_target="decode")
    eng = ServingEngine(inj, num_blocks=30, max_batch_size=2,
                        max_model_len=64, decode_horizon=4,
                        nan_policy="greedy")
    sp = SamplingParams(max_tokens=12)
    rid = eng.add_request([1, 2, 3], sp)
    while eng.has_work():
        eng.step()
    out = eng.outputs()[rid]
    assert out.finish_reason == "length"
    assert out.output_tokens == naive_generate(runner, [1, 2, 3], sp,
                                               max_model_len=64)
    assert eng.metrics.snapshot()["nan_logit_events"] > 0
    assert eng.pool.allocator.check_no_leaks()


# ------------------------------------------------------ host-sync pins


def test_host_syncs_pin_pure_greedy():
    """ISSUE 6 satellite pin: host_syncs <= ceil(tokens/s) +
    prefill_steps on a pure-greedy workload, for every horizon."""
    for s in (1, 4, 8):
        runner = StubPagedRunner(block_size=4, max_model_len=64)
        eng = ServingEngine(runner, num_blocks=40, max_batch_size=3,
                            max_model_len=64, decode_horizon=s)
        for i in range(3):
            eng.add_request([1 + i, 2, 3, 4], SamplingParams(max_tokens=32))
        while eng.has_work():
            eng.step()
        m = eng.metrics.snapshot()
        assert m["tokens_generated"] == 96
        bound = math.ceil(m["tokens_generated"] / s) + m["prefill_chunks"]
        assert m["host_syncs"] <= bound, (s, m["host_syncs"], bound)
    assert eng.pool.allocator.check_no_leaks()


def test_syncs_per_token_drops_4x_at_horizon_8():
    """The acceptance-criteria ratio, measured engine-side on CPU: s=8
    must cut blocking syncs per generated token >= 4x vs s=1."""
    spt = {}
    for s in (1, 8):
        runner = StubPagedRunner(block_size=4, max_model_len=64)
        eng = ServingEngine(runner, num_blocks=40, max_batch_size=2,
                            max_model_len=64, decode_horizon=s)
        for i in range(2):
            eng.add_request([i + 1, 2, 3, 4], SamplingParams(max_tokens=40))
        while eng.has_work():
            eng.step()
        spt[s] = eng.metrics.ratio("host_syncs_per_token")
    assert spt[1] / spt[8] >= 4.0, spt


# ------------------------------------------------------------------ fuzz


@pytest.mark.slow
def test_fuzz_multistep_oracle_equivalence():
    """ISSUE 6 acceptance: 200 seeded trials of random horizons (1-8),
    pool sizes, budgets, stop tokens mid-horizon, immediate deadlines,
    mid-run aborts, fault-injected decode_multi retries, and
    kill-and-restore mid-horizon — with the auditor armed on every
    step, every cleanly-finished request must equal the naive oracle
    token-for-token (interrupted ones must be an exact prefix), with
    zero page/slot leaks, and the totals must prove the interesting
    paths (horizons, overshoot rollback, retries, restores) ran."""
    tot_hsteps = tot_overshoot = tot_retries = tot_restores = 0
    for trial in range(200):
        wl = np.random.default_rng(9000 + trial)
        block_size = int(wl.integers(2, 5))
        num_blocks = int(wl.integers(7, 16))
        max_batch = int(wl.integers(1, 5))
        max_model_len = (num_blocks - 1) * block_size
        stub_kw = dict(vocab_size=31, block_size=block_size,
                       max_model_len=max_model_len)
        runner = (PeriodicStubRunner(period=int(wl.integers(2, 5)),
                                     **stub_kw)
                  if trial % 3 == 0 else StubPagedRunner(**stub_kw))
        inject = trial % 4 == 0
        target = (FaultInjector(runner, error_every=int(wl.integers(3, 9)),
                                error_target="decode") if inject else runner)
        horizon = int(wl.integers(1, 9))
        budget = (None if int(wl.integers(0, 3)) == 0
                  else int(wl.integers(2, 9)))
        kw = dict(num_blocks=num_blocks, max_batch_size=max_batch,
                  max_model_len=max_model_len, decode_horizon=horizon,
                  max_prefill_tokens_per_step=budget,
                  enable_prefix_cache=bool(wl.integers(0, 2)),
                  retry_backoff_s=0.0)
        eng = ServingEngine(target, sleep_fn=lambda _t: None, **kw)
        assert eng.audit, "fuzz must run under the invariant auditor"
        n_req = int(wl.integers(2, 8))
        pending = []
        for i in range(n_req):
            plen = int(wl.integers(2, min(12, max_model_len - 2) + 1))
            p = list(map(int, wl.integers(0, 31, plen)))
            mt = int(wl.integers(1, min(10, max_model_len - plen) + 1))
            stops = (tuple(map(int, wl.integers(0, 31, 2)))
                     if int(wl.integers(0, 3)) == 0 else ())
            timeout = 1e-9 if int(wl.integers(0, 12)) == 0 else None
            pending.append((p, SamplingParams(max_tokens=mt,
                                              stop_token_ids=stops,
                                              timeout_s=timeout)))
        restore_at = (int(wl.integers(1, 8))
                      if int(wl.integers(0, 4)) == 0 else None)
        abort_at = (int(wl.integers(1, 8))
                    if int(wl.integers(0, 6)) == 0 else None)
        work, steps, aborted = [], 0, set()
        while pending or eng.has_work():
            for _ in range(int(wl.integers(0, 3))):
                if pending:
                    p, sp = pending.pop(0)
                    work.append((eng.add_request(
                        p, sp, request_id=f"t{trial}-r{len(work)}"), p, sp))
            eng.step()
            steps += 1
            if abort_at is not None and steps == abort_at:
                live = [r for r, _, _ in work
                        if r in eng._requests and not eng._requests[r].done]
                if live:
                    victim = live[int(wl.integers(0, len(live)))]
                    eng.abort(victim)
                    aborted.add(victim)
            if restore_at is not None and steps == restore_at:
                state = json.loads(json.dumps(eng.snapshot()))
                eng = ServingEngine.restore(
                    target, state, sleep_fn=lambda _t: None)
                tot_restores += 1
                restore_at = None
        outs = eng.outputs()
        assert len(outs) == len(work), f"trial {trial}: lost requests"
        eng.release_prefix_cache()
        assert eng.pool.allocator.check_no_leaks(), \
            f"trial {trial}: leaked pages"
        assert sorted(eng.scheduler._free_slots) == list(range(max_batch)), \
            f"trial {trial}: leaked slots"
        m = eng.metrics.snapshot()
        tot_hsteps += m["decode_horizon_steps"]
        tot_overshoot += m["horizon_overshoot_tokens"]
        tot_retries += m["step_retries"]
        for rid, p, sp in work:
            ref = naive_generate(runner, p, sp,
                                 max_model_len=max_model_len)
            got = outs[rid].output_tokens
            if outs[rid].finish_reason in ("stop", "length"):
                assert got == ref, \
                    f"trial {trial}: {rid} diverged from the oracle"
            else:           # timeout / abort: an exact oracle prefix
                assert got == ref[:len(got)], \
                    f"trial {trial}: {rid} interrupted stream diverged"
    assert tot_hsteps > 0, "fuzz never ran a device-resident horizon"
    assert tot_overshoot > 0, "fuzz never rolled back horizon overshoot"
    assert tot_retries > 0, "fuzz never retried a faulted decode_multi"
    assert tot_restores > 0, "fuzz never killed and restored mid-run"


# ------------------------------------------------------ real-model pin


@pytest.mark.slow
def test_real_llama_decode_multi_matches_naive():
    """End-to-end on the real jitted runner: GQA Llama, prefix cache,
    decode_horizon=8 — bit-exact vs the sequential oracle (the lax.scan
    argmax feedback chain reproduces per-step greedy exactly)."""
    import paddle_tpu as paddle
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
                        max_model_len=64, decode_horizon=8,
                        enable_prefix_cache=True)
    rng = np.random.default_rng(7)
    work = []
    for i in range(4):
        prompt = list(map(int, rng.integers(1, 97,
                                            int(rng.integers(4, 12)))))
        sp = SamplingParams(max_tokens=int(rng.integers(4, 9)))
        work.append((eng.add_request(prompt, sp, request_id=f"r{i}"),
                     prompt, sp))
    outs = eng.run()
    assert eng.metrics.snapshot()["decode_horizon_steps"] > 0
    for rid, prompt, sp in work:
        assert outs[rid].output_tokens == naive_generate(
            runner, prompt, sp, max_model_len=64), rid
    eng.release_prefix_cache()
    assert eng.pool.allocator.check_no_leaks()
