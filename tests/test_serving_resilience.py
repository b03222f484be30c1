"""Fault-tolerant serving (ISSUE 2): deadlines, abort, backpressure,
transient-failure recovery, NaN guards, crash-safe snapshot/restore, and
the invariant auditor. Every failure mode must have a defined, tested
outcome — no unhandled exception ever escapes engine.step().

Most tests drive the numpy StubPagedRunner (fast, history-faithful via
the real KV pool + block tables); the two ISSUE acceptance pins —
kill-mid-workload-and-restore and the 1-in-5 decode-fault workload —
run on the real Llama runner against the naive_generate oracle.
"""

import json

import numpy as np
import pytest

import paddle_tpu as paddle
from _helpers import StubPagedRunner
from paddle_tpu.serving import (
    EngineMetrics, FaultInjector, InjectedDeviceError, InvariantViolation,
    QueueFullError, SamplingParams, ServingEngine, audit_engine,
    naive_generate,
)

rng = np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _audit_every_engine(monkeypatch):
    """ISSUE-2 contract: the invariant auditor runs under every serving
    test (engines pick it up via the env default)."""
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")


def _stub_engine(num_blocks=16, block_size=4, max_batch=4, max_model_len=32,
                 clock=None, **kw):
    runner = StubPagedRunner(vocab_size=31, block_size=block_size,
                             max_model_len=max_model_len)
    metrics = EngineMetrics(clock=clock) if clock is not None else None
    return ServingEngine(runner, num_blocks=num_blocks,
                         max_batch_size=max_batch,
                         max_model_len=max_model_len, metrics=metrics, **kw)


@pytest.fixture(scope="module")
def llama_setup():
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LlamaRunner

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=2, num_kv_heads=1, max_seq_len=64,
                      dropout=0.0)
    model = Llama(cfg)
    model.eval()

    def make_runner():
        return LlamaRunner(model, block_size=8, max_model_len=64,
                           attn_impl="reference")

    return make_runner


# ------------------------------------------------------ deadlines / abort


def test_timeout_expires_waiting_and_running():
    t = [0.0]
    eng = _stub_engine(max_batch=1, clock=lambda: t[0])
    r1 = eng.add_request([1, 2, 3], SamplingParams(max_tokens=20,
                                                   timeout_s=5.0))
    r2 = eng.add_request([4, 5], SamplingParams(max_tokens=20,
                                                timeout_s=5.0))
    eng.step()                     # r1 admitted+running, r2 waiting
    assert len(eng.scheduler.running) == 1
    t[0] = 6.0                     # past both deadlines
    eng.step()
    outs = eng.outputs()
    assert outs[r1].finish_reason == "timeout"
    assert outs[r2].finish_reason == "timeout"
    assert outs[r1].output_tokens          # partial generation surfaced
    assert outs[r2].output_tokens == []    # never admitted
    assert outs[r2].ttft_s is None
    assert not eng.has_work()
    assert eng.pool.allocator.check_no_leaks()
    assert eng.metrics.requests_timed_out.value == 2
    assert eng.metrics.snapshot()["requests_timed_out"] == 2


def test_abort_waiting_and_running_requests():
    eng = _stub_engine(max_batch=1)
    r1 = eng.add_request([1, 2, 3], SamplingParams(max_tokens=20))
    r2 = eng.add_request([4, 5], SamplingParams(max_tokens=20))
    eng.step()
    assert eng.abort(r1)                     # running: frees pages + slot
    assert eng.abort(r2)                     # waiting: dequeued
    assert eng.abort(r1) is False            # already finished
    assert eng.abort("no-such-request") is False
    outs = eng.outputs()
    assert outs[r1].finish_reason == "aborted"
    assert outs[r2].finish_reason == "aborted"
    assert not eng.has_work()
    assert eng.pool.allocator.check_no_leaks()
    assert eng.metrics.requests_aborted.value == 2


# ------------------------------------------------------------ backpressure


def test_bounded_queue_reject_policy():
    eng = _stub_engine(max_queue_depth=2, shed_policy="reject")
    eng.add_request([1], SamplingParams(max_tokens=2))
    eng.add_request([2], SamplingParams(max_tokens=2))
    with pytest.raises(QueueFullError):
        eng.add_request([3], SamplingParams(max_tokens=2))
    assert eng.metrics.shed_requests.value == 1
    outs = eng.run()
    assert len(outs) == 2 and all(o.finish_reason == "length"
                                  for o in outs.values())


def test_bounded_queue_drop_oldest_policy():
    eng = _stub_engine(max_queue_depth=2, shed_policy="drop_oldest")
    r1 = eng.add_request([1], SamplingParams(max_tokens=2))
    r2 = eng.add_request([2], SamplingParams(max_tokens=2))
    r3 = eng.add_request([3], SamplingParams(max_tokens=2))  # sheds r1
    outs = eng.run()
    assert outs[r1].finish_reason == "shed"
    assert outs[r1].output_tokens == []
    assert outs[r2].finish_reason == "length"
    assert outs[r3].finish_reason == "length"
    assert eng.metrics.shed_requests.value == 1
    assert eng.pool.allocator.check_no_leaks()


def test_admission_watermark_paces_admission():
    # 16 usable pages, watermark 0.5 -> at most 8 pages admitted at once;
    # each 3-token prompt needs 2 pages (context+1 = 4 tokens / bs 2)
    eng = _stub_engine(num_blocks=17, block_size=2, max_batch=8,
                       max_model_len=16, admission_watermark=0.5)
    for i in range(6):
        eng.add_request([1, 2, 3], SamplingParams(max_tokens=5))
    eng.step()
    assert len(eng.scheduler.running) == 4          # 4 x 2 pages = watermark
    assert eng.scheduler.queue_depth == 2
    used = eng.pool.allocator.num_usable - eng.pool.allocator.num_free
    assert used <= 8
    outs = eng.run()                                 # still drains fully
    assert len(outs) == 6
    assert eng.pool.allocator.check_no_leaks()


def test_watermark_progress_guarantee():
    # a request larger than the watermark still runs when the pool is idle
    eng = _stub_engine(num_blocks=17, block_size=2, max_batch=2,
                       max_model_len=16, admission_watermark=0.1)
    rid = eng.add_request(list(range(1, 10)), SamplingParams(max_tokens=3))
    outs = eng.run()
    assert outs[rid].finish_reason == "length"


# -------------------------------------------------- transient-step faults


@pytest.mark.slow
def test_decode_fault_one_in_five_full_workload(llama_setup):
    """ISSUE-2 acceptance: FaultInjector raising on 1-in-5 decode calls, a
    16-request workload completes with zero page/slot leaks and every
    request ends with an explicit finish_reason; retries are exact, so
    tokens still match the fault-free oracle."""
    runner = llama_setup()
    faulty = FaultInjector(runner, error_every=5, error_target="decode")
    eng = ServingEngine(faulty, num_blocks=10, max_batch_size=4,
                        max_model_len=64, max_step_retries=2,
                        retry_backoff_s=0.001)
    wl = np.random.default_rng(7)
    work = []
    for i in range(16):
        p = list(wl.integers(1, 97, int(wl.integers(3, 25))))
        sp = SamplingParams(max_tokens=int(wl.integers(2, 11)))
        work.append((eng.add_request(p, sp), p, sp))
    outs = eng.run()                      # no exception may escape step()
    assert len(outs) == 16
    assert faulty.injected["error"] >= 1
    assert eng.metrics.step_retries.value >= 1
    for rid, p, sp in work:
        assert outs[rid].finish_reason == "length"
        assert outs[rid].output_tokens == naive_generate(
            runner, p, sp, max_model_len=64)
    assert eng.pool.allocator.check_no_leaks()
    assert sorted(eng.scheduler._free_slots) == list(range(4))


def test_persistent_decode_fault_quarantines_with_explicit_reason():
    runner = StubPagedRunner(block_size=4, max_model_len=32)
    faulty = FaultInjector(runner, error_every=1, error_target="decode")
    eng = ServingEngine(faulty, num_blocks=16, max_batch_size=4,
                        max_model_len=32, max_step_retries=1,
                        retry_backoff_s=0.0)
    ids = [eng.add_request([i + 1, i + 2], SamplingParams(max_tokens=4))
           for i in range(3)]
    outs = eng.run()
    assert len(outs) == 3
    for rid in ids:
        assert outs[rid].finish_reason == "error"
        assert len(outs[rid].output_tokens) == 1   # prefill token survived
    assert eng.pool.allocator.check_no_leaks()
    assert eng.metrics.requests_aborted.value == 3


def test_persistent_prefill_fault_quarantines_request():
    runner = StubPagedRunner(block_size=4, max_model_len=32)
    faulty = FaultInjector(runner, error_every=1, error_target="prefill")
    eng = ServingEngine(faulty, num_blocks=16, max_batch_size=2,
                        max_model_len=32, max_step_retries=2,
                        retry_backoff_s=0.0)
    ids = [eng.add_request([7, 8, 9], SamplingParams(max_tokens=4))
           for _ in range(2)]
    outs = eng.run()
    for rid in ids:
        assert outs[rid].finish_reason == "error"
        assert outs[rid].output_tokens == []
    assert eng.pool.allocator.check_no_leaks()
    # 2 retries per attempt, per request
    assert eng.metrics.step_retries.value == 4


def test_transient_prefill_fault_recovers_exactly():
    runner = StubPagedRunner(block_size=4, max_model_len=32)
    faulty = FaultInjector(runner, error_calls=(1,), error_target="prefill")
    eng = ServingEngine(faulty, num_blocks=16, max_batch_size=2,
                        max_model_len=32, max_step_retries=2,
                        retry_backoff_s=0.0)
    sp = SamplingParams(max_tokens=4)
    rid = eng.add_request([5, 6, 7], sp)
    outs = eng.run()
    assert outs[rid].finish_reason == "length"
    assert outs[rid].output_tokens == naive_generate(runner, [5, 6, 7], sp,
                                                     max_model_len=32)
    assert eng.metrics.step_retries.value == 1


# ------------------------------- what a retry cannot cure leaves the engine


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_first_call_failure_of_a_new_jit_entry_leaves_the_engine(
        llama_setup, kind):
    """A step that cannot be traced/compiled is not a transient device
    fault: the first call of a newly built jit-cache entry raises
    StepCompileError through the retry loops, instead of every request
    being answered with "error"."""
    from paddle_tpu.serving.model_runner import StepCompileError

    runner = llama_setup()

    def refused(*a, **kw):
        raise NotImplementedError("the compiler refuses this step")

    setattr(runner, f"_{kind}_step", refused)
    eng = ServingEngine(runner, num_blocks=10, max_batch_size=2,
                        max_model_len=64, retry_backoff_s=0.0)
    eng.add_request([3, 1, 4, 1, 5], SamplingParams(max_tokens=4))
    with pytest.raises(StepCompileError, match="compiler refuses"):
        eng.run()
    assert eng.metrics.step_retries.value == 0
    assert not eng.outputs()           # nobody was told "error"
    assert not any(k[0] == kind for k in runner._jit_cache)


def test_warm_step_fault_is_still_retried(llama_setup):
    """The same exception from an entry that has already run once is a
    device fault as before: retried, token-exact."""
    runner = llama_setup()
    eng = ServingEngine(runner, num_blocks=10, max_batch_size=2,
                        max_model_len=64, retry_backoff_s=0.0)
    sp = SamplingParams(max_tokens=5)
    rid = eng.add_request([3, 1, 4, 1, 5], sp)
    eng.step()                          # prefill
    eng.step()                          # first decode: entry is warm now
    key = ("decode", 2)
    warm = runner._jit_cache[key]
    calls = []

    def flaky(*a):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("transient device fault")
        return warm(*a)

    runner._jit_cache[key] = flaky
    outs = eng.run()
    assert eng.metrics.step_retries.value == 1
    assert outs[rid].finish_reason == "length"
    assert outs[rid].output_tokens == naive_generate(
        runner, [3, 1, 4, 1, 5], sp, max_model_len=64)


def test_retry_never_resubmits_donated_pools():
    """On a TPU the pools are donated to the launch. A fault after the
    launch took them leaves deleted buffers; the retry must not hand
    those back to the runner (every attempt would fail and every request
    would end in "error") — it raises DonatedPoolError."""
    import jax

    from paddle_tpu.serving.model_runner import DonatedPoolError

    runner = StubPagedRunner(block_size=4, max_model_len=32)
    eng = ServingEngine(runner, num_blocks=16, max_batch_size=2,
                        max_model_len=32, retry_backoff_s=0.0)
    eng.add_request([5, 6, 7], SamplingParams(max_tokens=6))
    eng.step()
    eng.step()
    # the next decode fails on the deleted buffers, as a launch that
    # faulted after taking them would
    for a in jax.tree_util.tree_leaves(eng.pool.pools):
        a.delete()                      # what donation does to them
    with pytest.raises(DonatedPoolError):
        eng.run()
    assert eng.metrics.step_retries.value == 0


# ------------------------------------------------------------- NaN guards


def test_nan_logits_abort_policy():
    runner = StubPagedRunner(block_size=4, max_model_len=32)
    faulty = FaultInjector(runner, nan_calls=(2,), nan_target="decode")
    eng = ServingEngine(faulty, num_blocks=16, max_batch_size=2,
                        max_model_len=32)   # nan_policy="abort" default
    ids = [eng.add_request([i + 1, i + 5], SamplingParams(max_tokens=6))
           for i in range(2)]
    outs = eng.run()
    for rid in ids:
        assert outs[rid].finish_reason == "error"
        assert len(outs[rid].output_tokens) == 2   # prefill + 1 decode
    assert eng.metrics.nan_logit_events.value == 2
    assert eng.pool.allocator.check_no_leaks()


def test_nan_logits_greedy_fallback_completes():
    runner = StubPagedRunner(block_size=4, max_model_len=32)
    faulty = FaultInjector(runner, nan_calls=(1,), nan_target="decode",
                           nan_fraction=0.5)
    eng = ServingEngine(faulty, num_blocks=16, max_batch_size=1,
                        max_model_len=32, nan_policy="greedy")
    rid = eng.add_request([3, 4, 5], SamplingParams(max_tokens=4))
    outs = eng.run()
    assert outs[rid].finish_reason == "length"      # degraded, not dead
    assert len(outs[rid].output_tokens) == 4
    assert eng.metrics.nan_logit_events.value == 1


def test_all_nan_greedy_still_aborts():
    runner = StubPagedRunner(block_size=4, max_model_len=32)
    faulty = FaultInjector(runner, nan_calls=(1,), nan_target="decode",
                           nan_fraction=1.0)
    eng = ServingEngine(faulty, num_blocks=16, max_batch_size=1,
                        max_model_len=32, nan_policy="greedy")
    rid = eng.add_request([3, 4, 5], SamplingParams(max_tokens=4))
    outs = eng.run()
    assert outs[rid].finish_reason == "error"


# ------------------------------------------------------------------ stall


def test_stalled_step_pushes_request_past_deadline():
    t = [0.0]
    runner = StubPagedRunner(block_size=4, max_model_len=32)
    faulty = FaultInjector(runner, stall_calls=(2,), stall_target="decode",
                           on_stall=lambda: t.__setitem__(0, t[0] + 10.0))
    eng = ServingEngine(faulty, num_blocks=16, max_batch_size=1,
                        max_model_len=32,
                        metrics=EngineMetrics(clock=lambda: t[0]))
    rid = eng.add_request([1, 2], SamplingParams(max_tokens=10,
                                                 timeout_s=5.0))
    outs = eng.run()
    assert outs[rid].finish_reason == "timeout"
    assert faulty.injected["stall"] == 1
    assert eng.metrics.requests_timed_out.value == 1
    assert eng.pool.allocator.check_no_leaks()


# ------------------------------------------------------ snapshot / restore


def test_kill_and_restore_matches_naive(llama_setup):
    """ISSUE-2 acceptance: snapshot mid-workload (>=1 preempted AND >=1
    running request), restore on a FRESH runner, finish — every request's
    tokens equal naive_generate, token for token."""
    runner = llama_setup()
    eng = ServingEngine(runner, num_blocks=10, max_batch_size=4,
                        max_model_len=64)
    wl = np.random.default_rng(7)
    work = []
    for i in range(16):
        p = list(wl.integers(1, 97, int(wl.integers(3, 25))))
        sp = SamplingParams(max_tokens=int(wl.integers(2, 11)))
        work.append((eng.add_request(p, sp), p, sp))

    state = None
    for _ in range(300):
        eng.step()
        preempted_waiting = any(r.num_preemptions > 0
                                for r in eng.scheduler.waiting)
        if preempted_waiting and eng.scheduler.running:
            state = eng.snapshot()          # "kill" here
            break
    assert state is not None, "workload never reached the snapshot shape"
    assert any(r["num_preemptions"] > 0 for r in state["requests"])
    assert any(r["output_tokens"] for r in state["requests"])

    state = json.loads(json.dumps(state))   # crash-safe = JSON round-trip
    fresh = llama_setup()                   # fresh runner, same weights
    eng2 = ServingEngine.restore(fresh, state)
    outs = eng2.run()
    assert len(outs) == 16                  # pre-crash finishes carried over
    for rid, p, sp in work:
        assert outs[rid].finish_reason == "length"
        assert outs[rid].output_tokens == naive_generate(
            runner, p, sp, max_model_len=64), f"{rid} diverged after restore"
    assert eng2.pool.allocator.check_no_leaks()


def test_restore_preserves_seeded_sample_streams():
    """Seedless sampling derives its stream from arrival_index — restore
    must preserve it, and new requests must not collide with it."""
    runner = StubPagedRunner(block_size=4, max_model_len=32)
    eng = ServingEngine(runner, num_blocks=16, max_batch_size=2,
                        max_model_len=32)
    sp = SamplingParams(max_tokens=6, temperature=0.9, top_k=8)
    ids = [eng.add_request([i + 2, i + 3], sp) for i in range(3)]
    seeds = {rid: eng._requests[rid].arrival_index for rid in ids}
    for _ in range(2):
        eng.step()
    state = json.loads(json.dumps(eng.snapshot()))
    eng2 = ServingEngine.restore(StubPagedRunner(block_size=4,
                                                 max_model_len=32), state)
    outs = eng2.run()
    for rid in ids:
        ref = naive_generate(runner, eng._requests[rid].prompt_tokens, sp,
                             max_model_len=32, fallback_seed=seeds[rid])
        assert outs[rid].output_tokens == ref
    # a request added after restore must get a fresh arrival_index
    new_rid = eng2.add_request([9, 9], SamplingParams(max_tokens=1))
    assert eng2._requests[new_rid].arrival_index > max(seeds.values())


def test_restore_rejects_unknown_version():
    runner = StubPagedRunner()
    with pytest.raises(ValueError):
        ServingEngine.restore(runner, {"version": 99})


# --------------------------------------------------------------- auditor


def test_auditor_catches_leaked_and_double_owned_pages():
    eng = _stub_engine(max_batch=2)
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=8))
    eng.add_request([4, 5], SamplingParams(max_tokens=8))
    eng.step()
    audit_engine(eng)                          # clean state passes
    victim = eng.scheduler.running[0]
    page = victim.kv.pages[0]
    eng.pool.allocator.free([page])            # now free AND owned
    with pytest.raises(InvariantViolation):
        audit_engine(eng)
    eng.pool.allocator._free.remove(page)      # un-corrupt
    eng.pool.allocator._ref[page] = 1
    eng.pool.allocator._tags[page] = victim.kv.kv_tag   # tag died with
    audit_engine(eng)                                   # the forced free


def test_auditor_catches_slot_corruption():
    eng = _stub_engine(max_batch=2)
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=8))
    eng.step()
    eng.scheduler._free_slots.append(eng.scheduler.running[0].slot)
    with pytest.raises(InvariantViolation):
        audit_engine(eng)


def test_audit_env_var_arms_every_step(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "1")
    eng = _stub_engine()
    assert eng.audit is True
    monkeypatch.setenv("PADDLE_TPU_SERVING_AUDIT", "0")
    assert _stub_engine().audit is False


# -------------------------------------------------------- injector chrome


def test_fault_injector_is_dropin():
    runner = StubPagedRunner(block_size=4, max_model_len=32)
    inj = FaultInjector(runner, error_calls=(1,), error_target="decode")
    assert inj.block_size == 4 and inj.num_layers == 1
    assert inj.max_model_len == 32
    with pytest.raises(InjectedDeviceError):
        inj.decode(np.zeros((1,), np.int32), np.zeros((1, 8), np.int32),
                   np.zeros((1,), np.int32),
                   [(np.zeros((16, 4, 1, 1), np.float32),
                     np.zeros((16, 4, 1, 1), np.float32))])
    assert inj.calls["decode"] == 1 and inj.injected["error"] == 1


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(timeout_s=0.0)
    with pytest.raises(ValueError):
        ServingEngine(StubPagedRunner(), num_blocks=8, shed_policy="bogus")
    with pytest.raises(ValueError):
        ServingEngine(StubPagedRunner(), num_blocks=8, nan_policy="bogus")
    with pytest.raises(ValueError):
        ServingEngine(StubPagedRunner(), num_blocks=8, max_queue_depth=0)
