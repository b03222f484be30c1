"""The two single spellings of serving/engine.py.

ONE configuration record (`EngineConfig`): every field survives
snapshot -> JSON -> restore, and a snapshot shaped as the commit before
the record wrote it still loads. ONE launch skeleton
(`ServingEngine._launch` / `_call_retrying`): every kind of launch — a
prefill chunk, a decode step, a multi-step horizon, a fused speculative
horizon, a ragged step — retries a transient failure `max_step_retries`
times with the doubling back-off and an exact stream, quarantines the
youngest row after one failure more, and lets an UnrecoverableStepError
out. All under the armed auditor.
"""

import dataclasses
import json

import pytest
from _helpers import PeriodicStubRunner, StubPagedRunner

from paddle_tpu.serving import (
    EngineConfig, FaultInjector, InjectedDeviceError, SamplingParams,
    ServingEngine, naive_generate,
)
from paddle_tpu.serving.model_runner import (
    StepCompileError, UnrecoverableStepError,
)

# ------------------------------------------------- the configuration record

# a legal value that is not the default, for every field of the record: a
# new field without a line here fails its case with a KeyError
NON_DEFAULT = {
    "num_blocks": 40, "block_size": 4, "max_batch_size": 3,
    "max_model_len": 48, "max_queue_depth": 7,
    "shed_policy": "drop_oldest", "admission_watermark": 0.9,
    "max_step_retries": 5, "retry_backoff_s": 0.5, "nan_policy": "greedy",
    "max_prefill_tokens_per_step": 4, "enable_prefix_cache": True,
    "host_tier_pages": 6, "host_tier_headroom": True, "pagein_prefetch": 3,
    "ragged_batch": True, "decode_horizon": 4, "pipelined": True,
    "horizon_sampling": True, "horizon_early_stop": True,
    "spill_async": True, "role": "decode", "num_speculative_tokens": 3,
    "spec_max_ngram": 4, "spec_min_ngram": 2, "spec_adaptive_k": True,
    "spec_draft_model": "shadow:fp32", "spec_draft_blocks": 12,
    "spec_ngram_window": 16,
}
FIELDS = [f.name for f in dataclasses.fields(EngineConfig)]


def _through_json(eng):
    state = json.loads(json.dumps(eng.snapshot()))
    return state, ServingEngine.restore(StubPagedRunner(), state, audit=True)


@pytest.mark.parametrize("name", FIELDS)
def test_config_field_round_trips(name):
    """snapshot -> JSON -> restore gives the record back, whichever
    field was set: one forgotten in the snapshot comes back as its
    default and fails here."""
    value = NON_DEFAULT[name]
    default = {f.name: f.default for f in dataclasses.fields(EngineConfig)}
    assert value != default[name]
    # the draft rung is only built with speculation on, and a shadow
    # needs a real runner: with it off the record just carries the string
    eng = ServingEngine(StubPagedRunner(), audit=True,
                        **{"num_blocks": 32, name: value})
    assert getattr(eng, name) == getattr(eng.config, name) == value
    rid = eng.add_request([1, 2, 3, 1, 2, 3], SamplingParams(max_tokens=6))
    eng.step()
    state, back = _through_json(eng)
    assert state["config"][name] == value
    assert back.config == eng.config
    assert back.run()[rid].output_tokens == naive_generate(
        StubPagedRunner(), [1, 2, 3, 1, 2, 3], SamplingParams(max_tokens=6))


def test_every_field_at_once_round_trips():
    eng = ServingEngine(PeriodicStubRunner(period=3), audit=True,
                        **{**NON_DEFAULT, "spec_draft_model": None})
    state, back = _through_json(eng)
    assert back.config == eng.config
    assert dataclasses.asdict(eng.config).items() <= state["config"].items()


def test_unknown_option_is_a_type_error():
    with pytest.raises(TypeError):
        ServingEngine(StubPagedRunner(), num_blocks=32, decode_horizont=4)
    with pytest.raises(ValueError, match="decode_horizon"):
        ServingEngine(StubPagedRunner(), num_blocks=32, decode_horizon=0)


# "config" exactly as the commit before the record wrote it (its engine
# spelled the keys out one by one), for an engine built with
# num_blocks=48, max_batch_size=3, max_model_len=48,
# max_prefill_tokens_per_step=4, enable_prefix_cache, decode_horizon=4
PARENT_CONFIG = {
    "num_blocks": 48, "block_size": 4, "max_batch_size": 3,
    "max_model_len": 48, "max_queue_depth": None, "shed_policy": "reject",
    "admission_watermark": 1.0, "max_step_retries": 2,
    "retry_backoff_s": 0.02, "nan_policy": "abort",
    "max_prefill_tokens_per_step": 4, "enable_prefix_cache": True,
    "host_tier_pages": 0, "host_tier_headroom": False, "pagein_prefetch": 2,
    "ragged_batch": False, "decode_horizon": 4, "pipelined": False,
    "horizon_sampling": False, "horizon_early_stop": False,
    "spill_async": False, "role": "mixed", "num_speculative_tokens": 0,
    "spec_max_ngram": 3, "spec_min_ngram": 1, "spec_adaptive_k": False,
    "spec_draft_model": None, "spec_draft_blocks": None,
    "spec_ngram_window": None, "kv_dtype": "fp32", "weight_dtype": "fp32",
    "weight_group_size": 128, "comm_dtype": "fp32", "mesh_axes": None,
}
# the keys a snapshot of the first serving PRs already had: everything
# else was added later, one PR at a time, and an older journal lacks it
OLDEST_KEYS = ("num_blocks", "block_size", "max_batch_size",
               "max_model_len", "max_queue_depth", "shed_policy",
               "admission_watermark", "max_step_retries", "retry_backoff_s",
               "nan_policy")


@pytest.mark.parametrize("keys", [tuple(PARENT_CONFIG), OLDEST_KEYS],
                         ids=["parent", "oldest"])
def test_parent_shaped_snapshot_restores_token_for_token(keys):
    """This commit writes the parent's "config" key for key, and loads
    it — also with the later keys missing, which then take the record's
    defaults — resuming every request token for token."""
    work = [([1, 2, 3, 1, 2, 3], SamplingParams(max_tokens=10)),
            ([4, 5, 6, 4, 5, 6, 7, 8, 9], SamplingParams(max_tokens=10)),
            ([2, 4, 2, 4], SamplingParams(max_tokens=10))]
    eng = ServingEngine(StubPagedRunner(), num_blocks=48, max_batch_size=3,
                        max_model_len=48, max_prefill_tokens_per_step=4,
                        enable_prefix_cache=True, decode_horizon=4,
                        audit=True)
    rids = [eng.add_request(p, sp) for p, sp in work]
    for _ in range(3):
        eng.step()
    state = json.loads(json.dumps(eng.snapshot()))
    assert state["config"] == PARENT_CONFIG and state["version"] == 1
    state["config"] = {k: PARENT_CONFIG[k] for k in keys}
    back = ServingEngine.restore(StubPagedRunner(), state, audit=True)
    assert back.config == (eng.config if keys is not OLDEST_KEYS else
                           EngineConfig(num_blocks=48, block_size=4,
                                        max_batch_size=3, max_model_len=48))
    outs = back.run()
    for rid, (p, sp) in zip(rids, work):
        assert outs[rid].output_tokens == naive_generate(
            StubPagedRunner(), p, sp, max_model_len=48)
    back.release_prefix_cache()
    assert back.pool.allocator.check_no_leaks()


# ------------------------------------------------------ the launch skeleton

RETRIES, BACKOFF = 2, 0.25
# kind -> (the runner entry it launches through, the injector's op
# counter that entry shares, the engine options that choose it)
KINDS = {
    "prefill_chunk": ("prefill_chunk", "prefill",
                      dict(max_prefill_tokens_per_step=4)),
    "decode": ("decode", "decode", {}),
    "decode_horizon": ("decode_multi", "decode", dict(decode_horizon=4)),
    "fused_speculation": ("decode_multi_spec", "decode",
                          dict(num_speculative_tokens=3, decode_horizon=2)),
    "ragged_batch": ("ragged_step", "decode",
                     dict(ragged_batch=True, max_prefill_tokens_per_step=4)),
}
ENTRIES = sorted({entry for entry, _, _ in KINDS.values()})
PROMPTS = [[1, 2, 3, 1, 2, 3], [4, 5, 6, 4, 5, 6],
           [2, 4, 2, 4, 2, 4, 2, 4, 2, 4, 2, 4, 2, 4]]
SAMPLING = SamplingParams(max_tokens=12)


def _stub():
    return PeriodicStubRunner(period=3, vocab_size=31, block_size=4,
                              max_model_len=64)


class Spy(FaultInjector):
    """A FaultInjector that also notes, per op counter, which entry each
    call came through and what it was fed: from a fault-free run, the
    call to fail; from a faulted one, the chunk that failed."""

    def __init__(self, runner, refuse=False, **kw):
        super().__init__(runner, **kw)
        self.seen = {"prefill": [], "decode": []}
        self.refuse = refuse

    def _pre(self, op):
        try:
            return super()._pre(op)
        except InjectedDeviceError as e:
            if self.refuse:       # what the backend's compiler would say
                raise StepCompileError(str(e)) from e
            raise


def _spied(entry, op):
    def call(self, *args, **kw):
        self.seen[op].append((entry, args[:2]))
        return getattr(FaultInjector, entry)(self, *args, **kw)
    return call


for _entry, _op, _ in KINDS.values():
    setattr(Spy, _entry, _spied(_entry, _op))


def _serve(kind, **faults):
    """Three requests through an audited engine of `kind`; returns the
    engine, its runner, the request ids, the back-offs slept, and at the
    first back-off the youngest row of the failing batch."""
    _, op, options = KINDS[kind]
    spy = Spy(_stub(), error_target=op, **faults)
    slept, victim = [], []

    def sleep(dt):
        if not slept:
            if kind == "prefill_chunk":
                chunk, start = spy.seen[op][-1][1]
                victim.extend(r for r in eng.scheduler.running if list(
                    r.context_tokens[start:start + len(chunk)]) == list(
                        chunk))
            else:
                rows = eng.scheduler.decode_ready() + (
                    [r for r, _, _ in eng.scheduler.prefill_plan()]
                    if kind == "ragged_batch" else [])
                victim.append(max(rows, key=lambda r: r.admission_index))
        slept.append(dt)

    eng = ServingEngine(spy, num_blocks=64, max_batch_size=3,
                        max_model_len=64, max_step_retries=RETRIES,
                        retry_backoff_s=BACKOFF, sleep_fn=sleep, audit=True,
                        **options)
    rids = [eng.add_request(p, SAMPLING) for p in PROMPTS]
    return eng, spy, rids, slept, victim


def _failing_calls(kind, n):
    """The 1-based indices of `n` consecutive calls on the entry's op
    counter, the first of which reaches the runner through the kind's own
    entry in a fault-free run, late enough that a batch is under way."""
    entry, op, _ = KINDS[kind]
    eng, spy, _, _, _ = _serve(kind)
    eng.run()
    first = next(i for i, (e, _) in enumerate(spy.seen[op], 1)
                 if e == entry and i >= 3)
    return range(first, first + n)


def _oracle(prompt):
    return naive_generate(_stub(), prompt, SAMPLING, max_model_len=64)


@pytest.mark.parametrize("kind", KINDS)
def test_launch_retries_transient_failures_exactly(kind):
    eng, spy, rids, slept, _ = _serve(
        kind, error_calls=_failing_calls(kind, RETRIES))
    outs = eng.run()
    assert spy.injected["error"] == RETRIES
    assert eng.metrics.step_retries.value == RETRIES
    assert slept == [BACKOFF, 2 * BACKOFF]
    for rid, p in zip(rids, PROMPTS):
        assert outs[rid].finish_reason == "length"
        assert outs[rid].output_tokens == _oracle(p)
    assert eng.pool.allocator.check_no_leaks()


@pytest.mark.parametrize("kind", KINDS)
def test_launch_quarantines_the_youngest_after_one_failure_more(kind):
    eng, spy, rids, slept, victim = _serve(
        kind, error_calls=_failing_calls(kind, RETRIES + 1))
    outs = eng.run()
    assert spy.injected["error"] == RETRIES + 1
    assert eng.metrics.step_retries.value == RETRIES
    assert slept == [BACKOFF, 2 * BACKOFF]
    (gone,) = victim
    assert [rid for rid in rids if outs[rid].finish_reason == "error"] == [
        gone.request_id]
    for rid, p in zip(rids, PROMPTS):
        want = _oracle(p)
        if rid == gone.request_id:       # what it had is what it keeps
            assert outs[rid].output_tokens == want[:len(
                outs[rid].output_tokens)]
        else:
            assert outs[rid].finish_reason == "length"
            assert outs[rid].output_tokens == want
    assert eng.pool.allocator.check_no_leaks()


@pytest.mark.parametrize("kind", KINDS)
def test_launch_lets_an_unrecoverable_error_out(kind):
    eng, spy, _, slept, _ = _serve(
        kind, refuse=True, error_calls=_failing_calls(kind, 1))
    with pytest.raises(UnrecoverableStepError):
        eng.run()
    assert eng.metrics.step_retries.value == 0 and slept == []


@pytest.mark.parametrize("kind", [k for k in KINDS if k != "prefill_chunk"])
def test_pipelined_launch_defers_and_commits_the_same_stream(kind):
    """The skeleton's other tail: with `pipelined` the launch of each
    kind that defers stays in flight for a step, and the stream is the
    same."""
    options = KINDS[kind][2]
    eng = ServingEngine(_stub(), num_blocks=64, max_batch_size=3,
                        max_model_len=64, pipelined=True, audit=True,
                        **options)
    rids = [eng.add_request(p, SAMPLING) for p in PROMPTS]
    flown = set()
    while eng.has_work():
        eng.step()
        if eng._inflight is not None:
            flown.add(eng._inflight.kind.name)
    assert flown
    for rid, p in zip(rids, PROMPTS):
        assert eng.outputs()[rid].output_tokens == _oracle(p)
    assert eng.pool.allocator.check_no_leaks()
