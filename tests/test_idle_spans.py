"""The spans that split the launch and the drain where the device starts and
stops (ISSUE 40): under a live profiler session every `runner.launch` holds
`runner.account`, `runner.stage`, `runner.dispatch` in that order and every
`engine.drain` holds `drain.enqueue` (where the drain has device work of its
own to dispatch) and `drain.fetch`; with no session a step records nothing
and makes the calls it made before the spans were there; set-up compiles a
fixed list of programs."""

import jax
import numpy as np
import pytest

from paddle_tpu import profiler as prof
from paddle_tpu.serving import SamplingParams
from paddle_tpu.serving import engine as engine_mod
from test_tracing import mark, since, toy_engine

NAME, T0, T1, SID, PARENT, STEP, REQUEST, ATTRS = range(8)
LAUNCH_PARTS = ["runner.account", "runner.stage", "runner.dispatch"]


def serve(eng, prompts, max_tokens):
    for p in prompts:
        eng.add_request(p, SamplingParams(max_tokens=max_tokens))
    eng.run()


def traced(tmp_path, **kw):
    """The spans of a traced serve on an engine whose programs exist."""
    eng = toy_engine(**kw)
    serve(eng, [[1, 2, 3]], 6)                  # compile outside the trace
    m = mark()
    with jax.profiler.trace(str(tmp_path)):
        # the second prompt arrives while the first decodes, so that a
        # ragged engine has a chunk and a decode span to fuse
        eng.add_request([4, 5, 6, 7], SamplingParams(max_tokens=9))
        eng.step()
        eng.step()
        serve(eng, [[8, 9, 10]], 9)
    return since(m)


# the default loop (prefill + decode), a device-resident horizon behind the
# pipelined loop (decode_multi, drained by `_to_host` alone), the fused
# ragged step, and the speculative paths (ragged verify, decode_multi_spec)
ENGINES = {
    "default": ({}, {"prefill", "decode"}),
    "pipelined-horizon": ({"pipelined": True, "decode_horizon": 4},
                          {"prefill", "decode_multi"}),
    "ragged": ({"ragged_batch": True}, {"ragged"}),
    "speculative": ({"num_speculative_tokens": 2}, set()),
}


@pytest.mark.parametrize("which", sorted(ENGINES))
def test_launch_and_drain_hold_their_parts_under_a_session(which, tmp_path):
    kw, kinds = ENGINES[which]
    spans = traced(tmp_path, **kw)
    by_id = {s[SID]: s for s in spans}
    kids = {}
    for s in spans:
        kids.setdefault(s[PARENT], []).append(s)
    launches = [s for s in spans if s[NAME] == "runner.launch"]
    drains = [s for s in spans if s[NAME] == "engine.drain"]
    assert launches and drains
    assert kinds <= {s[ATTRS]["kind"] for s in launches}
    for launch in launches:
        mine = sorted(kids[launch[SID]], key=lambda s: s[T0])
        assert [s[NAME] for s in mine] == LAUNCH_PARTS, launch[ATTRS]
        # the parent's extent covers its children, and they do not overlap
        assert launch[T0] <= mine[0][T0] and mine[-1][T1] <= launch[T1]
        assert all(a[T1] <= b[T0] for a, b in zip(mine, mine[1:]))
        assert all(s[STEP] == launch[STEP] for s in mine)
    enqueues = 0
    for drain in drains:
        mine = sorted(kids[drain[SID]], key=lambda s: s[T0])
        names = [s[NAME] for s in mine]
        assert names in (["drain.enqueue", "drain.fetch"], ["drain.fetch"])
        enqueues += names[0] == "drain.enqueue"
        assert drain[T0] <= mine[0][T0] and mine[-1][T1] <= drain[T1]
        assert all(a[T1] <= b[T0] for a, b in zip(mine, mine[1:]))
    # a drain that reads logits dispatches the argmax pass itself; one that
    # pulls a horizon's packed buffer has nothing to enqueue
    assert enqueues > 0 or which == "pipelined-horizon"
    # the new spans are nobody else's children
    for s in spans:
        if s[NAME] in LAUNCH_PARTS:
            assert by_id[s[PARENT]][NAME] == "runner.launch"
        if s[NAME].startswith("drain."):
            assert by_id[s[PARENT]][NAME] == "engine.drain"


def test_the_counts_fetch_is_a_second_drain_fetch(tmp_path, monkeypatch):
    """A runner that counts (`COUNTS`) has its counters fetched inside the
    drain, after the tokens: a `drain.fetch` of its own, told by `what`."""
    eng = toy_engine()
    serve(eng, [[1, 2, 3]], 3)
    runner = eng.runner
    monkeypatch.setattr(type(runner), "COUNTS", ("prefill_chunks",),
                        raising=False)
    emit = runner._emit

    def counting_emit(out):
        logits, pools = emit(out)
        if runner.on_step_counts is not None:
            runner.on_step_counts(np.ones((1,), np.int32))
        return logits, pools

    monkeypatch.setattr(runner, "_emit", counting_emit)
    m = mark()
    with jax.profiler.trace(str(tmp_path)):
        serve(eng, [[4, 5, 6]], 4)
    spans = since(m)
    fetches = [s for s in spans if s[NAME] == "drain.fetch"]
    counted = [s for s in fetches if (s[ATTRS] or {}).get("what") == "counts"]
    assert counted and len(counted) < len(fetches)
    by_id = {s[SID]: s for s in spans}
    for c in counted:
        tokens = [s for s in fetches if s[PARENT] == c[PARENT] and s is not c]
        assert len(tokens) == 1 and tokens[0][T1] <= c[T0]
        assert by_id[c[PARENT]][NAME] == "engine.drain"


class Calls:
    """How often a step crosses to the device by each door."""

    def __init__(self, monkeypatch):
        self.n = {"to_host": 0, "device_get": 0, "device_put": 0,
                  "block_until_ready": 0}
        self._wrap(monkeypatch, engine_mod, "_to_host", "to_host")
        for name in ("device_get", "device_put", "block_until_ready"):
            self._wrap(monkeypatch, jax, name, name)

    def _wrap(self, monkeypatch, owner, attr, key):
        real = getattr(owner, attr)

        def counting(*a, **k):
            self.n[key] += 1
            return real(*a, **k)

        monkeypatch.setattr(owner, attr, counting)

    def take(self):
        got, self.n = self.n, dict.fromkeys(self.n, 0)
        return got


def stepped_counts(eng, calls):
    """(calls of the step that prefills and decodes, calls of a decode-only
    step) for one fresh request."""
    eng.add_request([4, 5, 6, 7], SamplingParams(max_tokens=6))
    calls.take()
    eng.step()
    first = calls.take()
    eng.step()
    second = calls.take()
    eng.run()
    return first, second


# the parent commit's: a completing prefill samples through one drain and the
# decode step through another; a decode-only step drains once; nothing else
# touches the device from the host
PARENT_CALLS = ({"to_host": 2, "device_get": 0, "device_put": 0,
                 "block_until_ready": 0},
                {"to_host": 1, "device_get": 0, "device_put": 0,
                 "block_until_ready": 0})


def test_no_session_no_span_and_the_parents_calls(monkeypatch, tmp_path):
    eng = toy_engine()
    serve(eng, [[1, 2, 3]], 3)                  # the programs exist
    calls = Calls(monkeypatch)
    m = mark()
    got = stepped_counts(eng, calls)
    assert since(m) == []                       # not one span recorded
    assert prof.span("anything") is prof.NO_SPAN
    assert got == PARENT_CALLS
    # recording, the step crosses to the device as often and no oftener
    with jax.profiler.trace(str(tmp_path)):
        assert stepped_counts(eng, calls) == PARENT_CALLS


def test_set_up_compiles_a_fixed_list_of_programs():
    """An engine built and stepped once with no session: the programs of its
    first step, by the `(kind, key)` their `runner.compile` spans carry, and
    no per-step span beside them."""
    m = mark()
    eng = toy_engine()
    eng.add_request([1, 2, 3, 4, 5], SamplingParams(max_tokens=3))
    eng.step()
    spans = since(m)
    compiles = [(s[ATTRS]["kind"], s[ATTRS]["key"]) for s in spans
                if s[NAME] == "runner.compile"]
    assert compiles == [("prefill", 8), ("decode", 4)]
    assert {s[NAME] for s in spans} == {"model.build", "engine.build",
                                        "kv_pool.alloc", "runner.compile"}
    eng.run()
    assert [s[NAME] for s in since(m)
            if s[NAME] == "runner.compile"] == ["runner.compile"] * 2
