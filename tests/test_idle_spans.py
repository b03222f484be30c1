"""The spans that split the launch and the drain where the device starts and
stops (ISSUE 40): under a live profiler session every `runner.launch` holds
`runner.stage`, `runner.dispatch`, `runner.account` in that order and every
`engine.drain` holds `drain.enqueue` (where the drain has device work of its
own to dispatch) and `drain.fetch`; with no session a step records nothing
and makes the calls it made before the spans were there; set-up compiles a
fixed list of programs. And what lies between a step's tokens and the next
dispatch (ISSUE 41): only what that dispatch reads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler as prof
from paddle_tpu.serving import SamplingParams
from paddle_tpu.serving import engine as engine_mod
from test_tracing import mark, since, toy_engine

NAME, T0, T1, SID, PARENT, STEP, REQUEST, ATTRS = range(8)
# the call's byte accounting comes once the device has the program
LAUNCH_PARTS = ["runner.stage", "runner.dispatch", "runner.account"]


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


# what the next dispatch reads, and the spans that hold it
NEEDED = {"engine.commit", "engine.drain", "engine.step", "engine.plan",
          "engine.build_batch", "runner.launch", "runner.stage"}
# the last with the ragged kernel (interpret mode here), whose launches
# count the few-rows fold's blocks beside their bytes
LOOPS = {"default": {}, "pipelined": {"pipelined": True},
         "horizon": {"decode_horizon": 4},
         "ragged-kernel": {"attn_impl": "ragged"}}


class Counts:
    """What a counting runner hands over at a launch: an output of the
    step's program, with its two doors to the host logged."""

    def __init__(self, log):
        self.log, self.array = log, jnp.ones((1,), jnp.int32)

    def copy_to_host_async(self):
        self.log.append("copy")
        self.array.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.log.append("read")
        return np.asarray(self.array)


def counting(eng, monkeypatch, log):
    """Make the toy runner count: every single-pass launch hands the engine
    one `Counts`, as a runner with `COUNTS` does from `_emit`."""
    runner = eng.runner
    monkeypatch.setattr(type(runner), "COUNTS", ("prefill_chunks",),
                        raising=False)
    emit = runner._emit

    def counting_emit(out):
        logits, pools = emit(out)
        if runner.on_step_counts is not None:
            runner.on_step_counts(Counts(log))
        return logits, pools

    monkeypatch.setattr(runner, "_emit", counting_emit)


@pytest.mark.parametrize("which", sorted(LOOPS))
def test_between_tokens_and_dispatch_only_what_the_dispatch_reads(
        which, tmp_path, monkeypatch):
    """A counting runner on each loop: from a decode-only step's token fetch
    to the next decode's dispatch the host opens no span but the ones that
    build that dispatch; the counts set out for the host at the hand-over
    and are read after the tokens without a call that waits; the byte
    accounting and the last step's gauges follow a dispatch."""
    eng = toy_engine(**LOOPS[which])
    serve(eng, [[1, 2, 3]], 6)                  # the programs exist
    log = []
    counting(eng, monkeypatch, log)
    calls = Calls(monkeypatch)
    to_host = engine_mod._to_host
    block_counts = []                           # when each was taken
    count_blocks = eng.runner._account_blocks

    def counted_blocks(*a, **k):
        block_counts.append(prof.stamp())
        count_blocks(*a, **k)

    monkeypatch.setattr(eng.runner, "_account_blocks", counted_blocks)

    def logged(x):
        log.append("to_host")
        return to_host(x)

    monkeypatch.setattr(engine_mod, "_to_host", logged)
    m = mark()
    per_step = []
    with jax.profiler.trace(str(tmp_path)):
        eng.add_request([4, 5, 6, 7], SamplingParams(max_tokens=26))
        eng.add_request([8, 9, 10], SamplingParams(max_tokens=26))
        while eng.has_work():
            calls.take()
            eng.step()
            per_step.append(calls.take())
    spans = since(m)
    # one blocking door a step at most (a step that prefills and decodes
    # crosses it twice), and never the two calls that wait on their own
    assert all(c["device_get"] == 0 and c["block_until_ready"] == 0
               for c in per_step)
    by_step = {}
    for s in spans:
        by_step.setdefault(s[STEP], []).append(s)
    decode_only = {
        k for k, mine in by_step.items()
        if any(s[NAME] == "runner.launch" for s in mine)
        and all(str(s[ATTRS]["kind"]).startswith("decode")
                for s in mine if s[NAME] == "runner.launch")}
    for k, c in zip(sorted(by_step), per_step):
        if k in decode_only:
            assert c["to_host"] == 1
    fetches = sorted((s for s in spans if s[NAME] == "drain.fetch"),
                     key=lambda s: s[T1])
    assert not any((s[ATTRS] or {}).get("what") == "counts"
                   for s in fetches)
    dispatches = sorted((s for s in spans if s[NAME] == "runner.dispatch"),
                        key=lambda s: s[T0])
    checked = 0
    for d in dispatches:
        before = [f for f in fetches if f[T1] <= d[T0]]
        if not before or d[STEP] not in decode_only:
            continue
        r = before[-1]
        if r[STEP] not in decode_only or d[STEP] - r[STEP] > 1:
            continue
        between = {s[NAME] for s in spans
                   if s[T1] > r[T1] and s[T0] < d[T0]}
        assert between <= NEEDED, (which, d[STEP], between - NEEDED)
        checked += 1
    assert checked >= 3
    # the work that left the interval is done all the same, behind a
    # dispatch of its own step
    by_id = {s[SID]: s for s in spans}
    for name in ("runner.account", "engine.settle"):
        moved = [s for s in spans if s[NAME] == name]
        assert moved, name
        for s in moved:
            mine = [d for d in dispatches if d[STEP] == s[STEP]
                    and d[T1] <= s[T0]]
            assert mine, (name, s[STEP])
    assert all(by_id[s[PARENT]][NAME] in ("engine.step", "request.prefill")
               for s in spans if s[NAME] == "engine.settle")
    # the ragged kernel's block counts are taken inside `runner.account`,
    # so behind their step's dispatch like the bytes
    accounts = [s for s in spans if s[NAME] == "runner.account"]
    assert all(any(s[T0] <= t <= s[T1] for s in accounts)
               for t in block_counts)
    assert bool(block_counts) == (which == "ragged-kernel")
    # the counts: asked for at the hand-over, before the drain that reads
    # them blocks for the tokens; read once, after those
    assert log.count("copy") == log.count("read") > 0
    waiting = 0                     # handed over, not yet read
    fetched = False
    for what in log:
        if what == "copy":
            waiting, fetched = waiting + 1, False
        elif what == "to_host":
            fetched = True
        else:
            assert waiting > 0 and fetched, log
            waiting -= 1
    assert waiting == 0
    assert eng.metrics.snapshot()["prefill_chunks"] >= log.count("copy")


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


def test_dispatch_floor_times_every_variant_on_a_toy_engine():
    """tools/dispatch_floor.py's `measure` off the chip: every variant of
    the decode call and of the empty program is timed, the engine's pools
    come back threaded through the calls, and the engine serves on."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import dispatch_floor

    eng = toy_engine()
    for p in ([1, 2, 3], [4, 5, 6, 7]):
        eng.add_request(p, SamplingParams(max_tokens=12))
    for _ in range(3):
        eng.step()
    got = dispatch_floor.measure(eng, steps=3)
    assert got["workload_batch"] == 4 and got["leaves"] > 0
    for variant in ("as_passed", "on_device", "packed", "packed_dev", "nop",
                    "nop_leaves", "nop_host3"):
        t = got[variant]
        assert t["n"] == 3 and 0 < t["call_ms"] <= t["wall_ms"], variant
    assert got["program_ms"] is None            # no device plane on the CPU
    outs = eng.run()
    assert all(len(o.output_tokens) == 12 for o in outs.values())
