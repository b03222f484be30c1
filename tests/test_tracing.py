"""The program's spans (ISSUE 25): the one primitive in paddle_tpu.profiler,
where the engine, the runner, TrainStep and set-up open it, that the same
spans reach the profiler's own trace, and the scope names on the device."""

import glob
import re
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler as prof
from paddle_tpu.models.gpt import GPT, GPTConfig, gpt_loss_fn
from paddle_tpu.serving import EngineMetrics, SamplingParams
from paddle_tpu.serving.metrics import aggregate_snapshots

NAME, T0, T1, SID, PARENT, STEP, REQUEST, ATTRS = range(8)
REMOVED_KEYS = ("host_plan_seconds", "overlapped_plan_seconds",
                "drain_wait_seconds", "step_seconds", "device_idle_fraction",
                "busy_seconds", "tokens_per_sec")


def toy_gpt():
    paddle.seed(0)
    model = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                          num_heads=2, max_seq_len=64))
    model.eval()
    return model


def toy_engine(**kw):
    from paddle_tpu.inference import create_serving_engine

    return create_serving_engine(toy_gpt(), num_blocks=32, block_size=8,
                                 max_batch_size=4, max_model_len=64, **kw)


def since(mark):
    return [s for s in prof.spans() if s[SID] > mark]


def mark():
    """A span id below every span recorded from now on."""
    prof.record("test.mark", prof.stamp())
    return prof.spans()[-1][SID]


# ------------------------------------------------------------- primitive


def test_nesting_parent_and_self_time():
    m = mark()
    with prof.always_span("outer", who="test") as outer:
        with prof.always_span("inner"):
            time.sleep(0.002)
        with prof.always_span("inner"):
            time.sleep(0.002)
        outer.set(more=1)
    got = {s[SID]: s for s in since(m)}
    outer_t = next(s for s in got.values() if s[NAME] == "outer")
    inners = [s for s in got.values() if s[NAME] == "inner"]
    assert outer_t[PARENT] is None
    assert outer_t[ATTRS] == {"who": "test", "more": 1}
    assert [s[PARENT] for s in inners] == [outer_t[SID]] * 2
    assert all(outer_t[T0] <= s[T0] <= s[T1] <= outer_t[T1] for s in inners)
    own = prof.self_ns(list(got.values()))
    covered = sum(s[T1] - s[T0] for s in inners)
    assert own[outer_t[SID]] == outer_t[T1] - outer_t[T0] - covered
    assert 0 <= own[outer_t[SID]] < outer_t[T1] - outer_t[T0]
    assert all(own[s[SID]] == s[T1] - s[T0] for s in inners)


def test_children_take_their_parents_step_and_request():
    m = mark()
    root = prof.Span("root", step_id=7, request_id="r1")
    with root:
        with prof.always_span("child"):
            pass
        prof.record("past", prof.stamp() - 10, request_id="r2")
    child, past, root_t = since(m)
    assert (child[STEP], child[REQUEST]) == (7, "r1")
    assert (past[STEP], past[REQUEST], past[PARENT]) == (7, "r2", None)
    assert root_t[NAME] == "root"


def test_a_span_left_open_goes_with_its_parent():
    m = mark()
    with pytest.raises(RuntimeError):
        with prof.always_span("parent"):
            prof.always_span("leaked").begin()
            raise RuntimeError("between begin and end")
    with prof.always_span("after"):
        pass
    after = since(m)[-1]
    assert after[NAME] == "after" and after[PARENT] is None


def test_ring_is_bounded():
    assert prof._ring.maxlen == prof.RING_SPANS
    for _ in range(prof.RING_SPANS + 10):
        prof.record("filler", 0, 1)
    assert len(prof.spans()) == prof.RING_SPANS
    prof.clear()
    assert prof.spans() == []


def test_spans_of_other_threads_do_not_nest():
    import threading

    m = mark()
    seen = []

    def worker():
        with prof.always_span("theirs"):
            pass
        seen.append(1)

    with prof.always_span("mine"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    theirs = next(s for s in since(m) if s[NAME] == "theirs")
    assert seen and theirs[PARENT] is None


def test_off_path_is_one_noop_object_and_records_nothing():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    before = prof.spans()
    root = prof.step_span("engine.step", 1)
    assert root is prof.NO_SPAN and prof.recording is False
    assert prof.span("engine.plan") is prof.NO_SPAN
    assert prof.span("runner.launch", kind="decode") is prof.NO_SPAN
    with root, prof.span("engine.drain") as s:
        s.set(kind="x")
        prof.span("engine.build_batch").begin().end()
    assert prof.spans() == before


def test_record_event_records_only_under_a_session(tmp_path):
    m = mark()
    with prof.RecordEvent("quiet"):
        pass
    assert since(m) == []
    with jax.profiler.trace(str(tmp_path)):
        with prof.RecordEvent("loud"):
            pass
        ev = prof.RecordEvent("by_hand")
        ev.begin()
        ev.end()
    assert [s[NAME] for s in since(m)] == ["loud", "by_hand"]


def test_profiler_handle_reads_the_ring(tmp_path):
    import json

    p = prof.Profiler(trace_dir=str(tmp_path / "xplane"))
    with p:
        with prof.RecordEvent("outer_region"):
            with prof.RecordEvent("inner_region"):
                time.sleep(0.002)
    assert glob.glob(str(tmp_path / "xplane" / "**" / "*.xplane.pb"),
                     recursive=True)
    path = p.export_chrome_tracing(str(tmp_path / "out" / "trace.json"))
    events = json.load(open(path))["traceEvents"]
    assert {e["name"] for e in events} == {"outer_region", "inner_region"}
    table = p.summary()
    assert "outer_region" in table and "Self(ms)" in table
    row = next(ln.split() for ln in table.splitlines()
               if ln.startswith("outer_region"))
    assert float(row[3]) < float(row[2])        # self < total: a child


def test_profiler_start_raises_what_jax_raises(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(Exception):
            prof.Profiler(trace_dir=str(tmp_path / "second")).start()
    quiet = prof.Profiler(timer_only=True)
    quiet.start()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    quiet.stop()


# --------------------------------------------------------------- serving


@pytest.fixture(scope="module")
def traced_serve(tmp_path_factory):
    """A toy GPT engine: one request served with no session (its programs
    compile there), three under jax.profiler.trace."""
    d = str(tmp_path_factory.mktemp("trace"))
    m0 = mark()
    eng = toy_engine()
    eng.add_request([1, 2, 3, 4, 5], SamplingParams(max_tokens=3))
    eng.run()
    untraced = since(m0)
    m1 = mark()
    with jax.profiler.trace(d):
        rids = [eng.add_request(list(range(1, 4 + i)),
                                SamplingParams(max_tokens=4))
                for i in range(3)]
        eng.run()
    path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
    return {"eng": eng, "untraced": untraced, "traced": since(m1),
            "rids": rids, "xplane": path}


def test_untraced_steps_record_only_once_per_program_sites(traced_serve):
    names = {s[NAME] for s in traced_serve["untraced"]}
    assert names == {"model.build", "engine.build", "kv_pool.alloc",
                     "runner.compile"}
    by = {s[NAME]: s for s in traced_serve["untraced"]}
    assert by["kv_pool.alloc"][PARENT] == by["engine.build"][SID]


def test_compile_recorded_once_per_shape_key_naming_its_kind(traced_serve):
    compiles = [s for s in traced_serve["untraced"]
                if s[NAME] == "runner.compile"]
    keys = [(s[ATTRS]["kind"], s[ATTRS]["key"]) for s in compiles]
    assert sorted(keys) == [("decode", 4), ("prefill", 8)]
    assert len(set(keys)) == len(keys)
    # the traced serve reused both programs: nothing compiled again
    assert not [s for s in traced_serve["traced"]
                if s[NAME] == "runner.compile"]


def test_engine_step_holds_its_layers(traced_serve):
    spans = traced_serve["traced"]
    by_id = {s[SID]: s for s in spans}
    steps = [s for s in spans if s[NAME] == "engine.step"]
    assert len(steps) >= 3
    assert [s[STEP] for s in steps] == sorted(s[STEP] for s in steps)
    own = prof.self_ns(spans)
    for st in steps:
        kids = [s for s in spans if s[PARENT] == st[SID]]
        assert all(s[STEP] == st[STEP] for s in kids)
        assert all(st[T0] <= s[T0] <= s[T1] <= st[T1] for s in kids)
        assert sum(s[T1] - s[T0] for s in kids) <= st[T1] - st[T0]
        assert own[st[SID]] >= 0
    decode_step = steps[-1]

    def under(step, name):
        out = []
        for s in spans:
            p = s
            while p[PARENT] in by_id and p[SID] != step[SID]:
                p = by_id[p[PARENT]]
            if p[SID] == step[SID] and s[NAME] == name:
                out.append(s)
        return out

    for name in ("engine.plan", "engine.build_batch", "runner.launch",
                 "engine.drain", "engine.commit"):
        assert under(decode_step, name), name
    launch = under(decode_step, "runner.launch")[0]
    assert launch[ATTRS] == {"kind": "decode", "key": 4}
    drain = under(decode_step, "engine.drain")[0]
    assert by_id[drain[PARENT]][NAME] == "engine.commit"


def test_request_spans_share_their_request_id(traced_serve):
    spans = traced_serve["traced"]
    by_id = {s[SID]: s for s in spans}
    for rid in traced_serve["rids"]:
        mine = [s for s in spans if s[REQUEST] == rid]
        names = [s[NAME] for s in mine]
        assert names.count("request.queue") == 1
        assert names.count("request.prefill") == 1
        queue = next(s for s in mine if s[NAME] == "request.queue")
        prefill = next(s for s in mine if s[NAME] == "request.prefill")
        assert queue[PARENT] is None and queue[T1] <= prefill[T0]
        assert by_id[prefill[PARENT]][NAME] == "engine.step"
        # what the chunk ran under it carries the request's id too
        assert {"engine.build_batch", "runner.launch", "engine.commit",
                "engine.drain"} <= set(names)


def test_spans_lie_in_the_xplane_at_a_constant_offset(traced_serve):
    pd = jax.profiler.ProfileData.from_file(traced_serve["xplane"])
    in_trace = {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "runner.", "request.",
                                         "drain.")):
                    in_trace.setdefault(e.name, []).append(
                        (float(e.start_ns), float(e.duration_ns)))
    ring = {}
    for s in traced_serve["traced"]:
        if s[PARENT] is not None or s[NAME] == "engine.step":
            ring.setdefault(s[NAME], []).append((s[T0], s[T1] - s[T0]))
    assert set(ring) == set(in_trace) >= {
        "engine.step", "engine.plan", "engine.build_batch", "runner.launch",
        "engine.drain", "engine.commit", "request.prefill"}
    offsets = []
    for name, mine in ring.items():
        theirs = sorted(in_trace[name])
        assert len(theirs) == len(mine), name
        offsets += [t[0] - m[0] for t, m in zip(theirs, sorted(mine))]
    mid = statistics.median(offsets)
    # one clock against the other: the same offset for every span, to
    # within what entering the annotation and reading the clock take
    assert max(abs(o - mid) for o in offsets) < 200_000


def test_pipelined_and_horizon_paths_share_the_boundaries(tmp_path):
    eng = toy_engine(pipelined=True, decode_horizon=4)
    eng.add_request([1, 2, 3], SamplingParams(max_tokens=2))
    eng.run()                                   # compile outside the trace
    m = mark()
    with jax.profiler.trace(str(tmp_path)):
        eng.add_request([4, 5, 6, 7], SamplingParams(max_tokens=9))
        eng.run()
    spans = since(m)
    names = {s[NAME] for s in spans}
    assert {"engine.step", "engine.plan", "engine.build_batch",
            "runner.launch", "engine.drain", "engine.commit"} <= names
    kinds = {s[ATTRS]["kind"] for s in spans if s[NAME] == "runner.launch"}
    assert "decode_multi" in kinds
    steps = {s[SID] for s in spans if s[NAME] == "engine.step"}
    tops = [s for s in spans if s[PARENT] in steps]
    # pipelined: the drain of a launch is a later step's, under no commit
    assert any(s[NAME] == "engine.drain" for s in tops)


# -------------------------------------------------------------- training


@pytest.fixture(scope="module")
def traced_train(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("train_trace"))
    paddle.seed(0)
    model = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                          num_heads=2, max_seq_len=32))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    m0 = mark()
    step = paddle.jit.TrainStep(model, gpt_loss_fn, opt)
    tokens = np.arange(2 * 16).reshape(2, 16) % 128
    step(tokens, tokens)
    step(tokens, tokens)
    untraced = since(m0)
    m1 = mark()
    with jax.profiler.trace(d):
        for _ in range(3):
            step(tokens, tokens)
    return {"step": step, "tokens": tokens, "untraced": untraced,
            "traced": since(m1)}


def test_train_step_spans(traced_train):
    untraced = [s[NAME] for s in traced_train["untraced"]]
    assert untraced == ["train.init", "train.compile"]
    spans = traced_train["traced"]
    steps = [s for s in spans if s[NAME] == "train.step"]
    assert [s[STEP] for s in steps] == [3, 4, 5]
    for st in steps:
        kids = [s for s in spans if s[PARENT] == st[SID]]
        assert [s[NAME] for s in kids] == ["train.stage_inputs",
                                           "train.dispatch"]
        assert all(s[STEP] == st[STEP] for s in kids)
        assert sum(s[T1] - s[T0] for s in kids) <= st[T1] - st[T0]


@pytest.mark.parametrize("scope", ["loss", "optimizer", "embed",
                                   "block/attn", "block/mlp", "final_norm",
                                   "lm_head"])
def test_train_step_program_names_its_layers(traced_train, scope):
    step = traced_train["step"]
    _, args = step._stage_inputs((traced_train["tokens"],) * 2)
    text = step._compiled.lower(step.params, step.buffers, step.opt_state,
                                *args).as_text(debug_info=True)
    if scope in ("loss", "optimizer"):
        assert f"jit(step)/{scope}/" in text
        return
    # the model's scopes nest under the step's; differentiation wraps the
    # outermost of them, which tells forward from backward
    outer, _, inner = scope.partition("/")
    tail = f"/{inner}/" if inner else "/"
    assert f"jit(step)/loss/jvp({outer}){tail}" in text
    assert f"jit(step)/loss/transpose(jvp({outer})){tail}" in text


@pytest.mark.parametrize("scope", ["embed", "block/attn", "block/mlp",
                                   "final_norm", "lm_head"])
def test_decode_step_program_names_its_layers(traced_serve, scope):
    runner = traced_serve["eng"].runner
    B, P = 4, traced_serve["eng"].max_pages_per_seq
    text = jax.jit(runner._decode_step).lower(
        runner.params, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B, P), jnp.int32), jnp.zeros((B,), jnp.int32),
        traced_serve["eng"].pool.pools).as_text(debug_info=True)
    assert f"jit(_decode_step)/{scope}/" in text


def test_pallas_kernels_are_named():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention

    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: flash_attention(*a, causal=True, interpret=True).sum(),
        argnums=(0, 1, 2)))(q, q, q))
    kernels = [n for n in re.findall(r"name=(\w+)", text) if "flash" in n]
    assert kernels == ["flash_fwd", "flash_bwd_delta", "flash_bwd"]
    pool = jnp.zeros((4, 8, 2, 64), jnp.float32)
    text = str(jax.make_jaxpr(lambda q, k, v, t, s, n: ragged_paged_attention(
        q, k, v, t, s, n, interpret=True))(
            jnp.zeros((1, 1, 2, 64), jnp.float32), pool, pool,
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.int32)))
    assert "ragged_paged_attn" in text


# ---------------------------------------------------------------- set-up


def test_import_span_covers_the_package_import():
    # recorded when conftest imported the package; filler tests may have
    # pushed it out of the ring since, so look at what the import left
    assert prof.LOADED_NS < prof.stamp()
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import paddle_tpu; from paddle_tpu import profiler as p; "
         "s = p.spans(); print(len(s), s[0][0], s[0][2] - s[0][1] > 0, "
         "s[0][1] == p.LOADED_NS)"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.split() == ["1", "paddle_tpu.import", "True", "True"], \
        out.stderr[-2000:]


def test_set_state_dict_and_build_spans():
    m = mark()
    model = toy_gpt()
    model.set_state_dict(model.state_dict())
    names = [s[NAME] for s in since(m)]
    assert names == ["model.build", "model.set_state_dict"]
    assert since(m)[0][ATTRS] == {"model": "GPT"}


# --------------------------------------------------------------- metrics


@pytest.mark.parametrize("key", REMOVED_KEYS)
def test_engine_metrics_keep_no_host_clock_seconds(key):
    m = EngineMetrics()
    snap = m.snapshot()
    assert key not in snap
    assert key not in aggregate_snapshots([snap, snap])
    assert not hasattr(m, key) and not hasattr(m, "mark_active")
    # the counts stay
    assert {"host_syncs", "tokens_generated", "planned_ahead_steps",
            "batch_occupancy_mean", "ttft_s_p50"} <= set(snap)
