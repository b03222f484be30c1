"""Serving cells: drive paddle_tpu's ServingEngine from the client's side.

The engine is built through the program's public bridge
(`create_serving_engine(model, dtype=bfloat16, max_batch_size=, num_blocks=)`,
every other option at its default) on weights drawn by the configuration's reference.
One thread: it adds the requests that are due, calls `engine.step()`, and
stamps every token event with its own clock when the step returns. No clock
or counter inside paddle_tpu feeds an end-to-end metric.

`correct` has two parts, both against the configuration's plain reference
once the engine is gone. Tokens: the served tokens of a sample of the
requests the window finished (catches a scheduler or a cache that serves the
wrong thing). Logits: the window's loop is driven `check_steps` steps past
its close, every slot still live, and what the runner's decode entry
returned to the engine in those steps is kept; the program's error against
the float32 reference is measured in units of the error that the STATED
precision itself makes (the reference with bfloat16 activations): a lower
precision in weights or pages adds to it (catches that).
"""

from __future__ import annotations

import gc
import statistics
import time

import numpy as np

import traffic_gen
from run import load_json, say

# controls: the program's own lower-precision paths switched on (never a
# cell); each has to come out not correct
PROBES = {
    "int8-weights": {"weight_dtype": "int8"},
    "fp8-kv": {"kv_dtype": "fp8"},
}
CHECK_PAD = 512            # reference sequences are padded to a multiple


class Live:
    """One request as the client sees it."""

    __slots__ = ("req", "client", "due", "added", "sched", "times", "tokens",
                 "done")

    def __init__(self, req, client, due):
        self.req, self.client, self.due = req, client, due
        self.added = self.sched = None
        self.times, self.tokens, self.done = [], [], False


def build_engine(run):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import create_serving_engine

    cfg, reference = run.model_cfg(), run.reference
    # the model holds its weights in the type they are served in, so that
    # no float32 copy is alive when the engine makes its KV pool
    model = run.program("model")(run.program("config")(**cfg)).bfloat16()
    # one jitted call from the seed, under the program's parameter names.
    # Tensors, not raw arrays: set_state_dict takes anything else through
    # numpy, and parameters left on the host are uploaded at every step
    named = jax.jit(lambda key: {
        k: v.astype(jnp.bfloat16) for k, v in reference.program_names(
            reference.init_weights(cfg, key)).items()})(
                reference.seed_key(run.seed))
    missing, unexpected = model.set_state_dict(
        {k: paddle.Tensor(v) for k, v in named.items()})
    del named
    if missing or unexpected:
        raise SystemExit(f"weights do not fit the model: missing {missing}, "
                         f"unexpected {unexpected}")
    model.eval()
    if run.probe and run.probe not in PROBES:
        raise SystemExit(f"unknown probe {run.probe!r} for a serving cell")
    kw = PROBES[run.probe] if run.probe else {}
    eng = create_serving_engine(
        model, dtype=jnp.bfloat16,
        max_batch_size=run.traffic["max_batch_size"],
        num_blocks=traffic_gen.pool_blocks(run.traffic), **kw)
    return eng


class Driver:
    """The client side of one engine: adds requests, steps, stamps."""

    def __init__(self, eng):
        from paddle_tpu.serving import SamplingParams

        self.eng, self.SamplingParams = eng, SamplingParams
        self.live = {}             # request id -> Live, while it runs
        self.all = []              # every Live ever added
        self.steps = []            # (t0, t1, events, context_tokens, decoding)
        self.finished = []         # Lives, in finishing order

    def add(self, req, client=None, due=None):
        lv = Live(req, client, due)
        lv.added = time.perf_counter()
        rid = self.eng.add_request(
            req.prompt, self.SamplingParams(max_tokens=req.max_tokens))
        self.live[rid] = lv
        self.all.append(lv)
        return lv

    def step(self):
        """One engine step; returns the Lives that finished in it."""
        import jax.profiler

        decoding = [lv for lv in self.live.values() if lv.tokens]
        context = sum(len(lv.req.prompt) + len(lv.tokens) for lv in decoding)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            events = self.eng.step()
        t1 = time.perf_counter()
        done = []
        for ev in events:
            lv = self.live[ev.request_id]
            if lv.sched is None:
                lv.sched = t0
            lv.times.append(t1)
            lv.tokens.append(ev.token)
            if ev.finished:
                lv.done = ev.finish_reason
                del self.live[ev.request_id]
                done.append(lv)
        self.steps.append((t0, t1, len(events), context, len(decoding)))
        self.finished.extend(done)
        return done


def _warm_open(run, drv):
    """One request in every power-of-two class of prompt length that the
    traffic holds (the program pads prompts to such buckets), two tokens
    each, so that every prefill program and the decode program exist."""
    n = max(1, round(run.traffic["rate_rps"] * run.seconds))
    lens = traffic_gen.lengths(run.traffic["prompt_len"], n)
    classes = {}
    for ln in lens:
        classes[int(ln - 1).bit_length()] = int(ln)
    rng = np.random.default_rng(run.seed + 1)
    vocab = run.config["vocab_size"]
    for ln in sorted(classes.values()):
        drv.add(traffic_gen.Request(0.0, tuple(
            rng.integers(0, vocab, ln).tolist()), 2))
        while drv.eng.has_work():
            drv.step()
    say(f"warmed prompt lengths {sorted(classes.values())}")


def _window_closed(run, drv, clients):
    """Closed loop. Fill: every client's first request, stepped until all
    are past prefill; then the window. A client whose request finishes
    sends its next one before the next step."""
    nxt = [1] * len(clients)

    def resend(done):
        for lv in done:
            c = lv.client
            drv.add(clients[c][nxt[c] % len(clients[c])], client=c)
            nxt[c] += 1

    first = [drv.add(reqs[0], client=c) for c, reqs in enumerate(clients)]
    while not all(lv.tokens for lv in first):
        resend(drv.step())
    say(f"filled: {len(first)} clients decoding after {len(drv.steps)} steps")
    run.open_window()
    before = _counters(drv.eng)
    n0 = len(drv.steps)
    t_end = time.perf_counter() + run.seconds
    while time.perf_counter() < t_end:
        resend(drv.step())
        run.tick()
    run.close_window()
    return n0, _delta(before, _counters(drv.eng)), lambda: resend(drv.step())


def _window_open(run, drv, schedule):
    """Open loop on the wall clock: a request is added at the first step
    boundary at or after its due instant; latency counts from the due
    instant. With nothing to do the loop sleeps until the next arrival."""
    run.open_window()
    before = _counters(drv.eng)
    n0 = len(drv.steps)
    t_open = time.perf_counter()
    t_end = t_open + run.seconds
    i = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        while i < len(schedule) and t_open + schedule[i].due_s <= now:
            drv.add(schedule[i], due=t_open + schedule[i].due_s)
            i += 1
        if drv.eng.has_work():
            drv.step()
        else:
            nxt = t_open + schedule[i].due_s if i < len(schedule) else t_end
            time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
        run.tick()
    run.close_window()

    def more():
        """One more step of the loop; with nothing live, the schedule's
        next request first."""
        nonlocal i
        if not drv.eng.has_work():
            drv.add(schedule[i % len(schedule)], due=time.perf_counter())
            i += 1
        drv.step()

    return n0, _delta(before, _counters(drv.eng)), more


def _counters(eng) -> dict:
    snap = eng.metrics.snapshot()
    occ = eng.metrics.batch_occupancy
    snap["batch_occupancy_sum"], snap["batch_occupancy_count"] = (
        occ.sum, occ.count)
    return snap


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a
            if isinstance(a[k], (int, float)) and k in b}


def percentile(values, p: float) -> float:
    """The p-th percentile by the nearest-rank rule on the sorted sample."""
    vs = sorted(values)
    return vs[min(len(vs) - 1, max(0, int(np.ceil(p / 100 * len(vs))) - 1))]


def _tapped_steps(run, drv, more) -> dict:
    """The window's loop driven `check_steps` steps past its close, every
    slot as the window left it, keeping what the runner's decode entry
    (`decode(tokens[B], tables, pos[B], pools) -> (logits[B, V], pools)`,
    the compiled program the window drove) returned to the engine. A row is
    a request's when it sits at that request's position and was fed its last
    token. Returns, for a seeded sample of `check_slots` requests with the
    longest context always in it: Live -> [(k, row)], the float32 logits row
    that chose output token k."""
    import jax.numpy as jnp

    runner, calls, rows = drv.eng.runner, [], {}
    entry = runner.decode

    def tapped(tokens, tables, pos, pools):
        logits, pools = entry(tokens, tables, pos, pools)
        calls.append((np.array(tokens).ravel(), np.array(pos), logits))
        return logits, pools

    runner.decode = tapped
    try:
        for _ in range(run.traffic["check_steps"]):
            fed = {}
            for lv in drv.live.values():
                if lv.tokens:
                    key = (len(lv.req.prompt) + len(lv.tokens) - 1,
                           lv.tokens[-1])
                    # two requests alike in both: neither row is read
                    fed[key] = None if key in fed else (lv, len(lv.tokens))
            n = len(calls)
            more()
            for c in range(n, len(calls)):
                toks, pos, _ = calls[c]
                for r in range(len(pos)):
                    hit = fed.get((int(pos[r]), int(toks[r])))
                    if hit:
                        rows.setdefault(hit[0], []).append((hit[1], c, r))
    finally:
        del runner.decode           # the class's own entry again
    if not rows:
        return {}
    by_len = sorted(rows, key=lambda lv: -(len(lv.req.prompt)
                                           + len(lv.tokens)))
    k = min(run.traffic["check_slots"], len(by_len))
    rng = np.random.default_rng(run.seed + 2)
    picks = [by_len[0]] + [by_len[1 + j] for j in sorted(
        rng.choice(len(by_len) - 1, k - 1, replace=False))]
    return {lv: [(k, np.asarray(calls[c][2][r].astype(jnp.float32)))
                 for k, c, r in rows[lv]] for lv in picks}


def _check(run, lives, taps) -> bool:
    """Against the reference, after the engine is gone.

    Tokens: for a seeded sample of finished requests (the longest always in
    it), one float32 forward over prompt + served tokens; by how much the
    served token's reference logit lies below the reference's best: the
    widest such gap, and the share of served tokens that are not the
    reference's best at all (a count, so it is steady).

    Logits: for the rows of `taps`, the program's squared distance from the
    float32 reference over the squared distance of the reference with
    bfloat16 activations from it, less 1: the error the program adds to
    what the stated precision makes anyway, in units of that."""
    import jax
    import jax.numpy as jnp

    reference = run.reference
    limits = load_json(run.files, "limits", run.cell["name"] + ".json")
    pool = [lv for lv in lives if lv.done] or \
        [lv for lv in lives if len(lv.tokens) >= 2]
    pool.sort(key=lambda lv: -(len(lv.req.prompt) + len(lv.tokens)))
    k = min(run.traffic["check_requests"], len(pool))
    rng = np.random.default_rng(run.seed)
    picks = [pool[0]] + [pool[1 + j] for j in sorted(
        rng.choice(len(pool) - 1, k - 1, replace=False))]

    cfg = run.model_cfg()
    t0 = time.perf_counter()
    weights = jax.jit(lambda key: reference.round_weights(
        reference.init_weights(cfg, key), "bfloat16"))(
            reference.seed_key(run.seed))
    # one reference program per padded length: the count of positions is
    # the traffic's longest answer, whatever the sample holds
    longest = run.traffic.get("output_tokens") or run.traffic["output_len"]["hi"]
    n_out = -(-longest // 32) * 32

    @jax.jit
    def logits(w, toks, first):
        return tuple(reference.logits_at(cfg, w, toks, first, n_out, stored)
                     for stored in ("float32", "bfloat16"))

    def forward(lv):
        """(float32, bfloat16-stored) reference logits [n_out, vocab] at the
        positions that chose lv's output tokens."""
        p, total = lv.req.prompt, len(lv.req.prompt) + len(lv.tokens)
        need = max(total, len(p) - 1 + n_out)
        pad = min(-(-need // CHECK_PAD) * CHECK_PAD, cfg["max_seq_len"])
        toks = np.zeros(pad, np.int32)
        toks[:total] = list(p) + lv.tokens
        return logits(weights, jnp.asarray(toks), len(p) - 1)

    gaps = []
    for lv in picks:
        o = lv.tokens
        ref = forward(lv)[0][:len(o)]
        gap = np.asarray(jnp.max(ref, -1) - ref[jnp.arange(len(o)),
                                                np.asarray(o)])
        say(f"check: request prompt {len(lv.req.prompt)} + {len(o)} served "
            f"tokens: widest gap {gap.max():.5f} mean {gap.mean():.6f} "
            f"(reference argmax differs at {int((gap > 0).sum())})")
        gaps.append(gap)
    gaps = np.concatenate(gaps)
    ok = run.check("served_logit_gap_max", float(gaps.max()),
                   limits["served_logit_gap_max"])
    ok &= run.check("served_not_best_share", float((gaps > 0).mean()),
                    limits["served_not_best_share"])

    added = stated = 0.0
    for lv, taken in taps.items():
        ks = np.asarray([k for k, _ in taken])
        prog = np.stack([row for _, row in taken])
        ref32, ref16 = (np.asarray(r[ks]) for r in forward(lv))
        e2, f2 = np.sum((prog - ref32) ** 2), np.sum((ref16 - ref32) ** 2)
        scale = np.sum((ref32 - ref32.mean(-1, keepdims=True)) ** 2)
        say(f"check: request prompt {len(lv.req.prompt)}, logits that chose "
            f"tokens {ks.min()}..{ks.max()}: error {np.sqrt(e2 / scale):.5f} "
            f"of the logits' spread, bfloat16 reference's "
            f"{np.sqrt(f2 / scale):.5f}")
        added, stated = added + e2, stated + f2
    say(f"reference: {len(picks)} + {len(taps)} requests, {len(gaps)} tokens, "
        f"{sum(len(t) for t in taps.values())} logit rows in "
        f"{time.perf_counter() - t0:.1f} s")
    ok &= run.check("logit_excess_error",
                    added / stated - 1 if stated else float("inf"),
                    limits["logit_excess_error"])
    return ok


def drive(run) -> dict:
    drv = Driver(build_engine(run))
    vocab = run.config["vocab_size"]
    kind = run.traffic["kind"]
    if kind == "closed-serve":
        clients = traffic_gen.closed_clients(run.traffic, vocab, run.seed)
        n0, counters, more = _window_closed(run, drv, clients)
    else:
        schedule = traffic_gen.open_schedule(run.traffic, run.seconds, vocab,
                                             run.seed)
        _warm_open(run, drv)
        warm_lives = len(drv.all)
        n0, counters, more = _window_open(run, drv, schedule)
    steps = drv.steps[n0:]
    e2e, attempted = {}, 0
    if steps:
        span = steps[-1][1] - steps[0][0]
        tokens = sum(s[2] for s in steps)
        say(f"window: {len(steps)} engine steps, {tokens} tokens in "
            f"{span:.3f} s; compiles in window: {run.compiles}")
    if kind == "closed-serve":
        # every output token emitted between the first and the last step
        # boundary inside the window, over exactly that time
        e2e["serve_tokens_per_s"] = tokens / span
        lives = [lv for lv in drv.all if lv.times and lv.times[-1] >= steps[0][0]]
        attempted = len(lives)
    else:
        lives = drv.all[warm_lives:]
        attempted = len(lives)
        ttft = [lv.times[0] - lv.due for lv in lives if lv.times]
        itl = [b - a for lv in lives for a, b in zip(lv.times, lv.times[1:])]
        e2e["itl_p95_ms"] = 1e3 * percentile(itl, 95)
        say(f"offered {len(schedule)} requests, added {len(lives)}, first "
            f"token for {sum(1 for lv in lives if lv.times)}, finished "
            f"{sum(1 for lv in lives if lv.done)}, running at close "
            f"{len(drv.live)}; {len(itl)} gaps; ttft p50 "
            f"{1e3 * statistics.median(ttft):.1f} ms, itl p50 "
            f"{1e3 * statistics.median(itl):.1f} ms")
    # a request still running when the window closes is attempted, not failed
    failed = sum(1 for lv in lives if lv.done and lv.done != "length")
    served = lives
    if run.trace_requested:
        # bench-clock readers see the requests the profiler's start did not
        # touch: those due a second or more before it was asked for
        lives = [lv for lv in lives
                 if lv.due is None or lv.due < run.trace_requested - 1.0]
    ctx = {"steps": steps, "lives": lives, "counters": counters,
           "t_close": steps[-1][1] if steps else None,
           "percentile": percentile, "median": statistics.median}

    taps = _tapped_steps(run, drv, more)
    ctx["memory_peak_bytes"] = run.memory_peak_bytes()
    # the engine goes before the reference comes: the peak stays the program's
    drv.eng = None
    gc.collect()
    correct = _check(run, served, taps) and run.compiles == 0 and failed == 0
    return run.result(correct, attempted, failed, e2e, ctx)
