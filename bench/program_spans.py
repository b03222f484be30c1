"""The program's own spans, for the per-layer readers that are built on them.

paddle_tpu.profiler keeps every span the program records in a ring of tuples

    (name, t0_ns, t1_ns, span_id, parent_id, step_id, request_id, attrs)

stamped with `time.perf_counter_ns()`: the clock serve.py and train.py stamp
`steps`, `trace_span`, `due` and `added` with, in seconds. Per-step spans
(`engine.*`, `runner.launch`, `train.step`, ...) exist only for the steps
that ran while the profiler was on; set-up's (`paddle_tpu.import`,
`model.build`, `engine.build`, `train.init`, `*.compile`) always.

The device trace is on another clock (ProfileData's `start_ns` is neither
`perf_counter_ns` nor `time_ns`). Both records hold the same calls, though:
the k-th `bench.engine_step` / `bench.train_step` of `ctx["trace"].host` and
the k-th entry of `ctx["steps"]` inside the traced span. `Spans.align()`
gives the median difference of their starts, and how far the widest pair
lies from it.

A program without spans (an older commit) gives an empty ring: every reader
then returns None, and the result line leaves its metric out.
"""

from __future__ import annotations

import bisect
import statistics

import trace_reduce

NAME, T0, T1, SID, PARENT, STEP, REQUEST, ATTRS = range(8)
ANCHORS = ("bench.engine_step", "bench.train_step")
BUILD = ("model.build", "model.set_state_dict", "engine.build", "train.init")
COMPILE = ("runner.compile", "train.compile")


def ring() -> list:
    """Every span the program holds, or [] where it records none."""
    try:
        from paddle_tpu import profiler
    except ImportError:
        return []
    spans = getattr(profiler, "spans", None)
    return list(spans()) if spans else []


class Spans:
    """The ring as one traced run's readers need it (kept in ctx, so the
    readers of a run share one)."""

    def __init__(self, ctx, recorded=None):
        self.ctx = ctx
        self.all = ring() if recorded is None else list(recorded)
        self.by_id = {s[SID]: s for s in self.all}
        span = ctx.get("trace_span")
        lo, hi = (span[0] * 1e9, span[1] * 1e9) if span else (0, 0)
        self.traced = [s for s in self.all if s[T0] >= lo and s[T1] <= hi]
        self._root = {}
        self._steps = {}
        self._align = None

    # ------------------------------------------------------------ steps

    def root(self, s):
        """The outermost span `s` lies in (itself, if it has no parent in
        the ring)."""
        sid = s[SID]
        if sid not in self._root:
            top = s
            while top[PARENT] in self.by_id:
                top = self.by_id[top[PARENT]]
            self._root[sid] = top
        return self._root[sid]

    def steps(self, root_name: str) -> list:
        """The root spans of that name inside the traced part, in order."""
        if root_name not in self._steps:
            self._steps[root_name] = sorted(
                (s for s in self.traced
                 if s[NAME] == root_name and s[PARENT] is None),
                key=lambda s: s[T0])
        return self._steps[root_name]

    def per_step_ms(self, root_name: str, names, own: bool = False) -> list:
        """For each traced step, the summed duration in ms of the spans
        named in `names` that lie in it at any depth; `own`: less what
        their children cover (self time)."""
        steps = self.steps(root_name)
        total = {s[SID]: 0 for s in steps}
        for s in self.traced:
            top = self.root(s)[SID]
            if top not in total:
                continue
            if s[NAME] in names:
                total[top] += s[T1] - s[T0]
            if own and s[PARENT] in self.by_id and \
                    self.by_id[s[PARENT]][NAME] in names:
                total[top] -= s[T1] - s[T0]
        return [total[s[SID]] / 1e6 for s in steps]

    def self_ms(self, root_name: str) -> list:
        """Each traced step's own time: the root less its children."""
        steps = self.steps(root_name)
        own = {s[SID]: s[T1] - s[T0] for s in steps}
        for s in self.traced:
            if s[PARENT] in own:
                own[s[PARENT]] -= s[T1] - s[T0]
        return [own[s[SID]] / 1e6 for s in steps]

    # ----------------------------------------------------------- set-up

    def before_window_s(self, names) -> float | None:
        """Summed seconds of the spans named in `names` that ended before
        the window's first step, none counted inside another of them."""
        steps = self.ctx.get("steps")
        if not steps:
            return None
        first = steps[0][0] * 1e9
        picked = [s for s in self.all if s[NAME] in names and s[T1] <= first]
        ids = {s[SID] for s in picked}

        def nested(s):
            while s[PARENT] in self.by_id:
                s = self.by_id[s[PARENT]]
                if s[SID] in ids:
                    return True
            return False

        outer = [s for s in picked if not nested(s)]
        return sum(s[T1] - s[T0] for s in outer) / 1e9 if outer else None

    # -------------------------------------------------------- alignment

    def align(self):
        """(offset_ns, residual_ns, pairs): trace clock = bench clock +
        offset, from the anchors both records hold; None without them."""
        if self._align is None:
            self._align = self._anchor() or ()
        return self._align or None

    def _anchor(self):
        tr, span = self.ctx.get("trace"), self.ctx.get("trace_span")
        if tr is None or not span:
            return None
        host = sorted(e[1] for e in tr.host if e[0] in ANCHORS)
        mine = [s[0] * 1e9 for s in self.ctx.get("steps", ())
                if s[0] >= span[0] and s[1] <= span[1]]
        n = min(len(host), len(mine))
        if n == 0:
            return None
        # the profiler's stop is the sharp edge: where the counts differ,
        # the LAST n of each are the same calls
        diffs = [h - m for h, m in zip(host[-n:], mine[-n:])]
        offset = statistics.median(diffs)
        return offset, max(abs(d - offset) for d in diffs), n

    # ------------------------------------------------------ device idle

    def device_idle(self, root_name: str = "engine.step",
                    wait_name: str = "engine.drain"):
        """Per traced step, the device's idle nanoseconds inside the step
        as (in a `wait_name` span, outside one); and the idle time of the
        traced part that lies in no step at all. None without a device
        trace or anchors."""
        tr, al = self.ctx.get("trace"), self.align()
        if tr is None or not tr.ops or al is None:
            return None
        offset = al[0]
        ivs = trace_reduce.merged(tr.ops[min(tr.ops)])
        starts = [a for a, _ in ivs]
        cum = [0]
        for a, b in ivs:
            cum.append(cum[-1] + b - a)

        def busy(a, b):
            """Nanoseconds of [a, b) in which an operation ran."""
            if b <= a:
                return 0
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            j = bisect.bisect_left(starts, b)
            total = cum[j] - cum[i]
            if i < j:
                total -= min(max(a - ivs[i][0], 0), ivs[i][1] - ivs[i][0])
                total -= max(ivs[j - 1][1] - b, 0)
            return max(total, 0)

        steps = self.steps(root_name)
        waits = {s[SID]: [] for s in steps}
        for s in self.traced:
            if s[NAME] == wait_name and self.root(s)[SID] in waits:
                waits[self.root(s)[SID]].append(s)
        out, in_steps = [], 0
        for st in steps:
            a, b = st[T0] + offset, st[T1] + offset
            idle = (b - a) - busy(a, b)
            inside = sum((w[T1] - w[T0]) - busy(w[T0] + offset,
                                                w[T1] + offset)
                         for w in waits[st[SID]])
            out.append((inside, idle - inside))
            in_steps += idle
        total_idle = tr.window_s * 1e9 - trace_reduce.union_ns(
            tr.ops[min(tr.ops)])
        return out, total_idle - in_steps, total_idle


def of(ctx) -> Spans:
    if "_program_spans" not in ctx:
        ctx["_program_spans"] = Spans(ctx)
    return ctx["_program_spans"]


def median_ms(ctx, root_name: str, names, own: bool = False):
    """Median over the traced steps of `per_step_ms`; None without
    spans."""
    ms = of(ctx).per_step_ms(root_name, names, own)
    return ctx["median"](ms) if ms else None


PARTS = {"engine.step": ("engine.plan", "engine.build_batch",
                         "runner.launch", "engine.drain", "engine.commit"),
         "train.step": ("train.stage_inputs", "train.dispatch")}


def report(ctx, root_name: str) -> None:
    """One line on stdout, before the result line, with what the consistency
    of a traced run is judged by (PERF.md quotes it): the root spans against
    the bench's own step records, the parts against the whole, the anchors'
    residual, and where the device's idle time lies."""
    sp, med = of(ctx), ctx["median"]
    steps = sp.steps(root_name)
    if not steps or ctx.get("_program_spans_said"):
        return
    ctx["_program_spans_said"] = True
    mean = lambda xs: sum(xs) / len(xs)
    # a part at any depth of the step; engine.commit less the drain in it
    parts = {n: sp.per_step_ms(root_name, (n,), own=n == "engine.commit")
             for n in PARTS[root_name]}
    parts["self"] = sp.self_ms(root_name)
    whole = [(s[T1] - s[T0]) / 1e6 for s in steps]
    line = {"steps": len(steps), "root_p50_ms": med(whole),
            "root_mean_ms": mean(whole),
            "parts_p50_ms": {n: med(v) for n, v in parts.items()},
            "parts_mean_sum_ms": sum(mean(v) for v in parts.values())}
    al, span = sp.align(), ctx.get("trace_span")
    if al:
        # the same calls as the bench's own records time them
        mine = [1e3 * (s[1] - s[0]) for s in ctx["steps"]
                if s[0] >= span[0] and s[1] <= span[1]]
        line.update(bench_p50_ms=med(mine), anchor_offset_ns=al[0],
                    anchor_residual_us=al[1] / 1e3, anchors=al[2])
    idle = sp.device_idle() if root_name == "engine.step" else None
    if idle:
        per_step, no_span, total = idle
        line.update(idle_in_drain_s=sum(i for i, _ in per_step) / 1e9,
                    idle_outside_drain_s=sum(o for _, o in per_step) / 1e9,
                    idle_under_no_span_s=no_span / 1e9,
                    idle_total_s=total / 1e9)
    print(f"[program_spans] {root_name}: {line}", flush=True)
