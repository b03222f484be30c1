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
lies from it. run.py moves the traced span onto the trace's clock with it
and cuts the table to that (`trace_reduce.clip`): what the readers here and
under layer_metrics/ get in `ctx["trace"]` is the clipped table.

A program without spans (an older commit) gives an empty ring: every reader
then returns None, and the result line leaves its metric out.
"""

from __future__ import annotations

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
        # one loop starts and stops the profiler between its steps, so both
        # records hold the same calls; should either hold more, the pairing
        # that agrees best is the one of the same calls (the last n, where
        # several agree alike: the profiler's stop is the sharper edge)
        best = None
        for i in range(len(host) - n + 1):
            for j in range(len(mine) - n + 1):
                diffs = [h - m for h, m in zip(host[i:i + n], mine[j:j + n])]
                offset = statistics.median(diffs)
                residual = max(abs(d - offset) for d in diffs)
                if best is None or residual <= best[1]:
                    best = (offset, residual, n)
        return best

    # ------------------------------------------------------ device lead

    def device_lead_ms(self, root_name: str = "engine.step",
                       launch_name: str = "runner.launch") -> list:
        """Per traced step, how long after the host entered its first
        `launch_name` span the step's first program began on the device, in
        ms. No program begins before the host enters the launch that
        dispatches it, so a reading under 0 says that this trace's device
        plane lies early on its host plane (the anchors tie only the HOST
        plane to the bench's clock; PERF.md, PR 33: about 1 ms in some
        sessions). Empty without a device trace or anchors."""
        tr, al = self.ctx.get("trace"), self.align()
        if tr is None or not tr.modules or al is None:
            return []
        runs = trace_reduce.whole_runs(tr, lambda name: True, min(tr.modules))
        launched = {}
        for s in self.traced:
            if s[NAME] == launch_name:
                top = self.root(s)[SID]
                launched[top] = min(launched.get(top, s[T0]), s[T0])
        steps = [st for st in self.steps(root_name) if st[SID] in launched]
        held = runs_held(runs, [(st[T0] + al[0], st[T1] + al[0])
                                for st in steps])
        return [(mine[0][0] - launched[st[SID]] - al[0]) / 1e6
                for st, mine in zip(steps, held) if mine]


def runs_held(runs, intervals) -> list:
    """For each of the ordered, disjoint `intervals` (ns on the trace's
    clock), the `runs` (trace_reduce.whole_runs, in order) whose middle lies
    inside it."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(runs) and runs[j][0] + runs[j][1] / 2 < a:
            j += 1
        k = j
        while k < len(runs) and runs[k][0] + runs[k][1] / 2 < b:
            k += 1
        out.append(runs[j:k])
        j = k
    return out


def of(ctx) -> Spans:
    if "_program_spans" not in ctx:
        ctx["_program_spans"] = Spans(ctx)
    return ctx["_program_spans"]


def steps_with_whole_runs(ctx, match) -> tuple:
    """(records, table): the bench's own step records (`ctx["steps"]`) that
    lie inside the traced span and hold a whole run of a program `match`
    accepts (first device; a run is a record's where its middle lies inside
    the record, on the trace's clock), and `ctx["trace"]` with only the
    operations of those runs. A reader sets the table's kernel seconds
    against the work of the records: both of the same whole steps, whatever
    the span cut at its edges. Empty without a device trace or anchors."""
    tr, al, span = ctx.get("trace"), of(ctx).align(), ctx.get("trace_span")
    if tr is None or not tr.modules or al is None:
        return [], trace_reduce.Trace()
    dev = min(tr.modules)
    inside = [rec for rec in ctx["steps"]
              if rec[0] >= span[0] and rec[1] <= span[1]]
    held = runs_held(trace_reduce.whole_runs(tr, match, dev),
                     [(rec[0] * 1e9 + al[0], rec[1] * 1e9 + al[0])
                      for rec in inside])
    records = [rec for rec, mine in zip(inside, held) if mine]
    ops = [e for mine in held for _, _, evs in mine for e in evs]
    return records, trace_reduce.Trace(ops={dev: ops}, span=tr.span)


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
    residual, and how long after the host's launch the device begins."""
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
    lead = sp.device_lead_ms() if root_name == "engine.step" else None
    if lead:
        line.update(device_lead_p50_ms=med(lead), device_lead_min_ms=min(lead))
    print(f"[program_spans] {root_name}: {line}", flush=True)
